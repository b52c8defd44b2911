"""Pallas TPU flash attention (prefill path).

The hot op of the Llama/BERT serve path, written per
/opt/skills/guides/pallas_guide.md as the canonical 3D-grid flash kernel:
grid (batch·q-heads, q-blocks, k-blocks) with the k-axis innermost
("arbitrary" semantics), flash statistics (m, l, acc) carried across k
steps in fp32 VMEM scratch. Only one (block_q, D) Q tile and one
(block_k, D) K/V tile live in VMEM per step — tested to S=32K on a single
v5e core where the dense path's (S, S) scores cannot exist. Causal Q/K
block pairs that are fully masked are skipped with ``pl.when`` (≈2× FLOPs
saved at long S).

GQA is expressed in the K/V BlockSpec index maps: the flattened (batch·Hq)
grid axis maps onto (batch·Hkv), so grouped heads read the same K/V tile
without materialising a repeat.

``flash_attention`` is the kernel and nothing else: callers that want
the dense einsum for shapes Mosaic cannot tile choose it themselves from
``select.flash_tileable`` (models/llama does, and says so). Same numerics
either way (tests assert equality against ops.attention).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.ops.pallas.select import lower_for_target

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  block_q: int, block_k: int, num_k: int, causal: bool,
                  sm_scale: float):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: skip K blocks strictly after the Q block
    should_run = True
    if causal:
        should_run = ki * block_k < (qi + 1) * block_q

    @pl.when(should_run)
    def _step():
        q = q_ref[0].astype(jnp.float32) * sm_scale       # (bq, D)
        k_blk = k_ref[0].astype(jnp.float32)              # (bk, D)
        v_blk = v_ref[0].astype(jnp.float32)
        scores = jnp.dot(q, k_blk.T,
                         preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            k_pos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            scores = jnp.where(q_pos >= k_pos, scores, _NEG_INF)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_blk = scores.max(axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32)

    @pl.when(ki == num_k - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
                    ).astype(o_ref.dtype)


def _pallas_flash(q, k, v, *, causal: bool, block_q: int, block_k: int,
                  interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_len, q_heads, head_dim = q.shape
    kv_heads = k.shape[2]
    group = q_heads // kv_heads
    num_k = seq_len // block_k
    # (B, S, H, D) → (B·H, S, D): head-major layout for per-head tiles
    qf = q.transpose(0, 2, 1, 3).reshape(batch * q_heads, seq_len, head_dim)
    kf = k.transpose(0, 2, 1, 3).reshape(batch * kv_heads, seq_len, head_dim)
    vf = v.transpose(0, 2, 1, 3).reshape(batch * kv_heads, seq_len, head_dim)

    def kv_index(bh, qi, ki):
        return (bh // group if group > 1 else bh, ki, 0)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, num_k=num_k,
        causal=causal, sm_scale=head_dim ** -0.5)
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    out = pl.pallas_call(
        kernel,
        grid=(batch * q_heads, seq_len // block_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim),
                         lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, head_dim), kv_index),
            pl.BlockSpec((1, block_k, head_dim), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, head_dim),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(batch, q_heads, seq_len, head_dim).transpose(
        0, 2, 1, 3)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, block_q: int = 512,
                    block_k: int = 512,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Flash attention. q (B,S,Hq,D), k/v (B,S,Hkv,D) → (B,S,Hq,D).

    S must split into whole blocks (blocks clamp to S, so any S up to the
    block size does). ``interpret=None`` follows the lowering target
    (ops/pallas/select); compiled for TPU the geometry must also satisfy
    ``select.flash_tileable`` or Mosaic rejects it.
    """
    seq_len = q.shape[1]
    block_q = min(block_q, seq_len)
    block_k = min(block_k, seq_len)
    if seq_len % block_q or seq_len % block_k:
        raise ValueError(
            f"flash_attention: S={seq_len} does not split into "
            f"{block_q}/{block_k}-row blocks")
    return lower_for_target(
        functools.partial(_pallas_flash, causal=causal, block_q=block_q,
                          block_k=block_k),
        interpret, q, k, v)
