"""Pallas TPU flash attention (prefill path).

The hot op of a prefill, written per /opt/skills/guides/pallas_guide.md
as the canonical flash kernel: grid (batch, q-heads, q-blocks, k-blocks)
with the k-axis innermost ("arbitrary" semantics), flash statistics (m,
l, acc) carried across k steps in fp32 VMEM scratch. Only one (block_q,
D) Q tile and one (block_k, D) K/V tile live in VMEM per step — tested
to S=32K on a single v5e core where the dense path's (S, S) scores
cannot exist. The two products run in the operands' own dtype on the
MXU (bfloat16 in, float32 accumulated; the softmax in float32, its
weights rounded to V's dtype for the second product, as the XLA
formulations do).

**The band.** Causal: query ``t`` attends keys ``s <= t``. With
``window``: ``t - window < s <= t`` (a sliding-window layer). K blocks
wholly outside a Q block's band are skipped with ``pl.when``, and their
K/V tiles are never copied: the index map holds the block index at the
band's edge, so the pipeline sees no new block. Blocks wholly inside the
band skip the mask.

Heads are columns: Q is read as (B, S, Hq·D) and K/V as (B, S, Hkv·D),
a head a 128-lane column block, so nothing is transposed on the way in
or out. GQA is expressed in the K/V BlockSpec index maps: query head
``h`` reads KV head ``h // group``.

``flash_attention`` is the kernel and nothing else: callers that want
an XLA formulation for shapes Mosaic cannot tile choose it themselves
from ``select.flash_tileable`` (models/llama and models/swa_moe do, and
say so). Same numerics either way (tests assert equality against
ops.attention and ops.banded_attention).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.ops.pallas.select import lower_for_target

_NEG_INF = -1e30


def _live_blocks(qi, block_q: int, block_k: int, num_k: int, causal: bool,
                 window: Optional[int]):
    """First and last K block a Q block's band touches."""
    first = 0
    if window is not None:
        first = jnp.maximum(qi * block_q - window + 1, 0) // block_k
    last = num_k - 1
    if causal:
        last = jnp.minimum(((qi + 1) * block_q - 1) // block_k, last)
    return first, last


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  block_q: int, block_k: int, num_k: int, causal: bool,
                  window: Optional[int], sm_scale: float):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def step(masked: bool):
        q, k_blk, v_blk = q_ref[0], k_ref[0], v_ref[0]
        scores = lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (bq, bk)
        if masked:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            k_pos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            ok = k_pos <= q_pos
            if window is not None:
                ok = ok & (k_pos > q_pos - window)
            # a row with no key in this block sums garbage against m =
            # _NEG_INF; the first real key rescales it by exp(-1e30) = 0,
            # and the diagonal block gives every row one
            scores = jnp.where(ok, scores, _NEG_INF)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jnp.dot(
            p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)

    if not causal:
        step(False)
    else:
        first, last = _live_blocks(qi, block_q, block_k, num_k, causal,
                                   window)
        live = (ki >= first) & (ki <= last)
        # wholly inside the band: the block's last key is no later than
        # the Q block's first row, and (with a window) its first key is
        # inside the window of the Q block's last row
        inside = (ki + 1) * block_k - 1 <= qi * block_q
        if window is not None:
            inside = inside & (ki * block_k
                               > (qi + 1) * block_q - 1 - window)
        pl.when(live & inside)(lambda: step(False))
        pl.when(live & jnp.logical_not(inside))(lambda: step(True))

    @pl.when(ki == num_k - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
                    ).astype(o_ref.dtype)


def _pallas_flash(q, k, v, *, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_len, q_heads, head_dim = q.shape
    kv_heads = k.shape[2]
    group = q_heads // kv_heads
    num_k = seq_len // block_k
    # heads as column blocks: (B, S, H, D) -> (B, S, H·D) is no copy
    qf = q.reshape(batch, seq_len, q_heads * head_dim)
    kf = k.reshape(batch, seq_len, kv_heads * head_dim)
    vf = v.reshape(batch, seq_len, kv_heads * head_dim)

    def kv_index(b, h, qi, ki):
        # outside the band the index stays at the band's edge: a block
        # index that does not change is not copied again
        if causal:
            first, last = _live_blocks(qi, block_q, block_k, num_k, causal,
                                       window)
            ki = jnp.clip(ki, first, last)
        return (b, ki, h // group)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, num_k=num_k,
        causal=causal, window=window, sm_scale=head_dim ** -0.5)
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))
    out = pl.pallas_call(
        kernel,
        grid=(batch, q_heads, seq_len // block_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim),
                         lambda b, h, qi, ki: (b, qi, h)),
            pl.BlockSpec((1, block_k, head_dim), kv_index),
            pl.BlockSpec((1, block_k, head_dim), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, head_dim),
                               lambda b, h, qi, ki: (b, qi, h)),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(q.shape)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Flash attention. q (B,S,Hq,D), k/v (B,S,Hkv,D) → (B,S,Hq,D).
    ``window`` (causal only): query ``t`` attends ``t - window < s <=
    t``.

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
    if window is not None and not causal:
        raise ValueError("flash_attention: a window needs causal=True")
    return lower_for_target(
        functools.partial(_pallas_flash, causal=causal, window=window,
                          block_q=block_q, block_k=block_k),
        interpret, q, k, v)
