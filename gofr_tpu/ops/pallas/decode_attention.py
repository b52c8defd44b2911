"""Pallas TPU decode attention (experimental — default OFF).

Design: grid (batch, k-blocks); each program handles ALL heads of one
sequence for one K/V block, streaming the caches once in their natural
(B, T, Hkv, D) layout (no transposed HBM copy) with flash statistics
(m, l, acc) carried across k-blocks in VMEM scratch. ``cache_len`` rides
scalar prefetch: the K/V index maps clamp past the fill so the pipeline
elides re-fetching the dead tail of the static window (short sequences
read ~fill, not T), and compute for those blocks is skipped with
``pl.when``. The current token's K/V folds into the final block step, so
no pre-scatter of the cache is needed (same contract as
decode_attention_cached). GQA maps q-head h to kv-head h // group via an
in-VMEM einsum — no materialized repeat.

MEASURED (v5e, 7B int8 geometry, 2026-07-30): numerics match the dense
path on TPU, and as a standalone op it is competitive — but inside the
per-layer decode ``lax.scan`` the whole step is ~5x SLOWER (640 vs
131 ms/tick): every pallas_call is an opaque boundary to XLA, breaking
the weight-prefetch/fusion pipeline 32 times per decode step. The dense
einsum stays the production path (`use_flash_decode=False`); a win here
needs a kernel spanning the whole decode step (weights + attention in
one grid), for which this is the numerics-tested starting point.

The entry point is the kernel only; models/llama selects it from
``select.decode_shapes_tileable`` (head_dim % 128, T % block, heads % 8)
and otherwise runs the dense implementation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.ops.pallas.select import lower_for_target

_NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, kn_ref, vn_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, block_k: int, num_k: int,
                   kv_heads: int, group: int, sm_scale: float):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    ki = pl.program_id(1)
    length = len_ref[b]                       # this sequence's fill
    q_heads = kv_heads * group

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def q3():
        # (Hq, D) → (Hkv, G, D) so kv-head alignment is a reshape
        return (q_ref[0, 0].astype(jnp.float32) * sm_scale).reshape(
            kv_heads, group, -1)

    @pl.when(ki * block_k < length)
    def _step():
        # per-kv-head dots unrolled in Python: Mosaic does not lower a
        # batched dot_general with unequal non-contracting dims
        qh = q3()
        k_blk = k_ref[0].astype(jnp.float32)          # (bk, Hkv, D)
        v_blk = v_ref[0].astype(jnp.float32)
        scores = jnp.concatenate(
            [jnp.dot(qh[h], k_blk[:, h, :].T,
                     preferred_element_type=jnp.float32)   # (G, bk)
             for h in range(kv_heads)], axis=0)       # (Hq, bk)
        pos = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        scores = jnp.where(pos < length, scores, _NEG_INF)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        p3 = p.reshape(kv_heads, group, block_k)
        pv = jnp.concatenate(
            [jnp.dot(p3[h], v_blk[:, h, :],
                     preferred_element_type=jnp.float32)   # (G, D)
             for h in range(kv_heads)], axis=0)       # (Hq, D)
        acc_ref[:] = acc_ref[:] * corr + pv

    @pl.when(ki == num_k - 1)
    def _finish():
        # fold the current token's K/V (position == length, always valid)
        k_new = kn_ref[0, 0].astype(jnp.float32)      # (Hkv, D)
        v_new = vn_ref[0, 0].astype(jnp.float32)
        s_new = (q3() * k_new[:, None, :]).sum(-1)    # (Hkv, G)
        s_new = s_new.reshape(q_heads, 1)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_fin = jnp.maximum(m_prev, s_new)
        corr = jnp.exp(m_prev - m_fin)
        p_new = jnp.exp(s_new - m_fin)                # (Hq, 1)
        l_fin = l_prev * corr + p_new
        vn_rep = jnp.repeat(v_new, group, axis=0) if group > 1 else v_new
        acc = acc_ref[:] * corr + p_new * vn_rep
        o_ref[0, 0] = (acc / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)


def _pallas_decode(q, k_cache, v_cache, k_new, v_new, cache_len, *,
                   block_k: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, _, q_heads, head_dim = q.shape
    t_max = k_cache.shape[1]
    kv_heads = k_cache.shape[2]
    group = q_heads // kv_heads
    num_k = t_max // block_k
    # caches stay 4D (B, T, Hkv, D): heads are selected inside the block,
    # so NO transposed/reshaped HBM copy is ever materialized
    knf = k_new[:, None, :, :]                # (B, 1, Hkv, D)
    vnf = v_new[:, None, :, :]
    lens = cache_len.astype(jnp.int32)

    def kv_index(b, ki, lens_ref):
        # index maps get (grid indices..., scalar-prefetch refs...).
        # Clamp to the last block holding valid rows: the pipeline elides
        # re-fetching an unchanged block index, so the dead tail of the
        # static window is never streamed
        length = lens_ref[b]
        last = jnp.maximum(lax.div(length + block_k - 1, block_k) - 1, 0)
        return (b, jnp.minimum(ki, last), 0, 0)

    kernel = functools.partial(
        _decode_kernel, block_k=block_k, num_k=num_k, kv_heads=kv_heads,
        group=group, sm_scale=head_dim ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, num_k),
        in_specs=[
            pl.BlockSpec((1, 1, q_heads, head_dim),
                         lambda b, ki, lens_ref: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_k, kv_heads, head_dim), kv_index),
            pl.BlockSpec((1, block_k, kv_heads, head_dim), kv_index),
            pl.BlockSpec((1, 1, kv_heads, head_dim),
                         lambda b, ki, lens_ref: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, kv_heads, head_dim),
                         lambda b, ki, lens_ref: (b, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_heads, head_dim),
                               lambda b, ki, lens_ref: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((q_heads, head_dim), jnp.float32),
            pltpu.VMEM((q_heads, 1), jnp.float32),
            pltpu.VMEM((q_heads, 1), jnp.float32),
        ],
    )
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
    )(lens, q, k_cache, v_cache, knf, vnf)
    return out


def flash_decode_attention(q, k_cache, v_cache, k_new, v_new, cache_len,
                           block_k: int = 128,
                           interpret: Optional[bool] = None) -> jnp.ndarray:
    """Kernel counterpart of ops.attention.decode_attention_cached.
    q (B,1,Hq,D); caches (B,Tmax,Hkv,D); k_new/v_new (B,Hkv,D);
    cache_len (B,) valid entries excluding the current token. Tmax must
    split into whole ``block_k`` blocks; ``interpret=None`` follows the
    lowering target (ops/pallas/select). Returns (B,1,Hq,D)."""
    t_max = k_cache.shape[1]
    block_k = min(block_k, t_max)
    if t_max % block_k:
        raise ValueError(
            f"flash_decode_attention: Tmax={t_max} does not split into "
            f"{block_k}-row blocks")
    return lower_for_target(
        functools.partial(_pallas_decode, block_k=block_k), interpret,
        q, k_cache, v_cache, k_new, v_new, cache_len)
