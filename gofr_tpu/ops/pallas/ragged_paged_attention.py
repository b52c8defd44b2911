"""Fused ragged paged attention — one Pallas TPU kernel over
variable-length page tables (ROADMAP top item, after "Ragged Paged
Attention", arxiv 2604.15464).

The gather formulation (ops/attention.paged_decode_attention) simulates
raggedness: it materializes a dense ``(B, P*page)`` KV view per layer
with ``P`` a static ladder rung, so every width rung is a separately
compiled executable (graftcheck's GT003 page-width hazard class exists
because of it) and HBM bandwidth is spent rebuilding views the kernel
could walk in place. This kernel walks them in place:

- Grid ``(slot, kv-page-block)``; the page table and per-slot fill ride
  **scalar prefetch**, so each program's K/V BlockSpec index map reads
  its slot's *actual* pool row directly from the table — no materialized
  gather, no static width ladder, one executable for every fill level.
- The pool arrives STACKED, ``(L, num_pages, page, Hkv, D)``, exactly as
  the layer scan carries it, and the layer index is a third
  scalar-prefetch operand that the same index maps read. A pallas_call
  is opaque to XLA: a ``dynamic_index_in_dim`` taken outside it cannot
  fuse into the kernel as it fuses into a gather, so XLA materializes
  one layer's plane of the WHOLE pool (live or free) per operand, per
  layer, per step — 8 ms of a 35 ms Mistral-7B decode step on a v5e
  (PERF.md, PR 26). Picking the layer in the BlockSpec is what makes
  "in place" true on the chip; tests/test_pallas_aot.py holds the
  compiled engine tick to it.
- TWO-PHASE page walk for token identity: the page-block axis runs the
  table twice. Phase 0 streams K only and finishes the softmax
  statistics (max and normalizer in VMEM scratch); phase 1 re-derives
  each block's scores, materializes the *final* per-position
  probabilities, and accumulates P·V. A single-pass online-softmax
  kernel is cheaper but renormalizes probabilities with correction
  factors the gather oracle never applies — its probs are rounded to the
  cache dtype *after* global normalization, and at bf16 that rounding
  difference walks greedy decode off the oracle's token stream within a
  few ticks. Phase 1 reproduces the oracle's rounding points exactly
  (scores rounded at the einsum boundary, probs rounded post-
  normalization, cache/new contributions added in cache dtype), so
  kernel vs gather is bit-equal up to f32 sum-order noise that the
  dtype rounding absorbs. Cost: K streams twice, V once (V's index map
  parks on one row during phase 0 so no dead fetches) — still far below
  the gather path, which writes AND reads a materialized (B, P·page)
  copy of both K and V every layer.
- Pages past the slot's fill are clamped to the last valid row in the
  index map (the pipeline elides re-fetching an unchanged block) and
  their compute is skipped with ``pl.when`` — sentinel page ids are
  never dereferenced, which the tests assert by poisoning unreferenced
  pages with NaN.
- int8 pools dequantize **in-kernel** from the scale planes that live
  beside the pages (k/v scaled to f32 before the dots — the same math
  as the gather path's post-einsum score folding, without ever
  materializing a converted cache copy).
- The γ+1-token query variant (:func:`ragged_paged_verify_attention`)
  backs speculative verify: G queries at positions ``cache_len + g``
  attend the paged cache plus each other causally, so verify stops
  paying prefill-shaped attention.

Post-mortem context (ops/pallas/decode_attention): the dense flash
prototype lost 5x *inside* the per-layer scan because each pallas_call
is an opaque boundary to XLA's weight-prefetch pipeline. The economics
here differ — this kernel *replaces* a per-layer HBM gather
materialization instead of competing with a fused einsum — but the same
rule applies: judge it on the full decode tick (bench.py
``llama_ragged_attn``), never the standalone op. The gather formulation
stays the correctness oracle; choosing it over this kernel is the
caller's decision (ops/pallas/select), never taken in here.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.ops.pallas.select import lower_for_target

_NEG_INF = -1e30

__all__ = ["ragged_paged_decode_attention", "ragged_paged_verify_attention"]


def _round_to(x, dtype):
    """``ops/attention._snap`` for the kernel body: round f32 ``x`` to
    ``dtype``'s mantissa, nearest-even, without leaving f32.

    Pallas TPU has no lowering for ``lax.reduce_precision`` and an astype
    round trip is a convert pair XLA may fold in interpret mode, so the
    rounding is spelled out on the bit pattern — the one form that means
    the same thing to Mosaic, to the interpreter and to the oracle. Only
    the mantissa narrows, which is all reduce_precision does when the
    exponent keeps f32's 8 bits (bfloat16); f32 passes through."""
    info = jnp.finfo(dtype)
    if info.bits >= 32:
        return x
    if info.nexp != 8:
        raise ValueError(
            f"ragged paged attention reproduces bfloat16 and float32 "
            f"rounding only, got cache dtype {jnp.dtype(dtype).name}")
    drop = 23 - info.nmant
    bits = lax.bitcast_convert_type(x, jnp.int32)
    odd = lax.shift_right_logical(bits, drop) & 1
    bits = (bits + ((1 << (drop - 1)) - 1) + odd) & ~((1 << drop) - 1)
    return lax.bitcast_convert_type(bits, jnp.float32)


def _ragged_kernel(table_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, kn_ref,
                   vn_ref, *rest, page: int, num_pi: int, kv_heads: int,
                   group: int, g_len: int, int8: bool, sm_scale: float):
    """One (slot, walk-step) program on the doubled page-block axis.

    Steps ``[0, num_pi)`` are phase 0 (K only): accumulate the softmax
    max and normalizer over the slot's live pages, then fold the G new
    tokens' scores so the statistics are FINAL. Steps
    ``[num_pi, 2*num_pi)`` are phase 1: re-derive each block's scores,
    form the oracle's exact per-position probabilities (rounded to the
    cache dtype after normalization, just like the gather path's
    ``probs.astype(q.dtype)``), and accumulate P·V; the last step adds
    the new tokens' contribution and writes the output. ``rest`` is
    (ks, vs, out, acc, m, l) on int8 pools — the scale-plane blocks ride
    the same index maps as their pages — and (out, acc, m, l) on bf16
    pools, so bf16 never fetches a dead operand. ``layer_ref`` is read by
    the index maps alone: the layer dimension is squeezed out of every
    pool block, so the body sees one layer's ``(1, page, Hkv, D)`` page.

    Everything is per kv-head with the head on a LEADING axis (q, the new
    K/V, the output and the scratch all arrive head-major from the
    wrapper), and every mask is built at the shape it is applied at:
    Mosaic tiles the last two dims, so a head picked off a leading axis
    is a plain tile load, while reshaping heads out of the sublane dim or
    broadcasting/tiling an i1 vector is a layout change it refuses."""
    from jax.experimental import pallas as pl

    if int8:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest

    b = pl.program_id(0)
    pi = pl.program_id(1)
    pj = lax.rem(pi, num_pi)                   # page index within a phase
    length = len_ref[b]                        # valid tokens, excl. new
    cdt = o_ref.dtype                          # the oracle's cache dtype
    rows = g_len * group                       # query rows per kv-head

    def _round(x):
        return _round_to(x, cdt)

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def scale_row(s_ref, h):
        # (page, Hkv) scale block -> head h's scales as a (1, page) lane
        # row. The column sits on sublanes; an exact select-and-sum over
        # the diagonal moves it to lanes without a transpose.
        col = s_ref[0][:, h:h + 1]                          # (page, 1)
        eye = (lax.broadcasted_iota(jnp.int32, (page, page), 0)
               == lax.broadcasted_iota(jnp.int32, (page, page), 1))
        return jnp.where(eye, col, 0.0).sum(axis=0, keepdims=True)

    def block_scores(h):
        # rounding order matches the oracle exactly: dot -> cache-dtype
        # round -> * sm_scale -> (* k_scale on int8) -> length mask.
        # q stays UNSCALED: the oracle applies sm_scale after the
        # (rounded) score einsum.
        q_h = q_ref[0, h].astype(jnp.float32)               # (rows, D)
        k_h = k_ref[0][:, h, :].astype(jnp.float32)         # (page, D)
        s_h = _round(jnp.dot(q_h, k_h.T,
                             preferred_element_type=jnp.float32)) * sm_scale
        if int8:
            # fused dequant, oracle formulation: the int8 scores are
            # exact through the rounded dot, and the per-vector scale
            # folds into f32 AFTER — never a converted cache copy
            s_h = s_h * scale_row(ks_ref, h)
        pos = pj * page + lax.broadcasted_iota(jnp.int32, (rows, page), 1)
        return jnp.where(pos < length, s_h, _NEG_INF)

    def new_scores(h):
        # the G new tokens (positions length..length+G-1, causal among
        # themselves: key u attends to query s iff u <= s); their K
        # arrives unquantized even on int8 pools (oracle contract).
        # Row r of a head is query r // group, and u <= r // group is
        # u * group <= r.
        q_h = q_ref[0, h].astype(jnp.float32)
        k_new = kn_ref[0, h].astype(jnp.float32)            # (G, D)
        s_new = _round(jnp.dot(q_h, k_new.T,
                               preferred_element_type=jnp.float32)) * sm_scale
        r_pos = lax.broadcasted_iota(jnp.int32, (rows, g_len), 0)
        u_pos = lax.broadcasted_iota(jnp.int32, (rows, g_len), 1)
        return jnp.where(u_pos * group <= r_pos, s_new, _NEG_INF)

    def fold_stats(h, scores):
        m_prev, l_prev = m_ref[h], l_ref[h]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        m_ref[h] = m_new
        l_ref[h] = (l_prev * jnp.exp(m_prev - m_new)
                    + jnp.exp(scores - m_new).sum(axis=-1, keepdims=True))

    # -- phase 0: softmax statistics over the live pages ------------------
    @pl.when(jnp.logical_and(pi < num_pi, pj * page < length))
    def _stats_step():
        for h in range(kv_heads):
            fold_stats(h, block_scores(h))

    @pl.when(pi == num_pi - 1)
    def _stats_finish():
        # fold the new tokens' scores: m/l are FINAL after this step (the
        # causal diagonal guarantees l >= 1, so phase 1 never divides by
        # zero)
        for h in range(kv_heads):
            fold_stats(h, new_scores(h))

    # -- phase 1: oracle-identical probabilities, P·V accumulation --------
    @pl.when(jnp.logical_and(pi >= num_pi, pj * page < length))
    def _value_step():
        for h in range(kv_heads):
            p = jnp.exp(block_scores(h) - m_ref[h]) / l_ref[h]
            if int8:
                # oracle int8 V path: normalized probs stay f32 and the
                # per-vector scale folds in pre-einsum (precision over
                # bandwidth — see decode_attention_cached)
                p = p * scale_row(vs_ref, h)
            else:
                p = _round(p)                  # probs.astype(q.dtype)
            v_h = v_ref[0][:, h, :].astype(jnp.float32)     # (page, D)
            acc_ref[h] += jnp.dot(p, v_h,
                                  preferred_element_type=jnp.float32)

    @pl.when(pi == 2 * num_pi - 1)
    def _finish():
        for h in range(kv_heads):
            p_new = _round(jnp.exp(new_scores(h) - m_ref[h]) / l_ref[h])
            v_new = vn_ref[0, h].astype(jnp.float32)        # (G, D)
            pv = jnp.dot(p_new, v_new, preferred_element_type=jnp.float32)
            # the oracle snaps the cache and new-token einsum outputs,
            # adds them in f32 and snaps the sum (ops/attention._snap
            # schedule)
            o_ref[0, h] = _round(_round(acc_ref[h]) + _round(pv)) \
                .astype(o_ref.dtype)


def _pallas_ragged(q, k_pages, v_pages, page_table, k_new, v_new,
                   cache_len, layer, *scale_pages, interpret: bool):
    """``k_pages`` / ``v_pages`` are the STACKED pool leaves
    ``(L, num_pages, page, Hkv, D)`` and ``layer`` (int32, shape (1,),
    traced) says which layer to read; ``scale_pages`` is
    ``(k_scale_pages, v_scale_pages)``, stacked the same way, on int8
    pools and empty on bf16 pools.

    The scale leaves are the one thing sliced out here: their minor
    dimension is Hkv (8 of 128 lanes), so XLA carries them in a layout
    of its own, and handing a custom call the whole leaf makes it
    re-lay the WHOLE leaf out, padded sixteenfold, every layer (AOT at
    Mistral-7B sizes: two 452 MB copies a layer). One layer's scale
    plane is 1/128 of its K plane, so slicing it costs what it always
    did."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, g_len, q_heads, head_dim = q.shape
    _, num_pages, page, kv_heads, _ = k_pages.shape
    group = q_heads // kv_heads
    rows = g_len * group
    num_pi = page_table.shape[1]
    int8 = bool(scale_pages)
    table = page_table.astype(jnp.int32)
    lens = cache_len.astype(jnp.int32)
    # head-major operands (see _ragged_kernel): q-head kv*group + j of
    # query g becomes row g*group + j of kv-head kv. q and the new K/V
    # are a few KB per slot, so the transposes cost nothing next to the
    # pool walk; the pool itself is read in place, the layer picked by
    # the index maps (module docstring).
    q_hm = q.reshape(batch, g_len, kv_heads, group, head_dim) \
        .transpose(0, 2, 1, 3, 4).reshape(batch, kv_heads, rows, head_dim)
    kn_hm = k_new.transpose(0, 2, 1, 3)            # (B, Hkv, G, D)
    vn_hm = v_new.transpose(0, 2, 1, 3)

    def _row(b, pj, table_ref, len_ref):
        # scalar-prefetch table walk: fetch this slot's ACTUAL pool row.
        # Clamp pj to the last page holding valid tokens (the pipeline
        # elides re-fetching an unchanged row, so the dead tail of the
        # table is never streamed), then clamp a sentinel id in-bounds —
        # its compute is skipped by pl.when, never attended.
        length = len_ref[b]
        last = jnp.maximum(lax.div(length + page - 1, page) - 1, 0)
        pid = table_ref[b, jnp.minimum(pj, last)]
        return jnp.minimum(pid, num_pages - 1)

    def k_index(b, pi, table_ref, len_ref, layer_ref):
        # K streams in BOTH phases (scores are re-derived in phase 1)
        return (layer_ref[0],
                _row(b, lax.rem(pi, num_pi), table_ref, len_ref), 0, 0, 0)

    def v_index(b, pi, table_ref, len_ref, layer_ref):
        # V is only read in phase 1; during phase 0 the map parks on the
        # row phase 1 fetches first, so no dead V block is ever streamed
        pj = jnp.where(pi >= num_pi, lax.rem(pi, num_pi), 0)
        return (layer_ref[0], _row(b, pj, table_ref, len_ref), 0, 0, 0)

    def ks_index(*args):
        return k_index(*args)[1:4]

    def vs_index(*args):
        return v_index(*args)[1:4]

    def q_index(b, pi, table_ref, len_ref, layer_ref):
        return (b, 0, 0, 0)

    kernel = functools.partial(
        _ragged_kernel, page=page, num_pi=num_pi, kv_heads=kv_heads,
        group=group, g_len=g_len, int8=int8, sm_scale=head_dim ** -0.5)
    in_specs = [
        pl.BlockSpec((1, kv_heads, rows, head_dim), q_index),
        pl.BlockSpec((None, 1, page, kv_heads, head_dim), k_index),
        pl.BlockSpec((None, 1, page, kv_heads, head_dim), v_index),
        pl.BlockSpec((1, kv_heads, g_len, head_dim), q_index),
        pl.BlockSpec((1, kv_heads, g_len, head_dim), q_index),
    ]
    operands = [q_hm, k_pages, v_pages, kn_hm, vn_hm]
    if int8:
        in_specs += [pl.BlockSpec((1, page, kv_heads), ks_index),
                     pl.BlockSpec((1, page, kv_heads), vs_index)]
        operands += [lax.dynamic_index_in_dim(s, layer[0], 0, keepdims=False)
                     for s in scale_pages]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(batch, 2 * num_pi),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kv_heads, rows, head_dim), q_index),
        scratch_shapes=[
            pltpu.VMEM((kv_heads, rows, head_dim), jnp.float32),
            pltpu.VMEM((kv_heads, rows, 1), jnp.float32),
            pltpu.VMEM((kv_heads, rows, 1), jnp.float32),
        ],
    )
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_hm.shape, q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
    )(table, lens, layer, *operands)
    return out.reshape(batch, kv_heads, g_len, group, head_dim) \
        .transpose(0, 2, 1, 3, 4).reshape(q.shape)


def _scales(k_scale_pages, v_scale_pages):
    if (k_scale_pages is None) != (v_scale_pages is None):
        raise ValueError("int8 pools pass both scale planes, bf16 neither")
    return () if k_scale_pages is None else (k_scale_pages, v_scale_pages)


def _layer(layer):
    return jnp.asarray(layer, jnp.int32).reshape(1)


def ragged_paged_decode_attention(q, k_pages, v_pages, page_table, k_new,
                                  v_new, cache_len, layer,
                                  k_scale_pages=None, v_scale_pages=None,
                                  interpret: Optional[bool] = None
                                  ) -> jnp.ndarray:
    """Kernel counterpart of ops.attention.paged_decode_attention, over
    the pool as the engine holds it. q (B,1,Hq,D); k_pages/v_pages the
    stacked pool leaves (L,num_pages,page,Hkv,D) and ``layer`` the int32
    scalar (traced: the layer scan's index) naming the plane to read —
    a caller holding a single plane passes ``plane[None]`` and 0;
    page_table (B,P) int32 with ``num_pages`` the unallocated sentinel;
    k_new/v_new (B,Hkv,D); cache_len (B,) valid tokens excluding the
    current one; int8 pools pass the (L,num_pages,page,Hkv) scale
    planes. ``interpret=None`` follows the lowering target
    (ops/pallas/select). Returns (B,1,Hq,D)."""
    return lower_for_target(
        _pallas_ragged, interpret, q, k_pages, v_pages, page_table,
        k_new[:, None], v_new[:, None], cache_len, _layer(layer),
        *_scales(k_scale_pages, v_scale_pages))


def ragged_paged_verify_attention(q, k_pages, v_pages, page_table, k_new,
                                  v_new, cache_len, layer,
                                  k_scale_pages=None, v_scale_pages=None,
                                  interpret: Optional[bool] = None
                                  ) -> jnp.ndarray:
    """γ+1-token variant backing speculative verify: kernel counterpart
    of ops.attention.paged_verify_attention, pool and ``layer`` as on
    :func:`ragged_paged_decode_attention`. q (B,G,Hq,D); k_new/v_new
    (B,G,Hkv,D) — query g sits at position ``cache_len + g``, attends
    the paged cache (< cache_len) plus the new tokens causally
    (u <= g). Returns (B,G,Hq,D)."""
    return lower_for_target(
        _pallas_ragged, interpret, q, k_pages, v_pages, page_table,
        k_new, v_new, cache_len, _layer(layer),
        *_scales(k_scale_pages, v_scale_pages))
