"""Fused ragged paged attention — one Pallas TPU kernel over
variable-length page tables (ROADMAP top item, after "Ragged Paged
Attention", arxiv 2604.15464).

The gather formulation (ops/attention.paged_decode_attention) simulates
raggedness: it materializes a dense ``(B, P*page)`` KV view per layer
with ``P`` a static ladder rung, so every width rung is a separately
compiled executable (graftcheck's GT003 page-width hazard class exists
because of it) and HBM bandwidth is spent rebuilding views the kernel
could walk in place. This kernel walks them in place:

- Grid ``(slot,)``: **one program a slot, which walks that slot's live
  pages and nothing else.** The page table, the per-slot fill and the
  layer ride **scalar prefetch**; the pool leaves stay in HBM
  (``memory_space=ANY``) and the body copies pages itself
  (``make_async_copy`` into a VMEM ring with DMA semaphores), a block of
  pages at a time, several blocks in flight while one is computed. The
  loop bound is ``ceil(length / page)``: a dead table column costs
  nothing, an inactive slot only folds its new token, a sentinel id is
  never read. No materialized gather, no static width ladder, one
  executable for every fill level. A deeper BlockSpec pipeline would
  have been the short way to several pages in flight, but
  ``pl.Buffered(3)`` is refused by this jax's TPU lowering, and a grid
  step a table column costs a step for a dead column too; hence the
  walk and its own ring (PERF.md, PR 28: 0.59 -> 0.06 ms a call at 16
  slots x 64 columns on a v5e).
- The pool arrives STACKED, ``(L, num_pages, page, Hkv, D)``, exactly as
  the layer scan carries it, and the page copies index it with the
  layer. A pallas_call is opaque to XLA: a ``dynamic_index_in_dim``
  taken outside it cannot fuse into the kernel as it fuses into a
  gather, so XLA materializes one layer's plane of the WHOLE pool (live
  or free) per operand, per layer, per step — 8 ms of a 35 ms
  Mistral-7B decode step on a v5e (PERF.md, PR 26). Picking the layer
  inside the kernel is what makes "in place" true on the chip;
  tests/test_pallas_aot.py holds the compiled engine tick to it.
- ALL KV HEADS IN ONE PRODUCT. A page ``(page, Hkv, D)`` is read as
  ``page * Hkv`` rows of ``D``, and every query row of every head is
  multiplied with all of them: of the ``Hkv`` columns a token gets, a
  row keeps its own head's and masks the rest. Picking one head out of
  the sublane dimension costs a shuffle a token a head, and a DMA cannot
  pick it either (bf16 heads are packed in pairs in a 32-bit sublane);
  the MXU, at a few percent, has the room for the other heads' products,
  P.V needs no picking (a masked probability is exactly 0), and a block
  of pages is one ``(rows, D) x (D, block * page * Hkv)`` product. The
  MXU takes its operands in the cache dtype: their products are exact
  in float32, so only the order of the float32 sums differs from
  float32 dots.
- TWO-PHASE page walk for token identity. Phase 0 streams K and
  finishes the softmax statistics (max and normalizer); phase 1 forms
  the *final* per-position probabilities and accumulates P·V. A
  single-pass online-softmax kernel is cheaper but renormalizes
  probabilities with correction factors the gather oracle never
  applies — its probs are rounded to the cache dtype *after* global
  normalization, and at bf16 that rounding difference walks greedy
  decode off the oracle's token stream within a few ticks. Phase 1
  reproduces the oracle's rounding points exactly (scores rounded at
  the einsum boundary, probs rounded post-normalization, cache/new
  contributions added in cache dtype), so kernel vs gather is bit-equal
  up to f32 sum-order noise that the dtype rounding absorbs in the
  cases the CPU tests hold to the last bit (tens of tokens, 4 query
  heads; over 32 query heads or hundreds of tokens an output's last bit
  moves now and then, under this walk as under the grid kernel before
  it: tests/test_ragged_attention.py ``_assert_identity``). **K
  leaves HBM once**: phase 0 keeps its masked float32 scores in VMEM
  (2 MiB at 16 slots x 2048 positions, GQA 32:8) and phase 1 reads V
  alone. Where a table's scores pass ``_KEEP_SCORE_BYTES`` (the verify
  variant at that table, any decode table much longer) phase 1 streams
  K again and re-derives them: the same body, chosen by the shapes
  (:func:`walk_sizes`), not by a flag.
- The copies of one slot are ONE sequence, K blocks then V blocks, so
  V's first blocks are in flight while phase 0 ends, and a program
  starts the first copies of the next slot before it returns: the ring
  is never cold but at the call's first slot. That, and the ring's
  zero-fill at the first slot, are why the grid runs in order
  (``arbitrary``): a dead page inside a partly live block is not
  copied, and its stale rows meet probabilities that are exactly 0, so
  they must be finite, which a former block's rows are and fresh VMEM
  may not be. The tests poison every page outside a live prefix with
  NaN, table entries past the prefix inside a partly live block
  included.
- int8 pools dequantize **in-kernel** from the scale planes that live
  beside the pages: a page's scales ride the ring as one lane row, in
  the order of its score columns (the same math as the gather path's
  post-einsum score folding and pre-einsum value folding, without ever
  materializing a converted cache copy).
- The γ+1-token query variant (:func:`ragged_paged_verify_attention`)
  backs speculative verify: G queries at positions ``cache_len + g``
  attend the paged cache plus each other causally, so verify stops
  paying prefill-shaped attention.

Measured on the chip through the engine: bf16 pools at GQA 32:8, decode
(the benchmark's ``mistral7b.batch``). int8 pools, the verify variant
and MHA 32:32 have been timed alone and compared with the oracle there,
not served (PERF.md, PR 28).

Post-mortem context: a dense flash-decode kernel over the per-slot cache
(deleted in PR 29) lost 5x *inside* the per-layer scan, 640 vs 131 ms a
tick at 7B geometry (2026-07-30), because each pallas_call is an opaque
boundary to XLA's weight-prefetch pipeline. The economics here differ —
this kernel *replaces* a per-layer HBM gather materialization instead of
competing with a fused einsum — but the same rule applies: judge it on
the full decode tick (the benchmark's ``mistral7b.batch``:
``attn_kernel_ms_per_call`` beside ``decode_step_ms``), never the
standalone op. The gather formulation
stays the correctness oracle; choosing it over this kernel is the
caller's decision (ops/pallas/select), never taken in here.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
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


# the walk's sizes come from these three and the shapes the call sees
_BLOCK_SCORE_BYTES = 256 * 1024   # float32 scores of one block: 64 vregs
_RING_BYTES = 2 << 20             # page copies in flight, K or V
_KEEP_SCORE_BYTES = 8 << 20       # a slot's masked scores kept for phase 1
_NEVER = 1 << 30                  # ``lim`` of a column of another head


def walk_sizes(page: int, kv_heads: int, head_dim: int, rows_all: int,
               itemsize: int, table_width: int):
    """``(block_pages, ring_blocks, keep_scores)`` of the page walk.

    A block is ``block_pages`` pages scored in one product,
    ``(rows_all, D) x (D, block_pages * page * kv_heads)``: as many as
    keep the block's float32 scores within ``_BLOCK_SCORE_BYTES``.
    The ring holds ``ring_blocks`` blocks of page copies, about
    ``_RING_BYTES`` of them and at least two. ``keep_scores`` says
    whether a slot's masked scores over the whole table fit in
    ``_KEEP_SCORE_BYTES`` of VMEM, so that phase 1 reads V alone; where
    they do not, phase 1 streams K a second time."""
    cols = page * kv_heads
    block_pages = max(1, min(table_width,
                             _BLOCK_SCORE_BYTES // (rows_all * cols * 4)))
    block_bytes = block_pages * cols * head_dim * itemsize
    ring_blocks = max(2, min(8, _RING_BYTES // block_bytes))
    blocks = -(-table_width // block_pages)
    keep = rows_all * blocks * block_pages * cols * 4 <= _KEEP_SCORE_BYTES
    return block_pages, ring_blocks, keep


def _ragged_kernel(table_ref, len_ref, layer_ref, *rest,
                   block_pages: int, ring_blocks: int, keep_scores: bool,
                   int8: bool, sm_scale: float, bounded: bool):
    """One slot's program: walk its live pages, and nothing else.

    ``bounded`` (a sliding-window layer): a fourth scalar-prefetch
    operand gives each slot's first attended position. The walk starts
    at the block that holds it, pages wholly before it are not copied
    (their table entries may be the sentinel: the pages went back to the
    pool), and positions before it inside its page are masked, by the
    same comparison with ``lim_ref`` that masks the positions past the
    length. Without it the program is the one it always was.

    ``k_hbm`` / ``v_hbm`` are the stacked pool leaves, left in HBM; the
    body copies the live pages of this layer into a VMEM ring itself,
    ``ring_blocks`` blocks of ``block_pages`` pages, and keeps the ring
    full while it computes. The copies of one slot are a single
    sequence of *items*: the K blocks in table order (phase 0), then
    the V blocks (phase 1), so V's first blocks are in flight while
    phase 0 ends, and the first items of the NEXT slot are started
    before this program returns (the grid runs in order on one core).
    Where the scores are not kept, phase 1's items alternate K and V.

    Every kv-head is scored at once: a page ``(page, Hkv, D)`` is read
    as ``(page * Hkv, D)`` rows, token-major, so ``q (R, D)`` (all
    query rows of all heads) times its transpose gives ``(R, page *
    Hkv)`` scores of which a row's own head holds one column in
    ``Hkv``; the others are masked, like the positions past the slot's
    length, by one comparison with ``lim_ref`` (column's token offset in
    the block, or ``_NEVER`` for another head's column). Picking a head
    out of the sublane dimension costs a shuffle a token a head; the
    MXU has the room for the other heads' products and P.V needs no
    picking either, since a masked probability is exactly 0.

    Rounding points are the oracle's (module docstring): the MXU takes
    its operands in the cache dtype, whose products are exact in
    float32, so only the order of the float32 sums differs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if bounded:
        start_ref, *rest = rest
    q_ref, k_hbm, v_hbm, kn_ref, vn_ref, lim_ref, new_ok_ref, *rest = rest
    if int8:
        ks_hbm, vs_hbm, *rest = rest
    o_ref, ring, sems, *rest = rest
    if keep_scores:
        score_ref, *rest = rest
    if int8:
        scale_ring, = rest

    b = pl.program_id(0)
    num_slots = pl.num_programs(0)
    table_width = table_ref.shape[1]
    num_pages, page, kv_heads, head_dim = k_hbm.shape[1:]
    rows_all = q_ref.shape[1]
    cols = page * kv_heads
    width = block_pages * cols                 # score columns of a block
    block_tokens = block_pages * page
    layer = layer_ref[0]
    # valid tokens, excl. new; the table holds no more than its columns
    length = jnp.minimum(len_ref[b], table_width * page)
    cdt = o_ref.dtype                          # the oracle's cache dtype

    def _round(x):
        return _round_to(x, cdt)

    def walk(slot_b):
        """(pages up to the length, first live page, first live block,
        live blocks, items) of a slot's walk."""
        pages = jnp.minimum(lax.div(len_ref[slot_b] + page - 1, page),
                            table_width)
        blocks = lax.div(pages + block_pages - 1, block_pages)
        if not bounded:
            return pages, 0, 0, blocks, blocks * (2 if keep_scores else 3)
        first_page = lax.div(
            jnp.minimum(start_ref[slot_b], len_ref[slot_b]), page)
        first_block = lax.div(first_page, block_pages)
        live = blocks - first_block
        return (pages, first_page, first_block, live,
                live * (2 if keep_scores else 3))

    def for_copies(slot_b, item, walked, do):
        """``do`` each page copy of ``item`` of slot ``slot_b``: live
        pages only, so a sentinel id is never read, let alone fetched."""
        pages, first_page, first_block, blocks, _ = walked
        in_phase0 = item < blocks
        if keep_scores:
            is_k, j = in_phase0, jnp.where(in_phase0, item, item - blocks)
        else:
            turn = item - blocks
            is_k = jnp.logical_or(in_phase0, lax.rem(turn, 2) == 0)
            j = jnp.where(in_phase0, item, lax.div(turn, 2))
        at = lax.rem(item, ring_blocks)
        first = (first_block + j if bounded else j) * block_pages
        sources = [(k_hbm, ks_hbm if int8 else None),
                   (v_hbm, vs_hbm if int8 else None)]
        for wants_k, (pages_hbm, scales_hbm) in zip((True, False), sources):
            @pl.when(is_k == wants_k)
            def _(pages_hbm=pages_hbm, scales_hbm=scales_hbm):
                def one(c, carry):
                    pid = jnp.minimum(table_ref[slot_b, first + c],
                                      num_pages - 1)
                    do(pltpu.make_async_copy(
                        pages_hbm.at[layer, pid], ring.at[at, c],
                        sems.at[at]))
                    if int8:
                        do(pltpu.make_async_copy(
                            scales_hbm.at[pid], scale_ring.at[at, c],
                            sems.at[at]))
                    return carry

                lax.fori_loop(
                    jnp.maximum(first_page - first, 0) if bounded else 0,
                    jnp.minimum(pages - first, block_pages), one, 0)

    def start_first_items(slot_b):
        walked = walk(slot_b)

        def one(item, carry):
            for_copies(slot_b, item, walked, lambda copy: copy.start())
            return carry

        lax.fori_loop(0, jnp.minimum(walked[-1], ring_blocks - 1), one, 0)

    @pl.when(b == 0)
    def _first_program():
        # a dead page inside a partly live block is not fetched, and its
        # stale rows meet probabilities that are exactly 0: they must be
        # finite, which rows of an earlier block are and fresh VMEM may
        # not be
        ring[...] = jnp.zeros_like(ring)
        if int8:
            scale_ring[...] = jnp.zeros_like(scale_ring)
        start_first_items(b)

    walked = walk(b)
    _, _, first_block, blocks, items = walked

    def arrive(item):
        """Keep the ring full, then wait for ``item``; its ring place."""
        ahead = item + ring_blocks - 1

        @pl.when(ahead < items)
        def _():
            for_copies(b, ahead, walked, lambda copy: copy.start())

        for_copies(b, item, walked, lambda copy: copy.wait())
        return lax.rem(item, ring_blocks)

    def table_block(j):
        """The table's block of the walk's ``j``-th live block."""
        return first_block + j if bounded else j

    def flat(at, dtype):
        # (block_pages, page, Hkv, D) -> (width, D): free in float32,
        # where a token's (Hkv, D) is whole tiles
        return ring[at].astype(jnp.float32) \
            .reshape(width, head_dim).astype(dtype)

    def scale_row(at):
        return jnp.concatenate(
            [scale_ring[at, c] for c in range(block_pages)], axis=-1)

    q_all = q_ref[0]                                        # (R, D)

    def scores_of(keys):
        # rounding order matches the oracle exactly: dot -> cache-dtype
        # round -> * sm_scale -> (* k_scale on int8) -> mask. q stays
        # UNSCALED: the oracle applies sm_scale after the (rounded)
        # score einsum.
        return _round(lax.dot_general(
            q_all, keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)) * sm_scale

    def block_scores(at, j):
        s = scores_of(flat(at, q_all.dtype))
        if int8:
            # fused dequant, oracle formulation: the int8 scores are
            # exact through the rounded dot, and the per-vector scale
            # folds into f32 AFTER — never a converted cache copy
            s = s * scale_row(at)
        ok = lim_ref[...] < length - j * block_tokens
        if bounded:
            ok = jnp.logical_and(
                ok, lim_ref[...] >= start_ref[b] - j * block_tokens)
        return jnp.where(ok, s, _NEG_INF)

    def fold_stats(stats, scores):
        m_prev, l_prev = stats
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        return m_new, (l_prev * jnp.exp(m_prev - m_new)
                       + jnp.exp(scores - m_new).sum(axis=-1, keepdims=True))

    def score_cols(j):
        return pl.ds(pl.multiple_of(j * width, width), width)

    # -- phase 0: softmax statistics over the live pages ------------------
    def stats_step(j, stats):
        s = block_scores(arrive(j), table_block(j))
        if keep_scores:
            score_ref[:, score_cols(table_block(j))] = s
        return fold_stats(stats, s)

    stats = lax.fori_loop(
        0, blocks, stats_step,
        (jnp.full((rows_all, 1), _NEG_INF, jnp.float32),
         jnp.zeros((rows_all, 1), jnp.float32)))
    # the G new tokens (positions length..length+G-1, causal among
    # themselves); their K arrives unquantized even on int8 pools (oracle
    # contract). Folding them makes m/l FINAL (the causal diagonal
    # guarantees l >= 1, so phase 1 never divides by zero)
    s_new = jnp.where(new_ok_ref[...] > 0, scores_of(kn_ref[0]), _NEG_INF)
    m_fin, l_fin = fold_stats(stats, s_new)

    # -- phase 1: oracle-identical probabilities, P.V accumulation --------
    def value_step(j, acc):
        if keep_scores:
            s = score_ref[:, score_cols(table_block(j))]
            at = arrive(blocks + j)
        else:
            s = block_scores(arrive(blocks + 2 * j), table_block(j))
            at = arrive(blocks + 2 * j + 1)
        p = jnp.exp(s - m_fin) / l_fin
        if int8:
            # oracle int8 V path: normalized probs stay f32 and the
            # per-vector scale folds in pre-einsum (precision over
            # bandwidth — see decode_attention_cached)
            p, v = p * scale_row(at), flat(at, jnp.float32)
        else:
            p, v = _round(p).astype(cdt), flat(at, cdt)  # probs.astype
        return acc + jnp.dot(p, v, preferred_element_type=jnp.float32)

    acc = lax.fori_loop(0, blocks, value_step,
                        jnp.zeros((rows_all, head_dim), jnp.float32))

    @pl.when(b + 1 < num_slots)
    def _next_program():
        start_first_items(b + 1)

    p_new = _round(jnp.exp(s_new - m_fin) / l_fin).astype(cdt)
    pv = jnp.dot(p_new, vn_ref[0], preferred_element_type=jnp.float32)
    # the oracle snaps the cache and new-token einsum outputs, adds them
    # in f32 and snaps the sum (ops/attention._snap schedule)
    o_ref[0] = _round(_round(acc) + _round(pv)).astype(o_ref.dtype)


def _pallas_ragged(q, k_pages, v_pages, page_table, k_new, v_new,
                   cache_len, layer, *scale_pages, interpret: bool,
                   start=None):
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
    did; the slice comes out as one lane row a page, token-major like
    the scores it scales."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, g_len, q_heads, head_dim = q.shape
    _, num_pages, page, kv_heads, _ = k_pages.shape
    group = q_heads // kv_heads
    rows = g_len * group
    rows_all = kv_heads * rows
    cols = page * kv_heads
    int8 = bool(scale_pages)
    block_pages, ring_blocks, keep_scores = walk_sizes(
        page, kv_heads, head_dim, rows_all, k_pages.dtype.itemsize,
        page_table.shape[1])
    width = block_pages * cols
    table = page_table.astype(jnp.int32)
    lens = cache_len.astype(jnp.int32)
    # head-major rows (see _ragged_kernel): q-head kv*group + j of query
    # g becomes row kv*rows + g*group + j; new token u of kv-head kv is
    # column kv*G + u. q and the new K/V are a few KB per slot, so the
    # transposes cost nothing next to the pool walk; the pool itself is
    # read in place.
    q_hm = q.reshape(batch, g_len, kv_heads, group, head_dim) \
        .transpose(0, 2, 1, 3, 4).reshape(batch, rows_all, head_dim)
    kn_hm = k_new.transpose(0, 2, 1, 3).reshape(
        batch, kv_heads * g_len, head_dim)
    vn_hm = v_new.transpose(0, 2, 1, 3).reshape(
        batch, kv_heads * g_len, head_dim)
    # the masks' shape-only halves, built here so the body divides nothing
    row_head = np.arange(rows_all)[:, None] // rows
    col = np.arange(width)[None, :]
    lim = np.where(col % kv_heads == row_head, col // kv_heads, _NEVER)
    new_col = np.arange(kv_heads * g_len)[None, :]
    new_ok = np.logical_and(
        new_col // g_len == row_head,
        # row r of a head is query r // group: key u <= r // group
        (new_col % g_len) * group <= np.arange(rows_all)[:, None] % rows)

    def slot_block(b, *_):
        return (b, 0, 0)

    def whole(b, *_):
        return (0, 0)

    bounded = start is not None
    kernel = functools.partial(
        _ragged_kernel, block_pages=block_pages, ring_blocks=ring_blocks,
        keep_scores=keep_scores, int8=int8, sm_scale=head_dim ** -0.5,
        bounded=bounded)
    prefetch = (table, lens, layer) + (
        (start.astype(jnp.int32),) if bounded else ())
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [
        pl.BlockSpec((1, rows_all, head_dim), slot_block),
        in_hbm, in_hbm,
        pl.BlockSpec((1, kv_heads * g_len, head_dim), slot_block),
        pl.BlockSpec((1, kv_heads * g_len, head_dim), slot_block),
        pl.BlockSpec((rows_all, width), whole),
        pl.BlockSpec((rows_all, kv_heads * g_len), whole),
    ]
    operands = [q_hm, k_pages, v_pages, kn_hm, vn_hm,
                jnp.asarray(lim, jnp.int32), jnp.asarray(new_ok, jnp.int32)]
    scratch = [
        pltpu.VMEM((ring_blocks, block_pages, page, kv_heads, head_dim),
                   k_pages.dtype),
        pltpu.SemaphoreType.DMA((ring_blocks,)),
    ]
    if keep_scores:
        blocks = -(-page_table.shape[1] // block_pages)
        scratch.append(pltpu.VMEM((rows_all, blocks * width), jnp.float32))
    if int8:
        in_specs += [in_hbm, in_hbm]
        operands += [
            lax.dynamic_index_in_dim(s, layer[0], 0, keepdims=False)
            .reshape(num_pages, 1, cols) for s in scale_pages]
        scratch.append(pltpu.VMEM((ring_blocks, block_pages, 1, cols),
                                  jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(batch,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows_all, head_dim), slot_block),
        scratch_shapes=scratch,
    )
    compiler_params = None
    if not interpret:
        # in order, on one core: the ring and its first copies pass from
        # a slot's program to the next
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_hm.shape, q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
    )(*prefetch, *operands)
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
                                  interpret: Optional[bool] = None,
                                  start=None) -> jnp.ndarray:
    """Kernel counterpart of ops.attention.paged_decode_attention, over
    the pool as the engine holds it. q (B,1,Hq,D); k_pages/v_pages the
    stacked pool leaves (L,num_pages,page,Hkv,D) and ``layer`` the int32
    scalar (traced: the layer scan's index) naming the plane to read —
    a caller holding a single plane passes ``plane[None]`` and 0;
    page_table (B,P) int32 with ``num_pages`` the unallocated sentinel;
    k_new/v_new (B,Hkv,D); cache_len (B,) valid tokens excluding the
    current one; int8 pools pass the (L,num_pages,page,Hkv) scale
    planes. ``start`` (B,) int32 or None: a sliding-window layer's first
    attended position (``max(cache_len - window + 1, 0)``); the walk
    begins at its page and masks what lies before it in that page.
    ``interpret=None`` follows the lowering target
    (ops/pallas/select). Returns (B,1,Hq,D)."""
    kernel = _pallas_ragged
    operands = (q, k_pages, v_pages, page_table, k_new[:, None],
                v_new[:, None], cache_len, _layer(layer),
                *_scales(k_scale_pages, v_scale_pages))
    if start is not None:
        # the bound rides as the last operand through platform_dependent
        def kernel(*ops, interpret):
            return _pallas_ragged(*ops[:-1], interpret=interpret,
                                  start=ops[-1])
        operands += (start,)
    return lower_for_target(kernel, interpret, *operands)


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
