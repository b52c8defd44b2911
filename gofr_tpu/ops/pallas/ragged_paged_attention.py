"""Fused ragged paged attention — one Pallas TPU kernel over
variable-length page tables (ROADMAP top item, after "Ragged Paged
Attention", arxiv 2604.15464).

The gather formulation (ops/attention.paged_decode_attention) simulates
raggedness: it materializes a dense ``(B, P*page)`` KV view per layer
over the whole static table of ``P`` columns, live or not (graftcheck's
GT003 page-width hazard is the class a live-count ``P`` would open), so
HBM bandwidth is spent rebuilding views the kernel could walk in place.
This kernel walks them in place:

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
- A QUERY ROW MEETS ITS OWN KV HEAD ONLY. A block of pages lies in the
  ring token-major, ``(tokens, Hkv, D)``, and a head's ``(tokens, D)``
  rows come out of it by a strided load of 32-bit words down the
  sublanes (every ``Hkv / 2``-th word of a bfloat16 pool is one pair of
  heads of every token) and a shift or a mask (a bfloat16 is the top
  half of a float32; an int8 a byte, shifted down with its sign): two
  vector ops a vreg, after jax's own ragged kernel
  (``strided_load_kv``). A head's ``rows = G x group`` query rows are
  multiplied with that head's rows alone, so a block's scores are
  ``(rows_all, tokens)`` and no column is another head's. Until PR 32
  a page was read as ``page * Hkv`` rows and every query row scored
  all of them, its own head's column kept and the others masked,
  because picking a head out of the sublanes was reckoned a shuffle a
  token a head: the MXU had the room, but the rounding, the masks, the
  exponential and the sums then ran over ``Hkv`` times the scores the
  attention has, and at 128 query rows (GQA 128:8) the kernel was bound
  by that vector work at 22 % of its HBM roofline (PERF.md, PR 32: 1.56
  -> 0.50 ms a call of 32 slots x ~2500 rows). Where a head's rows are
  no whole sublane tile (4 at GQA 32:8, 1 at MHA) the fewest heads
  whose rows are share a product (:func:`walk_sizes`,
  ``product_heads``): their query rows lie block-diagonally over the
  heads' lanes against the heads' rows side by side, the zeros do the
  picking inside the MXU, and the K tiles the MXU loads are the same
  count. The MXU takes its operands in the cache dtype: their products
  are exact in float32, so only the order of the float32 sums differs
  from float32 dots.
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
  leaves HBM once**: phase 0 keeps its masked float32 scores in VMEM,
  a row a token (0.25 MiB at 2048 positions and GQA 32:8, 4.7 MB at
  9216 positions and GQA 128:8, where a column a token a KV head was
  38 MB and K was streamed twice), and phase 1 reads V alone. Where a
  table's scores pass ``_KEEP_SCORE_BYTES`` (128 query rows over some
  16000 positions and more) phase 1 streams K again and re-derives
  them: the same body, chosen by the shapes (:func:`walk_sizes`), not
  by a flag.
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
  beside the pages: a page's scales ride the ring head-major in whole
  lane rows, ``page`` scales a head, in the order of the head's score
  columns (the same math as the gather path's post-einsum score folding
  and pre-einsum value folding, without ever materializing a converted
  cache copy).
- The γ+1-token query variant (:func:`ragged_paged_verify_attention`)
  backs speculative verify: G queries at positions ``cache_len + g``
  attend the paged cache plus each other causally, so verify stops
  paying prefill-shaped attention.

Measured on the chip through the engine: bf16 pools at GQA 32:8 (the
benchmark's ``mistral7b.batch``) and at GQA 128:8 with and without a
window's bound (``command-a-plus-ep8.mixed``), decode. int8 pools, the
verify variant and MHA 32:32 are compared with the oracle there by
``chip_smoke.py``, not served (PERF.md, PR 28 and PR 32).

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
from typing import NamedTuple, Optional

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


# the walk's sizes come from these and the shapes the call sees
_BLOCK_SCORE_BYTES = 256 * 1024   # float32 scores of one block: 64 vregs
_RING_BYTES = 2 << 20             # page copies in flight, K or V
_KEEP_SCORE_BYTES = 8 << 20       # a slot's masked scores kept for phase 1
_ROW_TILE = 16                    # sublanes of a bfloat16 tile
_LANES = 128


def _score_columns(block_pages: int, page: int) -> int:
    """Columns a block takes among the kept scores: its tokens, in whole
    lane tiles, so that every block's scores start at one."""
    return -(-block_pages * page // _LANES) * _LANES


class Walk(NamedTuple):
    """What :func:`walk_sizes` answers for a call's shapes."""
    block_pages: int
    ring_blocks: int
    keep_scores: bool
    product_heads: int


def walk_sizes(page: int, kv_heads: int, head_dim: int, rows_all: int,
               itemsize: int, table_width: int) -> Walk:
    """``(block_pages, ring_blocks, keep_scores, product_heads)`` of the
    page walk.

    A block is ``block_pages`` pages, scored a KV head at a time,
    ``(rows, D) x (D, block_pages * page)``: as many pages as keep the
    block's float32 scores ``(rows_all, block_pages * page)`` within
    ``_BLOCK_SCORE_BYTES`` and the block's page copies within a quarter
    of ``_RING_BYTES``. The ring holds ``ring_blocks`` blocks, about
    ``_RING_BYTES`` of them: four blocks of 8 pages in flight were as
    fast as two or three of 16 at GQA 128:8 (0.408 against 0.416 and
    0.396 ms a call of 32 slots) and faster at GQA 32:8, where a slot
    holds some 18 pages and a long block is mostly dead columns (0.0742
    against 0.0807 ms; PERF.md, PR 32).

    ``keep_scores`` says whether a slot's masked scores over the whole
    table fit in ``_KEEP_SCORE_BYTES`` of VMEM, so that phase 1 reads V
    alone; where they do not, phase 1 streams K a second time.

    ``product_heads`` is how many KV heads share one product: one where
    a head's query rows are whole ``_ROW_TILE`` tiles (GQA 128:8: 16
    rows), else the fewest whose rows together are (GQA 32:8: 4 heads
    of 4 rows; all of them where none does), their query rows laid
    block-diagonally over the heads' lanes so that a row still meets
    its own head only."""
    page_bytes = page * kv_heads * head_dim * itemsize
    block_pages = max(1, min(table_width,
                             _BLOCK_SCORE_BYTES // (rows_all * page * 4),
                             _RING_BYTES // (4 * page_bytes)))
    ring_blocks = max(2, min(8, _RING_BYTES // (block_pages * page_bytes)))
    blocks = -(-table_width // block_pages)
    keep = (rows_all * blocks * _score_columns(block_pages, page) * 4
            <= _KEEP_SCORE_BYTES)
    rows = rows_all // kv_heads
    product_heads = next(
        (n for n in range(1, kv_heads) if kv_heads % n == 0
         and n * rows % _ROW_TILE == 0), kv_heads)
    return Walk(block_pages, ring_blocks, keep, product_heads)


def _ragged_kernel(table_ref, len_ref, layer_ref, *rest,
                   block_pages: int, ring_blocks: int, keep_scores: bool,
                   product_heads: int, int8: bool, sm_scale: float,
                   bounded: bool):
    """One slot's program: walk its live pages, and nothing else.

    ``bounded`` (a sliding-window layer): a fourth scalar-prefetch
    operand gives each slot's first attended position. The walk starts
    at the block that holds it, pages wholly before it are not copied
    (their table entries may be the sentinel: the pages went back to the
    pool), and positions before it inside its page are masked, by the
    same comparison with the token's offset that masks the positions
    past the length. Without it the program is the one it always was.

    ``k_hbm`` / ``v_hbm`` are the stacked pool leaves, left in HBM; the
    body copies the live pages of this layer into a VMEM ring itself,
    ``ring_blocks`` blocks of ``block_pages`` pages, and keeps the ring
    full while it computes. The copies of one slot are a single
    sequence of *items*: the K blocks in table order (phase 0), then
    the V blocks (phase 1), so V's first blocks are in flight while
    phase 0 ends, and the first items of the NEXT slot are started
    before this program returns (the grid runs in order on one core).
    Where the scores are not kept, phase 1's items alternate K and V.

    A query row is scored against its own KV head only. A block lies in
    the ring token-major, ``(tokens, Hkv, D)``; ``heads_of`` takes each
    head's ``(tokens, D)`` rows out of it with a strided load of 32-bit
    words and a shift or a mask (module docstring), and a head's
    ``rows`` query rows meet those alone, so a block's scores are
    ``(rows_all, tokens)`` and no column is another head's. Where a
    head's rows are no whole sublane tile, ``product_heads`` heads share
    a product: their query rows sit block-diagonally over the heads'
    lanes, ``(product_heads * rows, product_heads * D)``, against the
    heads' rows side by side, so the zeros do the picking inside the
    MXU; P.V comes out a head a lane block, and a row keeps its own.

    Rounding points are the oracle's (module docstring): the MXU takes
    its operands in the cache dtype, whose products are exact in
    float32, so only the order of the float32 sums differs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if bounded:
        start_ref, *rest = rest
    q_ref, k_hbm, v_hbm, kn_ref, vn_ref, new_ok_ref, *rest = rest
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
    rows = rows_all // kv_heads                # query rows of a KV head
    product_rows = product_heads * rows
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

    def heads_of(at, dtype):
        """The block at ring place ``at`` a KV head: ``kv_heads`` arrays
        ``(block_tokens, D)`` in ``dtype``. The ring's rows are (token,
        head), and a 32-bit word down the sublanes holds ``pack`` heads
        of one token: a load of every ``kv_heads / pack``-th word is
        ``pack`` heads' rows, and a shift or a mask is one of them (a
        bfloat16 is the top half of a float32; an int8 its top byte,
        shifted down with its sign)."""
        tokens = ring.at[at].reshape(block_tokens * kv_heads, head_dim)
        pack = 4 // ring.dtype.itemsize
        if pack == 1 or kv_heads % pack:
            # a float32 pool's rows are words; heads that fill no whole
            # word are a geometry ``ragged_tileable`` keeps off the
            # chip, the interpreter's
            return [tokens[pl.ds(h, block_tokens, stride=kv_heads), :]
                    .astype(dtype) for h in range(kv_heads)]
        words = tokens.bitcast(jnp.int32)
        heads = []
        for w in range(kv_heads // pack):
            word = words[pl.ds(w, block_tokens, stride=kv_heads // pack), :]
            for i in range(pack):
                if pack == 2:
                    head = lax.bitcast_convert_type(
                        word << 16 if i == 0 else word & -65536,
                        jnp.float32)
                else:
                    head = lax.shift_right_arithmetic(
                        word << (24 - 8 * i), 24).astype(jnp.float32)
                heads.append(head.astype(dtype))
        return heads

    # a product is ``product_heads`` KV heads and their query rows
    firsts = range(0, kv_heads, product_heads)
    row = lax.broadcasted_iota(jnp.int32, (product_rows, 1), 0)

    def rows_of(h):
        return slice(h * rows, h * rows + product_rows)

    def own(parts):
        """One ``(product_rows, n)`` of a part a head of a product: each
        row takes the part of its own head."""
        out = parts[0]
        for i, part in enumerate(parts[1:], 1):
            out = jnp.where(row >= i * rows, part, out)
        return out

    def products(fn):
        """``fn(first head)`` of each product, one under the other."""
        return jnp.concatenate([fn(h) for h in firsts], axis=0)

    def side_by_side(heads, h):
        return jnp.concatenate(heads[h:h + product_heads], axis=1)

    def scale_of(at, h):
        """The scales of a product's score columns, ``(product_rows,
        block_tokens)``. A page's scales ride the ring head-major in
        whole lane rows, so a head's are ``page`` lanes of a row (of
        several rows where a page is longer than one)."""
        def of_head(head):
            pieces = []
            for c in range(block_pages):
                lo, hi = head * page, (head + 1) * page
                while lo < hi:
                    r, lane = divmod(lo, _LANES)
                    n = min(_LANES - lane, hi - lo)
                    pieces.append(scale_ring[at, c, r:r + 1, lane:lane + n])
                    lo += n
            return jnp.concatenate(pieces, axis=1)

        return own([of_head(h + i) for i in range(product_heads)])

    q_all = q_ref[0]                                        # (R, D)

    def diagonal(q):
        """A product's query rows, block-diagonal over its heads' lanes:
        a row is zero in the lanes of every head but its own."""
        if product_heads == 1:
            return q
        wide = q.astype(jnp.float32)
        return jnp.concatenate(
            [jnp.where(jnp.logical_and(row >= i * rows,
                                       row < (i + 1) * rows), wide, 0.0)
             for i in range(product_heads)], axis=1).astype(q.dtype)

    q_own = {h: diagonal(q_all[rows_of(h)]) for h in firsts}
    offset = lax.broadcasted_iota(jnp.int32, (1, block_tokens), 1)

    def scores_of(q, keys):
        # rounding order matches the oracle exactly: dot -> cache-dtype
        # round -> * sm_scale -> (* k_scale on int8) -> mask. q stays
        # UNSCALED: the oracle applies sm_scale after the (rounded)
        # score einsum.
        return _round(lax.dot_general(
            q, keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)) * sm_scale

    def block_scores(at, j):
        heads = heads_of(at, q_all.dtype)

        def one(h):
            s = scores_of(q_own[h], side_by_side(heads, h))
            # fused dequant, oracle formulation: the int8 scores are
            # exact through the rounded dot, and the per-vector scale
            # folds into f32 AFTER — never a converted cache copy
            return s * scale_of(at, h) if int8 else s

        ok = offset < length - j * block_tokens
        if bounded:
            ok = jnp.logical_and(
                ok, offset >= start_ref[b] - j * block_tokens)
        return jnp.where(ok, products(one), _NEG_INF)

    def fold_stats(stats, scores):
        m_prev, l_prev = stats
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        return m_new, (l_prev * jnp.exp(m_prev - m_new)
                       + jnp.exp(scores - m_new).sum(axis=-1, keepdims=True))

    def score_cols(j):
        stride = _score_columns(block_pages, page)
        return pl.ds(pl.multiple_of(j * stride, stride), block_tokens)

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
    # themselves), once a slot and a few columns, so every row meets
    # every head's and ``new_ok`` masks the others'; their K arrives
    # unquantized even on int8 pools (oracle contract). Folding them
    # makes m/l FINAL (the causal diagonal guarantees l >= 1, so phase 1
    # never divides by zero)
    s_new = jnp.where(new_ok_ref[...] > 0, scores_of(q_all, kn_ref[0]),
                      _NEG_INF)
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
        # oracle int8 V path: normalized probs stay f32 and the
        # per-vector scale folds in pre-einsum (precision over
        # bandwidth — see decode_attention_cached); else probs.astype
        heads = heads_of(at, jnp.float32 if int8 else cdt)
        if not int8:
            p = _round(p).astype(cdt)

        def one(h):
            p_own = p[rows_of(h)]
            wide = jnp.dot(p_own * scale_of(at, h) if int8 else p_own,
                           side_by_side(heads, h),
                           preferred_element_type=jnp.float32)
            # a head a lane block: a row keeps its own head's
            return own([wide[:, i * head_dim:(i + 1) * head_dim]
                        for i in range(product_heads)])

        return acc + products(one)

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
    did; the slice comes out head-major in whole lane rows, ``page``
    scales a head a page, in the order of the scores they scale."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, g_len, q_heads, head_dim = q.shape
    _, num_pages, page, kv_heads, _ = k_pages.shape
    group = q_heads // kv_heads
    rows = g_len * group
    rows_all = kv_heads * rows
    int8 = bool(scale_pages)
    sizes = walk_sizes(page, kv_heads, head_dim, rows_all,
                       k_pages.dtype.itemsize, page_table.shape[1])
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
    # the new tokens' mask, built here so the body divides nothing
    row_head = np.arange(rows_all)[:, None] // rows
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
        _ragged_kernel, block_pages=sizes.block_pages,
        ring_blocks=sizes.ring_blocks, keep_scores=sizes.keep_scores,
        product_heads=sizes.product_heads, int8=int8,
        sm_scale=head_dim ** -0.5, bounded=bounded)
    prefetch = (table, lens, layer) + (
        (start.astype(jnp.int32),) if bounded else ())
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [
        pl.BlockSpec((1, rows_all, head_dim), slot_block),
        in_hbm, in_hbm,
        pl.BlockSpec((1, kv_heads * g_len, head_dim), slot_block),
        pl.BlockSpec((1, kv_heads * g_len, head_dim), slot_block),
        pl.BlockSpec((rows_all, kv_heads * g_len), whole),
    ]
    operands = [q_hm, k_pages, v_pages, kn_hm, vn_hm,
                jnp.asarray(new_ok, jnp.int32)]
    scratch = [
        pltpu.VMEM((sizes.ring_blocks, sizes.block_pages, page, kv_heads,
                    head_dim), k_pages.dtype),
        pltpu.SemaphoreType.DMA((sizes.ring_blocks,)),
    ]
    if sizes.keep_scores:
        blocks = -(-page_table.shape[1] // sizes.block_pages)
        scratch.append(pltpu.VMEM(
            (rows_all, blocks * _score_columns(sizes.block_pages, page)),
            jnp.float32))
    if int8:
        in_specs += [in_hbm, in_hbm]
        scale_rows = -(-kv_heads * page // _LANES)
        operands += [jnp.pad(
            lax.dynamic_index_in_dim(s, layer[0], 0, keepdims=False)
            .transpose(0, 2, 1).reshape(num_pages, kv_heads * page),
            ((0, 0), (0, scale_rows * _LANES - kv_heads * page)))
            .reshape(num_pages, scale_rows, _LANES) for s in scale_pages]
        scratch.append(pltpu.VMEM(
            (sizes.ring_blocks, sizes.block_pages, scale_rows, _LANES),
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
