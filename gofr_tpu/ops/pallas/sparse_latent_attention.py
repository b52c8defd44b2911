"""A full layer's sparse decode attention over the latent rows the
indexer selected, read where they lie in the page pool: one Pallas TPU
kernel (DeepSeek-V3.2's sparse attention over DeepSeek-V3's latent cache,
as ``models/dsa_mla_moe.py`` serves it). With every live position
selected it is also the latent family's absorbed decode attention
(``models/mla_moe.py``'s paged step, pangu's and xing's).

A decode step's query of a full layer attends the ``index_topk`` cached
positions of its slot that the indexer scored highest (2048 of 2k-8k at
the published widths). The cache keeps one latent row a token a layer,
``[c; k_r; zeros]`` (640 lanes), in the pool's stacked ``ckv`` leaf; the
query arrives absorbed, ``[q_n W_UK; q_r; zeros]`` a head, so a score is
one product with a row and the weighted latent ``u = sum_s p_s c_s`` is
another.

- Grid ``(slot,)``: one program a slot walks that slot's live pages and
  nothing else, as ``index_scores`` does. The page table, the live
  lengths and the layer ride scalar prefetch; the stacked leaf
  ``(layers, num_pages, page, width)`` stays in HBM
  (``memory_space=ANY``) and the body copies a block of ``block_pages``
  pages at a time into a VMEM ring (a page a copy), ``ring_blocks``
  blocks in flight while one is attended; the next slot's first blocks
  are started before a program returns (the grid runs in order on one
  core). A slot of length 0 (an inactive one) copies nothing and
  returns zeros.
- The selection arrives as a mask, one int32 a position of the slot's
  table, nonzero where the position is selected; or, with ``chosen``
  None, every live position is selected and no mask is read. A block's
  scores are one ``(heads, width) x (width, tokens)`` product on the MXU
  in the pool's dtype with float32 accumulation; positions the mask
  leaves out (and any at or past the slot's length) get no weight. The
  softmax is online, a head a row, in float32: the block's running
  maximum and sum, and ``acc += p . rows[:, :lanes]`` with ``p`` in the
  pool's dtype.
- The output is ``u`` in float32, ``(slots, heads, rank)``: the caller
  applies ``W_UV``.

A dead page inside a partly live block is not copied; its ring place
holds an earlier block's rows (finite, and zeroed before the first
program), which meet weights of exactly 0.

The gathered form (``lax.top_k``, or every position, the rows gathered
through the table, ``mla_moe.absorbed_attention``) is the oracle the
tests hold this kernel to, and the path a caller takes where ``select.sparse_latent_tileable``
says Mosaic cannot tile the shape. The ``pallas_call`` is named
``sparse_latent_attention``: a trace finds the kernel by that name.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.ops.pallas.select import lower_for_target

__all__ = ["sparse_latent_attention", "latent_walk"]

# positions a block of pages attends at once, and blocks of page copies
# in flight: at the cell's call shape on v5e a call is 1.43 / 1.23 / 1.15 /
# 1.14 ms at blocks of 256 / 512 / 1024 / 2048 positions (the per-block
# work of the walk is amortised; the page copies alone take 0.67 ms)
_BLOCK_TOKENS = 1024
_RING_BLOCKS = 3
_LANES = 128
_NEG_INF = -1e30


def latent_walk(page: int) -> int:
    """Pages a block: ``_BLOCK_TOKENS`` positions, a whole number of
    lane tiles of the mask's row and the scores' columns."""
    return max(1, _BLOCK_TOKENS // page)


def _sparse_kernel(table_ref, len_ref, layer_ref, q_ref, *refs,
                   block_pages: int, ring_blocks: int, lanes: int,
                   scale: float, masked: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if masked:
        mask_ref, rows_hbm, o_ref, ring, sems = refs
    else:                   # every live position: no mask operand
        rows_hbm, o_ref, ring, sems = refs
    b = pl.program_id(0)
    num_slots = pl.num_programs(0)
    table_width = table_ref.shape[1]
    num_pages, page, width = rows_hbm.shape[1:]
    heads = q_ref.shape[1]
    block_tokens = block_pages * page
    layer = layer_ref[0]

    def walk(slot):
        """(live length, live pages, live blocks) of a slot."""
        length = jnp.minimum(len_ref[slot], table_width * page)
        pages = lax.div(length + page - 1, page)
        return length, pages, lax.div(pages + block_pages - 1, block_pages)

    def copies(slot, j, do):
        """``do`` each page copy of block ``j`` of ``slot``: live pages
        only, so a sentinel id is never read."""
        _, pages, _ = walk(slot)
        at = lax.rem(j, ring_blocks)
        first = j * block_pages

        def one(c, carry):
            pid = jnp.minimum(table_ref[slot, first + c], num_pages - 1)
            do(pltpu.make_async_copy(rows_hbm.at[layer, pid], ring.at[at, c],
                                     sems.at[at]))
            return carry

        lax.fori_loop(0, jnp.minimum(pages - first, block_pages), one, 0)

    def start_first_blocks(slot):
        def one(j, carry):
            copies(slot, j, lambda copy: copy.start())
            return carry

        lax.fori_loop(0, jnp.minimum(walk(slot)[2], ring_blocks - 1), one, 0)

    @pl.when(b == 0)
    def _first_program():
        ring[...] = jnp.zeros_like(ring)
        start_first_blocks(b)

    length, _, blocks = walk(b)
    q = q_ref[0]                                            # (heads, width)
    offset = lax.broadcasted_iota(jnp.int32, (1, block_tokens), 1)

    def block(j, carry):
        m_prev, l_prev, acc = carry
        ahead = j + ring_blocks - 1

        @pl.when(ahead < blocks)
        def _():
            copies(b, ahead, lambda copy: copy.start())

        copies(b, j, lambda copy: copy.wait())
        rows = ring.at[lax.rem(j, ring_blocks)].reshape(
            block_tokens, width)[...]
        s = lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if masked:
            start = pl.multiple_of(j * block_tokens, block_tokens)
            chosen = jnp.logical_and(
                mask_ref[0, :, pl.ds(start, block_tokens)] != 0,
                offset < length - j * block_tokens)        # (1, tokens)
        else:
            chosen = offset < length - j * block_tokens
        s = jnp.where(chosen, s * scale, _NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(chosen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + p.sum(axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(rows.dtype), rows[:, :lanes],
                                    preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    _, total, acc = lax.fori_loop(
        0, blocks, block,
        (jnp.full((heads, 1), _NEG_INF, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, lanes), jnp.float32)))

    @pl.when(b + 1 < num_slots)
    def _next_program():
        start_first_blocks(b + 1)

    # a slot with nothing selected (an inactive one) has a sum of 0
    o_ref[0] = jnp.where(total > 0, acc / jnp.where(total > 0, total, 1.0),
                         0.0)


def _pallas_sparse(q, chosen, rows_pages, page_table, lengths, layer, *,
                   rank: int, scale: float, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, width = q.shape
    page = rows_pages.shape[2]
    block_pages = latent_walk(page)
    block_tokens = block_pages * page
    lanes = -(-rank // _LANES) * _LANES
    masked = chosen is not None

    def slot(b, *_):
        return (b, 0, 0)

    mask, mask_specs = (), []
    if masked:
        columns = -(-page_table.shape[1] // block_pages) * block_tokens
        mask = (jnp.pad(chosen.astype(jnp.int32),
                        ((0, 0), (0, columns - chosen.shape[1])))[:, None, :],)
        mask_specs = [pl.BlockSpec((1, 1, columns), slot)]
    kernel = functools.partial(_sparse_kernel, block_pages=block_pages,
                               ring_blocks=_RING_BLOCKS, lanes=lanes,
                               scale=scale, masked=masked)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(batch,),
        in_specs=[pl.BlockSpec((1, heads, width), slot), *mask_specs,
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, heads, lanes), slot),
        scratch_shapes=[
            pltpu.VMEM((_RING_BLOCKS, block_pages, page, width),
                       rows_pages.dtype),
            pltpu.SemaphoreType.DMA((_RING_BLOCKS,)),
        ],
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
        out_shape=jax.ShapeDtypeStruct((batch, heads, lanes), jnp.float32),
        compiler_params=compiler_params,
        interpret=interpret,
        name="sparse_latent_attention",
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, *mask, rows_pages)
    return out[..., :rank]


def sparse_latent_attention(q, chosen, rows_pages, page_table, lengths,
                            layer, *, rank: int, scale: float,
                            interpret: Optional[bool] = None) -> jnp.ndarray:
    """``u[b, h] = sum_s softmax_s(scale * q[b, h] . row_s) c_s`` over
    slot ``b``'s positions ``s < lengths[b]`` that ``chosen[b, s]``
    selects; zeros where it selects none. q (B, heads, width) the
    absorbed query in the pool's dtype; chosen (B, P * page) bool, or
    None for every position under the length (no mask is read);
    rows_pages the stacked ``ckv`` leaf (L, num_pages, page, width) and
    ``layer`` the int32 scalar naming the plane; page_table (B, P) int32,
    ``num_pages`` the unallocated sentinel; lengths (B,) int32, the live
    positions (the new token's own included, its row already written);
    ``rank`` the latent width ``c`` (the row's first lanes). Returns
    (B, heads, rank) float32. ``interpret=None`` follows the lowering
    target (ops/pallas/select)."""
    return lower_for_target(
        functools.partial(_pallas_sparse, rank=rank, scale=scale),
        interpret, q, chosen, rows_pages, page_table, lengths, layer)
