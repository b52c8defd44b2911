"""The sparse-attention indexer's scores over a slot's index keys, read
where they lie in the page pool: one Pallas TPU kernel (DeepSeek-V3.2's
DSA indexer, as ``models/dsa_mla_moe.py`` serves it).

A decode step's query ``t`` of a full layer scores every cached
position ``s`` of its slot by ``I_{t,s} = sum_j w_j ReLU(q_j . k_s)``
over the indexer's heads ``j`` (64 of 128 at the published widths),
and then attends the top 2048 positions only. The keys ``k_s`` are one
row of ``index_head_dim`` a token a layer, the pool's ``ik`` leaf.

- Grid ``(slot,)``: one program a slot, which walks that slot's live
  pages and nothing else, as the ragged attention kernel does. The page
  table, the live lengths and the layer ride scalar prefetch; the pool's
  stacked ``ik`` leaf ``(layers, num_pages, page, D)`` stays in HBM
  (``memory_space=ANY``) and the body copies a block of ``block_pages``
  pages at a time into a VMEM ring (``make_async_copy``, a page a copy),
  ``ring_blocks`` blocks in flight while one is scored. A dead table
  column costs nothing and a sentinel id is never read; a slot of length
  0 (an inactive one) copies nothing.
- A block's scores are one ``(heads, D) x (D, tokens)`` product on the
  MXU in the pool's dtype with float32 accumulation, the ReLU and the
  heads' weighted sum in float32 on the vector unit: ``(1, tokens)``.
- The scores leave as one float32 row a slot, ``-inf`` at every position
  at or past the slot's length (and past the table's columns), which is
  what the caller's top-k reads.

``ops/attention.index_scores`` is the plain formulation over the
gathered keys: the oracle the tests hold this kernel to, and the path a
caller takes where ``select.index_tileable`` says Mosaic cannot tile the
shape. The ``pallas_call`` is named ``index_scores``: a trace finds the
kernel by that name.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.ops.pallas.select import lower_for_target

__all__ = ["index_scores", "index_walk"]

_BLOCK_TOKENS = 256          # positions a block of pages scores at once
_RING_BLOCKS = 4             # blocks of page copies in flight


def index_walk(page: int) -> int:
    """Pages a block: ``_BLOCK_TOKENS`` positions, a whole number of
    lane tiles of the scores' row."""
    return max(1, _BLOCK_TOKENS // page)


def _index_kernel(table_ref, len_ref, layer_ref, q_ref, w_ref, k_hbm, o_ref,
                  ring, sems, *, block_pages: int, ring_blocks: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    table_width = table_ref.shape[1]
    num_pages, page, dim = k_hbm.shape[1:]
    block_tokens = block_pages * page
    layer = layer_ref[0]
    length = jnp.minimum(len_ref[b], table_width * page)
    pages = lax.div(length + page - 1, page)
    blocks = lax.div(pages + block_pages - 1, block_pages)

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    def copies(j, do):
        """``do`` each page copy of block ``j``: live pages only."""
        at = lax.rem(j, ring_blocks)
        first = j * block_pages

        def one(c, carry):
            pid = jnp.minimum(table_ref[b, first + c], num_pages - 1)
            do(pltpu.make_async_copy(k_hbm.at[layer, pid], ring.at[at, c],
                                     sems.at[at]))
            return carry

        lax.fori_loop(0, jnp.minimum(pages - first, block_pages), one, 0)

    def start(j, carry):
        copies(j, lambda copy: copy.start())
        return carry

    lax.fori_loop(0, jnp.minimum(blocks, ring_blocks - 1), start, 0)
    q = q_ref[0]                                            # (heads, D)
    w = w_ref[0]                                            # (heads, 1)
    offset = lax.broadcasted_iota(jnp.int32, (1, block_tokens), 1)

    def block(j, carry):
        ahead = j + ring_blocks - 1

        @pl.when(ahead < blocks)
        def _():
            copies(ahead, lambda copy: copy.start())

        copies(j, lambda copy: copy.wait())
        keys = ring.at[lax.rem(j, ring_blocks)].reshape(block_tokens, dim)
        s = lax.dot_general(q, keys[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        score = jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True)
        # a dead page inside a partly live block was not copied: its
        # stale rows are masked here
        score = jnp.where(offset < length - j * block_tokens, score,
                          -jnp.inf)
        o_ref[0, :, pl.ds(pl.multiple_of(j * block_tokens, block_tokens),
                          block_tokens)] = score
        return carry

    lax.fori_loop(0, blocks, block, 0)


def _pallas_index(q, w, k_pages, page_table, lengths, layer, *,
                  interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, heads, dim = q.shape
    _, _, page, _ = k_pages.shape
    width = page_table.shape[1]
    block_pages = index_walk(page)
    block_tokens = block_pages * page
    columns = -(-width // block_pages) * block_tokens

    def slot(b, *_):
        return (b, 0, 0)

    kernel = functools.partial(_index_kernel, block_pages=block_pages,
                               ring_blocks=_RING_BLOCKS)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(batch,),
        in_specs=[pl.BlockSpec((1, heads, dim), slot),
                  pl.BlockSpec((1, heads, 1), slot),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, columns), slot),
        scratch_shapes=[
            pltpu.VMEM((_RING_BLOCKS, block_pages, page, dim),
                       k_pages.dtype),
            pltpu.SemaphoreType.DMA((_RING_BLOCKS,)),
        ],
    )
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, 1, columns), jnp.float32),
        compiler_params=compiler_params,
        interpret=interpret,
        name="index_scores",
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q,
      w.astype(jnp.float32)[..., None], k_pages)
    return out[:, 0, :width * page]


def index_scores(q, w, k_pages, page_table, lengths, layer,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """``I[b, s] = sum_j w[b, j] ReLU(q[b, j] . k_s)`` over slot ``b``'s
    positions ``s < lengths[b]``, ``-inf`` elsewhere. q (B, heads, D) in
    the pool's dtype; w (B, heads) float32; k_pages the stacked ``ik``
    leaf (L, num_pages, page, D) and ``layer`` the int32 scalar naming
    the plane; page_table (B, P) int32, ``num_pages`` the unallocated
    sentinel; lengths (B,) int32, the live positions (the new token's
    own included, its key already written). Returns (B, P * page)
    float32. ``interpret=None`` follows the lowering target
    (ops/pallas/select)."""
    return lower_for_target(_pallas_index, interpret, q, w, k_pages,
                            page_table, lengths, layer)
