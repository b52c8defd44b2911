"""Which Pallas kernel runs, decided where it can be seen.

A kernel entry point in this package IS the kernel: it never swaps in
the pure-jnp formulation from ops/attention on its own. That formulation
stays the correctness oracle, and choosing it over a kernel is the
caller's decision, taken from the predicates below and reported (the
engine logs its attention path once at start). Two rules:

- **Tiling predicates answer for Mosaic.** ``*_tileable`` says whether
  the compiled TPU kernel accepts a geometry; callers that select
  automatically use it on every platform, so a CPU run and a TPU run of
  the same config take the same path. A caller that forces a kernel
  onto a geometry Mosaic rejects gets the compiler's own error.
- **Interpret mode follows the lowering target, not the process.**
  ``interpret=None`` lowers the compiled kernel when the computation is
  lowered for TPU and the interpreter everywhere else, chosen per
  lowering by ``lax.platform_dependent``. A TPU executable built from a
  CPU process (AOT against a topology) therefore holds the real kernel,
  never an inlined interpreter, and nothing on a TPU ever interprets.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

__all__ = ["flash_tileable", "ragged_tileable", "scan_tileable",
           "step_tileable", "index_tileable", "sparse_latent_tileable",
           "lower_for_target"]


def flash_tileable(seq_len: int, head_dim: int, block_q: int = 512,
                   block_k: int = 512) -> bool:
    """Flash-attention (prefill) tiling predicate: whole blocks, a
    128-lane head_dim and at least one 128-row tile of sequence. A
    narrower head goes in zero-padded to 128 lanes by its caller, with
    its own scale (models/conv_moe.py at head 64), and is asked about
    as the 128 it then is (AOT-compiled for v5e at heads of 128 in
    blocks of 512, of 64 padded to 128 in blocks of 128 ... 1024, and
    of 192 padded to 256, by tests/test_pallas_aot.py)."""
    block_q, block_k = min(block_q, seq_len), min(block_k, seq_len)
    return (seq_len % block_q == 0 and seq_len % block_k == 0
            and head_dim % 128 == 0 and seq_len >= 128)


def ragged_tileable(head_dim: int, q_heads: int, kv_heads: int,
                    page: int, kv_itemsize: int = 2,
                    packed: bool = False) -> bool:
    """Ragged-paged-attention tiling predicate: a 128-lane head_dim, a
    sublane-filling q-head count, a page deep enough to tile the KV
    block, and KV heads that fill whole 32-bit words down a page's
    sublanes (two bfloat16 heads, four int8: the kernel takes a head's
    rows out of a block by words; Mosaic has no strided load of
    narrower rows, and refused a single KV head before that). A
    ``packed`` pool, whose leaf keeps a token's heads side by side in
    one row of ``Hkv * head_dim`` lanes (``kernel_heads`` of a 1-D
    per-token shape), may instead hold bfloat16 heads of 64, an even
    number of them: the kernel walks two heads a 128-lane unit
    (ragged_paged_attention's packed form). Heads of 64 in a pool of
    ``(Hkv, 64)`` rows are refused: that is the strided load of rows
    narrower than 128 lanes. AOT-compiled for v5e at MHA 32:32, GQA
    32:8 and GQA 128:8 at head 128, and packed GQA 32:8 at head 64, by
    tests/test_pallas_aot.py."""
    pairs = (packed and head_dim == 64 and kv_heads % 2 == 0
             and kv_itemsize == 2)
    return (q_heads % kv_heads == 0 and q_heads % 8 == 0 and page % 16 == 0
            and (pairs or (head_dim % 128 == 0
                           and kv_heads % (4 // kv_itemsize) == 0)))


def scan_tileable(seq_len: int, channels: int, n_state: int) -> bool:
    """Selective-scan (state-space prefill) tiling predicate: whole
    128-token blocks (one 128 x 128 transpose of the projections a
    block), whole 512-channel tiles, and states that fill whole
    sublanes with both projections inside one 128-lane row
    (AOT-compiled for v5e at 5120 channels x 16 states by
    tests/test_pallas_aot.py)."""
    return (seq_len % 128 == 0 and channels % 512 == 0
            and n_state % 8 == 0 and 2 * n_state <= 128)


def step_tileable(channels: int, n_state: int, rows: int) -> bool:
    """Selective-step (state-space decode) tiling predicate: whole
    1024-channel tiles, whole 16-row blocks of slots (a bfloat16 block
    of rows fills its sublanes) and states that fill whole sublanes
    (AOT-compiled for v5e at 128 slots x 5120 channels x 16 states by
    tests/test_pallas_aot.py)."""
    return channels % 1024 == 0 and rows % 16 == 0 and n_state % 8 == 0


def index_tileable(head_dim: int, heads: int, page: int) -> bool:
    """Index-scores (sparse attention's indexer, decode) tiling
    predicate: a 128-lane key, heads that fill whole sublanes, a page
    deep enough to tile a bfloat16 block of keys and no longer than a
    block (the ring holds blocks of pages in VMEM), and blocks that are
    whole lane tiles of the scores' row (AOT-compiled for v5e at 64
    heads of 128, page 32, by tests/test_pallas_aot.py)."""
    from gofr_tpu.ops.pallas.index_scores import _BLOCK_TOKENS, index_walk

    return (head_dim % 128 == 0 and heads % 8 == 0 and page % 16 == 0
            and page <= _BLOCK_TOKENS
            and index_walk(page) * page % 128 == 0)


def sparse_latent_tileable(cache_row: int, heads: int, page: int) -> bool:
    """Sparse-latent-attention (a full layer's decode attention over its
    selection) tiling predicate: a latent row of whole lanes, heads that
    fill whole sublanes, a page deep enough to tile a bfloat16 block of
    rows and no longer than a block (the ring holds blocks of pages in
    VMEM), and blocks that are whole lane tiles of the mask's row
    (AOT-compiled for v5e at 128 heads over rows of 640, page 32, by
    tests/test_pallas_aot.py)."""
    from gofr_tpu.ops.pallas.sparse_latent_attention import (_BLOCK_TOKENS,
                                                             latent_walk)

    return (cache_row % 128 == 0 and heads % 8 == 0 and page % 16 == 0
            and page <= _BLOCK_TOKENS
            and latent_walk(page) * page % 128 == 0)


def lower_for_target(kernel: Callable, interpret: Optional[bool],
                     *operands):
    """Run ``kernel(*operands, interpret=...)``. An explicit
    ``interpret`` passes through; ``None`` resolves per lowering target
    (module docstring)."""
    if interpret is not None:
        return kernel(*operands, interpret=bool(interpret))
    from jax import lax

    return lax.platform_dependent(
        *operands,
        tpu=functools.partial(kernel, interpret=False),
        default=functools.partial(kernel, interpret=True))
