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
           "step_tileable", "lower_for_target"]


def flash_tileable(seq_len: int, head_dim: int, block_q: int = 512,
                   block_k: int = 512) -> bool:
    """Flash-attention (prefill) tiling predicate: whole blocks, a
    128-lane head_dim and at least one 128-row tile of sequence."""
    block_q, block_k = min(block_q, seq_len), min(block_k, seq_len)
    return (seq_len % block_q == 0 and seq_len % block_k == 0
            and head_dim % 128 == 0 and seq_len >= 128)


def ragged_tileable(head_dim: int, q_heads: int, kv_heads: int,
                    page: int, kv_itemsize: int = 2) -> bool:
    """Ragged-paged-attention tiling predicate: a 128-lane head_dim, a
    sublane-filling q-head count, a page deep enough to tile the KV
    block, and KV heads that fill whole 32-bit words down a page's
    sublanes (two bfloat16 heads, four int8: the kernel takes a head's
    rows out of a block by words; Mosaic has no strided load of
    narrower rows, and refused a single KV head before that) (AOT-
    compiled for v5e at MHA 32:32, GQA 32:8 and GQA 128:8 by
    tests/test_pallas_aot.py)."""
    return (q_heads % kv_heads == 0 and head_dim % 128 == 0
            and q_heads % 8 == 0 and page % 16 == 0
            and kv_heads % (4 // kv_itemsize) == 0)


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
