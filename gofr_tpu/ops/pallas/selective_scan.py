"""Pallas TPU selective scan (a Mamba-1 layer's prefill).

``ops/ssm.py`` has the recurrence and its plain forms. As XLA it is a
scan that either walks tokens one program step at a time or materialises
(tokens, N, C) float32 a row; this kernel keeps ``h`` where it is used.

Grid (row, channel tile, token block), the token axis innermost and
sequential: a tile's ``h`` (N x ``TILE`` float32: 8 vector registers at
16 x 512) stays in VMEM scratch from a row's first block to its last,
then goes out once as the row's final state. A step of the grid holds
``BLOCK`` tokens of ``x``, ``dt`` and ``y`` for the tile, (BLOCK, TILE)
each, and walks them: ``h = exp(dt_t * a) * h + b_t (dt_t x_t)``,
``y_t = c_t . h + d x_t``, states down the sublanes and channels along
the lanes, so ``dt_t`` and ``x_t`` broadcast down and ``b_t``, ``c_t``
across.

``b_t`` and ``c_t`` have to be columns (N, 1), and they arrive as rows of
(tokens, N). The caller lays both side by side into one (tokens, 128)
array (``b`` in lanes 0..N, ``c`` in N..2N, zeros after: in HBM a
(tokens, 2N) float32 array is padded to 128 lanes anyway), the kernel
transposes a block of 128 x 128 once, and a token's columns are static
lane slices of the result: the walk over a block is unrolled.

Padding behind a row's ``lengths`` is masked by the caller's ``dt = 0``
(``ops.ssm.mask_steps``): the decay is one and the input zero, so the
state that goes out is the state at the prompt's end.

``selective_scan`` is the kernel and nothing else: a caller that wants
the XLA form for shapes Mosaic cannot tile chooses it from
``select.scan_tileable``. The ``pallas_call`` is named ``selective_scan``
(a trace finds it by that).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from gofr_tpu.ops.pallas.select import lower_for_target
from gofr_tpu.ops.ssm import mask_steps

BLOCK = 128        # tokens a grid step: one 128 x 128 transpose of b and c
TILE = 512         # channels a grid step
LANES = 128


def _scan_kernel(x_ref, dt_ref, bc_ref, a_ref, d_ref, h0_ref, y_ref,
                 hout_ref, h_ref, *, block: int, n_state: int,
                 num_blocks: int):
    from jax.experimental import pallas as pl

    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[:] = h0_ref[0]

    a = a_ref[:]                                        # (N, TILE)
    d = d_ref[:]                                        # (1, TILE)
    # (block, 128) -> (128, block): rows 0..N are b's columns, N..2N c's
    bc = bc_ref[0].T
    h = h_ref[:]
    for t in range(block):
        x = x_ref[0, t:t + 1, :].astype(jnp.float32)    # (1, TILE)
        dt = dt_ref[0, t:t + 1, :]
        b = bc[:n_state, t:t + 1]                       # (N, 1)
        c = bc[n_state:2 * n_state, t:t + 1]
        h = jnp.exp(dt * a) * h + b * (dt * x)
        y_ref[0, t:t + 1, :] = ((h * c).sum(axis=0, keepdims=True)
                                + d * x)
    h_ref[:] = h

    @pl.when(ti == num_blocks - 1)
    def _finish():
        hout_ref[0] = h


def _pallas_scan(x, dt, bc, a, d, h0, *, n_state: int, block: int,
                 interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, seq_len, channels = x.shape
    tile = min(TILE, channels)
    num_blocks = seq_len // block
    kernel = functools.partial(_scan_kernel, block=block, n_state=n_state,
                               num_blocks=num_blocks)
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    tokens = pl.BlockSpec((1, block, tile), lambda r, ci, ti: (r, ti, ci))
    state = pl.BlockSpec((1, n_state, tile), lambda r, ci, ti: (r, 0, ci))
    return pl.pallas_call(
        kernel,
        grid=(batch, channels // tile, num_blocks),
        in_specs=[
            tokens,                                                  # x
            tokens,                                                  # dt
            pl.BlockSpec((1, block, LANES), lambda r, ci, ti: (r, ti, 0)),
            pl.BlockSpec((n_state, tile), lambda r, ci, ti: (0, ci)),  # a
            pl.BlockSpec((1, tile), lambda r, ci, ti: (0, ci)),        # d
            state,                                                   # h0
        ],
        out_specs=[tokens, state],
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq_len, channels), jnp.float32),
            jax.ShapeDtypeStruct((batch, n_state, channels), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n_state, tile), jnp.float32)],
        compiler_params=compiler_params,
        interpret=interpret,
        name="selective_scan",
    )(x, dt, bc, a, d, h0)


def selective_scan(x: jnp.ndarray, dt: jnp.ndarray, b: jnp.ndarray,
                   c: jnp.ndarray, a: jnp.ndarray, d: jnp.ndarray,
                   h0: Optional[jnp.ndarray] = None,
                   lengths: Optional[jnp.ndarray] = None,
                   interpret: Optional[bool] = None, block: int = BLOCK
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``ops.ssm.selective_scan_ref``'s operands and results: x (B, S, C)
    in its own type, dt (B, S, C), b and c (B, S, N), a (N, C), d (C,),
    h0 (B, N, C) or None, lengths (B,) or None; returns (y (B, S, C)
    float32, the state after each row's last live token (B, N, C)
    float32).

    S must split into whole ``block``-token blocks and C into whole
    512-channel tiles, or be one block or tile (``interpret=None``
    follows the lowering target; compiled for TPU the shape must satisfy
    ``select.scan_tileable`` at the default block, or Mosaic rejects it;
    a test of the interpreter walks shorter blocks)."""
    f32 = jnp.float32
    batch, seq_len, channels = x.shape
    n_state = a.shape[0]
    block = min(block, seq_len)
    if seq_len % block or channels % min(TILE, channels) \
            or 2 * n_state > LANES:
        raise ValueError(
            f"selective_scan: {seq_len} tokens x {channels} channels x "
            f"{n_state} states do not split into {block}-token blocks, "
            f"{TILE}-channel tiles and {LANES} lanes of b and c")
    dt = mask_steps(dt.astype(f32), lengths)
    bc = jnp.concatenate([b.astype(f32), c.astype(f32)], axis=-1)
    bc = jnp.pad(bc, ((0, 0), (0, 0), (0, LANES - 2 * n_state)))
    if h0 is None:
        h0 = jnp.zeros((batch, n_state, channels), f32)
    y, h = lower_for_target(
        functools.partial(_pallas_scan, n_state=n_state, block=block),
        interpret,
        x, dt, bc, a.astype(f32), d.astype(f32)[None, :], h0.astype(f32))
    return y, h
