"""Pallas TPU selective step (a Mamba-1 layer's decode step).

``ops/ssm.py`` has the recurrence and ``selective_step``, its one-token
XLA form and this kernel's oracle. As XLA a decode step of a state-space
layer is a dozen small fusions around the state's update: ``dt_proj``
and its softplus, the decay, the update, the read-out and the gate, and
the update writes the layer's rows of the whole state stack through a
``dynamic-update-slice``. Here one kernel does all of it and touches
``h`` once: each block of ``h`` is read, stepped and written back in
place in the stack.

Grid (channel tile, block of rows), rows innermost: a tile's ``w_dt``,
``b_dt``, ``a`` and ``d`` stay in VMEM while the row blocks stream past,
so they are read once a call. A step of the grid holds ``ROWS`` rows of
``h`` for the tile, (ROWS, N, TILE) float32, and walks the rows in a
loop: states down the sublanes and channels along the lanes, ``dt`` and
``x`` a row broadcast down, ``b`` and ``c`` a row broadcast across. Those
two have to be columns: the caller lays a block of rows' ``b`` and ``c``
side by side and transposed, (2N, ROWS), and a row takes its lane of
that by a masked sum.

``h`` is the **whole** state stack (layers, B, N, C) with the layer's
index by scalar prefetch and the output aliased to it: a layer's slice
would be a copy inside the model's layer scan. ``active`` rides by
scalar prefetch too; a row that is not active writes back the ``h`` it
read, bit for bit (its ``y`` is computed and not used).

``selective_step`` is the kernel and nothing else: a caller that wants
the XLA form for shapes Mosaic cannot tile chooses it from
``select.step_tileable``. The ``pallas_call`` is named
``selective_step`` (a trace finds it by that).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from gofr_tpu.ops.pallas.select import lower_for_target

# a grid step's slots and channels (select.step_tileable asks for whole
# ones); blocks of 8-64 rows and tiles of 512-5120 channels ran within
# 4 % of one another on the chip, rows unrolled (PERF.md §6, PR 38)
ROWS = 16
TILE = 1024


def _softplus(v):
    """``jax.nn.softplus`` (``logaddexp(v, 0)``) written out."""
    return jnp.maximum(v, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(v)))


def _step_kernel(layer_ref, active_ref, xc_ref, low_ref, bc_ref, z_ref,
                 w_dt_ref, b_dt_ref, a_ref, d_ref, h_ref, y_ref, hout_ref,
                 dt_rows, dtx_rows, y_rows, *, rows: int, n_state: int):
    from jax import lax
    from jax.experimental import pallas as pl

    del layer_ref                         # the index maps' alone
    f32 = jnp.float32
    first = pl.program_id(1) * rows
    dt = _softplus(jnp.dot(low_ref[:], w_dt_ref[:],
                           preferred_element_type=f32) + b_dt_ref[:])
    x = xc_ref[:].astype(f32)                           # (rows, TILE)
    dt_rows[:] = dt
    dtx_rows[:] = dt * x
    a = a_ref[:]                                        # (N, TILE)
    bc = bc_ref[0]                                      # (2N, rows)
    lanes = lax.broadcasted_iota(jnp.int32, bc.shape, 1)

    # a loop, not a Python unroll: the kernel is traced at every call
    # site of every program, and 16 unrolled rows cost a tick program
    # ~3 s of set-up on the chip's host (PERF.md §6, PR 38)
    def row(r, carry):
        # row r's b and c as columns (2N, 1): its lane of bc, summed
        # with zeros
        col = jnp.sum(jnp.where(lanes == r, bc, 0.0), axis=1,
                      keepdims=True)
        h = h_ref[0, r]                                 # (N, TILE)
        new = (jnp.exp(dt_rows[pl.ds(r, 1), :] * a) * h
               + col[:n_state] * dtx_rows[pl.ds(r, 1), :])
        hout_ref[0, r] = jnp.where(active_ref[first + r] != 0, new, h)
        y_rows[pl.ds(r, 1), :] = (new * col[n_state:]).sum(
            axis=0, keepdims=True)
        return carry

    lax.fori_loop(0, rows, row, 0)
    z = z_ref[:].astype(f32)
    y = y_rows[:] + d_ref[:] * x
    y_ref[:] = (y * (z * jax.nn.sigmoid(z))).astype(y_ref.dtype)


def _pallas_step(layer, active, xc, low, bc, z, w_dt, b_dt, a, d, h, *,
                 interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, channels = xc.shape
    n_state, rank = a.shape[0], low.shape[1]
    rows, tile = ROWS, TILE
    kernel = functools.partial(_step_kernel, rows=rows, n_state=n_state)
    by_row = pl.BlockSpec((rows, tile), lambda ci, ri, *_: (ri, ci))
    by_tile = pl.BlockSpec((1, tile), lambda ci, ri, *_: (0, ci))
    state = pl.BlockSpec((1, rows, n_state, tile),
                         lambda ci, ri, layer, _: (layer[0], ri, 0, ci))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(channels // tile, batch // rows),
        in_specs=[
            by_row,                                                 # xc
            pl.BlockSpec((rows, rank), lambda ci, ri, *_: (ri, 0)),  # low
            pl.BlockSpec((1, 2 * n_state, rows),
                         lambda ci, ri, *_: (ri, 0, 0)),             # bc
            by_row,                                                 # z
            pl.BlockSpec((rank, tile), lambda ci, ri, *_: (0, ci)),  # w_dt
            by_tile,                                                # b_dt
            pl.BlockSpec((n_state, tile), lambda ci, ri, *_: (0, ci)),  # a
            by_tile,                                                # d
            state,                                                  # h
        ],
        out_specs=[by_row, state],
        scratch_shapes=[pltpu.VMEM((rows, tile), jnp.float32)] * 3,
    )
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((batch, channels), xc.dtype),
                   jax.ShapeDtypeStruct(h.shape, h.dtype)],
        # operands count the two prefetched scalars: h is the 11th
        input_output_aliases={10: 1},
        compiler_params=compiler_params,
        interpret=interpret,
        name="selective_step",
    )(layer, active, xc, low, bc, z, w_dt, b_dt, a, d, h)


def selective_step(xc: jnp.ndarray, dt_low: jnp.ndarray, b: jnp.ndarray,
                   c: jnp.ndarray, z: jnp.ndarray, w_dt: jnp.ndarray,
                   b_dt: jnp.ndarray, a: jnp.ndarray, d: jnp.ndarray,
                   h: jnp.ndarray, layer, active: jnp.ndarray,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token a row of one state-space layer, from the convolved input
    to the gated output: xc (B, C) in the activations' type; dt_low
    (B, R), the normed low-rank step in the same type; b, c (B, N);
    z (B, C); w_dt (R, C); b_dt (C,); a = -exp(A_log) (N, C); d (C,);
    h (layers, B, N, C) float32, the whole stack, and ``layer`` its
    index; active (B,) bool. Returns (``(xc dt-step's y) * silu(z)``
    (B, C) in xc's type, the stack with the layer's active rows
    stepped): ``ops.ssm.selective_step`` with ``dt = softplus(dt_low
    w_dt + b_dt)`` (a bfloat16 product, float32 sums) and the gate.

    B must split into whole ``ROWS``-row blocks and C into whole
    ``TILE``-channel tiles: ``select.step_tileable`` (``interpret=None``
    follows the lowering target)."""
    f32 = jnp.float32
    batch, channels = xc.shape
    n_state = a.shape[0]
    if batch % ROWS or channels % TILE:
        raise ValueError(
            f"selective_step: {batch} rows x {channels} channels do not "
            f"split into {ROWS}-row blocks and {TILE}-channel tiles")
    # a block's b and c as columns: (B / ROWS, 2N, ROWS)
    bc = jnp.concatenate([b.astype(f32), c.astype(f32)], axis=-1)
    bc = bc.reshape(batch // ROWS, ROWS, 2 * n_state).transpose(0, 2, 1)
    return lower_for_target(
        _pallas_step, interpret,
        jnp.asarray(layer, jnp.int32).reshape(1),
        active.astype(jnp.int32), xc, dt_low.astype(xc.dtype), bc, z,
        w_dt, b_dt.astype(f32)[None, :], a.astype(f32),
        d.astype(f32)[None, :], h)
