"""The selective scan of a Mamba-1 state-space layer, in plain XLA.

The recurrence, a row of the batch, ``h`` of (N states, C channels) in
float32 (``x`` the convolved input, ``dt`` the step after its softplus,
``b`` and ``c`` the input's and the output's projections, ``a = -exp(A_log)``
of (N, C), ``d`` of (C,)):

    h_t = exp(dt_t[None, :] * a) * h_{t-1} + b_t[:, None] * (dt_t * x_t)[None, :]
    y_t = sum_n c_t[n] * h_t[n, :] + d * x_t

The decay is a value a (state, channel) pair, so the chunked *matrix*
form of scalar-decay models does not apply: the state has to be walked.
Three forms of the same walk, all with the signature of
``ops.pallas.selective_scan`` (the served prefill's kernel):

- ``selective_scan_ref``: a ``lax.scan`` over the tokens. The oracle.
- ``selective_scan_chunked``: a ``lax.scan`` over chunks of tokens that
  carries ``h``, a chunk an ``associative_scan`` (which materialises
  (chunk, N, C) float32 a row: what the kernel exists to avoid). For
  tests, and the served prefill's path where Mosaic cannot tile the
  shape (``select.scan_tileable``).
- ``selective_step``: one token, the decode form, on a state a row. The
  oracle of ``ops.pallas.selective_step``, the served decode step's
  kernel (``dt_proj`` and its softplus, this step on the layer's rows of
  the whole state stack in place, and the gate, in one Pallas call), and
  its XLA fallback where Mosaic cannot tile the shape
  (``select.step_tileable``).

**States lie states-major**, ``(B, N, C)``: on the chip an array's last
two axes are tiled (8, 128), and 16 states last would be padded to 128,
eight times the bytes of a state that is read and written every step.

**Padding is masked in the step**: ``mask_steps`` sets ``dt = 0`` at
positions ``>= length``, which makes the decay one and the input zero, so
``h`` at the bucket's end is ``h`` at the prompt's end. Every form takes
``lengths`` and does that first.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
from jax import lax

__all__ = ["mask_steps", "selective_scan_ref", "selective_scan_chunked",
           "selective_step"]


def mask_steps(dt: jnp.ndarray, lengths: Optional[jnp.ndarray]
               ) -> jnp.ndarray:
    """``dt`` (B, S, C) with the steps at positions ``>= lengths`` (B,)
    set to zero: a step of zero leaves the state as it was."""
    if lengths is None:
        return dt
    live = (jnp.arange(dt.shape[1], dtype=jnp.int32)[None, :]
            < lengths.astype(jnp.int32)[:, None])
    return jnp.where(live[:, :, None], dt, 0.0)


def _operands(x, dt, b, c, a, d, h0, lengths):
    """Everything in float32, the steps masked, the state a row."""
    f32 = jnp.float32
    dt = mask_steps(dt.astype(f32), lengths)
    if h0 is None:
        h0 = jnp.zeros((x.shape[0],) + a.shape, f32)
    return (x.astype(f32), dt, b.astype(f32), c.astype(f32), a.astype(f32),
            d.astype(f32), h0.astype(f32))


def selective_step(x: jnp.ndarray, dt: jnp.ndarray, b: jnp.ndarray,
                   c: jnp.ndarray, a: jnp.ndarray, d: jnp.ndarray,
                   h: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token a row: x, dt (B, C); b, c (B, N); a (N, C); d (C,);
    h (B, N, C) float32. Returns (y (B, C) float32, the new h)."""
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    h = (jnp.exp(dt[:, None, :] * a.astype(f32)[None]) * h
         + b.astype(f32)[:, :, None] * (dt * x)[:, None, :])
    y = (h * c.astype(f32)[:, :, None]).sum(axis=1) + d.astype(f32) * x
    return y, h


def selective_scan_ref(x: jnp.ndarray, dt: jnp.ndarray, b: jnp.ndarray,
                       c: jnp.ndarray, a: jnp.ndarray, d: jnp.ndarray,
                       h0: Optional[jnp.ndarray] = None,
                       lengths: Optional[jnp.ndarray] = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x, dt (B, S, C); b, c (B, S, N); a (N, C); d (C,); h0 (B, N, C)
    or None (zeros); lengths (B,) or None. Returns (y (B, S, C) float32,
    the state after the last live token (B, N, C) float32). Token by
    token."""
    x, dt, b, c, a, d, h0 = _operands(x, dt, b, c, a, d, h0, lengths)

    def one(h, step):
        y, h = selective_step(*step, a, d, h)
        return h, y

    h, y = lax.scan(one, h0, tuple(jnp.moveaxis(v, 1, 0)
                                   for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), h


def selective_scan_chunked(x: jnp.ndarray, dt: jnp.ndarray, b: jnp.ndarray,
                           c: jnp.ndarray, a: jnp.ndarray, d: jnp.ndarray,
                           h0: Optional[jnp.ndarray] = None,
                           lengths: Optional[jnp.ndarray] = None,
                           chunk: int = 64
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``selective_scan_ref``'s operands and results. A ``lax.scan`` over
    chunks of ``chunk`` tokens carries ``h``; inside a chunk the pairs
    (decay, input) compose associatively, ``(p1, q1) then (p2, q2) =
    (p1 p2, p2 q1 + q2)``, and the state at each token is the chunk's
    running pair applied to the carried ``h``."""
    x, dt, b, c, a, d, h0 = _operands(x, dt, b, c, a, d, h0, lengths)
    batch, seq_len, _ = x.shape
    chunk = min(chunk, seq_len)
    pad = (-seq_len) % chunk
    if pad:          # steps of zero behind the end leave the state alone
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                       for v in (x, dt, b, c))

    def chunks(v):   # (B, S, W) -> (S / chunk, chunk, B, W)
        return jnp.moveaxis(v, 1, 0).reshape(-1, chunk, batch, v.shape[-1])

    def combine(first, second):
        return (first[0] * second[0], second[0] * first[1] + second[1])

    def one(h, step):
        x, dt, b, c = step                              # (chunk, B, .)
        decay = jnp.exp(dt[:, :, None, :] * a)          # (chunk, B, N, C)
        fed = b[:, :, :, None] * (dt * x)[:, :, None, :]
        decays, feds = lax.associative_scan(combine, (decay, fed), axis=0)
        states = decays * h[None] + feds
        y = (states * c[:, :, :, None]).sum(axis=2) + d * x
        return states[-1], y

    h, y = lax.scan(one, h0, tuple(chunks(v) for v in (x, dt, b, c)))
    y = jnp.moveaxis(y.reshape(-1, batch, y.shape[-1]), 0, 1)
    return y[:, :seq_len], h
