"""Rotary position embeddings (RoPE) for the Llama serving path.

TPU-first details: the cos/sin tables are precomputed once per max length
(static shape, lives in HBM alongside weights) and gathered with a static
slice or integer positions — no dynamic shapes under jit. Rotation is done
in fp32 then cast back so bf16 Q/K keep precision at long context.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def rope_table(max_len: int, head_dim: int, theta: float = 10000.0):
    """Precompute (max_len, head_dim/2) cos/sin tables in fp32."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                                / head_dim))
    positions = jnp.arange(max_len, dtype=jnp.float32)
    angles = jnp.outer(positions, inv_freq)          # (max_len, head_dim/2)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               positions: jnp.ndarray,
               interleaved: bool = False) -> jnp.ndarray:
    """Rotate ``x`` of shape (batch, seq, heads, head_dim).

    ``positions`` is (batch, seq) int32 — absolute positions, so the same
    function serves prefill (0..S-1) and single-token decode (cache_len).
    The pairing is half-split (dimension ``i`` with ``i + D/2``: Llama,
    NeoX) or, with ``interleaved``, of neighbours (``2i`` with ``2i +
    1``: GPT-J, Cohere's ``rope_gptj``); frequency ``i`` turns pair
    ``i`` in both.
    """
    dtype = x.dtype
    cos_g = cos[positions][:, :, None, :]            # (B, S, 1, D/2)
    sin_g = sin[positions][:, :, None, :]
    x32 = x.astype(jnp.float32)
    if interleaved:
        # each dimension's partner (-x[2i+1] for 2i, x[2i] for 2i+1)
        # through a product with a signed permutation: one term a sum,
        # so exact, and the rotation fuses into the product's result.
        # Not a (..., D/2, 2) reshape: XLA moved that reshape onto the
        # query weights and copied all of wq a layer a decode step (0.52
        # ms a sliding layer); and not two rolls of the minor dimension:
        # a prefill's float32 copies of q, 0.5 GB each at 8192 tokens of
        # 128 heads, were 3 GB of its temporaries and an eighth of its
        # time (PERF.md, PR 31)
        cos_g, sin_g = (jnp.repeat(t, 2, axis=-1) for t in (cos_g, sin_g))
        at = jnp.arange(x.shape[-1])
        even = at % 2 == 0
        swap = ((at[:, None] == jnp.where(even, at + 1, at - 1)[None, :])
                * jnp.where(even, -1.0, 1.0)[None, :]).astype(dtype)
        partner = jnp.einsum("...d,de->...e", x, swap,
                             precision=lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
        return (x32 * cos_g + partner * sin_g).astype(dtype)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    rotated = jnp.concatenate(
        [x1 * cos_g - x2 * sin_g, x2 * cos_g + x1 * sin_g], axis=-1)
    return rotated.astype(dtype)
