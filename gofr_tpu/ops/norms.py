"""Normalisation ops, written MXU/VPU-friendly.

No reference analog (hxzhouh/gofr is a Go microservice framework); these
exist for the north-star model serving path (BASELINE.json). Design rules:
accumulate statistics in fp32 regardless of activation dtype (bf16 on TPU),
return in the input dtype so surrounding matmuls stay bf16 on the MXU, and
keep everything shape-static so XLA fuses the whole norm into neighbouring
elementwise/matmul ops.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm (Llama-family). fp32 accumulation, cast back to x.dtype."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = x32 * (1.0 / jnp.sqrt(var + eps))
    return (normed * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray,
               bias: Optional[jnp.ndarray] = None,
               eps: float = 1e-12) -> jnp.ndarray:
    """LayerNorm (BERT-family; without ``bias`` Cohere's). fp32
    accumulation, cast back to x.dtype."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    normed = (x32 - mean) * (1.0 / jnp.sqrt(var + eps))
    out = normed * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(dtype)
