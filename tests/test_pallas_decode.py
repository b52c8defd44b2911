"""Pallas decode-attention kernel numerics vs the dense reference
(interpret mode on CPU), across fill levels, GQA groupings, and the
zero-length fresh-slot edge."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ops.attention import decode_attention_cached
from gofr_tpu.ops.pallas import flash_decode_attention


def _rand(key, *shape):
    return jax.random.normal(key, shape, jnp.float32) * 0.3


@pytest.mark.parametrize("q_heads,kv_heads", [(4, 4), (8, 2)])
@pytest.mark.parametrize("fills", [[0, 1, 64, 200], [128, 512, 37, 300]])
def test_kernel_matches_dense(q_heads, kv_heads, fills):
    batch, t_max, head_dim = 4, 512, 128
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q = _rand(keys[0], batch, 1, q_heads, head_dim)
    k_cache = _rand(keys[1], batch, t_max, kv_heads, head_dim)
    v_cache = _rand(keys[2], batch, t_max, kv_heads, head_dim)
    k_new = _rand(keys[3], batch, kv_heads, head_dim)
    v_new = _rand(keys[4], batch, kv_heads, head_dim)
    cache_len = jnp.asarray(fills, jnp.int32)

    dense = decode_attention_cached(q, k_cache, v_cache, k_new, v_new,
                                    cache_len)
    kernel = flash_decode_attention(q, k_cache, v_cache, k_new, v_new,
                                    cache_len, interpret=True)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


def test_untileable_shapes_run_the_kernel_and_llama_picks_dense():
    """The entry point is the kernel on every geometry (interpreted
    here); the model selects it only where Mosaic can tile."""
    from gofr_tpu.models import llama
    from gofr_tpu.models.llama import _flash_decode

    batch, t_max, heads, head_dim = 2, 32, 4, 16   # tiny preset geometry
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    q = _rand(keys[0], batch, 1, heads, head_dim)
    k_cache = _rand(keys[1], batch, t_max, heads, head_dim)
    v_cache = _rand(keys[2], batch, t_max, heads, head_dim)
    k_new = _rand(keys[3], batch, heads, head_dim)
    v_new = _rand(keys[4], batch, heads, head_dim)
    cache_len = jnp.asarray([0, 17], jnp.int32)
    args = (q, k_cache, v_cache, k_new, v_new, cache_len)
    assert "pallas_call" in str(jax.make_jaxpr(flash_decode_attention)(*args))
    out = flash_decode_attention(*args)
    ref = decode_attention_cached(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert not _flash_decode(
        llama.config("tiny", use_flash_decode=True), t_max)
    assert _flash_decode(llama.config("7b", use_flash_decode=True), 512)
    assert not _flash_decode(llama.config("7b"), 512)
