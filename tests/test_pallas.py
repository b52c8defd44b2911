"""Pallas flash-attention kernel tests (interpret mode on CPU). The entry
point is the kernel on every shape; models/llama picks dense attention
for shapes Mosaic cannot tile, and says so."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import llama
from gofr_tpu.ops import attention, banded_attention, prefill_attention
from gofr_tpu.ops.pallas import flash_attention


def _qkv(seq, q_heads=4, kv_heads=2, dim=128, batch=2):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (batch, seq, q_heads, dim))
    k = jax.random.normal(keys[1], (batch, seq, kv_heads, dim))
    v = jax.random.normal(keys[2], (batch, seq, kv_heads, dim))
    return q, k, v


def test_flash_matches_dense_causal():
    q, k, v = _qkv(256)
    ref = prefill_attention(q, k, v)
    out = flash_attention(q, k, v, interpret=True, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_matches_dense_noncausal():
    q, k, v = _qkv(256)
    ref = attention(q, k, v)
    out = flash_attention(q, k, v, causal=False, interpret=True,
                          block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_uneven_blocks():
    """block_q != block_k exercises the causal block-skip boundary."""
    q, k, v = _qkv(512)
    ref = prefill_attention(q, k, v)
    out = flash_attention(q, k, v, interpret=True, block_q=128, block_k=256)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    out = flash_attention(q, k, v, interpret=True, block_q=256, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_mha_no_gqa():
    q, k, v = _qkv(128, q_heads=2, kv_heads=2)
    ref = prefill_attention(q, k, v)
    out = flash_attention(q, k, v, interpret=True, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("window", [64, 128, 200, 384, 1000])
@pytest.mark.parametrize("block_q,block_k", [(128, 128), (256, 128),
                                             (128, 256)])
def test_flash_with_a_window_equals_the_banded_oracle(window, block_q,
                                                      block_k):
    """Query t attends t - window < s <= t: K blocks wholly behind the
    window are skipped like those after the diagonal, the block at each
    edge is masked, the blocks wholly inside are not. GQA 4:1, windows
    narrower than a block, of a whole number of blocks, across blocks
    and wider than the prompt."""
    q, k, v = _qkv(512, q_heads=8, kv_heads=2)
    ref = banded_attention(q, k, v, window, block=128)
    out = flash_attention(q, k, v, window=window, interpret=True,
                          block_q=block_q, block_k=block_k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_in_bfloat16_runs_its_products_in_bfloat16():
    """bfloat16 operands reach the two products as they are (the MXU's
    own dtype; float32 sums), the softmax weights rounded to V's dtype
    for the second: what ops.banded_attention does, so the two agree to
    bfloat16's last bits at 16 query heads a KV head."""
    q, k, v = (x.astype(jnp.bfloat16)
               for x in _qkv(512, q_heads=16, kv_heads=1, batch=1))
    ref = banded_attention(q, k, v, 192, block=128).astype(jnp.float32)
    out = flash_attention(q, k, v, window=192, interpret=True, block_q=128,
                          block_k=128).astype(jnp.float32)
    assert float(jnp.abs(out - ref).max()) <= 2.0 ** -7 * float(
        jnp.abs(ref).max())


def test_flash_window_needs_causal():
    q, k, v = _qkv(128)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=64)


def test_flash_small_shapes_run_the_kernel():
    """head_dim 32 / seq 16 tile nowhere; the entry point still runs the
    kernel (blocks clamp to S), never a quiet dense substitute."""
    from gofr_tpu.ops.pallas import flash_tileable

    assert not flash_tileable(16, 32)
    q, k, v = _qkv(16, dim=32)
    assert "pallas_call" in str(jax.make_jaxpr(flash_attention)(q, k, v))
    ref = prefill_attention(q, k, v)
    out = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_rejects_ragged_blocks():
    q, k, v = _qkv(640)            # 640 = 512 + 128: no whole 512-blocks
    with pytest.raises(ValueError, match="does not split"):
        flash_attention(q, k, v)


def test_llama_dense_for_untileable_is_never_silent():
    """``use_flash`` on a shape Mosaic cannot tile takes dense attention
    — which at long S is an opaque 16 GB OOM (r5, measured on v5e) — so
    the model says so at trace time, at every size."""
    cfg = llama.config("tiny", use_flash=True, max_seq_len=8192)
    params = jax.eval_shape(lambda: llama.init(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    with pytest.warns(UserWarning, match="DENSE attention"):
        jax.eval_shape(lambda p, t: llama.forward(p, cfg, t), params, tokens)


def test_llama_use_flash_config():
    """tiny preset (head_dim 16) does not tile, so the model runs dense
    attention — forward must be identical with the flag on."""
    cfg = llama.config("tiny")
    cfg_flash = llama.config("tiny", use_flash=True)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.ones((1, 8), jnp.int32)
    ref = llama.forward(params, cfg, tokens)
    with pytest.warns(UserWarning, match="DENSE attention"):
        out = llama.forward(params, cfg_flash, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
