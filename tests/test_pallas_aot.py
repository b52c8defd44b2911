"""Every Pallas kernel compiles for the real target — without a chip.

The kernels' CPU tests run the Pallas interpreter, which accepts programs
Mosaic refuses (PR 14's ragged kernel passed every interpret-mode test and
could not be lowered for a TPU at all). libtpu can compile for a topology
it is not attached to, so this AOT-compiles each kernel for ``v5e:2x2``
with ``interpret=False`` at the two serving geometries, Llama-2-7B MHA
(32:32) and Llama-3-8B GQA (32:8), head_dim 128. It proves compilation
only; numerics on the chip are ``chip_smoke.py``'s kernels phase.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.models import llama
from gofr_tpu.ops.pallas import (flash_attention, flash_decode_attention,
                                 flash_tileable, decode_shapes_tileable,
                                 ragged_paged_decode_attention,
                                 ragged_paged_verify_attention,
                                 ragged_tileable)

HEAD_DIM, PAGE, SLOTS, PAGES_PER_SLOT, NUM_PAGES = 128, 32, 2, 2, 8
GEOMETRIES = [(32, 32), (32, 8)]


@pytest.fixture(scope="module")
def v5e():
    """One device of a detached v5e 2x2 topology, as a sharding."""
    try:
        from jax.experimental import topologies
        topology = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no libtpu, or it cannot
        pytest.skip(f"get_topology_desc unavailable: {exc!r}")
    return jax.sharding.SingleDeviceSharding(topology.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile()


def _paged_shapes(q_heads, kv_heads, g_len, int8):
    bf16 = jnp.bfloat16
    new = ((SLOTS, kv_heads, HEAD_DIM) if g_len == 1
           else (SLOTS, g_len, kv_heads, HEAD_DIM))
    pool = ((NUM_PAGES, PAGE, kv_heads, HEAD_DIM),
            jnp.int8 if int8 else bf16)
    shapes = [((SLOTS, g_len, q_heads, HEAD_DIM), bf16), pool, pool,
              ((SLOTS, PAGES_PER_SLOT), jnp.int32), (new, bf16),
              (new, bf16), ((SLOTS,), jnp.int32)]
    if int8:
        shapes += [((NUM_PAGES, PAGE, kv_heads), jnp.float32)] * 2
    return shapes


@pytest.mark.parametrize("q_heads,kv_heads", GEOMETRIES)
def test_flash_attention_compiles_for_v5e(v5e, q_heads, kv_heads):
    assert flash_tileable(1024, HEAD_DIM)
    bf16 = jnp.bfloat16
    _compile(functools.partial(flash_attention, interpret=False), v5e,
             ((1, 1024, q_heads, HEAD_DIM), bf16),
             ((1, 1024, kv_heads, HEAD_DIM), bf16),
             ((1, 1024, kv_heads, HEAD_DIM), bf16))


@pytest.mark.parametrize("q_heads,kv_heads", GEOMETRIES)
def test_flash_decode_compiles_for_v5e(v5e, q_heads, kv_heads):
    assert decode_shapes_tileable(256, 128, HEAD_DIM, q_heads)
    bf16 = jnp.bfloat16
    _compile(functools.partial(flash_decode_attention, interpret=False),
             v5e, ((SLOTS, 1, q_heads, HEAD_DIM), bf16),
             ((SLOTS, 256, kv_heads, HEAD_DIM), bf16),
             ((SLOTS, 256, kv_heads, HEAD_DIM), bf16),
             ((SLOTS, kv_heads, HEAD_DIM), bf16),
             ((SLOTS, kv_heads, HEAD_DIM), bf16), ((SLOTS,), jnp.int32))


@pytest.mark.parametrize("q_heads,kv_heads", GEOMETRIES)
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_ragged_decode_compiles_for_v5e(v5e, q_heads, kv_heads, int8):
    assert ragged_tileable(HEAD_DIM, q_heads, kv_heads, PAGE)
    _compile(functools.partial(ragged_paged_decode_attention,
                               interpret=False),
             v5e, *_paged_shapes(q_heads, kv_heads, 1, int8))


@pytest.mark.parametrize("q_heads,kv_heads", GEOMETRIES)
def test_ragged_verify_compiles_for_v5e(v5e, q_heads, kv_heads):
    _compile(functools.partial(ragged_paged_verify_attention,
                               interpret=False),
             v5e, *_paged_shapes(q_heads, kv_heads, 5, False))


def test_default_interpret_follows_the_lowering_target(v5e):
    """``interpret=None`` inside the model's paged decode step: lowered
    for a TPU from this CPU process it must hold the Mosaic kernel, not
    an inlined interpreter — the way a first chipless attempt "compiled"
    ``ragged=True`` and proved nothing."""
    cfg = llama.config("llama3-8b", n_layers=1, max_seq_len=256)
    params = jax.eval_shape(lambda: llama.init_int8(cfg))
    pool_shape = (cfg.n_layers, NUM_PAGES, PAGE, cfg.n_kv_heads,
                  cfg.head_dim)
    pool = {"k": jax.ShapeDtypeStruct(pool_shape, cfg.dtype),
            "v": jax.ShapeDtypeStruct(pool_shape, cfg.dtype)}
    abstract = (params, jax.ShapeDtypeStruct((SLOTS,), jnp.int32), pool,
                jax.ShapeDtypeStruct((SLOTS, PAGES_PER_SLOT), jnp.int32),
                jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
                jax.ShapeDtypeStruct((SLOTS,), jnp.bool_))
    abstract = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=v5e), abstract)

    def step(params, token, pool, table, cache_len, active):
        return llama.decode_step_paged(params, cfg, token, pool, table,
                                       cache_len, active, ragged=True)
    compiled = jax.jit(step).lower(*abstract).compile()
    assert "tpu_custom_call" in compiled.as_text()
