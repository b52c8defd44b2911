"""Every Pallas kernel compiles for the real target — without a chip.

The kernels' CPU tests run the Pallas interpreter, which accepts programs
Mosaic refuses (PR 14's ragged kernel passed every interpret-mode test and
could not be lowered for a TPU at all). libtpu can compile for a topology
it is not attached to, so this AOT-compiles each kernel for ``v5e:2x2``
with ``interpret=False`` at the two serving geometries, Llama-2-7B MHA
(32:32) and Llama-3-8B GQA (32:8), head_dim 128. It proves compilation
only; numerics on the chip are ``chip_smoke.py``'s kernels phase.

The ragged kernel is also compiled at the call shape of the benchmark's
``mistral7b.batch`` cell (16 slots, a table of 64 columns, page 32), where
its page walk has its real sizes: the ring of page copies and the kept
scores must fit the chip's scoped VMEM, which the compiler checks and
the test's arithmetic repeats.

The last tests compile what the engine really runs around the ragged
kernel — its fused decode tick and its verify step, pool donated — and
read the optimised HLO: the pool must reach the kernel as the layer
scan's carry itself, with no per-layer plane and no copy of the pool
materialised (PR 26: two such planes cost 8 ms of a 35 ms decode step),
and the layer body must hold the kernel exactly once (the benchmark
counts decode steps as kernel calls over layers). The same tick at
``mistral7b.batch``'s widths, given the weights in the layouts the
engine puts them into at start (``generate.weight_formats``), must not
re-lay a stacked weight when it is called (PR 30: three whole stacks
were transposed once a tick, 0.74 ms of a 13.3 ms step), and the cell's
largest prefill must take the same leaves as they are.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.models import llama
from gofr_tpu.ops.pallas import (flash_attention, flash_tileable,
                                 ragged_paged_decode_attention,
                                 ragged_paged_verify_attention,
                                 ragged_tileable)
from gofr_tpu.ops.pallas.ragged_paged_attention import walk_sizes
from gofr_tpu.tpu.generate import weight_formats

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


def _on_chip(tree, sharding):
    """A tree of shapes, each placed on the described chip."""
    return jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=sharding), tree)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile()


def _paged_shapes(q_heads, kv_heads, g_len, int8, slots=SLOTS,
                  pages_per_slot=PAGES_PER_SLOT, num_pages=NUM_PAGES,
                  layers=1):
    bf16 = jnp.bfloat16
    new = ((slots, kv_heads, HEAD_DIM) if g_len == 1
           else (slots, g_len, kv_heads, HEAD_DIM))
    pool = ((layers, num_pages, PAGE, kv_heads, HEAD_DIM),
            jnp.int8 if int8 else bf16)
    shapes = [((slots, g_len, q_heads, HEAD_DIM), bf16), pool, pool,
              ((slots, pages_per_slot), jnp.int32), (new, bf16),
              (new, bf16), ((slots,), jnp.int32), ((), jnp.int32)]
    if int8:
        shapes += [((layers, num_pages, PAGE, kv_heads), jnp.float32)] * 2
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


# the benchmark's mistral7b.batch call: 16 slots, 64 table columns of page
# 32 (max_len 2048), a pool of about a thousand pages a layer
CELL_SLOTS, CELL_COLUMNS, CELL_PAGES, SCOPED_VMEM = 16, 64, 1024, 16 << 20
CELL_CALLS = {
    "gqa-bf16": (ragged_paged_decode_attention, 32, 8, 1, False),
    "gqa-int8": (ragged_paged_decode_attention, 32, 8, 1, True),
    "mha-bf16": (ragged_paged_decode_attention, 32, 32, 1, False),
    "verify-gqa": (ragged_paged_verify_attention, 32, 8, 5, False),
    "verify-mha": (ragged_paged_verify_attention, 32, 32, 5, False),
}


@pytest.mark.parametrize("case", CELL_CALLS)
def test_ragged_compiles_at_the_cells_call_shape(v5e, case):
    """The page walk at its real sizes. Mosaic refuses a kernel whose
    scratch passes the scoped VMEM limit, so the compile is the check;
    the arithmetic says what the scratch is: the ring of page copies,
    the masked scores a row a token (kept in every case here, so K
    leaves HBM once: a column is a token, not a token a KV head), the
    scale rows of an int8 pool, and the new tokens' constant mask the
    pipeline double-buffers."""
    kernel, q_heads, kv_heads, g_len, int8 = CELL_CALLS[case]
    rows_all, cols = q_heads * g_len, PAGE * kv_heads
    sizes = walk_sizes(PAGE, kv_heads, HEAD_DIM, rows_all,
                       1 if int8 else 2, CELL_COLUMNS)
    ring_pages = sizes.ring_blocks * sizes.block_pages
    ring = ring_pages * cols * HEAD_DIM * (1 if int8 else 2)
    blocks = -(-CELL_COLUMNS // sizes.block_pages)
    scores = rows_all * blocks * max(sizes.block_pages * PAGE, 128) * 4
    scratch = (ring + scores + (ring_pages * cols * 4 if int8 else 0)
               + 2 * rows_all * kv_heads * g_len * 4)
    assert 2 <= sizes.ring_blocks
    assert sizes.keep_scores, (case, scores)
    assert scratch < SCOPED_VMEM, (case, scratch)
    _compile(functools.partial(kernel, interpret=False), v5e,
             *_paged_shapes(q_heads, kv_heads, g_len, int8,
                            slots=CELL_SLOTS, pages_per_slot=CELL_COLUMNS,
                            num_pages=CELL_PAGES, layers=2))


def _compile_paged_step(step, sharding, n_layers, num_pages, token_shape,
                        donate=(), kv_int8=False):
    """Compile ``step(params, cfg, token, pool, table, cache_len,
    active)`` for v5e at Llama-3-8B widths (GQA 32:8), int8 weights and
    a pool of ``num_pages`` pages. Returns (optimised HLO text, K/V pool
    leaf shape)."""
    cfg = llama.config("llama3-8b", n_layers=n_layers, max_seq_len=256,
                       kv_int8=kv_int8)
    params = jax.eval_shape(lambda: llama.init_int8(cfg))
    pool_shape = (cfg.n_layers, num_pages, PAGE, cfg.n_kv_heads,
                  cfg.head_dim)
    kv = jax.ShapeDtypeStruct(pool_shape, jnp.int8 if kv_int8 else cfg.dtype)
    pool = {"k": kv, "v": kv}
    if kv_int8:
        scale = jax.ShapeDtypeStruct(pool_shape[:-1], jnp.float32)
        pool.update(ks=scale, vs=scale)
    abstract = (params, jax.ShapeDtypeStruct(token_shape, jnp.int32), pool,
                jax.ShapeDtypeStruct((SLOTS, PAGES_PER_SLOT), jnp.int32),
                jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
                jax.ShapeDtypeStruct((SLOTS,), jnp.bool_))
    abstract = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=sharding), abstract)
    compiled = jax.jit(
        lambda params, *rest: step(params, cfg, *rest),
        donate_argnums=donate).lower(*abstract).compile()
    return compiled.as_text(), pool_shape


def test_default_interpret_follows_the_lowering_target(v5e):
    """``interpret=None`` inside the model's paged decode step: lowered
    for a TPU from this CPU process it must hold the Mosaic kernel, not
    an inlined interpreter — the way a first chipless attempt "compiled"
    ``ragged=True`` and proved nothing."""
    hlo, _ = _compile_paged_step(
        functools.partial(llama.decode_step_paged, ragged=True), v5e,
        n_layers=1, num_pages=NUM_PAGES, token_shape=(SLOTS,))
    assert "tpu_custom_call" in hlo


def _decode_tick(params, cfg, token, pool, table, cache_len, active):
    """The engine's fused greedy tick (GenerationEngine's paged
    ``decode_k``): a scan of K=4 ragged decode steps."""
    def one(carry, _):
        token, pool, cache_len = carry
        logits, pool, new_len = llama.decode_step_paged(
            params, cfg, token, pool, table, cache_len, active,
            ragged=True)
        next_token = logits.argmax(axis=-1).astype(token.dtype)
        return (jnp.where(active, next_token, token), pool,
                jnp.where(active, new_len, cache_len)), next_token

    (_, pool, cache_len), tokens = jax.lax.scan(
        one, (token, pool, cache_len), None, length=4)
    return tokens, pool, cache_len


# 11 pages: no other tensor of these programs has a leading 11, so the
# plane's and the pool's shapes below can only be the pool's
POOL_READ_IN_PLACE = {
    "decode-tick": (_decode_tick, (SLOTS,), (2, 4), False),
    "decode-tick-int8": (_decode_tick, (SLOTS,), (2, 4), True),
    "verify": (functools.partial(llama.verify_step_paged, ragged=True),
               (SLOTS, 5), (2,), False),
}


@functools.lru_cache(maxsize=None)
def _engine_program(sharding, case):
    step, token_shape, donate, kv_int8 = POOL_READ_IN_PLACE[case]
    return _compile_paged_step(
        step, sharding, n_layers=2, num_pages=11, token_shape=token_shape,
        donate=donate, kv_int8=kv_int8)


@pytest.mark.parametrize("case", POOL_READ_IN_PLACE)
def test_layer_body_holds_the_kernel_exactly_once(v5e, case):
    """One ``tpu_custom_call`` a layer a step and none elsewhere in the
    program: the benchmark counts decode steps as the trace's
    ``tpu_custom_call`` events over ``num_hidden_layers``
    (``benchmark/layer_metrics/decode_step_ms.json``), so a kernel split
    in two calls would halve the step time it reports. The layer scan's
    body is compiled once, whatever the layer count and the tick's K, so
    the program's text holds the call once."""
    hlo, _ = _engine_program(v5e, case)
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("case", POOL_READ_IN_PLACE)
def test_ragged_step_reads_the_stacked_pool_in_place(v5e, case):
    """A pallas_call is opaque to XLA, so a layer slice taken outside
    the kernel is materialised: one layer's plane of the whole pool, per
    leaf, per layer, per step. The kernel picks the layer in its index
    maps instead; this holds the compiled program to it. Nothing may
    have the plane's shape, and the kernel must read the pool before the
    layer's in-place append without XLA copying the pool to keep the two
    apart (``copy(`` alone: a pool this small is also prefetched whole
    into fast memory, copy-start / copy-done, which the real one never
    fits). On an int8 pool the scale leaves must NOT reach the kernel
    whole: XLA carries them in a layout of its own (their minor
    dimension is Hkv), and a custom call handed a whole leaf has it
    re-laid out, padded sixteenfold, every layer — so the kernel's
    wrapper slices those (1/128 of a K plane)."""
    hlo, pool_shape = _engine_program(v5e, case)

    def dims(shape):
        return "[" + ",".join(map(str, shape)) + "]"

    def results(shape, opcode=""):
        return re.findall(rf"^.*= \w+{re.escape(dims(shape))}\S* {opcode}.*$",
                          hlo, re.M)

    kernel, = re.findall(r"^.*custom_call_target=\"tpu_custom_call\".*$",
                         hlo, re.M)
    assert dims(pool_shape) in kernel
    assert dims(pool_shape[:-1]) + "{" not in kernel
    planes = results(pool_shape[1:])
    assert not planes, planes[:2]
    copies = results(pool_shape, r"copy\(")
    assert not copies, copies[:2]


# -- the weights' layouts (GenerationEngine._lay_out_weights) ---------------------

@functools.lru_cache(maxsize=None)
def _cell_engine(sharding):
    """``mistral7b.batch``'s engine on shapes alone: Mistral-7B widths,
    all 32 layers, int8 weights, 16 slots, a table of 64 columns over a
    pool of about a thousand pages. Returns (cfg, the weights, the
    steady tick's other operands, the formats ``weight_formats`` picks
    for the weights, the deciding compile's temporaries)."""
    cfg = llama.config("llama3-8b", vocab_size=32768, max_seq_len=2048,
                       rope_theta=1e6)

    def on_chip(tree):
        return jax.tree.map(
            lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                              sharding=sharding), tree)

    kv = jax.ShapeDtypeStruct(
        (cfg.n_layers, 1100, PAGE, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
    params = on_chip(jax.eval_shape(lambda: llama.init_int8(cfg)))
    rest = on_chip((
        jax.ShapeDtypeStruct((CELL_SLOTS,), jnp.int32), {"k": kv, "v": kv},
        jax.ShapeDtypeStruct((CELL_SLOTS, CELL_COLUMNS), jnp.int32),
        jax.ShapeDtypeStruct((CELL_SLOTS,), jnp.int32),
        jax.ShapeDtypeStruct((CELL_SLOTS,), jnp.bool_)))
    formats, temp_bytes, _ = weight_formats(
        lambda params, *rest: _decode_tick(params, cfg, *rest),
        (params,) + rest, donate_argnums=(2, 4))
    return cfg, params, rest, formats, temp_bytes


def _copied_stacks(hlo, cfg):
    """``copy`` instructions whose result is a whole stacked int8
    weight: ``s8[n_layers, ., .]``."""
    return re.findall(rf"^.*= s8\[{cfg.n_layers},\d+,\d+\]\S* copy\(.*$",
                      hlo, re.M)


def test_steady_tick_reads_the_stacked_weights_where_they_lie(v5e):
    """An executable's parameter layouts are fixed when it is compiled,
    and the layer loop wants ``wq`` / ``wk`` / ``wv`` with the input
    dimension minor: handed the default layouts it transposes all 32
    layers of all three at every call (805 MB in, 805 MB out, 811 MB of
    temporaries). Handed the formats the engine's helper picks, the
    steady tick holds no copy of a whole stacked weight and a few MB of
    temporaries."""
    cfg, params, rest, formats, temp_bytes = _cell_engine(v5e)
    assert temp_bytes < 64 << 20
    moved = [fmt for leaf, fmt in zip(
        jax.tree.leaves(params),
        jax.tree.structure(params).flatten_up_to(formats))
        if fmt.layout.major_to_minor != tuple(range(len(leaf.shape)))]
    assert moved, "the compiler asks for the default layouts"
    compiled = jax.jit(
        lambda params, *rest: _decode_tick(params, cfg, *rest),
        in_shardings=(formats,) + (None,) * len(rest),
        donate_argnums=(2, 4)).lower(params, *rest).compile()
    copies = _copied_stacks(compiled.as_text(), cfg)
    assert not copies, copies[:3]
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_prefill_takes_the_ticks_weight_layouts_as_they_are(v5e):
    """The steady tick decides the layouts alone and every other
    program takes what it is given: the cell's largest admission group
    (16 x 1024), compiled for the same leaves, copies no int8 tensor of
    any size."""
    cfg, params, _, formats, _ = _cell_engine(v5e)
    rows, bucket = CELL_SLOTS, 1024

    def prefill(params, tokens, lengths):
        logits, small, _ = llama.prefill(
            params, cfg, tokens, llama.init_cache(cfg, rows, bucket),
            lengths=lengths)
        return logits.argmax(axis=-1), small

    compiled = jax.jit(prefill, in_shardings=(formats, None, None)).lower(
        params,
        jax.ShapeDtypeStruct((rows, bucket), jnp.int32, sharding=v5e),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=v5e)).compile()
    copies = re.findall(r"^.*= s8\[[\d,]+\]\S* copy\(.*$",
                        compiled.as_text(), re.M)
    assert not copies, copies[:3]


# -- the window kind's lower bound, GQA 128:8 (ISSUE 31) ----------------------

# the benchmark's command-a-plus-ep8.mixed call: 32 slots, 288 table
# columns of page 32 (max_len 9216), 128 query heads over 8 KV heads
CMDA_SLOTS, CMDA_COLUMNS, CMDA_HEADS, CMDA_KV = 32, 288, 128, 8
# what the cell's kernel metrics find the call by
# (benchmark/layer_metrics/*.cmda.json)
CMDA_RESULT = r"bf16\[32,128,128\]\S* custom-call\("


@pytest.mark.parametrize("bounded", [True, False],
                         ids=["window-kind", "full-kind"])
def test_ragged_compiles_at_128_query_rows_with_and_without_a_bound(
        v5e, bounded):
    """Both kinds' calls of the window/global family at their real
    sizes: 16 query rows a KV head, so a product a head; the scores of
    all 288 columns are kept (4.7 MB) and K leaves HBM once; the bound
    is a fourth scalar-prefetch operand; the call's result is the type
    the cell's metrics find it by."""
    assert ragged_tileable(HEAD_DIM, CMDA_HEADS, CMDA_KV, PAGE)
    sizes = walk_sizes(PAGE, CMDA_KV, HEAD_DIM, CMDA_HEADS, 2, CMDA_COLUMNS)
    assert sizes.keep_scores and sizes.product_heads == 1
    assert sizes.ring_blocks >= 2
    shapes = _paged_shapes(CMDA_HEADS, CMDA_KV, 1, False, slots=CMDA_SLOTS,
                           pages_per_slot=CMDA_COLUMNS, num_pages=4224,
                           layers=3)

    def call(*ops):
        return ragged_paged_decode_attention(
            *ops[:8], interpret=False, start=ops[8] if bounded else None)

    hlo = _compile(call, v5e, *shapes, ((CMDA_SLOTS,), jnp.int32)).as_text()
    assert len(re.findall(CMDA_RESULT, hlo)) == 1
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("window", [4096, None],
                         ids=["window-kind", "full-kind"])
def test_flash_with_a_window_compiles_at_128_query_heads_for_v5e(v5e,
                                                                 window):
    """The prefill attention of the window/global family at the cell's
    longest served bucket: 128 query heads over 8 KV heads as column
    blocks of (B, S, H x D), the band's edge in the K/V index maps."""
    assert flash_tileable(6144, HEAD_DIM)
    bf16 = jnp.bfloat16
    _compile(functools.partial(flash_attention, window=window,
                               interpret=False), v5e,
             ((1, 6144, CMDA_HEADS, HEAD_DIM), bf16),
             ((1, 6144, CMDA_KV, HEAD_DIM), bf16),
             ((1, 6144, CMDA_KV, HEAD_DIM), bf16))


@pytest.mark.parametrize("seq,block", [(8192, 1024), (4608, 512)],
                         ids=["bucket-8192", "probe-4608"])
def test_flash_with_latent_widths_compiles_for_v5e(v5e, seq, block):
    """The prefill attention of the latent family as
    ``mla_moe.flash_expanded_attention`` calls it at Xing4.0's widths:
    32 heads, keys of 128 + 64 zero-padded to 256 lanes, values of 128,
    the scale the caller's. Keys of 192 as they are do not lower: a
    column block is a whole number of 128-lane tiles."""
    bf16 = jnp.bfloat16
    call = functools.partial(flash_attention, block_q=block, block_k=block,
                             sm_scale=0.144, interpret=False)
    compiled = _compile(call, v5e, ((1, seq, 32, 256), bf16),
                        ((1, seq, 32, 256), bf16), ((1, seq, 32, 128), bf16))
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        == 1
    with pytest.raises(Exception, match="divisible by 8 and 128"):
        _compile(call, v5e, ((1, seq, 32, 192), bf16),
                 ((1, seq, 32, 192), bf16), ((1, seq, 32, 128), bf16))


def test_window_and_global_kinds_decode_in_one_tick_for_v5e(v5e):
    """The steady tick of ``models/swa_moe.py`` at the published widths,
    one period: a pool and a page table a kind, both through the ragged
    kernel, the window kind's with its bound. The layer scan holds the
    kernel once a layer of the period, and no plane of either pool is
    copied."""
    from gofr_tpu.models import swa_moe

    cfg = swa_moe.config(
        "command-a-plus", n_layers=4, layer_types=swa_moe.SwaMoeConfig()
        .layer_types[:4], n_held_experts=16, vocab_size=32768,
        max_seq_len=9216)
    pages = {"window": 4224, "full": 9216}

    params = _on_chip(jax.eval_shape(
        lambda: swa_moe.init(cfg, jax.random.key(0))), v5e)
    pool = {kind: {name: jax.ShapeDtypeStruct(
        (spec["layers"], pages[kind], PAGE, *tail), dtype)
        for name, (tail, dtype) in spec["leaves"].items()}
        for kind, spec in swa_moe.cache_leaves(cfg).items()}
    rest = _on_chip((
        jax.ShapeDtypeStruct((CMDA_SLOTS,), jnp.int32), pool,
        {kind: jax.ShapeDtypeStruct((CMDA_SLOTS, CMDA_COLUMNS), jnp.int32)
         for kind in pool},
        jax.ShapeDtypeStruct((CMDA_SLOTS,), jnp.int32),
        jax.ShapeDtypeStruct((CMDA_SLOTS,), jnp.bool_)), v5e)

    def tick(params, token, pool, table, cache_len, active):
        logits, pool, cache_len, counted = swa_moe.decode_step_paged(
            params, cfg, token, pool, table, cache_len, active,
            ragged=True, counters=True)
        return logits.argmax(-1), pool, cache_len, counted

    compiled = jax.jit(tick, donate_argnums=(2, 4)).lower(
        params, *rest).compile()
    hlo = compiled.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          hlo)) == 4
    assert len(re.findall(CMDA_RESULT, hlo)) == 4
    for kind, layers in (("window", 3), ("full", 1)):
        plane = rf"bf16\[{layers},{pages[kind]},{PAGE},8,128\]\S* copy\("
        assert not re.findall(plane, hlo), kind
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# -- a latent prefill reads its routed experts where they lie (ISSUE 34) ------

def test_latent_prefill_copies_no_layers_experts_for_v5e(v5e):
    """``mla_moe.prefill`` at pangu-ultra-moe-ep16's widths (16 of 256
    experts held, hidden 7680, expert width 2048), a 1 x 512 group, one
    dense and two expert layers. The layer scan once handed the grouped
    product's block loop a slice of the expert stack, and the compiler
    made the slice a buffer: three fusions a layer whose result was a
    layer's leaf, 503 MB each. No operation's result is a layer's leaf
    now, and the whole stack is met only as it is given: a parameter
    or an element of a loop's tuple."""
    from gofr_tpu.models import mla_moe

    cfg = mla_moe.config("pangu-ultra-moe", n_layers=3, n_dense_layers=1,
                         n_held_experts=16, vocab_size=19200,
                         max_seq_len=2048)
    params = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=v5e),
        jax.eval_shape(lambda: mla_moe.init(cfg, jax.random.key(0))))
    rows, bucket = 1, 512

    def prefill(params, tokens, lengths):
        return mla_moe.prefill(params, cfg, tokens,
                               mla_moe.init_cache(cfg, rows, bucket),
                               lengths=lengths)

    hlo = jax.jit(prefill).lower(
        params,
        jax.ShapeDtypeStruct((rows, bucket), jnp.int32, sharding=v5e),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=v5e)
    ).compile().as_text()
    leaf = r"(?:2048,7680|7680,2048)\]"
    a_layers = re.findall(rf"^.*= bf16\[(?:1,)?16,{leaf}.*$", hlo, re.M)
    assert not a_layers, a_layers[:3]
    whole = re.findall(rf"^.*= bf16\[2,16,{leaf}\S* (\S+?)\(", hlo, re.M)
    assert whole and set(whole) <= {"parameter", "get-tuple-element"}, whole


# -- a decode step reads only the experts hit, where they lie (ISSUE 36) ------

def _expert_step(family):
    """(module, its config at the cell's widths with two expert layers,
    pool, page table, the step's keywords), shapes only."""
    from gofr_tpu.models import hc_mla_moe, mla_moe, swa_moe

    def shape(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype)

    if family == "cmda-two-periods":
        # two periods: the scan's slice of a stack is no longer the leaf;
        # 4 of 128 experts held, so that 8 layers fit a chip
        cfg = swa_moe.config(
            "command-a-plus", n_layers=8, layer_types=swa_moe.SwaMoeConfig()
            .layer_types[:8], n_held_experts=4, vocab_size=32768,
            max_seq_len=9216)
        pool = {kind: {name: shape(spec["layers"], 256, PAGE, *tail,
                                   dtype=dtype)
                       for name, (tail, dtype) in spec["leaves"].items()}
                for kind, spec in swa_moe.cache_leaves(cfg).items()}
        return (swa_moe, cfg, pool, {kind: shape(32, 8) for kind in pool},
                {"ragged": True})
    if family == "pangu":
        module, cfg = mla_moe, mla_moe.config(
            "pangu-ultra-moe", n_layers=3, n_dense_layers=1,
            n_held_experts=16, vocab_size=19200, max_seq_len=2048)
    else:
        module, cfg = hc_mla_moe, hc_mla_moe.config(
            "xing4", n_layers=3, n_dense_layers=1, max_seq_len=6656)
    pool = {"ckv": shape(cfg.n_layers, 256, PAGE, cfg.cache_row,
                         dtype=cfg.dtype)}
    return module, cfg, pool, shape(32, 8), {}


@pytest.mark.parametrize("family", ["pangu", "xing", "cmda-two-periods"])
def test_decode_steps_read_the_hit_experts_where_they_lie_for_v5e(v5e,
                                                                  family):
    """Each expert family's paged decode step at its cell's widths, two
    expert layers (two periods for the window family): the routed part
    is a loop over the experts hit, so the optimised HLO holds no
    operation whose result is a layer's experts (the layer scan's slice,
    which a loop's operand would make a copy every step), none whose
    result is a batched ``(experts, rows, width)`` product, and the
    whole stack only as it is given."""
    module, cfg, pool, table, kwargs = _expert_step(family)
    params = _on_chip(jax.eval_shape(
        lambda: module.init(cfg, jax.random.key(0))), v5e)
    slots = jax.tree.leaves(table)[0].shape[0]
    rest = _on_chip((jax.ShapeDtypeStruct((slots,), jnp.int32), pool, table,
                     jax.ShapeDtypeStruct((slots,), jnp.int32),
                     jax.ShapeDtypeStruct((slots,), jnp.bool_)), v5e)

    def step(params, token, pool, table, cache_len, active):
        return module.decode_step_paged(params, cfg, token, pool, table,
                                        cache_len, active, counters=True,
                                        **kwargs)

    hlo = jax.jit(step, donate_argnums=(2,)).lower(
        params, *rest).compile().as_text()
    held, d, f = cfg.n_held_experts, cfg.dim, cfg.moe_ffn_dim
    leaf = rf"(?:{d},{f}|{f},{d})\]"
    a_layers = re.findall(rf"^.*= bf16\[(?:1,)?{held},{leaf}.*$", hlo, re.M)
    assert not a_layers, a_layers[:3]
    whole = re.findall(rf"^.*= bf16\[2,{held},{leaf}\S* (\S+?)\(", hlo,
                       re.M)
    assert whole and set(whole) <= {"parameter", "get-tuple-element"}, whole
    batched = re.findall(
        rf"^.*= \w+\[{held},(?:{slots},(?:{d}|{f})|(?:{d}|{f}),{slots})\].*$",
        hlo, re.M)
    assert not batched, batched[:3]
    one = re.findall(rf"bf16\[1,1,{d},{f}\]\S* dynamic-slice\(", hlo)
    assert one, "no single expert is sliced from the stack"


# -- the selective scan and the per-slot state (models/jamba.py) ---------------------

@pytest.mark.parametrize("rows,bucket", [(4, 512), (1, 2048), (16, 128)])
def test_selective_scan_compiles_for_v5e(v5e, rows, bucket):
    """The scan kernel at Jamba2-3B's widths (5120 channels x 16 states)
    at three of ``jamba2-3b.chat``'s prefill shapes: Mosaic takes the
    128 x 128 transpose of the projections, the static lane slices of a
    token's columns and the unrolled walk; its name is the custom call's
    (a trace finds it by that)."""
    from gofr_tpu.ops.pallas import scan_tileable, selective_scan

    channels, states = 5120, 16
    assert scan_tileable(bucket, channels, states)
    f32 = jnp.float32
    compiled = _compile(
        functools.partial(selective_scan, interpret=False), v5e,
        ((rows, bucket, channels), jnp.bfloat16),
        ((rows, bucket, channels), f32), ((rows, bucket, states), f32),
        ((rows, bucket, states), f32), ((states, channels), f32),
        ((channels,), f32), ((rows, states, channels), f32),
        ((rows,), jnp.int32))
    kernel, = re.findall(r"^.*custom_call_target=\"tpu_custom_call\".*$",
                         compiled.as_text(), re.M)
    assert re.match(r"\s*(ROOT )?%selective_scan[.\d]* = ", kernel), kernel
    assert f"f32[{rows},{bucket},{channels}]" in kernel


def test_state_space_tick_updates_the_state_in_place_for_v5e(v5e):
    """``jamba2-3b.chat``'s fused decode tick on shapes alone: all 28
    layers, 128 slots, 12288 pages. The per-slot state is the tick's
    donated carry: the optimised HLO copies no array of a state leaf's
    shape and none of one layer's, the outputs alias the state and the
    pages, the temporaries are a few hundred MB (the gathered K/V views
    in float32), and a layer's weights reach their product out of the
    stack (no result has a layer's ``w_in`` shape but a slice fused
    into its product)."""
    from gofr_tpu.models import jamba

    cfg = jamba.config("jamba2_3b", max_seq_len=3072)
    slots, columns, pages, k_steps = 128, 96, 12288, 4
    params = _on_chip(jax.eval_shape(
        lambda key: jamba.init(cfg, key), jax.random.key(0)), v5e)
    leaves = jamba.cache_leaves(cfg)
    pool = {kind: {name: jax.ShapeDtypeStruct(
        (spec["layers"],) + ((slots,) if spec.get("per_slot")
                             else (pages, PAGE)) + tuple(shape), dtype)
        for name, (shape, dtype) in spec["leaves"].items()}
        for kind, spec in leaves.items()}
    rest = _on_chip((
        jax.ShapeDtypeStruct((slots,), jnp.int32), pool,
        {"attn": jax.ShapeDtypeStruct((slots, columns), jnp.int32)},
        jax.ShapeDtypeStruct((slots,), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.bool_)), v5e)

    def tick(params, token, pool, table, cache_len, active):
        def one(carry, _):
            token, pool, cache_len = carry
            logits, pool, new_len, counts = jamba.decode_step_paged(
                params, cfg, token, pool, table, cache_len, active,
                counters=True)
            token = jnp.where(active, logits.argmax(-1).astype(token.dtype),
                              token)
            return (token, pool, jnp.where(active, new_len, cache_len)), \
                (token, counts)

        (_, pool, cache_len), (tokens, counts) = jax.lax.scan(
            one, (token, pool, cache_len), None, length=k_steps)
        return tokens, pool, cache_len, counts.sum(0)

    compiled = jax.jit(tick, donate_argnums=(2, 4)).lower(
        params, *rest).compile()
    hlo = compiled.as_text()
    state = r"(?:26,)?128,(?:16,5120|15360)\]"
    copies = re.findall(rf"^.*= \w+\[{state}\S* copy\(.*$", hlo, re.M)
    assert not copies, copies[:3]
    memory = compiled.memory_analysis()
    state_bytes = 26 * slots * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert memory.alias_size_in_bytes >= state_bytes
    assert memory.temp_size_in_bytes < 512 << 20
    # a layer's w_in is read by its product, not copied out of the stack
    sliced = re.findall(r"^\s*(?:ROOT )?%\S+ = bf16\[2560,10240\]\S* "
                        r"(\S+?)\(", hlo, re.M)
    assert set(sliced) <= {"fusion", "bitcast", "parameter"}, sliced
