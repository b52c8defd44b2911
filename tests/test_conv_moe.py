"""Gated short-convolution layers beside GQA attention at head 64 over
sparse experts, with a per-slot window beside the page pool
(models/conv_moe.py), against the benchmark's plain reference
(benchmark/reference_conv_moe.py: float32, highest precision, no cache,
no kernel, no padding, the convolution an explicit causal sum; it
imports nothing of the program).

Everything is float32 at ``highest`` matmul precision on both sides
unless a test says otherwise, so the logits agree to ~3e-6 (``TOL``:
float32 sums in another order over 6 layers and tens of tokens). That is
tight enough that a control fails it by orders of magnitude: the
reference with QK-norm dropped, the router's bias ignored, a gate
dropped, or float8 weights.

(a) ``prefill`` equals the reference's full forward, routing included;
    a prompt alone equals the same prompt right-padded in a bucket
    beside a longer row, window and all;
(b) ``prefill``, the engine's own paged insert, then paged decode steps
    (the ragged kernel's packed form, interpreted, and the gathered
    oracle) give the reference's logits at every position;
(c) a row that is not active keeps its window bit for bit;
(d) through ``GenerationEngine``: a slot released and claimed again
    gives the tokens of a fresh engine; ``stats()``; each refusal.
"""

import asyncio
import importlib.util
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import conv_moe, experts, llama
from gofr_tpu.tpu.generate import GenerationEngine
from gofr_tpu.tpu.page_pool import slot_kinds

_spec = importlib.util.spec_from_file_location(
    "reference_conv_moe", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "reference_conv_moe.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

PAGE = 8
TOL = 5e-5      # relative L2 of logits; float32 both sides


def published(cfg):
    """The reference reads the published keys of a configuration file."""
    return {"hidden_size": cfg.dim, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "num_hidden_layers": cfg.n_layers,
            "layer_types": list(cfg.layer_types),
            "num_dense_layers": cfg.n_dense_layers,
            "norm_eps": cfg.norm_eps, "conv_L_cache": cfg.conv_width,
            "rope_theta": cfg.rope_theta, "num_experts": cfg.n_routed_experts,
            "num_experts_per_tok": cfg.top_k, "norm_topk_prob": True,
            "routed_scaling_factor": cfg.routed_scale}


@pytest.fixture(scope="module")
def model():
    cfg = conv_moe.config("tiny", dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: conv_moe.init(cfg, k))(
            jax.random.PRNGKey(0))
    return cfg, params


def tokens_of(seed, length, vocab=256):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (length,), 0, vocab), np.int32)


def want_logits(model, tokens, positions, **switches):
    """The reference's logits at ``positions`` and its experts chosen."""
    cfg, params = model
    logits, chosen = jax.jit(
        lambda p, t, at, sw: reference.forward_logits(
            p, published(cfg), t, at, switches=sw))(
        params, jnp.asarray(tokens), jnp.asarray(positions, jnp.int32),
        {k: jnp.float32(v) for k, v in dict(reference.SOUND,
                                              **switches).items()})
    return np.asarray(logits), np.asarray(chosen)


def prefill(model, rows, bucket, routes=False):
    """``rows`` of tokens right-padded with zeros into one bucket."""
    cfg = model[0]
    padded = np.zeros((len(rows), bucket), np.int32)
    for i, row in enumerate(rows):
        padded[i, :len(row)] = row
    lengths = jnp.asarray([len(row) for row in rows], jnp.int32)
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t, l: conv_moe.prefill(
            p, cfg, t, conv_moe.init_cache(cfg, *t.shape), lengths=l,
            routes=routes))(model[1], jnp.asarray(padded), lengths)


# -- (a) prefill ---------------------------------------------------------------------------

def test_layer_kinds_periods_and_cache_leaves():
    cfg = conv_moe.config("lfm2_8b_a1b")
    kinds = cfg.kinds
    assert len(kinds) == 24 and cfg.count("attn") == 6
    assert [i for i, (m, _) in enumerate(kinds) if m == "attn"] == [
        2, 6, 10, 14, 18, 21]
    assert [f for _, f in kinds[:3]] == ["dense", "dense", "moe"]
    # the published 24 repeat nothing after the dense layers; the first
    # 14, the benchmark's stage, are three periods of (attn, conv x 3)
    assert conv_moe._period(kinds[2:]) == 22
    stage = conv_moe.config("lfm2_8b_a1b", n_layers=14,
                            layer_types=cfg.layer_types[:14])
    assert conv_moe._period(stage.kinds[2:]) == 4
    assert (stage.count("conv"), stage.count("attn"), stage.count("moe")) \
        == (11, 3, 12)
    leaves = conv_moe.cache_leaves(stage)
    assert list(leaves) == ["conv", "attn"]       # as they first appear
    assert leaves["conv"]["per_slot"] and leaves["conv"]["layers"] == 11
    assert leaves["conv"]["leaves"]["window"] == ((2 * 2048,), jnp.bfloat16)
    # K and V packed: a token's 8 heads of 64 in one row of 512 lanes
    assert leaves["attn"]["leaves"]["k"] == ((512,), jnp.bfloat16)
    assert stage.flash_block(128) == 512 and stage.flash_block(2048) == 1024
    assert stage.lane_head == 128
    with pytest.raises(ValueError, match="layer_types names"):
        conv_moe.config("lfm2_8b_a1b", n_layers=14)
    with pytest.raises(ValueError, match="a layer is one of"):
        conv_moe.config("tiny", layer_types=("conv",) * 5 + ("mamba",))


def test_prefill_and_its_routing_equal_the_reference(model):
    tokens = tokens_of(1, 48)
    logits, cache, cache_len, routes = prefill(model, [tokens], 48,
                                               routes=True)
    want, chosen = want_logits(model, tokens, [47])
    assert reference.rel_l2(logits[0], want[0]) < TOL
    assert cache_len.tolist() == [48]
    assert cache["conv"]["window"].shape == (4, 1, 2 * 256)
    assert cache["attn"]["k"].shape == (2, 1, 48, 2 * 64)
    assert routes.shape == (4, 1, 48, 2)
    assert (np.sort(routes[:, 0], -1) == np.sort(chosen, -1)).all()


@pytest.mark.parametrize("switches", [
    {"qk_norm": 0.0}, {"bias": 0.0}, {"b_gate": 0.0}, {"c_gate": 0.0},
    {"float8": 1.0}], ids=["no-qk-norm", "no-router-bias", "no-b-gate",
                           "no-c-gate", "float8-weights"])
def test_a_control_fails_the_tolerance(model, switches):
    """QK-norm, the router's bias and each gate move the logits, and so
    do weights a precision below the configuration's."""
    tokens = tokens_of(1, 48)
    want, _ = want_logits(model, tokens, [40, 47])
    control, _ = want_logits(model, tokens, [40, 47], **switches)
    assert reference.rel_l2(control, want) > 100 * TOL


def test_the_bias_changes_the_experts_chosen(model):
    tokens = tokens_of(1, 48)
    _, chosen = want_logits(model, tokens, [47])
    _, unbiased = want_logits(model, tokens, [47], bias=0.0)
    differs = (np.sort(chosen, -1) != np.sort(unbiased, -1)).any(-1)
    assert 0.02 < differs.mean() < 0.6


def test_the_top_k_normalisation_adds_its_eps():
    cfg = conv_moe.config("tiny", dtype=jnp.float32)
    router = jax.random.normal(jax.random.PRNGKey(2), (cfg.dim, 8))
    h = jax.random.normal(jax.random.PRNGKey(3), (5, cfg.dim))
    ids, weights = experts.route(cfg, router, h)
    # a family with no eps (the four other expert configurations)
    other = types.SimpleNamespace(scoring="sigmoid", top_k=cfg.top_k,
                                  norm_topk_prob=True, routed_scale=1.0,
                                  topk_norm_eps=0.0)
    _, plain = experts.route(other, router, h)
    top = jnp.take_along_axis(jax.nn.sigmoid(h @ router), ids, -1)
    np.testing.assert_allclose(weights, top / (top.sum(-1, keepdims=True)
                                               + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(plain.sum(-1), 1.0, rtol=1e-6)
    assert float(plain.sum(-1).min()) > float(weights.sum(-1).max())


def test_a_prompt_alone_equals_itself_padded_beside_a_longer_row(model):
    """Padding masked: the window is gathered at each row's length, not
    at the bucket's end."""
    short, longer = tokens_of(2, 21), tokens_of(3, 61)
    exact_logits, exact, _ = prefill(model, [short], 21)
    both_logits, both, cache_len = prefill(model, [short, longer], 64)
    assert cache_len.tolist() == [21, 61]
    assert reference.rel_l2(both_logits[0], exact_logits[0]) < TOL
    # float32 sums in another order over three layers
    np.testing.assert_allclose(both["conv"]["window"][:, 0],
                               exact["conv"]["window"][:, 0], atol=1e-4,
                               rtol=1e-4)
    assert reference.rel_l2(both_logits[1],
                            want_logits(model, longer, [60])[0][0]) < TOL
    # the window at the bucket's end is another one
    _, unmasked, _ = jax.jit(lambda p, t: conv_moe.prefill(
        p, model[0], t, conv_moe.init_cache(model[0], *t.shape)))(
        model[1], jnp.asarray(np.pad(short, (0, 43))[None]))
    assert float(jnp.abs(unmasked["conv"]["window"][:, 0]
                         - exact["conv"]["window"][:, 0]).max()) > 0.1


# -- (b), (c) decode over the pool ------------------------------------------------------

def engine_of(model, **kwargs):
    cfg, params = model
    settings = dict(max_slots=4, max_len=128, prompt_buckets=(16, 32, 64),
                    steps_per_tick=2, paged_kv=True, kv_page=PAGE,
                    model_module=conv_moe)
    settings.update(kwargs)
    return GenerationEngine(cfg, params, **settings)


@pytest.mark.parametrize("ragged", [True, False], ids=["kernel", "gather"])
def test_prefill_insert_and_paged_steps_equal_the_reference(model, ragged):
    """The engine's own prefill and insert executables, called as the
    engine calls them: two rows of one bucket into slots 2 and 0, then
    paged decode steps with the reference's tokens forced, through the
    ragged kernel's packed form (interpreted) or the gathered pages."""
    cfg, params = model
    engine = engine_of(model)
    rows = [tokens_of(7, 53), tokens_of(8, 30)]
    steps = 3
    sequences = [np.concatenate([row, tokens_of(9 + i, steps)])
                 for i, row in enumerate(rows)]
    wants = [want_logits(model, seq, range(len(row) - 1, len(seq)))[0]
             for seq, row in zip(sequences, rows)]
    slots = [2, 0]
    padded = np.zeros((2, 64), np.int32)
    flat_ids = np.full((2 * 64 // PAGE,), engine._pool.sentinel, np.int32)
    for i, row in enumerate(rows):
        padded[i, :len(row)] = row
        pages = -(-(len(row) + steps) // PAGE)
        ids = engine._pool.alloc(pages, kind="attn")
        engine._tables["attn"][slots[i], :pages] = ids
        filled = -(-len(row) // PAGE)
        flat_ids[i * 8:i * 8 + filled] = ids[:filled]
    engine._table_version += 1
    dev = dict(padded=jnp.asarray(padded),
               lengths=jnp.asarray([53, 30], jnp.int32),
               slots=jnp.asarray(slots, jnp.int32),
               temps=jnp.zeros((2,)), top_ks=jnp.zeros((2,), jnp.int32),
               top_ps=jnp.ones((2,)), seeds=jnp.zeros((2,), jnp.uint32),
               flat_ids={"attn": jnp.asarray(flat_ids)})
    with jax.default_matmul_precision("highest"):
        first, small, keys = engine._run_prefill(2, 64, dev)
        for i in range(2):
            assert int(first[i]) == int(wants[i][0].argmax())
        engine._run_insert(2, 64, 0, dev, first, small, keys)
        assert engine.cache_len.tolist() == [30, 0, 53, 0]
        active = jnp.asarray([True, False, True, False])
        step = jax.jit(lambda *a: conv_moe.decode_step_paged(
            params, cfg, *a, ragged=ragged, counters=True))
        pool, cache_len = engine._pool.leaves, engine.cache_len
        table = engine._table_dev()
        for t in range(steps):
            token = np.zeros((4,), np.int32)
            for i, slot in enumerate(slots):
                token[slot] = sequences[i][len(rows[i]) + t]
            logits, pool, new_len, counts = step(
                jnp.asarray(token), pool, table, cache_len, active)
            for i, slot in enumerate(slots):
                assert reference.rel_l2(logits[slot],
                                        wants[i][1 + t]) < TOL, (i, t)
            live = int(cache_len[0] + cache_len[2])
            # 4 convolution layers of 2 live rows; 2 attention layers;
            # 4 expert layers of 2 rows x 2 of 8 experts
            got = dict(zip(conv_moe.STEP_COUNTERS, counts.tolist()))
            assert got["conv.layer_steps"] == 4
            assert got["conv.rows_live"] == 8
            assert got["attn.calls"] == 2
            assert got["attn.kernel_calls"] == (2 if ragged else 0)
            assert got["attn.rows_live"] == 2 * live
            assert got["moe.layer_steps"] == 4
            assert got["moe.routed_pairs"] == got["moe.held_pairs"] == 16
            cache_len = jnp.where(active, new_len, cache_len)


def test_the_dense_decode_step_equals_the_reference(model):
    """``decode_step`` over the dense cache a prefill fills: the tests'
    oracle of the paged step."""
    cfg, params = model
    tokens = tokens_of(12, 30)
    want, _ = want_logits(model, tokens, range(19, 30))
    with jax.default_matmul_precision("highest"):
        logits, cache, cache_len = jax.jit(lambda p, t: conv_moe.prefill(
            p, cfg, t, conv_moe.init_cache(cfg, 1, 32)))(
            params, jnp.asarray(tokens[None, :20]))
        step = jax.jit(lambda *a: conv_moe.decode_step(params, cfg, *a))
        got = [np.asarray(logits[0])]
        for t in range(20, 30):
            logits, cache, cache_len = step(jnp.asarray(tokens[t:t + 1]),
                                            cache, cache_len)
            got.append(np.asarray(logits[0]))
    assert reference.rel_l2(np.stack(got), want) < TOL


def test_an_inactive_rows_window_is_bit_identical_after_a_tick(model):
    engine = engine_of(model)
    leaves = jax.tree.map(
        lambda leaf: jax.random.normal(
            jax.random.PRNGKey(leaf.size % 97), leaf.shape,
            jnp.float32).astype(leaf.dtype), engine._pool.leaves["conv"])
    before = np.asarray(leaves["window"])
    engine._pool.leaves = dict(engine._pool.leaves, conv=leaves)
    engine.cache_len = jnp.asarray([5, 9, 0, 3], jnp.int32)
    active = jnp.asarray([False, True, False, False])
    engine._run_tick(1, False, active)
    got = np.asarray(engine._pool.leaves["conv"]["window"])
    for row in (0, 2, 3):
        assert (got[:, row] == before[:, row]).all(), row
    assert (got[:, 1] != before[:, 1]).any()
    # the active row's window shifted: its newer row is now its older
    dim = model[0].dim
    np.testing.assert_array_equal(got[:, 1, :dim], before[:, 1, dim:])
    assert engine.cache_len.tolist() == [5, 10, 0, 3]


# -- (d) the engine --------------------------------------------------------------------------

def serve(engine, prompts, budget):
    async def run():
        await engine.start()
        try:
            return await asyncio.gather(*[
                engine.generate(list(map(int, p)), budget) for p in prompts])
        finally:
            await engine.stop()
    return asyncio.run(run())


def test_a_slot_claimed_again_gives_the_tokens_of_a_fresh_engine(model):
    """Two slots, five requests through the ragged kernel's packed form
    (interpreted): every slot is released and claimed again with another
    prompt of another length, and each answer is what an engine that
    has served nothing gives for that prompt alone, and what the
    reference chooses. The window a slot holds is never its last
    tenant's."""
    prompts = [tokens_of(20 + i, length)
               for i, length in enumerate((40, 9, 25, 60, 14))]
    with jax.default_matmul_precision("highest"):
        engine = engine_of(model, max_slots=2, ragged_attn="on")
        served = serve(engine, prompts, 5)
        stats = engine.stats()
        alone = [serve(engine_of(model, max_slots=2, ragged_attn="on"),
                       [prompt], 5)[0] for prompt in prompts[3:]]
    assert served[3:] == alone
    for prompt, reply in zip(prompts, served):
        sequence = np.concatenate([prompt, reply[:-1]]).astype(np.int32)
        want, _ = want_logits(model, sequence,
                              range(len(prompt) - 1, len(sequence)))
        assert want.argmax(-1).tolist() == reply
    kind = stats["kv_pool"]["kinds"]["conv"]
    assert kind["claimed"] == 0 and kind["claimed_peak"] == 2
    assert kind["slots"] == 2 and kind["per_slot"]
    assert stats["kv_pool"]["kinds"]["attn"]["used_pages"] == 0
    # 4 convolution layers a step, 2 attention layers, all in the kernel
    assert stats["conv"]["layer_steps"] * 2 == stats["attn"]["calls"] * 4
    assert stats["attn"]["kernel_calls"] == stats["attn"]["calls"]
    assert stats["moe"]["held_pairs"] == stats["moe"]["routed_pairs"] > 0
    assert engine.attn_path == "ragged"
    walk = engine.attention_paths()["ragged_walk"]["attn"]
    assert walk["product_heads"] == 1          # one unit of two heads


def test_auto_takes_the_kernel_on_a_tpu_for_the_packed_pool(monkeypatch):
    """The pool's attention leaf is one row of ``Hkv * 64`` lanes a
    token: on a TPU, auto walks it in the ragged kernel (eight query
    heads and pages of 16, so that they fill the sublanes)."""
    cfg = conv_moe.config("tiny", n_heads=8)
    params = conv_moe.init(cfg, jax.random.PRNGKey(0))
    engine = engine_of((cfg, params), ragged_attn="auto", kv_page=16)
    assert engine.attn_path == "gather"                    # cpu
    assert engine._pool.kinds["attn"].leaves["k"][0] == (2 * 64,)
    monkeypatch.setattr(engine._jax, "devices", lambda: [
        types.SimpleNamespace(platform="tpu")])
    assert engine._resolve_ragged() == (
        True, "auto: tpu devices and the geometry tiles")


def test_the_pool_keeps_the_window_beside_the_pages(model):
    cfg = model[0]
    engine = engine_of(model)
    pool = engine.stats()["kv_pool"]
    assert [k.name for k in slot_kinds(conv_moe.cache_leaves(cfg))] == [
        "conv"]
    assert engine._pool.leaves["conv"]["window"].shape == (4, 4, 2 * 256)
    assert engine._pool.leaves["attn"]["k"].shape[2:] == (PAGE, 128)
    assert pool["kinds"]["conv"]["slot_bytes"] == 4 * 2 * 256 * 4
    assert list(engine._tables) == ["attn"]


@pytest.mark.parametrize("what,kwargs,match", [
    ("dense", {"paged_kv": False}, "kept by the page pool's manager"),
    ("prefix", {"prefix_cache": True}, "prefix pages hold no state"),
    ("spec", {"draft_cfg": llama.config("tiny"), "draft_params": {}},
     "roll the state back"),
    ("mesh", {"mesh": "a mesh"}, "no sharding rule"),
    ("shared", {"page_pool": "a pool"}, "a shared page_pool's slots"),
])
def test_engine_names_what_a_per_slot_window_cannot_use(model, what, kwargs,
                                                        match):
    with pytest.raises(ValueError, match=f"per-slot cache kind.*{match}"):
        engine_of(model, **kwargs)


def test_kv_wire_is_refused_by_name_for_the_window(model):
    engine = engine_of(model)
    assert "no snapshot yet" in engine._kv_wire_refusal
    for call in (engine.prefill_export([1, 2, 3]),
                 engine.adopt_kv(b"", 4),
                 engine.adopt_session(b"", 4),
                 engine.export_session(None)):
        with pytest.raises(ValueError, match="per-slot cache kind"):
            asyncio.run(call)
