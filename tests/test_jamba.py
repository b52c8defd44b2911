"""State-space layers around attention layers with a per-slot state
beside the page pool (models/jamba.py, ISSUE 37), against the benchmark's
plain reference (benchmark/reference_jamba.py: float32, highest
precision, no cache, no kernel, no padding, the recurrence token by
token; it imports nothing of the program).

Everything is float32 at ``highest`` matmul precision on both sides
unless a test says otherwise, so the logits agree to ~1e-5 (``TOL``:
float32 sums in another order over 8 layers and tens of tokens of
recurrence). That is tight enough that a control fails it: the
reference with ``h`` in bfloat16, or with the inner norms left out.

(a) ``prefill`` equals the reference's full forward; a prompt alone
    equals the same prompt right-padded in a bucket beside a longer row,
    state and all; the kernel's path (interpreted) equals the chunked;
(b) ``prefill``, the engine's own paged insert, then K fused paged
    decode steps give the reference's logits at every position;
(c) a row that is not active keeps its state bit for bit;
(d) the pool's per-slot kind: shapes, bytes, counts, reset;
(e) through ``GenerationEngine``: a slot released and claimed again
    gives the tokens of a fresh engine; ``stats()``; each refusal.
"""

import asyncio
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import jamba, llama
from gofr_tpu.tpu.generate import GenerationEngine
from gofr_tpu.tpu.page_pool import PagePool, cache_kinds, slot_kinds

_spec = importlib.util.spec_from_file_location(
    "reference_jamba", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "reference_jamba.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

PAGE = 8
TOL = 5e-5      # relative L2 of logits; float32 both sides


def published(cfg):
    """The reference reads the published keys of a configuration file."""
    return {"hidden_size": cfg.dim, "num_attention_heads": cfg.n_heads,
            "num_hidden_layers": cfg.n_layers,
            "num_key_value_heads": cfg.n_kv_heads,
            "rms_norm_eps": cfg.norm_eps, "mamba_expand": cfg.expand,
            "mamba_d_state": cfg.d_state, "mamba_dt_rank": cfg.dt_rank,
            "mamba_d_conv": cfg.d_conv,
            "attn_layer_period": cfg.attn_layer_period,
            "attn_layer_offset": cfg.attn_layer_offset}


@pytest.fixture(scope="module")
def model():
    cfg = jamba.config("tiny", dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        params = jamba.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def tokens_of(seed, length, vocab=256):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (length,), 0, vocab), np.int32)


def want_logits(model, tokens, positions, **switches):
    cfg, params = model
    return np.asarray(reference.forward_logits(
        params, published(cfg), jnp.asarray(tokens),
        jnp.asarray(positions, jnp.int32), **switches))


def prefill(model, rows, bucket):
    """``rows`` of tokens right-padded with zeros into one bucket."""
    cfg = model[0]
    padded = np.zeros((len(rows), bucket), np.int32)
    for i, row in enumerate(rows):
        padded[i, :len(row)] = row
    lengths = jnp.asarray([len(row) for row in rows], jnp.int32)
    with jax.default_matmul_precision("highest"):
        return jamba.prefill(model[1], cfg, jnp.asarray(padded),
                             jamba.init_cache(cfg, len(rows), bucket),
                             lengths=lengths)


# -- (a) prefill ---------------------------------------------------------------------------

def test_layer_types_follow_the_period_and_offset():
    cfg = jamba.config("jamba2_3b")
    kinds = cfg.layer_types
    assert len(kinds) == 28 and kinds.count("attn") == 2
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [7, 21]
    leaves = jamba.cache_leaves(cfg)
    assert list(leaves) == ["ssm", "attn"]        # as they first appear
    assert leaves["ssm"]["per_slot"] and leaves["ssm"]["layers"] == 26
    assert leaves["ssm"]["leaves"]["h"] == ((16, 5120), jnp.float32)
    assert leaves["ssm"]["leaves"]["conv"] == ((3 * 5120,), jnp.bfloat16)
    assert leaves["attn"]["leaves"]["k"] == ((1, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="whole number of periods"):
        jamba.config("jamba2_3b", n_layers=27)


def test_seeded_init_gives_long_lived_states_and_the_published_inits(model):
    cfg, params = model
    ssm = params["ssm"]
    assert ssm["w_in"].shape[0] == 6 and params["attn"]["wq"].shape[0] == 2
    assert np.allclose(np.exp(ssm["a_log"][0, :, 0]), np.arange(1, 17))
    assert bool((ssm["d"] == 1).all())
    steps = np.asarray(jax.nn.softplus(ssm["b_dt"]))
    assert 1e-3 * 0.99 <= steps.min() and steps.max() <= 1e-1 * 1.01
    # one jitted program for every seed: the key is an argument
    other = jax.jit(lambda k: jamba.init(cfg, k))(jax.random.PRNGKey(1))
    assert not np.allclose(other["tok_emb"], params["tok_emb"])


def test_prefill_equals_the_reference(model):
    tokens = tokens_of(1, 48)
    logits, cache, cache_len = prefill(model, [tokens], 48)
    want = want_logits(model, tokens, [47])
    assert reference.rel_l2(logits[0], want[0]) < TOL
    assert cache_len.tolist() == [48]
    assert cache["ssm"]["h"].shape == (6, 1, 16, 128)
    assert cache["ssm"]["conv"].shape == (6, 1, 3 * 128)
    assert cache["attn"]["k"].shape == (2, 1, 48, 1, 16)


@pytest.mark.parametrize("switches", [
    {"h_round_to": jnp.bfloat16}, {"inner_norms": False},
    {"round_to": jnp.float8_e4m3fn}], ids=["bf16-h", "no-inner-norms",
                                           "float8-weights"])
def test_a_control_fails_the_tolerance(model, switches):
    tokens = tokens_of(1, 48)
    want = want_logits(model, tokens, [47])
    control = want_logits(model, tokens, [47], **switches)
    assert reference.rel_l2(control, want) > 20 * TOL


def test_a_prompt_alone_equals_itself_padded_beside_a_longer_row(model):
    short, longer = tokens_of(2, 21), tokens_of(3, 61)
    alone_logits, alone, _ = prefill(model, [short], 32)
    exact_logits, exact, _ = prefill(model, [short[:21]], 21)
    both_logits, both, cache_len = prefill(model, [short, longer], 64)
    assert cache_len.tolist() == [21, 61]
    for logits, cache in ((alone_logits, alone), (exact_logits, exact)):
        assert reference.rel_l2(both_logits[0], logits[0]) < TOL
        for name in ("h", "conv"):
            np.testing.assert_allclose(both["ssm"][name][:, 0],
                                       cache["ssm"][name][:, 0], atol=1e-5)
    assert reference.rel_l2(both_logits[1],
                            want_logits(model, longer, [60])[0]) < TOL
    # the conv window is the last three inputs before the prompt's end
    assert float(jnp.abs(both["ssm"]["conv"][:, 0]).max()) > 0


def test_a_prompt_shorter_than_the_conv_window_keeps_zeros_before_it(model):
    tokens = tokens_of(4, 2)
    logits, cache, _ = prefill(model, [tokens], 16)
    assert reference.rel_l2(logits[0],
                            want_logits(model, tokens, [1])[0]) < TOL
    channels = model[0].d_inner
    assert bool((cache["ssm"]["conv"][:, 0, :channels] == 0).all())
    assert float(jnp.abs(cache["ssm"]["conv"][:, 0, channels:]).max()) > 0


def test_the_kernels_path_equals_the_chunked_path(model, monkeypatch):
    cfg = model[0]
    rows = [tokens_of(5, 21), tokens_of(6, 32)]
    assert not cfg.scans_in_kernel(128)       # 128 channels: no tile
    chunked_logits, chunked, _ = prefill(model, rows, 32)
    # the toy widths through the (interpreted) kernel all the same: one
    # block of 32 tokens, so the interpreter's unrolled walk is short
    from gofr_tpu.ops import pallas
    monkeypatch.setattr(pallas, "scan_tileable", lambda *shape: True)
    assert cfg.scans_in_kernel(32)
    kernel_logits, kernel, _ = prefill(model, rows, 32)
    monkeypatch.undo()
    assert reference.rel_l2(kernel_logits, chunked_logits) < TOL
    np.testing.assert_allclose(kernel["ssm"]["h"], chunked["ssm"]["h"],
                               atol=1e-5)
    assert jamba.config("jamba2_3b").scans_in_kernel(2048)
    assert jamba.config("jamba2_3b").flash_block(2048) == 1024
    assert jamba.config("jamba2_3b").flash_block(128) == 512


# -- (b), (c) decode over the pool ------------------------------------------------------

def engine_of(model, **kwargs):
    cfg, params = model
    settings = dict(max_slots=4, max_len=128, prompt_buckets=(16, 32, 64),
                    steps_per_tick=2, paged_kv=True, kv_page=PAGE,
                    model_module=jamba)
    settings.update(kwargs)
    return GenerationEngine(cfg, params, **settings)


def test_prefill_insert_and_fused_paged_steps_equal_the_reference(model):
    """The engine's own insert and tick executables, called as the
    engine calls them: two rows of one bucket into slots 2 and 0, then
    two ticks of K = 2 with the reference's tokens forced."""
    cfg, params = model
    engine = engine_of(model)
    rows = [tokens_of(7, 53), tokens_of(8, 30)]
    steps = 4
    sequences = [np.concatenate([row, tokens_of(9 + i, steps)])
                 for i, row in enumerate(rows)]
    wants = [want_logits(model, seq, range(len(row) - 1, len(seq)))
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
        step = jax.jit(lambda *a: jamba.decode_step_paged(
            params, cfg, *a, counters=True))
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
            # 6 state-space layers of 2 live rows of 4; 2 attention
            # layers, the gathered view 4 rows x 16 pages x 8
            assert counts.tolist() == [12, 24, 6, 2 * live, 2 * 512, 2, 0]
            cache_len = jnp.where(active, new_len, cache_len)


def test_an_inactive_rows_state_is_bit_identical_after_a_tick(model):
    cfg, params = model
    engine = engine_of(model)
    leaves = jax.tree.map(
        lambda leaf: jax.random.normal(
            jax.random.PRNGKey(leaf.size % 97), leaf.shape,
            jnp.float32).astype(leaf.dtype), engine._pool.leaves["ssm"])
    before = jax.tree.map(np.asarray, leaves)
    engine._pool.leaves = dict(engine._pool.leaves, ssm=leaves)
    engine.cache_len = jnp.asarray([5, 9, 0, 3], jnp.int32)
    active = jnp.asarray([False, True, False, False])
    engine._run_tick(2, False, active)
    after = engine._pool.leaves["ssm"]
    for name in ("h", "conv"):
        got = np.asarray(after[name])
        for row in (0, 2, 3):
            assert (got[:, row] == before[name][:, row]).all(), (name, row)
        assert (got[:, 1] != before[name][:, 1]).any(), name
    assert engine.cache_len.tolist() == [5, 11, 0, 3]


@pytest.fixture(scope="module")
def wide():
    """The toy preset at 1024 state channels, which the decode step's
    kernel tiles at 16 slots (the toy's 128 do not)."""
    cfg = jamba.config("tiny", dim=512, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        params = jamba.init(cfg, jax.random.PRNGKey(3))
    return cfg, params


def paged_steps(model, slots, steps=3):
    """``steps`` paged decode steps over a pool of random contents, every
    fifth slot from the third not active. Returns (the pool before, the
    pool after, each step's logits and counters)."""
    cfg, params = model
    columns = 4
    pool = PagePool(cfg, page=PAGE, num_pages={"attn": slots * columns},
                    leaf_specs=jamba.cache_leaves(cfg), slots=slots)
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 8))
    leaves = jax.tree.map(lambda leaf: jax.random.normal(
        next(keys), leaf.shape).astype(leaf.dtype), pool.leaves)
    before = jax.tree.map(np.asarray, leaves)
    table = jnp.arange(slots * columns, dtype=jnp.int32).reshape(slots, -1)
    cache_len = jax.random.randint(next(keys), (slots,), 1,
                                   columns * PAGE - steps)
    active = jnp.arange(slots) % 5 != 2
    step = jax.jit(lambda token, leaves, cache_len: jamba.decode_step_paged(
        params, cfg, token, leaves, {"attn": table}, cache_len, active,
        counters=True))
    out = []
    with jax.default_matmul_precision("highest"):
        for t in range(steps):
            token = jnp.asarray(tokens_of(20 + t, slots))
            logits, leaves, new_len, counts = step(token, leaves, cache_len)
            out.append((np.asarray(logits), counts.tolist()))
            cache_len = jnp.where(active, new_len, cache_len)
    return before, jax.tree.map(np.asarray, leaves), out, np.asarray(active)


def test_the_decode_steps_kernel_equals_its_xla_form(wide, monkeypatch):
    """Three paged decode steps of 16 slots through the step kernel
    (interpreted) and through the XLA form give the same logits, state
    and conv window; an inactive slot's state comes back bit for bit on
    both; the counters say which ran."""
    cfg = wide[0]
    assert cfg.steps_in_kernel(16) and not cfg.steps_in_kernel(8)
    assert not jamba.config("tiny").steps_in_kernel(16)
    assert jamba.config("jamba2_3b").steps_in_kernel(128)
    before, kernel, kernel_steps, active = paged_steps(wide, 16)
    from gofr_tpu.ops import pallas
    monkeypatch.setattr(pallas, "step_tileable", lambda *shape: False)
    assert not cfg.steps_in_kernel(16)
    _, xla, xla_steps, _ = paged_steps(wide, 16)
    monkeypatch.undo()
    layer_steps = cfg.layer_types.count("ssm")
    for (got, counts), (want, xla_counts) in zip(kernel_steps, xla_steps):
        assert reference.rel_l2(got, want) < TOL
        assert counts[:6] == xla_counts[:6]
        assert counts[2] == counts[6] == layer_steps
        assert xla_counts[6] == 0
    # the inputs of later steps and layers carry float32 sums in
    # another order
    for name in ("h", "conv"):
        np.testing.assert_allclose(kernel["ssm"][name], xla["ssm"][name],
                                   atol=1e-5, rtol=1e-5)
    for after in (kernel, xla):
        for name in ("h", "conv"):
            assert (after["ssm"][name][:, ~active]
                    == before["ssm"][name][:, ~active]).all(), name
            assert (after["ssm"][name][:, active]
                    != before["ssm"][name][:, active]).any(), name


# -- (d) the pool's per-slot kind ---------------------------------------------------------

def test_the_pool_keeps_a_per_slot_kind_beside_the_pages():
    cfg = jamba.config("tiny")
    specs = jamba.cache_leaves(cfg)
    assert [k.name for k in cache_kinds(cfg, specs)] == ["attn"]
    assert [k.name for k in slot_kinds(specs)] == ["ssm"]
    assert slot_kinds(None) == [] and slot_kinds({"k": ((1, 2), "f")}) == []
    pool = PagePool(cfg, page=PAGE, num_pages={"attn": 10},
                    leaf_specs=specs, slots=3)
    assert list(pool.kinds) == ["attn"] and list(pool.slot_kinds) == ["ssm"]
    assert pool.leaves["ssm"]["h"].shape == (6, 3, 16, 128)
    assert pool.leaves["ssm"]["h"].dtype == jnp.float32
    assert pool.leaves["ssm"]["conv"].shape == (6, 3, 384)
    assert pool.leaves["attn"]["k"].shape == (2, 10, PAGE, 1, 16)
    slot_bytes = 6 * (16 * 128 * 4 + 384 * 2)
    assert pool.slot_kinds["ssm"].slot_bytes == slot_bytes
    assert pool.state_bytes == 3 * slot_bytes
    assert pool.pool_bytes == 3 * slot_bytes + 10 * pool.page_bytes
    assert pool.num_pages == 10           # pages are the paged kinds'
    pool.claim_slot(2)
    pool.claim_slot(0)
    pool.claim_slot(2)
    pool.release_slot(0)
    pool.release_slot(0)
    stats = pool.stats()["kinds"]["ssm"]
    assert stats == {"layers": 6, "per_slot": True, "slots": 3,
                     "slot_bytes": slot_bytes, "bytes": 3 * slot_bytes,
                     "claimed": 1, "claimed_peak": 2, "resets": 0}
    assert "num_pages" in pool.stats()["kinds"]["attn"]
    pool.leaves["ssm"]["h"] = pool.leaves["ssm"]["h"] + 1
    pool.reset()
    stats = pool.stats()["kinds"]["ssm"]
    assert stats["claimed"] == 0 and stats["resets"] == 1
    assert stats["claimed_peak"] == 2     # history survives
    assert not bool(pool.leaves["ssm"]["h"].any())
    with pytest.raises(ValueError, match="slot count"):
        PagePool(cfg, page=PAGE, num_pages={"attn": 10}, leaf_specs=specs)


def test_a_byte_budget_sizes_the_pages_less_the_state(model):
    cfg = model[0]
    state = 4 * PagePool.slot_bytes_of(slot_kinds(
        jamba.cache_leaves(cfg))[0])
    page_bytes = 2 * PAGE * 2 * 16 * 4
    engine = engine_of(model, kv_pool_bytes=state + 20 * page_bytes)
    pool = engine.stats()["kv_pool"]
    assert pool["kinds"]["attn"]["num_pages"] == 20
    assert pool["kinds"]["ssm"]["bytes"] == state
    assert pool["pool_bytes"] == state + 20 * page_bytes
    # without a budget: every slot at max_len
    assert engine_of(model).stats()["kv_pool"]["kinds"]["attn"][
        "num_pages"] == 4 * 128 // PAGE


# -- (e) the engine --------------------------------------------------------------------------

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
    """Two slots, six requests: every slot is released and claimed
    again with another prompt of another length, and each answer is
    what an engine that has served nothing gives for that prompt alone,
    and what the reference chooses."""
    prompts = [tokens_of(20 + i, length)
               for i, length in enumerate((40, 9, 25, 60, 14, 33))]
    with jax.default_matmul_precision("highest"):
        engine = engine_of(model, max_slots=2)
        served = serve(engine, prompts, 6)
        stats = engine.stats()
        alone = [serve(engine_of(model, max_slots=2), [prompt], 6)[0]
                 for prompt in prompts[3:]]
    assert served[3:] == alone
    for prompt, reply in zip(prompts, served):
        sequence = np.concatenate([prompt, reply[:-1]]).astype(np.int32)
        want = want_logits(model, sequence,
                           range(len(prompt) - 1, len(sequence)))
        assert want.argmax(-1).tolist() == reply
    kind = stats["kv_pool"]["kinds"]["ssm"]
    assert kind["claimed"] == 0 and kind["claimed_peak"] == 2
    assert kind["slots"] == 2 and kind["per_slot"]
    assert stats["kv_pool"]["kinds"]["attn"]["used_pages"] == 0
    # 6 state-space layers a step, 2 attention layers
    assert stats["ssm"]["layer_steps"] * 2 == stats["attn"]["calls"] * 6
    assert 0 < stats["ssm"]["rows_live"] <= stats["ssm"]["rows_read"]
    assert 0 < stats["attn"]["rows_live"] <= stats["attn"]["rows_read"]
    assert engine.attn_path == "gather"


def test_warm_up_and_reset_build_the_state(model):
    engine = engine_of(model, max_slots=2)
    asyncio.run(engine.warmup(prompt_counts=(1, 2)))
    assert engine.stats()["compiles"]["serving"] == 0
    # warm-up's padding groups and idle ticks wrote no row
    assert not bool(engine._pool.leaves["ssm"]["h"].any())
    engine._pool.leaves["ssm"]["h"] = engine._pool.leaves["ssm"]["h"] + 1
    engine._reset_device_state()
    assert not bool(engine._pool.leaves["ssm"]["h"].any())
    assert engine.stats()["kv_pool"]["kinds"]["ssm"]["resets"] == 1
    prompt = tokens_of(30, 12)
    with jax.default_matmul_precision("highest"):
        assert serve(engine, [prompt], 4) \
            == serve(engine_of(model, max_slots=2), [prompt], 4)
    assert engine.stats()["compiles"]["serving"] == 0


@pytest.mark.parametrize("what,kwargs,match", [
    ("dense", {"paged_kv": False}, "kept by the page pool's manager"),
    ("prefix", {"prefix_cache": True}, "prefix pages hold no state"),
    ("spec", {"draft_cfg": llama.config("tiny"), "draft_params": {}},
     "roll the state back"),
    ("mesh", {"mesh": "a mesh"}, "no sharding rule"),
    ("shared", {"page_pool": "a pool"}, "a shared page_pool's slots"),
])
def test_engine_names_what_a_per_slot_kind_cannot_use(model, what, kwargs,
                                                      match):
    with pytest.raises(ValueError, match=f"per-slot cache kind.*{match}"):
        engine_of(model, **kwargs)


def test_kv_wire_is_refused_by_name_for_a_per_slot_kind(model):
    engine = engine_of(model)
    assert "no snapshot yet" in engine._kv_wire_refusal
    for call in (engine.prefill_export([1, 2, 3]),
                 engine.adopt_kv(b"", 4),
                 engine.adopt_session(b"", 4),
                 engine.export_session(None)):
        with pytest.raises(ValueError, match="per-slot cache kind"):
            asyncio.run(call)
