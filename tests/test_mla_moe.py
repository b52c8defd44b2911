"""Latent attention over a latent page pool and the expert layer that
holds a share of the experts (models/mla_moe.py, ISSUE 27), against the
benchmark's plain reference (benchmark/reference_mla_moe.py: float32,
highest precision, no cache, no absorbed form, one expert at a time).

Everything is float32 at ``highest`` matmul precision on both sides, so
routing cannot flip between the two and logits agree to ~1e-6:

(a) ``prefill`` then ``decode_step_paged`` through a ``PagePool`` of
    latent leaves equals the reference's full forward, at lengths that
    cross a page;
(b) the greedy stream of ``GenerationEngine`` (paged, the normal path)
    equals stepping the reference;
(c) absorbed attention equals expanded attention;
(d) the shares add up: every rank's routed part plus the shared expert
    once is the uncut layer;
(e) no token is dropped when the router sends every token to one expert;
(f) llama's pool leaves and page bytes are what they were;
(g) the admission bound splits a group and never reorders it;
(h) the step counters add up, and llama's tick has none.
"""

import asyncio
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.container import new_mock_container
from gofr_tpu.models import llama, mla_moe
from gofr_tpu.tpu.generate import GenerationEngine
from gofr_tpu.tpu.page_pool import PagePool, kv_leaf_specs

_spec = importlib.util.spec_from_file_location(
    "reference_mla_moe", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "reference_mla_moe.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

PAGE = 8


def published(cfg):
    """The reference reads the published keys of a configuration file."""
    return {"num_attention_heads": cfg.n_heads,
            "qk_nope_head_dim": cfg.qk_nope_dim,
            "qk_rope_head_dim": cfg.qk_rope_dim,
            "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.top_k,
            "n_routed_experts": cfg.n_held_experts,
            "expert_parallel_rank": cfg.expert_rank,
            "routed_scaling_factor": cfg.routed_scale,
            "norm_topk_prob": cfg.norm_topk_prob,
            "sandwich_norm": cfg.sandwich_norm}


@pytest.fixture(scope="module")
def model():
    """One chip's share: 8 of 32 routed experts, rank 1."""
    cfg = mla_moe.config("tiny", dtype=jnp.float32, n_held_experts=8,
                         expert_rank=1)
    return cfg, mla_moe.init(cfg, jax.random.PRNGKey(0))


def engine_for(module, cfg, params, **kwargs):
    container = new_mock_container()
    kwargs.setdefault("max_slots", 4)
    kwargs.setdefault("max_len", 64)
    kwargs.setdefault("prompt_buckets", (8, 16))
    engine = GenerationEngine(
        cfg, params, paged_kv=True, kv_page=PAGE, model_module=module,
        logger=container.logger, metrics=container.metrics, **kwargs)
    return engine, container


# -- (a) prefill, then paged decode, against the reference's full forward --------

@pytest.mark.parametrize("length", [5, 8, 15, 21])
def test_prefill_then_paged_decode_equals_the_reference(model, length):
    cfg, params = model
    steps = 5                       # 5 -> 10, 8 -> 13, 15 -> 20, 21 -> 26
    tokens = jax.random.randint(jax.random.PRNGKey(length),
                                (length + steps,), 0, cfg.vocab_size)
    want, _ = reference.forward_logits(params, published(cfg), tokens,
                                       last=steps + 1)
    pool = PagePool(cfg, page=PAGE, num_pages=12,
                    leaf_specs=mla_moe.cache_leaves(cfg))
    assert set(pool.leaves) == {"ckv"}
    assert pool.leaves["ckv"].shape == (cfg.n_layers, 12, PAGE,
                                        cfg.cache_row)
    bucket = 24
    with jax.default_matmul_precision("highest"):
        padded = jnp.zeros((2, bucket), jnp.int32).at[0, :length].set(
            tokens[:length])
        logits, small, cache_len = mla_moe.prefill(
            params, cfg, padded, mla_moe.init_cache(cfg, 2, bucket),
            lengths=jnp.array([length, 1]))
        assert reference.rel_l2(logits[0], want[0]) < 1e-5
        # the first row's pages, out of order; the second row is a
        # frozen slot whose page must not be written
        ids = pool.alloc(4)[::-1]
        table = np.full((2, 4), pool.sentinel, np.int32)
        table[0] = ids
        leaves = pool.leaves
        for column, pid in enumerate(ids[:bucket // PAGE]):
            leaves = {"ckv": leaves["ckv"].at[:, pid].set(
                small["ckv"][:, 0, column * PAGE:(column + 1) * PAGE])}
        active = jnp.array([True, False])
        for step in range(steps):
            fed = jnp.array([tokens[length + step], 0])
            logits, leaves, grown = mla_moe.decode_step_paged(
                params, cfg, fed, leaves, jnp.asarray(table), cache_len,
                active)
            cache_len = jnp.where(active, grown, cache_len)
            assert reference.rel_l2(logits[0], want[step + 1]) < 1e-5
    assert int(cache_len[0]) == length + steps and int(cache_len[1]) == 1


def test_reference_at_positions_is_not_moved_by_what_follows(model):
    """The benchmark pads every sequence to one length and reads the
    reference at ``positions``: causal attention and per-token experts
    leave a position's logits where they were."""
    cfg, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(5), (13,), 0,
                                cfg.vocab_size)
    want, routes = reference.forward_logits(params, published(cfg), tokens,
                                            last=4)
    padded = jnp.concatenate([tokens, jnp.zeros((7,), tokens.dtype)])
    got, padded_routes = reference.forward_logits(
        params, published(cfg), padded, positions=jnp.arange(9, 13))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(padded_routes[:, :13], routes)


def test_a_family_member_without_pangus_flags_equals_the_reference():
    """No sandwich norm, softmax scores not renormalised, no leading
    dense layer, two shared experts: another member of the family is
    data."""
    cfg = mla_moe.config("tiny", dtype=jnp.float32, sandwich_norm=False,
                         scoring="softmax", norm_topk_prob=False,
                         n_dense_layers=0, n_shared_experts=2,
                         routed_scale=1.0)
    params = mla_moe.init(cfg, jax.random.PRNGKey(2))
    assert "dense" not in params and "post_attn_norm" not in params["moe"]
    tokens = jax.random.randint(jax.random.PRNGKey(4), (14,), 0,
                                cfg.vocab_size)
    hp = dict(published(cfg), scoring_func="softmax")
    want, _ = reference.forward_logits(params, hp, tokens, last=2)
    with jax.default_matmul_precision("highest"):
        logits, cache, cache_len = mla_moe.prefill(
            params, cfg, tokens[None, :13], mla_moe.init_cache(cfg, 1, 16))
        assert reference.rel_l2(logits[0], want[0]) < 1e-5
        logits, _, _ = mla_moe.decode_step(params, cfg, tokens[13:],
                                           cache, cache_len)
    assert reference.rel_l2(logits[0], want[1]) < 1e-5


def test_dense_decode_step_equals_the_paged_one(model):
    cfg, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(3), (11,), 0,
                                cfg.vocab_size)
    want, _ = reference.forward_logits(params, published(cfg), tokens)
    with jax.default_matmul_precision("highest"):
        _, cache, cache_len = mla_moe.prefill(
            params, cfg, tokens[None, :10], mla_moe.init_cache(cfg, 1, 16))
        logits, _, _ = mla_moe.decode_step(params, cfg, tokens[10:],
                                           cache, cache_len)
    assert reference.rel_l2(logits[0], want[0]) < 1e-5


# -- (b) the engine's greedy stream equals stepping the reference ----------------

def test_engine_greedy_stream_equals_stepping_the_reference(model):
    cfg, params = model
    prompt = [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(7), (13,), 0, cfg.vocab_size)]
    want, tokens = [], list(prompt)
    step = jax.jit(lambda t: reference.forward_logits(
        params, published(cfg), t)[0][0].argmax())
    for _ in range(6):
        want.append(int(step(jnp.asarray(tokens, jnp.int32))))
        tokens.append(want[-1])

    async def serve():
        engine, _ = engine_for(mla_moe, cfg, params, steps_per_tick=2)
        await engine.start()
        try:
            return (await asyncio.wait_for(
                engine.generate(prompt, max_new_tokens=6), 120.0),
                engine.stats())
        finally:
            await engine.stop()

    got, stats = asyncio.run(serve())
    assert got == want
    assert stats["kv_pool"]["attn_path"] == "gather"
    assert stats["kv_pool"]["bytes_per_token"] \
        == cfg.n_layers * cfg.cache_row * 4          # float32 here


# -- (c) absorbed equals expanded attention --------------------------------------

def test_absorbed_attention_equals_expanded(model):
    cfg, params = model
    attn = jax.tree.map(lambda leaf: leaf[0], params["moe"]["attn"])
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    seq = 9
    q_n = jax.random.normal(keys[0], (2, seq, cfg.n_heads, cfg.qk_nope_dim))
    q_r = jax.random.normal(keys[1], (2, seq, cfg.n_heads, cfg.qk_rope_dim))
    rows = jax.random.normal(keys[2], (2, seq, cfg.cache_row))
    with jax.default_matmul_precision("highest"):
        expanded = mla_moe.expanded_attention(attn, q_n, q_r, rows, cfg)
        # the last query of each sequence, against the rows as cached:
        # six rows of garbage follow, masked
        view = jnp.concatenate([rows, jnp.full((2, 6, cfg.cache_row), 1e4)],
                               axis=1)
        valid = jnp.arange(seq + 6)[None, :] < jnp.array([[seq], [seq]])
        absorbed = mla_moe.absorbed_attention(
            attn, q_n[:, -1], q_r[:, -1], view, valid, cfg)
    np.testing.assert_allclose(absorbed, expanded[:, -1], rtol=2e-5,
                               atol=2e-5)


def kernel_and_gathered_steps(monkeypatch, module, cfg, params, rung,
                              steps=2):
    """``steps`` paged decode steps of ``module`` at page 32, float32,
    through the sparse latent kernel (interpreted) and with
    ``select.sparse_latent_tileable`` refused (the gathered form), from
    one pool of seeded rows: slot 0 past two pages, slot 1 filling two
    pages exactly with its first new row, slot 2 starting a page with
    it, slot 3 inactive over slot 0's first page. ``rung`` "narrowed"
    gives the table 3 columns (the window ladder's narrowest rung that
    covers every slot), "whole" 8 with the unallocated sentinel after
    the live ones. Returns ({"kernel" | "gathered": [(logits, counters)
    a step]}, active)."""
    from gofr_tpu.ops import pallas

    page, num_pages = 32, 16
    columns = {"narrowed": 3, "whole": 8}[rung]
    cache_len = jnp.array([70, 63, 64, 5], jnp.int32)
    active = jnp.array([True, True, True, False])
    rng = np.random.default_rng(cfg.n_heads)
    ids = rng.permutation(num_pages)
    table = np.full((4, columns), num_pages, np.int32)
    table[:3, :3] = ids[:9].reshape(3, 3)
    table[3, 0] = table[0, 0]
    rows = jnp.asarray(rng.standard_normal(
        (cfg.n_layers, num_pages, page, cfg.cache_row)), jnp.float32)
    tokens = rng.integers(0, cfg.vocab_size, (steps, 4))
    assert pallas.sparse_latent_tileable(cfg.cache_row, cfg.n_heads, page)
    out = {}
    for form in ("kernel", "gathered"):
        if form == "gathered":
            monkeypatch.setattr(pallas, "sparse_latent_tileable",
                                lambda *_: False)
        step = jax.jit(lambda p, t, leaves, n: module.decode_step_paged(
            p, cfg, t, leaves, jnp.asarray(table), n, active,
            counters=True))
        leaves, n, out[form] = {"ckv": rows}, cache_len, []
        with jax.default_matmul_precision("highest"):
            for i in range(steps):
                logits, leaves, grown, counted = step(
                    params, jnp.asarray(tokens[i]), leaves, n)
                n = jnp.where(active, grown, n)
                out[form].append((np.asarray(logits), np.asarray(counted)))
    return out, np.asarray(active)


@pytest.mark.parametrize("rung", ["whole", "narrowed"])
@pytest.mark.parametrize("heads", [128, 32])
def test_paged_step_through_the_latent_kernel_equals_the_gathered_step(
        monkeypatch, heads, rung):
    """pangu's 128 heads and xing's 32 at page 32: the paged step's
    absorbed attention walks every live position in the sparse latent
    kernel and equals the gathered form's, active slot by active slot,
    over a whole table and a ladder rung's narrowed one; the inactive
    slot's logits are finite, and the step counts its layers' attention
    calls and the kernel's."""
    cfg = mla_moe.config("tiny", dtype=jnp.float32, n_heads=heads)
    params = mla_moe.init(cfg, jax.random.PRNGKey(4))
    out, active = kernel_and_gathered_steps(monkeypatch, mla_moe, cfg,
                                            params, rung)
    calls = mla_moe.STEP_COUNTERS.index("attn.calls")
    for (got, got_counts), (want, want_counts) in zip(out["kernel"],
                                                      out["gathered"]):
        assert reference.rel_l2(got[active], want[active]) < 1e-5
        assert np.isfinite(got).all()
        assert list(got_counts[calls:]) == [cfg.n_layers, cfg.n_layers]
        assert list(want_counts[calls:]) == [cfg.n_layers, 0]
        np.testing.assert_array_equal(got_counts[:calls],
                                      want_counts[:calls])


# -- (d) the shares add up -------------------------------------------------------

@pytest.mark.parametrize("grouped", [False, True],
                         ids=["batched", "grouped"])
def test_all_sixteen_shares_add_up_to_the_uncut_layer(grouped):
    whole = mla_moe.config("tiny", dtype=jnp.float32)      # 32 of 32 held
    params = mla_moe.init(whole, jax.random.PRNGKey(5))
    layer = jax.tree.map(lambda leaf: leaf[1], params["moe"])
    h = jax.random.normal(jax.random.PRNGKey(6), (24, whole.dim))
    with jax.default_matmul_precision("highest"):
        uncut, _, _ = mla_moe.moe_ffn(whole, layer, h)
        shared = mla_moe._swiglu(layer["shared"], h)
        total = shared                                      # counted once
        held_pairs = 0
        for rank in range(16):
            cfg = mla_moe.config("tiny", dtype=jnp.float32,
                                 n_held_experts=2, expert_rank=rank)
            share = dict(layer, experts=jax.tree.map(
                lambda leaf: leaf[2 * rank:2 * rank + 2], layer["experts"]))
            part, counters, _ = mla_moe.moe_ffn(cfg, share, h,
                                                grouped=grouped)
            total = total + (part - shared)
            held_pairs += int(counters[1])
            assert int(counters[0]) == 24 * whole.top_k
    np.testing.assert_allclose(total, uncut, rtol=1e-5, atol=1e-5)
    assert held_pairs == 24 * whole.top_k       # every pair held once
    # and the uncut layer is the reference's
    with jax.default_matmul_precision("highest"):
        want, _ = reference.expert_layer(layer, h, published(whole))
    np.testing.assert_allclose(uncut, want, rtol=1e-5, atol=1e-5)


# -- (d') a prefill reads its routed experts where they lie ------------------------

def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(inner)


def test_prefill_scans_no_expert_leaf_and_blocks_read_the_stack():
    """A layer scan that carried the routed experts' stack as a scanned
    operand would hand the grouped product's block loop a slice, and a
    loop's operand is a buffer: a copy of every held expert a layer a
    call (3 x 503 MB at pangu-ultra-moe-ep16's widths, PERF.md section
    6). At the tiny preset with a share held (8 of 32, 2 expert layers;
    8 so that no other leaf starts like the experts') no scan of
    ``prefill`` scans a leaf of shape (expert layers, held, ..), and
    inside the block loop each ``dynamic_slice`` of weights takes the
    four-dimensional stack."""
    cfg = mla_moe.config("tiny", n_held_experts=8)
    params = jax.eval_shape(lambda key: mla_moe.init(cfg, key),
                            jax.random.key(0))
    rows, bucket = 2, 16
    jaxpr = jax.make_jaxpr(lambda p, t, l: mla_moe.prefill(
        p, cfg, t, mla_moe.init_cache(cfg, rows, bucket), lengths=l))(
        params, jnp.zeros((rows, bucket), jnp.int32),
        jnp.full((rows,), bucket, jnp.int32)).jaxpr
    stack = (cfg.n_moe_layers, cfg.n_held_experts)
    scans = [eqn for eqn in _eqns(jaxpr) if eqn.primitive.name == "scan"]
    assert len(scans) >= 2                      # the two stacks, at least
    for scan in scans:
        first = scan.params["num_consts"] + scan.params["num_carry"]
        scanned = [var.aval.shape for var in scan.invars[first:]]
        assert not [shape for shape in scanned if shape[:2] == stack], scanned
    loops = [eqn for eqn in _eqns(jaxpr) if eqn.primitive.name == "while"]
    read = [eqn.invars[0].aval.shape
            for loop in loops for eqn in _eqns(loop.params["body_jaxpr"].jaxpr)
            if eqn.primitive.name == "dynamic_slice"
            and eqn.invars[0].aval.shape[-2:] in (
                (cfg.dim, cfg.moe_ffn_dim), (cfg.moe_ffn_dim, cfg.dim))]
    assert len(read) == 3 and all(shape[:2] == stack and len(shape) == 4
                                  for shape in read), read


# -- (e) no token is dropped -----------------------------------------------------

@pytest.mark.parametrize("grouped", [False, True],
                         ids=["batched", "grouped"])
def test_no_token_is_dropped_when_all_go_to_one_expert(grouped):
    cfg = mla_moe.config("tiny", dtype=jnp.float32, n_held_experts=4,
                         expert_rank=0, top_k=1, n_shared_experts=0)
    params = mla_moe.init(cfg, jax.random.PRNGKey(8))
    layer = jax.tree.map(lambda leaf: leaf[0], params["moe"])
    # a router that scores expert 2 highest for every token
    forced = jnp.zeros_like(layer["router"]).at[:, 2].set(1.0)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (40, cfg.dim)))
    layer = dict(layer, router=forced)
    with jax.default_matmul_precision("highest"):
        got, counters, ids = mla_moe.moe_ffn(cfg, layer, h, grouped=grouped)
        expert = jax.tree.map(lambda leaf: leaf[2], layer["experts"])
        want = cfg.routed_scale * mla_moe._swiglu(expert, h)
    assert (np.asarray(ids) == 2).all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # 40 pairs, all held, one expert hit and it holds all 40
    assert [int(c) for c in counters] == [40, 40, 1, 40, 1]


# -- (f) llama's pool is what it was ---------------------------------------------

@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8"])
def test_llama_pool_leaves_and_page_bytes_are_unchanged(kv_int8):
    cfg = llama.config("tiny", kv_int8=kv_int8)
    pool = PagePool(cfg, page=4, num_pages=6)
    shape = (cfg.n_layers, 6, 4, cfg.n_kv_heads, cfg.head_dim)
    if kv_int8:
        assert {n: (a.shape, a.dtype) for n, a in pool.leaves.items()} == {
            "k": (shape, jnp.int8), "v": (shape, jnp.int8),
            "ks": (shape[:-1], jnp.float32), "vs": (shape[:-1], jnp.float32)}
        assert float(pool.leaves["ks"].min()) == 1.0
        per_token = 2 * cfg.n_layers * cfg.n_kv_heads * (cfg.head_dim + 4)
    else:
        assert {n: (a.shape, a.dtype) for n, a in pool.leaves.items()} == {
            "k": (shape, cfg.dtype), "v": (shape, cfg.dtype)}
        per_token = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * 2
    assert not np.asarray(pool.leaves["k"]).any()
    assert pool.page_bytes == 4 * per_token
    assert PagePool._page_bytes(cfg, 4) == pool.page_bytes
    assert pool.stats()["bytes_per_token"] == per_token
    # llama declares no leaves of its own: the pool's k/v form is its
    assert not hasattr(llama, "cache_leaves")
    assert pool.leaf_specs == kv_leaf_specs(cfg)


def test_router_is_float32_on_a_bfloat16_hidden_state(model):
    """What the benchmark's router check holds the chip to: on one
    input the program's router chooses as the reference's does, and the
    reference with bfloat16 scores (the control) does not."""
    cfg, params = model
    h = jax.random.normal(jax.random.PRNGKey(3), (64, cfg.dim),
                          jnp.float32).astype(jnp.bfloat16)
    router = params["moe"]["router"][0]
    ids, weights = mla_moe.route(cfg, router, h)
    assert weights.dtype == jnp.float32
    np.testing.assert_allclose(weights.sum(-1), cfg.routed_scale, rtol=1e-5)
    want, _ = reference.route(router, h.astype(jnp.float32), published(cfg))
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want, -1))
    control, _ = reference.route(router, h.astype(jnp.float32),
                                 published(cfg), round_to=jnp.bfloat16)
    assert (np.sort(control, -1) != np.sort(want, -1)).any()


def test_latent_pool_page_bytes_follow_the_module(model):
    cfg, _ = model
    pool = PagePool(cfg, page=PAGE, budget_bytes=100_000,
                    leaf_specs=mla_moe.cache_leaves(cfg))
    per_token = cfg.n_layers * cfg.cache_row * 4
    assert pool.page_bytes == PAGE * per_token
    assert pool.num_pages == 100_000 // pool.page_bytes


@pytest.mark.parametrize("how,match", [
    (dict(prefix_cache=True), "prefix_cache needs llama"),
    (dict(draft_cfg=llama.config("tiny"), draft_params={}),
     "speculative decode needs llama"),
    (dict(mesh="a mesh"), "sharding specs are llama's"),
    (dict(page_pool="llama's"), "layers and leaves"),
])
def test_what_a_latent_module_cannot_use_is_refused_at_construction(
        model, how, match):
    cfg, params = model
    if how.get("page_pool"):
        how = dict(page_pool=PagePool(llama.config("tiny"), page=PAGE,
                                      num_pages=4))
    with pytest.raises(ValueError, match=match) as raised:
        engine_for(mla_moe, cfg, params, **how)
    assert "page_pool" in how or "mla_moe" in str(raised.value)


def test_kv_wire_refuses_a_latent_module_and_names_it(model):
    cfg, params = model
    engine, _ = engine_for(mla_moe, cfg, params)

    async def export():
        await engine.prefill_export([1, 2, 3])

    with pytest.raises(ValueError, match=r"mla_moe.*\['ckv'\].*kv_wire"):
        asyncio.run(export())
    with pytest.raises(ValueError, match="kv_wire"):
        asyncio.run(engine.adopt_kv(None, 4))


def test_a_module_without_its_entry_point_is_named():
    class Half:
        __name__ = "half"
        init_cache = prefill = decode_step = staticmethod(lambda *a: None)

    with pytest.raises(ValueError, match="half lacks .*decode_step_paged"):
        GenerationEngine(llama.config("tiny"), {}, paged_kv=True,
                         model_module=Half())


# -- (g) the admission bound -----------------------------------------------------

def test_admission_bound_splits_a_group_and_keeps_its_order(model):
    cfg, params = model

    async def serve(bound):
        engine, _ = engine_for(mla_moe, cfg, params, max_slots=8,
                               max_group_tokens=bound)
        groups = []
        prefill_fn = engine._prefill_fn

        def spy(nb, lb, biased=False):
            fn = prefill_fn(nb, lb, biased)

            def call(params, tokens, *rest):
                groups.append((nb, lb, [int(t) for t in tokens[:, 0]]))
                return fn(params, tokens, *rest)
            return call

        engine._prefill_fn = spy
        # six prompts of one bucket, queued before the loop starts: one
        # admission pass sees them all
        prompts = [[first] + [7] * 10 for first in range(1, 7)]
        tasks = [asyncio.ensure_future(
            engine.generate(p, max_new_tokens=2)) for p in prompts]
        await asyncio.sleep(0)
        await engine.start()
        try:
            outs = await asyncio.wait_for(asyncio.gather(*tasks), 120.0)
        finally:
            await engine.stop()
        return groups, outs

    unbounded, outs = asyncio.run(serve(None))
    assert [(nb, lb) for nb, lb, _ in unbounded] == [(8, 16)]
    # 16-token bucket, 64 tokens a group: four rows a group at most
    bounded, outs_bounded = asyncio.run(serve(64))
    assert [(nb, lb) for nb, lb, _ in bounded] == [(4, 16), (2, 16)]
    assert [first for _, _, firsts in bounded for first in firsts
            if first] == [1, 2, 3, 4, 5, 6]
    assert outs_bounded == outs


def test_warmup_skips_the_rungs_the_bound_makes_unreachable(model):
    cfg, params = model

    async def warm(bound):
        engine, _ = engine_for(mla_moe, cfg, params, max_slots=8,
                               max_group_tokens=bound)
        await engine.warmup(prompt_counts=tuple(engine._n_ladder), ks=(1,))
        return sorted((n, b) for n, b, _ in engine._prefill_fns)

    assert asyncio.run(warm(None)) == [(n, b) for n in (1, 2, 4, 8)
                                       for b in (8, 16)]
    assert asyncio.run(warm(32)) == [(1, 8), (1, 16), (2, 8), (2, 16),
                                     (4, 8)]


# -- (h) the step counters -------------------------------------------------------

def test_step_counters_add_up_through_the_engine(model):
    cfg, params = model

    async def serve():
        engine, container = engine_for(mla_moe, cfg, params,
                                       steps_per_tick=2)
        await engine.start()
        try:
            await asyncio.wait_for(asyncio.gather(*[
                engine.generate([3 + i] * 9, max_new_tokens=7)
                for i in range(3)]), 120.0)
            return engine.stats(), container.metrics
        finally:
            await engine.stop()

    stats, metrics = asyncio.run(serve())
    moe = stats["moe"]
    assert set(moe) == {"routed_pairs", "held_pairs", "experts_hit",
                        "hot_expert_pairs", "layer_steps"}
    # 3 requests x 6 decode steps (the first token is the prefill's) x
    # 2 expert layers x top-4
    assert moe["routed_pairs"] == 3 * 6 * cfg.n_moe_layers * cfg.top_k
    assert 0 < moe["held_pairs"] <= moe["routed_pairs"]
    assert moe["experts_hit"] <= cfg.n_held_experts * moe["layer_steps"]
    assert moe["hot_expert_pairs"] <= moe["held_pairs"]
    assert moe["hot_expert_pairs"] >= moe["held_pairs"] / cfg.n_held_experts
    assert moe["layer_steps"] % cfg.n_moe_layers == 0
    assert metrics.value("app_tpu_step_counter_total", model="generate",
                         counter="moe.routed_pairs") == moe["routed_pairs"]
    # every layer's attention a step; the tiny preset's 4 heads do not
    # tile the sparse latent kernel, so none ran in it
    steps = moe["layer_steps"] // cfg.n_moe_layers
    assert stats["attn"] == {"calls": steps * cfg.n_layers,
                             "kernel_calls": 0}
    assert metrics.value("app_tpu_step_counter_total", model="generate",
                         counter="attn.calls") == steps * cfg.n_layers


def test_llama_tick_has_no_counters_and_three_outputs():
    cfg = llama.config("tiny")
    params = llama.init(cfg, jax.random.PRNGKey(0))
    engine, _ = engine_for(llama, cfg, params)
    assert engine._step_counters == ()
    out = jax.eval_shape(
        engine._tick_fn(2, False, False), engine.params,
        engine.last_token,
        engine._pool.leaves, jnp.zeros((4, 8), jnp.int32), engine.cache_len,
        jnp.zeros((4,), bool))
    assert len(out) == 3 and out[0].shape == (2, 4)
    assert "moe" not in engine.stats()
