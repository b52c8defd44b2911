"""The sparse-attention member of the latent family (models/dsa_mla_moe.py,
ops/pallas/index_scores.py) against the benchmark's plain reference
(benchmark/reference_dsa_mla_moe.py: float32, highest precision, no
cache, no kernel, written from the equations).

Float32 at ``highest`` matmul precision on both sides, so the selection
and the routing cannot flip between the two and logits agree to ~1e-5.
The tiny preset selects 16 keys, so every context past 16 tokens
attends a selection:

(a) ``prefill`` logits, routing and each full layer's selection equal
    the reference's, short of the selection size and past it;
(b) ``prefill`` then ``decode_step_paged`` through a ``PagePool`` of
    both kinds (``ckv`` and ``ik`` in the full kind's pages, a window
    kind of its own width) equals the reference's full forward, and the
    decode steps select the reference's keys; the counters add up;
(c) the selection is the float32 exact top-k, ties in position order;
(d) the headwise gate scales each head by its own sigmoid and moves the
    result;
(e) the expert layer's shares, the shared expert counted once, add up
    to the uncut layer;
(f) the index kernel equals its gathered oracle (interpreted); the
    sparse latent kernel equals the gathered absorbed attention over the
    same selection, and a decode step through it equals the step through
    the gathered rows;
(g) the engine serves it: the greedy stream is the reference's choice,
    the window kind's pages go back to the pool behind the window, the
    counters reach ``stats()`` and ``app_tpu_step_counter_total``.
"""

import asyncio
import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from gofr_tpu.container import new_mock_container
from gofr_tpu.models import dsa_mla_moe, mla_moe
from gofr_tpu.models.experts import _swiglu, moe_ffn
from gofr_tpu.ops.attention import index_scores as gathered_index_scores
from gofr_tpu.ops.pallas import index_scores, sparse_latent_attention
from gofr_tpu.tpu.generate import GenerationEngine
from gofr_tpu.tpu.page_pool import PagePool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:        # the reference reads its sibling's route
    sys.path.insert(0, BENCH)


def _load(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("reference_dsa_mla_moe", "reference_dsa_mla_moe.py")

PAGE = 8


def published(cfg):
    """The reference reads the published keys of a configuration file."""
    return {"layer_types": list(cfg.layer_types),
            "rms_norm_eps": cfg.norm_eps,
            "num_attention_heads": cfg.n_heads,
            "qk_nope_head_dim": cfg.qk_nope_dim,
            "qk_rope_head_dim": cfg.qk_rope_dim,
            "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
            "rope_theta": cfg.rope_theta,
            "swa_num_attention_heads": cfg.swa_n_heads,
            "swa_qk_nope_head_dim": cfg.swa_qk_nope_dim,
            "swa_qk_rope_head_dim": cfg.swa_qk_rope_dim,
            "swa_v_head_dim": cfg.swa_v_head_dim,
            "swa_kv_lora_rank": cfg.swa_kv_lora_rank,
            "swa_rope_theta": cfg.swa_rope_theta,
            "sliding_window_size": cfg.sliding_window,
            "index_n_heads": cfg.index_n_heads,
            "index_head_dim": cfg.index_head_dim,
            "index_topk": cfg.index_topk,
            "index_norm_eps": cfg.index_norm_eps,
            "num_experts_per_tok": cfg.top_k,
            "n_routed_experts": cfg.n_held_experts,
            "expert_parallel_rank": cfg.expert_rank,
            "routed_scaling_factor": cfg.routed_scale,
            "norm_topk_prob": cfg.norm_topk_prob,
            "first_k_dense_replace": cfg.n_dense_layers}


@pytest.fixture(scope="module")
def model():
    cfg = dsa_mla_moe.config("tiny", dtype=jnp.float32)
    return cfg, jax.jit(lambda key: dsa_mla_moe.init(cfg, key))(
        jax.random.key(0))


def _tokens(cfg, seed, count):
    return jax.random.randint(jax.random.PRNGKey(seed), (count,), 0,
                              cfg.vocab_size)


def _selected(spots, length):
    """Positions (k,) with -1 for none -> a (length,) bool row."""
    row = np.zeros((length,), bool)
    spots = np.asarray(spots)
    row[spots[spots >= 0]] = True
    return row


# -- (a), (b): against the reference's full forward ---------------------------

@pytest.mark.parametrize("length", [12, 16, 40, 64])
def test_prefill_logits_routing_and_selection_equal_the_reference(
        model, length):
    """16 keys selected: 12 and 16 attend every key, 40 and 64 select
    (64: the queries go in four steps of 16)."""
    cfg, params = model
    tokens = _tokens(cfg, length, length)
    want, want_routes, want_picked = reference.forward_logits(
        params, published(cfg), tokens)
    with jax.default_matmul_precision("highest"):
        logits, _, cache_len, routes, picked = dsa_mla_moe.prefill(
            params, cfg, tokens[None],
            dsa_mla_moe.init_cache(cfg, 1, length), routes=True,
            selections=True)
    assert reference.rel_l2(logits[0], want[0]) < 1e-5
    assert int(cache_len[0]) == length
    np.testing.assert_array_equal(np.sort(routes[:, 0], -1),
                                  np.sort(want_routes, -1))
    np.testing.assert_array_equal(picked[:, 0], want_picked)
    counts = np.asarray(picked[:, 0]).sum(-1)
    assert (counts == np.minimum(np.arange(length) + 1,
                                 cfg.index_topk)).all()


def test_a_bucket_that_tiles_attends_its_selection_in_the_flash_kernel():
    """Values of 128 lanes and a bucket of 1024 rows: the full layers'
    selection reaches the Pallas flash kernel as its mask (interpreted
    here), and the prompt's logits, routing and selections are still
    the reference's."""
    cfg = dsa_mla_moe.config("tiny", dtype=jnp.float32, v_head_dim=128,
                             max_seq_len=1024)
    assert cfg.view("full").flash_block(1024) == 1024
    params = jax.jit(lambda key: dsa_mla_moe.init(cfg, key))(
        jax.random.key(1))
    tokens = _tokens(cfg, 7, 1024)
    want, want_routes, want_picked = reference.forward_logits(
        params, published(cfg), tokens)

    def run(tokens):
        return dsa_mla_moe.prefill(
            params, cfg, tokens, dsa_mla_moe.init_cache(cfg, 1, 1024),
            routes=True, selections=True)

    with jax.default_matmul_precision("highest"):
        text = str(jax.make_jaxpr(run)(tokens[None]))
        logits, _, _, routes, picked = jax.jit(run)(tokens[None])
    # the three full layers, each through the kernel with its mask (a
    # call for the chip and one interpreted, as the lowering target picks)
    assert text.count("pallas_call") == 2 * 3
    assert reference.rel_l2(logits[0], want[0]) < 1e-5
    np.testing.assert_array_equal(np.sort(routes[:, 0], -1),
                                  np.sort(want_routes, -1))
    np.testing.assert_array_equal(picked[:, 0], want_picked)


def _pool_and_table(cfg, small, bucket, columns, page=PAGE, slots=1):
    """Both kinds' prompt rows put into pages of a pool, in another order
    than the sequence's, through the pool's own tables; the first
    ``slots`` slots hold pages, the last slot none."""
    pool = PagePool(cfg, page=page,
                    num_pages={"full": slots * columns + 2,
                               "window": slots * columns + 2},
                    leaf_specs=dsa_mla_moe.cache_leaves(cfg))
    tables, leaves = {}, dict(pool.leaves)
    for kind in ("full", "window"):
        table = np.full((slots + 1, columns), pool.sentinel_of(kind),
                        np.int32)
        for b in range(slots):
            ids = pool.alloc(columns, kind=kind)[::-1]
            table[b] = ids
            leaves[kind] = {
                name: leaf.at[:, jnp.asarray(ids[:bucket // page])]
                .set(small[kind][name][:, b].reshape(
                    leaf.shape[0], bucket // page, page, *leaf.shape[3:]))
                for name, leaf in leaves[kind].items()}
        tables[kind] = jnp.asarray(table)
    return leaves, tables


@pytest.mark.parametrize("length", [9, 15, 30])
def test_prefill_then_paged_decode_equals_the_reference(model, length):
    """Decode steps through both kinds' pages: 9 + 8 stays under the
    selection of 16, 15 crosses it, 30 starts past it; the window of 12
    is passed in every case."""
    cfg, params = model
    steps, bucket, columns = 8, 32, 6
    tokens = _tokens(cfg, 100 + length, length + steps)
    positions = jnp.arange(length - 1, length + steps)
    want, _, want_picked = reference.forward_logits(
        params, published(cfg), tokens, positions=positions)
    prefill = jax.jit(lambda p, t, n: dsa_mla_moe.prefill(
        p, cfg, t, dsa_mla_moe.init_cache(cfg, 2, bucket), lengths=n))
    one_step = jax.jit(lambda p, t, leaves, table, n, active:
                       dsa_mla_moe.decode_step_paged(
                           p, cfg, t, leaves, table, n, active,
                           counters=True, selections=True))
    with jax.default_matmul_precision("highest"):
        padded = jnp.zeros((2, bucket), jnp.int32).at[0, :length].set(
            tokens[:length])
        logits, small, cache_len = prefill(params, padded,
                                           jnp.array([length, 1]))
        assert reference.rel_l2(logits[0], want[0]) < 1e-5
        leaves, tables = _pool_and_table(cfg, small, bucket, columns)
        active = jnp.array([True, False])
        for step in range(steps):
            fed = jnp.array([tokens[length + step], 0])
            logits, leaves, grown, counted, picked = one_step(
                params, fed, leaves, tables, cache_len, active)
            live = length + step + 1
            assert reference.rel_l2(logits[0], want[step + 1]) < 1e-5
            for layer in range(3):
                np.testing.assert_array_equal(
                    _selected(picked[layer, 0], live),
                    want_picked[layer, live - 1, :live])
            names = dsa_mla_moe.STEP_COUNTERS
            got = dict(zip(names, np.asarray(counted).tolist()))
            assert got["dsa.rows_live"] == 3 * live
            assert got["dsa.rows_selected"] == 3 * min(live,
                                                       cfg.index_topk)
            assert got["dsa.calls"] == 3
            assert got["attn.window_rows"] == 3 * min(live,
                                                      cfg.sliding_window)
            assert got["dsa.kernel_calls"] == 0          # 4 heads: gathered
            assert got["moe.layer_steps"] == 5
            cache_len = jnp.where(active, grown, cache_len)
    assert int(cache_len[0]) == length + steps and int(cache_len[1]) == 1


def test_window_reads_no_page_behind_it(model):
    """The window kind's columns behind the window hold the sentinel (the
    engine gave the pages back) and NaN-poisoned pages elsewhere: the
    step reads neither."""
    cfg, params = model
    length, bucket, columns = 30, 32, 6
    tokens = _tokens(cfg, 5, length + 1)
    want, _, _ = reference.forward_logits(params, published(cfg), tokens)
    with jax.default_matmul_precision("highest"):
        _, small, cache_len = dsa_mla_moe.prefill(
            params, cfg, jnp.zeros((2, bucket), jnp.int32).at[0, :length]
            .set(tokens[:length]), dsa_mla_moe.init_cache(cfg, 2, bucket),
            lengths=jnp.array([length, 1]))
        leaves, tables = _pool_and_table(cfg, small, bucket, columns)
        # position 30 reads 19..30: columns 2 and 3 only
        window = np.array(tables["window"])
        behind = window[0, :2].copy()
        window[0, :2] = columns + 2                    # the kind's sentinel
        tables["window"] = jnp.asarray(window)
        leaves["window"] = {"ckv": leaves["window"]["ckv"].at[:, behind]
                            .set(jnp.nan)}
        logits, *_ = dsa_mla_moe.decode_step_paged(
            params, cfg, jnp.array([tokens[length], 0]), leaves, tables,
            cache_len, jnp.array([True, False]))
    assert reference.rel_l2(logits[0], want[0]) < 1e-5


def test_dense_decode_step_equals_the_reference(model):
    cfg, params = model
    tokens = _tokens(cfg, 7, 40)
    want, _, _ = reference.forward_logits(params, published(cfg), tokens)
    with jax.default_matmul_precision("highest"):
        _, cache, cache_len = dsa_mla_moe.prefill(
            params, cfg, tokens[None, :39], dsa_mla_moe.init_cache(cfg, 1, 48))
        logits, _, grown = dsa_mla_moe.decode_step(
            params, cfg, tokens[39:], cache, cache_len)
    assert int(grown[0]) == 40
    assert reference.rel_l2(logits[0], want[0]) < 1e-5


# -- (c) the selection ------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 5, 16, 40])
def test_selection_is_the_exact_top_k_in_float32(k):
    """Integer scores tie often, and rows run from fewer ``ok`` keys than
    ``k`` to many more: the selection is ``lax.top_k``'s set, ties in
    position order, and every ``ok`` key of a row that has fewer."""
    rng = np.random.default_rng(k)
    scores = jnp.asarray(rng.integers(-4, 5, (24, 48)).astype(np.float32)
                         * rng.choice([1.0, 1e-30, 1e30], (24, 48)))
    ok = jnp.asarray(rng.random((24, 48)) < 0.7)
    got = np.asarray(dsa_mla_moe._top_mask(scores, ok, k))
    top, spots = lax.top_k(jnp.where(ok, scores, -jnp.inf), min(k, 48))
    want = np.zeros_like(got)
    for r in range(24):
        want[r, np.asarray(spots[r])[np.asarray(top[r]) > -np.inf]] = True
    np.testing.assert_array_equal(got, want)


# -- (d) the gate -----------------------------------------------------------------

def test_the_gate_scales_each_head_by_its_own_sigmoid(model):
    cfg, params = model
    attn = params["layers"][1]["attn"]
    view = cfg.view("full")
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 5, cfg.dim))
    out = jax.random.normal(jax.random.PRNGKey(4),
                            (2, 5, view.n_heads * view.v_head_dim))
    got = dsa_mla_moe._gate(attn, h, out, view).reshape(
        2, 5, view.n_heads, view.v_head_dim)
    gate = jax.nn.sigmoid(h @ attn["w_gate"])
    for head in range(view.n_heads):
        np.testing.assert_allclose(
            got[:, :, head], out.reshape(got.shape)[:, :, head]
            * gate[:, :, head, None], rtol=1e-6)
    tokens = _tokens(cfg, 11, 24)
    want, _, _ = reference.forward_logits(params, published(cfg), tokens)
    ungated, _, _ = reference.forward_logits(params, published(cfg), tokens,
                                             no_gates=True)
    assert reference.rel_l2(ungated, want) > 0.05


# -- (e) the chip's share of the experts ------------------------------------------

@pytest.mark.parametrize("grouped", [True, False], ids=["prefill", "decode"])
def test_every_share_of_the_experts_adds_up_to_the_whole_layer(grouped):
    """Four chips of 8 held of 32: each share's result less the shared
    expert (which every chip computes alike), summed, plus the shared
    expert once, is the uncut layer's result."""
    whole_cfg = dsa_mla_moe.config("tiny", dtype=jnp.float32)
    params = dsa_mla_moe.init(whole_cfg, jax.random.key(1))
    layer = params["layers"][2]
    h = jax.random.normal(jax.random.PRNGKey(2), (10, whole_cfg.dim))
    with jax.default_matmul_precision("highest"):
        whole, _, _ = moe_ffn(whole_cfg, layer, h, grouped=grouped)
        parts = []
        for rank in range(4):
            cfg = dataclasses.replace(whole_cfg, n_held_experts=8,
                                      expert_rank=rank)
            share = dict(layer, experts={
                name: leaf[rank * 8:(rank + 1) * 8]
                for name, leaf in layer["experts"].items()})
            parts.append(moe_ffn(cfg, share, h, grouped=grouped)[0])
        shared = _swiglu(layer["shared"], h)
    total = sum(part - shared for part in parts) + shared
    np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-5)


# -- (f) the index kernel ---------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_index_kernel_equals_its_oracle(dtype):
    """The kernel interpreted against the gathered scores: slots of no
    key, of part of a page, of part of a block and of the whole table;
    pages in another order than the table's, layer 1 of 2, dead table
    entries the sentinel."""
    slots, heads, dim, page, columns, pages = 4, 8, 128, 16, 20, 48
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.standard_normal((2, pages, page, dim)), dtype)
    lengths = jnp.array([0, 7, 200, columns * page], jnp.int32)
    table = np.full((slots, columns), pages, np.int32)
    free = rng.permutation(pages)
    taken = 0
    for b, length in enumerate(np.asarray(lengths)):
        used = -(-int(length) // page)
        table[b, :used] = free[taken:taken + used] % pages
        taken = min(taken + used, pages - columns)
    table = jnp.asarray(table)
    q = jnp.asarray(rng.standard_normal((slots, heads, dim)), dtype)
    w = jnp.asarray(rng.standard_normal((slots, heads)), jnp.float32)
    got = index_scores(q, w, keys, table, lengths, 1, interpret=True)
    want = gathered_index_scores(q, w, keys[1], table, lengths)
    assert got.shape == want.shape == (slots, columns * page)
    finite = np.isfinite(np.asarray(want))
    np.testing.assert_array_equal(np.isfinite(np.asarray(got)), finite)
    np.testing.assert_allclose(np.asarray(got)[finite],
                               np.asarray(want)[finite], rtol=1e-5,
                               atol=1e-4)


# a full layer's latent geometry that the sparse latent kernel tiles:
# heads in whole sublanes, a row of one lane tile (64 + 16, padded)
_VIEW = mla_moe.MlaMoeConfig(n_layers=1, n_dense_layers=0, n_heads=8,
                             kv_lora_rank=64, qk_nope_dim=16, qk_rope_dim=16,
                             v_head_dim=32, dtype=jnp.float32)


@pytest.mark.parametrize("case", ["below-page", "below-k", "at-k", "past-k",
                                  "ties", "shuffled", "inactive"])
def test_sparse_latent_kernel_equals_the_gathered_attention(case):
    """The kernel interpreted against ``lax.top_k``'s positions, the rows
    gathered through the table and ``mla_moe.absorbed_attention``, a
    selection of 40 in pages of 16 (blocks of 1024 positions): slot 0 at
    a length below a page, below the selection, at it, or past it over
    three blocks; its scores normal, or whole numbers that tie at the
    40th (as keys every indexer head scores below zero tie at 0); its
    pages in table order or shuffled over the pool; or inactive (length
    0), whose weighted latent must be finite zeros. Slot 1 selects 40
    of 1500 over two blocks after it in the same ring, on layer 1 of 2."""
    view, page, columns, pages, k = _VIEW, 16, 160, 256, 40
    length = {"below-page": 9, "below-k": 30, "at-k": 40,
              "inactive": 0}.get(case, 2300)
    lengths = jnp.array([length, 1500], jnp.int32)
    rng = np.random.default_rng(len(case))
    rows = jnp.asarray(rng.standard_normal((2, pages, page, view.cache_row)),
                       jnp.float32)
    ids = rng.permutation(pages) if case == "shuffled" else np.arange(pages)
    table = np.full((2, columns), pages, np.int32)
    taken = 0
    for b, n in enumerate(np.asarray(lengths)):
        used = -(-int(n) // page)
        table[b, :used] = ids[taken:taken + used]
        taken += used
    table = jnp.asarray(table)
    if case == "ties":
        scores = np.maximum(rng.integers(-3, 3, (2, columns * page)), 0)
    else:
        scores = rng.standard_normal((2, columns * page))
    scores = jnp.where(jnp.arange(columns * page) < lengths[:, None],
                       jnp.asarray(scores, jnp.float32), -jnp.inf)
    heads = view.n_heads
    attn = {name: jnp.asarray(rng.standard_normal(
                (heads, view.kv_lora_rank, width)) / 8, jnp.float32)
            for name, width in (("w_uk", view.qk_nope_dim),
                                ("w_uv", view.v_head_dim))}
    q_n = jnp.asarray(rng.standard_normal((2, heads, view.qk_nope_dim)),
                      jnp.float32)
    q_r = jnp.asarray(rng.standard_normal((2, heads, view.qk_rope_dim)),
                      jnp.float32)
    with jax.default_matmul_precision("highest"):
        top, spots = lax.top_k(scores, k)
        gathered = rows[1, jnp.take_along_axis(table, spots // page, axis=1),
                        spots % page]
        want = mla_moe.absorbed_attention(attn, q_n, q_r, gathered,
                                          top > -jnp.inf, view)
        u = sparse_latent_attention(
            mla_moe.absorbed_query(attn, q_n, q_r, view),
            dsa_mla_moe._top_mask(scores, scores > -jnp.inf, k), rows,
            table, lengths, 1, rank=view.kv_lora_rank,
            scale=view.softmax_scale, interpret=True)
        got = jnp.einsum("bhc,hcd->bhd", u, attn["w_uv"]).reshape(2, -1)
    assert u.shape == (2, heads, view.kv_lora_rank)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-5, atol=1e-5)
    if case == "inactive":
        assert np.isfinite(np.asarray(u[0])).all()
        assert not np.asarray(u[0]).any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_decode_step_through_the_sparse_kernel_equals_the_gathered_step(
        monkeypatch, dtype):
    """Eight heads and pages of 16: the full layers attend through the
    sparse latent kernel (interpreted), and the same steps with the
    predicate refused gather ``lax.top_k``'s rows. Slot 0 selects 16 of
    41-43 rows, slot 1 holds 10-12, slot 2 is inactive. The selections
    are the same set (every full layer in float32; the first, whose
    scores are the same numbers, in bfloat16), the logits equal within
    the dtype's rounding, and the kernel's calls are counted."""
    from gofr_tpu.ops import pallas

    cfg = dsa_mla_moe.config("tiny", dtype=dtype, n_heads=8)
    params = jax.jit(lambda key: dsa_mla_moe.init(cfg, key))(
        jax.random.key(2))
    page, bucket, columns, steps = 16, 48, 4, 3
    lengths = np.array([40, 9])
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(9), (2, bucket + steps), 0, cfg.vocab_size))
    active = jnp.array([True, True, False])

    def run(kernel):
        if not kernel:
            monkeypatch.setattr(pallas, "sparse_latent_tileable",
                                lambda *_: False)
        step = jax.jit(lambda p, t, leaves, table, n: (
            dsa_mla_moe.decode_step_paged(p, cfg, t, leaves, table, n,
                                          active, counters=True,
                                          selections=True)))
        with jax.default_matmul_precision("highest"):
            prompt = jnp.asarray(np.where(np.arange(bucket) < lengths[:, None],
                                          tokens[:, :bucket], 0))
            _, small, cache_len = dsa_mla_moe.prefill(
                params, cfg, prompt, dsa_mla_moe.init_cache(cfg, 2, bucket),
                lengths=jnp.asarray(lengths))
            leaves, tables = _pool_and_table(cfg, small, bucket, columns,
                                             page=page, slots=2)
            cache_len = jnp.concatenate([cache_len, jnp.zeros((1,),
                                                              jnp.int32)])
            out = []
            for i in range(steps):
                fed = jnp.asarray(np.append(tokens[np.arange(2), lengths + i],
                                            0))
                logits, leaves, grown, counted, picked = step(
                    params, fed, leaves, tables, cache_len)
                cache_len = jnp.where(active, grown, cache_len)
                got = dict(zip(dsa_mla_moe.STEP_COUNTERS,
                               np.asarray(counted).tolist()))
                out.append((np.asarray(logits[:2], np.float32),
                            np.asarray(picked), got))
        monkeypatch.undo()
        return out

    for (logits, picked, got), (want, want_picked, want_got) in zip(
            run(True), run(False)):
        assert got["dsa.kernel_calls"] == 3
        assert want_got["dsa.kernel_calls"] == 0
        assert {name: n for name, n in got.items()
                if name != "dsa.kernel_calls"} == {
            name: n for name, n in want_got.items()
            if name != "dsa.kernel_calls"}
        layers = 3 if dtype == jnp.float32 else 1
        for layer in range(layers):
            for b in range(2):
                assert (set(picked[layer, b][picked[layer, b] >= 0])
                        == set(want_picked[layer, b][want_picked[layer, b]
                                                     >= 0]))
        tolerance = 1e-5 if dtype == jnp.float32 else 2e-2
        for b in range(2):
            assert reference.rel_l2(logits[b], want[b]) < tolerance


# -- (g) through the engine ---------------------------------------------------------

def test_the_engine_serves_it_and_releases_the_window(model):
    cfg, params = model
    container = new_mock_container()
    engine = GenerationEngine(
        cfg, params, model_module=dsa_mla_moe, paged_kv=True, kv_page=PAGE,
        max_slots=2, max_len=96, prompt_buckets=(16, 32), steps_per_tick=2,
        metrics=container.metrics, model_name="dsa")
    prompt = [int(t) for t in _tokens(cfg, 21, 27)]

    async def serve():
        await engine.start()
        try:
            stream = await engine.generate_stream(prompt, max_new_tokens=30)
            return [token async for token in stream]
        finally:
            await engine.stop()

    served = asyncio.run(serve())
    assert len(served) == 30
    sequence = jnp.asarray(prompt + served[:-1], jnp.int32)
    want, _, _ = reference.forward_logits(
        params, published(cfg), sequence,
        positions=jnp.arange(len(prompt) - 1, len(sequence)))
    # teacher-forced on what was served: nearly every served token is the
    # reference's choice and none lies far below it. The engine runs K = 1
    # and K = 2 tick programs as its host's timing has it, whose float32
    # sums go in other orders: now and then two index scores that nearly
    # tie at a row's 16th swap, and a selection of 16 keys moves a logit
    # by a fifth of a deviation (a wrong window or selection moves every
    # token by several)
    want = np.asarray(want)
    rows = np.arange(len(served))
    below = (want.max(-1) - want[rows, served]) / want.std(-1)
    assert below.max() < 1.0, below.max()
    assert (want.argmax(-1) == np.asarray(served)).mean() >= 0.9
    stats = engine.stats()
    assert stats["kv_pool"]["kinds"]["window"]["freed_behind"] > 0
    assert stats["dsa"]["calls"] == 3 * (len(served) - 1)
    assert stats["dsa"]["rows_selected"] < stats["dsa"]["rows_live"]
    assert stats["attn"]["window_rows"] > 0
    from gofr_tpu.metrics.exposition import render_prometheus
    text = render_prometheus(container.metrics)
    assert 'counter="dsa.rows_selected"' in text
    assert 'counter="attn.window_rows"' in text


@pytest.mark.parametrize("given", [
    {"prefix_cache": True}, {"mesh": "mesh"}, {"draft": True},
    {"paged_kv": False}], ids=["prefix", "mesh", "speculation", "dense"])
def test_the_engine_refuses_what_the_module_cannot_serve(model, given):
    cfg, params = model
    kwargs = dict(model_module=dsa_mla_moe, paged_kv=True, kv_page=PAGE,
                  max_slots=2, max_len=96, prompt_buckets=(16, 32))
    if given.get("mesh"):
        from jax.sharding import Mesh
        kwargs["mesh"] = Mesh(np.array(jax.devices()[:1]), ("tp",))
    elif given.get("draft"):
        from gofr_tpu.models import llama
        draft = llama.config("tiny")
        kwargs.update(draft_cfg=draft, draft_params=llama.init(
            draft, jax.random.PRNGKey(0)))
    else:
        kwargs.update(given)
    with pytest.raises(ValueError, match="dsa_mla_moe"):
        GenerationEngine(cfg, params, **kwargs)


def test_kv_wire_is_refused_by_name(model):
    cfg, params = model
    engine = GenerationEngine(cfg, params, model_module=dsa_mla_moe,
                              paged_kv=True, kv_page=PAGE, max_slots=2,
                              max_len=96, prompt_buckets=(16, 32))
    assert "dsa_mla_moe" in engine._kv_wire_refusal


@pytest.mark.parametrize("head_dim,heads,page,tiles", [
    (128, 64, 32, True), (128, 64, 16, True), (16, 4, 8, False),
    (128, 60, 32, False), (128, 64, 10240, False)],
    ids=["cell", "page16", "tiny", "heads", "dense-cache"])
def test_which_shapes_take_the_index_kernel(head_dim, heads, page, tiles):
    """The dense cache's decode step is a page of the whole length: no
    block of the kernel's ring holds it, so it takes the gathered form."""
    from gofr_tpu.ops.pallas import index_tileable

    assert index_tileable(head_dim, heads, page) is tiles


@pytest.mark.parametrize("cache_row,heads,page,tiles", [
    (640, 128, 32, True), (640, 128, 16, True), (128, 4, 8, False),
    (128, 8, 16, True), (640, 60, 32, False), (576, 128, 32, False),
    (640, 128, 10240, False)],
    ids=["cell", "page16", "tiny", "wide", "heads", "row", "dense-cache"])
def test_which_shapes_take_the_sparse_latent_kernel(cache_row, heads, page,
                                                   tiles):
    """The cell's full layers take the kernel; the tiny preset's four
    heads and the dense cache's page of the whole length take the
    gathered rows."""
    from gofr_tpu.ops.pallas import sparse_latent_tileable

    assert sparse_latent_tileable(cache_row, heads, page) is tiles
