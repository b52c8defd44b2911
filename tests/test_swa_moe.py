"""Window and global attention layers over one page pool with a table a
layer kind, and the shared expert layer's second client
(models/swa_moe.py, ISSUE 31), against the benchmark's plain reference
(benchmark/reference_swa_moe.py: float32, highest precision, no cache,
no kernel, one expert at a time, four shared experts kept four).

Everything is float32 at ``highest`` matmul precision on both sides
unless a test says otherwise, so routing cannot flip between the two and
logits agree to ~1e-6 (the tolerance is 2e-5: float32 sums in another
order over 8 layers):

(a) ``prefill`` equals the reference's full forward, at lengths past the
    window, alone and right-padded in a batch;
(b) ``prefill`` then ``decode_step_paged`` through a ``PagePool`` of both
    kinds equals the reference over a context of three windows, with the
    window kind's pages given back as the slot decodes past them and
    taken by a second slot: no logit of either moves;
(c) the ragged kernel with a lower bound equals the gather oracle bit
    for bit with every page outside the live range poisoned;
(d) the shares add up: every rank's routed part plus the shared experts
    once is the uncut layer;
(e) ``PagePool`` by kind: alloc, release, release_behind, reset, stats;
    one kind is today's pool;
(f) the engine serves the module token for token the same through the
    kernel and through the gather path, gives window pages back, and
    names what it refuses;
(g) rotary's two pairings, LayerNorm without bias, banded attention.
"""

import asyncio
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import llama, mla_moe, swa_moe
from gofr_tpu.ops import (apply_rope, attention, banded_attention,
                          layer_norm, paged_decode_attention, rope_table)
from gofr_tpu.ops.pallas import ragged_paged_decode_attention
from gofr_tpu.tpu.generate import GenerationEngine
from gofr_tpu.tpu.page_pool import PagePool, cache_kinds, kv_leaf_specs

_spec = importlib.util.spec_from_file_location(
    "reference_swa_moe", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "reference_swa_moe.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

PAGE = 8
TOL = 2e-5      # float32 both sides; sums in another order over 8 layers


def published(cfg):
    """The reference reads the published keys of a configuration file."""
    return {"num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "layer_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "sliding_window": cfg.sliding_window,
            "layer_types": list(cfg.layer_types),
            "intermediate_size": cfg.moe_ffn_dim,
            "num_experts": cfg.n_held_experts,
            "expert_parallel_rank": cfg.expert_rank,
            "num_experts_per_tok": cfg.top_k,
            "num_shared_experts": cfg.n_shared_experts,
            "shared_expert_combination_strategy": "average",
            "norm_topk_prob": cfg.norm_topk_prob, "logit_scale": 1}


@pytest.fixture(scope="module")
def model():
    """One chip's share: 4 of 16 routed experts, rank 1; window 16, two
    periods of (sliding, sliding, sliding, full)."""
    cfg = swa_moe.config("tiny", dtype=jnp.float32, n_held_experts=4,
                         expert_rank=1)
    return cfg, swa_moe.init(cfg, jax.random.PRNGKey(0))


def tokens_of(seed, n, vocab=256):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, vocab)


# -- (a) prefill ----------------------------------------------------------------

@pytest.mark.parametrize("length", [7, 16, 40, 48])
def test_prefill_logits_equal_the_reference(model, length):
    cfg, params = model
    tokens = tokens_of(length, length)
    want, routes = reference.forward_logits(params, published(cfg), tokens)
    got, cache, cache_len, chosen = swa_moe.prefill(
        params, cfg, tokens[None], swa_moe.init_cache(cfg, 1, length),
        routes=True)
    assert reference.rel_l2(got[0], want[0]) < TOL
    assert int(cache_len[0]) == length
    assert (np.sort(chosen[:, 0], -1) == np.sort(routes, -1)).all()
    assert set(cache) == {"window", "full"}
    assert cache["window"]["k"].shape == (6, 1, length, 2, 16)
    assert cache["full"]["v"].shape == (2, 1, length, 2, 16)


def test_prefill_takes_the_flash_kernel_where_it_tiles_and_equals_the_reference():
    """Heads of 128 and a prompt of whole 128-row tiles: the prefill's
    attention is the Pallas flash kernel given the window (interpreted
    here), else the same band in plain XLA; either way the reference's
    logits. The window (160) ends inside a block."""
    cfg = swa_moe.config("tiny", dtype=jnp.float32, n_layers=4,
                         layer_types=swa_moe.PRESETS["tiny"].layer_types[:4],
                         head_dim=128, n_heads=4, n_kv_heads=1,
                         sliding_window=160, n_held_experts=4,
                         max_seq_len=256)
    params = swa_moe.init(cfg, jax.random.PRNGKey(3))
    tokens = tokens_of(11, 256)
    text = str(jax.make_jaxpr(lambda t: swa_moe.prefill(
        params, cfg, t, swa_moe.init_cache(cfg, 1, 256)))(tokens[None]))
    assert text.count("pallas_call") >= 1
    short = str(jax.make_jaxpr(lambda t: swa_moe.prefill(
        params, cfg, t, swa_moe.init_cache(cfg, 1, 48)))(tokens[None, :48]))
    assert "pallas_call" not in short
    want, _ = reference.forward_logits(params, published(cfg), tokens)
    got, _, _ = swa_moe.prefill(params, cfg, tokens[None],
                                swa_moe.init_cache(cfg, 1, 256))
    assert reference.rel_l2(got[0], want[0]) < TOL


def test_prefill_of_a_padded_batch_equals_each_prompt_alone(model):
    cfg, params = model
    lengths = [40, 9, 23]
    rows = [tokens_of(10 + i, n) for i, n in enumerate(lengths)]
    padded = jnp.stack([jnp.pad(r, (0, 48 - r.shape[0])) for r in rows])
    got, _, cache_len = swa_moe.prefill(
        params, cfg, padded, swa_moe.init_cache(cfg, 3, 48),
        lengths=jnp.asarray(lengths))
    assert cache_len.tolist() == lengths
    for row, tokens in enumerate(rows):
        want, _ = reference.forward_logits(params, published(cfg), tokens)
        assert reference.rel_l2(got[row], want[0]) < TOL


@pytest.mark.parametrize("switch", ["ignore_window", "rope_full"])
def test_a_reference_without_the_mechanism_is_another_model(model, switch):
    """The controls of the benchmark's cell: with the window ignored on
    the sliding layers, or rotary on the full layers too, the reference
    gives other logits than the program past the window."""
    cfg, params = model
    tokens = tokens_of(3, 48)
    got, _, _ = swa_moe.prefill(params, cfg, tokens[None],
                                swa_moe.init_cache(cfg, 1, 48))
    other, _ = reference.forward_logits(params, published(cfg), tokens,
                                        **{switch: True})
    assert reference.rel_l2(got[0], other[0]) > 0.05


# -- (b) paged decode past the window, pages given back and reused ---------------

def _put(pool, tables, slot, small, row, first_cols, length):
    """Scatter row ``row`` of a prefill's small cache into the pages of
    ``tables[kind][slot]``, a window kind's from its first column on."""
    leaves = {}
    for kind, planes in pool.leaves.items():
        first = first_cols[kind]
        cols = np.asarray(tables[kind][slot, first:-(-length // PAGE)])
        pad = len(cols) * PAGE + first * PAGE
        leaves[kind] = {}
        for name, plane in planes.items():
            rows = small[kind][name][:, row]
            rows = jnp.pad(rows, ((0, 0), (0, max(0, pad - rows.shape[1])),
                                  (0, 0), (0, 0)))[:, first * PAGE:pad]
            leaves[kind][name] = plane.at[:, cols].set(rows.reshape(
                plane.shape[0], len(cols), PAGE, *plane.shape[3:]))
    pool.leaves = leaves


@pytest.mark.parametrize("ragged", [False, True], ids=["gather", "ragged"])
def test_paged_decode_past_the_window_with_pages_given_back(model, ragged):
    """Slot 0: a 40-token prompt decoded to 64 tokens (four windows of
    16) through a pool whose window kind holds 6 pages, fewer than its 8
    columns: the pages behind the window go back as it decodes. Slot 1
    is prefilled into pages slot 0 gave back and decoded beside it.
    Every logit of both equals the reference's."""
    cfg, params = model
    hp = published(cfg)
    window = cfg.sliding_window
    a, b = tokens_of(21, 64), tokens_of(22, 30)
    want_a, _ = reference.forward_logits(params, hp, a,
                                         positions=jnp.arange(39, 64))
    want_b, _ = reference.forward_logits(params, hp, b,
                                         positions=jnp.arange(19, 30))
    pool = PagePool(cfg, page=PAGE, leaf_specs=swa_moe.cache_leaves(cfg),
                    num_pages={"window": 6, "full": 12})
    assert pool.by_kind and pool.kinds["window"].window == window
    columns = 8
    tables = {kind: np.full((2, columns), pool.sentinel_of(kind), np.int32)
              for kind in pool.kinds}
    held = {0: {}, 1: {}}

    def admit(slot, tokens):
        n = len(tokens)
        logits, small, _ = swa_moe.prefill(
            params, cfg, tokens[None], swa_moe.init_cache(cfg, 1, n))
        firsts = {}
        for kind, spec in pool.kinds.items():
            first = 0 if spec.window is None \
                else max(n - spec.window + 1, 0) // PAGE
            ids = pool.alloc(-(-n // PAGE) - first, kind=kind)
            assert ids is not None, (kind, pool.stats()["kinds"])
            tables[kind][slot, first:first + len(ids)] = ids
            held[slot][kind] = [first, ids]
            firsts[kind] = first
        _put(pool, tables, slot, small, 0, firsts, n)
        return logits[0]

    def cover_and_release(slot, fill):
        for kind, spec in pool.kinds.items():
            first, ids = held[slot][kind]
            if spec.window is not None:
                keep = max(fill - spec.window + 1, 0) // PAGE
                if keep > first:
                    pool.release_behind(ids[:keep - first], kind)
                    tables[kind][slot, first:keep] = pool.sentinel_of(kind)
                    first, ids = keep, ids[keep - first:]
                    held[slot][kind] = [first, ids]
            if fill // PAGE >= first + len(ids):
                new = pool.alloc(1, kind=kind)
                assert new is not None, (kind, fill)
                tables[kind][slot, first + len(ids)] = new[0]
                ids.extend(new)

    step = jax.jit(lambda token, leaves, tables, cache_len, active:
                   swa_moe.decode_step_paged(
                       params, cfg, token, leaves, tables, cache_len,
                       active, ragged=ragged, counters=True))
    got_a, got_b = [admit(0, a[:40])], []
    fills = np.asarray([40, 0], np.int32)
    active = np.asarray([True, False])
    last = np.asarray([int(a[40]), 0], np.int32)
    while fills[0] < 64:
        if fills[0] == 50:
            # slot 0 has given a page back by now: slot 1 takes it
            assert pool.kinds["window"].freed_behind >= 1
            assert pool.free_pages_of("window") == 3
            got_b.append(admit(1, b[:20]))
            fills[1], active[1], last[1] = 20, True, int(b[20])
        for slot in np.nonzero(active)[0]:
            cover_and_release(slot, int(fills[slot]))
        logits, pool.leaves, _, counted = step(
            jnp.asarray(last), pool.leaves,
            {k: jnp.asarray(t) for k, t in tables.items()},
            jnp.asarray(fills), jnp.asarray(active))
        rows = {kind: sum(int(f) if spec.window is None
                          else min(int(f), spec.window - 1)
                          for f in fills[active])
                for kind, spec in pool.kinds.items()}
        assert counted[5:].tolist() == [6 * rows["window"],
                                        2 * rows["full"], 8]
        got_a.append(logits[0])
        fills[0] += 1
        last[0] = int(a[min(fills[0], 63)])
        if active[1]:
            got_b.append(logits[1])
            fills[1] += 1
            if fills[1] == 30:
                active[1] = False
            else:
                last[1] = int(b[fills[1]])
    assert pool.kinds["window"].freed_behind >= 4
    assert pool.kinds["full"].freed_behind == 0
    for got, want in ((got_a, want_a), (got_b, want_b)):
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert reference.rel_l2(g, w) < TOL


def test_dense_decode_equals_the_reference(model):
    cfg, params = model
    tokens = tokens_of(5, 44)
    want, _ = reference.forward_logits(params, published(cfg), tokens,
                                       positions=jnp.arange(35, 44))
    logits, small, cache_len = swa_moe.prefill(
        params, cfg, tokens[None, :36], swa_moe.init_cache(cfg, 1, 36))
    cache = jax.tree.map(lambda big, s: big.at[:, :, :36].set(s),
                         swa_moe.init_cache(cfg, 1, 48), small)
    got = [logits[0]]
    for i in range(36, 44):
        logits, cache, cache_len = swa_moe.decode_step(
            params, cfg, tokens[i][None], cache, cache_len)
        got.append(logits[0])
    for g, w in zip(got, want):
        assert reference.rel_l2(g, w) < TOL


# -- (c) the kernel's lower bound -------------------------------------------------

@pytest.mark.parametrize("q_heads,kv_heads", [(16, 1), (32, 2), (8, 2)],
                         ids=["gqa16:1", "gqa32:2", "gqa4:1"])
@pytest.mark.parametrize("window", [16, 40])
def test_ragged_kernel_with_a_lower_bound_equals_the_oracle(
        q_heads, kv_heads, window):
    """Bit for bit, with every page that is not live (before the window,
    past the length, never allocated) poisoned with NaN: the walk starts
    at the window's page and copies nothing else."""
    page, columns, head_dim = 8, 12, 16
    lens = np.asarray([0, 5, 17, 40, 64, 90], np.int32)
    start = np.maximum(lens - window + 1, 0).astype(np.int32)
    batch, num_pages = len(lens), len(lens) * columns + 2
    keys = jax.random.split(jax.random.PRNGKey(q_heads + window), 5)

    def draw(key, *shape):
        return jax.random.normal(key, shape, jnp.float32) \
            .astype(jnp.bfloat16)

    k_pages = draw(keys[0], num_pages, page, kv_heads, head_dim)
    v_pages = draw(keys[1], num_pages, page, kv_heads, head_dim)
    q = draw(keys[2], batch, 1, q_heads, head_dim)
    k_new = draw(keys[3], batch, kv_heads, head_dim)
    v_new = draw(keys[4], batch, kv_heads, head_dim)
    table = np.full((batch, columns), num_pages, np.int32)
    live = np.zeros(num_pages, bool)
    free = iter(np.random.default_rng(0).permutation(num_pages - 1))
    for b in range(batch):
        for column in range(start[b] // page, -(-lens[b] // page)):
            table[b, column] = next(free)
            live[table[b, column]] = True
    want = paged_decode_attention(
        q, k_pages, v_pages, jnp.asarray(table), k_new, v_new,
        jnp.asarray(lens), start=jnp.asarray(start))
    mask = jnp.asarray(live)[:, None, None, None]
    got = jax.jit(lambda *ops: ragged_paged_decode_attention(
        *ops[:7], 0, interpret=True, start=ops[7]))(
        q, jnp.where(mask, k_pages, jnp.nan)[None],
        jnp.where(mask, v_pages, jnp.nan)[None], jnp.asarray(table), k_new,
        v_new, jnp.asarray(lens), jnp.asarray(start))
    assert not np.isnan(np.asarray(got, np.float32)).any()
    assert (np.asarray(got, np.float32)
            == np.asarray(want, np.float32)).all()


def _prefetched(jaxpr):
    """Scalar-prefetch operands of the one pallas_call in ``jaxpr``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn.params["grid_mapping"].num_index_operands
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found = _prefetched(sub)
            if found is not None:
                return found
    return None


def test_ragged_kernel_without_a_bound_is_the_program_it_was():
    """No ``start``: three scalar-prefetch operands, as before; with
    one, four (traced for the compiled kernel; nothing runs)."""
    args = (jnp.zeros((2, 1, 8, 16), jnp.bfloat16),
            jnp.zeros((1, 6, 8, 2, 16), jnp.bfloat16),
            jnp.zeros((1, 6, 8, 2, 16), jnp.bfloat16),
            jnp.zeros((2, 4), jnp.int32), jnp.zeros((2, 2, 16), jnp.bfloat16),
            jnp.zeros((2, 2, 16), jnp.bfloat16), jnp.zeros((2,), jnp.int32))
    plain = jax.make_jaxpr(lambda *a: ragged_paged_decode_attention(
        *a, 0, interpret=False))(*args)
    bound = jax.make_jaxpr(lambda *a: ragged_paged_decode_attention(
        *a[:7], 0, interpret=False, start=a[7]))(
        *args, jnp.zeros((2,), jnp.int32))
    assert _prefetched(plain.jaxpr) == 3
    assert _prefetched(bound.jaxpr) == 4


# -- (d) the shares add up ---------------------------------------------------------

def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer():
    """Eight ranks of 2 experts each, the routed parts of all of them
    plus the shared experts counted once, equal the reference's expert
    layer over all 16 experts; the program's part of each rank equals
    the reference's part of that rank."""
    whole = swa_moe.config("tiny", dtype=jnp.float32)
    params = swa_moe.init(whole, jax.random.PRNGKey(4))
    layer = jax.tree.map(lambda leaf: leaf[1], params["layers"][2])
    h = jax.random.normal(jax.random.PRNGKey(5), (24, whole.dim),
                          jnp.float32)
    hp = published(whole)
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.expert_layer(layer, h, hp)
        shared = reference.shared_experts(layer["shared"], h, hp)
        total = shared
        for rank in range(8):
            cfg = swa_moe.config("tiny", dtype=jnp.float32,
                                 n_held_experts=2, expert_rank=rank)
            mine = dict(layer, experts=jax.tree.map(
                lambda leaf: leaf[2 * rank:2 * rank + 2], layer["experts"]))
            theirs, _ = reference.expert_layer(
                mine, h, published(cfg), shared=False)
            for grouped in (False, True):
                ours, counters, _ = swa_moe.moe_ffn(cfg, mine, h,
                                                    grouped=grouped)
                assert reference.rel_l2(ours - shared, theirs) < TOL
            total = total + theirs
    assert reference.rel_l2(total, uncut) < TOL


@pytest.mark.parametrize("tokens,rows", [(512, 64), (1024, 128), (2048, 256),
                                         (4096, 512), (6144, 512),
                                         (8192, 1024), (16, 8)])
def test_the_family_sizes_the_grouped_products_blocks(tokens, rows):
    """At the published widths a block's time is its expert's weights'
    read, so a block has a quarter of room over the pairs an expert
    expects and nearly every held expert is one block; a family that
    says nothing (``mla_moe``) keeps the expected pairs, 8..256."""
    from gofr_tpu.models import experts, mla_moe
    assert experts._block_rows(swa_moe.SwaMoeConfig(), tokens) == rows
    latent = mla_moe.config("tiny")
    expect = max(1, tokens * latent.top_k // latent.n_routed_experts)
    assert experts._block_rows(latent, tokens) == min(
        256, max(8, 1 << (expect - 1).bit_length()))


def test_the_grouped_product_does_not_depend_on_its_block_size(model):
    """Each (token, expert) pair is computed from its own row, and a
    token's pairs are added in expert order: a block of 8 rows and one
    of 64 give the same sums, to float32's last bits (a product of 8
    rows and one of 64 may sum a row in another order)."""
    import dataclasses
    from gofr_tpu.models import experts
    cfg, params = model
    layer = jax.tree.map(lambda leaf: leaf[0], params["layers"][0])
    h = jax.random.normal(jax.random.PRNGKey(5), (48, cfg.dim), jnp.float32)
    ids, weights = experts.route(cfg, layer["router"], h)

    class Wide(type(cfg)):
        def expert_block_rows(self, tokens):
            return 64

    class Narrow(type(cfg)):
        def expert_block_rows(self, tokens):
            return 8

    got = [experts.experts_grouped(
        kind(**dataclasses.asdict(cfg)), layer["experts"], h, ids, weights)
        for kind in (Wide, Narrow)]
    np.testing.assert_allclose(np.asarray(got[0][0]), np.asarray(got[1][0]),
                               rtol=1e-5, atol=1e-6)
    assert (np.asarray(got[0][1]) == np.asarray(got[1][1])).all()
    assert int(got[0][1].sum()) > 0


def test_the_expert_layer_is_one_module_for_both_families():
    from gofr_tpu.models import experts
    for name in ("route", "experts_batched", "experts_grouped", "moe_ffn"):
        assert getattr(mla_moe, name) is getattr(experts, name)
        assert getattr(swa_moe, name) is getattr(experts, name)
    assert mla_moe.STEP_COUNTERS == experts.STEP_COUNTERS
    assert swa_moe.STEP_COUNTERS[:5] == experts.STEP_COUNTERS
    assert swa_moe.STEP_COUNTERS[5:] == (
        "attn.window_rows", "attn.full_rows", "attn.calls")
    assert swa_moe.config("tiny").shared_scale == 0.5
    assert swa_moe.config("tiny", n_shared_experts=4).shared_scale == 0.25
    assert not hasattr(mla_moe.config("tiny"), "shared_scale")


# -- (e) the pool by kind -----------------------------------------------------------

def test_page_pool_by_kind_keeps_each_kinds_pages_apart():
    cfg = swa_moe.config("tiny")
    pool = PagePool(cfg, page=PAGE, leaf_specs=swa_moe.cache_leaves(cfg),
                    num_pages={"window": 5, "full": 9})
    assert [k.name for k in cache_kinds(cfg, swa_moe.cache_leaves(cfg))] \
        == ["window", "full"]
    assert pool.leaves["window"]["k"].shape == (6, 5, PAGE, 2, 16)
    assert pool.leaves["full"]["v"].shape == (2, 9, PAGE, 2, 16)
    assert (pool.sentinel_of("window"), pool.sentinel_of("full")) == (5, 9)
    assert pool.num_pages == 14 and pool.free_pages == 14
    per_token = 2 * 2 * 16 * 2                     # k and v, bf16
    assert pool.kinds["window"].page_bytes == 6 * PAGE * per_token
    assert pool.kinds["full"].page_bytes == 2 * PAGE * per_token
    assert pool.pool_bytes == PAGE * per_token * (6 * 5 + 2 * 9)
    ids = pool.alloc(4, kind="window")
    assert sorted(ids) == sorted(set(ids)) and pool.alloc(2, kind="window") \
        is None
    assert pool.kinds["window"].stalls == 1 and pool.stalls == 1
    assert pool.alloc(9, kind="full") is not None
    assert pool.free_pages_of("window") == 1 and pool.free_pages == 1
    pool.retain(ids[:1], "window")
    pool.release_behind(ids[:2], "window")
    assert pool.refs(ids[0], "window") == 1 and pool.refs(ids[1],
                                                         "window") == 0
    stats = pool.stats()
    assert stats["used_pages"] == 12 and stats["num_pages"] == 14
    assert stats["kinds"]["window"] == {
        "layers": 6, "window": 16, "num_pages": 5, "used_pages": 3,
        "page_bytes": 6 * PAGE * per_token, "allocs": 4, "stalls": 1,
        "freed_behind": 2}
    assert stats["kinds"]["full"]["freed_behind"] == 0
    pool.reset()
    assert pool.used_pages == 0
    assert pool.kinds["window"].freed_behind == 2      # history survives
    with pytest.raises(ValueError, match="several kinds"):
        PagePool(cfg, page=PAGE, budget_bytes=1 << 20,
                 leaf_specs=swa_moe.cache_leaves(cfg))


def test_one_kind_is_todays_pool():
    """llama's and the latent module's pools: flat leaves, one kind over
    all layers, the totals under the names they had."""
    cfg = llama.config("tiny")
    pool = PagePool(cfg, page=PAGE, num_pages=6)
    assert not pool.by_kind and list(pool.kinds) == ["kv"]
    assert set(pool.leaves) == {"k", "v"}
    assert pool.leaves["k"].shape[:3] == (cfg.n_layers, 6, PAGE)
    assert pool.page_bytes == PagePool._page_bytes(cfg, PAGE) \
        == PagePool._page_bytes(cfg, PAGE, kv_leaf_specs(cfg))
    ids = pool.alloc(2)
    assert pool.free_pages == pool.free_pages_of("kv") == 4
    assert pool.refs(ids[0]) == 1 and pool.sentinel == 6
    stats = pool.stats()
    assert stats["kinds"]["kv"]["used_pages"] == stats["used_pages"] == 2
    assert stats["kinds"]["kv"]["window"] is None
    latent = mla_moe.config("tiny")
    pool = PagePool(latent, page=PAGE, num_pages=3,
                    leaf_specs=mla_moe.cache_leaves(latent))
    assert set(pool.leaves) == {"ckv"} and not pool.by_kind
    pool.num_pages = 2
    pool.reset()
    assert pool.free_pages == 2 and pool.sentinel == 2


# -- (f) the engine ---------------------------------------------------------------------

def _serve(cfg, params, ragged, prompts, budgets, **kwargs):
    async def run():
        engine = GenerationEngine(
            cfg, params, max_slots=4, max_len=128,
            prompt_buckets=(16, 32, 64), steps_per_tick=2,
            max_inflight_ticks=2, paged_kv=True, kv_page=PAGE,
            model_module=swa_moe, ragged_attn=ragged, max_group_tokens=64,
            **kwargs)
        await engine.warmup(prompt_counts=tuple(engine._n_ladder))
        await engine.start()
        peak = {"window": 0}

        async def watch():
            while True:
                for slot in engine._slots:
                    if "window" in slot.chains:
                        peak["window"] = max(peak["window"],
                                             len(slot.chains["window"][1]))
                await asyncio.sleep(0)

        watcher = asyncio.ensure_future(watch())
        out = await asyncio.gather(*[
            engine.generate(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)])
        watcher.cancel()
        stats = engine.stats()
        await engine.stop()
        return out, stats, peak["window"], engine

    return asyncio.run(run())


def test_engine_serves_token_identical_through_kernel_and_gather():
    """The normal path: admission by kind, the insert that writes a
    window kind's last window only, K-step ticks with a table a kind,
    window pages given back while slots decode. Seven requests over four
    slots, contexts up to 56 tokens (three and a half windows), where
    the kernel gives the oracle's bfloat16 outputs bit for bit ((c);
    over a hundred tokens a last bit moves now and then,
    tests/test_ragged_attention.py ``_assert_identity``, and a last bit
    at a tie of the 4th and 5th router scores is another expert and so
    another token: the streams may part only there, which is what is
    held: at every parting the reference's logits for the two tokens
    lie within a tie's distance. How many streams part follows the
    host's timing (which prompts share an admission group, which rung
    of table width the gather tick took: a row's bfloat16 sums follow
    those shapes), 0 to 4 of the 7 over runs of one tree, so the count
    is held loosely)."""
    cfg = swa_moe.config("tiny", n_held_experts=8, max_seq_len=128)
    params = swa_moe.init(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).tolist()
               for n in (5, 20, 40, 28, 33, 12, 44)]
    budgets = (30, 24, 16, 28, 20, 36, 10)
    kernel, stats, peak, engine = _serve(cfg, params, "on", prompts, budgets)
    gather, other, _, _ = _serve(cfg, params, "off", prompts, budgets)
    assert [len(o) for o in kernel] == list(budgets)
    hp = published(cfg)
    for prompt, ours, theirs in zip(prompts, kernel, gather):
        if ours == theirs:
            continue
        # a swapped expert costs a position 0.1-0.25 deviations of its
        # logits (PERF.md, PR 31); a wrong page or mask ~1 and more
        at = next(i for i, (a, b) in enumerate(zip(ours, theirs)) if a != b)
        want, _ = reference.forward_logits(
            params, hp, jnp.asarray(prompt + ours[:at]))
        want = np.asarray(want[0])
        assert abs(want[ours[at]] - want[theirs[at]]) / want.std() < 0.3
    assert sum(a == b for a, b in zip(kernel, gather)) >= 2
    assert engine.attn_path == "ragged" and engine._by_kind
    # the kernel's form at the decode call's shape, a kind, at start-up
    # and in every stats(): no selection is silent
    walk = stats["kv_pool"]["ragged_walk"]
    assert walk == engine.attention_paths()["ragged_walk"]
    assert set(walk) == {"window", "full"}
    assert all(kind["keep_scores"] and kind["ring_blocks"] >= 2
               for kind in walk.values())
    assert "ragged_walk" not in other["kv_pool"]
    assert stats["compiles"]["serving"] == 0
    kinds = stats["kv_pool"]["kinds"]
    # window 16 at page 8: two pages, a partial one at each end, and the
    # two steps of a tick before the release
    assert 0 < peak <= 16 // PAGE + 2
    assert kinds["window"]["num_pages"] == 4 * (16 // PAGE + 2)
    assert kinds["full"]["num_pages"] == 4 * (128 // PAGE)
    assert kinds["window"]["freed_behind"] > 10
    assert kinds["full"]["freed_behind"] == 0
    assert kinds["window"]["used_pages"] == kinds["full"]["used_pages"] == 0
    assert stats["kv_pool"]["used_pages"] == 0
    assert stats["attn"]["calls"] == stats["moe"]["layer_steps"] > 0
    assert 0 < stats["attn"]["window_rows"] < stats["attn"]["full_rows"] * 3
    assert other["kv_pool"]["kinds"]["window"]["freed_behind"] \
        == kinds["window"]["freed_behind"]


def test_engine_matches_the_reference_token_for_token():
    """Greedy float32 streams of the engine (gather path) are the
    reference's choices when it is teacher-forced over them, past the
    window and with slots reused."""
    cfg = swa_moe.config("tiny", dtype=jnp.float32, n_held_experts=8,
                         max_seq_len=128)
    params = swa_moe.init(cfg, jax.random.PRNGKey(6))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).tolist() for n in (30, 9, 18, 25, 6)]
    out, _, _, _ = _serve(cfg, params, "off", prompts, (12,) * 5)
    hp = published(cfg)
    for prompt, served in zip(prompts, out):
        sequence = jnp.asarray(prompt + served[:-1])
        want, _ = reference.forward_logits(
            params, hp, sequence,
            positions=jnp.arange(len(prompt) - 1, len(sequence)))
        # the reference's choice, or one it holds nearly level with it:
        # the sums' order differs between the two, which a tie of two
        # tokens' logits or of the 4th and 5th router scores shows as
        # another token (seen: 0.014 deviations of the logits below;
        # a wrong mask, page or rotary phase reads ~1 and more)
        want = np.asarray(want)
        rows = np.arange(len(served))
        below = (want.max(-1) - want[rows, served]) / want.std(-1)
        assert below.max() < 0.05, below.max()
        assert (want.argmax(-1) == np.asarray(served)).mean() >= 0.9


@pytest.mark.parametrize("what,kwargs,match", [
    ("dense", {"paged_kv": False}, "served from the page pool"),
    ("prefix", {"prefix_cache": True}, "prefix_cache needs llama"),
    ("spec", {"draft_cfg": llama.config("tiny"), "draft_params": {}},
     "speculative decode needs llama"),
    ("mesh", {"mesh": "a mesh"}, "serve unsharded"),
    ("pages", {"kv_pages": 64}, "a number of pages a cache kind"),
])
def test_engine_names_what_a_module_with_cache_kinds_cannot_use(
        what, kwargs, match):
    cfg = swa_moe.config("tiny", max_seq_len=128)
    params = swa_moe.init(cfg, jax.random.PRNGKey(0))
    settings = dict(max_slots=2, max_len=128, prompt_buckets=(16,),
                    paged_kv=True, kv_page=PAGE, model_module=swa_moe)
    settings.update(kwargs)
    with pytest.raises(ValueError, match=match):
        GenerationEngine(cfg, params, **settings)


def test_kv_wire_is_refused_by_name_for_a_table_a_kind():
    cfg = swa_moe.config("tiny", max_seq_len=128)
    engine = GenerationEngine(
        cfg, swa_moe.init(cfg, jax.random.PRNGKey(0)), max_slots=2,
        max_len=128, prompt_buckets=(16,), paged_kv=True, kv_page=PAGE,
        model_module=swa_moe)
    assert "a page table a layer kind" in engine._kv_wire_refusal
    assert engine._kind_pages(40) == {"window": (3, 2), "full": (0, 5)}
    assert engine._kind_pages(10) == {"window": (0, 2), "full": (0, 2)}


# -- (g) the ops --------------------------------------------------------------------------

def test_rotary_pairs_neighbours_or_halves():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 8), jnp.float32)
    cos, sin = rope_table(16, 8, 50000.0)
    positions = jnp.broadcast_to(jnp.arange(5), (2, 5)) + 3
    halves = apply_rope(x, cos, sin, positions)
    pairs = apply_rope(x, cos, sin, positions, interleaved=True)
    # pairing neighbours is pairing halves of the de-interleaved vector
    order = jnp.concatenate([jnp.arange(0, 8, 2), jnp.arange(1, 8, 2)])
    again = apply_rope(x[..., order], cos, sin, positions)
    assert jnp.allclose(pairs[..., order], again, atol=1e-6)
    assert not jnp.allclose(pairs, halves, atol=1e-3)
    a = np.asarray(cos[positions[0, 2]][0]), np.asarray(sin[positions[0, 2]][0])
    assert np.allclose(pairs[0, 2, 1, 0], x[0, 2, 1, 0] * a[0]
                       - x[0, 2, 1, 1] * a[1], atol=1e-6)


def test_layer_norm_without_bias():
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32), jnp.float32)
    gain = jnp.linspace(0.5, 1.5, 32)
    got = layer_norm(x, gain, None, 1e-5)
    want = (x - x.mean(-1, keepdims=True)) \
        / jnp.sqrt(x.var(-1, keepdims=True) + 1e-5) * gain
    assert jnp.allclose(got, want, atol=1e-6)
    assert jnp.allclose(layer_norm(x, gain, jnp.ones(32), 1e-5), want + 1,
                        atol=1e-6)


@pytest.mark.parametrize("window", [None, 16, 24, 100])
@pytest.mark.parametrize("block", [16, 64])
def test_banded_attention_equals_the_masked_product(window, block):
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(keys[0], (2, 64, 8, 16), jnp.float32)
    k = jax.random.normal(keys[1], (2, 64, 2, 16), jnp.float32)
    v = jax.random.normal(keys[2], (2, 64, 2, 16), jnp.float32)
    t = jnp.arange(64)
    mask = t[None, :] <= t[:, None]
    if window is not None:
        mask = mask & (t[None, :] > t[:, None] - window)
    want = attention(q, k, v, mask[None, None, None])
    got = jax.jit(lambda q, k, v: banded_attention(q, k, v, window,
                                                   block=block))(q, k, v)
    assert jnp.abs(got - want).max() < 5e-6
