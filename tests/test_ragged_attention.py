"""Fused ragged paged attention (ISSUE 13): one Pallas kernel over
variable-length page tables.

The load-bearing contracts, in order:

1. TOKEN IDENTITY — the kernel's output is bit-equal to the gather
   formulation (the correctness oracle) for every fill pattern, bf16
   and int8, decode and the γ+1 verify variant, eager AND jitted. The
   oracle itself is made jit-stable by explicit ``lax.reduce_precision``
   rounding points (ops/attention._snap), which the kernel reproduces.
2. SENTINEL SKIP — sentinel / dead-tail table entries are never
   dereferenced: NaN-poisoning every unreferenced page must not perturb
   the output (the gather path merely masks *scores*, so it cannot make
   this guarantee — ``0 * NaN`` poisons V; the kernel copies a slot's
   live pages only, and these tests pin it).
3. LADDER RETIREMENT — with ragged active the engine compiles ONE
   decode executable per (steps, sampled) family: no per-width entries
   in the ledger, gather_widths collapses to the full table width.
4. NO HIDDEN FALLBACK — the entry points are the kernel on every
   geometry; choosing the gather formulation instead is the engine's
   decision (``ragged_tileable`` + platform), taken once and reported.

All tests run the kernel in Pallas interpret mode on CPU (tier-1).
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.container import new_mock_container
from gofr_tpu.models import llama
from gofr_tpu.ops.attention import (check_sentinel_masked,
                                    paged_decode_attention,
                                    paged_verify_attention)
from gofr_tpu.ops.pallas import (ragged_paged_decode_attention,
                                 ragged_paged_verify_attention,
                                 ragged_tileable)
from gofr_tpu.ops.pallas.ragged_paged_attention import walk_sizes
from gofr_tpu.tpu.generate import GenerationEngine, Sampling
from gofr_tpu.tpu.page_pool import PagePool

NUM_PAGES, PAGE, HKV, HQ, D, P = 12, 16, 2, 4, 16, 4
SENTINEL = NUM_PAGES


def _operands(seed, B, g_len, int8, num_pages, page, hkv, hq, head_dim):
    """Random pool planes, scale planes (int8 only), q and the new K/V."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    shape = (num_pages, page, hkv, head_dim)
    if int8:
        k_pages = jax.random.randint(keys[0], shape, -127, 128, jnp.int8)
        v_pages = jax.random.randint(keys[1], shape, -127, 128, jnp.int8)
        scales = dict(
            k_scale_pages=jax.random.uniform(
                keys[5], shape[:-1], jnp.float32, 0.01, 0.03),
            v_scale_pages=jax.random.uniform(
                keys[6], shape[:-1], jnp.float32, 0.01, 0.03))
    else:
        k_pages = jax.random.normal(keys[0], shape, jnp.float32) \
            .astype(jnp.bfloat16)
        v_pages = jax.random.normal(keys[1], shape, jnp.float32) \
            .astype(jnp.bfloat16)
        scales = {}
    q = jax.random.normal(keys[2], (B, g_len, hq, head_dim),
                          jnp.float32).astype(jnp.bfloat16)
    k_new = jax.random.normal(keys[3], (B, g_len, hkv, head_dim),
                              jnp.float32).astype(jnp.bfloat16)
    v_new = jax.random.normal(keys[4], (B, g_len, hkv, head_dim),
                              jnp.float32).astype(jnp.bfloat16)
    if g_len == 1:
        k_new, v_new = k_new[:, 0], v_new[:, 0]
    return q, k_pages, v_pages, k_new, v_new, scales


def _scenario(cache_lens, g_len=1, int8=False, head_dim=D, seed=0, hq=HQ):
    """Pool leaves + a page table covering each slot's cache_len (pages
    allocated bottom-up, page NUM_PAGES-1 deliberately never used — it
    is the kernel's clamp target for sentinel entries)."""
    B = len(cache_lens)
    q, k_pages, v_pages, k_new, v_new, scales = _operands(
        seed, B, g_len, int8, NUM_PAGES, PAGE, HKV, hq, head_dim)
    table = np.full((B, P), SENTINEL, np.int32)
    nxt = 0
    for b, n in enumerate(cache_lens):
        for col in range(-(-n // PAGE)):
            table[b, col] = nxt
            nxt += 1
    assert nxt < NUM_PAGES - 1          # keep the clamp target unused
    return (q, k_pages, v_pages, jnp.asarray(table), k_new, v_new,
            jnp.asarray(cache_lens, jnp.int32)), scales, table


def _stacked(args, scales=None, layer=0, depth=1):
    """The kernel's operands for a one-plane scenario: each pool plane
    becomes layer ``layer`` of a ``depth``-layer stacked pool whose other
    layers are poison (NaN; 127 on int8 pages, whose scales are NaN), and
    the layer index follows ``cache_len``. At depth 1 that is
    ``plane[None]`` and layer 0 — what a caller holding one plane does."""
    def stack(plane):
        bad = jnp.full_like(plane, jnp.nan if jnp.issubdtype(
            plane.dtype, jnp.floating) else 127)
        return jnp.stack([plane if i == layer else bad
                          for i in range(depth)])

    q, k_pages, v_pages, *rest = args
    return ((q, stack(k_pages), stack(v_pages), *rest,
             jnp.asarray(layer, jnp.int32)),
            {name: stack(plane) for name, plane in (scales or {}).items()})


FILLS = [0, 5, P * PAGE, 17]            # empty / one partial / max / mixed


# -- tentpole: bit-identity with the gather oracle ---------------------------

# query heads over the 2 KV heads: 2 rows a KV head (both heads share a
# product, block-diagonally) and 16 (a product a head)
ROWS_A_HEAD = pytest.mark.parametrize("hq", [HQ, 16 * HKV],
                                      ids=["2-rows", "16-rows"])


@ROWS_A_HEAD
def test_decode_identity_vs_gather_fill_patterns(hq):
    assert walk_sizes(PAGE, HKV, D, hq, 2, P).product_heads == (
        HKV if hq == HQ else 1)
    args, _, _ = _scenario(FILLS, hq=hq)
    oracle = paged_decode_attention(*args)
    out = ragged_paged_decode_attention(*_stacked(args)[0])
    assert out.dtype == oracle.dtype
    assert bool((out == oracle).all())


def test_decode_identity_under_jit():
    """The oracle's rounding points are explicit (reduce_precision), so
    jit cannot fold them away: eager == jit == kernel, all four ways."""
    args, _, _ = _scenario(FILLS)
    eager = paged_decode_attention(*args)
    jitted = jax.jit(paged_decode_attention)(*args)
    ragged = jax.jit(ragged_paged_decode_attention)(*_stacked(args)[0])
    assert bool((eager == jitted).all())
    assert bool((jitted == ragged).all())


@ROWS_A_HEAD
def test_decode_identity_int8_fused_dequant(hq):
    args, scales, _ = _scenario([5, 33, 64], int8=True, hq=hq)
    oracle = paged_decode_attention(*args, **scales)
    args, scales = _stacked(args, scales)
    out = ragged_paged_decode_attention(*args, **scales)
    assert bool((out == oracle).all())


@ROWS_A_HEAD
def test_verify_identity_gamma_plus_one(hq):
    """γ+1-token verify variant: causal among the new tokens, same
    rounding schedule — bit-equal to paged_verify_attention."""
    args, _, _ = _scenario([0, 7, 40], g_len=3, hq=hq)
    oracle = paged_verify_attention(*args)
    out = ragged_paged_verify_attention(*_stacked(args)[0])
    assert bool((out == oracle).all())


@ROWS_A_HEAD
def test_verify_identity_int8(hq):
    args, scales, _ = _scenario([9, 21], g_len=2, int8=True, hq=hq)
    oracle = paged_verify_attention(*args, **scales)
    args, scales = _stacked(args, scales)
    out = ragged_paged_verify_attention(*args, **scales)
    assert bool((out == oracle).all())


# -- the layer is picked inside the kernel -----------------------------------

LAYER_CASES = {
    "decode-bf16": (ragged_paged_decode_attention, paged_decode_attention,
                    dict(cache_lens=FILLS)),
    "decode-int8": (ragged_paged_decode_attention, paged_decode_attention,
                    dict(cache_lens=[5, 33, 64], int8=True)),
    "verify": (ragged_paged_verify_attention, paged_verify_attention,
               dict(cache_lens=[0, 7, 40], g_len=3)),
}


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("case", LAYER_CASES)
def test_layer_picked_in_kernel_from_stacked_pool(case, layer):
    """The kernel takes the pool as the layer scan carries it, all
    layers stacked, and a TRACED layer index (the scan's ``idx``): it
    must read exactly that layer. Every other layer is poisoned, so one
    block fetched from a wrong plane turns the output NaN."""
    kernel, oracle_fn, scenario = LAYER_CASES[case]
    args, scales, _ = _scenario(**scenario)
    oracle = oracle_fn(*args, **scales)
    args, scales = _stacked(args, scales, layer=layer, depth=3)
    out = jax.jit(kernel)(*args, **scales)
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
    assert bool((out == oracle).all())


# -- the page walk's edges ---------------------------------------------------
# A geometry at which the walk has something to walk: the published page
# and head widths, so a block is 8 pages (walk_sizes), a table of 76
# columns is several blocks and a partly live one, and longer than the
# ring of page copies. Two query-head counts over the 8 KV heads: 4 rows a
# KV head (GQA 32:8: four heads share a product) and 16 (GQA 128:8: a
# product a head).

W_PAGE, W_HKV, W_D, W_P = 32, 8, 128, 76
W_HEADS = {"4-rows": 32, "16-rows": 128}
WALKS = {
    "decode-bf16": (ragged_paged_decode_attention, paged_decode_attention,
                    dict()),
    "decode-int8": (ragged_paged_decode_attention, paged_decode_attention,
                    dict(int8=True)),
    "verify": (ragged_paged_verify_attention, paged_verify_attention,
               dict(g_len=3)),
}
EDGES = ["empty", "one", "page-1", "page", "page+1", "block-1", "block",
         "block+1", "table"]
walks_by_rows = pytest.mark.parametrize("rows", W_HEADS)


def _walk_sizes(rows, g_len=1, int8=False, table_width=W_P):
    return walk_sizes(W_PAGE, W_HKV, W_D, W_HEADS[rows] * g_len,
                      1 if int8 else 2, table_width)


def _edge_lengths(rows, **variant):
    sizes = _walk_sizes(rows, **variant)
    assert 1 < sizes.block_pages * sizes.ring_blocks < W_P   # > the ring
    assert W_P % sizes.block_pages                 # a partly live block
    block = sizes.block_pages * W_PAGE
    return [0, 1, W_PAGE - 1, W_PAGE, W_PAGE + 1, block - 1, block,
            block + 1, W_P * W_PAGE]


def _walk_scenario(cache_lens, rows, g_len=1, int8=False, full_table=False,
                   seed=0, table_width=W_P, starts=None):
    """``_scenario`` at the walk's geometry. Pages are handed out in a
    shuffled order; ``full_table`` fills the columns past a slot's live
    prefix with real page ids too (what a table looks like when a slot
    holds more pages than it has filled); with ``starts`` a slot holds
    no page wholly before its first attended position. Returns (args,
    scales, ids of the live pages)."""
    rng = np.random.default_rng(seed)
    B = len(cache_lens)
    num_pages = B * table_width + 1
    q, k_pages, v_pages, k_new, v_new, scales = _operands(
        seed, B, g_len, int8, num_pages, W_PAGE, W_HKV, W_HEADS[rows], W_D)
    ids = rng.permutation(num_pages - 1).reshape(B, table_width)
    table = np.full((B, table_width), num_pages, np.int32)
    live = set()
    for b, n in enumerate(cache_lens):
        first = 0 if starts is None else starts[b] // W_PAGE
        held = min(-(-n // W_PAGE), table_width)
        last = table_width if full_table else held
        table[b, first:last] = ids[b, first:last]
        live.update(ids[b, first:held].tolist())
    return (q, k_pages, v_pages, jnp.asarray(table), k_new, v_new,
            jnp.asarray(cache_lens, jnp.int32)), scales, live


def _poisoned(args, scales, live):
    """Every page outside ``live`` made poison: NaN, or 127 with NaN
    scales on an int8 pool."""
    q, k_pages, v_pages, *rest = args
    dead = np.array([p for p in range(k_pages.shape[0]) if p not in live])

    def poison(plane):
        bad = jnp.nan if jnp.issubdtype(plane.dtype, jnp.floating) else 127
        return plane.at[dead].set(bad)

    return ((q, poison(k_pages), poison(v_pages), *rest),
            {name: poison(plane) for name, plane in scales.items()}, dead)


def _assert_identity(out, oracle):
    """Identity with the oracle as far as it is the kernel's to give, a
    slot at a time: equal in 99 % of its outputs, the rest within a
    bf16 step of the slot's largest. At this geometry (4096-49152
    outputs a slot, contexts up to 2304 tokens) the order of the float32
    partial sums shows in the last bit of an output now and then,
    whichever kernel walks the pages: the grid kernel and the all-heads
    product this one replaced differed from the oracle in the same few
    outputs in a thousand of the longest slots. The cases above, at tens
    of tokens, stay bit-equal. One token skipped in 256 moves most
    outputs by a step; a page skipped, doubled or taken from another
    slot moves every output by far more."""
    out = np.asarray(out, np.float32)
    oracle = np.asarray(oracle, np.float32)
    assert np.isfinite(out).all()
    for slot in range(out.shape[0]):
        differ = out[slot] != oracle[slot]
        assert differ.mean() <= 0.01, (slot, float(differ.mean()))
        step = np.abs(oracle[slot]).max() * 2.0 ** -7
        assert np.abs(out[slot] - oracle[slot]).max() <= step, slot


_EDGE_RUNS = {}


def _edge_run(walk, rows):
    """One call a variant, a slot an edge: the kernel's and the oracle's
    outputs, kept for the cases that each look at their own slot."""
    if (walk, rows) not in _EDGE_RUNS:
        kernel, oracle_fn, variant = WALKS[walk]
        args, scales, _ = _walk_scenario(
            _edge_lengths(rows, **variant), rows, **variant)
        oracle = oracle_fn(*args, **scales)
        args, scales = _stacked(args, scales)
        _EDGE_RUNS[walk, rows] = (jax.jit(kernel)(*args, **scales), oracle)
    return _EDGE_RUNS[walk, rows]


@pytest.mark.parametrize("edge", EDGES)
@pytest.mark.parametrize("walk", WALKS)
@walks_by_rows
def test_identity_at_the_walks_edges(rows, walk, edge):
    """Identity with the gather oracle where the walk changes shape:
    no page, one token, a page less one / exact / plus one, a block of
    pages less one token / exact / plus one, the whole table (more
    blocks than the ring holds, the last one partly live)."""
    out, oracle = _edge_run(walk, rows)
    slot = EDGES.index(edge)
    _assert_identity(out[slot:slot + 1], oracle[slot:slot + 1])


@pytest.mark.parametrize("walk", WALKS)
@walks_by_rows
def test_identity_mixed_batch_with_inactive_slots(rows, walk):
    """Inactive slots (length 0, a row of sentinels) between live ones:
    they fold their new tokens alone, and the copies a slot's program
    starts for the next slot must not leak into a slot without any."""
    kernel, oracle_fn, variant = WALKS[walk]
    lens = [0, 300, 0, 0, 77, 1025, 0]
    args, scales, _ = _walk_scenario(lens, rows, **variant)
    oracle = oracle_fn(*args, **scales)
    args, scales = _stacked(args, scales)
    out = jax.jit(kernel)(*args, **scales)
    _assert_identity(out, oracle)


# -- sentinel skip guarantee -------------------------------------------------

def test_sentinel_pages_never_dereferenced():
    """NaN-poison every page no table row references (including the
    clamp target NUM_PAGES-1): the kernel's output must not move. The
    gather oracle cannot pass this — its clamp gathers the poisoned
    page and ``0 * NaN`` rides through the V einsum — which is exactly
    why copying live pages only is the stronger contract."""
    args, _, table = _scenario([5, 0, 37])
    clean = ragged_paged_decode_attention(*_stacked(args)[0])
    q, k_pages, v_pages, table_dev, k_new, v_new, cache_len = args
    live = set(table[table != SENTINEL].tolist())
    dead = [p for p in range(NUM_PAGES) if p not in live]
    assert NUM_PAGES - 1 in dead
    poison = np.asarray(k_pages, np.float32)
    poison[dead] = np.nan
    k_poison = jnp.asarray(poison).astype(k_pages.dtype)
    poison = np.asarray(v_pages, np.float32)
    poison[dead] = np.nan
    v_poison = jnp.asarray(poison).astype(v_pages.dtype)
    out = ragged_paged_decode_attention(
        q, k_poison[None], v_poison[None], table_dev, k_new, v_new,
        cache_len, 0)
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
    assert bool((out == clean).all())


@pytest.mark.parametrize("walk", WALKS)
@walks_by_rows
def test_walk_copies_live_pages_only(rows, walk):
    """The poison test at the walk's geometry: the table is FULL (the
    columns past a slot's live prefix name real pages, as when a slot
    holds more than it has filled), longer than the ring, and every
    page outside a live prefix is poison: NaN, or 127 with NaN scales
    on an int8 pool. Lengths end inside a block, so poisoned entries sit
    in partly live blocks beside live ones. One copied dead page turns
    the output NaN (P.V multiplies its rows by exactly 0)."""
    kernel, oracle_fn, variant = WALKS[walk]
    sizes = _walk_sizes(rows, **variant)
    block = sizes.block_pages * W_PAGE
    lens = [block + 1, 0, W_P * W_PAGE - block - W_PAGE - 3, W_PAGE, 5]
    args, scales, live = _walk_scenario(lens, rows, full_table=True,
                                        **variant)
    oracle = oracle_fn(*args, **scales)
    args, scales, dead = _poisoned(args, scales, live)
    assert len(dead) > sizes.ring_blocks * sizes.block_pages
    args, scales = _stacked(args, scales)
    out = jax.jit(kernel)(*args, **scales)
    _assert_identity(out, oracle)


@walks_by_rows
def test_identity_with_a_start_inside_a_block(rows):
    """A sliding-window layer's bound at the walk's geometry: first
    attended positions inside a page inside a block (the first, a
    middle one, the last, partly live), at a block's first token, past
    the length's page, and none. The pages wholly before a slot's
    bound are sentinels in its table, and every page that is not live
    is poison: the walk starts at the bound's block and copies from the
    bound's page on."""
    block = _walk_sizes(rows).block_pages * W_PAGE
    lens = [3 * block + 70, 2 * block, block + 9, W_P * W_PAGE, 900, 0]
    starts = [block + 3 * W_PAGE + 5, block, 7, W_P * W_PAGE - 40, 0, 0]
    args, scales, live = _walk_scenario(lens, rows, starts=starts)
    start = jnp.asarray(starts, jnp.int32)
    oracle = paged_decode_attention(*args, start=start)
    args, _, _ = _poisoned(args, scales, live)
    out = jax.jit(lambda *ops: ragged_paged_decode_attention(
        *ops, start=start))(*_stacked(args)[0])
    _assert_identity(out, oracle)


@walks_by_rows
def test_length_beyond_the_table_attends_the_table(rows):
    """A length past what the table's columns hold attends exactly the
    table, as the oracle's gathered window does — also where the table
    ends inside a block of pages (20 columns, blocks of 8), whose
    further ring rows were never copied."""
    assert 20 % _walk_sizes(rows, table_width=20).block_pages
    lens = [20 * W_PAGE + 40, 100]
    args, scales, _ = _walk_scenario(lens, rows, full_table=True,
                                     table_width=20)
    oracle = paged_decode_attention(*args)
    out = jax.jit(ragged_paged_decode_attention)(*_stacked(args)[0])
    _assert_identity(out, oracle)


# -- the walk's sizes ---------------------------------------------------------

WALK_SIZES = {
    # command-a-plus-ep8.mixed's decode call (GQA 128:8, max_len 9216):
    # four blocks of 8 pages in flight, the scores of all 288 columns
    # kept, K read once
    "cmda-decode": ((32, 8, 128, 128, 2, 288), (8, 4, True, 1)),
    # the same heads over the uncut 200000 positions: K streamed twice
    "cmda-uncut": ((32, 8, 128, 128, 2, 6250), (8, 4, False, 1)),
    # mistral7b.batch's (GQA 32:8, max_len 2048): four heads a product
    "mistral7b-decode": ((32, 8, 128, 32, 2, 64), (8, 4, True, 4)),
    "mistral7b-int8": ((32, 8, 128, 32, 1, 64), (16, 4, True, 4)),
    "mistral7b-verify": ((32, 8, 128, 160, 2, 64), (8, 4, True, 4)),
    # the same verify at 128 query heads: the block's scores set its size
    "cmda-verify": ((32, 8, 128, 640, 2, 288), (3, 8, False, 1)),
    # MHA 32:32: a row a KV head, sixteen heads a product
    "mha-decode": ((32, 32, 128, 32, 2, 64), (2, 4, True, 16)),
}


@pytest.mark.parametrize("case", WALK_SIZES)
def test_walk_sizes_at_the_serving_shapes(case):
    """``walk_sizes`` is where the kernel's form is decided, from the
    call's shapes and the module's constants alone."""
    shapes, want = WALK_SIZES[case]
    assert tuple(walk_sizes(*shapes)) == want


def test_check_sentinel_masked_contract():
    """The gather path's safety assertion: sentinel entries inside the
    covered prefix (live tokens + the new token) are a table-corruption
    bug, sentinel tails are fine."""
    table = np.full((2, P), SENTINEL, np.int32)
    table[0, :2] = [0, 1]
    table[1, :1] = [2]
    check_sentinel_masked(table, np.array([17, 3]), PAGE, SENTINEL)
    bad = table.copy()
    bad[0, 1] = SENTINEL                # covered by cache_len=17
    with pytest.raises(AssertionError):
        check_sentinel_masked(bad, np.array([17, 3]), PAGE, SENTINEL)


def test_pad_table_tiles_with_sentinel():
    table = np.arange(6, dtype=np.int32).reshape(2, 3)
    padded = PagePool.pad_table(table, 4, SENTINEL)
    assert padded.shape == (2, 4)
    assert (padded[:, 3] == SENTINEL).all()
    assert PagePool.pad_table(padded, 4, SENTINEL) is padded


# -- selection ---------------------------------------------------------------

def test_no_fallback_on_untileable_head_dim():
    """head_dim=12 tiles nowhere, and the entry point still runs the
    KERNEL (interpreted here) rather than quietly handing the call to
    the gather formulation — which it matches bit for bit anyway."""
    assert not ragged_tileable(12, HQ, HKV, PAGE)
    args, _, _ = _scenario([5, 33], head_dim=12)
    oracle = paged_decode_attention(*args)
    args = _stacked(args)[0]
    jaxpr = str(jax.make_jaxpr(ragged_paged_decode_attention)(*args))
    assert "pallas_call" in jaxpr
    out = ragged_paged_decode_attention(*args)
    assert bool((out == oracle).all())


def test_ragged_tileable_predicate():
    """The predicate answers for Mosaic on every platform, so a CPU run
    and a TPU run of one config select the same path."""
    assert ragged_tileable(128, 32, 32, 32)                  # 7B MHA
    assert ragged_tileable(128, 32, 8, 32)                   # GQA 32:8
    assert ragged_tileable(128, 8, 2, 16)
    assert not ragged_tileable(64, 8, 2, 16)                 # hd % 128
    assert not ragged_tileable(128, 4, 2, 16)                # hq % 8
    assert not ragged_tileable(128, 8, 2, 8)                 # page % 16
    assert not ragged_tileable(128, 8, 3, 16)                # hq % hkv
    # a head's rows leave a block by 32-bit words of 2 bf16 / 4 int8 heads
    assert not ragged_tileable(128, 8, 1, 16)
    assert not ragged_tileable(128, 24, 3, 16)
    assert ragged_tileable(128, 32, 8, 32, kv_itemsize=1)
    assert not ragged_tileable(128, 8, 2, 16, kv_itemsize=1)
    assert not ragged_tileable(D, HQ, HKV, PAGE)             # test shapes


# -- engine integration ------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = llama.config("tiny")
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _make_engine(cfg, params, **kwargs):
    container = new_mock_container()
    kwargs.setdefault("max_slots", 4)
    kwargs.setdefault("max_len", 64)
    kwargs.setdefault("prompt_buckets", (8, 16))
    engine = GenerationEngine(cfg, params, logger=container.logger,
                              metrics=container.metrics, **kwargs)
    return engine, container


async def _serve(engine, prompts, budget=6, sampling=None):
    await engine.start()
    try:
        outs = []
        for prompt in prompts:
            outs.append(await asyncio.wait_for(
                engine.generate(prompt, max_new_tokens=budget,
                                sampling=sampling), 60.0))
        return outs
    finally:
        await engine.stop()


def test_engine_greedy_identity_and_ladder_retirement(setup):
    """The acceptance criterion: identical greedy streams dense vs
    gather vs ragged — and with ragged active the per-width decode
    executable class is gone (one (steps, sampled) family, gather
    width pinned at the full table width)."""
    cfg, params = setup
    prompts = [[1, 2, 3, 4, 5], list(range(1, 11)), [9, 8, 7]]

    dense = asyncio.run(_serve(_make_engine(cfg, params)[0], prompts))
    g_eng, _ = _make_engine(cfg, params, paged_kv=True, kv_page=4,
                            ragged_attn="off")
    gather = asyncio.run(_serve(g_eng, prompts))
    r_eng, container = _make_engine(cfg, params, paged_kv=True, kv_page=4,
                                    ragged_attn="on")
    ragged = asyncio.run(_serve(r_eng, prompts))
    assert gather == dense
    assert ragged == dense

    assert g_eng.attn_path == "gather" and r_eng.attn_path == "ragged"
    # the start-up report says what walk_sizes answered for the decode
    # call's shape; an engine on the gather path has no walk to report
    assert r_eng.attention_paths()["ragged_walk"] == {"kv": walk_sizes(
        4, cfg.n_kv_heads, cfg.head_dim, cfg.n_heads, 2,
        r_eng.pages_per_slot)._asdict()}
    assert r_eng.stats()["kv_pool"]["ragged_walk"]["kv"]["keep_scores"]
    assert "ragged_walk" not in g_eng.attention_paths()
    assert "ragged_walk" not in g_eng.stats()["kv_pool"]
    widths = r_eng.xlaz()["paged_kv"]["gather_widths"]
    assert widths == [r_eng.pages_per_slot]          # ladder collapsed
    assert len(g_eng.xlaz()["paged_kv"]["gather_widths"]) >= 1
    # no per-width decode executables: every key carries the same
    # (full-table) gather width
    keys = r_eng.xlaz()["paged_kv"]["decode_executables"]
    assert keys and len({k.rstrip(")").split(", ")[-1] for k in keys}) == 1
    served = container.metrics.value("app_tpu_attn_kernel_total",
                                     model=r_eng.model_name, path="ragged")
    assert served and served > 0


def test_engine_seeded_sampling_identity(setup):
    cfg, params = setup
    prompts = [[1, 2, 3, 4, 5], [7, 7, 7]]
    sampling = Sampling(temperature=0.8, top_k=20, seed=7)
    gather = asyncio.run(_serve(
        _make_engine(cfg, params, paged_kv=True, kv_page=4,
                     ragged_attn="off")[0], prompts, sampling=sampling))
    ragged = asyncio.run(_serve(
        _make_engine(cfg, params, paged_kv=True, kv_page=4,
                     ragged_attn="on")[0], prompts, sampling=sampling))
    assert ragged == gather


def test_engine_prefix_hit_and_miss_identity(setup):
    """Prefix-cache hits admit via table entries (zero-copy); decode
    over adopted pages must still match the dense reference stream."""
    cfg, params = setup
    shared = list(range(1, 9))
    prompts = [shared + [50 + i] for i in range(2)]
    prompts = prompts + prompts          # second wave hits
    ref = asyncio.run(_serve(_make_engine(cfg, params)[0], prompts))
    engine, _ = _make_engine(cfg, params, paged_kv=True, kv_page=4,
                             prefix_cache=True, ragged_attn="on")
    out = asyncio.run(_serve(engine, prompts))
    assert out == ref
    lookups = engine.stats()["prefix_cache"]["lookups"]
    assert lookups["hit"] + lookups["partial"] >= 2


def test_engine_int8_identity(setup):
    import dataclasses
    cfg, _ = setup
    cfg8 = dataclasses.replace(cfg, kv_int8=True)
    params = llama.init(cfg8, jax.random.PRNGKey(0))
    prompts = [[1, 2, 3, 4, 5], [4, 4, 8, 1]]
    gather = asyncio.run(_serve(
        _make_engine(cfg8, params, paged_kv=True, kv_page=4,
                     ragged_attn="off")[0], prompts, budget=4))
    ragged = asyncio.run(_serve(
        _make_engine(cfg8, params, paged_kv=True, kv_page=4,
                     ragged_attn="on")[0], prompts, budget=4))
    assert ragged == gather


def test_ragged_attn_knob_validation(setup):
    cfg, params = setup
    with pytest.raises(ValueError):
        _make_engine(cfg, params, ragged_attn="on")      # needs paged_kv
    with pytest.raises(ValueError):
        _make_engine(cfg, params, paged_kv=True, kv_page=4,
                     ragged_attn="sometimes")
    # auto off-TPU resolves to the gather path (interpret mode is for
    # tests that opt in with "on", not production auto-selection)
    engine, _ = _make_engine(cfg, params, paged_kv=True, kv_page=4,
                             ragged_attn="auto")
    assert engine.attn_path == "gather"
    # ... and says why, instead of choosing silently
    assert "cpu" in engine.attn_reason
    assert engine.attention_paths()["decode"] == "gather"
