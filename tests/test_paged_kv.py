"""Unified paged ragged KV (ISSUE 6): one page pool for prefill output,
the prefix cache, and decode.

The load-bearing contracts, in order:

1. TOKEN IDENTITY — greedy decode through the paged engine must emit
   exactly the dense engine's stream (prefix cache on or off, hit or
   miss): the page gather reconstructs the very rows a dense cache row
   would hold, and ``paged_decode_attention`` delegates to the same
   attention math.
2. ZERO-COPY ADMISSION — a prefix hit becomes page-table entries; the
   pool write counter must advance only by the suffix's fresh pages.
3. BACKPRESSURE, NOT FAILURE — when free pages run out, admission defers
   (FIFO) and decode growth sits a tick out; everything still completes.
4. REFCOUNTED RECLAIM — cancelling mid-decode frees the slot's private
   pages while trie-adopted pages survive for future hits.
"""

import asyncio

import jax
import numpy as np
import pytest

from gofr_tpu.container import new_mock_container
from gofr_tpu.models import llama
from gofr_tpu.tpu.generate import GenerationEngine
from gofr_tpu.tpu.page_pool import PagePool


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


async def _serve(engine, prompts, budget=6):
    await engine.start()
    try:
        outs = []
        for prompt in prompts:
            outs.append(await asyncio.wait_for(
                engine.generate(prompt, max_new_tokens=budget), 60.0))
        return outs
    finally:
        await engine.stop()


# -- PagePool unit behavior --------------------------------------------------

def test_pool_alloc_release_refcount(setup):
    cfg, _ = setup
    pool = PagePool(cfg, page=4, num_pages=4)
    ids = pool.alloc(3)
    assert len(ids) == 3 and pool.free_pages == 1
    pool.retain([ids[0]])                    # second owner (trie adoption)
    pool.release(ids)                        # first owner gone
    assert pool.free_pages == 3              # ids[0] still held at ref 1
    pool.release([ids[0]])
    assert pool.free_pages == 4
    # all-or-nothing: a 5-page ask on a 4-page pool fails without
    # consuming anything, and counts a stall
    assert pool.alloc(5) is None
    assert pool.free_pages == 4 and pool.stalls == 1


def test_pool_reclaim_callback_runs_until_satisfied(setup):
    cfg, _ = setup
    pool = PagePool(cfg, page=4, num_pages=2)
    held = pool.alloc(2)
    hoard = list(held)

    def reclaim():
        if hoard:
            pool.release([hoard.pop()])
            return True
        return False

    assert pool.alloc(2, reclaim=reclaim) == sorted(held, reverse=True) \
        or pool.free_pages == 0              # got both pages back
    assert not hoard


# -- tentpole: token identity ------------------------------------------------

def test_greedy_token_identity_dense_vs_paged(setup):
    """The acceptance criterion: identical greedy streams with the paged
    pool, across buckets, multi-page decode growth, and slot churn."""
    cfg, params = setup
    prompts = [[1, 2, 3, 4, 5],
               list(range(1, 11)),           # 16-bucket, 3 pages
               [9, 8, 7],
               [1, 2, 3, 4, 5]]              # repeat: fresh slot, same ids

    ref = asyncio.run(_serve(
        _make_engine(cfg, params)[0], prompts, budget=14))
    out = asyncio.run(_serve(
        _make_engine(cfg, params, paged_kv=True, kv_page=4)[0],
        prompts, budget=14))
    assert out == ref


def test_greedy_token_identity_with_prefix_hits(setup):
    """Paged + prefix cache: misses (first pass) and hits (second pass)
    both match the dense cache-off reference stream."""
    cfg, params = setup
    shared = list(range(1, 9))               # 2 pages of 4
    prompts = [shared + [50 + i] for i in range(3)]
    prompts = prompts + prompts              # second wave hits

    ref = asyncio.run(_serve(_make_engine(cfg, params)[0], prompts))
    engine, _ = _make_engine(cfg, params, paged_kv=True, kv_page=4,
                             prefix_cache=True)
    out = asyncio.run(_serve(engine, prompts))
    assert out == ref
    stats = engine.stats()
    lookups = stats["prefix_cache"]["lookups"]
    assert lookups["hit"] + lookups["partial"] >= 3   # the second wave
    assert stats["prefix_cache"]["adoptions"] >= 2    # zero-copy publish


def test_sampled_decode_seed_deterministic_paged(setup):
    """Sampling rides the same paged executables; a fixed seed must give
    the dense engine's stream (same per-row PRNG discipline)."""
    cfg, params = setup
    from gofr_tpu.tpu.generate import Sampling
    sampling = Sampling(temperature=0.8, top_k=20, seed=7)

    async def run(paged):
        kw = {"paged_kv": True, "kv_page": 4} if paged else {}
        engine, _ = _make_engine(cfg, params, **kw)
        await engine.start()
        try:
            return await asyncio.wait_for(engine.generate(
                [1, 2, 3, 4], max_new_tokens=8, sampling=sampling), 60.0)
        finally:
            await engine.stop()

    assert asyncio.run(run(True)) == asyncio.run(run(False))


# -- zero-copy admission -----------------------------------------------------

def test_prefix_hit_admits_with_zero_prefix_page_writes(setup):
    """A hit's prefix pages enter the slot as TABLE ENTRIES: the pool
    write counter advances only by the suffix's fresh pages, and the
    slot's table row points at the trie's own page ids."""
    cfg, params = setup
    prompt = list(range(1, 10))              # 9 tokens: 2 pages + 1 tail

    async def main():
        engine, _ = _make_engine(cfg, params, paged_kv=True, kv_page=4,
                                 prefix_cache=True)
        pool = engine._pool
        await engine.start()
        try:
            first = await asyncio.wait_for(
                engine.generate(prompt, max_new_tokens=4), 60.0)
            writes_before = pool.writes
            chain = engine._prefix.lookup(prompt)
            assert len(chain) == 2           # both full pages adopted
            trie_ids = [n.page_id for n in chain]
            second = await asyncio.wait_for(
                engine.generate(prompt, max_new_tokens=4), 60.0)
            return first, second, pool.writes - writes_before, trie_ids
        finally:
            await engine.stop()

    first, second, delta, trie_ids = asyncio.run(main())
    assert first == second
    # suffix = 1 token = 1 fresh page; the 2 prefix pages cost 0 writes
    assert delta == 1
    assert len(trie_ids) == 2


# -- backpressure ------------------------------------------------------------

def test_page_exhaustion_defers_admission_then_completes(setup):
    """A pool far smaller than max_slots x pages_per_slot: admission
    defers when free pages run short and decode growth waits its turn,
    but every request completes with the dense engine's tokens."""
    cfg, params = setup
    prompts = [[10 + i] * 8 for i in range(4)]   # 2 pages each, distinct

    ref = asyncio.run(_serve(_make_engine(cfg, params)[0],
                             prompts, budget=4))

    async def main():
        engine, _ = _make_engine(cfg, params, paged_kv=True, kv_page=4,
                                 kv_pages=8, kv_page_reserve=1)
        await engine.start()
        try:
            outs = await asyncio.wait_for(asyncio.gather(*[
                engine.generate(p, max_new_tokens=4) for p in prompts]),
                120.0)
            return outs, engine
        finally:
            await engine.stop()

    outs, engine = asyncio.run(main())
    assert outs == ref
    # pool is whole again: every slot's pages came back
    assert engine._pool.free_pages == engine._pool.num_pages
    assert engine.stats()["kv_pool"]["deferred_requests"] == 0
    # the pool never held the dense footprint
    assert engine._pool.num_pages < engine.max_slots * engine.pages_per_slot


def test_never_fitting_prompt_fails_fast(setup):
    """A prompt whose worst-case pages exceed the whole pool must fail at
    admission with a clear error, not wedge the queue."""
    cfg, params = setup

    async def main():
        engine, _ = _make_engine(cfg, params, paged_kv=True, kv_page=4,
                                 kv_pages=2, kv_page_reserve=1)
        await engine.start()
        try:
            with pytest.raises(RuntimeError, match="never be admitted"):
                await asyncio.wait_for(
                    engine.generate([1] * 12, max_new_tokens=2), 60.0)
        finally:
            await engine.stop()

    asyncio.run(main())


# -- refcounted reclaim ------------------------------------------------------

def test_cancel_mid_decode_frees_slot_pages_keeps_trie_pages(setup):
    """Cancelling a stream mid-decode drops the slot's refs: private
    (growth/suffix) pages return to the free list, while pages the trie
    adopted survive and serve the next request."""
    cfg, params = setup
    prompt = list(range(1, 9))               # 2 fully-valid pages

    async def main():
        engine, _ = _make_engine(cfg, params, paged_kv=True, kv_page=4,
                                 prefix_cache=True)
        pool = engine._pool
        await engine.start()
        try:
            stream = await engine.generate_stream(prompt,
                                                  max_new_tokens=24)
            tokens = []
            async for token in stream:
                tokens.append(token)
                if len(tokens) == 2:
                    stream.cancel()
                    break
            await asyncio.sleep(0.2)         # let the loop settle
            trie_pages = engine._prefix.used_pages
            free_after_cancel = pool.free_pages
            # the cancelled request's KV is gone; only the trie holds on
            assert trie_pages == 2
            assert free_after_cancel == pool.num_pages - trie_pages
            # the surviving pages are LIVE: a rerun hits them and decodes
            # the same stream a fresh dense engine produces
            out = await asyncio.wait_for(
                engine.generate(prompt, max_new_tokens=6), 60.0)
            hits = engine.stats()["prefix_cache"]["lookups"]
            assert hits["hit"] + hits["partial"] >= 1
            return out
        finally:
            await engine.stop()

    out = asyncio.run(main())
    ref = asyncio.run(_serve(_make_engine(cfg, params)[0], [prompt]))[0]
    assert out == ref


def test_engine_failure_resets_pool_and_table(setup):
    """The donated-buffer failure path: after _fail_outstanding the pool
    rebuilds, the table is all-sentinel, and serving continues."""
    cfg, params = setup

    async def main():
        engine, _ = _make_engine(cfg, params, paged_kv=True, kv_page=4,
                                 prefix_cache=True)
        await engine.start()
        try:
            before = await asyncio.wait_for(
                engine.generate([1, 2, 3, 4, 5], max_new_tokens=4), 60.0)
            engine._fail_outstanding(RuntimeError("boom"))
            engine._reset_device_state()
            assert engine._pool.free_pages == engine._pool.num_pages
            assert (engine._tables["kv"] == engine._pool.sentinel).all()
            after = await asyncio.wait_for(
                engine.generate([1, 2, 3, 4, 5], max_new_tokens=4), 60.0)
            return before, after
        finally:
            await engine.stop()

    before, after = asyncio.run(main())
    assert before == after


# -- the HBM claim -----------------------------------------------------------

def test_pool_hbm_does_not_scale_with_max_len_times_slots(setup):
    """Decode KV HBM is the pool: leaves are (L, num_pages, page, ...) —
    sized by kv_pages/budget, not (max_slots, max_len, ...)."""
    cfg, params = setup
    engine, _ = _make_engine(cfg, params, paged_kv=True, kv_page=4,
                             kv_pages=6)
    k = engine._pool.leaves["k"]
    assert k.shape[1] == 6 and k.shape[2] == 4
    assert engine.cache is None              # no dense decode cache at all
    dense_rows = engine.max_slots * engine.max_len
    assert k.shape[1] * k.shape[2] < dense_rows
    # bytes accounting agrees
    stats = engine._pool.stats()
    assert stats["pool_bytes"] == 6 * stats["page_bytes"]


def test_window_ladder_demotes_to_page_gather_width(setup):
    """Satellite: attention_window on the paged path only bounds the
    page-gather width; requesting it explicitly warns."""
    cfg, params = setup

    class _Warns:
        def __init__(self):
            self.messages = []

        def warn(self, msg, *args):
            self.messages.append(msg % args if args else msg)

        def info(self, msg, *args):
            pass

        def error(self, msg, *args):
            pass

    logger = _Warns()
    container = new_mock_container()
    engine = GenerationEngine(cfg, params, max_slots=4, max_len=256,
                              prompt_buckets=(8, 16), paged_kv=True,
                              kv_page=4, window_ladder=True,
                              logger=logger, metrics=container.metrics)
    # 256 max_len -> window rungs [128, None] -> widths [32, 64]
    assert engine._pick_page_width(128) == 32
    assert engine._pick_page_width(None) == engine.pages_per_slot
    assert any("paging supersedes windowing" in m for m in logger.messages)
