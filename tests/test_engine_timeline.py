"""The device's timeline as the engine keeps it (ISSUE 35):
``stats()["timeline"]``, fed where a program's token fetch lands. An
entry's interval runs from the later of the previous landing and its own
dispatch to its own landing, so device time is charged once however many
ticks are in flight; a prefill group's interval is also booked on every
decoding request it stopped."""

import asyncio
import re
from types import SimpleNamespace

import pytest

from tests.util import run

PATHS = {"gather": dict(paged_kv=True, kv_page=4, ragged_attn="off"),
         "ragged": dict(paged_kv=True, kv_page=4, ragged_attn="on"),
         "dense": dict()}


@pytest.fixture(scope="module")
def setup():
    import jax
    from gofr_tpu.models import llama
    cfg = llama.config("tiny")
    return cfg, llama.init(cfg, jax.random.PRNGKey(0))


def _make_engine(cfg, params, **kwargs):
    from gofr_tpu.container import new_mock_container
    from gofr_tpu.tpu.generate import GenerationEngine
    container = new_mock_container()
    kwargs.setdefault("max_slots", 4)
    kwargs.setdefault("max_len", 64)
    kwargs.setdefault("prompt_buckets", (8,))
    kwargs.setdefault("steps_per_tick", 4)
    kwargs.setdefault("max_inflight_ticks", 4)
    engine = GenerationEngine(cfg, params, logger=container.logger,
                              metrics=container.metrics, **kwargs)
    return engine, container


def _serve(engine, waves):
    """Run ``waves`` of concurrent (prompt, budget) requests, one wave
    after the other; returns (stats at the end, the finished records)."""
    async def main():
        await engine.start()
        try:
            for wave in waves:
                await asyncio.wait_for(asyncio.gather(*[
                    engine.generate(prompt, max_new_tokens=budget)
                    for prompt, budget in wave]), 120.0)
            await asyncio.sleep(0.02)           # the engine parks
            return engine.stats()
        finally:
            await engine.stop()

    stats = run(main())
    return stats, engine.recorder.snapshot()["recent"]


def _wave(n, budget=12, first=1):
    """Budgets five tokens apart: the requests end in different ticks, so
    a queued one is admitted while the others still decode."""
    return [([first + i, 2, 3, 4 + i], budget + 5 * i) for i in range(n)]


# -- the interval, on hand-made entries ------------------------------------------

def _entry(kind, dispatched_at, landed_at, family="f", work=None):
    return SimpleNamespace(kind=kind, dispatched_at=dispatched_at,
                           landed_at=landed_at, family=family, work=work)


def test_intervals_are_disjoint_where_dispatch_to_publish_overlapped():
    """Four ticks in flight, each dispatched a second after the other and
    landing ten seconds apart: dispatch -> landing windows sum to 94 s of
    a 45 s run, the intervals to the 40 s the device worked."""
    from gofr_tpu.tpu.generate import _Timeline

    timeline = _Timeline()
    work = (4, 10, 64)
    windows = []
    for i in range(4):
        entry = _entry("tick", 5.0 + i, 15.0 + 10.0 * i, work=work)
        windows.append(entry.landed_at - entry.dispatched_at)
        # the first starts at its dispatch: the device had nothing queued
        assert timeline.land(entry) == pytest.approx(10.0)
    assert sum(windows) == pytest.approx(94.0)
    # the device went idle: the next interval starts at the dispatch
    assert timeline.land(_entry("prefill", 50.0, 53.0,
                                family="prefill[nb=1,b=8]")) \
        == pytest.approx(3.0)
    # a landing stamped before its predecessor's (two worker threads) is
    # no negative time, and does not move the timeline back
    assert timeline.land(_entry("tick", 51.0, 52.9, work=work)) == 0.0
    assert timeline.land(_entry("spec", 52.0, 54.0)) == pytest.approx(1.0)
    stats = timeline.stats()
    assert stats["busy_s"] == pytest.approx(44.0)
    assert stats["tick_s"] == pytest.approx(40.0)
    assert stats["prefill_s"] == pytest.approx(3.0)
    assert stats["spec_s"] == pytest.approx(1.0)
    assert (stats["ticks"], stats["tick_steps"]) == (5, 20)
    assert stats["prefill_groups"] == 1
    assert (stats["rows_live"], stats["rows_gathered"]) == (50, 320)
    assert stats["longest"]["prefill"]["family"] == "prefill[nb=1,b=8]"
    assert stats["longest"]["tick"]["s"] == pytest.approx(10.0)
    assert set(stats["longest"]) == {"tick", "prefill", "spec"}


# -- through the engine, a path a case --------------------------------------------

@pytest.fixture(scope="module", params=sorted(PATHS))
def served(request, setup):
    """Seven requests on four slots (three queue for a slot, so prefill
    groups meet decoding requests), then one alone on the engine."""
    cfg, params = setup
    engine, container = _make_engine(cfg, params, **PATHS[request.param])
    assert engine.attn_path == request.param
    stats, records = _serve(engine, [_wave(7), _wave(1, budget=9, first=40)])
    return SimpleNamespace(path=request.param, engine=engine, stats=stats,
                           records=records, container=container)


def test_device_time_is_charged_once(served):
    """With four ticks in flight the timeline's busy time and the sum of
    ``device_seconds`` stay under the loop's wall time (dispatch ->
    publish windows overlapped: 3.6-5.6 x wall), and agree with each
    other and with the executable ledger: one charge, three readers."""
    timeline, loop = served.stats["timeline"], served.stats["loop"]
    assert 0.0 < timeline["busy_s"] <= loop["wall_s"]
    charged = sum(served.stats["device_seconds"].values())
    assert charged <= loop["wall_s"]
    assert charged == pytest.approx(timeline["busy_s"], rel=0.01)
    assert timeline["busy_s"] == pytest.approx(
        timeline["tick_s"] + timeline["prefill_s"] + timeline["spec_s"])
    ledger = served.engine.exec_ledger.snapshot()
    assert ledger["device_seconds_total"] == pytest.approx(
        timeline["busy_s"], rel=0.01)
    counter = sum(served.container.metrics.snapshot()[
        "app_tpu_device_seconds_total"].series.values())
    assert counter == pytest.approx(timeline["busy_s"], rel=0.01)


def test_ticks_are_counted_by_rung_and_by_step(served):
    """A tick is counted once and its k rung's steps with it; its family
    names the rung and nothing else (every tick reads the whole table
    or cache row)."""
    timeline = served.stats["timeline"]
    assert timeline["ticks"] == served.stats["decode_steps"] > 0
    assert timeline["ticks"] <= timeline["tick_steps"] \
        <= 4 * timeline["ticks"]
    assert timeline["prefill_groups"] == served.stats["prefill_batches"]
    # every token but a request's first came from a tick
    tokens = sum(r["tokens"] for r in served.records)
    assert len(served.records) == 8
    assert timeline["tick_tokens"] == tokens - 8
    ticks = [row["family"] for row in
             served.engine.exec_ledger.snapshot(limit=64)["top"]
             if row["family"].startswith("decode")]
    assert timeline["longest"]["tick"]["family"] in ticks
    for family in ticks:
        assert re.fullmatch(r"decode(_paged)?\[k=[124]\]", family), family
    for kind in ("tick", "prefill"):
        longest = timeline["longest"][kind]
        assert 0.0 < longest["s"] <= timeline[kind + "_s"]
        assert longest["family"].startswith(
            "decode" if kind == "tick" else "prefill[")


def test_rows_are_counted_on_the_gather_path_only(served):
    timeline = served.stats["timeline"]
    if served.path != "gather":
        assert timeline["rows_live"] == timeline["rows_gathered"] == 0
        return
    assert 0 < timeline["rows_live"] <= timeline["rows_gathered"]
    # a step gathers the whole table, max_slots x pages_per_slot x page
    # rows, whoever is live
    engine = served.engine
    assert timeline["rows_gathered"] == timeline["tick_steps"] \
        * engine.max_slots * engine.pages_per_slot * engine.kv_page
    # a request of p prompt tokens and t tokens read p+1 .. p+t-1 rows in
    # its steps; a tick's steps past a request's budget read rows too
    least = sum(sum(range(r["prompt_len"] + 1,
                          r["prompt_len"] + r["tokens"]))
                for r in served.records)
    assert timeline["rows_live"] >= least


def test_a_prefill_group_stalls_the_others_and_never_its_own(served):
    timeline = served.stats["timeline"]
    stalled = sum(r["stall_s"] for r in served.records)
    assert stalled == pytest.approx(timeline["stalled_slot_s"], abs=1e-5)
    assert timeline["stalled_slot_s"] > 0.0
    # requests that shared the first admission pass met only the groups
    # admitted after it; the one alone on the engine met none
    alone = [r for r in served.records if r["prompt_len"] == 4
             and r["tokens"] == 9]
    assert len(alone) == 1
    assert alone[0]["prefills_met"] == 0 and alone[0]["stall_s"] == 0.0
    met = [r["prefills_met"] for r in served.records]
    assert max(met) >= 1
    for record in served.records:
        # never more groups than were dispatched besides its own, and
        # never more stall than its decode phase lasted
        assert record["prefills_met"] <= timeline["prefill_groups"] - 1
        assert record["stall_s"] <= record["decode_s"] + 1e-6
        assert (record["stall_s"] > 0.0) == (record["prefills_met"] > 0)


def test_a_request_alone_meets_no_prefill(setup):
    """One request at a time, three in a row: each is its group's only
    row and decodes alone, so no request is stalled and the timeline
    books no stalled slot, whatever the groups cost."""
    cfg, params = setup
    engine, _ = _make_engine(cfg, params, **PATHS["gather"])
    stats, records = _serve(engine, [_wave(1), _wave(1), _wave(1)])
    assert stats["timeline"]["prefill_groups"] == 3
    assert stats["timeline"]["stalled_slot_s"] == 0.0
    assert [r["prefills_met"] for r in records] == [0, 0, 0]
