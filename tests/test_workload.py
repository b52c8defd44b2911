"""Workload capture & replay plane tests (ISSUE 17).

Load-bearing contracts:
- the TrafficRecorder ring is bounded (deque(maxlen)) under sustained
  traffic and stores shape only — never token ids or strings;
- exported traces round-trip through JSON bit-faithfully and a
  kind/version skew raises TraceVersionError instead of replaying;
- replay_trace is deterministic: two replays of the same trace through
  a live engine produce identical admitted-token counts, per-class
  outcome tallies, and digests;
- the xlaz suggested-ladder DP re-weights by recorded traffic shape
  when a recorder is attached (ladder_source flips);
- charge_device_time keeps the per-class aggregate and the
  per-executable family ledger in agreement by construction.
"""

import asyncio
import json

import jax
import pytest

from gofr_tpu.container import new_mock_container
from gofr_tpu.models import llama
from gofr_tpu.tpu.compile_ledger import ExecutableLedger, charge_device_time
from gofr_tpu.tpu.flightrecorder import RequestRecord
from gofr_tpu.tpu.generate import GenerationEngine
from gofr_tpu.tpu.workload import (TraceVersionError, TrafficRecorder,
                                   WorkloadTrace, _request_seed,
                                   _synth_prompt, load_trace,
                                   new_traffic_recorder, replay_trace)


class _Metrics:
    """Counts increment_counter / delta_updown_counter calls by name."""

    def __init__(self):
        self.counts = {}
        self.sums = {}

    def increment_counter(self, name, **labels):
        key = (name, tuple(sorted(labels.items())))
        self.counts[key] = self.counts.get(key, 0) + 1

    def delta_updown_counter(self, name, value, **labels):
        key = (name, tuple(sorted(labels.items())))
        self.sums[key] = self.sums.get(key, 0.0) + value

    def count(self, name):
        return sum(v for (n, _), v in self.counts.items() if n == name)

    def total(self, name):
        return sum(v for (n, _), v in self.sums.items() if n == name)


def _record(model="generate", prompt_len=8, budget=4):
    return RequestRecord(model=model, prompt_len=prompt_len, budget=budget)


def _admit_n(rec, n, prompt_len=8, cls="standard", start=100.0, step=0.01):
    records = []
    for i in range(n):
        record = _record(prompt_len=prompt_len)
        rec.admit(record, cls, now=start + i * step)
        records.append(record)
    return records


# -- recorder ring -----------------------------------------------------------
def test_ring_bounded_under_sustained_traffic():
    metrics = _Metrics()
    rec = TrafficRecorder(capacity=32, metrics=metrics)
    _admit_n(rec, 500)
    snap = rec.snapshot()
    assert snap["window_events"] == 32          # ring stayed bounded
    assert snap["admitted_total"] == 500        # totals kept counting
    assert metrics.count("app_tpu_workload_events_total") == 500
    # the batcher plane is bounded by the same capacity
    for i in range(500):
        rec.note_enqueue("classify", now=200.0 + i * 0.001)
    assert len(rec._enqueue_dt) == 32


def test_finish_closes_event_once():
    rec = TrafficRecorder(capacity=8)
    record = _record(prompt_len=5, budget=7)
    event = rec.admit(record, "interactive", now=10.0)
    assert record.wevent is event
    assert event.finish is None
    record.tokens = 7
    record.cached_prefix_len = 3
    record.status = "done"
    rec.finish(record)
    assert event.output_len == 7
    assert event.cached_prefix_len == 3
    assert event.finish == "done"
    assert record.wevent is None                # parked event cleared
    # second finish (e.g. a cancelled-then-drained race) is a no-op
    record.status = "cancelled"
    rec.finish(record)
    assert event.finish == "done"
    assert rec.snapshot()["finished_total"] == 1


def test_snapshot_mixes_and_prefix_reuse():
    rec = TrafficRecorder(capacity=64)
    for i, (cls, cached) in enumerate(
            [("interactive", 4), ("standard", 0), ("standard", 2),
             ("batch", 0)]):
        record = _record(prompt_len=8)
        rec.admit(record, cls, now=50.0 + i)
        record.tokens = 3
        record.cached_prefix_len = cached
        record.status = "done"
        rec.finish(record)
    snap = rec.snapshot()
    assert snap["class_mix"] == {"interactive": 1, "standard": 2, "batch": 1}
    assert snap["finish_mix"] == {"done": 4}
    reuse = snap["prefix_reuse"]
    assert reuse["requests_with_reuse"] == 2
    assert reuse["request_rate"] == 0.5
    assert reuse["token_rate"] == round(6 / 32, 4)
    assert snap["interarrival_s"]["mean"] == 1.0


def test_class_mix_cardinality_is_gated():
    rec = TrafficRecorder(capacity=4)
    for i in range(200):
        rec.admit(_record(), f"cls{i}", now=10.0 + i)
    mix = rec.snapshot()["class_mix"]
    assert len(mix) <= 65                       # _MAX_KEYS + "_other"
    assert mix["_other"] == 200 - (len(mix) - 1)


# -- trace export / import ---------------------------------------------------
def _finished_trace(n=6, prompt_len=9, cls="standard"):
    rec = TrafficRecorder(capacity=64)
    for i, record in enumerate(_admit_n(rec, n, prompt_len=prompt_len,
                                        cls=cls, step=0.005)):
        record.tokens = 3 + (i % 2)
        record.status = "done"
        rec.finish(record)
    return rec.export_trace()


def test_trace_round_trips_through_json():
    data = _finished_trace()
    trace = load_trace(json.dumps(data))       # string path
    again = load_trace(data)                   # dict path
    assert trace.version == 1
    assert len(trace.events) == 6
    for a, b in zip(trace.events, again.events):
        for field in ("dt_s", "cls", "model", "prompt_len", "budget",
                      "output_len", "deadline_ms", "cached_prefix_len",
                      "finish"):
            assert getattr(a, field) == getattr(b, field)
    event = trace.events[1]
    assert event.dt_s == 0.005
    assert event.model == "generate"
    assert event.cls == "standard"
    assert event.prompt_len == 9
    assert event.output_len == 4
    assert event.finish == "done"
    assert event.deadline_ms is None


def test_trace_version_and_kind_rejected_on_skew():
    data = _finished_trace()
    stale = dict(data, version=99)
    with pytest.raises(TraceVersionError):
        load_trace(stale)
    alien = dict(data, kind="some-other-payload")
    with pytest.raises(TraceVersionError):
        load_trace(alien)
    with pytest.raises(TraceVersionError):
        load_trace([1, 2, 3])
    # TraceVersionError is a ValueError — callers catching broadly still work
    assert issubclass(TraceVersionError, ValueError)


def test_synth_prompt_deterministic_and_in_vocab():
    a = _synth_prompt(3, 17, 256, seed=42)
    b = _synth_prompt(3, 17, 256, seed=42)
    assert a == b
    assert len(a) == 17
    assert all(1 <= t < 256 for t in a)        # never the pad id 0
    assert _synth_prompt(4, 17, 256, seed=42) != a
    assert _request_seed(5, 7) == _request_seed(5, 7)
    assert _request_seed(5, 7) != _request_seed(6, 7)


# -- config factory ----------------------------------------------------------
class _Config:
    def __init__(self, values=None):
        self.values = values or {}

    def get(self, key, default=None):
        return self.values.get(key, default)

    def get_int(self, key, default=0):
        return int(self.values.get(key, default))


def test_new_traffic_recorder_knobs():
    assert new_traffic_recorder(_Config()).capacity == 2048
    assert new_traffic_recorder(
        _Config({"TRAFFIC_REC_CAPACITY": "64"})).capacity == 64
    assert new_traffic_recorder(
        _Config({"TRAFFIC_REC_ENABLED": "off"})) is None
    assert new_traffic_recorder(
        _Config({"TRAFFIC_REC_CAPACITY": "0"})) is None


# -- shared timing helper / executable ledger --------------------------------
def test_charge_device_time_totals_agree():
    """One elapsed charges both planes; their totals must be equal."""
    metrics = _Metrics()
    ledger = ExecutableLedger(metrics=metrics)
    device_seconds = {}
    charge_device_time(0.12, "llama", classes=["interactive", "standard"],
                       family="decode_paged[k=8]",
                       device_seconds=device_seconds, metrics=metrics,
                       ledger=ledger)
    charge_device_time(0.03, "llama", classes=["standard"],
                       family="prefill[nb=1,b=16]",
                       device_seconds=device_seconds, metrics=metrics,
                       ledger=ledger)
    agg = sum(device_seconds.values())
    assert agg == pytest.approx(0.15)
    assert ledger.total_seconds("llama") == pytest.approx(agg)
    assert metrics.total("app_tpu_device_seconds_total") == \
        pytest.approx(metrics.total("app_tpu_executable_device_seconds_total"))
    # class split is even across participants
    assert device_seconds[("llama", "interactive")] == pytest.approx(0.06)
    assert device_seconds[("llama", "standard")] == pytest.approx(0.09)
    # executor path: family only, aggregate untouched
    charge_device_time(0.5, "classify", family="b32", ledger=ledger,
                       flops=1.0e9)
    assert ("classify", "b32") not in device_seconds
    assert ledger.total_seconds("classify") == pytest.approx(0.5)


def test_executable_ledger_roofline_and_bounds():
    ledger = ExecutableLedger(peak_flops=4.0e9, max_families=2)
    ledger.charge("m", "b8", 0.5, flops=1.0e9)
    ledger.charge("m", "b8", 0.5, flops=1.0e9)
    ledger.charge("m", "b16", 1.0)
    ledger.charge("m", "b32", 1.0)              # over the family cap
    snap = ledger.snapshot()
    assert snap["families"] == 2
    assert snap["dropped_families"] == 1
    top = snap["top"][0]
    assert top["family"] in ("b8", "b16")
    by_family = {row["family"]: row for row in snap["top"]}
    assert by_family["b8"]["dispatches"] == 2
    assert by_family["b8"]["achieved_flops_per_s"] == pytest.approx(2.0e9)
    assert by_family["b8"]["roofline_ratio"] == pytest.approx(0.5)
    assert by_family["b16"]["roofline_ratio"] is None
    assert sum(row["share"] for row in snap["top"]) == pytest.approx(1.0)


# -- engine integration: replay determinism + ladder re-weight ---------------
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
    return GenerationEngine(cfg, params, logger=container.logger,
                            metrics=container.metrics, **kwargs)


def test_replay_is_deterministic(setup):
    """Two replays of the same trace → identical admitted-token counts,
    per-class tallies, and digest (the ISSUE 17 acceptance bar)."""
    cfg, params = setup
    rec = TrafficRecorder(capacity=64)
    lens = [(3, "interactive"), (5, "standard"), (4, "standard"),
            (6, "batch")]
    for i, (plen, cls) in enumerate(lens):
        record = _record(prompt_len=plen, budget=4)
        rec.admit(record, cls, now=10.0 + i * 0.002)
        record.tokens = 3
        record.status = "done"
        rec.finish(record)
    trace = load_trace(json.dumps(rec.export_trace()))

    async def run_once():
        engine = _make_engine(cfg, params)
        await engine.start()
        try:
            return await asyncio.wait_for(
                replay_trace(engine, trace, time_scale=0.0), 120.0)
        finally:
            await engine.stop()

    first = asyncio.run(run_once())
    second = asyncio.run(run_once())
    assert first["requests"] == len(lens)
    assert first["errors"] == 0
    assert first["admitted_tokens"] == 3 * len(lens)   # recorded lengths
    assert first["per_class"]["standard"]["requests"] == 2
    assert first["per_class"]["interactive"]["outcomes"] == {"ok": 1}
    assert first["digest"] == second["digest"]
    assert first == second


def test_xlaz_ladder_reweights_with_recorded_traffic(setup):
    """The suggested-ladder DP must follow the recorder's recent window
    when one is attached, and fall back to lifetime shape stats when
    not — the ladder_source tag says which happened."""
    cfg, params = setup
    engine = _make_engine(cfg, params, prompt_buckets=(8, 64))
    # lifetime history says short prompts...
    for _ in range(50):
        engine.shapes.record("prompt", 4, 8)
    base = engine.xlaz(max_rungs=2)["models"]["prompt"]
    assert base["ladder_source"] == "observed_lengths"
    assert max(base["suggested_ladder"]) <= 8
    # ...but recent recorded traffic is long: suggestion must move
    rec = TrafficRecorder(capacity=64)
    for i in range(50):
        rec.admit(_record(model=engine.model_name, prompt_len=60),
                  "standard", now=10.0 + i * 0.01)
    engine.attach_workload(rec)
    shifted = engine.xlaz(max_rungs=2)["models"]["prompt"]
    assert shifted["ladder_source"] == "workload_trace"
    assert max(shifted["suggested_ladder"]) >= 60
    assert shifted["suggested_ladder"] != base["suggested_ladder"]


def test_engine_attributes_device_time_to_families(setup):
    """After real traffic, the per-family executable ledger total must
    agree with the per-class aggregate (shared charge site) and xlaz
    must rank families."""
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params)
        rec = TrafficRecorder(capacity=64)
        engine.attach_workload(rec)
        await engine.start()
        try:
            await asyncio.wait_for(asyncio.gather(*[
                engine.generate([i + 1, i + 2], max_new_tokens=4)
                for i in range(3)]), 120.0)
        finally:
            await engine.stop()
        agg = sum(engine._device_seconds.values())
        fam = engine.exec_ledger.total_seconds(engine.model_name)
        assert agg > 0
        assert fam == pytest.approx(agg, rel=1e-6)   # same charge site
        snap = engine.xlaz()["executables"]
        families = {row["family"] for row in snap["top"]}
        assert any(f.startswith("prefill[") for f in families)
        assert any(f.startswith("decode") for f in families)
        # workload plane saw the traffic end to end
        wsnap = rec.snapshot()
        assert wsnap["admitted_total"] == 3
        assert wsnap["finished_total"] == 3
        assert wsnap["finish_mix"] == {"done": 3}
    asyncio.run(main())
