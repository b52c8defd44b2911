"""Continuous telemetry plane (ISSUE 16): time-series store, anomaly
watchdog, cursor deltas, fleet rollup, and the autoscaler's window-mean
signals.

The load-bearing contracts, in order:

1. MEMORY IS A DOCUMENTED CONSTANT — every ring is bounded; a week of
   uptime holds exactly as many buckets as ten minutes.
2. TIERS ALIGN — all signals sampled at one instant land in the same
   bucket, so the timez series share one time axis.
3. THE DETECTOR HAS HYSTERESIS BOTH WAYS — one outlier never raises,
   one quiet bucket never clears, and the anomaly cannot poison its own
   baseline (guard buckets).
4. DELTAS RESUME — a puller that missed probes resumes from its cursor;
   a cursor that fell off the log (or a restarted source) is told
   ``reset`` instead of being handed a silent gap.
5. WINDOW MEANS DON'T FLAP THE AUTOSCALER — a dead probe's contribution
   decays over the window instead of vanishing from an instantaneous
   sum, so one stale replica no longer manufactures a scale-down
   streak.
"""

import asyncio
from types import SimpleNamespace

import pytest

from gofr_tpu.metrics.timeseries import (DELTA_LOG_CAPACITY,
                                         DELTA_MAX_SAMPLES,
                                         MAX_BUCKETS_PER_SIGNAL, TIERS,
                                         RobustDetector, SeriesRing,
                                         TimeSeriesStore)
from gofr_tpu.slo import SLOTracker, Watchdog
from gofr_tpu.timez import build_timez
from gofr_tpu.tpu.cluster import ROLE_DECODE, ClusterRegistry
from gofr_tpu.tpu.fleet import Autoscaler, FleetRouter, FleetSeriesRollup


class _Metrics:
    def __init__(self):
        self.counters = {}

    def increment_counter(self, name, **labels):
        key = (name, tuple(sorted(labels.items())))
        self.counters[key] = self.counters.get(key, 0) + 1

    def count(self, name):
        return sum(v for (n, _), v in self.counters.items() if n == name)


# -- rings: bounding, alignment, downsampling ---------------------------------

def test_ring_tiers_bound_and_downsample_under_simulated_clock():
    store = TimeSeriesStore()
    clock = {"v": 0.0}
    store.register("sig", lambda: clock["v"])
    # 2 hours of 1 Hz samples — far past every tier capacity
    for t in range(7200):
        clock["v"] = float(t)
        store.sample(now=float(t))

    signal = store._signals["sig"]
    for ring, (_, bucket_s, cap) in zip(signal.rings, TIERS):
        assert len(ring) <= cap
        # every bucket start is aligned on its tier's grid
        assert all(b[0] % bucket_s == 0 for b in ring._buckets)
    # the memory contract holds live
    info = store.memory_info()
    assert info["buckets_held"] <= MAX_BUCKETS_PER_SIGNAL
    assert info["delta_log_held"] <= DELTA_LOG_CAPACITY

    # downsampling is an aggregate, not a decimation: the 10s bucket
    # holding samples 7000..7009 means to 7004.5 and keeps min/max
    ten_s = signal.rings[1]
    bucket = next(b for b in ten_s._buckets if b[0] == 7000.0)
    assert bucket[1] == 10
    assert bucket[2] / bucket[1] == pytest.approx(7004.5)
    assert (bucket[3], bucket[4]) == (7000.0, 7009.0)


def test_series_aligns_signals_on_a_shared_axis():
    store = TimeSeriesStore()
    values = {"a": None, "b": None}
    store.register("a", lambda: values["a"])
    store.register("b", lambda: values["b"])
    # a reports always; b misses the middle sample entirely
    for t, b_val in ((100, 1.0), (101, None), (102, 3.0)):
        values["a"] = float(t)
        values["b"] = b_val
        store.sample(now=float(t))
    out = store.series(tier="1s")
    assert out["t"] == [100.0, 101.0, 102.0]
    assert out["series"]["a"] == [100.0, 101.0, 102.0]
    # alignment fills b's missing instant with None, not a shift
    assert out["series"]["b"] == [1.0, None, 3.0]
    with pytest.raises(ValueError):
        store.series(tier="5m")


def test_counter_signals_difference_into_rates():
    store = TimeSeriesStore()
    cum = {"v": 0.0}
    store.register("c", lambda: cum["v"], kind="counter")
    assert store.sample(now=0.0) == {}          # first sample: no rate yet
    cum["v"] = 10.0
    assert store.sample(now=1.0) == {"c": 10.0}
    cum["v"] = 40.0
    assert store.sample(now=3.0) == {"c": 15.0}  # 30 over 2s
    cum["v"] = 5.0                               # counter reset
    assert store.sample(now=4.0) == {"c": 0.0}   # clamped, not negative


# -- change-point detector ----------------------------------------------------

def _primed_ring(n=40, level=100.0):
    ring = SeriesRing(1.0, 600)
    for t in range(n):
        ring.add(level + (t % 3) * 0.5, float(t))   # small organic wiggle
    return ring, float(n)


def test_detector_requires_streak_then_raises_and_clears():
    det = RobustDetector(threshold=6.0, min_baseline=20,
                         trigger_after=3, clear_after=5)
    ring, t = _primed_ring()
    # two consecutive cliffs: hot streak building, nothing raised
    for _ in range(2):
        ring.add(10.0, t)
        assert det.observe(10.0, ring, t) is None
        t += 1
    # third one raises, direction named
    ring.add(10.0, t)
    event = det.observe(10.0, ring, t)
    assert event == {"state": "raised", "direction": "down",
                     "z": event["z"], "at": t}
    assert det.active["direction"] == "down"
    t += 1
    # recovery: clear_after-1 quiet samples keep it active (hysteresis)
    for _ in range(4):
        ring.add(100.0, t)
        assert det.observe(100.0, ring, t) is None
        assert det.active is not None
        t += 1
    ring.add(100.0, t)
    event = det.observe(100.0, ring, t)
    assert event["state"] == "cleared"
    assert det.active is None


def test_detector_ignores_in_band_wiggle_and_thin_baselines():
    det = RobustDetector(min_baseline=20, trigger_after=1)
    ring, t = _primed_ring(n=10)      # below min_baseline
    assert det.observe(500.0, ring, t) is None     # no baseline, no call
    ring, t = _primed_ring()
    for value in (101.0, 99.5, 100.8):             # organic variation
        ring.add(value, t)
        assert det.observe(value, ring, t) is None
        t += 1
    assert det.active is None


def test_idle_cold_start_is_not_an_anomaly():
    # a server idling at zero, then taking its first traffic: a
    # dead-flat zero baseline has no variance and no level, so the
    # move is cold start, not a change point (live-app regression —
    # the epsilon floor used to score it z=800000 "up")
    det = RobustDetector(trigger_after=1)
    ring = SeriesRing(1.0, 600)
    for t in range(40):
        ring.add(0.0, float(t))
    t = 40.0
    for value in (0.8, 12.0, 11.0):       # traffic arrives and ramps
        ring.add(value, t)
        assert det.observe(value, ring, t) is None
        t += 1.0
    assert det.active is None


def test_flat_baseline_does_not_explode_z_scores():
    # a perfectly flat signal (mad == 0) must not turn a 1% wiggle into
    # an infinite z — the MAD floor prices the smallest scoreable move
    det = RobustDetector(trigger_after=1)
    ring = SeriesRing(1.0, 600)
    for t in range(40):
        ring.add(100.0, float(t))
    assert det.observe(101.0, ring, 40.0) is None
    assert abs(det.last_z) < 6.0


# -- anomalies feed the metric + the watchdog ---------------------------------

def _goodput_store(metrics=None):
    store = TimeSeriesStore(metrics=metrics, detector_min_baseline=20,
                            detector_trigger_after=3)
    feed = {"v": 100.0}
    store.register("goodput_tok_s", lambda: feed["v"], watch="down")
    store.register("padding_ratio", lambda: 0.2, watch="up")
    return store, feed


def test_goodput_cliff_raises_anomaly_names_signal_in_watchdog():
    metrics = _Metrics()
    store, feed = _goodput_store(metrics)
    t = 0.0
    for _ in range(40):
        store.sample(now=t)
        t += 1.0
    assert store.watchdog_reasons() == []
    feed["v"] = 5.0                       # the cliff
    for _ in range(3):                    # one detector window
        store.sample(now=t)
        t += 1.0
    active = store.anomalies()["active"]
    assert "goodput_tok_s" in active
    assert active["goodput_tok_s"]["direction"] == "down"
    assert metrics.count("app_tpu_anomaly_total") == 1
    reasons = store.watchdog_reasons()
    assert len(reasons) == 1
    assert "goodput_tok_s down" in reasons[0]

    # the watchdog consumes the feed: DEGRADED after its own hysteresis,
    # with the offending signal named in statusz
    watchdog = Watchdog(SLOTracker(), hysteresis=2,
                        anomaly_fn=store.watchdog_reasons)
    assert watchdog.evaluate(now=t) == "READY"
    assert watchdog.evaluate(now=t) == "DEGRADED"
    assert any("goodput_tok_s" in r
               for r in watchdog.statusz()["last_reasons"])


def test_watch_direction_filters_benign_moves():
    store, feed = _goodput_store()
    t = 0.0
    for _ in range(40):
        store.sample(now=t)
        t += 1.0
    feed["v"] = 5000.0                    # goodput SPIKE: good news
    for _ in range(4):
        store.sample(now=t)
        t += 1.0
    assert "goodput_tok_s" in store.anomalies()["active"]
    # ...but a spike on a watch="down" signal never degrades health
    assert store.watchdog_reasons() == []


# -- cursor deltas ------------------------------------------------------------

def test_delta_cursor_resumes_after_missed_probes():
    store = TimeSeriesStore()
    store.register("q", lambda: 1.0)
    t = 0.0
    for _ in range(10):
        store.sample(now=t)
        t += 1.0
    first = store.delta(None)
    assert first["reset"] is True          # no cursor: fresh start
    assert first["cursor"] == 10
    assert len(first["samples"]) == 10

    # a few missed probes later, the puller resumes contiguously
    for _ in range(5):
        store.sample(now=t)
        t += 1.0
    resumed = store.delta(first["cursor"])
    assert resumed["reset"] is False
    assert [s["seq"] for s in resumed["samples"]] == [11, 12, 13, 14, 15]

    # nothing new: empty, same cursor, still not a reset
    idle = store.delta(resumed["cursor"])
    assert idle["samples"] == [] and idle["reset"] is False


def test_delta_resets_when_cursor_falls_off_or_rewinds():
    store = TimeSeriesStore()
    store.register("q", lambda: 1.0)
    t = 0.0
    for _ in range(DELTA_LOG_CAPACITY + 50):   # push the log past capacity
        store.sample(now=t)
        t += 1.0
    stale = store.delta(10)                    # cursor fell off the log
    assert stale["reset"] is True
    assert len(stale["samples"]) <= DELTA_MAX_SAMPLES
    # a rewound sequence (source restarted) is also a reset
    rewound = store.delta(10 ** 9)
    assert rewound["reset"] is True


# -- tick anatomy + sparklines + schema ---------------------------------------

def test_tick_ring_is_bounded_and_aggregates_phases():
    store = TimeSeriesStore(tick_capacity=16)
    for i in range(100):
        store.note_tick({"admission_s": 0.001 * i, "device_wait_s": 0.01,
                         "kind": "tick", "batch": 2})
    out = store.tick_anatomy(limit=4)
    assert out["recorded"] == 16               # ring, not a log
    assert out["capacity"] == 16
    assert len(out["recent"]) == 4
    assert out["phases"]["device_wait_s"]["max_s"] == pytest.approx(0.01)
    assert "admission_s" in out["phases"]


def test_sparklines_render_and_flag_active_anomalies():
    store, feed = _goodput_store()
    t = 0.0
    for _ in range(40):
        store.sample(now=t)
        t += 1.0
    feed["v"] = 5.0
    for _ in range(3):
        store.sample(now=t)
        t += 1.0
    lines = store.sparklines(tier="1s")
    good = next(l for l in lines if l.startswith("goodput_tok_s"))
    assert "!! down" in good
    pad = next(l for l in lines if l.startswith("padding_ratio"))
    assert "!!" not in pad


def test_timez_schema_and_cursor_mode():
    store = TimeSeriesStore()
    store.register("q", lambda: 2.0)
    for t in range(30):
        store.sample(now=float(t))
    app = SimpleNamespace(container=SimpleNamespace(
        app_name="t", app_version="v", telemetry=store))
    page = build_timez(app, tier="1s", signals=["q"], limit=5)
    assert sorted(page) == ["anomalies", "app", "memory", "series",
                            "signals", "sparklines", "ticks"]
    assert page["signals"] == ["q"]
    assert page["series"]["tier"] == "1s"
    assert len(page["series"]["t"]) == 5
    assert page["memory"]["max_buckets_per_signal"] == \
        MAX_BUCKETS_PER_SIGNAL
    # cursor switches to the bounded delta payload
    pull = build_timez(app, cursor=0)
    assert sorted(pull) == ["app", "delta"]
    assert pull["delta"]["cursor"] == 30
    # no store wired: explicit null, not an error
    empty = build_timez(SimpleNamespace(container=SimpleNamespace(
        app_name="t", app_version="v", telemetry=None)))
    assert empty["telemetry"] is None


def test_broken_signal_sources_never_break_sampling():
    store = TimeSeriesStore()
    store.register("ok", lambda: 1.0)
    store.register("boom", lambda: 1 / 0)
    store.register_provider(("p",), lambda: {"p": None})
    assert store.sample(now=0.0) == {"ok": 1.0}


# -- fleet series rollup ------------------------------------------------------

def _delta(cursor, samples, reset=False):
    return {"cursor": cursor, "reset": reset, "interval_s": 1.0,
            "samples": [
                {"seq": cursor - len(samples) + 1 + i, "t": t,
                 "values": values}
                for i, (t, values) in enumerate(samples)]}


def test_rollup_window_means_sum_queue_and_max_occupancy():
    rollup = FleetSeriesRollup(window_s=30.0)
    rollup.ingest("d0", _delta(2, [
        (10.0, {"queue_depth": 4, "kv_occupancy": 0.5,
                "goodput_tok_s": 100.0}),
        (11.0, {"queue_depth": 6, "kv_occupancy": 0.7,
                "goodput_tok_s": 80.0}),
    ]), now=100.0)
    rollup.ingest("d1", _delta(2, [
        (20.0, {"queue_depth": 1, "kv_occupancy": 0.2,
                "goodput_tok_s": 50.0}),
    ]), now=100.0)
    sig = rollup.signals(now=100.0)
    assert sig["queue_depth"] == pytest.approx(6.0)   # 5 + 1 (sums)
    assert sig["occupancy"] == pytest.approx(0.6)     # max of replica means
    assert sig["goodput_tok_s"] == pytest.approx(140.0)
    assert sig["contributing"] == 2
    # cursor bookkeeping for the next pull
    assert rollup.cursor("d0") == 2 and rollup.cursor("d1") == 2


def test_rollup_reset_drops_stale_window_and_misses_decay():
    rollup = FleetSeriesRollup(window_s=30.0)
    rollup.ingest("d0", _delta(5, [(10.0, {"queue_depth": 50,
                                           "kv_occupancy": 0.9,
                                           "goodput_tok_s": 1.0})]),
                  now=100.0)
    # the replica restarted: reset delta must not blend with old samples
    rollup.ingest("d0", _delta(2, [(3.0, {"queue_depth": 1,
                                          "kv_occupancy": 0.1,
                                          "goodput_tok_s": 1.0})],
                               reset=True), now=110.0)
    assert rollup.signals(now=110.0)["queue_depth"] == pytest.approx(1.0)
    # a missed probe keeps the window contributing...
    rollup.note_miss("d0", now=120.0)
    assert rollup.signals(now=120.0)["queue_depth"] == pytest.approx(1.0)
    # ...until the window drains past it
    assert rollup.signals(now=200.0)["queue_depth"] is None
    assert rollup.statusz(now=120.0)["misses"] == {"d0": 1}
    rollup.drop("d0")
    assert rollup.statusz(now=120.0)["replicas"] == {}


class _ProbeTransport:
    """Decode transport double: live probes answer, dead ones raise."""

    kind = "probe"

    def __init__(self, queue_depth=2, store=None):
        self.queue_depth = queue_depth
        self.dead = False
        self.store = store

    def available(self):
        return True

    def health_check(self):
        return {"status": "UP"}

    def describe(self):
        return {"kind": self.kind}

    async def observe(self):
        if self.dead:
            raise RuntimeError("probe timeout")
        return {"kind": self.kind, "health": "UP",
                "stats": {"queue_depth": self.queue_depth}}

    async def telemetry_delta(self, cursor=None):
        if self.dead or self.store is None:
            raise RuntimeError("probe timeout")
        return self.store.delta(cursor)


def test_refresh_pulls_deltas_and_resumes_cursors():
    store = TimeSeriesStore()
    feed = {"v": 3.0}
    store.register("queue_depth", lambda: feed["v"])
    store.register("kv_occupancy", lambda: 0.4)
    store.register("goodput_tok_s", lambda: 120.0)
    for t in range(5):
        store.sample(now=float(t))

    cluster = ClusterRegistry()
    live = _ProbeTransport(store=store)
    cluster.register("d0", "decode", live)
    router = FleetRouter(cluster)

    async def run():
        await router.refresh()
        assert router.rollup.cursor("d0") == 5
        sig = router.rollup.signals()
        assert sig["queue_depth"] == pytest.approx(3.0)
        # more samples, another pass: the cursor advances, no reset
        for t in range(5, 8):
            store.sample(now=float(t))
        await router.refresh()
        assert router.rollup.cursor("d0") == 8
        assert router.rollup._resets <= 1      # only the initial pull
        # a dead probe on the next pass is a miss, never an exception
        live.dead = True
        await router.refresh()
        assert router.rollup._misses.get("d0", 0) >= 1

    asyncio.run(run())


# -- autoscaler flap regression -----------------------------------------------

def _flap_fixture():
    """Two decode replicas, each holding fleet queue depth 2; losing one
    probe used to read as the fleet going idle (sum 2 <= queue_low 2)."""
    cluster = ClusterRegistry()
    transports = {name: _ProbeTransport(queue_depth=2)
                  for name in ("d0", "d1")}
    for name, transport in transports.items():
        cluster.register(name, "decode", transport)
    router = FleetRouter(cluster)
    calls = []
    scaler = Autoscaler(cluster, router=router,
                        scale_up=lambda: calls.append("up"),
                        scale_down=lambda name: calls.append(
                            ("down", name)),
                        min_decode=1, max_decode=3,
                        queue_high=10, queue_low=2,
                        up_after=2, down_after=2, cooldown_s=0.0)
    return cluster, transports, router, scaler, calls


def test_dead_probe_no_longer_produces_a_scale_down_streak():
    import time as _time

    cluster, transports, router, scaler, calls = _flap_fixture()
    now = _time.monotonic()
    for name in transports:
        router.rollup.ingest(name, _delta(3, [
            (now - 2.0 + i, {"queue_depth": 2.0, "kv_occupancy": 0.3,
                             "goodput_tok_s": 10.0})
            for i in range(3)]), now=now)

    async def run():
        transports["d1"].dead = True            # the probe dies NOW
        for _ in range(3):                      # > down_after firings
            event = await scaler()
            assert event["signals"]["source"] == "rollup"
        # window means keep d1's contribution: no manufactured idle
        assert calls == []
        assert scaler._down_streak == 0

    asyncio.run(run())


def test_gather_falls_back_to_probe_sweep_without_rollup_data():
    cluster, transports, router, scaler, calls = _flap_fixture()

    async def run():
        # empty rollup: the probe sweep serves, and it still carries the
        # old failure mode — the dead probe's share vanishes from the
        # sum and two firings manufacture a scale-down. This is the
        # behavior the rollup path exists to retire.
        transports["d1"].dead = True
        first = await scaler._gather()
        assert first["source"] == "probe"
        assert first["queue_depth"] == 2        # d1's 2 silently missing
        for _ in range(2):
            await scaler()
        assert ("down", "d0") in calls or ("down", "d1") in calls

    asyncio.run(run())


# -- engine integration: sampled decode-tick anatomy --------------------------

@pytest.fixture(scope="module")
def setup():
    import jax
    from gofr_tpu.models import llama
    cfg = llama.config("tiny")
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _make_engine(cfg, params, **kwargs):
    from gofr_tpu.container import new_mock_container
    from gofr_tpu.tpu.generate import GenerationEngine
    container = new_mock_container()
    kwargs.setdefault("max_slots", 2)
    kwargs.setdefault("max_len", 32)
    kwargs.setdefault("prompt_buckets", (8,))
    kwargs.setdefault("paged_kv", True)
    kwargs.setdefault("kv_page", 4)
    engine = GenerationEngine(cfg, params, logger=container.logger,
                              metrics=container.metrics, **kwargs)
    return engine, container


def test_engine_records_sampled_tick_anatomy(setup):
    cfg, params = setup
    engine, _ = _make_engine(cfg, params)
    store = TimeSeriesStore(tick_capacity=64, tick_sample=4)

    async def run():
        await engine.start()
        try:
            # unattached first: the ≤1% overhead bound rests on this
            # path doing nothing — no clock reads, no sequence counting,
            # no dict allocation
            await engine.generate([1, 2, 3], max_new_tokens=6)
            assert engine.telemetry is None
            assert engine._tick_seq == 0
            # same engine (same compiled executables), now attached
            engine.attach_telemetry(store, every=store.tick_sample)
            assert engine._tick_every == 4
            await engine.generate([1, 2, 3], max_new_tokens=12)
        finally:
            await engine.stop()

    asyncio.run(run())
    assert engine._tick_seq > 0
    out = engine.telemetry.tick_anatomy()
    assert out["sample_every"] == 4
    # every 4th dispatched tick lands in the ring (allow boundary slack)
    assert out["recorded"] >= engine._tick_seq // 4
    assert out["recorded"] <= engine._tick_seq // 4 + 1
    entry = out["recent"][-1]
    assert entry["kind"] in ("tick", "spec")
    assert entry["batch"] >= 1
    # the ring is a view of the loop clock (ISSUE 25): the old keys, the
    # publish phase, and the CPU twins of the phases that hold the thread
    for phase in ("admission_s", "host_dispatch_s", "device_wait_s",
                  "publish_s", "admission_cpu_s", "host_dispatch_cpu_s",
                  "publish_cpu_s"):
        assert entry[phase] >= 0.0
    assert out["phases"]["publish_s"]["mean_s"] > 0.0
    assert out["phases"]["device_wait_s"]["mean_s"] > 0.0
