"""Compile-plane & shape observability (ISSUE 3): the compile ledger,
padding/bucket-fit accounting, step-phase anatomy, the recompile-storm
watchdog signal, /debug/xlaz, and the metrics-catalog drift lint.

Everything runs on the CPU backend — a serve-time XLA compile on CPU is
the identical code path to one on a TPU slice, just cheaper. Watchdog and
window tests drive the clock explicitly (every API takes ``now``)."""

import asyncio
import json
import subprocess
import sys
import time

import numpy as np
import pytest

from gofr_tpu.container import new_mock_container
from gofr_tpu.slo import SLOTracker, Watchdog, new_watchdog
from gofr_tpu.tpu import DynamicBatcher, Executor
from gofr_tpu.tpu.compile_ledger import (
    CAUSE_SERVING,
    CAUSE_WARMUP,
    CompileLedger,
    ShapeStats,
    suggest_ladder,
)
from gofr_tpu.tpu.flightrecorder import FlightRecorder
from tests.util import http_request, make_app, run, serving


def _simple_model():
    def fn(params, x):
        return x * 2.0

    return fn, {}


class _SpyLogger:
    """Captures log lines by level; duck-types the framework logger."""

    def __init__(self):
        self.lines = {"debug": [], "info": [], "warn": [], "error": []}

    def _log(self, level, message, *args, **fields):
        self.lines[level].append(message % args if args else message)

    def debug(self, *a, **k):
        self._log("debug", *a, **k)

    def info(self, *a, **k):
        self._log("info", *a, **k)

    def warn(self, *a, **k):
        self._log("warn", *a, **k)

    def error(self, *a, **k):
        self._log("error", *a, **k)


# -- compile ledger ----------------------------------------------------------

class TestCompileLedger:
    def test_warmup_compiles_are_ledgered_with_cause_warmup(self):
        container = new_mock_container()
        executor = Executor(container.logger, container.metrics)
        fn, params = _simple_model()
        executor.register("m", fn, params, buckets=(1, 2, 4))
        executor.warmup("m", np.ones((3,), np.float32))
        assert executor.ledger.total() == 3
        assert executor.ledger.total(CAUSE_WARMUP) == 3
        assert executor.ledger.total(CAUSE_SERVING) == 0
        assert container.metrics.value("app_tpu_compile_total",
                                       cause="warmup", model="m") == 3.0
        assert container.metrics.value("app_tpu_compile_total",
                                       cause="serving", model="m") is None
        snap = executor.ledger.snapshot()
        assert snap["by_cause"] == {"warmup": 3}
        assert {e["bucket"] for e in snap["recent"]} == {1, 2, 4}
        # distinct buckets lower to distinct programs
        prints = {e["fingerprint"] for e in snap["recent"]}
        assert None not in prints and len(prints) == 3

    def test_serve_time_compile_ledgered_and_logged_at_warn(self):
        """The acceptance path: a request at an unwarmed bucket compiles
        at serve time — serving counter increments, the event lands in
        the ledger with an HLO fingerprint, and the executor warns about
        the queue impact before and after."""
        container = new_mock_container()
        logger = _SpyLogger()
        executor = Executor(logger, container.metrics)
        fn, params = _simple_model()
        executor.register("m", fn, params, buckets=(2, 4))
        executor.warmup("m", np.ones((3,), np.float32))
        logger.lines["warn"].clear()

        # warm bucket: no new compile
        executor.predict("m", np.ones((2, 3), np.float32))
        assert executor.ledger.total(CAUSE_SERVING) == 0

        # drop the compiled executable for bucket 4 → next hit recompiles
        del executor._models["m"].compiled[4]
        executor.predict("m", np.ones((3, 3), np.float32))
        assert executor.ledger.total(CAUSE_SERVING) == 1
        assert container.metrics.value("app_tpu_compile_total",
                                       cause="serving", model="m") == 1.0
        event = executor.ledger.snapshot()["recent"][0]
        assert event["cause"] == "serving"
        assert event["bucket"] == 4
        assert event["fingerprint"] is not None
        # same shape recompiled → same program → same fingerprint as the
        # warmup compile of bucket 4 (the eviction-forensics signal)
        warmup_event = next(e for e in executor.ledger.snapshot()["recent"]
                            if e["cause"] == "warmup" and e["bucket"] == 4)
        assert event["fingerprint"] == warmup_event["fingerprint"]
        assert any("serve-time compile" in line and "queue" in line
                   for line in logger.lines["warn"])

    def test_serving_window_and_statusz_section(self):
        ledger = CompileLedger()
        for i in range(3):
            ledger.record("m", 4, CAUSE_SERVING, 1.0, now=100.0 + i)
        assert ledger.serving_compiles(60.0, now=104.0) == 3.0
        # outside the window they stop counting (lifetime totals persist)
        assert ledger.serving_compiles(60.0, now=500.0) == 0.0
        assert ledger.total(CAUSE_SERVING) == 3

    def test_health_check_lists_in_progress_compiles(self):
        container = new_mock_container()
        executor = Executor(container.logger, container.metrics)
        fn, params = _simple_model()
        executor.register("m", fn, params, buckets=(2,))
        executor._compiling[("m", 2)] = time.monotonic() - 1.5
        health = executor.health_check()
        entry, = health["compiling"]
        assert entry["model"] == "m" and entry["bucket"] == 2
        assert entry["for_s"] == pytest.approx(1.5, abs=0.5)
        executor._compiling.clear()
        assert executor.health_check()["compiling"] == []


# -- recompile-storm watchdog signal -----------------------------------------

class TestRecompileStorm:
    def test_burst_of_serving_compiles_flips_degraded(self):
        container = new_mock_container()
        slo = SLOTracker(container.metrics)
        ledger = CompileLedger()
        dog = Watchdog(slo, metrics=container.metrics, hysteresis=1,
                       window_s=60.0, ledger=ledger, max_serving_compiles=2)
        assert dog.evaluate(now=50.0) == "READY"
        for i in range(3):
            ledger.record("m", 4, CAUSE_SERVING, 2.0, now=100.0 + i)
        assert dog.evaluate(now=105.0) == "DEGRADED"
        assert any("recompile storm" in reason
                   for reason in dog._last_reasons)
        # the storm ages out of the window → recovery
        assert dog.evaluate(now=400.0) == "READY"

    def test_warmup_compiles_never_trip_the_watchdog(self):
        ledger = CompileLedger()
        dog = Watchdog(SLOTracker(), hysteresis=1, ledger=ledger,
                       max_serving_compiles=0)
        for i in range(10):
            ledger.record("m", 4, CAUSE_WARMUP, 2.0, now=100.0 + i)
        assert dog.evaluate(now=105.0) == "READY"

    def test_new_watchdog_reads_max_serving_compiles(self):
        container = new_mock_container({"SLO_MAX_SERVING_COMPILES": "7"})
        ledger = CompileLedger()
        dog = new_watchdog(container.config, SLOTracker(), ledger=ledger)
        assert dog.max_serving_compiles == 7
        assert dog.ledger is ledger
        assert dog.statusz()["thresholds"]["max_serving_compiles"] == 7
        # <= 0 disables the check entirely
        container = new_mock_container({"SLO_MAX_SERVING_COMPILES": "0"})
        dog = new_watchdog(container.config, SLOTracker(), ledger=ledger)
        assert dog.max_serving_compiles is None


# -- padding & bucket-fit accounting -----------------------------------------

class TestPaddingAccounting:
    def test_padded_execute_records_ratio_and_bucket_hit(self):
        container = new_mock_container()
        executor = Executor(container.logger, container.metrics)
        fn, params = _simple_model()
        executor.register("m", fn, params, buckets=(4,))
        executor.predict("m", np.ones((3, 2), np.float32))
        # 3 real rows rode a 4-row bucket → 1/4 of device rows were padding
        assert executor.shapes.padding_ratio(60.0) == pytest.approx(0.25)
        assert executor.shapes.distribution("m") == {3: 1}
        assert executor.shapes.bucket_hits("m") == {4: 1}
        assert container.metrics.value("app_tpu_bucket_hits_total",
                                       model="m", bucket="4") == 1.0
        sat = executor.saturation(window_s=60.0)
        assert sat["padding_ratio"] == pytest.approx(0.25)
        assert container.metrics.value(
            "app_tpu_padding_ratio") == pytest.approx(0.25)

    def test_exact_fit_is_zero_padding(self):
        container = new_mock_container()
        executor = Executor(container.logger, container.metrics)
        fn, params = _simple_model()
        executor.register("m", fn, params, buckets=(4,))
        executor.predict("m", np.ones((4, 2), np.float32))
        assert executor.shapes.padding_ratio(60.0) == 0.0

    def test_no_traffic_means_no_ratio(self):
        shapes = ShapeStats()
        assert shapes.padding_ratio(60.0, now=100.0) is None
        snap = shapes.snapshot(now=100.0)
        assert snap["60s"]["padding_ratio"] is None

    def test_effective_mfu_discounts_padded_rows(self):
        container = new_mock_container()
        executor = Executor(container.logger, container.metrics,
                            peak_flops=1e12)
        params = {"w": np.float32(2.0)}

        def fn(params, x):
            return x @ x.T * params["w"]   # enough flops for cost_analysis

        executor.register("m", fn, params, buckets=(4,))
        executor.predict("m", np.ones((2, 8), np.float32))
        sat = executor.saturation(window_s=60.0)
        if sat["flops_per_s"] > 0:   # backend exposes cost_analysis
            # half the rows were padding → effective is half of raw
            assert sat["useful_flops_per_s"] == pytest.approx(
                sat["flops_per_s"] * 0.5)
            assert sat["effective_mfu"] == pytest.approx(sat["mfu"] * 0.5)


# -- step-phase anatomy ------------------------------------------------------

class TestStepPhases:
    def test_phases_metric_and_flight_recorder_timeline(self):
        container = new_mock_container()
        recorder = FlightRecorder()
        executor = Executor(container.logger, container.metrics,
                            recorder=recorder)
        fn, params = _simple_model()
        executor.register("m", fn, params, buckets=(4,))
        executor.predict("m", np.ones((3, 2), np.float32))
        # staged dispatch (the default) splits host_prep into
        # serialize/stage/upload so the host side is attributable per phase
        staged_phases = ("serialize", "stage", "upload", "enqueue",
                         "device_wait")
        for phase in staged_phases:
            assert container.metrics.value(
                "app_tpu_step_phase_seconds",
                phase=phase, model="m") == 1.0, phase
        snap = recorder.snapshot()
        assert snap["total_steps"] == 1
        step = snap["steps"][0]
        assert step["model"] == "m" and step["bucket"] == 4
        assert step["batch"] == 3
        assert step["fill"] == pytest.approx(0.75)
        assert set(step["phases"]) == set(staged_phases)
        assert all(seconds >= 0.0 for seconds in step["phases"].values())
        # EXEC_STAGING=0 keeps the legacy host_prep anatomy
        off_container = new_mock_container()
        off = Executor(off_container.logger, off_container.metrics,
                       staging=False)
        off.register("m", fn, params, buckets=(4,))
        off.predict("m", np.ones((3, 2), np.float32))
        for phase in ("host_prep", "enqueue", "device_wait"):
            assert off_container.metrics.value(
                "app_tpu_step_phase_seconds",
                phase=phase, model="m") == 1.0, phase


# -- batcher flush causes + error outcome ------------------------------------

class TestBatcherObservability:
    def test_flush_causes_full_and_timer(self):
        container = new_mock_container()
        executor = Executor(container.logger, container.metrics)
        fn, params = _simple_model()
        executor.register("m", fn, params, buckets=(1, 2))
        batcher = DynamicBatcher(executor, max_batch=2, max_delay_ms=5.0,
                                 metrics=container.metrics)

        async def scenario():
            # two concurrent submissions hit max_batch → "full" flush
            await asyncio.gather(batcher.predict("m", np.zeros((2,))),
                                 batcher.predict("m", np.ones((2,))))
            # a lone submission can only flush on the timer
            await batcher.predict("m", np.ones((2,)))

        asyncio.run(scenario())
        assert batcher.flush_causes == {"full": 1, "timer": 1}
        metrics = container.metrics
        assert metrics.value("app_tpu_flush_total",
                             cause="full", model="m") == 1.0
        assert metrics.value("app_tpu_flush_total",
                             cause="timer", model="m") == 1.0
        # histogram count: one fill observation per flush
        assert metrics.value("app_tpu_batch_fill", model="m") == 2.0

    def test_failed_batch_records_error_outcome(self):
        container = new_mock_container()
        slo = SLOTracker(container.metrics)

        class _BrokenExecutor:
            def predict(self, name, batch):
                raise RuntimeError("device fell over")

        batcher = DynamicBatcher(_BrokenExecutor(), max_batch=2,
                                 max_delay_ms=1.0, slo=slo,
                                 metrics=container.metrics)

        async def scenario():
            results = await asyncio.gather(
                batcher.predict("m", np.zeros((2,))),
                batcher.predict("m", np.ones((2,))),
                return_exceptions=True)
            assert all(isinstance(r, RuntimeError) for r in results)

        asyncio.run(scenario())
        # every request the failed step carried is classified, none vanish
        assert container.metrics.value("app_tpu_slo_total",
                                       outcome="error") == 2.0
        assert slo.snapshot(now=time.monotonic())["60s"]["outcomes"][
            "error"] == 2.0


# -- suggested ladder (exact DP) ---------------------------------------------

class TestSuggestLadder:
    def test_empty_and_degenerate(self):
        assert suggest_ladder({}) == []
        assert suggest_ladder({0: 5}) == []
        assert suggest_ladder({7: 3}) == [7]

    def test_enough_rungs_means_zero_padding(self):
        assert suggest_ladder({3: 10, 9: 5}, max_rungs=4) == [3, 9]

    def test_rung_budget_forces_merging_toward_heavy_sizes(self):
        # one rung: everything pads to the max observed size
        assert suggest_ladder({2: 100, 8: 1}, max_rungs=1) == [8]
        # two rungs: split where the padding is — the heavy size 2 gets
        # its own rung instead of padding 100 requests up by 6 rows
        assert suggest_ladder({2: 100, 8: 1}, max_rungs=2) == [2, 8]
        # skew decides which sizes share: padding 4→8 once beats
        # padding 2→4 a hundred times
        assert suggest_ladder({2: 100, 4: 1, 8: 1},
                              max_rungs=2) == [2, 8]

    def test_round_to_honors_dp_multiple(self):
        ladder = suggest_ladder({3: 10, 9: 5}, max_rungs=4, round_to=8)
        assert ladder == [8, 16]
        # collapsing rungs after rounding dedups
        assert suggest_ladder({1: 1, 2: 1}, max_rungs=2, round_to=8) == [8]

    def test_optimality_against_brute_force(self):
        import itertools
        observed = {1: 7, 3: 4, 5: 9, 6: 1, 11: 2}
        sizes = sorted(observed)

        def padded_rows(ladder):
            total = 0
            for size, count in observed.items():
                bucket = next(b for b in ladder if b >= size)
                total += count * (bucket - size)
            return total

        for max_rungs in (1, 2, 3):
            best = min(
                padded_rows(sorted(combo))
                for r in range(1, max_rungs + 1)
                for combo in itertools.combinations(sizes, r)
                if max(combo) >= max(sizes))
            got = suggest_ladder(observed, max_rungs=max_rungs)
            assert padded_rows(got) == best, (max_rungs, got)


# -- mesh-rounded ladders × shape accounting ---------------------------------

class TestMeshRoundedBuckets:
    def test_is_warm_and_bucket_hits_agree_with_rounded_ladder(
            self, mock_container):
        """With a dp mesh the ladder the executor *actually* serves is the
        rounded one — warm-ness checks, bucket-hit labels, and the xlaz
        suggested ladder must all speak rounded bucket values, not the
        registered ones."""
        from gofr_tpu.parallel import make_mesh
        mesh = make_mesh({"dp": 8})
        executor = Executor(mock_container.logger, mock_container.metrics,
                            mesh=mesh)
        fn, params = _simple_model()
        executor.register("m", fn, params, buckets=(1, 2, 4, 8, 16, 32))
        assert executor._models["m"].buckets == (8, 16, 32)
        assert not executor.is_warm("m", 3)   # nothing compiled yet
        executor.warmup("m", np.ones((4,), np.float32))
        assert executor.is_warm("m", 3)       # rides the rounded 8-bucket
        assert executor.is_warm("m", 32)
        assert not executor.is_warm("m", 33)  # beyond the ladder

        executor.predict("m", np.ones((3, 4), np.float32))
        assert executor.shapes.bucket_hits("m") == {8: 1}
        assert mock_container.metrics.value(
            "app_tpu_bucket_hits_total", model="m", bucket="8") == 1.0
        assert executor.shapes.padding_ratio(60.0) == pytest.approx(5 / 8)

        fit = executor.xlaz()["models"]["m"]
        assert fit["ladder"] == [8, 16, 32]
        assert fit["observed_batch_sizes"] == {"3": 1}
        # the suggestion honors the same dp multiple the register() did
        assert fit["suggested_ladder"] == [8]


# -- /debug/xlaz endpoint ----------------------------------------------------

def test_debug_xlaz_serves_suggested_ladder_for_skewed_traffic():
    """ISSUE acceptance: traffic heavily skewed to small batches against a
    too-coarse ladder → /debug/xlaz shows the distribution, the padding
    waste, and a suggested ladder with rungs at the observed sizes."""

    async def main():
        app = make_app()
        executor = Executor(app.logger, app.container.metrics)
        fn, params = _simple_model()
        executor.register("m", fn, params, buckets=(16,))
        for _ in range(5):
            executor.predict("m", np.ones((3, 2), np.float32))
        executor.predict("m", np.ones((9, 2), np.float32))
        app.container.tpu = executor
        app.enable_xlaz()
        async with serving(app) as port:
            resp = await asyncio.wait_for(
                http_request(port, "GET", "/debug/xlaz"), 60.0)
            assert resp.status == 200
            data = resp.json()["data"]
            fit = data["models"]["m"]
            assert fit["ladder"] == [16]
            assert fit["buckets_compiled"] == [16]
            assert fit["observed_batch_sizes"] == {"3": 5, "9": 1}
            assert fit["bucket_hits"] == {"16": 6}
            # rungs land exactly on the observed sizes → zero padding
            assert fit["suggested_ladder"] == [3, 9]
            # 24 real rows over 6 sixteen-row executes
            assert data["padding"]["60s"]["padding_ratio"] == pytest.approx(
                1.0 - 24.0 / 96.0)
            compiles = data["compiles"]
            assert compiles["by_cause"] == {"serving": 1}
            assert compiles["recent"][0]["fingerprint"] is not None
    run(main())


def test_statusz_includes_compile_summary():
    async def main():
        app = make_app()
        executor = Executor(app.logger, app.container.metrics)
        fn, params = _simple_model()
        executor.register("m", fn, params, buckets=(2,))
        executor.warmup("m", np.ones((3,), np.float32))
        app.container.tpu = executor
        app.enable_statusz()
        async with serving(app) as port:
            resp = await asyncio.wait_for(
                http_request(port, "GET", "/debug/statusz"), 60.0)
            data = resp.json()["data"]
            assert data["compiles"]["by_cause"] == {"warmup": 1}
            assert data["compiles"]["recent"][0]["bucket"] == 2
    run(main())


# -- generation engine prompt-bucket fit -------------------------------------

def test_engine_xlaz_reports_prompt_bucket_fit():
    import jax

    from gofr_tpu.models import llama
    from gofr_tpu.tpu.generate import GenerationEngine

    container = new_mock_container()
    cfg = llama.config("tiny")
    params = llama.init(cfg, jax.random.PRNGKey(0))
    engine = GenerationEngine(cfg, params, max_slots=2, max_len=64,
                              prompt_buckets=(8, 16),
                              logger=container.logger,
                              metrics=container.metrics)
    engine._validate([1, 2, 3], 4)
    engine._validate([1, 2, 3], 4)
    engine._validate(list(range(12)), 4)
    fit = engine.xlaz()["models"]["prompt"]
    assert fit["ladder"] == [8, 16]
    assert fit["observed_batch_sizes"] == {"3": 2, "12": 1}
    assert fit["bucket_hits"] == {"8": 2, "16": 1}
    assert fit["suggested_ladder"] == [3, 12]
    assert container.metrics.value("app_tpu_bucket_hits_total",
                                   model="prompt", bucket="8") == 2.0


# -- docs-drift lint ---------------------------------------------------------

def test_lint_metrics_fails_when_catalog_drops_a_metric(tmp_path):
    """The drift gate's negative test: remove one documented metric from a
    copy of the catalog and the lint must fail naming it."""
    import pathlib
    catalog = pathlib.Path("docs/quick-start/observability.md").read_text()
    assert "app_tpu_compile_total" in catalog
    stripped = tmp_path / "observability.md"
    stripped.write_text(catalog.replace("app_tpu_compile_total", ""))
    result = subprocess.run(
        [sys.executable, "scripts/lint_metrics.py",
         "--docs", str(stripped)],
        capture_output=True, text=True, timeout=120)
    assert result.returncode == 1
    assert "app_tpu_compile_total" in result.stderr
    assert "missing from the metrics catalog" in result.stderr


def test_lint_metrics_passes_against_real_catalog():
    result = subprocess.run(
        [sys.executable, "scripts/lint_metrics.py"],
        capture_output=True, text=True, timeout=120)
    assert result.returncode == 0, result.stderr
