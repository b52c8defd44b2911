"""One clock for the engine's event loop (ISSUE 25): ``stats()["loop"]``
(who holds the serving thread, by phase, wall and CPU), the request's two
phases beside ``app_tpu_ttft``, the HTTP server's self time in a stream,
and the same phases as host annotations in a profiler capture."""

import asyncio
import glob
import json
import os
import re

import pytest

from gofr_tpu.http.response import Stream
from tests.util import http_request, make_app, run, serving

HOLDING = ("admit", "dispatch", "publish", "enqueue", "upload")
PHASES = HOLDING + ("wait", "park")
LOOP_KEYS = (("passes", "wall_s", "held_s", "held_cpu_s", "yield_cpu_s")
             + tuple(p + "_s" for p in PHASES)
             + tuple(p + "_cpu_s" for p in HOLDING))


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
    kwargs.setdefault("max_slots", 2)
    kwargs.setdefault("max_len", 32)
    kwargs.setdefault("prompt_buckets", (8,))
    kwargs.setdefault("paged_kv", True)
    kwargs.setdefault("kv_page", 4)
    engine = GenerationEngine(cfg, params, logger=container.logger,
                              metrics=container.metrics, **kwargs)
    return engine, container


async def _drain(engine, prompt, budget):
    stream = await engine.generate_stream(prompt, max_new_tokens=budget)
    return [token async for token in stream]


def _histogram(container, name, **labels):
    """(sum, count) over the series of a histogram that carry ``labels``."""
    want = set(labels.items())
    states = [state for key, state
              in container.metrics.snapshot()[name].series.items()
              if want <= set(key)]
    return (sum(s["sum"] for s in states), sum(s["count"] for s in states))


def test_loop_stats_cover_the_wall_by_phase(setup):
    cfg, params = setup
    engine, _ = _make_engine(cfg, params)
    assert engine.stats()["loop"]["passes"] == 0      # not started: zeros

    async def main():
        await engine.start()
        try:
            await asyncio.gather(*[_drain(engine, [1, 2, 3 + i], 6)
                                   for i in range(4)])
            first = engine.stats()["loop"]
            await asyncio.gather(*[_drain(engine, [4, 5, 6 + i], 5)
                                   for i in range(3)])
            await asyncio.sleep(0.02)                 # the engine parks
            return first, engine.stats()["loop"]
        finally:
            await engine.stop()

    first, second = run(main())
    for loop_stats in (first, second):
        assert set(loop_stats) == set(LOOP_KEYS) | {"longest"}
        assert all(isinstance(loop_stats[k], (int, float))
                   for k in LOOP_KEYS)
        phases = sum(loop_stats[p + "_s"] for p in PHASES)
        assert phases == pytest.approx(loop_stats["wall_s"], rel=1e-6)
        assert loop_stats["held_s"] == pytest.approx(
            sum(loop_stats[p + "_s"] for p in HOLDING))
        # the warm calls into the runtime and the uploads are laps of
        # their own, and a phase's longest lap is one of its laps
        assert loop_stats["enqueue_s"] > 0.0 and loop_stats["upload_s"] > 0.0
        for phase, lap in loop_stats["longest"].items():
            assert 0.0 <= lap["wall_s"] <= loop_stats[phase + "_s"] + 1e-9
            assert lap["at"] > 1e9                    # time.time()
        assert loop_stats["held_cpu_s"] <= loop_stats["held_s"] + 0.05
        assert loop_stats["passes"] > 0
    for key in LOOP_KEYS:                              # all monotone
        assert second[key] >= first[key], key
    assert second["passes"] > first["passes"]
    assert second["park_s"] > first["park_s"]
    assert second["held_s"] > first["held_s"] > 0.0
    stopped = engine.stats()["loop"]["wall_s"]    # no loop, no open phase
    assert engine.stats()["loop"]["wall_s"] == stopped


def test_request_phases_add_up_to_ttft(setup):
    """queue + first_token = ttft per request; a request cancelled before
    it got a slot has neither phase (and no ttft)."""
    cfg, params = setup
    engine, container = _make_engine(cfg, params)
    finished = 5

    async def main():
        await engine.start()
        try:
            # 5 requests on 2 slots: three of them queue for a slot
            work = [asyncio.ensure_future(_drain(engine, [1, 2, 3 + i], 5))
                    for i in range(finished)]
            doomed = await engine.generate_stream([7, 8, 9],
                                                  max_new_tokens=4)
            doomed.cancel()               # before the loop ever admits it
            await asyncio.gather(*work)
        finally:
            await engine.stop()

    run(main())
    name = "app_tpu_request_phase_seconds"
    queue = _histogram(container, name, model="generate", phase="queue")
    first = _histogram(container, name, model="generate",
                       phase="first_token")
    ttft = _histogram(container, "app_tpu_ttft", model="generate")
    assert queue[1] == first[1] == ttft[1] == finished
    assert queue[0] + first[0] == pytest.approx(ttft[0],
                                                abs=1e-3 * finished)
    assert queue[0] > 0.0 and first[0] > 0.0
    # stall: once a request that had a first token; the doomed one none
    stall = _histogram(container, name, model="generate", phase="stall")
    assert stall[1] == finished
    assert stall[0] == pytest.approx(
        engine.stats()["timeline"]["stalled_slot_s"], abs=1e-9)


def test_stream_self_time_excludes_the_producer():
    """Five items 50 ms apart: the response takes over 250 ms, of which
    the server's own framing and writes are a few hundred microseconds."""
    app = make_app()

    async def slow(ctx):
        async def gen():
            for i in range(5):
                await asyncio.sleep(0.05)
                yield f"n{i}"
        return Stream(gen(), sse=True)

    app.get("/slow", slow)

    async def main():
        async with serving(app) as port:
            result = await http_request(port, "GET", "/slow")
            assert result.status == 200
            assert result.body.count(b"data: n") == 5

    run(main())
    self_time = _histogram(app.container, "app_http_stream_self_seconds",
                           path="/slow")
    response = _histogram(app.container, "app_http_response", path="/slow")
    assert self_time[1] == 1 and response[1] == 1
    assert 0.0 < self_time[0] < 0.020
    assert response[0] > 0.250


def test_profiler_capture_holds_the_phase_annotations(setup, tmp_path):
    """The same phases on the trace's clock: a jax.profiler capture round
    a short run holds host events named tpu.engine.<phase>."""
    import jax
    from jax.profiler import ProfileData

    cfg, params = setup
    engine, _ = _make_engine(cfg, params)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0          # annotations, not frames

    async def main():
        await engine.start()
        try:
            await _drain(engine, [1, 2, 3], 4)        # warm: compile first
            jax.profiler.start_trace(str(tmp_path), profiler_options=options)
            try:
                await asyncio.gather(*[_drain(engine, [1, 2, 3 + i], 8)
                                       for i in range(3)])
            finally:
                jax.profiler.stop_trace()
        finally:
            await engine.stop()

    run(asyncio.wait_for(main(), 120.0))     # the test's own time limit
    paths = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert paths
    names = {event.name
             for plane in ProfileData.from_file(paths[-1]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for event in line.events
             if event.name.startswith("tpu.")}
    for phase in ("admit", "dispatch", "publish", "wait", "enqueue",
                  "upload"):
        assert "tpu.engine." + phase in names, (phase, sorted(names))
    assert "tpu.engine.step" in names        # XProf keeps its step numbers
    # the landings lie on the same clock, under names the benchmark's
    # host patterns do not take: a fetch spans the device's work from
    # another thread and would take the idle gaps from the loop's phases
    assert {"tpu.fetch.tick", "tpu.fetch.prefill"} <= names
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark",
            "trace_names.json")) as handle:
        patterns = json.load(handle)["host_annotations"]
    assert not any(re.search(p, name) for p in patterns
                   for name in names if name.startswith("tpu.fetch."))


class _FakeTime:
    """``time`` as ``generate`` sees it, moved by hand: wall and thread
    CPU are separate counters."""

    def __init__(self):
        self.now = 100.0
        self.cpu = 5.0

    def monotonic(self):
        return self.now

    def thread_time(self):
        return self.cpu

    def time(self):
        return 1.7e9 + self.now

    def tick(self, wall, cpu=0.0):
        self.now += wall
        self.cpu += cpu


class _NoSpan:
    def __init__(self, name):
        self.name = name

    def __exit__(self, *exc):
        return False


def test_seven_phases_sum_as_five_and_longest_names_a_planted_lap(
        monkeypatch):
    """With the clock in hand: the laps cut out of ``admit`` and
    ``dispatch`` are holding phases of their own, so ``held_s`` and
    ``wall_s`` are what they were without them; ``longest`` names the
    phase of a 4 s lap planted among short ones; a lap asked for under a
    yielding phase (another thread's) or a stopped clock is not
    stamped."""
    from gofr_tpu.tpu import generate

    fake = _FakeTime()
    monkeypatch.setattr(generate, "time", fake)
    clock = generate._LoopClock(_NoSpan)
    assert clock.PHASES == PHASES and clock.HOLDING == HOLDING
    with clock.lap("enqueue"):            # stopped: nothing to stamp
        fake.tick(9.0)
    assert clock.stats()["wall_s"] == 0.0
    clock.enter("admit")
    fake.tick(0.010, 0.004)
    with clock.lap("enqueue"):
        fake.tick(0.030, 0.001)
        with clock.lap("upload"):
            fake.tick(0.002, 0.002)
        assert clock.phase == "enqueue"
        fake.tick(0.008)
    assert clock.phase == "admit"
    fake.tick(0.005, 0.001)
    clock.enter("dispatch")
    for wall in (0.020, 4.0, 0.025):      # the stall sits in an enqueue
        fake.tick(0.001, 0.001)
        with clock.lap("enqueue"):
            fake.tick(wall, 0.0005)
    clock.enter("wait")
    with clock.lap("upload"):             # a worker thread's: unclocked
        fake.tick(0.5)
    assert clock.phase == "wait"
    clock.enter("publish")
    fake.tick(0.003, 0.003)
    clock.enter("park")
    fake.tick(1.0)
    stats = clock.stats()
    by_phase = {"admit": 0.015, "enqueue": 0.038 + 4.045, "upload": 0.002,
                "dispatch": 0.003, "publish": 0.003, "wait": 0.5,
                "park": 1.0}
    for phase, seconds in by_phase.items():
        assert stats[phase + "_s"] == pytest.approx(seconds), phase
    five = sum(by_phase[p] for p in HOLDING)
    assert stats["held_s"] == pytest.approx(five)
    assert stats["wall_s"] == pytest.approx(five + 1.5)
    assert stats["held_cpu_s"] == pytest.approx(
        0.005 + 0.001 + 0.002 + 0.003 + 0.0015 + 0.003)
    assert stats["yield_cpu_s"] == 0.0
    longest = stats["longest"]
    assert max(longest, key=lambda p: longest[p]["wall_s"]
               if p in HOLDING else 0.0) == "enqueue"
    assert longest["enqueue"]["wall_s"] == pytest.approx(4.0)
    assert longest["enqueue"]["cpu_s"] == pytest.approx(0.0005)
    # when the planted lap ended: 0.060 + 0.021 + 4.001 s in
    assert longest["enqueue"]["at"] == pytest.approx(
        1.7e9 + 100.0 + 0.060 + 0.021 + 4.001)
    assert longest["wait"]["wall_s"] == pytest.approx(0.5)
