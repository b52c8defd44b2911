"""One clock for the engine's event loop (ISSUE 25): ``stats()["loop"]``
(who holds the serving thread, by phase, wall and CPU), the request's two
phases beside ``app_tpu_ttft``, the HTTP server's self time in a stream,
and the same phases as host annotations in a profiler capture."""

import asyncio
import glob
import os

import pytest

from gofr_tpu.http.response import Stream
from tests.util import http_request, make_app, run, serving

LOOP_KEYS = ("passes", "wall_s", "admit_s", "dispatch_s", "publish_s",
             "wait_s", "park_s", "admit_cpu_s", "dispatch_cpu_s",
             "publish_cpu_s", "held_s", "held_cpu_s", "yield_cpu_s")
PHASES = ("admit", "dispatch", "publish", "wait", "park")


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
        assert set(loop_stats) == set(LOOP_KEYS)
        assert all(isinstance(v, (int, float)) for v in loop_stats.values())
        phases = sum(loop_stats[p + "_s"] for p in PHASES)
        assert phases == pytest.approx(loop_stats["wall_s"], rel=1e-6)
        assert loop_stats["held_s"] == pytest.approx(
            loop_stats["admit_s"] + loop_stats["dispatch_s"]
            + loop_stats["publish_s"])
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
             if event.name.startswith("tpu.engine.")}
    for phase in ("admit", "dispatch", "publish", "wait"):
        assert "tpu.engine." + phase in names, (phase, sorted(names))
    assert "tpu.engine.step" in names        # XProf keeps its step numbers
