"""Inference flight recorder (ISSUE 1): one trace_id connects the HTTP
request span, the engine's ``queue.wait``/``prefill``/``decode`` child
spans, and the batched engine-step spans via span links; ``/debug/statusz``
serves the live timeline and ``/metrics`` carries trace_id exemplars."""

import asyncio
import json

import jax
import pytest

from gofr_tpu.app import App
from gofr_tpu.container import new_mock_container
from gofr_tpu.models import llama
from gofr_tpu.tpu import FlightRecorder, RequestRecord
from gofr_tpu.tpu.generate import GenerationEngine
from gofr_tpu.trace import ListExporter, Tracer
from tests.util import http_request, run, serving


@pytest.fixture(scope="module")
def setup():
    cfg = llama.config("tiny")
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _traced_app(config=None):
    """make_app() variant whose tracer exports into an in-memory list (the
    middleware captures the tracer at App construction, so it must be
    swapped before App() runs)."""
    container = new_mock_container(config)
    exporter = ListExporter()
    container.tracer = Tracer(exporter=exporter)
    app = App(config=container.config, container=container)
    app.http_port = 0
    app.metrics_port = 0
    return app, exporter


def _wire_engine(app, cfg, params):
    engine = GenerationEngine(cfg, params, max_slots=2, max_len=64,
                              prompt_buckets=(8,),
                              logger=app.logger,
                              metrics=app.container.metrics,
                              tracer=app.container.tracer)
    app.container.tpu = engine
    app.enable_statusz()

    async def generate(ctx):
        await engine.start()
        data = ctx.bind()
        out = await engine.generate(
            data["prompt"], max_new_tokens=int(data.get("max_new_tokens", 4)))
        return {"tokens": out}

    app.post("/generate", generate)
    return engine


async def _post_generate(port, prompt, max_new_tokens=4):
    return await asyncio.wait_for(http_request(
        port, "POST", "/generate",
        body=json.dumps({"prompt": prompt,
                         "max_new_tokens": max_new_tokens}).encode(),
        headers={"Content-Type": "application/json"}), 120.0)


def test_one_trace_id_connects_http_to_engine_step(setup):
    """The acceptance path: request → queue.wait/prefill/decode children
    sharing the HTTP trace, and root engine-step spans whose links carry
    the request's span id (many requests : one batched step)."""
    cfg, params = setup

    async def main():
        app, exporter = _traced_app()
        engine = _wire_engine(app, cfg, params)
        async with serving(app) as port:
            resp = await _post_generate(port, [1, 2, 3], max_new_tokens=4)
            assert resp.status == 201
            assert len(resp.json()["data"]["tokens"]) == 4
            trace_id = resp.headers["x-trace-id"]
            await engine.stop()
        # app.stop() → container.close() → tracer.shutdown() drained the
        # export queue, so every finished span is in the exporter now
        return exporter, trace_id

    exporter, trace_id = run(main())

    http_spans = [s for s in exporter.find("POST /generate")
                  if s.trace_id == trace_id]
    assert len(http_spans) == 1
    req_span = http_spans[0]

    for name in ("queue.wait", "prefill", "decode"):
        children = [s for s in exporter.find(name)
                    if s.trace_id == trace_id]
        assert children, f"no {name} span in the request's trace"
        assert children[0].parent_id == req_span.span_id

    steps = exporter.find("tpu.engine.prefill") + exporter.find("tpu.engine.step")
    assert steps, "engine emitted no step spans"
    want = {"trace_id": trace_id, "span_id": req_span.span_id}
    linked = [s for s in steps if want in s.links]
    assert linked, "no engine step span links back to the request span"
    # step spans are engine-internal roots, not children of any request
    assert all(s.parent_id is None for s in steps)


def test_statusz_and_metrics_exemplars(setup):
    cfg, params = setup

    async def main():
        app, _ = _traced_app()
        engine = _wire_engine(app, cfg, params)
        async with serving(app) as port:
            resp = await _post_generate(port, [5, 6, 7], max_new_tokens=4)
            assert resp.status == 201
            trace_id = resp.headers["x-trace-id"]

            statusz = await http_request(port, "GET",
                                         "/debug/statusz?recent=8")
            snap = statusz.json()["data"]
            assert snap["app"]["name"]
            engine_snap = snap["engine"]
            assert engine_snap["queue_depth"] == 0
            assert len(engine_snap["slots"]) == 2
            for slot in engine_snap["slots"]:
                assert slot["state"] in ("active", "free")
            kv = engine_snap["kv_cache"]
            assert kv["max_slots"] == 2 and kv["max_len"] == 64
            assert 0.0 <= kv["occupancy"] <= 1.0
            assert snap["devices"]["status"] == "UP"

            timelines = engine_snap["requests"]
            assert timelines["total_requests"] >= 1
            newest = timelines["recent"][0]
            assert newest["trace_id"] == trace_id
            assert newest["status"] == "done"
            assert newest["tokens"] == 4
            assert newest["queue_wait_s"] is not None
            assert newest["ttft_s"] is not None
            assert newest["tokens_per_s"] > 0
            assert newest["batch_sizes"]["ticks"] >= 1
            assert newest["batch_sizes"]["max"] >= 1

            mport = app._metrics_server.bound_port
            text = (await http_request(mport, "GET", "/metrics")
                    ).body.decode()
            ttft_exemplars = [
                line for line in text.splitlines()
                if line.startswith("app_tpu_ttft_bucket") and " # {" in line]
            assert ttft_exemplars, "no exemplar on the TTFT histogram"
            assert any(f'trace_id="{trace_id}"' in line
                       for line in ttft_exemplars)
            await engine.stop()
    run(main())


def test_flight_recorder_ring_and_lifecycle():
    recorder = FlightRecorder(capacity=2)
    for i in range(3):
        record = RequestRecord(model="generate", prompt_len=3, budget=4,
                               trace_id=f"trace-{i}", span_id=f"span-{i}")
        recorder.start(record)
        record.admitted()
        record.rode_batch(2)
        record.rode_batch(1)
        record.first_token()
        record.tokens = 4
        recorder.finish(record, "done")
    snap = recorder.snapshot()
    assert snap["total_requests"] == 3
    assert snap["in_flight"] == []
    assert len(snap["recent"]) == 2          # ring stays bounded
    assert snap["recent"][0]["trace_id"] == "trace-2"   # newest first
    newest = snap["recent"][0]
    assert newest["status"] == "done"
    assert newest["queue_wait_s"] >= 0.0
    assert newest["ttft_s"] >= newest["queue_wait_s"]
    assert newest["batch_sizes"] == {"ticks": 2, "min": 1, "max": 2,
                                     "mean": 1.5}


@pytest.mark.parametrize("stalls", [(), (0.25,), (0.25, 0.5, 0.06)])
def test_request_record_books_the_prefills_it_met(stalls):
    """Other requests' prefill groups that ran while this one decoded are
    two bounded aggregates, shown beside the decode phase they are part
    of; a request that has no first token has no decode phase."""
    record = RequestRecord(prompt_len=3, budget=8)
    assert record.decode_s is None and record.to_dict()["decode_s"] is None
    record.admitted()
    record.first_token()
    for seconds in stalls:
        record.stalled(seconds)
    record.finish("done")
    shown = record.to_dict()
    assert shown["prefills_met"] == len(stalls)
    assert shown["stall_s"] == pytest.approx(sum(stalls))
    assert shown["decode_s"] == pytest.approx(
        record.finished_at - record.first_token_at, abs=1e-6)
    assert not hasattr(record, "__dict__")       # still a slotted record


def test_flight_recorder_tracks_in_flight():
    recorder = FlightRecorder(capacity=4)
    record = recorder.start(RequestRecord(prompt_len=1, budget=2))
    snap = recorder.snapshot()
    assert len(snap["in_flight"]) == 1
    assert snap["in_flight"][0]["status"] == "queued"
    recorder.finish(record, "cancelled")
    snap = recorder.snapshot()
    assert snap["in_flight"] == []
    assert snap["recent"][0]["status"] == "cancelled"
    # double-finish is a no-op, not a duplicate ring entry
    recorder.finish(record, "done")
    assert len(recorder.snapshot()["recent"]) == 1


def test_batcher_step_span_links_requests():
    """ctx.predict path: the batcher opens a queue.wait child per request
    and one root tpu.batch step span linked to every coalesced request;
    the executor stamps the step's trace onto app_tpu_execute."""
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.metrics import render_prometheus

    async def main():
        app, exporter = _traced_app({"TPU_ENABLED": "true"})
        app.add_model("clf", lambda p, x: x @ p["w"],
                      params={"w": jnp.eye(3)}, buckets=(1, 2, 4))

        async def classify(ctx):
            out = await ctx.predict(
                "clf", np.asarray(ctx.bind()["x"], np.float32))
            return {"y": [float(v) for v in out]}

        app.post("/classify", classify)
        async with serving(app) as port:
            resp = await http_request(
                port, "POST", "/classify",
                body=json.dumps({"x": [1.0, 0.0, 0.0]}).encode(),
                headers={"Content-Type": "application/json"})
            assert resp.status == 201
            trace_id = resp.headers["x-trace-id"]
            text = render_prometheus(app.container.metrics)
        return exporter, trace_id, text

    exporter, trace_id, text = run(main())

    qwaits = [s for s in exporter.find("queue.wait")
              if s.trace_id == trace_id]
    assert qwaits and qwaits[0].attributes["model"] == "clf"
    batches = exporter.find("tpu.batch")
    assert batches, "batcher emitted no step span"
    assert any(any(link["trace_id"] == trace_id for link in s.links)
               for s in batches)
    assert any(line.startswith("app_tpu_execute_bucket") and " # {" in line
               for line in text.splitlines()), \
        "no exemplar on app_tpu_execute"
