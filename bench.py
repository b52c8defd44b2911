"""Headline bench: ResNet-50 classify + Llama decode on one TPU chip.

North-star target (BASELINE.md config 2): ≥1000 req/s/chip AND p99 < 10 ms
on the classify path. This bench measures all of it honestly:

1. **Device-resident steady state** — the compiled classify step at the
   serving batch (MXU utilisation ceiling), with MFU computed from XLA's
   own cost analysis against the chip's bf16 peak.
2. **Operating point** — a device-attributable sweep over the bucket
   ladder (8..256, each bucket timed by iterating the step inside ONE
   executable so the dispatch round trip cancels exactly); the operating
   point is the largest bucket fitting the p99 < 10 ms budget at
   ≥1000 req/s, the full sweep is reported so the knee is visible.
3. **Closed-loop HTTP** — real requests through router → middleware →
   handler → dynamic batcher → executor (the path BASELINE.md names),
   reporting measured p50/p99 for /hello (framework overhead, config 1)
   and /classify.
4. **Host link** — the dispatch round trip and H2D/D2H bandwidth of this
   host's link to the device on their own (``host_link``), so full-path
   numbers can be read against their floor.
5. **BERT gRPC embeddings** (BASELINE config 3) — device-side batching
   gain curve + closed-loop gRPC unary at concurrency 1 vs 32 (the
   dynamic batcher's coalescing gain) + server-streaming TTFB.
6. **Llama continuous-batching decode** — aggregate tok/s through the
   generation engine, post-warmup (the executable ladder is precompiled;
   round 2 accidentally timed four TPU compiles).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
from typing import Optional

import numpy as np

TARGET_REQ_S = 1000.0   # BASELINE.md config 2
TARGET_P99_MS = 10.0

# Published peaks of one chip by PJRT device_kind (Google Cloud TPU
# documentation: bf16 FLOP/s, HBM bytes/s). A device missing from the
# table is an error, never a default: a roofline share against a guessed
# peak is not a measurement.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},   # v5e
    "TPU v5": {"bf16_flops": 459e12, "hbm_bytes_s": 2765e9},       # v5p
    "TPU v4": {"bf16_flops": 275e12, "hbm_bytes_s": 1228e9},
    "TPU v6 lite": {"bf16_flops": 918e12, "hbm_bytes_s": 1640e9},  # v6e
}


def _device_peak(name: str) -> float:
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {kind!r}; add it to "
            f"DEVICE_PEAKS with its source before benchmarking on it")
    return DEVICE_PEAKS[kind][name]


def main() -> None:
    import jax

    from gofr_tpu.tpu.compile_cache import configure_compile_cache
    configure_compile_cache()
    platform = jax.devices()[0].platform
    on_tpu = platform != "cpu"

    host_link = _host_link_bench()
    h2d = _h2d_roundtrip_bench()
    resnet_stats = _resnet_bench(on_tpu)
    http_stats = _http_bench(on_tpu)
    bert_stats = _bert_grpc_bench(on_tpu)
    llama_small = _llama_decode_bench(on_tpu)
    llama_prefix = _llama_prefix_reuse_bench(on_tpu)
    llama_paged = _llama_paged_kv_bench(on_tpu)
    llama_ragged = _llama_ragged_attn_bench(on_tpu)
    llama_spec = _llama_speculative_bench(on_tpu)
    llama_disagg = _llama_disagg_bench(on_tpu)
    llama_fleet = _llama_fleet_bench(on_tpu)
    llama_chaos = _llama_chaos_bench(on_tpu)
    llama_replay = _llama_replay_bench(on_tpu)
    llama_sloz = _llama_sloz_bench(on_tpu)
    llama_autotune = _llama_autotune_bench(on_tpu)
    multi_model = _multi_model_bench(on_tpu)
    llama_batch_lane = _llama_batch_lane_bench(on_tpu)
    llama7b = _llama7b_int8_bench(on_tpu)

    req_per_s = resnet_stats.pop("req_per_s")
    out = {
        "metric": "resnet50_classify_throughput_per_chip",
        "value": round(req_per_s, 1),
        "unit": "req/s",
        "vs_baseline": round(req_per_s / TARGET_REQ_S, 3),
        "platform": platform,
        "host_link": host_link,
        "h2d_roundtrip": h2d,
        **resnet_stats,
        **http_stats,
        "bert": bert_stats,
        "llama_small_decode_tok_s": llama_small.pop("tok_s_best"),
        "llama_small_decode": llama_small,
        "llama_prefix_reuse": llama_prefix,
        "llama_paged_kv": llama_paged,
        "llama_ragged_attn": llama_ragged,
        "llama_speculative": llama_spec,
        "llama_disagg": llama_disagg,
        "llama_fleet": llama_fleet,
        "llama_chaos": llama_chaos,
        "llama_replay": llama_replay,
        "llama_sloz": llama_sloz,
        "llama_autotune": llama_autotune,
        "multi_model": multi_model,
        "llama_batch_lane": llama_batch_lane,
        "llama7b_int8": llama7b,
    }
    # how much of the hardware the full served path delivers — THE ratio
    # the zero-copy data plane exists to move (ISSUE 9 acceptance)
    ratios = {}
    if resnet_stats.get("device_only_req_per_s"):
        ratios["resnet50"] = round(
            req_per_s / resnet_stats["device_only_req_per_s"], 3)
    if isinstance(llama7b, dict) and llama7b.get("decode_tok_s") \
            and llama7b.get("device_only_tok_s"):
        ratios["llama7b"] = round(
            llama7b["decode_tok_s"] / llama7b["device_only_tok_s"], 3)
    out["full_path_vs_device_only"] = ratios
    print(json.dumps(out))


def _host_link_bench() -> dict:
    """What this host's link to the device costs on its own: the per-call
    dispatch + D2H round trip of a trivial executable, and H2D/D2H
    bandwidth on an 8 MB blob. Every full-path figure below includes at
    least one such round trip and moves its inputs over this link, so
    the floor is reported beside them instead of being read as framework
    overhead."""
    import jax
    import jax.numpy as jnp

    tiny = jax.jit(lambda x: x + 1)
    dev = jax.device_put(jnp.zeros((8,), jnp.float32))
    jax.block_until_ready(tiny(dev))
    dispatch = []
    for _ in range(10):
        t0 = time.perf_counter()
        np.asarray(tiny(dev))        # dispatch + D2H sync round trip
        dispatch.append(time.perf_counter() - t0)

    blob = np.ones((8 * 2**20,), np.uint8)          # 8 MB
    h2d = []
    for _ in range(3):
        t0 = time.perf_counter()
        dev_blob = jax.device_put(blob)
        jax.block_until_ready(dev_blob)
        h2d.append(time.perf_counter() - t0)
    bump = jax.jit(lambda x: x + 1)
    d2h = []
    for _ in range(3):
        fresh = jax.block_until_ready(bump(dev_blob))  # no cached host copy
        t0 = time.perf_counter()
        np.asarray(fresh)
        d2h.append(time.perf_counter() - t0)

    return {
        "dispatch_roundtrip_ms_p50": round(
            float(np.percentile(dispatch, 50)) * 1e3, 2),
        "h2d_mb_s": round(len(blob) / 2**20 / min(h2d), 1),
        "d2h_mb_s": round(len(blob) / 2**20 / min(d2h), 1),
    }


def _h2d_roundtrip_bench() -> dict:
    """Zero-copy data-plane micro-scenario (ISSUE 9): the same
    dispatch→fetch round trip through the executor with the staging-slab
    pool on vs off, plus one decode tick's control-array upload cost
    coalesced (one packed transfer) vs per-array. When the
    full_path_vs_device_only ratio moves, this block pins whether the
    host-copy side (staging) or the transfer count (coalescing) moved
    it. Absolute numbers include the host link — compare within the
    run."""
    import jax
    import jax.numpy as jnp

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.tpu.executor import Executor
    from gofr_tpu.tpu.staging import TransferCoalescer

    container = new_mock_container()

    def fn(params, x):
        return x * params["scale"]

    params = {"scale": jnp.float32(2.0)}
    batch = 16
    x = np.ones((batch, 64, 64, 3), np.float32)   # ~3 MB per dispatch

    def roundtrip_ms(**kwargs):
        ex = Executor(container.logger, container.metrics, **kwargs)
        ex.register("stage_probe", fn, params, buckets=(batch,))
        ex.predict("stage_probe", x)              # warm the bucket
        lat = []
        for _ in range(7):
            t0 = time.perf_counter()
            ex.fetch(ex.dispatch("stage_probe", x))
            lat.append(time.perf_counter() - t0)
        return float(np.percentile(lat, 50)) * 1e3

    staged_ms = roundtrip_ms()
    unstaged_ms = roundtrip_ms(staging=False)

    # one decode tick's admission/control group (the engine ships these
    # every tick): 7 small 4-byte arrays, ~1 KB total
    group = {
        "padded": np.zeros((8, 16), np.int32),
        "lengths": np.full((8,), 16, np.int32),
        "slots": np.arange(8, dtype=np.int32),
        "temps": np.zeros((8,), np.float32),
        "top_ks": np.zeros((8,), np.int32),
        "top_ps": np.ones((8,), np.float32),
        "seeds": np.zeros((8,), np.uint32),
    }
    coalescer = TransferCoalescer()

    def upload_ms(f):
        jax.block_until_ready(list(f().values()))  # warm (jit the split)
        lat = []
        for _ in range(7):
            t0 = time.perf_counter()
            jax.block_until_ready(list(f().values()))
            lat.append(time.perf_counter() - t0)
        return float(np.percentile(lat, 50)) * 1e3

    coalesced_ms = upload_ms(lambda: coalescer.upload(group))
    per_array_ms = upload_ms(
        lambda: {k: jnp.asarray(v) for k, v in group.items()})

    return {
        "dispatch_roundtrip_ms_staged": round(staged_ms, 2),
        "dispatch_roundtrip_ms_unstaged": round(unstaged_ms, 2),
        "staged_vs_unstaged": (round(staged_ms / unstaged_ms, 2)
                               if unstaged_ms else None),
        "bytes_per_dispatch": x.nbytes,
        "tick_upload_ms_coalesced": round(coalesced_ms, 3),
        "tick_upload_ms_per_array": round(per_array_ms, 3),
        "arrays_per_tick": len(group),
        "data_plane": {"ingest": "in-proc ndarray",
                       "staging": "slab-vs-off A/B"},
    }


def _chained_device_latency(make_step, params, x, batch: int,
                            reps: int = 5, n: Optional[int] = None):
    """Device-attributable latency of one model step, measured by
    iterating the step N times INSIDE one executable (``lax.fori_loop``
    with an unfoldable inter-iteration dependency) and fetching a scalar.

    Why not a chain of separate dispatches: every dispatch carries host
    cost (argument handling, launch, the final fetch) that varies with
    what else the host is doing, and a slope over separate dispatches
    inherits that noise. Fusing the chain into a single program makes
    the subtraction (t_N - t_2)/(N - 2) remove the dispatch + fetch
    round trip exactly.

    ``make_step(params, x, eps)`` must run one model step whose input
    depends on the scalar ``eps`` (derived from the previous iteration's
    output, zero at runtime but unprovable by XLA, so the loop cannot be
    hoisted). Returns (latency_seconds | None, spread | None)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def chained(n):
        def fn(p, xin):
            def body(i, acc):
                eps = jnp.max(jnp.abs(acc.astype(jnp.float32))) * 1e-30
                return make_step(p, xin, eps)
            acc = make_step(p, xin, jnp.float32(0.0))
            acc = lax.fori_loop(0, n - 1, body, acc)
            return jnp.sum(acc.astype(jnp.float32))   # 4-byte fetch
        return jax.jit(fn).lower(params, x).compile()

    # iterate enough that the signal dwarfs round-trip jitter (a floor of
    # 8 let a lucky rep read batch-256 ResNet at 11 ms vs its true ~20 —
    # spread 1.0 flagged it), bounded so big batches stay ~1 s per rep.
    # Callers timing steps that already run 100s of ms pass ``n`` low.
    if n is None:
        n = max(24, min(128, 2048 // max(1, batch)))
    big = chained(n)
    small = chained(2)
    np.asarray(big(params, x))      # warm both executables
    np.asarray(small(params, x))

    def once(compiled):
        t0 = time.perf_counter()
        np.asarray(compiled(params, x))
        return time.perf_counter() - t0

    diffs = []
    for _ in range(reps):
        t_small = once(small)
        t_big = once(big)
        diffs.append((t_big - t_small) / (n - 2))
    lat = float(np.median(diffs))
    if lat <= 0:
        return None, None
    return lat, (max(diffs) - min(diffs)) / lat


def _percentiles(latencies):
    arr = np.asarray(sorted(latencies))
    return (round(float(np.percentile(arr, 50)) * 1e3, 2),
            round(float(np.percentile(arr, 99)) * 1e3, 2))


def _resnet_bench(on_tpu: bool) -> dict:
    """Device-resident steady state + MFU + operating point + pipelined
    host-input (H2D-overlapped) throughput."""
    import jax
    import jax.numpy as jnp

    from gofr_tpu.models import resnet

    batch = 256 if on_tpu else 16
    iters = 20 if on_tpu else 4

    cfg = resnet.config("50" if on_tpu else "tiny")
    params = jax.device_put(resnet.init(cfg, jax.random.PRNGKey(0)))

    def classify(p, u8):
        x = u8.astype(jnp.bfloat16) / 255.0  # on-device normalize
        return resnet.apply(p, cfg, x)

    step = jax.jit(classify)
    u8_host = np.ones((batch, cfg.image_size, cfg.image_size, 3), np.uint8)
    u8_dev = jax.device_put(jnp.asarray(u8_host))
    # one AOT compile serves the warm call, the timed windows AND the
    # cost analysis (calling step() here would compile the identical
    # program a second time through the jit cache)
    compiled = step.lower(params, u8_dev).compile()
    jax.block_until_ready(compiled(params, u8_dev))  # warm

    # XLA's own FLOP count for the serving batch → MFU
    flops_per_batch = float(compiled.cost_analysis().get("flops", 0.0))
    flops_per_image = flops_per_batch / batch

    def timed_window(fn, arg, n):
        t0 = time.perf_counter()
        outs = [fn(params, arg) for _ in range(n)]
        jax.block_until_ready(outs)
        return (time.perf_counter() - t0) / n

    timed_window(compiled, u8_dev, 3)  # settle
    per_batch = min(timed_window(compiled, u8_dev, iters) for _ in range(3))
    req_per_s = batch / per_batch

    device_kind = jax.devices()[0].device_kind
    peak = _device_peak("bf16_flops") if on_tpu else None

    # operating point (VERDICT r4 #1): sweep the bucket ladder and time
    # each bucket's DEVICE-attributable latency via an in-executable
    # chain (see _chained_device_latency). The point is the largest
    # bucket whose closed-loop p99 proxy (service + one queued batch of
    # slack = 2x latency) fits the 10 ms budget; fits_budget is judged on
    # device-attributable latency — the host link's floor is reported
    # alongside in the top-level `host_link` block, never folded in.
    def classify_step(p, u8, eps):
        x = (u8 + eps.astype(jnp.uint8)).astype(jnp.bfloat16) / 255.0
        return resnet.apply(p, cfg, x)

    sweep = []
    op = None
    head_lat = None     # unrounded latency at the serving batch
    for b in ((8, 16, 32, 64, 128, 256) if on_tpu else (4, 8, 16)):
        xb = jax.device_put(jnp.asarray(u8_host[:1]).repeat(b, axis=0))
        lat, spread = _chained_device_latency(classify_step, params, xb, b)
        if b == batch and lat:
            head_lat = lat
        if lat is None:
            sweep.append({"batch": b, "device_latency_ms": None,
                          "note": "slope <= 0: host noise swamped signal"})
            continue
        point = {"batch": b,
                 "device_latency_ms": round(lat * 1e3, 2),
                 "req_per_s": round(b / lat, 1),
                 "p99_proxy_ms": round(2.0 * lat * 1e3, 2),
                 "slope_spread": round(spread, 2),
                 "fits_budget": 2.0 * lat * 1e3 < TARGET_P99_MS}
        sweep.append(point)
        if point["fits_budget"] and point["req_per_s"] >= TARGET_REQ_S \
                and (op is None or point["req_per_s"] > op["req_per_s"]):
            op = point
    if op is None:      # nothing fits: report the knee, honestly failing
        candidates = [p for p in sweep if p.get("device_latency_ms")]
        op = min(candidates,
                 key=lambda p: p["p99_proxy_ms"]) if candidates else {
                     "batch": None, "fits_budget": False}
    op_point = {**op, "p99_budget_ms": TARGET_P99_MS,
                "target_req_s": TARGET_REQ_S,
                "basis": "device-attributable latency (single-dispatch "
                         "in-executable chain); per-call floor reported "
                         "in `host_link`"}

    # device-resident rate + MFU from the sweep's serving-batch
    # measurement, kept unrounded (same in-executable chain method)
    device_per_batch = head_lat
    device_req_s = batch / device_per_batch if device_per_batch else None
    mfu = (device_req_s * flops_per_image / peak) \
        if (peak and device_req_s) else None

    return {
        "req_per_s": req_per_s,
        "batch": batch,
        "batch_latency_ms": round(per_batch * 1e3, 2),
        "device_only_req_per_s": round(device_req_s, 1)
        if device_req_s else None,
        "device_batch_latency_ms": round(device_per_batch * 1e3, 2)
        if device_per_batch else None,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "flops_per_image": round(flops_per_image / 1e9, 2),
        "device_kind": device_kind,
        "operating_point": op_point,
        "bucket_sweep": sweep,
        "data_plane": {"ingest": "device-resident",
                       "staging": "n/a (inputs pre-uploaded)"},
    }


async def _closed_loop(port: int, path: str, body: bytes, method: str,
                       clients: int, seconds: float,
                       content_type: str = "application/octet-stream"):
    """Closed-loop load: ``clients`` persistent connections, each sending
    back-to-back requests. Returns (req_s, latencies) over the timed
    window (a warm half-window is discarded)."""
    head = (f"{method} {path} HTTP/1.1\r\nHost: bench\r\n"
            f"Content-Type: {content_type}\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode() + body

    latencies: list = []
    warm_until = time.perf_counter() + seconds * 0.4
    stop_at = warm_until + seconds
    counted = [0]

    async def one_client():
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        try:
            while True:
                now = time.perf_counter()
                if now >= stop_at:
                    return
                writer.write(head)
                await writer.drain()
                header_blob = await reader.readuntil(b"\r\n\r\n")
                length = 0
                for line in header_blob.split(b"\r\n"):
                    if line.lower().startswith(b"content-length:"):
                        length = int(line.split(b":", 1)[1])
                await reader.readexactly(length)
                if now >= warm_until:
                    latencies.append(time.perf_counter() - now)
                    counted[0] += 1
        finally:
            writer.close()

    t0 = time.perf_counter()
    await asyncio.gather(*[one_client() for _ in range(clients)])
    elapsed = time.perf_counter() - t0 - (warm_until - t0)
    return counted[0] / elapsed, latencies


def _http_bench(on_tpu: bool) -> dict:
    """Measured p50/p99 through the real serve path (BASELINE.md config 2
    names router → handler → batcher → executor).

    /hello is config 1 (pure framework overhead, no model). /classify
    carries a raw uint8 image per request, so its figure includes the
    host link's H2D for every image (floor in `host_link`)."""
    import jax

    from gofr_tpu.app import App
    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import resnet

    container = new_mock_container({"TPU_ENABLED": "true",
                                    "TPU_MAX_BATCH": "16",
                                    "TPU_BATCH_DELAY_MS": "1.0"})
    app = App(config=container.config, container=container)
    app.http_port = 0
    app.metrics_port = 0

    cfg = resnet.config("50" if on_tpu else "tiny")
    params = resnet.init(cfg, jax.random.PRNGKey(0))
    shape = (cfg.image_size, cfg.image_size, 3)

    def classify_fn(p, u8):
        import jax.numpy as jnp
        x = u8.astype(jnp.bfloat16) / 255.0
        return resnet.apply(p, cfg, x)

    app.add_model("resnet50", classify_fn, params=params,
                  buckets=(4, 8, 16))

    def hello(ctx):
        return {"message": "Hello World!"}

    async def classify(ctx):
        img = np.frombuffer(ctx.bind(), np.uint8).reshape(shape)
        logits = await ctx.predict("resnet50", img)
        return {"label": int(np.argmax(logits))}

    app.get("/hello", hello)
    app.post("/classify", classify)

    image = np.ones(shape, np.uint8).tobytes()
    seconds = 4.0 if on_tpu else 1.5

    def load_in_thread(*args, **kwargs):
        """Clients get their own event loop (asyncio.run) in the executor
        worker thread: sharing the server's loop would measure client-side
        queuing as latency."""
        return asyncio.run(_closed_loop(*args, **kwargs))

    async def run_loads():
        await app.start()
        loop = asyncio.get_running_loop()
        app.container.tpu.warmup(
            "resnet50", np.ones(shape, np.uint8))  # compile all buckets
        port = app._http_server.bound_port
        # hello is CPU-bound on this 1-core container, so a single window
        # swings ±30% with host load (r4 shipped 5495 vs r3's 9090 from
        # exactly this; an A/B of the r3 server code on the same host
        # measured inside the same band). Run 3 windows, report median +
        # the spread so readers can judge the noise.
        hello_rounds = []
        hello_lat = []
        for _ in range(3):
            r, lats = await loop.run_in_executor(
                None, load_in_thread, port, "/hello", b"", "GET", 32,
                seconds)
            hello_rounds.append(r)
            hello_lat.extend(lats)
        cls_req_s, cls_lat = await loop.run_in_executor(
            None, load_in_thread, port, "/classify", image, "POST", 16,
            seconds)
        await app.stop()
        return hello_rounds, hello_lat, cls_req_s, cls_lat

    hello_rounds, hello_lat, cls_req_s, cls_lat = asyncio.run(run_loads())
    hello_p50, hello_p99 = _percentiles(hello_lat)
    cls_p50, cls_p99 = _percentiles(cls_lat)
    return {
        "http_hello": {"req_per_s": round(float(np.median(hello_rounds)), 1),
                       "rounds_req_per_s": [round(r, 1)
                                            for r in hello_rounds],
                       "p50_ms": hello_p50, "p99_ms": hello_p99,
                       "clients": 32,
                       "data_plane": {"ingest": "none (empty GET)",
                                      "staging": "n/a"}},
        "http_classify": {"req_per_s": round(cls_req_s, 1),
                          "p50_ms": cls_p50, "p99_ms": cls_p99,
                          "clients": 16, "max_batch": 16,
                          "note": "full path incl. per-request H2D",
                          "data_plane": {
                              "ingest": "binary (octet-stream body)",
                              "staging": "slab (EXEC_STAGING default)"}},
        "p50_ms": cls_p50,
        "p99_ms": cls_p99,
    }


def _bert_grpc_bench(on_tpu: bool) -> dict:
    """BASELINE.md config 3: gRPC streaming BERT-base embeddings with
    dynamic batching (VERDICT r4 #3 — the one config with no perf number).

    Three views, because the *batching gain curve* is the point:
    1. Device-side ceiling — the compiled embed step at batch 1/8/32 via
       the in-executable timing chain: what one chip sustains per batch
       shape.
    2. Full gRPC unary path at concurrency 1 vs 32 — through grpc.aio,
       dynamic JSON codec, context middleware, and the dynamic batcher;
       the concurrency-32 number shows the batcher coalescing real
       concurrent RPCs (each call still pays one dispatch round trip,
       which amortizes across the coalesced batch).
    3. Server-streaming TTFB — `/gofr.Embeddings/embedStream` emits one
       embedding message per sentence; time to the first message.
    """
    import jax
    import jax.numpy as jnp

    from gofr_tpu.app import App
    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import bert

    max_len = 64
    cfg = bert.config("base" if on_tpu else "tiny", max_len=max_len)
    params = jax.device_put(bert.init(cfg, jax.random.PRNGKey(0)))

    def embed_step(p, inputs):
        ids, mask = inputs
        return bert.apply(p, cfg, ids, mask)["mean"]

    # 1. device-side batching gain curve (in-executable chain: the
    # dispatch round trip cancels exactly — see _chained_device_latency)
    def embed_chain_step(p, inputs, eps):
        ids, mask = inputs
        return bert.apply(p, cfg, ids + eps.astype(jnp.int32),
                          mask)["mean"]

    gain = []
    for b in ((1, 8, 32) if on_tpu else (1, 4)):
        ids = jax.device_put(jnp.ones((b, max_len), jnp.int32))
        mask = jax.device_put(jnp.ones((b, max_len), jnp.int32))
        lat, _spread = _chained_device_latency(embed_chain_step, params,
                                               (ids, mask), b)
        gain.append({"batch": b,
                     "device_latency_ms": round(lat * 1e3, 3)
                     if lat else None,
                     "emb_per_s": round(b / lat, 1) if lat else None})

    # 2 + 3. the real gRPC path
    container = new_mock_container({"TPU_ENABLED": "true",
                                    "TPU_MAX_BATCH": "32",
                                    "TPU_BATCH_DELAY_MS": "2.0"})
    app = App(config=container.config, container=container)
    app.http_port = 0
    app.metrics_port = 0
    app.grpc_port = 0
    app.add_model("bert", embed_step, params=params, buckets=(1, 4, 16, 32))

    async def embed(ctx):
        data = ctx.bind()
        ids = np.zeros((max_len,), np.int32)
        mask = np.zeros((max_len,), np.int32)
        tokens = data["token_ids"][:max_len]
        ids[:len(tokens)] = tokens
        mask[:len(tokens)] = 1
        out = await ctx.predict("bert", (ids, mask))
        return {"dim": len(out)}     # skip float serialization in the loop

    async def embed_stream(ctx):
        data = ctx.bind()
        for sentence in data["batch"]:
            ids = np.zeros((max_len,), np.int32)
            mask = np.zeros((max_len,), np.int32)
            tokens = sentence[:max_len]
            ids[:len(tokens)] = tokens
            mask[:len(tokens)] = 1
            out = await ctx.predict("bert", (ids, mask))
            yield {"embedding": [round(float(v), 4) for v in out[:8]]}

    app.register_grpc_unary("Embeddings", "embed", embed)
    app.register_grpc_stream("Embeddings", "embedStream", embed_stream)

    seconds = 4.0 if on_tpu else 1.5
    payload = json.dumps({"token_ids": list(range(16))}).encode()

    def grpc_load(port, concurrency, seconds):
        """Closed-loop unary load from a worker thread's own event loop."""
        import grpc

        async def go():
            async with grpc.aio.insecure_channel(f"127.0.0.1:{port}") as ch:
                method = ch.unary_unary("/gofr.Embeddings/embed")
                warm_until = time.perf_counter() + seconds * 0.3
                stop_at = warm_until + seconds
                counted = [0]

                async def one():
                    while time.perf_counter() < stop_at:
                        await method(payload)
                        if time.perf_counter() >= warm_until:
                            counted[0] += 1
                await asyncio.gather(*[one() for _ in range(concurrency)])
                rate = counted[0] / seconds
            await asyncio.sleep(0.1)   # let grpc.aio's poller quiesce
            return rate
        return asyncio.run(go())

    def grpc_ttfb(port, samples=8):
        import grpc

        async def go():
            body = json.dumps({"batch": [list(range(12))] * 4}).encode()
            ttfbs = []
            async with grpc.aio.insecure_channel(f"127.0.0.1:{port}") as ch:
                method = ch.unary_stream("/gofr.Embeddings/embedStream")
                for _ in range(samples):
                    t0 = time.perf_counter()
                    call = method(body)
                    async for _ in call:
                        ttfbs.append(time.perf_counter() - t0)
                        break
                    call.cancel()
            await asyncio.sleep(0.1)   # let grpc.aio's poller quiesce
            return ttfbs
        return asyncio.run(go())

    async def run_loads():
        await app.start()
        loop = asyncio.get_running_loop()
        container.tpu.warmup("bert", (np.ones((max_len,), np.int32),
                                      np.ones((max_len,), np.int32)))
        port = app._grpc_server.bound_port
        seq = await loop.run_in_executor(None, grpc_load, port, 1, seconds)
        batched = await loop.run_in_executor(
            None, grpc_load, port, 32, seconds)
        ttfbs = await loop.run_in_executor(None, grpc_ttfb, port)
        await app.stop()
        return seq, batched, ttfbs

    seq, batched, ttfbs = asyncio.run(run_loads())
    p50, p99 = _percentiles(ttfbs)
    return {
        "device_gain_curve": gain,
        "grpc_emb_per_s_concurrency_1": round(seq, 1),
        "grpc_emb_per_s_concurrency_32": round(batched, 1),
        "batching_gain": round(batched / seq, 2) if seq else None,
        "stream_ttfb_ms": {"p50": p50, "p99": p99, "samples": len(ttfbs)},
        "data_plane": {"ingest": "json (grpc dynamic codec)",
                       "staging": "slab (EXEC_STAGING default)"},
        "note": ("grpc path numbers include the per-call dispatch floor "
                 "(see `host_link`); concurrency 32 shows the dynamic "
                 "batcher amortizing it across a coalesced batch"),
    }


def _llama_decode_bench(on_tpu: bool) -> dict:
    """Aggregate decode tok/s through the continuous-batching engine
    (8 streams, llama-small, K=8 multi-step), post-warmup steady state.

    Reports best AND median over 5 rounds (best-of-2 cannot tell a
    regression from noise), plus
    time-to-first-token p50/p99 measured through the real HTTP SSE path
    (`/generate/stream` — the surface BASELINE config 3/5 names)."""
    import jax

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import llama
    from gofr_tpu.tpu.generate import GenerationEngine

    preset = "small" if on_tpu else "tiny"
    cfg = llama.config(preset, max_seq_len=1024)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    container = new_mock_container()
    # K=32 fused steps, mirroring the 7B operating point: fewer, longer
    # ticks amortize the per-tick host cost. The adaptive ladder still
    # drops K when admissions wait, so TTFT stays bounded. Whether K=32
    # is still the knee on a direct-attached host is ROADMAP S2/D9's
    # measurement.
    engine = GenerationEngine(cfg, params, max_slots=8, max_len=512,
                              prompt_buckets=(32,), steps_per_tick=32,
                              max_inflight_ticks=4,
                              logger=container.logger,
                              metrics=container.metrics)
    # 65 = 1 prefill token + exactly two fused K=32 ticks of decode per
    # request — the budget never strands tokens on small tail rungs
    # (64 would decay 32,16,8,4,2,1: six dispatches for one request)
    tokens_each = 65 if on_tpu else 8
    rounds = 5 if on_tpu else 2

    async def run_streams():
        # precompile the ladder BEFORE timing: round 2 shipped 43 tok/s
        # because four TPU compiles landed inside the timed window. The
        # throughput rounds stay < 120 fill (128 rung), but the
        # under-load TTFT's 192-token background generations climb past
        # 112 into the 256 rung — warm both columns of the matrix.
        await engine.warmup(prompt_counts=(1, 8), windows=(128, 256))
        await engine.start()
        # settle: absorbs each executable's one-time first-call stall
        # (warmup compiles don't absorb it on this host; see
        # _llama7b_int8_bench) before the timed window. Budget 64 decays
        # 32+16+8+4+2+1 — every ladder rung executes once, so neither the
        # timed rounds (K=32) nor the TTFT probes (small rungs) hit a
        # first-execution stall (r5: a 33-token settle left K≤16 cold and
        # put a 2.2 s outlier in sequential TTFT p99)
        await engine.generate(list(range(8)), max_new_tokens=64)
        rates = []
        for _ in range(rounds):
            start = time.perf_counter()
            outs = await asyncio.gather(*[
                engine.generate([i + 1] * 16, max_new_tokens=tokens_each)
                for i in range(8)])
            elapsed = time.perf_counter() - start
            rates.append(sum(len(o) for o in outs) / elapsed)
        ttfts, ttft_loaded = await _llama_stream_ttft(engine)
        await engine.stop()
        return rates, ttfts, ttft_loaded

    rates, ttfts, ttft_loaded = asyncio.run(run_streams())
    p50, p99 = _percentiles(ttfts)
    median_rate = float(np.median(rates))
    if ttft_loaded.get("aggregate_tok_s"):
        ttft_loaded["tok_s_vs_unloaded"] = round(
            ttft_loaded["aggregate_tok_s"] / median_rate, 2)
    return {
        "tok_s_best": round(max(rates), 1),
        "tok_s_median": round(median_rate, 1),
        "tok_s_min": round(min(rates), 1),
        "rounds": len(rates),
        "ttft": {"p50_ms": p50, "p99_ms": p99, "requests": len(ttfts),
                 "note": "sequential, via HTTP SSE /generate/stream"},
        "ttft_under_load": ttft_loaded,
        "data_plane": {"ingest": "json (HTTP /generate + SSE)",
                       "staging": "per-array uploads (coalescer off)"},
    }


def _build_stream_app(engine):
    """App serving POST /generate/stream over SSE from ``engine``. The
    request body may carry {"max_new_tokens": N} (default 24)."""
    from gofr_tpu.app import App
    from gofr_tpu.container import new_mock_container
    from gofr_tpu.http.response import Stream

    container = new_mock_container()
    app = App(config=container.config, container=container)
    app.http_port = 0
    app.metrics_port = 0

    async def generate_stream(ctx):
        try:
            tokens = int((ctx.bind() or {}).get("max_new_tokens", 24))
        except Exception:  # noqa: BLE001 — empty body
            tokens = 24
        stream = await engine.generate_stream([1, 2, 3, 4] * 4,
                                              max_new_tokens=tokens)

        async def frames():
            async for token_id in stream:
                yield str(token_id)

        return Stream(frames(), sse=True, on_close=stream.cancel)

    app.post("/generate/stream", generate_stream)
    return app


async def _stream_once(port: int, max_new_tokens: int = 24):
    """One SSE client: returns (ttft_seconds, tokens_received). Drains the
    stream to EOF so the engine slot frees cleanly."""
    body = json.dumps({"max_new_tokens": max_new_tokens}).encode()
    head = (b"POST /generate/stream HTTP/1.1\r\nHost: bench\r\n"
            b"Connection: close\r\nContent-Type: application/json\r\n"
            b"Content-Length: %d\r\n\r\n" % len(body)) + body
    t0 = time.perf_counter()
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(head)
    await writer.drain()
    ttft = None
    count = 0
    while True:
        # bounded read: an engine failure path must fail the bench after
        # 30 s, not wedge it forever on a silent open connection
        line = await asyncio.wait_for(reader.readline(), 30.0)
        if line.startswith(b"data:"):
            count += 1
            if ttft is None:
                ttft = time.perf_counter() - t0
            continue
        if not line:
            break
    writer.close()
    if ttft is None:
        raise RuntimeError("stream closed before first token")
    return ttft, count


async def _llama_stream_ttft(engine) -> tuple:
    """TTFT through the REAL serve path: HTTP server → SSE Stream response
    → engine.generate_stream. Runs on the engine's own event loop (its
    queues are loop-bound).

    Two regimes (VERDICT r4 weak #5 — the loaded number is what an
    operator cares about):
    - sequential: one client at a time, idle engine — the latency floor;
    - under load: every slot is already decoding a long generation, then
      2x max_slots probes arrive concurrently — TTFT includes admission
      contention with inflight ticks and waiting for slots to free.
    Returns (sequential_ttfts, loaded_result_dict)."""
    app = _build_stream_app(engine)
    await app.start()
    port = app._http_server.bound_port

    seq_ttfts = []
    for _ in range(16):
        ttft, _count = await _stream_once(port)
        seq_ttfts.append(ttft)

    # saturate: one long generation per slot, probes contend for admission
    n_slots = engine.max_slots
    probes = 2 * n_slots
    t_all = time.perf_counter()
    background = [
        asyncio.ensure_future(_stream_once(port, max_new_tokens=192))
        for _ in range(n_slots)]
    await asyncio.sleep(0.05)           # let the background fill the slots
    results = await asyncio.gather(
        *[_stream_once(port) for _ in range(probes)])
    bg = await asyncio.gather(*background)
    elapsed_all = time.perf_counter() - t_all
    loaded_ttfts = [ttft for ttft, _ in results]
    total_tokens = sum(count for _, count in results) \
        + sum(count for _, count in bg)
    p50, p99 = _percentiles(loaded_ttfts)
    loaded = {
        "p50_ms": p50, "p99_ms": p99, "requests": probes,
        "busy_slots": n_slots,
        "aggregate_tok_s": round(total_tokens / elapsed_all, 1),
        "background_complete": all(count == 192 for _, count in bg),
        "note": ("probes issued concurrently against an engine whose "
                 "every slot is mid-generation; TTFT includes slot-wait "
                 "+ admission contention with inflight decode ticks; "
                 "aggregate_tok_s spans the whole mixed window incl. "
                 "probe prefills interleaving the decode loop"),
    }
    await app.stop()
    return seq_ttfts, loaded


def _llama_prefix_reuse_bench(on_tpu: bool):
    """Shared-system-prompt workload through the prefix-KV cache
    (docs/tpu/model-serving.md "Prefix KV reuse"): every request opens
    with the same page-aligned system prefix — 128 tokens (4 pages of
    32) at serving scale — plus its own short tail. The first request
    prefills the full prompt and publishes the prefix pages; later ones
    gather the cached pages and prefill only their suffix bucket, so
    TTFT drops by roughly the prefill FLOPs the cache skipped. The same
    workload runs against a cache-off engine of identical geometry:
    `token_identical` reports the determinism contract (greedy outputs
    must match bit-for-bit with bf16 KV), and the FLOPs saving is the
    ratio of prefill bucket tokens actually dispatched."""
    import jax

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import llama
    from gofr_tpu.tpu.generate import GenerationEngine

    # tiny geometry on CPU keeps the scenario exercised everywhere; the
    # small preset with the issue's 128-token shared prefix on TPU
    if on_tpu:
        preset, max_len, buckets, page, pages = (
            "small", 512, (32, 64, 128, 256), 32, 4)
    else:
        preset, max_len, buckets, page, pages = "tiny", 64, (8, 16), 4, 2
    cfg = llama.config(preset)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    prefix_len = pages * page
    system = [(i % 250) + 1 for i in range(prefix_len)]
    tails = [[101 + i, 67, 13 + i] for i in range(8)]
    budget = 8

    def build(prefix_on):
        container = new_mock_container()
        return GenerationEngine(
            cfg, params, max_slots=4, max_len=max_len,
            prompt_buckets=buckets, steps_per_tick=4,
            prefix_cache=prefix_on, prefix_page=page,
            prefix_cache_bytes=8 << 20,
            logger=container.logger, metrics=container.metrics)

    async def drive(engine):
        await engine.start()
        try:
            # warm pass: compiles the executables off the timed path and
            # (cache on) publishes the shared prefix's pages
            for tail in tails:
                await engine.generate(system + tail, max_new_tokens=budget)
            outs = []
            for tail in tails:        # timed pass: warm + prefix cached
                outs.append(await engine.generate(system + tail,
                                                  max_new_tokens=budget))
            recent = engine.recorder.snapshot(limit=len(tails))["recent"]
            ttfts = [r["ttft_s"] for r in recent
                     if r["ttft_s"] is not None]
            stats = engine.stats()
        finally:
            await engine.stop()
        return outs, ttfts, stats

    off_outs, off_ttfts, off_stats = asyncio.run(drive(build(False)))
    on_outs, on_ttfts, on_stats = asyncio.run(drive(build(True)))

    def med_ms(values):
        return round(float(np.median(values)) * 1e3, 2) if values else None

    bucket_on = on_stats["prefill_bucket_tokens"]
    bucket_off = off_stats["prefill_bucket_tokens"]
    prefix = on_stats.get("prefix_cache", {})
    return {
        "preset": preset,
        "data_plane": {"ingest": "in-proc prompt ids",
                       "staging": "per-array uploads (coalescer off)"},
        "shared_prefix_tokens": prefix_len,
        "page_tokens": page,
        "requests_per_pass": len(tails),
        # determinism contract: greedy outputs identical cache on/off
        "token_identical": on_outs == off_outs,
        "ttft_ms_prefix_on": med_ms(on_ttfts),
        "ttft_ms_prefix_off": med_ms(off_ttfts),
        # prompt FLOPs scale with the bucket tokens dispatched to prefill
        # executables (padding included — that's what the device runs)
        "prefill_bucket_tokens_on": bucket_on,
        "prefill_bucket_tokens_off": bucket_off,
        "prefill_flops_saved_pct": round(
            (1.0 - bucket_on / bucket_off) * 100.0, 1)
        if bucket_off else None,
        "prefix_tokens_saved": prefix.get("tokens_saved"),
        "lookups": prefix.get("lookups"),
        "note": ("TTFT via the flight recorder (admission wait included); "
                 "both passes per engine, second pass timed — warm "
                 "executables, prefix published. Compare on vs off within "
                 "this run, not across rounds"),
    }


def _llama_paged_kv_bench(on_tpu: bool):
    """Mixed-length traffic through the unified KV page pool
    (docs/tpu/model-serving.md "Unified paged KV") against a dense
    engine of identical geometry. The dense cache prices HBM at
    ``max_slots * max_len`` regardless of what decode actually holds;
    the paged engine runs the SAME workload out of a pool half that
    size, so the scenario reports the determinism contract
    (`token_identical`: greedy outputs must match bit-for-bit), decode
    throughput both ways, and the HBM the pool did not reserve."""
    import time

    import jax

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import llama
    from gofr_tpu.tpu.generate import GenerationEngine

    # tiny geometry on CPU keeps the scenario exercised everywhere
    if on_tpu:
        preset, max_len, buckets, page, slots = (
            "small", 512, (32, 64, 128, 256), 32, 8)
    else:
        preset, max_len, buckets, page, slots = "tiny", 64, (8, 16), 4, 4
    cfg = llama.config(preset)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    # mixed lengths spread across the bucket ladder — the workload the
    # dense cache overprovisions for hardest
    prompts = [[(7 * i + j) % 250 + 1 for j in range(length)]
               for i, length in enumerate(
                   [b - 3 for b in buckets] * 3 + [buckets[0] // 2] * 2)]
    budget = 8
    dense_pages = slots * (max_len // page)

    def build(paged):
        container = new_mock_container()
        kwargs = dict(paged_kv=True, kv_page=page,
                      kv_pages=dense_pages // 2) if paged else {}
        return GenerationEngine(
            cfg, params, max_slots=slots, max_len=max_len,
            prompt_buckets=buckets, steps_per_tick=4,
            logger=container.logger, metrics=container.metrics, **kwargs)

    async def drive(engine):
        await engine.start()
        try:
            # warm pass compiles the executable family off the timed path
            await asyncio.gather(*[
                engine.generate(p, max_new_tokens=budget) for p in prompts])
            start = time.perf_counter()
            outs = await asyncio.gather(*[
                engine.generate(p, max_new_tokens=budget) for p in prompts])
            elapsed = time.perf_counter() - start
            stats = engine.stats()
        finally:
            await engine.stop()
        tokens = sum(len(o) for o in outs)
        return outs, tokens / elapsed if elapsed else None, stats

    dense_outs, dense_tok_s, _ = asyncio.run(drive(build(False)))
    paged_outs, paged_tok_s, paged_stats = asyncio.run(drive(build(True)))

    pool = paged_stats.get("kv_pool", {})
    page_bytes = pool.get("page_bytes") or 0
    dense_bytes = page_bytes * dense_pages
    return {
        "preset": preset,
        "requests_per_pass": len(prompts),
        "page_tokens": page,
        "data_plane": {"ingest": "in-proc prompt ids",
                       "staging": "per-array uploads (coalescer off)"},
        # determinism contract: greedy outputs identical dense vs paged
        "token_identical": dense_outs == paged_outs,
        "decode_tok_s_dense": round(dense_tok_s, 1) if dense_tok_s else None,
        "decode_tok_s_paged": round(paged_tok_s, 1) if paged_tok_s else None,
        # the headline: same workload, half the KV HBM reservation
        "kv_hbm_bytes_dense": dense_bytes,
        "kv_hbm_bytes_paged": pool.get("pool_bytes"),
        "kv_hbm_saved_pct": round(
            (1.0 - pool.get("pool_bytes", 0) / dense_bytes) * 100.0, 1)
        if dense_bytes else None,
        "pool_occupancy_at_end": pool.get("occupancy"),
        "pages_written": pool.get("writes"),
        "page_stalls": pool.get("stalls"),
        "deferred_admissions": pool.get("deferred_requests"),
        "note": ("pool sized to half the dense reservation; identical "
                 "greedy outputs prove the gather path, the saving is the "
                 "HBM the pool never reserved. Compare dense vs paged "
                 "within this run, not across rounds"),
    }


def _llama_ragged_attn_bench(on_tpu: bool):
    """Fused ragged paged attention (docs/tpu/model-serving.md "Ragged
    paged attention") against a gather-path control of identical
    geometry. The decode_attention post-mortem applies in full here: a
    pallas_call inside the per-layer scan once broke XLA's weight
    prefetch and lost 5x at the TICK level while winning at the op
    level — so this scenario times the FULL compiled decode tick
    (device-only chain, donation-threaded) both ways, never the kernel
    alone. Also reports the determinism contract (`token_identical`:
    greedy engine streams must match bit-for-bit), the executable-count
    collapse (ragged retires the per-gather-width ladder), and the HBM
    gather traffic the kernel stops materializing."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import llama
    from gofr_tpu.tpu.generate import GenerationEngine

    # tiny geometry on CPU (kernel in interpret mode) keeps the scenario
    # exercised everywhere; TPU runs the compiled kernel at 4k context
    if on_tpu:
        preset, max_len, buckets, page, slots = (
            "small", 4096, (128, 256), 128, 8)
    else:
        preset, max_len, buckets, page, slots = "tiny", 64, (8, 16), 8, 4
    cfg = llama.config(preset)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    prompts = [[(5 * i + j) % 250 + 1 for j in range(length)]
               for i, length in enumerate(
                   [b - 2 for b in buckets] * 2 + [buckets[0] // 2])]
    budget = 8
    k_steps = 4

    def build(mode):
        container = new_mock_container()
        return GenerationEngine(
            cfg, params, max_slots=slots, max_len=max_len,
            prompt_buckets=buckets, steps_per_tick=k_steps,
            paged_kv=True, kv_page=page, ragged_attn=mode,
            logger=container.logger, metrics=container.metrics)

    async def drive(engine):
        await engine.start()
        try:
            await asyncio.gather(*[
                engine.generate(p, max_new_tokens=budget) for p in prompts])
            start = time.perf_counter()
            outs = await asyncio.gather(*[
                engine.generate(p, max_new_tokens=budget) for p in prompts])
            elapsed = time.perf_counter() - start
        finally:
            await engine.stop()
        tokens = sum(len(o) for o in outs)
        return outs, tokens / elapsed if elapsed else None

    def device_only(engine):
        # full-tick chain at full table width, mid-fill context: the
        # donation-threaded loop cancels the dispatch floor, the token
        # fetch is the barrier (post-mortem method: measure the tick a
        # serving engine actually dispatches, weight stream included)
        pw = engine.pages_per_slot
        fn = engine._decode_paged_fn(k_steps, pw=pw)
        fill = (max_len // 2 // page) * page
        table = np.full((slots, pw), engine._pool.sentinel, np.int32)
        nxt = 0
        for b in range(slots):
            for col in range(fill // page):
                table[b, col] = nxt % engine._pool.num_pages
                nxt += 1
        table = jnp.asarray(table)
        token = jnp.zeros((slots,), jnp.int32)
        active = jnp.ones((slots,), bool)
        pool = engine._pool.leaves
        cache_len = jnp.full((slots,), fill, jnp.int32)
        tokens_dev, pool, cache_len = fn(
            engine.params, token, pool, table, cache_len, active)
        np.asarray(tokens_dev)                       # warm + barrier

        def chain(n):
            nonlocal tokens_dev, pool, cache_len
            t0 = time.perf_counter()
            for _ in range(n):
                tokens_dev, pool, cache_len = fn(
                    engine.params, tokens_dev[-1], pool, table,
                    cache_len, active)
            np.asarray(tokens_dev)
            return time.perf_counter() - t0
        slopes = [(chain(6) - chain(2)) / 4 for _ in range(2)]
        tick_s = float(np.median(slopes))
        return (slots * k_steps / tick_s) if tick_s > 0 else None

    g_eng = build("off")
    gather_outs, gather_tok_s = asyncio.run(drive(g_eng))
    r_eng = build("on" if not on_tpu else "auto")
    ragged_outs, ragged_tok_s = asyncio.run(drive(r_eng))
    gather_execs = len(g_eng._decode_paged_fns)
    ragged_execs = len(r_eng._decode_paged_fns)
    gather_dev = device_only(g_eng)
    ragged_dev = device_only(r_eng)

    # the gather materialization each tick step stops paying for: K+V
    # copies of the full gathered window, every layer, every slot
    itemsize = 1 if cfg.kv_int8 else jnp.dtype(cfg.dtype).itemsize
    gather_bytes_per_step = (cfg.n_layers * slots * r_eng.pages_per_slot
                             * page * cfg.n_kv_heads * cfg.head_dim
                             * itemsize * 2)
    return {
        "preset": preset,
        "attn_path": r_eng.attn_path,
        "page_tokens": page,
        "interpret_mode": not on_tpu,
        # determinism contract: greedy streams identical gather vs ragged
        "token_identical": gather_outs == ragged_outs,
        "decode_tok_s_gather": round(gather_tok_s, 1)
        if gather_tok_s else None,
        "decode_tok_s_ragged": round(ragged_tok_s, 1)
        if ragged_tok_s else None,
        "device_only_tok_s_gather": round(gather_dev, 1)
        if gather_dev else None,
        "device_only_tok_s_ragged": round(ragged_dev, 1)
        if ragged_dev else None,
        # ladder retirement: executables compiled while serving the SAME
        # workload (ragged pins one width; gather walks the rung ladder)
        "decode_executables_gather": gather_execs,
        "decode_executables_ragged": ragged_execs,
        "gather_widths_ragged": r_eng.xlaz()["paged_kv"]["gather_widths"],
        "hbm_gather_bytes_saved_per_step": gather_bytes_per_step,
        "note": ("CPU runs the kernel in Pallas interpret mode, so "
                 "device-only numbers only mean something on TPU — "
                 "token_identical and the executable counts are the "
                 "cross-platform contract. Compare gather vs ragged "
                 "within this run, not across rounds"),
    }


def _llama_disagg_bench(on_tpu: bool):
    """Disaggregated serving (docs/tpu/model-serving.md "Disaggregated
    serving") vs a monolithic control on the same config and workload:
    one DENSE prefill replica exports each prompt's KV, the paged decode
    replica adopts it over the full kv_wire pack → chunk → unpack path
    (in-proc transport: the codec and the adopt scatter are priced, the
    network is not), and the router relays the stream. Reports TTFT both
    ways (disagg TTFT carries the transfer leg), decode tok/s, packed
    bytes shipped per request, and the determinism contract — greedy
    outputs bit-identical with ZERO prefill dispatches on the decode
    replica (`decode_prefill_bucket_tokens` must read 0)."""
    import time

    import jax

    from gofr_tpu.clusterz import build_clusterz
    from gofr_tpu.container import new_mock_container
    from gofr_tpu.metrics.timeseries import TimeSeriesStore
    from gofr_tpu.models import llama
    from gofr_tpu.tpu.cluster import (ClusterRegistry, DisaggRouter,
                                      InProcTransport)
    from gofr_tpu.tpu.generate import GenerationEngine

    # tiny geometry on CPU keeps the scenario exercised everywhere
    if on_tpu:
        preset, max_len, buckets, page, slots = (
            "small", 512, (32, 64, 128, 256), 32, 8)
    else:
        preset, max_len, buckets, page, slots = "tiny", 64, (8, 16), 4, 4
    cfg = llama.config(preset)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    prompts = [[(5 * i + j) % 250 + 1 for j in range(length)]
               for i, length in enumerate([b - 2 for b in buckets] * 2)]
    budget = 8

    def build(paged):
        container = new_mock_container()
        kwargs = dict(paged_kv=True) if paged else {}
        return GenerationEngine(
            cfg, params, max_slots=slots, max_len=max_len,
            prompt_buckets=buckets, kv_page=page, steps_per_tick=4,
            logger=container.logger, metrics=container.metrics, **kwargs)

    async def drive(open_stream):
        """Sequential closed loop (TTFT needs an uncontended prefill):
        per-request time-to-first-token plus aggregate tok/s."""
        outs, ttfts = [], []
        start = time.perf_counter()
        for prompt in prompts:
            t0 = time.perf_counter()
            stream = await open_stream(prompt)
            tokens = [await stream.__anext__()]
            ttfts.append(time.perf_counter() - t0)
            async for token in stream:
                tokens.append(token)
            outs.append(tokens)
        elapsed = time.perf_counter() - start
        total = sum(len(o) for o in outs)
        ttfts.sort()
        return (outs, total / elapsed if elapsed else None,
                ttfts[len(ttfts) // 2] * 1000.0)

    async def run_monolithic():
        engine = build(True)
        await engine.start()
        try:
            # warm pass compiles the executable family off the timed path —
            # sequential like the timed loop, so the nb=1 prefill variants
            # the closed loop actually dispatches are the ones compiled
            for prompt in prompts:
                await engine.generate(prompt, max_new_tokens=budget)
            return await drive(
                lambda p: engine.generate_stream(p, max_new_tokens=budget))
        finally:
            await engine.stop()

    async def run_disagg():
        prefill_eng, decode_eng = build(False), build(True)
        # sampled decode-tick anatomy rides the round's artifact: the
        # bench decode path is where the unsampled-tick overhead bound
        # is priced, so phase timings land in the ledger diff
        telemetry = TimeSeriesStore(tick_sample=8)
        decode_eng.attach_telemetry(telemetry, every=telemetry.tick_sample)
        cluster = ClusterRegistry()
        cluster.register("p0", "prefill", InProcTransport(prefill_eng))
        cluster.register("d0", "decode", InProcTransport(decode_eng))
        router = DisaggRouter(cluster)
        await decode_eng.start()        # prefill replica needs no loop
        try:
            for prompt in prompts:      # warm pass: both executable families
                await router.generate(prompt, max_new_tokens=budget)
            result = await drive(
                lambda p: router.generate_stream(p, max_new_tokens=budget))
            # fleet-observability snapshots ride the round's artifact so a
            # regression in the rollup/attribution surfaces shows up in
            # the ledger diff, not just in a failing endpoint later
            fleet = await build_clusterz(cluster, router=router)
            hbm = decode_eng.hbm_attribution()
            timez = {"ticks": telemetry.tick_anatomy(limit=4),
                     "memory": telemetry.memory_info()}
            return result + (router.stats(), decode_eng.stats(), fleet,
                             hbm, timez)
        finally:
            await decode_eng.stop()

    mono_outs, mono_tok_s, mono_ttft_ms = asyncio.run(run_monolithic())
    (dis_outs, dis_tok_s, dis_ttft_ms, router_stats,
     decode_stats, fleet, hbm, timez) = asyncio.run(run_disagg())

    requests = router_stats["requests"] or 1
    return {
        "preset": preset,
        "requests_per_pass": len(prompts),
        "page_tokens": page,
        "data_plane": {"ingest": "in-proc prompt ids",
                       "staging": "per-array uploads (coalescer off)"},
        # determinism contract: greedy streams identical across the split
        "token_identical": mono_outs == dis_outs,
        # zero re-prefill: migrated KV became page-table entries
        "decode_prefill_bucket_tokens": decode_stats[
            "prefill_bucket_tokens"],
        "kv_adoptions": decode_stats["kv_adoptions"],
        "ttft_ms_monolithic": round(mono_ttft_ms, 1),
        "ttft_ms_disagg": round(dis_ttft_ms, 1),
        "decode_tok_s_monolithic": (round(mono_tok_s, 1)
                                    if mono_tok_s else None),
        "decode_tok_s_disagg": round(dis_tok_s, 1) if dis_tok_s else None,
        "transfer_bytes_per_req": round(
            router_stats["bytes_shipped"] / requests),
        "clusterz": {
            "roles": fleet["roles"],
            "router": fleet["router"],
        },
        "hbmz": {
            "params_bytes": hbm["params_bytes"],
            "page_pool_bytes": (hbm["page_pool"] or {}).get("pool_bytes"),
            "staging_bytes": hbm["staging_bytes"],
            "attributed_bytes": hbm["attributed_bytes"],
            "device_bytes_in_use": hbm["device_bytes_in_use"],
            "unattributed_bytes": hbm["unattributed_bytes"],
            "device_seconds": hbm.get("device_seconds"),
        },
        "timez": timez,
        "note": ("in-proc transport: codec + adopt scatter priced, "
                 "network not; disagg TTFT carries the transfer leg. "
                 "Compare monolithic vs disagg within this run, not "
                 "across rounds"),
    }


def _llama_fleet_bench(on_tpu: bool):
    """Fleet control plane (docs/tpu/model-serving.md "Fleet routing,
    migration & autoscaling") on a shared-prefix workload: 3 in-proc
    ``both`` replicas behind a FleetRouter, request groups sharing a
    multi-page prefix, repeats interleaved round-robin. The AFFINITY arm
    refreshes the digest index between requests so repeats route back to
    the replica already holding the prefix; the CONTROL arm never
    refreshes, so every request rides the registry's least-inflight/RR
    fallback and repeats scatter across the fleet. The headline is the
    fleet-wide prefix hit rate (sum of radix-cache hits over lookups
    across every replica) — affinity must read strictly higher, that is
    the routing layer's whole job. Also prices one live mid-stream
    migration (client-visible downtime = export + wire + adopt) and runs
    the autoscaler twice: once against a hot compile ledger (must hold:
    ``compile_guard``) and once quiet (scales up a pre-built replica) —
    no serve-time recompile rides the scale event because the new
    replica takes no traffic the affinity router still maps elsewhere."""
    import time

    import jax

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import llama
    from gofr_tpu.tpu.cluster import ROLE_BOTH, ClusterRegistry, InProcTransport
    from gofr_tpu.tpu.fleet import Autoscaler, FleetRouter
    from gofr_tpu.tpu.generate import GenerationEngine

    if on_tpu:
        preset, max_len, buckets, page, slots = (
            "small", 512, (64, 128), 32, 8)
        prefix_len, tail_len = 96, 8
    else:
        preset, max_len, buckets, page, slots = "tiny", 64, (8, 16), 4, 4
        prefix_len, tail_len = 12, 2
    cfg = llama.config(preset)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    budget = 6

    # 4 prefix groups x 3 repeats, interleaved so consecutive requests
    # never share a prefix — RR placement cannot luck into residency
    groups = [[(37 * g + j) % 250 + 1 for j in range(prefix_len)]
              for g in range(4)]
    workload = [groups[g] + [(11 * g + 7 * r + k) % 250 + 1
                             for k in range(tail_len)]
                for r in range(3) for g in range(4)]

    def build():
        container = new_mock_container()
        return GenerationEngine(
            cfg, params, max_slots=slots, max_len=max_len,
            prompt_buckets=buckets, kv_page=page, paged_kv=True,
            prefix_cache=True, steps_per_tick=4,
            logger=container.logger, metrics=container.metrics)

    def hit_rate(engines):
        hits = total = 0
        for engine in engines.values():
            lookups = engine.stats().get("prefix_cache", {}).get(
                "lookups", {})
            hits += lookups.get("hit", 0) + lookups.get("partial", 0)
            total += lookups.get("total", 0)
        return hits / total if total else 0.0

    async def arm(affinity):
        engines = {name: build() for name in ("d0", "d1", "d2")}
        cluster = ClusterRegistry()
        for name, engine in engines.items():
            cluster.register(name, ROLE_BOTH, InProcTransport(engine))
        router = FleetRouter(cluster)
        for engine in engines.values():
            await engine.start()
        try:
            start = time.perf_counter()
            total = 0
            for prompt in workload:
                out = await asyncio.wait_for(router.generate(
                    prompt, max_new_tokens=budget), 60.0)
                total += len(out)
                if affinity:
                    await router.refresh()
            elapsed = time.perf_counter() - start
            result = {
                "prefix_hit_rate": round(hit_rate(engines), 4),
                "tok_s": round(total / elapsed, 1) if elapsed else None,
                "routing": dict(router.fleet_stats()["routing"]),
            }
            if not affinity:
                return result

            # one live migration, priced end to end: the downtime the
            # client could observe is export + pack/chunk + adopt
            session = await router.generate_stream(
                workload[0], max_new_tokens=16)
            tokens = [await asyncio.wait_for(session.__anext__(), 60.0)
                      for _ in range(2)]
            t0 = time.perf_counter()
            target = await router.migrate_session(session)
            downtime_ms = (time.perf_counter() - t0) * 1000.0
            async for token in session:
                tokens.append(token)
            result["migration"] = {
                "downtime_ms": round(downtime_ms, 2),
                "tokens_delivered": len(tokens),
                "target": target,
                "target_session_adoptions": engines[target].stats()[
                    "session_adoptions"],
            }

            # autoscaler: a hot ledger must hold the scale event; a
            # quiet one admits the pre-built replica. Neither path
            # touches a serving executable — the guard exists so a
            # scale step can never pile onto a recompile storm.
            class _Ledger:
                def __init__(self, n):
                    self.n = n

                def serving_compiles(self, window_s):
                    return self.n

            spare = build()

            async def grow():
                await spare.start()
                cluster.register("d3", ROLE_BOTH, InProcTransport(spare))

            events = []
            for ledger in (_Ledger(1), _Ledger(0)):
                scaler = Autoscaler(
                    cluster, scale_up=grow, scale_down=lambda name: None,
                    router=router, compile_ledger=ledger,
                    up_after=1, cooldown_s=0.0, max_decode=4,
                    signals_fn=lambda: {"queue_depth": 99,
                                        "decode_replicas": 3})
                events.append((await scaler())["result"])
            post = await asyncio.wait_for(router.generate(
                workload[0], max_new_tokens=budget), 60.0)
            engines["d3"] = spare
            result["autoscale"] = {
                "events": events,
                "post_scale_tokens": len(post),
            }
            return result
        finally:
            for engine in engines.values():
                await engine.stop()

    control = asyncio.run(arm(affinity=False))
    affinity = asyncio.run(arm(affinity=True))

    return {
        "preset": preset,
        "requests_per_arm": len(workload),
        "prefix_pages": prefix_len // page,
        "prefix_hit_rate_affinity": affinity["prefix_hit_rate"],
        "prefix_hit_rate_rr": control["prefix_hit_rate"],
        # the acceptance bar: routing by residency must beat rotation
        "affinity_beats_rr": (affinity["prefix_hit_rate"]
                              > control["prefix_hit_rate"]),
        "decode_tok_s_affinity": affinity["tok_s"],
        "decode_tok_s_rr": control["tok_s"],
        "routing_affinity": affinity["routing"],
        "routing_rr": control["routing"],
        "migration": affinity["migration"],
        "autoscale": affinity["autoscale"],
        "note": ("in-proc fleet: the hit-rate spread is the routing "
                 "signal, the tok/s spread mostly amortized dispatch — "
                 "compare arms within this run, not across rounds; "
                 "migration downtime is export + wire + adopt on the "
                 "host, no network priced"),
    }


def _llama_chaos_bench(on_tpu: bool):
    """Chaos plane (docs/tpu/model-serving.md "Failure semantics"): what
    a mid-stream decode-replica death actually costs the client. Two
    arms on an identical 3-replica in-proc fleet and workload: the
    CONTROL arm streams every request undisturbed; the CHAOS arm arms a
    seeded ``crash_mid_decode`` plan per request (nth-token varies
    across requests so the crash lands at different decode depths) and
    lets the router's resumable-decode path heal each one. Priced:

    - ``goodput_ratio`` — chaos-arm tok/s over control tok/s, the
      steady-state throughput tax of recovery (re-prefill of
      prompt+emitted on the resume target rides inside the timed
      window);
    - ``resume_downtime_ms`` — median over requests of the largest
      inter-token gap, i.e. the stall the client saw around the crash
      (the control arm's ``max_gap_ms`` is the no-fault baseline for
      the same statistic);
    - ``exactly_once`` — every healed stream delivers its full budget
      with the pre-crash prefix matching the control arm exactly (no
      duplicated, no missing token index), and every page pool drains
      back to its free-list baseline. Those are the acceptance bar; a
      fast recovery that corrupts a stream or leaks pages is a
      regression, not a win.

    ``identical_streams`` counts full token-for-token matches. It can
    sit below ``requests`` without a bug: the resume re-prefills
    prompt+emitted, and when two logits are EXACTLY tied (the tiny
    bf16 bench model produces real ties) the prefill and decode paths
    may break the argmax differently — identity is guaranteed in exact
    arithmetic, prefix identity plus full budget is the hard
    invariant."""
    import time

    import jax

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import llama
    from gofr_tpu.tpu import faults
    from gofr_tpu.tpu.cluster import ROLE_BOTH, ClusterRegistry, InProcTransport
    from gofr_tpu.tpu.fleet import FleetRouter
    from gofr_tpu.tpu.generate import GenerationEngine

    if on_tpu:
        preset, max_len, buckets, page, slots = (
            "small", 512, (64, 128), 32, 8)
        prompt_len = 24
    else:
        preset, max_len, buckets, page, slots = "tiny", 64, (8, 16), 4, 4
        prompt_len = 6
    cfg = llama.config(preset)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    budget, n_requests = 12, 6
    prompts = [[(13 * i + 5 * j) % 250 + 1 for j in range(prompt_len)]
               for i in range(n_requests)]

    def build():
        container = new_mock_container()
        return GenerationEngine(
            cfg, params, max_slots=slots, max_len=max_len,
            prompt_buckets=buckets, kv_page=page, paged_kv=True,
            steps_per_tick=4,
            logger=container.logger, metrics=container.metrics)

    async def arm(chaos):
        engines = {name: build() for name in ("d0", "d1", "d2")}
        cluster = ClusterRegistry()
        for name, engine in engines.items():
            cluster.register(name, ROLE_BOTH, InProcTransport(engine))
        router = FleetRouter(cluster)
        for engine in engines.values():
            await engine.start()
        try:
            baseline = {n: e._pool.free_pages for n, e in engines.items()}
            outs, max_gaps_ms, total = [], [], 0
            start = time.perf_counter()
            for i, prompt in enumerate(prompts):
                if chaos:
                    # vary the crash depth so recovery is priced across
                    # early/late kills, not one lucky token index
                    faults.install(faults.FaultPlan(
                        f"crash_mid_decode:@{3 + i % 5}", seed=i))
                try:
                    session = await router.generate_stream(
                        prompt, max_new_tokens=budget)
                    tokens, max_gap = [], 0.0
                    last = time.perf_counter()
                    async for token in session:
                        now = time.perf_counter()
                        max_gap = max(max_gap, now - last)
                        last = now
                        tokens.append(token)
                finally:
                    faults.reset()
                outs.append(tokens)
                max_gaps_ms.append(max_gap * 1000.0)
                total += len(tokens)
            elapsed = time.perf_counter() - start

            deadline = time.perf_counter() + 10.0
            while {n: e._pool.free_pages
                   for n, e in engines.items()} != baseline:
                if time.perf_counter() > deadline:
                    break
                await asyncio.sleep(0.05)
            pages_restored = {n: e._pool.free_pages
                              for n, e in engines.items()} == baseline
            gaps = sorted(max_gaps_ms)
            return {
                "outs": outs,
                "tok_s": round(total / elapsed, 1) if elapsed else None,
                "max_gap_ms": round(gaps[len(gaps) // 2], 2),
                "resumes": dict(router.fleet_stats()["resumes"]),
                "pages_restored": pages_restored,
            }
        finally:
            for engine in engines.values():
                await engine.stop()

    control = asyncio.run(arm(chaos=False))
    chaos = asyncio.run(arm(chaos=True))

    goodput = None
    if control["tok_s"] and chaos["tok_s"]:
        goodput = round(chaos["tok_s"] / control["tok_s"], 3)
    exactly_once = all(
        len(healed) == budget
        and healed[:3 + i % 5 - 1] == ref[:3 + i % 5 - 1]
        for i, (ref, healed) in enumerate(zip(control["outs"],
                                              chaos["outs"])))
    identical = sum(ref == healed for ref, healed
                    in zip(control["outs"], chaos["outs"]))
    return {
        "preset": preset,
        "requests": n_requests,
        "budget": budget,
        "decode_tok_s_control": control["tok_s"],
        "decode_tok_s_chaos": chaos["tok_s"],
        "goodput_ratio": goodput,
        "resume_downtime_ms": chaos["max_gap_ms"],
        "max_gap_ms_control": control["max_gap_ms"],
        # acceptance: recovery must be invisible in CONTENT even while
        # it costs time — full budget, exact pre-crash prefix, no leaks
        "exactly_once": exactly_once,
        "identical_streams": identical,
        "resumes": chaos["resumes"],
        "pages_restored": (control["pages_restored"]
                           and chaos["pages_restored"]),
        "note": ("in-proc fleet: downtime is re-admission + re-prefill "
                 "of prompt+emitted on the resume target, no network or "
                 "failure-detection latency priced — compare the chaos "
                 "arm against control from the SAME run, not across "
                 "rounds; identical_streams < requests without "
                 "exactly_once=false means exact-logit-tie argmax "
                 "flips at the re-prefill, not lost or duplicated "
                 "tokens"),
    }


def _llama_replay_bench(on_tpu: bool):
    """Workload capture & replay plane (ISSUE 17, docs/quick-start/
    observability.md "Workload capture & replay"): record a live
    class-mixed workload shape-only, export the versioned trace, then
    replay it twice through fresh engines on the virtual clock. Priced:

    - ``deterministic`` — 1 iff both replays produced identical
      admitted-token counts, per-class outcome tallies, and digests.
      This is the ISSUE 17 acceptance bar and the property that makes
      a recorded trace a usable A/B harness for knob changes; asserted
      in-artifact, a 0 here fails the round.
    - ``attribution_gap_pct`` — |per-family executable-ledger total −
      per-class aggregate device-seconds| as a percentage of the
      aggregate, from the capture arm's engine. Both planes charge from
      the same dispatch-site helper, so the acceptance bar is <= 10%
      (asserted in-artifact).
    - ``replay_tok_s`` — delivered tok/s of the first replay arm, the
      throughput of the replay harness itself (compare within a round,
      it rides host load like every CPU-bench number)."""
    import time

    import jax

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import llama
    from gofr_tpu.slo import set_request_deadline
    from gofr_tpu.tpu.generate import GenerationEngine
    from gofr_tpu.tpu.workload import (TrafficRecorder, load_trace,
                                       replay_trace)

    if on_tpu:
        preset, max_len, buckets, page, slots = (
            "small", 512, (64, 128), 32, 8)
        prompt_len = 24
    else:
        preset, max_len, buckets, page, slots = "tiny", 64, (8, 16), 4, 4
        prompt_len = 6
    cfg = llama.config(preset)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    n_requests, budget = 8, 6
    # class mix via deadline budgets: <=2s → interactive, larger →
    # standard, None → batch (sched.deadline_class)
    budgets_ms = [1500, None, 30000, 1500, None, 30000, 1500, None]
    prompts = [[(7 * i + 3 * j) % 250 + 1 for j in range(prompt_len)]
               for i in range(n_requests)]

    def build():
        container = new_mock_container()
        return GenerationEngine(
            cfg, params, max_slots=slots, max_len=max_len,
            prompt_buckets=buckets, kv_page=page, paged_kv=True,
            steps_per_tick=4,
            logger=container.logger, metrics=container.metrics)

    # -- capture arm: live traffic through an instrumented engine -----
    recorder = TrafficRecorder(capacity=256)
    capture_engine = build()

    async def capture():
        await capture_engine.start()
        try:
            async def req(prompt, budget_ms):
                set_request_deadline(budget_ms)
                return await capture_engine.generate(
                    prompt, max_new_tokens=budget)
            # warm the compile ladder deadline-free BEFORE attaching the
            # recorder: first-round compiles dwarf any interactive
            # budget, and the recorded trace should price the workload,
            # not the cold start
            await asyncio.gather(*[req(p, None) for p in prompts])
            capture_engine.attach_workload(recorder)
            await asyncio.gather(*[
                req(p, b) for p, b in zip(prompts, budgets_ms)])
        finally:
            await capture_engine.stop()

    asyncio.run(capture())
    snap = recorder.snapshot()
    # per-family ledger vs per-class aggregate: one charge site, so the
    # totals must agree (ISSUE 17 acceptance: within 10%)
    agg = sum(capture_engine._device_seconds.values())
    fam = capture_engine.exec_ledger.total_seconds(
        capture_engine.model_name)
    gap_pct = round(abs(fam - agg) / agg * 100.0, 3) if agg else None
    assert gap_pct is not None and gap_pct <= 10.0, (fam, agg)
    exec_top = capture_engine.xlaz()["executables"]["top"]

    trace = load_trace(recorder.export_trace())

    # -- replay ×2 on fresh engines: determinism is the acceptance bar
    async def replay_once():
        engine = build()
        await engine.start()
        try:
            start = time.perf_counter()
            result = await replay_trace(engine, trace, time_scale=1.0)
            result["_elapsed_s"] = time.perf_counter() - start
            return result
        finally:
            await engine.stop()

    first = asyncio.run(replay_once())
    second = asyncio.run(replay_once())
    elapsed = first.pop("_elapsed_s")
    second.pop("_elapsed_s")
    deterministic = int(first == second)
    assert deterministic, (first, second)
    assert first["errors"] == 0, first

    return {
        "preset": preset,
        "requests": n_requests,
        "recorded": {
            "class_mix": snap["class_mix"],
            "finish_mix": snap["finish_mix"],
            "mean_interarrival_s": snap["interarrival_s"]["mean"],
        },
        "replay_tok_s": (round(first["admitted_tokens"] / elapsed, 1)
                         if elapsed else None),
        "admitted_tokens": first["admitted_tokens"],
        "per_class": {cls: entry["tokens"]
                      for cls, entry in first["per_class"].items()},
        "digest": first["digest"],
        # acceptance: two replays bit-identical, attribution planes agree
        "deterministic": deterministic,
        "attribution_gap_pct": gap_pct,
        "executable_families": [
            {"family": row["family"], "share": row["share"]}
            for row in exec_top[:4]],
        "note": ("capture arm records shape only (lengths/classes/"
                 "inter-arrivals); replays synthesize prompts of the "
                 "recorded lengths with per-index seeds and decode with "
                 "eos_id=None, so admitted tokens are pinned by the "
                 "trace — compare replay_tok_s within a round only"),
    }


def _llama_sloz_bench(on_tpu: bool):
    """Slow-request diagnosis plane (ISSUE 18, docs/quick-start/
    observability.md "whyz"): induce a queue-wait regression — the same
    request mix run sequentially (no admission contention) and then as
    one concurrent burst into a slot-starved engine — and check the
    worst-offender ring's finish-time verdict blames admission, not the
    device. Priced:

    - ``verdict_names_admission`` — 1 iff the burst arm's worst
      offender's top verdict is ``admission_backlog`` and its cause
      names the admission depth. This is the ISSUE 18 acceptance bar
      (a diagnosis that misattributes a pure queueing regression to
      the model is worse than no diagnosis); asserted in-artifact.
    - ``worst_queue_wait_share`` — queue.wait seconds / e2e of that
      worst offender; the induced regression should push this near 1.
    - ``diagnose_us_per_call`` — the rule table re-run on the captured
      record + a fresh window context; the per-request cost the ring
      pays at finish time (host-only, no device work)."""
    import jax

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import llama
    from gofr_tpu.tpu.diagnose import (WorstOffenders,
                                       build_window_context, diagnose)
    from gofr_tpu.tpu.generate import GenerationEngine

    if on_tpu:
        preset, max_len, buckets, page = "small", 512, (64,), 32
        prompt_len = 24
    else:
        preset, max_len, buckets, page = "tiny", 64, (8,), 4
        prompt_len = 6
    cfg = llama.config(preset)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    n_requests, budget = 12, 6
    prompts = [[(5 * i + 3 * j) % 250 + 1 for j in range(prompt_len)]
               for i in range(n_requests)]

    container = new_mock_container()
    # max_slots=1 is the regression lever: a concurrent burst can only
    # be served one request at a time, so every non-head request's
    # latency is admission wait — exactly the shape whyz must name
    engine = GenerationEngine(
        cfg, params, max_slots=1, max_len=max_len,
        prompt_buckets=buckets, kv_page=page, paged_kv=True,
        steps_per_tick=4, model_name="llama-sloz",
        logger=container.logger, metrics=container.metrics)
    ring = WorstOffenders(
        k=8, window_s=600.0, keep_windows=2,
        context_fn=lambda: build_window_context(engine=engine))

    sequential_s: list = []
    burst = {}

    async def run() -> None:
        await engine.start()
        try:
            # warm the compile ladder so neither arm times a compile
            await engine.generate(prompts[0], max_new_tokens=budget)
            # baseline arm: one request at a time, no contention
            for prompt in prompts:
                t0 = time.perf_counter()
                await engine.generate(prompt, max_new_tokens=budget)
                sequential_s.append(time.perf_counter() - t0)
            # regression arm: the same mix as one burst, diagnosed at
            # finish time by the offender ring
            engine.recorder.offenders = ring
            t0 = time.perf_counter()
            await asyncio.gather(*[
                engine.generate(prompt, max_new_tokens=budget)
                for prompt in prompts])
            burst["elapsed_s"] = time.perf_counter() - t0
        finally:
            await engine.stop()

    asyncio.run(run())
    worst = ring.worst()
    assert worst is not None, "offender ring recorded nothing"
    top = worst["verdicts"][0]
    names_admission = int(top["rule"] == "admission_backlog"
                          and "admission depth" in top["cause"])
    assert names_admission, worst["verdicts"]
    share = (top["phase_s"]["queue.wait"] / worst["e2e_s"]
             if worst["e2e_s"] else None)

    # diagnosis cost: the rule table over the captured record + a fresh
    # window snapshot, the work offer() does once per ring admission
    ctx = build_window_context(engine=engine)
    reps = 200
    t0 = time.perf_counter()
    for _ in range(reps):
        diagnose(worst["record"], ctx)
    diagnose_us = (time.perf_counter() - t0) / reps * 1e6

    sequential_s.sort()
    return {
        "preset": preset,
        "requests": n_requests,
        "sequential_p50_s": round(
            sequential_s[len(sequential_s) // 2], 4),
        "burst_elapsed_s": round(burst["elapsed_s"], 4),
        "worst_e2e_s": worst["e2e_s"],
        "worst_queue_wait_share": (round(share, 3)
                                   if share is not None else None),
        # acceptance: the induced admission regression is named as such
        "verdict_names_admission": names_admission,
        "top_verdict": top["cause"],
        "dominant_phase": top["dominant_phase"],
        "diagnose_us_per_call": round(diagnose_us, 1),
        "note": ("single-slot engine + concurrent burst makes queue.wait "
                 "the dominant phase by construction; judge "
                 "worst_queue_wait_share and the verdict within a run — "
                 "absolute latencies ride host load"),
    }


def _llama_autotune_bench(on_tpu: bool):
    """SLO-driven online auto-tuning (ISSUE 19, docs/tpu/
    model-serving.md "Online auto-tuning"): start an engine on a
    deliberately DETUNED operating point — one oversized prompt bucket
    and unfused ticks, the shape every artifact since r3 flagged as
    ``fits_budget=false`` — record live traffic, then let the
    :class:`AutoTuner` converge by shadow-replay scoring with no human
    input. Priced:

    - ``operating_point`` — the converged point straight from
      ``engine.operating_point()`` (provenance ``source=autotune``,
      generation count), with ``fits_budget`` judged against a
      hand-tuned reference: the converged point's deterministic replay
      score must reach 90% of the score of the knobs a human swept for
      this scale (the r5 method: tight buckets + fused ticks). Asserted
      in-artifact — the closed loop must land within 10% of the hand
      sweep or the round fails.
    - ``serving_compiles`` — serve-time compiles across the WHOLE
      scenario (capture, every apply, post-apply traffic). Prewarm
      charges candidate executables as warmup-class, so the bar is
      staying under ``SLO_MAX_SERVING_COMPILES`` (default 3); asserted
      in-artifact at 0.
    - ``goodput_gain`` — tuned-arm tok/s over detuned-arm tok/s on the
      same live workload, wall-clock. Rides host load on the CPU bench
      container; the stable acceptance number is the score ratio.
    - ``rollback`` — the forced-regression drill: the chaos plane's
      ``autotune.select`` site pushes the WORST candidate through, live
      goodput collapses, and the probation window must re-apply the
      previous point (``source=rollback``) — asserted in-artifact."""
    import time

    import jax

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import llama
    from gofr_tpu.tpu import faults
    from gofr_tpu.tpu.autotune import (AutoTuner, FAULT_SITE_SELECT,
                                       OperatingPoint)
    from gofr_tpu.tpu.faults import FaultPlan
    from gofr_tpu.tpu.generate import GenerationEngine
    from gofr_tpu.tpu.workload import TrafficRecorder

    if on_tpu:
        preset, max_len, slots = "small", 256, 4
        detuned_buckets = (256,)
        hand_tuned = OperatingPoint(prompt_buckets=(32, 64),
                                    steps_per_tick=4)
        prompt_lens = [18 + (i % 14) for i in range(12)]
    else:
        preset, max_len, slots = "tiny", 64, 4
        detuned_buckets = (64,)
        hand_tuned = OperatingPoint(prompt_buckets=(8, 16),
                                    steps_per_tick=4)
        prompt_lens = [3 + (i % 7) for i in range(12)]
    cfg = llama.config(preset)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    container = new_mock_container()
    budget = 6
    prompts = [[(5 * i + 3 * j) % 250 + 1 for j in range(n)]
               for i, n in enumerate(prompt_lens)]

    engine = GenerationEngine(cfg, params, max_slots=slots,
                              max_len=max_len,
                              prompt_buckets=detuned_buckets,
                              steps_per_tick=1,
                              logger=container.logger,
                              metrics=container.metrics)
    recorder = TrafficRecorder(capacity=256)
    engine.attach_workload(recorder)

    async def serve():
        start = time.perf_counter()
        outs = await asyncio.gather(*[
            engine.generate(p, max_new_tokens=budget, eos_id=None)
            for p in prompts])
        elapsed = time.perf_counter() - start
        return sum(len(t) for t in outs) / elapsed

    async def drive():
        out = {}
        await engine.warmup(prompt_counts=(1, 2, 4))
        await engine.start()
        try:
            # -- detuned arm: live traffic builds the evidence trace --
            out["tok_s_detuned"] = await serve()
            assert engine.serving_compiles(window_s=3600.0) == 0, \
                engine.stats()["compiles"]

            goodput = {"value": 100.0}
            tuner = AutoTuner(engine, workload=recorder,
                              logger=container.logger,
                              improve_after=1, cooldown_s=0.0,
                              probation_ticks=1, min_trace_events=8,
                              goodput_fn=lambda: goodput["value"])

            # -- converge: fire until no candidate clears min-gain ----
            firings = 0
            for _ in range(10):
                step = await tuner()
                firings += 1
                if step["result"] not in ("applied", "probation"):
                    break
            assert step["result"] in ("rejected", "hold"), \
                tuner.ledger()[-3:]
            converged = engine.operating_point()
            assert converged["source"] == "autotune", converged
            assert converged["generation"] >= 1, converged
            out["converge_firings"] = firings
            out["converge_applies"] = tuner.status()["applies"]

            # -- tuned arm: same workload on the converged point ------
            out["tok_s_tuned"] = await serve()
            assert engine.serving_compiles(window_s=3600.0) == 0, \
                engine.stats()["compiles"]

            # fits_budget: deterministic replay scores, converged vs
            # the hand-swept reference knobs for this scale
            trace = tuner._load_trace()
            score_tuned = await tuner._score_point(
                OperatingPoint.from_engine(engine), trace)
            score_hand = await tuner._score_point(hand_tuned, trace)
            score_detuned = await tuner._score_point(
                OperatingPoint(prompt_buckets=detuned_buckets,
                               steps_per_tick=1), trace)
            fits = score_tuned >= 0.9 * score_hand
            assert fits, (score_tuned, score_hand)
            out["operating_point"] = dict(converged,
                                          fits_budget=bool(fits))
            out["score_detuned"] = round(score_detuned, 5)
            out["score_tuned"] = round(score_tuned, 5)
            out["score_hand_tuned"] = round(score_hand, 5)
            out["score_vs_hand_tuned"] = round(
                score_tuned / score_hand, 3) if score_hand else None

            # -- forced-regression drill: rollback must fire ----------
            faults.install(FaultPlan(FAULT_SITE_SELECT))
            try:
                forced = await tuner()
            finally:
                faults.install(None)
            assert forced["result"] == "applied" and forced["forced"], \
                forced
            goodput["value"] = 5.0
            verdict = await tuner()
            assert verdict["result"] == "rolled_back", \
                tuner.ledger()[-3:]
            restored = engine.operating_point()
            assert restored["source"] == "rollback", restored
            assert restored["prompt_buckets"] == \
                converged["prompt_buckets"], (restored, converged)
            assert engine.serving_compiles(window_s=3600.0) == 0, \
                engine.stats()["compiles"]
            out["rollback"] = {
                "forced": 1,
                "rolled_back": 1,
                "restored_matches_tuned": int(
                    restored["prompt_buckets"]
                    == converged["prompt_buckets"]
                    and restored["steps_per_tick"]
                    == converged["steps_per_tick"]),
            }
            out["serving_compiles"] = engine.serving_compiles(
                window_s=3600.0)
            out["warmup_compiles"] = engine.stats()[
                "compiles"]["warmup"]
            out["tuner_results"] = [event["result"]
                                    for event in tuner.ledger()
                                    if event["result"] != "proposed"]
        finally:
            await engine.stop()
        return out

    out = asyncio.run(drive())
    out["goodput_gain"] = (round(out["tok_s_tuned"]
                                 / out["tok_s_detuned"], 3)
                           if out["tok_s_detuned"] else None)
    out["tok_s_detuned"] = round(out["tok_s_detuned"], 1)
    out["tok_s_tuned"] = round(out["tok_s_tuned"], 1)
    # acceptance bar: stay under the compile-watchdog budget throughout
    out["max_serving_compiles"] = 3      # SLO_MAX_SERVING_COMPILES
    assert out["serving_compiles"] <= out["max_serving_compiles"], out
    return {
        "preset": preset,
        "requests": len(prompts),
        "detuned_buckets": list(detuned_buckets),
        **out,
        "note": ("goodput_gain is wall-clock on the CPU bench "
                 "container and rides host load; the acceptance "
                 "number is score_vs_hand_tuned (deterministic "
                 "replay scores, bar >= 0.9) — the controller must "
                 "land within 10% of the hand-swept knobs with no "
                 "human input, then survive the forced-regression "
                 "rollback drill"),
    }


def _llama_speculative_bench(on_tpu: bool):
    """Draft-verify speculative decode vs a target-only control on the
    SAME config and workload (docs/tpu/model-serving.md "Speculative
    decode"), in speculation's home regime: single-stream latency-bound
    decode, where the control commits ONE token per dispatch round trip
    and a spec tick commits up to γ+1 in two dispatches (draft scan +
    batched verify). The draft here is the target itself — a perfect
    draft — so acceptance sits at ~1.0 and the scenario isolates the
    mechanism gain; with a genuinely cheaper draft the compute saving
    stacks on top, while at high batch the control amortizes dispatch
    across slots and the gap narrows (that regime is the paged/7B
    scenarios' job). float32 so greedy outputs stay comparable across
    the two engines (bf16 near-ties flip argmax between the one-token
    and batched-verify matmuls)."""
    import time

    import jax
    import jax.numpy as jnp

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import llama
    from gofr_tpu.tpu.generate import GenerationEngine

    preset = "small" if on_tpu else "tiny"
    max_len, buckets = (256, (16, 32)) if on_tpu else (128, (8, 16))
    cfg = llama.config(preset, dtype=jnp.float32)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    gamma = 4
    prompts = [[(11 * i + j) % 250 + 1 for j in range(6 + i % 5)]
               for i in range(4)]
    budget = 48

    def build(spec):
        container = new_mock_container()
        kwargs = dict(draft_cfg=cfg, draft_params=params,
                      spec_gamma=gamma) if spec else {}
        return GenerationEngine(
            cfg, params, max_slots=1, max_len=max_len,
            prompt_buckets=buckets,
            logger=container.logger, metrics=container.metrics, **kwargs)

    async def drive(engine):
        await engine.start()
        try:
            # warm pass compiles the executable family off the timed path
            for p in prompts:
                await engine.generate(p, max_new_tokens=budget)
            outs = []
            start = time.perf_counter()
            for p in prompts:     # sequential: single-stream latency
                outs.append(await engine.generate(p, max_new_tokens=budget))
            elapsed = time.perf_counter() - start
            stats = engine.stats()
        finally:
            await engine.stop()
        tokens = sum(len(o) for o in outs)
        return outs, tokens / elapsed if elapsed else None, stats

    ctrl_outs, ctrl_tok_s, _ = asyncio.run(drive(build(False)))
    spec_outs, spec_tok_s, spec_stats = asyncio.run(drive(build(True)))

    spec = spec_stats.get("speculative", {})
    return {
        "preset": preset,
        "gamma": gamma,
        "data_plane": {"ingest": "in-proc prompt ids",
                       "staging": "per-array uploads (coalescer off)"},
        "requests_per_pass": len(prompts),
        # determinism contract: greedy spec == greedy target-only (f32)
        "token_identical": spec_outs == ctrl_outs,
        "decode_tok_s_spec": round(spec_tok_s, 1) if spec_tok_s else None,
        "decode_tok_s_control": (round(ctrl_tok_s, 1)
                                 if ctrl_tok_s else None),
        "spec_above_control": bool(spec_tok_s and ctrl_tok_s
                                   and spec_tok_s > ctrl_tok_s),
        "acceptance_rate": spec.get("acceptance_rate"),
        "spec_ticks": spec.get("spec_ticks"),
        "tokens_proposed": spec.get("proposed"),
        "tokens_accepted": spec.get("accepted"),
        "gamma_cap_at_end": spec.get("gamma_cap"),
        "note": ("single-stream latency regime; perfect draft (draft == "
                 "target) isolates the dispatch mechanism: γ+1 tokens "
                 "per two dispatches vs one dispatch per token. Compare "
                 "spec vs control within this run, not across rounds; a "
                 "real deployment's gain also depends on draft quality "
                 "(acceptance_rate) and the draft/target size ratio"),
    }


def _multi_model_bench(on_tpu: bool):
    """Two co-resident models on ONE shared KV page pool, driven through
    the ModelRegistry with mixed SLO classes (docs/tpu/model-serving.md
    "Model registry"). Both engines draw pages from the same literal
    PagePool — the tenancy the registry arbitrates — while interactive
    (deadline-carrying) and batch (deadline-free) requests land on each.
    Reports per-model goodput under contention, per-class served counts
    across both engines, and the shared pool's end-state occupancy."""
    import time

    import jax

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import llama
    from gofr_tpu.slo import set_request_deadline
    from gofr_tpu.tpu.generate import GenerationEngine
    from gofr_tpu.tpu.page_pool import PagePool
    from gofr_tpu.tpu.registry import ModelRegistry

    if on_tpu:
        preset, max_len, buckets, page = "small", 256, (16, 32), 32
    else:
        preset, max_len, buckets, page = "tiny", 64, (8, 16), 8
    slots = 4
    cfg = llama.config(preset)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    # pool sized for both tenants' worst case — contention shows up as
    # occupancy, not stalls, so goodput stays attributable
    num_pages = 2 * slots * (max_len // page)
    prompts = [[(5 * i + j) % 250 + 1 for j in range(5 + i % 4)]
               for i in range(8)]
    budget = 8

    container = new_mock_container()
    pool = PagePool(cfg, page=page, num_pages=num_pages,
                    metrics=container.metrics)
    registry = ModelRegistry(page_pool=pool, logger=container.logger,
                             metrics=container.metrics)
    kw = dict(max_slots=slots, max_len=max_len, prompt_buckets=buckets,
              paged_kv=True, kv_page=page, page_pool=pool,
              logger=container.logger, metrics=container.metrics)
    registry.register("big", GenerationEngine(cfg, params,
                                              model_name="big", **kw),
                      fallback="cheap", default=True)
    registry.register("cheap", GenerationEngine(cfg, params,
                                                model_name="cheap", **kw))

    async def drive():
        await registry.start()
        try:
            async def one(name, prompt, interactive):
                engine = registry.route(name)
                if interactive:
                    set_request_deadline(1500.0)
                try:
                    return name, await engine.generate(
                        prompt, max_new_tokens=budget)
                finally:
                    set_request_deadline(None)

            async def one_pass():
                return await asyncio.gather(*[
                    one(("big", "cheap")[i % 2], p,
                        interactive=(i % 4 == 0))
                    for i, p in enumerate(prompts)])

            # warm pass: identical shape to the timed pass, so both
            # engines compile their full executable families (page-width
            # variants included) off the clock
            await one_pass()
            start = time.perf_counter()
            results = await one_pass()
            elapsed = time.perf_counter() - start
            stats = registry.stats()
        finally:
            await registry.stop()
        return results, elapsed, stats

    results, elapsed, stats = asyncio.run(drive())
    tokens = {"big": 0, "cheap": 0}
    for name, out in results:
        tokens[name] += len(out)
    served = {}
    for model in stats["models"].values():
        per_class = model.get("stats", {}).get("classes", {})
        for cls, count in per_class.get("served", {}).items():
            served[cls] = served.get(cls, 0) + count
    pool_stats = stats.get("shared_pool", {})
    total = sum(tokens.values())
    return {
        "preset": preset,
        "requests_per_pass": len(prompts),
        "data_plane": {"ingest": "in-proc prompt ids",
                       "staging": "per-array uploads (coalescer off)"},
        "aggregate_tok_s": round(total / elapsed, 1) if elapsed else None,
        "tok_s_big": (round(tokens["big"] / elapsed, 1)
                      if elapsed else None),
        "tok_s_cheap": (round(tokens["cheap"] / elapsed, 1)
                        if elapsed else None),
        "served_by_class": served,
        "fallbacks_taken": stats.get("fallbacks_taken"),
        "pool_pages": pool_stats.get("num_pages"),
        "pool_occupancy_at_end": pool_stats.get("occupancy"),
        "pool_stalls": pool_stats.get("stalls"),
        "note": ("two engines, one literal PagePool, mixed deadline "
                 "classes through the registry; per-model tok/s shares "
                 "one wall clock (goodput under contention). Compare "
                 "models within this run, not across rounds"),
    }


def _llama_batch_lane_bench(on_tpu: bool):
    """Async batch lane (docs/tpu/model-serving.md "Batch lane") riding
    an interactive workload, vs an interactive-only control on the same
    engine geometry. A queue of pub/sub jobs drips through the WFQ
    ``batch`` class while waves of deadline-carrying requests run in the
    foreground; the scenario reports how many batch tokens the lane
    soaked out of the same wall clock and the interactive goodput ratio
    against the control — the lane's acceptance bar is that the ratio
    stays within 5% of 1.0."""
    import time

    import jax

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.datasource.pubsub.inmem import InMemoryBroker
    from gofr_tpu.models import llama
    from gofr_tpu.slo import DeadlineExceeded, set_request_deadline
    from gofr_tpu.tpu.batch_lane import BatchLane
    from gofr_tpu.tpu.generate import GenerationEngine

    if on_tpu:
        preset, max_len, buckets, slots = "small", 256, (16, 32), 8
        groups, conc, jobs = 6, 5, 48
    else:
        preset, max_len, buckets, slots = "tiny", 64, (8, 16), 6
        groups, conc, jobs = 4, 4, 24
    budget = 8
    think_s = 0.1   # inter-wave gap: the idle ticks batch exists to soak
    cfg = llama.config(preset)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    prompts = [[(5 * i + j) % 250 + 1 for j in range(buckets[i % 2] - 2)]
               for i in range(conc)]
    sheds = {"count": 0}

    def build():
        container = new_mock_container()
        engine = GenerationEngine(
            cfg, params, max_slots=slots, max_len=max_len,
            prompt_buckets=buckets, steps_per_tick=4,
            logger=container.logger, metrics=container.metrics)
        return container, engine

    async def interactive_one(engine, prompt):
        # a fresh ~2 s budget at submit classifies as `interactive`
        set_request_deadline(2000.0)
        try:
            return await engine.generate(prompt, max_new_tokens=budget)
        except DeadlineExceeded:
            sheds["count"] += 1
            return []

    async def interactive_load(engine):
        # open-ish loop: waves separated by think time, concurrency held
        # under the slot count — the duty-cycle shape real interactive
        # traffic has, and the idle capacity the lane is meant to soak
        tokens = 0
        start = time.perf_counter()
        for _ in range(groups):
            outs = await asyncio.gather(*[
                interactive_one(engine, p) for p in prompts])
            tokens += sum(len(o) for o in outs)
            await asyncio.sleep(think_s)
        return tokens, time.perf_counter() - start

    # mixed admission coalesces interactive and batch prompts into one
    # prefill dispatch, so row counts up to conc+1 (the waves plus the
    # lane's one in-flight job) all occur — warm every one of them, in
    # both runs, or the first mixed wave eats a prefill_batch compile
    # the control never pays
    warm_counts = tuple(range(1, conc + 2))

    async def control():
        _, engine = build()
        await engine.warmup(prompt_counts=warm_counts)
        await engine.start()
        try:
            await asyncio.gather(*[   # warm the serving path end to end
                engine.generate(p, max_new_tokens=budget) for p in prompts])
            tokens, elapsed = await interactive_load(engine)
        finally:
            await engine.stop()
        return tokens / elapsed if elapsed else None

    async def mixed():
        container, engine = build()
        broker = InMemoryBroker(container.logger, container.metrics)
        lane = BatchLane(engine, broker, "bench.jobs", max_inflight=1,
                         default_max_new_tokens=budget,
                         logger=container.logger,
                         metrics=container.metrics)
        await engine.warmup(prompt_counts=warm_counts)
        await engine.start()
        try:
            await asyncio.gather(*[
                engine.generate(p, max_new_tokens=budget) for p in prompts])
            await lane.start()
            # pull two jobs through the lane itself before the timed
            # window — the batch class's first trip through prefill/
            # insert is the lane's compile bill, not its steady state
            for i in range(2):
                broker.publish("bench.jobs", json.dumps(
                    {"id": f"warm-{i}",
                     "prompt_ids": [7 + i] * (buckets[0] - 2),
                     "max_new_tokens": budget}).encode())
            deadline = time.perf_counter() + 120
            while lane.jobs_ok < 2 and time.perf_counter() < deadline:
                await asyncio.sleep(0.02)
            for i in range(jobs):   # queue outlives the timed window
                broker.publish("bench.jobs", json.dumps(
                    {"id": f"job-{i}",
                     "prompt_ids": [(3 * i + j) % 250 + 1
                                    for j in range(buckets[0] - 2)],
                     "max_new_tokens": budget}).encode())
            before = lane.jobs_ok
            tokens, elapsed = await interactive_load(engine)
            soaked = lane.jobs_ok - before
            stats = engine.stats().get("classes", {}).get("served", {})
        finally:
            await lane.stop()
            await engine.stop()
        tok_s = tokens / elapsed if elapsed else None
        batch_tok_s = soaked * budget / elapsed if elapsed else None
        return tok_s, batch_tok_s, soaked, stats

    control_tok_s = asyncio.run(control())
    mixed_tok_s, batch_tok_s, soaked, served = asyncio.run(mixed())
    ratio = (round(mixed_tok_s / control_tok_s, 3)
             if control_tok_s and mixed_tok_s else None)
    return {
        "preset": preset,
        "interactive_waves": groups,
        "interactive_concurrency": conc,
        "batch_jobs_queued": jobs,
        "data_plane": {"ingest": "in-mem broker JSON jobs",
                       "staging": "per-array uploads (coalescer off)"},
        "interactive_tok_s_control": (round(control_tok_s, 1)
                                      if control_tok_s else None),
        "interactive_tok_s_mixed": (round(mixed_tok_s, 1)
                                    if mixed_tok_s else None),
        # the acceptance bar: >= 0.95 means batch rode idle ticks, not
        # the interactive lane's slots
        "interactive_goodput_ratio": ratio,
        "batch_tok_s_soaked": (round(batch_tok_s, 1)
                               if batch_tok_s else None),
        "batch_jobs_completed_in_window": soaked,
        "interactive_sheds": sheds["count"],
        "served_by_class": served,
        "note": ("same interactive workload with and without the lane "
                 "draining a batch-job queue behind it; the ratio is the "
                 "interference price (WFQ should hold it near 1.0), the "
                 "soak is free throughput. Compare within this run, not "
                 "across rounds"),
    }


def _llama7b_int8_bench(on_tpu: bool):
    """BASELINE.md config 5 at its stated scale: Llama-2-7B geometry,
    int8 weight-only (6.7 GB — fits one ~16 GB v5e chip with the KV
    cache), continuous-batching decode. Weights are random int8 generated
    leaf by leaf on the device (llama.init_int8 — decode throughput
    depends only on layout). Reports aggregate tok/s
    and the fraction of the HBM-bandwidth roofline achieved.

    r5 operating point (measured sweep over slots {16,24,32,40,48,56,64}
    x K {16,32,64} x max_len {256,512}): **56 slots x K=32 fused steps,
    max_len 256, full-window attention, falling back to 48 slots when
    HBM headroom is tight** — device-only 2519 tok/s (56) / 2343 (48) at
    ~0.78 of the HBM roofline, vs r4's 16x16@512 at 730 tok/s / 0.428.
    What moved: (1) K=32 drops per-step overhead 21.9→20.5 ms/step at
    48 slots (14.1 at 16 slots) by amortizing per-tick cost inside the
    scan; (2) 3.5x slots amortize the 6.16 GB weight stream per step.
    Post-mortems from the sweep: 56 slots leaves <2 GB HBM headroom
    (64 fails to compile outright), hence the try-56-fall-back-to-48;
    K=64 measured no better than K=32 (17.2 vs 17.4 ms/step @32 slots);
    the fill-bounded 128 window at K=32/48 slots measured 29.4 ms/step
    vs 20.5 full-window — the windowed dynamic-slice gather breaks XLA's
    cache-read pipelining at this scale, so full-window wins at
    max_len 256 and the roofline counts the full cache honestly.
    The KV cache stays bf16: int8-KV was built and measured ~12% slower
    through plain XLA (the dequant convert un-fuses — see
    LlamaConfig.kv_int8's post-mortem), so it ships as a capacity
    option, not the bench config."""
    if not on_tpu:
        return None
    import jax
    import jax.numpy as jnp

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import llama
    from gofr_tpu.tpu.generate import GenerationEngine

    cfg = llama.config("7b", max_seq_len=1024)
    params = llama.init_int8(cfg)

    # r5 operating point from the measured sweep (docstring): K=32 x
    # max_len=256, full-window attention. 56 slots measured 7% faster
    # than 48 (2516 vs 2343 tok/s) but leaves <2 GB HBM headroom on a
    # 16 GB chip and 64 fails to compile outright — so TRY 56 and fall
    # back to 48 if the allocator says this chip's headroom can't take
    # it. The fallback path is exercised by the same warmup that would
    # OOM, so a failed 56 costs ~1 min, never the run.
    k_steps = 32
    budget = 81     # prefill + 80 decode = K32+K32+K16 ticks

    def leaf_bytes(tree):
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(tree))

    def build(slots):
        container = new_mock_container()
        # window_ladder=False: ONE decode executable (full window) for
        # warmup, the timed run, the device-only chain and the roofline
        # bytes alike. r5 shipped with the ladder on and predicted the
        # run's window from the FINAL fill (16+81, +K = 129 > the 128
        # rung → full) — but the engine picks per dispatch, and
        # dispatch-time fills peak at 17+2*32 = 81 (the last tick needs
        # 81+31 = 112 ≤ 128), so the timed run actually rode a
        # lazily-compiled 128-window executable while device-only and
        # the roofline were computed full-window. The sweep measured
        # full-window faster at this scale anyway (29.4 vs 20.5 ms/step
        # — docstring), so forcing one rung fixes the attribution
        # without moving the operating point.
        engine = GenerationEngine(cfg, params, max_slots=slots,
                                  max_len=256, prompt_buckets=(32,),
                                  steps_per_tick=k_steps,
                                  max_inflight_ticks=6,
                                  window_ladder=False,
                                  logger=container.logger,
                                  metrics=container.metrics)

        async def compile_all():
            await engine.warmup(prompt_counts=(slots,), ks=(16, 32))
        asyncio.run(compile_all())
        return engine

    engine = None
    for slots in (56, 48):
        try:
            engine = build(slots)
            break
        except jax.errors.JaxRuntimeError as exc:
            # the allocator's error only: anything else is a bug to see
            if "RESOURCE_EXHAUSTED" not in str(exc):
                raise
            print(f"# llama7b: {slots} slots did not fit in HBM; "
                  f"falling back", file=sys.stderr)
            engine = None
        # collect OUTSIDE the except block: exc.__traceback__ pins
        # build()'s frame (and the failed engine's multi-GB cache) until
        # the handler exits, so a collect inside it frees nothing
        if engine is None:
            import gc
            gc.collect()
    if engine is None:
        return {"error": "no 7B engine configuration fit this chip"}

    weight_bytes = leaf_bytes({"layers": params["layers"],
                               "head": params["lm_head"]})
    cache_bytes = leaf_bytes(engine.cache)
    # window_ladder=False above: every tick runs the full-window
    # executable, so the roofline counts the FULL cache streamed per
    # step — the same executable warmup compiled and the device-only
    # chain times below (r6 attribution fix; see build())
    step_bytes = weight_bytes + cache_bytes
    hbm_bw = _device_peak("hbm_bytes_s")

    async def run_streams():
        await engine.start()
        # settle = 1 prefill + exactly one K=32 tick: absorbs the one-time
        # first-execution stall (warmup's donated buffers are laid out
        # again on first use)
        # that otherwise lands inside the timed window
        await asyncio.gather(*[
            engine.generate([i + 1] * 16, max_new_tokens=33)
            for i in range(slots)])
        start = time.perf_counter()
        outs = await asyncio.gather(*[
            engine.generate([i + 1] * 16, max_new_tokens=budget)
            for i in range(slots)])
        elapsed = time.perf_counter() - start
        await engine.stop()
        return sum(len(o) for o in outs) / elapsed

    tok_s = asyncio.run(run_streams())

    # device-only rate via two-point slope: time donated chains of 2 and
    # 12 ticks, each ended by an actual token fetch, and take
    # (t12 - t2) / 10 — fixed dispatch/fetch overhead cancels, leaving
    # the per-tick device time.
    fn = engine._decode_fn(k_steps, window=None)
    active = jnp.zeros((engine.max_slots,), bool)
    tokens_dev, cache, cache_len = fn(engine.params, engine.last_token,
                                      engine.cache, engine.cache_len,
                                      active)   # queue warm
    np.asarray(tokens_dev)

    def chain(n):
        nonlocal tokens_dev, cache, cache_len
        t0 = time.perf_counter()
        for _ in range(n):
            tokens_dev, cache, cache_len = fn(
                engine.params, tokens_dev[-1], cache, cache_len, active)
        np.asarray(tokens_dev)       # the fetch is the barrier
        return time.perf_counter() - t0

    slopes = [(chain(12) - chain(2)) / 10 for _ in range(3)]
    slope = float(np.median(slopes))
    device_tick_s = slope if slope > 0 else None   # None = failed measure
    device_tok_s = (engine.max_slots * k_steps / device_tick_s
                    if device_tick_s else None)

    # prefill throughput + the 7B TTFT floor: one batched 256-token
    # prompt forward (pure compute, no cache involvement) timed with the
    # in-executable chain. This is where the MXU earns its keep — and
    # the prompt-processing latency an operator adds to one decode tick
    # to get time-to-first-token at 7B scale.
    prefill_bucket, prefill_nb = 256, 8
    prefill_fn = engine._prefill_fn(prefill_nb, prefill_bucket)

    def prefill_step(p, toks, eps):
        lengths = jnp.full((prefill_nb,), prefill_bucket, jnp.int32)
        zeros_f = jnp.zeros((prefill_nb,), jnp.float32)
        zeros_i = jnp.zeros((prefill_nb,), jnp.int32)
        ones_f = jnp.ones((prefill_nb,), jnp.float32)
        seeds = jnp.zeros((prefill_nb,), jnp.uint32)
        first, _small, _keys = prefill_fn(
            p, toks + eps.astype(jnp.int32), lengths, zeros_f, zeros_i,
            ones_f, seeds)
        return first
    prompt_toks = jnp.ones((prefill_nb, prefill_bucket), jnp.int32)
    prefill_lat, _spread = _chained_device_latency(
        prefill_step, params, prompt_toks, prefill_nb * prefill_bucket,
        reps=3, n=6)    # a ~27-TFLOP step: 6 iterations already ~1.5 s
    prefill = None
    if prefill_lat:
        prefill_tokens = prefill_nb * prefill_bucket
        # 2 FLOPs per param per token (weights dominate at 7B)
        prefill_flops = 2.0 * 6.7e9 * prefill_tokens
        peak = _device_peak("bf16_flops")
        prefill = {
            "bucket": prefill_bucket, "batch": prefill_nb,
            "device_latency_ms": round(prefill_lat * 1e3, 2),
            "prompt_tok_s": round(prefill_tokens / prefill_lat, 1),
            "mfu_est": round(prefill_flops / prefill_lat / peak, 3),
            "ttft_floor_ms": round(
                (prefill_lat + (device_tick_s or 0) / k_steps) * 1e3, 2),
            "note": ("ttft_floor = one batched 256-token prefill + one "
                     "decode step at the operating point; real TTFT adds "
                     "admission wait (measured at llama-small scale in "
                     "llama_small_decode.ttft_under_load)"),
        }

    roofline = engine.max_slots * hbm_bw / step_bytes
    return {"decode_tok_s": round(tok_s, 1),
            "data_plane": {"ingest": "in-proc prompt ids",
                           "staging": "per-array uploads (coalescer off)"},
            "prefill": prefill,
            "roofline_tok_s": round(roofline, 1),
            "roofline_frac": round(tok_s / roofline, 3),
            "device_only_tok_s": round(device_tok_s, 1)
            if device_tok_s else None,
            "device_only_roofline_frac": round(device_tok_s / roofline, 3)
            if device_tok_s else None,
            "device_tick_ms": round(device_tick_s * 1e3, 2)
            if device_tick_s else None,
            "slots": engine.max_slots,
            "steps_per_tick": k_steps,
            "weights_gb": round(weight_bytes / 2**30, 2),
            "kv_cache_gb": round(cache_bytes / 2**30, 2),
            "kv_cache_dtype": "bf16",
            "attention_window": engine.max_len,
            "streamed_bytes_per_step_gb": round(step_bytes / 2**30, 2),
            "note": ("r5 sweep moved the operating point 16x16@512 -> "
                     "56(or 48)xK32@256 full-window: K=32 amortizes "
                     "per-step overhead, 3.5x slots amortize the 6.16 GB "
                     "weight stream; device-only rose 730 -> ~2350-2520 "
                     "tok/s and roofline frac 0.428 -> ~0.78. 56 slots "
                     "is attempted first and falls back to 48 when the "
                     "chip's HBM headroom is tight (post-mortems for "
                     "64-slot, K=64 and windowed variants in the "
                     "function docstring). r6 forces window_ladder=False "
                     "so the timed run executes the same full-window "
                     "executable as warmup/device-only/roofline — r5's "
                     "timed run had silently ridden a cold-compiled "
                     "128-window executable (see build())")}


if __name__ == "__main__":
    main()
