#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that gofr-tpu still starts on the chip.

Run with no arguments, from the root of a checkout, on a machine with a
TPU: ``python3 chip_smoke.py``. One process holds the chip and drives the
system's two hot paths once, through the entry points a user calls, at the
full width of models the repo supports, on seeded random weights:

- *device*    JAX must report a TPU, or the script exits non-zero naming
              what it found. Nothing here swaps in a small model.
- *kernels*   every Pallas kernel the serving path can select, compiled
              (never interpreted) at serving geometry and compared with
              its ops/attention oracle.
- *classify*  ``examples/http-server`` unmodified (ResNet-50), real
              sockets, sequential requests and a concurrent burst.
- *generate*  Llama-2-7B geometry, int8 weights made on the device, paged
              KV, behind a real App with an SSE ``/generate/stream``
              route; twice as many concurrent streams as slots.
- *mesh*      with four or more devices: the same generate path at tp=4
              with weights and pool born sharded, and classify at dp=4.

Each phase prints one JSON line (device as JAX reports it, jax version,
compile-cache directory with hit/miss counts, compile time as set-up
seconds, what was checked). A failed check raises: the exit code is then
non-zero and no result line is printed. The last line of standard output
is ``{"ok": true, "device": {...}}``. No rate or latency is reported:
this script proves the path runs and is right, not how fast it is.

The phase functions take a size so that ``tests/test_chip_smoke.py`` can
call them at ``tiny`` on the CPU; that is also how to rehearse a change
to this file before spending chip time on it.
"""

from __future__ import annotations

import asyncio
import contextlib
import functools
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# every socket read is bounded, so a dead engine fails the run instead of
# hanging it; generous, because the first execution after warmup can stall
READ_TIMEOUT_S = 120.0

SIZES: Dict[str, Dict[str, Any]] = {
    # the real thing: what `python3 chip_smoke.py` runs
    "full": {
        "resnet_preset": "50", "image_hw": 224, "burst": 16,
        "llama_preset": "7b", "llama_overrides": {}, "max_slots": 8,
        "max_len": 512,
        "kv_page": 32, "prompt_buckets": (32, 128), "steps_per_tick": 4,
        "max_new_tokens": 24,
        # kernels: (q_heads, kv_heads) pairs at head_dim 128 — Llama-2-7B
        # MHA and Llama-3-8B GQA
        "heads": ((32, 32), (32, 8)), "head_dim": 128, "flash_seq": 1024,
        "kernel_slots": 8,
        # the window kind of command-a-plus-ep8.mixed: GQA 128:8 (16 query
        # rows a KV head), 32 slots, a table longer than the window
        "window_case": {"heads": (128, 8), "slots": 32, "columns": 192,
                        "window": 4096},
    },
    # CPU rehearsal and the tier-1 test: same code, toy widths
    "tiny": {
        "resnet_preset": "tiny", "image_hw": 32, "burst": 8,
        # 4 kv-heads so that the mesh phase's tp=4 divides them
        "llama_preset": "tiny", "llama_overrides": {"n_kv_heads": 4},
        "max_slots": 2, "max_len": 64,
        "kv_page": 8, "prompt_buckets": (8, 16), "steps_per_tick": 2,
        "max_new_tokens": 6,
        "heads": ((4, 2),), "head_dim": 16, "flash_seq": 32,
        "kernel_slots": 4,
        "window_case": {"heads": (32, 2), "slots": 4, "columns": 12,
                        "window": 40},
    },
}


class SmokeFailure(AssertionError):
    """A check did not hold. Never caught on the smoke's own path."""


def check(condition: bool, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


# -- what every phase line carries -------------------------------------------

class Run:
    """The process-wide facts a phase line reports: the device as JAX sees
    it and the compile cache's directory and hit/miss counts."""

    def __init__(self):
        from gofr_tpu.tpu.compile_cache import configure_compile_cache

        # before the first compile: JAX decides then whether a cache is used
        self.cache_dir = configure_compile_cache()
        import jax

        self.jax = jax
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_listener(self._on_event)
        self.devices = jax.devices()

    def _on_event(self, event: str, **_kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def device(self) -> Dict[str, Any]:
        first = self.devices[0]
        return {"platform": first.platform, "kind": first.device_kind,
                "count": len(self.devices)}

    def emit(self, phase: str, setup_s: Optional[float] = None,
             **details) -> None:
        device = self.device()
        line = {"phase": phase, "ok": True,
                "platform": device["platform"],
                "device_kind": device["kind"],
                "device_count": device["count"],
                "jax": self.jax.__version__,
                "compile_cache": {"dir": self.cache_dir,
                                  "hits": self.cache_hits,
                                  "misses": self.cache_misses}}
        if setup_s is not None:
            line["setup_s"] = round(setup_s, 1)
        line.update(details)
        print(json.dumps(line), flush=True)


def phase_device(run: Run) -> None:
    device = run.device()
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{device['platform']!r} ({device['kind']}, "
                 f"{device['count']} device(s))")
    run.emit("device")


# -- kernels -----------------------------------------------------------------

def _agree(name: str, out, ref) -> Dict[str, Any]:
    """Kernel output against its oracle. Both are bfloat16 tensors whose
    values come from the same bfloat16 inputs; they may differ where f32
    sums ran in another order before a rounding point, and on int8 pools
    where the MXU rounds the f32 ``probs * v_scale`` product and the
    kernel does not. Either moves a result by a few bfloat16 steps
    (2**-7 relative) at the output's magnitude, so the bound is 4 steps
    at the largest oracle value. A wrong mask, page walk or scale moves
    outputs by their whole magnitude — two orders above the bound."""
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    check(out.shape == ref.shape, f"{name}: shape {out.shape} != oracle "
                                  f"{ref.shape}")
    check(bool(np.isfinite(out).all()), f"{name}: non-finite output")
    scale = max(1.0, float(np.abs(ref).max()))
    bound = 4 * 2.0 ** -7 * scale
    err = float(np.abs(out - ref).max())
    check(err <= bound, f"{name}: max |kernel - oracle| {err:.4g} exceeds "
                        f"{bound:.4g} (4 bf16 steps at {scale:.3g})")
    return {"max_abs_err": round(err, 5), "bound": round(bound, 5),
            "equal_share": round(float((out == ref).mean()), 4)}


def _bf16(rng, *shape):
    import jax.numpy as jnp

    return jnp.asarray(rng.standard_normal(shape, np.float32), jnp.bfloat16)


def _paged_case(rng, slots, pages_per_slot, page, kv_heads, q_heads,
                head_dim, g_len, int8, window=None):
    """A pool, a shuffled page table covering mixed fills (empty, one
    token, page boundaries either side, full) and the new tokens. With a
    ``window`` the fills lie either side of it, a slot holds no page
    wholly before its first attended position (the engine gave those
    back), and the positions come back as ``start`` among the scales."""
    import jax.numpy as jnp

    full = pages_per_slot * page
    fills = [0, full, page + 1, full // 2 + 3, 1, page - 1, page, full - 1]
    if window:
        fills += [window - 1, window, window + 1, window + page + 5]
    fills = (fills * (slots // len(fills) + 1))[:slots]
    starts = [max(fill - window + 1, 0) if window else 0 for fill in fills]
    num_pages = slots * pages_per_slot + 1
    order = rng.permutation(num_pages - 1)        # last page: never used
    table = np.full((slots, pages_per_slot), num_pages, np.int32)
    taken = 0
    for slot, (fill, first) in enumerate(zip(fills, starts)):
        first, need = first // page, -(-fill // page)
        table[slot, first:need] = order[taken:taken + need - first]
        taken += need - first

    shape = (num_pages, page, kv_heads, head_dim)
    scales = {}
    if int8:
        k_pages = jnp.asarray(rng.integers(-127, 128, shape, np.int8))
        v_pages = jnp.asarray(rng.integers(-127, 128, shape, np.int8))
        scales = {"k_scale_pages": jnp.asarray(
                      rng.uniform(0.01, 0.03, shape[:-1]), jnp.float32),
                  "v_scale_pages": jnp.asarray(
                      rng.uniform(0.01, 0.03, shape[:-1]), jnp.float32)}
    else:
        k_pages, v_pages = _bf16(rng, *shape), _bf16(rng, *shape)
    new_shape = ((slots, kv_heads, head_dim) if g_len == 1
                 else (slots, g_len, kv_heads, head_dim))
    args = (_bf16(rng, slots, g_len, q_heads, head_dim), k_pages, v_pages,
            jnp.asarray(table), _bf16(rng, *new_shape),
            _bf16(rng, *new_shape), jnp.asarray(fills, jnp.int32))
    if window:
        scales["start"] = jnp.asarray(starts, jnp.int32)
    return args, scales


def phase_kernels(run: Run, size: str, interpret: bool = False) -> None:
    """Compile each Pallas kernel the serving path can select and compare
    it with its oracle. ``interpret`` exists for the CPU rehearsal only;
    the script itself always compiles."""
    import jax

    from gofr_tpu.ops.attention import (paged_decode_attention,
                                        paged_verify_attention,
                                        prefill_attention)
    from gofr_tpu.ops.pallas import (flash_attention,
                                     ragged_paged_decode_attention,
                                     ragged_paged_verify_attention)

    spec = SIZES[size]
    rng = np.random.default_rng(0)
    head_dim, page = spec["head_dim"], spec["kv_page"]
    slots = spec["kernel_slots"]
    pages_per_slot = spec["max_len"] // page
    results: Dict[str, Any] = {}
    started = time.perf_counter()

    def compare(name, kernel, reference, args, kwargs=None):
        kwargs = kwargs or {}
        out = jax.jit(functools.partial(kernel, interpret=interpret,
                                        **kwargs))(*args)
        ref = jax.jit(functools.partial(reference, **kwargs))(*args)
        results[name] = _agree(name, out, ref)

    def one_plane(kernel):
        # the ragged kernels read the stacked (L, ...) pool and take the
        # layer; a case holding one plane is layer 0 of plane[None]
        def call(q, k_pages, v_pages, *rest, interpret, start=None,
                 **scales):
            bound = {} if start is None else {"start": start}
            return kernel(q, k_pages[None], v_pages[None], *rest, 0,
                          interpret=interpret, **bound,
                          **{name: plane[None]
                             for name, plane in scales.items()})
        return call

    for q_heads, kv_heads in spec["heads"]:
        tag = f"{q_heads}:{kv_heads}"
        seq = spec["flash_seq"]
        qkv = [_bf16(rng, 1, seq, heads, head_dim)
               for heads in (q_heads, kv_heads, kv_heads)]
        compare(f"flash_attention {tag}", flash_attention,
                prefill_attention, qkv)
        for int8 in (False, True):
            args, scales = _paged_case(rng, slots, pages_per_slot, page,
                                       kv_heads, q_heads, head_dim, 1, int8)
            compare(f"ragged_decode {tag} {'int8' if int8 else 'bf16'}",
                    one_plane(ragged_paged_decode_attention),
                    paged_decode_attention, args, scales)
        args, scales = _paged_case(rng, slots, pages_per_slot, page,
                                   kv_heads, q_heads, head_dim, 5, False)
        compare(f"ragged_verify {tag} g=5",
                one_plane(ragged_paged_verify_attention),
                paged_verify_attention, args)
    case = spec["window_case"]
    q_heads, kv_heads = case["heads"]
    args, bound = _paged_case(rng, case["slots"], case["columns"], page,
                              kv_heads, q_heads, head_dim, 1, False,
                              window=case["window"])
    compare(f"ragged_decode {q_heads}:{kv_heads} bf16 "
            f"window {case['window']}",
            one_plane(ragged_paged_decode_attention),
            paged_decode_attention, args, bound)
    run.emit("kernels", setup_s=time.perf_counter() - started,
             interpret=interpret, kernels=results)


# -- HTTP over real sockets --------------------------------------------------

async def _request(port: int, method: str, path: str,
                   body: bytes = b"") -> "tuple[int, bytes]":
    """One HTTP/1.1 exchange on a fresh connection, read to EOF with every
    read bounded. Returns (status, de-chunked body)."""
    reader, writer = await asyncio.wait_for(
        asyncio.open_connection("127.0.0.1", port), READ_TIMEOUT_S)
    try:
        writer.write((f"{method} {path} HTTP/1.1\r\nHost: chip-smoke\r\n"
                      f"Connection: close\r\n"
                      f"Content-Type: application/json\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n").encode()
                     + body)
        await writer.drain()
        raw = bytearray()
        while True:
            chunk = await asyncio.wait_for(reader.read(1 << 16),
                                           READ_TIMEOUT_S)
            if not chunk:
                break
            raw.extend(chunk)
    finally:
        writer.close()
    head, _, payload = bytes(raw).partition(b"\r\n\r\n")
    status = int(head.split(b"\r\n", 1)[0].split()[1])
    if b"transfer-encoding: chunked" in head.lower():
        out, rest = bytearray(), payload
        while rest:
            size_line, _, rest = rest.partition(b"\r\n")
            size = int(size_line.split(b";")[0], 16)
            if size == 0:
                break
            out.extend(rest[:size])
            rest = rest[size + 2:]
        payload = bytes(out)
    return status, payload


def _metric(text: str, name: str, **labels) -> float:
    """Sum of a Prometheus sample over every series matching ``labels``."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if not line.startswith(name) or line[len(name)] not in " {":
            continue
        if all(f'{key}="{value}"' in line for key, value in labels.items()):
            total += float(line.rsplit(" ", 1)[1])
            seen = True
    check(seen, f"/metrics has no {name} {labels or ''}")
    return total


# -- classify ----------------------------------------------------------------

def _load_example(name: str):
    path = os.path.join(ROOT, "examples", name, "main.py")
    spec = importlib.util.spec_from_file_location(
        f"chip_smoke_example_{name.replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@contextlib.contextmanager
def _environ(**values: Optional[str]):
    """Set (or, for None, unset) environment variables for the block."""
    def apply(env):
        for key, value in env.items():
            if value is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = value

    saved = {key: os.environ.get(key) for key in values}
    try:
        apply(values)
        yield
    finally:
        apply(saved)


async def _classify(run: Run, size: str, phase: str,
                    mesh: Optional[str]) -> None:
    from gofr_tpu.tpu.compile_ledger import CAUSE_SERVING, CAUSE_WARMUP

    spec = SIZES[size]
    preset = spec["resnet_preset"]
    # the example is driven the way its operator would: by environment.
    # Full width IS its default, so RESNET_PRESET is then left unset.
    with _environ(RESNET_PRESET=None if preset == "50" else preset,
                  TPU_MESH=mesh, LOG_LEVEL="WARN"):
        app = _load_example("http-server").build_app()
    app.http_port = app.metrics_port = 0        # ephemeral
    hw = spec["image_hw"]
    executor = app.container.tpu
    buckets = list(executor._models["resnet50"].buckets)
    warm_s = 0.0

    @app.on_startup
    def warm_buckets():
        nonlocal warm_s
        started = time.perf_counter()
        executor.warmup("resnet50", np.zeros((hw, hw, 3), np.float32))
        warm_s = time.perf_counter() - started

    rng = np.random.default_rng(1)
    images = [rng.standard_normal((hw, hw, 3), np.float32)
              for _ in range(spec["burst"])]
    bodies = [json.dumps({"image": image.tolist()}).encode()
              for image in images]

    def result(status, payload, what):
        check(status == 201, f"classify {what}: status {status}, "
                             f"body {payload[:200]!r}")
        data = json.loads(payload)["data"]
        check(np.isfinite(data["score"]), f"classify {what}: score "
                                          f"{data['score']!r}")
        return data

    await app.start()
    try:
        port = app._http_server.bound_port
        metrics_port = app._metrics_server.bound_port
        alone = [result(*await _request(port, "POST", "/classify", body),
                        f"sequential #{i}")
                 for i, body in enumerate(bodies[:3])]
        burst = await asyncio.gather(*[
            _request(port, "POST", "/classify", body) for body in bodies])
        together = [result(status, payload, f"burst #{i}")
                    for i, (status, payload) in enumerate(burst)]
        _status, scrape = await _request(metrics_port, "GET", "/metrics")
    finally:
        await app.stop()
    for i, (one, many) in enumerate(zip(alone, together)):
        check(one["label"] == many["label"],
              f"classify image {i}: label {one['label']} alone, "
              f"{many['label']} inside a coalesced batch")
    text = scrape.decode()
    executes = _metric(text, "app_tpu_batch_size_count", model="resnet50")
    singles = _metric(text, "app_tpu_batch_size_bucket", model="resnet50",
                      le="1")
    check(executes > singles, f"no coalesced batch: all {executes:.0f} "
                              f"executes had batch size 1")
    serving = executor.ledger.total(CAUSE_SERVING)
    check(serving == 0, f"classify: {serving} serve-time compile(s) after "
                        f"warmup")
    run.emit(phase, setup_s=warm_s, model=f"resnet-{spec['resnet_preset']}",
             mesh=mesh, buckets=buckets,
             requests=len(alone) + len(together),
             executes=int(executes), coalesced_executes=int(executes
                                                            - singles),
             warm_compiles=executor.ledger.total(CAUSE_WARMUP),
             serving_compiles=serving, labels_agree=len(alone))


def phase_classify(run: Run, size: str, mesh: Optional[str] = None) -> None:
    asyncio.run(_classify(run, size, "classify" if mesh is None
                          else "mesh.classify", mesh))


# -- generate ----------------------------------------------------------------

def _counting_logger():
    """The engine's tick loop logs a failed tick and carries on, so the
    smoke looks: every ERROR line is kept and must be absent at the end."""
    from gofr_tpu.logging.logger import Level, Logger

    class CountingLogger(Logger):
        def __init__(self):
            super().__init__(Level.INFO, out=sys.stderr, err=sys.stderr)
            self.errors: List[str] = []

        def logf(self, level, message, *args, **fields):
            if level >= Level.ERROR:
                self.errors.append(str(message) % args if args
                                   else str(message))
            super().logf(level, message, *args, **fields)

    return CountingLogger()


def build_stream_app(engine, container, tokenizer):
    """A real App serving ``POST /generate/stream`` over SSE from
    ``engine`` — the route examples/llama-generate serves, assembled the
    way bench._build_stream_app does: JSON frames per token, then
    ``[DONE]``."""
    from gofr_tpu.app import App
    from gofr_tpu.http.response import Stream

    app = App(config=container.config, container=container)
    app.http_port = app.metrics_port = 0

    async def generate_stream(ctx):
        data = ctx.bind()
        stream = await engine.generate_stream(
            tokenizer.encode(data["prompt"]),
            max_new_tokens=int(data["max_new_tokens"]))

        async def frames():
            try:
                async for token in stream:
                    yield json.dumps({"token": token})
                yield "[DONE]"
            finally:
                await stream.aclose()

        return Stream(frames(), sse=True, on_close=stream.cancel)

    app.post("/generate/stream", generate_stream)
    return app


def _prompts(spec, count: int) -> List[str]:
    """Seeded texts of mixed byte lengths spread over every prompt bucket,
    so admission groups of several sizes form while ticks are in flight."""
    rng = np.random.default_rng(2)
    longest = max(spec["prompt_buckets"])
    lengths = np.linspace(3, longest, count).astype(int)
    rng.shuffle(lengths)
    letters = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz ", np.uint8)
    return [bytes(rng.choice(letters, n)).decode() for n in lengths]


def _hbm(device) -> Optional[Dict[str, int]]:
    stats = device.memory_stats()
    if device.platform == "tpu":
        check(bool(stats), "TPU reports no memory_stats()")
    return stats or None


async def _generate(run: Run, size: str, phase: str, mesh,
                    keep_logits: bool, ref_logits) -> Optional[np.ndarray]:
    import jax
    import jax.numpy as jnp

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import llama
    from gofr_tpu.tokenizer import Tokenizer
    from gofr_tpu.tpu.generate import GenerationEngine

    spec = SIZES[size]
    started = time.perf_counter()
    cfg = llama.config(spec["llama_preset"],
                       max_seq_len=max(spec["max_len"], 128),
                       **spec["llama_overrides"])
    params = llama.init_int8(cfg, mesh=mesh)
    jax.block_until_ready(params)
    weight_bytes = sum(leaf.size * leaf.dtype.itemsize
                       for leaf in jax.tree.leaves(params))
    devices = list(mesh.devices.flat) if mesh is not None \
        else [run.devices[0]]
    # the pool takes what the chip has left, less room for XLA's own
    # temporaries (prefill activations, logits, the gather path's views)
    stats = _hbm(devices[0])
    if stats:
        left = stats["bytes_limit"] - stats["bytes_in_use"]
        pool_bytes = max(left - (2 << 30), left // 2) * len(devices)
    else:
        pool_bytes = 4 << 20                     # CPU rehearsal
    container = new_mock_container()
    container.logger = logger = _counting_logger()
    engine = GenerationEngine(
        cfg, params, mesh=mesh, max_slots=spec["max_slots"],
        max_len=spec["max_len"], prompt_buckets=spec["prompt_buckets"],
        steps_per_tick=spec["steps_per_tick"], max_inflight_ticks=4,
        paged_kv=True, kv_page=spec["kv_page"], kv_pool_bytes=pool_bytes,
        logger=logger, metrics=container.metrics)
    expected = ("ragged" if mesh is None
                and run.devices[0].platform == "tpu" else "gather")
    check(engine.attn_path == expected,
          f"attention path {engine.attn_path!r} ({engine.attn_reason}), "
          f"expected {expected!r}")
    # warm only what the run uses: every admission-count rung (groups of
    # any size up to max_slots form under concurrent arrivals), every
    # k rung, and the window rungs warmup() takes as reachable at start —
    # which must cover the longest prompt plus its whole generation, or a
    # deeper rung would compile on the serving path
    deepest = engine._pick_window(
        [max(spec["prompt_buckets"]) + spec["max_new_tokens"]],
        spec["steps_per_tick"])
    check(deepest in engine._startup_window_rungs(engine._k_ladder),
          f"generation reaches window rung {deepest}, which warmup() "
          f"does not compile")
    await engine.warmup(prompt_counts=tuple(engine._n_ladder))
    setup_s = time.perf_counter() - started

    logits = None
    if keep_logits or ref_logits is not None:
        # first-step logits of one fixed prompt, straight from the model
        tokens = jnp.asarray([list(range(1, 33))], jnp.int32) \
            % cfg.vocab_size
        tokens = tokens[:, :min(spec["prompt_buckets"])]
        logits = np.asarray(jax.jit(
            lambda p, t: llama.prefill(
                p, cfg, t, llama.init_cache(cfg, 1, t.shape[1]))[0]
        )(engine.params, tokens))[0]

    tokenizer = Tokenizer()
    app = build_stream_app(engine, container, tokenizer)
    streams = 2 * spec["max_slots"]
    budget = spec["max_new_tokens"]
    prompts = _prompts(spec, streams)

    def frames_of(status, payload, what) -> List[int]:
        check(status == 200, f"{what}: status {status}, "
                             f"body {payload[:200]!r}")
        events = [line[len(b"data: "):].decode()
                  for block in payload.split(b"\n\n")
                  for line in block.split(b"\n")
                  if line.startswith(b"data: ")]
        check(bool(events) and events[-1] == "[DONE]",
              f"{what}: stream did not end with [DONE]: {events[-2:]}")
        tokens = [json.loads(event)["token"] for event in events[:-1]]
        check(len(tokens) == budget,
              f"{what}: {len(tokens)} frames, expected {budget}")
        check(all(0 <= t < cfg.vocab_size for t in tokens),
              f"{what}: token out of vocabulary: {tokens}")
        return tokens

    def body(prompt):
        return json.dumps({"prompt": prompt,
                           "max_new_tokens": budget}).encode()

    await engine.start()
    await app.start()
    try:
        port = app._http_server.bound_port
        metrics_port = app._metrics_server.bound_port
        once = frames_of(*await _request(port, "POST", "/generate/stream",
                                         body(prompts[0])), "greedy #1")
        again = frames_of(*await _request(port, "POST", "/generate/stream",
                                          body(prompts[0])), "greedy #2")
        check(once == again, f"the same greedy prompt gave {once} then "
                             f"{again}")
        served = await asyncio.gather(*[
            _request(port, "POST", "/generate/stream", body(prompt))
            for prompt in prompts])
        for i, (status, payload) in enumerate(served):
            frames_of(status, payload, f"stream #{i}")
        _status, scrape = await _request(metrics_port, "GET", "/metrics")
        page_stalls = engine.stats()["kv_pool"]["page_stalls"]
    finally:
        await app.stop()
        await engine.stop()
    serving = engine.serving_compiles(window_s=3600.0)
    check(serving == 0, f"generate: {serving} serve-time compile(s) after "
                        f"warmup: {engine._compile_events[-serving:]}")
    check(not logger.errors, f"engine logged errors: {logger.errors[:3]}")
    ttft = _metric(scrape.decode(), "app_tpu_ttft_count")
    check(ttft >= streams + 2, f"app_tpu_ttft_count {ttft:.0f} < "
                               f"{streams + 2} streams")
    details: Dict[str, Any] = {}
    if stats:                        # the backend reports device memory
        in_use = [_hbm(device)["bytes_in_use"] for device in devices]
        check(sum(in_use) >= weight_bytes,
              f"bytes_in_use {sum(in_use)} < weight bytes {weight_bytes}")
        details["bytes_in_use_gb"] = round(sum(in_use) / 2 ** 30, 2)
        if mesh is not None:
            # born sharded: had any leaf been whole on one device first,
            # that device's allocator would still hold more than its share
            check(max(in_use) <= 1.15 * min(in_use),
                  f"per-device bytes_in_use not balanced: {in_use}")
            details["bytes_in_use_per_device"] = in_use
    if ref_logits is not None:
        # tp=4 against one chip on the same seeded weights: the row-
        # parallel matmuls all-reduce bf16 partial sums in another order,
        # which moves a logit by bf16 rounding noise accumulated over the
        # layers — a few percent of the logits' RMS at most. A wrong
        # sharding spec scrambles them entirely (relative error ~1).
        rel = float(np.linalg.norm(logits - ref_logits)
                    / np.linalg.norm(ref_logits))
        check(rel <= 0.05, f"first-step logits differ from the one-chip "
                           f"run: relative L2 error {rel:.4f} > 0.05")
        details["logits_rel_l2_vs_one_chip"] = round(rel, 5)
    run.emit(phase, setup_s=setup_s,
             model=f"llama-{spec['llama_preset']} int8 weights",
             layers=cfg.n_layers, mesh=dict(mesh.shape) if mesh else None,
             attn_path=engine.attn_path, attn_why=engine.attn_reason,
             tokenizer="c++" if tokenizer._native is not None else "python",
             max_slots=engine.max_slots, streams=streams + 2,
             tokens_per_stream=budget, kv_pages=engine._pool.num_pages,
             kv_pool_gb=round(engine._pool.num_pages
                              * engine._pool.page_bytes / 2 ** 30, 2),
             page_stalls=page_stalls,
             weight_gb=round(weight_bytes / 2 ** 30, 2),
             warm_compiles=engine._compiles_by_class["warmup"],
             serving_compiles=serving, failed_ticks=len(logger.errors),
             ttft_count=int(ttft), **details)
    return logits


def phase_generate(run: Run, size: str, keep_logits: bool = False
                   ) -> Optional[np.ndarray]:
    logits = asyncio.run(_generate(run, size, "generate", None,
                                   keep_logits, None))
    gc.collect()          # the engine's HBM goes back before the next phase
    return logits


def phase_mesh(run: Run, size: str, ref_logits: np.ndarray) -> None:
    """tp=4 generate with weights and pool born sharded, then classify at
    dp=4 — the sharded-by-default serving topology on real chips."""
    from gofr_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"dp": 1, "tp": 4}, devices=run.devices[:4])
    asyncio.run(_generate(run, size, "mesh.generate", mesh, False,
                          ref_logits))
    gc.collect()
    phase_classify(run, size, mesh="dp:4")


def main() -> None:
    run = Run()
    phase_device(run)
    phase_kernels(run, "full")
    phase_classify(run, "full")
    many = len(run.devices) >= 4
    logits = phase_generate(run, "full", keep_logits=many)
    if many:
        phase_mesh(run, "full", logits)
    else:
        run.emit("mesh", skipped=f"{len(run.devices)} device")
    print(json.dumps({"ok": True, "device": run.device()}), flush=True)


if __name__ == "__main__":
    main()
