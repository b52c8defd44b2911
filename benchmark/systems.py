"""The system under test, built by a configuration's ``kind``. The set-up
is ``chip_smoke.py``'s (weights on the device, a pool sized from what the
chip has left, every reachable shape warmed, a real ``App`` on real
sockets), copied here because later PRs may change the program and may
not change the yardstick. From the program this takes the served system
and its counters; everything that judges it lives in ``benchmark/``.

A builder returns a ``System``: the port to load, the registry to read
metrics from, ``stats()`` for the engine's counts, ``spec()`` for what the
load generator must know, and ``verdict()`` for the checks made after the
window. The self-checks that need the chip but not the window (the same
greedy prompt twice, logits against the plain reference, labels alone
against labels in a coalesced batch) run in set-up.
"""

from __future__ import annotations

import asyncio
import importlib
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional

import numpy as np

import reference
import traffic as traffic_mod
import weights

HERE = os.path.dirname(os.path.abspath(__file__))
READ_TIMEOUT_S = 120.0


class CheckFailed(AssertionError):
    """A correctness check did not hold; the run reports correct=false."""


def counting_logger():
    """The engine logs a failed tick and carries on, so the benchmark
    looks: every ERROR line is kept and must be absent at the end."""
    from gofr_tpu.logging.logger import Level, Logger

    class CountingLogger(Logger):
        def __init__(self):
            super().__init__(Level.WARN, out=sys.stderr, err=sys.stderr)
            self.errors: List[str] = []

        def logf(self, level, message, *args, **fields):
            if level >= Level.ERROR:
                self.errors.append(str(message) % args if args
                                   else str(message))
            super().logf(level, message, *args, **fields)

    return CountingLogger()


def published(config: Dict[str, Any], size: str) -> Dict[str, Any]:
    """The configuration as run: the file, with its ``tiny`` block laid
    over it under the rehearsal switch."""
    merged = dict(config)
    if size == "tiny":
        for key, value in config["tiny"].items():
            merged[key] = (dict(config.get(key, {}), **value)
                           if isinstance(value, dict)
                           and isinstance(config.get(key), dict) else value)
    return merged


def model_config(config: Dict[str, Any]):
    """The program's config object from the file's published keys."""
    module = importlib.import_module(f"gofr_tpu.models.{config['module']}")
    model = config["model"]
    overrides = {}
    for ours, theirs in model.get("from_keys", {}).items():
        value = config[theirs]
        overrides[ours] = tuple(value) if isinstance(value, list) else value
    return module, module.config(model["preset"], **overrides)


async def http(port: int, method: str, path: str, body: bytes = b"",
               headers: Optional[Dict[str, str]] = None):
    """One HTTP/1.1 exchange on a fresh connection, read to EOF with
    every read bounded. Returns (status, de-chunked body)."""
    reader, writer = await asyncio.wait_for(
        asyncio.open_connection("127.0.0.1", port), READ_TIMEOUT_S)
    try:
        extra = "".join(f"{k}: {v}\r\n" for k, v in (headers or {
            "Content-Type": "application/json"}).items())
        writer.write((f"{method} {path} HTTP/1.1\r\nHost: bench\r\n"
                      f"Connection: close\r\n{extra}"
                      f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
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


class System:
    """What run.py needs of a built system, whatever its kind."""

    kind = ""

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.logger = counting_logger()
        self.port = 0
        self.metrics = None                  # the container's registry
        self.checks: Dict[str, Any] = {}     # what set-up's checks found
        self.notes: Dict[str, Any] = {}      # sizes worth the out file

    def stats(self) -> Dict[str, Any]:
        return {}

    def spec(self) -> Dict[str, Any]:
        raise NotImplementedError

    def verdict(self) -> List[str]:
        """Faults found after the window; empty when all is well."""
        return [f"logged error: {e}" for e in self.logger.errors[:3]]

    async def stop(self) -> None:
        raise NotImplementedError


class GenerateSystem(System):
    kind = "generate"

    async def start(self) -> None:
        import jax
        import jax.numpy as jnp

        from gofr_tpu.app import App
        from gofr_tpu.container import new_mock_container
        from gofr_tpu.http.response import Stream
        from gofr_tpu.tpu.generate import GenerationEngine

        config = self.config
        module, cfg = model_config(config)
        self.cfg = cfg
        params = weights.int8_params(module, cfg, self.seed)
        jax.block_until_ready(params)
        self.notes["weight_bytes"] = weights.tree_bytes(params)

        # the plain reference runs before the pool takes the chip's rest:
        # it dequantises one layer at a time into float32
        tokens = jnp.asarray(traffic_mod.prompt_ids(
            self.seed, 1 << 30, 32, cfg.vocab_size), jnp.int32)
        want = np.asarray(jax.jit(
            lambda p, t: reference.llama_last_logits(p, config, t)
        )(params, tokens))
        got = np.asarray(jax.jit(
            lambda p, t: module.prefill(
                p, cfg, t[None], module.init_cache(cfg, 1, t.shape[0]))[0]
        )(params, tokens))[0]
        self.checks["logits_rel_l2"] = reference.rel_l2(got, want)
        self.checks["logits_tol"] = reference.LOGITS_REL_L2_TOL
        del want, got

        memory = jax.devices()[0].memory_stats()
        if memory:
            left = memory["bytes_limit"] - memory["bytes_in_use"]
            pool_bytes = left - int(config["pool_headroom_bytes"])
            if pool_bytes <= 0:
                raise CheckFailed(f"no room for a KV pool: {left} bytes "
                                  f"left beside the weights")
        else:
            pool_bytes = 4 << 20                 # CPU rehearsal
        container = new_mock_container()
        container.logger = self.logger
        self.metrics = container.metrics
        settings = dict(config["engine"])
        settings["prompt_buckets"] = tuple(settings["prompt_buckets"])
        engine = GenerationEngine(cfg, params, kv_pool_bytes=pool_bytes,
                                  model_module=module, logger=self.logger,
                                  metrics=container.metrics, **settings)
        self.engine = engine
        self.notes.update(kv_pool_bytes=pool_bytes,
                          kv_pages=engine.stats()["kv_pool"]["num_pages"],
                          attn_path=engine.attn_path,
                          attn_why=engine.attn_reason)
        # every admission-count rung x bucket (groups of any size up to
        # max_slots form under concurrent arrivals) and every k rung; the
        # traffic stays under max_len, so no deeper rung is reachable
        await engine.warmup(prompt_counts=tuple(engine._n_ladder))
        self.notes["warm_compiles"] = engine.stats()["compiles"]["warmup"]

        app = App(config=container.config, container=container)
        app.http_port = app.metrics_port = 0

        async def generate_stream(ctx):
            data = ctx.bind()
            stream = await engine.generate_stream(
                data["prompt_ids"],
                max_new_tokens=int(data["max_new_tokens"]))

            async def frames():
                try:
                    async for token in stream:
                        yield json.dumps({"token": token})
                    yield "[DONE]"
                finally:
                    await stream.aclose()

            return Stream(frames(), sse=True, on_close=stream.cancel)

        app.post(config["route"], generate_stream)
        self.app = app
        await engine.start()
        await app.start()
        self.port = app._http_server.bound_port

        # the same greedy prompt, alone, twice: the same tokens
        body = json.dumps({
            "prompt_ids": traffic_mod.prompt_ids(self.seed, (1 << 30) + 1,
                                                 48, cfg.vocab_size),
            "max_new_tokens": 12}).encode()
        twice = []
        for _ in range(2):
            status, payload = await http(self.port, "POST", config["route"],
                                         body)
            events = [line[6:].decode() for line in payload.split(b"\n")
                      if line.startswith(b"data: ")]
            if status != 200 or not events or events[-1] != "[DONE]":
                raise CheckFailed(f"greedy probe: status {status}, "
                                  f"tail {events[-2:]}")
            twice.append([json.loads(e)["token"] for e in events[:-1]])
        self.checks["greedy_repeatable"] = twice[0] == twice[1]
        self.checks["greedy_frames"] = len(twice[0])

    def stats(self) -> Dict[str, Any]:
        return self.engine.stats()

    def spec(self) -> Dict[str, Any]:
        return {"kind": "generate", "path": self.config["route"],
                "vocab": self.cfg.vocab_size}

    def verdict(self) -> List[str]:
        faults = super().verdict()
        checks, engine = self.checks, self.engine
        if checks["logits_rel_l2"] > checks["logits_tol"]:
            faults.append(f"first-step logits differ from the reference: "
                          f"relative L2 {checks['logits_rel_l2']:.4g} > "
                          f"{checks['logits_tol']}")
        if not checks["greedy_repeatable"] or checks["greedy_frames"] != 12:
            faults.append("the same greedy prompt did not give the same "
                          "12 tokens twice")
        serving = engine.stats()["compiles"]["serving"]
        if serving:
            faults.append(f"{serving} serve-time compile(s): "
                          f"{engine._compile_events[-serving:]}")
        expected = self.config.get("expect_attn_path")
        if expected and engine.attn_path != expected:
            faults.append(f"attention path {engine.attn_path!r} "
                          f"({engine.attn_reason}), expected {expected!r}")
        return faults

    async def stop(self) -> None:
        await self.app.stop()
        await self.engine.stop()


class ClassifySystem(System):
    kind = "classify"

    async def start(self) -> None:
        import jax
        import jax.numpy as jnp

        from gofr_tpu.app import App
        from gofr_tpu.container import new_mock_container

        config = self.config
        module, cfg = model_config(config)
        self.cfg = cfg
        batcher = config["batcher"]
        name = batcher["name"]
        container = new_mock_container({
            "TPU_MAX_BATCH": str(batcher["TPU_MAX_BATCH"]),
            "TPU_BATCH_DELAY_MS": str(batcher["TPU_BATCH_DELAY_MS"])})
        container.logger = self.logger
        self.metrics = container.metrics
        app = App(config=container.config, container=container)
        app.http_port = app.metrics_port = 0
        self.shape = (cfg.image_size, cfg.image_size, config["channels"])
        self.inputs = int(self.traffic["images"])

        # the seed is an argument, so one program serves every seed
        params = jax.jit(lambda s: module.init(cfg, jax.random.PRNGKey(s)))(
            jnp.uint32(self.seed % (2 ** 31 - 1)))
        self.notes["weight_bytes"] = weights.tree_bytes(params)

        def classify_fn(p, u8):
            return module.apply(p, cfg, u8.astype(jnp.bfloat16) / 255.0)

        app.add_model(name, classify_fn, params=params,
                      buckets=tuple(batcher["buckets"]))
        executor = app.container.tpu
        self.executor = executor

        async def classify(ctx):
            logits = await ctx.predict(name, ctx.bind())
            top = int(np.argmax(logits))
            return {"label": top, "score": float(logits[top])}

        app.post(config["route"], classify)
        executor.warmup(name, np.zeros(self.shape, np.uint8))
        self.app = app
        await app.start()
        self.port = app._http_server.bound_port

        # float32 reference, unbatched, highest precision, on the images
        # the window will send; then each alone and all in one burst
        images = traffic_mod.images(self.seed, self.inputs, self.shape)
        probe = images[:4]
        f32 = jax.tree.map(lambda leaf: leaf.astype(jnp.float32), params)
        cfg32 = module.config(config["model"]["preset"], **{
            **{k: getattr(cfg, k) for k in
               config["model"].get("from_keys", {})}, "dtype": jnp.float32})

        def reference_fn(p, u8):
            with jax.default_matmul_precision("highest"):
                return module.apply(p, cfg32,
                                    u8.astype(jnp.float32)[None] / 255.0)[0]

        ref = np.stack([np.asarray(jax.jit(reference_fn)(f32, image))
                        for image in probe])
        del f32
        headers = {"Content-Type": "application/x-tensor",
                   "X-Tensor-Dtype": "uint8",
                   "X-Tensor-Shape": ",".join(map(str, self.shape))}

        async def ask(image):
            status, payload = await http(self.port, "POST", config["route"],
                                         image.tobytes(), headers)
            if status not in (200, 201):
                raise CheckFailed(f"classify probe: status {status}, "
                                  f"body {payload[:200]!r}")
            return json.loads(payload)["data"]

        alone = [await ask(image) for image in probe]
        burst = await asyncio.gather(*[ask(image) for image in images])
        scale = float(np.abs(ref).max())
        tol = reference.CLASSIFY_SCORE_TOL * scale
        worst = 0.0
        agree = True
        for i, (one, many) in enumerate(zip(alone, burst)):
            for answer in (one, many):
                # the served label must be the reference's top label, or
                # tie with it inside the tolerance; its score must be the
                # reference's score for that label inside the tolerance
                err = abs(answer["score"] - float(ref[i][answer["label"]]))
                gap = float(ref[i].max() - ref[i][answer["label"]])
                worst = max(worst, err, gap)
                agree = agree and err <= tol and gap <= tol
        self.checks.update(score_err_max=worst, score_tol=tol,
                           scores_agree=agree,
                           labels_alone=[a["label"] for a in alone],
                           labels_coalesced=[b["label"] for b in burst[:4]])

    def spec(self) -> Dict[str, Any]:
        return {"kind": "classify", "path": self.config["route"],
                "input_shape": list(self.shape), "inputs": self.inputs,
                "classes": self.cfg.num_classes}

    def verdict(self) -> List[str]:
        from gofr_tpu.tpu.compile_ledger import CAUSE_SERVING

        faults = super().verdict()
        if not self.checks["scores_agree"]:
            faults.append(f"served scores differ from the float32 "
                          f"reference: {self.checks['score_err_max']:.4g} > "
                          f"{self.checks['score_tol']:.4g}")
        serving = self.executor.ledger.total(CAUSE_SERVING)
        if serving:
            faults.append(f"{serving} serve-time compile(s)")
        return faults

    async def stop(self) -> None:
        await self.app.stop()


KINDS = {"generate": GenerateSystem, "classify": ClassifySystem}


def build(config: Dict[str, Any], traffic: Dict[str, Any],
          seed: int) -> System:
    """By the configuration's ``kind``; a family that needs more than its
    ``module`` brings ``adapters/<module>.py`` with a ``System`` subclass
    named ``Adapter``."""
    adapter = os.path.join(HERE, "adapters", f"{config['module']}.py")
    if os.path.exists(adapter):
        spec = importlib.util.spec_from_file_location(
            f"adapter_{config['module']}", adapter)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.Adapter(config, traffic, seed)
    return KINDS[config["kind"]](config, traffic, seed)
