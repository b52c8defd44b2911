"""TPU executor — the container datasource that owns compiled XLA programs.

North star (BASELINE.json): "handlers call ``ctx.tpu.predict()`` which
dispatches through an in-process client that loads modules into TPU HBM".
In this framework the PJRT client is JAX itself (jax → XLA → libtpu); the
executor's job is everything around it, mirroring how GoFr's datasources
wrap driver libs with config/logging/metrics/health (e.g.
/root/reference/pkg/gofr/datasource/sql/sql.go:37-92):

- **Bucketed AOT compilation**: XLA traces once per static shape, so the
  executor compiles each model at a ladder of batch sizes (1,2,4,...) and
  pads every request batch up to the next bucket — one warm executable per
  bucket, zero recompiles at serve time.
- **Weights resident in HBM**: params are device_put once at register time
  (sharded over a mesh when given — tp for Llama, dp for batch serving).
- **Health/metrics**: per-device liveness probe + HBM occupancy gauges
  feed the same health aggregation GoFr applies to SQL/Redis
  (/root/reference/pkg/gofr/container/health.go:8-66).
- Narrow interface + in-process CPU fallback = the "miniredis of XLA"
  test story (SURVEY.md §4): the identical executor runs on the CPU
  backend in unit tests.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from gofr_tpu.metrics.digest import WindowedCounter
from gofr_tpu.tpu.compile_ledger import (
    CAUSE_SERVING,
    CAUSE_WARMUP,
    CompileLedger,
    ExecutableLedger,
    ShapeStats,
    charge_device_time,
    fingerprint_lowered,
    suggest_ladder,
)
from gofr_tpu.tpu.staging import StagingPool

DEFAULT_BUCKETS = (1, 2, 4, 8, 16, 32, 64)


def _pad_batch(leaf: np.ndarray, bucket: int) -> np.ndarray:
    """Pad the leading axis up to ``bucket``. A leaf that already fills
    the bucket is returned **as-is** — same object, no allocation — so
    full-bucket batches ride the zero-copy path even with staging off."""
    n = leaf.shape[0]
    if n == bucket:
        return leaf
    pad = [(0, bucket - n)] + [(0, 0)] * (leaf.ndim - 1)
    # graftcheck: ignore[GT007] — the staging-off fallback's pad copy;
    # EXEC_STAGING=1 (the default) writes rows into a recycled slab instead
    return np.pad(leaf, pad)


class _Model:
    def __init__(self, name: str, fn: Callable, params: Any,
                 buckets: Sequence[int]):
        self.name = name
        self.fn = fn
        self.params = params
        self.buckets = tuple(sorted(buckets))
        self.compiled: Dict[int, Callable] = {}
        self.lock = threading.Lock()


class Executor:
    """Owns registered models, their compiled executables, and device health.

    ``fn(params, inputs)`` must be jit-compatible; ``inputs`` is one array
    or a tuple of arrays whose leading axis is the batch.
    """

    def __init__(self, logger, metrics, mesh=None, batch_axis: str = "dp",
                 donate_cache: bool = False, peak_flops: float = 0.0,
                 ledger: Optional[CompileLedger] = None,
                 recorder: Any = None, staging: bool = True,
                 staging_depth: int = 2, donate_inputs: str = "auto"):
        import jax

        from gofr_tpu.tpu.compile_cache import configure_compile_cache
        configure_compile_cache()
        self._jax = jax
        self.logger = logger
        self.metrics = metrics
        self.mesh = mesh
        self.batch_axis = batch_axis
        self._models: Dict[str, _Model] = {}
        self.devices = jax.devices()
        self._up = {d.id: True for d in self.devices}
        # zero-copy data plane (ISSUE 9): request leaves are written once
        # into a recycled per-(model, bucket) host slab and uploaded with a
        # single device_put; input donation lets XLA reuse the uploaded
        # buffers for outputs ("auto" = on everywhere but the CPU backend,
        # where donation is a no-op that only emits warnings)
        self._staging = (StagingPool(metrics, depth=staging_depth,
                                     wait_ready=jax.block_until_ready)
                         if staging else None)
        backend = self.devices[0].platform
        self._donate = (donate_inputs == "on"
                        or (donate_inputs == "auto" and backend != "cpu"))
        # saturation accounting: windowed device-busy seconds and executed
        # FLOPs feed duty-cycle and MFU; peak_flops (TPU_PEAK_FLOPS, whole
        # slice) of 0 means "unknown hardware" and disables the MFU ratio
        self.peak_flops = float(peak_flops)
        self._busy_s = WindowedCounter()
        self._flops_done = WindowedCounter()
        # padded-FLOPs split: _flops_useful counts only the real rows'
        # share of each execute, so MFU can report raw vs *effective*
        self._flops_useful = WindowedCounter()
        # cost_analysis FLOPs per (model, bucket); None = analysis
        # unavailable on this backend, don't retry every step
        self._flops_cache: Dict[Tuple[str, int], Optional[float]] = {}
        # compile-plane & shape-plane observability (ISSUE 3): every
        # compile — warmup or serving — lands in the ledger; every
        # execute lands in the shape stats (real rows vs bucket)
        self.ledger = ledger if ledger is not None \
            else CompileLedger(metrics)
        self.shapes = ShapeStats(metrics)
        # per-executable roofline attribution (ISSUE 17): device time and
        # executed FLOPs per (model, bucket family), achieved vs
        # peak_flops — the "which executable burns the seconds" view.
        # classes=None at the charge site keeps the engine-owned
        # app_tpu_device_seconds_total aggregate untouched (no double
        # count; the batcher plane never charged it).
        self.exec_ledger = ExecutableLedger(metrics,
                                            peak_flops=self.peak_flops)
        # flight recorder for step-phase timelines (statusz); optional
        self.recorder = recorder
        # (model, bucket) -> monotonic start of an in-progress serve-time
        # compile — surfaced by health_check so an operator can see what
        # the model lock is stuck behind
        self._compiling: Dict[Tuple[str, int], float] = {}

    # -- registration (analog of datasource connect) ------------------------
    def register(self, name: str, fn: Callable, params: Any,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 param_specs: Any = None) -> None:
        """Put weights on device (sharded if a mesh + specs are given) and
        set up the compile-bucket ladder."""
        jax = self._jax
        if self.mesh is not None and param_specs is not None:
            from gofr_tpu.parallel.sharding import shard_pytree
            params = shard_pytree(params, self.mesh, param_specs)
        else:
            params = jax.device_put(params)
        if self.mesh is not None and self.batch_axis in self.mesh.shape:
            # Every bucket must shard evenly over the dp axis —
            # device_put with an uneven NamedSharding raises, so round the
            # ladder up to multiples of the axis size (1,2,4,… → dp,2dp,…).
            dp = self.mesh.shape[self.batch_axis]
            buckets = sorted({-(-b // dp) * dp for b in buckets})
        # donate the inputs tree (argnum 1): every dispatch uploads fresh
        # arrays, so XLA may reuse their device buffers for the outputs —
        # dispatching batch N+1 overlaps batch N's execute without holding
        # two generations of input buffers in HBM
        jitted = (jax.jit(fn, donate_argnums=(1,)) if self._donate
                  else jax.jit(fn))
        model = _Model(name, jitted, params, buckets)
        self._models[name] = model
        self.logger.info("tpu: model %s registered (buckets=%s, mesh=%s)",
                         name, list(buckets),
                         dict(self.mesh.shape) if self.mesh else None)

    def models(self) -> Sequence[str]:
        return list(self._models)

    def warmup(self, name: str, example: Any) -> None:
        """Pre-compile every bucket from one example input (no cold-start
        compiles on the serving path)."""
        model = self._models[name]
        leaves = self._leaves(example)
        for bucket in model.buckets:
            batch = self._tree_unflatten(
                example, [np.repeat(l[None], bucket, axis=0) for l in leaves])
            self._execute(model, batch, bucket, cause=CAUSE_WARMUP)

    # -- predict (the hot path) ---------------------------------------------
    def predict(self, name: str, inputs: Any) -> Any:
        """Synchronous batched predict. ``inputs`` leading axis = batch; it
        is padded up to the next compiled bucket and results are sliced
        back. Single-example calls (no batch axis) go through
        ``predict_one``/the dynamic batcher instead."""
        model = self._models.get(name)
        if model is None:
            raise KeyError(f"tpu model {name!r} not registered "
                           f"(have {list(self._models)})")
        leaves = self._leaves(inputs)
        n = leaves[0].shape[0]
        bucket = next((b for b in model.buckets if b >= n), None)
        if bucket is None:  # larger than biggest bucket: split
            bucket = model.buckets[-1]
            outs = [self.predict(name, self._tree_unflatten(
                inputs, [l[i:i + bucket] for l in leaves]))
                for i in range(0, n, bucket)]
            return self._tree_concat(outs)
        return self.fetch(self._dispatch(model, name, inputs, leaves,
                                         n, bucket))

    # -- async dispatch/fetch split (H2D/compute overlap) --------------------
    def is_warm(self, name: str, n: int) -> bool:
        """True when a batch of ``n`` hits an already-compiled bucket, i.e.
        ``dispatch`` is cheap enough to run on the event loop."""
        model = self._models.get(name)
        if model is None:
            return False
        bucket = next((b for b in model.buckets if b >= n), None)
        return bucket is not None and bucket in model.compiled

    def dispatch(self, name: str, inputs: Any):
        """Asynchronous half of ``predict``: pad, *start* the H2D transfer
        and enqueue the XLA execute without syncing. Returns an opaque
        handle for ``fetch``. Double-buffering falls out: dispatching batch
        N+1 while batch N computes rides the transfer stream under the
        running execute, so the device never idles waiting on the host
        link."""
        model = self._models.get(name)
        if model is None:
            raise KeyError(f"tpu model {name!r} not registered "
                           f"(have {list(self._models)})")
        leaves = self._leaves(inputs)
        n = leaves[0].shape[0]
        bucket = next((b for b in model.buckets if b >= n), None)
        if bucket is None:
            raise ValueError(
                f"batch {n} exceeds largest bucket {model.buckets[-1]}; "
                "use predict() which splits oversized batches")
        return self._dispatch(model, name, inputs, leaves, n, bucket)

    def _dispatch(self, model: _Model, name: str, inputs: Any, leaves,
                  n: int, bucket: int):
        start = time.perf_counter()
        # capture the dispatching context's span (request span, or the
        # batcher's step span) so fetch — possibly on a worker thread with
        # no context — can stamp the latency histogram's exemplar
        from gofr_tpu.trace import current_span
        span = current_span()
        if self._staging is not None:
            return self._dispatch_staged(model, name, inputs, leaves, n,
                                         bucket, start, span)
        # staging-off fallback (EXEC_STAGING=0): the classic pad-then-
        # upload path. host_prep = host-side padding/stacking, enqueue =
        # building device args + queueing the (async) execute — a serve-
        # time compile shows up as a pathological enqueue phase —
        # device_wait = the block_until_ready in fetch
        # graftcheck: ignore[GT007,GT001] — this alloc IS what the staging
        # pool replaces; kept as the EXEC_STAGING=0 escape hatch. GT001:
        # the leaves here are host request arrays (wire-decoded), not
        # device values, so np.asarray is a cheap host copy, not a D2H sync
        padded = self._tree_unflatten(
            inputs, [_pad_batch(np.asarray(l), bucket) for l in leaves])
        prepped = time.perf_counter()
        out = self._execute_async(model, padded, bucket)
        enqueued = time.perf_counter()
        phases = {"host_prep": prepped - start, "enqueue": enqueued - prepped}
        return (name, out, n, start, span, bucket, phases)

    def _dispatch_staged(self, model: _Model, name: str, inputs: Any,
                         leaves, n: int, bucket: int, start: float, span):
        """The zero-copy dispatch: request leaves are written once into a
        recycled host slab (or, when a leaf already matches the bucket
        shape and dtype, uploaded as-is with **zero** host copies), then
        shipped with one ``device_put`` per leaf.

        Step-phase anatomy replaces ``host_prep`` with a three-way split:
        ``serialize`` (non-ndarray leaves → arrays), ``stage`` (rows into
        the slab), ``upload`` (device_put) — the host side of a dispatch
        is attributable per phase instead of one opaque number.
        """
        # graftcheck: ignore[GT007,GT001] — serialize phase: converting a
        # non-ndarray request leaf is the single permitted host copy.
        # GT001: request leaves are host-side (lists/wire buffers), so
        # np.asarray never triggers a device->host sync here
        arrs = [leaf if isinstance(leaf, np.ndarray) else np.asarray(leaf)
                for leaf in leaves]
        serialized = time.perf_counter()
        specs = [((bucket,) + a.shape[1:], self._canon_dtype(a.dtype).name)
                 for a in arrs]
        key = (name, bucket)
        slab = self._staging.acquire(key, specs)
        staged = []
        for buf, arr in zip(slab.buffers, arrs):
            if arr.shape == buf.shape and arr.dtype == buf.dtype:
                staged.append(arr)   # full bucket, right dtype: no copy
            else:
                buf[:n] = arr        # converting write, straight into slab
                if n < bucket:
                    buf[n:] = 0      # recycled slab: re-zero the pad rows
                staged.append(buf)
        staged_at = time.perf_counter()
        dev = [self._staging.upload(a, self._put_leaf) for a in staged]
        padded = self._tree_unflatten(inputs, dev)
        uploaded = time.perf_counter()
        out = self._execute_async(model, padded, bucket)
        # the slab may be rewritten only after this execute's output is
        # ready — by then the device has consumed the uploaded bytes
        self._staging.retire(key, slab, out)
        enqueued = time.perf_counter()
        phases = {"serialize": serialized - start,
                  "stage": staged_at - serialized,
                  "upload": uploaded - staged_at,
                  "enqueue": enqueued - uploaded}
        return (name, out, n, start, span, bucket, phases)

    def dispatch_rows(self, name: str, examples: Sequence[Any]):
        """Batcher entry point: write each request's rows **directly** into
        the staging slab — no intermediate ``np.stack`` batch, no pad
        copy — and dispatch. With staging off this falls back to the
        classic stack+dispatch path (identical results, one extra copy)."""
        model = self._models.get(name)
        if model is None:
            raise KeyError(f"tpu model {name!r} not registered "
                           f"(have {list(self._models)})")
        n = len(examples)
        bucket = next((b for b in model.buckets if b >= n), None)
        if bucket is None:
            raise ValueError(
                f"batch {n} exceeds largest bucket {model.buckets[-1]}; "
                "use predict() which splits oversized batches")
        if self._staging is None:
            # graftcheck: ignore[GT007,GT001] — staging-off fallback keeps
            # the classic stack path (one extra host copy, same results);
            # GT001: rows are host request leaves, not device arrays
            batch = self._jax.tree.map(
                lambda *rows: np.stack([np.asarray(r) for r in rows]),
                *examples)
            return self._dispatch(model, name, batch, self._leaves(batch),
                                  n, bucket)
        start = time.perf_counter()
        from gofr_tpu.trace import current_span
        span = current_span()
        # serialize: non-ndarray leaves → arrays (identity for ndarrays,
        # so wire-decoded numpy rows stay zero-copy here)
        # graftcheck: ignore[GT007,GT001] — per-row conversion is the
        # single permitted host copy; ndarray leaves pass through
        # untouched. GT001: request rows are host data, never device values
        rows = [[r if isinstance(r, np.ndarray) else np.asarray(r)
                 for r in self._leaves(e)] for e in examples]
        nleaves = len(rows[0])
        serialized = time.perf_counter()
        # slab specs must match np.stack semantics, not just row 0: equal
        # shapes or raise, dtypes promoted across rows (then jax-
        # canonicalized) — a silent buf[i] = row cast/broadcast would make
        # warm (staged) and cold (stack) paths disagree on the same batch
        for i in range(1, n):
            if len(rows[i]) != nleaves:
                raise ValueError(
                    f"dispatch_rows: example {i} has {len(rows[i])} "
                    f"leaves, example 0 has {nleaves}")
        specs = []
        for j in range(nleaves):
            shape = rows[0][j].shape
            for i in range(1, n):
                if rows[i][j].shape != shape:
                    raise ValueError(
                        f"dispatch_rows: leaf {j} shape mismatch — "
                        f"example {i} is {rows[i][j].shape}, example 0 "
                        f"is {shape} (all rows must stack)")
            dtype = np.result_type(*[r[j].dtype for r in rows])
            specs.append(((bucket,) + shape, self._canon_dtype(dtype).name))
        key = (name, bucket)
        slab = self._staging.acquire(key, specs)
        for j, buf in enumerate(slab.buffers):
            for i in range(n):
                buf[i] = rows[i][j]  # value-preserving cast into the slab
            if n < bucket:
                buf[n:] = 0
        staged_at = time.perf_counter()
        dev = [self._staging.upload(b, self._put_leaf, path="rows")
               for b in slab.buffers]
        padded = self._tree_unflatten(examples[0], dev)
        uploaded = time.perf_counter()
        out = self._execute_async(model, padded, bucket)
        self._staging.retire(key, slab, out)
        enqueued = time.perf_counter()
        phases = {"serialize": serialized - start,
                  "stage": staged_at - serialized,
                  "upload": uploaded - staged_at,
                  "enqueue": enqueued - uploaded}
        return (name, out, n, start, span, bucket, phases)

    def _put_leaf(self, arr):
        """One H2D transfer for a staged host array (sharded over the dp
        axis when a mesh is present)."""
        jax = self._jax
        if self.mesh is not None and self.batch_axis in self.mesh.shape:
            from jax.sharding import NamedSharding, PartitionSpec as P
            spec = P(self.batch_axis, *([None] * (arr.ndim - 1)))
            return jax.device_put(arr, NamedSharding(self.mesh, spec))
        return jax.device_put(arr)

    def _canon_dtype(self, dt) -> np.dtype:
        """Match jax's dtype canonicalization so the slab holds the bytes
        the device will actually consume (x64 off: 64-bit → 32-bit) —
        otherwise device_put would re-convert, adding the copy back."""
        dt = np.dtype(dt)
        if self._jax.config.jax_enable_x64:
            return dt
        return {np.dtype(np.float64): np.dtype(np.float32),
                np.dtype(np.int64): np.dtype(np.int32),
                np.dtype(np.uint64): np.dtype(np.uint32),
                np.dtype(np.complex128): np.dtype(np.complex64)}.get(dt, dt)

    def data_plane(self) -> Dict[str, Any]:
        """Data-plane snapshot for statusz: staging-slab occupancy, H2D
        upload totals, and whether input donation is active."""
        staging = (dict(self._staging.stats(), enabled=True)
                   if self._staging is not None else {"enabled": False})
        return {"staging": staging, "donate_inputs": self._donate}

    def fetch(self, handle) -> Any:
        """Sync a ``dispatch`` handle: wait for the execute, record metrics,
        slice off the padding."""
        name, out, n, start, span, bucket, phases = handle
        wait_start = time.perf_counter()
        out = self._jax.block_until_ready(out)
        done = time.perf_counter()
        phases = dict(phases, device_wait=done - wait_start)
        elapsed = done - start
        exemplar = ({"trace_id": span.trace_id} if span is not None else None)
        self.metrics.record_histogram("app_tpu_execute", elapsed,
                                      exemplar=exemplar, model=name)
        self.metrics.record_histogram("app_tpu_batch_size", float(n),
                                      model=name)
        self.metrics.increment_counter("app_tpu_requests_total", model=name)
        for phase, seconds in phases.items():
            self.metrics.record_histogram("app_tpu_step_phase_seconds",
                                          seconds, phase=phase, model=name)
        self.shapes.record(name, n, bucket)
        if self.recorder is not None:
            self.recorder.record_step(model=name, bucket=bucket, batch=n,
                                      phases=phases)
        self._busy_s.add(elapsed)
        flops = self._bucket_flops(name, bucket)
        if flops:
            self._flops_done.add(flops)
            # only the real rows' share of the padded execute is useful
            self._flops_useful.add(flops * n / bucket)
        # per-executable roofline ledger (ISSUE 17): the batcher plane's
        # executables are keyed (model, bucket). classes=None — the
        # engine owns the class-keyed aggregate; this plane never
        # contributed to it, so charging the family view adds no double
        # count.
        charge_device_time(elapsed, name, family=f"b{bucket}",
                           ledger=self.exec_ledger, flops=flops)
        return self._jax.tree.map(lambda l: np.asarray(l)[:n], out)

    # -- saturation telemetry ------------------------------------------------
    def note_execution(self, seconds: float, flops: float = 0.0) -> None:
        """Feed device-busy wall time (and optionally FLOPs) executed
        outside the dispatch/fetch path — the generation engine's prefill
        and decode steps run their own executables but count toward the
        same duty cycle."""
        if seconds > 0:
            self._busy_s.add(seconds)
        if flops > 0:
            self._flops_done.add(flops)

    def _bucket_flops(self, name: str, bucket: int) -> Optional[float]:
        """FLOPs of one compiled (model, bucket) execution, from XLA's
        ``cost_analysis`` — computed once and cached; None when the
        backend doesn't expose it (then MFU stays unreported rather than
        lying)."""
        key = (name, bucket)
        if key in self._flops_cache:
            return self._flops_cache[key]
        flops: Optional[float] = None
        model = self._models.get(name)
        compiled = model.compiled.get(bucket) if model is not None else None
        if compiled is not None:
            try:
                value = float(compiled.cost_analysis().get("flops", 0.0))
                flops = value if value > 0 else None
            except Exception:
                flops = None
        self._flops_cache[key] = flops
        return flops

    def saturation(self, window_s: float = 60.0) -> Dict[str, Any]:
        """Windowed device-saturation view: duty cycle (busy seconds per
        wall second — can exceed 1.0 when dispatches overlap), achieved
        FLOP/s, MFU against ``TPU_PEAK_FLOPS``, and HBM occupancy."""
        busy = self._busy_s.sum(window_s)
        duty = busy / max(window_s, 1e-9)
        flops_per_s = self._flops_done.rate(window_s)
        useful_per_s = self._flops_useful.rate(window_s)
        mfu = (flops_per_s / self.peak_flops) if self.peak_flops > 0 else None
        # effective MFU discounts padded rows: raw MFU can look healthy
        # while half the device rows are zeros
        effective_mfu = (useful_per_s / self.peak_flops
                         if self.peak_flops > 0 else None)
        padding_ratio = self.shapes.padding_ratio(window_s)
        hbm: Dict[str, Any] = {}
        for device in self.devices:
            try:
                mem = device.memory_stats() or {}
            except Exception:
                continue
            in_use = float(mem.get("bytes_in_use", 0))
            limit = float(mem.get("bytes_limit", 0))
            hbm[str(device.id)] = {
                "bytes_in_use": in_use,
                "bytes_limit": limit,
                "occupancy": round(in_use / limit, 4) if limit > 0 else None,
            }
        out = {
            "window_s": window_s,
            "busy_s": round(busy, 4),
            "duty_cycle": round(duty, 4),
            "flops_per_s": flops_per_s,
            "useful_flops_per_s": useful_per_s,
            "mfu": round(mfu, 4) if mfu is not None else None,
            "effective_mfu": (round(effective_mfu, 4)
                              if effective_mfu is not None else None),
            "padding_ratio": (round(padding_ratio, 4)
                              if padding_ratio is not None else None),
            "peak_flops": self.peak_flops or None,
            "hbm": hbm,
        }
        self.metrics.set_gauge("app_tpu_duty_cycle", min(duty, 1.0))
        if mfu is not None:
            self.metrics.set_gauge("app_tpu_mfu", mfu)
        if effective_mfu is not None:
            self.metrics.set_gauge("app_tpu_effective_mfu", effective_mfu)
        if padding_ratio is not None:
            self.metrics.set_gauge("app_tpu_padding_ratio", padding_ratio)
        for device_id, entry in hbm.items():
            if entry["occupancy"] is not None:
                self.metrics.set_gauge("app_tpu_hbm_occupancy",
                                       entry["occupancy"], device=device_id)
        return out

    def _execute(self, model: _Model, padded: Any, bucket: int,
                 cause: str = CAUSE_SERVING) -> Any:
        return self._jax.block_until_ready(
            self._execute_async(model, padded, bucket, cause=cause))

    def _execute_async(self, model: _Model, padded: Any, bucket: int,
                       cause: str = CAUSE_SERVING) -> Any:
        """Enqueue H2D + execute; returns un-synced device arrays (JAX async
        dispatch)."""
        compiled = model.compiled.get(bucket)
        if compiled is None:
            with model.lock:
                compiled = model.compiled.get(bucket)
                if compiled is None:
                    compiled = self._compile(model, padded, bucket, cause)
        # serving labels on the device timeline: an on-demand XProf
        # capture shows which model/bucket each execute belongs to
        with self._jax.profiler.TraceAnnotation(f"{model.name}/b{bucket}"):
            return compiled(model.params, self._constrain(padded))

    def _compile(self, model: _Model, padded: Any, bucket: int,
                 cause: str):
        """One ``.lower().compile()`` under ``model.lock``: records the
        ledger event (with HLO fingerprint) and — for serve-time compiles,
        which stall every request for this model behind the lock — logs at
        warn with the queue impact instead of a quiet info line."""
        key = (model.name, bucket)
        if cause == CAUSE_SERVING and self.logger is not None:
            self.logger.warn(
                "tpu: serve-time compile of %s bucket=%d started — "
                "requests for this model queue behind model.lock until it "
                "finishes (warm this bucket at startup to avoid it)",
                model.name, bucket)
        self._compiling[key] = time.monotonic()
        try:
            t0 = time.perf_counter()
            args = self._constrain(padded)
            lowered = model.fn.lower(model.params, args)
            compiled = lowered.compile()
            duration = time.perf_counter() - t0
        finally:
            self._compiling.pop(key, None)
        model.compiled[bucket] = compiled
        event = self.ledger.record(model.name, bucket, cause, duration,
                                   fingerprint_lowered(lowered))
        if self.logger is not None:
            log = (self.logger.warn if cause == CAUSE_SERVING
                   else self.logger.info)
            log("tpu: compiled %s bucket=%d in %.1fs (cause=%s, "
                "fingerprint=%s)", model.name, bucket, duration, cause,
                event.fingerprint)
        return compiled

    # -- compile/shape-plane snapshot (/debug/xlaz) --------------------------
    def xlaz(self, recent: int = 64, max_rungs: int = 4) -> Dict[str, Any]:
        """The bucket-tuning view: compile ledger, observed batch-size
        distribution vs the registered ladder per model, padding-waste
        windows, and a padding-optimal suggested ladder derived from the
        observed distribution (rounded to the dp-mesh multiple when a
        mesh is present)."""
        round_to = 1
        if self.mesh is not None and self.batch_axis in self.mesh.shape:
            round_to = self.mesh.shape[self.batch_axis]
        models: Dict[str, Any] = {}
        for name, model in self._models.items():
            observed = self.shapes.distribution(name)
            models[name] = {
                "ladder": list(model.buckets),
                "buckets_compiled": sorted(model.compiled),
                "observed_batch_sizes": {str(k): v for k, v
                                         in sorted(observed.items())},
                "bucket_hits": {str(k): v for k, v in
                                sorted(self.shapes.bucket_hits(name).items())},
                "suggested_ladder": suggest_ladder(
                    observed, max_rungs=max(len(model.buckets), max_rungs),
                    round_to=round_to),
            }
        return {
            "compiles": self.ledger.snapshot(limit=recent),
            "models": models,
            "padding": self.shapes.snapshot(),
            # per-executable roofline table (ISSUE 17): device-seconds,
            # dispatches, achieved FLOP/s vs TPU_PEAK_FLOPS per
            # (model, bucket family), ranked by seconds
            "executables": self.exec_ledger.snapshot(limit=max_rungs * 3),
        }

    def _constrain(self, inputs: Any):
        jax = self._jax
        if self.mesh is not None and self.batch_axis in self.mesh.shape:
            from jax.sharding import NamedSharding, PartitionSpec as P
            def put(leaf):
                arr = jax.numpy.asarray(leaf)
                spec = P(self.batch_axis, *([None] * (arr.ndim - 1)))
                return jax.device_put(arr, NamedSharding(self.mesh, spec))
            return jax.tree.map(put, inputs)
        return jax.tree.map(jax.numpy.asarray, inputs)

    # -- pytree plumbing ----------------------------------------------------
    def _leaves(self, inputs: Any):
        return self._jax.tree.leaves(inputs)

    def _tree_unflatten(self, like: Any, leaves):
        treedef = self._jax.tree.structure(like)
        return self._jax.tree.unflatten(treedef, leaves)

    def _tree_concat(self, outs):
        return self._jax.tree.map(
            lambda *ls: np.concatenate([np.asarray(l) for l in ls]), *outs)

    # -- health (container/health.go analog, per-chip) ----------------------
    def health_check(self) -> Dict[str, Any]:
        details: Dict[str, Any] = {"backend": self.devices[0].platform,
                                   "devices": {}}
        all_up = True
        for device in self.devices:
            stats = {}
            try:
                mem = device.memory_stats() or {}
                stats = {"hbm_bytes_in_use": mem.get("bytes_in_use", 0),
                         "hbm_bytes_limit": mem.get("bytes_limit", 0)}
                self.metrics.set_gauge("app_tpu_hbm_bytes_in_use",
                                       float(mem.get("bytes_in_use", 0)),
                                       device=str(device.id))
                up = True
            except Exception as exc:  # chip unreachable
                stats = {"error": repr(exc)}
                up = False
                all_up = False
            self._up[device.id] = up
            self.metrics.set_gauge("app_tpu_device_up", 1.0 if up else 0.0,
                                   device=str(device.id))
            details["devices"][str(device.id)] = {
                "status": "UP" if up else "DOWN", **stats}
        details["models"] = {
            name: {"buckets_compiled": sorted(m.compiled)}
            for name, m in self._models.items()}
        # serve-time compiles in flight: these hold model.lock, so every
        # request for that model is invisibly queued behind them (ISSUE 3)
        now = time.monotonic()
        details["compiling"] = [
            {"model": name, "bucket": bucket, "for_s": round(now - since, 3)}
            for (name, bucket), since in list(self._compiling.items())]
        details["status"] = "UP" if all_up else "DOWN"
        return details

    def close(self) -> None:
        self._models.clear()


def new_executor(config, logger, metrics) -> Executor:
    """Factory (container.go:63-146 composition-root style): mesh shape from
    env — ``TPU_MESH=dp:2,tp:4`` — else single-mesh over all devices.
    Data-plane knobs: ``EXEC_STAGING`` (default on), ``EXEC_STAGING_DEPTH``
    (slabs per (model, bucket) ring), ``EXEC_STAGING_DONATE``
    (``auto`` | ``on`` | ``off``)."""
    mesh = None
    mesh_env = config.get("TPU_MESH") if config else None
    if mesh_env:
        from gofr_tpu.parallel.mesh import make_mesh
        axes = {}
        for part in str(mesh_env).split(","):
            axis, _, size = part.partition(":")
            axes[axis.strip()] = int(size)
        mesh = make_mesh(axes)
    peak_flops = config.get_float("TPU_PEAK_FLOPS", 0.0) if config else 0.0
    staging_env = (config.get("EXEC_STAGING") if config else None)
    staging = str(staging_env).strip().lower() not in (
        "0", "false", "off", "no") if staging_env is not None else True
    depth_env = (config.get("EXEC_STAGING_DEPTH") if config else None)
    staging_depth = int(depth_env) if depth_env else 2
    donate = str((config.get("EXEC_STAGING_DONATE") if config else None)
                 or "auto").strip().lower()
    return Executor(logger, metrics, mesh=mesh, peak_flops=peak_flops,
                    staging=staging, staging_depth=staging_depth,
                    donate_inputs=donate)
