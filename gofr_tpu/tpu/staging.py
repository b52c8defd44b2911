"""Zero-copy data plane: pinned host staging slabs + transfer coalescing.

Every bench round since r3 has the same punchline: the hardware is ~2x
faster than the served path, and most of the gap is host-side copies —
``np.asarray`` + ``np.pad`` per dispatch, one ``jnp.asarray`` per tiny
admission array, one D2H sync per slot. This module owns the two
primitives that kill those copies (ISSUE 9; transport tax per
arxiv 1804.01138, micro-batch amortization per arxiv 1812.11731):

- :class:`StagingPool` — preallocated per-(model, bucket) host slabs,
  recycled round-robin. Request leaves are written **once**, directly
  into the slab rows, and the slab is uploaded with a single
  ``device_put``. A slab is only reused after the execute that consumed
  it has produced its output (output-ready implies the H2D read of the
  inputs completed), so dispatching batch N+1 genuinely overlaps batch
  N's execute without corrupting it.
- :class:`TransferCoalescer` — packs several small 4-byte-dtype host
  arrays (decode tick inputs, admission scatters) into one ``uint8``
  blob, ships it as **one** transfer, and splits it back on device with
  a jitted bitcast — bit-exact, so greedy decode output is token-
  identical with coalescing on or off.

Both record ``app_tpu_h2d_bytes_total`` / ``app_tpu_h2d_seconds`` so the
host link's share of a dispatch is attributable per phase.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

LeafSpec = Tuple[Tuple[int, ...], str]   # (shape, dtype-name)


class _Slab:
    """One set of preallocated host buffers matching a bucket's leaves,
    plus the device handle whose readiness gates reuse."""

    __slots__ = ("buffers", "inflight")

    def __init__(self, specs: Sequence[LeafSpec]):
        self.buffers: List[np.ndarray] = [
            np.zeros(shape, dtype=np.dtype(dtype)) for shape, dtype in specs]
        self.inflight: Any = None


class StagingPool:
    """Recycled host staging slabs, one ring per (model, bucket) key.

    Lifecycle per dispatch: ``acquire`` → write rows into
    ``slab.buffers`` → ``upload`` each buffer (one ``device_put``) →
    enqueue the execute → ``retire(key, slab, out)``. ``acquire`` grows
    the ring up to ``depth`` slabs before it ever waits: while the only
    free slabs are still tied to in-flight executes and fewer than
    ``depth`` exist, it allocates a fresh slab instead of stalling the
    dispatcher (often the event loop) on the previous batch. Only once
    ``depth`` slabs exist does it block — on the *oldest* slab's execute
    output, by which point the device has consumed that slab's bytes, so
    the rewrite cannot race the in-flight execute. ``depth`` slabs per
    key give genuine double buffering with natural backpressure.
    """

    def __init__(self, metrics=None, depth: int = 2,
                 wait_ready: Optional[Callable[[Any], Any]] = None):
        self.metrics = metrics
        self.depth = max(1, int(depth))
        self._wait_ready = wait_ready
        self._free: Dict[Any, deque] = {}
        self._lock = threading.Lock()
        # observability (statusz data-plane section)
        self._allocated: Dict[Any, int] = {}
        self._slab_bytes = 0
        self._reuse_waits = 0
        self._uploads = 0
        self._upload_bytes = 0
        self._upload_seconds = 0.0

    # -- slab ring -----------------------------------------------------------
    def acquire(self, key: Any, specs: Sequence[LeafSpec]) -> _Slab:
        """A slab whose buffers match ``specs``, safe to write into."""
        while True:
            slab: Optional[_Slab] = None
            can_grow = False
            with self._lock:
                ring = self._free.setdefault(key, deque())
                if ring:
                    slab = ring.popleft()
                    can_grow = self._allocated.get(key, 0) < self.depth
            if slab is None:
                return self._alloc(key, specs)
            if not self._matches(slab, specs):
                # stale geometry — drop it without waiting on its execute
                # (device_put holds its own reference to the host buffers
                # until the copy completes)
                self._forget(key, slab)
                continue
            if slab.inflight is not None:
                if can_grow:
                    # every free slab is still tied to an in-flight
                    # execute and the ring is under depth: allocate a
                    # fresh slab instead of stalling the dispatcher
                    # (often the event loop) on the previous batch
                    with self._lock:
                        self._free[key].appendleft(slab)
                    return self._alloc(key, specs)
                # depth slabs exist — natural backpressure: wait for the
                # oldest execute's output, which implies its H2D inputs
                # were read and the slab is safe to rewrite
                self._reuse_waits += 1
                self._block(slab.inflight)
                slab.inflight = None
            return slab

    def _alloc(self, key: Any, specs: Sequence[LeafSpec]) -> _Slab:
        slab = _Slab(specs)
        with self._lock:
            self._allocated[key] = self._allocated.get(key, 0) + 1
            self._slab_bytes += sum(b.nbytes for b in slab.buffers)
        return slab

    def retire(self, key: Any, slab: _Slab, inflight: Any) -> None:
        """Return a slab to the ring once its execute is enqueued;
        ``inflight`` is the device output whose readiness proves the
        slab's bytes were consumed."""
        slab.inflight = inflight
        with self._lock:
            ring = self._free.setdefault(key, deque())
            ring.append(slab)
            while len(ring) > self.depth:        # cap transient growth
                dropped = ring.popleft()
                self._forget_locked(key, dropped)

    def _matches(self, slab: _Slab, specs: Sequence[LeafSpec]) -> bool:
        if len(slab.buffers) != len(specs):
            return False
        return all(buf.shape == tuple(shape) and buf.dtype == np.dtype(dtype)
                   for buf, (shape, dtype) in zip(slab.buffers, specs))

    def _forget(self, key: Any, slab: _Slab) -> None:
        with self._lock:
            self._forget_locked(key, slab)

    def _forget_locked(self, key: Any, slab: _Slab) -> None:
        self._allocated[key] = max(0, self._allocated.get(key, 1) - 1)
        self._slab_bytes -= sum(b.nbytes for b in slab.buffers)

    def _block(self, handle: Any) -> None:
        if self._wait_ready is not None:
            self._wait_ready(handle)
        else:
            import jax
            jax.block_until_ready(handle)

    # -- metered upload ------------------------------------------------------
    def upload(self, arr: Any, put: Callable[[Any], Any],
               path: str = "dispatch") -> Any:
        """One host→device transfer through ``put``, metered into
        ``app_tpu_h2d_bytes_total`` / ``app_tpu_h2d_seconds``."""
        nbytes = int(getattr(arr, "nbytes", 0))
        t0 = time.perf_counter()
        dev = put(arr)
        self.note_h2d(nbytes, time.perf_counter() - t0, path)
        return dev

    def note_h2d(self, nbytes: int, seconds: float, path: str) -> None:
        self._uploads += 1
        self._upload_bytes += nbytes
        self._upload_seconds += seconds
        if self.metrics is not None:
            self.metrics.delta_updown_counter("app_tpu_h2d_bytes_total",
                                              float(nbytes), path=path)
            self.metrics.record_histogram("app_tpu_h2d_seconds", seconds,
                                          path=path)

    # -- statusz -------------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "depth": self.depth,
                "slabs": {str(k): v for k, v in self._allocated.items() if v},
                "slab_bytes": self._slab_bytes,
                "reuse_waits": self._reuse_waits,
                "uploads": self._uploads,
                "upload_bytes": self._upload_bytes,
                "upload_mb_per_s": (
                    round(self._upload_bytes / self._upload_seconds / 2**20, 1)
                    if self._upload_seconds > 0 else None),
            }


class TransferCoalescer:
    """One H2D transfer for many small arrays.

    Decode ticks and admissions upload half a dozen tiny arrays each —
    lengths, slots, temps, top-k/p, seeds — and every one pays the
    fixed per-transfer cost of the host link. The coalescer packs them
    (all 4-byte
    dtypes) into a single ``uint8`` blob on the host, ships it with one
    ``device_put``, and splits it back on device with a jitted
    ``bitcast_convert_type`` keyed by the static (name, shape, dtype)
    spec — a pure byte reinterpretation, so values are bit-identical to
    uploading each array on its own.
    """

    _ITEM = 4  # only 4-byte dtypes qualify; everything else falls back

    def __init__(self, metrics=None, pool: Optional[StagingPool] = None):
        self.metrics = metrics
        self.pool = pool
        self._unpack: Dict[Tuple, Callable] = {}
        self._transfers = 0
        self._arrays = 0
        self._bytes = 0

    @classmethod
    def _eligible(cls, arrays: Dict[str, np.ndarray]) -> bool:
        # byteorder must be native/little-endian: the device-side bitcast
        # reinterprets bytes in little-endian order, so a '>f4' array
        # (constructible via X-Tensor-Dtype binary ingest) would come back
        # byte-swapped — such arrays fall back to per-array uploads, where
        # jnp.asarray converts values correctly
        return bool(arrays) and all(
            a.dtype.itemsize == cls._ITEM and a.dtype.kind in "iuf"
            and a.dtype.byteorder in "=<|"
            for a in arrays.values())

    def upload(self, arrays: Dict[str, Any]) -> Dict[str, Any]:
        """Device arrays for ``arrays`` (name → host array) via one
        transfer; falls back to per-array uploads when a dtype does not
        qualify (never silently changes values)."""
        import jax
        import jax.numpy as jnp

        host = {}
        for name, a in arrays.items():
            # graftcheck: ignore[GT007] — identity (a view, no copy) for
            # the contiguous arrays the staging path produces; copies only
            # the rare strided ingest leaf, which the byte-level coalesce
            # below requires to be contiguous
            a = np.ascontiguousarray(a)
            if a.dtype.byteorder not in "=<|":
                # jax rejects non-native dtypes outright, and the device-
                # side bitcast split assumes little-endian bytes — byteswap
                # to native (value-preserving) so a '>f4' array from binary
                # ingest uploads correctly instead of as garbage
                a = a.astype(a.dtype.newbyteorder("="))
            host[name] = a
        if not self._eligible(host):
            return {name: jnp.asarray(a) for name, a in host.items()}
        spec = tuple((name, a.shape, a.dtype.name) for name, a in host.items())
        total = sum(a.nbytes for a in host.values())
        blob = np.empty((total,), np.uint8)
        off = 0
        for a in host.values():
            blob[off:off + a.nbytes] = a.reshape(-1).view(np.uint8)
            off += a.nbytes
        t0 = time.perf_counter()
        blob_dev = jax.device_put(blob)
        fn = self._unpack.get(spec)
        if fn is None:
            fn = self._build_unpack(spec)
            self._unpack[spec] = fn
        outs = fn(blob_dev)
        dt = time.perf_counter() - t0
        self._transfers += 1
        self._arrays += len(host)
        self._bytes += total
        if self.pool is not None:
            self.pool.note_h2d(total, dt, path="coalesced")
        elif self.metrics is not None:
            self.metrics.delta_updown_counter("app_tpu_h2d_bytes_total",
                                              float(total), path="coalesced")
            self.metrics.record_histogram("app_tpu_h2d_seconds", dt,
                                          path="coalesced")
        return dict(zip(host.keys(), outs))

    @staticmethod
    def _build_unpack(spec: Tuple) -> Callable:
        """Jit one blob→arrays splitter for a static spec. Bitcast from
        ``uint8 (n, 4)`` to the 4-byte target dtype collapses the
        trailing axis — an exact byte reinterpretation on a little-
        endian device, matching the host layout."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        def split(blob):
            outs = []
            off = 0
            for _name, shape, dtype in spec:
                dt = np.dtype(dtype)
                count = int(np.prod(shape, dtype=np.int64)) if shape else 1
                nbytes = count * dt.itemsize
                chunk = lax.slice(blob, (off,), (off + nbytes,))
                words = chunk.reshape(count, dt.itemsize)
                arr = lax.bitcast_convert_type(words, jnp.dtype(dt))
                outs.append(arr.reshape(shape))
                off += nbytes
            return tuple(outs)

        return jax.jit(split)

    def stats(self) -> Dict[str, Any]:
        return {
            "transfers": self._transfers,
            "arrays_coalesced": self._arrays,
            "bytes": self._bytes,
            "arrays_per_transfer": (round(self._arrays / self._transfers, 2)
                                    if self._transfers else None),
        }
