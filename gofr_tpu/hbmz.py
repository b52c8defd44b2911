"""Device-memory attribution over HTTP: ``/debug/hbmz``.

ISSUE 10: ``memory_stats()`` says how many bytes the backend holds;
nobody could say WHOSE they are. The engine-side
``GenerationEngine.hbm_attribution`` splits what the serving stack
knowingly placed on device — params per model, the KV page pool divided
into free / prefix-pinned / decode / migrated pages, staging slabs —
and this page reconciles that against the backend figure. The
difference is rendered as an explicit ``unattributed`` residual (XLA
temporaries, executables, allocator fragmentation): an honest line
item, not an error, and the one to watch when it grows.

The same numbers feed the watchdog a real HBM-pressure signal:
:func:`hbm_occupancy` prefers the backend's ``bytes_in_use /
bytes_limit`` when the platform reports a limit (TPU/GPU), and falls
back to KV-pool occupancy (the serving-pressure proxy that also works
on CPU). ``enable_hbmz`` wires it as ``watchdog.hbm_fn``.

:func:`build_hbmz` is app-independent — tests call it with a bare
container or engine; ``enable_hbmz`` is the HTTP binding.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

__all__ = ["build_hbmz", "hbm_occupancy", "enable_hbmz"]


def build_hbmz(container, metrics=None) -> Dict[str, Any]:
    """One attribution snapshot from whatever the container wired
    (engine or model registry — both duck-type ``hbm_attribution``)."""
    tpu = getattr(container, "tpu", None)
    attribution_fn = getattr(tpu, "hbm_attribution", None)
    if attribution_fn is None:
        return {"error": "no engine with hbm_attribution wired",
                "at": time.time()}
    report = attribution_fn()
    report["at"] = time.time()
    report["occupancy"] = hbm_occupancy(container)
    metrics = metrics if metrics is not None \
        else getattr(container, "metrics", None)
    if metrics is not None:
        if report.get("attributed_bytes") is not None:
            metrics.set_gauge("app_tpu_hbm_attributed_bytes",
                              float(report["attributed_bytes"]))
        if report.get("unattributed_bytes") is not None:
            metrics.set_gauge("app_tpu_hbm_unattributed_bytes",
                              float(report["unattributed_bytes"]))
    return report


def hbm_occupancy(container) -> Optional[float]:
    """HBM-pressure fraction in [0, 1] for the watchdog: backend
    ``bytes_in_use / bytes_limit`` when a limit is reported, else the
    KV page pool's occupancy, else ``None`` (signal unavailable —
    the watchdog must NOT treat that as pressure)."""
    try:
        import jax
        in_use = limit = 0
        for device in jax.local_devices():
            try:
                stats = device.memory_stats() or {}
            except Exception:
                continue
            if stats.get("bytes_limit"):
                in_use += int(stats.get("bytes_in_use", 0))
                limit += int(stats["bytes_limit"])
        if limit > 0:
            return min(1.0, in_use / limit)
    except Exception:
        pass
    tpu = getattr(container, "tpu", None)
    pool = getattr(tpu, "_pool", None)
    if pool is None:
        # registry: the shared pool, when one exists
        pool = getattr(tpu, "page_pool", None)
    if pool is not None and getattr(pool, "num_pages", 0):
        return pool.used_pages / pool.num_pages
    return None


def enable_hbmz(app, prefix: str = "/debug/hbmz") -> None:
    container = app.container
    watchdog = getattr(container, "watchdog", None)
    if watchdog is not None and hasattr(watchdog, "hbm_fn"):
        watchdog.hbm_fn = lambda: hbm_occupancy(container)

    def hbmz(ctx):
        return build_hbmz(container)

    app.get(prefix, hbmz)
