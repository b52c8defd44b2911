#!/usr/bin/env python3
"""What the engine's timeline is good to: how far a landing stamp lies
behind the end of the device program it stands for.

    python3 scripts/landing_lag.py <lag.json> --workload <cell> --seed <n> \\
        --seconds 40 [--platform cpu --size tiny]

Runs ``benchmark/run.py`` with ``--trace 1`` in this process (same
arguments, same last line, same out file) and, before the harness throws
the profiler's capture away, reads from it the host annotations
``tpu.fetch.<kind>`` (each ends where ``_Fetch`` stamps ``landed_at``)
and the device's "XLA Modules" line. A landing is paired with the module
of its kind (``jit_decode_k`` for a tick, ``jit_prefill_batch`` for a
prefill group) whose end lies nearest, and ``lag = landing - that end``:
the fetch's latency plus the worker thread's wake-up. Written to
``<lag.json>``: a kind's count, median, p90, p99 and max in
milliseconds. A capture without ``tpu.fetch.*`` (a program from before
ISSUE 35) gives empty lists. On the CPU there is no device plane, so
nothing pairs: a rehearsal of the script's plumbing only.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
MODULE_OF = {"tick": "jit_decode_k", "prefill": "jit_prefill_batch"}


def lags_ms(path: str) -> dict:
    """Landing minus the nearest same-kind module's end, a kind, from
    the capture at ``path``."""
    from jax.profiler import ProfileData

    landings = {kind: [] for kind in MODULE_OF}
    ends = {kind: [] for kind in MODULE_OF}
    for plane in ProfileData.from_file(path).planes:
        host = plane.name.startswith("/host:")
        device = re.match(r"^/device:TPU:\d+$", plane.name) is not None
        for line in plane.lines:
            if not host and not (device and line.name == "XLA Modules"):
                continue
            for event in line.events:
                end = int(event.start_ns) + int(event.duration_ns)
                for kind, module in MODULE_OF.items():
                    if host and event.name == "tpu.fetch." + kind:
                        landings[kind].append(end)
                    elif device and event.name.startswith(module):
                        ends[kind].append(end)
    out = {}
    for kind in MODULE_OF:
        known = sorted(ends[kind])
        lags = []
        for landed in landings[kind]:
            at = bisect.bisect_left(known, landed)
            near = [known[i] for i in (at - 1, at) if 0 <= i < len(known)]
            if near:
                end = min(near, key=lambda e: abs(landed - e))
                lags.append((landed - end) / 1e6)
        out[kind] = lags
    return out


def summary(lags: dict) -> dict:
    def at(values, q):
        return values[min(len(values) - 1, int(q * len(values)))]

    out = {}
    for kind, values in lags.items():
        values = sorted(values)
        out[kind] = ({"count": 0} if not values else {
            "count": len(values), "median_ms": at(values, 0.5),
            "p90_ms": at(values, 0.9), "p99_ms": at(values, 0.99),
            "max_ms": values[-1], "min_ms": values[0]})
    return out


def main() -> None:
    out_path, argv = sys.argv[1], sys.argv[2:]
    sys.path.insert(0, BENCH)
    import reduce
    import run

    load = reduce.load_xplane

    def load_and_measure(path, host_patterns, window_s):
        with open(out_path, "w") as handle:
            json.dump(summary(lags_ms(path)), handle, indent=1)
        return load(path, host_patterns, window_s)

    reduce.load_xplane = load_and_measure
    sys.argv = [os.path.join(BENCH, "run.py"), *argv, "--trace", "1"]
    run.main()


if __name__ == "__main__":
    main()
