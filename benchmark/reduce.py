"""From a profiler trace to numbers: device busy time, idle share, the
operations that took most time, and the idle gaps named by what the host
was doing. Every PR computes these the same way from here, and no PR that
claims a gain can change how.

The reduction works on an *intermediate form*, plain JSON:

    {"window_s": 2.0,
     "devices": {"/device:TPU:0": {"ops":     [[name, start_ns, dur_ns], ...],
                                   "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

``ops`` are the device's "XLA Ops" line (one event per executed HLO
operation, named by ``short_name``; a ``while`` encloses its body's
operations, so times are *self* times below), ``modules`` its "XLA Modules" line (one event per
executable run), ``host`` the host annotations the program writes
(``StepTraceAnnotation`` / ``TraceAnnotation``), all on one clock.
``load_xplane`` makes the form from the profiler's ``.xplane.pb``;
``tests/test_reduce.py`` checks the arithmetic on a recorded form.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, Iterable, List, Sequence, Tuple

Event = Sequence  # [name, start_ns, dur_ns]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_ANNOTATION = "no annotation"


# -- the profiler's file -> intermediate form ----------------------------------

def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


_HLO = re.compile(r"^%?(?P<lhs>\S+) = (?P<type>\(?\w+\[[\d,]*\])?.*?[})] ?"
                  r"(?P<opcode>[a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """A device event's name is the whole HLO instruction (hundreds of
    characters). Keep what identifies it: its name, result type, opcode,
    and for a custom call its target, which is where a Pallas kernel
    shows (``tpu_custom_call``):
    ``branch_0_fun.5 bf16[16,8,4,128] custom-call:tpu_custom_call``."""
    found = _HLO.match(name)
    if not found:
        return name.split(" = ")[0].lstrip("%")[:120]
    kind = found["type"] or ""
    if kind.startswith("("):
        kind += ",..)"                  # a tuple: its first element only
    parts = [found["lhs"], kind, found["opcode"]]
    if found["opcode"] == "custom-call":
        target = _TARGET.search(name)
        if target:
            parts[-1] += ":" + target.group(1)
    return " ".join(p for p in parts if p)


def load_xplane(path: str, host_patterns: Sequence[str],
                window_s: float) -> Dict[str, Any]:
    """Read ``.xplane.pb`` with nothing but JAX. Device planes are those
    named ``/device:TPU:<n>``; host events are kept where their name
    matches one of ``host_patterns``. ``window_s`` (the host's clock
    round the capture) stands only where the trace holds no event."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_res = [re.compile(p) for p in host_patterns]
    form: Dict[str, Any] = {"window_s": window_s, "devices": {}, "host": []}
    first, last = None, None
    for plane in data.planes:
        device = None
        if re.match(r"^/device:TPU:\d+$", plane.name):
            device = form["devices"][plane.name] = {"ops": [], "modules": []}
        keep_host = plane.name.startswith("/host:")
        for line in plane.lines:
            target = None
            if device is not None and line.name == OPS_LINE:
                target = device["ops"]
            elif device is not None and line.name == MODULES_LINE:
                target = device["modules"]
            for e in line.events:
                start, dur = int(e.start_ns), int(e.duration_ns)
                first = start if first is None else min(first, start)
                last = start + dur if last is None else max(last, start + dur)
                if target is not None:
                    target.append([short_name(e.name), start, dur])
                elif keep_host and any(r.search(e.name) for r in host_res):
                    form["host"].append([e.name, start, dur])
    form["host"].sort(key=lambda e: e[1])
    if first is not None:
        # the traced window is what the trace covers, on its own clock:
        # from the first to the last event of any plane, host or device
        form["window_s"] = (last - first) / 1e9
    return form


def crop(form: Dict[str, Any], first_ms: float) -> Dict[str, Any]:
    """The first ``first_ms`` of the form's events (for a test fixture)."""
    starts = [e[1] for d in form["devices"].values() for e in d["ops"]]
    if not starts:
        return form
    t0 = min(starts)
    limit = t0 + int(first_ms * 1e6)

    def keep(events):
        return [e for e in events if t0 <= e[1] and e[1] + e[2] <= limit]

    return {"window_s": first_ms / 1e3,
            "devices": {name: {"ops": keep(d["ops"]),
                               "modules": keep(d["modules"])}
                        for name, d in form["devices"].items()},
            "host": keep(form["host"])}


# -- arithmetic on intervals ---------------------------------------------------

def merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of [start, end) intervals as sorted disjoint intervals."""
    out: List[Tuple[int, int]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def busy_ns(events: Iterable[Event]) -> int:
    """Time in which at least one operation ran: the union, so that a
    ``while`` and the operations inside it count once."""
    return sum(end - start for start, end in
               merge((e[1], e[1] + e[2]) for e in events))


def self_times(events: Iterable[Event]) -> List[Tuple[str, int]]:
    """Each event's duration less the part its nested events cover. On
    one device line events nest or are disjoint; one that straddles its
    parent's end is cut to it."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    out: List[List] = []              # [name, self_ns]
    stack: List[Tuple[int, int]] = []  # (end_ns, index into out)
    for name, start, dur in ordered:
        end = start + dur
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            parent_end, parent = stack[-1]
            end = min(end, parent_end)
            out[parent][1] -= end - start
        out.append([name, end - start])
        stack.append((end, len(out) - 1))
    return [(name, max(0, ns)) for name, ns in out]


def op_table(events: Iterable[Event], top: int = 10
             ) -> List[Tuple[str, float]]:
    """Self seconds by operation name, largest first."""
    totals: Dict[str, int] = {}
    for name, ns in self_times(events):
        totals[name] = totals.get(name, 0) + ns
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [(name, ns / 1e9) for name, ns in ranked]


def matching_ns(events: Iterable[Event], pattern: str) -> Tuple[int, int]:
    """(self nanoseconds, count) of the events whose name matches."""
    rx = re.compile(pattern)
    hits = [ns for name, ns in self_times(events) if rx.search(name)]
    return sum(hits), len(hits)


def idle_gaps(events: Iterable[Event], host: Iterable[Event],
              top: int = 10, min_ns: int = 1000
              ) -> List[Tuple[str, float]]:
    """The device's idle gaps (between the merged busy intervals), each
    charged to the host annotation that overlaps most of it, else to
    ``no annotation``; seconds summed by annotation, largest first."""
    busy = merge((e[1], e[1] + e[2]) for e in events)
    host = sorted(host, key=lambda e: e[1])
    starts = [e[1] for e in host]
    totals: Dict[str, int] = {}
    for (_s, gap_start), (gap_end, _e) in zip(busy, busy[1:]):
        if gap_end - gap_start < min_ns:
            continue
        best_name, best_overlap = NO_ANNOTATION, 0
        # annotations that start before the gap ends; they are short and
        # few overlap, so a bounded look back finds every candidate
        last = bisect.bisect_left(starts, gap_end)
        for name, start, dur in host[max(0, last - 256):last]:
            overlap = min(gap_end, start + dur) - max(gap_start, start)
            if overlap > best_overlap:
                best_name, best_overlap = name, overlap
        totals[best_name] = totals.get(best_name, 0) + gap_end - gap_start
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [(name, ns / 1e9) for name, ns in ranked]


def reduce_form(form: Dict[str, Any]) -> Dict[str, Any]:
    """What the last line's ``device`` and ``breakdown`` carry: busy
    seconds averaged over the devices that ran anything, the window, the
    idle share, and the two top-ten lists (of the busiest device)."""
    window_s = float(form["window_s"])
    used = {name: d for name, d in form["devices"].items() if d["ops"]}
    if not used:
        return {"busy_s": 0.0, "window_s": window_s, "idle_share": 1.0,
                "devices_used": 0, "device_ops": [], "idle_gaps": []}
    busy = {name: busy_ns(d["ops"]) / 1e9 for name, d in used.items()}
    busy_s = sum(busy.values()) / len(busy)
    busiest = used[max(busy, key=busy.get)]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s else None,
            "devices_used": len(used),
            "device_ops": [list(x) for x in op_table(busiest["ops"])],
            "idle_gaps": [list(x) for x in
                          idle_gaps(busiest["ops"], form["host"])],
            "names": names(busiest, form["host"])}


def names(device: Dict[str, Any], host: Iterable[Event],
          top: int = 30) -> Dict[str, Any]:
    """What a reader needs to write a regex for a layer metric: the full
    names (with their source path) of the operations that took most self
    time, every executable's name, and the host annotations, with counts
    and seconds. Goes to the out file, not to the last line."""
    def ranked(pairs: Iterable[Tuple[str, float]]) -> List[List]:
        totals: Dict[str, List[float]] = {}
        for name, ns in pairs:
            entry = totals.setdefault(name, [0, 0.0])
            entry[0] += 1
            entry[1] += ns / 1e9
        return sorted(([name, *entry] for name, entry in totals.items()),
                      key=lambda row: -row[2])[:top]

    return {"ops": ranked(self_times(device["ops"])),
            "modules": ranked((e[0], e[2]) for e in device["modules"]),
            "host": ranked((e[0], e[2]) for e in host)}


def all_ops(form: Dict[str, Any], line: str = "ops") -> List[Event]:
    return [e for d in form["devices"].values() for e in d[line]]
