"""Per-layer metrics: each is a data file ``layer_metrics/<name>.json``
with a ``source`` of one of the kinds below, which this module implements
once. A later PR that adds a counter adds a JSON file, not code. A reader
that finds nothing to read returns ``None`` and the harness leaves the
metric out of the line.

What a reader is given (``Evidence``):

- ``prom0`` / ``prom1``: the program's Prometheus exposition at the
  window's start and end, parsed to ``{(name, labels): value}``;
- ``stats0`` / ``stats1`` and ``samples``: the system's ``stats()`` at the
  window's ends and once a second in between;
- ``generator``: what the load generator's children reported;
- ``trace``: the reduction's intermediate form (``reduce.py``), traced
  runs only;
- ``config``, ``peaks``, ``seconds``.

Source kinds:

``generator``         ``{"field": "late_p99_ms"}``
``prom_mean``         histogram sum / count over the window:
                      ``{"metric", "labels", "scale"}``
``prom_delta``        a counter's growth over the window
``stats``             ``{"path": "a.b"}`` (delta over the window), with
                      ``"over": "c.d"`` a ratio of two deltas, and
                      ``"one_minus"`` / ``"scale"``
``stats_samples``     ``{"path", "over", "times", "reduce": "max"|"mean"}``
                      on the per-second samples
``trace_share``       self time of device ops matching ``regex`` over the
                      device's busy time
``trace_ms_per_call`` time of events matching ``regex`` on ``line``
                      (``ops`` or ``modules``) over a count of calls:
                      events matching ``calls_regex`` on ``calls_line``
                      divided by ``calls_per`` (a number or a config key)
``roofline``          least time the chip could take (``bytes_fn`` of
                      ``bytes.py`` over the ``peak``) over the measured
                      time of the metric ``time_of``
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Tuple

import bytes as bytes_mod
import reduce
import stats as stats_mod

_SAMPLE = re.compile(r"^([A-Za-z_:][\w:]*)(\{.*\})?\s+(\S+)")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text: str) -> Dict[Tuple[str, Tuple], float]:
    out: Dict[Tuple[str, Tuple], float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        found = _SAMPLE.match(line)
        if not found:
            continue
        labels = tuple(sorted(_LABEL.findall(found.group(2) or "")))
        try:
            out[(found.group(1), labels)] = float(found.group(3))
        except ValueError:
            continue
    return out


def prom_sum(samples: Dict[Tuple[str, Tuple], float], name: str,
             labels: Dict[str, str]) -> Optional[float]:
    """Sum over every series of ``name`` that carries ``labels``."""
    want = set(labels.items())
    hits = [value for (n, have), value in samples.items()
            if n == name and want <= set(have)]
    return sum(hits) if hits else None


def dig(tree: Any, path: str) -> Optional[float]:
    for key in path.split("."):
        if not isinstance(tree, dict) or key not in tree:
            return None
        tree = tree[key]
    return tree if isinstance(tree, (int, float)) else None


@dataclasses.dataclass
class Evidence:
    prom0: Dict = dataclasses.field(default_factory=dict)
    prom1: Dict = dataclasses.field(default_factory=dict)
    stats0: Dict = dataclasses.field(default_factory=dict)
    stats1: Dict = dataclasses.field(default_factory=dict)
    samples: List = dataclasses.field(default_factory=list)
    generator: Dict = dataclasses.field(default_factory=dict)
    trace: Optional[Dict] = None
    config: Dict = dataclasses.field(default_factory=dict)
    peaks: Dict = dataclasses.field(default_factory=dict)
    seconds: float = 0.0
    # metrics read so far, for a reader that builds on another's value
    values: Dict[str, float] = dataclasses.field(default_factory=dict)


def _labels(source: Dict[str, Any], evidence: Evidence) -> Dict[str, str]:
    """Label values starting with ``$`` name a key of the configuration
    (``$batcher.name``)."""
    out = {}
    for key, value in source.get("labels", {}).items():
        if isinstance(value, str) and value.startswith("$"):
            found = evidence.config
            for part in value[1:].split("."):
                found = found[part]
            value = found
        out[key] = str(value)
    return out


def _finish(value: Optional[float], source: Dict[str, Any]
            ) -> Optional[float]:
    if value is None:
        return None
    if source.get("one_minus"):
        value = 1.0 - value
    return value * source.get("scale", 1.0)


def _delta(evidence: Evidence, path: str) -> Optional[float]:
    end, start = dig(evidence.stats1, path), dig(evidence.stats0, path)
    return None if end is None or start is None else end - start


def read_generator(source, evidence):
    return _finish(evidence.generator.get(source["field"]), source)


def read_prom_mean(source, evidence):
    labels = _labels(source, evidence)
    parts = []
    for suffix in ("_sum", "_count"):
        end = prom_sum(evidence.prom1, source["metric"] + suffix, labels)
        start = prom_sum(evidence.prom0, source["metric"] + suffix, labels)
        if end is None:
            return None
        parts.append(end - (start or 0.0))
    return _finish(parts[0] / parts[1], source) if parts[1] else None


def read_prom_delta(source, evidence):
    labels = _labels(source, evidence)
    end = prom_sum(evidence.prom1, source["metric"], labels)
    start = prom_sum(evidence.prom0, source["metric"], labels)
    return None if end is None else _finish(end - (start or 0.0), source)


def read_stats(source, evidence):
    value = _delta(evidence, source["path"])
    if value is not None and "over" in source:
        base = _delta(evidence, source["over"])
        value = value / base if base else None
    return _finish(value, source)


def read_stats_samples(source, evidence):
    values = []
    for sample in evidence.samples:
        value = dig(sample, source["path"])
        if value is not None and "over" in source:
            base = dig(sample, source["over"])
            value = value / base if base else None
        if value is not None and "times" in source:
            factor = dig(sample, source["times"])
            value = value * factor if factor is not None else None
        if value is not None:
            values.append(value)
    if not values:
        return None
    picked = max(values) if source.get("reduce", "max") == "max" \
        else stats_mod.mean(values)
    return _finish(picked, source)


def read_trace_share(source, evidence):
    if evidence.trace is None:
        return None
    ops = reduce.all_ops(evidence.trace, "ops")
    busy = sum(reduce.busy_ns(d["ops"])
               for d in evidence.trace["devices"].values())
    matched, count = reduce.matching_ns(ops, source["regex"])
    return _finish(matched / busy, source) if busy and count else None


def read_trace_ms_per_call(source, evidence):
    if evidence.trace is None:
        return None
    events = reduce.all_ops(evidence.trace, source.get("line", "modules"))
    rx = re.compile(source["regex"])
    total = sum(e[2] for e in events if rx.search(e[0]))
    calls_rx = re.compile(source.get("calls_regex", source["regex"]))
    calls = sum(1 for e in reduce.all_ops(
        evidence.trace, source.get("calls_line", source.get("line",
                                                            "modules")))
        if calls_rx.search(e[0]))
    per = source.get("calls_per", 1)
    if isinstance(per, str):
        per = evidence.config[per]
    if not total or not calls:
        return None
    return _finish(total / 1e6 / (calls / per), source)


def read_roofline(source, evidence):
    time_ms = evidence.values.get(source["time_of"])
    if not time_ms:
        return None
    args = {}
    for name, how in source.get("args", {}).items():
        args[name] = read(how, evidence)
        if args[name] is None:
            return None
    needed = getattr(bytes_mod, source["bytes_fn"])(
        evidence.config, evidence.config["precision"], **args)
    least_ms = needed / evidence.peaks[source["peak"]] * 1e3
    return _finish(least_ms / time_ms, source)


READERS = {"generator": read_generator, "prom_mean": read_prom_mean,
           "prom_delta": read_prom_delta, "stats": read_stats,
           "stats_samples": read_stats_samples,
           "trace_share": read_trace_share,
           "trace_ms_per_call": read_trace_ms_per_call,
           "roofline": read_roofline}


def read(source: Dict[str, Any], evidence: Evidence) -> Optional[float]:
    return READERS[source["kind"]](source, evidence)
