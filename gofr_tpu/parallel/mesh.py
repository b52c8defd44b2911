"""Device-mesh construction for SPMD serving and training.

The Go reference has no distributed compute backend (SURVEY.md §2.8 — its
scale-out is Kafka consumer groups + Kubernetes). The TPU-native equivalent
is a ``jax.sharding.Mesh`` over the slice: shardings are annotated on
arrays, XLA inserts the collectives, and they ride ICI within a slice / DCN
across slices (scaling-book recipe). Nothing here opens a socket — exactly
as GoFr delegates broker IO to kafka-go, we delegate tensor traffic to XLA.

Axis-name conventions used across the framework:
  dp — data parallel (batch)        tp — tensor parallel (hidden/heads)
  sp — sequence parallel (context)  ep — expert parallel (MoE)
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
from jax.sharding import Mesh


def make_mesh(axes: Optional[Dict[str, int]] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a mesh from ``{"dp": 2, "tp": 4}``-style axis sizes.

    ``-1`` for at most one axis means "all remaining devices". Default is a
    pure data-parallel mesh over every addressable device.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if not axes:
        axes = {"dp": n}
    names = tuple(axes.keys())
    sizes = list(axes.values())
    if -1 in sizes:
        known = 1
        for s in sizes:
            if s != -1:
                known *= s
        sizes[sizes.index(-1)] = max(1, n // known)
    total = 1
    for s in sizes:
        total *= s
    if total > n:
        raise ValueError(f"mesh {dict(zip(names, sizes))} needs {total} "
                         f"devices, have {n}")
    # Auto axis types = classic GSPMD: annotate with with_sharding_constraint
    # / NamedSharding and let the partitioner propagate, no mesh context
    # manager needed (jax 0.9 defaults to Explicit, which requires one).
    return jax.make_mesh(
        tuple(sizes), names, devices=devices[:total],
        axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def parse_mesh_spec(spec: Optional[str]) -> Optional[Dict[str, int]]:
    """Parse the ``MESH`` knob into ``make_mesh`` axis sizes.

    Accepted forms: ``"dp:2,tp:4"`` (explicit axes), ``"tp:8"`` (one
    axis, dp fills the rest), a bare integer ``"8"`` (shorthand for
    ``tp:<n>`` — the common "shard the model N ways" intent), and
    ``"auto"`` (tp over every addressable device, dp:1). Returns None
    for empty/absent specs; malformed axis sizes raise ``ValueError``
    because a typo'd topology must fail at startup."""
    spec = (spec or "").strip().lower()
    if not spec:
        return None
    if spec == "auto":
        return {"dp": 1, "tp": -1}
    if spec.isdigit():
        return {"dp": -1, "tp": int(spec)}
    axes: Dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        axis, sep, size = part.partition(":")
        if not sep or not axis.strip():
            raise ValueError(
                f"MESH entry {part!r}: expected axis:size (e.g. tp:4)")
        try:
            axes[axis.strip()] = int(size)
        except ValueError:
            raise ValueError(
                f"MESH entry {part!r}: size must be an integer") from None
    if len(axes) == 1 and "tp" in axes:
        axes = {"dp": -1, "tp": axes["tp"]}
    return axes or None


def serving_mesh(tp: int = 1) -> Mesh:
    """dp×tp mesh: shard the model tp-ways, data-parallel over the rest —
    the v5e-8 serving topology from BASELINE.json (tp=4 or 8 for Llama-7B)."""
    return make_mesh({"dp": -1, "tp": tp})
