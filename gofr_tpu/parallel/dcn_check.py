"""Two-process DCN proof: jax.distributed over a real coordinator.

VERDICT r3 missing #6: ``multihost.initialize_distributed`` had never
executed with >1 real process. This module is both the child program and
the parent-side launcher for a 2-process CPU check that exercises the
REAL multi-host path end-to-end:

- each process boots its own JAX runtime (N virtual CPU devices),
- ``initialize_distributed`` wires them through the coordinator
  (the same env contract a k8s deployment would use),
- ``hybrid_mesh`` lays out a dcn-outermost × ici-innermost mesh over the
  2×N global device view,
- one dp all-reduce (psum over both axes, compiled under jit via
  shard_map) runs across the process boundary and both processes assert
  the globally-reduced value.

Run standalone:  python -m gofr_tpu.parallel.dcn_check
(parent mode: spawns both children, prints their reports).
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
from typing import Dict, List

_CHILD_ENV_FLAG = "GOFR_DCN_CHECK_CHILD"


def _child() -> None:
    """One process of the 2-process job. Must configure platform/devices
    before any JAX backend use. Forcing the CPU here is also what keeps a
    child from wanting the chip its parent may hold."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from gofr_tpu.parallel import multihost

    started = multihost.initialize_distributed()
    assert started, "initialize_distributed must start with JAX_COORDINATOR"

    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    mesh = multihost.hybrid_mesh(
        {"dp": jax.local_device_count()},
        {"dp_outer": jax.process_count()})
    n_global = jax.device_count()
    data = np.arange(n_global, dtype=np.float32)
    sharding = NamedSharding(mesh, P(("dp_outer", "dp")))
    x = jax.make_array_from_callback(
        (n_global,), sharding, lambda index: data[index])

    @jax.jit
    def global_sum(values):
        return shard_map(
            lambda v: jax.lax.psum(jnp.sum(v), ("dp_outer", "dp")),
            mesh=mesh, in_specs=P(("dp_outer", "dp")), out_specs=P(),
        )(values)

    reduced = float(global_sum(x))
    expected = float(data.sum())
    report = {
        "process": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": jax.local_device_count(),
        "global_devices": n_global,
        "psum": reduced,
        "expected": expected,
        "ok": abs(reduced - expected) < 1e-6,
    }
    print(json.dumps(report), flush=True)
    assert report["ok"], report


def _free_port() -> int:
    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        return probe.getsockname()[1]


def run_two_process_check(local_devices: int = 4,
                          timeout: float = 180.0) -> List[Dict]:
    """Spawn the 2-process job; returns both children's reports (parent
    asserts nothing itself — callers check ``ok``/``psum``)."""
    import re
    import tempfile

    port = _free_port()
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    children = []
    for process_id in range(2):
        env = dict(os.environ)
        env[_CHILD_ENV_FLAG] = "1"
        env["JAX_COORDINATOR"] = f"127.0.0.1:{port}"
        env["JAX_NUM_PROCESSES"] = "2"
        env["JAX_PROCESS_ID"] = str(process_id)
        # preserve inherited XLA_FLAGS (dump/determinism flags), only
        # overriding the forced device count
        flags = re.sub(r"--xla_force_host_platform_device_count=\S+", "",
                       env.get("XLA_FLAGS", ""))
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count="
            f"{local_devices}").strip()
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH",
                                                             "")
        # stderr → a temp file: both children must reach the collective
        # for either to exit, so an undrained stderr PIPE filling up
        # would deadlock the pair (and eat the diagnostics)
        errfile = tempfile.TemporaryFile(mode="w+")
        child = subprocess.Popen(
            [sys.executable, "-m", "gofr_tpu.parallel.dcn_check"],
            env=env, stdout=subprocess.PIPE, stderr=errfile, text=True)
        children.append((child, errfile))
    reports = []
    try:
        for child, errfile in children:
            try:
                out, _ = child.communicate(timeout=timeout)
            finally:
                errfile.seek(0)
                err = errfile.read()
            if child.returncode != 0:
                raise RuntimeError(
                    f"dcn check child failed rc={child.returncode}:\n"
                    f"{err[-2000:]}")
            reports.append(json.loads(out.strip().splitlines()[-1]))
    finally:
        for child, errfile in children:
            if child.poll() is None:
                child.kill()
            errfile.close()
    return reports


if __name__ == "__main__":
    if os.environ.get(_CHILD_ENV_FLAG):
        _child()
    else:
        for entry in run_two_process_check():
            print(json.dumps(entry))
