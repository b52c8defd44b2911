"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh.

Unit tests never want a chip, even on a machine that has one: they
exercise the multi-chip sharding paths (gofr_tpu.parallel) on a virtual
8-device CPU mesh — the "miniredis of XLA" strategy from SURVEY.md §4.
``jax.config.update`` below holds whatever ``JAX_PLATFORMS`` says (as a
plain env var it works too; the tier-1 command sets it to ``cpu``). The
program itself runs on the chip through ``python chip_smoke.py``.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# The suite builds hundreds of engines and executors whose toy executables
# are identical, each behind a fresh ``jax.jit`` wrapper, so JAX's
# in-memory cache never hits and every test recompiles them. The
# persistent cache (same directory the program uses) turns the repeats
# into reads, which is what lets the serial 870 s gate reach its later
# tests. It must be placed before the first compile, and the default
# one-second floor would skip nearly every compile here.
from gofr_tpu.tpu.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

import pytest  # noqa: E402


@pytest.fixture()
def mock_container():
    from gofr_tpu.container import new_mock_container
    return new_mock_container()


@pytest.fixture(scope="session")
def cpu_mesh():
    """2×4 dp×tp mesh over the 8 virtual CPU devices."""
    return jax.make_mesh((2, 4), ("dp", "tp"))


@pytest.fixture(scope="session")
def graftcheck_repo_scan(tmp_path_factory):
    """One cold full-repo graftcheck scan, shared by every test that
    needs a no-baseline repo report or a warm cache — the scan is the
    single most expensive fixture in the suite, so pay it exactly once.
    Returns ``(cache_path, cold_report, cold_seconds)``; the cache file
    is a throwaway so the repo's own ``.graftcheck_cache.json`` (and the
    committed baseline) stay untouched."""
    import time as _time

    from gofr_tpu.analysis import engine
    from gofr_tpu.analysis.rules import default_rules

    cache = tmp_path_factory.mktemp("graftcheck") / "cache.json"
    t0 = _time.perf_counter()
    cold = engine.run(paths=[engine.PACKAGE], rules=default_rules(),
                      baseline={}, cache_path=cache)
    cold_secs = _time.perf_counter() - t0
    return cache, cold, cold_secs
