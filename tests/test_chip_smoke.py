"""``chip_smoke.py`` cannot rot, and its contract holds without a chip.

The script itself demands a TPU; its phase functions take a size, so the
same code runs here at ``tiny`` on the CPU (the Pallas kernels through
the interpreter). That is also the rehearsal to run before spending chip
time on a change to the script. The compile-cache placement it relies on
is checked both ways: the deployment's directory and no other, or the
fixed directory beside the package.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def run():
    return chip_smoke.Run()


def _phase_lines(capsys):
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith('{"phase"')]


def test_kernels_phase_at_tiny(run, capsys):
    chip_smoke.phase_kernels(run, "tiny", interpret=True)
    (line,) = _phase_lines(capsys)
    assert line["ok"] and line["interpret"] and line["platform"] == "cpu"
    assert {name.split()[0] for name in line["kernels"]} == {
        "flash_attention", "ragged_decode", "ragged_verify"}
    # the interpreter reproduces the oracle's rounding exactly
    assert line["kernels"]["ragged_decode 4:2 int8"]["max_abs_err"] == 0.0


def test_classify_phase_at_tiny(run, capsys):
    # another test file in this worker may have left the variable set
    before = os.environ.get("RESNET_PRESET")
    chip_smoke.phase_classify(run, "tiny")
    (line,) = _phase_lines(capsys)
    assert line["phase"] == "classify" and line["model"] == "resnet-tiny"
    assert line["coalesced_executes"] >= 1
    assert line["serving_compiles"] == 0
    assert line["compile_cache"]["dir"] == run.cache_dir
    assert os.environ.get("RESNET_PRESET") == before   # the phase restores it


def test_generate_phase_at_tiny(run, capsys):
    chip_smoke.phase_generate(run, "tiny")
    (line,) = _phase_lines(capsys)
    spec = chip_smoke.SIZES["tiny"]
    assert line["phase"] == "generate" and line["attn_path"] == "gather"
    assert "cpu" in line["attn_why"]
    assert line["streams"] >= 2 * spec["max_slots"]
    assert line["tokens_per_stream"] == spec["max_new_tokens"]
    assert line["serving_compiles"] == 0 and line["failed_ticks"] == 0
    assert line["ttft_count"] >= line["streams"]
    assert line["tokenizer"] in ("c++", "python")


def test_a_failed_check_raises(run):
    """No path turns a failure into a result line."""
    with pytest.raises(chip_smoke.SmokeFailure, match="exceeds"):
        chip_smoke._agree("probe", [[1.0, 2.0]], [[1.0, 2.5]])


def test_no_arguments_demands_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert done.returncode != 0
    assert "needs a TPU" in done.stderr and "'cpu'" in done.stderr
    assert '"ok"' not in done.stdout


def test_compile_cache_unset_is_the_checkout(monkeypatch):
    import jax

    from gofr_tpu.tpu.compile_cache import configure_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(ROOT, ".jax_cache")
    assert configure_compile_cache() == fixed
    assert jax.config.jax_compilation_cache_dir == fixed
    with open(os.path.join(ROOT, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_compile_cache_env_dir_and_no_other(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there — through
    jit and through AOT ``.lower().compile()`` alike — and the function
    sets no other directory. The checkout's ``.jax_cache`` is shared
    with every other test worker, which may write to it meanwhile, so
    the subprocess's two programs carry names no other program has and
    only entries of those names are looked for."""
    probe = (
        "import os, jax, jax.numpy as jnp\n"
        "from gofr_tpu.tpu.compile_cache import configure_compile_cache\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "print(configure_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "def cache_env_probe_affine(x): return x * 2 + 1\n"
        "def cache_env_probe_square(x): return x @ x\n"
        "jax.jit(cache_env_probe_affine)(jnp.ones((8, 8)))\n"
        "jax.jit(cache_env_probe_square).lower(jnp.ones((8, 8))).compile()\n")

    def mine(directory):
        return sorted(name for name in os.listdir(directory)
                      if "cache_env_probe" in name) \
            if os.path.isdir(directory) else []

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    done = subprocess.run([sys.executable, "-c", probe], env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.split() == [str(tmp_path), str(tmp_path)]
    written = mine(tmp_path)
    assert any("affine" in name for name in written), os.listdir(tmp_path)
    assert any("square" in name for name in written), os.listdir(tmp_path)
    assert mine(os.path.join(ROOT, ".jax_cache")) == []
