"""Digests of the model programs the benchmark's cells run, at the
published widths of ``benchmark/configs/*.json``: the lowered text for
the TPU (StableHLO with the Mosaic kernels inside; shapes only, nothing
compiled or allocated) of each generate configuration's ``prefill`` at
the largest row rung of every bucket and of its paged decode step with
counters. Equal digests on a parent and a change mean the chip compiles
the same programs, so a cell's device work cannot have moved: the proof
a PR gives for the cells it says it left alone.

``tests/test_program_digests.py`` holds the tree to
``tests/program_digests.json``. A PR that means to change a cell's
program writes the file anew and says so:

    python tests/program_digests.py --write        # this tree
    python tests/program_digests.py /path/to/parent/checkout

A Mosaic kernel's serialized body carries file paths and line numbers,
so each is read back as MLIR and hashed without debug locations.
"""

from __future__ import annotations

import base64
import hashlib
import importlib
import json
import os
import re
import sys
from typing import Any, Dict

STORED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "program_digests.json")
# rows of a decode step's pool: the text depends on it, a cell's own
# number does not matter to the comparison
POOL_PAGES = 4096


def canonical(text: str) -> str:
    """Lowered text with every Mosaic kernel body replaced by the hash
    of its MLIR assembly without debug locations."""
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def body(match):
        context = jax_mlir.make_ir_context()
        tpu.register_dialect(context)
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            assembly = module.operation.get_asm(enable_debug_info=False)
        return ('"body": "'
                + hashlib.sha256(assembly.encode()).hexdigest() + '"')

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)


def _digest(lowered) -> str:
    return hashlib.sha256(
        canonical(lowered.as_text()).encode()).hexdigest()[:16]


def config_digests(root: str, name: str) -> Dict[str, str]:
    """{program: digest} of one configuration of ``root``'s checkout."""
    import jax
    import jax.numpy as jnp

    with open(os.path.join(root, "benchmark", "configs",
                           name + ".json")) as handle:
        config = json.load(handle)
    module = importlib.import_module(f"gofr_tpu.models.{config['module']}")
    model = config["model"]
    overrides = {ours: tuple(config[theirs])
                 if isinstance(config[theirs], list) else config[theirs]
                 for ours, theirs in model.get("from_keys", {}).items()}
    cfg = module.config(model["preset"], **overrides)
    engine = config["engine"]
    params = jax.eval_shape(lambda key: module.init(cfg, key),
                            jax.random.key(0))
    slots, page = engine["max_slots"], engine["kv_page"]
    out: Dict[str, str] = {}

    def shape(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype)

    for bucket in engine["prompt_buckets"]:
        rows = max(1, min(slots,
                          engine.get("max_group_tokens", 1 << 30) // bucket))
        prefill = jax.jit(lambda p, t, l: module.prefill(
            p, cfg, t, module.init_cache(cfg, t.shape[0], t.shape[1]),
            lengths=l))
        out[f"{name}.prefill.{rows}x{bucket}"] = _digest(
            prefill.trace(params, shape(rows, bucket), shape(rows))
            .lower(lowering_platforms=("tpu",)))
    if not engine.get("paged_kv") or not hasattr(module, "cache_leaves"):
        return out
    columns = engine["max_len"] // page
    leaves = module.cache_leaves(cfg)
    if all(isinstance(leaf, tuple) for leaf in leaves.values()):
        # one cache kind: {leaf: (tail, dtype)}, one table
        pool: Any = {leaf: shape(cfg.n_layers, POOL_PAGES, page, *tail,
                                 dtype=dtype)
                     for leaf, (tail, dtype) in leaves.items()}
        table: Any = shape(slots, columns)
        kwargs = {}
    else:
        # a pool and a table a layer kind, read through the ragged kernel
        # where the configuration expects it; a per-slot kind is rows
        # of slots and has no table
        pool = {kind: {leaf: shape(spec["layers"],
                                   *((slots,) if spec.get("per_slot")
                                     else (POOL_PAGES, page)),
                                   *tail, dtype=dtype)
                       for leaf, (tail, dtype) in spec["leaves"].items()}
                for kind, spec in leaves.items()}
        table = {kind: shape(slots, columns)
                 for kind, spec in leaves.items()
                 if not spec.get("per_slot")}
        kwargs = {"ragged": config.get("expect_attn_path",
                                       "ragged") == "ragged"}
    step = jax.jit(lambda p, t, pl, tb, cl, a: module.decode_step_paged(
        p, cfg, t, pl, tb, cl, a, counters=True, **kwargs))
    out[f"{name}.decode_paged"] = _digest(
        step.trace(params, shape(slots), pool, table, shape(slots),
                   shape(slots, dtype=bool))
        .lower(lowering_platforms=("tpu",)))
    return out


def generate_configs(root: str):
    """Names of the generate configurations ``root``'s benchmark has."""
    with open(os.path.join(root, "BENCHMARK.json")) as handle:
        entries = json.load(handle)["configs"]
    names = []
    for entry in entries:
        with open(os.path.join(root, entry["file"])) as handle:
            if json.load(handle).get("kind") == "generate":
                names.append(entry["name"])
    return names


def main() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    args = [arg for arg in sys.argv[1:] if arg != "--write"]
    root = os.path.abspath(args[0]) if args else os.path.dirname(
        os.path.dirname(STORED))
    sys.path.insert(0, root)
    out: Dict[str, str] = {}
    for name in generate_configs(root):
        try:
            out.update(config_digests(root, name))
        except ModuleNotFoundError as error:     # a parent without it
            print(f"# {name}: {error}", file=sys.stderr)
    text = json.dumps(out, indent=1) + "\n"
    if "--write" in sys.argv[1:]:
        with open(STORED, "w") as handle:
            handle.write(text)
    sys.stdout.write(text)


if __name__ == "__main__":
    main()
