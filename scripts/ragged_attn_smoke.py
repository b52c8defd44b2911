#!/usr/bin/env python
"""Tier-1 ragged-paged-attention smoke (ISSUE 13): one process, tiny
model, Pallas kernel in interpret mode on CPU.

Gates every commit on the properties the fused kernel must never break,
cheap enough to run before the test sweep:

1. **Token identity** — greedy decode through the generation engine is
   token-identical dense vs gather-paged vs ragged (the kernel
   reproduces the gather oracle's reduce_precision rounding schedule,
   so any divergence is a kernel bug, not numerics drift).
2. **Ladder retirement** — with ragged active the compile ledger shows
   ONE decode-executable family (no per-gather-width entries) and the
   gather-width ladder collapses to the full table width.
3. **Sentinel skip** — NaN-poisoning every unreferenced pool page does
   not move the kernel's output (sentinel entries are never
   dereferenced, only length-masked away).

Prints ``ragged attn smoke: OK`` and exits 0, or raises with the
failing property. Budget: a few seconds on host CPU.
"""

import asyncio
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.container import new_mock_container
    from gofr_tpu.models import llama
    from gofr_tpu.ops.pallas import ragged_paged_decode_attention
    from gofr_tpu.tpu.generate import GenerationEngine

    cfg = llama.config("tiny")
    params = llama.init(cfg, jax.random.PRNGKey(0))
    prompts = [[3, 5, 7, 11, 2, 9], [4, 4, 8, 1]]
    budget = 8

    def build(**kw):
        container = new_mock_container()
        return GenerationEngine(
            cfg, params, max_slots=2, max_len=32, prompt_buckets=(8,),
            logger=container.logger, metrics=container.metrics, **kw)

    async def drive(engine):
        await engine.start()
        try:
            return [await asyncio.wait_for(
                engine.generate(p, max_new_tokens=budget), 60.0)
                for p in prompts]
        finally:
            await engine.stop()

    # 1. token identity: dense vs gather vs ragged
    dense = asyncio.run(drive(build()))
    gather = asyncio.run(drive(build(paged_kv=True, kv_page=8,
                                     ragged_attn="off")))
    ragged_eng = build(paged_kv=True, kv_page=8, ragged_attn="on")
    ragged = asyncio.run(drive(ragged_eng))
    assert gather == dense, f"gather diverged: {gather} vs {dense}"
    assert ragged == dense, f"ragged diverged: {ragged} vs {dense}"
    assert ragged_eng.attn_path == "ragged"

    # 2. ladder retirement: one executable family, one gather width
    ledger = ragged_eng.xlaz()["paged_kv"]
    widths = ledger["gather_widths"]
    assert widths == [ragged_eng.pages_per_slot], widths
    keys = ledger["decode_executables"]
    assert keys and len(
        {k.rstrip(")").split(", ")[-1] for k in keys}) == 1, keys

    # 3. sentinel skip: poisoned dead pages never reach the output
    num_pages, page, hkv, hd, hq = 8, 8, cfg.n_kv_heads, cfg.head_dim, \
        cfg.n_heads
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    k_pages = jax.random.normal(
        keys[0], (num_pages, page, hkv, hd), jnp.float32).astype(cfg.dtype)
    v_pages = jax.random.normal(
        keys[1], (num_pages, page, hkv, hd), jnp.float32).astype(cfg.dtype)
    q = jax.random.normal(keys[2], (1, 1, hq, hd),
                          jnp.float32).astype(cfg.dtype)
    k_new = jax.random.normal(keys[3], (1, hkv, hd),
                              jnp.float32).astype(cfg.dtype)
    v_new = jax.random.normal(keys[4], (1, hkv, hd),
                              jnp.float32).astype(cfg.dtype)
    table = np.full((1, 4), num_pages, np.int32)
    table[0, :2] = [0, 1]
    cache_len = jnp.asarray([13], jnp.int32)
    # the kernel reads the stacked (L, ...) pool: one plane is layer 0
    clean = ragged_paged_decode_attention(
        q, k_pages[None], v_pages[None], jnp.asarray(table), k_new, v_new,
        cache_len, 0)
    poisoned_k = np.asarray(k_pages, np.float32)
    poisoned_k[2:] = np.nan
    poisoned_v = np.asarray(v_pages, np.float32)
    poisoned_v[2:] = np.nan
    out = ragged_paged_decode_attention(
        q, jnp.asarray(poisoned_k).astype(cfg.dtype)[None],
        jnp.asarray(poisoned_v).astype(cfg.dtype)[None],
        jnp.asarray(table), k_new, v_new, cache_len, 0)
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all()), \
        "sentinel page NaN reached the kernel output"
    assert bool((out == clean).all()), "poisoned dead pages moved output"

    print("ragged attn smoke: OK")


if __name__ == "__main__":
    main()
