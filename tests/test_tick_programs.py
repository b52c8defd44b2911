"""The tick seam of the generation engine: one builder for the decode
tick (``_tick_fn``), one function that calls it (``_run_tick``), and the
three callers that must agree on its keys (``warmup``,
``prewarm_operating_point``, ``_dispatch_tick``).

(a) the bias is a part of one program, not a second program: a biased
    tick whose bias is all zero emits the unbiased tick's tokens from
    the same state, on both cache kinds, greedy and sampled;
(b) what the warm-ups compile is what serving asks for: after
    ``warmup`` and ``prewarm_operating_point`` greedy, sampled and
    constrained requests compile nothing on the serving path;
(c) a constrained tick of a module that counts its steps returns the
    counters like every other tick.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.container import new_mock_container
from gofr_tpu.models import llama, mla_moe
from gofr_tpu.tpu.autotune import OperatingPoint
from gofr_tpu.tpu.generate import GenerationEngine, Sampling

PAGE = 8
KINDS = {"dense": {}, "paged": {"paged_kv": True, "kv_page": PAGE,
                                "ragged_attn": "off"}}
CASES = [(kind, sampled) for kind in KINDS for sampled in (False, True)]
CASE_IDS = [f"{kind}-{'sampled' if sampled else 'greedy'}"
            for kind, sampled in CASES]
LETTERS = {"type": "regex", "pattern": "[a-z]{12}"}


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.config("tiny")
    return cfg, llama.init(cfg, jax.random.PRNGKey(0))


def engine_for(cfg, params, kind, module=None, **kwargs):
    container = new_mock_container()
    kwargs.setdefault("max_slots", 4)
    kwargs.setdefault("max_len", 64)
    kwargs.setdefault("prompt_buckets", (8, 16))
    if module is not None:
        kwargs["model_module"] = module
    return GenerationEngine(cfg, params, logger=container.logger,
                            metrics=container.metrics, **KINDS[kind],
                            **kwargs)


# -- (a) an all-zero bias changes no token ---------------------------------------

def admit_two(engine, temperature):
    """Two prompts into slots 0 and 1 through the engine's own prefill
    and insert, as ``_admit_pending`` would: bucket 16, three pages a
    slot on the pool (two of the prompt, one to decode into)."""
    nb, lb = 2, 16
    prompts = [[5, 9, 2, 7, 1, 3, 8, 4, 6, 11, 13], [21, 22, 23, 24, 25]]
    padded = np.zeros((nb, lb), np.int32)
    for row, prompt in enumerate(prompts):
        padded[row, :len(prompt)] = prompt
    dev = dict(padded=jnp.asarray(padded),
               lengths=jnp.asarray([len(p) for p in prompts], jnp.int32),
               slots=jnp.asarray([0, 1], jnp.int32),
               temps=jnp.full((nb,), temperature, jnp.float32),
               top_ks=jnp.zeros((nb,), jnp.int32),
               top_ps=jnp.ones((nb,), jnp.float32),
               seeds=jnp.asarray([11, 12], jnp.uint32))
    if engine.paged:
        ids = np.asarray(engine._pool.alloc(6), np.int32).reshape(2, 3)
        engine._tables["kv"][:2, :3] = ids
        engine._table_version += 1
        dev["flat_ids"] = jnp.asarray(ids[:, :2].reshape(-1))
    first, small, keys = engine._run_prefill(nb, lb, dev)
    engine._run_insert(nb, lb, 0, dev, first, small, keys)
    return [len(p) for p in prompts]


@pytest.mark.parametrize("kind,sampled", CASES, ids=CASE_IDS)
def test_zero_bias_emits_the_unbiased_ticks_tokens(tiny, kind, sampled):
    cfg, params = tiny
    active = np.zeros((4,), bool)
    active[:2] = True
    emitted = {}
    for biased in (False, True):
        engine = engine_for(cfg, params, kind, steps_per_tick=2)
        fills = admit_two(engine, 0.8 if sampled else 0.0)
        if biased:
            mask = jnp.asarray(active.astype(np.int32))
            bias = jnp.zeros((4, cfg.vocab_size), jnp.float32)
        else:
            mask, bias = jnp.asarray(active), None
        ticks = []
        for k in (2, 1, 2):
            tokens, counts = engine._run_tick(k, sampled, mask, bias=bias)
            assert counts == ()
            ticks.append(np.asarray(tokens))
            fills = [fill + k for fill in fills]
        emitted[biased] = np.concatenate(ticks)
        assert {key[2] for key in engine._tick_fns} == {biased}
        assert np.asarray(engine.cache_len).tolist() == fills + [0, 0]
    assert emitted[False].shape == (5, 4)
    np.testing.assert_array_equal(emitted[True], emitted[False])
    # the active rows moved, the frozen rows kept their token
    assert len({tuple(row[:2]) for row in emitted[False]}) > 1
    assert (emitted[False][:, 2:] == 0).all()


# -- (b) the three callers agree on keys -----------------------------------------

@pytest.mark.parametrize("kind,sampled", CASES, ids=CASE_IDS)
def test_warmed_engine_serves_every_tick_form_without_compiling(
        tiny, kind, sampled):
    """``sampled`` is what ``warmup`` is asked for: where it is not,
    ``prewarm_operating_point`` has the sampled ticks to add, and in
    both cases the constrained forms."""
    cfg, params = tiny

    async def main():
        engine = engine_for(cfg, params, kind, max_len=256,
                            steps_per_tick=2)
        await engine.warmup(prompt_counts=tuple(engine._n_ladder),
                            sampling=sampled)
        warm = set(engine._tick_fns)
        assert {key[1] for key in warm} == ({False, True} if sampled
                                            else {False})
        assert not any(key[2] for key in warm)
        done = await engine.prewarm_operating_point(OperatingPoint(
            prompt_buckets=engine.prompt_buckets,
            steps_per_tick=engine.steps_per_tick))
        assert done["compiled"] > 0
        prewarmed = set(engine._tick_fns)
        assert warm < prewarmed
        served = []
        run_tick = engine._run_tick

        def spy(k, sampled, active, bias=None, state=None):
            served.append((k, sampled, bias is not None))
            return run_tick(k, sampled, active, bias=bias, state=state)

        engine._run_tick = spy
        await engine.start()
        try:
            # a tick is sampled as soon as one of its rows is: greedy
            # and sampled traffic in turn, each with a constrained request
            outs = []
            for sampling in (None, Sampling(temperature=0.8, seed=3)):
                outs += await asyncio.wait_for(asyncio.gather(
                    engine.generate([1, 2, 3], max_new_tokens=9,
                                    sampling=sampling),
                    engine.generate([9] * 12, max_new_tokens=6,
                                    sampling=sampling,
                                    response_format=LETTERS)), 120.0)
        finally:
            await engine.stop()
        assert [len(out) for out in outs] == [9, 6, 9, 6]
        for out in outs[1::2]:
            assert all(ord("a") <= token <= ord("z") for token in out)
        stats = engine.stats()
        assert stats["compiles"]["serving"] == 0, engine._compile_events
        assert set(served) <= prewarmed and set(engine._tick_fns) == prewarmed
        # all four forms of the tick ran
        assert {(key[1], key[2]) for key in served} == {
            (False, False), (False, True), (True, False), (True, True)}
        assert stats["constrained"]["ticks"] > 0

    asyncio.run(main())


# -- (c) a constrained tick counts its steps -------------------------------------

def test_constrained_tick_of_a_counting_module_returns_its_counters():
    cfg = mla_moe.config("tiny", dtype=jnp.float32, n_held_experts=8,
                         expert_rank=1)
    params = mla_moe.init(cfg, jax.random.PRNGKey(0))

    async def serve():
        engine = engine_for(cfg, params, "paged", module=mla_moe)
        assert engine._step_counters
        await engine.start()
        try:
            out = await asyncio.wait_for(engine.generate(
                [3, 4, 5, 6, 7], max_new_tokens=6,
                response_format=LETTERS), 120.0)
            return out, engine.stats(), sorted(engine._tick_fns)
        finally:
            await engine.stop()

    out, stats, keys = asyncio.run(serve())
    assert len(out) == 6
    # every tick was a constrained one
    assert keys and all(biased for _, _, biased in keys)
    # 5 decode steps (the first token is the prefill's) x the expert
    # layers x top-k: a tick that dropped its counters leaves no "moe"
    moe = stats["moe"]
    assert moe["routed_pairs"] == 5 * cfg.n_moe_layers * cfg.top_k
    assert moe["layer_steps"] == 5 * cfg.n_moe_layers
