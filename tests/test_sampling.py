"""Sampling tests: ops/sampling math + per-request sampling and token
streaming through the continuous-batching engine (VERDICT r3 next #1)."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import llama
from gofr_tpu.ops.sampling import sample_batch, sample_logits
from gofr_tpu.tpu.generate import GenerationEngine, Sampling
from tests.test_generate_engine import _make_engine


@pytest.fixture(scope="module")
def setup():
    cfg = llama.config("tiny")
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


# -- ops-level ------------------------------------------------------------

def test_zero_temperature_is_argmax():
    logits = jnp.asarray([0.1, 3.0, -1.0, 2.9], jnp.float32)
    key = jax.random.PRNGKey(7)
    for _ in range(3):
        token = sample_logits(logits, jnp.float32(0.0), jnp.int32(0),
                              jnp.float32(1.0), key)
        assert int(token) == 1


def test_top_k_one_is_argmax_even_with_temperature():
    logits = jnp.asarray([0.1, 3.0, -1.0, 2.9], jnp.float32)
    for seed in range(5):
        token = sample_logits(logits, jnp.float32(5.0), jnp.int32(1),
                              jnp.float32(1.0), jax.random.PRNGKey(seed))
        assert int(token) == 1


def test_tiny_top_p_is_argmax():
    logits = jnp.asarray([0.0, 1.0, 5.0, 2.0], jnp.float32)
    for seed in range(5):
        token = sample_logits(logits, jnp.float32(2.0), jnp.int32(0),
                              jnp.float32(1e-6), jax.random.PRNGKey(seed))
        assert int(token) == 2


def test_top_k_restricts_support():
    logits = jnp.asarray([5.0, 4.9, 4.8, -10.0, -10.0], jnp.float32)
    seen = set()
    for seed in range(32):
        token = sample_logits(logits, jnp.float32(1.0), jnp.int32(3),
                              jnp.float32(1.0), jax.random.PRNGKey(seed))
        seen.add(int(token))
    assert seen <= {0, 1, 2}
    assert len(seen) > 1   # temperature 1 over near-ties must actually mix


def test_sample_batch_mixes_greedy_and_sampled_rows():
    logits = jnp.tile(jnp.asarray([[0.0, 2.0, 1.9, -5.0]], jnp.float32),
                      (3, 1))
    temps = jnp.asarray([0.0, 1.0, 0.0], jnp.float32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(3, dtype=jnp.uint32))
    tokens, new_keys = sample_batch(
        logits, temps, jnp.zeros((3,), jnp.int32), jnp.ones((3,)), keys)
    assert int(tokens[0]) == 1 and int(tokens[2]) == 1   # greedy rows
    assert new_keys.shape == (3, 2)
    assert not np.array_equal(np.asarray(new_keys[1]), np.asarray(keys[1]))


def test_sample_batch_deterministic_per_key():
    logits = jax.random.normal(jax.random.PRNGKey(3), (4, 64))
    temps = jnp.full((4,), 0.9, jnp.float32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(4, dtype=jnp.uint32))
    args = (logits, temps, jnp.zeros((4,), jnp.int32),
            jnp.full((4,), 0.9, jnp.float32), keys)
    t1, k1 = sample_batch(*args)
    t2, k2 = sample_batch(*args)
    assert np.array_equal(np.asarray(t1), np.asarray(t2))
    assert np.array_equal(np.asarray(k1), np.asarray(k2))


# -- engine-level ---------------------------------------------------------

def test_stream_matches_generate_greedy(setup):
    """Streamed tokens must equal the gather-all result token for token."""
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params)
        await engine.start()
        try:
            prompt = [1, 2, 3, 4]
            full = await asyncio.wait_for(
                engine.generate(prompt, max_new_tokens=6), 60.0)
            streamed = []
            stream = await engine.generate_stream(prompt, max_new_tokens=6)
            async for token in stream:
                streamed.append(token)
            assert streamed == full
        finally:
            await engine.stop()
    asyncio.run(main())


def test_sampled_generate_deterministic_with_seed(setup):
    """Same seed → same completion, across separate requests (the per-slot
    PRNG must not leak state between requests or depend on tick batching)."""
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params, steps_per_tick=4)
        await engine.start()
        try:
            sampling = Sampling(temperature=0.8, top_k=20, seed=42)
            out1 = await asyncio.wait_for(engine.generate(
                [5, 6, 7], max_new_tokens=8, sampling=sampling), 60.0)
            out2 = await asyncio.wait_for(engine.generate(
                [5, 6, 7], max_new_tokens=8, sampling=sampling), 60.0)
            assert out1 == out2
            other = await asyncio.wait_for(engine.generate(
                [5, 6, 7], max_new_tokens=8,
                sampling=Sampling(temperature=0.8, top_k=20, seed=43)), 60.0)
            assert len(other) == 8
        finally:
            await engine.stop()
    asyncio.run(main())


def test_mixed_batch_keeps_greedy_rows_greedy(setup):
    """A sampled request sharing ticks with a greedy one must not disturb
    the greedy request's tokens (they ride the sampled executable, where
    temp=0 rows resolve to argmax in-program)."""
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params)
        await engine.start()
        try:
            prompt = [1, 2, 3, 4, 5]
            ref = llama.generate(params, cfg,
                                 np.asarray([prompt], np.int32), 6)
            ref = [int(t) for t in np.asarray(ref)[0]]
            greedy_task = asyncio.ensure_future(engine.generate(
                prompt, max_new_tokens=6))
            sampled_task = asyncio.ensure_future(engine.generate(
                [9, 8], max_new_tokens=6,
                sampling=Sampling(temperature=1.2, seed=7)))
            greedy, sampled = await asyncio.wait_for(
                asyncio.gather(greedy_task, sampled_task), 120.0)
            assert greedy == ref
            assert len(sampled) == 6
        finally:
            await engine.stop()
    asyncio.run(main())


def test_stream_sampled_deterministic(setup):
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params)
        await engine.start()
        try:
            sampling = Sampling(temperature=0.7, top_p=0.9, seed=11)
            runs = []
            for _ in range(2):
                tokens = []
                stream = await engine.generate_stream(
                    [2, 4, 6], max_new_tokens=5, sampling=sampling)
                async for token in stream:
                    tokens.append(token)
                runs.append(tokens)
            assert runs[0] == runs[1]
            assert len(runs[0]) == 5
        finally:
            await engine.stop()
    asyncio.run(main())


def test_stream_eos_stops_early(setup):
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params)
        await engine.start()
        try:
            prompt = [1, 2, 3]
            free = await asyncio.wait_for(
                engine.generate(prompt, max_new_tokens=8), 60.0)
            eos = free[2]
            streamed = []
            stream = await engine.generate_stream(
                prompt, max_new_tokens=8, eos_id=eos)
            async for token in stream:
                streamed.append(token)
            assert streamed == free[:3]   # eos token included, then stop
        finally:
            await engine.stop()
    asyncio.run(main())


def test_stream_engine_failure_raises(setup):
    """A loop failure mid-request must surface as an exception on the
    stream, not a hang (pairs with _fail_outstanding queue push)."""
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params)
        real = engine._prefill_fn

        def exploding(nb, lb, biased=False):
            raise RuntimeError("injected stream failure")

        engine._prefill_fn = exploding
        await engine.start()
        try:
            with pytest.raises(RuntimeError, match="injected"):
                stream = await engine.generate_stream([1, 2],
                                                      max_new_tokens=3)
                async for _ in stream:
                    pass
        finally:
            engine._prefill_fn = real
            await engine.stop()
    asyncio.run(main())

def test_stream_validation_is_eager(setup):
    """A bad request must raise at generate_stream() call time — before
    any response bytes could have been written (code-review r4 finding)."""
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params)
        await engine.start()
        try:
            with pytest.raises(ValueError, match="exceeds largest bucket"):
                await engine.generate_stream(list(range(50)),
                                             max_new_tokens=4)
        finally:
            await engine.stop()
    asyncio.run(main())


def test_stream_cancel_frees_slot(setup):
    """Closing the stream iterator early (client disconnect) must free the
    slot instead of decoding the remaining budget into an unread queue."""
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params)
        await engine.start()
        try:
            stream = await engine.generate_stream([1, 2, 3],
                                                  max_new_tokens=40)
            got = []
            async for token in stream:
                got.append(token)
                if len(got) == 2:
                    break
            await stream.aclose()
            assert len(got) == 2
            for _ in range(100):
                if engine.active_slots == 0:
                    break
                await asyncio.sleep(0.05)
            assert engine.active_slots == 0
            assert engine.stats()["free_slots"] == engine.max_slots
            # the engine must still serve fresh requests afterwards
            out = await asyncio.wait_for(
                engine.generate([4, 5], max_new_tokens=3), 60.0)
            assert len(out) == 3
        finally:
            await engine.stop()
    asyncio.run(main())

def test_stream_cancel_before_first_iteration(setup):
    """TokenStream.cancel must release the request even if iteration never
    started (unstarted async-generator aclose can't run a finally)."""
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params)
        await engine.start()
        try:
            stream = await engine.generate_stream([1, 2], max_new_tokens=30)
            stream.cancel()   # before any __anext__
            await asyncio.sleep(0.3)
            for _ in range(100):
                if engine.active_slots == 0:
                    break
                await asyncio.sleep(0.05)
            assert engine.active_slots == 0
            out = await asyncio.wait_for(
                engine.generate([3], max_new_tokens=2), 60.0)
            assert len(out) == 2
        finally:
            await engine.stop()
    asyncio.run(main())


def test_multibucket_admission_failure_fails_all(setup):
    """If one bucket's prefill dispatch raises, requests admitted in the
    same batch for OTHER buckets must be failed too, not stranded
    (code-review r4 finding: slots are claimed for all buckets before any
    dispatch)."""
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params)
        boom = {"armed": True}
        real = engine._prefill_fn

        def exploding(nb, lb, biased=False):
            if boom["armed"]:
                raise RuntimeError("injected admission failure")
            return real(nb, lb, biased)

        engine._prefill_fn = exploding
        await engine.start()
        try:
            # bucket 8 and bucket 16 in one admission batch
            t_small = asyncio.ensure_future(
                engine.generate([1, 2], max_new_tokens=2))
            t_large = asyncio.ensure_future(
                engine.generate(list(range(12)), max_new_tokens=2))
            results = await asyncio.wait_for(
                asyncio.gather(t_small, t_large, return_exceptions=True),
                60.0)
            assert all(isinstance(r, RuntimeError) for r in results), results
            boom["armed"] = False
            out = await asyncio.wait_for(
                engine.generate([1, 2], max_new_tokens=2), 60.0)
            assert len(out) == 2
        finally:
            await engine.stop()
    asyncio.run(main())
