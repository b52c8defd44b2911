"""Continuous-batching generation engine tests (tiny Llama on CPU)."""

import asyncio

import jax
import numpy as np
import pytest

from gofr_tpu.container import new_mock_container
from gofr_tpu.models import llama
from gofr_tpu.tpu.generate import GenerationEngine


@pytest.fixture(scope="module")
def setup():
    cfg = llama.config("tiny")
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _make_engine(cfg, params, **kwargs):
    container = new_mock_container()
    kwargs.setdefault("max_slots", 4)
    kwargs.setdefault("max_len", 64)
    kwargs.setdefault("prompt_buckets", (8, 16))
    return GenerationEngine(cfg, params, logger=container.logger,
                            metrics=container.metrics, **kwargs)


def test_single_generate_matches_reference(setup):
    """Engine output must equal the fused lax.scan generate (greedy)."""
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params)
        await engine.start()
        try:
            prompt = [1, 2, 3, 4, 5]
            out = await asyncio.wait_for(
                engine.generate(prompt, max_new_tokens=6), 60.0)
            ref = llama.generate(params, cfg,
                                 np.asarray([prompt], np.int32), 6)
            assert out == [int(t) for t in np.asarray(ref)[0]]
        finally:
            await engine.stop()
    asyncio.run(main())


def test_concurrent_generates_share_decode_steps(setup):
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params)
        await engine.start()
        try:
            prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
            outs = await asyncio.wait_for(asyncio.gather(*[
                engine.generate(p, max_new_tokens=5) for p in prompts]),
                120.0)
            for p, out in zip(prompts, outs):
                assert len(out) == 5
                ref = llama.generate(params, cfg,
                                     np.asarray([p], np.int32), 5)
                assert out == [int(t) for t in np.asarray(ref)[0]], p
            # continuous batching actually shared ticks: 3 requests × 4
            # decode tokens each needed ≤ ~12 sequential steps if serial;
            # shared slots must do far fewer
            assert engine.stats()["decode_steps"] <= 8
        finally:
            await engine.stop()
    asyncio.run(main())


def test_more_requests_than_slots(setup):
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params, max_slots=2)
        await engine.start()
        try:
            outs = await asyncio.wait_for(asyncio.gather(*[
                engine.generate([i + 1], max_new_tokens=3)
                for i in range(5)]), 120.0)
            assert all(len(out) == 3 for out in outs)
            assert engine.stats()["free_slots"] == 2
        finally:
            await engine.stop()
    asyncio.run(main())


def test_eos_stops_early(setup):
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params)
        await engine.start()
        try:
            prompt = [1, 2, 3]
            free_run = await engine.generate(prompt, max_new_tokens=8)
            eos = free_run[2]  # force stop at the 3rd token
            stopped = await engine.generate(prompt, max_new_tokens=8,
                                            eos_id=eos)
            assert stopped == free_run[:3]
        finally:
            await engine.stop()
    asyncio.run(main())


def test_rejects_oversized_prompts(setup):
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params)
        await engine.start()
        try:
            with pytest.raises(ValueError):
                await engine.generate(list(range(17)), max_new_tokens=2)
            with pytest.raises(ValueError):
                await engine.generate([1], max_new_tokens=1000)
        finally:
            await engine.stop()
    asyncio.run(main())


def test_multi_step_scheduling_matches_reference(setup):
    """steps_per_tick=4 fuses 4 decode steps per host round trip; output
    must be identical to single-step (greedy is deterministic)."""
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params, steps_per_tick=4)
        await engine.start()
        try:
            prompt = [1, 2, 3, 4]
            out = await asyncio.wait_for(
                engine.generate(prompt, max_new_tokens=7), 60.0)
            ref = llama.generate(params, cfg,
                                 np.asarray([prompt], np.int32), 7)
            assert out == [int(t) for t in np.asarray(ref)[0]]
            # 7 tokens: 1 from prefill + 6 decode → ceil(6/4)=2 ticks
            assert engine.stats()["decode_steps"] == 2
        finally:
            await engine.stop()
    asyncio.run(main())


def test_mesh_engine_matches_single_device(setup):
    """BASELINE.md config 5 shape: tensor-parallel engine over a dp×tp mesh
    must produce token-identical output to the single-device engine —
    params sharded with llama_param_specs, KV cache with llama_cache_specs
    (slots on dp, kv-heads on tp)."""
    cfg, params = setup
    from gofr_tpu.parallel import make_mesh
    mesh = make_mesh({"dp": 4, "tp": 2})

    async def main():
        single = _make_engine(cfg, params)
        sharded = _make_engine(cfg, params, mesh=mesh)
        assert sharded.max_slots % 4 == 0
        # cache actually carries the mesh sharding
        spec = sharded.cache["k"].sharding.spec
        assert tuple(spec) == (None, "dp", None, "tp", None)
        await single.start()
        await sharded.start()
        try:
            prompts = [[1, 2, 3], [9, 8, 7, 6], [4, 4], [5]]
            ref = await asyncio.wait_for(asyncio.gather(*[
                single.generate(p, max_new_tokens=6) for p in prompts]),
                120.0)
            out = await asyncio.wait_for(asyncio.gather(*[
                sharded.generate(p, max_new_tokens=6) for p in prompts]),
                120.0)
            assert out == ref
        finally:
            await single.stop()
            await sharded.stop()
    asyncio.run(main())


def test_ttft_histogram_recorded_per_request(setup):
    """Every request's time-to-first-token (admission wait + prefill —
    the first token is sampled in the prefill executable) lands in
    app_tpu_ttft — the operator-facing TTFT signal (r5; previously only
    the bench measured TTFT, externally)."""
    cfg, params = setup

    async def main():
        container = new_mock_container()
        engine = GenerationEngine(cfg, params, max_slots=2, max_len=64,
                                  prompt_buckets=(8,),
                                  logger=container.logger,
                                  metrics=container.metrics)
        await engine.start()
        try:
            await asyncio.wait_for(asyncio.gather(*[
                engine.generate([i + 1, i + 2], max_new_tokens=3)
                for i in range(3)]), 120.0)
            count = container.metrics.value("app_tpu_ttft",
                                            model="generate")
            assert count == 3, count
            # streamed requests record it too (on first published token)
            stream = await engine.generate_stream([5, 6],
                                                  max_new_tokens=2)
            async for _ in stream:
                break
            stream.cancel()
            assert container.metrics.value("app_tpu_ttft",
                                           model="generate") == 4
        finally:
            await engine.stop()
    asyncio.run(main())


def test_engine_warmup_precompiles(setup):
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params, steps_per_tick=4)
        await engine.warmup(prompt_counts=(1, 2))
        assert sorted(engine._tick_fns) == [(1, False, False, None),
                                            (2, False, False, None),
                                            (4, False, False, None)]
        assert set(engine._prefill_fns) == {
            (1, 8, False), (1, 16, False), (2, 8, False), (2, 16, False)}
        await engine.start()
        try:
            out = await asyncio.wait_for(
                engine.generate([1, 2, 3], max_new_tokens=5), 60.0)
            assert len(out) == 5
        finally:
            await engine.stop()
    asyncio.run(main())


def test_warmup_defaults_to_startup_window_subset(setup):
    """ADVICE r4 medium: default warmup must not compile the full
    k x window cross-product — only the startup-reachable rungs; the
    full matrix is opt-in via windows="all"."""
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params, max_len=512,
                              prompt_buckets=(8, 16), steps_per_tick=4)
        assert engine._window_ladder == [128, 256, None]
        await engine.warmup(prompt_counts=(1,))
        warmed = {w for (_, _, _, w) in engine._tick_fns}
        assert warmed == {128}, warmed   # bucket 16 + k 4 fits rung 128

        full = _make_engine(cfg, params, max_len=512,
                            prompt_buckets=(8, 16), steps_per_tick=4)
        await full.warmup(prompt_counts=(1,), windows="all")
        assert {w for (_, _, _, w) in full._tick_fns} == {128, 256, None}
    asyncio.run(main())


def test_warmup_rejects_unknown_rungs(setup):
    """ADVICE r4 low: a windows/ks filter that matches nothing must raise,
    not silently warm zero executables."""
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params, max_len=512, steps_per_tick=4)
        with pytest.raises(ValueError, match="window-ladder"):
            await engine.warmup(windows=(999,))
        with pytest.raises(ValueError, match="k-ladder"):
            await engine.warmup(ks=(3,))
        with pytest.raises(ValueError, match="window-ladder"):
            await engine.warmup(windows=())     # empty = warms nothing
        with pytest.raises(ValueError, match="k-ladder"):
            await engine.warmup(ks=())
        with pytest.raises(ValueError, match="sentinel"):
            await engine.warmup(windows="ALL")
    asyncio.run(main())


def test_warmup_after_start_rejected(setup):
    """warmup() mutates donated device state; racing the engine loop would
    dispatch against invalidated buffers (ADVICE r2)."""
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params)
        await engine.start()
        try:
            with pytest.raises(RuntimeError):
                await engine.warmup()
        finally:
            await engine.stop()
    asyncio.run(main())


def test_saturated_engine_keeps_fused_ticks(setup):
    """VERDICT r2 weak #2: a fully loaded engine (pending queue non-empty,
    zero free slots) must keep multi-step ticks — K drops to 1 only when a
    pending request could actually be admitted."""
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params, max_slots=2, steps_per_tick=4)
        await engine.start()
        try:
            outs = await asyncio.wait_for(asyncio.gather(*[
                engine.generate([i + 1, i + 2], max_new_tokens=9)
                for i in range(4)]), 120.0)
            assert all(len(out) == 9 for out in outs)
            # 4 requests × 8 decode tokens in 2 waves of 2 slots. Fused
            # K=4 ticks → 2 ticks per wave ≈ 4-6 ticks total. The old
            # K=1-under-saturation bug needed 8 ticks for wave 1 alone.
            assert engine.stats()["decode_steps"] <= 7, engine.stats()
        finally:
            await engine.stop()
    asyncio.run(main())


def test_non_power_of_two_slots(setup):
    """ADVICE r2 medium: max_slots=3 (non-power-of-2) must admit a full
    3-request group without StopIteration killing the loop."""
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params, max_slots=3)
        assert engine._n_ladder[-1] == 3
        await engine.warmup(prompt_counts=(3,))
        await engine.start()
        try:
            outs = await asyncio.wait_for(asyncio.gather(*[
                engine.generate([i + 1] * 3, max_new_tokens=4)
                for i in range(3)]), 120.0)
            assert all(len(out) == 4 for out in outs)
        finally:
            await engine.stop()
    asyncio.run(main())


def test_loop_failure_fails_futures_and_recovers(setup):
    """ADVICE r2 medium: an exception inside the engine loop must fail the
    outstanding callers (not hang them) and leave the engine serving."""
    cfg, params = setup

    async def main():
        engine = _make_engine(cfg, params)
        boom = {"armed": True}
        real = engine._prefill_fn

        def exploding(nb, lb, biased=False):
            if boom["armed"]:
                raise RuntimeError("injected prefill failure")
            return real(nb, lb, biased)

        engine._prefill_fn = exploding
        await engine.start()
        try:
            with pytest.raises(RuntimeError, match="injected"):
                await asyncio.wait_for(
                    engine.generate([1, 2], max_new_tokens=3), 60.0)
            boom["armed"] = False
            out = await asyncio.wait_for(
                engine.generate([1, 2], max_new_tokens=3), 60.0)
            assert len(out) == 3
            assert engine.stats()["free_slots"] == engine.max_slots - 0
        finally:
            await engine.stop()
    asyncio.run(main())


def test_tick_failure_resets_device_state_and_recovers(setup):
    """A failure AFTER decode dispatch (donated cache consumed) must not
    poison the engine: outstanding callers fail, device state is rebuilt,
    and the next request succeeds with correct tokens (code-review r3
    finding on _fail_outstanding)."""
    cfg, params = setup
    import numpy as np

    async def main():
        engine = _make_engine(cfg, params)
        real = engine._tick_fn
        boom = {"armed": True}

        def exploding(k, sampled, biased, width):
            fn = real(k, sampled, biased, width)

            def wrapped(*args):
                out = fn(*args)   # consumes the donated cache for real
                if boom["armed"]:
                    raise RuntimeError("injected post-dispatch failure")
                return out
            return wrapped

        engine._tick_fn = exploding
        await engine.start()
        try:
            with pytest.raises(RuntimeError, match="post-dispatch"):
                await asyncio.wait_for(
                    engine.generate([1, 2, 3], max_new_tokens=4), 60.0)
            boom["armed"] = False
            # device state was rebuilt — a fresh request must produce the
            # same tokens as a clean engine
            out = await asyncio.wait_for(
                engine.generate([1, 2, 3], max_new_tokens=4), 60.0)
            ref = llama.generate(params, cfg,
                                 np.asarray([[1, 2, 3]], np.int32), 4)
            assert out == [int(t) for t in np.asarray(ref)[0]]
            assert engine.stats()["free_slots"] == engine.max_slots
        finally:
            await engine.stop()
    asyncio.run(main())


def test_exhausted_slot_does_not_stall_tick(setup):
    """ADVICE r2 low: one budget-exhausted slot (remaining covered by
    in-flight tokens) must not skip the tick for everyone — other active
    slots keep decoding that iteration."""
    cfg, params = setup

    async def main():
        # steps_per_tick=4 with budgets 2 and 16: the short slot is
        # budget-covered after one K=2-capped tick while the long one
        # still wants tokens. Completion of both proves no permanent
        # stall; the step-count bound proves ticks kept fusing.
        engine = _make_engine(cfg, params, steps_per_tick=4)
        await engine.start()
        try:
            long_req = engine.generate([1, 2, 3], max_new_tokens=13)
            short_req = engine.generate([7, 8], max_new_tokens=2)
            outs = await asyncio.wait_for(
                asyncio.gather(long_req, short_req), 120.0)
            assert len(outs[0]) == 13 and len(outs[1]) == 2
            # 12 decode tokens for the long slot; if the exhausted short
            # slot skipped ticks we'd need many extra iterations
            assert engine.stats()["decode_steps"] <= 8, engine.stats()
        finally:
            await engine.stop()
    asyncio.run(main())


def test_inactive_slots_frozen(setup):
    """ADVICE r1: a freed slot's cache_len must not grow while other slots
    keep decoding. Run a short and a long request concurrently: the short
    one's slot must sit at exactly prompt+budget when the long one ends."""
    cfg, params = setup
    import numpy as np

    async def main():
        engine = _make_engine(cfg, params)
        await engine.start()
        try:
            long_req = asyncio.ensure_future(
                engine.generate([1, 2, 3, 4, 5], max_new_tokens=16))
            short_req = asyncio.ensure_future(
                engine.generate([7, 8], max_new_tokens=2))
            await asyncio.wait_for(
                asyncio.gather(long_req, short_req), 120.0)
            lens = sorted(int(x) for x in np.asarray(engine.cache_len))
            # cache holds prompt + budget-1 positions (the final emitted
            # token is never scattered): long 5+15=20, short 2+1=3
            # (frozen there while long kept decoding), rest 0
            assert lens == [0, 0, 3, 20]
        finally:
            await engine.stop()
    asyncio.run(main())


def test_window_ladder_token_identical(setup):
    """Fill-bounded attention (window ladder) must not change tokens: an
    engine whose max_len spans several window rungs produces exactly the
    reference sequence, and actually exercises a sub-full rung."""
    cfg, params = setup

    async def main():
        # max_len 256 > 128 → ladder [128, None]; fills stay < 128 so
        # every tick should run the 128-window executable
        engine = _make_engine(cfg, params, max_len=128, window_ladder=True)
        engine.max_len = 128
        assert engine._window_ladder == [None]  # 128 is not > 128
        engine2 = GenerationEngine(cfg, params, max_slots=4, max_len=256,
                                   prompt_buckets=(8, 16))
        assert engine2._window_ladder == [128, None]
        await engine2.start()
        try:
            prompt = [1, 2, 3, 4, 5]
            out = await asyncio.wait_for(
                engine2.generate(prompt, max_new_tokens=6), 60.0)
            ref = llama.generate(params, cfg,
                                 np.asarray([prompt], np.int32), 6)
            assert out == [int(t) for t in np.asarray(ref)[0]]
            # the sub-full rung was used (fills stayed far below 128)
            assert any(key[3] == 128 for key in engine2._tick_fns)
        finally:
            await engine2.stop()
    asyncio.run(main())


def test_engine_kv_int8_serves(setup):
    """int8 KV cache through the full engine path: prefill quantizes,
    insert scatters scale planes, decode dequantizes — output tokens match
    the fused generate under the same quantized-cache config."""
    cfg, params = setup
    import dataclasses
    cfg8 = dataclasses.replace(cfg, kv_int8=True)

    async def main():
        engine = _make_engine(cfg8, params)
        await engine.start()
        try:
            prompt = [1, 2, 3, 4, 5]
            out = await asyncio.wait_for(
                engine.generate(prompt, max_new_tokens=6), 60.0)
            assert len(out) == 6
            ref = llama.generate(params, cfg8,
                                 np.asarray([prompt], np.int32), 6)
            assert out == [int(t) for t in np.asarray(ref)[0]]
            assert engine.cache["k"].dtype == jnp_int8()
            assert "ks" in engine.cache and "vs" in engine.cache
        finally:
            await engine.stop()

    def jnp_int8():
        import jax.numpy as jnp
        return jnp.int8
    asyncio.run(main())
