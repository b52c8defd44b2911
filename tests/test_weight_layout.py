"""The weights are laid out once, at engine start, as the steady decode
tick reads them (``GenerationEngine._lay_out_weights``).

The CPU's compiler wants the default layouts, so an engine built here
moves nothing and runs the programs it always ran. To hold the path the
chip takes, these tests make the compile that decides
(``generate.weight_formats``) ask for one stacked weight with its input
dimension minor, as the chip's compiler does for ``wq``, and go through
the engine's own re-laying from there:

(a) a re-laid engine serves the greedy tokens of one that was not, on
    both cache kinds, both model modules and a mesh; the caller's tree
    is still the caller's; ``stats()["weights"]`` says what moved;
(b) every executable is compiled for the leaves as they then are: a
    tick compiled after start is the one warm-up finds, and serving
    compiles nothing;
(c) the program that re-lays a leaf is compiled in the process that
    runs it, never read from the persistent cache nor written there.
"""

import asyncio
import logging

import jax
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

from gofr_tpu.container import new_mock_container
from gofr_tpu.models import llama, mla_moe
from gofr_tpu.tpu import generate
from gofr_tpu.tpu.generate import GenerationEngine

PAGED = {"paged_kv": True, "kv_page": 8, "ragged_attn": "off"}
# (model module, the stacked weight the forced compile turns, engine kind).
# mla_moe serves through the pool alone (the engine's dense cache is
# llama's init_cache), so it has no dense case
CASES = {
    "llama-dense": (llama, ("layers", "wq"), {}),
    "llama-paged": (llama, ("layers", "wq"), PAGED),
    "llama-mesh": (llama, ("layers", "wq"), {"mesh": {"tp": 2}}),
    "mla_moe-paged": (mla_moe, ("moe", "attn", "w_uq_n"), PAGED),
}
PROMPTS = [[5, 9, 2, 7, 1, 3, 8, 4, 6, 11, 13], [21, 22, 23], [4, 4]]
TURNED = Layout(major_to_minor=(0, 2, 1), tiling=())


def leaf_at(tree, path):
    for name in path:
        tree = tree[name]
    return tree


@pytest.fixture
def turn(monkeypatch):
    """``turn(path)``: make the deciding compile ask for the leaf at
    ``path`` with its last two dimensions swapped in memory, and for
    every other leaf as the compiler said."""
    decide = generate.weight_formats

    def turn(path):
        def turned(fn, operands, donate_argnums=()):
            formats, temp_bytes, _ = decide(fn, operands, donate_argnums)
            node = leaf_at(formats, path[:-1])
            node[path[-1]] = Format(TURNED, node[path[-1]].sharding)
            # the deciding program becomes the steady tick: one that
            # takes the leaves as this answer lays them
            compiled = jax.jit(
                fn, in_shardings=(formats,) + (None,) * (len(operands) - 1),
                donate_argnums=donate_argnums).lower(*operands).compile()
            return formats, temp_bytes, compiled

        monkeypatch.setattr(generate, "weight_formats", turned)

    return turn


def build(case):
    module, _, kind = CASES[case]
    kind = dict(kind)
    if "mesh" in kind:
        from gofr_tpu.parallel import make_mesh
        kind["mesh"] = make_mesh(kind["mesh"])
    cfg = module.config("tiny")
    params = module.init(cfg, jax.random.PRNGKey(0))
    container = new_mock_container()

    def engine():
        return GenerationEngine(
            cfg, params, max_slots=4, max_len=64, prompt_buckets=(8, 16),
            steps_per_tick=2, logger=container.logger,
            metrics=container.metrics,
            model_module=None if module is llama else module, **kind)

    return params, engine


def serve(engine, warm=False):
    async def main():
        if warm:
            await engine.warmup(prompt_counts=(1, 2, 4))
        await engine.start()
        try:
            return await asyncio.wait_for(asyncio.gather(*[
                engine.generate(prompt, max_new_tokens=7)
                for prompt in PROMPTS]), 120.0)
        finally:
            await engine.stop()
    return asyncio.run(main())


@pytest.mark.parametrize("case", CASES)
def test_relaid_engine_serves_the_same_greedy_tokens(
        turn, case):
    path = CASES[case][1]
    params, engine = build(case)
    plain = engine()
    assert plain.stats()["weights"]["relaid_leaves"] == 0
    want = serve(plain)

    turn(path)
    relaid = engine()
    theirs, ours = leaf_at(params, path), leaf_at(relaid.params, path)
    weights = relaid.stats()["weights"]
    assert (weights["relaid_leaves"], weights["relaid_bytes"]) == (
        1, theirs.nbytes)
    assert weights["temp_bytes"] > 0
    assert ours.format.layout.major_to_minor == (0, 2, 1)
    assert (ours.shape, ours.dtype) == (theirs.shape, theirs.dtype)
    assert serve(relaid) == want
    # a clone over the engine's leaves finds them as its tick wants them
    clone = relaid.shadow_clone()
    assert clone.stats()["weights"]["relaid_leaves"] == 0
    assert leaf_at(clone.params, path).format.layout == ours.format.layout

    # the caller's tree is neither consumed nor re-laid, and holds the
    # values the engine's does
    assert not theirs.is_deleted()
    assert theirs.format.layout.major_to_minor == (0, 1, 2)
    np.testing.assert_array_equal(np.asarray(theirs), np.asarray(ours))
    logits = jax.jit(lambda p: leaf_at(p, path).sum())(params)
    assert np.isfinite(float(logits))


def test_default_layouts_leave_the_callers_leaves_in_place():
    """Where the steady tick wants the layouts the leaves have (the
    CPU's compiler does), the engine's leaves are the arrays it was
    given: no copy, and every program the one it was."""
    params, engine = build("llama-paged")
    engine = engine()
    assert engine.stats()["weights"]["relaid_leaves"] == 0
    assert engine.stats()["weights"]["relaid_bytes"] == 0
    for ours, theirs in zip(jax.tree.leaves(engine.params),
                            jax.tree.leaves(params)):
        assert ours is theirs


PROGRAMS = ("decode_k", "prefill_batch", "insert")


@pytest.fixture
def log_compiles():
    """The flag itself, not ``jax.log_compiles()``: warm-up and dispatch
    compile on executor threads, and the context manager is the calling
    thread's alone."""
    jax.config.update("jax_log_compiles", True)
    yield
    jax.config.update("jax_log_compiles", False)


def compiled_programs(caplog):
    """Names of the engine's programs XLA was asked to compile, from
    ``jax_log_compiles``' records: a compile ``stats()["compiles"]``
    cannot see, because ``jit`` made it behind a builder's back."""
    return [name for record in caplog.records
            for name in PROGRAMS
            if f"Compiling jit({name})" in record.getMessage()]


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_a_tick_compiled_after_start_is_the_one_warmup_finds(
        turn, log_compiles, caplog, kind):
    """Everything is built after the leaves are put, and for one
    placement: a re-laid leaf is committed to its device and so is every
    output of a program that took it, fresh slot state and uploads are
    not, and ``jit`` would compile a tick a second time for the second
    kind of operands it meets, on the serving path. The steady tick is
    the program that decided the layouts: asked for, it is charged and
    compiles nothing. Another tick called before warm-up compiles once,
    warm-up finds both, and once warm the engine compiles nothing it
    serves with."""
    turn(("layers", "wq"))
    _, engine = build(f"llama-{kind}")
    engine = engine()
    assert engine.stats()["compiles"] == {"warmup": 0, "serving": 0}
    mask = engine._jnp.zeros((engine.max_slots,), bool)
    caplog.clear()                   # the deciding compile is a decode_k too
    with caplog.at_level(logging.WARNING,
                         logger="jax._src.interpreters.pxla"):
        engine._run_tick(2, False, mask)
        assert engine.stats()["compiles"]["serving"] == 1
        assert compiled_programs(caplog) == []
        early = {}
        for k in (2, 1, 1):
            # the state a tick returned is committed: no second compile
            engine._run_tick(k, False, mask)
            early[k] = engine._tick_fns[(k, False, False)]
        assert engine.stats()["compiles"]["serving"] == 2
        assert compiled_programs(caplog) == ["decode_k"]

        asyncio.run(engine.warmup(prompt_counts=(1, 2, 4)))
        assert {k: engine._tick_fns[(k, False, False)]
                for k in early} == early
        charged = [what for _, _, what in engine._compile_events]
        assert len(charged) == len(set(charged))
        warm = compiled_programs(caplog)
        assert len(warm) == len(charged) - 1, (warm, charged)

        serve(engine)
        assert compiled_programs(caplog) == warm
    assert engine.stats()["compiles"]["serving"] == 2


def test_weight_formats_states_a_format_for_every_leaf():
    """The deciding compile on shapes alone: one ``Format`` a leaf of
    the first operand, and the program's temporaries."""
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    weights = {"w": jax.ShapeDtypeStruct((3, 8, 4), np.float32,
                                         sharding=sharding),
               "b": jax.ShapeDtypeStruct((4,), np.float32,
                                         sharding=sharding)}
    x = jax.ShapeDtypeStruct((2, 8), np.float32)
    formats, temp_bytes, _ = generate.weight_formats(
        lambda p, x: (x @ p["w"][1] + p["b"]).sum(), (weights, x))
    assert jax.tree.structure(weights).flatten_up_to(formats)
    assert formats["w"].layout.major_to_minor in {(0, 1, 2), (0, 2, 1)}
    assert formats["b"].sharding == sharding
    assert isinstance(temp_bytes, int)


def test_relay_is_compiled_here_whatever_the_cache_holds():
    """An executable that writes another layout than the default, loaded
    from the persistent cache, hands back an array that says default and
    is not (``jax.device_put(x, Format)`` twice under this suite's cache
    settings shows it: the second array reads wrongly through ``jit``).
    ``_relay`` makes a new ``jit`` a call, so each call asks the cache:
    what the first had written the second would load."""
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    x = jax.numpy.arange(2 * 3 * 4, dtype=np.float32).reshape(2, 3, 4)
    wanted = Format(TURNED, x.sharding)
    for _ in range(2):
        put, = generate._relay([x], [wanted])
        assert put.format.layout == TURNED
        np.testing.assert_array_equal(
            np.asarray(jax.jit(lambda a: a.max(axis=1))(put)),
            np.asarray(x).max(axis=1))
    assert jax.config.jax_persistent_cache_min_compile_time_secs == floor
