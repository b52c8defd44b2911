"""A decode step's routed part (models/experts.py: ``experts_hit``, ISSUE
36) against the plain batched product (``experts_batched``, the oracle):
a loop with one trip a held expert that some row chose, each read where
it lies, in every family that routes.

(a) the loop's sum and per-expert counts equal the batched product's:
    every expert hit, some unhit, no valid row, a chip's share of the
    experts at several ranks, padding rows;
(b) with ``at`` into the whole (layers, E, ..) stack it equals the same
    call on that layer's own (E, ..) leaves, bit for bit;
(c) an unhit expert is not computed: with every unhit expert's weights
    NaN the loop's result is finite and equal to the clean one, where
    the batched product's is not (NaN x 0): with (a), each hit expert
    is computed once and no other;
(d) no family's decode step holds an operation whose result is a whole
    layer's routed experts: the stack stays out of the layer scan.
"""

import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import hc_mla_moe, mla_moe, swa_moe
from gofr_tpu.models.experts import experts_batched, experts_hit, route

DIM, WIDTH, ROUTED, TOP_K = 24, 16, 16, 4


def _cfg(held=ROUTED, rank=0):
    return types.SimpleNamespace(
        scoring="sigmoid", top_k=TOP_K, norm_topk_prob=True,
        routed_scale=2.0, n_routed_experts=ROUTED, n_held_experts=held,
        expert_rank=rank, topk_norm_eps=0.0)


def _case(cfg, tokens, seed=0, layers=None):
    """(experts' leaves, h, ids, weights): leaves (E, ..), or with
    ``layers`` the stack (layers, E, ..)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    lead = (cfg.n_held_experts,) if layers is None \
        else (layers, cfg.n_held_experts)
    leaves = {name: 0.2 * jax.random.normal(key, lead + shape)
              for name, key, shape in (("w_gate", keys[0], (DIM, WIDTH)),
                                       ("w_up", keys[1], (DIM, WIDTH)),
                                       ("w_down", keys[2], (WIDTH, DIM)))}
    h = jax.random.normal(keys[3], (tokens, DIM))
    ids, weights = route(cfg, jax.random.normal(keys[4], (DIM, ROUTED)), h)
    return leaves, h, ids, weights


def _hit(cfg, *args):
    return jax.jit(lambda *a: experts_hit(cfg, *a))(*args)


# -- (a) the loop against the batched product ------------------------------------

@pytest.mark.parametrize("held,rank,tokens,valid_rows", [
    (16, 0, 64, None),          # every expert hit
    (16, 0, 2, None),           # at most 8 of 16 hit
    (16, 0, 1, None),           # one row: 4 of 16
    (16, 0, 32, 3),             # padding rows: few valid, most unhit
    (16, 0, 8, 0),              # no valid row: zero trips, a zero sum
    (4, 0, 32, None),           # a chip's share, first rank
    (4, 1, 32, None), (4, 3, 32, None),
    (4, 2, 2, None),            # a share with experts unhit
    (4, 1, 32, 0),              # a share, no valid row
    (8, 1, 5, 4)])
def test_the_loop_over_the_hit_experts_equals_the_batched_product(
        held, rank, tokens, valid_rows):
    cfg = _cfg(held, rank)
    leaves, h, ids, weights = _case(cfg, tokens, seed=held + tokens)
    valid = None if valid_rows is None else jnp.arange(tokens) < valid_rows
    want, want_counts = experts_batched(cfg, leaves, h, ids, weights, valid)
    got, counts = _hit(cfg, leaves, h, ids, weights, valid)
    np.testing.assert_array_equal(counts, want_counts)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert got.dtype == jnp.float32 and got.shape == h.shape
    if valid_rows == 0:
        assert int(counts.sum()) == 0
        np.testing.assert_array_equal(got, np.zeros_like(got))
    if (held, tokens, valid_rows) == (16, 64, None):
        assert int((counts > 0).sum()) == held      # the case it names
    if tokens * TOP_K < held:
        assert int((counts > 0).sum()) < held


# -- (b) read in place from the whole stack ----------------------------------------

@pytest.mark.parametrize("held,rank,tokens,at", [
    (16, 0, 3, 0), (16, 0, 3, 2), (16, 0, 40, 1), (4, 2, 24, 2),
    (4, 1, 2, 0)])
def test_the_stack_read_at_a_layer_equals_that_layers_leaves(held, rank,
                                                             tokens, at):
    cfg = _cfg(held, rank)
    stack, h, ids, weights = _case(cfg, tokens, seed=at, layers=3)
    layer = {name: leaf[at] for name, leaf in stack.items()}
    want, want_counts = _hit(cfg, layer, h, ids, weights)
    got, counts = _hit(cfg, stack, h, ids, weights, None, jnp.int32(at))
    np.testing.assert_array_equal(counts, want_counts)
    np.testing.assert_array_equal(got, want)
    dense, _ = experts_batched(cfg, layer, h, ids, weights)
    np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-5)


# -- (c) an unhit expert is not computed -------------------------------------------

@pytest.mark.parametrize("held,rank,tokens,at", [
    (16, 0, 2, None), (16, 0, 2, 1), (4, 1, 1, None)])
def test_unhit_experts_of_nan_leave_the_loop_finite(held, rank, tokens, at):
    cfg = _cfg(held, rank)
    clean, h, ids, weights = _case(cfg, tokens, seed=7,
                                   layers=None if at is None else 3)
    where = () if at is None else (jnp.int32(at),)
    want, counts = _hit(cfg, clean, h, ids, weights, None, *where)
    unhit = np.asarray(counts) == 0
    assert unhit.any() and not unhit.all()
    lead = unhit if at is None else np.broadcast_to(unhit, (3, held))
    poisoned = {name: jnp.where(lead[..., None, None], jnp.nan, leaf)
                for name, leaf in clean.items()}
    got, got_counts = _hit(cfg, poisoned, h, ids, weights, None, *where)
    np.testing.assert_array_equal(got_counts, counts)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(got, want)
    # the oracle computes them, and NaN x 0 is NaN: it cannot pass this
    layer = poisoned if at is None else {name: leaf[at]
                                         for name, leaf in poisoned.items()}
    dense, _ = experts_batched(cfg, layer, h, ids, weights)
    assert np.isnan(np.asarray(dense)).any()


# -- (d) the stack stays out of every family's layer scan ------------------------

def _decode_text(module, cfg):
    params = jax.eval_shape(lambda key: module.init(cfg, key),
                            jax.random.key(0))
    slots, pages, page, columns = 2, 8, 4, 4

    def shape(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype)

    leaves = module.cache_leaves(cfg)
    if all(isinstance(leaf, tuple) for leaf in leaves.values()):
        pool = {leaf: shape(cfg.n_layers, pages, page, *tail, dtype=dtype)
                for leaf, (tail, dtype) in leaves.items()}
        table = shape(slots, columns)
    else:
        pool = {kind: {leaf: shape(spec["layers"], pages, page, *tail,
                                   dtype=dtype)
                       for leaf, (tail, dtype) in spec["leaves"].items()}
                for kind, spec in leaves.items()}
        table = {kind: shape(slots, columns) for kind in leaves}
    step = jax.jit(lambda p, t, pl, tb, cl, a: module.decode_step_paged(
        p, cfg, t, pl, tb, cl, a, counters=True))
    return step.lower(params, shape(slots), pool, table, shape(slots),
                      shape(slots, dtype=bool)).as_text()


@pytest.mark.parametrize("module,overrides", [
    (mla_moe, {}), (hc_mla_moe, {}),
    (swa_moe, {}),                               # two periods of four
    (mla_moe, {"n_held_experts": 8, "expert_rank": 1})])
def test_no_decode_step_slices_a_layers_experts(module, overrides):
    """A scan over the routed experts' stack would hand the loop a
    (E, D, F) slice a layer, which on the chip is a copy of the layer's
    experts every step. The lowered step may hold the whole stack and
    single experts, nothing between."""
    cfg = module.config("tiny", **overrides)
    held, d, f = cfg.n_held_experts, cfg.dim, cfg.moe_ffn_dim
    text = _decode_text(module, cfg)
    one = rf"tensor<1x1x{d}x{f}x"                # [layer, expert]'s gate / up
    layer = rf"tensor<(1x)?{held}x({d}x{f}|{f}x{d})x"
    assert re.search(one, text), "no single expert is read"
    assert not re.search(layer, text), re.search(layer, text).group(0)
