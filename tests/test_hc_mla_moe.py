"""The hyper-connected member of the latent family (models/hc_mla_moe.py,
models/hyper_connection.py, ISSUE 33) against the benchmark's plain
reference (benchmark/reference_hc_mla_moe.py: float32, highest
precision, no cache, no kernel, written from the equations).

Float32 at ``highest`` matmul precision on both sides, so routing cannot
flip between the two and logits agree to ~1e-6:

(a) ``prefill`` logits and routing equal the reference's, at lengths
    either side of YaRN's original context;
(b) ``prefill`` then ``decode_step_paged`` through a ``PagePool`` of
    latent leaves equals the reference's full forward;
(c) ``H_res`` is doubly stochastic after 20 iterations and the clamp
    holds;
(d) with ``hc_mult`` 1 and the mixers forced to the plain residual the
    module is ``mla_moe`` on the same weights;
(e) the YaRN table equals the reference's either side of position 4096;
(f) ``route`` with a bias chooses by ``s + b`` and weighs by ``s``, and
    without one is bit-equal to what it was; the grouped experts read in
    place from the whole stack equal the same on a layer's slice;
(g) the flash kernel with keys of 192 and values of 128 equals
    ``ops.attention``, and the module's flash prefill its expanded one;
(h) every control of the adapter moves the result the way its limit
    reads;
(i) the engine serves it: the greedy stream equals stepping the
    reference, the counters add up.
"""

import asyncio
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from gofr_tpu.container import new_mock_container
from gofr_tpu.models import hc_mla_moe, hyper_connection, mla_moe
from gofr_tpu.models.experts import (experts_batched, experts_grouped,
                                     route)
from gofr_tpu.ops import prefill_attention, rope_table
from gofr_tpu.ops.pallas import flash_attention
from gofr_tpu.tpu.generate import GenerationEngine
from gofr_tpu.tpu.page_pool import PagePool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load("reference_hc_mla_moe", "reference_hc_mla_moe.py")
adapter = _load("adapter_hc_mla_moe", "adapters", "hc_mla_moe.py")

PAGE = 8


def published(cfg):
    """The reference reads the published keys of a configuration file."""
    return {"num_attention_heads": cfg.n_heads,
            "qk_nope_head_dim": cfg.qk_nope_dim,
            "qk_rope_head_dim": cfg.qk_rope_dim,
            "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "rope_scaling": cfg.rope_group,
            "num_experts_per_tok": cfg.top_k,
            "n_routed_experts": cfg.n_held_experts,
            "routed_scaling_factor": cfg.routed_scale,
            "norm_topk_prob": cfg.norm_topk_prob,
            "hc_mult": cfg.hc_mult,
            "hc_sinkhorn_iters": cfg.hc_sinkhorn_iters,
            "hc_eps": cfg.hc_eps,
            "mhc_h_res_clamp_min": cfg.hc_clamp_min,
            "mhc_h_res_clamp_max": cfg.hc_clamp_max}


@pytest.fixture(scope="module")
def model():
    cfg = hc_mla_moe.config("tiny", dtype=jnp.float32)
    return cfg, hc_mla_moe.init(cfg, jax.random.PRNGKey(0))


def _tokens(cfg, seed, count):
    return jax.random.randint(jax.random.PRNGKey(seed), (count,), 0,
                              cfg.vocab_size)


# -- (a), (b): against the reference's full forward ---------------------------

@pytest.mark.parametrize("length", [5, 16, 17, 40])
def test_prefill_logits_and_routing_equal_the_reference(model, length):
    """The tiny preset's YaRN has an original context of 16: 5 lies
    inside it, 17 and 40 past it."""
    cfg, params = model
    tokens = _tokens(cfg, length, length)
    want, want_routes = reference.forward_logits(params, published(cfg),
                                                 tokens)
    with jax.default_matmul_precision("highest"):
        logits, _, cache_len, routes = hc_mla_moe.prefill(
            params, cfg, tokens[None], hc_mla_moe.init_cache(cfg, 1, length),
            routes=True)
    assert reference.rel_l2(logits[0], want[0]) < 1e-5
    assert int(cache_len[0]) == length
    assert routes.shape == (cfg.n_moe_layers, 1, length, cfg.top_k)
    np.testing.assert_array_equal(np.sort(routes[:, 0], -1),
                                  np.sort(want_routes, -1))


@pytest.mark.parametrize("length", [5, 8, 15, 21])
def test_prefill_then_paged_decode_equals_the_reference(model, length):
    cfg, params = model
    steps = 5
    tokens = _tokens(cfg, 100 + length, length + steps)
    want, _ = reference.forward_logits(params, published(cfg), tokens,
                                       last=steps + 1)
    pool = PagePool(cfg, page=PAGE, num_pages=12,
                    leaf_specs=hc_mla_moe.cache_leaves(cfg))
    assert pool.leaves["ckv"].shape == (cfg.n_layers, 12, PAGE,
                                        cfg.cache_row)
    bucket = 24
    prefill = jax.jit(lambda p, t, n: hc_mla_moe.prefill(
        p, cfg, t, hc_mla_moe.init_cache(cfg, 2, bucket), lengths=n))
    one_step = jax.jit(lambda p, t, leaves, table, n, active:
                   hc_mla_moe.decode_step_paged(
                       p, cfg, t, leaves, table, n, active, counters=True))
    with jax.default_matmul_precision("highest"):
        padded = jnp.zeros((2, bucket), jnp.int32).at[0, :length].set(
            tokens[:length])
        logits, small, cache_len = prefill(params, padded,
                                           jnp.array([length, 1]))
        assert reference.rel_l2(logits[0], want[0]) < 1e-5
        ids = pool.alloc(4)[::-1]
        table = np.full((2, 4), pool.sentinel, np.int32)
        table[0] = ids
        leaves = pool.leaves
        for column, pid in enumerate(ids[:bucket // PAGE]):
            leaves = {"ckv": leaves["ckv"].at[:, pid].set(
                small["ckv"][:, 0, column * PAGE:(column + 1) * PAGE])}
        active = jnp.array([True, False])
        for step in range(steps):
            fed = jnp.array([tokens[length + step], 0])
            logits, leaves, grown, counted = one_step(
                params, fed, leaves, jnp.asarray(table), cache_len, active)
            # one active slot: its rows under cache_len and the new one;
            # both slots' whole tables gathered, in every layer
            assert counted.shape == (len(hc_mla_moe.STEP_COUNTERS),)
            assert int(counted[-2]) == cfg.n_layers * (length + step + 1)
            assert int(counted[-1]) == cfg.n_layers * 2 * 4 * PAGE
            cache_len = jnp.where(active, grown, cache_len)
            assert reference.rel_l2(logits[0], want[step + 1]) < 1e-5
    assert int(cache_len[0]) == length + steps and int(cache_len[1]) == 1


def test_dense_decode_step_equals_the_paged_one(model):
    cfg, params = model
    tokens = _tokens(cfg, 7, 12)
    want, _ = reference.forward_logits(params, published(cfg), tokens)
    with jax.default_matmul_precision("highest"):
        _, cache, cache_len = hc_mla_moe.prefill(
            params, cfg, tokens[None, :11],
            hc_mla_moe.init_cache(cfg, 1, 16))
        logits, _, grown = hc_mla_moe.decode_step(
            params, cfg, tokens[11:], cache, cache_len)
    assert int(grown[0]) == 12
    assert reference.rel_l2(logits[0], want[0]) < 1e-5


# -- (c) the mixer ----------------------------------------------------------------

def _state(cfg, seed, tokens=64):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (cfg.hc_mult, tokens, cfg.dim), jnp.float32)


def _mixer(params, which="hc_attn", layer=0):
    return jax.tree.map(lambda leaf: leaf[layer], params["moe"][which])


@pytest.mark.parametrize("which", ["hc_attn", "hc_ffn"])
def test_h_res_is_doubly_stochastic_and_equals_the_references(model, which):
    cfg, params = model
    x = _state(cfg, 3)
    mixer = _mixer(params, which)
    with jax.default_matmul_precision("highest"):
        pre, post, res = hyper_connection.mix(cfg, mixer, x)
        want = reference.mixer(mixer, x.transpose(1, 0, 2), published(cfg))
    n = cfg.hc_mult
    assert pre.shape == post.shape == (n, 64) and res.shape == (n, n, 64)
    assert float(jnp.abs(res.sum(0) - 1).max()) < 1e-4      # columns
    assert float(jnp.abs(res.sum(1) - 1).max()) < 1e-4      # rows
    assert float(res.min()) > 0 and 0 < float(pre.min()) \
        and float(pre.max()) < 1 and float(post.max()) < 2
    np.testing.assert_allclose(pre.T, want[0], atol=1e-6)
    np.testing.assert_allclose(post.T, want[1], atol=1e-6)
    np.testing.assert_allclose(res.transpose(2, 0, 1), want[2], atol=1e-6)


@pytest.mark.parametrize("scale", [1e3, -1e3])
def test_the_clamp_holds_at_thirty(model, scale):
    """A mixer whose logits run to +-1e3 times the usual: exp() of them
    unclamped is inf or 0 and the iterations give NaN; clamped at +-30
    every entry is finite, the columns (normalised last) sum to one, and
    the reference, which clips the same way, agrees."""
    cfg, params = model
    x = _state(cfg, 4)
    mixer = dict(_mixer(params), scale=jnp.array([1.0, 1.0, scale]))
    with jax.default_matmul_precision("highest"):
        _, _, res = hyper_connection.mix(cfg, mixer, x)
        want = reference.mixer(mixer, x.transpose(1, 0, 2),
                               published(cfg))[2]
        loose = hc_mla_moe.config("tiny", dtype=jnp.float32,
                                  hc_clamp_min=-1e6, hc_clamp_max=1e6)
        _, _, wild = hyper_connection.mix(loose, mixer, x)
    assert bool(jnp.isfinite(res).all())
    assert float(jnp.abs(res.sum(0) - 1).max()) < 1e-4
    np.testing.assert_allclose(res.transpose(2, 0, 1), want, atol=2e-4)
    assert not bool(jnp.isfinite(wild).all())


def test_every_sinkhorn_iteration_runs(model):
    """The mix's loop has ``hc_sinkhorn_iters`` iterations, a constant
    of the program (none is skipped for staying inside a tolerance),
    and 19 iterations give another H_res than 20."""
    cfg, params = model
    x = _state(cfg, 5)
    mixer = _mixer(params)
    jaxpr = str(jax.make_jaxpr(
        lambda x: hyper_connection.mix(cfg, mixer, x))(x))
    assert f"length={cfg.hc_sinkhorn_iters}\n" in jaxpr      # the loop
    fewer = hc_mla_moe.config("tiny", dtype=jnp.float32,
                              hc_sinkhorn_iters=19)
    full = hyper_connection.mix(cfg, mixer, x)[2]
    short = hyper_connection.mix(fewer, mixer, x)[2]
    assert float(jnp.abs(full - short).max()) > 0


# -- (d) the plain residual is the family's --------------------------------------

def test_one_stream_with_the_plain_residual_is_mla_moe():
    """``hc_mult`` 1, ``H_pre = H_post = H_res = 1`` (scales 0, a base
    that saturates ``H_pre``, ``hc_eps`` 0): the module's logits are
    ``mla_moe``'s on the same weights, through prefill and a paged
    decode step."""
    shared = dict(dtype=jnp.float32)
    hc_cfg = hc_mla_moe.config("tiny", hc_mult=1, hc_eps=0.0,
                               topk_method="greedy", **shared)
    fields = {f.name: getattr(hc_cfg, f.name)
              for f in __import__("dataclasses").fields(mla_moe.MlaMoeConfig)}
    plain_cfg = mla_moe.MlaMoeConfig(**fields)
    params = hc_mla_moe.init(hc_cfg, jax.random.PRNGKey(2))
    for stack in (params["dense"], params["moe"]):
        assert "router_bias" not in stack
        for which in ("hc_attn", "hc_ffn"):
            layers = stack[which]["scale"].shape[0]
            stack[which]["scale"] = jnp.zeros((layers, 3))
            stack[which]["base"] = jnp.tile(jnp.array([40.0, 0.0, 0.0]),
                                            (layers, 1))
    tokens = _tokens(hc_cfg, 9, 13)
    with jax.default_matmul_precision("highest"):
        ours = hc_mla_moe.prefill(params, hc_cfg, tokens[None, :12],
                                  hc_mla_moe.init_cache(hc_cfg, 1, 16))
        theirs = mla_moe.prefill(params, plain_cfg, tokens[None, :12],
                                 mla_moe.init_cache(plain_cfg, 1, 16))
        np.testing.assert_allclose(ours[0], theirs[0], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(ours[1]["ckv"], theirs[1]["ckv"],
                                   rtol=1e-5, atol=1e-6)
        ours = hc_mla_moe.decode_step(params, hc_cfg, tokens[12:], ours[1],
                                      ours[2])
        theirs = mla_moe.decode_step(params, plain_cfg, tokens[12:],
                                     theirs[1], theirs[2])
    np.testing.assert_allclose(ours[0], theirs[0], rtol=1e-5, atol=1e-6)


# -- (e) YaRN ---------------------------------------------------------------------

XING_ROPE = hc_mla_moe.PRESETS["xing4"].rope_group


@pytest.mark.parametrize("position", [0, 1, 4095, 4096, 4097, 8575])
def test_yarn_table_equals_the_references(position):
    hp = {"qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
          "rope_theta": 10000, "rope_scaling": XING_ROPE}
    cos, sin = rope_table(8704, 64, 10000.0, XING_ROPE)
    want_cos, want_sin, scale = reference.rotary_tables(hp, 8704)
    np.testing.assert_allclose(cos[position], want_cos[position, :32],
                               atol=2e-4)
    np.testing.assert_allclose(sin[position], want_sin[position, :32],
                               atol=2e-4)
    cfg = hc_mla_moe.PRESETS["xing4"]
    assert scale == pytest.approx(cfg.softmax_scale)
    assert cfg.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)


def test_yarn_keeps_the_fast_pairs_and_slows_the_slow_ones():
    plain_cos, plain_sin = rope_table(8704, 64, 10000.0)
    cos, sin = rope_table(8704, 64, 10000.0, XING_ROPE)
    # pair 0 turns once in 6.3 positions: extrapolated, unchanged; the
    # last pair turns once in ~47000: interpolated, 64 times slower
    np.testing.assert_allclose(cos[:, 0], plain_cos[:, 0], atol=1e-6)
    np.testing.assert_allclose(sin[4096 + 640, -1], plain_sin[74, -1],
                               atol=1e-4)
    assert float(jnp.abs(cos[4097] - plain_cos[4097]).max()) > 0.1
    with pytest.raises(ValueError, match="not yarn"):
        rope_table(16, 8, 10000.0, {"type": "linear", "factor": 2})


def test_plain_members_keep_their_scale_and_table():
    cfg = mla_moe.config("pangu-ultra-moe")
    assert cfg.rope_scaling is None
    assert cfg.softmax_scale == 192 ** -0.5
    assert hash(hc_mla_moe.PRESETS["xing4"]) is not None    # stays hashable


# -- (f) the router's correction bias -------------------------------------------

class _Routing:
    scoring, top_k, norm_topk_prob, routed_scale = "sigmoid", 4, True, 2.0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_route_without_a_bias_is_bit_equal_to_what_it_was(dtype):
    h = jax.random.normal(jax.random.PRNGKey(0), (64, 32)).astype(dtype)
    router = jax.random.normal(jax.random.PRNGKey(1), (32, 16)).astype(dtype)
    ids, weights = route(_Routing, router, h)
    # the parent's route(), written out
    logits = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    top, want_ids = lax.top_k(jax.nn.sigmoid(logits), 4)
    top = top / top.sum(axis=-1, keepdims=True)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(weights, top * 2.0)


def test_route_with_a_bias_chooses_by_s_plus_b_and_weighs_by_s():
    h = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    router = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    bias = jnp.zeros((16,)).at[3].set(10.0).at[5].set(-10.0)
    ids, weights = route(_Routing, router, h, bias)
    scores = jax.nn.sigmoid(h @ router)
    assert bool((ids == 3).any(-1).all())       # always chosen
    assert not bool((ids == 5).any())           # never chosen
    _, want = lax.top_k(scores + bias, 4)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want, -1))
    chosen = jnp.take_along_axis(scores, ids, -1)
    np.testing.assert_allclose(
        weights, 2.0 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    plain, _ = route(_Routing, router, h)
    assert bool((np.sort(plain, -1) != np.sort(ids, -1)).any())


@pytest.mark.parametrize("held,rank,padded,tokens", [
    (16, 0, False, 40), (16, 0, True, 40), (4, 1, False, 40),
    (4, 3, True, 96), (16, 0, False, 7), (4, 1, True, 40),
    (4, 3, False, 96)])
def test_experts_read_in_place_equal_a_layers_slice(held, rank, padded,
                                                    tokens):
    """``experts_grouped`` over the whole stack at a layer's index
    against the same call on that layer's slice: where the weights are
    read is all that differs, so the results are equal bit for bit,
    whether every expert is held (each block written at its rows'
    sorted places, the tokens' sums taken once) or a share (each block
    added to its tokens' rows). Both forms against the batched product:
    every held pair, padding rows and absent experts nowhere, tail
    blocks' dead rows written over."""
    cfg = hc_mla_moe.config("tiny", n_held_experts=held, expert_rank=rank)
    keys = jax.random.split(jax.random.PRNGKey(held + tokens), 6)
    stack = {name: 0.2 * jax.random.normal(key, (3, held, *shape))
             for name, key, shape in (
                 ("w_gate", keys[0], (cfg.dim, cfg.moe_ffn_dim)),
                 ("w_up", keys[1], (cfg.dim, cfg.moe_ffn_dim)),
                 ("w_down", keys[2], (cfg.moe_ffn_dim, cfg.dim)))}
    h = jax.random.normal(keys[3], (tokens, cfg.dim))
    ids, weights = route(cfg, jax.random.normal(
        keys[4], (cfg.dim, cfg.n_routed_experts)), h)
    valid = (jnp.arange(tokens) < tokens - 9) if padded else None
    for at in (0, 2):
        layer = {name: leaf[at] for name, leaf in stack.items()}
        want, want_counts = jax.jit(lambda *a: experts_grouped(cfg, *a))(
            layer, h, ids, weights, valid)
        got, counts = jax.jit(lambda *a: experts_grouped(cfg, *a))(
            stack, h, ids, weights, valid, jnp.int32(at))
        np.testing.assert_array_equal(counts, want_counts)
        np.testing.assert_array_equal(got, want)
        dense, dense_counts = experts_batched(cfg, layer, h, ids, weights,
                                              valid)
        np.testing.assert_array_equal(counts, dense_counts)
        np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-5)


# -- (g) the latent prefill kernel ------------------------------------------------

@pytest.mark.parametrize("seq,block", [(256, 128), (512, 256)])
def test_flash_with_keys_of_192_and_values_of_128_equals_the_oracle(seq,
                                                                    block):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (1, seq, 2, 192))
    k = jax.random.normal(keys[1], (1, seq, 2, 192))
    v = jax.random.normal(keys[2], (1, seq, 2, 128))
    want = prefill_attention(q, k, v)           # scale 192 ** -0.5
    pad = ((0, 0), (0, 0), (0, 0), (0, 64))     # keys to whole lanes
    out = flash_attention(jnp.pad(q, pad), jnp.pad(k, pad), v,
                          block_q=block, block_k=block,
                          sm_scale=192 ** -0.5, interpret=True)
    assert out.shape == (1, seq, 2, 128)
    np.testing.assert_allclose(out, want, atol=2e-5)


def test_the_flash_prefill_equals_the_expanded_one():
    """A member at the published head widths (keys 128 + 64, values
    128), two heads, a 1024-token bucket: the kernel path (interpreted
    here) against ``expanded_attention``."""
    cfg = hc_mla_moe.config(
        "tiny", dtype=jnp.float32, n_heads=2, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128, kv_lora_rank=32, max_seq_len=1024)
    assert cfg.flash_block(1024) == 1024 and cfg.flash_block(512) is None
    params = hc_mla_moe.init(cfg, jax.random.PRNGKey(0))
    attn = jax.tree.map(lambda leaf: leaf[0], params["dense"]["attn"])
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 1024, cfg.dim))
    positions = jnp.arange(1024, dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        q_n, q_r, rows = mla_moe._latent(attn, h, cfg, *cfg.rope(),
                                         positions)
        want = mla_moe.expanded_attention(attn, q_n, q_r, rows, cfg)
        got = mla_moe.prefill_attention(attn, q_n, q_r, rows, cfg)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("bucket,block", [
    (128, None), (256, None), (512, None), (1024, 1024), (2048, 1024),
    (4096, 1024), (4608, 512), (6144, 1024), (8192, 1024)])
def test_which_buckets_take_the_kernel(bucket, block):
    """pangu's buckets (128 / 256 / 512) stay on the expanded form; from
    1024 rows up a bucket of whole blocks takes the kernel."""
    for cfg in (mla_moe.config("pangu-ultra-moe"),
                hc_mla_moe.PRESETS["xing4"]):
        assert cfg.flash_block(bucket) == block
    assert hc_mla_moe.PRESETS["tiny"].flash_block(1024) is None   # v of 16


@pytest.mark.parametrize("family,bucket,block", [
    ("llama", 100, None), ("llama", 256, 512), ("llama", 1024, 512),
    ("llama-dense", 1024, None), ("llama-tiny", 1024, None),
    ("swa", 100, None), ("swa", 512, 512), ("swa", 1536, 512),
    ("swa", 2048, 1024), ("swa", 6144, 1024), ("swa-tiny", 1024, None)])
def test_every_family_answers_by_flash_block(family, bucket, block):
    """One rule: a family's config says which buckets its prefill takes
    through the flash kernel (``flash_block``), its ``_prefill_attend``
    follows it, and ``GenerationEngine.attention_paths()`` reports by
    it: ``llama`` where ``use_flash`` asks and the shape tiles (the
    kernel's default block), ``swa_moe`` wherever it tiles (1024-row
    blocks where the bucket splits into them)."""
    from gofr_tpu.models import llama, swa_moe
    cfg = {"llama": llama.config("tiny", dim=512, n_heads=4, use_flash=True),
           "llama-dense": llama.config("tiny", dim=512, n_heads=4),
           "llama-tiny": llama.config("tiny", use_flash=True),
           "swa": swa_moe.config("command-a-plus"),
           "swa-tiny": swa_moe.config("tiny")}[family]
    assert cfg.flash_block(bucket) == block


# -- (h) the controls -------------------------------------------------------------

@pytest.mark.parametrize("name", [c for c in adapter.CONTROLS if c])
def test_each_control_moves_what_its_limit_reads(model, name):
    """Each control of ``adapters/hc_mla_moe.py`` against the reference
    on the tiny model: the logits of a 40-token sequence move by more
    than the probe's limit, or the mixer alone / the router alone
    differs by more than its own."""
    cfg, params = model
    hp = published(cfg)
    switches = adapter._typed(adapter.CONTROLS[name])
    tokens = _tokens(cfg, 11, 40)
    want, want_routes = reference.forward_logits(params, hp, tokens, last=8)
    got, routes = reference.forward_logits(params, hp, tokens, last=8,
                                           **switches)
    logits = max(reference.rel_l2(g, w) for g, w in zip(got, want))
    x = _state(cfg, 6).astype(jnp.bfloat16).astype(jnp.float32)
    mixer = _mixer(params)
    with jax.default_matmul_precision("highest"):
        sound = reference.mixer(mixer, x.transpose(1, 0, 2), hp)
        moved = reference.mixer(
            mixer, x.transpose(1, 0, 2), hp,
            identity_res=switches.get("identity_res", False),
            sinkhorn_iters=switches.get("sinkhorn_iters"),
            round_to=switches.get("mixer_round_to"))
    mixer_err = max(float(jnp.abs(a - b).max())
                    for a, b in zip(sound, moved))
    # the router alone at its published width: 4 of 64
    h = jax.random.normal(jax.random.PRNGKey(12), (2048, cfg.dim)) \
        .astype(jnp.bfloat16).astype(jnp.float32)
    weight = jax.random.normal(jax.random.PRNGKey(13), (cfg.dim, 64)) / 8
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(14), (64,))
    theirs = reference.route(weight, h, hp, None, bias)[0]
    ours = reference.route(
        weight, h, hp, switches.get("router_round_to"),
        None if switches.get("ignore_router_bias") else bias)[0]
    router = float((np.sort(ours, -1) != np.sort(theirs, -1)).any(-1).mean())
    limits = reference.LIMITS
    assert (logits > limits["logits_rel_l2_q1"]
            or mixer_err > limits["mixer_err_max"]
            or router > limits["router_swap_share"]), (
        name, logits, mixer_err, router)
    # and the program itself passes all three at this size
    with jax.default_matmul_precision("highest"):
        ours = hyper_connection.mix(cfg, mixer, x)
    assert max(float(jnp.abs(a.T - b).max()) for a, b in
               zip(ours[:2], sound[:2])) < limits["mixer_err_max"]


def test_the_adapter_names_every_control_the_issue_lists():
    assert set(adapter.CONTROLS) >= {
        "identity-res", "sinkhorn-1", "yarn-off", "no-router-bias",
        "float8_e4m3fn", "bf16-mixer"}
    assert set(reference.LIMITS) >= {
        "served_argmax_share_min", "served_margin_p99",
        "served_request_share_min", "served_margin_mean", "logits_rel_l2_q1",
        "logits_rel_l2_max", "router_swap_share", "mixer_err_max",
        "route_swap_share"}


@pytest.fixture(scope="module")
def served(model):
    """An adapter without an engine, and sequences as a sound system
    would have served them: three requests and a deep one, each reply
    the reference's own greedy continuation."""
    cfg, params = model
    judge = adapter.Adapter.__new__(adapter.Adapter)
    judge.config = dict(
        published(cfg), probe={"prompt": 16, "steps": 2},
        engine={"prompt_buckets": [8, 16]},
        served_check={"requests": 3, "sample": 3, "new_tokens": [4],
                      "deep": {"requests": 1, "prompt": 24,
                               "new_tokens": 6}})
    judge.cfg, judge.params, judge.control = cfg, params, {}
    judge._reference, judge.checks = {}, {}
    lengths, budgets = [5, 7, 8, 24], [4, 4, 4, 6]
    prompts = [[int(t) for t in _tokens(cfg, 30 + i, length)]
               for i, length in enumerate(lengths)]
    replies = []
    for prompt, budget in zip(prompts, budgets):
        reply = []
        for _ in range(budget):
            sequence = prompt + reply
            logits, _ = judge.reference_logits(sequence,
                                               [len(sequence) - 1])
            reply.append(int(logits[0].argmax()))
        replies.append(reply)
    return judge, (prompts, replies, lengths, budgets)


@pytest.mark.parametrize("fault,faults", [
    (None, 0), ("one-request", 1), ("the-deep-one", 1), ("every-token", 4)])
def test_the_served_check_names_a_faulty_request(served, fault, faults):
    """A sound system's served tokens pass the four served limits; one
    sampled request (or the deep one) served wrongly, which the share
    over all tokens forgives, fails by the lowest request's share (a
    quarter of this small sample, it may fail by the margins too);
    every token wrong fails by all four."""
    judge, (prompts, replies, lengths, budgets) = served
    vocab = judge.cfg.vocab_size
    wrong = {None: [], "one-request": [1], "the-deep-one": [3],
             "every-token": [0, 1, 2, 3]}[fault]
    judge._served = (prompts, [
        [(token + vocab // 2) % vocab for token in reply]
        if i in wrong else reply
        for i, reply in enumerate(replies)], lengths, budgets)
    judge.judge_served()
    checks = judge.checks
    # the deep request's last 4 tokens: the longest budget of the others
    assert checks["served_tokens_checked"] == 4 * 4
    assert len(checks["served_margins"]) == 16
    found = judge.served_faults()
    assert len(found) == faults or 1 <= faults <= len(found) < 4, (
        found, checks)
    if fault is None:
        assert checks["served_argmax_share"] == 1.0
        assert checks["served_margin_mean"] == 0.0
    elif faults == 1:
        assert "one sampled request" in found[0]
        assert checks["served_request_share_min"] == 0.0
        assert checks["served_argmax_share"] \
            >= reference.LIMITS["served_argmax_share_min"]


# -- (i) through the engine ------------------------------------------------------

def engine_for(cfg, params, **kwargs):
    container = new_mock_container()
    kwargs.setdefault("max_slots", 4)
    kwargs.setdefault("max_len", 64)
    kwargs.setdefault("prompt_buckets", (8, 16))
    engine = GenerationEngine(
        cfg, params, paged_kv=True, kv_page=PAGE, model_module=hc_mla_moe,
        logger=container.logger, metrics=container.metrics, **kwargs)
    return engine, container


def test_engine_greedy_stream_equals_stepping_the_reference(model):
    cfg, params = model
    prompt = [int(t) for t in _tokens(cfg, 21, 11)]

    async def serve():
        engine, _ = engine_for(cfg, params, steps_per_tick=2)
        assert engine.attn_path == "gather"
        await engine.start()
        try:
            return await asyncio.wait_for(
                engine.generate(prompt, max_new_tokens=6), 120.0)
        finally:
            await engine.stop()

    with jax.default_matmul_precision("highest"):
        served = asyncio.run(serve())
    sequence = prompt + served[:-1]
    want, _ = jax.jit(lambda t: reference.forward_logits(
        params, published(cfg), t, last=len(served)))(
        jnp.asarray(sequence, jnp.int32))
    assert [int(row.argmax()) for row in want] == served


def test_step_counters_add_up_through_the_engine(model):
    cfg, params = model

    async def serve():
        engine, container = engine_for(cfg, params, steps_per_tick=2)
        await engine.start()
        try:
            await asyncio.wait_for(asyncio.gather(*[
                engine.generate([3 + i] * 9, max_new_tokens=7)
                for i in range(3)]), 120.0)
            return engine.stats(), container.metrics
        finally:
            await engine.stop()

    stats, metrics = asyncio.run(serve())
    moe, attn = stats["moe"], stats["attn"]
    assert set(attn) == {"rows_live", "rows_read"}
    # all 16 experts are held: every routed pair is a held pair
    assert moe["held_pairs"] == moe["routed_pairs"] \
        == 3 * 6 * cfg.n_moe_layers * cfg.top_k
    # a request's 6 decode steps read 10 .. 15 live rows a layer
    assert attn["rows_live"] == 3 * sum(range(10, 16)) * cfg.n_layers
    assert attn["rows_read"] >= attn["rows_live"]
    assert attn["rows_read"] % (4 * PAGE * cfg.n_layers) == 0
    assert metrics.value("app_tpu_step_counter_total", model="generate",
                         counter="attn.rows_live") == attn["rows_live"]
    # the host's count of the same rows (the engine's timeline, a layer)
    # is the device's
    timeline = stats["timeline"]
    assert timeline["rows_live"] * cfg.n_layers == attn["rows_live"]
    assert timeline["rows_gathered"] * cfg.n_layers == attn["rows_read"]
