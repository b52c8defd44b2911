"""Decoder of gated short-convolution layers and a few attention layers
over sparse experts, for /generate.

The family of LiquidAI's LFM2-MoE (``lfm2_moe``): layer ``i`` is a short
convolution where ``layer_types[i]`` is ``conv`` and GQA attention where
it is ``full_attention``; the first ``n_dense_layers`` layers end in a
SwiGLU block, every later one in the sparse expert layer
(``models/experts.py``). Every layer is ``h = x + mixer(norm1(x)); out =
h + ffn(norm2(h))`` with RMSNorm (a gain), then a final norm and the
head tied to the embedding. What differs between members is a field of
``ConvMoeConfig``; what no member the repo has met varies is written
into the code once (no projection bias, no conv bias, half-split
rotary, QK-norm before the rotary).

Equations (``u`` a layer's normed input, ``D`` = ``dim``, ``W`` =
``conv_width``):

- Short convolution: ``[B; C; x~] = u W_in`` (``D x 3D``), ``v = B *
  x~``, ``w_t = sum_{j<W} k_j * v_{t-W+1+j}`` (depthwise, ``v_{<0} =
  0``), ``out = (C * w) W_out``. The products and the sum in float32,
  ``v`` and the output in ``dtype``.
- Attention: ``q = u W_q`` (``n_heads`` x ``head_dim``), ``k = u W_k``,
  ``v = u W_v`` (``n_kv_heads``); ``q`` and ``k`` each through an
  RMSNorm over the head's lanes with a gain, then the half-split rotary
  at ``rope_theta``; causal softmax at ``head_dim ** -0.5``; ``W_o``.
- Feed-forward: ``W_down (silu(W_gate u) * (W_up u))`` of ``ffn_dim``
  in the dense layers; in the others the sigmoid router over
  ``n_routed_experts``, the ``top_k`` largest of ``s + b`` (a per-layer
  bias, ``expert_bias``), weighed ``s / (sum s + topk_norm_eps)``, and
  SwiGLUs of ``moe_ffn_dim``; no shared expert.

**The cache has two kinds** (``cache_leaves``): the attention layers
keep ``k`` and ``v`` a *token*, on pages; the convolution layers keep a
window a *slot* (``per_slot``): the last ``W - 1`` rows of ``v``,
oldest first, as one row of ``(W - 1) * D``. The page pool keeps it as
a ``(layers, max_slots, ...)`` array with no pages and no table, as it
keeps ``models/jamba.py``'s state. The serving contract
(``docs/tpu/model-serving.md``) asks of it what it asks of jamba's:

- ``prefill`` returns each row's window *at its prompt's end*, whatever
  the bucket: gathered at ``lengths - W + 1 .. lengths - 1``, never at
  the bucket's end. The engine's insert writes it into the claimed
  slots' rows.
- ``decode_step_paged`` shifts the window of the rows that are
  ``active`` and writes every other row back bit for bit.

K and V are kept **packed**: a token's KV heads side by side in one row
of ``n_kv_heads * head_dim`` lanes. At head 64 a trailing ``(8, 64)``
pair of axes would be tiled to whole 128-lane rows on the chip, twice
its bytes or more; a row of 512 lanes is its own bytes, and the ragged
kernel walks such a pool in units of two heads
(``ops/pallas/ragged_paged_attention.py``).

The parameters are stacked by kind, in model order: ``params["conv"]``
and ``params["attn"]`` over the layers of each mixer, ``params["dense"]``
and ``params["moe"]`` over those of each feed-forward, as a kind's cache
is. A forward walks the leading dense layers in runs of one kind, then
a ``lax.scan`` over the periods of what follows (the shortest run of
kinds it repeats: ``(attn, conv, conv, conv)`` at LFM2-8B-A1B's first
fourteen layers), each period a run of one kind after another, a run a
``lax.scan`` over its layers reading each out of the kind's whole stack
by its index: three layer bodies a program at that cut, as jamba's.
The routed experts are read where they lie (``at=``): the
prefill's grouped product and the decode step's loop over the experts
hit take the whole stack and the layer's index.
Prefill's attention is the flash kernel where the bucket tiles, with q,
k and v zero-padded to whole 128-lane heads and the true head's scale,
else ``ops.banded_attention``; a decode step's is the ragged kernel with
``ragged``, else the pages gathered. Entry points: ``init``,
``init_cache``, ``prefill``, ``decode_step_paged``, ``decode_step``,
``cache_leaves``, ``STEP_COUNTERS``.
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.models import experts
from gofr_tpu.ops import (banded_attention, decode_attention_cached,
                          gather_kv_pages, rms_norm)
from gofr_tpu.ops.rotary import apply_rope, rope_table

# what a decode step counts: the convolution layers' steps and their
# active rows; the attention layers' calls, those that ran in the ragged
# kernel, and the cached K/V rows of the active slots; and the expert
# layers' (``experts.STEP_COUNTERS``). Summed by the engine over a
# tick's steps
STEP_COUNTERS = ("conv.layer_steps", "conv.rows_live", "attn.calls",
                 "attn.kernel_calls", "attn.rows_live") \
    + experts.STEP_COUNTERS
_N_COUNTERS = len(STEP_COUNTERS)
_MOE = STEP_COUNTERS.index(experts.STEP_COUNTERS[0])

# the published layer kinds of LFM2-8B-A1B, all 24
LFM2_8B_A1B_LAYERS = (
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "conv", "full_attention", "conv",
    "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention", "conv", "conv", "full_attention", "conv", "conv")
_MIXERS = {"conv": "conv", "full_attention": "attn"}


@dataclasses.dataclass(frozen=True)
class ConvMoeConfig:
    vocab_size: int = 65536
    dim: int = 2048
    n_layers: int = 24
    layer_types: Tuple[str, ...] = LFM2_8B_A1B_LAYERS
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    ffn_dim: int = 7168
    moe_ffn_dim: int = 1792
    n_dense_layers: int = 2
    n_routed_experts: int = 32
    top_k: int = 4
    norm_topk_prob: bool = True
    routed_scale: float = 1.0
    conv_width: int = 3
    rope_theta: float = 1e6
    max_seq_len: int = 128000
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # what the family writes down once (read by models/experts.py): a
    # sigmoid router, 1e-6 in the top-k normalisation, and every routed
    # expert of a layer held (a pipeline stage holds its layers whole)
    scoring = "sigmoid"
    topk_norm_eps = 1e-6
    expert_rank = 0

    def __post_init__(self):
        if len(self.layer_types) != self.n_layers:
            raise ValueError(f"layer_types names {len(self.layer_types)} "
                             f"layers, n_layers is {self.n_layers}")
        unknown = set(self.layer_types) - set(_MIXERS)
        if unknown:
            raise ValueError(f"layer_types {sorted(unknown)}: a layer is "
                             f"one of {sorted(_MIXERS)}")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError("n_dense_layers lies outside the layers")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if self.conv_width < 2:
            raise ValueError("conv_width keeps at least one row a slot")

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        """(mixer, feed-forward) of each layer, in model order."""
        return tuple((_MIXERS[kind], "dense" if i < self.n_dense_layers
                      else "moe")
                     for i, kind in enumerate(self.layer_types))

    def count(self, kind: str) -> int:
        """Layers of a mixer or a feed-forward kind."""
        return sum(kind in pair for pair in self.kinds)

    @property
    def n_held_experts(self) -> int:
        return self.n_routed_experts

    @property
    def lane_head(self) -> int:
        """The head as the flash kernel takes it: whole 128-lane tiles."""
        return -(-self.head_dim // 128) * 128

    def flash_block(self, seq_len: int) -> Optional[int]:
        """The block size the prefill's flash kernel runs a bucket in,
        or None where Mosaic cannot tile it (``ops.banded_attention``
        then; what ``attention_paths()`` reports a bucket by)."""
        from gofr_tpu.ops.pallas import flash_tileable
        block = 1024 if seq_len % 1024 == 0 else 512
        return block if flash_tileable(seq_len, self.lane_head, block,
                                       block) else None


PRESETS: Dict[str, ConvMoeConfig] = {
    # tiny: unit tests and the benchmark's CPU rehearsal; two dense
    # layers, then two periods of (attention, convolution)
    "tiny": ConvMoeConfig(
        vocab_size=256, dim=256, n_layers=6,
        layer_types=("conv", "conv", "full_attention", "conv",
                     "full_attention", "conv"),
        n_heads=4, n_kv_heads=2, head_dim=64, ffn_dim=128, moe_ffn_dim=32,
        n_routed_experts=8, top_k=2, max_seq_len=256),
    "lfm2_8b_a1b": ConvMoeConfig(),
}


def config(preset: str = "tiny", **overrides) -> ConvMoeConfig:
    return dataclasses.replace(PRESETS[preset], **overrides)


def cache_leaves(cfg: ConvMoeConfig) -> Dict[str, Dict[str, Any]]:
    """What the cache holds, by layer kind, kinds in the order they first
    appear. ``attn``: what one *token* leaves in a layer, on pages: K
    and V packed, a token's heads in one row. ``conv`` (``per_slot``):
    what one *slot* holds in a layer whatever its context, the last
    ``conv_width - 1`` rows of ``v``; the pool keeps ``(layers,
    max_slots, *shape)`` and no pages."""
    row = (cfg.n_kv_heads * cfg.head_dim,)
    kinds = {
        "conv": {"layers": cfg.count("conv"), "per_slot": True,
                 "leaves": {"window": (((cfg.conv_width - 1) * cfg.dim,),
                                       cfg.dtype)}},
        "attn": {"layers": cfg.count("attn"), "window": None,
                 "leaves": {"k": (row, cfg.dtype), "v": (row, cfg.dtype)}}}
    order = dict.fromkeys(mixer for mixer, _ in cfg.kinds)
    return {kind: kinds[kind] for kind in order}


# -- parameters ---------------------------------------------------------------

def init(cfg: ConvMoeConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random parameters in the served types: matmul weights
    ~N(0, 1/fan_in), the convolution's taps ~N(0, 1/W), layer gains of
    one; the QK-norm gains ``1.5 * exp(N(0, 0.3^2))`` a lane, so that a
    head's scale is the norm's and not the projection's; the router's
    bias ~N(0, 0.015^2) float32, so that it changes about one token's
    chosen set in four. Jitted with the key as an argument it is one program for
    every seed. A kind's layers are stacked in model order."""
    dt, d = cfg.dtype, cfg.dim
    count = iter(range(1 << 16))

    def normal(*shape):
        return jax.random.normal(jax.random.fold_in(key, next(count)),
                                 shape, jnp.float32)

    def dense(*shape):
        # a layer at a time: one layer's float32 draw at most, never a
        # whole stack's (12 x 32 experts are 5.6 GB of it at the
        # published widths)
        layer_key = jax.random.fold_in(key, next(count))
        return lax.map(lambda i: (jax.random.normal(
            jax.random.fold_in(layer_key, i), shape[1:], jnp.float32)
            / math.sqrt(shape[-2])).astype(dt), jnp.arange(shape[0]))

    def gains(*shape):
        return (1.5 * jnp.exp(0.3 * normal(*shape))).astype(dt)

    def swiglu(*lead, width):
        return {"w_gate": dense(*lead, d, width),
                "w_up": dense(*lead, d, width),
                "w_down": dense(*lead, width, d)}

    q_width = cfg.n_heads * cfg.head_dim
    kv_width = cfg.n_kv_heads * cfg.head_dim
    n = {kind: cfg.count(kind) for kind in ("conv", "attn", "dense", "moe")}
    e = cfg.n_held_experts
    return {
        "tok_emb": (normal(cfg.vocab_size, d) / math.sqrt(d)).astype(dt),
        "out_norm": jnp.ones((d,), dt),
        "conv": {"norm1": jnp.ones((n["conv"], d), dt),
                 "w_in": dense(n["conv"], d, 3 * d),
                 "conv_w": (normal(n["conv"], cfg.conv_width, d)
                            / math.sqrt(cfg.conv_width)).astype(dt),
                 "w_out": dense(n["conv"], d, d)},
        "attn": {"norm1": jnp.ones((n["attn"], d), dt),
                 "wq": dense(n["attn"], d, q_width),
                 "wk": dense(n["attn"], d, kv_width),
                 "wv": dense(n["attn"], d, kv_width),
                 "q_norm": gains(n["attn"], cfg.head_dim),
                 "k_norm": gains(n["attn"], cfg.head_dim),
                 "wo": dense(n["attn"], q_width, d)},
        "dense": {"norm2": jnp.ones((n["dense"], d), dt),
                  **swiglu(n["dense"], width=cfg.ffn_dim)},
        "moe": {"norm2": jnp.ones((n["moe"], d), dt),
                "router": dense(n["moe"], d, cfg.n_routed_experts),
                "router_bias": 0.015 * normal(n["moe"],
                                             cfg.n_routed_experts),
                "experts": swiglu(n["moe"], e, width=cfg.moe_ffn_dim)}}


def init_cache(cfg: ConvMoeConfig, batch: int,
               max_len: Optional[int] = None
               ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """What a prefill of ``batch`` rows fills: ``attn`` the dense rows
    (layers, B, T, n_kv_heads * head_dim), ``conv`` a window a row
    (layers, B, (W - 1) * D)."""
    t_max = max_len or cfg.max_seq_len
    out = {}
    for kind, spec in cache_leaves(cfg).items():
        lead = (spec["layers"], batch) + (
            () if spec.get("per_slot") else (t_max,))
        out[kind] = {name: jnp.zeros(lead + tuple(shape), dtype)
                     for name, (shape, dtype) in spec["leaves"].items()}
    return out


# -- the walk over the layers -------------------------------------------------

def _runs(kinds):
    """[(kind, count)] of the consecutive layers of one kind."""
    runs = []
    for kind in kinds:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return runs


def _period(kinds) -> int:
    """The shortest run of layer kinds that ``kinds`` repeats whole."""
    return next(p for p in range(1, len(kinds) + 1)
                if len(kinds) % p == 0
                and kinds == kinds[:p] * (len(kinds) // p))


def _walk(cfg: ConvMoeConfig, carry, layer):
    """Every layer in model order over ``carry``. ``layer(carry, mixer,
    mixer_idx, ffn, ffn_idx)`` gets the layer's kinds and its place
    among each kind's layers (traced) and returns the carry. The leading
    dense layers go in runs of one kind; the rest is a ``lax.scan`` over
    its periods, each a run of one kind after another; a run of more
    than one layer is a ``lax.scan`` over them."""
    kinds = cfg.kinds
    head, rest = kinds[:cfg.n_dense_layers], kinds[cfg.n_dense_layers:]
    seen = collections.Counter()

    def run(carry, kind, first, count):
        mixer, ffn = kind
        if count == 1:
            return layer(carry, mixer, first[0], ffn, first[1])
        return lax.scan(
            lambda carry, j: (layer(carry, mixer, first[0] + j, ffn,
                                    first[1] + j), None),
            carry, jnp.arange(count, dtype=jnp.int32))[0]

    for kind, n in _runs(head):
        carry = run(carry, kind, tuple(jnp.int32(seen[k]) for k in kind),
                    n)
        seen.update({k: n for k in kind})
    if not rest:
        return carry
    period = _period(rest)
    per = collections.Counter(k for kind in rest[:period] for k in kind)

    def one_period(carry, p):
        within = collections.Counter()
        for kind, n in _runs(rest[:period]):
            carry = run(carry, kind, tuple(
                seen[k] + p * per[k] + within[k] for k in kind), n)
            within.update({k: n for k in kind})
        return carry, None

    return lax.scan(one_period, carry, jnp.arange(
        len(rest) // period, dtype=jnp.int32))[0]


def _at(stack, idx):
    """One layer's leaves out of a kind's stack."""
    return jax.tree.map(
        lambda leaf: lax.dynamic_index_in_dim(leaf, idx, 0, keepdims=False),
        stack)


# -- the layers ---------------------------------------------------------------

def _norm(cfg: ConvMoeConfig, x, gain):
    return rms_norm(x, gain, cfg.norm_eps)


def _gates(layer, u):
    """u (..., D) -> (v = B * x~, C), both in u's type."""
    d = u.shape[-1]
    bcx = u @ layer["w_in"]
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    v = (b.astype(jnp.float32) * x.astype(jnp.float32)).astype(u.dtype)
    return v, c


def _gated_out(layer, c, w):
    """The convolution's sum ``w`` (float32) gated by C, projected."""
    return (c.astype(jnp.float32) * w).astype(c.dtype) @ layer["w_out"]


def _conv_prefill(cfg: ConvMoeConfig, layer, u, lengths):
    """The short convolution over u (B, S, D). Returns (out (B, S, D),
    the window (B, (W - 1) D): the rows of ``v`` before each row's
    prompt end, oldest first)."""
    b, s, d = u.shape
    taps = cfg.conv_width
    with jax.named_scope("conv.mix"):
        v, c = _gates(layer, u)
        padded = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
        w = sum(layer["conv_w"][j].astype(jnp.float32)
                * padded[:, j:j + s].astype(jnp.float32)
                for j in range(taps))
        # padded[i] is v[i - W + 1]: the rows at lengths - W + 1 ..
        # lengths - 1, wherever the bucket ends
        ends = (jnp.full((b,), s, jnp.int32) if lengths is None
                else lengths.astype(jnp.int32))
        window = jnp.take_along_axis(
            padded, (ends[:, None] + jnp.arange(taps - 1))[:, :, None],
            axis=1).reshape(b, (taps - 1) * d)
        return _gated_out(layer, c, w), window


def _conv_step(cfg: ConvMoeConfig, layer, u, held, active):
    """One token a row: u (B, D), held (B, (W - 1) D). Returns (out (B,
    D), the window shifted by this token where ``active`` and as it was
    elsewhere, bit for bit)."""
    d, taps = u.shape[-1], cfg.conv_width
    with jax.named_scope("conv.mix"):
        v, c = _gates(layer, u)
        rows = [held[:, j * d:(j + 1) * d] for j in range(taps - 1)] + [v]
        w = sum(layer["conv_w"][j].astype(jnp.float32)
                * rows[j].astype(jnp.float32) for j in range(taps))
        shifted = jnp.concatenate(rows[1:], axis=-1)
        return (_gated_out(layer, c, w),
                jnp.where(active[:, None], shifted, held))


def _qkv(cfg: ConvMoeConfig, layer, u, positions, rope):
    """u (B, S, D), positions (B, S), rope the (cos, sin) tables -> q
    (B, S, H, Dh), k and v (B, S, Hkv, Dh): QK-norm over each head's
    lanes, then the rotary."""
    b, s, _ = u.shape
    q = (u @ layer["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = (u @ layer["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = (u @ layer["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    with jax.named_scope("attn.qk_norm"):
        q = _norm(cfg, q, layer["q_norm"])
        k = _norm(cfg, k, layer["k_norm"])
    return (apply_rope(q, *rope, positions), apply_rope(k, *rope, positions),
            v)


def _ffn(cfg: ConvMoeConfig, params, ffn, idx, h, valid, decode,
         routes=False):
    """The feed-forward of layer ``idx`` of kind ``ffn`` on h (T, D).
    Returns (y (T, D) in h's type, the expert layer's counters (5,) or
    zeros, the experts chosen (T, top_k) or None)."""
    if ffn == "dense":
        w = _at(params["dense"], idx)
        y = jnp.matmul((jax.nn.silu((h @ w["w_gate"]).astype(jnp.float32))
                        * (h @ w["w_up"]).astype(jnp.float32)
                        ).astype(h.dtype), w["w_down"])
        return y, jnp.zeros((_N_COUNTERS - _MOE,), jnp.int32), None
    stack = params["moe"]
    with jax.named_scope("moe.route"):
        ids, weights = experts.route(
            cfg, lax.dynamic_index_in_dim(stack["router"], idx, 0, False),
            h, lax.dynamic_index_in_dim(stack["router_bias"], idx, 0, False))
    run = experts.experts_hit if decode else experts.experts_grouped
    y, per_expert = run(cfg, stack["experts"], h, ids, weights, valid,
                        at=idx)
    rows = (jnp.int32(h.shape[0]) if valid is None
            else valid.sum().astype(jnp.int32))
    counters = jnp.stack([rows * cfg.top_k, per_expert.sum(),
                          (per_expert > 0).sum().astype(jnp.int32),
                          per_expert.max(), jnp.int32(1)])
    return y.astype(h.dtype), counters, ids


def _head(params, cfg: ConvMoeConfig, x):
    x = _norm(cfg, x, params["out_norm"])
    return jnp.einsum("...d,vd->...v", x,
                      params["tok_emb"]).astype(jnp.float32)


def _attend_prefill(cfg: ConvMoeConfig, q, k, v):
    """Causal attention of a bucket: the flash kernel where it tiles,
    heads zero-padded to whole lanes and the true head's scale, else the
    banded XLA form."""
    block = cfg.flash_block(q.shape[1])
    if block is None:
        return banded_attention(q, k, v, None)
    from gofr_tpu.ops.pallas import flash_attention
    pad = [(0, 0)] * 3 + [(0, cfg.lane_head - cfg.head_dim)]
    out = flash_attention(jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad),
                          block_q=block, block_k=block,
                          sm_scale=cfg.head_dim ** -0.5)
    return out[..., :cfg.head_dim]


def prefill(params: Dict[str, Any], cfg: ConvMoeConfig,
            tokens: jnp.ndarray, cache: Dict[str, Dict[str, jnp.ndarray]],
            lengths: Optional[jnp.ndarray] = None, routes: bool = False):
    """Run the prompts, fill the cache. tokens (B, S) right-padded to
    ``lengths``; returns (last-token logits (B, V), cache: ``attn`` with
    rows [0, S) written, ``conv`` each row's window at its prompt's end,
    cache_len (B,)), and with ``routes`` the experts chosen at every
    position (expert layers, B, S, top_k) as a fourth. Causal attention
    and the causal convolution never see the padding on the right; the
    window is taken at the prompt's end, and padding rows are no routed
    pairs."""
    b, s = tokens.shape
    x = params["tok_emb"][tokens]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    valid = None if lengths is None else (
        positions < lengths[:, None]).reshape(-1)
    chosen = jnp.zeros((cfg.count("moe"), b * s, cfg.top_k), jnp.int32)
    rope = rope_table(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta)

    def layer(carry, mixer, mi, ffn, fi):
        x, cache, chosen = carry
        stack = params[mixer]
        w = _at(stack, mi)
        u = _norm(cfg, x, w["norm1"])
        if mixer == "conv":
            mixed, window = _conv_prefill(cfg, w, u, lengths)
            cache = dict(cache, conv={"window": lax.dynamic_update_index_in_dim(
                cache["conv"]["window"],
                window.astype(cache["conv"]["window"].dtype), mi, 0)})
        else:
            q, k, v = _qkv(cfg, w, u, positions, rope)
            mixed = _attend_prefill(cfg, q, k, v).reshape(b, s, -1) \
                @ w["wo"]
            cache = dict(cache, attn={
                name: lax.dynamic_update_slice(
                    cache["attn"][name],
                    rows.reshape(1, b, s, -1).astype(
                        cache["attn"][name].dtype), (mi, 0, 0, 0))
                for name, rows in (("k", k), ("v", v))})
        x = x + mixed
        gain = _at(params[ffn]["norm2"], fi)
        y, _, ids = _ffn(cfg, params, ffn, fi,
                         _norm(cfg, x, gain).reshape(b * s, -1), valid,
                         decode=False)
        if routes and ids is not None:
            chosen = lax.dynamic_update_index_in_dim(chosen, ids, fi, 0)
        return x + y.reshape(b, s, -1), cache, chosen

    x, cache, chosen = _walk(cfg, (x, cache, chosen), layer)
    if lengths is None:
        last = x[:, -1]
        cache_len = jnp.full((b,), s, jnp.int32)
    else:
        last = x[jnp.arange(b), lengths - 1]
        cache_len = lengths.astype(jnp.int32)
    out = (_head(params, cfg, last), cache, cache_len)
    if routes:
        return out + (chosen.reshape(-1, b, s, cfg.top_k),)
    return out


def decode_step_paged(params: Dict[str, Any], cfg: ConvMoeConfig,
                      token: jnp.ndarray,
                      pool: Dict[str, Dict[str, jnp.ndarray]],
                      page_table: Dict[str, jnp.ndarray],
                      cache_len: jnp.ndarray, active: jnp.ndarray,
                      ragged: bool = False, counters: bool = False,
                      routes: bool = False):
    """One decode step over the pool. ``pool["attn"]``: ``k`` and ``v``
    (layers of the kind, num_pages, page, Hkv * Dh) with
    ``page_table["attn"]`` (B, P), ``num_pages`` the unallocated
    sentinel. ``pool["conv"]``: ``window`` (layers, B, (W - 1) D), row
    ``i`` slot ``i``'s; it has no table. ``active`` (B,) bool gates every
    write: an inactive slot's K/V row goes to the sentinel page and is
    dropped, and its window stays **bit for bit**. ``ragged`` (static)
    reads the pages in place through the Pallas ragged kernel (packed:
    two heads of 64 a 128-lane unit), otherwise a layer's table pages are
    gathered, the oracle. Returns (logits, pool, cache_len + 1), with
    ``counters`` the step's ``STEP_COUNTERS`` after them, and with
    ``routes`` the experts chosen (expert layers, B, top_k) last."""
    batch = token.shape[0]
    x = params["tok_emb"][token][:, None, :]                # (B, 1, D)
    table = page_table["attn"]
    num_pages, page = pool["attn"]["k"].shape[1:3]
    page_row = jnp.take_along_axis(
        table, (cache_len // page)[:, None], axis=1, mode="clip")[:, 0]
    dest = (jnp.where(active, page_row, num_pages), cache_len % page)
    live_rows = jnp.where(active, cache_len, 0).sum().astype(jnp.int32)
    zero, one = jnp.int32(0), jnp.int32(1)
    moe_zeros = [zero] * (_N_COUNTERS - _MOE)
    counted = {
        "conv": jnp.stack([one, active.sum().astype(jnp.int32), zero, zero,
                           zero] + moe_zeros),
        "attn": jnp.stack([zero, zero, one, jnp.int32(ragged), live_rows]
                          + moe_zeros)}
    chosen = jnp.zeros((cfg.count("moe"), batch, cfg.top_k), jnp.int32)
    positions = cache_len[:, None]
    rope = rope_table(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta)

    def layer(carry, mixer, mi, ffn, fi):
        x, pool, counts, chosen = carry
        w = _at(params[mixer], mi)
        u = _norm(cfg, x, w["norm1"])
        if mixer == "conv":
            stack = pool["conv"]["window"]
            held = lax.dynamic_index_in_dim(stack, mi, 0, keepdims=False)
            mixed, window = _conv_step(cfg, w, u[:, 0], held, active)
            pool = dict(pool, conv={"window": lax.dynamic_update_index_in_dim(
                stack, window.astype(stack.dtype), mi, 0)})
            mixed = mixed[:, None]
        else:
            q, k, v = _qkv(cfg, w, u, positions, rope)
            leaves, k_new, v_new = pool["attn"], k[:, 0], v[:, 0]
            if ragged:
                from gofr_tpu.ops.pallas import ragged_paged_decode_attention
                out = ragged_paged_decode_attention(
                    q, leaves["k"], leaves["v"], table, k_new, v_new,
                    cache_len, mi)
            else:
                views = [gather_kv_pages(lax.dynamic_index_in_dim(
                    leaves[name], mi, 0, keepdims=False), table).reshape(
                    batch, -1, cfg.n_kv_heads, cfg.head_dim)
                    for name in "kv"]
                out = decode_attention_cached(q, *views, k_new, v_new,
                                              cache_len)
            row, offset = dest
            pool = dict(pool, attn={
                name: leaves[name].at[mi, row, offset].set(
                    new.reshape(batch, -1), mode="drop")
                for name, new in (("k", k_new), ("v", v_new))})
            mixed = out.reshape(batch, 1, -1) @ w["wo"]
        counts = counts + counted[mixer]
        x = x + mixed
        gain = _at(params[ffn]["norm2"], fi)
        y, moe_counts, ids = _ffn(cfg, params, ffn, fi,
                                  _norm(cfg, x, gain)[:, 0], active,
                                  decode=True)
        counts = counts.at[_MOE:].add(moe_counts)
        if routes and ids is not None:
            chosen = lax.dynamic_update_index_in_dim(chosen, ids, fi, 0)
        return x + y[:, None], pool, counts, chosen

    x, pool, counts, chosen = _walk(
        cfg, (x, pool, jnp.zeros((_N_COUNTERS,), jnp.int32), chosen), layer)
    out = (_head(params, cfg, x[:, 0]), pool, cache_len + 1)
    if counters:
        out += (counts,)
    return out + (chosen,) if routes else out


def decode_step(params: Dict[str, Any], cfg: ConvMoeConfig,
                token: jnp.ndarray, cache: Dict[str, Dict[str, jnp.ndarray]],
                cache_len: jnp.ndarray):
    """One decode step over the dense cache ``init_cache`` /
    ``prefill`` fill (every row active): each row's cache as one table
    of its own pages over the dense rows, through
    ``decode_step_paged``'s gather path. The tests' oracle; the engine
    serves this module paged only. Returns (logits, cache, cache_len +
    1)."""
    layers, batch, t_max, width = cache["attn"]["k"].shape
    pool = {"conv": cache["conv"],
            "attn": {name: leaf.reshape(layers, batch * t_max, 1, width)
                     for name, leaf in cache["attn"].items()}}
    table = jnp.arange(batch * t_max, dtype=jnp.int32).reshape(batch, t_max)
    logits, pool, cache_len = decode_step_paged(
        params, cfg, token, pool, {"attn": table}, cache_len,
        jnp.ones((batch,), bool))
    return logits, {"conv": pool["conv"], "attn": {
        name: leaf.reshape(layers, batch, t_max, width)
        for name, leaf in pool["attn"].items()}}, cache_len
