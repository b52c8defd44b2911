"""Latent-attention decoder with a sparse expert layer, for /generate.

The family of DeepSeek-V2/V3 and openPangu-Ultra-MoE: multi-head latent
attention (MLA) over a *latent* cache, and a feed-forward layer that
routes each token to ``top_k`` of many small experts beside a shared one.
Everything that differs between members of the family is a field of
``MlaMoeConfig`` (sandwich norm, how the router scores, shared experts,
leading dense layers, the chip's share of the experts), so a later member
is data.

Equations (``h`` the normed input of a block, ``RMS(x; g) = x /
sqrt(mean(x^2) + eps) * g``):

- Layer, with ``sandwich_norm``: ``x = x + RMS(Attn(RMS(x; g_in));
  g_post_attn)``, ``x = x + RMS(FFN(RMS(x; g_pre_mlp)); g_post_mlp)``;
  without it the two outer norms are absent. ``FFN`` is a SwiGLU of
  ``ffn_dim`` in the ``n_dense_layers`` leading layers and the expert
  layer after them. Two stacks, each one ``lax.scan``.
- Attention: ``c_q = RMS(W_DQ h)``; ``q = W_UQ c_q``, per head ``[q_n;
  q_r]``; ``[c_kv; k_r] = W_DKV h``; ``c = RMS(c_kv)``; rotary (half-split
  pairing) on ``q_r`` and on the one ``k_r`` all heads share; ``k_h =
  [W_UK,h c; k_r]``, ``v_h = W_UV,h c``; scores over ``sqrt(qk_nope_dim +
  qk_rope_dim)``. **The cache is ``[c; k_r]``**: one row of
  ``kv_lora_rank + qk_rope_dim`` values a token a layer, padded to
  whole lanes (``cache_leaves``, ``cache_row``). ``prefill`` expands
  ``k_h`` / ``v_h``: through the Pallas flash kernel for buckets from
  1024 rows (``flash_expanded_attention``: keys of ``qk_head_dim``
  zero-padded to whole lanes, values of ``v_head_dim``; 32 heads over
  8192 positions are 8.6 GB of float32 scores otherwise), through
  ``expanded_attention`` for shorter ones (16 prompts of 512 at 128
  heads: 0.25 GiB a block of 16 heads). Rotary is plain or, with
  ``rope_scaling``, YaRN's, whose ``mscale`` enters ``softmax_scale``.
  The decode steps are *absorbed*: ``q~_h = W_UK,h^T q_n,h``, score ``= (q~_h . c_t
  + q_r,h . k_r,t) / sqrt(..)``, ``u_h = sum_t p_t c_t``, ``o_h = W_UV,h
  u_h`` — the cached rows are read as they are, never expanded to heads.
- Expert layer (``models/experts.py``, shared with ``models/swa_moe.py``):
  ``s = sigmoid(W_g h)`` (or softmax) over all ``n_routed_experts``, in
  float32; ``T`` = the ``top_k`` largest; ``w_e = routed_scale * s_e /
  sum_{j in T} s_j``; ``y = Shared(h) + sum_{e in T and held} w_e
  Expert_e(h)``. This chip holds experts ``expert_rank * n_held_experts
  ..`` of them; ``prefill`` runs the grouped form (work that follows the
  routed pairs), a decode step the loop over the experts its rows hit
  (``experts_hit``: an expert no row chose is not read). Both keep the
  routed experts' stack out of their layer scans and read ``[layer,
  expert]`` from it: a scan's slice handed to a loop is a copy of a
  layer's experts a layer a call (``docs/tpu/model-serving.md``; every
  member reads in place, ``models/hc_mla_moe.py`` too).

Serving contract (``docs/tpu/model-serving.md``): ``init_cache``,
``prefill``, ``decode_step``, ``decode_step_paged``, ``cache_leaves``
and ``STEP_COUNTERS``.

``models/hc_mla_moe.py`` (a member whose residual is hyper-connected)
imports from here and calls with its own ``Residual``: ``init``,
``init_cache``, ``cache_leaves``, ``_stacks``, ``_ffn``, and the entry
points ``prefill``, ``decode_step``, ``decode_step_paged`` with
everything under them (``_latent``, ``prefill_attention``,
``absorbed_attention``, the two stacks' scans).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.models.experts import (  # noqa: F401  (the module's names)
    STEP_COUNTERS, _block_rows, _held, _swiglu, experts_batched,
    experts_grouped, experts_hit, moe_ffn, route)
from gofr_tpu.ops import apply_rope, rms_norm, rope_table
from gofr_tpu.ops.rotary import yarn_mscale

_NEG_INF = -1e30
_LANES = 128

# what a decode step counts: the expert layer's (models/experts.py)
_N_COUNTERS = len(STEP_COUNTERS)


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    vocab_size: int = 153600
    dim: int = 7680
    n_layers: int = 61
    n_dense_layers: int = 3           # leading layers with a dense FFN
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 18432              # the dense layers' SwiGLU
    moe_ffn_dim: int = 2048           # one expert's SwiGLU
    n_routed_experts: int = 256       # the router's width
    n_held_experts: int = 256         # how many of them live on this chip
    expert_rank: int = 0              # which share: rank * held .. + held
    top_k: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scale: float = 2.5
    scoring: str = "sigmoid"          # or "softmax"
    sandwich_norm: bool = True
    max_seq_len: int = 131072
    rope_theta: float = 25.6e6
    # a published ``rope_scaling`` group of type yarn (a dict; held as
    # its sorted items so that the config stays hashable), or None
    rope_scaling: Any = None
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring must be sigmoid|softmax, "
                             f"got {self.scoring!r}")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError("n_dense_layers must lie in 0..n_layers")
        if (self.expert_rank + 1) * self.n_held_experts \
                > self.n_routed_experts:
            raise ValueError(
                f"share {self.expert_rank} of {self.n_held_experts} experts "
                f"lies outside the router's {self.n_routed_experts}")

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def cache_dim(self) -> int:
        """Values a token leaves in a layer's cache: [c; k_r]."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def cache_row(self) -> int:
        """A cached row: ``cache_dim`` padded with zeros to whole lanes.
        A 576-wide minor dimension is not a whole number of the TPU's
        128 lanes, so the runtime's compact layout would make the
        *pages* the pool's minor dimension, and XLA would re-lay the
        whole pool out twice a tick (described-chip compile, PERF.md
        PR 27)."""
        return -(-self.cache_dim // _LANES) * _LANES

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def rope_group(self) -> Optional[Dict[str, Any]]:
        """``rope_scaling`` as the published group, or None."""
        return None if self.rope_scaling is None else dict(self.rope_scaling)

    @property
    def softmax_scale(self) -> float:
        """What the scores are multiplied by: ``qk_head_dim ** -0.5``,
        times YaRN's ``yarn_mscale(factor, mscale_all_dim) ** 2`` where
        the rotary is scaled."""
        scale, group = self.qk_head_dim ** -0.5, self.rope_group
        if group is not None:
            scale *= yarn_mscale(group["factor"],
                                 group.get("mscale_all_dim", 0.0)) ** 2
        return scale

    def rope(self):
        """The (cos, sin) tables of the rotary dimensions."""
        return rope_table(self.max_seq_len, self.qk_rope_dim,
                          self.rope_theta, self.rope_group)

    def flash_block(self, seq_len: int) -> Optional[int]:
        """The block size the prefill's flash kernel runs a
        ``seq_len``-token bucket in, or None where the bucket goes
        through ``expanded_attention``: buckets of at least 1024 rows
        that split into whole blocks, with values of whole lanes (the
        keys are padded to them). Decided by shape alone, so a CPU run
        and a chip run take one path. Up to 512 rows a prompt is one
        block pair a head and the expanded form's scores are small;
        those buckets stay on the form they were measured on."""
        from gofr_tpu.ops.pallas import flash_tileable
        block = 1024 if seq_len % 1024 == 0 else 512
        if (seq_len >= 1024 and self.v_head_dim % _LANES == 0
                and flash_tileable(seq_len, _LANES, block, block)):
            return block
        return None


PRESETS: Dict[str, MlaMoeConfig] = {
    # tiny: unit tests and the benchmark's CPU rehearsal
    "tiny": MlaMoeConfig(
        vocab_size=256, dim=64, n_layers=3, n_dense_layers=1, n_heads=4,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, ffn_dim=128, moe_ffn_dim=32, n_routed_experts=32,
        n_held_experts=32, top_k=4, max_seq_len=128),
    "pangu-ultra-moe": MlaMoeConfig(),
}


def config(preset: str = "tiny", **overrides) -> MlaMoeConfig:
    return dataclasses.replace(PRESETS[preset], **overrides)


def cache_leaves(cfg: MlaMoeConfig) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """What one token leaves in the cache, a layer: name -> (trailing
    shape, dtype). The page pool and ``init_cache`` build from this."""
    return {"ckv": ((cfg.cache_row,), cfg.dtype)}


# -- parameters ---------------------------------------------------------------

def init(cfg: MlaMoeConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random parameters in the served dtype: matmul weights
    ~N(0, 1/fan_in) (fan-in is every weight's second-last axis), gains of
    one. Jitted with the key as an argument it is one program for every
    seed, each draw fused into its output leaf."""
    dt = cfg.dtype
    d, heads = cfg.dim, cfg.n_heads
    count = iter(range(1 << 16))

    def dense(*shape):
        leaf = jax.random.normal(jax.random.fold_in(key, next(count)),
                                 shape, jnp.float32)
        return (leaf / math.sqrt(shape[-2])).astype(dt)

    def ones(*shape):
        return jnp.ones(shape, dt)

    def block(n):
        norms = ["in_norm", "pre_mlp_norm"]
        if cfg.sandwich_norm:
            norms += ["post_attn_norm", "post_mlp_norm"]
        out = {name: ones(n, d) for name in norms}
        out["attn"] = {
            "w_dq": dense(n, d, cfg.q_lora_rank),
            "q_norm": ones(n, cfg.q_lora_rank),
            "w_uq_n": dense(n, cfg.q_lora_rank, heads * cfg.qk_nope_dim),
            "w_uq_r": dense(n, cfg.q_lora_rank, heads * cfg.qk_rope_dim),
            "w_dkv": dense(n, d, cfg.cache_dim),
            "kv_norm": ones(n, cfg.kv_lora_rank),
            "w_uk": dense(n, heads, cfg.kv_lora_rank, cfg.qk_nope_dim),
            "w_uv": dense(n, heads, cfg.kv_lora_rank, cfg.v_head_dim),
            "w_o": dense(n, heads * cfg.v_head_dim, d)}
        return out

    def swiglu(*lead, width):
        return {"w_gate": dense(*lead, d, width),
                "w_up": dense(*lead, d, width),
                "w_down": dense(*lead, width, d)}

    params: Dict[str, Any] = {
        "tok_emb": (jax.random.normal(jax.random.fold_in(key, next(count)),
                                      (cfg.vocab_size, d), jnp.float32)
                    / math.sqrt(d)).astype(dt),
        "out_norm": ones(d),
        "lm_head": dense(d, cfg.vocab_size)}
    if cfg.n_dense_layers:
        n = cfg.n_dense_layers
        params["dense"] = dict(block(n), **swiglu(n, width=cfg.ffn_dim))
    if cfg.n_moe_layers:
        n = cfg.n_moe_layers
        moe = block(n)
        moe["router"] = dense(n, d, cfg.n_routed_experts)
        moe["experts"] = swiglu(n, cfg.n_held_experts,
                                width=cfg.moe_ffn_dim)
        if cfg.n_shared_experts:
            moe["shared"] = swiglu(
                n, width=cfg.n_shared_experts * cfg.moe_ffn_dim)
        params["moe"] = moe
    return params


def init_cache(cfg: MlaMoeConfig, batch: int,
               max_len: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    """Static-shape latent cache: ``ckv`` (L, B, T, cache_row)."""
    t_max = max_len or cfg.max_seq_len
    return {name: jnp.zeros((cfg.n_layers, batch, t_max, *tail), dtype)
            for name, (tail, dtype) in cache_leaves(cfg).items()}


# -- attention ----------------------------------------------------------------

def _latent(attn, h, cfg: MlaMoeConfig, cos, sin, positions):
    """h (B, S, D) -> q_n (B,S,H,dn), q_r (B,S,H,dr) rotated, and the
    cached row [c; k_r; zeros] (B, S, cache_row). W_UQ is held as its
    two column blocks, the heads' nope and rope parts."""
    b, s, _ = h.shape
    c_q = rms_norm(h @ attn["w_dq"], attn["q_norm"], cfg.norm_eps)
    q_n = (c_q @ attn["w_uq_n"]).reshape(b, s, cfg.n_heads, cfg.qk_nope_dim)
    q_r = (c_q @ attn["w_uq_r"]).reshape(b, s, cfg.n_heads, cfg.qk_rope_dim)
    q_r = apply_rope(q_r, cos, sin, positions)
    down = h @ attn["w_dkv"]
    c = rms_norm(down[..., :cfg.kv_lora_rank], attn["kv_norm"],
                 cfg.norm_eps)
    k_r = apply_rope(down[..., None, cfg.kv_lora_rank:], cos, sin,
                     positions)[:, :, 0]
    pad = jnp.zeros((b, s, cfg.cache_row - cfg.cache_dim), c.dtype)
    return q_n, q_r, jnp.concatenate([c, k_r, pad], axis=-1)


def expanded_attention(attn, q_n, q_r, rows, cfg: MlaMoeConfig):
    """Causal self-attention over a whole prompt with per-head keys and
    values expanded from the cached rows. q_n (B,S,H,dn), q_r (B,S,H,dr),
    rows (B,S,cache_row) -> (B, S, H * v_head_dim). Heads go in blocks
    of 16, one after the other: the (B, heads, S, S) float32 scores of
    all 128 at once are 2 GiB for 16 prompts of 512, a block's 0.25."""
    b, s, heads = q_n.shape[:3]
    c = rows[..., :cfg.kv_lora_rank]
    k_r = rows[..., cfg.kv_lora_rank:cfg.cache_dim]
    causal = jnp.tril(jnp.ones((s, s), bool))
    block = math.gcd(heads, 16)

    def blocks(x, axis):                 # heads -> (blocks, block) in front
        x = x.reshape(*x.shape[:axis], heads // block, block,
                      *x.shape[axis + 1:])
        return jnp.moveaxis(x, axis, 0)

    def one_block(args):
        w_uk, w_uv, q_n, q_r = args
        k_n = jnp.einsum("bsc,hcd->bshd", c, w_uk)
        v = jnp.einsum("bsc,hcd->bshd", c, w_uv)
        scores = (jnp.einsum("bshd,bthd->bhst", q_n, k_n,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshd,btd->bhst", q_r, k_r,
                               preferred_element_type=jnp.float32))
        scores = scores * cfg.softmax_scale
        scores = jnp.where(causal[None, None], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(rows.dtype)
        return jnp.einsum("bhst,bthd->bshd", probs, v)

    out = lax.map(one_block, (blocks(attn["w_uk"], 0), blocks(attn["w_uv"], 0),
                              blocks(q_n, 2), blocks(q_r, 2)))
    return jnp.moveaxis(out, 0, 2).reshape(b, s, heads * cfg.v_head_dim)


def flash_expanded_attention(attn, q_n, q_r, rows, cfg: MlaMoeConfig,
                             block: int):
    """``expanded_attention`` through the Pallas flash kernel
    (``ops.pallas.flash_attention``), for prompts whose (heads, S, S)
    scores cannot exist: 32 heads over 8192 positions are 8.6 GB in
    float32. Keys ``[W_UK,h c; k_r]`` and values ``W_UV,h c`` are
    expanded from the rows a head a column block; the keys' and
    queries' ``qk_head_dim`` (192) is padded with zeros to whole lanes
    (256): the kernel's blocks are lane tiles, and a 192-deep
    contraction takes the MXU two 128-deep passes as a 256-deep one
    does."""
    from gofr_tpu.ops.pallas import flash_attention
    b, s, heads = q_n.shape[:3]
    c = rows[..., :cfg.kv_lora_rank]
    k_r = rows[..., None, cfg.kv_lora_rank:cfg.cache_dim]
    pad = jnp.zeros((b, s, heads, -cfg.qk_head_dim % _LANES), rows.dtype)
    q = jnp.concatenate([q_n, q_r, pad], axis=-1)
    k = jnp.concatenate(
        [jnp.einsum("bsc,hcd->bshd", c, attn["w_uk"]),
         jnp.broadcast_to(k_r, (b, s, heads, cfg.qk_rope_dim)), pad],
        axis=-1)
    v = jnp.einsum("bsc,hcd->bshd", c, attn["w_uv"])
    out = flash_attention(q, k, v, block_q=block, block_k=block,
                          sm_scale=cfg.softmax_scale)
    return out.reshape(b, s, heads * cfg.v_head_dim)


def prefill_attention(attn, q_n, q_r, rows, cfg: MlaMoeConfig):
    """A prompt's causal self-attention: the flash kernel where the
    bucket tiles (``cfg.flash_block``), else the expanded form."""
    block = cfg.flash_block(q_n.shape[1])
    if block is None:
        return expanded_attention(attn, q_n, q_r, rows, cfg)
    return flash_expanded_attention(attn, q_n, q_r, rows, cfg, block)


def absorbed_attention(attn, q_n, q_r, view, valid, cfg: MlaMoeConfig):
    """One query a row against cached rows read as they are. q_n
    (B,H,dn), q_r (B,H,dr), view (B,T,cache_row), valid (B,T) bool ->
    (B, H * v_head_dim). W_UK is absorbed into the query and W_UV applied
    to the weighted latent, so no per-head key or value of a cached token
    is ever formed."""
    b = view.shape[0]
    q_abs = jnp.einsum("bhd,hcd->bhc", q_n, attn["w_uk"])
    pad = jnp.zeros((*q_r.shape[:2], cfg.cache_row - cfg.cache_dim),
                    q_r.dtype)
    q_full = jnp.concatenate([q_abs, q_r, pad], axis=-1)   # (B,H,cache_row)
    scores = jnp.einsum("bhc,btc->bht", q_full, view,
                        preferred_element_type=jnp.float32)
    scores = scores * cfg.softmax_scale
    scores = jnp.where(valid[:, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(view.dtype)
    u = jnp.einsum("bht,btc->bhc", probs, view[..., :cfg.kv_lora_rank])
    out = jnp.einsum("bhc,hcd->bhd", u, attn["w_uv"])
    return out.reshape(b, cfg.n_heads * cfg.v_head_dim)


# -- feed-forward: the expert layer is models/experts.py's ------------------

# -- the two stacks -------------------------------------------------------------

def _stacks(params, cfg: MlaMoeConfig):
    """(name, stacked layers, index of the stack's first layer)."""
    out = []
    if cfg.n_dense_layers:
        out.append(("dense", params["dense"], 0))
    if cfg.n_moe_layers:
        out.append(("moe", params["moe"], cfg.n_dense_layers))
    return out


def _ffn(cfg: MlaMoeConfig, kind: str, layer, h, valid, grouped):
    """A layer's feed-forward on the normed h (B, S, D): the SwiGLU of a
    leading dense layer, or the expert layer. Returns (y, counters,
    experts chosen (B, S, top_k); zeros if dense)."""
    b, s, d = h.shape
    if kind == "dense":
        return (_swiglu(layer, h), jnp.zeros((_N_COUNTERS,), jnp.int32),
                jnp.zeros((b, s, cfg.top_k), jnp.int32))
    y, counters, ids = moe_ffn(
        cfg, layer, h.reshape(b * s, d),
        None if valid is None else valid.reshape(b * s), grouped)
    return y.reshape(b, s, d), counters, ids.reshape(b, s, cfg.top_k)


def _layer(cfg: MlaMoeConfig, kind: str, layer, x, attend, valid, grouped):
    """One layer on x (B, S, D). ``attend(attn_params, h)`` gives the
    attention output before W_O and whatever it carries. Returns (x,
    carried, counters, experts chosen (B, S, top_k); zeros if dense)."""
    h = rms_norm(x, layer["in_norm"], cfg.norm_eps)
    attn, carried = attend(layer["attn"], h)
    attn = attn @ layer["attn"]["w_o"]
    if cfg.sandwich_norm:
        attn = rms_norm(attn, layer["post_attn_norm"], cfg.norm_eps)
    x = x + attn
    h = rms_norm(x, layer["pre_mlp_norm"], cfg.norm_eps)
    y, counters, ids = _ffn(cfg, kind, layer, h, valid, grouped)
    if cfg.sandwich_norm:
        y = rms_norm(y, layer["post_mlp_norm"], cfg.norm_eps)
    return x + y, carried, counters, ids


class Residual:
    """A member's residual path, what ``prefill`` and the decode steps
    are built around: ``spread`` makes the state from the embedded
    tokens (B, S, D), ``layer`` is one layer on it (``_layer``'s
    signature), ``gather`` gives back the (B, S, D) the final norm
    reads. The family's own: one stream, ``x + f(x)``. A member whose
    residual is of another form (``models/hc_mla_moe.py``) passes its
    own to the entry points below."""
    spread = staticmethod(lambda cfg, x: x)
    layer = staticmethod(_layer)
    gather = staticmethod(lambda x: x)


def _head(params, cfg: MlaMoeConfig, x):
    x = rms_norm(x, params["out_norm"], cfg.norm_eps)
    return (x @ params["lm_head"]).astype(jnp.float32)


def prefill(params: Dict[str, Any], cfg: MlaMoeConfig, tokens: jnp.ndarray,
            cache: Dict[str, jnp.ndarray],
            lengths: Optional[jnp.ndarray] = None, routes: bool = False,
            residual=Residual):
    """Run the prompts, fill the cache. tokens (B, S) right-padded to
    ``lengths``; returns (last-token logits (B, V), cache with rows
    [0, S) written, cache_len (B,)). Attention is expanded; the expert
    layer is grouped, and padding rows are routed nowhere. ``routes``
    adds the experts chosen, (expert layers, B, S, top_k), for a
    comparison of the routing with a reference's. The routed experts'
    stack stays out of the layer scan and the grouped product reads
    ``[layer, expert]`` from it (``experts_grouped``'s ``at``): the
    scan's slice of it is a copy of a layer's experts before every
    block loop."""
    b, s = tokens.shape
    cos, sin = cfg.rope()
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    valid = (None if lengths is None
             else positions < lengths.astype(jnp.int32)[:, None])
    x = residual.spread(cfg, params["tok_emb"][tokens])

    def attend(attn, h):
        q_n, q_r, rows = _latent(attn, h, cfg, cos, sin, positions)
        return prefill_attention(attn, q_n, q_r, rows, cfg), rows

    written, chosen = [], None
    for kind, stack, _ in _stacks(params, cfg):
        scanned = stack
        if kind == "moe":       # all but the routed experts, and the index
            scanned = ({name: leaf for name, leaf in stack.items()
                        if name != "experts"},
                       jnp.arange(cfg.n_moe_layers, dtype=jnp.int32))

        def body(x, layer, kind=kind, stack=stack):
            if kind == "moe":
                layer, at = layer
                layer = dict(layer, experts=stack["experts"], experts_at=at)
            x, rows, _, ids = residual.layer(cfg, kind, layer, x, attend,
                                             valid, True)
            return x, (rows, ids)

        x, (rows, ids) = lax.scan(body, x, scanned)
        written.append(rows)                                # (n, B, S, C)
        if kind == "moe":
            chosen = ids
    x = residual.gather(x)
    rows = jnp.concatenate(written, axis=0)
    new_cache = {"ckv": lax.dynamic_update_slice_in_dim(
        cache["ckv"], rows.astype(cache["ckv"].dtype), 0, axis=2)}
    if lengths is None:
        last = x[:, -1]
        cache_len = jnp.full((b,), s, jnp.int32)
    else:
        last = x[jnp.arange(b), lengths - 1]
        cache_len = lengths.astype(jnp.int32)
    out = (_head(params, cfg, last), new_cache, cache_len)
    return out + (chosen,) if routes else out


def _decode(params, cfg: MlaMoeConfig, token, leaf, cache_len, active,
            append, view_of, residual=Residual, routes=False):
    """One absorbed decode step. ``append(leaf, idx, row)`` writes layer
    ``idx``'s new row for every sequence, ``view_of(leaf, idx)`` gives
    the (B, T, cache_row) rows attention reads. The new row is written
    first and read back with the rest: position ``cache_len`` is valid.
    The routed experts' stack stays out of the layer scan, as in
    ``prefill``: the expert layer's loop over the experts hit reads
    ``[layer, expert]`` from it (``experts_hit``'s ``at``), where the
    scan's slice would be a copy of a layer's experts every step.
    Returns (logits, leaf, counters), and with ``routes`` the experts
    chosen, (expert layers, B, top_k), as a fourth."""
    cos, sin = cfg.rope()
    positions = cache_len[:, None]
    x = residual.spread(cfg, params["tok_emb"][token][:, None, :])
    counters = jnp.zeros((_N_COUNTERS,), jnp.int32)
    chosen = None

    for kind, stack, first in _stacks(params, cfg):
        n = jax.tree.leaves(stack)[0].shape[0]
        scanned = stack
        if kind == "moe":       # all but the routed experts, as prefill
            scanned = {name: leaf for name, leaf in stack.items()
                       if name != "experts"}

        def body(carry, layer_and_idx, kind=kind, stack=stack, first=first):
            x, leaf, counters = carry
            layer, idx = layer_and_idx
            if kind == "moe":
                layer = dict(layer, experts=stack["experts"],
                             experts_at=idx - first)

            def attend(attn, h):
                q_n, q_r, row = _latent(attn, h, cfg, cos, sin, positions)
                grown = append(leaf, idx, row[:, 0])
                view = view_of(grown, idx)
                valid = (jnp.arange(view.shape[1])[None, :]
                         <= cache_len[:, None])
                out = absorbed_attention(attn, q_n[:, 0], q_r[:, 0], view,
                                         valid, cfg)
                return out[:, None, :], grown

            x, leaf, counted, ids = residual.layer(
                cfg, kind, layer, x, attend,
                None if active is None else active[:, None], False)
            return (x, leaf, counters + counted), \
                (ids[:, 0] if routes else None)

        (x, leaf, counters), ids = lax.scan(
            body, (x, leaf, counters),
            (scanned, first + jnp.arange(n, dtype=jnp.int32)))
        if kind == "moe":
            chosen = ids
    out = (_head(params, cfg, residual.gather(x)[:, 0]), leaf, counters)
    return out + (chosen,) if routes else out


def decode_step(params: Dict[str, Any], cfg: MlaMoeConfig,
                token: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                cache_len: jnp.ndarray, window: Optional[int] = None,
                residual=Residual
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], jnp.ndarray]:
    """One decode step over the dense latent cache (L, B, T, cache_row).
    ``window`` statically bounds the rows attention reads."""
    batch = jnp.arange(token.shape[0])

    def append(leaf, idx, row):
        return leaf.at[idx, batch, cache_len].set(row, mode="drop")

    def view_of(leaf, idx):
        view = lax.dynamic_index_in_dim(leaf, idx, 0, keepdims=False)
        return view if window is None else view[:, :window]

    logits, leaf, _ = _decode(params, cfg, token, cache["ckv"], cache_len,
                              None, append, view_of, residual)
    return logits, {"ckv": leaf}, cache_len + 1


def decode_step_paged(params: Dict[str, Any], cfg: MlaMoeConfig,
                      token: jnp.ndarray, pool: Dict[str, jnp.ndarray],
                      page_table: jnp.ndarray, cache_len: jnp.ndarray,
                      active: jnp.ndarray, counters: bool = False,
                      residual=Residual, routes: bool = False):
    """One decode step over the latent page pool: ``pool["ckv"]`` (L,
    num_pages, page, cache_row), ``page_table`` (B, P) with ``num_pages``
    as the unallocated sentinel, ``active`` (B,) bool gating the append
    (an inactive slot's page may belong to another stream by now: its
    row goes to the sentinel page and is dropped). Per layer the table's
    pages are gathered as they are, (B, P * page, cache_row), and
    attended in the absorbed form: plain XLA, a gather and two products.
    Returns (logits, pool, cache_len + 1), and with ``counters`` the
    step's ``STEP_COUNTERS`` over the active rows as a fourth, with
    ``routes`` the experts chosen, (expert layers, B, top_k), as the
    last (for a comparison of the routing with a reference's)."""
    num_pages, page = pool["ckv"].shape[1:3]
    page_col = cache_len // page
    page_row = jnp.take_along_axis(page_table, page_col[:, None], axis=1,
                                   mode="clip")[:, 0]
    dest_row = jnp.where(active, page_row, num_pages)       # sentinel: drop
    offset = cache_len % page

    def append(leaf, idx, row):
        return leaf.at[idx, dest_row, offset].set(row, mode="drop")

    def view_of(leaf, idx):
        # one gather straight out of the stacked leaf: a layer's plane
        # sliced out first is a copy of the whole pool a layer a step.
        # Sentinel ids clamp to a real page, masked by cache_len
        pages = leaf[idx, page_table]                   # (B, P, page, C)
        return pages.reshape(pages.shape[0], -1, pages.shape[-1])

    logits, leaf, counted, *chosen = _decode(
        params, cfg, token, pool["ckv"], cache_len, active, append, view_of,
        residual, routes)
    out = (logits, {"ckv": leaf}, cache_len + 1)
    return out + ((counted,) if counters else ()) + tuple(chosen)
