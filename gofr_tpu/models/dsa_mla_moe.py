"""Latent attention with a learned sparse selection in its full layers,
latent attention of other widths over a sliding window in the others,
headwise gates, and a sparse expert layer, for /generate.

The family of dots-studio's dots3-note (``model_type`` ``dots3_note``):
DeepSeek-V3's latent attention (MLA) with DeepSeek-V3.2's sparse
attention indexer (DSA) in the ``full_attention`` layers, and in the
``sliding_attention`` layers a latent attention of its own widths over
the last ``sliding_window`` tokens, with no indexer. Every layer gates
its heads' outputs before ``W_O``. The feed-forward is a SwiGLU in the
leading dense layers and the expert layer of ``models/experts.py`` after
them (sigmoid scores, ``noaux_tc``'s correction bias, a shared expert).

Equations (``h`` the normed input of a block, ``RMS(x; g) = x /
sqrt(mean(x^2) + eps) * g``, ``LN`` a LayerNorm with gain and bias):

- Layer: ``x = x + Attn_kind(RMS(x; g_in))``, ``x = x +
  FFN(RMS(x; g_mlp))``; ``logits = RMS(x; g_out) W_head`` (untied).
- Latent attention, both kinds with their own widths (``view(kind)``,
  an ``mla_moe.MlaMoeConfig``): ``c_q = RMS(W_DQ h)``, ``q = W_UQ c_q``
  per head ``[q_n; q_r]``; ``[c_kv; k_r] = W_DKV h``, ``c = RMS(c_kv)``;
  rotary (half-split) on ``q_r`` and the shared ``k_r``, with the kind's
  own ``theta``; ``k_h = [W_UK,h c; k_r]``, ``v_h = W_UV,h c``; scores
  over ``sqrt(qk_nope + qk_rope)``. **The headwise gate**: ``o_h <-
  sigmoid(h W_G)_h o_h`` before ``W_O``. The cache is the row ``[c;
  k_r]`` padded to whole lanes (576 -> 640 in a full layer, 1088 -> 1152
  in a sliding one). A sliding layer's query ``t`` attends ``t - window
  < s <= t``.
- The indexer (full layers): ``qI_j = W_IQ,j c_q`` (``index_n_heads``
  heads of ``index_head_dim``), ``kI_s = LN(W_IK h_s)`` (one key a
  token), rotary on the first ``qk_rope_dim`` dimensions of both, ``w_j
  = (W_IW h)_j / sqrt(index_n_heads * index_head_dim)``; ``I_{t,s} =
  sum_j w_j ReLU(qI_j . kI_s)``. ``S_t`` is the ``index_topk`` keys ``s
  <= t`` of the largest ``I_{t,s}``, all of them while there are fewer;
  a full layer's softmax runs over ``S_t`` only. **The cache of a full
  layer keeps two leaves a token**: the latent row ``ckv`` and the index
  key ``ik``, both in the ``full`` kind's pages (one table: a position's
  row and key share a page id).

**Selection.** A decode step (``decode_step_paged``) appends the token's
row and key, scores the slot's live keys where they lie through the
Pallas ``index_scores`` kernel (its gathered oracle,
``ops.attention.index_scores``, where ``select.index_tileable`` refuses
the shape), and selects the exact top ``min(index_topk, table
positions)`` as a mask (``_top_mask``, the prefill's threshold below).
The Pallas ``sparse_latent_attention`` kernel then walks the slot's live
latent pages where they lie and attends the masked rows absorbed (the
query with ``W_UK`` in it, an online softmax a head, ``W_UV`` applied
to the weighted latent after): no row is gathered and no position is
looked up in the table. Where ``select.sparse_latent_tileable`` refuses
the shape (the tiny preset's four heads, a dense cache's one page of its
whole length) the step takes ``lax.top_k``'s positions, maps them
through the slot's table to (page, offset), gathers the selected latent
rows and runs ``mla_moe.absorbed_attention`` over them: the kernel's
oracle. Both attend the same set. A sliding layer gathers its window's
pages alone (the columns from the window's lower
bound; the engine has given the pages behind it back to the pool). A
prefill selects per query row, in blocks of ``_QUERY_BLOCK`` rows, with
an exact threshold: the ``index_topk``-th largest score of each row
found bit by bit (``_top_mask``: 32 counting passes over the row's
scores as ordered integers), every key at or above it taken; the keys a
block of queries cannot reach are left out in four static steps. The
selection, a ``(B, S, S)`` mask, is then attended over expanded keys and
values by the Pallas flash kernel given the mask (``_selected_attention``:
its scores never leave the chip's vector memory), or in blocks of plain
XLA where the bucket does not tile; no ``(bucket, bucket, heads)`` array
exists either way. A bucket no longer than ``index_topk`` selects every
key, and its full layers attend causally through
``mla_moe.prefill_attention``'s flash kernel.
Keys that tie at a row's ``index_topk``-th score are taken in position
order at a prefill as at a decode step: both select ``lax.top_k``'s
set (ties are real: a key every indexer head scores below zero scores
exactly 0).

The layers run unrolled, each with its own parameters (``params
["layers"][i]``), so a layer's routed experts are read where they lie
by the expert layer's loops (``experts_hit``, ``experts_grouped``). A
kind's cache is stacked over the kind's layers in model order. Serving
contract (``docs/tpu/model-serving.md``): ``init``, ``init_cache``,
``cache_leaves``, ``prefill``, ``decode_step``, ``decode_step_paged``
and ``STEP_COUNTERS``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.models import experts, mla_moe
from gofr_tpu.models.experts import (  # noqa: F401  (the module's names)
    _swiglu, moe_ffn, route)
from gofr_tpu.ops import (apply_rope, banded_attention,
                          layer_norm, rms_norm)
from gofr_tpu.ops.attention import index_scores as gathered_index_scores

# layer_types' names for the two kinds, and the kinds' own
KIND_OF = {"full_attention": "full", "sliding_attention": "window",
           "full": "full", "window": "window"}

# what a decode step counts: the expert layer's five, then a full layer's
# live rows (the keys its indexer scores) and the rows it selects, its
# calls (one a full layer a step), a sliding layer's rows in its window,
# and the full layers' calls that attended in the sparse latent kernel;
# rows are summed over the active slots and the kind's layers. Summed by
# the engine over a tick's steps
STEP_COUNTERS = experts.STEP_COUNTERS + (
    "dsa.rows_live", "dsa.rows_selected", "dsa.calls", "attn.window_rows",
    "dsa.kernel_calls")
_N_MOE = len(experts.STEP_COUNTERS)
_N_COUNTERS = len(STEP_COUNTERS)

_LANES = 128
_NEG_INF = -1e30
# query rows of a prefill's selection block: its (rows, index heads,
# bucket) float32 scores are 0.2 GB at 64 heads over 6144 keys (and the
# blocked XLA attention's (rows, heads, bucket) 0.4 GB at 128 heads)
_QUERY_BLOCK = 128


@dataclasses.dataclass(frozen=True)
class DsaMlaMoeConfig:
    vocab_size: int = 152064
    dim: int = 5120
    n_layers: int = 46
    # a kind a layer, in model order
    layer_types: Tuple[str, ...] = (
        ("full_attention",) * 2
        + ("sliding_attention",) * 3 + ("full_attention",)) \
        + (("sliding_attention",) * 3 + ("full_attention",)) * 10
    n_dense_layers: int = 1             # leading layers with a dense FFN
    # full layers: latent attention and the indexer
    n_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    index_norm_eps: float = 1e-6
    # sliding layers: latent attention of their own widths
    sliding_window: int = 513
    swa_n_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_dim: int = 192
    swa_qk_rope_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    # feed-forward
    ffn_dim: int = 13824                # the dense layers' SwiGLU
    moe_ffn_dim: int = 1536             # one expert's SwiGLU
    n_routed_experts: int = 256         # the router's width
    n_held_experts: int = 256           # how many of them live here
    expert_rank: int = 0                # which share: rank * held ..
    top_k: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scale: float = 1.0
    max_seq_len: int = 524288
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    # what models/experts.py reads and this family has not varied
    scoring = "sigmoid"
    topk_norm_eps = 0.0

    def __post_init__(self):
        if len(self.layer_types) != self.n_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"n_layers is {self.n_layers}")
        unknown = sorted(set(self.layer_types) - set(KIND_OF))
        if unknown:
            raise ValueError(f"layer_types: unknown kinds {unknown}; "
                             f"have {sorted(KIND_OF)}")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError("n_dense_layers must lie in 0..n_layers")
        if (self.expert_rank + 1) * self.n_held_experts \
                > self.n_routed_experts:
            raise ValueError(
                f"share {self.expert_rank} of {self.n_held_experts} experts "
                f"lies outside the router's {self.n_routed_experts}")
        if self.qk_rope_dim > self.index_head_dim:
            raise ValueError("the indexer rotates its first qk_rope_dim "
                             "dimensions: index_head_dim is shorter")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The kind of each layer, in model order."""
        return tuple(KIND_OF[name] for name in self.layer_types)

    def ordinal(self, layer: int) -> int:
        """Layer ``layer``'s place among the layers of its kind: its
        index into the kind's stacked cache."""
        return self.kinds[:layer].count(self.kinds[layer])

    def window_of(self, kind: str) -> Optional[int]:
        return self.sliding_window if kind == "window" else None

    def view(self, kind: str) -> mla_moe.MlaMoeConfig:
        """A kind's latent attention as ``mla_moe``'s config: what its
        ``absorbed_attention``, ``prefill_attention`` and the rotary
        table read."""
        swa = kind == "window"
        return mla_moe.MlaMoeConfig(
            vocab_size=self.vocab_size, dim=self.dim, n_layers=1,
            n_dense_layers=0,
            n_heads=self.swa_n_heads if swa else self.n_heads,
            q_lora_rank=self.swa_q_lora_rank if swa else self.q_lora_rank,
            kv_lora_rank=self.swa_kv_lora_rank if swa
            else self.kv_lora_rank,
            qk_nope_dim=self.swa_qk_nope_dim if swa else self.qk_nope_dim,
            qk_rope_dim=self.swa_qk_rope_dim if swa else self.qk_rope_dim,
            v_head_dim=self.swa_v_head_dim if swa else self.v_head_dim,
            rope_theta=self.swa_rope_theta if swa else self.rope_theta,
            max_seq_len=self.max_seq_len, norm_eps=self.norm_eps,
            dtype=self.dtype)


PRESETS: Dict[str, DsaMlaMoeConfig] = {
    # tiny: unit tests and the benchmark's CPU rehearsal; the published
    # pattern's first six layers, a selection of 16 keys
    "tiny": DsaMlaMoeConfig(
        vocab_size=256, dim=64, n_layers=6,
        layer_types=("full_attention",) * 2 + ("sliding_attention",) * 3
        + ("full_attention",),
        n_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=16,
        qk_rope_dim=8, v_head_dim=16, index_n_heads=4, index_head_dim=16,
        index_topk=16, sliding_window=12, swa_n_heads=4, swa_q_lora_rank=32,
        swa_kv_lora_rank=24, swa_qk_nope_dim=24, swa_qk_rope_dim=8,
        swa_v_head_dim=16, ffn_dim=128, moe_ffn_dim=32, n_routed_experts=32,
        n_held_experts=32, top_k=4, max_seq_len=128),
    "dots3-note": DsaMlaMoeConfig(),
}


def config(preset: str = "tiny", **overrides) -> DsaMlaMoeConfig:
    return dataclasses.replace(PRESETS[preset], **overrides)


def cache_leaves(cfg: DsaMlaMoeConfig) -> Dict[str, Dict[str, Any]]:
    """What one token leaves in the cache, by layer kind (the page pool
    keeps pages and a slot's table a kind): ``full`` keeps the latent
    row ``ckv`` and the index key ``ik`` and reads the whole context;
    ``window`` keeps a latent row of its own width and reads its
    window."""
    out: Dict[str, Dict[str, Any]] = {}
    for kind in cfg.kinds:
        if kind in out:
            continue
        leaves = {"ckv": ((cfg.view(kind).cache_row,), cfg.dtype)}
        if kind == "full":
            leaves["ik"] = ((cfg.index_head_dim,), cfg.dtype)
        out[kind] = {"layers": cfg.kinds.count(kind),
                     "window": cfg.window_of(kind), "leaves": leaves}
    return out


# -- parameters ---------------------------------------------------------------

def init(cfg: DsaMlaMoeConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random parameters in the served dtype: matmul weights
    ~N(0, 1/fan_in) (fan-in is every weight's second-last axis), gains of
    one, the indexer's LayerNorm bias zero, the router's correction bias
    ~N(0, 0.1^2) in float32. Jitted with the key as an argument it is one
    program for every seed. ``layers`` is a tuple a layer."""
    dt, d = cfg.dtype, cfg.dim
    count = iter(range(1 << 16))

    def normal(*shape):
        return jax.random.normal(jax.random.fold_in(key, next(count)),
                                 shape, jnp.float32)

    def dense(*shape):
        return (normal(*shape) / math.sqrt(shape[-2])).astype(dt)

    def ones(*shape):
        return jnp.ones(shape, dt)

    def swiglu(*lead, width):
        return {"w_gate": dense(*lead, d, width),
                "w_up": dense(*lead, d, width),
                "w_down": dense(*lead, width, d)}

    def attention(kind):
        view = cfg.view(kind)
        heads = view.n_heads
        out = {"w_dq": dense(d, view.q_lora_rank),
               "q_norm": ones(view.q_lora_rank),
               "w_uq_n": dense(view.q_lora_rank, heads * view.qk_nope_dim),
               "w_uq_r": dense(view.q_lora_rank, heads * view.qk_rope_dim),
               "w_dkv": dense(d, view.cache_dim),
               "kv_norm": ones(view.kv_lora_rank),
               "w_uk": dense(heads, view.kv_lora_rank, view.qk_nope_dim),
               "w_uv": dense(heads, view.kv_lora_rank, view.v_head_dim),
               "w_o": dense(heads * view.v_head_dim, d),
               "w_gate": dense(d, heads)}
        if kind == "full":
            out.update(
                w_iq=dense(view.q_lora_rank,
                           cfg.index_n_heads * cfg.index_head_dim),
                w_ik=dense(d, cfg.index_head_dim),
                ik_norm=ones(cfg.index_head_dim),
                ik_bias=jnp.zeros((cfg.index_head_dim,), dt),
                w_iw=dense(d, cfg.index_n_heads))
        return out

    def layer(i, kind):
        out = {"in_norm": ones(d), "pre_mlp_norm": ones(d),
               "attn": attention(kind)}
        if i < cfg.n_dense_layers:
            out.update(swiglu(width=cfg.ffn_dim))
        else:
            out["router"] = dense(d, cfg.n_routed_experts)
            out["router_bias"] = 0.1 * normal(cfg.n_routed_experts)
            out["experts"] = swiglu(cfg.n_held_experts,
                                    width=cfg.moe_ffn_dim)
            if cfg.n_shared_experts:
                out["shared"] = swiglu(
                    width=cfg.n_shared_experts * cfg.moe_ffn_dim)
        return out

    return {"tok_emb": (normal(cfg.vocab_size, d) / math.sqrt(d)).astype(dt),
            "out_norm": ones(d),
            "lm_head": dense(d, cfg.vocab_size),
            "layers": tuple(layer(i, kind)
                            for i, kind in enumerate(cfg.kinds))}


def init_cache(cfg: DsaMlaMoeConfig, batch: int,
               max_len: Optional[int] = None
               ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Static-shape dense cache, a kind: ``{kind: {leaf: (layers of the
    kind, B, T, width)}}``. A window kind's is as long as the others'
    here (positions index it); the page pool is where a window costs a
    window."""
    t_max = max_len or cfg.max_seq_len
    return {kind: {name: jnp.zeros((spec["layers"], batch, t_max, *tail),
                                   dtype)
                   for name, (tail, dtype) in spec["leaves"].items()}
            for kind, spec in cache_leaves(cfg).items()}


# -- attention ----------------------------------------------------------------

def _latent(attn, h, view: mla_moe.MlaMoeConfig, rope, positions):
    """h (B, S, D) -> c_q (B, S, q_lora), q_n (B,S,H,dn), q_r (B,S,H,dr)
    rotated, and the cached row [c; k_r; zeros] (B, S, cache_row):
    ``mla_moe._latent`` with ``c_q`` kept for the indexer."""
    b, s, _ = h.shape
    c_q = rms_norm(h @ attn["w_dq"], attn["q_norm"], view.norm_eps)
    q_n = (c_q @ attn["w_uq_n"]).reshape(b, s, view.n_heads,
                                         view.qk_nope_dim)
    q_r = apply_rope((c_q @ attn["w_uq_r"]).reshape(
        b, s, view.n_heads, view.qk_rope_dim), *rope, positions)
    down = h @ attn["w_dkv"]
    c = rms_norm(down[..., :view.kv_lora_rank], attn["kv_norm"],
                 view.norm_eps)
    k_r = apply_rope(down[..., None, view.kv_lora_rank:], *rope,
                     positions)[:, :, 0]
    pad = jnp.zeros((b, s, view.cache_row - view.cache_dim), c.dtype)
    return c_q, q_n, q_r, jnp.concatenate([c, k_r, pad], axis=-1)


def _indexer(cfg: DsaMlaMoeConfig, attn, h, c_q, rope, positions):
    """The indexer's queries (B, S, heads, D) and weights (B, S, heads)
    float32 of the tokens ``h`` (B, S, D) with their ``c_q``, and their
    keys (B, S, D) as the cache keeps them: normed, then rotated on the
    first ``qk_rope_dim`` dimensions."""
    b, s, _ = h.shape
    r = cfg.qk_rope_dim
    q = (c_q @ attn["w_iq"]).reshape(b, s, cfg.index_n_heads,
                                     cfg.index_head_dim)
    q = jnp.concatenate([apply_rope(q[..., :r], *rope, positions),
                         q[..., r:]], axis=-1)
    k = layer_norm(h @ attn["w_ik"], attn["ik_norm"], attn["ik_bias"],
                   cfg.index_norm_eps)
    k = jnp.concatenate(
        [apply_rope(k[:, :, None, :r], *rope, positions)[:, :, 0],
         k[..., r:]], axis=-1)
    w = (h @ attn["w_iw"]).astype(jnp.float32) * (
        (cfg.index_n_heads * cfg.index_head_dim) ** -0.5)
    return q, w, k


def _gate(attn, h, out, view: mla_moe.MlaMoeConfig):
    """The headwise gate on the heads' outputs (B, S, H * v) before
    W_O: each head times ``sigmoid(h W_G)`` of its own, in float32."""
    b, s, _ = out.shape
    gate = jax.nn.sigmoid((h @ attn["w_gate"]).astype(jnp.float32))
    heads = out.reshape(b, s, view.n_heads, view.v_head_dim)
    return (heads.astype(jnp.float32) * gate[..., None]).astype(
        out.dtype).reshape(b, s, -1)


def _positions(chosen, k: int):
    """A selection mask (B, T) -> its positions (B, k) in order, -1
    where it holds fewer."""
    top, spots = lax.top_k(chosen.astype(jnp.int32), k)
    return jnp.where(top > 0, spots, -1)


def _ordered(scores):
    """float32 -> uint32 whose order is the floats' order."""
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    negative = lax.shift_right_logical(bits, jnp.uint32(31)) == 1
    return jnp.where(negative, ~bits, bits | jnp.uint32(1 << 31))


def _top_mask(scores, ok, k: int):
    """The ``k`` ``ok`` entries of each row with the largest ``scores``,
    ties at the ``k``-th taken in position order (``lax.top_k``'s set);
    every ``ok`` entry of a row that has fewer. Exact: the ``k``-th
    largest is found bit by bit, 32 passes that count a row's keys at or
    above a candidate (no sort)."""
    key = jnp.where(ok, _ordered(scores), jnp.uint32(0))

    def bit(i, found):
        candidate = found | lax.shift_left(
            jnp.uint32(1), (31 - i).astype(jnp.uint32))
        enough = (key >= candidate[..., None]).sum(
            axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, candidate, found)

    found = lax.fori_loop(0, 32, bit, jnp.zeros(key.shape[:-1], jnp.uint32))
    above = ok & (key > found[..., None])
    ties = ok & (key == found[..., None])
    room = k - above.sum(axis=-1, dtype=jnp.int32)
    return above | (ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32)
                            <= room[..., None]))


def _selection(cfg: DsaMlaMoeConfig, q_i, w_i, k_i):
    """Each query row's selection at a prefill, (B, S, S) bool: per block
    of ``_QUERY_BLOCK`` rows the indexer's scores against every key the
    block can reach and their exact top-``index_topk`` mask. The queries
    go in four static steps, each against the keys up to its last row
    (a causal block's later keys are never scored)."""
    b, s = q_i.shape[:2]
    blk = min(_QUERY_BLOCK, s)
    steps = 4 if s % (4 * blk) == 0 else 1
    picked = []
    for step in range(steps):
        lo, hi = step * s // steps, (step + 1) * s // steps

        def one(i, lo=lo, hi=hi):
            start = lo + i * blk

            def rows_of(x):
                return lax.dynamic_slice_in_dim(x, start, blk, 1)

            t = start + jnp.arange(blk)
            causal = jnp.arange(hi)[None, :] <= t[:, None]
            index = jnp.einsum("bqjd,bsd->bqjs", rows_of(q_i), k_i[:, :hi],
                               preferred_element_type=jnp.float32)
            index = (jnp.maximum(index, 0.0)
                     * rows_of(w_i)[..., None]).sum(axis=2)
            return _top_mask(index, causal[None], cfg.index_topk)

        chosen = lax.map(one, jnp.arange((hi - lo) // blk))
        # (blocks, B, blk, hi) -> (B, hi - lo, S)
        chosen = jnp.moveaxis(chosen, 0, 1).reshape(b, hi - lo, hi)
        picked.append(jnp.pad(chosen, ((0, 0), (0, 0), (0, s - hi))))
    return jnp.concatenate(picked, axis=1)


def _selected_attention(cfg: DsaMlaMoeConfig, attn, q_n, q_r, rows,
                        chosen):
    """A full layer's prefill attention over each query row's selection
    ``chosen`` (B, S, S): the Pallas flash kernel given the selection as
    its mask where the bucket tiles (``MlaMoeConfig.flash_block``: keys
    padded to 256 lanes, values of 128), else the expanded keys and
    values attended under the mask in blocks of ``_QUERY_BLOCK`` query
    rows of plain XLA. Returns (B, S, H * v)."""
    from gofr_tpu.ops.pallas import flash_attention

    view = cfg.view("full")
    b, s, heads = q_n.shape[:3]
    rank = view.kv_lora_rank
    c = rows[..., :rank]
    k_r = rows[..., rank:view.cache_dim]
    k_n = jnp.einsum("bsc,hcd->bshd", c, attn["w_uk"])
    v = jnp.einsum("bsc,hcd->bshd", c, attn["w_uv"])
    block = view.flash_block(s)
    if block is not None:
        pad = jnp.zeros((b, s, heads, -view.qk_head_dim % _LANES),
                        rows.dtype)
        q = jnp.concatenate([q_n, q_r, pad], axis=-1)
        k = jnp.concatenate(
            [k_n, jnp.broadcast_to(k_r[:, :, None],
                                   (b, s, heads, view.qk_rope_dim)), pad],
            axis=-1)
        out = flash_attention(q, k, v, block_q=block, block_k=block,
                              sm_scale=view.softmax_scale, mask=chosen)
        return out.reshape(b, s, heads * view.v_head_dim)
    blk = min(_QUERY_BLOCK, s)

    def one(i):
        start = i * blk

        def rows_of(x):
            return lax.dynamic_slice_in_dim(x, start, blk, 1)

        scores = (jnp.einsum("bqhd,bshd->bhqs", rows_of(q_n), k_n,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhd,bsd->bhqs", rows_of(q_r), k_r,
                               preferred_element_type=jnp.float32))
        scores = jnp.where(rows_of(chosen)[:, None],
                           scores * view.softmax_scale, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqs,bshd->bqhd", probs, v)

    out = lax.map(one, jnp.arange(s // blk))
    # (blocks, B, blk, ...) -> (B, S, ...)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, heads * view.v_head_dim)


def _window_attention(attn, q_n, q_r, rows, view: mla_moe.MlaMoeConfig,
                      window: int):
    """A sliding layer's prefill attention, ``t - window < s <= t``, with
    keys and values expanded from the rows: the Pallas flash kernel
    given the window where Mosaic tiles the bucket (keys of
    ``qk_nope + qk_rope`` = 256 lanes, values of 128), else the same band
    in blocks of plain XLA (``ops.banded_attention``). Both scale by
    ``(qk_nope + qk_rope) ** -0.5``, the view's ``softmax_scale``."""
    from gofr_tpu.ops.pallas import flash_attention, flash_tileable

    b, s, heads = q_n.shape[:3]
    rank = view.kv_lora_rank
    c = rows[..., :rank]
    k_r = rows[..., None, rank:view.cache_dim]
    q = jnp.concatenate([q_n, q_r], axis=-1)
    k = jnp.concatenate(
        [jnp.einsum("bsc,hcd->bshd", c, attn["w_uk"]),
         jnp.broadcast_to(k_r, (b, s, heads, view.qk_rope_dim))], axis=-1)
    v = jnp.einsum("bsc,hcd->bshd", c, attn["w_uv"])
    block = 1024 if s % 1024 == 0 else 512
    if (s >= 1024 and view.v_head_dim % _LANES == 0
            and flash_tileable(s, view.qk_head_dim, block, block)):
        out = flash_attention(q, k, v, window=window, block_q=block,
                              block_k=block, sm_scale=view.softmax_scale)
    else:
        out = banded_attention(q, k, v, window)
    return out.reshape(b, s, heads * view.v_head_dim)


# -- the layers -----------------------------------------------------------------

def _ffn(cfg: DsaMlaMoeConfig, i: int, layer, h, valid, grouped):
    """Layer ``i``'s feed-forward on the normed h (B, S, D): the SwiGLU
    of a leading dense layer, or the expert layer. Returns (y,
    counters, experts chosen (B, S, top_k), or None where dense)."""
    b, s, d = h.shape
    if i < cfg.n_dense_layers:
        return _swiglu(layer, h), jnp.zeros((_N_MOE,), jnp.int32), None
    y, counters, ids = moe_ffn(
        cfg, layer, h.reshape(b * s, d),
        None if valid is None else valid.reshape(b * s), grouped)
    return y.reshape(b, s, d), counters, ids.reshape(b, s, cfg.top_k)


def _ropes(cfg: DsaMlaMoeConfig):
    """Each kind's rotary tables, kinds in model order (a program's text
    must not follow the process's string hashing)."""
    return {kind: cfg.view(kind).rope() for kind in dict.fromkeys(cfg.kinds)}


def _head(params, cfg: DsaMlaMoeConfig, x):
    x = rms_norm(x, params["out_norm"], cfg.norm_eps)
    return (x @ params["lm_head"]).astype(jnp.float32)


def prefill(params: Dict[str, Any], cfg: DsaMlaMoeConfig,
            tokens: jnp.ndarray, cache: Dict[str, Dict[str, jnp.ndarray]],
            lengths: Optional[jnp.ndarray] = None, routes: bool = False,
            selections: bool = False):
    """Run the prompts, fill the cache. tokens (B, S) right-padded to
    ``lengths``; returns (last-token logits (B, V), cache with rows [0,
    S) written in every kind, the index keys beside the full kind's
    rows, cache_len (B,)). ``routes`` adds the experts chosen (expert
    layers, B, S, top_k); ``selections`` the keys each full layer's
    query rows attended, (full layers, B, S, S) bool: a comparison with
    a reference's."""
    b, s = tokens.shape
    ropes = _ropes(cfg)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    valid = (None if lengths is None
             else positions < lengths.astype(jnp.int32)[:, None])
    x = params["tok_emb"][tokens]
    written: Dict[str, Dict[str, List]] = {
        kind: {name: [] for name in spec["leaves"]}
        for kind, spec in cache_leaves(cfg).items()}
    chosen, picked = [], []
    for i, kind in enumerate(cfg.kinds):
        layer = params["layers"][i]
        attn, view = layer["attn"], cfg.view(kind)
        h = rms_norm(x, layer["in_norm"], cfg.norm_eps)
        c_q, q_n, q_r, rows = _latent(attn, h, view, ropes[kind], positions)
        written[kind]["ckv"].append(rows)
        if kind == "full":
            q_i, w_i, k_i = _indexer(cfg, attn, h, c_q, ropes[kind],
                                     positions)
            written[kind]["ik"].append(k_i)
            if s > cfg.index_topk:
                sel = _selection(cfg, q_i, w_i, k_i)
                out = _selected_attention(cfg, attn, q_n, q_r, rows, sel)
                sel = selections and sel
            else:
                out = mla_moe.prefill_attention(attn, q_n, q_r, rows, view)
                sel = selections and jnp.broadcast_to(
                    jnp.tril(jnp.ones((s, s), bool)), (b, s, s))
            picked.append(sel)
        else:
            out = _window_attention(attn, q_n, q_r, rows, view,
                                    cfg.sliding_window)
        x = x + _gate(attn, h, out, view) @ attn["w_o"]
        h = rms_norm(x, layer["pre_mlp_norm"], cfg.norm_eps)
        y, _, ids = _ffn(cfg, i, layer, h, valid, True)
        x = x + y
        if ids is not None:
            chosen.append(ids)
    new_cache = {
        kind: {name: lax.dynamic_update_slice_in_dim(
            cache[kind][name],
            jnp.stack(rows).astype(cache[kind][name].dtype), 0, axis=2)
            for name, rows in leaves.items()}
        for kind, leaves in written.items()}
    if lengths is None:
        last = x[:, -1]
        cache_len = jnp.full((b,), s, jnp.int32)
    else:
        last = x[jnp.arange(b), lengths - 1]
        cache_len = lengths.astype(jnp.int32)
    out = (_head(params, cfg, last), new_cache, cache_len)
    if routes:
        out += (jnp.stack(chosen),)
    if selections:
        out += (jnp.stack(picked),)
    return out


def decode_step_paged(params: Dict[str, Any], cfg: DsaMlaMoeConfig,
                      token: jnp.ndarray,
                      pool: Dict[str, Dict[str, jnp.ndarray]],
                      page_table: Dict[str, jnp.ndarray],
                      cache_len: jnp.ndarray, active: jnp.ndarray,
                      counters: bool = False, routes: bool = False,
                      selections: bool = False):
    """One decode step over the page pool, a kind: ``pool[kind][leaf]``
    (layers of the kind, that kind's num_pages, page, width),
    ``page_table[kind]`` (B, P) with that kind's ``num_pages`` as the
    unallocated sentinel; a window kind's columns behind its window may
    hold it (the pages went back to the pool) and are never read.
    ``active`` (B,) bool gates every write (an inactive slot's page may
    belong to another stream by now: its row goes to the sentinel page
    and is dropped) and the indexer's walk (an inactive slot scores no
    key). Each layer appends the token's row (and a full layer its index
    key) first and reads it back with the rest: position ``cache_len``
    is live. The keys are scored through the Pallas kernel where
    ``select.index_tileable`` says Mosaic tiles the shape, else gathered;
    the selection is attended where its rows lie, in the sparse latent
    kernel given the exact top-k as a mask, where
    ``select.sparse_latent_tileable`` says Mosaic tiles the shape, else
    its rows are gathered through ``lax.top_k``'s positions: a CPU run
    and a chip run of one configuration take one path.
    Returns (logits, pool, cache_len + 1),
    and with ``counters`` the step's ``STEP_COUNTERS`` as a fourth, with
    ``routes`` the experts chosen (expert layers, B, top_k), with
    ``selections`` the positions each full layer attended (full layers,
    B, k) int32, -1 where fewer were live."""
    from gofr_tpu.ops.pallas import (index_scores, index_tileable,
                                     sparse_latent_attention,
                                     sparse_latent_tileable)

    ropes = _ropes(cfg)
    batch = token.shape[0]
    live = cache_len + 1
    positions = cache_len[:, None]
    dest = {}
    for kind, leaves in pool.items():
        num_pages, page = leaves["ckv"].shape[1:3]
        page_row = jnp.take_along_axis(
            page_table[kind], (cache_len // page)[:, None], axis=1,
            mode="clip")[:, 0]
        dest[kind] = (jnp.where(active, page_row, num_pages),   # drop
                      cache_len % page)
    index_kernel = index_tileable(cfg.index_head_dim, cfg.index_n_heads,
                                  pool["full"]["ik"].shape[2])
    full = cfg.view("full")
    latent_kernel = sparse_latent_tileable(full.cache_row, full.n_heads,
                                           pool["full"]["ckv"].shape[2])
    mine = jnp.where(active, live, 0)
    x = params["tok_emb"][token][:, None, :]                 # (B, 1, D)
    moe = jnp.zeros((_N_MOE,), jnp.int32)
    dsa = jnp.zeros((_N_COUNTERS - _N_MOE,), jnp.int32)
    chosen, picked = [], []
    for i, kind in enumerate(cfg.kinds):
        layer, at = params["layers"][i], cfg.ordinal(i)
        attn, view = layer["attn"], cfg.view(kind)
        table, leaves = page_table[kind], dict(pool[kind])
        page = leaves["ckv"].shape[2]
        row_at, offset = dest[kind]
        h = rms_norm(x, layer["in_norm"], cfg.norm_eps)
        c_q, q_n, q_r, row = _latent(attn, h, view, ropes[kind], positions)
        leaves["ckv"] = leaves["ckv"].at[at, row_at, offset].set(
            row[:, 0], mode="drop")
        if kind == "full":
            q_i, w_i, k_i = _indexer(cfg, attn, h, c_q, ropes[kind],
                                     positions)
            leaves["ik"] = leaves["ik"].at[at, row_at, offset].set(
                k_i[:, 0], mode="drop")
            if index_kernel:
                scores = index_scores(q_i[:, 0], w_i[:, 0], leaves["ik"],
                                      table, mine, at)
            else:
                scores = gathered_index_scores(
                    q_i[:, 0], w_i[:, 0],
                    lax.dynamic_index_in_dim(leaves["ik"], at, 0,
                                             keepdims=False),
                    table, mine)
            k = min(cfg.index_topk, scores.shape[1])
            if latent_kernel:
                # the selection as a mask, attended where the rows lie
                mask = _top_mask(scores, scores > -jnp.inf, k)
                u = sparse_latent_attention(
                    mla_moe.absorbed_query(attn, q_n[:, 0], q_r[:, 0], view),
                    mask, leaves["ckv"], table, mine, at,
                    rank=view.kv_lora_rank, scale=view.softmax_scale)
                out = jnp.einsum("bhc,hcd->bhd",
                                 u.astype(leaves["ckv"].dtype),
                                 attn["w_uv"]).reshape(batch, 1, -1)
                if selections:
                    picked.append(_positions(mask, k))
            else:
                top, spots = lax.top_k(scores, k)
                ok = top > -jnp.inf
                pages = jnp.take_along_axis(table, spots // page, axis=1)
                view_rows = leaves["ckv"][at, pages, spots % page]
                out = mla_moe.absorbed_attention(
                    attn, q_n[:, 0], q_r[:, 0], view_rows, ok, view)[:, None]
                if selections:
                    picked.append(jnp.where(ok, spots, -1))
            selected = jnp.minimum(mine, k)
            dsa = dsa + jnp.stack([mine.sum(), selected.sum(), jnp.int32(1),
                                   jnp.int32(0), jnp.int32(latent_kernel)]
                                  ).astype(jnp.int32)
        else:
            window = cfg.sliding_window
            start = jnp.maximum(live - window, 0)
            columns = (start // page)[:, None] + jnp.arange(
                window // page + 2)
            ids = jnp.take_along_axis(table, columns, axis=1, mode="clip")
            # one gather straight out of the stacked leaf: the layer's
            # plane sliced out first is a copy of the kind's whole pool a
            # layer a step (0.26 ms at the cell's 1152 pages)
            view_rows = leaves["ckv"][at, ids].reshape(
                batch, -1, leaves["ckv"].shape[-1])
            spots = (columns[:, :, None] * page
                     + jnp.arange(page)).reshape(batch, -1)
            ok = (spots >= start[:, None]) & (spots < live[:, None])
            rows = jnp.where(active, jnp.minimum(live, window), 0)
            dsa = dsa + jnp.stack([jnp.int32(0), jnp.int32(0), jnp.int32(0),
                                   rows.sum(), jnp.int32(0)]).astype(jnp.int32)
            out = mla_moe.absorbed_attention(attn, q_n[:, 0], q_r[:, 0],
                                             view_rows, ok, view)[:, None]
        pool = dict(pool, **{kind: leaves})
        x = x + _gate(attn, h, out, view) @ attn["w_o"]
        h = rms_norm(x, layer["pre_mlp_norm"], cfg.norm_eps)
        y, counted, ids = _ffn(cfg, i, layer, h, active[:, None], False)
        x = x + y
        moe = moe + counted
        if ids is not None:
            chosen.append(ids[:, 0])
    out = (_head(params, cfg, x[:, 0]), pool, cache_len + 1)
    if counters:
        out += (jnp.concatenate([moe, dsa]),)
    if routes:
        out += (jnp.stack(chosen),)
    if selections:
        out += (jnp.stack(picked),)
    return out


def decode_step(params: Dict[str, Any], cfg: DsaMlaMoeConfig,
                token: jnp.ndarray, cache: Dict[str, Dict[str, jnp.ndarray]],
                cache_len: jnp.ndarray):
    """One decode step over the dense cache of ``init_cache``: a slot's
    rows are one page of the cache's length, its table the one column
    holding its own index, so the step is ``decode_step_paged``'s
    (a page that long tiles no kernel block: the index scores are
    gathered)."""
    batch = token.shape[0]
    table = jnp.arange(batch, dtype=jnp.int32)[:, None]
    return decode_step_paged(params, cfg, token, cache,
                             {kind: table for kind in cache}, cache_len,
                             jnp.ones((batch,), bool))
