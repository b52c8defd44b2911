"""Decoder with sliding-window and global attention layers interleaved,
grouped-query attention, and a sparse expert layer, for /generate.

The family of Cohere's Command A+ (``cohere2_moe``): most layers attend
a window of the last ``sliding_window`` tokens and carry rotary
positions, every few layers one attends the whole context and carries
no position at all; a layer is a *parallel block* (one norm, attention
and feed-forward both read it); the feed-forward is a routed expert
layer beside shared experts. What differs between the members the repo
has met is a field of ``SwaMoeConfig`` (the layer pattern, the window,
which kinds are rotated, the parallel block, the shared experts' count,
the chip's share of the experts); what no member has varied yet is
written into the code once (LayerNorm without bias, rotary pairing
neighbouring dimensions, a tied head, the shared experts averaged,
sigmoid scores), to become a field when a member needs another.

Equations (``h`` the normed input of a block, ``LN(x; g) = (x - mean(x))
/ sqrt(var(x) + eps) * g`` with no bias, in float32):

- Layer, with ``parallel_block``: ``x = x + Attn_kind(h) W_O + FFN(h)``,
  ``h = LN(x; g)``: one norm a layer. Without it: ``x = x +
  Attn(LN(x; g)) W_O``, then ``x = x + FFN(LN(x; g_ffn))``. After the
  last layer ``LN(x; g_out)``; ``logits = logit_scale * x E^T`` with
  ``E`` the embedding (the head is tied).
- Attention: ``q = h W_q`` as ``n_heads`` heads of ``head_dim``, ``k = h
  W_k``, ``v = h W_v`` as ``n_kv_heads`` (``n_heads / n_kv_heads`` query
  heads share a KV head); no bias, no q/k norm; scores over
  ``sqrt(head_dim)``. Kind ``window`` (``sliding_attention`` in
  ``layer_types``): token ``t`` attends ``s`` with ``t - sliding_window
  < s <= t``. Kind ``full`` (``full_attention``): every ``s <= t``.
  Rotary on ``q`` and ``k`` of the kinds in ``rope_kinds`` only, over
  the whole head, neighbouring dimensions paired (``rope_gptj``); the
  others carry no position.
- Expert layer: ``models/experts.py`` (shared with ``models/mla_moe.py``):
  ``s = sigmoid(h W_g)`` over all ``n_routed_experts`` in float32, the
  ``top_k`` largest, weights normalised over them; this chip's held
  experts' part, plus ``shared_scale`` times the sum of the
  ``n_shared_experts`` shared SwiGLUs (their mean: "average"), held as
  one SwiGLU of ``n_shared_experts * moe_ffn_dim``.

**The cache has a kind a kind of layer** (``cache_leaves``): the
``window`` layers and the ``full`` layers each keep ``k`` and ``v``, ``(layers
of the kind, ..., n_kv_heads, head_dim)``, and only the kind says how
far back its layers read. On the page pool each kind has its own pages
and a slot a page table a kind (``decode_step_paged`` takes ``pool`` and
``page_table`` as ``{kind: ...}``): a window kind's table holds the
sentinel in the columns its slot has decoded past, the engine having
let those pages go. Prefill attends in blocks that follow the band: the
Pallas flash kernel given the window where Mosaic can tile the shape
(``ops.pallas.flash_attention``), else the same blocks in plain XLA
(``ops.banded_attention``, the oracle); a decode step reads the pool in place
through the ragged kernel with the window's lower bound (``ragged=``),
or through the gather formulation with the same bound, the oracle.

The layers are scanned a *period* of ``layer_types`` at a time: the
parameters of the period's ``i``-th layer are stacked over periods
(``params["layers"][i]``), a kind's cache over its layers in model
order. Serving contract (``docs/tpu/model-serving.md``): ``init``,
``init_cache``, ``prefill``, ``decode_step``, ``decode_step_paged``,
``cache_leaves`` and ``STEP_COUNTERS``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.models import experts
from gofr_tpu.models.experts import (  # noqa: F401  (the module's names)
    experts_batched, experts_grouped, experts_hit, moe_ffn, route)
from gofr_tpu.ops import (apply_rope, banded_attention,
                          decode_attention_cached, gather_kv_pages,
                          layer_norm, rope_table)

# layer_types' names for the two kinds, and the kinds' own
KIND_OF = {"sliding_attention": "window", "full_attention": "full",
           "window": "window", "full": "full"}

# what a decode step counts: the expert layer's five, then the cached
# rows its attention has to read, by kind (rows of one layer, summed
# over the kind's layers and the active slots), and the attention calls
# (one a layer). Summed by the engine over a tick's steps
STEP_COUNTERS = experts.STEP_COUNTERS + (
    "attn.window_rows", "attn.full_rows", "attn.calls")
_N_MOE = len(experts.STEP_COUNTERS)
_N_COUNTERS = len(STEP_COUNTERS)


@dataclasses.dataclass(frozen=True)
class SwaMoeConfig:
    vocab_size: int = 262144
    dim: int = 4096
    n_layers: int = 32
    # a kind a layer, in model order (a period repeated)
    layer_types: Tuple[str, ...] = (
        "sliding_attention", "sliding_attention", "sliding_attention",
        "full_attention") * 8
    sliding_window: int = 4096
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    rope_kinds: Tuple[str, ...] = ("window",)   # the kinds that rotate
    rope_theta: float = 50000.0
    moe_ffn_dim: int = 4096               # one expert's SwiGLU
    n_routed_experts: int = 128           # the router's width
    n_held_experts: int = 128             # how many of them live here
    expert_rank: int = 0                  # which share: rank * held ..
    top_k: int = 8
    n_shared_experts: int = 4
    norm_topk_prob: bool = True
    parallel_block: bool = True
    logit_scale: float = 1.0
    max_seq_len: int = 200000
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    # what models/experts.py reads of a family's config and this family
    # has not varied: not fields
    scoring = "sigmoid"
    routed_scale = 1.0
    topk_norm_eps = 0.0
    def flash_block(self, seq_len: int) -> Optional[int]:
        """The block size the prefill's flash kernel runs a
        ``seq_len``-token bucket in, or None where Mosaic cannot tile
        the shape (``ops.banded_attention`` then; what the engine's
        ``attention_paths()`` reports a bucket by). 1024-row blocks
        where the prompt splits into them: a (head, block pair) step
        has a fixed cost, and at 128 heads the steps are many (PERF.md,
        PR 31: 12.5 ms a layer of 6144 tokens against 20.4 at 512)."""
        from gofr_tpu.ops.pallas import flash_tileable
        block = 1024 if seq_len % 1024 == 0 else 512
        if flash_tileable(seq_len, self.head_dim, block, block):
            return block
        return None

    def __post_init__(self):
        if len(self.layer_types) != self.n_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"n_layers is {self.n_layers}")
        unknown = sorted(set(self.layer_types) - set(KIND_OF))
        if unknown:
            raise ValueError(f"layer_types: unknown kinds {unknown}; "
                             f"have {sorted(KIND_OF)}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if (self.expert_rank + 1) * self.n_held_experts \
                > self.n_routed_experts:
            raise ValueError(
                f"share {self.expert_rank} of {self.n_held_experts} experts "
                f"lies outside the router's {self.n_routed_experts}")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The kind of each layer, in model order."""
        return tuple(KIND_OF[name] for name in self.layer_types)

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest pattern of kinds whose repetition is ``kinds``."""
        kinds = self.kinds
        for size in range(1, len(kinds) + 1):
            if len(kinds) % size == 0 \
                    and kinds == kinds[:size] * (len(kinds) // size):
                return kinds[:size]
        return kinds

    @property
    def shared_scale(self) -> float:
        """What the shared experts' sum is multiplied by: they are
        averaged."""
        return 1.0 / max(self.n_shared_experts, 1)

    def window_of(self, kind: str) -> Optional[int]:
        return self.sliding_window if kind == "window" else None

    def expert_block_rows(self, tokens: int) -> int:
        """Rows a block of the prefill's grouped expert product holds
        (``experts.experts_grouped``): a power of two with a quarter of
        room over the pairs an expert expects, in 8..1024, so that
        nearly every held expert is ONE block. A block reads its
        expert's whole SwiGLU (100 MB at the published widths, 0.12 ms
        of HBM) whatever its rows, and up to ~256 rows that read is the
        block's time: at the expected pairs exactly, half the experts
        run a second, nearly empty block (PERF.md, PR 31: 5 of the 10
        ms a layer of a 1024-token prefill)."""
        expect = max(1, tokens * self.top_k // self.n_routed_experts)
        want = expect + (expect + 3) // 4
        return min(1024, max(8, 1 << (want - 1).bit_length()))


PRESETS: Dict[str, SwaMoeConfig] = {
    # tiny: unit tests and the benchmark's CPU rehearsal; two periods
    "tiny": SwaMoeConfig(
        vocab_size=256, dim=64, n_layers=8,
        layer_types=("sliding_attention",) * 3 + ("full_attention",)
        + ("sliding_attention",) * 3 + ("full_attention",),
        sliding_window=16, n_heads=8, n_kv_heads=2, head_dim=16,
        moe_ffn_dim=32, n_routed_experts=16, n_held_experts=16, top_k=4,
        n_shared_experts=2, max_seq_len=128),
    "command-a-plus": SwaMoeConfig(),
}


def config(preset: str = "tiny", **overrides) -> SwaMoeConfig:
    return dataclasses.replace(PRESETS[preset], **overrides)


def cache_leaves(cfg: SwaMoeConfig) -> Dict[str, Dict[str, Any]]:
    """What one token leaves in the cache, **by layer kind**: kind ->
    ``{"layers": how many layers keep it, "window": how far back they
    read (None: everything), "leaves": name -> (trailing shape,
    dtype)}``, kinds in the order they first appear. The page pool
    keeps pages, a free list and a slot's table a kind, and the engine
    lets a window kind's pages go as a slot decodes past them."""
    tail = (cfg.n_kv_heads, cfg.head_dim)
    out: Dict[str, Dict[str, Any]] = {}
    for kind in cfg.kinds:
        if kind not in out:
            out[kind] = {"layers": cfg.kinds.count(kind),
                         "window": cfg.window_of(kind),
                         "leaves": {"k": (tail, cfg.dtype),
                                    "v": (tail, cfg.dtype)}}
    return out


# -- parameters ---------------------------------------------------------------

def init(cfg: SwaMoeConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random parameters in the served dtype: matmul weights
    ~N(0, 1/fan_in) (fan-in is every weight's second-last axis), gains of
    one. Jitted with the key as an argument it is one program for every
    seed, each draw fused into its output leaf. ``layers`` is a tuple
    over the period's positions, each leaf stacked over the periods."""
    dt, d = cfg.dtype, cfg.dim
    periods = cfg.n_layers // len(cfg.period)
    count = iter(range(1 << 16))

    def dense(*shape):
        leaf = jax.random.normal(jax.random.fold_in(key, next(count)),
                                 shape, jnp.float32)
        return (leaf / math.sqrt(shape[-2])).astype(dt)

    def swiglu(*lead, width):
        return {"w_gate": dense(*lead, d, width),
                "w_up": dense(*lead, d, width),
                "w_down": dense(*lead, width, d)}

    def layer(n):
        out = {"norm": jnp.ones((n, d), dt),
               "wq": dense(n, d, cfg.n_heads * cfg.head_dim),
               "wk": dense(n, d, cfg.n_kv_heads * cfg.head_dim),
               "wv": dense(n, d, cfg.n_kv_heads * cfg.head_dim),
               "wo": dense(n, cfg.n_heads * cfg.head_dim, d),
               "router": dense(n, d, cfg.n_routed_experts),
               "experts": swiglu(n, cfg.n_held_experts,
                                 width=cfg.moe_ffn_dim)}
        if not cfg.parallel_block:
            out["ffn_norm"] = jnp.ones((n, d), dt)
        if cfg.n_shared_experts:
            out["shared"] = swiglu(
                n, width=cfg.n_shared_experts * cfg.moe_ffn_dim)
        return out

    params: Dict[str, Any] = {
        "tok_emb": (jax.random.normal(jax.random.fold_in(key, next(count)),
                                      (cfg.vocab_size, d), jnp.float32)
                    / math.sqrt(d)).astype(dt),
        "out_norm": jnp.ones((d,), dt),
        "layers": tuple(layer(periods) for _ in cfg.period)}
    return params


def init_cache(cfg: SwaMoeConfig, batch: int, max_len: Optional[int] = None
               ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Static-shape dense cache, a kind: ``{kind: {"k", "v"}}``, each
    (layers of the kind, B, T, n_kv_heads, head_dim). A window kind's is
    as long as the others' here (positions index it); the page pool is
    where a window costs a window."""
    t_max = max_len or cfg.max_seq_len
    return {kind: {name: jnp.zeros((spec["layers"], batch, t_max, *tail),
                                   dtype)
                   for name, (tail, dtype) in spec["leaves"].items()}
            for kind, spec in cache_leaves(cfg).items()}


# -- the layer ----------------------------------------------------------------

def _norm(cfg: SwaMoeConfig, x, gain):
    return layer_norm(x, gain, None, cfg.norm_eps)


def _qkv(cfg: SwaMoeConfig, kind: str, layer, h, rope, positions):
    """h (B, S, D) -> q (B,S,Hq,Dh), k, v (B,S,Hkv,Dh); rotated where
    the kind carries positions."""
    b, s, _ = h.shape
    q = (h @ layer["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = (h @ layer["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ layer["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    if kind in cfg.rope_kinds:
        q = apply_rope(q, *rope, positions, interleaved=True)
        k = apply_rope(k, *rope, positions, interleaved=True)
    return q, k, v


def _prefill_attend(cfg: SwaMoeConfig, seq_len: int):
    """The banded self-attention a ``seq_len``-token prompt forward
    runs, ``attend(q, k, v, window)``: the Pallas flash kernel where
    Mosaic can tile the shape, else the same band in blocks of plain
    XLA (128 heads over 8192 positions are 34 GB of scores if
    materialised, so nothing here is dense). Decided by shape alone,
    so a CPU run and a chip run of one configuration take one path."""
    from gofr_tpu.ops.pallas import flash_attention
    block = cfg.flash_block(seq_len)
    if block is not None:
        return lambda q, k, v, window: flash_attention(
            q, k, v, window=window, block_q=block, block_k=block)
    return banded_attention


def _layer(cfg: SwaMoeConfig, kind: str, layer, x, attend, valid, grouped):
    """One layer on x (B, S, D). ``attend(q, k, v)`` gives the attention
    output (B, S, Hq, Dh) and whatever it carries. Returns (x, carried,
    the expert layer's counters, experts chosen (B, S, top_k))."""
    b, s, d = x.shape
    h = _norm(cfg, x, layer["norm"])
    attn, carried = attend(h)
    attn = attn.reshape(b, s, -1) @ layer["wo"]
    if not cfg.parallel_block:
        x = x + attn
        h = _norm(cfg, x, layer["ffn_norm"])
    y, counters, ids = moe_ffn(
        cfg, layer, h.reshape(b * s, d),
        None if valid is None else valid.reshape(b * s), grouped)
    y = y.reshape(b, s, d)
    x = x + y if not cfg.parallel_block else x + attn + y
    return x, carried, counters, ids.reshape(b, s, cfg.top_k)


def _head(params, cfg: SwaMoeConfig, x):
    x = _norm(cfg, x, params["out_norm"])
    logits = jnp.einsum("...d,vd->...v", x,
                        params["tok_emb"]).astype(jnp.float32)
    return logits if cfg.logit_scale == 1.0 else logits * cfg.logit_scale


def _ordinals(cfg: SwaMoeConfig) -> List[Tuple[str, int, int]]:
    """For each position of the period: (kind, how many layers of that
    kind a period holds, this one's place among them)."""
    period = cfg.period
    return [(kind, period.count(kind), period[:i].count(kind))
            for i, kind in enumerate(period)]


def prefill(params: Dict[str, Any], cfg: SwaMoeConfig, tokens: jnp.ndarray,
            cache: Dict[str, Dict[str, jnp.ndarray]],
            lengths: Optional[jnp.ndarray] = None, routes: bool = False):
    """Run the prompts, fill the cache. tokens (B, S) right-padded to
    ``lengths``; returns (last-token logits (B, V), cache with rows
    [0, S) written in every kind, cache_len (B,)). Attention goes in
    blocks along the band (``_prefill_attend``); the expert layer
    is grouped, and padding rows are routed nowhere. ``routes`` adds
    the experts chosen, (layers, B, S, top_k) in model order."""
    b, s = tokens.shape
    rope = rope_table(max(cfg.max_seq_len, s), cfg.head_dim, cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    valid = (None if lengths is None
             else positions < lengths.astype(jnp.int32)[:, None])
    x = params["tok_emb"][tokens]
    banded = _prefill_attend(cfg, s)

    def body(x, layers):
        written, chosen = [], []
        for kind, layer in zip(cfg.period, layers):
            def attend(h, kind=kind, layer=layer):
                q, k, v = _qkv(cfg, kind, layer, h, rope, positions)
                return banded(q, k, v, cfg.window_of(kind)), (k, v)

            x, kv, _, ids = _layer(cfg, kind, layer, x, attend, valid, True)
            written.append(kv)
            chosen.append(ids)
        return x, (tuple(written), jnp.stack(chosen))

    x, (written, chosen) = lax.scan(body, x, params["layers"])
    new_cache = {}
    for kind, leaves in cache.items():
        # (periods, in-period layers of the kind, B, S, ...) -> model order
        mine = [kv for kv, k in zip(written, cfg.period) if k == kind]
        new_cache[kind] = {}
        for at, name in enumerate(("k", "v")):
            rows = jnp.stack([kv[at] for kv in mine], axis=1)
            rows = rows.reshape(-1, *rows.shape[2:])
            new_cache[kind][name] = lax.dynamic_update_slice_in_dim(
                leaves[name], rows.astype(leaves[name].dtype), 0, axis=2)
    if lengths is None:
        last = x[:, -1]
        cache_len = jnp.full((b,), s, jnp.int32)
    else:
        last = x[jnp.arange(b), lengths - 1]
        cache_len = lengths.astype(jnp.int32)
    out = (_head(params, cfg, last), new_cache, cache_len)
    if routes:
        # (periods, period, B, S, K) -> (layers, B, S, K)
        out += (chosen.reshape(-1, *chosen.shape[2:]),)
    return out


def _decode(params, cfg: SwaMoeConfig, token, cache, cache_len, active,
            attend_kind, append):
    """One decode step. ``attend_kind(kind, leaves, idx, q, k_new, v_new,
    start)`` attends the kind's cached rows of layer ``idx`` (among the
    kind's) plus the new token's own; ``append(kind, leaves, idx, k, v)``
    writes the new row for every sequence. The cache is read as it was
    before this layer's append: the new row arrives beside it."""
    rope = rope_table(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta)
    positions = cache_len[:, None]
    x = params["tok_emb"][token][:, None, :]                # (B, 1, D)
    live = (jnp.ones_like(cache_len, bool) if active is None else active)
    starts, rows = {}, {}
    for kind in cache:
        window = cfg.window_of(kind)
        starts[kind] = (None if window is None
                        else jnp.maximum(cache_len - window + 1, 0))
        read = cache_len if window is None else cache_len - starts[kind]
        rows[kind] = jnp.where(live, read, 0).sum().astype(jnp.int32)
    ordinals = _ordinals(cfg)
    # the routed experts stay out of the scan: the expert layer's loop
    # reads [period, expert] from the whole stack (experts_hit's at),
    # where a scan's slice of it would be a copy of a layer's experts
    scanned = tuple({name: leaf for name, leaf in layer.items()
                     if name != "experts"} for layer in params["layers"])

    def body(carry, layers_and_period):
        x, cache, counters = carry
        layers, p = layers_and_period
        for (kind, per_period, place), layer, whole in zip(
                ordinals, layers, params["layers"]):
            idx = p * per_period + place
            layer = dict(layer, experts=whole["experts"], experts_at=p)

            def attend(h, kind=kind, layer=layer, idx=idx):
                q, k, v = _qkv(cfg, kind, layer, h, rope, positions)
                out = attend_kind(kind, cache[kind], idx, q, k[:, 0],
                                  v[:, 0], starts[kind])
                return out, (k[:, 0], v[:, 0])

            x, (k, v), counted, _ = _layer(
                cfg, kind, layer, x, attend,
                None if active is None else active[:, None], False)
            cache = dict(cache, **{kind: append(kind, cache[kind], idx,
                                                k, v)})
            attn = jnp.stack([rows[kind] * (kind == "window"),
                              rows[kind] * (kind == "full"),
                              jnp.int32(1)])
            counters = counters + jnp.concatenate([counted, attn])
        return (x, cache, counters), None

    periods = cfg.n_layers // len(cfg.period)
    (x, cache, counters), _ = lax.scan(
        body, (x, cache, jnp.zeros((_N_COUNTERS,), jnp.int32)),
        (scanned, jnp.arange(periods, dtype=jnp.int32)))
    return _head(params, cfg, x[:, 0]), cache, counters


def decode_step(params: Dict[str, Any], cfg: SwaMoeConfig,
                token: jnp.ndarray, cache: Dict[str, Dict[str, jnp.ndarray]],
                cache_len: jnp.ndarray):
    """One decode step over the dense cache of ``init_cache``: a kind's
    whole cache row, a window kind's own bound a mask beside it."""
    batch = jnp.arange(token.shape[0])

    def attend_kind(kind, leaves, idx, q, k_new, v_new, start):
        views = [lax.dynamic_index_in_dim(leaves[name], idx, 0,
                                          keepdims=False) for name in "kv"]
        return decode_attention_cached(q, *views, k_new, v_new, cache_len,
                                       start=start)

    def append(kind, leaves, idx, k, v):
        return {"k": leaves["k"].at[idx, batch, cache_len].set(
                    k, mode="drop"),
                "v": leaves["v"].at[idx, batch, cache_len].set(
                    v, mode="drop")}

    logits, cache, _ = _decode(params, cfg, token, cache, cache_len, None,
                               attend_kind, append)
    return logits, cache, cache_len + 1


def decode_step_paged(params: Dict[str, Any], cfg: SwaMoeConfig,
                      token: jnp.ndarray,
                      pool: Dict[str, Dict[str, jnp.ndarray]],
                      page_table: Dict[str, jnp.ndarray],
                      cache_len: jnp.ndarray, active: jnp.ndarray,
                      ragged: bool = False, counters: bool = False):
    """One decode step over the page pool, a kind: ``pool[kind]["k"]``
    (layers of the kind, that kind's num_pages, page, Hkv, Dh),
    ``page_table[kind]`` (B, P) with that kind's ``num_pages`` as the
    unallocated sentinel. Column ``c`` of either table is positions
    ``c * page ..``; a window kind's columns behind ``cache_len -
    sliding_window + 1`` may hold the sentinel (the pages went back to
    the pool) and are never read: ``start`` masks them in the gather
    formulation and is where the ragged kernel's walk begins.
    ``active`` (B,) bool gates the append (an inactive slot's page may
    belong to another stream by now: its row goes to the sentinel page
    and is dropped). ``ragged`` (static) reads the pool in place
    through the Pallas ragged kernel, the stacked leaves whole and the
    layer picked in its page copies; otherwise a layer's table pages
    are gathered, the oracle. Returns (logits, pool, cache_len + 1),
    and with ``counters`` the step's ``STEP_COUNTERS`` over the active
    rows as a fourth."""
    dest = {}
    for kind, leaves in pool.items():
        num_pages, page = leaves["k"].shape[1:3]
        page_row = jnp.take_along_axis(
            page_table[kind], (cache_len // page)[:, None], axis=1,
            mode="clip")[:, 0]
        dest[kind] = (jnp.where(active, page_row, num_pages),   # drop
                      cache_len % page)

    def attend_kind(kind, leaves, idx, q, k_new, v_new, start):
        if ragged:
            from gofr_tpu.ops.pallas import ragged_paged_decode_attention
            return ragged_paged_decode_attention(
                q, leaves["k"], leaves["v"], page_table[kind], k_new,
                v_new, cache_len, idx, start=start)
        # XLA fuses the layer slice into the gather: no plane of the
        # pool is materialised. Sentinel ids clamp to a real page,
        # masked by cache_len and start
        views = [gather_kv_pages(
            lax.dynamic_index_in_dim(leaves[name], idx, 0, keepdims=False),
            page_table[kind]) for name in "kv"]
        return decode_attention_cached(q, *views, k_new, v_new, cache_len,
                                       start=start)

    def append(kind, leaves, idx, k, v):
        row, offset = dest[kind]
        return {"k": leaves["k"].at[idx, row, offset].set(k, mode="drop"),
                "v": leaves["v"].at[idx, row, offset].set(v, mode="drop")}

    logits, pool, counted = _decode(params, cfg, token, pool, cache_len,
                                    active, attend_kind, append)
    out = (logits, pool, cache_len + 1)
    return out + (counted,) if counters else out
