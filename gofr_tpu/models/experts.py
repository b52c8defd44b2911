"""The sparse expert layer, shared by the model families that route
(``models/mla_moe.py``, ``models/swa_moe.py``, ``models/hc_mla_moe.py``):
router, this chip's share of the routed experts in a form for a prefill
and one for a decode step, the shared experts, and the step counters.
A family's config object is read for ``scoring`` (``sigmoid`` or
``softmax``), ``top_k``, ``norm_topk_prob``, ``routed_scale``,
``n_routed_experts`` (the router's width), ``n_held_experts`` and
``expert_rank`` (which share lives on this chip), and ``shared_scale``
(what the shared experts' sum is multiplied by: 1, or ``1 /
n_shared_experts`` where a family averages them).

``s = score(W_g h)`` over all ``n_routed_experts``, in float32; ``T`` =
the ``top_k`` largest (of ``s + b`` where a layer carries a correction
bias ``router_bias``, DeepSeek-V3's ``noaux_tc``); ``w_e = routed_scale * s_e / sum_{j in T} s_j``;
``y = shared_scale * Shared(h) + sum_{e in T and held} w_e Expert_e(h)``.
The shared experts are held as one SwiGLU of ``n_shared_experts`` times
the expert width: a sum of SwiGLUs over disjoint columns is one SwiGLU
over all of them. This chip holds experts ``expert_rank *
n_held_experts ..`` (expert parallelism: one chip's share of a layer).
What absent experts would add is left out; nothing here stands in for
the other chips or for their exchange. No capacity, no dropped token.
A prefill sorts the held (token, expert) pairs by expert and runs
blocks of one expert's rows, so its work follows the pairs
(``experts_grouped``: given the whole stack and a layer's index it
reads each expert where it lies; how a token's pairs are summed
follows from the share of the experts held); a decode
step has one token a slot and is bound by the experts' bytes and not
by rows, so its work follows the experts *hit* (``experts_hit``): a
loop with one trip a held expert that some row chose, which reads that
expert where it lies and runs it over all rows (rows that did not
choose it weigh zero); an expert no row chose is not read.
``experts_batched``, every held expert over every row in one batched
product, is the plain formulation the tests hold that loop to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# what a decode step's expert layers count, summed by the engine over a
# tick's steps and its active rows (stats()["moe"][...],
# app_tpu_step_counter_total)
STEP_COUNTERS = ("moe.routed_pairs", "moe.held_pairs", "moe.experts_hit",
                 "moe.hot_expert_pairs", "moe.layer_steps")


def _swiglu(w, x, out_dtype=None):
    """``out_dtype``: what the down product leaves (its accumulator's
    float32 unrounded, where asked for); x's dtype without it."""
    gate = jax.nn.silu((x @ w["w_gate"]).astype(jnp.float32))
    up = (x @ w["w_up"]).astype(jnp.float32)
    return jnp.matmul((gate * up).astype(x.dtype), w["w_down"],
                      preferred_element_type=out_dtype)


def route(cfg, router, h, bias=None):
    """h (T, D) -> (ids (T, top_k) int32 over all routed experts, weights
    (T, top_k) float32). Scores in float32 whatever h's dtype. ``bias``
    (E,) float32 is a member's per-expert correction (``topk_method:
    noaux_tc``): the experts are chosen by ``s + bias`` and weighed by
    ``s`` alone; without it the program is the one it was."""
    logits = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = (jax.nn.sigmoid(logits) if cfg.scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    if bias is None:
        top, ids = lax.top_k(scores, cfg.top_k)
    else:
        _, ids = lax.top_k(scores + bias.astype(jnp.float32), cfg.top_k)
        top = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg.norm_topk_prob:
        top = top / top.sum(axis=-1, keepdims=True)
    return ids.astype(jnp.int32), top * cfg.routed_scale


def _held(cfg, ids, valid):
    """Global expert ids (T, K) -> (index among the held experts, is the
    pair this chip's). ``valid`` (T,) bool drops rows that carry no
    token (padding, frozen slots)."""
    local = ids - cfg.expert_rank * cfg.n_held_experts
    held = (local >= 0) & (local < cfg.n_held_experts)
    if valid is not None:
        held = held & valid[:, None]
    return local, held


def _combine(cfg, ids, weights, valid):
    """(each row's weight for each held expert (T, E) float32, zero
    where the row did not choose it; pairs routed to each held expert
    (E,) int32)."""
    local, held = _held(cfg, ids, valid)
    onehot = (local[..., None] == jnp.arange(cfg.n_held_experts)) \
        & held[..., None]                                   # (T, K, E)
    return ((onehot * weights[..., None]).sum(axis=1),
            onehot.sum(axis=(0, 1)).astype(jnp.int32))


def _expert_at(experts, e, at=None):
    """Held expert ``e``'s matrices, read where they lie: ``experts`` is
    one layer's, (E, ..) a leaf, or with ``at`` (a layer's index,
    traced) the whole stack, (layers, E, ..) a leaf, read at ``[at,
    e]``."""
    if at is None:
        return {name: lax.dynamic_index_in_dim(leaf, e, 0, keepdims=False)
                for name, leaf in experts.items()}
    return {name: lax.dynamic_slice(
        leaf, (at, e) + (0,) * (leaf.ndim - 2),
        (1, 1) + leaf.shape[2:]).reshape(leaf.shape[2:])
        for name, leaf in experts.items()}


def experts_batched(cfg, experts, h, ids, weights, valid=None):
    """Every held expert over every row in one batched product; a row
    weighs zero for an expert it did not choose. The plain formulation
    of a decode step's routed part, on no served path: the oracle the
    tests hold ``experts_hit`` to (it reads every held expert whatever
    the router chose). Returns (y (T, D) float32, pairs routed to each
    held expert (E,) int32)."""
    combine, counts = _combine(cfg, ids, weights, valid)
    gate = jax.nn.silu(jnp.einsum(
        "td,edf->etf", h, experts["w_gate"]).astype(jnp.float32))
    up = jnp.einsum("td,edf->etf", h, experts["w_up"]).astype(jnp.float32)
    out = jnp.einsum("etf,efd->etd", (gate * up).astype(h.dtype),
                     experts["w_down"], preferred_element_type=jnp.float32)
    y = jnp.einsum("te,etd->td", combine, out)
    return y, counts


def experts_hit(cfg, experts, h, ids, weights, valid=None, at=None):
    """A decode step's routed part, with work that follows the experts
    hit: few rows, and the cost is the experts' bytes, so an expert no
    row chose is not read. The held experts are put hit-first (a stable
    sort, so the hit ones stay in ascending order) and a loop makes one
    trip a hit expert: its three matrices read where they lie
    (``_expert_at``; a caller whose layers are a scan's steps passes the
    whole stack and ``at``, as for ``experts_grouped``: a scan's slice
    handed to a loop is a copy of the layer's experts), all rows
    through it, and its result added to the (T, D) float32 sum with
    each row's weight for it, zero for a row that did not choose it. No
    row gather, no capacity, no dropped pair; no valid row is zero trips
    and a zero sum. bfloat16 products, float32 accumulators and
    weights, as ``experts_batched``; the sum over experts goes in the
    loop's order. Returns (y (T, D) float32, pairs routed to each held
    expert (E,) int32)."""
    combine, counts = _combine(cfg, ids, weights, valid)
    hit_first = jnp.argsort(counts == 0, stable=True).astype(jnp.int32)

    def add_expert(i, y):
        e = hit_first[i]
        out = _swiglu(_expert_at(experts, e, at), h, jnp.float32)
        return y + lax.dynamic_slice_in_dim(combine, e, 1, axis=1) * out

    y = lax.fori_loop(0, (counts > 0).sum().astype(jnp.int32), add_expert,
                      jnp.zeros(h.shape, jnp.float32))
    return y, counts


def _block_rows(cfg, tokens: int) -> int:
    """Rows a block of the grouped product holds: the pairs one expert
    expects under even routing, as a power of two in 8..256; or what
    the family says (``cfg.expert_block_rows(tokens)``), where it has
    been measured."""
    own = getattr(cfg, "expert_block_rows", None)
    if own is not None:
        return own(tokens)
    expect = max(1, tokens * cfg.top_k // cfg.n_routed_experts)
    return min(256, max(8, 1 << (expect - 1).bit_length()))


def experts_grouped(cfg, experts, h, ids, weights, valid=None, at=None):
    """The held experts' part with work that follows the routed pairs.
    The held (token, expert) pairs are sorted by expert; each expert's
    run is cut into blocks of ``_block_rows`` rows, and a loop over the
    blocks *that exist* gathers a block's rows and runs its one expert.
    Nothing is sized by a capacity: however the router spreads the
    tokens, each pair is computed. Returns (y (T, D) float32, pairs per
    held expert (E,)).

    Where an expert's weights are read: with ``at`` (a layer's index,
    traced) ``experts`` is the whole stack, (layers, E, ..) a leaf, and
    a block reads its expert at ``[at, e]`` in place; without it
    ``experts`` is one layer's, (E, ..) a leaf. A caller whose layers
    are a scan's steps passes the stack and ``at``: a layer's slice
    handed to the block loop is a copy of every held expert, because a
    loop's operand is a buffer (3 x 470 MB a layer a prefill at
    xing4-29b's widths, 3 x 503 MB at pangu-ultra-moe-ep16's: PERF.md
    section 6, docs/tpu/model-serving.md).

    How a token's pairs are summed follows from the share held. A
    chip's share of the experts (``n_held_experts <
    n_routed_experts``): a block's weighted result is added to its
    tokens' rows of the (T, D) sum; one sized by every routed pair
    would be 2 GB at 16 x 512 tokens, 8 of 256, for the 6 % of its
    places ever written. Every routed expert held: a block's result is
    written at its rows' places in the sorted order, one (T * top_k +
    block, D) slab, and the tokens' sums are taken once after the loop:
    a tail block's dead rows land on the next expert's places and are
    written over by the blocks that own them (the loop runs in order);
    the places of padding rows' pairs, the last, stay zero or finite
    and weigh zero."""
    tokens, k = ids.shape
    n_held, block = cfg.n_held_experts, _block_rows(cfg, tokens)
    local, held = _held(cfg, ids, valid)
    flat = jnp.where(held, local, n_held).reshape(-1)       # absent: last
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    counts = (flat[:, None] == jnp.arange(n_held)).sum(0).astype(jnp.int32)
    starts = jnp.cumsum(counts) - counts                    # in sorted order
    blocks = -(-counts // block)
    block_ends = jnp.cumsum(blocks)
    flat_w = weights.reshape(-1)

    def rows_of(i):
        e = jnp.searchsorted(block_ends, i, side="right").astype(jnp.int32)
        first = starts[e] + (i - (block_ends[e] - blocks[e])) * block
        rows = first + jnp.arange(block, dtype=jnp.int32)
        live = rows < starts[e] + counts[e]
        pair = order[jnp.minimum(rows, tokens * k - 1)]
        return e, first, live, pair

    def expert(e):
        return _expert_at(experts, e, at)

    def add_block(i, y):
        e, _, live, pair = rows_of(i)
        token = pair // k
        out = _swiglu(expert(e), h[token]).astype(jnp.float32)
        scale = jnp.where(live, flat_w[pair], 0.0)
        return y.at[token].add(out * scale[:, None])

    def place_block(i, out):
        e, first, _, pair = rows_of(i)
        return lax.dynamic_update_slice_in_dim(
            out, _swiglu(expert(e), h[pair // k]).astype(jnp.float32),
            first, 0)

    if n_held < cfg.n_routed_experts:
        y = lax.fori_loop(0, block_ends[-1], add_block,
                          jnp.zeros((tokens, h.shape[-1]), jnp.float32))
        return y, counts
    out = lax.fori_loop(
        0, block_ends[-1], place_block,
        jnp.zeros((tokens * k + block, h.shape[-1]), jnp.float32))
    place = jnp.zeros((tokens * k,), jnp.int32).at[order].set(
        jnp.arange(tokens * k, dtype=jnp.int32))
    scale = jnp.where(held, weights, 0.0).reshape(-1)
    y = (out[place] * scale[:, None]).reshape(tokens, k, -1).sum(axis=1)
    return y, counts


def moe_ffn(cfg, layer, h, valid=None, grouped=False):
    """The expert layer on h (T, D): shared experts + this chip's routed
    part. Returns (y (T, D) in h's dtype, the step counters (5,) int32
    in ``STEP_COUNTERS`` order, over the ``valid`` rows, the experts
    chosen (T, top_k))."""
    ids, weights = route(cfg, layer["router"], h, layer.get("router_bias"))
    if grouped:
        y, per_expert = experts_grouped(cfg, layer["experts"], h, ids,
                                        weights, valid,
                                        layer.get("experts_at"))
    else:
        y, per_expert = experts_hit(cfg, layer["experts"], h, ids, weights,
                                    valid, layer.get("experts_at"))
    if "shared" in layer:
        shared = _swiglu(layer["shared"], h).astype(jnp.float32)
        scale = getattr(cfg, "shared_scale", 1.0)
        y = y + (shared if scale == 1.0 else shared * scale)
    rows = (jnp.int32(h.shape[0]) if valid is None
            else valid.sum().astype(jnp.int32))
    counters = jnp.stack([rows * cfg.top_k, per_expert.sum(),
                          (per_expert > 0).sum().astype(jnp.int32),
                          per_expert.max(), jnp.int32(1)])
    return y.astype(h.dtype), counters, ids
