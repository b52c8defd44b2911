"""Attention ops for the serving path (BASELINE.json north star).

TPU-first design notes:
- Scores/softmax accumulate in fp32; Q/K/V stay bf16 so the two einsums hit
  the MXU. XLA fuses scale+mask+softmax between them.
- GQA is expressed by reshaping Q to (kv_heads, group, ...) and letting the
  einsum broadcast over the group axis — no materialised `repeat_kv` copy,
  which matters at 7B scale where KV is the HBM-bandwidth bottleneck.
- Decode attends over a static-shape KV cache with a length mask instead of
  a dynamic slice, so one compiled executable serves every cache fill level.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30  # large-negative instead of -inf: keeps softmax NaN-free


def _snap(x: jnp.ndarray, dtype) -> jnp.ndarray:
    """Round f32 values to ``dtype``'s precision without leaving f32.

    The decode/verify formulations round at specific points (score and
    value einsum outputs, normalized probs) — that rounding schedule IS
    the numerics contract the ragged Pallas kernel reproduces bit-for-
    bit. Written as ``lax.reduce_precision`` rather than an astype
    round-trip because XLA under its default excess-precision setting
    may elide an f32→bf16→f32 convert pair inside jit, silently moving
    the rounding points between the eager and compiled runs of the SAME
    function; ``reduce_precision`` is always preserved, so the oracle
    is bit-stable under jit and the kernel can match it everywhere.
    f32 (and wider) dtypes pass through untouched.
    """
    info = jnp.finfo(dtype)
    if info.bits >= 32:
        return x
    return lax.reduce_precision(x, info.nexp, info.nmant)


def causal_mask(seq_len: int) -> jnp.ndarray:
    """(seq, seq) boolean mask, True where attention is allowed."""
    return jnp.tril(jnp.ones((seq_len, seq_len), dtype=bool))


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Multi-head (optionally grouped-query) attention.

    q: (B, S, Hq, D); k, v: (B, T, Hkv, D) with Hq % Hkv == 0.
    mask: broadcastable to (B, 1, 1, S, T), True = attend.
    Returns (B, S, Hq, D) in q.dtype.
    """
    batch, s_len, q_heads, head_dim = q.shape
    kv_heads = k.shape[2]
    group = q_heads // kv_heads
    qg = q.reshape(batch, s_len, kv_heads, group, head_dim)

    scale = head_dim ** -0.5
    # (B, Hkv, G, S, T) — contraction on head_dim feeds the MXU
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, _NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(q.dtype), v)
    return out.reshape(batch, s_len, q_heads, head_dim)


def prefill_attention(q, k, v) -> jnp.ndarray:
    """Causal self-attention over a full prompt (prefill phase)."""
    s_len = q.shape[1]
    mask = causal_mask(s_len)[None, None, None, :, :]
    return attention(q, k, v, mask)


def banded_attention(q, k, v, window=None, block: int = 512) -> jnp.ndarray:
    """Causal self-attention over whole prompts in blocks, with an
    optional sliding window: query ``t`` attends keys ``s`` with ``t -
    window < s <= t`` (``window`` None: every ``s <= t``).

    q: (B, S, Hq, D); k, v: (B, S, Hkv, D). The (S, S) scores never
    exist: a prompt goes one block of ``block`` query rows after the
    other, each against the key blocks its band touches and no others
    (a loop whose bounds follow the band), with the softmax's maximum
    and sum carried in float32 and rescaled as blocks arrive. So the
    work is the band's, and the temporaries are one ``(Hq, block,
    block)`` float32 tile whatever S: 128 heads over 8192 positions are
    a 34 GB score tensor if materialised. Prompts go one after the
    other too (``lax.map``): a tile a prompt at a time. Plain XLA; S
    must be a whole number of blocks (``block`` clamps to S)."""
    from jax import lax as _lax

    batch, s_len, q_heads, head_dim = q.shape
    kv_heads = k.shape[2]
    group = q_heads // kv_heads
    blk = min(block, s_len)
    if s_len % blk:
        raise ValueError(f"banded_attention: S={s_len} does not split "
                         f"into {blk}-row blocks")
    n_blocks = s_len // blk
    scale = head_dim ** -0.5
    # (B, blocks, Hkv, G, blk, D) and (B, Hkv, S, D)
    qh = q.reshape(batch, n_blocks, blk, kv_heads, group, head_dim) \
        .transpose(0, 1, 3, 4, 2, 5)
    kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    rows = jnp.arange(blk)

    def one_prompt(args):
        q_row, k_row, v_row = args

        def one_block(i, q_blk):
            q_pos = i * blk + rows[:, None]

            def fold(j, carry):
                m, l, acc = carry
                k_blk = _lax.dynamic_slice_in_dim(k_row, j * blk, blk, 1)
                v_blk = _lax.dynamic_slice_in_dim(v_row, j * blk, blk, 1)
                scores = jnp.einsum(
                    "kgqd,ktd->kgqt", q_blk, k_blk,
                    preferred_element_type=jnp.float32) * scale
                k_pos = j * blk + rows[None, :]
                ok = k_pos <= q_pos
                if window is not None:
                    ok = ok & (k_pos > q_pos - window)
                scores = jnp.where(ok, scores, _NEG_INF)
                # a row with no key yet carries m = _NEG_INF and sums
                # garbage; the first real key rescales it by exp(-1e30)
                # = 0, and the diagonal block gives every row one
                m_new = jnp.maximum(m, scores.max(axis=-1))
                p = jnp.exp(scores - m_new[..., None])
                corr = jnp.exp(m - m_new)
                acc = acc * corr[..., None] + jnp.einsum(
                    "kgqt,ktd->kgqd", p.astype(v.dtype), v_blk,
                    preferred_element_type=jnp.float32)
                return m_new, l * corr + p.sum(axis=-1), acc

            first = 0 if window is None else \
                jnp.maximum(i * blk - window + 1, 0) // blk
            stat = (kv_heads, group, blk)
            m, l, acc = _lax.fori_loop(
                first, i + 1, fold,
                (jnp.full(stat, _NEG_INF, jnp.float32),
                 jnp.zeros(stat, jnp.float32),
                 jnp.zeros(stat + (head_dim,), jnp.float32)))
            return (acc / l[..., None]).astype(q.dtype)

        return _lax.map(lambda xs: one_block(*xs),
                        (jnp.arange(n_blocks), q_row))

    out = _lax.map(one_prompt, (qh, kh, vh))   # (B, blocks, Hkv, G, blk, D)
    return out.transpose(0, 1, 4, 2, 3, 5).reshape(q.shape)


def prefix_prefill_attention(q, k, v, prefix_len: int) -> jnp.ndarray:
    """Causal attention for a suffix prefill over cached-prefix + suffix
    K/V (the prefix-KV-reuse path, tpu/prefix_cache).

    q: (B, S, Hq, D) — the S suffix tokens, at absolute positions
    ``prefix_len + i``; k, v: (B, prefix_len + S, Hkv, D) — the cached
    prefix K/V concatenated with the suffix's fresh K/V, in absolute
    position order. ``prefix_len`` is static. Every query may attend the
    whole prefix plus causally into the suffix, i.e. key position
    ``j <= prefix_len + i``.
    """
    s_len = q.shape[1]
    t_len = k.shape[1]
    mask = (jnp.arange(t_len)[None, :]
            <= prefix_len + jnp.arange(s_len)[:, None])
    return attention(q, k, v, mask[None, None, None])


def decode_attention(q, k_cache, v_cache, cache_len) -> jnp.ndarray:
    """One-token decode against a static-shape KV cache.

    q: (B, 1, Hq, D); caches: (B, Tmax, Hkv, D); cache_len: (B,) int32 —
    number of valid cache entries per sequence (the new token's K/V must
    already be written at position cache_len-1 ... i.e. caller scatters
    first, then calls with the post-write length).
    """
    t_max = k_cache.shape[1]
    valid = jnp.arange(t_max)[None, :] < cache_len[:, None]    # (B, Tmax)
    mask = valid[:, None, None, None, :]                       # (B,1,1,1,T)
    return attention(q, k_cache, v_cache, mask)


def gather_kv_pages(pages: jnp.ndarray,
                    page_table: jnp.ndarray) -> jnp.ndarray:
    """Gather a per-slot contiguous KV view out of a shared page pool.

    pages: (num_pages, page, ...) — one KV-cache leaf of the unified
    page pool (tpu/page_pool), layer axis already indexed out.
    page_table: (B, P) int32 — page ids per slot in sequence order;
    entries == num_pages are the unallocated sentinel. Returns
    (B, P * page, ...): the dense-cache-shaped view ragged paged
    attention runs over.

    Sentinel ids are out of bounds, and JAX gathers clamp out-of-bounds
    indices (here: to the last pool row). That is safe only under a
    contract this function cannot check itself: every table entry
    covering a position < cache_len must be a real page id, so the
    clamped garbage always lands at key positions >= cache_len, which
    every consumer masks to _NEG_INF before the softmax. The engine
    upholds it by construction (it only dispatches slots whose allocated
    pages cover cache_len + the tick's growth); tests and debug paths
    enforce it with :func:`check_sentinel_masked` instead of assuming
    it. Note the mask guards *scores*, not V values — a NaN in a clamped
    row would still poison the output through ``0 * NaN`` in the V
    einsum, which is why pool pages are zero-initialized and the Pallas
    ragged kernel goes further and never dereferences sentinel entries
    at all (``pl.when`` skip, asserted by NaN-poisoning tests).
    """
    b, p = page_table.shape
    gathered = pages[page_table]                    # (B, P, page, ...)
    return gathered.reshape(b, p * pages.shape[1], *pages.shape[2:])


def check_sentinel_masked(page_table, cache_len, page: int, sentinel: int,
                          new_tokens: int = 1) -> None:
    """Enforce the sentinel-safety contract :func:`gather_kv_pages` can
    only document: every table entry covering a live key position must be
    a real page id, so the clamped out-of-bounds garbage a sentinel
    gathers is always masked by ``cache_len`` downstream.

    Host-side (numpy) debug/test assertion — never call under jit.
    page_table: (B, P) int; cache_len: (B,) valid tokens per slot;
    ``new_tokens`` extends the check over the positions the current tick
    scatters into (decode: 1, verify: γ+1), which must also land on real
    pages. Raises AssertionError naming the first offending slot.
    """
    import numpy as np

    table = np.asarray(page_table)
    lens = np.asarray(cache_len)
    covered = np.minimum(
        -(-(lens + new_tokens) // page),            # ceil-div: pages live
        table.shape[1])
    pos = np.arange(table.shape[1])[None, :]        # (1, P)
    bad = (table == sentinel) & (pos < covered[:, None])
    if bad.any():
        b = int(np.argwhere(bad.any(axis=1))[0, 0])
        raise AssertionError(
            f"sentinel page covers live positions: slot {b} has "
            f"cache_len={int(lens[b])} (+{new_tokens} new) but table row "
            f"{table[b].tolist()} holds sentinel {sentinel} inside the "
            f"first {int(covered[b])} page(s) — gather_kv_pages would "
            f"clamp it to unmasked garbage")


def paged_decode_attention(q, k_pages, v_pages, page_table, k_new, v_new,
                           cache_len, k_scale_pages=None,
                           v_scale_pages=None, start=None) -> jnp.ndarray:
    """Ragged paged decode attention (pure-jnp gather formulation).

    The unified-paged-KV decode op (ISSUE 6, after "Ragged Paged
    Attention", arxiv 2604.15464): each slot's KV lives in pool pages
    addressed by its page-table row, so sequences are ragged — HBM held
    is ``pages_held × page`` per slot, not ``max_len``. The gather
    reconstructs exactly the rows a dense cache would hold at positions
    ``[0, P * page)`` and delegates to :func:`decode_attention_cached`,
    which makes this op token-identical to the dense path by
    construction (same einsums, same masking, same dtypes).

    q: (B, 1, Hq, D); k_pages/v_pages: (num_pages, page, Hkv, D);
    page_table: (B, P) int32 (P is the *ladder-rung* width — a static
    shape, never derived from a live page count); k_new/v_new:
    (B, Hkv, D) — the current token's K/V, carried explicitly exactly
    as on the dense path (the caller scatters into the pool after);
    cache_len: (B,) valid tokens excluding the current one. int8 pools
    pass ``k_scale_pages``/``v_scale_pages`` (num_pages, page, Hkv).
    ``start`` (B,), a sliding-window layer's lower bound: positions
    before it are masked (their table columns may hold the sentinel:
    the pages went back to the pool).

    A fused Pallas variant (gather + flash inside one kernel, no
    materialized (B, P*page) view) is the known next step; this
    formulation is the correctness baseline it must match.
    """
    k_cache = gather_kv_pages(k_pages, page_table)
    v_cache = gather_kv_pages(v_pages, page_table)
    k_scale = (gather_kv_pages(k_scale_pages, page_table)
               if k_scale_pages is not None else None)
    v_scale = (gather_kv_pages(v_scale_pages, page_table)
               if v_scale_pages is not None else None)
    return decode_attention_cached(q, k_cache, v_cache, k_new, v_new,
                                   cache_len, k_scale=k_scale,
                                   v_scale=v_scale, start=start)


def verify_attention(q, k_cache, v_cache, k_new, v_new,
                     cache_len, k_scale=None,
                     v_scale=None) -> jnp.ndarray:
    """Multi-query decode attention for speculative verify (draft-verify
    decode): G draft tokens per row are judged by the target model in one
    forward instead of G sequential decode steps.

    Generalizes :func:`decode_attention_cached` from 1 query to G: query
    ``g`` sits at absolute position ``cache_len + g``, attends every
    prior cache entry (``t < cache_len[b]``) plus the new tokens' own
    K/V causally (``u <= g``). The new K/V ride along explicitly for the
    same reason as the decode path — attending a just-scattered cache
    lowers poorly — and the caller scatters them afterwards.

    q: (B, G, Hq, D); caches: (B, Tmax, Hkv, D); k_new/v_new:
    (B, G, Hkv, D); cache_len: (B,) — valid entries *excluding* the G
    new tokens. int8 caches pass ``k_scale``/``v_scale`` (B, Tmax, Hkv);
    scale folding mirrors decode_attention_cached exactly (K into f32
    scores post-einsum, V into f32 probs pre-einsum) so G=1 verify is
    bit-identical to a decode step. Returns (B, G, Hq, D).
    """
    batch, g_len, q_heads, head_dim = q.shape
    kv_heads = k_cache.shape[2]
    group = q_heads // kv_heads
    qg = q.reshape(batch, g_len, kv_heads, group,
                   head_dim).astype(jnp.float32)

    # same _snap rounding schedule as decode_attention_cached (f32
    # end-to-end, explicit rounding points) so G=1 verify stays
    # bit-identical to a decode step and the ragged kernel's verify
    # variant can reproduce this path exactly under jit.
    scale = head_dim ** -0.5
    scores = _snap(jnp.einsum("bskgd,btkd->bkgst", qg,
                              k_cache.astype(jnp.float32)),
                   q.dtype) * scale
    if k_scale is not None:
        scores = scores * k_scale.transpose(0, 2, 1)[:, :, None, None, :]
    valid = jnp.arange(k_cache.shape[1])[None, None, None, None, :] \
        < cache_len[:, None, None, None, None]
    scores = jnp.where(valid, scores, _NEG_INF)
    # the G new tokens attend each other causally (key u <= query s)
    scores_new = _snap(jnp.einsum("bskgd,bukd->bkgsu", qg,
                                  k_new.astype(jnp.float32)),
                       q.dtype) * scale
    causal = (jnp.arange(g_len)[None, :]
              <= jnp.arange(g_len)[:, None])            # (S, U)
    scores_new = jnp.where(causal[None, None, None], scores_new, _NEG_INF)
    scores = jnp.concatenate([scores, scores_new], axis=-1)  # (B,K,G,S,T+S)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    probs_cache = probs[..., :-g_len]
    probs_new = probs[..., -g_len:]
    if v_scale is not None:
        probs_cache = probs_cache \
            * v_scale.transpose(0, 2, 1)[:, :, None, None, :]
    else:
        probs_cache = _snap(probs_cache, q.dtype)
    out = _snap(jnp.einsum("bkgst,btkd->bskgd", probs_cache,
                           v_cache.astype(jnp.float32)), q.dtype)
    out_new = _snap(jnp.einsum("bkgsu,bukd->bskgd",
                               _snap(probs_new, q.dtype),
                               v_new.astype(jnp.float32)), q.dtype)
    out = _snap(out + out_new, q.dtype)
    return out.reshape(batch, g_len, q_heads, head_dim).astype(q.dtype)


def paged_verify_attention(q, k_pages, v_pages, page_table, k_new, v_new,
                           cache_len, k_scale_pages=None,
                           v_scale_pages=None) -> jnp.ndarray:
    """Paged variant of :func:`verify_attention`: gathers the slot's KV
    view out of the shared page pool (same formulation as
    :func:`paged_decode_attention`) and delegates, so the paged verify is
    token-identical to the dense verify by construction."""
    k_cache = gather_kv_pages(k_pages, page_table)
    v_cache = gather_kv_pages(v_pages, page_table)
    k_scale = (gather_kv_pages(k_scale_pages, page_table)
               if k_scale_pages is not None else None)
    v_scale = (gather_kv_pages(v_scale_pages, page_table)
               if v_scale_pages is not None else None)
    return verify_attention(q, k_cache, v_cache, k_new, v_new,
                            cache_len, k_scale=k_scale, v_scale=v_scale)


def decode_attention_cached(q, k_cache, v_cache, k_new, v_new,
                            cache_len, k_scale=None,
                            v_scale=None, start=None) -> jnp.ndarray:
    """Decode attention over (prior cache entries + the current token's
    K/V), *without* requiring the scatter first.

    Scattering into the cache and then attending over it makes the
    attention read data-dependent on a scatter inside the same step, which
    XLA:TPU lowers poorly (measured 2× whole-step cost at B=16/T=1024).
    Attending over the old cache (masked < cache_len) plus the fresh K/V
    carried explicitly breaks that dependency; the caller scatters after,
    where nothing in the step consumes the result.

    q: (B, 1, Hq, D); caches: (B, Tmax, Hkv, D); k_new/v_new: (B, Hkv, D);
    cache_len: (B,) — valid entries *excluding* the current token.
    ``start`` (B,) or None: the first position attended (a sliding
    window's lower bound, ``cache_len - window + 1`` clipped at 0);
    entries before it are masked like those past ``cache_len``.
    Returns (B, 1, Hq, D).

    int8 KV cache (ops/quant.quantize_kv): pass ``k_cache``/``v_cache`` as
    int8 with ``k_scale``/``v_scale`` (B, Tmax, Hkv) per-vector scales.
    K dequant folds the scale into the f32 scores after the einsum; V
    dequant folds ``v_scale`` into the f32 probs *before* an f32 cache
    einsum (ADVICE r4: scaling bf16 probs stacked mantissa loss on the
    int8 error — this path is the capacity lever, so it buys precision
    with bandwidth). Either lowering leaves the int8→wide convert
    unfused on v5e — XLA materializes a converted cache copy, which is
    why int8-KV MEASURED ~12% slower than bf16 under the original bf16
    lowering and remains default-off (post-mortem: models/llama.py
    LlamaConfig.kv_int8); a fused Pallas kernel is the known speed fix.
    """
    batch, _, q_heads, head_dim = q.shape
    kv_heads = k_cache.shape[2]
    group = q_heads // kv_heads
    qg = q[:, 0].reshape(batch, kv_heads, group,
                         head_dim).astype(jnp.float32)

    # f32 end-to-end with _snap at the points the low-precision
    # formulation rounds (score einsums, normalized probs, value
    # einsums, the final add) — same values as computing in q.dtype,
    # but jit-stable and exactly reproducible by the ragged kernel.
    scale = head_dim ** -0.5
    scores = _snap(jnp.einsum("bkgd,btkd->bkgt", qg,
                              k_cache.astype(jnp.float32)),
                   q.dtype) * scale
    if k_scale is not None:
        scores = scores * k_scale.transpose(0, 2, 1)[:, :, None, :]
    at = jnp.arange(k_cache.shape[1])[None, None, None, :]
    valid = at < cache_len[:, None, None, None]
    if start is not None:
        valid = valid & (at >= start[:, None, None, None])
    scores = jnp.where(valid, scores, _NEG_INF)
    score_new = _snap(jnp.einsum("bkgd,bkd->bkg", qg,
                                 k_new.astype(jnp.float32)),
                      q.dtype)[..., None] * scale
    scores = jnp.concatenate([scores, score_new], axis=-1)  # (B,K,G,T+1)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    probs_cache = probs[..., :-1]
    if v_scale is not None:
        # int8 path: keep the probs * v_scale product in f32 through the
        # cache V einsum — snapping the scaled probs first stacks
        # low-precision mantissa loss on top of the int8 quantization
        # error, and this path is the capacity (not speed) lever anyway.
        probs_cache = probs_cache * v_scale.transpose(0, 2, 1)[:, :, None, :]
    else:
        probs_cache = _snap(probs_cache, q.dtype)
    out = _snap(jnp.einsum("bkgt,btkd->bkgd", probs_cache,
                           v_cache.astype(jnp.float32)), q.dtype)
    out_new = _snap(jnp.einsum("bkg,bkd->bkgd",
                               _snap(probs[..., -1], q.dtype),
                               v_new.astype(jnp.float32)), q.dtype)
    out = _snap(out + out_new, q.dtype)
    return out.reshape(batch, 1, q_heads, head_dim).astype(q.dtype)
