"""Llama-family decoder for the /generate serving path.

North star (BASELINE.json): "Llama-2-7B /generate endpoint, tensor-parallel
across v5e-8, KV-cache in HBM". The Go reference has no models
(SURVEY.md §2.7); this is an original TPU-first design:

- **Stacked layers + lax.scan**: all per-layer weights are stacked on a
  leading (L, ...) axis and the decoder is one ``lax.scan`` — one traced
  layer body regardless of depth, so Llama-2-7B (32 layers) compiles as
  fast as the tiny test preset.
- **bf16 weights/activations** (MXU native), fp32 for norms/softmax/logits.
- **Static-shape KV cache** (B, Tmax, Hkv, Dh) per layer with a fill-length
  mask — one compiled decode executable serves every fill level, the
  prerequisite for continuous batching.
- **Tensor parallelism by sharding annotation only**: the model code is
  SPMD-agnostic; gofr_tpu.parallel.tensor_parallel assigns PartitionSpecs
  to these param names and XLA inserts the all-reduces over ICI
  (scaling-book recipe), instead of hand-written collective calls.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.ops import (
    apply_rope,
    decode_attention,
    decode_attention_cached,
    gather_kv_pages,
    prefill_attention,
    prefix_prefill_attention,
    rms_norm,
    rope_table,
    verify_attention,
)
from gofr_tpu.ops.quant import qmm, quantize_kv, quantize_tree


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    ffn_dim: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # pallas flash-attention prefill (ops/pallas): O(S) memory, causal-block
    # skipping — required beyond ~8K context on one core; sequence lengths
    # Mosaic cannot tile take the dense einsum (_prefill_attend)
    use_flash: bool = False
    # int8 KV cache (ops/quant.quantize_kv): per-(token, head) scales,
    # halving the cache's HBM *footprint* — the capacity lever for longer
    # contexts / more slots per chip. MEASURED (v5e, 7B geometry,
    # 2026-07-30): decode is ~12% SLOWER than bf16 through plain XLA —
    # the int8→bf16 convert does not stay fused into the attention dots,
    # so the "saved" bytes come back as a materialized converted copy
    # (bf16 full-window 300 tok/s vs int8 265; window-bounded 366 vs 260
    # standalone-tick numbers). Default off: use it when the cache must
    # fit, not to go faster; on the paged path the ragged kernel
    # (ops/pallas/ragged_paged_attention) dequantizes in-kernel.
    kv_int8: bool = False

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads


PRESETS: Dict[str, LlamaConfig] = {
    # tiny: unit tests + driver dryrun (shapes divisible by tp=4, sp=2)
    "tiny": LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, ffn_dim=128, max_seq_len=128),
    # small: single-chip bench model
    "small": LlamaConfig(vocab_size=32000, dim=1024, n_layers=8, n_heads=16,
                         n_kv_heads=16, ffn_dim=2816, max_seq_len=2048),
    "7b": LlamaConfig(),  # Llama-2-7B geometry
    # Llama-3-8B geometry: GQA 32:8 (the engine's decode attention and
    # cache specs handle grouped KV heads natively), 128K-token-family
    # vocab, rope theta 500k
    "llama3-8b": LlamaConfig(vocab_size=128256, dim=4096, n_layers=32,
                             n_heads=32, n_kv_heads=8, ffn_dim=14336,
                             max_seq_len=8192, rope_theta=500000.0),
}


def config(preset: str = "tiny", **overrides) -> LlamaConfig:
    return dataclasses.replace(PRESETS[preset], **overrides)


def init(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """Random params (serving benches run on random weights; real weights
    arrive via gofr_tpu checkpoint loading — same pytree layout)."""
    keys = jax.random.split(key, 10)
    dt = cfg.dtype
    d, f, l_count = cfg.dim, cfg.ffn_dim, cfg.n_layers
    qd = cfg.n_heads * cfg.head_dim
    kvd = cfg.n_kv_heads * cfg.head_dim

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(dt)

    return {
        "tok_emb": dense(keys[0], (cfg.vocab_size, d), d),
        "layers": {
            "attn_norm": jnp.ones((l_count, d), dt),
            "wq": dense(keys[1], (l_count, d, qd), d),
            "wk": dense(keys[2], (l_count, d, kvd), d),
            "wv": dense(keys[3], (l_count, d, kvd), d),
            "wo": dense(keys[4], (l_count, qd, d), qd),
            "ffn_norm": jnp.ones((l_count, d), dt),
            "w_gate": dense(keys[5], (l_count, d, f), d),
            "w_up": dense(keys[6], (l_count, d, f), d),
            "w_down": dense(keys[7], (l_count, f, d), f),
        },
        "out_norm": jnp.ones((d,), dt),
        "lm_head": dense(keys[8], (d, cfg.vocab_size), d),
    }


def init_int8(cfg: LlamaConfig, mesh=None) -> Dict[str, Any]:
    """Random params with int8 matmul weights (the ``quantize_params``
    layout), seeded, for serving runs where only the layout matters.

    Every leaf is made by its own ``jit`` directly in its final dtype —
    and, with ``mesh``, directly into its ``llama_param_specs`` sharding
    (``out_shardings``) — so the 7B geometry never exists in float32 and
    no leaf is ever whole on one device. ``init`` + ``quantize_params``
    builds ``w_gate`` alone as 5.8 GB of float32 first, which a 16 GB
    chip does not survive. Scales are sized so dequantized weights look
    ~N(0, 1/fan_in)."""
    d, f, l_count = cfg.dim, cfg.ffn_dim, cfg.n_layers
    qd = cfg.n_heads * cfg.head_dim
    kvd = cfg.n_kv_heads * cfg.head_dim

    def qrand(seed, *shape):
        def make():
            q = jax.random.randint(jax.random.PRNGKey(seed), shape, -127,
                                   128, jnp.int8)
            scale = jnp.full(shape[:-2] + (1, shape[-1]),
                             1.0 / (127.0 * math.sqrt(shape[-2])),
                             jnp.float32)
            return {"q": q, "s": scale}
        return make

    def brand(seed, *shape):
        def make():
            return (jax.random.normal(jax.random.PRNGKey(seed), shape,
                                      jnp.float32)
                    / math.sqrt(shape[-2])).astype(cfg.dtype)
        return make

    def ones(*shape):
        return lambda: jnp.ones(shape, cfg.dtype)

    makers = {
        "tok_emb": brand(0, cfg.vocab_size, d),
        "layers": {
            "attn_norm": ones(l_count, d),
            "wq": qrand(1, l_count, d, qd),
            "wk": qrand(2, l_count, d, kvd),
            "wv": qrand(3, l_count, d, kvd),
            "wo": qrand(4, l_count, qd, d),
            "ffn_norm": ones(l_count, d),
            "w_gate": qrand(5, l_count, d, f),
            "w_up": qrand(6, l_count, d, f),
            "w_down": qrand(7, l_count, f, d),
        },
        "out_norm": ones(d),
        "lm_head": qrand(8, d, cfg.vocab_size),
    }

    def born(make, sharding=None):
        # graftcheck: ignore[GT003] — one jit per leaf, run once at
        # start-up: the point is that each leaf is created on device in
        # its final dtype and sharding, not that the callable is reused
        return jax.jit(make, out_shardings=sharding)()

    if mesh is None:
        return jax.tree.map(born, makers)
    from gofr_tpu.ops.quant import quantized_specs
    from gofr_tpu.parallel.sharding import (llama_param_specs,
                                            named_shardings, prune_specs)
    abstract = jax.tree.map(jax.eval_shape, makers)
    return jax.tree.map(born, makers, named_shardings(mesh, prune_specs(
        quantized_specs(llama_param_specs(), abstract), mesh)))


def init_cache(cfg: LlamaConfig, batch: int,
               max_len: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    """Static-shape per-layer KV cache resident in HBM. With
    ``cfg.kv_int8`` the k/v arrays are int8 plus per-vector scale planes
    ``ks``/``vs`` (L, B, T, Hkv) — half the bytes, same layout."""
    t_max = max_len or cfg.max_seq_len
    shape = (cfg.n_layers, batch, t_max, cfg.n_kv_heads, cfg.head_dim)
    if cfg.kv_int8:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "ks": jnp.ones(shape[:-1], jnp.float32),
                "vs": jnp.ones(shape[:-1], jnp.float32)}
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def _prefill_attend(cfg: LlamaConfig, seq_len: int):
    """The causal self-attention a ``seq_len``-token prompt forward runs:
    the Pallas flash kernel when ``cfg.use_flash`` asks for it and Mosaic
    can tile the shape, else the dense einsum. The engine reports the
    same choice per prompt bucket at start (``attention_paths``)."""
    if not cfg.use_flash:
        return prefill_attention
    from gofr_tpu.ops.pallas import flash_attention, flash_tileable
    if flash_tileable(seq_len, cfg.head_dim):
        return flash_attention
    # dense materializes a (B, H, S, S) f32 score tensor: at long S that
    # is an opaque device OOM (16 GB at B=1, H=8, S=32K), so the miss is
    # never silent
    import warnings
    warnings.warn(
        f"use_flash: S={seq_len}, head_dim={cfg.head_dim} does not tile "
        f"(head_dim % 128, S >= 128, whole 512-blocks) — DENSE attention "
        f"with a {cfg.n_heads * seq_len * seq_len * 4 / 2**30:.2f} GB "
        f"score tensor per sequence", stacklevel=3)
    return prefill_attention


def _qkv(layer, x, cfg, cos, sin, positions):
    # qmm: weights may be int8-quantized (ops/quant) — transparent here
    b, s, _ = x.shape
    q = qmm(x, layer["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = qmm(x, layer["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = qmm(x, layer["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q = apply_rope(q, cos, sin, positions)
    k = apply_rope(k, cos, sin, positions)
    return q, k, v


def _ffn(layer, x):
    gate = jax.nn.silu(qmm(x, layer["w_gate"]).astype(jnp.float32))
    up = qmm(x, layer["w_up"]).astype(jnp.float32)
    return qmm((gate * up).astype(x.dtype), layer["w_down"])


def quantize_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """int8 weight-only quantization of every matmul weight (attention,
    FFN, lm_head); norms and tok_emb stay bf16. Halves decode HBM traffic
    and fits 7B geometry on one ~16 GB chip (ops/quant rationale)."""
    return quantize_tree(params)


def forward(params: Dict[str, Any], cfg: LlamaConfig, tokens: jnp.ndarray,
            mesh=None, sp_axis: str = "sp", dp_axis: str = "dp",
            tp_axis: str = "tp") -> jnp.ndarray:
    """Full causal forward → logits (B, S, V) in fp32. Training/eval path.

    With ``mesh`` given (long-context sequence parallelism), attention runs
    as ring attention over the ``sp_axis`` ring — K/V blocks rotate via
    ppermute over ICI, composing with dp (batch) and tp (heads) sharding.
    """
    b, s = tokens.shape
    cos, sin = rope_table(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = params["tok_emb"][tokens]

    if mesh is not None:
        from gofr_tpu.parallel.ring_attention import ring_attention

        def attend(q, k, v):
            return ring_attention(q, k, v, mesh, axis_name=sp_axis,
                                  batch_axis=dp_axis, head_axis=tp_axis)
    else:
        attend = _prefill_attend(cfg, s)

    def body(x, layer):
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(layer, h, cfg, cos, sin, positions)
        attn = attend(q, k, v).reshape(b, s, -1)
        x = x + qmm(attn, layer["wo"])
        h = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
        x = x + _ffn(layer, h)
        return x, None

    x, _ = lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["out_norm"], cfg.norm_eps)
    return qmm(x, params["lm_head"]).astype(jnp.float32)


def prefill(params: Dict[str, Any], cfg: LlamaConfig, tokens: jnp.ndarray,
            cache: Dict[str, jnp.ndarray],
            lengths: Optional[jnp.ndarray] = None,
            prefix: Optional[Dict[str, jnp.ndarray]] = None,
            prefix_len: int = 0
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], jnp.ndarray]:
    """Run the prompt, fill the cache. Returns (last-token logits (B, V),
    cache, cache_len (B,)).

    ``lengths`` (B,) supports right-padded prompts (the bucketed serving
    path): logits are taken at position lengths-1 per sequence and
    cache_len = lengths, so junk positions past a prompt's real end are
    never attended to in decode.

    ``prefix``/``prefix_len`` is the suffix-only prefill path (prefix KV
    reuse, tpu/prefix_cache): ``prefix`` holds pre-computed KV for the
    prompt's first ``prefix_len`` tokens (same leaves as ``cache``,
    shapes (L, B, prefix_len, ...)), ``tokens`` carries only the suffix.
    RoPE positions offset by the *static* ``prefix_len`` and attention
    for each suffix token spans cached-prefix + suffix
    (ops.prefix_prefill_attention); the returned ``cache`` still holds
    only the suffix KV (the caller owns prefix placement) while
    ``cache_len`` counts prefix + suffix. With ``cfg.kv_int8`` the prefix
    arrives quantized and is dequantized to the compute dtype here —
    decode reads quantized KV either way, but suffix-prefill logits see
    quantization-level drift vs a full prefill (documented contract:
    exact token-identity holds for bf16 caches).
    """
    b, s = tokens.shape
    cos, sin = rope_table(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta)
    positions = jnp.broadcast_to(
        prefix_len + jnp.arange(s, dtype=jnp.int32), (b, s))
    x = params["tok_emb"][tokens]
    # the flash kernel is strictly causal — the prefix path needs the
    # rectangular prefix block, so it uses the dense mask form
    attend = (_prefill_attend(cfg, s) if prefix is None
              else prefill_attention)

    xs: Dict[str, Any] = {"layer": params["layers"], "cache": cache}
    if prefix is not None:
        xs["prefix"] = prefix

    def body(x, xs):
        layer = xs["layer"]
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(layer, h, cfg, cos, sin, positions)
        if prefix is None:
            attn = attend(q, k, v).reshape(b, s, -1)
        else:
            pk, pv = xs["prefix"]["k"], xs["prefix"]["v"]
            if cfg.kv_int8:
                pk = pk.astype(cfg.dtype) * \
                    xs["prefix"]["ks"][..., None].astype(cfg.dtype)
                pv = pv.astype(cfg.dtype) * \
                    xs["prefix"]["vs"][..., None].astype(cfg.dtype)
            k_all = jnp.concatenate([pk, k], axis=1)
            v_all = jnp.concatenate([pv, v], axis=1)
            attn = prefix_prefill_attention(
                q, k_all, v_all, prefix_len).reshape(b, s, -1)
        x = x + qmm(attn, layer["wo"])
        h = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
        x = x + _ffn(layer, h)
        if cfg.kv_int8:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            new_cache = {
                "k": lax.dynamic_update_slice_in_dim(
                    xs["cache"]["k"], kq, 0, axis=1),
                "v": lax.dynamic_update_slice_in_dim(
                    xs["cache"]["v"], vq, 0, axis=1),
                "ks": lax.dynamic_update_slice_in_dim(
                    xs["cache"]["ks"], ks, 0, axis=1),
                "vs": lax.dynamic_update_slice_in_dim(
                    xs["cache"]["vs"], vs, 0, axis=1)}
        else:
            new_cache = {
                "k": lax.dynamic_update_slice_in_dim(
                    xs["cache"]["k"], k, 0, axis=1),
                "v": lax.dynamic_update_slice_in_dim(
                    xs["cache"]["v"], v, 0, axis=1)}
        return x, new_cache

    x, new_cache = lax.scan(body, x, xs)
    if lengths is None:
        last = x[:, -1]
        cache_len = jnp.full((b,), prefix_len + s, jnp.int32)
    else:
        last = x[jnp.arange(b), lengths - 1]
        cache_len = prefix_len + lengths.astype(jnp.int32)
    last = rms_norm(last, params["out_norm"], cfg.norm_eps)
    logits = qmm(last, params["lm_head"]).astype(jnp.float32)
    return logits, new_cache, cache_len


def decode_step(params: Dict[str, Any], cfg: LlamaConfig,
                token: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                cache_len: jnp.ndarray, window: Optional[int] = None
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], jnp.ndarray]:
    """One decode step. token (B,) int32; returns (logits (B,V), cache,
    cache_len+1). Static shapes: scatters into the cache at cache_len.

    The full stacked cache is a scan CARRY, not an xs→ys pair: scanning
    the cache as xs makes XLA materialize a fresh stacked ys every step —
    a full-cache rewrite that measured ~40% of 7B decode tick time. As a
    carry the while-loop state buffer is updated in place and the scatter
    writes only the B new (H, D) rows per layer (measured 1.6× faster
    end-to-end at 7B geometry, within 6% of a no-scatter ceiling). The
    attention still runs over (old cache + current K/V) via
    decode_attention_cached with the scatter off its critical path.

    ``window`` (static) bounds the attention read to the cache's first
    ``window`` positions — fill-bounded decode: the caller guarantees
    every *active* row's cache_len < window, picks the executable from a
    small window ladder, and the dead tail of the static cache is never
    streamed from HBM (it dominates early-fill decode traffic). The
    scatter still targets the full cache, so growing past a window rung
    just switches executables, never moves data. With ``cfg.kv_int8`` the
    cache is int8 + scale planes; the new row quantizes before scatter.
    """
    b = token.shape[0]
    cos, sin = rope_table(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta)
    positions = cache_len[:, None]                       # (B, 1)
    x = params["tok_emb"][token][:, None, :]             # (B, 1, D)
    batch_idx = jnp.arange(b)
    int8 = cfg.kv_int8
    carry_keys = ("k", "v", "ks", "vs") if int8 else ("k", "v")

    def body(carry, layer_and_idx):
        x = carry[0]
        caches = carry[1:]
        layer, idx = layer_and_idx
        views = [lax.dynamic_index_in_dim(c, idx, 0, keepdims=False)
                 for c in caches]
        if window is not None:
            views = [v[:, :window] for v in views]
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(layer, h, cfg, cos, sin, positions)
        k_scale = views[2] if int8 else None
        v_scale = views[3] if int8 else None
        attn = decode_attention_cached(q, views[0], views[1], k[:, 0],
                                       v[:, 0], cache_len,
                                       k_scale=k_scale, v_scale=v_scale)
        x = x + qmm(attn.reshape(b, 1, -1), layer["wo"])
        h = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
        x = x + _ffn(layer, h)
        # in-place scatter of the B new rows at [layer idx, b, cache_len[b]]
        if int8:
            kq, ks = quantize_kv(k[:, 0])
            vq, vs = quantize_kv(v[:, 0])
            new_rows = (kq, vq, ks, vs)
        else:
            new_rows = (k[:, 0], v[:, 0])
        caches = tuple(
            c.at[idx, batch_idx, cache_len].set(row)
            for c, row in zip(caches, new_rows))
        return (x,) + caches, None

    carry, _ = lax.scan(
        body, (x,) + tuple(cache[key] for key in carry_keys),
        (params["layers"], jnp.arange(cfg.n_layers)))
    x = carry[0]
    new_cache = dict(zip(carry_keys, carry[1:]))
    x = rms_norm(x[:, 0], params["out_norm"], cfg.norm_eps)
    logits = qmm(x, params["lm_head"]).astype(jnp.float32)
    return logits, new_cache, cache_len + 1


def _gather_layer_pages(pools, idx, page_table):
    """Layer ``idx``'s dense-cache-shaped (B, P*page, ...) views of the
    carried pool leaves. XLA fuses the layer slice into the gather, so
    no plane is materialized on this path."""
    return [gather_kv_pages(
        lax.dynamic_index_in_dim(c, idx, 0, keepdims=False), page_table)
        for c in pools]


def decode_step_paged(params: Dict[str, Any], cfg: LlamaConfig,
                      token: jnp.ndarray, pool: Dict[str, jnp.ndarray],
                      page_table: jnp.ndarray, cache_len: jnp.ndarray,
                      active: jnp.ndarray, ragged: bool = False
                      ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray],
                                 jnp.ndarray]:
    """One decode step over the unified paged KV pool (ISSUE 6).

    token (B,) int32; ``pool`` holds the shared page-pool leaves
    (L, num_pages, page, Hkv, Dh) (+ int8 scale planes); ``page_table``
    (B, P) int32 maps each slot's sequence pages to pool rows, with
    ``num_pages`` as the unallocated sentinel — P is a *static* ladder
    rung, so one executable serves every fill level just like the dense
    cache, and P plays the attention-window role (only the table's pages
    are gathered/streamed, not a max_len tail). ``active`` (B,) bool
    gates the append: the pool is shared, so an inactive slot must not
    scatter — its row could have been freed and reallocated to another
    stream while a pipelined tick was in flight — hence its destination
    is routed to the sentinel page and dropped (the dense path could
    ignore this: each slot owned its cache row forever).

    Per layer this gathers the table's pages into the dense-cache-shaped
    (B, P*page, Hkv, Dh) view and runs exactly the dense decode-step
    attention over it (ops.paged_decode_attention formulation), then
    appends the new K/V row at page ``cache_len // page``, offset
    ``cache_len % page``. Pool leaves ride the scan carry for the same
    reason the dense cache does (no stacked-ys rewrite). Returns
    (logits (B, V), pool, cache_len + 1) — the caller freezes inactive
    rows' cache_len, as on the dense path.

    ``ragged=True`` (static) swaps the gather-then-attend formulation
    for the fused Pallas ragged kernel
    (ops.pallas.ragged_paged_decode_attention): no (B, P*page) view is
    materialized — the kernel walks the slot's actual pages via scalar
    prefetch — so ``page_table`` may carry the slot's *full* table (no
    ladder rung slicing) and int8 dequant happens in-kernel from the
    scale planes. The kernel takes the carried leaves WHOLE plus the
    scan's layer index and picks the layer in its page copies: a
    ``dynamic_index_in_dim`` here cannot fuse into a pallas_call the way
    it fuses into the gather, so XLA would copy one layer's plane of
    the entire pool per leaf, per layer, per step (8 ms of a 35 ms step
    on a v5e, PERF.md PR 26). It reads the pool as it was before this
    layer's append; the new row arrives beside it. Supports int8.
    Token-identical to the gather path, which remains the correctness
    oracle. Whether the geometry suits the kernel is the caller's call
    (ops.pallas.ragged_tileable); nothing in here falls back.
    """
    b = token.shape[0]
    cos, sin = rope_table(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta)
    positions = cache_len[:, None]                       # (B, 1)
    x = params["tok_emb"][token][:, None, :]             # (B, 1, D)
    int8 = cfg.kv_int8
    carry_keys = ("k", "v", "ks", "vs") if int8 else ("k", "v")
    num_pages = pool["k"].shape[1]
    page = pool["k"].shape[2]
    # the append destination is the same for every layer: hoist it
    page_col = cache_len // page                         # (B,)
    page_row = jnp.take_along_axis(page_table, page_col[:, None],
                                   axis=1, mode="clip")[:, 0]
    dest_row = jnp.where(active, page_row, num_pages)    # sentinel-drop
    offset = cache_len % page

    def body(carry, layer_and_idx):
        x = carry[0]
        pools = carry[1:]
        layer, idx = layer_and_idx
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(layer, h, cfg, cos, sin, positions)
        if ragged:
            from gofr_tpu.ops.pallas import ragged_paged_decode_attention
            attn = ragged_paged_decode_attention(
                q, pools[0], pools[1], page_table, k[:, 0], v[:, 0],
                cache_len, idx, *pools[2:])
        else:
            views = _gather_layer_pages(pools, idx, page_table)
            k_scale = views[2] if int8 else None
            v_scale = views[3] if int8 else None
            attn = decode_attention_cached(q, views[0], views[1], k[:, 0],
                                           v[:, 0], cache_len,
                                           k_scale=k_scale, v_scale=v_scale)
        x = x + qmm(attn.reshape(b, 1, -1), layer["wo"])
        h = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
        x = x + _ffn(layer, h)
        if int8:
            kq, ks = quantize_kv(k[:, 0])
            vq, vs = quantize_kv(v[:, 0])
            new_rows = (kq, vq, ks, vs)
        else:
            new_rows = (k[:, 0], v[:, 0])
        pools = tuple(
            c.at[idx, dest_row, offset].set(row, mode="drop")
            for c, row in zip(pools, new_rows))
        return (x,) + pools, None

    carry, _ = lax.scan(
        body, (x,) + tuple(pool[key] for key in carry_keys),
        (params["layers"], jnp.arange(cfg.n_layers)))
    x = carry[0]
    new_pool = dict(zip(carry_keys, carry[1:]))
    x = rms_norm(x[:, 0], params["out_norm"], cfg.norm_eps)
    logits = qmm(x, params["lm_head"]).astype(jnp.float32)
    return logits, new_pool, cache_len + 1


def verify_step(params: Dict[str, Any], cfg: LlamaConfig,
                tokens: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                cache_len: jnp.ndarray, window: Optional[int] = None
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Speculative verify forward: score G tokens per row in ONE step.

    ``tokens`` (B, G) sit at absolute positions ``cache_len + g``; the
    target model computes logits for every position (judging draft token
    g+1 at position g, plus the bonus position) while writing the G new
    KV rows into the cache — exactly G sequential :func:`decode_step`
    calls fused into one forward, which is the whole speculative-decode
    bargain: decode is HBM-bandwidth-bound streaming weights + cache, so
    verifying G tokens costs roughly one step's traffic. G is a *static*
    ladder rung (the engine's γ family), so shapes stay compile-stable.

    Returns (logits (B, G, V) fp32, cache). ``cache_len`` is NOT
    advanced here — the caller commits ``a + 1`` of the G+1 candidate
    tokens after acceptance and advances cache_len itself; rows written
    past the committed point sit beyond cache_len, are never attended,
    and are overwritten by the next tick (the same masking argument that
    lets inactive dense rows scatter garbage). Scatters use
    ``mode="drop"`` so a near-full row cannot clamp-corrupt its tail.
    """
    b, g_len = tokens.shape
    cos, sin = rope_table(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta)
    positions = cache_len[:, None] + jnp.arange(g_len,
                                                dtype=jnp.int32)[None, :]
    x = params["tok_emb"][tokens]                        # (B, G, D)
    batch_idx = jnp.arange(b)
    int8 = cfg.kv_int8
    carry_keys = ("k", "v", "ks", "vs") if int8 else ("k", "v")

    def body(carry, layer_and_idx):
        x = carry[0]
        caches = carry[1:]
        layer, idx = layer_and_idx
        views = [lax.dynamic_index_in_dim(c, idx, 0, keepdims=False)
                 for c in caches]
        if window is not None:
            views = [v[:, :window] for v in views]
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(layer, h, cfg, cos, sin, positions)
        k_scale = views[2] if int8 else None
        v_scale = views[3] if int8 else None
        attn = verify_attention(q, views[0], views[1], k, v, cache_len,
                                k_scale=k_scale, v_scale=v_scale)
        x = x + qmm(attn.reshape(b, g_len, -1), layer["wo"])
        h = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
        x = x + _ffn(layer, h)
        if int8:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            new_rows = (kq, vq, ks, vs)
        else:
            new_rows = (k, v)
        caches = tuple(
            c.at[idx, batch_idx[:, None], positions].set(row, mode="drop")
            for c, row in zip(caches, new_rows))
        return (x,) + caches, None

    carry, _ = lax.scan(
        body, (x,) + tuple(cache[key] for key in carry_keys),
        (params["layers"], jnp.arange(cfg.n_layers)))
    x = carry[0]
    new_cache = dict(zip(carry_keys, carry[1:]))
    x = rms_norm(x, params["out_norm"], cfg.norm_eps)
    logits = qmm(x, params["lm_head"]).astype(jnp.float32)
    return logits, new_cache


def verify_step_paged(params: Dict[str, Any], cfg: LlamaConfig,
                      tokens: jnp.ndarray, pool: Dict[str, jnp.ndarray],
                      page_table: jnp.ndarray, cache_len: jnp.ndarray,
                      active: jnp.ndarray, ragged: bool = False
                      ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Paged-pool variant of :func:`verify_step` (unified page pool).

    Same contract; the G new KV rows land at pool positions
    ``cache_len + g`` through the slot's page-table row. ``active`` (B,)
    bool routes inactive rows' appends to the sentinel page (dropped) —
    mandatory here because pool pages are shared and may have been
    reallocated, exactly as in :func:`decode_step_paged`. The engine
    guarantees an active row's allocated pages cover
    ``cache_len + G`` before dispatching a γ=G verify rung.
    ``ragged=True`` runs the fused Pallas kernel's γ+1-query variant
    over the stacked pool leaves in place (no gathered view, no
    per-layer slice), same semantics as on :func:`decode_step_paged`.
    """
    b, g_len = tokens.shape
    cos, sin = rope_table(cfg.max_seq_len, cfg.head_dim, cfg.rope_theta)
    positions = cache_len[:, None] + jnp.arange(g_len,
                                                dtype=jnp.int32)[None, :]
    x = params["tok_emb"][tokens]                        # (B, G, D)
    int8 = cfg.kv_int8
    carry_keys = ("k", "v", "ks", "vs") if int8 else ("k", "v")
    num_pages = pool["k"].shape[1]
    page = pool["k"].shape[2]
    # per-position append destinations, hoisted out of the layer scan
    page_col = positions // page                         # (B, G)
    page_row = jnp.take_along_axis(page_table, page_col, axis=1,
                                   mode="clip")
    dest_row = jnp.where(active[:, None], page_row, num_pages)
    offset = positions % page

    def body(carry, layer_and_idx):
        x = carry[0]
        pools = carry[1:]
        layer, idx = layer_and_idx
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(layer, h, cfg, cos, sin, positions)
        if ragged:
            from gofr_tpu.ops.pallas import ragged_paged_verify_attention
            attn = ragged_paged_verify_attention(
                q, pools[0], pools[1], page_table, k, v, cache_len, idx,
                *pools[2:])
        else:
            views = _gather_layer_pages(pools, idx, page_table)
            k_scale = views[2] if int8 else None
            v_scale = views[3] if int8 else None
            attn = verify_attention(q, views[0], views[1], k, v, cache_len,
                                    k_scale=k_scale, v_scale=v_scale)
        x = x + qmm(attn.reshape(b, g_len, -1), layer["wo"])
        h = rms_norm(x, layer["ffn_norm"], cfg.norm_eps)
        x = x + _ffn(layer, h)
        if int8:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            new_rows = (kq, vq, ks, vs)
        else:
            new_rows = (k, v)
        pools = tuple(
            c.at[idx, dest_row, offset].set(row, mode="drop")
            for c, row in zip(pools, new_rows))
        return (x,) + pools, None

    carry, _ = lax.scan(
        body, (x,) + tuple(pool[key] for key in carry_keys),
        (params["layers"], jnp.arange(cfg.n_layers)))
    x = carry[0]
    new_pool = dict(zip(carry_keys, carry[1:]))
    x = rms_norm(x, params["out_norm"], cfg.norm_eps)
    logits = qmm(x, params["lm_head"]).astype(jnp.float32)
    return logits, new_pool


def generate(params: Dict[str, Any], cfg: LlamaConfig, tokens: jnp.ndarray,
             max_new_tokens: int, temperature: float = 0.0,
             rng: Optional[jax.Array] = None) -> jnp.ndarray:
    """Greedy (or temperature) generation, fully jittable: prefill then a
    ``lax.scan`` of decode steps (static trip count → one executable)."""
    b, s = tokens.shape
    cache = init_cache(cfg, b, max_len=min(cfg.max_seq_len,
                                           s + max_new_tokens))
    logits, cache, cache_len = prefill(params, cfg, tokens, cache)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def sample(logits, key):
        if temperature > 0.0:
            return jax.random.categorical(key, logits / temperature, axis=-1)
        return jnp.argmax(logits, axis=-1)

    # Split once up front: one key for the prefill sample, distinct fresh
    # keys for the max_new_tokens-1 decode steps (never reuse a consumed key).
    all_keys = jax.random.split(rng, max_new_tokens)
    first = sample(logits, all_keys[0]).astype(jnp.int32)

    def body(carry, key):
        token, cache, cache_len = carry
        logits, cache, cache_len = decode_step(params, cfg, token, cache,
                                               cache_len)
        next_token = sample(logits, key).astype(jnp.int32)
        return (next_token, cache, cache_len), token

    keys = all_keys[1:]
    (last, _, _), out = lax.scan(body, (first, cache, cache_len),
                                 keys[:max_new_tokens - 1] if max_new_tokens > 1
                                 else keys[:0])
    out_tokens = jnp.concatenate(
        [out.T, last[:, None]], axis=1) if max_new_tokens > 1 else last[:, None]
    return out_tokens


def loss_fn(params: Dict[str, Any], cfg: LlamaConfig, tokens: jnp.ndarray,
            targets: jnp.ndarray, mesh=None) -> jnp.ndarray:
    """Next-token cross-entropy — the training-step objective used by
    gofr_tpu.parallel.train and the driver's dryrun_multichip."""
    logits = forward(params, cfg, tokens, mesh=mesh)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()
