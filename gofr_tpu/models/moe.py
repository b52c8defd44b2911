"""Mixture-of-Experts Llama variant with expert parallelism (ep).

No reference analog (SURVEY.md §2.7/§2.8); this completes the framework's
parallelism axes (dp/tp/sp/ep). TPU-first design:

- **Static-shape einsum dispatch** (GShard/Switch style): top-1 routing
  with a fixed per-expert capacity C; dispatch/combine are one-hot einsums
  so the whole MoE layer is three MXU matmuls + masking — no gather/sort,
  no dynamic shapes, jit-stable at any routing distribution (overflow
  tokens are dropped, the standard capacity-factor trade).
- **Expert parallelism by annotation**: expert-stacked weights carry a
  leading E axis sharded on the ``ep`` mesh axis
  (parallel.sharding.moe_param_specs). Under GSPMD the dispatch einsum
  lowers to an all-to-all over ICI — no hand-written collectives.
- Router/gating in fp32 (softmax stability), experts in bf16 (MXU).
- Aux load-balance loss (Switch §2.2 style: E · Σ fraction·probability)
  keeps routing uniform; exposed from ``loss_fn`` for the train step.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.models import llama as llama_mod
from gofr_tpu.ops import (decode_attention_cached, prefill_attention,
                          rms_norm, rope_table)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    base: llama_mod.LlamaConfig = dataclasses.field(
        default_factory=lambda: llama_mod.PRESETS["tiny"])
    n_experts: int = 4
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01

    # Serving-contract delegation: GenerationEngine and its cache sizing
    # read these off the config it is handed, so an MoEConfig quacks like
    # the base LlamaConfig for everything that is not an FFN concern.
    @property
    def vocab_size(self) -> int:
        return self.base.vocab_size

    @property
    def dim(self) -> int:
        return self.base.dim

    @property
    def n_layers(self) -> int:
        return self.base.n_layers

    @property
    def n_heads(self) -> int:
        return self.base.n_heads

    @property
    def n_kv_heads(self) -> int:
        return self.base.n_kv_heads

    @property
    def head_dim(self) -> int:
        return self.base.head_dim

    @property
    def max_seq_len(self) -> int:
        return self.base.max_seq_len

    @property
    def dtype(self):
        return self.base.dtype

    @property
    def kv_int8(self) -> bool:
        return self.base.kv_int8


PRESETS = {
    "tiny": MoEConfig(),
    "small": MoEConfig(base=llama_mod.PRESETS["small"], n_experts=8),
}


def config(preset: str = "tiny", **overrides) -> MoEConfig:
    return dataclasses.replace(PRESETS[preset], **overrides)


def init(cfg: MoEConfig, key: jax.Array) -> Dict[str, Any]:
    """Same layout as llama.init but the FFN weights gain a leading
    (E,) expert axis and each layer gains a router."""
    base = cfg.base
    params = llama_mod.init(base, key)
    keys = jax.random.split(jax.random.fold_in(key, 1), 4)
    d, f, l_count, e = base.dim, base.ffn_dim, base.n_layers, cfg.n_experts
    dt = base.dtype

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(dt)

    layers = dict(params["layers"])
    layers.pop("w_gate"), layers.pop("w_up"), layers.pop("w_down")
    layers["router"] = (jax.random.normal(keys[0], (l_count, d, e),
                                          jnp.float32) * 0.02)
    layers["w_gate"] = dense(keys[1], (l_count, e, d, f), d)
    layers["w_up"] = dense(keys[2], (l_count, e, d, f), d)
    layers["w_down"] = dense(keys[3], (l_count, e, f, d), f)
    params["layers"] = layers
    return params


def _moe_ffn(layer, x, cfg: MoEConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, D) → (out (B, S, D), aux_loss scalar). Top-1 capacity
    routing with einsum dispatch/combine."""
    b, s, d = x.shape
    e = cfg.n_experts
    tokens = b * s
    capacity = max(1, int(math.ceil(tokens / e * cfg.capacity_factor)))

    flat = x.reshape(tokens, d)
    logits = (flat.astype(jnp.float32) @ layer["router"])      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)                    # (T,)
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=-1)[:, 0]

    # position of each token within its expert's capacity buffer
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # (T, E)
    position = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot     # (T, E)
    kept = (position < capacity) * onehot                      # (T, E)
    pos_idx = position.sum(axis=-1).astype(jnp.int32)          # (T,)
    kept_mask = kept.sum(axis=-1)                              # (T,)

    # dispatch (T, E, C) one-hot → expert inputs (E, C, D)
    dispatch = (kept[:, :, None]
                * jax.nn.one_hot(pos_idx, capacity,
                                 dtype=jnp.float32)[:, None, :])
    expert_in = jnp.einsum("tec,td->ecd", dispatch,
                           flat.astype(jnp.float32)).astype(x.dtype)

    # expert FFN: batched over the (sharded) E axis
    gate_act = jax.nn.silu(jnp.einsum(
        "ecd,edf->ecf", expert_in, layer["w_gate"]).astype(jnp.float32))
    up = jnp.einsum("ecd,edf->ecf", expert_in,
                    layer["w_up"]).astype(jnp.float32)
    expert_out = jnp.einsum("ecf,efd->ecd",
                            (gate_act * up).astype(x.dtype),
                            layer["w_down"])

    combine = dispatch * (gate * kept_mask)[:, None, None]
    out = jnp.einsum("tec,ecd->td", combine,
                     expert_out.astype(jnp.float32)).astype(x.dtype)

    # Switch-style load balance: E · Σ_e fraction_e · mean-prob_e
    fraction = onehot.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = cfg.n_experts * jnp.sum(fraction * mean_prob)
    return out.reshape(b, s, d), aux


def forward(params: Dict[str, Any], cfg: MoEConfig, tokens: jnp.ndarray
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """→ (logits (B, S, V) fp32, aux_loss scalar)."""
    base = cfg.base
    b, s = tokens.shape
    cos, sin = rope_table(base.max_seq_len, base.head_dim, base.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = params["tok_emb"][tokens]

    def body(carry, layer):
        x, aux = carry
        h = rms_norm(x, layer["attn_norm"], base.norm_eps)
        q, k, v = llama_mod._qkv(layer, h, base, cos, sin, positions)
        attn = prefill_attention(q, k, v).reshape(b, s, -1)
        x = x + attn @ layer["wo"]
        h = rms_norm(x, layer["ffn_norm"], base.norm_eps)
        ffn_out, layer_aux = _moe_ffn(layer, h, cfg)
        return (x + ffn_out, aux + layer_aux), None

    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                           params["layers"])
    x = rms_norm(x, params["out_norm"], base.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, aux / base.n_layers


# -- serving bridge (llama serving contract: ISSUE 7 registry entry) --------
#
# GenerationEngine accepts any model module exposing
# init_cache/prefill/decode_step with llama's signatures; these mirror
# llama's dense serving path with ``_moe_ffn`` substituted for the dense
# FFN (the router aux loss is a training regularizer and is dropped).
# Deliberately narrower than llama: no paged KV, no prefix reuse, no
# int8 cache — the engine's custom-module validation already blocks the
# first two, and the bf16-only guard here keeps the last honest.


def _check_serving_cfg(cfg: MoEConfig) -> llama_mod.LlamaConfig:
    if cfg.base.kv_int8:
        raise ValueError("MoE serving path is bf16-only (kv_int8=False)")
    return cfg.base


def init_cache(cfg: MoEConfig, batch: int,
               max_len: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    """Same static-shape per-layer KV cache as llama (attention is
    identical; only the FFN differs)."""
    return llama_mod.init_cache(cfg.base, batch, max_len)


def prefill(params: Dict[str, Any], cfg: MoEConfig, tokens: jnp.ndarray,
            cache: Dict[str, jnp.ndarray],
            lengths: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], jnp.ndarray]:
    """Run the prompt, fill the cache. Returns (last-token logits (B, V),
    cache, cache_len (B,)) — llama.prefill's bucketed-serving contract
    (``lengths`` supports right-padded prompts)."""
    base = _check_serving_cfg(cfg)
    b, s = tokens.shape
    cos, sin = rope_table(base.max_seq_len, base.head_dim, base.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = params["tok_emb"][tokens]

    def body(x, xs):
        layer = xs["layer"]
        h = rms_norm(x, layer["attn_norm"], base.norm_eps)
        q, k, v = llama_mod._qkv(layer, h, base, cos, sin, positions)
        attn = prefill_attention(q, k, v).reshape(b, s, -1)
        x = x + attn @ layer["wo"]
        h = rms_norm(x, layer["ffn_norm"], base.norm_eps)
        ffn_out, _ = _moe_ffn(layer, h, cfg)
        x = x + ffn_out
        new_cache = {
            "k": lax.dynamic_update_slice_in_dim(
                xs["cache"]["k"], k, 0, axis=1),
            "v": lax.dynamic_update_slice_in_dim(
                xs["cache"]["v"], v, 0, axis=1)}
        return x, new_cache

    x, new_cache = lax.scan(
        body, x, {"layer": params["layers"], "cache": cache})
    if lengths is None:
        last = x[:, -1]
        cache_len = jnp.full((b,), s, jnp.int32)
    else:
        last = x[jnp.arange(b), lengths - 1]
        cache_len = lengths.astype(jnp.int32)
    last = rms_norm(last, params["out_norm"], base.norm_eps)
    logits = (last @ params["lm_head"]).astype(jnp.float32)
    return logits, new_cache, cache_len


def decode_step(params: Dict[str, Any], cfg: MoEConfig,
                token: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                cache_len: jnp.ndarray
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], jnp.ndarray]:
    """One decode step: token (B,) → (logits (B, V), cache, cache_len+1).
    Cache rides the scan carry and the scatter writes only the B new
    rows, exactly llama.decode_step's layout (its rationale applies
    unchanged — the FFN swap doesn't touch the KV path)."""
    base = _check_serving_cfg(cfg)
    b = token.shape[0]
    cos, sin = rope_table(base.max_seq_len, base.head_dim, base.rope_theta)
    positions = cache_len[:, None]                       # (B, 1)
    x = params["tok_emb"][token][:, None, :]             # (B, 1, D)
    batch_idx = jnp.arange(b)

    def body(carry, layer_and_idx):
        x, ck, cv = carry
        layer, idx = layer_and_idx
        k_view = lax.dynamic_index_in_dim(ck, idx, 0, keepdims=False)
        v_view = lax.dynamic_index_in_dim(cv, idx, 0, keepdims=False)
        h = rms_norm(x, layer["attn_norm"], base.norm_eps)
        q, k, v = llama_mod._qkv(layer, h, base, cos, sin, positions)
        attn = decode_attention_cached(q, k_view, v_view, k[:, 0],
                                       v[:, 0], cache_len)
        x = x + attn.reshape(b, 1, -1) @ layer["wo"]
        h = rms_norm(x, layer["ffn_norm"], base.norm_eps)
        ffn_out, _ = _moe_ffn(layer, h, cfg)
        x = x + ffn_out
        ck = ck.at[idx, batch_idx, cache_len].set(k[:, 0])
        cv = cv.at[idx, batch_idx, cache_len].set(v[:, 0])
        return (x, ck, cv), None

    (x, ck, cv), _ = lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(base.n_layers)))
    x = rms_norm(x[:, 0], params["out_norm"], base.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, {"k": ck, "v": cv}, cache_len + 1


def loss_fn(params: Dict[str, Any], cfg: MoEConfig, tokens: jnp.ndarray,
            targets: jnp.ndarray) -> jnp.ndarray:
    logits, aux = forward(params, cfg, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean() + cfg.router_aux_weight * aux
