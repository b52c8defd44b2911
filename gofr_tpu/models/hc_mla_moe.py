"""Latent attention and a sparse expert layer under a hyper-connected
residual, for /generate: the member of ``models/mla_moe.py``'s family
whose token carries ``hc_mult`` residual streams (Xing4.0-29B-A4B,
``model_type`` ``xing4_0``).

This file holds what is new: the hyper-connected layer, the state the
two stacks carry around it, and the read-out. Latent attention (the
latent cache ``[c; k_r]``, the expanded prefill through the Pallas
flash kernel, the absorbed decode step over the latent pages),
the two stacks, YaRN (``MlaMoeConfig.rope_scaling``) and the entry
points are ``models/mla_moe.py``'s, imported and called with this
member's ``Residual``; the expert layer with its correction bias is
``models/experts.py``'s; the mixer is ``models/hyper_connection.py``.

Equations (``n = hc_mult``, a token's state ``X in R^{n x D}``; the
mixer's ``H_pre``, ``H_post``, ``H_res`` of a sublayer and everything
they are made of in float32: ``models/hyper_connection.py``):

- Embedding: ``X_i = E[token]`` for every stream ``i``.
- A layer is two sublayers, each with a mixer of its own: ``h = sum_i
  H_pre,i X_i``; ``y = F(RMS(h; g))``; ``X'_i = sum_j H_res[i, j] X_j +
  H_post,i y``, with ``F = Attn(.) W_O`` (``g = in_norm``) and then
  ``F = FFN`` (``g = pre_mlp_norm``): the SwiGLU of a leading dense
  layer, or the expert layer. No sandwich norm.
- Read-out: ``x = sum_i X_i``, then ``out_norm`` and the head.
- Attention: ``mla_moe.py``'s, rotary by YaRN (``ops/rotary.py``:
  per-frequency blend of ``theta^(-2i/d)`` and the same over
  ``factor``; softmax scale ``qk_head_dim^-0.5 * yarn_mscale(factor,
  mscale_all_dim)^2``).
- Expert layer: ``s = sigmoid(W_g h)`` in float32; ``T`` = the
  ``top_k`` largest of ``s + b_e`` (``topk_method: noaux_tc``, the
  layer's ``router_bias``); ``w_e = routed_scale * s_e / sum_{j in T}
  s_j``; ``y = Shared(h) + sum_{e in T} w_e Expert_e(h)``. ``n_group =
  topk_group = 1``: no group limit.

The state is (n, B, S, D): the streams lead, each a plain activation
(``hyper_connection.py`` says why). With ``hc_mult`` 1, ``H_pre =
H_post = H_res = 1`` this is ``mla_moe.py``'s layer on the same
weights (a test holds it to that).

Serving contract (``docs/tpu/model-serving.md``): ``init``,
``init_cache``, ``prefill``, ``decode_step``, ``decode_step_paged``,
``cache_leaves`` and ``STEP_COUNTERS``: ``mla_moe.py``'s (the expert
layer's five, ``attn.calls``, ``attn.kernel_calls``), then
``attn.rows_live`` (latent rows under ``cache_len``, the new one with
them, over the active slots) and ``attn.rows_read`` (rows a gather of
the table's pages brings in for all slots), each summed over layers and
steps: their ratio is what the gathered form wastes, and what the
sparse latent kernel's walk of live pages does not read.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from gofr_tpu.models import hyper_connection, mla_moe
from gofr_tpu.models.experts import route  # noqa: F401  (the module's name)
from gofr_tpu.models.mla_moe import (  # noqa: F401  (the module's names)
    MlaMoeConfig, cache_leaves, init_cache)
from gofr_tpu.ops import rms_norm

STEP_COUNTERS = mla_moe.STEP_COUNTERS + ("attn.rows_live", "attn.rows_read")

_TOPK_METHODS = ("greedy", "noaux_tc")


@dataclasses.dataclass(frozen=True)
class HcMlaMoeConfig(MlaMoeConfig):
    hc_mult: int = 4                  # residual streams a token
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp_min: float = -30.0       # mhc_h_res_clamp_min / _max
    hc_clamp_max: float = 30.0
    topk_method: str = "noaux_tc"     # experts chosen by s + bias
    n_group: int = 1
    topk_group: int = 1

    def __post_init__(self):
        super().__post_init__()
        if self.topk_method not in _TOPK_METHODS:
            raise ValueError(f"topk_method must be one of {_TOPK_METHODS}, "
                             f"got {self.topk_method!r}")
        if (self.n_group, self.topk_group) != (1, 1):
            raise ValueError(
                f"n_group {self.n_group}, topk_group {self.topk_group}: "
                f"group-limited routing is not written; this member "
                f"publishes 1 and 1")
        if self.sandwich_norm:
            raise ValueError("a hyper-connected layer has no sandwich norm")

    @property
    def hc_clamp(self) -> Tuple[float, float]:
        return self.hc_clamp_min, self.hc_clamp_max


PRESETS: Dict[str, HcMlaMoeConfig] = {
    # tiny: unit tests and the benchmark's CPU rehearsal
    "tiny": HcMlaMoeConfig(
        vocab_size=256, dim=64, n_layers=3, n_dense_layers=1, n_heads=4,
        q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, ffn_dim=128, moe_ffn_dim=32, n_routed_experts=16,
        n_held_experts=16, top_k=4, routed_scale=2.0, sandwich_norm=False,
        max_seq_len=128, rope_theta=10000.0, norm_eps=1e-6,
        rope_scaling={"type": "yarn", "factor": 8, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 16}),
    "xing4": HcMlaMoeConfig(
        vocab_size=131072, dim=3584, n_layers=40, n_dense_layers=2,
        n_heads=32, q_lora_rank=768, kv_lora_rank=512, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128, ffn_dim=9216, moe_ffn_dim=1024,
        n_routed_experts=64, n_held_experts=64, top_k=4, routed_scale=2.0,
        sandwich_norm=False, max_seq_len=262144, rope_theta=10000.0,
        norm_eps=1e-6,
        rope_scaling={"type": "yarn", "factor": 64, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096}),
}


def config(preset: str = "tiny", **overrides) -> HcMlaMoeConfig:
    return dataclasses.replace(PRESETS[preset], **overrides)


def init(cfg: HcMlaMoeConfig, key: jax.Array) -> Dict[str, Any]:
    """``mla_moe.init``'s tree, and in each stack the two sublayers'
    mixers (``hc_attn``, ``hc_ffn``: float32, ``hyper_connection.init``)
    and, with ``noaux_tc``, the expert layers' correction bias
    (``router_bias`` (layers, experts) float32, small seeded normals:
    large enough beside the scores' spacing that ignoring it chooses
    other experts)."""
    params = mla_moe.init(cfg, key)
    for at, (name, stack, _) in enumerate(mla_moe._stacks(params, cfg)):
        layers = stack["in_norm"].shape[0]
        for sub, which in enumerate(("hc_attn", "hc_ffn")):
            stack[which] = hyper_connection.init(
                jax.random.fold_in(key, (1 << 20) + 2 * at + sub), layers,
                cfg.hc_mult, cfg.dim)
        if name == "moe" and cfg.topk_method == "noaux_tc":
            stack["router_bias"] = 0.1 * jax.random.normal(
                jax.random.fold_in(key, (1 << 20) + 16),
                (layers, cfg.n_routed_experts), jnp.float32)
    return params


def _layer(cfg: HcMlaMoeConfig, kind: str, layer, x, attend, valid, grouped):
    """One hyper-connected layer on the streams x (n, B, S, D);
    ``mla_moe._layer``'s signature and results."""
    n, b, s, d = x.shape
    x = x.reshape(n, b * s, d)

    def sublayer(x, mixer, gain, run):
        pre, post, res = hyper_connection.mix(cfg, mixer, x)
        h = rms_norm(hyper_connection.read(x, pre), gain, cfg.norm_eps)
        y, *rest = run(h.reshape(b, s, d))
        return hyper_connection.write(x, res, post,
                                      y.reshape(b * s, d)), rest

    def attention(h):
        out, carried = attend(layer["attn"], h)
        return out @ layer["attn"]["w_o"], carried

    x, (carried,) = sublayer(x, layer["hc_attn"], layer["in_norm"],
                             attention)
    x, (counters, ids) = sublayer(
        x, layer["hc_ffn"], layer["pre_mlp_norm"],
        lambda h: mla_moe._ffn(cfg, kind, layer, h, valid, grouped))
    return x.reshape(n, b, s, d), carried, counters, ids


class Residual:
    """This member's residual path (``mla_moe.Residual``): the embedding
    copied into every stream, the hyper-connected layer, the streams'
    sum before the final norm."""
    spread = staticmethod(lambda cfg, x: jnp.broadcast_to(
        x[None], (cfg.hc_mult, *x.shape)))
    layer = staticmethod(_layer)
    gather = staticmethod(
        lambda x: x.astype(jnp.float32).sum(axis=0).astype(x.dtype))


def prefill(params: Dict[str, Any], cfg: HcMlaMoeConfig, tokens: jnp.ndarray,
            cache: Dict[str, jnp.ndarray],
            lengths: Optional[jnp.ndarray] = None, routes: bool = False):
    """``mla_moe.prefill`` around the hyper-connected layer."""
    return mla_moe.prefill(params, cfg, tokens, cache, lengths, routes,
                           residual=Residual)


def decode_step(params: Dict[str, Any], cfg: HcMlaMoeConfig,
                token: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                cache_len: jnp.ndarray, window: Optional[int] = None):
    """``mla_moe.decode_step`` around the hyper-connected layer."""
    return mla_moe.decode_step(params, cfg, token, cache, cache_len, window,
                               residual=Residual)


def decode_step_paged(params: Dict[str, Any], cfg: HcMlaMoeConfig,
                      token: jnp.ndarray, pool: Dict[str, jnp.ndarray],
                      page_table: jnp.ndarray, cache_len: jnp.ndarray,
                      active: jnp.ndarray, counters: bool = False,
                      routes: bool = False):
    """``mla_moe.decode_step_paged`` around the hyper-connected layer;
    with ``counters`` the step's ``STEP_COUNTERS``: ``mla_moe``'s and
    the latent rows live and read."""
    out = mla_moe.decode_step_paged(params, cfg, token, pool, page_table,
                                    cache_len, active, counters,
                                    residual=Residual, routes=routes)
    if not counters:
        return out
    live = jnp.where(active, cache_len + 1, 0).sum().astype(jnp.int32)
    read = page_table.shape[0] * page_table.shape[1] * pool["ckv"].shape[2]
    rows = cfg.n_layers * jnp.stack([live, jnp.int32(read)])
    return out[:3] + (jnp.concatenate([out[3], rows]),) + out[4:]
