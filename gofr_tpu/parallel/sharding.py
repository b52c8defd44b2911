"""Sharding rules: map param pytrees to PartitionSpecs.

The whole tensor-parallel design is annotation-only (no collective calls in
model code): Megatron-style column/row parallel pairs —

  wq/wk/wv, w_gate/w_up : column-parallel (shard output features on ``tp``)
  wo, w_down            : row-parallel   (shard input features on ``tp``)

so each attention/FFN block needs exactly one all-reduce on its output,
which XLA inserts automatically from these specs and runs over ICI.
Layers are stacked (L, ...) so every spec carries a leading ``None``.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def shard_pytree(tree: Any, mesh: Mesh, specs: Any) -> Any:
    """device_put every leaf to its NamedSharding (specs mirrors tree)."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs)


def named_shardings(mesh: Mesh, specs: Any) -> Any:
    """NamedShardings mirroring a PartitionSpec pytree — the
    ``out_shardings`` of a jit that creates a tree already sharded, so
    no leaf is ever whole on one device."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def replicated_specs(tree: Any) -> Any:
    return jax.tree.map(lambda _: P(), tree)


def prune_specs(specs: Any, mesh: Mesh) -> Any:
    """Drop axis names the mesh doesn't have (→ replicated on that dim), so
    one canonical rule-set serves every mesh topology."""
    def prune(spec: P) -> P:
        return P(*(axis if axis in mesh.shape else None for axis in spec))
    return jax.tree.map(prune, specs,
                        is_leaf=lambda x: isinstance(x, P))


def llama_param_specs(tp: str = "tp") -> Dict[str, Any]:
    """PartitionSpecs mirroring gofr_tpu.models.llama param pytree."""
    return {
        "tok_emb": P(None, None),        # replicated: lookup stays local
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, tp),     # column parallel
            "wk": P(None, None, tp),
            "wv": P(None, None, tp),
            "wo": P(None, tp, None),     # row parallel → all-reduce out
            "ffn_norm": P(None, None),
            "w_gate": P(None, None, tp),
            "w_up": P(None, None, tp),
            "w_down": P(None, tp, None),
        },
        "out_norm": P(None,),
        "lm_head": P(None, tp),          # vocab-sharded logits
    }


def llama_cache_specs(dp: str = "dp", tp: str = "tp",
                      kv_int8: bool = False) -> Dict[str, P]:
    """KV cache (L, B, T, Hkv, Dh): batch on dp, kv-heads on tp. int8
    caches add per-vector scale planes (L, B, T, Hkv), sharded alike."""
    spec = P(None, dp, None, tp, None)
    specs = {"k": spec, "v": spec}
    if kv_int8:
        specs["ks"] = P(None, dp, None, tp)
        specs["vs"] = P(None, dp, None, tp)
    return specs


def llama_prefix_pool_specs(tp: str = "tp",
                            kv_int8: bool = False) -> Dict[str, P]:
    """Prefix-KV page pool (L, num_pages, page, Hkv, Dh): kv-heads on tp
    like the main cache; pages replicate across dp (any dp shard may
    gather any page — tpu/prefix_cache)."""
    spec = P(None, None, None, tp, None)
    specs = {"k": spec, "v": spec}
    if kv_int8:
        specs["ks"] = P(None, None, None, tp)
        specs["vs"] = P(None, None, None, tp)
    return specs


def moe_param_specs(tp: str = "tp", ep: str = "ep") -> Dict[str, Any]:
    """PartitionSpecs for gofr_tpu.models.moe: expert-stacked FFN weights
    (L, E, D, F) shard the expert axis on ``ep`` (GSPMD lowers the
    dispatch einsum to an all-to-all over ICI); attention stays Megatron
    tensor-parallel on ``tp``; routers replicate."""
    specs = llama_param_specs(tp)
    layers = dict(specs["layers"])
    layers.pop("w_gate"), layers.pop("w_up"), layers.pop("w_down")
    layers["router"] = P(None, None, None)
    layers["w_gate"] = P(None, ep, None, tp)
    layers["w_up"] = P(None, ep, None, tp)
    layers["w_down"] = P(None, ep, tp, None)
    specs["layers"] = layers
    return specs


def bert_param_specs(tp: str = "tp") -> Dict[str, Any]:
    """PartitionSpecs mirroring gofr_tpu.models.bert param pytree."""
    return {
        "tok_emb": P(None, None),
        "pos_emb": P(None, None),
        "type_emb": P(None, None),
        "emb_norm_w": P(None,), "emb_norm_b": P(None,),
        "layers": {
            "wq": P(None, None, tp), "wk": P(None, None, tp),
            "wv": P(None, None, tp), "wo": P(None, tp, None),
            "bq": P(None, tp), "bk": P(None, tp), "bv": P(None, tp),
            "bo": P(None, None),
            "attn_norm_w": P(None, None), "attn_norm_b": P(None, None),
            "w_in": P(None, None, tp), "b_in": P(None, tp),
            "w_out": P(None, tp, None), "b_out": P(None, None),
            "ffn_norm_w": P(None, None), "ffn_norm_b": P(None, None),
        },
        "pool_w": P(None, None), "pool_b": P(None,),
    }


def batch_spec(dp: str = "dp", ndim: int = 2) -> P:
    """Shard the leading (batch) axis on dp, replicate the rest."""
    return P(dp, *([None] * (ndim - 1)))
