"""The bytes a decode step of the latent-attention / sparse-expert family
has to move (``models/mla_moe.py``), for ``decode_step_roofline.pangu``.
Computed from the configuration's published sizes, never read from the
program; ``adapters/mla_moe.py`` makes ``decode_step_bytes`` reachable to
``layers.read_roofline`` under the name ``decode_step_bytes_mla_moe``.

One decode step reads, once: every weight outside the routed experts
(latent attention's five matrices and two inner norms, the four sandwich
gains, the dense layers' SwiGLU, the router, the shared expert, the final
norm), the output head's slice of the vocabulary, one embedding row a
slot, each routed expert *that at least one token of the step chose*
(``experts_hit``: the mean over expert layers and steps, from the
program's ``moe.experts_hit / moe.layer_steps``), and the latent cache
rows of every live token of every layer. Writes (one cache row a slot a
layer, the logits) are thousands of times smaller and left out, and an
expert the program reads without a token for it is not counted, so the
count is a floor: the share it gives errs low, never over 100 %."""

from __future__ import annotations

from typing import Any, Dict

_ITEM = {"bfloat16": 2, "float32": 4}


def attention_params(hp: Dict[str, Any]) -> int:
    d, heads = hp["hidden_size"], hp["num_attention_heads"]
    q_rank, kv_rank = hp["q_lora_rank"], hp["kv_lora_rank"]
    nope, rope, v = (hp["qk_nope_head_dim"], hp["qk_rope_head_dim"],
                     hp["v_head_dim"])
    return (d * q_rank + q_rank                      # W_DQ, its norm
            + q_rank * heads * (nope + rope)         # W_UQ
            + d * (kv_rank + rope) + kv_rank         # W_DKV, its norm
            + kv_rank * heads * (nope + v)           # W_UK, W_UV
            + heads * v * d)                         # W_O


def expert_params(hp: Dict[str, Any]) -> int:
    return 3 * hp["hidden_size"] * hp["moe_intermediate_size"]


def cache_bytes_per_token(hp: Dict[str, Any],
                          precision: Dict[str, str]) -> int:
    """One latent row a token a layer: [c (kv_lora_rank); k_r (rope)]."""
    return (hp["num_hidden_layers"]
            * (hp["kv_lora_rank"] + hp["qk_rope_head_dim"])
            * _ITEM[precision["kv"]])


def fixed_params(hp: Dict[str, Any]) -> int:
    """Parameters a step reads whatever the router does."""
    d = hp["hidden_size"]
    layers, dense = hp["num_hidden_layers"], hp["first_k_dense_replace"]
    gains = 4 if hp["sandwich_norm"] else 2
    per_layer = attention_params(hp) + gains * d
    dense_ffn = 3 * d * hp["intermediate_size"]
    expert_layer = (d * hp["n_routed_experts_published"]      # the router
                    + hp["n_shared_experts"] * expert_params(hp))
    return (layers * per_layer + dense * dense_ffn
            + (layers - dense) * expert_layer
            + d + d * hp["vocab_size"])              # final norm, the head


def decode_step_bytes(hp: Dict[str, Any], precision: Dict[str, str],
                      experts_hit: float, live_tokens: float) -> float:
    """``experts_hit``: held experts a step reads, a layer (at most
    ``n_routed_experts``, the number held); ``live_tokens``: tokens of
    context summed over the decoding slots."""
    w = _ITEM[precision["weights"]]
    expert_layers = hp["num_hidden_layers"] - hp["first_k_dense_replace"]
    rows = hp["engine"]["max_slots"] * hp["hidden_size"]     # embedding
    return ((fixed_params(hp) + rows
             + expert_layers * experts_hit * expert_params(hp)) * w
            + live_tokens * cache_bytes_per_token(hp, precision))
