"""The parameters and bytes of the sparse-attention member of the latent
family (``models/dsa_mla_moe.py``), for ``decode_step_roofline.dots``
and ``index_kernel_roofline.dots``. Computed from the configuration's
published sizes, never read from the program; ``adapters/dsa_mla_moe.py``
makes the functions reachable to ``layers.read_roofline`` as
``decode_step_bytes_dsa_mla_moe`` and ``index_call_bytes_dsa_mla_moe``.

One decode step reads, once: every weight outside the routed experts
(both kinds' attention with the indexer and the gates, the dense FFN,
the routers, the shared experts, the head's slice of the vocabulary),
one embedding row a slot, the routed experts its tokens hit
(``experts_hit`` a layer, the program's ``moe.experts_hit /
moe.layer_steps``), and of the cache: a full layer's live index keys
(``rows_live``, ``dsa.rows_live / dsa.calls``) and the latent rows it
selected (``rows_selected``), a sliding layer's rows in its window
(``window_rows``, ``attn.window_rows / dsa.calls``: a step makes one
call a full layer, so that is a step's window rows over the full
layers' count). The latent rows are read at their padded width. The new
rows, the scores and the logits are left out: the count is a floor, and
the share it gives errs low, never over 100 %.

One call of the index kernel (one full layer of one decode step) reads
the live index keys of the active slots (``rows_live``), the queries and
weights of every slot, and writes one float32 score a position of every
slot's table.
"""

from __future__ import annotations

from typing import Any, Dict

_ITEM = {"bfloat16": 2, "float32": 4}
_LANES = 128


def _padded(width: int) -> int:
    return -(-width // _LANES) * _LANES


def kinds(hp: Dict[str, Any]) -> Dict[str, int]:
    layers = hp["layer_types"][:hp["num_hidden_layers"]]
    full = sum(1 for name in layers if name == "full_attention")
    return {"full": full, "window": len(layers) - full}


def attention_params(hp: Dict[str, Any], kind: str) -> int:
    """A layer's latent attention, its gate, and a full layer's
    indexer."""
    swa = "swa_" if kind == "window" else ""
    d = hp["hidden_size"]
    heads = hp[f"{swa}num_attention_heads"]
    q_rank, kv_rank = hp[f"{swa}q_lora_rank"], hp[f"{swa}kv_lora_rank"]
    nope, rope = hp[f"{swa}qk_nope_head_dim"], hp[f"{swa}qk_rope_head_dim"]
    v = hp[f"{swa}v_head_dim"]
    out = (d * q_rank + q_rank + q_rank * heads * (nope + rope)
           + d * (kv_rank + rope) + kv_rank + heads * kv_rank * (nope + v)
           + heads * v * d + d * heads)
    if kind == "full":
        i_heads, i_dim = hp["index_n_heads"], hp["index_head_dim"]
        out += q_rank * i_heads * i_dim + d * i_dim + 2 * i_dim + d * i_heads
    return out


def expert_params(hp: Dict[str, Any]) -> int:
    return 3 * hp["hidden_size"] * hp["moe_intermediate_size"]


def resident_params(hp: Dict[str, Any]) -> int:
    """Every parameter a step reads whatever its tokens chose: all but
    the routed experts (the head's slice once, the embedding's rows
    apart)."""
    d, count = hp["hidden_size"], kinds(hp)
    layers = hp["num_hidden_layers"]
    dense = hp["first_k_dense_replace"]
    moe = layers - dense
    return (count["full"] * attention_params(hp, "full")
            + count["window"] * attention_params(hp, "window")
            + dense * 3 * d * hp["intermediate_size"]
            + moe * (d * hp["n_routed_experts_published"]
                     + hp["n_shared_experts"] * expert_params(hp))
            + 2 * layers * d + d + d * hp["vocab_size"])


def row_bytes(hp: Dict[str, Any], kind: str) -> int:
    swa = "swa_" if kind == "window" else ""
    return _padded(hp[f"{swa}kv_lora_rank"] + hp[f"{swa}qk_rope_head_dim"]) \
        * _ITEM[hp["precision"]["kv"]]


def decode_step_bytes(hp: Dict[str, Any], precision: Dict[str, str],
                      experts_hit: float, rows_live: float,
                      rows_selected: float, window_rows: float) -> float:
    count = kinds(hp)
    w = _ITEM[precision["weights"]]
    moe = hp["num_hidden_layers"] - hp["first_k_dense_replace"]
    key = hp["index_head_dim"] * _ITEM[precision["index_keys"]]
    embedding = hp["engine"]["max_slots"] * hp["hidden_size"] * w
    return (resident_params(hp) * w + embedding
            + moe * experts_hit * expert_params(hp) * w
            + count["full"] * (rows_live * key
                               + rows_selected * row_bytes(hp, "full"))
            + count["full"] * window_rows * row_bytes(hp, "window"))


def index_call_bytes(hp: Dict[str, Any], precision: Dict[str, str],
                     rows_live: float) -> float:
    slots = hp["engine"]["max_slots"]
    heads, dim = hp["index_n_heads"], hp["index_head_dim"]
    key = _ITEM[precision["index_keys"]]
    return (rows_live * dim * key
            + slots * heads * (dim * _ITEM[precision["activations"]] + 4)
            + slots * hp["engine"]["max_len"] * 4)
