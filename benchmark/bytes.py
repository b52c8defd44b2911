"""The bytes a decode step has to move, for the roofline share. Computed
from the configuration's published sizes, never read from the program.

One decode step reads every matmul weight once (int8: one byte each, plus
its float32 per-channel scales), one embedding row a slot, and the keys
and values of every live token of every decoding slot. Writes (one new
key/value a slot, the logits) are thousands of times smaller and left
out, so the count is a floor: the roofline share it gives errs low, never
over 100 %."""

from __future__ import annotations

from typing import Any, Dict

_ITEM = {"int8": 1, "bfloat16": 2, "float32": 4}


def weight_bytes_per_step(hp: Dict[str, Any], precision: Dict[str, str]) -> int:
    d, f, layers = (hp["hidden_size"], hp["intermediate_size"],
                    hp["num_hidden_layers"])
    head_dim = d // hp["num_attention_heads"]
    qd = hp["num_attention_heads"] * head_dim
    kvd = hp["num_key_value_heads"] * head_dim
    w = _ITEM[precision["weights"]]
    per_layer = (d * qd + 2 * d * kvd + qd * d + 3 * d * f) * w
    scales = 0
    if precision["weights"] == "int8":     # one float32 per output channel
        scales = (qd + 2 * kvd + d + 2 * f + d) * 4
    head = d * hp["vocab_size"] * w + (hp["vocab_size"] * 4
                                       if precision["weights"] == "int8"
                                       else 0)
    norms = (2 * layers + 1) * d * _ITEM[precision["activations"]]
    return layers * (per_layer + scales) + head + norms


def kv_bytes_per_token(hp: Dict[str, Any], precision: Dict[str, str]) -> int:
    head_dim = hp["hidden_size"] // hp["num_attention_heads"]
    return (2 * hp["num_hidden_layers"] * hp["num_key_value_heads"]
            * head_dim * _ITEM[precision["kv"]])


def decode_step_bytes(hp: Dict[str, Any], precision: Dict[str, str],
                      live_tokens: float) -> float:
    """``live_tokens``: tokens of context summed over the decoding slots."""
    return (weight_bytes_per_step(hp, precision)
            + live_tokens * kv_bytes_per_token(hp, precision))
