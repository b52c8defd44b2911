"""The parameters, bytes and operations of the short-convolution /
expert family (``models/conv_moe.py``), for ``decode_step_roofline.lfm2``,
``attn_kernel_roofline.lfm2`` and ``prefill_attn_mxu_share.lfm2``.
Computed from the configuration's published sizes, never read from the
program; ``adapters/conv_moe.py`` makes the functions reachable to
``layers.read_roofline`` as ``decode_step_bytes_conv_moe``,
``attn_call_bytes_conv_moe`` and ``prefill_attn_flops_conv_moe``.

One decode step reads, once: every weight outside the routed experts
(the tied embedding once: it is the head; the routers and their float32
biases), one embedding row a slot, the routed experts the step's tokens
hit (``experts_hit`` a layer: the program's ``moe.experts_hit /
moe.layer_steps``), an attention layer's K and V rows under
``cache_len`` of the live slots, once (``attn_rows`` a layer:
``attn.rows_live / attn.calls``), and a convolution layer's window of
the live slots, read *and written* (``conv_rows`` a layer:
``conv.rows_live / conv.layer_steps``). The program reads and writes
every slot's window, live or not; the new K/V rows and the logits are
left out: the count is a floor, the share it gives errs low, never over
100 %.

One call of the ragged kernel (one attention layer of one step) has to
read the live slots' K and V rows, once each: ``attn_call_bytes``. The
query rows, the new token's K/V and the output are some hundred KB
beside megabytes of rows, and left out."""

from __future__ import annotations

from typing import Any, Dict

_ITEM = {"bfloat16": 2, "float32": 4}


def layer_kinds(hp: Dict[str, Any]) -> Dict[str, int]:
    """Layers of each mixer and each feed-forward kind."""
    types = hp["layer_types"][:hp["num_hidden_layers"]]
    dense = hp["num_dense_layers"]
    return {"conv": types.count("conv"),
            "attn": types.count("full_attention"),
            "dense": dense, "moe": len(types) - dense}


def head_dim(hp: Dict[str, Any]) -> int:
    return hp["hidden_size"] // hp["num_attention_heads"]


def conv_params(hp: Dict[str, Any]) -> int:
    """A short-convolution mixer: in_proj (B, C, x~), out_proj, the
    depthwise taps (no bias)."""
    d = hp["hidden_size"]
    return d * 3 * d + d * d + hp["conv_L_cache"] * d


def attention_params(hp: Dict[str, Any]) -> int:
    """q, k, v, o and the two QK-norm gains."""
    d, width = hp["hidden_size"], head_dim(hp)
    q = hp["num_attention_heads"] * width
    kv = hp["num_key_value_heads"] * width
    return 2 * d * q + 2 * d * kv + 2 * width


def expert_params(hp: Dict[str, Any]) -> int:
    return 3 * hp["hidden_size"] * hp["moe_intermediate_size"]


def total_params(hp: Dict[str, Any]) -> int:
    """Every layer with its two gains, the routers and their biases, the
    final norm, the tied embedding."""
    d, n = hp["hidden_size"], layer_kinds(hp)
    return (n["conv"] * conv_params(hp) + n["attn"] * attention_params(hp)
            + n["dense"] * 3 * d * hp["intermediate_size"]
            + n["moe"] * (hp["num_experts"] * (expert_params(hp) + d + 1))
            + 2 * hp["num_hidden_layers"] * d + d + hp["vocab_size"] * d)


def kv_bytes_per_token_layer(hp: Dict[str, Any],
                             precision: Dict[str, str]) -> int:
    return (2 * hp["num_key_value_heads"] * head_dim(hp)
            * _ITEM[precision["kv"]])


def window_bytes_per_slot_layer(hp: Dict[str, Any],
                                precision: Dict[str, str]) -> int:
    return ((hp["conv_L_cache"] - 1) * hp["hidden_size"]
            * _ITEM[precision["conv"]])


def decode_step_bytes(hp: Dict[str, Any], precision: Dict[str, str],
                      experts_hit: float, conv_rows: float,
                      attn_rows: float) -> float:
    """``experts_hit``: routed experts a step's tokens hit, a layer;
    ``conv_rows``: live slots a convolution layer's step; ``attn_rows``:
    cached rows of the live slots an attention layer's step."""
    n, d = layer_kinds(hp), hp["hidden_size"]
    w = _ITEM[precision["weights"]]
    routed = n["moe"] * hp["num_experts"] * expert_params(hp)
    biases = n["moe"] * hp["num_experts"] * (4 - w)    # float32, not w
    rows = hp["engine"]["max_slots"] * d                # embedding rows
    return ((total_params(hp) - routed + rows) * w + biases
            + n["moe"] * experts_hit * expert_params(hp) * w
            + n["conv"] * conv_rows * 2
            * window_bytes_per_slot_layer(hp, precision)
            + n["attn"] * attn_rows * kv_bytes_per_token_layer(hp, precision))


def attn_call_bytes(hp: Dict[str, Any], precision: Dict[str, str],
                    rows_live: float) -> float:
    """``rows_live``: cached rows of the live slots a call."""
    return rows_live * kv_bytes_per_token_layer(hp, precision)


def prefill_attn_flops(hp: Dict[str, Any], precision: Dict[str, str],
                       row_tokens: float, rows: float) -> float:
    """Floating-point operations one call of the prefill's flash kernel
    (one attention layer of one admission group) has to do: a product
    over the keys' head of 64 and one over the values' a (query, key)
    pair a query head, over the causal half ``t (t + 1) / 2`` of each of
    the group's ``rows`` prompts. ``row_tokens`` is the *mean* bucket a
    row ran in and ``rows`` the mean rows a group; the pairs grow with
    the square, so the pairs of the mean length are no more than the
    mean pairs. The zero lanes that pad each head to 128 (half of every
    product the kernel runs) and the diagonal blocks' upper half are not
    counted: a floor."""
    pairs = row_tokens * (row_tokens + 1) / 2
    return rows * pairs * hp["num_attention_heads"] * 2 * 2 * head_dim(hp)
