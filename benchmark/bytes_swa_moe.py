"""The parameters and bytes of the window/global attention + sparse-expert
family (``models/swa_moe.py``), for ``decode_step_roofline.cmda``,
``attn_kernel_roofline.cmda`` and ``prefill_attn_mxu_share.cmda``. Computed
from the configuration's published sizes, never read from the program;
``adapters/swa_moe.py`` makes the functions reachable to
``layers.read_roofline`` as ``decode_step_bytes_swa_moe``,
``attn_call_bytes_swa_moe`` and ``prefill_attn_flops_swa_moe``.

One decode step reads, once: every weight outside the routed experts
(attention's four matrices, the shared experts, the router, one norm a
layer, the final norm), the tied embedding's slice as the output head,
one embedding row a slot, each routed expert *that at least one token
of the step chose* (``experts_hit``: the mean over layers and steps,
from the program's ``moe.experts_hit / moe.layer_steps``), and the K
and V rows its attention has to read: a window layer's at most
``sliding_window`` rows a slot, a global layer's every row, counted by
the program's ``attn.window_rows`` / ``attn.full_rows`` (rows of one
layer call, summed) over ``attn.calls``. Writes (one K and V row a slot
a layer, the logits) are thousands of times smaller and left out, an
expert the program reads without a token for it is not counted, and K
is counted once although the kernel may stream it twice, so the count
is a floor: the share it gives errs low, never over 100 %."""

from __future__ import annotations

from typing import Any, Dict

_ITEM = {"bfloat16": 2, "float32": 4}


def attention_params(hp: Dict[str, Any]) -> int:
    d, width = hp["hidden_size"], hp["head_dim"]
    heads, kv_heads = hp["num_attention_heads"], hp["num_key_value_heads"]
    return (d * heads * width + 2 * d * kv_heads * width    # wq, wk, wv
            + heads * width * d)                             # wo


def expert_params(hp: Dict[str, Any]) -> int:
    return 3 * hp["hidden_size"] * hp["intermediate_size"]


def layer_fixed_params(hp: Dict[str, Any]) -> int:
    """A layer outside its routed experts: attention, the shared
    experts, the router (its published width), the norm's gain."""
    return (attention_params(hp)
            + hp["num_shared_experts"] * expert_params(hp)
            + hp["hidden_size"] * hp["num_experts_published"]
            + hp["hidden_size"])


def total_params(hp: Dict[str, Any]) -> int:
    """Everything this chip holds: the layers with their held experts,
    the final norm, the (tied) embedding's slice."""
    d = hp["hidden_size"]
    return (hp["num_hidden_layers"]
            * (layer_fixed_params(hp)
               + hp["num_experts"] * expert_params(hp))
            + d + hp["vocab_size"] * d)


def cache_bytes_per_token_layer(hp: Dict[str, Any],
                                precision: Dict[str, str]) -> int:
    """One K and one V row a token a layer."""
    return (2 * hp["num_key_value_heads"] * hp["head_dim"]
            * _ITEM[precision["kv"]])


def attn_call_bytes(hp: Dict[str, Any], precision: Dict[str, str],
                    window_rows: float, full_rows: float) -> float:
    """K and V bytes one call of the ragged kernel (one layer of one
    step) has to read, once each: ``window_rows`` / ``full_rows`` are
    the cached rows of the window / the global layers' calls over *all*
    calls (``attn.*_rows / attn.calls``), so their sum is the mean rows
    a call."""
    return (window_rows + full_rows) * cache_bytes_per_token_layer(
        hp, precision)


def decode_step_bytes(hp: Dict[str, Any], precision: Dict[str, str],
                      experts_hit: float, window_rows: float,
                      full_rows: float) -> float:
    """``experts_hit``: held experts a step reads, a layer (at most
    ``num_experts``, the number held); the rows as ``attn_call_bytes``
    takes them."""
    w = _ITEM[precision["weights"]]
    layers, d = hp["num_hidden_layers"], hp["hidden_size"]
    rows = hp["engine"]["max_slots"] * d                     # embedding
    return ((layers * layer_fixed_params(hp) + d + hp["vocab_size"] * d
             + rows + layers * experts_hit * expert_params(hp)) * w
            + layers * attn_call_bytes(hp, precision, window_rows,
                                       full_rows))


def band_pairs(tokens: float, window) -> float:
    """(query, key) pairs of one prompt of ``tokens`` positions inside
    the causal band: every ``s <= t``, or with a window the last
    ``window`` of them."""
    if window is None or tokens <= window:
        return tokens * (tokens + 1) / 2
    return window * (window + 1) / 2 + (tokens - window) * window


def prefill_attn_flops(hp: Dict[str, Any], precision: Dict[str, str],
                       row_tokens: float, rows: float) -> float:
    """Floating-point operations one call of the prefill's attention
    kernel (one layer of one admission group) has to do: two products of
    ``head_dim`` a (query, key) pair a query head, over the band of each
    of the group's ``rows`` prompts, a mean over the period's kinds.
    ``row_tokens`` is the *mean* bucket a row ran in (the program's
    ``prefill_bucket_tokens / prefill_rows``) and ``rows`` the mean rows
    a group; the band grows faster than the length, so the band of the
    mean length is no more than the mean band, and the blocks on the
    band's edges that the kernel computes whole are counted for their
    live pairs only: the count is a floor, the share errs low."""
    kinds = hp["layer_types"]
    pairs = sum(band_pairs(row_tokens, hp["sliding_window"]
                           if kind == "sliding_attention" else None)
                for kind in kinds) / len(kinds)
    return (rows * pairs * hp["num_attention_heads"]
            * 2 * 2 * hp["head_dim"])
