"""The parameters and bytes of the state-space / attention family
(``models/jamba.py``), for ``decode_step_roofline.jamba`` and
``scan_kernel_roofline.jamba``. Computed from the configuration's
published sizes, never read from the program; ``adapters/jamba.py`` makes
the functions reachable to ``layers.read_roofline`` as
``decode_step_bytes_jamba`` and ``scan_call_bytes_jamba``.

One decode step reads, once: every weight (the tied embedding once: it
is the head), one embedding row a slot, and of the cache what the live
slots need: a state-space layer's ``h`` (float32) and conv window, read
*and written* (the recurrence rewrites both every step), ``ssm_rows``
rows a layer (the program's ``ssm.rows_live / ssm.layer_steps``); an
attention layer's K and V rows under ``cache_len`` of the live slots,
once, ``attn_rows`` a layer (``attn.rows_live / attn.calls``). The
program reads and writes every slot's state, live or not, and gathers
whole table widths; the new K/V rows and the logits are left out: the
count is a floor, the share it gives errs low, never over 100 %.

One call of the scan kernel (one state-space layer of one admission
group) has to read ``x'`` (the activations' type), ``D_t`` (float32),
``B_t`` and ``C_t`` (float32), and write ``y`` (float32) a token, and
write the final state a row. ``a``, ``d`` and the zero initial state are
left out. The kernel's own bound is the vector unit (an exponential and
a handful of multiply-adds a (state, channel) pair a token), for which
``peaks.json`` has no published number: the share of the HBM roofline
says how far the scan is from free, and stays well under 100 %."""

from __future__ import annotations

from typing import Any, Dict

_ITEM = {"bfloat16": 2, "float32": 4}


def channels(hp: Dict[str, Any]) -> int:
    return hp["mamba_expand"] * hp["hidden_size"]


def mlp_params(hp: Dict[str, Any]) -> int:
    return 3 * hp["hidden_size"] * hp["intermediate_size"]


def mixer_params(hp: Dict[str, Any]) -> int:
    """A state-space mixer: in_proj, out_proj, x_proj, dt_proj and its
    bias, the conv and its bias, A_log, D, the three inner norms."""
    d, c = hp["hidden_size"], channels(hp)
    n, r = hp["mamba_d_state"], hp["mamba_dt_rank"]
    return (d * 2 * c + c * d + c * (r + 2 * n) + r * c + c
            + hp["mamba_d_conv"] * c + c + c * n + c + r + 2 * n)


def attention_params(hp: Dict[str, Any]) -> int:
    d, heads = hp["hidden_size"], hp["num_attention_heads"]
    width = d // heads
    return 2 * d * heads * width + 2 * d * hp["num_key_value_heads"] * width


def layer_counts(hp: Dict[str, Any]) -> Dict[str, int]:
    attn = sum(1 for i in range(hp["num_hidden_layers"])
               if i % hp["attn_layer_period"] == hp["attn_layer_offset"])
    return {"attn": attn, "ssm": hp["num_hidden_layers"] - attn}


def total_params(hp: Dict[str, Any]) -> int:
    """Every layer with its two gains, the final norm, the tied
    embedding."""
    d, count = hp["hidden_size"], layer_counts(hp)
    return (count["ssm"] * (mixer_params(hp) + mlp_params(hp) + 2 * d)
            + count["attn"] * (attention_params(hp) + mlp_params(hp) + 2 * d)
            + d + hp["vocab_size"] * d)


def state_bytes_per_slot_layer(hp: Dict[str, Any],
                               precision: Dict[str, str]) -> int:
    """``h`` and the conv window of one slot in one state-space layer."""
    c = channels(hp)
    return (c * hp["mamba_d_state"] * _ITEM[precision["state"]]
            + (hp["mamba_d_conv"] - 1) * c * _ITEM[precision["conv"]])


def kv_bytes_per_token_layer(hp: Dict[str, Any],
                             precision: Dict[str, str]) -> int:
    width = hp["hidden_size"] // hp["num_attention_heads"]
    return 2 * hp["num_key_value_heads"] * width * _ITEM[precision["kv"]]


def decode_step_bytes(hp: Dict[str, Any], precision: Dict[str, str],
                      ssm_rows: float, attn_rows: float) -> float:
    """``ssm_rows``: live slots a state-space layer's step; ``attn_rows``:
    cached rows of the live slots an attention layer's step."""
    count = layer_counts(hp)
    rows = hp["engine"]["max_slots"] * hp["hidden_size"]     # embedding
    return ((total_params(hp) + rows) * _ITEM[precision["weights"]]
            + count["ssm"] * ssm_rows * 2
            * state_bytes_per_slot_layer(hp, precision)
            + count["attn"] * attn_rows
            * kv_bytes_per_token_layer(hp, precision))


def scan_call_bytes(hp: Dict[str, Any], precision: Dict[str, str],
                    row_tokens: float, rows: float) -> float:
    """``row_tokens``: the mean bucket a row ran in (the program's
    ``prefill_bucket_tokens / prefill_rows``); ``rows``: the mean rows a
    group (``prefill_rows / prefill_batches``)."""
    c, n = channels(hp), hp["mamba_d_state"]
    f32 = _ITEM["float32"]
    a_token = c * (_ITEM[precision["activations"]] + 2 * f32) + 2 * n * f32
    return rows * (row_tokens * a_token + c * n * _ITEM[precision["state"]])
