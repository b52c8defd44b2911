"""TPU-first neural-net ops: norms, rotary embeddings, attention.

These back the servable model zoo (gofr_tpu.models) required by the
north star (BASELINE.json); the Go reference has no compute ops at all
(SURVEY.md §2.7 "there are none").
"""

from gofr_tpu.ops.attention import (
    attention,
    banded_attention,
    causal_mask,
    decode_attention,
    decode_attention_cached,
    gather_kv_pages,
    paged_decode_attention,
    paged_verify_attention,
    prefill_attention,
    prefix_prefill_attention,
    verify_attention,
)
from gofr_tpu.ops.norms import layer_norm, rms_norm
from gofr_tpu.ops.rotary import apply_rope, rope_table

__all__ = [
    "attention", "banded_attention", "causal_mask", "decode_attention", "prefill_attention",
    "prefix_prefill_attention", "gather_kv_pages", "paged_decode_attention",
    "verify_attention", "paged_verify_attention",
    "layer_norm", "rms_norm", "apply_rope", "rope_table",
]
