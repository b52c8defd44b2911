"""Plain references the served outputs are held to. Nothing here uses a
kernel, a cache or batching, and nothing here is imported from the
program's model files except where said.

``llama_last_logits``: the decoder-only transformer of the Llama / Mistral
family as published (Touvron et al. 2023; Jiang et al. 2023, "Mistral
7B"): token embedding, then per layer RMSNorm -> grouped-query causal
attention with rotary embeddings (the half-split "rotate_half" form of
the published Hugging Face implementation) -> residual -> RMSNorm ->
SwiGLU -> residual, a final RMSNorm and the output head. float32
throughout at ``highest`` matmul precision (on a TPU a float32 matmul
otherwise runs in bfloat16 passes). int8 weights are dequantised
(q * scale) one layer at a time inside the scan, so a 7B model's float32
copy never exists. Departures from the publication: none in the
mathematics; the weights are the benchmark's seeded random ones.

``LOGITS_REL_L2_TOL``: the served path computes activations in bfloat16
(8 bits of mantissa, 2**-8 relative rounding) through 32 layers of
residual sums, which on random weights measured a relative L2 error of
the last-position logits of about 1e-2 against this float32 reference
(PERF.md, Findings). The tolerance is 3e-2: three times that, and under
what a lower precision than the configuration states would give (an fp8
activation path rounds 2**-4, sixteen times coarser; int4 weights move
every logit by tens of percent). A wrong mask, rotary phase, head
grouping or scale gives an error near 1.

``CLASSIFY_SCORE_TOL``: ResNet-50's served path is bfloat16 too; the
float32 reference is ``resnet.apply`` itself (the model file *is* the
plain form: ``lax`` convolutions, no kernel, cache or batching) run
unbatched in float32 at highest precision, so the reference shares the
model file and checks precision and the serving path around it (cast,
batching, padding, slicing), not the architecture. The returned score
must lie within 2e-2 of the largest reference logit's magnitude of the
reference's score for that label, and that label must be the reference's
top label or tie with it inside the same margin. Measured on the chip:
0.46e-2 (PR 24); a path rounding sixteen times coarser would not pass.
"""

from __future__ import annotations

from typing import Any, Dict

LOGITS_REL_L2_TOL = 3e-2
CLASSIFY_SCORE_TOL = 2e-2


def llama_last_logits(params: Dict[str, Any], hp: Dict[str, Any], tokens):
    """Logits (vocab,) after the last of ``tokens`` (S,) int32. ``hp``
    holds the published keys: hidden_size, num_attention_heads,
    num_key_value_heads, rope_theta, rms_norm_eps."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    heads, kv_heads = hp["num_attention_heads"], hp["num_key_value_heads"]
    head_dim = hp["hidden_size"] // heads
    eps, theta = hp["rms_norm_eps"], hp["rope_theta"]
    seq = tokens.shape[0]

    def dense(w):
        if isinstance(w, dict):                       # int8 + scale
            return w["q"].astype(f32) * w["s"].astype(f32)
        return w.astype(f32)

    def rms(x, gain):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * gain.astype(f32)

    inv_freq = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=f32)
                               / head_dim)
    angles = jnp.arange(seq, dtype=f32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]

    def rope(x):                                      # (S, H, D)
        half = head_dim // 2
        rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
        return x * cos + rotated * sin

    causal = jnp.tril(jnp.ones((seq, seq), bool))

    def layer(x, w):
        h = rms(x, w["attn_norm"])
        q = rope((h @ dense(w["wq"])).reshape(seq, heads, head_dim))
        k = rope((h @ dense(w["wk"])).reshape(seq, kv_heads, head_dim))
        v = (h @ dense(w["wv"])).reshape(seq, kv_heads, head_dim)
        k = jnp.repeat(k, heads // kv_heads, axis=1)  # grouped-query
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(f32(head_dim))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
        x = x + attn.reshape(seq, -1) @ dense(w["wo"])
        h = rms(x, w["ffn_norm"])
        gated = jax.nn.silu(h @ dense(w["w_gate"])) * (h @ dense(w["w_up"]))
        return x + gated @ dense(w["w_down"]), None

    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][tokens].astype(f32)
        x, _ = lax.scan(layer, x, params["layers"])
        last = rms(x[-1], params["out_norm"])
        return last @ dense(params["lm_head"])


def rel_l2(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))
