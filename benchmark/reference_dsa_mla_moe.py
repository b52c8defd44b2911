"""The plain reference for the sparse-attention member of the latent-
attention / sparse-expert family (``gofr_tpu/models/dsa_mla_moe.py``):
dots-studio's dots3-note-prev as its published ``config.json`` gives it
(huggingface.co/dots-studio/dots3-note-prev, ``model_type``
``dots3_note``): DeepSeek-V3's latent attention and ``noaux_tc``
routing (arXiv:2412.19437) with DeepSeek-V3.2's sparse attention
indexer (DSA) in the full layers, a latent attention of its own widths
over a sliding window in the others, a headwise gate in every layer.

``forward_logits``: one sequence, no cache, no kernel, no batching, no
absorbed form, float32 throughout at ``highest`` matmul precision.
Written from the equations below, not from the program: nothing is
imported from ``gofr_tpu.models``; only the parameter tree is read, one
layer at a time, one head's scores at a time, one indexer head's
products at a time, one expert at a time, so the (heads, S, S) scores
of a 6152-token sequence never exist.

``RMS(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g``; ``LN(x; g, b)
= (x - mean(x)) / sqrt(var(x) + index_norm_eps) * g + b``; rotary
half-split (pair ``i`` with ``i + d/2``, frequency ``theta^(-2i/d)``):

- Layer: ``x = x + Attn(RMS(x; g_in))``, ``x = x + FFN(RMS(x;
  g_mlp))``; ``logits = RMS(x; g_out) W_head``.
- Attention of a kind (its own widths and ``theta``): ``c_q = RMS(W_DQ
  h)``, ``q = W_UQ c_q`` per head ``[q_n; q_r]``; ``[c_kv; k_r] = W_DKV
  h``, ``c = RMS(c_kv)``; rotary on ``q_r`` and the one ``k_r``; ``k_h
  = [W_UK,h c; k_r]``, ``v_h = W_UV,h c``; softmax over the keys the
  row may read of ``(q_h . k_h) / sqrt(qk_nope + qk_rope)``; ``o_h`` times
  ``sigmoid(h W_G)_h``; ``W_O`` on the concatenated heads. A sliding
  layer's row ``t`` reads ``t - sliding_window_size < s <= t``; a full
  layer's reads ``S_t``.
- Indexer (full layers): ``qI_j = W_IQ,j c_q`` (``index_n_heads`` of
  ``index_head_dim``), ``kI_s = LN(W_IK h_s)``, rotary (the full layers'
  ``theta``) on the first ``qk_rope_head_dim`` dimensions of both; ``w_j
  = (W_IW h)_j (index_n_heads index_head_dim)^-1/2``; ``I_{t,s} = sum_j
  w_j ReLU(qI_j . kI_s)``; ``S_t`` = the ``index_topk`` keys ``s <= t``
  with the largest ``I_{t,s}`` (``lax.top_k`` of the row: its order
  breaks a tie), every ``s <= t`` while there are fewer.
- FFN: SwiGLU in the leading dense layers; after them ``s = sigmoid(W_g
  h)``, ``T`` = the ``num_experts_per_tok`` largest of ``s + b_e``,
  ``w_e = routed_scaling_factor s_e / sum_{j in T} s_j``, ``y =
  Shared(h) + sum_{e in T, held} w_e Expert_e(h)`` (the chip's share:
  what absent experts would add is left out, in the program alike).

Departures from the publication, all under ``assumed`` in the
configuration file: ``apply_mla_qkv_lora_rescale`` is a constant on the
normed latents, folded into the seeded up-projections; no router groups;
the indexer's LayerNorm with a gain and a bias; bfloat16 index keys
with no Hadamard rotation and no float8 (an orthogonal rotation leaves
``qI . kI`` as it is); the gate read from ``h``; half-split rotary; no
multi-token prediction, no vision or audio tower; seeded weights.

*Controls*: switches that make another system of this reference, which
``adapters/dsa_mla_moe.py`` puts in the program's place and which has to
come out not correct: ``dense`` (every ``s <= t``: the selection
ignored), ``no_relu`` (the indexer's ReLU dropped), ``no_ik_norm``
(``kI`` not normed: gain and bias kept), ``no_window`` (the sliding
layers read every ``s <= t``), ``no_gates``, ``float8_weights`` (every
weight rounded through ``float8_e4m3fn`` by ``lax.reduce_precision``).
Each is a Python bool or a traced boolean scalar (``SWITCHES`` names
them in order), so one jitted program serves the sound forward and
every control.

Tolerances, with their reasons: see ``LIMITS`` below.
"""

from __future__ import annotations

from typing import Any, Dict

import reference_hc_mla_moe as _hc

rel_l2 = _hc.rel_l2

# forward_logits' control switches, in the order a flags operand gives
SWITCHES = ("dense", "no_relu", "no_ik_norm", "no_window", "no_gates",
            "float8_weights")

# The served path computes in bfloat16 through 6 layers and scores the
# indexer on bfloat16 keys and queries; the reference is float32
# throughout. The probe gives this reference *the system's own
# selection and routing* (as xing4-29b's probe is given its routing):
# a last bit of a bfloat16 index key that moves a key across a row's
# 2048th score changes which rows that position reads, in any sound
# system alike, so the logits are held to rounding and the selection to
# the reference's own beside it, by a share of overlap.
# Each limit lies between the largest reading of the sound runs and the
# reading of a control put in the program's place (adapters/
# dsa_mla_moe.py, main()); each control fails by one limit at least.
# Readings on a TPU v5e at the cell's size (PERF.md section 6): sound
# logits 0.0183-0.0193 (lower quartile) and 0.0206-0.0244 (largest),
# overlaps 0.9896-0.9897 (prompt) and 0.9827-0.9861 (decode); the
# controls' logits 0.130 / 0.156 (the window ignored) and above 0.95
# (gates dropped, float8 weights), their overlaps 0.5486-0.9697
# (prompt) and 0.3318-0.9507 (decode): kI not normed (0.9697 / 0.9507)
# is the one control the overlaps alone must catch, so they sit
# between it and the sound runs.
#  - logits_rel_l2_q1 / _max: the probe's nine positions (the last of a
#    6144-token prefill and 8 paged decode steps), the reference given
#    the program's selection and routing.
#  - select_overlap_min: a full layer's selected keys of a row that
#    selects (t >= index_topk) in common with the reference's own
#    selection, as a share of index_topk, averaged over the rows of a
#    layer (prompt and decode rows); the lowest layer.
#  - decode_overlap_min: the same over the decode steps' rows alone: a
#    decode step selects through the kernel and the pool, which a
#    stale key of a slot's last tenant moves and the prefill does not.
#  - router_swap_share: the router alone, bias and all.
#  - index_kernel_err: the index kernel against its gathered oracle on
#    the probe's pool, the largest difference over the scores' scale.
#  - served_*: the served check's tokens against this reference's own
#    choices, teacher-forced.
LIMITS = {"logits_rel_l2_q1": 0.06, "logits_rel_l2_max": 0.10,
          "select_overlap_min": 0.98, "decode_overlap_min": 0.97,
          "router_swap_share": 0.01, "index_kernel_err": 1e-3,
          "route_swap_share": 0.35,
          "served_argmax_share_min": 0.40, "served_margin_p99": 3.6,
          "served_request_share_min": 0.10, "served_margin_mean": 0.45}


def _rope(x, positions, theta: float):
    """Half-split rotary of ``x`` (S, ..., d) at ``positions`` (S,)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=f32) / d)
    angles = positions.astype(f32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d,)
    half = d // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos.reshape(shape) + rotated * sin.reshape(shape)


def forward_logits(params: Dict[str, Any], hp: Dict[str, Any], tokens,
                   positions=None, routes=None, selections=None,
                   dense=False, no_relu=False, no_ik_norm=False,
                   no_window=False,
                   no_gates=False, float8_weights=False):
    """Logits (P, vocab) at ``positions`` (P,) int32 of ``tokens`` (S,)
    int32 (the last position where None), the experts this reference's
    router chooses at every token (expert layers, S, top_k) int32 and
    each full layer's own selection (full layers, S, S) bool (every ``s
    <= t`` under ``dense``). ``hp`` holds the published keys of the
    configuration file. Attention is causal and everything else sees one
    token at a time, so what follows a position (padding to a fixed S)
    does not move its logits. ``routes`` (expert layers, S, top_k) names
    each token's experts where not negative, ``selections`` (full
    layers, S, S) bool the keys each full layer's rows read: the forward
    a system that routed and selected so has to agree with to rounding;
    the choices returned are still this reference's own, made on that
    forward's hidden states. A row of ``selections`` with no key set,
    and a token's ``routes`` of -1, read the reference's own choice
    there. The switches (``SWITCHES``) may be traced."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    seq = tokens.shape[0]
    eps = hp["rms_norm_eps"]
    kinds = ["full" if name == "full_attention" else "window"
             for name in hp["layer_types"]]
    pos = jnp.arange(seq)
    causal = pos[None, :] <= pos[:, None]

    def weight(w):
        w = w.astype(f32)
        kind = jnp.finfo(jnp.float8_e4m3fn)
        return jnp.where(float8_weights,
                         lax.reduce_precision(w, kind.nexp, kind.nmant), w)

    def rms(x, gain):
        return (x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
                * weight(gain))

    def widths(kind):
        swa = "swa_" if kind == "window" else ""
        return dict(heads=hp[f"{swa}num_attention_heads"],
                    nope=hp[f"{swa}qk_nope_head_dim"],
                    rope=hp[f"{swa}qk_rope_head_dim"],
                    v=hp[f"{swa}v_head_dim"],
                    rank=hp[f"{swa}kv_lora_rank"],
                    theta=float(hp[f"{swa}rope_theta"]))

    def index_selection(w, h, c_q):
        """(S, S) bool: the reference's own ``S_t`` of every row."""
        heads, dim = hp["index_n_heads"], hp["index_head_dim"]
        r, theta = hp["qk_rope_head_dim"], float(hp["rope_theta"])
        q = (c_q @ weight(w["w_iq"])).reshape(seq, heads, dim)
        q = jnp.concatenate([_rope(q[..., :r], pos, theta), q[..., r:]], -1)
        k = h @ weight(w["w_ik"])
        centred = k - k.mean(-1, keepdims=True)
        k = jnp.where(no_ik_norm, k, centred * lax.rsqrt(
            jnp.mean(centred * centred, -1, keepdims=True)
            + hp["index_norm_eps"]))
        k = k * weight(w["ik_norm"]) + weight(w["ik_bias"])
        k = jnp.concatenate([_rope(k[:, :r], pos, theta), k[:, r:]], -1)
        gains = (h @ weight(w["w_iw"])) * (heads * dim) ** -0.5   # (S, J)

        def one_head(total, args):
            q_j, g_j = args
            s = q_j @ k.T
            return total + g_j[:, None] * jnp.where(
                no_relu, s, jnp.maximum(s, 0.0)), None

        index, _ = lax.scan(one_head, jnp.zeros((seq, seq), f32),
                            (q.transpose(1, 0, 2), gains.T))
        top, ids = lax.top_k(jnp.where(causal, index, -jnp.inf),
                             min(hp["index_topk"], seq))
        return jnp.zeros((seq, seq), bool).at[pos[:, None], ids].set(
            top > -jnp.inf)

    def attention(w, h, kind, given):
        n = widths(kind)
        c_q = rms(h @ weight(w["w_dq"]), w["q_norm"])
        q_n = (c_q @ weight(w["w_uq_n"])).reshape(seq, n["heads"],
                                                  n["nope"])
        q_r = _rope((c_q @ weight(w["w_uq_r"])).reshape(
            seq, n["heads"], n["rope"]), pos, n["theta"])
        down = h @ weight(w["w_dkv"])
        c = rms(down[:, :n["rank"]], w["kv_norm"])
        k_r = _rope(down[:, n["rank"]:], pos, n["theta"])
        own = None
        if kind == "window":
            reads = causal & (no_window | (
                pos[None, :] > pos[:, None] - hp["sliding_window_size"]))
        else:
            own = jnp.where(dense, causal, index_selection(w, h, c_q))
            reads = own if given is None else jnp.where(
                given.any(-1, keepdims=True), given, own)
        scale = (n["nope"] + n["rope"]) ** -0.5

        def one_head(args):                 # one (S, S) of scores alive
            q_n, q_r, w_uk, w_uv = args
            k_n = c @ weight(w_uk)
            scores = (q_n @ k_n.T + q_r @ k_r.T) * scale
            scores = jnp.where(reads, scores, -jnp.inf)
            return jax.nn.softmax(scores, -1) @ (c @ weight(w_uv))

        out = lax.map(one_head, (q_n.transpose(1, 0, 2),
                                 q_r.transpose(1, 0, 2),
                                 w["w_uk"], w["w_uv"]))   # (H, S, d_v)
        gate = jax.nn.sigmoid(h @ weight(w["w_gate"])).T[..., None]
        out = out * jnp.where(no_gates, 1.0, gate)
        out = out.transpose(1, 0, 2).reshape(seq, n["heads"] * n["v"])
        return out @ weight(w["w_o"]), own

    def swiglu(w, h):
        gate = jax.nn.silu(h @ weight(w["w_gate"]))
        return (gate * (h @ weight(w["w_up"]))) @ weight(w["w_down"])

    def experts(w, h, given):
        own, _ = _hc.route(w["router"], h, hp, None, w["router_bias"])
        ids, gains = _hc.route(w["router"], h, hp, None, w["router_bias"],
                               given)

        def one_expert(y, expert_and_id):
            expert, e = expert_and_id
            mine = ((ids == e) * gains).sum(-1)         # 0 where not chosen
            return y + mine[:, None] * swiglu(expert, h), None

        held = hp["n_routed_experts"]
        first = hp.get("expert_parallel_rank", 0) * held
        y, _ = lax.scan(one_expert, jnp.zeros_like(h),
                        (w["experts"],
                         first + jnp.arange(held, dtype=ids.dtype)))
        return y + swiglu(w["shared"], h), own

    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"][tokens].astype(f32)
        chosen, picked = [], []
        full = moe = 0
        for i, kind in enumerate(kinds):
            w = params["layers"][i]
            given = None
            if kind == "full" and selections is not None:
                given = selections[full]
            out, own = attention(w["attn"], rms(x, w["in_norm"]), kind, given)
            if own is not None:
                picked.append(own)
                full += 1
            x = x + out
            h = rms(x, w["pre_mlp_norm"])
            if i < hp["first_k_dense_replace"]:
                x = x + swiglu(w, h)
            else:
                y, ids = experts(w, h, None if routes is None
                                 else routes[moe])
                x = x + y
                chosen.append(ids)
                moe += 1
        tail = rms(x[-1:] if positions is None else x[positions],
                   params["out_norm"])
        return (tail @ weight(params["lm_head"]), jnp.stack(chosen),
                jnp.stack(picked))
