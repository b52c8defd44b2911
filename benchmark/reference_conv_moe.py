"""The plain reference for the short-convolution / expert family
(``gofr_tpu/models/conv_moe.py``): LFM2-8B-A1B (``lfm2_moe``) as the
catalog row gives it (huggingface.co/LiquidAI/LFM2-8B-A1B, config.json).

``forward_logits``: one sequence, no cache, no kernel, no batching, no
padding, float32 throughout at ``highest`` matmul precision (on a TPU a
float32 matmul otherwise runs in bfloat16 passes). The convolution is
its explicit causal sum over the sequence. Nothing is imported from the
program; only its parameter tree is read (a kind's layers stacked in
model order), a layer at a time and an expert at a time (a ``lax.scan``
over a layer's experts casts one expert's matrices to float32, 44 MB at
the published widths), and attention goes in blocks of query rows, so
the (heads, S, S) scores never exist.

A layer is ``h = x + mixer(norm(x)); out = h + ffn(norm(h))``, ``norm``
RMSNorm with a gain, ``eps = norm_eps``; after the last layer the final
norm and the tied head. Layer ``i`` is a short convolution where
``layer_types[i]`` is ``conv``, attention where it is
``full_attention``; its feed-forward is a SwiGLU of
``intermediate_size`` for ``i < num_dense_layers``, the experts after.

- Short convolution (``L = conv_L_cache``): ``[B; C; x~] = W_in u``,
  ``v_t = B_t * x~_t``, ``w_t = sum_{j=0..L-1} k_j * v_{t-L+1+j}``
  (``v_{<0} = 0``), ``out = W_out (C * w)``.
- Attention: ``q = W_q u`` (``num_attention_heads`` x 64), ``k``, ``v``
  (``num_key_value_heads``); ``q`` and ``k`` through an RMSNorm over a
  head's 64 lanes with a gain, then the half-split rotary at
  ``rope_theta``; causal softmax at ``64 ** -0.5``; ``W_o``.
- Experts: ``s = sigmoid(W_r u)`` over ``num_experts``; the
  ``num_experts_per_tok`` largest of ``s + b`` (``use_expert_bias``),
  weighed ``s_e / (sum s + 1e-6)`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``sum_e w_e SwiGLU_e(u)`` of
  ``moe_intermediate_size``; no shared expert.

Departures, all under ``assumed`` in the configuration file: the head
tied to the embedding, head 64, the order B, C, x~ of ``in_proj``'s
split, QK-norm before the rotary, the 1e-6 of the normalisation, seeded
weights.

``given`` (expert layers, S, top_k) names the experts where it is not
negative, in place of this reference's own choice (the weights are
still its own scores of them): the forward a system with that routing
has to agree with to rounding. Every expert is held here, so a last bit
that flips a token's fourth-best expert moves its output by a quarter of
the routed part in any sound bfloat16 system: the probe holds the logits
to this reference *given the system's routing*, and the routing to its
own beside it (``adapters/conv_moe.py``).

Switches make a *control*, a system that ``adapters/conv_moe.py`` puts
in the program's place and that has to come out not correct. They are
operands, not constants, so that every control is one program:
``qk_norm`` 0 (q and k as the projections give them), ``bias`` 0 (the
experts chosen by ``s`` alone), ``b_gate`` / ``c_gate`` 0 (that gate
dropped: ``v = x~``, or ``out = W_out w``), ``router_bf16`` 1 (the
router's scores rounded through bfloat16), ``float8`` 1 (every weight
rounded through float8_e4m3fn, the nearest precision below bfloat16).

Tolerances, with their reasons: see ``LIMITS`` below.
"""

from __future__ import annotations

from typing import Any, Dict

# The served path computes in bfloat16 through 14 layers and routes in
# float32 on a bfloat16 hidden state. Two things move its logits off
# this float32 reference: rounding at every position, and routing: every
# expert is held, and a last bit of the hidden state flips a token's
# fourth expert in 27-29 % of (token, expert layer) pairs, which moves
# that token by a quarter of its routed part in any sound system. So the
# probe gives this reference the system's own routing and holds the
# logits to rounding; the routing has its own limit. Each limit lies
# between the sound runs' readings (the program on a TPU v5e, six
# seeds: the adapter's main() and five runs of the cell; PERF.md keeps
# the runs) and the controls' (main(), one process, seed 2718281828),
# with room on both sides; each control fails by one limit at least.
# P = the prompt's padding not masked (the window taken at the bucket's
# end), W0 = the window not carried into decode, ST = a claimed slot
# keeping its last tenant's window, Q = QK-norm dropped, B = the
# router's bias ignored, GB / GC = the B / C gate dropped, R16 =
# bfloat16 router scores, F8 = float8 weights.
#  - logits_rel_l2_q1: the lower quartile of the probe's 18 positions
#    (two rows of one 2048 bucket, 1536 and 700 tokens, the prefill's
#    last position and eight paged decode steps through the packed
#    kernel) against this reference given the program's routing.
#    Sound 0.0386-0.0439; Q 0.448, W0 0.653, ST 0.684, P 0.749, F8
#    1.297, GC 1.401, GB 1.406 (B and R16 read 0.0 and 0.001: the same
#    forward given their own routing).
#  - logits_rel_l2_max: no position far over rounding. Sound 0.0449-
#    0.0508; Q 0.579, F8 1.360, P 1.371, W0 1.375, ST 1.429, GB 1.434,
#    GC 1.473.
#  - route_swap_share: (token, expert layer) pairs of the probe routed
#    otherwise than this reference routes for itself. Sound 0.268-
#    0.288; B 0.556, Q 0.819, F8 0.999, GB and GC 1.0 (R16 0.173 and
#    the window controls 0.289 pass it).
#  - router_swap_share: the router alone, the program's and this one's
#    on one bfloat16 input (512 rows, every expert layer): rows whose
#    chosen set differs. The same float32 operations: sound 0.0 on
#    every seed; R16 0.0265, B 0.272. The only limit R16 fails.
#  - served_argmax_share_min: the served check's tokens (312-360 of 11
#    requests) teacher-forced through this reference routing for
#    itself: the share that are its choice. Sound 0.564-0.673 (rounding
#    and the routing it flips); B 0.352, Q 0.087, GB, GC and F8 0.0
#    (R16 0.686 passes).
#  - served_margin_p99: how far below the reference's choice a served
#    token lies, in standard deviations of that position's logits,
#    exceeded by one token in a hundred. Sound 0.706-1.125; Q 3.02, F8
#    6.47, GC 6.63, GB 6.89 (B 1.28 and R16 0.64 pass it).
# SW0 / SST = the engine's own insert writing zeros / the claimed rows'
# last tenant's window on the served path: the served check decides
# them alone. Not read on the chip yet; the CPU rehearsal (tiny, 12
# tokens) reads served_argmax_share 0.25 / 0.25 and served_margin_p99
# 2.33 / 4.37, failing both served limits.
LIMITS = {"logits_rel_l2_q1": 0.1, "logits_rel_l2_max": 0.15,
          "route_swap_share": 0.4, "router_swap_share": 0.01,
          "served_argmax_share_min": 0.45, "served_margin_p99": 2.0}

ROW_BLOCK = 256          # query rows a block of an attention layer holds
SOUND = {"qk_norm": 1.0, "bias": 1.0, "b_gate": 1.0, "c_gate": 1.0,
         "router_bf16": 0.0, "float8": 0.0}


def _round(x, dtype):
    """``x`` float32 through ``dtype``'s exponent and mantissa. Not
    ``astype`` there and back: on a TPU the compiler may keep the excess
    precision of such a pair."""
    import jax.numpy as jnp
    from jax import lax

    info = jnp.finfo(dtype)
    return lax.reduce_precision(x, info.nexp, info.nmant)


def route(router, h, hp: Dict[str, Any], bias=None, given=None,
          bf16_scores=None):
    """The router on ``h`` (S, D) float32: (experts chosen (S, top_k),
    their weights). ``bias`` (E,): the experts are the largest of ``s +
    bias`` and weighed by ``s`` alone. ``given`` (S, top_k): the experts
    named where not negative. ``bf16_scores`` (a 0/1 operand): the
    scores rounded through bfloat16."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    scores = jax.nn.sigmoid(h @ router.astype(jnp.float32))
    if bf16_scores is not None:
        scores = jnp.where(bf16_scores > 0,
                           _round(scores, jnp.bfloat16), scores)
    ranked = scores if bias is None else scores + bias.astype(jnp.float32)
    _, ids = lax.top_k(ranked, hp["num_experts_per_tok"])
    if given is not None:
        ids = jnp.where(given >= 0, given, ids)
    top = jnp.take_along_axis(scores, ids, -1)
    if hp["norm_topk_prob"]:
        top = top / (top.sum(-1, keepdims=True) + 1e-6)
    return ids, top * hp["routed_scaling_factor"]


def forward_logits(params: Dict[str, Any], hp: Dict[str, Any], tokens,
                   positions, given=None, switches=None):
    """(logits (P, vocab) at ``positions`` (P,) of ``tokens`` (S,) int32,
    the experts chosen (expert layers, S, top_k)). ``hp`` holds the
    published keys of the configuration file; ``switches`` the operands
    of ``SOUND`` (a control's). Attention and the convolution are causal,
    so what follows a position (padding to a fixed S, say) does not move
    its logits."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    sw = dict(SOUND, **(switches or {}))
    heads, kv_heads = hp["num_attention_heads"], hp["num_key_value_heads"]
    d_model = hp["hidden_size"]
    d_head = d_model // heads
    group = heads // kv_heads
    eps, taps = hp["norm_eps"], hp["conv_L_cache"]
    seq = tokens.shape[0]
    block = min(ROW_BLOCK, seq)
    padded = -(-seq // block) * block
    tokens = jnp.pad(tokens, (0, padded - seq))
    every = jnp.arange(padded)

    def w_of(leaf):
        leaf = leaf.astype(f32)
        return jnp.where(sw["float8"] > 0, _round(leaf, jnp.float8_e4m3fn),
                         leaf)

    def norm(x, gain):
        return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * w_of(gain)

    def swiglu(w, u):
        return (jax.nn.silu(u @ w_of(w["w_gate"])) * (u @ w_of(w["w_up"]))
                ) @ w_of(w["w_down"])

    # the half-split rotary of positions 0 .. padded - 1
    inv_freq = 1.0 / (hp["rope_theta"] ** (
        jnp.arange(0, d_head, 2, dtype=f32) / d_head))
    angles = every.astype(f32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]

    def rope(x):                                   # (S, heads, d_head)
        x1, x2 = x[..., :d_head // 2], x[..., d_head // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    def qk_norm(x, gain):
        return jnp.where(sw["qk_norm"] > 0, norm(x, gain), x)

    def attention(w, u):
        k = rope(qk_norm((u @ w_of(w["wk"])).reshape(padded, kv_heads,
                                                     d_head), w["k_norm"]))
        v = (u @ w_of(w["wv"])).reshape(padded, kv_heads, d_head)
        q = rope(qk_norm((u @ w_of(w["wq"])).reshape(padded, heads, d_head),
                         w["q_norm"]))

        def rows_block(args):
            q_blk, rows = args
            q_blk = q_blk.reshape(block, kv_heads, group, d_head)
            scores = jnp.einsum("qkgd,skd->kgqs", q_blk, k) * d_head ** -0.5
            scores = jnp.where(every[None, :] <= rows[:, None], scores,
                               -jnp.inf)
            out = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, -1),
                             v)
            return out.reshape(block, heads * d_head)

        out = lax.map(rows_block, (q.reshape(-1, block, heads, d_head),
                                   every.reshape(-1, block)))
        return out.reshape(padded, -1) @ w_of(w["wo"])

    def short_conv(w, u):
        bcx = u @ w_of(w["w_in"])
        b, c, x = (bcx[:, :d_model], bcx[:, d_model:2 * d_model],
                   bcx[:, 2 * d_model:])
        v = jnp.where(sw["b_gate"] > 0, b * x, x)
        taps_w = w_of(w["conv_w"])                       # (L, D)
        shifted = jnp.pad(v, ((taps - 1, 0), (0, 0)))
        conv = sum(taps_w[j] * shifted[j:j + padded] for j in range(taps))
        return jnp.where(sw["c_gate"] > 0, c * conv, conv) @ w_of(w["w_out"])

    def experts(j, u, given_l):
        w = params["moe"]
        ids, weight = route(w["router"][j], u, hp,
                            sw["bias"] * w["router_bias"][j].astype(f32),
                            given_l, sw["router_bf16"])

        def one(y, e):
            # one expert's matrices read out of the whole stack: a
            # layer's slice of it would be a copy of all 32
            expert = {name: lax.dynamic_index_in_dim(
                lax.dynamic_index_in_dim(leaf, j, 0, False), e, 0, False)
                for name, leaf in w["experts"].items()}
            share = jnp.where(ids == e, weight, 0.0).sum(-1)     # (S,)
            return y + share[:, None] * swiglu(expert, u), None

        y, _ = lax.scan(one, jnp.zeros_like(u),
                        jnp.arange(hp["num_experts"]))
        return y, ids

    def at(kind, i):
        return jax.tree.map(lambda leaf: leaf[i], params[kind])

    kinds = hp["layer_types"][:hp["num_hidden_layers"]]
    n_dense = hp["num_dense_layers"]
    seen = {"conv": 0, "full_attention": 0}
    chosen = []
    with jax.default_matmul_precision("highest"):
        embedding = w_of(params["tok_emb"])
        x = embedding[tokens]
        for i, kind in enumerate(kinds):
            mixer = "conv" if kind == "conv" else "attn"
            w = at(mixer, seen[kind])
            seen[kind] += 1
            mix = short_conv if kind == "conv" else attention
            x = x + mix(w, norm(x, w["norm1"]))
            if i < n_dense:
                w = at("dense", i)
                x = x + swiglu(w, norm(x, w["norm2"]))
                continue
            j = i - n_dense
            given_l = None
            if given is not None:
                given_l = jnp.pad(given[j], ((0, padded - given.shape[1]),
                                             (0, 0)), constant_values=-1)
            y, ids = experts(j, norm(x, params["moe"]["norm2"][j]), given_l)
            chosen.append(ids[:seq])
            x = x + y
        logits = norm(x[positions], params["out_norm"]) @ embedding.T
    return logits, (jnp.stack(chosen) if chosen else
                    jnp.zeros((0, seq, hp["num_experts_per_tok"]),
                              jnp.int32))


def rel_l2(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))
