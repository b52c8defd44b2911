"""The plain reference for the state-space / attention family
(``gofr_tpu/models/jamba.py``): AI21-Jamba2-3B (``jamba``) as the catalog
row gives it (huggingface.co/ai21labs/AI21-Jamba2-3B, config.json).

``forward_logits``: one sequence, no cache, no kernel, no batching, no
padding, float32 throughout at ``highest`` matmul precision (on a TPU a
float32 matmul otherwise runs in bfloat16 passes), the recurrence a
``lax.scan`` over the tokens. Nothing is imported from the program
(neither its module nor ``ops/ssm.py``); only its parameter tree is
read (a kind's layers stacked in model order), a layer at a time (a
``lax.scan`` over a run of layers of one kind casts one layer's weights
to float32 at a time, 0.4 GB at the published widths, beside the 6 GB
served copy), and attention goes in blocks of query rows, so the
(heads, S, S) scores never exist.

A layer is ``x = x + mixer(norm1(x)); x = x + mlp(norm2(x))``, ``norm``
RMSNorm with a gain, ``eps = rms_norm_eps``; ``mlp(u) = W_down
(silu(W_gate u) * (W_up u))``; after the last layer the final norm and
the tied head. Layer ``i`` is attention where ``i % attn_layer_period
== attn_layer_offset``, state space otherwise.

- Attention: ``q = W_q u`` (``num_attention_heads`` x ``head_dim``),
  ``k = W_k u``, ``v = W_v u`` (``num_key_value_heads``), no bias, no
  rotary, causal softmax at ``head_dim ** -0.5``, ``W_o``.
- State space (``C = mamba_expand * hidden_size`` channels, ``N =
  mamba_d_state``, ``R = mamba_dt_rank``):

      [x_t ; z_t] = W_in u_t
      x'_t = silu(b_conv + sum_{j=0..3} w_conv[:, j] * x_{t-3+j})   (x_{<0} = 0)
      [dt_t ; B_t ; C_t] = W_x x'_t
      dt_t = rmsnorm_dt(dt_t); B_t = rmsnorm_B(B_t); C_t = rmsnorm_C(C_t)
      D_t = softplus(W_dt dt_t + b_dt)
      h_t = exp(D_t[:, None] * A) * h_{t-1} + (D_t * x'_t)[:, None] * B_t[None, :]
      A = -exp(A_log),  h_{-1} = 0
      y_t = h_t C_t + D * x'_t;   out_t = W_out (y_t * silu(z_t))

  ``h`` is (C, N) here, the published order; the program keeps it
  (N, C) and its ``a_log`` with it, which this reads transposed.

Departures, all under ``assumed`` in the configuration file: the order
of the layer types (the family's rule), the inner norms with gains,
``dt_proj``'s bias, head 128, no rotary, seeded weights.

Switches make a *control*, a system that ``adapters/jamba.py`` puts in
the program's place and that has to come out not correct:
``inner_norms=False`` (``dt``, ``B``, ``C`` as ``W_x`` gives them),
``h_round_to`` (``h`` rounded through that type after every token:
``bfloat16`` is the published cache's type and the nearest below this
configuration's float32), ``round_to`` (every weight rounded through
that type: ``float8_e4m3fn`` is the nearest precision below bfloat16).
``recurrence`` is the scan alone, for the check that holds the
program's scan to float32 (``scan_rel_l2``).

Tolerances, with their reasons: see ``LIMITS`` below.
"""

from __future__ import annotations

from typing import Any, Dict

# The served path computes in bfloat16 through 28 layers around a float32
# recurrence; its logits leave this float32 reference by rounding alone:
# the program's own code in float32 at the published widths (one period,
# both kernels, prefill and paged decode steps) agrees with this file to
# 2.3e-6 .. 2.6e-6 relative L2 on the chip, in bfloat16 it reads
# 0.031 .. 0.037 over one period and 0.040 .. 0.053 over both, with the
# chunked XLA scan in the kernel's place the same (my chip runs, PR 37,
# `pr37_b`, `pr37_a`, `pr37_c`). That is ten times a dense model's 0.0046
# (`reference.py`): every state-space layer multiplies rounded values
# three deep (the step, the input and its projection; the output and its
# gate) where a dense layer adds them. Each limit lies between the
# largest reading of the sound runs over their seeds and the reading of a
# control (adapters/jamba.py, main(); PERF.md section 6, PR 37, has every
# reading). Controls: P, the prefill's padding not masked (the state at
# the bucket's end); W, the conv window taken from the bucket's end; H0,
# ``h`` not carried from prefill into decode; ST, a claimed slot keeping
# its last tenant's ``h``; NN, the inner norms left out; H16, ``h`` in
# bfloat16; F8, float8 weights. Each fails by one limit at least, not by
# each.
#
# The probe (two rows of one 2048 bucket, 1536 and 700 tokens, so 512
# and 1348 positions of padding lie behind the prompts; the prefill's
# last position and eight paged decode steps a row, 18 positions):
#  - logits_rel_l2_q1: the lower quartile of the 18: rounding's limit.
#    Sound 0.0426 .. 0.0469 over nine runs on nine seeds; H16 0.0104
#    (it passes: the logits forgive a bfloat16 state), H0 0.74 .. 0.78,
#    NN 0.80 .. 0.82, ST 0.81 .. 0.82, W 0.88 .. 0.96, P 1.32 .. 1.33,
#    F8 1.35.
#  - logits_rel_l2_max: no position far over rounding. Sound 0.048 ..
#    0.059; NN 1.06 .. 1.09, H0 1.11 .. 1.16, ST 1.14 .. 1.17, F8 1.38,
#    W 1.41, P 1.42 (H16 0.019).
# The scan alone (the program's prefill scan, the kernel on the chip,
# and this file's ``recurrence`` on one seeded input of the
# configuration's types, 512 tokens):
#  - scan_rel_l2: the outputs' and the final state's distance. Sound 0.0
#    on the chip (the kernel, the token-by-token oracle and this
#    recurrence agree bit for bit there; 1.2e-7 for the chunked form on
#    the CPU); H16 0.0061: ``h`` rounded at every token. The only
#    limit H16 fails.
# The served check (the timed path: 192 requests, every slot live, 64
# slots freed and claimed again, ~370 served tokens teacher-forced
# through this reference):
#  - served_argmax_share_min: share of served tokens that are float32's
#    choice. Sound 0.85 .. 0.93 (rounding of 4-5 % flips near ties of
#    seeded weights' logits; H16 against float32 0.988); NN 0.02 ..
#    0.04, F8 0.0.
#  - served_margin_p99: how far below float32's choice a served token
#    lies, in standard deviations of that position's logits, exceeded
#    by one token in a hundred. Sound 0.069 .. 0.112; NN 3.78 .. 3.94,
#    F8 6.26.
LIMITS = {"logits_rel_l2_q1": 0.15, "logits_rel_l2_max": 0.25,
          "scan_rel_l2": 1e-4,
          "served_argmax_share_min": 0.6, "served_margin_p99": 0.6}

ROW_BLOCK = 256          # query rows a block of an attention layer holds


def _snapper(round_to):
    """Round float32 values through ``round_to``'s exponent and mantissa.
    Not ``astype`` there and back: on a TPU the compiler may keep the
    excess precision of such a pair (my chip run, PR 37: a bfloat16 pair
    left every value as it was)."""
    import jax.numpy as jnp
    from jax import lax

    if round_to is None:
        return lambda x: x
    info = jnp.finfo(round_to)
    return lambda x: lax.reduce_precision(x, info.nexp, info.nmant)


def recurrence(x, dt, b, c, a, d, h_round_to=None):
    """The selective scan of one sequence, token by token, in float32:
    x, dt (S, C); b, c (S, N); a (C, N); d (C,). Returns (y (S, C), the
    last h (C, N)). ``h_round_to`` rounds ``h`` through a type after
    every token."""
    import jax.numpy as jnp
    from jax import lax

    snap = _snapper(h_round_to)

    def one(h, step):
        x_t, dt_t, b_t, c_t = step
        h = snap(jnp.exp(dt_t[:, None] * a) * h
                 + (dt_t * x_t)[:, None] * b_t[None, :])
        return h, h @ c_t + d * x_t

    h, y = lax.scan(one, jnp.zeros(a.shape, jnp.float32), (x, dt, b, c))
    return y, h


def forward_logits(params: Dict[str, Any], hp: Dict[str, Any], tokens,
                   positions, round_to=None, h_round_to=None,
                   inner_norms: bool = True):
    """Logits (P, vocab) at ``positions`` (P,) int32 of ``tokens`` (S,)
    int32. ``hp`` holds the published keys of the configuration file.
    Attention is causal and the recurrence runs forward, so what follows
    a position (padding to a fixed S, say) does not move its logits."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    heads, kv_heads = hp["num_attention_heads"], hp["num_key_value_heads"]
    d_head = hp["hidden_size"] // heads
    group = heads // kv_heads
    eps = hp["rms_norm_eps"]
    channels = hp["mamba_expand"] * hp["hidden_size"]
    n_state, rank = hp["mamba_d_state"], hp["mamba_dt_rank"]
    taps = hp["mamba_d_conv"]
    period, offset = hp["attn_layer_period"], hp["attn_layer_offset"]
    seq = tokens.shape[0]
    block = min(ROW_BLOCK, seq)
    padded = -(-seq // block) * block
    tokens = jnp.pad(tokens, (0, padded - seq))
    every = jnp.arange(padded)
    weight = _snapper(round_to)

    def w_of(leaf):
        return weight(leaf.astype(f32))

    def norm(x, gain):
        return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
            * gain.astype(f32)

    def mlp(w, u):
        return (jax.nn.silu(u @ w_of(w["w_gate"])) * (u @ w_of(w["w_up"]))
                ) @ w_of(w["w_down"])

    def attention(w, u):
        k = (u @ w_of(w["wk"])).reshape(padded, kv_heads, d_head)
        v = (u @ w_of(w["wv"])).reshape(padded, kv_heads, d_head)

        def rows_block(args):
            u_blk, rows = args
            q = (u_blk @ w_of(w["wq"])).reshape(block, kv_heads, group,
                                                d_head)
            scores = jnp.einsum("qkgd,skd->kgqs", q, k) * d_head ** -0.5
            scores = jnp.where(every[None, :] <= rows[:, None], scores,
                               -jnp.inf)
            out = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, -1),
                             v)
            return out.reshape(block, heads * d_head) @ w_of(w["wo"])

        out = lax.map(rows_block, (u.reshape(-1, block, u.shape[-1]),
                                   every.reshape(-1, block)))
        return out.reshape(padded, -1)

    def state_space(w, u):
        xz = u @ w_of(w["w_in"])
        x, z = xz[:, :channels], xz[:, channels:]
        shifted = jnp.pad(x, ((taps - 1, 0), (0, 0)))
        conv_w = w_of(w["conv_w"])                        # (taps, C)
        xc = jax.nn.silu(w_of(w["conv_b"]) + sum(
            conv_w[j] * shifted[j:j + padded] for j in range(taps)))
        low = xc @ w_of(w["w_x"])
        dt, b, c = (low[:, :rank], low[:, rank:rank + n_state],
                    low[:, rank + n_state:])
        if inner_norms:
            dt, b, c = (norm(dt, w["dt_norm"]), norm(b, w["b_norm"]),
                        norm(c, w["c_norm"]))
        dt = jax.nn.softplus(dt @ w_of(w["w_dt"]) + w["b_dt"].astype(f32))
        a = -jnp.exp(w["a_log"].astype(f32)).T            # (C, N)
        y, _ = recurrence(xc, dt, b, c, a, w["d"].astype(f32), h_round_to)
        return (y * jax.nn.silu(z)) @ w_of(w["w_out"])

    def layer(mixer):
        def one(x, w):
            x = x + mixer(w, norm(x, w["norm1"]))
            return x + mlp(w, norm(x, w["norm2"])), None
        return one

    def stack(kind, first, count):
        return jax.tree.map(lambda leaf: leaf[first:first + count],
                            params[kind])

    # a period: the state-space layers before its attention layer, that
    # layer, those after it; a kind's layers are stacked in model order
    per_period = period - 1
    with jax.default_matmul_precision("highest"):
        embedding = w_of(params["tok_emb"])
        x = embedding[tokens]
        for p in range(hp["num_hidden_layers"] // period):
            for kind, first, count in (
                    ("ssm", p * per_period, offset), ("attn", p, 1),
                    ("ssm", p * per_period + offset, per_period - offset)):
                if count:
                    x, _ = lax.scan(
                        layer(attention if kind == "attn" else state_space),
                        x, stack(kind, first, count))
        return norm(x[positions], params["out_norm"]) @ embedding.T


def rel_l2(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))
