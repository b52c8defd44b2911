"""Decoder of state-space (Mamba-1) layers around a few attention layers,
for /generate.

The family of AI21's Jamba (``jamba``): layer ``i`` is an attention layer
where ``i % attn_layer_period == attn_layer_offset``, a state-space layer
otherwise; every layer is ``x = x + mixer(norm1(x)); x = x + mlp(norm2(x))``
with RMSNorm (a gain), a SwiGLU block, then a final norm and the tied
head. What differs between members is a field of ``JambaConfig``; what no
member the repo has met varies is written into the code once (no
positional encoding at all, no projection bias, a conv bias, inner norms
with gains, dense blocks: ``num_experts`` 1), to become a field when a
member needs another.

Equations (``u`` a layer's normed input; ``C`` = ``d_inner`` channels,
``N`` = ``d_state``, ``R`` = ``dt_rank``):

- Attention: ``q = u W_q`` (``n_heads`` x ``head_dim``), ``k = u W_k``,
  ``v = u W_v`` (``n_kv_heads``), no bias, **no rotary**, causal softmax
  at ``head_dim ** -0.5``, ``W_o``.
- State space:

      [x_t ; z_t] = u_t W_in
      x'_t = silu(b_conv + sum_j w_conv[j] * x_{t-3+j})     (x_{<0} = 0)
      [dt_t ; B_t ; C_t] = x'_t W_x, each through its inner RMSNorm
      D_t = softplus(dt_t W_dt + b_dt)
      h_t = exp(D_t * A) * h_{t-1} + B_t (D_t * x'_t),  A = -exp(A_log)
      y_t = C_t . h_t + D * x'_t;   out_t = (y_t * silu(z_t)) W_out

  Weights, activations, the conv window and keys/values in ``dtype``;
  ``h``, ``A``, ``D``, ``D_t``, the softplus, the exponent, the inner
  norms and the recurrence in float32 (``ops/ssm.py``).

**The cache has two kinds of another nature** (``cache_leaves``): the
attention layers keep ``k`` and ``v`` a *token*, on pages; the
state-space layers keep a fixed-size state a *slot* (``per_slot``): ``h``
(N, C) float32, states-major (``ops/ssm.py`` says why), and ``conv``,
the last ``d_conv - 1`` inputs of the convolution, oldest first, as one
row of ``(d_conv - 1) * C``. The page pool keeps the second as
``(layers, max_slots, ...)`` arrays with no pages and no table. What the
serving contract (``docs/tpu/model-serving.md``) asks of such a kind:

- ``prefill`` returns each row's state *at its prompt's end*, whatever
  the bucket: the scan's steps are zero at positions ``>= lengths``
  (``ops.ssm.mask_steps``), and the conv window is gathered at
  ``lengths - 3 .. lengths - 1``. The engine's insert writes all of it
  into the claimed slots' rows.
- ``decode_step_paged`` rewrites the state of the rows that are
  ``active`` and leaves every other row bit for bit.

The parameters are stacked by kind, in model order: ``params["ssm"]``
over the state-space layers, ``params["attn"]`` over the attention
layers, as a kind's cache is. A forward is a ``lax.scan`` over the
periods whose body is a scan over the state-space layers before the
period's attention layer, that layer, and a scan over those after it,
each reading its layer out of the whole stack by its index: three layer
bodies a program instead of a period's fourteen (a fifth of the
compile time and of the program's size, which every bucket's and
rung's program pays: ``swa_moe.py`` stacks by position in its period of
four and unrolls that).
Prefill's scan is the Pallas kernel where Mosaic tiles the bucket
(``select.scan_tileable``), else ``ops.ssm.selective_scan_chunked``;
prefill's attention the flash kernel where it tiles, else
``ops.banded_attention``. A decode step's state-space layer runs
``dt_proj`` and its softplus, the recurrence on the layer's rows of the
whole ``h`` stack (in place) and the gate in the Pallas step kernel
where Mosaic tiles the slots (``select.step_tileable``), else
``ops.ssm.selective_step`` and the gate in XLA. Entry points: ``init``,
``init_cache``, ``prefill``, ``decode_step_paged``, ``cache_leaves``,
``STEP_COUNTERS``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from gofr_tpu.ops import (banded_attention, decode_attention_cached,
                          gather_kv_pages, rms_norm)
from gofr_tpu.ops.ssm import selective_scan_chunked, selective_step

# what a decode step counts: the state rows of the active slots and the
# rows the step read (every slot's: the state is one array a layer), a
# state-space layer; the cached K/V rows of the active slots and the
# rows the step read (the gathered view's, or the live rows through the
# ragged kernel), an attention layer; the layers; and the state-space
# layer steps that ran in the Pallas step kernel. Summed by the engine
# over a tick's steps
STEP_COUNTERS = ("ssm.rows_live", "ssm.rows_read", "ssm.layer_steps",
                 "attn.rows_live", "attn.rows_read", "attn.calls",
                 "ssm.kernel_steps")
_N_COUNTERS = len(STEP_COUNTERS)


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    dim: int = 2560
    n_layers: int = 28
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    n_heads: int = 20
    n_kv_heads: int = 1
    head_dim: int = 128
    ffn_dim: int = 8192
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    max_seq_len: int = 262144
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_layers % self.attn_layer_period:
            raise ValueError(
                f"n_layers {self.n_layers} is not a whole number of "
                f"periods of {self.attn_layer_period}")
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError("attn_layer_offset lies outside the period")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def period(self) -> Tuple[str, ...]:
        """The kind of each layer of one period: the family's rule."""
        return tuple("attn" if i == self.attn_layer_offset else "ssm"
                     for i in range(self.attn_layer_period))

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """The kind of each layer, in model order."""
        return self.period * (self.n_layers // self.attn_layer_period)

    def flash_block(self, seq_len: int) -> Optional[int]:
        """The block size the prefill's flash kernel runs a bucket in,
        or None where Mosaic cannot tile it (``ops.banded_attention``
        then; what ``attention_paths()`` reports a bucket by)."""
        from gofr_tpu.ops.pallas import flash_tileable
        block = 1024 if seq_len % 1024 == 0 else 512
        return block if flash_tileable(seq_len, self.head_dim, block,
                                       block) else None

    def scans_in_kernel(self, seq_len: int) -> bool:
        """Does a ``seq_len``-token bucket's scan run in the kernel
        (where Mosaic tiles it), or in the chunked XLA form?"""
        from gofr_tpu.ops.pallas import scan_tileable
        return scan_tileable(seq_len, self.d_inner, self.d_state)

    def steps_in_kernel(self, rows: int) -> bool:
        """Does a decode step of ``rows`` slots run its state-space
        layers in the step kernel (where Mosaic tiles it), or in the
        XLA form?"""
        from gofr_tpu.ops.pallas import step_tileable
        return step_tileable(self.d_inner, self.d_state, rows)


PRESETS: Dict[str, JambaConfig] = {
    # tiny: unit tests and the benchmark's CPU rehearsal; two periods
    "tiny": JambaConfig(
        vocab_size=256, dim=64, n_layers=8, attn_layer_period=4,
        attn_layer_offset=1, n_heads=4, n_kv_heads=1, head_dim=16,
        ffn_dim=128, d_state=16, dt_rank=8, max_seq_len=256),
    "jamba2_3b": JambaConfig(),
}


def config(preset: str = "tiny", **overrides) -> JambaConfig:
    return dataclasses.replace(PRESETS[preset], **overrides)


def cache_leaves(cfg: JambaConfig) -> Dict[str, Dict[str, Any]]:
    """What the cache holds, by layer kind, kinds in the order they first
    appear. ``attn``: what one *token* leaves in a layer, on pages.
    ``ssm`` (``per_slot``): what one *slot* holds in a layer whatever its
    context: name -> (shape, dtype); the pool keeps ``(layers, max_slots,
    *shape)`` and no pages."""
    tail = (cfg.n_kv_heads, cfg.head_dim)
    kinds = {
        "attn": {"layers": cfg.layer_types.count("attn"), "window": None,
                 "leaves": {"k": (tail, cfg.dtype), "v": (tail, cfg.dtype)}},
        "ssm": {"layers": cfg.layer_types.count("ssm"), "per_slot": True,
                "leaves": {
                    "h": ((cfg.d_state, cfg.d_inner), jnp.float32),
                    "conv": (((cfg.d_conv - 1) * cfg.d_inner,),
                             cfg.dtype)}}}
    return {kind: kinds[kind] for kind in dict.fromkeys(cfg.layer_types)}


# -- parameters ---------------------------------------------------------------

def init(cfg: JambaConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random parameters in the served types: matmul weights
    ~N(0, 1/fan_in), gains of one; ``a_log = log(1..N)`` a channel and
    ``d = 1`` (the published inits), ``b_dt`` the inverse softplus of
    log-uniform steps in [1e-3, 1e-1], so that a state keeps hundreds
    of tokens. Jitted with the key as an argument it is one program
    for every seed. ``ssm`` and ``attn`` hold their layers' leaves
    stacked in model order."""
    dt, d, c = cfg.dtype, cfg.dim, cfg.d_inner
    n, r = cfg.d_state, cfg.dt_rank
    count = iter(range(1 << 16))

    def normal(*shape):
        return jax.random.normal(jax.random.fold_in(key, next(count)),
                                 shape, jnp.float32)

    def dense(*shape):
        return (normal(*shape) / math.sqrt(shape[-2])).astype(dt)

    def layer(kind, p):
        out = {"norm1": jnp.ones((p, d), dt), "norm2": jnp.ones((p, d), dt),
               "w_gate": dense(p, d, cfg.ffn_dim),
               "w_up": dense(p, d, cfg.ffn_dim),
               "w_down": dense(p, cfg.ffn_dim, d)}
        if kind == "attn":
            out.update(wq=dense(p, d, cfg.n_heads * cfg.head_dim),
                       wk=dense(p, d, cfg.n_kv_heads * cfg.head_dim),
                       wv=dense(p, d, cfg.n_kv_heads * cfg.head_dim),
                       wo=dense(p, cfg.n_heads * cfg.head_dim, d))
            return out
        steps = jnp.exp(jax.random.uniform(
            jax.random.fold_in(key, next(count)), (p, c), jnp.float32,
            math.log(1e-3), math.log(1e-1)))
        out.update(
            w_in=dense(p, d, 2 * c),
            conv_w=(normal(p, cfg.d_conv, c)
                    / math.sqrt(cfg.d_conv)).astype(dt),
            conv_b=(0.1 * normal(p, c)).astype(dt),
            w_x=dense(p, c, r + 2 * n),
            dt_norm=jnp.ones((p, r), dt), b_norm=jnp.ones((p, n), dt),
            c_norm=jnp.ones((p, n), dt),
            w_dt=(0.5 * normal(p, r, c) / math.sqrt(r)).astype(dt),
            b_dt=steps + jnp.log(-jnp.expm1(-steps)),
            a_log=jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[
                    None, :, None], (p, n, c)),
            d=jnp.ones((p, c), jnp.float32),
            w_out=dense(p, c, d))
        return out

    return {"tok_emb": (normal(cfg.vocab_size, d) / math.sqrt(d)).astype(dt),
            "out_norm": jnp.ones((d,), dt),
            **{kind: layer(kind, cfg.layer_types.count(kind))
               for kind in dict.fromkeys(cfg.layer_types)}}


def init_cache(cfg: JambaConfig, batch: int, max_len: Optional[int] = None
               ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """What a prefill of ``batch`` rows fills: ``attn`` the dense rows
    (layers, B, T, n_kv_heads, head_dim), ``ssm`` a state a row (layers,
    B, ...)."""
    t_max = max_len or cfg.max_seq_len
    out = {}
    for kind, spec in cache_leaves(cfg).items():
        lead = (spec["layers"], batch) + (
            () if spec.get("per_slot") else (t_max,))
        out[kind] = {name: jnp.zeros(lead + tuple(shape), dtype)
                     for name, (shape, dtype) in spec["leaves"].items()}
    return out


# -- the layers ---------------------------------------------------------------

def _norm(cfg: JambaConfig, x, gain):
    return rms_norm(x, gain, cfg.norm_eps)


def _mlp(layer, u):
    return (jax.nn.silu(u @ layer["w_gate"]) * (u @ layer["w_up"])
            ) @ layer["w_down"]


def _residual(cfg: JambaConfig, layer, x, mixed):
    """A layer's two residual adds: the mixer's output, then the MLP."""
    x = x + mixed
    return x + _mlp(layer, _norm(cfg, x, layer["norm2"]))


def _qkv(cfg: JambaConfig, layer, u):
    b, s, _ = u.shape
    q = (u @ layer["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = (u @ layer["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = (u @ layer["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def _low_steps(cfg: JambaConfig, layer, xc):
    """The convolved input ``xc`` (..., C) through ``x_proj`` and its
    inner norms: (the low-rank step (..., R) in xc's type, B_t (..., N),
    C_t (..., N) float32)."""
    f32 = jnp.float32
    n, r = cfg.d_state, cfg.dt_rank
    low = jnp.matmul(xc, layer["w_x"], preferred_element_type=f32)
    dt = rms_norm(low[..., :r], layer["dt_norm"].astype(f32), cfg.norm_eps)
    bm = rms_norm(low[..., r:r + n], layer["b_norm"].astype(f32),
                  cfg.norm_eps)
    cm = rms_norm(low[..., r + n:], layer["c_norm"].astype(f32),
                  cfg.norm_eps)
    return dt.astype(xc.dtype), bm, cm


def _steps(cfg: JambaConfig, layer, xc):
    """The convolved input ``xc`` (..., C) to what the recurrence takes:
    (D_t (..., C), B_t (..., N), C_t (..., N)), all float32."""
    dt, bm, cm = _low_steps(cfg, layer, xc)
    dt = jnp.matmul(dt, layer["w_dt"], preferred_element_type=jnp.float32)
    return jax.nn.softplus(dt + layer["b_dt"]), bm, cm


def _gate_out(layer, y, z):
    """y float32, z in the activations' type -> the mixer's output."""
    gated = y * jax.nn.silu(z.astype(jnp.float32))
    return gated.astype(z.dtype) @ layer["w_out"]


def _ssm_prefill(cfg: JambaConfig, layer, u, lengths):
    """The state-space mixer over u (B, S, D). Returns (out (B, S, D),
    (h (B, N, C) float32 at each row's prompt end, conv (B, 3 C): the
    last three inputs before it, oldest first))."""
    f32 = jnp.float32
    b, s, _ = u.shape
    c, taps = cfg.d_inner, cfg.d_conv
    xz = u @ layer["w_in"]
    x, z = xz[..., :c], xz[..., c:]
    with jax.named_scope("ssm.conv"):
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        conv = layer["conv_b"].astype(f32) + sum(
            layer["conv_w"][j].astype(f32) * padded[:, j:j + s].astype(f32)
            for j in range(taps))
        xc = jax.nn.silu(conv).astype(x.dtype)
        # padded[i] is x[i - 3]: the inputs at lengths - 3 .. lengths - 1
        ends = (jnp.full((b,), s, jnp.int32) if lengths is None
                else lengths.astype(jnp.int32))
        window = jnp.take_along_axis(
            padded, (ends[:, None] + jnp.arange(taps - 1))[:, :, None],
            axis=1).reshape(b, (taps - 1) * c)
    dt, bm, cm = _steps(cfg, layer, xc)
    y, h = prefill_scan(cfg, xc, dt, bm, cm, -jnp.exp(layer["a_log"]),
                        layer["d"], lengths)
    return _gate_out(layer, y, z), (h, window)


def prefill_scan(cfg: JambaConfig, xc, dt, bm, cm, a, d, lengths=None):
    """The prefill's selective scan of a bucket, from a zero state:
    ``ops.ssm.selective_scan_ref``'s operands and results. The Pallas
    kernel where Mosaic tiles the bucket, else the chunked XLA form;
    decided by shape alone (``scans_in_kernel``), so a CPU run and a
    chip run of one configuration take one path."""
    with jax.named_scope("ssm.scan"):
        if cfg.scans_in_kernel(xc.shape[1]):
            from gofr_tpu.ops.pallas import selective_scan
            return selective_scan(xc, dt, bm, cm, a, d, None, lengths)
        return selective_scan_chunked(xc, dt, bm, cm, a, d, None, lengths)


def _ssm_step(cfg: JambaConfig, layer, u, h, conv, idx, active):
    """One token a row: u (B, D); h (layers, B, N, C), the whole stack,
    and ``idx`` the layer's index in it; conv (B, 3 C); active (B,).
    Returns (out (B, D), the stack with the layer's active rows stepped
    and every other row bit for bit, the new conv). The step kernel
    where Mosaic tiles the slots (``steps_in_kernel``), else the XLA
    form (``ops.ssm.selective_step`` and the gate); decided by shape
    alone, so a CPU run and a chip run take one path."""
    f32 = jnp.float32
    c, taps = cfg.d_inner, cfg.d_conv
    xz = u @ layer["w_in"]
    x, z = xz[..., :c], xz[..., c:]
    with jax.named_scope("ssm.conv"):
        held = [conv[:, j * c:(j + 1) * c] for j in range(taps - 1)] + [x]
        xc = jax.nn.silu(layer["conv_b"].astype(f32) + sum(
            layer["conv_w"][j].astype(f32) * held[j].astype(f32)
            for j in range(taps))).astype(x.dtype)
        conv = jnp.concatenate(held[1:], axis=-1)
    a = -jnp.exp(layer["a_log"])
    if cfg.steps_in_kernel(u.shape[0]):
        from gofr_tpu.ops.pallas import selective_step as step_kernel
        low, bm, cm = _low_steps(cfg, layer, xc)
        with jax.named_scope("ssm.step"):
            gated, h = step_kernel(xc, low, bm, cm, z, layer["w_dt"],
                                   layer["b_dt"], a, layer["d"], h, idx,
                                   active)
        return gated @ layer["w_out"], h, conv
    dt, bm, cm = _steps(cfg, layer, xc)
    held_h = lax.dynamic_index_in_dim(h, idx, 0, keepdims=False)
    with jax.named_scope("ssm.step"):
        y, new = selective_step(xc, dt, bm, cm, a, layer["d"], held_h)
    new = jnp.where(active[:, None, None], new, held_h)
    return (_gate_out(layer, y, z),
            lax.dynamic_update_index_in_dim(h, new, idx, 0), conv)


def _head(params, cfg: JambaConfig, x):
    x = _norm(cfg, x, params["out_norm"])
    return jnp.einsum("...d,vd->...v", x,
                      params["tok_emb"]).astype(jnp.float32)


def _layers(params, cfg: JambaConfig, carry, ssm_layer, attn_layer):
    """Every layer in model order over ``carry``: a ``lax.scan`` over the
    periods, each a scan over the state-space layers before its
    attention layer, that layer, and a scan over those after it.
    ``ssm_layer(carry, layer, idx)`` and ``attn_layer(carry, layer, idx)``
    take the layer's parameters (read out of the kind's whole stack at
    ``idx``, its place among the kind's layers) and return (carry, what
    the layer leaves behind). Returns (carry, the state-space layers'
    leavings stacked in model order, the attention layers')."""
    before = cfg.attn_layer_offset
    after = cfg.attn_layer_period - 1 - before
    periods = cfg.n_layers // cfg.attn_layer_period

    def at(kind, idx):
        return jax.tree.map(
            lambda leaf: lax.dynamic_index_in_dim(leaf, idx, 0,
                                                  keepdims=False),
            params[kind])

    def span(carry, first, count):
        return lax.scan(
            lambda carry, idx: ssm_layer(carry, at("ssm", idx), idx), carry,
            first + jnp.arange(count, dtype=jnp.int32))

    def period(carry, p):
        left = []
        first = p * (before + after)
        if before:
            carry, out = span(carry, first, before)
            left.append(out)
        carry, kept = attn_layer(carry, at("attn", p), p)
        if after:
            carry, out = span(carry, first + before, after)
            left.append(out)
        return carry, (tuple(left), kept)

    carry, (left, kept) = lax.scan(period, carry,
                                   jnp.arange(periods, dtype=jnp.int32))
    # (periods, layers of a span, ...) a span -> (state-space layers, ...)
    left = jax.tree.map(
        lambda *spans: jnp.concatenate(spans, axis=1).reshape(
            -1, *spans[0].shape[2:]), *left) if left else None
    return carry, left, kept


def prefill(params: Dict[str, Any], cfg: JambaConfig, tokens: jnp.ndarray,
            cache: Dict[str, Dict[str, jnp.ndarray]],
            lengths: Optional[jnp.ndarray] = None):
    """Run the prompts, fill the cache. tokens (B, S) right-padded to
    ``lengths``; returns (last-token logits (B, V), cache: ``attn`` with
    rows [0, S) written, ``ssm`` each row's state at its prompt's end,
    cache_len (B,)). Causal attention never sees the padding on the
    right; the recurrence would, so its steps there are zero and its
    conv window is taken at the prompt's end."""
    b, s = tokens.shape
    x = params["tok_emb"][tokens]
    block = cfg.flash_block(s)
    if block is not None:
        from gofr_tpu.ops.pallas import flash_attention

        def attend(q, k, v):
            return flash_attention(q, k, v, block_q=block, block_k=block)
    else:
        def attend(q, k, v):
            return banded_attention(q, k, v, None)

    def ssm_layer(x, layer, idx):
        mixed, state = _ssm_prefill(cfg, layer,
                                    _norm(cfg, x, layer["norm1"]), lengths)
        return _residual(cfg, layer, x, mixed), state

    def attn_layer(x, layer, idx):
        q, k, v = _qkv(cfg, layer, _norm(cfg, x, layer["norm1"]))
        mixed = attend(q, k, v).reshape(b, s, -1) @ layer["wo"]
        return _residual(cfg, layer, x, mixed), (k, v)

    x, states, kept = _layers(params, cfg, x, ssm_layer, attn_layer)
    new_cache = {
        "attn": {name: lax.dynamic_update_slice_in_dim(
            cache["attn"][name], rows.astype(cache["attn"][name].dtype), 0,
            axis=2) for name, rows in zip(("k", "v"), kept)},
        "ssm": {name: rows.astype(cache["ssm"][name].dtype)
                for name, rows in zip(("h", "conv"), states)}}
    if lengths is None:
        last = x[:, -1]
        cache_len = jnp.full((b,), s, jnp.int32)
    else:
        last = x[jnp.arange(b), lengths - 1]
        cache_len = lengths.astype(jnp.int32)
    return _head(params, cfg, last), new_cache, cache_len


def decode_step_paged(params: Dict[str, Any], cfg: JambaConfig,
                      token: jnp.ndarray,
                      pool: Dict[str, Dict[str, jnp.ndarray]],
                      page_table: Dict[str, jnp.ndarray],
                      cache_len: jnp.ndarray, active: jnp.ndarray,
                      ragged: bool = False, counters: bool = False):
    """One decode step over the pool. ``pool["attn"]``: ``k`` and ``v``
    (layers of the kind, num_pages, page, Hkv, Dh) with
    ``page_table["attn"]`` (B, P), ``num_pages`` the unallocated
    sentinel. ``pool["ssm"]``: ``h`` (layers of the kind, B, N, C) and
    ``conv`` (layers, B, 3 C), row ``i`` slot ``i``'s; it has no table.
    ``active`` (B,) bool gates every write: an inactive slot's K/V row
    goes to the sentinel page and is dropped, and its state stays **bit
    for bit** (the tick runs every slot every step; a slot between two
    of its requests, or waiting for its insert, must find what it
    left). ``ragged`` (static) reads the pages in place through the
    Pallas ragged kernel, otherwise a layer's table pages are gathered,
    the oracle. Returns (logits, pool, cache_len + 1), and with
    ``counters`` the step's ``STEP_COUNTERS`` as a fourth."""
    batch = token.shape[0]
    x = params["tok_emb"][token][:, None, :]                # (B, 1, D)
    table = page_table["attn"]
    num_pages, page = pool["attn"]["k"].shape[1:3]
    page_row = jnp.take_along_axis(
        table, (cache_len // page)[:, None], axis=1, mode="clip")[:, 0]
    dest = (jnp.where(active, page_row, num_pages), cache_len % page)
    live_rows = jnp.where(active, cache_len, 0).sum().astype(jnp.int32)
    read = live_rows if ragged else jnp.int32(batch * table.shape[1] * page)
    zero, one = jnp.int32(0), jnp.int32(1)
    in_kernel = jnp.int32(cfg.steps_in_kernel(batch))
    counted = {"ssm": jnp.stack([active.sum().astype(jnp.int32),
                                 jnp.int32(batch), one, zero, zero, zero,
                                 in_kernel]),
               "attn": jnp.stack([zero, zero, zero, live_rows, read, one,
                                  zero])}

    def attn_layer(carry, layer, idx):
        x, pool, counts = carry
        q, k, v = _qkv(cfg, layer, _norm(cfg, x, layer["norm1"]))
        leaves, k_new, v_new = pool["attn"], k[:, 0], v[:, 0]
        if ragged:
            from gofr_tpu.ops.pallas import ragged_paged_decode_attention
            out = ragged_paged_decode_attention(
                q, leaves["k"], leaves["v"], table, k_new, v_new,
                cache_len, idx)
        else:
            views = [gather_kv_pages(lax.dynamic_index_in_dim(
                leaves[name], idx, 0, keepdims=False), table)
                for name in "kv"]
            out = decode_attention_cached(q, *views, k_new, v_new,
                                          cache_len)
        row, offset = dest
        leaves = {"k": leaves["k"].at[idx, row, offset].set(k_new,
                                                            mode="drop"),
                  "v": leaves["v"].at[idx, row, offset].set(v_new,
                                                            mode="drop")}
        x = _residual(cfg, layer, x,
                      out.reshape(batch, 1, -1) @ layer["wo"])
        return (x, dict(pool, attn=leaves), counts + counted["attn"]), None

    def ssm_layer(carry, layer, idx):
        x, pool, counts = carry
        stacks = pool["ssm"]
        held = lax.dynamic_index_in_dim(stacks["conv"], idx, 0,
                                        keepdims=False)
        out, h, conv = _ssm_step(cfg, layer,
                                 _norm(cfg, x, layer["norm1"])[:, 0],
                                 stacks["h"], held, idx, active)
        conv = jnp.where(active[:, None], conv, held)
        leaves = {"h": h, "conv": lax.dynamic_update_index_in_dim(
            stacks["conv"], conv.astype(stacks["conv"].dtype), idx, 0)}
        x = _residual(cfg, layer, x, out[:, None, :])
        return (x, dict(pool, ssm=leaves), counts + counted["ssm"]), None

    (x, pool, counts), _, _ = _layers(
        params, cfg,
        (x, pool, jnp.zeros((_N_COUNTERS,), jnp.int32)),
        ssm_layer, attn_layer)
    out = (_head(params, cfg, x[:, 0]), pool, cache_len + 1)
    return out + (counts,) if counters else out
