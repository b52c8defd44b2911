"""The selective scan's three forms (ops/ssm.py) and its Pallas kernel
(ops/pallas/selective_scan.py, in interpret mode here; compiled for the
chip by tests/test_pallas_aot.py): token by token (the oracle), chunked
with a carried state, and the kernel agree, with and without an initial
state, across chunk and block boundaries, and with ``lengths`` shorter
than the bucket, where the state that comes out is the state at the
prompt's end. The decode step's kernel (ops/pallas/selective_step.py,
interpreted) equals ``dt_proj`` + softplus, ``ops.ssm.selective_step``
and the gate, writes its layer's active rows of the whole stack and no
other bit of it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ops import ssm
from gofr_tpu.ops.pallas import (scan_tileable, selective_scan,
                                 selective_step, step_tileable)

TOL = 2e-5      # float32 all round; sums and products in another order


def operands(seed, batch, seq, channels, states, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (batch, seq, channels)).astype(dtype)
    # steps of 0.01 .. 0.3: states that keep tens of tokens
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, channels))
                         - 3.0)
    b = jax.random.normal(keys[2], (batch, seq, states))
    c = jax.random.normal(keys[3], (batch, seq, states))
    a = -jnp.broadcast_to(jnp.arange(1, states + 1, dtype=jnp.float32)
                          [:, None], (states, channels))
    d = jax.random.normal(keys[4], (channels,))
    h0 = jax.random.normal(keys[5], (batch, states, channels))
    return x, dt, b, c, a, d, h0


def kernel(*args, **kwargs):
    """The kernel through the interpreter, in blocks of 16 tokens: the
    walk over a block is unrolled, and 128 steps take the interpreter
    half a minute to compile."""
    return selective_scan(*args, interpret=True, block=16, **kwargs)


FORMS = {"chunked": ssm.selective_scan_chunked, "kernel": kernel}


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("initial", [False, True], ids=["zero", "h0"])
@pytest.mark.parametrize("masked", [False, True], ids=["whole", "lengths"])
def test_forms_agree_with_the_walk_token_by_token(form, initial, masked):
    # 96 tokens: six of the kernel's blocks here, two chunks of 64
    x, dt, b, c, a, d, h0 = operands(0, 2, 96, 128, 16)
    h0 = h0 if initial else None
    lengths = jnp.asarray([70, 96], jnp.int32) if masked else None
    want_y, want_h = ssm.selective_scan_ref(x, dt, b, c, a, d, h0, lengths)
    got_y, got_h = jax.jit(FORMS[form])(x, dt, b, c, a, d, h0, lengths)
    assert got_y.dtype == jnp.float32 and got_h.dtype == jnp.float32
    np.testing.assert_allclose(got_h, want_h, atol=TOL)
    live = 70 if masked else 96
    np.testing.assert_allclose(got_y[0, :live], want_y[0, :live], atol=TOL)
    np.testing.assert_allclose(got_y[1], want_y[1], atol=TOL)


@pytest.mark.parametrize("form", ["ref", "chunked", "kernel"])
def test_the_state_at_the_buckets_end_is_the_state_at_the_prompts_end(form):
    scan = dict(FORMS, ref=ssm.selective_scan_ref)[form]
    x, dt, b, c, a, d, h0 = operands(1, 2, 64, 128, 16)
    lengths = jnp.asarray([37, 61], jnp.int32)
    _, padded = scan(x, dt, b, c, a, d, h0, lengths)
    for row, length in enumerate(lengths.tolist()):
        _, alone = ssm.selective_scan_ref(
            *(v[row:row + 1, :length] for v in (x, dt, b, c)), a, d,
            h0[row:row + 1])
        np.testing.assert_allclose(padded[row], alone[0], atol=TOL)
    # and the padding is what is masked: without lengths the state moves
    _, unmasked = scan(x, dt, b, c, a, d, h0)
    assert float(jnp.abs(unmasked - padded).max()) > 1e-2


def test_chunks_that_do_not_divide_the_sequence_carry_the_state():
    x, dt, b, c, a, d, h0 = operands(2, 1, 50, 32, 8)
    want_y, want_h = ssm.selective_scan_ref(x, dt, b, c, a, d, h0)
    for chunk in (1, 7, 16, 50, 64):
        y, h = ssm.selective_scan_chunked(x, dt, b, c, a, d, h0,
                                          chunk=chunk)
        assert y.shape == want_y.shape
        np.testing.assert_allclose(y, want_y, atol=TOL)
        np.testing.assert_allclose(h, want_h, atol=TOL)


def test_one_step_is_one_token_of_the_walk():
    x, dt, b, c, a, d, h0 = operands(3, 3, 4, 64, 16, jnp.bfloat16)
    want_y, want_h = ssm.selective_scan_ref(x, dt, b, c, a, d, h0)
    h = h0
    for t in range(4):
        y, h = ssm.selective_step(x[:, t], dt[:, t], b[:, t], c[:, t], a,
                                  d, h)
        np.testing.assert_allclose(y, want_y[:, t], atol=TOL)
    np.testing.assert_allclose(h, want_h, atol=TOL)
    # a step of zero leaves the state bit for bit
    _, same = ssm.selective_step(x[:, 0], jnp.zeros_like(dt[:, 0]), b[:, 0],
                                 c[:, 0], a, d, h)
    assert bool((same == h).all())


def test_the_kernel_reads_the_inputs_type_and_keeps_float32():
    x, dt, b, c, a, d, h0 = operands(4, 1, 32, 128, 16, jnp.bfloat16)
    want_y, want_h = ssm.selective_scan_ref(x, dt, b, c, a, d, h0)
    y, h = kernel(x, dt, b, c, a, d, h0)
    np.testing.assert_allclose(y, want_y, atol=TOL)
    np.testing.assert_allclose(h, want_h, atol=TOL)


@pytest.mark.parametrize("shape,tiles", [
    ((2048, 5120, 16), True),       # jamba2-3b's largest bucket
    ((128, 5120, 16), True),
    ((64, 5120, 16), False),        # under one block of tokens
    ((128, 128, 16), False),        # under one tile of channels
    ((128, 5120, 12), False),       # states that do not fill sublanes
    ((128, 5120, 128), False),      # b and c past one row of lanes
])
def test_tiling_predicate(shape, tiles):
    assert scan_tileable(*shape) is tiles


def test_the_kernel_names_a_shape_it_cannot_split():
    x, dt, b, c, a, d, _ = operands(5, 1, 40, 128, 16)
    with pytest.raises(ValueError, match="do not split"):
        kernel(x, dt, b, c, a, d)


# -- the decode step's kernel -------------------------------------------------------------

def step_operands(seed, layers=1, rows=16, states=16, channels=1024,
                  rank=160, dtype=jnp.bfloat16):
    """A decode step's operands as ``models/jamba.py`` hands them over:
    activations and weights in ``dtype``, the rest float32; rows 3, 7
    and 12 not active."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    xc = jax.random.normal(keys[0], (rows, channels)).astype(dtype)
    low = jax.random.normal(keys[1], (rows, rank)).astype(dtype)
    b = jax.random.normal(keys[2], (rows, states))
    c = jax.random.normal(keys[3], (rows, states))
    z = jax.random.normal(keys[4], (rows, channels)).astype(dtype)
    w_dt = (0.5 * jax.random.normal(keys[5], (rank, channels))
            / rank ** 0.5).astype(dtype)
    b_dt = jax.random.normal(keys[6], (channels,)) - 3.0
    a = -jnp.broadcast_to(jnp.arange(1, states + 1, dtype=jnp.float32)
                          [:, None], (states, channels))
    d = jax.random.normal(keys[7], (channels,))
    h = jax.random.normal(keys[8], (layers, rows, states, channels))
    active = ~jnp.isin(jnp.arange(rows), jnp.asarray([3, 7, 12]))
    return xc, low, b, c, z, w_dt, b_dt, a, d, h, active


def step_oracle(xc, low, b, c, z, w_dt, b_dt, a, d, h, layer, active):
    """What ``models/jamba.py``'s XLA form does: ``dt_proj`` (a product
    in the activations' type, float32 sums) and its softplus, the
    step, the active rows kept, the gate."""
    f32 = jnp.float32
    dt = jax.nn.softplus(jnp.matmul(low, w_dt, preferred_element_type=f32)
                         + b_dt)
    y, new = ssm.selective_step(xc, dt, b, c, a, d, h[layer])
    new = jnp.where(active[:, None, None], new, h[layer])
    return ((y * jax.nn.silu(z.astype(f32))).astype(z.dtype),
            h.at[layer].set(new))


def step_kernel(*operands, layer):
    return jax.jit(lambda *o: selective_step(*o[:-1], layer, o[-1],
                                             interpret=True))(*operands)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_the_step_kernel_equals_the_xla_step_and_gate(dtype):
    ops = step_operands(6, dtype=dtype)
    want_y, want_h = step_oracle(*ops[:-1], 0, ops[-1])
    got_y, got_h = step_kernel(*ops, layer=0)
    assert got_y.dtype == dtype and got_h.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got_y, np.float32),
                               np.asarray(want_y, np.float32), atol=TOL,
                               rtol=1e-2 if dtype == jnp.bfloat16 else TOL)
    np.testing.assert_allclose(got_h, want_h, atol=TOL)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_the_step_kernel_writes_its_layers_active_rows_alone(layer):
    """A three-layer stack: the other layers, and the inactive rows of
    the layer, come back bit for bit; the active rows move."""
    ops = step_operands(7, layers=3)
    h, active = np.asarray(ops[9]), np.asarray(ops[10])
    _, got = step_kernel(*ops, layer=layer)
    got = np.asarray(got)
    others = [i for i in range(3) if i != layer]
    assert (got[others] == h[others]).all()
    assert (got[layer][~active] == h[layer][~active]).all()
    assert (got[layer][active] != h[layer][active]).any(axis=(1, 2)).all()
    _, want = step_oracle(*ops[:-1], layer, ops[-1])
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("rank", [160, 256], ids=["160", "padded-256"])
def test_a_zero_padded_dt_rank_gives_the_unpadded_step(rank):
    """Mosaic takes ``dt_rank`` 160 as it is (tests/test_pallas_aot.py);
    zero rows of ``w_dt`` and zero columns of the low-rank step past it
    change nothing, bit for bit."""
    ops = list(step_operands(8))
    want = step_kernel(*ops, layer=0)
    pad = rank - ops[1].shape[1]
    ops[1] = jnp.pad(ops[1], ((0, 0), (0, pad)))
    ops[5] = jnp.pad(ops[5], ((0, pad), (0, 0)))
    got = step_kernel(*ops, layer=0)
    for g, w in zip(got, want):
        assert bool((g == w).all())


@pytest.mark.parametrize("shape,tiles", [
    ((5120, 16, 128), True),        # jamba2-3b's decode step, 128 slots
    ((1024, 16, 16), True),
    ((128, 16, 4), False),          # the tiny preset: under one tile
    ((5120, 16, 4), False),         # under one block of rows
    ((5120, 12, 128), False),       # states that do not fill sublanes
])
def test_step_tiling_predicate(shape, tiles):
    assert step_tileable(*shape) is tiles


def test_the_step_kernel_names_a_shape_it_cannot_split():
    ops = step_operands(9, rows=24)
    with pytest.raises(ValueError, match="do not split"):
        step_kernel(*ops, layer=0)
