"""The selective scan's three forms (ops/ssm.py) and its Pallas kernel
(ops/pallas/selective_scan.py, in interpret mode here; compiled for the
chip by tests/test_pallas_aot.py): token by token (the oracle), chunked
with a carried state, and the kernel agree, with and without an initial
state, across chunk and block boundaries, and with ``lengths`` shorter
than the bucket, where the state that comes out is the state at the
prompt's end."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ops import ssm
from gofr_tpu.ops.pallas import scan_tileable, selective_scan

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
