"""``ops/row_window.circular_window``: the batch-wide kernel, driven through
the Pallas interpreter on the CPU, against the ``jnp.roll`` /
``lax.dynamic_slice`` it replaced in the device augmentation — bit for bit,
since it only moves data. What the chip's compiler makes of it is
``tests/test_chip_compile.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from seist_tpu.ops import row_window as rw

L = 12000  # the benchmark's raw row (not a multiple of 128: the wrap is 32 lanes late)
W = 8192  # its window


def _rows(batch, channels, length=L, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.standard_normal((batch, channels, length)), jnp.float32
    )


def _starts(batch, first, length, seed=0):
    """``first`` in row 0, then draws over the whole row."""
    rng = np.random.default_rng(seed + 1)
    rest = rng.integers(0, length, size=batch - 1)
    return jnp.asarray(np.concatenate([[first], rest]), jnp.int32)


def _window(width, **kw):
    return jax.jit(
        jax.vmap(lambda d, s: rw.circular_window(d, s, width, **kw))
    )


@pytest.mark.parametrize("channels", [1, 3])
@pytest.mark.parametrize("batch", [1, 5, 19])
@pytest.mark.parametrize(
    "first", [0, 1, 127, 128, 129, L - 1, 3777], ids=lambda s: f"start{s}"
)
def test_full_width_window_is_jnp_roll(first, batch, channels):
    """``shift_event``'s use: the window of width L from ``(L - s) % L`` is
    ``jnp.roll(data, s)``, every start class of the 128-lane tiling in row
    0 and drawn starts behind it."""
    data = _rows(batch, channels)
    shifts = _starts(batch, first, L)
    want = jax.vmap(lambda d, s: jnp.roll(d, s, axis=1))(data, shifts)
    got = _window(L, interpret=True)(data, -shifts)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert bool((got == want).all())


@pytest.mark.parametrize("channels", [1, 3])
@pytest.mark.parametrize("batch", [1, 5, 19])
@pytest.mark.parametrize(
    "first", [0, 1, 127, 128, 129, L - W, 2500], ids=lambda s: f"start{s}"
)
def test_narrow_window_is_dynamic_slice(first, batch, channels):
    """``cut_window``'s use: a window that does not wrap is
    ``lax.dynamic_slice``."""
    data = _rows(batch, channels)
    starts = jnp.minimum(_starts(batch, first, L), L - W)
    want = jax.vmap(
        lambda d, s: lax.dynamic_slice(d, (0, s), (channels, W))
    )(data, starts)
    got = _window(W, interpret=True)(data, starts)
    assert bool((got == want).all())


@pytest.mark.parametrize("first", [L - W + 1, L - 128, L - 1])
def test_narrow_window_wraps_around_the_row(first):
    data = _rows(5, 3)
    starts = _starts(5, first, L)
    idx = (np.asarray(starts)[:, None] + np.arange(W)[None, :]) % L
    want = np.take_along_axis(np.asarray(data), idx[:, None, :], axis=2)
    got = _window(W, interpret=True)(data, starts)
    assert (np.asarray(got) == want).all()


@pytest.mark.parametrize("length,width", [(1024, 1024), (1024, 512), (300, 77)])
def test_rows_of_other_lengths(length, width):
    """A row that fills its lanes (no late wrap), and one shorter than three
    tiles."""
    data = _rows(5, 3, length)
    starts = _starts(5, length - 1, length)
    idx = (np.asarray(starts)[:, None] + np.arange(width)[None, :]) % length
    want = np.take_along_axis(np.asarray(data), idx[:, None, :], axis=2)
    got = _window(width, interpret=True)(data, starts)
    assert (np.asarray(got) == want).all()


@pytest.mark.parametrize("interpret", [True, False], ids=["kernel", "plain"])
def test_one_row_without_vmap(interpret):
    """The host-parity tests call ``process_event`` on one row: the same
    function, a grid of one (or the plain form off the TPU)."""
    data = _rows(1, 3)[0]
    got = rw.circular_window(data, jnp.int32(129), W, interpret=interpret)
    assert bool((got == data[:, 129:129 + W]).all())
    got = rw.circular_window(data, -5, L, interpret=interpret)
    assert bool((got == jnp.roll(data, 5, axis=1)).all())


def test_the_plain_form_is_the_kernels_bits():
    data = _rows(19, 3)
    starts = _starts(19, L - 1, L)
    for width in (L, W):
        assert bool(
            (_window(width)(data, starts)
             == _window(width, interpret=True)(data, starts)).all()
        )


@pytest.mark.parametrize("batched", ["data", "start"])
def test_vmap_over_one_argument_only(batched):
    data = _rows(5, 3)
    starts = _starts(5, 127, L)
    if batched == "data":
        got = jax.vmap(
            lambda d: rw.circular_window(d, starts[1], W, interpret=True)
        )(data)
        want = jax.vmap(lambda d: rw.circular_window(d, starts[1], W))(data)
    else:
        got = jax.vmap(
            lambda s: rw.circular_window(data[0], s, W, interpret=True)
        )(starts)
        want = jax.vmap(lambda s: rw.circular_window(data[0], s, W))(starts)
    assert bool((got == want).all())


def test_int32_rows_and_other_widths_of_element():
    ints = jnp.arange(2 * 3 * 300, dtype=jnp.int32).reshape(2, 3, 300)
    starts = jnp.asarray([299, 5], jnp.int32)
    want = jax.vmap(lambda d, s: jnp.roll(d, -s, axis=1))(ints, starts)
    assert bool((_window(300, interpret=True)(ints, starts) == want).all())
    # 2-byte rows take the plain form, kernel asked for or not
    halves = ints.astype(jnp.bfloat16)
    want = jax.vmap(lambda d, s: jnp.roll(d, -s, axis=1))(halves, starts)
    assert bool((_window(300, interpret=True)(halves, starts) == want).all())


@pytest.mark.parametrize("width", [0, L + 1])
def test_a_window_wider_than_the_row_is_refused(width):
    with pytest.raises(ValueError, match="window of"):
        rw.circular_window(_rows(1, 3)[0], 0, width)


def test_no_gradient_passes_through_the_window():
    """The augmentation runs outside the step's gradient: a loss
    differentiated in its parameters, fed through the window, needs no
    differentiation rule of the kernel."""
    data = _rows(5, 3, 300)
    starts = _starts(5, 299, 300)

    def loss(w):
        x = _window(256, interpret=True)(data, starts)
        return ((x * w) ** 2).sum()

    g = jax.jit(jax.grad(loss))(jnp.float32(2.0))
    x = _window(256)(data, starts)
    np.testing.assert_allclose(g, 4.0 * (x**2).sum(), rtol=1e-5)


@pytest.mark.parametrize(
    "shape,kept",
    [
        ((256, 3, L), (1, 2, 0)),  # a full batch of raw rows: batch along the lanes
        ((256, 3, W), None),  # its windows fill the lanes themselves
        ((32, 3, L), None),  # a batch that does not fill the lanes
        ((5, 3, L), None),
    ],
    ids=["full_batch_raw", "full_batch_window", "small_batch", "ragged_batch"],
)
def test_rows_are_pinned_to_the_layout_the_compiler_keeps_them_in(shape, kept):
    """What ``tests/test_chip_compile.py`` reads in the compiled program,
    as the rule states it (no compile: the constraint in the jaxpr)."""
    jaxpr = jax.make_jaxpr(rw._as_the_compiler_keeps)(
        jax.ShapeDtypeStruct(shape, jnp.float32)
    )
    pins = [e for e in jaxpr.eqns if e.primitive.name == "layout_constraint"]
    if kept is None:
        assert not pins
    else:
        (pin,) = pins
        assert pin.params["layout"].major_to_minor == kept
