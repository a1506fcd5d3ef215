"""Geometry-parity helpers in models/common.py: the gather-free integer
upsampling must match both the generic gather path and torch
F.interpolate exactly (the dpk head's pick alignment depends on it —
SURVEY.md hard-part #3)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from seist_tpu.models import common
from seist_tpu.models.common import (
    _interpolate_linear_intscale,
    interpolate_linear,
)


def _gather_reference(x, out_size):
    """The generic (gather) formula, inlined so the fast path can't shadow it."""
    L_in = x.shape[-2]
    scale = L_in / out_size
    dst = np.arange(out_size, dtype=np.float32)
    src = np.clip((dst + 0.5) * scale - 0.5, 0.0, L_in - 1)
    lo = np.floor(src).astype(np.int32)
    hi = np.minimum(lo + 1, L_in - 1)
    w = (src - lo)[None, :, None].astype(np.float32)
    return x[:, lo, :] * (1.0 - w) + x[:, hi, :] * w


@pytest.mark.parametrize("r", [2, 4, 8, 64])
def test_intscale_matches_gather_dyadic_exact(rng, r):
    # Power-of-two factors (the only ones the dpk ladder uses): the static
    # phase weights are exact binary fractions -> bit-identical results.
    x = rng.standard_normal((2, 16, 3)).astype(np.float32)
    want = _gather_reference(x, 16 * r)
    got = np.asarray(_interpolate_linear_intscale(jnp.asarray(x), r))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("r", [3, 5, 6])
def test_intscale_matches_gather_odd(rng, r):
    # Non-dyadic factors: the gather path rounds its weights through
    # fp32 `(d+0.5)*scale`, ours are exact doubles -> ~1e-6 fp noise.
    x = rng.standard_normal((2, 16, 3)).astype(np.float32)
    want = _gather_reference(x, 16 * r)
    got = np.asarray(_interpolate_linear_intscale(jnp.asarray(x), r))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=5e-6)


@pytest.mark.parametrize("out", [24, 40, 100])
def test_non_integer_ratio_uses_gather(rng, out):
    x = rng.standard_normal((2, 16, 3)).astype(np.float32)
    want = _gather_reference(x, out)
    got = np.asarray(interpolate_linear(jnp.asarray(x), out))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("out", [32, 48, 100, 1024])
def test_matches_torch_interpolate(rng, out):
    torch = pytest.importorskip("torch")
    x = rng.standard_normal((2, 16, 3)).astype(np.float32)
    want = (
        torch.nn.functional.interpolate(
            torch.from_numpy(x.transpose(0, 2, 1)),
            size=out,
            mode="linear",
            align_corners=False,
        )
        .numpy()
        .transpose(0, 2, 1)
    )
    got = np.asarray(interpolate_linear(jnp.asarray(x), out))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=5e-6)


def test_identity_when_same_size(rng):
    x = jnp.asarray(rng.standard_normal((1, 8, 2)).astype(np.float32))
    assert interpolate_linear(x, 8) is x


class TestNoScatterBackward:
    """HLO regression locks for the round-2 lowering work: the backward
    passes of the conv lowerings must not contain scatter ops (XLA lowers
    the transpose of a strided slice to scatter-adds — the pathology the
    phase-split and composed lowerings exist to remove)."""

    def _grad_hlo(self, fn, *args):
        g = jax.jit(jax.grad(fn))
        return g.lower(*args).compile().as_text()

    @pytest.mark.parametrize("s", [2, 4])
    def test_depthwise_shift_stride_backward(self, rng, s):
        x = jnp.asarray(rng.standard_normal((2, 64, 8)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((11, 8)), jnp.float32)

        def loss(x):
            return jnp.sum(common.depthwise_shift_fma(x, w, s) ** 2)

        assert " scatter(" not in self._grad_hlo(loss, x)

    def test_dsconv_backward(self, rng):
        # impl='composed' only: the 'paths' impl lowers to grouped conv on
        # the CPU CI backend, so a scatter lock there would be vacuous.
        from seist_tpu.models.seist import DSConvNormAct

        m = DSConvNormAct(
            in_dim=8, out_dim=16, kernel_size=11, stride=2, impl="composed"
        )
        x = jnp.asarray(rng.standard_normal((2, 64, 3)), jnp.float32)
        v = m.init(jax.random.PRNGKey(0), x, True)

        def loss(x):
            y, _ = m.apply(v, x, True, mutable=["batch_stats"])
            return jnp.sum(y**2)

        assert " scatter(" not in self._grad_hlo(loss, x)

    def test_fused_stem_backward(self, rng):
        from seist_tpu.models.seist import StemBlock

        m = StemBlock(
            in_dim=8, out_dim=16, kernel_size=11, stride=2, impl="fused"
        )
        x = jnp.asarray(rng.standard_normal((2, 64, 3)), jnp.float32)
        v = m.init(jax.random.PRNGKey(0), x, True)

        def loss(x):
            y, _ = m.apply(v, x, True, mutable=["batch_stats"])
            return jnp.sum(y**2)

        assert " scatter(" not in self._grad_hlo(loss, x)


def test_lstm_unroll_is_pure_scheduling(rng, monkeypatch):
    """SEIST_LSTM_UNROLL must not change LSTM math (fwd or grad) — it only
    unrolls the scan body so XLA can pipeline the tiny per-step matmuls
    (common._lstm_unroll). Odd L exercises the remainder handling."""
    x = jnp.asarray(rng.standard_normal((2, 37, 5)), jnp.float32)
    m = common.BiLSTM(hidden=7)
    v = m.init(jax.random.PRNGKey(0), x)

    def fwd_and_grad(unroll):
        monkeypatch.setenv("SEIST_LSTM_UNROLL", unroll)
        o, h = m.apply(v, x)

        def loss(v):
            o, h = m.apply(v, x)
            return (o**2).sum() + (h**2).sum()

        return o, h, jax.grad(loss)(v)

    o1, h1, g1 = fwd_and_grad("1")
    o8, h8, g8 = fwd_and_grad("8")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o8), atol=1e-6)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h8), atol=1e-6)
    fa = jax.tree_util.tree_flatten_with_path(g1)[0]
    fb = jax.tree_util.tree_flatten_with_path(g8)[0]
    for (p, a), (_, b) in zip(fa, fb):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, err_msg=str(p)
        )


@pytest.mark.parametrize("out", [16, 32, 48, 100, 37])
def test_nearest_matches_torch_interpolate(rng, out):
    """Both the integer-factor repeat path and the gather path must match
    torch F.interpolate(mode='nearest') (ditingmotion's upsampler)."""
    torch = pytest.importorskip("torch")
    x = rng.standard_normal((2, 16, 3)).astype(np.float32)
    want = (
        torch.nn.functional.interpolate(
            torch.from_numpy(x.transpose(0, 2, 1)), size=out, mode="nearest"
        )
        .numpy()
        .transpose(0, 2, 1)
    )
    got = np.asarray(common.interpolate_nearest(jnp.asarray(x), out))
    np.testing.assert_allclose(got, want, rtol=0, atol=0)


class TestConvLowerings:
    """DepthwiseConv1D / GroupedConv1D: every lowering must match the
    nn.Conv(feature_group_count=...) it replaces, on the same param tree
    (checkpoint compatibility is the contract — models/common.py)."""

    @pytest.mark.parametrize(
        "k,s,C,L",
        [
            (11, 2, 16, 64),
            (5, 1, 8, 33),
            # phase-split stride path (common.depthwise_shift_fma s>1):
            # odd L, k<s taps empty phases, k%s==0, stride>2
            (10, 2, 3, 57),
            (3, 2, 5, 33),
            (4, 4, 8, 41),
            (7, 3, 4, 50),
        ],
    )
    @pytest.mark.parametrize("impl", ["shift", "grouped"])
    def test_depthwise_matches_nn_conv(self, rng, k, s, C, L, impl):
        from flax import linen as nn

        x = jnp.asarray(rng.standard_normal((2, L, C)), jnp.float32)
        ref = nn.Conv(
            C, (k,), strides=(s,), padding="VALID",
            feature_group_count=C, use_bias=False,
        )
        v = ref.init(jax.random.PRNGKey(0), x)
        want = ref.apply(v, x)
        got = common.DepthwiseConv1D(C, k, stride=s, impl=impl).apply(
            {"params": {"kernel": v["params"]["kernel"]}}, x
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-6
        )

    @pytest.mark.parametrize("k,s,C,L", [(10, 2, 3, 57), (7, 3, 4, 50)])
    def test_depthwise_shift_gradients_match_grouped(self, rng, k, s, C, L):
        """The phase-split stride path must be gradient-exact vs the
        lax grouped-conv lowering (both d/dx and d/dw) — the backward is
        exactly what the phase-split reshape exists to reroute."""
        x = jnp.asarray(rng.standard_normal((2, L, C)), jnp.float32)
        kern = jnp.asarray(rng.standard_normal((k, 1, C)), jnp.float32)

        def loss(impl, x, kern):
            y = common.DepthwiseConv1D(C, k, stride=s, impl=impl).apply(
                {"params": {"kernel": kern}}, x
            )
            return jnp.sum(jnp.sin(y) * y)

        gx_s, gw_s = jax.grad(lambda x, w: loss("shift", x, w), (0, 1))(x, kern)
        gx_g, gw_g = jax.grad(lambda x, w: loss("grouped", x, w), (0, 1))(x, kern)
        np.testing.assert_allclose(np.asarray(gx_s), np.asarray(gx_g), atol=2e-5)
        np.testing.assert_allclose(np.asarray(gw_s), np.asarray(gw_g), atol=2e-5)

    @pytest.mark.parametrize(
        "k,cin,cout,g", [(3, 24, 24, 3), (7, 96, 96, 12), (5, 32, 64, 4)]
    )
    @pytest.mark.parametrize("impl", ["grouped", "einsum", "dense"])
    def test_grouped_matches_nn_conv(self, rng, k, cin, cout, g, impl):
        from flax import linen as nn

        x = jnp.asarray(rng.standard_normal((2, 40, cin)), jnp.float32)
        ref = nn.Conv(
            cout, (k,), padding="VALID",
            feature_group_count=g, use_bias=False,
        )
        v = ref.init(jax.random.PRNGKey(0), x)
        want = ref.apply(v, x)
        got = common.GroupedConv1D(cout, g, k, impl=impl).apply(
            {"params": {"kernel": v["params"]["kernel"]}}, x
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-6
        )

    def test_dense_grouped_no_cross_group_leak(self, rng):
        """The dense lowering's block-diagonal expansion must keep groups
        independent: output features of group 0 cannot depend on input
        channels of group 1 (falsifiable via input-gradient support)."""
        x = jnp.asarray(rng.standard_normal((1, 16, 8)), jnp.float32)
        m = common.GroupedConv1D(8, 2, 3, impl="dense")
        v = m.init(jax.random.PRNGKey(0), x)

        def group0_sum(xin):
            return m.apply(v, xin)[..., :4].sum()

        gx = np.asarray(jax.grad(group0_sum)(x))
        assert np.abs(gx[..., :4]).max() > 0  # own group: real dependence
        np.testing.assert_array_equal(gx[..., 4:], 0.0)  # other group: none
