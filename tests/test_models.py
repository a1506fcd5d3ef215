"""Model-zoo tests: registration surface, output shapes, parameter parity.

Parameter parity: reference state-dict totals (measured from
pretrained/*.pth) equal our params + batch_stats + one `num_batches_tracked`
scalar per BN layer. Counting uses jax.eval_shape (no compute) so the suite
stays fast.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import seist_tpu
from seist_tpu import taskspec
from seist_tpu.models import api
from seist_tpu.registry import MODELS

seist_tpu.load_all()

ALL_MODELS = [
    "phasenet",
    "eqtransformer",
    "magnet",
    "baz_network",
    "distpt_network",
    "ditingmotion",
] + [f"seist_{s}_{t}" for s in "sml" for t in ("dpk", "pmp", "emg", "baz", "dis")]


def test_registry_has_21_models():
    # API surface parity: SURVEY.md Appendix B / reference README.md:54
    assert set(ALL_MODELS) <= set(MODELS.names())
    assert len(ALL_MODELS) == 21


def _count_with_bn(model, in_samples, in_channels):
    shapes = api.param_shapes(model, in_samples=in_samples, in_channels=in_channels)
    n_params = api.count_params(shapes["params"])
    bn_leaves = jax.tree_util.tree_leaves(shapes.get("batch_stats", {}))
    n_stats = sum(int(np.prod(p.shape)) for p in bn_leaves)
    n_bn_layers = len(bn_leaves) // 2
    return n_params + n_stats + n_bn_layers


@pytest.mark.parametrize(
    "name,ref_total",
    [
        # Reference state-dict numels incl. BN buffers.
        ("seist_s_dpk", 128_981),
        ("seist_m_dpk", 387_620),
        ("seist_l_dpk", 670_681),
        ("seist_l_emg", 537_461),
    ],
)
def test_seist_param_parity(name, ref_total):
    model = api.create_model(name)
    assert _count_with_bn(model, 8192, 3) == ref_total


L_SMALL = 512


@pytest.mark.parametrize(
    "size",
    [
        "s",
        pytest.param("m", marks=pytest.mark.slow),
        pytest.param("l", marks=pytest.mark.slow),
    ],
)
def test_seist_dpk_output_shape(size):
    model = api.create_model(f"seist_{size}_dpk", in_samples=L_SMALL)
    x = jnp.zeros((2, L_SMALL, 3))
    v = api.init_variables(model, in_samples=L_SMALL, batch_size=2)
    out = jax.jit(lambda v, x: model.apply(v, x, train=False))(v, x)
    assert out.shape == (2, L_SMALL, 3)
    # sigmoid outputs are probabilities
    assert float(jnp.min(out)) >= 0.0 and float(jnp.max(out)) <= 1.0


def test_seist_cls_and_reg_heads():
    x = jnp.zeros((2, L_SMALL, 3))
    m_cls = api.create_model("seist_s_pmp", in_samples=L_SMALL)
    v = api.init_variables(m_cls, in_samples=L_SMALL, batch_size=2)
    out = jax.jit(lambda v, x: m_cls.apply(v, x, train=False))(v, x)
    assert out.shape == (2, 2)
    np.testing.assert_allclose(np.asarray(out.sum(-1)), 1.0, atol=1e-5)  # softmax

    m_reg = api.create_model("seist_s_emg", in_samples=L_SMALL)
    v = api.init_variables(m_reg, in_samples=L_SMALL, batch_size=2)
    out = jax.jit(lambda v, x: m_reg.apply(v, x, train=False))(v, x)
    assert out.shape == (2, 1)
    assert 0.0 <= float(out.min()) and float(out.max()) <= 8.0  # sigmoid x 8


def test_phasenet_output_is_softmax():
    model = api.create_model("phasenet")
    x = jnp.zeros((2, 1024, 3))
    v = api.init_variables(model, in_samples=1024, batch_size=2)
    out = jax.jit(lambda v, x: model.apply(v, x, train=False))(v, x)
    assert out.shape == (2, 1024, 3)
    np.testing.assert_allclose(np.asarray(out.sum(-1)), 1.0, atol=1e-5)


def test_eqtransformer_output_shape():
    model = api.create_model("eqtransformer", in_samples=L_SMALL)
    x = jnp.zeros((2, L_SMALL, 3))
    v = api.init_variables(model, in_samples=L_SMALL, batch_size=2)
    out = jax.jit(lambda v, x: model.apply(v, x, train=False))(v, x)
    assert out.shape == (2, L_SMALL, 3)
    assert float(out.min()) >= 0.0 and float(out.max()) <= 1.0  # sigmoid


def test_magnet_output_shape():
    model = api.create_model("magnet")
    x = jnp.zeros((2, 1024, 3))
    v = api.init_variables(model, in_samples=1024, batch_size=2)
    out = jax.jit(lambda v, x: model.apply(v, x, train=False))(v, x)
    assert out.shape == (2, 2)  # (y_hat, log sigma^2)


def test_baz_network_output_shape():
    model = api.create_model("baz_network", in_samples=1024)
    x = jnp.ones((2, 1024, 3)) * jnp.arange(3)[None, None, :]
    v = api.init_variables(model, in_samples=1024, batch_size=2)
    out = jax.jit(lambda v, x: model.apply(v, x, train=False))(v, x)
    assert isinstance(out, tuple) and out[0].shape == (2, 1) and out[1].shape == (2, 1)


def test_baz_cov_features_match_reference_semantics(rng):
    import torch

    from seist_tpu.models.baz_network import _cov_features

    x = rng.normal(size=(2, 64, 3)).astype(np.float32)
    feats = np.asarray(_cov_features(jnp.asarray(x)))  # (N, 2C+1, C)
    # torch-side covariance on channels-first input (ref: baz_network.py:67-77)
    xt = torch.from_numpy(np.moveaxis(x, -1, 1).copy())
    diff = xt - xt.mean(-1, keepdim=True)
    cov_ref = torch.einsum("ncl,ndl->ncd", diff, diff) / (x.shape[1] - 1)
    cov_ref = cov_ref / cov_ref.abs().amax(dim=(-2, -1), keepdim=True)
    np.testing.assert_allclose(
        feats[:, :3, :].transpose(0, 2, 1), cov_ref.numpy(), atol=2e-3
    )


def test_distpt_output_shape():
    model = api.create_model("distpt_network")
    x = jnp.zeros((2, 1024, 3))
    v = api.init_variables(model, in_samples=1024, batch_size=2)
    out = jax.jit(lambda v, x: model.apply(v, x, train=False))(v, x)
    assert out[0].shape == (2, 2) and out[1].shape == (2, 2)


def test_ditingmotion_output_shape():
    model = api.create_model("ditingmotion", in_channels=2, in_samples=128)
    x = jnp.zeros((2, 128, 2))
    v = api.init_variables(model, in_samples=128, in_channels=2, batch_size=2)
    clr, pmp = jax.jit(lambda v, x: model.apply(v, x, train=False))(v, x)
    assert clr.shape == (2, 2) and pmp.shape == (2, 2)


def test_every_model_has_a_task_spec():
    for name in ALL_MODELS:
        if name == "distpt_network":
            # Registered but config-disabled in the reference too
            # (config.py:112-125: no travel-time data in DiTing).
            with pytest.raises(KeyError):
                taskspec.get_task_spec(name)
            continue
        taskspec.get_task_spec(name)


def test_train_mode_uses_dropout_rngs():
    model = api.create_model("seist_s_dpk", in_samples=L_SMALL)
    v = api.init_variables(model, in_samples=L_SMALL)
    x = jnp.ones((2, L_SMALL, 3))
    apply = jax.jit(
        lambda v, x, k: model.apply(
            v, x, train=True, rngs={"dropout": k}, mutable=["batch_stats"]
        )
    )
    out1, _ = apply(v, x, jax.random.PRNGKey(1))
    out2, _ = apply(v, x, jax.random.PRNGKey(2))
    # different dropout keys => different outputs
    assert not np.allclose(np.asarray(out1), np.asarray(out2))


def test_batch_stats_update_in_train_mode():
    model = api.create_model("phasenet")
    v = api.init_variables(model, in_samples=256)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 256, 3)), jnp.float32)
    _, updates = jax.jit(
        lambda v, x, k: model.apply(
            v, x, train=True, rngs={"dropout": k}, mutable=["batch_stats"]
        )
    )(v, x, jax.random.PRNGKey(0))
    before = jax.tree_util.tree_leaves(v["batch_stats"])
    after = jax.tree_util.tree_leaves(updates["batch_stats"])
    assert any(
        not np.allclose(np.asarray(b), np.asarray(a)) for b, a in zip(before, after)
    )


def test_eqt_banded_mask_matches_torch():
    torch = pytest.importorskip("torch")
    for w in (3, 4, 5):
        L = 9
        ref = (
            torch.ones((L, L), dtype=torch.bool)
            .tril(w // 2 - 1)
            .triu(-w // 2)
            .numpy()
        )
        i = np.arange(L)[:, None]
        j = np.arange(L)[None, :]
        ours = (j - i <= w // 2 - 1) & (j - i >= (-w) // 2)
        np.testing.assert_array_equal(ours, ref, err_msg=f"width {w}")


class TestComposedDSConv:
    """DSConvNormAct's composed lowering (one dense conv from the
    in_proj*dconv*pconv triple product) must be checkpoint-identical and
    numerically equivalent to the literal 3-stage pipeline
    (seist_tpu/models/seist.py DSConvNormAct docstring)."""

    def _make(self, impl, stride, k=11):
        from seist_tpu.models.seist import DSConvNormAct

        return DSConvNormAct(
            in_dim=8, out_dim=16, kernel_size=k, stride=stride, impl=impl
        )

    @pytest.mark.parametrize("stride", [1, 2])
    def test_param_tree_and_values_identical(self, stride):
        x = jnp.zeros((2, 64, 3))
        key = jax.random.PRNGKey(0)
        vp = self._make("paths", stride).init(key, x, True)
        vc = self._make("composed", stride).init(key, x, True)
        fp = jax.tree_util.tree_flatten_with_path(vp)[0]
        fc = jax.tree_util.tree_flatten_with_path(vc)[0]
        assert [p for p, _ in fp] == [p for p, _ in fc]
        for (p, a), (_, b) in zip(fp, fc):
            np.testing.assert_array_equal(a, b, err_msg=str(p))

    @pytest.mark.parametrize("stride", [1, 2])
    @pytest.mark.parametrize("train", [False, True])
    def test_outputs_and_stats_match(self, stride, train):
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 63, 3))
        variables = self._make("paths", stride).init(
            jax.random.PRNGKey(0), x, True
        )
        outs = {}
        stats = {}
        for impl in ("paths", "composed"):
            m = self._make(impl, stride)
            if train:
                y, mut = m.apply(variables, x, True, mutable=["batch_stats"])
                stats[impl] = mut["batch_stats"]
            else:
                y = m.apply(variables, x, False)
            outs[impl] = y
        np.testing.assert_allclose(
            outs["paths"], outs["composed"], rtol=2e-5, atol=2e-5
        )
        if train:
            fa = jax.tree_util.tree_flatten_with_path(stats["paths"])[0]
            fb = jax.tree_util.tree_flatten_with_path(stats["composed"])[0]
            assert [p for p, _ in fa] == [p for p, _ in fb]
            for (p, a), (_, b) in zip(fa, fb):
                np.testing.assert_allclose(
                    a, b, rtol=2e-5, atol=2e-5, err_msg=str(p)
                )

    @pytest.mark.parametrize("stride", [1, 2])
    def test_gradients_match(self, stride):
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 48, 3))
        variables = self._make("paths", stride, k=7).init(
            jax.random.PRNGKey(0), x, True
        )

        def loss(impl, params):
            m = self._make(impl, stride, k=7)
            y, _ = m.apply(
                {**variables, "params": params}, x, True,
                mutable=["batch_stats"],
            )
            return jnp.sum(y * jnp.cos(y))

        gp = jax.grad(lambda p: loss("paths", p))(variables["params"])
        gc = jax.grad(lambda p: loss("composed", p))(variables["params"])
        fa = jax.tree_util.tree_flatten_with_path(gp)[0]
        fb = jax.tree_util.tree_flatten_with_path(gc)[0]
        assert [p for p, _ in fa] == [p for p, _ in fb]
        for (p, a), (_, b) in zip(fa, fb):
            np.testing.assert_allclose(
                a, b, rtol=5e-4, atol=5e-5, err_msg=str(p)
            )

    def test_full_model_forward_matches(self):
        import os

        from seist_tpu.models import api

        x = jax.random.normal(jax.random.PRNGKey(2), (1, 512, 3))
        model = api.create_model("seist_s_dpk", in_samples=512)
        variables = model.init(jax.random.PRNGKey(0), x, False)
        prev = os.environ.get("SEIST_DSCONV_IMPL")
        try:
            os.environ["SEIST_DSCONV_IMPL"] = "paths"
            y_paths = model.apply(variables, x, False)
            os.environ["SEIST_DSCONV_IMPL"] = "composed"
            y_comp = model.apply(variables, x, False)
        finally:
            if prev is None:
                os.environ.pop("SEIST_DSCONV_IMPL", None)
            else:
                os.environ["SEIST_DSCONV_IMPL"] = prev
        np.testing.assert_allclose(y_paths, y_comp, rtol=1e-5, atol=1e-5)


class TestMergedStem:
    """StemBlock's merged lowering must be checkpoint-identical and
    numerically equivalent to the literal 3-path architecture
    (seist_tpu/models/seist.py StemBlock docstring)."""

    def _make(self, impl, stride):
        from seist_tpu.models.seist import StemBlock

        return StemBlock(
            in_dim=16, out_dim=16, kernel_size=11, stride=stride, impl=impl
        )

    @pytest.mark.parametrize("other", ["merged", "fused"])
    @pytest.mark.parametrize("stride", [1, 2])
    def test_param_tree_and_values_identical(self, stride, other):
        x = jnp.zeros((2, 64, 3))
        key = jax.random.PRNGKey(0)
        vp = self._make("paths", stride).init(key, x, True)
        vm = self._make(other, stride).init(key, x, True)
        fp = jax.tree_util.tree_flatten_with_path(vp)[0]
        fm = jax.tree_util.tree_flatten_with_path(vm)[0]
        assert [p for p, _ in fp] == [p for p, _ in fm]
        for (p, a), (_, b) in zip(fp, fm):
            np.testing.assert_array_equal(a, b, err_msg=str(p))

    @pytest.mark.parametrize("stride", [1, 2])
    @pytest.mark.parametrize("train", [False, True])
    def test_outputs_and_stats_match(self, stride, train):
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 63, 3))
        variables = self._make("paths", stride).init(jax.random.PRNGKey(0), x, True)
        outs = {}
        stats = {}
        for impl in ("paths", "merged", "fused"):
            m = self._make(impl, stride)
            if train:
                y, mut = m.apply(variables, x, True, mutable=["batch_stats"])
                stats[impl] = mut["batch_stats"]
            else:
                y = m.apply(variables, x, False)
            outs[impl] = y
        for other in ("merged", "fused"):
            np.testing.assert_allclose(
                outs["paths"], outs[other], rtol=2e-5, atol=2e-5,
                err_msg=other,
            )
            if train:
                fa = jax.tree_util.tree_flatten_with_path(stats["paths"])[0]
                fb = jax.tree_util.tree_flatten_with_path(stats[other])[0]
                assert [p for p, _ in fa] == [p for p, _ in fb]
                for (p, a), (_, b) in zip(fa, fb):
                    np.testing.assert_allclose(
                        a, b, rtol=2e-5, atol=2e-5, err_msg=f"{other}:{p}"
                    )

    def test_full_model_forward_matches(self):
        import os

        from seist_tpu.models import api

        x = jax.random.normal(jax.random.PRNGKey(2), (1, 512, 3))
        model = api.create_model("seist_s_dpk", in_samples=512)
        variables = model.init(jax.random.PRNGKey(0), x, False)
        prev = os.environ.get("SEIST_STEM_IMPL")
        try:
            os.environ["SEIST_STEM_IMPL"] = "paths"
            y_paths = model.apply(variables, x, False)
            os.environ["SEIST_STEM_IMPL"] = "merged"
            y_merged = model.apply(variables, x, False)
        finally:
            if prev is None:
                os.environ.pop("SEIST_STEM_IMPL", None)
            else:
                os.environ["SEIST_STEM_IMPL"] = prev
        np.testing.assert_allclose(y_paths, y_merged, rtol=1e-5, atol=1e-5)


class TestChannelPad:
    """SEIST_CHANNEL_PAD (off by default) pads composed/fused dense-conv
    out-channels to a lane multiple and slices the zeros away — values,
    grads, and the checkpoint tree must be IDENTICAL to the unpadded
    lowering (models/common.py pad_kernel_out_channels)."""

    @pytest.mark.parametrize("mult", ["8", "128"])
    def test_full_model_forward_identical(self, mult, monkeypatch):
        from seist_tpu.models import api

        x = jax.random.normal(jax.random.PRNGKey(2), (1, 512, 3))
        model = api.create_model("seist_s_dpk", in_samples=512)
        variables = model.init(jax.random.PRNGKey(0), x, False)
        monkeypatch.setenv("SEIST_DSCONV_IMPL", "composed")
        monkeypatch.setenv("SEIST_STEM_IMPL", "fused")
        monkeypatch.delenv("SEIST_CHANNEL_PAD", raising=False)
        y_base = model.apply(variables, x, False)
        monkeypatch.setenv("SEIST_CHANNEL_PAD", mult)
        y_pad = model.apply(variables, x, False)
        # The padded columns are zeros, but a different backend tiling
        # may reorder the real columns' accumulations — tight allclose,
        # not bitwise (the whole point of the flag is to change tiling).
        np.testing.assert_allclose(
            np.asarray(y_base), np.asarray(y_pad), rtol=1e-6, atol=1e-7
        )

    def test_train_step_gradients_identical(self, monkeypatch):
        from seist_tpu.models.seist import DSConvNormAct

        x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 3))
        m = DSConvNormAct(16, 24, 7, 2, impl="composed")
        variables = m.init(jax.random.PRNGKey(0), x, True)

        def loss(params):
            y, _ = m.apply(
                {**variables, "params": params}, x, True,
                mutable=["batch_stats"],
            )
            return jnp.sum(y * jnp.cos(y))

        monkeypatch.delenv("SEIST_CHANNEL_PAD", raising=False)
        g_base = jax.grad(loss)(variables["params"])
        monkeypatch.setenv("SEIST_CHANNEL_PAD", "128")
        g_pad = jax.grad(loss)(variables["params"])
        fa = jax.tree_util.tree_flatten_with_path(g_base)[0]
        fb = jax.tree_util.tree_flatten_with_path(g_pad)[0]
        assert [p for p, _ in fa] == [p for p, _ in fb]
        for (p, a), (_, b) in zip(fa, fb):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7, err_msg=str(p))
