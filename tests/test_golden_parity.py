"""Golden-parity tests: our flax models must reproduce the reference's
shipped pretrained checkpoints (SURVEY.md §7.9).

For each of the 18 ``pretrained/*.pth`` artifacts: convert the torch
state-dict with tools/parity.py, forward a fixed waveform through our model,
and compare against the torch reference model's output (reference imported
read-only from /root/reference, with a timm.DropPath stub — identity at
eval). Tolerance 1e-4 absolute on probability/regression outputs; observed
diffs are ~1e-5 (fp32 op-order noise).
"""

import os
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import seist_tpu  # noqa: E402
from seist_tpu.models import api  # noqa: E402

seist_tpu.load_all()

REFERENCE = "/root/reference"
PRETRAINED = os.path.join(REFERENCE, "pretrained")

pytestmark = [
    pytest.mark.slow,  # 18 ckpts x 8192-sample forwards + torch reference
    pytest.mark.skipif(
        not os.path.isdir(PRETRAINED),
        reason="reference pretrained weights absent",
    ),
]

CHECKPOINTS = sorted(
    f[: -len(".pth")] for f in os.listdir(PRETRAINED) if f.endswith(".pth")
) if os.path.isdir(PRETRAINED) else []


def _stub_timm():
    import torch.nn as tnn

    class DropPath(tnn.Module):  # identity at eval — parity-safe
        def __init__(self, drop_prob=None):
            super().__init__()

        def forward(self, x):
            return x

    timm = types.ModuleType("timm")
    models_m = types.ModuleType("timm.models")
    layers_m = types.ModuleType("timm.models.layers")
    layers_m.DropPath = DropPath
    sys.modules.setdefault("timm", timm)
    sys.modules.setdefault("timm.models", models_m)
    sys.modules.setdefault("timm.models.layers", layers_m)


@pytest.fixture(scope="module")
def torch_models():
    _stub_timm()
    if REFERENCE not in sys.path:
        sys.path.insert(0, REFERENCE)
    from models import create_model as torch_create  # reference registry

    return torch_create


def _as_tuple(x):
    return x if isinstance(x, (tuple, list)) else (x,)


@pytest.mark.parametrize("ckpt", CHECKPOINTS)
def test_pretrained_forward_parity(ckpt, torch_models):
    import torch

    from parity import convert_state_dict

    model_name = ckpt.rsplit("_", 1)[0]  # strip _diting/_pnw suffix

    sd = torch.load(
        os.path.join(PRETRAINED, f"{ckpt}.pth"),
        map_location="cpu",
        weights_only=True,
    )
    model = api.create_model(model_name, in_samples=8192)
    shapes = api.param_shapes(model, in_samples=8192)
    variables = convert_state_dict(sd, shapes)

    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 8192, 3)).astype(np.float32)
    ours = _as_tuple(model.apply(variables, x, train=False))

    tm = torch_models(model_name, in_channels=3, in_samples=8192)
    tm.load_state_dict(sd)
    tm.eval()
    with torch.no_grad():
        ref = _as_tuple(tm(torch.from_numpy(x.transpose(0, 2, 1))))

    assert len(ours) == len(ref)
    for o, r in zip(ours, ref):
        o = np.asarray(o)
        r = r.numpy()
        if o.ndim == 3:  # dense outputs: ours (N, L, C), torch (N, C, L)
            r = r.transpose(0, 2, 1)
        assert o.shape == r.shape, (o.shape, r.shape)
        np.testing.assert_allclose(o, r, atol=1e-4, rtol=1e-3)


# ----------------------------------------------------- gradient-level parity
# Forward parity can't catch a silent backward divergence (BN momentum,
# DropPath scaling, interpolate vjp...). These tests push ONE identical
# batch through the torch reference (its own loss, ref train.py:108-111)
# and through our flax step with converted weights, then compare loss and
# per-leaf gradients.

L_GRAD = 1024
# eqtransformer exercises the scan-BiLSTM + additive-attention backward —
# the converter splits torch's fused LSTM gates into OptimizedLSTMCell
# leaves (tools/parity.py::_convert_lstm_group).
# magnet covers the fused-LSTM split at hidden 100 + MousaviLoss; ditingmotion
# covers CombConv/side-fusion + dual Focal loss (and pinned the channel-major
# flatten fix in models/ditingmotion.py::SideLayer). baz_network is excluded:
# its eigen feature branch uses eigh on the symmetric covariance where the
# reference uses no-grad general eig — eigenvalue ordering/eigenvector sign
# conventions differ, so forward activations (and hence all grads) diverge by
# design (the branch is no-grad in BOTH frameworks).
GRAD_MODELS = [
    "phasenet",
    "seist_s_dpk",
    "seist_m_dpk",
    "eqtransformer",
    "magnet",
    "ditingmotion",
]


def _grad_case(model_name):
    """(x, in_channels, y) for one gradient-parity case; the torch-side
    target is derived from ``y`` in the test (transpose for dense labels,
    per-element tensors for tuple labels)."""
    rng = np.random.default_rng(7)
    if model_name == "magnet":
        x = rng.standard_normal((2, L_GRAD, 3)).astype(np.float32)
        y = rng.uniform(1.0, 6.0, (2, 1)).astype(np.float32)
        return x, 3, y
    if model_name == "ditingmotion":
        x = rng.standard_normal((2, L_GRAD, 2)).astype(np.float32)
        clr = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 2)]
        pmp = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 2)]
        return x, 2, (clr, pmp)
    x, y = _dpk_batch()
    return x, 3, y


def _dpk_batch(batch=2, length=L_GRAD):
    rng = np.random.default_rng(42)
    x = rng.standard_normal((batch, length, 3)).astype(np.float32)
    y = np.zeros((batch, length, 3), np.float32)
    y[:, length // 4, 1] = 1.0
    y[:, length // 2, 2] = 1.0
    y[..., 0] = 1.0 - y[..., 1] - y[..., 2]
    return x, y


def _torch_loss_for(model_name):
    """The reference's own loss construction (ref config.py:421-432)."""
    if REFERENCE not in sys.path:
        sys.path.insert(0, REFERENCE)
    from config import Config  # reference, read-only

    return Config.get_loss(model_name)


def _flat_grads_from_torch(tm, shapes):
    """torch .grad tensors -> our flax tree layout via tools/parity.py."""
    from parity import _fit_leaf, torch_key_to_flax

    import jax

    flat_target = {}
    leaves = jax.tree_util.tree_flatten_with_path(shapes["params"])[0]
    for path, leaf in leaves:
        key = tuple(str(k.key) for k in path)
        flat_target[key] = np.shape(leaf)

    from parity import _convert_lstm_group, collect_lstm_leaf

    out = {}
    lstm_groups = {}
    for tkey, p in tm.named_parameters():
        if p.grad is None:
            continue
        mapped = torch_key_to_flax(tkey)
        assert mapped is not None, tkey
        coll, path = mapped
        if coll != "params":
            continue
        if collect_lstm_leaf(path, p.grad.detach().cpu().numpy(), lstm_groups):
            continue
        out[path] = _fit_leaf(
            p.grad.detach().cpu().numpy(), flat_target[path], tkey
        )
    if lstm_groups:
        ft = {("params", k): v for k, v in flat_target.items()}
        for (prefix, direction), leaves in lstm_groups.items():
            # The gate-split transform is linear so it maps grads too, with
            # one twist: flax's single bias is torch's bias_ih + bias_hh, so
            # dL/d(flax bias) == dL/d(bias_ih) == dL/d(bias_hh); the
            # converter SUMS the two bias leaves, so zero one side.
            leaves = dict(leaves)
            leaves["bias_hh"] = np.zeros_like(leaves["bias_hh"])
            for (_, pth), val in _convert_lstm_group(
                prefix, direction, leaves, ft
            ).items():
                out[pth] = val
    return out


def _torch_state_dict(model_name, torch_models, in_channels=3):
    """Shipped pretrained weights for seist models; the 18 published
    checkpoints are all seist variants, so other models use a seeded
    random-init torch model's state-dict instead."""
    import torch

    path = os.path.join(PRETRAINED, f"{model_name}_diting.pth")
    if os.path.exists(path):
        return torch.load(path, map_location="cpu", weights_only=True)
    torch.manual_seed(0)
    tm = torch_models(model_name, in_channels=in_channels, in_samples=L_GRAD)
    return tm.state_dict()


@pytest.mark.parametrize("model_name", GRAD_MODELS)
def test_gradient_parity_eval_mode(model_name, torch_models):
    """Grads of loss(model(x)) w.r.t. every param match torch (eval mode:
    running BN stats, no dropout — isolates the backward of conv /
    attention / interpolate / pooling)."""
    import jax
    import torch

    from parity import convert_state_dict

    from seist_tpu import taskspec

    x, in_ch, y = _grad_case(model_name)
    sd = _torch_state_dict(model_name, torch_models, in_channels=in_ch)
    model = api.create_model(model_name, in_samples=L_GRAD, in_channels=in_ch)
    shapes = api.param_shapes(model, in_samples=L_GRAD, in_channels=in_ch)
    variables = convert_state_dict(sd, shapes)

    flax_loss = taskspec.make_loss(model_name)
    spec = taskspec.get_task_spec(model_name)

    def loss_fn(params):
        var = {"params": params}
        if "batch_stats" in variables:  # ditingmotion/magnet have no BN
            var["batch_stats"] = variables["batch_stats"]
        out = model.apply(
            var,
            x,
            train=False,
        )
        o, t = out, y
        if spec.outputs_transform_for_loss is not None:
            o = spec.outputs_transform_for_loss(o)
        return flax_loss(o, t)

    our_loss, our_grads = jax.value_and_grad(loss_fn)(variables["params"])

    tm = torch_models(model_name, in_channels=in_ch, in_samples=L_GRAD)
    tm.load_state_dict(sd)
    tm.eval()
    tl_fn = _torch_loss_for(model_name)
    tx = torch.from_numpy(x.transpose(0, 2, 1))
    if isinstance(y, tuple):
        ty = [torch.from_numpy(t) for t in y]
    else:
        ty = torch.from_numpy(y)
        ty = ty.permute(0, 2, 1) if ty.ndim == 3 else ty
    t_out = tm(tx)
    t_loss = tl_fn(t_out, ty)
    t_loss.backward()

    np.testing.assert_allclose(
        float(our_loss), float(t_loss.detach()), rtol=1e-5, atol=1e-6
    )

    t_grads = _flat_grads_from_torch(tm, shapes)
    checked = _compare_grad_trees(our_grads, t_grads)
    assert checked > 10


def _compare_grad_trees(
    our_grads, t_grads, cos_tol=0.9999, rel_tol=5e-3, expect_zero=None
):
    """Per-leaf comparison. Leaves with MATHEMATICALLY zero gradients are
    exempted BY NAME (never by a broad magnitude heuristic, which could
    silently exempt a corrupted small leaf):

    * ``k_proj/bias`` always: softmax is invariant to a uniform key shift.
    * ``attn/ba`` always (eqtransformer): the additive-attention score bias
      is a uniform shift under the softmax over L (ref
      eqtransformer.py:135-198), so its gradient is identically 0.
    * ``expect_zero(key)`` per call: e.g. train-mode conv biases feeding
      straight into BatchNorm — the batch-mean subtraction cancels a
      uniform bias exactly, so its gradient is identically 0.

    Exempted leaves are still asserted to BE ~zero on both sides.
    """
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(our_grads)[0]
    gscale = max(
        (np.abs(t_grads[k]).max() for k in t_grads), default=1.0
    )
    checked = 0
    for path, g in leaves:
        key = tuple(str(k.key) for k in path)
        assert key in t_grads, f"missing torch grad for {key}"
        a = np.asarray(g).ravel()
        b = t_grads[key].ravel()
        both_tiny = max(np.abs(a).max(), np.abs(b).max()) < 1e-6 * gscale
        if key[-2:] == ("k_proj", "bias") or key[-2:] == ("attn", "ba") or (
            expect_zero is not None and expect_zero(key)
        ):
            assert both_tiny, f"{key}: expected ~0 grad"
            continue
        if np.abs(a).max() < 1e-20 and np.abs(b).max() < 1e-20:
            continue  # exactly-zero pair (e.g. genuinely unused param)
        cos = float(
            np.dot(a, b) / (np.linalg.norm(a) * np.linalg.norm(b))
        )
        assert cos > cos_tol, f"{key}: grad cosine {cos}"
        scale = max(np.abs(b).max(), 1e-12)
        assert np.abs(a - b).max() / scale < rel_tol, (
            f"{key}: rel grad err {np.abs(a - b).max() / scale}"
        )
        checked += 1
    return checked


def test_gradient_and_bn_parity_train_mode(torch_models):
    """Train-mode parity on phasenet (dropout-free): batch-stat BN forward,
    gradients, AND the updated running stats (BN momentum semantics,
    ref train.py:108-111 + SyncBN analogue)."""
    import jax
    import torch

    from parity import convert_state_dict

    from seist_tpu import taskspec

    model_name = "phasenet"
    sd = _torch_state_dict(model_name, torch_models)
    # drop_rate=0 on BOTH sides: train mode would otherwise draw different
    # dropout masks per framework and nothing would be comparable.
    model = api.create_model(model_name, in_samples=L_GRAD, drop_rate=0.0)
    shapes = api.param_shapes(model, in_samples=L_GRAD)
    variables = convert_state_dict(sd, shapes)
    x, y = _dpk_batch()
    flax_loss = taskspec.make_loss(model_name)

    def loss_fn(params):
        out, mutated = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            x,
            train=True,
            mutable=["batch_stats"],
            rngs={"dropout": jax.random.PRNGKey(0)},
        )
        return flax_loss(out, y), mutated["batch_stats"]

    (our_loss, new_stats), our_grads = jax.value_and_grad(
        loss_fn, has_aux=True
    )(variables["params"])

    tm = torch_models(
        model_name, in_channels=3, in_samples=L_GRAD, drop_rate=0.0
    )
    tm.load_state_dict(sd)
    tm.train()
    tl_fn = _torch_loss_for(model_name)
    t_out = tm(torch.from_numpy(x.transpose(0, 2, 1)))
    t_loss = tl_fn(t_out, torch.from_numpy(y.transpose(0, 2, 1)))
    t_loss.backward()

    np.testing.assert_allclose(
        float(our_loss), float(t_loss.detach()), rtol=1e-5, atol=1e-6
    )

    # Updated running stats must match (momentum 0.1 torch == 0.9 flax).
    t_sd = tm.state_dict()
    from parity import torch_key_to_flax

    flat_new = {
        tuple(str(k.key) for k in path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(new_stats)[0]
    }
    stats_checked = 0
    for tkey, tval in t_sd.items():
        mapped = torch_key_to_flax(tkey)
        if mapped is None or mapped[0] != "batch_stats":
            continue
        ours_leaf = flat_new[mapped[1]]
        np.testing.assert_allclose(
            ours_leaf, tval.numpy(), rtol=1e-4, atol=1e-5,
            err_msg=f"running stat {tkey}",
        )
        stats_checked += 1
    assert stats_checked > 10

    t_grads = _flat_grads_from_torch(tm, shapes)

    # Train-mode BN cancels any uniform bias added by the conv right before
    # it (batch-mean subtraction), so every conv bias except the final
    # conv_out (no BN after it) has an identically-zero gradient.
    def bn_cancelled_bias(key):
        return (
            key[-1] == "bias"
            and key[-2].startswith("conv")
            and key[-2] != "conv_out"
        )

    assert (
        _compare_grad_trees(our_grads, t_grads, expect_zero=bn_cancelled_bias)
        > 10
    )


@pytest.mark.slow
@pytest.mark.parametrize(
    "env",
    [
        # round-2 defaults-on-TPU: shift-FMA depthwise + block-diag-dense
        # grouped, with the per-path stems
        {"SEIST_DWCONV_IMPL": "shift", "SEIST_GCONV_IMPL": "dense"},
        # composed DSConv (the TPU default since the triple-product
        # lowering) + fused one-conv stem, on published weights
        {
            "SEIST_DSCONV_IMPL": "composed",
            "SEIST_STEM_IMPL": "fused",
            "SEIST_GCONV_IMPL": "dense",
        },
    ],
    ids=["shift+dense", "composed+fused"],
)
def test_pretrained_forward_parity_tpu_lowerings(torch_models, monkeypatch, env):
    """Golden parity THROUGH the TPU-default conv lowerings
    (models/common.py, models/seist.py DSConvNormAct/StemBlock). Off-TPU
    the defaults fall back to native grouped convs, so without forcing the
    env this path would only ever be exercised on real hardware."""
    import torch

    from parity import convert_state_dict

    for k, v in env.items():
        monkeypatch.setenv(k, v)

    ckpt = "seist_s_dpk_diting"
    model_name = "seist_s_dpk"
    sd = torch.load(
        os.path.join(PRETRAINED, f"{ckpt}.pth"),
        map_location="cpu",
        weights_only=True,
    )
    model = api.create_model(model_name, in_samples=8192)
    shapes = api.param_shapes(model, in_samples=8192)
    variables = convert_state_dict(sd, shapes)

    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 8192, 3)).astype(np.float32)
    ours = np.asarray(model.apply(variables, x, train=False))

    tm = torch_models(model_name, in_channels=3, in_samples=8192)
    tm.load_state_dict(sd)
    tm.eval()
    with torch.no_grad():
        ref = tm(torch.from_numpy(x.transpose(0, 2, 1))).numpy()
    np.testing.assert_allclose(ours, ref.transpose(0, 2, 1), atol=1e-4, rtol=1e-3)


def test_distpt_random_init_forward_parity(torch_models):
    """distpt_network has no task spec (the reference ships its config
    commented out, ref config.py:112-125), so it gets forward parity with
    a seeded random-init torch state-dict instead of a gradient test —
    covering the causal-TCN trunk and both regression heads."""
    import torch

    from parity import convert_state_dict

    sd = _torch_state_dict("distpt_network", torch_models)
    model = api.create_model("distpt_network", in_samples=L_GRAD)
    shapes = api.param_shapes(model, in_samples=L_GRAD)
    variables = convert_state_dict(sd, shapes)

    rng = np.random.default_rng(3)
    x = rng.standard_normal((2, L_GRAD, 3)).astype(np.float32)
    ours = _as_tuple(model.apply(variables, x, train=False))

    tm = torch_models("distpt_network", in_channels=3, in_samples=L_GRAD)
    tm.load_state_dict(sd)
    tm.eval()
    with torch.no_grad():
        ref = _as_tuple(tm(torch.from_numpy(x.transpose(0, 2, 1))))

    assert len(ours) == len(ref)
    for o, r in zip(ours, ref):
        # Both heads are (N, 2) regression outputs — no layout transpose.
        np.testing.assert_allclose(
            np.asarray(o), r.numpy(), atol=1e-5, rtol=1e-4
        )
