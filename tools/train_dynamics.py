"""Training-dynamics parity harness: torch reference vs seist_tpu.

Forward/gradient parity (tools/parity.py) proves single-step math; this tool
probes what those tests cannot see — BN-momentum convention, LR-schedule
shape, optimizer-epsilon, loss-scaling drift — by training BOTH frameworks
from the IDENTICAL initialization on byte-identical fixture batches in the
same order with the same cyclic LR schedule, and recording the full loss
trajectories:

  * per-step train loss (ref training/train.py:90-135: loss on the train=True
    forward of each batch, recorded before the optimizer step applies)
  * per-epoch val loss (ref training/train.py:397-410 -> validate.py:54-127:
    eval-mode forward, which runs on BN *running* stats — the only place a
    BN-momentum drift can show up)

Models (--model): phasenet (plain conv/BN/softmax/CE), seist_s_dpk (the
flagship family: multi-path stems, grouped convs, pooled attention,
DropPath residuals, BCE), eqtransformer (scan-BiLSTM + banded additive
attention — the recurrent dynamics), magnet (conv+BiLSTM regression
under the sum-reduced MousaviLoss, with the val-MAE metric),
ditingmotion ((z, dz) input into dual softmax heads under
CombinationLoss of two FocalLosses — the multi-head focal family),
seist_s_pmp (classification head, CE, with the accuracy metric), and
seist_s_dpk_droppath (stochastic depth ON with the per-sample DropPath
uniforms injected identically on both sides). The
zero-drop lanes zero every drop rate because free-running dropout masks
are framework-RNG-specific; the droppath lane instead shares the masks,
closing that excluded axis. Everything else under the
reference's CyclicLR (train.py:343-354) is deterministic and directly
comparable. Each epoch also records per-epoch val metrics through ONE
shared numpy scorer (P/S pick F1; accuracy for pmp and the motion
polarity head; magnitude-head MAE for the magnet regression lane).

Usage (each side prints one JSON line and optionally writes it to --out):
    python tools/train_dynamics.py --side torch --out /tmp/torch.json
    python tools/train_dynamics.py --side jax --init /tmp/dyn_init.npz \
        --out /tmp/jax.json

The torch side writes its INITIAL state-dict to --init (npz) so the jax side
trains from the converted identical weights. tests/test_train_dynamics.py
runs both and asserts the trajectories agree within tolerance.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# One config both sides share — keep in lockstep with the test.
CFG = {
    "model": "phasenet",
    "in_samples": 512,
    "batch": 8,
    "steps_per_epoch": 8,
    "epochs": 6,
    "val_n": 32,
    "base_lr": 8e-5,
    "max_lr": 1e-3,
    "warmup_steps": 16,
    "down_steps": 32,
    "data_seed": 123,
    "init_seed": 7,
}

# Per-model specifics: kwargs that zero every dropout (masks are
# framework-RNG-specific and must be excluded from a trajectory
# comparison; both factories accept the same names), the label layout,
# and the reference loss. phasenet: softmax CE over (non, ppk, spk)
# (ref config.py:67-75); seist dpk family: sigmoid BCE over
# (det, ppk, spk) with weights [[.5],[1],[1]] (ref config.py:138) —
# covering the flagship architecture's attention / DropPath / grouped
# convs / multi-stem dynamics, not just phasenet's plain conv+BN.
MODELS = {
    "phasenet": {
        "zero_drop_kwargs": {"drop_rate": 0.0},
        "labels": "non_ppk_spk",
        "ref_loss": "ce",
    },
    "seist_s_dpk": {
        "zero_drop_kwargs": {
            "path_drop_rate": 0.0,
            "attn_drop_rate": 0.0,
            "key_drop_rate": 0.0,
            "mlp_drop_rate": 0.0,
            "other_drop_rate": 0.0,
        },
        "labels": "det_ppk_spk",
        "ref_loss": "bce_dpk",
    },
    # EQTransformer lane: scan-BiLSTM + banded additive attention + 3
    # decoders under the same BCE/CyclicLR — the recurrent-model
    # dynamics (ref eqtransformer.py:532 drop_rate=0.1 zeroed; L1 grad
    # hooks default-off in both frameworks).
    "eqtransformer": {
        "zero_drop_kwargs": {"drop_rate": 0.0},
        "labels": "det_ppk_spk",
        "ref_loss": "bce_dpk",
    },
    # Multi-head focal lane: DiTingMotion — (z, dz) 2-channel input into
    # two softmax heads (clarity, polarity) under CombinationLoss of two
    # FocalLosses (ref config.py:127-135) — the last loss family. The
    # polarity class is the P-wavelet sign (learnable); clarity is an
    # independent random class (no signal by construction — its loss
    # floors, which both sides must agree on too).
    "ditingmotion": {
        "zero_drop_kwargs": {"drop_rate": 0.0},
        "labels": "clr_pmp_onehot",
        "ref_loss": "focal_combo",
        "in_channels": 2,
    },
    # Regression lane: MagNet — conv+BiLSTM into (mag, log-var) under the
    # sum-reduced MousaviLoss (ref loss.py:193-210), the remaining loss
    # family (regression + heteroscedastic sum reduction). The synthetic
    # magnitude IS the P-wavelet amplitude (make_data), so it is
    # learnable; the per-epoch metric is val MAE on the mag head.
    "magnet": {
        "zero_drop_kwargs": {"drop_rate": 0.0},
        "labels": "emg_value",
        "ref_loss": "mousavi",
        # Why this lane diverges faster than every other (measured, not
        # guessed): at the shared init the frameworks' gradients agree
        # to 1.2e-6 worst-leaf, but Adam's first updates are
        # ~lr*sign(g) — coordinates where g is near zero FLIP SIGN
        # under fp-level noise, giving macroscopic 2*lr parameter
        # deltas. The dense-loss lanes average that away over 8192x3
        # outputs; MagNet's sum-reduced scalar objective (plus a
        # log-var head with large curvature at init) feels it
        # immediately: step-0 loss exact, step-1 rel drift ~5e-4
        # regardless of LR. A gentler ceiling (identical on both
        # sides) keeps the trajectory in a comparable regime.
        "cfg_overrides": {"max_lr": 3e-4},
    },
    # Classification lane (metric half): first-motion
    # polarity, CE over a (N, 2) softmax — the accuracy-metric dynamics.
    # The synthetic data encodes the class as the SIGN of the P wavelet
    # (make_data), so polarity is learnable from the waveform.
    "seist_s_pmp": {
        "zero_drop_kwargs": {
            "path_drop_rate": 0.0,
            "attn_drop_rate": 0.0,
            "key_drop_rate": 0.0,
            "mlp_drop_rate": 0.0,
            "other_drop_rate": 0.0,
        },
        "labels": "pmp_onehot",
        "ref_loss": "ce_pmp",
    },
    # Dropout-ON lane: stochastic depth active, with the
    # per-sample DropPath uniforms INJECTED identically on both sides
    # (torch: the timm-stub's DropPath.inject; jax: models/common.py
    # droppath_mask_injection) — the technique ring attention's
    # dropout-parity test already uses, applied cross-framework. Element
    # dropouts stay 0: their masks live in layout-specific activations
    # and (for attention probs) inside the fused kernel's counter PRNG.
    "seist_s_dpk_droppath": {
        "factory": "seist_s_dpk",
        "zero_drop_kwargs": {
            "path_drop_rate": 0.2,
            "attn_drop_rate": 0.0,
            "key_drop_rate": 0.0,
            "mlp_drop_rate": 0.0,
            "other_drop_rate": 0.0,
        },
        "labels": "det_ppk_spk",
        "ref_loss": "bce_dpk",
        "inject_droppath": True,
    },
}

# Rows available per forward for injected DropPath uniforms; each call
# consumes one row, both sides in call order. Far above seist_s's actual
# call count (asserted equal across sides by the test).
MAX_DROPPATH_CALLS = 64


def droppath_uniforms(cfg: dict, global_step: int) -> np.ndarray:
    """The SHARED per-step uniform draws for injected DropPath — both
    sides regenerate this exact array from the config seed."""
    rng = np.random.default_rng([cfg["data_seed"], 777, global_step])
    return rng.random((MAX_DROPPATH_CALLS, cfg["batch"]), dtype=np.float32)


def class_accuracy(probs_nc, true_cls):
    """argmax accuracy on (N, num_classes) eval-mode probabilities — the
    shared scorer for the pmp lane (both sides run this exact code)."""
    return round(
        float((np.argmax(probs_nc, axis=1) == np.asarray(true_cls)).mean()), 4
    )


def value_mae(preds_n2, true_vals):
    """MAE of the magnitude head (column 0 of MagNet's (mag, log-var)
    output) — the shared scorer for the emg regression lane."""
    return round(
        float(
            np.mean(np.abs(np.asarray(preds_n2)[:, 0] - np.asarray(true_vals)))
        ),
        4,
    )


def pick_f1(probs_nlc, true_p, true_s, thresh=0.3, tol=25):
    """P/S pick F1 on eval-mode probabilities — the ONE scorer both sides
    run, so the metric trajectories are comparable by construction.
    ``probs_nlc``: (N, L, 3) channels-last with (det|non, ppk, spk);
    per trace: the argmax of a phase curve is the pick when it clears
    ``thresh``, a hit when within ``tol`` samples of the true arrival
    (ref utils/metrics.py's greedy match at its default tolerance)."""
    out = {}
    for name, ch, true in (("p", 1, true_p), ("s", 2, true_s)):
        tp = fp = fn = 0
        for i in range(probs_nlc.shape[0]):
            curve = probs_nlc[i, :, ch]
            j = int(np.argmax(curve))
            if curve[j] < thresh:
                fn += 1
            elif abs(j - int(true[i])) <= tol:
                tp += 1
            else:
                fp += 1
                fn += 1
        out[name] = round(2 * tp / max(2 * tp + fp + fn, 1), 4)
    return out


def lane_cfg(model: str, base=CFG) -> dict:
    """The ONE place a lane's effective config is assembled: CFG +
    the lane's cfg_overrides (e.g. magnet's gentler max_lr). run_torch
    and run_jax re-apply it defensively (idempotent), so direct callers
    that build ``dict(CFG, model=...)`` still train at the calibrated
    config."""
    cfg = dict(base, model=model)
    cfg.update(MODELS[model].get("cfg_overrides", {}))
    return cfg


def make_data(cfg=CFG):
    """Deterministic synthetic picks, identical bytes for both sides.

    Returns (x, y) with torch layout (N, C, L) fp32; the jax side
    transposes to channels-last. Labels are (non, ppk, spk) prob curves
    (gaussian sigma=10, the reference's label quirk preprocess.py:698).
    """
    n = cfg["batch"] * cfg["steps_per_epoch"] + cfg["val_n"]
    L = cfg["in_samples"]
    rng = np.random.default_rng(cfg["data_seed"])
    t = np.arange(L, dtype=np.float32)
    x = rng.standard_normal((n, 3, L)).astype(np.float32) * 0.1
    tp = rng.integers(L // 8, L // 2, size=n)
    ts = tp + rng.integers(L // 16, L // 4, size=n)
    labels_kind = MODELS[cfg["model"]]["labels"]
    is_pmp = labels_kind == "pmp_onehot"
    is_emg = labels_kind == "emg_value"
    is_motion = labels_kind == "clr_pmp_onehot"
    n_train = cfg["batch"] * cfg["steps_per_epoch"]
    # pmp lane: the class IS the P-wavelet polarity, so accuracy is
    # learnable from the waveform (class 1 flips the P onset sign).
    # emg lane: the magnitude IS the P-wavelet amplitude (relative to
    # the fixed noise floor, which survives per-sample normalization).
    # Both draws happen unconditionally AFTER every draw the other lanes
    # consume, so their data bytes are unchanged (asserted by the
    # byte-stability check in this file's history).
    cls = rng.integers(0, 2, size=n)
    amp = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    clr = rng.integers(0, 2, size=n)  # motion lane only; drawn last
    pol = (1.0 - 2.0 * cls) if (is_pmp or is_motion) else np.ones(n)
    scale = amp if is_emg else np.ones(n, np.float32)
    y = np.zeros((n, 3, L), np.float32)
    for i in range(n):
        env_p = np.where(t >= tp[i], np.exp(-(t - tp[i]) / (L / 8)), 0.0)
        env_s = np.where(t >= ts[i], np.exp(-(t - ts[i]) / (L / 8)), 0.0)
        x[i] += scale[i] * pol[i] * np.sin(2 * np.pi * t / 11.0) * env_p
        x[i, 1:] += 1.5 * np.sin(2 * np.pi * t / 17.0) * env_s
        if not (is_pmp or is_emg or is_motion):
            y[i, 1] = np.exp(-((t - tp[i]) ** 2) / (2 * 10.0**2))
            y[i, 2] = np.exp(-((t - ts[i]) ** 2) / (2 * 10.0**2))
    # Per-sample std normalization (norm_mode="std", ref preprocess.py):
    x /= x.std(axis=(1, 2), keepdims=True) + 1e-12
    if is_motion:
        # (z, dz): the vertical component and its sample derivative —
        # DiTingMotion's 2-channel input contract (ref config.py:129).
        z = x[:, 0]
        dz = np.gradient(z, axis=-1).astype(np.float32)
        x = np.stack([z, dz], axis=1)  # (n, 2, L)
        # y: (n, 2 heads, 2 classes) — [clarity, polarity] one-hots.
        eye = np.eye(2, dtype=np.float32)
        y = np.stack([eye[clr], eye[cls]], axis=1)
        return (
            (x[:n_train], y[:n_train]),
            (x[n_train:], y[n_train:]),
            cls[n_train:],  # true val polarity for the accuracy scorer
        )
    if is_pmp:
        y = np.eye(2, dtype=np.float32)[cls]  # (n, 2) one-hot
        return (
            (x[:n_train], y[:n_train]),
            (x[n_train:], y[n_train:]),
            cls[n_train:],  # true val classes for the accuracy scorer
        )
    if is_emg:
        y = amp.reshape(-1, 1)  # (n, 1) magnitude targets
        return (
            (x[:n_train], y[:n_train]),
            (x[n_train:], y[n_train:]),
            amp[n_train:],  # true val magnitudes for the MAE scorer
        )
    if labels_kind == "det_ppk_spk":
        # det: 1 over [tp, ts + 0.4*(ts-tp)] (the reference's coda-scaled
        # detection span; exact shape is irrelevant here — both sides
        # train on the identical bytes).
        for i in range(n):
            end = ts[i] + 0.4 * (ts[i] - tp[i])
            y[i, 0] = ((t >= tp[i]) & (t <= end)).astype(np.float32)
    else:
        y[:, 0] = np.clip(1.0 - y[:, 1] - y[:, 2], 0.0, 1.0)
    return (
        (x[:n_train], y[:n_train]),
        (x[n_train:], y[n_train:]),
        (tp[n_train:], ts[n_train:]),  # true val picks for the F1 scorer
    )


def run_torch(init_path: str, cfg=CFG) -> dict:
    cfg = lane_cfg(cfg["model"], cfg)  # idempotent (see lane_cfg)
    import torch

    from tools.bench_reference import _install_timm_stub

    _install_timm_stub()  # reference seist.py imports timm's DropPath
    sys.path.insert(0, "/root/reference")
    from models import create_model  # reference models/_factory.py
    from models.loss import BCELoss, CELoss  # reference models/loss.py

    spec = MODELS[cfg["model"]]
    torch.manual_seed(cfg["init_seed"])
    if spec["ref_loss"] == "ce_pmp":
        # The reference's seist_*_pmp factories hard-code their drop
        # rates (ref seist.py:987-1000), so passing zeroed rates through
        # create_model raises "multiple values". Build the same model
        # directly: the factory body with the rates zeroed.
        from functools import partial

        import torch.nn as nn
        from models.seist import HeadClassification, SeismogramTransformer_S

        model = SeismogramTransformer_S(
            in_channels=3,
            in_samples=cfg["in_samples"],
            output_head=partial(
                HeadClassification,
                out_act_layer=partial(nn.Softmax, dim=-1),
                num_classes=2,
            ),
            **spec["zero_drop_kwargs"],
        )
    else:
        model = create_model(
            spec.get("factory", cfg["model"]),
            in_channels=spec.get("in_channels", 3),
            in_samples=cfg["in_samples"],
            **spec["zero_drop_kwargs"],
        )
    # Persist the initial weights for the jax side (npz of numpy arrays).
    np.savez(
        init_path,
        **{k: v.detach().cpu().numpy() for k, v in model.state_dict().items()},
    )

    if spec["ref_loss"] == "bce_dpk":
        loss_fn = BCELoss(weight=[[0.5], [1], [1]])  # ref config.py:138
    elif spec["ref_loss"] == "ce_pmp":
        loss_fn = CELoss(weight=[1, 1])  # ref config.py:147-148 (flat)
    elif spec["ref_loss"] == "mousavi":
        from models.loss import MousaviLoss  # ref loss.py:193-210

        loss_fn = MousaviLoss()
    elif spec["ref_loss"] == "focal_combo":
        from models.loss import CombinationLoss, FocalLoss  # ref config.py:128

        loss_fn = CombinationLoss(losses=[FocalLoss, FocalLoss])
    else:
        loss_fn = CELoss(weight=[[1], [1], [1]])
    opt = torch.optim.Adam(model.parameters(), lr=cfg["base_lr"])
    total = cfg["epochs"] * cfg["steps_per_epoch"]
    sched = torch.optim.lr_scheduler.CyclicLR(
        opt,
        base_lr=cfg["base_lr"],
        max_lr=cfg["max_lr"],
        step_size_up=cfg["warmup_steps"],
        step_size_down=cfg["down_steps"],
        mode="exp_range",
        gamma=cfg["base_lr"] ** ((total * 2) ** -1),  # ref train.py:350
        cycle_momentum=False,
    )

    is_pmp = spec["labels"] == "pmp_onehot"
    is_emg = spec["labels"] == "emg_value"
    is_motion = spec["labels"] == "clr_pmp_onehot"
    (xt, yt), (xv, yv), val_truth = make_data(cfg)
    xt, yt = torch.from_numpy(xt), torch.from_numpy(yt)
    xv, yv = torch.from_numpy(xv), torch.from_numpy(yv)
    b = cfg["batch"]

    def to_targets(yb):
        # motion: per-head list [clarity, polarity] (ref CombinationLoss)
        return [yb[:, 0], yb[:, 1]] if is_motion else yb

    inject = spec.get("inject_droppath", False)
    StubDropPath = sys.modules["timm.models.layers"].DropPath
    dp_calls = 0

    train_losses, val_losses = [], []
    f1_p, f1_s = [], []
    for epoch in range(cfg["epochs"]):
        model.train()
        for s in range(cfg["steps_per_epoch"]):
            xb, yb = xt[s * b : (s + 1) * b], yt[s * b : (s + 1) * b]
            if inject:
                gstep = epoch * cfg["steps_per_epoch"] + s
                StubDropPath.inject = {
                    "uniforms": torch.from_numpy(droppath_uniforms(cfg, gstep)),
                    "i": 0,
                }
            opt.zero_grad()
            loss = loss_fn(model(xb), to_targets(yb))
            if inject:
                dp_calls = StubDropPath.inject["i"]
                StubDropPath.inject = None
            loss.backward()
            opt.step()
            sched.step()  # per optimizer step, ref train.py:115
            train_losses.append(float(loss.item()))
        model.eval()
        with torch.no_grad():
            val_out = model(xv)
            val_losses.append(float(loss_fn(val_out, to_targets(yv)).item()))
        if is_pmp:
            f1_p.append(class_accuracy(val_out.detach().numpy(), val_truth))
        elif is_motion:
            # polarity head (index 1 of [clarity, polarity])
            f1_p.append(
                class_accuracy(val_out[1].detach().numpy(), val_truth)
            )
        elif is_emg:
            f1_p.append(value_mae(val_out.detach().numpy(), val_truth))
        else:
            # channels-last for the shared scorer
            f1 = pick_f1(
                val_out.detach().numpy().transpose(0, 2, 1), *val_truth
            )
            f1_p.append(f1["p"])
            f1_s.append(f1["s"])
    result = {
        "side": "torch",
        "train_loss_per_step": train_losses,
        "val_loss_per_epoch": val_losses,
        "droppath_calls_per_forward": dp_calls,
        "config": cfg,
    }
    if is_pmp or is_motion:
        result["val_acc_per_epoch"] = f1_p
    elif is_emg:
        result["val_mae_per_epoch"] = f1_p
    else:
        result["val_f1_p_per_epoch"] = f1_p
        result["val_f1_s_per_epoch"] = f1_s
    return result


def run_jax(init_path: str, cfg=CFG) -> dict:
    cfg = lane_cfg(cfg["model"], cfg)  # idempotent (see lane_cfg)
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import seist_tpu
    from seist_tpu import taskspec
    from seist_tpu.models import api
    from seist_tpu.train import (
        build_cyclic_schedule,
        build_optimizer,
        create_train_state,
        make_eval_step,
        make_train_step,
    )
    from tools.parity import convert_state_dict

    seist_tpu.load_all()
    mspec = MODELS[cfg["model"]]
    model = api.create_model(
        mspec.get("factory", cfg["model"]),
        in_channels=mspec.get("in_channels", 3),
        in_samples=cfg["in_samples"],
        **mspec["zero_drop_kwargs"],
    )
    variables = api.init_variables(
        model,
        in_samples=cfg["in_samples"],
        in_channels=mspec.get("in_channels", 3),
        batch_size=cfg["batch"],
    )
    sd = dict(np.load(init_path))
    variables = convert_state_dict(sd, variables)

    total = cfg["epochs"] * cfg["steps_per_epoch"]
    sched = build_cyclic_schedule(
        cfg["base_lr"],
        cfg["max_lr"],
        total_steps=total,
        warmup_steps=cfg["warmup_steps"],
        down_steps=cfg["down_steps"],
    )
    state = create_train_state(model, variables, build_optimizer("adam", sched))

    task = mspec.get("factory", cfg["model"])
    spec = taskspec.get_task_spec(task)
    loss_fn = taskspec.make_loss(task)
    inject = mspec.get("inject_droppath", False)
    dp_probe = {}
    if inject:
        # Same semantics as make_train_step (shared _forward_loss body:
        # BN mutation, task transforms, fp32 compute) with the per-step
        # DropPath uniforms threaded through as a traced argument and
        # routed to every DropPath call via the injection context
        # (models/common.py). The rng arg is unused: element dropouts
        # are all 0 and DropPath reads the injected rows.
        from seist_tpu.models.common import droppath_mask_injection
        from seist_tpu.train.precision import cast_to_float32
        from seist_tpu.train.step import _forward_loss

        def train_step_inj(state, x, y, uniforms):
            def apply_fn(variables, inputs, **kw):
                with droppath_mask_injection(uniforms) as rec:
                    out = model.apply(variables, inputs, **kw)
                dp_probe["calls"] = rec["i"]  # trace-time capture
                return out

            fwd = _forward_loss(spec, loss_fn, jnp.float32, apply_fn)
            (loss, (_outputs, new_stats)), grads = jax.value_and_grad(
                fwd, has_aux=True
            )(state.params, state.batch_stats, x, y, jax.random.PRNGKey(0))
            state = state.apply_gradients(grads=grads)
            if new_stats is not None:
                state = state.replace(batch_stats=cast_to_float32(new_stats))
            return state, loss

        train_step = jax.jit(train_step_inj)
    else:
        train_step = jax.jit(make_train_step(spec, loss_fn))
    eval_step = jax.jit(make_eval_step(spec, loss_fn))

    is_pmp = mspec["labels"] == "pmp_onehot"
    is_emg = mspec["labels"] == "emg_value"
    is_motion = mspec["labels"] == "clr_pmp_onehot"
    (xt, yt), (xv, yv), val_truth = make_data(cfg)
    # channels-last for this framework (pmp (N,2) / emg (N,1) / motion
    # (N,2,2) labels have no L axis)
    xt, xv = xt.transpose(0, 2, 1), xv.transpose(0, 2, 1)
    if not (is_pmp or is_emg or is_motion):
        yt, yv = yt.transpose(0, 2, 1), yv.transpose(0, 2, 1)
    b = cfg["batch"]

    def to_targets(yb):
        # motion: per-head tuple (clarity, polarity) — a jax pytree the
        # jitted step threads like any other target structure.
        if is_motion:
            a = jnp.asarray(yb)
            return (a[:, 0], a[:, 1])
        return jnp.asarray(yb)
    rng = jax.random.PRNGKey(0)  # drop_rate=0: stream is never consumed
    vmask = jnp.ones((xv.shape[0],), jnp.float32)

    train_losses, val_losses = [], []
    f1_p, f1_s = [], []
    for epoch in range(cfg["epochs"]):
        for s in range(cfg["steps_per_epoch"]):
            xb, yb = xt[s * b : (s + 1) * b], yt[s * b : (s + 1) * b]
            if inject:
                gstep = epoch * cfg["steps_per_epoch"] + s
                state, loss = train_step(
                    state,
                    jnp.asarray(xb),
                    jnp.asarray(yb),
                    jnp.asarray(droppath_uniforms(cfg, gstep)),
                )
            else:
                state, loss, _ = train_step(
                    state, jnp.asarray(xb), to_targets(yb), rng
                )
            train_losses.append(float(loss))
        vloss, vout = eval_step(state, jnp.asarray(xv), to_targets(yv), vmask)
        val_losses.append(float(vloss))
        if is_pmp:
            f1_p.append(class_accuracy(np.asarray(vout), val_truth))
        elif is_motion:
            # polarity head (index 1 of (clarity, polarity))
            f1_p.append(class_accuracy(np.asarray(vout[1]), val_truth))
        elif is_emg:
            f1_p.append(value_mae(np.asarray(vout), val_truth))
        else:
            f1 = pick_f1(np.asarray(vout), *val_truth)
            f1_p.append(f1["p"])
            f1_s.append(f1["s"])
    result = {
        "side": "jax",
        "train_loss_per_step": train_losses,
        "val_loss_per_epoch": val_losses,
        "droppath_calls_per_forward": dp_probe.get("calls", 0),
        "config": cfg,
    }
    if is_pmp or is_motion:
        result["val_acc_per_epoch"] = f1_p
    elif is_emg:
        result["val_mae_per_epoch"] = f1_p
    else:
        result["val_f1_p_per_epoch"] = f1_p
        result["val_f1_s_per_epoch"] = f1_s
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--side", choices=("torch", "jax"), required=True)
    ap.add_argument("--model", choices=sorted(MODELS), default="phasenet")
    ap.add_argument(
        "--init",
        default=os.path.join(_REPO, "logs", "dyn_init.npz"),
        help="npz path the torch side writes / the jax side reads",
    )
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    os.makedirs(os.path.dirname(os.path.abspath(args.init)), exist_ok=True)

    cfg = lane_cfg(args.model)
    result = (
        run_torch(args.init, cfg)
        if args.side == "torch"
        else run_jax(args.init, cfg)
    )
    line = json.dumps(result)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line)
    print(line)


if __name__ == "__main__":
    main()
