"""Training-dynamics parity: torch reference vs seist_tpu.

Both sides train phasenet and seist_s_dpk (all drop rates zeroed) from the
IDENTICAL initialization on
byte-identical batches in the same order under the same cyclic LR schedule
(tools/train_dynamics.py). Asserting the loss trajectories agree catches
BN-momentum / LR-schedule / optimizer-epsilon / loss-scaling drift that
single-step forward+gradient parity (tests/test_golden_parity.py) cannot see.

Ref anchor: /root/reference/training/train.py:378-468 (the epoch loop being
mirrored); validate.py:54-127 (the eval-mode val loss, which runs on BN
running stats — the BN-momentum probe).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # two full (small) training runs

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOL = os.path.join(_REPO, "tools", "train_dynamics.py")


def _run_side(side: str, model: str, tmp: str) -> dict:
    out = os.path.join(tmp, f"{side}.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [
            sys.executable,
            _TOOL,
            "--side",
            side,
            "--model",
            model,
            "--init",
            os.path.join(tmp, "init.npz"),
            "--out",
            out,
        ],
        capture_output=True,
        text=True,
        timeout=1200,
        env=env,
        cwd=_REPO,
    )
    assert r.returncode == 0, f"{side} side failed:\n{r.stdout}\n{r.stderr}"
    with open(out) as f:
        return json.load(f)


# phasenet: plain conv+BN+CE dynamics. seist_s_dpk: the flagship family —
# stems, grouped convs, pooled attention, DropPath residuals, BCE. Both
# measured 2026-07-31: max train-loss drift 1.0e-4 / 1.5e-5 respectively.
# seist_s_dpk_droppath: the dropout-ON lane — stochastic
# depth at 0.2 with per-sample uniforms INJECTED identically on both
# sides; measured 2026-08-01: max train-loss drift 8.4e-6 over 48 steps,
# 33 DropPath calls consumed per forward on each side.
# seist_s_pmp: the accuracy-metric (classification) lane. Its loss is a
# mean over just `batch` scalars from a global-pooled head, so fp-level
# noise amplifies chaotically once training moves: measured 2026-08-01,
# steps 0-10 agree to ~3e-6, then the drift grows with OSCILLATING sign
# (jax above torch at step 16, below at 28) to ~9e-2 by step 48 — the
# signature of chaotic divergence, not a systematic convention drift
# (BN momentum / LR shape / eps would bias one side early and
# monotonically). Tolerances below are per-lane, calibrated to those
# measurements.
@pytest.fixture(
    scope="module",
    params=[
        "phasenet",
        "seist_s_dpk",
        "seist_s_dpk_droppath",
        "seist_s_pmp",
        "eqtransformer",
        "magnet",
        "ditingmotion",
    ],
)
def trajectories(request, tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp(f"dyn_{request.param}"))
    torch_run = _run_side("torch", request.param, tmp)  # writes init.npz
    jax_run = _run_side("jax", request.param, tmp)
    return torch_run, jax_run


# (early-window max rel drift, full-trajectory max, val max) per lane;
# early window = first quarter of the steps (pure-parity regime before
# chaotic amplification dominates).
_TOL = {
    "phasenet": (1e-3, 5e-3, 5e-3),
    "seist_s_dpk": (1e-3, 5e-3, 5e-3),
    "seist_s_dpk_droppath": (1e-3, 5e-3, 5e-3),
    "seist_s_pmp": (5e-3, 1.5e-1, 5e-2),
    # scan-BiLSTM recurrence accumulates fp drift ~20x faster than the
    # pure-conv lanes (measured 2026-08-01: first-quarter 1.1e-4, full
    # 2.0e-3, val 2.8e-3); its band keeps the file's ~10x-over-measured
    # margin so host/XLA variation cannot flake the slow lane.
    "eqtransformer": (1e-3, 2e-2, 3e-2),
    # MagNet's sum-reduced scalar objective feels Adam's sign-flips at
    # near-zero gradient coordinates immediately (init grads agree to
    # 1.2e-6 — see the MODELS['magnet'] comment in the harness);
    # measured at the lane's max_lr=3e-4: first-quarter 8.9e-3, full
    # 6.6e-2, val 6.1e-2. Band ~5x over measured.
    "magnet": (5e-2, 3e-1, 3e-1),
    # Dual-Focal multi-head lane: the tightest of all (measured full
    # drift 1.7e-6, val 5e-7).
    "ditingmotion": (1e-4, 1e-4, 1e-4),
}

# Denylist for the must-actually-learn assertion (fails safe: a lane
# added to the fixture without an entry here IS held to the 5% bar).
# ditingmotion barely moves at this toy scale (measured end/start ratio
# 0.9993 on BOTH sides — the focal objective on 2-channel 512-sample
# windows needs more steps); its purpose here is loss-family parity,
# which its 1.7e-6 drift locks, and absolute learning is covered by the
# other six lanes.
_TOO_SLOW_TO_LEARN = {"ditingmotion"}


def test_train_loss_trajectory_matches(trajectories):
    torch_run, jax_run = trajectories
    t = np.asarray(torch_run["train_loss_per_step"])
    j = np.asarray(jax_run["train_loss_per_step"])
    assert t.shape == j.shape and t.size >= 40
    # Same init + same batches: step 0 is near-exact (pure forward parity);
    # later steps accumulate fp drift through 40+ optimizer updates, BN
    # stats and the exp_range LR decay, so the band widens with depth.
    np.testing.assert_allclose(j[0], t[0], rtol=1e-5)
    # Calibrated 2026-07-31/08-01 on this host: measured max rel drift
    # 1.0e-4 over 48 optimizer steps for the dense-loss lanes (first
    # half 4.6e-5); the pmp classification lane amplifies chaotically
    # (see _TOL comment). Tolerances sit ~10-50x above the measurements
    # so only a real dynamics divergence (BN momentum, LR schedule,
    # optimizer eps, loss scaling) trips them, not fp noise.
    early_tol, full_tol, _ = _TOL[torch_run["config"]["model"]]
    rel = np.abs(j - t) / np.maximum(np.abs(t), 1e-8)
    early = rel[: len(rel) // 4]
    assert early.max() < early_tol, (
        f"early train-loss drift {early.max():.2e} exceeds {early_tol:g}"
    )
    assert rel.max() < full_tol, (
        f"train-loss drift {rel.max():.2e} exceeds {full_tol:g}"
    )
    # Both must actually LEARN (measured: 1.276 -> 1.143 over 6 epochs)
    # — except lanes explicitly exempted as too slow at toy scale.
    if torch_run["config"]["model"] not in _TOO_SLOW_TO_LEARN:
        assert t[-8:].mean() < t[:8].mean() * 0.95
        assert j[-8:].mean() < j[:8].mean() * 0.95


def test_val_loss_trajectory_matches(trajectories):
    # Eval-mode forward runs on BN *running* stats: a BN-momentum
    # convention drift shows up first here (and only here).
    torch_run, jax_run = trajectories
    t = np.asarray(torch_run["val_loss_per_epoch"])
    j = np.asarray(jax_run["val_loss_per_epoch"])
    assert t.shape == j.shape and t.size >= 4
    # Calibrated: measured max val drift 1.2e-4 across 6 epochs (dense
    # lanes); 2.3e-2 for the chaotic pmp lane (last epoch only).
    val_tol = _TOL[torch_run["config"]["model"]][2]
    rel = np.abs(j - t) / np.maximum(np.abs(t), 1e-8)
    assert rel.max() < val_tol, (
        f"val-loss drift {rel.max():.2e} exceeds {val_tol:g}"
    )


def test_val_metric_trajectory_matches(trajectories):
    # Metric half: per-epoch P/S pick F1 on the val set,
    # scored by the ONE shared numpy scorer on each side's eval-mode
    # probabilities. A dynamics drift that losses average away would
    # move individual picks across the threshold/tolerance and split the
    # trajectories. Measured 2026-08-01: phasenet trajectories agree to
    # one pick (0.031 abs) per epoch; end F1 exactly equal. The seist
    # lanes sit at 0.0 F1 at this 48-step toy scale on BOTH frameworks
    # (equality still asserted); absolute dpk learning is covered by the
    # phasenet lane here and tests/test_worker_e2e.py's learning
    # regression.
    torch_run, jax_run = trajectories
    if "val_acc_per_epoch" in torch_run:
        keys = ("val_acc_per_epoch",)
    elif "val_mae_per_epoch" in torch_run:
        # MAE in magnitude units on the volatile magnet lane (measured
        # max per-epoch diff 0.026): wider band than the [0,1] scores.
        keys = ("val_mae_per_epoch",)
    else:
        keys = ("val_f1_p_per_epoch", "val_f1_s_per_epoch")
    metric_tol = 0.1 if keys == ("val_mae_per_epoch",) else 0.05
    for key in keys:
        t = np.asarray(torch_run[key])
        j = np.asarray(jax_run[key])
        assert t.shape == j.shape and t.size >= 4
        diff = np.abs(j - t)
        assert diff.max() <= metric_tol, (
            f"{key} trajectories diverge: {diff.max():.3f} (torch {t}, jax {j})"
        )
        # End-metric agreement (the r3 ask's second half).
        assert diff[-1] <= metric_tol, (
            f"end {key}: torch {t[-1]} vs jax {j[-1]}"
        )
    # The phasenet lane must actually move the metric (non-vacuous check
    # that the scorer sees learning; measured: P-F1 0.03 -> 0.47).
    if torch_run["config"]["model"] == "phasenet":
        t = np.asarray(torch_run["val_f1_p_per_epoch"])
        assert t[-1] > t[0], f"P-F1 did not improve: {t}"


def test_droppath_lane_consumed_identical_masks(trajectories):
    # Dropout-ON lane: both frameworks must consume the
    # SAME number of injected DropPath rows per forward (call-order
    # symmetry), and — asserted by the trajectory tests above running on
    # this lane too — produce matching losses WITH stochastic depth
    # active. With divergent masks the train-loss drift would be O(1);
    # measured with injection: 8.4e-6.
    torch_run, jax_run = trajectories
    if not torch_run["config"]["model"].endswith("_droppath"):
        pytest.skip("injection lane only")
    # (measured: 33 calls/forward for seist_s — 2 per encoder block +
    # decoder residuals; the invariant is equal-and-consuming, not the
    # exact count, which tracks depth config)
    assert (
        torch_run["droppath_calls_per_forward"]
        == jax_run["droppath_calls_per_forward"]
        > 0
    )
