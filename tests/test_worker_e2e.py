"""End-to-end orchestration tests: train_worker -> checkpoint -> test_worker
on the synthetic dataset (the workflow of ref main.py --mode train_test),
plus eval-masking semantics."""

import os
from types import SimpleNamespace

import jax
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # full train->ckpt->test runs

import seist_tpu
from seist_tpu import taskspec
from seist_tpu.utils.logger import logger
from tests.test_tb import event_file, read_scalars

seist_tpu.load_all()


def make_args(**over):
    d = dict(
        mode="train_test",
        model_name="phasenet",
        checkpoint="",
        seed=1,
        log_base="",
        log_step=100,
        use_tensorboard=False,
        save_test_results=True,
        data="",
        dataset_name="synthetic",
        data_split=True,
        train_size=0.8,
        val_size=0.1,
        shuffle=True,
        workers=2,
        in_samples=1024,
        label_width=0.5,
        label_shape="gaussian",
        coda_ratio=2.0,
        norm_mode="std",
        min_snr=-float("inf"),
        p_position_ratio=-1,
        augmentation=False,
        add_event_rate=0.0,
        max_event_num=1,
        shift_event_rate=0.0,
        add_noise_rate=0.0,
        add_gap_rate=0.0,
        min_event_gap=0.5,
        drop_channel_rate=0.0,
        scale_amplitude_rate=0.0,
        pre_emphasis_rate=0.0,
        pre_emphasis_ratio=0.97,
        generate_noise_rate=0.0,
        mask_percent=0,
        noise_percent=0,
        epochs=1,
        patience=30,
        steps=0,
        start_epoch=0,
        batch_size=8,
        optim="Adam",
        momentum=0.9,
        weight_decay=0.0,
        use_lr_scheduler=True,
        lr_scheduler_mode="exp_range",
        base_lr=8e-5,
        max_lr=1e-3,
        warmup_steps=2000,
        down_steps=3000,
        time_threshold=0.1,
        min_peak_dist=1.0,
        ppk_threshold=0.3,
        spk_threshold=0.3,
        det_threshold=0.5,
        max_detect_event_num=1,
        dataset_kwargs={"num_events": 40, "trace_samples": 4096},
    )
    d.update(over)
    return SimpleNamespace(**d)


@pytest.fixture(scope="module")
def e2e_run(tmp_path_factory):
    from seist_tpu.train.worker import test_worker, train_worker

    logdir = str(tmp_path_factory.mktemp("e2e_logs"))
    logger.set_logdir(logdir)
    # The one run of this file with the scalar writer on, as a user's run
    # and the benchmark's cells have it (--use-tensorboard defaults to true).
    args = make_args(use_tensorboard=True)
    ckpt = train_worker(args)
    assert ckpt and os.path.exists(ckpt)
    args.checkpoint = ckpt
    loss = test_worker(args)
    return logdir, ckpt, loss


def test_train_then_test(e2e_run):
    logdir, ckpt, loss = e2e_run
    assert np.isfinite(loss)


@pytest.mark.slow  # ~2 min incl. compile: 30-epoch learning regression
def test_training_learns_p_picks(tmp_path_factory):
    """Training must actually LEARN, not merely keep the loss finite: 30
    constant-LR epochs of phasenet on the synthetic dataset reach P-pick
    F1 0.75 on the held-out test split (~2 min incl. compile on this
    host). Guards against silent optimizer / label / postprocess /
    metric-wiring regressions the loss-only e2e can't see."""
    import json

    from seist_tpu.train.worker import test_worker, train_worker

    logdir = str(tmp_path_factory.mktemp("learn_logs"))
    logger.set_logdir(logdir)
    # Dataset left at its defaults (256 events, 12000-sample traces): this
    # matches the CLI calibration run; smaller fixtures train noisily.
    args = make_args(
        in_samples=512,
        batch_size=32,
        epochs=30,
        use_lr_scheduler=False,
        max_lr=1e-3,
        patience=1000,
        dataset_kwargs={},
    )
    ckpt = train_worker(args)
    assert ckpt and os.path.exists(ckpt)
    args.checkpoint = ckpt
    test_worker(args)
    metrics_json = os.path.join(logdir, "test_metrics_synthetic.json")
    assert os.path.exists(metrics_json), os.listdir(logdir)
    with open(metrics_json) as f:
        payload = json.load(f)
    # Measured 0.75 at this exact seeded config (27 test events; chance is
    # ~0); 0.6 leaves margin for legitimate augmentation/label changes
    # while still failing hard on a model that didn't learn.
    f1 = payload["metrics"]["ppk"]["f1"]
    assert f1 >= 0.6, payload["metrics"]


def test_event_file_holds_the_trainers_tags(e2e_run):
    """The writer frames its own event files (utils/tb.py): what the trainer
    wrote through it comes back out of TensorBoard's protobuf classes."""
    pytest.importorskip("tensorboard.compat.proto.event_pb2")
    logdir, _, _ = e2e_run
    scalars = read_scalars(event_file(os.path.join(logdir, "tensorboard")))
    tags = {tag for tag, _, _ in scalars}
    assert {"train-loss/step", "train-loss/epoch", "val-loss/epoch"} <= tags, tags
    assert any(t.startswith("val.ppk.metrics/epoch/") for t in tags), tags
    assert all(np.isfinite(v) for t, v, _ in scalars if "loss" in t), scalars
    # The epoch's curves are the numbers the run saved beside them.
    by_tag = {tag: value for tag, value, step in scalars if step == 0}
    assert by_tag["train-loss/epoch"] == np.float32(
        np.mean(np.load(os.path.join(logdir, "train_losses.npy")))  # one epoch
    )
    assert by_tag["val-loss/epoch"] == np.float32(
        np.load(os.path.join(logdir, "val_losses.npy"))[0]
    )


def test_preempt_exit_leaves_nothing_running(tmp_path, monkeypatch):
    """``cli.main`` left through the preempt exit, the benchmark's way out
    (``benchmarks/run.py`` then ends by ``sys.exit``, which joins every
    non-daemon thread and orphans nothing only if every child was closed):
    no thread of the run's but the main one outlives it, and no child
    process (PRs 32 and 34 were refused as ``process_left_running``)."""
    import multiprocessing
    import threading

    from seist_tpu import cli
    from seist_tpu.train.checkpoint import PREEMPT_EXIT_CODE

    before = set(threading.enumerate())
    monkeypatch.setenv("SEIST_FAULT_SIGTERM_STEP", "3")
    with pytest.raises(SystemExit) as exit_info:
        cli.main([
            "--mode", "train", "--model-name", "phasenet",
            "--dataset-name", "synthetic", "--synthetic-events", "40",
            "--in-samples", "512", "--batch-size", "8", "--epochs", "2",
            "--seed", "1", "--augmentation", "false", "--workers", "2",
            "--save-interval-steps", "2", "--log-step", "1",
            "--log-base", str(tmp_path),
        ])
    assert exit_info.value.code == PREEMPT_EXIT_CODE
    left = [
        t for t in threading.enumerate()
        if t not in before and t.is_alive() and not t.daemon
    ]
    assert left == [], [(t.name, t.daemon) for t in left]
    assert multiprocessing.active_children() == []
    # The writer was on (the flag's default) and was closed on the way out:
    # the file is whole, and holds the steps trained before the signal.
    (run_dir,) = os.listdir(str(tmp_path))
    scalars = read_scalars(event_file(os.path.join(str(tmp_path), run_dir, "tensorboard")))
    steps = [s for tag, _, s in scalars if tag == "train-loss/step"]
    assert steps and steps == sorted(steps), scalars


def test_results_csv_written(e2e_run):
    logdir, _, _ = e2e_run
    csvs = [f for f in os.listdir(logdir) if f.startswith("test_results_")]
    assert csvs, os.listdir(logdir)
    import pandas as pd

    df = pd.read_csv(os.path.join(logdir, csvs[0]))
    # 40 events * 10% test split = 4 rows; pred/tgt columns present per task.
    assert len(df) == 4
    for col in ("pred_ppk", "tgt_ppk", "pred_spk", "tgt_spk"):
        assert col in df.columns


def test_loss_curves_saved(e2e_run):
    logdir, _, _ = e2e_run
    assert os.path.exists(os.path.join(logdir, "train_losses.npy"))
    assert os.path.exists(os.path.join(logdir, "val_losses.npy"))


def test_eval_mask_excludes_padding(rng):
    """Padded rows must not change the eval loss (code-review finding)."""
    from seist_tpu.models import api
    from seist_tpu.train import (
        build_optimizer,
        create_train_state,
        make_eval_step,
    )

    spec = taskspec.get_task_spec("phasenet")
    loss_fn = spec.loss()
    model = api.create_model("phasenet", in_channels=3, in_samples=1024)
    variables = api.init_variables(model, in_samples=1024, in_channels=3)
    state = create_train_state(model, variables, build_optimizer("adam", 1e-3))
    estep = jax.jit(make_eval_step(spec, loss_fn))

    x = rng.normal(size=(4, 1024, 3)).astype(np.float32)
    y = np.abs(rng.normal(size=(4, 1024, 3))).astype(np.float32)
    y /= y.sum(-1, keepdims=True)

    half_mask = np.array([1, 1, 0, 0], dtype=np.float32)

    # Replace masked rows with garbage — the loss must not move at all.
    x2 = x.copy()
    x2[2:] = 999.0
    loss_masked, _ = estep(state, x2, y, half_mask)
    loss_ref, _ = estep(state, x, y, half_mask)
    assert float(loss_masked) == pytest.approx(float(loss_ref), rel=1e-5)


def test_train_with_grad_accum(tmp_path):
    """--grad-accum-steps e2e: the worker routes k loader batches into one
    scanned update (step.py make_accum_train_step) and still produces a
    loadable checkpoint + test metrics."""
    from seist_tpu.train.worker import test_worker, train_worker

    logger.set_logdir(str(tmp_path))
    args = make_args(grad_accum_steps=2, epochs=1)
    ckpt = train_worker(args)
    assert ckpt and os.path.exists(ckpt)
    args.checkpoint = ckpt
    loss = test_worker(args)
    assert np.isfinite(loss)


@pytest.mark.parametrize("mode", ["cached", "step"])
def test_train_with_device_aug(tmp_path, mode):
    """--device-aug e2e: augmentation + label synthesis inside the jitted
    step (step mode: host-fed raw rows; cached mode: HBM-resident epochs
    + scan executor), through the full worker path to a loadable
    checkpoint and finite test loss."""
    from seist_tpu.train.worker import test_worker, train_worker

    logger.set_logdir(str(tmp_path))
    args = make_args(
        mode="train_test",
        epochs=1,
        device_aug=mode,
        augmentation=True,
        shift_event_rate=0.3,
        add_noise_rate=0.3,
        add_gap_rate=0.3,
        drop_channel_rate=0.3,
        scale_amplitude_rate=0.3,
        pre_emphasis_rate=0.3,
        generate_noise_rate=0.05,
        add_event_rate=0.3,
        max_event_num=2,
        dataset_kwargs={"num_events": 24, "trace_samples": 1536},
    )
    ckpt = train_worker(args)
    assert ckpt and os.path.exists(ckpt)
    args.checkpoint = ckpt
    loss = test_worker(args)
    assert np.isfinite(loss)


def test_device_aug_unsupported_config_falls_back(tmp_path):
    """mask_percent is host-only: the worker must fall back to the host
    path (and still train) instead of crashing or silently changing
    semantics."""
    from seist_tpu.train.worker import train_worker

    logger.set_logdir(str(tmp_path))
    args = make_args(
        mode="train",
        epochs=1,
        device_aug="cached",
        augmentation=True,
        mask_percent=10,
        dataset_kwargs={"num_events": 16, "trace_samples": 1536},
    )
    ckpt = train_worker(args)
    assert ckpt and os.path.exists(ckpt)


def test_train_then_test_on_packed_dataset(tmp_path_factory):
    """The packed-shard dataset through the FULL worker path (train ->
    checkpoint -> test -> metrics), the integration a reference user
    hits with `--dataset-name packed` (docs/MIGRATING.md)."""
    from tests.conftest import make_packed_dir

    from seist_tpu.train.worker import test_worker, train_worker

    _, packed_dir = make_packed_dir(
        tmp_path_factory, n_events=40, trace_samples=4096, n_parts=1
    )

    logdir = str(tmp_path_factory.mktemp("e2e_packed_logs"))
    logger.set_logdir(logdir)
    args = make_args(
        dataset_name="packed", data=packed_dir, dataset_kwargs={}
    )
    ckpt = train_worker(args)
    assert ckpt and os.path.exists(ckpt)
    args.checkpoint = ckpt
    loss = test_worker(args)
    assert np.isfinite(loss)
    assert os.path.exists(
        os.path.join(logdir, "test_metrics_packed.json")
    )


def test_train_packed_direct_ingest(tmp_path_factory):
    """--device-aug step + --ingest direct on a packed dataset: the raw
    rows stream straight off the shard memmaps (data/ingest.py), the
    strict flag proves the fast path actually engaged (it errors on any
    silent fallback), and training completes to a checkpoint."""
    from tests.conftest import make_packed_dir

    from seist_tpu.train.worker import train_worker

    _, packed_dir = make_packed_dir(
        tmp_path_factory, n_events=40, trace_samples=1536, n_parts=1
    )
    logdir = str(tmp_path_factory.mktemp("e2e_direct_logs"))
    logger.set_logdir(logdir)
    args = make_args(
        dataset_name="packed",
        data=packed_dir,
        dataset_kwargs={},
        device_aug="step",
        ingest="direct",
        augmentation=True,
        in_samples=1024,
    )
    ckpt = train_worker(args)
    assert ckpt and os.path.exists(ckpt)
    with open(os.path.join(logdir, "global.log")) as f:
        log = f.read()
    assert "packed direct ingest" in log
    assert "device-aug step" in log


def test_train_mixture_pack_with_temperature(tmp_path_factory):
    """Temperature-weighted mixture training end to end: two packed
    sources, --mixture-temperature on the host path; loss stays finite
    and the run checkpoints."""
    from seist_tpu.data.packed import PackSource, pack_sources
    from seist_tpu.train.worker import train_worker

    out = str(tmp_path_factory.mktemp("e2e_mix_pack"))
    pack_sources(
        [
            PackSource(
                name="synthetic",
                dataset_kwargs={
                    "num_events": n, "trace_samples": 1536, "cache": False,
                },
            )
            for n in (30, 10)
        ],
        out,
        samples_per_shard=8,
    )
    logdir = str(tmp_path_factory.mktemp("e2e_mix_logs"))
    logger.set_logdir(logdir)
    args = make_args(
        dataset_name="packed",
        data=out,
        dataset_kwargs={},
        mixture_temperature=2.0,
        in_samples=1024,
    )
    ckpt = train_worker(args)
    assert ckpt and os.path.exists(ckpt)
