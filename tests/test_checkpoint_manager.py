"""TrainCheckpointManager: step-granular async checkpointing, retention,
atomic finalize, overwrite protection, and full-resume-state round-trips
(the tentpole of the fault-tolerance layer; docs/FAULT_TOLERANCE.md)."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import orbax.checkpoint as ocp
import pytest
from flax import linen as nn

import seist_tpu
from seist_tpu import taskspec
from seist_tpu.train import (
    PREEMPT_EXIT_CODE,
    TrainCheckpointManager,
    build_optimizer,
    create_train_state,
    load_checkpoint,
    make_train_step,
    restore_into_state,
    save_checkpoint,
)

seist_tpu.load_all()

L = 64


class TinyBN(nn.Module):
    """Smallest state shape that exercises every checkpoint field: Dense
    params, BatchNorm running stats, Adam moments. (A real-model state is
    structurally identical — tests/test_train.py covers that round trip —
    and the multi-second phasenet compile would dominate this file.)"""

    @nn.compact
    def __call__(self, x, train=False):
        h = nn.Dense(8)(x)
        h = nn.BatchNorm(use_running_average=not train)(h)
        return jax.nn.softmax(nn.Dense(3)(h), axis=-1)


def fresh_state():
    model = TinyBN()
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, L, 3)))
    return create_train_state(model, variables, build_optimizer("adam", 1e-3))


@pytest.fixture(scope="module")
def trained_state():
    """A state advanced one step (so BN stats and Adam moments are
    non-trivial), shared across the module."""
    state = fresh_state()
    spec = taskspec.get_task_spec("phasenet")  # CE on (N, L, 3) probs
    loss_fn = taskspec.make_loss("phasenet")
    step = jax.jit(make_train_step(spec, loss_fn))
    rng = np.random.default_rng(0)
    x = np.asarray(rng.standard_normal((4, L, 3)), np.float32)
    ppk = np.zeros((4, L), np.float32)
    ppk[:, 16] = 1.0
    spk = np.zeros((4, L), np.float32)
    spk[:, 32] = 1.0
    y = np.stack([1.0 - ppk - spk, ppk, spk], axis=-1)
    state, _, _ = step(state, x, y, jax.random.PRNGKey(0))
    return state


# ------------------------------------------------------------ round trips
def test_manager_roundtrip_full_resume_state(tmp_path, trained_state):
    mgr = TrainCheckpointManager(str(tmp_path / "c"), keep_last=3)
    mgr.save(
        7, trained_state, epoch=1, data_epoch=1, data_batch_offset=3,
        seed=42, wait=True,
    )
    fresh = fresh_state()
    restored = mgr.restore(fresh)
    meta = restored["meta"]
    assert int(meta["data_epoch"]) == 1
    assert int(meta["data_batch_offset"]) == 3
    assert int(meta["seed"]) == 42
    assert int(meta["total_batches"]) == 7
    resumed = restore_into_state(fresh, restored)
    # The LR-schedule position rides on state.step + the opt_state count.
    assert int(resumed.step) == int(trained_state.step)
    for a, b in zip(
        jax.tree_util.tree_leaves(trained_state.params),
        jax.tree_util.tree_leaves(resumed.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Satellite: opt_state flat-leaves restore into a live TrainState —
    # Adam moments must round-trip exactly, not just params.
    for a, b in zip(
        jax.tree_util.tree_leaves(trained_state.opt_state),
        jax.tree_util.tree_leaves(resumed.opt_state),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


def test_the_snapshot_is_the_only_host_copy(trained_state):
    """orbax deep-copies every numpy leaf before its save returns; the
    snapshot taken for the save is nobody else's, so that copy (8 of an
    8 GB save's 11 s on the chip) is declined — and the snapshot is still
    a copy of the state, which the train loop donates."""
    import copy

    from seist_tpu.train import checkpoint

    snap = checkpoint._host_copy(checkpoint._state_payload(trained_state))
    leaves = [x for x in jax.tree_util.tree_leaves(snap) if hasattr(x, "shape")]
    assert leaves and all(isinstance(x, np.ndarray) for x in leaves)
    assert all(copy.deepcopy(x) is x for x in leaves)
    live = jax.tree_util.tree_leaves(checkpoint._state_payload(trained_state))
    assert all(
        not np.shares_memory(x, np.asarray(y)) for x, y in zip(leaves, live)
    )


def test_legacy_load_checkpoint_reads_manager_step_dir(tmp_path, trained_state):
    """tools/supervise.py hands `--checkpoint <...>/model_<step>` to the
    CLI; load_checkpoint must descend into the manager's item layout."""
    mgr = TrainCheckpointManager(str(tmp_path / "c"), keep_last=2)
    path = mgr.save(
        4, trained_state, epoch=0, data_epoch=0, data_batch_offset=4,
        wait=True,
    )
    mgr.close()
    fresh = fresh_state()
    restored = load_checkpoint(path, fresh)
    assert int(restored["meta"]["data_batch_offset"]) == 4
    resumed = restore_into_state(fresh, restored)
    for a, b in zip(
        jax.tree_util.tree_leaves(trained_state.params),
        jax.tree_util.tree_leaves(resumed.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Raw (state-free) read works too — the serve/pool.py path.
    raw = load_checkpoint(path)
    assert "params" in raw and "opt_state" in raw


def test_params_only_checkpoint_restores_with_fresh_opt_state(
    tmp_path, trained_state
):
    """Satellite: params(+stats)-only restore — the import_pretrained
    layout. Weights adopted, optimizer state left fresh, epoch -1."""
    path = str(tmp_path / "params_only")
    with ocp.StandardCheckpointer() as saver:
        saver.save(
            path,
            {
                "params": jax.tree.map(np.asarray, trained_state.params),
                "batch_stats": jax.tree.map(
                    np.asarray, trained_state.batch_stats
                ),
            },
        )
    fresh = fresh_state()
    restored = load_checkpoint(path, fresh)
    assert int(restored["meta"]["epoch"]) == -1
    resumed = restore_into_state(fresh, restored)
    assert int(resumed.step) == 0
    for a, b in zip(
        jax.tree_util.tree_leaves(trained_state.params),
        jax.tree_util.tree_leaves(resumed.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Optimizer state left exactly as the live (fresh) one.
    for a, b in zip(
        jax.tree_util.tree_leaves(fresh.opt_state),
        jax.tree_util.tree_leaves(resumed.opt_state),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------------------------- retention
def test_retention_keeps_last_k_plus_best(tmp_path, trained_state):
    mgr = TrainCheckpointManager(str(tmp_path / "c"), keep_last=2)
    kw = dict(epoch=0, data_epoch=0)
    mgr.save(1, trained_state, data_batch_offset=1, **kw)
    mgr.save(2, trained_state, data_batch_offset=2, val_loss=0.1, **kw)  # best
    mgr.save(3, trained_state, data_batch_offset=3, val_loss=0.5, **kw)
    mgr.save(4, trained_state, data_batch_offset=4, **kw)
    mgr.save(5, trained_state, data_batch_offset=5, **kw)
    mgr.wait()
    # Last 2 (4, 5) + the best-val step (2); 1 and 3 GC'd.
    assert mgr.all_steps() == [2, 4, 5]
    assert mgr.best_step == 2
    assert not os.path.exists(mgr.step_path(1))
    assert os.path.exists(mgr.step_path(2))
    mgr.close()


def test_best_step_survives_manager_reopen(tmp_path, trained_state):
    """Preempt/relaunch scenario: the best-val step is tracked in a
    best.json sidecar, so a reopened manager's GC still protects it
    (code-review finding: in-memory-only tracking deleted the run's best
    checkpoint a few saves after resume)."""
    root = str(tmp_path / "c")
    kw = dict(epoch=0, data_epoch=0)
    mgr = TrainCheckpointManager(root, keep_last=2)
    mgr.save(2, trained_state, data_batch_offset=2, val_loss=0.1, **kw)
    mgr.wait()
    mgr.close()

    mgr2 = TrainCheckpointManager(root, keep_last=2)
    assert mgr2.best_step == 2  # recovered from the sidecar
    mgr2.save(4, trained_state, data_batch_offset=4, **kw)
    mgr2.save(6, trained_state, data_batch_offset=6, **kw)
    mgr2.save(8, trained_state, data_batch_offset=8, val_loss=0.5, **kw)
    mgr2.wait()
    # Last 2 (6, 8) + the PRE-RESTART best (2); 0.5 never displaces 0.1.
    assert mgr2.all_steps() == [2, 6, 8]
    assert mgr2.best_step == 2
    mgr2.close()


def test_overwrite_is_an_explicit_error(tmp_path, trained_state):
    mgr = TrainCheckpointManager(str(tmp_path / "c"), keep_last=3)
    mgr.save(
        3, trained_state, epoch=0, data_epoch=0, data_batch_offset=3,
        wait=True,
    )
    with pytest.raises(FileExistsError):
        mgr.save(3, trained_state, epoch=0, data_epoch=0, data_batch_offset=3)
    # on_exists='skip' tolerates (epoch-end save after an interval save).
    path = mgr.save(
        3, trained_state, epoch=0, data_epoch=0, data_batch_offset=3,
        val_loss=0.25, on_exists="skip",
    )
    assert os.path.exists(path)
    assert mgr.best_step == 3  # skip still records the metric
    mgr.close()


def test_legacy_save_checkpoint_refuses_overwrite(tmp_path, trained_state):
    """Satellite: the old force=True silently clobbered model-<epoch>."""
    p = save_checkpoint(str(tmp_path / "c"), trained_state, epoch=2, loss=1.0)
    assert os.path.exists(p)
    with pytest.raises(FileExistsError):
        save_checkpoint(str(tmp_path / "c"), trained_state, epoch=2, loss=0.5)


# ------------------------------------------------------- atomic finalize
def test_interrupted_save_layout_is_ignored_and_swept(tmp_path, trained_state):
    """A crash mid-save leaves `model_<s>.orbax-checkpoint-tmp-<n>`: the
    committed step stays the latest, and reopening the manager sweeps the
    debris (cleanup_tmp_directories)."""
    root = str(tmp_path / "c")
    mgr = TrainCheckpointManager(root, keep_last=3)
    mgr.save(
        5, trained_state, epoch=0, data_epoch=0, data_batch_offset=5,
        wait=True,
    )
    mgr.close()
    fake_tmp = os.path.join(root, "model_6.orbax-checkpoint-tmp-1234567")
    os.makedirs(fake_tmp)
    with open(os.path.join(fake_tmp, "junk"), "w") as f:
        f.write("partial write")
    mgr2 = TrainCheckpointManager(root, keep_last=3)
    assert mgr2.latest_step() == 5
    # orbax 0.11.32 sweeps on a background thread: CheckpointManager's
    # constructor wraps cleanup_temporary_paths in a futures.CommitFuture
    # (a started thread) that only the next save() awaits
    # (_maybe_await_cleanup_tmp_directory), so the debris is gone shortly
    # after the open, not necessarily by its return.
    deadline = time.monotonic() + 30
    while os.path.exists(fake_tmp) and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not os.path.exists(fake_tmp), "tmp debris must be swept on open"
    mgr2.close()


def test_preempt_exit_code_is_ex_tempfail():
    assert PREEMPT_EXIT_CODE == 75  # sysexits EX_TEMPFAIL, documented


# ------------------------------------------------ data-pipeline position
def test_loader_mid_epoch_position_resume():
    """Satellite: restoring (epoch, batch_offset) must continue the exact
    sample sequence — no replay, no skips — because the shuffle order is
    a pure function of (seed, epoch)."""
    from seist_tpu.data import pipeline

    spec = taskspec.get_task_spec("phasenet")
    sds = pipeline.from_task_spec(
        spec, "synthetic", "train", seed=3, in_samples=512,
        dataset_kwargs={"num_events": 30, "trace_samples": 1024},
    )
    def make_loader():
        return pipeline.Loader(
            sds, batch_size=4, shuffle=True, drop_last=True,
            num_workers=2, seed=3,
        )

    full = make_loader()
    full.set_epoch(2)
    all_batches = list(full)
    assert len(all_batches) >= 3

    resumed = make_loader()
    resumed.set_epoch(2)
    resumed.set_start_batch(2)
    rest = list(resumed)
    assert len(rest) == len(all_batches) - 2
    for want, got in zip(all_batches[2:], rest):
        np.testing.assert_array_equal(want.inputs, got.inputs)
        assert want.meta == got.meta
    # One-shot: the next epoch starts from batch 0 again.
    resumed.set_epoch(3)
    assert len(list(resumed)) == len(all_batches)
    full.close()
    resumed.close()


def test_loader_rejects_negative_start_batch():
    from seist_tpu.data import pipeline

    spec = taskspec.get_task_spec("phasenet")
    sds = pipeline.from_task_spec(
        spec, "synthetic", "train", seed=0, in_samples=512,
        dataset_kwargs={"num_events": 12, "trace_samples": 1024},
    )
    loader = pipeline.Loader(sds, batch_size=4)
    with pytest.raises(ValueError):
        loader.set_start_batch(-1)
    loader.close()
