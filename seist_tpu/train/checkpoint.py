"""Checkpoint save / restore (orbax), step-granular and preemption-safe.

Two layers:

* :class:`TrainCheckpointManager` — the fault-tolerance layer built on
  ``orbax.checkpoint.CheckpointManager``: step-granular saves keyed by the
  GLOBAL BATCH counter, async (background) writes with a
  barrier-at-next-save, a keep-last-K-plus-best retention policy with
  logged GC, and orbax's atomic finalize (a save lands in
  ``model_<step>.orbax-checkpoint-tmp-<n>`` and is renamed only when
  complete, so a crash mid-save never corrupts — or even exposes — the
  newest checkpoint; interrupted tmp dirs are swept on the next open).
  The payload carries FULL resume state: params, BN stats, optimizer
  leaves, and a meta record with the data-pipeline position
  (``data_epoch``, ``data_batch_offset``, seed) and the schedule step, so
  a restore continues mid-epoch without replaying or skipping data.

* Legacy functions (``save_checkpoint`` / ``load_checkpoint`` /
  ``restore_into_state``) — the epoch-named single-checkpoint path the
  reference's torch checkpointing maps onto
  (/root/reference/models/_factory.py:59-126). ``load_checkpoint`` also
  restores manager-written step directories (it descends into the
  ``default/`` item dir), so tools/supervise.py can hand either layout to
  ``--checkpoint``.

Orbax handles multi-host coordination internally (every process must call
save; only process 0 writes metadata), replacing the reference's
rank-0-only torch.save guard (train.py:407-415).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

import jax
import numpy as np
import orbax.checkpoint as ocp

from seist_tpu.utils.logger import logger

# Exit code of a training process that checkpointed and exited on SIGTERM
# (sysexits.h EX_TEMPFAIL: "temporary failure, retry"). tools/supervise.py
# treats it as a clean preemption — immediate relaunch, retry budget
# untouched. Keep in sync with tools/supervise.py:PREEMPT_EXIT_CODE (that
# file stays stdlib-only and cannot import this one).
PREEMPT_EXIT_CODE = 75

# Resume meta written by the manager. Superset of the legacy
# {epoch, loss, step}: data_epoch/data_batch_offset pin the data-pipeline
# position (the shuffle order is a pure function of (seed, data_epoch),
# data/pipeline.py), and step doubles as the LR-schedule position (optax
# schedules read the update count, which save/restore round-trips via
# state.step and the opt_state count leaves).
_RESUME_META = {
    "epoch": 0,
    "loss": 0.0,
    "step": 0,
    "data_epoch": 0,
    "data_batch_offset": 0,
    "total_batches": 0,
    "seed": 0,
    # Batch geometry the data position is expressed in: a resume with a
    # different --batch-size would reinterpret the offset and replay/skip
    # samples, so the worker validates these like the seed.
    "steps_per_epoch": 0,
    "batch_size": 0,
}
_LEGACY_META = {"epoch": 0, "loss": 0.0, "step": 0}


def _as_abstract(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype), tree
    )


class _Snapshot(np.ndarray):
    """A host array that only its save holds. orbax's numpy handler
    deep-copies every leaf before it returns (the caller might go on writing
    to it while the background write reads); of a snapshot taken for the
    save that second copy protects nothing and was 8.0-9.4 s of an 8 GB
    save's 10.6-12.7 s, the device idle (PERF.md, PR 29)."""

    def __deepcopy__(self, memo):
        return self


def _host_copy(tree):
    """Snapshot a pytree to host numpy that owns its memory. Async saves
    serialize in the background while the train loop keeps stepping with
    DONATED state buffers; on the CPU backend np-views of those buffers
    would be silently rewritten mid-serialization, so there the snapshot is
    a copy. From an accelerator the device-to-host transfer already is one
    (a second copy of an 8 GB state would be 8 GB of host memory for
    nothing), leaf after leaf so that the transfers' staging stays one
    leaf large."""

    def one(x):
        if not hasattr(x, "shape"):
            return x
        on_cpu = not isinstance(x, jax.Array) or all(
            d.platform == "cpu" for d in x.devices())
        return (np.array(x) if on_cpu else np.asarray(x)).view(_Snapshot)

    return jax.tree_util.tree_map(one, tree)


def _state_payload(state) -> Dict[str, Any]:
    # opt_state is stored as a flat leaves list: optax state trees contain
    # empty-namedtuple nodes (EmptyState) that do not round-trip through a
    # structured orbax restore; the treedef comes from the live TrainState
    # at restore time (restore_into_state).
    return {
        "params": state.params,
        "batch_stats": state.batch_stats if state.batch_stats is not None else {},
        "opt_state": list(jax.tree_util.tree_leaves(state.opt_state)),
    }


def _restore_target(state, meta_defaults: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "params": _as_abstract(state.params),
        "batch_stats": _as_abstract(
            state.batch_stats if state.batch_stats is not None else {}
        ),
        "opt_state": _as_abstract(
            list(jax.tree_util.tree_leaves(state.opt_state))
        ),
        "meta": dict(meta_defaults),
    }


class ProgressFile:
    """Tiny atomic JSON progress record — the ``best.json`` tmp+rename
    pattern generalized for flat (non-orbax) progress state. Used by the
    batch re-picking workers (tools/repick_archive.py) to persist their
    position between segment commits: ``load()`` returns the last saved
    dict (or None), ``save()`` replaces it atomically, so a SIGKILL at
    any instant leaves either the previous record or the new one —
    never a torn file. The record is advisory (the committed segment
    files are the authoritative resume state); it exists so a resumed
    worker can log where it died and skip completed units in O(1)."""

    def __init__(self, path: str):
        self.path = path

    def load(self) -> Optional[Dict[str, Any]]:
        try:
            with open(self.path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def save(self, record: Dict[str, Any]) -> None:
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(record, f, sort_keys=True)
        os.replace(tmp, self.path)


class TrainCheckpointManager:
    """Step-granular async checkpointing with keep-last-K + best retention.

    ``step`` keys are the run's global batch counter
    (``epoch * steps_per_epoch + batches_done``): monotonic across
    epochs, aligned with the fault-injection step numbering, and exactly
    the quantity "work lost on preemption" is measured in.

    Async contract: ``save`` snapshots the state to host memory
    synchronously (donation-safe) and serializes in the background; the
    next ``save`` (or ``wait()`` / ``close()``) barriers on the previous
    one, so at most one write is ever in flight and a completed ``save``
    call means the PREVIOUS checkpoint is durable.
    """

    def __init__(
        self,
        directory: str,
        *,
        keep_last: int = 3,
        async_save: bool = True,
        step_prefix: str = "model",
    ):
        self.directory = os.path.abspath(directory)
        self.keep_last = max(1, int(keep_last))
        self._step_prefix = step_prefix
        self._best_step: Optional[int] = None
        self._best_loss = float("inf")
        # Best tracking must survive the manager's own process dying —
        # that is the PR's whole scenario. A preempted run that resumed
        # with only in-memory best state would let _gc delete the run's
        # best-val checkpoint a few saves later.
        self._best_file = os.path.join(self.directory, "best.json")
        self._load_best()
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=None,  # retention is ours: last K + best
                step_prefix=step_prefix,
                enable_async_checkpointing=async_save,
                create=True,
                # Sweep `.orbax-checkpoint-tmp-*` left by a crash mid-save.
                cleanup_tmp_directories=True,
            ),
        )

    # ------------------------------------------------------------- queries
    def step_path(self, step: int) -> str:
        return os.path.join(self.directory, f"{self._step_prefix}_{step}")

    def all_steps(self) -> List[int]:
        return sorted(self._mgr.all_steps())

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    @property
    def best_step(self) -> Optional[int]:
        return self._best_step

    # --------------------------------------------------------------- save
    def save(
        self,
        step: int,
        state,
        *,
        epoch: int,
        data_epoch: int,
        data_batch_offset: int,
        loss: float = float("inf"),
        val_loss: Optional[float] = None,
        seed: int = 0,
        steps_per_epoch: int = 0,
        batch_size: int = 0,
        wait: bool = False,
        on_exists: str = "error",
    ) -> str:
        """Write checkpoint ``step``. Returns the (future) step path.

        ``data_epoch`` / ``data_batch_offset`` must be the position of the
        NEXT batch to consume — restore hands them straight to
        ``Loader.set_start_batch``. ``val_loss`` (when this save follows a
        validation pass) feeds the best-checkpoint retention. Overwriting
        an existing step is an explicit error (``on_exists='error'``);
        schedule-driven savers that may legitimately re-reach a step
        boundary (epoch-end save after an interval save, resume replay)
        pass ``on_exists='skip'``.
        """
        if step in self._mgr.all_steps():
            if on_exists == "skip":
                logger.info(f"Checkpoint step {step} already saved; skipping")
                self._note_metric(step, val_loss)
                if wait:  # the skipped step's async write may be in flight
                    self.wait()
                return self.step_path(step)
            raise FileExistsError(
                f"checkpoint step {step} already exists in {self.directory}; "
                "refusing to overwrite (pass on_exists='skip' to tolerate)"
            )
        # The previous save's snapshot goes before this one is taken (orbax
        # would wait for that write inside its own save anyway): two
        # snapshots of a state that fills a chip do not fit a small host.
        self.wait()
        payload = _host_copy(_state_payload(state))
        payload["meta"] = {
            "epoch": int(epoch),
            "loss": float(loss if val_loss is None else val_loss),
            "step": int(state.step),
            "data_epoch": int(data_epoch),
            "data_batch_offset": int(data_batch_offset),
            "total_batches": int(step),
            "seed": int(seed),
            "steps_per_epoch": int(steps_per_epoch),
            "batch_size": int(batch_size),
        }
        # Implicit barrier-at-next-save: orbax waits for the in-flight
        # write before starting this one. force=True bypasses orbax's
        # should_save, which silently SKIPS any step <= the directory's
        # latest — a run resumed from an older step (manual rollback to
        # best) would otherwise log saves that never happened. Overwrite
        # protection is ours (the on_exists check above).
        saved = self._mgr.save(
            step, args=ocp.args.StandardSave(payload), force=True
        )
        if not saved:
            raise RuntimeError(
                f"orbax declined checkpoint save at step {step} in "
                f"{self.directory}"
            )
        self._note_metric(step, val_loss)
        self._gc(protect=step)
        if wait:
            self.wait()
        logger.info(
            f"Checkpoint save dispatched: step {step} "
            f"(epoch {epoch}, data position {data_epoch}:{data_batch_offset})"
        )
        return self.step_path(step)

    def _load_best(self) -> None:
        try:
            with open(self._best_file) as f:
                best = json.load(f)
            self._best_step = int(best["step"])
            self._best_loss = float(best["loss"])
        except (OSError, ValueError, KeyError):
            pass  # no sidecar yet (fresh run / legacy dir)

    def _note_metric(self, step: int, val_loss: Optional[float]) -> None:
        if val_loss is None or float(val_loss) >= self._best_loss:
            return
        self._best_loss = float(val_loss)
        self._best_step = step
        # Persist (process 0 only; every host computes the same best from
        # the host-identical val loss). Atomic tmp+rename so a crash
        # mid-write leaves the previous record intact.
        if jax.process_index() == 0:
            tmp = self._best_file + ".tmp"
            try:
                with open(tmp, "w") as f:
                    json.dump({"step": step, "loss": self._best_loss}, f)
                os.replace(tmp, self._best_file)
            except OSError as e:
                logger.warning(f"best.json write failed: {e!r}")

    def _gc(self, protect: int) -> None:
        """Keep the last ``keep_last`` steps plus the best-val step; delete
        (and log) the rest. ``protect`` is the just-dispatched step, which
        may not appear in ``all_steps`` until its async write finalizes."""
        steps = sorted(set(self._mgr.all_steps()) | {protect})
        keep = set(steps[-self.keep_last:])
        keep.add(protect)
        if self._best_step is not None:
            keep.add(self._best_step)
        for s in steps:
            if s in keep:
                continue
            logger.info(
                f"Checkpoint GC: deleting step {s} ({self.step_path(s)}) — "
                f"retention keeps last {self.keep_last} + best "
                f"({self._best_step})"
            )
            self._mgr.delete(s)

    # ------------------------------------------------------------ restore
    def restore(self, state, step: Optional[int] = None) -> Dict[str, Any]:
        """Restore checkpoint ``step`` (default: latest) shaped like the
        live ``state``; returns the payload dict for
        :func:`restore_into_state`."""
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(
                f"no checkpoint to restore in {self.directory}"
            )
        target = _restore_target(state, _RESUME_META)
        restored = self._mgr.restore(
            step, args=ocp.args.StandardRestore(target)
        )
        return restored

    # ------------------------------------------------------------ control
    def wait(self) -> None:
        """Barrier on the in-flight async save (preempt exit path: the
        checkpoint must be durable before the process dies)."""
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self._mgr.close()


def save_checkpoint(
    ckpt_dir: str,
    state,
    epoch: int,
    loss: float,
) -> str:
    """Write ``<ckpt_dir>/model-<epoch>`` (ref naming: `model-{epoch}.pth`,
    train.py:411). Returns the checkpoint path.

    Overwriting an existing checkpoint is an explicit error: the old
    ``force=True`` silently clobbered a prior ``model-<epoch>`` (e.g. two
    runs sharing a log dir, or a resume with a stale ``--start-epoch``),
    destroying the only copy of those params. Step-granular training
    should use :class:`TrainCheckpointManager` instead.
    """
    path = os.path.join(os.path.abspath(ckpt_dir), f"model-{epoch}")
    if os.path.exists(path):
        raise FileExistsError(
            f"checkpoint {path} already exists; refusing to overwrite "
            "(delete it or choose a different epoch/log dir)"
        )
    payload = _state_payload(state)
    payload["meta"] = {"epoch": epoch, "loss": float(loss), "step": int(state.step)}
    with ocp.StandardCheckpointer() as saver:
        saver.save(path, payload)
    logger.info(f"Checkpoint saved: {path}")
    return path


def _payload_dir(ckpt_path: str) -> str:
    """Resolve the orbax item dir: manager-written steps nest the payload
    under ``<step>/default`` (single-item CheckpointManager layout)."""
    path = os.path.abspath(ckpt_path)
    default = os.path.join(path, "default")
    if os.path.isdir(default):
        return default
    return path


def load_checkpoint(
    ckpt_path: str,
    state=None,
) -> Dict[str, Any]:
    """Restore a checkpoint.

    With ``state`` given, the restored arrays adopt the state's exact
    structure/dtypes (full resume: params + batch_stats + opt_state + meta).
    Without it, returns the raw pytree (params-only inspection / inference),
    mirroring the reference's tolerance for bare state-dicts
    (_factory.py:101-102). Accepts both legacy ``model-<epoch>`` dirs and
    manager-written ``model_<step>`` dirs (resume meta included).
    """
    path = _payload_dir(ckpt_path)
    is_manager_layout = path != os.path.abspath(ckpt_path)
    with ocp.StandardCheckpointer() as restorer:
        if state is None:
            return restorer.restore(path)
        # Manager-written checkpoints (default/ item layout) carry the
        # full resume meta; legacy ones only {epoch, loss, step}. Try the
        # layout's native format first so the kept exception is the
        # informative one (a param-shape mismatch, not the other
        # format's meta-tree mismatch).
        metas = (
            (_RESUME_META, _LEGACY_META)
            if is_manager_layout
            else (_LEGACY_META, _RESUME_META)
        )
        first_exc: Optional[Exception] = None
        for meta in metas:
            try:
                return restorer.restore(path, _restore_target(state, meta))
            # Probing both meta layouts: orbax raises layout-specific types
            # we can't enumerate. The first (most informative) failure is
            # kept and re-raised below if the raw restore can't save us.
            except Exception as e:
                first_exc = first_exc or e
        raw = restorer.restore(path)
        if "opt_state" in raw:
            # The checkpoint IS a full one — the structured restore
            # failed for a real reason (shape mismatch from a wrong
            # --model-name, partial write, ...). Surface that (chaining
            # orbax's precise mismatch message), don't silently resume
            # with fresh optimizer moments.
            raise ValueError(
                f"checkpoint {path} has optimizer state but does not match "
                "the live TrainState (wrong --model-name? partial write?)"
            ) from first_exc
    # Params(+stats)-only checkpoint — e.g. written by
    # tools/import_pretrained.py from the reference's raw .pth state-dicts.
    # Adopt the weights, keep the fresh optimizer state: the reference's
    # loader has the same tolerance (_factory.py:101-102 treats a bare
    # state-dict as the model dict and resumes with epoch -1).
    logger.info(
        f"Checkpoint {path} has no optimizer state; loading params only"
    )
    return {
        "params": raw["params"],
        "batch_stats": raw.get("batch_stats") or {},
        "opt_state": list(jax.tree_util.tree_leaves(state.opt_state)),
        "meta": raw.get("meta")
        or {"epoch": -1, "loss": float("inf"), "step": 0},
    }


def restore_into_state(state, restored: Dict[str, Any]):
    """Apply a restored payload onto a TrainState (resume path,
    ref train.py:255-264,324-326)."""
    opt_state = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(state.opt_state),
        jax.tree_util.tree_leaves(restored["opt_state"]),
    )
    return state.replace(
        params=restored["params"],
        batch_stats=restored["batch_stats"] or state.batch_stats,
        opt_state=opt_state,
        step=int(restored["meta"]["step"]),
    )
