"""Train / validate / test orchestration.

Counterpart of the reference's L6 layer (training/train.py:182-484,
training/validate.py:10-134, training/test.py:10-88), redesigned around one
jitted step over a device mesh:

* no DDP wrap, no SyncBatchNorm conversion, no explicit collectives — the
  batch is sharded on the mesh's ``data`` axis and XLA emits gradient/BN
  reductions over ICI;
* the epoch structure, best-val-loss checkpointing, patience early stop,
  per-step cyclic LR, TensorBoard scalars, loss-curve ``.npy`` dumps and
  test-time CSV results all mirror the reference's workflow contract.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import signal
import sys
import threading
import weakref
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from seist_tpu import obs, taskspec
from seist_tpu.data import io_guard, pipeline
from seist_tpu.models import api
from seist_tpu.ops import Metrics, ResultSaver, process_outputs
from seist_tpu.parallel import mesh as mesh_lib
from seist_tpu.train import feed as feed_lib
from seist_tpu.train import (
    PREEMPT_EXIT_CODE,
    TrainCheckpointManager,
    build_cyclic_schedule,
    build_optimizer,
    create_train_state,
    jit_cached_call,
    jit_device_aug_step,
    jit_eval_step,
    jit_multi_step,
    jit_step,
    load_checkpoint,
    make_accum_train_step,
    make_cached_train_call,
    make_device_aug_train_step,
    make_eval_step,
    make_multi_train_step,
    make_train_step,
    restore_into_state,
)
from seist_tpu.utils import faults as faults_lib
from seist_tpu.utils import profiling
from seist_tpu.utils.logger import logger
from seist_tpu.utils.meters import AverageMeter, ProgressMeter
from seist_tpu.utils.misc import (
    count_params,
    device_summary,
    get_safe_path,
    get_time_str,
    strftimedelta,
)
from seist_tpu.utils.tb import ScalarWriter


def is_main_process() -> bool:
    return jax.process_index() == 0


class _LapClock:
    """Seconds since the last call (since construction, the first time), on
    the bus clock: the log lines' wave/s interval."""

    def __init__(self):
        self._last = obs.monotonic()

    def __call__(self) -> float:
        now = obs.monotonic()
        lap, self._last = now - self._last, now
        return lap


class _PreemptionHandler:
    """SIGTERM -> checkpoint-at-next-step-boundary -> exit(75).

    The handler only flips a flag; the train loop polls it at step
    boundaries (between jitted dispatches), saves a final checkpoint, and
    exits with :data:`~seist_tpu.train.checkpoint.PREEMPT_EXIT_CODE` so
    tools/supervise.py relaunches immediately without burning its retry
    budget. Cluster managers deliver SIGTERM to every host's process, so
    the collective orbax save finds all participants.

    Install/uninstall is a context manager; outside the main thread (e.g.
    a test harness driving train_worker from a worker thread) signal
    handlers cannot be installed and the guard degrades to inert.
    """

    def __init__(self):
        self.triggered = False
        self._prev = None
        self._installed = False

    def __enter__(self) -> "_PreemptionHandler":
        if threading.current_thread() is threading.main_thread():
            def _on_term(signum, frame):
                self.triggered = True
                # threadlint: disable=signal-handler-unsafe -- best-effort
                # operator notice; logging's RLock is reentrant from the
                # interrupted main thread (worst case: interleaved output,
                # never a deadlock), and the flag above is already set so
                # the preempt proceeds even if this line dies.
                logger.warning(
                    "SIGTERM received: will checkpoint at the next step "
                    f"boundary and exit {PREEMPT_EXIT_CODE}"
                )
            self._prev = signal.signal(signal.SIGTERM, _on_term)
            self._installed = True
        return self

    def __exit__(self, *exc) -> None:
        if self._installed:
            signal.signal(signal.SIGTERM, self._prev)
            self._installed = False


class _BadUpdateMonitor:
    """Host-side consecutive-skipped-update tracking for the bad-update
    guard (train/step.py ``guard=True``).

    Fetching the per-step finite flag immediately would serialize JAX's
    async dispatch (the same stall the worker avoids for losses), so
    flags are evaluated ``lag`` calls late: by then the device has long
    finished that step and the host read costs nothing. The rollback
    decision is therefore delayed by at most ``lag`` extra bad updates —
    all of which the guard already prevented from touching the params.

    Every host computes the same flags (they derive from the all-reduced
    gradients), so rollback decisions cannot diverge across hosts.
    """

    def __init__(self, max_bad: int, lag: int = 2):
        self.max_bad = int(max_bad)
        self.lag = max(0, int(lag))
        self.bad_run = 0  # consecutive skipped updates at the tail
        self.total_skipped = 0
        self._pending: "collections.deque" = collections.deque()

    def push(self, applied_dev) -> bool:
        """Queue one call's applied flag (scalar 0/1) or per-micro-step
        applied mask (ordered (k,) array from the scanned paths); returns
        True when the consecutive-bad run has reached ``max_bad``
        (rollback needed)."""
        self._pending.append(applied_dev)
        while len(self._pending) > self.lag:
            self._eval(self._pending.popleft())
        return self.exceeded

    def flush(self) -> bool:
        while self._pending:
            self._eval(self._pending.popleft())
        return self.exceeded

    def reset(self) -> None:
        self.bad_run = 0
        self._pending.clear()

    @property
    def exceeded(self) -> bool:
        return bool(self.max_bad) and self.bad_run >= self.max_bad

    def _eval(self, applied_dev) -> None:
        mask = np.atleast_1d(np.asarray(jax.device_get(applied_dev)))
        skipped = int(mask.size - mask.sum())
        self.total_skipped += skipped
        if skipped == 0:
            self.bad_run = 0
        else:
            # Only the TRAILING skips extend a consecutive run: a call
            # ending in a successful update (e.g. [skip, skip, ok] on the
            # packed paths) breaks the run regardless of earlier skips.
            trailing = 0
            for v in mask[::-1]:
                if v:
                    break
                trailing += 1
            if trailing == mask.size:
                self.bad_run += trailing
            else:
                self.bad_run = trailing
        if skipped > 0:
            logger.warning(
                f"Bad-update guard: skipped {skipped} non-finite update(s) "
                f"(consecutive run: {self.bad_run})"
            )


def _build_loader(args: Any, spec: taskspec.TaskSpec, mode: str) -> pipeline.Loader:
    sds = pipeline.from_task_spec(
        spec,
        args.dataset_name,
        mode,
        seed=args.seed,
        data_dir=args.data,
        in_samples=args.in_samples,
        # a token sequence has nothing to augment (and the 2x-epoch rule
        # would only repeat it)
        augmentation=args.augmentation and not spec.tokens,
        shuffle=args.shuffle,
        data_split=args.data_split,
        train_size=args.train_size,
        val_size=args.val_size,
        max_event_num=args.max_event_num,
        min_snr=args.min_snr,
        p_position_ratio=args.p_position_ratio,
        coda_ratio=args.coda_ratio,
        norm_mode=args.norm_mode,
        add_event_rate=args.add_event_rate,
        add_noise_rate=args.add_noise_rate,
        add_gap_rate=args.add_gap_rate,
        drop_channel_rate=args.drop_channel_rate,
        scale_amplitude_rate=args.scale_amplitude_rate,
        pre_emphasis_rate=args.pre_emphasis_rate,
        pre_emphasis_ratio=args.pre_emphasis_ratio,
        generate_noise_rate=args.generate_noise_rate,
        shift_event_rate=args.shift_event_rate,
        mask_percent=args.mask_percent,
        noise_percent=args.noise_percent,
        min_event_gap_sec=args.min_event_gap,
        soft_label_shape=args.label_shape,
        label_width=args.label_width,
        dataset_kwargs=getattr(args, "dataset_kwargs", None),
        # Forwarded only when set: SeismicDataset owns the single default,
        # and an explicit 0 means zero tolerance (abort on the first
        # quarantined sample) so no `or`-coercion.
        **(
            {"max_quarantine_frac": float(args.max_quarantine_frac)}
            if getattr(args, "max_quarantine_frac", None) is not None
            else {}
        ),
    )
    return pipeline.Loader(
        sds,
        batch_size=args.batch_size,
        shuffle=(mode == "train" and args.shuffle),
        drop_last=(mode == "train"),
        num_workers=args.workers,
        # Process workers only where the throughput matters: a second
        # resident pool (each child holding a full dataset copy) for the
        # occasional eval pass is pure memory cost.
        worker_processes=(
            int(getattr(args, "loader_processes", 0) or 0)
            if mode == "train"
            else 0
        ),
        seed=args.seed,
        num_shards=jax.process_count(),
        shard_index=jax.process_index(),
        mixture_temperature=feed_lib.mixture_temperature(args, mode),
    )


def _make_metrics(args: Any, tasks: List[str], fs: int) -> Dict[str, Metrics]:
    return {
        task: Metrics(
            task=task,
            metric_names=taskspec.get_metrics(task),
            sampling_rate=fs,
            time_threshold=args.time_threshold,
            num_samples=args.in_samples,
        )
        for task in tasks
    }


def _postprocess_batch(
    args: Any,
    spec: taskspec.TaskSpec,
    outputs,
    fs: int,
):
    if spec.outputs_transform_for_results is not None:
        outputs = spec.outputs_transform_for_results(outputs)
    return process_outputs(
        outputs,
        spec.labels,
        sampling_rate=fs,
        ppk_threshold=args.ppk_threshold,
        spk_threshold=args.spk_threshold,
        det_threshold=args.det_threshold,
        min_peak_dist=args.min_peak_dist,
        max_detect_event_num=args.max_detect_event_num,
    )


def _update_task_metrics(
    metrics_merged: Dict[str, Metrics],
    batch_metrics: Dict[str, Metrics],
    results: Dict[str, Any],
    metrics_targets: Dict[str, np.ndarray],
    valid: int,
) -> None:
    """Feed one batch into fresh per-batch metrics + running accumulators
    (ref train.py:144-164). ``valid`` trims eval tail padding. Results may be
    globally-sharded device arrays — ``to_local`` keeps only this host's
    rows, which line up with the host-local metrics_targets."""
    for task, m in batch_metrics.items():
        tgt = mesh_lib.to_local(metrics_targets[task])[:valid]
        prd = mesh_lib.to_local(results[task])[:valid]
        if prd.ndim < 2:
            prd = prd[:, None]
        m.compute(tgt, prd)
        metrics_merged[task].add(m)


@jax.jit
def _token_hits(logits, targets, mask):
    from seist_tpu.models.losses import token_hits

    rows = (mask > 0)[:, None]
    return token_hits(logits, jax.numpy.where(rows, targets, -1))


def _feed_token_counters(aux_per_step: List[Dict[str, Any]]) -> None:
    """A token task's per-step counts (fetched with the epoch's losses)
    onto the bus: ``tokens_trained``, ``moe_slots_local`` (token-slots on
    experts held), ``moe_overflow_rows`` (slots that found no room in the
    experts' buffer: must stay 0) and the gauge
    ``moe_load_max_over_mean``."""
    if not aux_per_step:
        return
    counters = {
        name: obs.BUS.counter(name)
        for name in ("tokens_trained", "moe_slots_local", "moe_overflow_rows")
    }
    for aux in aux_per_step:
        counters["tokens_trained"].inc(float(aux["tokens"]))
        if "moe_slots_local" in aux:
            for name in ("moe_slots_local", "moe_overflow_rows"):
                counters[name].inc(float(aux[name]))
            obs.BUS.gauge("moe_load_max_over_mean").set(
                float(aux["moe_load_max_over_mean"]))


def validate(
    args: Any,
    state,
    eval_step,
    spec: taskspec.TaskSpec,
    val_loader: pipeline.Loader,
    mesh,
    *,
    testing: bool = False,
    save_results: bool = False,
    watchdog: Optional[io_guard.StallWatchdog] = None,
    resident: Optional[pipeline.ResidentEvalPass] = None,
) -> Tuple[float, Dict[str, Metrics]]:
    """Eval loop (ref validate.py:10-134): loss + per-task metrics; at test
    time optionally accumulate the results CSV. ``watchdog`` (the train
    worker's data-plane stall watchdog) is armed while blocked on val
    batches — a wedged val loader preempts instead of hanging the run.
    ``resident`` (a training run's memo of its validation pass) replays
    the first whole pass's placed batches in every later one; without it
    every pass streams from the host loader."""
    tasks = list(spec.eval)
    fs = val_loader.dataset.sampling_rate()
    metrics_merged = _make_metrics(args, tasks, fs)
    loss_meter = AverageMeter("loss", ":.4e")
    saver = (
        ResultSaver(item_names=tasks)
        if (save_results and is_main_process() and not spec.tokens)
        else None
    )
    token_counts: List[Any] = []  # per batch (hits, positions), on the device

    # Four spans partition a pass (docs/OBSERVABILITY.md): the wait on the
    # host loader, the eval call with the loss fetch (the device's share and
    # the sync), the un-jitted picking, and the host scoring. A replayed
    # pass waits on nothing: its val_host_wait reads the replay itself.
    for step, batch in enumerate(
        obs.timed_iter(
            pipeline.eval_batches(
                val_loader, mesh, watchdog=watchdog, resident=resident
            ),
            "val_host_wait",
        )
    ):
        with obs.BUS.span("val_step"):
            loss, outputs = eval_step(
                state, batch.inputs, batch.loss_targets, batch.mask
            )
            valid = int(mesh_lib.to_local(batch.mask).sum())
            # Weight by the GLOBAL valid count so every host's running val
            # loss is identical — checkpoint/early-stop decisions must not
            # diverge across hosts (tail padding lives on one host's shard
            # only).
            # jaxlint: disable=host-sync-item-loop -- one scalar per VAL batch; the running meter (and the float(loss) next line) needs it now
            global_valid = int(np.asarray(jax.device_get(batch.mask.sum())))
            loss_meter.update(float(loss), max(global_valid, 1))
        if spec.tokens:
            # No picker and no per-task scores: token accuracy, counted on
            # the device from the logits (padded rows carry real targets,
            # so they are weighted out like the loss's).
            with obs.BUS.span("val_metrics"):
                token_counts.append(
                    _token_hits(outputs, batch.loss_targets, batch.mask))
            continue
        with obs.BUS.span("val_postprocess"):
            results = _postprocess_batch(args, spec, outputs, fs)
        with obs.BUS.span("val_metrics"):
            batch_metrics = _make_metrics(args, tasks, fs)
            _update_task_metrics(
                metrics_merged, batch_metrics, results,
                batch.metrics_targets, valid,
            )
        if saver is not None:
            import json as _json

            metas = [_json.loads(m) for m in batch.meta[:valid]]
            meta_cols = {k: [m[k] for m in metas] for k in metas[0]} if metas else {}
            saver.append(
                meta_cols,
                {
                    t: mesh_lib.to_local(batch.metrics_targets[t])[:valid]
                    for t in tasks
                },
                {t: mesh_lib.to_local(results[t])[:valid] for t in tasks},
            )

    for m in metrics_merged.values():
        m.synchronize_between_processes()

    if saver is not None:
        # No-clobber contract (ref validate.py:130 get_safe_path): test mode
        # reusing an existing log dir must not overwrite prior results.
        out_csv = get_safe_path(
            os.path.join(
                logger.logdir(), f"test_results_{val_loader.dataset.name()}.csv"
            )
        )
        saver.save_as_csv(out_csv)
        logger.info(f"Test results saved: {out_csv}")

    phase = "test" if testing else "val"
    if spec.tokens:
        with obs.BUS.span("val_metrics"):  # one fetch for the whole pass
            fetched = jax.device_get(token_counts)
        token_hits = sum(int(h) for h, _ in fetched)
        token_count = sum(int(c) for _, c in fetched)
        accuracy = token_hits / max(token_count, 1)
        obs.BUS.gauge(f"{phase}_token_accuracy").set(accuracy)
        logger.info(
            f"[{phase}] {args.model_name} tokens: loss {loss_meter.avg:.4f} "
            f"accuracy {accuracy:.4f} over {token_count} positions"
        )
    for task, m in metrics_merged.items():
        logger.info(f"[{phase}] {args.model_name} {task}: {m}")
    return loss_meter.avg, metrics_merged


# Cleanup callbacks registered by the running worker (its _Telemetry.close);
# drained by _dump_flight_on_exception's finally so EVERY exit path —
# return, sys.exit, uncaught exception — tears the telemetry plane down
# (os._exit hard deaths skip it; the process is gone anyway).
_OBS_CLEANUP: List[Any] = []


def _dump_flight_on_exception(fn):
    """Any uncaught exception in the wrapped worker leaves a flight-
    recorder dump (reason ``exception``) before propagating — the crash
    path that ISN'T one of the managed deaths (rollback/preempt/stall/
    quarantine) still gets its forensic record. Deduped: a managed path
    that already dumped seconds earlier doesn't leave a second file.
    The finally drains _OBS_CLEANUP, so a crashed run cannot leak the
    metrics HTTP port, the events fd, or the SIGUSR2 handler into the
    process's next run."""
    import functools

    @functools.wraps(fn)
    def wrapper(*a, **k):
        try:
            # A preempt exit or an early stop leaves train_epoch open: the
            # frame drops such spans from this thread's stack of open spans.
            with obs.span_frame():
                return fn(*a, **k)
        except Exception as e:
            obs.flight.dump_on_death("exception", dedup_s=5.0, error=repr(e))
            raise
        finally:
            while _OBS_CLEANUP:
                cb = _OBS_CLEANUP.pop()
                try:
                    cb()
                except Exception:  # noqa: BLE001 - teardown must not mask
                    # the real exception propagating out of the worker
                    pass

    return wrapper


class _Telemetry:
    """A run's telemetry plane (docs/OBSERVABILITY.md): the flight recorder,
    the events log, the opt-in Prometheus endpoint and the SIGUSR2 profiler
    trigger, opened at set-up and closed once on whichever path the run
    ends."""

    def __init__(self, args: Any):
        # Flight recorder: always on (a deque append per step — priced in
        # BENCH step_breakdown.telemetry); every death path dumps it.
        # Any --flight-steps <= 0 falls back to the documented default
        # rather than crashing the run at startup.
        fsteps = int(getattr(args, "flight_steps", 0) or 0)
        self.recorder = obs.FlightRecorder(capacity=fsteps if fsteps > 0 else 256)
        obs.flight.install(self.recorder)
        obs.register_default_collectors()
        self.events = (
            obs.EventLog(os.path.join(logger.logdir(), "events.jsonl"))
            if is_main_process()
            else None
        )
        # Opt-in Prometheus endpoint (--metrics-port; obs/http.py): >0 binds
        # that loopback port, -1 an ephemeral one (logged), 0 disables.
        self.profile_trigger = profile_trigger = obs.ProfileTrigger()
        self.metrics_server = None
        mport = int(getattr(args, "metrics_port", 0) or 0)
        if mport and is_main_process():
            self.metrics_server = obs.start_metrics_server(
                max(mport, 0), profile_trigger=profile_trigger
            )
        # SIGUSR2 -> on-demand profiler capture at the next step boundary
        # (same window machinery as --profile-steps and POST /profile).
        self._prev_usr2 = None
        if (
            threading.current_thread() is threading.main_thread()
            and hasattr(signal, "SIGUSR2")
        ):
            def _on_usr2(signum, frame):
                # threadlint: disable=signal-handler-unsafe -- request() is a
                # single lock-free GIL-atomic deque append (ProfileTrigger is
                # deliberately lockless for exactly this call site: the
                # interrupted main thread may be inside consume()).
                profile_trigger.request()
                # threadlint: disable=signal-handler-unsafe -- best-effort
                # notice; logging's RLock is reentrant from the interrupted
                # main thread, worst case interleaved output.
                logger.info(
                    "[obs] SIGUSR2: profiler capture requested "
                    f"({obs.http.DEFAULT_PROFILE_STEPS} steps)"
                )
            self._prev_usr2 = signal.signal(signal.SIGUSR2, _on_usr2)
        self._closed = False
        _OBS_CLEANUP.append(self.close)

    def close(self) -> None:
        """Tear down the telemetry plane. Idempotent; runs on the normal
        return, the preempt exit, AND — via _OBS_CLEANUP drained in the
        _dump_flight_on_exception finally — every exception/SystemExit
        path, so a crashed run cannot leave the metrics port bound or
        the events fd open for the process's next run. Uninstalling the
        recorder also unhooks its bus span sink, so back-to-back runs in
        one process never stack sinks."""
        if self._closed:
            return
        self._closed = True
        obs.flight.install(None)
        if self.events is not None:
            self.events.close()
        if self.metrics_server is not None:
            self.metrics_server.shutdown()
            self.metrics_server.server_close()  # release the listening port
        if self._prev_usr2 is not None:
            try:
                signal.signal(signal.SIGUSR2, self._prev_usr2)
            except ValueError:  # not the main thread anymore
                pass

    def emit(self, kind: str, **fields) -> None:
        self.recorder.record_event(kind, **fields)
        if self.events is not None:
            self.events.emit(kind, **fields)


class _ProfileWindow:
    """--profile-steps N: capture a jax.profiler trace of N steady-state
    OPTIMIZER steps (skipping compile/warmup) in the first trained epoch.
    Counted in optimizer steps whatever the feed (each call advances
    ``updates_per_call`` of them). Later captures are re-armed on demand:
    SIGUSR2 or POST /profile on --metrics-port."""

    def __init__(self, args: Any, updates_per_call: int, telemetry: _Telemetry):
        self.steps = int(getattr(args, "profile_steps", 0) or 0)
        self.updates_per_call = updates_per_call
        self.start = 2 * updates_per_call  # skip the first two calls
        self.tracing = False
        self.dir = ""
        self.telemetry = telemetry

    def step(self, opt_step: int, loss) -> None:
        """``opt_step``: optimizer steps completed before this call."""
        if not is_main_process():
            return
        if not self.tracing:
            # On-demand capture (SIGUSR2 / POST /profile): open the
            # window at the next step boundary. Consume ONLY when idle —
            # a request arriving mid-capture stays in the trigger box and
            # opens its own window once this one closes.
            req = self.telemetry.profile_trigger.consume()
            if req:
                self.steps = req
                self.start = opt_step + self.updates_per_call
                self.telemetry.emit("profile_requested", steps=req)
        if not self.steps:
            return
        if not self.tracing and opt_step >= self.start:
            # Unique per supervise attempt AND per capture window
            # (timestamp + pid + no-clobber suffix): a relaunched run must
            # never overwrite the previous attempt's trace.
            self.dir = get_safe_path(
                os.path.join(
                    logger.logdir(), "profile",
                    f"{get_time_str()}_p{os.getpid()}",
                )
            )
            profiling.trace_start(self.dir)
            self.tracing = True
        elif self.tracing and opt_step >= self.start + self.steps:
            self._stop(loss)
            logger.info(f"Profiler trace saved: {self.dir}")

    def end_epoch(self, losses: List[Any]) -> None:
        if self.tracing:  # epoch shorter than the capture window
            self._stop(losses)
            logger.info(f"Profiler trace saved (short epoch): {self.dir}")

    def _stop(self, pending) -> None:
        # Sync first: steps may still be executing asynchronously, and
        # stopping early would truncate their device activity.
        jax.block_until_ready(pending)
        profiling.trace_stop()
        self.tracing = False
        self.steps = 0  # one-shot; the trigger re-arms it


@dataclasses.dataclass
class _Run:
    """One training run: what set-up built, where the loop stands (``state``,
    ``epoch``, ``batches_done``: what a death path checkpoints) and what the
    epochs have yielded so far."""

    args: Any
    spec: taskspec.TaskSpec
    mesh: Any
    train_loader: pipeline.Loader
    val_loader: pipeline.Loader
    # Validation builds the same batches in every pass: the first whole
    # pass is kept resident and replayed (independent of the model state,
    # so a rollback keeps it and a restart refills it).
    resident_val: pipeline.ResidentEvalPass
    steps_per_epoch: int
    epochs: int
    state: Any
    start_epoch: int
    start_batch: int  # mid-epoch resume offset (batches already consumed)
    feed: feed_lib.Feed
    train_step: Any
    eval_step: Any
    writer: Optional[ScalarWriter]
    ckpt_mgr: TrainCheckpointManager
    save_every: int
    faults: faults_lib.FaultInjector
    watchdog: Optional[io_guard.StallWatchdog]
    telemetry: _Telemetry
    monitor: _BadUpdateMonitor
    preempt: _PreemptionHandler
    profile: _ProfileWindow
    epoch: int = 0
    batches_done: int = 0
    best_loss: float = float("inf")
    best_ckpt_path: str = ""
    patience_counter: int = 0
    train_losses: List[float] = dataclasses.field(default_factory=list)
    val_losses: List[float] = dataclasses.field(default_factory=list)
    epoch_times: List[float] = dataclasses.field(default_factory=list)


def _step_out(ret):
    """Normalize (state, loss, outputs[, diag]) across guard on/off."""
    if len(ret) == 4:
        return ret
    s, l, o = ret
    return s, l, o, None


def _save(run: _Run, epoch: int, batches_done: int, **kw) -> str:
    """Async save of the state with its data position: at a
    --save-interval-steps boundary, at the end of the best epoch so far
    (``val_loss=``, which feeds the manager's keep-best retention) and on
    the preempt exit (``wait=True``). The recorded position is the NEXT
    batch to consume; returns the checkpoint's path."""
    gstep = epoch * run.steps_per_epoch + batches_done
    with obs.BUS.span("checkpoint_save"):
        return run.ckpt_mgr.save(
            gstep,
            run.state,
            epoch=epoch,
            data_epoch=gstep // run.steps_per_epoch,
            data_batch_offset=gstep % run.steps_per_epoch,
            seed=run.args.seed,
            steps_per_epoch=run.steps_per_epoch,
            batch_size=int(run.args.batch_size),
            # resume/rollback may re-reach a step, and an interval save may
            # own an epoch's end
            on_exists="skip",
            **kw,
        )


def _rollback(run: _Run):
    """Bad-update-guard rollback: restore the last checkpoint (params
    + optimizer) and continue from the CURRENT data position."""
    monitor = run.monitor
    run.ckpt_mgr.wait()
    step_r = run.ckpt_mgr.latest_step()
    if step_r is None:
        raise RuntimeError(
            f"{monitor.bad_run} consecutive non-finite updates and no "
            "checkpoint to roll back to — aborting (enable "
            "--save-interval-steps for rollback coverage)"
        )
    logger.warning(
        f"Bad-update guard: {monitor.bad_run} consecutive non-finite "
        f"updates; rolling back to checkpoint step {step_r}"
    )
    # The run survives a rollback, but the steps leading into it are
    # exactly what a post-mortem wants — snapshot them now, before
    # the ring rolls past (docs/OBSERVABILITY.md).
    run.telemetry.emit(
        "bad_update_rollback",
        rollback_to_step=int(step_r),
        consecutive_bad=int(monitor.bad_run),
    )
    # arm_dedup=False: this dump is non-fatal (the run continues) and
    # must never suppress the record of a crash seconds later.
    obs.flight.dump_on_death(
        "bad_update_rollback", arm_dedup=False,
        rollback_to_step=int(step_r),
    )
    restored = run.ckpt_mgr.restore(run.state, step=step_r)
    monitor.reset()
    return mesh_lib.replicate(run.mesh, restore_into_state(run.state, restored))


def _preempt_exit(run: _Run, epoch: int, batches_done: int, hard: bool = False):
    """Step-boundary preemption: make the final checkpoint durable
    (wait=True barriers the async write), then exit with the
    documented preempt code for tools/supervise.py.

    ``hard=True`` (the loader-death path) ends in ``os._exit``: the
    data plane is known-wedged and its pool threads are non-daemon,
    so ``sys.exit`` would hang forever in ``threading._shutdown``
    joining a thread stuck inside a dead read — the exact hang this
    machinery exists to eliminate. The watchdog is left armed as the
    escalation if even the final save wedges."""
    if run.watchdog is not None and not hard:
        run.watchdog.stop()
    gstep = epoch * run.steps_per_epoch + batches_done
    d_epoch, d_off = divmod(gstep, run.steps_per_epoch)  # the next batch
    _save(run, epoch, batches_done, wait=True)
    logger.warning(
        f"Preempted: checkpoint step {gstep} durable "
        f"(data position {d_epoch}:{d_off}); exiting {PREEMPT_EXIT_CODE}"
    )
    run.telemetry.emit(
        "preempt", gstep=int(gstep), data_epoch=int(d_epoch),
        data_batch_offset=int(d_off), hard=bool(hard),
    )
    obs.flight.dump_on_death("preempt", gstep=int(gstep))
    if run.writer is not None:
        run.writer.close()
    run.train_loader.close()
    run.val_loader.close()
    run.ckpt_mgr.close()
    run.telemetry.close()
    if hard:
        io_guard.hard_exit(PREEMPT_EXIT_CODE)
    sys.exit(PREEMPT_EXIT_CODE)


def _loader_death_exit(run: _Run, e, epoch: int, batches_done: int):
    """Loader-thread death (data/io_guard.py LoaderDeathError): the
    device and params are healthy — checkpoint the current position
    and preempt-exit so the supervisor relaunches with a fresh data
    plane rather than the run dying opaquely (or, pre-watchdog,
    hanging forever)."""
    logger.error(
        f"Loader worker death: {e}; dumping thread stacks and "
        "preempt-exiting for supervised relaunch"
    )
    io_guard.dump_thread_stacks()
    if run.watchdog is not None:
        # Escalation: the data plane is wedged; if the final save
        # below hangs too, the watchdog's os._exit still gets us out.
        run.watchdog.arm()
    _preempt_exit(run, epoch, batches_done, hard=True)


def _init_state(args: Any, steps_per_epoch: int, total_steps: int):
    """Model + optimizer + state (+ restore): ``(state, start_epoch,
    start_batch)``, the state placed as the jitted step leaves it."""
    in_channels = taskspec.get_num_inchannels(args.model_name)
    model = api.create_model(
        args.model_name, in_channels=in_channels, in_samples=args.in_samples
    )
    variables = api.init_variables(
        model, seed=args.seed, in_samples=args.in_samples, in_channels=in_channels
    )
    logger.info(f"{args.model_name} params: {count_params(variables['params']):,}")

    if args.use_lr_scheduler:
        schedule = build_cyclic_schedule(
            base_lr=args.base_lr,
            max_lr=args.max_lr,
            total_steps=total_steps,
            warmup_steps=args.warmup_steps,
            down_steps=args.down_steps,
            mode=args.lr_scheduler_mode,
        )
    else:
        schedule = args.max_lr
    l1_kernel = getattr(args, "conv_kernel_l1_alpha", 0.0)
    l1_bias = getattr(args, "conv_bias_l1_alpha", 0.0)
    l1_mask_fn = None
    if l1_kernel or l1_bias:
        # Reference scope: these L1 grad hooks exist only on EQTransformer's
        # encoder/decoder convs (ref eqtransformer.py:43-51,388-396).
        if args.model_name != "eqtransformer":
            raise ValueError(
                "--conv-{kernel,bias}-l1-alpha apply only to eqtransformer "
                f"(got --model-name {args.model_name})"
            )
        from seist_tpu.models.eqtransformer import l1_param_mask

        l1_mask_fn = l1_param_mask
    tx = build_optimizer(
        args.optim,
        schedule,
        weight_decay=args.weight_decay,
        momentum=args.momentum,
        l1_kernel_alpha=l1_kernel,
        l1_bias_alpha=l1_bias,
        l1_mask_fn=l1_mask_fn,
    )
    state = create_train_state(model, variables, tx)

    start_epoch = args.start_epoch
    start_batch = 0
    if args.checkpoint:
        restored = load_checkpoint(args.checkpoint, state)
        state = restore_into_state(state, restored)
        meta = restored["meta"]
        if "data_epoch" in meta:
            # Step-granular checkpoint: continue mid-epoch from the exact
            # data position — no replayed, no skipped samples.
            start_epoch = int(meta["data_epoch"])
            start_batch = int(meta["data_batch_offset"])
            if start_batch >= steps_per_epoch:
                start_epoch += 1
                start_batch = 0
            # The shuffle order is a pure function of (seed, epoch) and
            # the batch offset is expressed in the saving run's batch
            # geometry: resuming mid-epoch with a different seed or batch
            # size would replay some samples and skip others — the exact
            # failure this machinery exists to prevent.
            for field, current in (
                ("seed", int(args.seed)),
                ("steps_per_epoch", steps_per_epoch),
                ("batch_size", int(args.batch_size)),
            ):
                saved_v = int(meta.get(field, 0) or current)
                if saved_v == current:
                    continue
                if start_batch > 0:
                    raise ValueError(
                        f"{field} {current} does not match the "
                        f"checkpoint's {field} {saved_v}; a mid-epoch "
                        f"resume (batch offset {start_batch}) would "
                        "replay/skip data. Relaunch with the original "
                        f"{field}."
                    )
                logger.warning(
                    f"{field} {current} differs from the checkpoint's "
                    f"{saved_v}: epoch boundaries/shuffles will not "
                    "match the original run"
                )
        else:
            # Legacy epoch checkpoint: next epoch from scratch.
            start_epoch = int(meta["epoch"]) + 1
        logger.info(
            f"Resumed from {args.checkpoint} (epoch {start_epoch}, "
            f"batch offset {start_batch}, loss {float(meta['loss']):.4f}, "
            f"update step {int(state.step)})"
        )
    return state, start_epoch, start_batch


def _build_steps(args: Any, spec: taskspec.TaskSpec, feed: feed_lib.Feed, mesh):
    """``(train_step, eval_step)``, jitted: the train step of the feed's
    path, one row a kind. The ``make_*`` / ``jit_*`` names are THIS module's
    globals, looked up when called: the benchmark's harness wraps and
    replaces them here (benchmarks/drivers/train.py ``install_taps``,
    benchmarks/tests/broken_run.py), so a step built through another module
    would run untapped."""
    loss_fn, k = spec.loss(), feed.batches_per_call
    dtype = getattr(args, "dtype", "fp32")
    # Bad-update guard: detect non-finite loss/grad-norm inside the jitted
    # step, skip the poisoned update, and after max_bad_steps consecutive
    # skips roll back to the last checkpoint (train/step.py
    # _guarded_update; docs/FAULT_TOLERANCE.md).
    kw = dict(compute_dtype=dtype, guard=bool(getattr(args, "bad_step_guard", True)))
    if feed.kind == "cached":
        train_step = jit_cached_call(
            make_cached_train_call(
                spec, loss_fn, feed.processor, steps_per_call=k, **kw
            ),
            mesh,
            feed.cache.arrays,
        )
    elif feed.kind == "step":
        train_step = jit_device_aug_step(
            make_device_aug_train_step(spec, loss_fn, feed.processor, **kw), mesh
        )
    elif feed.kind == "packed" and feed.accumulate:
        # One update from k micro-batch gradients, scanned in one jitted
        # program; the stacked-batch layout shares jit_multi_step's sharding.
        train_step = jit_multi_step(
            make_accum_train_step(spec, loss_fn, accum_steps=k, **kw), mesh
        )
    elif feed.kind == "packed":
        # k updates scanned inside one jitted program (dispatch amortization).
        train_step = jit_multi_step(
            make_multi_train_step(spec, loss_fn, steps_per_call=k, **kw), mesh
        )
    else:
        train_step = jit_step(make_train_step(spec, loss_fn, **kw), mesh)
    eval_step = jit_eval_step(
        make_eval_step(spec, loss_fn, compute_dtype=dtype), mesh
    )
    return train_step, eval_step


def _train_call(run: _Run, epoch: int, call: int, step_args, g_gstep):
    """One call of the step, ``batches_per_call`` batches, every duty once:
    the flight recorder's tag, the ``global_step`` gauge, the fault hook,
    the dispatch, the bad-update monitor and its rollback, the profile
    window, the interval save and the preempt exit. Returns the call's
    ``(loss, outputs)``, both still on the device."""
    feed, monitor, save_every = run.feed, run.monitor, run.save_every
    k = feed.batches_per_call
    first = epoch * run.steps_per_epoch + call * k
    # Record BEFORE the spans of this call end, so the recorder tags them
    # with the step that is actually running — the dying step's spans must
    # carry its number.
    run.telemetry.recorder.record_step(first)
    g_gstep.set(first)
    run.faults.on_step(first, n_steps=k)
    with obs.BUS.span("step_dispatch"):
        run.state, loss, outputs, diag = _step_out(
            run.train_step(run.state, *step_args)
        )
    if diag is not None and monitor.push(diag["applied"]):
        run.state = _rollback(run)
    run.profile.step(call * feed.updates_per_call, loss)
    run.batches_done = done = (call + 1) * k
    if save_every and done // save_every > (done - k) // save_every:
        _save(run, epoch, done)
    if run.preempt.triggered:
        _preempt_exit(run, epoch, done)
    return loss, outputs


def _train_epoch(run: _Run, epoch: int):
    """One epoch's training (ref train.py:20-179), whatever the feed:
    ``(losses, aux, train metrics, wave/s)``, the first two still on the
    device, one entry a call."""
    args, feed, monitor = run.args, run.feed, run.monitor
    k, steps_per_epoch = feed.batches_per_call, run.steps_per_epoch
    obs.BUS.gauge("epoch").set(epoch)
    run.train_loader.set_epoch(epoch)
    skip = run.start_batch if epoch == run.start_epoch else 0
    if skip % k:
        # A call consumes k batches; a checkpoint from a run with another
        # k may sit off a call boundary.
        logger.warning(
            f"Resume offset {skip} is not a multiple of the packed "
            f"group {k}; rounding down (re-trains {skip % k} "
            "batch(es))"
        )
        skip = (skip // k) * k
    if skip:
        run.train_loader.set_start_batch(skip)
        logger.info(f"Mid-epoch resume: epoch {epoch} from batch {skip}")
    epoch_rng = jax.random.fold_in(jax.random.PRNGKey(args.seed), epoch)

    tasks = list(run.spec.eval)
    fs = run.train_loader.dataset.sampling_rate()
    loss_meter = AverageMeter("loss", ":.4e")
    wps_meter = AverageMeter("wave/s", ":.1f")
    metrics_merged = _make_metrics(args, tasks, fs)
    progress = ProgressMeter(
        steps_per_epoch, [loss_meter, wps_meter], prefix=f"Epoch[{epoch}] "
    )
    # Log-interval clock for wave/s (seconds since the last log line).
    lap = _LapClock()
    global_bs = args.batch_size * jax.process_count()
    # Bus handles resolved once an epoch (a per-step gauge set is then one
    # lock, no registry lookup). All interval clocks are obs spans on the
    # shared monotonic source: an NTP step or suspend must not corrupt ETA
    # or throughput math on a days-long run.
    g_loss = obs.BUS.gauge("train_loss")
    g_wps = obs.BUS.gauge("waveforms_per_sec")
    g_gstep = obs.BUS.gauge("global_step")
    # Device->host transfers are confined to every --log-step calls:
    # pulling loss/outputs every step serializes JAX's async dispatch
    # and stalls the chip on host postprocess (the per-step numbers are
    # only diagnostics — TB scalars and the progress line). Per-call
    # losses are kept as device scalars and fetched once per epoch.
    deferred_losses: List[Any] = []
    deferred_aux: List[Any] = []  # a token task's per-step counts
    # The progress line reads a loss back. Reading the newest call's would
    # empty the device's queue every --log-step calls, and whatever then
    # holds the host up is the device's idle time: beside a checkpoint's
    # background write the next dispatches took 0.2-0.5 s each where they
    # take 3 ms (PERF.md, PR 29). So the line is made `monitor.lag` calls
    # late, where the guard reads too: that call has ended by then and two
    # more are queued behind it.
    late_logs: "collections.deque" = collections.deque()

    def _log_call(call, loss, outputs, batch) -> None:
        first = epoch * steps_per_epoch + call * k
        loss_f = float(loss)
        loss_meter.update(loss_f, 1)
        calls_done = min(args.log_step, call) or 1
        wps_meter.update(global_bs * k * calls_done / max(lap(), 1e-9))
        g_loss.set(loss_f)
        g_wps.set(wps_meter.val)
        batch_metrics = {}
        # Train metrics where the feed hands on a batch that can score the
        # step's outputs (a token task has nothing to pick).
        if tasks and batch is not None and batch.metrics_targets:
            results = _postprocess_batch(args, run.spec, outputs, fs)
            batch_metrics = _make_metrics(args, tasks, fs)
            _update_task_metrics(
                metrics_merged, batch_metrics, results,
                batch.metrics_targets, args.batch_size,
            )
        if run.writer is not None:
            run.writer.add_scalar("train-loss/step", loss_f, first)
            for task, m in batch_metrics.items():
                run.writer.add_scalars(
                    f"train.{task}.metrics/step", m.get_all_metrics(), first
                )
        if is_main_process():
            logger.info(f"{args.model_name}_train {progress.get_str(call * k)}")

    # `run.epoch` / `run.batches_done` are what a loader death checkpoints
    # (the feed's on_death reads them, and the latest state, at fire time).
    run.epoch, run.batches_done = epoch, skip
    for call, (step_args, batch) in enumerate(
        feed.epoch(epoch, skip, epoch_rng), start=skip // k
    ):
        loss, outputs = _train_call(run, epoch, call, step_args, g_gstep)
        deferred_losses.append(loss)
        if run.spec.tokens and outputs is not None:
            deferred_aux.append(outputs)
        if call % args.log_step == 0:
            late_logs.append((call, loss, outputs, batch))
        while late_logs and call - late_logs[0][0] >= monitor.lag:
            _log_call(*late_logs.popleft())
    while late_logs:  # the epoch's tail
        _log_call(*late_logs.popleft())

    run.profile.end_epoch(deferred_losses)
    if monitor.flush():  # lagging guard flags from the epoch tail
        run.state = _rollback(run)
    return deferred_losses, deferred_aux, metrics_merged, wps_meter.val


def _end_epoch(run: _Run, epoch: int, epoch_span, trained) -> bool:
    """An epoch's end: drain the device, report the data plane, validate,
    checkpoint the best, close ``epoch_span``. True = stop early."""
    args, steps_per_epoch = run.args, run.steps_per_epoch
    deferred_losses, deferred_aux, metrics_merged, wps = trained
    # The device finishing the calls the host ran ahead of.
    with obs.BUS.span("epoch_drain"):
        epoch_losses, epoch_aux = jax.device_get((deferred_losses, deferred_aux))
        epoch_losses = [float(l) for l in epoch_losses]
    _feed_token_counters(epoch_aux)
    run.train_losses.extend(epoch_losses)
    # Exact epoch mean from every call's loss (the meter only samples
    # every log_step calls, for the progress line). Guard-skipped steps
    # leave non-finite entries in the raw curve; the epoch mean is
    # taken over the finite ones only.
    finite_losses = [l for l in epoch_losses if np.isfinite(l)]
    epoch_train_loss = float(np.mean(finite_losses)) if finite_losses else 0.0
    for m in metrics_merged.values():
        m.synchronize_between_processes()

    # -- data-plane epoch report (docs/FAULT_TOLERANCE.md) ----------------
    # Quarantined samples and guard counters, logged every epoch so a
    # slowly-rotting dataset is visible long before the
    # --max-quarantine-frac abort trips.
    q_report = run.train_loader.dataset.quarantine_report()
    if q_report["quarantined"]:
        logger.warning(
            f"[data-plane] epoch {epoch} quarantine report: "
            f"{json.dumps(q_report)}"
        )
        run.telemetry.emit(
            "quarantine_report", epoch=epoch,
            quarantined=len(q_report["quarantined"]),
            frac=q_report["frac"],
        )
    if io_guard.COUNTERS.any_faults():
        logger.info(f"[data-plane] counters: {io_guard.COUNTERS.snapshot()}")

    # -- validate + checkpoint (ref train.py:402-415) ---------------------
    try:
        with obs.BUS.span("validate"):
            val_loss, val_metrics = validate(
                args, run.state, run.eval_step, run.spec, run.val_loader,
                run.mesh, watchdog=run.watchdog, resident=run.resident_val,
            )
    except io_guard.LoaderDeathError as e:
        _loader_death_exit(run, e, epoch, steps_per_epoch)
    obs.BUS.gauge("val_loss").set(val_loss)
    run.val_losses.append(val_loss)
    if run.writer is not None:
        run.writer.add_scalar("train-loss/epoch", epoch_train_loss, epoch)
        run.writer.add_scalar("val-loss/epoch", val_loss, epoch)
        # Train metrics accumulated at --log-step cadence + psum'd
        # across hosts above (ref train.py:420-442 logs both phases).
        for phase, merged in (("train", metrics_merged), ("val", val_metrics)):
            for task, m in merged.items():
                run.writer.add_scalars(
                    f"{phase}.{task}.metrics/epoch", m.get_all_metrics(), epoch
                )
        # On disk by the epoch's end (the writer has no flushing thread).
        run.writer.flush()

    if val_loss < run.best_loss:
        run.best_loss = val_loss
        run.patience_counter = 0
        # Checkpoint path is deterministic across hosts: step-numbered
        # under the log_dir that cli.main_worker broadcast from process 0
        # (replacing the reference's rank0 ckpt-path broadcast,
        # train.py:481-482). The val metric feeds the manager's
        # keep-best retention, so GC never deletes this step.
        run.best_ckpt_path = _save(run, epoch, steps_per_epoch, val_loss=val_loss)
    else:
        run.patience_counter += 1
        if run.patience_counter > args.patience:
            logger.info(
                f"Early stopping at epoch {epoch} "
                f"(no val improvement in {args.patience} epochs)"
            )
            return True
    if run.preempt.triggered:  # SIGTERM during validation
        _preempt_exit(run, epoch, steps_per_epoch)

    dt = epoch_span.end()
    run.epoch_times.append(dt)
    eta = float(np.mean(run.epoch_times)) * (run.epochs - epoch - 1)
    logger.info(
        f"Epoch {epoch}: train-loss {epoch_train_loss:.4e} "
        f"val-loss {val_loss:.4e} best {run.best_loss:.4e} "
        f"time {strftimedelta(dt)} ETA {strftimedelta(eta)}"
    )
    run.telemetry.emit(
        "epoch_summary",
        epoch=epoch,
        train_loss=round(epoch_train_loss, 6),
        val_loss=round(float(val_loss), 6),
        best_loss=round(float(run.best_loss), 6),
        epoch_time_s=round(dt, 3),
        wps=round(wps, 1),
        data_plane=io_guard.COUNTERS.snapshot(),
    )
    return False


def _train_epochs(run: _Run) -> None:
    """The epochs from the resume position on, until the last or the
    early stop; each a ``train_epoch`` span."""
    for epoch in range(run.start_epoch, run.epochs):
        epoch_span = obs.BUS.begin("train_epoch")
        if _end_epoch(run, epoch, epoch_span, _train_epoch(run, epoch)):
            break


@_dump_flight_on_exception
def train_worker(args: Any) -> str:
    """Full training run; returns the best checkpoint path
    (ref train.py:182-484)."""
    spec = taskspec.get_task_spec(args.model_name)
    seq_shards = int(getattr(args, "seq_shards", 1) or 1)
    mesh = mesh_lib.make_mesh(seq=seq_shards)
    logger.info(
        f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}, "
        f"process {jax.process_index()}/{jax.process_count()}, "
        f"devices: {json.dumps(device_summary())}"
    )
    if seq_shards > 1:
        logger.info(
            f"Sequence parallelism: ring attention over {seq_shards} shards "
            f"(--seq-shards); dropout semantics match dense training"
        )
    data_axis = mesh.shape[mesh_lib.AXIS_DATA]
    if (args.batch_size * jax.process_count()) % data_axis:
        raise ValueError(
            f"global batch size {args.batch_size * jax.process_count()} must "
            f"be divisible by the mesh 'data' axis ({data_axis} devices)"
        )

    # Set-up phases as spans (explicit begin/end: the phases are this
    # function's own sections). With jit_first_call and the first
    # train_epoch they cover the program's part of a run's set-up.
    setup = obs.BUS.begin("setup_loaders")
    train_loader = _build_loader(args, spec, "train")
    val_loader = _build_loader(args, spec, "val")
    setup.end()

    steps_per_epoch = len(train_loader)
    if steps_per_epoch == 0:
        raise ValueError("Train split is empty — check data_dir / split sizes")
    # `--steps > 0` overrides epochs (ref train.py:250-253).
    epochs = args.epochs
    if args.steps > 0:
        epochs = max(1, int(np.ceil(args.steps / steps_per_epoch)))
    total_steps = steps_per_epoch * epochs

    # Gradient accumulation: k loader batches -> ONE optimizer update
    # (step.py make_accum_train_step). state.step counts UPDATES and the
    # LR schedule follows it, so the schedule length shrinks by k.
    gas = max(1, int(getattr(args, "grad_accum_steps", 1) or 1))
    if gas > 1:
        if steps_per_epoch // gas == 0:
            raise ValueError(
                f"--grad-accum-steps {gas} exceeds steps_per_epoch "
                f"{steps_per_epoch}: every epoch would apply ZERO updates"
            )
        total_steps = (steps_per_epoch // gas) * epochs

    setup = obs.BUS.begin("setup_init")
    state, start_epoch, start_batch = _init_state(args, steps_per_epoch, total_steps)
    # Place the state where the jitted step leaves it (replicated over the
    # mesh) BEFORE the first step: a freshly built state carries no mesh in
    # its avals, the step's output does, and the difference would retrace
    # and recompile the whole step on its second call.
    state = mesh_lib.replicate(mesh, state)
    setup.end()

    setup = obs.BUS.begin("setup_store")  # device store build and upload
    feed = feed_lib.resolve_feed(args, train_loader, mesh, steps_per_epoch, gas)
    setup.end()

    setup = obs.BUS.begin("setup_steps")  # the step closures
    train_step, eval_step = _build_steps(args, spec, feed, mesh)
    setup.end()

    # Scalar writer (utils/tb.py frames its own event files), checkpoint
    # manager, watchdog, telemetry plane, signal handlers.
    setup = obs.BUS.begin("setup_writers")
    writer = (
        ScalarWriter(os.path.join(logger.logdir(), "tensorboard"))
        if (args.use_tensorboard and is_main_process())
        else None
    )
    ckpt_mgr = TrainCheckpointManager(
        os.path.join(logger.logdir(), "checkpoints"),
        keep_last=int(getattr(args, "keep_checkpoints", 3) or 3),
    )
    if args.checkpoint:
        # Manual rollback (resume from an older step while newer step
        # dirs exist): saves that re-reach those exact steps are SKIPPED
        # (overwrite refused), so the stale lineage would shadow this
        # one. Make the operator decide.
        resume_gstep = start_epoch * steps_per_epoch + start_batch
        stale = [s for s in ckpt_mgr.all_steps() if s > resume_gstep]
        if stale:
            logger.warning(
                f"Checkpoint dir has steps {stale} AHEAD of the resume "
                f"position ({resume_gstep}); saves re-reaching them will "
                "be skipped, and resume tooling may prefer them. Delete "
                "them if this resume supersedes that lineage."
            )
    faults = faults_lib.FaultInjector.from_env()
    if faults.enabled:
        logger.warning(f"Fault injection ACTIVE: {faults.plan}")

    # Data-plane stall watchdog (--data-watchdog-sec; data/io_guard.py):
    # armed only while the loop is blocked waiting for a host batch
    # (io_guard.watch), so step compute, jit compiles, validation and
    # checkpoint saves never count toward the budget. A trip dumps every
    # thread's stack and hard-exits with the clean-preempt code —
    # tools/supervise.py relaunches from the newest checkpoint instead of
    # the run hanging forever.
    wd_timeout = float(getattr(args, "data_watchdog_sec", 0.0) or 0.0)
    watchdog = (
        io_guard.StallWatchdog(wd_timeout).start() if wd_timeout > 0 else None
    )
    telemetry = _Telemetry(args)
    run = _Run(
        args=args, spec=spec, mesh=mesh,
        train_loader=train_loader, val_loader=val_loader,
        resident_val=pipeline.ResidentEvalPass(),
        steps_per_epoch=steps_per_epoch, epochs=epochs,
        state=state, start_epoch=start_epoch, start_batch=start_batch,
        feed=feed, train_step=train_step, eval_step=eval_step,
        writer=writer, ckpt_mgr=ckpt_mgr,
        save_every=int(getattr(args, "save_interval_steps", 0) or 0),
        faults=faults, watchdog=watchdog, telemetry=telemetry,
        monitor=_BadUpdateMonitor(int(getattr(args, "max_bad_steps", 3) or 0)),
        preempt=_PreemptionHandler(),
        profile=_ProfileWindow(args, feed.updates_per_call, telemetry),
    )
    # The death callback reaches the record through a weak reference: record
    # -> feed -> callback -> record would be a cycle, and the record's state
    # (8 GB on the device in the token cell) would outlive this function
    # until the collector runs; whoever uses the device next (the benchmark's
    # check does) then finds no room (PERF.md, PR 31).
    def _on_loader_death(e, run_ref=weakref.ref(run)) -> None:
        r = run_ref()
        _loader_death_exit(r, e, r.epoch, r.batches_done)

    feed.attach(watchdog=watchdog, faults=faults, on_death=_on_loader_death)
    run.preempt.__enter__()  # uninstalled after the epoch loop (normal path)
    setup.end()

    _train_epochs(run)

    run.preempt.__exit__()
    if watchdog is not None:
        watchdog.stop()
    if io_guard.COUNTERS.any_faults():
        logger.info(
            f"[data-plane] run counters: {io_guard.COUNTERS.snapshot()}"
        )
    if run.monitor.total_skipped:
        logger.warning(
            f"Bad-update guard skipped {run.monitor.total_skipped} non-finite "
            "update(s) this run"
        )
    ckpt_mgr.close()  # barrier on any in-flight async save
    if is_main_process():
        np.save(os.path.join(logger.logdir(), "train_losses.npy"), run.train_losses)
        np.save(os.path.join(logger.logdir(), "val_losses.npy"), run.val_losses)
    if writer is not None:
        writer.close()
    telemetry.emit("train_done", best_loss=round(float(run.best_loss), 6))
    telemetry.close()
    train_loader.close()
    val_loader.close()
    return run.best_ckpt_path


def test_worker(args: Any) -> float:
    """Test run on the held-out split (ref test.py:10-88). Returns loss."""
    spec = taskspec.get_task_spec(args.model_name)
    loss_fn = spec.loss()
    mesh = mesh_lib.make_mesh(seq=int(getattr(args, "seq_shards", 1) or 1))

    test_loader = _build_loader(args, spec, "test")

    in_channels = taskspec.get_num_inchannels(args.model_name)
    model = api.create_model(
        args.model_name, in_channels=in_channels, in_samples=args.in_samples
    )
    variables = api.init_variables(
        model, seed=args.seed, in_samples=args.in_samples, in_channels=in_channels
    )
    tx = build_optimizer(args.optim, args.max_lr)
    state = create_train_state(model, variables, tx)

    if not args.checkpoint:
        raise ValueError("test mode requires --checkpoint")
    # Raw (target-free) restore: test never steps the optimizer, and the
    # test-time tx may have a different state structure (float LR vs
    # schedule) — params + batch_stats are all that matter (the reference
    # likewise tolerates bare state-dicts, _factory.py:101-102).
    restored = load_checkpoint(args.checkpoint)
    state = state.replace(
        params=restored["params"],
        batch_stats=restored.get("batch_stats") or state.batch_stats,
    )
    logger.info(f"Loaded checkpoint: {args.checkpoint}")

    eval_step = jit_eval_step(
        make_eval_step(
            spec, loss_fn, compute_dtype=getattr(args, "dtype", "fp32")
        ),
        mesh,
    )
    # Same stall protection as training (--data-watchdog-sec): a wedged
    # test loader exits with the preempt code instead of hanging. A
    # loader death here simply propagates — there is no training state
    # to checkpoint, and a loud crash beats a silent hang.
    wd_timeout = float(getattr(args, "data_watchdog_sec", 0.0) or 0.0)
    watchdog = (
        io_guard.StallWatchdog(wd_timeout).start() if wd_timeout > 0 else None
    )
    try:
        loss, metrics_merged = validate(
            args,
            state,
            eval_step,
            spec,
            test_loader,
            mesh,
            testing=True,
            save_results=args.save_test_results,
            watchdog=watchdog,
        )
    finally:
        if watchdog is not None:
            watchdog.stop()
    if is_main_process():
        # Structured metrics artifact beside the log/CSV (the reference only
        # logs a formatted string, test.py:83-88); consumed by
        # tools/parity_eval.py and anything scripting over test runs.
        payload = {
            "model": args.model_name,
            "dataset": args.dataset_name,
            "loss": float(loss),
            "metrics": {
                task: m.get_metrics(m.metric_names())
                for task, m in metrics_merged.items()
            },
        }
        out_json = get_safe_path(
            os.path.join(
                logger.logdir(), f"test_metrics_{args.dataset_name}.json"
            )
        )
        with open(out_json, "w") as f:
            json.dump(payload, f, indent=1)
        logger.info(f"Test metrics saved: {out_json}")
    test_loader.close()
    return loss
