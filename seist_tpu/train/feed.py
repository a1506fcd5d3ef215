"""The train loop's input paths, one feed each (docs/DATA_PIPELINE.md).

A feed knows what only its path knows: how many loader batches one call of
the jitted step consumes (``batches_per_call``), how many optimizer updates
that call applies (``updates_per_call``), and how an epoch's step arguments
are produced (``epoch``). :func:`resolve_feed` is the one place that turns
the flags (``--device-aug``, ``--ingest``, ``--steps-per-call``,
``--grad-accum-steps``) into a path; the loop in train/worker.py calls
``train_step(state, *step_args)`` and asks nothing else. An input path costs
one feed here and one row of the worker's step table.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from seist_tpu import obs
from seist_tpu.data import io_guard, pipeline
from seist_tpu.parallel import mesh as mesh_lib
from seist_tpu.utils import faults as faults_lib
from seist_tpu.utils.logger import logger

StepArgs = Tuple[Any, ...]


def mixture_temperature(args: Any, mode: str) -> float:
    """--mixture-temperature applies to TRAIN sampling only: evaluation
    walks every source's split plainly so per-source metrics stay
    comparable across temperature settings."""
    if mode != "train":
        return 0.0
    return float(getattr(args, "mixture_temperature", 0.0) or 0.0)


class Feed:
    """One input path. ``epoch`` yields ``(step_args, batch_or_None)``, one
    item a call of the step: the loop calls ``train_step(state,
    *step_args)``; ``batch`` is the host batch where the path has one whose
    ``metrics_targets`` can score the step's outputs."""

    kind = ""
    batches_per_call = 1  # loader batches one call consumes
    updates_per_call = 1  # optimizer updates one call applies
    # The run's data-plane guards: a feed is resolved before they exist, and
    # the worker attaches them before the first epoch.
    watchdog: Optional[io_guard.StallWatchdog] = None
    on_death: Optional[Callable[[io_guard.LoaderDeathError], None]] = None
    faults = faults_lib.FaultInjector()  # inert

    def attach(self, *, watchdog, faults, on_death) -> None:
        self.watchdog, self.faults, self.on_death = watchdog, faults, on_death

    def epoch(
        self, epoch: int, skip: int, epoch_rng: jax.Array
    ) -> Iterator[Tuple[StepArgs, Optional[pipeline.Batch]]]:
        """The calls of one epoch from loader batch ``skip`` on (a multiple
        of ``batches_per_call``); a trailing part-group is dropped."""
        raise NotImplementedError

    def _waited(self, batches, on_death=None):
        """Host batches under the stall watchdog, every wait for one a
        ``host_wait`` span."""
        return obs.timed_iter(
            io_guard.watch(batches, self.watchdog, on_death=on_death), "host_wait"
        )


class CachedFeed(Feed):
    """``--device-aug cached``: whole raw epochs live in HBM and one call
    scans ``steps_per_call`` updates over them; the only per-call host to
    device traffic is the (k, B) int32 index array."""

    kind = "cached"

    def __init__(self, cache, processor, mesh, order, steps_per_call):
        self.cache, self.processor = cache, processor  # the step's, of the cache
        self.mesh, self.order = mesh, order
        self.batches_per_call = self.updates_per_call = steps_per_call

    def epoch(self, epoch, skip, epoch_rng):
        for idx_k in self.cache.epoch_index_chunks(
            epoch, steps_per_call=self.batches_per_call, start_batch=skip,
            **self.order,
        ):
            idx_dev = mesh_lib.shard_stacked_batch(self.mesh, idx_k)
            yield (self.cache.arrays, idx_dev, jnp.int32(epoch), epoch_rng), None


class StepFeed(Feed):
    """``--device-aug step``: raw rows cross the host per step (a
    fancy-index gather: no per-sample augmentation, label synthesis or
    stacking); the jitted step does the rest."""

    kind = "step"

    def __init__(self, store, processor, mesh, order):
        self.store, self.processor = store, processor  # the step's, of a row batch
        self.mesh, self.order = mesh, order

    def epoch(self, epoch, skip, epoch_rng):
        raw = pipeline.iter_raw_batches(
            self.store, epoch, start_batch=skip, **self.order
        )
        for rows, idx, aug in self._waited(
            pipeline.prefetch_raw_to_device(raw, self.mesh)
        ):
            yield (rows, idx, aug, jnp.int32(epoch), epoch_rng), None


class PackedFeed(Feed):
    """One call consumes k host batches: k sequential updates
    (``--steps-per-call``) or, with ``accumulate``, one update over k
    micro-batch gradients (``--grad-accum-steps``). The call's loss is
    already the mean over its micro-batches; the scan returns no per-step
    outputs, so no batch is handed on."""

    kind = "packed"

    def __init__(self, loader, mesh, steps_per_epoch, k, accumulate):
        self.loader, self.mesh, self.steps_per_epoch = loader, mesh, steps_per_epoch
        self.batches_per_call, self.accumulate = k, accumulate
        self.updates_per_call = 1 if accumulate else k

    def epoch(self, epoch, skip, epoch_rng):
        k = self.batches_per_call
        first = epoch * self.steps_per_epoch + skip
        for xk, yk in self._waited(
            pipeline.prefetch_packed_to_device(iter(self.loader), self.mesh, k),
            self.on_death,
        ):
            xk = self.faults.corrupt_inputs(first, xk, n_steps=k)
            yield (xk, yk, epoch_rng), None
            first += k


class PlainFeed(Feed):
    """The host path: the Loader's augmented, labelled batches, one a
    step, each handed on for the progress line's train metrics."""

    kind = "plain"

    def __init__(self, loader, mesh, steps_per_epoch):
        self.loader, self.mesh, self.steps_per_epoch = loader, mesh, steps_per_epoch

    def epoch(self, epoch, skip, epoch_rng):
        batches = self._waited(
            pipeline.prefetch_to_device(iter(self.loader), self.mesh), self.on_death
        )
        for gstep, batch in enumerate(batches, epoch * self.steps_per_epoch + skip):
            inputs = self.faults.corrupt_inputs(gstep, batch.inputs)
            yield (inputs, batch.loss_targets, epoch_rng), batch


def _require_a_call(spc: int, steps_per_epoch: int) -> None:
    if steps_per_epoch // spc == 0:
        raise ValueError(
            f"--steps-per-call {spc} exceeds steps_per_epoch "
            f"{steps_per_epoch}: every epoch would train ZERO steps "
            f"(trailing part-groups are dropped)"
        )


def _warn_dropped(flag: str, k: int, steps_per_epoch: int) -> None:
    if steps_per_epoch % k:
        logger.warning(
            f"{flag}={k} drops {steps_per_epoch % k} "
            f"trailing batch(es) per epoch ({steps_per_epoch} steps)"
        )


def resolve_feed(
    args: Any, train_loader: pipeline.Loader, mesh, steps_per_epoch: int, gas: int
) -> Feed:
    """The run's input path from its flags (docs/DATA_PIPELINE.md), with the
    device store or cache it needs built and uploaded.

    ``--device-aug step``: raw rows cross the host per step, augmentation +
    label synthesis run inside the jitted step. ``cached``: whole raw epochs
    live in HBM and a scan executor consumes (k, B) index arrays — zero
    per-step host stacking. Unsupported configs fall back to the host path;
    an over-budget 'cached' falls back to 'step' (both logged). ``gas`` is
    --grad-accum-steps, which the worker has already held against
    ``steps_per_epoch`` for its schedule."""
    # steps_per_call <= 0 means "auto" (CLI default): 1 on the host path,
    # raised high under --device-aug cached. An EXPLICIT 1 is honored there
    # (per-step save/preempt granularity costs throughput but is a choice).
    spc_raw = int(getattr(args, "steps_per_call", 0) or 0)
    spc = max(1, spc_raw)
    if spc > 1 and gas > 1:
        raise ValueError(
            "--steps-per-call and --grad-accum-steps are mutually "
            "exclusive (both scan stacked micro-batches, with different "
            "update semantics)"
        )
    device_req = str(getattr(args, "device_aug", "off") or "off")
    device_mode = "off"
    store = None
    sds_train = train_loader.dataset
    # --ingest: how raw rows reach the device on the device-aug step path.
    # 'auto' takes the direct shard->staging->device fast path whenever
    # the dataset is packed (data/ingest.py), 'host' forces the resident
    # RawStore upload, 'direct' demands the fast path and errors when the
    # prerequisites are missing instead of degrading silently.
    ingest_req = str(getattr(args, "ingest", "auto") or "auto")
    if ingest_req not in ("auto", "direct", "host"):
        raise ValueError(
            f"--ingest must be auto|direct|host, got '{ingest_req}'"
        )
    if ingest_req == "direct" and device_req == "off":
        raise ValueError(
            "--ingest direct feeds the device-aug step path; run with "
            "--device-aug step (docs/DATA.md)"
        )
    mixture_t = mixture_temperature(args, "train")
    order: Dict[str, Any] = dict(
        seed=args.seed,
        shuffle=args.shuffle,
        batch_size=args.batch_size,
        num_shards=jax.process_count(),
        shard_index=jax.process_index(),
        source_ids=sds_train.source_ids() if mixture_t > 0 else None,
        mixture_temperature=mixture_t,
    )
    if device_req != "off":
        from seist_tpu.data import device_aug as da

        if gas > 1:
            raise ValueError(
                "--device-aug is incompatible with --grad-accum-steps "
                "(accumulation scans stacked host batches)"
            )
        reasons = da.unsupported_reasons(
            sds_train.preprocessor, sds_train.input_names,
            sds_train.label_names,
        )
        budget = da.hbm_budget_bytes(
            float(getattr(args, "device_aug_hbm_gb", 0.0) or 0.0)
        )
        # The cache shards its sample axis over the mesh 'data' axis, so
        # the budget comparison is PER-DEVICE bytes vs per-device HBM —
        # comparing the raw total would downgrade a 40 GiB dataset on an
        # 8-chip mesh (5 GiB/chip) that actually fits.
        est = 0
        if not reasons:
            try:
                est = pipeline.RawStore.estimate_bytes(
                    sds_train
                ) // max(mesh.shape[mesh_lib.AXIS_DATA], 1)
            except ValueError as e:
                # The size probe reads raw sample 0 through the guarded
                # path; a permanently-corrupt sample refuses the device
                # store — same fallback as a build-time refusal: host
                # path, whose quarantine machinery handles it.
                reasons = [str(e)]
        device_mode, why = da.select_device_aug_mode(
            device_req, est, budget, reasons
        )
        if device_mode != device_req:
            logger.warning(f"--device-aug {device_req} -> {device_mode}: {why}")
        if ingest_req == "direct" and device_mode != "step":
            # The ONE resolved-mode guard for --ingest direct (the
            # pre-flight check above already rejected --device-aug off;
            # a non-packed dataset is rejected by the build below).
            raise ValueError(
                "--ingest direct requires the device-aug step path; the "
                f"run resolved --device-aug to '{device_mode}' ({why})"
            )
        if device_mode != "off":
            from seist_tpu.data import ingest as ingest_lib

            # Direct shard->device ingest: on a packed dataset the step
            # path streams staging batches straight off the shard memmaps
            # — no Event decode, no resident waveform upload. The cached
            # mode keeps the RawStore (its whole point is HBM residency).
            direct = device_mode == "step" and ingest_req != "host" and (
                ingest_req == "direct"
                or ingest_lib.packed_dataset_of(sds_train) is not None
            )
            if direct:
                try:
                    store = ingest_lib.PackedRawStore.build(
                        sds_train, batch_size=args.batch_size
                    )
                    logger.info(ingest_lib.describe(store))
                except ValueError as e:
                    if ingest_req == "direct":
                        raise
                    logger.warning(
                        f"packed direct ingest unavailable ({e}); "
                        "uploading a resident RawStore instead"
                    )
                    direct = False
            if not direct:
                try:
                    store = pipeline.RawStore.build(sds_train)
                except ValueError as e:
                    logger.warning(f"--device-aug {device_mode} -> off: {e}")
                    device_mode = "off"
        if device_mode == "step" and spc > 1:
            # Explicit 'step' + packing is a config error; but a 'cached'
            # request that FELL BACK to 'step' must not crash on its
            # now-meaningless packing flag.
            if device_req == "step":
                raise ValueError(
                    "--steps-per-call > 1 requires --device-aug cached "
                    "(the step mode feeds one raw batch per dispatch)"
                )
            logger.warning(
                f"--steps-per-call {spc} ignored on the device-aug step "
                "fallback path"
            )
            spc = 1
        if (
            device_mode != "off"
            and faults_lib.FaultInjector.from_env().plan.nan_step >= 0
        ):
            raise ValueError(
                "SEIST_FAULT_NAN_STEP corrupts host-fed input batches, "
                "which the device-aug paths never materialize; use "
                "--device-aug off for NaN-injection runs (process-level "
                "faults — SIGTERM/kill/slow — work on every path)"
            )

    if device_mode != "off":
        proc_args = (
            da.AugConfig.from_preprocessor(
                sds_train.preprocessor,
                seed=args.seed,
                raw_len=store.raw_len,
                phase_slots=store.phase_slots,
            ),
            sds_train.input_names,
            sds_train.label_names,
        )
    if device_mode == "cached":
        # steps_per_call defaults HIGH here: with epochs resident there is
        # no host work to overlap, so the only per-step cost left is the
        # dispatch — amortize it.
        if spc_raw <= 0:
            spc = max(1, min(32, steps_per_epoch))
        _require_a_call(spc, steps_per_epoch)
        cache = pipeline.DeviceEpochCache(store, mesh)
        logger.info(
            f"device-aug cached: {len(store)} epoch samples resident "
            f"({cache.nbytes / 2**20:.1f} MiB HBM), "
            f"steps_per_call={spc}"
        )
        _warn_dropped("steps_per_call", spc, steps_per_epoch)
        processor = da.make_cache_processor(
            *proc_args, n_raw=store.n_raw, augmentation=store.augmentation
        )
        return CachedFeed(cache, processor, mesh, order, spc)
    if device_mode == "step":
        logger.info(
            "device-aug step: augmentation + labels inside the jitted "
            "step; host feeds raw rows only"
        )
        return StepFeed(store, da.make_row_processor(*proc_args), mesh, order)
    if gas > 1:
        # One update from gas micro-batch gradients, scanned in one jitted
        # program; stacked-batch layout shares jit_multi_step's sharding.
        _warn_dropped("grad_accum_steps", gas, steps_per_epoch)
        logger.info(
            f"grad_accum_steps={gas}: effective batch "
            f"{args.batch_size * gas * jax.process_count()}, "
            f"{steps_per_epoch // gas} updates/epoch"
        )
        return PackedFeed(train_loader, mesh, steps_per_epoch, gas, True)
    if spc > 1:
        # k updates scanned inside one jitted program (dispatch
        # amortization; step.py make_multi_train_step).
        _require_a_call(spc, steps_per_epoch)
        _warn_dropped("steps_per_call", spc, steps_per_epoch)
        logger.info(f"steps_per_call={spc}: scanned multi-step training")
        return PackedFeed(train_loader, mesh, steps_per_epoch, spc, False)
    return PlainFeed(train_loader, mesh, steps_per_epoch)
