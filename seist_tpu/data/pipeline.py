"""Input pipeline: dataset + preprocessor -> sharded device batches.

TPU-native redesign of the reference's torch ``Dataset``/``DataLoader``/
``DistributedSampler`` stack (training/preprocess.py:824-953,
train.py:221-247):

* :class:`SeismicDataset` — composes an L2 dataset reader with the
  ``DataPreprocessor``; same io contract as the reference adapter
  (inputs, loss_targets, metrics_targets, meta json) including the
  2x-epoch augmentation rule — raw copy for ``idx < size``, augmented for
  ``idx >= size`` (ref preprocess.py:918-937). Every sample's RNG is
  ``default_rng((seed, epoch, idx))`` — reproducible regardless of worker
  scheduling (the reference relies on global numpy state per worker).
* :class:`Loader` — per-epoch seeded shuffle, per-host contiguous sharding
  (the ``DistributedSampler`` equivalent: each host reads only its slice),
  thread-pool batch assembly (h5py/numpy release the GIL for the heavy
  parts), fixed batch shapes (``drop_last`` on train; tail batch padded and
  masked on eval so jit never retraces).
* :func:`prefetch_to_device` — double-buffered ``jax.device_put`` with a
  ``NamedSharding`` so host->HBM copy of batch N+1 overlaps the step on N
  (replaces torch ``pin_memory`` + H2D copies at train.py:77-84).
"""

from __future__ import annotations

import collections
import json
import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from seist_tpu import taskspec
from seist_tpu.data import io_guard
from seist_tpu.data.preprocess import DataPreprocessor, pad_phases
from seist_tpu.registry import DATASETS
from seist_tpu.utils import faults as faults_lib
from seist_tpu.utils.logger import logger

Batch = collections.namedtuple(
    "Batch", ["inputs", "loss_targets", "metrics_targets", "meta", "mask"]
)


class SeismicDataset:
    """Dataset reader + preprocessing -> one training example
    (ref preprocess.py:824-953)."""

    def __init__(
        self,
        dataset_name: str,
        mode: str,
        *,
        seed: int,
        data_dir: str = "",
        input_names: Sequence = (),
        label_names: Sequence = (),
        task_names: Sequence[str] = (),
        in_samples: int = 8192,
        augmentation: bool = False,
        shuffle: bool = True,
        data_split: bool = True,
        train_size: float = 0.8,
        val_size: float = 0.1,
        max_event_num: int = 1,
        max_quarantine_frac: float = 0.05,
        dataset_kwargs: Optional[dict] = None,
        **preprocessor_kwargs,
    ) -> None:
        self._seed = int(seed)
        self._mode = mode.lower()
        self._input_names = list(input_names)
        self._label_names = list(label_names)
        self._task_names = list(task_names)
        self._max_event_num = max_event_num
        self._epoch = 0

        # val/test never augment (ref preprocess.py:858-860).
        self._augmentation = bool(augmentation) and self._mode == "train"
        if self._augmentation != bool(augmentation):
            logger.warning(f"[{self._mode}] Augmentation -> {self._augmentation}")

        self._dataset = DATASETS.create(
            dataset_name,
            seed=self._seed,
            mode=self._mode,
            data_dir=data_dir,
            shuffle=shuffle,
            data_split=data_split,
            train_size=train_size,
            val_size=val_size,
            **(dataset_kwargs or {}),
        )
        logger.info(repr(self._dataset))
        self._dataset_size = len(self._dataset)
        # Data-plane self-healing (data/io_guard.py): per-dataset
        # quarantine registry + the env-driven chaos injector, both
        # captured at construction so tests can set SEIST_FAULT_IO_* /
        # --max-quarantine-frac deterministically.
        self._quarantine = io_guard.Quarantine(
            self._dataset_size, max_frac=float(max_quarantine_frac)
        )
        self._io_faults = faults_lib.IoFaultInjector.from_env()
        # Immutable after construction: lets the clean read path skip the
        # injector entirely (guard fast path in _fetch_event).
        self._io_faults_enabled = self._io_faults.enabled
        if self._augmentation:
            logger.warning(
                f"Data augmentation: Dataset size -> {self._dataset_size * 2}"
            )

        label_width_sec = preprocessor_kwargs.pop("label_width", 0.5)
        self._preprocessor = DataPreprocessor(
            data_channels=self._dataset.channels(),
            sampling_rate=self._dataset.sampling_rate(),
            in_samples=in_samples,
            max_event_num=max_event_num,
            soft_label_width=int(label_width_sec * self._dataset.sampling_rate()),
            **preprocessor_kwargs,
        )

    @property
    def preprocessor(self) -> DataPreprocessor:
        return self._preprocessor

    @property
    def augmentation(self) -> bool:
        return self._augmentation

    @property
    def raw_size(self) -> int:
        """Number of RAW events (len() doubles under augmentation)."""
        return self._dataset_size

    @property
    def input_names(self) -> list:
        return list(self._input_names)

    @property
    def label_names(self) -> list:
        return list(self._label_names)

    @property
    def quarantine(self) -> io_guard.Quarantine:
        return self._quarantine

    @property
    def io_faults(self) -> faults_lib.IoFaultInjector:
        return self._io_faults

    def quarantine_report(self) -> Dict[str, Any]:
        """Epoch-end quarantine report (logged by train/worker.py)."""
        return self._quarantine.report()

    def raw_event(self, idx: int):
        """One UNprocessed event + meta — the device-aug upload path reads
        raw traces here and runs augmentation/labels on device."""
        return self._dataset[idx % self._dataset_size]

    def source_ids(self) -> Optional[np.ndarray]:
        """Per-LOGICAL-index source ids when the underlying dataset is a
        multi-source (mixture) pack, else None. Under the 2x augmentation
        rule the array is doubled — logical index ``n + i`` is sample
        ``i``'s augmented replica, same source."""
        fn = getattr(self._dataset, "source_ids", None)
        sids = fn() if callable(fn) else None
        if sids is None:
            return None
        sids = np.asarray(sids)
        return np.concatenate([sids, sids]) if self._augmentation else sids

    def _fetch_event(self, raw_idx: int, *, idx: int) -> Tuple[Event, dict]:
        """Guarded sample read (data/io_guard.py): transient faults are
        retried (with injected flakiness riding the same loop); a sample
        that is permanently corrupt — failed ingest validation or an
        exhausted retry budget — is quarantined and deterministically
        replaced by the first cleanly-reading candidate of the
        ``(seed, epoch, idx)``-keyed fallback sequence, so batch shapes
        and the global sample order stay fixed and resume-stable.

        Fast path (no quarantined samples, no injected faults): one
        direct read + ingest validation — a try frame, a counter bump and
        one ``np.isfinite`` pass per sample (benched ~1% of loader stage
        time; the BENCH ``data_plane`` section re-measures it every run).
        Any failure falls through to the full retry/quarantine ladder."""
        if not (self._quarantine.active or self._io_faults_enabled):
            try:
                event, meta = self._dataset[raw_idx]
                io_guard.validate_event(event)
                io_guard.COUNTERS.inc("reads")
                return event, meta
            except (OSError, io_guard.CorruptSampleError):
                pass  # enter the retrying/quarantining ladder below
        return self._fetch_event_slow(raw_idx, idx=idx)

    def _fetch_event_slow(
        self, raw_idx: int, *, idx: int
    ) -> Tuple[Event, dict]:
        for cand in self._quarantine.candidates(
            raw_idx, seed=self._seed, epoch=self._epoch, idx=idx
        ):
            try:
                event, meta = io_guard.guarded_event_read(
                    lambda c=cand: self._dataset[c],
                    key=cand,
                    desc=f"{self._dataset.name()}[{cand}]",
                    injector=self._io_faults,
                )
            except io_guard.CorruptSampleError as e:
                # Covers RetriesExhaustedError too; add() raises
                # QuarantineOverflowError past --max-quarantine-frac.
                self._quarantine.add(cand, repr(e))
                continue
            if cand != raw_idx:
                io_guard.COUNTERS.inc("fallback_reads")
            return event, meta
        raise io_guard.CorruptSampleError(
            f"no clean fallback found for sample {raw_idx} "
            f"(quarantined: {len(self._quarantine)}/{self._dataset_size})"
        )

    def sampling_rate(self) -> int:
        return self._dataset.sampling_rate()

    def data_channels(self) -> list:
        return self._dataset.channels()

    def name(self) -> str:
        return f"{self._dataset.name()}_{self._mode}"

    def set_epoch(self, epoch: int) -> None:
        """Advance the per-sample RNG stream (the reference reshuffles via
        ``DistributedSampler.set_epoch``, train.py:381-382)."""
        self._epoch = int(epoch)

    def __len__(self) -> int:
        # Augmentation doubles the epoch (ref preprocess.py:918-922).
        return 2 * self._dataset_size if self._augmentation else self._dataset_size

    def __getitem__(self, idx: int) -> Tuple[Any, Any, Dict[str, np.ndarray], str]:
        raw_idx = idx % self._dataset_size
        if io_guard.enabled():
            event, meta_data = self._fetch_event(raw_idx, idx=int(idx))
        else:
            event, meta_data = self._dataset[raw_idx]
        rng = np.random.default_rng(
            np.random.SeedSequence([self._seed, self._epoch, int(idx)])
        )
        event = self._preprocessor.process(
            event=event,
            augmentation=(self._augmentation and idx >= self._dataset_size),
            rng=rng,
        )
        inputs = self._preprocessor.get_inputs(event, self._input_names)
        loss_targets = self._preprocessor.get_targets_for_loss(
            event, self._label_names
        )
        metrics_targets = self._preprocessor.get_targets_for_metrics(
            event, max_event_num=self._max_event_num, task_names=self._task_names
        )
        meta_json = json.dumps({k: str(v) for k, v in dict(meta_data).items()})
        return inputs, loss_targets, metrics_targets, meta_json


def from_task_spec(
    spec: taskspec.TaskSpec,
    dataset_name: str,
    mode: str,
    **kwargs,
) -> SeismicDataset:
    """Build a :class:`SeismicDataset` wired to a model's task spec
    (inputs/labels/eval lists; ref train.py:199-217)."""
    return SeismicDataset(
        dataset_name,
        mode,
        input_names=[
            list(g) if isinstance(g, (tuple, list)) else g for g in spec.inputs
        ],
        label_names=[
            list(g) if isinstance(g, (tuple, list)) else g for g in spec.labels
        ],
        task_names=list(spec.eval),
        **kwargs,
    )


def _shard_order(
    order: np.ndarray, num_shards: int, shard_index: int
) -> np.ndarray:
    """Host-shard a global epoch order: head-wrapped to equalize shard
    sizes (torch ``DistributedSampler``'s pad rule; unequal step counts
    would deadlock the collective-bearing jitted steps), then interleaved
    ``rank::world`` — the union over hosts covers the full order and the
    per-position shards are disjoint (test-pinned)."""
    if num_shards <= 1:
        return order
    n = len(order)
    target = -(-n // num_shards) * num_shards
    if target > n:
        order = np.concatenate([order, order[: target - n]])
    return order[shard_index::num_shards]


def epoch_indices(
    n: int,
    *,
    seed: int,
    epoch: int,
    shuffle: bool,
    num_shards: int = 1,
    shard_index: int = 0,
) -> np.ndarray:
    """This host's epoch-``epoch`` sample order — THE shuffle contract
    shared by the host :class:`Loader` and the device-aug executors, so
    both paths consume the identical global sample sequence: seeded
    permutation (a pure function of (seed, epoch) — mid-epoch resume
    depends on this), host-sharded by :func:`_shard_order`. Together with
    a batch offset this is the full resume address: ``(seed, epoch,
    shard_index, start_batch)`` determines the remaining batch sequence
    exactly, with no replay and no skips."""
    if shuffle:
        rng = np.random.default_rng(np.random.SeedSequence([seed, epoch]))
        order = rng.permutation(n)
    else:
        order = np.arange(n)
    return _shard_order(order, num_shards, shard_index)


# Keys the mixture-draw PRNG stream apart from the shuffle/fallback ones.
_MIXTURE_SALT = 0x313C7


def mixture_epoch_indices(
    source_ids: np.ndarray,
    *,
    seed: int,
    epoch: int,
    temperature: float,
    num_shards: int = 1,
    shard_index: int = 0,
) -> np.ndarray:
    """Temperature-weighted mixture epoch order over multi-source packed
    data (seqio-style mixing, arXiv:2203.17189), under the SAME resume
    contract as :func:`epoch_indices`: a pure function of
    ``(seed, epoch)``, epoch length fixed at ``len(source_ids)`` (so
    steps_per_epoch and ``(epoch, start_batch)`` addressing are
    unchanged), host-sharded by :func:`_shard_order`.

    Each epoch slot draws its source with probability
    ``p_s ∝ (n_s / n)^(1/T)`` (T=1: proportional — every sample appears
    ~once; T→∞: uniform over sources) and consumes the next sample of
    that source's stream: a seeded permutation of the source's members,
    re-permuted on every wrap — small sources are resampled evenly,
    large ones subsampled without replacement."""
    source_ids = np.asarray(source_ids)
    n = int(source_ids.shape[0])
    if temperature <= 0:
        raise ValueError(f"mixture temperature must be > 0, got {temperature}")
    counts = np.bincount(source_ids)
    if counts.size < 2:
        raise ValueError("mixture sampling needs >= 2 sources")
    p = (counts / n) ** (1.0 / float(temperature))
    p = np.where(counts > 0, p, 0.0)
    p = p / p.sum()
    rng = np.random.default_rng(
        np.random.SeedSequence([int(seed), int(epoch), _MIXTURE_SALT])
    )
    choice = rng.choice(counts.size, size=n, p=p)
    order = np.empty(n, np.int64)
    for s in range(counts.size):
        slots = np.flatnonzero(choice == s)
        if slots.size == 0:
            continue
        members = np.flatnonzero(source_ids == s)
        wraps = -(-slots.size // members.size)
        stream = np.concatenate(
            [
                np.random.default_rng(
                    np.random.SeedSequence(
                        [int(seed), int(epoch), _MIXTURE_SALT, s, w]
                    )
                ).permutation(members)
                for w in range(wraps)
            ]
        )
        order[slots] = stream[: slots.size]
    return _shard_order(order, num_shards, shard_index)


def _epoch_order(
    n: int,
    *,
    seed: int,
    epoch: int,
    shuffle: bool,
    num_shards: int = 1,
    shard_index: int = 0,
    source_ids: Optional[np.ndarray] = None,
    mixture_temperature: float = 0.0,
) -> np.ndarray:
    """The ONE epoch-order dispatcher every consumer goes through (host
    Loader, raw-row step feed, cached device executor): plain seeded
    permutation, or the temperature-weighted mixture order when a
    multi-source pack + temperature are configured. Both are pure
    functions of (seed, epoch) — the O(1) mid-epoch resume contract."""
    if mixture_temperature and source_ids is not None:
        if len(source_ids) != n:
            raise ValueError(
                f"source_ids has {len(source_ids)} entries for {n} samples"
            )
        return mixture_epoch_indices(
            source_ids,
            seed=seed,
            epoch=epoch,
            temperature=mixture_temperature,
            num_shards=num_shards,
            shard_index=shard_index,
        )
    return epoch_indices(
        n,
        seed=seed,
        epoch=epoch,
        shuffle=shuffle,
        num_shards=num_shards,
        shard_index=shard_index,
    )


def _stack(samples: List[Any]) -> Any:
    """Stack a list of per-sample structures (arrays / tuples of arrays)."""
    first = samples[0]
    if isinstance(first, tuple):
        return tuple(
            np.stack([s[i] for s in samples]) for i in range(len(first))
        )
    return np.stack(samples)


class Loader:
    """Host-side batch loader with per-host sharding and fixed shapes.

    Each epoch: seeded global permutation -> this host's interleaved slice ->
    fixed-size batches assembled by a worker pool. Train drops the global
    tail (every host sees the same number of steps — the collective-sync
    equivalent of ``drop_last``); eval pads the final batch and sets
    ``Batch.mask`` zeros on padding rows.

    Workers: ``num_workers`` threads by default (the hot per-sample ops —
    h5py reads, numpy array math, native wavekit kernels — release the
    GIL, so threads scale on multi-core hosts). ``worker_processes > 0``
    switches to a process pool instead, sidestepping the GIL entirely for
    Python-bound augmentation mixes at the cost of per-sample IPC; batches
    are bit-identical either way (per-sample RNG is derived from
    (seed, epoch, idx), never worker identity).
    """

    def __init__(
        self,
        dataset: SeismicDataset,
        batch_size: int,
        *,
        shuffle: bool = False,
        drop_last: bool = False,
        num_workers: int = 8,
        worker_processes: int = 0,
        seed: int = 0,
        num_shards: int = 1,
        shard_index: int = 0,
        mixture_temperature: float = 0.0,
    ) -> None:
        if batch_size <= 0:
            raise ValueError(f"batch_size must be positive, got {batch_size}")
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.num_workers = max(1, num_workers)
        self.worker_processes = max(0, worker_processes)
        self.seed = seed
        self.num_shards = num_shards
        self.shard_index = shard_index
        # Temperature-weighted mixture sampling (multi-source packs only;
        # see mixture_epoch_indices). Resolved once: the per-sample source
        # ids are static for the dataset's lifetime.
        self.mixture_temperature = float(mixture_temperature or 0.0)
        self._source_ids = None
        if self.mixture_temperature > 0:
            fn = getattr(dataset, "source_ids", None)
            self._source_ids = fn() if callable(fn) else None
            if self._source_ids is None:
                raise ValueError(
                    "mixture_temperature set but the dataset exposes no "
                    "mixture sources (pack with tools/pack_dataset.py "
                    "--mixture)"
                )
        self.epoch = 0
        self._start_batch = 0
        self._pool: Optional[ThreadPoolExecutor] = None
        self._proc_pool = None
        if self.worker_processes and io_guard.enabled():
            # Each process-pool worker holds its own pickled dataset copy:
            # quarantine state and io_guard counters accumulate PER WORKER
            # (replacement content stays deterministic — the fallback rule
            # depends only on the data), so the parent's epoch report and
            # counter logs understate faults and --max-quarantine-frac is
            # enforced per worker rather than globally. Thread workers
            # (the default) share one registry and report exactly.
            logger.warning(
                "worker_processes > 0: data-plane quarantine/counters are "
                "tracked per worker process; parent-side epoch reports "
                "undercount and the --max-quarantine-frac abort applies "
                "per worker (docs/FAULT_TOLERANCE.md)"
            )
        # One injector per pipeline: reuse the dataset's (so a
        # programmatic fault plan reaches the stall hook too); fall back
        # to env parsing only for bare-dataset callers.
        self._io_faults = (
            getattr(dataset, "io_faults", None)
            or faults_lib.IoFaultInjector.from_env()
        )

    def set_epoch(self, epoch: int) -> None:
        self.epoch = int(epoch)
        self.dataset.set_epoch(epoch)

    def set_start_batch(self, start_batch: int) -> None:
        """Begin the NEXT ``__iter__`` at batch ``start_batch`` instead of
        0 (one-shot; subsequent epochs start at 0 again). This is the
        mid-epoch resume hook: the shuffle order is a pure function of
        (seed, epoch), so a restored (epoch, batch_offset) position
        continues the exact same sample sequence an uninterrupted run
        would have seen — no replayed and no skipped data."""
        if start_batch < 0:
            raise ValueError(f"start_batch must be >= 0, got {start_batch}")
        self._start_batch = int(start_batch)

    def close(self) -> None:
        """Release the worker pool(s). Safe to call multiple times; the
        loader remains usable (a new pool spins up on the next __iter__)."""
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)
            self._pool = None
        if self._proc_pool is not None:
            self._proc_pool.shutdown(wait=False, cancel_futures=True)
            self._proc_pool = None

    def __del__(self):  # best-effort: Loaders built in loops must not leak
        try:
            self.close()
        # __del__ runs during interpreter teardown when pool/module state
        # may already be gone; raising here would only print an unraisable
        # warning, so swallow everything.
        except Exception:
            pass

    def _indices(self) -> np.ndarray:
        return _epoch_order(
            len(self.dataset),
            seed=self.seed,
            epoch=self.epoch,
            shuffle=self.shuffle,
            num_shards=self.num_shards,
            shard_index=self.shard_index,
            source_ids=self._source_ids,
            mixture_temperature=self.mixture_temperature,
        )

    def __len__(self) -> int:
        n = len(self._indices())
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size

    def _fetch(self, chunk: np.ndarray) -> List[Any]:
        """Fetch one batch's samples via the configured worker pool.

        Sample-level faults never reach here (the guarded read in
        SeismicDataset retries transients and quarantines corruption);
        anything a worker still raises is a loader-thread death — a bug
        or an environment failure the retry ladder cannot absorb — and is
        wrapped as LoaderDeathError so the train worker can checkpoint
        and preempt-exit instead of crashing opaquely. The deliberate
        aborts (QuarantineOverflowError, CorruptSampleError's
        no-clean-fallback) pass through untouched: those must kill the
        run loudly, not trigger a relaunch loop.
        """
        try:
            return self._fetch_inner(chunk)
        except (io_guard.QuarantineOverflowError, io_guard.CorruptSampleError):
            raise
        # Not swallowed — re-raised as the typed loader-death signal the
        # train worker turns into a checkpoint + clean-preempt exit.
        except Exception as e:
            io_guard.COUNTERS.inc("loader_deaths")
            raise io_guard.LoaderDeathError(
                f"loader worker died fetching batch chunk "
                f"[{int(chunk[0])}..{int(chunk[-1])}]: {e!r}"
            ) from e

    def _fetch_inner(self, chunk: np.ndarray) -> List[Any]:
        if self.worker_processes:
            if self._proc_pool is None:
                import multiprocessing
                from concurrent.futures import ProcessPoolExecutor

                # forkserver/spawn, never fork: the pool is created lazily
                # from the prefetch producer THREAD of a JAX-initialized
                # parent — forking there can inherit locks held mid-acquire
                # by other threads (h5py/logging/libtpu) and hang the
                # children. The dataset is pickled ONCE per worker via the
                # initializer — never per sample.
                try:
                    ctx = multiprocessing.get_context("forkserver")
                except ValueError:  # platform without forkserver
                    ctx = multiprocessing.get_context("spawn")
                self._proc_pool = ProcessPoolExecutor(
                    max_workers=self.worker_processes,
                    mp_context=ctx,
                    initializer=_proc_worker_init,
                    initargs=(self.dataset,),
                )
            epoch = self.epoch
            return list(
                self._proc_pool.map(
                    _proc_worker_getitem,
                    [(epoch, int(i)) for i in chunk],
                    # Batch the IPC: one message per worker-chunk, not per
                    # sample (ordering is preserved by map).
                    chunksize=max(1, len(chunk) // self.worker_processes),
                )
            )
        # One persistent pool for the loader's lifetime (threads are reused
        # across epochs instead of re-spawned each __iter__).
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=self.num_workers,
                thread_name_prefix="seist-loader",
            )
        # Chunked tasks, not per-sample: at batch 500 the per-future
        # lock/notify traffic alone cost ~25% of loader wall time
        # (profiled). A few tasks per worker keeps load balance without
        # hundreds of futures per batch.
        n_tasks = min(len(chunk), self.num_workers * 4)
        slices = np.array_split(np.asarray(chunk), n_tasks)
        getitem = self.dataset.__getitem__

        def run_slice(ids):
            return [getitem(int(i)) for i in ids]

        out: List[Any] = []
        for part in self._pool.map(run_slice, slices):
            out.extend(part)
        return out

    def __iter__(self) -> Iterator[Batch]:
        # Bus counters resolved once per epoch, not per batch (obs/bus.py;
        # the scrape side reads them via --metrics-port / snapshot()).
        from seist_tpu.obs.bus import BUS

        c_batches = BUS.counter("loader_batches")
        c_samples = BUS.counter("loader_samples")
        indices = self._indices()
        nb = len(self)
        start, self._start_batch = self._start_batch, 0  # one-shot
        for b in range(start, nb):
            # Chaos hook: SEIST_FAULT_IO_STALL_BATCH wedges the loader
            # here — the stall-watchdog e2e's stand-in for a deadlocked
            # worker pool or a hung filesystem.
            self._io_faults.maybe_stall(b)
            chunk = indices[b * self.batch_size : (b + 1) * self.batch_size]
            pad = self.batch_size - len(chunk)
            if pad:
                chunk = np.concatenate([chunk, np.repeat(chunk[-1], pad)])
            samples = self._fetch(chunk)
            inputs = _stack([s[0] for s in samples])
            loss_targets = _stack([s[1] for s in samples])
            metrics_targets = {
                k: np.stack([s[2][k] for s in samples])
                for k in samples[0][2]
            }
            meta = [s[3] for s in samples]
            mask = np.ones(self.batch_size, dtype=np.float32)
            if pad:
                mask[-pad:] = 0.0
            c_batches.inc()
            c_samples.inc(len(samples) - pad)
            yield Batch(inputs, loss_targets, metrics_targets, meta, mask)




_PROC_DATASET: Optional[SeismicDataset] = None


def _proc_worker_init(dataset: SeismicDataset) -> None:
    global _PROC_DATASET
    _PROC_DATASET = dataset


def _proc_worker_getitem(epoch_idx):
    """Process-pool sample fetch. Epoch rides along with every index: the
    parent's ``set_epoch`` does not propagate to live workers, and the
    per-sample RNG is seeded from (seed, epoch, idx)."""
    epoch, idx = epoch_idx
    _PROC_DATASET.set_epoch(epoch)
    return _PROC_DATASET[idx]


def _double_buffer(iterator, transform, prefetch: int, account: str = ""):
    """Producer-thread double buffering: apply ``transform`` (typically a
    sharded device_put) to each item ahead of the consumer, propagating
    producer exceptions. Shared by the prefetch_* variants.

    ``account`` names a bus-counter prefix for backpressure accounting on
    the bounded queue: ``<account>_backpressure_s`` accumulates the
    seconds the producer spent blocked on a full queue (the consumer —
    i.e. the device step — was the bottleneck), ``<account>_queue_full``
    counts the blocking puts. Zero backpressure = the pipeline is
    input-bound; saturated backpressure = the chip is."""
    buf: "queue.Queue" = queue.Queue(maxsize=prefetch)
    sentinel = object()
    err: List[BaseException] = []
    if account:
        from seist_tpu.obs.bus import BUS, monotonic

        c_wait = BUS.counter(f"{account}_backpressure_s")
        c_full = BUS.counter(f"{account}_queue_full")

    def _put(item) -> None:
        if not account or not buf.full():
            buf.put(item)
            return
        c_full.inc()
        t0 = monotonic()
        buf.put(item)
        c_wait.inc(monotonic() - t0)

    def producer():
        try:
            for item in iterator:
                _put(transform(item))
        except BaseException as e:  # propagate loader errors to the consumer
            err.append(e)
        finally:
            buf.put(sentinel)

    thread = threading.Thread(target=producer, daemon=True)
    thread.start()
    while True:
        item = buf.get()
        if item is sentinel:
            if err:
                raise err[0]
            return
        yield item


def prefetch_to_device(
    iterator: Iterator[Batch],
    mesh=None,
    prefetch: int = 2,
) -> Iterator[Batch]:
    """Double-buffered host->device transfer of Batch arrays.

    Arrays are ``device_put`` with the batch axis sharded over the mesh's
    ``data`` axis (XLA overlaps the copy with the running step); ``meta``
    stays on host. With ``mesh=None`` batches pass through untouched.
    """
    if mesh is None:
        yield from iterator
        return

    import jax

    from seist_tpu.parallel.mesh import shard_batch

    def put(batch: Batch) -> Batch:
        def _put(x):
            # shard_batch holds the single placement rule (device_put vs
            # make_array_from_process_local_data on multi-host).
            return shard_batch(mesh, x) if isinstance(x, np.ndarray) else x

        return Batch(
            jax.tree.map(_put, batch.inputs),
            jax.tree.map(_put, batch.loss_targets),
            {k: _put(v) for k, v in batch.metrics_targets.items()},
            batch.meta,
            _put(batch.mask),
        )

    yield from _double_buffer(iterator, put, prefetch)


def _per_device_bytes(batch: Batch) -> int:
    """Device memory one placed eval batch takes on each device (``meta``
    stays on the host)."""
    import jax

    def nbytes(x) -> int:
        shards = getattr(x, "addressable_shards", None)
        return int(shards[0].data.nbytes if shards else np.asarray(x).nbytes)

    return sum(
        nbytes(x)
        for x in jax.tree.leaves(
            (batch.inputs, batch.loss_targets, batch.metrics_targets, batch.mask)
        )
    )


def free_device_bytes() -> int:
    """What a run may still keep resident on each device: ``bytes_limit``
    less ``peak_bytes_in_use`` (the train step's temporaries come back
    every step, so the peak and not the current use), less an eighth of
    the limit for fragmentation, the eval step's own temporaries and what
    the host places on demand (PERF.md). The CPU backend reports no stats:
    its nominal budget is ``device_aug.hbm_budget_bytes``; an accelerator
    that reports none has nothing free."""
    import jax

    from seist_tpu.data.device_aug import hbm_budget_bytes

    free = []
    for dev in jax.local_devices():
        if dev.platform == "cpu":
            return hbm_budget_bytes()
        stats = dev.memory_stats() or {}
        limit = int(stats.get("bytes_limit", 0))
        free.append(
            limit - int(stats.get("peak_bytes_in_use", 0)) - limit // 8
        )
    return min(free)


class ResidentEvalPass:
    """Memo of one eval loader's pass, owned by a training run: validation
    never augments, never shuffles and never advances its epoch, so every
    pass of a run builds bit-identical batches. The first whole pass
    streams from the host loader and keeps each placed ``Batch`` as the
    eval step consumed it; every later pass replays that list — no loader
    threads, no preprocessing, no host-to-device copy, the same buffers
    and shardings (the eval step donates nothing).

    A pass that ends early keeps nothing. A pass that does not fit
    ``free_bytes()`` (reckoned from the first placed batch x the loader's
    batches) makes the run stream every pass, with one log line."""

    def __init__(self, free_bytes=free_device_bytes) -> None:
        from seist_tpu.obs.bus import BUS

        self._free_bytes = free_bytes
        # (placed batch, its valid rows) of one whole pass, once held
        self._pass: Optional[List[Tuple[Batch, int]]] = None
        self._too_large = False
        self._c_replayed = BUS.counter("val_samples_replayed")
        self._g_bytes = BUS.gauge("val_resident_bytes")

    @property
    def ready(self) -> bool:
        return self._pass is not None

    def replay(self) -> Iterator[Batch]:
        for batch, valid in self._pass:
            self._c_replayed.inc(valid)
            yield batch

    def fits(self, first: Batch, n_batches: int) -> bool:
        """Whether a pass of ``n_batches`` like ``first`` may stay on the
        devices; a no holds for the run and is logged once."""
        if not self._too_large:
            need = _per_device_bytes(first) * n_batches
            free = int(self._free_bytes())
            if need > free:
                self._too_large = True
                logger.info(
                    f"validation pass stays on the host loader: {need} "
                    f"bytes a device to keep it resident, {free} free"
                )
        return not self._too_large

    def hold(self, batches: List[Batch], valid: List[int]) -> None:
        """Keep one whole pass: its placed batches and their valid rows."""
        self._pass = list(zip(batches, valid))
        self._g_bytes.set(sum(_per_device_bytes(b) for b in batches))


def eval_batches(
    loader: Loader,
    mesh=None,
    *,
    watchdog: Optional[io_guard.StallWatchdog] = None,
    resident: Optional[ResidentEvalPass] = None,
) -> Iterator[Batch]:
    """The placed batches of one eval pass: replayed from ``resident`` once
    it holds a whole pass, else streamed from the host loader (the stall
    watchdog armed while blocked on it) and, if a ``resident`` has room,
    handed to it when the pass ran to its end."""
    if resident is not None and resident.ready:
        yield from resident.replay()
        return
    from seist_tpu.obs.bus import BUS

    c_streamed = BUS.counter("val_samples_streamed")
    valid: List[int] = []  # per batch, noted on the host side of the copy

    def noting() -> Iterator[Batch]:
        for batch in loader:
            valid.append(int(batch.mask.sum()))
            yield batch

    n_batches = len(loader)
    keep = resident is not None
    kept: List[Batch] = []
    for i, batch in enumerate(
        io_guard.watch(prefetch_to_device(noting(), mesh), watchdog)
    ):
        c_streamed.inc(valid[i])
        if keep and i == 0:
            keep = resident.fits(batch, n_batches)
        if keep:
            kept.append(batch)
        yield batch
    if keep and len(kept) == n_batches:
        resident.hold(kept, valid)


def _guarded_raw_event(sds: SeismicDataset, i: int) -> dict:
    """RawStore ingest read: transient faults retried like the host path;
    a permanently-corrupt sample raises ValueError — the device store
    holds EVERY sample resident for the whole run, so it refuses rather
    than bake a fallback in; the worker catches the ValueError and falls
    back to the host path, whose per-read quarantine handles it."""
    if not io_guard.enabled():
        return sds.raw_event(i)[0]
    try:
        event, _ = io_guard.guarded_event_read(
            lambda: sds.raw_event(i),
            key=i,
            desc=f"{sds.name()}.raw[{i}]",
            injector=sds.io_faults,
        )
        return event
    except io_guard.CorruptSampleError as e:
        raise ValueError(
            f"sample {i} is permanently corrupt ({e}); --device-aug "
            "falls back to the host path, which quarantines it"
        ) from e


class RawStore:
    """Host-side fixed-shape raw arrays for the device-aug paths
    (``--device-aug step|cached``): every raw trace decoded ONCE, the
    draw-free preprocessing (``_is_noise`` classification + ``pad_phases``)
    precomputed per sample, VALUE/ONEHOT label fields extracted to dense
    arrays. The per-step host work collapses to (at most) a fancy-index
    row gather — all augmentation, windowing, normalization and label
    synthesis happen on device (seist_tpu/data/device_aug.py).

    Requires a uniform raw trace length (every real dataset here decodes
    fixed-length traces); :meth:`build` raises ``ValueError`` otherwise
    and the worker falls back to the host path.
    """

    def __init__(
        self,
        arrays: Dict[str, Any],
        *,
        n_raw: int,
        augmentation: bool,
        raw_len: int,
        phase_slots: int,
    ) -> None:
        self.arrays = arrays
        self.n_raw = int(n_raw)
        self.augmentation = bool(augmentation)
        self.raw_len = int(raw_len)
        self.phase_slots = int(phase_slots)

    def __len__(self) -> int:
        # 2x-epoch rule: raw copy for idx < n_raw, augmented for >= n_raw
        # (matches SeismicDataset.__len__).
        return 2 * self.n_raw if self.augmentation else self.n_raw

    @property
    def nbytes(self) -> int:
        import jax

        return int(
            sum(np.asarray(a).nbytes for a in jax.tree.leaves(self.arrays))
        )

    @classmethod
    def estimate_bytes(cls, sds: SeismicDataset) -> int:
        """Resident-cache size estimate WITHOUT decoding the dataset:
        one sample's raw waveform bytes x dataset size (phase/value
        sidecars are noise next to the waveforms). The probe read goes
        through the guarded path — a transient fault at setup time must
        not crash device-aug selection when the same fault one call
        later (inside build) would be retried."""
        event = _guarded_raw_event(sds, 0)
        return int(
            np.asarray(event["data"]).astype(np.float32, copy=False).nbytes
            * sds.raw_size
        )

    @classmethod
    def build(cls, sds: SeismicDataset) -> "RawStore":
        pre = sds.preprocessor
        names = taskspec.flatten_io_names(
            sds.input_names + sds.label_names
        )
        value_names = sorted(
            {n for n in names if taskspec.get_kind(n) == taskspec.VALUE}
        )
        onehot_names = sorted(
            {n for n in names if taskspec.get_kind(n) == taskspec.ONEHOT}
        )

        from seist_tpu.data import device_aug as da

        # ONE decode pass per sample (the expensive part); the big
        # waveform arrays are written straight into the final stacked
        # buffer and per-sample events are dropped as they are consumed,
        # so peak host RAM stays ~1x the dataset. The cheap
        # _is_noise/pad_phases list math runs twice (once to size
        # phase_slots, once inside host_prepare — the ONE implementation
        # of the row contract the device kernels rely on).
        n = sds.raw_size
        events: List[Optional[dict]] = []
        raw_len = None
        max_phases = 1
        for i in range(n):
            event = _guarded_raw_event(sds, i)
            length = int(np.asarray(event["data"]).shape[-1])
            if raw_len is None:
                raw_len = length
            elif length != raw_len:
                raise ValueError(
                    f"device-aug needs uniform raw trace lengths; sample "
                    f"{i} has {length} != {raw_len}"
                )
            ppks, spks = list(event["ppks"]), list(event["spks"])
            if not pre._is_noise(event["data"], ppks, spks, event["snr"]):
                p, s = pad_phases(
                    ppks, spks, pre.min_event_gap, pre.in_samples
                )
                max_phases = max(max_phases, len(p), len(s))
            events.append(event)
        phase_slots = max(max_phases, pre._max_event_num)
        n_ch = len(pre.data_channels)

        arrays: Dict[str, Any] = {
            "data": np.empty((n, n_ch, int(raw_len or 0)), np.float32),
            "ppks": np.empty((n, phase_slots), np.int32),
            "np_p": np.empty((n,), np.int32),
            "spks": np.empty((n, phase_slots), np.int32),
            "np_s": np.empty((n,), np.int32),
        }
        vals = {name: np.zeros((n, 1), np.float32) for name in value_names}
        oh = {name: np.zeros((n,), np.int32) for name in onehot_names}
        for i in range(n):
            event = events[i]
            events[i] = None  # free as consumed
            row = da.host_prepare(pre, event, phase_slots)
            arrays["data"][i] = row["data"]
            arrays["ppks"][i] = row["ppks"]
            arrays["np_p"][i] = row["np_p"]
            arrays["spks"][i] = row["spks"]
            arrays["np_s"][i] = row["np_s"]
            if row["is_noise"] and (value_names or onehot_names):
                # The host path ERRORS on a noise-classified trace with
                # VALUE/ONEHOT labels (_clear_event_except empties the
                # field and get_io_item raises / stacking fails);
                # zero-filling here would silently train on fabricated
                # labels. Refuse — the worker falls back to the host
                # path, which surfaces the dataset problem loudly.
                raise ValueError(
                    f"sample {i} is noise-classified but the task has "
                    f"VALUE/ONEHOT labels "
                    f"({value_names + onehot_names}); the device path "
                    "will not fabricate label values for it"
                )
            for name in value_names:
                v = np.asarray(event.get(name, []), np.float32)
                if v.size == 0:  # host path would crash at stacking
                    raise ValueError(
                        f"sample {i} has no '{name}' value; refusing to "
                        "fabricate a device-path label"
                    )
                vals[name][i] = v.reshape(-1)[:1]
            for name in onehot_names:
                v = event.get(name, [])
                if not len(v):  # host get_io_item raises here too
                    raise ValueError(
                        f"sample {i} has no '{name}' class; refusing to "
                        "fabricate a device-path label"
                    )
                oh[name][i] = int(v[0])
        if value_names:
            arrays["values"] = vals
        if onehot_names:
            arrays["onehots"] = oh
        return cls(
            arrays,
            n_raw=n,
            augmentation=sds.augmentation,
            raw_len=int(raw_len or 0),
            phase_slots=phase_slots,
        )

    def row_batch(self, raw_idx: np.ndarray) -> Dict[str, Any]:
        """Fancy-index a batch of raw rows (numpy; the step-mode per-step
        host work)."""
        import jax

        return jax.tree.map(lambda a: a[raw_idx], self.arrays)


class DeviceEpochCache:
    """HBM-resident raw epochs (``--device-aug cached``): the RawStore
    arrays uploaded ONCE, sample axis sharded over the mesh's ``data``
    axis (sample count padded to divisibility; pad rows are never
    indexed). Each train step then only receives a (k, B) int32 index
    array — there is no per-step sample traffic across the host boundary
    at all."""

    def __init__(self, store: RawStore, mesh=None) -> None:
        import jax

        self.store = store
        arrays = store.arrays
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from seist_tpu.parallel.mesh import AXIS_DATA

            shards = mesh.shape[AXIS_DATA]
            n = store.n_raw
            pad = (-n) % shards
            if pad:
                arrays = jax.tree.map(
                    lambda a: np.concatenate(
                        [a, np.zeros((pad,) + a.shape[1:], a.dtype)]
                    ),
                    arrays,
                )
            sharding = NamedSharding(mesh, P(AXIS_DATA))
            if jax.process_count() > 1:
                # Multi-host: every host holds the full raw arrays (the
                # upload reads the whole dataset), but device_put cannot
                # place onto non-addressable devices — hand XLA only the
                # slices this host's devices own. Combined with the
                # host-sharded epoch_index_chunks below this is the
                # deterministic global shard contract that used to force
                # the cached->step fallback on multi-host.
                self.arrays = jax.tree.map(
                    lambda a: jax.make_array_from_callback(
                        a.shape, sharding, lambda idx, a=a: a[idx]
                    ),
                    arrays,
                )
            else:
                self.arrays = jax.tree.map(
                    lambda a: jax.device_put(a, sharding), arrays
                )
        else:
            self.arrays = jax.tree.map(jax.device_put, arrays)
        self.nbytes = int(
            sum(a.nbytes for a in jax.tree.leaves(self.arrays))
        )

    def epoch_index_chunks(
        self,
        epoch: int,
        *,
        seed: int,
        shuffle: bool,
        batch_size: int,
        steps_per_call: int,
        start_batch: int = 0,
        num_shards: int = 1,
        shard_index: int = 0,
        source_ids: Optional[np.ndarray] = None,
        mixture_temperature: float = 0.0,
    ):
        """Yield (k, B) int32 index arrays for one epoch — the same
        global sample sequence the host Loader would produce
        (:func:`_epoch_order`), chunked for the scan-based executor. On
        multi-host runs each host yields ITS interleaved shard of the
        global order (``batch_size`` local rows per step;
        ``shard_stacked_batch`` assembles the global batch), so the
        union over hosts covers exactly what a single host would train
        on. Trailing part-groups are dropped (drop-last + static jit
        shapes, as on the packed host path)."""
        order = _epoch_order(
            len(self.store),
            seed=seed,
            epoch=epoch,
            shuffle=shuffle,
            num_shards=num_shards,
            shard_index=shard_index,
            source_ids=source_ids,
            mixture_temperature=mixture_temperature,
        )
        nb = len(order) // batch_size
        calls = nb // steps_per_call
        for c in range(start_batch // steps_per_call, calls):
            flat = order[
                c * steps_per_call * batch_size
                : (c + 1) * steps_per_call * batch_size
            ]
            yield np.asarray(
                flat.reshape(steps_per_call, batch_size), np.int32
            )


def iter_raw_batches(
    store: RawStore,
    epoch: int,
    *,
    seed: int,
    shuffle: bool,
    batch_size: int,
    num_shards: int = 1,
    shard_index: int = 0,
    start_batch: int = 0,
    source_ids: Optional[np.ndarray] = None,
    mixture_temperature: float = 0.0,
):
    """Step-mode (``--device-aug step``) feed: per batch, gather the raw
    rows on host (a numpy fancy index — no per-sample augmentation, no
    label synthesis, no Python stacking) and yield
    ``(rows, idx, aug)`` for the augment-inside-the-step train step.
    Sample order matches the host Loader exactly (:func:`_epoch_order`,
    drop-last). A store exposing ``row_batch_at`` (the packed
    direct-ingest store) gets the (epoch, logical idx) context its
    guarded reads key quarantine fallbacks on."""
    order = _epoch_order(
        len(store),
        seed=seed,
        epoch=epoch,
        shuffle=shuffle,
        num_shards=num_shards,
        shard_index=shard_index,
        source_ids=source_ids,
        mixture_temperature=mixture_temperature,
    )
    nb = len(order) // batch_size
    n_raw = store.n_raw
    row_batch_at = getattr(store, "row_batch_at", None)
    for b in range(start_batch, nb):
        sel = np.asarray(order[b * batch_size : (b + 1) * batch_size], np.int64)
        raw = sel % n_raw if store.augmentation else sel
        aug = (
            (sel >= n_raw)
            if store.augmentation
            else np.zeros(sel.shape, bool)
        )
        if row_batch_at is not None:
            rows = row_batch_at(raw, epoch=epoch, idx=sel)
        else:
            rows = store.row_batch(raw)
        yield rows, sel.astype(np.int32), aug


def prefetch_raw_to_device(iterator, mesh, prefetch: int = 2):
    """Double-buffered device feed for :func:`iter_raw_batches` items:
    rows/idx/aug all batch-sharded on ``data`` (same placement rule as
    the host path's batches). The bounded queue's backpressure is
    accounted on the bus (``data_ingest_backpressure_s`` /
    ``data_ingest_queue_full`` — docs/OBSERVABILITY.md)."""
    if mesh is None:
        yield from iterator
        return

    from seist_tpu.parallel.mesh import shard_batch

    yield from _double_buffer(
        iterator,
        lambda item: shard_batch(mesh, item),
        prefetch,
        account="data_ingest",
    )


def prefetch_packed_to_device(
    iterator: Iterator[Batch],
    mesh,
    steps_per_call: int,
    prefetch: int = 2,
) -> Iterator[Tuple[Any, Any]]:
    """Group ``steps_per_call`` train batches into one stacked
    ``(inputs_k, targets_k)`` pair — leading axis = micro-step, second =
    batch — double-buffered to device with the batch axis sharded on
    ``data`` (``shard_stacked_batch``). Feeds ``make_multi_train_step``.

    A trailing group smaller than ``steps_per_call`` is DROPPED (same
    spirit as the train loader's drop-last; jit shapes must stay static).
    Only inputs/loss_targets survive packing: the multi-step path returns
    no per-micro-step outputs, so metrics targets/meta have no consumer.
    """
    import jax

    from seist_tpu.parallel.mesh import shard_stacked_batch

    def packed():
        group: List[Batch] = []
        for b in iterator:
            group.append(b)
            if len(group) == steps_per_call:
                inputs = jax.tree.map(
                    lambda *xs: np.stack(xs), *[g.inputs for g in group]
                )
                targets = jax.tree.map(
                    lambda *xs: np.stack(xs), *[g.loss_targets for g in group]
                )
                yield inputs, targets
                group = []

    if mesh is None:
        yield from packed()
        return

    yield from _double_buffer(
        packed(), lambda item: shard_stacked_batch(mesh, item), prefetch
    )
