"""Resilient data-plane I/O: retries, corrupt-sample quarantine, stall watchdog.

PR 2 made the *step loop* self-healing; this module does the same for the
data plane that feeds it. Production TPU stacks read training data from
network filesystems where transient faults are routine — one truncated
HDF5 part, one NaN-filled trace, or one wedged loader thread must not
take down (or silently hang) a days-long run. Three mechanisms, each
independently testable (tests/test_io_guard.py, tests/test_data_plane_chaos.py):

* **Retry with exponential backoff + jitter** (:func:`read_with_retry`)
  around every sample read. Faults are classified: *transient*
  (``OSError`` — flaky NFS, stale h5py handle; the reader evicts the
  cached handle so the retry reopens) vs *permanent*
  (:class:`CorruptSampleError` — short read, bad shape, non-finite data).
  Transients that outlive the retry budget are promoted to permanent
  (:class:`RetriesExhaustedError`).
* **Corrupt-sample quarantine** (:class:`Quarantine`): a permanently-bad
  sample index is benched and *deterministically replaced* by a fallback
  index drawn from a PRNG keyed by ``(seed, epoch, idx)`` — batch shapes
  and the global sample sequence (``pipeline.epoch_indices``) stay fixed
  and resume-stable; the replacement does not depend on worker scheduling
  or discovery order (the candidate sequence is deterministic and a
  candidate is accepted iff it itself reads cleanly). Past a configurable
  quarantined fraction the run aborts loudly
  (:class:`QuarantineOverflowError`) instead of training on garbage.
* **Pipeline stall watchdog** (:class:`StallWatchdog` + :func:`watch`):
  armed while the train loop is blocked waiting for the next batch (so
  step compute / compiles / validation never count against the budget);
  if no batch arrives for ``timeout_s`` it dumps every thread's stack and
  exits with the clean-preempt code so ``tools/supervise.py`` relaunches
  from the last checkpoint instead of the run hanging forever. A loader
  worker thread dying surfaces as :class:`LoaderDeathError`, which the
  train worker converts into the same checkpoint-then-preempt exit.

Counters (reads/retries/reopens/quarantined/fallbacks/stalls) accumulate
in :data:`COUNTERS`; they surface through worker epoch logs,
``ops.metrics.data_plane_counters()`` and the BENCH ``data_plane``
section (bench.py). The guard is on by default; ``SEIST_IO_GUARD=0`` (or
the :func:`disabled` context manager) restores the raw read path — the
clean-path overhead is a try/except plus one ``np.isfinite`` pass per
sample (benched at well under 2% of loader stage time).

Fault injection for all three mechanisms lives in
``seist_tpu/utils/faults.py`` (``SEIST_FAULT_IO_*``).
"""

from __future__ import annotations

import contextlib
import logging
import os
import random
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, Iterator, Optional

import numpy as np

from seist_tpu.utils.logger import logger

# Keep in sync with seist_tpu.train.checkpoint.PREEMPT_EXIT_CODE (pinned
# by tests/test_io_guard.py; importing train.checkpoint here would pull
# orbax into every data-plane import).
PREEMPT_EXIT_CODE = 75


# ----------------------------------------------------------------- fault classes
class CorruptSampleError(Exception):
    """Permanent per-sample fault: the bytes came back but the sample is
    unusable (short read, wrong shape/dtype, non-finite values, missing
    trace key). Never retried — the sample gets quarantined."""


class RetriesExhaustedError(CorruptSampleError):
    """A transient fault outlived the retry budget. Treated like
    corruption from the quarantine's point of view: the sample is benched
    and replaced so the run keeps its shape contract."""


class QuarantineOverflowError(RuntimeError):
    """Quarantined fraction crossed ``max_frac``: the dataset is rotted
    (or the fault classification is wrong) and silently training on
    fallback samples would be worse than dying. Crashes the run — this is
    NOT converted into a preempt/relaunch."""


class LoaderDeathError(RuntimeError):
    """A loader worker raised something that is neither transient nor
    per-sample corruption (i.e. a bug or an environment failure the retry
    ladder cannot absorb). The train worker turns this into a
    checkpoint + clean-preempt exit rather than an opaque crash."""


# ------------------------------------------------------------------- counters
class Counters:
    """Thread-safe monotonic counters for the data-plane guard."""

    _FIELDS = (
        "reads",
        "retries",
        "reopens",
        "quarantined",
        "fallback_reads",
        "stall_trips",
        "loader_deaths",
    )

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._v: Dict[str, int] = {k: 0 for k in self._FIELDS}

    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._v[name] = self._v.get(name, 0) + n

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._v)

    def reset(self) -> None:
        with self._lock:
            for k in self._v:
                self._v[k] = 0

    def any_faults(self) -> bool:
        s = self.snapshot()
        return any(v for k, v in s.items() if k != "reads")


COUNTERS = Counters()


# ------------------------------------------------------------- enable/disable
_ENABLED = os.environ.get("SEIST_IO_GUARD", "1") != "0"


def enabled() -> bool:
    return _ENABLED


@contextlib.contextmanager
def disabled():
    """Bypass the guard (raw reads, no validation) — bench.py uses this to
    price the clean-path overhead; not intended for production runs."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = False
    try:
        yield
    finally:
        _ENABLED = prev


# ------------------------------------------------------------------ retry core
class RetryPolicy:
    """Exponential backoff with jitter: attempt k sleeps
    ``min(base * 2**k, cap) * uniform(0.5, 1.5)``. Jitter decorrelates a
    thread-pool's retries after a shared-filesystem hiccup (every loader
    thread fails at once; synchronized retries would hammer the server in
    lockstep). The jitter only shapes *sleep time* — it never touches
    sample content, so determinism contracts are unaffected."""

    def __init__(
        self,
        attempts: Optional[int] = None,
        backoff_base_s: Optional[float] = None,
        backoff_cap_s: Optional[float] = None,
    ) -> None:
        env = os.environ
        self.attempts = max(
            1,
            int(attempts if attempts is not None
                else env.get("SEIST_IO_RETRIES", 3)),
        )
        self.backoff_base_s = float(
            backoff_base_s if backoff_base_s is not None
            else env.get("SEIST_IO_BACKOFF_MS", 50)
        ) / (1.0 if backoff_base_s is not None else 1000.0)
        self.backoff_cap_s = float(
            backoff_cap_s if backoff_cap_s is not None
            else env.get("SEIST_IO_BACKOFF_CAP_MS", 2000)
        ) / (1.0 if backoff_cap_s is not None else 1000.0)

    def sleep_s(self, attempt: int) -> float:
        base = min(self.backoff_base_s * (2.0 ** attempt), self.backoff_cap_s)
        # detlint: disable=unseeded-rng -- jitter shapes SLEEP TIME only
        # (retry decorrelation after a shared-fs hiccup needs it to be
        # uncorrelated across threads); it never touches sample content.
        return base * random.uniform(0.5, 1.5)


_DEFAULT_POLICY: Optional[RetryPolicy] = None


def default_policy() -> RetryPolicy:
    global _DEFAULT_POLICY
    if _DEFAULT_POLICY is None:
        _DEFAULT_POLICY = RetryPolicy()
    return _DEFAULT_POLICY


def read_with_retry(
    fn: Callable[[], Any],
    *,
    desc: str = "read",
    fault_key: int = -1,
    injector=None,
    policy: Optional[RetryPolicy] = None,
    sleep: Callable[[float], None] = time.sleep,
) -> Any:
    """Call ``fn`` with transient-fault retries.

    * ``OSError`` -> counted, backed off, retried (readers evict stale
      h5py handles / memmaps before raising, so the retry reopens);
      exhausted retries raise :class:`RetriesExhaustedError`.
    * :class:`CorruptSampleError` -> re-raised immediately (permanent).
    * anything else -> re-raised immediately (a bug is not a fault to
      absorb).

    ``injector``/``fault_key`` hook the chaos harness in: the injected
    flaky failure fires *inside* the retry loop, exactly where a real
    flaky filesystem would.
    """
    policy = policy or default_policy()
    COUNTERS.inc("reads")
    last: Optional[BaseException] = None
    for attempt in range(policy.attempts):
        try:
            if injector is not None:
                injector.maybe_flaky_read(fault_key, attempt)
            return fn()
        except CorruptSampleError:
            raise
        except OSError as e:
            last = e
            COUNTERS.inc("retries")
            if attempt + 1 < policy.attempts:
                logger.warning(
                    f"[io-guard] transient fault on {desc} "
                    f"(attempt {attempt + 1}/{policy.attempts}): {e!r}; "
                    "retrying"
                )
                sleep(policy.sleep_s(attempt))
    raise RetriesExhaustedError(
        f"{desc} still failing after {policy.attempts} attempts: {last!r}"
    ) from last


def guarded_event_read(
    fn: Callable[[], Any],
    *,
    key: int,
    desc: str,
    injector=None,
) -> Any:
    """The ONE classification ladder for a sample read, shared by the
    host path (``SeismicDataset._fetch_event_slow``) and the device-aug
    ingest (``pipeline._guarded_raw_event``): transient retries
    (:func:`read_with_retry`, with injected flakiness riding the loop),
    the injected-corruption hook, then ingest validation. ``fn`` returns
    ``(event, meta)``; any permanent fault surfaces as
    :class:`CorruptSampleError` — each caller keeps only its distinct
    fallback policy (quarantine vs refusal)."""
    event, meta = read_with_retry(fn, desc=desc, fault_key=key, injector=injector)
    if injector is not None and injector.is_corrupt(key):
        raise CorruptSampleError(f"[faults] injected corrupt sample {key}")
    validate_event(event, desc=desc)
    return event, meta


# ------------------------------------------------------------------ validation
def validate_event(event: Any, *, desc: str = "sample") -> None:
    """Ingest validation: the permanent-fault classifier for a decoded
    Event dict. Raises :class:`CorruptSampleError` on a missing/empty/
    non-numeric/non-finite waveform or a non-2D shape; anything that
    passes here is safe to hand to the preprocessor.

    Runs once per sample on the clean fast path, so the checks are kept
    deliberately lean: one attribute walk plus (for float data) a single
    ``np.isfinite`` pass — a few microseconds against a loader stage
    measured in hundreds (the BENCH ``data_plane`` section prices it)."""
    try:
        data = event["data"]
    except (TypeError, KeyError, IndexError):
        raise CorruptSampleError(f"{desc}: event has no 'data' field") from None
    if type(data) is not np.ndarray:
        data = np.asarray(data)
    kind = data.dtype.kind
    if kind not in "fiu":
        raise CorruptSampleError(
            f"{desc}: non-numeric waveform dtype {data.dtype}"
        )
    if data.ndim != 2:
        raise CorruptSampleError(
            f"{desc}: waveform must be (C, L), got shape {data.shape}"
        )
    if data.shape[-1] == 0 or data.shape[0] == 0:
        raise CorruptSampleError(f"{desc}: empty waveform {data.shape}")
    if kind == "f" and not np.isfinite(data).all():
        bad = int(data.size - np.isfinite(data).sum())
        raise CorruptSampleError(
            f"{desc}: waveform has {bad} non-finite value(s)"
        )


# ------------------------------------------------------------------ quarantine
_FALLBACK_SALT = 0x5E15_7  # keys the fallback PRNG stream apart from others


class Quarantine:
    """Registry of benched raw sample indices + the deterministic
    replacement rule.

    ``candidates(raw, seed=, epoch=, idx=)`` yields the read order for
    one logical sample: the sample itself first, then fallback draws from
    ``default_rng(SeedSequence([seed, epoch, idx, salt]))``. The caller
    accepts the first candidate that reads cleanly and quarantines the
    ones that don't — so the accepted replacement is a pure function of
    (seed, epoch, idx) and the set of *actually corrupt* samples,
    independent of discovery order, worker scheduling, or resume point.

    ``add`` raises :class:`QuarantineOverflowError` once more than
    ``max_frac`` of the dataset is benched.
    """

    MAX_DRAWS = 64  # fallback draws per logical sample before giving up

    def __init__(self, n_total: int, max_frac: float = 0.05) -> None:
        if n_total <= 0:
            raise ValueError(f"n_total must be positive, got {n_total}")
        self.n_total = int(n_total)
        self.max_frac = float(max_frac)
        self._lock = threading.Lock()
        self._bad: Dict[int, str] = {}
        # Lock-free hot-path hint: False until the first add(). The clean
        # path checks this plain bool (atomic under the GIL) instead of
        # taking the lock per sample.
        self.active = False

    def __contains__(self, raw_idx: int) -> bool:
        with self._lock:
            return int(raw_idx) in self._bad

    def __len__(self) -> int:
        with self._lock:
            return len(self._bad)

    def add(self, raw_idx: int, reason: str) -> None:
        with self._lock:
            if int(raw_idx) in self._bad:
                return
            self._bad[int(raw_idx)] = str(reason)
            n_bad = len(self._bad)
            self.active = True
        COUNTERS.inc("quarantined")
        logger.warning(
            f"[io-guard] quarantined sample {raw_idx} "
            f"({n_bad}/{self.n_total}): {reason}"
        )
        limit = self.max_frac * self.n_total
        if n_bad > limit:
            # The overflow crashes the run (deliberately NOT a preempt);
            # leave the forensic record first — which samples, why, when.
            _flight_dump(
                "quarantine_overflow", quarantined=n_bad, n_total=self.n_total
            )
            raise QuarantineOverflowError(
                f"{n_bad}/{self.n_total} samples quarantined exceeds "
                f"--max-quarantine-frac {self.max_frac}: the dataset is "
                "rotted; refusing to keep training on fallback samples"
            )

    def candidates(
        self, raw_idx: int, *, seed: int, epoch: int, idx: int
    ) -> Iterator[int]:
        if raw_idx not in self:
            yield int(raw_idx)
        rng = np.random.default_rng(
            np.random.SeedSequence(
                [int(seed), int(epoch), int(idx), _FALLBACK_SALT]
            )
        )
        for _ in range(self.MAX_DRAWS):
            cand = int(rng.integers(self.n_total))
            if cand == raw_idx or cand in self:
                continue
            yield cand

    # The owning SeismicDataset is pickled into process-pool loader
    # workers; locks don't pickle, so ship the plain state. Each worker
    # process then quarantines independently — the deterministic
    # fallback rule keeps the CONTENT identical across workers (a
    # candidate is accepted iff it reads cleanly, and the corrupt set is
    # a property of the data, not of the process), but the parent's
    # epoch-end report only covers thread-pool loaders.
    def __getstate__(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "n_total": self.n_total,
                "max_frac": self.max_frac,
                "bad": dict(self._bad),
            }

    def __setstate__(self, state: Dict[str, Any]) -> None:
        self.__init__(state["n_total"], state["max_frac"])
        self._bad.update(state["bad"])
        self.active = bool(self._bad)

    def report(self) -> Dict[str, Any]:
        """JSON-able epoch-end report (logged by the train worker)."""
        with self._lock:
            bad = dict(self._bad)
        return {
            "quarantined": sorted(bad),
            "reasons": {str(k): bad[k] for k in sorted(bad)},
            "n_total": self.n_total,
            "frac": round(len(bad) / self.n_total, 6),
            "max_frac": self.max_frac,
        }


# ------------------------------------------------------------- stall watchdog
def _flight_dump(reason: str, **fields) -> None:
    """Best-effort flight-recorder dump (obs/flight.py) on a death path.
    A no-op when no recorder is installed (library use outside the train
    worker) and never raises — the exit matters more than the artifact."""
    try:
        from seist_tpu.obs import flight

        flight.dump_on_death(reason, **fields)
    except Exception:  # noqa: BLE001 - death path; the exit must proceed
        pass


def hard_exit(code: int) -> None:
    """Flush log handlers and ``os._exit``. The only safe exit when
    non-daemon data-plane threads may be wedged: ``sys.exit`` would hang
    forever in ``threading._shutdown`` joining a pool thread stuck
    inside a dead read — the exact hang this module exists to eliminate.
    A separate function so in-process tests can monkeypatch it.

    Dumps the flight recorder first (docs/OBSERVABILITY.md): this is the
    funnel every hard death path drains through, so the dump happens even
    when the caller forgot (deduped when the caller already dumped a
    richer record seconds ago)."""
    _flight_dump("hard_exit", dedup_s=5.0, exit_code=code)
    logging.shutdown()
    os._exit(code)


def dump_thread_stacks(to=None) -> str:
    """Format every live thread's stack (the post-mortem a hung loader
    never gives you) — logged AND returned."""
    frames = sys._current_frames()
    names = {t.ident: t.name for t in threading.enumerate()}
    chunks = []
    for ident, frame in frames.items():
        header = f"--- thread {names.get(ident, '?')} ({ident}) ---"
        chunks.append(header + "\n" + "".join(traceback.format_stack(frame)))
    text = "\n".join(chunks)
    stream = to if to is not None else sys.stderr
    try:
        print(text, file=stream, flush=True)
    # The dump is best-effort post-mortem output on a process that is
    # about to exit; a broken stderr must not mask the preempt exit.
    except Exception:
        pass
    try:
        logger.error(f"[io-guard] thread stacks at stall:\n{text}")
    except Exception:  # noqa: BLE001 - same best-effort contract as above
        pass
    return text


class StallWatchdog:
    """Background thread that trips when the consumer has been *armed*
    (blocked waiting for a batch) longer than ``timeout_s``.

    Armed/disarmed around each ``next()`` by :func:`watch`, so device
    step time, jit compiles, validation, and checkpoint saves never count
    toward the budget — only actual time spent waiting on the data plane
    does. On trip: dump all thread stacks, flush, and hard-exit with the
    clean-preempt code (``os._exit`` — a wedged loader may hold arbitrary
    locks, so a cooperative exit could itself hang; tools/supervise.py
    relaunches from the newest checkpoint). ``exit_fn`` is injectable for
    tests.
    """

    def __init__(
        self,
        timeout_s: float,
        *,
        exit_code: int = PREEMPT_EXIT_CODE,
        exit_fn: Optional[Callable[[int], None]] = None,
        poll_s: Optional[float] = None,
    ) -> None:
        if timeout_s <= 0:
            raise ValueError(f"timeout_s must be > 0, got {timeout_s}")
        self.timeout_s = float(timeout_s)
        self.exit_code = int(exit_code)
        self._exit_fn = exit_fn if exit_fn is not None else hard_exit
        self._poll_s = (
            float(poll_s) if poll_s else max(min(self.timeout_s / 4, 5.0), 0.01)
        )
        self._armed_since: Optional[float] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.tripped = False

    def start(self) -> "StallWatchdog":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="seist-data-watchdog", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2 * self._poll_s)
            self._thread = None

    def arm(self) -> None:
        self._armed_since = time.monotonic()

    def disarm(self) -> None:
        self._armed_since = None

    def _run(self) -> None:
        # A watchdog that dies silently IS the failure it guards against:
        # the stall it would have caught then hangs the run forever. Log
        # loudly and re-raise (threadlint thread-target-raises).
        try:
            while not self._stop.wait(self._poll_s):
                armed = self._armed_since
                if armed is None:
                    continue
                waited = time.monotonic() - armed
                if waited > self.timeout_s:
                    self._trip(waited)
                    return
        except Exception:
            logger.exception(
                "[io-guard] stall watchdog thread died — stall protection "
                "is GONE for the rest of this run"
            )
            raise

    def _trip(self, waited: float) -> None:
        self.tripped = True
        COUNTERS.inc("stall_trips")
        logger.error(
            f"[io-guard] pipeline stall: no batch for {waited:.1f}s "
            f"(timeout {self.timeout_s}s); dumping thread stacks and "
            f"exiting {self.exit_code} for supervised relaunch"
        )
        stacks = dump_thread_stacks()
        # Explicit dump here (hard_exit would also fire one) so the stall
        # record carries the thread stacks and wait time even when a test
        # injects a custom exit_fn.
        _flight_dump("stall_watchdog", waited_s=round(waited, 1),
                     thread_stacks=stacks)
        # The default exit_fn is hard_exit (logging.shutdown + os._exit):
        # every registered handler flushes, so the stall post-mortem is
        # durable before the process dies.
        self._exit_fn(self.exit_code)


def watch(
    iterator,
    watchdog: Optional[StallWatchdog],
    on_death: Optional[Callable[[LoaderDeathError], None]] = None,
):
    """Wrap a batch iterator so the watchdog is armed exactly while
    blocked in ``next()``. ``watchdog=None`` is a passthrough for the
    arming (the wrapper stays in place so call sites need no branching).
    ``on_death`` fires when the data plane raises
    :class:`LoaderDeathError` — the train worker uses it to checkpoint
    and preempt-exit at the exact batch position reached."""
    if watchdog is None and on_death is None:
        yield from iterator
        return
    it = iter(iterator)
    while True:
        if watchdog is not None:
            watchdog.arm()
        try:
            item = next(it)
        except StopIteration:
            return
        except LoaderDeathError as e:
            if on_death is not None:
                on_death(e)
            raise
        finally:
            if watchdog is not None:
                watchdog.disarm()
        yield item
