"""Profiling / tracing — a first-class subsystem the reference lacks
(SURVEY.md §5: only coarse epoch timing + TensorBoard scalars).

* :func:`trace` / :func:`trace_start` / :func:`trace_stop` — a
  ``jax.profiler`` capture (device ops and the program's bus spans, on one
  clock) written to the log dir as an ``.xplane.pb``.
* :func:`stopwatch`, :class:`StepTimeSplit` — interval timing on the bus
  clock (obs/bus.py).
"""

from __future__ import annotations

import contextlib
from typing import Callable, Dict, Iterator, List, Optional


def trace_start(logdir: str) -> None:
    """Begin a jax.profiler trace (pair with :func:`trace_stop`) — the
    non-contextmanager form for capture windows that span loop iterations
    (the worker's --profile-steps path, SIGUSR2, ``POST /profile``).

    Device ops and TraceMe annotations (every bus span is one), no Python
    tracer: with it on, a few seconds of a trainer's loader threads filled
    40 GiB of host memory on the chip (PERF.md, PR 23, call 1). The same
    options as the benchmark's capture, so an operator's trace holds the
    same spans and regions."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(logdir, profiler_options=options)


def trace_stop() -> None:
    import jax

    jax.profiler.stop_trace()


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """``with trace(dir):`` profiles everything inside; view with
    TensorBoard's profile plugin or Perfetto."""
    trace_start(logdir)
    try:
        yield
    finally:
        trace_stop()


@contextlib.contextmanager
def stopwatch() -> Iterator[Callable[[], float]]:
    """``with stopwatch() as elapsed:`` — ``elapsed()`` returns seconds
    since entry (monotonic), both inside the block and after it exits.
    Used by serve warmup/handlers so timing reads the same everywhere.

    Thin re-export of the obs bus's timing primitive (obs/bus.py): every
    interval in the repo reads ONE monotonic clock, so the span API, this
    stopwatch and :class:`StepTimeSplit` can never drift apart."""
    from seist_tpu.obs.bus import stopwatch as _stopwatch

    with _stopwatch() as elapsed:
        yield elapsed


class StepTimeSplit:
    """Per-step host-wait vs device-compute split.

    ``host_wait`` is the time the step loop spends BEFORE the device can
    start — fetching/stacking the batch and staging it to the device;
    ``device_time`` is dispatch-to-block_until_ready. The
    ``input_bound_fraction`` (host / (host + device)) is the number that
    says whether training is input-bound: ~0 means the chip sets the
    pace, ~1 means it idles behind the input pipeline. Recorded per step
    so bench.py can emit the raw split; the first ``skip_first`` steps
    (jit compile / warmup) are excluded from the summary.
    """

    def __init__(self, skip_first: int = 1):
        self.skip_first = int(skip_first)
        self.host_s: List[float] = []
        self.device_s: List[float] = []
        self._pending_host: Optional[float] = None

    def step(self, host_s: float, device_s: float) -> None:
        self.host_s.append(float(host_s))
        self.device_s.append(float(device_s))

    @contextlib.contextmanager
    def host(self) -> Iterator[None]:
        """Time the host half of one step (batch fetch/stack/stage) on
        the shared obs stopwatch; pair with :meth:`device`, which records
        the completed (host, device) step."""
        with stopwatch() as elapsed:
            yield
        self._pending_host = elapsed()

    @contextlib.contextmanager
    def device(self) -> Iterator[None]:
        """Time the device half (dispatch→block_until_ready) and record
        the step with the pending host time from :meth:`host`."""
        with stopwatch() as elapsed:
            yield
        self.step(self._pending_host or 0.0, elapsed())
        self._pending_host = None

    def summary(self) -> Dict[str, object]:
        h = self.host_s[self.skip_first :]
        d = self.device_s[self.skip_first :]
        if not h:
            return {
                "steps": 0,
                "host_wait_ms_per_step": None,
                "device_time_ms_per_step": None,
                "input_bound_fraction": None,
                "per_step_host_wait_ms": [],
                "per_step_device_time_ms": [],
            }
        hm = sum(h) / len(h)
        dm = sum(d) / len(d)
        return {
            "steps": len(h),
            "host_wait_ms_per_step": round(hm * 1e3, 3),
            "device_time_ms_per_step": round(dm * 1e3, 3),
            "input_bound_fraction": round(hm / max(hm + dm, 1e-12), 4),
            "per_step_host_wait_ms": [round(x * 1e3, 3) for x in h],
            "per_step_device_time_ms": [round(x * 1e3, 3) for x in d],
        }
