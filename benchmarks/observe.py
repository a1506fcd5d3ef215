"""What the harness watches from outside the program's code: the spans and
counters of ``seist_tpu.obs.BUS``, JAX's compile log, and the device's
memory. Host clock throughout (``time.monotonic``, the bus's own clock)."""

from __future__ import annotations

import logging
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

_COMPILED = re.compile(r"Finished XLA compilation of (\S+) in ([0-9.eE+-]+) sec")
_CACHE_HIT = re.compile(r"Persistent compilation cache hit for '([^']+)'")


class SpanLog:
    """Every span that ends on the bus, as (name, start, seconds). A
    callback may be hooked to a span name (the drivers open and close
    their windows on the program's own epoch spans)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.spans: List[Tuple[str, float, float]] = []
        self._hooks: Dict[str, Callable[[float], None]] = {}

    def on(self, name: str, fn: Callable[[float], None]) -> None:
        self._hooks[name] = fn

    def __call__(self, span: Any) -> None:  # the bus's sink signature
        end = time.monotonic()
        dur = float(span.duration_s or 0.0)
        with self._lock:
            self.spans.append((span.name, end - dur, dur))
        hook = self._hooks.get(span.name)
        if hook is not None:
            hook(end)

    def between(self, name: str, t0: float, t1: float) -> List[Tuple[float, float]]:
        """(start, seconds) of the named spans, clipped to [t0, t1]."""
        out = []
        with self._lock:
            spans = list(self.spans)
        for n, s, d in spans:
            if n != name:
                continue
            a, b = max(s, t0), min(s + d, t1)
            if b > a:
                out.append((a, b - a))
        return out


class CompileLog(logging.Handler):
    """JAX's own compile messages (``jax_log_compiles``), timestamped:
    compiled programs with their seconds, and persistent-cache hits."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.compiled: List[Tuple[float, str, float]] = []
        self.hits: List[Tuple[float, str]] = []

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:  # noqa: BLE001 - a malformed record is not ours
            return
        m = _COMPILED.search(msg)
        if m:
            self.compiled.append((time.monotonic(), m.group(1), float(m.group(2))))
            return
        m = _CACHE_HIT.search(msg)
        if m:
            self.hits.append((time.monotonic(), m.group(1)))

    def install(self) -> "CompileLog":
        import jax

        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(self)
        return self

    def between(self, t0: float, t1: float) -> List[Tuple[float, str, float]]:
        return [c for c in self.compiled if t0 <= c[0] <= t1]


def bus_counters() -> Dict[str, float]:
    """Every counter and gauge on the bus, by name (labels dropped: the
    drivers here run one trainer or one replica per process)."""
    from seist_tpu.obs.bus import BUS, Counter, Gauge

    out: Dict[str, float] = {}
    with BUS._lock:
        metrics = list(BUS._metrics.values())
    for m in metrics:
        if isinstance(m, (Counter, Gauge)):
            out[m.name] = out.get(m.name, 0.0) + float(m.value)
    return out


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where the backend
    reports none, as the CPU does)."""
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def host_peak_gb() -> float:
    """Peak resident memory of this process (GB): the chip's host is small."""
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def start_trace(logdir: str) -> None:
    """Device tracing only: with the Python tracer on, a few seconds of a
    trainer's loader threads fill the host's memory (40 GiB met on the first
    chip call of PR 23)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(logdir, profiler_options=options)


def stop_trace(logdir: str) -> Optional[str]:
    """Stop the profiler; path of the ``.xplane.pb`` it wrote."""
    import glob
    import os

    import jax

    jax.profiler.stop_trace()
    found = sorted(glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return found[-1] if found else None
