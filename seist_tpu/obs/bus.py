"""Process-wide metrics bus: counters / gauges / histograms, a span API
for timing phases, Prometheus text exposition, and a structured JSONL
event log.

PR 2-5 grew five disconnected telemetry surfaces (worker epoch log lines,
``io_guard.COUNTERS``, serve's JSON ``/metrics``, BENCH sections, the
quarantine report). This module is the ONE registry they all publish to,
in the shape a production JAX training stack needs (t5x's metrics/summary
bus, arXiv:2203.17189, is the blueprint):

* :class:`MetricsBus` — name+label keyed :class:`Counter` / :class:`Gauge`
  / :class:`Histogram` registry. ``BUS`` is the process singleton.
* **Span API** — ``with BUS.span("checkpoint/save"):`` times a phase on
  ``time.monotonic()`` (NTP-step safe), feeds a ``<name>_ms`` histogram,
  and fans out to registered sinks (the flight recorder rides this).
  ``BUS.begin(name)`` is the explicit-stop form for phases that don't
  nest as a ``with`` block (epoch timing in the worker loop). This is THE
  repo's interval-timing primitive: ``utils/profiling.stopwatch`` and
  ``StepTimeSplit`` delegate here, and jaxlint's ``wallclock-interval``
  rule keeps ad-hoc ``time.time()`` pairs from growing back.
* **Collectors** — scrape-time callables (io_guard counters, serve
  batcher stats, loader counters) so sources that already keep their own
  thread-safe state publish without double bookkeeping.
* :func:`render_prometheus` — text exposition (version 0.0.4) of the
  whole bus, served by ``obs/http.py`` on the train worker's
  ``--metrics-port`` and by serve's ``/metrics?format=prometheus``.
* :class:`EventLog` — append-only JSONL of structured events (epoch
  summaries, rollbacks, quarantines, deaths) for reconstructing a
  days-long supervised run after the fact.

Hot-path cost: one span is two ``monotonic()`` calls, a TraceMe (a flag
test outside a profiler capture), a push and a pop on the thread's span
stack, one dict lookup and one locked histogram observe — microseconds,
benched in the BENCH ``step_breakdown.telemetry`` section at <1% of step
time.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

from seist_tpu.utils.meters import LATENCY_BOUNDS_MS, LatencyHistogram

#: Default histogram bounds for span durations (ms) — reuse the serve
#: latency ladder; spans range from sub-ms host waits to multi-second
#: checkpoint saves, the same span.
SPAN_BOUNDS_MS = LATENCY_BOUNDS_MS

_LabelKey = Tuple[Tuple[str, str], ...]


def _label_key(labels: Dict[str, Any]) -> _LabelKey:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def monotonic() -> float:
    """The bus clock. One indirection point so every interval in the repo
    reads the same monotonic source (jaxlint wallclock-interval rationale:
    a wall-clock step must never corrupt a measured duration)."""
    return time.monotonic()


@contextlib.contextmanager
def stopwatch() -> Iterator[Callable[[], float]]:
    """``with stopwatch() as elapsed:`` — ``elapsed()`` returns seconds
    since entry, inside the block and after exit. The primitive behind
    ``utils/profiling.stopwatch`` (kept importable from there) and the
    span API; not registered on any bus."""
    t0 = monotonic()
    done: List[float] = []

    def elapsed() -> float:
        return (done[0] if done else monotonic()) - t0

    try:
        yield elapsed
    finally:
        done.append(monotonic())


class Counter:
    """Monotonic counter (Prometheus ``counter``)."""

    __slots__ = ("name", "labels", "_value", "_lock")

    def __init__(self, name: str, labels: Dict[str, str]):
        self.name = name
        self.labels = labels
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Gauge:
    """Last-write-wins instantaneous value (Prometheus ``gauge``)."""

    __slots__ = ("name", "labels", "_value", "_lock")

    def __init__(self, name: str, labels: Dict[str, str]):
        self.name = name
        self.labels = labels
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class Histogram(LatencyHistogram):
    """Bus-registered fixed-bucket histogram. The implementation IS
    ``utils.meters.LatencyHistogram`` (serve's /metrics payload keeps its
    exact shape); this subclass only adds the registry identity and the
    cumulative-bucket view Prometheus exposition needs."""

    def __init__(
        self,
        name: str,
        labels: Dict[str, str],
        bounds: Sequence[float] = SPAN_BOUNDS_MS,
    ):
        super().__init__(bounds=bounds)
        self.name = name
        self.labels = labels


_SPAN_STACKS = threading.local()


def _open_spans() -> List["Span"]:
    """The calling thread's stack of open spans, innermost last."""
    stack = getattr(_SPAN_STACKS, "stack", None)
    if stack is None:
        stack = _SPAN_STACKS.stack = []
    return stack


@contextlib.contextmanager
def span_frame() -> Iterator[None]:
    """Whatever spans the block opens on this thread and never ends (a
    preempt exit inside an epoch, an early stop) are dropped from the
    thread's stack when the block ends, so that they are not taken for
    the parents of the next run's spans."""
    stack = _open_spans()
    depth = len(stack)
    try:
        yield
    finally:
        del stack[depth:]


def _trace_annotation(name: str, labels: Dict[str, str]):
    """An entered ``jax.profiler.TraceAnnotation``, or None in a process
    that has not loaded jax (the model-free serving front tier never does,
    and no profiler capture can run there). Outside a capture a TraceMe is
    a flag test."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    annotation = jax.profiler.TraceAnnotation(name, **labels)
    annotation.__enter__()
    return annotation


class Span:
    """One timed phase. Context manager (``with bus.span(...)``) or
    explicit form (``s = bus.begin(...)``, later ``s.end()``).
    ``duration_s`` is available after exit/end.

    ``start`` is the span's start on the bus clock and ``parent`` the name
    of the span that was open around it on its thread: with both, a sink
    has the tree, and a span's self time is its duration less its
    children's. For its lifetime the span is also a
    ``jax.profiler.TraceAnnotation`` of its own name (labels as arguments),
    so any profiler capture shows the program's spans on ``/host:CPU``, on
    the device's clock."""

    __slots__ = ("name", "labels", "start", "parent", "duration_s",
                 "_bus", "_stack", "_annotation")

    def __init__(self, bus: "MetricsBus", name: str, labels: Dict[str, str]):
        self.name = name
        self.labels = labels
        self._bus = bus
        self._stack = _open_spans()
        self.parent: Optional[str] = (
            self._stack[-1].name if self._stack else None
        )
        self._stack.append(self)
        self.duration_s: Optional[float] = None
        self._annotation = _trace_annotation(name, labels)
        self.start = monotonic()

    def end(self) -> float:
        """Stop the clock, record on the bus, return elapsed seconds.
        Idempotent: a second end() returns the first duration."""
        if self.duration_s is None:
            self.duration_s = monotonic() - self.start
            if self._annotation is not None:
                self._annotation.__exit__(None, None, None)
            # This span and whatever was opened under it and never ended
            # leave the stack (a span_frame may have dropped it already).
            try:
                del self._stack[self._stack.index(self):]
            except ValueError:
                pass
            self._bus._record_span(self)
        return self.duration_s

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.end()


class MetricsBus:
    """Name+label keyed metric registry + span fan-out + collectors."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: Dict[Tuple[str, _LabelKey], Any] = {}
        self._collectors: Dict[str, Callable[[], Dict[str, Any]]] = {}
        self._span_sinks: List[Callable[[Span], None]] = []

    # ------------------------------------------------------------ metrics
    def _get(self, cls, name: str, labels: Dict[str, Any], **kw) -> Any:
        key = (name, _label_key(labels))
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = cls(name, {k: str(v) for k, v in labels.items()}, **kw)
                self._metrics[key] = m
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric '{name}' already registered as "
                    f"{type(m).__name__}, not {cls.__name__}"
                )
            return m

    def counter(self, name: str, **labels) -> Counter:
        return self._get(Counter, name, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get(Gauge, name, labels)

    def histogram(
        self, name: str, bounds: Sequence[float] = SPAN_BOUNDS_MS, **labels
    ) -> Histogram:
        return self._get(Histogram, name, labels, bounds=bounds)

    # -------------------------------------------------------------- spans
    def span(self, name: str, **labels) -> Span:
        """Start a span now; use as a context manager."""
        return Span(self, name, labels)

    # Alias for the explicit begin/end form (same object, reads better at
    # call sites that can't nest a with-block around the phase).
    begin = span

    def _record_span(self, span: Span) -> None:
        self.histogram(f"{span.name}_ms", **span.labels).observe(
            (span.duration_s or 0.0) * 1e3
        )
        # Snapshot under the lock: install()/remove_span_sink mutate the
        # list from other threads (flight-recorder swap on a death path),
        # and iterating a list being resized raises mid-span.
        with self._lock:
            sinks = list(self._span_sinks)
        for sink in sinks:
            try:
                sink(span)
            except Exception:  # noqa: BLE001 - a sick sink (e.g. a closed
                # flight recorder) must never break the timed code path
                pass

    def add_span_sink(self, sink: Callable[[Span], None]) -> None:
        with self._lock:
            if sink not in self._span_sinks:
                self._span_sinks.append(sink)

    def remove_span_sink(self, sink: Callable[[Span], None]) -> None:
        with self._lock:
            if sink in self._span_sinks:
                self._span_sinks.remove(sink)

    # --------------------------------------------------------- collectors
    def register_collector(
        self,
        key: str,
        fn: Callable[[], Dict[str, Any]],
        name: Optional[str] = None,
        **labels,
    ) -> None:
        """Register a scrape-time source. ``fn`` returns a (possibly
        nested) dict of numbers; keys re-registering replace the previous
        collector (a fresh serve batcher supersedes a drained one).
        ``name`` overrides the metric-name prefix (default: the key), so
        per-instance keys can share one metric family distinguished by
        ``labels`` (serve batchers: one family, ``model=...`` labels)."""
        with self._lock:
            self._collectors[key] = (
                fn,
                {k: str(v) for k, v in labels.items()},
                name or key,
            )

    def unregister_collector(
        self, key: str, fn: Optional[Callable[[], Dict[str, Any]]] = None
    ) -> None:
        """Remove a collector. With ``fn``, remove only if the registered
        callable is still that one — a replaced instance's late shutdown
        must not tear down its successor's registration."""
        with self._lock:
            cur = self._collectors.get(key)
            if cur is None:
                return
            if fn is not None and cur[0] != fn:
                return
            self._collectors.pop(key, None)

    def _collect(self) -> List[Tuple[str, Dict[str, str], float]]:
        """Flattened collector samples: (name, labels, value)."""
        with self._lock:
            collectors = dict(self._collectors)
        out: List[Tuple[str, Dict[str, str], float]] = []
        for key, (fn, labels, name) in collectors.items():
            try:
                data = fn()
            except Exception:  # noqa: BLE001 - one sick collector must not
                # take down the whole scrape
                continue
            for sample_name, value in _flatten(name, data):
                out.append((sample_name, labels, value))
        return out

    # ----------------------------------------------------------- snapshot
    def snapshot(self) -> Dict[str, Any]:
        """JSON-able view of everything on the bus (the /metrics.json
        payload and the flight recorder's final-state stamp). Histogram
        entries carry their raw buckets (``bounds`` + ``bucket_counts``)
        on top of the summary so the fleet aggregator (obs/fleet.py) can
        merge replicas' histograms bucket-wise instead of averaging
        percentiles (which is statistically meaningless)."""
        with self._lock:
            metrics = list(self._metrics.values())
        out: Dict[str, Any] = {"counters": {}, "gauges": {}, "histograms": {}}
        for m in metrics:
            label_sfx = _label_suffix(m.labels)
            if isinstance(m, Counter):
                out["counters"][m.name + label_sfx] = m.value
            elif isinstance(m, Gauge):
                out["gauges"][m.name + label_sfx] = m.value
            elif isinstance(m, Histogram):
                entry = m.summary()
                bounds, counts, _, total_sum = m.buckets()
                entry["bounds"] = bounds
                entry["bucket_counts"] = counts
                entry["sum"] = total_sum
                out["histograms"][m.name + label_sfx] = entry
        out["collectors"] = {
            name + _label_suffix(labels): value
            for name, labels, value in self._collect()
        }
        return out

    def reset(self) -> None:
        """Drop every metric, collector and sink — test isolation only."""
        with self._lock:
            self._metrics.clear()
            self._collectors.clear()
            self._span_sinks.clear()


def _flatten(prefix: str, data: Any) -> List[Tuple[str, float]]:
    out: List[Tuple[str, float]] = []
    if isinstance(data, dict):
        for k, v in data.items():
            out.extend(_flatten(f"{prefix}_{k}", v))
    elif isinstance(data, bool):
        out.append((prefix, 1.0 if data else 0.0))
    elif isinstance(data, (int, float)):
        out.append((prefix, float(data)))
    # non-numeric leaves (strings, lists) are dropped: Prometheus samples
    # are numbers; the JSON snapshot keeps structure via the collectors'
    # own surfaces.
    return out


# ------------------------------------------------------------- exposition
def _sanitize(name: str) -> str:
    return "".join(
        c if (c.isalnum() or c == "_") else "_" for c in name
    ).strip("_") or "metric"


def _label_suffix(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
    return "{" + inner + "}"


def _prom_labels(labels: Dict[str, str], extra: str = "") -> str:
    parts = [
        f'{_sanitize(k)}="{_escape(v)}"' for k, v in sorted(labels.items())
    ]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def _escape(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def render_prometheus(bus: MetricsBus, prefix: str = "seist") -> str:
    """Prometheus text exposition (format 0.0.4) of the whole bus:
    registered metrics plus scrape-time collector samples. Histograms
    emit cumulative ``_bucket{le=...}`` series, ``_sum`` and ``_count``
    per the exposition format."""
    lines: List[str] = []
    typed: Dict[str, str] = {}

    def emit(name: str, mtype: str, labels: Dict[str, str], value: float,
             extra_label: str = "") -> None:
        full = f"{prefix}_{_sanitize(name)}"
        if typed.get(full) is None:
            lines.append(f"# TYPE {full} {mtype}")
            typed[full] = mtype
        lines.append(f"{full}{_prom_labels(labels, extra_label)} {_fmt(value)}")

    with bus._lock:
        metrics = list(bus._metrics.values())
    for m in metrics:
        if isinstance(m, Counter):
            emit(m.name + "_total", "counter", m.labels, m.value)
        elif isinstance(m, Gauge):
            emit(m.name, "gauge", m.labels, m.value)
    for m in metrics:
        if not isinstance(m, Histogram):
            continue
        bounds, counts, total, total_sum = m.buckets()
        full = f"{prefix}_{_sanitize(m.name)}"
        if typed.get(full) is None:
            lines.append(f"# TYPE {full} histogram")
            typed[full] = "histogram"
        cum = 0
        for bound, c in zip(bounds, counts[:-1]):
            cum += c
            le = 'le="' + _fmt(bound) + '"'
            lines.append(f"{full}_bucket{_prom_labels(m.labels, le)} {cum}")
        inf = 'le="+Inf"'
        lines.append(f"{full}_bucket{_prom_labels(m.labels, inf)} {total}")
        lines.append(f"{full}_sum{_prom_labels(m.labels)} {_fmt(total_sum)}")
        lines.append(f"{full}_count{_prom_labels(m.labels)} {total}")
    # Collector samples are untyped (source decides semantics; most are
    # monotonic counters already named *_total-compatible).
    for name, labels, value in bus._collect():
        full = f"{prefix}_{_sanitize(name)}"
        if typed.get(full) is None:
            lines.append(f"# TYPE {full} untyped")
            typed[full] = "untyped"
        lines.append(f"{full}{_prom_labels(labels)} {_fmt(value)}")
    return "\n".join(lines) + "\n"


def _fmt(v: float) -> str:
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


# --------------------------------------------------------------- event log
class EventLog:
    """Append-only JSONL of structured events. One line per event:
    ``{"t": <unix seconds>, "event": <kind>, ...fields}`` — ``t`` is a
    reported timestamp (wall clock is correct here; intervals come from
    spans). Writes are line-buffered and fsync-free: the log is forensic
    context, not a durability contract (the flight recorder dump is the
    crash artifact)."""

    def __init__(self, path: str):
        self.path = path
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._lock = threading.Lock()
        self._f = open(path, "a", buffering=1)

    def emit(self, event: str, **fields) -> None:
        rec = {"t": round(time.time(), 3), "event": event}
        rec.update(fields)
        try:
            line = json.dumps(rec, default=str)
        except (TypeError, ValueError):
            line = json.dumps({"t": rec["t"], "event": event,
                               "error": "unserializable fields"})
        with self._lock:
            if not self._f.closed:
                self._f.write(line + "\n")

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.close()


def timed_iter(iterator, name: str, bus: Optional[MetricsBus] = None, **labels):
    """Wrap an iterator so every ``next()`` is a recorded span — the
    worker loops' host-wait measurement (``host_wait_ms``), replacing the
    ad-hoc ``time.monotonic()`` pairs. Composes outside
    ``io_guard.watch`` (the watchdog arms inside; its arm/disarm costs
    nanoseconds against a real batch wait)."""
    bus = bus if bus is not None else BUS
    it = iter(iterator)
    while True:
        sp = bus.span(name, **labels)
        try:
            item = next(it)
        except StopIteration:
            return  # the end-of-iterator probe is not a batch wait
        sp.end()
        yield item


# ------------------------------------------------------------- process bus
BUS = MetricsBus()


def register_default_collectors(bus: Optional[MetricsBus] = None) -> None:
    """Attach the repo's standing sources to ``bus`` (idempotent): the
    data-plane I/O-guard counters (via ``ops.metrics.data_plane_counters``
    so there is ONE reader of ``io_guard.COUNTERS``)."""
    bus = bus if bus is not None else BUS

    def _data_plane() -> Dict[str, int]:
        from seist_tpu.ops.metrics import data_plane_counters

        return data_plane_counters()

    bus.register_collector("data_plane", _data_plane)
