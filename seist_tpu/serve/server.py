"""Online inference service: micro-batched /predict, streaming /annotate,
health + metrics — stdlib HTTP only (http.server), no new dependencies.

Layering:

* :class:`ServeService` — transport-free core (also the in-process test
  client): model pool + one MicroBatcher per model + counters. Single
  fixed-window traces go through the batcher; long records go through
  ``ops/stream.annotate`` driving the SAME warm per-bucket forward
  (``jitted=True``, ``batch_size=largest bucket``), so the expensive
  model forward never compiles after warm-up. (The lightweight
  stitch/pick programs in /annotate still compile once per new record
  length — small, host-bound, and amortized across same-length records.)
* :class:`ServeHTTPServer` + handler — a thin JSON shim: ServeError
  subclasses carry their own HTTP status (429 queue-full backpressure,
  504 deadline, 503 draining, 400/404 client errors).

Endpoints::

    POST /predict       one (window, C) trace -> picks / regression / class
    POST /annotate      one (L >= window, C) record -> picks over the record
    POST /stream        one station packet into a long-lived StreamSession;
                        picks stream out as they become final, network
                        alerts ride along (docs/SERVING.md "Streaming
                        inference")
    POST /admin/reload  hot-swap a new checkpoint behind the full gate
                        ladder (docs/SERVING.md "Live rollout")
    GET  /healthz       liveness + model list + per-entry version/variants
    GET  /metrics       queue depth, batch-fill ratio, latency histograms
    GET  /stream/alerts recent cross-station association alerts + mux stats

CLI: ``python main.py serve --model seist_s_dpk=CKPT --port 8080 ...``
(see ``main()``); ``make serve-smoke`` runs the no-checkpoint smoke.
"""

from __future__ import annotations

import argparse
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from seist_tpu.obs import flight as obs_flight
from seist_tpu.obs import trace as obs_trace
from seist_tpu.serve.batcher import BatcherConfig, MicroBatcher
from seist_tpu.serve.pool import ModelPool, decode_outputs
from seist_tpu.serve.protocol import (
    PRIORITIES,
    BadRequest,
    DeadlineExceeded,
    Overloaded,
    PredictOptions,
    QueueFull,
    ReloadFailed,
    ServeError,
    ShuttingDown,
    json_bytes,
    parse_body,
    parse_station,
    parse_tasks,
    parse_waveform,
)
from seist_tpu.serve.shed import AdmissionController, ShedConfig
from seist_tpu.utils.faults import ServeFaultInjector, stream_faults
from seist_tpu.utils.logger import logger
from seist_tpu.utils.meters import LatencyHistogram

MAX_BODY_BYTES = 64 * 1024 * 1024  # one hours-long fp32 record is ~tens of MB

_NORM_MODES = ("std", "max", "absmax", "")

# Clean-preempt exit code (sysexits EX_TEMPFAIL), shared with the train
# plane: a SIGTERM'd replica drains and exits 75, telling its supervisor
# (tools/supervise_fleet.py) "managed drain — relaunch immediately, budget
# untouched". Kept in sync with seist_tpu.train.checkpoint.PREEMPT_EXIT_CODE
# by tests/test_serve_fleet.py (checkpoint.py drags orbax in; a serve
# replica should not pay that import).
PREEMPT_EXIT_CODE = 75

#: replica lifecycle as a scrapeable gauge (serve_state_code): the
#: warming -> ok -> draining state machine the router's health probes,
#: the flight recorder and events.jsonl all see identically.
STATE_CODES = {"dead": 0, "warming": 1, "ok": 2, "draining": 3}


class _BadCandidate(ServeError):
    """SEIST_FAULT_SERVE_BAD_CANDIDATE chaos verdict: this replica is
    deliberately serving a "bad" model version, so its /predict errors —
    the elevated-error-rate signal the router's canary auto-rollback
    must catch. 500: the router classifies it retryable + breaker
    failure, exactly like a genuine candidate regression."""

    status = 500
    code = "bad_candidate"


class ServeService:
    """Transport-free serving core; every public method raises ServeError
    subclasses on failure and returns JSON-able dicts on success."""

    def __init__(
        self,
        pool: ModelPool,
        batcher_config: Optional[BatcherConfig] = None,
        warmup_async: bool = False,
        shed_config: Optional[ShedConfig] = None,
        event_log: Optional[Any] = None,  # obs.EventLog
        faults: Optional[ServeFaultInjector] = None,
        stream_config: Optional[Dict[str, Any]] = None,
    ):
        self.pool = pool
        self.config = batcher_config or BatcherConfig()
        self.buckets = self.config.resolved_buckets()
        self.shed_config = shed_config or ShedConfig()
        self._event_log = event_log
        # Serving-plane fault injection (SEIST_FAULT_SERVE_*): inert
        # unless the env schedules a fault targeting this replica.
        self._faults = faults if faults is not None else (
            ServeFaultInjector.from_env()
        )
        # One batcher per (entry, enabled variant): requests batch by
        # TRUNK INPUT SHAPE within a variant (a bf16 program cannot serve
        # an fp32 request), task-blind — a group's dpk+emg+dis traffic
        # coalesces into the same flushes. The fp32 batcher keeps the
        # bare model name (wire/metrics back-compat); other variants are
        # keyed "<model>@<variant>". The forward closes over the entry
        # NAME, not the entry object: each flush resolves the entry from
        # the pool, so a hot reload (/admin/reload swapping the pool
        # slot) takes effect at the very next flush with no batcher
        # restart — the hot-swap seam.
        self._batchers: Dict[str, MicroBatcher] = {}
        self._shedders: Dict[str, AdmissionController] = {}
        self._reload_lock = threading.Lock()
        for name in pool.names():
            entry = pool.get(name)
            entry_batchers = []
            # getattr defaults keep bare-namespace test pools (see
            # watch_until_shutdown) and pre-variant entries working.
            for variant in getattr(entry, "variants", ("fp32",)):
                key = name if variant == "fp32" else f"{name}@{variant}"
                self._batchers[key] = MicroBatcher(
                    self._make_forward(name, variant), self.config, name=key
                )
                entry_batchers.append(self._batchers[key])
            # Tiered admission gate per model, fed by the worst
            # queue-delay estimate across its variant batchers
            # (serve/shed.py): overload on any variant sheds the entry.
            self._shedders[name] = AdmissionController(
                lambda _bs=tuple(entry_batchers): max(
                    b.queue_delay_ms() for b in _bs
                ),
                self.shed_config,
                model=name,
            )
        self._annotate_locks = {n: threading.Lock() for n in pool.names()}
        # /stream: one StationMux (sessions + associator) per picking
        # model, created lazily on the first stream request for that
        # model — see _stream_mux_for for the config-freeze contract.
        self._stream_config = dict(stream_config or {})
        self._stream_muxes: Dict[str, Any] = {}
        self._stream_lock = threading.Lock()
        # Streaming-plane fault injection (SEIST_FAULT_STREAM_*): the
        # module singleton so journal.py's corrupt hook and the /stream
        # kill share one stamp. Reorder faults hold a packet here until
        # the station's next one arrives (delivered late -> stale seq).
        self._stream_faults = stream_faults()
        self._held_packets: Dict[Any, Any] = {}
        self.annotate_latency_ms = LatencyHistogram()
        self._lock = threading.Lock()
        self._requests = {"predict": 0, "annotate": 0, "stream": 0}
        self._annotate_windows = 0
        # monotonic: _started_at only ever feeds uptime_s intervals, and a
        # wall-clock step must not make uptime jump (or go negative).
        self._started_at = time.monotonic()
        self._draining = False
        # Readiness gate: /healthz/ready reports 503 while the pool is
        # still pre-compiling (warmup_async=True lets the HTTP socket come
        # up first so orchestrators can probe during the compile) and
        # during SIGTERM drain. Requests arriving while warming are still
        # served — they just pay the compile — so readiness is advisory,
        # exactly what a load balancer wants.
        self._warming = True
        self._warmup_error: Optional[BaseException] = None
        self._last_state: Optional[str] = None
        # Metrics-bus collector (obs/bus.py): the request/annotate half
        # of metrics(); batchers self-register their own. One key per
        # service — a restarted service replaces its predecessor.
        from seist_tpu.obs.bus import BUS

        BUS.register_collector("serve", self._bus_metrics)
        self.publish_state("startup")
        if warmup_async:
            threading.Thread(
                target=self._run_warmup, name="serve-warmup", daemon=True
            ).start()
        else:
            self._run_warmup()
            if self._warmup_error is not None:
                raise self._warmup_error  # sync path keeps crashing loudly

    def _make_forward(self, name: str, variant: str):
        """Flush-time forward for one (entry, variant) batcher. Resolves
        the entry from the pool PER FLUSH (hot reload swaps the pool
        slot; in-flight flushes keep the entry they already grabbed) and
        dispatches by its capabilities."""
        injector = self._faults

        def batched_forward(batch, tasks=None, _n=name, _v=variant,
                            _inj=injector):
            entry = self.pool.get(_n)
            # Injected model slowness runs IN the flush thread, so
            # queued requests age exactly as behind a slow device.
            _inj.forward_delay()
            if getattr(entry, "is_group", False):
                return entry.fanout(batch, sorted(tasks or entry.tasks), _v)
            if hasattr(entry, "run"):
                return entry.run(batch, _v)
            # bare forward-only entry (test doubles)
            import jax.numpy as jnp

            return entry.forward(jnp.asarray(batch))

        return batched_forward

    def _run_warmup(self) -> None:
        try:
            self.pool.warmup(self.buckets)
            self._warming = False
            self.publish_state("warmup_done")
        except BaseException as e:  # noqa: BLE001
            # A failed warm-up (compile OOM, bad bucket, XLA error) must
            # never flip the service to ready: record it so liveness goes
            # false and the watchdog exits non-zero — the async
            # equivalent of the sync path's crash.
            self._warmup_error = e
            logger.warning(f"[serve] warm-up failed: {e!r}")
            self.publish_state("warmup_failed")

    # ------------------------------------------------------ lifecycle state
    def publish_state(self, reason: str = "") -> None:
        """Publish the replica lifecycle state machine (warming -> ok ->
        draining, or -> dead) everywhere an observer might look: a bus
        gauge (``serve_state_code``, scraped by Prometheus and the
        router's operators), a structured ``events.jsonl`` event, and the
        flight recorder ring when one is installed — one state machine,
        three views (docs/SERVING.md). Transition-edge-triggered: calling
        it redundantly is free."""
        state = self._state_str()
        with self._lock:
            if state == self._last_state:
                return
            prev, self._last_state = self._last_state, state
        from seist_tpu.obs import flight
        from seist_tpu.obs.bus import BUS

        BUS.gauge("serve_state_code").set(STATE_CODES.get(state, 0))
        if self._event_log is not None:
            self._event_log.emit(
                "serve_state", state=state, prev=prev, reason=reason
            )
        rec = flight.get()
        if rec is not None:
            rec.record_event("serve_state", state=state, prev=prev,
                             reason=reason)
        logger.info(
            f"[serve] state {prev or 'start'} -> {state}"
            + (f" ({reason})" if reason else "")
        )

    # ----------------------------------------------------------- predict
    def _batcher_for(self, name: str, variant: str) -> MicroBatcher:
        return self._batchers[
            name if variant == "fp32" else f"{name}@{variant}"
        ]

    def _check_variant(self, entry: Any, variant: str, tasks: Any) -> None:
        if variant == "fp32":
            return
        if variant not in getattr(entry, "variants", ("fp32",)):
            # Never loaded — no batcher, no programs: always a 400.
            raise BadRequest(
                f"variant '{variant}' is not loaded for model "
                f"'{entry.name}' (serve --variants); loaded: "
                f"{list(getattr(entry, 'variants', ('fp32',)))}"
            )
        if self._warming:
            # Parity gates are computed by the (async) warm-up; a loaded
            # variant must not bounce 400 during the warm-up window when
            # the documented pre-warm fallback can serve it — the same
            # contract fp32 traffic gets. Gate verdicts apply once warm.
            return
        supported = entry.supported_variants(tasks)
        if variant not in supported:
            raise BadRequest(
                f"variant '{variant}' is not served for this request "
                f"(model '{entry.name}'"
                + (f", tasks {list(tasks)}" if tasks else "")
                + f"); available: {supported} — variants are enabled at "
                "load (serve --variants) and parity-gated against fp32"
            )

    def predict(
        self,
        data: Any,
        model: Optional[str] = None,
        options: Optional[Dict[str, Any]] = None,
        tasks: Optional[Any] = None,
        station: Optional[Any] = None,
        trace: Optional[obs_trace.RequestTrace] = None,
    ) -> Dict[str, Any]:
        """One fixed-window trace through the micro-batcher.

        ``station`` (optional ``{"id", "network", "lat", "lon"}``):
        provenance metadata, validated and echoed back verbatim so a
        caller fanning one response out into a catalog keeps the trace's
        origin without a side channel (the same block /stream requires).

        ``tasks`` (multi-task groups only): which heads to answer with —
        the shared trunk runs ONCE and fans out to all of them
        (serve/pool.MultiTaskEntry); default is every task the group
        serves. Single-task models keep the PR 1 request/response shape
        byte-for-byte.

        ``trace`` (obs/trace.RequestTrace, minted by the HTTP handler
        from the request's ``traceparent``): every stage of this method
        becomes a child span — admission (with the shed verdict), parse,
        normalize, the batcher's queue wait + device forward, decode —
        so a slow request decomposes instead of being one opaque number."""
        if self._draining:
            raise ShuttingDown("service is draining")
        t = obs_trace.ensure(trace)
        entry = self.pool.get(model)
        version = int(getattr(entry, "version", 0) or 0)
        opts = PredictOptions.from_dict(options)
        req_tasks = entry.resolve_tasks(parse_tasks(tasks))
        station_meta = parse_station(station)
        self._check_variant(entry, opts.variant, req_tasks)
        t.annotate(model=entry.name, variant=opts.variant,
                   tier=opts.priority, version=version)
        if self._faults.is_bad_candidate(version):
            raise _BadCandidate(
                f"model '{entry.name}' version {version} is the injected "
                "bad candidate (SEIST_FAULT_SERVE_BAD_CANDIDATE)"
            )
        # Request arrival: count, fire any scheduled serving fault
        # (SIGKILL at request k / black-hole window), then the admission
        # gate — shedding happens BEFORE the expensive waveform parse, so
        # an overloaded replica spends no decode work on a request it is
        # about to drop.
        with self._lock:
            self._requests["predict"] += 1
            n_request = self._requests["predict"]
        self._faults.on_request(n_request)
        with t.span("admission", tier=opts.priority) as sp:
            try:
                self._shedders[entry.name].admit(opts.priority)
            except Overloaded as e:
                # The shed verdict rides the trace (and the tail
                # retention always keeps shed traces).
                sp.annotate(verdict="shed",
                            retry_after_s=round(e.retry_after_s, 3))
                t.flag("shed")
                raise
            sp.annotate(verdict="admitted")
        with t.span("parse"):
            x = parse_waveform(data, entry.in_channels)
        if x.shape[0] > entry.window:
            raise BadRequest(
                f"trace length {x.shape[0]} > window {entry.window}; "
                "use POST /annotate for long records"
            )
        with t.span("normalize"):
            x = _normalize_trace(x, opts.norm_mode)
            n_real = x.shape[0]
            if n_real < entry.window:  # pad AFTER normalize: zeros stay 0
                pad = np.zeros(
                    (entry.window - n_real, x.shape[1]), dtype=x.dtype
                )
                x = np.concatenate([x, pad], axis=0)
        raw = self._batcher_for(entry.name, opts.variant).submit(
            x,
            timeout_ms=opts.timeout_ms,
            rank=PRIORITIES[opts.priority],
            tasks=frozenset(req_tasks) if req_tasks is not None else None,
            trace=trace,
        )
        fs = float(opts.sampling_rate)
        if req_tasks is not None:  # multi-task group: one entry per head
            per_task: Dict[str, Any] = {}
            with t.span("decode", heads=",".join(req_tasks)):
                for tk in req_tasks:
                    # The flush may have computed the UNION of coalesced
                    # requests' tasks; decode only what THIS caller asked.
                    r = decode_outputs(entry.heads[tk], raw[tk], opts)
                    if n_real < entry.window:
                        _clip_picks(r, n_real, fs)
                    per_task[tk] = r
            out = {
                "model": entry.name,
                # Which checkpoint generation answered — the rollout
                # acceptance signal (bench_serve by_version accounting).
                "model_version": version,
                "tasks": per_task,
                # The fan-out contract, observable per response: all
                # heads above came from ONE trunk execution.
                "trunk_runs": 1,
                "variant": opts.variant,
            }
            if station_meta is not None:
                out["station"] = station_meta
            return out
        with t.span("decode"):
            result = decode_outputs(entry, raw, opts)
        if n_real < entry.window:
            # The signal->zeros step at the padding boundary can fabricate
            # picks/detections inside samples the client never sent.
            _clip_picks(result, n_real, fs)
        result["model"] = entry.name
        result["model_version"] = version
        if station_meta is not None:
            result["station"] = station_meta
        return result

    # ---------------------------------------------------------- annotate
    def annotate(
        self,
        data: Any,
        model: Optional[str] = None,
        options: Optional[Dict[str, Any]] = None,
        trace: Optional[obs_trace.RequestTrace] = None,
    ) -> Dict[str, Any]:
        """A long (L >= window) record via sliding windows + stitching,
        reusing the pool's warm largest-bucket forward."""
        if self._draining:
            raise ShuttingDown("service is draining")
        t = obs_trace.ensure(trace)
        entry = self.pool.get(model)
        if not entry.is_picker:
            raise BadRequest(
                f"model '{entry.name}' is not a picking model; /annotate "
                "needs (non|det, ppk, spk) outputs"
            )
        opts = PredictOptions.from_dict(options)
        if opts.variant != "fp32":
            # /annotate is hardwired to the fp32 picking path; silently
            # serving fp32 against an explicit bf16/int8 request would
            # misreport which numerics answered.
            raise BadRequest(
                "variant selection is /predict-only; /annotate always "
                "runs fp32"
            )
        # Same tiered gate as /predict: an overloaded replica sheds
        # low-tier record backfill before paying the (large) record parse.
        with t.span("admission", tier=opts.priority) as sp:
            try:
                self._shedders[entry.name].admit(opts.priority)
            except Overloaded as e:
                sp.annotate(verdict="shed",
                            retry_after_s=round(e.retry_after_s, 3))
                t.flag("shed")
                raise
            sp.annotate(verdict="admitted")
        with t.span("parse"):
            record = parse_waveform(data, entry.in_channels)
        if record.shape[0] < entry.window:
            raise BadRequest(
                f"record length {record.shape[0]} < window {entry.window}; "
                "use POST /predict for single windows"
            )
        from seist_tpu.ops.stream import annotate as stream_annotate

        t0 = time.monotonic()
        lock = self._annotate_locks[entry.name]
        # One record at a time per model: annotate saturates the device by
        # itself; interleaving two would only thrash. The wait counts
        # against the request's own deadline.
        if not lock.acquire(timeout=opts.timeout_ms / 1000.0):
            raise DeadlineExceeded(
                f"/annotate queue wait exceeded {opts.timeout_ms:.0f} ms"
            )
        # Groups stream through trunk+dpk (the group's picking path);
        # single-task pickers through their warm AOT forward. Both hit
        # shapes compiled at warm-up (batch_size = largest bucket).
        forward = (
            entry.picker_forward
            if entry.is_group
            else (lambda x: entry.run(x, "fp32"))
        )
        try:
            with self._lock:
                self._requests["annotate"] += 1
            with t.span("stream", model=entry.name,
                        record_samples=int(record.shape[0])):
                picks = stream_annotate(
                    forward,
                    record,
                    window=entry.window,
                    stride=opts.stride or None,
                    batch_size=self.buckets[-1],
                    sampling_rate=opts.sampling_rate,
                    ppk_threshold=opts.ppk_threshold,
                    spk_threshold=opts.spk_threshold,
                    det_threshold=opts.det_threshold,
                    min_peak_dist=opts.min_peak_dist,
                    combine=opts.combine,
                    max_events=opts.record_max_events or None,
                    channel0=entry.channel0,
                    jitted=True,
                )
        finally:
            lock.release()
        self.annotate_latency_ms.observe((time.monotonic() - t0) * 1000.0)
        fs = float(opts.sampling_rate)
        from seist_tpu.ops.stream import window_offsets

        n_windows = len(
            window_offsets(
                record.shape[0], entry.window, opts.stride or entry.window // 2
            )
        )
        with self._lock:
            self._annotate_windows += n_windows
        return {
            "model": entry.name,
            "model_version": int(getattr(entry, "version", 0) or 0),
            "task": "picking",
            "record_samples": int(record.shape[0]),
            "windows": int(n_windows),
            "ppk": [
                {"sample": int(i), "time_s": round(int(i) / fs, 6)}
                for i in picks["ppk"]
            ],
            "spk": [
                {"sample": int(i), "time_s": round(int(i) / fs, 6)}
                for i in picks["spk"]
            ],
            "det": [
                {"onset": int(a), "offset": int(b),
                 "onset_s": round(int(a) / fs, 6),
                 "offset_s": round(int(b) / fs, 6)}
                for a, b in picks["det"]
            ],
        }

    # ------------------------------------------------------------- stream
    def _stream_mux_for(self, entry: Any, opts: PredictOptions) -> Any:
        """Lazy per-model StationMux (seist_tpu/stream). The mux — and
        every session it will ever open — is configured from the FIRST
        stream request's options plus the server-level stream_config, and
        frozen: a model's streaming tenant is one coherent pick/stitch
        config shared by the whole network (per-request knobs belong to
        /predict and /annotate). Later requests' session options are
        ignored."""
        name = entry.name
        with self._stream_lock:
            mux = self._stream_muxes.get(name)
            if mux is None:
                from seist_tpu.stream.assoc import AssocConfig, Associator
                from seist_tpu.stream.mux import MuxConfig, StationMux
                from seist_tpu.stream.session import SessionConfig

                sc = self._stream_config
                session = SessionConfig(
                    window=entry.window,
                    stride=opts.stride or entry.window // 2,
                    in_channels=entry.in_channels,
                    channel0=entry.channel0,
                    combine=opts.combine,
                    sampling_rate=opts.sampling_rate,
                    ppk_threshold=opts.ppk_threshold,
                    spk_threshold=opts.spk_threshold,
                    det_threshold=opts.det_threshold,
                    min_peak_dist=opts.min_peak_dist,
                )
                # Durability plane (docs/FAULT_TOLERANCE.md "Streaming
                # faults"): a shared journal_dir turns this replica into
                # a crash-survivable stream home — sessions journal
                # every journal_every_s, the associator WALs each alert
                # before a consumer can see it, and a restart (or a
                # failover survivor pointed at the same dir) seeds its
                # dedup window from the WAL so nothing double-alerts.
                journal_dir = sc.get("journal_dir") or None
                journal = None
                wal = None
                if journal_dir:
                    from seist_tpu.obs.trace import replica_suffix
                    from seist_tpu.stream.journal import (
                        AlertWAL,
                        StationJournal,
                    )

                    journal = StationJournal(str(journal_dir), model=name)
                    # Per-replica WAL file (the journal dir is shared by
                    # the fleet; alerts are per-associator and must not
                    # interleave across writers).
                    wal = AlertWAL(os.path.join(
                        str(journal_dir), name,
                        f"alerts{replica_suffix()}.wal",
                    ))
                assoc = Associator(AssocConfig(
                    window_s=float(sc.get("assoc_window_s", 30.0)),
                    min_stations=int(sc.get("assoc_min_stations", 4)),
                    velocity_kms=float(sc.get("assoc_velocity_kms", 6.0)),
                    tolerance_s=float(sc.get("assoc_tolerance_s", 2.0)),
                    grid_step_deg=float(
                        sc.get("assoc_grid_step_deg", 0.25)
                    ),
                    dedup_window_s=float(
                        sc.get("assoc_dedup_window_s", 2.0)
                    ),
                ), wal=wal)
                if wal is not None:
                    seeded = assoc.seed_from_wal()
                    if seeded:
                        logger.info(
                            f"[serve] stream '{name}': seeded "
                            f"{seeded} WAL alerts into dedup window"
                        )
                batcher = self._batcher_for(name, "fp32")
                timeout_ms = float(opts.timeout_ms)

                def submit(x, _b=batcher, _t=timeout_ms):
                    # Due windows ride the SAME warm fp32 bucket programs
                    # /predict runs, at alert rank — thousands of
                    # stations coalesce in the batcher's flushes with
                    # zero new compiles (tests/test_stream_mux.py pin).
                    return _b.submit(x, timeout_ms=_t,
                                     rank=PRIORITIES["alert"])

                mux = StationMux(
                    submit,
                    MuxConfig(
                        session=session,
                        max_stations=int(sc.get("max_stations", 4096)),
                        idle_timeout_s=float(
                            sc.get("idle_timeout_s", 900.0)
                        ),
                        journal_every_s=float(
                            sc.get("journal_every_s", 5.0)
                        ),
                        model=name,
                    ),
                    assoc=assoc,
                    journal=journal,
                )
                self._stream_muxes[name] = mux
            return mux

    @staticmethod
    def _synthetic_stream_result() -> Dict[str, Any]:
        """Feed-shaped success for a faulted (dropped/held) packet: the
        client sees a 200 with no picks, exactly what a swallowed packet
        looks like from outside."""
        return {
            "n_samples": 0,
            "windows": 0,
            "duplicate": False,
            "closed": False,
            "degraded": False,
            "dropped_windows": 0,
            "picks": {"ppk": [], "spk": [], "det": []},
            "alerts": [],
        }

    def stream(
        self,
        body: Dict[str, Any],
        trace: Optional[obs_trace.RequestTrace] = None,
    ) -> Dict[str, Any]:
        """One station packet into the long-lived streaming plane (``POST
        /stream``): route it to the station's StreamSession, run whatever
        windows fell due through the micro-batcher at alert rank, and
        return the picks that just became final plus any network alerts
        the associator raised. ``end=true`` flushes the tail window and
        closes the session. Packets are raw counts — the session applies
        the same per-window normalization /annotate uses, which is what
        makes its picks bit-identical to offline re-annotation."""
        if self._draining:
            raise ShuttingDown("service is draining")
        t = obs_trace.ensure(trace)
        entry = self.pool.get(body.get("model"))
        if not entry.is_picker:
            raise BadRequest(
                f"model '{entry.name}' is not a picking model; /stream "
                "needs (non|det, ppk, spk) outputs"
            )
        if getattr(entry, "is_group", False):
            raise BadRequest(
                f"model '{entry.name}' is a multi-task group; /stream "
                "serves single-task picking models"
            )
        options = dict(body.get("options") or {})
        # Streaming IS the early-warning path: default to the alert tier
        # (shed last, ride to the 429 bound) unless the caller says so.
        options.setdefault("priority", "alert")
        opts = PredictOptions.from_dict(options)
        if opts.variant != "fp32":
            raise BadRequest(
                "variant selection is /predict-only; /stream always "
                "runs fp32"
            )
        station = parse_station(body.get("station"), required=True)
        end = bool(body.get("end", False))
        seq = body.get("seq")
        if seq is not None and (isinstance(seq, bool)
                                or not isinstance(seq, int)):
            raise BadRequest("'seq' must be an integer")
        version = int(getattr(entry, "version", 0) or 0)
        t.annotate(model=entry.name, tier=opts.priority,
                   station=station["id"], version=version)
        with self._lock:
            self._requests["stream"] += 1
            n_request = self._requests["stream"]
        # Packet arrival: fire any scheduled stream fault (SIGKILL at
        # packet k) before admission — a mid-mainshock crash must not be
        # dodged by the shedder.
        self._stream_faults.on_packet(n_request)
        with t.span("admission", tier=opts.priority) as sp:
            try:
                # end=true RELEASES a station slot — always admitted
                # (serve/shed.py final-exemption contract).
                self._shedders[entry.name].admit(opts.priority, final=end)
            except Overloaded as e:
                sp.annotate(verdict="shed",
                            retry_after_s=round(e.retry_after_s, 3))
                t.flag("shed")
                raise
            sp.annotate(verdict="admitted")
        with t.span("parse"):
            if body.get("data") is None:
                if not end:
                    raise BadRequest(
                        "'data' is required unless end=true (a bare "
                        "end=true flushes and closes the session)"
                    )
                x = np.zeros((0, entry.in_channels), np.float32)
            else:
                x = parse_waveform(body.get("data"), entry.in_channels)
        mux = self._stream_mux_for(entry, opts)
        if n_request % 64 == 0:
            # Amortized housekeeping: sessions whose station went quiet
            # past idle_timeout_s are reaped on the request path itself.
            mux.reap_idle()
        from seist_tpu.stream.mux import MuxClosed, StationLimit

        # Packet fate (SEIST_FAULT_STREAM_{DROP,DUP,REORDER}_P): 'ok'
        # unless the chaos lane scheduled faults for this replica. A
        # dropped packet is swallowed server-side AFTER the client got
        # its 200 — the failure mode a transport ack cannot see, which
        # the session's gap-stitch must absorb. A reordered packet is
        # held and delivered after the station's next one; the plane
        # does not reassemble, so it arrives stale and degrades to
        # gap+duplicate (the documented semantics, now exercised).
        fate = "ok"
        if not end:
            fate = self._stream_faults.packet_fate(station["id"], seq)
        held_key = (entry.name, station["id"])
        try:
            with t.span("stream_feed", station=station["id"],
                        packet_samples=int(x.shape[0]), fate=fate):
                if fate == "drop":
                    t.flag("fault_drop")
                    result = self._synthetic_stream_result()
                elif fate == "reorder":
                    t.flag("fault_reorder")
                    with self._stream_lock:
                        prev_held = self._held_packets.pop(held_key, None)
                        self._held_packets[held_key] = (station, x, seq)
                    if prev_held is not None:
                        # Two holds in a row: deliver the older one now
                        # (still late) instead of losing it outright.
                        mux.feed(prev_held[0], prev_held[1],
                                 seq=prev_held[2], end=False)
                    result = self._synthetic_stream_result()
                else:
                    with self._stream_lock:
                        held = self._held_packets.pop(held_key, None)
                    if held is not None and end:
                        # Flush the held packet before the closing feed;
                        # after end the session is gone.
                        mux.feed(held[0], held[1], seq=held[2], end=False)
                        held = None
                    result = mux.feed(station, x, seq=seq, end=end)
                    if held is not None:
                        # Late delivery: stale seq -> idempotent drop.
                        mux.feed(held[0], held[1], seq=held[2], end=False)
                    if fate == "dup":
                        t.flag("fault_dup")
                        mux.feed(station, x, seq=seq, end=False)
        except StationLimit as e:
            # Same backpressure contract as a full queue: 429, back off.
            raise QueueFull(str(e)) from None
        except MuxClosed as e:
            # close_all() latched (SIGTERM drain): 503 so the router
            # retries this packet on a surviving replica, which restores
            # the station from its journal.
            raise ShuttingDown(str(e)) from None
        fs = float(mux.config.session.sampling_rate)
        picks = result["picks"]
        return {
            "model": entry.name,
            "model_version": version,
            "station": station,
            "n_samples": int(result["n_samples"]),
            "windows": int(result["windows"]),
            "duplicate": bool(result["duplicate"]),
            "closed": bool(result["closed"]),
            "degraded": bool(result["degraded"]),
            "dropped_windows": int(result["dropped_windows"]),
            "ppk": [
                {"sample": int(i), "time_s": round(int(i) / fs, 6)}
                for i in picks["ppk"]
            ],
            "spk": [
                {"sample": int(i), "time_s": round(int(i) / fs, 6)}
                for i in picks["spk"]
            ],
            "det": [
                {"onset": int(a), "offset": int(b),
                 "onset_s": round(int(a) / fs, 6),
                 "offset_s": round(int(b) / fs, 6)}
                for a, b in picks["det"]
            ],
            "alerts": result["alerts"],
        }

    def stream_alerts(self, n: int = 50) -> Dict[str, Any]:
        """``GET /stream/alerts``: recent association alerts + mux stats
        per streaming model — the downstream (alerting UI, twin gate)
        poll surface."""
        with self._stream_lock:
            muxes = dict(self._stream_muxes)
        return {
            "models": {
                name: {
                    "alerts": mux.assoc.recent_alerts(n),
                    "stats": mux.stats(),
                }
                for name, mux in muxes.items()
            },
        }

    # ------------------------------------------------------------- reload
    def reload(
        self,
        model: Optional[str] = None,
        checkpoint: Optional[str] = None,
        checkpoints: Optional[Dict[str, str]] = None,
        version: Optional[Any] = None,
    ) -> Dict[str, Any]:
        """Hot-swap one pool entry for a new checkpoint (``POST
        /admin/reload``). The candidate loads beside the incumbent,
        re-runs the full load-time gate ladder (AOT compile + variant
        parity + finite probe — serve/pool.ModelPool.reload), and only
        full success swaps; a failure leaves the incumbent serving and
        raises the structured error. The incumbent serves throughout —
        reload is invisible to in-flight traffic except as the
        ``model_version`` flip in responses."""
        if self._draining:
            raise ShuttingDown("service is draining; not accepting reloads")
        if self._warming:
            raise ReloadFailed(
                "initial warm-up still running; retry once /healthz/ready "
                "reports ready"
            )
        entry = self.pool.get(model)
        if checkpoint is not None and not isinstance(checkpoint, str):
            raise BadRequest("'checkpoint' must be a string path")
        if checkpoints is not None and not (
            isinstance(checkpoints, dict)
            and all(
                isinstance(k, str) and isinstance(v, str)
                for k, v in checkpoints.items()
            )
        ):
            raise BadRequest("'checkpoints' must be {task: ckpt} strings")
        if version is not None:
            try:
                version = int(version)
            except (TypeError, ValueError):
                raise BadRequest(
                    f"'version' must be an integer, got {version!r}"
                ) from None
        with self._reload_lock:  # one reload at a time per replica
            previous = int(getattr(entry, "version", 0) or 0)
            target = version if version is not None else previous + 1
            from seist_tpu.obs.bus import BUS

            t0 = time.monotonic()
            try:
                new_entry, report = self.pool.reload(
                    entry.name,
                    buckets=self.buckets,
                    checkpoint=checkpoint,
                    checkpoints=checkpoints,
                    version=target,
                    force_gate_failure=self._faults.is_bad_candidate(target),
                )
            except ServeError as e:
                BUS.counter(
                    "serve_reload_total", model=entry.name, outcome=e.code
                ).inc()
                if self._event_log is not None:
                    self._event_log.emit(
                        "serve_reload", model=entry.name, outcome=e.code,
                        version=target, error=str(e),
                    )
                raise
            reload_s = time.monotonic() - t0
            BUS.counter(
                "serve_reload_total", model=entry.name, outcome="ok"
            ).inc()
            if self._event_log is not None:
                self._event_log.emit(
                    "serve_reload", model=entry.name, outcome="ok",
                    version=target, previous_version=previous,
                    reload_s=round(reload_s, 3),
                )
            return {
                "model": entry.name,
                "version": target,
                "previous_version": previous,
                "variants": new_entry.supported_variants(),
                "programs": len(report),
                "reload_s": round(reload_s, 3),
            }

    # ------------------------------------------------------ health/metrics
    def alive(self) -> bool:
        """Liveness: warm-up didn't fail and every batcher flush thread
        is still running. Neither condition can recover — the server
        watchdog exits non-zero on this so the orchestrator restarts the
        process instead of leaving a zombie that black-holes requests."""
        return self._warmup_error is None and all(
            b.healthy for b in self._batchers.values()
        )

    def ready(self) -> bool:
        """Readiness: alive, warm-compiled, and not draining."""
        return self.alive() and not self._warming and not self._draining

    def _state_str(self) -> str:
        if not self.alive():
            return "dead"
        if self._draining:
            return "draining"
        if self._warming:
            return "warming"
        return "ok"

    def model_versions(self) -> Dict[str, int]:
        """{model: served version} — rides /healthz AND /healthz/ready so
        the router's prober (canary cohorts) and the fleet supervisor's
        rolling restart can tell a converged fleet from a mid-roll one
        without scraping logs."""
        return {
            name: int(getattr(self.pool.get(name), "version", 0) or 0)
            for name in self.pool.names()
        }

    def healthz(self) -> Dict[str, Any]:
        # lazy: this module is imported by the jax-free front tier
        from seist_tpu.utils.misc import device_summary

        entries: Dict[str, Any] = {}
        for name in self.pool.names():
            e = self.pool.get(name)
            info: Dict[str, Any] = {
                "version": int(getattr(e, "version", 0) or 0),
                "variants": (
                    e.supported_variants()
                    if hasattr(e, "supported_variants")
                    else ["fp32"]
                ),
            }
            if getattr(e, "is_group", False):
                info["tasks"] = list(e.tasks)
            entries[name] = info
        return {
            "status": self._state_str(),
            "live": self.alive(),
            "ready": self.ready(),
            "models": self.pool.names(),
            # Per-entry served version + variant surface: the converged-
            # vs-mid-roll discriminator (docs/SERVING.md "Live rollout").
            "entries": entries,
            "buckets": list(self.buckets),
            "device": device_summary(),
            "uptime_s": round(time.monotonic() - self._started_at, 3),
            "warmup": self.pool.warmup_report,
        }

    def metrics(self) -> Dict[str, Any]:
        with self._lock:
            requests = dict(self._requests)
            annotate_windows = self._annotate_windows
        with self._stream_lock:
            stream_stats = {
                name: mux.stats()
                for name, mux in self._stream_muxes.items()
            }
        return {
            "uptime_s": round(time.monotonic() - self._started_at, 3),
            "requests": requests,
            "annotate": {
                "windows": annotate_windows,
                "latency_ms": self.annotate_latency_ms.summary(),
            },
            "models": {
                name: batcher.stats()
                for name, batcher in self._batchers.items()
            },
            "shed": {
                name: shedder.stats()
                for name, shedder in self._shedders.items()
            },
            # Streaming plane: per-model session/window/pick/alert
            # accounting (stream_* / assoc_* counters mirror these on
            # the bus, labeled — docs/OBSERVABILITY.md).
            "stream": stream_stats,
            # Multi-task groups: trunk-once accounting (trunk_runs,
            # per-head runs, amortized trunk FLOPs, variant gates).
            "fanout": {
                name: self.pool.get(name).fanout_stats()
                for name in self.pool.names()
                if getattr(self.pool.get(name), "is_group", False)
            },
        }

    def _bus_metrics(self) -> Dict[str, Any]:
        """The bus-collector payload: everything in :meth:`metrics` except
        the per-model stats (batchers publish those themselves, labeled)."""
        m = self.metrics()
        m.pop("models", None)
        m.pop("shed", None)  # AdmissionControllers publish their own
        m.pop("stream", None)  # StationMux counters publish their own
        return m

    def metrics_prometheus(self) -> str:
        """Prometheus text exposition of the process bus — the serve
        process's scrape surface (``GET /metrics?format=prometheus``),
        same renderer as the train worker's --metrics-port."""
        from seist_tpu.obs.bus import BUS, render_prometheus

        return render_prometheus(BUS)

    # ----------------------------------------------------------- shutdown
    def begin_drain(self) -> None:
        """Flip to not-ready (new /predict //annotate get 503, readiness
        probe fails) without yet stopping the batchers — the signal
        handler calls this so in-flight work finishes while the load
        balancer routes away."""
        self._draining = True
        self.publish_state("drain")

    def shutdown(self, drain: bool = True) -> None:
        """Refuse new work, then (with ``drain``) serve what's queued."""
        self._draining = True
        self.publish_state("shutdown")
        # Streaming sessions close before their batchers stop: a mux
        # submit into a shut-down batcher would only error anyway.
        with self._stream_lock:
            muxes, self._stream_muxes = dict(self._stream_muxes), {}
        for mux in muxes.values():
            mux.close_all()
        for batcher in self._batchers.values():
            batcher.shutdown(drain=drain)
        for shedder in self._shedders.values():
            shedder.close()
        # Mirror the batchers: a shut-down service must neither pin the
        # model pool via the bus's collector ref nor report its stale
        # request counters as live on a later scrape.
        from seist_tpu.obs.bus import BUS

        BUS.unregister_collector("serve", fn=self._bus_metrics)


def _clip_picks(result: Dict[str, Any], n_real: int, fs: float) -> None:
    """Drop decoded picking outputs that fall inside zero-padding (sample
    >= ``n_real``); detection intervals are clipped to the real extent."""
    if result.get("task") != "picking":
        return
    for kind in ("ppk", "spk"):
        if kind in result:
            result[kind] = [p for p in result[kind] if p["sample"] < n_real]
    if "det" in result:
        kept = []
        for d in result["det"]:
            if d["onset"] >= n_real:
                continue
            if d["offset"] >= n_real:
                d = dict(
                    d,
                    offset=n_real - 1,
                    offset_s=round((n_real - 1) / fs, 6),
                )
            kept.append(d)
        result["det"] = kept


def _normalize_trace(x: np.ndarray, norm_mode: str) -> np.ndarray:
    if norm_mode not in _NORM_MODES:
        raise BadRequest(
            f"norm_mode must be one of {_NORM_MODES}, got '{norm_mode}'"
        )
    from seist_tpu.data.preprocess import normalize

    # (L, C): time axis is 0.
    return np.asarray(normalize(x, norm_mode, axis=0), np.float32)


# ---------------------------------------------------------------- HTTP shim
class _Handler(BaseHTTPRequestHandler):
    server_version = "seist-serve/0.1"
    protocol_version = "HTTP/1.1"

    @property
    def service(self) -> ServeService:
        return self.server.service  # type: ignore[attr-defined]

    def log_message(self, format: str, *args: Any) -> None:
        logger.debug(f"[serve] {self.address_string()} {format % args}")

    def _reply(
        self,
        status: int,
        payload: Dict[str, Any],
        extra_headers: Optional[Dict[str, str]] = None,
    ) -> None:
        body = json_bytes(payload)
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (extra_headers or {}).items():
            self.send_header(k, v)
        if self.close_connection:
            # Tell the client, not just the socket: without the header an
            # HTTP/1.1 client assumes keep-alive and retries a dead conn.
            self.send_header("Connection", "close")
        self.end_headers()
        self.wfile.write(body)

    def _reply_text(self, status: int, text: str, ctype: str) -> None:
        body = text.encode()
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self) -> None:  # noqa: N802 (http.server API)
        try:
            if self.path == "/healthz":
                # Combined report (back-compat); always 200 while the
                # process can answer at all.
                self._reply(200, self.service.healthz())
            elif self.path == "/healthz/live":
                live = self.service.alive()
                self._reply(
                    200 if live else 503,
                    {"status": "ok" if live else "dead"},
                )
            elif self.path == "/healthz/ready":
                ready = self.service.ready()
                self._reply(
                    200 if ready else 503,
                    {
                        "status": self.service._state_str(),
                        "ready": ready,
                        # The router's prober reads versions from here
                        # (one probe, no extra round trip) to keep canary
                        # cohorts and /router/replicas current.
                        "versions": self.service.model_versions(),
                    },
                )
            elif self.path == "/metrics.json":
                # Raw bus snapshot — the payload the fleet aggregator
                # scrapes and merges (obs/fleet.py); bucket counts ride
                # along for bucket-wise histogram merging.
                from seist_tpu.obs.bus import BUS

                self._reply(200, BUS.snapshot())
            elif self.path.split("?", 1)[0] == "/stream/alerts":
                self._reply(200, self.service.stream_alerts())
            elif self.path.split("?", 1)[0].startswith("/traces"):
                routed = obs_trace.handle_traces_path(self.path)
                if routed is None:
                    self._reply(404, {"error": "not_found",
                                      "message": self.path})
                else:
                    self._reply(*routed)
            elif self.path.split("?", 1)[0] == "/metrics":
                # ?format=prometheus selects text exposition regardless
                # of other params/ordering (real scrapers append job
                # labels etc.); bare /metrics stays the back-compat JSON
                # (docs/OBSERVABILITY.md).
                from urllib.parse import parse_qs, urlparse

                query = parse_qs(urlparse(self.path).query)
                if "prometheus" in query.get("format", []):
                    self._reply_text(
                        200,
                        self.service.metrics_prometheus(),
                        "text/plain; version=0.0.4; charset=utf-8",
                    )
                else:
                    self._reply(200, self.service.metrics())
            else:
                self._reply(404, {"error": "not_found", "message": self.path})
        except Exception as e:  # noqa: BLE001
            # An unexpected handler bug is a death-path-shaped event even
            # though the process survives: leave the forensic flight
            # record (non-fatal — must never suppress a later crash dump).
            obs_flight.dump_on_death(
                "serve_handler_exception", arm_dedup=False,
                request_path=self.path, error=repr(e),
            )
            self._reply(500, {"error": "internal", "message": repr(e)})

    def _trace_headers(
        self, rt: Optional[obs_trace.RequestTrace], status: int
    ) -> Dict[str, str]:
        """Finish the request trace and render its response headers: a
        ``Server-Timing``-style breakdown plus the ``traceparent`` echo
        (so a client that did not mint the id can still fetch
        ``/traces/<id>``)."""
        if rt is None:
            return {}
        rt.finish(status)
        return {
            "Server-Timing": rt.server_timing(),
            obs_trace.TRACEPARENT_HEADER: rt.traceparent,
        }

    def do_POST(self) -> None:  # noqa: N802
        rt: Optional[obs_trace.RequestTrace] = None
        try:
            length = int(self.headers.get("Content-Length") or 0)
            if length > MAX_BODY_BYTES:
                # The unread body would desync this keep-alive connection
                # (its bytes would parse as the next request line) — close.
                self.close_connection = True
                self._reply(
                    413,
                    {"error": "too_large",
                     "message": f"body {length} > {MAX_BODY_BYTES} bytes"},
                )
                return
            raw = self.rfile.read(length)
            if self.path in ("/predict", "/annotate", "/stream"):
                # Continue the upstream trace (bench client / router) or
                # mint here — the replica is the last possible edge.
                rt = obs_trace.RequestTrace(
                    self.headers.get(obs_trace.TRACEPARENT_HEADER),
                    name=f"server:{self.path}",
                )
            body = parse_body(raw)
            if self.path == "/predict":
                result = self.service.predict(
                    body.get("data"),
                    model=body.get("model"),
                    options=body.get("options"),
                    tasks=body.get("tasks"),
                    station=body.get("station"),
                    trace=rt,
                )
            elif self.path == "/annotate":
                result = self.service.annotate(
                    body.get("data"),
                    model=body.get("model"),
                    options=body.get("options"),
                    trace=rt,
                )
            elif self.path == "/stream":
                result = self.service.stream(body, trace=rt)
            elif self.path == "/admin/reload":
                # Hot checkpoint rollout (docs/SERVING.md "Live
                # rollout"): load-gate-swap, incumbent serves throughout;
                # structured 4xx on an unfit candidate.
                result = self.service.reload(
                    model=body.get("model"),
                    checkpoint=body.get("checkpoint"),
                    checkpoints=body.get("checkpoints"),
                    version=body.get("version"),
                )
            else:
                self._reply(404, {"error": "not_found", "message": self.path})
                return
            self._reply(200, result,
                        extra_headers=self._trace_headers(rt, 200))
        except ServeError as e:
            # e.headers() carries e.g. the shed path's Retry-After.
            headers = e.headers()
            headers.update(self._trace_headers(rt, e.status))
            self._reply(e.status, e.payload(), extra_headers=headers)
        except Exception as e:  # noqa: BLE001
            logger.warning(f"[serve] unhandled error: {e!r}")
            obs_flight.dump_on_death(
                "serve_handler_exception", arm_dedup=False,
                request_path=self.path, error=repr(e),
            )
            self._reply(500, {"error": "internal", "message": repr(e)},
                        extra_headers=self._trace_headers(rt, 500))


class ServeHTTPServer(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True
    # socketserver's default listen backlog is 5: a conn-per-request
    # client burst overflows it and dropped SYNs retry at 1/3/7/15/31 s,
    # showing up as client-side latency clusters while the batcher is
    # idle. Overload must surface via the shed/429 tiers, not the
    # kernel's SYN queue (see RouterHTTPServer).
    request_queue_size = 1024

    def __init__(self, addr: Tuple[str, int], service: ServeService):
        super().__init__(addr, _Handler)
        self.service = service


def start_http_server(
    service: ServeService, host: str = "127.0.0.1", port: int = 8080
) -> ServeHTTPServer:
    """Bind + serve on a daemon thread; returns the bound server (use
    ``server.server_address`` to discover an ephemeral port)."""
    server = ServeHTTPServer((host, port), service)
    thread = threading.Thread(
        target=server.serve_forever, name="serve-http", daemon=True
    )
    thread.start()
    return server


# ----------------------------------------------------------------- CLI
def get_serve_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="serve", description="seist_tpu online inference service"
    )
    ap.add_argument(
        "--model", action="append", default=[], metavar="NAME[=CKPT]",
        help="model to serve, repeatable; NAME alone serves fresh-init "
        "weights (smoke/testing)",
    )
    ap.add_argument(
        "--model-group", action="append", default=[],
        metavar="PREFIX=TASK[:CKPT],TASK[:CKPT],...",
        help="multi-task SeisT group: PREFIX_TASK models on ONE shared "
        "trunk, e.g. seist_s=dpk:CKPT,emg:CKPT2 — a multi-task /predict "
        "runs the trunk once and fans out (docs/SERVING.md)",
    )
    ap.add_argument(
        "--variants", default="fp32",
        help="comma-separated serving weight variants to AOT-compile at "
        "load: fp32,bf16,int8 (selected per request via options.variant; "
        "non-fp32 variants are parity-gated against fp32)",
    )
    ap.add_argument("--model-name", default="", help="single-model shorthand")
    ap.add_argument("--checkpoint", default="", help="with --model-name")
    ap.add_argument(
        "--model-version", type=int,
        default=int(os.environ.get("SEIST_MODEL_VERSION", "") or 1),
        help="monotonic version stamp for the loaded checkpoints "
        "(default: $SEIST_MODEL_VERSION or 1) — reported in every "
        "response and /healthz; the rolling-restart handle "
        "(docs/SERVING.md 'Live rollout')",
    )
    ap.add_argument("--window", type=int, default=8192)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-delay-ms", type=float, default=10.0)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument(
        "--buckets", default="",
        help="comma-separated batch buckets (default: powers of 2 up to "
        "--max-batch); largest must equal --max-batch",
    )
    ap.add_argument("--seed", type=int, default=0)
    # Adaptive load shedding (serve/shed.py): per-tier queue-delay
    # budgets. 'inf' disables policy shedding for a tier.
    ap.add_argument("--shed-batch-delay-ms", type=float, default=50.0,
                    help="shed 'batch' tier above this queue delay")
    ap.add_argument("--shed-interactive-delay-ms", type=float,
                    default=250.0,
                    help="shed 'interactive' tier above this queue delay")
    ap.add_argument("--shed-alert-delay-ms", type=float,
                    default=float("inf"),
                    help="shed 'alert' tier above this queue delay "
                    "(default: never — alerts ride to the 429 bound)")
    # Streaming plane (/stream): station mux capacity + cross-station
    # association (docs/SERVING.md "Streaming inference").
    ap.add_argument("--stream-max-stations", type=int, default=4096,
                    help="concurrent streaming sessions per model; new "
                    "stations past this get 429")
    ap.add_argument("--stream-idle-timeout-s", type=float, default=900.0,
                    help="reap a station's session after this much "
                    "feed silence")
    ap.add_argument("--assoc-min-stations", type=int, default=4,
                    help="distinct co-detecting stations to raise a "
                    "network alert")
    ap.add_argument("--assoc-window-s", type=float, default=30.0,
                    help="cross-station co-detection window")
    ap.add_argument("--assoc-velocity-kms", type=float, default=6.0,
                    help="P moveout velocity for origin back-projection")
    ap.add_argument("--assoc-tolerance-s", type=float, default=2.0,
                    help="origin-time coherence tolerance")
    ap.add_argument("--assoc-grid-step-deg", type=float, default=0.25,
                    help="origin grid-search resolution")
    ap.add_argument("--assoc-dedup-window-s", type=float, default=2.0,
                    help="suppress a network alert whose origin sits "
                    "within this many seconds (and dedup_dist_deg) of an "
                    "already-emitted one — the exactly-once half of the "
                    "alert WAL contract")
    ap.add_argument("--stream-journal-dir", default=None,
                    help="directory for per-station session journals + "
                    "the alert WAL; share it across a fleet to enable "
                    "failover re-homing (unset = no journaling)")
    ap.add_argument("--stream-journal-every-s", type=float, default=5.0,
                    help="min seconds between journal writes per station")
    return ap.parse_args(argv)


def parse_model_flags(args: argparse.Namespace) -> List[Tuple[str, str]]:
    entries: List[Tuple[str, str]] = []
    for spec in args.model:
        name, _, ckpt = spec.partition("=")
        entries.append((name, ckpt))
    if args.model_name:
        entries.append((args.model_name, args.checkpoint))
    if not entries and not getattr(args, "model_group", None):
        raise SystemExit(
            "serve: need --model NAME[=CKPT], --model-name or --model-group"
        )
    return entries


def parse_group_flags(
    args: argparse.Namespace,
) -> List[Tuple[str, List[Tuple[str, str]]]]:
    """--model-group PREFIX=TASK[:CKPT],... -> [(prefix, [(task, ckpt)])]."""
    groups: List[Tuple[str, List[Tuple[str, str]]]] = []
    for spec in getattr(args, "model_group", []) or []:
        prefix, sep, rest = spec.partition("=")
        if not sep or not prefix or not rest:
            raise SystemExit(
                f"serve: bad --model-group '{spec}' "
                "(want PREFIX=TASK[:CKPT],TASK[:CKPT],...)"
            )
        tasks: List[Tuple[str, str]] = []
        for part in rest.split(","):
            task, _, ckpt = part.partition(":")
            if not task:
                raise SystemExit(
                    f"serve: empty task in --model-group '{spec}'"
                )
            tasks.append((task, ckpt))
        groups.append((prefix, tasks))
    return groups


def watch_until_shutdown(
    service: ServeService,
    stop: "threading.Event",
    poll_s: float = 0.5,
) -> int:
    """Main-thread watchdog: block until ``stop`` (graceful shutdown) or
    a batcher flush thread dies. Returns the process exit code — 0 for a
    clean drain, 1 for a dead batcher. The non-zero exit is the point: a
    server whose flush thread died would otherwise sit silently while
    every request times out, and no orchestrator would restart it."""
    while not stop.is_set():
        if not service.alive():
            sick = [
                n for n, b in service._batchers.items() if not b.healthy
            ]
            reason = (
                f"batcher flush thread(s) died: {sick}"
                if sick
                else f"warm-up failed: {service._warmup_error!r}"
            )
            publish = getattr(service, "publish_state", None)
            if publish is not None:  # tests pass bare namespaces
                publish(reason)
            # The batcher's own death path already dumped with the rich
            # reason; dedup keeps this exit-side record from shadowing it.
            obs_flight.dump_on_death("serve_unhealthy", dedup_s=5.0,
                                     detail=reason)
            logger.warning(f"[serve] {reason}; exiting 1")
            return 1
        stop.wait(poll_s)
    return 0


def main(argv: Optional[List[str]] = None) -> None:
    from seist_tpu.utils.misc import enable_compile_cache
    # Warm-up compiles dominate replica startup; the persistent cache
    # (same one cli.main_worker uses) makes a supervisor relaunch re-enter
    # rotation in seconds instead of re-paying every bucket's compile.
    enable_compile_cache()
    args = get_serve_args(argv)
    entries = parse_model_flags(args)
    config = BatcherConfig(
        max_batch=args.max_batch,
        max_delay_ms=args.max_delay_ms,
        max_queue=args.max_queue,
        buckets=(
            tuple(int(b) for b in args.buckets.split(","))
            if args.buckets
            else None
        ),
    )
    import os as _os

    from seist_tpu.obs.bus import EventLog

    shed_config = ShedConfig(
        batch_delay_ms=args.shed_batch_delay_ms,
        interactive_delay_ms=args.shed_interactive_delay_ms,
        alert_delay_ms=args.shed_alert_delay_ms,
    )
    # Replica lifecycle events (warming/ok/draining + shed decisions) go
    # to the same events stream the train worker writes — suffixed with
    # the fleet ordinal (events_r0.jsonl, ...) so N replicas sharing one
    # --logdir never interleave/clobber one file (obs/trace.replica_suffix).
    events = EventLog(_os.path.join(
        logger.logdir(), f"events{obs_trace.replica_suffix()}.jsonl"
    ))
    # Serve-plane flight recorder: request spans land in the ring via the
    # bus sink, and the serve death paths (batcher flush death, handler
    # exception, unhealthy exit) dump it exactly like the train worker's.
    obs_flight.install(obs_flight.FlightRecorder())
    # Trace-plane retention counters on the scrape surface.
    obs_trace.register_trace_collector()
    pool = ModelPool(
        entries,
        window=args.window,
        seed=args.seed,
        groups=parse_group_flags(args),
        variants=tuple(
            v.strip() for v in args.variants.split(",") if v.strip()
        ),
        version=args.model_version,
    )
    # Async warm-up: the socket (and /healthz/ready, reporting 503
    # "warming") comes up immediately; orchestrators gate traffic on
    # readiness instead of timing out their liveness probe on the compile.
    service = ServeService(
        pool, config, warmup_async=True, shed_config=shed_config,
        event_log=events,
        stream_config={
            "max_stations": args.stream_max_stations,
            "idle_timeout_s": args.stream_idle_timeout_s,
            "assoc_min_stations": args.assoc_min_stations,
            "assoc_window_s": args.assoc_window_s,
            "assoc_velocity_kms": args.assoc_velocity_kms,
            "assoc_tolerance_s": args.assoc_tolerance_s,
            "assoc_grid_step_deg": args.assoc_grid_step_deg,
            "assoc_dedup_window_s": args.assoc_dedup_window_s,
            "journal_dir": args.stream_journal_dir,
            "journal_every_s": args.stream_journal_every_s,
        },
    )
    server = start_http_server(service, args.host, args.port)
    host, port = server.server_address[:2]
    logger.info(
        f"[serve] listening on http://{host}:{port} "
        f"models={pool.names()} buckets={list(service.buckets)}"
    )

    import signal

    stop = threading.Event()
    # SIGTERM = managed preemption (orchestrator reschedule, node drain):
    # drain in-flight work, then exit PREEMPT_EXIT_CODE so the fleet
    # supervisor relaunches immediately with its retry budget untouched.
    # SIGINT = an operator stopping the process: exit 0, no relaunch.
    exit_code = {"rc": 0}

    def _term(signum, frame):
        if signum == signal.SIGTERM:
            exit_code["rc"] = PREEMPT_EXIT_CODE
        # threadlint: disable=signal-handler-unsafe -- begin_drain is a
        # plain flag store + edge-triggered publish; the interrupted main
        # thread is parked in watch_until_shutdown's stop.wait and never
        # holds service._lock, and logging's RLock is reentrant from the
        # same thread. Flipping 503s on immediately (vs at the next poll
        # tick) is what lets the load balancer route away during drain.
        service.begin_drain()
        stop.set()

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    rc = watch_until_shutdown(service, stop)
    if rc == 0:
        rc = exit_code["rc"]
        logger.info("[serve] draining...")
        service.shutdown(drain=True)
        server.shutdown()
        logger.info(f"[serve] stopped (rc={rc})")
    else:
        server.shutdown()
        service.shutdown(drain=False)
        logger.info("[serve] stopped (unhealthy)")
    events.close()
    if rc:
        raise SystemExit(rc)


if __name__ == "__main__":
    main()
