"""Serving latency/throughput bench + SLO gate -> BENCH-style JSON.

Two client modes against two kinds of target:

* **closed-loop** (default): ``--concurrency`` workers each fire the next
  request as soon as the previous answers — measures the service's
  best-case batching behavior.
* **open-loop** (``--arrival-rps R``): requests are *launched on a
  Poisson-less fixed-interval arrival clock* regardless of completions —
  the production traffic model (arXiv:2605.25645: closed-loop numbers
  flatter a service because overload slows the offered load down).
  Combined with ``--slo-p99-ms`` this is the ROADMAP SLO harness: exit 3
  when the p99 (or the error budget, ``--max-error-rate``) is violated.

* **in-process** (default): builds a ServeService in this process — no
  sockets, measures batching + forward + decode.
* **HTTP** (``--url http://host:port``): drives a live replica or the
  fleet router over real sockets — the serve-chaos lane's client.

Every request error is caught and *accounted*, never aborts the bench:
the JSON carries ``error_rate`` and per-status counts (a shed 503 and a
queue-full 429 are different statuses by design — docs/SERVING.md).

    python tools/bench_serve.py --model-name phasenet --window 256 \
        --requests 64 --concurrency 8 [--checkpoint CKPT]
    python tools/bench_serve.py --url http://127.0.0.1:8080 \
        --arrival-rps 200 --requests 400 --priority alert \
        --slo-p99-ms 250 --window 256

`make serve-smoke` runs a small CPU configuration of the in-process mode.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from datetime import datetime, timezone
from typing import Any, Dict, List, Optional, Tuple

_TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_TOOLS))

#: exit code for an SLO-gate violation (distinct from crash=1/usage=2)
SLO_EXIT_CODE = 3


def get_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="serve bench + SLO gate")
    ap.add_argument("--model-name", default="phasenet")
    ap.add_argument("--checkpoint", default="",
                    help="optional; fresh-init weights when omitted")
    ap.add_argument("--window", type=int, default=256)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--concurrency", type=int, default=8,
                    help="closed-loop workers; in open-loop mode the "
                    "client-side in-flight cap is 4x this (burst "
                    "headroom so overload is shed by the SERVICE, not "
                    "dropped at the client)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-delay-ms", type=float, default=10.0)
    ap.add_argument("--max-queue", type=int, default=256)
    ap.add_argument("--timeout-ms", type=float, default=60_000.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--output", default="", help="also write JSON here")
    # --- new: target / traffic shape / gate -------------------------------
    ap.add_argument("--url", default="",
                    help="drive a live HTTP endpoint (replica or router) "
                    "instead of an in-process service")
    ap.add_argument("--in-channels", type=int, default=3,
                    help="trace channels for --url mode (in-process mode "
                    "reads it from the model)")
    ap.add_argument("--priority", default="",
                    help="request tier: alert | interactive | batch "
                    "(empty = service default)")
    ap.add_argument("--tasks", default="",
                    help="comma-separated task heads for multi-task "
                    "fan-out (e.g. dpk,emg,dis): --model-name is then a "
                    "SeisT group prefix (e.g. seist_s) served on one "
                    "shared trunk; every response is checked to contain "
                    "ALL requested heads (missing_head error otherwise)")
    ap.add_argument("--variant", default="",
                    help="serving weight variant (fp32 | bf16 | int8); "
                    "in-process mode loads fp32 + the requested variant")
    ap.add_argument("--arrival-rps", type=float, default=0.0,
                    help="open-loop arrival rate (0 = closed loop)")
    ap.add_argument("--duration-s", type=float, default=0.0,
                    help="sustained-load mode: keep offering load for "
                    "this many seconds (open-loop: arrivals until the "
                    "deadline; closed-loop: workers loop until it) "
                    "instead of a fixed --requests count — the client "
                    "shape a rolling restart is measured under")
    ap.add_argument("--expect-version", type=int, default=0,
                    help="rollout acceptance gate: poll the router's "
                    "/router/replicas until every replica is ready on "
                    "this model version (convergence), then require "
                    "ZERO responses launched after convergence to carry "
                    "another version (stale_after_convergence == 0); "
                    "exit 1 otherwise. Requires --url (router). Every "
                    "response's model_version is counted in by_version "
                    "regardless")
    ap.add_argument("--slo-p99-ms", type=float, default=0.0,
                    help=f"gate: exit {SLO_EXIT_CODE} if p99 of SUCCESSFUL "
                    "requests exceeds this (0 = no gate)")
    ap.add_argument("--max-error-rate", type=float, default=0.0,
                    help="gate companion: tolerated error_rate before the "
                    "SLO gate trips (default 0 = any error trips it when "
                    "--slo-p99-ms is set)")
    ap.add_argument("--trace-log", default="",
                    help="also write one JSONL line per request "
                    "({trace_id, status, latency_ms}) — the lookup table "
                    "for stitching ANY request with tools/trace_report.py "
                    "(the output JSON always carries the slowest-N and "
                    "failed exemplars)")
    # High-fan-in streaming mode (POST /stream): N stations on an
    # open-loop packet cadence, per-station latency accounting.
    ap.add_argument("--stream-stations", type=int, default=0,
                    help="streaming bench: drive this many stations "
                    "through POST /stream on an open-loop per-station "
                    "packet cadence (0 = normal /predict bench)")
    ap.add_argument("--stream-cadence-s", type=float, default=0.0,
                    help="seconds between one station's packets "
                    "(0 = real time: packet_samples / 50 Hz)")
    ap.add_argument("--stream-packet-samples", type=int, default=0,
                    help="samples per packet (0 = window // 2, one "
                    "stride per packet at the default session stride)")
    return ap.parse_args(argv)


class _Stats:
    """Thread-safe per-request accounting: latencies of successes, error
    counts by HTTP status and by serve error code, and the per-request
    trace ids so a bench run hands you the exact traces to pull from
    ``GET /traces/<id>`` (p99 exemplars + every failure)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.latencies_ms: List[float] = []
        self.successes: List[Dict[str, Any]] = []  # {trace_id, latency_ms}
        self.failed: List[Dict[str, Any]] = []  # {trace_id, status, code}
        self.by_status: Dict[str, int] = {}
        self.by_code: Dict[str, int] = {}
        #: responses per served model_version ("unknown" when absent) —
        #: the rollout acceptance accounting (docs/SERVING.md).
        self.by_version: Dict[str, int] = {}
        #: (launched_at monotonic, version) per success, for the
        #: stale-after-convergence gate.
        self._versioned: List[Tuple[float, int]] = []
        self.ok = 0
        self.errors = 0

    def success(
        self,
        latency_ms: float,
        trace_id: str = "",
        version: Optional[int] = None,
        launched_at: float = 0.0,
    ) -> None:
        with self._lock:
            self.ok += 1
            self.by_status["200"] = self.by_status.get("200", 0) + 1
            key = str(version) if version is not None else "unknown"
            self.by_version[key] = self.by_version.get(key, 0) + 1
            if version is not None:
                self._versioned.append((launched_at, int(version)))
            self.latencies_ms.append(latency_ms)
            if trace_id:
                self.successes.append({
                    "trace_id": trace_id,
                    "latency_ms": round(latency_ms, 3),
                })

    def stale_after(self, converged_at: float, expect: int) -> int:
        """Successes LAUNCHED after the fleet converged on ``expect``
        that still reported another version — the zero-staleness gate's
        numerator. Launch time (not completion) is the honest clock: a
        request sent pre-convergence may legitimately answer old."""
        with self._lock:
            return sum(
                1 for launched, v in self._versioned
                if launched > converged_at and v != expect
            )

    def error(self, status: int, code: str, trace_id: str = "",
              latency_ms: float = 0.0) -> None:
        with self._lock:
            self.errors += 1
            key = str(status)
            self.by_status[key] = self.by_status.get(key, 0) + 1
            if code:
                self.by_code[code] = self.by_code.get(code, 0) + 1
            if trace_id:
                self.failed.append({
                    "trace_id": trace_id,
                    "status": status,
                    "code": code,
                    "latency_ms": round(latency_ms, 3),
                })

    def exemplars(self, slowest_n: int = 5,
                  failed_cap: int = 32) -> Dict[str, Any]:
        """The JSON block: trace ids of the slowest-N successes (the p99
        suspects) and every failed request (capped, count reported)."""
        with self._lock:
            successes = list(self.successes)
            failed = list(self.failed)
        slowest = sorted(
            successes, key=lambda e: e["latency_ms"], reverse=True
        )[:slowest_n]
        return {
            "slowest": slowest,
            "failed": failed[:failed_cap],
            "failed_total": len(failed),
        }


class _ConvergenceWatch:
    """Poll ``<router>/router/replicas`` until every listed replica is
    probe-ready AND reports only ``expect_version`` — the client-side
    definition of "the roll converged". ``converged_at`` (monotonic) is
    None until then."""

    def __init__(self, url: str, expect_version: int, poll_s: float = 0.3):
        self.url = url
        self.expect_version = int(expect_version)
        self.poll_s = poll_s
        self.converged_at: Optional[float] = None
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="bench-converge", daemon=True
        )

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)

    def _converged(self, payload: Dict[str, Any]) -> bool:
        replicas = payload.get("replicas") or []
        if not replicas:
            return False
        for r in replicas:
            versions = r.get("versions") or {}
            if not r.get("ready") or not versions:
                return False
            try:
                if any(
                    int(v) != self.expect_version
                    for v in versions.values()
                ):
                    return False
            except (TypeError, ValueError):
                return False
        return True

    def _loop(self) -> None:
        # The whole body under try: a watcher surprise must not kill the
        # gate silently mid-bench (threadlint thread-target-raises) —
        # converged_at just stays None and the gate fails loudly.
        try:
            from seist_tpu.serve.router import _http_request

            while not self._stop.is_set() and self.converged_at is None:
                try:
                    status, _, body = _http_request(
                        self.url, "GET", "/router/replicas", timeout_s=2.0
                    )
                    if status == 200 and self._converged(
                        json.loads(body.decode())
                    ):
                        self.converged_at = time.monotonic()
                        return
                except Exception:  # noqa: BLE001 — poll again next tick
                    pass
                self._stop.wait(self.poll_s)
        except BaseException as e:  # noqa: BLE001
            print(f"[bench_serve] convergence watcher died: {e!r}",
                  file=sys.stderr, flush=True)


def _http_client(url: str, timeout_ms: float):
    """-> fn(payload_dict, traceparent) that POSTs /predict and returns
    (status, body dict); network failures surface as status 0. Transport
    is the router's own jax-free helper so the bench client and the
    front tier can't drift on HTTP semantics. The client IS the trace
    edge: the minted ``traceparent`` rides the request header."""
    import http.client

    from seist_tpu.serve.router import _http_request

    def call(payload: Dict[str, Any], traceparent: str = ""):
        body = json.dumps(payload).encode()
        headers = {"traceparent": traceparent} if traceparent else None
        try:
            status, _, raw = _http_request(
                url, "POST", "/predict", body,
                timeout_s=timeout_ms / 1000.0 + 5.0,
                headers=headers,
            )
        except (OSError, http.client.HTTPException) as e:
            return 0, {"error": "unreachable", "message": str(e)}
        try:
            out = json.loads(raw)
        except ValueError:
            out = {}
        # A non-object error body (some LBs answer 503 with a bare JSON
        # string) must not crash the accounting downstream.
        return status, out if isinstance(out, dict) else {"error": str(out)}

    return call


def main(argv: Optional[List[str]] = None) -> int:
    args = get_args(argv)

    if args.stream_stations > 0:
        return _run_stream_bench(args)

    import numpy as np

    # jax-free (obs/trace.py is stdlib + the bus): the bench client is
    # the trace edge — it mints every request's traceparent, so the ids
    # in its JSON are the exact handles for GET /traces/<id>.
    from seist_tpu.obs import trace as obs_trace
    from seist_tpu.utils.profiling import stopwatch

    options: Dict[str, Any] = {"timeout_ms": args.timeout_ms}
    if args.priority:
        options["priority"] = args.priority
    if args.variant:
        options["variant"] = args.variant
    tasks = [t for t in args.tasks.split(",") if t] if args.tasks else None

    service = None
    if args.url:
        in_channels = args.in_channels
        call = _http_client(args.url, args.timeout_ms)

        def one_request(waveform, traceparent: str) -> Any:
            payload = {"data": waveform, "options": options}
            if args.model_name:
                payload["model"] = args.model_name
            if tasks:
                payload["tasks"] = tasks
            return call(payload, traceparent)

    else:
        from seist_tpu.serve import BatcherConfig, ModelPool, ServeService
        from seist_tpu.serve.protocol import ServeError

        variants = ("fp32",) + ((args.variant,) if args.variant else ())
        if tasks:
            # Multi-task fan-out: --model-name is the SeisT group prefix;
            # one shared trunk serves every requested head.
            pool = ModelPool(
                groups=[(args.model_name, [(t, "") for t in tasks])],
                window=args.window, seed=args.seed, variants=variants,
            )
        else:
            pool = ModelPool(
                [(args.model_name, args.checkpoint)], window=args.window,
                seed=args.seed, variants=variants,
            )
        service = ServeService(
            pool,
            BatcherConfig(
                max_batch=args.max_batch,
                max_delay_ms=args.max_delay_ms,
                max_queue=args.max_queue,
            ),
        )
        entry = pool.get(args.model_name)
        in_channels = entry.in_channels
        if entry.is_picker and not tasks:
            options.update(ppk_threshold=0.05, spk_threshold=0.05)

        def one_request(waveform, traceparent: str) -> Any:
            # In-process mode: this process IS the server, so the trace
            # plays the HTTP handler's part (mint -> spans -> finish).
            rt = obs_trace.RequestTrace(traceparent,
                                        name="server:/predict")
            try:
                result = service.predict(
                    waveform, options=options, tasks=tasks, trace=rt
                )
                rt.finish(200)
                return 200, result
            except ServeError as e:
                if e.code == "shed":
                    rt.flag("shed")
                rt.finish(e.status)
                return e.status, e.payload()
            except BaseException:
                rt.finish(0)
                raise

    rng = np.random.default_rng(args.seed)
    traces = [
        rng.standard_normal((args.window, in_channels))
        .astype(np.float32).tolist()
        for _ in range(min(args.requests, 32))  # cycle a small pool
    ]

    stats = _Stats()

    def one(i: int) -> None:
        traceparent = obs_trace.mint_traceparent()
        trace_id = traceparent.split("-")[1]
        launched_at = time.monotonic()
        with stopwatch() as elapsed:
            try:
                status, body = one_request(
                    traces[i % len(traces)], traceparent
                )
            except Exception as e:  # noqa: BLE001
                # The docstring contract: every request error is counted,
                # never aborts the bench. A raise here would abort the
                # closed-loop ex.map — or, worse, vanish inside an
                # open-loop daemon thread so the request is counted
                # neither ok nor error and the SLO gate reads a fake pass.
                status, body = 0, {"error": "client_exception",
                                   "message": repr(e)}
        if status == 200 and tasks:
            # Multi-task acceptance: a 200 that silently dropped a head
            # is an error, not a success — the fan-out contract is that
            # ONE trunk run answers EVERY requested head.
            answered = body.get("tasks") or {}
            if sorted(answered) != sorted(tasks):
                status = 0
                body = {"error": "missing_head",
                        "message": f"answered {sorted(answered)} of "
                                   f"{sorted(tasks)}"}
        latency_ms = elapsed() * 1000.0
        if status == 200:
            version = body.get("model_version")
            try:
                version = int(version) if version is not None else None
            except (TypeError, ValueError):
                version = None
            stats.success(latency_ms, trace_id=trace_id, version=version,
                          launched_at=launched_at)
        else:
            stats.error(status, str(body.get("error", "")),
                        trace_id=trace_id, latency_ms=latency_ms)

    # Rollout convergence watcher: a background poll of the router's
    # /router/replicas that records the moment EVERY replica is ready on
    # --expect-version — the timestamp the staleness gate compares
    # per-request launch times against.
    watch: Optional[_ConvergenceWatch] = None
    if args.expect_version > 0 and args.url:
        watch = _ConvergenceWatch(args.url, args.expect_version)
        watch.start()

    t_start = time.monotonic()
    with stopwatch() as wall:
        if args.arrival_rps > 0:
            _drive_open_loop(one, args.requests, args.arrival_rps,
                             args.concurrency, stats,
                             duration_s=args.duration_s)
        elif args.duration_s > 0:
            _drive_closed_loop_for(one, args.concurrency, args.duration_s)
        else:
            with ThreadPoolExecutor(args.concurrency) as ex:
                # ex.map would abort the whole bench on the first raised
                # error; one() catches per-request instead.
                list(ex.map(one, range(args.requests)))
    wall_s = wall()
    if watch is not None:
        watch.stop()

    batcher_stats: Dict[str, Any] = {}
    fanout_stats: Dict[str, Any] = {}
    if service is not None:
        metrics = service.metrics()
        key = args.model_name
        if args.variant and args.variant != "fp32":
            key = f"{args.model_name}@{args.variant}"
        batcher_stats = metrics["models"][key]
        fanout_stats = metrics.get("fanout", {}).get(args.model_name, {})
        service.shutdown()

    lat = np.asarray(stats.latencies_ms) if stats.latencies_ms else None
    total = stats.ok + stats.errors
    error_rate = stats.errors / total if total else 0.0

    def pct(q: float) -> float:
        return round(float(np.percentile(lat, q)), 3) if lat is not None else -1.0

    if args.url:
        device = "remote"
    else:
        import jax

        device = jax.devices()[0].device_kind

    result = {
        "metric": "serve_predict_latency",
        "model": args.model_name,
        "target": args.url or "in-process",
        "mode": "open-loop" if args.arrival_rps > 0 else "closed-loop",
        "window": args.window,
        # Sustained-load mode offers whatever fits the duration; report
        # what was actually driven, not the unused --requests default.
        "requests": total if args.duration_s > 0 else args.requests,
        "duration_s": args.duration_s,
        "concurrency": args.concurrency,
        "arrival_rps": args.arrival_rps,
        "priority": args.priority or "default",
        "tasks": tasks or [],
        "variant": args.variant or "fp32",
        "max_batch": args.max_batch,
        "max_delay_ms": args.max_delay_ms,
        "p50_ms": pct(50),
        "p90_ms": pct(90),
        "p99_ms": pct(99),
        "mean_ms": round(float(lat.mean()), 3) if lat is not None else -1.0,
        "throughput_rps": round(stats.ok / wall_s, 2) if wall_s else 0.0,
        "ok": stats.ok,
        "errors": stats.errors,
        "error_rate": round(error_rate, 4),
        "by_status": dict(sorted(stats.by_status.items())),
        "by_error_code": dict(sorted(stats.by_code.items())),
        # Served model versions per response — the live-rollout
        # accounting (docs/SERVING.md "Live rollout").
        "by_version": dict(sorted(stats.by_version.items())),
        "device": device,
        # The handles for `python tools/trace_report.py --from-bench`:
        # p99 suspects + every failure, by trace id. Failed exemplars are
        # flagged on the servers and evicted last; slowest-N SUCCESSES
        # are unflagged, so on a bench larger than the servers' trace
        # ring they may already be evicted by the time you pull them.
        "trace_exemplars": stats.exemplars(),
        "measured_at": datetime.now(timezone.utc).strftime(
            "%Y-%m-%dT%H:%M:%SZ"
        ),
    }
    if args.trace_log:
        with open(args.trace_log, "w") as f:
            for e in stats.successes:
                f.write(json.dumps({**e, "status": 200}) + "\n")
            for e in stats.failed:
                f.write(json.dumps(e) + "\n")
    trace_capacity = int(
        float(os.environ.get("SEIST_TRACE_CAPACITY", "") or 256)
    )
    if args.requests > trace_capacity:
        # Tail retention evicts unflagged (successful) traces first, so
        # the slowest-N exemplars of a big bench likely 404 on
        # GET /traces/<id> unless the serving processes keep more.
        print(
            f"[bench_serve] note: {args.requests} requests > trace ring "
            f"capacity (~{trace_capacity}); slowest-N exemplars may be "
            "evicted on the servers — raise SEIST_TRACE_CAPACITY on the "
            "fleet or set SEIST_TRACE_SLO_MS to flag slow requests for "
            "retention",
            file=sys.stderr, flush=True,
        )
    if batcher_stats:
        result["batch_fill_ratio"] = round(
            batcher_stats["batch_fill_ratio"], 4
        )
        result["forwards"] = batcher_stats["forwards"]
        result["completed"] = batcher_stats["completed"]
    if fanout_stats:
        result["trunk_runs"] = fanout_stats.get("trunk_runs", 0)
        result["head_runs"] = fanout_stats.get("head_runs", {})
        result["trunk_flops_saved"] = fanout_stats.get(
            "trunk_flops_saved", 0.0
        )

    rc = 0
    if args.expect_version > 0:
        # The rollout acceptance gate: the fleet must converge on the
        # expected version during the bench, and once it has, every
        # subsequently-launched response must carry it.
        result["expected_version"] = args.expect_version
        if watch is None:
            result["converged_at_s"] = -1.0
            result["stale_after_convergence"] = -1
            print("[bench_serve] --expect-version needs --url (router)",
                  file=sys.stderr, flush=True)
            rc = 1
        elif watch.converged_at is None:
            result["converged_at_s"] = -1.0
            result["stale_after_convergence"] = -1
            print(
                f"[bench_serve] ROLLOUT GATE FAILED: fleet never "
                f"converged on version {args.expect_version}",
                file=sys.stderr, flush=True,
            )
            rc = 1
        else:
            stale = stats.stale_after(
                watch.converged_at, args.expect_version
            )
            result["converged_at_s"] = round(
                watch.converged_at - t_start, 3
            )
            result["stale_after_convergence"] = stale
            if stale:
                print(
                    f"[bench_serve] ROLLOUT GATE FAILED: {stale} "
                    f"stale-version responses after convergence "
                    f"(by_version={result['by_version']})",
                    file=sys.stderr, flush=True,
                )
                rc = 1
    if tasks:
        missing = stats.by_code.get("missing_head", 0)
        result["fanout_complete"] = missing == 0 and stats.ok > 0
        if not result["fanout_complete"]:
            print(
                f"[bench_serve] FAN-OUT INCOMPLETE: {missing} responses "
                f"missing heads, {stats.ok} complete",
                file=sys.stderr, flush=True,
            )
            rc = 1
    if args.slo_p99_ms > 0:
        violations = []
        if lat is None:
            violations.append("no successful requests")
        elif result["p99_ms"] > args.slo_p99_ms:
            violations.append(
                f"p99 {result['p99_ms']:.1f} ms > SLO {args.slo_p99_ms:.1f} ms"
            )
        if error_rate > args.max_error_rate:
            violations.append(
                f"error_rate {error_rate:.4f} > {args.max_error_rate:.4f}"
            )
        if violations:
            result["slo_violations"] = violations
            print(f"[bench_serve] SLO GATE FAILED: {'; '.join(violations)}",
                  file=sys.stderr, flush=True)
            rc = SLO_EXIT_CODE
        else:
            result["slo_violations"] = []

    line = json.dumps(result)
    print(line)
    if args.output:
        with open(args.output, "w") as f:
            f.write(line + "\n")
    return rc


def _run_stream_bench(args) -> int:
    """``--stream-stations N``: the high-fan-in streaming client. N
    stations each POST /stream packets on their own open-loop cadence
    (launch at t0 + k*cadence regardless of completions — the production
    telemetry model: a seismic network does not slow down because the
    server is busy). ``--concurrency`` workers each OWN stations
    ``w::W``, preserving per-station packet ordering (a station's seq
    numbers must arrive in order; different stations are independent).

    The JSON carries aggregate packet-latency percentiles PLUS
    per-station accounting — percentiles over station mean latencies,
    the worst stations by mean, and per-station failure ledgers
    (by_status, dropped/duplicated/resumed packet counts) — so one hot
    or unlucky station can't hide in (or masquerade as) a fleet-wide
    tail. Connection errors and 5xx are RETRIED with the same seq
    (reconnect-with-resume) instead of abandoning the station: during a
    fleet failover the retry lands on a survivor and the packet counts
    as ``resumed``, so a chaos run's "dropped" number is honest
    client-observed loss, not transport noise. ``--slo-p99-ms`` gates
    the aggregate p99 exactly like the /predict bench."""
    import numpy as np

    n_st = int(args.stream_stations)
    duration = args.duration_s or 10.0
    pkt = args.stream_packet_samples or args.window // 2
    cadence = args.stream_cadence_s or pkt / 50.0
    options: Dict[str, Any] = {"timeout_ms": args.timeout_ms}
    if args.priority:
        options["priority"] = args.priority

    service = None
    if args.url:
        import http.client

        from seist_tpu.serve.router import _http_request

        def send(body: Dict[str, Any]):
            raw = json.dumps(body).encode()
            try:
                status, _, resp = _http_request(
                    args.url, "POST", "/stream", raw,
                    timeout_s=args.timeout_ms / 1000.0 + 5.0,
                )
            except (OSError, http.client.HTTPException) as e:
                return 0, {"error": "unreachable", "message": str(e)}
            try:
                out = json.loads(resp)
            except ValueError:
                out = {}
            return status, out if isinstance(out, dict) else {}

    else:
        from seist_tpu.serve import BatcherConfig, ModelPool, ServeService
        from seist_tpu.serve.protocol import ServeError

        pool = ModelPool(
            [(args.model_name, args.checkpoint)], window=args.window,
            seed=args.seed,
        )
        service = ServeService(
            pool,
            BatcherConfig(
                max_batch=args.max_batch,
                max_delay_ms=args.max_delay_ms,
                max_queue=args.max_queue,
            ),
            stream_config={"max_stations": max(4096, 2 * n_st)},
        )
        options.update(ppk_threshold=0.05, spk_threshold=0.05)

        def send(body: Dict[str, Any]):
            try:
                return 200, service.stream(body)
            except ServeError as e:
                return e.status, e.payload()

    rng = np.random.default_rng(args.seed)
    # A small shared packet pool: per-station payload identity doesn't
    # matter for latency, and N_stations x duration packets would not
    # fit memory at thousand-station scale.
    packets = [
        rng.standard_normal((pkt, args.in_channels))
        .astype(np.float32).tolist()
        for _ in range(16)
    ]
    # Station grid over ~2 deg so coordinates are plausible and the
    # association path runs (alerts on synthetic noise are fine — the
    # bench measures the pipeline, not seismology).
    side = max(1, int(np.ceil(np.sqrt(n_st))))
    stations = [
        {"id": f"BN{i:05d}", "network": "BN",
         "lat": round(34.0 + 2.0 * (i // side) / side, 4),
         "lon": round(-118.0 + 2.0 * (i % side) / side, 4)}
        for i in range(n_st)
    ]

    lock = threading.Lock()
    agg = {"ok": 0, "errors": 0, "windows": 0, "picks": 0, "alerts": 0,
           "dropped_windows": 0, "by_status": {},
           "dropped_packets": 0, "duplicate_packets": 0,
           "resumed_packets": 0}
    latencies: List[float] = []
    per_station: Dict[str, List[float]] = {s["id"]: [] for s in stations}
    #: per-station failure ledger: the chaos lane's client-side truth.
    st_acc: Dict[str, Dict[str, Any]] = {
        s["id"]: {"by_status": {}, "dropped": 0, "duplicates": 0,
                  "resumed": 0}
        for s in stations
    }
    #: reconnect-with-resume budget per packet: transport errors and
    #: 5xx re-send the SAME seq (idempotent server-side — a replayed
    #: packet the first send actually reached dedups as a duplicate).
    max_retries = 3
    n_workers = max(1, min(args.concurrency, n_st))
    t0 = time.monotonic()
    deadline = t0 + duration

    def worker(w: int) -> None:
        # Whole body under try: (threadlint thread-target-raises).
        try:
            mine = stations[w::n_workers]
            seqs = {s["id"]: 0 for s in mine}
            rounds = 0
            while True:
                for st in mine:
                    seqs[st["id"]] += 1
                    body = {
                        "station": st,
                        "data": packets[
                            (rounds + hash(st["id"])) % len(packets)
                        ],
                        "seq": seqs[st["id"]],
                        "options": options,
                    }
                    if args.model_name:
                        body["model"] = args.model_name
                    attempts = 0
                    while True:
                        t_send = time.monotonic()
                        status, resp = send(body)
                        lat_ms = (time.monotonic() - t_send) * 1000.0
                        acc = st_acc[st["id"]]
                        with lock:
                            agg["by_status"][status] = (
                                agg["by_status"].get(status, 0) + 1
                            )
                            acc["by_status"][status] = (
                                acc["by_status"].get(status, 0) + 1
                            )
                            if status == 200:
                                agg["ok"] += 1
                                latencies.append(lat_ms)
                                per_station[st["id"]].append(lat_ms)
                                agg["windows"] += resp.get("windows", 0)
                                agg["picks"] += (
                                    len(resp.get("ppk", []))
                                    + len(resp.get("spk", []))
                                    + len(resp.get("det", []))
                                )
                                agg["alerts"] += len(
                                    resp.get("alerts", [])
                                )
                                agg["dropped_windows"] = max(
                                    agg["dropped_windows"],
                                    resp.get("dropped_windows", 0),
                                )
                                if resp.get("duplicate"):
                                    acc["duplicates"] += 1
                                    agg["duplicate_packets"] += 1
                                if attempts:
                                    acc["resumed"] += 1
                                    agg["resumed_packets"] += 1
                                break
                            retryable = (
                                status == 0 or status >= 500
                            ) and attempts < max_retries                                 and time.monotonic() < deadline
                            if not retryable:
                                agg["errors"] += 1
                                acc["dropped"] += 1
                                agg["dropped_packets"] += 1
                                break
                        # Reconnect-with-resume: same seq, brief
                        # backoff — a failover needs a beat for the
                        # router to re-home the station.
                        attempts += 1
                        time.sleep(0.2 * attempts)
                rounds += 1
                # Open loop: the next round launches on the cadence
                # clock, not after completions.
                target = t0 + rounds * cadence
                now = time.monotonic()
                if now >= deadline:
                    return
                if target > now:
                    time.sleep(min(target, deadline) - now)
        except BaseException as e:  # noqa: BLE001
            print(f"[bench_serve] stream worker {w} died: {e!r}",
                  file=sys.stderr, flush=True)

    threads = [
        threading.Thread(target=worker, args=(w,), daemon=True)
        for w in range(n_workers)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall_s = time.monotonic() - t0

    stream_stats: Dict[str, Any] = {}
    if service is not None:
        stream_stats = service.metrics()["stream"].get(args.model_name, {})
        service.shutdown()

    lat = np.asarray(latencies) if latencies else None

    def pct(a, q):
        return round(float(np.percentile(a, q)), 3) if a is not None and len(a) else -1.0

    means = {
        sid: float(np.mean(v)) for sid, v in per_station.items() if v
    }
    mean_arr = np.asarray(list(means.values())) if means else None
    worst = sorted(means.items(), key=lambda kv: -kv[1])[:5]
    total = agg["ok"] + agg["errors"]
    result = {
        "metric": "serve_stream_latency",
        "model": args.model_name,
        "target": args.url or "in-process",
        "mode": "stream-open-loop",
        "stations": n_st,
        "concurrency": n_workers,
        "cadence_s": round(cadence, 4),
        "packet_samples": pkt,
        "duration_s": round(wall_s, 3),
        "packets": total,
        "ok": agg["ok"],
        "errors": agg["errors"],
        "error_rate": round(agg["errors"] / total, 4) if total else 0.0,
        "by_status": dict(sorted(agg["by_status"].items())),
        "windows": agg["windows"],
        "picks": agg["picks"],
        "alerts": agg["alerts"],
        "p50_ms": pct(lat, 50),
        "p90_ms": pct(lat, 90),
        "p99_ms": pct(lat, 99),
        "mean_ms": round(float(lat.mean()), 3) if lat is not None else -1.0,
        "packets_per_s": round(agg["ok"] / wall_s, 2) if wall_s else 0.0,
        # Per-station accounting: a single hot station must be visible.
        "station_mean_ms": {
            "p50": pct(mean_arr, 50),
            "p99": pct(mean_arr, 99),
            "max": round(float(mean_arr.max()), 3) if mean_arr is not None else -1.0,
        },
        "worst_stations": [
            {"id": sid, "mean_ms": round(m, 3)} for sid, m in worst
        ],
        "stations_reporting": len(means),
        "dropped_packets": agg["dropped_packets"],
        "duplicate_packets": agg["duplicate_packets"],
        "resumed_packets": agg["resumed_packets"],
        # Only stations that saw trouble (capped): a thousand clean
        # ledgers would drown the artifact.
        "station_failures": {
            sid: acc
            for sid, acc in sorted(
                st_acc.items(),
                key=lambda kv: -(kv[1]["dropped"] + kv[1]["resumed"]),
            )[:20]
            if acc["dropped"] or acc["resumed"] or acc["duplicates"]
            or set(acc["by_status"]) - {200}
        },
        "stream_stats": stream_stats,
        "measured_at": datetime.now(timezone.utc).strftime(
            "%Y-%m-%dT%H:%M:%SZ"
        ),
    }
    rc = 0
    if args.slo_p99_ms > 0:
        violations = []
        if lat is None:
            violations.append("no successful packets")
        elif result["p99_ms"] > args.slo_p99_ms:
            violations.append(
                f"p99 {result['p99_ms']:.1f} ms > SLO "
                f"{args.slo_p99_ms:.1f} ms"
            )
        if result["error_rate"] > args.max_error_rate:
            violations.append(
                f"error_rate {result['error_rate']:.4f} > "
                f"{args.max_error_rate:.4f}"
            )
        result["slo_violations"] = violations
        if violations:
            print(
                f"[bench_serve] SLO GATE FAILED: {'; '.join(violations)}",
                file=sys.stderr, flush=True,
            )
            rc = SLO_EXIT_CODE
    line = json.dumps(result)
    print(line)
    if args.output:
        with open(args.output, "w") as f:
            f.write(line + "\n")
    return rc


def _drive_closed_loop_for(one, concurrency: int, duration_s: float) -> None:
    """Sustained closed-loop: ``concurrency`` workers each fire the next
    request as soon as the previous answers, until the deadline — the
    fixed-duration client a rolling restart is benched under (total
    request count is whatever the service sustained)."""
    deadline = time.monotonic() + duration_s
    counter = iter(range(1 << 62))
    counter_lock = threading.Lock()

    def worker() -> None:
        # one() accounts every exception itself; the loop shape is the
        # only logic here (threadlint thread-target-raises).
        try:
            while time.monotonic() < deadline:
                with counter_lock:
                    i = next(counter)
                one(i)
        except BaseException as e:  # noqa: BLE001
            print(f"[bench_serve] closed-loop worker died: {e!r}",
                  file=sys.stderr, flush=True)

    threads = [
        threading.Thread(target=worker, daemon=True)
        for _ in range(max(1, concurrency))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def _drive_open_loop(
    one, n_requests: int, arrival_rps: float, max_inflight: int,
    stats: "_Stats", duration_s: float = 0.0,
) -> None:
    """Launch request i at t0 + i/rps on a worker thread, independent of
    completions (the open-loop arrival model). The thread pool is capped
    at ``4 * max_inflight`` — the 4x headroom lets a backlog build so an
    overloaded SERVICE gets to exercise its shedding tiers instead of the
    client silently throttling arrivals. Past that cap, further arrivals
    are dropped ON THE CLIENT and counted as status 0 ``client_overrun``
    errors — an open-loop bench that quietly stopped offering load would
    otherwise report a fake SLO pass.

    ``duration_s > 0`` switches from a fixed request count to sustained
    load: arrivals keep coming on the same clock until the deadline."""
    interval = 1.0 / arrival_rps
    cap = max(1, max_inflight) * 4
    sem = threading.Semaphore(cap)
    n_over = 0
    threads: List[threading.Thread] = []
    t0 = time.monotonic()
    if duration_s > 0:
        deadline = t0 + duration_s

        def arrivals():
            i = 0
            while time.monotonic() < deadline:
                yield i
                i += 1

        schedule = arrivals()
    else:
        schedule = iter(range(n_requests))
    for i in schedule:
        target = t0 + i * interval
        delay = target - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        if not sem.acquire(blocking=False):
            n_over += 1
            stats.error(0, "client_overrun")
            continue

        def run(idx: int) -> None:
            try:
                one(idx)
            finally:
                sem.release()

        # threadlint: disable=thread-target-raises -- one() accounts every
        # exception as a status-0 client_exception itself; the try/finally
        # only guarantees the in-flight semaphore is returned.
        t = threading.Thread(target=run, args=(i,), daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    if n_over:
        print(f"[bench_serve] WARNING: {n_over} arrivals dropped client-side "
              f"(in-flight cap {cap}); offered load was lower than requested",
              file=sys.stderr, flush=True)


if __name__ == "__main__":
    sys.exit(main())
