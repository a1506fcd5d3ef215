"""Serving chaos lane (`make serve-chaos`): REAL replica processes
(`main.py serve`, phasenet fresh-init, CPU) under injected faults — the
ISSUE 7 acceptance runs.

* SIGKILL one of two replicas mid-load: the fleet supervisor restarts it,
  the router retries the in-flight failures, and the client's own
  accounting (bench_serve --url) shows ZERO failed well-formed requests.
* Black-holed replica (accepts, answers health probes, never answers
  /predict): the request-path circuit opens within a bounded number of
  probes and closes after the injected fault clears.
* Overload at ~2x the sustainable arrival rate: the batch tier is shed
  with the distinct 503 'shed' (not the queue-full 429) while the alert
  tier's p99 passes its SLO gate — both verdicts from bench_serve.
* Live-model flywheel (ISSUE 13): a 3-replica fleet rolled to a new
  model version under sustained open-loop load — zero failed requests,
  zero stale-version responses after convergence, the roll visible
  drain -> relaunch -> ready per replica.
* Canary auto-rollback: an injected bad candidate version
  (SEIST_FAULT_SERVE_BAD_CANDIDATE) is drained back to 0% by the
  router's cohort-delta budget while retries keep clients green.

Replica warm-up is compile-bound; the serve CLI enables the persistent
XLA cache, so replicas after the first (and every supervisor relaunch)
re-enter rotation in seconds.
"""

import http.client
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.chaos]

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(REPO, "tools"))

SUPERVISE_FLEET = os.path.join(REPO, "tools", "supervise_fleet.py")
MAIN = os.path.join(REPO, "main.py")
WINDOW = 256

REPLICA_CMD = [
    sys.executable, MAIN, "serve",
    "--model", "phasenet=",
    "--window", str(WINDOW),
    "--max-batch", "4",
    "--max-delay-ms", "5",
]
#: generous: first-ever run pays the phasenet bucket compiles
WARM_TIMEOUT_S = 300.0


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _drain_pipe(pipe, buf):
    for line in pipe:
        buf.append(line)


def _start_fleet(tmp_path, env_extra=None, replicas=2, fleet_args=(),
                 replica_args=()):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    proc = subprocess.Popen(
        [
            sys.executable, SUPERVISE_FLEET,
            "--replicas", str(replicas),
            "--base-port", str(_free_port()),
            "--router-port", "0",
            "--probe-interval-s", "0.3",
            "--backoff", "0.5",
            "--drain-timeout-s", "20",
            *fleet_args,
            "--",
            *REPLICA_CMD, *replica_args,
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        env=env,
        text=True,
    )
    # Drain both pipes on background threads for the whole fleet
    # lifetime: the replicas inherit these fds, and an undrained pipe
    # that hits the 64 KB kernel buffer blocks EVERY fleet process on
    # its next write — a silent way to wedge the supervisor's monitor
    # loop mid-test. Draining also means a failure report carries the
    # complete fleet log, not whatever fit in the buffer.
    proc.fleet_err = []
    err_thread = threading.Thread(
        target=_drain_pipe, args=(proc.stderr, proc.fleet_err), daemon=True
    )
    err_thread.start()
    proc.fleet_err_thread = err_thread
    router = None
    for _ in range(50):
        line = proc.stdout.readline()
        if not line:
            break
        m = re.search(r"ROUTER=http://([\d.]+):(\d+)", line)
        if m:
            router = (m.group(1), int(m.group(2)))
            break
    if router is None:
        proc.kill()
        raise AssertionError("no ROUTER line from supervise_fleet")
    proc.fleet_out = []
    threading.Thread(
        target=_drain_pipe, args=(proc.stdout, proc.fleet_out), daemon=True
    ).start()
    return proc, router[0], router[1]


def _get(host, port, path, timeout=5.0):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        raw = resp.read()
        try:
            return resp.status, json.loads(raw)
        except ValueError:
            return resp.status, raw.decode()
    finally:
        conn.close()


def _wait_probed_ready(host, port, n, timeout_s=WARM_TIMEOUT_S):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            _, payload = _get(host, port, "/router/replicas")
            states = [
                r["probe_state"] for r in payload.get("replicas", [])
            ]
            if states.count("ok") >= n:
                return
        except OSError:
            pass
        time.sleep(0.25)
    raise AssertionError(
        f"fleet never reached {n} probed-ready replicas in {timeout_s}s"
    )


def _stop_fleet(proc, timeout=60):
    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        raise
    proc.fleet_err_thread.join(timeout=10)
    return rc, "".join(proc.fleet_err)


def _bench(url, tmp_path, tag, *extra):
    """Run bench_serve in-process against a live url; return (rc, json)."""
    import bench_serve

    out = str(tmp_path / f"bench_{tag}.json")
    rc = bench_serve.main([
        "--url", url,
        "--window", str(WINDOW),
        "--model-name", "phasenet",
        "--output", out,
        *extra,
    ])
    with open(out) as f:
        return rc, json.load(f)


def _read_trace_log(path):
    with open(path) as f:
        return {
            rec["trace_id"]: rec
            for rec in (json.loads(line) for line in f if line.strip())
        }


def test_sigkill_mid_load_zero_failed_requests(tmp_path):
    """Acceptance: 2 replicas under closed-loop load, one SIGKILLed by the
    fault injector at its 8th request. supervise_fleet restarts it, the
    router retries the severed in-flight requests on the survivor, and the
    client-side accounting ends with error_rate == 0."""
    stamp = str(tmp_path / "kill.stamp")
    proc, host, port = _start_fleet(
        tmp_path,
        env_extra={
            "SEIST_FAULT_SERVE_KILL_REQ": "8",
            "SEIST_FAULT_SERVE_REPLICA": "0",
            "SEIST_FAULT_STAMP": stamp,
        },
        fleet_args=("--router-retries", "3", "--request-timeout-s", "30"),
    )
    try:
        _wait_probed_ready(host, port, 2)
        tlog = str(tmp_path / "kill_traces.jsonl")
        rc, result = _bench(
            f"http://{host}:{port}", tmp_path, "kill",
            "--requests", "48",
            "--concurrency", "6",
            "--timeout-ms", "60000",
            "--trace-log", tlog,
        )
        assert os.path.exists(stamp), (
            "kill fault never fired — the run proved nothing"
        )
        assert rc == 0
        assert result["errors"] == 0 and result["ok"] == 48, result
        assert result["error_rate"] == 0.0
        # The rescue is visible on the router's own metrics plane.
        _, text = _get(host, port, "/metrics")
        assert "seist_router_retries" in text

        # --- ISSUE 11 acceptance: the rescue is visible on the TRACE
        # plane too. A request that survived the SIGKILL via router
        # retry must stitch (tools/trace_report.py) into one tree
        # showing both attempts (failed + succeeded), the surviving
        # replica's queue wait and device program span — and the span
        # tree's total must be within 10% of what the CLIENT measured
        # for that same request.
        import trace_report

        client_lat = _read_trace_log(tlog)
        assert len(client_lat) == 48
        _, idx = _get(host, port, "/traces")
        retried = [
            t for t in idx["traces"]
            if "retried" in t["flags"] and t["trace_id"] in client_lat
            and client_lat[t["trace_id"]]["status"] == 200
        ]
        assert retried, (
            f"no retried trace on the router: {idx['traces'][:5]}"
        )
        _, reg = _get(host, port, "/router/replicas")
        endpoints = [f"http://{host}:{port}"] + [
            r["url"] for r in reg["replicas"]
        ]
        # ANY surviving retried request must satisfy the acceptance —
        # walk them slowest-first (relative client-side overhead is
        # smallest there) and keep the verdicts for the failure report.
        verdicts = []
        passed = None
        for cand in sorted(
            retried,
            key=lambda t: client_lat[t["trace_id"]]["latency_ms"],
            reverse=True,
        ):
            st = trace_report.stitch_from_endpoints(
                cand["trace_id"], endpoints
            )
            attempts = st.find("attempt")
            classes = [
                (s.get("annotations") or {}).get("class")
                for s in attempts
            ]
            fwd = st.find("forward")
            client_ms = client_lat[cand["trace_id"]]["latency_ms"]
            rel = (
                abs(st.total_ms - client_ms) / client_ms
                if client_ms else 1.0
            )
            ok = (
                len(attempts) >= 2
                and any(c in ("net_error", "server_error")
                        for c in classes)
                and "ok" in classes
                and bool(st.find("queue_wait"))
                and any(
                    "phasenet" in str(
                        (s.get("annotations") or {}).get("program"))
                    for s in fwd
                )
                and "replica" in ",".join(st.processes())
                and rel <= 0.10
            )
            verdicts.append({
                "trace_id": cand["trace_id"],
                "attempts": len(attempts), "classes": classes,
                "client_ms": client_ms,
                "total_ms": round(st.total_ms, 1),
                "rel": round(rel, 3), "ok": ok,
            })
            if ok:
                passed = st
                break
        assert passed is not None, (
            "no retried trace satisfied the stitched-trace acceptance "
            f"(both attempts + queue wait + device program span + total "
            f"within 10% of client latency): {verdicts}"
        )
        print(passed.format(), file=sys.stderr, flush=True)

        # The killed replica comes back (stamped: the relaunch stays up).
        _wait_probed_ready(host, port, 2, timeout_s=120.0)
    finally:
        rc, err = _stop_fleet(proc)
    assert rc == 0, err
    assert re.search(r"replica 0 crashed rc=-9; relaunch", err), err


def test_blackhole_circuit_opens_then_closes(tmp_path):
    """Acceptance: a black-holed replica (accepts + answers probes, never
    answers requests) is routed around via its circuit breaker within a
    bounded number of probes, and the circuit closes after recovery —
    while every client request still succeeds via the healthy replica."""
    proc, host, port = _start_fleet(
        tmp_path,
        env_extra={
            "SEIST_FAULT_SERVE_BLACKHOLE_AFTER": "2",
            "SEIST_FAULT_SERVE_BLACKHOLE_COUNT": "4",
            "SEIST_FAULT_SERVE_BLACKHOLE_HOLD_S": "120",
            "SEIST_FAULT_SERVE_REPLICA": "0",
        },
        fleet_args=(
            "--router-retries", "2",
            "--request-timeout-s", "1.5",
            "--breaker-failures", "2",
            "--breaker-cooldown-s", "0.3",
        ),
    )
    try:
        _wait_probed_ready(host, port, 2)
        body = json.dumps({
            "data": [[0.0, 0.0, 0.0]] * WINDOW,
            "options": {"timeout_ms": 30000.0},
        }).encode()
        failures, opens_seen, closed_after_open = [], False, False
        deadline = time.monotonic() + 90.0
        blackholed_url = None
        while time.monotonic() < deadline:
            conn = http.client.HTTPConnection(host, port, timeout=35)
            try:
                conn.request("POST", "/predict", body,
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                if resp.status != 200:
                    failures.append(resp.status)
            except OSError as e:
                failures.append(repr(e))
            finally:
                conn.close()
            _, payload = _get(host, port, "/router/replicas")
            snap = {
                r["url"]: r["breaker"] for r in payload["replicas"]
            }
            for url, breaker in snap.items():
                if breaker["state"] != "closed":
                    opens_seen = True
                    blackholed_url = url
            if (
                opens_seen
                and blackholed_url is not None
                and snap[blackholed_url]["state"] == "closed"
                and snap[blackholed_url]["opens"] >= 1
            ):
                closed_after_open = True
                break
            time.sleep(0.1)
        assert opens_seen, "circuit never opened on the black-holed replica"
        assert closed_after_open, (
            "circuit never closed after the black-hole recovered"
        )
        assert not failures, (
            f"client saw failures despite the breaker: {failures[:5]}"
        )
    finally:
        rc, err = _stop_fleet(proc)
    assert rc == 0, err


def test_rollout_flywheel_zero_downtime(tmp_path):
    """Acceptance (ISSUE 13): roll a 3-replica fleet to a new model
    version under sustained open-loop load — ZERO failed requests
    (error_rate 0.0), ZERO stale-version responses after convergence
    (bench_serve's --expect-version gate), with the roll visible per
    replica (drain -> relaunch -> ready) in the supervisor log."""
    spec = tmp_path / "rollout.json"
    proc, host, port = _start_fleet(
        tmp_path,
        replicas=3,
        fleet_args=(
            "--router-retries", "3",
            "--request-timeout-s", "30",
            "--rollout-file", str(spec),
            "--rollout-ready-timeout-s", "240",
        ),
    )
    try:
        _wait_probed_ready(host, port, 3)
        url = f"http://{host}:{port}"
        results = {}

        def run_bench():
            results["bench"] = _bench(
                url, tmp_path, "flywheel",
                "--arrival-rps", "5",
                "--duration-s", "150",
                "--concurrency", "32",
                "--timeout-ms", "30000",
                "--expect-version", "2",
            )

        bench_thread = threading.Thread(target=run_bench)
        bench_thread.start()
        time.sleep(3.0)  # load flowing against version 1 first
        spec.write_text(json.dumps({"version": 2}))
        proc.send_signal(signal.SIGHUP)
        bench_thread.join(timeout=400)
        assert not bench_thread.is_alive(), "bench never finished"
        rc, res = results["bench"]
        # Zero downtime: every request of the sustained run succeeded.
        assert res["errors"] == 0 and res["error_rate"] == 0.0, res
        # The run really spanned the roll: both versions answered...
        assert res["by_version"].get("1", 0) > 0, res
        assert res["by_version"].get("2", 0) > 0, res
        # ...the fleet converged during it, and afterwards not one
        # response carried the old version.
        assert res["converged_at_s"] > 0, res
        assert res["stale_after_convergence"] == 0, res
        assert rc == 0, res  # the bench's own rollout gate agrees
    finally:
        rc, err = _stop_fleet(proc, timeout=120)
    assert rc == 0, err
    # The roll is visible per replica, strictly one at a time.
    for i in range(3):
        assert f"rollout: draining replica {i}" in err, err
        assert re.search(
            rf"rollout: replica {i} ready \+ re-registered \(version 2\)",
            err,
        ), err
    assert err.index("rollout: replica 0 ready") < err.index(
        "rollout: draining replica 1"
    ), "replica 1 drained before replica 0 converged"
    assert err.index("rollout: replica 1 ready") < err.index(
        "rollout: draining replica 2"
    ), "replica 2 drained before replica 1 converged"
    assert "rollout complete: version 2" in err, err
    assert "clean preempt (rc=75)" in err, err


def test_canary_bad_candidate_auto_rollback(tmp_path):
    """Acceptance (ISSUE 13): an injected bad candidate
    (SEIST_FAULT_SERVE_BAD_CANDIDATE — elevated error rate on the
    candidate version) is drained back to 0% automatically, the
    incumbent cohort serves 100% of traffic, clients see no failures
    (router retries rescue every canary error), and the rollback event
    is on the bus and in the trace flags."""
    spec = tmp_path / "rollout.json"
    proc, host, port = _start_fleet(
        tmp_path,
        replicas=2,
        env_extra={"SEIST_FAULT_SERVE_BAD_CANDIDATE": "2"},
        fleet_args=(
            "--router-retries", "2",
            "--request-timeout-s", "30",
            # The canary policy, not the breaker, must do the draining.
            "--breaker-failures", "100",
            "--rollout-file", str(spec),
            "--rollout-ready-timeout-s", "240",
        ),
    )
    try:
        _wait_probed_ready(host, port, 2)
        url = f"http://{host}:{port}"
        # Canary stage: roll ONE replica to the (bad) candidate version.
        spec.write_text(json.dumps({"version": 2, "replicas": [0]}))
        proc.send_signal(signal.SIGHUP)
        deadline = time.monotonic() + 240.0
        while time.monotonic() < deadline:
            _, reg = _get(host, port, "/router/replicas")
            versions = sorted(
                r.get("versions", {}).get("phasenet", 0)
                for r in reg.get("replicas", [])
                if r["probe_state"] == "ok"
            )
            if versions == [1, 2]:
                break
            time.sleep(0.25)
        else:
            raise AssertionError("canary replica never came up on v2")

        # 40% canary with a tight budget over the candidate cohort.
        conn = http.client.HTTPConnection(host, port, timeout=10)
        try:
            body = json.dumps({
                "version": 2, "percent": 40,
                "max_error_delta": 0.2, "min_requests": 8,
            }).encode()
            conn.request("POST", "/router/canary", body,
                         {"Content-Type": "application/json"})
            assert conn.getresponse().status == 200
        finally:
            conn.close()

        rc, res = _bench(
            url, tmp_path, "canary",
            "--requests", "80", "--concurrency", "8",
            "--timeout-ms", "30000",
        )
        # No client-visible failures: every candidate 500 was retried
        # onto the incumbent cohort within the request.
        assert res["errors"] == 0 and res["error_rate"] == 0.0, res

        _, canary = _get(host, port, "/router/canary")
        assert canary["state"] == "rolled_back", canary
        assert canary["percent"] == 0.0, canary
        assert "error-rate delta" in canary["rollback_reason"], canary
        assert canary["cohorts"]["candidate"]["errors"] >= 8, canary

        # Drained to 0%: the candidate replica takes not one more
        # request while the incumbent serves all of a follow-up run.
        _, reg = _get(host, port, "/router/replicas")
        cand = next(
            r for r in reg["replicas"]
            if r.get("versions", {}).get("phasenet") == 2
        )
        routed_at_rollback = cand["routed"]
        rc2, res2 = _bench(
            url, tmp_path, "post_rollback",
            "--requests", "24", "--concurrency", "6",
            "--timeout-ms", "30000",
        )
        assert res2["errors"] == 0, res2
        assert res2["by_version"] == {"1": 24}, res2
        _, reg2 = _get(host, port, "/router/replicas")
        cand2 = next(
            r for r in reg2["replicas"]
            if r.get("versions", {}).get("phasenet") == 2
        )
        assert cand2["routed"] == routed_at_rollback, (
            cand2, routed_at_rollback
        )

        # The rollback event: bus counter + flagged trace.
        _, text = _get(host, port, "/metrics")
        assert "router_canary_rollback" in text
        _, idx = _get(host, port, "/traces")
        assert any(
            "canary_rollback" in t["flags"] for t in idx["traces"]
        ), [t["flags"] for t in idx["traces"][:10]]
    finally:
        rc, err = _stop_fleet(proc, timeout=120)
    assert rc == 0, err


def test_overload_sheds_batch_tier_protects_alert_slo(tmp_path):
    """Acceptance: at ~2x the sustainable arrival rate the batch tier is
    shed with the DISTINCT 503 'shed' verdict (Retry-After semantics, not
    the queue-full 429) while the alert tier's p99 passes its SLO gate —
    both measured by the extended bench_serve."""
    proc, host, port = _start_fleet(
        tmp_path,
        env_extra={"SEIST_FAULT_SERVE_SLOW_MS": "150"},
        replicas=1,
        fleet_args=("--router-retries", "0", "--request-timeout-s", "60"),
        replica_args=(
            "--shed-batch-delay-ms", "30",
            "--shed-interactive-delay-ms", "100000",
            "--max-queue", "512",
        ),
    )
    try:
        _wait_probed_ready(host, port, 1)
        url = f"http://{host}:{port}"
        # Sustainable ~= max_batch 4 / (150 ms injected + real forward)
        # <= ~25 rps; batch offers ~4x that. The alert tier offers only
        # 5 rps — far enough under even a contended-CPU capacity that
        # its latency is pure queue-delay, i.e. exactly what shedding
        # the batch tier is supposed to protect.
        results = {}

        def run(tag, *extra):
            results[tag] = _bench(url, tmp_path, tag, *extra)

        alert = threading.Thread(
            target=run,
            args=(
                "alert",
                "--priority", "alert",
                "--arrival-rps", "5",
                "--requests", "60",
                "--concurrency", "64",
                "--timeout-ms", "30000",
                "--slo-p99-ms", "10000",
                # one refused TCP accept under the batch hammering is a
                # client-socket artifact, not a shed/latency failure
                "--max-error-rate", "0.05",
            ),
        )
        batch = threading.Thread(
            target=run,
            args=(
                "batch",
                "--priority", "batch",
                "--arrival-rps", "100",
                "--requests", "600",
                "--concurrency", "64",
                "--timeout-ms", "30000",
            ),
        )
        alert.start()
        batch.start()
        alert.join(timeout=300)
        batch.join(timeout=300)
        rc_alert, res_alert = results["alert"]
        rc_batch, res_batch = results["batch"]
        # Low tier: actually shed, with the shed error-class code (not 429).
        assert res_batch["by_error_code"].get("shed", 0) > 0, res_batch
        assert res_batch["by_status"].get("503", 0) > 0, res_batch
        # High tier: NEVER shed, and p99 inside the SLO (the gate's rc).
        assert res_alert["by_error_code"].get("shed", 0) == 0, res_alert
        assert rc_alert == 0, res_alert
        # Replica-side shed counters scrape via the PR 6 bus.
        _, payload = _get(host, port, "/router/replicas")
        replica_url = payload["replicas"][0]["url"]
        rhost, rport = replica_url.split(":")
        _, text = _get(rhost, int(rport), "/metrics?format=prometheus")
        assert "seist_serve_shed" in text, text[:500]
    finally:
        rc, err = _stop_fleet(proc)
    assert rc == 0, err
