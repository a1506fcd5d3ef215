"""Batch-fleet supervisor: N lease-based repick workers, relaunched
through preemption and crashes, then a fence-audited merge.

The fleet counterpart of tools/supervise_fleet.py (serving) for the
batch plane (docs/FAULT_TOLERANCE.md "Batch fleet faults"): spawn N
``tools/repick_archive --fleet`` workers over one shared lease
directory and keep the fleet converging without human intervention —

* **exit 75** (the PR 2 preemption contract: SIGTERM -> drain the
  current segment -> release the lease -> exit) schedules a RELAUNCH
  after ``--rejoin-delay-s``, without consuming the crash budget; while
  the worker is away its released/expired leases are reclaimed by
  peers, and on rejoin it steals whatever work is still open;
* **any other nonzero exit** (SIGKILL, OOM, a real bug) consumes one
  unit of that worker's ``--retries`` crash budget and relaunches
  immediately; a worker that exhausts its budget is ABANDONED — the
  fleet still finishes, because its leases expire and peers reclaim
  them (the supervisor only fails when EVERY worker is gone);
* after the last worker joins, the reduce runs with the lease store's
  done-fence ledger so the merge audits every segment's fence sidecar
  (a zombie-written segment refuses the merge — ``batch/catalog.py``).

Per-worker fault injection for the chaos lane: ``--fault-env
i:KEY=VALUE`` (repeatable) scopes SEIST_FAULT_BATCH_* knobs to worker
``i`` only; every worker additionally gets ``SEIST_BATCH_WORKER=<i>``
and its own stamp file, so kill/preempt faults fire once across that
worker's relaunches. Worker stdout goes to per-incarnation log files
under ``<out>/logs/`` and the final verdict aggregates every
incarnation's lease counters (acquire/renew/reclaim/fence-reject/
double-commit) — the numbers ``make batch-chaos`` gates on.

    python -m tools.supervise_repick --archive A --out O \
        --model phasenet --workers 3 --lease-dir O/leases

Prints ONE JSON verdict line (role "supervisor").
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

from tools.device_procs import refuse_shared_chip
from tools.repick_archive import _archive_index, _units_from_cols

PREEMPT_EXIT_CODE = 75  # train.checkpoint contract (import-free: no jax here)

#: lease counter keys aggregated across every worker incarnation
_LEASE_KEYS = (
    "acquires", "reclaims", "renews", "releases", "expires",
    "fence_rejects", "double_commits", "store_errors", "parks",
)


def get_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="python -m tools.supervise_repick", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--archive", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--model", default="")
    ap.add_argument("--model-group", default="")
    ap.add_argument("--tasks", default="")
    ap.add_argument("--variant", default="fp32",
                    choices=("fp32", "bf16", "int8"))
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--batches-per-call", type=int, default=4)
    ap.add_argument("--commit-every", type=int, default=4)
    ap.add_argument("--prefetch", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workers", type=int, default=3,
                    help="fleet size (worker indices 0..N-1)")
    ap.add_argument("--lease-dir", required=True,
                    help="shared lease-store directory (created if absent)")
    ap.add_argument("--retries", type=int, default=2,
                    help="crash-relaunch budget per worker (exit-75 "
                    "preempt relaunches never consume it)")
    ap.add_argument("--rejoin-delay-s", type=float, default=0.5,
                    help="delay before relaunching a preempted (exit-75) "
                    "worker — the window in which peers reclaim its units")
    ap.add_argument("--fault-env", action="append", default=[],
                    metavar="I:KEY=VALUE",
                    help="inject KEY=VALUE into worker I's environment "
                    "only (repeatable; scopes SEIST_FAULT_BATCH_* knobs "
                    "per worker for the chaos lane)")
    ap.add_argument("--compile-gate", action="store_true")
    ap.add_argument("--no-merge", action="store_true")
    ap.add_argument("--timeout-s", type=float, default=900.0,
                    help="overall fleet deadline (a wedged fleet must "
                    "fail loudly, not hang CI)")
    args = ap.parse_args(argv)
    if bool(args.model) == bool(args.model_group):
        ap.error("exactly one of --model / --model-group is required")
    return args


def _parse_fault_env(specs: List[str], n_workers: int) -> Dict[int, Dict[str, str]]:
    out: Dict[int, Dict[str, str]] = {i: {} for i in range(n_workers)}
    for spec in specs:
        head, sep, val = spec.partition("=")
        idx_s, sep2, key = head.partition(":")
        if not sep or not sep2 or not key:
            raise SystemExit(f"bad --fault-env '{spec}' (want I:KEY=VALUE)")
        idx = int(idx_s)
        if idx not in out:
            raise SystemExit(
                f"--fault-env '{spec}': worker {idx} out of range "
                f"(fleet has {n_workers})"
            )
        out[idx][key] = val
    return out


def _worker_cmd(args, i: int) -> List[str]:
    cmd = [
        sys.executable, "-m", "tools.repick_archive",
        "--archive", args.archive, "--out", args.out,
        "--variant", args.variant,
        "--batch-size", str(args.batch_size),
        "--batches-per-call", str(args.batches_per_call),
        "--commit-every", str(args.commit_every),
        "--prefetch", str(args.prefetch),
        "--seed", str(args.seed),
        "--fleet", "--lease-dir", args.lease_dir,
        "--lease-store", "dir",
        "--worker-index", str(i),
        "--worker-id", f"w{i}",
        "--no-merge",
    ]
    if args.model:
        cmd += ["--model", args.model]
    if args.model_group:
        cmd += ["--model-group", args.model_group]
    if args.tasks:
        cmd += ["--tasks", args.tasks]
    if args.compile_gate:
        cmd += ["--compile-gate"]
    return cmd


class _Worker:
    """One worker slot: its process, crash budget, incarnation logs,
    and (for exit-75) its scheduled rejoin time."""

    def __init__(self, index: int, budget: int, fault_env: Dict[str, str],
                 log_dir: str, stamp_dir: str):
        self.index = index
        self.budget = budget
        self.fault_env = fault_env
        self.log_dir = log_dir
        self.stamp = os.path.join(stamp_dir, f"w{index}.stamp")
        self.incarnation = 0
        self.proc: Optional[subprocess.Popen] = None
        self.log_f = None
        self.logs: List[str] = []
        self.rejoin_at: Optional[float] = None  # monotonic
        self.done = False
        self.failed = False
        self.relaunches = 0
        self.preempts = 0
        self.crashes = 0

    def launch(self, args) -> None:
        self.incarnation += 1
        if self.incarnation > 1:
            self.relaunches += 1
        path = os.path.join(
            self.log_dir, f"w{self.index}.{self.incarnation:02d}.log"
        )
        self.logs.append(path)
        env = dict(os.environ)
        env["SEIST_BATCH_WORKER"] = str(self.index)
        if self.fault_env:
            env["SEIST_FAULT_STAMP"] = self.stamp
            env.update(self.fault_env)
        self.log_f = open(path, "w")
        self.proc = subprocess.Popen(
            _worker_cmd(args, self.index),
            stdout=self.log_f, stderr=subprocess.STDOUT, env=env,
        )
        self.rejoin_at = None

    def close_log(self) -> None:
        if self.log_f is not None:
            self.log_f.close()
            self.log_f = None


def _drain_verdicts(w: _Worker) -> List[dict]:
    """Every fleet-worker verdict line this slot's incarnations printed
    (a SIGKILL'd incarnation prints none — that's expected)."""
    out = []
    for path in w.logs:
        try:
            with open(path) as f:
                for line in f:
                    try:
                        d = json.loads(line)
                    except ValueError:
                        continue
                    if d.get("role") == "fleet-worker":
                        out.append(d)
        except FileNotFoundError:
            pass
    return out


def main(argv=None) -> int:
    args = get_args(argv)
    t0 = time.monotonic()
    os.makedirs(args.out, exist_ok=True)
    os.makedirs(args.lease_dir, exist_ok=True)
    log_dir = os.path.join(args.out, "logs")
    os.makedirs(log_dir, exist_ok=True)
    refuse_shared_chip(args.workers, "supervise_repick")
    fault_env = _parse_fault_env(args.fault_env, args.workers)

    workers = [
        _Worker(i, args.retries, fault_env[i], log_dir, log_dir)
        for i in range(args.workers)
    ]
    for w in workers:
        w.launch(args)

    deadline = t0 + args.timeout_s
    while True:
        live = [w for w in workers if w.proc is not None]
        waiting = [w for w in workers if w.rejoin_at is not None]
        if not live and not waiting:
            break
        if time.monotonic() > deadline:
            for w in live:
                w.proc.kill()
                w.close_log()
            print(json.dumps({
                "ok": False, "role": "supervisor",
                "error": f"fleet deadline {args.timeout_s}s exceeded",
            }))
            return 1
        now = time.monotonic()
        for w in list(waiting):
            if now >= w.rejoin_at:
                w.launch(args)
        for w in list(live):
            rc = w.proc.poll()
            if rc is None:
                continue
            w.proc = None
            w.close_log()
            if rc == 0:
                w.done = True
            elif rc == PREEMPT_EXIT_CODE:
                w.preempts += 1
                w.rejoin_at = time.monotonic() + args.rejoin_delay_s
            elif w.budget > 0:
                w.budget -= 1
                w.crashes += 1
                w.launch(args)
            else:
                w.crashes += 1
                w.failed = True
        time.sleep(0.1)

    finished = [w for w in workers if w.done]
    if not finished:
        print(json.dumps({
            "ok": False, "role": "supervisor",
            "error": "every worker exhausted its relaunch budget",
            "crashes": sum(w.crashes for w in workers),
        }))
        return 1

    lease = {k: 0 for k in _LEASE_KEYS}
    verdicts = 0
    for w in workers:
        for v in _drain_verdicts(w):
            verdicts += 1
            for k in _LEASE_KEYS:
                lease[k] += int(v.get("lease", {}).get(k, 0))

    verdict: Dict[str, Any] = {
        "ok": True,
        "role": "supervisor",
        "workers": args.workers,
        "finished": len(finished),
        "abandoned": [w.index for w in workers if w.failed],
        "relaunches": sum(w.relaunches for w in workers),
        "preempts": sum(w.preempts for w in workers),
        "crashes": sum(w.crashes for w in workers),
        "worker_verdicts": verdicts,
        "lease": lease,
    }
    if not args.no_merge:
        from tools.repick_archive import _merge

        meta, cols = _archive_index(args.archive)
        units = _units_from_cols(cols)
        merged = _merge(args, meta, units, print_verdict=False)
        verdict["rows"] = merged["rows"]
        verdict["units"] = merged["units"]
        verdict["fence_audit"] = merged.get("fence_audit")
    verdict["wall_s"] = round(time.monotonic() - t0, 2)
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
