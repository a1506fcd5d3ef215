"""Batch-fleet chaos lane: SIGKILL + exit-75 preempt + lease-store
partition, all injected mid-archive — merged catalog byte-identical.

The ``make batch-chaos`` headline (docs/FAULT_TOLERANCE.md "Batch fleet
faults"): a 3-worker lease fleet (tools/supervise_repick.py) re-picks a
synthetic packed archive while every failure class the lease plane
exists for fires at once —

* **worker 0** loses the lease store entirely (an injected partition
  window opening shortly after its first lease op): it commits its
  in-flight segments while the lease is still locally valid, PARKS on
  the done-marker write, and heals into the discovery that a peer
  reclaimed + completed its unit — the zombie completion is refused by
  the fence ladder (fence_rejects >= 1, the counter this lane proves is
  live);
* **worker 1** is SIGKILL'd at its first lease acquisition (hard crash,
  no handlers): its lease expires, a peer reclaims at the next fence,
  and the supervisor's crash budget relaunches the worker;
* **worker 2** is SIGTERM'd at its first acquisition (the exit-75
  preemption contract): it drains, releases its lease, exits 75, and
  rejoins after a delay to steal whatever is still open.

Gates: the fleet finishes without human intervention (supervisor rc 0);
the merged catalog's sha256 EQUALS the serial no-fault run's (the
paper-scale invariant: chaos may cost time, never bytes); ZERO
double-committed segments; fence_rejects >= 1 (under chaos the counter
must account the zombie attempt — in a clean run it must be zero, which
``tests/test_batch_fleet.py`` pins).

Geometry is tools/repick_smoke.py's ON PURPOSE: the same programs
lower, so the persistent XLA compile cache is warm for every worker
incarnation. One JSON verdict line; exit 0 iff every gate holds.

    python -m tools.batch_chaos            # the make lane
    python -m tools.batch_chaos --runs 3   # the acceptance loop
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List

from tools.device_procs import refuse_shared_chip

# repick_smoke geometry (warm XLA cache across lanes): 44 events over
# 16-sample shards -> 3 shards == 3 work units, one per worker.
N_EVENTS = 44
TRACE = 256
SPS = 16
BATCH = 4
BPC = 2  # rows_per_call = 8 -> 2 calls/unit
COMMIT = 1  # -> 2 segments/unit: a partition can land BETWEEN commits

#: lease clocks for the scenario (seconds). TTL/heartbeat are shrunk so
#: expiry-reclaim happens in seconds; the partition window is sized so
#: worker 0 commits inside it but its TTL lapses before it heals.
LEASE_ENV = {
    "SEIST_LEASE_TTL_S": "2.5",
    "SEIST_LEASE_HEARTBEAT_S": "0.5",
    "SEIST_LEASE_GRACE_S": "0.5",
    "SEIST_LEASE_OP_TIMEOUT_S": "1.0",
    "SEIST_LEASE_RETRIES": "3",
    "SEIST_LEASE_BACKOFF_MS": "30",
    "SEIST_LEASE_BACKOFF_CAP_MS": "200",
    "SEIST_LEASE_PARK_S": "0.3",
}

#: per-device-call sleep making unit runtime fault-window-sized (sleep,
#: not compute: the host budget is one core)
SLOW_MS = "400"

#: worker 0's partition: opens 0.6s after its first lease op (mid-unit,
#: after seg 0's fence check, before seg 1's). The window must dominate
#: the PEERS' schedule, not just TTL+grace: the fence reject fires only
#: if a peer reclaims w0's expired unit (and writes its done marker —
#: cheap, the committed segments resume-scan as already present) BEFORE
#: w0 heals and retries its own parked done-marker write. Both peers
#: pay a full process relaunch (kill + preempt) of ~15-25s on a loaded
#: 1-core host, so a short window lets w0 win its own race back and the
#: zombie never forms; 60s covers the slowest observed relaunch cycle
#: (~50s) with margin.
PARTITION_AFTER_S = "0.6"
PARTITION_FOR_S = "60"


def _sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _pack(archive: str) -> None:
    from seist_tpu.data.packed import PackSource, pack_sources

    pack_sources(
        [PackSource(
            name="synthetic",
            dataset_kwargs={
                "num_events": N_EVENTS, "trace_samples": TRACE,
                "cache": False,
            },
        )],
        archive,
        num_workers=1,
        samples_per_shard=SPS,
    )


def _last_json(text: str, role: str) -> Dict[str, Any]:
    for line in reversed(text.strip().splitlines()):
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if d.get("role") == role:
            return d
    raise SystemExit(f"no '{role}' verdict in output: {text[-400:]}")


def _repick_args(archive: str, out: str) -> List[str]:
    return [
        "--archive", archive, "--out", out, "--model", "phasenet",
        "--batch-size", str(BATCH), "--batches-per-call", str(BPC),
        "--commit-every", str(COMMIT),
    ]


def _serial(archive: str, out: str) -> str:
    """Clean single-process reference run -> catalog sha256."""
    env = dict(os.environ)
    env.pop("SEIST_FAULT_REPICK_SLOW_MS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.repick_archive",
         *_repick_args(archive, out)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=600,
    )
    if proc.returncode != 0:
        print(proc.stdout[-2000:], file=sys.stderr)
        raise SystemExit(f"serial reference run rc={proc.returncode}")
    return _sha256(os.path.join(out, "catalog.jsonl"))


def _fleet(archive: str, out: str) -> Dict[str, Any]:
    """The 3-worker chaos fleet -> supervisor verdict."""
    lease_dir = os.path.join(out, "leases")
    env = dict(os.environ)
    env.update(LEASE_ENV)
    env["SEIST_FAULT_REPICK_SLOW_MS"] = SLOW_MS
    cmd = [
        sys.executable, "-m", "tools.supervise_repick",
        *_repick_args(archive, out),
        "--workers", "3", "--lease-dir", lease_dir,
        "--retries", "2", "--rejoin-delay-s", "1.0",
        "--timeout-s", "300",
        # worker 0: lease-store partition mid-unit
        "--fault-env", f"0:SEIST_FAULT_BATCH_PARTITION_AFTER_S={PARTITION_AFTER_S}",
        "--fault-env", f"0:SEIST_FAULT_BATCH_PARTITION_FOR_S={PARTITION_FOR_S}",
        # worker 1: SIGKILL at its first lease acquisition
        "--fault-env", "1:SEIST_FAULT_BATCH_KILL_UNIT=1",
        # worker 2: exit-75 preempt at its first lease acquisition
        "--fault-env", "2:SEIST_FAULT_BATCH_PREEMPT_UNIT=1",
    ]
    proc = subprocess.run(
        cmd, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=600,
    )
    if proc.returncode != 0:
        print(proc.stdout[-4000:], file=sys.stderr)
        raise SystemExit(f"chaos fleet rc={proc.returncode}")
    return _last_json(proc.stdout, "supervisor")


def _one_run(root: str, run: int) -> Dict[str, Any]:
    archive = os.path.join(root, "archive")
    if not os.path.isdir(archive):
        _pack(archive)
    serial_out = os.path.join(root, f"serial_{run}")
    fleet_out = os.path.join(root, f"fleet_{run}")
    serial_sha = _serial(archive, serial_out)
    sup = _fleet(archive, fleet_out)
    fleet_sha = _sha256(os.path.join(fleet_out, "catalog.jsonl"))
    lease = sup.get("lease", {})
    gates = {
        "fleet_finished": bool(sup.get("ok")),
        "byte_identical": fleet_sha == serial_sha,
        "zero_double_commits": int(lease.get("double_commits", -1)) == 0,
        "fence_reject_counted": int(lease.get("fence_rejects", 0)) >= 1,
        "kill_fired": int(sup.get("crashes", 0)) >= 1,
        "preempt_fired": int(sup.get("preempts", 0)) >= 1,
    }
    return {
        "run": run,
        "ok": all(gates.values()),
        "gates": gates,
        "sha256": fleet_sha,
        "serial_sha256": serial_sha,
        "supervisor": {
            k: sup.get(k)
            for k in ("relaunches", "preempts", "crashes", "abandoned",
                      "rows", "units", "wall_s")
        },
        "lease": lease,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.batch_chaos",
        description=__doc__.splitlines()[0],
    )
    ap.add_argument("--runs", type=int, default=1,
                    help="repeat the scenario N times (acceptance: 3)")
    ap.add_argument("--keep", action="store_true",
                    help="keep the scratch directory for inspection")
    args = ap.parse_args(argv)

    refuse_shared_chip(3, "batch_chaos")  # the 3-worker chaos fleet
    t0 = time.monotonic()
    root = tempfile.mkdtemp(prefix="batch_chaos_")
    try:
        runs = [_one_run(root, i) for i in range(args.runs)]
        verdict = {
            "ok": all(r["ok"] for r in runs),
            "role": "batch-chaos",
            "runs": len(runs),
            "gates": {
                k: all(r["gates"][k] for r in runs)
                for k in runs[0]["gates"]
            },
            "sha256": runs[0]["sha256"],
            "supervisor": [r["supervisor"] for r in runs],
            "lease": [r["lease"] for r in runs],
            "wall_s": round(time.monotonic() - t0, 1),
        }
        print(json.dumps(verdict))
        return 0 if verdict["ok"] else 1
    finally:
        if not args.keep:
            shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
