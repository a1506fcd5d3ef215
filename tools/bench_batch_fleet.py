"""Batch-fleet scaling lane: 3 lease workers vs 1, byte-identity gated
-> BENCH_batch_fleet_r01.json.

The fleet's economic claim is linear-ish scaling — N workers re-pick an
archive ~N x faster than one, because leases partition the units with
no coordination on the hot path (one acquire + a heartbeat per unit,
against seconds of device compute). This lane measures it: the same
synthetic packed archive re-picked (a) by one fleet worker and (b) by a
3-worker fleet under tools/supervise_repick.py, wall-clock compared
AFTER each worker's warm-up (compile time is a fixed per-process cost
the persistent XLA cache amortizes; the scaling story is about the feed
loop).

Two gates, one hard and one hardware-conditional:

* **byte-identity (hard)** — sha256(catalog.jsonl) of the 3-worker
  fleet EQUALS the 1-worker run's. Fleet concurrency may never cost
  bytes; a scaling number for a diverging catalog would be meaningless.
* **scaling (>= --min-speedup, >= 3 cores only)** — on a single-core
  host 3 compute-bound workers just time-slice one CPU, so the gate is
  recorded as ``pending`` and the measured speedup is logged, not
  enforced. On a >= 3-core host it gates.

Three worker processes each initialise a JAX device, and a chip belongs to
one process at a time, so this lane runs on the CPU backend only
(tools/device_procs.py refuses anything else); fleet scaling across chips
is not measured.

Writes the BENCH JSON (--out) and prints it. Exit 0 iff every
applicable gate holds.
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
from typing import Any, Dict

from tools.batch_chaos import BATCH, BPC, COMMIT, _pack, _repick_args
from tools.device_procs import refuse_shared_chip

_DEF_OUT = "BENCH_batch_fleet_r01.json"


def _sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _last_json(text: str, role: str) -> Dict[str, Any]:
    for line in reversed(text.strip().splitlines()):
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if d.get("role") == role:
            return d
    raise SystemExit(f"no '{role}' verdict in output: {text[-400:]}")


def _run_fleet(archive: str, out: str, workers: int, slow_ms: int) -> Dict[str, Any]:
    lease_dir = os.path.join(out, "leases")
    env = dict(os.environ)
    env["SEIST_FAULT_REPICK_SLOW_MS"] = str(slow_ms)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.supervise_repick",
         *_repick_args(archive, out),
         "--workers", str(workers), "--lease-dir", lease_dir,
         "--timeout-s", "420"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=600,
    )
    if proc.returncode != 0:
        print(proc.stdout[-3000:], file=sys.stderr)
        raise SystemExit(f"{workers}-worker fleet rc={proc.returncode}")
    return _last_json(proc.stdout, "supervisor")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.bench_batch_fleet",
        description=__doc__.splitlines()[0],
    )
    ap.add_argument("--out", default=_DEF_OUT)
    ap.add_argument("--min-speedup", type=float, default=1.8,
                    help="3-vs-1 wall-clock gate (>= 3 cores only)")
    ap.add_argument("--slow-ms", type=int, default=150,
                    help="per-device-call sleep standing in for real "
                    "device latency — sleeps overlap across workers "
                    "even on one core, so the lease plane's overhead "
                    "is what the ratio exposes")
    ap.add_argument("--keep", action="store_true")
    args = ap.parse_args(argv)

    refuse_shared_chip(3, "bench_batch_fleet")
    cores = os.cpu_count() or 1
    root = tempfile.mkdtemp(prefix="bench_batch_fleet_")
    try:
        archive = os.path.join(root, "archive")
        _pack(archive)
        sup1 = _run_fleet(
            archive, os.path.join(root, "one"), 1, args.slow_ms
        )
        sup3 = _run_fleet(
            archive, os.path.join(root, "three"), 3, args.slow_ms
        )
        sha1 = _sha256(os.path.join(root, "one", "catalog.jsonl"))
        sha3 = _sha256(os.path.join(root, "three", "catalog.jsonl"))
        speedup = round(sup1["wall_s"] / sup3["wall_s"], 2)
        scaling_gated = cores >= 3
        identical = sha1 == sha3
        ok = identical and (speedup >= args.min_speedup or not scaling_gated)
        bench = {
            "metric": "batch_fleet_scaling_3v1",
            "value": speedup,
            "unit": "wall-clock speedup, 3-worker lease fleet vs 1 "
                    "(supervise_repick end-to-end incl. merge)",
            "gate_min_speedup": args.min_speedup,
            "scaling_gate": (
                "enforced" if scaling_gated
                else f"pending ({cores} core host: 3 compute-bound "
                     "workers time-slice one CPU)"
            ),
            "byte_identical": identical,
            "sha256": sha3,
            "wall_s": {"workers_1": sup1["wall_s"],
                       "workers_3": sup3["wall_s"]},
            "rows": sup3.get("rows"),
            "units": sup3.get("units"),
            "lease_ops_3w": sup3.get("lease"),
            "config": {
                "model": "phasenet", "batch": BATCH,
                "batches_per_call": BPC, "commit_every": COMMIT,
                "slow_ms": args.slow_ms, "host_cores": cores,
            },
            # refuse_shared_chip pinned it: the only backend N worker
            # processes can share
            "backend": os.environ["JAX_PLATFORMS"],
            "measured_at": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
            ),
            "pass": bool(ok),
        }
        with open(args.out, "w") as f:
            json.dump(bench, f, indent=1)
            f.write("\n")
        print(json.dumps(bench))
        return 0 if ok else 1
    finally:
        if not args.keep:
            shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
