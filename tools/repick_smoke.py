"""Repick kill/resume smoke: map-reduce catalog == serial catalog, bytes.

The ``make repick-smoke`` lane (docs/DATA.md "Batch re-picking"):

1. pack a synthetic archive (3 shards, a partial tail);
2. SERIAL reference: one in-process ``tools.repick_archive`` run ->
   ``catalog.jsonl`` bytes;
3. MAP-REDUCE run: two worker SUBPROCESSES over the same archive
   (``SEIST_FAULT_REPICK_SLOW_MS`` slows worker 0 so the kill lands
   mid-shard deterministically); worker 0 is SIGKILL'd after its first
   segment commit, relaunched (resume at the exact segment offset),
   then the reduce merges;
4. assert the merged catalog is BYTE-IDENTICAL to the serial one and
   that every worker's ``CompileBudget`` window after warm-up recorded
   ZERO compiles.

Prints ONE JSON verdict line; exit 0 iff every assertion held.
"""

from __future__ import annotations

import glob
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

N_EVENTS = 44
TRACE = 256
SPS = 16  # 3 shards: 16 + 16 + 12 (partial tail unit)
BATCH = 4
BPC = 2  # batches per call -> 8 rows/call
COMMIT = 1  # one call per segment: several segments per unit


def _repick_args(archive: str, out: str):
    return [
        "--archive", archive, "--out", out, "--model", "phasenet",
        "--batch-size", str(BATCH), "--batches-per-call", str(BPC),
        "--commit-every", str(COMMIT),
    ]


def _worker_cmd(archive: str, out: str, index: int):
    return [
        sys.executable, "-m", "tools.repick_archive",
        *_repick_args(archive, out),
        "--worker-index", str(index), "--num-workers", "2",
        "--no-merge", "--compile-gate",
    ]


def main() -> int:
    import seist_tpu
    from seist_tpu.data.packed import PackSource, pack_sources
    from tools.device_procs import refuse_shared_chip

    # this process runs the serial reference, then two workers at once
    refuse_shared_chip(3, "repick_smoke")
    seist_tpu.load_all()
    t0 = time.monotonic()
    root = tempfile.mkdtemp(prefix="repick_smoke_")
    archive = os.path.join(root, "archive")
    pack_sources(
        [PackSource(
            name="synthetic",
            dataset_kwargs={
                "num_events": N_EVENTS, "trace_samples": TRACE,
                "cache": False,
            },
        )],
        archive,
        samples_per_shard=SPS,
    )

    # --- serial reference ------------------------------------------------
    from tools.repick_archive import main as repick_main

    serial_out = os.path.join(root, "serial")
    rc = repick_main(_repick_args(archive, serial_out))
    assert rc == 0, f"serial repick rc={rc}"
    with open(os.path.join(serial_out, "catalog.jsonl"), "rb") as f:
        ref = f.read()

    # --- 2-worker map with a SIGKILL mid-shard ---------------------------
    mr_out = os.path.join(root, "mapreduce")
    env = dict(os.environ)
    env0 = dict(env, SEIST_FAULT_REPICK_SLOW_MS="300")  # kill lands mid-unit
    w0 = subprocess.Popen(_worker_cmd(archive, mr_out, 0), env=env0,
                          stdout=subprocess.PIPE, text=True)
    w1 = subprocess.Popen(_worker_cmd(archive, mr_out, 1), env=env,
                          stdout=subprocess.PIPE, text=True)

    # SIGKILL worker 0 as soon as its first segment commits (unit 0 has
    # 2 segments at this geometry, so the kill is mid-shard by
    # construction; the slow-call fault keeps it from finishing first).
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        if glob.glob(os.path.join(mr_out, "unit_00000.seg_*.jsonl")):
            break
        if w0.poll() is not None:
            raise SystemExit("worker 0 exited before its first commit")
        time.sleep(0.02)
    else:
        raise SystemExit("worker 0 never committed a segment")
    w0.send_signal(signal.SIGKILL)
    w0.wait()
    killed_at = len(glob.glob(os.path.join(mr_out, "unit_00000.seg_*.jsonl")))
    out1, _ = w1.communicate(timeout=600)
    assert w1.returncode == 0, f"worker 1 rc={w1.returncode}"

    # Relaunch worker 0 WITHOUT the slow fault: resumes at its exact
    # segment offset and finishes.
    w0b = subprocess.Popen(_worker_cmd(archive, mr_out, 0), env=env,
                           stdout=subprocess.PIPE, text=True)
    out0, _ = w0b.communicate(timeout=600)
    assert w0b.returncode == 0, f"resumed worker 0 rc={w0b.returncode}"

    # --- reduce + asserts (model-free: geometry/identity from the plan
    # file, so no --model and deliberately NO geometry flags) -------------
    rc = repick_main(
        ["--archive", archive, "--out", mr_out, "--merge-only"]
    )
    assert rc == 0, f"merge rc={rc}"
    with open(os.path.join(mr_out, "catalog.jsonl"), "rb") as f:
        got = f.read()
    identical = got == ref

    def _verdict_line(text: str) -> dict:
        for line in reversed(text.strip().splitlines()):
            try:
                d = json.loads(line)
            except ValueError:
                continue
            if d.get("role") == "worker":
                return d
        raise SystemExit(f"no worker verdict in output: {text[-400:]}")

    v0, v1 = _verdict_line(out0), _verdict_line(out1)
    compiles = v0.get("compiles_after_warmup", -1) + v1.get(
        "compiles_after_warmup", -1
    )
    resumed_skip = v0.get("segments_skipped", 0)
    verdict = {
        "ok": bool(
            identical
            and compiles == 0
            and v0["ok"] and v1["ok"]
        ),
        "byte_identical": identical,
        "rows": len(ref.splitlines()),
        "killed_after_segments": killed_at,
        "resumed_worker_segments": v0.get("segments", 0),
        "compiles_after_warmup": compiles,
        "wall_s": round(time.monotonic() - t0, 1),
        "out": mr_out,
    }
    print(json.dumps(verdict))
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
