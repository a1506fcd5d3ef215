"""Batch re-picking throughput headline vs the serving path -> BENCH JSON.

The ISSUE-15 acceptance number: waveforms/sec/chip for the straight-line
batch engine (tools/repick_archive.py over a packed archive) must be
>= 5x the serve-path per-chip throughput (tools/bench_serve.py, same
model, same host, same window) — the whole point of a dedicated batch
plane is that an archive re-pick must never ride the request path.

Both measurements run in-process on the same device:

* **batch** — pack a synthetic archive, run the inline map-reduce
  (``tools.repick_archive`` verbatim — the measured path IS the shipped
  tool), read the worker verdict's ``waveforms_per_sec`` + per-stage
  budget (fill / device / decode / write, the ``step_breakdown`` idiom);
* **serve** — ``tools.bench_serve`` closed-loop against the in-process
  service (micro-batcher + AOT programs + per-request decode), read
  ``throughput_rps`` (one waveform per request).

Writes ``BENCH_repick_r01.json``-style output (--out) and prints it.
Exit 0 iff the >= --min-speedup gate holds.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile
import time


def _last_json(text: str, role=None) -> dict:
    for line in reversed(text.strip().splitlines()):
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if role is None or d.get("role") == role:
            return d
    raise SystemExit(f"no JSON verdict found in: {text[-400:]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m tools.bench_repick")
    ap.add_argument("--model", default="phasenet")
    ap.add_argument("--events", type=int, default=1024)
    ap.add_argument("--trace", type=int, default=256,
                    help="archive window length (= model window)")
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--batches-per-call", type=int, default=4)
    ap.add_argument("--serve-requests", type=int, default=64)
    ap.add_argument("--serve-concurrency", type=int, default=8)
    ap.add_argument("--serve-max-batch", type=int, default=8)
    ap.add_argument("--min-speedup", type=float, default=5.0)
    ap.add_argument("--out", default="BENCH_repick_r01.json")
    args = ap.parse_args(argv)

    import jax

    import seist_tpu
    from seist_tpu.data.packed import PackSource, pack_sources

    seist_tpu.load_all()
    root = tempfile.mkdtemp(prefix="bench_repick_")
    archive = os.path.join(root, "archive")
    pack_sources(
        [PackSource(
            name="synthetic",
            dataset_kwargs={
                "num_events": args.events, "trace_samples": args.trace,
                "cache": False,
            },
        )],
        archive,
        samples_per_shard=max(args.events // 4, 1),
    )

    # --- batch path (the shipped tool, inline) ---------------------------
    from tools.repick_archive import main as repick_main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = repick_main([
            "--archive", archive, "--out", os.path.join(root, "catalog"),
            "--model", args.model,
            "--batch-size", str(args.batch_size),
            "--batches-per-call", str(args.batches_per_call),
            "--compile-gate",
        ])
    if rc != 0:
        print(buf.getvalue())
        raise SystemExit(f"repick run failed rc={rc}")
    worker = _last_json(buf.getvalue(), role="worker")

    # --- serve path (same model/window/host) -----------------------------
    from tools.bench_serve import main as bench_serve_main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = bench_serve_main([
            "--model-name", args.model, "--window", str(args.trace),
            "--requests", str(args.serve_requests),
            "--concurrency", str(args.serve_concurrency),
            "--max-batch", str(args.serve_max_batch),
        ])
    if rc not in (0, None):
        print(buf.getvalue())
        raise SystemExit(f"bench_serve failed rc={rc}")
    serve = _last_json(buf.getvalue())

    batch_wfs = float(worker["waveforms_per_sec"])
    serve_rps = float(serve.get("throughput_rps", 0.0))
    speedup = batch_wfs / serve_rps if serve_rps else float("inf")
    result = {
        "metric": f"{args.model}_repick_throughput",
        "value": round(batch_wfs, 2),
        "unit": "waveforms/sec/chip",
        "serve_baseline_rps": round(serve_rps, 2),
        "speedup_vs_serve": round(speedup, 2),
        "gate_min_speedup": args.min_speedup,
        "step_breakdown": {
            "stage_seconds": worker["stage_seconds"],
            "stage_ms_per_wf": worker.get("stage_ms_per_wf", {}),
        },
        "compiles_after_warmup": worker.get("compiles_after_warmup"),
        "aot_program": worker.get("warmup_program"),
        "aot_compile_ms": worker.get("warmup_compile_ms"),
        "config": {
            "model": args.model,
            "events": args.events,
            "window": args.trace,
            "batch": args.batch_size,
            "batches_per_call": args.batches_per_call,
            "serve_requests": args.serve_requests,
            "serve_concurrency": args.serve_concurrency,
            "serve_max_batch": args.serve_max_batch,
            "serve_p50_ms": serve.get("p50_ms"),
            "serve_p99_ms": serve.get("p99_ms"),
        },
        "device": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
        "measured_at": time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
        ),
        "pass": speedup >= args.min_speedup,
    }
    line = json.dumps(result)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0 if result["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
