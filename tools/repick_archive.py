"""Re-pick a packed waveform archive as a map-reduce batch job.

The ROADMAP's planetary-archive workload: when a model improves,
observatories re-process decades x thousands of stations — billions of
windows, purely throughput-bound. This tool drives the
seist_tpu/batch engine (docs/DATA.md "Batch re-picking"):

* **map** — the archive's packed shards become deterministic work units;
  each worker owns ``units[worker_index::num_workers]`` and runs a
  straight-line device feed (double-buffered ``PackedRawStore`` fills
  against ONE AOT multi-batch executable — trunk-once head fan-out for
  task groups), committing catalog segments atomically every
  ``--commit-every`` device calls;
* **resume** — a SIGKILL'd worker restarts at its exact segment offset
  (committed segments are the durable state; ``worker_<i>.json`` is the
  advisory progress record); SIGTERM drains the current segment and
  exits 75 (the PR 2 preemption contract);
* **reduce** — ``--merge-only`` (or the driver, after its workers join)
  concatenates segments in (unit, segment) order into ``catalog.jsonl``
  + ``catalog_meta.json`` (written LAST). The merged catalog is
  byte-identical across worker counts and kill/resume histories —
  ``make repick-smoke`` pins it.

    # serial (one process does everything)
    python -m tools.repick_archive --archive /data/packed \
        --model phasenet=CKPT --out /data/catalog --batch-size 64

    # 4-worker driver (spawns workers, then merges)
    python -m tools.repick_archive --archive /data/packed \
        --model-group seist_s=dpk:CKPT,emg:CKPT2 --out /data/catalog \
        --workers 4 --variant bf16

Prints ONE JSON verdict line per role (worker / driver / merge).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np


def get_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="python -m tools.repick_archive", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--archive", required=True,
                    help="packed archive dir (tools/pack_dataset.py output)")
    ap.add_argument("--out", required=True, help="catalog output dir")
    ap.add_argument("--model", default="", metavar="NAME[=CKPT]",
                    help="single-task model (fresh-init weights without "
                    "=CKPT — smoke/testing)")
    ap.add_argument("--model-group", default="",
                    metavar="PREFIX=TASK[:CKPT],TASK[:CKPT],...",
                    help="multi-task SeisT group served on ONE shared "
                    "trunk (the PR 10 fan-out at full batch)")
    ap.add_argument("--tasks", default="",
                    help="comma-separated subset of a group's heads")
    ap.add_argument("--variant", default="fp32",
                    choices=("fp32", "bf16", "int8"),
                    help="serving weight variant (parity-gated against "
                    "fp32 at load; a failing gate refuses the run)")
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--batches-per-call", type=int, default=4,
                    help="micro-batches per compiled device call "
                    "(lax.map'd in ONE executable — host Python is off "
                    "the critical path)")
    ap.add_argument("--commit-every", type=int, default=4,
                    help="segment commit granularity in device calls")
    ap.add_argument("--prefetch", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0,
                    help="fresh-init weight seed (checkpoint-free runs)")
    ap.add_argument("--workers", type=int, default=0,
                    help="driver mode: spawn N worker subprocesses, then "
                    "merge (0 = do everything in-process)")
    ap.add_argument("--worker-index", type=int, default=-1,
                    help="worker mode: this worker's index (driver sets it)")
    ap.add_argument("--num-workers", type=int, default=1,
                    help="worker mode: total workers (driver sets it)")
    ap.add_argument("--retries", type=int, default=2,
                    help="driver: crash-relaunch budget per worker "
                    "(preempt exits never consume it)")
    ap.add_argument("--fleet", action="store_true",
                    help="lease-based fleet worker (batch/fleet.py): "
                    "instead of a static units[i::N] slice, acquire "
                    "work-unit leases with heartbeat + fencing token, "
                    "reclaim peers' expired leases, and park through "
                    "lease-store partitions — any number of workers, "
                    "joining and dying at any time, converge on the "
                    "same catalog (docs/FAULT_TOLERANCE.md)")
    ap.add_argument("--lease-dir", default="",
                    help="shared-directory lease store root (fleet "
                    "mode; also lets --merge-only audit segment fences "
                    "against the done ledger)")
    ap.add_argument("--worker-id", default="",
                    help="fleet mode: this worker's lease owner id "
                    "(default: worker<index>@<pid>)")
    ap.add_argument("--lease-store", default="auto",
                    choices=("auto", "dir", "kv"),
                    help="fleet lease store: 'dir' = shared directory "
                    "(--lease-dir), 'kv' = the jax coordination-service "
                    "KV (multi-host slices), 'auto' = kv when a "
                    "coordination service is initialized, else dir")
    ap.add_argument("--no-merge", action="store_true",
                    help="skip the reduce step (driver/smoke runs merge "
                    "separately)")
    ap.add_argument("--merge-only", action="store_true",
                    help="reduce only: merge committed segments into "
                    "catalog.jsonl (no model, no jax)")
    ap.add_argument("--compile-gate", action="store_true",
                    help="run the post-warm-up loop under CompileBudget "
                    "and report compiles_after_warmup (must be 0)")
    ap.add_argument("--ppk-threshold", type=float, default=0.3)
    ap.add_argument("--spk-threshold", type=float, default=0.3)
    ap.add_argument("--det-threshold", type=float, default=0.5)
    ap.add_argument("--min-peak-dist", type=float, default=1.0)
    ap.add_argument("--max-events", type=int, default=8)
    ap.add_argument("--station-meta", default="", metavar="FILE",
                    help="JSON file mapping waveform key -> station "
                    "metadata {'id', 'network', 'lat', 'lon'}; matched "
                    "rows carry a 'station' field in the catalog "
                    "(the /predict //stream provenance block)")
    args = ap.parse_args(argv)
    if args.merge_only:
        # The reduce is model-free: identity comes from repick_plan.json.
        if args.model or args.model_group:
            ap.error("--merge-only takes no --model/--model-group (the "
                     "plan file records them)")
    elif bool(args.model) == bool(args.model_group):
        ap.error("exactly one of --model / --model-group is required")
    if args.fleet and args.lease_store != "kv" and not args.lease_dir:
        ap.error("--fleet needs --lease-dir (or --lease-store kv under "
                 "an initialized jax coordination service)")
    return args


def _archive_index(archive: str) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """(meta.json dict, index columns needed for planning) — no jax."""
    from seist_tpu.data import packed as packed_mod

    with open(os.path.join(archive, packed_mod._META)) as f:
        meta = json.load(f)
    with np.load(
        os.path.join(archive, packed_mod._INDEX), allow_pickle=False
    ) as z:
        # Only the planning columns: 'key' (the biggest index array at
        # archive scale) is read by the worker via the packed dataset's
        # frame, not here — the model-free merge role must not pay it.
        cols = {"shard": z["shard"], "n_samp": z["n_samp"]}
    return meta, cols


def _parse_group(spec: str) -> Tuple[str, List[Tuple[str, str]]]:
    """PREFIX=TASK[:CKPT],... (the serve CLI's --model-group grammar)."""
    prefix, sep, rest = spec.partition("=")
    if not sep or not prefix or not rest:
        raise SystemExit(
            f"bad --model-group '{spec}' "
            "(want PREFIX=TASK[:CKPT],TASK[:CKPT],...)"
        )
    tasks: List[Tuple[str, str]] = []
    for part in rest.split(","):
        task, _, ckpt = part.partition(":")
        if not task:
            raise SystemExit(f"empty task in --model-group '{spec}'")
        tasks.append((task, ckpt))
    return prefix, tasks


def _plan_dict(args, meta, n_rows: int, n_units: int) -> Dict[str, Any]:
    """Everything that determines segment boundaries and row content —
    the resume geometry guard (catalog.write_or_check_plan)."""
    return {
        "format_version": 1,
        "source": meta.get("source", ""),
        "dtype": meta.get("dtype", "float32"),
        "n_rows": n_rows,
        "n_units": n_units,
        "model": args.model or args.model_group,
        "tasks": args.tasks,
        "variant": args.variant,
        "batch_size": args.batch_size,
        "batches_per_call": args.batches_per_call,
        "commit_every": args.commit_every,
        "sampling_rate": int(meta["sampling_rate"]),
        "decode": {
            "ppk_threshold": args.ppk_threshold,
            "spk_threshold": args.spk_threshold,
            "det_threshold": args.det_threshold,
            "min_peak_dist": args.min_peak_dist,
            "max_events": args.max_events,
        },
    }


def _merge(args, meta, units, print_verdict: bool = True) -> Dict[str, Any]:
    from seist_tpu.batch import catalog

    # Segment geometry and model identity come from the RECORDED plan,
    # never from this invocation's flags: a --merge-only run with
    # different defaults must not under-count segments (merge_catalog's
    # completeness guard would pass on a prefix and silently drop rows)
    # or misattribute the producing model in catalog_meta.json.
    plan = catalog.read_plan(args.out)
    rows_per_call = int(plan["batch_size"]) * int(plan["batches_per_call"])
    # Fleet merges audit every segment's fence sidecar against the lease
    # store's done ledger (merge_catalog refuses zombie-written
    # segments); catalog.jsonl bytes are identical either way.
    fences = None
    if args.lease_dir and os.path.isdir(args.lease_dir):
        from seist_tpu.batch import fleet

        fences = fleet.DirLeaseStore(args.lease_dir).done_fences(
            [u.unit_id for u in units]
        )
    out_meta = catalog.merge_catalog(
        args.out, units, rows_per_call, int(plan["commit_every"]),
        meta={
            "archive_source": meta.get("source", ""),
            "sampling_rate": int(meta["sampling_rate"]),
            "model": plan["model"],
            "variant": plan["variant"],
            "plan": plan,
        },
        fences=fences,
    )
    verdict = {
        "ok": True,
        "role": "merge",
        "out": args.out,
        "rows": out_meta["n_rows"],
        "units": out_meta["n_units"],
    }
    if fences is not None:
        verdict["fence_audit"] = out_meta["fleet"]
    if print_verdict:
        print(json.dumps(verdict))
    return verdict


def _load_entry(args, window: int):
    from seist_tpu.serve.pool import load_group_entry, load_model_entry

    variants = (args.variant,)
    if args.model_group:
        prefix, task_entries = _parse_group(args.model_group)
        return load_group_entry(
            prefix, task_entries, window=window, seed=args.seed,
            variants=variants,
        )
    name, _, ckpt = args.model.partition("=")
    return load_model_entry(
        name, ckpt, window=window, seed=args.seed, variants=variants
    )


def run_worker(args, worker_index: int, num_workers: int) -> int:
    """One map worker: build store + entry + engine, re-pick this
    worker's units, honor SIGTERM with a drain-and-exit-75."""
    from seist_tpu.batch import catalog
    from seist_tpu.batch.engine import RepickEngine
    from seist_tpu.data import pipeline
    from seist_tpu.data.ingest import PackedRawStore, packed_dataset_of
    from seist_tpu.train.checkpoint import PREEMPT_EXIT_CODE, ProgressFile

    meta, cols = _archive_index(args.archive)
    units = _units_from_cols(cols)
    if not units:
        raise SystemExit(f"archive {args.archive} has no rows")
    raw_len = int(cols["n_samp"][0])
    rows_per_call = args.batch_size * args.batches_per_call
    os.makedirs(args.out, exist_ok=True)
    catalog.write_or_check_plan(
        args.out, _plan_dict(args, meta, len(cols["shard"]), len(units))
    )

    # The store covers the WHOLE archive in pack order: no shuffle, no
    # split, no labels (inference needs waveforms only — a NaN label
    # column must not refuse the build).
    sds = pipeline.SeismicDataset(
        "packed", "train", seed=0, data_dir=args.archive,
        input_names=[], label_names=[], task_names=[],
        in_samples=raw_len, augmentation=False, shuffle=False,
        data_split=False,
    )
    # int8 v3 archives feed the device-dequant path: rows stay int8
    # through staging and the host->device copy, the program widens
    # (batch/engine.dequant_rows is fused ahead of the z-score).
    pds = packed_dataset_of(sds)
    store = PackedRawStore.build(
        sds, batch_size=rows_per_call, prefetch=args.prefetch,
        stage_raw=(pds.storage_dtype == np.int8),
    )
    keys = pds._meta_data["key"].to_numpy()
    entry = _load_entry(args, raw_len)
    engine = RepickEngine(
        entry, store,
        sampling_rate=int(meta["sampling_rate"]),
        batch_size=args.batch_size,
        batches_per_call=args.batches_per_call,
        variant=args.variant,
        decode_opts={
            "ppk_threshold": args.ppk_threshold,
            "spk_threshold": args.spk_threshold,
            "det_threshold": args.det_threshold,
            "min_peak_dist": args.min_peak_dist,
            "max_events": args.max_events,
        },
        keys=keys,
        stations=_load_station_meta(args.station_meta),
        prefetch=args.prefetch,
        tasks=[t for t in args.tasks.split(",") if t] or None,
    )

    stop = threading.Event()
    # threadlint: handlers do flag stores only (the drain happens on the
    # main thread, at the next segment boundary).
    signal.signal(signal.SIGTERM, lambda s, f: stop.set())

    if args.fleet:
        return _run_fleet_worker(args, worker_index, units, engine, stop)

    mine = list(units)[worker_index::num_workers]
    progress = ProgressFile(
        os.path.join(args.out, f"worker_{worker_index}.json")
    )
    engine.warmup()
    stats = engine.run_units(
        mine, args.out,
        commit_every=args.commit_every,
        stop_event=stop,
        compile_gate=args.compile_gate,
        progress=progress,
    )
    verdict = {
        "ok": not stats["preempted"],
        "role": "worker",
        "worker": worker_index,
        "num_workers": num_workers,
        "units_assigned": len(mine),
        **stats,
        **{f"warmup_{k}": v for k, v in engine.warmup_report.items()},
    }
    print(json.dumps(verdict), flush=True)
    if stats["preempted"]:
        return PREEMPT_EXIT_CODE
    return 0


def _lease_store(args):
    """Build the configured lease store. 'auto' prefers the jax
    coordination-service KV (real multi-host slices) and falls back to
    the shared directory when no service is initialized."""
    from seist_tpu.batch import fleet

    if args.lease_store in ("auto", "kv"):
        try:
            return fleet.KVLeaseStore.from_runtime()
        except fleet.LeaseStoreError:
            if args.lease_store == "kv":
                raise
    return fleet.DirLeaseStore(args.lease_dir)


def _run_fleet_worker(args, worker_index, units, engine, stop) -> int:
    """One FLEET worker: every unit is a candidate (work-stealing over
    leases, scan rotated by the worker index); the engine runs each
    leased unit with the fence guard on every segment commit. Exits 75
    on preemption — the supervisor relaunches and the worker re-joins
    whatever work is still unleased."""
    from seist_tpu.batch import fleet
    from seist_tpu.train.checkpoint import PREEMPT_EXIT_CODE, ProgressFile

    owner = args.worker_id or f"worker{max(worker_index, 0)}@{os.getpid()}"
    store = _lease_store(args)
    progress = ProgressFile(
        os.path.join(args.out, f"fleet_{max(worker_index, 0)}.json")
    )
    engine.warmup()  # burn compile time BEFORE any lease TTL is ticking
    totals = {"rows": 0, "calls": 0, "segments": 0}

    def run_one(unit, held):
        u = engine.run_unit(
            unit, args.out, commit_every=args.commit_every,
            stop_event=stop, lease=held,
        )
        for k in totals:
            totals[k] += u[k]
        progress.save({
            "owner": owner, "unit": unit.unit_id, "fence": held.fence,
            "preempted": u["preempted"], **totals,
        })
        return u

    worker = fleet.FleetWorker(
        store, units, owner, run_one,
        stop_event=stop, scan_offset=max(worker_index, 0),
    )
    budget = None
    if args.compile_gate:
        from tools.jaxlint.runtime import CompileBudget

        budget = CompileBudget()
        budget.__enter__()
    try:
        stats = worker.run()
    finally:
        if budget is not None:
            budget.__exit__(None, None, None)
    verdict = {
        "ok": stats["all_done"] or stats["preempted"],
        "role": "fleet-worker",
        "worker": worker_index,
        "owner": owner,
        "store": type(store).__name__,
        **{k: stats[k] for k in (
            "units_done", "units_lost", "parks", "preempted", "all_done",
        )},
        **totals,
        "lease": stats["lease"],
        **{f"warmup_{k}": v for k, v in engine.warmup_report.items()},
    }
    if budget is not None:
        verdict["compiles_after_warmup"] = budget.total("")
        verdict["xla_compiles_after_warmup"] = budget.backend_compiles
    print(json.dumps(verdict), flush=True)
    if stats["preempted"] and not stats["all_done"]:
        return PREEMPT_EXIT_CODE
    return 0 if verdict["ok"] else 1


def _load_station_meta(path: str):
    """--station-meta FILE -> {key: normalized station dict} or None.
    Validated through the same parse_station the serve plane uses, so a
    catalog's 'station' blocks and a /stream request's are one schema."""
    if not path:
        return None
    from seist_tpu.serve.protocol import BadRequest, parse_station

    with open(path) as f:
        raw = json.load(f)
    if not isinstance(raw, dict):
        raise SystemExit(f"--station-meta {path}: want a JSON object "
                         "mapping waveform key -> station metadata")
    out = {}
    for key, st in raw.items():
        try:
            out[str(key)] = parse_station(st, required=True)
        except BadRequest as e:
            raise SystemExit(f"--station-meta {path}: key {key!r}: {e}")
    return out


def _units_from_cols(cols):
    from seist_tpu.batch import catalog

    return catalog.plan_units(cols["shard"])


def _worker_cmd(args, worker_index: int) -> List[str]:
    cmd = [
        sys.executable, "-m", "tools.repick_archive",
        "--archive", args.archive, "--out", args.out,
        "--variant", args.variant,
        "--batch-size", str(args.batch_size),
        "--batches-per-call", str(args.batches_per_call),
        "--commit-every", str(args.commit_every),
        "--prefetch", str(args.prefetch),
        "--seed", str(args.seed),
        "--worker-index", str(worker_index),
        "--num-workers", str(args.workers),
        "--no-merge",
        "--ppk-threshold", str(args.ppk_threshold),
        "--spk-threshold", str(args.spk_threshold),
        "--det-threshold", str(args.det_threshold),
        "--min-peak-dist", str(args.min_peak_dist),
        "--max-events", str(args.max_events),
    ]
    if args.model:
        cmd += ["--model", args.model]
    if args.model_group:
        cmd += ["--model-group", args.model_group]
    if args.tasks:
        cmd += ["--tasks", args.tasks]
    if args.compile_gate:
        cmd += ["--compile-gate"]
    if args.station_meta:
        cmd += ["--station-meta", args.station_meta]
    return cmd


def run_driver(args) -> int:
    """Map-reduce driver: spawn the workers, relaunch preempted/crashed
    ones (preempt exits never consume the crash budget — the supervise
    contract), then run the reduce."""
    from seist_tpu.obs.bus import monotonic
    from seist_tpu.train.checkpoint import PREEMPT_EXIT_CODE
    from tools.device_procs import refuse_shared_chip

    refuse_shared_chip(args.workers, "repick_archive --workers")
    t0 = monotonic()
    meta, cols = _archive_index(args.archive)
    units = _units_from_cols(cols)
    budget = {i: args.retries for i in range(args.workers)}
    pending = list(range(args.workers))
    failed: List[int] = []
    while pending:
        procs = {
            i: subprocess.Popen(_worker_cmd(args, i)) for i in pending
        }
        pending = []
        for i, p in procs.items():
            rc = p.wait()
            if rc == 0:
                continue
            if rc == PREEMPT_EXIT_CODE:
                pending.append(i)  # resume, budget untouched
            elif budget[i] > 0:
                budget[i] -= 1
                pending.append(i)
            else:
                failed.append(i)
    if failed:
        print(json.dumps({
            "ok": False, "role": "driver",
            "error": f"worker(s) {failed} exhausted the relaunch budget",
        }))
        return 1
    verdict: Dict[str, Any] = {
        "ok": True, "role": "driver", "workers": args.workers,
        "units": len(units), "wall_s": round(monotonic() - t0, 2),
    }
    if not args.no_merge:
        merged = _merge(args, meta, units, print_verdict=False)
        verdict["rows"] = merged["rows"]
        verdict["out"] = args.out
    print(json.dumps(verdict))
    return 0


def main(argv=None) -> int:
    args = get_args(argv)
    import seist_tpu
    from seist_tpu.utils.misc import enable_compile_cache

    seist_tpu.load_all()
    if args.merge_only:
        meta, cols = _archive_index(args.archive)
        _merge(args, meta, _units_from_cols(cols))
        return 0
    enable_compile_cache()
    if args.worker_index >= 0:
        return run_worker(args, args.worker_index, args.num_workers)
    if args.workers > 0:
        return run_driver(args)
    # Inline: one process maps every unit, then reduces.
    rc = run_worker(args, 0, 1)
    if rc == 0 and not args.no_merge:
        meta, cols = _archive_index(args.archive)
        _merge(args, meta, _units_from_cols(cols))
    return rc


if __name__ == "__main__":
    sys.exit(main())
