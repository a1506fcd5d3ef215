"""Input-pipeline-only benchmark: waveforms/sec through the full host path.

Measures the loader end to end — dataset read, the nine augmentations,
window cut, normalize, soft-label generation, batch assembly — with no
device in the loop (SURVEY.md hard-part #1: at reference training shape,
batch 500 x 8192, the host must outrun the TPU step or the chip starves).

Prints ONE JSON line:
  {"metric": "input_pipeline_throughput", "value", "unit", "vs_baseline"}
``vs_baseline`` is loader wf/s divided by the most recent *device* step
rate (from BENCH env DEVICE_WFS or the default below) — the ratio that
matters; >= 2.0 means the pipeline can feed the chip with headroom.

Env knobs: BENCH_BATCH (500), BENCH_SAMPLES (8192), BENCH_BATCHES (8),
BENCH_WORKERS (os.cpu_count), DEVICE_WFS, BENCH_DATASET
(synthetic | diting_light | packed — diting_light writes a
DiTing-light-format CSV+HDF5 fixture once under logs/ and measures the
real h5py/pandas reader path end to end; packed measures the
packed-shard repack of that same fixture, tools/pack_dataset.py).

--compare (``python -m tools.bench_loader --compare [--out f.json]``)
runs the packed-ingest ladder on ONE shared fixture instead: hdf5
per-sample reads vs packed per-sample reads vs packed+direct-ingest
batch fills (data/ingest.py), with a per-stage budget that shows the
per-sample Event decode and ``_stack`` assembly eliminated on the fast
path — plus the storage-dtype ladder (fp32/bf16/int8 sibling packs of
the same fixture: per-dtype fill ms/wf and measured on-disk bytes/wf;
int8 also measures the stage_raw device-dequant lane). Pass gates:
direct >= 2x the hdf5 per-sample read throughput (ISSUE 14) and int8
on-disk bytes <= 0.55x fp32 (ISSUE 18); the committed verdict lives in
BENCH_loader_r02.json. Env: BENCH_EVENTS (512), BENCH_SAMPLES (8192),
BENCH_READS (400), BENCH_BATCH (64).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def run() -> None:
    import numpy as np  # noqa: F401 (keeps import cost out of the timing)

    import seist_tpu
    from seist_tpu import taskspec
    from seist_tpu.data import pipeline

    seist_tpu.load_all()

    batch = int(os.environ.get("BENCH_BATCH", 500))
    in_samples = int(os.environ.get("BENCH_SAMPLES", 8192))
    n_batches = int(os.environ.get("BENCH_BATCHES", 8))
    workers = int(os.environ.get("BENCH_WORKERS", os.cpu_count() or 1))
    # BENCH_PROCESSES > 0 routes the per-sample work through the process
    # pool (`--loader-processes` in the CLI) — the measured scaling knob
    # for feeding a chip from a multi-core host.
    processes = int(os.environ.get("BENCH_PROCESSES", 0))
    device_wfs = float(os.environ.get("DEVICE_WFS", 4236.0))

    dataset_name = os.environ.get("BENCH_DATASET", "synthetic")
    spec = taskspec.get_task_spec("seist_l_dpk")
    ds_kw: dict = {}
    data_dir = ""
    if dataset_name == "synthetic":
        ds_kw = {"num_events": batch * 4}
    elif dataset_name == "packed":
        # Packed-shard repack of the diting_light fixture.
        from tools.fixtures import ensure_packed_fixture

        data_dir = ensure_packed_fixture(max(batch * 2, 512), in_samples)
    elif dataset_name == "diting_light":
        # Real-format reader path: write the fixture once (keyed by shape)
        # and reuse it across runs.
        from tools.fixtures import write_diting_light_fixture

        n_events = max(batch * 2, 512)
        data_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            os.pardir,
            "logs",
            f"loader_fixture_{n_events}x{in_samples}",
        )
        # Sentinel written only after the full fixture lands — the CSV is
        # the FIRST artifact the writer produces, so its existence alone
        # would turn an interrupted write into a permanently broken cache.
        marker = os.path.join(data_dir, ".complete")
        if not os.path.exists(marker):
            t0 = time.perf_counter()
            write_diting_light_fixture(
                data_dir, n_events=n_events, trace_samples=in_samples
            )
            with open(marker, "w") as f:
                f.write("ok\n")
            print(
                f"fixture written in {time.perf_counter() - t0:.1f}s: "
                f"{data_dir}",
                file=sys.stderr,
            )
    else:
        raise SystemExit(f"unknown BENCH_DATASET {dataset_name!r}")
    dataset = pipeline.from_task_spec(
        spec,
        dataset_name,
        "train",
        seed=0,
        in_samples=in_samples,
        augmentation=True,
        data_dir=data_dir,
        dataset_kwargs=ds_kw,
    )
    loader = pipeline.Loader(
        dataset,
        batch,
        shuffle=True,
        drop_last=True,
        num_workers=workers,
        worker_processes=processes,
        seed=0,
    )

    # Warm one batch (imports, native-kernel dlopen, thread spin-up).
    it = iter(loader)
    next(it)

    t0 = time.perf_counter()
    done = 0
    for _ in range(n_batches):
        try:
            next(it)
        except StopIteration:
            loader.set_epoch(loader.epoch + 1)
            it = iter(loader)
            next(it)
        done += 1
    dt = time.perf_counter() - t0
    wfs = batch * done / dt

    print(
        json.dumps(
            {
                "metric": "input_pipeline_throughput",
                "value": round(wfs, 2),
                "unit": "waveforms/sec/host",
                "vs_baseline": round(wfs / device_wfs, 3),
                "device_wfs_ref": device_wfs,
                "batch": batch,
                "workers": workers,
                "worker_processes": processes,
                "augmentation": True,
                "dataset": dataset_name,
            }
        )
    )


def compare(out_path: str = "") -> int:
    """hdf5 vs packed vs packed+direct-ingest on one shared fixture."""
    import numpy as np

    import seist_tpu
    from seist_tpu import taskspec
    from seist_tpu.data import pipeline
    from seist_tpu.data.ingest import PackedRawStore
    from seist_tpu.registry import DATASETS
    from tools.fixtures import ensure_loader_fixture, ensure_packed_fixture

    seist_tpu.load_all()
    n_events = int(os.environ.get("BENCH_EVENTS", 512))
    in_samples = int(os.environ.get("BENCH_SAMPLES", 8192))
    n_reads = int(os.environ.get("BENCH_READS", 400))
    batch = int(os.environ.get("BENCH_BATCH", 64))

    src_dir = ensure_loader_fixture(n_events, in_samples)
    packed_dir = ensure_packed_fixture(n_events, in_samples)
    hdf5 = DATASETS.create(
        "diting_light", seed=0, mode="train", data_dir=src_dir
    )
    packed = DATASETS.create(
        "packed", seed=0, mode="train", data_dir=packed_dir
    )
    idxs = [i % len(hdf5) for i in range(n_reads)]
    for i in idxs[:16]:  # warm h5 handles / memmaps / page cache
        hdf5[i]
        packed[i]

    def rate(fn, items):
        t0 = time.perf_counter()
        for i in items:
            fn(i)
        dt = time.perf_counter() - t0
        return len(items) / dt, dt * 1e3 / len(items)

    hdf5_wfs, hdf5_ms = rate(lambda i: hdf5[i], idxs)
    packed_wfs, packed_ms = rate(lambda i: packed[i], idxs)

    # The batch-assembly (_stack) tax both per-sample paths pay per wf.
    rows = [packed[i][0]["data"] for i in idxs[:batch]]
    reps = max(1, n_reads // batch)
    t0 = time.perf_counter()
    for _ in range(reps):
        pipeline._stack(rows)
    stack_ms = (time.perf_counter() - t0) * 1e3 / (reps * batch)

    # Direct ingest: memmap -> staging slab batch fills, no Event decode.
    spec = taskspec.get_task_spec("seist_l_dpk")
    sds = pipeline.from_task_spec(
        spec, "packed", "train", seed=0, in_samples=in_samples,
        augmentation=False, data_dir=packed_dir,
    )
    store = PackedRawStore.build(sds, batch_size=batch)
    order = np.arange(store.n_raw)
    chunks = [
        order[b * batch : (b + 1) * batch]
        for b in range(max(1, min(len(order) // batch, n_reads // batch)))
    ]
    store.row_batch(chunks[0])  # warm
    t0 = time.perf_counter()
    for c in chunks:
        store.row_batch(c)
    dt = time.perf_counter() - t0
    direct_n = sum(len(c) for c in chunks)
    direct_wfs = direct_n / dt
    fill_ms = dt * 1e3 / direct_n

    # ------------------------------------------------------ dtype ladder
    # fp32/bf16/int8 direct-ingest fills off sibling packs of the SAME
    # fixture: per-dtype fill ms/wf plus on-disk bytes/wf measured from
    # the shards (ISSUE 18 — the bandwidth claim is measured, not
    # asserted). int8 additionally measures the stage_raw lane (rows
    # staged AS int8 + resident scales, the repick engine's
    # device-dequant feed) — that is the lane whose host->device bytes
    # shrink 4x.
    def shard_bytes(d):
        return sum(
            os.path.getsize(os.path.join(d, f))
            for f in sorted(os.listdir(d))
            if f.startswith("shard_") and f.endswith(".bin")
        )

    ladder = {}
    fp32_bytes_wf = shard_bytes(packed_dir) / n_events
    for dname in ("float32", "bfloat16", "int8"):
        pdir = (
            packed_dir
            if dname == "float32"
            else ensure_packed_fixture(n_events, in_samples, dtype=dname)
        )
        dsds = pipeline.from_task_spec(
            spec, "packed", "train", seed=0, in_samples=in_samples,
            augmentation=False, data_dir=pdir,
        )
        entry = {"bytes_per_wf": round(shard_bytes(pdir) / n_events, 1)}
        entry["bytes_vs_fp32"] = round(
            entry["bytes_per_wf"] / fp32_bytes_wf, 4
        )
        lanes = [("fill_f32", False)]
        if dname == "int8":
            lanes.append(("fill_raw_int8", True))
        for lane, raw in lanes:
            dstore = PackedRawStore.build(
                dsds, batch_size=batch, stage_raw=raw
            )
            dstore.row_batch(chunks[0])  # warm memmaps/page cache
            t0 = time.perf_counter()
            for c in chunks:
                dstore.row_batch(c)
            ddt = time.perf_counter() - t0
            entry[lane + "_wfs"] = round(direct_n / ddt, 1)
            entry[lane + "_ms_per_wf"] = round(ddt * 1e3 / direct_n, 4)
        ladder[dname] = entry

    verdict = {
        "metric": "packed_ingest_throughput",
        "unit": "waveforms/sec/host (single-thread read lane)",
        "hdf5_read_wfs": round(hdf5_wfs, 1),
        "packed_read_wfs": round(packed_wfs, 1),
        "packed_direct_wfs": round(direct_wfs, 1),
        "speedup_packed_vs_hdf5": round(packed_wfs / hdf5_wfs, 2),
        "speedup_direct_vs_hdf5": round(direct_wfs / hdf5_wfs, 2),
        "stage_budget_ms_per_wf": {
            "hdf5": {
                "per_sample_event_decode": round(hdf5_ms, 4),
                "_stack": round(stack_ms, 4),
            },
            "packed": {
                "per_sample_event_decode": round(packed_ms, 4),
                "_stack": round(stack_ms, 4),
            },
            "packed_direct": {
                "batch_fill": round(fill_ms, 4),
                "eliminated": ["per_sample_event_decode", "_stack"],
            },
        },
        "dtype_ladder": ladder,
        "config": {
            "n_events": n_events,
            "in_samples": in_samples,
            "n_reads": n_reads,
            "batch": batch,
        },
        # Two gates: the ISSUE 14 direct>=2x hdf5 throughput floor and
        # the ISSUE 18 int8 on-disk bytes<=0.55x fp32 ceiling.
        "pass": (
            direct_wfs >= 2.0 * hdf5_wfs
            and ladder["int8"]["bytes_vs_fp32"] <= 0.55
        ),
    }
    line = json.dumps(verdict)
    print(line)
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")
    return 0 if verdict["pass"] else 1


def main() -> int:
    argv = sys.argv[1:]
    if "--compare" in argv:
        out = ""
        if "--out" in argv:
            out = argv[argv.index("--out") + 1]
        return compare(out)
    run()
    return 0


if __name__ == "__main__":
    sys.exit(main())
