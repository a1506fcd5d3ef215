"""int8 end-to-end smoke: pack -> direct ingest -> repick -> parity gate.

The ``make quant-smoke`` lane (docs/DATA.md "Storage dtype"): proves the
whole ISSUE 18 quantization ladder on one tiny synthetic event set, in
one process, in seconds:

1. pack the SAME synthetic source twice — fp32 (format v2) and int8
   (format v3, per-row scale sidecar) — and gate the measured on-disk
   bytes at <= 0.55x fp32;
2. re-pick both archives inline (``tools.repick_archive``): fp32
   weights on fp32 shards vs the int8 weight variant on int8 shards
   through the stage_raw device-dequant path, both under the
   CompileBudget gate (zero post-warm-up compiles);
3. gate DECISION parity: the fraction of catalog rows whose pick
   decisions match the fp32 reference at the repo's pick-residual
   convention (positions within ``--time-threshold`` 0.1 s, same pick
   counts — seist_tpu/cli.py eval uses the same tolerance). The smoke
   decodes at threshold 0.4: a FRESH-INIT phasenet emits near-uniform
   softmax (~0.33/class), so the serving default 0.3 sits inside the
   init noise band where every pick is a coin flip — 0.4 gates real
   peaks, which a trained checkpoint produces regardless;
4. mechanism proof for the >=1.7x throughput acceptance on the CPU
   backend: the repick host feed is bytes-bound, so the gate measures
   the engine's per-call host path — PackedRawStore fill + device_put
   — fp32 vs int8 stage_raw at the engine's b64x2 rows-per-call on the
   shared bench_loader fixture (512 events x 8192 samples), min-of-5
   trials against scheduler noise. The end-to-end TPU run stays
   flagged ``tpu_run: pending`` until a chip runs it.

Prints ONE JSON verdict line; exit 0 iff every gate held. With
``--out FILE`` also writes the BENCH-style headline
(``BENCH_repick_r02.json`` is the committed artifact).
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

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# Tiny repick geometry (mirrors tools/repick_smoke.py).
N_EVENTS = 48
TRACE = 256
SPS = 16
BATCH = 4
BPC = 2
COMMIT = 2

# Decision-parity convention (docstring point 3): decode at 0.4 (above
# the fresh-init softmax noise band), match picks at the repo's 0.1 s
# residual tolerance (cli.py --time-threshold) at the packs' 50 Hz.
PICK_THR = 0.4
PICK_TOL = int(0.1 * 50)

# Mechanism feed bench (docstring point 4): the bench_loader fixture
# (512 x 8192, marker-cached under logs/), fill + device_put at the
# engine's b64x2 = 128 rows per call, min-of-5 trials.
MECH_EVENTS = 512
MECH_TRACE = 8192
MECH_BATCH = 128
MECH_PASSES = 2
MECH_TRIALS = 5

PARITY_MIN = 0.95
SPEEDUP_MIN = 1.7
BYTES_MAX = 0.55


def _pack(root: str, name: str, dtype: str, n_events: int, trace: int,
          sps: int):
    from seist_tpu.data.packed import PackSource, pack_sources

    return pack_sources(
        [PackSource(
            name="synthetic",
            dataset_kwargs={
                "num_events": n_events, "trace_samples": trace,
                "cache": False,
            },
        )],
        os.path.join(root, name),
        samples_per_shard=sps,
        dtype=dtype,
    )


def _repick(archive: str, out: str, variant: str) -> dict:
    """Inline single-process repick; returns the worker verdict."""
    from tools.repick_archive import main as repick_main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = repick_main([
            "--archive", archive, "--out", out, "--model", "phasenet",
            "--batch-size", str(BATCH), "--batches-per-call", str(BPC),
            "--commit-every", str(COMMIT), "--variant", variant,
            "--compile-gate",
            "--ppk-threshold", str(PICK_THR),
            "--spk-threshold", str(PICK_THR),
        ])
    if rc != 0:
        raise SystemExit(
            f"repick({variant}) rc={rc}: {buf.getvalue()[-400:]}"
        )
    for line in reversed(buf.getvalue().strip().splitlines()):
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if d.get("role") == "worker":
            return d
    raise SystemExit(f"no worker verdict: {buf.getvalue()[-400:]}")


def _decisions(out_dir: str) -> list:
    rows = []
    with open(os.path.join(out_dir, "catalog.jsonl")) as f:
        for line in f:
            r = json.loads(line)
            rows.append({
                k: v for k, v in r.items() if k not in ("key", "row")
            })
    return rows


def _rows_match(a: dict, b: dict) -> bool:
    """Decision-level row equality: same heads, same pick/detection
    counts, positions within PICK_TOL samples (0.1 s), scalar heads
    within 5% relative."""
    if set(a) != set(b):
        return False
    for key, va in a.items():
        vb = b[key]
        if isinstance(va, list):
            if len(va) != len(vb):
                return False
            for x, y in zip(va, vb):
                if isinstance(x, list):  # det [start, end] windows
                    if len(x) != len(y) or any(
                        abs(p - q) > PICK_TOL for p, q in zip(x, y)
                    ):
                        return False
                elif abs(x - y) > PICK_TOL:
                    return False
        elif isinstance(va, (int, float)):
            if abs(va - vb) > max(1e-6, 0.05 * abs(va)):
                return False
        elif va != vb:
            return False
    return True


def _feed_ms_per_wf(archive: str, stage_raw: bool) -> float:
    """The engine's per-call host feed — PackedRawStore fill +
    device_put of what was staged — at MECH_BATCH rows per call.
    Min-of-MECH_TRIALS full passes (least-noise estimate of the true
    per-wf cost on a shared-CPU box)."""
    import jax
    import numpy as np

    from seist_tpu.data import pipeline
    from seist_tpu.data.ingest import PackedRawStore

    sds = pipeline.SeismicDataset(
        "packed", "train", seed=0, data_dir=archive,
        input_names=[], label_names=[], task_names=[],
        in_samples=MECH_TRACE, augmentation=False, shuffle=False,
        data_split=False,
    )
    store = PackedRawStore.build(
        sds, batch_size=MECH_BATCH, stage_raw=stage_raw
    )
    chunks = [
        np.arange(b * MECH_BATCH, (b + 1) * MECH_BATCH)
        for b in range(store.n_raw // MECH_BATCH)
    ]
    store.row_batch(chunks[0])  # warm memmaps / page cache
    best = float("inf")
    for _ in range(MECH_TRIALS):
        t0 = time.perf_counter()
        n = 0
        for _ in range(MECH_PASSES):
            for c in chunks:
                rows = store.row_batch(c)
                dev = jax.device_put(
                    (rows["data"], rows["data_scale"])
                    if stage_raw else rows["data"]
                )
                jax.block_until_ready(dev)
                n += len(c)
        best = min(best, (time.perf_counter() - t0) * 1e3 / n)
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m tools.quant_smoke")
    ap.add_argument("--out", default="", help="also write the BENCH-style "
                    "headline JSON here (BENCH_repick_r02.json)")
    args = ap.parse_args(argv)

    import jax

    import seist_tpu
    from seist_tpu.utils.misc import enable_compile_cache

    seist_tpu.load_all()
    enable_compile_cache()
    t0 = time.monotonic()
    root = tempfile.mkdtemp(prefix="quant_smoke_")

    # 1. pack fp32 + int8 of the same source; bytes gate.
    s_f32 = _pack(root, "f32", "float32", N_EVENTS, TRACE, SPS)
    s_i8 = _pack(root, "i8", "int8", N_EVENTS, TRACE, SPS)
    bytes_ratio = s_i8["on_disk_bytes"] / max(s_f32["on_disk_bytes"], 1)

    # 2. repick both (inline, compile-gated).
    v_f32 = _repick(os.path.join(root, "f32"),
                    os.path.join(root, "cat_f32"), "fp32")
    v_i8 = _repick(os.path.join(root, "i8"),
                   os.path.join(root, "cat_i8"), "int8")
    compiles = (
        v_f32.get("compiles_after_warmup", -1)
        + v_i8.get("compiles_after_warmup", -1)
    )

    # 3. decision parity at the pick-residual tolerance.
    ref = _decisions(os.path.join(root, "cat_f32"))
    got = _decisions(os.path.join(root, "cat_i8"))
    same = sum(1 for a, b in zip(ref, got) if _rows_match(a, b))
    parity = same / max(len(ref), 1)

    # 4. host-feed mechanism bench (bytes-bound CPU proof) on the
    # shared bench_loader fixture — same data BENCH_loader_r02 measures.
    from tools.fixtures import ensure_packed_fixture

    mech_f32 = ensure_packed_fixture(MECH_EVENTS, MECH_TRACE)
    mech_i8 = ensure_packed_fixture(MECH_EVENTS, MECH_TRACE, dtype="int8")
    f32_ms = _feed_ms_per_wf(mech_f32, False)
    i8_ms = _feed_ms_per_wf(mech_i8, True)
    feed_speedup = f32_ms / i8_ms

    verdict = {
        "ok": bool(
            len(ref) == len(got) == N_EVENTS
            and bytes_ratio <= BYTES_MAX
            and parity >= PARITY_MIN
            and feed_speedup >= SPEEDUP_MIN
            and compiles == 0
            and v_f32["ok"] and v_i8["ok"]
        ),
        "bytes_vs_fp32": round(bytes_ratio, 4),
        "gate_max_bytes": BYTES_MAX,
        "decision_parity": round(parity, 4),
        "decision_rows": f"{same}/{len(ref)}",
        "pick_tol_samples": PICK_TOL,
        "gate_min_parity": PARITY_MIN,
        "feed_speedup_int8_vs_fp32": round(feed_speedup, 2),
        "feed_ms_per_wf": {
            "fp32": round(f32_ms, 4), "int8_raw": round(i8_ms, 4),
        },
        "gate_min_speedup": SPEEDUP_MIN,
        "compiles_after_warmup": compiles,
        "int8_program": v_i8.get("warmup_program", ""),
        "tpu_run": "pending",
        "backend": jax.default_backend(),
        "wall_s": round(time.monotonic() - t0, 1),
    }
    print(json.dumps(verdict))
    if args.out:
        headline = {
            "metric": "phasenet_repick_int8_ladder",
            "value": verdict["feed_speedup_int8_vs_fp32"],
            "unit": "host-feed (fill+device_put) speedup int8 shards vs "
                    "fp32 (bytes-bound mechanism; end-to-end chip run "
                    "pending)",
            "gate_min_speedup": SPEEDUP_MIN,
            "bytes_vs_fp32": verdict["bytes_vs_fp32"],
            "gate_max_bytes": BYTES_MAX,
            "decision_parity": verdict["decision_parity"],
            "pick_tol_samples": PICK_TOL,
            "gate_min_parity": PARITY_MIN,
            "feed_ms_per_wf": verdict["feed_ms_per_wf"],
            "stage_ms_per_wf_int8": v_i8.get("stage_ms_per_wf", {}),
            "stage_ms_per_wf_fp32": v_f32.get("stage_ms_per_wf", {}),
            "compiles_after_warmup": compiles,
            "aot_program": verdict["int8_program"],
            "config": {
                "model": "phasenet", "events": N_EVENTS, "window": TRACE,
                "batch": BATCH, "batches_per_call": BPC,
                "pick_threshold": PICK_THR,
                "mech_events": MECH_EVENTS, "mech_window": MECH_TRACE,
                "mech_rows_per_call": MECH_BATCH,
            },
            "device": jax.devices()[0].platform,
            "backend": jax.default_backend(),
            "tpu_run": "pending",
            "measured_at": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
            ),
            "pass": verdict["ok"],
        }
        with open(args.out, "w") as f:
            f.write(json.dumps(headline) + "\n")
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
