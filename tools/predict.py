"""Continuous-record phase picking CLI (capability the reference lacks —
its demo scores exactly one 8192-sample window, demo_predict.py:59-97).

    python tools/predict.py --model-name seist_s_dpk \
        --checkpoint ./imported/seist_s_dpk \
        --input record.npz --output picks.csv \
        [--window 8192] [--stride 4096] [--batch-size 32]

``--input``: .npz with a ``data`` array of shape (L, C) or (C, L), any
length >= window. Output CSV: one row per pick/detection with absolute
sample index and time (s at --sampling-rate).
"""

from __future__ import annotations

import argparse
import os
import sys

_TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_TOOLS))


def main() -> None:
    ap = argparse.ArgumentParser(description="continuous-record picking")
    ap.add_argument("--model-name", default="seist_s_dpk")
    ap.add_argument("--checkpoint", required=True)
    ap.add_argument("--input", required=True, help=".npz with 'data'")
    ap.add_argument("--output", default="picks.csv")
    ap.add_argument("--window", type=int, default=8192)
    ap.add_argument("--stride", type=int, default=0, help="0 = window//2")
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--sampling-rate", type=int, default=50)
    ap.add_argument("--ppk-threshold", type=float, default=0.3)
    ap.add_argument("--spk-threshold", type=float, default=0.3)
    ap.add_argument("--det-threshold", type=float, default=0.5)
    ap.add_argument("--min-peak-dist", type=float, default=1.0)
    ap.add_argument("--combine", default="max", choices=["mean", "max"],
                    help="overlap stitching: max (robust picks, default) "
                    "or mean (smoother curves)")
    ap.add_argument("--max-events", type=int, default=0,
                    help="cap on picks over the whole record; 0 = scale "
                    "with record length (4 per window span)")
    args = ap.parse_args()

    import numpy as np
    import pandas as pd

    import seist_tpu
    from seist_tpu import taskspec
    from seist_tpu.ops.stream import annotate
    from seist_tpu.serve.pool import load_model_entry

    seist_tpu.load_all()

    # Fail fast on model family before touching the input file.
    spec = taskspec.get_task_spec(args.model_name)
    first_group = spec.labels[0]
    if not (
        isinstance(first_group, (tuple, list))
        and tuple(first_group)[0] in ("non", "det")
        and len(first_group) == 3
    ):
        raise SystemExit(
            f"{args.model_name} is not a dpk-family model "
            f"(labels {spec.labels}); continuous picking needs "
            f"(non|det, ppk, spk) outputs"
        )
    channel0 = first_group[0]

    npz = np.load(args.input)
    record = np.asarray(npz["data"], np.float32)
    if record.ndim != 2:
        raise ValueError(f"'data' must be 2-D, got {record.shape}")
    if record.shape[0] < record.shape[1]:  # (C, L) -> (L, C)
        record = record.T

    # Checkpoint loading/warm-up logic lives in the serve model pool —
    # offline CLI and online service share exactly one loader.
    entry = load_model_entry(
        args.model_name, args.checkpoint, window=args.window
    )

    picks = annotate(
        entry.forward,
        record,
        jitted=True,  # entry.forward is already jax.jit'd by the pool
        window=args.window,
        stride=args.stride or None,
        batch_size=args.batch_size,
        sampling_rate=args.sampling_rate,
        ppk_threshold=args.ppk_threshold,
        spk_threshold=args.spk_threshold,
        det_threshold=args.det_threshold,
        min_peak_dist=args.min_peak_dist,
        combine=args.combine,
        max_events=args.max_events or None,
        channel0=channel0,
    )

    fs = float(args.sampling_rate)
    rows = []
    for idx in picks["ppk"]:
        rows.append({"kind": "P", "sample": int(idx), "time_s": idx / fs})
    for idx in picks["spk"]:
        rows.append({"kind": "S", "sample": int(idx), "time_s": idx / fs})
    for on, off in picks["det"]:
        rows.append({
            "kind": "detection", "sample": int(on), "time_s": on / fs,
            "end_sample": int(off), "end_time_s": off / fs,
        })
    pd.DataFrame(rows).to_csv(args.output, index=False)
    print(
        f"{len(picks['ppk'])} P, {len(picks['spk'])} S, "
        f"{len(picks['det'])} detections -> {args.output}"
    )


if __name__ == "__main__":
    main()
