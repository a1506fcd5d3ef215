"""Run bench.py over the per-config matrix; collect JSON lines.

Sequentially benchmarks each config from BASELINE.json's `configs` list
(SURVEY.md §6) via bench.py subprocesses — this parent never imports jax,
so each child has the chip to itself — writing
``chiprun_out/bench_matrix.json`` and printing a markdown table. A config
whose bench.py fails is recorded with its error and makes the sweep exit
non-zero; nothing is carried over from an earlier sweep.

Usage:
    python tools/bench_matrix.py [--steps 20] [--only seist_m_pmp,...]
    python tools/bench_matrix.py --mode eval
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_TOOLS = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_TOOLS)

# (model, batch) — batch chosen so batch*in_samples stays ~2M samples
# (the flagship's 256 x 8192 working set); all in_samples 8192 per the
# reference training shape (ref main.py:119-149).
CONFIGS = [
    ("seist_s_dpk", 256),
    ("seist_m_dpk", 256),
    ("seist_l_dpk", 256),
    ("phasenet", 256),
    ("eqtransformer", 64),  # BiLSTM scan: far slower per wf, keep runs short
    ("magnet", 256),
    ("ditingmotion", 256),
    ("baz_network", 256),
    # distpt_network: registered but no task spec, matching the reference's
    # commented-out config (ref config.py:112-125) — nothing to train.
    ("seist_m_pmp", 256),
    ("seist_l_emg", 256),
    ("seist_l_baz", 256),
    ("seist_l_dis", 256),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--only", type=str, default="")
    ap.add_argument(
        "--mode",
        default="train",
        choices=["train", "eval"],
        help="bench.py BENCH_MODE: full train step or no-grad eval step",
    )
    ap.add_argument(
        "--out",
        default=None,
        help="result JSON (default: chiprun_out/bench_matrix.json, or "
        "chiprun_out/bench_matrix_eval.json under --mode eval)",
    )
    args = ap.parse_args()
    if args.out is None:
        name = "bench_matrix_eval.json" if args.mode == "eval" else "bench_matrix.json"
        args.out = os.path.join(_REPO, "chiprun_out", name)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    only = set(args.only.split(",")) if args.only else None
    results = {}

    for model, batch in CONFIGS:
        if only and model not in only:
            continue
        env = dict(
            os.environ,
            BENCH_MODEL=model,
            BENCH_BATCH=str(batch),
            BENCH_STEPS=str(args.steps),
            BENCH_MODE=args.mode,
        )
        # Pin the dtype unless the caller chose one: the matrix's rows are
        # only comparable to each other at a fixed dtype, and bench.py's
        # own default may evolve.
        env.setdefault("BENCH_DTYPE", "fp32")
        print(f"=== {model} (batch {batch}) ===", file=sys.stderr, flush=True)
        try:
            r = subprocess.run(
                [sys.executable, os.path.join(_REPO, "bench.py")],
                capture_output=True,
                text=True,
                env=env,
                timeout=3600,
            )
        except subprocess.TimeoutExpired:
            payload = {"error": "timeout after 3600s"}
            r = None
        if r is not None:
            sys.stderr.write(r.stderr[-800:] + "\n")
            line = (
                r.stdout.strip().splitlines()[-1] if r.stdout.strip() else "{}"
            )
            try:
                payload = json.loads(line)
            except json.JSONDecodeError:
                payload = {"error": f"unparseable: {line[:200]}"}
            if r.returncode != 0:
                payload = {"error": f"bench.py rc={r.returncode}: {line[:200]}"}
        results[model] = payload
        with open(args.out, "w") as f:  # persist incrementally
            json.dump(results, f, indent=1)
        print(json.dumps(payload), flush=True)

    print("\n| config | batch | wf/s/chip | step ms | MFU |", flush=True)
    print("|---|---|---|---|---|", flush=True)
    for model, _ in CONFIGS:
        p = results.get(model)
        if not p or not p.get("value"):
            continue
        print(
            f"| {model} | {p.get('batch')} | {p.get('value'):,.0f} | "
            f"{p.get('step_time_ms')} | {p.get('mfu', 0) * 100:.1f}% |",
            flush=True,
        )
    failed = sorted(m for m, p in results.items() if p.get("error"))
    if failed:
        sys.exit(f"bench_matrix: no measurement for {failed}")


if __name__ == "__main__":
    main()
