"""End-to-end metric parity: torch reference vs this framework, same data.

The accuracy half of the north-star ("P/S-pick F1 parity with the reference",
BASELINE.json) cannot be run on real PNW/DiTing archives in this sandbox (no
datasets on disk, zero egress) — so this harness constructs the strongest
available evidence: BOTH frameworks evaluate the SAME published reference
weights on the SAME on-disk DiTing-light-format fixture through their FULL
test pipelines (reader -> split -> preprocess -> forward -> postprocess ->
metrics), and the per-task metrics are compared.

Exactness levers:
* fixture traces are exactly ``--in-samples`` long, making the reference's
  randomized eval window cut a no-op (ref preprocess.py:207-219) — model
  inputs are bit-identical;
* both sides read the identical CSV+HDF5 bytes and use the same pandas
  ``sample(frac=1, random_state=seed)`` shuffle + contiguous split (ref
  diting.py:281-299); the harness asserts the test-split ev_id lists match
  before comparing metrics;
* the reference's missing deps are stubbed read-only in the driver
  (tools/_ref_eval_driver.py) — /root/reference is never modified.

Usage:
    python tools/parity_eval.py [--model-name seist_s_dpk] [--n-events 240]

Writes <workdir>/parity_eval_result.json and prints a comparison table.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys

_TOOLS = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_TOOLS)
sys.path.insert(0, _TOOLS)
sys.path.insert(0, _REPO)

from fixtures import write_diting_light_fixture  # noqa: E402


def _run(cmd, env=None, timeout=3600) -> str:
    print("+", " ".join(cmd), file=sys.stderr, flush=True)
    r = subprocess.run(
        cmd, capture_output=True, text=True, env=env, timeout=timeout
    )
    if r.returncode != 0:
        sys.stderr.write(r.stdout[-2000:] + "\n" + r.stderr[-4000:] + "\n")
        raise RuntimeError(f"{cmd[1]} failed rc={r.returncode}")
    return r.stdout


def _make_random_init_pth(
    model_name: str, in_samples: int, seed: int, out_path: str
) -> None:
    """Seeded random-init torch state-dict from the READ-ONLY reference
    registry (shared timm stub from tools/bench_reference.py)."""
    import torch

    from bench_reference import _install_timm_stub

    _install_timm_stub()
    if "/root/reference" not in sys.path:
        sys.path.insert(0, "/root/reference")
    from models import create_model as torch_create  # reference registry

    from seist_tpu import taskspec

    torch.manual_seed(seed)
    tm = torch_create(
        model_name,
        in_channels=taskspec.get_num_inchannels(model_name),
        in_samples=in_samples,
    )
    torch.save(tm.state_dict(), out_path)
    print(f"random-init state dict -> {out_path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model-name", default="seist_s_dpk")
    ap.add_argument("--n-events", type=int, default=240)
    ap.add_argument("--in-samples", type=int, default=8192)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--seed", type=int, default=3)
    # 0.05/0.05 split -> 90% of events land in the test split (the only
    # split this harness evaluates).
    ap.add_argument("--train-size", type=float, default=0.05)
    ap.add_argument("--val-size", type=float, default=0.05)
    ap.add_argument(
        "--workdir", default=os.path.join(_REPO, "logs", "parity_eval")
    )
    ap.add_argument("--keep-workdir", action="store_true")
    ap.add_argument(
        "--random-init-seed",
        type=int,
        default=None,
        help="for models WITHOUT a published reference checkpoint (e.g. "
        "eqtransformer — the 18 shipped .pth are all seist variants): "
        "generate a seeded random-init torch state-dict and run both "
        "pipelines with it. The metrics are then meaningless as accuracy "
        "but must still MATCH — this compares the pipelines, not the "
        "model quality.",
    )
    args = ap.parse_args()

    os.makedirs(args.workdir, exist_ok=True)
    pth = os.path.join(
        "/root/reference/pretrained", f"{args.model_name}_diting.pth"
    )
    if not os.path.exists(pth):
        if args.random_init_seed is None:
            raise FileNotFoundError(
                f"{pth} (pass --random-init-seed N to compare pipelines "
                "with generated weights)"
            )
        # Cache key carries seed AND in_samples: a bare model-name key
        # would silently reuse stale weights when either changes (and the
        # imported-orbax cache below must track the same identity or the
        # two sides could load different weights).
        tag = f"{args.model_name}_s{args.random_init_seed}_l{args.in_samples}"
        pth = os.path.join(args.workdir, f"random_{tag}.pth")
        if not os.path.exists(pth):
            _make_random_init_pth(
                args.model_name, args.in_samples, args.random_init_seed, pth
            )

    fixture = os.path.join(args.workdir, "diting_fixture")
    if not os.path.exists(os.path.join(fixture, "DiTing330km_light.csv")):
        print("writing fixture ...", file=sys.stderr, flush=True)
        write_diting_light_fixture(
            fixture,
            n_events=args.n_events,
            trace_samples=args.in_samples,
        )

    common = [
        "--mode", "test",
        "--model-name", args.model_name,
        "--dataset-name", "diting_light",
        "--data", fixture,
        "--seed", str(args.seed),
        "--batch-size", str(args.batch_size),
        "--workers", "0",  # inline loading on this 1-core host (ours clamps to 1 thread)
        "--in-samples", str(args.in_samples),
        "--train-size", str(args.train_size),
        "--val-size", str(args.val_size),
        "--save-test-results", "false",
        "--use-tensorboard", "false",
    ]

    # --- reference side (torch, CPU) ---
    ref_log = os.path.join(args.workdir, "ref_logs")
    out = _run(
        [
            sys.executable, os.path.join(_TOOLS, "_ref_eval_driver.py"),
            *common,
            "--device", "cpu",
            "--use-torch-compile", "false",
            "--checkpoint", pth,
            "--log-base", ref_log,
        ]
    )
    ref = json.loads(
        [ln for ln in out.splitlines() if ln.startswith("PARITY_JSON ")][-1][
            len("PARITY_JSON "):
        ]
    )

    # --- our side: import weights, then the production test CLI ---
    # Key the imported-orbax cache by the SOURCE .pth filename so the
    # random-init tag (seed/in_samples) flows through.
    ckpt = os.path.join(
        args.workdir, "imported", os.path.splitext(os.path.basename(pth))[0]
    )
    if not os.path.exists(ckpt):
        _run(
            [
                sys.executable, os.path.join(_TOOLS, "import_pretrained.py"),
                "--pth", pth,
                "--model-name", args.model_name,
                "--in-samples", str(args.in_samples),
                "--out", ckpt,
            ]
        )
    ours_log = os.path.join(args.workdir, "ours_logs", "run")
    shutil.rmtree(ours_log, ignore_errors=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    _run(
        [
            sys.executable, os.path.join(_REPO, "main.py"),
            *common,
            "--checkpoint", ckpt,
            "--log-base", ours_log,
        ],
        env=env,
    )
    # main.py derives the log dir from --checkpoint when set (reference
    # contract, ref main.py:184-188) — find the metrics JSON where the run
    # actually wrote it.
    metrics_files = []
    for root in (ours_log, os.path.dirname(ckpt)):
        for dirpath, _, files in os.walk(root):
            metrics_files += [
                os.path.join(dirpath, f)
                for f in files
                if f.startswith("test_metrics_")
            ]
    if not metrics_files:
        raise RuntimeError("our test run produced no test_metrics_*.json")
    with open(max(metrics_files, key=os.path.getmtime)) as f:
        ours = json.load(f)

    # --- compare ---
    # Split identity check: metrics are only comparable if both frameworks
    # put the SAME events in the test split (both use pandas
    # sample(frac=1, random_state=seed) + contiguous ranges — ref
    # diting.py:281-299).
    import seist_tpu.data  # noqa: F401  (dataset registration; CPU-only path)
    from seist_tpu.registry import DATASETS

    ours_ds = DATASETS.create(
        "diting_light",
        seed=args.seed,
        mode="test",
        data_dir=fixture,
        shuffle=True,
        data_split=True,
        train_size=args.train_size,
        val_size=args.val_size,
    )
    our_ev_ids = [int(v) for v in ours_ds._meta_data["ev_id"]]
    if our_ev_ids != ref["ev_ids"]:
        raise RuntimeError(
            f"test splits differ: ref {len(ref['ev_ids'])} events, "
            f"ours {len(our_ev_ids)} — metric comparison would be invalid"
        )
    print(f"test split identical on both sides: {len(our_ev_ids)} events")
    rows, max_abs = [], 0.0
    for task, ref_m in sorted(ref["metrics"].items()):
        our_m = ours["metrics"].get(task, {})
        for name, rv in sorted(ref_m.items()):
            ov = our_m.get(name, float("nan"))
            d = abs(ov - rv)
            max_abs = max(max_abs, d if d == d else float("inf"))
            rows.append((task, name, rv, ov, d))
    print(f"\n{'task':8s} {'metric':10s} {'reference':>12s} "
          f"{'ours':>12s} {'|diff|':>10s}")
    for task, name, rv, ov, d in rows:
        print(f"{task:8s} {name:10s} {rv:12.6f} {ov:12.6f} {d:10.2e}")
    print(f"\nloss: ref {ref['loss']:.6f}  ours {ours['loss']:.6f}")
    print(f"max metric |diff|: {max_abs:.3e}")

    result = {
        "model": args.model_name,
        "n_test_events": len(ref.get("ev_ids", [])),
        "reference": ref["metrics"],
        "ours": ours["metrics"],
        "ref_loss": ref["loss"],
        "our_loss": ours["loss"],
        "max_abs_diff": max_abs,
    }
    out_path = os.path.join(args.workdir, "parity_eval_result.json")
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(f"saved: {out_path}")


if __name__ == "__main__":
    main()
