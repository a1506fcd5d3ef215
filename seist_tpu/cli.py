"""CLI entry point — flag-compatible with the reference's ``main.py:8-179``.

Same ~60 flags, same modes (train / test / train_test), same log-dir layout.
TPU-specific deltas: ``--device`` is gone (JAX owns device placement; the
mesh covers every visible chip), torch-compile flags are gone (jit is always
on), and multi-host init uses ``jax.distributed`` instead of torchrun env
vars (seist_tpu/parallel/dist.py).
"""

from __future__ import annotations

import argparse
import os
from typing import List, Optional

from seist_tpu.utils.logger import logger
from seist_tpu.utils.misc import dump_namespace, get_time_str, setup_seed


def bool_(x) -> bool:
    return (
        False
        if str(x).strip().lower() in ("0", "false", "f", "no", "n")
        else bool(x)
    )


def get_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(
        description="seist_tpu model training/testing arguments"
    )

    # Mode
    parser.add_argument("--mode", type=str, default="train_test",
                        help="train/test/train_test (default:'train_test')")

    # Model
    parser.add_argument("--model-name", default="seist_m_dpk", type=str)
    parser.add_argument("--checkpoint", default="", type=str,
                        help="path to latest checkpoint (default: none)")
    parser.add_argument("--seq-shards", default=1, type=int,
                        dest="seq_shards",
                        help="shard the sequence axis over this many devices "
                        "(ring attention through the SeisT attention blocks; "
                        "device count must be divisible; use for long "
                        "--in-samples). Default 1 = pure data parallel")
    parser.add_argument("--conv-kernel-l1-alpha", default=0.0, type=float,
                        dest="conv_kernel_l1_alpha",
                        help="L1 (sign) regularization strength on "
                        "eqtransformer's encoder/decoder conv kernels "
                        "(ref eqtransformer.py conv_kernel_l1_regularization)")
    parser.add_argument("--conv-bias-l1-alpha", default=0.0, type=float,
                        dest="conv_bias_l1_alpha",
                        help="as --conv-kernel-l1-alpha, for conv biases")
    parser.add_argument("--dtype", default="fp32", type=str,
                        choices=["fp32", "bf16"],
                        help="compute dtype for train/eval steps: bf16 runs "
                        "matmuls/activations in bfloat16 on the MXU with "
                        "fp32 params/optimizer/BN-stats/softmax/loss "
                        "(default: fp32)")
    parser.add_argument("--loader-processes", default=0, type=int,
                        dest="loader_processes",
                        help="assemble batches with this many worker "
                        "PROCESSES instead of the --workers thread pool "
                        "(sidesteps the GIL for Python-bound augmentation "
                        "mixes; batches are bit-identical). Default 0 = "
                        "threads")
    parser.add_argument("--profile-steps", default=0, type=int,
                        dest="profile_steps",
                        help="capture a jax.profiler trace of this many "
                        "steady-state train steps (first epoch, after "
                        "warmup) into a unique "
                        "<logdir>/profile/<timestamp>_p<pid> dir (a "
                        "relaunched supervise attempt never clobbers the "
                        "previous capture); view with TensorBoard's "
                        "profile plugin. Later captures can be re-armed "
                        "live via SIGUSR2 or POST /profile on "
                        "--metrics-port. Default 0 = off")
    parser.add_argument("--metrics-port", default=0, type=int,
                        dest="metrics_port",
                        help="serve the telemetry plane on this loopback "
                        "port (docs/OBSERVABILITY.md): GET /metrics is "
                        "Prometheus text exposition of the metrics bus "
                        "(step spans, loss/wps gauges, data-plane "
                        "counters), /metrics.json + /flight are JSON "
                        "views, POST /profile triggers an on-demand "
                        "jax.profiler capture. -1 binds an ephemeral "
                        "port (logged). Default 0 = off")
    parser.add_argument("--flight-steps", default=256, type=int,
                        dest="flight_steps",
                        help="flight-recorder ring size: the last N "
                        "steps' metrics and span events are dumped to "
                        "<logdir>/flight/*.json on every death path "
                        "(rollback, stall, preempt, quarantine "
                        "overflow, crash). Default 256")
    parser.add_argument("--steps-per-call", default=0, type=int,
                        dest="steps_per_call",
                        help="scan this many optimizer updates inside one "
                        "jitted call (distinct micro-batches, NOT gradient "
                        "accumulation) — amortizes per-dispatch latency on "
                        "remote/contended devices. Per-step train metrics "
                        "are skipped (loss only); trailing batches that "
                        "don't fill a call are dropped. Default 0 = auto: "
                        "1 on the host path, min(32, steps/epoch) under "
                        "--device-aug cached (pass an explicit 1 to keep "
                        "per-step save/preempt granularity there)")
    parser.add_argument("--grad-accum-steps", default=1, type=int,
                        dest="grad_accum_steps",
                        help="accumulate gradients over this many "
                        "micro-batches into ONE optimizer update (scanned "
                        "in a single jitted program; peak memory is one "
                        "micro-batch) — train the reference's batch-500 "
                        "effective batch on a memory-tight chip by e.g. "
                        "--batch-size 100 --grad-accum-steps 5. Per-step "
                        "train metrics are skipped (loss only), and "
                        "trailing batches that don't fill an update are "
                        "dropped, as with --steps-per-call. Mutually "
                        "exclusive with --steps-per-call. Default 1")

    parser.add_argument("--device-aug", default="off", type=str,
                        choices=["off", "step", "cached"], dest="device_aug",
                        help="device-side augmentation + label synthesis "
                        "(docs/DATA_PIPELINE.md). 'step': the jitted train "
                        "step augments raw rows the host feeds (no "
                        "per-sample numpy work, no Python stacking). "
                        "'cached': whole raw epochs live in HBM, sharded "
                        "over the mesh data axis, and a scan executor "
                        "consumes (k,B) index arrays — zero per-step host "
                        "stacking; falls back to 'step' over the HBM "
                        "budget, to 'off' on unsupported configs (both "
                        "logged). Default off")
    parser.add_argument("--device-aug-hbm-gb", default=0.0, type=float,
                        dest="device_aug_hbm_gb",
                        help="HBM budget (GiB) for the --device-aug cached "
                        "epoch store. 0 = auto: half the device "
                        "bytes_limit, or 4 GiB when the backend reports "
                        "no memory stats")
    parser.add_argument("--ingest", default="auto", type=str,
                        choices=["auto", "direct", "host"],
                        help="raw-row feed for the device-aug step path "
                        "(docs/DATA.md). 'auto': direct shard->staging->"
                        "device ingest whenever the dataset is packed "
                        "(no Event decode, no resident waveform upload); "
                        "'host': always upload a resident RawStore; "
                        "'direct': demand the fast path, error instead "
                        "of degrading. Default auto")

    # Random seed
    parser.add_argument("--seed", default=0, type=int)

    # Logs
    parser.add_argument("--log-base", default="./logs", type=str)
    parser.add_argument("--log-step", default=4, type=int)
    parser.add_argument("--use-tensorboard", default=True, type=bool_)

    # Save results
    parser.add_argument("--save-test-results", default=True, type=bool_)

    # Dataset
    parser.add_argument("--data", default="", type=str, help="path to dataset")
    parser.add_argument("--dataset-name", default="diting_light", type=str,
                        help="'diting', 'diting_light', 'pnw', 'pnw_light', "
                        "'sos' or 'synthetic'")
    parser.add_argument("--data-split", type=bool_, default=True)
    parser.add_argument("--train-size", type=float, default=0.8)
    parser.add_argument("--val-size", type=float, default=0.1)
    parser.add_argument("--mixture-temperature", default=0.0, type=float,
                        dest="mixture_temperature",
                        help="temperature-weighted TRAIN sampling over a "
                        "multi-source packed mixture (pack_dataset.py "
                        "--mixture): per epoch slot, source s is drawn "
                        "with p ∝ (n_s/N)^(1/T) — 1.0 = proportional, "
                        "higher = flatter across sources. Deterministic "
                        "under the (seed, epoch, start_batch) resume "
                        "contract; 0 disables (plain global shuffle). "
                        "Eval/test always walk their splits plainly")

    # Data loader
    parser.add_argument("--shuffle", type=bool_, default=True)
    parser.add_argument("--workers", default=8, type=int)

    # Data preprocess
    parser.add_argument("--in-samples", default=8192, type=int)
    parser.add_argument("--label-width", type=float, default=0.5,
                        help="width of soft label (seconds)")
    parser.add_argument("--label-shape", type=str, default="gaussian",
                        help="'gaussian' 'triangle' 'box' or 'sigmoid'")
    parser.add_argument("--coda-ratio", default=2.0, type=float)
    parser.add_argument("--norm-mode", default="std", type=str)
    parser.add_argument("--min-snr", type=float, default=-float("inf"))
    parser.add_argument("--p-position-ratio", type=float, default=-1)

    # Data augmentation
    parser.add_argument("--augmentation", type=bool_, default=True)
    parser.add_argument("--add-event-rate", default=0.0, type=float)
    parser.add_argument("--max-event-num", default=1, type=int)
    parser.add_argument("--shift-event-rate", default=0.2, type=float)
    parser.add_argument("--add-noise-rate", default=0.4, type=float)
    parser.add_argument("--add-gap-rate", default=0.4, type=float)
    parser.add_argument("--min-event-gap", default=0.5, type=float,
                        help="minimum event gap (seconds)")
    parser.add_argument("--drop-channel-rate", default=0.4, type=float)
    parser.add_argument("--scale-amplitude-rate", default=0.4, type=float)
    parser.add_argument("--pre-emphasis-rate", default=0.4, type=float)
    parser.add_argument("--pre-emphasis-ratio", default=0.97, type=float)
    parser.add_argument("--generate-noise-rate", default=0.05, type=float)
    parser.add_argument("--mask-percent", default=0, type=int)
    parser.add_argument("--noise-percent", default=0, type=int)

    # Train
    parser.add_argument("--epochs", default=200, type=int)
    parser.add_argument("--patience", default=30, type=int)
    parser.add_argument("--steps", default=0, type=int,
                        help="if steps > 0, epochs is ignored")
    parser.add_argument("--start-epoch", default=0, type=int)
    parser.add_argument("--batch-size", default=500, type=int,
                        help="per-host batch size")
    parser.add_argument("--optim", default="Adam", type=str)
    parser.add_argument("--momentum", default=0.9, type=float)
    parser.add_argument("--weight_decay", default=0.0, type=float)
    parser.add_argument("--save-interval-steps", default=0, type=int,
                        dest="save_interval_steps",
                        help="step-granular async checkpoints every N "
                        "batches (orbax CheckpointManager; resume continues "
                        "mid-epoch at the exact data position). 0 = only "
                        "the best-val epoch checkpoints. A preemption "
                        "loses at most N batches of work")
    parser.add_argument("--keep-checkpoints", default=3, type=int,
                        dest="keep_checkpoints",
                        help="checkpoint retention: keep the last K step "
                        "checkpoints plus the best-val one; older ones are "
                        "GC'd (logged). Default 3")
    parser.add_argument("--bad-step-guard", default=True, type=bool_,
                        dest="bad_step_guard",
                        help="detect non-finite loss/grad-norm inside the "
                        "jitted step and skip the poisoned update (params, "
                        "optimizer state and LR-schedule step untouched). "
                        "Default true")
    parser.add_argument("--max-bad-steps", default=3, type=int,
                        dest="max_bad_steps",
                        help="consecutive guard-skipped updates before "
                        "rolling back to the last checkpoint. 0 disables "
                        "rollback (skips only). Default 3")
    parser.add_argument("--max-quarantine-frac", default=0.05, type=float,
                        dest="max_quarantine_frac",
                        help="abort the run once more than this fraction "
                        "of the dataset has been quarantined by the "
                        "data-plane guard (corrupt samples are benched "
                        "and deterministically replaced; past this "
                        "threshold the dataset is considered rotted and "
                        "training on fallbacks would be silent garbage). "
                        "Default 0.05")
    parser.add_argument("--data-watchdog-sec", default=600.0, type=float,
                        dest="data_watchdog_sec",
                        help="pipeline stall watchdog: if the train loop "
                        "waits longer than this for the next host batch "
                        "(loader wedged or a worker thread dead), dump "
                        "all thread stacks and exit with the clean-"
                        "preempt code (75) so tools/supervise.py "
                        "relaunches from the newest checkpoint. Only "
                        "time spent BLOCKED on the data plane counts — "
                        "step compute/compiles/validation do not. "
                        "0 disables. Default 600")
    parser.add_argument("--use-lr-scheduler", default=True, type=bool_)
    parser.add_argument("--lr-scheduler-mode", default="exp_range", type=str,
                        help="'triangular', 'triangular2' or 'exp_range'")
    parser.add_argument("--base-lr", default=8e-5, type=float)
    parser.add_argument("--max-lr", default=1e-3, type=float)
    parser.add_argument("--warmup-steps", default=2000, type=float,
                        help="<1 means ratio of total steps")
    parser.add_argument("--down-steps", default=3000, type=float,
                        help="<1 means ratio of total steps")

    # Val/Test
    parser.add_argument("--time-threshold", default=0.1, type=float,
                        help="pick residual threshold (seconds)")
    parser.add_argument("--min-peak-dist", default=1.0, type=float,
                        help="minimum peak distance (seconds)")
    parser.add_argument("--ppk-threshold", default=0.3, type=float)
    parser.add_argument("--spk-threshold", default=0.3, type=float)
    parser.add_argument("--det-threshold", default=0.5, type=float)
    parser.add_argument("--max-detect-event-num", default=1, type=int)

    # Synthetic-dataset shortcuts (no reference analogue; synthetic only)
    parser.add_argument("--synthetic-events", default=0, type=int,
                        help="synthetic dataset size (0 = default)")

    args = parser.parse_args(argv)

    if not 0 <= args.p_position_ratio <= 1:
        args.p_position_ratio = -1

    args.log_base = os.path.abspath(args.log_base)
    if args.data:
        args.data = os.path.abspath(args.data)
    if args.checkpoint:
        args.checkpoint = os.path.abspath(args.checkpoint)

    args.dataset_kwargs = None
    if args.dataset_name == "synthetic" and args.synthetic_events:
        args.dataset_kwargs = {"num_events": args.synthetic_events}
    return args


def main_worker(args: argparse.Namespace) -> None:
    """Mode dispatch (ref main.py:182-210)."""
    from seist_tpu.obs.bus import BUS

    with BUS.span("setup_imports"):  # flax, optax, orbax come in here
        from seist_tpu.train.worker import (
            is_main_process,
            test_worker,
            train_worker,
        )
        from seist_tpu.utils.misc import enable_compile_cache

        enable_compile_cache()

    log_dir = (
        os.path.join(
            args.log_base,
            f"{get_time_str()}_{args.model_name}_{args.dataset_name}",
        )
        if not args.checkpoint
        else args.checkpoint.split("checkpoints")[0]
    )
    # Multi-host: the timestamped dir is built from per-host wall clocks
    # that can straddle a second boundary; every process must agree on one
    # path before the collective orbax save (ref broadcasts the ckpt path
    # rank0->all, train.py:481-482 — here the whole log dir is agreed up
    # front instead).
    from seist_tpu.parallel.dist import broadcast_object, process_count

    if process_count() > 1:
        log_dir = broadcast_object(log_dir)
    logger.set_logdir(log_dir)
    logger.set_logger("global")
    if not is_main_process():
        logger.enable_console(False)
    logger.info(f"pid: {os.getpid()}")
    logger.info(f"\n{dump_namespace(args)}")

    mode = args.mode.split("_")
    if not set(("train", "test")) & set(mode):
        raise ValueError(
            f"`mode` must be 'train','test' or 'train_test', got '{args.mode}'"
        )
    if "train" in mode:
        setup_seed(args.seed)
        logger.set_logger("train")
        ckpt_path = train_worker(args)
        args.checkpoint = ckpt_path
    if "test" in mode:
        setup_seed(args.seed)
        logger.set_logger("test")
        test_worker(args)


def main(argv: Optional[List[str]] = None) -> None:
    import sys

    import seist_tpu
    from seist_tpu.parallel.dist import init_distributed_mode

    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "serve":
        # Online inference service (seist_tpu/serve/): own flag namespace,
        # no train/test machinery — dispatch before the big parser.
        from seist_tpu.serve.server import main as serve_main

        return serve_main(argv[1:])
    from seist_tpu.obs.bus import BUS

    # The first of the set-up spans (docs/OBSERVABILITY.md): its start is
    # the program's start on the bus clock.
    with BUS.span("setup_imports"):
        args = get_args(argv)
        args.distributed = init_distributed_mode()
        seist_tpu.load_all()
    main_worker(args)


if __name__ == "__main__":
    main()
