"""The control of the eval check: the plain reference put in the program's
place, computed one precision below the one the configuration states
(operands in fp8 for a bf16 configuration, in bf16 for an fp32 one, float32
accumulation). Prints, per seed, the gap numbers the check compares — they
have to come out ABOVE the configuration's limits (PERF.md section 2).

    python benchmarks/tools/control.py --config seist_l_dpk --seeds 1 2 3 \
        [--batch 256] [--in-samples 8192]

Runs on whatever device JAX has; a limit is set only from a chip run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

BELOW = {"bf16": "float8_e4m3fn", "fp32": "bfloat16"}


def control_gaps(config: dict, seed: int, batch: int, rows: int = 32) -> dict:
    import jax.numpy as jnp

    from checks import eval_reference as er

    reference = er.load_reference(config)
    n, c = int(config["in_samples"]), int(config["in_channels"])
    x = er.seeded_waveforms(seed, batch, n, c)
    rows = min(rows, batch)
    variables = er.make_variables(reference, config, seed, jnp.asarray(x[:rows]))
    ref = er.reference_outputs(reference, config, variables, x, rows)
    lower = getattr(jnp, BELOW[config["dtype"]])
    ctl = er.reference_outputs(
        reference, config, variables, x, rows, q=reference.ops.through(lower)
    )
    return {"seed": seed, "control": BELOW[config["dtype"]],
            "range": float(ref.max() - ref.min()), **er.gap_stats(ctl, ref)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--in-samples", type=int, default=0)
    args = ap.parse_args(argv)
    with open(os.path.join(BENCH, "configs", f"{args.config}.json")) as f:
        config = json.load(f)
    if args.in_samples:
        config["in_samples"] = args.in_samples
    import jax

    dev = jax.devices()[0]
    for seed in args.seeds:
        out = control_gaps(config, seed, args.batch or int(config["batch"]))
        out["device"] = f"{dev.platform}:{dev.device_kind}"
        out["limits"] = config["limits"]["eval_reference"]
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
