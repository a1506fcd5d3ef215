"""The control of ``checks/lm_reference.py``: the plain reference put in the
program's place, computed one precision below the one the configuration
states (the operands of every matrix product it computes in bf16 rounded to
fp8 first; float32 accumulation; router, norms, softmax, decay, loss left in
float32 as the configuration states them). Per seed, its logits, loss and
gradient go through the check's own comparison (``compare_logits`` /
``compare_step`` of ``checks/lm_reference.py``) against the configuration's
limits, and the line printed is what a run would print: ``compared`` and
``correct``, which has to come out false (PERF.md section 2).

    python benchmarks/tools/control_lm.py --config nemotron3_nano_ep16 --seeds 1 2 \
        [--rehearse]

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


def control_lines(config: dict, seed: int, log=lambda text: None) -> list:
    """The check's ``compared`` lines with the control in the program's place."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from checks import lm_reference as lm

    reference = lm.load_reference(config)
    a = config["architecture"]
    limits = config["limits"]["lm_reference"]
    ids = lm.seeded_ids(seed, int(config["batch"]), int(config["in_samples"]),
                        int(a["vocab_size"]))
    lower = getattr(jnp, BELOW[config["dtype"]])

    def q(t):
        # Rounded on the way forward, exact on the way back (the cotangent
        # passes straight through): the gentler control. Cast back through
        # fp8, the cotangents of 1e-5 underflow and every gradient reads 0,
        # which any limit catches.
        return t + jax.lax.stop_gradient(
            t.astype(lower).astype(jnp.float32) - t)

    lines = []
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: reference.init(k, config))(
            jax.random.PRNGKey(seed % (2**31 - 1)))["params"]
        logits = {}
        for name, hook in (("ref", reference.exact), ("ctl", q)):
            logits[name] = np.asarray(jax.jit(
                lambda p, i, hook=hook: reference.forward({"params": p}, i, config, hook)
            )(params, ids), np.float32)
        lines += lm.compare_logits(logits["ctl"], logits["ref"], limits, log)
        del logits
        step = {}
        for name, hook in (("ref", reference.exact), ("ctl", q)):
            loss, gr = jax.jit(jax.value_and_grad(
                lambda p, i, hook=hook: reference.loss({"params": p}, i, config, hook)
            ))(params, ids)
            step[name] = (float(loss), lm.to_host(gr))
    lines += lm.compare_step(*step["ctl"], *step["ref"], limits, log)
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's CPU-sized rehearsal preset")
    args = ap.parse_args(argv)
    with open(os.path.join(BENCH, "configs", f"{args.config}.json")) as f:
        config = json.load(f)
    if args.rehearse:
        config = {**config, **config.get("rehearse", {})}
    import jax

    dev = jax.devices()[0]
    for seed in args.seeds:
        lines = control_lines(config, seed, log=lambda t: print(t, file=sys.stderr))
        print(json.dumps({
            "seed": seed, "control": BELOW[config["dtype"]],
            "device": f"{dev.platform}:{dev.device_kind}",
            "correct": all(line["ok"] for line in lines), "compared": lines,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
