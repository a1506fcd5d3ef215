"""A kernel's share of its roofline (%): the least time the chip could take
for the calls made — max(operations / peak FLOP/s, bytes / peak B/s), from
``flops.py`` on the configuration's shapes — over the kernel's measured
device time per step (inside the slice's step frame: whole steps,
counted). ``args.pattern`` finds the kernel's events; ``args.shapes``
names the configuration key that lists its shapes; ``args.passes`` says how
many kernel passes one optimizer step makes per listed shape (forward and
backward). A reading over 100 means the count is too high."""

import flops
import trace_reduce


def read(record, args, ctx):
    frame = (record.get("trace") or {}).get("frame")
    shapes = ctx.config.get(args["shapes"])
    if not frame or not shapes:
        return None
    seconds, count = trace_reduce.matching(frame, "ops_per_step", args["pattern"])
    if count == 0:
        return None
    peaks = ctx.device["peaks"]
    batch = int(ctx.config["batch"])
    least = 0.0
    for s in shapes:
        for p in args["passes"]:
            ops, nbytes = flops.attention_cost(
                batch=batch, dtype_bytes=int(args.get("dtype_bytes", 2)),
                backward=(p == "bwd"), **{k: s[k] for k in ("L", "M", "H", "E")}
            )
            least += s.get("calls", 1) * max(
                ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
            )
    return 100.0 * least / seconds
