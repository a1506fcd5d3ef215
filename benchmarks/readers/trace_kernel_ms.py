"""Summed device time (ms) of the operations whose trace name matches
``args.pattern``, per optimizer step (device trace, ``XLA Ops`` line, inside
the slice's step frame: whole steps, counted)."""

import trace_reduce


def read(record, args, ctx):
    frame = (record.get("trace") or {}).get("frame")
    if not frame:
        return None
    seconds, count = trace_reduce.matching(frame, "ops_per_step", args["pattern"])
    if count == 0:
        return None
    return 1e3 * seconds
