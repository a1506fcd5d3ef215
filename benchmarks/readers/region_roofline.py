"""A region's share of its roofline (%): the least time the chip could take
for what the region has to compute in one optimizer step —
max(operations / peak FLOP/s, bytes / peak B/s), the counts from
``costs_lm.<args.cost>(config, record)`` on the configuration's shapes and
the run's own counters — over the region's measured device time per step
(``args.region``, through the scope table of ``trace_scope_ms``). The same
count whatever implements the region; a reading over 100 means the count is
too high. Nothing to read (None) without a frame, without a scope map, or
where the region ran nothing."""

import costs_lm


def read(record, args, ctx):
    scope = ctx.load_module("readers", "trace_scope_ms")
    tab = scope.table(record, ctx)
    if tab is None:
        return None
    seconds = scope.seconds_of(tab, args["region"])
    if not seconds:
        return None
    ops, nbytes = getattr(costs_lm, args["cost"])(ctx.config, record)
    peaks = ctx.device["peaks"]
    least = max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
