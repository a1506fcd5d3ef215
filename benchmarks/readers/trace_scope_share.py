"""Share (%) of the frame's summed op seconds that a region
(``args.region``, e.g. ``unowned``: the coverage of the instrumentation
itself) or a pass (``args.pass``, e.g. ``bwd``) of the train step takes:
the table of ``trace_scope_ms``, as a share. None where that has nothing."""


def read(record, args, ctx):
    scope = ctx.load_module("readers", "trace_scope_ms")
    tab = scope.table(record, ctx)
    if tab is None or not tab["total"]:
        return None
    part = scope.seconds_of(tab, args.get("region"), args.get("pass"))
    return 100.0 * part / tab["total"]
