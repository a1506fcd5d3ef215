"""Share of the traced slice (%) in which no operation ran on the device:
1 - busy / slice, both on the trace's clock (``trace_reduce.reduce_planes``).
A train cell's slice lies inside an epoch's calls: it does not see the idle
of the validation passes, which ``validate_share.train`` reads."""


def read(record, args, ctx):
    trace = record.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
