"""Increase of the bus counter ``args.counter`` over the window, per second
of window. A counter the run never touched reads nothing."""


def read(record, args, ctx):
    name = args["counter"]
    if name not in record.get("counters_close", {}):
        return None
    delta = record["counters_close"][name] - record["counters_open"].get(name, 0.0)
    return delta / record["window_s"]
