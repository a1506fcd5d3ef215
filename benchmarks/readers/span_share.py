"""Seconds of the bus span ``args.span`` inside the window, as a share of
the window (%). A run in which the span never occurs reads nothing."""


def read(record, args, ctx):
    spans = record["spans"]
    t0, t1 = _window(record)
    if not any(s[0] == args["span"] for s in spans.spans):
        return None
    inside = spans.between(args["span"], t0, t1)
    return 100.0 * sum(d for _s, d in inside) / (t1 - t0)


def _window(record):
    return record["t_open"], record["t_close"]
