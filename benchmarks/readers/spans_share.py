"""Seconds of the bus spans ``args.spans`` (several: ``span_share`` takes
one) inside the window, as a share of the window (%). The spans must not
nest in one another. A run in which none of them occurs reads nothing."""


def read(record, args, ctx):
    spans = record["spans"]
    t0, t1 = record["t_open"], record["t_close"]
    if not any(s[0] in args["spans"] for s in spans.spans):
        return None
    inside = sum(d for name in args["spans"]
                 for _s, d in spans.between(name, t0, t1))
    return 100.0 * inside / (t1 - t0)
