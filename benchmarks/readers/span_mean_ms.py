"""Mean duration (ms) of the bus span ``args.span`` over the calls that
started inside the window."""


def read(record, args, ctx):
    t0, t1 = record["t_open"], record["t_close"]
    durs = [d for n, s, d in record["spans"].spans
            if n == args["span"] and t0 <= s <= t1]
    if not durs:
        return None
    return 1e3 * sum(durs) / len(durs)
