"""Sum of the bus counters ``args.counters`` as they stood when the window
opened (``record["counters_open"]``): what set-up accumulated. A program
that has none of them reads nothing."""


def read(record, args, ctx):
    at_open = record.get("counters_open") or {}
    found = [at_open[c] for c in args["counters"] if c in at_open]
    return sum(found) if found else None
