"""Increase of the bus counter ``args.counter`` over the window as a share
(%) of ``args.per`` x the increase of ``args.over``: e.g. token-slots on
experts held, summed over the expert layers, per (experts per token x
expert layers x tokens trained). A run that touched either counter never
reads nothing."""


def read(record, args, ctx):
    opened = record.get("counters_open") or {}
    closed = record.get("counters_close") or {}
    if args["counter"] not in closed or args["over"] not in closed:
        return None
    num = closed[args["counter"]] - opened.get(args["counter"], 0.0)
    den = closed[args["over"]] - opened.get(args["over"], 0.0)
    if den <= 0:
        return None
    return 100.0 * num / (float(args.get("per", 1.0)) * den)
