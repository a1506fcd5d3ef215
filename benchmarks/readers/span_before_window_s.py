"""Seconds of the bus spans ``args.spans`` before the window opened (a span
that runs into the window counts up to its opening): a phase of set-up. A
run in which none of them occurs reads nothing.

Also leaves every span of the run in ``<out>/spans.json`` (name, start
relative to the window's opening, seconds; plus the window's length), which
``tools/span_tree.py`` turns into the coverage tables of PERF.md."""

import json
import os


def read(record, args, ctx):
    t_open = record["t_open"]
    spans = list(record["spans"].spans)
    path = os.path.join(ctx.out, "spans.json")
    if not os.path.exists(path):
        with open(path, "w") as f:
            json.dump({
                "window_s": record["t_close"] - t_open,
                "setup_s": record["setup_s"],
                "spans": [[n, s - t_open, d] for n, s, d in spans],
            }, f)
    found = [(s, d) for n, s, d in spans if n in args["spans"]]
    if not found:
        return None
    return sum(max(0.0, min(s + d, t_open) - s) for s, d in found)
