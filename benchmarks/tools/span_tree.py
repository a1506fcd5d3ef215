"""The span tree of one run, from the ``spans.json`` a traced run leaves in
``.bench_cache/out/<cell>/`` (``readers/span_before_window_s.py``):

    python benchmarks/tools/span_tree.py .bench_cache/out/<cell>/spans.json

Two tables, set-up (from the first span's start to the window's opening) and
the window: per path of nested span names, the calls, the seconds, the self
seconds (a span's duration less its children's) and the share of the
stretch. Nesting is read from the times: a span lies under the latest span
that still contains it.
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Tuple


def tree(spans: List[Tuple[str, float, float]], t0: float, t1: float
         ) -> Dict[Tuple[str, ...], List[float]]:
    """``{path: [calls, seconds, self seconds]}`` of the spans clipped to
    [t0, t1]."""
    clipped = []
    for name, start, dur in spans:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            clipped.append((a, -b, name))
    out: Dict[Tuple[str, ...], List[float]] = {}
    stack: List[Tuple[float, Tuple[str, ...]]] = []  # (end, path)
    for a, neg_b, name in sorted(clipped):
        b = -neg_b
        while stack and stack[-1][0] <= a:
            stack.pop()
        # A span that outlasts the one around it (clock jitter of
        # microseconds) is cut to it.
        if stack:
            b = min(b, stack[-1][0])
        path = (stack[-1][1] if stack else ()) + (name,)
        entry = out.setdefault(path, [0, 0.0, 0.0])
        entry[0] += 1
        entry[1] += b - a
        entry[2] += b - a
        if stack:
            out[stack[-1][1]][2] -= b - a
        stack.append((b, path))
    return out


def table(title: str, rows: Dict[Tuple[str, ...], List[float]],
          stretch: float) -> str:
    lines = [f"{title}: {stretch:.3f} s"]
    top = sum(v[1] for k, v in rows.items() if len(k) == 1)
    for path, (calls, seconds, self_s) in sorted(rows.items()):
        lines.append(
            f"  {'  ' * (len(path) - 1)}{path[-1]:<{26 - 2 * len(path)}} "
            f"x{calls:<5d} {seconds:9.3f} s  self {self_s:9.3f} s  "
            f"{100 * seconds / stretch:6.2f}%")
    lines.append(f"  top-level spans cover {100 * top / stretch:.2f}% "
                 "of the stretch")
    return "\n".join(lines)


def main(argv=None) -> int:
    path = (argv or sys.argv[1:])[0]
    with open(path) as f:
        data = json.load(f)
    spans = [tuple(s) for s in data["spans"]]
    first = min(s for _n, s, _d in spans)
    print(table("set-up, first span to the window's opening",
                tree(spans, first, 0.0), -first))
    print(f"  (process start to the first span: "
          f"{data['setup_s'] + first:.3f} s of setup_s {data['setup_s']:.3f})")
    print(table("window", tree(spans, 0.0, data["window_s"]), data["window_s"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
