"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but
``jax.profiler.ProfileData``.

A TPU trace has one plane per device (``/device:TPU:<n>``) whose lines
include ``XLA Modules`` (one event per execution of a compiled program,
named ``<jit name>(<fingerprint>)``) and ``XLA Ops`` (one event per HLO
operation). Times are nanoseconds on the trace's own clock.

* busy seconds: the union of the op intervals of a device plane, averaged
  over the device planes; idle share = 1 - busy / window;
* per-name sums over ops and over programs;
* the step frame of a training slice: a stretch of whole optimizer steps,
  counted and not estimated, with its busy seconds and per-name sums per
  step (``step_frame``);
* idle gaps: the longest intervals with no op on the device, each
  attributed to the bus span that covered most of it on the host clock.
  The two clocks are aligned by the window's ends (the host's
  ``start_trace``/``stop_trace`` instants against the trace's first and
  last device event), which is good to a few milliseconds; spans written
  into the trace itself are the tracing issue's job (PERF.md).
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
# Ops that only contain other ops: a scanned call is one ``while`` event
# spanning all its steps, idle gaps included, and its children are events of
# their own — counting it would make every slice read 100% busy.
CONTAINERS = ("while", "conditional", "call")
MODULES_LINE = "XLA Modules"
Interval = Tuple[float, float]


def union_seconds(intervals: Iterable[Interval]) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def gaps(intervals: Iterable[Interval], t0: float, t1: float) -> List[Interval]:
    """Intervals of [t0, t1] covered by none of ``intervals``."""
    out, edge = [], t0
    for a, b in sorted(intervals):
        if a > edge:
            out.append((edge, min(a, t1)))
        edge = max(edge, b)
        if edge >= t1:
            break
    if edge < t1:
        out.append((edge, t1))
    return [(a, b) for a, b in out if b > a]


def sum_by_name(events: Iterable[Tuple[str, float, float]]) -> Dict[str, Dict[str, float]]:
    out: Dict[str, Dict[str, float]] = {}
    for name, _start, dur in events:
        e = out.setdefault(name, {"count": 0, "seconds": 0.0})
        e["count"] += 1
        e["seconds"] += dur
    return out


def attribute_gap(gap: Interval, spans: Sequence[Tuple[str, float, float]],
                  ignore: Sequence[str] = ("train_epoch", "log_interval")) -> str:
    """Name of the host span covering most of ``gap`` (host clock), or
    "none". Enclosing bookkeeping spans are ignored."""
    best, best_cover = "none", 0.0
    ga, gb = gap
    for name, start, dur in spans:
        if name in ignore:
            continue
        cover = min(gb, start + dur) - max(ga, start)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def opcode(name: str) -> str:
    m = _OPCODE.search(name.partition(" = ")[2])
    return m.group(1) if m else ""


def label(name: str) -> str:
    """A trace event's name cut to what a reader needs: an op event carries
    its whole HLO line (``%fusion.7 = bf16[256,2048,16]{...} fusion(...)``);
    keep the instruction, its opcode and its first output shape."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    op, shape = _OPCODE.search(rest), _SHAPE.search(rest)
    return " ".join(x for x in (
        head, op.group(1) if op else "", shape.group(0) if shape else "") if x)


def read_planes(path: str) -> List[Dict[str, Any]]:
    """Device planes as ``{"name", "lines": {line: [(event, start_s, dur_s)]}}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines: Dict[str, List[Tuple[str, float, float]]] = {}
        for line in plane.lines:
            events = [
                (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                for ev in line.events
            ]
            lines[line.name] = events
        planes.append({"name": plane.name, "lines": lines})
    return planes


def reduce_planes(
    planes: Sequence[Dict[str, Any]],
    spans: Sequence[Tuple[str, float, float]] = (),
    host_t0: Optional[float] = None,
    host_t1: Optional[float] = None,
    top: int = 10,
) -> Dict[str, Any]:
    if not planes:
        return {"busy_s": 0.0, "window_s": 0.0, "planes": 0}
    # Ops that only contain other ops are left out: see CONTAINERS.
    leaves = [
        [e for e in p["lines"].get(OPS_LINE, [])
         if opcode(e[0]) not in CONTAINERS]
        for p in planes
    ]
    busy, ops_all, programs_all, gap_all = [], [], [], []
    t_first = min((e[1] for ops in leaves for e in ops), default=0.0)
    t_last = max((e[1] + e[2] for ops in leaves for e in ops), default=0.0)
    # The traced window, on the trace's clock. The device records from
    # somewhere inside ``start_trace`` to somewhere inside ``stop_trace``,
    # the host stamps the instants between the two calls: the trace holds a
    # millisecond or so more than the host's interval (0.2512 s against
    # 0.2502 s, chip call B of PR 23), and a device that is busy throughout
    # then reads busier than the window is long. So the window is the host's
    # interval and the events' extent together, aligned at their ends: every
    # event lies inside it, and idle time before the first event counts as
    # far as the host's interval reaches back.
    # Host clock of a trace instant: the window's end is the last device
    # event.
    shift = (host_t1 - t_last) if host_t1 is not None else 0.0
    t0 = t_first if host_t0 is None else min(t_first, host_t0 - shift)
    window = t_last - t0
    for p, ops in zip(planes, leaves):
        iv = [(s, s + d) for _n, s, d in ops]
        idle = gaps(iv, t0, t_last)
        # Busy as the window less its gaps, so that no rounding over a
        # hundred thousand intervals can carry it past the window.
        busy.append(window - sum(b - a for a, b in idle))
        ops_all.extend(ops)
        programs_all.extend(p["lines"].get(MODULES_LINE, []))
        gap_all.extend(idle)
    n = len(planes)
    ops = sum_by_name(ops_all)
    programs = sum_by_name(programs_all)
    by_time = sorted(ops.items(), key=lambda kv: -kv[1]["seconds"])
    named: Dict[str, float] = {}
    for a, b in gap_all:
        who = attribute_gap((a + shift, b + shift), spans)
        named[who] = named.get(who, 0.0) + (b - a) / n
    longest = sorted(gap_all, key=lambda g: g[0] - g[1])[:top]
    return {
        "planes": n,
        "busy_s": sum(busy) / n,
        "window_s": window,
        "ops": {k: {"count": v["count"] / n, "seconds": v["seconds"] / n}
                for k, v in ops.items()},
        "programs": {k: {"count": v["count"] / n, "seconds": v["seconds"] / n}
                     for k, v in programs.items()},
        "frame": merge_frames([step_frame(o) for o in leaves]),
        "device_ops": [[label(k), v["seconds"] / n] for k, v in by_time[:top]],
        "idle_by_span": sorted(named.items(), key=lambda kv: -kv[1]),
        "idle_gaps": [
            [attribute_gap((a + shift, b + shift), spans), b - a]
            for a, b in longest
        ],
    }


def step_frame(ops: Sequence[Tuple[str, float, float]],
               min_agree: float = 0.9) -> Optional[Dict[str, Any]]:
    """A stretch of whole optimizer steps in one device's ops (containers
    left out), counted and not estimated.

    Inside a training slice every op of the step runs once per step, whether
    the step is a program of its own or one iteration of a scanned call; an
    op in the body of an inner loop runs a multiple of that, a per-call op
    once. Of the distinct op names in the slice, by far the most are the
    step's own, so the count that most names share is the number of times
    the step ran (a slice cut inside a step gives two neighbouring counts;
    either serves). From the first start of one such op to its last start
    lie exactly count - 1 steps, whatever else runs in between: that
    stretch is the frame. The op is the heaviest with that count, only so
    that the choice is definite: an op that runs more often, however heavy,
    cannot be taken for the step.

    ``agree`` is the share of the frame's op names whose count there is a
    multiple of the steps; a frame on which they do not agree is no frame
    (None), so that a per-step metric is left out rather than read wrong.
    """
    counts: Dict[str, int] = {}
    seconds: Dict[str, float] = {}
    for name, _s, d in ops:
        counts[name] = counts.get(name, 0) + 1
        seconds[name] = seconds.get(name, 0.0) + d
    names_with: Dict[int, int] = {}
    for c in counts.values():
        names_with[c] = names_with.get(c, 0) + 1
    names_with = {c: k for c, k in names_with.items() if c >= 2}
    if not names_with:
        return None  # under two steps in the slice
    ran = max(names_with, key=lambda c: (names_with[c], -c))
    anchor = max((n for n, c in counts.items() if c == ran),
                 key=lambda n: seconds[n])
    starts = sorted(s for n, s, _d in ops if n == anchor)
    t0, t1, steps = starts[0], starts[-1], ran - 1
    inside = [(n, s, min(d, t1 - s)) for n, s, d in ops if t0 <= s < t1]
    per_name = sum_by_name(inside)
    agree = sum(v["count"] % steps == 0 for v in per_name.values()) / len(per_name)
    if agree < min_agree:
        return None
    return {
        "steps": steps, "seconds": t1 - t0, "agree": agree,
        "anchor": label(anchor),
        "busy_s": union_seconds((s, s + d) for _n, s, d in inside),
        "ops": per_name,
    }


def merge_frames(frames: Sequence[Optional[Dict[str, Any]]]) -> Optional[Dict[str, Any]]:
    """The devices' frames as per-step numbers, averaged over the devices
    (each device's sums over its own number of steps)."""
    found = [f for f in frames if f]
    if not found or len(found) != len(frames):
        return None
    n = len(found)
    ops: Dict[str, Dict[str, float]] = {}
    for f in found:
        for name, v in f["ops"].items():
            e = ops.setdefault(name, {"count": 0.0, "seconds": 0.0})
            e["count"] += v["count"] / f["steps"] / n
            e["seconds"] += v["seconds"] / f["steps"] / n
    return {
        "planes": n,
        "steps": sum(f["steps"] for f in found) / n,
        "agree": min(f["agree"] for f in found),
        "anchor": found[0]["anchor"],
        "period_s": sum(f["seconds"] / f["steps"] for f in found) / n,
        "busy_per_step_s": sum(f["busy_s"] / f["steps"] for f in found) / n,
        "ops_per_step": ops,
    }


def matching(trace: Dict[str, Any], table: str, pattern: str) -> Tuple[float, float]:
    """(seconds, count) summed over the entries of ``trace[table]`` ("ops"
    or "programs" of a reduced trace, "ops_per_step" of its frame) whose
    name matches ``pattern``."""
    pat = re.compile(pattern)
    hit = [v for k, v in trace.get(table, {}).items() if pat.search(k)]
    return sum(v["seconds"] for v in hit), sum(v["count"] for v in hit)


def reduce_file(path: str, **kw) -> Dict[str, Any]:
    return reduce_planes(read_planes(path), **kw)


def describe(path: str, top: int = 40) -> str:
    """Planes, lines and the heaviest event names of a trace, for a human
    to look at before writing a reader against it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = [(ev.name, ev.duration_ns * 1e-9) for ev in line.events]
            out.append(f"  line {line.name!r}: {len(events)} events")
            agg = sum_by_name((n, 0.0, d) for n, d in events)
            heavy = sorted(agg.items(), key=lambda kv: -kv[1]["seconds"])[:top]
            for name, v in heavy:
                out.append(f"    {v['seconds']:.6f}s x{v['count']} {name[:160]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(sys.argv[1]))
