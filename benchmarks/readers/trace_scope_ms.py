"""Device time (ms) per optimizer step of a region of the train step
(``args.region``: one name or a list; ``args.pass`` optional: ``fwd``,
``bwd`` or ``""``): the frame's per-step op seconds
(``record["trace"]["frame"]["ops_per_step"]``) joined, by the instruction
name a trace event carries before its `` = ``, with the scope map of the
train tap's step (``seist_tpu.obs.scopes.scope_map``: region and pass of
every instruction of the optimized HLO, from its ``op_name``).

Nothing to read (None) without a frame, or where the program has no scope
map to give (a program from before the region scopes). The identity the
table keeps: regions + ``unowned`` = the frame's summed op seconds; an op
the map does not know is ``unowned``. The whole region x pass table and the
ten heaviest unowned ops go to the log, for PERF.md."""

import json
import os
import time

UNOWNED = "unowned"


def table(record, ctx):
    """``{"by": {(region, pass): seconds per step}, "total": seconds}`` of
    this run, built once (the map is read after the window, on demand) and
    kept on the record; None where there is nothing to join."""
    if "scope_table" not in record:
        record["scope_table"] = _build(record, ctx)
    return record["scope_table"]


def _build(record, ctx):
    frame = (record.get("trace") or {}).get("frame")
    tap = next((t for t in (record.get("taps") or {}).values()
                if not t.is_eval), None)
    if not frame or tap is None:
        return None
    try:
        from seist_tpu.obs import scopes
    except ImportError:  # a program without region scopes
        return None
    t0 = time.monotonic()
    scope_map = scopes.scope_map(tap.fn)
    if not scope_map:
        return None
    seconds = time.monotonic() - t0
    with open(os.path.join(ctx.out, "scope_map.json"), "w") as f:
        json.dump(scope_map, f)
    out = join(frame["ops_per_step"], scope_map)
    total = out["total"] or 1.0
    ctx.log(f"scope map: {len(scope_map)} instructions, "
            f"{os.path.getsize(f.name)} bytes as JSON, read in {seconds:.2f}s; "
            f"{100 * out['known'] / total:.2f}% of the frame's op seconds "
            "are ops the map names")
    for (region, which), s in sorted(out["by"].items(), key=lambda kv: -kv[1]):
        ctx.log(f"region {region:<13} {which or '-':<3} {1e3 * s:9.3f} ms/step "
                f"{100 * s / total:6.2f}%")
    for s, name, op_name in out["unowned_ops"][:10]:
        ctx.log(f"unowned {1e3 * s:8.3f} ms/step {name[:100]} "
                f"[{op_name or 'no op_name'}]")
    return out


def join(ops_per_step, scope_map):
    by, unowned_ops, known = {}, [], 0.0
    for event, v in ops_per_step.items():
        entry = scope_map.get(event.partition(" = ")[0])
        if entry is not None:
            known += v["seconds"]
        region = entry["region"] if entry else UNOWNED
        key = (region, entry["pass"] if entry else "")
        by[key] = by.get(key, 0.0) + v["seconds"]
        if region == UNOWNED:
            unowned_ops.append((
                v["seconds"], event,
                entry["op_name"] if entry else "not in the map"))
    unowned_ops.sort(key=lambda o: -o[0])
    return {"by": by, "total": sum(by.values()), "known": known,
            "unowned_ops": unowned_ops}


def seconds_of(tab, regions=None, which=None):
    if isinstance(regions, str):
        regions = [regions]
    return sum(s for (region, p), s in tab["by"].items()
               if (regions is None or region in regions)
               and (which is None or p == which))


def read(record, args, ctx):
    tab = table(record, ctx)
    if tab is None:
        return None
    return 1e3 * seconds_of(tab, args.get("region"), args.get("pass"))
