"""One cell, one run:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process loads the cell's data files, checks the device, hands the run
to the driver its traffic file names (``drivers/<kind>.py``), reduces what
the driver observed with the readers the metric files name
(``metrics/<metric>.json`` -> ``readers/<reader>.py``) and prints ONE JSON
object as the last line of stdout. Everything else goes to stderr or under
``.bench_cache/out/<cell>/``.

Nothing in this file knows a cell, a configuration, a driver or a metric
by name: they are found through ``BENCHMARK.json`` and the files beside
this one (benchmarks/README.md).
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # process start, as close as Python allows

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T_START:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(*parts: str):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` by file, so that a later PR adds a
    driver or a reader by adding a file."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"no {kind[:-1]} '{name}': {path} does not exist")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def get_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="CPU rehearsal at the traffic file's 'rehearse' sizes: "
        "prints counts, never a time, a rate or a device metric",
    )
    return ap.parse_args(argv)


def device_info(chips: int, rehearse: bool) -> dict:
    """The devices as JAX reports them; no chip, no number."""
    import jax

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if rehearse:
        return info
    if info["platform"] != "tpu":
        raise SystemExit(
            f"no accelerator: JAX reports platform '{info['platform']}'; "
            "this benchmark measures on the chip only (--rehearse for a "
            "CPU rehearsal that prints counts)"
        )
    if info["count"] < chips:
        raise SystemExit(
            f"cell needs {chips} chip(s), JAX reports {info['count']}"
        )
    peaks = load_json("peaks.json")
    if info["kind"] not in peaks["devices"]:
        raise SystemExit(
            f"device kind '{info['kind']}' is not in benchmarks/peaks.json: "
            "add its published peaks with their source before measuring on it"
        )
    info["peaks"] = peaks["devices"][info["kind"]]
    return info


def main(argv=None) -> int:
    args = get_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    # The cell's own file says what it is; BENCHMARK.json lists the cells
    # that are proven on the chip (a planned cell can be rehearsed before).
    if not os.path.isfile(os.path.join(BENCH, "workloads", f"{args.workload}.json")):
        raise SystemExit(
            f"unknown workload '{args.workload}': no "
            f"benchmarks/workloads/{args.workload}.json"
        )
    cell = load_json("workloads", f"{args.workload}.json")
    config_entry = next(
        c for c in manifest["configs"] if c["name"] == cell["config"]
    )
    with open(os.path.join(ROOT, config_entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", f"{cell['traffic']}.json")
    if args.rehearse:
        traffic = {**traffic, **traffic.get("rehearse", {})}
        config = {**config, **config.get("rehearse", {})}
        cell = {**cell, **cell.get("rehearse", {})}

    # The program is imported from the checkout this file sits in.
    if not os.path.isdir(os.path.join(ROOT, "seist_tpu")):
        raise SystemExit(f"the program (seist_tpu/) is not in {ROOT}")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH)  # observe.py, trace_reduce.py, flops.py
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    # The compile cache stays inside this checkout, at the fixed path the
    # program itself falls back to, whatever the machine's environment says:
    # the program takes JAX_COMPILATION_CACHE_DIR where it is set, and a
    # machine that sets it shares one directory between checkouts and may cap
    # it (192 MiB on the chip machine of PR 23, under which seist_l_dpk's
    # programs are never found again and every run compiles for ten minutes).
    # Set before anything imports jax, which reads both at import.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"

    cache = os.path.join(ROOT, ".bench_cache")
    out = os.path.join(cache, "out", cell["name"])
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)

    device = device_info(int(cell["chips"]), args.rehearse)
    log(f"{cell['name']} seed {args.seed} on {device['count']} x "
        f"{device['kind']} ({device['platform']})")

    ctx = SimpleNamespace(
        root=ROOT, bench=BENCH, cache=cache, out=out, cell=cell,
        config=config, traffic=traffic, seed=int(args.seed),
        seconds=float(args.seconds), trace=bool(args.trace),
        rehearse=args.rehearse, device=device, t_start=T_START, log=log,
        load_module=load_module, load_json=load_json,
    )
    driver = load_module("drivers", traffic["driver"])
    record = driver.run(ctx)

    # A metric's own ``workloads`` list says which cells report it; an
    # end-to-end metric without one is reported by all, a per-layer metric
    # without one by every cell that reports the metric it moves.
    name = cell["name"]
    e2e = [m for m in manifest["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    wanted = e2e if not args.trace else [
        m for m in manifest["per_layer"]
        if (name in m["workloads"] if "workloads" in m
            else m["moves"] in e2e_names)
    ]
    metrics = {}
    for m in wanted:
        spec = load_json("metrics", f"{m['name']}.json")
        reader = load_module("readers", spec["reader"])
        value = reader.read(record, spec.get("args", {}), ctx)
        if value is None:
            log(f"metric {m['name']}: nothing to read in this run")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    for line in record.get("compared", []):
        log("compared: " + json.dumps(line))
    result = {
        "correct": bool(record["correct"]),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": {} if args.rehearse else metrics,
        "device": {
            "platform": device["platform"], "kind": device["kind"],
            "count": device["count"],
            "memory_peak_bytes": int(record.get("memory_peak_bytes", 0)),
        },
        "workload": cell["name"], "seed": int(args.seed),
        "compared": record.get("compared", []),
    }
    if args.rehearse:
        result["rehearsal"] = True
        result["counts"] = record.get("counts", {})
        result["would_report"] = sorted(metrics)
    elif args.trace:
        trace = record.get("trace") or {}
        busy, window = trace.get("busy_s", 0.0), trace.get("window_s", 0.0)
        if not 0.0 < busy <= window:
            raise SystemExit(
                f"traced run without a usable device trace: busy {busy!r} s "
                f"in a window of {window!r} s (no trace file, no device "
                "plane, or no operation on the device inside the slice)"
            )
        result["device"]["busy_s"] = busy
        result["device"]["window_s"] = window
        result["breakdown"] = {
            "device_ops": trace.get("device_ops", [])[:10],
            "idle_gaps": trace.get("idle_gaps", [])[:10],
        }
    with open(os.path.join(out, "result.json"), "w") as f:
        json.dump(result, f)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
