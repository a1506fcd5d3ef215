"""Driver ``train``: the program's trainer, as ``python main.py <argv>`` runs
it, inside this process.

The trainer owns the main thread (its SIGTERM handler is its normal way to
stop); a conductor thread watches it from outside: the bus's spans and
counters, JAX's compile log, and a tap on the compiled step the trainer
builds (call count, the first call's state).

Window: opens when the ``warm_epochs``-th ``train_epoch`` span ends (the
first epoch compiles, uploads the store and validates once), i.e. at the
first step of the next epoch, with the device drained. It closes at the end
of the first epoch that ends after ``--seconds``: whole train + validate +
checkpoint cycles, so the rate does not depend on where in a cycle the clock
runs out (the pools are sized so that an epoch lasts seconds, and the
trainer runs a whole epoch ahead of the device on the cached path, so only
an epoch's end is a point at which the work done is known).

``train_wf_per_s`` = optimizer steps completed in the window x batch /
window seconds, steps counted on call boundaries from the bus's
``global_step`` gauge — never from a logged wave/s. With ``--trace 1`` the
profiler records ``trace_seconds`` (configuration file) of steady steps in
the warm-up epoch, device tracing only (``trace_slice``); spans and counters
are read over the whole window.
"""

from __future__ import annotations

import os
import signal
import threading
import time
from typing import Any, Dict, List, Optional

import observe

PREEMPT_EXIT_CODE = 75
# From the return of the step's first call (the device then runs it) to the
# start of the traced slice: past the call's once-only ops, inside its steps.
TRACE_DELAY_S = 0.3


class StepTap:
    """Wraps the compiled callable one of the program's ``jit_*`` wrappers
    returned. Counts calls and keeps the ``global_step`` gauge at each; for
    the first call keeps copies of the parameters before and after and of
    the optimizer's state after (the state itself is donated), or, of an
    eval step, the call's types."""

    def __init__(self, factory: str, fn: Any) -> None:
        self.factory = factory
        self.fn = fn
        self.lock = threading.Lock()
        self.calls = 0
        self.gsteps: List[float] = []
        self.first: Optional[Dict[str, Any]] = None
        self.first_args: Any = None
        self.is_eval = "eval" in factory

    def __call__(self, *args, **kwargs):
        import jax
        import jax.numpy as jnp

        from seist_tpu.obs.bus import BUS

        first = self.calls == 0
        before = None
        if first and not self.is_eval:
            before = jax.tree.map(jnp.copy, args[0].params)
        out = self.fn(*args, **kwargs)
        with self.lock:
            self.calls += 1
            self.gsteps.append(BUS.gauge("global_step").value)
        if first:
            if self.is_eval:
                # Shapes, dtypes and shardings of one call: what the check
                # needs to drive this same compiled program again.
                self.first_args = jax.tree.map(_describe, args)
            else:
                self.first = {
                    "before": before,
                    "after": jax.tree.map(jnp.copy, out[0].params),
                    "opt_state": jax.tree.map(jnp.copy, out[0].opt_state),
                    "loss": out[1],
                }
        return out


def _describe(a: Any) -> Any:
    import jax

    if isinstance(a, jax.Array):
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding, weak_type=a.weak_type
        )
    return a


def install_taps(worker_module: Any) -> Dict[str, StepTap]:
    """Wrap every ``jit_*`` factory the trainer module imported, so that
    whichever step it builds for this argv is tapped — no list of paths."""
    taps: Dict[str, StepTap] = {}
    for name in dir(worker_module):
        factory = getattr(worker_module, name)
        if not name.startswith("jit_") or not callable(factory):
            continue

        def wrapped(*a, _factory=factory, _name=name, **k):
            tap = StepTap(_name, _factory(*a, **k))
            taps[_name] = tap
            return tap

        setattr(worker_module, name, wrapped)
    return taps


def ensure_pool(ctx: Any) -> str:
    """The waveform pool as packed shards under the benchmark's cache
    directory; only the first run in a checkout pays the synthesis."""
    pool = ctx.traffic["pool"]
    name = "pool_{dataset}_{events}x{trace_samples}".format(**pool)
    path = os.path.join(ctx.cache, "pool", name)
    done = os.path.join(path, ".complete")
    if os.path.exists(done):
        return path
    from seist_tpu.data.packed import PackSource, pack_sources

    import seist_tpu

    seist_tpu.load_all()
    t0 = time.monotonic()
    pack_sources(
        [PackSource(name=pool["dataset"], dataset_kwargs={
            "num_events": int(pool["events"]),
            "trace_samples": int(pool["trace_samples"]),
            "cache": False,
        })],
        path,
        num_workers=min(int(pool.get("pack_workers", 8)), os.cpu_count() or 1),
        shard_mb=float(pool.get("shard_mb", 512)),
    )
    with open(done, "w") as f:
        f.write("ok\n")
    ctx.log(f"pool packed in {time.monotonic() - t0:.1f}s: {path}")
    return path


def program_seed(seed: int, traffic: Dict[str, Any]) -> int:
    """The seed the trainer gets. Its seeds feed int32 PRNG keys and pandas'
    random_state, so ``--seed`` is folded into that range. A traffic file
    may pin it (``program_seed``): on the cached path the seed is a
    compile-time constant of the step (``AugConfig.seed``), a new seed is a
    new program and minutes of compile in every run, so those cells train
    from one seed and ``--seed`` draws the check's weights and waveforms."""
    if "program_seed" in traffic:
        return int(traffic["program_seed"])
    return int(seed) % (2**31 - 1)


def run(ctx: Any) -> Dict[str, Any]:
    cellfile, traffic, config = ctx.cell, ctx.traffic, ctx.config
    pool = ensure_pool(ctx)
    fields = {
        **{k: v for k, v in config.items() if isinstance(v, (str, int, float))},
        "pool": pool, "seed": program_seed(ctx.seed, traffic),
        "logs": os.path.join(ctx.out, "logs"),
    }
    argv = [str(a).format(**fields) for a in cellfile["argv"]]
    argv += [str(a).format(**fields) for a in traffic.get("argv", [])]
    ctx.log("argv: " + " ".join(argv))

    compiles = observe.CompileLog().install()
    spans = observe.SpanLog()
    from seist_tpu.obs.bus import BUS

    BUS.add_span_sink(spans)

    from seist_tpu import cli
    from seist_tpu.train import worker

    taps = install_taps(worker)

    epoch_ends: List[float] = []
    epoch_calls: List[int] = []
    main_done = threading.Event()
    wake = threading.Condition()

    def train_tap() -> Optional[StepTap]:
        return next((t for t in taps.values() if not t.is_eval), None)

    def on_epoch_end(t: float) -> None:
        tap = train_tap()
        with wake:
            epoch_ends.append(t)
            epoch_calls.append(tap.calls if tap else 0)
            wake.notify_all()

    spans.on("train_epoch", on_epoch_end)

    warm_epochs = int(traffic.get("warm_epochs", 1))
    # How long a slice the profiler records belongs to the configuration:
    # its step's op events per second set what the host's memory can hold.
    trace_seconds = float(config.get("trace_seconds", 0.25))
    rec: Dict[str, Any] = {"error": None}

    def wait_epochs(n: int) -> bool:
        with wake:
            while len(epoch_ends) < n and not main_done.is_set():
                wake.wait(timeout=0.5)
            return len(epoch_ends) >= n

    def trace_slice() -> None:
        """``trace_seconds`` of steady steps out of the warm-up epoch, from
        ``TRACE_DELAY_S`` after the step's first call returned (it compiles,
        then the device runs it while the trainer dispatches the next).
        Not out of the window: the profiler takes a minute to hand over a
        quarter of a second of ``seist_l_dpk`` (63.5 s, chip call A of PR
        23's review), which after the window opened would carry a warm run
        past the 360 s a run may take; from here it is done by the time the
        window closes. Inside a call the slice holds whole steps and
        neither the dispatch gap nor what a call does once."""
        while not main_done.is_set():
            tap = train_tap()
            if tap is not None and tap.calls >= 1:
                break
            time.sleep(0.05)
        main_done.wait(timeout=TRACE_DELAY_S)
        if main_done.is_set():
            return
        trace_dir = os.path.join(ctx.out, "trace")
        observe.start_trace(trace_dir)
        rec["trace_t0"] = time.monotonic()
        main_done.wait(timeout=trace_seconds)
        rec["trace_t1"] = time.monotonic()
        rec["trace_file"] = observe.stop_trace(trace_dir)
        ctx.log(f"trace of {trace_seconds:g}s written in "
                f"{time.monotonic() - rec['trace_t1']:.1f}s, host peak "
                f"{observe.host_peak_gb():.1f} GB")

    def conduct() -> None:
        try:
            if ctx.trace:
                trace_slice()
            if not wait_epochs(warm_epochs):
                rec["error"] = "the trainer ended before its warm-up epoch did"
                return
            # An epoch ends on a drained device: its losses were fetched and
            # its validation pass read back.
            t_open = epoch_ends[warm_epochs - 1]
            n_open = epoch_calls[warm_epochs - 1]
            rec.update(
                t_open=t_open, setup_s=t_open - ctx.t_start,
                counters_open=observe.bus_counters(),
            )
            ctx.log(f"window open after {rec['setup_s']:.1f}s of set-up")
            n = warm_epochs
            while True:
                n += 1
                if not wait_epochs(n):
                    rec["error"] = "the trainer ended inside the window"
                    return
                if epoch_ends[n - 1] - t_open >= ctx.seconds:
                    break
            rec.update(
                t_close=epoch_ends[n - 1], n_open=n_open,
                n_close=epoch_calls[n - 1], epochs=n - warm_epochs,
                counters_close=observe.bus_counters(),
                memory_peak_bytes=observe.memory_peak_bytes(),
            )
        except BaseException as e:  # noqa: BLE001 - reported, then re-raised
            rec["error"] = f"conductor: {e!r}"
            raise
        finally:
            # The trainer's own way to stop: checkpoint at the next step
            # boundary, exit 75.
            if not main_done.is_set():
                os.kill(os.getpid(), signal.SIGTERM)

    conductor = threading.Thread(target=conduct, name="bench-conductor")
    conductor.start()
    rc: Any = 0
    try:
        cli.main(argv)
    except SystemExit as e:
        rc = e.code
    finally:
        main_done.set()
        with wake:
            wake.notify_all()
        conductor.join()
        BUS.remove_span_sink(spans)
    if rec["error"]:
        raise SystemExit(f"run failed: {rec['error']}")
    if rc not in (0, None, PREEMPT_EXIT_CODE):
        raise SystemExit(f"the trainer exited with {rc}")

    tap = train_tap()
    deltas = [b - a for a, b in zip(tap.gsteps, tap.gsteps[1:]) if b > a]
    steps_per_call = int(min(deltas)) if deltas else 1
    batch = int(config["batch"])
    calls = rec["n_close"] - rec["n_open"]
    window_s = rec["t_close"] - rec["t_open"]
    record: Dict[str, Any] = {
        **rec,
        "window_s": window_s,
        "steps_per_call": steps_per_call,
        "waveforms": calls * steps_per_call * batch,
        "spans": spans,
        "compiles": compiles,
        "taps": taps,
        "attempted": calls * steps_per_call,
        "failed": 0,
        "counts": {
            "calls_in_window": calls, "steps_per_call": steps_per_call,
            "epochs_in_window": rec["epochs"],
            "compiles_in_window": len(
                compiles.between(rec["t_open"], rec["t_close"])),
            "cache_hits_in_setup": len(
                [h for h in compiles.hits if h[0] < rec["t_open"]]
            ),
        },
    }
    if ctx.trace and rec.get("trace_file"):
        import trace_reduce

        record["trace"] = trace_reduce.reduce_file(
            rec["trace_file"], spans=spans.spans,
            host_t0=rec["trace_t0"], host_t1=rec["trace_t1"],
        )
        frame = record["trace"].get("frame")
        ctx.log("step frame: " + (
            "none (under two steps, or the ops do not agree on their count)" if not frame
            else f"{frame['steps']:g} whole steps of {1e3 * frame['period_s']:.3f} ms, "
                 f"{1e3 * frame['busy_per_step_s']:.3f} ms busy, from "
                 f"{frame['anchor']}, agreement {frame['agree']:.4f}"))

    compared: List[Dict[str, Any]] = []
    ok = True
    log_text = _read_logs(fields["logs"])
    for check in traffic.get("checks", []):
        mod = ctx.load_module("checks", check["name"])
        # Limits that belong to a configuration (the eval step is one
        # program on every data path) sit in its file, those that belong to
        # a configuration on one data path in the cell's.
        args = {**check.get("args", {}),
                **(config.get("limits") or {}).get(check["name"], {}),
                **(cellfile.get("limits") or {}).get(check["name"], {})}
        for line in mod.check(record, args, ctx, log_text):
            compared.append(line)
            ok = ok and bool(line["ok"])
    record["compared"] = compared
    record["correct"] = ok and bool(compared)
    return record


def _read_logs(log_base: str) -> str:
    text = []
    for dirpath, _, files in os.walk(log_base):
        for f in files:
            if f.endswith(".log"):
                with open(os.path.join(dirpath, f), errors="replace") as fh:
                    text.append(fh.read())
    return "\n".join(text)
