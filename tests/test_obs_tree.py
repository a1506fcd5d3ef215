"""The span tree (obs/bus.py), the spans in the profiler's trace, compile
accounting (obs/jit_events.py), and the spans the train worker emits."""

import gc
import glob
import os
import threading
import weakref

import jax
import jax.numpy as jnp
import pytest

from seist_tpu import obs
from seist_tpu.obs import jit_events
from seist_tpu.obs.bus import BUS, MetricsBus
from seist_tpu.utils import profiling
from seist_tpu.utils.logger import logger
from tests.test_worker_e2e import make_args


@pytest.fixture(autouse=True)
def clean_span_stack():
    """Spans another test file began on this thread and never ended are
    not these tests' parents."""
    from seist_tpu.obs.bus import _open_spans

    del _open_spans()[:]
    yield


class Sink:
    def __init__(self):
        self.spans = []

    def __call__(self, span):
        self.spans.append(span)

    def named(self, name):
        return [s for s in self.spans if s.name == name]

    def seconds(self, name):
        return sum(s.duration_s for s in self.named(name))


def test_span_keeps_its_start_and_its_parent():
    bus, sink = MetricsBus(), Sink()
    bus.add_span_sink(sink)
    with bus.span("outer") as outer:
        with bus.span("inner", k="v") as inner:
            pass
        sibling = bus.begin("sibling")
        sibling.end()
    assert [s.name for s in sink.spans] == ["inner", "sibling", "outer"]
    assert outer.parent is None
    assert inner.parent == "outer" and sibling.parent == "outer"
    assert outer.start <= inner.start <= sibling.start
    assert inner.start + inner.duration_s <= outer.start + outer.duration_s + 1e-6
    # self time: a span's duration less its children's
    assert outer.duration_s - inner.duration_s - sibling.duration_s >= 0
    with bus.span("next") as after:
        pass
    assert after.parent is None  # the stack unwound


def test_each_thread_has_its_own_stack():
    bus = MetricsBus()
    seen = {}

    def other():
        with bus.span("theirs") as s:
            seen["parent"] = s.parent

    with bus.span("mine"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert seen["parent"] is None


def test_span_frame_drops_what_was_never_ended():
    bus = MetricsBus()
    with obs.span_frame():
        bus.begin("train_epoch")  # a preempt exit: never ended
    with bus.span("setup_loaders") as s:
        pass
    assert s.parent is None


def test_an_ended_parent_takes_its_open_children_along():
    bus = MetricsBus()
    outer = bus.begin("outer")
    bus.begin("leaked")
    outer.end()
    with bus.span("after") as s:
        pass
    assert s.parent is None


def test_flight_recorder_shows_the_tree():
    bus = MetricsBus()
    rec = obs.FlightRecorder(capacity=8)
    bus.add_span_sink(rec.on_span)
    with bus.span("validate"):
        with bus.span("val_step"):
            pass
    spans = rec.payload("test")["spans"]
    assert [s["name"] for s in spans] == ["val_step", "validate"]
    assert spans[0]["parent"] == "validate" and "parent" not in spans[1]
    assert spans[0]["t_mono"] >= spans[1]["t_mono"]


def _host_events(path):
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, dict(ev.stats), ev.start_ns, ev.duration_ns))
    return out


def test_a_capture_shows_bus_spans_on_the_host_plane(tmp_path):
    """The program's own capture (--profile-steps, SIGUSR2, POST /profile:
    the benchmark's options) holds every bus span as a TraceMe on
    /host:CPU, name and labels, on the profiler's clock."""
    logdir = str(tmp_path / "trace")
    profiling.trace_start(logdir)
    try:
        with BUS.span("val_step", fn="probe", k=3):
            jnp.ones(8).block_until_ready()
        with BUS.span("step_dispatch"):
            pass
    finally:
        profiling.trace_stop()
    found = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    assert found
    events = _host_events(found[0])
    val = [e for e in events if e[0] == "val_step"]
    assert len(val) == 1
    assert val[0][1] == {"fn": "probe", "k": 3}
    dispatch = [e for e in events if e[0] == "step_dispatch"]
    assert len(dispatch) == 1 and dispatch[0][2] >= val[0][2] + val[0][3]
    # device ops and TraceMe only: no Python tracer frames
    assert not any(name.startswith("$") for name, *_ in events)


def test_outside_a_capture_a_span_is_only_a_span():
    with BUS.span("quiet") as s:
        pass
    assert s.duration_s is not None and s.duration_s < 0.05


def _counters():
    return {k: v for k, v in BUS.snapshot()["counters"].items()
            if k.startswith(("jit_", "compile_cache_"))}


def test_compile_counters_move_on_a_compile_and_on_a_cache_hit(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    jit_events.install()
    jit_events.install()  # idempotent
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs,
             jax.config.jax_persistent_cache_min_entry_size_bytes)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    try:
        x = jnp.ones(7)  # compiled before the counting starts
        before = _counters()

        def fresh():
            return jax.jit(lambda x: jnp.tanh(x) * 3.25 + 0.125)

        fresh()(x).block_until_ready()
        compiled = _counters()
        assert compiled["jit_compiles"] == before.get("jit_compiles", 0) + 1
        assert compiled["compile_cache_misses"] == before.get(
            "compile_cache_misses", 0) + 1
        for name in ("jit_trace_s", "jit_lower_s", "jit_backend_compile_s"):
            assert compiled[name] > before.get(name, 0)
        assert compiled.get("compile_cache_hits", 0) == before.get(
            "compile_cache_hits", 0)
        jax.clear_caches()  # the in-memory caches; the directory stays
        fresh()(x).block_until_ready()
        hit = _counters()
        assert hit["compile_cache_hits"] == before.get("compile_cache_hits", 0) + 1
        assert hit["compile_cache_misses"] == compiled["compile_cache_misses"]
        assert hit["compile_cache_retrieval_s"] > before.get(
            "compile_cache_retrieval_s", 0)
        assert hit["jit_compiles"] == compiled["jit_compiles"] + 1
        # an operator sees a recompile on /metrics
        text = obs.render_prometheus(BUS)
        assert "seist_jit_compiles_total" in text
        assert "seist_compile_cache_hits_total" in text
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", saved[2])
        cc.reset_cache()


class Tap:
    """What benchmarks/drivers/train.py ``StepTap`` keeps of a call."""

    def __init__(self, fn):
        self.fn, self.gsteps = fn, []

    def __call__(self, *args, **kwargs):
        self.gsteps.append(BUS.gauge("global_step").value)
        return self.fn(*args, **kwargs)


@pytest.fixture(scope="module")
def worker_spans(tmp_path_factory):
    """One small cached-path training run (two epochs) under a span sink,
    with the worker module's ``jit_*`` factories wrapped the way the
    benchmark's harness wraps them (``install_taps``): ``sink.taps``,
    and the run's log text as ``sink.log``."""
    from seist_tpu.train import worker

    logdir = str(tmp_path_factory.mktemp("tree_logs"))
    logger.set_logdir(logdir)
    sink = Sink()
    sink.taps, sink.runs = {}, []
    BUS.add_span_sink(sink)
    BUS.gauge("global_step").set(-1)
    with pytest.MonkeyPatch.context() as patch:
        for name in dir(worker):
            factory = getattr(worker, name)
            if not name.startswith("jit_") or not callable(factory):
                continue

            def wrapped(*a, _factory=factory, _name=name, **k):
                sink.taps[_name] = Tap(_factory(*a, **k))
                return sink.taps[_name]

            patch.setattr(worker, name, wrapped)

        class TrackedRun(worker._Run):
            def __init__(self, **fields):
                super().__init__(**fields)
                sink.runs.append(weakref.ref(self))

        patch.setattr(worker, "_Run", TrackedRun)
        gc.disable()  # a record kept alive by a cycle must stay visible
        try:
            worker.train_worker(make_args(
                mode="train", epochs=2, in_samples=512, device_aug="cached",
                dataset_kwargs={"num_events": 80, "trace_samples": 1500},
            ))
            sink.runs_alive_at_return = [r() is not None for r in sink.runs]
        finally:
            gc.enable()
            BUS.remove_span_sink(sink)
    sink.log = "".join(
        open(f).read() for f in glob.glob(os.path.join(logdir, "*.log")))
    return sink


def test_the_harness_finds_its_taps_and_its_log_line(worker_spans):
    """The benchmark holds the trainer by three unwritten contracts
    (ROADMAP D16): every ``jit_*`` factory is called as an attribute of the
    module ``seist_tpu.train.worker``, the ``global_step`` gauge is set
    before a call is dispatched, and ``checks/train_invariants.py`` reads
    the resolved input path out of this log line."""
    import re

    taps = worker_spans.taps
    assert set(taps) == {"jit_cached_call", "jit_eval_step"}
    train, evalt = taps["jit_cached_call"], taps["jit_eval_step"]
    # 64 train events at batch 8: one call of 8 steps an epoch
    assert train.gsteps == [0, 8]
    assert len(evalt.gsteps) >= 2 and evalt.gsteps[0] == 0
    assert len(worker_spans.named("step_dispatch")) == len(train.gsteps)
    found = re.search(
        r"device-aug cached: (\d+) epoch samples resident", worker_spans.log)
    assert found and int(found.group(1)) == 64
    # The check calls the trainer's programs once more after the run: the run
    # record, and the train state it holds on the device, must be gone when
    # train_worker returns, without waiting for the cycle collector.
    assert worker_spans.runs_alive_at_return == [False]


@pytest.mark.parametrize("which", ["streamed", "replayed"])
def test_validation_spans_partition_a_pass(worker_spans, which):
    """The first pass streams from the host loader, the second replays the
    resident batches (data/pipeline.py): the same four spans either way."""
    parts = ("val_host_wait", "val_step", "val_postprocess", "val_metrics")
    passes = sorted(worker_spans.named("validate"), key=lambda s: s.start)
    assert len(passes) == 2
    whole = passes[["streamed", "replayed"].index(which)]
    assert whole.parent == "train_epoch"

    def inside(name, of=whole):
        return [s for s in worker_spans.named(name)
                if of.start <= s.start <= of.start + of.duration_s]

    covered = sum(s.duration_s for p in parts for s in inside(p))
    assert covered <= whole.duration_s
    # the rest of a pass is its set-up and two log lines: milliseconds,
    # which a replayed pass of one batch on the CPU is made of too
    assert 0.8 * whole.duration_s <= covered or whole.duration_s - covered < 0.05
    for p in parts:
        assert inside(p), p
        assert all(s.parent == "validate" for s in inside(p))
    n = len(inside("val_step"))
    assert len(inside("val_host_wait")) == n
    assert len(inside("val_postprocess")) == n
    assert len(inside("val_metrics")) == n
    # a replayed batch waits on nothing
    streamed, replayed = (
        sum(s.duration_s for s in inside("val_host_wait", of)) for of in passes
    )
    assert replayed < streamed


def test_setup_spans_run_once_in_order(worker_spans):
    names = ["setup_loaders", "setup_init", "setup_store", "setup_steps",
             "setup_writers"]
    found = [worker_spans.named(n) for n in names]
    assert all(len(f) == 1 for f in found), [len(f) for f in found]
    starts = [f[0].start for f in found]
    assert starts == sorted(starts)
    for (a,), (b,) in zip(found, found[1:]):
        assert a.start + a.duration_s <= b.start + 1e-6  # they do not nest
    first_epoch = min(worker_spans.named("train_epoch"), key=lambda s: s.start)
    last = found[-1][0]
    assert last.start + last.duration_s <= first_epoch.start
    # the first call of each step is inside the warm-up epoch
    calls = worker_spans.named("jit_first_call")
    assert {s.labels["fn"] for s in calls} == {"cached_call", "eval_step"}
    assert all(first_epoch.start <= s.start
               <= first_epoch.start + first_epoch.duration_s for s in calls)


def test_epoch_turnover_spans(worker_spans):
    assert len(worker_spans.named("train_epoch")) == 2
    assert len(worker_spans.named("epoch_drain")) == 2
    assert all(s.parent == "train_epoch" for s in worker_spans.named("epoch_drain"))
    # the best-checkpoint save at an epoch's end is a checkpoint_save span
    assert worker_spans.named("checkpoint_save")
    assert all(s.parent == "train_epoch"
               for s in worker_spans.named("checkpoint_save"))
    assert all(s.parent == "train_epoch"
               for s in worker_spans.named("step_dispatch"))


def test_what_nothing_read_is_gone(worker_spans):
    assert not worker_spans.named("log_interval")
    assert not any(k.startswith("log_interval")
                   for k in BUS.snapshot()["histograms"])
    assert not hasattr(profiling, "ThroughputMeter")
    assert not hasattr(profiling, "device_memory_stats")
