"""The readers that came with the region scopes and the span tree: on
made-up records, and on a small trace recorded on the chip with its scope
map beside it (``tools/record_probe.py`` -> ``tests/data/scoped.*``)."""

import json
import os
import sys
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import run  # noqa: E402
import trace_reduce as tr  # noqa: E402
from observe import SpanLog  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
TRACE = os.path.join(DATA, "scoped.xplane.pb")
MAP = os.path.join(DATA, "scoped.scope_map.json")


def reader(name):
    return run.load_module("readers", name)


def ctx(tmp_path):
    return SimpleNamespace(out=str(tmp_path), log=lambda msg: None,
                           load_module=run.load_module)


def spanlog(spans):
    log = SpanLog()
    log.spans = list(spans)
    return log


def test_join_keeps_the_identity():
    scope = reader("trace_scope_ms")
    ops = {
        "%fusion.1 = bf16[8]{0} fusion(%a)": {"count": 1, "seconds": 0.004},
        "%fusion.2 = bf16[8]{0} fusion(%a)": {"count": 1, "seconds": 0.003},
        "%copy.9 = f32[8]{0} copy(%p)": {"count": 1, "seconds": 0.002},
        "%while_body_slice = f32[8]{0} slice(%p)": {"count": 2, "seconds": 0.001},
    }
    scope_map = {
        "%fusion.1": {"region": "stem", "pass": "fwd", "op_name": "x/stem0/y"},
        "%fusion.2": {"region": "stem", "pass": "bwd", "op_name": "t/stem0/y"},
        "%copy.9": {"region": "unowned", "pass": "", "op_name": ""},
    }
    tab = scope.join(ops, scope_map)
    assert tab["total"] == pytest.approx(0.010)  # regions + unowned = the ops
    assert tab["known"] == pytest.approx(0.009)
    assert scope.seconds_of(tab, "stem") == pytest.approx(0.007)
    assert scope.seconds_of(tab, ["stem", "msmc"], "bwd") == pytest.approx(0.003)
    assert scope.seconds_of(tab, "unowned") == pytest.approx(0.003)
    assert scope.seconds_of(tab, which="bwd") == pytest.approx(0.003)
    # heaviest first; an op the map does not know says so
    assert [o[1].split(" ")[0] for o in tab["unowned_ops"]] == [
        "%copy.9", "%while_body_slice"]
    assert tab["unowned_ops"][1][2] == "not in the map"


@pytest.mark.parametrize("record", [
    {},                                        # no trace at all
    {"trace": {"frame": None}, "taps": {}},    # a slice without a frame
    {"trace": {"frame": {"ops_per_step": {}}}, "taps": {}},  # no train tap
    {"trace": {"frame": {"ops_per_step": {"%a = f32[] add()": {"count": 1, "seconds": 1.0}}}},
     # a program from before the scopes: its step keeps nothing to lower
     "taps": {"jit_step": SimpleNamespace(is_eval=False, fn=lambda *a: None)}},
])
def test_nothing_to_read_is_none_not_an_error(record, tmp_path):
    c = ctx(tmp_path)
    assert reader("trace_scope_ms").read(record, {"region": "stem"}, c) is None
    assert reader("trace_scope_share").read(dict(record), {"pass": "bwd"}, c) is None


def test_counter_at_open():
    read = reader("counter_at_open").read
    args = {"counters": ["jit_trace_s", "jit_lower_s"]}
    assert read({"counters_open": {"jit_trace_s": 100.0, "jit_lower_s": 26.0,
                                   "loader_samples": 5.0}}, args, None) == 126.0
    assert read({"counters_open": {"jit_trace_s": 3.0}}, args, None) == 3.0
    assert read({"counters_open": {"loader_samples": 5.0}}, args, None) is None
    assert read({}, args, None) is None


def test_span_before_window_and_the_dump(tmp_path):
    read = reader("span_before_window_s").read
    record = {
        "t_open": 100.0, "t_close": 130.0, "setup_s": 90.0,
        "spans": spanlog([("setup_loaders", 20.0, 5.0), ("setup_init", 25.0, 7.0),
                          ("setup_store", 40.0, 21.0), ("validate", 95.0, 8.0)]),
    }
    c = ctx(tmp_path)
    assert read(record, {"spans": ["setup_loaders", "setup_init"]}, c) == 12.0
    assert read(record, {"spans": ["setup_store"]}, c) == 21.0
    assert read(record, {"spans": ["validate"]}, c) == 5.0  # cut at the opening
    assert read(record, {"spans": ["setup_steps"]}, c) is None
    dumped = json.load(open(os.path.join(c.out, "spans.json")))
    assert dumped["window_s"] == 30.0 and len(dumped["spans"]) == 4
    assert dumped["spans"][0] == ["setup_loaders", -80.0, 5.0]


def test_spans_share_sums_several_spans():
    read = reader("spans_share").read
    record = {"t_open": 0.0, "t_close": 10.0, "spans": spanlog([
        ("val_postprocess", 1.0, 1.0), ("val_metrics", 2.0, 0.5),
        ("val_postprocess", 9.5, 1.0), ("val_step", 3.0, 4.0)])}
    args = {"spans": ["val_postprocess", "val_metrics"]}
    assert read(record, args, None) == pytest.approx(20.0)
    assert read(record, {"spans": ["val_nothing"]}, None) is None


def test_span_tree_self_time():
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import span_tree

    spans = [("train_epoch", 0.0, 10.0), ("step_dispatch", 0.0, 1.0),
             ("validate", 6.0, 4.0), ("val_host_wait", 6.0, 2.0),
             ("val_step", 8.0, 1.0), ("val_step", 9.0, 1.0000001)]
    rows = span_tree.tree(spans, 0.0, 10.0)
    assert rows[("train_epoch",)] == [1, 10.0, pytest.approx(5.0)]
    assert rows[("train_epoch", "validate")][2] == pytest.approx(0.0, abs=1e-6)
    assert rows[("train_epoch", "validate", "val_step")][:2] == [2, pytest.approx(2.0)]
    assert "cover 100.00%" in span_tree.table("window", rows, 10.0)


recorded = pytest.mark.skipif(
    not (os.path.isfile(TRACE) and os.path.isfile(MAP)),
    reason="no recorded probe")


@recorded
def test_recorded_trace_joins_with_its_map():
    """Four steps of the probe on a TPU v5 lite: every op event of the step
    is an instruction the map names, and regions + unowned are the frame's
    op seconds."""
    scope = reader("trace_scope_ms")
    frame = tr.reduce_file(TRACE)["frame"]
    assert frame["steps"] == 3
    scope_map = json.load(open(MAP))
    tab = scope.join(frame["ops_per_step"], scope_map)
    total = sum(v["seconds"] for v in frame["ops_per_step"].values())
    assert tab["total"] == pytest.approx(total)
    assert sum(tab["by"].values()) == pytest.approx(total)
    assert tab["known"] == pytest.approx(total)  # no event the map lacks
    regions = {r for r, _ in tab["by"]}
    assert {"stem", "head", "optimizer", "model_other"} <= regions
    assert scope.seconds_of(tab, which="bwd") > 0
    assert scope.seconds_of(tab, which="fwd") > 0
    assert scope.seconds_of(tab, "unowned") < 0.15 * total


@recorded
def test_recorded_trace_holds_the_bus_spans_on_the_device_clock():
    """``step_dispatch`` and ``val_step`` (with its label) are on /host:CPU,
    and each run of the step on the device starts inside a dispatch span or
    shortly after it: one clock, no alignment."""
    from jax.profiler import ProfileData

    host, modules = [], []
    for plane in ProfileData.from_file(TRACE).planes:
        for line in plane.lines:
            for ev in line.events:
                if plane.name == "/host:CPU" and ev.name in ("step_dispatch", "val_step"):
                    host.append((ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats)))
                if tr.DEVICE_PLANE.match(plane.name) and line.name == tr.MODULES_LINE \
                        and "train_step" in ev.name:
                    modules.append(ev.start_ns)
    dispatches = sorted(h for h in host if h[0] == "step_dispatch")
    assert len(dispatches) == 4 and len(modules) == 4
    for (_, start, dur, _), module_start in zip(dispatches, sorted(modules)):
        assert start <= module_start <= start + dur + 5e6  # within 5 ms
    val = [h for h in host if h[0] == "val_step"]
    assert len(val) == 1 and val[0][3] == {"probe": "yes"}
