"""The trace reducer: interval arithmetic on made-up planes, and the whole
reduction on a small trace recorded on the chip (tests/data/)."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_union_merges_overlaps():
    assert tr.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert tr.union_seconds([]) == 0.0
    assert tr.union_seconds([(1, 2), (1, 2)]) == pytest.approx(1.0)


def test_gaps_are_the_complement():
    g = tr.gaps([(1, 2), (3, 4)], 0, 5)
    assert g == [(0, 1), (2, 3), (4, 5)]
    assert tr.gaps([(0, 5)], 0, 5) == []


def test_container_ops_are_told_apart():
    assert tr.opcode("%while.9 = (s32[], f32[8]{0}) while((s32[]) %t)") == "while"
    assert tr.opcode("%f.1 = bf16[2,3]{1,0:T(8,128)(2,1)} fusion(%a)") == "fusion"
    assert tr.opcode("jit_step(1)") == ""


def test_per_name_sums_and_attribution():
    planes = [{"name": "/device:TPU:0", "lines": {
        "XLA Ops": [("fusion.1", 10.0, 1.0), ("fusion.1", 12.0, 1.0),
                    ("conv.2", 11.0, 0.5)],  # read_planes drops containers
        "XLA Modules": [("jit_step(1)", 10.0, 3.0)],
    }}]
    # host clock = trace clock + 100; a 'validate' span covers 11.5 - 12.
    spans = [("validate", 111.4, 0.7), ("train_epoch", 100.0, 50.0)]
    out = tr.reduce_planes(planes, spans=spans, host_t0=110.0, host_t1=113.0)
    assert out["busy_s"] == pytest.approx(2.5)
    assert out["window_s"] == pytest.approx(3.0)
    assert out["ops"]["fusion.1"] == {"count": 2, "seconds": pytest.approx(2.0)}
    assert out["programs"]["jit_step(1)"]["count"] == 1
    assert out["device_ops"][0][0] == "fusion.1"
    assert out["idle_gaps"][0][0] == "validate"
    assert out["idle_gaps"][0][1] == pytest.approx(0.5)
    assert tr.matching(out, "ops", r"^fusion") == (pytest.approx(2.0), 2)
    # fusion.1 ran twice: one whole step between its two starts.
    assert out["frame"]["steps"] == 1
    assert out["frame"]["period_s"] == pytest.approx(2.0)
    assert out["frame"]["busy_per_step_s"] == pytest.approx(1.5)


@pytest.mark.parametrize("host_window, window", [(0.9, 1.0), (1.0, 1.0), (1.5, 1.5)])
def test_busy_never_exceeds_the_window(host_window, window):
    """The trace holds a little more than the interval the host stamped
    (the driver's check read busy_s above window_s on a device that was busy
    throughout): the window covers every event, and reaches back as far as
    the host's interval where that is the longer."""
    ops = [(f"%fusion.{i} = f32[8]{{0}} fusion(%a)", 5.0 + 0.1 * i, 0.1)
           for i in range(10)]
    out = tr.reduce_planes(
        [{"name": "/device:TPU:0", "lines": {"XLA Ops": ops}}],
        host_t0=50.0, host_t1=50.0 + host_window)
    assert out["busy_s"] == pytest.approx(1.0)
    assert out["window_s"] == pytest.approx(window)
    assert 0 < out["busy_s"] <= out["window_s"] + 1e-12
    idle = sum(g[1] for g in out["idle_gaps"])
    assert idle == pytest.approx(window - 1.0, abs=1e-9)


def scanned_call(steps, period=1.0, cut=0.55, trips=4):
    """One device's ops of a scanned call cut by the slice's end: a copy
    once before the first step, then per step a heavy kernel, three light
    fusions, an inner loop and two more fusions (a ``while`` container around ``trips`` x
    two body ops that together outweigh everything else)."""
    ops = [("%copy.9 = f32[8]{0} copy(%p)", 0.0, 0.3)]
    for k in range(steps + 1):
        t = 0.5 + k * period
        last = k == steps
        ops.append(("%attention.1 = bf16[8]{0} custom-call(%q)", t, 0.17))
        for i in range(3):
            ops.append((f"%fusion.{i} = f32[8]{{0}} fusion(%c)", t + 0.17 + 0.01 * i, 0.01))
        ops.append(("%while.3 = (s32[]) while(%t)", t + 0.2, 0.5))
        for i in range(trips):
            if last and 0.2 + 0.12 * i > cut:
                break
            ops.append(("%body.1 = f32[8]{0} fusion(%a)", t + 0.2 + 0.12 * i, 0.07))
            ops.append(("%body.2 = f32[8]{0} fusion(%b)", t + 0.27 + 0.12 * i, 0.05))
        if not last:
            ops.append(("%fusion.7 = f32[8]{0} fusion(%c)", t + 0.7, 0.06))
            ops.append(("%fusion.8 = f32[8]{0} fusion(%c)", t + 0.76, 0.04))
    return ops


@pytest.mark.parametrize("steps", [2, 3, 12])
def test_step_frame_counts_whole_steps(steps):
    """The inner loop's body ops are the heaviest names and run four times
    a step: they are not taken for the step, and every per-step number is
    over whole steps, counted."""
    ops = scanned_call(steps)
    out = tr.reduce_planes([{"name": "/device:TPU:0", "lines": {"XLA Ops": ops}}])
    assert out["device_ops"][0][0].startswith("%body.1")
    frame = out["frame"]
    assert frame["steps"] == steps and frame["agree"] == 1.0
    assert frame["anchor"].startswith("%attention.1")
    assert frame["period_s"] == pytest.approx(1.0)
    assert frame["busy_per_step_s"] == pytest.approx(0.2 + 4 * 0.12 + 0.1)
    seconds, count = tr.matching(frame, "ops_per_step", r"^%body")
    assert (seconds, count) == (pytest.approx(4 * 0.12), pytest.approx(8))
    assert tr.matching(frame, "ops_per_step", r"^%copy") == (0, 0)
    # two devices, one a step ahead: per-step numbers are the mean
    two = tr.reduce_planes([
        {"name": "/device:TPU:0", "lines": {"XLA Ops": ops}},
        {"name": "/device:TPU:1", "lines": {"XLA Ops": scanned_call(steps + 1)}},
    ])
    assert two["frame"]["planes"] == 2
    assert two["frame"]["steps"] == steps + 0.5
    assert two["frame"]["busy_per_step_s"] == pytest.approx(0.78)


def test_no_frame_where_the_ops_do_not_agree():
    ops = [(f"%fusion.{i} = f32[8]{{0}} fusion(%a)", float(i), 0.1) for i in range(6)]
    assert tr.step_frame(ops) is None  # every op ran once: under two steps
    odd = [("%a = f32[] fusion(%x)", t, 0.1) for t in (0.0, 1.0, 2.0)]
    odd += [(f"%b{i} = f32[] fusion(%x)", 0.5, 0.1) for i in range(4)]
    odd += [(f"%b{i} = f32[] fusion(%x)", 1.7, 0.1) for i in range(4)]
    odd += [(f"%b{i} = f32[] fusion(%x)", 1.8, 0.1) for i in range(4)]
    assert tr.step_frame(odd) is None


@pytest.mark.skipif(not os.path.isfile(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace_reduces():
    out = tr.reduce_file(RECORDED)
    assert out["planes"] >= 1
    assert out["frame"]["steps"] == 3  # four probes: three whole periods
    assert out["frame"]["agree"] == 1.0
    assert out["device_ops"][0][0] == "%fusion fusion bf16[]"
    assert 0 < out["busy_s"] <= out["window_s"]
    seconds, count = tr.matching(out, "programs", "bench_probe")
    assert count >= 3 and seconds > 0
    assert sum(v["seconds"] for v in out["ops"].values()) >= out["busy_s"] * 0.999
