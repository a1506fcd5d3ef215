"""The token configuration's benchmark files on the CPU: the stored counts
reproduce from ``costs_lm.py``; the region costs are the shapes' own; a
rehearsed run of the new cell through the new driver is correct; faults
planted under the harness are each caught by the number meant to catch it;
the fp8 control, through the check's own comparison, is not correct."""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}
CELL = "train.nemotron3_nano_ep16.seq8k"


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def run(args, script=os.path.join(BENCH, "run.py"), pre=()):
    return subprocess.run(
        [sys.executable, script, *pre, *args], cwd=ROOT, env=ENV,
        capture_output=True, text=True, timeout=900,
    )


def last_json(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


REHEARSE = ["--workload", CELL, "--seed", str(2**31 + 29), "--seconds", "2",
            "--trace", "0", "--rehearse"]


def test_stored_counts_reproduce():
    import costs_lm

    cfg = load("configs", "nemotron3_nano_ep16.json")
    assert costs_lm.sequence_flops(cfg) == cfg["flops_per_waveform"]
    # 0.71 GFLOP a token forward: 2 x 318 M active weights + scores + scan
    per_token = cfg["flops_per_waveform"]["forward"] / cfg["in_samples"]
    assert 0.68e9 < per_token < 0.75e9
    assert cfg["flops_per_waveform"]["train"] == 3 * cfg["flops_per_waveform"]["forward"]


def test_the_count_leaves_out_what_the_model_does_not_need():
    """The reference's masked expert loop and the masked half of its
    attention scores are not the model's work: the count is the same
    whatever the reference spends on them."""
    import costs_lm

    cfg = load("configs", "nemotron3_nano_ep16.json")
    cfg = {**cfg, **cfg["rehearse"]}
    a = cfg["architecture"]
    small = costs_lm.sequence_flops(cfg)
    length = cfg["in_samples"]
    routed = costs_lm.routed_flops(a, costs_lm.expected_local_rows(a, length))
    assert routed == (4 * length * 3 * 4 / 16) * 4 * a["hidden_size"] * a["moe_intermediate_size"]
    twice = {**cfg, "architecture": {**a, "experts_held": [0, 8]}}
    more = costs_lm.sequence_flops(twice)["forward"] - small["forward"]
    assert more == routed  # twice the experts held: twice the routed rows
    assert costs_lm.masked_half_of_scores(a, length) == (
        4 * a["num_attention_heads"] * a["head_dim"] * length * (length - 1) // 2)


def test_region_costs_follow_the_shapes_and_the_counted_rows():
    import costs_lm

    cfg = load("configs", "nemotron3_nano_ep16.json")
    ops, nbytes = costs_lm.ssd_cost(cfg, {})
    tokens = 2 * 8192
    assert ops == 4 * 4 * tokens * 4 * 64 * 64 * 128
    assert nbytes == 4 * 4 * tokens * (2 * (4096 + 2048 + 4096) + 256)
    record = {"counters_open": {"moe_slots_local": 1000.0},
              "counters_close": {"moe_slots_local": 1000.0 + 64 * 24000.0},
              "attempted": 64}
    assert costs_lm.local_rows_per_step(record) == 24000.0
    ops, _ = costs_lm.moe_experts_cost(cfg, record)
    assert ops == 4 * 24000.0 * 4 * 2688 * 1856
    expected, _ = costs_lm.moe_experts_cost(cfg, {})  # no counter: expected load
    assert expected == 4 * (4 * 6144) * 4 * 2688 * 1856


def test_new_readers_read_nothing_from_a_program_without_the_counters():
    import importlib.util

    def reader(name):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, "readers", f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    bare = {"counters_open": {}, "counters_close": {"global_step": 3.0}}
    ratio = load("metrics", "moe_local_share.train.json")["args"]
    assert reader("counter_ratio").read(bare, ratio, None) is None
    gauge = load("metrics", "moe_load_max_over_mean.train.json")["args"]
    assert reader("gauge_at_close").read(bare, gauge, None) is None
    # 3 steps of 16384 tokens; the counter sums the 4 expert layers' slots
    full = {"counters_open": {"moe_slots_local": 10.0, "tokens_trained": 100.0},
            "counters_close": {"moe_slots_local": 10.0 + 3 * 4 * 6144,
                               "tokens_trained": 100.0 + 3 * 16384,
                               "moe_load_max_over_mean": 1.7}}
    assert ratio["per"] == 6 * 4  # experts per token x expert layers
    assert reader("counter_ratio").read(full, ratio, None) == pytest.approx(6.25)
    assert reader("gauge_at_close").read(full, gauge, None) == 1.7


def test_rehearsed_run_through_the_host_tap_driver():
    assert load("traffic", "train_tokens.json")["driver"] == "train_hosttap"
    out = last_json(run(REHEARSE))
    assert out["rehearsal"] is True and out["metrics"] == {}
    assert out["correct"] is True, out["compared"]
    assert out["counts"]["compiles_in_window"] == 0
    assert out["counts"]["calls_in_window"] > 0
    names = {c["name"] for c in out["compared"]}
    assert {"moved_share", "eval_recompiled", "train_recompiled", "eval_rms_gap",
            "train_loss_gap", "grad_cos_gap_max_leaf", "grad_norm_gap_max_leaf",
            "update_norm_ratio_min_leaf", "update_norm_ratio_max_leaf",
            "moe_overflow_rows"} <= names
    assert all("limit" in c for c in out["compared"])
    assert set(out["would_report"]) == {"setup_s", "train_wf_per_s"}


@pytest.mark.parametrize("kind,failing", [
    ("scan_carry_dropped", "grad_cos_gap_max_leaf"),
    ("expert_mask_off_by_one", "grad_cos_gap_max_leaf"),
    ("half_positions_loss", "train_loss_gap"),
    ("lr_half_again", "update_norm_ratio_max_leaf"),
])
def test_broken_token_path_is_not_correct(kind, failing):
    """Each fault is caught by the number that is there to catch it. At
    fresh weights what a state carried over a chunk's edge, or one expert
    of sixteen, adds to a logit is under bf16's rounding (``eval_rms_gap``
    reads as in a sound run); the gradient of the leaves concerned
    (``A_log``, ``dt_bias``, the experts) is not: that is why the check
    compares gradients leaf by leaf. A learning rate half again as large
    is inside ``train_invariants``' room for a step; the update against the
    reference's own is not."""
    out = last_json(run(REHEARSE, script=os.path.join(BENCH, "tests", "broken_lm.py"),
                        pre=(kind,)))
    assert out["correct"] is False
    bad = {c["name"] for c in out["compared"] if not c["ok"]}
    assert failing in bad, out["compared"]


def test_fp8_control_is_not_correct():
    """The control's numbers go through the check's own comparison and come
    out as a run that is not correct."""
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "control_lm.py"),
         "--config", "nemotron3_nano_ep16", "--seeds", "7", "--rehearse"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=600)
    out = last_json(proc)
    assert out["correct"] is False
    names = {c["name"] for c in out["compared"]}
    assert {"logits_range", "train_loss_gap", "grad_cos_gap_all",
            "grad_norm_gap_max_leaf"} <= names
    bad = {c["name"] for c in out["compared"] if not c["ok"]}
    assert {"eval_rms_gap", "eval_p999_gap", "grad_cos_gap_max_leaf"} <= bad
