"""Whole runs of the harness on the CPU: a rehearsal of each data path
prints counts and no metric; a run without a chip fails and prints no
result; so does a run in a directory that holds only the benchmark; a
timed path broken underneath comes out not correct."""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def run(args, script=os.path.join(BENCH, "run.py"), cwd=ROOT, pre=()):
    return subprocess.run(
        [sys.executable, script, *pre, *args], cwd=cwd, env=ENV,
        capture_output=True, text=True, timeout=900,
    )


def last_json(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["train.phasenet.cached", "train.seist_l_dpk.hostfed"])
def test_rehearsal_prints_counts_and_no_metric(cell):
    out = last_json(run(["--workload", cell, "--seed", str(2**31 + 11),
                         "--seconds", "2", "--trace", "1", "--rehearse"]))
    assert out["rehearsal"] is True and out["metrics"] == {}
    assert out["correct"] is True, out["compared"]
    assert out["device"]["platform"] == "cpu"
    assert out["counts"]["compiles_in_window"] == 0
    assert out["counts"]["calls_in_window"] > 0
    assert all("limit" in c for c in out["compared"])


def test_no_chip_no_number():
    proc = run(["--workload", "train.phasenet.cached", "--seed", "1",
                "--seconds", "2", "--trace", "0"])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_fails_where_only_the_benchmark_is(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run(["--workload", "train.phasenet.cached", "--seed", "1",
                "--seconds", "2", "--trace", "0", "--rehearse"],
               script=str(tmp_path / "benchmarks" / "run.py"), cwd=tmp_path)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


@pytest.mark.parametrize("kind,failing", [
    ("state_unchanged", "moved_share"),
    ("module_grad_zeroed", "moved_share"),
    ("lr_x10", "update_ratio_max_leaf"),
    ("half_batch_loss", "first_call_loss"),
    ("answer_altered", "eval_rms_gap"),
])
def test_broken_timed_path_is_not_correct(kind, failing):
    """Each fault is caught by the number that is there to catch it. Known
    to pass (PERF.md section 2): rows dropped before the forward pass, with
    the loss a mean over the rows kept, which is a sound smaller batch."""
    out = last_json(run(
        ["--workload", "train.phasenet.cached", "--seed", "5", "--seconds",
         "2", "--trace", "0", "--rehearse"],
        script=os.path.join(BENCH, "tests", "broken_run.py"), pre=(kind,),
    ))
    assert out["correct"] is False
    bad = {c["name"] for c in out["compared"] if not c["ok"]}
    assert failing in bad, out["compared"]
