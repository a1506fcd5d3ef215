"""Pure-unit tests for bench.py's measurement helpers and its failure
contract: a run that cannot measure exits non-zero and prints no result;
a device kind without a published peak is an error, never a default. No
backend is touched except where a test patches ``jax.devices``.
"""

import importlib
import json

import pytest


@pytest.fixture()
def bench():
    import bench as bench_mod

    return importlib.reload(bench_mod)


def _emitted(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# ------------------------------------------------------------- peak table
@pytest.mark.parametrize(
    "kind,peak",
    [
        ("TPU v5 lite", 197e12),
        ("TPU v5e", 197e12),
        ("TPU v4", 275e12),
        ("TPU v5p", 459e12),
        ("TPU v6 lite", 918e12),
    ],
)
def test_peak_flops_known_kinds(bench, kind, peak):
    assert bench._peak_flops(kind) == peak


@pytest.mark.parametrize(
    "kind", ["some new TPU kind", "TPU v7x", "cpu", "NVIDIA H100", ""]
)
def test_peak_flops_unknown_kind_raises(bench, kind):
    # No assumed peak: an MFU against a made-up denominator is worse than
    # no MFU.
    with pytest.raises(KeyError, match="peak table"):
        bench._peak_flops(kind)


def test_roofline_context(bench):
    # seist_l-ish numbers: 870 GFLOP/step, 30 GB accessed -> intensity 29
    # vs v5e ridge 240 -> memory-bound, MFU ceiling ~12%.
    r = bench._roofline(8.7e11, 3.0e10, "TPU v5 lite")
    assert r["memory_bound"] is True
    assert r["arithmetic_intensity"] == 29.0
    assert 0.1 < r["mfu_bound"] < 0.15
    # Compute-bound case caps at 1.0.
    r = bench._roofline(1e12, 1e9, "TPU v5 lite")
    assert r["memory_bound"] is False and r["mfu_bound"] == 1.0
    # No cost analysis -> no roofline; an unlisted device is an error.
    assert bench._roofline(0.0, 3e10, "TPU v5 lite") is None
    with pytest.raises(KeyError):
        bench._roofline(8.7e11, 3.0e10, "cpu")


# ----------------------------------------------------------------- config
def test_lowering_overrides_ride_the_config(bench, monkeypatch):
    # A sweep that forces a non-default lowering (SEIST_CHANNEL_PAD,
    # SEIST_GCONV_IMPL, ...) compiles a DIFFERENT program; the payload's
    # config must say so.
    monkeypatch.delenv("SEIST_CHANNEL_PAD", raising=False)
    assert bench.env_config()["lowering_overrides"] == {}
    monkeypatch.setenv("SEIST_CHANNEL_PAD", "128")
    assert bench.env_config()["lowering_overrides"] == {
        "SEIST_CHANNEL_PAD": "128"
    }
    assert bench.stream_config()["lowering_overrides"] == {
        "SEIST_CHANNEL_PAD": "128"
    }


def test_env_config_defaults_are_the_flagship(bench, monkeypatch):
    for k in ("BENCH_MODEL", "BENCH_DTYPE", "BENCH_BATCH", "BENCH_SAMPLES"):
        monkeypatch.delenv(k, raising=False)
    cfg = bench.env_config()
    assert (cfg["model"], cfg["dtype"]) == ("seist_l_dpk", "bf16")
    assert (cfg["batch"], cfg["in_samples"]) == (512, 8192)


def test_vs_baseline_rejects_mismatched_length(bench, tmp_path, monkeypatch):
    tools_dir = tmp_path / "tools"
    tools_dir.mkdir()
    (tools_dir / "reference_baseline.json").write_text(
        json.dumps(
            {
                "per_model": {
                    "m": {"waveforms_per_sec": 10.0, "in_samples": 8192}
                }
            }
        )
    )
    monkeypatch.setattr(bench, "_REPO", str(tmp_path))
    # wf/s scales inversely with length: an 8192-sample baseline must not
    # be compared against a 512-sample run.
    assert bench._vs_baseline(100.0, "m", 8192) == 10.0
    assert bench._vs_baseline(100.0, "m", 512) == 0.0


# ------------------------------------------------------- failure contract
def test_emit_stamps_schema_and_nothing_about_replays(bench, capsys):
    bench._emit({"metric": "m", "value": 1.0})
    out = _emitted(capsys)
    assert out["schema_version"] == bench._SCHEMA_VERSION
    assert "cached" not in out and "degraded" not in out


class _Dev:
    def __init__(self, kind):
        self.device_kind = kind
        self.platform = "tpu"


def test_main_refuses_unlisted_device_before_compiling(
    bench, monkeypatch, capsys
):
    import jax

    monkeypatch.setenv("BENCH_MODE", "train")
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v9 mega")])
    monkeypatch.setattr(
        bench, "bench_train", lambda kind: pytest.fail("bench ran anyway")
    )
    with pytest.raises(KeyError, match="TPU v9 mega"):
        bench.main()
    assert capsys.readouterr().out.strip() == ""  # no result line


@pytest.mark.parametrize("mode", ["train", "eval", "stream"])
def test_main_propagates_a_failed_measurement(bench, monkeypatch, capsys, mode):
    # A run that cannot measure raises (exit code != 0 from the script) and
    # prints no JSON — there is nothing to replay.
    import jax

    def boom(kind):
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setenv("BENCH_MODE", mode)
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v5 lite")])
    for fn in ("bench_train", "bench_eval", "bench_stream"):
        monkeypatch.setattr(bench, fn, boom)
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        bench.main()
    assert capsys.readouterr().out.strip() == ""


def test_script_exits_nonzero_without_a_listed_device():
    """The real script on this CPU-only host: no chip, so no number and a
    non-zero exit code."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "peak table" in r.stderr
