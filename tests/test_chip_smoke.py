"""chip_smoke.py and the placeable compile cache, rehearsed on the CPU.

The smoke's contract is about a TPU chip (python chip_smoke.py must FAIL
here); what the CPU can show is that both phases drive the real entry
points end to end at a tiny size, that the result line has the exact
format, that a failed phase means a non-zero exit and no result line, and
that the compile cache goes where it is told (JAX_COMPILATION_CACHE_DIR) or
to the one fixed in-checkout path.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from seist_tpu.utils import misc  # noqa: E402


def _tiny(tmp_path, **kw):
    return chip_smoke.SmokeConfig(
        model="phasenet", in_samples=512, batch=8, events=80, dtype="fp32",
        platform="cpu", expect_kernel=False, expect_cache_hit=False,
        annotate_samples=1500, burst=4, out_dir=str(tmp_path / "smoke"),
        train_timeout_s=600, ready_timeout_s=300, **kw,
    )


# ------------------------------------------------------- phase rehearsals
@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """One tiny trainer child for the module: the train rehearsal checks
    it, the serve rehearsal serves its last checkpoint (as the smoke does)."""
    cfg = _tiny(tmp_path_factory.mktemp("trained"))
    return cfg, chip_smoke.run_train(cfg, "train_cold")


def test_train_phase_rehearsal_on_cpu(trained):
    """The real trainer as a child (phasenet-sized): optimizer steps,
    checkpoints, test pass; then the parent's assertions, including the
    one-compile-per-run check that the second step did not compile."""
    cfg, run = trained
    chip_smoke.check_train(run)
    chip_smoke.check_params_moved(run)
    assert run["compiles"]["train_step"] and run["wall_s"] > 0
    # the HLO of the step was dumped where the kernel check looks for it
    assert os.listdir(run["dump_dir"])
    with pytest.raises(chip_smoke.PhaseFailed, match="Pallas custom call"):
        chip_smoke.check_kernel_in_step(run)  # phasenet on CPU: none


def test_serve_phase_rehearsal_on_cpu(trained):
    """The real server as a child, serving the trainer's last checkpoint:
    readiness, /predict single and burst, /annotate on a long record,
    /healthz, /metrics, clean drain; picks in every response."""
    cfg, run = trained
    report = chip_smoke.run_serve(cfg, chip_smoke.checkpoints(run)[-1])
    assert report["ready_s"] > 0


def test_a_response_without_picks_fails_the_serve_phase():
    """A forward that answered NaNs, zeros or a constant yields no picks at
    any threshold: that is a failed phase, not an empty success."""
    ok = {"ppk": [{"sample": 3, "time_s": 0.06}], "spk": [{"sample": 9, "time_s": 0.18}]}
    chip_smoke._check_picks(ok, 512, "t")
    with pytest.raises(chip_smoke.PhaseFailed, match="0 P and 1 S picks"):
        chip_smoke._check_picks(dict(ok, ppk=[]), 512, "t")
    with pytest.raises(chip_smoke.PhaseFailed, match="out of range"):
        chip_smoke._check_picks(dict(ok, spk=[{"sample": 512, "time_s": 10.24}]), 512, "t")


def test_train_phase_refuses_the_wrong_platform(tmp_path):
    cfg = _tiny(tmp_path)
    cfg.platform = "tpu"  # the children come up on the CPU here
    with pytest.raises(chip_smoke.PhaseFailed, match='"platform": "cpu"'):
        chip_smoke.run_train(cfg, "train_cold")


def test_four_chip_phase_rehearsal_on_virtual_devices(monkeypatch):
    """The data-parallel step on four virtual CPU devices against one
    device, through a SeisT model whose attention runs the Pallas kernels
    — in interpret mode, and only inside this test."""
    from seist_tpu.ops import pallas_attention as pa

    real = pa._call_fused
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        pa, "_call_fused",
        lambda kernel, out_shapes, seed, inputs, interpret: real(
            kernel, out_shapes, seed, inputs, True
        ),
    )
    chip_smoke.four_chips(
        model="seist_s_dpk", in_samples=512, batch=8, platform="cpu",
        devices=jax.devices()[:4], attn_shapes=((64, 16, 24), (32, 32, 48)),
    )


def test_four_chip_phase_needs_exactly_four_devices():
    with pytest.raises(chip_smoke.PhaseFailed, match="four tpu devices"):
        chip_smoke.four_chips(devices=jax.devices()[:2])


# ------------------------------------------------------- the result line
_V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_last_line_format_is_exact(monkeypatch, capsys):
    monkeypatch.setattr(misc, "device_summary", lambda: dict(_V5E))
    monkeypatch.setattr(chip_smoke, "one_chip", lambda cfg: None)
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last == (
        '{"ok": true, "device": {"platform": "tpu", '
        '"kind": "TPU v5 lite", "count": 1}}'
    )


def test_four_chip_line_counts_four(monkeypatch, capsys):
    monkeypatch.setattr(misc, "device_summary", lambda: dict(_V5E, count=4))
    monkeypatch.setattr(chip_smoke, "four_chips", lambda: None)
    monkeypatch.setattr(
        chip_smoke, "one_chip", lambda cfg: pytest.fail("one-chip phase ran")
    )
    assert chip_smoke.main(["--chips", "4"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": dict(_V5E, count=4)}


@pytest.mark.parametrize(
    "dev", [{"platform": "cpu", "kind": "cpu", "count": 1}, dict(_V5E, count=4)]
)
def test_no_result_line_for_the_wrong_device(monkeypatch, capsys, dev):
    # every phase "passed", but jax reports no single TPU chip
    monkeypatch.setattr(misc, "device_summary", lambda: dict(dev))
    monkeypatch.setattr(chip_smoke, "one_chip", lambda cfg: None)
    assert chip_smoke.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out


def test_failed_phase_exits_nonzero_without_a_result(monkeypatch, capsys):
    def failing(cfg):
        raise chip_smoke.PhaseFailed("serve: POST /predict #0 200")

    monkeypatch.setattr(misc, "device_summary", lambda: dict(_V5E))
    monkeypatch.setattr(chip_smoke, "one_chip", failing)
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out
    assert "FAILED: serve: POST /predict" in out and '"ok"' not in out


def _run_script(cwd, script):
    return subprocess.run(
        [sys.executable, script], cwd=cwd, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )


def test_script_fails_without_an_accelerator():
    r = _run_script(REPO, os.path.join(REPO, "chip_smoke.py"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert '"platform": "cpu"' in r.stdout  # it says where it came up


def test_script_fails_alone_in_a_directory(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run_script(str(tmp_path), str(tmp_path / "chip_smoke.py"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_compile_report_reads_a_child_log():
    log = "\n".join([
        "WARNING:2026-01-01 00:00:00,000:jax._src.dispatch:207: Finished XLA "
        "compilation of jit(train_step) in 101.250000000 sec",
        "WARNING:jax._src.dispatch:Finished XLA compilation of "
        "jit(train_step) in 101.250000000 sec",
        "WARNING:jax._src.dispatch:Finished XLA compilation of "
        "jit(eval_step) in 3.500000000 sec",
        "WARNING:jax._src.compiler:Persistent compilation cache hit for "
        "'jit_train_step' with key 'jit_train_step-abc123'",
        "WARNING:2026-01-01 00:00:00,000:jax._src.compiler:102: Persistent "
        "compilation cache hit for 'jit_train_step' with key "
        "'jit_train_step-abc123'",
    ])
    rep = chip_smoke.compile_report(log)
    assert rep["compiles"] == {"train_step": [101.25], "eval_step": [3.5]}
    assert rep["cache_hits"] == 1
    assert rep["cache_hit_programs"] == ["jit_train_step"]


# ------------------------------------------------- the placeable cache
@pytest.fixture
def cache_config():
    """Put jax's cache settings back however a test leaves them."""
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)


def _recorded_updates(monkeypatch):
    calls = []
    real = jax.config.update

    def spy(name, value):
        calls.append((name, value))
        return real(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    return calls


@pytest.mark.parametrize("value", ["/x", ""])
def test_cache_env_is_the_whole_story(cache_config, monkeypatch, value):
    # With JAX_COMPILATION_CACHE_DIR set (even to "", jax's "no cache"),
    # no code path of ours sets a directory.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", value)
    calls = _recorded_updates(monkeypatch)
    misc.enable_compile_cache()
    assert [n for n, _ in calls] == ["jax_persistent_cache_min_compile_time_secs"]


def test_cache_defaults_to_the_fixed_in_checkout_path(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    misc.enable_compile_cache(min_compile_seconds=7)
    assert misc.DEFAULT_COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == misc.DEFAULT_COMPILE_CACHE_DIR
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 7


def test_cache_path_is_fixed_and_ignored_by_git():
    src = open(os.path.join(REPO, "seist_tpu", "utils", "misc.py")).read()
    for word in ("expanduser", "mkdtemp", "getpid"):
        assert word not in src
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored and "chiprun_out/" in ignored


def test_cache_failures_are_not_swallowed(cache_config, monkeypatch):
    def boom(name, value):
        raise RuntimeError("config refused")

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax.config, "update", boom)
    with pytest.raises(RuntimeError, match="config refused"):
        misc.enable_compile_cache()
