"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the full
width of ``seist_l_dpk`` (3 channels x 8192 samples, random weights from a
seed), on ONE TPU chip:

* train  — ``python main.py --mode train_test --model-name seist_l_dpk
  --dataset-name synthetic`` (batch 32, bf16): a few optimizer steps, the
  validation pass, checkpoints, the test pass. Then the same training
  again (``--mode train``): the step now comes out of the persistent
  compile cache.
* serve  — ``python main.py serve --model seist_l_dpk=<the trainer's last
  checkpoint> --window 8192``: readiness, single and burst ``POST
  /predict``, ``POST /annotate`` on a long record, ``GET /healthz`` and
  ``/metrics``, then a clean drain. Every response must carry picks the
  model's probabilities produced (with fresh weights and fresh BatchNorm
  statistics the served output is a constant 0.5, hence the checkpoint;
  requests set a near-zero pick threshold).

``--chips 4`` runs ONLY the path that exists across chips and what it is
compared with: the data-parallel train step on ``make_mesh(data=4)`` (one
process driving four devices) against the same global batch on one device.

One process per chip: a process that has touched JAX holds the chip, and a
child that needs it then fails or hangs. So in the default run this script
stays OFF jax: it runs the two entry points as children, one after the
other, and only after the last child has exited does it import jax itself —
to read the checkpoints the trainer wrote and to ask for the device line.
The ``--chips 4`` run has no children and does everything in this process.

The last line of standard output is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``;
everything else worth knowing is printed on earlier lines. Any failed phase,
and any platform other than ``tpu``, exits non-zero without that line.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))


@dataclass
class SmokeConfig:
    """What the smoke drives. The defaults are the contract (full width of
    seist_l_dpk on one v5e chip); the CPU rehearsal in
    tests/test_chip_smoke.py passes a tiny one."""

    model: str = "seist_l_dpk"
    in_samples: int = 8192
    batch: int = 32
    # 160 synthetic events -> 128 train (x2 by augmentation = 8 optimizer
    # steps at batch 32), 16 val, 16 test.
    events: int = 160
    dtype: str = "bf16"
    platform: str = "tpu"  # what every phase must have run on
    expect_kernel: bool = True  # Pallas custom call in the compiled step
    # the second train run takes the step out of the persistent cache (the
    # program caches compiles of 10 s and more: a tiny rehearsal has none)
    expect_cache_hit: bool = True
    annotate_samples: int = 20000  # a record longer than the window
    burst: int = 6
    out_dir: str = os.path.join(REPO, "logs", "chip_smoke")
    train_timeout_s: float = 800.0
    ready_timeout_s: float = 600.0


class PhaseFailed(Exception):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)
    say(f"ok: {what}")


# --------------------------------------------------------------- children
def child_env(dump_dir: Optional[str] = None) -> Dict[str, str]:
    """The children's environment: jax reports every compile and every
    persistent-cache hit on stderr (JAX_LOG_COMPILES), and writes the
    module it lowers for each jit to ``dump_dir`` (JAX_DUMP_IR_TO — at
    lowering time, so also when the executable then comes out of the
    persistent cache) for the parent to look for the kernel in the train
    step's. Nothing here selects a platform or a cache directory: the
    program's own rules apply (utils/misc.enable_compile_cache)."""
    env = dict(os.environ)
    env["JAX_LOG_COMPILES"] = "1"
    if dump_dir:
        env["JAX_DUMP_IR_TO"] = dump_dir
        env["JAX_DUMP_IR_MODES"] = "stablehlo"
    return env


_DEVICES_RE = re.compile(r"devices: (\{.*?\})\s*$", re.M)
_COMPILED_RE = re.compile(
    r"Finished XLA compilation of jit\((\w+)\) in ([0-9.]+) sec"
)
_CACHE_HIT_RE = re.compile(
    r"Persistent compilation cache hit for '(\w+)' with key '([\w-]+)'"
)


def _read(path: str) -> str:
    with open(path, errors="replace") as f:
        return f.read()


def compile_report(log_text: str) -> Dict[str, Any]:
    """Per-program compile seconds and cache hits from a child's log. Each
    jax record is echoed by two handlers, so identical (name, seconds)
    pairs are one compile."""
    compiles = sorted(set(_COMPILED_RE.findall(log_text)))
    by_name: Dict[str, List[float]] = {}
    for name, secs in compiles:
        by_name.setdefault(name, []).append(float(secs))
    hits = set(_CACHE_HIT_RE.findall(log_text))
    return {
        "compiles": by_name,
        "compile_s_total": round(sum(map(sum, by_name.values())), 1),
        "cache_hits": len(hits),
        "cache_hit_programs": sorted({name for name, _ in hits}),
    }


def run_train(
    cfg: SmokeConfig, tag: str, mode: str = "train_test"
) -> Dict[str, Any]:
    """One ``main.py --mode <mode>`` child. Returns what it left."""
    out = os.path.join(cfg.out_dir, tag)
    shutil.rmtree(out, ignore_errors=True)  # nothing stale is ever read
    os.makedirs(out)
    log_path = os.path.join(out, "child.log")
    dump_dir = os.path.join(out, "ir_dump")
    cmd = [
        sys.executable, os.path.join(REPO, "main.py"),
        "--mode", mode,
        "--model-name", cfg.model,
        "--dataset-name", "synthetic",
        "--synthetic-events", str(cfg.events),
        "--in-samples", str(cfg.in_samples),
        "--batch-size", str(cfg.batch),
        "--dtype", cfg.dtype,
        "--epochs", "1",
        "--seed", "0",
        "--log-step", "1",
        "--save-interval-steps", "2",
        "--keep-checkpoints", "8",
        "--use-tensorboard", "false",
        "--log-base", os.path.join(out, "logs"),
    ]
    say(f"{tag}: {' '.join(cmd[1:])}")
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            cmd, cwd=REPO, env=child_env(dump_dir), stdout=log,
            stderr=subprocess.STDOUT,
        )
        try:
            _wait_on_platform(proc, log_path, cfg, t0)
        finally:
            _stop(proc)
    wall = time.monotonic() - t0
    text = _read(log_path)
    if proc.returncode != 0:
        raise PhaseFailed(
            f"{tag}: trainer exited {proc.returncode} after {wall:.0f} s; "
            f"its log ends:\n{text[-3000:]}"
        )
    say(f"ok: {tag}: trainer exited 0 ({wall:.0f} s)")
    runs = sorted(glob.glob(os.path.join(out, "logs", "*")))
    check(len(runs) >= 1, f"{tag}: the trainer left a log directory")
    return {
        "tag": tag, "mode": mode, "wall_s": round(wall, 1), "logdir": runs[-1],
        "log": text, "dump_dir": dump_dir, **compile_report(text),
    }


def _wait_on_platform(proc, log_path: str, cfg: SmokeConfig, t0: float):
    """Wait for the child, reading its 'devices:' line as soon as it is
    there: a child that came up on another platform is stopped at once
    instead of training the full-width model on a CPU for an hour."""
    platform_seen = False
    while proc.poll() is None:
        if time.monotonic() - t0 > cfg.train_timeout_s:
            raise PhaseFailed(
                f"trainer still running after {cfg.train_timeout_s:.0f} s"
            )
        if not platform_seen:
            m = _DEVICES_RE.search(_read(log_path))
            if m:
                platform_seen = True
                dev = json.loads(m.group(1))
                check(
                    dev["platform"] == cfg.platform,
                    f"trainer runs on {json.dumps(dev)}",
                )
        time.sleep(0.5)
    if not platform_seen:
        m = _DEVICES_RE.search(_read(log_path))
        dev = json.loads(m.group(1)) if m else None
        check(
            bool(dev) and dev["platform"] == cfg.platform,
            f"trainer ran on {json.dumps(dev)}",
        )


def _stop(proc: subprocess.Popen) -> None:
    """Leave no process behind, whatever happened."""
    if proc.poll() is None:
        proc.kill()
    proc.wait()


def check_train(run: Dict[str, Any]) -> None:
    """The assertions a parent that has not touched jax can make."""
    import numpy as np

    tag = run["tag"]
    losses = np.load(os.path.join(run["logdir"], "train_losses.npy"))
    say(f"{tag}: train losses {np.array2string(losses, precision=5)}")
    check(
        len(losses) >= 3 and bool(np.isfinite(losses).all()),
        f"{tag}: {len(losses)} optimizer steps, every loss finite",
    )
    if run["mode"] == "train_test":
        metrics = glob.glob(os.path.join(run["logdir"], "test_metrics_*.json"))
        check(len(metrics) == 1, f"{tag}: the test pass wrote its metrics")
        say(f"{tag}: test metrics {_read(metrics[0]).strip()[:400]}")
    steps = run["compiles"].get("train_step", [])
    big = {k: v for k, v in run["compiles"].items() if max(v) >= 1.0}
    say(
        f"{tag}: compile seconds (programs of 1 s and more) "
        f"{json.dumps(big)}, all programs {run['compile_s_total']}; "
        f"persistent-cache hits {run['cache_hits']} "
        f"{run['cache_hit_programs']}"
    )
    check(
        len(steps) == 1,
        f"{tag}: the train step compiled once for {len(losses)} steps "
        f"(the second step did not compile)",
    )
    rates = re.findall(r"wave/s ([0-9.]+) \(", run["log"])
    if rates:
        say(f"{tag}: waveforms/s per logged step {rates}")


def check_kernel_in_step(run: Dict[str, Any]) -> None:
    """The program the trainer handed to the compiler as its train step
    holds the Pallas custom call (and therefore not the einsum path: there
    is no fallback between them)."""
    files = glob.glob(os.path.join(run["dump_dir"], "*train_step*"))
    check(len(files) > 0, f"{run['tag']}: jax dumped the train step's module")
    holders = [f for f in files if "tpu_custom_call" in _read(f)]
    say(
        f"{run['tag']}: tpu_custom_call found in "
        f"{sorted(os.path.basename(f) for f in holders)[:4]}"
    )
    check(
        len(holders) > 0,
        f"{run['tag']}: the train step contains the Pallas custom call",
    )


# ------------------------------------------------------------------ serve
def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(method: str, url: str, body: Any = None, timeout: float = 120.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    t0 = time.monotonic()
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            status, raw = r.status, r.read()
    except urllib.error.HTTPError as e:
        status, raw = e.code, e.read()
    ms = (time.monotonic() - t0) * 1e3
    try:
        payload = json.loads(raw)
    except ValueError:
        payload = {"raw": raw[:200].decode(errors="replace")}
    return status, payload, ms


# Every request asks for picks at a near-zero threshold: after a few
# optimizer steps the weights are as good as random, so at the default 0.3
# a noise trace yields none and a response would carry nothing the model
# computed. At 1e-6 every positive local
# maximum of the P and S probability traces is a pick; a forward that
# returned NaNs or zeros yields none, and the phase fails.
_PICK_OPTIONS = {"ppk_threshold": 1e-6, "spk_threshold": 1e-6}


def _check_picks(resp: Dict[str, Any], n_samples: int, what: str) -> None:
    import math

    for phase in ("ppk", "spk"):
        check(isinstance(resp.get(phase), list), f"{what}: '{phase}' list")
        for pick in resp[phase]:
            if not (
                0 <= int(pick["sample"]) < n_samples
                and math.isfinite(float(pick["time_s"]))
            ):
                raise PhaseFailed(f"{what}: pick out of range: {pick}")
    check(
        len(resp["ppk"]) > 0 and len(resp["spk"]) > 0,
        f"{what}: {len(resp['ppk'])} P and {len(resp['spk'])} S picks from "
        f"the model's probabilities (threshold "
        f"{_PICK_OPTIONS['ppk_threshold']}), all inside [0, {n_samples})",
    )


def run_serve(cfg: SmokeConfig, checkpoint: str) -> Dict[str, Any]:
    """Serve ``checkpoint`` (one of the train phase's). With weights fresh
    from a seed the served (eval-mode) output is exactly 0.5 for every
    input — the 0.02-scaled signal dies under BatchNorm statistics that were
    never estimated — and no response could show that the forward computed
    anything. After a few optimizer steps the output is finite and varies
    along the trace (it does not depend on the input yet): a NaN or Inf
    anywhere in the forward still empties the picks and fails the phase."""
    import numpy as np

    out = os.path.join(cfg.out_dir, "serve")
    os.makedirs(out, exist_ok=True)
    log_path = os.path.join(out, "child.log")
    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    cmd = [
        sys.executable, os.path.join(REPO, "main.py"), "serve",
        "--model", f"{cfg.model}={checkpoint}",
        "--window", str(cfg.in_samples), "--port", str(port),
    ]
    say(f"serve: {' '.join(cmd[1:])}")
    rng = np.random.default_rng(0)

    def body(n: int) -> Dict[str, Any]:
        data = rng.standard_normal((n, 3)).astype(np.float32).tolist()
        return {"data": data, "options": _PICK_OPTIONS}

    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            cmd, cwd=REPO, env=child_env(), stdout=log,
            stderr=subprocess.STDOUT,
        )
        try:
            # -- readiness --------------------------------------------------
            status = None
            while time.monotonic() - t0 < cfg.ready_timeout_s:
                if proc.poll() is not None:
                    raise PhaseFailed(
                        f"server exited rc={proc.returncode} while warming: "
                        f"{_read(log_path)[-1500:]}"
                    )
                try:
                    status, _, _ = _http("GET", base + "/healthz/ready", timeout=5)
                except OSError:
                    status = None
                if status == 200:
                    break
                time.sleep(0.5)
            ready_s = time.monotonic() - t0
            check(status == 200, f"serve: ready after {ready_s:.0f} s")

            # -- where it runs ----------------------------------------------
            status, health, _ = _http("GET", base + "/healthz")
            check(status == 200, "serve: GET /healthz 200")
            dev = health.get("device")
            check(
                bool(dev) and dev["platform"] == cfg.platform,
                f"serve: the serving programs run on {json.dumps(dev)}",
            )
            warm = health.get("warmup", [])
            say(
                "serve: warm-up compiled "
                f"{[(w.get('program'), round(w.get('seconds', 0), 1)) for w in warm]}"
            )
            check(
                {w.get("batch") for w in warm} >= set(health["buckets"]),
                f"serve: an AOT program for every bucket {health['buckets']}",
            )

            # -- single /predict --------------------------------------------
            lat: List[float] = []
            for i in range(3):
                status, resp, ms = _http(
                    "POST", base + "/predict", body(cfg.in_samples)
                )
                check(status == 200, f"serve: POST /predict #{i} 200 ({ms:.0f} ms)")
                _check_picks(resp, cfg.in_samples, f"serve: /predict #{i}")
                lat.append(round(ms, 1))

            # -- a burst that coalesces -------------------------------------
            _, before, _ = _http("GET", base + "/metrics")
            results: List[Tuple[int, float, Any]] = []
            bodies = [body(cfg.in_samples) for _ in range(cfg.burst)]

            def one(b):
                s, resp, ms = _http("POST", base + "/predict", b)
                results.append((s, round(ms, 1), resp))

            threads = [threading.Thread(target=one, args=(b,)) for b in bodies]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            check(
                [s for s, _, _ in results] == [200] * cfg.burst,
                f"serve: burst of {cfg.burst} /predict all 200 "
                f"(ms: {[ms for _, ms, _ in results]})",
            )
            for i, (_, _, resp) in enumerate(results):
                _check_picks(resp, cfg.in_samples, f"serve: burst #{i}")
            status, after, _ = _http("GET", base + "/metrics")
            check(status == 200, "serve: GET /metrics 200")
            b0, b1 = before["models"][cfg.model], after["models"][cfg.model]
            forwards = b1["forwards"] - b0["forwards"]
            check(
                b1["completed"] - b0["completed"] == cfg.burst
                and forwards < cfg.burst,
                f"serve: the burst coalesced into {forwards} forward(s)",
            )

            # -- /annotate on a record longer than the window ---------------
            status, resp, ms = _http(
                "POST", base + "/annotate",
                body(cfg.annotate_samples), timeout=300,
            )
            check(status == 200, f"serve: POST /annotate 200 ({ms:.0f} ms)")
            check(
                resp["record_samples"] == cfg.annotate_samples
                and resp["windows"] >= 2,
                f"serve: /annotate stitched {resp['windows']} windows over "
                f"{resp['record_samples']} samples",
            )
            _check_picks(resp, cfg.annotate_samples, "serve: /annotate")

            # -- clean drain ------------------------------------------------
            proc.send_signal(signal.SIGINT)
            try:
                proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                raise PhaseFailed("serve: no exit within 120 s of SIGINT")
            check(proc.returncode == 0, "serve: drained and exited 0")
        finally:
            _stop(proc)
    text = _read(log_path)
    check("[serve] stopped" in text, "serve: the log says 'stopped'")
    report = compile_report(text)
    say(
        f"serve: ready in {ready_s:.0f} s; predict ms {lat}; compile "
        f"seconds total {report['compile_s_total']}; persistent-cache hits "
        f"{report['cache_hits']}"
    )
    return {"ready_s": ready_s, **report}


# ------------------------------------------------- after the last child
def checkpoints(run: Dict[str, Any]) -> List[str]:
    """The trainer's checkpoints, oldest first."""
    return sorted(
        glob.glob(os.path.join(run["logdir"], "checkpoints", "model_*")),
        key=lambda p: int(p.rsplit("_", 1)[1]),
    )


def check_params_moved(run: Dict[str, Any]) -> None:
    """The trainer's checkpoints at two optimizer steps differ and are
    finite. Imports jax (orbax restores onto the default device), so it
    runs only after the last child has exited."""
    import jax
    import numpy as np

    from seist_tpu.train.checkpoint import load_checkpoint

    ckpts = checkpoints(run)
    check(len(ckpts) >= 2, f"the trainer kept checkpoints {[os.path.basename(c) for c in ckpts]}")
    first = jax.tree.leaves(load_checkpoint(ckpts[0])["params"])
    last = jax.tree.leaves(load_checkpoint(ckpts[-1])["params"])
    finite = all(bool(np.isfinite(np.asarray(a)).all()) for a in last)
    moved = sum(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(first, last)
    )
    check(finite, f"every parameter in {os.path.basename(ckpts[-1])} is finite")
    check(
        moved > len(last) // 2,
        f"{moved} of {len(last)} parameter tensors moved between "
        f"{os.path.basename(ckpts[0])} and {os.path.basename(ckpts[-1])}",
    )


def device_line(expected_platform: str, expected_count: int) -> str:
    from seist_tpu.utils.misc import device_summary

    dev = device_summary()
    if dev["platform"] != expected_platform or dev["count"] != expected_count:
        raise PhaseFailed(
            f"jax reports {json.dumps(dev)}; this run needs "
            f"{expected_count} x {expected_platform}"
        )
    return json.dumps({"ok": True, "device": dev})


def native_input_path() -> str:
    """Which host preprocessing path this commit runs (one, always)."""
    import seist_tpu.native as native

    return (
        f"native wavekit built from wavekit.cpp -> "
        f"{os.path.basename(native.lib_path())}"
        if native.available()
        else "numpy (SEIST_TPU_NATIVE=0)"
    )


def one_chip(cfg: SmokeConfig) -> None:
    say(f"host input path: {native_input_path()}")
    cold = run_train(cfg, "train_cold")
    check_train(cold)
    if cfg.expect_kernel:
        check_kernel_in_step(cold)
    warm = run_train(cfg, "train_warm", mode="train")  # same step program
    check_train(warm)
    say(
        f"train-step compile {cold['compiles']['train_step'][0]:.1f} s cold "
        f"-> {warm['compiles']['train_step'][0]:.1f} s warm; whole run "
        f"{cold['wall_s']} s -> {warm['wall_s']} s; the second run hit the "
        f"persistent compile cache {warm['cache_hits']} time(s)"
    )
    if cfg.expect_cache_hit:
        check(
            "jit_train_step" in warm["cache_hit_programs"],
            "train_warm: the second run took the train step out of the "
            "persistent compile cache",
        )
    check(bool(checkpoints(cold)), "train_cold left a checkpoint to serve")
    run_serve(cfg, checkpoints(cold)[-1])
    # Every child has exited: this process may now touch jax.
    check_params_moved(cold)


# ---------------------------------------------------------- four chips
# Folded attention shapes (L, M, H*E) of seist_l_dpk at 8192 samples, 3 heads.
_ATTN_SHAPES = ((1024, 128, 24), (512, 128, 24), (256, 128, 48), (128, 128, 96))
_ATTN_HEADS = 3

# Limit on ||dp4 update - one-device update|| / ||update|| for the whole
# step, fp32 at the TPU's default matmul precision: the sound run reads
# 5.556e-02 (PERF.md, PR 22). The two are different XLA programs (batch 32
# on one device, 8 on each of four), and at default precision fp32
# convolutions multiply bf16-rounded operands, so this norm cannot be
# tight; at precision "highest" the one-device step takes 1648 s to compile
# (sandbox compile, PR 22), which no chip call can pay. Gradients summed
# instead of averaged read 3.0 here and one shard's gradient taken for the
# batch's about 0.5. What this cannot see, a small fault in the sharded
# kernel's backward pass, kernel_rows_agree() checks to 1e-6.
_DP_UPDATE_LIMIT = 0.1


def kernel_rows_agree(mesh, batch: int, shapes, heads: int) -> None:
    """The attention kernel alone, forward and gradients with dropout on,
    on the mesh against one device. Each batch row is one grid step of the
    same Mosaic program wherever it runs, so the only thing that can differ
    is what the sharded call adds: the rows' global offset in the dropout
    counter, forward and backward. The results must agree to rounding."""
    import jax
    import numpy as np

    from seist_tpu.ops.pallas_attention import fused_pooled_attention
    from seist_tpu.parallel import mesh as mesh_lib

    def loss(q, k, v, seed):
        o = fused_pooled_attention(q, k, v, dropout_rate=0.3, dropout_seed=seed)
        return (o**2).sum(), o

    seed = np.array([20220922], np.int32)
    for l, m, he in shapes:
        rng = np.random.default_rng(l)
        q, k, v = (
            rng.standard_normal((batch, n, heads, he // heads)).astype(np.float32)
            for n in (l, m, m)
        )
        grad = lambda: jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))
        (_, o1), g1 = grad()(q, k, v, seed)
        with mesh_lib.use_mesh(mesh):  # a fresh jit: traced under the mesh
            (_, o4), g4 = grad()(*mesh_lib.shard_batch(mesh, (q, k, v)), seed)
        check(
            len(o4.sharding.device_set) == 4,
            f"kernel L{l} M{m} HE{he}: the output stays laid over 4 devices",
        )
        worst = max(
            float(np.abs(np.asarray(a) - np.asarray(b)).max() / np.abs(np.asarray(b)).max())
            for a, b in zip((o4, *g4), (o1, *g1))
        )
        check(
            worst <= 1e-6,
            f"kernel L{l} M{m} HE{he}: output, dq, dk, dv on 4 devices equal "
            f"one device's (worst |diff| / max {worst:.1e}, limit 1e-6)",
        )


def four_chips(
    model: str = "seist_l_dpk", in_samples: int = 8192, batch: int = 32,
    platform: str = "tpu", steps: int = 2, devices=None,
    attn_shapes=_ATTN_SHAPES,
) -> None:
    """Data-parallel train step on make_mesh(data=4) vs the same global
    batch on one device, all in this process: first the attention kernel
    alone at ``attn_shapes`` (tight), then the whole fp32 step (structure,
    and agreement as far as two XLA programs at the TPU's default precision
    agree). ``devices`` defaults to all of jax's (the rehearsal on virtual
    CPU devices passes four of them)."""
    import jax
    import numpy as np

    import seist_tpu
    from seist_tpu import taskspec
    from seist_tpu.data.pipeline import Loader, from_task_spec
    from seist_tpu.models import api
    from seist_tpu.parallel import mesh as mesh_lib
    from seist_tpu.train import (
        build_optimizer, create_train_state, jit_step, make_train_step,
    )
    from seist_tpu.utils.misc import device_summary, enable_compile_cache

    devices = list(devices if devices is not None else jax.devices())
    say(f"devices: {json.dumps(device_summary())}")
    check(
        len(devices) == 4 and devices[0].platform == platform,
        f"four {platform} devices in this process",
    )
    enable_compile_cache()
    seist_tpu.load_all()
    mesh = mesh_lib.make_mesh(data=4, devices=devices)
    kernel_rows_agree(mesh, batch, attn_shapes, _ATTN_HEADS)
    spec = taskspec.get_task_spec(model)
    loss_fn = taskspec.make_loss(model)
    in_channels = taskspec.get_num_inchannels(model)
    net = api.create_model(model, in_channels=in_channels, in_samples=in_samples)
    variables = api.init_variables(
        net, seed=0, in_samples=in_samples, in_channels=in_channels
    )
    # Plain SGD: the update is lr * gradient, so agreement of the updated
    # parameters is agreement of the gradients (Adam's normalisation would
    # blow reduction-order noise in near-zero gradients up to +-lr).
    tx = build_optimizer("sgd", 1e-2, momentum=0.0)

    def fresh_state():
        return create_train_state(net, jax.tree.map(np.array, variables), tx)

    ds = from_task_spec(
        spec, "synthetic", "train", seed=0, in_samples=in_samples,
        augmentation=False, data_split=False,
        dataset_kwargs={
            "num_events": batch * steps,
            "trace_samples": in_samples + in_samples // 2,
        },
    )
    loader = Loader(ds, batch_size=batch, shuffle=False, num_workers=1)
    try:
        batches = [(b.inputs, b.loss_targets) for b in loader][:steps]
    finally:
        loader.close()
    check(len(batches) == steps, f"{steps} global batches of {batch} x {in_samples}")
    key = jax.random.PRNGKey(0)
    step_fn = make_train_step(spec, loss_fn)  # fp32

    def run(mesh) -> Tuple[List[float], Any, Any]:
        state = fresh_state()
        if mesh is not None:
            state = mesh_lib.replicate(mesh, state)
        step = jit_step(step_fn, mesh)
        losses, placed = [], None
        t0 = time.monotonic()
        for x, y in batches:
            if mesh is not None:
                x, y = mesh_lib.shard_batch(mesh, (x, y))
                placed = x if placed is None else placed
            state, loss, _ = step(state, x, y, key)
            losses.append(float(loss))
        jax.block_until_ready(state.params)
        say(
            f"{'dp4' if mesh is not None else 'one device'}: losses "
            f"{losses} ({time.monotonic() - t0:.0f} s incl. compile)"
        )
        return losses, state, placed

    ref_losses, ref_state, _ = run(None)
    dp_losses, dp_state, placed = run(mesh)

    # -- the batch really lies over four devices ---------------------------
    leaf = jax.tree.leaves(placed)[0]
    shard_shapes = sorted({s.data.shape for s in leaf.addressable_shards})
    check(
        len(leaf.sharding.device_set) == 4
        and shard_shapes == [(batch // 4,) + leaf.shape[1:]],
        f"the batch {leaf.shape} is split over 4 devices in shards of "
        f"{shard_shapes}",
    )
    # -- and so do the gradients: each device reduces its quarter, and the
    # step's compiled text carries the cross-device all-reduce ------------
    x, y = mesh_lib.shard_batch(mesh, batches[0])
    compiled = (
        jit_step(step_fn, mesh).__wrapped__.lower(dp_state, x, y, key).compile()
    )
    text = compiled.as_text()
    n_allreduce = len(re.findall(r"\ball-reduce(?:-start)?\(", text))
    check(
        n_allreduce > 0,
        f"the 4-device step all-reduces gradients across devices "
        f"({n_allreduce} all-reduce ops)",
    )
    if platform == "tpu":
        check(
            "tpu_custom_call" in text,
            "the 4-device step holds the Pallas custom call",
        )
    pleaf = jax.tree.leaves(dp_state.params)[0]
    copies = [np.asarray(s.data) for s in pleaf.addressable_shards]
    check(
        len(pleaf.sharding.device_set) == 4
        and all(np.array_equal(copies[0], c) for c in copies[1:]),
        "the updated parameters are replicated, identical, on all 4 devices",
    )

    # -- agreement with one device ------------------------------------------
    np.testing.assert_allclose(dp_losses, ref_losses, rtol=2e-3)
    check(True, f"losses agree: {dp_losses} vs {ref_losses} (rtol 2e-3)")
    # Updated parameters: the error of the 4-device update against the
    # one-device update, in the L2 norm over ALL parameters, relative to the
    # update itself. Per tensor a relative bound is meaningless: tensors
    # whose exact gradient is zero (a bias in front of a BatchNorm, the key
    # bias under a softmax) move by rounding noise only, and that noise is
    # different in every program. For the limit see _DP_UPDATE_LIMIT.
    paths = [
        jax.tree_util.keystr(k)
        for k, _ in jax.tree_util.tree_leaves_with_path(ref_state.params)
    ]
    init = jax.tree.leaves(variables["params"])
    err2 = upd2 = 0.0
    rows, moved = [], 0
    groups: Dict[str, List[float]] = {}  # top-level module -> [err2, upd2]
    for path, a, b, p0 in zip(
        paths, jax.tree.leaves(dp_state.params),
        jax.tree.leaves(ref_state.params), init,
    ):
        a, b, p0 = (np.asarray(t, np.float64) for t in (a, b, p0))
        moved += not np.array_equal(b, p0)
        e2, u2 = float(((a - b) ** 2).sum()), float(((b - p0) ** 2).sum())
        err2, upd2 = err2 + e2, upd2 + u2
        g = groups.setdefault(path.split("']")[0].lstrip("['"), [0.0, 0.0])
        g[0], g[1] = g[0] + e2, g[1] + u2
        rows.append((float(np.abs(a - b).max()), float(np.abs(b - p0).max()), path))
    check(moved > len(init) // 2, f"{moved} of {len(init)} parameter tensors moved")
    for diff, update, path in sorted(rows, reverse=True)[:5]:
        say(f"largest |dp - single| {diff:.3e} (that tensor's update {update:.3e}) at {path}")
    say(
        "||dp - single|| / ||update|| by module: "
        + ", ".join(
            f"{name} {(e2 / u2) ** 0.5:.1e}" for name, (e2, u2) in groups.items() if u2
        )
    )
    rel = (err2 / upd2) ** 0.5
    check(
        rel <= _DP_UPDATE_LIMIT,
        f"updated parameters agree: ||dp - single|| / ||update|| = {rel:.3e} "
        f"over all {len(init)} tensors (limit {_DP_UPDATE_LIMIT})",
    )


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, default=1, choices=[1, 4],
        help="4 = only the data-parallel step across four chips vs one",
    )
    args = ap.parse_args(argv)
    try:
        if args.chips == 4:
            four_chips()
        else:
            one_chip(SmokeConfig())
        line = device_line("tpu", args.chips)
    except PhaseFailed as e:
        say(f"FAILED: {e}")
        return 1
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
