"""True multi-process tests: 2 simulated hosts x 4 virtual CPU devices.

Spawns two python processes that rendezvous through jax.distributed on a
localhost coordinator and run tests/_multihost_worker.py — the only way to
exercise make_array_from_process_local_data, cross-host metric sync, and
broadcast_object for real (the in-process suite runs single-host). The
reference framework has no equivalent capability (its multi-node path needs
actual torchrun, SURVEY.md §4).
"""

import os
import socket
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # two 540s-timeout process rendezvous

HERE = os.path.dirname(__file__)
WORKER = os.path.join(HERE, "_multihost_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def test_two_host_simulation():
    port = _free_port()
    repo = os.path.abspath(os.path.join(HERE, ".."))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(i), "2", str(port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            env=env,
            cwd=os.path.join(HERE, ".."),
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=540)
            outs.append(out.decode())
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("multi-host workers timed out:\n" + "\n".join(outs))
    for i, p in enumerate(procs):
        assert p.returncode == 0, f"worker {i} failed:\n{outs[i]}"
        assert f"worker {i}: OK" in outs[i]


@pytest.mark.parametrize("device_aug", ["off", "cached"])
def test_two_host_training(tmp_path, device_aug):
    """Full train_worker epoch across 2 simulated hosts: sharded loaders,
    global eval loss, synced metrics, multi-host orbax checkpoint.

    device_aug='cached' additionally pins the multi-host epoch cache
    (per-host addressable-slice placement + host-sharded index chunks) —
    the contract that let PR 14 remove the cached->step multi-host
    fallback."""
    port = _free_port()
    repo = os.path.abspath(os.path.join(HERE, ".."))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    worker = os.path.join(HERE, "_multihost_train_worker.py")
    procs = [
        subprocess.Popen(
            [
                sys.executable, worker, str(i), "2", str(port),
                str(tmp_path), device_aug,
            ],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            env=env,
            cwd=repo,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            # Generous: both workers compile on the same single CPU core.
            out, _ = p.communicate(timeout=900)
            outs.append(out.decode())
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("multi-host train workers timed out:\n" + "\n".join(outs))
    for i, p in enumerate(procs):
        assert p.returncode == 0, f"train worker {i} failed:\n{outs[i][-3000:]}"
        assert f"train worker {i}: OK" in outs[i]
