"""seist_tpu/utils/tb.py: the event files ScalarWriter frames itself.

Records are re-read here by their framing with a crc32c of this file's own
(bitwise, not the writer's table), events are parsed with TensorBoard's
protobuf classes, and a subprocess shows that neither the writer nor the
trainer's module loads torch or TensorFlow (the 38-43 s of every set-up the
writer used to cost, PERF.md PR 35)."""

import math
import os
import re
import socket
import struct
import subprocess
import sys
import threading

import numpy as np
import pytest

from seist_tpu.utils import tb
from seist_tpu.utils.tb import ScalarWriter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def crc32c(data: bytes) -> int:
    """Castagnoli CRC bit by bit: independent of the writer's table."""
    crc = 0xFFFFFFFF
    for byte in data:
        crc ^= byte
        for _ in range(8):
            crc = (crc >> 1) ^ (0x82F63B78 if crc & 1 else 0)
    return crc ^ 0xFFFFFFFF


def masked(crc: int) -> int:
    return (((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF


def read_records(path):
    """The payloads of a TFRecord file, both crcs of every record checked."""
    with open(path, "rb") as f:
        blob = f.read()
    records, at = [], 0
    while at < len(blob):
        header = blob[at:at + 8]
        (length,) = struct.unpack("<Q", header)
        (header_crc,) = struct.unpack("<I", blob[at + 8:at + 12])
        assert header_crc == masked(crc32c(header)), f"length crc at byte {at}"
        data = blob[at + 12:at + 12 + length]
        assert len(data) == length, f"record cut short at byte {at}"
        (data_crc,) = struct.unpack("<I", blob[at + 12 + length:at + 16 + length])
        assert data_crc == masked(crc32c(data)), f"data crc at byte {at}"
        records.append(data)
        at += 16 + length
    return records


def read_events(path):
    """The file's events as TensorBoard's own ``Event`` messages."""
    from tensorboard.compat.proto import event_pb2

    return [event_pb2.Event.FromString(r) for r in read_records(path)]


def read_scalars(path):
    """``[(tag, value, step)]`` of every scalar event, in file order, after
    checking that the file opens with the version event."""
    events = read_events(path)
    assert events[0].file_version == "brain.Event:2"
    assert not events[0].HasField("summary")
    out = []
    for e in events[1:]:
        assert e.wall_time > 0 and len(e.summary.value) == 1
        v = e.summary.value[0]
        assert v.WhichOneof("value") == "simple_value"
        out.append((v.tag, v.simple_value, e.step))
    return out


def event_file(logdir):
    (name,) = os.listdir(logdir)
    return os.path.join(logdir, name)


def test_crc32c_of_this_file_is_the_standard_one():
    assert crc32c(b"123456789") == 0xE3069283  # the CRC-32C check value
    google_crc32c = pytest.importorskip("google_crc32c")
    blob = np.random.default_rng(0).bytes(257)
    assert google_crc32c.value(blob) == crc32c(blob)


@pytest.mark.parametrize("n", [0, 1, 8, 60, 257])
def test_writer_crc_agrees_with_the_independent_one(n):
    blob = np.random.default_rng(n).bytes(n)
    assert tb._masked_crc32c(blob) == struct.pack("<I", masked(crc32c(blob)))


def test_round_trip(tmp_path):
    pytest.importorskip("tensorboard.compat.proto.event_pb2")
    written = [
        ("train-loss/step", 0.5627874135971069, 0),
        ("train-loss/step", 0.25, 32),
        ("train-loss/epoch", 1.130150318145752, 1),
        ("val-loss/epoch", 3.0e-7, 1),
        ("val-metrics/ppk/f1", 0.0, 1),
    ]
    w = ScalarWriter(str(tmp_path))
    for tag, value, step in written[:3]:
        w.add_scalar(tag, value, step)
    w.add_scalars("val-loss", {"epoch": written[3][1]}, 1)
    w.add_scalars("val-metrics/ppk", {"f1": 0.0}, 1)
    w.flush()
    w.close()
    got = read_scalars(event_file(str(tmp_path)))
    assert [(t, s) for t, _, s in got] == [(t, s) for t, _, s in written]
    # simple_value is a float32 field: what comes back is the cast, exactly.
    assert [v for _, v, _ in got] == [float(np.float32(v)) for _, v, _ in written]
    events = read_events(event_file(str(tmp_path)))
    times = [e.wall_time for e in events]
    assert times == sorted(times)
    # Byte for byte what protobuf itself makes of the same messages (a step
    # of 0 left out, as proto3 does), so also what TensorBoard's writer frames.
    records = read_records(event_file(str(tmp_path)))
    assert [e.SerializeToString() for e in events] == records


@pytest.mark.parametrize(
    "value, expect",
    [
        (-2.5, -2.5),
        (np.float32(0.1), float(np.float32(0.1))),
        (np.float64(1e-300), 0.0),
        (7, 7.0),
        (float("inf"), math.inf),
        (1e40, math.inf),  # beyond float32: the cast's answer, no exception
        (-1e40, -math.inf),
        (float("nan"), math.nan),
    ],
)
def test_value_is_stored_as_float32(tmp_path, value, expect):
    pytest.importorskip("tensorboard.compat.proto.event_pb2")
    w = ScalarWriter(str(tmp_path))
    w.add_scalar("x", value, 1)
    w.close()
    ((_, got, _),) = read_scalars(event_file(str(tmp_path)))
    assert (math.isnan(got) and math.isnan(expect)) or got == expect


def test_device_scalar_is_accepted(tmp_path):
    """The trainer hands the writer what ``float()`` takes: also a jax
    scalar that was never fetched."""
    pytest.importorskip("tensorboard.compat.proto.event_pb2")
    import jax.numpy as jnp

    w = ScalarWriter(str(tmp_path))
    w.add_scalar("x", jnp.float32(1.5), np.int64(4))
    w.close()
    assert read_scalars(event_file(str(tmp_path))) == [("x", 1.5, 4)]


@pytest.mark.parametrize("step", [0, 1, 127, 128, 16383, 16384, 2**31, 2**40, -1])
def test_step_round_trips_as_int64(tmp_path, step):
    pytest.importorskip("tensorboard.compat.proto.event_pb2")
    w = ScalarWriter(str(tmp_path))
    w.add_scalar("x", 1.0, step)
    w.close()
    assert read_scalars(event_file(str(tmp_path))) == [("x", 1.0, step)]


def test_tag_is_utf8_and_may_be_long(tmp_path):
    pytest.importorskip("tensorboard.compat.proto.event_pb2")
    tags = ["val-metrics/Δt/mean", "t" * 300]
    w = ScalarWriter(str(tmp_path))
    for tag in tags:
        w.add_scalar(tag, 1.0, 1)
    w.close()
    assert [t for t, _, _ in read_scalars(event_file(str(tmp_path)))] == tags


def test_file_is_named_as_tensorboard_names_it(tmp_path):
    logdir = str(tmp_path / "made" / "on" / "demand")
    a, b = ScalarWriter(logdir), ScalarWriter(logdir)  # the same second
    a.close()
    b.close()
    names = sorted(os.listdir(logdir))
    assert len(names) == 2
    for name in names:
        m = re.fullmatch(r"events\.out\.tfevents\.(\d{10})\.(.+)\.(\d+)\.(\d+)", name)
        assert m, name
        assert m.group(2) == socket.gethostname()
        assert int(m.group(3)) == os.getpid()
    # Each file is whole by itself: it opens with the version event.
    for name in names:
        (record,) = read_records(os.path.join(logdir, name))
        assert b"brain.Event:2" in record


def test_flush_leaves_whole_records_on_disk(tmp_path):
    w = ScalarWriter(str(tmp_path))
    w.add_scalar("x", 1.0, 1)
    w.flush()
    assert len(read_records(event_file(str(tmp_path)))) == 2  # before close
    w.add_scalar("x", 2.0, 2)
    w.close()
    assert len(read_records(event_file(str(tmp_path)))) == 3
    with pytest.raises(ValueError):
        w.add_scalar("x", 3.0, 3)  # a closed writer says so


def test_writer_starts_no_thread(tmp_path):
    before = set(threading.enumerate())
    w = ScalarWriter(str(tmp_path))
    w.add_scalars("a", {"b": 1.0, "c": 2.0}, 1)
    assert set(threading.enumerate()) == before  # written on this thread
    w.flush()
    w.close()
    assert set(threading.enumerate()) == before


def test_one_writer_path_and_no_guarded_import():
    """ROADMAP D5: one path; nothing to fall back to, so nothing is tried."""
    with open(tb.__file__) as f:
        source = f.read()
    assert "jsonl" not in source.lower()
    assert not re.search(r"^\s*(import|from)\s+(torch|tensorflow|tensorboard)", source, re.M)
    assert not re.search(r"try:\s*\n\s*(import|from)\s", source)


_NO_HEAVY_IMPORTS = """
import os, sys, tempfile
heavy = lambda: sorted(m for m in ("torch", "tensorflow") if m in sys.modules)
from seist_tpu.utils.tb import ScalarWriter
d = tempfile.mkdtemp()
w = ScalarWriter(d)
w.add_scalars("val-metrics/ppk", {"f1": 0.5, "mean": 0.1}, 3)
w.flush()
w.close()
assert os.path.getsize(os.path.join(d, os.listdir(d)[0])) > 100
print("after the writer:", heavy())
import seist_tpu.train.worker
print("after the trainer:", heavy())
"""


def test_neither_torch_nor_tensorflow_is_loaded():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-c", _NO_HEAVY_IMPORTS],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "after the writer: []" in proc.stdout, proc.stdout
    assert "after the trainer: []" in proc.stdout, proc.stdout
