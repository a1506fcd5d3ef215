"""Scalar logging: TensorBoard event files, framed here.

The reference writes per-step/per-epoch scalars through
``torch.utils.tensorboard.SummaryWriter`` (train.py:166-173,420-442). Every
packaged writer loads torch or TensorFlow when it opens (that one both,
through ``tensorboard.compat.tf``: 38-43 s of a set-up on the chip's host,
PERF.md PR 35), to frame records of 60 bytes, so ``ScalarWriter`` encodes
them itself and imports no package:

* an event is one protobuf ``Event``: ``wall_time`` (field 1, double),
  ``step`` (2, varint) and either ``file_version`` (3, string; the file's
  first record, ``brain.Event:2``) or ``summary`` (5) holding one
  ``Summary.Value`` (1) of ``tag`` (1, string) and ``simple_value`` (2,
  float32);
* a record is TFRecord framing: length (uint64), masked crc32c of the
  length, the event, masked crc32c of the event;
* the file is named as TensorBoard names it,
  ``events.out.tfevents.<time>.<host>.<pid>.<n>``.

Records go through the file's buffer on the caller's thread (a few scalars
an epoch): no queue, no thread; ``flush()`` and ``close()`` are the file's.
"""

from __future__ import annotations

import itertools
import math
import os
import socket
import struct
import time
from typing import Dict


def _crc32c_table():
    table = []
    for n in range(256):
        for _ in range(8):
            n = (n >> 1) ^ (0x82F63B78 if n & 1 else 0)  # Castagnoli, reflected
        table.append(n)
    return table


_CRC_TABLE = _crc32c_table()
_FILE_UID = itertools.count()  # <n> of the file name: files this process opened


def _masked_crc32c(data: bytes) -> bytes:
    crc = 0xFFFFFFFF
    for b in data:
        crc = _CRC_TABLE[(crc ^ b) & 0xFF] ^ (crc >> 8)
    crc ^= 0xFFFFFFFF
    return struct.pack("<I", (((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF)


def _varint(n: int) -> bytes:
    n &= 0xFFFFFFFFFFFFFFFF  # int64 as protobuf sends it: two's complement
    out = bytearray()
    while n > 0x7F:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _delimited(tag: int, payload: bytes) -> bytes:
    return bytes([tag]) + _varint(len(payload)) + payload


class ScalarWriter:
    def __init__(self, logdir: str):
        os.makedirs(logdir, exist_ok=True)
        self._file = open(
            os.path.join(
                logdir,
                f"events.out.tfevents.{int(time.time()):010d}."
                f"{socket.gethostname()}.{os.getpid()}.{next(_FILE_UID)}",
            ),
            "wb",
        )
        self._write_event(0, _delimited(0x1A, b"brain.Event:2"))
        from seist_tpu.utils.logger import logger

        logger.info(f"ScalarWriter: tensorboard event files -> {logdir}")

    def _write_event(self, step: int, what: bytes) -> None:
        event = b"\x09" + struct.pack("<d", time.time())
        if step:  # proto3 leaves a zero out
            event += b"\x10" + _varint(step)
        event += what
        header = struct.pack("<Q", len(event))
        self._file.write(
            header + _masked_crc32c(header) + event + _masked_crc32c(event)
        )

    def add_scalar(self, tag: str, value: float, step: int) -> None:
        try:
            value32 = struct.pack("<f", float(value))
        except OverflowError:  # beyond float32: what a cast would give
            value32 = struct.pack("<f", math.copysign(math.inf, value))
        value_msg = _delimited(0x0A, tag.encode("utf-8")) + b"\x15" + value32
        self._write_event(int(step), _delimited(0x2A, _delimited(0x0A, value_msg)))

    def add_scalars(self, prefix: str, values: Dict[str, float], step: int) -> None:
        for k, v in values.items():
            self.add_scalar(f"{prefix}/{k}", v, step)

    def flush(self) -> None:
        self._file.flush()

    def close(self) -> None:
        self._file.close()
