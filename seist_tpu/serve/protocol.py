"""Wire protocol for the serve subsystem: request parsing, response
shaping, and the error classes the HTTP front-end maps to status codes.

Everything is plain JSON over stdlib types — no new dependencies. A
predict request body is::

    {"model": "seist_s_dpk",              # optional when one model loaded
     "data": [[...], ...],                # (C, L) or (L, C) floats
     "options": {"ppk_threshold": 0.3, "spk_threshold": 0.3,
                 "det_threshold": 0.5, "min_peak_dist": 1.0,
                 "sampling_rate": 50, "norm_mode": "std",
                 "timeout_ms": 2000}}

Multi-task fan-out (``model`` names a task GROUP served with
``--model-group``, e.g. ``seist_s``)::

    {"model": "seist_s", "tasks": ["dpk", "emg", "dis"],  # default: all
     "data": [[...], ...],
     "options": {"variant": "bf16"}}      # fp32 (default) | bf16 | int8

and the response carries one entry per requested head::

    {"model": "seist_s", "trunk_runs": 1,
     "tasks": {"dpk": {...picks...}, "emg": {...}, "dis": {...}}}

The single-task request/response shape above is unchanged (PR 1 wire
compatibility); ``tasks`` on a single-task model is a 400.

``data`` orientation is resolved against the model's channel count (the
same (C, L)/(L, C) tolerance as tools/predict.py); windows shorter than
the model's compiled window are right-padded with zeros AFTER
normalization (so padding never shifts the z-score), longer ones are
rejected toward ``POST /annotate`` which exists precisely for long
records.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import numpy as np


class ServeError(Exception):
    """Base service error; ``status`` is the HTTP status it maps to."""

    status = 500
    code = "internal"

    def payload(self) -> Dict[str, Any]:
        return {"error": self.code, "message": str(self)}

    def headers(self) -> Dict[str, str]:
        """Extra HTTP response headers (e.g. Retry-After for shedding)."""
        return {}


class BadRequest(ServeError):
    status = 400
    code = "bad_request"


class UnknownModel(ServeError):
    status = 404
    code = "unknown_model"


class QueueFull(ServeError):
    """Bounded-queue backpressure — the 429 the ISSUE's '429-style
    rejection' refers to. Clients should retry with backoff."""

    status = 429
    code = "queue_full"


class DeadlineExceeded(ServeError):
    status = 504
    code = "deadline_exceeded"


class ShuttingDown(ServeError):
    """The replica is draining (SIGTERM latch) or its stream mux has
    been closed (``MuxClosed``) — nothing here is wrong with the
    request. 503 is deliberate: the router treats it as retryable, so
    an in-flight ``/stream`` packet re-routes to a surviving replica,
    which restores the station's session from its journal (or
    gap-stitches a fresh one). The failover handoff IS this status
    code."""

    status = 503
    code = "shutting_down"


class IncompatibleCheckpoint(ServeError):
    """The checkpoint's param tree does not fit the target model config
    (missing/extra keys, shape or dtype mismatch). Raised by the loader
    BEFORE any swap/serving, naming the first mismatching path — without
    this, a wrong-architecture checkpoint surfaces as a deep flax apply
    traceback mid-request."""

    status = 400
    code = "incompatible_checkpoint"


class ReloadFailed(ServeError):
    """A hot reload (``POST /admin/reload``) was rejected or died before
    the atomic swap: the incumbent entry keeps serving, unchanged. 409:
    the request was well-formed, the candidate just didn't earn the
    traffic (the "disable, don't serve wrong" contract applied to
    reload)."""

    status = 409
    code = "reload_failed"


class ParityGateFailed(ReloadFailed):
    """A reload candidate failed the load-time acceptance gates (variant
    parity vs fp32, or the fp32 finite-output probe). Same 409 contract
    as :class:`ReloadFailed` with the gate verdict in the message."""

    code = "parity_gate_failed"


#: Priority tiers, highest first. Order IS the shed order reversed:
#: ``batch`` (backfill) is dropped first under overload, ``alert``
#: (streaming early-warning picks — a missed one is a missed event) last.
#: The numeric level is what serve/shed.py compares thresholds against.
PRIORITIES = {"alert": 0, "interactive": 1, "batch": 2}
DEFAULT_PRIORITY = "interactive"

#: Serving weight variants (serve/aot.py builds + parity-gates them):
#: fp32 = the checkpoint as restored; bf16 = weights+activations cast;
#: int8 = weight-only quantization. Selected per request via
#: ``options.variant``; a variant a model/task wasn't loaded (or failed
#: its parity gate) for is a 400.
VARIANTS = ("fp32", "bf16", "int8")
DEFAULT_VARIANT = "fp32"


class Overloaded(ServeError):
    """Adaptive load shedding (serve/shed.py): the replica's queue delay
    says this request's tier cannot be served within its latency budget.
    Distinct from QueueFull's 429 (a hard bounded-queue bounce) — this is
    a *policy* drop of a low tier, delivered as 503 + Retry-After so
    well-behaved batch clients back off for a computed interval while
    alert traffic keeps flowing."""

    status = 503
    code = "shed"

    def __init__(self, message: str, retry_after_s: float = 1.0):
        super().__init__(message)
        # No floor here: the shed policy (ShedConfig.min_retry_after_s)
        # owns the minimum — clamping again would silently override a
        # sub-second operator setting. Only guard against negatives.
        self.retry_after_s = max(0.0, float(retry_after_s))

    def payload(self) -> Dict[str, Any]:
        p = super().payload()
        p["retry_after_s"] = round(self.retry_after_s, 1)
        return p

    def headers(self) -> Dict[str, str]:
        # Retry-After is delta-seconds, integral per RFC 9110.
        return {"Retry-After": str(int(math.ceil(self.retry_after_s)))}


@dataclass
class PredictOptions:
    """Per-request knobs; defaults mirror cli.py's eval flags."""

    ppk_threshold: float = 0.3
    spk_threshold: float = 0.3
    det_threshold: float = 0.5
    min_peak_dist: float = 1.0  # seconds
    sampling_rate: int = 50
    norm_mode: str = "std"
    max_events: int = 8
    timeout_ms: float = 5000.0
    priority: str = DEFAULT_PRIORITY  # admission tier (serve/shed.py)
    variant: str = DEFAULT_VARIANT  # weight variant (serve/aot.py)
    # /annotate only:
    stride: int = 0  # 0 = window // 2
    combine: str = "max"
    record_max_events: int = 0  # 0 = scale with record length

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "PredictOptions":
        d = dict(d or {})
        unknown = set(d) - set(cls.__dataclass_fields__)
        if unknown:
            raise BadRequest(f"unknown options: {sorted(unknown)}")
        int_fields = ("sampling_rate", "max_events", "stride",
                      "record_max_events")
        for key, value in d.items():
            if key in ("norm_mode", "combine", "priority", "variant"):
                if not isinstance(value, str):
                    raise BadRequest(f"option '{key}' must be a string")
                continue
            if isinstance(value, bool) or not isinstance(value, (int, float)):
                # bool is an int subclass; a JSON true/false here is a
                # client bug, not a number.
                raise BadRequest(
                    f"option '{key}' must be a number, "
                    f"got {type(value).__name__}"
                )
            if not math.isfinite(value):
                # json.loads accepts NaN/Infinity; NaN would sail through
                # every range check below (all comparisons are False).
                raise BadRequest(f"option '{key}' must be finite")
            if key in int_fields:
                if float(value) != int(value):
                    raise BadRequest(
                        f"option '{key}' must be an integer, got {value}"
                    )
                d[key] = int(value)
        try:
            opts = cls(**d)
        except (TypeError, ValueError) as e:
            raise BadRequest(f"bad options: {e}") from None
        # Range checks: a negative timeout_ms would otherwise turn
        # lock.acquire()/Event.wait() timeouts into unbounded waits or
        # ValueErrors deep in the service (500s instead of 400s).
        if opts.timeout_ms <= 0:
            raise BadRequest(f"timeout_ms must be > 0, got {opts.timeout_ms}")
        if opts.sampling_rate <= 0:
            raise BadRequest(
                f"sampling_rate must be > 0, got {opts.sampling_rate}"
            )
        if opts.min_peak_dist < 0:
            raise BadRequest(
                f"min_peak_dist must be >= 0, got {opts.min_peak_dist}"
            )
        if opts.max_events < 1:
            raise BadRequest(f"max_events must be >= 1, got {opts.max_events}")
        if opts.stride < 0 or opts.record_max_events < 0:
            raise BadRequest("stride and record_max_events must be >= 0")
        if opts.combine not in ("max", "mean"):
            raise BadRequest(
                f"combine must be 'max' or 'mean', got '{opts.combine}'"
            )
        if opts.priority not in PRIORITIES:
            raise BadRequest(
                f"priority must be one of {sorted(PRIORITIES)}, "
                f"got '{opts.priority}'"
            )
        if opts.variant not in VARIANTS:
            raise BadRequest(
                f"variant must be one of {list(VARIANTS)}, "
                f"got '{opts.variant}'"
            )
        return opts


def parse_tasks(obj: Any) -> Optional[Tuple[str, ...]]:
    """Validate a request's ``tasks`` field: a non-empty list of unique
    task-name strings (which tasks EXIST is the pool entry's call —
    ``resolve_tasks``); ``None`` passes through (single-task request /
    group default = all its tasks)."""
    if obj is None:
        return None
    if not isinstance(obj, (list, tuple)) or not obj:
        raise BadRequest(
            "'tasks' must be a non-empty list of task names, "
            f"got {type(obj).__name__}"
        )
    out = []
    for t in obj:
        if not isinstance(t, str):
            raise BadRequest(
                f"'tasks' entries must be strings, got {type(t).__name__}"
            )
        if t in out:
            raise BadRequest(f"duplicate task '{t}' in 'tasks'")
        out.append(t)
    return tuple(out)


def parse_body(raw: bytes) -> Dict[str, Any]:
    try:
        body = json.loads(raw.decode("utf-8"))
    except (ValueError, UnicodeDecodeError) as e:
        raise BadRequest(f"body is not valid JSON: {e}") from None
    if not isinstance(body, dict):
        raise BadRequest(f"body must be a JSON object, got {type(body).__name__}")
    return body


def parse_waveform(obj: Any, in_channels: int) -> np.ndarray:
    """JSON nested lists -> (L, C) float32, resolving (C, L) vs (L, C) by
    the model's channel count (ambiguous square inputs read as (L, C))."""
    try:
        arr = np.asarray(obj, dtype=np.float32)
    except (ValueError, TypeError) as e:
        raise BadRequest(f"'data' is not a numeric array: {e}") from None
    if arr.ndim != 2:
        raise BadRequest(f"'data' must be 2-D, got shape {arr.shape}")
    if arr.shape[1] == in_channels:
        pass  # already (L, C)
    elif arr.shape[0] == in_channels:
        arr = arr.T
    else:
        raise BadRequest(
            f"'data' shape {arr.shape} has no axis of {in_channels} channels"
        )
    if not np.all(np.isfinite(arr)):
        raise BadRequest("'data' contains non-finite values")
    return arr


_STATION_FIELDS = {"id", "network", "lat", "lon"}


def parse_station(obj: Any, required: bool = False) -> Optional[Dict[str, Any]]:
    """Validate a request's ``station`` metadata block: ``{"id": str,
    "network": str?, "lat": float?, "lon": float?}``. ``id`` is
    mandatory inside the block; ``lat``/``lon`` must come together (a
    lone coordinate cannot place a station, and the associator needs
    both or neither). Returns a normalized dict, or None when the block
    is absent and not required."""
    if obj is None:
        if required:
            raise BadRequest("'station' metadata is required: {'id': ...}")
        return None
    if not isinstance(obj, dict):
        raise BadRequest(
            f"'station' must be an object, got {type(obj).__name__}"
        )
    unknown = set(obj) - _STATION_FIELDS
    if unknown:
        raise BadRequest(f"unknown station fields: {sorted(unknown)}")
    sid = obj.get("id")
    if not isinstance(sid, str) or not sid:
        raise BadRequest("'station.id' must be a non-empty string")
    if len(sid) > 64:
        # Journal filenames slug the id (stream/journal.py) and router
        # affinity hashes it; a bounded id keeps slugs collision-free
        # and is far beyond any real SEED/FDSN station code.
        raise BadRequest("'station.id' must be <= 64 characters")
    out: Dict[str, Any] = {"id": sid, "network": ""}
    net = obj.get("network")
    if net is not None:
        if not isinstance(net, str):
            raise BadRequest("'station.network' must be a string")
        out["network"] = net
    lat, lon = obj.get("lat"), obj.get("lon")
    if (lat is None) != (lon is None):
        raise BadRequest("'station.lat' and 'station.lon' must come together")
    if lat is not None:
        for key, val in (("lat", lat), ("lon", lon)):
            if isinstance(val, bool) or not isinstance(val, (int, float)) \
                    or not math.isfinite(val):
                raise BadRequest(f"'station.{key}' must be a finite number")
        if not -90.0 <= float(lat) <= 90.0:
            raise BadRequest("'station.lat' out of range [-90, 90]")
        if not -180.0 <= float(lon) <= 360.0:
            raise BadRequest("'station.lon' out of range [-180, 360]")
        out["lat"], out["lon"] = float(lat), float(lon)
    return out


def json_bytes(payload: Dict[str, Any]) -> bytes:
    return json.dumps(payload, default=_jsonable).encode("utf-8")


def _jsonable(x: Any):
    if isinstance(x, (np.integer,)):
        return int(x)
    if isinstance(x, (np.floating,)):
        return float(x)
    if isinstance(x, np.ndarray):
        return x.tolist()
    raise TypeError(f"not JSON-serializable: {type(x)}")
