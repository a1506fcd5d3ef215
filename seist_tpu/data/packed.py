"""Packed-shard dataset: offline HDF5 -> contiguous binary shards.

SURVEY.md §7's input-pipeline mitigation (the ArrayRecord-style offline
repack), built for the measured bottleneck: the r3 loader stage budget
put ~30% of per-sample cost in the read stage — h5py's per-sample
group/dataset lookup and decode — before any augmentation runs
(ref datasets/diting.py:139-142 does one ``grp.get(key)`` per sample;
our reader mirrors it in data/diting.py:103-146).

The repack trades that per-sample API cost for ONE seek-free slice:

* ``shard_XXXXX.bin`` — raw float32 C-order ``(C, L)`` waveforms,
  concatenated. Served through a per-process ``np.memmap`` (page-cache
  backed, zero-copy until the training-path ``.astype`` copy).
* ``shard_XXXXX.idx.npz`` — the shard's columnar sidecar (per-sample
  within-shard byte offset, shape, every Event label field, source id).
  Written atomically AFTER the ``.bin`` — its presence is the
  shard-complete marker the resumable packer keys on.
* ``index.npz`` — the merged columnar metadata (sidecars + a ``shard``
  column), loaded once into the pandas frame that
  :class:`~seist_tpu.data.base.DatasetBase`'s seeded
  shuffle-then-contiguous-split already operates on.
* ``meta.json`` — source dataset name(s), channels, sampling rate,
  counts. Written LAST: a directory without it is an incomplete pack
  and the reader refuses it.

Packing is **plan-first**: the shard partition is a pure function of the
source sizes and ``samples_per_shard`` (derived deterministically from
sample 0 when only ``--shard-mb`` is given), computed before any bytes
move. That buys three properties at once:

* **parallel** — workers own disjoint shard ranges; an N-worker pack is
  bit-identical to a 1-worker pack (pinned by tests/test_packed.py);
* **resumable** — an interrupted pack re-plans identically and skips
  every shard whose sidecar already matches its ``.bin``;
* **mixture** — several registered datasets pack into ONE directory
  (sources occupy consecutive shard ranges; every index row carries a
  ``source_id`` provenance column) for temperature-weighted joint
  training (``pipeline.mixture_epoch_indices``, arXiv:2203.17189).

``pack_dataset`` converts ANY registered dataset (constructed with
``data_split=False, shuffle=False`` so the pack order is the source
metadata order); :class:`PackedDataset` (registered as ``packed``) then
serves the identical Event dicts through the standard reader contract —
same seed => same split as any other dataset.

Label encoding: every current dataset emits 0-or-1-element lists for
ppks/spks/emg/smg/pmp/clr/baz/dis (one event per window — ref
datasets/*.py); the packer asserts that and stores scalar-or-NaN.
"""

from __future__ import annotations

import dataclasses
import json
import os
import zipfile
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pandas as pd

from seist_tpu.data.base import DatasetBase, Event
from seist_tpu.data.io_guard import COUNTERS, CorruptSampleError
from seist_tpu.registry import register_dataset
from seist_tpu.utils.logger import logger

_INDEX = "index.npz"
_META = "meta.json"
_SIDECAR_SUFFIX = ".idx.npz"

# Event fields packed as scalar-or-NaN columns, in a fixed order.
# ppks/spks are sample indices (int at heart, float for the NaN), the
# rest are the label scalars the TaskSpec io catalog consumes.
_SCALAR_FIELDS = ("ppks", "spks", "emg", "smg", "pmp", "clr", "baz", "dis")
_INT_FIELDS = frozenset({"ppks", "spks", "pmp", "clr"})

# Sidecar/index column dtypes (keys excluded; they stay str).
_INT_COLS = (
    "shard", "offset", "n_ch", "n_samp", "source_id",
    "total_bytes", "plan_lo", "plan_hi", "storage_itemsize",
)
# Per-shard bookkeeping columns that never reach the merged index.
_SIDECAR_ONLY = ("total_bytes", "plan_lo", "plan_hi", "storage_itemsize")

# On-disk waveform storage dtypes (``meta.json["dtype"]``). float32 is
# the training-parity default; bfloat16 halves the shard bytes (and
# therefore read bandwidth) for inference-only archives; int8 (format
# v3) quarters them with a per-row per-channel scale sidecar column —
# readers dequantize/upcast to float32 on fill, so every consumer
# downstream of the read stays dtype-blind (the ROADMAP "quantized
# shard variants" item); the direct-ingest path can additionally stage
# int8 rows AS-IS and dequantize on device (data/ingest.py).
_DTYPE_ALIASES = {"fp32": "float32", "bf16": "bfloat16", "i8": "int8"}

#: int8 per-channel scale sidecar columns (format v3): NaN-padded to 3
#: channels exactly like snr_0..2; float packs never carry them, so the
#: v2 index schema is byte-for-byte unchanged.
_SCALE_COLS = ("scale_0", "scale_1", "scale_2")

#: Symmetric int8 quantization never emits -128 (clip to [-127, 127]),
#: so any -128 byte in a shard is out-of-contract — the poison marker
#: the io_guard ladder treats as permanent corruption (int8 rows cannot
#: carry NaN, this is their NaN-poison equivalent).
INT8_POISON = -128


#: Storage of sources whose ``data`` is integer (token sequences): the ids
#: as they are, never a float rendering of them. Not a choice of the caller:
#: :func:`pack_sources` takes it whenever the source's data is integer.
INT_SEQUENCE_DTYPE = "int32"


def canonical_dtype(name: str) -> str:
    name = _DTYPE_ALIASES.get(str(name).lower(), str(name).lower())
    if name not in ("float32", "bfloat16", "int8", INT_SEQUENCE_DTYPE):
        raise ValueError(
            f"unsupported packed storage dtype '{name}' "
            "(use float32, bfloat16 or int8)"
        )
    return name


def storage_dtype(name: str) -> np.dtype:
    """Resolve a pack's on-disk waveform dtype. bfloat16 comes from
    ml_dtypes (a jax dependency), which registers it as a real numpy
    dtype — memmap slices / frombuffer / cast-assignment all work."""
    name = canonical_dtype(name)
    if name == "float32":
        return np.dtype(np.float32)
    if name == INT_SEQUENCE_DTYPE:
        return np.dtype(np.int32)
    if name == "int8":
        return np.dtype(np.int8)
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16)


def quantize_rows(data: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric per-channel int8 quantization of one ``(C, L)`` float32
    waveform: ``scale = max|x| / 127`` (clamped like serve/aot's
    weight quantizer), ``q = clip(round(x / scale), -127, 127)``.
    Returns ``(q int8 (C, L), scale float32 (C,))`` — THE pack-time
    quantizer, shared by the repick engine's parity probe and the
    round-trip tests so tolerances cannot drift from the format."""
    data = np.asarray(data, np.float32)
    scale = (
        np.maximum(np.abs(data).max(axis=1), 1e-8) / 127.0
    ).astype(np.float32)
    q = np.clip(
        np.round(data / scale[:, None]), -127, 127
    ).astype(np.int8)
    return q, scale


class DtypeMixError(ValueError):
    """A pack directory already holds shards across the quantized/float
    boundary from what this run requests. Float<->float resumes repack
    (itemsize is part of the plan identity); int8 packs change the
    SIDECAR SCHEMA too (scale columns), so mixing is refused loudly
    instead of half-rewriting a directory two readers would disagree
    on."""

    def __init__(self, existing: str, requested: str, out_dir: str):
        self.existing = existing
        self.requested = requested
        self.out_dir = out_dir
        super().__init__(
            f"pack dir {out_dir} already holds {existing} shards; "
            f"refusing to mix with --dtype {requested} (int8 packs carry "
            "a scale sidecar column float packs lack). Pack into a fresh "
            "directory, or rewrite this one with --no-resume."
        )


def shard_path(out_dir: str, shard_id: int) -> str:
    return os.path.join(out_dir, f"shard_{shard_id:05d}.bin")


def sidecar_path(out_dir: str, shard_id: int) -> str:
    return shard_path(out_dir, shard_id) + _SIDECAR_SUFFIX


# ------------------------------------------------------------------- planning
@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """One shard's assignment: source ``source_id``'s samples
    ``[lo, hi)`` (source-local indices, source metadata order)."""

    shard_id: int
    source_id: int
    lo: int
    hi: int

    @property
    def n(self) -> int:
        return self.hi - self.lo


def _samples_per_shard(sample_nbytes: int, shard_mb: float) -> int:
    """Deterministic shard capacity from a byte budget: how many sample-0
    sized waveforms fit in ``shard_mb`` (matches the v1 rollover rule for
    uniform-size datasets — every current dataset decodes fixed-length
    traces)."""
    return max(1, int(shard_mb * 1_000_000) // max(int(sample_nbytes), 1))


def plan_shards(
    sources: Sequence[Any],
    *,
    samples_per_shard: Optional[int] = None,
    shard_mb: float = 512,
    dtype: str = "float32",
) -> Tuple[List[ShardPlan], List[int]]:
    """The deterministic shard partition: a pure function of the source
    lengths and the capacity knobs — NEVER of worker count or of which
    shards already exist. Returns ``(plans, per-source capacities)``.

    Sources occupy consecutive shard-id ranges (shards never span
    sources: provenance stays a per-shard constant and workers can own
    contiguous per-source sample ranges). With only ``shard_mb`` given,
    capacity derives PER SOURCE from that source's sample-0 nbytes —
    mixture sources with different trace lengths each honor the byte
    budget; reading one sample per source is the only data the plan
    ever touches."""
    caps: List[int] = []
    for src in sources:
        if samples_per_shard is not None:
            caps.append(max(1, int(samples_per_shard)))
            continue
        event0, _ = src[0]
        nbytes0 = (
            np.ascontiguousarray(event0["data"], dtype=np.float32).size
            * storage_dtype(dtype).itemsize
        )
        caps.append(_samples_per_shard(nbytes0, shard_mb))
    plans: List[ShardPlan] = []
    shard_id = 0
    for source_id, src in enumerate(sources):
        n = len(src)
        sps = caps[source_id]
        for lo in range(0, n, sps):
            plans.append(
                ShardPlan(shard_id, source_id, lo, min(lo + sps, n))
            )
            shard_id += 1
    return plans, caps


# ---------------------------------------------------------------- shard write
def _new_cols(quantized: bool = False) -> Dict[str, list]:
    return {
        **{f: [] for f in _SCALAR_FIELDS},
        "snr_0": [],
        "snr_1": [],
        "snr_2": [],
        **({c: [] for c in _SCALE_COLS} if quantized else {}),
        "offset": [],
        "n_ch": [],
        "n_samp": [],
        "key": [],
    }


def _append_sample(cols: Dict[str, list], event: Event, row: Any, i: int) -> None:
    for f in _SCALAR_FIELDS:
        v = event.get(f, [])
        if len(v) > 1:
            raise ValueError(
                f"event {i}: field {f} has {len(v)} values; the "
                "packed format stores one event per window"
            )
        cols[f].append(float(v[0]) if len(v) else np.nan)
    snr = np.asarray(event.get("snr", []), dtype=np.float64).ravel()
    for c in range(3):
        cols[f"snr_{c}"].append(float(snr[c]) if c < snr.size else np.nan)
    cols["key"].append(str(row.get("key", i)) if isinstance(row, dict) else str(i))


def _col_array(name: str, values: list) -> np.ndarray:
    if name in _INT_COLS:
        return np.asarray(values, np.int64)
    if name == "key":
        return np.asarray(values, str)
    return np.asarray(values, np.float64)


def _write_atomic_npz(path: str, cols: Dict[str, Any]) -> None:
    # Suffix .npz so np.savez doesn't append one of its own.
    tmp = path + ".tmp.npz"
    np.savez(tmp, **{k: _col_array(k, v) for k, v in cols.items()})
    os.replace(tmp, path)


def pack_shard(
    src, out_dir: str, plan: ShardPlan, *, dtype: str = "float32"
) -> Dict[str, int]:
    """Pack ONE shard: the plan's sample range streamed into
    ``shard_XXXXX.bin`` (via a ``.tmp`` rename) followed by its sidecar —
    the sidecar rename is the shard-complete commit point, so a kill at
    any instant leaves either a complete shard or a resumable hole."""
    store_dt = storage_dtype(dtype)
    quantized = store_dt == np.int8
    cols = _new_cols(quantized)
    total = 0
    bin_path = shard_path(out_dir, plan.shard_id)
    tmp_bin = bin_path + ".tmp"
    try:
        with open(tmp_bin, "wb") as f:
            for j in range(plan.lo, plan.hi):
                event, row = src[j]
                data = np.ascontiguousarray(
                    event["data"],
                    dtype=np.int32 if store_dt == np.int32 else np.float32,
                )
                if data.ndim != 2:
                    raise ValueError(
                        f"event {j}: data must be (C, L), got {data.shape}"
                    )
                if quantized:
                    if data.shape[0] > len(_SCALE_COLS):
                        raise ValueError(
                            f"event {j}: int8 packs support up to "
                            f"{len(_SCALE_COLS)} channels (scale sidecar "
                            f"columns), got {data.shape[0]}"
                        )
                    data, scale = quantize_rows(data)
                    for c in range(len(_SCALE_COLS)):
                        cols[f"scale_{c}"].append(
                            float(scale[c]) if c < scale.size else np.nan
                        )
                elif store_dt not in (np.float32, np.int32):
                    data = data.astype(store_dt)
                f.write(data.tobytes())
                _append_sample(cols, event, row, j)
                cols["offset"].append(total)
                cols["n_ch"].append(data.shape[0])
                cols["n_samp"].append(data.shape[1])
                total += data.nbytes
    except BaseException:
        # A failed/interrupted shard must not leave a .tmp that a later
        # resume could mistake for progress (it can't — only the sidecar
        # commits a shard — but don't litter the pack dir either).
        try:
            os.unlink(tmp_bin)
        except OSError:
            pass
        raise
    os.replace(tmp_bin, bin_path)
    cols["source_id"] = [plan.source_id] * plan.n
    cols["total_bytes"] = [total]
    # Plan identity: lets shard_complete refuse a resume whose re-plan
    # assigns this shard a different sample range (source count/order or
    # capacity knobs changed). NOTE an in-place content change of the
    # SOURCE with identical sizes is undetectable without re-reading it
    # — resume assumes immutable sources; use --no-resume after editing
    # a source in place (docs/DATA.md).
    cols["plan_lo"] = [plan.lo]
    cols["plan_hi"] = [plan.hi]
    # Storage dtype is part of the plan identity too: a resume that
    # switches --dtype must repack, not silently mix itemsizes.
    cols["storage_itemsize"] = [store_dt.itemsize]
    _write_atomic_npz(sidecar_path(out_dir, plan.shard_id), cols)
    return {"samples": plan.n, "bytes": total}


def shard_complete(
    out_dir: str, plan: ShardPlan, *, dtype: str = "float32"
) -> bool:
    """A shard is complete iff its sidecar exists, describes the plan's
    sample count AND storage dtype, and the ``.bin`` on disk has exactly
    the byte length the sidecar recorded (a truncated bin from a crashed
    ``os.replace`` window, a re-plan with different capacity, or a resume
    with a different ``--dtype`` all fail this)."""
    side = sidecar_path(out_dir, plan.shard_id)
    bin_p = shard_path(out_dir, plan.shard_id)
    if not (os.path.exists(side) and os.path.exists(bin_p)):
        return False
    try:
        with np.load(side, allow_pickle=False) as z:
            total = int(z["total_bytes"][0])
            n = int(z["offset"].shape[0])
            source_id = int(z["source_id"][0]) if n else plan.source_id
            lo = int(z["plan_lo"][0])
            hi = int(z["plan_hi"][0])
            # Pre-dtype sidecars are all float32 packs.
            itemsize = (
                int(z["storage_itemsize"][0])
                if "storage_itemsize" in z.files
                else 4
            )
    except (OSError, ValueError, KeyError, zipfile.BadZipFile):
        # A torn/garbled sidecar (np.load raises BadZipFile), or one
        # from a pre-plan-identity pack, is just an incomplete shard:
        # repack it.
        return False
    return (
        n == plan.n
        and source_id == plan.source_id
        and (lo, hi) == (plan.lo, plan.hi)
        and itemsize == storage_dtype(dtype).itemsize
        and os.path.getsize(bin_p) == total
    )


# --------------------------------------------------------------- orchestration
@dataclasses.dataclass
class PackSource:
    """One pack input: either a live dataset instance or a registered
    dataset spec (name + data_dir + kwargs) that every pack worker can
    construct for itself. Spec-based sources are what the CLI builds;
    live instances serve in-process callers and tests."""

    name: str = ""
    data_dir: str = ""
    dataset_kwargs: Optional[dict] = None
    dataset: Any = None

    def create(self) -> Any:
        if self.dataset is not None:
            return self.dataset
        from seist_tpu.registry import DATASETS

        # Pack order must be the source metadata order: no shuffle, no
        # split (the packed reader applies the standard seeded
        # shuffle/split itself — same seed => same split as the source).
        self.dataset = DATASETS.create(
            self.name,
            seed=0,
            mode="train",
            data_dir=self.data_dir,
            shuffle=False,
            data_split=False,
            **(self.dataset_kwargs or {}),
        )
        return self.dataset


_POOL_SOURCES: Optional[List[Any]] = None


def _pack_pool_init(sources: List[PackSource]) -> None:
    global _POOL_SOURCES
    import seist_tpu.data  # noqa: F401  (dataset registrations)

    _POOL_SOURCES = [s.create() for s in sources]


def _pack_pool_shard(job: Tuple[str, ShardPlan, str]) -> Dict[str, int]:
    out_dir, plan, dtype = job
    return pack_shard(
        _POOL_SOURCES[plan.source_id], out_dir, plan, dtype=dtype
    )


def merge_index(
    out_dir: str, plans: Sequence[ShardPlan]
) -> Dict[str, np.ndarray]:
    """Concatenate every sidecar (in shard order) into ``index.npz``
    with the per-row ``shard`` column added. Returns the merged columns."""
    merged: Dict[str, List[Any]] = {}
    for plan in plans:
        with np.load(
            sidecar_path(out_dir, plan.shard_id), allow_pickle=False
        ) as z:
            for k in z.files:
                if k in _SIDECAR_ONLY:
                    continue
                merged.setdefault(k, []).append(z[k])
            merged.setdefault("shard", []).append(
                np.full(plan.n, plan.shard_id, np.int64)
            )
    arrays = {k: np.concatenate(v) for k, v in merged.items()}
    _write_atomic_npz(os.path.join(out_dir, _INDEX), arrays)
    return arrays


def _existing_pack_dtype(out_dir: str) -> Optional[str]:
    """Best-effort canonical dtype of whatever already lives in
    ``out_dir``: meta.json when the pack committed, else the first
    complete sidecar (an interrupted pack has no meta yet). None when
    the directory holds no pack artifacts."""
    meta_p = os.path.join(out_dir, _META)
    if os.path.exists(meta_p):
        try:
            with open(meta_p) as f:
                return canonical_dtype(
                    json.load(f).get("dtype", "float32")
                )
        except (OSError, ValueError, KeyError):
            return None
    try:
        sidecars = sorted(
            f for f in os.listdir(out_dir) if f.endswith(_SIDECAR_SUFFIX)
        )
    except OSError:
        return None
    for name in sidecars:
        try:
            with np.load(
                os.path.join(out_dir, name), allow_pickle=False
            ) as z:
                if "scale_0" in z.files:
                    return "int8"
                itemsize = (
                    int(z["storage_itemsize"][0])
                    if "storage_itemsize" in z.files
                    else 4
                )
            return {1: "int8", 2: "bfloat16"}.get(itemsize, "float32")
        except (OSError, ValueError, KeyError, zipfile.BadZipFile):
            continue
    return None


def pack_sources(
    sources: Sequence[PackSource],
    out_dir: str,
    *,
    num_workers: int = 0,
    samples_per_shard: Optional[int] = None,
    shard_mb: float = 512,
    resume: bool = True,
    dtype: str = "float32",
) -> Dict[str, Any]:
    """Pack one or more sources into ``out_dir`` (the parallel,
    resumable, mixture-capable path behind both :func:`pack_dataset` and
    ``python -m tools.pack_dataset``). Returns the stats dict the CLI
    prints as its JSON verdict."""
    from seist_tpu.obs.bus import monotonic

    dtype = canonical_dtype(dtype)
    t0 = monotonic()
    os.makedirs(out_dir, exist_ok=True)
    if resume:
        existing = _existing_pack_dtype(out_dir)
        if existing is not None and (existing == "int8") != (
            dtype == "int8"
        ):
            raise DtypeMixError(existing, dtype, out_dir)
    datasets = [s.create() for s in sources]
    if np.asarray(datasets[0][0][0]["data"]).dtype.kind in "iu":
        dtype = INT_SEQUENCE_DTYPE  # integer sequences are stored as such
    channels = list(datasets[0].channels())
    fs = int(datasets[0].sampling_rate())
    for ds in datasets[1:]:
        if list(ds.channels()) != channels or int(ds.sampling_rate()) != fs:
            raise ValueError(
                "mixture sources must share channels and sampling rate: "
                f"{ds.name()} has ({ds.channels()}, {ds.sampling_rate()}) "
                f"vs ({channels}, {fs})"
            )
    plans, caps = plan_shards(
        datasets, samples_per_shard=samples_per_shard, shard_mb=shard_mb,
        dtype=dtype,
    )
    todo = [
        p for p in plans
        if not (resume and shard_complete(out_dir, p, dtype=dtype))
    ]
    skipped = len(plans) - len(todo)
    if skipped:
        logger.info(
            f"pack resume: {skipped}/{len(plans)} shard(s) already "
            f"complete in {out_dir}; packing the remaining {len(todo)}"
        )

    stats = {"samples": 0, "bytes": 0}
    if todo:
        if num_workers and num_workers > 1:
            import multiprocessing
            from concurrent.futures import ProcessPoolExecutor

            # forkserver/spawn, never fork: pack may run inside a
            # JAX-initialized parent (pipeline.py has the full rationale).
            try:
                ctx = multiprocessing.get_context("forkserver")
            except ValueError:
                ctx = multiprocessing.get_context("spawn")
            # Spec-based sources are shipped as specs (workers rebuild
            # them), not as the parent's live instances — a live reader
            # can hold unpicklable/expensive state (e.g. a PackedDataset
            # source's cached memmap pickles as the whole shard).
            ship = [
                dataclasses.replace(s, dataset=None) if s.name else s
                for s in sources
            ]
            with ProcessPoolExecutor(
                max_workers=num_workers,
                mp_context=ctx,
                initializer=_pack_pool_init,
                initargs=(ship,),
            ) as pool:
                for out in pool.map(
                    _pack_pool_shard, [(out_dir, p, dtype) for p in todo]
                ):
                    stats["samples"] += out["samples"]
                    stats["bytes"] += out["bytes"]
        else:
            for plan in todo:
                out = pack_shard(
                    datasets[plan.source_id], out_dir, plan, dtype=dtype
                )
                stats["samples"] += out["samples"]
                stats["bytes"] += out["bytes"]

    arrays = merge_index(out_dir, plans)
    n_total = int(arrays["offset"].shape[0])
    meta = {
        "source": (
            datasets[0].name()
            if len(datasets) == 1
            else "mixture:" + "+".join(ds.name() for ds in datasets)
        ),
        "channels": channels,
        "sampling_rate": fs,
        "n_events": n_total,
        "n_shards": len(plans),
        # v3 = int8 waveforms + scale sidecar columns; float packs stay
        # v2 so every pre-int8 reader keeps accepting them unchanged.
        "format_version": 3 if dtype == "int8" else 2,
        "dtype": dtype,
        "samples_per_shard": caps[0] if len(set(caps)) == 1 else caps,
        "sources": [
            {
                "source_id": sid,
                "name": ds.name(),
                "data_dir": getattr(sources[sid], "data_dir", ""),
                "n_events": len(ds),
                "samples_per_shard": caps[sid],
            }
            for sid, ds in enumerate(datasets)
        ],
    }
    # meta.json LAST — its presence is the whole-pack commit point.
    tmp = os.path.join(out_dir, _META + ".tmp")
    with open(tmp, "w") as f:
        json.dump(meta, f)
    os.replace(tmp, os.path.join(out_dir, _META))
    wall_s = monotonic() - t0
    logger.info(
        f"packed {n_total} events into {len(plans)} shard(s) at {out_dir} "
        f"({skipped} resumed, {wall_s:.1f}s)"
    )
    # On-disk accounting for the dtype ladder verdict: actual shard
    # bytes vs what the same event set costs at fp32 (the ISSUE 18
    # bytes<=0.55x acceptance is measured here, not asserted).
    on_disk = sum(
        os.path.getsize(shard_path(out_dir, p.shard_id)) for p in plans
    )
    fp32_bytes = int((arrays["n_ch"] * arrays["n_samp"]).sum()) * 4
    return {
        "out": out_dir,
        "dtype": dtype,
        "shards": len(plans),
        "shards_skipped": skipped,
        "samples": n_total,
        "samples_packed": stats["samples"],
        "bytes": stats["bytes"],
        "on_disk_bytes": on_disk,
        "bytes_per_row": round(on_disk / max(n_total, 1), 1),
        "fp32_bytes_per_row": round(fp32_bytes / max(n_total, 1), 1),
        "bytes_vs_fp32": round(on_disk / max(fp32_bytes, 1), 4),
        "samples_per_shard": meta["samples_per_shard"],
        "sources": [s["name"] for s in meta["sources"]],
        "wall_s": round(wall_s, 2),
    }


def pack_dataset(
    src,
    out_dir: str,
    *,
    shard_mb: float = 512,
    samples_per_shard: Optional[int] = None,
    num_workers: int = 0,
    dtype: str = "float32",
    log_every: int = 0,  # kept for call-site compat; progress is per shard
) -> str:
    """Repack ``src`` (any DatasetBase, pre-split disabled) into packed
    shards under ``out_dir``. Returns ``out_dir``."""
    del log_every
    pack_sources(
        [PackSource(dataset=src)],
        out_dir,
        num_workers=num_workers,
        samples_per_shard=samples_per_shard,
        shard_mb=shard_mb,
        dtype=dtype,
    )
    return out_dir


def read_waveform_slice(
    mmaps: Dict[int, np.memmap],
    data_dir: str,
    shard: int,
    off: int,
    nbytes: int,
    *,
    desc: str,
) -> np.ndarray:
    """THE raw-slice fault ladder for packed shards, shared by the Event
    reader (:class:`PackedDataset`) and the direct-ingest store
    (data/ingest.py) so their io_guard classification can never diverge:
    per-shard memmaps cached in ``mmaps``; ``OSError`` (shard vanished /
    page-in failure on a network mount) evicts the cached map — counted
    as ``reopens``, same telemetry as evict_h5 — and re-raises as a
    TRANSIENT fault (the retry re-mmaps a fresh fd); a slice that comes
    back short means the shard file is truncated — PERMANENT corruption
    (:class:`CorruptSampleError`). Returns the uint8 slice view."""
    mm = mmaps.get(shard)
    if mm is None:
        mm = mmaps[shard] = np.memmap(
            shard_path(data_dir, shard), dtype=np.uint8, mode="r"
        )
    try:
        raw = mm[off : off + nbytes]
    except OSError:
        if mmaps.pop(shard, None) is not None:
            COUNTERS.inc("reopens")
        raise
    if raw.size != nbytes:
        raise CorruptSampleError(
            f"{desc}: short read in shard {shard} (want {nbytes} bytes "
            f"at {off}, got {raw.size} — truncated shard?)"
        )
    return raw


class PackedDataset(DatasetBase):
    """Reader for :func:`pack_dataset` output (registered as ``packed``).

    Same metadata/split/Event contract as every other dataset; the
    waveform read is a single memmap slice + one ``.astype`` copy
    instead of h5py's per-sample group walk."""

    _name = "packed"

    def __init__(self, **kwargs):
        data_dir = kwargs.get("data_dir", "")
        with open(os.path.join(data_dir, _META)) as f:
            self._meta = json.load(f)
        # Pre-dtype packs (and every v1 pack) stored float32.
        self._storage_dtype = storage_dtype(
            self._meta.get("dtype", "float32")
        )
        self._mmaps: Dict[int, np.memmap] = {}
        super().__init__(**kwargs)

    # Instance-level overrides of the classmethod accessors: the values
    # come from meta.json, not the class.
    def name(self):  # type: ignore[override]
        return self._name

    def __repr__(self) -> str:
        return (
            f"Dataset(name:packed, source:{self._meta['source']}, "
            f"channels:{self._meta['channels']}, "
            f"sampling_rate:{self._meta['sampling_rate']}, "
            f"n_events:{self._meta['n_events']}, "
            f"n_shards:{self._meta['n_shards']}, "
            f"data_dir:{self._data_dir}, mode:{self._mode})"
        )

    def channels(self):  # type: ignore[override]
        return list(self._meta["channels"])

    def sampling_rate(self):  # type: ignore[override]
        return int(self._meta["sampling_rate"])

    @property
    def storage_dtype(self) -> np.dtype:
        """On-disk waveform dtype (readers upcast to float32 on read)."""
        return self._storage_dtype

    def sources(self) -> List[Dict[str, Any]]:
        """Provenance of a mixture pack (one entry per source; v1 packs
        report their single source)."""
        return list(
            self._meta.get(
                "sources",
                [{"source_id": 0, "name": self._meta["source"],
                  "n_events": self._meta["n_events"]}],
            )
        )

    def source_ids(self) -> Optional[np.ndarray]:
        """Per-sample source id (THIS split's row order) when the pack
        holds a mixture; ``None`` for single-source packs — the signal
        ``pipeline``'s temperature-weighted sampler keys on."""
        if len(self.sources()) <= 1 or "source_id" not in self._meta_data:
            return None
        return self._meta_data["source_id"].to_numpy()

    def _load_meta_data(self) -> pd.DataFrame:
        with np.load(
            os.path.join(self._data_dir, _INDEX), allow_pickle=False
        ) as z:
            frame = pd.DataFrame({k: z[k] for k in z.files})
        if len(frame) != self._meta["n_events"]:
            raise ValueError(
                f"index has {len(frame)} rows, meta.json says "
                f"{self._meta['n_events']}"
            )
        return self._shuffle_and_split(frame)

    # Instances cross process boundaries (process-pool loader workers,
    # shard-parallel pack workers). A cached np.memmap pickles as a FULL
    # ndarray — the entire shard's bytes per worker — so ship the state
    # without the maps; workers re-mmap lazily on first read.
    def __getstate__(self) -> Dict[str, Any]:
        state = dict(self.__dict__)
        state["_mmaps"] = {}
        return state

    def _load_event_data(self, idx: int) -> Tuple[Event, dict]:
        row = self._row_dict(idx)
        c, length = int(row["n_ch"]), int(row["n_samp"])
        raw = read_waveform_slice(
            self._mmaps,
            self._data_dir,
            int(row["shard"]),
            int(row["offset"]),
            c * length * self._storage_dtype.itemsize,
            desc=f"packed (sample {idx})",
        )
        # .astype always copies — bf16 packs upcast, f32 packs keep the
        # original copy-out-of-the-memmap semantics.
        data = np.frombuffer(raw, dtype=self._storage_dtype).reshape(c, length)
        # .astype always copies; integer sequences stay integers
        data = data.astype(
            np.int32 if self._storage_dtype == np.int32 else np.float32)
        if self._storage_dtype == np.int8:
            # Format v3 host-path dequant. int8 rows cannot carry NaN,
            # so their poison markers are the out-of-contract -128 byte
            # and a non-finite sidecar scale — both permanent corruption
            # through the same io_guard ladder as a NaN-poisoned float
            # row.
            scale = np.array(
                [row[f"scale_{ch}"] for ch in range(c)], np.float32
            )
            if data.min() <= INT8_POISON:
                raise CorruptSampleError(
                    f"packed (sample {idx}): int8 row holds the "
                    f"out-of-contract {INT8_POISON} byte (poisoned?)"
                )
            if not np.isfinite(scale).all():
                raise CorruptSampleError(
                    f"packed (sample {idx}): non-finite int8 scale "
                    f"{scale.tolist()}"
                )
            data *= scale[:, None]

        def scalar(field):
            v = row[field]
            if v != v:  # NaN
                return []
            return [int(v)] if field in _INT_FIELDS else [np.float32(v)]

        event: Event = {"data": data}
        for f in _SCALAR_FIELDS:
            event[f] = scalar(f)
        event["snr"] = np.array(
            [row["snr_0"], row["snr_1"], row["snr_2"]]
        )
        return event, row


@register_dataset
def packed(**kwargs):
    return PackedDataset(**kwargs)
