"""Packed-shard dataset (seist_tpu/data/packed.py): conversion fidelity,
split contract, and pipeline integration.

SURVEY §7's offline input-pipeline mitigation: tools/pack_dataset.py
repacks an HDF5 dataset into contiguous binary shards + columnar index;
the ``packed`` dataset then serves the identical Event dicts through a
memmap slice instead of h5py's per-sample group walk (the measured ~30%
read tax).
"""

import json
import os

import numpy as np
import pytest

import seist_tpu
from seist_tpu.data.packed import (
    PackedDataset,
    PackSource,
    pack_dataset,
    pack_sources,
    sidecar_path,
    shard_path,
)
from seist_tpu.registry import DATASETS

seist_tpu.load_all()

N_EVENTS = 24
L_TRACE = 1024


@pytest.fixture(scope="module")
def packed_pair(tmp_path_factory):
    """(source diting_light dataset, packed dir) over the same fixture.
    Tiny shard budget forces multiple shards (multi-shard indexing
    covered, not just the single-file happy path)."""
    from tests.conftest import make_packed_dir

    return make_packed_dir(
        tmp_path_factory,
        n_events=N_EVENTS,
        trace_samples=L_TRACE,
        shard_mb=0.05,
    )


def test_pack_roundtrip_events_identical(packed_pair):
    src, out = packed_pair
    dst = PackedDataset(
        seed=0, mode="train", data_dir=out, shuffle=False, data_split=False
    )
    assert len(dst) == len(src) == N_EVENTS
    n_shards = len(
        [f for f in os.listdir(out) if f.startswith("shard_")]
    )
    assert n_shards > 1  # shard_mb=1 must have rolled over
    for i in range(len(src)):
        ev_s, _ = src[i]
        ev_p, row_p = dst[i]
        np.testing.assert_array_equal(ev_p["data"], ev_s["data"])
        assert ev_p["data"].dtype == np.float32
        for f in ("ppks", "spks", "emg", "smg", "pmp", "clr", "baz", "dis"):
            got, want = ev_p[f], ev_s[f]
            assert len(got) == len(want), (i, f, got, want)
            if want:
                np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(ev_p["snr"], float),
            np.asarray(ev_s["snr"], float),
            rtol=1e-6,
        )
        assert "key" in row_p  # ResultSaver metadata passthrough


def test_packed_split_matches_source_split(packed_pair):
    # Pack order == source metadata order, and both readers apply the
    # SAME seeded shuffle-then-contiguous-split (data/base.py) — so for
    # a given seed the packed train split serves the same events as the
    # source train split, event for event.
    src_dir = packed_pair[0]._data_dir
    _, out = packed_pair
    for mode in ("train", "val", "test"):
        a = DATASETS.create(
            "diting_light", seed=11, mode=mode, data_dir=src_dir
        )
        b = DATASETS.create("packed", seed=11, mode=mode, data_dir=out)
        assert len(a) == len(b) > 0
        ev_a, _ = a[0]
        ev_b, _ = b[0]
        np.testing.assert_array_equal(ev_b["data"], ev_a["data"])


def test_packed_through_pipeline(packed_pair):
    from seist_tpu import taskspec
    from seist_tpu.data import pipeline

    _, out = packed_pair
    spec = taskspec.get_task_spec("seist_s_dpk")
    ds = pipeline.from_task_spec(
        spec,
        "packed",
        "train",
        seed=0,
        in_samples=512,
        augmentation=True,
        data_dir=out,
    )
    assert ds.sampling_rate() == 50
    loader = pipeline.Loader(ds, batch_size=8, shuffle=True, num_workers=2)
    try:
        batch = next(iter(loader))
    finally:
        loader.close()
    assert batch.inputs.shape == (8, 512, 3)
    assert np.isfinite(batch.inputs).all()


# ------------------------------------------------ parallel / resume / mixture
def _synthetic_source(n_events=30, trace_samples=512):
    return PackSource(
        name="synthetic",
        dataset_kwargs={
            "num_events": n_events,
            "trace_samples": trace_samples,
            "cache": False,
        },
    )


def _dir_fingerprint(root):
    """Byte content of every shard bin + the index/sidecar ARRAY contents
    (npz zip bytes carry timestamps, so the arrays are the identity)."""
    out = {}
    for f in sorted(os.listdir(root)):
        p = os.path.join(root, f)
        if f.endswith(".bin"):
            with open(p, "rb") as fh:
                out[f] = fh.read()
        elif f.endswith(".npz"):
            with np.load(p, allow_pickle=False) as z:
                out[f] = {k: z[k].tolist() for k in sorted(z.files)}
    return out


def test_parallel_pack_bit_identical_to_serial(tmp_path):
    """A 2-worker pack must produce byte-identical shards and an
    identical index to a 1-worker pack: the shard partition is a pure
    function of the plan, never of worker count (ISSUE acceptance)."""
    a, b = str(tmp_path / "serial"), str(tmp_path / "par")
    s1 = pack_sources([_synthetic_source()], a, samples_per_shard=7)
    s2 = pack_sources(
        [_synthetic_source()], b, num_workers=2, samples_per_shard=7
    )
    assert s1["shards"] == s2["shards"] > 1
    assert s1["samples"] == s2["samples"] == 30
    assert _dir_fingerprint(a) == _dir_fingerprint(b)


def test_pack_resume_skips_complete_shards(tmp_path):
    """Interrupted pack: kill after some shards -> the re-run re-plans
    identically, skips every complete shard, and the result is identical
    to an uninterrupted pack."""
    full, part = str(tmp_path / "full"), str(tmp_path / "part")
    pack_sources([_synthetic_source()], full, samples_per_shard=7)
    pack_sources([_synthetic_source()], part, samples_per_shard=7)
    # Simulate the interruption: no meta/index yet, shard 1 half-written
    # (bin exists, sidecar missing), shard 2 gone entirely.
    os.unlink(os.path.join(part, "meta.json"))
    os.unlink(os.path.join(part, "index.npz"))
    os.unlink(sidecar_path(part, 1))
    os.unlink(shard_path(part, 2))
    os.unlink(sidecar_path(part, 2))
    stats = pack_sources([_synthetic_source()], part, samples_per_shard=7)
    assert stats["shards_skipped"] == stats["shards"] - 2
    assert stats["samples_packed"] == 7 + 7  # only the two holes re-read
    assert _dir_fingerprint(full) == _dir_fingerprint(part)


def test_mixture_pack_provenance_and_roundtrip(tmp_path):
    """--mixture: two sources in one directory, consecutive shard
    ranges, a source_id column on every row, and events identical to
    reading each source directly."""
    out = str(tmp_path / "mix")
    src_a = _synthetic_source(n_events=10, trace_samples=256)
    src_b = _synthetic_source(n_events=17, trace_samples=256)
    stats = pack_sources(
        [src_a, src_b], out, samples_per_shard=4, num_workers=0
    )
    assert stats["samples"] == 27
    with open(os.path.join(out, "meta.json")) as f:
        meta = json.load(f)
    assert [s["n_events"] for s in meta["sources"]] == [10, 17]
    assert meta["source"].startswith("mixture:")

    ds = PackedDataset(
        seed=0, mode="train", data_dir=out, shuffle=False, data_split=False
    )
    sids = ds.source_ids()
    assert sids is not None and sids.shape == (27,)
    assert (sids[:10] == 0).all() and (sids[10:] == 1).all()
    # Row 10+j of the mixture == source B's own event j.
    b = src_b.create()
    for j in (0, 16):
        ev_mix, row = ds[10 + j]
        ev_src, _ = b[j]
        np.testing.assert_array_equal(ev_mix["data"], ev_src["data"])
        assert int(row["source_id"]) == 1
    # Single-source packs expose no source ids (mixture sampler stays off).
    single = PackedDataset(
        seed=0,
        mode="train",
        data_dir=pack_sources(
            [_synthetic_source(8, 256)], str(tmp_path / "one"),
            samples_per_shard=4,
        )["out"],
        shuffle=False,
        data_split=False,
    )
    assert single.source_ids() is None


def test_mixture_rejects_mismatched_sources(tmp_path):
    class OtherRate:
        def __len__(self):
            return 1

        def __getitem__(self, i):
            return {"data": np.zeros((3, 64), np.float32), "snr": np.zeros(3)}, {}

        def name(self):
            return "other"

        def channels(self):
            return ["z", "n", "e"]

        def sampling_rate(self):
            return 100  # != synthetic's 50

    with pytest.raises(ValueError, match="sampling rate"):
        pack_sources(
            [_synthetic_source(4, 128), PackSource(dataset=OtherRate())],
            str(tmp_path / "bad"),
        )


def test_pack_rejects_multi_event_windows(tmp_path):
    class TwoPick:
        def __init__(self):
            self._rows = [0]

        def __len__(self):
            return 1

        def __getitem__(self, i):
            return (
                {
                    "data": np.zeros((3, 64), np.float32),
                    "ppks": [1, 2],  # two picks: not representable
                    "spks": [],
                    "snr": np.zeros(3),
                },
                {"key": "k"},
            )

        def name(self):
            return "twopick"

        def channels(self):
            return ["z", "n", "e"]

        def sampling_rate(self):
            return 50

    with pytest.raises(ValueError, match="one event per window"):
        pack_dataset(TwoPick(), str(tmp_path / "out"))
