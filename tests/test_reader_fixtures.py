"""Real-format reader tests against tiny on-disk fixtures.

Each dataset's production read path (``_load_meta_data`` +
``_load_event_data``: pandas dtype maps, h5py layouts, key quirks) is
exercised end to end — fixture files on disk -> reader -> preprocessor ->
Loader batch -> one jitted train step — so a malformed dtype/column
assumption dies here, not at step 0 of a real run.

Formats reproduced (ref anchors):
* DiTing: 28 CSV (+HDF5) parts, ``earthquake/<key>`` datasets of shape
  (L, 3), zero-padded keys, string-numeric columns with stray spaces,
  ms/mb->ml magnitude conversion (ref datasets/diting.py:52-214).
* DiTing_light: single numeric CSV (ref diting.py:217-311).
* PNW: ComCat CSV + bucketed HDF5 ``data/bucket$n`` refs, '|'-separated
  snr triple, polarity word map (ref datasets/pnw.py:102-150).
* PNW_light: same with the light metadata filename (ref pnw.py:153-188).
* SOS: pre-split train/val/test dirs of per-trace npz (data stored (L, 1);
  the reader emits (1, L)) + ``_all_label.csv`` (ref datasets/sos.py:53-86).
"""

import os

import h5py
import numpy as np
import pandas as pd
import pytest

import seist_tpu
from seist_tpu import taskspec
from seist_tpu.data import pipeline
from seist_tpu.data.diting import normalize_key

seist_tpu.load_all()

L_TRACE = 1024  # raw trace samples in fixtures
L_IN = 512  # training window
N_PARTS = 28


def _wave(rng, n_ch=3, length=L_TRACE):
    w = rng.standard_normal((length, n_ch)).astype(np.float32)
    w[300:420] *= 6.0  # an "event"
    return w


# ------------------------------------------------------------------- fixtures
def _diting_row(i, part):
    key = f"{100 + i}.{part}"  # short on purpose: exercises zero-padding
    row = {
        "key": key,
        "part": part,
        "ev_id": 1000 + i,
        "mag_type": "ms" if i % 2 else "ml",
        "p_pick": 300,
        "p_clarity": "i" if i % 2 else "e",
        "p_motion": "u" if i % 2 else "d",
        "s_pick": 420,
        "net": "XX",
        "sta_id": i,
        "dis": 12.5,
        # Full-release quirk: numeric values arrive as strings with spaces
        # (ref diting.py:62-72,95-97).
        "evmag": " 2.3",
        "st_mag": " 2.1",
        "baz": " 405.0",  # exercises %= 360
        "P_residual": " 0.1",
        "S_residual": " 0.2",
    }
    for c in "ZNE":
        for ph in "PS":
            for kind in ("amplitude", "power"):
                row[f"{c}_{ph}_{kind}_snr"] = 10.0 + i
    return row


@pytest.fixture(scope="module")
def diting_dir(tmp_path_factory):
    root = tmp_path_factory.mktemp("diting")
    rng = np.random.default_rng(0)
    for part in range(N_PARTS):
        rows = [_diting_row(2 * part + j, part) for j in range(2)]
        pd.DataFrame(rows).to_csv(root / f"DiTing330km_part_{part}.csv")
        with h5py.File(root / f"DiTing330km_part_{part}.hdf5", "w") as f:
            for r in rows:
                # HDF5 layout: (L, 3), read with .T (ref diting.py:139-142).
                f.create_dataset(
                    "earthquake/" + normalize_key(r["key"]), data=_wave(rng)
                )
    return str(root)


@pytest.fixture(scope="module")
def diting_light_dir(tmp_path_factory):
    root = tmp_path_factory.mktemp("diting_light")
    rng = np.random.default_rng(1)
    rows = []
    for i in range(12):
        r = _diting_row(i, part=i % 3)
        # Light release: numeric columns are numeric (ref diting.py:217-311).
        for col in ("evmag", "st_mag", "baz", "P_residual", "S_residual"):
            r[col] = float(r[col])
        rows.append(r)
    pd.DataFrame(rows).to_csv(root / "DiTing330km_light.csv")
    for part in sorted({r["part"] for r in rows}):
        with h5py.File(root / f"DiTing330km_part_{part}.hdf5", "w") as f:
            for r in rows:
                if r["part"] == part:
                    f.create_dataset(
                        "earthquake/" + normalize_key(r["key"]),
                        data=_wave(rng),
                    )
    return str(root)


def _pnw_fixture(root, meta_filename):
    rng = np.random.default_rng(2)
    n = 12
    buckets = {"bucket0": [], "bucket1": []}
    rows = []
    for i in range(n):
        bucket = f"bucket{i % 2}"
        bi = len(buckets[bucket])
        trace = _wave(rng).T  # (3, L) rows per bucket entry (ref pnw.py:107-110)
        if i == 0:
            trace[0, :5] = np.nan  # reader must nan_to_num (ref pnw.py:110)
        buckets[bucket].append(trace)
        rows.append(
            {
                "trace_name": f"{bucket}${bi},:3,:{L_TRACE}",
                "trace_P_polarity": ["positive", "negative", "undecidable", ""][i % 4],
                "preferred_source_magnitude_type": "ml",
                "preferred_source_magnitude": 2.0 + 0.1 * i,
                "trace_snr_db": "10.0|nan|12.5",
                "trace_P_arrival_sample": 300,
                "trace_S_arrival_sample": 420,
                "station_network_code": "UW",
            }
        )
    pd.DataFrame(rows).to_csv(root / meta_filename, index=False)
    with h5py.File(root / "comcat_waveforms.hdf5", "w") as f:
        for name, traces in buckets.items():
            f.create_dataset(f"data/{name}", data=np.stack(traces))
    return str(root)


@pytest.fixture(scope="module")
def pnw_dir(tmp_path_factory):
    return _pnw_fixture(tmp_path_factory.mktemp("pnw"), "comcat_metadata.csv")


@pytest.fixture(scope="module")
def pnw_light_dir(tmp_path_factory):
    return _pnw_fixture(
        tmp_path_factory.mktemp("pnw_light"), "comcat_metadata_light.csv"
    )


@pytest.fixture(scope="module")
def sos_dir(tmp_path_factory):
    root = tmp_path_factory.mktemp("sos")
    rng = np.random.default_rng(3)
    for mode in ("train", "val", "test"):
        d = root / mode
        d.mkdir()
        rows = []
        for i in range(8 if mode == "train" else 3):
            fname = f"trace_{mode}_{i}.npz"
            # On-disk layout: (L, 1); reader emits (1, L) via np.stack
            # (ref sos.py:74-77).
            np.savez(
                d / fname,
                data=_wave(rng, n_ch=1).reshape(L_TRACE, 1),
            )
            rows.append({"fname": fname, "itp": 300, "its": 420})
        pd.DataFrame(rows).to_csv(d / "_all_label.csv", index=False)
    return str(root)


# --------------------------------------------------------------------- helpers
def _one_train_step(loader, in_channels):
    import jax

    from seist_tpu.models import api
    from seist_tpu.train import (
        build_optimizer,
        create_train_state,
        jit_step,
        make_train_step,
    )

    model = api.create_model(
        "phasenet", in_channels=in_channels, in_samples=L_IN
    )
    variables = api.init_variables(
        model, in_samples=L_IN, in_channels=in_channels, batch_size=4
    )
    state = create_train_state(model, variables, build_optimizer("adam", 1e-3))
    spec = taskspec.get_task_spec("phasenet")
    loss_fn = taskspec.make_loss("phasenet")
    step = jit_step(make_train_step(spec, loss_fn), donate_state=False)
    batch = next(iter(loader))
    state, loss, out = step(
        state, batch.inputs, batch.loss_targets, jax.random.PRNGKey(0)
    )
    assert np.isfinite(float(loss))
    assert out.shape[0] == 4 and out.shape[1] == L_IN
    return batch


def _loader(dataset_name, data_dir, mode="train", **kw):
    spec = taskspec.get_task_spec("phasenet")
    sds = pipeline.from_task_spec(
        spec,
        dataset_name,
        mode,
        seed=11,
        data_dir=data_dir,
        in_samples=L_IN,
        augmentation=(mode == "train"),
        **kw,
    )
    return pipeline.Loader(sds, 4, shuffle=True, drop_last=True, num_workers=2)


# ----------------------------------------------------------------------- tests
class TestDiTing:
    def test_reader_and_train_step(self, diting_dir):
        loader = _loader("diting", diting_dir)
        batch = _one_train_step(loader, in_channels=3)
        assert batch.inputs.shape == (4, L_IN, 3)
        assert batch.inputs.dtype == np.float32

    def test_event_semantics(self, diting_dir):
        from seist_tpu.registry import DATASETS

        ds = DATASETS.create(
            "diting", seed=11, mode="train", data_dir=diting_dir
        )
        ev, meta = ds[0]
        assert ev["data"].shape == (3, L_TRACE)
        assert ev["ppks"] == [300] and ev["spks"] == [420]
        assert ev["baz"] and 0 <= ev["baz"][0] < 360  # 405 -> 45
        assert ev["pmp"][0] in (0, 1)
        assert ev["clr"][0] in (0, 1)
        assert 0 <= float(ev["emg"][0]) <= 8  # string "2.3" parsed + ml-converted
        assert len(ev["snr"]) == 3


class TestDiTingLight:
    def test_reader_roundtrip(self, diting_light_dir):
        loader = _loader("diting_light", diting_light_dir)
        batch = next(iter(loader))
        assert batch.inputs.shape == (4, L_IN, 3)
        assert np.isfinite(batch.inputs).all()


class TestPNW:
    def test_reader_and_train_step(self, pnw_dir):
        loader = _loader("pnw", pnw_dir)
        batch = _one_train_step(loader, in_channels=3)
        assert np.isfinite(batch.inputs).all()  # nan row was zeroed

    def test_event_semantics(self, pnw_dir):
        from seist_tpu.registry import DATASETS

        ds = DATASETS.create("pnw", seed=11, mode="train", data_dir=pnw_dir)
        ev, meta = ds[0]
        assert ev["data"].shape == (3, L_TRACE)
        assert ev["pmp"][0] in (0, 1, 2, 3)
        assert len(ev["snr"]) == 3 and ev["snr"][1] == 0.0  # 'nan' -> 0
        assert np.isfinite(ev["data"]).all()

    def test_mostly_nan_trace_is_corrupt_not_zeroed(self, tmp_path):
        """Sparse NaNs are zeroed (reference parity, ref pnw.py:110 —
        covered above); a trace that is MOSTLY non-finite is rotted and
        must classify as permanent corruption (data/io_guard.py) instead
        of silently becoming a near-all-zeros sample."""
        import shutil

        import h5py

        from seist_tpu.data.io_guard import CorruptSampleError
        from seist_tpu.registry import DATASETS

        src = tmp_path / "pnw_src"
        src.mkdir()
        root = tmp_path / "pnw_rot"
        shutil.copytree(_pnw_fixture(src, "comcat_metadata.csv"), root)
        with h5py.File(root / "comcat_waveforms.hdf5", "r+") as f:
            arr = f["data/bucket0"][...]
            arr[0] = np.nan  # whole first trace rotted
            del f["data/bucket0"]
            f.create_dataset("data/bucket0", data=arr)
        ds = DATASETS.create(
            "pnw", seed=11, mode="train", data_dir=str(root),
            data_split=False, shuffle=False,
        )
        rotted = next(
            i for i in range(len(ds))
            if ds._row_dict(i)["trace_name"].startswith("bucket0$0,")
        )
        with pytest.raises(CorruptSampleError, match="non-finite"):
            ds[rotted]


class TestPNWLight:
    def test_reader_roundtrip(self, pnw_light_dir):
        loader = _loader("pnw_light", pnw_light_dir)
        batch = next(iter(loader))
        assert batch.inputs.shape == (4, L_IN, 3)


class TestSOS:
    def test_reader_and_train_step(self, sos_dir):
        # SOS is single-channel: bypass the 3-channel model spec and wire
        # the pipeline explicitly (ref uses SOS for picking only).
        sds = pipeline.SeismicDataset(
            "sos",
            "train",
            seed=11,
            data_dir=sos_dir,
            input_names=[["z"]],
            label_names=[["non", "ppk", "spk"]],
            task_names=["ppk", "spk"],
            in_samples=L_IN,
            augmentation=True,
            data_split=False,
        )
        loader = pipeline.Loader(
            sds, 4, shuffle=True, drop_last=True, num_workers=2
        )
        batch = _one_train_step(loader, in_channels=1)
        assert batch.inputs.shape == (4, L_IN, 1)

    def test_presplit_modes(self, sos_dir):
        from seist_tpu.registry import DATASETS

        for mode, n in (("train", 8), ("val", 3), ("test", 3)):
            ds = DATASETS.create(
                "sos", seed=11, mode=mode, data_dir=sos_dir, data_split=False
            )
            assert len(ds) == n
            ev, meta = ds[0]
            assert ev["data"].shape == (1, L_TRACE)
            assert ev["ppks"] == [300]
