"""Native wavekit kernels vs the numpy reference path.

seist_tpu.native builds its library from wavekit.cpp on import (g++ is in
the image); a failed build is an error. Parity uses fp32-accumulation
tolerances.
"""

import importlib
import os

import numpy as np
import pytest


@pytest.fixture(scope="module")
def native():
    import seist_tpu.native as native_mod

    assert native_mod.available()
    return native_mod


def test_library_is_built_from_this_source(native):
    # The loaded library is named after wavekit.cpp's hash and sits next
    # to it: a foreign .so left in the checkout can never be picked up.
    path = native.lib_path()
    assert os.path.exists(path)
    assert os.path.dirname(path) == os.path.dirname(native.__file__)
    assert native._lib._name == path


@pytest.mark.parametrize("mode", ["std", "max", ""])
def test_znorm_matches_numpy(native, mode, rng):
    data = rng.normal(3.0, 2.0, size=(3, 4096)).astype(np.float32)

    want = data - np.mean(data, axis=1, keepdims=True)
    if mode == "max":
        d = np.max(want, axis=1, keepdims=True)
        d[d == 0] = 1
        want = want / d
    elif mode == "std":
        d = np.std(want, axis=1, keepdims=True)
        d[d == 0] = 1
        want = want / d

    got = np.ascontiguousarray(data.copy())
    assert native.znorm(got, mode)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_znorm_zero_channel(native):
    data = np.zeros((2, 128), dtype=np.float32)
    got = data.copy()
    assert native.znorm(got, "std")
    assert np.all(got == 0)


def test_soft_label_matches_python(native, rng):
    from seist_tpu.data.preprocess import DataPreprocessor

    pre = DataPreprocessor(
        data_channels=["z", "n", "e"], sampling_rate=50, in_samples=1024
    )
    width = 25
    window = pre._soft_window(width, "gaussian")
    # Edge cases: negative, head-clipped, interior, tail-clipped, > L-1.
    idxs = np.array([-5, 3, 500, 1020, 1500], dtype=np.int64)

    got = np.zeros(1024)
    assert native.soft_label_add(got, idxs, window, width)

    want = np.zeros(1024)
    left = width // 2
    right = width - left
    for idx in idxs:
        if idx < 0 or idx > 1023:
            continue
        if idx - left < 0:
            want[: idx + right + 1] += window[width + 1 - (idx + right + 1) :]
        elif idx + right <= 1023:
            want[idx - left : idx + right + 1] += window
        else:
            want[-(1024 - (idx - left)) :] += window[: 1024 - (idx - left)]
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


def test_preprocessor_uses_native_transparently(native, rng):
    """End-to-end: preprocess with the native path produces the same labels
    as the pure-python fallback."""
    from seist_tpu.data.preprocess import DataPreprocessor

    pre = DataPreprocessor(
        data_channels=["z", "n", "e"], sampling_rate=50, in_samples=2048
    )
    event = {
        "data": rng.normal(size=(3, 4096)).astype(np.float32),
        "ppks": [900],
        "spks": [1800],
        "snr": np.array([20.0, 20.0, 20.0]),
    }
    ev = pre.process(
        dict(event), augmentation=False, rng=np.random.default_rng(7), inplace=False
    )
    label = pre._generate_soft_label("ppk", ev)

    os.environ["SEIST_TPU_NATIVE"] = "0"
    try:
        import seist_tpu.native as native_mod

        importlib.reload(native_mod)
        assert not native_mod.available()
        ev2 = pre.process(
            dict(event),
            augmentation=False,
            rng=np.random.default_rng(7),
            inplace=False,
        )
        label2 = pre._generate_soft_label("ppk", ev2)
    finally:
        os.environ.pop("SEIST_TPU_NATIVE", None)
        importlib.reload(native_mod)

    np.testing.assert_allclose(
        np.asarray(ev["data"]), np.asarray(ev2["data"]), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(label, label2, rtol=1e-6, atol=1e-7)
