"""Task-spec and io-item catalog tests (ref semantics: config.py:20-435)."""

import pytest

from seist_tpu import taskspec
from seist_tpu.models import losses as L


def test_io_item_catalog_complete():
    # The io-items of the reference catalog (config.py:207-264) and the
    # two of the token task (ids in, the same shifted by one as the label).
    expected = {
        "ids", "next_ids",
        "z", "n", "e", "dz", "dn", "de", "non", "det", "ppk", "spk",
        "ppk+", "spk+", "det+", "ppks", "spks", "emg", "smg", "baz",
        "dis", "pmp", "clr",
    }
    assert set(taskspec.IO_ITEMS) == expected


def test_io_item_kinds():
    assert taskspec.get_kind("ppk") == "soft"
    assert taskspec.get_kind("emg") == "value"
    assert taskspec.get_kind("pmp") == "onehot"
    assert taskspec.get_num_classes("pmp") == 2
    with pytest.raises(ValueError):
        taskspec.get_num_classes("emg")


def test_get_io_items_by_kind():
    assert "ppks" in taskspec.get_io_items("value")
    assert "det" in taskspec.get_io_items("soft")
    assert set(taskspec.get_io_items()) == set(taskspec.IO_ITEMS)


@pytest.mark.parametrize(
    "model,pattern",
    [
        ("phasenet", "phasenet"),
        ("eqtransformer", "eqtransformer"),
        ("magnet", "magnet"),
        ("baz_network", "baz_network"),
        ("ditingmotion", "ditingmotion"),
        ("seist_s_dpk", "seist_.*?_dpk.*"),
        ("seist_m_dpk", "seist_.*?_dpk.*"),
        ("seist_l_dpk", "seist_.*?_dpk.*"),
        ("seist_s_pmp", "seist_.*?_pmp"),
        ("seist_m_emg", "seist_.*?_emg"),
        ("seist_l_baz", "seist_.*?_baz"),
        ("seist_l_dis", "seist_.*?_dis"),
    ],
)
def test_spec_resolution_unique(model, pattern):
    spec = taskspec.get_task_spec(model)
    assert spec.pattern == pattern


def test_unknown_model_spec():
    with pytest.raises(KeyError):
        taskspec.get_task_spec("unknown_model_xyz")


def test_num_inchannels():
    assert taskspec.get_num_inchannels("phasenet") == 3
    assert taskspec.get_num_inchannels("seist_l_dpk") == 3
    assert taskspec.get_num_inchannels("ditingmotion") == 2


def test_loss_instantiation():
    assert isinstance(taskspec.make_loss("phasenet"), L.CELoss)
    assert isinstance(taskspec.make_loss("seist_s_dpk"), L.BCELoss)
    assert isinstance(taskspec.make_loss("seist_s_emg"), L.HuberLoss)
    assert isinstance(taskspec.make_loss("magnet"), L.MousaviLoss)
    assert isinstance(taskspec.make_loss("baz_network"), L.CombinationLoss)


def test_baz_transforms_roundtrip():
    import jax.numpy as jnp
    import numpy as np

    spec = taskspec.get_task_spec("baz_network")
    deg = jnp.asarray([[0.0], [90.0], [180.0], [250.0]])
    cos, sin = spec.targets_transform_for_loss(deg)
    out = spec.outputs_transform_for_results((cos, sin))
    # atan2 wraps to (-180, 180]; compare as angles modulo 360
    diff = (np.asarray(out) - np.asarray(deg)) % 360.0
    diff = np.minimum(diff, 360.0 - diff)
    np.testing.assert_allclose(diff, 0.0, atol=1e-3)


def test_validate_passes():
    taskspec.validate(strict_models=False)


def test_flatten_io_names():
    assert taskspec.flatten_io_names((("z", "n", "e"), "emg")) == ["z", "n", "e", "emg"]
