"""The eval check's control, at a size a test run can hold: the reference one
precision below the configuration's has to read above the configuration's
limits; the reference against itself reads 0."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)


@pytest.mark.parametrize("config", ["seist_l_dpk", "phasenet"])
def test_lower_precision_reads_over_the_limit(config):
    from tools.control import control_gaps

    with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    cfg["in_samples"] = 2048
    limits = cfg["limits"]["eval_reference"]
    for seed in (1, 2, 3):
        g = control_gaps(cfg, seed, batch=4, rows=4)
        assert g["range"] > limits["range_min"]
        assert g["rms"] > limits["rms_max"] or g["p999"] > limits["p999_max"], g
