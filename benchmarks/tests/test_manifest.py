"""BENCHMARK.json and the data files keep to the contract's letters, every
per-layer metric moves an end-to-end metric its cells report, and flops.py
reproduces the counts stored in the configuration files."""

import importlib
import json
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def test_top_level_keys_and_sizes():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert any(e["name"] == "setup_s" for e in m["end_to_end"])


def test_names_units_and_one_liners():
    m = manifest()
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in m[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group, e["name"]))
            for key in ("why", "layer", "source"):
                if key in e and group != "end_to_end" and not (
                        group == "per_layer" and key == "source"):
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                        and "\t" not in e[key], (e["name"], key)
    assert len(names) == len(set(names))
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]), (e["name"], e["unit"])
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in m["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0 < e["bound"] <= 0.1
    for w in m["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_entry_has_its_files():
    m = manifest()
    used = {w["config"] for w in m["workloads"]}
    for c in m["configs"]:
        assert c["name"] in used
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"]
        assert os.path.isfile(os.path.join(BENCH, cfg["reference"]))
    for w in m["workloads"]:
        cell = load("workloads", f"{w['name']}.json")
        for key in ("config", "traffic", "chips", "why"):
            assert cell[key] == w[key], (w["name"], key)
        traffic = load("traffic", f"{w['traffic']}.json")
        assert os.path.isfile(os.path.join(BENCH, "drivers", f"{traffic['driver']}.py"))
        for check in traffic["checks"]:
            assert os.path.isfile(os.path.join(BENCH, "checks", f"{check['name']}.py"))
    for e in m["end_to_end"] + m["per_layer"]:
        spec = load("metrics", f"{e['name']}.json")
        for key, value in e.items():
            if key != "workloads":  # said in BENCHMARK.json alone
                assert spec[key] == value, (e["name"], key)
        assert os.path.isfile(os.path.join(BENCH, "readers", f"{spec['reader']}.py"))


def test_each_per_layer_metric_moves_a_metric_its_cells_report():
    m = manifest()
    cells = [w["name"] for w in m["workloads"]]
    reports = {c: {e["name"] for e in m["end_to_end"]
                   if c in e.get("workloads", cells)} for c in cells}
    for c in cells:
        assert "setup_s" in reports[c] and len(reports[c]) >= 2
    layers = {}
    for e in m["per_layer"]:
        where = e.get("workloads") or [c for c in cells if e["moves"] in reports[c]]
        assert where, e["name"]
        for c in where:
            assert c in cells and e["moves"] in reports[c], (e["name"], c)
        layers.setdefault(e["layer"].split(" (")[0], set()).add(e["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers
    for c in cells:  # every cell reports at least one per-layer metric
        assert any(c in (e.get("workloads") or cells) for e in m["per_layer"])


@pytest.mark.parametrize("config", ["seist_l_dpk", "phasenet"])
def test_flops_reproduce_the_stored_counts(config):
    import flops

    cfg = load("configs", f"{config}.json")
    name = cfg["reference"].rsplit("/", 1)[-1].removesuffix(".py")
    reference = importlib.import_module(f"reference.{name}")
    assert flops.reference_flops_per_waveform(reference, cfg) == cfg["flops_per_waveform"]


def test_attention_cost_counts_products_and_bytes():
    import flops

    ops, nbytes = flops.attention_cost(batch=2, L=8, M=4, H=3, E=16,
                                       dtype_bytes=2, backward=False)
    assert ops == 2 * (2 * 2 * 3 * 8 * 4 * 16)
    assert nbytes == 2 * (2 * 8 * 3 * 16 * 2) + 2 * (2 * 4 * 3 * 16 * 2)
    ops_b, _ = flops.attention_cost(batch=2, L=8, M=4, H=3, E=16,
                                    dtype_bytes=2, backward=True)
    assert ops_b == 2.5 * ops


def test_peaks_name_their_source():
    peaks = load("peaks.json")
    assert "TPU v5 lite" in peaks["devices"] and "Google Cloud" in peaks["source"]
