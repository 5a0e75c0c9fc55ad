"""Every file BENCHMARK.json names resolves, and its entries keep to the
benchmark's naming rules."""
import json
import re

import pytest

from harness.cell import BENCH, ROOT, load_cell, metric_reader

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(w):
    cell = load_cell(w["name"], SPEC)
    assert cell.traffic["loop"] == "sweep"
    assert set(cell.traffic.get("variants", [])) <= set(
        cell.config["variants"])
    assert cell.traffic["limits"]
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for key in (w["name"], w["config"], w["traffic"]):
        assert NAME.match(key)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file(c):
    cfg = json.loads((ROOT / c["file"]).read_text())
    assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
    assert cfg["reduced"] == c["reduced"]
    assert all(k in cfg for k in c["reduced"])
    assert cfg["precision"] == "float64" and cfg["guarantees"]
    assert c["file"].startswith(SPEC["paths"][0] + "/")
    assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_reader(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert callable(metric_reader(m["name"]))
    assert (BENCH / "metrics" / f"{m['name']}.py").exists()


def test_names_unique_and_moves_known():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in SPEC[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells


def test_peaks_table():
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
