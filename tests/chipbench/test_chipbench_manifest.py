"""The manifest keeps to the contract's shapes, and every name in it
finds its file."""

import json
import os
import re

import pytest

from chipbench_tiny import ROOT, harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
M = harness.load_manifest()
METRICS = M["end_to_end"] + M["per_layer"]
CELLS = [w["name"] for w in M["workloads"]]


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["command"][:2] == ["python3", "chipbench/run.py"]
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for p in M["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


def test_names_are_unique():
    for group in (METRICS, M["workloads"], M["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"])
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    e2e = metric in M["end_to_end"]
    if e2e:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert metric["moves"] in [m["name"] for m in M["end_to_end"]]
        assert 1 <= len(metric["layer"]) <= 200
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    # its reader is a file of its own, found by the metric's name
    assert harness.find_file(ROOT, M, "metrics", metric["name"] + ".py")


@pytest.mark.parametrize("entry", M["configs"], ids=lambda c: c["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert any(entry["file"].startswith(p + "/") for p in M["paths"])
    assert entry["name"] in [w["config"] for w in M["workloads"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == entry["reduced"] and len(entry["reduced"]) <= 16
    assert os.path.isfile(os.path.join(
        ROOT, os.path.splitext(entry["file"])[0] + ".py"))
    for text in (entry["why"], entry["source"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("name", CELLS)
def test_cell_entry_and_files(name):
    work = next(w for w in M["workloads"] if w["name"] == name)
    assert set(work) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(name) and NAME.match(work["traffic"])
    assert work["chips"] in (1, 4)
    assert 1 <= len(work["why"]) <= 200
    cell = harness.load_cell(name)
    assert cell.traffic["global_batch"] % cell.chips == 0
    assert cell.traffic["pool_rows"] >= cell.traffic["global_batch"]
    names = [m["name"] for m in cell.metrics["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2
    assert cell.metrics["per_layer"]
    for m in cell.metrics["per_layer"]:
        assert m["moves"] in names
    # each limit is for a number the comparison gives; the three that
    # catch a training cell's faults are always held
    known = {"loss1_gap", "loss_gap", "grad_gap", "grad_worst", "head_gap",
             "update_gap", "update_worst", "stats_gap", "stats_worst"}
    assert {"loss_gap", "grad_gap", "update_gap"} <= set(cell.limits) <= known
    assert all(0 < v < 1 for v in cell.limits.values())


def test_four_chip_cells_are_at_most_a_quarter():
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 4)


def test_the_full_check_fits_the_budget():
    per_run = M["run_seconds"] + 60
    full = (2 + 14 * 24) * per_run + 24 * 2 * 90 + 1200
    assert full <= 43200


def test_peaks_table_and_unknown_kind():
    assert harness.load_peaks("TPU v5 lite") == {
        "bf16_tflops": 197.0, "hbm_gb_per_s": 819.0, "hbm_gb": 16.0}
    for kind in ("cpu", "TPU v9", "source"):
        with pytest.raises(harness.BenchError):
            harness.load_peaks(kind)
