"""The harness is driven by data: a made-up cell and a made-up per-layer
metric, added as files and BENCHMARK.json entries in a temporary copy,
are found with no edit to a file that is there."""

import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "benchmark"))

from harness.catalog import Catalog  # noqa: E402
from harness.scrape import Scrape  # noqa: E402


def _copy(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_a_new_cell_and_a_new_layer_metric_are_found(tmp_path):
    root = _copy(tmp_path)
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    bench = json.loads((root / "BENCHMARK.json").read_text())
    # a new traffic mix over an existing configuration: one data file
    (root / "benchmark/traffic/trickle.perf5k-basic.json").write_text(json.dumps({
        "arrivals": "uniform", "rate_per_s": 10, "pod_template": "measured",
        "warmup": {"burst_pods": 600, "trickle_s": 1.0}}))
    bench["workloads"].append({
        "name": "perf5k-basic.trickle", "config": "perf5k-basic",
        "traffic": "trickle", "chips": 1, "why": "made up for the test"})
    # a new per-layer metric over an existing kind of source: one data file
    (root / "benchmark/layer_metrics/audit_passes_per_s.json").write_text(
        json.dumps({"reader": "counter_ratio", "args": {
            "num": {"source": "sched", "name": "snapshot_audit_passes_total"},
            "den": {"client": "seconds"}}}))
    bench["per_layer"].append({
        "name": "audit_passes_per_s.trickle", "unit": "1/s", "better": "higher",
        "source": "program_counter", "layer": "readback + guards",
        "moves": "create_to_bound_p50_ms",
        "workloads": ["perf5k-basic.trickle"]})
    for m in bench["end_to_end"]:
        if m["name"].startswith("create_to_bound"):
            m["workloads"].append("perf5k-basic.trickle")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cat = Catalog(str(root))
    assert "perf5k-basic.trickle" in cat.cells()
    cell = cat.cell("perf5k-basic.trickle")
    assert cat.traffic(cell)["rate_per_s"] == 10
    assert cat.config(cell["config"])["nodes"]["count"] == 5000
    assert [m["name"] for m in cat.metrics("end_to_end", cell["name"])] == [
        "create_to_bound_p50_ms", "setup_s"]
    assert [m["name"] for m in cat.metrics("per_layer", cell["name"])] == [
        "audit_passes_per_s.trickle"]
    ctx = {"client": {"seconds": 4.0},
           "sched": (Scrape("snapshot_audit_passes_total 1"),
                     Scrape("snapshot_audit_passes_total 3")),
           "api": (Scrape(""), Scrape("")), "trace": None}
    assert cat.read_layer_metrics(cell["name"], ctx) == {
        "audit_passes_per_s.trickle": {"value": 0.5, "unit": "1/s"}}
    # the cells that were there read as before, and no file of theirs changed
    assert set(cat.read_layer_metrics("perf5k-basic.steady", ctx)) == set()
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_a_new_kind_of_source_is_one_more_reader_file(tmp_path):
    root = _copy(tmp_path)
    (root / "benchmark/readers/constant.py").write_text(
        "def read(ctx, value):\n    return value\n")
    (root / "benchmark/layer_metrics/made_up.json").write_text(
        json.dumps({"reader": "constant", "args": {"value": 7}}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "made_up.steady", "unit": "x", "better": "higher",
        "source": "program_counter", "layer": "whole path",
        "moves": "create_to_bound_p50_ms",
        "workloads": ["perf5k-basic.steady"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cat = Catalog(str(root))
    ctx = {"client": {}, "sched": (Scrape(""), Scrape("")),
           "api": (Scrape(""), Scrape("")), "trace": None}
    assert cat.read_layer_metrics("perf5k-basic.steady", ctx) == {
        "made_up.steady": {"value": 7, "unit": "x"}}


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    cat = Catalog(str(REPO))
    ctx = {"client": {}, "sched": (Scrape(""), Scrape("")),
           "api": (Scrape(""), Scrape("")), "trace": None}
    for cell in cat.cells():
        assert cat.read_layer_metrics(cell, ctx) == {}
