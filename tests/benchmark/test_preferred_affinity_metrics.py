"""The wave kernel's per-layer metric of `perf5k-preferredaffinity`: the
share of launches whose score a preferred pod (anti-)affinity term moved
(`scheduler_wave_preferred_affinity_batches_total` over
`scheduler_wave_batches_total`, x 100). The entry has its file and lists
the cells that report what it moves; the new cell stands beside the other
`.backlog` cells in every list; the file reads what it says from two
scrapes and nothing from two empty ones. (A CPU rehearsal has at most 256
nodes, where the small-batch host lane places the window's pods and no
wave launches: the counter is read off the production Scheduler in
tests/test_wave_preferred_affinity_score.py.)"""

import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "benchmark"))

from harness.catalog import Catalog  # noqa: E402
from harness.scrape import Scrape  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CAT = Catalog(str(REPO))
ENTRIES = {m["name"]: m for m in BENCH["per_layer"]}
CELL = "perf5k-preferredaffinity.backlog"
NEW = {"pref_wave_share.backlog": "%"}

# two scrapes of the scheduler, 10 s apart: 50 launches, 40 of them with
# a preferred term in sight
SCHED_0 = """
process_clock_seconds 100.0
scheduler_wave_batches_total 200
scheduler_wave_preferred_affinity_batches_total 150
"""
SCHED_1 = """
process_clock_seconds 110.0
scheduler_wave_batches_total 250
scheduler_wave_preferred_affinity_batches_total 190
"""
EXPECT = {"pref_wave_share.backlog": 80.0}  # 40 of 50 launches


def _ctx(s0, s1):
    return {"client": {}, "sched": (Scrape(s0), Scrape(s1)),
            "api": (Scrape(""), Scrape("")), "trace": None}


def test_the_benchmark_and_its_files_agree():
    assert set(NEW) <= set(ENTRIES)
    assert CAT.problems() == []


@pytest.mark.parametrize("name", sorted(NEW))
def test_every_new_entry_has_its_file(name):
    entry = ENTRIES[name]
    stem = name.rsplit(".", 1)[0]
    assert (entry["unit"], entry["better"], entry["source"]) == (
        NEW[name], "lower", "program_counter")
    assert entry["layer"] == "wave kernel"
    assert entry["moves"] == "bound_pods_per_s"
    assert sorted(entry["workloads"]) == sorted(CAT.reporting("bound_pods_per_s"))
    assert (REPO / "benchmark" / "layer_metrics" / f"{stem}.json").is_file()
    assert CAT.layer_metric(name)["reader"] == "counter_ratio"


def test_the_new_cell_stands_beside_every_backlog_cell():
    """Wherever a list of what moves `bound_pods_per_s` names the standing
    one-chip `.backlog` cells, it names the new one too: the wave
    kernel's roofline share among them."""
    standing = [c for c in CAT.reporting("bound_pods_per_s")
                if c != CELL and CAT.cell(c)["chips"] == 1]
    assert CELL in CAT.reporting("bound_pods_per_s") and standing
    for m in BENCH["per_layer"]:
        if m["moves"] == "bound_pods_per_s" and set(standing) <= set(
                m.get("workloads", [])):
            assert CELL in m["workloads"], m["name"]
    assert CELL in ENTRIES["wave_kernel_roofline"]["workloads"]


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_a_new_metric_reads_what_it_says(name):
    spec = CAT.layer_metric(name)
    value = CAT.reader(spec["reader"])(_ctx(SCHED_0, SCHED_1), **spec["args"])
    assert value == pytest.approx(EXPECT[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_nothing_is_read_from_empty_scrapes(name):
    spec = CAT.layer_metric(name)
    assert CAT.reader(spec["reader"])(_ctx("", ""), **spec["args"]) is None
    for cell in CAT.cells():
        got = CAT.read_layer_metrics(cell, _ctx("", ""))
        assert not set(got) & set(NEW), (cell, got)

