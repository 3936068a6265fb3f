"""The bind lane's per-layer metrics (scheduler/bindlane.py): waves a
binding request carries (`scheduler_bind_lane_waves_total` over
`rest_client_binding_requests_total`) and a binding's wait from its
hand-off to the request that carries it (`scheduler_bind_lane_wait_seconds`).
Each entry of BENCHMARK.json has its file and lists the cells that report
what it moves, the four stand in their order one after another, each file
reads what it says from two scrapes and nothing from two empty ones; the
CPU rehearsal that reads the `.steady` pair is test_queue_wait_split.py's."""

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
NEW = {
    "waves_per_bind_request.steady": ("waves", "higher", "program_counter"),
    "waves_per_bind_request.backlog": ("waves", "higher", "program_counter"),
    "bind_lane_wait_ms.steady": ("ms", "lower", "program_span"),
    "bind_lane_wait_ms.backlog": ("ms", "lower", "program_span"),
}
READER = {"waves_per_bind_request": "counter_ratio",
          "bind_lane_wait_ms": "hist_mean"}

# two scrapes of the scheduler, 10 s apart: 30 hand-offs to the lane went
# out in 25 binding requests, and 40 bindings waited 0.05 s in all
SCHED_0 = """
process_clock_seconds 100.0
scheduler_bind_lane_waves_total 500
rest_client_binding_requests_total 450
scheduler_bind_lane_wait_seconds_sum 1.0
scheduler_bind_lane_wait_seconds_count 900
"""
SCHED_1 = """
process_clock_seconds 110.0
scheduler_bind_lane_waves_total 530
rest_client_binding_requests_total 475
scheduler_bind_lane_wait_seconds_sum 1.05
scheduler_bind_lane_wait_seconds_count 940
"""
EXPECT = {
    "waves_per_bind_request": 1.2,   # 30 hand-offs over 25 requests
    "bind_lane_wait_ms": 1.25,       # 0.05 s over 40 bindings
}


def _ctx(s0, s1):
    return {"client": {}, "sched": (Scrape(s0), Scrape(s1)),
            "api": (Scrape(""), Scrape("")), "trace": None}


def test_the_four_stand_in_order_one_after_another():
    assert set(NEW) <= set(ENTRIES)
    assert CAT.problems() == []
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index(next(iter(NEW)))
    assert names[first:first + len(NEW)] == list(NEW)


@pytest.mark.parametrize("name", sorted(NEW))
def test_every_new_entry_has_its_file(name):
    entry = ENTRIES[name]
    stem, _suffix = name.rsplit(".", 1)
    assert (entry["unit"], entry["better"], entry["source"]) == NEW[name]
    assert entry["layer"] == "REST + store + WAL"
    assert sorted(entry["workloads"]) == sorted(CAT.reporting(entry["moves"]))
    assert (REPO / "benchmark" / "layer_metrics" / f"{stem}.json").is_file()
    assert CAT.layer_metric(name)["reader"] == READER[stem]


@pytest.mark.parametrize("stem", sorted(EXPECT))
def test_a_new_metric_reads_what_it_says(stem):
    for suffix in ("steady", "backlog"):
        spec = CAT.layer_metric(f"{stem}.{suffix}")
        value = CAT.reader(spec["reader"])(_ctx(SCHED_0, SCHED_1), **spec["args"])
        assert value == pytest.approx(EXPECT[stem], rel=1e-9)


@pytest.mark.parametrize("stem", sorted(EXPECT))
def test_nothing_is_read_from_empty_scrapes(stem):
    spec = CAT.layer_metric(f"{stem}.steady")
    assert CAT.reader(spec["reader"])(_ctx("", ""), **spec["args"]) is None
    for cell in CAT.cells():
        got = CAT.read_layer_metrics(cell, _ctx("", ""))
        assert not set(got) & set(NEW), (cell, got)
