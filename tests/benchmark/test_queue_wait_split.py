"""The per-layer metrics of ISSUE 38: the scheduler's queue wait split by
the loop phase that held each pod (`scheduler_queue_wait_seconds_total
{phase}` over `scheduler_queue_waits_total`) and the store's commit ->
queue admit leg (`scheduler_pod_admit_lag_seconds`). The eight entries
stand in BENCHMARK.json in their order, one after another, wherever
later entries put them; each has its file and lists the cells that
report what it moves, each file reads what it says from two scrapes,
nothing from two empty ones nor from a program without the series (the
parent commit), and a number in a CPU rehearsal of the first one-chip
cell of its suffix. That rehearsal also reads the bind lane's `.steady`
entries (test_bind_lane_metrics.py)."""

import json
import os
import pathlib
import subprocess
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
    "queue_wait_in_pop_ms.steady": "queue + batch former",
    "queue_wait_in_bind_ms.steady": "queue + batch former",
    "queue_wait_in_bind_ms.backlog": "queue + batch former",
    "queue_wait_in_readback_ms.steady": "queue + batch former",
    "queue_wait_in_launch_ms.steady": "queue + batch former",
    "queue_wait_counted_ms.steady": "queue + batch former",
    "admit_lag_ms.steady": "REST + store + WAL",
    "admit_lag_ms.backlog": "REST + store + WAL",
}
SUFFIXES = sorted({name.rsplit(".", 1)[1] for name in NEW})


def _first_one_chip(suffix):
    moves = ENTRIES[f"admit_lag_ms.{suffix}"]["moves"]
    return [c for c in CAT.reporting(moves) if CAT.cell(c)["chips"] == 1][0]


# two scrapes of the scheduler, 10 s apart: 200 visits to the queue, which
# waited 1.9 pod-seconds in all (9.5 ms a visit), and 150 first admissions
SCHED_0 = """
process_clock_seconds 100.0
scheduler_queue_waits_total 1000
scheduler_queue_wait_seconds_total{phase="pop"} 1.0
scheduler_queue_wait_seconds_total{phase="bind"} 2.0
scheduler_pod_admit_lag_seconds_sum 4.0
scheduler_pod_admit_lag_seconds_count 1000
"""
SCHED_1 = """
process_clock_seconds 110.0
scheduler_queue_waits_total 1200
scheduler_queue_wait_seconds_total{phase="pop"} 1.2
scheduler_queue_wait_seconds_total{phase="bind"} 2.6
scheduler_queue_wait_seconds_total{phase="readback"} 0.5
scheduler_queue_wait_seconds_total{phase="launch"} 0.3
scheduler_queue_wait_seconds_total{phase="other"} 0.001
scheduler_queue_wait_seconds_total{phase="prepare"} 0.299
scheduler_pod_admit_lag_seconds_sum 4.45
scheduler_pod_admit_lag_seconds_count 1150
"""
EXPECT = {
    "queue_wait_in_pop_ms": 1.0,       # 0.2 pod-seconds over 200 visits
    "queue_wait_in_bind_ms": 3.0,
    "queue_wait_in_readback_ms": 2.5,
    "queue_wait_in_launch_ms": 1.5,
    "queue_wait_counted_ms": 9.5,      # every phase: 1.9 over 200
    "admit_lag_ms": 3.0,               # 0.45 s over 150 admissions
}
# what the parent commit's scheduler has of these (none of the new series)
PARENT_0 = """
process_clock_seconds 100.0
scheduler_loop_phase_seconds_total{inflight="0",phase="pop"} 1.0
scheduling_pod_stage_duration_seconds_sum{stage="queue"} 0.1
scheduling_pod_stage_duration_seconds_count{stage="queue"} 10
"""
PARENT_1 = """
process_clock_seconds 110.0
scheduler_loop_phase_seconds_total{inflight="0",phase="pop"} 2.0
scheduling_pod_stage_duration_seconds_sum{stage="queue"} 0.9
scheduling_pod_stage_duration_seconds_count{stage="queue"} 110
"""


def _ctx(s0, s1):
    return {"client": {}, "sched": (Scrape(s0), Scrape(s1)),
            "api": (Scrape(""), Scrape("")), "trace": None}


def test_the_benchmark_and_its_files_agree():
    assert set(NEW) <= set(ENTRIES)
    assert CAT.problems() == []
    # in their order and none in between; entries appended after them, by
    # a later configuration or metric, leave them where they are
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index(next(iter(NEW)))
    assert names[first:first + len(NEW)] == list(NEW)


@pytest.mark.parametrize("name", sorted(NEW))
def test_every_new_entry_has_its_file(name):
    entry = ENTRIES[name]
    stem, _suffix = name.rsplit(".", 1)
    assert entry["layer"] == NEW[name]
    assert entry["source"] == "program_span"
    assert entry["unit"] == "ms" and entry["better"] == "lower"
    assert sorted(entry["workloads"]) == sorted(CAT.reporting(entry["moves"]))
    assert (REPO / "benchmark" / "layer_metrics" / f"{stem}.json").is_file()
    spec = CAT.layer_metric(name)
    assert spec["reader"] == ("hist_mean" if stem == "admit_lag_ms"
                              else "counter_ratio")


@pytest.mark.parametrize("stem", sorted(EXPECT))
def test_a_new_metric_reads_what_it_says(stem):
    spec = CAT.layer_metric(f"{stem}.steady")
    value = CAT.reader(spec["reader"])(_ctx(SCHED_0, SCHED_1), **spec["args"])
    assert value == pytest.approx(EXPECT[stem], rel=1e-9)


@pytest.mark.parametrize("suffix", SUFFIXES)
def test_nothing_is_read_from_empty_scrapes_or_the_parent(suffix):
    cell = _first_one_chip(suffix)
    for s0, s1 in (("", ""), (PARENT_0, PARENT_1)):
        got = CAT.read_layer_metrics(cell, _ctx(s0, s1))
        assert not set(got) & set(NEW), got


def _env(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               TMPDIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    return env


@pytest.mark.parametrize("suffix", SUFFIXES)
def test_the_rehearsal_reads_a_number_for_every_new_metric(suffix, tmp_path):
    """300 nodes, as the rehearsals of ISSUE 25's metrics: at <= 256 the
    host lane takes the small batches and the loop never reaches `launch`
    or `readback` with a pod waiting."""
    cell = _first_one_chip(suffix)
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell,
         "--seed", str(2**31 + 38), "--seconds", "4", "--trace", "1",
         "--rehearse-cpu", "--nodes", "300", "--out", str(tmp_path / "out")],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env=_env(tmp_path))
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["correct"] is True
    m = last["metrics"]
    for name in NEW:
        if name.endswith("." + suffix):
            assert name in m, f"{name} read nothing"
            assert isinstance(m[name]["value"], float)
            assert m[name]["value"] >= 0.0
    assert m[f"admit_lag_ms.{suffix}"]["value"] > 0.0
    if suffix == "steady":
        # the bind lane's entries, read in the same run (a rehearsal costs
        # the tier-1 run its time)
        for name in ("waves_per_bind_request.steady", "bind_lane_wait_ms.steady"):
            assert name in m, f"{name} read nothing"
            assert isinstance(m[name]["value"], float)
            assert m[name]["value"] >= 0.0
        parts = sum(m[f"queue_wait_in_{p}_ms.steady"]["value"]
                    for p in ("pop", "bind", "readback", "launch"))
        assert parts <= m["queue_wait_counted_ms.steady"]["value"] * (1 + 1e-9)
