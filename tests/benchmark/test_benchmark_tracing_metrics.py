"""The per-layer metrics of ISSUE 25 (loop phases, pod stages, the
apiserver's write path, stalls) and ISSUE 29's `api_background_ms_per_s`
(the apiserver's background passes: WAL compaction's locked copy): each entry of BENCHMARK.json has its
file, each file reads what it says from two scrapes, nothing from two
empty ones, and a number in a CPU rehearsal of its cell."""

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

STEMS = [
    "queue_wait_ms", "cache_lock_wait_ms_per_wave", "guard_ms_per_wave",
    "assume_ms_per_wave", "deferred_pods_per_wave", "loop_bind_share",
    "loop_pop_share", "idle_in_bind_share", "bind_post_ms", "api_create_ms",
    "api_bind_ms", "store_lock_wait_ms", "store_apply_ms", "wal_append_ms",
    "watch_notify_ms", "wal_records_per_fsync", "watch_delivery_ms",
    "sched_gc_pause_ms_per_s", "api_gc_pause_ms_per_s",
    "sched_background_ms_per_s", "api_background_ms_per_s",
]
# suffix -> (the cells that report it, the end-to-end metric it moves)
CELLS_OF = {"backlog": (["perf5k-podaffinity.backlog", "perf5k-basic.backlog"],
                        "bound_pods_per_s"),
            "steady": (["perf5k-basic.steady"], "create_to_bound_p50_ms")}
CELL_OF = {suffix: (cells[0], moves)
           for suffix, (cells, moves) in CELLS_OF.items()}
SUFFIX_OF = {cell: suffix for suffix, (cells, _) in CELLS_OF.items()
             for cell in cells}

# two scrapes of each child, 10 s apart on the process's own clock, with
# round numbers: what each metric has to read from them
SCHED_0 = """
process_clock_seconds 100.0
scheduler_wave_batches_total 10
"""
SCHED_1 = """
process_clock_seconds 110.0
scheduler_wave_batches_total 30
scheduler_wave_deferred_pods_total 5
scheduler_cache_lock_wait_seconds_sum 0.004
scheduler_cache_lock_wait_seconds_count 25
scheduling_pod_stage_duration_seconds_sum{stage="queue"} 0.8
scheduling_pod_stage_duration_seconds_count{stage="queue"} 100
scheduling_pod_stage_duration_seconds_sum{stage="bind"} 4.0
scheduling_pod_stage_duration_seconds_count{stage="bind"} 100
scheduling_stage_duration_seconds_sum{stage="guard"} 0.01
scheduling_stage_duration_seconds_count{stage="guard"} 20
scheduling_stage_duration_seconds_sum{stage="assume"} 0.03
scheduling_stage_duration_seconds_count{stage="assume"} 20
scheduler_loop_phase_seconds_total{inflight="0",phase="bind"} 6.0
scheduler_loop_phase_seconds_total{inflight="1",phase="bind"} 1.0
scheduler_loop_phase_seconds_total{inflight="0",phase="pop"} 1.5
scheduler_loop_phase_seconds_total{inflight="1",phase="readback"} 1.0
scheduler_loop_phase_seconds_total{inflight="0",phase="other"} 0.5
rest_client_request_duration_seconds_sum{resource="pods/binding",verb="POST"} 0.6
rest_client_request_duration_seconds_count{resource="pods/binding",verb="POST"} 100
rest_client_request_duration_seconds_sum{resource="pods",verb="GET"} 9.0
rest_client_request_duration_seconds_count{resource="pods",verb="GET"} 1
process_gc_pause_seconds_sum{generation="0"} 0.01
process_gc_pause_seconds_count{generation="0"} 400
process_gc_pause_seconds_sum{generation="2"} 0.29
process_gc_pause_seconds_count{generation="2"} 2
scheduler_background_pass_seconds_sum{task="antientropy"} 0.04
scheduler_background_pass_seconds_count{task="antientropy"} 2
scheduler_background_pass_seconds_sum{task="assume_ttl"} 0.01
scheduler_background_pass_seconds_count{task="assume_ttl"} 10
"""
API_0 = """
process_clock_seconds 50.0
wal_records_appended_total 1000
wal_fsyncs_total 1000
"""
API_1 = """
process_clock_seconds 60.0
wal_records_appended_total 1300
wal_fsyncs_total 1200
apiserver_request_duration_seconds_sum{resource="pods",verb="POST"} 0.45
apiserver_request_duration_seconds_count{resource="pods",verb="POST"} 150
apiserver_request_duration_seconds_sum{resource="pods/binding",verb="POST"} 0.3
apiserver_request_duration_seconds_count{resource="pods/binding",verb="POST"} 150
apiserver_request_duration_seconds_sum{resource="metrics",verb="GET"} 5.0
apiserver_request_duration_seconds_count{resource="metrics",verb="GET"} 2
store_lock_wait_seconds_sum{kind="pods",op="create"} 0.1
store_lock_wait_seconds_count{kind="pods",op="create"} 150
store_lock_wait_seconds_sum{kind="pods",op="bind"} 0.2
store_lock_wait_seconds_count{kind="pods",op="bind"} 150
store_commit_stage_seconds_sum{kind="pods",op="create",stage="apply"} 0.05
store_commit_stage_seconds_count{kind="pods",op="create",stage="apply"} 150
store_commit_stage_seconds_sum{kind="pods",op="bind",stage="apply"} 0.01
store_commit_stage_seconds_count{kind="pods",op="bind",stage="apply"} 150
store_commit_stage_seconds_sum{kind="pods",op="create",stage="wal_append"} 0.06
store_commit_stage_seconds_count{kind="pods",op="create",stage="wal_append"} 150
store_commit_stage_seconds_sum{kind="pods",op="bind",stage="wal_append"} 0.03
store_commit_stage_seconds_count{kind="pods",op="bind",stage="wal_append"} 150
store_commit_stage_seconds_sum{kind="pods",op="create",stage="notify"} 0.02
store_commit_stage_seconds_count{kind="pods",op="create",stage="notify"} 150
store_commit_stage_seconds_sum{kind="pods",op="bind",stage="notify"} 0.01
store_commit_stage_seconds_count{kind="pods",op="bind",stage="notify"} 150
apiserver_watch_delivery_seconds_sum{kind="pods"} 1.2
apiserver_watch_delivery_seconds_count{kind="pods"} 600
apiserver_watch_delivery_seconds_sum{kind="nodes"} 50.0
apiserver_watch_delivery_seconds_count{kind="nodes"} 1
process_gc_pause_seconds_sum{generation="1"} 0.05
process_gc_pause_seconds_count{generation="1"} 30
store_background_pass_seconds_sum{task="wal_compact_copy"} 0.8
store_background_pass_seconds_count{task="wal_compact_copy"} 1
"""
EXPECT = {
    "queue_wait_ms": 8.0,
    "cache_lock_wait_ms_per_wave": 0.2,      # 0.004 s over 20 waves
    "guard_ms_per_wave": 0.5,
    "assume_ms_per_wave": 1.5,
    "deferred_pods_per_wave": 0.25,
    "loop_bind_share": 70.0,                 # 7 of the 10 s
    "loop_pop_share": 15.0,
    "idle_in_bind_share": 75.0,              # 6 of the 8 s with the chip idle
    "bind_post_ms": 6.0,
    "api_create_ms": 3.0,
    "api_bind_ms": 2.0,
    "store_lock_wait_ms": 1.0,               # both ops together
    "store_apply_ms": 0.2,
    "wal_append_ms": 0.3,
    "watch_notify_ms": 0.1,
    "wal_records_per_fsync": 1.5,
    "watch_delivery_ms": 2.0,
    "sched_gc_pause_ms_per_s": 30.0,         # 0.3 s of pauses in 10 s
    "api_gc_pause_ms_per_s": 5.0,
    "sched_background_ms_per_s": 5.0,
    "api_background_ms_per_s": 80.0,         # one 0.8 s compaction copy in 10 s
}


def _ctx(s0, s1, a0, a1):
    return {"client": {}, "sched": (Scrape(s0), Scrape(s1)),
            "api": (Scrape(a0), Scrape(a1)), "trace": None}


def _entries():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    return {m["name"]: m for m in bench["per_layer"]}


@pytest.mark.parametrize("suffix", sorted(CELL_OF))
@pytest.mark.parametrize("stem", STEMS)
def test_every_new_entry_has_its_file(stem, suffix):
    entry = _entries()[f"{stem}.{suffix}"]
    cells, moves = CELLS_OF[suffix]
    assert entry["workloads"] == cells and entry["moves"] == moves
    assert entry["source"] in ("program_span", "program_counter")
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    spec = Catalog(str(REPO)).layer_metric(entry["name"])
    assert spec["reader"] in ("hist_mean", "counter_ratio")
    assert (REPO / "benchmark" / "layer_metrics" / f"{stem}.json").is_file()
    assert (REPO / "benchmark" / "readers" / f"{spec['reader']}.py").is_file()


LAYERS = {"load generator", "queue + batch former", "host encode",
          "wave kernel", "readback + guards", "REST + store + WAL", "device",
          "whole path"}


def test_the_layers_are_ones_the_benchmark_already_names():
    """PERF.md section 3's eight, letter for letter; a retired metric
    (PR 29: `readback_wait_ms_per_wave`, the last of `readback + guards`
    that was not ISSUE 25's) takes no layer with it."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert {m["layer"] for m in bench["per_layer"]} == LAYERS
    assert {m["layer"] for m in bench["per_layer"]
            if m["name"].rsplit(".", 1)[0] in STEMS} <= LAYERS


@pytest.mark.parametrize("stem", STEMS)
def test_a_new_metric_reads_what_it_says(stem):
    cat = Catalog(str(REPO))
    spec = cat.layer_metric(f"{stem}.steady")
    value = cat.reader(spec["reader"])(
        _ctx(SCHED_0, SCHED_1, API_0, API_1), **spec["args"])
    assert value == pytest.approx(EXPECT[stem], rel=1e-9)


@pytest.mark.parametrize("suffix", sorted(CELL_OF))
def test_from_two_scrapes_of_the_parent_nothing_new_is_read(suffix):
    """A program without the new series (the parent commit, both scrapes
    holding what it always had) reads no new metric, and none raises —
    but for the two whose denominator the parent has too: a count of
    waves with no deferral or lock-wait series beside it reads 0."""
    cat = Catalog(str(REPO))
    old_s0 = "scheduler_wave_batches_total 10\n"
    old_s1 = "scheduler_wave_batches_total 30\n"
    old_a = "wal_fsync_duration_seconds_sum 1\nwal_fsync_duration_seconds_count 9\n"
    got = cat.read_layer_metrics(CELL_OF[suffix][0],
                                 _ctx(old_s0, old_s1, "", old_a))
    new = {n: v["value"] for n, v in got.items()
           if n.rsplit(".", 1)[0] in STEMS}
    assert new == {f"cache_lock_wait_ms_per_wave.{suffix}": 0.0,
                   f"deferred_pods_per_wave.{suffix}": 0.0}
    empty = cat.read_layer_metrics(CELL_OF[suffix][0], _ctx("", "", "", ""))
    assert empty == {}


def _env(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               TMPDIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    return env


@pytest.mark.parametrize("cell", sorted(SUFFIX_OF))
def test_the_rehearsal_reads_a_number_for_every_new_metric(cell, tmp_path):
    """300 nodes, not 64: on a cluster of <= 256 nodes the program's own
    host lane takes the window's 1-4 pod batches, no wave is launched,
    and the per-wave metrics have nothing to divide by."""
    suffix = SUFFIX_OF[cell]
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell,
         "--seed", str(2**31 + 25), "--seconds", "4", "--trace", "1",
         "--rehearse-cpu", "--nodes", "300", "--out", str(tmp_path / "out")],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env=_env(tmp_path))
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["correct"] is True
    for stem in STEMS:
        m = last["metrics"].get(f"{stem}.{suffix}")
        assert m is not None, f"{stem}.{suffix} read nothing"
        assert isinstance(m["value"], float) and m["value"] >= 0.0
    m = last["metrics"]
    assert m[f"wal_records_per_fsync.{suffix}"]["value"] >= 1.0
    for share in ("loop_bind_share", "loop_pop_share", "idle_in_bind_share"):
        assert m[f"{share}.{suffix}"]["value"] <= 100.0
