"""The one command, rehearsed on the CPU at its configuration's
`rehearsal_nodes` (64 unless it says more): each cell twice in a row, the
last line, no process left; the refusal without a chip; and
the run driven with the timed path broken underneath, or with a guarantee
of the configuration switched off, which has to come out not correct."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "benchmark"))
CELLS = [w["name"] for w in
         json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]
SECONDS = 4


def _rehearsal(cell: str) -> tuple:
    """(--nodes, the pods the window has to attempt) of a cell's
    rehearsal: the configuration's `rehearsal_nodes`, and the supervisor's
    own arithmetic of a small cluster's rate."""
    from harness.catalog import Catalog
    from harness.supervisor import offered_rate

    cat = Catalog(str(REPO))
    c = cat.cell(cell)
    config = cat.config(c["config"])
    nodes = cat.rehearsal_nodes(config)
    rate = offered_rate(cat.traffic(c), nodes / config["nodes"]["count"])
    return nodes, int(rate * SECONDS + 1e-9)


def _env(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               TMPDIR=str(tmp_path))
    # one CPU device: the 8-device mesh of conftest.py only slows compiles
    env.pop("XLA_FLAGS", None)
    return env


def _leftovers(marker: str) -> list:
    out = subprocess.run(["pgrep", "-f", marker], capture_output=True,
                         text=True).stdout.split()
    return [p for p in out if int(p) != os.getpid()]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_twice_in_a_row(cell, tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("rehearsal")
    nodes, attempted = _rehearsal(cell)
    for attempt, trace in ((1, 0), (2, 1)):
        out = tmp_path / f"out{attempt}"
        r = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", cell,
             "--seed", str(2**31 + attempt), "--seconds", str(SECONDS),
             "--trace", str(trace), "--rehearse-cpu", "--nodes", str(nodes),
             "--out", str(out)],
            capture_output=True, text=True, timeout=300, cwd=REPO,
            env=_env(tmp_path))
        assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
        last = json.loads(r.stdout.strip().splitlines()[-1])
        want = KEYS[:5] + (["breakdown"] if trace else []) + KEYS[5:]
        assert list(last) == want
        assert last["correct"] is True and last["failed"] == 0
        assert last["attempted"] == attempted
        assert last["device"]["platform"] == "cpu"
        assert all(v == 0 and lim == 0 for v, lim in last["compared"].values())
        bench = json.loads((REPO / "BENCHMARK.json").read_text())
        names = {m["name"] for m in bench["per_layer" if trace else "end_to_end"]
                 if cell in m.get("workloads", [cell])}
        assert set(last["metrics"]) <= names
        if not trace:
            assert set(last["metrics"]) == names
            assert all(m["value"] > 0 for m in last["metrics"].values())
        else:
            assert {"busy_s", "window_s"} <= set(last["device"])
            # which rule ended the trace: the span, the cap is far off
            stopped = json.loads(
                (out / "detail.json").read_text())["trace_stopped"]
            assert stopped["stopped_by"] == "span"
            assert stopped["window_s"] == last["device"]["window_s"]
        # each number compared stands beside its limit at the end of stderr
        tail = r.stderr.strip().splitlines()[-len(last["compared"]):]
        assert all(ln.startswith("compared ") for ln in tail), tail
        assert not _leftovers(str(out)) and not _leftovers(str(tmp_path))
        assert not list(tmp_path.glob("bench_wal_*"))
    assert (tmp_path / "out1" / "detail.json").is_file()


def test_without_a_chip_no_result_line(tmp_path):
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "2", "--trace", "0",
         "--out", str(tmp_path / "out")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env=_env(tmp_path))
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no result" in r.stderr
    assert not _leftovers(str(tmp_path))


def test_outside_a_checkout_no_result_line(tmp_path):
    import shutil

    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, timeout=60, cwd=tmp_path,
        env=_env(tmp_path))
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_an_unknown_workload_is_no_result(tmp_path):
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "no.such",
         "--rehearse-cpu", "--nodes", "64"],
        capture_output=True, text=True, timeout=60, cwd=REPO,
        env=_env(tmp_path))
    assert r.returncode != 0 and r.stdout.strip() == ""


# the run with a fault planted under the timed path, or a guarantee off:
# (argument, the compared number that has to catch it)
BROKEN = [
    ({"fault": "alter-bind"}, "infeasible"),   # an answer altered where produced
    ({"fault": "drop-half"}, "unbound"),       # half of the answers left out
    ({"fault": "no-bind"}, "unbound"),         # the state returned unchanged
    ({"control": "no-wal"}, "wal_missing"),    # durability switched off
    ({"control": "platform-cpu"}, "off_device"),  # the device left out
]


def test_the_host_lane_switched_on_is_counted_off_device(tmp_path, monkeypatch):
    """At 300 nodes (over the size at which the program itself uses the
    host lane) the lane switched on from outside has to show."""
    from harness import supervisor

    for k, v in _env(tmp_path).items():
        monkeypatch.setenv(k, v)
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    result = supervisor.run_cell(
        str(REPO), "perf5k-basic.steady", 7, 3.0, False,
        str(tmp_path / "out"), rehearse_cpu=True, nodes=300,
        fault="host-lane")
    value, limit = result["compared"]["off_device"]
    assert result["correct"] is False and value >= 10 and limit == 0
    assert result["compared"]["unbound"][0] == 0


@pytest.mark.parametrize("how,catches", BROKEN,
                         ids=[next(iter(h.values())) for h, _ in BROKEN])
def test_broken_runs_come_out_not_correct(how, catches, tmp_path, monkeypatch):
    from harness import supervisor

    for k, v in _env(tmp_path).items():
        monkeypatch.setenv(k, v)
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    # platform-cpu: a run that expects the TPU and gets the CPU backend
    rehearse = how.get("control") != "platform-cpu"
    result = supervisor.run_cell(
        str(REPO), "perf5k-podaffinity.backlog", 7, 3.0, False,
        str(tmp_path / "out"), rehearse_cpu=rehearse, nodes=64,
        drain_deadline_s=4.0, warmup_deadline_s=8.0, **how)
    assert result["correct"] is False
    value, limit = result["compared"][catches]
    assert limit == 0 and value >= 10, result["compared"]
    assert list(result)[-1] == "compared"
    assert not _leftovers(str(tmp_path))
