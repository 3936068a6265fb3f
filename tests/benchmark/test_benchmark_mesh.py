"""A cell on four chips, without the chips: in a CPU rehearsal the scheduler
child alone is given as many virtual CPU devices as the cell asks chips
(its environment, nobody else's), so it builds the mesh the cell is about
and goes through `parallel/sharded.py`; on the chip nothing is given and
fewer devices than `chips` is a run with no result. Then each four-chip
cell of BENCHMARK.json at 300 nodes, where waves are launched on that
mesh, and the same with a bind altered underneath."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "benchmark"))
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
FOUR = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]


def test_a_rehearsal_gives_the_scheduler_child_the_cells_chips():
    from harness.supervisor import HOST_DEVICES_FLAG, rehearsal_mesh_env

    assert rehearsal_mesh_env(1) == {} == rehearsal_mesh_env(1, "--xla_foo=1")
    assert rehearsal_mesh_env(4) == {"XLA_FLAGS": f"{HOST_DEVICES_FLAG}=4"}
    # conftest.py's eight are replaced, another flag is kept
    assert rehearsal_mesh_env(4, f"--xla_foo=1 {HOST_DEVICES_FLAG}=8") == {
        "XLA_FLAGS": f"--xla_foo=1 {HOST_DEVICES_FLAG}=4"}


def _run_of(chips: int, rehearse: bool):
    """A Run that was never set up, of a made-up cell on `chips` chips."""
    from harness import supervisor

    run = supervisor.Run.__new__(supervisor.Run)
    run.cell, run.rehearse_cpu = {"name": "x.y", "chips": chips}, rehearse
    return run


@pytest.mark.parametrize("chips", [1, 4])
def test_only_a_rehearsals_child_is_given_virtual_devices(chips, monkeypatch):
    """The child's environment alone; on the chip nothing is forced and
    the supervisor's own pin to the CPU is taken off."""
    from harness.supervisor import HOST_DEVICES_FLAG

    monkeypatch.setenv("XLA_FLAGS", f"{HOST_DEVICES_FLAG}=8")
    monkeypatch.setenv("BENCH_RUN", "3")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    before = dict(os.environ)
    env = _run_of(chips, rehearse=True)._sched_env()
    assert env["JAX_PLATFORMS"] == "cpu" and "BENCH_RUN" not in env
    assert env["XLA_FLAGS"] == f"{HOST_DEVICES_FLAG}={4 if chips == 4 else 8}"
    env = _run_of(chips, rehearse=False)._sched_env()
    assert "JAX_PLATFORMS" not in env and "BENCH_RUN" not in env
    assert env["XLA_FLAGS"] == f"{HOST_DEVICES_FLAG}=8"  # ours: not touched
    assert dict(os.environ) == before


@pytest.mark.parametrize("rehearse", [False, True], ids=["chip", "rehearsal"])
def test_fewer_devices_than_chips_is_no_result(rehearse, monkeypatch):
    """Outside a rehearsal as in one: a scheduler that found one device
    where the cell asks for four ends the run, with both numbers named."""
    from harness.children import RunFailure
    from harness.scrape import Scrape

    def says(devices):
        return lambda: {"sched": Scrape(
            'scheduler_device_info{devices="%s",platform="tpu"} 1\n' % devices
            if devices else "")}

    run = _run_of(4, rehearse)
    run.scrapes = says(1)
    with pytest.raises(RunFailure, match=r"the cell asks for 4 chip\(s\); "
                       "the scheduler found 1"):
        run._require_chips()
    run.scrapes = says(None)  # it said nothing at all
    with pytest.raises(RunFailure, match="the scheduler found 0"):
        run._require_chips()
    run.scrapes = says(4)
    run._require_chips()
    one = _run_of(1, rehearse)  # four devices serve a one-chip cell too
    one.scrapes = says(4)
    one._require_chips()


def _env(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    # conftest.py's eight devices: the child gets the cell's four regardless
    monkeypatch.delenv("XLA_FLAGS", raising=False)


def _leftovers(marker: str) -> list:
    out = subprocess.run(["pgrep", "-f", marker], capture_output=True,
                         text=True).stdout.split()
    return [p for p in out if int(p) != os.getpid()]


@pytest.mark.parametrize("cell", FOUR)
def test_a_four_chip_cell_launches_its_waves_on_a_cpu_mesh(
        cell, tmp_path, monkeypatch):
    """At 300 nodes (over the size at which the program's host lane takes
    the small batches) the window's waves go through the node-sharded
    program on four virtual devices, every placement is feasible, and
    every per-layer metric of the cell that is not the device trace's
    reads a number."""
    from harness import supervisor

    _env(tmp_path, monkeypatch)
    out = tmp_path / "out"
    result = supervisor.run_cell(
        str(REPO), cell, 2**31 + 30, 4.0, True, str(out),
        rehearse_cpu=True, nodes=300)
    assert result["correct"] is True, result["compared"]
    assert all(v == 0 and lim == 0 for v, lim in result["compared"].values())
    assert result["device"]["count"] == 4
    detail = json.loads((out / "detail.json").read_text())
    assert detail["scheduler_info"]["devices"] == "4"
    assert detail["scheduler_info"]["mesh"] == "4"
    assert detail["wave_batches_in_window"] >= 10
    want = {m["name"] for m in BENCH["per_layer"]
            if cell in m.get("workloads", [cell])
            and m["source"] != "device_trace"}
    assert want and set(result["metrics"]) == want
    assert not _leftovers(str(tmp_path))


def test_a_bind_altered_under_the_mesh_comes_out_infeasible(
        tmp_path, monkeypatch):
    """The control of a four-chip cell, at a size a test run can hold:
    what a fault of the exchange between the chips would do (a wrong
    placement: the partitioner's own collectives cannot be left out from
    here) is planted where the answer is produced, and `infeasible`
    catches it."""
    from harness import supervisor

    _env(tmp_path, monkeypatch)
    result = supervisor.run_cell(
        str(REPO), FOUR[0], 7, 3.0, False, str(tmp_path / "out"),
        rehearse_cpu=True, nodes=300, fault="alter-bind",
        drain_deadline_s=4.0, warmup_deadline_s=8.0)
    value, limit = result["compared"]["infeasible"]
    assert result["correct"] is False and limit == 0 and value >= 10
    assert result["device"]["count"] == 4
    assert not _leftovers(str(tmp_path))
