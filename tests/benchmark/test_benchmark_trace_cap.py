"""What ends a device trace, checked without a chip: the launcher's
TraceSession against a fake counter, clock and profiler (the `trace-stop`
command or `max_launches` launches, whichever comes first, and once);
`memstats` answered while a slow profiler writes; the cap as data of the
traffic files; and, rehearsed on the CPU, a capped trace that still ends
in a result line and a trace that never stops, which ends the run with no
result and the trace named."""

import json
import os
import pathlib
import subprocess
import sys
import threading
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "benchmark"))

from harness import sched_child, supervisor  # noqa: E402
from harness.catalog import Catalog  # noqa: E402
from harness.children import RunFailure  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


class Fakes:
    """A counter the test moves, a clock that ticks 1 s a reading and a
    profiler that records its calls."""

    def __init__(self, stop_blocks_on: threading.Event | None = None):
        self.count, self.now = 100.0, 0.0
        self.calls: list = []
        self._blocks_on = stop_blocks_on

    def launches(self) -> float:
        return self.count

    def clock(self) -> float:
        self.now += 1.0
        return self.now

    def start_trace(self, trace_dir: str) -> None:
        self.calls.append(("start", trace_dir))

    def stop_trace(self) -> None:
        self.calls.append(("stop", sorted(os.listdir(self.calls[0][1]))))
        if self._blocks_on is not None:
            assert self._blocks_on.wait(20.0)

    def session(self, trace_dir, max_launches) -> sched_child.TraceSession:
        return sched_child.TraceSession(
            str(trace_dir), max_launches, launches=self.launches,
            start_trace=self.start_trace, stop_trace=self.stop_trace,
            clock=self.clock)


def _stopped(trace_dir) -> dict:
    return json.loads((trace_dir / "stopped").read_text())


def test_a_trace_ends_at_its_launch_cap(tmp_path):
    f = Fakes()
    s = f.session(tmp_path, 5)
    s.start()
    f.count += 4
    time.sleep(0.2)
    assert not s.done.is_set() and f.calls == [("start", str(tmp_path))]
    f.count += 3  # a poll can see more than the cap: the count is as read
    assert s.done.wait(10.0)
    assert _stopped(tmp_path) == {"window_s": 1.0, "launches": 7,
                                  "stopped_by": "launches", "stop_s": 1.0}
    # `stopping` stood beside the trace before the profiler was asked
    assert f.calls[1] == ("stop", ["stopping"])
    assert json.loads((tmp_path / "stopping").read_text()) == {
        "window_s": 1.0, "launches": 7, "stopped_by": "launches"}


def test_a_trace_ends_at_the_stop_command_where_the_cap_is_not_reached(tmp_path):
    f = Fakes()
    s = f.session(tmp_path, 200)
    s.start()
    f.count += 160
    time.sleep(0.1)
    s.ask_stop()
    assert s.done.wait(10.0)
    assert _stopped(tmp_path) == {"window_s": 1.0, "launches": 160,
                                  "stopped_by": "span", "stop_s": 1.0}


def test_no_cap_means_the_span_alone(tmp_path):
    f = Fakes()
    s = f.session(tmp_path, 0)
    s.start()
    f.count += 5000
    time.sleep(0.2)
    assert not s.done.is_set()
    s.ask_stop()
    assert s.done.wait(10.0)
    assert _stopped(tmp_path)["stopped_by"] == "span"


def test_a_trace_never_stops_twice(tmp_path):
    """The cap is reached, the command comes too (and again): one stop."""
    release = threading.Event()
    f = Fakes(stop_blocks_on=release)
    s = f.session(tmp_path, 3)
    s.start()
    f.count += 3
    deadline = time.monotonic() + 10.0
    while len(f.calls) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    s.ask_stop()
    s.ask_stop()
    f.count += 50
    time.sleep(0.2)
    release.set()
    assert s.done.wait(10.0)
    time.sleep(0.2)
    assert [c[0] for c in f.calls] == ["start", "stop"]
    assert _stopped(tmp_path)["stopped_by"] == "launches"
    assert _stopped(tmp_path)["launches"] == 3


def test_memstats_is_answered_while_the_profiler_writes(tmp_path):
    """`trace-stop`, then `memstats`, on the one command thread, with a
    profiler that does not return until the memory reading is there."""
    answered = threading.Event()
    f = Fakes(stop_blocks_on=answered)
    sessions = []

    def new_session(trace_dir, cap):
        sessions.append(f.session(trace_dir, cap))
        return sessions[-1]

    def memstats(path):
        pathlib.Path(path).write_text("{}")
        answered.set()

    trace_dir = tmp_path / "t"
    trace_dir.mkdir()
    sched_child._commands(
        [f"trace-start {trace_dir} 200\n", "trace-stop\n",
         f"trace-start {trace_dir} 200\n",  # refused: the first still runs
         "\n", "no-such-command\n", f"memstats {tmp_path / 'm.json'}\n"],
        new_session, memstats)
    assert (tmp_path / "m.json").exists()
    assert len(sessions) == 1 and sessions[0].done.wait(10.0)
    assert _stopped(trace_dir)["stopped_by"] == "span"
    assert [c[0] for c in f.calls] == ["start", "stop"]


def test_a_second_trace_starts_once_the_first_has_stopped(tmp_path):
    """The sweep's several windows, one trace each."""
    f = Fakes()
    sessions = []

    def lines():
        for d in ("a", "b"):
            (tmp_path / d).mkdir()
            yield f"trace-start {tmp_path / d}\n"  # no cap given: none
            yield "trace-stop\n"
            assert sessions[-1].done.wait(10.0)

    def new_session(trace_dir, cap):
        assert cap == 0
        sessions.append(f.session(trace_dir, cap))
        return sessions[-1]

    sched_child._commands(lines(), new_session, lambda path: None)
    assert len(sessions) == 2
    assert (tmp_path / "a" / "stopped").exists()
    assert (tmp_path / "b" / "stopped").exists()


@pytest.mark.parametrize("cell", CELLS)
def test_the_cap_is_data_of_the_cells_traffic_file(cell):
    cat = Catalog(str(REPO))
    tr = cat.traffic(cat.cell(cell))["trace"]
    assert isinstance(tr["max_launches"], int) and tr["max_launches"] >= 1
    assert tr["seconds"] > 0 and tr["start_s"] >= 0
    assert "max_launches" in cat.traffic(cat.cell(cell))["trace_why"]


def test_the_cap_is_no_option_and_no_environment_variable():
    """Read from the traffic file's `trace` block and from nowhere else."""
    assert "max_launches" not in (REPO / "benchmark/run.py").read_text()
    for path in (REPO / "benchmark").rglob("*.py"):
        text = path.read_text()
        assert "MAX_LAUNCHES" not in text, path
        if "max_launches" in text:
            assert path.name in ("supervisor.py", "sched_child.py"), path
    assert "tr.get('max_launches'" in (
        REPO / "benchmark/harness/supervisor.py").read_text()


# -- rehearsed on the CPU -------------------------------------------------------


def _env(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    # one CPU device: the 8-device mesh of conftest.py only slows compiles
    monkeypatch.delenv("XLA_FLAGS", raising=False)


def _leftovers(marker: str) -> list:
    out = subprocess.run(["pgrep", "-f", marker], capture_output=True,
                         text=True).stdout.split()
    return [p for p in out if int(p) != os.getpid()]


def test_a_capped_trace_still_ends_in_a_result(tmp_path, monkeypatch):
    """At 300 nodes the rehearsal launches waves; with the cell's cap
    lowered to 12 the launches end the trace, early, and the run ends
    with every key of its line and the rule that ended the trace in
    detail.json."""
    _env(tmp_path, monkeypatch)
    real = Catalog.traffic

    def traffic(self, cell):
        t = json.loads(json.dumps(real(self, cell)))
        t["trace"].update(start_s=0.2, seconds=5.0, max_launches=12)
        return t

    monkeypatch.setattr(Catalog, "traffic", traffic)
    out = tmp_path / "out"
    result = supervisor.run_cell(
        str(REPO), "perf5k-basic.steady", 2**31 + 5, 6.0, True, str(out),
        rehearse_cpu=True, nodes=300)
    assert result["correct"] is True, result["compared"]
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "breakdown", "compared"]
    stopped = json.loads((out / "detail.json").read_text())["trace_stopped"]
    assert stopped["stopped_by"] == "launches"
    assert 12 <= stopped["launches"] <= 20  # the cap and the poll's slack
    assert 0 < stopped["window_s"] < 4.5 and stopped["stop_s"] > 0
    assert result["device"]["window_s"] == stopped["window_s"]
    assert not _leftovers(str(tmp_path))


def test_a_trace_that_never_stops_is_no_result_and_names_the_trace(
        tmp_path, monkeypatch, capsys):
    """The profiler that never finishes writing (`--fault
    slow-trace-stop`), through the one command: `benchmark: no result:`
    with the trace in the reason, nothing on stdout, no process left, and
    never a NOT CORRECT for want of the devices."""
    import run as bench_run

    _env(tmp_path, monkeypatch)
    monkeypatch.setattr(supervisor, "TRACE_STOP_DEADLINE_S", 3.0)
    real = supervisor.run_cell
    monkeypatch.setattr(
        supervisor, "run_cell",
        lambda *a, **kw: real(*a, **kw, fault="slow-trace-stop"))
    rc = bench_run.main([
        "--workload", "perf5k-podaffinity.backlog", "--seed", "7",
        "--seconds", "3", "--trace", "1", "--rehearse-cpu", "--nodes", "64",
        "--out", str(tmp_path / "out")])
    said = capsys.readouterr()
    assert rc == 1 and said.out.strip() == ""
    last = said.err.strip().splitlines()[-1]
    assert last.startswith("benchmark: no result: the device trace in ")
    assert "did not stop: waited 3 s past the drain" in last
    assert "decided" in last and "by 'span' after" in last
    assert "the profiler is still writing it" in last
    assert "NOT CORRECT" not in said.err
    assert not _leftovers(str(tmp_path))


def test_a_trace_that_was_never_stopped_is_named_too(tmp_path, monkeypatch):
    """No `stopping` beside the trace: the launcher never decided."""

    class Gone:
        def alive(self):
            return False

    run = supervisor.Run.__new__(supervisor.Run)
    run.sched = Gone()
    with pytest.raises(RunFailure, match="it was never stopped .*"
                       r"scheduler alive: False"):
        run._wait_for_trace(str(tmp_path))
    (tmp_path / "stopped").write_text("{}")
    run._wait_for_trace(str(tmp_path))  # written: returns
