"""The benchmark's own tests take an addition as it comes: a configuration,
a cell of it and per-layer entries, added as new files and appended
entries in a temporary copy of BENCHMARK.json, benchmark/ and
tests/benchmark/. Every copied test that reads BENCHMARK.json and starts
no child process then passes, run in a process of its own against the
copy, and no file of the copy that was there changed but BENCHMARK.json's
lists that name the new cell. A test that pins where an entry stands, or
how many there are, fails here before it refuses the next configuration."""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import xml.etree.ElementTree as ET

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "benchmark"))

from harness.catalog import Catalog  # noqa: E402

# the addition: names no later PR will take
CONFIG = "madeup-density1k"
CELL = f"{CONFIG}.backlog"
OWN = "madeup_density_probe.backlog"  # read in the new cell alone
STEM = "madeup_split_probe"           # a split pair, `.steady` and `.backlog`
EVERY = "madeup_setup_probe_s"        # no `workloads`: read in every cell

# what in a test's own source says it starts a child process
CHILD = ("subprocess", "Popen", "run_cell", ".main(")

PLUGIN = f'''
import inspect

CHILD = {CHILD!r}


def pytest_collection_modifyitems(config, items):
    """The tests that start no child process, and only those."""
    keep = [i for i in items
            if not any(w in inspect.getsource(i.function) for w in CHILD)]
    config.hook.pytest_deselected(items=[i for i in items if i not in keep])
    items[:] = keep
'''


def _files(root):
    return {p.relative_to(root): p.read_bytes() for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts
            and p.name != "BENCHMARK.json"}


def _copy(root):
    ignore = shutil.ignore_patterns("__pycache__")
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(REPO / "benchmark", root / "benchmark", ignore=ignore)
    shutil.copytree(REPO / "tests/benchmark", root / "tests/benchmark",
                    ignore=ignore)


def _write(path, obj):
    path.write_text(json.dumps(obj, indent=1) + "\n")


def _add(root):
    """The density shape over perf5k-basic's pods (1,000 nodes, 30,000
    residents), its `.backlog` cell, one per-layer entry under its own
    file and one split pair under a stem file, appended after the last,
    and one entry without `workloads`, which every cell reports."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bd = root / "benchmark"
    cfg = json.loads((bd / "configs/perf5k-basic.json").read_text())
    cfg["name"] = CONFIG
    cfg["nodes"]["count"], cfg["residents"]["count"] = 1000, 30000
    _write(bd / f"configs/{CONFIG}.json", cfg)
    basic = next(c for c in bench["configs"] if c["name"] == "perf5k-basic")
    bench["configs"].append(dict(
        basic, name=CONFIG, file=f"benchmark/configs/{CONFIG}.json",
        why="made up for the test: 30,000 pods on 1,000 nodes"))
    shutil.copy(bd / "traffic/backlog.perf5k-basic.json",
                bd / f"traffic/backlog.{CONFIG}.json")
    bench["workloads"].append({"name": CELL, "config": CONFIG,
                               "traffic": "backlog", "chips": 1,
                               "why": "made up for the test"})
    # one more cell that reports `bound_pods_per_s`: in that metric's list
    # and in the list of every split entry that moves it
    for m in bench["end_to_end"]:
        if m["name"] == "bound_pods_per_s":
            m["workloads"].append(CELL)
    for m in bench["per_layer"]:
        split = not (bd / "layer_metrics" / f"{m['name']}.json").exists()
        if split and m["moves"] == "bound_pods_per_s":
            m["workloads"].append(CELL)
    _write(bd / f"layer_metrics/{OWN}.json", {"reader": "counter_ratio", "args": {
        "num": {"source": "sched", "name": "madeup_density_total"},
        "den": {"client": "seconds"}}})
    _write(bd / f"layer_metrics/{STEM}.json", {"reader": "hist_mean", "args": {
        "source": "sched", "name": "madeup_split_probe_seconds",
        "scale": 1000.0}})
    _write(bd / f"layer_metrics/{EVERY}.json", {"reader": "hist_mean", "args": {
        "source": "sched", "name": "madeup_setup_probe_seconds"}})
    cat = Catalog(str(root))
    bench["per_layer"].append({
        "name": OWN, "unit": "1/s", "better": "higher",
        "source": "program_counter", "layer": "made-up layer",
        "moves": "bound_pods_per_s", "workloads": [CELL]})
    for suffix, moves in (("steady", "create_to_bound_p50_ms"),
                          ("backlog", "bound_pods_per_s")):
        listed = cat.reporting(moves) + ([CELL] if suffix == "backlog" else [])
        bench["per_layer"].append({
            "name": f"{STEM}.{suffix}", "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "REST + store + WAL",
            "moves": moves, "workloads": listed})
    bench["per_layer"].append({
        "name": EVERY, "unit": "s", "better": "lower",
        "source": "program_span", "layer": "whole path", "moves": "setup_s"})
    _write(root / "BENCHMARK.json", bench)


def _only_appended(before, after):
    """Entries appended; of those that were there, only a list of cells
    that now names the new cell changed."""
    lists = ("configs", "workloads", "end_to_end", "per_layer")
    assert ({k: v for k, v in after.items() if k not in lists}
            == {k: v for k, v in before.items() if k not in lists})
    for key in ("configs", "workloads"):
        assert after[key][:len(before[key])] == before[key], key
    for key in ("end_to_end", "per_layer"):
        for old, new in zip(before[key], after[key]):
            assert new == old or new == dict(
                old, workloads=old["workloads"] + [CELL]), old["name"]
    assert len(after["end_to_end"]) == len(before["end_to_end"])
    assert [m["name"] for m in after["per_layer"][len(before["per_layer"]):]] == [
        OWN, f"{STEM}.steady", f"{STEM}.backlog", EVERY]


def _benchmark_tests(root):
    """The copied test files that read BENCHMARK.json, but this one."""
    me = pathlib.Path(__file__).name
    return sorted(str(p) for p in (root / "tests/benchmark").glob("test_*.py")
                  if p.name != me and any(
                      w in p.read_text() for w in ("BENCHMARK.json", "Catalog(")))


def test_an_addition_passes_every_benchmark_test_that_reads_the_file(tmp_path):
    root = tmp_path / "copy"
    root.mkdir()
    _copy(root)
    before = _files(root)
    bench_before = json.loads((root / "BENCHMARK.json").read_text())
    _add(root)
    assert Catalog(str(root)).problems() == []
    _only_appended(bench_before, json.loads((root / "BENCHMARK.json").read_text()))
    assert _files(root).items() >= before.items()

    plugin = tmp_path / "plugin"
    plugin.mkdir()
    (plugin / "in_process_only.py").write_text(PLUGIN)
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    # the program's package from this checkout, the plugin from its folder;
    # `harness` and the tests resolve to the copy through their own paths
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(plugin), str(REPO)]))
    xml = tmp_path / "junit.xml"
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-p", "in_process_only",
         f"--basetemp={tmp_path / 'basetemp'}", f"--junitxml={xml}",
         *_benchmark_tests(root)],
        capture_output=True, text=True, timeout=240, cwd=root, env=env)
    failed = [ln for ln in r.stdout.splitlines()
              if ln.startswith(("FAILED", "ERROR"))]
    assert r.returncode == 0, (failed, r.stdout[-6000:], r.stderr[-2000:])
    cases = [(c.get("classname"), c.get("name"))
             for c in ET.parse(xml).getroot().iter("testcase")]
    ran = {name for _cls, name in cases}
    # the tests that pin the catalogue ran, and saw the addition
    assert {"test_the_benchmark_and_its_files_agree",
            "test_the_layers_are_ones_the_benchmark_already_names",
            "test_the_four_stand_in_order_one_after_another",
            f"test_benchmark_json_entry[per_layer:{STEM}.backlog]",
            f"test_benchmark_json_entry[per_layer:{EVERY}]",
            f"test_a_pod_carries_its_configurations_namespace[{CELL}]"} <= ran
    assert "test_rehearsal_twice_in_a_row[perf5k-basic.steady]" not in ran
    assert len(cases) >= 200
    # and the run changed nothing that was there
    assert _files(root).items() >= before.items()
