"""The harness is driven by data: a made-up cell and a made-up per-layer
metric, added as files and BENCHMARK.json entries in a temporary copy,
are found with no edit to a file that is there."""

import json
import pathlib
import shutil
import sys

import pytest

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


# -- a constrained deployment is files: a reference rule, a rehearsal size ------

# a made-up filter the four standing rules do not hold: at most one pod
# labelled `tier=gold` on a node
ONE_GOLD = '''
def why_not(manifest, node_name, cluster):
    labels = manifest["metadata"].get("labels") or {}
    if labels.get("tier") != "gold":
        return None
    if node_name in cluster.rule_state.setdefault("one_gold", set()):
        return f"a tier=gold pod already on {node_name}"
    return None


def bind(manifest, node_name, cluster):
    if (manifest["metadata"].get("labels") or {}).get("tier") == "gold":
        cluster.rule_state.setdefault("one_gold", set()).add(node_name)
'''


def _with_config(root, name, **extra):
    """A new configuration in the copy: perf5k-basic's file under another
    name, with `extra` keys, and its entry in BENCHMARK.json."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "benchmark/configs/perf5k-basic.json").read_text())
    cfg.update(name=name, **extra)
    (root / f"benchmark/configs/{name}.json").write_text(json.dumps(cfg))
    entry = dict(next(c for c in bench["configs"] if c["name"] == "perf5k-basic"),
                 name=name, file=f"benchmark/configs/{name}.json")
    bench["configs"].append(entry)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return Catalog(str(root))


def _gold(name):
    return {"metadata": {"name": name, "namespace": "b",
                         "labels": {"tier": "gold"}},
            "spec": {"containers": [{"requests": {"cpu": "100m"}}]}}


def test_a_reference_rule_is_found_by_name_and_applied(tmp_path):
    from harness import check

    root = _copy(tmp_path)
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    (root / "benchmark/reference_rules").mkdir(exist_ok=True)
    (root / "benchmark/reference_rules/one_gold.py").write_text(ONE_GOLD)
    cat = _with_config(root, "perf5k-gold", reference_rules=["one_gold"])
    rules = cat.reference_rules(cat.config("perf5k-gold"))
    assert [r.__name__ for r in rules] == ["benchmark_reference_rules_one_gold"]
    nodes = [{"metadata": {"name": n, "labels": {}}, "status": {
        "allocatable": {"cpu": "4", "memory": "1Gi", "pods": 10}}}
        for n in ("n0", "n1")]
    pods = {f"b/{n}": _gold(n) for n in ("g0", "g1", "g2")}
    order = [("b/g0", "n0"), ("b/g1", "n1"), ("b/g2", "n0")]
    # the four standing rules see nothing wrong; the configuration's does
    assert check.check_placements(nodes, order, pods.get, []) == []
    assert check.check_placements(nodes, order, pods.get, [], rules) == [
        "b/g2 on n0: a tier=gold pod already on n0"]
    # a standing rule still answers first
    assert check.check_placements(
        nodes, [("b/g0", "n9")], pods.get, [], rules) == [
        "b/g0 on n9: unknown node"]
    # no file that was there changed, none under harness/ was needed
    assert {p: p.read_bytes() for p in before} == before


def test_a_rule_that_is_named_and_missing_fails_with_its_name(tmp_path):
    from harness.supervisor import Run

    root = _copy(tmp_path)
    cat = _with_config(root, "perf5k-spread", reference_rules=["topology_spread"])
    with pytest.raises(KeyError, match="topology_spread"):
        cat.reference_rules(cat.config("perf5k-spread"))
    # and the run ends before anything is started
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "perf5k-spread.steady", "config": "perf5k-spread",
        "traffic": "steady", "chips": 1, "why": "made up for the test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copy(root / "benchmark/traffic/steady.perf5k-basic.json",
                root / "benchmark/traffic/steady.perf5k-spread.json")
    with pytest.raises(KeyError, match="reference_rules/topology_spread.py"):
        Run(Catalog(str(root)), "perf5k-spread.steady", 1, str(tmp_path / "o"),
            rehearse_cpu=True, nodes=64)


def test_the_standing_configurations_name_no_rule():
    cat = Catalog(str(REPO))
    for c in cat.bench["configs"]:
        config = cat.config(c["name"])
        assert "reference_rules" not in config
        assert cat.reference_rules(config) == []
        assert cat.rehearsal_nodes(config) == 64
    assert not (REPO / "benchmark/reference_rules").exists()


def test_rehearsal_nodes_is_read_defaults_to_64_and_stops_at_256(tmp_path):
    root = _copy(tmp_path)
    assert Catalog.rehearsal_nodes({}) == 64
    cat = _with_config(root, "perf5k-onepernode", rehearsal_nodes=256)
    assert cat.rehearsal_nodes(cat.config("perf5k-onepernode")) == 256
    for n in (257, 5000, 0):
        with pytest.raises(ValueError, match="rehearsal_nodes"):
            Catalog.rehearsal_nodes({"rehearsal_nodes": n})


def test_a_one_per_node_deployment_fits_its_rehearsal_at_256_nodes():
    """What `rehearsal_nodes` is for: upstream's hostname anti-affinity
    row holds one pod a node. At 64 nodes the rehearsal's floors (20
    pods/s, 10 warm-up pods) offer more pods than nodes; at 256 they fit."""
    from harness.supervisor import offered_rate, warmup_burst

    def pods(nodes, traffic, seconds=4.0):
        scale = nodes / 5000
        rate = offered_rate(traffic, scale)
        return (warmup_burst(traffic, scale)
                + int(rate * traffic["warmup"]["trickle_s"]) + int(rate * seconds))

    traffic = {"rate_per_s": 85, "warmup": {"burst_pods": 300, "trickle_s": 2.0}}
    assert pods(64, traffic) == 10 + 40 + 80 > 64
    assert pods(256, traffic) == 15 + 40 + 80 <= 256
    # at full size the floors are out of play
    assert offered_rate(traffic, 1) == 85.0 and warmup_burst(traffic, 1) == 300


def test_the_window_closes_on_time_however_late_the_offering_runs(tmp_path):
    """Past the knee the generator's own queue grows and the last creates
    leave seconds after the window's end; the closing scrapes (every
    per-layer Δ) are still taken at the window's end."""
    import time

    from harness.supervisor import Run

    class SlowRest:
        def create(self, path, body):
            time.sleep(0.02)
            return True

    run = Run(Catalog(str(REPO)), "perf5k-basic.backlog", 1, str(tmp_path),
              rehearse_cpu=True, nodes=64)
    run.rest = SlowRest()
    run.traffic = dict(run.traffic, senders=1)
    closed = []
    win, t_offered = run._offer(200.0, 0.5, 1, "x",
                                at_end=lambda: closed.append(time.monotonic()))
    assert len(win.keys) == 100 and all(win.acked)
    assert t_offered - win.t0 > 1.5          # 100 creates of 20 ms, one sender
    assert len(closed) == 1
    assert abs(closed[0] - (win.t0 + 0.5)) < 0.2


def test_the_client_opens_its_connections_before_the_window_not_in_it():
    """A server that listens with a backlog of 5 (the apiserver's) and a
    burst of 32 senders: after `warm(32)` no sender opens a connection,
    every create is acknowledged, and a refusal says why."""
    import http.server
    import threading
    import time

    from harness.rest import Rest

    accepted = []

    class Handler(http.server.BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def setup(self):
            accepted.append(self.client_address)
            super().setup()

        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Length", "2")
            self.end_headers()
            self.wfile.write(b"ok")

        def do_POST(self):
            self.rfile.read(int(self.headers["Content-Length"]))
            code = 409 if self.path == "/taken" else 201
            self.send_response(code)
            self.send_header("Content-Length", "2")
            self.end_headers()
            self.wfile.write(b"{}")

        def log_message(self, *args):
            pass

    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    server.daemon_threads = True
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        rest = Rest(server.server_address[1])
        t = time.monotonic()
        rest.warm(32, "/healthz")
        assert len(rest._idle) == 32
        # no SYN was dropped and retransmitted a second later
        assert time.monotonic() - t < 0.9
        acked = []
        threads = [threading.Thread(
            target=lambda: acked.extend(rest.create("/pods", b"{}")
                                        for _ in range(5)))
            for _ in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert acked == [True] * 160 and rest.refused == []
        assert len(accepted) == 32 and len(rest._idle) == 32
        assert rest.create("/taken", b"{}") is False
        assert rest.refused == ["HTTP 409"]
        rest.close()
        assert rest._idle == []
    finally:
        server.shutdown()
        server.server_close()
