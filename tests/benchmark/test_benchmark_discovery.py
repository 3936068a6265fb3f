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


STANDING = ["perf5k-podaffinity", "perf5k-basic"]


def _copy(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def _files(root):
    return {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}


def test_a_new_cell_and_a_new_layer_metric_are_found(tmp_path):
    root = _copy(tmp_path)
    before = _files(root / "benchmark")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    # a new traffic mix over an existing configuration: one data file
    (root / "benchmark/traffic/trickle.perf5k-basic.json").write_text(json.dumps({
        "arrivals": "uniform", "rate_per_s": 10, "pod_template": "measured",
        "warmup": {"burst_pods": 600, "trickle_s": 1.0},
        "trace": {"start_s": 1.0, "seconds": 2.0, "max_launches": 50}}))
    bench["workloads"].append({
        "name": "perf5k-basic.trickle", "config": "perf5k-basic",
        "traffic": "trickle", "chips": 1, "why": "made up for the test"})
    # a new per-layer metric over an existing kind of source: one data file,
    # under its whole name, since it is read in this cell alone
    (root / "benchmark/layer_metrics/audit_passes_per_s.trickle.json").write_text(
        json.dumps({"reader": "counter_ratio", "args": {
            "num": {"source": "sched", "name": "snapshot_audit_passes_total"},
            "den": {"client": "seconds"}}}))
    bench["per_layer"].append({
        "name": "audit_passes_per_s.trickle", "unit": "1/s", "better": "higher",
        "source": "program_counter", "layer": "readback + guards",
        "moves": "create_to_bound_p50_ms",
        "workloads": ["perf5k-basic.trickle"]})
    # and the cell is one more of those that report what `.steady` entries move
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "perf5k-basic.steady" in m.get("workloads", []):
            m["workloads"].append("perf5k-basic.trickle")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cat = Catalog(str(root))
    assert cat.problems() == []
    assert "perf5k-basic.trickle" in cat.cells()
    cell = cat.cell("perf5k-basic.trickle")
    assert cat.traffic(cell)["rate_per_s"] == 10
    assert cat.config(cell["config"])["nodes"]["count"] == 5000
    # what `perf5k-basic.steady` reports, whatever later entries add to it
    e2e = [m["name"] for m in cat.metrics("end_to_end", cell["name"])]
    assert e2e == [m["name"] for m in cat.metrics("end_to_end",
                                                  "perf5k-basic.steady")]
    assert {"create_to_bound_p50_ms", "setup_s"} <= set(e2e)
    steady = [m["name"] for m in cat.metrics("per_layer", "perf5k-basic.steady")]
    assert [m["name"] for m in cat.metrics("per_layer", cell["name"])] == (
        steady + ["audit_passes_per_s.trickle"])
    ctx = {"client": {"seconds": 4.0},
           "sched": (Scrape("snapshot_audit_passes_total 1"),
                     Scrape("snapshot_audit_passes_total 3")),
           "api": (Scrape(""), Scrape("")), "trace": None}
    assert cat.read_layer_metrics(cell["name"], ctx) == {
        "audit_passes_per_s.trickle": {"value": 0.5, "unit": "1/s"}}
    # the cells that were there read as before, and no file of theirs changed
    assert cat.read_layer_metrics("perf5k-basic.steady", ctx) == Catalog(
        str(REPO)).read_layer_metrics("perf5k-basic.steady", ctx)
    assert _files(root / "benchmark").items() >= before.items()


def _add_gold(root, chips=4):
    """The next kind of PR, as files and entries in the copy: a third
    configuration that names a reference rule and a rehearsal of 256
    nodes, the rule's file, and a cell of it under a traffic name no cell
    has, on four chips, listed wherever `perf5k-basic.backlog` is."""
    (root / "benchmark/reference_rules").mkdir(exist_ok=True)
    (root / "benchmark/reference_rules/one_gold.py").write_text(ONE_GOLD)
    cat = _with_config(root, "perf5k-gold", reference_rules=["one_gold"],
                       rehearsal_nodes=256)
    shutil.copy(root / "benchmark/traffic/backlog.perf5k-basic.json",
                root / "benchmark/traffic/surge.perf5k-gold.json")
    bench = cat.bench
    bench["workloads"].append({
        "name": "perf5k-gold.surge", "config": "perf5k-gold",
        "traffic": "surge", "chips": chips, "why": "made up for the test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "perf5k-basic.backlog" in m.get("workloads", []):
            m["workloads"].append("perf5k-gold.surge")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return Catalog(str(root))


def test_a_configuration_with_a_rule_a_rehearsal_size_and_a_four_chip_cell_is_files(
        tmp_path):
    """What `test_the_standing_configurations_name_no_rule` once forbade:
    after it every generic premise still holds, and no file that was there
    has changed."""
    from harness.supervisor import Run, rehearsal_mesh_env

    root = _copy(tmp_path)
    before = _files(root / "benchmark")
    bench_before = json.loads((root / "BENCHMARK.json").read_text())
    assert Catalog(str(root)).problems() == []
    cat = _add_gold(root)
    assert cat.problems() == []
    config = cat.config("perf5k-gold")
    assert [r.__name__ for r in cat.reference_rules(config)] == [
        "benchmark_reference_rules_one_gold"]
    assert cat.rehearsal_nodes(config) == 256
    # the standing configurations read as they did
    for name in STANDING:
        assert cat.reference_rules(cat.config(name)) == []
        assert cat.rehearsal_nodes(cat.config(name)) == 64
    # the cell reports what the backlog cells report, under its own traffic
    assert cat.reporting("bound_pods_per_s")[-1] == "perf5k-gold.surge"
    assert [m["name"] for m in cat.metrics("per_layer", "perf5k-gold.surge")] == [
        m["name"] for m in cat.metrics("per_layer", "perf5k-basic.backlog")]
    # the run finds its rule and its size, and rehearses on a mesh of four
    run = Run(cat, "perf5k-gold.surge", 1, str(tmp_path / "o"),
              rehearse_cpu=True, nodes=cat.rehearsal_nodes(config))
    assert [r.__name__ for r in run.rules] == [
        "benchmark_reference_rules_one_gold"]
    assert run.cluster.n_nodes == 256
    assert run._sched_env()["XLA_FLAGS"] == rehearsal_mesh_env(4)["XLA_FLAGS"]
    # entries were added, none edited but the lists that name the cell
    bench = cat.bench
    for section in ("configs", "workloads"):
        assert bench[section][:len(bench_before[section])] == bench_before[section]
    for section in ("end_to_end", "per_layer"):
        for old, new in zip(bench_before[section], bench[section], strict=True):
            assert new == old or new == dict(
                old, workloads=old["workloads"] + ["perf5k-gold.surge"])
    assert _files(root / "benchmark").items() >= before.items()


BROKEN_COPIES = {
    "the rule's file is not there":
        (lambda root: (root / "benchmark/reference_rules/one_gold.py").unlink(),
         "reference_rules/one_gold.py"),
    "the traffic file is not there":
        (lambda root: (root / "benchmark/traffic/surge.perf5k-gold.json").unlink(),
         "cell perf5k-gold.surge"),
    "one `.backlog` entry leaves the cell out":
        (lambda root: _edit_bench(root, lambda b: next(
            m for m in b["per_layer"] if m["name"] == "queue_wait_ms.backlog")
            ["workloads"].remove("perf5k-gold.surge")),
         "per-layer queue_wait_ms.backlog"),
    "a `.steady` entry lists a cell that does not report the median":
        (lambda root: _edit_bench(root, lambda b: next(
            m for m in b["per_layer"] if m["name"] == "queue_wait_ms.steady")
            ["workloads"].append("perf5k-gold.surge")),
         "per-layer queue_wait_ms.steady"),
    "a rehearsal of 5,000 nodes":
        (lambda root: _edit_config(root, rehearsal_nodes=5000),
         "rehearsal_nodes 5000"),
    "more than half of the cells on four chips":
        (lambda root: _edit_bench(root, _four_chips_past_half),
         "more than half"),
}


def _four_chips_past_half(bench):
    """One-chip cells moved to four until one more than half of the cells,
    however many there are, ask for four."""
    cells = bench["workloads"]
    ones = [w for w in cells if w["chips"] == 1]
    for w in ones[:len(cells) // 2 + 1 - (len(cells) - len(ones))]:
        w["chips"] = 4


def _edit_bench(root, edit):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    edit(bench)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def _edit_config(root, **keys):
    path = root / "benchmark/configs/perf5k-gold.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()), **keys)))


@pytest.mark.parametrize("what", sorted(BROKEN_COPIES))
def test_problems_names_what_is_missing(what, tmp_path):
    root = _copy(tmp_path)
    _add_gold(root)
    breaks, names = BROKEN_COPIES[what]
    breaks(root)
    problems = Catalog(str(root)).problems()
    assert len(problems) == 1 and names in problems[0], problems


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
    cat = _with_config(root, "perf5k-madeup", reference_rules=["no_such_rule"])
    with pytest.raises(KeyError, match="no_such_rule"):
        cat.reference_rules(cat.config("perf5k-madeup"))
    # and the run ends before anything is started
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "perf5k-madeup.steady", "config": "perf5k-madeup",
        "traffic": "steady", "chips": 1, "why": "made up for the test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copy(root / "benchmark/traffic/steady.perf5k-basic.json",
                root / "benchmark/traffic/steady.perf5k-madeup.json")
    with pytest.raises(KeyError, match="reference_rules/no_such_rule.py"):
        Run(Catalog(str(root)), "perf5k-madeup.steady", 1, str(tmp_path / "o"),
            rehearse_cpu=True, nodes=64)


@pytest.mark.parametrize("name", STANDING)
def test_the_standing_configurations_name_no_rule(name):
    """The two that were here before a configuration could name one; what
    a later configuration names, and whether `benchmark/reference_rules/`
    exists, is its own affair."""
    cat = Catalog(str(REPO))
    config = cat.config(name)
    assert "reference_rules" not in config
    assert cat.reference_rules(config) == []
    assert cat.rehearsal_nodes(config) == 64


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


@pytest.mark.parametrize("cell", [
    w["name"] for w in json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]])
def test_a_pod_carries_its_configurations_namespace(cell, tmp_path):
    """`$NAME` is a prefix of `$NAMESPACE`: replaced first, it left every
    pod `"namespace": "<pod name>SPACE"`, in the body POSTed and in the
    manifest handed to the reference, and a rule that compares namespaces
    was blind. Residents, the warm-up's pods and the window's, each cell."""
    from harness.supervisor import Run

    posted = []

    class Rest:
        def create(self, path, body):
            posted.append((path, json.loads(body)))
            return True

    run = Run(Catalog(str(REPO)), cell, 2**31 + 7, str(tmp_path),
              rehearse_cpu=True, nodes=64)
    ns = run.config["namespace"]
    run.rest = Rest()
    win, _ = run._offer(100.0, 0.2, 2**31 + 7, "w1")
    c = run.cluster
    name, tpl, node = c.residents[0]
    posted.append((None, json.loads(c.pod_body(name, tpl, node))))
    posted.append((None, json.loads(c.pod_body(
        "u-5", run.traffic["pod_template"], None))))
    assert len(posted) == 20 + 2 and ns and "$" not in ns
    for path, body in posted:
        meta = body["metadata"]
        assert meta["namespace"] == ns, meta
        assert "SPACE" not in json.dumps(body) and "$" not in json.dumps(body)
        assert path in (None, f"/api/v1/namespaces/{ns}/pods")
        manifest = c.manifest_of(f"{ns}/{meta['name']}")
        assert manifest == body
    # (64 sender threads: the order in which two close creates leave is theirs)
    assert sorted(b["metadata"]["name"] for _, b in posted[:20]) == sorted(
        k.split("/", 1)[1] for k in win.keys)
    assert posted[-2][1]["spec"]["nodeName"] == node
    assert c.manifest_of(f"{ns}/never-made") is None


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
