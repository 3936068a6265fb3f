"""The benchmark's own arithmetic, checked without a chip: the arrival
schedule, the window's rate and percentiles, the readers on a canned
/metrics page, the trace reduction on a small recorded trace, the plain
reference and the WAL read-back, the roofline's byte count, and the
names in BENCHMARK.json."""

import json
import pathlib
import re
import sys
import zlib

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "benchmark"))

from harness import check, loadgen, roofline, trace_reduce  # noqa: E402
from harness.catalog import Catalog  # noqa: E402
from harness.scrape import Scrape  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())


# -- the arrival schedule ------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_schedule_repeats_exactly_for_a_seed(seed):
    a = loadgen.schedule(80.0, 25.0, seed)
    assert a == loadgen.schedule(80.0, 25.0, seed)
    assert len(a) == 2000 and a == sorted(a)
    assert 0 < a[0] and a[-1] < 25.0


def test_every_seed_offers_the_same_gaps_in_another_order():
    def gaps(times):
        return sorted(round(b - a, 9) for a, b in zip([0.0] + times, times))

    a, b = loadgen.schedule(400.0, 10.0, 1), loadgen.schedule(400.0, 10.0, 2)
    assert a != b and gaps(a) == gaps(b)
    # the exponential law's shape: mean gap ~ 1/rate, the largest many times it
    g = gaps(a)
    assert abs(sum(g) / len(g) - 1 / 400.0) < 0.02 / 400.0
    assert g[-1] > 5 / 400.0


def test_uniform_law_and_unknown_law():
    assert loadgen.schedule(10.0, 1.0, 0, "uniform") == pytest.approx(
        [(i + 0.5) / 10 for i in range(10)])
    with pytest.raises(ValueError):
        loadgen.schedule(10.0, 1.0, 0, "bursty")


# -- a window's rate and percentiles ---------------------------------------------


def _window(stall_at=None, stall_s=0.0, n=1000, seconds=10.0, service_s=0.05):
    """A made-up server: every pod is bound `service_s` after it is due,
    except that nothing is bound during a stall, whose pods are all bound
    at its end."""
    due = [(i + 0.5) * seconds / n for i in range(n)]
    keys = [f"bench/w-{i}" for i in range(n)]
    win = loadgen.Window(100.0, seconds, due, keys)
    t_bound = {}
    for i, d in enumerate(due):
        t = 100.0 + d + service_s
        if stall_at is not None and stall_at <= d + service_s < stall_at + stall_s:
            t = 100.0 + stall_at + stall_s
        t_bound[keys[i]] = t
        win.t_sent[i], win.acked[i] = 100.0 + d + 0.001, True
    return win, t_bound


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert loadgen.percentile(v, 50) == 50
    assert loadgen.percentile(v, 99) == 99
    assert loadgen.percentile(v, 100) == 100
    assert loadgen.percentile([3.0], 99) == 3.0


def test_a_stall_moves_the_rate_and_the_tail():
    calm = loadgen.window_stats(*_window(), t_gave_up=200.0)
    # a 2 s stall that ends after the window: its pods are late, not lost
    win, t_bound = _window(stall_at=8.5, stall_s=2.0)
    stalled = loadgen.window_stats(win, t_bound, t_gave_up=200.0)
    assert calm["failed"] == stalled["failed"] == 0
    assert calm["bound_pods_per_s"] == pytest.approx(99.5, abs=0.2)
    assert stalled["bound_pods_per_s"] < 0.87 * calm["bound_pods_per_s"]
    assert calm["create_to_bound_p99_ms"] == pytest.approx(50.0, abs=1e-6)
    assert stalled["create_to_bound_p99_ms"] > 1900.0
    assert stalled["create_to_bound_p50_ms"] == pytest.approx(50.0, abs=1e-6)
    assert stalled["loadgen_lag_p99_ms"] == pytest.approx(1.0, abs=1e-6)


def test_an_unbound_pod_is_a_failure_and_waits_until_the_drain_gave_up():
    win, t_bound = _window(n=100)
    del t_bound["bench/w-99"]
    s = loadgen.window_stats(win, t_bound, t_gave_up=170.0)
    assert s["attempted"] == 100 and s["failed"] == 1
    assert s["create_to_bound_max_ms"] == pytest.approx(
        (170.0 - 100.0 - win.due[99]) * 1e3)


# -- the readers on a canned /metrics page ---------------------------------------

PAGE_0 = """# TYPE scheduler_wave_batches_total counter
scheduler_wave_batches_total 10.0
binding_duration_seconds{quantile="0.5"} 0.01
binding_duration_seconds_sum 1.0
binding_duration_seconds_count 100
scheduling_stage_duration_seconds_sum{stage="encode"} 0.5
scheduling_stage_duration_seconds_count{stage="encode"} 10
scheduling_stage_duration_seconds_sum{stage="kernel"} 9.0
scheduling_stage_duration_seconds_count{stage="kernel"} 10
jax_backend_compiles_total{persistent_cache="hit",program="jit(wave_kernel)"} 3.0
"""
PAGE_1 = PAGE_0.replace("total 10.0", "total 14.0").replace(
    "_sum 1.0", "_sum 3.0").replace("_count 100", "_count 200").replace(
    '_sum{stage="encode"} 0.5', '_sum{stage="encode"} 0.9').replace(
    '_count{stage="encode"} 10', '_count{stage="encode"} 14')


def test_readers_on_a_canned_metrics_page():
    cat = Catalog(str(REPO))
    ctx = {"sched": (Scrape(PAGE_0), Scrape(PAGE_1)),
           "api": (Scrape(""), Scrape("")),
           "client": {"bound_in_window": 1000, "loadgen_lag_p99_ms": 1.5},
           "trace": None}
    hist = cat.reader("hist_mean")
    assert hist(ctx, source="sched", name="binding_duration_seconds",
                scale=1000.0) == pytest.approx(20.0)
    assert hist(ctx, source="sched", name="scheduling_stage_duration_seconds",
                labels={"stage": "encode"}, scale=1000.0) == pytest.approx(100.0)
    # nothing observed in the window: nothing read, never a 0
    assert hist(ctx, source="sched", name="scheduling_stage_duration_seconds",
                labels={"stage": "kernel"}) is None
    assert hist(ctx, source="api", name="wal_fsync_duration_seconds") is None
    ratio = cat.reader("counter_ratio")
    assert ratio(ctx, num={"client": "bound_in_window"},
                 den={"source": "sched",
                      "name": "scheduler_wave_batches_total"}) == 250.0
    assert ratio(ctx, num={"client": "bound_in_window"},
                 den={"source": "api", "name": "absent_total"}) is None
    assert cat.reader("client_stat")(ctx, stat="loadgen_lag_p99_ms") == 1.5
    assert cat.reader("trace_program_ms")(ctx, program="jit_wave_kernel") is None
    assert cat.reader("trace_idle_share")(ctx) is None
    s = Scrape(PAGE_1)
    assert s.by_label("jax_backend_compiles_total", "persistent_cache") == {
        "hit": 3.0}


# -- the trace reduction on a small recorded trace -------------------------------


def test_trace_reduce_on_a_small_trace():
    from jax.profiler import ProfileData

    text = (pathlib.Path(__file__).parent / "small_trace.textproto").read_text()
    r = trace_reduce.reduce_profile(ProfileData.from_text_proto(text))
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.0035)  # overlapping ops count once
    assert r["idle_share"] == pytest.approx(65.0)
    wave = r["programs"]["jit_wave_kernel"]
    assert wave["launches"] == 2 and wave["device_s"] == pytest.approx(0.003)
    assert r["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(0.002)]
    # a gap is named after the loop phase that covers most of it, and
    # after the program that ended it where the loop's line is bare
    assert r["breakdown"]["idle_gaps"] == [
        ["before end of trace", pytest.approx(0.003)],
        ["bind", pytest.approx(0.0015)],
        ["bind", pytest.approx(0.001)],
        ["before jit_wave_kernel", pytest.approx(0.001)]]
    # where the launcher gives the traced window's length, that is the window
    given = trace_reduce.reduce_profile(ProfileData.from_text_proto(text), 0.02)
    assert given["window_s"] == 0.02 and given["busy_s"] == r["busy_s"]
    assert given["idle_share"] == pytest.approx(82.5)
    cat = Catalog(str(REPO))
    ctx = {"trace": r, "root": str(REPO),
           "config": {"nodes": {"count": 5000}},
           "device": {"kind": "TPU v5 lite", "count": 1}}
    assert cat.reader("trace_program_ms")(
        ctx, program="jit_wave_kernel") == pytest.approx(1.5)
    assert cat.reader("trace_idle_share")(ctx) == pytest.approx(65.0)
    share = cat.reader("roofline_share")(
        ctx, program="jit_wave_kernel", work="wave_kernel")
    assert share == pytest.approx(100 * 26395168 / 819e9 / 0.0015)
    assert cat.reader("trace_program_ms")(ctx, program="jit_absent") is None


def test_a_trace_without_loop_annotations_names_gaps_by_program():
    """The parent of a program that writes none, or a CPU rehearsal."""
    from jax.profiler import ProfileData

    text = (pathlib.Path(__file__).parent / "small_trace.textproto").read_text()
    r = trace_reduce.reduce_profile(
        ProfileData.from_text_proto(text.replace("ktpu.loop.", "other.")))
    assert [g[0] for g in r["breakdown"]["idle_gaps"]] == [
        "before end of trace", "before jit_wave_kernel",
        "before jit_scatter", "before jit_wave_kernel"]


def test_the_loop_line_and_what_covers_a_gap():
    loop = [("ktpu.loop.launch", 0.0, 1.0), ("ktpu.loop.bind", 1.0, 8.0),
            ("host_work", 0.0, 9.0), ("ktpu.loop.pop", 9.0, 1.0)]
    other = [("ktpu.loop.bind", 0.0, 10.0), ("ktpu.loop.bind", 10.0, 10.0),
             ("ktpu.loop.bind", 20.0, 10.0), ("ktpu.loop.bind", 30.0, 10.0)]
    phases = trace_reduce.loop_phases([other, [("host_work", 0.0, 5.0)], loop])
    assert phases == [("launch", 0.0, 1.0), ("bind", 1.0, 9.0),
                      ("pop", 9.0, 10.0)]
    assert trace_reduce.loop_phases([[("host_work", 0.0, 5.0)]]) == []
    assert trace_reduce.phase_cover(phases, 0.5, 9.5) == {
        "launch": 0.5, "bind": 8.0, "pop": 0.5}
    assert trace_reduce.phase_cover(phases, 20.0, 30.0) == {}


def test_a_trace_with_no_device_plane_reads_nothing():
    assert trace_reduce.reduce_dir("/nonexistent") == {}
    cat = Catalog(str(REPO))
    ctx = {"trace": {"devices": 0, "window_s": 3.0, "busy_s": 0.0,
                     "programs": {}}}
    assert cat.reader("trace_idle_share")(ctx) is None


# -- the roofline's bytes ---------------------------------------------------------


def test_roofline_bytes_and_peaks():
    assert roofline.capacities(5000)["N"] == 8192
    assert roofline.snapshot_bytes(5000) == {
        "read": 19186208, "written": 7208960}
    one = roofline.least_seconds("wave_kernel", {"nodes": {"count": 5000}},
                                 "TPU v5 lite")
    assert one == pytest.approx(26395168 / 819e9)
    assert roofline.least_seconds(
        "wave_kernel", {"nodes": {"count": 5000}}, "TPU v5 lite", 4
    ) == pytest.approx(one / 4)
    with pytest.raises(KeyError):
        roofline.least_seconds("wave_kernel", {"nodes": {"count": 5000}},
                               "TPU v9")
    with pytest.raises(KeyError):
        roofline.peaks("source")


# -- the plain reference and the WAL read-back -------------------------------------


def _node(name, zone, cpu="1"):
    return {"metadata": {"name": name, "labels": {
        "topology.kubernetes.io/zone": zone}},
        "status": {"allocatable": {"cpu": cpu, "memory": "1Gi", "pods": 10}}}


def _pod(name, labels=None, affinity=None, cpu="400m"):
    spec = {"containers": [{"requests": {"cpu": cpu, "memory": "100Mi"}}]}
    if affinity:
        spec["affinity"] = affinity
    return {"metadata": {"name": name, "namespace": "b",
                         "labels": labels or {}}, "spec": spec}


AFF = {"podAffinity": {"required": [{
    "labelSelector": {"matchLabels": [["app", "bench"]]},
    "topologyKey": "topology.kubernetes.io/zone"}]}}
ANTI = {"podAntiAffinity": {"required": [{
    "labelSelector": {"matchLabels": {"app": "bench"}},
    "topologyKey": "topology.kubernetes.io/zone"}]}}


def test_quantities():
    assert check.quantity("100m", milli=True) == 100
    assert check.quantity("4", milli=True) == 4000
    assert check.quantity("500Mi") == 500 * 2**20
    assert check.quantity("32Gi") == 32 * 2**30
    assert check.quantity(110) == 110


def test_reference_replay_finds_what_is_infeasible():
    nodes = [_node("n0", "z0"), _node("n1", "z1")]
    pods = {
        "b/r": _pod("r", {"app": "bench"}),
        "b/a": _pod("a", {"app": "bench"}, AFF),
        "b/c": _pod("c", {"app": "bench"}, AFF),
        "b/d": _pod("d", {}, None),
        "b/e": _pod("e", {}, ANTI),
    }
    ok = [("b/r", "n0"), ("b/a", "n0"), ("b/e", "n1")]
    assert check.check_placements(nodes, ok, pods.get, []) == []
    bad = check.check_placements(
        nodes,
        [("b/r", "n0"), ("b/a", "n1"),     # no app=bench pod in z1 yet
         ("b/c", "n0"), ("b/d", "n0"),     # third pod: 1200m of 1000m cpu
         ("b/e", "n1"),                    # anti-affinity: a bench pod in z1
         ("b/x", "n0"), ("b/r", "n9")],    # never created; unknown node
        pods.get, [("b/a", "n1", "n0")])
    text = "\n".join(bad)
    assert len(bad) == 6, text
    assert "b/a on n1: no pod matching" in text
    assert "b/d on n0: over the node's allocatable cpu" in text
    assert "b/e on n1: a pod matching" in text
    assert "never created" in text and "unknown node" in text
    assert "seen bound to n1 and then to n0" in text


class _Quiet:
    """A configuration's rule that refuses nothing and counts its calls."""

    def __init__(self):
        self.asked = self.told = 0

    def why_not(self, manifest, node, cluster):
        self.asked += 1
        return None

    def bind(self, manifest, node, cluster):
        self.told += 1


@pytest.mark.parametrize("rules", ["none", "empty", "quiet"])
def test_the_four_standing_rules_read_the_same_beside_a_configurations_rule(
        rules):
    """The replay of test_reference_replay_finds_what_is_infeasible, with
    no rules argument (as before PR 29), an empty list, and a rule that
    refuses nothing: the same violations, in the same order."""
    nodes = [_node("n0", "z0"), _node("n1", "z1")]
    pods = {
        "b/r": _pod("r", {"app": "bench"}),
        "b/a": _pod("a", {"app": "bench"}, AFF),
        "b/c": _pod("c", {"app": "bench"}, AFF),
        "b/d": _pod("d", {}, None),
        "b/e": _pod("e", {}, ANTI),
    }
    order = [("b/r", "n0"), ("b/a", "n1"), ("b/c", "n0"), ("b/d", "n0"),
             ("b/e", "n1"), ("b/x", "n0"), ("b/r", "n9")]
    quiet = _Quiet()
    args = {"none": (), "empty": ([],), "quiet": ([quiet],)}[rules]
    got = check.check_placements(nodes, order, pods.get,
                                 [("b/a", "n1", "n0")], *args)
    assert got == [
        "b/a: seen bound to n1 and then to n0",
        "b/a on n1: no pod matching {'app': 'bench'} in "
        "topology.kubernetes.io/zone=z1",
        "b/d on n0: over the node's allocatable cpu",
        "b/e on n1: a pod matching {'app': 'bench'} already in "
        "topology.kubernetes.io/zone=z1",
        "b/x: a bind of a pod this run never created",
        "b/r on n9: unknown node",
    ]
    if rules == "quiet":
        # asked only where the four let the bind pass; told of every bind
        # of a pod of ours to a node that exists
        assert (quiet.asked, quiet.told) == (2, 5)


def test_first_pod_of_a_self_matching_affinity_group_may_go_anywhere():
    nodes = [_node("n0", "z0"), _node("n1", "z1")]
    pods = {"b/a": _pod("a", {"app": "bench"}, AFF),
            "b/c": _pod("c", {"app": "bench"}, AFF)}
    assert check.check_placements(
        nodes, [("b/a", "n1"), ("b/c", "n1")], pods.get, []) == []
    assert len(check.check_placements(
        nodes, [("b/a", "n1"), ("b/c", "n0")], pods.get, [])) == 1


def _frame(rec):
    payload = json.dumps(rec)
    return f"K2 {zlib.crc32(payload.encode()) & 0xFFFFFFFF:08x} {payload}\n"


def _rec(rv, verb, name, node=""):
    return {"rv": rv, "verb": verb, "kind": "pods", "obj": {
        "metadata": {"name": name, "namespace": "b"},
        "spec": {"nodeName": node}}}


def test_wal_read_back(tmp_path):
    snap = {"rv": 2, "objects": {"pods": [
        {"metadata": {"name": "s", "namespace": "b"},
         "spec": {"nodeName": "n0"}}]}}
    (tmp_path / "cluster.snapshot.json").write_text(json.dumps(snap))
    log = (_frame(_rec(1, "create", "old", "n9"))      # before the snapshot
           + _frame(_rec(3, "create", "a"))
           + _frame({"rv": 4, "verb": "create", "kind": "nodes",
                     "obj": {"metadata": {"name": "n0", "namespace": ""}}})
           + _frame(_rec(5, "update", "a", "n1"))
           + _frame(_rec(6, "create", "gone", "n1"))
           + _frame(_rec(7, "delete", "gone", "n1"))
           + _frame(_rec(8, "update", "torn", "n1"))[:-20])  # cut by the kill
    (tmp_path / "cluster.wal").write_text(log)
    pods, damaged = check.read_wal_pods(str(tmp_path))
    assert pods == {"b/s": "n0", "b/a": "n1"} and damaged == 0
    assert check.check_wal(str(tmp_path), {"b/a": "n1", "b/s": "n0"}) == []
    missing = check.check_wal(str(tmp_path), {"b/a": "n0", "b/torn": "n1"})
    assert len(missing) == 2
    # a flipped bit inside the acknowledged prefix is damage, not a tail
    (tmp_path / "cluster.wal").write_text(
        log.replace('"name": "a", "namespace": "b"}, "spec": {"nodeName": "n1"',
                    '"name": "a", "namespace": "b"}, "spec": {"nodeName": "n2"'))
    pods, damaged = check.read_wal_pods(str(tmp_path))
    assert damaged == 1 and pods["b/a"] == ""
    assert check.read_wal_pods(str(tmp_path / "absent")) == ({}, 0)


def test_device_path_check_counts_what_left_the_device():
    ok = Scrape('scheduler_device_info{platform="tpu",pallas_fit="on",'
                'pallas_interpret="false",device_kind="TPU v5 lite"} 1\n'
                'scheduler_host_path_pods_total{lane="small_batch"} 0\n')
    assert check.check_device_path(ok, "fine", "tpu", 5000, 100) == (0, [])
    bad = Scrape('scheduler_device_info{platform="tpu",pallas_fit="off",'
                 'pallas_interpret="false"} 1\n'
                 'scheduler_host_path_pods_total{lane="small_batch"} 7\n'
                 'kernel_guard_trips_total 2\n')
    off, why = check.check_device_path(
        bad, "x\nscheduling batch failed\n", "tpu", 5000, 100)
    assert off == 100 + 7 + 2 + 1 and len(why) == 4
    # only a rehearsal-sized cluster may use the small-batch host lane
    assert check.check_device_path(bad, "", "tpu", 64, 100)[0] == 100 + 2
    assert check.check_device_path(ok, "", "cpu", 5000, 100)[0] == 100


def _served_block(last_line):
    return ("-" * 40 + "\nException occurred during processing of request "
            "from ('127.0.0.1', 32411)\nTraceback (most recent call last):\n"
            '  File "socketserver.py", line 845, in write\n'
            "    self._sock.sendall(b)\n" + last_line + "\n" + "-" * 40 + "\n")


@pytest.mark.parametrize("log,counted", [
    # the harness's own poll hung up on a slow /healthz: not the scheduler's
    ("up\n" + _served_block("BrokenPipeError: [Errno 32] Broken pipe")
     + "fine\n", 0),
    ("up\n" + _served_block("ConnectionResetError: [Errno 104] reset"), 0),
    # a handler that failed for any other reason is counted
    ("up\n" + _served_block("ValueError: bad page"), 1),
    # and so is every traceback outside such a block, beside a hung-up one
    (_served_block("BrokenPipeError: [Errno 32] Broken pipe")
     + "Traceback (most recent call last):\n  x\nBrokenPipeError: y\n", 1),
    # a block that never closes is left as it is
    ("-" * 40 + "\nException occurred during processing of request from x\n"
     "Traceback (most recent call last):\nBrokenPipeError: z\n", 1),
], ids=["hung-up", "reset", "other-exception", "bare-traceback", "unclosed"])
def test_a_poll_that_hung_up_is_not_a_failure_of_the_scheduler(log, counted):
    ok = Scrape('scheduler_device_info{platform="tpu",pallas_fit="on",'
                'pallas_interpret="false"} 1\n')
    off, why = check.check_device_path(ok, log, "tpu", 5000, 100)
    assert off == counted and len(why) == counted
    kept = check.without_hung_up_clients(log)
    assert kept.count("Traceback") == counted
    assert "up\n" not in log or kept.startswith("up\n")


# -- BENCHMARK.json ------------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _names():
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[section]:
            yield section, entry


@pytest.mark.parametrize("section,entry", list(_names()),
                         ids=[f"{s}:{e['name']}" for s, e in _names()])
def test_benchmark_json_entry(section, entry):
    assert NAME.match(entry["name"]), entry["name"]
    if "unit" in entry:
        assert UNIT.match(entry["unit"]), entry["unit"]
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    cells = {w["name"] for w in BENCH["workloads"]}
    for cell in entry.get("workloads", []):
        assert cell in cells
    for key in ("why", "layer", "source"):
        if key in entry and section in ("configs", "workloads", "per_layer"):
            v = entry[key]
            assert 1 <= len(v) <= 200 and "\n" not in v and "\t" not in v
    cat = Catalog(str(REPO))
    if section == "configs":
        assert (REPO / entry["file"]).is_file()
        cfg = cat.config(entry["name"])
        assert cfg["source"] == entry["source"]
        assert cfg["reduced"] == entry["reduced"]
    if section == "workloads":
        assert NAME.match(entry["traffic"]) and entry["chips"] in (1, 4)
        assert cat.traffic(entry)["rate_per_s"] > 0
        reported = {m["name"] for m in cat.metrics("end_to_end", entry["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        assert cat.metrics("per_layer", entry["name"])
    if section == "end_to_end":
        assert 0.01 <= entry["bound"] <= 0.25
        assert entry["source"] in ("host_clock", "device_trace")
    if section == "per_layer":
        e2e = {m["name"]: m for m in BENCH["end_to_end"]}
        assert entry["moves"] in e2e
        spec = cat.layer_metric(entry["name"])
        assert callable(cat.reader(spec["reader"]))
        # every cell that reports a layer metric reports the metric it moves
        for cell in entry["workloads"]:
            assert cell in e2e[entry["moves"]].get("workloads", cells)
