"""Per-pod scheduling traces (ISSUE 13): the span pipeline, tail
exemplars, reservoir-sampled histograms, the debug listeners, and
cross-process trace propagation over the REST /binding hop.

Covers the tentpole acceptance shape end to end, in-process:

  * a pod admitted to the queue gets a trace whose span chain covers
    queue -> encode -> device -> readback -> guard -> assume -> bind,
    the store stamps the apply under the same id, and the trace is
    retrievable by id from the ring, the SIGUSR2 dump, and
    /debug/traces;
  * the `e2e_scheduling_duration_seconds` p99 exemplar resolves to a
    complete per-pod trace whose in-cycle stage sum reconciles with the
    histogram within 5%;
  * a trace id attached to a /binding POST (X-Trace-Context) survives
    the wire and appears in the server-side stamp ledger — for a
    normal bind AND for a LeaderFenced zombie bind;
  * Histogram._samples is a true seeded reservoir: late-arriving
    outliers shift the reported p99 (the first-100k freeze is gone)
    while `quantiles_since` windowing keeps working.
"""

import json
import time
import urllib.request

import pytest

from kubernetes_tpu.api.objects import (
    Binding,
    Container,
    Node,
    NodeSpec,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodSpec,
)
from kubernetes_tpu.apiserver.client import RESTClient
from kubernetes_tpu.apiserver.rest import serve
from kubernetes_tpu.client.apiserver import APIServer, LeaderFenced
from kubernetes_tpu.client.leaderelection import BindFence
from kubernetes_tpu.scheduler import KubeSchedulerConfiguration, Scheduler
from kubernetes_tpu.scheduler.cache.debugger import CacheDebugger
from kubernetes_tpu.utils.debugserver import serve_debug
from kubernetes_tpu.utils.metrics import Histogram, metrics
from kubernetes_tpu.utils.tracing import (
    TRACE_HEADER,
    Tracer,
    bind_context,
    tracer,
)


def make_node(name, cpu="32"):
    return Node(
        metadata=ObjectMeta(name=name, namespace=""),
        spec=NodeSpec(),
        status=NodeStatus(
            allocatable={"cpu": cpu, "memory": "64Gi", "pods": 110}
        ),
    )


def make_pod(name):
    return Pod(
        metadata=ObjectMeta(name=name, namespace="default"),
        spec=PodSpec(containers=[Container(requests={"cpu": "100m"})]),
    )


def wait_until(cond, timeout):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return cond()


@pytest.fixture(autouse=True)
def _fresh_tracer():
    tracer.reset()
    yield
    tracer.reset()


# -- reservoir sampling (the _samples satellite) ------------------------------


def test_reservoir_late_outliers_shift_p99():
    """The seed bug: the old code kept only the FIRST max_samples
    observations, freezing long-run quantiles at the warmup
    distribution. With true reservoir sampling the late outlier regime
    must move the reported p99."""
    h = Histogram(max_samples=200)
    for _ in range(2000):
        h.observe(0.01)
    frozen_p99 = h.quantile(0.99)
    assert frozen_p99 == pytest.approx(0.01)
    # the workload shifts: a late 10x-slower tail the old reservoir
    # would never have admitted (its first 200 slots were taken forever)
    for _ in range(2000):
        h.observe(0.1)
    assert h.quantile(0.99) == pytest.approx(0.1), (
        "late-arriving outliers must shift the reported p99 — the "
        "first-N freeze is back"
    )
    # deterministic: same seed, same sequence, same reservoir
    h2 = Histogram(max_samples=200)
    for _ in range(2000):
        h2.observe(0.01)
    for _ in range(2000):
        h2.observe(0.1)
    assert h2._samples == h._samples


def test_reservoir_quantiles_since_windows_out_warmup():
    h = Histogram(max_samples=1000)
    for _ in range(500):
        h.observe(5.0)  # compile-laden warmup
    n0 = h.n
    for _ in range(500):
        h.observe(0.002)
    assert h.quantiles_since(n0, (0.99,))[0] == pytest.approx(0.002)
    # and the all-time quantile still sees both regimes
    assert h.quantile(0.2) in (pytest.approx(0.002), pytest.approx(5.0))


def test_histogram_exemplars_track_the_tail():
    h = Histogram()
    for i in range(100):
        h.observe(0.001 * (i + 1), exemplar=f"t{i}")
    ex = h.exemplars()
    assert ex[0] == (pytest.approx(0.1), "t99")
    near = h.exemplar_near(0.99)
    assert near is not None and near[1] in {f"t{i}" for i in range(90, 100)}
    # render_prometheus carries the exemplar as a comment line
    metrics.observe("tracing_test_series_seconds", 1.5, exemplar="deadbeef")
    text = metrics.render_prometheus()
    assert '# exemplar tracing_test_series_seconds 1.5 trace_id="deadbeef"' in text


# -- tracer unit behavior ------------------------------------------------------


def test_tracer_span_chain_and_ring():
    t = Tracer(ring_size=4)
    tid = t.start("pod", "default/x")
    t0 = time.monotonic()
    t.add_span(tid, "queue", t0 - 0.05, t0)
    with t.span(tid, "bind"):
        time.sleep(0.002)
    t.event(tid, "unschedulable", "0/5 nodes")
    t.finish(tid, outcome="bound", node="n-1")
    got = t.get(tid)
    assert got["finished"] and got["outcome"] == "bound"
    assert set(got["stages_ms"]) == {"queue", "bind"}
    assert got["stages_ms"]["queue"] == pytest.approx(50, rel=0.2)
    assert got["events"][0]["name"] == "unschedulable"
    # ring is bounded: oldest completed traces fall off
    for i in range(10):
        tid_i = t.start("pod", f"default/y{i}")
        t.finish(tid_i)
    assert t.get(tid) is None
    assert len(t.slowest(100)) == 4


def test_tracer_span_closes_on_exception():
    t = Tracer()
    tid = t.start("pod", "default/exc")
    with pytest.raises(RuntimeError):
        with t.span(tid, "bind"):
            raise RuntimeError("boom")
    t.finish(tid)
    assert "bind" in t.get(tid)["stages_ms"]


def test_tracer_disabled_is_inert():
    t = Tracer()
    t.set_enabled(False)
    try:
        assert t.start("pod", "default/z") == ""
        t.add_span("", "queue", 0.0, 1.0)
        t.finish("")
        assert t.slowest(5) == []
        assert t.trace_for_pod("default/z") == ""
    finally:
        t.set_enabled(True)


def test_tracer_active_overflow_evicts_oldest():
    t = Tracer(max_active=3)
    tids = [t.start("pod", f"default/a{i}") for i in range(5)]
    assert t.get(tids[0]) is None and t.get(tids[1]) is None
    assert all(t.get(tid) is not None for tid in tids[2:])


def test_bind_context_overrides_active_index():
    t0 = tracer.start("pod", "default/ctx")
    with bind_context({"default/ctx": "feedface"}):
        assert tracer.trace_for_pod("default/ctx") == "feedface"
    assert tracer.trace_for_pod("default/ctx") == t0


# -- in-process end-to-end: scheduler -> store, host path ---------------------


@pytest.fixture
def bound_cluster():
    metrics.reset()
    server = APIServer()
    sched = Scheduler(server, KubeSchedulerConfiguration(use_device=False))
    for i in range(6):
        server.create("nodes", make_node(f"tr-{i}"))
    sched.start()
    try:
        for i in range(8):
            server.create("pods", make_pod(f"tp-{i}"))

        def bound():
            pods, _ = server.list("pods")
            return sum(1 for p in pods if p.spec.node_name)

        assert wait_until(lambda: bound() >= 8, 30)
        yield server, sched
    finally:
        sched.stop()


def test_pod_trace_complete_and_store_stamped(bound_cluster):
    server, sched = bound_cluster
    slow = tracer.slowest(20)
    assert len(slow) >= 8
    d = next(t for t in slow if t["key"].startswith("default/tp-"))
    assert d["outcome"] == "bound"
    # host path chain: queue wait, algorithm, bind — all monotonic spans
    assert {"queue", "algo", "bind"} <= set(d["stages_ms"])
    # the store stamped the apply under the SAME id
    full = tracer.get(d["trace_id"])
    stamps = full.get("store_stamps", [])
    assert any(s["event"] == "applied" for s in stamps), stamps
    assert full["attrs"].get("node", "").startswith("tr-")


def test_preempt_span_chain_on_preemptor_trace():
    """ISSUE-15 satellite: a preemption-delayed pod's waterfall must show
    where the time went — the preempt.select → preempt.delete →
    preempt.nominate chain lands on the PREEMPTOR pod's own trace id."""
    metrics.reset()
    server = APIServer()
    sched = Scheduler(server, KubeSchedulerConfiguration(use_device=False))
    server.create("nodes", make_node("pr-0", cpu="2"))
    sched.start()
    try:
        victim = make_pod("victim")
        victim.spec.priority = 0
        victim.spec.containers[0].requests = {"cpu": "2"}
        server.create("pods", victim)
        assert wait_until(
            lambda: server.get("pods", "default", "victim").spec.node_name,
            30,
        )
        hi = make_pod("preemptor")
        hi.spec.priority = 100
        hi.spec.containers[0].requests = {"cpu": "2"}
        server.create("pods", hi)
        assert wait_until(
            lambda: server.get(
                "pods", "default", "preemptor"
            ).status.nominated_node_name
            == "pr-0",
            30,
        )
        tid = tracer.trace_for_pod("default/preemptor")
        assert tid
        full = tracer.get(tid)
    finally:
        sched.stop()
    assert full is not None
    stages = {s["name"] for s in full["spans"]}
    assert {"preempt.select", "preempt.delete", "preempt.nominate"} <= stages
    # the delete span records how many victims the eviction covered
    delete = next(s for s in full["spans"] if s["name"] == "preempt.delete")
    assert delete["attrs"].get("victims") == 1


def test_p99_exemplar_resolves_to_full_trace(bound_cluster):
    h = metrics.histogram("e2e_scheduling_duration_seconds")
    assert h is not None and h.n >= 8
    ex = h.exemplar_near(0.99)
    assert ex is not None
    val, tid = ex
    d = tracer.get(tid)
    assert d is not None and d["finished"], "p99 exemplar must resolve"
    # reconciliation: the trace's in-cycle stage sum vs the histogram
    # observation it rode in on (within 5%)
    cycle = sum(
        v
        for k, v in d["stages_ms"].items()
        if k in ("algo", "assume", "bind", "encode", "device", "readback",
                 "guard")
    )
    assert cycle / 1e3 == pytest.approx(val, rel=0.05), (cycle, val)


def test_sigusr2_dump_has_traces_section(bound_cluster):
    server, sched = bound_cluster
    dump = CacheDebugger(sched).dump()
    assert "Dump of per-pod scheduling traces (slowest first):" in dump
    assert "total=" in dump
    assert "Dump of tracing pipeline state:" in dump
    assert "tracing_traces_completed_total" in dump


def test_debug_listener_serves_metrics_and_traces(bound_cluster):
    srv = serve_debug(0)
    port = srv.server_address[1]
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ) as r:
            body = r.read().decode()
        assert "e2e_scheduling_duration_seconds" in body
        assert "tracing_traces_total" in body
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/traces?n=5", timeout=5
        ) as r:
            payload = json.loads(r.read())
        assert payload["slowest"] and payload["stages"]
        tid = payload["slowest"][0]["trace_id"]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/traces?id={tid}", timeout=5
        ) as r:
            one = json.loads(r.read())
        assert one["trace_id"] == tid and one["spans"]
    finally:
        srv.shutdown()


# -- the bench stage waterfall (device wave path) ------------------------------


def test_latency_bench_waterfall_reconciles_with_e2e():
    """ISSUE-13 acceptance: the steady-state bench reports a per-stage
    waterfall from REAL spans whose in-cycle stage sums reconcile with
    e2e_scheduling_duration_seconds within 5%, and the p99 exemplar
    resolves to a complete per-pod trace retrievable by id."""
    from kubernetes_tpu.perf.harness import run_latency_benchmark
    from kubernetes_tpu.perf.workloads import WORKLOADS

    lat = run_latency_benchmark(
        WORKLOADS["SchedulingBasic/500"], rate_pods_per_s=120.0, n_pods=60
    )
    assert lat.scheduled == 60
    wf = lat.stage_waterfall
    # wave-path chain: every in-cycle stage attributed
    for stage in ("queue", "encode", "device", "readback", "guard",
                  "assume", "bind"):
        assert stage in wf, (stage, wf)
        assert wf[stage]["count"] >= 50
    assert 0.95 <= lat.waterfall_vs_e2e <= 1.05, lat.waterfall_vs_e2e
    assert lat.p99_trace_id, "no p99 exemplar"
    assert lat.p99_trace is not None and lat.p99_trace["finished"]
    assert tracer.get(lat.p99_trace_id) is not None


# -- the REST hop: X-Trace-Context survives the wire ---------------------------


@pytest.fixture
def rest_stack():
    srv, port, store = serve(store=APIServer(), port=0)
    client = RESTClient(f"http://127.0.0.1:{port}", timeout=5.0)
    yield srv, port, store, client
    srv.shutdown()


def test_trace_header_survives_rest_bind(rest_stack):
    srv, port, store, client = rest_stack
    store.create("nodes", make_node("rest-0"))
    store.create("pods", make_pod("rp-0"))
    # a trace id the SERVER process cannot know from its own active
    # index: only the X-Trace-Context header can deliver it
    with bind_context({"default/rp-0": "feedbeefcafe0001"}):
        errs = client.bind_pods(
            [Binding(pod_name="rp-0", pod_namespace="default",
                     target_node="rest-0")]
        )
    assert errs == [None]
    stamps = tracer.stamps_for("feedbeefcafe0001")
    assert any(
        s["event"] == "applied" and s["node"] == "rest-0" for s in stamps
    ), stamps
    # /debug/traces on the API server resolves the foreign id too
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/debug/traces?id=feedbeefcafe0001",
        timeout=5,
    ) as r:
        payload = json.loads(r.read())
    assert payload["store_stamps"][0]["event"] == "applied"


def test_trace_header_stamps_fenced_zombie_bind(rest_stack):
    srv, port, store, client = rest_stack
    store.create("nodes", make_node("rest-1"))
    store.create("pods", make_pod("rp-1"))
    stale = BindFence(
        namespace="kube-system", name="kube-scheduler",
        identity="zombie-a", transitions=41,
    )
    with bind_context({"default/rp-1": "feedbeefcafe0002"}):
        with pytest.raises(LeaderFenced):
            client.bind_pods(
                [Binding(pod_name="rp-1", pod_namespace="default",
                         target_node="rest-1")],
                fence=stale,
            )
    stamps = tracer.stamps_for("feedbeefcafe0002")
    assert any(
        s["event"] == "fenced" and s["identity"] == "zombie-a"
        for s in stamps
    ), stamps
    # the fenced bind applied NOTHING
    assert store.get("pods", "default", "rp-1").spec.node_name == ""


def test_apiserver_rest_metrics_endpoint(rest_stack):
    srv, port, store, client = rest_stack
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5
    ) as r:
        assert "# TYPE" in r.read().decode()


# -- monotonic discipline ------------------------------------------------------


def test_spans_use_monotonic_never_wall_clock():
    """Deflake guard: tracing.py must never call time.time() for span
    timestamps (wall-clock steps would fabricate negative stages)."""
    import inspect

    import kubernetes_tpu.utils.tracing as tracing_mod

    src = inspect.getsource(tracing_mod)
    assert "time.time()" not in src
    assert "time.monotonic()" in src


# -- ISSUE 25: the loop's wall, a pod's stages over a window, the store's ------
# -- write path and the stalls, as series on /metrics --------------------------

import contextlib  # noqa: E402
import glob  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

from kubernetes_tpu.utils import tracing as tracing_mod  # noqa: E402

POD_STAGES = ("queue", "encode", "device", "readback", "guard", "assume", "bind")
N_WINDOW = 3000  # three times the ring


def _hist_n(name, labels=None):
    h = metrics.histogram(name, labels)
    return h.n if h is not None else 0


def _hist_total(name, labels=None):
    h = metrics.histogram(name, labels)
    return h.total if h is not None else 0.0


def _device_sched(server):
    # the device path at any batch size (the small-batch host lane is for
    # clusters of <= 256 nodes: switched off, as at 5,000 nodes)
    return Scheduler(server, KubeSchedulerConfiguration(small_batch_host_max=0))


def _bound(server):
    pods, _ = server.list("pods")
    return sum(1 for p in pods if p.spec.node_name)


@pytest.fixture(scope="module")
def driven_loop():
    """One driven loop for the tests below: 40 nodes, a warm-up, then
    3,000 pods through the wave path of an in-process scheduler; the
    phase totals and the registry before and after."""
    tracer.reset()
    metrics.reset()
    server = APIServer()
    sched = _device_sched(server)
    for i in range(40):
        server.create("nodes", make_node(f"ph-{i}", cpu="128"))
    sched.start()
    try:
        for i in range(8):
            server.create("pods", make_pod(f"warm-{i}"))
        assert wait_until(lambda: _bound(server) >= 8, 180)
        assert sched.wait_for_idle(30)
        tracer.publish_gauges()
        before = {
            "phases": sched._phase.totals(),
            "waits": sched._phase.queue_waits(),
            "queue_s": _hist_total(tracing_mod.HIST_POD_STAGE,
                                   {"stage": "queue"}),
            "admits": _hist_n(tracing_mod.HIST_ADMIT_LAG),
            "t": time.monotonic(),
            "pod_stage": {s: _hist_n(tracing_mod.HIST_POD_STAGE, {"stage": s})
                          for s in POD_STAGES},
            "completed": metrics.counter(
                "tracing_traces_completed_total", {"kind": "pod"}),
        }
        # no collector pause inside the window: a generation-2 pass over
        # this test process's heap is 0.1 s wherever it happens to land,
        # and would count against whatever phase that is
        import gc

        gc.collect()
        gc.disable()
        try:
            for i in range(N_WINDOW):
                server.create("pods", make_pod(f"win-{i}"))
            assert wait_until(lambda: _bound(server) >= N_WINDOW + 8, 300)
            assert sched.wait_for_idle(60)
            after = {"phases": sched._phase.totals(),
                     "waits": sched._phase.queue_waits(),
                     "t": time.monotonic()}
        finally:
            gc.enable()
        sched._phase.publish()
        tracer.publish_gauges()
        after["pod_stage"] = {
            s: _hist_n(tracing_mod.HIST_POD_STAGE, {"stage": s})
            for s in POD_STAGES}
        after["completed"] = metrics.counter(
            "tracing_traces_completed_total", {"kind": "pod"})
        after["queue_s"] = _hist_total(tracing_mod.HIST_POD_STAGE,
                                       {"stage": "queue"})
        after["admits"] = _hist_n(tracing_mod.HIST_ADMIT_LAG)
        after["page"] = metrics.render_prometheus()
        yield before, after
    finally:
        sched.stop()
        tracer.reset()


def _phase_deltas(before, after):
    d = {}
    for (phase, _inflight), v in after["phases"].items():
        d[phase] = d.get(phase, 0.0) + v - before["phases"].get(
            (phase, _inflight), 0.0)
    return d


def test_loop_phases_sum_to_the_wall(driven_loop):
    before, after = driven_loop
    d = _phase_deltas(before, after)
    wall = after["t"] - before["t"]
    assert sum(d.values()) == pytest.approx(wall, rel=0.01), (d, wall)


def test_loop_phase_other_stays_small(driven_loop):
    before, after = driven_loop
    d = _phase_deltas(before, after)
    assert d.get("other", 0.0) < 0.02 * sum(d.values()), d


@pytest.mark.parametrize(
    "phase", ["pop", "lock_wait", "encode", "launch", "readback", "guard",
              "assume", "bind"])
def test_loop_phase_is_entered(driven_loop, phase):
    """`bind` is the hand-off of a wave's bindings to the bind lane: the
    loop enters it once a wave, however short the stay."""
    before, after = driven_loop
    assert _phase_deltas(before, after).get(phase, 0.0) > 0.0
    assert (f'scheduler_loop_phase_seconds_total{{inflight="0",'
            f'phase="{phase}"}}' in after["page"]
            or f'scheduler_loop_phase_seconds_total{{inflight="1",'
            f'phase="{phase}"}}' in after["page"])


@pytest.mark.parametrize("stage", POD_STAGES)
def test_pod_stage_series_counts_every_finished_pod(driven_loop, stage):
    """The ring holds 1,024 traces; the series holds the whole window."""
    before, after = driven_loop
    assert after["completed"] - before["completed"] == N_WINDOW
    # a requeued pod has several spans of a stage and counts once
    assert after["pod_stage"][stage] - before["pod_stage"][stage] == N_WINDOW
    assert f'scheduling_pod_stage_duration_seconds_count{{stage="{stage}"}}' \
        in after["page"]


@pytest.mark.parametrize("stage", ["encode", "flush", "kernel", "guard",
                                   "assume"])
def test_stage_histograms_share_the_phase_boundaries(driven_loop, stage):
    _before, after = driven_loop
    assert f'scheduling_stage_duration_seconds_count{{stage="{stage}"}}' \
        in after["page"]


def test_cache_lock_wait_is_observed_per_acquisition(driven_loop):
    _before, after = driven_loop
    assert _hist_n("scheduler_cache_lock_wait_seconds") >= _hist_n(
        "scheduling_stage_duration_seconds", {"stage": "flush"}) > 0


def test_queue_wait_split_closes_on_the_queue_spans(driven_loop):
    """The pod-seconds the phases were credited over the window are the
    `queue` spans' sum (every visit of every pod: no backoff here; the
    gap is the microseconds from pop_batch's return to `prepare`), the
    visits are at least the window's pods, and each window pod was
    admitted once with its commit -> admit lag."""
    before, after = driven_loop
    (w0, n0), (w1, n1) = before["waits"], after["waits"]
    counted = sum(w1.values()) - sum(w0.values())
    spans = after["queue_s"] - before["queue_s"]
    assert counted == pytest.approx(spans, rel=0.05), (counted, spans)
    assert counted <= spans
    assert n1 - n0 >= N_WINDOW
    assert w1["pop"] - w0.get("pop", 0.0) > 0.0
    assert after["admits"] - before["admits"] == N_WINDOW
    assert "scheduler_queue_waits_total" in after["page"]
    assert 'scheduler_queue_wait_seconds_total{phase="pop"}' in after["page"]


def test_tracing_off_keeps_loop_and_store_series():
    """KTPU_TRACING=0: every tracer entry point is one attribute test, so
    the pod-stage series stays empty; the loop's phases and the store's
    commit stages are always-on."""
    metrics.reset()
    tracer.set_enabled(False)
    server = APIServer()
    sched = _device_sched(server)
    try:
        for i in range(4):
            server.create("nodes", make_node(f"off-{i}"))
        sched.start()
        for i in range(12):
            server.create("pods", make_pod(f"off-{i}"))
        assert wait_until(lambda: _bound(server) >= 12, 180)
        assert sched.wait_for_idle(30)
        sched._phase.publish()
        tracer.publish_gauges()
        page = metrics.render_prometheus()
        assert "scheduling_pod_stage_duration_seconds" not in page
        assert "scheduler_pod_admit_lag_seconds" not in page
        assert "scheduler_loop_phase_seconds_total" in page
        assert "scheduler_queue_wait_seconds_total" in page
        assert "scheduler_queue_waits_total" in page
        assert _hist_n("store_commit_stage_seconds",
                       {"op": "bind", "kind": "pods", "stage": "apply"}) >= 1
        assert _hist_n("store_lock_wait_seconds",
                       {"op": "create", "kind": "pods"}) >= 12
        assert _hist_n("scheduling_stage_duration_seconds",
                       {"stage": "guard"}) >= 1
    finally:
        sched.stop()
        tracer.set_enabled(True)


def test_profiler_session_holds_loop_phase_annotations(tmp_path):
    """A profiler session started from outside (as the benchmark's
    --trace 1 does) records the loop's phases as ktpu.loop.* host events
    on the trace's own clock."""
    import jax

    server = APIServer()
    sched = _device_sched(server)
    try:
        for i in range(4):
            server.create("nodes", make_node(f"pr-{i}"))
        sched.start()
        for i in range(6):
            server.create("pods", make_pod(f"prw-{i}"))
        assert wait_until(lambda: _bound(server) >= 6, 180)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for k in range(4):
                for i in range(5):
                    server.create("pods", make_pod(f"pr-{k}-{i}"))
                time.sleep(0.1)
            assert wait_until(lambda: _bound(server) >= 26, 60)
        finally:
            jax.profiler.stop_trace()
    finally:
        sched.stop()
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert files
    data = jax.profiler.ProfileData.from_file(files[-1])
    everything, loop_lines = [], []
    for plane in data.planes:
        for line in plane.lines:
            evs = list(line.events)
            everything.extend(evs)
            mine = [e for e in evs if e.name.startswith("ktpu.loop.")]
            if mine:
                assert plane.name.startswith("/host:"), plane.name
                loop_lines.append(mine)
    # another scheduler alive in this process (a module fixture) idles in
    # `pop` on a line of its own: this one's is the line that launched
    loop_lines = [evs for evs in loop_lines
                  if any(e.name == "ktpu.loop.launch" for e in evs)]
    assert len(loop_lines) == 1, "one loop thread, one host line"
    names = {e.name for e in loop_lines[0]}
    assert {"ktpu.loop.pop", "ktpu.loop.launch", "ktpu.loop.readback",
            "ktpu.loop.bind"} <= names, names
    lo = min(e.start_ns for e in everything)
    hi = max(e.start_ns + e.duration_ns for e in everything)
    for e in loop_lines[0]:
        assert lo <= e.start_ns and e.start_ns + e.duration_ns <= hi
    # phases do not overlap: the thread is in one at a time
    spans = sorted((e.start_ns, e.start_ns + e.duration_ns)
                   for e in loop_lines[0])
    assert all(a[1] <= b[0] + 1 for a, b in zip(spans, spans[1:]))


def test_stall_log_lists_passes_and_gc_pauses(bound_cluster):
    server, sched = bound_cluster
    tracing_mod.install_stall_probes()
    t0 = time.monotonic()
    tracing_mod.note_pass("antientropy", t0, 0.0123)
    import gc

    junk = [[i] for i in range(200000)]
    junk.append(junk)
    del junk
    gc.collect()
    ev = tracing_mod.stall_events()
    assert any(p["task"] == "antientropy" and p["ms"] == 12.3
               for p in ev["passes"])
    assert ev["now"] >= t0
    metrics.render_prometheus()  # the collector publishes at a scrape
    assert _hist_n("process_gc_pause_seconds", {"generation": "2"}) >= 1
    assert metrics.gauge("process_clock_seconds") >= t0
    # the same list over HTTP and in the SIGUSR2 dump
    dbg = serve_debug(0)
    try:
        port = dbg.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/traces?stalls=1",
                timeout=5) as r:
            payload = json.loads(r.read())
        assert any(p["task"] == "antientropy" for p in payload["passes"])
        assert set(payload) == {"now", "gc", "passes"}
    finally:
        dbg.shutdown()
    dump = CacheDebugger(sched).dump()
    assert "Dump of stalls" in dump and "antientropy" in dump


def test_background_passes_are_timed(bound_cluster):
    """The queue's flush and the cache's assume-TTL sweep run every
    second on their own threads: each leaves an observation and an
    event."""
    assert wait_until(
        lambda: _hist_n("scheduler_background_pass_seconds",
                        {"task": "queue_flush_backoff"}) >= 1
        and _hist_n("scheduler_background_pass_seconds",
                    {"task": "assume_ttl"}) >= 1, 10)
    tasks = {p["task"] for p in tracing_mod.stall_events()["passes"]}
    assert {"queue_flush_backoff", "assume_ttl"} <= tasks


def test_deferred_pods_are_counted_and_forgotten():
    """scheduler_wave_deferred_pods_total / _max_attempts: a pod deferred
    wave after wave shows as a growing maximum, and leaves it when
    placed."""
    from types import SimpleNamespace

    metrics.reset()
    sched = Scheduler(APIServer(), KubeSchedulerConfiguration(use_device=False))
    a, b = SimpleNamespace(key="d/a"), SimpleNamespace(key="d/b")
    batch = SimpleNamespace(pis=[a, b])
    for _ in range(3):
        sched._note_deferrals(batch, [a])
    assert metrics.counter("scheduler_wave_deferred_pods_total") == 3
    assert metrics.gauge("scheduler_wave_deferred_max_attempts") == 3
    sched._note_deferrals(batch, [b])  # a was placed this time
    assert metrics.gauge("scheduler_wave_deferred_max_attempts") == 1
    sched._note_deferrals(batch, [])
    assert metrics.gauge("scheduler_wave_deferred_max_attempts") == 0


def test_slow_batch_report_is_rendered_from_the_wave_trace():
    t0 = time.monotonic() - 0.5
    tid = tracer.start("wave", "wave/3pods", t0=t0, pods=3)
    tracer.add_span(tid, "launch", t0 + 0.01, t0 + 0.4)
    tracer.add_span(tid, "encode", t0, t0 + 0.01)
    assert tracer.render_if_long(tid, "schedule_batch", 5.0) is None
    text = tracer.render_if_long(tid, "schedule_batch", 0.1)
    lines = text.splitlines()
    assert lines[0].startswith('"schedule_batch" {\'pods\': 3} (')
    assert lines[1].endswith("ms encode") and lines[2].endswith("ms launch")
    assert lines[2].strip().startswith("+390.0ms")
    tracer.finish(tid, outcome="committed")
    assert tracer.render_if_long(tid, "schedule_batch", 0.1) is not None


def test_histogram_merge_feeds_sum_count_and_bucket_quantiles():
    h = Histogram()
    counts = [0] * (len(h.buckets) + 1)
    counts[3] = 9   # <= 1 ms
    counts[8] = 1   # <= 50 ms
    h.merge(counts, 0.059, 10)
    assert (h.n, h.total) == (10, 0.059)
    assert h.quantile(0.5) == h.buckets[3]
    assert h.quantile(0.99) == h.buckets[8]


def test_histogram_set_is_one_hop_for_a_family_and_survives_reset():
    metrics.reset()
    hs = metrics.histogram_set(
        "t25_stage_seconds", {"op": "x", "stage": ("a", "b", "c")}
    ) + metrics.histogram_set("t25_wait_seconds", {"op": "x"})
    hs.observe((0.001, None, 0.02, 0.3))
    hs.observe((0.003, 0.004, 0.02, 0.1))
    assert _hist_n("t25_stage_seconds", {"op": "x", "stage": "a"}) == 2
    assert _hist_n("t25_stage_seconds", {"op": "x", "stage": "b"}) == 1
    assert metrics.histogram("t25_wait_seconds", {"op": "x"}).total == \
        pytest.approx(0.4)
    # bucket quantiles (no reservoir behind a set)
    assert metrics.histogram(
        "t25_stage_seconds", {"op": "x", "stage": "c"}).quantile(0.5) == 0.02
    metrics.reset()  # the set re-resolves its series in the new registry
    hs.observe((0.001, 0.001, 0.001, 0.001))
    assert _hist_n("t25_stage_seconds", {"op": "x", "stage": "b"}) == 1
    assert 't25_wait_seconds_count{op="x"} 1' in metrics.render_prometheus()


# -- the apiserver's write path, from a real cmd/apiserver ---------------------


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _scrape(port):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                timeout=5) as r:
        text = r.read().decode()
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


@contextlib.contextmanager
def apiserver_child(sink, tmp_path):
    """`python -m kubernetes_tpu.cmd.apiserver --data-dir`: WAL + fsync
    on, the native group-commit sink or (`sink == "python"`: no compiler
    on PATH, an empty build cache) the Python one. Yields (port, process,
    WAL path); the process is SIGKILLed on the way out."""
    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if sink == "python":
        env["PATH"] = str(tmp_path / "no-compiler-here")
        env["TMPDIR"] = str(tmp_path / "tmp")
        os.makedirs(env["TMPDIR"])
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubernetes_tpu.cmd.apiserver",
         "--port", str(port), "--data-dir", str(tmp_path / "data")],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    try:
        def up():
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=1).read()
                return True
            except OSError:
                return False

        assert wait_until(up, 60), "cmd/apiserver did not come up"
        yield port, proc, str(tmp_path / "data" / "cluster")
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGKILL)
        proc.wait(10)
        proc.stderr.close()


@pytest.fixture(params=["native", "python"])
def apiserver_process(request, tmp_path):
    with apiserver_child(request.param, tmp_path) as (port, _proc, _wal):
        yield request.param, port


N_OPS = 25
WRITE_SERIES = [
    ('apiserver_request_duration_seconds_count'
     '{resource="pods",verb="POST"}', "create"),
    ('apiserver_request_duration_seconds_count'
     '{resource="pods/binding",verb="POST"}', "bind"),
] + [
    (f'store_commit_stage_seconds_count'
     f'{{kind="pods",op="{op}",stage="{stage}"}}', op)
    for op in ("create", "bind")
    for stage in ("apply", "wal_append", "fsync", "notify")
] + [
    (f'store_lock_wait_seconds_count{{kind="pods",op="{op}"}}', op)
    for op in ("create", "bind")
] + [
    (f'apiserver_request_stage_seconds_count'
     f'{{resource="{res}",stage="{stage}"}}', op)
    for op, res in (("create", "pods"), ("bind", "pods/binding"))
    for stage in ("authz", "read", "admit", "store", "observe", "respond")
]


def test_a_write_is_counted_once_per_stage(apiserver_process):
    """N creates and N binds over REST: every request and stage series
    reads exactly N per op, the WAL counts 2N + 1 records (one node) and
    at most as many physical fsyncs, a bind sent with its trace id
    shows its store stages under /debug/traces?id=, and the watch
    delivered every event."""
    sink, port = apiserver_process
    client = RESTClient(f"http://127.0.0.1:{port}", timeout=10.0)
    seen = []
    watcher = client.watch("pods")

    def pump():
        for ev in watcher:
            seen.append(ev)

    import threading

    threading.Thread(target=pump, daemon=True).start()
    client.create("nodes", make_node("ws-0", cpu="64"))
    before = _scrape(port)
    metrics.reset()
    for i in range(N_OPS):
        client.create("pods", make_pod(f"ws-{i}"))
    binds = [Binding(pod_name=f"ws-{i}", pod_namespace="default",
                     target_node="ws-0") for i in range(N_OPS)]
    # one binding per request, so that every series reads N (a list of N
    # in one request: tests/test_bind_batch.py)
    with bind_context({"default/ws-0": "feedbeefcafe0025"}):
        for b in binds:
            assert client.bind_pods([b]) == [None]
    assert wait_until(lambda: len(seen) >= 2 * N_OPS, 20)
    watcher.stop()
    # a stream folds its deliveries locally and merges them when it idles
    assert wait_until(
        lambda: _scrape(port).get(
            'apiserver_watch_delivery_seconds_count{kind="pods"}', 0.0)
        - before.get(
            'apiserver_watch_delivery_seconds_count{kind="pods"}', 0.0)
        >= 2 * N_OPS, 10)
    page = _scrape(port)

    def delta(name):
        return page.get(name, 0.0) - before.get(name, 0.0)

    for name, _op in WRITE_SERIES:
        assert delta(name) == N_OPS, (name, delta(name))
    assert delta("wal_records_appended_total") == 2 * N_OPS
    assert 1 <= delta("wal_fsyncs_total") <= 2 * N_OPS
    assert delta('wal_fsync_duration_seconds_count') == 2 * N_OPS
    # one client of one thread: nothing to group, one fsync per record
    assert delta("wal_fsyncs_total") == 2 * N_OPS, sink
    assert delta('apiserver_watch_delivery_seconds_count{kind="pods"}') \
        >= 2 * N_OPS
    assert page["apiserver_requests_inflight"] == 1.0  # this scrape
    assert page["process_clock_seconds"] > before["process_clock_seconds"]
    # the client's own series, in this process: one exchange per bind
    assert _hist_n("rest_client_request_duration_seconds",
                   {"verb": "POST", "resource": "pods/binding"}) == N_OPS
    assert _hist_n("rest_client_request_duration_seconds",
                   {"verb": "POST", "resource": "pods"}) == N_OPS
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/traces?id=feedbeefcafe0025",
            timeout=5) as r:
        stamp = json.loads(r.read())["store_stamps"][0]
    assert stamp["event"] == "applied"
    stages = {k for k in stamp if k.endswith("_ms")}
    assert stages == {"lock_wait_ms", "apply_ms", "wal_append_ms",
                      "fsync_ms", "notify_ms"}
    assert stamp["fsync_ms"] > 0.0
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/traces?stalls=1", timeout=5) as r:
        assert set(json.loads(r.read())) == {"now", "gc", "passes"}


# -- ISSUE 38: the queue's wait by the loop phase that held it, and the -------
# -- store's commit -> queue admit leg -----------------------------------------

from types import SimpleNamespace  # noqa: E402

from kubernetes_tpu.client.informers import SharedInformerFactory  # noqa: E402
from kubernetes_tpu.scheduler.eventhandlers import (  # noqa: E402
    add_all_event_handlers,
)
from kubernetes_tpu.scheduler.queue.scheduling_queue import (  # noqa: E402
    PriorityQueue,
)
from kubernetes_tpu.utils.tracing import PhaseTracker  # noqa: E402


class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def _split(t=100.0):
    """A queue and a phase tracker on one injected clock, the loop in
    `other` from t."""
    metrics.reset()
    clock = _Clock(t)
    q = PriorityQueue(clock=clock)
    return clock, q, PhaseTracker(waiting=q.waiting, clock=clock)


def _published():
    return ({p: metrics.counter(tracing_mod.COUNTER_QUEUE_WAIT, {"phase": p})
             for p in ("other", "bind", "readback", "pop", "launch")},
            metrics.counter(tracing_mod.COUNTER_QUEUE_WAITS))


def _at(clock, t, fn, *args, **kw):
    clock.t = t
    return fn(*args, **kw)


def test_queue_wait_pod_seconds_are_exact_by_phase():
    """Pods enter at 101, 103 and 104.5 and leave at pop_batch's returns
    (105.5, 107); the loop is in other, bind, readback, pop, other, pop,
    other: each phase holds exactly the pods x seconds that fell in it,
    and the phases sum to the pods' (entry -> pop_batch return)."""
    clock, q, ph = _split()
    _at(clock, 101.0, q.add, make_pod("qa"))
    _at(clock, 102.0, ph.switch, "bind")
    _at(clock, 103.0, q.add, make_pod("qb"))
    _at(clock, 104.0, ph.switch, "readback")
    _at(clock, 104.5, q.add, make_pod("qc"))
    _at(clock, 105.0, ph.switch, "pop")
    got = _at(clock, 105.5, q.pop_batch, 2)
    assert [pi.key for pi in got] == ["default/qa", "default/qb"]
    _at(clock, 106.0, ph.switch, "other")
    _at(clock, 106.5, ph.switch, "pop")
    assert len(_at(clock, 107.0, q.pop_batch, 8)) == 1
    _at(clock, 107.25, ph.switch, "other")
    _at(clock, 110.0, ph.publish)
    by_phase, visits = _published()
    # other: qa 101-102, then qc 106-106.5; bind: qa 102-104 + qb 103-104;
    # pop: three pods 105-105.5, then qc 105.5-106 and 106.5-107
    assert by_phase == pytest.approx(
        {"other": 1.0 + 0.5, "bind": 3.0, "readback": 2 * 0.5 + 3 * 0.5,
         "pop": 3 * 0.5 + 0.5 + 0.5, "launch": 0.0}, abs=1e-12)
    entry_to_return = (105.5 - 101.0) + (105.5 - 103.0) + (107.0 - 104.5)
    assert sum(by_phase.values()) == pytest.approx(entry_to_return, abs=1e-12)
    assert visits == 3
    waits, handed = ph.queue_waits()
    assert sum(waits.values()) == pytest.approx(entry_to_return, abs=1e-12)
    assert handed == 3


def test_a_scrape_mid_phase_publishes_up_to_its_instant():
    clock, q, ph = _split()
    _at(clock, 100.0, q.add, make_pod("sa"))
    _at(clock, 100.0, q.add, make_pod("sb"))
    _at(clock, 101.0, ph.switch, "bind")
    _at(clock, 101.5, ph.publish)        # two pods, half a second of bind
    assert _published()[0]["bind"] == pytest.approx(1.0, abs=1e-12)
    assert _published()[0]["other"] == pytest.approx(2.0, abs=1e-12)
    _at(clock, 102.0, ph.switch, "pop")
    _at(clock, 102.5, q.pop_batch, 8)
    _at(clock, 103.0, ph.switch, "other")
    _at(clock, 104.0, ph.publish)        # only the rest is new
    by_phase, visits = _published()
    assert by_phase["bind"] == pytest.approx(2.0, abs=1e-12)
    assert by_phase["pop"] == pytest.approx(1.0, abs=1e-12)
    assert by_phase["other"] == pytest.approx(2.0, abs=1e-12)
    assert visits == 2
    _at(clock, 105.0, ph.publish)        # nothing waits: nothing new
    assert _published() == (by_phase, visits)


def test_a_pod_readded_after_deferral_counts_a_second_wait():
    clock, q, ph = _split()
    _at(clock, 100.0, q.add, make_pod("ra"))
    _at(clock, 101.0, ph.switch, "pop")
    (pi,) = _at(clock, 101.0, q.pop_batch, 8)
    _at(clock, 101.0, ph.switch, "launch")
    _at(clock, 102.0, q.readd, pi)       # deferred by the wave
    _at(clock, 103.0, ph.switch, "pop")
    assert _at(clock, 103.5, q.pop_batch, 8) == [pi]
    _at(clock, 104.0, ph.switch, "other")
    _at(clock, 104.0, ph.publish)
    by_phase, visits = _published()
    assert visits == 2
    assert sum(by_phase.values()) == pytest.approx(1.0 + 1.5, abs=1e-12)
    assert metrics.counter(tracing_mod.COUNTER_QUEUE_WAIT,
                           {"phase": "launch"}) == pytest.approx(1.0)


def test_queue_wait_integral_survives_racing_writers():
    """Eight adders, one batch former and a scrape thread that switches
    phases, with the interpreter switching threads every microsecond: a
    lost update of the population or of the forming count would leave
    pods counted after the last pop, or visits unequal to the adds."""
    import sys
    import threading

    metrics.reset()
    q = PriorityQueue()
    ph = PhaseTracker(waiting=q.waiting)
    n_adders, per = 8, 150
    done = threading.Event()

    def adder(a):
        for i in range(per):
            q.add(make_pod(f"race-{a}-{i}"))

    def former():
        got = 0
        while got < n_adders * per:
            got += len(q.pop_batch(16, timeout=0.05))
        done.set()

    def scraper():
        phases = ("bind", "readback", "pop", "launch")
        i = 0
        while not done.is_set():
            ph.switch(phases[i % 4])
            ph.publish()
            i += 1

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=adder, args=(a,))
                   for a in range(n_adders)]
        threads += [threading.Thread(target=former),
                    threading.Thread(target=scraper)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    area, n, _at, handed = q.waiting()
    assert (n, handed) == (0, n_adders * per)
    ph.switch("other")
    ph.publish()
    published = sum(metrics.counter(tracing_mod.COUNTER_QUEUE_WAIT,
                                    {"phase": p})
                    for p in ("bind", "readback", "pop", "launch", "other"))
    assert published == pytest.approx(area, rel=1e-9)
    assert metrics.counter(tracing_mod.COUNTER_QUEUE_WAITS) == n_adders * per


class _NotAssumed:
    def is_assumed(self, key):
        return False


def _wired(server):
    """The scheduler's own informer handlers (eventhandlers.py) over an
    informer factory and a queue, with nothing else of a scheduler."""
    factory = SharedInformerFactory(server)
    sched = SimpleNamespace(
        informer_factory=factory, cache=_NotAssumed(), queue=PriorityQueue(),
        profiles=SimpleNamespace(for_pod=lambda pod: object()))
    add_all_event_handlers(sched)
    factory.start()
    assert factory.wait_for_cache_sync(10)
    return sched, factory


def test_admit_lag_is_observed_once_per_first_admission():
    """Five creates reach the queue through the watch: five observations,
    each the trace's own `admit_lag_s`. A readd, an update and a second
    informer's list of the same pods (a relist) observe nothing."""
    metrics.reset()
    server = APIServer()
    sched, factory = _wired(server)
    other = None
    try:
        for i in range(5):
            server.create("pods", make_pod(f"al-{i}"))
        assert wait_until(lambda: sched.queue.active_len() == 5, 10)
        tracer.publish_gauges()
        h = metrics.histogram(tracing_mod.HIST_ADMIT_LAG)
        assert h.n == 5 and 0.0 <= h.total < 5.0
        attrs = [tracer.get(pi.trace_id)["attrs"]
                 for pi in sched.queue.pending_pod_infos()]
        assert sum(a["admit_lag_s"] for a in attrs) == pytest.approx(h.total)
        for pi in sched.queue.pop_batch(8):
            sched.queue.readd(pi)
        pod = server.get("pods", "default", "al-0")
        pod.metadata.labels["touched"] = "yes"
        server.update("pods", pod)
        other, other_factory = _wired(server)
        assert wait_until(lambda: other.queue.active_len() == 5, 10)
        tracer.publish_gauges()
        assert metrics.histogram(tracing_mod.HIST_ADMIT_LAG).n == 5
    finally:
        factory.stop()
        if other is not None:
            other_factory.stop()


def test_the_commit_instant_is_the_stores_after_the_decode(rest_stack):
    """A create's watch event carries the store's commit instant, on the
    in-process watch and on both REST wires: later than the instant the
    apiserver decoded the body (the dataclass default of a body without
    creationTimestamp), no later than the reply."""
    srv, port, store, client = rest_stack
    local = store.watch("pods")
    binary = client.watch("pods")
    url = client._url("pods", "default") + "?watch=1&resourceVersion=0"
    lines = urllib.request.urlopen(url, timeout=10)   # no Accept: JSON lines
    body = {"metadata": {"name": "ci-0", "namespace": "default"},
            "spec": {"containers": [{"name": "c",
                                     "resources": {"requests": {"cpu": "1m"}}}]}}
    sent = time.time()
    client._request("POST", client._url("pods", "default"), body)
    replied = time.time()
    decoded = store.get("pods", "default", "ci-0").metadata.creation_timestamp
    assert sent <= decoded

    def added(w):
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            ev = w.get(timeout=0.5)
            if ev is not None and ev.type == "ADDED":
                return ev
        raise AssertionError("no ADDED event")

    committed = [added(local).committed, added(binary).committed]
    for line in lines:
        msg = json.loads(line)
        if msg["type"] == "ADDED":
            committed.append(msg["committed"])
            break
    lines.close()
    binary.stop()
    local.stop()
    assert len(committed) == 3 and len(set(committed)) == 1
    assert decoded < committed[0] <= replied
