"""Benchmark harness: in-process topology, throughput + latency collection.

Mirrors test/integration/scheduler_perf (util.go:55 mustSetupScheduler,
:210-251 throughputCollector): in-memory API store + real scheduler + real
informers, no kubelets (binding is acknowledged by the store, the moral
equivalent of the fake PV controller / hollow-node trick). Reports
sustained throughput (scheduled pods per second over the measurement
window) and the latency histograms the reference collects.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ..api.objects import Pod
from ..client.apiserver import APIServer
from ..scheduler import KubeSchedulerConfiguration, Scheduler
from ..utils.metrics import metrics
from ..utils.tracing import tracer
from .workloads import WorkloadConfig, build_workload


@dataclass
class BenchResult:
    workload: str
    num_nodes: int
    num_measured_pods: int
    duration_s: float
    throughput_pods_per_s: float
    scheduled: int
    unscheduled: int
    e2e_p50_ms: float = 0.0
    e2e_p90_ms: float = 0.0
    e2e_p99_ms: float = 0.0
    algo_p99_ms: float = 0.0
    # per-batch stage breakdown (sums over the measurement window)
    encode_total_s: float = 0.0
    kernel_total_s: float = 0.0
    n_batches: int = 0
    # pipeline amortization: device->host readbacks per launched wave batch
    # (< 1.0 means one readback is being shared across batches)
    n_readbacks: int = 0
    readbacks_per_batch: float = 0.0
    # device-side ("algo-only") latency: wall of the kernel stage — device
    # compute + the one result sync — per readback (p50/p99) and averaged
    # per scheduled pod. Subtracting the measured readback RTT isolates the
    # algorithm from the host<->device link.
    kernel_cycle_p50_ms: float = 0.0
    kernel_cycle_p99_ms: float = 0.0
    kernel_per_pod_ms: float = 0.0
    # wave pipelining on the generational snapshot: configured depth and
    # the high-water mark of batches concurrently in flight (≥2 is the
    # pipelined-wave acceptance bar — one wave's device time overlapping
    # another's readback/bind instead of serializing on a device lock)
    pipeline_depth: int = 0
    max_waves_inflight: int = 0
    samples: List[int] = field(default_factory=list)  # scheduled count / 100ms

    def to_dict(self) -> dict:
        d = self.__dict__.copy()
        d.pop("samples", None)
        return d


def run_benchmark(
    cfg: WorkloadConfig,
    sched_config: Optional[KubeSchedulerConfiguration] = None,
    timeout_s: float = 300.0,
    quiet: bool = True,
    presize_nodes: Optional[int] = None,
    xplane_dir: Optional[str] = None,
) -> BenchResult:
    """xplane_dir: capture a jax-profiler (XPlane/TensorBoard) trace of the
    measured window — the device-side profiling hook SURVEY §5 calls for
    (the reference's /debug/pprof analogue for the TPU data plane). View
    with TensorBoard or xprof."""
    metrics.reset()
    server = APIServer()
    scfg = sched_config or KubeSchedulerConfiguration()
    sched = Scheduler(server, scfg)
    # presize for a larger target cluster so a warm-up run compiles the same
    # kernel variant (same v_cap/n_cap) the measured run will use
    with sched.cache.lock:
        sched.cache.encoder.presize_for_cluster(presize_nodes or cfg.num_nodes)

    nodes, init_pods, factory = build_workload(cfg)
    for n in nodes:
        server.create("nodes", n)

    sched.start()
    try:
        if xplane_dir:
            import jax

            with jax.profiler.trace(xplane_dir):
                return _run_benchmark_body(
                    cfg, server, sched, init_pods, factory, timeout_s, quiet
                )
        return _run_benchmark_body(
            cfg, server, sched, init_pods, factory, timeout_s, quiet
        )
    finally:
        sched.stop()


def _run_benchmark_body(
    cfg: WorkloadConfig,
    server: APIServer,
    sched: Scheduler,
    init_pods: List[Pod],
    factory,
    timeout_s: float,
    quiet: bool,
) -> BenchResult:
    # init pods: scheduled before measurement starts (mustSetupScheduler's
    # "init pods" stage)
    for p in init_pods:
        server.create("pods", p)
    _wait_all_scheduled(server, len(init_pods), timeout_s)

    measured = [factory(i) for i in range(cfg.num_measured_pods)]
    # baseline the stage histograms so the breakdown covers only the
    # measurement window (init pods above already ran encode/kernel)
    _e0 = metrics.histogram("scheduling_stage_duration_seconds", {"stage": "encode"})
    _k0 = metrics.histogram("scheduling_stage_duration_seconds", {"stage": "kernel"})
    base_enc, base_kern, base_n = (
        (_e0.total if _e0 else 0.0),
        (_k0.total if _k0 else 0.0),
        (_k0.n if _k0 else 0),
    )
    base_batches = metrics.counter("scheduler_wave_batches_total")
    base_readbacks = metrics.counter("scheduler_wave_readbacks_total")
    # warm the kernel before the clock starts (XLA compile is one-off)
    t0 = time.monotonic()
    for p in measured:
        server.create("pods", p)
    create_done = time.monotonic()

    total_target = len(init_pods) + cfg.num_measured_pods
    samples = []
    deadline = time.monotonic() + timeout_s
    scheduled = 0
    while time.monotonic() < deadline:
        scheduled = _count_scheduled(server)
        samples.append(scheduled)
        if scheduled >= total_target:
            break
        time.sleep(0.05)
    t1 = time.monotonic()

    measured_scheduled = scheduled - len(init_pods)
    duration = t1 - t0
    thr = measured_scheduled / duration if duration > 0 else 0.0
    e2e = metrics.histogram("e2e_scheduling_duration_seconds")
    algo = metrics.histogram("scheduling_algorithm_duration_seconds")
    enc_h = metrics.histogram(
        "scheduling_stage_duration_seconds", {"stage": "encode"}
    )
    kern_h = metrics.histogram(
        "scheduling_stage_duration_seconds", {"stage": "kernel"}
    )
    n_wave_batches = int(
        metrics.counter("scheduler_wave_batches_total") - base_batches
    )
    n_readbacks = int(
        metrics.counter("scheduler_wave_readbacks_total") - base_readbacks
    )
    res = BenchResult(
        workload=cfg.name,
        num_nodes=cfg.num_nodes,
        num_measured_pods=cfg.num_measured_pods,
        duration_s=duration,
        throughput_pods_per_s=thr,
        scheduled=measured_scheduled,
        unscheduled=cfg.num_measured_pods - measured_scheduled,
        e2e_p50_ms=(e2e.quantile(0.5) * 1000 if e2e else 0.0),
        e2e_p90_ms=(e2e.quantile(0.9) * 1000 if e2e else 0.0),
        e2e_p99_ms=(e2e.quantile(0.99) * 1000 if e2e else 0.0),
        algo_p99_ms=(algo.quantile(0.99) * 1000 if algo else 0.0),
        encode_total_s=((enc_h.total if enc_h else 0.0) - base_enc),
        kernel_total_s=((kern_h.total if kern_h else 0.0) - base_kern),
        n_batches=(
            n_wave_batches
            if n_wave_batches > 0
            else ((kern_h.n if kern_h else 0) - base_n)
        ),
        n_readbacks=n_readbacks,
        readbacks_per_batch=(
            n_readbacks / n_wave_batches if n_wave_batches > 0 else 0.0
        ),
        # quantiles over the MEASURED window only (samples past base_n):
        # the init-pod stage's compile-laden cycles would otherwise own p99
        kernel_cycle_p50_ms=(
            kern_h.quantiles_since(base_n, (0.5,))[0] * 1000 if kern_h else 0.0
        ),
        kernel_cycle_p99_ms=(
            kern_h.quantiles_since(base_n, (0.99,))[0] * 1000 if kern_h else 0.0
        ),
        kernel_per_pod_ms=(
            ((kern_h.total if kern_h else 0.0) - base_kern)
            / measured_scheduled
            * 1000
            if measured_scheduled > 0
            else 0.0
        ),
        pipeline_depth=sched._pipeline_depth,
        max_waves_inflight=int(
            metrics.gauge("scheduler_wave_inflight_max") or 0
        ),
        samples=samples,
    )
    if not quiet:
        print(
            f"{cfg.name}/{cfg.num_nodes}: {thr:.0f} pods/s "
            f"({measured_scheduled}/{cfg.num_measured_pods} in {duration:.2f}s; "
            f"create took {create_done - t0:.2f}s), "
            f"e2e p99 {res.e2e_p99_ms:.1f}ms"
        )
    return res


@dataclass
class LatencyResult:
    """Steady-state per-pod latency: pods injected at a fixed rate below
    saturation, latency = queue entry → bound (incl. queue wait). This is
    the honest p99 the burst-throughput run can't show (its per-pod latency
    is dominated by the batch former's deliberate batching window).
    Metric semantics: reference pod_scheduling_duration_seconds /
    e2e_scheduling_duration_seconds (scheduler_perf util.go:127-195)."""

    workload: str
    num_nodes: int
    rate_pods_per_s: float
    scheduled: int
    pod_p50_ms: float
    pod_p90_ms: float
    pod_p99_ms: float
    cycle_p50_ms: float
    cycle_p99_ms: float
    # where the pod latency lives: time-in-queue (queue entry → cycle
    # start, from the real per-pod "queue" spans) vs time-in-flight (the
    # in-cycle e2e histogram). pod_* ≈ queue_wait_* + in_flight_* at the
    # mean; the percentiles are each distribution's own, not a sum.
    queue_wait_p50_ms: float = 0.0
    queue_wait_p99_ms: float = 0.0
    in_flight_p50_ms: float = 0.0
    in_flight_p99_ms: float = 0.0
    # split-phase readback amortization: host-BLOCKING device syncs per
    # bound pod over the measured window (< 1.0 means most binds consumed
    # an already-landed async transfer; the r17 acceptance metric)
    readbacks_per_bind: float = 0.0
    # wave pipelining over the measured window (see BenchResult)
    pipeline_depth: int = 0
    max_waves_inflight: int = 0
    # per-stage waterfall from REAL per-pod spans (utils/tracing.py):
    # stage -> {count, total_s, p50_ms, p99_ms}, waterfall order
    stage_waterfall: Optional[dict] = None
    # mean per-trace in-cycle stage sum over the e2e histogram mean —
    # the reconciliation check (acceptance: within 5% of 1.0)
    waterfall_vs_e2e: float = 0.0
    # the p99 exemplar's trace id + its full rendered trace: "what is
    # the p99" answered with the actual pod's waterfall
    p99_trace_id: str = ""
    p99_trace: Optional[dict] = None


def run_latency_benchmark(
    cfg: WorkloadConfig,
    rate_pods_per_s: float,
    n_pods: int = 1000,
    sched_config: Optional[KubeSchedulerConfiguration] = None,
    timeout_s: float = 120.0,
    presize_nodes: Optional[int] = None,
) -> LatencyResult:
    """Inject pods one at a time at a fixed rate and report per-pod latency
    percentiles. The rate should be well below the burst throughput so the
    queue never backs up (latency is then scheduling cost, not queue depth)."""
    metrics.reset()
    tracer.reset()
    server = APIServer()
    scfg = sched_config or KubeSchedulerConfiguration()
    sched = Scheduler(server, scfg)
    with sched.cache.lock:
        sched.cache.encoder.presize_for_cluster(presize_nodes or cfg.num_nodes)

    nodes, init_pods, factory = build_workload(cfg)
    for n in nodes:
        server.create("nodes", n)
    sched.start()
    try:
        for p in init_pods:
            server.create("pods", p)
        _wait_all_scheduled(server, len(init_pods), timeout_s)

        # warm both padded-batch kernel variants (single pod → small bucket)
        # so the measured window sees no XLA compiles
        warm = factory(10**6)
        server.create("pods", warm)
        _wait_all_scheduled(server, len(init_pods) + 1, timeout_s)
        metrics.reset()
        # trace window matches the metrics window: the waterfall must
        # describe the measured pods, not init/warmup cycles
        tracer.reset()
        # the reset wiped the inflight-max gauge, but the scheduler only
        # republishes it when the peak GROWS — zero the peak too, or the
        # measured window can never re-reach the warmup burst's depth and
        # max_waves_inflight reads 0 forever
        sched._wave_inflight_peak = 0

        interval = 1.0 / rate_pods_per_s
        t_next = time.monotonic()
        for i in range(n_pods):
            server.create("pods", factory(i))
            t_next += interval
            pause = t_next - time.monotonic()
            if pause > 0:
                time.sleep(pause)
        deadline = time.monotonic() + timeout_s
        target = len(init_pods) + 1 + n_pods
        while time.monotonic() < deadline:
            if _count_scheduled(server) >= target:
                break
            time.sleep(0.02)
        scheduled = _count_scheduled(server) - len(init_pods) - 1
    finally:
        sched.stop()

    pod_h = metrics.histogram("pod_scheduling_duration_seconds")
    e2e_h = metrics.histogram("e2e_scheduling_duration_seconds")
    q = lambda h, p: (h.quantile(p) * 1000 if h else 0.0)  # noqa: E731
    waterfall, vs_e2e = _stage_waterfall(e2e_h)
    queue_stats = tracer.stage_stats(kind="pod").get("queue") or {}
    blocking = metrics.counter("scheduler_wave_readbacks_blocking_total")
    p99_tid, p99_trace = "", None
    if e2e_h is not None:
        ex = e2e_h.exemplar_near(0.99)
        if ex is not None:
            p99_tid = ex[1]
            p99_trace = tracer.get(p99_tid)
    return LatencyResult(
        workload=cfg.name,
        num_nodes=cfg.num_nodes,
        rate_pods_per_s=rate_pods_per_s,
        scheduled=scheduled,
        pod_p50_ms=q(pod_h, 0.5),
        pod_p90_ms=q(pod_h, 0.9),
        pod_p99_ms=q(pod_h, 0.99),
        cycle_p50_ms=q(e2e_h, 0.5),
        cycle_p99_ms=q(e2e_h, 0.99),
        queue_wait_p50_ms=float(queue_stats.get("p50_ms", 0.0)),
        queue_wait_p99_ms=float(queue_stats.get("p99_ms", 0.0)),
        in_flight_p50_ms=q(e2e_h, 0.5),
        in_flight_p99_ms=q(e2e_h, 0.99),
        readbacks_per_bind=(blocking / scheduled if scheduled > 0 else 0.0),
        pipeline_depth=sched._pipeline_depth,
        max_waves_inflight=int(
            metrics.gauge("scheduler_wave_inflight_max") or 0
        ),
        stage_waterfall=waterfall,
        waterfall_vs_e2e=vs_e2e,
        p99_trace_id=p99_tid,
        p99_trace=p99_trace,
    )


# pod-trace stages INSIDE the scheduling cycle (everything after the
# queue wait): their per-trace sum must reconcile with what the
# e2e_scheduling_duration_seconds histogram measured for the same pods.
# outage.wait is deliberately absent: only outcome=="bound" traces enter
# the numerator (below) because only those pods observe e2e — a
# ride-through "landed"/"rebound" pod never does, and its multi-second
# outage span would poison the ratio without any matching e2e sample.
_CYCLE_STAGES = (
    "encode", "device", "readback", "guard", "assume", "bind", "algo",
)


def _stage_waterfall(e2e_h) -> tuple:
    """(stage waterfall dict, mean in-cycle stage sum / e2e mean) from
    the tracer ring's completed pod traces. The ratio is the built-in
    honesty check: spans are contiguous stamps of the same wall interval
    the e2e histogram observes, so a drift past a few percent means the
    span chain has a hole (a stage nobody attributes)."""
    waterfall = tracer.stage_stats(kind="pod")
    if e2e_h is None or not e2e_h.n:
        return waterfall, 0.0
    sums = []
    for d in tracer.slowest(10**6, kind="pod"):
        stages = d.get("stages_ms", {})
        if d.get("outcome") != "bound":
            continue
        sums.append(
            sum(v for k, v in stages.items() if k in _CYCLE_STAGES) / 1e3
        )
    if not sums:
        return waterfall, 0.0
    return waterfall, (sum(sums) / len(sums)) / e2e_h.avg


@dataclass
class AutoscalerBenchResult:
    """The `autoscaler` bench workload: N pending pods against an empty
    cluster with a candidate-shape catalog — how long until the
    scale-up→provision→flush→bind loop has EVERY pod bound."""

    num_pods: int
    num_shapes: int
    scheduled: int
    time_to_all_bound_s: float
    nodes_provisioned: int
    nodes_by_group: Dict[str, int]
    simulation_passes: int
    simulation_p50_ms: float
    simulation_p99_ms: float


def run_autoscaler_benchmark(
    n_pods: int = 1000,
    pod_cpu: str = "500m",
    timeout_s: float = 300.0,
    period_s: float = 0.5,
    max_provision_per_cycle: int = 16,
) -> AutoscalerBenchResult:
    """Time-to-all-bound for a pending-pod burst served entirely by
    autoscaler-provisioned capacity (store-acked hollow nodes, like the
    throughput harness)."""
    from ..api.objects import Container, ObjectMeta, PodSpec
    from ..autoscaler import ClusterAutoscaler, NodeGroupCatalog
    from .workloads import autoscaler_candidate_shapes

    metrics.reset()
    server = APIServer()
    sched = Scheduler(server, KubeSchedulerConfiguration())
    groups = autoscaler_candidate_shapes()
    auto = ClusterAutoscaler(
        server,
        sched,
        NodeGroupCatalog(groups),
        period_s=period_s,
        max_provision_per_cycle=max_provision_per_cycle,
        scale_down_enabled=False,
    )
    for i in range(n_pods):
        server.create(
            "pods",
            Pod(
                metadata=ObjectMeta(name=f"asc-{i}"),
                spec=PodSpec(
                    containers=[Container(requests={"cpu": pod_cpu})]
                ),
            ),
        )
    sched.start()
    t0 = time.monotonic()
    auto.start()
    try:
        deadline = time.monotonic() + timeout_s
        scheduled = 0
        while time.monotonic() < deadline:
            scheduled = _count_scheduled(server)
            if scheduled >= n_pods:
                break
            time.sleep(0.05)
        elapsed = time.monotonic() - t0
    finally:
        auto.stop()
        sched.stop()
    nodes, _ = server.list("nodes")
    by_group = {
        g.name: int(
            metrics.counter(
                "autoscaler_nodes_provisioned_total", {"group": g.name}
            )
        )
        for g in groups
    }
    sim_h = metrics.histogram("autoscaler_simulation_duration_seconds")
    passes = sum(
        v
        for _n, _l, v in metrics.snapshot_counters(
            "autoscaler_simulation_passes_total"
        )
    )
    p50, p99 = sim_h.quantiles((0.5, 0.99)) if sim_h else (0.0, 0.0)
    return AutoscalerBenchResult(
        num_pods=n_pods,
        num_shapes=len(groups),
        scheduled=scheduled,
        time_to_all_bound_s=elapsed,
        nodes_provisioned=len(nodes),
        nodes_by_group=by_group,
        simulation_passes=int(passes),
        simulation_p50_ms=p50 * 1e3,
        simulation_p99_ms=p99 * 1e3,
    )


@dataclass
class ReadpathBenchResult:
    """The `readpath` bench workload: N hollow informers (watch-cache
    fan-out clients) attached to one apiserver while an event storm
    flows. Delivery latency is enqueue→drain on a hot-sampled subset;
    fan-out throughput counts every queued client delivery."""

    n_informers: int
    n_events: int
    duration_s: float
    fanout_deliveries: int
    fanout_deliveries_per_s: float
    delivery_p50_ms: float
    delivery_p99_ms: float
    store_watchers: int  # the scale contract: must be 1
    replays: int
    slow_evicted: int


def run_readpath_benchmark(
    n_informers: int = 10000,
    n_events: int = 200,
    n_sampled: int = 64,
    drainers: int = 4,
) -> ReadpathBenchResult:
    """10k hollow informers on ONE store watch: measure p99 watch-delivery
    latency and fan-out throughput through the watch cache. Informers are
    hollow the same way kubemark nodes are — real fan-out queues, a
    shared drain pool instead of 10k threads."""
    import threading

    from ..api.objects import Container, ObjectMeta, PodSpec
    from ..apiserver.cacher import Cacher
    from ..runtime.watch import BOOKMARK

    server = APIServer()
    cacher = Cacher(server, bookmark_period_s=1.0)
    kc = cacher.cache_for("pods")
    r0 = metrics.counter("watch_cache_replays_total", {"kind": "pods"})
    s0 = metrics.counter(
        "watch_cache_slow_watchers_evicted_total", {"kind": "pods"}
    )
    watchers = [cacher.watch("pods") for _ in range(n_informers)]
    sampled = watchers[:n_sampled]
    latencies: List[float] = []
    lat_lock = threading.Lock()
    stop = threading.Event()

    def drain_loop(ws):
        while not stop.is_set():
            idle = True
            for w in ws:
                ev = w.get(timeout=0)
                while ev is not None:
                    idle = False
                    if ev.type != BOOKMARK and ev.ts:
                        with lat_lock:
                            latencies.append(time.monotonic() - ev.ts)
                    ev = w.get(timeout=0)
            if idle:
                time.sleep(0.001)

    chunk = max(1, len(sampled) // drainers)
    threads = [
        threading.Thread(
            target=drain_loop, args=(sampled[i : i + chunk],), daemon=True
        )
        for i in range(0, len(sampled), chunk)
    ]
    for t in threads:
        t.start()
    t0 = time.monotonic()
    for i in range(n_events):
        server.create(
            "pods",
            Pod(
                metadata=ObjectMeta(name=f"rp-{i}"),
                spec=PodSpec(containers=[Container(requests={"cpu": "1m"})]),
            ),
        )
    # dispatch is synchronous into every client queue: once the cache rv
    # catches the store rv, every delivery is enqueued
    deadline = time.monotonic() + 60.0
    while kc.current_rv < server.resource_version and time.monotonic() < deadline:
        time.sleep(0.001)
    duration = time.monotonic() - t0
    # let the sampled drainers finish their queues for honest percentiles
    sdeadline = time.monotonic() + 10.0
    while time.monotonic() < sdeadline:
        with lat_lock:
            if len(latencies) >= n_events * len(sampled):
                break
        time.sleep(0.005)
    stop.set()
    for t in threads:
        t.join(timeout=2.0)
    store_watchers = server.watcher_count("pods")
    with lat_lock:
        lat = sorted(latencies)
    p50 = lat[int(0.5 * len(lat))] * 1e3 if lat else 0.0
    p99 = lat[min(int(0.99 * len(lat)), len(lat) - 1)] * 1e3 if lat else 0.0
    deliveries = n_events * n_informers
    for w in watchers:
        w.stop()
    cacher.stop()
    return ReadpathBenchResult(
        n_informers=n_informers,
        n_events=n_events,
        duration_s=duration,
        fanout_deliveries=deliveries,
        fanout_deliveries_per_s=deliveries / duration if duration else 0.0,
        delivery_p50_ms=p50,
        delivery_p99_ms=p99,
        store_watchers=store_watchers,
        replays=int(
            metrics.counter("watch_cache_replays_total", {"kind": "pods"}) - r0
        ),
        slow_evicted=int(
            metrics.counter(
                "watch_cache_slow_watchers_evicted_total", {"kind": "pods"}
            )
            - s0
        ),
    )


def _count_scheduled(server: APIServer) -> int:
    return server.count("pods", lambda p: bool(p.spec.node_name))


def _wait_all_scheduled(server: APIServer, count: int, timeout_s: float) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if _count_scheduled(server) >= count:
            return
        time.sleep(0.05)
    raise TimeoutError("init pods did not all schedule")


@dataclass
class ServingBenchResult:
    """The `serving` bench workload: a MULTI-PROCESS frontend fleet
    behind the balancer — bind RTT through the pooled REST chain
    (client -> balancer -> frontend -> primary) and watch fan-out
    across hollow watchers attached to the frontends' own caches."""

    n_frontends: int
    n_watchers: int
    n_events: int
    n_binds: int
    duration_s: float
    bind_p50_ms: float
    bind_p99_ms: float
    delivery_p99_ms: float
    fanout_deliveries: int
    fanout_deliveries_per_s: float
    conn_opened: int
    conn_reused: int


def run_serving_benchmark(
    n_watchers: int = 100_000,
    n_frontends: int = 2,
    n_pods: int = 100,
    timeout_s: float = 240.0,
) -> ServingBenchResult:
    """Serving-tier fleet benchmark, real OS processes end to end.

    A primary apiserver and n_frontends stateless frontends are spawned
    as child processes (testing/netchaos_procs.py roles); each frontend
    attaches n_watchers/n_frontends hollow watchers to its OWN watch
    cache (the kubemark discipline: real fan-out queues, a sampled drain
    pool). The bench then drives n_pods creates + n_pods binds through
    an in-process LoadBalancerProxy on ONE pooled RESTClient, timing
    every bind POST round trip, and reads each frontend's delivery
    stats back over its /bench-stats endpoint."""
    import json as _json
    import os
    import subprocess
    import sys
    import tempfile
    import threading
    import urllib.request

    from ..api.objects import Binding, Container, Node, NodeSpec, NodeStatus, ObjectMeta, PodSpec
    from ..apiserver.client import (
        COUNTER_CONN_OPENED,
        COUNTER_CONN_REUSED,
        RESTClient,
    )
    from ..testing.netchaos import LoadBalancerProxy

    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    procs = []
    tmp_paths: List[str] = []  # stderr logs + ledger, removed in finally

    def spawn(args, tag):
        err = tempfile.NamedTemporaryFile(
            "w+", prefix=f"serving-bench-{tag}-", suffix=".log", delete=False
        )
        tmp_paths.append(err.name)
        p = subprocess.Popen(
            [sys.executable, "-m", "kubernetes_tpu.testing.netchaos_procs",
             *args],
            cwd=repo, stdout=subprocess.PIPE, stderr=err, text=True, env=env,
        )
        err.close()  # the child holds its own duped fd
        procs.append(p)
        lines: List[str] = []

        def read():
            for line in p.stdout:
                lines.append(line.strip())

        threading.Thread(target=read, daemon=True).start()
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            ready = [l for l in lines if l.startswith("READY")]
            if ready:
                return ready[0].split()
            if p.poll() is not None:
                raise RuntimeError(f"{tag} exited rc={p.returncode}")
            time.sleep(0.05)
        raise TimeoutError(f"{tag} never became ready")

    per_frontend = max(1, n_watchers // n_frontends)
    lb = None
    client = None
    try:
        with tempfile.NamedTemporaryFile(suffix=".jsonl", delete=False) as lf:
            ledger = lf.name
        tmp_paths.append(ledger)
        ready = spawn(["apiserver", "--port", "0", "--ledger", ledger],
                      "primary")
        primary_port = int(ready[2])
        primary_url = f"http://127.0.0.1:{primary_port}"
        stats_ports = []
        backends = []
        for i in range(n_frontends):
            r = spawn(
                ["frontend", "--primary", primary_url,
                 "--hollow-watchers", str(per_frontend)],
                f"frontend-{i}",
            )
            backends.append(("127.0.0.1", int(r[2])))
            stats_ports.append(int(r[3]))
        lb = LoadBalancerProxy(backends).start()
        client = RESTClient(f"http://127.0.0.1:{lb.port}", timeout=30.0)
        client.create(
            "nodes",
            Node(
                metadata=ObjectMeta(name="bench-n1", namespace=""),
                spec=NodeSpec(),
                status=NodeStatus(
                    allocatable={"cpu": "512", "memory": "2Ti", "pods": 100000}
                ),
            ),
        )
        opened0 = metrics.counter(COUNTER_CONN_OPENED)
        reused0 = metrics.counter(COUNTER_CONN_REUSED)
        t0 = time.monotonic()
        bind_lat: List[float] = []
        for i in range(n_pods):
            client.create(
                "pods",
                Pod(
                    metadata=ObjectMeta(name=f"sv-{i}", namespace="default"),
                    spec=PodSpec(
                        containers=[Container(requests={"cpu": "1m"})]
                    ),
                ),
            )
        for i in range(n_pods):
            b = Binding(
                pod_name=f"sv-{i}", pod_namespace="default",
                target_node="bench-n1",
            )
            bt0 = time.monotonic()
            errs = client.bind_pods([b])
            if errs[0] is None:
                bind_lat.append(time.monotonic() - bt0)
        n_events = 2 * n_pods  # each pod: one ADDED + one bind MODIFIED

        def stats(port):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/", timeout=10
            ) as r:
                return _json.loads(r.read())

        # the storm ends when every frontend's cache consumed every event
        deadline = time.monotonic() + timeout_s
        snaps = []
        while time.monotonic() < deadline:
            snaps = [stats(p) for p in stats_ports]
            if all(s["cache_events"] >= n_events for s in snaps):
                break
            time.sleep(0.1)
        duration = time.monotonic() - t0
        # drain window: sampled watchers finish their queues for honest
        # percentiles
        sample_target = sum(s["sampled"] for s in snaps) * n_events
        drain_deadline = time.monotonic() + 20.0
        while time.monotonic() < drain_deadline:
            snaps = [stats(p) for p in stats_ports]
            if sum(s["drained"] for s in snaps) >= sample_target:
                break
            time.sleep(0.1)
        deliveries = sum(
            int(s["cache_events"]) * s["watchers"] for s in snaps
        )
        blat = sorted(bind_lat)
        return ServingBenchResult(
            n_frontends=n_frontends,
            n_watchers=sum(s["watchers"] for s in snaps),
            n_events=n_events,
            n_binds=len(bind_lat),
            duration_s=duration,
            bind_p50_ms=(blat[len(blat) // 2] * 1e3) if blat else 0.0,
            bind_p99_ms=(
                blat[min(int(0.99 * len(blat)), len(blat) - 1)] * 1e3
                if blat
                else 0.0
            ),
            delivery_p99_ms=max(
                (s["delivery_p99_ms"] for s in snaps), default=0.0
            ),
            fanout_deliveries=deliveries,
            fanout_deliveries_per_s=(
                deliveries / duration if duration else 0.0
            ),
            conn_opened=int(metrics.counter(COUNTER_CONN_OPENED) - opened0),
            conn_reused=int(metrics.counter(COUNTER_CONN_REUSED) - reused0),
        )
    finally:
        if client is not None:
            client.close()
        if lb is not None:
            lb.stop()
        for p in procs:
            try:
                p.kill()
                p.wait(timeout=10)
            except Exception:
                pass
        for path in tmp_paths:
            try:
                os.unlink(path)
            except OSError:
                pass


@dataclass
class RelayServingBenchResult:
    """The relay `serving` bench workload (ISSUE 20): a million-watcher
    TLS fan-out through the shared-memory watch relay. A primary plus
    n_frontends frontend processes run as real OS processes; each
    frontend publishes frames once into its ring and relay_workers
    SO_REUSEPORT worker processes carry the hollow watcher load, with a
    handful of REAL TLS watch clients sampled through a balancer for
    honest end-to-end latency percentiles. CPU seconds are per process
    so the flatness claim (frontend pays per FRAME, not per client) is
    checkable across watcher scales."""

    n_frontends: int
    n_relay_workers: int  # total across frontends
    n_watchers: int  # hollow + real, as registered by the workers
    n_real_clients: int
    n_events: int
    n_binds: int
    tls: bool
    duration_s: float
    bind_p50_ms: float
    bind_p99_ms: float
    watch_p50_ms: float  # bind POST -> real TLS client sees the MODIFIED
    watch_p99_ms: float
    fanout_deliveries: int  # conservative: events x watchers (no bookmarks)
    fanout_deliveries_per_s: float
    deliveries_measured: int  # worker-counter delta (includes bookmarks)
    evicted_slow: int
    shed: int
    frontend_cpu_s: List[float]  # per frontend process, storm window only
    worker_cpu_s: List[float]  # per relay worker process, storm window


def _proc_cpu_s(pid: int) -> float:
    """utime+stime of one process from /proc (Linux), seconds."""
    import os

    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        hz = os.sysconf("SC_CLK_TCK")
        return (int(fields[11]) + int(fields[12])) / hz
    except (OSError, IndexError, ValueError):
        return 0.0


def run_relay_serving_benchmark(
    n_watchers: int = 1_000_000,
    n_frontends: int = 2,
    relay_workers: int = 2,
    n_real_clients: int = 32,
    n_pods: int = 100,
    tls: bool = True,
    timeout_s: float = 600.0,
) -> RelayServingBenchResult:
    """Million-client serving through the watch relay, TLS end to end.

    Topology: primary apiserver -> n_frontends stateless frontends (each
    with --relay-workers fan-out processes over its shared-memory ring)
    -> hollow watchers in the workers plus n_real_clients genuine TLS
    watch streams through a LoadBalancerProxy over the relay ports.
    The bench drives n_pods creates + binds through the frontend REST
    hop (also TLS), then waits until every worker's dispatch has fanned
    the last bound rv out to all its clients. Deliveries are counted
    frames x subscribers — the economics the relay exists for."""
    import json as _json
    import math
    import os
    import subprocess
    import sys
    import tempfile
    import threading
    import urllib.request

    from ..api.objects import Binding, Container, Node, NodeSpec, NodeStatus, ObjectMeta, PodSpec
    from ..apiserver.client import RESTClient
    from ..runtime.watch import BOOKMARK
    from ..testing.netchaos import LoadBalancerProxy

    cert = key = ""
    if tls:
        from ..testing.tlsutil import ensure_self_signed

        cert, key = ensure_self_signed()
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    procs = []
    tmp_paths: List[str] = []

    def spawn(args, tag):
        err = tempfile.NamedTemporaryFile(
            "w+", prefix=f"relay-bench-{tag}-", suffix=".log", delete=False
        )
        tmp_paths.append(err.name)
        p = subprocess.Popen(
            [sys.executable, "-m", "kubernetes_tpu.testing.netchaos_procs",
             *args],
            cwd=repo, stdout=subprocess.PIPE, stderr=err, text=True, env=env,
        )
        err.close()
        procs.append(p)
        lines: List[str] = []

        def read():
            for line in p.stdout:
                lines.append(line.strip())

        threading.Thread(target=read, daemon=True).start()
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            ready = [l for l in lines if l.startswith("READY")]
            if ready:
                return p, ready[0].split()
            if p.poll() is not None:
                raise RuntimeError(f"{tag} exited rc={p.returncode}")
            time.sleep(0.05)
        raise TimeoutError(f"{tag} never became ready")

    # round the hollow split UP so worker-level floor division never
    # undershoots the requested watcher count
    target_hollow = max(0, n_watchers - n_real_clients)
    per_frontend = math.ceil(target_hollow / n_frontends)
    per_frontend = math.ceil(per_frontend / max(relay_workers, 1)) * max(
        relay_workers, 1
    )
    scheme = "https" if tls else "http"
    lb = rlb = None
    client = None
    real_clients: List = []
    real_watchers: List = []
    try:
        with tempfile.NamedTemporaryFile(suffix=".jsonl", delete=False) as lf:
            ledger = lf.name
        tmp_paths.append(ledger)
        _p, ready = spawn(
            ["apiserver", "--port", "0", "--ledger", ledger], "primary"
        )
        primary_url = f"http://127.0.0.1:{int(ready[2])}"
        fe_pids: List[int] = []
        fe_ports: List[int] = []
        stats_ports: List[int] = []
        relay_ports: List[int] = []
        for i in range(n_frontends):
            fargs = [
                "frontend", "--primary", primary_url,
                "--relay-workers", str(relay_workers),
                "--relay-hollow", str(per_frontend),
            ]
            if tls:
                fargs += ["--tls-cert", cert, "--tls-key", key]
            p, r = spawn(fargs, f"frontend-{i}")
            fe_pids.append(p.pid)
            fe_ports.append(int(r[2]))
            stats_ports.append(int(r[3]))
            relay_ports.append(int(r[4]))
        lb = LoadBalancerProxy([("127.0.0.1", p) for p in fe_ports]).start()
        rlb = LoadBalancerProxy(
            [("127.0.0.1", p) for p in relay_ports]
        ).start()
        client = RESTClient(f"{scheme}://127.0.0.1:{lb.port}", timeout=30.0)
        client.create(
            "nodes",
            Node(
                metadata=ObjectMeta(name="bench-n1", namespace=""),
                spec=NodeSpec(),
                status=NodeStatus(
                    allocatable={"cpu": "512", "memory": "2Ti", "pods": 100000}
                ),
            ),
        )

        def stats(port):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/", timeout=10
            ) as r:
                return _json.loads(r.read())

        # real TLS watch clients through the relay balancer: each one is
        # a genuine https stream terminated by a relay worker; they time
        # bind POST -> observed MODIFIED for end-to-end percentiles
        bind_t0: dict = {}
        wlat: List[float] = []
        wlock = threading.Lock()

        def drain(w, remaining):
            while remaining[0] > 0:
                ev = w.get(timeout=5.0)
                if ev is None:
                    if w.stopped:
                        return
                    continue
                if ev.type == BOOKMARK:
                    continue
                name = ev.object.metadata.name
                if getattr(ev.object.spec, "node_name", "") and name in bind_t0:
                    with wlock:
                        wlat.append(time.monotonic() - bind_t0[name])
                    remaining[0] -= 1

        for _ in range(n_real_clients):
            c = RESTClient(f"{scheme}://127.0.0.1:{rlb.port}", timeout=30.0)
            real_clients.append(c)
            real_watchers.append(c.watch("pods", 0))
        remainders = [[n_pods] for _ in real_watchers]
        for w, rem in zip(real_watchers, remainders):
            threading.Thread(target=drain, args=(w, rem), daemon=True).start()

        # pre-storm baselines: idle bookmark heartbeats already tick the
        # hollow counters, and frontends burned CPU warming up
        base = [stats(p) for p in stats_ports]
        base_delivered = sum(s["delivered"] for s in base)
        base_evicted = sum(s["evicted_slow"] for s in base)
        base_shed = sum(s["shed"] for s in base)
        base_fe_cpu = [_proc_cpu_s(pid) for pid in fe_pids]
        base_w_cpu = {
            w["pid"]: w["cpu_s"] for s in base for w in s["per_worker"]
        }
        actual_hollow = sum(s["hollow"] for s in base)

        t0 = time.monotonic()
        bind_lat: List[float] = []
        for i in range(n_pods):
            client.create(
                "pods",
                Pod(
                    metadata=ObjectMeta(name=f"rsv-{i}", namespace="default"),
                    spec=PodSpec(
                        containers=[Container(requests={"cpu": "1m"})]
                    ),
                ),
            )
        for i in range(n_pods):
            b = Binding(
                pod_name=f"rsv-{i}", pod_namespace="default",
                target_node="bench-n1",
            )
            bind_t0[f"rsv-{i}"] = time.monotonic()
            errs = client.bind_pods([b])
            if errs[0] is None:
                bind_lat.append(time.monotonic() - bind_t0[f"rsv-{i}"])
        n_events = 2 * n_pods
        final_rv = client.get(
            "pods", "default", f"rsv-{n_pods - 1}"
        ).metadata.resource_version

        # storm over when every worker's dispatch has fanned the final
        # bound rv out (hollow counters update in the same dispatch pass)
        deadline = time.monotonic() + timeout_s
        snaps = base
        while time.monotonic() < deadline:
            snaps = [stats(p) for p in stats_ports]
            if all(
                w["kinds"].get("pods", {}).get("last_rv", 0) >= final_rv
                for s in snaps
                for w in s["per_worker"]
            ):
                break
            time.sleep(0.2)
        duration = time.monotonic() - t0
        fe_cpu = [
            _proc_cpu_s(pid) - b0 for pid, b0 in zip(fe_pids, base_fe_cpu)
        ]
        w_cpu = [
            w["cpu_s"] - base_w_cpu.get(w["pid"], 0.0)
            for s in snaps
            for w in s["per_worker"]
        ]
        # honest percentile drain: give the sampled real streams a
        # moment to observe the tail of the storm
        drain_deadline = time.monotonic() + 30.0
        while time.monotonic() < drain_deadline:
            if all(rem[0] <= 0 for rem in remainders):
                break
            time.sleep(0.1)
        n_watchers_actual = actual_hollow + n_real_clients
        deliveries = n_events * n_watchers_actual
        measured = sum(s["delivered"] for s in snaps) - base_delivered
        blat = sorted(bind_lat)
        wl = sorted(wlat)
        return RelayServingBenchResult(
            n_frontends=n_frontends,
            n_relay_workers=n_frontends * relay_workers,
            n_watchers=n_watchers_actual,
            n_real_clients=n_real_clients,
            n_events=n_events,
            n_binds=len(bind_lat),
            tls=tls,
            duration_s=duration,
            bind_p50_ms=(blat[len(blat) // 2] * 1e3) if blat else 0.0,
            bind_p99_ms=(
                blat[min(int(0.99 * len(blat)), len(blat) - 1)] * 1e3
                if blat
                else 0.0
            ),
            watch_p50_ms=(wl[len(wl) // 2] * 1e3) if wl else 0.0,
            watch_p99_ms=(
                wl[min(int(0.99 * len(wl)), len(wl) - 1)] * 1e3
                if wl
                else 0.0
            ),
            fanout_deliveries=deliveries,
            fanout_deliveries_per_s=(
                deliveries / duration if duration else 0.0
            ),
            deliveries_measured=int(measured),
            evicted_slow=int(
                sum(s["evicted_slow"] for s in snaps) - base_evicted
            ),
            shed=int(sum(s["shed"] for s in snaps) - base_shed),
            frontend_cpu_s=[round(c, 3) for c in fe_cpu],
            worker_cpu_s=[round(c, 3) for c in w_cpu],
        )
    finally:
        for w in real_watchers:
            w.stop()
        for c in real_clients:
            try:
                c.close()
            except Exception:
                pass
        if client is not None:
            client.close()
        if lb is not None:
            lb.stop()
        if rlb is not None:
            rlb.stop()
        for p in procs:
            try:
                p.kill()
                p.wait(timeout=10)
            except Exception:
                pass
        for path in tmp_paths:
            try:
                os.unlink(path)
            except OSError:
                pass


@dataclass
class PreemptionBenchResult:
    """The `preemption` bench workload: a high-priority burst over a FULL
    cluster — every placement requires displacing lower-priority victims.
    The acceptance shape (ISSUE 15): victims resolve through the batched
    vectorized pass (select_batches stays per-wave, not per-pod; zero
    full host walks on the happy path)."""

    num_nodes: int
    burst_pods: int
    scheduled: int
    time_to_all_bound_s: float
    victims_evicted: int
    select_batches: int  # batched preempt_select launches (per-wave)
    vector_attempts: int  # preemption attempts served by the batched pass
    host_walk_fallbacks: int  # full per-pod host walks (happy path: 0)
    guard_trips: int
    oracle_divergences: int
    select_p50_ms: float
    select_p99_ms: float


def run_preemption_benchmark(
    n_nodes: int = 1000,
    burst: int = 1000,
    timeout_s: float = 600.0,
) -> PreemptionBenchResult:
    """1k-pending high-priority burst over a full 1k-node cluster: every
    node carries 4x 1-cpu priority-0 pods (pre-bound, store-acked), the
    burst pods need 2 cpu each at priority 100 — nothing places without
    victim selection. Reports time-to-all-bound plus the engine's
    batched-pass accounting."""
    from ..api import objects as v1

    metrics.reset()
    server = APIServer()
    sched = Scheduler(server, KubeSchedulerConfiguration())
    for i in range(n_nodes):
        server.create(
            "nodes",
            v1.Node(
                metadata=v1.ObjectMeta(name=f"pn{i}", namespace=""),
                status=v1.NodeStatus(
                    allocatable={"cpu": "4", "memory": "32Gi", "pods": 110}
                ),
            ),
        )
    # the resident victims arrive PRE-BOUND (store-acked like the
    # throughput harness): the bench measures displacement, not the
    # initial fill
    for i in range(n_nodes):
        for k in range(4):
            p = Pod(
                metadata=v1.ObjectMeta(name=f"low-{i}-{k}"),
                spec=v1.PodSpec(
                    containers=[v1.Container(requests={"cpu": "1"})],
                    priority=0,
                    node_name=f"pn{i}",
                ),
            )
            server.create("pods", p)
    sched.start()
    try:
        for i in range(burst):
            server.create(
                "pods",
                Pod(
                    metadata=v1.ObjectMeta(name=f"hi-{i}"),
                    spec=v1.PodSpec(
                        containers=[v1.Container(requests={"cpu": "2"})],
                        priority=100,
                    ),
                ),
            )
        t0 = time.monotonic()
        deadline = t0 + timeout_s
        bound = 0
        while time.monotonic() < deadline:
            pods, _ = server.list("pods")
            bound = sum(
                1
                for p in pods
                if p.metadata.name.startswith("hi-") and p.spec.node_name
            )
            if bound >= burst:
                break
            time.sleep(0.25)
        elapsed = time.monotonic() - t0
    finally:
        sched.stop()

    def _count(name, label_filter=None):
        return int(
            sum(
                v
                for _n, labels, v in metrics.snapshot_counters(name)
                if label_filter is None or label_filter(labels)
            )
        )

    sel_h = metrics.histogram("scheduler_preemption_select_duration_seconds")
    p50, p99 = sel_h.quantiles((0.5, 0.99)) if sel_h else (0.0, 0.0)
    return PreemptionBenchResult(
        num_nodes=n_nodes,
        burst_pods=burst,
        scheduled=bound,
        time_to_all_bound_s=elapsed,
        victims_evicted=_count("preemption_victims_total"),
        select_batches=_count("scheduler_preemption_batches_total"),
        vector_attempts=_count("scheduler_preemption_vector_hits_total"),
        # only the reasons that actually run a full host walk count —
        # batch_saturated is a skip (no walk), retried next wave
        host_walk_fallbacks=_count(
            "scheduler_preemption_fallback_total",
            lambda labels: labels.get("reason")
            in ("oracle_reject", "kernel_error", "group_overflow"),
        ),
        guard_trips=_count("scheduler_preemption_guard_trips_total"),
        oracle_divergences=_count(
            "scheduler_preemption_oracle_divergence_total"
        ),
        select_p50_ms=p50 * 1e3,
        select_p99_ms=p99 * 1e3,
    )


@dataclass
class HeteroBenchResult:
    """The `hetero` bench workload: one pending burst autoscaled twice —
    cheapest-feasible-shape packing vs cost-blind MostAllocated — on the
    mixed-cost catalog. Equal feasibility (same pods bound), strictly
    cheaper fleet is the acceptance bar."""

    num_pods: int
    num_shapes: int
    cost_aware_scheduled: int
    cost_aware_nodes: Dict[str, int]
    cost_aware_fleet_per_hour: float
    cost_aware_time_s: float
    blind_scheduled: int
    blind_nodes: Dict[str, int]
    blind_fleet_per_hour: float
    blind_time_s: float

    @property
    def strictly_cheaper(self) -> bool:
        return (
            self.cost_aware_scheduled >= self.blind_scheduled
            and self.cost_aware_fleet_per_hour < self.blind_fleet_per_hour
        )


def run_hetero_benchmark(
    n_pods: int = 300, timeout_s: float = 300.0, period_s: float = 0.5
) -> HeteroBenchResult:
    """Run the same pending burst through the autoscaler twice on the
    mixed-cost catalog (perf/workloads.hetero_candidate_shapes):
    cost_aware=True (cheapest-feasible-shape) vs cost_aware=False (pure
    MostAllocated pack, the pre-ISSUE-15 behavior)."""
    from ..api import objects as v1
    from ..autoscaler import ClusterAutoscaler, NodeGroupCatalog
    from .workloads import hetero_candidate_shapes

    def one_arm(cost_aware: bool):
        metrics.reset()
        server = APIServer()
        sched = Scheduler(server, KubeSchedulerConfiguration())
        groups = hetero_candidate_shapes()
        auto = ClusterAutoscaler(
            server,
            sched,
            NodeGroupCatalog(groups),
            period_s=period_s,
            scale_down_enabled=False,
            cost_aware=cost_aware,
        )
        for i in range(n_pods):
            server.create(
                "pods",
                Pod(
                    metadata=v1.ObjectMeta(name=f"h-{i}"),
                    spec=v1.PodSpec(
                        containers=[v1.Container(requests={"cpu": "1"})]
                    ),
                ),
            )
        sched.start()
        t0 = time.monotonic()
        auto.start()
        try:
            deadline = time.monotonic() + timeout_s
            scheduled = 0
            while time.monotonic() < deadline:
                scheduled = _count_scheduled(server)
                if scheduled >= n_pods:
                    break
                time.sleep(0.1)
            elapsed = time.monotonic() - t0
        finally:
            auto.stop()
            sched.stop()
        nodes, _ = server.list("nodes")
        catalog = NodeGroupCatalog(groups)
        by_group: Dict[str, int] = {}
        fleet = 0.0
        for n in nodes:
            g = catalog.group_of_node(n)
            if g is not None:
                by_group[g.name] = by_group.get(g.name, 0) + 1
                fleet += g.cost_per_hour()
        return scheduled, by_group, round(fleet, 3), elapsed

    aware = one_arm(True)
    blind = one_arm(False)
    return HeteroBenchResult(
        num_pods=n_pods,
        num_shapes=len(hetero_candidate_shapes()),
        cost_aware_scheduled=aware[0],
        cost_aware_nodes=aware[1],
        cost_aware_fleet_per_hour=aware[2],
        cost_aware_time_s=round(aware[3], 3),
        blind_scheduled=blind[0],
        blind_nodes=blind[1],
        blind_fleet_per_hour=blind[2],
        blind_time_s=round(blind[3], 3),
    )


@dataclass
class TunerBenchResult:
    """The `tuner` bench workload: the policy gym driven through a
    workload-mix flip on a mixed-cost fleet. Pre-flip waves saturate
    every node (cost-undifferentiated: no arm can beat the incumbent, so
    NOTHING must promote); the flip switches to small bursts where a
    cost-aware vector provably wins — time from the flip to the
    promotion landing is the re-convergence number. The same pre-flip
    rounds run with the tuner off vs on give the steady-state overhead."""

    num_nodes: int
    pre_flip_rounds: int
    pre_flip_promotions: int
    baseline_pods_per_s: float
    tuner_on_pods_per_s: float
    overhead_pct: float
    converged: bool
    time_to_converge_s: float
    promoted_policy: str
    promoted_cost_weight: float
    promotions: int
    waves_recorded: int
    gym_passes: int
    gym_pass_p50_ms: float
    gym_pass_p99_ms: float


def run_tuner_benchmark(
    n_nodes: int = 8, rounds: int = 4, timeout_s: float = 120.0
) -> TunerBenchResult:
    """Drive the self-tuning scheduler (kubernetes_tpu/tuner) end to end.

    Topology: n_nodes/2 cheap + n_nodes/2 spendy nodes (9x cost spread),
    serial non-donating kernel path (the replayable path the gym's
    differential corpus certifies). Three measured segments:

      1. baseline arm — `rounds` full-width bursts (one 7-CPU pod per
         node), tuner OFF: scheduling throughput without the gym;
      2. tuner-on arm — the SAME bursts with the gym replaying every
         recorded wave in the background: throughput delta = steady-state
         overhead. Full-width waves use every node in every arm, so all
         candidate utilities tie and the gate must hold `default`;
      3. the flip — small 2-pod 500m bursts: a cost-aware arm now beats
         the incumbent on the $-per-hour term, and the wall clock from
         the first flipped burst to `set_score_policy` landing is the
         re-convergence time.
    """
    import numpy as np

    from ..api import objects as v1
    from ..ops.encoding import LABEL_COST_PER_HOUR
    from ..ops.lattice import SC_COST, WEIGHT_PROFILES
    from ..tuner.controller import PolicyTuner
    from ..tuner.policy import (
        COUNTER_GYM_PASSES,
        COUNTER_POLICY_PROMOTIONS,
        COUNTER_WAVES_RECORDED,
        HIST_GYM_PASS_SECONDS,
    )

    def node(name: str, cost: str) -> v1.Node:
        return v1.Node(
            metadata=v1.ObjectMeta(
                name=name, namespace="", labels={LABEL_COST_PER_HOUR: cost}
            ),
            status=v1.NodeStatus(
                allocatable={"cpu": "8", "memory": "32Gi", "pods": 110}
            ),
        )

    def topology():
        server = APIServer()
        for i in range(n_nodes // 2):
            server.create("nodes", node(f"tb-cheap-{i}", "1.0"))
        for i in range(n_nodes - n_nodes // 2):
            server.create("nodes", node(f"tb-spendy-{i}", "9.0"))
        cfg = KubeSchedulerConfiguration(
            use_wave=False,
            small_batch_host_max=0,
            pod_initial_backoff_seconds=0.2,
            pod_max_backoff_seconds=2.0,
        )
        return server, Scheduler(server, cfg)

    def one_burst(server, tag: str, size: int, cpu: str) -> None:
        names = [f"{tag}-{i}" for i in range(size)]
        for nm in names:
            server.create(
                "pods",
                Pod(
                    metadata=v1.ObjectMeta(name=nm),
                    spec=v1.PodSpec(
                        containers=[v1.Container(requests={"cpu": cpu})]
                    ),
                ),
            )
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if _count_scheduled(server) >= size:
                break
            time.sleep(0.02)
        for nm in names:
            server.delete("pods", "default", nm)
        time.sleep(0.2)  # let the informer restore capacity

    def full_width_rounds(server, tag: str) -> float:
        # untimed warmup burst: the first burst of an arm absorbs this
        # process's kernel compile at the 8-pod shape — without it the
        # first measured arm eats the compile storm and the off-vs-on
        # overhead comparison measures XLA, not the gym
        one_burst(server, f"{tag}-warm", n_nodes, "7")
        t0 = time.monotonic()
        for r in range(rounds):
            one_burst(server, f"{tag}-{r}", n_nodes, "7")
        elapsed = time.monotonic() - t0
        return (rounds * n_nodes) / max(elapsed, 1e-9)

    metrics.reset()
    profiles0 = set(WEIGHT_PROFILES)

    # segment 1: tuner OFF
    server, sched = topology()
    sched.start()
    try:
        baseline = full_width_rounds(server, "off")
    finally:
        sched.stop()

    # segments 2+3: tuner ON — same bursts, then the flip
    server, sched = topology()
    tuner = PolicyTuner(
        sched,
        server,
        period_s=0.2,
        shadow_windows=2,
        noise_floor=0.005,
        seed=7,
    )
    sched.start()
    tuner.start()
    try:
        on_rate = full_width_rounds(server, "on")
        pre_flip_promotions = int(metrics.counter(COUNTER_POLICY_PROMOTIONS))

        flip_t0 = time.monotonic()
        converged_at = None
        burst = 0
        while time.monotonic() - flip_t0 < timeout_s:
            one_burst(server, f"flip-{burst}", 2, "500m")
            burst += 1
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline:
                if (
                    metrics.counter(COUNTER_POLICY_PROMOTIONS) > pre_flip_promotions
                    and float(np.asarray(sched._weights)[SC_COST]) > 0.0
                ):
                    converged_at = time.monotonic()
                    break
                time.sleep(0.05)
            if converged_at is not None:
                break
        promoted = sched._score_policy_name
        cost_w = float(np.asarray(sched._weights)[SC_COST])
        promotions = int(metrics.counter(COUNTER_POLICY_PROMOTIONS))
    finally:
        tuner.stop()
        sched.stop()
        for name in set(WEIGHT_PROFILES) - profiles0:
            WEIGHT_PROFILES.pop(name, None)

    h = metrics.histogram(HIST_GYM_PASS_SECONDS)
    p50, p99 = (h.quantiles([0.5, 0.99]) if h is not None else (0.0, 0.0))
    waves = int(
        metrics.counter(COUNTER_WAVES_RECORDED, {"path": "serial"})
        + metrics.counter(COUNTER_WAVES_RECORDED, {"path": "wave"})
    )
    return TunerBenchResult(
        num_nodes=n_nodes,
        pre_flip_rounds=rounds,
        pre_flip_promotions=pre_flip_promotions,
        baseline_pods_per_s=round(baseline, 1),
        tuner_on_pods_per_s=round(on_rate, 1),
        overhead_pct=round((baseline - on_rate) / max(baseline, 1e-9) * 100, 2),
        converged=converged_at is not None,
        time_to_converge_s=round(
            (converged_at - flip_t0) if converged_at is not None else -1.0, 3
        ),
        promoted_policy=promoted,
        promoted_cost_weight=round(cost_w, 4),
        promotions=promotions,
        waves_recorded=waves,
        gym_passes=int(metrics.counter(COUNTER_GYM_PASSES)),
        gym_pass_p50_ms=round(p50 * 1e3, 2),
        gym_pass_p99_ms=round(p99 * 1e3, 2),
    )


@dataclass
class DurabilityBenchResult:
    """The `durability` bench workload: raw WAL economics (ISSUE 18).

    Group-committed append throughput with the fsync contract on and
    off, the fsync latency distribution the stall watchdog monitors, and
    cold recovery time for a large log — the numbers that size the
    store's write path and its crash-restart MTTR."""

    n_records: int
    batch: int
    append_fsync_per_s: float
    append_nofsync_per_s: float
    fsync_p50_ms: float
    fsync_p99_ms: float
    recovery_s: float
    recovery_records_per_s: float
    recovered_rv: int
    native_sink: bool


def run_durability_benchmark(
    n_records: int = 50_000, batch: int = 64, fsync_records: int = 2_000
) -> DurabilityBenchResult:
    """Benchmark the WAL on a scratch directory: (1) `n_records` appends
    in `batch`-record group commits with fsync OFF (page-cache ceiling),
    (2) cold recovery of that log, (3) `fsync_records` appends with
    fsync ON plus the wal_fsync_duration_seconds p50/p99 over exactly
    this run's observations. Pods carry a realistic container spec so
    record size matches the scheduler's write mix."""
    import shutil
    import tempfile

    from ..api import objects as v1
    from ..runtime.wal import HIST_FSYNC, WriteAheadLog

    def pod(i: int) -> Pod:
        p = Pod(
            metadata=v1.ObjectMeta(name=f"bench-{i}"),
            spec=v1.PodSpec(
                containers=[v1.Container(requests={"cpu": "100m"})]
            ),
        )
        p.metadata.resource_version = i + 1
        return p

    def append_run(wal: WriteAheadLog, count: int, rv0: int = 0) -> float:
        t0 = time.monotonic()
        for start in range(0, count, batch):
            n = min(batch, count - start)
            wal.append_batch([  # graftlint: walseam-exempt(scratch bench WAL: nothing is acked against it and a sink failure must crash the bench loudly)
                (rv0 + start + k + 1, "create", "pods", pod(start + k))
                for k in range(n)
            ])
        return count / max(time.monotonic() - t0, 1e-9)

    tmp = tempfile.mkdtemp(prefix="ktpu-durability-")
    try:
        # arm 1: fsync off — the group-commit/encode ceiling
        wal = WriteAheadLog(tmp + "/nofsync", compact_every=n_records * 2,
                            fsync=False)
        nofsync_rate = append_run(wal, n_records)
        native = wal._native is not None
        wal.close()

        # arm 2: cold recovery of the 50k-record log (crash-restart MTTR)
        t0 = time.monotonic()
        rv, _objects = WriteAheadLog.recover(tmp + "/nofsync")
        recovery_s = max(time.monotonic() - t0, 1e-9)

        # arm 3: fsync on — the durability contract's real price, with
        # the latency histogram scoped to exactly this run
        h0 = metrics.histogram(HIST_FSYNC)
        n0 = h0.count if h0 is not None else 0
        wal = WriteAheadLog(tmp + "/fsync", compact_every=n_records * 2,
                            fsync=True)
        fsync_rate = append_run(wal, fsync_records)
        wal.close()
        h = metrics.histogram(HIST_FSYNC)
        p50, p99 = (
            h.quantiles_since(n0, [0.5, 0.99])
            if h is not None
            else (0.0, 0.0)
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    return DurabilityBenchResult(
        n_records=n_records,
        batch=batch,
        append_fsync_per_s=round(fsync_rate, 1),
        append_nofsync_per_s=round(nofsync_rate, 1),
        fsync_p50_ms=round(p50 * 1e3, 3),
        fsync_p99_ms=round(p99 * 1e3, 3),
        recovery_s=round(recovery_s, 3),
        recovery_records_per_s=round(rv / recovery_s, 1),
        recovered_rv=rv,
        native_sink=native,
    )


@dataclass
class DefragBenchResult:
    """The `defrag` bench workload: a deliberately fragmented fleet
    (half the nodes nearly full, half nearly empty, every pod owned by a
    satisfied ReplicaSet) handed to the verified descheduler. Acceptance
    is the consolidation contract itself: node count AND fleet $/h drop
    strictly, fragmentation drops, and every replica stays bound."""

    num_pods: int
    nodes_before: int
    nodes_after: int
    fleet_per_hour_before: float
    fleet_per_hour_after: float
    fragmentation_before: float
    fragmentation_after: float
    plans: int
    evictions: int
    aborts: int
    bound_after: int
    time_to_quiesce_s: float

    @property
    def strictly_tighter(self) -> bool:
        return (
            self.nodes_after < self.nodes_before
            and self.fleet_per_hour_after < self.fleet_per_hour_before
            and self.bound_after == self.num_pods
        )


def run_defrag_benchmark(
    n_heavy: int = 4,
    n_light: int = 4,
    heavy_pods: int = 6,
    light_pods: int = 2,
    node_cpu: int = 8,
    cost_per_hour: float = 2.0,
    timeout_s: float = 120.0,
    period_s: float = 0.1,
) -> DefragBenchResult:
    """Fragment a fleet on purpose (heavy nodes at heavy_pods/node_cpu
    utilization, light nodes at light_pods/node_cpu), pre-placed under a
    satisfied ReplicaSet so evicted pods are recreated and re-packed by
    the live scheduler, then time the descheduler's convergence."""
    from ..api import objects as v1
    from ..autoscaler import NodeGroup, NodeGroupCatalog, machine_shape
    from ..controller.evictionbudget import EvictionBudget
    from ..controller.replicaset import ReplicaSetController
    from ..descheduler import Descheduler
    from ..ops.encoding import LABEL_COST_PER_HOUR

    metrics.reset()
    server = APIServer()
    sched = Scheduler(server, KubeSchedulerConfiguration())
    group = NodeGroup(
        name="defrag",
        template=machine_shape(
            cpu=str(node_cpu), memory="64Gi", pods=64,
            cost_per_hour=cost_per_hour,
        ),
        max_size=n_heavy + n_light,
    )
    layout: List[tuple] = []  # (node, resident count)
    for i in range(n_heavy):
        layout.append((f"defrag-h{i}", heavy_pods))
    for i in range(n_light):
        layout.append((f"defrag-l{i}", light_pods))
    for name, _cnt in layout:
        server.create("nodes", group.make_node(name))
    n_pods = sum(c for _n, c in layout)
    rs = v1.ReplicaSet(
        metadata=v1.ObjectMeta(name="defrag-rs"),
        spec=v1.ReplicaSetSpec(
            replicas=n_pods,
            selector={"app": "defrag"},
            template=v1.PodTemplateSpec(
                metadata=v1.ObjectMeta(labels={"app": "defrag"}),
                spec=v1.PodSpec(
                    containers=[v1.Container(requests={"cpu": "1"})]
                ),
            ),
        ),
    )
    server.create("replicasets", rs)
    owners = [
        v1.OwnerReference(
            kind="ReplicaSet", name="defrag-rs", uid=rs.metadata.uid,
            controller=True,
        )
    ]
    i = 0
    for name, cnt in layout:
        for _ in range(cnt):
            server.create(
                "pods",
                Pod(
                    metadata=v1.ObjectMeta(
                        name=f"defrag-p{i}",
                        labels={"app": "defrag"},
                        owner_references=list(owners),
                    ),
                    spec=v1.PodSpec(
                        containers=[v1.Container(requests={"cpu": "1"})],
                        node_name=name,
                    ),
                ),
            )
            i += 1

    def fleet_cost() -> float:
        nodes, _ = server.list("nodes")
        total = 0.0
        for n in nodes:
            raw = n.metadata.labels.get(LABEL_COST_PER_HOUR)
            total += float(raw) if raw else 0.0
        return round(total, 3)

    rsc = ReplicaSetController(server, resync_period=0.3)
    budget = EvictionBudget(qps=200.0, burst=50)
    desch = Descheduler(
        server,
        sched,
        budget,
        catalog=NodeGroupCatalog([group]),
        period_s=period_s,
        util_threshold=(heavy_pods - 1) / node_cpu,
        max_nodes_per_plan=2,
    )
    sched.start()
    rsc.start()
    try:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if _count_scheduled(server) >= n_pods:
                break
            time.sleep(0.05)
        frag_before = sched.fragmentation_score()
        nodes_before = server.count("nodes")
        cost_before = fleet_cost()
        t0 = time.monotonic()
        desch.start()

        # quiesce: a planning pass can take seconds inside the kernel
        # simulation with nothing externally "active", so stability of
        # the observable state alone is not convergence. Converged =
        # every replica bound, no latched plan, and >= 2 FURTHER planning
        # passes since the state last moved all came back empty-handed.
        def _reject_sum() -> float:
            return sum(
                v
                for _n, l, v in metrics.snapshot_counters(
                    "descheduler_plan_rejected_total"
                )
                if l.get("reason")
                in ("no_candidates", "infeasible", "gang_strand")
            )

        state = None
        rej_at_change = _reject_sum()
        while time.monotonic() < deadline:
            cur = (
                server.count("nodes"),
                _count_scheduled(server),
                desch.executor.active,
                metrics.counter("descheduler_plans_total"),
                metrics.counter("descheduler_evictions_total"),
            )
            if cur != state:
                state = cur
                rej_at_change = _reject_sum()
            elif (
                not cur[2]
                and cur[1] >= n_pods
                and _reject_sum() - rej_at_change >= 2
            ):
                break
            time.sleep(0.05)
        elapsed = time.monotonic() - t0
    finally:
        desch.stop()
        rsc.stop()
        sched.stop()
    aborts = sum(
        v
        for _n, _l, v in metrics.snapshot_counters(
            "descheduler_plan_aborts_total"
        )
    )
    return DefragBenchResult(
        num_pods=n_pods,
        nodes_before=nodes_before,
        nodes_after=server.count("nodes"),
        fleet_per_hour_before=cost_before,
        fleet_per_hour_after=fleet_cost(),
        fragmentation_before=round(frag_before, 4),
        fragmentation_after=round(sched.fragmentation_score(), 4),
        plans=int(metrics.counter("descheduler_plans_total")),
        evictions=int(metrics.counter("descheduler_evictions_total")),
        aborts=int(aborts),
        bound_after=_count_scheduled(server),
        time_to_quiesce_s=round(elapsed, 3),
    )
