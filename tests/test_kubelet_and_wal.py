"""Kubelet-lite node agent + durable WAL store.

Covers VERDICT r1 item 7: a minimal node agent (pod sync against a fake
runtime, status + lease heartbeats) sharing one code path between hollow
and real nodes, plus a durable snapshot/WAL behind the API store with a
crash-recovery test (reference fault model: crash-only against etcd,
etcd3/store.go)."""

import json
import os
import time

from kubernetes_tpu.api import objects as v1
from kubernetes_tpu.client import APIServer
from kubernetes_tpu.controller.nodelifecycle import NodeLifecycleController
from kubernetes_tpu.kubelet import ANN_FAIL, ANN_RUN_SECONDS, NodeAgentPool
from kubernetes_tpu.runtime.wal import WriteAheadLog
from kubernetes_tpu.scheduler import KubeSchedulerConfiguration, Scheduler


def wait_until(fn, timeout=20.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if fn():
            return True
        time.sleep(0.03)
    return False


def make_pod(name, cpu="100m", annotations=None):
    return v1.Pod(
        metadata=v1.ObjectMeta(name=name, annotations=annotations or {}),
        spec=v1.PodSpec(containers=[v1.Container(requests={"cpu": cpu})]),
    )


# ---------------------------------------------------------------------------
# kubelet
# ---------------------------------------------------------------------------


def test_kubelet_runs_bound_pods_and_reports_status():
    server = APIServer()
    pool = NodeAgentPool(server, housekeeping_interval=0.1)
    pool.add_node("node-0")
    sched = Scheduler(server, KubeSchedulerConfiguration())
    pool.start()
    sched.start()
    try:
        server.create("pods", make_pod("web"))
        assert wait_until(
            lambda: server.get("pods", "default", "web").status.phase == "Running"
        )
        pod = server.get("pods", "default", "web")
        assert pod.spec.node_name == "node-0"
        assert pod.status.pod_ip.startswith("10.")
        assert pod.status.start_time is not None
    finally:
        sched.stop()
        pool.stop()


def test_kubelet_pleg_drives_scripted_completion():
    server = APIServer()
    pool = NodeAgentPool(server, housekeeping_interval=0.05)
    pool.add_node("node-0")
    sched = Scheduler(server, KubeSchedulerConfiguration())
    pool.start()
    sched.start()
    try:
        server.create(
            "pods", make_pod("batch", annotations={ANN_RUN_SECONDS: "0.2"})
        )
        server.create(
            "pods",
            make_pod(
                "doomed", annotations={ANN_RUN_SECONDS: "0.2", ANN_FAIL: "true"}
            ),
        )
        assert wait_until(
            lambda: server.get("pods", "default", "batch").status.phase
            == "Succeeded"
        )
        assert wait_until(
            lambda: server.get("pods", "default", "doomed").status.phase
            == "Failed"
        )
    finally:
        sched.stop()
        pool.stop()


def test_kubelet_heartbeats_feed_nodelifecycle_eviction():
    server = APIServer()
    pool = NodeAgentPool(server, heartbeat_interval=0.1, housekeeping_interval=0.1)
    pool.add_node("alive")
    pool.add_node("dying")
    sched = Scheduler(server, KubeSchedulerConfiguration())
    nlc = NodeLifecycleController(
        server,
        node_monitor_period=0.1,
        node_monitor_grace_period=0.6,
        pod_eviction_timeout=0.2,
    )
    pool.start()
    sched.start()
    nlc.start()
    try:
        # pin a pod to the doomed node via nodeName
        pod = make_pod("victim")
        pod.spec.node_name = "dying"
        server.create("pods", pod)
        assert wait_until(
            lambda: server.get("pods", "default", "victim").status.phase
            == "Running"
        )
        pool.remove_node("dying")  # node stops heartbeating
        # nodelifecycle marks NotReady and evicts the pod
        assert wait_until(
            lambda: not any(
                p.metadata.name == "victim" for p in server.list("pods")[0]
            ),
            timeout=30,
        )
    finally:
        nlc.stop()
        sched.stop()
        pool.stop()


# ---------------------------------------------------------------------------
# WAL / crash recovery
# ---------------------------------------------------------------------------


def test_wal_crash_recovery_roundtrip(tmp_path):
    path = str(tmp_path / "cluster")
    server = APIServer(wal=WriteAheadLog(path))
    server.create("nodes", v1.Node(metadata=v1.ObjectMeta(name="n0", namespace="")))
    server.create("pods", make_pod("p0"))
    server.create("pods", make_pod("p1"))
    server.delete("pods", "default", "p1")

    def bind(cur):
        cur.spec.node_name = "n0"
        return cur

    server.guaranteed_update("pods", "default", "p0", bind)
    rv_before = server.resource_version

    # "crash": drop the in-memory server entirely, recover from disk
    recovered = APIServer.recover(path)
    assert recovered.resource_version == rv_before
    pods, _ = recovered.list("pods")
    assert [p.metadata.name for p in pods] == ["p0"]
    assert pods[0].spec.node_name == "n0"
    nodes, _ = recovered.list("nodes")
    assert [n.metadata.name for n in nodes] == ["n0"]
    # writes continue with monotone resourceVersion
    recovered.create("pods", make_pod("p2"))
    assert recovered.resource_version > rv_before


def test_wal_snapshot_compaction_and_torn_tail(tmp_path):
    path = str(tmp_path / "cluster")
    wal = WriteAheadLog(path, compact_every=10)
    server = APIServer(wal=wal)
    for i in range(25):
        server.create("pods", make_pod(f"p{i}"))
    # compaction runs async off the mutation path; wait for the snapshot
    # generous timeout: fsync-per-append + async compaction under a
    # CPU-contended suite can stretch well past 10s
    assert wait_until(lambda: os.path.exists(path + ".snapshot.json"), timeout=60)
    # simulate a torn final record (crash mid-append)
    with open(path + ".wal", "a", encoding="utf-8") as f:
        f.write('{"rv": 99999, "verb": "create", "kind": "pods", "obj": {tru')
    recovered = APIServer.recover(path)
    pods, _ = recovered.list("pods")
    assert len(pods) == 25  # torn record dropped, everything else intact


def test_wal_scheduler_end_to_end_restart(tmp_path):
    """Full crash-restart: scheduler + kubelet pool against a durable store;
    after 'crash', a fresh control plane on the recovered store sees the
    bound pods and schedules new ones."""
    path = str(tmp_path / "cluster")
    server = APIServer(wal=WriteAheadLog(path))
    pool = NodeAgentPool(server, housekeeping_interval=0.1)
    pool.add_node("node-0")
    sched = Scheduler(server, KubeSchedulerConfiguration())
    pool.start()
    sched.start()
    try:
        server.create("pods", make_pod("before-crash"))
        assert wait_until(
            lambda: server.get("pods", "default", "before-crash").status.phase
            == "Running"
        )
    finally:
        sched.stop()
        pool.stop()

    # crash + recover
    server2 = APIServer.recover(path)
    pod = server2.get("pods", "default", "before-crash")
    assert pod.spec.node_name == "node-0"
    pool2 = NodeAgentPool(server2, housekeeping_interval=0.1)
    pool2.add_node("node-0", register=False)  # node object survived the crash
    sched2 = Scheduler(server2, KubeSchedulerConfiguration())
    pool2.start()
    sched2.start()
    try:
        server2.create("pods", make_pod("after-crash"))
        assert wait_until(
            lambda: server2.get("pods", "default", "after-crash").status.phase
            == "Running"
        )
        # the recovered scheduler accounted the pre-crash pod: node-0 has 2
        assert (
            server2.get("pods", "default", "after-crash").spec.node_name
            == "node-0"
        )
    finally:
        sched2.stop()
        pool2.stop()


# ---------------------------------------------------------------------------
# probes (pkg/kubelet/prober)
# ---------------------------------------------------------------------------


def _probe_pod(name, annotations, readiness=None, liveness=None):
    return v1.Pod(
        metadata=v1.ObjectMeta(name=name, annotations=annotations),
        spec=v1.PodSpec(
            containers=[
                v1.Container(
                    name="c0",
                    requests={"cpu": "100m"},
                    readiness_probe=readiness,
                    liveness_probe=liveness,
                )
            ]
        ),
    )


def test_readiness_probe_gates_ready_condition_and_endpoints():
    from kubernetes_tpu.controller.endpoints import EndpointsController
    from kubernetes_tpu.kubelet.runtime import ANN_READY_AFTER

    server = APIServer()
    pool = NodeAgentPool(server, housekeeping_interval=0.05)
    pool.add_node("node-0")
    sched = Scheduler(server, KubeSchedulerConfiguration())
    epc = EndpointsController(server)
    pool.start()
    sched.start()
    epc.start()
    try:
        server.create(
            "services",
            v1.Service(
                metadata=v1.ObjectMeta(name="web"),
                spec=v1.ServiceSpec(selector={"app": "web"}),
            ),
        )
        p = _probe_pod(
            "warm",
            {ANN_READY_AFTER: "0.6"},
            readiness=v1.Probe(period_seconds=0.05, failure_threshold=1),
        )
        p.metadata.labels = {"app": "web"}
        server.create("pods", p)
        # runs, but NOT Ready during warmup: condition False, endpoints
        # list it under notReadyAddresses
        assert wait_until(
            lambda: server.get("pods", "default", "warm").status.phase
            == "Running"
        )
        pod = server.get("pods", "default", "warm")
        conds = {c.type: c.status for c in pod.status.conditions}
        assert conds.get("Ready") == "False"

        def ep_ready_count():
            try:
                ep = server.get("endpoints", "default", "web")
            except Exception:
                return -1
            return sum(len(s.addresses) for s in ep.subsets)

        assert ep_ready_count() < 1
        # after warmup the probe flips Ready and endpoints pick it up
        assert wait_until(
            lambda: {
                c.type: c.status
                for c in server.get("pods", "default", "warm").status.conditions
            }.get("Ready")
            == "True",
        ), "readiness probe must flip Ready after ready-after elapses"
        assert wait_until(lambda: ep_ready_count() == 1)
    finally:
        epc.stop()
        sched.stop()
        pool.stop()


def test_liveness_probe_restarts_container():
    from kubernetes_tpu.kubelet.runtime import ANN_UNHEALTHY_AFTER

    server = APIServer()
    pool = NodeAgentPool(server, housekeeping_interval=0.05)
    pool.add_node("node-0")
    sched = Scheduler(server, KubeSchedulerConfiguration())
    pool.start()
    sched.start()
    try:
        p = _probe_pod(
            "crashy",
            {ANN_UNHEALTHY_AFTER: "0.3"},
            liveness=v1.Probe(period_seconds=0.05, failure_threshold=2),
        )
        server.create("pods", p)
        assert wait_until(
            lambda: server.get("pods", "default", "crashy").status.phase
            == "Running"
        )
        # the runtime goes unhealthy after 0.3s; two consecutive failures
        # trigger an in-place restart, counted in containerStatuses
        assert wait_until(
            lambda: any(
                cs.restart_count >= 1
                for cs in server.get(
                    "pods", "default", "crashy"
                ).status.container_statuses
            ),
        ), "liveness failure must restart the container and count it"
        # pod stays Running (restart, not kill)
        assert server.get("pods", "default", "crashy").status.phase == "Running"
    finally:
        sched.stop()
        pool.stop()
