"""REST façade + client + kubectl: the full HTTP path.

Mirrors the reference's integration topology (test/integration/: real
in-process apiserver over HTTP, real components as clients) — here the
scheduler itself runs against the REST client to prove every component
works across the wire, not just in-process.
"""

import io
import json
import time
import urllib.request
from contextlib import redirect_stdout

import pytest

from kubernetes_tpu.api import serialization as codec
from kubernetes_tpu.api.objects import (
    Container,
    Node,
    NodeSpec,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodSpec,
)
from kubernetes_tpu.apiserver import RESTClient, serve
from kubernetes_tpu.client.apiserver import AlreadyExists, NotFound
from kubernetes_tpu.cmd.kubectl import main as kubectl_main
from kubernetes_tpu.scheduler import KubeSchedulerConfiguration, Scheduler


@pytest.fixture
def rest():
    srv, port, store = serve(port=0)
    yield RESTClient(f"http://127.0.0.1:{port}"), store, port
    srv.shutdown()


def make_node(name):
    return Node(
        metadata=ObjectMeta(name=name, namespace=""),
        spec=NodeSpec(),
        status=NodeStatus(allocatable={"cpu": "4", "memory": "32Gi", "pods": 110}),
    )


def make_pod(name):
    return Pod(
        metadata=ObjectMeta(name=name),
        spec=PodSpec(containers=[Container(requests={"cpu": "100m"})]),
    )


def test_rest_crud_roundtrip(rest):
    client, _store, _port = rest
    client.create("nodes", make_node("n0"))
    got = client.get("nodes", "", "n0")
    assert got.metadata.name == "n0"
    assert got.status.allocatable["cpu"] == "4"
    with pytest.raises(AlreadyExists):
        client.create("nodes", make_node("n0"))

    def mutate(n):
        n.spec.unschedulable = True
        return n

    client.guaranteed_update("nodes", "", "n0", mutate)
    assert client.get("nodes", "", "n0").spec.unschedulable is True
    objs, rv = client.list("nodes")
    assert len(objs) == 1 and rv > 0
    client.delete("nodes", "", "n0")
    with pytest.raises(NotFound):
        client.get("nodes", "", "n0")


def test_rest_watch_streams_events(rest):
    client, _store, _port = rest
    w = client.watch("pods")
    time.sleep(0.2)
    client.create("pods", make_pod("a"))
    ev = w.get(timeout=5)
    assert ev is not None and ev.type == "ADDED"
    assert ev.object.metadata.name == "a"
    client.delete("pods", "default", "a")
    types = set()
    for _ in range(2):
        ev = w.get(timeout=5)
        if ev:
            types.add(ev.type)
    assert "DELETED" in types
    w.stop()


def test_scheduler_runs_over_rest(rest):
    client, _store, _port = rest
    for i in range(3):
        client.create("nodes", make_node(f"n{i}"))
    sched = Scheduler(client, KubeSchedulerConfiguration())
    sched.start()
    try:
        client.create("pods", make_pod("p"))
        deadline = time.time() + 20
        while time.time() < deadline:
            if client.get("pods", "default", "p").spec.node_name:
                break
            time.sleep(0.05)
        assert client.get("pods", "default", "p").spec.node_name
    finally:
        sched.stop()


def test_binding_subresource(rest):
    client, _store, port = rest
    client.create("nodes", make_node("n0"))
    client.create("pods", make_pod("p"))
    body = json.dumps(
        {"podName": "p", "podNamespace": "default", "targetNode": "n0"}
    ).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/v1/namespaces/default/pods/p/binding",
        data=body,
        headers={"Content-Type": "application/json"},
    )
    resp = urllib.request.urlopen(req, timeout=5)
    assert resp.status == 201
    assert client.get("pods", "default", "p").spec.node_name == "n0"


def test_kubectl_get_apply_taint(rest, tmp_path):
    client, _store, port = rest
    server_flag = f"--server=http://127.0.0.1:{port}"
    manifest = tmp_path / "node.json"
    manifest.write_text(
        json.dumps(codec.encode(make_node("kn")))
    )
    assert kubectl_main([server_flag, "apply", "-f", str(manifest)]) == 0
    out = io.StringIO()
    with redirect_stdout(out):
        assert kubectl_main([server_flag, "get", "nodes"]) == 0
    assert "kn" in out.getvalue()
    assert (
        kubectl_main(
            [server_flag, "taint", "nodes", "kn", "dedicated=infra:NoSchedule"]
        )
        == 0
    )
    assert client.get("nodes", "", "kn").spec.taints[0].key == "dedicated"
    assert kubectl_main([server_flag, "cordon", "kn"]) == 0
    assert client.get("nodes", "", "kn").spec.unschedulable is True
    out = io.StringIO()
    with redirect_stdout(out):
        assert kubectl_main([server_flag, "-o", "json", "get", "nodes", "kn"]) == 0
    assert json.loads(out.getvalue())["metadata"]["name"] == "kn"
    assert kubectl_main([server_flag, "delete", "nodes", "kn"]) == 0


def test_serializer_roundtrip_pod_affinity():
    from kubernetes_tpu.api.objects import (
        Affinity,
        PodAffinityTerm,
        PodAntiAffinity,
        Toleration,
    )
    from kubernetes_tpu.api.selectors import LabelSelector

    pod = Pod(
        metadata=ObjectMeta(name="p", labels={"app": "x"}),
        spec=PodSpec(
            containers=[Container(name="c", requests={"cpu": "100m"})],
            affinity=Affinity(
                pod_anti_affinity=PodAntiAffinity(
                    required=(
                        PodAffinityTerm(
                            label_selector=LabelSelector.make(
                                match_labels={"app": "x"}
                            ),
                            topology_key="zone",
                        ),
                    )
                )
            ),
            tolerations=[Toleration(key="k", operator="Exists")],
        ),
    )
    wire = json.dumps(codec.encode(pod))
    back = codec.decode("pods", json.loads(wire))
    term = back.spec.affinity.pod_anti_affinity.required[0]
    assert term.topology_key == "zone"
    assert term.label_selector.matches({"app": "x"})
    assert back.spec.tolerations[0].operator == "Exists"
    # cluster-scoped namespace survives
    node_wire = json.dumps(codec.encode(make_node("n")))
    assert codec.decode("nodes", json.loads(node_wire)).metadata.namespace == ""


# -- leadership fencing over REST (ISSUE 10) ---------------------------------


def _make_lease(store, holder="sched-a", transitions=3):
    from kubernetes_tpu.client.leaderelection import Lease

    lease = Lease(
        metadata=ObjectMeta(name="kube-scheduler", namespace="kube-system"),
        holder_identity=holder,
        lease_duration_seconds=15.0,
        renew_time=time.monotonic(),
        lease_transitions=transitions,
    )
    store.create("leases", lease)
    return lease


def _fence(identity="sched-a", transitions=3, name="kube-scheduler"):
    from kubernetes_tpu.client.leaderelection import BindFence

    return BindFence(
        namespace="kube-system",
        name=name,
        identity=identity,
        transitions=transitions,
    )


def test_rest_bind_fence_valid_and_rejections(rest):
    """The /binding route validates X-Leadership-Fence against the live
    lease: a matching token binds, a stale-transitions token, an
    identity mismatch, and a fence naming a lease the server has never
    seen all reject with LeaderFenced — and nothing applies."""
    from kubernetes_tpu.client.apiserver import LeaderFenced

    client, store, _port = rest
    client.create("nodes", make_node("n0"))
    _make_lease(store, holder="sched-a", transitions=3)
    for i in range(4):
        client.create("pods", make_pod(f"fp{i}"))
    from kubernetes_tpu.api.objects import Binding

    def binding(i):
        return Binding(
            pod_name=f"fp{i}", pod_namespace="default", target_node="n0"
        )

    # matching fence: binds land
    assert client.bind_pods([binding(0)], fence=_fence()) == [None]
    assert client.get("pods", "default", "fp0").spec.node_name == "n0"
    # stale transitions (a takeover bumped the lease since this token)
    with pytest.raises(LeaderFenced):
        client.bind_pods([binding(1)], fence=_fence(transitions=2))
    # identity mismatch (someone else holds the lease)
    with pytest.raises(LeaderFenced):
        client.bind_pods([binding(1)], fence=_fence(identity="sched-b"))
    # fence on a lease the server has never seen
    with pytest.raises(LeaderFenced):
        client.bind_pods([binding(1)], fence=_fence(name="no-such-lease"))
    # single-pod surface rejects identically
    with pytest.raises(LeaderFenced):
        client.bind_pod(binding(2), fence=_fence(transitions=99))
    # none of the rejected binds applied
    for i in (1, 2, 3):
        assert client.get("pods", "default", f"fp{i}").spec.node_name == ""


def test_rest_bind_fence_malformed_header_is_400(rest):
    """A garbage fence header must 400, never silently degrade to an
    UNfenced bind."""
    client, store, port = rest
    client.create("nodes", make_node("n0"))
    client.create("pods", make_pod("mp0"))
    from kubernetes_tpu.client.leaderelection import FENCE_HEADER

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/v1/namespaces/default/pods/mp0/binding",
        data=json.dumps(
            {"podName": "mp0", "podNamespace": "default", "targetNode": "n0"}
        ).encode(),
        method="POST",
        headers={
            "Content-Type": "application/json",
            FENCE_HEADER: "not json at all",
        },
    )
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=5)
    assert ei.value.code == 400
    assert client.get("pods", "default", "mp0").spec.node_name == ""


def test_rest_fenced_mid_batch_leaves_prefix_applied_once(rest, monkeypatch):
    """A fenced 409 arriving mid-batch raises (the remaining chunks are
    never attempted) while the chunk that landed before the takeover
    stays applied exactly once. Chunks of one binding, so that three
    bindings are three requests."""
    from kubernetes_tpu.apiserver import client as client_mod
    from kubernetes_tpu.client.apiserver import LeaderFenced
    from kubernetes_tpu.api.objects import Binding

    monkeypatch.setattr(client_mod, "BIND_CHUNK", 1)
    client, store, _port = rest
    client.create("nodes", make_node("n0"))
    _make_lease(store, holder="sched-a", transitions=3)
    for i in range(3):
        client.create("pods", make_pod(f"bp{i}"))
    applied = []
    orig_bind = store.bind_pods

    def bind_and_then_takeover(bindings, fence=None):
        errs = orig_bind(bindings, fence=fence)
        applied.extend(
            b.pod_name for b, e in zip(bindings, errs) if e is None
        )
        if len(applied) == 1:
            # a standby takes over between this request and the next:
            # holder + transitions move on
            lease = store.get("leases", "kube-system", "kube-scheduler")
            lease.holder_identity = "sched-b"
            lease.lease_transitions += 1
            store.update("leases", lease)
        return errs

    store.bind_pods = bind_and_then_takeover
    bindings = [
        Binding(pod_name=f"bp{i}", pod_namespace="default", target_node="n0")
        for i in range(3)
    ]
    with pytest.raises(LeaderFenced):
        client.bind_pods(bindings, fence=_fence())
    store.bind_pods = orig_bind
    # the pre-takeover prefix applied exactly once; nothing after it
    assert applied == ["bp0"]
    assert client.get("pods", "default", "bp0").spec.node_name == "n0"
    assert client.get("pods", "default", "bp1").spec.node_name == ""
    assert client.get("pods", "default", "bp2").spec.node_name == ""


def test_leader_elector_over_rest(rest):
    """LeaderElector driven through the RESTClient: acquire/renew/release
    work over the wire, and a degraded store (503 Degraded), a fenced
    store (503 without Retry-After -> NotPrimary), and a transport
    failure all classify as COUNTED SKIPS — the holder keeps leading
    within renew_deadline, exactly the in-process contract."""
    from kubernetes_tpu.client.leaderelection import (
        COUNTER_DEGRADED_SKIPS,
        LeaderElectionConfig,
        LeaderElector,
    )
    from kubernetes_tpu.utils.metrics import metrics

    client, store, _port = rest

    class _Gate:
        degraded = False

        def check_writable(self):
            if self.degraded:
                from kubernetes_tpu.runtime.consensus import DegradedWrites

                raise DegradedWrites("test: degraded")

    gate = _Gate()
    store.write_gate.attach_consensus(gate)
    cfg = LeaderElectionConfig(
        identity="rest-elector",
        lease_duration=4.0,
        renew_deadline=3.0,
        retry_period=0.5,
    )
    started = []
    elector = LeaderElector(
        client, cfg, on_started_leading=lambda: started.append(1)
    )
    # acquire over REST (lease create through the wire)
    assert elector._try_acquire_or_renew() is True
    lease = client.get("leases", "kube-system", "kube-scheduler")
    assert lease.holder_identity == "rest-elector"
    fence = elector.fence()
    assert fence.transitions == lease.lease_transitions

    def skips():
        return metrics.dump().get(f"{COUNTER_DEGRADED_SKIPS}{{}}", 0.0)

    # degraded store: renew is a counted skip, not an exception
    before = skips()
    gate.degraded = True
    assert elector._try_acquire_or_renew() is False
    assert skips() == before + 1
    gate.degraded = False
    assert elector._try_acquire_or_renew() is True
    # fenced store (503 without Retry-After -> NotPrimary): counted skip
    before = skips()
    store.write_gate.fenced = True
    assert elector._try_acquire_or_renew() is False
    assert skips() == before + 1
    store.write_gate.fenced = False
    # transport failure (nothing listening): counted skip, no exception
    dead = LeaderElector(
        RESTClient("http://127.0.0.1:9", timeout=0.5),
        LeaderElectionConfig(
            identity="dead",
            lease_duration=4.0,
            renew_deadline=3.0,
            retry_period=0.5,
        ),
        on_started_leading=lambda: None,
    )
    before = skips()
    assert dead._try_acquire_or_renew() is False
    assert skips() == before + 1
    # graceful release over REST: holder cleared, transitions bumped
    t0 = client.get("leases", "kube-system", "kube-scheduler").lease_transitions
    assert elector.release() is True
    lease = client.get("leases", "kube-system", "kube-scheduler")
    assert lease.holder_identity == ""
    assert lease.lease_transitions == t0 + 1
