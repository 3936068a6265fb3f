"""One binding request per wave: POST /api/v1/bindings (a BindingList)
and RESTClient.bind_pods, which sends a wave's bindings in chunks of at
most BIND_CHUNK, one request each.

Every guarantee the per-pod binding POST gave, held per request: one
store call (one lock hold, one WAL group, one fsync, the watch events
after it), a typed outcome per item in order, the fence and the
authorisation for the whole request with nothing applied on a refusal,
the trace id per item, and the transport taxonomy per chunk — a lost
ack is QuorumLost for every binding of that chunk, read back before any
retry, never replayed."""

import json
import math
import signal
import threading
import urllib.error
import urllib.request

import pytest

from test_chaos_net import _Stack
from test_chaos_pipeline import wait_until
from test_rest_api import _fence, _make_lease, make_node, make_pod
from test_tracing import _hist_n, _scrape, apiserver_child

from kubernetes_tpu.api.objects import Binding
from kubernetes_tpu.apiserver import RESTClient, serve
from kubernetes_tpu.apiserver import client as client_mod
from kubernetes_tpu.apiserver.auth import (
    RBACAuthorizer,
    TokenAuthenticator,
    make_rule,
)
from kubernetes_tpu.apiserver.client import (
    BIND_CHUNK,
    COUNTER_BINDING_REQUESTS,
    COUNTER_BINDINGS_SENT,
    AuthRESTClient,
)
from kubernetes_tpu.client.apiserver import (
    APIServer,
    Conflict,
    LeaderFenced,
    NotFound,
)
from kubernetes_tpu.client.leaderelection import FENCE_HEADER
from kubernetes_tpu.runtime.consensus import DegradedWrites, QuorumLost
from kubernetes_tpu.utils.metrics import metrics, rest_resource_label
from kubernetes_tpu.utils.tracing import bind_context, tracer

@pytest.fixture
def rest():
    srv, port, store = serve(port=0)
    store.create("nodes", make_node("n0"))
    yield RESTClient(f"http://127.0.0.1:{port}", timeout=10.0), store, port
    srv.shutdown()


def _pods(store, prefix, n, namespace="default"):
    """n unbound pods in the store; their bindings, uid and all."""
    out = []
    for i in range(n):
        pod = make_pod(f"{prefix}{i}")
        pod.metadata.namespace = namespace
        created = store.create("pods", pod)
        out.append(
            Binding(
                pod_name=created.metadata.name,
                pod_namespace=namespace,
                pod_uid=created.metadata.uid,
                target_node="n0",
            )
        )
    return out


def _node_of(store, b):
    return store.get("pods", b.pod_namespace, b.pod_name).spec.node_name


def _post_list(port, items, headers=None, token=None):
    """A raw POST of a BindingList; (status, body)."""
    hdrs = {"Content-Type": "application/json", **(headers or {})}
    if token:
        hdrs["Authorization"] = f"Bearer {token}"
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/v1/bindings",
        data=json.dumps({"kind": "BindingList", "items": items}).encode(),
        method="POST",
        headers=hdrs,
    )
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _wire(b):
    return {
        "podName": b.pod_name,
        "podNamespace": b.pod_namespace,
        "podUid": b.pod_uid,
        "targetNode": b.target_node,
    }


def test_all_good_list_binds_every_pod_in_list_order(rest):
    """One request binds every pod, and a watch sees the binds in the
    order of the list."""
    client, store, _port = rest
    bindings = _pods(store, "ok", 12)
    watcher = store.watch("pods")
    seen = []

    def pump():
        for ev in watcher:
            if ev.object.spec.node_name:
                seen.append(ev.object.metadata.name)

    threading.Thread(target=pump, daemon=True).start()
    requests0 = metrics.counter(COUNTER_BINDING_REQUESTS)
    assert client.bind_pods(bindings) == [None] * 12
    assert metrics.counter(COUNTER_BINDING_REQUESTS) - requests0 == 1
    assert all(_node_of(store, b) == "n0" for b in bindings)
    assert wait_until(lambda: len(seen) >= 12, 10)
    watcher.stop()
    assert seen == [b.pod_name for b in bindings]


def test_mixed_list_returns_typed_outcomes_in_order(rest):
    """None / NotFound / Conflict (already bound) / Conflict (uid
    mismatch) / None, each for the item it concerns; the good ones are
    applied, the others untouched."""
    client, store, _port = rest
    good_a, bound, stale_uid, good_b = _pods(store, "mx", 4)
    assert store.bind_pods([bound]) == [None]
    gone = Binding(pod_name="never-created", pod_namespace="default",
                   target_node="n0")
    stale_uid.pod_uid = "someone-else"
    errs = client.bind_pods([good_a, gone, bound, stale_uid, good_b])
    assert errs[0] is None and errs[4] is None
    assert type(errs[1]) is NotFound
    assert type(errs[2]) is Conflict and "already bound" in str(errs[2])
    assert type(errs[3]) is Conflict and "uid mismatch" in str(errs[3])
    assert _node_of(store, good_a) == "n0" and _node_of(store, good_b) == "n0"
    assert _node_of(store, stale_uid) == ""


def test_superseded_fence_rejects_the_whole_request(rest):
    """A token a takeover has superseded raises LeaderFenced and nothing
    of that request is applied; the matching token then binds them."""
    client, store, _port = rest
    _make_lease(store, holder="sched-a", transitions=3)
    bindings = _pods(store, "fz", 5)
    with pytest.raises(LeaderFenced):
        client.bind_pods(bindings, fence=_fence(transitions=2))
    assert [_node_of(store, b) for b in bindings] == [""] * 5
    assert client.bind_pods(bindings, fence=_fence()) == [None] * 5


def test_fence_superseded_between_chunks_keeps_landed_chunks(
    rest, monkeypatch
):
    """LeaderFenced at a later chunk raises and the chunks after it are
    not attempted; the chunk that landed while the grant was valid stays
    applied, once."""
    monkeypatch.setattr(client_mod, "BIND_CHUNK", 2)
    client, store, _port = rest
    _make_lease(store, holder="sched-a", transitions=3)
    bindings = _pods(store, "fc", 6)
    calls = []
    orig = store.bind_pods

    def bind_then_takeover(bs, fence=None):
        errs = orig(bs, fence=fence)
        calls.append([b.pod_name for b in bs])
        if len(calls) == 1:
            lease = store.get("leases", "kube-system", "kube-scheduler")
            lease.holder_identity = "sched-b"
            lease.lease_transitions += 1
            store.update("leases", lease)
        return errs

    monkeypatch.setattr(store, "bind_pods", bind_then_takeover)
    with pytest.raises(LeaderFenced):
        client.bind_pods(bindings, fence=_fence())
    assert calls == [["fc0", "fc1"]]  # the fenced call raised inside orig
    assert [_node_of(store, b) for b in bindings] == ["n0", "n0"] + [""] * 4


def test_malformed_fence_header_is_400_and_nothing_applies(rest):
    _client, store, port = rest
    bindings = _pods(store, "mf", 3)
    status, body = _post_list(
        port, [_wire(b) for b in bindings],
        headers={FENCE_HEADER: "not json at all"},
    )
    assert status == 400 and body["reason"] == "BadRequest"
    assert [_node_of(store, b) for b in bindings] == [""] * 3


@pytest.mark.parametrize(
    "body",
    [{"kind": "BindingList"}, {"items": []}, {"items": "x"},
     {"items": [["not", "a", "binding"]]}],
    ids=["no-items", "empty", "not-a-list", "item-not-an-object"],
)
def test_list_without_bindings_is_400(rest, body):
    _client, _store, port = rest
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/v1/bindings",
        data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"},
    )
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=5)
    assert ei.value.code == 400


def test_kubelet_identity_is_denied_by_the_node_authorizer():
    """`bindings` is the scheduler's verb: the node authorizer denies a
    kubelet the list as it denies it the single binding."""
    from kubernetes_tpu.apiserver.nodeauth import (
        NODE_USER_PREFIX,
        NODES_GROUP,
        NodeAwareAuthorizer,
    )

    store = APIServer()
    store.create("nodes", make_node("n0"))
    authn = TokenAuthenticator(allow_anonymous=False)
    authn.add_token("kubelet-token", NODE_USER_PREFIX + "n0", (NODES_GROUP,))
    authn.add_token("sched-token", "system:kube-scheduler", ())
    rbac = RBACAuthorizer()
    rbac.bind("system:kube-scheduler", make_rule(["create"], ["bindings"]))
    srv, port, _ = serve(
        store=store, port=0, authenticator=authn,
        authorizer=NodeAwareAuthorizer(rbac, store),
    )
    try:
        bindings = _pods(store, "kb", 2)
        items = [_wire(b) for b in bindings]
        status, body = _post_list(port, items, token="kubelet-token")
        assert status == 403 and body["reason"] == "Forbidden"
        assert [_node_of(store, b) for b in bindings] == ["", ""]
        status, _ = _post_list(port, items)  # no identity at all
        assert status == 401
        status, body = _post_list(port, items, token="sched-token")
        assert status == 200
        assert [it["status"] for it in body["items"]] == ["Success"] * 2
    finally:
        srv.shutdown()


def test_list_spanning_a_denied_namespace_applies_nothing():
    """`create` on `bindings` is asked for EVERY namespace in the list
    before the store sees any of it: one denied namespace refuses the
    whole request."""
    store = APIServer()
    store.create("nodes", make_node("n0"))
    authn = TokenAuthenticator(allow_anonymous=False)
    authn.add_token("team-a-token", "team-a-scheduler", ())
    rbac = RBACAuthorizer()
    rbac.bind(
        "team-a-scheduler",
        make_rule(["create"], ["bindings"], namespaces=["team-a"]),
    )
    srv, port, _ = serve(
        store=store, port=0, authenticator=authn, authorizer=rbac
    )
    try:
        client = AuthRESTClient(f"http://127.0.0.1:{port}", "team-a-token")
        mine = _pods(store, "a", 2, namespace="team-a")
        theirs = _pods(store, "b", 1, namespace="team-b")
        errs = client.bind_pods(mine + theirs)
        # a refusal the server DID answer: known, not parked, not typed
        assert all(isinstance(e, str) and "403" in e for e in errs), errs
        assert [_node_of(store, b) for b in mine + theirs] == [""] * 3
        assert client.bind_pods(mine) == [None, None]
        assert _node_of(store, theirs[0]) == ""
    finally:
        srv.shutdown()


def test_degraded_store_marks_every_binding_and_skips_later_chunks(
    rest, monkeypatch
):
    """A store that refuses writes answers the whole request 503: every
    binding of the chunk carries the DegradedWrites itself, the chunks
    after it a fresh `not attempted`, and nothing is applied."""
    monkeypatch.setattr(client_mod, "BIND_CHUNK", 2)
    client, store, port = rest
    client.degraded_retries = 0

    class _Gate:
        def check_writable(self):
            raise DegradedWrites("test: degraded")

    bindings = _pods(store, "dg", 5)
    store.write_gate.attach_consensus(_Gate())
    requests0 = metrics.counter(COUNTER_BINDING_REQUESTS)
    errs = client.bind_pods(bindings)
    assert len(errs) == 5
    assert all(type(e) is DegradedWrites for e in errs), errs
    assert errs[0] is errs[1] and "test: degraded" in str(errs[0])
    assert all("not attempted" in str(e) for e in errs[2:])
    assert metrics.counter(COUNTER_BINDING_REQUESTS) - requests0 == 1
    assert [_node_of(store, b) for b in bindings] == [""] * 5


def test_quorum_lost_answer_marks_the_chunk_unknown(rest, monkeypatch):
    """503 WriteQuorumLost for a list: the request applied but missed
    its quorum — every binding of the chunk is QuorumLost (read back
    before any retry), later chunks are not attempted."""
    monkeypatch.setattr(client_mod, "BIND_CHUNK", 3)
    client, store, _port = rest
    bindings = _pods(store, "ql", 4)
    orig = store.bind_pods

    def apply_then_lose_quorum(bs, fence=None):
        orig(bs, fence=fence)
        raise QuorumLost("test: applied, ack window missed")

    monkeypatch.setattr(store, "bind_pods", apply_then_lose_quorum)
    errs = client.bind_pods(bindings)
    assert all(type(e) is QuorumLost for e in errs[:3]), errs
    assert type(errs[3]) is DegradedWrites and "not attempted" in str(errs[3])
    assert [_node_of(store, b) for b in bindings] == ["n0"] * 3 + [""]


@pytest.fixture
def stack():
    s = _Stack()
    yield s
    s.stop()


def test_blackholed_ack_is_quorum_lost_for_that_chunk_only(
    stack, monkeypatch
):
    """The second chunk's request is applied and its answer dropped:
    the first chunk is acknowledged, every binding of the second is
    QuorumLost, the third is never sent — and never was the second
    sent twice."""
    monkeypatch.setattr(client_mod, "BIND_CHUNK", 2)
    c = stack.client
    c.timeout = 1.0
    pods = [stack.store.create("pods", make_pod(f"bh{i}")) for i in range(6)]
    bindings = [
        Binding(pod_name=p.metadata.name, pod_namespace="default",
                pod_uid=p.metadata.uid, target_node="net-0")
        for p in pods
    ]
    assert c.bind_pods(bindings[:2]) == [None, None]
    stack.proxy.blackhole_next_responses(1, match=b"/bindings")
    errs = c.bind_pods(bindings[2:])
    assert all(type(e) is QuorumLost for e in errs[:2]), errs
    assert all(
        type(e) is DegradedWrites and "not attempted" in str(e)
        for e in errs[2:]
    ), errs
    names = [
        stack.store.get("pods", "default", f"bh{i}").spec.node_name
        for i in range(6)
    ]
    assert names == ["net-0"] * 4 + ["", ""]
    stack.assert_exactly_once()


def test_refused_connect_is_retryable_degraded_writes(stack):
    """Nothing reached the server: DegradedWrites, not QuorumLost, for
    every binding; after the heal the same list binds."""
    c = stack.client
    pods = [stack.store.create("pods", make_pod(f"rf{i}")) for i in range(3)]
    bindings = [
        Binding(pod_name=p.metadata.name, pod_namespace="default",
                pod_uid=p.metadata.uid, target_node="net-1")
        for p in pods
    ]
    stack.proxy.partition("refuse")
    errs = c.bind_pods(bindings)
    assert len(errs) == 3
    assert all(
        isinstance(e, DegradedWrites) and not isinstance(e, QuorumLost)
        for e in errs
    ), errs
    stack.proxy.heal()
    assert c.bind_pods(bindings) == [None] * 3
    stack.assert_exactly_once()


def test_scheduler_reads_back_a_blackholed_wave_and_finds_it_bound(stack):
    """Through the scheduler: the answer to a wave's binding request is
    dropped, its placements park as unknown, the read-back finds them
    bound — every pod ends bound exactly once, none replayed."""
    def landed():
        return metrics.dump().get(
            "scheduler_bind_reconcile_total{'outcome': 'landed'}", 0.0
        )

    stack.client.timeout = 1.0
    stack.start_scheduler()
    before = landed()
    stack.proxy.blackhole_next_responses(1, match=b"/bindings")
    for i in range(5):
        stack.store.create("pods", make_pod(f"wave-{i}"))
    assert wait_until(lambda: stack.bound_count("wave-") == 5, 30)
    assert wait_until(lambda: landed() > before, 30), (
        "the read-back never resolved the blackholed request as landed"
    )
    assert wait_until(lambda: stack.sched._ridethrough.depth == 0, 15)
    stack.assert_exactly_once()


def test_600_bindings_go_out_in_chunks_and_come_back_in_order(rest):
    """ceil(600 / K) requests, 600 results, each for its own binding:
    every 50th pod is already bound and reads Conflict at its index."""
    client, store, _port = rest
    bindings = _pods(store, "big", 600)
    taken = set(range(0, 600, 50))
    assert store.bind_pods([bindings[i] for i in taken]) == [None] * 12
    requests0 = metrics.counter(COUNTER_BINDING_REQUESTS)
    sent0 = metrics.counter(COUNTER_BINDINGS_SENT)
    errs = client.bind_pods(bindings)
    assert len(errs) == 600
    for i, e in enumerate(errs):
        if i in taken:
            assert type(e) is Conflict and f"big{i} " in str(e), (i, e)
        else:
            assert e is None, (i, e)
    assert (
        metrics.counter(COUNTER_BINDING_REQUESTS) - requests0
        == math.ceil(600 / BIND_CHUNK)
    )
    assert metrics.counter(COUNTER_BINDINGS_SENT) - sent0 == 600
    pods, _ = store.list("pods")
    assert sum(1 for p in pods if p.spec.node_name == "n0") == 600


def test_counters_count_bindings_and_requests_sent(rest, monkeypatch):
    """rest_client_bindings_sent_total / rest_client_binding_requests_total:
    what went out, whatever came back; a chunk that is not attempted is
    not counted."""
    monkeypatch.setattr(client_mod, "BIND_CHUNK", 4)
    client, store, _port = rest
    bindings = _pods(store, "ct", 9)
    requests0 = metrics.counter(COUNTER_BINDING_REQUESTS)
    sent0 = metrics.counter(COUNTER_BINDINGS_SENT)
    assert client.bind_pods(bindings) == [None] * 9
    assert metrics.counter(COUNTER_BINDING_REQUESTS) - requests0 == 3
    assert metrics.counter(COUNTER_BINDINGS_SENT) - sent0 == 9
    assert client.bind_pods([]) == []
    assert metrics.counter(COUNTER_BINDING_REQUESTS) - requests0 == 3
    client.degraded_retries = 0

    class _Gate:
        def check_writable(self):
            raise DegradedWrites("test: degraded")

    more = _pods(store, "cu", 9)
    store.write_gate.attach_consensus(_Gate())
    client.bind_pods(more)
    assert metrics.counter(COUNTER_BINDING_REQUESTS) - requests0 == 4
    assert metrics.counter(COUNTER_BINDINGS_SENT) - sent0 == 13


def test_trace_id_sent_per_item_is_the_id_the_store_stamps(rest):
    """Each item carries the id its pod's trace was minted under; the
    store's apply — or the fenced rejection — is stamped under it."""
    client, store, _port = rest
    _make_lease(store, holder="sched-a", transitions=3)
    bindings = _pods(store, "tr", 3)
    ids = {f"default/tr{i}": f"feedbeefbatch000{i}" for i in range(3)}
    with bind_context(ids):
        with pytest.raises(LeaderFenced):
            client.bind_pods(bindings, fence=_fence(identity="zombie"))
        assert client.bind_pods(bindings, fence=_fence()) == [None] * 3
    for key, tid in ids.items():
        events = [(s["event"], s["key"]) for s in tracer.stamps_for(tid)]
        assert events == [("fenced", key), ("applied", key)], events


def test_both_request_histograms_carry_pods_binding(rest):
    """`bind_post_ms` / `api_bind_ms` read {verb="POST",
    resource="pods/binding"}: a list is one observation on each side,
    with its stages."""
    client, store, port = rest
    bindings = _pods(store, "hs", 7)
    labels = {"verb": "POST", "resource": "pods/binding"}
    client_n0 = _hist_n("rest_client_request_duration_seconds", labels)
    before = _scrape(port)
    assert client.bind_pods(bindings) == [None] * 7
    assert (
        _hist_n("rest_client_request_duration_seconds", labels) - client_n0
        == 1
    )
    page = _scrape(port)

    def delta(name):
        return page.get(name, 0.0) - before.get(name, 0.0)

    assert delta(
        'apiserver_request_duration_seconds_count'
        '{resource="pods/binding",verb="POST"}'
    ) == 1
    for stage in ("authz", "read", "admit", "store", "observe", "respond"):
        assert delta(
            'apiserver_request_stage_seconds_count'
            f'{{resource="pods/binding",stage="{stage}"}}'
        ) == 1, stage
    assert delta(
        'store_commit_stage_seconds_count'
        '{kind="pods",op="bind",stage="fsync"}'
    ) == 1


@pytest.mark.parametrize(
    "path, label",
    [
        ("/api/v1/bindings", "pods/binding"),
        ("/api/v1/namespaces/default/pods/p/binding", "pods/binding"),
        ("/api/v1/namespaces/default/pods", "pods"),
        ("/api/v1/namespaces/default/rolebindings", "rolebindings"),
    ],
)
def test_resource_label_of_a_binding_request(path, label):
    assert rest_resource_label(path) == label


@pytest.fixture(params=["native", "python"])
def wal_apiserver(request, tmp_path):
    """(sink, port, process, WAL path) of a `cmd/apiserver --data-dir`."""
    with apiserver_child(request.param, tmp_path) as (port, proc, wal_path):
        yield request.param, port, proc, wal_path


def test_one_request_is_one_wal_group_read_back_after_a_kill(wal_apiserver):
    """N bindings in one request: N WAL records under one commit — ONE
    fsync from the Python sink; the native committer may split a burst
    but never comes near one per record — acknowledged only after it,
    so all N are read back from the WAL files after a SIGKILL."""
    sink, port, proc, wal_path = wal_apiserver
    n = 64
    client = RESTClient(f"http://127.0.0.1:{port}", timeout=10.0)
    client.create("nodes", make_node("n0"))
    for i in range(n):
        client.create("pods", make_pod(f"w{i}"))
    bindings = [
        Binding(pod_name=f"w{i}", pod_namespace="default", target_node="n0")
        for i in range(n)
    ]
    before = _scrape(port)
    assert client.bind_pods(bindings) == [None] * n
    page = _scrape(port)
    client.close()

    def delta(name):
        return page.get(name, 0.0) - before.get(name, 0.0)

    assert delta("wal_records_appended_total") == n
    if sink == "python":
        assert delta("wal_fsyncs_total") == 1
    else:
        assert 1 <= delta("wal_fsyncs_total") <= 8
    assert delta("wal_fsync_duration_seconds_count") == 1  # one append
    proc.send_signal(signal.SIGKILL)
    proc.wait(10)
    recovered = APIServer.recover(wal_path)
    pods, _ = recovered.list("pods")
    assert sorted(
        p.metadata.name for p in pods if p.spec.node_name == "n0"
    ) == sorted(f"w{i}" for i in range(n))
