"""Serving-tier transport + topology units: the pooled REST client's
connection-reuse failure edges, watch-codec negotiation, the balancer,
and RV-consistent follower reads.

The contracts under test (ISSUE 14):
  * a stale pooled socket (server closed it idle) reopens exactly once
    and never double-sends a bind;
  * a reused connection that dies mid-bind-POST (request delivered, ack
    lost) classifies as QuorumLost — never a transparent replay;
  * binary watch-codec negotiation falls back to newline-JSON against a
    server that doesn't speak it;
  * a follower read demanding an rv ahead of the follower's commit index
    blocks until the commit catches up (or 504s with Retry-After on
    timeout — the PR-6 freshness-wait contract, generalized to the
    commit index).
"""

import copy
import json
import socket
import threading
import time
import urllib.request

import pytest

from kubernetes_tpu.api import serialization as codec
from kubernetes_tpu.api.objects import (
    Binding,
    Container,
    ObjectMeta,
    Pod,
    PodSpec,
)
from kubernetes_tpu.apiserver.client import (
    COUNTER_CONN_OPENED,
    COUNTER_CONN_REUSED,
    COUNTER_WATCH_RECONNECTS,
    HTTPConnectionPool,
    RESTClient,
    _WATCH_RESUME_ATTEMPTS,
)
from kubernetes_tpu.apiserver.frontend import (
    FollowerReadStore,
    serve_frontend,
)
from kubernetes_tpu.apiserver.rest import serve
from kubernetes_tpu.apiserver.watchcodec import WATCH_CONTENT_TYPE
from kubernetes_tpu.client.apiserver import APIServer
from kubernetes_tpu.runtime.consensus import QuorumLost
from kubernetes_tpu.runtime.watch import BOOKMARK
from kubernetes_tpu.testing.netchaos import LoadBalancerProxy
from kubernetes_tpu.utils.metrics import metrics


def make_pod(name, ns="default"):
    return Pod(
        metadata=ObjectMeta(name=name, namespace=ns),
        spec=PodSpec(containers=[Container(requests={"cpu": "1m"})]),
    )


def wait_until(cond, timeout=10.0, tick=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(tick)
    return cond()


@pytest.fixture
def rest():
    srv, port, store = serve(port=0, bookmark_period_s=0.5)
    client = RESTClient(f"http://127.0.0.1:{port}", timeout=5.0)
    yield client, store, port
    client.close()
    srv.shutdown()


# -- connection pool ----------------------------------------------------------


def test_pool_reuses_one_connection_across_requests(rest):
    client, _store, _port = rest
    opened0 = metrics.counter(COUNTER_CONN_OPENED)
    reused0 = metrics.counter(COUNTER_CONN_REUSED)
    for i in range(8):
        client.create("pods", make_pod(f"pool-{i}"))
    objs, _ = client.list("pods")
    assert len(objs) == 8
    # one socket carried everything after the first request opened it
    assert metrics.counter(COUNTER_CONN_OPENED) - opened0 == 1
    assert metrics.counter(COUNTER_CONN_REUSED) - reused0 == 8
    assert client.pool.size() == 1


class _ScriptedServer:
    """Minimal raw HTTP/1.1 server for connection-lifecycle edges: each
    accepted connection serves requests until the per-connection script
    says close. Records every request line + body it actually SAW —
    the double-send assertions read this, not client-side state."""

    def __init__(
        self,
        close_after=1,
        status=201,
        body=b'{"ok":1}',
        blackhole_paths=(),
    ):
        self.close_after = close_after  # requests served per connection
        self.status = status
        self.body = body
        # paths whose request is READ (recorded) but never answered: the
        # connection closes instead — write delivered, ack lost
        self.blackhole_paths = blackhole_paths
        self.requests = []  # (method, path, body_bytes)
        self.connections = 0
        self._lock = threading.Lock()
        self._lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lst.bind(("127.0.0.1", 0))
        self._lst.listen(8)
        self.port = self._lst.getsockname()[1]
        self._stop = threading.Event()
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._lst.accept()
            except OSError:
                return
            with self._lock:
                self.connections += 1
            threading.Thread(
                target=self._serve, args=(conn,), daemon=True
            ).start()

    def _read_request(self, f):
        line = f.readline()
        if not line:
            return None
        method, path, _ = line.decode().split(" ", 2)
        length = 0
        while True:
            h = f.readline()
            if not h or h in (b"\r\n", b"\n"):
                break
            if h.lower().startswith(b"content-length:"):
                length = int(h.split(b":", 1)[1])
        body = f.read(length) if length else b""
        return method, path, body

    def _serve(self, conn):
        f = conn.makefile("rb")
        served = 0
        try:
            while served < self.close_after and not self._stop.is_set():
                req = self._read_request(f)
                if req is None:
                    return
                with self._lock:
                    self.requests.append(req)
                served += 1
                if any(p in req[1].encode() for p in self.blackhole_paths):
                    return  # delivered but unanswered: close in finally
                conn.sendall(
                    b"HTTP/1.1 %d X\r\nContent-Type: application/json\r\n"
                    b"Content-Length: %d\r\n\r\n%s"
                    % (self.status, len(self.body), self.body)
                )
        except OSError:
            pass
        finally:
            # FIN-close after the scripted request count: the pooled
            # client socket is now stale
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            conn.close()

    def stop(self):
        self._stop.set()
        try:
            self._lst.close()
        except OSError:
            pass


def test_stale_pooled_socket_reopens_once_and_never_double_sends_bind():
    """The server closes the kept-alive socket while it idles in the
    pool; the next bind must detect the pending EOF at acquire, open ONE
    fresh connection, and the server must see the bind exactly once."""
    server = _ScriptedServer(close_after=1)
    client = RESTClient(f"http://127.0.0.1:{server.port}", timeout=5.0)
    try:
        client._request("GET", client._url("pods", ""))  # pools the socket
        assert wait_until(lambda: client.pool.size() == 1, 2.0)
        # server has FIN-closed it by now (close_after=1); give the FIN
        # a moment to land so the stale check is deterministic
        assert wait_until(lambda: server.connections == 1, 2.0)
        time.sleep(0.05)
        opened0 = metrics.counter(COUNTER_CONN_OPENED)
        b = Binding(pod_name="p", pod_namespace="default", target_node="n1")
        client.bind_pods([b])
        binds = [r for r in server.requests if r[1].endswith("/bindings")]
        assert len(binds) == 1, f"bind sent {len(binds)} times"
        assert metrics.counter(COUNTER_CONN_OPENED) - opened0 == 1
        assert server.connections == 2
    finally:
        client.close()
        server.stop()


def test_reused_conn_dying_mid_bind_post_classifies_quorum_lost():
    """The reused connection delivers the bind and dies before any
    response (the server read it, then closed) — outcome unknown, so the
    ONLY honest result is QuorumLost (read back before retry), never a
    transparent resend."""
    server = _ScriptedServer(
        close_after=99, blackhole_paths=(b"/binding",)
    )
    client = RESTClient(f"http://127.0.0.1:{server.port}", timeout=5.0)
    try:
        client._request("GET", client._url("pods", ""))  # pools the socket
        assert wait_until(lambda: server.connections == 1, 2.0)
        b = Binding(pod_name="p", pod_namespace="default", target_node="n1")
        errs = client.bind_pods([b])
        assert isinstance(errs[0], QuorumLost), errs
        binds = [r for r in server.requests if r[1].endswith("/bindings")]
        assert len(binds) == 1  # delivered once, NEVER re-sent
        assert server.connections == 1  # the bind rode the reused socket
    finally:
        client.close()
        server.stop()


def test_reused_conn_dying_awaiting_get_response_retries_transparently(
    monkeypatch,
):
    """Same stale-socket race on an idempotent GET: one transparent
    reopen, the caller never sees the dead socket."""
    monkeypatch.setattr(
        HTTPConnectionPool, "_stale", staticmethod(lambda conn: False)
    )
    server = _ScriptedServer(close_after=1, body=b'{"items": []}')
    client = RESTClient(f"http://127.0.0.1:{server.port}", timeout=5.0)
    try:
        client._request("GET", client._url("pods", ""))
        assert wait_until(lambda: server.connections == 1, 2.0)
        time.sleep(0.05)
        out = client._request("GET", client._url("pods", ""))
        assert out == {"items": []}
        assert server.connections == 2  # exactly one reopen
    finally:
        client.close()
        server.stop()


def test_text_subresources_share_request_plumbing_and_degraded_retry():
    """get_text rides _request_raw now: a degraded-store 503 with
    Retry-After is transparently retried instead of surfacing a raw
    RuntimeError (the old hand-rolled error path skipped the contract)."""
    hits = []

    class _Flaky(_ScriptedServer):
        def _serve(self, conn):
            f = conn.makefile("rb")
            try:
                while True:
                    req = self._read_request(f)
                    if req is None:
                        return
                    hits.append(req)
                    if len(hits) == 1:
                        payload = json.dumps(
                            {"reason": "Degraded", "message": "quorum lost"}
                        ).encode()
                        conn.sendall(
                            b"HTTP/1.1 503 X\r\nRetry-After: 0\r\n"
                            b"Content-Length: %d\r\n\r\n%s"
                            % (len(payload), payload)
                        )
                    else:
                        conn.sendall(
                            b"HTTP/1.1 200 X\r\nContent-Type: text/plain\r\n"
                            b"Content-Length: 5\r\n\r\nhello"
                        )
            except OSError:
                pass

    server = _Flaky()
    client = RESTClient(f"http://127.0.0.1:{server.port}", timeout=5.0)
    try:
        text = client.get_text("pods", "default", "p/log")
        assert text == "hello"
        assert len(hits) == 2  # one 503, one retried success
    finally:
        client.close()
        server.stop()


# -- watch codec --------------------------------------------------------------


def test_binary_watch_codec_negotiated_and_decodes(rest):
    client, store, _port = rest
    store.create("pods", make_pod("bin-1"))
    resp, conn = client._open_watch("pods", 0)
    try:
        assert WATCH_CONTENT_TYPE in (resp.headers.get("Content-Type") or "")
    finally:
        client._discard(conn)
    w = client.watch("pods", from_version=0)
    ev = None
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        ev = w.get(timeout=0.5)
        if ev is not None and ev.type != BOOKMARK:
            break
    assert ev is not None and ev.object.metadata.name == "bin-1"
    w.stop()


def test_codec_negotiation_falls_back_to_json_against_old_server():
    """A server that ignores the Accept offer answers newline-JSON; the
    client must branch on the RESPONSE Content-Type and decode the
    legacy wire."""
    event = {
        "type": "ADDED",
        "object": codec.encode(make_pod("old-wire")),
    }
    line = json.dumps(event).encode() + b"\n"

    lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    port = lst.getsockname()[1]

    def old_server():
        conn, _ = lst.accept()
        f = conn.makefile("rb")
        while True:
            h = f.readline()
            if not h or h in (b"\r\n", b"\n"):
                break
        conn.sendall(
            b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
            b"Transfer-Encoding: chunked\r\n\r\n"
        )
        conn.sendall(b"%x\r\n%s\r\n" % (len(line), line))
        time.sleep(1.0)
        conn.close()

    threading.Thread(target=old_server, daemon=True).start()
    client = RESTClient(f"http://127.0.0.1:{port}", timeout=5.0)
    try:
        w = client.watch("pods", from_version=0)
        ev = w.get(timeout=5.0)
        assert ev is not None and ev.object.metadata.name == "old-wire"
        w.stop()
    finally:
        client.close()
        lst.close()


def test_kind_resource_version_probe_is_kind_scoped(rest):
    client, store, _port = rest
    client.create("pods", make_pod("krv-1"))
    pods_rv = store.kind_resource_version("pods")
    # another kind's writes advance the GLOBAL rv but not pods' kind rv
    from kubernetes_tpu.api.objects import ConfigMap

    client.create(
        "configmaps",
        ConfigMap(metadata=ObjectMeta(name="cm"), data={"a": "b"}),
    )
    assert client.kind_resource_version("pods") == pods_rv
    assert client.kind_resource_version("pods") < store.resource_version


# -- balancer + frontends -----------------------------------------------------


def test_watch_through_balancer_resumes_on_frontend_death(rest):
    """Kill the frontend serving a watch stream: the client pump must
    resume through the balancer onto the surviving frontend, whose watch
    cache replays the gap — the consumer-visible Watcher never stops and
    every event arrives exactly once."""
    _client, store, pport = rest
    primary_url = f"http://127.0.0.1:{pport}"
    fe1, p1, c1 = serve_frontend(primary_url, bookmark_period_s=0.3)
    fe2, p2, c2 = serve_frontend(primary_url, bookmark_period_s=0.3)
    lb = LoadBalancerProxy(
        [("127.0.0.1", p1), ("127.0.0.1", p2)], retry_cooldown_s=0.2
    ).start()
    client = RESTClient(f"http://127.0.0.1:{lb.port}", timeout=5.0)
    try:
        store.create("pods", make_pod("lb-0"))
        w = client.watch("pods", from_version=0)
        assert wait_until(
            lambda: (lambda e: e is not None and e.type != BOOKMARK)(
                w.get(timeout=0.2)
            ),
            5.0,
        )
        # find which backend carries the stream and kill that frontend
        per = lb.connections_per_backend()
        assert per, "no live relayed connection"
        backend = max(per, key=per.get)
        victim, survivor = (
            (fe1, fe2) if backend[1] == p1 else (fe2, fe1)
        )
        reconnects0 = sum(
            metrics.counter(COUNTER_WATCH_RECONNECTS, {"reason": r})
            for r in ("error", "eof", "truncated")
        )
        victim.shutdown()
        victim.server_close()
        store.create("pods", make_pod("lb-after-kill"))
        seen = []

        def saw_new():
            ev = w.get(timeout=0.2)
            if ev is not None and ev.type != BOOKMARK:
                seen.append(ev.object.metadata.name)
            return "lb-after-kill" in seen

        assert wait_until(saw_new, 15.0), f"saw only {seen}"
        assert not w.stopped  # the consumer never observed the death
        assert (
            sum(
                metrics.counter(COUNTER_WATCH_RECONNECTS, {"reason": r})
                for r in ("error", "eof", "truncated")
            )
            > reconnects0
        )
        assert seen.count("lb-after-kill") == 1
        w.stop()
        survivor.shutdown()
        survivor.server_close()
    finally:
        client.close()
        c1.close()
        c2.close()
        lb.stop()


def test_poison_watch_stream_stops_after_bounded_resumes():
    """A stream that dies on an undecodable event at a fixed rv must NOT
    reconnect at full speed forever: _open_watch succeeds every time (the
    server is healthy), so the connect backoff never engages — the pump
    must bound consecutive zero-progress resumes, then stop the watcher
    so the consumer takes its relist path."""
    server = _ScriptedServer(close_after=1, status=200, body=b"not-json\n")
    client = RESTClient(f"http://127.0.0.1:{server.port}", timeout=5.0)
    try:
        w = client.watch("pods", from_version=0)
        assert wait_until(lambda: w.stopped, 10.0), "pump never gave up"
        watches = [r for r in server.requests if "watch=1" in r[1]]
        assert 1 < len(watches) <= 1 + _WATCH_RESUME_ATTEMPTS, (
            f"expected bounded resumes, server saw {len(watches)} opens"
        )
    finally:
        client.close()
        server.stop()


# -- follower reads -----------------------------------------------------------


class _StubFollower:
    """Deterministic follower replica for freshness-wait edges: the test
    drives applies and commit advances by hand."""

    def __init__(self):
        self.objects = {}
        self.rv = 0
        self.commit_index = 0
        self._obs = []

    def register_observer(self, obs):
        self._obs.append(obs)

    def list_kind(self, kind):
        d = self.objects.get(kind, {})
        return [copy.deepcopy(o) for o in d.values()], self.rv

    def wait_commit(self, rv, timeout=5.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.commit_index >= rv:
                return True
            time.sleep(0.01)
        return self.commit_index >= rv

    # test drivers ----------------------------------------------------------
    def apply(self, verb, kind, obj):
        self.rv += 1
        obj = copy.deepcopy(obj)
        obj.metadata.resource_version = self.rv
        d = self.objects.setdefault(kind, {})
        if verb == "delete":
            d.pop(obj.metadata.key, None)
        else:
            d[obj.metadata.key] = obj
        for o in self._obs:
            o.on_records([(self.rv, verb, kind, copy.deepcopy(obj))])
        return self.rv

    def commit(self, c):
        self.commit_index = c
        for o in self._obs:
            o.on_commit(c)


class _StubPrimary:
    def __init__(self):
        self.kind_rv = 0

    def kind_resource_version(self, kind):
        return self.kind_rv


def test_follower_read_withholds_uncommitted_events():
    follower = _StubFollower()
    primary = _StubPrimary()
    frs = FollowerReadStore(follower, primary)
    w = frs.watch("pods", from_version=0)
    follower.apply("create", "pods", make_pod("unc-1"))
    assert w.get(timeout=0.2) is None  # applied but NOT committed
    follower.commit(1)
    ev = w.get(timeout=2.0)
    assert ev is not None and ev.object.metadata.name == "unc-1"
    # the list label never runs ahead of the commit index
    follower.apply("create", "pods", make_pod("unc-2"))
    objs, rv = frs.list("pods")
    assert rv == 1 and len(objs) == 2  # state fresh, label committed


def test_follower_consistent_list_blocks_then_serves_on_commit():
    """A consistent (limit) list demanding the primary's kind rv blocks
    while the follower's commit index is behind, then serves the moment
    the commit catches up — the PR-6 wait_until_fresh seam generalized
    to the commit index."""
    follower = _StubFollower()
    primary = _StubPrimary()
    frs = FollowerReadStore(follower, primary)
    srv, port, _ = serve(store=frs, port=0, bookmark_period_s=0.5)
    client = RESTClient(f"http://127.0.0.1:{port}", timeout=20.0)
    try:
        follower.apply("create", "pods", make_pod("f-1"))
        follower.commit(1)
        follower.apply("create", "pods", make_pod("f-2"))  # rv 2 uncommitted
        primary.kind_rv = 2  # the primary has acked rv 2: clients demand it
        result = {}

        def consistent_list():
            t0 = time.monotonic()
            out = client._request("GET", client._url("pods", "") + "?limit=10")
            result["elapsed"] = time.monotonic() - t0
            result["out"] = out

        t = threading.Thread(target=consistent_list, daemon=True)
        t.start()
        time.sleep(0.4)
        assert "out" not in result, "served before the commit covered rv 2"
        follower.commit(2)
        t.join(timeout=10.0)
        assert "out" in result
        assert int(result["out"]["metadata"]["resourceVersion"]) >= 2
        names = {i["metadata"]["name"] for i in result["out"]["items"]}
        assert names == {"f-1", "f-2"}
        assert result["elapsed"] >= 0.3  # it genuinely waited
    finally:
        client.close()
        srv.shutdown()


def test_follower_consistent_list_times_out_504_with_retry_after():
    follower = _StubFollower()
    primary = _StubPrimary()
    frs = FollowerReadStore(follower, primary)
    srv, port, _ = serve(
        store=frs, port=0, bookmark_period_s=0.5, freshness_timeout_s=1.0
    )
    client = RESTClient(f"http://127.0.0.1:{port}", timeout=20.0)
    try:
        follower.apply("create", "pods", make_pod("t-1"))
        follower.commit(1)
        primary.kind_rv = 99  # demanded rv the follower will never reach
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api/v1/pods?limit=10"
        )
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=20.0)
        assert exc.value.code == 504
        assert exc.value.headers.get("Retry-After") is not None
    finally:
        client.close()
        srv.shutdown()


def test_follower_rv0_list_serves_stale_without_waiting():
    """resourceVersion=0 keeps the reference semantics on followers too:
    'give me what you have' never blocks on freshness."""
    follower = _StubFollower()
    primary = _StubPrimary()
    frs = FollowerReadStore(follower, primary)
    srv, port, _ = serve(store=frs, port=0, bookmark_period_s=0.5)
    client = RESTClient(f"http://127.0.0.1:{port}", timeout=5.0)
    try:
        follower.apply("create", "pods", make_pod("rv0-1"))
        follower.commit(1)
        primary.kind_rv = 99  # far ahead: rv=0 must not care
        out = client._request(
            "GET", client._url("pods", "") + "?resourceVersion=0"
        )
        assert [i["metadata"]["name"] for i in out["items"]] == ["rv0-1"]
    finally:
        client.close()
        srv.shutdown()


def test_follower_snapshot_reset_terminates_watchers():
    follower = _StubFollower()
    frs = FollowerReadStore(follower, _StubPrimary())
    w = frs.watch("pods", from_version=0)
    follower.apply("create", "pods", make_pod("s-1"))
    follower.commit(1)
    assert w.get(timeout=1.0) is not None
    for o in follower._obs:
        o.on_snapshot()
    assert wait_until(lambda: w.stopped, 2.0)
