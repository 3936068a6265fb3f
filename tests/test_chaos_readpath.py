"""Read-path chaos: hollow-informer storms against the watch cache + APF.

The PR-6 acceptance scenario: thousands of concurrent hollow informers
(cheap cache-fan-out clients — the read-side analogue of kubemark hollow
nodes) plus heartbeat/bind load against ONE apiserver, with the gates:

  * exactly ONE store watch per kind, no matter the client count
  * zero informer full-relists after a forced watch flap (bookmark/RV
    resume through the event window)
  * zero bind-path starvation while the read storm saturates watch-init
  * p99 watch-delivery latency measured (the 10k-client version runs
    through perf/harness.run_readpath_benchmark)

Bind-invariant accounting rides the ChaosStore ledger from
test_chaos_pipeline: every bind acked under the storm stays bound.
"""

import threading
import time

import pytest

from test_chaos_pipeline import (
    ChaosStore,
    assert_bind_invariants,
    make_pod,
    wait_until,
)

from kubernetes_tpu.api import objects as v1
from kubernetes_tpu.apiserver.auth import TokenAuthenticator
from kubernetes_tpu.apiserver.cacher import Cacher
from kubernetes_tpu.apiserver.client import AuthRESTClient
from kubernetes_tpu.apiserver.rest import serve
from kubernetes_tpu.client.informers import SharedInformer
from kubernetes_tpu.runtime.watch import BOOKMARK
from kubernetes_tpu.testing import lockgraph
from kubernetes_tpu.utils.metrics import metrics


@pytest.fixture(autouse=True, scope="module")
def lock_order_watchdog():
    """Lock-order watchdog over the read path: the per-kind cache locks
    (one watchdog node, "cacher.kind") against the store lock under the
    informer storms. A cycle = an inversion that deadlocks only under
    the right interleaving; the graph catches it even when the storm
    happens to survive (ISSUE 7's runtime companion to graftlint)."""
    lockgraph.enable(eraser=True)
    yield
    try:
        # zero CYCLES and zero empty-lockset RACES (Eraser mode, ISSUE
        # 12): the informer storms drive every watch-cache lockset
        lockgraph.assert_clean()
        # zero EDGES is legitimate (the read path never nests two named
        # locks); zero ACQUISITIONS would mean the instrumentation died
        assert lockgraph.acquire_count() > 0, (
            "watchdog observed no named-lock acquisitions: the named "
            "locks are not instrumented"
        )
        assert lockgraph.tracked_access_count() > 0, (
            "lockset sanitizer observed no tracked-attribute accesses: "
            "the watch-cache classes are not instrumented"
        )
    finally:
        lockgraph.disable()


def _relist_total(kind="pods"):
    return sum(
        metrics.counter(
            "informer_relists_total", {"kind": kind, "reason": r}
        )
        for r in ("watch-closed", "window_expired", "expired", "list-error")
    )


class HollowInformerFleet:
    """N cache-fan-out watchers drained by a small shared thread pool —
    the memory/thread shape that lets one process model 10k informers.
    A sampled subset is drained hot and records delivery latency
    (event.ts is stamped by the cache dispatch loop)."""

    def __init__(self, cacher: Cacher, kind: str, n: int, sampled: int = 32,
                 drainers: int = 4):
        rv = cacher.current_rv(kind)
        self.watchers = [
            cacher.watch(kind, from_version=rv) for _ in range(n)
        ]
        self.sampled = self.watchers[:sampled]
        self.rest = self.watchers[sampled:]
        self.latencies = []
        self.delivered = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads = []
        chunk = max(1, len(self.sampled) // drainers)
        for i in range(0, len(self.sampled), chunk):
            t = threading.Thread(
                target=self._drain_loop,
                args=(self.sampled[i : i + chunk],),
                daemon=True,
            )
            t.start()
            self._threads.append(t)

    def _drain_loop(self, watchers):
        while not self._stop.is_set():
            idle = True
            for w in watchers:
                ev = w.get(timeout=0)
                while ev is not None:
                    idle = False
                    if ev.type != BOOKMARK and ev.ts:
                        with self._lock:
                            self.latencies.append(
                                time.monotonic() - ev.ts
                            )
                            self.delivered += 1
                    ev = w.get(timeout=0)
            if idle:
                time.sleep(0.002)

    def p99_ms(self) -> float:
        with self._lock:
            lat = sorted(self.latencies)
        if not lat:
            return 0.0
        return lat[min(int(0.99 * len(lat)), len(lat) - 1)] * 1e3

    def stop(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=2.0)
        for w in self.watchers:
            w.stop()


def _storm_scenario(n_informers: int, n_events: int, sampled: int = 32):
    """Shared body for the fast and slow storm tests."""
    store = ChaosStore()
    cacher = Cacher(store, bookmark_period_s=0.5)
    try:
        store.create("pods", make_pod("seed"))
        kc = cacher.cache_for("pods")
        assert wait_until(lambda: kc.current_rv == store.resource_version, 5)

        # a handful of REAL informers ride along: they are the clients
        # whose relist behavior the flap gate asserts
        informers = [SharedInformer(cacher, "pods") for _ in range(4)]
        seen = [[] for _ in informers]
        for inf, sink in zip(informers, seen):
            inf.add_handler(on_add=lambda p, s=sink: s.append(p.metadata.name))
            inf.start()
        assert all(inf.wait_for_sync(10) for inf in informers)

        fleet = HollowInformerFleet(
            cacher, "pods", n_informers, sampled=sampled
        )
        # gate 1: one store watch for pods regardless of fan-out width
        assert store.watcher_count("pods") == 1

        # heartbeat + bind load concurrent with the event storm
        for i in range(8):
            store.create("nodes", v1.Node(metadata=v1.ObjectMeta(name=f"n{i}")))
        bind_errors = []

        def bind_load():
            for i in range(50):
                p = store.create("pods", make_pod(f"bindme-{i}"))
                b = v1.Binding(
                    pod_name=p.metadata.name,
                    pod_namespace=p.metadata.namespace,
                    pod_uid=p.metadata.uid,
                    target_node=f"n{i % 8}",
                )
                errs = store.bind_pods([b])
                if errs[0] is not None:
                    bind_errors.append(errs[0])

        binder = threading.Thread(target=bind_load, daemon=True)
        binder.start()
        for i in range(n_events):
            store.create("pods", make_pod(f"storm-{i}"))
        binder.join(timeout=60)
        assert not binder.is_alive(), "bind load starved under the read storm"
        assert not bind_errors

        total_rv = store.resource_version
        assert wait_until(lambda: kc.current_rv == total_rv, 30)
        assert wait_until(
            lambda: all(f"storm-{n_events-1}" in s for s in seen), 30
        ), "real informers never saw the end of the storm"
        p99 = fleet.p99_ms()
        assert fleet.delivered > 0

        # gate 2: forced flap — kill every informer's stream at once (the
        # thundering-herd moment). All must resume through the window:
        # ZERO full relists.
        relists0 = _relist_total()
        resumes0 = metrics.counter(
            "informer_watch_resumes_total", {"kind": "pods"}
        )
        for inf in informers:
            inf._watcher.stop()
        store.create("pods", make_pod("post-flap"))
        assert wait_until(
            lambda: all("post-flap" in s for s in seen), 30
        ), "informers never recovered from the forced flap"
        assert (
            metrics.counter(
                "informer_watch_resumes_total", {"kind": "pods"}
            )
            - resumes0
            >= len(informers)
        )
        assert _relist_total() == relists0, (
            "a forced flap must resume from the watch-cache window, "
            "never re-list"
        )
        assert store.watcher_count("pods") == 1

        # ledger: every acked bind is still bound, none applied twice
        assert_bind_invariants(store)
        fleet.stop()
        for inf in informers:
            inf.stop()
        return p99
    finally:
        cacher.stop()


def test_readpath_storm_500_one_store_watch_zero_relists():
    """Fast tier: 500 hollow informers + 4 real informers + bind and
    heartbeat-shaped write load. One store watch, zero relists after the
    forced flap, zero bind starvation. (The acceptance-scale 10k variant
    is the slow-marked test below.)"""
    p99 = _storm_scenario(n_informers=500, n_events=80)
    # sanity, not a perf gate (CI boxes swing): sampled delivery stayed
    # sub-second under the fan-out
    assert p99 < 5000, f"watch delivery p99 {p99:.1f} ms"


@pytest.mark.slow
def test_readpath_storm_10k_acceptance():
    """The acceptance-scale storm: 10 000 hollow informers. Gates are
    structural (one store watch, zero relists, zero starvation); the
    measured p99 is reported by bench.py's readpath line."""
    p99 = _storm_scenario(n_informers=10000, n_events=150, sampled=64)
    print(f"10k-informer watch-delivery p99: {p99:.2f} ms")


def test_degraded_store_cache_keeps_serving_reads_and_watches():
    """Failure-mode matrix row: store degraded (writes 503) → the cache
    keeps serving lists, replays, and watches from memory."""
    store = ChaosStore()
    cacher = Cacher(store, bookmark_period_s=0.2)
    try:
        kc = cacher.cache_for("pods")
        for i in range(5):
            store.create("pods", make_pod(f"p{i}"))
        assert wait_until(lambda: kc.current_rv == store.resource_version, 5)
        rv = store.resource_version
        store.degrade()
        # writes refuse...
        from kubernetes_tpu.runtime.consensus import DegradedWrites

        with pytest.raises(DegradedWrites):
            store.create("pods", make_pod("refused"))
        # ...reads, paginated lists, windowed replays, bookmarks all serve
        objs, lrv = cacher.list("pods")
        assert len(objs) == 5 and lrv == rv
        items, prv, tok = cacher.list_page("pods", limit=2)
        assert len(items) == 2 and prv == rv and tok
        w = cacher.watch("pods", from_version=1)
        replayed = 0
        deadline = time.time() + 2.0
        while time.time() < deadline:
            ev = w.get(timeout=0.3)
            if ev is None:
                break
            if ev.type != BOOKMARK:
                replayed += 1
        assert replayed == 4  # events 2..5 (rv 1 already seen)
        store.recover()
        store.create("pods", make_pod("after-recover"))
        assert wait_until(lambda: kc.current_rv == store.resource_version, 5)
        w.stop()
    finally:
        cacher.stop()


# -- REST + APF: the bind path survives a watch-init storm --------------------


@pytest.fixture
def apf_server():
    store = ChaosStore()
    authn = TokenAuthenticator()
    authn.add_token("node-token", "system:node:n0", ("system:nodes",))
    # NOT system:masters: the scheduler must ride the throttled system
    # level (exempt would prove nothing about isolation)
    authn.add_token("sched-token", "system:kube-scheduler", ())
    for i in range(200):
        authn.add_token(f"informer-{i}", f"hollow-informer-{i}", ())
    # a small concurrency budget makes the contention real: watch-init
    # gets ~10% of 24 seats, system its own isolated share
    srv, port, _ = serve(
        store=store,
        port=0,
        authenticator=authn,
        max_in_flight=24,
        priority_and_fairness=True,
        bookmark_period_s=0.5,
    )
    yield srv, port, store
    srv.shutdown()


@pytest.mark.slow
def test_bind_path_no_starvation_under_watch_init_storm(apf_server):
    """Cold-informer connection storm over HTTP (every watch init takes a
    watch-init APF seat) while a kubelet heartbeats and the scheduler
    binds. Gates: ZERO 429s and bounded latency on the system paths; the
    storm itself must see rejections (proof the server was saturated)."""
    srv, port, store = apf_server
    base = f"http://127.0.0.1:{port}"
    for i in range(4):
        store.create("nodes", v1.Node(metadata=v1.ObjectMeta(name=f"n{i}")))
    # a deep current state: every cold informer's rv=0 watch replays
    # ~1500 synthetic ADDED events at init, so its watch-init seat is
    # held for real encode/write work (an empty replay releases the seat
    # in microseconds and nothing would contend). Note the pods predate
    # the KindCache, so they are STATE, not window events — an rv=1
    # reconnect would just 410 against the floor without costing a seat.
    for i in range(1500):
        store.create("pods", make_pod(f"window-{i}"))
    stop = threading.Event()
    storm_429 = [0]
    storm_ok = [0]

    def informer_storm(idx: int):
        import urllib.error
        import urllib.request

        while not stop.is_set():
            # cold informer connect at rv=0: full state replay under a
            # watch-init seat, then drop (flap) and come back
            req = urllib.request.Request(
                base + "/api/v1/pods?watch=1&resourceVersion=0",
                headers={"Authorization": f"Bearer informer-{idx}"},
            )
            try:
                resp = urllib.request.urlopen(req, timeout=2)
                resp.read(4096)
                resp.close()
                storm_ok[0] += 1
            except urllib.error.HTTPError as e:
                if e.code == 429:
                    storm_429[0] += 1
            except Exception:
                pass

    threads = [
        threading.Thread(target=informer_storm, args=(i,), daemon=True)
        for i in range(24)
    ]
    for t in threads:
        t.start()
    time.sleep(0.3)  # let the storm build

    kubelet = AuthRESTClient(base, "node-token", timeout=10.0)
    scheduler = AuthRESTClient(base, "sched-token", timeout=10.0)
    heartbeat_lat = []
    bind_lat = []
    failures = []
    try:
        for i in range(30):
            t0 = time.monotonic()
            try:
                # heartbeat-shaped write: the kubelet's periodic node
                # status/lease renewal (system priority level over REST)
                def _renew(n, i=i):
                    n.metadata.annotations = dict(
                        n.metadata.annotations or {},
                        **{"heartbeat": str(i)},
                    )
                    return n

                kubelet.guaranteed_update("nodes", "", "n0", _renew)
            except Exception as e:  # a 429/503 here is the starvation bug
                failures.append(("heartbeat", e))
            heartbeat_lat.append(time.monotonic() - t0)
            p = store.create("pods", make_pod(f"storm-bind-{i}"))
            b = v1.Binding(
                pod_name=p.metadata.name,
                pod_namespace=p.metadata.namespace,
                pod_uid=p.metadata.uid,
                target_node=f"n{i % 4}",
            )
            t0 = time.monotonic()
            try:
                scheduler.bind_pod(b)
            except Exception as e:
                failures.append(("bind", e))
            bind_lat.append(time.monotonic() - t0)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5.0)

    assert not failures, f"system path starved under read storm: {failures}"
    # the HARD gate is zero rejections above; the latency bound is a
    # sanity rail only and deliberately loose — a loaded CI box pushes
    # worst-case GIL/accept latency into seconds without any APF bug
    hb_p99 = sorted(heartbeat_lat)[-1]
    bind_p99 = sorted(bind_lat)[-1]
    assert hb_p99 < 15.0, f"heartbeat worst-case {hb_p99:.2f}s under storm"
    assert bind_p99 < 15.0, f"bind worst-case {bind_p99:.2f}s under storm"
    # the storm was real: watch-init rejected at least once while system
    # traffic sailed through
    assert storm_429[0] > 0, (
        f"storm never saturated watch-init (ok={storm_ok[0]}) — "
        "the no-starvation gate proved nothing"
    )
    # every acked bind survived the storm
    assert_bind_invariants(store)
    bound = store.count("pods", lambda p: bool(p.spec.node_name))
    assert bound == 30
