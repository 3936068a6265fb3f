"""Scheduler-HA chaos: leader-elected warm standby with snapshot handoff.

The scheduler was the last single process in the stack: PR 1 made the
store survive leader death, PR 3 taught the control plane to ride out a
degraded store, PR 4 taught the data plane to heal itself — but a dead
scheduler still cost a full HBM-snapshot rebuild plus a compile storm.
These scenarios prove the warm-standby design closes that gap:

  * kill the leader MID-WAVE (binds parked assumed-but-unbound) → the
    standby adopts from store read-back and binds every in-flight pod,
    ZERO double-binds on the ChaosStore ledger, time-to-first-bind after
    the kill under one autoscaler period;
  * a paused ex-leader resuming after the standby promoted gets its late
    binds REJECTED by the leadership fence, never applied twice;
  * graceful stop releases the lease (rolling upgrade: handoff well under
    lease_duration);
  * N scheduler replicas on the shared watch cache cost ONE store watch
    per kind;
  * leader-election edge cases: expired-lease takeover, single grant per
    transition under concurrency, renew-deadline loss is fatal, degraded
    renews are counted skips that keep the holder leading, clock-jittered
    renew races never let a challenger steal a live lease.
"""

import threading
import time

import numpy as np
import pytest

from test_chaos_pipeline import (
    ChaosStore,
    _bound_count,
    assert_bind_invariants,
    make_pod,
    wait_until,
)

from kubernetes_tpu.api import objects as v1
from kubernetes_tpu.api.objects import Binding
from kubernetes_tpu.apiserver.cacher import Cacher
from kubernetes_tpu.client.apiserver import APIServer, LeaderFenced
from kubernetes_tpu.client.leaderelection import (
    LeaderElectionConfig,
    LeaderElector,
)
from kubernetes_tpu.kubelet.kubelet import NodeAgentPool
from kubernetes_tpu.runtime.consensus import DegradedWrites
from kubernetes_tpu.scheduler import KubeSchedulerConfiguration, Scheduler
from kubernetes_tpu.testing import lockgraph
from kubernetes_tpu.utils.metrics import metrics


@pytest.fixture(autouse=True, scope="module")
def lock_order_watchdog():
    """Lock-order watchdog + lockset sanitizer over the HA suite (ISSUE
    12): leader, standby, and zombie replicas share one store and one
    watch cache from different threads — exactly the concurrency the
    guarded-by contract exists for. Any lock-order cycle or any tracked
    attribute whose lockset goes empty across threads fails the suite,
    even when the interleaving happened to be benign."""
    lockgraph.enable(eraser=True)
    yield
    try:
        lockgraph.assert_clean()
        assert lockgraph.acquire_count() > 0, (
            "watchdog observed no named-lock acquisitions: the named "
            "locks are not instrumented"
        )
        assert lockgraph.tracked_access_count() > 0, (
            "lockset sanitizer observed no tracked-attribute accesses: "
            "the production classes are not instrumented"
        )
    finally:
        lockgraph.disable()

# The acceptance budget for "the standby starts binding fast": ONE
# autoscaler period. The PR-5 autoscaler's what-if simulation alone costs
# 2.2-6.6 s on the CPU backend, so a CPU
# deployment runs multi-second scan periods; 5 s is the tight end of
# that range and comfortably covers lease expiry + takeover + adoption +
# the first warm wave — but NOT a snapshot rebuild + compile storm.
AUTOSCALER_PERIOD_S = 5.0

# fast-failover lease: expiry well inside the bind budget. Invariants
# still hold: lease(1.5) > renew(1.0) > retry(0.2)*1.2
def _lease_cfg(identity: str) -> LeaderElectionConfig:
    return LeaderElectionConfig(
        identity=identity,
        lease_duration=1.5,
        renew_deadline=1.0,
        retry_period=0.2,
    )


class _Replica:
    """One scheduler replica: a Scheduler standing by + its elector,
    wired the way cmd/scheduler.py wires them (standby first, the
    election winner promotes with the fence)."""

    def __init__(self, store, cacher, identity, lease_cfg=None):
        self.identity = identity
        self.sched = Scheduler(cacher, KubeSchedulerConfiguration())
        self.sched.start_standby(identity=identity)
        self.promoted = threading.Event()
        self.deposed = threading.Event()

        def on_started():
            self.sched.promote(fence=self.elector.fence())
            self.promoted.set()

        self.elector = LeaderElector(
            store,
            lease_cfg or _lease_cfg(identity),
            on_started_leading=on_started,
            on_stopped_leading=self.deposed.set,
        )
        self._thread = threading.Thread(
            target=self.elector.run, daemon=True, name=f"elector-{identity}"
        )
        self._thread.start()

    def stop(self):
        self.elector.stop()
        self.sched.stop()

    def crash(self):
        """Leader death: no lease release, scheduling threads stopped hard
        with whatever was mid-flight left dangling in the store."""
        self.elector.crash()
        self.sched.stop()


def _cluster(n_nodes=6):
    store = ChaosStore()
    cacher = Cacher(store)
    pool = NodeAgentPool(store, housekeeping_interval=0.1)
    for i in range(n_nodes):
        pool.add_node(f"ha-{i}")
    pool.start()
    return store, cacher, pool


# -- warm-up absorber (lint-exempt; see scripts/check_slow_markers.py) --------


def test_warmup_compile_ha_absorber():
    """Absorb this process's standby/leader kernel compiles at the suite's
    shapes (6 nodes, ≤256-pod small-bucket waves + the serial variant):
    the standby pre-warm path compiles the same programs the promoted
    leader launches, so every later test in this file runs at steady
    state. Asserts liveness only."""
    store, cacher, pool = _cluster()
    w0 = metrics.counter("scheduler_ha_standby_warmups_total")
    sched = Scheduler(cacher, KubeSchedulerConfiguration())
    sched.start_standby(identity="warmup")
    try:
        assert metrics.counter("scheduler_ha_standby_warmups_total") > w0
        for i in range(30):
            store.create("pods", make_pod(f"wu-{i}"))
        sched.promote()
        assert wait_until(lambda: _bound_count(store) == 30, 60)
    finally:
        sched.stop()
        pool.stop()
        cacher.stop()


# -- scenario 1: kill the leader mid-wave; the standby adopts -----------------


@pytest.mark.slow
def test_kill_leader_mid_wave_standby_adopts_and_binds():
    """Acceptance scenario. The leader dies with a wave ASSUMED but
    unbound (its binds parked in the ride-through buffer during a store
    blip — in-memory state that dies with it). The warm standby takes the
    lease, adopts the in-flight pods from store read-back, and binds
    every one of them: zero double-binds on the ledger, first bind after
    the kill in well under one autoscaler period (no snapshot rebuild,
    no compile storm)."""
    store, cacher, pool = _cluster()
    a = _Replica(store, cacher, "ha-leader-a")
    assert wait_until(a.promoted.is_set, 15), "first replica never led"
    b = _Replica(store, cacher, "ha-standby-b")
    try:
        n1 = 30
        for i in range(n1):
            store.create("pods", make_pod(f"pre-{i}"))
        assert wait_until(lambda: _bound_count(store) == n1, 30)

        # mid-wave: the next wave's bulk bind is refused (degraded store)
        # so the leader parks the whole wave assumed-but-unbound, then DIES
        # before the buffer can ever drain
        store.fail_next_bind = "degraded"
        n2 = 30
        for i in range(n2):
            store.create("pods", make_pod(f"wave-{i}"))
        assert wait_until(lambda: a.sched._ridethrough.depth > 0, 15), (
            "leader never parked the mid-flight wave"
        )
        # NOTE: the trickling burst may split into several bind calls and
        # some wave pods can ack BEFORE the injected failure lands — the
        # invariant is the parked remainder, not an exact bound count
        bound_at_kill = _bound_count(store)
        assert bound_at_kill < n1 + n2
        adopt0 = metrics.counter(
            "scheduler_ha_adoptions_total", {"outcome": "pending"}
        )
        t_kill = time.monotonic()
        a.crash()
        store.recover()

        # the standby takes over and starts binding the adopted wave
        assert wait_until(b.promoted.is_set, 15), "standby never promoted"
        assert wait_until(lambda: _bound_count(store) > bound_at_kill, 15), (
            "no bind ever landed after the kill"
        )
        t_first = time.monotonic() - t_kill
        assert t_first < AUTOSCALER_PERIOD_S, (
            f"time-to-first-bind after the kill {t_first:.2f}s >= one "
            f"autoscaler period ({AUTOSCALER_PERIOD_S}s)"
        )
        assert wait_until(lambda: _bound_count(store) == n1 + n2, 30), (
            f"only {_bound_count(store)}/{n1 + n2} bound after failover"
        )
        print(
            f"\n[chaos-ha] leader killed mid-wave: standby adopted and "
            f"first-bound in {t_first:.2f}s (< {AUTOSCALER_PERIOD_S}s), "
            f"all {n1 + n2} pods bound",
            flush=True,
        )
        # the adoption pass actually saw the in-flight wave
        assert (
            metrics.counter(
                "scheduler_ha_adoptions_total", {"outcome": "pending"}
            )
            > adopt0
        ), "promotion ran no adoption pass over the in-flight wave"
        # THE ledger gate: every acked bind intact, no bind applied twice
        assert_bind_invariants(store)
        assert all(c == 1 for c in store.applied_binds.values())
    finally:
        b.stop()
        a.stop()
        pool.stop()
        cacher.stop()


# -- scenario 2: zombie ex-leader's late binds are fenced ---------------------


@pytest.mark.slow
def test_zombie_ex_leader_late_binds_are_fenced():
    """The leader PAUSES (stops renewing — GC pause / partition / SIGSTOP)
    but its scheduling threads keep running. The standby takes the
    expired lease. When the zombie's binds arrive they carry the stale
    fencing token and the store rejects them — racing the new leader over
    a burst of pods never applies a bind twice."""
    store, cacher, pool = _cluster()
    a = _Replica(store, cacher, "zombie-a")
    assert wait_until(a.promoted.is_set, 15)
    b = _Replica(store, cacher, "fresh-b")
    try:
        for i in range(10):
            store.create("pods", make_pod(f"pre-z-{i}"))
        assert wait_until(lambda: _bound_count(store) == 10, 30)

        # pause: the elector stops renewing WITHOUT releasing, but the
        # zombie's scheduler keeps running (no on_stopped teardown)
        a.elector.crash()
        assert wait_until(b.promoted.is_set, 15), "standby never took over"

        # deterministic fence check on the zombie's own bind funnel: a pod
        # no profile owns (so neither scheduler races us for it)
        zp = v1.Pod(
            metadata=v1.ObjectMeta(name="zombie-target"),
            spec=v1.PodSpec(
                scheduler_name="nobody",
                containers=[v1.Container(requests={"cpu": "100m"})],
            ),
        )
        zp = store.create("pods", zp)
        with pytest.raises(LeaderFenced):
            a.sched._bind_pods_fenced(
                [
                    Binding(
                        pod_name="zombie-target",
                        pod_namespace="default",
                        pod_uid=zp.metadata.uid,
                        target_node="ha-0",
                    )
                ]
            )
        assert not store.get("pods", "default", "zombie-target").spec.node_name

        # the race: both the zombie and the new leader see this burst.
        # Fencing (plus the store's bound/uid checks) makes a double-apply
        # structurally impossible; the new leader binds everything.
        for i in range(20):
            store.create("pods", make_pod(f"race-{i}"))
        assert wait_until(
            lambda: store.count(
                "pods",
                lambda p: p.metadata.name.startswith("race-")
                and bool(p.spec.node_name),
            )
            == 20,
            30,
        ), "racing burst never fully bound after the takeover"
        assert_bind_invariants(store)
        assert all(c == 1 for c in store.applied_binds.values()), (
            "a zombie bind applied twice"
        )
    finally:
        b.stop()
        a.stop()
        pool.stop()
        cacher.stop()


# -- scenario 3: graceful stop releases the lease (rolling upgrade) ------------


@pytest.mark.slow
def test_graceful_stop_releases_lease_fast_handoff():
    """stop() clears holder_identity and bumps lease_transitions
    (ReleaseOnCancel), so the standby promotes in a few retry periods —
    NOT after waiting out lease_duration. The zero-downtime rolling
    upgrade path."""
    store, cacher, pool = _cluster()
    # a deliberately LONG lease: if the handoff were expiry-driven it
    # could not beat the assertion below
    long_lease = LeaderElectionConfig(
        identity="old", lease_duration=8.0, renew_deadline=5.0,
        retry_period=0.3,
    )
    a = _Replica(store, cacher, "old", lease_cfg=long_lease)
    assert wait_until(a.promoted.is_set, 15)
    new_lease = LeaderElectionConfig(
        identity="new", lease_duration=8.0, renew_deadline=5.0,
        retry_period=0.3,
    )
    b = _Replica(store, cacher, "new", lease_cfg=new_lease)
    try:
        rel0 = metrics.counter("leader_election_releases_total")
        t0 = time.monotonic()
        a.stop()  # graceful: releases the lease
        assert wait_until(b.promoted.is_set, 15), "standby never promoted"
        elapsed = time.monotonic() - t0
        assert metrics.counter("leader_election_releases_total") > rel0
        assert elapsed < 3.0, (
            f"handoff took {elapsed:.2f}s — the release was not honored "
            f"(lease_duration is 8s)"
        )
        lease = store.get("leases", "kube-system", "kube-scheduler")
        assert lease.holder_identity == "new"
        # new leader schedules normally
        for i in range(10):
            store.create("pods", make_pod(f"rolled-{i}"))
        assert wait_until(lambda: _bound_count(store) == 10, 30)
        assert_bind_invariants(store)
    finally:
        b.stop()
        a.stop()
        pool.stop()
        cacher.stop()


# -- scenario 4: standby death leaves the leader untouched --------------------


@pytest.mark.slow
def test_standby_killed_leader_unaffected():
    store, cacher, pool = _cluster()
    a = _Replica(store, cacher, "solo-leader")
    assert wait_until(a.promoted.is_set, 15)
    b = _Replica(store, cacher, "doomed-standby")
    try:
        b.crash()
        for i in range(15):
            store.create("pods", make_pod(f"after-sb-{i}"))
        assert wait_until(lambda: _bound_count(store) == 15, 30)
        assert a.elector.is_leader and not b.promoted.is_set()
        assert_bind_invariants(store)
    finally:
        a.stop()
        b.stop()
        cacher.stop()
        pool.stop()
        cacher.stop()


# -- scenario 5: N replicas, ONE store watch per kind -------------------------


def test_ha_replicas_share_one_store_watch_per_kind():
    """The standby's informer stream rides the shared watch cache
    (ROADMAP item-2 follow-up): leader + standby together add exactly ONE
    store watch per kind — the Cacher's — however many replicas stand by."""
    store = ChaosStore()
    cacher = Cacher(store)
    for i in range(3):
        store.create(
            "nodes",
            v1.Node(
                metadata=v1.ObjectMeta(name=f"w-{i}"),
                status=v1.NodeStatus(
                    capacity={"cpu": "8", "memory": "16Gi", "pods": "110"},
                    allocatable={"cpu": "8", "memory": "16Gi", "pods": "110"},
                ),
            ),
        )
    base = {k: store.watcher_count(k) for k in ("pods", "nodes", "services")}
    a = Scheduler(cacher, KubeSchedulerConfiguration())
    b = Scheduler(cacher, KubeSchedulerConfiguration())
    try:
        a.start_standby(identity="watch-a")
        b.start_standby(identity="watch-b")
        for kind in ("pods", "nodes", "services"):
            added = store.watcher_count(kind) - base[kind]
            assert added == 1, (
                f"{kind}: {added} store watches for 2 replicas — informers "
                f"are not riding the shared cache"
            )
            # both replicas really are tailing that one watch
            assert cacher.cache_for(kind).fanout_clients() >= 2
    finally:
        a.stop()
        b.stop()
        cacher.stop()


# -- cmd wiring: run() with election = standby → promote ----------------------


def test_cmd_run_with_election_standby_promotes_and_binds():
    """cmd/scheduler.run with leader election configured starts the
    process as a warm standby behind a shared Cacher and promotes on the
    (instant) first-replica win; the SIGUSR2 dump carries the HA
    section."""
    from kubernetes_tpu.cmd import scheduler as cmd_scheduler
    from kubernetes_tpu.scheduler.cache.debugger import CacheDebugger

    store = ChaosStore()
    pool = NodeAgentPool(store, housekeeping_interval=0.1)
    for i in range(4):
        pool.add_node(f"cmd-{i}")
    pool.start()
    cfg = KubeSchedulerConfiguration()
    cfg.leader_election = _lease_cfg("cmd-replica-0")
    sched = cmd_scheduler.run(
        server=store, config=cfg, healthz_port=0, block=False
    )
    try:
        assert type(sched.server).__name__ == "Cacher"
        assert wait_until(
            lambda: sched._elector.is_leader and sched._sched_thread is not None,
            15,
        ), "run() never promoted the first replica"
        assert sched._bind_fence is not None, "promotion armed no fence"
        for i in range(10):
            store.create("pods", make_pod(f"cmd-p-{i}"))
        assert wait_until(lambda: _bound_count(store) == 10, 30)
        dump = CacheDebugger(sched).dump()
        assert "scheduler-HA / leader-election state" in dump
        assert "scheduler_ha_role" in dump
        assert_bind_invariants(store)
    finally:
        sched._elector.stop()
        sched.stop()  # also tears down the run()-owned Cacher
        pool.stop()


# -- leader-election edge cases (fake clocks, no scheduler) -------------------


def _edge_cfg(identity, **kw):
    kw.setdefault("lease_duration", 3.0)
    kw.setdefault("renew_deadline", 2.0)
    kw.setdefault("retry_period", 0.05)
    return LeaderElectionConfig(identity=identity, **kw)


def test_expired_lease_takeover_bumps_transitions_once():
    s = APIServer()
    now = [0.0]
    clock = lambda: now[0]
    e1 = LeaderElector(s, _edge_cfg("one"), lambda: None, clock=clock)
    e2 = LeaderElector(s, _edge_cfg("two"), lambda: None, clock=clock)
    assert e1._try_acquire_or_renew()
    assert s.get("leases", "kube-system", "kube-scheduler").lease_transitions == 0
    assert not e2._try_acquire_or_renew(), "takeover of a live lease"
    now[0] += 10.0  # past lease_duration: expired
    assert e2._try_acquire_or_renew()
    lease = s.get("leases", "kube-system", "kube-scheduler")
    assert lease.holder_identity == "two"
    assert lease.lease_transitions == 1, "takeover must bump exactly once"
    # the old holder's next renew fails (its fence is stale too)
    assert not e1._try_acquire_or_renew()


def test_concurrent_candidates_single_grant_per_transition():
    """Two (or N) candidates racing an expired lease: optimistic
    concurrency on the lease record guarantees exactly ONE grant — split
    leadership is structurally impossible."""
    s = APIServer()
    now = [0.0]
    clock = lambda: now[0]
    seed = LeaderElector(s, _edge_cfg("seed"), lambda: None, clock=clock)
    assert seed._try_acquire_or_renew()
    now[0] += 10.0  # expire it
    n = 8
    electors = [
        LeaderElector(s, _edge_cfg(f"cand-{i}"), lambda: None, clock=clock)
        for i in range(n)
    ]
    results = [None] * n
    barrier = threading.Barrier(n)

    def race(i):
        barrier.wait()
        results[i] = electors[i]._try_acquire_or_renew()

    threads = [threading.Thread(target=race, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(5.0)
    assert sum(1 for r in results if r) == 1, f"grants: {results}"
    lease = s.get("leases", "kube-system", "kube-scheduler")
    assert lease.lease_transitions == 1, (
        "one transition may grant at most once"
    )
    assert lease.holder_identity.startswith("cand-")


def test_same_identity_reacquire_after_expiry_mints_fresh_fence():
    """A replacement process reusing a STATIC identity (pod name via
    --leader-elect-identity) that re-acquires the expired lease is a NEW
    grant: transitions must bump so the paused old incarnation's fence
    token goes stale — otherwise its late binds would pass the zombie
    fence unchallenged."""
    s = APIServer()
    now = [0.0]
    clock = lambda: now[0]
    old = LeaderElector(s, _edge_cfg("static-id"), lambda: None, clock=clock)
    assert old._try_acquire_or_renew()
    stale_fence = old.fence()
    # the old incarnation pauses; its lease expires; a replacement with
    # the SAME identity acquires
    now[0] += 10.0
    new = LeaderElector(s, _edge_cfg("static-id"), lambda: None, clock=clock)
    assert new._try_acquire_or_renew()
    lease = s.get("leases", "kube-system", "kube-scheduler")
    assert lease.lease_transitions == 1, (
        "same-identity re-acquire after expiry must mint a new grant"
    )
    assert new.fence().transitions == 1
    # the zombie's token no longer validates
    s.create(
        "nodes",
        v1.Node(
            metadata=v1.ObjectMeta(name="fz-1"),
            status=v1.NodeStatus(
                capacity={"cpu": "8", "memory": "16Gi", "pods": "110"},
                allocatable={"cpu": "8", "memory": "16Gi", "pods": "110"},
            ),
        ),
    )
    p = s.create("pods", make_pod("fz-pod"))
    binding = Binding(
        pod_name="fz-pod", pod_namespace="default",
        pod_uid=p.metadata.uid, target_node="fz-1",
    )
    with pytest.raises(LeaderFenced):
        s.bind_pods([binding], fence=stale_fence)
    assert s.bind_pods([binding], fence=new.fence()) == [None]


def test_renew_deadline_loss_is_fatal():
    """A store degraded for longer than renew_deadline deposes the leader
    (on_stopped fires) — exactly the reference's fatal-loss contract —
    while every refused renew is a counted skip, not an exception."""
    store = ChaosStore()
    stopped = threading.Event()
    cfg = LeaderElectionConfig(
        identity="fatal", lease_duration=1.2, renew_deadline=0.8,
        retry_period=0.15,
    )
    el = LeaderElector(
        store, cfg, on_started_leading=lambda: None,
        on_stopped_leading=stopped.set,
    )
    t = threading.Thread(target=el.run, daemon=True)
    t.start()
    assert wait_until(lambda: el.is_leader, 5)
    skips0 = metrics.counter("leader_election_degraded_renew_skips_total")
    store.degrade()
    assert stopped.wait(5.0), "renew-deadline loss never deposed the leader"
    assert not el.is_leader
    assert (
        metrics.counter("leader_election_degraded_renew_skips_total") > skips0
    ), "degraded renews were not counted as skips"
    store.recover()


def test_degraded_renew_within_deadline_keeps_leading():
    """A degraded-store window SHORTER than renew_deadline must not cost
    leadership: refused renews are counted skips and the next healthy
    renew re-arms the deadline (PR-3 ride-through discipline applied to
    the lease path)."""
    store = ChaosStore()
    stopped = threading.Event()
    cfg = LeaderElectionConfig(
        identity="rider", lease_duration=3.0, renew_deadline=2.0,
        retry_period=0.1,
    )
    el = LeaderElector(
        store, cfg, on_started_leading=lambda: None,
        on_stopped_leading=stopped.set,
    )
    t = threading.Thread(target=el.run, daemon=True)
    t.start()
    try:
        assert wait_until(lambda: el.is_leader, 5)
        skips0 = metrics.counter("leader_election_degraded_renew_skips_total")
        store.degrade()
        time.sleep(0.5)  # several refused renews, well inside the deadline
        store.recover()
        assert (
            metrics.counter("leader_election_degraded_renew_skips_total")
            > skips0
        )
        time.sleep(0.4)  # a healthy renew lands
        assert el.is_leader, "a sub-deadline outage deposed the leader"
        assert not stopped.is_set()
    finally:
        el.stop()
        t.join(5.0)


def test_clock_jittered_renew_races_never_steal_a_live_lease():
    """The holder renews at jittered intervals (always inside
    lease_duration); a challenger probing after every renew must never
    acquire. Once renewals stop and the lease ages out, the challenger
    takes over with exactly one transition bump."""
    import random

    s = APIServer()
    now = [100.0]
    clock = lambda: now[0]
    rng = random.Random(42)
    holder = LeaderElector(s, _edge_cfg("holder"), lambda: None, clock=clock)
    chall = LeaderElector(s, _edge_cfg("chall"), lambda: None, clock=clock)
    assert holder._try_acquire_or_renew()
    for _ in range(40):
        # jittered renewal gap, always < lease_duration (3.0)
        now[0] += rng.uniform(0.2, 2.8)
        assert not chall._try_acquire_or_renew(), (
            f"challenger stole a live lease at t={now[0]:.2f}"
        )
        assert holder._try_acquire_or_renew(), "holder failed to renew"
    assert s.get("leases", "kube-system", "kube-scheduler").lease_transitions == 0
    # holder goes silent: the challenger wins after expiry, once
    now[0] += 3.5
    assert chall._try_acquire_or_renew()
    lease = s.get("leases", "kube-system", "kube-scheduler")
    assert lease.holder_identity == "chall"
    assert lease.lease_transitions == 1


# -- scenario: the plugin-bearing per-pod bind path is fenced too --------------


def test_plugin_per_pod_bind_path_is_fenced():
    """ISSUE-10 acceptance: the plugin-bearing per-pod path (DefaultBinder
    through the framework's bind surface, and the async binding cycle
    around it) funnels through the same fence-attaching seam as batch
    binds — a deposed replica's per-pod bind raises LeaderFenced, the
    placement is dropped (never applied, never requeued), and the fenced
    counter carries the transport label."""
    from kubernetes_tpu.scheduler.framework.interface import CycleState
    from kubernetes_tpu.scheduler.queue.scheduling_queue import QueuedPodInfo

    store, cacher, pool = _cluster()
    a = _Replica(store, cacher, "plugin-zombie-a")
    assert wait_until(a.promoted.is_set, 15)
    b = _Replica(store, cacher, "plugin-fresh-b")
    try:
        # depose a: pause its elector (no release), b takes the lease
        a.elector.crash()
        assert wait_until(b.promoted.is_set, 15), "standby never took over"

        zp = v1.Pod(
            metadata=v1.ObjectMeta(name="plugin-zombie-target"),
            spec=v1.PodSpec(
                # unsatisfiable selector: neither live scheduler can PLACE
                # it (stays pending), but the default profile still owns
                # it — the direct bind writes below target ha-0 explicitly
                node_selector={"no-such-label": "nowhere"},
                containers=[v1.Container(requests={"cpu": "100m"})],
            ),
        )
        zp = store.create("pods", zp)

        # (1) DefaultBinder through the framework context's bind surface:
        # the plugin's write funnels into _bind_pods_fenced and the store
        # rejects it with the zombie's stale token
        prof = a.sched.profiles.for_pod(zp)
        with pytest.raises(LeaderFenced):
            prof.framework.run_bind_plugins(CycleState(), zp, "ha-0")
        assert not store.get(
            "pods", "default", "plugin-zombie-target"
        ).spec.node_name, "a fenced plugin bind reached the store"

        # (2) the whole async binding cycle: LeaderFenced is handled (not
        # an unhandled thread exception), the placement dropped and
        # counted under the transport label
        before = metrics.dump().get(
            "scheduler_ha_fenced_binds_total{'path': 'local'}", 0.0
        )
        a.sched.cache.assume_pod(zp, "ha-0", device_synced=False)
        pi = QueuedPodInfo(pod=zp)
        a.sched._bind_async(pi, "ha-0", CycleState(), time.monotonic())
        assert not store.get(
            "pods", "default", "plugin-zombie-target"
        ).spec.node_name
        after = metrics.dump().get(
            "scheduler_ha_fenced_binds_total{'path': 'local'}", 0.0
        )
        assert after == before + 1, (before, after)

        # (3) the extender pre-check seam rejects a deposed replica
        with pytest.raises(LeaderFenced):
            a.sched._check_fence_live()

        assert_bind_invariants(store)
    finally:
        b.stop()
        a.stop()
        pool.stop()
        cacher.stop()


# -- scenario: failover adopts the persisted tuned score policy ---------------


@pytest.mark.slow
def test_failover_adopts_persisted_score_policy():
    """The policy gym persists its promoted vector as the singleton
    ScorePolicy object precisely so a promotion survives its promoter.
    Regression: a tuned vector is in the store; replica A wins the
    election and adopts it at promote(); A crashes; the standby that
    takes over MUST come up running the tuned vector — NOT revert to
    ``default``, which would silently undo the promotion on every
    failover. Adoption is read-only on the store object (the promotions
    ledger must not move)."""
    from kubernetes_tpu.ops.lattice import (
        DEFAULT_WEIGHTS,
        SC_COST,
        WEIGHT_PROFILES,
    )
    from kubernetes_tpu.tuner import ACTIVE_POLICY_NAME, persist_active_policy

    store, cacher, pool = _cluster(n_nodes=2)
    vec = DEFAULT_WEIGHTS.copy()
    vec[SC_COST] = 21.0
    assert persist_active_policy(store, "t-ha-tuned", vec, identity="gym")
    a = b = None
    try:
        a = _Replica(store, cacher, "adopt-a")
        assert a.promoted.wait(20), "replica A never won the election"
        assert a.sched._score_policy_name == "t-ha-tuned"
        assert np.allclose(np.asarray(a.sched._weights), vec)

        b = _Replica(store, cacher, "adopt-b")
        adopted0 = metrics.dump().get(
            "tuner_policy_adoptions_total{'outcome': 'adopted'}", 0.0
        )
        a.crash()
        assert b.promoted.wait(30), "standby never took over the lease"

        # the failover winner runs the tuned vector, not `default`
        assert b.sched._score_policy_name == "t-ha-tuned"
        assert np.allclose(np.asarray(b.sched._weights), vec)
        adopted1 = metrics.dump().get(
            "tuner_policy_adoptions_total{'outcome': 'adopted'}", 0.0
        )
        assert adopted1 >= adopted0 + 1, (adopted0, adopted1)

        # adoption reads, never writes: the persisted object is untouched
        obj = store.get("scorepolicies", "", ACTIVE_POLICY_NAME)
        assert obj.policy_name == "t-ha-tuned"
        assert int(obj.promotions) == 1
        assert [float(x) for x in obj.weights] == [float(x) for x in vec]
        assert_bind_invariants(store)
    finally:
        if b is not None:
            b.stop()
        if a is not None:
            a.stop()
        pool.stop()
        cacher.stop()
        WEIGHT_PROFILES.pop("t-ha-tuned", None)
