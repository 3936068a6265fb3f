"""Data-plane chaos: snapshot corruption, poisoned kernel outputs, and
TPU device loss — the detect → quarantine → repair → resume discipline of
scheduler/antientropy.py, the kernel-output guards (ops/lattice.py +
scheduler.py), and the device-loss ride-through (parallel/sharded.py).

The control-plane chaos suites (test_chaos_pipeline.py) prove the
scheduler rides out a lying STORE; these prove it rides out a lying
DEVICE. Shared invariant ledger: zero acked-bind loss, zero double-binds
(ChaosStore), plus the data-plane additions — zero wrong placements (no
node oversubscribed by scheduler-placed pods) and zero leaked assumes.

Fault injection is deterministic (kubernetes_tpu/testing/device_faults.py):
counter-indexed launch/readback failures and output corruption, never
random.
"""

import time

import jax
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
from kubernetes_tpu.api.resources import CPU
from kubernetes_tpu.api.selectors import selector_from_match_labels
from kubernetes_tpu.kubelet.kubelet import NodeAgentPool, make_node_object
from kubernetes_tpu.ops.encoding import (
    RES_CPU,
    RETIRE_STALL_AFTER_S,
    SnapshotEncoder,
)
from kubernetes_tpu.scheduler import KubeSchedulerConfiguration, Scheduler
from kubernetes_tpu.scheduler.antientropy import SnapshotAntiEntropy
from kubernetes_tpu.scheduler.cache.cache import SchedulerCache
from kubernetes_tpu.testing.device_faults import (
    DeviceFaultInjector,
    corrupt_device_rows,
)
from kubernetes_tpu.testing import lockgraph
from kubernetes_tpu.utils.metrics import metrics


@pytest.fixture(autouse=True, scope="module")
def lock_order_watchdog():
    """Record the acquisition-order graph of the named production locks
    (store / scheduler.cache / encoder.gen_lock) across the whole
    suite and fail on any cycle: a lock-order inversion deadlocks only
    under the right interleaving, so the run SUCCEEDING is no evidence —
    the graph is (ISSUE 7's runtime companion to graftlint).

    Eraser mode rides along (ISSUE 12): every tracked shared attribute
    of the cache/encoder/store/queue records the intersection of named
    locks held across threads, and an intersection going empty — an
    access pattern no lock protects — fails the suite the same way a
    cycle does, even when the interleaving happened to be benign."""
    lockgraph.enable(eraser=True)
    yield
    try:
        lockgraph.assert_clean()
        assert lockgraph.edge_count() > 0, (
            "watchdog recorded no lock-order edges: the data-plane suite "
            "must exercise nested cache-lock -> gen-lock acquisitions"
        )
        assert lockgraph.tracked_access_count() > 0, (
            "lockset sanitizer observed no tracked-attribute accesses: "
            "the production classes are not instrumented"
        )
    finally:
        lockgraph.disable()


def _cfg(**overrides):
    kw = dict(
        pod_initial_backoff_seconds=0.2,
        pod_max_backoff_seconds=2.0,
        antientropy_period_s=0.15,
        antientropy_sample_rows=256,
    )
    kw.update(overrides)
    return KubeSchedulerConfiguration(**kw)


def _no_oversubscription(store, cpu_capacity_m: int):
    """Zero wrong placements: no node's bound-pod cpu requests exceed its
    allocatable."""
    pods, _ = store.list("pods")
    per_node = {}
    for p in pods:
        if p.spec.node_name and p.metadata.deletion_timestamp is None:
            req = v1.compute_pod_resource_request(p).get(CPU, 0)
            per_node[p.spec.node_name] = per_node.get(p.spec.node_name, 0) + req
    over = {n: r for n, r in per_node.items() if r > cpu_capacity_m}
    assert not over, f"oversubscribed nodes (wrong placements): {over}"


def _no_leaked_assumes(sched, timeout=10.0):
    # assumed_keys() reads under the cache lock (the sanitizer holds
    # test code to the guarded-by contract) at O(assumed) per poll
    assert wait_until(
        lambda: not sched.cache.assumed_keys(), timeout
    ), f"leaked assumes: {sched.cache.assumed_keys()}"


# -- scenario 1: snapshot corruption repaired, zero wrong placements ----------


@pytest.mark.slow  # full fill + audit periods + negative-bind soak hovers
# at the tier-1 lint threshold (4-8s depending on audit/wave interleaving);
# still runs in `make chaos` / `make chaos-device` (no marker filter)
def test_snapshot_corruption_repaired_within_one_audit_period():
    """Acceptance scenario. Device rows are corrupted to UNDER-report
    occupancy on a full cluster (the lie that would make the kernel
    overcommit). The anti-entropy auditor detects the drift within one
    period, repairs by targeted re-scatter in the same pass, and pods
    created after the repair cannot land on the lying rows — zero wrong
    placements."""
    store = ChaosStore()
    pool = NodeAgentPool(store, housekeeping_interval=0.1)
    for i in range(4):
        pool.add_node(f"cn-{i}", cpu="2")
    sched = Scheduler(store, _cfg())
    pool.start()
    sched.start()
    try:
        # fill the cluster exactly: 8 x 1-cpu pods on 4 x 2-cpu nodes
        for i in range(8):
            store.create("pods", make_pod(f"fill-{i}", cpu="1"))
        assert wait_until(lambda: _bound_count(store) == 8, 30)
        assert sched.wait_for_idle(20)
        _no_leaked_assumes(sched)

        drift0 = metrics.counter(
            "snapshot_drift_rows_total", {"column": "requested"}
        )
        passes0 = metrics.counter("snapshot_audit_passes_total")
        enc = sched.cache.encoder
        with sched.cache.lock:
            rows = [r for r, nm in enumerate(enc.row_names) if nm]
            corrupt_device_rows(
                enc, rows, field="requested", mutate=np.zeros_like
            )
        # detected AND repaired within one audit period: the pass that
        # sees the drift re-scatters it before returning
        assert wait_until(
            lambda: metrics.counter(
                "snapshot_drift_rows_total", {"column": "requested"}
            )
            > drift0,
            10,
        ), "auditor never detected the corrupted rows"

        def device_matches_masters():
            with sched.cache.lock:
                if enc._device is None or enc.has_pending_updates:
                    return False
                dev = np.asarray(jax.device_get(enc._device.requested))
                return np.array_equal(dev, enc.m_req)

        assert wait_until(device_matches_masters, 10), (
            "device never converged back to the host masters"
        )
        # the lie is gone: pods that would fit ONLY on the corrupted
        # (emptier-looking) rows must not place — the cluster is full
        for i in range(4):
            store.create("pods", make_pod(f"late-{i}", cpu="1"))
        time.sleep(1.0)
        assert _bound_count(store) == 8, "pod placed on a full node"
        _no_oversubscription(store, cpu_capacity_m=2000)
        assert_bind_invariants(store)
        # the repair pipeline is still healthy for legitimate work
        assert (
            metrics.counter("snapshot_audit_passes_total") > passes0
        )
    finally:
        sched.stop()
        pool.stop()


# -- scenario 2/3: poisoned kernel outputs quarantine the batch ---------------


@pytest.mark.parametrize(
    "kind,reason",
    [("nan", "nonfinite_score"), ("wild", "row_out_of_range")],
)
def test_poisoned_kernel_output_quarantines_batch_zero_pod_loss(kind, reason):
    """A NaN score (or an out-of-range chosen row) in the first wave's
    read-back trips the output guard: the whole batch quarantines to the
    host fallback path, the snapshot rebuilds, and every pod still binds
    exactly once — zero pod loss, zero wrong placements."""
    store = ChaosStore()
    pool = NodeAgentPool(store, housekeeping_interval=0.1)
    for i in range(6):
        pool.add_node(f"gn-{i}")
    n = 30
    for i in range(n):
        store.create("pods", make_pod(f"pz-{i}"))
    trips0 = metrics.counter("kernel_guard_trips_total", {"reason": reason})
    sched = Scheduler(store, _cfg())
    inj = DeviceFaultInjector(
        nan_scores_on_readbacks={0} if kind == "nan" else (),
        wild_rows_on_readbacks={0} if kind == "wild" else (),
    ).install(sched)
    pool.start()
    sched.start()
    try:
        assert wait_until(lambda: _bound_count(store) == n, 30), (
            f"only {_bound_count(store)}/{n} bound after guard quarantine"
        )
        # split-phase: the NaN rides the TRAILING bulk readback, validated
        # off the bind path — the trip can land just after the last bind
        assert wait_until(
            lambda: metrics.counter(
                "kernel_guard_trips_total", {"reason": reason}
            )
            > trips0,
            5,
        ), "guard never tripped on the poisoned readback"
        assert inj.injected, "injector never fired"
        _no_leaked_assumes(sched)
        _no_oversubscription(store, cpu_capacity_m=4000)
        assert_bind_invariants(store)
    finally:
        sched.stop()
        pool.stop()
        inj.uninstall()


# -- scenario 4: device killed mid-wave — ride-through to host path ----------


def test_device_killed_mid_wave_rides_through_to_host_path():
    """Acceptance scenario. Every wave launch dies with a device-loss
    error (the chip is gone). Bounded retries fail, the loss latch trips,
    and the scheduler degrades to the host path: every wave pod ends
    bound or back in the queue — no leaked assumes, zero pod loss."""
    store = ChaosStore()
    pool = NodeAgentPool(store, housekeeping_interval=0.1)
    for i in range(6):
        pool.add_node(f"dn-{i}")
    n = 24
    for i in range(n):
        store.create("pods", make_pod(f"dl-{i}"))
    sched = Scheduler(
        store,
        _cfg(device_retry_attempts=1, device_loss_disable_after=2),
    )
    inj = DeviceFaultInjector(fail_all_launches=True).install(sched)
    pool.start()
    sched.start()
    try:
        assert wait_until(lambda: _bound_count(store) == n, 40), (
            f"only {_bound_count(store)}/{n} bound after device loss"
        )
        assert sched._device_down, "device-down latch never tripped"
        assert metrics.gauge("scheduler_device_down") == 1.0
        assert metrics.counter("scheduler_device_loss_total", {"stage": "launch"}) >= 1
        _no_leaked_assumes(sched)
        assert_bind_invariants(store)
    finally:
        sched.stop()
        pool.stop()
        inj.uninstall()


def test_transient_readback_loss_retries_and_recovers():
    """One readback dies (a transient blip); the bounded jittered retry gets
    the same results on the second attempt — no quarantine, no device
    down, everything binds through the device path."""
    store = ChaosStore()
    pool = NodeAgentPool(store, housekeeping_interval=0.1)
    for i in range(6):
        pool.add_node(f"tn-{i}")
    n = 20
    for i in range(n):
        store.create("pods", make_pod(f"tr-{i}"))
    r0 = metrics.counter(
        "scheduler_device_retries_total", {"stage": "readback"}
    )
    sched = Scheduler(store, _cfg())
    inj = DeviceFaultInjector(fail_readbacks={0}).install(sched)
    pool.start()
    sched.start()
    try:
        assert wait_until(lambda: _bound_count(store) == n, 30)
        assert (
            metrics.counter(
                "scheduler_device_retries_total", {"stage": "readback"}
            )
            > r0
        ), "retry path never exercised"
        assert not sched._device_down
        _no_leaked_assumes(sched)
        assert_bind_invariants(store)
    finally:
        sched.stop()
        pool.stop()
        inj.uninstall()


@pytest.mark.slow
def test_partial_device_loss_shrinks_mesh_and_reshards():
    """Half the mesh dies: the ride-through probes survivors, shrinks the
    mesh to the largest power-of-two prefix, re-shards the snapshot, and
    the next wave schedules on the smaller mesh — zero pod loss."""
    store = ChaosStore()
    pool = NodeAgentPool(store, housekeeping_interval=0.1)
    for i in range(6):
        pool.add_node(f"mn-{i}")
    n = 16
    for i in range(n):
        store.create("pods", make_pod(f"ms-{i}"))
    shrinks0 = metrics.counter("scheduler_mesh_shrinks_total")
    sched = Scheduler(store, _cfg(device_retry_attempts=0))
    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device (virtual 8-chip) harness")
    # 4 of the 8 virtual chips "die": the probe is the injectable seam
    alive = {d.id for d in jax.devices()[:4]}
    sched._device_probe = lambda device: device is not None and device.id in alive
    inj = DeviceFaultInjector(fail_launches={0}).install(sched)
    pool.start()
    sched.start()
    try:
        assert wait_until(lambda: _bound_count(store) == n, 40), (
            f"only {_bound_count(store)}/{n} bound after mesh shrink"
        )
        assert metrics.counter("scheduler_mesh_shrinks_total") > shrinks0
        assert sched._mesh is not None
        assert len(list(sched._mesh.devices.flat)) == 4
        assert not sched._device_down
        _no_leaked_assumes(sched)
        assert_bind_invariants(store)
    finally:
        sched.stop()
        pool.stop()
        inj.uninstall()


def test_serial_device_path_rides_through_device_loss():
    """use_wave=False (the oracle-exact serial path): a device loss on
    the serial batch kernel must get the same ride-through as the wave
    path — classified, counted (`stage=serial`), retried, and the batch
    quarantined to the host path — instead of parking the batch in the
    unschedulable queue against a dead device forever."""
    store = ChaosStore()
    pool = NodeAgentPool(store, housekeeping_interval=0.1)
    for i in range(4):
        pool.add_node(f"sn-{i}")
    n = 12
    for i in range(n):
        store.create("pods", make_pod(f"sp-{i}"))
    losses0 = metrics.counter(
        "scheduler_device_loss_total", {"stage": "serial"}
    )
    sched = Scheduler(store, _cfg(use_wave=False, device_retry_attempts=0))
    inj = DeviceFaultInjector(fail_all_serials=True).install(sched)
    pool.start()
    sched.start()
    try:
        assert wait_until(lambda: _bound_count(store) == n, 30), (
            f"only {_bound_count(store)}/{n} bound via host fallback"
        )
        assert (
            metrics.counter(
                "scheduler_device_loss_total", {"stage": "serial"}
            )
            > losses0
        ), "serial device loss never classified/counted"
        _no_leaked_assumes(sched)
        assert_bind_invariants(store)
    finally:
        sched.stop()
        pool.stop()
        inj.uninstall()


# -- scenario 4b: trailing bulk readback dies AFTER the fast payload ----------


def test_trailing_readback_loss_unwinds_assumes_zero_wrong_bindings():
    """Split-phase late-disagreement drill (r17): every wave's fast
    index payload lands cleanly and drives assumes, then the trailing
    bulk readback dies on every attempt (retries included). The
    pre-bind trailing gate must quarantine each batch BEFORE its binds
    leave the process — assumes revert (counted), pods requeue —
    repeated trips latch the device path down, and the host path
    completes the backlog. Invariants: zero wrong bindings, zero
    leaked assumes, no oversubscription."""
    store = ChaosStore()
    pool = NodeAgentPool(store, housekeeping_interval=0.1)
    for i in range(6):
        pool.add_node(f"tb-{i}")
    n = 30
    for i in range(n):
        store.create("pods", make_pod(f"tw-{i}"))
    trips0 = metrics.counter(
        "kernel_guard_trips_total", {"reason": "trailing_readback_loss"}
    )
    unwound0 = metrics.counter(
        "scheduler_wave_trailing_unwound_assumes_total"
    )
    sched = Scheduler(store, _cfg())
    inj = DeviceFaultInjector(
        fail_trailing_readbacks=set(range(64))
    ).install(sched)
    pool.start()
    sched.start()
    try:
        assert wait_until(lambda: _bound_count(store) == n, 30), (
            f"only {_bound_count(store)}/{n} bound after trailing-loss "
            "quarantine + host-path latch"
        )
        assert (
            metrics.counter(
                "kernel_guard_trips_total",
                {"reason": "trailing_readback_loss"},
            )
            > trips0
        ), "trailing readback loss never tripped the guard"
        assert (
            metrics.counter("scheduler_wave_trailing_unwound_assumes_total")
            > unwound0
        ), "no assumes were unwound by the pre-bind trailing gate"
        assert any(k == "trailing_loss" for k, _ in inj.injected), (
            "injector never hit the trailing seam"
        )
        assert sched._device_down, (
            "repeated trailing trips must latch the device path down"
        )
        # the core promise: assumes reverted before any bind left the
        # process, and what DID bind (host path) is resource-sane
        _no_leaked_assumes(sched)
        _no_oversubscription(store, cpu_capacity_m=4000)
        assert_bind_invariants(store)
    finally:
        sched.stop()
        pool.stop()
        inj.uninstall()


# -- cache/encoder divergence regressions (satellites) ------------------------


def _node(name, cpu="8"):
    return make_node_object(name, cpu=cpu)


def _labeled_pod(name, node=None, cpu="500m", labels=None):
    p = v1.Pod(
        metadata=v1.ObjectMeta(name=name, labels=labels or {"app": "web"}),
        spec=v1.PodSpec(containers=[v1.Container(requests={"cpu": cpu})]),
    )
    if node:
        p.spec.node_name = node
    return p


def test_cleanup_expired_reverts_encoder_rows_to_pre_assume():
    """Regression: an expired assume must revert the DEVICE columns
    (sel_counts, resource requests), not just the host NodeInfo."""
    cache = SchedulerCache(ttl_seconds=0.01)
    for i in range(3):
        cache.add_node(_node(f"n{i}"))
    cache.encoder.register_service_predicate(
        "default", selector_from_match_labels({"app": "web"})
    )
    fields = ("requested", "nonzero_req", "sel_counts", "prio_req")
    snap0 = jax.device_get(cache.device_snapshot())
    # deep-copy the baseline: on the CPU backend device_get can hand back
    # zero-copy views of the encoder masters, which mutate with the assumes
    before = {f: np.array(np.asarray(getattr(snap0, f))) for f in fields}
    pods = [_labeled_pod(f"a{i}") for i in range(4)]
    errs = cache.assume_pods_bulk([(p, f"n{i % 3}", None, None) for i, p in enumerate(pods)])
    assert errs == [None] * 4
    for p in pods:
        cache.finish_binding(p)
    # bulk assumes are device-synced: the masters carry the occupancy
    # (the wave kernel is presumed to have committed the device side),
    # so the divergence-to-revert shows in the host masters
    assert not np.array_equal(cache.encoder.m_req, before["requested"]), (
        "assumes never reached the encoder masters"
    )
    assert cache.cleanup_expired(now=time.monotonic() + 60.0) == 4
    after = jax.device_get(cache.device_snapshot())
    for field in fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(after, field)),
            before[field],
            err_msg=f"device {field} did not revert to pre-assume values",
        )


def test_cleanup_expired_reverts_encoder_even_when_nodeinfo_diverged():
    """Regression for the divergence leak: encoder removal used to be
    gated on the NodeInfo still holding the pod — after a host/device
    divergence the encoder kept the expired assume's occupancy forever."""
    cache = SchedulerCache(ttl_seconds=0.01)
    cache.add_node(_node("n0"))
    before = jax.device_get(cache.device_snapshot())
    pod = _labeled_pod("diverged")
    cache.assume_pod(pod, "n0")
    cache.finish_binding(pod)
    # simulate the divergence: the NodeInfo loses the pod, the encoder
    # keeps its entry
    cache._nodes["n0"].remove_pod(pod.metadata.key)
    assert cache.cleanup_expired(now=time.monotonic() + 60.0) == 1
    after = jax.device_get(cache.device_snapshot())
    np.testing.assert_array_equal(
        np.asarray(after.requested), np.asarray(before.requested),
        err_msg="phantom encoder occupancy leaked past cleanup_expired",
    )


def test_bulk_fallback_encoder_failure_is_per_item_not_a_raise():
    """Regression (satellite 1): a non-KeyError from the per-pod encoder
    fallback must not propagate mid-wave — the failing item unwinds, gets
    a per-item error, and hands its row to the anti-entropy repairer; the
    rest of the wave assumes normally."""
    cache = SchedulerCache()
    for i in range(2):
        cache.add_node(_node(f"n{i}"))
    enc = cache.encoder
    orig_add = enc.add_pod

    def flaky_add(node_name, pod, **kw):
        if pod.metadata.name == "victim":
            raise RuntimeError("injected: scatter wedged")
        return orig_add(node_name, pod, **kw)

    def broken_bulk(items):
        raise RuntimeError("injected: bulk scatter down")

    enc.add_pod = flaky_add
    enc.add_pods_bulk = broken_bulk
    pods = [
        _labeled_pod("ok-0"), _labeled_pod("victim"), _labeled_pod("ok-1"),
    ]
    errs = cache.assume_pods_bulk(
        [(p, f"n{i % 2}", None, None) for i, p in enumerate(pods)]
    )
    assert errs[0] is None and errs[2] is None
    assert errs[1] and "victim" in errs[1]
    # the failed item is fully unwound: not assumed, not mapped, not in
    # the NodeInfo — it can be re-assumed cleanly next cycle
    key = pods[1].metadata.key
    assert not cache.has_pod(key)
    assert all(
        key not in {q.metadata.key for q in ni.pods}
        for ni in cache._nodes.values()
    )
    # the row went to the anti-entropy repairer and its masters are
    # already consistent with the surviving entries
    assert enc.suspect_rows
    enc.add_pod = orig_add
    for row in list(enc.suspect_rows):
        assert enc.verify_row_aggregates(row) == []
    # the survivors really assumed
    assert cache.has_pod(pods[0].metadata.key)
    assert cache.has_pod(pods[2].metadata.key)


def test_audit_repairs_device_corruption_by_targeted_rescatter():
    """Tier-1 (fast) version of the corruption acceptance scenario: pure
    encoder + auditor, no scheduler threads. Zeroed device rows are
    detected AND re-scattered back to the master values in ONE pass, with
    no rebuild escalation."""
    enc = SnapshotEncoder()
    for i in range(4):
        enc.add_node(_node(f"dc-{i}"))
    for i in range(8):
        enc.add_pod(f"dc-{i % 4}", _labeled_pod(f"dp-{i}"))
    enc.flush()
    rows = [r for r, nm in enumerate(enc.row_names) if nm]
    corrupt_device_rows(enc, rows, field="requested", mutate=np.zeros_like)
    aud = SnapshotAntiEntropy(enc, sample_rows=256)
    report = aud.audit_once()
    assert report["device_drift"].get("requested") == rows
    assert not report["rebuilt"], "targeted re-scatter escalated to rebuild"
    dev = np.asarray(jax.device_get(enc._device.requested))
    np.testing.assert_array_equal(dev, enc.m_req)


def test_audit_repairs_master_drift_from_pod_entries():
    """The master self-check: a drifted aggregate column (simulated
    incremental-encoder bug) is re-derived from the per-pod entries and
    re-scattered to the device in one audit pass."""
    enc = SnapshotEncoder()
    for i in range(4):
        enc.add_node(_node(f"n{i}"))
    for i in range(6):
        enc.add_pod(f"n{i % 4}", _labeled_pod(f"p{i}"))
    enc.flush()
    aud = SnapshotAntiEntropy(enc, sample_rows=16)
    assert aud.audit_once()["device_drift"] == {}
    enc.m_req[1, RES_CPU] += 777  # the drift a lost remove_pod would leave
    report = aud.audit_once()
    assert any(r == 1 for r, _cols in report["master_repaired"])
    expected = sum(int(e.req[RES_CPU]) for e in enc._pods[1].values())
    assert int(enc.m_req[1, RES_CPU]) == expected
    dev = jax.device_get(enc.flush())
    np.testing.assert_array_equal(np.asarray(dev.requested), enc.m_req)

# -- review regressions: guard churn-skip, shrink pinning, suspect retention --


def test_oracle_guard_skips_post_launch_node_churn():
    """Informer churn between launch and commit (cordon, taint) must NOT
    trip the oracle guard: the placement was sound against the state the
    kernel encoding saw, and acting on newer node state would quarantine
    a correct batch — and, repeated, falsely latch the device path off.
    Churned nodes are recognized by their generation moving past the
    batch's launch generation and skipped; the same infeasibility visible
    AT launch still trips."""
    from types import SimpleNamespace

    store = ChaosStore()
    sched = Scheduler(store, _cfg())
    node = make_node_object("on-0", cpu="2")
    sched.cache.add_node(node)
    pi = SimpleNamespace(pod=make_pod("op-0", cpu="1"))
    to_bind = [(pi, "on-0", 0, None)]
    launch_gen = sched.cache._ext_generation
    # sound at launch, unchanged since: no violation
    assert sched._guard_oracle_sample(to_bind, launch_gen) is None
    # a sibling batch's DEVICE assume (device_synced=True) moves
    # `generation` but NOT ext_generation: the node stays ELIGIBLE for
    # the check (the device chain saw that placement — a disagreement
    # would be a real kernel signal)
    sched.cache.assume_pod(
        make_pod("sibling", cpu="500m"), "on-0", device_synced=True
    )
    skips0 = metrics.counter(
        "kernel_guard_oracle_skips_total", {"reason": "node_churn"}
    )
    assert sched._guard_oracle_sample(to_bind, launch_gen) is None
    assert (
        metrics.counter(
            "kernel_guard_oracle_skips_total", {"reason": "node_churn"}
        )
        == skips0
    ), "device-synced sibling assume must not exempt the node"
    # a HOST-path assume (fallback pod between launch and commit,
    # device_synced=False) is occupancy NO device chain saw: it stamps
    # ext_generation and the node is skipped. The host pod fills the
    # node (500m+1+1 > 2 cpu), so WITHOUT the skip the oracle would fail
    # feasibility and quarantine a correct batch — mixed host/device
    # load would falsely latch the device path off.
    sched.cache.assume_pod(make_pod("hostpod", cpu="1"), "on-0")
    assert sched._guard_oracle_sample(to_bind, launch_gen) is None
    assert (
        metrics.counter(
            "kernel_guard_oracle_skips_total", {"reason": "node_churn"}
        )
        > skips0
    ), "host-path assume must skip, not trip, the oracle"
    skips0 = metrics.counter(
        "kernel_guard_oracle_skips_total", {"reason": "node_churn"}
    )
    # cordon AFTER launch: infeasible against the live cache now, but the
    # node's ext_generation moved past launch_gen — churn, not corruption
    node.spec.unschedulable = True
    sched.cache.update_node(node)
    assert sched._guard_oracle_sample(to_bind, launch_gen) is None
    assert (
        metrics.counter(
            "kernel_guard_oracle_skips_total", {"reason": "node_churn"}
        )
        > skips0
    )
    # the cordon visible AT launch (launch_gen taken after it): real trip
    assert (
        sched._guard_oracle_sample(to_bind, sched.cache._ext_generation)
        is not None
    )


def test_single_survivor_shrink_pins_uploads_to_survivor():
    """Shrinking to ONE surviving device must pin snapshot uploads to it:
    an unsharded (None, None) fallback would device_put to the JAX default
    device — which after a device loss may be exactly the dead chip."""
    from kubernetes_tpu.parallel.mesh import single_device_shardings

    survivor = jax.devices()[1]
    enc = SnapshotEncoder()
    for i in range(4):
        enc.add_node(_node(f"sv-{i}"))
    enc.flush()
    enc.set_sharding(*single_device_shardings(survivor))
    snap = enc.flush()  # set_sharding invalidates: full re-upload, pinned
    for field in snap._fields:
        assert list(getattr(snap, field).devices()) == [survivor], field
    # update scatters (dirty-row path) stay pinned too
    enc.add_pod("sv-0", _labeled_pod("sv-pod"))
    snap = enc.flush()
    assert list(snap.requested.devices()) == [survivor]


def test_suspect_rows_survive_failed_audit_pass():
    """A mid-pass device error (fetch/flush raising) must not discard the
    failure-flagged suspect rows: they keep their audit-first priority for
    the next pass and are drained only after a pass completes."""
    enc = SnapshotEncoder()
    for i in range(4):
        enc.add_node(_node(f"ar-{i}"))
    enc.add_pod("ar-0", _labeled_pod("ar-pod"))
    enc.flush()
    enc.suspect_rows.add(0)
    aud = SnapshotAntiEntropy(enc, sample_rows=4)
    orig = enc.fetch_device_rows

    def boom(rows):
        raise RuntimeError("device lost mid-fetch")

    enc.fetch_device_rows = boom
    with pytest.raises(RuntimeError):
        aud.audit_once()
    assert 0 in enc.suspect_rows, "failed pass discarded the suspect flag"
    enc.fetch_device_rows = orig
    report = aud.audit_once()
    assert report["rows_audited"] >= 1
    assert not enc.suspect_rows, "completed pass should drain the suspects"


# -- generational snapshot: pinned readers vs donating waves ------------------


def test_audit_gather_concurrent_with_donating_launch_on_newer_generation():
    """The EXACT round-8 failure shape, now legal: a reader holds a pin
    on generation N (the anti-entropy audit's row gather) while a
    donating advance lands on the newer generation. Under the old
    process-wide device_lock this interleaving deadlocked the CPU client;
    under the generational discipline the donor pays one copy-on-pin and
    the pinned gather completes against intact, uncorrupted buffers."""
    metrics.reset()
    enc = SnapshotEncoder()
    # standalone encoder: in production the scheduler cache lock
    # serializes host-side encoder mutation — the soak honors the same
    # guarded-by contract (the lockset sanitizer holds tests to it too),
    # while the GATHER side stays deliberately lock-free (pin-protected)
    host_lock = lockgraph.named_lock("scheduler.cache")
    for i in range(8):
        enc.add_node(_node(f"gg-{i}"))
    enc.add_pod("gg-0", _labeled_pod("gg-pod"))
    enc.flush()
    expected_req = enc.m_req.copy()

    with enc.pin_generation() as lease:
        pinned_gen = lease.gen_id
        copies0 = metrics.counter("snapshot_generation_copy_on_pin_total")
        # donating advance while the pin is held: the old deadlock recipe
        with host_lock:
            enc.mark_row_dirty("gg-1")
            enc.flush(donate=True)
        assert enc.device_generation > pinned_gen
        assert (
            metrics.counter("snapshot_generation_copy_on_pin_total")
            == copies0 + 1
        ), "a donating advance under a reader pin must copy, never consume"
        # the pinned generation's buffers survived the donation: gather
        # them AFTER the donating scatter dispatched (round-8 ordering)
        pinned_req = np.asarray(jax.device_get(lease.snap.requested))
        assert np.array_equal(pinned_req, expected_req), (
            "pinned generation corrupted by a concurrent donation"
        )
        assert metrics.gauge("snapshot_generation_pinned_readers") == 1.0
        assert metrics.gauge("snapshot_generation_retiring") == 1.0
    # pin released -> the superseded generation retires
    assert metrics.gauge("snapshot_generation_retiring") == 0.0
    assert metrics.counter("snapshot_generation_retired_total") >= 1.0

    # threaded soak of the same shape: an auditor-style fetch loop races
    # a donating-flush loop; zero deadlocks, zero cross-generation reads
    # (every fetched row equals the host masters, which never change)
    import threading

    with host_lock:
        live = [r for r, nm in enumerate(enc.row_names) if nm]
    errors = []
    stop = threading.Event()

    def reader():
        try:
            while not stop.is_set():
                fetched = enc.fetch_device_rows(live)
                if fetched is None:
                    continue
                if not np.array_equal(
                    fetched["requested"], enc.m_req[live]
                ):
                    errors.append("cross-generation read: stale rows")
                    return
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append(repr(e))

    def writer():
        try:
            for i in range(60):
                with host_lock:
                    enc.mark_row_dirty(f"gg-{i % 8}")
                    enc.flush(donate=True)
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append(repr(e))
        finally:
            stop.set()

    tr, tw = threading.Thread(target=reader), threading.Thread(target=writer)
    tr.start()
    tw.start()
    tw.join(timeout=60.0)
    tr.join(timeout=60.0)
    assert not tw.is_alive() and not tr.is_alive(), (
        "gather vs donating flush deadlocked (the round-8 shape is back)"
    )
    assert not errors, errors


def test_chained_shared_generations_survive_intermediate_retirement():
    """Two overlapping readers across two capacity growths: R1 pins A; a
    t_cap growth installs B sharing A's kept buffers; R2 pins B; a second
    growth installs C sharing B's kept fields — which are still A's
    buffers. When R2 unpins, intermediate B retires, and C must INHERIT
    the shared-buffer tie to still-pinned A (not have it severed): a
    donating advance on C then pays copy-on-pin instead of consuming the
    buffers R1's gather reads."""
    metrics.reset()
    enc = SnapshotEncoder()
    for i in range(8):
        enc.add_node(_node(f"cs-{i}"))
    enc.add_pod("cs-0", _labeled_pod("cs-pod"))
    enc.flush()
    expected_req = enc.m_req.copy()

    with enc.pin_generation() as r1:  # pins A
        gen_a = r1.gen_id
        enc._ensure_cap("t_cap", enc.cfg.t_cap * 2)
        enc.flush()  # reshape-merge installs B sharing A's kept buffers
        with enc.pin_generation() as r2:  # pins B
            assert r2.gen_id > gen_a
            enc._ensure_cap("t_cap", enc.cfg.t_cap * 2)
            enc.flush()  # installs C sharing B (kept fields: A's buffers)
        # R2 unpinned -> intermediate B retired; the tie must now point
        # at A, the oldest still-pinned ancestor
        live = enc._gen
        assert live.shared_parent is not None, (
            "intermediate retirement severed the shared-buffer tie while "
            "the oldest ancestor is still pinned"
        )
        assert live.shared_parent.gen_id == gen_a
        copies0 = metrics.counter("snapshot_generation_copy_on_pin_total")
        enc.mark_row_dirty("cs-1")
        enc.flush(donate=True)  # donating advance on C
        assert (
            metrics.counter("snapshot_generation_copy_on_pin_total")
            == copies0 + 1
        ), "donation on a chained-shared generation must copy, not consume"
        # R1's pinned buffers (aliased by C's kept fields) survived
        pinned_req = np.asarray(jax.device_get(r1.snap.requested))
        assert np.array_equal(pinned_req, expected_req), (
            "reader R1's pinned buffers were donated out from under it"
        )
    # every pin drained: ties clear, all superseded generations retire
    assert enc._gen.shared_parent is None
    assert metrics.gauge("snapshot_generation_retiring") == 0.0
    assert not enc._retiring


def test_leaked_pin_trips_stall_watchdog_without_lease_traffic():
    """A leaked reader pin on an otherwise idle encoder must trip the
    retire-stall watchdog from the periodic sweep (anti-entropy pass /
    SIGUSR2 dump), not only when the next pin or donation arrives."""
    metrics.reset()
    enc = SnapshotEncoder()
    for i in range(4):
        enc.add_node(_node(f"lp-{i}"))
    enc.flush()
    leaked = enc.pin_generation().__enter__()  # never exited
    enc.mark_row_dirty("lp-0")
    enc.flush(donate=True)  # supersedes the pinned generation
    stuck = enc._retiring[0]
    stuck.superseded_at -= RETIRE_STALL_AFTER_S + 1.0
    assert metrics.counter("snapshot_generation_retire_stalls_total") == 0
    # the audit pass sweeps the watchdog even on its skip paths, which
    # take no generation lease at all
    aud = SnapshotAntiEntropy(enc, quiesced=lambda: False)
    report = aud.audit_once()
    assert report["skipped"] == "pipeline busy"
    assert metrics.counter("snapshot_generation_retire_stalls_total") == 1
    # reported once per stuck generation, not once per sweep
    enc.check_retire_stalls()
    assert metrics.counter("snapshot_generation_retire_stalls_total") == 1
    leaked.__exit__(None, None, None)
    assert metrics.gauge("snapshot_generation_retiring") == 0.0


@pytest.mark.slow  # multi-batch pipeline fill: several wave cycles + binds
def test_pipelined_waves_at_least_two_in_flight_with_concurrent_reads():
    """Pipelined-wave chaos variant (ISSUE 11 acceptance): with a deep
    pipeline configured, at least TWO wave batches are demonstrably in
    flight concurrently (`scheduler_wave_inflight_max`), while an
    auditor-style gather loop reads pinned generations the whole time —
    zero guard trips attributable to cross-generation reads, every pod
    bound exactly once, no leaked assumes."""
    import threading

    metrics.reset()
    store = ChaosStore()
    pool = NodeAgentPool(store, housekeeping_interval=0.1)
    for i in range(8):
        pool.add_node(f"pw-{i}", cpu="64")
    sched = Scheduler(
        store,
        _cfg(
            pipeline_depth=3,
            device_batch_size=8,
            device_batch_window=0.0,
        ),
    )
    pool.start()
    n = 96
    # pods exist BEFORE the scheduler starts: the queue opens with 12
    # full batches ready, so the loop stacks launches to pipeline depth
    for i in range(n):
        store.create("pods", make_pod(f"pw-{i}", cpu="100m"))
    enc = sched.cache.encoder
    stop = threading.Event()
    reader_errors = []

    def gather_loop():
        try:
            while not stop.is_set():
                with sched.cache.lock:  # row table read: guarded-by contract
                    rows = [r for r, nm in enumerate(enc.row_names) if nm]
                if rows:
                    enc.fetch_device_rows(rows)
                time.sleep(0.002)
        except Exception as e:  # pragma: no cover - failure reporting
            reader_errors.append(repr(e))

    t = threading.Thread(target=gather_loop, daemon=True)
    t.start()
    sched.start()
    try:
        assert wait_until(lambda: _bound_count(store) == n, 60)
        _no_leaked_assumes(sched)
    finally:
        stop.set()
        t.join(timeout=10.0)
        sched.stop()
        pool.stop()
    assert not reader_errors, reader_errors
    inflight_max = metrics.gauge("scheduler_wave_inflight_max") or 0.0
    assert inflight_max >= 2.0, (
        f"pipeline never had 2 waves in flight (max {inflight_max}); "
        "the generational snapshot exists to make this legal"
    )
    # zero guard trips of any reason: a cross-generation read would
    # surface as a poisoned readback or an oracle-infeasible placement
    # (oracle churn SKIPS are fine — they are the guard declining to
    # judge a node the informers legitimately mutated mid-wave)
    trips = [
        (name, labels, val)
        for name, labels, val in metrics.snapshot_counters(
            "kernel_guard_trips_total"
        )
        if val
    ]
    assert not trips, f"guard trips during pipelined waves: {trips}"
    assert_bind_invariants(store)
    _no_oversubscription(store, cpu_capacity_m=64000)
