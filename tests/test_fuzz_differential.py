"""Randomized device-kernel vs host-oracle differential fuzz.

The reference pins its scheduler semantics with 2.5k LoC of table-driven
oracle tests (pkg/scheduler/core/generic_scheduler_test.go); the TPU build's
equivalent is this seeded fuzz: random clusters (labels, taints, capacities,
existing pods with affinity terms) x random pod batches (requests, node
selectors, required/preferred node affinity, tolerations, topology spread,
inter-pod (anti-)affinity, host ports, priorities), asserting per (pod, node):

  1. the wave kernel's pre-commit feasibility mask == the host framework's
     filter verdict (the full default plugin chain, minus volume plugins
     which are host-only by design);
  2. every placement the kernel commits is feasible under the host filters
     AND capacity-sound after sequential replay of the whole batch;
  3. the kernel's committed occupancy tensors equal a host replay of the
     same placements (device/host convergence invariant).

Divergence policy (wave vs serial): the wave kernel may pick a different
near-tie node than the serial oracle (documented staleness, wavelattice.py
module docstring), so CHOICE equality is not asserted — feasibility and
accounting are exact and are.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.api.objects import (
    Affinity,
    Container,
    ContainerPort,
    Node,
    NodeSpec,
    NodeStatus,
    ObjectMeta,
    Pod,
    PodAffinity,
    PodAffinityTerm,
    PodAntiAffinity,
    PodSpec,
    Taint,
    Toleration,
    TopologySpreadConstraint,
    WeightedPodAffinityTerm,
    compute_pod_resource_request,
)
from kubernetes_tpu.api.selectors import LabelSelector
from kubernetes_tpu.ops.encoding import RES_CPU, RES_MEM, RES_PODS, SnapshotEncoder
from kubernetes_tpu.ops.lattice import DEFAULT_WEIGHTS
from kubernetes_tpu.ops.templates import TemplateCache, build_pair_table
from kubernetes_tpu.ops.wavelattice import make_wave_kernel_jit
from kubernetes_tpu.scheduler.cache.nodeinfo import NodeInfo, Snapshot
from kubernetes_tpu.scheduler.framework.interface import CycleState, is_success
from kubernetes_tpu.scheduler.framework.runtime import Framework
from kubernetes_tpu.scheduler.framework.registry import (
    PluginSet,
    default_plugin_set,
    default_registry,
)

ZONES = ["za", "zb", "zc"]
RACKS = ["r0", "r1", "r2", "r3"]
APPS = ["web", "db", "cache"]


def _oracle_framework(snapshot_holder):
    """Default filter chain minus the volume plugins (host-only fallback by
    design — encode_pod_batch flags PVC pods for the host path)."""
    ps = default_plugin_set()
    ps.filter = [
        n
        for n in ps.filter
        if n
        not in (
            "VolumeRestrictions",
            "NodeVolumeLimits",
            "EBSLimits",
            "GCEPDLimits",
            "AzureDiskLimits",
            "VolumeBinding",
            "VolumeZone",
        )
    ]
    ctx = {
        "snapshot_getter": lambda: snapshot_holder[0],
        "hard_pod_affinity_weight": 1.0,
        "ignored_extended_resources": frozenset(),
    }
    return Framework(default_registry(), ps, ctx)


def _rand_selector(rng) -> LabelSelector:
    return LabelSelector.make(match_labels={"app": rng.choice(APPS)})


def _rand_affinity(rng):
    """Random inter-pod affinity block (possibly None)."""
    kind = rng.randrange(6)
    sel = _rand_selector(rng)
    key = rng.choice(["zone", "rack", "kubernetes.io/hostname"])
    term = PodAffinityTerm(label_selector=sel, topology_key=key)
    if kind == 0:
        return Affinity(pod_anti_affinity=PodAntiAffinity(required=(term,)))
    if kind == 1:
        return Affinity(pod_affinity=PodAffinity(required=(term,)))
    if kind == 2:
        return Affinity(
            pod_affinity=PodAffinity(
                preferred=(WeightedPodAffinityTerm(weight=rng.randrange(1, 100), term=term),)
            )
        )
    if kind == 3:
        return Affinity(
            pod_anti_affinity=PodAntiAffinity(
                preferred=(WeightedPodAffinityTerm(weight=rng.randrange(1, 100), term=term),)
            )
        )
    return None


def _rand_node(rng, i: int) -> Node:
    labels = {
        "zone": rng.choice(ZONES),
        "rack": rng.choice(RACKS),
    }
    if rng.random() < 0.5:
        labels["disk"] = rng.choice(["ssd", "hdd"])
    taints = []
    if rng.random() < 0.2:
        taints.append(
            Taint(
                "dedicated",
                rng.choice(["infra", "gpu"]),
                rng.choice(["NoSchedule", "PreferNoSchedule", "NoExecute"]),
            )
        )
    return Node(
        metadata=ObjectMeta(name=f"n{i}", labels=labels),
        spec=NodeSpec(
            taints=taints, unschedulable=(rng.random() < 0.05)
        ),
        status=NodeStatus(
            allocatable={
                "cpu": str(rng.choice([2, 4, 8])),
                "memory": f"{rng.choice([4, 8, 16])}Gi",
                "pods": 32,
            }
        ),
    )


def _rand_pod(rng, name: str, allow_pin=None) -> Pod:
    kw = {}
    labels = {"app": rng.choice(APPS)}
    if rng.random() < 0.3:
        kw["node_selector"] = {"zone": rng.choice(ZONES)}
    aff = _rand_affinity(rng)
    if aff is not None:
        kw["affinity"] = aff
    if rng.random() < 0.25:
        kw["topology_spread_constraints"] = [
            TopologySpreadConstraint(
                max_skew=rng.randrange(1, 3),
                topology_key=rng.choice(["zone", "rack"]),
                when_unsatisfiable=rng.choice(["DoNotSchedule", "ScheduleAnyway"]),
                label_selector=_rand_selector(rng),
            )
        ]
    if rng.random() < 0.3:
        kw["tolerations"] = [
            Toleration(key="dedicated", operator="Exists")
        ]
    ports = []
    if rng.random() < 0.2:
        hp = rng.choice([8080, 9090])
        ports.append(ContainerPort(container_port=hp, host_port=hp))
    if allow_pin and rng.random() < 0.05:
        kw["node_name"] = rng.choice(allow_pin)
    return Pod(
        metadata=ObjectMeta(name=name, labels=labels),
        spec=PodSpec(
            containers=[
                Container(
                    requests={
                        "cpu": rng.choice(["250m", "500m", "1", "2"]),
                        "memory": rng.choice(["256Mi", "1Gi", "2Gi"]),
                    },
                    ports=ports,
                )
            ],
            priority=rng.choice([0, 0, 0, 100, 1000]),
            **kw,
        ),
    )


def _build_random_cluster(rng, n_nodes: int):
    """Returns (encoder, host NodeInfos dict, nodes list)."""
    enc = SnapshotEncoder()
    nodes = [_rand_node(rng, i) for i in range(n_nodes)]
    infos = {}
    for n in nodes:
        enc.add_node(n)
        infos[n.metadata.name] = NodeInfo(n)
    # existing pods (some with eterms: anti/affinity carried by placed pods)
    for j in range(n_nodes * 2):
        node = rng.choice(nodes)
        p = _rand_pod(rng, f"pre-{j}")
        p.spec.node_name = node.metadata.name
        enc.add_pod(node.metadata.name, p)
        infos[node.metadata.name].add_pod(p)
    return enc, infos, nodes


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6, 7, 8])
def test_fuzz_device_mask_matches_host_filters(seed):
    rng = random.Random(seed)
    n_nodes = rng.randrange(8, 33)
    enc, infos, nodes = _build_random_cluster(rng, n_nodes)
    node_names = [n.metadata.name for n in nodes]
    pods = [
        _rand_pod(rng, f"p{i}", allow_pin=node_names)
        for i in range(rng.randrange(4, 17))
    ]

    tc = TemplateCache(enc)
    P = 1
    while P < len(pods):
        P *= 2
    eb = tc.encode(pods, pad_to=P)
    # the table's pair axis follows the pairs (ops/templates.pair_slots)
    ptab = build_pair_table(enc, eb.tpl_np, eb.num_templates)
    snap = enc.flush()
    kern = make_wave_kernel_jit(enc.cfg.v_cap, 64, 8)
    new_snap, res = kern(
        snap, eb.batch, ptab, np.asarray(DEFAULT_WEIGHTS), jax.random.PRNGKey(seed)
    )
    feasible_tpl, chosen, placed, new_snap_h = jax.device_get(
        (res.feasible_tpl, res.chosen, res.placed, new_snap)
    )
    enc.invalidate_device()
    pod_tpl = eb.pod_tpl_np

    # ---- host oracle: full framework filter chain per (pod, node) --------
    snapshot = Snapshot([ni.clone() for ni in infos.values()])
    holder = [snapshot]
    fw = _oracle_framework(holder)
    row_of = {n: enc.row_of(n) for n in node_names}

    for i, pod in enumerate(pods):
        if eb.fallback[i]:
            continue
        t = int(pod_tpl[i])
        state = CycleState()
        st = fw.run_pre_filter_plugins(state, pod)
        if not is_success(st):
            # prefilter rejection = infeasible everywhere
            for nm in node_names:
                assert not feasible_tpl[t, row_of[nm]], (seed, pod.metadata.name, nm)
            continue
        for nm in node_names:
            ni = snapshot.get(nm)
            host_ok = is_success(fw.run_filter_plugins(state, pod, ni))
            # NodeName pinning is pod-level (not part of the template mask)
            if pod.spec.node_name and nm != pod.spec.node_name:
                continue
            dev_ok = bool(feasible_tpl[t, row_of[nm]])
            assert dev_ok == host_ok, (
                f"seed={seed} pod={pod.metadata.name} node={nm}: "
                f"device={dev_ok} host={host_ok}"
            )

    # ---- placements: feasible at commit time + capacity-sound replay -----
    # (prefill pods are injected without capacity checks, so the invariant
    # "requested <= allocatable" is asserted only on nodes that received a
    # batch placement: the kernel must never have placed onto negative free)
    replay = {nm: infos[nm].clone() for nm in node_names}
    touched = set()
    for i, pod in enumerate(pods):
        if eb.fallback[i] or not placed[i]:
            continue
        nm = enc.row_names[int(chosen[i])]
        assert nm is not None
        if pod.spec.node_name:
            assert nm == pod.spec.node_name, (seed, pod.metadata.name)
        ni = replay[nm]
        p2 = pod.deep_copy()
        p2.spec.node_name = nm
        ni.add_pod(p2)
        touched.add(nm)
    from kubernetes_tpu.api.resources import CPU, MEMORY, PODS

    for nm in touched:
        ni = replay[nm]
        assert ni.requested.get(CPU, 0) <= ni.allocatable.get(CPU, 0), (seed, nm)
        assert ni.requested.get(MEMORY, 0) <= ni.allocatable.get(MEMORY, 0), (
            seed,
            nm,
        )
        assert len(ni.pods) <= ni.allocatable.get(PODS, 10**9), (seed, nm)

    # ---- failures must be justified: a hard-failed pod (not deferred) had
    # no base-feasible node at batch start (the host filters agree via the
    # mask equality above). Wave-vs-serial divergence is thereby bounded:
    # the wave may DEFER a placeable pod to the next cycle (in-batch
    # contention / affinity chaining), but never wrongly hard-fails one.
    deferred = jax.device_get(res.deferred)
    for i, pod in enumerate(pods):
        if eb.fallback[i]:
            continue
        t = int(pod_tpl[i])
        if not placed[i] and not deferred[i] and not pod.spec.node_name:
            assert not feasible_tpl[t].any(), (
                f"seed={seed} pod={pod.metadata.name} hard-failed with "
                f"feasible nodes present"
            )

    # ---- device/host occupancy convergence -------------------------------
    # even seeds replay through the vectorized bulk path (the production
    # wave-bind route), odd seeds per-pod — both must converge with the
    # device's own commits
    replay = [
        (enc.row_names[int(chosen[i])], pod, int(eb.pod_band_np[i]))
        for i, pod in enumerate(pods)
        if not eb.fallback[i] and placed[i]
    ]
    if seed % 2 == 0:
        enc.add_pods_bulk([(nm, pod, band, None) for nm, pod, band in replay])
    else:
        for nm, pod, band in replay:
            enc.add_pod(nm, pod, device_synced=True, prio_band=band)
    np.testing.assert_array_equal(enc.m_req, new_snap_h.requested)
    np.testing.assert_array_equal(enc.m_sel_counts, new_snap_h.sel_counts)
    np.testing.assert_array_equal(enc.m_port_counts, new_snap_h.port_counts)
    np.testing.assert_array_equal(enc.m_prio_req, new_snap_h.prio_req)
    np.testing.assert_allclose(enc.m_eterm_w, new_snap_h.eterm_w, rtol=1e-6)


# ---------------------------------------------------------------------------
# Corruption-injection corpus (data-plane self-defense): every entry below
# injects a corruption into the DEVICE state or the kernel's read-back
# outputs and asserts it is caught by either the batch guards
# (ops/lattice.validate_batch_outputs) or ONE anti-entropy audit pass
# (scheduler/antientropy.py) — the online analogue of the oracle above.
# ---------------------------------------------------------------------------

from kubernetes_tpu.ops.lattice import (  # noqa: E402
    GUARD_NONFINITE,
    GUARD_ROW_RANGE,
    validate_batch_outputs,
)
from kubernetes_tpu.scheduler.antientropy import SnapshotAntiEntropy  # noqa: E402
from kubernetes_tpu.testing.device_faults import corrupt_device_rows  # noqa: E402


def _flip_taint_effect(a):
    return ((a + 1) % 3).astype(a.dtype)


def _swap_label_ids(a):
    # shift every present value-id to a sibling id and ghost absent slots:
    # the exact shape of a vocab-id mixup (selector matching silently
    # matches the WRONG label values)
    return np.where(a >= 0, a + 1, 0).astype(a.dtype)


def _clamp_rows(a):
    return np.zeros_like(a)


def _inflate_alloc(a):
    return (a * 2 + 1000).astype(a.dtype)


def _flip_bool(a):
    return ~a


# (name, DeviceSnapshot field, mutator applied to the corrupted rows)
SNAPSHOT_CORRUPTIONS = [
    ("taint_effect_flip", "taint_effect", _flip_taint_effect),
    ("label_vocab_id_swap", "label_vals", _swap_label_ids),
    ("requested_clamped_to_zero", "requested", _clamp_rows),
    ("allocatable_inflated", "allocatable", _inflate_alloc),
    ("sel_counts_zeroed", "sel_counts", _clamp_rows),
    ("unschedulable_flip", "unschedulable", _flip_bool),
]


@pytest.mark.parametrize(
    "name,field,mutate",
    SNAPSHOT_CORRUPTIONS,
    ids=[c[0] for c in SNAPSHOT_CORRUPTIONS],
)
@pytest.mark.parametrize("seed", [11, 12])
def test_fuzz_snapshot_corruption_caught_within_one_audit_pass(
    seed, name, field, mutate
):
    """Device-state corruption (host masters untouched — the drift a
    scatter bug or bit flip leaves) must be detected, attributed to the
    right column, and repaired back to the masters by a single
    anti-entropy pass, without escalating to a full rebuild."""
    from kubernetes_tpu.api.selectors import selector_from_match_labels

    rng = random.Random(seed)
    enc, _infos, _nodes = _build_random_cluster(rng, rng.randrange(8, 17))
    # service predicates populate sel_counts (intern backfills placed pods)
    for app in APPS:
        enc.register_service_predicate(
            "default", selector_from_match_labels({"app": app})
        )
    enc.flush()
    aud = SnapshotAntiEntropy(enc, sample_rows=enc.cfg.n_cap)
    clean = aud.audit_once()
    assert clean["device_drift"] == {} and not clean["master_repaired"], (
        "audit flagged drift on an uncorrupted snapshot (false positive)"
    )

    master = np.array(enc._master_of(field))
    live = [r for r, nm in enumerate(enc.row_names) if nm is not None]
    rows = [
        r
        for r in live
        if not np.array_equal(mutate(master[r : r + 1])[0], master[r])
    ][:4]
    assert rows, f"corpus entry {name!r} mutated nothing (vacuous)"
    corrupt_device_rows(enc, rows, field=field, mutate=mutate)

    report = aud.audit_once()
    assert set(report["device_drift"].get(field, [])) == set(rows), (
        f"{name}: audit missed corrupted rows — "
        f"drift={report['device_drift']}, injected rows={rows}"
    )
    assert not report["rebuilt"], "targeted re-scatter should have sufficed"
    # repaired: every device row equals the (untouched) host masters again
    fetched = enc.fetch_device_rows(live)
    for f in enc.ROW_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(fetched[f]),
            enc._master_of(f)[np.asarray(live)],
            err_msg=f"{name}: device field {f!r} not repaired",
        )


@pytest.mark.parametrize("seed", [31, 32])
def test_fuzz_poisoned_readback_corpus_caught_by_guards(seed):
    """Kernel-output corruption (NaN/Inf scores, wild or negative chosen
    rows) must trip validate_batch_outputs with the right reason — and
    the clean outputs of a healthy kernel must never trip it (a false
    positive would needlessly degrade waves to host speed)."""
    rng = random.Random(seed)
    n_nodes = rng.randrange(8, 17)
    enc, _infos, _nodes = _build_random_cluster(rng, n_nodes)
    pods = [_rand_pod(rng, f"g{i}") for i in range(rng.randrange(4, 9))]
    tc = TemplateCache(enc)
    P = 1
    while P < len(pods):
        P *= 2
    eb = tc.encode(pods, pad_to=P)
    ptab = build_pair_table(enc, eb.tpl_np, eb.num_templates)
    snap = enc.flush()
    kern = make_wave_kernel_jit(enc.cfg.v_cap, 64, 8)
    _new_snap, res = kern(
        snap, eb.batch, ptab, np.asarray(DEFAULT_WEIGHTS), jax.random.PRNGKey(seed)
    )
    chosen, placed, score = jax.device_get((res.chosen, res.placed, res.score))
    enc.invalidate_device()
    n_rows = len(enc.row_names)

    assert validate_batch_outputs(chosen, placed, score, n_rows) is None, (
        "guard tripped on a healthy kernel's outputs (false positive)"
    )
    assert placed.any(), f"seed {seed} placed nothing — corpus is vacuous"
    victim = int(np.nonzero(placed)[0][0])

    poisoned = np.array(score)
    poisoned[victim] = np.nan
    assert (
        validate_batch_outputs(chosen, placed, poisoned, n_rows)
        == GUARD_NONFINITE
    )
    poisoned[victim] = np.inf
    assert (
        validate_batch_outputs(chosen, placed, poisoned, n_rows)
        == GUARD_NONFINITE
    )
    for wild in (n_rows, 2**30, -1, -(2**30)):
        bad = np.array(chosen)
        bad[victim] = wild
        assert (
            validate_batch_outputs(bad, placed, score, n_rows)
            == GUARD_ROW_RANGE
        ), f"wild row {wild} not caught"


@pytest.mark.parametrize("seed", [21, 22, 23, 24])
def test_fuzz_selector_spread_device_picks_min_service_count(seed):
    """Score-differential for the device DefaultPodTopologySpread: with the
    spread component as the ONLY weighted score, every kernel placement
    must land on a node whose batch-start same-service pod count is
    minimal among that pod's feasible nodes (the host plugin's invert-by-
    max normalization picks exactly those). Services are one-per-app
    (non-overlapping), where the kernel's max-dedup equals the host's
    any()-dedup. Capacities are generous so in-batch fills never force a
    pod off the min-count tier."""
    from kubernetes_tpu.api.selectors import selector_from_match_labels
    from kubernetes_tpu.ops.lattice import (
        NUM_SCORE_COMPONENTS,
        SC_SELECTOR_SPREAD,
    )

    rng = random.Random(seed)
    n_nodes = rng.randrange(6, 14)
    enc = SnapshotEncoder()
    nodes = []
    infos = {}
    for i in range(n_nodes):
        n = Node(
            metadata=ObjectMeta(name=f"n{i}", namespace=""),
            status=NodeStatus(
                capacity={"cpu": "64", "memory": "256Gi", "pods": "200"}
            ),
        )
        nodes.append(n)
        enc.add_node(n)
        infos[n.metadata.name] = NodeInfo(n)
    for app in APPS:
        enc.register_service_predicate(
            "default", selector_from_match_labels({"app": app})
        )
    # existing pods: plain app labels only (no affinity noise)
    for j in range(n_nodes * 2):
        node = rng.choice(nodes)
        p = Pod(
            metadata=ObjectMeta(
                name=f"pre-{j}", labels={"app": rng.choice(APPS)}
            ),
            spec=PodSpec(
                node_name=node.metadata.name,
                containers=[Container(requests={"cpu": "100m"})],
            ),
        )
        enc.add_pod(node.metadata.name, p)
        infos[node.metadata.name].add_pod(p)

    pods = [
        Pod(
            metadata=ObjectMeta(name=f"p{i}", labels={"app": rng.choice(APPS)}),
            spec=PodSpec(containers=[Container(requests={"cpu": "100m"})]),
        )
        for i in range(rng.randrange(3, 8))
    ]

    tc = TemplateCache(enc)
    P = 1
    while P < len(pods):
        P *= 2
    eb = tc.encode(pods, pad_to=P)
    ptab = build_pair_table(enc, eb.tpl_np, eb.num_templates)
    snap = enc.flush()
    weights = np.zeros(NUM_SCORE_COMPONENTS, np.float32)
    weights[SC_SELECTOR_SPREAD] = 1.0
    kern = make_wave_kernel_jit(enc.cfg.v_cap, 64, 8)
    _new_snap, res = kern(snap, eb.batch, ptab, weights, jax.random.PRNGKey(seed))
    chosen, placed, feasible_tpl = jax.device_get(
        (res.chosen, res.placed, res.feasible_tpl)
    )
    enc.invalidate_device()

    def svc_count(app, node_name):
        return sum(
            1
            for p in infos[node_name].pods
            if p.metadata.labels.get("app") == app
        )

    pod_tpl = eb.pod_tpl_np
    for i, pod in enumerate(pods):
        assert placed[i], (seed, pod.metadata.name)
        t = int(pod_tpl[i])
        app = pod.metadata.labels["app"]
        feas_nodes = [
            enc.row_names[r]
            for r in np.nonzero(feasible_tpl[t])[0]
            if enc.row_names[r]
        ]
        min_cnt = min(svc_count(app, nm) for nm in feas_nodes)
        got = enc.row_names[int(chosen[i])]
        assert svc_count(app, got) == min_cnt, (
            f"seed={seed} pod={pod.metadata.name} app={app}: placed on {got} "
            f"(count {svc_count(app, got)}), min feasible count {min_cnt}"
        )


@pytest.mark.parametrize("seed", range(6))
def test_fuzz_rtc_nondefault_shape_matches_host_plugin(seed):
    """Score-differential for a NON-default RequestedToCapacityRatio shape
    (r4 verdict #7: the device kernel used to hardcode the default): with
    RTC as the ONLY weighted component and the spread-style piecewise
    shape {0%:10, 50%:4, 100%:0}, every kernel placement must land on a
    node whose host-plugin interpolated score is maximal among that pod's
    feasible nodes at batch start."""
    from kubernetes_tpu.ops.lattice import NUM_SCORE_COMPONENTS, SC_REQ_TO_CAP
    from kubernetes_tpu.scheduler.framework.plugins.noderesources import (
        RequestedToCapacityRatio,
    )

    shape = ((0.0, 10.0), (50.0, 4.0), (100.0, 0.0))
    rng = random.Random(seed)
    n_nodes = rng.randrange(5, 11)
    enc = SnapshotEncoder()
    nodes, infos = [], {}
    for i in range(n_nodes):
        n = Node(
            metadata=ObjectMeta(name=f"n{i}", namespace=""),
            status=NodeStatus(
                capacity={"cpu": "8", "memory": "32Gi", "pods": "50"}
            ),
        )
        nodes.append(n)
        enc.add_node(n)
        infos[n.metadata.name] = NodeInfo(n)
    # uneven pre-load so utilization differs per node
    for j in range(n_nodes * 3):
        node = rng.choice(nodes)
        p = Pod(
            metadata=ObjectMeta(name=f"pre-{j}"),
            spec=PodSpec(
                node_name=node.metadata.name,
                containers=[
                    Container(
                        requests={
                            "cpu": f"{rng.randrange(1, 20) * 100}m",
                            "memory": f"{rng.randrange(1, 8)}Gi",
                        }
                    )
                ],
            ),
        )
        enc.add_pod(node.metadata.name, p)
        infos[node.metadata.name].add_pod(p)

    pod = Pod(
        metadata=ObjectMeta(name="probe"),
        spec=PodSpec(
            containers=[Container(requests={"cpu": "500m", "memory": "1Gi"})]
        ),
    )
    tc = TemplateCache(enc)
    eb = tc.encode([pod], pad_to=1)
    ptab = build_pair_table(enc, eb.tpl_np, eb.num_templates)
    snap = enc.flush()
    weights = np.zeros(NUM_SCORE_COMPONENTS, np.float32)
    weights[SC_REQ_TO_CAP] = 1.0
    kern = make_wave_kernel_jit(enc.cfg.v_cap, 64, 4, rtc_shape=shape)
    _new, res = kern(snap, eb.batch, ptab, weights, jax.random.PRNGKey(seed))
    chosen, placed, feasible_tpl = jax.device_get(
        (res.chosen, res.placed, res.feasible_tpl)
    )
    enc.invalidate_device()
    assert placed[0], seed

    host = RequestedToCapacityRatio(list(shape))
    snapshot = Snapshot(list(infos.values()))

    def host_score(node_name):
        s, _ = host.score(None, pod, node_name, snapshot=snapshot)
        return s

    feas = [
        enc.row_names[r]
        for r in np.nonzero(feasible_tpl[0])[0]
        if enc.row_names[r]
    ]
    best = max(host_score(nm) for nm in feas)
    got = enc.row_names[int(chosen[0])]
    assert abs(host_score(got) - best) < 1e-3, (
        f"seed={seed}: placed on {got} (host score {host_score(got):.2f}) "
        f"but max feasible host score is {best:.2f}"
    )
