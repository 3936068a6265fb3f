"""Smoke tests: encoder + lattice kernel end-to-end on tiny clusters."""

import numpy as np
import jax

from kubernetes_tpu.api.objects import (
    Affinity,
    Container,
    ContainerPort,
    Node,
    NodeAffinity,
    NodeSelector,
    NodeSelectorRequirement,
    NodeSelectorTerm,
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
)
from kubernetes_tpu.api.selectors import LabelSelector
from kubernetes_tpu.ops.batch import encode_pod_batch
from kubernetes_tpu.ops.encoding import SnapshotEncoder
from kubernetes_tpu.ops.lattice import DEFAULT_WEIGHTS, make_schedule_batch
import jax.numpy as jnp


def make_node(name, cpu="4", mem="32Gi", labels=None, taints=None, unsched=False):
    return Node(
        metadata=ObjectMeta(name=name, namespace="", labels=labels or {}),
        spec=NodeSpec(unschedulable=unsched, taints=taints or []),
        status=NodeStatus(allocatable={"cpu": cpu, "memory": mem, "pods": 110}),
    )


def make_pod(name, cpu="1", mem="1Gi", ns="default", labels=None, **spec_kw):
    return Pod(
        metadata=ObjectMeta(name=name, namespace=ns, labels=labels or {}),
        spec=PodSpec(
            containers=[Container(requests={"cpu": cpu, "memory": mem})], **spec_kw
        ),
    )


def run(enc, pods, weights=None):
    # order matters: encoding may intern new predicates (back-filling counts),
    # so the device flush must come after batch encoding.
    eb = encode_pod_batch(enc, pods)
    snap = enc.flush()
    kern = make_schedule_batch(enc.cfg.v_cap)
    w = jnp.asarray(weights if weights is not None else DEFAULT_WEIGHTS)
    return kern(snap, eb.batch, w, jax.random.PRNGKey(0))


def test_basic_fit_and_least_allocated():
    enc = SnapshotEncoder()
    for i in range(4):
        enc.add_node(make_node(f"n{i}", cpu="4"))
    # n0 is loaded: 3 cpu used
    enc.add_pod("n0", make_pod("existing", cpu="3"))
    res = run(enc, [make_pod("p", cpu="2")])
    chosen = int(res.chosen[0])
    assert chosen != -1
    assert enc.row_names[chosen] != "n0"  # least-allocated avoids loaded node
    assert int(res.feasible_count[0]) == 3  # n0 has only 1 cpu free

def test_resources_infeasible():
    enc = SnapshotEncoder()
    enc.add_node(make_node("n0", cpu="2"))
    enc.add_pod("n0", make_pod("existing", cpu="1500m"))
    res = run(enc, [make_pod("p", cpu="1")])
    assert int(res.chosen[0]) == -1
    assert int(res.feasible_count[0]) == 0
    assert bool(res.resolvable[0][0])  # preemption might help


def test_in_batch_resource_conflict():
    """Two pods that both fit an empty node, but not together — the scan
    carry must route the second elsewhere."""
    enc = SnapshotEncoder()
    enc.add_node(make_node("n0", cpu="3"))
    enc.add_node(make_node("n1", cpu="3"))
    res = run(enc, [make_pod("a", cpu="2"), make_pod("b", cpu="2")])
    rows = {int(res.chosen[0]), int(res.chosen[1])}
    assert rows == {0, 1}


def test_node_selector_and_affinity():
    enc = SnapshotEncoder()
    enc.add_node(make_node("gpu", labels={"accel": "gpu", "zone": "z1"}))
    enc.add_node(make_node("cpu", labels={"zone": "z2"}))
    res = run(enc, [make_pod("p", node_selector={"accel": "gpu"})])
    assert enc.row_names[int(res.chosen[0])] == "gpu"
    aff = Affinity(
        node_affinity=NodeAffinity(
            required=NodeSelector(
                terms=(
                    NodeSelectorTerm(
                        match_expressions=(
                            NodeSelectorRequirement("zone", "In", ("z2",)),
                        )
                    ),
                )
            )
        )
    )
    res = run(enc, [make_pod("q", affinity=aff)])
    assert enc.row_names[int(res.chosen[0])] == "cpu"


def test_taints_and_tolerations():
    enc = SnapshotEncoder()
    enc.add_node(make_node("tainted", taints=[Taint("dedicated", "db", "NoSchedule")]))
    enc.add_node(make_node("open"))
    res = run(enc, [make_pod("p")])
    assert enc.row_names[int(res.chosen[0])] == "open"
    res = run(
        enc,
        [
            make_pod(
                "q",
                tolerations=[
                    Toleration(key="dedicated", operator="Equal", value="db", effect="NoSchedule")
                ],
            )
        ],
    )
    assert int(res.feasible_count[0]) == 2


def test_unschedulable_node():
    enc = SnapshotEncoder()
    enc.add_node(make_node("off", unsched=True))
    enc.add_node(make_node("on"))
    res = run(enc, [make_pod("p")])
    assert enc.row_names[int(res.chosen[0])] == "on"
    assert int(res.feasible_count[0]) == 1


def test_node_name_pinned():
    enc = SnapshotEncoder()
    enc.add_node(make_node("n0"))
    enc.add_node(make_node("n1"))
    res = run(enc, [make_pod("p", node_name="n1")])
    assert enc.row_names[int(res.chosen[0])] == "n1"


def test_pod_anti_affinity_existing():
    """Existing pod with anti-affinity keeps matching pods off its zone."""
    enc = SnapshotEncoder()
    enc.add_node(make_node("a1", labels={"zone": "z1"}))
    enc.add_node(make_node("b1", labels={"zone": "z2"}))
    anti = Affinity(
        pod_anti_affinity=PodAntiAffinity(
            required=(
                PodAffinityTerm(
                    label_selector=LabelSelector.make(match_labels={"app": "web"}),
                    topology_key="zone",
                ),
            )
        )
    )
    holder = make_pod("holder", labels={"app": "db"}, affinity=anti)
    enc.add_pod("a1", holder)
    res = run(enc, [make_pod("p", labels={"app": "web"})])
    assert enc.row_names[int(res.chosen[0])] == "b1"
    # non-matching pod can go anywhere
    res = run(enc, [make_pod("q", labels={"app": "cache"})])
    assert int(res.feasible_count[0]) == 2


def test_incoming_pod_affinity():
    enc = SnapshotEncoder()
    enc.add_node(make_node("a1", labels={"zone": "z1"}))
    enc.add_node(make_node("b1", labels={"zone": "z2"}))
    enc.add_pod("a1", make_pod("web-1", labels={"app": "web"}))
    aff = Affinity(
        pod_affinity=PodAffinity(
            required=(
                PodAffinityTerm(
                    label_selector=LabelSelector.make(match_labels={"app": "web"}),
                    topology_key="zone",
                ),
            )
        )
    )
    res = run(enc, [make_pod("p", affinity=aff)])
    assert enc.row_names[int(res.chosen[0])] == "a1"
    # anti-affinity on incoming pod avoids z1
    anti = Affinity(
        pod_anti_affinity=PodAntiAffinity(
            required=(
                PodAffinityTerm(
                    label_selector=LabelSelector.make(match_labels={"app": "web"}),
                    topology_key="zone",
                ),
            )
        )
    )
    res = run(enc, [make_pod("q", affinity=anti)])
    assert enc.row_names[int(res.chosen[0])] == "b1"


def test_affinity_first_pod_carveout():
    """First pod of a group: affinity to itself is allowed when nothing matches."""
    enc = SnapshotEncoder()
    enc.add_node(make_node("a1", labels={"zone": "z1"}))
    aff = Affinity(
        pod_affinity=PodAffinity(
            required=(
                PodAffinityTerm(
                    label_selector=LabelSelector.make(match_labels={"app": "solo"}),
                    topology_key="zone",
                ),
            )
        )
    )
    res = run(enc, [make_pod("p", labels={"app": "solo"}, affinity=aff)])
    assert int(res.chosen[0]) == 0
    # but a pod NOT matching its own selector stays pending
    res = run(enc, [make_pod("q", labels={"app": "other"}, affinity=aff)])
    assert int(res.chosen[0]) == -1


def test_topology_spread_hard():
    enc = SnapshotEncoder()
    enc.add_node(make_node("a1", labels={"zone": "z1"}))
    enc.add_node(make_node("a2", labels={"zone": "z1"}))
    enc.add_node(make_node("b1", labels={"zone": "z2"}))
    sel = LabelSelector.make(match_labels={"app": "web"})
    tsc = TopologySpreadConstraint(
        max_skew=1, topology_key="zone", when_unsatisfiable="DoNotSchedule",
        label_selector=sel,
    )
    enc.add_pod("a1", make_pod("w1", labels={"app": "web"}))
    # z1 has 1, z2 has 0; new web pod with maxSkew 1 must go to z2
    res = run(
        enc,
        [make_pod("p", labels={"app": "web"}, topology_spread_constraints=[tsc])],
    )
    assert enc.row_names[int(res.chosen[0])] == "b1"


def test_host_ports():
    enc = SnapshotEncoder()
    enc.add_node(make_node("n0"))
    enc.add_node(make_node("n1"))
    holder = Pod(
        metadata=ObjectMeta(name="holder"),
        spec=PodSpec(
            containers=[Container(ports=[ContainerPort(80, host_port=8080)])]
        ),
    )
    enc.add_pod("n0", holder)
    contender = Pod(
        metadata=ObjectMeta(name="contender"),
        spec=PodSpec(
            containers=[
                Container(
                    requests={"cpu": "100m"},
                    ports=[ContainerPort(80, host_port=8080)],
                )
            ]
        ),
    )
    res = run(enc, [contender])
    assert enc.row_names[int(res.chosen[0])] == "n1"
    assert int(res.feasible_count[0]) == 1


def test_batch_padding_invalid_rows():
    enc = SnapshotEncoder()
    enc.add_node(make_node("n0"))
    eb = encode_pod_batch(enc, [make_pod("p")], pad_to=4)
    kern = make_schedule_batch(enc.cfg.v_cap)
    res = kern(enc.flush(), eb.batch, jnp.asarray(DEFAULT_WEIGHTS), jax.random.PRNGKey(0))
    assert int(res.chosen[0]) == 0
    assert all(int(res.chosen[i]) == -1 for i in range(1, 4))


def test_add_pods_bulk_matches_sequential():
    """The vectorized bulk-assume scatter must leave the host masters
    byte-identical to per-pod add_pod."""
    import numpy as np

    from kubernetes_tpu.ops.encoding import SnapshotEncoder

    def build(n_pods, bulk):
        enc = SnapshotEncoder()
        for i in range(8):
            enc.add_node(make_node(f"n{i}"))
        pods = []
        for i in range(n_pods):
            p = make_pod(
                f"p{i}",
                cpu="250m" if i % 2 else "100m",
                labels={"app": "a" if i % 3 else "b"},
            )
            pods.append(p)
        # intern a predicate so match vectors are non-trivial
        from kubernetes_tpu.api.selectors import LabelSelector

        enc.intern_predicate(
            frozenset({"default"}), LabelSelector.make({"app": "a"})
        )
        items = []
        for i, p in enumerate(pods):
            p.spec.node_name = f"n{i % 8}"
            proto = enc.pod_proto(p) if i % 2 else None  # mixed proto/None
            items.append((f"n{i % 8}", p, i % 3, proto))
        if bulk:
            enc.add_pods_bulk(items)
        else:
            for node, p, band, proto in items:
                enc.add_pod(node, p, device_synced=True, prio_band=band, proto=proto)
        return enc

    a = build(24, bulk=False)
    b = build(24, bulk=True)
    for field in (
        "m_req", "m_nonzero", "m_prio_req", "m_sel_counts",
        "m_eterm_w", "m_port_counts",
    ):
        np.testing.assert_array_equal(
            getattr(a, field), getattr(b, field), err_msg=field
        )
    assert set(
        (r, k) for r, d in a._pods.items() for k in d
    ) == set((r, k) for r, d in b._pods.items() for k in d)


def test_wave_score_refresh_sees_in_batch_commits():
    """Serial-fidelity (SURVEY §7 hard part (c)): a pod committing in a
    LATER wave must score nodes with the batch's earlier commits applied.
    Setup: n1 (10 cpu) statically beats n2 (9 cpu); two 6-cpu pods and a
    1-cpu pod batch together. The 6-cpu pair forces the small pod past
    wave 1 (prefix-fit conservatism); with refresh it then prefers the
    emptier n2, without refresh it returns to the statically-best n1."""
    from kubernetes_tpu.ops.lattice import (
        NUM_SCORE_COMPONENTS,
        SC_LEAST_ALLOC,
    )
    from kubernetes_tpu.ops.templates import TemplateCache, build_pair_table
    from kubernetes_tpu.ops.wavelattice import make_wave_kernel_jit

    def build():
        enc = SnapshotEncoder()
        enc.add_node(make_node("n1", cpu="10", mem="64Gi"))
        enc.add_node(make_node("n2", cpu="9", mem="64Gi"))
        tc = TemplateCache(enc)
        pods = [
            make_pod("big-0", cpu="6"),
            make_pod("big-1", cpu="6"),
            make_pod("small", cpu="1"),
        ]
        eb = tc.encode(pods, pad_to=4)
        ptab = build_pair_table(enc, eb.tpl_np, eb.num_templates)
        snap = enc.flush()
        return enc, eb, ptab, snap

    weights = np.zeros(NUM_SCORE_COMPONENTS, np.float32)
    weights[SC_LEAST_ALLOC] = 1.0

    placements = {}
    for refresh in (True, False):
        enc, eb, ptab, snap = build()
        kern = make_wave_kernel_jit(
            enc.cfg.v_cap, 8, 4, 1.0, False, refresh
        )
        _snap2, res = kern(
            snap, eb.batch, ptab, weights, jax.random.PRNGKey(0)
        )
        chosen = jax.device_get(res.chosen)
        placed = jax.device_get(res.placed)
        assert placed[:3].all(), (refresh, placed)
        placements[refresh] = {
            p.metadata.name: enc.row_names[int(chosen[i])]
            for i, p in enumerate(eb.pods[:3])
        }
    # the big pair lands one per node either way (capacity)
    for ref, pl in placements.items():
        assert {pl["big-0"], pl["big-1"]} == {"n1", "n2"}, (ref, pl)
    # the refreshed kernel steers the wave-2 small pod to the node the
    # batch left emptier; the static kernel returns to the statically-best
    # n1 — BOTH arms are pinned so a refresh no-op regression is caught
    assert placements[True]["small"] == "n2", placements
    assert placements[False]["small"] == "n1", placements
