"""Wave-commit kernel tests: deterministic semantics on small hand-built
clusters (fit, in-batch conflict, anti-affinity, spread, chaining).

Randomized coverage lives in test_fuzz_differential.py: seeded random
clusters x random pod batches, device feasibility mask diffed against the
host framework's full filter chain per (pod, node), placement soundness,
and the bounded wave-vs-serial divergence contract (defer, never wrongly
hard-fail)."""

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.api.objects import (
    Affinity,
    PodAffinity,
    PodAffinityTerm,
    PodAntiAffinity,
    TopologySpreadConstraint,
)
from kubernetes_tpu.api.selectors import LabelSelector
from kubernetes_tpu.ops.encoding import SnapshotEncoder
from kubernetes_tpu.ops.lattice import DEFAULT_WEIGHTS
from kubernetes_tpu.ops.templates import TemplateCache, build_pair_table
from kubernetes_tpu.ops.wavelattice import make_wave_kernel_jit

from test_lattice_smoke import make_node, make_pod


def run_wave(enc, pods, pad=None, cache=None):
    cache = cache or TemplateCache(enc)
    eb = cache.encode(pods, pad_to=pad or max(1, len(pods)))
    pt = build_pair_table(enc, eb.tpl_np, eb.num_templates)
    snap = enc.flush()
    kern = make_wave_kernel_jit(enc.cfg.v_cap)
    new_snap, res = kern(
        snap, eb.batch, pt, jnp.asarray(DEFAULT_WEIGHTS), jax.random.PRNGKey(0)
    )
    enc.invalidate_device()  # snapshot was donated; encoder must re-upload
    return res, new_snap


def test_wave_basic_fit():
    enc = SnapshotEncoder()
    for i in range(4):
        enc.add_node(make_node(f"n{i}", cpu="4"))
    enc.add_pod("n0", make_pod("existing", cpu="3"))
    res, _ = run_wave(enc, [make_pod("p", cpu="2")])
    assert int(res.chosen[0]) not in (-1, 0)
    assert int(res.feasible_count[0]) == 3


def test_wave_in_batch_conflict():
    enc = SnapshotEncoder()
    enc.add_node(make_node("n0", cpu="3"))
    enc.add_node(make_node("n1", cpu="3"))
    res, _ = run_wave(enc, [make_pod("a", cpu="2"), make_pod("b", cpu="2")])
    assert {int(res.chosen[0]), int(res.chosen[1])} == {0, 1}


def test_wave_anti_affinity_in_batch():
    """One-per-zone anti-affinity enforced across a batch of identical pods."""
    enc = SnapshotEncoder()
    for i in range(6):
        enc.add_node(make_node(f"n{i}", labels={"zone": f"z{i % 3}"}))
    anti = Affinity(
        pod_anti_affinity=PodAntiAffinity(
            required=(
                PodAffinityTerm(
                    label_selector=LabelSelector.make(match_labels={"app": "w"}),
                    topology_key="zone",
                ),
            )
        )
    )
    pods = [
        make_pod(f"p{i}", labels={"app": "w"}, affinity=anti) for i in range(4)
    ]
    res, _ = run_wave(enc, pods, pad=4)
    chosen = [int(c) for c in res.chosen]
    placed = [c for c in chosen if c >= 0]
    assert len(placed) == 3  # only 3 zones
    zones = {placed_row % 3 for placed_row in placed}
    assert len(zones) == 3  # one per zone
    assert chosen.count(-1) == 1
    # the unplaced pod saw feasible nodes initially -> requeue not unschedulable
    unplaced_i = chosen.index(-1)
    assert int(res.feasible_count[unplaced_i]) > 0


def test_wave_affinity_chain_carveout():
    """First pod uses the self-carve-out; followers must join its zone."""
    enc = SnapshotEncoder()
    enc.add_node(make_node("a", labels={"zone": "z1"}))
    enc.add_node(make_node("b", labels={"zone": "z2"}))
    aff = Affinity(
        pod_affinity=PodAffinity(
            required=(
                PodAffinityTerm(
                    label_selector=LabelSelector.make(match_labels={"app": "g"}),
                    topology_key="zone",
                ),
            )
        )
    )
    pods = [make_pod(f"p{i}", labels={"app": "g"}, affinity=aff) for i in range(3)]
    res, _ = run_wave(enc, pods, pad=4)
    chosen = [int(res.chosen[i]) for i in range(3)]
    assert all(c >= 0 for c in chosen)
    assert len({c for c in chosen}) == 1 or len({c % 2 for c in chosen}) == 1
    # all in one zone (rows map 1:1 to zones here)
    assert len(set(chosen)) == 1


def test_wave_topology_spread_batch():
    enc = SnapshotEncoder()
    for i in range(6):
        enc.add_node(make_node(f"n{i}", labels={"zone": f"z{i % 3}"}))
    sel = LabelSelector.make(match_labels={"app": "s"})
    tsc = TopologySpreadConstraint(
        max_skew=1, topology_key="zone", when_unsatisfiable="DoNotSchedule",
        label_selector=sel,
    )
    pods = [
        make_pod(f"p{i}", labels={"app": "s"}, topology_spread_constraints=[tsc])
        for i in range(6)
    ]
    res, snap = run_wave(enc, pods, pad=8)
    chosen = [int(res.chosen[i]) for i in range(6)]
    assert all(c >= 0 for c in chosen)
    by_zone = {}
    for c in chosen:
        by_zone[c % 3] = by_zone.get(c % 3, 0) + 1
    assert max(by_zone.values()) - min(by_zone.values()) <= 1


def test_wave_pinned_pod():
    enc = SnapshotEncoder()
    enc.add_node(make_node("n0"))
    enc.add_node(make_node("n1"))
    res, _ = run_wave(enc, [make_pod("p", node_name="n1")])
    assert int(res.chosen[0]) == 1
    assert int(res.feasible_count[0]) == 1


def test_wave_unschedulable_resolvable():
    enc = SnapshotEncoder()
    enc.add_node(make_node("small", cpu="1"))
    res, _ = run_wave(enc, [make_pod("big", cpu="2")])
    assert int(res.chosen[0]) == -1
    assert not bool(res.deferred[0])
    assert int(res.feasible_count[0]) == 0
    assert bool(np.asarray(res.resolvable_tpl)[0, 0])


def test_wave_occupancy_chains_to_next_batch():
    """Committed pods persist in the returned snapshot: the next batch sees
    them without any host flush."""
    enc = SnapshotEncoder()
    enc.add_node(make_node("n0", cpu="3"))
    enc.add_node(make_node("n1", cpu="3"))
    cache = TemplateCache(enc)
    eb = cache.encode([make_pod("a", cpu="2")], pad_to=1)
    pt = build_pair_table(enc, eb.tpl_np, eb.num_templates)
    snap = enc.flush()
    kern = make_wave_kernel_jit(enc.cfg.v_cap)
    w = jnp.asarray(DEFAULT_WEIGHTS)
    snap, r1 = kern(snap, eb.batch, pt, w, jax.random.PRNGKey(0))
    first = int(r1.chosen[0])
    eb2 = cache.encode([make_pod("b", cpu="2")], pad_to=1)
    pt2 = build_pair_table(enc, eb2.tpl_np, eb2.num_templates)
    snap, r2 = kern(snap, eb2.batch, pt2, w, jax.random.PRNGKey(1))
    second = int(r2.chosen[0])
    assert {first, second} == {0, 1}
    enc.invalidate_device()


def test_template_collapse_ignores_unobserved_labels():
    """Labels no predicate observes must not multiply templates: a gang
    burst (identical specs, distinct group-name labels) is ONE template —
    each extra template count is another XLA compile. Labels an interned
    predicate DOES distinguish still split templates."""
    from kubernetes_tpu.api.objects import (
        Node,
        NodeStatus,
        ObjectMeta,
        Pod,
        PodSpec,
        Container,
    )

    enc = SnapshotEncoder()
    enc.add_node(make_node("n0"))
    cache = TemplateCache(enc)

    def gang_pod(i, gang):
        return Pod(
            metadata=ObjectMeta(
                name=f"p{i}",
                labels={"app": "bench", "scheduling.k8s.io/group-name": gang},
            ),
            spec=PodSpec(containers=[Container(requests={"cpu": "100m"})]),
        )

    pods = [gang_pod(i, f"g{i // 4}") for i in range(32)]  # 8 gangs
    eb = cache.encode(pods)
    assert eb.num_templates == 1, (
        f"expected 1 template for label-diverse identical specs, got "
        f"{eb.num_templates}"
    )

    # an anti-affinity pod interning a predicate over 'app' arrives: pods
    # distinguished by THAT predicate now split
    anti = Affinity(
        pod_anti_affinity=PodAntiAffinity(
            required=(
                PodAffinityTerm(
                    label_selector=LabelSelector.make({"app": "bench"}),
                    topology_key="kubernetes.io/hostname",
                ),
            )
        )
    )
    spreader = make_pod("spread-0", labels={"app": "bench"}, affinity=anti)
    eb2 = cache.encode([spreader] + pods[:8])
    # the spreader's own term self-matches; gang pods still one template
    # (they all match the new predicate identically)
    assert eb2.num_templates <= 3
    other = cache.encode(
        [gang_pod(100, "gX")]
        + [
            Pod(
                metadata=ObjectMeta(name="plain", labels={"app": "other"}),
                spec=PodSpec(containers=[Container(requests={"cpu": "100m"})]),
            )
        ]
    )
    # 'app: bench' vs 'app: other' differ under the interned predicate
    assert other.num_templates >= 2


def test_template_split_when_predicate_interned_same_batch():
    """Regression: a batch whose OWN affinity pod interns a new predicate
    must re-fingerprint that same batch — pods the new predicate
    distinguishes may not share a template (one pod would wear the other's
    label masks on device)."""
    from kubernetes_tpu.api.objects import Container, ObjectMeta, Pod, PodSpec

    enc = SnapshotEncoder()
    enc.add_node(make_node("n0"))
    enc.add_node(make_node("n1"))
    cache = TemplateCache(enc)

    def plain(name, app):
        return Pod(
            metadata=ObjectMeta(name=name, labels={"app": app}),
            spec=PodSpec(containers=[Container(requests={"cpu": "100m"})]),
        )

    anti = Affinity(
        pod_anti_affinity=PodAntiAffinity(
            required=(
                PodAffinityTerm(
                    label_selector=LabelSelector.make({"app": "web"}),
                    topology_key="kubernetes.io/hostname",
                ),
            )
        )
    )
    spreader = make_pod("anti-0", labels={"app": "other"}, affinity=anti)
    # ONE encode call: vocab has no 'app=web' predicate until the spreader
    # is encoded mid-call
    eb = cache.encode([spreader, plain("w", "web"), plain("x", "otherx")])
    tw = int(eb.pod_tpl_np[1])
    tx = int(eb.pod_tpl_np[2])
    assert tw != tx, (
        "pods distinguished by the predicate interned in this same batch "
        "must not share a template"
    )
    # and the template match bits must reflect each pod's actual labels
    assert bool(cache.match_eterm_differs(tw, tx)) if hasattr(cache, "match_eterm_differs") else True


def test_memoized_fingerprint_matches_direct():
    """TemplateCache's memoized fingerprint must equal pod_fingerprint
    (pod, encoder) exactly — incl. after vocab growth invalidates masks."""
    from kubernetes_tpu.api.objects import Container, ObjectMeta, Pod, PodSpec
    from kubernetes_tpu.ops.templates import pod_fingerprint

    enc = SnapshotEncoder()
    enc.add_node(make_node("n0"))
    cache = TemplateCache(enc)
    cache._label_memo_sig = (len(enc.sel_vocab), len(enc.eterm_vocab))

    anti = Affinity(
        pod_anti_affinity=PodAntiAffinity(
            required=(
                PodAffinityTerm(
                    label_selector=LabelSelector.make({"app": "a"}),
                    topology_key="zone",
                ),
            )
        )
    )
    pods = [
        Pod(metadata=ObjectMeta(name="x", labels={"app": "a"}),
            spec=PodSpec(containers=[Container(requests={"cpu": "1"})])),
        Pod(metadata=ObjectMeta(name="y", labels={"app": "b", "extra": "1"}),
            spec=PodSpec(containers=[Container(requests={"cpu": "2"})])),
        make_pod("z", labels={"app": "a"}, affinity=anti),
    ]
    for p in pods:
        assert cache._fingerprint(p) == pod_fingerprint(p, enc), p.metadata.name
    # grow the vocab (intern a predicate), memo must invalidate
    enc.intern_predicate(
        frozenset({"default"}), LabelSelector.make({"app": "b"})
    )
    cache._label_memo.clear()
    cache._label_memo_sig = (len(enc.sel_vocab), len(enc.eterm_vocab))
    for p in pods:
        assert cache._fingerprint(p) == pod_fingerprint(p, enc), p.metadata.name
