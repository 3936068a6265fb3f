"""benchmark/reference_rules/pod_topology_spread.py: the plain reference's
filter for `DoNotSchedule` topology spread constraints, held to hand-made
bind sequences and to the host's own plugin
(scheduler/framework/plugins/podtopologyspread.py) on seeded random ones.
The rule imports nothing of the program; this test does, to compare."""

import json
import pathlib
import random
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "benchmark"))

from harness.catalog import Catalog  # noqa: E402
from harness.check import ReferenceCluster, check_placements  # noqa: E402

ZONE = "topology.kubernetes.io/zone"
CELL = "perf5k-topologyspread.backlog"


@pytest.fixture(scope="module")
def rule():
    cat = Catalog(str(REPO))
    (mod,) = cat.reference_rules(cat.config("perf5k-topologyspread"))
    return mod


def _node(name, zone=None):
    labels = {} if zone is None else {ZONE: zone}
    return {"metadata": {"name": name, "labels": labels},
            "status": {"allocatable": {"cpu": "64", "memory": "256Gi",
                                       "pods": 1000}}}


def _pod(name, ns="bench", labels=None, skew=1, selector=(("color", "blue"),),
         when="DoNotSchedule", key=ZONE):
    spec = {"containers": [{"requests": {"cpu": "100m", "memory": "500Mi"}}]}
    if skew is not None:
        spec["topologySpreadConstraints"] = [{
            "maxSkew": skew, "topologyKey": key, "whenUnsatisfiable": when,
            # the codec's manifest form: matchLabels as a list of pairs
            "labelSelector": {"matchLabels": [list(kv) for kv in selector]}}]
    return {"metadata": {"name": name, "namespace": ns,
                         "labels": {"color": "blue"} if labels is None else labels},
            "spec": spec}


def _cluster(rule, zones=("a", "b", "c"), keyless=0):
    nodes = [_node(f"n-{z}-{i}", z) for z in zones for i in range(2)]
    nodes += [_node(f"bare-{i}") for i in range(keyless)]
    return ReferenceCluster(nodes, [rule])


def _bind_all(ref, binds):
    """[(reason or None)] of each bind at its turn."""
    out = []
    for pod, node in binds:
        out.append(ref.why_not(pod, node))
        ref.bind(pod, node)
    return out


def test_a_skew_of_two_is_refused_and_a_level_fill_is_not(rule):
    ref = _cluster(rule)
    # a, b, c level by level: every bind stands at the minimum
    level = [(_pod(f"p{i}"), f"n-{'abc'[i % 3]}-{i % 2}") for i in range(9)]
    assert _bind_all(ref, level) == [None] * 9
    # 3/3/3: one more on `a` is a skew of 1, a second one of 2
    assert ref.why_not(_pod("x"), "n-a-0") is None
    ref.bind(_pod("x"), "n-a-0")
    why = ref.why_not(_pod("y"), "n-a-1")
    assert why and why.startswith("pod_topology_spread:") and "skew 2" in why
    assert ref.why_not(_pod("y"), "n-b-0") is None
    # maxSkew 2 lets the same bind through
    assert ref.why_not(_pod("y", skew=2), "n-a-1") is None


def test_a_node_without_the_key_is_refused(rule):
    ref = _cluster(rule, keyless=1)
    why = ref.why_not(_pod("p"), "bare-0")
    assert why == f"pod_topology_spread: node lacks topology key {ZONE}"
    # a pod with no constraint may go there
    assert ref.why_not(_pod("q", skew=None), "bare-0") is None
    # and a keyless node is no domain: it does not hold the minimum at 0
    for i, z in enumerate("abc"):
        ref.bind(_pod(f"p{i}"), f"n-{z}-0")
    ref.bind(_pod("p3"), "n-a-0")
    assert ref.why_not(_pod("p4"), "n-a-1") is not None  # 3 - 1 > 1


def test_another_namespace_or_label_is_not_counted(rule):
    ref = _cluster(rule)
    others = [(_pod("o1", ns="other"), "n-a-0"), (_pod("o2", ns="other"), "n-a-1"),
              (_pod("r1", labels={"color": "red"}, skew=None), "n-a-0"),
              (_pod("r2", labels={"color": "red"}, skew=None), "n-a-1")]
    assert _bind_all(ref, others) == [None, "pod_topology_spread: "
                                      f"{ZONE}=a would hold 2 matching pods "
                                      "against a least domain of 0: skew 2 > "
                                      "maxSkew 1", None, None]
    # four pods on `a`, none of them a blue pod of `bench`: a is still empty
    assert ref.why_not(_pod("p"), "n-a-0") is None


def test_the_pods_own_match_is_counted(rule):
    ref = _cluster(rule)
    ref.bind(_pod("p0"), "n-a-0")
    # a blue pod on `a` again: 1 + itself - 0 = 2 > 1
    assert ref.why_not(_pod("p1"), "n-a-1") is not None
    # a red pod whose constraint selects blue does not count itself: 1 - 0
    red = _pod("r", labels={"color": "red"})
    assert ref.why_not(red, "n-a-1") is None


def test_an_empty_zone_counts_nought(rule):
    ref = _cluster(rule, zones=("a", "b", "c", "d"))
    for i, z in enumerate("abc"):
        ref.bind(_pod(f"p{i}"), f"n-{z}-0")
    # 1/1/1/0: `d` holds the minimum, so a second pod anywhere else is refused
    for z in "abc":
        assert ref.why_not(_pod("q"), f"n-{z}-1") is not None
    assert ref.why_not(_pod("q"), "n-d-0") is None


def test_schedule_anyway_is_not_a_filter(rule):
    ref = _cluster(rule)
    soft = [(_pod(f"s{i}", when="ScheduleAnyway"), "n-a-0") for i in range(4)]
    assert _bind_all(ref, soft) == [None] * 4


def test_the_standing_rules_still_run_beside_it(rule):
    """The rule is asked after the four standing ones and told of every
    bind; check_placements names both kinds of violation."""
    nodes = [_node("n-a-0", "a"), _node("n-b-0", "b")]
    pods = {f"bench/p{i}": _pod(f"p{i}") for i in range(3)}
    order = [("bench/p0", "n-a-0"), ("bench/p1", "n-a-0"), ("bench/p2", "gone")]
    out = check_placements(nodes, order, pods.get, [], [rule])
    assert len(out) == 2
    assert "pod_topology_spread" in out[0] and "unknown node" in out[1]


# -- the rule against the host's own plugin ----------------------------------


def _host_verdict(plugin_state, pod, node_name):
    from kubernetes_tpu.scheduler.cache.nodeinfo import Snapshot
    from kubernetes_tpu.scheduler.framework.interface import CycleState, is_success
    from kubernetes_tpu.scheduler.framework.plugins.podtopologyspread import (
        PodTopologySpreadPlugin,
    )

    infos = plugin_state
    snapshot = Snapshot(list(infos.values()))
    plugin = PodTopologySpreadPlugin(lambda: snapshot)
    state = CycleState()
    plugin.pre_filter(state, pod)
    return is_success(plugin.filter(state, pod, infos[node_name]))


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_the_rule_agrees_with_the_host_plugin_on_random_binds(rule, seed):
    from kubernetes_tpu.api import objects as v1
    from kubernetes_tpu.api.serialization import from_dict, to_dict
    from kubernetes_tpu.scheduler.cache.nodeinfo import NodeInfo

    rng = random.Random(seed)
    zones = [f"z{i}" for i in range(rng.randrange(2, 5))]
    manifests = []
    for i in range(rng.randrange(6, 13)):
        zone = rng.choice(zones + [None]) if i >= len(zones) else zones[i]
        manifests.append(_node(f"n{i}", zone))
    ref = ReferenceCluster(manifests, [rule])
    infos = {m["metadata"]["name"]: NodeInfo(from_dict(v1.Node, dict(
        m, metadata=dict(m["metadata"], namespace="")))) for m in manifests}
    refused = agreed = 0
    for j in range(120):
        manifest = _pod(
            f"p{j}", ns=rng.choice(["bench", "bench", "other"]),
            labels={"color": rng.choice(["blue", "blue", "red"])},
            skew=rng.choice([1, 1, 2, None]),
            selector=(("color", rng.choice(["blue", "red"])),),
            when=rng.choice(["DoNotSchedule", "DoNotSchedule", "ScheduleAnyway"]))
        # the manifest the codec writes for the same pod, as the run sees it
        pod = from_dict(v1.Pod, manifest)
        manifest = json.loads(json.dumps(to_dict(pod)))
        node = rng.choice(list(infos))
        mine = rule.why_not(manifest, node, ref) is None
        assert mine == _host_verdict(infos, pod, node), (seed, j, manifest, node)
        agreed += 1
        refused += not mine
        # bind it wherever it was asked: the state runs unlevel on purpose
        ref.bind(manifest, node)
        bound = pod.deep_copy()
        bound.spec.node_name = node
        infos[node].add_pod(bound)
    assert agreed == 120 and 5 < refused < 115  # both verdicts were seen
