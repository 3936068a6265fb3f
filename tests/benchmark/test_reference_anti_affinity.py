"""The plain reference's standing anti-affinity rule (harness/check.py:
the incoming pod's own required `podAntiAffinity` terms, over every bind
replayed so far) against the host's InterPodAffinity filter
(scheduler/framework/plugins/interpodaffinity.py), which also enforces the
other direction: a resident's required term that matches the incoming pod.

`perf5k-antiaffinity` names no reference rule for that direction. With ONE
template (residents and measured pods carry the same labels and the same
term) both directions refuse exactly the same binds; the tests below hold
the reference to the host filter on seeded random binds of the
configuration's own templates, say where the two would part (a labelled
pod without the term: no configuration has one), and hold the
configuration's arithmetic: one pod a node, so every pod of a run needs a
node of its own. The reference imports nothing of the program; this test
does, to compare."""

import json
import pathlib
import random
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "benchmark"))

from harness.catalog import Catalog  # noqa: E402
from harness.check import ReferenceCluster, required_terms  # noqa: E402
from harness.supervisor import Cluster, offered_rate, warmup_burst  # noqa: E402

CONFIG = "perf5k-antiaffinity"
CELL = "perf5k-antiaffinity.steady"
HOST = "kubernetes.io/hostname"
NODES = 64


@pytest.fixture(scope="module")
def catalog():
    return Catalog(str(REPO))


@pytest.fixture(scope="module")
def config(catalog):
    return catalog.config(CONFIG)


class Pair:
    """The reference and the host's view of one 64-node cluster of the
    configuration, told of the same binds."""

    def __init__(self, config, seed):
        from kubernetes_tpu.api import objects as v1
        from kubernetes_tpu.api.serialization import from_dict
        from kubernetes_tpu.scheduler.cache.nodeinfo import NodeInfo

        self.cluster = Cluster(config, seed, NODES)
        self.ref = ReferenceCluster(self.cluster.node_manifests)
        self.infos = {
            m["metadata"]["name"]: NodeInfo(from_dict(v1.Node, m))
            for m in self.cluster.node_manifests}

    def manifest(self, name, template="measured"):
        self.cluster.pod_body(name, template, None)
        return self.cluster.manifest_of(f"{self.cluster.ns}/{name}")

    def plain(self, name):
        """A pod of the run's namespace with neither the label nor the term."""
        m = self.manifest(name)
        del m["metadata"]["labels"], m["spec"]["affinity"]
        return m

    def pod(self, manifest):
        from kubernetes_tpu.api import objects as v1
        from kubernetes_tpu.api.serialization import from_dict

        return from_dict(v1.Pod, manifest)

    def verdicts(self, manifest, node):
        """(the reference admits, the host filter admits)."""
        from kubernetes_tpu.scheduler.cache.nodeinfo import Snapshot
        from kubernetes_tpu.scheduler.framework.interface import (
            CycleState, is_success)
        from kubernetes_tpu.scheduler.framework.plugins.interpodaffinity import (
            InterPodAffinityPlugin)

        snapshot = Snapshot(list(self.infos.values()))
        plugin = InterPodAffinityPlugin(lambda: snapshot)
        state = CycleState()
        pod = self.pod(manifest)
        st = plugin.pre_filter(state, pod)
        host = is_success(st) and is_success(
            plugin.filter(state, pod, self.infos[node]))
        return self.ref.why_not(manifest, node) is None, host

    def bind(self, manifest, node):
        self.ref.bind(manifest, node)
        bound = self.pod(manifest).deep_copy()
        bound.spec.node_name = node
        self.infos[node].add_pod(bound)


def test_the_templates_carry_the_sources_one_term(config):
    """Both templates: labels color=green, name=test; ONE required term,
    selector color=green on the hostname key, in the form check.py and the
    codec both read; the resident differs by `spec.nodeName` alone."""
    measured = config["pod_templates"]["measured"]
    resident = json.loads(json.dumps(config["pod_templates"]["resident"]))
    assert resident["spec"].pop("nodeName") == "$NODE"
    assert resident == measured
    assert measured["metadata"]["labels"] == {"color": "green", "name": "test"}
    assert required_terms(measured, "podAntiAffinity") == [
        ({"color": "green"}, HOST)]
    assert required_terms(measured, "podAffinity") == []
    assert config["reference_rules"] == []
    # one a node: the residents sit on as many distinct nodes as they are
    full = Cluster(config, 2**31 + 5, None)
    assert len(full.residents) == config["residents"]["count"] == 1000
    assert len({node for _n, _t, node in full.residents}) == 1000


def test_the_second_pod_on_a_node_is_refused_by_both(config):
    pair = Pair(config, 1)
    first, second = pair.manifest("a"), pair.manifest("b")
    assert pair.verdicts(first, "node-3") == (True, True)
    pair.bind(first, "node-3")
    assert pair.verdicts(second, "node-3") == (False, False)
    assert "already in kubernetes.io/hostname=node-3" in pair.ref.why_not(
        second, "node-3")
    # every other node still takes it
    assert pair.verdicts(second, "node-4") == (True, True)


def test_a_pod_without_the_label_is_admitted_beside_one_with_it(config):
    pair = Pair(config, 2)
    pair.bind(pair.manifest("a"), "node-0")
    plain = pair.plain("plain")
    assert pair.verdicts(plain, "node-0") == (True, True)
    pair.bind(plain, "node-0")
    # and a green pod is still refused there, admitted beside a plain one
    assert pair.verdicts(pair.manifest("b"), "node-0") == (False, False)
    pair.bind(pair.plain("plain2"), "node-1")
    assert pair.verdicts(pair.manifest("c"), "node-1") == (True, True)


def test_where_the_two_would_part_no_configuration_goes(config, catalog):
    """The direction the reference does not replay: a pod that carries the
    LABEL and not the term is refused by the host (the resident's term
    matches it) and admitted by the reference. A configuration with such a
    pod needs a reference rule; none has one."""
    pair = Pair(config, 3)
    pair.bind(pair.manifest("a"), "node-0")
    labelled = pair.manifest("b")
    del labelled["spec"]["affinity"]
    assert pair.verdicts(labelled, "node-0") == (True, False)
    for c in catalog.bench["configs"]:
        templates = catalog.config(c["name"])["pod_templates"].values()
        with_term = [t for t in templates
                     if required_terms(t, "podAntiAffinity")]
        # where any template carries a required anti-affinity term, every
        # template does, and the same one
        assert not with_term or all(
            required_terms(t, "podAntiAffinity")
            == required_terms(with_term[0], "podAntiAffinity")
            and t["metadata"].get("labels") == with_term[0]["metadata"]["labels"]
            for t in templates), c["name"]


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_the_reference_agrees_with_the_host_filter_on_random_binds(config, seed):
    """Seeded residents as the run creates them, then 150 tries of the
    configuration's own templates (and a plain pod) on random nodes of a
    64-node cluster, each bound wherever BOTH admit it: the same verdict
    every time, both verdicts seen, never two green pods on a node."""
    rng = random.Random(seed)
    pair = Pair(config, seed)
    c = pair.cluster
    # the residents, one a node, replayed as the supervisor's watch would
    for name, tpl, node in c.residents:
        c.pod_body(name, tpl, node)
        m = c.manifest_of(f"{c.ns}/{name}")
        assert pair.verdicts(m, node) == (True, True)
        pair.bind(m, node)
    refused = admitted = 0
    green_on = {node: 1 for _n, _t, node in c.residents}
    for j in range(150):
        kind = rng.choice(["measured", "measured", "measured", "plain"])
        manifest = (pair.plain if kind == "plain" else pair.manifest)(f"p{j}")
        node = f"node-{rng.randrange(NODES)}"
        mine, host = pair.verdicts(manifest, node)
        assert mine == host, (seed, j, kind, node)
        if kind == "measured":
            assert mine == (green_on.get(node, 0) == 0)
        else:
            assert mine
        refused += not mine
        admitted += mine
        if mine:
            pair.bind(manifest, node)
            if kind == "measured":
                green_on[node] = green_on.get(node, 0) + 1
    assert max(green_on.values()) == 1
    assert refused > 10 and admitted > 10  # both verdicts were seen


@pytest.mark.parametrize("scale", ["full", "rehearsal"])
def test_every_pod_of_a_run_has_a_node_of_its_own(catalog, config, scale):
    """One pod a node: residents + warm-up burst + warm-up trickle +
    window must not pass the nodes, at the full size under `run_seconds`
    and at `rehearsal_nodes` under the rehearsal's 4 s (its floors: 20
    pods/s, 10 burst pods). Read from the files: a re-rating that
    overfills the cluster fails here and not as `unbound` on the chip."""
    traffic = catalog.traffic(catalog.cell(CELL))
    full = config["nodes"]["count"]
    if scale == "full":
        nodes, seconds = full, float(catalog.bench["run_seconds"])
    else:
        from test_benchmark_rehearsal import SECONDS

        nodes, seconds = catalog.rehearsal_nodes(config), float(SECONDS)
    share = nodes / full
    rate = offered_rate(traffic, share)
    residents = len(Cluster(config, 7, None if scale == "full" else nodes).residents)
    pods = (residents + warmup_burst(traffic, share)
            + int(rate * traffic["warmup"]["trickle_s"] + 1e-9)
            + int(rate * seconds + 1e-9))
    assert pods <= nodes, (residents, pods, nodes)
    if scale == "full":
        assert (residents, pods) == (1000, 1000 + 600 + 120 + 3060)
        # the window's last pod still chooses among 4.4% of the cluster
        assert nodes - pods + 1 >= 0.04 * nodes
    else:
        assert nodes == 256 and pods == 51 + 31 + 40 + 80
