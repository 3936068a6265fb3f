"""benchmark/reference_rules/preferred_pod_affinity_score.py: the plain
reference's score rule, held to hand-built clusters of a few nodes. It
admits a placement that is the best under the binds so far, and one that
was the best when its launch began (the kernel's staleness); it refuses
one that is the best under no recent state: a placement that ignores the
preferred term, flips its sign, or drops the resource scores. The scores
it gives are checked against this file's own arithmetic. At the cell's
own shape (5,000 nodes, the warm-up's burst, then ~13-pod launches) the
harness's comparison admits pods placed on the reference's totals and
refuses pods placed on those totals held in bfloat16. The rule imports
nothing of the program, and neither does this test."""

import ast
import json
import pathlib
import random
import sys

import ml_dtypes
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "benchmark"))

from harness.catalog import Catalog  # noqa: E402
from harness.check import ReferenceCluster, check_placements  # noqa: E402
from harness.supervisor import Cluster, warmup_burst  # noqa: E402

HOST = "kubernetes.io/hostname"
ZONE = "topology.kubernetes.io/zone"
CONFIG = "perf5k-preferredaffinity"
MI, GI = 2**20, 2**30


@pytest.fixture(scope="module")
def rule():
    cat = Catalog(str(REPO))
    (mod,) = cat.reference_rules(cat.config(CONFIG))
    return mod


def _node(i, zones=2):
    return {"metadata": {"name": f"n{i}", "labels": {HOST: f"n{i}",
                                                     ZONE: f"z{i % zones}"}},
            "status": {"allocatable": {"cpu": "4", "memory": "32Gi",
                                       "pods": 110}}}


def _pod(name, cpu="100m", bound=None, labels=None, weight=1, key=HOST):
    spec = {"containers": [{"requests": {"cpu": cpu, "memory": "500Mi"}}]}
    if weight:
        spec["affinity"] = {"podAffinity": {"preferred": [{
            "weight": weight, "term": {
                "labelSelector": {"matchLabels": [["foo", ""]]},
                "topologyKey": key}}]}}
    if bound is not None:
        spec["nodeName"] = bound
    return {"metadata": {"name": name, "namespace": "bench",
                         "labels": {"foo": ""} if labels is None else labels},
            "spec": spec}


def _cluster(rule, n=8, residents=()):
    """n nodes and the residents [(node, cpu)], each a foo pod carrying
    the term, created bound."""
    ref = ReferenceCluster([_node(i) for i in range(n)], [rule])
    for k, (node, cpu) in enumerate(residents):
        m = _pod(f"r{k}", cpu=cpu, bound=node)
        assert ref.why_not(m, node) is None
        ref.bind(m, node)
    return ref


def _parts(ref, node, cpu_m=100):
    """(resource score, raw InterPodAffinity sum) of a foo pod with the
    term (weight 1, hostname) on `node`, from the replay's own record of
    the pods bound so far: written out here, apart from the rule."""
    nd = ref.nodes[node]
    cf = min((nd["used"]["cpu"] + cpu_m) / nd["cpu"], 1.0)
    mf = min((nd["used"]["memory"] + 500 * MI) / nd["memory"], 1.0)
    resource = ((1 - cf) + (1 - mf)) * 50 + (1 - abs(cf - mf)) * 100
    foo = sum(1 for labels, n in ref._placed if n == node and "foo" in labels)
    return resource, 2.0 * foo  # its term over them, and each of theirs


def _totals(ref, policy):
    res = {n: _parts(ref, n) for n in ref.nodes}
    hi = max(raw for _r, raw in res.values())
    ip = {n: (raw / hi * 100 if hi > 0 else 0.0) for n, (_r, raw) in res.items()}
    return {n: policy(r, ip[n]) for n, (r, _raw) in res.items()}


FULL = lambda r, ip: r + ip  # noqa: E731


def _place(ref, rule, pods, policy=FULL):
    """Bind each pod, one at a time, to the best node by `policy` over the
    state so far (ties: the lowest name); the rule's verdicts."""
    out = []
    for m in pods:
        tot = _totals(ref, policy)
        node = max(sorted(tot, key=lambda n: int(n[1:])), key=lambda n: tot[n])
        out.append(ref.why_not(m, node))
        ref.bind(m, node)
    return out


def test_the_rule_imports_nothing_of_the_program(rule):
    tree = ast.parse(pathlib.Path(rule.__file__).read_text())
    imported = {a.name.split(".")[0] for n in ast.walk(tree)
                if isinstance(n, ast.Import) for a in n.names}
    imported |= {(n.module or "").split(".")[0] for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)}
    assert imported == {"numpy"}


def test_its_scores_are_the_written_out_sum(rule):
    ref = _cluster(rule, residents=[("n0", "100m"), ("n0", "1"), ("n1", "2"),
                                    ("n3", "100m")])
    got = rule.scores(_pod("p"), ref)
    want = _totals(ref, FULL)
    assert set(got) == set(want)
    for node, s in got.items():
        assert s == pytest.approx(want[node] + rule.CONSTANT, abs=1e-9), node
    assert rule.CONSTANT == 100 + 10000 * 100 + 100 + 100
    # n3's one foo pod is worth half of n0's two: 50 points, less the
    # ~3 that its 100m costs the resource scores
    assert got["n3"] - got["n4"] == pytest.approx(50 - 2.987, abs=0.01)


def test_a_sequential_argmax_placement_is_admitted(rule):
    ref = _cluster(rule, residents=[("n0", "100m"), ("n2", "500m"),
                                    ("n5", "100m"), ("n5", "100m")])
    assert _place(ref, rule, [_pod(f"p{i}") for i in range(60)]) == [None] * 60
    # the hot node took pods until its resources outweighed the term
    assert 20 < sum(1 for _l, n in ref._placed if n == "n5") < 40


def test_a_placement_best_only_when_its_launch_began_is_admitted(rule):
    """n0 and n1 tie when the launch begins; its first pod goes to n0,
    which then outscores n1 by ~47 points; a second pod of the same
    launch scored on the launch's start may still go to n1."""
    ref = _cluster(rule, residents=[("n0", "100m"), ("n1", "100m")])
    start = rule.scores(_pod("a"), ref)
    assert start["n0"] == pytest.approx(start["n1"])
    assert ref.why_not(_pod("a"), "n0") is None
    ref.bind(_pod("a"), "n0")
    now = rule.scores(_pod("b"), ref)
    assert now["n0"] - now["n1"] > 40
    assert ref.why_not(_pod("b"), "n1") is None
    ref.bind(_pod("b"), "n1")


def test_a_placement_best_under_no_state_is_refused(rule):
    ref = _cluster(rule, residents=[("n0", "100m"), ("n1", "100m")])
    assert ref.why_not(_pod("a"), "n0") is None
    ref.bind(_pod("a"), "n0")
    # n4 has no foo pod and never had: the best under no state since the
    # residents were bound
    why = ref.why_not(_pod("b"), "n4")
    assert why and why.startswith(f"{rule.NAME}: n4 scores")
    # and the rule still admits the best node after a refusal
    ref.bind(_pod("b"), "n4")
    assert ref.why_not(_pod("c"), "n0") is None


def test_a_pod_created_bound_is_not_judged(rule):
    ref = _cluster(rule, residents=[("n0", "100m")])
    # a resident on the least attractive node: no scheduler placed it
    assert ref.why_not(_pod("r", bound="n7"), "n7") is None


@pytest.mark.parametrize("policy", [
    lambda r, ip: r,         # the preferred term ignored
    lambda r, ip: r - ip,    # its sign flipped
], ids=["term-ignored", "sign-flipped"])
def test_a_placement_against_the_term_is_refused(rule, policy):
    ref = _cluster(rule, residents=[("n0", "100m"), ("n3", "100m")])
    verdicts = _place(ref, rule, [_pod(f"p{i}") for i in range(10)], policy)
    # each of the six nodes without a foo pod takes one, against the term;
    # then every node holds one and any placement is the best
    assert all(v and v.startswith(rule.NAME) for v in verdicts[:6]), verdicts
    assert verdicts[6:] == [None] * 4


def test_a_placement_without_the_resource_scores_is_refused(rule):
    """n0's three foo pods hold 3 of its 4 cpu: the term alone sends the
    pod there (100 points against n1's 33), the whole score to n1 (its
    resources outweigh the difference by ~40 points)."""
    ref = _cluster(rule, residents=[("n0", "1"), ("n0", "1"), ("n0", "1"),
                                    ("n1", "100m")])
    full = _totals(ref, FULL)
    assert max(full, key=full.get) == "n1"
    verdicts = _place(ref, rule, [_pod("p0")], lambda r, ip: ip)
    assert verdicts[0] and "n0 scores" in verdicts[0]


def test_a_state_older_than_a_launch_is_not_an_anchor(rule, monkeypatch):
    """The staleness a launch may have is bounded by the pods it carries:
    a placement best only three binds ago is admitted when a launch may
    carry four pods, refused when it carries two."""
    def run():
        ref = _cluster(rule, residents=[("n0", "100m"), ("n1", "100m")])
        for k in range(3):
            assert ref.why_not(_pod(f"a{k}"), "n0") is None
            ref.bind(_pod(f"a{k}"), "n0")
        return ref.why_not(_pod("b"), "n1")

    assert run() is None
    monkeypatch.setattr(rule, "LAUNCH_MAX", 2)
    assert run() is not None


def test_the_standing_filters_still_run_beside_it(rule):
    ref = _cluster(rule, n=2, residents=[("n0", "3900m")])
    # n0 has 100m of cpu left: the standing rule refuses, not the score
    why = ref.why_not(_pod("p", cpu="200m"), "n0")
    assert why == "over the node's allocatable cpu"


def test_the_configuration_names_the_rule_and_carries_the_term():
    cat = Catalog(str(REPO))
    cfg = cat.config(CONFIG)
    assert cfg["reference_rules"] == ["preferred_pod_affinity_score"]
    assert any("within 0.25 points" in g for g in cfg["guarantees"])
    for name, tpl in cfg["pod_templates"].items():
        assert tpl["metadata"]["labels"] == {"foo": ""}
        (wt,) = tpl["spec"]["affinity"]["podAffinity"]["preferred"]
        assert wt == {"weight": 1, "term": {
            "labelSelector": {"matchLabels": [["foo", ""]]},
            "topologyKey": HOST}}
        assert ("nodeName" in tpl["spec"]) == (name == "resident")
    # one resident a node
    assert cfg["residents"]["count"] == cfg["nodes"]["count"]
    assert cfg["residents"]["zones"] == cfg["nodes"]["zones"]
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (cell,) = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert cell["chips"] == 1 and cell["traffic"] == "backlog"


# the window's launches on the chip carry ~13 pods (`pods_per_wave`)
CELL_LAUNCH = 13


def _cell_binds(rule, seed, rounded):
    """The cell's cluster as the harness makes it from `seed` (5,000
    nodes, a resident created bound on each), its warm-up burst in one
    launch, then launches of CELL_LAUNCH until LAUNCH_MAX + 1,500 pods are
    bound, each launch placed as the kernel's model places it: scored once
    on the occupancy at its start, every pod on a best node still feasible
    at its commit, ties broken at random. `rounded`: the scores held in
    bfloat16. (the Cluster, the binds as the watch would order them)."""
    cat = Catalog(str(REPO))
    cell = cat.cell(f"{CONFIG}.backlog")
    c = Cluster(cat.config(CONFIG), seed, None)
    nodes = [m["metadata"]["name"] for m in c.node_manifests]
    # the scheduler's side: the scores, and apart from them the standing
    # filters at commit (a cluster with the rule would judge as it places)
    sched = ReferenceCluster(c.node_manifests, [rule])
    filters = ReferenceCluster(c.node_manifests)
    rng = random.Random(seed)
    order = []

    def bind(name, node, template):
        c.pod_body(name, template, node if template == "resident" else None)
        key = f"{c.ns}/{name}"
        sched.bind(c.manifest_of(key), node)
        filters.bind(c.manifest_of(key), node)
        order.append((key, node))

    for name, template, node in c.residents:
        bind(name, template and node, template)
    want = rule.LAUNCH_MAX + 1500
    launch, k = warmup_burst(cat.traffic(cell), c.scale), 0
    while k < want:
        names = [f"m-{k + i}" for i in range(min(launch, want - k))]
        c.pod_body(names[0], "measured", None)
        m = c.manifest_of(f"{c.ns}/{names[0]}")
        got = rule.scores(m, sched)
        s = np.array([got.get(n, -np.inf) for n in nodes])
        if rounded:
            s = s.astype(ml_dtypes.bfloat16).astype(np.float64)
        # best first, ties in a random order
        ranked = sorted(range(len(nodes)), key=lambda i: (-s[i], rng.random()))
        for name in names:
            node = next(nodes[i] for i in ranked
                        if np.isfinite(s[i])
                        and filters.why_not(m, nodes[i]) is None)
            bind(name, node, "measured")
        k += len(names)
        launch = CELL_LAUNCH
    return c, order


@pytest.mark.parametrize("seed", [2**31 + 43, 2**32 + 7])
@pytest.mark.parametrize("rounded", [False, True], ids=["float64", "bfloat16"])
def test_the_cells_shape_tells_bfloat16_scores_apart(rule, seed, rounded):
    """In this deployment the contest between InterPodAffinity and the
    resource scores is tight (a pod costs its node ~3 points of the one
    and earns it ~3 of the other), so a placement made on scores that tie
    every node could pass a rule that looks too far back. Through the
    harness's own comparison, placements made on the reference's totals
    pass; the same totals in bfloat16 (whose step near the 10^6 that
    NodePreferAvoidPods adds is 4,096) tie every node, the pods land at
    random, and the rule refuses binds from the first one past
    LAUNCH_MAX on."""
    c, order = _cell_binds(rule, seed, rounded)
    bad = check_placements(c.node_manifests, order, c.manifest_of, [],
                           [rule])
    if not rounded:
        assert bad == []
        return
    assert bad and all(rule.NAME in b for b in bad)
    judged = len(order) - len(c.residents) - rule.LAUNCH_MAX
    assert len(bad) > judged // 4, (len(bad), judged)
