"""The wave kernel's score planes against the plain reference's score rule
(benchmark/reference_rules/preferred_pod_affinity_score.py, which imports
nothing of the program) on seeded random clusters whose pods carry
preferred pod affinity terms of random weight on the hostname and the zone
key: the residents' own terms (`ip_et`, ETERM_AFF_PREF) and the incoming
pods' (`ppref_t`). For every placed pod the score the kernel reports
(`WaveResult.score`) is the reference's score of the chosen node on the
state its launch began from, within the rule's EPSILON, and every
placement, replayed in the order the scheduler binds them, passes the
rule. Placements chosen on the reference's own totals rounded to bfloat16
fail it. The production Scheduler's own binds pass it too, and its
counter counts the launches a preferred term moved."""

import importlib.util
import pathlib
import random
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.api import objects as v1
from kubernetes_tpu.api.serialization import from_dict
from kubernetes_tpu.client.apiserver import APIServer
from kubernetes_tpu.ops.encoding import SnapshotEncoder
from kubernetes_tpu.ops.lattice import DEFAULT_WEIGHTS
from kubernetes_tpu.ops.templates import TemplateCache, build_pair_table
from kubernetes_tpu.ops.wavelattice import make_wave_kernel_jit
from kubernetes_tpu.scheduler import KubeSchedulerConfiguration, Scheduler
from kubernetes_tpu.utils.metrics import metrics

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "benchmark"))

from harness.check import ReferenceCluster  # noqa: E402


def _load_rule():
    path = REPO / "benchmark" / "reference_rules" / "preferred_pod_affinity_score.py"
    spec = importlib.util.spec_from_file_location("preferred_rule", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


RULE = _load_rule()
HOST = "kubernetes.io/hostname"
ZONE = "topology.kubernetes.io/zone"
N_NODES, ZONES, RESIDENTS = 24, 3, 20
LAUNCHES, P, M_CAND = 3, 8, 16
SEEDS = list(range(8))


def _term(rng):
    key, value = rng.choice([("app", rng.choice("abc")),
                             ("tier", rng.choice("xy"))])
    return {"weight": rng.randint(1, 100), "term": {
        "labelSelector": {"matchLabels": [[key, value]]},
        "topologyKey": rng.choice([HOST, ZONE])}}


def _pod(rng, name, node=None):
    spec = {"containers": [{"requests": {
        "cpu": rng.choice(["100m", "200m", "500m"]),
        "memory": rng.choice(["256Mi", "512Mi", "1Gi"])}}]}
    terms = [_term(rng) for _ in range(rng.randint(0, 2))]
    if terms:
        spec["affinity"] = {"podAffinity": {"preferred": terms}}
    if node is not None:
        spec["nodeName"] = node
    labels = {"app": rng.choice("abc")}
    if rng.random() < 0.5:
        labels["tier"] = rng.choice("xy")
    return {"metadata": {"name": name, "namespace": "bench", "labels": labels},
            "spec": spec}


def _cluster(seed):
    """(node manifests, resident manifests, launches of pod manifests)."""
    rng = random.Random(seed)
    nodes = [{"metadata": {"name": f"n{i}", "namespace": "",
                           "labels": {HOST: f"n{i}", ZONE: f"z{i % ZONES}"}},
              "status": {"allocatable": {
                  "cpu": rng.choice(["2", "4", "8"]),
                  "memory": rng.choice(["8Gi", "16Gi", "32Gi"]),
                  "pods": 110}}}
             for i in range(N_NODES)]
    residents = [_pod(rng, f"r{k}", f"n{rng.randrange(N_NODES)}")
                 for k in range(RESIDENTS)]
    launches = []
    for la in range(LAUNCHES):
        templates = [_pod(rng, "t") for _ in range(2)]
        for t in templates:  # every incoming pod carries a term
            t["spec"].setdefault("affinity", {"podAffinity": {
                "preferred": [_term(rng)]}})
        launches.append([
            {"metadata": dict(t["metadata"], name=f"l{la}-{i}"),
             "spec": t["spec"]}
            for i, t in enumerate(rng.choice(templates) for _ in range(P))])
    return nodes, residents, launches


def _start(nodes, residents):
    enc = SnapshotEncoder()
    for m in nodes:
        enc.add_node(from_dict(v1.Node, m))
    ref = ReferenceCluster(nodes, [RULE])
    for m in residents:
        node = m["spec"]["nodeName"]
        enc.add_pod(node, from_dict(v1.Pod, m))
        assert ref.why_not(m, node) is None
        ref.bind(m, node)
    return enc, ref


@pytest.mark.parametrize("refresh", [False, True], ids=["cpu", "tpu-refresh"])
@pytest.mark.parametrize("seed", SEEDS)
def test_the_kernel_scores_and_places_as_the_reference_says(
        seed, refresh, monkeypatch):
    """`refresh`: the per-iteration re-score of the resource planes the
    served path turns on on a TPU (scheduler._score_refresh). Every launch
    carries P pods, so the rule may look no further back than that."""
    monkeypatch.setattr(RULE, "LAUNCH_MAX", P)
    nodes, residents, launches = _cluster(seed)
    enc, ref = _start(nodes, residents)
    tc = TemplateCache(enc)
    placed_total = 0
    for la, manifests in enumerate(launches):
        pods = [from_dict(v1.Pod, m) for m in manifests]
        eb = tc.encode(pods, pad_to=P)
        ptab = build_pair_table(enc, eb.tpl_np, eb.num_templates)
        snap = enc.flush()
        kern = make_wave_kernel_jit(enc.cfg.v_cap, M_CAND, 2, 1.0, False, refresh)
        _new, res = kern(snap, eb.batch, ptab, jnp.asarray(DEFAULT_WEIGHTS),
                         jax.random.PRNGKey(seed * 10 + la))
        enc.invalidate_device()  # the kernel took the snapshot's buffers
        chosen, placed, cw, score = (np.asarray(jax.device_get(x)) for x in (
            res.chosen, res.placed, res.commit_wave, res.score))
        # the reference's scores on the state the launch began from
        start = [RULE.scores(m, ref) for m in manifests]
        order = sorted((i for i in range(P) if placed[i]),
                       key=lambda i: (cw[i], i))
        for i in order:
            node = enc.row_names[int(chosen[i])]
            assert abs(float(score[i]) - start[i][node]) <= RULE.EPSILON, (
                la, i, node, float(score[i]), start[i][node])
            assert ref.why_not(manifests[i], node) is None, (la, i, node)
            ref.bind(manifests[i], node)
            enc.add_pod(node, pods[i])
        placed_total += len(order)
    assert placed_total == LAUNCHES * P


@pytest.mark.parametrize("seed", SEEDS)
def test_placements_on_bfloat16_totals_fail_the_rule(seed):
    """The reference's own totals, held in bfloat16 (whose step near the
    10^6 that NodePreferAvoidPods adds is 8,192), tie every node: pods
    placed on them land anywhere, and the rule refuses some. The same
    totals in float64 place every pod where the rule admits it."""
    def run(rounded):
        nodes, residents, launches = _cluster(seed)
        _enc, ref = _start(nodes, residents)
        rng = random.Random(seed)
        refused = 0
        for manifests in launches:
            for m in manifests:
                tot = RULE.scores(m, ref)
                names = sorted(tot)
                v = np.array([tot[n] for n in names])
                if rounded:
                    v = np.asarray(jnp.asarray(v, jnp.bfloat16), np.float64)
                best = [n for n, x in zip(names, v) if x == v.max()]
                node = rng.choice(best)
                refused += ref.why_not(m, node) is not None
                ref.bind(m, node)
        return refused

    assert run(rounded=False) == 0
    assert run(rounded=True) > 0


# -- the served path: the production Scheduler, the in-process store ---------


def _served(n_nodes, n_pods, burst):
    """Nodes, a foo resident on each created bound, then `n_pods` foo pods
    with the configuration's soft hostname term, created one and then
    `burst` at a time while the production Scheduler runs: (node
    manifests, pod
    manifests in the order the store committed the binds, what the
    counters moved by, the most pods a launch carried). A manifest is the
    pod as it was created, as the benchmark's check reads it: a measured
    pod's without the node the scheduler gave it."""
    term = {"weight": 1, "term": {
        "labelSelector": {"matchLabels": [["foo", ""]]}, "topologyKey": HOST}}

    created = {}

    def foo(name, node=None):
        spec = {"containers": [{"requests": {"cpu": "100m", "memory": "500Mi"}}],
                "affinity": {"podAffinity": {"preferred": [term]}}}
        if node:
            spec["nodeName"] = node
        created[name] = {"metadata": {"name": name, "namespace": "bench",
                                      "labels": {"foo": ""}}, "spec": spec}
        return from_dict(v1.Pod, created[name])

    node_ms = [{"metadata": {"name": f"n{i}", "namespace": "",
                             "labels": {HOST: f"n{i}", ZONE: f"z{i % ZONES}"}},
                "status": {"allocatable": {"cpu": "4", "memory": "32Gi",
                                           "pods": 110}}}
               for i in range(n_nodes)]
    server = APIServer()
    for m in node_ms:
        server.create("nodes", from_dict(v1.Node, m))
    order = []
    watch = server.watch("pods")

    def follow():
        seen = set()
        for ev in watch:
            pod = ev.object
            if pod.spec.node_name and pod.metadata.name not in seen:
                seen.add(pod.metadata.name)
                order.append((pod.metadata.name, pod.spec.node_name))

    follower = threading.Thread(target=follow, daemon=True)
    follower.start()
    for k in range(n_nodes):
        server.create("pods", foo(f"r{k}", f"n{k}"))
    names = ("scheduler_wave_batches_total",
             "scheduler_wave_preferred_affinity_batches_total",
             "scheduler_wave_commit_iterations_total")
    c0 = {name: metrics.counter(name) for name in names}
    sched = Scheduler(server, KubeSchedulerConfiguration(small_batch_host_max=0))
    sched.start()
    try:
        # one pod first: its launch compiles the kernel, and the bursts
        # after it meet a loop that keeps up
        server.create("pods", foo("p0"))
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline and len(order) < n_nodes + 1:
            time.sleep(0.05)
        for i in range(1, n_pods, burst):
            for j in range(i, min(i + burst, n_pods)):
                server.create("pods", foo(f"p{j}"))
            time.sleep(0.1)
        while time.monotonic() < deadline and len(order) < n_nodes + n_pods:
            time.sleep(0.05)
        assert len(order) == n_nodes + n_pods
        assert sched.wait_for_idle(30)
    finally:
        sched.stop()
        watch.stop()
        follower.join(10)
    d = {n: metrics.counter(n) - v for n, v in c0.items()}
    binds = [(created[name], node) for name, node in order]
    return node_ms, binds, d, sched._wave_batch_pods_peak


def test_the_served_path_binds_what_the_rule_admits_and_counts_it(monkeypatch):
    """192 nodes, a foo resident on each, 400 foo pods created 8 at a
    time: every bind in the store's commit order passes the standing
    filters and the score rule, looking no further back than the largest
    launch of the run; every launch counts as one whose score a preferred
    term moved."""
    node_ms, order, d, peak = _served(192, 400, 8)
    assert 0 < peak < 400
    monkeypatch.setattr(RULE, "LAUNCH_MAX", peak)
    ref = ReferenceCluster(node_ms, [RULE])
    refused = []
    for m, node in order:
        why = ref.why_not(m, node)
        if why is not None:
            refused.append(why)
        ref.bind(m, node)
    assert refused == []
    assert d["scheduler_wave_preferred_affinity_batches_total"] == (
        d["scheduler_wave_batches_total"]) > 0
    # the two-iteration program: no pod commits later than the second
    assert d["scheduler_wave_commit_iterations_total"] <= (
        2 * d["scheduler_wave_batches_total"])
