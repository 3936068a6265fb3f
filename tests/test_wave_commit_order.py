"""Binds leave in the order the wave kernel committed them.

The wave loop commits at most one pod per (pair, domain) an iteration, and
which pods those are follows the candidates, not the pod index. So a
launch's placements are feasible one after another in (`commit_wave`, pod)
order, the order `Scheduler._commit_batch` sends them in, and NOT in
pod-index order: replayed through the host plugins
(framework/plugins/podtopologyspread.py, interpodaffinity.py), the first
order finds every placement feasible at its turn, the second breaks a hard
`maxSkew` 1 on some seed. One served-path test replays the in-process
store's own commit order."""

import functools
import threading
import time

import jax
import numpy as np
import pytest

from kubernetes_tpu.api.objects import (
    Affinity,
    PodAffinityTerm,
    PodAntiAffinity,
    TopologySpreadConstraint,
)
from kubernetes_tpu.api.selectors import LabelSelector
from kubernetes_tpu.client.apiserver import APIServer
from kubernetes_tpu.ops.encoding import SnapshotEncoder
from kubernetes_tpu.ops.lattice import (
    DEFAULT_WEIGHTS,
    GUARD_COMMIT_WAVE,
    validate_batch_outputs,
)
from kubernetes_tpu.ops.templates import TemplateCache, build_pair_table
from kubernetes_tpu.ops.wavelattice import make_wave_kernel_jit
from kubernetes_tpu.parallel.mesh import make_mesh, replicated, snapshot_shardings
from kubernetes_tpu.parallel.sharded import make_sharded_wave_kernel
from kubernetes_tpu.scheduler import KubeSchedulerConfiguration, Scheduler
from kubernetes_tpu.scheduler.cache.nodeinfo import NodeInfo, Snapshot
from kubernetes_tpu.scheduler.framework.interface import CycleState, is_success
from kubernetes_tpu.utils.metrics import metrics

from test_fuzz_differential import _oracle_framework
from test_lattice_smoke import make_node, make_pod

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
N_NODES, ZONES, P = 96, 3, 64
M_C = 32  # the candidate columns of a template, as the served small bucket
SEEDS = [1, 2, 3, 4, 5]
MESHES = ["single", "mesh4"]


def _spread_pod(name):
    return make_pod(
        name,
        cpu="100m",
        mem="500Mi",
        labels={"color": "blue"},
        topology_spread_constraints=[
            TopologySpreadConstraint(
                max_skew=1,
                topology_key=ZONE,
                when_unsatisfiable="DoNotSchedule",
                label_selector=LabelSelector.make(match_labels={"color": "blue"}),
            )
        ],
    )


def _anti_pod(name):
    term = PodAffinityTerm(
        label_selector=LabelSelector.make(match_labels={"app": "solo"}),
        topology_key=HOST,
    )
    return make_pod(
        name,
        cpu="100m",
        mem="500Mi",
        labels={"app": "solo"},
        affinity=Affinity(pod_anti_affinity=PodAntiAffinity(required=(term,))),
    )


KINDS = {"spread": _spread_pod, "anti": _anti_pod}


def _nodes():
    return [
        make_node(f"n{i}", labels={ZONE: f"zone-{i % ZONES}", HOST: f"n{i}"})
        for i in range(N_NODES)
    ]


@functools.lru_cache(maxsize=None)
def _launch(kind: str, seed: int, where: str, free: int | None = None):
    """One launch of the hard-pair program over a seeded cluster: (pods,
    node name of each placement or None, commit_wave, placed, the host's
    NodeInfos before the launch, deferred). `free`: all but that many
    nodes (a seeded choice) hold a resident of the measured kind; by
    default 0 to 6 nodes do."""
    rng = np.random.default_rng(seed)
    enc = SnapshotEncoder()
    infos = {}
    for n in _nodes():
        enc.add_node(n)
        infos[n.metadata.name] = NodeInfo(n)
    # residents of the measured kind, so that the launch starts from
    # counts that are not level (the seed decides where)
    mk = KINDS[kind]
    n_pre = int(rng.integers(0, 7)) if free is None else N_NODES - free
    for j, row in enumerate(rng.choice(N_NODES, size=n_pre, replace=False)):
        p = mk(f"pre-{j}")
        p.spec.node_name = f"n{row}"
        enc.add_pod(p.spec.node_name, p)
        infos[p.spec.node_name].add_pod(p)
    pods = [mk(f"{kind}-{i}") for i in range(P)]
    eb = TemplateCache(enc).encode(pods, pad_to=P)
    ptab = build_pair_table(enc, eb.tpl_np, eb.num_templates)
    w = np.asarray(DEFAULT_WEIGHTS)
    key = jax.random.PRNGKey(seed)
    if where == "single":
        kern = make_wave_kernel_jit(enc.cfg.v_cap, M_C, 16, stratify=True)
    else:
        mesh = make_mesh(jax.devices()[:4])
        enc.set_sharding(snapshot_shardings(mesh), replicated(mesh))
        kern = make_sharded_wave_kernel(
            enc.cfg.v_cap, M_C, 16, 1.0, mesh, stratify=True)
    _snap, res = kern(enc.flush(), eb.batch, ptab, w, key)
    chosen, placed, commit_wave, deferred = jax.device_get(
        (res.chosen, res.placed, res.commit_wave, res.deferred)
    )
    names = [enc.row_names[int(c)] if ok else None
             for c, ok in zip(chosen, placed)]
    return (pods, names, np.asarray(commit_wave), np.asarray(placed), infos,
            np.asarray(deferred))


def _replay(pods, names, infos, order) -> list:
    """The placements the host filter chain refuses when they are made in
    `order`, each against the cluster as the earlier ones left it."""
    live = {k: ni.clone() for k, ni in infos.items()}
    holder = [None]
    fw = _oracle_framework(holder)
    refused = []
    for i in order:
        if names[i] is None:
            continue
        holder[0] = Snapshot(list(live.values()))
        state = CycleState()
        st = fw.run_pre_filter_plugins(state, pods[i])
        if is_success(st):
            st = fw.run_filter_plugins(state, pods[i], live[names[i]])
        if not is_success(st):
            refused.append((pods[i].metadata.name, names[i], st.message))
        bound = pods[i].deep_copy()
        bound.spec.node_name = names[i]
        live[names[i]].add_pod(bound)
    return refused


def _commit_order(commit_wave, placed):
    return sorted(np.nonzero(placed)[0].tolist(),
                  key=lambda i: (int(commit_wave[i]), i))


@pytest.mark.parametrize("where", MESHES)
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("kind", list(KINDS))
def test_every_placement_is_feasible_in_commit_order(kind, seed, where):
    pods, names, commit_wave, placed, infos, deferred = _launch(kind, seed, where)
    # one commit an iteration is the floor the algorithm guarantees; the
    # spread's columns are stratified over the zones, so an unlevel start
    # does not run out of zones that have one (35-47 of 64: PERF.md, PR 35)
    # a hostname pair's domain is the node: a template's M_C candidate
    # columns are M_C distinct nodes, each takes one pod, and the program
    # commits all of them in 2 of its 16 iterations (32 of 64 on every
    # seed here; 32 of 64 on the chip at 1,000 / 2,500 / 4,700 of 5,000
    # nodes occupied: PERF.md, PR 36); the rest is deferred
    if kind == "anti":
        free = sum(1 for ni in infos.values() if not ni.pods)
        assert min(16, free) <= placed.sum() <= M_C
        taken = [n for n in names if n is not None]
        assert len(taken) == len(set(taken))  # at most one pod a node
        assert all(not infos[n].pods for n in taken)  # and none beside a resident
    else:
        assert placed.sum() >= 16
    # commit_wave names an iteration exactly where a pod was placed
    assert ((commit_wave >= 0) == placed).all()
    assert commit_wave.max() < 16 and commit_wave.min() >= -1
    assert validate_batch_outputs(
        [0] * P, placed, None, N_NODES, commit_wave) is None
    # what was not placed was deferred: feasible nodes were left
    assert (deferred == ~placed).all()
    assert _replay(pods, names, infos, _commit_order(commit_wave, placed)) == []
    if kind == "spread":
        # at most one pod a zone an iteration: the (pair, domain) exclusivity
        for w in range(int(commit_wave.max()) + 1):
            zones = [int(names[i][1:]) % ZONES
                     for i in np.nonzero(commit_wave == w)[0]]
            assert len(zones) == len(set(zones)) <= ZONES


@pytest.mark.parametrize("where", MESHES)
@pytest.mark.parametrize("seed", SEEDS)
def test_a_nearly_full_cluster_gives_up_exactly_its_free_nodes(seed, where):
    """5 free nodes of 96, 64 pods of the hostname anti-affinity kind: the
    feasible set is a twentieth of the cluster, as at the end of
    `perf5k-antiaffinity.steady`'s window. Exactly the free nodes are
    taken, one pod each; no pod lands beside a resident; what was not
    placed is deferred (the nodes ran out, the pods are not
    unschedulable) and the host's InterPodAffinity refuses nothing in
    (`commit_wave`, pod) order."""
    pods, names, commit_wave, placed, infos, deferred = _launch(
        "anti", seed, where, free=5)
    free = {k for k, ni in infos.items() if not ni.pods}
    assert len(free) == 5
    taken = [n for n in names if n is not None]
    assert sorted(taken) == sorted(free) and placed.sum() == 5
    assert ((commit_wave >= 0) == placed).all()
    assert (deferred == ~placed).all()
    assert validate_batch_outputs(
        [0] * P, placed, None, N_NODES, commit_wave) is None
    assert _replay(pods, names, infos, _commit_order(commit_wave, placed)) == []
    # the mesh takes what one device takes
    if where != "single":
        _p, names1, commit_wave1, placed1, _i, _d = _launch(
            "anti", seed, "single", free=5)
        assert names == names1 and (commit_wave == commit_wave1).all()


@pytest.mark.parametrize("where", MESHES)
def test_pod_index_order_breaks_the_hard_spread_on_some_seed(where):
    """The fault this order cures: the same placements in pod-index order
    (what `to_bind` held before) are refused by the host's
    PodTopologySpread for at least one seed."""
    refused = {}
    for seed in SEEDS:
        pods, names, commit_wave, placed, infos, _ = _launch("spread", seed, where)
        refused[seed] = _replay(pods, names, infos, range(P))
    assert any(refused.values()), refused
    assert all("PodTopologySpread" in why or "spread" in why.lower()
               for r in refused.values() for _p, _n, why in r), refused


@pytest.mark.parametrize("where", MESHES)
@pytest.mark.parametrize("seed", SEEDS)
def test_the_mesh_commits_what_one_device_commits(seed, where):
    _pods, names, commit_wave, placed, _i, _d = _launch("spread", seed, where)
    _pods, names1, commit_wave1, placed1, _i, _d = _launch("spread", seed, "single")
    assert names == names1
    assert (commit_wave == commit_wave1).all() and (placed == placed1).all()


@pytest.mark.parametrize("commit_wave,placed,want", [
    ([0, -1, 2], [True, False, True], None),
    ([-1, -1], [False, False], None),
    ([0, 1, 2], [True, False, True], GUARD_COMMIT_WAVE),   # an iteration, not placed
    ([-1, 0], [True, True], GUARD_COMMIT_WAVE),            # placed, no iteration
], ids=["agree", "nothing-placed", "iteration-unplaced", "placed-no-iteration"])
def test_validate_batch_outputs_holds_commit_wave_to_placed(
        commit_wave, placed, want):
    chosen = [0] * len(placed)
    got = validate_batch_outputs(
        chosen, np.array(placed), None, 4, np.array(commit_wave, np.int32))
    assert got == want


# -- the served path, the in-process store's own commit order ----------------


COUNTERS = (
    "scheduler_wave_commit_iterations_total",
    "scheduler_wave_hard_batches_total", "scheduler_wave_batches_total",
    "scheduler_wave_stratified_batches_total",
    "scheduler_wave_anti_affinity_batches_total",
    "scheduler_wave_deferred_pods_total", "kernel_guard_trips_total")


def _served_nodes(n_nodes):
    return [make_node(f"n{i}", cpu="8",
                      labels={ZONE: f"zone-{i % ZONES}", HOST: f"n{i}"})
            for i in range(n_nodes)]


def _serve(kind: str, n_nodes: int, n_pods: int):
    """`n_pods` pods of `kind`, all waiting when the production Scheduler
    starts on `n_nodes` nodes: ((pod, node) in the order the in-process
    store committed the binds, what the counters moved by)."""
    server = APIServer()
    for node in _served_nodes(n_nodes):
        server.create("nodes", node)
    order = []  # (pod name, node name) as the store committed each bind
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
    sched = Scheduler(server, KubeSchedulerConfiguration(small_batch_host_max=0))
    c0 = {name: metrics.counter(name) for name in COUNTERS}
    # the whole backlog waits when the scheduler starts: its first batch is
    # popped at the full size, before its kind is known
    pods = {f"{kind}-{i}": KINDS[kind](f"{kind}-{i}") for i in range(n_pods)}
    for p in pods.values():
        server.create("pods", p)
    sched.start()
    try:
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline and len(order) < n_pods:
            time.sleep(0.05)
        assert len(order) == n_pods, f"{len(order)} of {n_pods} bound"
        assert sched.wait_for_idle(30)
    finally:
        sched.stop()
        watch.stop()
        follower.join(10)
    d = {name: metrics.counter(name) - v for name, v in c0.items()}
    # no launch with a hard pair took more than four pods an iteration:
    # the tail of the first batch went back to the queue (_batch_limit)
    assert sched._hard_backlog and sched._batch_limit() == 64 < n_pods
    assert sched._wave_batch_pods_peak == 64
    assert d["kernel_guard_trips_total"] == 0
    # every launch was the hard-pair program, and pods were deferred on the way
    assert d["scheduler_wave_hard_batches_total"] == d["scheduler_wave_batches_total"] > 0
    assert d["scheduler_wave_deferred_pods_total"] > 0
    assert 0 < d["scheduler_wave_commit_iterations_total"] <= (
        16 * d["scheduler_wave_batches_total"])
    # the store's commit order through the host's own filter chain
    infos = {n.metadata.name: NodeInfo(n) for n in _served_nodes(n_nodes)}
    assert _replay([pods[p] for p, _n in order], [n for _p, n in order],
                   infos, range(n_pods)) == []
    return order, d


def test_served_path_binds_in_an_order_that_keeps_the_hard_spread():
    """300 nodes in 3 zones, 600 pods with a hard zone spread of 1 through
    the production Scheduler: replaying the binds in the order the store
    committed them (the watch's order) finds none infeasible, every
    deferred pod is bound in the end, the new counters count, and a
    backlog of hard-pair pods leaves the queue a small bucket at a time."""
    order, d = _serve("spread", 300, 600)
    # its columns stratified over the zones; no anti-affinity term in sight
    assert d["scheduler_wave_stratified_batches_total"] == d["scheduler_wave_batches_total"]
    assert d["scheduler_wave_anti_affinity_batches_total"] == 0
    names = [n for _p, n in order]
    per_zone = [sum(1 for n in names if int(n[1:]) % ZONES == z)
                for z in range(ZONES)]
    assert max(per_zone) - min(per_zone) <= 1


def test_served_path_fills_a_cluster_one_pod_a_node():
    """300 nodes, 290 pods with required hostname anti-affinity to their
    own label, all waiting when the scheduler starts: every pod is bound,
    no node takes two (the host's InterPodAffinity refuses nothing in the
    store's commit order) although the last launches find 10-42 feasible
    nodes of 300; every launch is counted as an anti-affinity launch and
    none as stratified; a launch of 64 commits at most M_C, so the backlog
    takes at least n_pods / M_C launches."""
    n_pods = 290
    order, d = _serve("anti", 300, n_pods)
    assert len({n for _p, n in order}) == n_pods
    assert d["scheduler_wave_anti_affinity_batches_total"] == d["scheduler_wave_batches_total"]
    assert d["scheduler_wave_stratified_batches_total"] == 0
    assert d["scheduler_wave_batches_total"] >= -(-n_pods // M_C)
