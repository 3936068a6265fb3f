"""A hard zone spread's candidate columns are stratified over every domain.

Stage A of the wave kernel used to take a template's `m_cand` candidate
columns from the nodes feasible when the launch began: with a hard
`maxSkew` 1 over three zones, from counts (n+1, n, n) no column lay in
zone 0, and two iterations later zone 0 was the only zone that might take
a pod: 4 commits of 64, then 2, then 4 (`perf5k-topologyspread.backlog`:
3.04 pods a launch; ledger, PR 34). With `stratify` the columns are taken
round-robin over the domains of the template's hard spread pair, from
every node that passes all launch-start verdicts but the skew comparison;
Stage B re-checks that verdict, with the others, at every column in every
iteration. These tests hold the candidate list to its definition, the
programs without a hard spread template to their bits, and every
placement to the host's own PodTopologySpread in commit order."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
from kubernetes_tpu.ops.wavelattice import (
    make_wave_kernel,
    make_wave_kernel_jit,
    stratified_columns,
)
from kubernetes_tpu.parallel.mesh import make_mesh, replicated, snapshot_shardings
from kubernetes_tpu.parallel.sharded import make_sharded_wave_kernel
from kubernetes_tpu.scheduler.cache.nodeinfo import NodeInfo

from test_lattice_smoke import make_node, make_pod
from test_wave_commit_order import HOST, ZONE, _commit_order, _replay

N_NODES, P, M_C, WAVES = 96, 64, 32, 16
V_CAP = 128

# -- (b) the candidate list's invariants --------------------------------------


def _columns(dom, elig, feas, score, m_c=M_C):
    v, col = jax.jit(stratified_columns, static_argnums=(4, 5))(
        jnp.asarray(dom, jnp.int32), jnp.asarray(elig), jnp.asarray(feas),
        jnp.asarray(score, jnp.float32), m_c, V_CAP,
    )
    return np.asarray(v), np.asarray(col)


def _seeded_planes(zones: int, max_skew: int, seed: int, n: int = 500):
    """What Stage A hands the stratification for one template on a seeded
    cluster: each node's zone, whether it passes every verdict but the
    skew (9 in 10 do), whether it is feasible at the launch's start (its
    zone within `max_skew` of the least loaded one, the pod counted), and
    a score with ties."""
    rng = np.random.default_rng(seed)
    dom = (np.arange(n) % zones).astype(np.int32)
    elig = rng.random(n) < 0.9
    counts = rng.integers(0, 4, size=zones)
    feas = elig & (counts[dom] + 1 - counts.min() <= max_skew)
    score = rng.integers(0, 40, size=n).astype(np.float32) * 2.5
    return dom, elig, feas, score


def _reference(dom, elig, feas, score, m_c=M_C) -> list:
    """The definition, as a loop: the eligible nodes in the order (rank
    within the domain by (feasible at the start, score, row), feasible at
    the start before not, score descending, row), the first m_c of them,
    then by score descending (stable)."""
    def best_first(i):
        return (not feas[i], -score[i], i)

    rank = {}
    for d in set(dom[elig].tolist()):
        mine = sorted(np.nonzero(elig & (dom == d))[0].tolist(), key=best_first)
        rank.update({i: r for r, i in enumerate(mine)})
    taken = sorted(rank, key=lambda i: (rank[i],) + best_first(i))[:m_c]
    return sorted(taken, key=lambda i: -score[i])


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("max_skew", [1, 2])
@pytest.mark.parametrize("zones", [3, 10])
def test_columns_are_each_domains_best_in_equal_shares(zones, max_skew, seed):
    dom, elig, feas, score = _seeded_planes(zones, max_skew, seed)
    assert feas.any() and (elig & ~feas).any()
    v, col = _columns(dom, elig, feas, score)
    # every column is a node of its own, eligible, with its real score
    assert np.isfinite(v).all() and len(set(col.tolist())) == M_C
    assert elig[col].all() and (v == score[col]).all()
    # top_v descending: the tie groups and the per-pod shuffle read it so
    assert (np.diff(v) <= 0).all()
    for d in range(zones):
        mine = np.nonzero(elig & (dom == d))[0]
        took = sorted(col[dom[col] == d].tolist())
        # an equal share (the zones hold far more than a share each)
        assert len(took) in (M_C // zones, -(-M_C // zones))
        # and the domain's best by (feasible at the start, score, row)
        best = sorted(mine.tolist(), key=lambda i: (not feas[i], -score[i], i))
        assert took == sorted(best[: len(took)])
    assert col.tolist() == _reference(dom, elig, feas, score)


def test_a_domain_with_fewer_nodes_than_its_share_gives_all_it_has():
    dom, elig, feas, score = _seeded_planes(3, 1, seed=7)
    elig &= (dom != 2) | (np.arange(len(dom)) < 12)  # zone 2: four nodes
    feas &= elig
    v, col = _columns(dom, elig, feas, score)
    assert np.isfinite(v).all() and elig[col].all()
    assert sorted(col[dom[col] == 2].tolist()) == [2, 5, 8, 11]
    assert {int((dom[col] == d).sum()) for d in (0, 1)} == {14}


def test_fewer_eligible_nodes_than_columns_leaves_the_rest_invalid():
    dom, elig, feas, score = _seeded_planes(3, 1, seed=8)
    elig &= np.arange(len(dom)) < 20
    feas &= elig
    v, col = _columns(dom, elig, feas, score)
    k = int(elig.sum())
    assert 0 < k < M_C
    assert np.isfinite(v[:k]).all() and np.isneginf(v[k:]).all()
    assert sorted(col[:k].tolist()) == np.nonzero(elig)[0].tolist()
    assert col[:k].tolist() == _reference(dom, elig, feas, score)


@pytest.mark.parametrize("seed", [1, 2])
def test_a_hostname_spread_keeps_the_unstratified_list(seed):
    """D >= m_c: every node is the best of its own domain, so the list is
    top_k's over the nodes feasible at the start, ties and all."""
    _dom, elig, feas, score = _seeded_planes(3, 1, seed)
    dom = np.arange(len(elig), dtype=np.int32) % V_CAP
    dom[V_CAP:] = -1  # 128 hosts carry the key
    elig = elig & (dom >= 0)
    feas = feas & elig
    assert feas.sum() >= M_C
    v, col = _columns(dom, elig, feas, score)
    want_v, want_i = jax.lax.top_k(
        jnp.where(jnp.asarray(feas), jnp.asarray(score), -jnp.inf), M_C)
    assert col.tolist() == np.asarray(want_i).tolist()
    assert v.tobytes() == np.asarray(want_v).tobytes()


# -- launches of the whole kernel ----------------------------------------------

SEL_BLUE = LabelSelector.make(match_labels={"color": "blue"})


def _spread(key, max_skew):
    return TopologySpreadConstraint(
        max_skew=max_skew, topology_key=key,
        when_unsatisfiable="DoNotSchedule", label_selector=SEL_BLUE)


def _blue(name, *constraints):
    return make_pod(name, cpu="100m", mem="500Mi", labels={"color": "blue"},
                    topology_spread_constraints=list(constraints))


def _plain(name):
    return make_pod(name, cpu="100m", mem="500Mi")


def _affine(name):
    term = PodAffinityTerm(
        label_selector=LabelSelector.make(match_labels={"app": "db"}),
        topology_key=ZONE)
    return make_pod(name, cpu="100m", mem="500Mi", labels={"app": "db"},
                    affinity=Affinity(pod_affinity=PodAffinity(required=(term,))))


def _solo(name):
    term = PodAffinityTerm(
        label_selector=LabelSelector.make(match_labels={"app": "solo"}),
        topology_key=HOST)
    return make_pod(name, cpu="100m", mem="500Mi", labels={"app": "solo"},
                    affinity=Affinity(
                        pod_anti_affinity=PodAntiAffinity(required=(term,))))


# name -> (zones, pod i of the batch, a resident of the measured kind)
SCENARIOS = {
    "ten-zones": (10, lambda i: _blue(f"p{i}", _spread(ZONE, 1))),
    "max-skew-2": (3, lambda i: _blue(f"p{i}", _spread(ZONE, 2))),
    "zone-and-hostname": (
        3, lambda i: _blue(f"p{i}", _spread(ZONE, 1), _spread(HOST, 1))),
    "spread-and-plain": (
        3, lambda i: _plain(f"p{i}") if i % 2 else _blue(f"p{i}", _spread(ZONE, 1))),
}
NO_HARD_SPREAD = {"plain": _plain, "required-affinity": _affine,
                  "hostname-anti-affinity": _solo}


def _cluster(zones, mk, seed):
    """A seeded cluster with up to six residents of the batch's first kind,
    so that the launch starts from counts that are not level."""
    rng = np.random.default_rng(seed)
    enc, infos = SnapshotEncoder(), {}
    for i in range(N_NODES):
        node = make_node(f"n{i}", labels={ZONE: f"zone-{i % zones}", HOST: f"n{i}"})
        enc.add_node(node)
        infos[node.metadata.name] = NodeInfo(node)
    for j, row in enumerate(rng.choice(N_NODES, size=int(rng.integers(1, 7)),
                                       replace=False)):
        p = mk(0)
        p.metadata.name = f"pre-{j}"
        p.spec.node_name = f"n{row}"
        enc.add_pod(p.spec.node_name, p)
        infos[p.spec.node_name].add_pod(p)
    return enc, infos


@functools.lru_cache(maxsize=None)
def _launch(scenario: str, where: str, stratify: bool = True, seed: int = 3):
    zones, mk = SCENARIOS[scenario]
    enc, infos = _cluster(zones, mk, seed)
    pods = [mk(i) for i in range(P)]
    eb = TemplateCache(enc).encode(pods, pad_to=P)
    ptab = build_pair_table(enc, eb.tpl_np, eb.num_templates)
    if where == "single":
        kern = make_wave_kernel_jit(enc.cfg.v_cap, M_C, WAVES, stratify=stratify)
    else:
        mesh = make_mesh(jax.devices()[:4])
        enc.set_sharding(snapshot_shardings(mesh), replicated(mesh))
        kern = make_sharded_wave_kernel(
            enc.cfg.v_cap, M_C, WAVES, 1.0, mesh, stratify=stratify)
    _snap, res = kern(enc.flush(), eb.batch, ptab, np.asarray(DEFAULT_WEIGHTS),
                      jax.random.PRNGKey(seed))
    res = jax.device_get(res)
    names = [enc.row_names[int(c)] if ok else None
             for c, ok in zip(res.chosen, res.placed)]
    return pods, names, infos, res


@pytest.mark.parametrize("where", ["single", "mesh4"])
@pytest.mark.parametrize("scenario", list(SCENARIOS))
def test_host_replay_in_commit_order_refuses_nothing(scenario, where):
    pods, names, infos, res = _launch(scenario, where)
    placed, commit_wave = np.asarray(res.placed), np.asarray(res.commit_wave)
    # one commit an iteration is the floor the algorithm guarantees
    assert placed.sum() >= WAVES
    assert ((commit_wave >= 0) == placed).all()
    assert _replay(pods, names, infos, _commit_order(commit_wave, placed)) == []


@pytest.mark.parametrize("scenario", list(SCENARIOS))
def test_the_mesh_commits_what_one_device_commits(scenario):
    _pods, names, _infos, res = _launch(scenario, "mesh4")
    _pods, names1, _infos, res1 = _launch(scenario, "single")
    assert names == names1
    assert (np.asarray(res.commit_wave) == np.asarray(res1.commit_wave)).all()


@pytest.mark.parametrize("scenario", list(SCENARIOS))
def test_what_the_host_is_told_about_a_pod_is_a_function_of_the_start(scenario):
    """`feasible_count`, `feasible_tpl`, `resolvable_tpl` and the meaning of
    `deferred` are the unstratified program's: the verdicts at the launch's
    start, whatever columns the iterations then work on."""
    _pods, _names, _infos, got = _launch(scenario, "single")
    _pods, _names, _infos, want = _launch(scenario, "single", stratify=False)
    for name in ("feasible_count", "feasible_tpl", "resolvable_tpl"):
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), name
    placed = np.asarray(got.placed)
    assert (np.asarray(got.deferred)
            == (~placed & (np.asarray(got.feasible_count) > 0))).all()
    # the stratified columns place more from an unlevel start, never fewer
    assert placed.sum() >= np.asarray(want.placed).sum()


@pytest.mark.parametrize("kind", list(NO_HARD_SPREAD))
def test_no_hard_spread_template_no_changed_bit(kind):
    """A batch without a hard spread template keeps top_k over the nodes
    feasible at the start also inside a stratified program: every
    WaveResult field and the snapshot, bit for bit."""
    mk = NO_HARD_SPREAD[kind]
    enc, _infos = _cluster(3, lambda i: mk(f"p{i}"), seed=5)
    pods = [mk(f"p{i}") for i in range(P)]
    eb = TemplateCache(enc).encode(pods, pad_to=P)
    ptab = build_pair_table(enc, eb.tpl_np, eb.num_templates)
    snap = enc.flush()
    w, key = jnp.asarray(DEFAULT_WEIGHTS), jax.random.PRNGKey(5)
    # no donation: both programs read the one snapshot
    got_snap, got = jax.jit(make_wave_kernel(
        enc.cfg.v_cap, M_C, WAVES, stratify=True))(snap, eb.batch, ptab, w, key)
    want_snap, want = jax.jit(make_wave_kernel(
        enc.cfg.v_cap, M_C, WAVES))(snap, eb.batch, ptab, w, key)
    assert int(np.asarray(got.placed).sum()) > 0
    for name in got._fields:
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert a.tobytes() == b.tobytes(), name
    for name in got_snap._fields:
        a = np.asarray(getattr(got_snap, name))
        b = np.asarray(getattr(want_snap, name))
        assert a.tobytes() == b.tobytes(), name
