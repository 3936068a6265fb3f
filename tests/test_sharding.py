"""Sharded scheduling step over a virtual 8-device CPU mesh.

Verifies (a) the kernel compiles+runs with the snapshot sharded over the
"nodes" mesh axis (XLA SPMD inserts the collectives), (b) sharded results
match single-device results exactly (same pods, same rows)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.api.objects import (
    Affinity,
    PodAffinityTerm,
    PodAntiAffinity,
    TopologySpreadConstraint,
)
from kubernetes_tpu.api.selectors import LabelSelector
from kubernetes_tpu.ops.batch import encode_pod_batch
from kubernetes_tpu.ops.encoding import SnapshotEncoder
from kubernetes_tpu.ops.lattice import DEFAULT_WEIGHTS, make_schedule_batch
from kubernetes_tpu.parallel import (
    make_mesh,
    make_sharded_schedule_batch,
    shard_snapshot,
)

from test_lattice_smoke import make_node, make_pod


@pytest.fixture
def cluster():
    enc = SnapshotEncoder()
    for i in range(32):
        enc.add_node(
            make_node(
                f"n{i}",
                cpu="4",
                labels={"zone": f"z{i % 4}", "disk": "ssd" if i % 2 else "hdd"},
            )
        )
    for i in range(16):
        enc.add_pod(f"n{i}", make_pod(f"pre-{i}", cpu="1", labels={"app": "web"}))
    return enc


def _mk_pods():
    sel = LabelSelector.make(match_labels={"app": "web"})
    anti = Affinity(
        pod_anti_affinity=PodAntiAffinity(
            required=(PodAffinityTerm(label_selector=sel, topology_key="zone"),)
        )
    )
    tsc = TopologySpreadConstraint(
        max_skew=2, topology_key="zone", when_unsatisfiable="DoNotSchedule",
        label_selector=sel,
    )
    return [
        make_pod("a", cpu="1", labels={"app": "web"}, topology_spread_constraints=[tsc]),
        make_pod("b", cpu="2"),
        make_pod("c", cpu="1", labels={"app": "other"}, affinity=anti),
        make_pod("d", cpu="500m", node_selector={"disk": "ssd"}),
    ]


def test_sharded_matches_single_device(cluster):
    enc = cluster
    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    eb = encode_pod_batch(enc, _mk_pods(), pad_to=4)
    snap = enc.flush()
    w = jnp.asarray(DEFAULT_WEIGHTS)
    key = jax.random.PRNGKey(7)

    single = make_schedule_batch(enc.cfg.v_cap)(snap, eb.batch, w, key)

    mesh = make_mesh()
    snap_sharded = shard_snapshot(snap, mesh)
    kern = make_sharded_schedule_batch(enc.cfg.v_cap, mesh)
    sharded = kern(snap_sharded, eb.batch, w, key)

    np.testing.assert_array_equal(
        np.asarray(single.chosen), np.asarray(sharded.chosen)
    )
    np.testing.assert_array_equal(
        np.asarray(single.feasible_count), np.asarray(sharded.feasible_count)
    )
    np.testing.assert_allclose(
        np.asarray(single.score), np.asarray(sharded.score), rtol=1e-5
    )
    np.testing.assert_array_equal(
        np.asarray(single.resolvable), np.asarray(sharded.resolvable)
    )


def test_sharded_collectives_in_hlo(cluster):
    """The compiled sharded program must actually communicate (all-reduce /
    all-gather over ICI), not gather everything to one device."""
    enc = cluster
    eb = encode_pod_batch(enc, _mk_pods(), pad_to=4)
    snap = enc.flush()
    mesh = make_mesh()
    snap_sharded = shard_snapshot(snap, mesh)
    kern = make_sharded_schedule_batch(enc.cfg.v_cap, mesh)
    lowered = kern.lower(
        snap_sharded, eb.batch, jnp.asarray(DEFAULT_WEIGHTS), jax.random.PRNGKey(0)
    )
    hlo = lowered.compile().as_text()
    assert "all-reduce" in hlo or "all-gather" in hlo or "reduce-scatter" in hlo


# ---------------------------------------------------------------------------
# Production wave kernel, sharded (VERDICT r2 item 3: the dryrun must
# exercise the kernel production runs, not the deprecated scan lattice)
# ---------------------------------------------------------------------------


def _wave_inputs(enc, pods):
    from kubernetes_tpu.ops.templates import TemplateCache, build_pair_table

    tc = TemplateCache(enc)
    eb = tc.encode(pods, pad_to=4)
    ptab = build_pair_table(enc, eb.tpl_np, eb.num_templates)
    return eb, ptab


def _plain_pods():
    return [make_pod(f"plain-{i}", cpu="1" if i % 2 else "500m") for i in range(4)]


# the pair axis follows the pairs (ops/templates.pair_slots): no pair is one
# dead slot, _mk_pods' spread + anti-affinity pairs take the rung of 4
@pytest.mark.parametrize(
    "mk_pods,slots", [(_plain_pods, 1), (_mk_pods, 4)], ids=["J1", "J4"]
)
def test_sharded_wave_matches_single_device(cluster, mk_pods, slots):
    from kubernetes_tpu.ops.wavelattice import make_wave_kernel_jit
    from kubernetes_tpu.parallel.sharded import make_sharded_wave_kernel
    from kubernetes_tpu.ops.lattice import DEFAULT_WEIGHTS

    enc = cluster
    eb, ptab = _wave_inputs(enc, mk_pods())
    assert ptab.col.shape[0] == slots
    w = np.asarray(DEFAULT_WEIGHTS)
    key = jax.random.PRNGKey(7)

    snap = enc.flush()  # donated by the single-device kernel
    single_snap, single = make_wave_kernel_jit(enc.cfg.v_cap, 64, 8)(
        snap, eb.batch, ptab, w, key
    )
    single_snap = jax.device_get(single_snap)

    mesh = make_mesh()
    enc.invalidate_device()
    from kubernetes_tpu.parallel.mesh import replicated, snapshot_shardings

    enc.set_sharding(snapshot_shardings(mesh), replicated(mesh))
    snap_sharded = enc.flush()
    kern = make_sharded_wave_kernel(enc.cfg.v_cap, 64, 8, 1.0, mesh)
    sh_snap, sharded = kern(snap_sharded, eb.batch, ptab, w, key)

    np.testing.assert_array_equal(
        np.asarray(single.placed), np.asarray(sharded.placed)
    )
    np.testing.assert_array_equal(
        np.asarray(single.chosen), np.asarray(sharded.chosen)
    )
    np.testing.assert_array_equal(
        np.asarray(single.feasible_count), np.asarray(sharded.feasible_count)
    )
    np.testing.assert_array_equal(
        np.asarray(single.resolvable_tpl), np.asarray(sharded.resolvable_tpl)
    )
    # the committed occupancy must agree too (chained-batch invariant)
    sh_snap = jax.device_get(sh_snap)
    np.testing.assert_array_equal(single_snap.requested, sh_snap.requested)
    np.testing.assert_array_equal(single_snap.sel_counts, sh_snap.sel_counts)
    np.testing.assert_array_equal(single_snap.prio_req, sh_snap.prio_req)


def test_sharded_wave_collectives_in_hlo(cluster):
    from kubernetes_tpu.parallel.sharded import make_sharded_wave_kernel
    from kubernetes_tpu.parallel.mesh import replicated, snapshot_shardings
    from kubernetes_tpu.ops.lattice import DEFAULT_WEIGHTS

    enc = cluster
    eb, ptab = _wave_inputs(enc, _mk_pods())
    mesh = make_mesh()
    enc.set_sharding(snapshot_shardings(mesh), replicated(mesh))
    snap_sharded = enc.flush()
    kern = make_sharded_wave_kernel(enc.cfg.v_cap, 64, 8, 1.0, mesh)
    hlo = (
        kern.lower(
            snap_sharded,
            eb.batch,
            ptab,
            np.asarray(DEFAULT_WEIGHTS),
            jax.random.PRNGKey(0),
        )
        .compile()
        .as_text()
    )
    assert "all-reduce" in hlo or "all-gather" in hlo or "reduce-scatter" in hlo


def test_scheduler_uses_mesh_end_to_end():
    """Full production path on the 8-device mesh: Scheduler.start() adopts
    the mesh, the wave kernel runs sharded, pods bind."""
    from kubernetes_tpu.client.apiserver import APIServer
    from kubernetes_tpu.scheduler import KubeSchedulerConfiguration, Scheduler

    server = APIServer()
    for i in range(16):
        server.create("nodes", make_node(f"n{i}", cpu="8", labels={"zone": f"z{i%4}"}))
    sched = Scheduler(server, KubeSchedulerConfiguration())
    sched.start()
    try:
        assert sched._mesh is not None, "scheduler must adopt the mesh"
        for i in range(24):
            server.create("pods", make_pod(f"p{i}", cpu="500m"))
        # poll for binds (wait_for_idle can win the race against informer
        # delivery of the just-created pods)
        import time

        deadline = time.time() + 120
        while time.time() < deadline:
            pods, _ = server.list("pods")
            if pods and all(p.spec.node_name for p in pods):
                break
            time.sleep(0.1)
        pods, _ = server.list("pods")
        assert all(p.spec.node_name for p in pods)
    finally:
        sched.stop()
