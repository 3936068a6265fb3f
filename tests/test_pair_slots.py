"""The pair axis J of the wave kernel follows the pairs the templates
reference (ops/templates.pair_slots: 1, 4, 16, 64, ...), and a dead slot
changes nothing: the kernel's outputs with the ladder's table are bitwise
what the 32-slot table of before PR 33 gives (padding kept here as the
reference)."""

import time

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
from kubernetes_tpu.client.apiserver import APIServer
from kubernetes_tpu.ops.encoding import SnapshotEncoder
from kubernetes_tpu.ops.lattice import DEFAULT_WEIGHTS
from kubernetes_tpu.ops.templates import (
    TemplateCache,
    build_pair_table,
    pair_slots,
)
from kubernetes_tpu.ops.wavelattice import make_wave_kernel
from kubernetes_tpu.scheduler import KubeSchedulerConfiguration, Scheduler
from kubernetes_tpu.utils.metrics import metrics

from test_lattice_smoke import make_node, make_pod


@pytest.mark.parametrize(
    "n_pairs,slots",
    [(0, 1), (1, 1), (2, 4), (4, 4), (5, 16), (16, 16), (17, 64), (65, 256)],
)
def test_pair_slots_rule(n_pairs, slots):
    assert pair_slots(n_pairs) == slots


def _cluster():
    enc = SnapshotEncoder()
    for i in range(24):
        enc.add_node(
            make_node(
                f"n{i}",
                cpu="8",
                labels={"zone": f"z{i % 4}", "rack": f"r{i % 6}"},
            )
        )
    for i in range(8):
        enc.add_pod(f"n{i}", make_pod(f"web-{i}", labels={"app": "web"}))
    # an existing pod whose own anti-affinity term batch pods can match
    enc.add_pod(
        "n9",
        make_pod("guard", labels={"app": "guard"}, affinity=_anti("victim", "rack")),
    )
    return enc


def _term(app, key):
    return PodAffinityTerm(
        label_selector=LabelSelector.make(match_labels={"app": app}),
        topology_key=key,
    )


def _aff(app, key):
    return Affinity(pod_affinity=PodAffinity(required=(_term(app, key),)))


def _anti(app, key):
    return Affinity(pod_anti_affinity=PodAntiAffinity(required=(_term(app, key),)))


def _spread(app, key):
    return TopologySpreadConstraint(
        max_skew=1,
        topology_key=key,
        when_unsatisfiable="DoNotSchedule",
        label_selector=LabelSelector.make(match_labels={"app": app}),
    )


def _plain(n):
    return [make_pod(f"plain-{i}", cpu="500m") for i in range(n)]


def _affine(n):  # required affinity to the residents' label: 1 pair
    return [
        make_pod(f"aff-{i}", labels={"app": "client"}, affinity=_aff("web", "zone"))
        for i in range(n)
    ]


def _anti_self(app, n):  # incoming term + the pods' own term matched: 2 pairs
    return [
        make_pod(f"{app}-{i}", labels={"app": app}, affinity=_anti(app, "zone"))
        for i in range(n)
    ]


def _hard_spread(n):  # 1 pair
    return [
        make_pod(
            f"spr-{i}",
            labels={"app": "s"},
            topology_spread_constraints=[_spread("s", "zone")],
        )
        for i in range(n)
    ]


def _victims(n):  # match the resident `guard`'s anti-affinity term: 1 pair
    return [make_pod(f"vic-{i}", labels={"app": "victim"}) for i in range(n)]


def _pinned():
    return [make_pod("pinned", cpu="500m", node_name="n5")]


# case -> (the pairs its templates reference, its pods)
CASES = {
    "plain-0": (0, lambda: _plain(6)),
    "pinned-0": (0, lambda: _plain(4) + _pinned()),
    "affinity-1": (1, lambda: _affine(6)),
    "anti-2": (2, lambda: _anti_self("w", 6)),
    "mixed-5": (
        5,
        lambda: _hard_spread(3) + _victims(2) + _anti_self("w", 3) + _affine(2)
        + _pinned(),
    ),
    "many-17": (
        17,
        lambda: sum((_anti_self(f"a{k}", 2) for k in range(8)), []) + _affine(2)
        + _pinned(),
    ),
}


def _padded(pt, j):
    """`pt` with its pair axis padded to `j` dead slots: what
    build_pair_table(j_cap=32) returned before PR 33."""

    def pad(x, axis, fill):
        x = np.asarray(x)
        width = [(0, 0)] * x.ndim
        width[axis] = (0, j - x.shape[axis])
        return jnp.asarray(np.pad(x, width, constant_values=fill))

    return pt._replace(
        is_eterm=pad(pt.is_eterm, 0, False),
        col=pad(pt.col, 0, -1),
        key=pad(pt.key, 0, 0),
        elig_tpl=pad(pt.elig_tpl, 0, -1),
        kind=pad(pt.kind, 0, -1),
        contrib=pad(pt.contrib, 1, 0.0),
        etm_match=pad(pt.etm_match, 1, False),
    )


@pytest.mark.parametrize("case", list(CASES))
def test_ladder_table_is_bitwise_the_32_slot_table(case):
    enc = _cluster()
    real, mk_pods = CASES[case]
    pods = mk_pods()
    eb = TemplateCache(enc).encode(pods, pad_to=32)
    pt = build_pair_table(enc, eb.tpl_np, eb.num_templates)
    assert int((np.asarray(pt.col) >= 0).sum()) == real
    assert pt.col.shape[0] == pair_slots(real)
    # 32 slots held every case but the last, whose 17 pairs take the 64 rung
    reference = _padded(pt, max(32, pt.col.shape[0]))
    has_pinned = bool((eb.batch.pod_name_row >= 0).any())
    assert has_pinned == ("pinned" in [p.metadata.name for p in pods])
    snap = enc.flush()
    # no donation: both runs read the one snapshot
    kern = jax.jit(
        make_wave_kernel(
            enc.cfg.v_cap, 16, 4, use_pallas_fit=True, has_pinned=has_pinned,
            pallas_interpret=True,
        )
    )
    w, key = jnp.asarray(DEFAULT_WEIGHTS), jax.random.PRNGKey(11)
    got_snap, got = kern(snap, eb.batch, pt, w, key)
    want_snap, want = kern(snap, eb.batch, reference, w, key)
    assert int(np.asarray(got.placed).sum()) > 0
    for name in got._fields:
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert a.tobytes() == b.tobytes(), name
    for name in got_snap._fields:
        a = np.asarray(getattr(got_snap, name))
        b = np.asarray(getattr(want_snap, name))
        assert a.tobytes() == b.tobytes(), name


def test_scheduler_counts_the_pair_slots_of_every_launch():
    """scheduler_wave_pair_slots_total grows by the table's J a launch: 1
    while the cached templates reference no pair, 4 from the first batch
    that brings two, and 4 for plain pods after it (the table is built
    over ALL cached templates)."""
    server = APIServer()
    # the device path at any batch size, as on a cluster of 5,000 nodes
    sched = Scheduler(server, KubeSchedulerConfiguration(small_batch_host_max=0))
    for i in range(8):
        server.create(
            "nodes", make_node(f"ps-{i}", cpu="64", labels={"zone": f"z{i}"})
        )

    def counts():
        return (
            metrics.counter("scheduler_wave_pair_slots_total"),
            metrics.counter("scheduler_wave_batches_total"),
        )

    def run(pods):
        slots0, waves0 = counts()
        for p in pods:
            server.create("pods", p)
        deadline = time.monotonic() + 180
        names = {p.metadata.name for p in pods}
        while time.monotonic() < deadline:
            bound = {
                p.metadata.name for p in server.list("pods")[0] if p.spec.node_name
            }
            if names <= bound:
                break
            time.sleep(0.05)
        assert names <= bound
        assert sched.wait_for_idle(30)
        slots1, waves1 = counts()
        assert waves1 > waves0
        return (slots1 - slots0) / (waves1 - waves0)

    sched.start()
    try:
        assert run(_plain(5)) == 1.0
        assert run(_anti_self("w", 5)) == 4.0
        assert run([make_pod(f"late-{i}", cpu="500m") for i in range(5)]) == 4.0
    finally:
        sched.stop()
