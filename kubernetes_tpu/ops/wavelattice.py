"""Wave-commit lattice: vectorized bulk pass + bounded conflict-resolution.

The first-cut kernel (ops/lattice.py) reproduced scheduleOne's serial
semantics as a P-step lax.scan, in which every step re-ran topology
segment-sums and rewrote a multi-MB carry. This kernel restructures the
batch cycle so nothing scales with P serially:

  Stage A (fully vectorized, template granularity):
    * filter masks, score matrix, normalization per TEMPLATE [TPL, N] — a
      burst of Deployment pods is one template, not P pods;
    * topology-domain sums ONCE per (predicate, topology-key) pair [J, V]
      (the PairTable), not once per pod; J is the table's own size (1, 4,
      16, ... slots for the pairs the templates reference): the segment
      scatters and gathers below cost a TPU one serial update per [J, N]
      element, dead slot or not;
    * per-template top-M candidate nodes: the M best-scoring nodes feasible
      at the launch's start, or, for a template with a hard
      (DoNotSchedule) topology-spread pair in a program compiled with
      `stratify`, the best nodes of EVERY domain of that pair, M / D a
      domain, whether or not the skew lets the domain take a pod yet: a
      zone over the skew at the start is the only feasible one two
      iterations later, and Stage B can commit only at the columns;
      per-pod candidate order = score-descending with per-pod random
      tie-noise (selectHost's uniform tie-break, generic_scheduler.go:235).

  Stage B (W waves, all-vectorized):
    every wave, each unplaced pod takes its best still-feasible candidate;
    conflicts are resolved batch-wide: per-node capacity by prefix-fit in
    pod order, per-(pair, domain) exclusivity by lowest pod index (one
    contributor per topology domain per wave keeps anti-affinity/spread
    sound). Losers retry next wave against updated deltas. The lowest
    active pod always wins all its groups, so every wave commits ≥1 pod —
    no livelock; leftovers defer to the next batch.

Serial-equivalence note (SURVEY §7 hard part (c)): within a batch, scores
are not recomputed after each commit (reference recomputes per pod), and
near-tie candidates may swap under the tie-noise epsilon. Placements remain
feasible-at-commit-time under full filter semantics; the divergence is
bounded to score staleness inside one batch window — the same staleness the
reference tolerates between its snapshot and async binds.

The snapshot's occupancy tensors are DONATED and returned updated with all
committed pods, so consecutive batches chain on-device with no host round
trip (SURVEY §7 hard part (d): persistent device state, delta-only uplink).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from .encoding import (
    DeviceSnapshot,
    ETERM_AFF_PREF,
    ETERM_AFF_REQ,
    ETERM_ANTI_PREF,
    ETERM_ANTI_REQ,
    PodBatch,
    RES_CPU,
    RES_MEM,
)
from .lattice import (
    DEFAULT_WEIGHTS,
    NUM_SCORE_COMPONENTS,
    SC_BALANCED,
    SC_IMAGE,
    SC_INTERPOD,
    SC_LEAST_ALLOC,
    SC_MOST_ALLOC,
    SC_NODE_AFFINITY,
    SC_PREFER_AVOID,
    SC_REQ_TO_CAP,
    SC_TAINT,
    SC_TOPO_SPREAD,
    _image_locality,
    _label_cols,
    _node_affinity_required,
    _node_affinity_score,
    _prefer_avoid,
    _taints,
)
from .templates import PairTable, TemplateBatch

TIE_EPS = 1e-3


class WaveResult(NamedTuple):
    chosen: Any  # [P] int32 node row, -1 = not placed
    placed: Any  # [P] bool
    deferred: Any  # [P] bool — feasible nodes existed but waves ran out
    commit_wave: Any  # [P] int32 — the Stage B iteration that committed the
    # pod, -1 = not placed. Binds leave in (commit_wave, pod) order: the
    # order in which every hard verdict held (one contributor per (pair,
    # domain) an iteration; within one iteration any order is sound)
    feasible_count: Any  # [P] int32 base-feasible node count
    score: Any  # [P] float32
    resolvable_tpl: Any  # [TPL, N] bool — preemption candidates per template
    feasible_tpl: Any  # [TPL, N] bool — pre-commit filter verdicts (the
    # differential-fuzz oracle surface; never fetched by the scheduler)


def _group_prefix_sums(groups, sort_key, values):
    """Exclusive prefix sums of `values` within equal-`groups` runs after
    sorting by sort_key (sort_key must sort group-contiguously, e.g.
    group*(P+1)+idx). Returns (order, exclusive_prefix[sorted order])."""
    order = jnp.argsort(sort_key)
    g = groups[order]
    v = values[order]
    cum = jnp.cumsum(v, axis=0)
    excl_global = cum - v
    base = excl_global[_run_starts(g)]
    return order, excl_global - base


def _run_starts(g):
    """For each position of `g` [X], where its run of equal values began:
    a running max over the indices at which a new run starts."""
    pos = jnp.arange(g.shape[0])
    is_start = jnp.concatenate([jnp.array([True]), g[1:] != g[:-1]])
    return jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, pos, -1)
    )


DEFAULT_RTC_SHAPE = ((0.0, 0.0), (100.0, 10.0))


def _ordered_bits(x):
    """int32 whose signed order is the float32 order of `x` (-0.0 below
    0.0): a sort key the TPU compares as an integer."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)


def _bool_cols(plane, idx):
    """plane[t, idx[t, m]] for a bool [T, N] plane, gathered as int32. On a
    2x2 TPU v5e mesh (jax 0.9.0, libtpu 0.0.34) the SPMD-partitioned gather
    of a bool operand sharded along the gathered axis returned wrong values
    for T >= 4 with M = 32 — int32 and float32 gathers of the same shapes
    were right — so every candidate of the small bucket read as statically
    infeasible and its pods were deferred forever."""
    return jnp.take_along_axis(plane.astype(jnp.int32), idx, axis=1) != 0


def stratified_columns(dom, elig, feas, score, m_c: int, v_cap: int):
    """One template's m_c candidate columns taken round-robin over the
    domains `dom` [N] (ids below v_cap) of a hard spread pair, from the
    `elig` nodes: (the columns' scores descending, -inf where no eligible
    node was left; their node rows). The order they are taken in is (rank
    of the node within its domain, feasible at the launch's start before
    not, score descending, row), the rank being by (`feas`, `score`, row)
    within the domain: each present domain gives its m_c / D best nodes,
    and a column that was feasible at the start is displaced only by
    another domain's share. With D >= m_c (a hostname spread) every rank
    is 0 and the list is top_k's over the feasible nodes.

    Three sorts over [N] with unique int32 keys: a sort the TPU compiles
    in seconds (a stable or several-key float32 sort of this size takes
    tens) and runs in 0.1 ms for [4, 8192] (my chip run, PR 35)."""
    n = dom.shape[0]
    if (2 * m_c + 3) * n >= 2**31:
        raise ValueError(f"{m_c} columns of {n} nodes overflow the int32 keys")
    rows = jnp.arange(n, dtype=jnp.int32)
    # 1: by (score descending, row); `at` is a node's place in that order
    _, row1, k1 = jax.lax.sort(
        (
            ~_ordered_bits(score),
            rows,
            jnp.where(elig, dom, v_cap) * 2 + (~feas).astype(jnp.int32),
        ),
        num_keys=2, is_stable=False,
    )
    # 2: by (domain, feasible first, score, row), the ineligible behind:
    # a node's rank within its domain
    k2, at2, row2 = jax.lax.sort((k1, rows, row1), num_keys=2, is_stable=False)
    d2 = k2 // 2
    # a node of rank m_c or more has m_c nodes before it: never taken
    rank = jnp.minimum(rows - _run_starts(d2), m_c)
    # 3: by (rank, feasible first, score, row)
    k3, row3 = jax.lax.sort(
        (
            jnp.where(d2 < v_cap, rank * 2 + k2 % 2, 2 * m_c + 2) * n + at2,
            row2,
        ),
        num_keys=1, is_stable=False,
    )
    col = row3[:m_c]
    v = jnp.where(k3[:m_c] < (2 * m_c + 2) * n, score[col], -jnp.inf)
    # real scores, descending: the tie groups, the per-pod shuffle and
    # the per-iteration re-score read top_v
    neg_v, col = jax.lax.sort((-v, col), num_keys=1, is_stable=True)
    return -neg_v, col


@functools.lru_cache(maxsize=32)
def make_wave_kernel(
    v_cap: int,
    m_cand: int = 128,
    n_waves: int = 8,
    hard_pod_affinity_weight: float = 1.0,
    use_pallas_fit: bool = False,
    score_refresh: bool = True,
    rtc_shape: tuple = DEFAULT_RTC_SHAPE,
    has_pinned: bool = True,
    pallas_interpret: bool = False,
    stratify: bool = False,
    mesh=None,
):
    """Build the wave kernel (unjitted) for the given static capacities.

    stratify=True compiles IN the candidate columns' stratification over
    a hard topology-spread pair's domains (Stage A, `stratified_columns`):
    three sorts over [TPL, N] that a batch without such a template does
    not pay. The host passes whether the batch's templates carry one
    (scheduler._batch_waves), as it passes the wave count; a template
    without a hard spread pair keeps its columns bit for bit either way.

    has_pinned=False compiles OUT the per-wave pinned-row plan (the
    [J, P] pair gathers + [TPL, J, P] verdict vmap below) — for the
    common all-unpinned batch that work is the same order as the [TPL, N]
    recompute this kernel eliminated, and its results would be discarded
    by the pinned select. The host passes the batch's actual pinnedness
    (a numpy any() over pod_name_row) as part of the variant key.

    rtc_shape: the RequestedToCapacityRatio piecewise points
    ((utilization%, score 0..10), ...) — static per profile, part of the
    kernel-variant key, interpolated device-side with jnp.interp so an
    arbitrary shape matches the host plugin exactly
    (requested_to_capacity_ratio.go:33; r4 verdict #7 closed the
    default-shape hardcode).

    use_pallas_fit routes the resource-fit mask (Stage A's fits0 and each
    wave's fits_w — the kernel's hottest recomputation) through the fused
    Pallas kernel in ops/pallas_ops.py instead of the XLA [TPL, N, R]
    broadcast. pallas_interpret runs that kernel in the Pallas
    interpreter instead of compiling it with Mosaic: the caller states
    it (tests on the CPU ask for it; the scheduler reports what it
    chose) — it is never inferred here. mesh: the snapshot's node-axis
    mesh when the kernel is jitted with sharded inputs
    (parallel/sharded.py). Mosaic kernels are not partitioned
    automatically; the fit mask is row-local, so under a mesh it runs
    once per node shard through shard_map.

    score_refresh re-evaluates the RESOURCE score components at each pod's
    candidate nodes every wave (cheap [P, M] gathers) so later waves see
    in-batch commits in their packing decisions instead of the batch-start
    snapshot — the serial-fidelity improvement for SURVEY §7 hard part (c);
    non-resource components stay Stage-A static (their pair counts are the
    documented in-batch staleness)."""
    if use_pallas_fit:
        from .pallas_ops import fit_mask as _pallas_fit_mask

        _fit = functools.partial(_pallas_fit_mask, interpret=pallas_interpret)
        if mesh is not None:
            (axis,) = mesh.axis_names
            _fit = jax.shard_map(
                _fit,
                mesh=mesh,
                in_specs=(PartitionSpec(), PartitionSpec(axis, None)),
                out_specs=PartitionSpec(None, axis),
                check_vma=False,
            )

    else:

        def _fit(req, free):
            return jnp.all(
                (req[:, None, :] == 0) | (req[:, None, :] <= free[None]),
                axis=-1,
            )

    def wave_kernel(
        snap: DeviceSnapshot, tb: TemplateBatch, pt: PairTable, weights, rng
    ):
        tpl: PodBatch = tb.tpl
        n = snap.valid.shape[0]
        TPL = tpl.valid.shape[0]
        P = tb.pod_tpl.shape[0]
        J = pt.col.shape[0]
        m_c = min(m_cand, n)  # candidate list cannot exceed node capacity

        # ================= Stage A: per-template statics =================
        def statics_one(bp):
            ns_aff = _node_affinity_required(snap, bp)
            taint_ok, prefer_cnt = _taints(snap, bp)
            unsched_ok = ~snap.unschedulable | bp.tolerates_unschedulable
            static_ok = snap.valid & ns_aff & taint_ok & unsched_ok
            return (
                static_ok,
                ns_aff,
                _node_affinity_score(snap, bp),
                prefer_cnt,
                _image_locality(snap, bp),
                _prefer_avoid(snap, bp),
            )

        static_ok, ns_aff, aff_score, prefer_cnt, img, avoid = jax.vmap(
            statics_one
        )(tpl)  # each [TPL, N]

        free0 = snap.allocatable - snap.requested  # [N, R]
        fits0 = _fit(tpl.req, free0)  # [TPL, N]
        ports0 = jnp.any(
            tpl.port_mask[:, None, :] & (snap.port_counts[None] > 0), axis=-1
        )  # [TPL, N]

        # ---- pair domain structure ----
        def pair_cols(j):
            col = jnp.clip(pt.col[j], 0, None)
            sidv = snap.sel_counts[:, jnp.clip(col, 0, snap.sel_counts.shape[1] - 1)]
            etv = snap.eterm_w[:, jnp.clip(col, 0, snap.eterm_w.shape[1] - 1)]
            w = jnp.where(pt.is_eterm[j], etv, sidv.astype(jnp.float32))
            dom, _ = _label_cols(snap, pt.key[j])
            e = pt.elig_tpl[j]
            elig = jnp.where(
                e >= 0, ns_aff[jnp.clip(e, 0, TPL - 1)], jnp.ones_like(snap.valid)
            )
            elig = elig & snap.valid & (dom >= 0)
            return w, dom, elig

        w_j, dom_j, elig_j = jax.vmap(pair_cols)(jnp.arange(J))  # [J, N]

        def dom_sums(w, dom, elig, delta):
            seg = jnp.where(elig, dom, v_cap)
            sums = jax.ops.segment_sum(
                jnp.where(elig, w, 0.0), seg, num_segments=v_cap
            ) + delta  # [V]
            present = (
                jax.ops.segment_max(elig.astype(jnp.int32), seg, num_segments=v_cap)
                > 0
            )
            node_cnt = jnp.where(dom >= 0, sums[jnp.clip(dom, 0, v_cap - 1)], 0.0)
            min_dom = jnp.min(jnp.where(present, sums, jnp.inf))
            return node_cnt, min_dom, jnp.sum(sums), sums, present

        cnt0, min0, tot0, base_dom, present_dom = jax.vmap(dom_sums)(
            w_j, dom_j, elig_j, jnp.zeros((J, v_cap))
        )  # cnt0 [J, N]; base_dom, present_dom [J, V] — wave-invariant

        def tpl_pair_verdicts(t, cnt, min_d, tot, dom):
            """Carry-dependent filter verdicts for template t given pair
            counts (cnt [J, X], min_d [J], tot [J], dom [J, X]). X is the
            column axis: all N node rows in Stage A, the template's M
            candidate columns in the waves."""
            def spread_c(pair, skew, hard, selfm):
                ok_pair = pair >= 0
                p = jnp.clip(pair, 0, J - 1)
                haskey = dom[p] >= 0
                m = jnp.where(jnp.isfinite(min_d[p]), min_d[p], 0.0)
                skewed = cnt[p] + jnp.where(selfm, 1.0, 0.0) - m > skew
                bad = hard & (skewed | ~haskey)
                soft = jnp.where(~hard, cnt[p], 0.0)
                return jnp.where(ok_pair, bad, False), jnp.where(ok_pair, soft, 0.0)

            sbad, ssoft = jax.vmap(spread_c)(
                pt.spr_pair[t], pt.spr_skew[t], pt.spr_hard[t], pt.spr_self[t]
            )
            spread_bad = jnp.any(sbad, axis=0)
            spread_pen = jnp.sum(ssoft, axis=0)

            def aff_a(pair, selfm):
                ok_pair = pair >= 0
                p = jnp.clip(pair, 0, J - 1)
                haskey = dom[p] >= 0
                ok = (cnt[p] > 0) | ((tot[p] == 0) & selfm & haskey)
                return jnp.where(ok_pair, ok, True)

            aff_ok = jnp.all(jax.vmap(aff_a)(pt.aff_pair[t], pt.aff_self[t]), axis=0)

            def anti_b(pair):
                ok_pair = pair >= 0
                p = jnp.clip(pair, 0, J - 1)
                bad = (dom[p] >= 0) & (cnt[p] > 0)
                return jnp.where(ok_pair, bad, False)

            anti_bad = jnp.any(jax.vmap(anti_b)(pt.anti_pair[t]), axis=0)

            et_rel = pt.etm_match[t] & (pt.kind == ETERM_ANTI_REQ)  # [J]
            eterm_bad = jnp.any(
                et_rel[:, None] & (dom >= 0) & (cnt > 0), axis=0
            )
            return spread_bad, spread_pen, aff_ok, anti_bad, eterm_bad

        spread_bad0, spread_pen0, aff_ok0, anti_bad0, eterm_bad0 = jax.vmap(
            lambda t: tpl_pair_verdicts(t, cnt0, min0, tot0, dom_j)
        )(jnp.arange(TPL))

        feasible0 = (
            static_ok & fits0 & ~ports0 & ~spread_bad0 & aff_ok0 & ~anti_bad0
            & ~eterm_bad0
        )  # [TPL, N]
        resolvable_tpl = static_ok & ~feasible0
        feas_cnt_tpl = jnp.sum(feasible0.astype(jnp.int32), axis=1)  # [TPL]

        # ---- scores [TPL, N] ----
        # resource scores only read the cpu/mem columns: compute the two
        # [TPL, N] fraction planes directly instead of materializing the
        # [TPL, N, R] nz_used broadcast (R× less HBM traffic in Stage A)
        def _frac(col):
            a = jnp.maximum(
                snap.allocatable[:, col].astype(jnp.float32), 1.0
            )[None]
            u = (
                snap.nonzero_req[:, col][None]
                + tpl.nonzero_req[:, col][:, None]
            ).astype(jnp.float32)
            return jnp.clip(u / a, 0.0, 1.0)

        cpu_f, mem_f = _frac(RES_CPU), _frac(RES_MEM)
        least = ((1.0 - cpu_f) + (1.0 - mem_f)) * 50.0
        most = (cpu_f + mem_f) * 50.0
        balanced = (1.0 - jnp.abs(cpu_f - mem_f)) * 100.0
        # piecewise shape over mean utilization%, scaled 0..100 like the
        # host plugin (score 0..10 * 10)
        rtc_xs = jnp.asarray([p[0] for p in rtc_shape], jnp.float32)
        rtc_ys = jnp.asarray([p[1] for p in rtc_shape], jnp.float32)

        def _rtc(cf, mf):
            return jnp.interp((cf + mf) * 50.0, rtc_xs, rtc_ys) * 10.0

        rtc = _rtc(cpu_f, mem_f)

        # interpod score: existing pods' terms + incoming preferred terms
        sgn = jnp.select(
            [
                pt.kind == ETERM_ANTI_PREF,
                pt.kind == ETERM_AFF_PREF,
                pt.kind == ETERM_AFF_REQ,
            ],
            [-1.0, 1.0, hard_pod_affinity_weight],
            default=0.0,
        )  # [J]
        ip_et = jnp.einsum(
            "tj,jn->tn", pt.etm_match.astype(jnp.float32) * sgn[None, :], cnt0
        )

        def ppref_t(t):
            def one(pair, w):
                p = jnp.clip(pair, 0, J - 1)
                return jnp.where(pair >= 0, w * cnt0[p], 0.0)

            return jnp.sum(jax.vmap(one)(pt.pref_pair[t], pt.pref_w[t]), axis=0)

        ip = ip_et + jax.vmap(ppref_t)(jnp.arange(TPL))  # [TPL, N]

        def norm_max(x, feas):
            mx = jnp.max(jnp.where(feas, x, -jnp.inf), axis=1, keepdims=True)
            safe = jnp.where(jnp.isfinite(mx) & (mx > 0), mx, 1.0)
            return jnp.clip(x / safe * 100.0, 0.0, 100.0)

        def norm_invert(x, feas):
            mx = jnp.max(jnp.where(feas, x, -jnp.inf), axis=1, keepdims=True)
            ok = jnp.isfinite(mx) & (mx > 0)
            safe = jnp.where(ok, mx, 1.0)
            return jnp.where(ok, (safe - x) / safe * 100.0, 100.0)

        ip_mx = jnp.max(
            jnp.where(feasible0, jnp.abs(ip), 0.0), axis=1, keepdims=True
        )
        ip_norm = jnp.where(ip_mx > 0, ip / ip_mx * 100.0, 0.0)

        # DefaultPodTopologySpread: same-service pods per node through the
        # service-derived sel_counts columns (templates sharing a service
        # share the mask); MAX over matching services mirrors the host's
        # any()-dedup for non-overlapping services. Stage-A counts like the
        # other pair scores — staleness within the batch window is the
        # kernel's documented score model.
        svc_cnt = jnp.max(
            jnp.where(
                tpl.match_svc[:, None, :],
                snap.sel_counts[None].astype(jnp.float32),
                0.0,
            ),
            axis=-1,
        )  # [TPL, N]

        # heterogeneity/cost columns are per-node; broadcast over templates
        # so the same norm_invert (per-template over feasible) applies
        cost_col = jnp.broadcast_to(
            snap.cost_milli.astype(jnp.float32)[None, :], least.shape
        )
        energy_col = jnp.broadcast_to(
            snap.energy_milli.astype(jnp.float32)[None, :], least.shape
        )
        comps = jnp.stack(
            [
                least,
                most,
                balanced,
                rtc,
                norm_max(aff_score, feasible0),
                norm_invert(prefer_cnt, feasible0),
                img,
                avoid,
                norm_invert(spread_pen0, feasible0),
                ip_norm,
                norm_invert(svc_cnt, feasible0),
                norm_invert(cost_col, feasible0),
                norm_invert(energy_col, feasible0),
            ]
        )  # [K, TPL, N]
        total_score = jnp.einsum("k,ktn->tn", weights, comps)

        # ---- top-M candidates per template ----
        masked = jnp.where(feasible0, total_score, -jnp.inf)
        top_v, top_i = jax.lax.top_k(masked, m_c)  # [TPL, M]
        if stratify:
            def stratified_one(spr_pair, spr_hard, elig, feas, score):
                """(whether the template has a hard spread pair, its
                stratified columns' scores and rows): stratified by the
                hard pair with the fewest present domains; the others
                are re-checked per iteration like every verdict."""
                is_hard = (spr_pair >= 0) & spr_hard  # [C]
                p = jnp.clip(spr_pair, 0, J - 1)
                n_dom = jnp.sum(present_dom[p].astype(jnp.int32), axis=1)
                by = p[jnp.argmin(jnp.where(is_hard, n_dom, v_cap + 1))]
                # a node without a hard pair's key fails that pair
                # whatever the counts are
                elig = elig & jnp.all(
                    ~is_hard[:, None] | (dom_j[p] >= 0), axis=0
                )
                return (jnp.any(is_hard),) + stratified_columns(
                    dom_j[by], elig, feas, score, m_c, v_cap
                )

            # every launch-start verdict but the hard spreads' skew
            # comparison: the one verdict that commits in OTHER domains
            # turn from bad to good inside a launch. Stage B re-checks it
            # with every other verdict at each column in each iteration
            eligible = (
                static_ok & fits0 & ~ports0 & aff_ok0 & ~anti_bad0
                & ~eterm_bad0
            )
            has_strat, strat_v, strat_i = jax.vmap(stratified_one)(
                pt.spr_pair, pt.spr_hard, eligible, feasible0, total_score
            )
            top_v = jnp.where(has_strat[:, None], strat_v, top_v)
            top_i = jnp.where(has_strat[:, None], strat_i, top_i)

        # ---- per-pod candidate ordering ----
        t_of = jnp.clip(tb.pod_tpl, 0, TPL - 1)  # [P]
        noise = jax.random.uniform(rng, (P, m_c), maxval=0.999)
        # top_v is sorted descending; equal-score runs form groups. Order
        # candidates by score-group, uniformly random within a group (the
        # float-safe form of selectHost's uniform tie-break — adding tiny
        # noise to raw scores underflows when weights reach 1e4×100).
        grp_id = jnp.cumsum(
            jnp.concatenate(
                [jnp.zeros((TPL, 1), jnp.float32),
                 (top_v[:, 1:] != top_v[:, :-1]).astype(jnp.float32)],
                axis=1,
            ),
            axis=1,
        )  # [TPL, M]
        pod_v = top_v[t_of]  # [P, M]
        order = jnp.argsort(grp_id[t_of] + noise, axis=1)  # [P, M]
        # order doubles as the SLOT index into the template's top-M column
        # list: per-wave feasibility is evaluated once per (template,
        # column) at [TPL, M] and pods read it through cand_slot — exact,
        # because every non-pinned candidate is one of its template's
        # top-M columns (r4 verdict #2: wave re-checks must not scale
        # with N)
        cand_slot = order
        cand_nodes = jnp.take_along_axis(top_i[t_of], order, axis=1)  # [P, M]
        cand_valid = jnp.isfinite(jnp.take_along_axis(pod_v, order, axis=1))
        # pinned pods: single candidate = the pinned row (still filter-checked)
        pinned = tb.pod_name_row >= 0
        pin_rows = jnp.clip(tb.pod_name_row, 0, n - 1)  # [P]
        cand_nodes = jnp.where(
            pinned[:, None],
            jnp.where(
                jnp.arange(m_c)[None, :] == 0,
                pin_rows[:, None],
                0,
            ),
            cand_nodes,
        )
        cand_slot = jnp.where(pinned[:, None], 0, cand_slot)
        pin_feas = _bool_cols(feasible0[t_of], pin_rows[:, None])[:, 0]
        cand_valid = jnp.where(
            pinned[:, None],
            (jnp.arange(m_c)[None, :] == 0) & pin_feas[:, None],
            cand_valid,
        )
        # spec.nodeName names a node the cache doesn't know (row -2): the
        # NodeName filter fails everywhere -> unschedulable, never placed
        cand_valid = cand_valid & (tb.pod_name_row != -2)[:, None]
        cand_nodes = jnp.clip(cand_nodes, 0, n - 1)

        # ---- per-wave candidate-column statics (hoisted gathers) ----
        static_ok_c = _bool_cols(static_ok, top_i)  # [TPL, M]
        free0_cols = free0[top_i]  # [TPL, M, R] batch-start free at columns
        port0_cols = snap.port_counts[top_i]  # [TPL, M, PV']
        dom_cols = jnp.moveaxis(dom_j[:, top_i], 1, 0)  # [TPL, J, M]
        cnt0_cols = jnp.moveaxis(cnt0[:, top_i], 1, 0)  # [TPL, J, M]
        # flat per-wave gather plan for dom_d at the candidate columns
        dom_cols_flat = jnp.clip(
            jnp.moveaxis(dom_cols, 0, 1).reshape(J, TPL * m_c), 0, v_cap - 1
        )  # [J, TPL*M]
        # pinned pods may name a row outside top-M: their per-wave checks
        # (resources, ports, AND pair verdicts) run per-pod at the pinned
        # row — the [J, P] column plan below keeps the pair re-check live
        # against in-batch commits, same as the candidate columns.
        if has_pinned:
            dom_pin = dom_j[:, pin_rows]  # [J, P]
            dom_pin_flat = jnp.clip(dom_pin, 0, v_cap - 1)
            cnt0_pin = cnt0[:, pin_rows]  # [J, P]
            pin_req = tpl.req[t_of]  # [P, R]
            pin_ports = tpl.port_mask[t_of]  # [P, PV']

        if score_refresh:
            # static pieces of the per-wave candidate re-score: the
            # NON-resource score residual at each candidate, plus the
            # batch-start nonzero/alloc cpu+mem columns there
            w_res = (
                weights[SC_LEAST_ALLOC] * least
                + weights[SC_MOST_ALLOC] * most
                + weights[SC_BALANCED] * balanced
                + weights[SC_REQ_TO_CAP] * rtc
            )  # [TPL, N]
            cand_resid = jnp.take_along_axis(
                (total_score - w_res)[t_of], cand_nodes, axis=1
            )  # [P, M]
            alloc_cpu_c = jnp.maximum(
                snap.allocatable[:, RES_CPU][cand_nodes].astype(jnp.float32),
                1.0,
            )
            alloc_mem_c = jnp.maximum(
                snap.allocatable[:, RES_MEM][cand_nodes].astype(jnp.float32),
                1.0,
            )
            nz_cpu0_c = snap.nonzero_req[:, RES_CPU][cand_nodes]
            nz_mem0_c = snap.nonzero_req[:, RES_MEM][cand_nodes]
            pod_nz_cpu = tpl.nonzero_req[:, RES_CPU][t_of][:, None]
            pod_nz_mem = tpl.nonzero_req[:, RES_MEM][t_of][:, None]

        # which pods participate in pair exclusivity (contributor or
        # hard-checker), per pair
        checks = jnp.zeros((TPL, J), bool)
        def scatter_pairs(checks, pairs, extra_mask=None):
            m = pairs >= 0 if extra_mask is None else (pairs >= 0) & extra_mask
            idx = jnp.clip(pairs, 0, J - 1)
            return checks.at[jnp.arange(TPL)[:, None], idx].max(m)

        checks = scatter_pairs(checks, pt.spr_pair, pt.spr_hard)
        checks = scatter_pairs(checks, pt.anti_pair)
        checks = checks | (pt.etm_match & (pt.kind == ETERM_ANTI_REQ)[None, :])
        # Exclusivity is only needed for pairs some template HARD-checks:
        # those verdicts can be invalidated by a same-wave contributor in the
        # same domain. Pure-affinity pairs (cnt>0 checks) are monotone under
        # additions, so their contributors commit freely — without this gate
        # a burst of one Deployment's affinity pods serializes to one commit
        # per wave.
        needs_excl = jnp.any(checks, axis=0)  # [J]
        participates = (checks | (pt.contrib != 0)) & needs_excl[None, :]
        is_contrib_tpl = pt.contrib != 0  # [TPL, J]
        uses_carveout = jnp.zeros((TPL, J), bool)
        uses_carveout = scatter_pairs(uses_carveout, pt.aff_pair, pt.aff_self)

        # resource matrix for prefix-fit: requests ⧺ port usage (capacity 1)
        PV = snap.port_counts.shape[1]
        req_ext_tpl = jnp.concatenate(
            [tpl.req.astype(jnp.int32), tpl.port_mask.astype(jnp.int32)], axis=1
        )  # [TPL, R+PV]

        # ================= Stage B: waves =================
        def wave(w, state):
            placed, chosen, commit_wave, req_d, port_d, dom_d, nz2_d = state
            free_d = free0 - req_d  # [N, R] (prefix-fit still needs full N)
            # ---- candidate-column re-checks: [TPL, M], never [TPL, N] ----
            free_c = free0_cols - req_d[top_i]  # [TPL, M, R]
            fits_w_c = jnp.all(
                (tpl.req[:, None, :] == 0) | (tpl.req[:, None, :] <= free_c),
                axis=-1,
            )  # [TPL, M]
            ports_w_c = jnp.any(
                tpl.port_mask[:, None, :]
                & ((port0_cols + port_d[top_i]) > 0),
                axis=-1,
            )  # [TPL, M]
            dd = jnp.take_along_axis(dom_d, dom_cols_flat, axis=1).reshape(
                J, TPL, m_c
            )  # [J, TPL, M] committed-delta at each column's domain
            cnt_w_cols = cnt0_cols + jnp.where(
                dom_cols >= 0, jnp.moveaxis(dd, 0, 1), 0.0
            )  # [TPL, J, M]
            sums_w = base_dom + dom_d  # [J, V]
            min_w = jnp.min(jnp.where(present_dom, sums_w, jnp.inf), axis=1)
            tot_w = tot0 + jnp.sum(dom_d, axis=1)

            sb, _, ao, ab, eb = jax.vmap(
                lambda t, cnt, dom: tpl_pair_verdicts(t, cnt, min_w, tot_w, dom)
            )(jnp.arange(TPL), cnt_w_cols, dom_cols)
            wave_feas_c = (
                static_ok_c & fits_w_c & ~ports_w_c & ~sb & ao & ~ab & ~eb
            )  # [TPL, M]

            cand_feas = wave_feas_c[t_of[:, None], cand_slot] & cand_valid
            if has_pinned:
                # pinned pods: live resource/port fit at the pinned row +
                # live pair verdicts there (row may be outside top-M; the
                # batch-start value would miss in-batch commits — a wave-1
                # contributor into domain D must block a wave-2 pinned pod
                # whose template requires anti-affinity on D)
                pin_free = free_d[pin_rows]  # [P, R]
                pin_fit = jnp.all(
                    (pin_req == 0) | (pin_req <= pin_free), axis=-1
                )
                pin_port_bad = jnp.any(
                    pin_ports & ((snap.port_counts + port_d)[pin_rows] > 0),
                    axis=-1,
                )
                dd_pin = jnp.take_along_axis(dom_d, dom_pin_flat, axis=1)
                cnt_pin = cnt0_pin + jnp.where(dom_pin >= 0, dd_pin, 0.0)
                sb_p, _, ao_p, ab_p, eb_p = jax.vmap(
                    lambda t: tpl_pair_verdicts(
                        t, cnt_pin, min_w, tot_w, dom_pin
                    )
                )(jnp.arange(TPL))  # each [TPL, P]
                pair_ok_pin = (~sb_p & ao_p & ~ab_p & ~eb_p)[
                    t_of, jnp.arange(P)
                ]  # [P]
                pin_ok_w = pin_fit & ~pin_port_bad & pair_ok_pin
                # replace (not AND): a pinned pod's single candidate is the
                # pinned row, whose verdict is pin_ok_w — slot 0 of the
                # template's column table is a different node entirely.
                # cand_valid already restricts pinned pods to slot 0 and
                # carries the batch-start full feasibility at the pinned
                # row.
                cand_feas = jnp.where(
                    pinned[:, None], cand_valid & pin_ok_w[:, None], cand_feas
                )  # [P, M]
            if score_refresh:
                # re-evaluate the resource scores at the candidates with
                # this wave's committed occupancy; the candidate list is
                # pre-shuffled within equal-static-score groups, so a
                # plain argmax inherits the uniform tie-break
                cpu_f_c = jnp.clip(
                    (nz_cpu0_c + nz2_d[:, 0][cand_nodes] + pod_nz_cpu)
                    .astype(jnp.float32)
                    / alloc_cpu_c,
                    0.0,
                    1.0,
                )
                mem_f_c = jnp.clip(
                    (nz_mem0_c + nz2_d[:, 1][cand_nodes] + pod_nz_mem)
                    .astype(jnp.float32)
                    / alloc_mem_c,
                    0.0,
                    1.0,
                )
                res_c = (
                    weights[SC_LEAST_ALLOC]
                    * (((1.0 - cpu_f_c) + (1.0 - mem_f_c)) * 50.0)
                    + weights[SC_MOST_ALLOC] * ((cpu_f_c + mem_f_c) * 50.0)
                    + weights[SC_BALANCED]
                    * ((1.0 - jnp.abs(cpu_f_c - mem_f_c)) * 100.0)
                    + weights[SC_REQ_TO_CAP] * _rtc(cpu_f_c, mem_f_c)
                )
                score_c = jnp.where(
                    cand_feas, cand_resid + res_c, -jnp.inf
                )  # [P, M]
                first = jnp.argmax(score_c, axis=1)
            else:
                first = jnp.argmax(cand_feas, axis=1)
            has = jnp.any(cand_feas, axis=1)
            cand_n = cand_nodes[jnp.arange(P), first]
            active = tb.pod_valid & ~placed & has

            # -- capacity prefix-fit in pod order --
            grp = jnp.where(active, cand_n, n)
            sort_key = grp * (P + 1) + jnp.arange(P)
            vals = req_ext_tpl[t_of] * active[:, None].astype(jnp.int32)
            order_c, excl = _group_prefix_sums(grp, sort_key, vals)
            free_ext = jnp.concatenate(
                [
                    free_d,
                    1 - jnp.minimum(snap.port_counts + port_d, 1),
                ],
                axis=1,
            )  # [N, R+PV]
            node_sorted = cand_n[order_c]
            req_sorted = req_ext_tpl[t_of][order_c]
            fit_sorted = jnp.all(
                excl + req_sorted <= free_ext[node_sorted], axis=1
            )
            fit_ok = jnp.zeros(P, bool).at[order_c].set(fit_sorted)

            # -- (pair, domain) exclusivity --
            pod_dom = dom_j[:, cand_n].T  # [P, J] domain of candidate per pair
            carve = (
                uses_carveout[t_of] & (tot_w == 0)[None, :] & active[:, None]
            )
            # carveout claims are exclusive regardless of the needs_excl gate
            # (two pods claiming "no matches anywhere" in different domains
            # would diverge from serial semantics)
            part = (participates[t_of] | carve) & active[:, None]  # [P, J]
            key_pd = jnp.where(
                carve,
                jnp.arange(J)[None, :] * (v_cap + 2) + v_cap + 1,
                jnp.arange(J)[None, :] * (v_cap + 2)
                + jnp.clip(pod_dom, 0, v_cap - 1),
            )
            part = part & ((pod_dom >= 0) | carve)
            is_contrib = (is_contrib_tpl[t_of] | carve) & part  # [P, J]
            dump = J * (v_cap + 2)
            flat_key = jnp.where(part, key_pd, dump).reshape(-1)
            flat_key_c = jnp.where(is_contrib, key_pd, dump).reshape(-1)
            pod_idx_mat = jnp.broadcast_to(
                jnp.arange(P)[:, None], (P, J)
            ).reshape(-1)
            nseg = dump + 1
            min_all = jax.ops.segment_min(pod_idx_mat, flat_key, num_segments=nseg)
            min_con = jax.ops.segment_min(
                pod_idx_mat, flat_key_c, num_segments=nseg
            )
            # contributor commits iff it is the group's lowest participant;
            # checker-only pods commit iff no contributor is committing in
            # their group this wave (group min is a checker)
            g_all = min_all[flat_key].reshape(P, J)
            g_con = min_con[flat_key].reshape(P, J)
            # serial-order guard for the carveout: in index order a lower
            # contributor to pair j would commit before the claimant, making
            # its tot==0 premise false — so block the claim this wave when
            # any lower-indexed active contributor exists pair-wide
            contrib_any = is_contrib_tpl[t_of] & active[:, None] & ~carve
            pair_key = jnp.where(
                contrib_any, jnp.arange(J)[None, :], J
            ).reshape(-1)
            min_contrib_pair = jax.ops.segment_min(
                pod_idx_mat, pair_key, num_segments=J + 1
            )[:J]
            carve_allowed = (
                jnp.arange(P)[:, None] < min_contrib_pair[None, :]
            )  # [P, J]
            ok_pair = jnp.where(
                is_contrib,
                g_all == pod_idx_mat.reshape(P, J),
                g_con > g_all,
            ) & (~carve | carve_allowed)
            dom_ok = jnp.all(ok_pair | ~part, axis=1)

            commit = active & fit_ok & dom_ok
            ci = jnp.where(commit, cand_n, n)  # OOB -> dropped
            req_d = req_d.at[ci].add(tpl.req[t_of], mode="drop")
            port_d = port_d.at[ci].add(
                tpl.port_mask[t_of].astype(jnp.int32), mode="drop"
            )
            nz2_d = nz2_d.at[ci].add(
                jnp.stack(
                    [
                        tpl.nonzero_req[:, RES_CPU],
                        tpl.nonzero_req[:, RES_MEM],
                    ],
                    axis=1,
                )[t_of],
                mode="drop",
            )
            contrib_p = pt.contrib[t_of] * commit[:, None]  # [P, J]
            dd_key = jnp.where(
                (pod_dom >= 0) & (contrib_p != 0),
                jnp.arange(J)[None, :] * v_cap + jnp.clip(pod_dom, 0, v_cap - 1),
                J * v_cap,
            ).reshape(-1)
            dom_d = (
                dom_d.reshape(-1)
                .at[dd_key]
                .add(contrib_p.reshape(-1), mode="drop")
                .reshape(J, v_cap)
            )
            placed = placed | commit
            chosen = jnp.where(commit, cand_n, chosen)
            commit_wave = jnp.where(
                commit, jnp.asarray(w, jnp.int32), commit_wave
            )
            return placed, chosen, commit_wave, req_d, port_d, dom_d, nz2_d

        state0 = (
            jnp.zeros(P, bool),
            jnp.full(P, -1, jnp.int32),
            jnp.full(P, -1, jnp.int32),
            jnp.zeros_like(snap.requested),
            jnp.zeros_like(snap.port_counts),
            jnp.zeros((J, v_cap), jnp.float32),
            jnp.zeros((n, 2), snap.nonzero_req.dtype),
        )
        # Static trip count: the host picks n_waves per batch shape
        # (scheduler._batch_waves), so one compiled variant serves every
        # batch of that shape and its cost does not depend on the data. An
        # early exit once nothing is left to place is ROADMAP A5 and
        # needs a measurement on the chip first.
        placed, chosen, commit_wave, req_d, port_d, dom_d, _nz2_d = (
            jax.lax.fori_loop(0, n_waves, wave, state0)
        )

        # ================= finalize: commit occupancy to snapshot ==========
        # Every field the host's add_pod touches is committed here (incl.
        # prio_req by priority band), so the scheduler's replay can skip the
        # dirty-row re-upload entirely (encoding.add_pod device_synced=True).
        ci = jnp.where(placed, chosen, n)
        band = jnp.clip(tb.pod_band, 0, snap.prio_req.shape[1] - 1)
        new_snap = snap._replace(
            requested=snap.requested.at[ci].add(tpl.req[t_of], mode="drop"),
            nonzero_req=snap.nonzero_req.at[ci].add(
                tpl.nonzero_req[t_of], mode="drop"
            ),
            sel_counts=snap.sel_counts.at[ci].add(
                tpl.match_sel[t_of].astype(jnp.int32), mode="drop"
            ),
            eterm_w=snap.eterm_w.at[ci].add(tpl.eterm_add[t_of], mode="drop"),
            port_counts=snap.port_counts.at[ci].add(
                tpl.port_mask[t_of].astype(jnp.int32), mode="drop"
            ),
            prio_req=snap.prio_req.at[ci, band].add(tpl.req[t_of], mode="drop"),
        )

        feas_cnt = jnp.where(tb.pod_valid, feas_cnt_tpl[t_of], 0)
        feas_cnt = jnp.where(
            pinned, jnp.where(pin_feas & tb.pod_valid, 1, 0), feas_cnt
        )
        # unknown pinned node: zero feasible so the pod FAILS (backoff +
        # unschedulable event) instead of deferring into a requeue hot-loop
        feas_cnt = jnp.where(tb.pod_name_row == -2, 0, feas_cnt)
        score_out = jnp.where(
            placed,
            total_score[t_of, jnp.clip(chosen, 0, n - 1)],
            -jnp.inf,
        )
        deferred = tb.pod_valid & ~placed & (feas_cnt > 0)
        return new_snap, WaveResult(
            chosen=jnp.where(placed, chosen, -1),
            placed=placed,
            deferred=deferred,
            commit_wave=commit_wave,
            feasible_count=feas_cnt,
            score=score_out,
            resolvable_tpl=resolvable_tpl,
            feasible_tpl=feasible0,
        )

    return wave_kernel


@functools.lru_cache(maxsize=32)
def make_wave_kernel_jit(
    v_cap: int,
    m_cand: int = 128,
    n_waves: int = 8,
    hard_pod_affinity_weight: float = 1.0,
    use_pallas_fit: bool = False,
    score_refresh: bool = True,
    rtc_shape: tuple = DEFAULT_RTC_SHAPE,
    has_pinned: bool = True,
    pallas_interpret: bool = False,
    stratify: bool = False,
):
    return jax.jit(
        make_wave_kernel(
            v_cap,
            m_cand,
            n_waves,
            hard_pod_affinity_weight,
            use_pallas_fit,
            score_refresh,
            rtc_shape,
            has_pinned,
            pallas_interpret,
            stratify,
        ),
        donate_argnums=(0,),
    )
