"""The fused filter→score→select lattice: one XLA program per pod batch.

This kernel absorbs everything between Schedule entry and selectHost in the
reference hot path (generic_scheduler.go:150-235: findNodesThatFitPod +
prioritizeNodes + selectHost), for a whole batch of pods at once:

* **Stage A** (vmap over pods, carry-free): plugins whose verdict cannot be
  changed by in-batch placements — NodeName, NodeUnschedulable, NodeAffinity
  (+nodeSelector), TaintToleration, ImageLocality, NodePreferAvoidPods. These
  also define the "unresolvable" failure class the preemption pass needs
  (UnschedulableAndUnresolvable semantics, framework interface.go:54-99).

* **Stage B** (lax.scan over pods): plugins that read cluster occupancy —
  NodeResourcesFit, NodePorts, PodTopologySpread, InterPodAffinity — against
  snapshot + an in-batch carry (requested/sel_counts/eterm/port deltas of the
  pods already committed this batch). The scan IS the conflict resolution:
  it reproduces the reference's strictly-serial scheduleOne semantics while
  staying on-device, so a batch of P pods costs one kernel launch instead of
  P scheduling cycles.

Scores mirror framework.RunScorePlugins (framework.go:503-580): each plugin
produces a [N] score normalized to 0..100 over feasible nodes, then a
weighted sum. Host selects via on-device argmax with uniform random
tie-break (selectHost's reservoir sampling, generic_scheduler.go:235).

Sharding: every [N]- or [N,·]-shaped value may be sharded over the mesh's
"nodes" axis; reductions (max/argmax/segment sums over domains) become XLA
collectives over ICI under pjit (see parallel/sharded.py).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .encoding import (
    DeviceSnapshot,
    EFFECT_NO_EXECUTE,
    EFFECT_NO_SCHEDULE,
    EFFECT_PREFER_NO_SCHEDULE,
    ENC_OP_EXISTS,
    ENC_OP_GT,
    ENC_OP_IN,
    ENC_OP_LT,
    ENC_OP_NOT_EXISTS,
    ENC_OP_NOT_IN,
    ETERM_AFF_PREF,
    ETERM_AFF_REQ,
    ETERM_ANTI_PREF,
    ETERM_ANTI_REQ,
    PodBatch,
    RES_CPU,
    RES_MEM,
)
from .batch import TOL_OP_EXISTS

INT_MIN = jnp.iinfo(jnp.int32).min

# Score component indices (fixed order; weights vector selects the profile).
SC_LEAST_ALLOC = 0
SC_MOST_ALLOC = 1
SC_BALANCED = 2
SC_REQ_TO_CAP = 3
SC_NODE_AFFINITY = 4
SC_TAINT = 5
SC_IMAGE = 6
SC_PREFER_AVOID = 7
SC_TOPO_SPREAD = 8
SC_INTERPOD = 9
SC_SELECTOR_SPREAD = 10  # DefaultPodTopologySpread (same-service pod count)
# heterogeneity/cost components (encoding's per-node column family):
# normalized-inverted within the feasible set, so a cheaper / lower-energy
# node scores higher; an unlabeled (all-zero) cluster scores flat
SC_COST = 11  # cost-per-hour (snap.cost_milli)
SC_ENERGY = 12  # energy proxy (snap.energy_milli)
NUM_SCORE_COMPONENTS = 13

# Default profile weights: all 1 except NodePreferAvoidPods=10000
# (algorithmprovider/registry.go:61-131).
DEFAULT_WEIGHTS = np.ones(NUM_SCORE_COMPONENTS, np.float32)
DEFAULT_WEIGHTS[SC_PREFER_AVOID] = 10000.0
# MostAllocated / RequestedToCapacityRatio are not in the default profile.
DEFAULT_WEIGHTS[SC_MOST_ALLOC] = 0.0
DEFAULT_WEIGHTS[SC_REQ_TO_CAP] = 0.0
# cost/energy are policy opt-ins, never part of the reference default
DEFAULT_WEIGHTS[SC_COST] = 0.0
DEFAULT_WEIGHTS[SC_ENERGY] = 0.0


def _profile(**overrides) -> np.ndarray:
    w = DEFAULT_WEIGHTS.copy()
    for name, val in overrides.items():
        w[globals()[name]] = val
    return w


# Named score policies: pluggable score matrices selected by a RUNTIME
# weight vector (a kernel input, not a compile-time constant — swapping
# policies never recompiles). `Scheduler.set_score_policy` accepts a name
# here or a raw [NUM_SCORE_COMPONENTS] vector; the ROADMAP-5 policy gym
# tunes these same vectors online.
WEIGHT_PROFILES = {
    "default": DEFAULT_WEIGHTS.copy(),
    # bin-pack: fill the fullest feasible node first
    "pack": _profile(SC_LEAST_ALLOC=0.0, SC_MOST_ALLOC=1.0),
    # spread: the default profile's LeastAllocated already spreads; name it
    "spread": DEFAULT_WEIGHTS.copy(),
    # heterogeneity/cost: cheapest feasible node dominates, pack breaks ties
    "cheapest": _profile(
        SC_LEAST_ALLOC=0.0, SC_MOST_ALLOC=1.0, SC_COST=100.0
    ),
    # energy-aware: minimize the fleet energy proxy, pack breaks ties
    "energy": _profile(
        SC_LEAST_ALLOC=0.0, SC_MOST_ALLOC=1.0, SC_ENERGY=100.0
    ),
}


def weights_for_policy(policy) -> np.ndarray:
    """Resolve a policy name or raw vector into a weight vector. Unknown
    names raise (a typo'd policy must fail loudly at config time, not
    schedule with silently-default weights). Raw vectors are validated
    for shape, dtype-coercibility AND finiteness here — a NaN/inf weight
    would otherwise poison every score in the next kernel launch and
    surface as an inscrutable guard trip instead of a ValueError at the
    call that introduced it (the seam the policy-gym promotion gate
    rejects poisoned candidates through)."""
    if isinstance(policy, str):
        try:
            return WEIGHT_PROFILES[policy].copy()
        except KeyError:
            raise ValueError(
                f"unknown score policy {policy!r}; known: "
                f"{sorted(WEIGHT_PROFILES)}"
            ) from None
    try:
        w = np.asarray(policy, np.float32)
    except (TypeError, ValueError) as e:
        raise ValueError(
            f"score weight vector is not float32-coercible: {e}"
        ) from None
    if w.shape != (NUM_SCORE_COMPONENTS,):
        raise ValueError(
            f"score weight vector must have shape ({NUM_SCORE_COMPONENTS},), "
            f"got {w.shape}"
        )
    if not np.isfinite(w).all():
        bad = np.flatnonzero(~np.isfinite(w)).tolist()
        raise ValueError(
            f"score weight vector has non-finite components at {bad}"
        )
    return w.copy()


# Names a promoted/tuned vector may never shadow: the built-in profiles
# are documented identities ("cheapest" must keep meaning cheapest).
_BUILTIN_PROFILES = frozenset(WEIGHT_PROFILES)


def register_weight_profile(
    name: str, vec, overwrite: bool = False
) -> np.ndarray:
    """Register a named weight profile at runtime so promoted vectors get
    STABLE names in metrics labels, SIGUSR2 dumps and the persisted
    score-policy object (the policy gym calls this before
    ``set_score_policy``; an HA standby calls it while adopting the
    persisted policy). The vector passes the full ``weights_for_policy``
    raw-vector validation; built-in profile names are reserved, and
    re-registering a tuned name requires ``overwrite=True`` unless the
    vector is unchanged (idempotent re-adoption)."""
    if not name or not isinstance(name, str):
        raise ValueError("profile name must be a non-empty string")
    w = weights_for_policy(np.asarray(vec))
    if name in _BUILTIN_PROFILES:
        raise ValueError(
            f"profile name {name!r} is reserved (built-in profile)"
        )
    existing = WEIGHT_PROFILES.get(name)
    if existing is not None and not overwrite and not np.array_equal(
        existing, w
    ):
        raise ValueError(
            f"profile {name!r} already registered with different weights "
            "(pass overwrite=True to replace)"
        )
    WEIGHT_PROFILES[name] = w.copy()
    return w

IMG_MIN_THRESHOLD = 23.0 * 1024 * 1024  # imagelocality minThreshold
IMG_MAX_THRESHOLD = 1000.0 * 1024 * 1024


class BatchResult(NamedTuple):
    chosen: Any  # [P] int32 node row, -1 = unschedulable (or invalid pod)
    score: Any  # [P] float32 winning weighted score
    feasible_count: Any  # [P] int32 number of feasible nodes at decision time
    resolvable: Any  # [P, N] bool — infeasible but preemption might help
    # (passes all UnschedulableAndUnresolvable-class filters)


# ---------------------------------------------------------------------------
# expression / selector evaluation (stage A primitives)
# ---------------------------------------------------------------------------


def _label_cols(snap: DeviceSnapshot, key: jnp.ndarray):
    """Gather per-node label value-id and numeric value for a key id.

    key < 0 (absent/unknown) yields value -1 / INT_MIN (label absent)."""
    k = jnp.clip(key, 0, snap.label_vals.shape[1] - 1)
    vals = snap.label_vals[:, k]
    nums = snap.label_numvals[:, k]
    absent = key < 0
    return (
        jnp.where(absent, -1, vals),
        jnp.where(absent, INT_MIN, nums),
    )


def _expr_mask(snap: DeviceSnapshot, key, op, vals, num) -> jnp.ndarray:
    """[N] bool: nodes matching a single NodeSelectorRequirement.

    Empty slot (op == -1) matches everything (AND identity)."""
    labval, labnum = _label_cols(snap, key)  # [N]
    has = labval >= 0
    in_set = jnp.any(labval[:, None] == vals[None, :], axis=1) & has
    has_num = labnum != INT_MIN
    result = jnp.select(
        [
            op == ENC_OP_IN,
            op == ENC_OP_NOT_IN,
            op == ENC_OP_EXISTS,
            op == ENC_OP_NOT_EXISTS,
            op == ENC_OP_GT,
            op == ENC_OP_LT,
        ],
        [
            in_set,
            ~in_set,  # NotIn: absent key also passes (selectors.py semantics)
            has,
            ~has,
            has_num & (labnum > num),
            has_num & (labnum < num),
        ],
        default=jnp.ones_like(has),
    )
    return jnp.where(op < 0, jnp.ones_like(result), result)


def _term_mask(snap, keys, ops, vals, nums, name_row) -> jnp.ndarray:
    """[N] bool for one NodeSelectorTerm: AND of expressions + matchFields."""
    ex = jax.vmap(lambda k, o, v, n: _expr_mask(snap, k, o, v, n))(
        keys, ops, vals, nums
    )  # [E, N]
    m = jnp.all(ex, axis=0)
    n = snap.valid.shape[0]
    rows = jnp.arange(n)
    name_ok = jnp.where(name_row == -1, True, rows == name_row)
    return m & name_ok


def _node_affinity_required(snap, bp) -> jnp.ndarray:
    """[N] bool: nodeSelector AND (OR of required nodeSelectorTerms).

    Mirrors PodMatchesNodeSelectorAndAffinityTerms
    (nodeaffinity/node_affinity.go:54 + v1helper)."""
    ns_ok = _term_mask(
        snap, bp.ns_key, bp.ns_op, bp.ns_vals, bp.ns_num, jnp.int32(-1)
    )
    terms = jax.vmap(
        lambda k, o, v, n, nr: _term_mask(snap, k, o, v, n, nr)
    )(bp.aff_key, bp.aff_op, bp.aff_vals, bp.aff_num, bp.aff_match_name_row)  # [T, N]
    terms = terms & bp.aff_term_valid[:, None]
    any_term = jnp.any(terms, axis=0)
    aff_ok = jnp.where(bp.aff_has, any_term, True)
    return ns_ok & aff_ok


def _node_affinity_score(snap, bp) -> jnp.ndarray:
    """[N] float: Σ weights of matched preferred terms (pre-normalization)."""
    terms = jax.vmap(
        lambda k, o, v, n: _term_mask(snap, k, o, v, n, jnp.int32(-1))
    )(bp.pref_key, bp.pref_op, bp.pref_vals, bp.pref_num)  # [PT, N]
    w = jnp.where(bp.pref_term_valid, bp.pref_weight, 0.0)
    return jnp.sum(terms.astype(jnp.float32) * w[:, None], axis=0)


def _taints(snap, bp) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """([N] bool tolerated-for-schedule, [N] float intolerable-prefer count).

    Filter: untolerated NoSchedule/NoExecute ⇒ infeasible
    (tainttoleration/taint_toleration.go:55-77, UnschedulableAndUnresolvable).
    Score: count of intolerable PreferNoSchedule taints (129-167)."""
    tk, tv, te = snap.taint_key, snap.taint_val, snap.taint_effect  # [N, TA]
    # toleration j tolerates taint slot (n, a)?
    def tol_matches(jk, jop, jv, je):
        key_ok = (jk == -1) | (jk == tk)
        val_ok = (jop == TOL_OP_EXISTS) | (jv == tv)
        eff_ok = (je == -1) | (je == te)
        return (jop >= 0) & key_ok & val_ok & eff_ok  # [N, TA]

    tol = jax.vmap(tol_matches)(bp.tol_key, bp.tol_op, bp.tol_val, bp.tol_effect)
    tolerated = jnp.any(tol, axis=0)  # [N, TA]
    active = tk >= 0
    hard = active & ((te == EFFECT_NO_SCHEDULE) | (te == EFFECT_NO_EXECUTE))
    ok = jnp.all(~hard | tolerated, axis=1)
    prefer = active & (te == EFFECT_PREFER_NO_SCHEDULE)
    intolerable = jnp.sum((prefer & ~tolerated).astype(jnp.float32), axis=1)
    return ok, intolerable


def _image_locality(snap, bp) -> jnp.ndarray:
    """[N] float 0..100 (imagelocality/image_locality.go:47)."""
    n_valid = jnp.maximum(jnp.sum(snap.valid.astype(jnp.float32)), 1.0)
    have = (snap.image_bytes > 0).astype(jnp.float32)  # [N, I]
    spread = jnp.sum(have, axis=0) / n_valid  # [I] fraction of nodes w/ image
    iid = jnp.clip(bp.image_ids, 0, snap.image_bytes.shape[1] - 1)  # [IM]
    use = (bp.image_ids >= 0).astype(jnp.float32)
    sizes = snap.image_bytes[:, iid] * use[None, :]  # [N, IM]
    scaled = sizes * spread[iid][None, :]
    total = jnp.sum(scaled, axis=1)  # [N]
    score = (
        (total - IMG_MIN_THRESHOLD)
        / (IMG_MAX_THRESHOLD - IMG_MIN_THRESHOLD)
        * 100.0
    )
    return jnp.clip(score, 0.0, 100.0)


def _prefer_avoid(snap, bp) -> jnp.ndarray:
    """[N] float: 0 if node's avoid-annotation lists the pod's controller,
    else 100 (nodepreferavoidpods/node_prefer_avoid_pods.go:39)."""
    a = jnp.clip(bp.ctrl_id, 0, snap.avoid.shape[1] - 1)
    avoided = snap.avoid[:, a] & (bp.ctrl_id >= 0)
    return jnp.where(avoided, 0.0, 100.0)


# ---------------------------------------------------------------------------
# stage B primitives (carry-dependent)
# ---------------------------------------------------------------------------


def _domain_ops(snap, key, weights, eligible, v_cap: int):
    """Per-topology-domain reduction for one topology key.

    Returns (node_domain_sum [N], min_over_eligible_domains scalar,
    total scalar, has_key [N]). `weights` [N] are summed per domain of
    label `key` over nodes where `eligible`; nodes lacking the key are
    excluded. This is the segment-sum form of the reference's
    TpPairToMatchNum maps (podtopologyspread/filtering.go:43-121)."""
    dom, _ = _label_cols(snap, key)  # [N] value-id or -1
    has_key = dom >= 0
    ok = has_key & eligible
    seg = jnp.where(ok, dom, v_cap)  # OOB -> dropped
    sums = jax.ops.segment_sum(
        jnp.where(ok, weights, 0.0), seg, num_segments=v_cap
    )  # [V]
    node_sum = jnp.where(has_key, sums[jnp.clip(dom, 0, v_cap - 1)], 0.0)
    present = (
        jax.ops.segment_max(ok.astype(jnp.int32), seg, num_segments=v_cap) > 0
    )
    min_dom = jnp.min(jnp.where(present, sums, jnp.inf))
    return node_sum, min_dom, jnp.sum(sums), has_key


def _gather_counts(counts, extra, sid):
    """[N] pod-match counts for predicate sid (<0 → zeros)."""
    s = jnp.clip(sid, 0, counts.shape[1] - 1)
    c = counts[:, s] + extra[:, s]
    return jnp.where(sid >= 0, c.astype(jnp.float32), 0.0)


def _pod_static(snap: DeviceSnapshot, bp) -> Tuple:
    """Stage A for one pod: static mask/score pieces. Returns
    (static_ok, ns_aff_mask, aff_score, prefer_cnt, img, avoid). Shared by
    the schedule kernel and the preemption what-if kernel (static_ok is
    exactly the UnschedulableAndUnresolvable boundary: nodes failing it
    cannot be helped by evictions, generic_scheduler.go:1033)."""
    n = snap.valid.shape[0]
    rows = jnp.arange(n)
    ns_aff = _node_affinity_required(snap, bp)
    taint_ok, prefer_cnt = _taints(snap, bp)
    unsched_ok = ~snap.unschedulable | bp.tolerates_unschedulable
    name_ok = jnp.where(
        bp.node_name_row == -1,
        True,
        jnp.where(bp.node_name_row < 0, False, rows == bp.node_name_row),
    )
    static_ok = snap.valid & ns_aff & taint_ok & unsched_ok & name_ok
    # Scores computed regardless of feasibility; normalization masks later.
    aff_score = _node_affinity_score(snap, bp)
    img = _image_locality(snap, bp)
    avoid = _prefer_avoid(snap, bp)
    return static_ok, ns_aff, aff_score, prefer_cnt, img, avoid


@functools.lru_cache(maxsize=32)
def make_schedule_batch_raw(v_cap: int, hard_pod_affinity_weight: float = 1.0):
    """Build the (unjitted) batch kernel for a given domain-segment capacity.

    Cached per (v_cap, weight); jitted by make_schedule_batch (single device)
    or parallel.sharded.make_sharded_schedule_batch (mesh)."""

    pod_static = _pod_static

    def step(snap: DeviceSnapshot, carry, xs, weights, rng):
        (req_x, nz_x, sel_x, et_x, port_x) = carry
        (bp, static_ok, ns_aff, aff_score, prefer_cnt, img, avoid, key) = xs
        n = snap.valid.shape[0]

        # --- NodeResourcesFit (noderesources/fit.go:181-250) ---------------
        used = snap.requested + req_x
        free = snap.allocatable - used
        fits = jnp.all((bp.req[None, :] == 0) | (bp.req[None, :] <= free), axis=1)

        # --- NodePorts (nodeports/node_ports.go) ---------------------------
        ports_used = snap.port_counts + port_x
        port_conflict = jnp.any(bp.port_mask[None, :] & (ports_used > 0), axis=1)

        # --- PodTopologySpread (podtopologyspread/filtering.go) ------------
        def spread_one(skey, sid, skew, hard, selfm):
            counts = _gather_counts(snap.sel_counts, sel_x, sid)
            node_sum, min_dom, _, has_key = _domain_ops(
                snap, skey, counts, ns_aff & snap.valid, v_cap
            )
            self_add = jnp.where(selfm, 1.0, 0.0)
            skewed = node_sum + self_add - jnp.where(
                jnp.isfinite(min_dom), min_dom, 0.0
            ) > skew.astype(jnp.float32)
            active = skey >= 0
            hard_bad = active & hard & (skewed | ~has_key)
            soft_pen = jnp.where(active & ~hard, node_sum, 0.0)
            return hard_bad, soft_pen

        hard_bad, soft_pen = jax.vmap(spread_one)(
            bp.spread_key, bp.spread_sid, bp.spread_skew, bp.spread_hard, bp.spread_self
        )  # [C, N]
        spread_ok = ~jnp.any(hard_bad, axis=0)
        spread_penalty = jnp.sum(soft_pen, axis=0)

        # --- InterPodAffinity: incoming pod's required terms ----------------
        def aff_term(sid, tkey, selfm):
            counts = _gather_counts(snap.sel_counts, sel_x, sid)
            node_sum, _, total, has_key = _domain_ops(
                snap, tkey, counts, snap.valid, v_cap
            )
            ok = (node_sum > 0) | ((total == 0) & selfm & has_key)
            return jnp.where(sid >= 0, ok, True)

        aff_ok = jnp.all(
            jax.vmap(aff_term)(bp.paff_sid, bp.paff_key, bp.paff_self), axis=0
        )

        def anti_term(sid, tkey):
            counts = _gather_counts(snap.sel_counts, sel_x, sid)
            node_sum, _, _, has_key = _domain_ops(snap, tkey, counts, snap.valid, v_cap)
            bad = has_key & (node_sum > 0)
            return jnp.where(sid >= 0, bad, False)

        anti_bad = jnp.any(
            jax.vmap(anti_term)(bp.panti_sid, bp.panti_key), axis=0
        )

        # --- existing pods' terms (eterms) ---------------------------------
        def eterm_one(t):
            w = snap.eterm_w[:, t] + et_x[:, t]
            node_sum, _, _, has_key = _domain_ops(
                snap, snap.eterm_topo_key[t], w, snap.valid, v_cap
            )
            matches = bp.match_eterm[t]
            kind = snap.eterm_kind[t]
            anti_req_bad = matches & (kind == ETERM_ANTI_REQ) & has_key & (node_sum > 0)
            sgn = jnp.select(
                [kind == ETERM_ANTI_PREF, kind == ETERM_AFF_PREF, kind == ETERM_AFF_REQ],
                [-1.0, 1.0, hard_pod_affinity_weight],
                default=0.0,
            )
            score = jnp.where(matches, sgn * node_sum, 0.0)
            return anti_req_bad, score

        t_cap = snap.eterm_w.shape[1]
        e_bad, e_score = jax.vmap(eterm_one)(jnp.arange(t_cap))  # [T, N]
        eterm_bad = jnp.any(e_bad, axis=0)
        interpod_score = jnp.sum(e_score, axis=0)

        # incoming pod's preferred terms
        def ppref_one(sid, tkey, w):
            counts = _gather_counts(snap.sel_counts, sel_x, sid)
            node_sum, _, _, _ = _domain_ops(snap, tkey, counts, snap.valid, v_cap)
            return jnp.where(sid >= 0, w * node_sum, 0.0)

        interpod_score = interpod_score + jnp.sum(
            jax.vmap(ppref_one)(bp.ppref_sid, bp.ppref_key, bp.ppref_w), axis=0
        )

        # --- combine mask ---------------------------------------------------
        feasible = (
            static_ok
            & fits
            & ~port_conflict
            & spread_ok
            & aff_ok
            & ~anti_bad
            & ~eterm_bad
        )
        # preemption-candidate nodes: fail only resolvable filters
        resolvable = static_ok & ~feasible

        # --- scores (normalized 0..100 over feasible, framework.go:503-580) -
        def norm_max(x):
            mx = jnp.max(jnp.where(feasible, x, -jnp.inf))
            safe = jnp.where(jnp.isfinite(mx) & (mx > 0), mx, 1.0)
            return jnp.clip(x / safe * 100.0, 0.0, 100.0)

        def norm_invert(x):  # lower raw -> higher score
            mx = jnp.max(jnp.where(feasible, x, -jnp.inf))
            safe = jnp.where(jnp.isfinite(mx) & (mx > 0), mx, 1.0)
            ok = jnp.isfinite(mx) & (mx > 0)
            return jnp.where(ok, (safe - x) / safe * 100.0, 100.0)

        # resource scores include the incoming pod (least_allocated.go:77-99)
        nz_used = snap.nonzero_req + nz_x + bp.nonzero_req[None, :]
        alloc = jnp.maximum(snap.allocatable.astype(jnp.float32), 1.0)
        frac = jnp.clip(nz_used.astype(jnp.float32) / alloc, 0.0, 1.0)
        cpu_f, mem_f = frac[:, RES_CPU], frac[:, RES_MEM]
        least = ((1.0 - cpu_f) * 100.0 + (1.0 - mem_f) * 100.0) / 2.0
        most = (cpu_f * 100.0 + mem_f * 100.0) / 2.0
        balanced = (1.0 - jnp.abs(cpu_f - mem_f)) * 100.0
        # requested-to-capacity-ratio, default shape {0:0, 100:10} scaled to
        # 0..100 (requested_to_capacity_ratio.go:33 with default buckets)
        util = (cpu_f + mem_f) / 2.0 * 100.0
        rtc = util / 100.0 * 10.0 * 10.0

        # interpod/prefer-style normalization: shift to >= 0 then max-scale
        # (interpodaffinity/scoring.go:287-310 normalizes by max |score|)
        ip = interpod_score
        ip_max = jnp.max(jnp.where(feasible, jnp.abs(ip), 0.0))
        ip_norm = jnp.where(ip_max > 0, ip / ip_max * 100.0, 0.0)

        # DefaultPodTopologySpread: same-service pods per node via the
        # service-derived sel_counts columns; MAX over matching services
        # matches the host's any()-dedup when services don't overlap (the
        # common case — overlapping services score each pod once there too)
        svc_cnt = jnp.max(
            jnp.where(
                bp.match_svc[None, :],
                (snap.sel_counts + sel_x).astype(jnp.float32),
                0.0,
            ),
            axis=1,
        )  # [N]

        comps = jnp.stack(
            [
                least,
                most,
                balanced,
                rtc,
                norm_max(aff_score),
                norm_invert(prefer_cnt),
                img,
                avoid,
                norm_invert(spread_penalty),
                ip_norm,
                norm_invert(svc_cnt),
                # heterogeneity/cost columns: cheaper / lower-energy nodes
                # score higher within the feasible set
                norm_invert(snap.cost_milli.astype(jnp.float32)),
                norm_invert(snap.energy_milli.astype(jnp.float32)),
            ]
        )  # [K, N]
        total_score = jnp.sum(comps * weights[:, None], axis=0)

        # --- select: argmax with uniform random tie-break -------------------
        noise = jax.random.uniform(key, (n,))
        keyed = jnp.where(feasible, total_score, -jnp.inf)
        best = jnp.max(keyed)
        is_best = feasible & (keyed == best)
        pick_key = jnp.where(is_best, noise, -1.0)
        chosen = jnp.argmax(pick_key).astype(jnp.int32)
        feas_count = jnp.sum(feasible.astype(jnp.int32))
        ok = (feas_count > 0) & bp.valid
        chosen = jnp.where(ok, chosen, -1)

        # --- commit to carry -------------------------------------------------
        idx = jnp.maximum(chosen, 0)
        gate = ok.astype(jnp.int32)
        gate_f = ok.astype(jnp.float32)
        req_x = req_x.at[idx].add(bp.req * gate)
        nz_x = nz_x.at[idx].add(bp.nonzero_req * gate)
        sel_x = sel_x.at[idx].add(bp.match_sel.astype(jnp.int32) * gate)
        et_x = et_x.at[idx].add(bp.eterm_add * gate_f)
        port_x = port_x.at[idx].add(bp.port_mask.astype(jnp.int32) * gate)

        new_carry = (req_x, nz_x, sel_x, et_x, port_x)
        out = (chosen, jnp.where(ok, best, -jnp.inf), feas_count, resolvable)
        return new_carry, out

    def schedule_batch(
        snap: DeviceSnapshot, batch: PodBatch, weights: jnp.ndarray, rng: jnp.ndarray
    ) -> BatchResult:
        n = snap.valid.shape[0]
        p = batch.valid.shape[0]
        statics = jax.vmap(lambda bp: pod_static(snap, bp))(batch)
        keys = jax.random.split(rng, p)
        carry0 = (
            jnp.zeros_like(snap.requested),
            jnp.zeros_like(snap.nonzero_req),
            jnp.zeros_like(snap.sel_counts),
            jnp.zeros_like(snap.eterm_w),
            jnp.zeros_like(snap.port_counts),
        )
        xs = (batch,) + statics + (keys,)
        _, (chosen, score, feas, resolvable) = jax.lax.scan(
            lambda c, x: step(snap, c, x, weights, None), carry0, xs
        )
        return BatchResult(
            chosen=chosen, score=score, feasible_count=feas, resolvable=resolvable
        )

    return schedule_batch


@functools.lru_cache(maxsize=32)
def make_schedule_batch(v_cap: int, hard_pod_affinity_weight: float = 1.0):
    """Single-device jitted batch kernel (cached per capacity)."""
    return jax.jit(make_schedule_batch_raw(v_cap, hard_pod_affinity_weight))


def _preempt_whatif(
    snap: DeviceSnapshot, batch: PodBatch, priority: jnp.ndarray
) -> jnp.ndarray:
    """Batched masked preemption what-if (SURVEY §7.6): for every (pod, node)
    pair, would the pod fit if all pods of lower priority were evicted?

    Replaces the serial per-node host scan of selectVictimsOnNode
    (generic_scheduler.go:850-877 parallel what-if) with one device pass.
    The mask is OPTIMISTIC: it accounts resources (via the priority-banded
    requested matrix) and the static UnschedulableAndUnresolvable filters,
    but not affinity/spread constraints contributed by would-be victims —
    the host reprieve loop does the exact plugin re-check on the (few)
    surviving candidates, so false positives cost time, never correctness.
    """
    statics = jax.vmap(lambda bp: _pod_static(snap, bp))(batch)
    static_ok = statics[0]  # [P, N]

    # removable[p, n, r] = Σ_b [band_prio[b] < prio_p] · prio_req[n, b, r]
    # (priority passed separately: template batches carry per-pod priority
    # outside the template tensors)
    removable_band = snap.band_prio[None, :] < priority[:, None]  # [P, B]
    removable = jnp.einsum(
        "pb,nbr->pnr",
        removable_band.astype(jnp.int32),
        snap.prio_req,
    )
    free = (
        snap.allocatable[None, :, :]
        - snap.requested[None, :, :]
        + removable
    )  # [P, N, R]
    req = batch.req[:, None, :]  # [P, 1, R]
    fits = jnp.all((req == 0) | (req <= free), axis=-1)  # [P, N]
    # a node already holding >= 1 lower-priority pod is the only kind where
    # eviction helps; removable pods count shows as the PODS column
    has_victims = jnp.any(removable > 0, axis=-1)
    return static_ok & fits & has_victims & batch.valid[:, None]


preempt_whatif = jax.jit(_preempt_whatif)


# -- kernel-output guards (scheduler data-plane self-defense) ----------------

GUARD_ROW_RANGE = "row_out_of_range"
GUARD_NONFINITE = "nonfinite_score"
GUARD_COMMIT_WAVE = "commit_wave_mismatch"
# split-phase readback: the trailing bulk transfer died after the fast
# index payload already drove assumes — the batch's device commits are
# unverifiable and must quarantine/unwind
GUARD_TRAILING_LOSS = "trailing_readback_loss"


class KernelGuardTrip(RuntimeError):
    """A batch's read-back results failed validation: the whole batch must
    be quarantined to the host fallback path and the device snapshot
    rebuilt (its commits for this batch are suspect)."""

    def __init__(self, reason: str, detail: str = ""):
        super().__init__(f"kernel guard trip: {reason} {detail}".rstrip())
        self.reason = reason


def validate_batch_outputs(
    chosen, placed, score, n_rows: int, commit_wave=None
):
    """Cheap structural validation of a read-back batch result BEFORE any
    placement is acted on: every placed pod's chosen row must name a live
    node row (negative or past-capacity indices would mis-index
    row_names — numpy's negative wrap silently picks the WRONG node), and
    its score must be finite (a NaN/Inf in the score matrix poisons the
    argmax for the whole column). A wave result also says in which
    iteration each pod committed (`commit_wave`, the order its binds
    leave in): one exactly where `placed`. Returns a trip reason or None."""
    placed = np.asarray(placed, dtype=bool)
    if commit_wave is not None and not np.array_equal(
        np.asarray(commit_wave) >= 0, placed
    ):
        return GUARD_COMMIT_WAVE
    if not placed.any():
        return None
    rows = np.asarray(chosen)[placed]
    if rows.size and (int(rows.min()) < 0 or int(rows.max()) >= n_rows):
        return GUARD_ROW_RANGE
    if score is not None:
        s = np.asarray(score)[placed]
        if not np.isfinite(s).all():
            return GUARD_NONFINITE
    return None


def validate_trailing_score(score, placed):
    """Split-phase trailing validation: the fast index payload was
    validated (and acted on) with score=None; when the bulk score vector
    lands it must agree that every placed pod scored finite — a NaN/Inf
    here means the argmax the fast payload reported was computed over a
    poisoned column. Returns a trip reason or None."""
    placed = np.asarray(placed, dtype=bool)
    if score is None or not placed.any():
        return None
    s = np.asarray(score)[placed]
    if not np.isfinite(s).all():
        return GUARD_NONFINITE
    return None
