"""Columnar snapshot encoding: the host↔device contract.

This is the TPU-native replacement for the reference's per-cycle Snapshot of
NodeInfo structs (pkg/scheduler/internal/cache/snapshot.go:31,
nodeinfo/node_info.go:48). Instead of a list of structs walked by 16
goroutines, cluster state is maintained as a set of fixed-capacity device
tensors, updated incrementally (the analogue of the cache's generation-based
UpdateSnapshot delta protocol, cache.go:203), so a scheduling batch launches
with zero host→device snapshot traffic beyond the pod batch itself.

Key design moves (SURVEY.md §7 stage 2):

* **Dictionary encoding.** Label keys, label values, resource names, host
  ports, images, and controller-refs are interned into growable vocabularies;
  node labels become a dense [N, K] int32 matrix of value-ids (-1 = absent),
  so selector matching is integer compares/gathers on the VPU.

* **Interned pod-predicates.** Every distinct (namespaces, label-selector)
  pair referenced by a PodTopologySpread constraint or InterPodAffinity term
  is interned to a selector id `sid`; the device holds `sel_counts[N, S]` =
  number of pods on node n matching predicate s, maintained incrementally on
  pod add/remove. The reference's O(all-nodes × pods-per-node) PreFilter scan
  (interpodaffinity/filtering.go:212,256) becomes a column gather + one
  segment-sum per term over topology domains.

* **Existing-pod terms ("eterms").** Anti-affinity/affinity terms *of pods
  already placed* are interned as (namespaces, selector, topology_key, kind);
  `eterm_w[N, T]` holds the per-node count (required terms) or weight-sum
  (preferred terms) of pods carrying each term. An incoming pod is matched
  against the small set of eterm predicates on the host (O(T) string work),
  yielding a boolean vector the kernel combines with domain segment-sums —
  this is the "incrementally-maintained device-side count structure" that
  replaces the existing-pods half of InterPodAffinity's PreFilter.

* **Generational double-buffering (pin → donate → retire).** The device
  snapshot is a sequence of immutable *generations*. Readers (the
  anti-entropy audit's row gather, the autoscaler's what-if overlay, the
  chaos fault injector) take a `pin_generation()` lease on the current
  generation; writers (the wave launch's donating kernel, flush's row
  scatters) advance it through a `donation_lease()`: the lease seals the
  live generation, and — when a reader holds a pin, or the generation
  shares buffers with a pinned ancestor — hands the donating program a
  fresh COPY instead, so the pinned buffers stay intact until their pin
  count drains and the generation retires. This replaces the old
  process-wide `device_lock`: a gather no longer serializes against a
  wave launch (the round-8 donation/audit deadlock shape is now legal
  concurrency), multiple waves pipeline in flight, and — because a
  donating program can never alias buffers a reader observes — the
  persistent JAX compilation cache is safe to enable everywhere.

Units: cpu in millicores, memory/ephemeral-storage quantised to KiB
(requests ceil, allocatable floor — conservative), pods/extended raw counts;
all int32. Nodes with >2 TiB of a single resource clamp to int32 max.
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from dataclasses import dataclass, replace
from typing import Any, Dict, FrozenSet, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger("kubernetes_tpu.ops.encoding")

from ..api import objects as v1
from ..api.resources import CPU, EPHEMERAL_STORAGE, MEMORY, PODS, ResourceList
from ..testing.lockgraph import named_lock, track_attrs
from ..utils.metrics import metrics
from ..api.selectors import (
    OP_DOES_NOT_EXIST,
    OP_EXISTS,
    OP_GT,
    OP_IN,
    OP_LT,
    OP_NOT_IN,
    LabelSelector,
)

# Node-selector operator codes used by the kernel.
ENC_OP_IN = 0
ENC_OP_NOT_IN = 1
ENC_OP_EXISTS = 2
ENC_OP_NOT_EXISTS = 3
ENC_OP_GT = 4
ENC_OP_LT = 5
_OP_CODES = {
    OP_IN: ENC_OP_IN,
    OP_NOT_IN: ENC_OP_NOT_IN,
    OP_EXISTS: ENC_OP_EXISTS,
    OP_DOES_NOT_EXIST: ENC_OP_NOT_EXISTS,
    OP_GT: ENC_OP_GT,
    OP_LT: ENC_OP_LT,
}

# Taint effects.
EFFECT_NO_SCHEDULE = 0
EFFECT_PREFER_NO_SCHEDULE = 1
EFFECT_NO_EXECUTE = 2
_EFFECT_CODES = {
    v1.TAINT_NO_SCHEDULE: EFFECT_NO_SCHEDULE,
    v1.TAINT_PREFER_NO_SCHEDULE: EFFECT_PREFER_NO_SCHEDULE,
    v1.TAINT_NO_EXECUTE: EFFECT_NO_EXECUTE,
}

# eterm kinds (terms carried by existing pods, matched against incoming pods)
ETERM_ANTI_REQ = 0  # existing pod's required anti-affinity -> filter
ETERM_ANTI_PREF = 1  # preferred anti-affinity -> negative score
ETERM_AFF_PREF = 2  # preferred affinity -> positive score
ETERM_AFF_REQ = 3  # required affinity -> score × hardPodAffinityWeight

# Base resource columns (fixed order); extended resources follow.
RES_CPU = 0
RES_MEM = 1
RES_STORAGE = 2
RES_PODS = 3
N_BASE_RES = 4

# Heterogeneity/cost column family: per-node economics fed from node
# labels (the autoscaler's NodeGroup templates stamp them; operators may
# label real fleets the same way). Costs/energy are encoded in MILLI
# units (int32) so a $2.4/h node is 2400 — float labels parse once at
# encode time, the kernel sees integers. Unlabeled nodes read 0
# (= free/no-data); score components normalize within the feasible set,
# so an all-unlabeled cluster scores flat and the policy is inert.
LABEL_COST_PER_HOUR = "kubernetes-tpu.io/cost-per-hour"
LABEL_ACCELERATOR_CLASS = "kubernetes-tpu.io/accelerator-class"
LABEL_ENERGY_WATTS = "kubernetes-tpu.io/energy-watts"


def _milli_of_label(labels: Dict[str, str], key: str) -> int:
    """Parse a float-valued node label into int32 milli-units (0 when
    absent or malformed — a bad label must not fail node encode)."""
    raw = labels.get(key)
    if not raw:
        return 0
    try:
        return int(min(max(float(raw), 0.0) * 1000.0, float(I32_MAX)))
    except (TypeError, ValueError):
        return 0

_KIB = 1024
I32_MAX = np.int32(2**31 - 1)

# -- snapshot generation lifecycle metrics (pin → donate → retire) ----------
GAUGE_GEN_CURRENT = "snapshot_generation_current"
GAUGE_GEN_PINNED = "snapshot_generation_pinned_readers"
GAUGE_GEN_RETIRING = "snapshot_generation_retiring"
COUNTER_GEN_RETIRED = "snapshot_generation_retired_total"
COUNTER_GEN_COPY_ON_PIN = "snapshot_generation_copy_on_pin_total"
COUNTER_GEN_RETIRE_STALLS = "snapshot_generation_retire_stalls_total"
HIST_GEN_RETIRE_LATENCY = "snapshot_generation_retire_latency_seconds"
# the histogram above serves /metrics quantiles; this gauge mirrors the
# most recent retirement's latency into the SIGUSR2 dataplane dump
# (which renders gauges/counters, not histograms)
GAUGE_GEN_LAST_RETIRE_LATENCY = "snapshot_generation_last_retire_latency_seconds"

# a superseded-but-still-pinned generation older than this is a stuck pin
# (a reader leaked its lease): reported once per generation, observable in
# /metrics and the SIGUSR2 dataplane dump instead of silently holding HBM
RETIRE_STALL_AFTER_S = 30.0


class SnapshotGeneration:
    """One immutable HBM buffer set of the double-buffered snapshot.

    ``pins`` counts readers holding a :class:`GenerationLease`; ``sealed``
    marks a donor mid-advance (new pins and new donors wait the few µs
    until the successor installs); ``shared_parent`` points at a still-
    live predecessor whose buffers this generation reuses (the reshape-
    merge upload keeps unreshaped fields) — donation must treat the pair
    as one pin scope. ``superseded_at`` stamps retirement latency."""

    __slots__ = (
        "gen_id", "snap", "pins", "sealed", "shared_parent",
        "superseded_at", "stall_reported",
    )

    def __init__(self, gen_id: int, snap: DeviceSnapshot, shared_parent=None):
        self.gen_id = gen_id
        self.snap = snap
        self.pins = 0
        self.sealed = False
        self.shared_parent = shared_parent
        self.superseded_at: Optional[float] = None
        self.stall_reported = False


class GenerationLease:
    """Reader pin on the current snapshot generation.

    While held, the pinned generation's buffers are never donated: a wave
    launch (or flush scatter) arriving mid-lease advances through a fresh
    copy instead (`snapshot_generation_copy_on_pin_total`). ``snap`` is
    None when no device snapshot exists yet."""

    __slots__ = ("_enc", "_gen", "gen_id", "snap")

    def __init__(self, enc: "SnapshotEncoder"):
        self._enc = enc
        self._gen = None
        self.gen_id = -1
        self.snap: Optional[DeviceSnapshot] = None

    def __enter__(self) -> "GenerationLease":
        enc = self._enc
        with enc._gen_lock:
            # a donor sealed the live generation and is mid-install
            # (microseconds — dispatch is async); bounded waits so a donor
            # that died mid-advance can never park readers forever
            while enc._gen is not None and enc._gen.sealed:
                enc._gen_lock.wait(timeout=0.05)
            gen = enc._gen
            if gen is None:
                return self
            gen.pins += 1
            self._gen = gen
            self.gen_id = gen.gen_id
            self.snap = gen.snap
            enc._check_retire_stalls_locked()
            enc._publish_gen_gauges_locked()
        return self

    def __exit__(self, *exc) -> None:
        gen, self._gen = self._gen, None
        self.snap = None
        if gen is not None:
            self._enc._unpin(gen)

    # Non-lexical hold (split-phase readback): the fast index payload's
    # source generation must stay pinned until the TRAILING bulk readback
    # lands, which happens in a later scheduling-loop iteration — a
    # with-block can't span that. acquire()/release() are __enter__/
    # __exit__ for holders that outlive their frame; release() is
    # idempotent-safe in the sense that the lease must be released
    # exactly once (the scheduler's trailing entry owns it).
    def acquire(self) -> "GenerationLease":
        return self.__enter__()

    def release(self) -> None:
        self.__exit__(None, None, None)


class DonationLease:
    """Writer-side generation advance: seal → dispatch → install.

    ``__enter__`` seals the live generation and yields ``.snap`` — the
    sealed buffers when nothing pins them, a fresh copy when a reader
    does (the double-buffer move: generation N keeps serving its pinned
    readers while the donor consumes a private copy that becomes N+1).
    The caller runs its donating (or alias-free, ``donating=False``)
    program and assigns ``.result``; ``__exit__`` installs the result as
    the next live generation and retires the predecessor once its pins
    drain. On a failed dispatch an in-place donation leaves the buffers
    unknowable, so the generation is dropped and the next flush re-uploads
    from the host masters; a copied/alias-free attempt just unseals."""

    __slots__ = (
        "_enc", "_base", "snap", "copied", "result", "donating", "shared",
    )

    def __init__(self, enc: "SnapshotEncoder", donating: bool = True):
        self._enc = enc
        self._base = None
        self.snap: Optional[DeviceSnapshot] = None
        self.copied = False
        self.result: Optional[DeviceSnapshot] = None
        self.donating = donating
        # caller sets True when .result reuses some of the base's buffers
        # (the reshape-merge upload): the installed generation then keeps
        # a shared-buffer tie to its pinned predecessor
        self.shared = False

    def __enter__(self) -> "DonationLease":
        enc = self._enc
        with enc._gen_lock:
            while enc._gen is not None and enc._gen.sealed:
                enc._gen_lock.wait(timeout=0.05)
            gen = enc._gen
            if gen is None:
                raise RuntimeError(
                    "no live snapshot generation to advance (flush first)"
                )
            gen.sealed = True
            self._base = gen
            try:
                enc._check_retire_stalls_locked()
                pinned = gen.pins > 0 or (
                    gen.shared_parent is not None
                    and gen.shared_parent.pins > 0
                )
                if self.donating and pinned:
                    # readers pin generation N: hand the donor a fresh copy
                    # so the pinned buffers survive until the pins drain
                    self.snap = _copy_snapshot(gen.snap)
                    self.copied = True
                    metrics.inc(COUNTER_GEN_COPY_ON_PIN)
                else:
                    self.snap = gen.snap
            except BaseException:
                # a failed post-seal step (e.g. the copy dispatch dying on
                # device loss) raises out of __enter__, so __exit__ never
                # runs — unseal HERE or every later pin/lease/install
                # waits on the sealed generation forever. The copy is
                # non-donating, so the sealed buffers are still intact.
                gen.sealed = False
                self._base = None
                enc._gen_lock.notify_all()
                raise
        return self

    def __exit__(self, et, ev, tb) -> bool:
        enc = self._enc
        with enc._gen_lock:
            base = self._base
            if et is not None or self.result is None:
                if self.donating and not self.copied:
                    # the donating program may have consumed the sealed
                    # buffers: content unknowable, force a full re-upload
                    if enc._gen is base:
                        enc._gen = None
                    enc._full_upload = True
                    enc._content_invalid = True
                elif base is not None:
                    base.sealed = False
                enc._gen_lock.notify_all()
                enc._publish_gen_gauges_locked()
                return False
            enc._install_locked(
                self.result,
                base,
                consumed=self.donating and not self.copied,
                shared_with_base=self.shared,
            )
        return False


def zpad(a: np.ndarray, n: int) -> np.ndarray:
    """Zero-pad a 1-D array to length n (np.resize repeats — never use it here)."""
    if len(a) >= n:
        return a[:n]
    out = np.zeros(n, a.dtype)
    out[: len(a)] = a
    return out


def _to_col_units(name: str, value: int, ceil: bool) -> int:
    if name in (MEMORY, EPHEMERAL_STORAGE):
        value = (value + _KIB - 1) // _KIB if ceil else value // _KIB
    return int(min(value, int(I32_MAX)))


@dataclass(frozen=True)
class EncodingConfig:
    """Static bucket capacities. All array shapes derive from these; growing
    any capacity doubles it and forces a device re-upload + kernel recompile
    (rare: vocabularies saturate quickly in steady state)."""

    n_cap: int = 128  # node rows
    k_cap: int = 32  # label keys
    v_cap: int = 256  # label values (also topology-domain segment count)
    r_cap: int = 6  # resource columns (4 base + extended)
    pb_cap: int = 8  # priority bands (distinct pod priorities; preempt what-if)
    s_cap: int = 8  # interned pod-predicates (sel_counts columns)
    t_cap: int = 8  # interned eterms
    pv_cap: int = 8  # interned (proto, port) host-port slots
    im_cap: int = 32  # interned images
    av_cap: int = 8  # interned avoid-controller refs
    taints_max: int = 8  # taints per node
    # pod-side buckets
    ns_max: int = 8  # nodeSelector entries per pod
    tol_max: int = 8  # tolerations per pod
    aff_terms: int = 4  # required node-affinity terms (OR)
    aff_exprs: int = 6  # expressions per term (AND)
    aff_vals: int = 8  # values per expression
    pref_terms: int = 4  # preferred node-affinity terms
    spread_max: int = 4  # topology-spread constraints per pod
    pod_aff_max: int = 4  # incoming required affinity terms
    pod_anti_max: int = 4  # incoming required anti-affinity terms
    pod_pref_max: int = 4  # incoming preferred (anti-)affinity terms (signed w)
    images_max: int = 8  # images per pod

    @classmethod
    def for_cluster(cls, num_nodes: int, **overrides) -> "EncodingConfig":
        """Capacities pre-sized for a cluster of ~num_nodes so steady-state
        runs never grow (growth = device re-upload + kernel recompile; an
        observed 14.5s recompile mid-benchmark wrecks p99). v_cap dominates:
        hostname-like labels contribute one value per node."""

        def pow2(n: int, floor: int) -> int:
            p = floor
            while p < n:
                p *= 2
            return p

        # 25% slack for churn (nodes come and go; rows are not reused until
        # compaction), plus a flat allowance for non-hostname label values.
        n_cap = pow2(int(num_nodes * 1.25) + 1, 128)
        v_cap = pow2(int(num_nodes * 1.25) + 512, 256)
        base = dict(
            n_cap=n_cap,
            v_cap=v_cap,
            # pod-side vocab headroom: at real-cluster scale the first
            # burst's pods intern label keys / selector predicates /
            # affinity eterms / host ports past the tiny defaults, and
            # every growth is a mid-window field re-upload PLUS a
            # multi-second kernel recompile (shapes change). Start wide
            # enough that steady state never grows; the extra columns ride
            # the one pre-window upload (~a few MB at 5k nodes).
            k_cap=128,
            s_cap=64,
            t_cap=64,
            pv_cap=32,
            im_cap=64,
            av_cap=16,
        )
        base.update(overrides)
        return cls(**base)


class Vocab:
    """Growable string->id intern table."""

    def __init__(self) -> None:
        self._ids: Dict[Any, int] = {}
        self.items: List[Any] = []

    def intern(self, item: Any) -> int:
        i = self._ids.get(item)
        if i is None:
            i = len(self.items)
            self._ids[item] = i
            self.items.append(item)
        return i

    def get(self, item: Any) -> int:
        """-1 if unknown (lookup without interning)."""
        return self._ids.get(item, -1)

    def __len__(self) -> int:
        return len(self.items)


class PodPredicate(NamedTuple):
    """Interned match unit: pod matches iff namespace ∈ namespaces and labels
    match selector. Namespaces resolved at intern time (term.namespaces or
    the owning pod's namespace, PodAffinityTerm semantics)."""

    namespaces: FrozenSet[str]
    selector: LabelSelector

    def matches(self, namespace: str, labels: Dict[str, str]) -> bool:
        return namespace in self.namespaces and self.selector.matches(labels)


class ETerm(NamedTuple):
    predicate: PodPredicate
    topo_key_id: int
    kind: int


class DeviceSnapshot(NamedTuple):
    """The HBM-resident cluster state the lattice kernel reads. All shapes are
    capacity-padded; `valid` masks live rows. This is a pytree (NamedTuple of
    arrays) so it flows through jit/pjit and can be donated across updates."""

    valid: Any  # [N] bool
    unschedulable: Any  # [N] bool (node.spec.unschedulable)
    allocatable: Any  # [N, R] int32
    requested: Any  # [N, R] int32 (sum of pod requests; PODS col = pod count)
    nonzero_req: Any  # [N, R] int32 (requests with scoring defaults applied)
    label_vals: Any  # [N, K] int32 value-id per key, -1 absent
    label_numvals: Any  # [N, K] int32 numeric value for Gt/Lt, INT_MIN sentinel
    taint_key: Any  # [N, TA] int32 key-id, -1 empty
    taint_val: Any  # [N, TA] int32
    taint_effect: Any  # [N, TA] int32
    sel_counts: Any  # [N, S] int32 pods-matching-predicate counts
    eterm_w: Any  # [N, T] float32 count/weight-sum of existing-pod terms
    eterm_topo_key: Any  # [T] int32 key-id of each eterm's topology key
    eterm_kind: Any  # [T] int32 ETERM_*
    port_counts: Any  # [N, PV] int32 host-port usage counts
    image_bytes: Any  # [N, I] float32 image size if present else 0
    avoid: Any  # [N, AV] bool node-avoids-controller flags
    # priority-banded requested resources: the preemption what-if kernel
    # reads "how much could be freed by evicting pods below priority p" as
    # a masked band sum (SURVEY §7.6 batched masked what-if)
    prio_req: Any  # [N, PB, R] int32 requested by pods in priority band b
    band_prio: Any  # [PB] int32 priority of band b (I32_MAX = empty band)
    # PDB budget column (ops/preemptlattice.py): count of pods in band b
    # on node n whose eviction would violate a PodDisruptionBudget at the
    # disruption controller's CURRENT published budgets (a pod matching
    # any PDB with disruptions_allowed <= 0 counts). Refreshed host-side
    # from PDB events (update_pdb_blocked); the victim-selection kernel
    # uses it to DEPRIORITIZE nodes whose minimal victim prefix spends
    # disruption budget — the exact per-victim countdown stays host-side
    # in the reprieve loop, so this is a ranking column, never an oracle.
    pdb_blocked: Any  # [N, PB] int32
    # heterogeneity/cost columns (node-static, from the labels above):
    cost_milli: Any  # [N] int32 cost-per-hour in milli-units
    accel_class: Any  # [N] int32 interned accelerator-class value id, -1 none
    energy_milli: Any  # [N] int32 energy proxy (watts) in milli-units


class PodBatch(NamedTuple):
    """A batch of P pods encoded for the kernel (built per scheduling cycle)."""

    valid: Any  # [P] bool
    req: Any  # [P, R] int32
    nonzero_req: Any  # [P, R] int32
    node_name_row: Any  # [P] int32 row of spec.nodeName, -1 unset, -2 unknown node
    tolerates_unschedulable: Any  # [P] bool
    # node selector (AND of exprs) — metadata.name matchFields folded to rows
    ns_key: Any  # [P, E] int32
    ns_op: Any  # [P, E] int32
    ns_vals: Any  # [P, E, V] int32
    ns_num: Any  # [P, E] int32
    # required node-affinity terms (OR of terms, AND of exprs)
    aff_has: Any  # [P] bool — has required node-affinity terms
    aff_key: Any  # [P, T, E] int32
    aff_op: Any  # [P, T, E] int32
    aff_vals: Any  # [P, T, E, V] int32
    aff_num: Any  # [P, T, E] int32
    aff_term_valid: Any  # [P, T] bool
    aff_match_name_row: Any  # [P, T] int32: matchFields metadata.name row (-1 none)
    # preferred node-affinity
    pref_key: Any  # [P, PT, E] int32
    pref_op: Any  # [P, PT, E] int32
    pref_vals: Any  # [P, PT, E, V] int32
    pref_num: Any  # [P, PT, E] int32
    pref_weight: Any  # [P, PT] float32 (0 = slot empty)
    pref_term_valid: Any  # [P, PT] bool
    # tolerations
    tol_key: Any  # [P, TO] int32 (-2 empty slot, -1 wildcard key)
    tol_op: Any  # [P, TO] int32 (0 Equal, 1 Exists)
    tol_val: Any  # [P, TO] int32
    tol_effect: Any  # [P, TO] int32 (-1 all effects)
    # topology spread constraints
    spread_key: Any  # [P, C] int32 topo key-id, -1 empty
    spread_sid: Any  # [P, C] int32 predicate id
    spread_skew: Any  # [P, C] int32 max skew
    spread_hard: Any  # [P, C] bool (DoNotSchedule)
    spread_self: Any  # [P, C] bool pod matches its own constraint selector
    # incoming interpod affinity
    paff_sid: Any  # [P, A] int32 (-1 empty)
    paff_key: Any  # [P, A] int32 topo key-id
    paff_self: Any  # [P, A] bool pod matches own selector (carve-out)
    panti_sid: Any  # [P, B] int32
    panti_key: Any  # [P, B] int32
    ppref_sid: Any  # [P, W] int32 preferred terms of incoming pod
    ppref_key: Any  # [P, W] int32
    ppref_w: Any  # [P, W] float32 signed weight (negative = anti)
    # cross-match tensors
    match_sel: Any  # [P, S] bool pod matches interned predicate s
    match_svc: Any  # [P, S] bool — match_sel restricted to SERVICE-derived
    # predicates (encoder.service_sids): the SelectorSpread score's count
    # columns (same-service pods via snap.sel_counts)
    match_eterm: Any  # [P, T] bool pod matches eterm t's predicate
    eterm_add: Any  # [P, T] float32 pod's own term contributions if placed
    port_mask: Any  # [P, PV] bool host ports the pod occupies
    image_ids: Any  # [P, IM] int32 -1 empty
    image_total: Any  # [P] float32 total bytes of pod images
    ctrl_id: Any  # [P] int32 avoid-controller id, -1 none
    priority: Any  # [P] int32


# --------------------------------------------------------------------------
# Host-side master state
# --------------------------------------------------------------------------


@dataclass
class _PodEntry:
    namespace: str
    labels: Dict[str, str]
    req: np.ndarray  # [R] request columns at add time
    nonzero: np.ndarray
    eterm_ids: List[int]
    eterm_ws: List[float]
    port_ids: List[int]
    match_cache_len: int  # sids evaluated so far (== len(sel vocab) at update)
    match_vec: np.ndarray  # [<=S] bool
    prio_band: int = 0  # priority band this pod's requests landed in


class SnapshotEncoder:
    """Maintains host numpy masters + vocabularies; emits DeviceSnapshot.

    Driven by the scheduler cache (add/update/remove node, add/remove pod on
    node). `flush()` returns an up-to-date DeviceSnapshot, applying
    incremental row scatters when capacities are unchanged, mirroring the
    reference's generation-diff UpdateSnapshot (cache.go:203-303).
    """

    def __init__(self, config: Optional[EncodingConfig] = None):
        self.cfg = config or EncodingConfig()
        self.key_vocab = Vocab()
        self.val_vocab = Vocab()
        self.res_vocab = Vocab()  # extended resource name -> idx-N_BASE_RES
        self.sel_vocab = Vocab()  # PodPredicate -> sid
        self.eterm_vocab = Vocab()  # ETerm -> tid
        self.port_vocab = Vocab()  # (proto, port) -> pid
        self.image_vocab = Vocab()
        self.avoid_vocab = Vocab()  # controller-ref "kind/name" -> aid
        # sids interned FROM SERVICE selectors (register_service_predicate):
        # the SelectorSpread device score counts same-service pods through
        # exactly these sel_counts columns and no others
        self.service_sids: set = set()

        self.row_names: List[Optional[str]] = []
        self._row_by_name: Dict[str, int] = {}
        self._free_rows: List[int] = []
        self._pods: Dict[int, Dict[str, _PodEntry]] = {}  # row -> pod-key -> entry
        # True iff the last update_pdb_blocked pass saw any exhausted
        # budget: lets the no-PDB-pressure common case skip the per-row
        # recompute entirely (it runs under the cache lock per failed
        # batch)
        self._pdb_any_blocked = False

        self._alloc_masters()
        # generation bookkeeping lock: guards ONLY the pin/seal/install
        # protocol (a few integer fields + list membership), never held
        # across a blocking device readback. LEAF lock — never acquire any
        # other lock while holding it (the cache lock, when needed, is
        # taken FIRST). Named so the lock-order watchdog
        # (testing/lockgraph.py) sees every acquisition during chaos runs;
        # a Condition so sealed-generation waits are event-driven.
        self._gen_lock = threading.Condition(named_lock("encoder.gen_lock"))
        self._gen: Optional[SnapshotGeneration] = None  # live generation
        self._retiring: List[SnapshotGeneration] = []  # superseded, pinned
        self._next_gen_id = 1
        self._dirty_rows: set = set()
        # rows a failure path could not keep host/device convergent on
        # (e.g. a mid-wave encoder exception after the kernel committed):
        # the anti-entropy auditor audits these FIRST, every pass
        self.suspect_rows: set = set()
        self._full_upload = True
        # device CONTENT unknowable (readback failure, kernel exception,
        # resharding): forces a true full re-upload. _full_upload alone now
        # means "shapes may have grown" and flush re-uploads per-field.
        self._content_invalid = True
        self._globals_dirty = False  # non-row fields (band_prio, eterm meta)
        # multi-chip placement: snapshot sharding pytree + replicated spec
        # (set by the scheduler when it owns a device mesh; None = one chip)
        self._snap_shardings: Optional[DeviceSnapshot] = None
        self._rep_sharding = None
        self.generation = 0  # host-mutation counter, bumped on every change

    # -- generation table (pin → donate → retire) ----------------------------

    @property
    def _device(self) -> Optional[DeviceSnapshot]:
        """The live generation's snapshot (compat read surface: tests and
        diagnostics check `enc._device is None` / diff its fields)."""
        gen = self._gen  # graftlint: unguarded(atomic ref read; diagnostics tolerate a stale generation)
        return None if gen is None else gen.snap

    @property
    def device_generation(self) -> int:
        """Monotonic id of the live device generation (-1 before first
        upload)."""
        gen = self._gen  # graftlint: unguarded(atomic ref read; diagnostics tolerate a stale generation)
        return -1 if gen is None else gen.gen_id

    def pin_generation(self) -> GenerationLease:
        """Reader lease on the current generation: while held, a wave
        launch cannot donate (consume) the pinned buffers — it advances
        through a copy instead. The lease-scoped snapshot is therefore
        safe to gather from concurrently with donating launches."""
        return GenerationLease(self)

    def donation_lease(self, donating: bool = True) -> DonationLease:
        """Writer lease that advances the generation; see
        :class:`DonationLease`. Every donating dispatch in the tree must
        sit lexically inside one of these blocks (graftlint's donation
        pass enforces it — the successor of the retired `device_lock`
        discipline)."""
        return DonationLease(self, donating=donating)

    def _unpin(self, gen: SnapshotGeneration) -> None:
        with self._gen_lock:
            gen.pins -= 1
            if gen.pins <= 0 and gen is not self._gen:
                try:
                    self._retiring.remove(gen)
                except ValueError:
                    pass
                self._retire_locked(gen)
                self._gen_lock.notify_all()
            self._publish_gen_gauges_locked()

    def _install_locked(
        self,
        snap: DeviceSnapshot,
        base: Optional[SnapshotGeneration],
        consumed: bool,
        shared_with_base: bool = False,
    ) -> None:
        """Install `snap` as the next live generation (caller holds
        `_gen_lock`). `consumed`: base's buffers were donated in place —
        the generation object is dead on arrival (seal guarantees it had
        zero pins). `shared_with_base`: the new generation reuses some of
        base's buffers (reshape-merge), so donation treats the pair as
        one pin scope until base retires."""
        now = time.monotonic()
        parent = None
        if base is not None:
            base.superseded_at = now
            if shared_with_base:
                if base.pins > 0:
                    parent = base
                elif (
                    base.shared_parent is not None
                    and base.shared_parent.pins > 0
                ):
                    # the tie must survive CHAINED sharing: base reuses a
                    # still-pinned grandparent's buffers (two capacity
                    # growths while one reader pins), so the new
                    # generation's kept fields are the grandparent's —
                    # dropping the tie here would let a later donation
                    # consume buffers that pinned reader still gathers
                    parent = base.shared_parent
            if consumed or base.pins <= 0:
                self._retire_locked(base)
            else:
                self._retiring.append(base)
        self._gen = SnapshotGeneration(
            self._next_gen_id, snap, shared_parent=parent
        )
        self._next_gen_id += 1
        self._gen_lock.notify_all()
        self._publish_gen_gauges_locked()

    def _install_generation(
        self, snap: DeviceSnapshot, shared_with_base: bool = False
    ) -> None:
        """Install a freshly-uploaded snapshot (device_put — fresh
        buffers unless shared_with_base) as the live generation."""
        with self._gen_lock:
            while self._gen is not None and self._gen.sealed:
                self._gen_lock.wait(timeout=0.05)
            self._install_locked(
                snap, self._gen, consumed=False,
                shared_with_base=shared_with_base,
            )

    def _retire_locked(self, gen: SnapshotGeneration) -> None:
        """Buffer set leaves service: count it, stamp retirement latency,
        re-point any child's shared-buffer tie PAST it. The tie must
        propagate, not sever: with chained sharing (reader R1 pins A, a
        reshape installs B sharing A, reader R2 pins B, a reshape
        installs C sharing B), R2's unpin retires intermediate B while
        C's kept fields are still A's buffers — C inherits the tie to
        the still-pinned A, or a later donation on C would consume the
        buffers R1's gather reads."""
        if gen.superseded_at is not None:
            latency = max(0.0, time.monotonic() - gen.superseded_at)
            metrics.observe(HIST_GEN_RETIRE_LATENCY, latency)
            metrics.set_gauge(GAUGE_GEN_LAST_RETIRE_LATENCY, latency)
        metrics.inc(COUNTER_GEN_RETIRED)
        parent = gen.shared_parent
        if parent is not None and parent.pins <= 0:
            # unpinned ancestors are already retired (or about to be):
            # dropping the reference keeps no dead buffer set reachable
            parent = None
        children = list(self._retiring)
        if self._gen is not None:
            children.append(self._gen)
        for child in children:
            if child.shared_parent is gen:
                child.shared_parent = parent

    def check_retire_stalls(self) -> None:
        """Stall-watchdog sweep for periodic callers (the anti-entropy
        pass, the SIGUSR2 dataplane dump). The lease-entry checks fire
        only on new pin/donation traffic, so without this a leaked
        reader pin on an otherwise idle encoder would hold its HBM
        generation invisibly until the next lease happened to arrive."""
        with self._gen_lock:
            self._check_retire_stalls_locked()
            self._publish_gen_gauges_locked()

    def _check_retire_stalls_locked(self) -> None:
        now = time.monotonic()
        for gen in self._retiring:
            if gen.stall_reported or gen.superseded_at is None:
                continue
            if now - gen.superseded_at > RETIRE_STALL_AFTER_S:
                gen.stall_reported = True
                metrics.inc(COUNTER_GEN_RETIRE_STALLS)
                logger.error(
                    "snapshot generation %d superseded %.1f s ago still "
                    "holds %d reader pin(s): a lease leaked — its HBM "
                    "buffers cannot retire",
                    gen.gen_id, now - gen.superseded_at, gen.pins,
                )

    def _publish_gen_gauges_locked(self) -> None:
        gen = self._gen
        pins = sum(g.pins for g in self._retiring)
        if gen is not None:
            pins += gen.pins
            metrics.set_gauge(GAUGE_GEN_CURRENT, float(gen.gen_id))
        metrics.set_gauge(GAUGE_GEN_PINNED, float(pins))
        metrics.set_gauge(GAUGE_GEN_RETIRING, float(len(self._retiring)))

    # -- master allocation / growth ---------------------------------------

    def _alloc_masters(self) -> None:
        c = self.cfg
        n = c.n_cap
        self.m_valid = np.zeros(n, np.bool_)
        self.m_unsched = np.zeros(n, np.bool_)
        self.m_alloc = np.zeros((n, c.r_cap), np.int32)
        self.m_req = np.zeros((n, c.r_cap), np.int32)
        self.m_nonzero = np.zeros((n, c.r_cap), np.int32)
        self.m_label_vals = np.full((n, c.k_cap), -1, np.int32)
        self.m_label_num = np.full((n, c.k_cap), np.iinfo(np.int32).min, np.int32)
        self.m_taint_key = np.full((n, c.taints_max), -1, np.int32)
        self.m_taint_val = np.zeros((n, c.taints_max), np.int32)
        self.m_taint_eff = np.zeros((n, c.taints_max), np.int32)
        self.m_sel_counts = np.zeros((n, c.s_cap), np.int32)
        self.m_eterm_w = np.zeros((n, c.t_cap), np.float32)
        self.m_eterm_topo = np.full(c.t_cap, -1, np.int32)
        self.m_eterm_kind = np.full(c.t_cap, -1, np.int32)
        self.m_port_counts = np.zeros((n, c.pv_cap), np.int32)
        self.m_image_bytes = np.zeros((n, c.im_cap), np.float32)
        self.m_avoid = np.zeros((n, c.av_cap), np.bool_)
        self.m_prio_req = np.zeros((n, c.pb_cap, c.r_cap), np.int32)
        self.m_band_prio = np.full(c.pb_cap, I32_MAX, np.int32)
        self.m_pdb_blocked = np.zeros((n, c.pb_cap), np.int32)
        self.m_cost = np.zeros(n, np.int32)
        self.m_accel = np.full(n, -1, np.int32)
        self.m_energy = np.zeros(n, np.int32)

    def _grow(self, **caps: int) -> None:
        """Grow one or more capacities; copies masters, forces full upload."""
        old = {
            "m_valid": self.m_valid,
            "m_unsched": self.m_unsched,
            "m_alloc": self.m_alloc,
            "m_req": self.m_req,
            "m_nonzero": self.m_nonzero,
            "m_label_vals": self.m_label_vals,
            "m_label_num": self.m_label_num,
            "m_taint_key": self.m_taint_key,
            "m_taint_val": self.m_taint_val,
            "m_taint_eff": self.m_taint_eff,
            "m_sel_counts": self.m_sel_counts,
            "m_eterm_w": self.m_eterm_w,
            "m_eterm_topo": self.m_eterm_topo,
            "m_eterm_kind": self.m_eterm_kind,
            "m_port_counts": self.m_port_counts,
            "m_image_bytes": self.m_image_bytes,
            "m_avoid": self.m_avoid,
            "m_prio_req": self.m_prio_req,
            "m_band_prio": self.m_band_prio,
            "m_pdb_blocked": self.m_pdb_blocked,
            "m_cost": self.m_cost,
            "m_accel": self.m_accel,
            "m_energy": self.m_energy,
        }
        self.cfg = replace(self.cfg, **caps)
        self._alloc_masters()
        for name, arr in old.items():
            dst = getattr(self, name)
            sl = tuple(slice(0, s) for s in arr.shape)
            dst[sl] = arr
        # shape growth, not content loss: flush re-uploads only the fields
        # whose shape changed (a mid-burst t_cap bump cost a full 5k-row
        # re-upload before this distinction)
        self._full_upload = True

    def presize_for_cluster(self, num_nodes: int) -> None:
        """Grow n_cap/v_cap ahead of a known cluster scale (see
        EncodingConfig.for_cluster). Cheap before the first flush; later it
        costs the same single re-upload a demand-grow would."""
        want = EncodingConfig.for_cluster(num_nodes)
        grown = {}
        for cap in (
            "n_cap", "v_cap", "k_cap", "s_cap", "t_cap", "pv_cap",
            "im_cap", "av_cap",
        ):
            cur, target = getattr(self.cfg, cap), getattr(want, cap)
            if target > cur:
                new = cur
                while new < target:
                    new *= 2
                grown[cap] = new
        if grown:
            self._grow(**grown)  # ONE reallocate-and-copy pass for all caps

    def _ensure_cap(self, attr: str, needed: int) -> None:
        cur = getattr(self.cfg, attr)
        if needed <= cur:
            return
        new = cur
        while new < needed:
            new *= 2
        self._grow(**{attr: new})

    # -- vocab helpers ------------------------------------------------------

    def intern_key(self, key: str) -> int:
        i = self.key_vocab.intern(key)
        self._ensure_cap("k_cap", len(self.key_vocab))
        return i

    def intern_val(self, val: str) -> int:
        i = self.val_vocab.intern(val)
        self._ensure_cap("v_cap", len(self.val_vocab))
        return i

    def intern_resource(self, name: str) -> int:
        """Resource name -> column index (base resources fixed)."""
        base = {CPU: RES_CPU, MEMORY: RES_MEM, EPHEMERAL_STORAGE: RES_STORAGE, PODS: RES_PODS}
        if name in base:
            return base[name]
        i = N_BASE_RES + self.res_vocab.intern(name)
        self._ensure_cap("r_cap", N_BASE_RES + len(self.res_vocab))
        return i

    def intern_predicate(self, namespaces: FrozenSet[str], sel: LabelSelector) -> int:
        pred = PodPredicate(namespaces, sel)
        known = self.sel_vocab.get(pred)
        if known >= 0:
            return known
        sid = self.sel_vocab.intern(pred)
        self._ensure_cap("s_cap", len(self.sel_vocab))
        # back-fill counts for already-placed pods (one host scan, amortised)
        for row, pods in self._pods.items():
            cnt = sum(
                1 for e in pods.values() if pred.matches(e.namespace, e.labels)
            )
            if cnt:
                self.m_sel_counts[row, sid] = cnt
                self._dirty_rows.add(row)
        self.generation += 1
        return sid

    def register_service_predicate(self, namespace: str, selector: LabelSelector) -> int:
        """Intern a Service's selector as a pod predicate and mark its sid
        service-derived (the DefaultPodTopologySpread device score reads
        sel_counts through service sids only). Idempotent; called from the
        scheduler's service event handlers so a new Service grows the vocab
        and thereby invalidates cached templates (their fingerprints embed
        vocab lengths)."""
        sid = self.intern_predicate(frozenset({namespace}), selector)
        self.service_sids.add(sid)
        return sid

    def service_sid_mask(self) -> np.ndarray:
        """[s_cap] bool — which predicate columns are service-derived."""
        mask = np.zeros(self.cfg.s_cap, np.bool_)
        for sid in self.service_sids:
            if sid < mask.shape[0]:
                mask[sid] = True
        return mask

    def intern_eterm(self, pred: PodPredicate, topo_key: str, kind: int) -> int:
        key_id = self.intern_key(topo_key)
        et = ETerm(pred, key_id, kind)
        known = self.eterm_vocab.get(et)
        if known >= 0:
            return known
        tid = self.eterm_vocab.intern(et)
        self._ensure_cap("t_cap", len(self.eterm_vocab))
        self.m_eterm_topo[tid] = key_id
        self.m_eterm_kind[tid] = kind
        self._globals_dirty = True
        self.generation += 1
        return tid

    def intern_port(self, proto: str, port: int) -> int:
        i = self.port_vocab.intern((proto, port))
        self._ensure_cap("pv_cap", len(self.port_vocab))
        return i

    def intern_image(self, name: str) -> int:
        i = self.image_vocab.intern(name)
        self._ensure_cap("im_cap", len(self.image_vocab))
        return i

    def intern_avoid(self, ref: str) -> int:
        i = self.avoid_vocab.intern(ref)
        self._ensure_cap("av_cap", len(self.avoid_vocab))
        return i

    def _band_of(self, priority: int) -> int:
        """Priority band index. Distinct priorities get their own band; once
        bands are exhausted, fall back to the band with the largest priority
        <= the pod's (else the lowest band). The fallback overstates what a
        higher-priority preemptor could free — the what-if mask must stay
        OPTIMISTIC (no false negatives vs the host reprieve loop, which does
        the exact check on surviving candidates)."""
        bands = self.m_band_prio
        exact = np.nonzero(bands == priority)[0]
        if exact.size:
            return int(exact[0])
        empty = np.nonzero(bands == I32_MAX)[0]
        if empty.size:
            b = int(empty[0])
            bands[b] = priority
            self._globals_dirty = True
            self.generation += 1
            return b
        lower = np.nonzero(bands <= priority)[0]
        if lower.size:
            return int(lower[np.argmax(bands[lower])])
        # every band sits above this pod: adopt the lowest band and relabel
        # it DOWN to this priority. Lowering a band's label is optimistic for
        # the band's existing pods (they appear removable to lower-priority
        # preemptors), never pessimistic — the invariant holds.
        b = int(np.argmin(bands))
        bands[b] = priority
        self._globals_dirty = True
        self.generation += 1
        return b

    # -- resource encoding ---------------------------------------------------

    def encode_resources(self, rl: ResourceList, ceil: bool) -> np.ndarray:
        cols = []
        for name, val in rl.items():
            col = self.intern_resource(name)  # may grow r_cap
            if name in (CPU, PODS):
                u = int(min(val, int(I32_MAX)))
            else:
                u = _to_col_units(name, val, ceil)
            cols.append((col, u))
        out = np.zeros(self.cfg.r_cap, np.int32)
        for col, u in cols:
            out[col] = u
        return out

    # -- node lifecycle ------------------------------------------------------

    def row_of(self, node_name: str) -> int:
        return self._row_by_name.get(node_name, -1)

    def add_node(self, node: v1.Node) -> int:
        name = node.metadata.name
        if name in self._row_by_name:
            return self.update_node(node)
        if self._free_rows:
            row = self._free_rows.pop()
        else:
            row = len(self.row_names)
            self.row_names.append(None)
            self._ensure_cap("n_cap", len(self.row_names))
        self.row_names[row] = name
        self._row_by_name[name] = row
        self._pods.setdefault(row, {})
        self._write_node_row(row, node)
        return row

    def update_node(self, node: v1.Node) -> int:
        row = self._row_by_name[node.metadata.name]
        self._write_node_row(row, node)
        return row

    def encode_node_row_values(self, node: v1.Node) -> Dict[str, np.ndarray]:
        """Encode one node's NODE-STATIC columns (no pod aggregates) into a
        standalone row-values dict keyed by DeviceSnapshot field name. This
        is the single row encoding shared by `_write_node_row` (live
        masters) and the autoscaler's what-if overlay (virtual candidate
        rows appended to a COPY of the snapshot — the values never touch
        the live masters there). Interning happens first so every capacity
        is final before the row arrays are allocated (a mid-encode `_grow`
        would otherwise orphan the half-filled arrays)."""
        alloc = self.encode_resources(node.allocatable(), ceil=False)
        # labels — metadata.name is matchable as a field selector; expose it
        # as a pseudo-label so matchFields shares the label path.
        labels = dict(node.metadata.labels)
        labels.setdefault("kubernetes.io/hostname", node.metadata.name)
        lab = [
            (self.intern_key(k), self.intern_val(v), v)
            for k, v in labels.items()
        ]
        taints = [
            (
                self.intern_key(t.key),
                self.intern_val(t.value),
                _EFFECT_CODES.get(t.effect, EFFECT_NO_SCHEDULE),
            )
            for t in node.spec.taints[: self.cfg.taints_max]
        ]
        images = [
            (self.intern_image(nm), float(img.size_bytes))
            for img in node.status.images
            for nm in img.names
        ]
        # avoid-pods annotation: comma-separated "Kind/name" controller refs
        # (simplified AvoidPods encoding; reference uses a JSON annotation,
        # v1helper.GetAvoidPodsFromNodeAnnotations).
        ann = node.metadata.annotations.get(
            "scheduler.alpha.kubernetes.io/preferAvoidPods", ""
        )
        avoids = [
            self.intern_avoid(ref)
            for ref in filter(None, (r.strip() for r in ann.split(",")))
        ]
        c = self.cfg  # re-read: interning above may have grown capacities
        label_vals = np.full(c.k_cap, -1, np.int32)
        label_num = np.full(c.k_cap, np.iinfo(np.int32).min, np.int32)
        for ki, vi, raw in lab:
            label_vals[ki] = vi
            try:
                label_num[ki] = int(raw)
            except ValueError:
                pass
        taint_key = np.full(c.taints_max, -1, np.int32)
        taint_val = np.zeros(c.taints_max, np.int32)
        taint_eff = np.zeros(c.taints_max, np.int32)
        for i, (ki, vi, eff) in enumerate(taints):
            taint_key[i] = ki
            taint_val[i] = vi
            taint_eff[i] = eff
        image_bytes = np.zeros(c.im_cap, np.float32)
        for ii, sz in images:
            image_bytes[ii] = sz
        avoid = np.zeros(c.av_cap, np.bool_)
        for ai in avoids:
            avoid[ai] = True
        accel_raw = labels.get(LABEL_ACCELERATOR_CLASS)
        return {
            "valid": np.bool_(True),
            "unschedulable": np.bool_(node.spec.unschedulable),
            "allocatable": zpad(alloc, c.r_cap),
            "label_vals": label_vals,
            "label_numvals": label_num,
            "taint_key": taint_key,
            "taint_val": taint_val,
            "taint_effect": taint_eff,
            "image_bytes": image_bytes,
            "avoid": avoid,
            # heterogeneity/cost columns (already interned above via the
            # generic label path; accel re-interns idempotently)
            "cost_milli": np.int32(_milli_of_label(labels, LABEL_COST_PER_HOUR)),
            "accel_class": np.int32(
                self.intern_val(accel_raw) if accel_raw else -1
            ),
            "energy_milli": np.int32(_milli_of_label(labels, LABEL_ENERGY_WATTS)),
        }

    def _write_node_row(self, row: int, node: v1.Node) -> None:
        vals = self.encode_node_row_values(node)
        # masters re-fetched AFTER the encode: interning can _grow (which
        # reallocates every master array)
        self.m_valid[row] = vals["valid"]
        self.m_unsched[row] = vals["unschedulable"]
        self.m_alloc[row, :] = vals["allocatable"]
        self.m_label_vals[row, :] = vals["label_vals"]
        self.m_label_num[row, :] = vals["label_numvals"]
        self.m_taint_key[row, :] = vals["taint_key"]
        self.m_taint_val[row, :] = vals["taint_val"]
        self.m_taint_eff[row, :] = vals["taint_effect"]
        self.m_image_bytes[row, :] = vals["image_bytes"]
        self.m_avoid[row, :] = vals["avoid"]
        self.m_cost[row] = vals["cost_milli"]
        self.m_accel[row] = vals["accel_class"]
        self.m_energy[row] = vals["energy_milli"]
        self._dirty_rows.add(row)
        self.generation += 1

    def remove_node(self, node_name: str) -> None:
        row = self._row_by_name.pop(node_name, None)
        if row is None:
            return
        self.row_names[row] = None
        self._free_rows.append(row)
        self._pods[row] = {}
        self.m_valid[row] = False
        self.m_sel_counts[row, :] = 0
        self.m_eterm_w[row, :] = 0
        self.m_req[row, :] = 0
        self.m_nonzero[row, :] = 0
        self.m_port_counts[row, :] = 0
        self.m_prio_req[row, :, :] = 0
        self.m_pdb_blocked[row, :] = 0
        self._dirty_rows.add(row)
        self.generation += 1

    # -- pod lifecycle -------------------------------------------------------

    def _pod_eterms(self, pod: v1.Pod) -> Tuple[List[int], List[float]]:
        """Intern the anti/affinity terms *carried by* this pod."""
        ids: List[int] = []
        ws: List[float] = []
        aff = pod.spec.affinity
        ns = pod.metadata.namespace
        if aff is None:
            return ids, ws

        def pred_of(term: v1.PodAffinityTerm) -> PodPredicate:
            nss = frozenset(term.namespaces) if term.namespaces else frozenset({ns})
            return PodPredicate(nss, term.label_selector or LabelSelector())

        if aff.pod_anti_affinity:
            for term in aff.pod_anti_affinity.required:
                ids.append(self.intern_eterm(pred_of(term), term.topology_key, ETERM_ANTI_REQ))
                ws.append(1.0)
            for wt in aff.pod_anti_affinity.preferred:
                ids.append(
                    self.intern_eterm(pred_of(wt.term), wt.term.topology_key, ETERM_ANTI_PREF)
                )
                ws.append(float(wt.weight))
        if aff.pod_affinity:
            for term in aff.pod_affinity.required:
                ids.append(self.intern_eterm(pred_of(term), term.topology_key, ETERM_AFF_REQ))
                ws.append(1.0)
            for wt in aff.pod_affinity.preferred:
                ids.append(
                    self.intern_eterm(pred_of(wt.term), wt.term.topology_key, ETERM_AFF_PREF)
                )
                ws.append(float(wt.weight))
        return ids, ws

    def pod_proto(self, pod: v1.Pod) -> tuple:
        """Shared encoding of everything add_pod derives from the SPEC
        (requests, carried terms, ports, label match vector): pods of one
        scheduling template produce identical protos, so a bulk bind
        computes this once per template instead of once per pod. Valid
        only at the current vocab state — add_pod revalidates."""
        from ..api.objects import compute_pod_resource_request, pod_host_ports

        req = self.encode_resources(compute_pod_resource_request(pod), ceil=True)
        nz = self.encode_resources(
            compute_pod_resource_request(pod, non_zero=True), ceil=True
        )
        req = zpad(req, self.cfg.r_cap)
        nz = zpad(nz, self.cfg.r_cap)
        req[RES_PODS] = 1
        nz[RES_PODS] = 1
        eids, ews = self._pod_eterms(pod)
        pids = [
            self.intern_port(proto, port)
            for (_, proto, port) in pod_host_ports(pod)
        ]
        mv = self._match_vec(pod.metadata.namespace, pod.metadata.labels)
        return (req, nz, eids, ews, pids, mv, len(self.sel_vocab))

    def add_pod(
        self,
        node_name: str,
        pod: v1.Pod,
        device_synced: bool = False,
        prio_band: Optional[int] = None,
        proto: Optional[tuple] = None,
    ) -> None:
        """device_synced=True: the wave kernel already committed this pod's
        occupancy (requested/nonzero/sel_counts/eterm_w/ports/prio_req) into
        the device snapshot it returned (wavelattice finalize), so replaying
        it here must update the host masters WITHOUT marking the row dirty —
        a dirty mark would re-upload values the device already holds, one
        redundant scatter per placed pod's row.

        proto: a pod_proto() result from a template sibling — reused
        (arrays treated as immutable) unless the vocab grew since."""
        row = self._row_by_name.get(node_name)
        if row is None:
            raise KeyError(f"unknown node {node_name}")
        if proto is not None and proto[6] == len(self.sel_vocab):
            req, nz, eids, ews, pids, mv, _ = proto
        else:
            req, nz, eids, ews, pids, mv, _ = self.pod_proto(pod)
        # device_synced replay must land in the band the kernel committed
        # prio_req under (captured at encode time); recomputing could pick a
        # different band after a relabel, silently diverging host vs device
        band = prio_band if prio_band is not None else self._band_of(pod.priority)
        entry = _PodEntry(
            namespace=pod.metadata.namespace,
            labels=dict(pod.metadata.labels),
            req=req,
            nonzero=nz,
            eterm_ids=eids,
            eterm_ws=ews,
            port_ids=pids,
            match_cache_len=len(self.sel_vocab),
            match_vec=mv,
            prio_band=band,
        )
        self._pods[row][pod.metadata.key] = entry
        self.m_req[row, : len(req)] += req
        self.m_nonzero[row, : len(nz)] += nz
        self.m_prio_req[row, band, : len(req)] += req
        for i, m in enumerate(entry.match_vec):
            if m:
                self.m_sel_counts[row, i] += 1
        for tid, w in zip(eids, ews):
            self.m_eterm_w[row, tid] += w
        for pid in pids:
            self.m_port_counts[row, pid] += 1
        if not device_synced:
            self._dirty_rows.add(row)
        self.generation += 1

    def add_pods_bulk(self, items: list) -> None:
        """Vectorized add_pod for a wave of device-synced placements:
        items = [(node_name, pod, band, proto)] with proto from
        pod_proto() (None entries computed here). Master updates become
        one np.add.at scatter per (proto, band) group instead of python
        loops per pod — the 50k pods/s target cannot afford ~0.1 ms of
        per-pod host bookkeeping on the bind path."""
        # pass 1 — resolve + (re)compute protos. All raising checks and all
        # vocab interning (which can GROW capacities) happen here, BEFORE
        # any entry insert or master scatter: an exception must leave the
        # masters untouched, or later removals would drive them negative
        resolved: list = []  # (row, pod, band, proto)
        for node_name, pod, band, proto in items:
            row = self._row_by_name.get(node_name)
            if row is None:
                raise KeyError(f"unknown node {node_name}")
            if proto is None or proto[6] != len(self.sel_vocab):
                proto = self.pod_proto(pod)
            resolved.append((row, pod, band, proto))
        # pass 2 — pure writes; nothing below interns or raises
        groups: dict = {}  # (id(proto), band) -> (proto, rows)
        for row, pod, band, proto in resolved:
            req, nz, eids, ews, pids, mv, _ = proto
            self._pods[row][pod.metadata.key] = _PodEntry(
                namespace=pod.metadata.namespace,
                labels=dict(pod.metadata.labels),
                req=req,
                nonzero=nz,
                eterm_ids=eids,
                eterm_ws=ews,
                port_ids=pids,
                match_cache_len=len(self.sel_vocab),
                match_vec=mv,
                prio_band=band,
            )
            key = (id(proto), band)
            g = groups.get(key)
            if g is None:
                groups[key] = (proto, [row])
            else:
                g[1].append(row)
        for (_, band), (proto, rows) in groups.items():
            req, nz, eids, ews, pids, mv, _ = proto
            r = np.asarray(rows, np.int64)
            # column-sliced like add_pod: a proto narrower than the
            # current r_cap (capacity grew after it was built) still lands
            np.add.at(self.m_req[:, : len(req)], r, req)
            np.add.at(self.m_nonzero[:, : len(nz)], r, nz)
            np.add.at(self.m_prio_req[:, band, : len(req)], r, req)
            if mv.any():
                np.add.at(
                    self.m_sel_counts[:, : len(mv)], r, mv.astype(np.int32)
                )
            for tid, w in zip(eids, ews):
                np.add.at(self.m_eterm_w[:, tid], r, w)
            for pid in pids:
                np.add.at(self.m_port_counts[:, pid], r, 1)
        self.generation += len(items)

    def remove_pod(self, node_name: str, pod_key: str) -> None:
        row = self._row_by_name.get(node_name)
        if row is None:
            return
        entry = self._pods[row].pop(pod_key, None)
        if entry is None:
            return
        r = zpad(entry.req, self.cfg.r_cap)
        z = zpad(entry.nonzero, self.cfg.r_cap)
        self.m_req[row, :] -= r
        self.m_nonzero[row, :] -= z
        self.m_prio_req[row, entry.prio_band, :] -= r
        for i, mv in enumerate(entry.match_vec):
            if mv:
                self.m_sel_counts[row, i] -= 1
        # predicates interned after this pod was added were back-filled by
        # intern_predicate's scan, which saw this pod — account for them too.
        for sid in range(entry.match_cache_len, len(self.sel_vocab)):
            if self.sel_vocab.items[sid].matches(entry.namespace, entry.labels):
                self.m_sel_counts[row, sid] -= 1
        for tid, w in zip(entry.eterm_ids, entry.eterm_ws):
            self.m_eterm_w[row, tid] -= w
        for pid in entry.port_ids:
            self.m_port_counts[row, pid] -= 1
        self._dirty_rows.add(row)
        self.generation += 1

    def _match_vec(self, namespace: str, labels: Dict[str, str]) -> np.ndarray:
        out = np.zeros(len(self.sel_vocab), np.bool_)
        for i, pred in enumerate(self.sel_vocab.items):
            out[i] = pred.matches(namespace, labels)
        return out

    def update_pdb_blocked(self, pdbs: List["v1.PodDisruptionBudget"]) -> int:
        """Recompute the PDB budget column family (`pdb_blocked[N, PB]`)
        from the disruption controller's CURRENT published budgets: a
        placed pod counts as blocked when it matches any PDB whose
        status.disruptions_allowed is already spent (<= 0). This is the
        vectorized victim-selection kernel's node-DEPRIORITIZER, not the
        oracle — the per-victim budget countdown (list-order consumption
        across overlapping PDBs) stays in the host reprieve loop that
        validates every candidate before eviction. Caller holds the cache
        lock. Returns the number of rows whose column changed (each is
        marked dirty for the next flush)."""
        from ..api.selectors import match_labels as _match_labels

        blocked = [
            (pdb.metadata.namespace, pdb.spec.selector)
            for pdb in pdbs
            if pdb.status.disruptions_allowed <= 0
        ]
        if not blocked and not self._pdb_any_blocked:
            # common case (no exhausted budgets, column already clear):
            # skip the per-row matching entirely — this runs under the
            # cache lock on every failed batch
            return 0
        changed = 0
        for row, pods in self._pods.items():
            want = np.zeros(self.cfg.pb_cap, np.int32)
            if blocked:
                for e in pods.values():
                    for ns, sel in blocked:
                        if ns == e.namespace and _match_labels(sel, e.labels):
                            want[e.prio_band] += 1
                            break
            if not np.array_equal(self.m_pdb_blocked[row], want):
                self.m_pdb_blocked[row] = want
                self._dirty_rows.add(row)
                changed += 1
        self._pdb_any_blocked = bool(blocked)
        if changed:
            self.generation += 1
        return changed

    # -- anti-entropy hooks (scheduler/antientropy.py) -----------------------
    #
    # The pod-aggregate columns are maintained INCREMENTALLY (add/remove
    # deltas), which is exactly where a drift bug or a half-applied update
    # accumulates silently. These hooks let the auditor re-derive a row's
    # expected aggregates from the per-pod entries (the host source of
    # truth) and repair the masters and/or the device row in place.

    # row-major pod-aggregate fields re-derivable from _PodEntry records
    AGGREGATE_FIELDS = (
        "requested", "nonzero_req", "prio_req", "sel_counts", "eterm_w",
        "port_counts",
    )
    # every row-major (per-node) DeviceSnapshot field, for device-vs-master
    # audits; globals (band_prio, eterm metadata) are compared wholesale
    ROW_FIELDS = tuple(
        f for f in DeviceSnapshot._fields
        if f not in ("eterm_topo_key", "eterm_kind", "band_prio")
    )

    def _master_of(self, field: str) -> np.ndarray:
        return {
            "valid": self.m_valid,
            "unschedulable": self.m_unsched,
            "allocatable": self.m_alloc,
            "requested": self.m_req,
            "nonzero_req": self.m_nonzero,
            "label_vals": self.m_label_vals,
            "label_numvals": self.m_label_num,
            "taint_key": self.m_taint_key,
            "taint_val": self.m_taint_val,
            "taint_effect": self.m_taint_eff,
            "sel_counts": self.m_sel_counts,
            "eterm_w": self.m_eterm_w,
            "port_counts": self.m_port_counts,
            "image_bytes": self.m_image_bytes,
            "avoid": self.m_avoid,
            "prio_req": self.m_prio_req,
            "pdb_blocked": self.m_pdb_blocked,
            "cost_milli": self.m_cost,
            "accel_class": self.m_accel,
            "energy_milli": self.m_energy,
        }[field]

    def expected_row_aggregates(self, row: int) -> Dict[str, np.ndarray]:
        """Re-encode the pod-aggregate columns of one row from its
        _PodEntry records — what the masters MUST say if every
        incremental add/remove landed exactly once."""
        c = self.cfg
        req = np.zeros(c.r_cap, np.int32)
        nz = np.zeros(c.r_cap, np.int32)
        prio = np.zeros((c.pb_cap, c.r_cap), np.int32)
        sel = np.zeros(c.s_cap, np.int32)
        et = np.zeros(c.t_cap, np.float32)
        ports = np.zeros(c.pv_cap, np.int32)
        for e in self._pods.get(row, {}).values():
            req[: len(e.req)] += e.req
            nz[: len(e.nonzero)] += e.nonzero
            prio[e.prio_band, : len(e.req)] += e.req
            mv = e.match_vec
            sel[: len(mv)] += mv.astype(np.int32)
            # predicates interned after this pod was added were back-filled
            # by intern_predicate's scan (same rule remove_pod applies)
            for sid in range(e.match_cache_len, len(self.sel_vocab)):
                if self.sel_vocab.items[sid].matches(e.namespace, e.labels):
                    sel[sid] += 1
            for tid, w in zip(e.eterm_ids, e.eterm_ws):
                et[tid] += w
            for pid in e.port_ids:
                ports[pid] += 1
        return {
            "requested": req,
            "nonzero_req": nz,
            "prio_req": prio,
            "sel_counts": sel,
            "eterm_w": et,
            "port_counts": ports,
        }

    def verify_row_aggregates(self, row: int, repair: bool = False) -> List[str]:
        """Column names whose master row diverges from the entry-derived
        expectation; repair=True rewrites the masters and marks the row
        dirty so the next flush re-scatters it to the device."""
        expected = self.expected_row_aggregates(row)
        bad: List[str] = []
        for field, want in expected.items():
            m = self._master_of(field)
            if not np.array_equal(m[row], want):
                bad.append(field)
                if repair:
                    m[row] = want
        if bad and repair:
            self._dirty_rows.add(row)
            self.generation += 1
        return bad

    def drop_pod_entry(self, node_name: str, pod_key: str) -> bool:
        """Remove a pod's entry WITHOUT subtracting its aggregates — for
        unwinding a half-applied add_pod whose master increments may be
        partial (subtracting would double the damage). The caller must
        follow with repair_row()."""
        row = self._row_by_name.get(node_name)
        if row is None:
            return False
        return self._pods.get(row, {}).pop(pod_key, None) is not None

    def repair_row(self, node_name: str) -> List[str]:
        """Rebuild one row's aggregate masters from its entries, mark it
        dirty (next flush overwrites the device row), and flag it suspect
        for the anti-entropy auditor's next pass. Returns the repaired
        column names."""
        row = self._row_by_name.get(node_name)
        if row is None:
            return []
        bad = self.verify_row_aggregates(row, repair=True)
        # even when the masters were consistent, the DEVICE row may hold
        # occupancy the masters never saw (kernel-committed, replay
        # failed): force the re-scatter regardless
        self._dirty_rows.add(row)
        self.suspect_rows.add(row)
        return bad

    def fetch_device_rows(self, rows: List[int]) -> Optional[Dict[str, np.ndarray]]:
        """Gather the sampled rows of every row-major device field to host
        in ONE transfer (the audit's read side). None when no device
        snapshot exists yet.

        Runs under a generation pin, NOT a lock: the pinned generation's
        buffers cannot be donated while the lease is held (a concurrent
        wave launch advances through a copy), so this gather may overlap
        a donating launch freely — the exact round-8 interleaving that
        used to deadlock the CPU client is now legal.

        The gather index is padded to the scatter program sizes (16/1024,
        chunking larger sets): a distinct XLA program per sample size
        would compile on nearly every audit pass (the round-robin window
        tail and the suspect set both vary), each compile seconds of
        cache-lock hold."""
        if not rows:
            return None
        out: Dict[str, np.ndarray] = {}
        with self.pin_generation() as lease:
            if lease.snap is None:
                return None
            # barrier before reading: the pinned generation may be the
            # output of a scatter still in flight; waiting on the pinned
            # buffers (ours by lease — no aliasing possible) keeps the
            # audit's confirm fetch ordered after the repair it confirms
            jax.block_until_ready(lease.snap)
            for i in range(0, len(rows), _SCATTER_PAD_BIG):
                chunk = rows[i : i + _SCATTER_PAD_BIG]
                pad = (
                    _SCATTER_PAD_SMALL
                    if len(chunk) <= _SCATTER_PAD_SMALL
                    else _SCATTER_PAD_BIG
                )
                # pad rows repeat row 0 (cheap, in range); sliced off below
                idx = np.zeros(pad, np.int32)
                idx[: len(chunk)] = chunk
                gathered = jax.device_get(_gather_rows(lease.snap, idx))
                for name, arr in gathered.items():
                    arr = np.asarray(arr)[: len(chunk)]
                    out[name] = (
                        arr
                        if name not in out
                        else np.concatenate([out[name], arr])
                    )
        return out

    # -- device sync ---------------------------------------------------------

    def _masters(self) -> DeviceSnapshot:
        return DeviceSnapshot(
            valid=self.m_valid,
            unschedulable=self.m_unsched,
            allocatable=self.m_alloc,
            requested=self.m_req,
            nonzero_req=self.m_nonzero,
            label_vals=self.m_label_vals,
            label_numvals=self.m_label_num,
            taint_key=self.m_taint_key,
            taint_val=self.m_taint_val,
            taint_effect=self.m_taint_eff,
            sel_counts=self.m_sel_counts,
            eterm_w=self.m_eterm_w,
            eterm_topo_key=self.m_eterm_topo,
            eterm_kind=self.m_eterm_kind,
            port_counts=self.m_port_counts,
            image_bytes=self.m_image_bytes,
            avoid=self.m_avoid,
            prio_req=self.m_prio_req,
            band_prio=self.m_band_prio,
            pdb_blocked=self.m_pdb_blocked,
            cost_milli=self.m_cost,
            accel_class=self.m_accel,
            energy_milli=self.m_energy,
        )

    def flush(self, donate: bool = True) -> DeviceSnapshot:
        """Return the device snapshot, applying pending row deltas.

        Dirty-row scatter indices are padded to the next power of FOUR so
        only O(log₄ N) distinct update programs are ever compiled — each
        distinct pad size is another XLA compile; out-of-range pad indices
        are dropped by the scatter.
        Capacity growth or first use forces a full upload (the cold-start
        path, SURVEY.md §5 failure recovery: device memory is a rebuildable
        cache). Global (non-row) fields changed without any dirty row
        (band allocation, eterm interning) refresh via a row-less scatter.

        Every device write advances the snapshot generation through a
        donation lease (seal → dispatch → install); concurrent readers
        keep gathering from their pinned (previous) generation throughout.
        `donate=False` routes row scatters through the alias-free variant
        (`_scatter_rows_safe`) — the anti-entropy audit uses it so a repair
        can never be corrupted by the in-place update path it is auditing.
        """
        t0 = time.monotonic()
        self._flush_what = None
        try:
            return self._flush_inner(donate=donate)
        finally:
            dt = time.monotonic() - t0
            if dt > 0.2:
                logger.warning(
                    "slow flush %.0f ms: %s", dt * 1e3, self._flush_what
                )

    def _flush_inner(self, donate: bool = True) -> DeviceSnapshot:
        masters = self._masters()
        if self._gen is None or self._content_invalid:  # graftlint: unguarded(gen rebinds only happen on flush paths, serialized by the cache lock this runs under)
            self._flush_what = "full upload (first use or content invalid)"
            if self._snap_shardings is not None:
                snap = jax.device_put(masters, self._snap_shardings)
            else:
                snap = jax.device_put(jax.tree.map(jnp.asarray, masters))
            self._full_upload = False
            self._content_invalid = False
            self._globals_dirty = False
            self._dirty_rows.clear()
            self._install_generation(snap)
            return snap
        if self._full_upload:
            # capacity growth (_grow): device content is still valid, only
            # some field SHAPES changed. Re-upload exactly those fields from
            # the (grown, content-preserving) masters and keep the rest —
            # a t_cap bump mid-burst then costs one [N, t_cap] transfer, not
            # the full ~2 s snapshot re-upload. Dirty rows stay pending: the
            # scatter below applies them to the kept fields (for re-uploaded
            # fields it rewrites values already present — harmless). The
            # merged generation SHARES the kept buffers with its
            # predecessor, so it installs shared_with_base: donation
            # treats the pair as one pin scope until the predecessor
            # retires.
            with self.donation_lease(donating=False) as dl:
                merged = {}
                reshaped = []
                for name in DeviceSnapshot._fields:
                    m = getattr(masters, name)
                    d = getattr(dl.snap, name)
                    if tuple(d.shape) != m.shape:
                        reshaped.append(name)
                        if self._snap_shardings is not None:
                            merged[name] = jax.device_put(
                                m, getattr(self._snap_shardings, name)
                            )
                        else:
                            merged[name] = jax.device_put(jnp.asarray(m))
                    else:
                        merged[name] = d
                dl.result = DeviceSnapshot(**merged)
                dl.shared = True  # kept fields are the base's own buffers
            self._full_upload = False
            self._flush_what = f"reshape upload of {reshaped}"
        if not self._dirty_rows:
            if not self._globals_dirty:
                return self._device
            rows = []
        else:
            rows = sorted(self._dirty_rows)
            self._dirty_rows.clear()
        self._globals_dirty = False
        # exactly TWO scatter program sizes (16 / 1024), chunking larger
        # sets: every distinct pad is an XLA compile, and the old
        # O(log4 N) pad ladder put those compiles in the measured window
        # the first time each size appeared. Both variants are warmable
        # at startup (warm_scatter_programs). Chunk dispatches pipeline
        # (async), so a large set still costs about one exchange.
        self._flush_what = (
            f"{(self._flush_what + ' + ') if self._flush_what else ''}"
            f"scatter of {len(rows)} dirty rows"
        )
        with self.donation_lease(donating=donate) as dl:
            snap = dl.snap
            first = True
            i = 0
            while first or i < len(rows):
                first = False
                chunk = rows[i : i + _SCATTER_PAD_BIG]
                i += _SCATTER_PAD_BIG
                snap = self._scatter_chunk(
                    snap, masters, chunk, donate=donate
                )
            dl.result = snap
        return snap

    def _scatter_chunk(  # graftlint: holds-generation-lease
        self,
        snap: DeviceSnapshot,
        masters: DeviceSnapshot,
        rows: list,
        pad: Optional[int] = None,
        donate: bool = True,
    ) -> DeviceSnapshot:
        # callers hold a donation lease (enforced by graftlint's donation
        # pass at every call site): `snap` is lease-scoped — the sealed
        # live buffers, or the lease's private copy when readers pin them
        if pad is None:
            pad = (
                _SCATTER_PAD_SMALL
                if len(rows) <= _SCATTER_PAD_SMALL
                else _SCATTER_PAD_BIG
            )
        n_cap = self.cfg.n_cap
        idx = np.full(pad, n_cap, np.int32)  # OOB pad rows -> dropped
        idx[: len(rows)] = rows
        sel = idx.clip(0, n_cap - 1)

        updates = DeviceSnapshot(
            **{
                name: (
                    getattr(masters, name)
                    if name in _GLOBAL_FIELDS
                    else np.ascontiguousarray(getattr(masters, name)[sel])
                )
                for name in DeviceSnapshot._fields
            }
        )
        # one device_put for the whole update pytree: the transfers
        # pipeline in a single exchange instead of one per field
        if self._rep_sharding is not None:
            sh = jax.tree.map(lambda _: self._rep_sharding, (idx, updates))
            idx_d, updates_d = jax.device_put((idx, updates), sh)
        else:
            idx_d, updates_d = jax.device_put((idx, updates))
        scatter = _scatter_rows if donate else _scatter_rows_safe
        return scatter(snap, idx_d, updates_d)

    def warm_scatter_programs(self) -> None:
        """Compile the scatter pad variants out-of-window (no-op scatters:
        all indices OOB-dropped), donating AND alias-free, plus the two
        padded audit gather programs and the copy-on-pin program — 7
        compiles at bring-up instead of mid-burst (or mid-audit under the
        cache lock: the first audit pass would otherwise pay the gather
        compiles while holding it). Call at component start, after the
        snapshot exists."""
        if self._gen is None:  # graftlint: unguarded(bring-up check: atomic ref read before any concurrent writer exists)
            self.flush()
        masters = self._masters()
        for donate in (True, False):
            with self.donation_lease(donating=donate) as dl:
                snap = self._scatter_chunk(
                    dl.snap, masters, [], pad=_SCATTER_PAD_SMALL,
                    donate=donate,
                )
                dl.result = self._scatter_chunk(
                    snap, masters, [], pad=_SCATTER_PAD_BIG, donate=donate
                )
        with self.pin_generation() as lease:
            if lease.snap is not None:
                for pad in (_SCATTER_PAD_SMALL, _SCATTER_PAD_BIG):
                    _gather_rows(lease.snap, np.zeros(pad, np.int32))
                # the copy program backs copy-on-pin donation: compile it
                # here, not the first time a reader overlaps a wave launch
                _copy_snapshot(lease.snap)

    def set_sharding(self, snap_shardings, replicated_sharding) -> None:
        """Adopt multi-chip placement (parallel/mesh.snapshot_shardings):
        row-major tensors shard over the mesh's node axis, update scatters
        replicate. Forces a fresh (sharded) upload."""
        self._snap_shardings = snap_shardings
        self._rep_sharding = replicated_sharding
        self.invalidate_device()

    @property
    def has_pending_updates(self) -> bool:
        """True when the host masters have diverged from an EXISTING device
        snapshot (flush would scatter or re-upload). Before the first flush
        there is no device state to be stale, so nothing is pending."""
        if self._device is None:
            return False
        return (
            # graftlint: unguarded(lock-free dirty peek by design: callers re-check under the cache lock before acting)
            bool(self._dirty_rows)
            or self._globals_dirty
            or self._full_upload
            or self._content_invalid
        )

    def mark_row_dirty(self, node_name: str) -> None:
        """Force a re-upload of one node row from the host masters. Used when
        a kernel-committed placement could NOT be replayed host-side (e.g.
        duplicate assume): the device row then holds occupancy the masters
        don't, and the next flush must overwrite it."""
        row = self._row_by_name.get(node_name)
        if row is not None:
            self._dirty_rows.add(row)

    def invalidate_device(self) -> None:
        """Device content unknowable (readback/kernel failure, resharding):
        the next flush re-uploads everything from the host masters."""
        self._full_upload = True
        self._content_invalid = True

    def swap_live_snapshot(self, snap: DeviceSnapshot) -> None:
        """Testing/fault-injection hook: install `snap` — typically the
        live snapshot with one field replaced — as a new generation that
        SHARES the remaining buffers with its predecessor (so a donating
        advance copies while any pin on the predecessor drains). The
        production write paths never call this; kernel outputs install
        through the wave launch's donation lease.

        (Design note, kept from the old `set_device_snapshot`: the wave
        kernel donates the input snapshot and returns it with batch
        commits applied; the scheduler replays the same commits into the
        host masters via cache assume → add_pod, so a subsequent row-set
        flush writes identical values — device and host stay convergent
        without a delta-add protocol, as long as replay happens before
        the next flush.)"""
        self._install_generation(snap, shared_with_base=True)

    # -- utilization / stranding columns (descheduler + tuner) ---------------

    def utilization_stats(self) -> "UtilizationStats":
        """Per-row utilization and stranded-capacity columns from the host
        masters — the fragmentation-score inputs (tuner/scoring.
        fragmentation_score) and the descheduler's candidate signal, read
        straight off the same aggregates the kernel's resource columns
        are scattered from (no second bookkeeping to drift). Pure numpy
        over the masters; caller holds the cache lock."""
        alloc = self.m_alloc.astype(np.int64)
        req = self.m_req.astype(np.int64)
        safe_alloc = np.maximum(alloc, 1)
        free = alloc - req
        # per-row utilization: max over resources of requested/allocatable
        # (the CA's node-utilization measure, matching the autoscaler's
        # host-side _utilization up to encoding quantization)
        util = np.where(alloc > 0, req / safe_alloc, 0.0).max(
            axis=1, initial=0.0
        )
        return UtilizationStats(
            valid=np.asarray(self.m_valid, bool).copy(),
            unschedulable=np.asarray(self.m_unsched, bool).copy(),
            used_any=(req > 0).any(axis=1) & np.asarray(self.m_valid, bool),
            util=np.asarray(util, np.float64),
            free_frac=np.clip(free / safe_alloc, 0.0, 1.0).mean(axis=1),
            cost_milli=self.m_cost.astype(np.int64).copy(),
        )

    # -- what-if simulation overlay (autoscaler) -----------------------------

    def free_row_indices(self) -> List[int]:
        """Row indices holding no live node (freed or never allocated), in
        ascending order — the rows a what-if overlay may claim for virtual
        candidate nodes without perturbing any live row."""
        used = {r for r, n in enumerate(self.row_names) if n is not None}
        return [r for r in range(self.cfg.n_cap) if r not in used]

    def whatif_overlay(
        self,
        virtual_nodes: List[v1.Node],
        mask_rows: Optional[List[int]] = None,
    ) -> Optional[Tuple[DeviceSnapshot, List[int]]]:
        """Copy-on-append simulation view of the snapshot: K VIRTUAL node
        rows (candidate machine shapes from the autoscaler's NodeGroup
        catalog) written into currently-free rows of a COPY of the live
        snapshot, plus `mask_rows` (scale-down drain what-if) flipped
        invalid. Returns (overlay_snapshot, rows) with rows[i] the row
        index assigned to virtual_nodes[i]; None when n_cap has no room
        for K more rows (the caller falls back to skipping the pass —
        growing n_cap here would recompile every kernel variant mid-run).

        Isolation contract (the generational successor of the PR-4
        donation discipline): the live snapshot is never mutated and
        never donated — the overlay is produced by the alias-free
        `_scatter_rows_safe` program, so every buffer of the returned
        snapshot is fresh; the overlay is never installed as a live
        generation and must never be handed to a donating program. The
        device section holds a generation PIN, not a lock: the scatter
        READS the pinned generation's buffers, which a concurrent wave
        launch cannot donate (it advances through a copy instead), so a
        what-if pass may overlap wave launches freely.

        Caller must hold the cache lock (vocab interning + the masters
        read must be consistent with row_names)."""
        mask_rows = list(mask_rows or [])
        free = self.free_row_indices()
        if len(virtual_nodes) > len(free):
            return None
        rows = free[: len(virtual_nodes)]
        # intern first: virtual labels/taints can grow capacities (shapes
        # change), which must settle before the base snapshot is chosen
        encoded = [self.encode_node_row_values(n) for n in virtual_nodes]
        masters = self._masters()
        with self.pin_generation() as lease:
            if lease.snap is not None and not self.has_pending_updates:
                # steady state: the live snapshot is current — the overlay
                # costs one padded row scatter, not a full upload. (When a
                # wave pipeline is in flight the device may additionally
                # hold kernel commits the masters haven't replayed yet;
                # the device view is then the MORE current base.)
                base = lease.snap
            elif self._snap_shardings is not None:
                base = jax.device_put(masters, self._snap_shardings)
            else:
                base = jax.device_put(jax.tree.map(jnp.asarray, masters))
            all_rows = rows + mask_rows
            out = base
            for i0 in range(0, max(len(all_rows), 1), _SCATTER_PAD_BIG):
                chunk = all_rows[i0 : i0 + _SCATTER_PAD_BIG]
                pad = (
                    _SCATTER_PAD_SMALL
                    if len(chunk) <= _SCATTER_PAD_SMALL
                    else _SCATTER_PAD_BIG
                )
                idx = np.full(pad, self.cfg.n_cap, np.int32)  # OOB dropped
                idx[: len(chunk)] = chunk
                upd = {}
                for name in DeviceSnapshot._fields:
                    m = getattr(masters, name)
                    if name in _GLOBAL_FIELDS:
                        upd[name] = m
                        continue
                    arr = np.zeros((pad,) + m.shape[1:], m.dtype)
                    for j, row in enumerate(chunk):
                        vi = i0 + j
                        if vi < len(rows):
                            # virtual row: node-static encoded values; the
                            # pod-aggregate columns stay zero (empty node)
                            v = encoded[vi].get(name)
                            if v is not None:
                                arr[j] = v
                        else:
                            # masked row: live values with valid cleared
                            arr[j] = m[row]
                            if name == "valid":
                                arr[j] = False
                    upd[name] = arr
                updates = DeviceSnapshot(**upd)
                if self._rep_sharding is not None:
                    sh = jax.tree.map(
                        lambda _: self._rep_sharding, (idx, updates)
                    )
                    idx_d, updates_d = jax.device_put((idx, updates), sh)
                else:
                    idx_d, updates_d = jax.device_put((idx, updates))
                out = _scatter_rows_safe(out, idx_d, updates_d)
        return out, rows


class UtilizationStats(NamedTuple):
    """Per-row utilization/stranding columns (SnapshotEncoder.
    utilization_stats): [N]-aligned with row_names. free_frac is the
    mean free/allocatable fraction per row — the stranded-capacity unit
    the fragmentation score sums; util is the CA-style max-over-resources
    requested/allocatable the descheduler thresholds candidates on."""

    valid: np.ndarray  # [N] bool — row holds a live node
    unschedulable: np.ndarray  # [N] bool — cordoned
    used_any: np.ndarray  # [N] bool — valid and hosting any request
    util: np.ndarray  # [N] float — max req/alloc over resources
    free_frac: np.ndarray  # [N] float — mean free/alloc over resources
    cost_milli: np.ndarray  # [N] int64 — $/h * 1000 (0 unlabeled)


# Fields of DeviceSnapshot that are NOT [N, ...] row-major (global metadata
# columns, replaced wholesale on flush instead of row-scattered).
_GLOBAL_FIELDS = frozenset({"eterm_topo_key", "eterm_kind", "band_prio"})

# The only two dirty-row scatter program sizes (see flush): small for the
# low-load trickle, big for storm/churn sets; larger sets chunk by big.
_SCATTER_PAD_SMALL = 16
_SCATTER_PAD_BIG = 1024


@jax.jit
def _gather_rows(snap: DeviceSnapshot, idx) -> dict:
    """Row gather of every row-major field (the anti-entropy audit's read
    side). idx is padded to one of the two scatter program sizes, so at
    most two gather programs ever compile."""
    return {
        name: jnp.take(getattr(snap, name), idx, axis=0)
        for name in SnapshotEncoder.ROW_FIELDS
    }


def _scatter_rows_impl(
    snap: DeviceSnapshot, idx, updates: DeviceSnapshot
) -> DeviceSnapshot:
    out = {}
    for name in DeviceSnapshot._fields:
        dst = getattr(snap, name)
        src = getattr(updates, name)
        if name in _GLOBAL_FIELDS:
            out[name] = src
        else:
            out[name] = dst.at[idx].set(src, mode="drop")
    return DeviceSnapshot(**out)


# hot path: donation lets XLA update the snapshot in place (no O(snapshot)
# copy per flush — the wave cadence depends on it)
_scatter_rows = functools.partial(jax.jit, donate_argnums=(0,))(_scatter_rows_impl)

# repair path: NO donation. The anti-entropy auditor's settle/repair
# scatters go through this variant: the PR-4 corruption (a donating
# executable deserialized from a persistent compilation cache writing
# garbage into non-targeted rows on CPU) hit exactly when donation
# aliased buffers a concurrent reader observed — gone structurally now
# that donation only ever consumes lease-private buffers, but the
# repairer still must not use the in-place update path it is auditing,
# so it pays the copy and gets fresh, alias-free output buffers. The
# marker below is machine-checked: graftlint fails if a donation keyword
# ever lands on this definition.
_scatter_rows_safe = jax.jit(_scatter_rows_impl)  # graftlint: alias-safe


def _copy_snapshot_impl(snap: DeviceSnapshot) -> DeviceSnapshot:
    # arithmetic identities, not `lambda x: x`: a jitted identity can
    # alias output to input, and an aliased "copy" would hand the donor
    # the very buffers the pin protects. Real ops allocate fresh output
    # buffers (no donation on this program, enforced by the marker below).
    def cp(a):
        if a.dtype == jnp.bool_:
            return jnp.logical_or(a, jnp.zeros((), jnp.bool_))
        return a + jnp.zeros((), a.dtype)

    return jax.tree.map(cp, snap)


# copy-on-pin: when a reader pins generation N, a donating wave launch
# consumes a fresh copy instead of the pinned buffers (DonationLease).
# NOT donating by construction — the whole point is fresh output buffers.
_copy_snapshot = jax.jit(_copy_snapshot_impl)  # graftlint: alias-safe


# lockset sanitizer (testing/lockgraph.py Eraser mode): the encoder's
# host bookkeeping is guarded by the CALLER's `scheduler.cache` lock
# (graftlint pass 6 infers the map; `--list-guards` prints it) and the
# generation table by `encoder.gen_lock`. Deliberately NOT tracked:
# `_gen` and the dirty flags, whose lock-free peeks are pragma'd
# `unguarded` in place — tracking them would indict the documented
# atomic-read design, not a bug.
track_attrs(
    SnapshotEncoder,
    "_retiring",
    "_next_gen_id",
    "_free_rows",
    "_pods",
    "_row_by_name",
    "row_names",
    "suspect_rows",
    "_flush_what",
)
