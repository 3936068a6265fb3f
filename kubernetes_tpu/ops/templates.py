"""Template-deduplicated pod batches.

Real scheduling bursts are template-shaped: a Deployment/Job stamps out
thousands of pods differing only in name (the reference's scheduler_perf
configs generate exactly this). Encoding every pod separately wastes host
CPU and uplink bytes; instead the batch is (unique templates → full device
encoding) + (per-pod: template id, priority, pinned-node row). For a 5000-pod
burst of one Deployment this turns ~3 MB of per-pod tensors into a few KB.

The template fingerprint covers every spec field the device encoding reads;
pods whose fingerprint misses the cache fall back to fresh encoding (and the
cache is invalidated when the encoder's vocabularies grow, since interned ids
inside an encoded template would go stale)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..api import objects as v1
from .batch import EncodedBatch, encode_pod_batch
from .encoding import PodBatch, SnapshotEncoder


def _own_selector_matches(pod: v1.Pod) -> Tuple:
    """Whether each of the pod's OWN term selectors matches its own labels
    (the encodings' aff_self/spr_self bits), in deterministic term order."""
    labels = pod.metadata.labels
    out = []
    aff = pod.spec.affinity
    if aff is not None:
        for pa in (aff.pod_affinity, aff.pod_anti_affinity):
            if pa is None:
                continue
            for term in pa.required:
                sel = term.label_selector
                out.append(sel is not None and sel.matches(labels))
            for wt in pa.preferred:
                sel = wt.term.label_selector
                out.append(sel is not None and sel.matches(labels))
    for c in pod.spec.topology_spread_constraints:
        sel = c.label_selector
        out.append(sel is not None and sel.matches(labels))
    return tuple(out)


def _label_masks(encoder: SnapshotEncoder, ns: str, labels) -> Tuple:
    """(len_sel, len_eterm, sel_mask, eterm_mask): which interned
    predicates match these labels, stamped with the vocab lengths so
    growth never aliases masks across vocab versions. THE single source
    for both the direct and the memoized fingerprint paths."""
    sel_mask = 0
    for i, pred in enumerate(encoder.sel_vocab.items):
        if pred.matches(ns, labels):
            sel_mask |= 1 << i
    et_mask = 0
    for i, et in enumerate(encoder.eterm_vocab.items):
        if et.predicate.matches(ns, labels):
            et_mask |= 1 << i
    return (len(encoder.sel_vocab), len(encoder.eterm_vocab), sel_mask, et_mask)


def _label_effect_key(encoder: SnapshotEncoder, pod: v1.Pod) -> Tuple:
    """Labels as the ENCODING sees them: which interned predicates (selector
    vocab + existing-pod term vocab) match, plus the pod's own-term
    self-matches. Two pods whose labels differ only in ways no predicate
    observes — e.g. 300 gangs distinguished solely by a group-name label —
    collapse to one template instead of 300 (each extra template count is
    another XLA variant; a 15k-pod gang burst compiled per batch without
    this)."""
    return (
        ("enc",)
        + _label_masks(encoder, pod.metadata.namespace, pod.metadata.labels)
        + (_own_selector_matches(pod),)
    )


def pod_fingerprint(pod: v1.Pod, encoder: Optional[SnapshotEncoder] = None) -> Tuple:
    """Structural key over every field the device encoding depends on.

    Everything here is hashable: dataclasses in api/objects.py that feed the
    encoder are frozen, labels/node_selector collapse to frozensets. With an
    encoder, raw labels are replaced by their encoded effect (see
    _label_effect_key) so scheduling-irrelevant label diversity doesn't
    multiply templates."""
    spec = pod.spec
    containers = tuple(
        (
            tuple(sorted(c.requests.items())),
            c.image,
            tuple((p.host_ip, p.protocol, p.host_port) for p in c.ports),
        )
        for c in spec.containers
    )
    inits = tuple(tuple(sorted(c.requests.items())) for c in spec.init_containers)
    ctrl = next(
        (
            (r.kind, r.name)
            for r in pod.metadata.owner_references
            if r.controller
        ),
        None,
    )
    volumes = tuple(
        (
            v.persistent_volume_claim,
            v.gce_persistent_disk,
            v.aws_elastic_block_store,
            v.iscsi,
            v.rbd,
            v.azure_disk,
            v.cinder,
        )
        for v in spec.volumes
        if v.persistent_volume_claim
        or v.gce_persistent_disk
        or v.aws_elastic_block_store
        or v.iscsi
        or v.rbd
        or v.azure_disk
        or v.cinder
    )
    return (
        pod.metadata.namespace,
        (
            _label_effect_key(encoder, pod)
            if encoder is not None
            else frozenset(pod.metadata.labels.items())
        ),
        containers,
        inits,
        tuple(sorted(spec.overhead.items())),
        frozenset(spec.node_selector.items()),
        spec.affinity,
        tuple(spec.tolerations),
        tuple(spec.topology_spread_constraints),
        ctrl,
        spec.scheduler_name,
        volumes,
    )


class TemplateBatch(NamedTuple):
    """Device-side batch: template tensors + per-pod indirection."""

    tpl: PodBatch  # [TPL, ...] template encodings
    pod_tpl: jnp.ndarray  # [P] int32 template index (-1 = invalid row)
    pod_valid: jnp.ndarray  # [P] bool
    pod_name_row: jnp.ndarray  # [P] int32 pinned node row (-1 none, -2 unknown)
    pod_prio: jnp.ndarray  # [P] int32
    pod_band: jnp.ndarray  # [P] int32 priority band (prio_req commit target)


@dataclass
class EncodedTemplateBatch:
    batch: TemplateBatch
    pods: List[v1.Pod]
    fallback: np.ndarray  # [P] bool (template overflowed device buckets)
    num_templates: int
    tpl_np: Optional[PodBatch] = None  # host mirror of batch.tpl (no D2H)
    # host mirrors of per-pod arrays: failure paths read these, and a
    # device_get of host-originated data would be a pointless device sync
    pod_tpl_np: Optional[np.ndarray] = None
    pod_prio_np: Optional[np.ndarray] = None
    pod_band_np: Optional[np.ndarray] = None


class TemplateCache:
    """fingerprint → row in a persistent template PodBatch.

    Encoded template rows embed interned vocabulary ids, so the cache is
    keyed to the encoder generation of its vocabularies: any growth in the
    relevant vocabularies invalidates (conservatively, any generation bump
    that changed vocab sizes)."""

    def __init__(self, encoder: SnapshotEncoder, max_templates: int = 64):
        self.encoder = encoder
        self.max_templates = max_templates
        # bumped by the scheduler when template-relevant state changes
        # WITHOUT growing a vocab (service delete/retarget: the match_svc
        # masks must rebuild even though fingerprints alone can't see it)
        self.extra_sig = 0
        self._rows: Dict[Tuple, int] = {}
        self._exemplars: List[v1.Pod] = []
        self._fallback: List[bool] = []
        # bumped whenever the fingerprint->row mapping changes (new
        # template, churn rebuild, vocab-growth clear): consumers caching
        # per-template-set derivations (the scheduler's pair table) key on
        # it so a DIFFERENT set with coincidentally equal count + vocab
        # sizes cannot alias a stale cache entry
        self.rows_gen = 0
        self._tpl_batch_np: Optional[PodBatch] = None
        self._vocab_sig = self._sig()
        self._label_memo: Dict[Tuple, Tuple] = {}
        self._label_memo_sig = (0, 0)
        # per-pod fingerprint memo: an unschedulable-storm batch re-encodes
        # the SAME pods every cycle (a full cluster retries thousands of
        # pending pods per event), and the per-pod tuple build in
        # pod_fingerprint was the dominant tpl-encode cost. (uid, rv)
        # uniquely identifies pod content (the API bumps rv on every
        # write); the epoch ties an entry to the vocab state its
        # fingerprint embedded.
        self._fp_memo: Dict[str, Tuple] = {}
        self._fp_epoch = 0
        self._fp_epoch_sig: Tuple = self._vocab_sig

    def _sig(self) -> Tuple:
        e = self.encoder
        return (
            len(e.key_vocab),
            len(e.val_vocab),
            len(e.sel_vocab),
            len(e.eterm_vocab),
            len(e.port_vocab),
            len(e.image_vocab),
            len(e.avoid_vocab),
            len(e.res_vocab),
            e.cfg,
            self.extra_sig,
        )

    def _fingerprint(self, pod: v1.Pod) -> Tuple:
        """pod_fingerprint with the label-effect masks memoized by
        (namespace, labels): a burst's pods repeat a handful of label sets
        thousands of times, and the per-pod vocab scans in
        _label_effect_key dominated tpl-encode."""
        key = (
            pod.metadata.namespace,
            tuple(sorted(pod.metadata.labels.items())),
        )
        memo = self._label_memo
        eff = memo.get(key)
        if eff is None:
            if len(memo) > 4096:
                memo.clear()  # unbounded label diversity: cap the memo
            eff = memo[key] = _label_masks(
                self.encoder, pod.metadata.namespace, pod.metadata.labels
            )
        fp = pod_fingerprint(pod, None)
        # splice the memoized effect key in place of the raw-labels slot
        # (index 1 — see pod_fingerprint's tuple layout)
        return (
            fp[0],
            ("enc",) + eff + (_own_selector_matches(pod),),
        ) + fp[2:]

    def _memo_valid(self) -> bool:
        return self._label_memo_sig == (
            len(self.encoder.sel_vocab),
            len(self.encoder.eterm_vocab),
        )

    def encode(
        self, pods: Sequence[v1.Pod], pad_to: Optional[int] = None
    ) -> EncodedTemplateBatch:
        P = pad_to or max(1, len(pods))
        assert len(pods) <= P
        # Fingerprint + encode to a FIXED POINT of the vocabularies:
        # encoding a batch's templates can intern new predicates (a pod's
        # own affinity terms), and fingerprints taken BEFORE that interning
        # may have collapsed pods the new predicate distinguishes — the
        # kernel would then see one pod wearing another's label masks.
        # Vocabs only grow and re-encoding the same exemplars interns
        # nothing new, so this converges in <= 2 extra passes.
        for _ in range(4):
            sig0 = self._sig()
            if not self._memo_valid():
                # vocab grew: every memoized mask is stale
                self._label_memo.clear()
                self._label_memo_sig = (
                    len(self.encoder.sel_vocab),
                    len(self.encoder.eterm_vocab),
                )
            if sig0 != self._fp_epoch_sig:
                self._fp_epoch += 1
                self._fp_epoch_sig = sig0
            memo, epoch = self._fp_memo, self._fp_epoch
            fps = []
            for p in pods:
                uid = p.metadata.uid
                ent = memo.get(uid) if uid else None
                if (
                    ent is not None
                    and ent[0] == p.metadata.resource_version
                    and ent[1] == epoch
                ):
                    fps.append(ent[2])
                    continue
                fp = self._fingerprint(p)
                if uid:
                    if len(memo) > 65536:
                        memo.clear()
                    memo[uid] = (p.metadata.resource_version, epoch, fp)
                fps.append(fp)
            changed = False
            for pod, fp in zip(pods, fps):
                if fp not in self._rows:
                    self._rows[fp] = len(self._exemplars)
                    self._exemplars.append(pod)
                    changed = True
            if len(self._exemplars) > self.max_templates:
                # template churn: rebuild the cache from this batch's
                # templates only (rare; steady workloads have a stable set)
                first_by_fp: Dict[Tuple, v1.Pod] = {}
                for pod, fp in zip(pods, fps):
                    first_by_fp.setdefault(fp, pod)
                uniq = list(first_by_fp)
                self._rows = {fp: i for i, fp in enumerate(uniq)}
                self._exemplars = [first_by_fp[fp] for fp in uniq]
                changed = True

            if changed:
                self.rows_gen += 1
            if self._sig() != self._vocab_sig or changed:
                # (re-)encode every template with current vocabularies
                eb = encode_pod_batch(
                    self.encoder,
                    self._exemplars,
                    pad_to=self._pad(len(self._exemplars)),
                )
                self._vocab_sig = self._sig()
                self._tpl_batch = eb.batch
                self._tpl_batch_np = eb.batch_np
                self._fallback = list(eb.fallback[: len(self._exemplars)])
            if self._sig() == sig0:
                break  # no interning this pass: fingerprints are current
            # interning happened: vocab lengths are embedded in every
            # fingerprint, so EVERY cached row is now dead weight — drop
            # them and rebuild from this batch (other batches' templates
            # re-register on their next encode)
            self._rows = {}
            self._exemplars = []
            self._fallback = []
            self.rows_gen += 1

        pod_tpl = np.full(P, -1, np.int32)
        pod_valid = np.zeros(P, np.bool_)
        pod_name_row = np.full(P, -1, np.int32)
        pod_prio = np.zeros(P, np.int32)
        pod_band = np.zeros(P, np.int32)
        fallback = np.zeros(P, np.bool_)
        for i, (pod, fp) in enumerate(zip(pods, fps)):
            t = self._rows[fp]
            fb = self._fallback[t] if t < len(self._fallback) else False
            pod_tpl[i] = t
            # fallback pods run the host path; they must be INVALID to the
            # kernel, else its finalize commits their occupancy on-device
            # for a placement the host will make differently (device drift)
            pod_valid[i] = not fb
            pod_prio[i] = pod.priority
            pod_band[i] = self.encoder._band_of(pod.priority)
            if pod.spec.node_name:
                row = self.encoder.row_of(pod.spec.node_name)
                pod_name_row[i] = row if row >= 0 else -2
            fallback[i] = fb
        # per-pod arrays stay numpy: they ride the kernel DISPATCH as its
        # host->device transfer instead of paying a separate device_put
        # exchange (one less sync point per cycle)
        batch = TemplateBatch(
            tpl=self._tpl_batch,
            pod_tpl=pod_tpl,
            pod_valid=pod_valid,
            pod_name_row=pod_name_row,
            pod_prio=pod_prio,
            pod_band=pod_band,
        )
        return EncodedTemplateBatch(
            batch=batch,
            pods=list(pods),
            fallback=fallback,
            num_templates=len(self._exemplars),
            tpl_np=self._tpl_batch_np,
            pod_tpl_np=pod_tpl,
            pod_prio_np=pod_prio,
            pod_band_np=pod_band,
        )

    @staticmethod
    def _pad(n: int) -> int:
        p = 4
        while p < n:
            p *= 2
        return p

    def match_sel_row(self, pod_index_in_batch_tpl: int) -> np.ndarray:
        """Host mirror of a template's predicate match vector (for assume)."""
        return np.asarray(self._tpl_batch_np.match_sel[pod_index_in_batch_tpl])


class PairTable(NamedTuple):
    """Topology (predicate, key) pairs referenced by a batch.

    A "pair" is one (count column, topology key) combination the kernel needs
    domain sums for: spread constraints, incoming required/preferred
    (anti-)affinity terms (column = interned predicate sid), and existing-pod
    anti-affinity terms matched by batch pods (column = eterm id). Domain sums
    are computed ONCE per pair per batch instead of once per pod — the key
    restructuring that removes the per-pod segment-sum cost.

    The pair axis J is the smallest rung of PAIR_SLOT_LADDER's rule (1, 4,
    16, 64, ...) that holds the pairs the templates reference: Stage A's
    segment scatters and gathers cost a TPU one serial update per [J, N]
    element, used or not. The slots past the last pair are dead (col -1, no
    template's *_pair points at them, contrib 0, etm_match False); a
    template set that crosses a rung compiles the kernel once a bucket.
    """

    is_eterm: jnp.ndarray  # [J] bool (column indexes eterm_w vs sel_counts)
    col: jnp.ndarray  # [J] int32 column id, -1 pad
    key: jnp.ndarray  # [J] int32 topology key id
    elig_tpl: jnp.ndarray  # [J] int32 template whose node-affinity gates
    #                        eligibility (spread), -1 = all valid nodes
    kind: jnp.ndarray  # [J] int32 eterm kind or -1 for sid pairs
    contrib: jnp.ndarray  # [TPL, J] f32 contribution of a template pod
    # per-template pair references (-1 = unused slot)
    spr_pair: jnp.ndarray  # [TPL, C]
    spr_skew: jnp.ndarray  # [TPL, C] f32
    spr_hard: jnp.ndarray  # [TPL, C] bool
    spr_self: jnp.ndarray  # [TPL, C] bool
    aff_pair: jnp.ndarray  # [TPL, A]
    aff_self: jnp.ndarray  # [TPL, A] bool
    anti_pair: jnp.ndarray  # [TPL, B]
    pref_pair: jnp.ndarray  # [TPL, PW]
    pref_w: jnp.ndarray  # [TPL, PW] f32
    etm_match: jnp.ndarray  # [TPL, J] bool — template pod matches pair's
    #                         eterm predicate (filter/scoring vs existing pods)


PAIR_SLOT_LADDER = 4  # each rung of the pair axis is this many times the last


def pair_slots(n_pairs: int) -> int:
    """The pair axis for `n_pairs` pairs: 1, 4, 16, 64, ... At least one
    slot, because the kernel clips pair indices to J - 1 and its segment
    operations need a non-empty axis."""
    j = 1
    while j < n_pairs:
        j *= PAIR_SLOT_LADDER
    return j


def build_pair_table(
    enc: SnapshotEncoder, tpl_batch: PodBatch, num_templates: int
) -> PairTable:
    """Host-side pair dedup over a template batch.

    `tpl_batch` must be the host (numpy) mirror — passing device arrays here
    would pay a device round trip per field."""
    b = jax.tree.map(np.asarray, tpl_batch)
    TPL = b.spread_sid.shape[0]
    pairs: Dict[Tuple, int] = {}

    def intern(is_et: bool, col: int, key: int, elig: int, kind: int) -> int:
        k = (is_et, col, key, elig)
        j = pairs.get(k)
        if j is None:
            j = len(pairs)
            pairs[k] = j
        return j

    C = b.spread_sid.shape[1]
    A = b.paff_sid.shape[1]
    B = b.panti_sid.shape[1]
    PW = b.ppref_sid.shape[1]
    spr_pair = np.full((TPL, C), -1, np.int32)
    aff_pair = np.full((TPL, A), -1, np.int32)
    anti_pair = np.full((TPL, B), -1, np.int32)
    pref_pair = np.full((TPL, PW), -1, np.int32)

    for t in range(num_templates):
        for c in range(C):
            sid, key = int(b.spread_sid[t, c]), int(b.spread_key[t, c])
            if key >= 0 and sid >= 0:
                spr_pair[t, c] = intern(False, sid, key, t, -1)
        for a in range(A):
            sid, key = int(b.paff_sid[t, a]), int(b.paff_key[t, a])
            if sid >= 0:
                aff_pair[t, a] = intern(False, sid, key, -1, -1)
        for bb in range(B):
            sid, key = int(b.panti_sid[t, bb]), int(b.panti_key[t, bb])
            if sid >= 0:
                anti_pair[t, bb] = intern(False, sid, key, -1, -1)
        for w in range(PW):
            sid, key = int(b.ppref_sid[t, w]), int(b.ppref_key[t, w])
            if sid >= 0:
                pref_pair[t, w] = intern(False, sid, key, -1, -1)
        for tid in range(len(enc.eterm_vocab)):
            if b.match_eterm[t, tid]:
                et = enc.eterm_vocab.items[tid]
                intern(True, tid, et.topo_key_id, -1, et.kind)

    j_cap = pair_slots(len(pairs))
    is_eterm = np.zeros(j_cap, np.bool_)
    col = np.full(j_cap, -1, np.int32)
    key_arr = np.zeros(j_cap, np.int32)
    elig = np.full(j_cap, -1, np.int32)
    kind = np.full(j_cap, -1, np.int32)
    for (et, c, k, e), j in pairs.items():
        is_eterm[j] = et
        col[j] = c
        key_arr[j] = k
        elig[j] = e
        # kind recorded below for eterm pairs
    for (et, c, k, e), j in pairs.items():
        if et:
            kind[j] = enc.eterm_vocab.items[c].kind

    contrib = np.zeros((TPL, j_cap), np.float32)
    etm_match = np.zeros((TPL, j_cap), np.bool_)
    for t in range(num_templates):
        for (et, c, k, e), j in pairs.items():
            if et:
                etm_match[t, j] = bool(b.match_eterm[t, c])
                contrib[t, j] = float(b.eterm_add[t, c])
            else:
                if c < b.match_sel.shape[1]:
                    contrib[t, j] = 1.0 if b.match_sel[t, c] else 0.0

    table = PairTable(
        is_eterm=jnp.asarray(is_eterm),
        col=jnp.asarray(col),
        key=jnp.asarray(key_arr),
        elig_tpl=jnp.asarray(elig),
        kind=jnp.asarray(kind),
        contrib=jnp.asarray(contrib),
        spr_pair=jnp.asarray(spr_pair),
        spr_skew=jnp.asarray(b.spread_skew.astype(np.float32)),
        spr_hard=jnp.asarray(b.spread_hard),
        spr_self=jnp.asarray(b.spread_self),
        aff_pair=jnp.asarray(aff_pair),
        aff_self=jnp.asarray(b.paff_self),
        anti_pair=jnp.asarray(anti_pair),
        pref_pair=jnp.asarray(pref_pair),
        pref_w=jnp.asarray(b.ppref_w),
        etm_match=jnp.asarray(etm_match),
    )
    return table
