"""Scheduler cache: aggregated live cluster state + assume/expire protocol.

Host twin of reference pkg/scheduler/internal/cache/cache.go:59 with the
TPU-critical addition: every mutation is forwarded to the columnar
SnapshotEncoder, so the HBM-resident snapshot is the same delta stream the
host NodeInfos see (the generation-number incremental-snapshot idea of
UpdateSnapshot, cache.go:203-303, realised as device scatters).

Assume protocol (cache.go:344 AssumePod / FinishBinding / ForgetPod, 30s TTL
wired at scheduler.go:240): optimistic placement before the API bind lands;
confirmed by the informer's scheduled-pod Add, expired by a janitor loop.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

from ...api import objects as v1
from ...ops.encoding import EncodingConfig, SnapshotEncoder
from ...testing.lockgraph import named_lock, track_attrs
from ...utils.metrics import metrics
from ...utils.tracing import note_pass
from .nodeinfo import NodeInfo, Snapshot, _has_affinity

logger = logging.getLogger("kubernetes_tpu.scheduler.cache")


@dataclass
class _AssumedInfo:
    pod: v1.Pod
    node_name: str
    deadline: Optional[float]  # None until finish_binding arms the TTL


class SchedulerCache:
    def __init__(
        self,
        ttl_seconds: float = 30.0,
        encoder: Optional[SnapshotEncoder] = None,
        encoding_config: Optional[EncodingConfig] = None,
    ):
        # named for the lock-order watchdog (testing/lockgraph.py): the
        # cache lock orders BEFORE the encoder's generation bookkeeping
        # lock (encoder.gen_lock), everywhere
        self.lock = named_lock("scheduler.cache")
        self._nodes: Dict[str, NodeInfo] = {}
        self._pod_to_node: Dict[str, str] = {}
        # pods scheduled to nodes the cache hasn't seen yet (informer start
        # races the node list; WAL recovery replays pods first): parked
        # here and replayed into the NodeInfo + encoder when the node
        # arrives — the reference's implicit-NodeInfo reconcile
        # (internal/cache/cache.go AddPod on an unknown node)
        self._orphans: Dict[str, Dict[str, v1.Pod]] = {}
        self._assumed: Dict[str, _AssumedInfo] = {}
        self._ttl = ttl_seconds
        self.encoder = encoder or SnapshotEncoder(encoding_config)
        self._generation = 0
        # informer-driven mutations only (node spec changes, foreign pod
        # add/remove) — scheduler assumes don't move it. The oracle guard
        # compares per-node ext_generation against its launch capture to
        # tell post-launch churn from kernel corruption.
        self._ext_generation = 0
        # name -> last handed-out clone (generation-tagged) for the
        # incremental update_snapshot below
        self._snap_clones: Dict[str, NodeInfo] = {}
        self._stop = threading.Event()
        self._janitor: Optional[threading.Thread] = None

    # -- nodes --------------------------------------------------------------

    def add_node(self, node: v1.Node) -> None:
        with self.lock:
            name = node.metadata.name
            ni = self._nodes.get(name)
            if ni is None:
                ni = NodeInfo(node)
                self._nodes[name] = ni
            else:
                ni.set_node(node)
            self._bump(ni)
            self._bump_ext(name)
            self.encoder.add_node(node)
            # replay pods that arrived before their node did
            for pod in self._orphans.pop(name, {}).values():
                ni.add_pod(pod)
                self.encoder.add_pod(name, pod)

    def update_node(self, node: v1.Node) -> None:
        self.add_node(node)

    def remove_node(self, node_name: str) -> None:
        with self.lock:
            self._nodes.pop(node_name, None)
            self.encoder.remove_node(node_name)
            self._generation += 1

    # -- pods ---------------------------------------------------------------

    def add_pod(self, pod: v1.Pod) -> None:
        """A scheduled pod appeared via the informer. Confirms the assume if
        one is outstanding (expired assumes re-add cleanly)."""
        key = pod.metadata.key
        with self.lock:
            a = self._assumed.pop(key, None)
            if a is not None:
                if a.node_name == pod.spec.node_name:
                    # confirmation: host+device state already reflect it;
                    # swap the stored pod for the API's copy
                    ni = self._nodes.get(a.node_name)
                    if ni is not None:
                        ni.remove_pod(key)
                        ni.add_pod(pod)
                        self._bump(ni)
                    else:
                        # node vanished mid-bind: park for a possible re-add
                        self._orphans.setdefault(a.node_name, {})[key] = pod
                    self._pod_to_node[key] = pod.spec.node_name
                    return
                # scheduled somewhere else than assumed: undo and re-add
                self._remove_pod_internal(key, a.node_name)
                self._bump_ext(a.node_name)
            elif key in self._pod_to_node:
                # re-delivered add (an informer Replace relist after a
                # watch flap replays every listed object): treat as an
                # update — NodeInfo/encoder appends don't dedup, so a
                # blind re-add would double-count the pod's resources
                self._remove_pod_internal(key, self._pod_to_node[key])
            # _add_pod_internal stamps ext_generation (device_synced
            # defaults False) — it is the single stamping point for adds
            self._add_pod_internal(pod)

    def update_pod(self, pod: v1.Pod) -> None:
        key = pod.metadata.key
        with self.lock:
            if key in self._assumed and pod.spec.node_name:
                # bind confirmation arriving as an UPDATE event (the usual
                # shape: unscheduled -> scheduled MODIFIED): route through
                # add_pod's confirmation branch instead of remove+re-add —
                # the re-add would dirty the node row and force a full-row
                # re-upload at the next flush for state the device already
                # holds (the kernel committed it). Only for updates that
                # CARRY a node: an unscheduled-shaped update of an assumed
                # pod must not consume the assume (add_pod's mismatch
                # branch would free the node and strand the pod)
                self.add_pod(pod)
                return
            old_node = self._pod_to_node.get(key)
            if old_node is not None:
                self._remove_pod_internal(key, old_node)
                self._bump_ext(old_node)
            if pod.spec.node_name:
                # ext stamped inside _add_pod_internal
                self._add_pod_internal(pod)

    def remove_pod(self, pod: v1.Pod) -> None:
        key = pod.metadata.key
        with self.lock:
            self._assumed.pop(key, None)
            node = self._pod_to_node.get(key)
            if node is not None:
                self._remove_pod_internal(key, node)
                self._bump_ext(node)

    def _add_pod_internal(
        self,
        pod: v1.Pod,
        device_synced: bool = False,
        prio_band: Optional[int] = None,
        proto: Optional[tuple] = None,
    ) -> None:
        node = pod.spec.node_name
        ni = self._nodes.get(node)
        if ni is None:
            # pod on a node the cache hasn't seen: park it for add_node's
            # replay (update_node races and recovery both hit this)
            self._pod_to_node[pod.metadata.key] = node
            self._orphans.setdefault(node, {})[pod.metadata.key] = pod
            return
        ni.add_pod(pod)
        self._bump(ni)
        if not device_synced:
            # host-path assumes (and informer adds) are occupancy no
            # in-flight device batch has seen: stamp ext_generation so
            # the oracle guard skips the node (node_churn) instead of
            # reading the unseen pod as kernel corruption and falsely
            # latching the device path off. Device-synced (wave) assumes
            # must NOT stamp — their chain saw the placement, so an
            # oracle disagreement there stays a real signal.
            self._bump_ext(node)
        self._pod_to_node[pod.metadata.key] = node
        self.encoder.add_pod(
            node, pod, device_synced=device_synced, prio_band=prio_band,
            proto=proto,
        )

    def _remove_pod_internal(self, key: str, node: str) -> None:
        ni = self._nodes.get(node)
        if ni is not None:
            if ni.remove_pod(key) is not None:
                self._bump(ni)
        # encoder removal is deliberately NOT gated on the NodeInfo still
        # holding the pod: after a host/device divergence (a mid-wave
        # encoder failure unwound the NodeInfo but the entry survived, or
        # vice versa) the gated form leaked phantom device occupancy
        # forever — cleanup_expired would revert the host NodeInfo while
        # the encoder row kept counting the expired assume. remove_pod is
        # a no-op when the encoder has no row/entry for the key.
        self.encoder.remove_pod(node, key)
        orphans = self._orphans.get(node)
        if orphans is not None:
            orphans.pop(key, None)
            if not orphans:
                del self._orphans[node]
        self._pod_to_node.pop(key, None)

    # -- assume protocol -----------------------------------------------------

    def assume_pod(
        self,
        pod: v1.Pod,
        node_name: str,
        device_synced: bool = False,
        prio_band: Optional[int] = None,
        proto: Optional[tuple] = None,
    ) -> None:
        """device_synced=True: the placement came from the wave kernel, whose
        finalize already committed the pod's occupancy into the device
        snapshot — replay host-side only (ops/encoding.add_pod). prio_band
        pins the priority band the kernel committed prio_req under (a band
        relabel between encode and replay would otherwise diverge).
        proto: encoder.pod_proto() from a template sibling (bulk binds
        compute the spec-derived encoding once per template)."""
        key = pod.metadata.key
        with self.lock:
            if key in self._assumed or key in self._pod_to_node:
                raise ValueError(f"pod {key} already assumed/added")
            assumed = pod.deep_copy()
            assumed.spec.node_name = node_name
            self._add_pod_internal(
                assumed,
                device_synced=device_synced,
                prio_band=prio_band,
                proto=proto,
            )
            self._assumed[key] = _AssumedInfo(assumed, node_name, None)

    def assume_pods_bulk(self, items: list) -> list:
        """Assume a whole wave of device-committed placements under ONE
        lock acquisition, with vectorized encoder scatters. items =
        [(pod, node_name, band, proto)]; returns a per-item error-message
        list (None = assumed). Entries that fail the duplicate/unknown-
        node checks are skipped without affecting the rest."""
        errors: list = [None] * len(items)
        enc_items: list = []
        # template siblings share a proto object; the spec-derived host
        # aggregates (requests, ports, affinity) are identical per template
        # (fingerprint pins them, ops/templates.py:82) — compute them once
        tmpl_pre: dict = {}
        with self.lock:
            for i, (pod, node_name, band, proto) in enumerate(items):
                key = pod.metadata.key
                if key in self._assumed or key in self._pod_to_node:
                    errors[i] = f"pod {key} already assumed/added"
                    continue
                assumed = v1.assume_copy(pod, node_name)
                ni = self._nodes.get(node_name)
                if ni is None:
                    # unknown node: track mapping only (matches add path)
                    self._pod_to_node[key] = node_name
                    self._assumed[key] = _AssumedInfo(assumed, node_name, None)
                    continue
                pre_key = id(proto) if proto is not None else None
                pre = tmpl_pre.get(pre_key) if pre_key is not None else None
                if pre is None:
                    pre = (
                        v1.compute_pod_resource_request(pod),
                        v1.compute_pod_resource_request(pod, non_zero=True),
                        v1.pod_host_ports(pod),
                        _has_affinity(pod),
                    )
                    if pre_key is not None:
                        tmpl_pre[pre_key] = pre
                ni.add_pod_precomputed(assumed, *pre)
                self._bump(ni)
                self._pod_to_node[key] = node_name
                self._assumed[key] = _AssumedInfo(assumed, node_name, None)
                enc_items.append(
                    (
                        i,
                        node_name,
                        assumed,
                        # same fallback as add_pod: an unpinned band is
                        # derived from the pod's priority, never 0
                        band
                        if band is not None
                        else self.encoder._band_of(assumed.priority),
                        proto,
                    )
                )
            if enc_items:
                try:
                    self.encoder.add_pods_bulk(
                        [item[1:] for item in enc_items]
                    )
                except Exception:
                    # bulk pass 1 raises BEFORE any master write, so the
                    # per-pod path can safely redo the whole wave — the
                    # NodeInfo/_assumed state above is already correct
                    logger.exception(
                        "bulk encoder scatter failed; per-pod fallback"
                    )
                    for i, node_name, assumed, band, proto in enc_items:
                        try:
                            self.encoder.add_pod(
                                node_name,
                                assumed,
                                device_synced=True,
                                prio_band=band,
                                proto=proto,
                            )
                        except KeyError:
                            pass  # node unknown to the encoder: row-less
                        except Exception as exc:
                            # a non-KeyError here used to propagate MID-WAVE
                            # with NodeInfo/_assumed already committed for
                            # every item: the raiser's host state kept the
                            # pod while the encoder (and the device row the
                            # kernel committed) silently diverged, and the
                            # remaining items never assumed at all. Unwind
                            # THIS pod's host state, surface a per-item
                            # error (the caller requeues it), and hand the
                            # row to the anti-entropy repairer — the device
                            # still holds the kernel's commit for a pod the
                            # masters no longer carry.
                            logger.exception(
                                "per-pod encoder replay failed for %s on %s",
                                assumed.metadata.key,
                                node_name,
                            )
                            key = assumed.metadata.key
                            # entry first, WITHOUT subtracting: the add may
                            # have half-applied its master increments
                            self.encoder.drop_pod_entry(node_name, key)
                            self._assumed.pop(key, None)
                            self._remove_pod_internal(key, node_name)
                            self.encoder.repair_row(node_name)
                            errors[i] = (
                                f"encoder replay failed for {key}: {exc}"
                            )
        return errors

    def finish_binding(self, pod: v1.Pod) -> None:
        """Arms the expiry TTL (cache.go FinishBinding)."""
        with self.lock:
            a = self._assumed.get(pod.metadata.key)
            if a is not None:
                a.deadline = time.monotonic() + self._ttl

    def forget_pod(self, pod: v1.Pod) -> None:
        with self.lock:
            a = self._assumed.pop(pod.metadata.key, None)
            if a is not None:
                self._remove_pod_internal(pod.metadata.key, a.node_name)

    def is_assumed(self, pod_key: str) -> bool:
        with self.lock:
            return pod_key in self._assumed

    def assumed_keys(self) -> List[str]:
        """Sorted outstanding-assume keys under the lock: the O(assumed)
        accessor pollers want (a `dump()` poll would serialize the whole
        cache per probe while holding the lock everyone else needs)."""
        with self.lock:
            return sorted(self._assumed)

    def has_pod(self, pod_key: str) -> bool:
        """True if the pod is assumed or placed (any node)."""
        with self.lock:
            return pod_key in self._assumed or pod_key in self._pod_to_node

    def cleanup_expired(self, now: Optional[float] = None) -> int:
        now = now if now is not None else time.monotonic()
        with self.lock:
            expired = [
                k
                for k, a in self._assumed.items()
                if a.deadline is not None and a.deadline < now
            ]
            for k in expired:
                a = self._assumed.pop(k)
                self._remove_pod_internal(k, a.node_name)
            return len(expired)

    def start_janitor(self, period: float = 1.0) -> None:
        if self._janitor is not None:
            return
        def loop():
            while not self._stop.wait(period):
                t0 = time.monotonic()
                self.cleanup_expired()
                dt = time.monotonic() - t0
                metrics.observe(
                    "scheduler_background_pass_seconds", dt,
                    {"task": "assume_ttl"},
                )
                note_pass("assume_ttl", t0, dt)
        self._janitor = threading.Thread(target=loop, daemon=True, name="cache-janitor")
        self._janitor.start()

    def stop(self) -> None:
        self._stop.set()

    # -- snapshots ----------------------------------------------------------

    def _bump(self, ni: NodeInfo) -> None:
        self._generation += 1
        ni.generation = self._generation

    def _bump_ext(self, node_name: Optional[str]) -> None:
        """Stamp a mutation NO in-flight device chain has seen (informer
        events, host-path assumes). Kept separate from _bump:
        device-synced wave assumes move `generation` (snapshot
        incrementality) but must NOT move `ext_generation`, or pipelined
        sibling-batch commits would exempt their nodes from the oracle
        guard exactly under sustained wave load."""
        ni = self._nodes.get(node_name) if node_name else None
        if ni is not None:
            self._ext_generation += 1
            ni.ext_generation = self._ext_generation

    def update_snapshot(self) -> Snapshot:
        """Host snapshot for oracle/fallback/preemption paths. NodeInfos are
        cloned so the cycle sees immutable state (snapshot.go semantics).

        Incremental by node generation (the reference's
        cache.UpdateSnapshot, cache.go:200): only nodes whose generation
        moved since the last call are re-cloned — the host path re-snapshots
        per pod (scheduleOne semantics), and a full 5k-node clone per pod
        would dominate small-batch latency. Cycles never mutate snapshot
        NodeInfos (preemption/nominated simulation clone first), so reuse
        across snapshots is safe."""
        with self.lock:
            cached = self._snap_clones
            fresh: Dict[str, NodeInfo] = {}
            for name, ni in self._nodes.items():
                old = cached.get(name)
                if old is not None and old.generation == ni.generation:
                    fresh[name] = old
                else:
                    fresh[name] = ni.clone()
            self._snap_clones = fresh
            snap = Snapshot(list(fresh.values()))
            snap.generation = self._generation
            return snap

    def device_snapshot(self):
        """Flush pending deltas, return HBM-resident DeviceSnapshot."""
        with self.lock:
            return self.encoder.flush()

    @property
    def node_count(self) -> int:
        with self.lock:
            return len(self._nodes)

    def pod_count(self) -> int:
        with self.lock:
            return sum(len(ni.pods) for ni in self._nodes.values())

    def node_names(self) -> List[str]:
        with self.lock:
            return list(self._nodes.keys())

    def get_node_info(self, name: str) -> Optional[NodeInfo]:
        with self.lock:
            return self._nodes.get(name)

    def node_infos(self) -> Dict[str, NodeInfo]:
        """One-lock snapshot of the NodeInfo map (references, not clones)
        — the autoscaler's per-pass utilization scan takes the cache lock
        once instead of once per node."""
        with self.lock:
            return dict(self._nodes)

    def dump(self) -> dict:
        """Debugger support (internal/cache/debugger): cache contents."""
        with self.lock:
            return {
                "nodes": {
                    n: [p.metadata.key for p in ni.pods]
                    for n, ni in self._nodes.items()
                },
                "assumed": sorted(self._assumed.keys()),
            }


# lockset sanitizer (testing/lockgraph.py Eraser mode): the maps every
# informer handler, wave commit, janitor sweep, and autoscaler scan
# shares — guarded by `scheduler.cache`, now machine-checked in chaos
track_attrs(
    SchedulerCache,
    "_nodes",
    "_pod_to_node",
    "_assumed",
    "_orphans",
    "_snap_clones",
    "_generation",
    "_ext_generation",
)
