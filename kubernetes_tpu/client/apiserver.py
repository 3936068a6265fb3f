"""Versioned in-memory API store with watch fan-out.

Collapses the reference's persistence stack — etcd (gRPC) + etcd3 store
(staging/src/k8s.io/apiserver/pkg/storage/etcd3/store.go) + watch cacher
(storage/cacher/cacher.go:448) — into one process-local component with the
same observable semantics the control plane depends on:

  * monotonically increasing resourceVersion per write
  * optimistic concurrency: update conflicts on stale resource_version
  * list + watch-from-version with ordered event delivery per watcher
  * per-(kind, namespace) keying

Components talk to it through plain method calls instead of REST; the handler
chain (authn/authz/admission) is represented by pluggable admit hooks.
"""

from __future__ import annotations

import copy
import logging
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..api import serialization, validation
from ..api.objects import event_copy
from ..runtime.watch import ADDED, DELETED, MODIFIED, Event, Watcher
from ..testing.lockgraph import named_lock, track_attrs
from ..utils.metrics import metrics
from ..utils.tracing import note_pass

logger = logging.getLogger("kubernetes_tpu.apiserver")

# disk-health state the write gate acts on: 0 = ok, 1 = pressure
# (read-only, lifts with free space), 2 = failed (fail-stop, permanent)
GAUGE_DISK_STATE = "store_disk_state"
# recovery found mid-log corruption: serving the longest valid prefix,
# must resync from a healthy peer before leading
GAUGE_DISK_CORRUPT = "store_disk_corrupt"
COUNTER_PRESSURE_ENTRIES = "store_disk_pressure_entries_total"
COUNTER_COMPACT_FAILURES = "wal_compaction_failures_total"
COUNTER_COMPACTIONS = "wal_compactions_total"


class NotFound(KeyError):
    pass


class AlreadyExists(ValueError):
    pass


class TooManyRequests(ValueError):
    """Eviction blocked by a PodDisruptionBudget (HTTP 429, the registry's
    eviction.go DisruptionBudget error)."""


class Conflict(ValueError):
    """Stale resource_version on update (optimistic-concurrency failure)."""


class Expired(ValueError):
    """Watch resourceVersion older than retained history (HTTP 410 Gone;
    the reference's "The resourceVersion for the provided watch is too
    old" — watchers must re-list)."""


def list_and_watch(server, kind: str, seed) -> "Watcher":
    """list → seed(objs) → watch(list rv), retrying the whole pair on
    Expired (the reflector's ListAndWatch restart). seed must tolerate
    re-delivery (queue adds dedup; event handlers treat re-adds as
    updates)."""
    while True:
        objs, rv = server.list(kind)
        seed(objs)
        try:
            return server.watch(kind, from_version=rv)
        except Expired:
            continue


AdmitHook = Callable[[str, str, Any], None]  # (verb, kind, obj) -> raise to deny


class NotPrimary(RuntimeError):
    """Write rejected: this store was fenced by a higher replication term
    (a follower promoted; see runtime/replication.py)."""


class LeaderFenced(Conflict):
    """Write rejected: the caller's leadership lease was superseded — a
    newer holder (or a graceful release) bumped the lease transitions
    since the caller's fencing token was minted. The zombie-ex-leader
    fence: a paused leader resuming after a standby promotion gets THIS,
    never a silently applied late bind. Non-retryable by design (the
    caller is not the leader anymore)."""


# the instants at which the calling thread's last create/bind began to
# wait for the store lock and finished its hold: the REST handler reads
# them so that its `store` stage is exactly what the store's own series
# (lock wait + commit stages) cover, with `admit` (admission + validation)
# before it and `observe` (those series being observed, the return) after
_commit_tls = threading.local()


def last_commit_instants() -> Tuple[Optional[float], Optional[float]]:
    """(began to wait for the store lock, finished its hold) of this
    thread's most recent create or bind_pods, on time.monotonic()
    (None: none yet)."""
    return getattr(_commit_tls, "t_w", None), getattr(_commit_tls, "t_e", None)


_COMMIT_STAGES = ("apply", "wal_append", "fsync", "notify")
_commit_sets: Dict[Tuple[str, str], Any] = {}


def _observe_commit(
    op: str, kind: str, lock_wait: float, apply: float, wal_append: float,
    fsync: float, notify: float,
) -> None:
    """One committed write's time at the store: the wait for the `store`
    lock, then the stages of its hold — `apply` (checks under the lock,
    rv bump, copies), `wal_append` (serialise + enqueue or write, and
    whatever else the log step does besides waiting), `fsync` (the wait),
    `notify` (watch fan-out). By `kind` as well as `op`: a scheduler over
    REST creates an Event for every pod it binds, and a pod's create is
    not an event's. Called AFTER the lock is released; one registry-lock
    hop for the five series (HistogramSet: a write costs ~3 ms and the
    apiserver is the wall, so its own accounting has to cost microseconds)."""
    hs = _commit_sets.get((op, kind))
    if hs is None:
        hs = _commit_sets[(op, kind)] = metrics.histogram_set(
            "store_lock_wait_seconds", {"op": op, "kind": kind}
        ) + metrics.histogram_set(
            "store_commit_stage_seconds",
            {"op": op, "kind": kind, "stage": _COMMIT_STAGES},
        )
    hs.observe((lock_wait, apply, wal_append, fsync, notify))


class APIServer:
    def __init__(self, watch_history: int = 200000, wal=None):
        # named for the lock-order watchdog (testing/lockgraph.py)
        self._lock = named_lock("store")
        self._rv = 0
        # kind -> key -> object
        self._objects: Dict[str, Dict[str, Any]] = {}
        # kind -> list of live watchers
        self._watchers: Dict[str, List[Watcher]] = {}
        # kind -> ring buffer of past events for watch-from-version replay
        self._history: Dict[str, deque] = {}
        self._history_len = watch_history
        # kind -> rv of the newest event EVICTED from its ring (watch()'s
        # exact staleness check)
        self._evicted_rv: Dict[str, int] = {}
        self.admit_hooks: List[AdmitHook] = []
        # optional durability (runtime/wal.py): every mutation is logged
        # before acknowledgment; recover() rebuilds a server from disk —
        # the crash-only contract of the reference's etcd layer
        self._wal = wal
        self._compacting = threading.Event()
        self._compact_failures = 0
        self._compact_backoff_until = 0.0
        # recovery classified the WAL as mid-log corrupt: state is the
        # longest valid prefix; a corrupt replica must resync from a
        # healthy peer (replication snap/catchup) before it may lead
        self.disk_corrupt = False
        # optional low-watermark free-space probe (runtime/wal.py
        # DiskSpaceProbe): checked on the write-admission path so the
        # store enters disk-pressure read-only BEFORE appends hit ENOSPC
        # and auto-reopens once space recovers
        self.disk_probe = None
        if wal is not None and hasattr(wal, "on_disk_failed"):
            # the WAL poisons on any write/fsync error, from ANY append
            # site (mutations, consensus epoch records, compaction
            # reopen) — mirror it into the write gate immediately
            wal.on_disk_failed(self._on_wal_disk_failed)
        # optional HA (runtime/replication.py): mutations ship to followers
        # synchronously after the local WAL append. write_gate is the one
        # write-admission authority (runtime/store.py): read_only maps to
        # its higher-term fence; consensus mode also arms its degraded
        # (quorum-lost, 503-retryable) state through it
        self.replicator = None
        from ..runtime.store import WriteGate

        self.write_gate = WriteGate()
        # node name -> callable(pod_key, ...) -> str: the kubelet's log and
        # exec surfaces (kubectl logs/exec flow apiserver -> kubelet ->
        # runtime GetContainerLogs/ExecSync in the reference; node agent
        # pools register here)
        self.log_providers: Dict[str, Callable] = {}
        self.exec_providers: Dict[str, Callable] = {}

    @classmethod
    def recover(cls, wal_path: str, watch_history: int = 200000) -> "APIServer":
        """Rebuild a server from its WAL + snapshot (crash restart).
        Watch history does not survive (watchers must re-list, exactly like
        an etcd compaction forcing a reflector relist)."""
        from ..runtime.wal import WriteAheadLog

        report = WriteAheadLog.recover_report(wal_path)
        srv = cls(watch_history=watch_history, wal=WriteAheadLog(wal_path))
        srv._rv = report.rv
        srv._objects = report.objects
        if report.corrupt:
            # mid-log corruption: the state below is the longest valid
            # prefix, honest but possibly missing acked writes — flag it
            # so replication refuses to promote this replica until it has
            # resynced from a healthy peer (Follower disk_corrupt gate)
            srv.disk_corrupt = True
            metrics.set_gauge(GAUGE_DISK_CORRUPT, 1.0)
        return srv

    @property
    def wal(self):
        """The write-ahead log behind this store (None: not durable)."""
        return self._wal

    def _log(self, verb: str, kind: str, obj: Any) -> float:
        if self._wal is None and self.replicator is None:
            return 0.0
        return self._log_batch([(self._rv, verb, kind, obj)])

    def _log_batch(self, records) -> float:
        """records: [(rv, verb, kind, obj)] — one group-committed append,
        then synchronous replication to any attached followers (ack'd
        before the mutation is acknowledged to the client: kill the
        primary at any point and no acknowledged write is lost). Returns
        the seconds of the append spent waiting for the fsync (the
        `fsync` commit stage; 0.0 without a WAL)."""
        if not records:
            return 0.0
        fsync_s = 0.0
        if self._wal is not None:
            try:
                fsync_s = self._wal.append_batch(records) or 0.0
            except OSError as e:
                # the record is NOT durable, so the client must not see an
                # ack — but the in-memory mutation already applied and is
                # READABLE, so watchers must still learn of it (same
                # reasoning as the ship() failure below). Then surface the
                # disk-classified degraded error: DiskPressure (ENOSPC,
                # retryable once space frees) or DiskFailed (sink
                # fail-stop; the write gate goes read-only for good).
                for rv, verb, kind, obj in records:
                    ev_type = {"create": ADDED, "delete": DELETED}.get(
                        verb, MODIFIED
                    )
                    self._notify(kind, Event(ev_type, copy.deepcopy(obj), rv))
                raise self._classify_disk_error(e) from e
            self._maybe_compact()
        if self.replicator is not None:
            try:
                self.replicator.ship(records)
            except Exception:
                # quorum loss (QuorumLost/NotPrimary) aborts the caller
                # BEFORE its own _notify — but the records stay applied,
                # WAL-durable, and READABLE (and may yet commit), so
                # watchers must still learn of them or every informer
                # desyncs from list() with a permanent rv gap in the
                # stream. Synthesize the events the caller would have
                # sent, then re-raise (the client still sees the 503).
                for rv, verb, kind, obj in records:
                    ev_type = {"create": ADDED, "delete": DELETED}.get(
                        verb, MODIFIED
                    )
                    self._notify(kind, Event(ev_type, copy.deepcopy(obj), rv))
                raise
        return fsync_s

    def _on_wal_disk_failed(self, why: str) -> None:
        """WAL fail-stop callback (fired under the wal lock: flag flips
        only, never call back into the WAL or take the store lock)."""
        self.write_gate.set_disk_failed(why)
        metrics.set_gauge(GAUGE_DISK_STATE, 2.0)

    def _classify_disk_error(self, e: OSError) -> Exception:
        """The fail-stop seam: every WAL-append OSError on the mutation
        path routes through here to flip the write gate and become the
        matching retryable DegradedWrites subclass."""
        from ..runtime.consensus import DiskFailed, DiskPressure
        from ..runtime.wal import DiskFull

        if isinstance(e, DiskFull):
            self._enter_disk_pressure(f"WAL append hit ENOSPC: {e}")
            return DiskPressure(str(e))
        self.write_gate.set_disk_failed(str(e))
        metrics.set_gauge(GAUGE_DISK_STATE, 2.0)
        return DiskFailed(
            f"WAL append failed; store is read-only (fail-stop): {e}"
        )

    def _enter_disk_pressure(self, why: str) -> None:
        self.write_gate.set_disk_pressure(True)
        metrics.inc(COUNTER_PRESSURE_ENTRIES)
        metrics.set_gauge(GAUGE_DISK_STATE, 1.0)
        logger.warning("store entering disk-pressure read-only: %s", why)
        if self.disk_probe is None and self._wal is not None:
            # nothing would ever clear the gate otherwise: arm a default
            # probe over the WAL volume so recovery is observed
            from ..runtime.wal import DiskSpaceProbe

            self.disk_probe = DiskSpaceProbe(self._wal.log_path)
        if self.disk_probe is not None:
            # sync the probe's hysteresis with the gate: an ENOSPC-driven
            # entry (quota exhaustion, a full volume the watermark never
            # saw coming) must still clear through the probe's recovery
            # transition — otherwise the gate sticks even after space
            # frees, because check() only reports a recovery AFTER an
            # observed entry
            self.disk_probe.under_pressure = True
        # compaction as reclaim: a snapshot + log rewrite usually SHRINKS
        # the volume (the log holds every record since the last snapshot)
        if self._wal is not None and not self._compacting.is_set():
            self._compacting.set()
            threading.Thread(
                target=self._compact_async, daemon=True, name="wal-reclaim"
            ).start()

    def _check_disk_pressure(self) -> None:
        """Write-admission-path probe: enter read-only BEFORE appends fail
        with ENOSPC; auto-reopen when free space recovers (the probe has
        hysteresis and rate-limits its own statvfs)."""
        probe = self.disk_probe
        if probe is None:
            return
        state = probe.check()
        if state is True and not self.write_gate.disk_pressure:
            self._enter_disk_pressure(
                f"free space below low watermark ({probe.low_bytes} B)"
            )
        elif state is False and self.write_gate.disk_pressure:
            self.write_gate.set_disk_pressure(False)
            if not self.write_gate.disk_failed:
                metrics.set_gauge(GAUGE_DISK_STATE, 0.0)
            logger.info("disk pressure cleared: store writable again")

    def _maybe_compact(self) -> None:
        if (
            self._wal.due()
            and not self._compacting.is_set()
            and time.monotonic() >= self._compact_backoff_until
        ):
            # compaction runs OFF the mutation path, and off this
            # interpreter: see _compact_async
            self._compacting.set()
            threading.Thread(
                target=self._compact_async, daemon=True, name="wal-compact"
            ).start()

    def _compact_async(self) -> None:
        """One compaction, from the WAL's own files: note where the log
        stands (`cut`), have a child process fold the snapshot on disk +
        the log up to there into the next snapshot (`fold`), publish it
        and keep the log's bytes past the cut (`publish`). The `store`
        lock is never taken and no object is copied, encoded or parsed
        here: this process is one core under the GIL, and a compaction
        from memory cost it ~5 s at 30,000 objects, ~2 s of them a full
        stop (the copy under the `store` lock that this comment used to
        call "cheap", 1.5 s, and the log's re-parse under the wal lock).
        What writers still wait for is the publish's hold of the wal
        lock: `store_background_pass_seconds{task="wal_compact_publish"}`.
        Only where the fold finds the files damaged is memory the truth:
        then, once, the old way (`_compact_from_memory`), which rewrites
        both files whole."""
        from ..runtime.wal import LogDamaged

        try:
            try:
                done, how = self._compact_from_files(), "files"
            except LogDamaged as e:
                logger.error(
                    "WAL files damaged before the compaction's cut (%s): "
                    "compacting from memory", e,
                )
                self._compact_from_memory()
                done, how = True, "memory"
            if done:
                metrics.inc(COUNTER_COMPACTIONS, {"how": how})
            self._compact_failures = 0
        except OSError:
            # failed compaction must never wedge the append path (the WAL
            # reopens its own sink) NOR retry hot: count it and back off —
            # due() stays true, so the next write past the backoff retries
            self._compact_failures += 1
            backoff = min(2.0 ** self._compact_failures, 60.0)
            self._compact_backoff_until = time.monotonic() + backoff
            metrics.inc(COUNTER_COMPACT_FAILURES)
            logger.exception(
                "WAL compaction failed (failure %d in a row); next retry "
                "in %.0fs",
                self._compact_failures,
                backoff,
            )
        finally:
            self._compacting.clear()

    def _compact_from_files(self) -> bool:
        """False where nothing was published: the sink closed or poisoned,
        or the log was rewritten under the fold (its owner's business)."""
        t0 = time.monotonic()
        cut = self._wal.cut()
        if cut is None:
            return False
        self._wal.fold(cut)
        t1 = time.monotonic()
        published = self._wal.publish(cut)
        t2 = time.monotonic()
        # the publish holds the wal lock, which every write takes under
        # the `store` lock: a stall of its length (the wait for the lock
        # included), observed after release and kept for
        # /debug/traces?stalls=1
        metrics.observe(
            "store_background_pass_seconds", t2 - t1,
            {"task": "wal_compact_publish"},
        )
        note_pass("wal_compact_publish", t1, t2 - t1)
        metrics.observe("wal_compaction_seconds", t1 - t0, {"phase": "fold"})
        metrics.observe("wal_compaction_seconds", t2 - t1, {"phase": "publish"})
        return published

    def _compact_from_memory(self) -> None:
        """The fallback: snapshot the live objects. The copy holds the
        `store` lock for its length (1.46-1.66 s at 30,000 objects)."""
        t0 = time.monotonic()
        with self._lock:
            rv = self._rv
            objects = {
                kind: [copy.deepcopy(o) for o in store.values()]
                for kind, store in self._objects.items()
            }
        dt = time.monotonic() - t0
        metrics.observe(
            "store_background_pass_seconds", dt,
            {"task": "wal_compact_copy"},
        )
        note_pass("wal_compact_copy", t0, dt)
        self._wal.write_snapshot(rv, objects)

    def backup_state(self) -> dict:
        """One-lock-consistent online backup image: the full object state
        at rv plus the consensus commit index and fencing term
        (runtime/backup.py writes it out; restore bumps the term so every
        pre-backup BindFence is structurally rejected)."""
        with self._lock:
            rv = self._rv
            objects = {
                kind: [serialization.encode(o) for o in store.values()]
                for kind, store in self._objects.items()
            }
        commit = rv
        term = 1
        rep = self.replicator
        if rep is not None:
            term = int(getattr(rep, "term", 1))
            cons = getattr(rep, "consensus", None)
            if cons is not None:
                commit = min(int(cons.commit_index), rv)
        return {
            "format": "ktpu-backup-v1",
            "rv": rv,
            "commit": commit,
            "term": term,
            "objects": objects,
        }

    # -- helpers ------------------------------------------------------------

    @staticmethod
    def _key(obj: Any) -> str:
        return obj.metadata.key

    @staticmethod
    def _normalize_scope(kind: str, obj: Any) -> None:
        """Cluster-scoped kinds store under namespace '' regardless of how
        the client spelled it (a plain manifest decode defaults to
        'default') — one canonical key, no per-consumer probe loops."""
        if kind in serialization.CLUSTER_SCOPED and obj.metadata.namespace:
            obj.metadata.namespace = ""

    @staticmethod
    def _normalize_ns(kind: str, namespace: str) -> str:
        if kind in serialization.CLUSTER_SCOPED:
            return ""
        return namespace

    def _bump(self, obj: Any) -> int:
        self._rv += 1
        obj.metadata.resource_version = self._rv
        return self._rv

    def _admit(self, verb: str, kind: str, obj: Any) -> None:
        for hook in self.admit_hooks:
            hook(verb, kind, obj)

    def _notify(self, kind: str, ev: Event) -> None:
        hist = self._history.setdefault(kind, deque(maxlen=self._history_len))
        if len(hist) == self._history_len and hist:
            # the append below evicts the oldest event: remember its rv so
            # watch() raises Expired exactly when a caller would actually
            # miss this kind's events (a global-rv heuristic would fire
            # spuriously for gaps made entirely of OTHER kinds' writes)
            self._evicted_rv[kind] = hist[0].resource_version
        hist.append(ev)
        for w in list(self._watchers.get(kind, [])):
            if w.stopped:
                self._watchers[kind].remove(w)
            else:
                w.push(ev)

    # -- CRUD ---------------------------------------------------------------

    @property
    def read_only(self) -> bool:
        return self.write_gate.fenced

    @read_only.setter
    def read_only(self, value: bool) -> None:
        self.write_gate.fenced = bool(value)

    def _check_writable(self) -> None:
        if self.write_gate.fenced:
            raise NotPrimary("store fenced: a newer primary holds the lease")
        # disk-pressure probe runs on the admission path so the store goes
        # read-only BEFORE appends fail and reopens when space recovers
        # (clients retrying a DiskPressure 503 drive the re-check)
        self._check_disk_pressure()
        # degraded read-only (consensus quorum lost / disk states): raises
        # the retryable DegradedWrites BEFORE any mutation is applied —
        # reads and watches are never gated
        self.write_gate.check_degraded()

    def create(self, kind: str, obj: Any) -> Any:
        self._check_writable()
        # admission runs OUTSIDE the store lock: webhook plugins do HTTP
        # round trips (and their handlers commonly read back from this
        # server), which under the lock would stall every API call and
        # deadlock read-back webhooks. In-process stateful gates serialize
        # themselves: QuotaAdmission check-and-reserves under its own mutex
        # (racing creates cannot both pass a quota with room for one,
        # matching the reference's transactional quota reservation)
        self._normalize_scope(kind, obj)
        self._admit("create", kind, obj)
        # always-on boundary validation AFTER admission mutators (the
        # reference's strategy.Validate ordering: defaulted fields are
        # validated, not raw input) — malformed objects 400 here instead
        # of surfacing later as encode-time scheduler exceptions
        validation.validate_object("create", kind, obj)
        # the instants are taken under the lock and observed after its
        # release (_observe_commit): they describe the lock's hold
        t_w = _commit_tls.t_w = time.monotonic()
        with self._lock:
            t_l = time.monotonic()
            store = self._objects.setdefault(kind, {})
            key = self._key(obj)
            if key in store:
                raise AlreadyExists(f"{kind} {key} already exists")
            if kind == "priorityclasses":
                # stateful uniqueness checks need the store lock (two
                # racing creates must not both land globalDefault: true)
                validation.validate_single_global_default(
                    obj, store.values()
                )
            self._bump(obj)
            stored = copy.deepcopy(obj)
            store[key] = stored
            t_a = time.monotonic()
            fsync_s = self._log("create", kind, stored)
            t_g = time.monotonic()
            self._notify(
                kind,
                Event(ADDED, copy.deepcopy(stored),
                      stored.metadata.resource_version, committed=time.time()),
            )
            t_n = time.monotonic()
            out = copy.deepcopy(stored)
            t_e = _commit_tls.t_e = time.monotonic()
        # the reply's copy is part of `apply` (the three deepcopies)
        _observe_commit(
            "create", kind, t_l - t_w, (t_a - t_l) + (t_e - t_n),
            t_g - t_a - fsync_s, fsync_s, t_n - t_g,
        )
        return out

    def get(self, kind: str, namespace: str, name: str) -> Any:
        namespace = self._normalize_ns(kind, namespace)
        with self._lock:
            key = f"{namespace}/{name}" if namespace else name
            store = self._objects.get(kind, {})
            if key not in store:
                raise NotFound(f"{kind} {key} not found")
            return copy.deepcopy(store[key])

    def update(self, kind: str, obj: Any, check_version: bool = True) -> Any:
        self._check_writable()
        self._normalize_scope(kind, obj)
        self._admit("update", kind, obj)  # outside the lock, see create()
        with self._lock:
            store = self._objects.setdefault(kind, {})
            key = self._key(obj)
            if key not in store:
                raise NotFound(f"{kind} {key} not found")
            cur = store[key]
            if (
                check_version
                and obj.metadata.resource_version
                and obj.metadata.resource_version != cur.metadata.resource_version
            ):
                raise Conflict(
                    f"{kind} {key}: rv {obj.metadata.resource_version} != "
                    f"{cur.metadata.resource_version}"
                )
            validation.validate_object("update", kind, obj, old=cur)
            if kind == "priorityclasses":
                validation.validate_single_global_default(
                    obj, (o for k, o in store.items() if k != key)
                )
            self._bump(obj)
            stored = copy.deepcopy(obj)
            # graceful deletion completes when the last finalizer is
            # stripped from a deletion-pending object (the registry's
            # deleteForEmptyFinalizers path)
            if (
                stored.metadata.deletion_timestamp is not None
                and not stored.metadata.finalizers
            ):
                store.pop(key, None)
                self._log("delete", kind, stored)
                self._notify(
                    kind,
                    Event(
                        DELETED,
                        copy.deepcopy(stored),
                        stored.metadata.resource_version,
                    ),
                )
                return copy.deepcopy(stored)
            store[key] = stored
            self._log("update", kind, stored)
            self._notify(
                kind,
                Event(
                    MODIFIED, copy.deepcopy(stored), stored.metadata.resource_version
                ),
            )
            return copy.deepcopy(stored)

    def guaranteed_update(
        self, kind: str, namespace: str, name: str, mutate: Callable[[Any], Any]
    ) -> Any:
        """Retry-on-conflict read-modify-write (etcd3 GuaranteedUpdate)."""
        while True:
            cur = self.get(kind, namespace, name)
            new = mutate(cur)
            if new is None:
                return cur
            try:
                return self.update(kind, new)
            except Conflict:
                continue

    def delete(self, kind: str, namespace: str, name: str) -> Any:
        self._check_writable()
        namespace = self._normalize_ns(kind, namespace)
        key = f"{namespace}/{name}" if namespace else name
        with self._lock:
            store = self._objects.get(kind, {})
            if key not in store:
                raise NotFound(f"{kind} {key} not found")
            admit_copy = copy.deepcopy(store[key])
        # outside the lock, see create(); validators get a copy so a
        # misbehaving plugin can't mutate stored state through the ref
        self._admit("delete", kind, admit_copy)
        with self._lock:
            store = self._objects.get(kind, {})
            if key not in store:
                raise NotFound(f"{kind} {key} not found")
            obj = store[key]
            if obj.metadata.finalizers:
                # graceful deletion (registry store.Delete with pending
                # finalizers): mark intent, keep the object; finalizer
                # owners strip their entries via update, and the LAST strip
                # removes it (see update())
                if obj.metadata.deletion_timestamp is None:
                    import time as _time

                    obj.metadata.deletion_timestamp = _time.time()
                    self._bump(obj)
                    self._log("update", kind, obj)
                    self._notify(
                        kind,
                        Event(
                            MODIFIED,
                            copy.deepcopy(obj),
                            obj.metadata.resource_version,
                        ),
                    )
                return copy.deepcopy(obj)
            store.pop(key)
            self._rv += 1
            self._log("delete", kind, obj)
            self._notify(kind, Event(DELETED, copy.deepcopy(obj), self._rv))
            return obj

    def list(
        self, kind: str, namespace: Optional[str] = None
    ) -> Tuple[List[Any], int]:
        """Returns (objects, resourceVersion-at-list-time)."""
        with self._lock:
            store = self._objects.get(kind, {})
            objs = [
                copy.deepcopy(o)
                for o in store.values()
                if namespace is None or o.metadata.namespace == namespace
            ]
            return objs, self._rv

    def pod_logs(
        self, namespace: str, name: str, tail_lines: Optional[int] = None
    ) -> str:
        """pods/{name}/log subresource: route to the pod's node's
        registered log provider (the kubelet-proxy hop of kubectl logs)."""
        pod = self.get("pods", namespace, name)
        node = pod.spec.node_name
        if not node:
            raise NotFound(f"pod {namespace}/{name} is not scheduled")
        provider = self.log_providers.get(node)
        if provider is None:
            raise NotFound(f"no log provider for node {node}")
        return provider(f"{namespace}/{name}", tail_lines)

    def pod_exec(self, namespace: str, name: str, command) -> str:
        """pods/{name}/exec subresource: ExecSync through the pod's node's
        registered exec provider (the kubelet hop of kubectl exec)."""
        pod = self.get("pods", namespace, name)
        node = pod.spec.node_name
        if not node:
            raise NotFound(f"pod {namespace}/{name} is not scheduled")
        provider = self.exec_providers.get(node)
        if provider is None:
            raise NotFound(f"no exec provider for node {node}")
        try:
            return provider(f"{namespace}/{name}", command)
        except KeyError as e:
            raise NotFound(str(e)) from None

    def exists(self, kind: str, key: str) -> bool:
        """O(1) copy-free presence check by store key ("ns/name")."""
        with self._lock:
            return key in self._objects.get(kind, {})

    def count(self, kind: str, predicate: Optional[Callable[[Any], bool]] = None) -> int:
        """Copy-free count over stored objects. The predicate runs under the
        store lock against live objects and MUST NOT mutate or retain them —
        it exists because a poll loop doing list() deep-copies the world per
        tick (observed: harness polling dominated a 5k-node benchmark)."""
        with self._lock:
            store = self._objects.get(kind, {})
            if predicate is None:
                return len(store)
            return sum(1 for o in store.values() if predicate(o))

    # -- watch --------------------------------------------------------------

    def watch(self, kind: str, from_version: int = 0) -> Watcher:
        """Watch a kind; events with rv > from_version are replayed first.

        Raises Expired ("resourceVersion too old", the reference's 410
        Gone from the etcd3 watcher / cacher) when the ring has already
        evicted events the caller would need: silently skipping them
        would hand the watcher a gapped stream it can't detect. Reflector
        equivalents respond by re-listing (SharedInformer does)."""
        with self._lock:
            hist = self._history.get(kind, ())
            evicted = self._evicted_rv.get(kind, 0)
            # from_version=0 is "from whenever" (no completeness contract);
            # list+watch pairs pass the list rv explicitly
            if from_version and from_version < evicted:
                raise Expired(
                    f"{kind} resourceVersion {from_version} is too old "
                    f"(events up to rv {evicted} were evicted)"
                )
            w = Watcher()
            for ev in hist:
                if ev.resource_version > from_version:
                    w.push(ev)
            self._watchers.setdefault(kind, []).append(w)
            return w

    def kind_resource_version(self, kind: str) -> int:
        """rv of the newest event OF THIS KIND (0 when none ever).
        The watch cache's freshness target: its per-kind rv can only
        ever reach this, not the global counter, which advances on
        every OTHER kind's writes too."""
        with self._lock:
            hist = self._history.get(kind)
            return hist[-1].resource_version if hist else 0

    def watcher_count(self, kind: str) -> int:
        """Live store-side watchers for a kind (stopped ones pruned).
        The watch cache's scale contract is asserted against this: N
        clients on the read path, exactly ONE watcher here per kind."""
        with self._lock:
            ws = [w for w in self._watchers.get(kind, []) if not w.stopped]
            self._watchers[kind] = ws
            return len(ws)

    @property
    def resource_version(self) -> int:
        with self._lock:
            return self._rv

    # -- typed convenience used by the scheduler ----------------------------

    def _check_fence(self, fence) -> None:
        """Caller holds the lock. Validates a leadership fencing token
        (client/leaderelection.BindFence, duck-typed: namespace/name/
        identity/transitions) against the CURRENT lease record. Any
        mismatch — taken over, released, or the lease gone entirely —
        raises LeaderFenced BEFORE anything is applied: the one-writer
        guarantee leader election promises is enforced here, not assumed."""
        ns = self._normalize_ns("leases", fence.namespace)
        key = f"{ns}/{fence.name}" if ns else fence.name
        lease = self._objects.get("leases", {}).get(key)
        if (
            lease is None
            or lease.holder_identity != fence.identity
            or lease.lease_transitions != fence.transitions
        ):
            holder = getattr(lease, "holder_identity", None)
            transitions = getattr(lease, "lease_transitions", None)
            raise LeaderFenced(
                f"bind fenced: lease {key} now held by {holder!r} at "
                f"transition {transitions} (caller's token: "
                f"{fence.identity!r} at {fence.transitions})"
            )

    def bind_pods(self, bindings, fence=None) -> list:
        """Batch bind: one lock acquisition for a whole device batch (the
        uplink analogue of the reference's per-pod POST /binding — our
        scheduler commits hundreds of placements per cycle, so the API layer
        accepts them in bulk). Returns per-binding errors (None = ok); an
        error entry is the NotFound/Conflict exception itself, so callers
        (the REST route's status mapping, the scheduler's reconciler)
        branch on type instead of re-deriving it from message text.

        fence: optional leadership fencing token (BindFence). When given,
        the WHOLE batch is rejected with LeaderFenced unless the token
        still matches the live lease — checked under the same lock the
        binds apply under, so a promotion can never interleave mid-batch.
        """
        from ..utils.tracing import stamp_bind, trace_for_binding

        self._check_writable()
        errors = []
        t_w = _commit_tls.t_w = time.monotonic()
        try:
            with self._lock:
                t_l = time.monotonic()
                if fence is not None:
                    self._check_fence(fence)
                records = []  # WAL batch: group-committed in ONE fsync
                events = []
                for b in bindings:
                    try:
                        store = self._objects.get("pods", {})
                        key = f"{b.pod_namespace}/{b.pod_name}"
                        pod = store.get(key)
                        if pod is None:
                            raise NotFound(f"pods {key} not found")
                        if pod.spec.node_name:
                            raise Conflict(f"pod {key} already bound")
                        if b.pod_uid and pod.metadata.uid != b.pod_uid:
                            raise Conflict("uid mismatch on binding")
                        pod.spec.node_name = b.target_node
                        self._bump(pod)
                        records.append(
                            (pod.metadata.resource_version, "update", "pods", pod)
                        )
                        events.append(
                            Event(
                                MODIFIED,
                                event_copy(pod),
                                pod.metadata.resource_version,
                            )
                        )
                        errors.append(None)
                    except (NotFound, Conflict) as e:
                        errors.append(e)
                # durable BEFORE any watcher learns of the binds (etcd fires
                # watch events post-commit); the batch shares one fsync
                t_a = time.monotonic()
                fsync_s = self._log_batch(records)
                t_g = time.monotonic()
                for ev in events:
                    self._notify("pods", ev)
                t_n = _commit_tls.t_e = time.monotonic()
        except LeaderFenced as fe:
            # the fenced rejection is a trace event too: a zombie's late
            # bind shows up under the SAME id the deposed scheduler
            # minted (the id crossed the REST hop in X-Trace-Context)
            for b in bindings:
                stamp_bind(
                    b, "fenced",
                    identity=getattr(fence, "identity", ""),
                    detail=str(fe)[:160],
                )
            raise
        # one bind call's time inside the store, stage by stage (observed
        # outside the lock it describes)
        lock_wait, apply, wal_append, notify = (
            t_l - t_w, t_a - t_l, t_g - t_a - fsync_s, t_n - t_g
        )
        _observe_commit(
            "bind", "pods", lock_wait, apply, wal_append, fsync_s, notify
        )
        # store-side stamp: the ack the scheduler's trace resolves to
        # (outside the store lock — the trace ledger is a leaf concern);
        # it carries the call's stages, so /debug/traces?id= shows where
        # the bind's time inside the store went (built only for a bind
        # somebody is tracing)
        stages = None
        for b, err in zip(bindings, errors):
            event = "applied" if err is None else type(err).__name__
            if not trace_for_binding(b):
                continue
            if stages is None:
                stages = {
                    "lock_wait_ms": round(lock_wait * 1e3, 3),
                    "apply_ms": round(apply * 1e3, 3),
                    "wal_append_ms": round(wal_append * 1e3, 3),
                    "fsync_ms": round(fsync_s * 1e3, 3),
                    "notify_ms": round(notify * 1e3, 3),
                }
            stamp_bind(b, event, **stages)
        return errors

    def write_events_bulk(self, events_in) -> None:
        """Event-recorder sink: upsert a drained batch of Event objects in
        ONE lock acquisition with ownership transfer — the recorder hands
        over freshly built objects and never touches them again, so the
        create path's three defensive deepcopies (~0.45 ms of GIL per
        event — per BOUND POD during a burst) are skipped. Watch delivery
        still isolates with a cheap shell copy; readers get deepcopies
        from get/list as usual. Existing (object, reason) rows aggregate
        count in place, matching the recorder's correlation semantics."""
        import dataclasses as _dc

        import dataclasses as _dc0

        self._check_writable()
        # admit/validate with the verb the apply below will actually use
        # (aggregating onto an existing row is an update, not a create) so
        # verb-sensitive hooks see the same stream as the per-event path.
        # Existence is snapshotted briefly under the lock; a concurrent
        # recorder racing the same key can at worst mis-verb one
        # best-effort event write.
        with self._lock:
            ev_store = self._objects.get("events", {})
            olds = {}
            for ev in events_in:
                self._normalize_scope("events", ev)
                cur = ev_store.get(self._key(ev))
                if cur is not None:
                    olds[id(ev)] = _dc0.replace(
                        cur, metadata=_dc0.replace(cur.metadata)
                    )
        for ev in events_in:
            old = olds.get(id(ev))
            verb = "create" if old is None else "update"
            self._admit(verb, "events", ev)
            validation.validate_object(verb, "events", ev, old=old)
        with self._lock:
            store = self._objects.setdefault("events", {})
            records = []
            notifies = []
            for ev in events_in:
                key = self._key(ev)
                cur = store.get(key)
                if cur is not None:
                    cur.count += ev.count
                    cur.last_timestamp = ev.last_timestamp
                    cur.note = ev.note
                    self._bump(cur)
                    records.append(
                        (cur.metadata.resource_version, "update", "events", cur)
                    )
                    notifies.append(
                        Event(
                            MODIFIED,
                            _dc.replace(
                                cur, metadata=_dc.replace(cur.metadata)
                            ),
                            cur.metadata.resource_version,
                        )
                    )
                else:
                    self._bump(ev)
                    store[key] = ev
                    records.append(
                        (ev.metadata.resource_version, "create", "events", ev)
                    )
                    notifies.append(
                        Event(
                            ADDED,
                            _dc.replace(
                                ev, metadata=_dc.replace(ev.metadata)
                            ),
                            ev.metadata.resource_version,
                        )
                    )
            self._log_batch(records)
            for e in notifies:
                self._notify("events", e)

    def evict_pod(self, namespace: str, name: str) -> None:
        """pods/{name}/eviction: a PDB-respecting delete (reference
        registry/core/pod/rest/eviction.go). Blocked evictions raise
        TooManyRequests (HTTP 429) and consume no budget; allowed ones
        decrement every covering PDB's disruptionsAllowed optimistically,
        exactly like the registry's checkAndDecrement."""
        self._check_writable()
        with self._lock:
            pods = self._objects.get("pods", {})
            key = f"{namespace}/{name}"
            pod = pods.get(key)
            if pod is None:
                raise NotFound(f"pods {key} not found")
            if (
                pod.status.phase in ("Succeeded", "Failed")
                or pod.metadata.deletion_timestamp is not None
            ):
                # terminal or already-terminating pods disrupt nothing: no
                # PDB check, no budget charge (eviction.go deletes them
                # outright; a drain retry must not double-charge)
                covering = []
            else:
                covering = self._covering_pdbs(namespace, pod)
            for pdb in covering:
                if pdb.status.disruptions_allowed <= 0:
                    raise TooManyRequests(
                        f"Cannot evict pod as it would violate the pod's "
                        f"disruption budget {pdb.metadata.name}"
                    )
            for pdb in covering:
                pdb.status.disruptions_allowed -= 1
                self._bump(pdb)
                self._log("update", "poddisruptionbudgets", pdb)
                self._notify(
                    "poddisruptionbudgets",
                    Event(
                        MODIFIED,
                        copy.deepcopy(pdb),
                        pdb.metadata.resource_version,
                    ),
                )
        self.delete("pods", namespace, name)

    def _covering_pdbs(self, namespace: str, pod) -> list:
        from ..api.selectors import match_labels

        # NOTE no truthiness guard on the selector: the empty selector
        # matches everything (selectors.match_labels convention) — the
        # disruption controller and preemptor treat it that way, and the
        # eviction gate must agree with them
        return [
            pdb
            for pdb in self._objects.get("poddisruptionbudgets", {}).values()
            if pdb.metadata.namespace == namespace
            and match_labels(pdb.spec.selector, pod.metadata.labels)
        ]

    def bind_pod(self, binding) -> None:
        """POST pods/{name}/binding: set spec.nodeName if not already bound.

        Reference: registry/core/pod/storage BindingREST -> assignPod; the
        scheduler calls it via DefaultBinder
        (framework/plugins/defaultbinder/default_binder.go:50).
        """

        def mutate(pod):
            if pod.spec.node_name:
                raise Conflict(
                    f"pod {binding.pod_namespace}/{binding.pod_name} already bound"
                )
            if binding.pod_uid and pod.metadata.uid != binding.pod_uid:
                raise Conflict("uid mismatch on binding")
            pod.spec.node_name = binding.target_node
            return pod

        self.guaranteed_update("pods", binding.pod_namespace, binding.pod_name, mutate)


# lockset sanitizer (testing/lockgraph.py Eraser mode): the store's
# object/watcher/history maps are guarded by the `store` lock on every
# CRUD, notify, and replication-catchup path. `_rv` is deliberately NOT
# tracked: the replication heartbeat piggybacks a lock-free int peek of
# it by design (runtime/replication.py _heartbeat_loop).
track_attrs(APIServer, "_objects", "_watchers", "_history", "_evicted_rv")
