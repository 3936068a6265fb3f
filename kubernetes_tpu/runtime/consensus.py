"""Raft-lite commit-index consensus for the API store.

The reference's durability rides etcd raft: a write is acknowledged to the
client only once a MAJORITY of the raft group has it durably logged
(etcd raft's commit index; surfaced through storage.Interface at
staging/src/k8s.io/apiserver/pkg/storage/etcd3/store.go). The previous
build's replication layer fanned records out and *hoped*: on a quorum miss
it logged "proceeding availability-first" and returned success, so an
acknowledged write could sit only on the primary and vanish at failover —
exactly the writes for which the quorum-gated election's leader-
completeness argument stops holding.

This module is the missing piece: a real **commit index** over the
existing WAL + replication fan-out.

  * **commit index**: the largest rv held durably by a majority of the
    replica set (self included). The leader advances it from follower
    acks (each follower acks only after its own durable apply) and
    piggybacks it on every ``recs``/``hb`` frame so followers learn it
    too. It is monotonic: once committed, always committed.
  * **quorum-gated acks**: ``ship()`` blocks until the commit index
    covers the shipped records or a bounded window expires. Quorum met →
    the write is acknowledged, and by construction a majority holds it.
  * **degraded read-only mode**: on quorum miss the store does NOT lie.
    The in-flight write fails with :class:`QuorumLost` (retryable; HTTP
    503 + Retry-After through apiserver/rest.py) and the store enters an
    explicit degraded mode — subsequent writes fail fast with
    :class:`DegradedWrites` while reads and watches keep serving. The
    WAL records the epoch transition. When follower acks catch the
    commit index up to the leader's tip (a quorum again holds every
    appended record), the leader re-opens writes and logs the
    ``restored`` epoch.
  * **provably lossless failover**: election votes on
    ``(term, commit_index, last_rv)`` — rv order is log-prefix order, so
    the winner holds every committed (= client-acknowledged) write.
    scripts/consistency_check.py replays a chaos run's client-visible
    acks against surviving replica state and fails on any loss.
  * **commit-index resync**: a reconnecting follower's hello carries its
    rv; when the leader's record buffer still covers that suffix it
    replays just the tail (``catchup`` frame) instead of shipping a full
    snapshot.

Kept deliberately raft-*lite*: there is one log (the store's rv sequence),
terms come from the existing promotion/fencing protocol, and membership is
static per process lifetime. What is NOT cut is the safety core: no
acknowledgment without majority durability, no commit-index regression,
no write acceptance without a quorum connected.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..utils.metrics import metrics

logger = logging.getLogger("kubernetes_tpu.runtime.consensus")

# quorum_state gauge values (utils/metrics.py: apiserver_quorum_state)
HEALTHY = 1.0
DEGRADED = 0.0

# metrics series names: the SIGUSR2
# debugger dump (scheduler/cache/debugger.py) renders every gauge under
# this prefix, so a wedged cluster is diagnosable without logs.
GAUGE_COMMIT_INDEX = "apiserver_commit_index"
GAUGE_QUORUM_STATE = "apiserver_quorum_state"
GAUGE_FOLLOWER_LAG = "apiserver_replication_follower_lag"
GAUGE_REPLICA_TIP = "apiserver_replication_tip_rv"
COUNTER_DEGRADED_ENTRIES = "apiserver_degraded_entries_total"
COUNTER_DEGRADED_REJECTS = "apiserver_writes_rejected_degraded_total"
COUNTER_CATCHUP_RESYNCS = "apiserver_replication_catchup_resyncs_total"
COUNTER_SNAPSHOT_RESYNCS = "apiserver_replication_snapshot_resyncs_total"


class DegradedWrites(RuntimeError):
    """Write rejected: the store is in degraded read-only mode because a
    quorum of the replica set is not caught up. Retryable — surfaced as
    HTTP 503 + Retry-After by apiserver/rest.py; reads and watches keep
    serving. Distinct from NotPrimary (a fenced store never re-opens)."""

    retry_after_s = 1.0


class QuorumLost(DegradedWrites):
    """THIS write missed quorum inside the ack window. Its outcome is
    unknown (the record is durable locally and streamed to followers; it
    may yet commit) — the one honest answer is "not acknowledged, retry".
    Raising it also flips the store into degraded read-only mode."""


class DiskFailed(DegradedWrites):
    """Write rejected: this replica's WAL sink hit a write/fsync error and
    is fail-stopped (runtime/wal.py SinkFailed — the fsyncgate discipline:
    a failed fsync is never retried). Permanent for THIS process; the
    503 + Retry-After is still honest because a leader with a failed disk
    releases its lease and a disk-healthy replica promotes, so retries
    land somewhere writable."""


class DiskPressure(DegradedWrites):
    """Write rejected: the WAL volume is under disk pressure (low-watermark
    probe tripped, or an append hit ENOSPC and was rolled back). Lifts
    automatically when free space recovers — compaction is attempted as
    reclaim — so this IS plainly retryable."""


class RecordBuffer:
    """Bounded in-memory tail of the leader's replicated log, for
    commit-index resync: a reconnecting follower at rv R gets the
    ``(R, tip]`` suffix replayed instead of a full snapshot whenever the
    buffer still covers R+1. Entries are wire-encoded records
    ``[rv, verb, kind, data]`` in strict rv order."""

    def __init__(self, maxlen: int = 50_000):
        self.maxlen = maxlen
        self._recs: List[list] = []
        self._lock = threading.Lock()

    def extend(self, recs: List[list]) -> None:
        with self._lock:
            self._recs.extend(recs)
            if len(self._recs) > self.maxlen:
                del self._recs[: len(self._recs) - self.maxlen]

    def since(self, rv: int) -> Optional[List[list]]:
        """Records with rv' > rv, or None when the suffix is no longer
        fully buffered (caller must fall back to a snapshot)."""
        with self._lock:
            if not self._recs:
                return None if rv < 0 else []
            if self._recs[0][0] > rv + 1:
                return None  # gap: the tail was evicted past rv
            return [r for r in self._recs if r[0] > rv]

    def __len__(self) -> int:
        with self._lock:
            return len(self._recs)


class ConsensusCoordinator:
    """Leader-side commit-index authority for one replica set.

    Owns: per-follower match indices, the monotonic commit index, the
    healthy/degraded epoch state, the WAL epoch records, and the metrics
    gauges. The ReplicationListener feeds it (local appends, follower
    acks/drops) and blocks on :meth:`wait_commit`; the APIServer's write
    gate (runtime/store.py) consults :meth:`check_writable` before any
    mutation is applied."""

    def __init__(
        self,
        cluster_size: int,
        term: int = 1,
        window_s: float = 0.75,
        buffer_len: int = 50_000,
    ):
        if cluster_size < 1:
            raise ValueError(f"cluster_size must be >= 1, got {cluster_size}")
        self.cluster_size = cluster_size
        self.term = term
        self.window_s = window_s
        self.buffer = RecordBuffer(buffer_len)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._match: Dict[int, int] = {}  # follower id -> durably acked rv
        self._tip = 0  # leader's own last durable rv
        self._commit = 0  # monotonic commit index
        self._degraded = False
        self._degraded_since: Optional[float] = None
        self._wal = None  # epoch-transition records land here
        self._on_reopen: List[Callable[[], None]] = []
        self._publish_locked()

    # -- wiring ---------------------------------------------------------------

    def attach_wal(self, wal) -> None:
        self._wal = wal

    def on_reopen(self, cb: Callable[[], None]) -> None:
        """Register a callback fired (off-lock) when degraded mode lifts."""
        self._on_reopen.append(cb)

    # -- quorum math ----------------------------------------------------------

    @property
    def majority(self) -> int:
        """Replicas (self included) that must hold a record durably."""
        return self.cluster_size // 2 + 1

    def _commit_candidate_locked(self) -> int:
        """Largest rv held by a majority: k-th largest of the match vector
        padded with zeros for unseen members (raft's matchIndex median)."""
        held = sorted([self._tip] + list(self._match.values()), reverse=True)
        held += [0] * max(0, self.cluster_size - len(held))
        return held[self.majority - 1]

    # -- leader-side events ---------------------------------------------------

    def local_append(self, rv: int, recs: Optional[List[list]] = None) -> None:
        """The leader durably appended up to rv (WAL fsync done); buffer
        the wire records for commit-index resync of reconnectors."""
        if recs:
            self.buffer.extend(recs)
        with self._cond:
            if rv > self._tip:
                self._tip = rv
            reopened = self._advance_locked()
        if reopened:
            self._after_reopen()

    def follower_ack(self, follower_id: int, rv: int) -> None:
        """A follower durably holds up to rv. Advances the commit index;
        lifts degraded mode when a quorum has caught the tip."""
        with self._cond:
            if rv > self._match.get(follower_id, 0):
                self._match[follower_id] = rv
            reopened = self._advance_locked()
        if reopened:
            self._after_reopen()

    def forget(self, follower_id: int) -> None:
        """Follower link died: its future acks can no longer advance the
        quorum. The commit index never regresses (committed is forever)."""
        with self._cond:
            self._match.pop(follower_id, None)
            self._publish_locked()
        # retire the departed link's lag series: a stale gauge would read
        # as a live in-sync replica in the SIGUSR2 dump
        metrics.remove_gauge(
            GAUGE_FOLLOWER_LAG, labels={"follower": str(follower_id)}
        )

    def _advance_locked(self) -> bool:
        """Recompute the commit index under the lock. Returns True when
        degraded mode just lifted — the caller runs _after_reopen() OFF
        the lock (the epoch WAL append and callbacks must not nest it)."""
        cand = self._commit_candidate_locked()
        if cand > self._commit:
            self._commit = cand
            self._cond.notify_all()
        reopened = False
        if self._degraded and self._commit >= self._tip:
            # a quorum again holds EVERY appended record: re-open writes
            self._degraded = False
            self._degraded_since = None
            reopened = True
        self._publish_locked()
        return reopened

    def _after_reopen(self) -> None:
        self._log_epoch("restored")
        logger.warning(
            "write quorum restored at commit_index=%d (tip=%d): "
            "leaving degraded read-only mode", self.commit_index, self.tip,
        )
        for cb in list(self._on_reopen):
            try:
                cb()
            except Exception:
                logger.exception("consensus reopen callback failed")

    # -- ship-path gate -------------------------------------------------------

    def wait_commit(self, rv: int, window_s: Optional[float] = None) -> bool:
        """Block until commit_index >= rv or the window expires. True =
        committed (the caller may acknowledge the write)."""
        deadline = time.monotonic() + (
            self.window_s if window_s is None else window_s
        )
        with self._cond:
            while self._commit < rv:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(remaining)
            return True

    def quorum_miss(self, rv: int) -> Optional[QuorumLost]:
        """The write at rv missed its window: enter degraded read-only
        mode (idempotent) and return the exception the write path must
        raise — the client is NOT acknowledged. Returns None when an ack
        raced the window expiry and the commit index already covers rv —
        the write IS committed and must be acknowledged normally;
        entering degraded mode then would wedge a healthy store
        read-only forever (nothing would ever lift it: rejected writes
        don't append, and caught-up followers send no further acks)."""
        with self._cond:
            if self._commit >= rv:
                return None
            entered = not self._degraded
            if entered:
                self._degraded = True
                self._degraded_since = time.monotonic()
                metrics.inc(COUNTER_DEGRADED_ENTRIES)
            self._publish_locked()
            commit, needed = self._commit, self.majority
        if entered:
            self._log_epoch("degraded")
            logger.error(
                "write quorum NOT met for rv=%d (commit_index=%d, need %d/%d "
                "replicas): entering degraded READ-ONLY mode until a quorum "
                "catches up; the in-flight write is NOT acknowledged",
                rv, commit, needed, self.cluster_size,
            )
        return QuorumLost(
            f"write quorum lost: rv {rv} not committed "
            f"(commit_index={commit}, majority={needed}/{self.cluster_size}); "
            "store is degraded read-only — retry after quorum recovery"
        )

    def check_writable(self) -> None:
        """Degraded-mode gate, consulted by the store BEFORE applying any
        mutation (runtime/store.py WriteGate): fail fast instead of
        burning an ack window per rejected write."""
        if self._degraded:
            metrics.inc(COUNTER_DEGRADED_REJECTS)
            with self._lock:
                commit, tip = self._commit, self._tip
            raise DegradedWrites(
                f"store degraded read-only: write quorum lost "
                f"(commit_index={commit}, tip={tip}); reads and watches "
                "still serve — retry later"
            )

    # -- introspection --------------------------------------------------------

    @property
    def commit_index(self) -> int:
        with self._lock:
            return self._commit

    @property
    def degraded(self) -> bool:
        with self._lock:
            return self._degraded

    @property
    def tip(self) -> int:
        with self._lock:
            return self._tip

    def acked_quorum_size(self, rv: int) -> int:
        """Replicas (self included) known to durably hold rv — the test
        hook behind "an ack implies commit_index >= rv on a majority"."""
        with self._lock:
            n = 1 if self._tip >= rv else 0
            return n + sum(1 for v in self._match.values() if v >= rv)

    def state(self) -> Dict[str, Any]:
        """Structured dump for the SIGUSR2 debugger and tests."""
        with self._lock:
            return {
                "term": self.term,
                "cluster_size": self.cluster_size,
                "majority": self.majority,
                "tip": self._tip,
                "commit_index": self._commit,
                "quorum_state": "degraded" if self._degraded else "healthy",
                "degraded_for_s": (
                    round(time.monotonic() - self._degraded_since, 3)
                    if self._degraded_since is not None
                    else 0.0
                ),
                "follower_match": dict(self._match),
                "follower_lag": {
                    fid: self._tip - rv for fid, rv in self._match.items()
                },
                "buffered_records": len(self.buffer),
            }

    # -- internals ------------------------------------------------------------

    def _publish_locked(self) -> None:
        # scalars only: this runs on every local append AND every
        # follower ack (the write hot path). The per-follower lag series
        # is O(followers) metrics-lock traffic and is refreshed from the
        # heartbeat loop instead (publish_follower_lags).
        metrics.set_gauge(GAUGE_COMMIT_INDEX, float(self._commit))
        metrics.set_gauge(GAUGE_REPLICA_TIP, float(self._tip))
        metrics.set_gauge(
            GAUGE_QUORUM_STATE, DEGRADED if self._degraded else HEALTHY
        )

    def publish_follower_lags(self) -> None:
        """Refresh the per-follower lag gauges — called once per
        heartbeat beat (runtime/replication.py), OFF the write path."""
        with self._lock:
            lags = {fid: max(self._tip - rv, 0) for fid, rv in self._match.items()}
        for fid, lag in lags.items():
            metrics.set_gauge(
                GAUGE_FOLLOWER_LAG, float(lag), labels={"follower": str(fid)}
            )

    def _log_epoch(self, event: str) -> None:
        """Durable epoch-transition record: recovery (and the consistency
        checker) can see exactly when acks stopped being quorum-backed."""
        wal = self._wal
        if wal is None:
            return
        with self._lock:
            tip, commit = self._tip, self._commit
        try:
            wal.append_commit(tip, commit, self.term, event)
        except OSError:
            logger.exception("failed to log %s epoch transition", event)


def vote_key(status: Dict[str, Any]) -> Tuple[int, int, int, int]:
    """Election ordering over (term, commit_index, last_rv): term first
    (raft's up-to-date check), then rv (log length; rv order is log-
    prefix order), then the candidate's HELD commit (its commit claim
    capped at its rv), then id as the deterministic tiebreak.

    rv deliberately outranks the commit claim: a lagging follower can
    LEARN a high commit index from a heartbeat without HOLDING the
    committed records (commit rides every hb frame), and ranking that
    claim above log length would elect it over the follower that
    actually has them — losing acknowledged writes. Raft's ballot is
    (term, lastLogIndex) for exactly this reason. The commit index still
    gates the election, as a floor: a candidate whose rv is below any
    learned commit index refuses to promote at all (the known_commit
    check in Follower._run_election) — it KNOWS acknowledged writes
    exist that it does not hold."""
    rv = int(status.get("rv", 0))
    return (
        int(status.get("term", 0)),
        rv,
        min(int(status.get("commit", 0)), rv),
        int(status.get("id", -1)),
    )


def log_key(status: Dict[str, Any]) -> Tuple[int, int, int]:
    """vote_key without the node-id tiebreak: the voter-side up-to-date
    check (raft §5.4.1). A voter grants to any candidate whose log is AT
    LEAST as up-to-date as its own — including exact ties, or two equally
    caught-up candidates would each self-vote and refuse the other
    forever (the id tiebreak belongs to ranking, not to grant
    eligibility; dueling ties resolve by jittered election timing)."""
    return vote_key(status)[:3]
