"""PriorityQueue: the three-part scheduling queue.

Reference pkg/scheduler/internal/queue/scheduling_queue.go:117-152:
  * activeQ     — heap ordered by the QueueSort plugin (priority desc, FIFO)
  * podBackoffQ — heap by backoff expiry; backoff 1s→10s doubling (:643)
  * unschedulableQ — map, flushed by events (MoveAllToActiveOrBackoffQueue
    :494) or after 60s (flushUnschedulableQLeftover)
plus the nominated-pods map for preemption.

TPU addition: `pop_batch(max_n, window)` pops up to a device batch of pods in
one call (the batch former of SURVEY.md §7 stage 4) — the reference pops one
pod per cycle; the device path amortizes one kernel launch over the batch.

The waiting population — activeQ plus the batch `pop_batch` is forming —
is integrated over time where it changes (`waiting()`): the scheduler's
PhaseTracker credits the pod-seconds to the loop phase that held them.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from ...api import objects as v1
from ...testing.lockgraph import named_lock, track_attrs
from ...utils.metrics import metrics
from ...utils.tracing import note_pass, tracer
from .heap import Heap


@dataclass
class QueuedPodInfo:
    pod: v1.Pod
    timestamp: float = field(default_factory=time.monotonic)
    attempts: int = 0
    initial_attempt_timestamp: float = field(default_factory=time.monotonic)
    backoff_expiry: float = 0.0
    # minted at queue admission (utils/tracing.py): the id every span of
    # this pod's lifecycle — and its cross-process bind stamp — lands under
    trace_id: str = ""
    # when this pod LAST entered a queue, for the `queue` span only:
    # readd() must refresh it without touching `timestamp` (which orders
    # the heap — resetting it would demote a deferred pod behind fresh
    # arrivals), or a deferred pod's next queue span re-spans from its
    # original admission and double-counts the prior cycle as queue wait
    trace_queued_at: float = field(default_factory=time.monotonic)

    @property
    def key(self) -> str:
        return self.pod.metadata.key


class PriorityQueue:
    def __init__(
        self,
        less: Optional[Callable[[QueuedPodInfo, QueuedPodInfo], bool]] = None,
        pod_initial_backoff: float = 1.0,
        pod_max_backoff: float = 10.0,
        unschedulable_timeout: float = 60.0,
        clock: Callable[[], float] = time.monotonic,
    ):
        # named for the lock-order watchdog + lockset sanitizer
        # (testing/lockgraph.py); _cond shares the SAME lock, so both
        # spellings record as "scheduler.queue"
        self._lock = named_lock("scheduler.queue")
        self._cond = threading.Condition(self._lock)
        if less is None:
            less = lambda a, b: (
                (a.pod.priority, -a.timestamp) > (b.pod.priority, -b.timestamp)
            )
        self._active = Heap(lambda pi: pi.key, less)
        self._backoff = Heap(
            lambda pi: pi.key, lambda a, b: a.backoff_expiry < b.backoff_expiry
        )
        self._unschedulable: Dict[str, QueuedPodInfo] = {}
        self._initial_backoff = pod_initial_backoff
        self._max_backoff = pod_max_backoff
        self._unsched_timeout = unschedulable_timeout
        self._nominated: Dict[str, str] = {}  # pod key -> node name
        self._nominated_by_node: Dict[str, set] = {}
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self.moves = 0  # MoveAllToActiveOrBackoffQueue invocations (metrics)
        # the waiting population (activeQ + pods pop_batch has taken but
        # not yet returned), integrated: (pod-seconds up to `at`, pods
        # since `at`, at, pods pop_batch has handed out). Rewritten whole
        # under the queue lock where the population changes, read whole
        # without it (waiting())
        self._clock = clock
        self._forming = 0
        self._wait = (0.0, 0, clock(), 0)

    # -- lifecycle ----------------------------------------------------------

    def run(self) -> None:
        """Start flush loops (scheduling_queue.go:234: backoff every 1s,
        unschedulable leftover every 30s)."""
        for period, fn, task in (
            (1.0, self.flush_backoff_completed, "queue_flush_backoff"),
            (30.0, self._flush_unschedulable_leftover,
             "queue_flush_unschedulable"),
        ):
            t = threading.Thread(
                target=self._loop, args=(period, fn, task), daemon=True
            )
            t.start()
            self._threads.append(t)

    def _loop(self, period: float, fn, task: str) -> None:
        while not self._stop.wait(period):
            t0 = time.monotonic()
            fn()
            # a flush holds the queue lock the scheduling loop pops
            # under: its start and length, observed outside that lock
            dt = time.monotonic() - t0
            metrics.observe(
                "scheduler_background_pass_seconds", dt, {"task": task}
            )
            note_pass(task, t0, dt)

    def close(self) -> None:
        self._stop.set()
        with self._cond:
            self._cond.notify_all()

    # -- the waiting population ---------------------------------------------

    def _note_wait_locked(self, handed: int = 0) -> None:
        """Caller holds the lock and has just changed activeQ or the
        forming batch: close the integral at now under the old count."""
        area, n, at, out = self._wait
        now = self._clock()
        self._wait = (
            area + n * (now - at),
            len(self._active) + self._forming,
            now,
            out + handed,
        )

    def waiting(self) -> tuple:
        """(pod-seconds waited up to `at`, pods waiting since `at`, at,
        pods handed out by pop_batch): one read, no lock. The pod-seconds
        at a later instant t are ``area + n * (t - at)``."""
        return self._wait  # graftlint: unguarded(one tuple, rewritten whole under the lock: a read sees one consistent state)

    # -- adds ---------------------------------------------------------------

    def add(self, pod: v1.Pod, committed: float = 0.0) -> None:
        """`committed`: the store's commit instant (wall) of the pod's
        create when the watch delivered it — a first admission; the
        commit -> admit lag is then the trace's ``admit_lag_s`` and one
        observation of ``scheduler_pod_admit_lag_seconds``. 0.0 (a
        relist, a direct add) observes nothing."""
        # mint the trace OUTSIDE the queue lock (tracing.ring is a leaf,
        # but the admit itself needs nothing the lock guards); the lag is
        # a wall-clock delta, an attribute, never mixed into the spans
        lag = None
        if committed and tracer.enabled:
            lag = round(max(time.time() - committed, 0.0), 6)
        tid = tracer.start("pod", pod.metadata.key, admit_lag_s=lag)
        with self._cond:
            pi = QueuedPodInfo(pod, trace_id=tid)
            self._active.add(pi)
            self._backoff.delete_by_key(pi.key)
            self._unschedulable.pop(pi.key, None)
            self._note_wait_locked()
            self._cond.notify()

    def readd(self, pi: QueuedPodInfo) -> None:
        """Return a popped-but-unprocessed pod to activeQ preserving its
        QueuedPodInfo (used for wave-deferred pods: feasible nodes existed
        but in-batch contention ran out of waves — not a scheduling failure,
        so no backoff and no attempt decay)."""
        with self._cond:
            pi.attempts = max(pi.attempts - 1, 0)
            pi.trace_queued_at = time.monotonic()
            self._active.add(pi)
            self._note_wait_locked()
            self._cond.notify()

    def add_unschedulable_if_not_present(
        self, pi: QueuedPodInfo, moves_at_failure: int
    ) -> None:
        """Failed pod re-entry (AddUnschedulableIfNotPresent:300): if a move
        event fired while the pod was being scheduled, it goes to backoffQ
        (something changed — retry soon); else unschedulableQ."""
        tracer.event(pi.trace_id, "queue.unschedulable")
        with self._cond:
            key = pi.key
            if key in self._active or key in self._backoff or key in self._unschedulable:
                return
            pi.timestamp = time.monotonic()
            pi.trace_queued_at = pi.timestamp
            if self.moves != moves_at_failure:
                pi.backoff_expiry = self._backoff_time(pi)
                self._backoff.add(pi)
            else:
                self._unschedulable[key] = pi

    def _backoff_time(self, pi: QueuedPodInfo) -> float:
        """Backoff expiry relative to the pod's LAST FAILURE (pi.timestamp
        — every failure path stamps it), not to "now". The reference's
        podBackoffQ keys expiry on lastFailure + backoffDuration
        (scheduling_queue.go isPodBackingoff): a move event must flush a
        pod whose backoff already elapsed straight to activeQ. The old
        now-relative form re-armed the full backoff on every
        MoveAllToActiveOrBackoffQueue, so a pod that had sat in
        unschedulableQ for minutes still waited out a fresh 1-10 s after
        the node-add that could place it — breaking the autoscaler's
        "pending pods bind within one period" guarantee."""
        d = self._initial_backoff * (2 ** max(pi.attempts - 1, 0))
        return pi.timestamp + min(d, self._max_backoff)

    def requeue_backoff(self, pi: QueuedPodInfo) -> None:
        """Re-queue a RETRYABLE pod through backoffQ (not unschedulableQ):
        it was feasible but lost a structural contention (e.g. an
        all-deferred hard-spread batch) — an immediate readd would hot-loop
        the identical conflict, and unschedulableQ would mislabel it (and
        sit out the flush interval). Backoff retries in 1-10 s."""
        tracer.event(pi.trace_id, "queue.backoff")
        with self._cond:
            if (
                pi.key in self._active
                or pi.key in self._backoff
                or pi.key in self._unschedulable
            ):
                return
            pi.timestamp = time.monotonic()
            pi.trace_queued_at = pi.timestamp
            pi.backoff_expiry = self._backoff_time(pi)
            self._backoff.add(pi)

    # -- pops ---------------------------------------------------------------

    def pop(
        self, timeout: Optional[float] = None, on_pop=None
    ) -> Optional[QueuedPodInfo]:
        """on_pop: invoked UNDER the queue lock before the first item is
        removed — the scheduler marks itself busy there, so no observer
        can ever see "queue empty and scheduler not busy" between a pop
        and the popped batch entering the in-flight pipeline."""
        with self._cond:
            pi = self._pop_locked(timeout, on_pop)
            if pi is not None:
                self._note_wait_locked()  # it left the waiting population
            return pi

    def _pop_locked(self, timeout, on_pop) -> Optional[QueuedPodInfo]:
        deadline = None if timeout is None else time.monotonic() + timeout
        while len(self._active) == 0 and not self._stop.is_set():
            rem = None if deadline is None else deadline - time.monotonic()
            if rem is not None and rem <= 0:
                return None
            self._cond.wait(rem if rem is None or rem < 0.1 else 0.1)
        if self._stop.is_set():
            return None
        if on_pop is not None:
            on_pop()
        pi = self._active.pop()
        if pi is not None:
            pi.attempts += 1
        return pi

    def pop_batch(
        self,
        max_n: int,
        timeout: Optional[float] = None,
        window: float = 0.0,
        on_first=None,
    ) -> List[QueuedPodInfo]:
        """Pop up to max_n pods: block for the first, then drain without
        blocking (optionally lingering up to `window` seconds to let a burst
        accumulate — the gang/batch former).

        The linger is ADAPTIVE (r4 verdict #4): it holds only while the
        producer is actively producing. Once no new pod has arrived for
        `idle_gap` the batch ships immediately — a lone low-load pod pays
        ~3 ms of former latency instead of the full window, while a burst
        mid-arrival keeps accumulating up to `window`.

        A popped pod stays in the waiting population (it moved from
        activeQ into the forming batch) until the batch is returned."""
        idle_gap = min(0.003, window) if window > 0 else 0.0
        with self._cond:
            first = self._pop_locked(timeout, on_first)
            if first is None:
                return []
            self._forming += 1
        out = [first]
        deadline = time.monotonic() + window
        last_arrival = time.monotonic()
        while True:
            with self._cond:
                pi = self._active.pop() if len(out) < max_n else None
                if pi is not None:
                    pi.attempts += 1
                    self._forming += 1
                    out.append(pi)
                    last_arrival = time.monotonic()
                    continue
                now = time.monotonic()
                if not (len(out) < max_n and window > 0 and now < deadline
                        and now - last_arrival < idle_gap):
                    self._forming -= len(out)
                    self._note_wait_locked(handed=len(out))
                    return out
            time.sleep(0.0005)

    # -- event-driven movement ----------------------------------------------

    def move_all_to_active_or_backoff(self, event: str) -> None:
        """(scheduling_queue.go:494) — every unschedulable pod re-enters
        either backoffQ (still backing off) or activeQ."""
        with self._cond:
            self.moves += 1
            now = time.monotonic()
            for key, pi in list(self._unschedulable.items()):
                expiry = self._backoff_time(pi)
                if expiry > now:
                    pi.backoff_expiry = expiry
                    self._backoff.add(pi)
                else:
                    self._active.add(pi)
                del self._unschedulable[key]
            self._note_wait_locked()
            self._cond.notify_all()

    def flush_backoff_completed(self) -> None:
        with self._cond:
            now = time.monotonic()
            while True:
                pi = self._backoff.peek()
                if pi is None or pi.backoff_expiry > now:
                    break
                self._backoff.pop()
                self._active.add(pi)
                self._note_wait_locked()
                self._cond.notify()

    def _flush_unschedulable_leftover(self) -> None:
        with self._cond:
            now = time.monotonic()
            moved = False
            for key, pi in list(self._unschedulable.items()):
                if now - pi.timestamp > self._unsched_timeout:
                    del self._unschedulable[key]
                    pi.backoff_expiry = self._backoff_time(pi)
                    if pi.backoff_expiry > now:
                        self._backoff.add(pi)
                    else:
                        self._active.add(pi)
                        moved = True
            if moved:
                self._note_wait_locked()
                self._cond.notify_all()

    # -- update/delete (informer-driven) ------------------------------------

    def update(self, old: Optional[v1.Pod], new: v1.Pod) -> None:
        with self._cond:
            key = new.metadata.key
            # the queue's own heaps, not the API store
            for q in (self._active, self._backoff):
                pi = q.get(key)
                if pi is not None:
                    pi.pod = new
                    q.update(pi)
                    return
            pi = self._unschedulable.get(key)
            if pi is not None:
                pi.pod = new
                # spec update may make it schedulable again
                if _significant_update(old, new):
                    del self._unschedulable[key]
                    self._active.add(pi)
                    self._note_wait_locked()
                    self._cond.notify()

    def delete(self, pod: v1.Pod) -> None:
        with self._cond:
            key = pod.metadata.key
            tid = ""
            for q in (self._active, self._backoff):
                pi = q.get(key)
                if pi is not None:
                    tid = pi.trace_id
            pi = self._unschedulable.get(key)
            if pi is not None:
                tid = pi.trace_id
            self._active.delete_by_key(key)
            self._backoff.delete_by_key(key)
            self._unschedulable.pop(key, None)
            self._note_wait_locked()
            self.delete_nominated_if_exists(pod)
        # pod deleted while queued: no lifecycle left to attribute
        tracer.discard(tid)

    def delete_if_uid(self, pod: v1.Pod) -> bool:
        """Delete the queued entry for pod's key ONLY while it still
        holds the same uid. The leader-adoption pass runs concurrently
        with informer delete/recreate churn: a blind by-key delete could
        remove a RECREATED pod's fresh entry and strand it (the informer
        stream itself is ordered, so its own handlers don't need this)."""
        with self._cond:
            key = pod.metadata.key
            uid = pod.metadata.uid
            for q in (self._active, self._backoff):
                pi = q.get(key)
                if pi is not None:
                    if pi.pod.metadata.uid != uid:
                        return False
                    q.delete_by_key(key)
                    self._note_wait_locked()
                    self.delete_nominated_if_exists(pod)
                    return True
            pi = self._unschedulable.get(key)
            if pi is not None and pi.pod.metadata.uid == uid:
                del self._unschedulable[key]
                self.delete_nominated_if_exists(pod)
                return True
            return False

    # -- nominated pods ------------------------------------------------------

    def add_nominated_pod(self, pod: v1.Pod, node_name: str) -> None:
        with self._lock:
            key = pod.metadata.key
            self.delete_nominated_if_exists(pod)
            self._nominated[key] = node_name
            self._nominated_by_node.setdefault(node_name, set()).add(key)

    def delete_nominated_if_exists(self, pod: v1.Pod) -> None:
        with self._lock:
            key = pod.metadata.key
            node = self._nominated.pop(key, None)
            if node is not None:
                self._nominated_by_node.get(node, set()).discard(key)

    def nominated_pods_for_node(self, node_name: str) -> List[str]:
        with self._lock:
            return sorted(self._nominated_by_node.get(node_name, set()))

    # -- introspection -------------------------------------------------------

    def moves_snapshot(self) -> int:
        """The move-event counter, read under the queue lock. The
        scheduler captures it before a scheduling attempt and compares at
        failure time (AddUnschedulableIfNotPresent's movesAtFailure);
        the bare attribute is for lock-holding internals only — the
        lockset sanitizer caught the scheduler reading it bare."""
        with self._lock:
            return self.moves

    def unschedulable_pod_infos(self) -> List[QueuedPodInfo]:
        """Snapshot of unschedulableQ (the autoscaler's scale-up input):
        pods the scheduler proved don't fit the CURRENT cluster. Read-only
        — entries stay queued; the autoscaler's node-add events flush them
        back to activeQ through the normal move machinery."""
        with self._lock:
            return list(self._unschedulable.values())

    def pending_pod_infos(self) -> List[QueuedPodInfo]:
        """Snapshot of EVERY queued pod (activeQ + backoffQ +
        unschedulableQ): the leader-adoption pass reads each back from
        the store on promotion. Read-only — entries stay queued; the
        adoption pass deletes the ones the store says are bound or gone
        through the normal delete path."""
        with self._lock:
            return (
                self._active.list()
                + self._backoff.list()
                + list(self._unschedulable.values())
            )

    def pending_pods(self) -> dict:
        with self._lock:
            return {
                "active": [pi.key for pi in self._active.list()],
                "backoff": [pi.key for pi in self._backoff.list()],
                "unschedulable": sorted(self._unschedulable.keys()),
            }

    def __len__(self) -> int:
        with self._lock:
            return len(self._active) + len(self._backoff) + len(self._unschedulable)

    def active_len(self) -> int:
        """Pods poppable RIGHT NOW (activeQ only — backoff/unschedulable
        pods are not available to the batch former)."""
        with self._lock:
            return len(self._active)


# lockset sanitizer (testing/lockgraph.py Eraser mode): the queue's
# heaps, the unschedulable/nominated maps, and the move counter are the
# shared state every scheduler/informer/autoscaler thread touches —
# chaos suites assert their lockset never goes empty
track_attrs(
    PriorityQueue,
    "_active",
    "_backoff",
    "_unschedulable",
    "_nominated",
    "_nominated_by_node",
    "moves",
    "_forming",
)


def _significant_update(old: Optional[v1.Pod], new: v1.Pod) -> bool:
    """UpdatePodInSchedulingQueue / isPodUpdated: ignore pure status churn."""
    if old is None:
        return True
    return (
        old.spec != new.spec
        or old.metadata.labels != new.metadata.labels
        or old.metadata.annotations != new.metadata.annotations
    )
