"""The bind lane: the one sender of a scheduler's in-cycle bindings.

A profile with nothing around its bind (Scheduler._binds_in_cycle) has
its bindings sent in the order the placements were committed: a later
placement may have been feasible only after an earlier one, so it must
not reach the store first. The bind pool's workers would reorder them;
the scheduling loop sending them itself kept the order but held the
next launch behind every binding request.

The lane keeps the order and frees the loop. It is one long-lived thread
that owns a FIFO of bindings: the loop hands a wave's bindings over in
commit order (a host-path pod's too) and goes on. At most one request is
in flight; when it returns, everything queued behind it leaves as the
next request, one BindingList of at most `chunk`, in FIFO order. So
waves that finish while a request is in flight coalesce into one
request, and no binding overtakes one handed over before it.

The loop waits for the lane only when a whole chunk is already queued
behind the request in flight (back-pressure). What a request's outcome
means for its entries is the sender's business (Scheduler.
_send_lane_request); take_queued hands it the entries queued behind a
request that must be the last one sent (a leadership fence's refusal).
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Any, Callable, List, NamedTuple, Optional

from ..utils.metrics import metrics

logger = logging.getLogger(__name__)

# +1 a hand-off (a wave's bindings, or one host-path pod's)
COUNTER_LANE_HANDOFFS = "scheduler_bind_lane_waves_total"
# hand-off -> the request carrying the entry leaves, one per entry
HIST_LANE_WAIT = "scheduler_bind_lane_wait_seconds"


class LaneEntry(NamedTuple):
    pi: Any  # QueuedPodInfo
    node_name: str
    prof: Any  # the pod's profile
    wave_tid: str  # the wave's trace id ("" on the host path)
    t_start: float  # the scheduling cycle's start
    t_handoff: float


class BindLane:
    """One thread, one FIFO of LaneEntry, one request in flight.

    `send(entries)` runs on the lane's thread, once a request, with at
    most `chunk` entries in hand-off order; it handles every outcome
    itself and must not raise (an exception is logged and the lane goes
    on). The thread starts with the first hand-off; close() drains the
    FIFO and ends it, after which a hand-off is sent on the caller's
    thread."""

    def __init__(self, send: Callable[[List[LaneEntry]], None], chunk: int):
        self._send = send
        self._chunk = chunk
        self._cv = threading.Condition()
        self._queue: deque = deque()
        self._inflight = 0  # entries whose request (or refusal) is running
        self._thread: Optional[threading.Thread] = None
        self._closing = False

    def put(self, entries: List[LaneEntry]) -> None:
        """Hand entries over, in order. Blocks while a whole chunk is
        already queued behind the request in flight."""
        if not entries:
            return
        metrics.inc(COUNTER_LANE_HANDOFFS)
        with self._cv:
            while (
                len(self._queue) >= self._chunk and self._thread is not None
            ):
                self._cv.wait()
            if self._thread is None and not self._closing:
                self._thread = threading.Thread(
                    target=self._run, name="bind-lane", daemon=True
                )
                self._thread.start()
            if self._thread is not None:
                self._queue.extend(entries)
                self._cv.notify_all()
                return
        # closed: nothing is queued or in flight, so sending here keeps
        # the order
        self._deliver(entries)

    def take_queued(self) -> List[LaneEntry]:
        """Every entry queued behind the request in flight, in order;
        they count as in flight until send() returns. For send() only."""
        with self._cv:
            rest = list(self._queue)
            self._queue.clear()
            self._inflight += len(rest)
            self._cv.notify_all()
        return rest

    def busy(self) -> bool:
        """Entries queued or in flight."""
        with self._cv:
            return bool(self._queue) or self._inflight > 0

    def close(self) -> None:
        """Send what is queued, then end the thread."""
        with self._cv:
            self._closing = True
            self._cv.notify_all()
            t = self._thread
        if t is not None:
            t.join()

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._queue and not self._closing:
                    self._cv.wait()
                if not self._queue:
                    self._thread = None
                    self._cv.notify_all()
                    return
                n = min(self._chunk, len(self._queue))
                batch = [self._queue.popleft() for _ in range(n)]
                self._inflight = n
                self._cv.notify_all()
            try:
                self._deliver(batch)
            finally:
                with self._cv:
                    self._inflight = 0
                    self._cv.notify_all()

    def _deliver(self, batch: List[LaneEntry]) -> None:
        t_leave = time.monotonic()
        for e in batch:
            metrics.observe(HIST_LANE_WAIT, t_leave - e.t_handoff)
        try:
            self._send(batch)
        except Exception:
            logger.exception(
                "bind lane: a request of %d bindings failed", len(batch)
            )
