"""Watch interface: typed change events over a queue.

Equivalent of apimachinery's watch.Interface
(staging/src/k8s.io/apimachinery/pkg/watch/watch.go): a result channel of
{Added, Modified, Deleted, Bookmark} events plus Stop. BOOKMARK events
carry only a resourceVersion (no object state change): the watch cache
(apiserver/cacher.py) emits them periodically so idle watchers' resume
positions keep advancing and a reconnect stays inside the replay window.
The raw store never emits them — only the cacher fan-out does.
"""

from __future__ import annotations

import queue
import threading
from dataclasses import dataclass
from typing import Any, Iterator, Optional

ADDED = "ADDED"
MODIFIED = "MODIFIED"
DELETED = "DELETED"
BOOKMARK = "BOOKMARK"


@dataclass
class Event:
    type: str
    object: Any
    resource_version: int = 0
    # fan-out enqueue timestamp (time.monotonic), stamped by the watch
    # cache's dispatch loop; lets consumers measure delivery latency
    # without a side channel. 0.0 for events from the raw store.
    ts: float = 0.0
    # wall clock of the store's commit of a CREATE (APIServer.create,
    # under the store lock, after the WAL): the start of a new pod's
    # commit -> queue admit leg, carried over both watch wires. 0.0 for
    # every other event (updates, deletes, a list's or a cache's replay
    # of state): the object's creation_timestamp stays the client's
    committed: float = 0.0


COUNTER_OVERFLOW = "watch_queue_overflow_total"


class Watcher:
    """A single watch stream; the store pushes events, the consumer iterates.

    push() and stop() are NON-BLOCKING by contract: both run on single-
    threaded dispatch paths (the store's write-path ``_notify`` fan-out,
    the watch cache's per-kind dispatch thread), where one blocking
    ``queue.put`` against a full queue wedges every watcher behind the
    loop — the CacheWatcher variant of this bug stalled the cacher
    dispatch thread on the stop() sentinel put until PR 6 overrode it.
    The discipline now lives in the base class: a consumer whose queue
    fills (maxsize events of backlog — dead, not slow) is terminated and
    counted, and stop() drops its wake-up sentinel on the floor when the
    queue is full, so iteration ends via the stopped-flag poll instead.
    """

    def __init__(self, maxsize: int = 100000):
        self._q: "queue.Queue[Optional[Event]]" = queue.Queue(maxsize=maxsize)
        self._stopped = threading.Event()

    def push(self, ev: Event) -> None:
        if self._stopped.is_set():
            return
        try:
            self._q.put_nowait(ev)
        except queue.Full:
            # a consumer maxsize events behind is gone; terminating it is
            # the only option that doesn't block the dispatch thread
            from ..utils.metrics import metrics

            metrics.inc(COUNTER_OVERFLOW)
            self.stop()

    def stop(self) -> None:
        if not self._stopped.is_set():
            self._stopped.set()
            try:
                self._q.put_nowait(None)
            except queue.Full:
                pass  # sentinel-free termination: __iter__/get poll stopped

    @property
    def stopped(self) -> bool:
        return self._stopped.is_set()

    def get(self, timeout: Optional[float] = None) -> Optional[Event]:
        """Next event, or None if stopped / timed out."""
        try:
            ev = self._q.get(timeout=timeout)
        except queue.Empty:
            return None
        return ev

    def __iter__(self) -> Iterator[Event]:
        # sentinel-free termination: a dropped sentinel (full queue at
        # stop time) must still end the iteration once the queue drains
        while True:
            try:
                ev = self._q.get(timeout=0.2)
            except queue.Empty:
                if self._stopped.is_set():
                    return
                continue
            if ev is None:
                return
            yield ev
