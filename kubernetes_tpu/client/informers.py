"""Shared informers: list+watch replay into local indexers and handlers.

Equivalent of client-go's Reflector (tools/cache/reflector.go:210
ListAndWatch) + DeltaFIFO + sharedIndexInformer (shared_informer.go), with
the simplification the in-process store allows: the watch stream is lossless
and ordered, so the delta queue collapses into direct dispatch on the
informer thread. Handlers see the same contract: OnAdd/OnUpdate/OnDelete
after an initial synthetic Add per listed object, HasSynced after the initial
list is delivered.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Callable, Dict, List, Optional

from ..runtime.store import Indexer, IndexFunc
from ..runtime.watch import ADDED, BOOKMARK, DELETED, MODIFIED
from ..utils.metrics import metrics

from .apiserver import APIServer, Expired

logger = logging.getLogger("kubernetes_tpu.client.informers")

# relist backoff for the ListAndWatch restart loop: grows on consecutive
# failures (Expired/410, list errors, watch streams dying at birth), resets
# to the floor once a re-established watch actually delivers an event
RELIST_BACKOFF_INITIAL = 0.05
RELIST_BACKOFF_CAP = 5.0
COUNTER_RELISTS = "informer_relists_total"  # labels: kind, reason
# bookmark events consumed (resume position advanced, no handlers invoked)
COUNTER_BOOKMARKS = "informer_bookmarks_total"  # labels: kind
# watch streams resumed at last_resource_version WITHOUT a re-list (the
# watch-cache window absorbed the flap)
COUNTER_RESUMES = "informer_watch_resumes_total"  # labels: kind


class ResourceEventHandler:
    """Duck-typed handler; subclass or pass callables to SharedInformer.add_handler."""

    def on_add(self, obj: Any) -> None:  # pragma: no cover - interface
        pass

    def on_update(self, old: Any, new: Any) -> None:  # pragma: no cover
        pass

    def on_delete(self, obj: Any) -> None:  # pragma: no cover
        pass


class _FuncHandler(ResourceEventHandler):
    def __init__(self, on_add=None, on_update=None, on_delete=None, filter_fn=None):
        self._add, self._update, self._delete = on_add, on_update, on_delete
        self._filter = filter_fn

    def on_add(self, obj):
        if self._add and (self._filter is None or self._filter(obj)):
            self._add(obj)

    def on_update(self, old, new):
        if self._filter is None:
            if self._update:
                self._update(old, new)
            return
        # FilteringResourceEventHandler semantics (client-go shared_informer):
        # filter old and new independently; add/delete on transition.
        old_ok = self._filter(old)
        new_ok = self._filter(new)
        if old_ok and new_ok:
            if self._update:
                self._update(old, new)
        elif not old_ok and new_ok and self._add:
            self._add(new)
        elif old_ok and not new_ok and self._delete:
            self._delete(old)

    def on_delete(self, obj):
        if self._delete and (self._filter is None or self._filter(obj)):
            self._delete(obj)


class SharedInformer:
    def __init__(
        self,
        server: APIServer,
        kind: str,
        indexers: Optional[Dict[str, IndexFunc]] = None,
    ):
        self.kind = kind
        self._server = server
        self.indexer = Indexer(indexers=indexers)
        self._handlers: List[ResourceEventHandler] = []
        self._synced = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._watcher = None
        self._relist_backoff = RELIST_BACKOFF_INITIAL
        # resume position: the rv of the last event (or bookmark) this
        # informer has fully processed. A dying watch stream reconnects
        # HERE instead of re-listing; only a true 410 — the watch cache
        # evicted events past this position — forces the relist.
        self.last_resource_version = 0
        self._resume = False  # True: skip the list, watch from last rv
        # the store's commit instant (wall) of the ADDED event whose
        # handlers run right now (Event.committed): read by an add
        # handler on this informer's thread; 0.0 during a list's replay,
        # an update's or a delete's handlers — no create is being delivered
        self.event_committed = 0.0

    def add_handler(
        self,
        on_add: Optional[Callable[[Any], None]] = None,
        on_update: Optional[Callable[[Any, Any], None]] = None,
        on_delete: Optional[Callable[[Any], None]] = None,
        filter_fn: Optional[Callable[[Any], bool]] = None,
    ) -> None:
        self._handlers.append(_FuncHandler(on_add, on_update, on_delete, filter_fn))

    def add_event_handler(self, handler: ResourceEventHandler) -> None:
        self._handlers.append(handler)

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, name=f"informer-{self.kind}", daemon=True
        )
        self._thread.start()

    def _replace(self, objs) -> None:
        """Replace-semantics sync (the reflector's DeltaFIFO Replace):
        DELETE + on_delete anything the indexer holds that the list no
        longer contains (a plain upsert replay would leave ghosts for
        objects deleted during a watch gap), on_update for keys already
        known, on_add only for genuinely new ones — a relist must not
        replay the world as adds: add handlers legitimately treat an add
        as new state (queue re-activation, cache accounting), and a
        flapping watch would hammer them with the full object set per
        flap. The filtering handler wrapper turns updates that cross its
        predicate into the right add/delete, so objects that changed
        sides during the gap still land correctly."""
        listed = {o.metadata.key for o in objs}
        for stale_key in [
            k for k in (o.metadata.key for o in self.indexer.list())
            if k not in listed
        ]:
            gone = self.indexer.get(stale_key)
            if gone is None:
                continue
            self.indexer.delete(gone)
            for h in self._handlers:
                h.on_delete(gone)
        for obj in objs:
            old = self.indexer.get(obj.metadata.key)
            self.indexer.add(obj)
            if old is None:
                for h in self._handlers:
                    h.on_add(obj)
            else:
                for h in self._handlers:
                    h.on_update(old, obj)

    def _sleep_backoff(self) -> bool:
        """Sleep the current backoff and grow it. True when stopping."""
        if self._stop.wait(self._relist_backoff):
            return True
        self._relist_backoff = min(self._relist_backoff * 2, RELIST_BACKOFF_CAP)
        return False

    def _backoff_failure(self, reason: str) -> bool:
        """Count one relist cause, sleep the current backoff, grow it.
        Returns True when the informer is stopping."""
        metrics.inc(COUNTER_RELISTS, {"kind": self.kind, "reason": reason})
        return self._sleep_backoff()

    def _advance_rv(self, rv: int) -> None:
        if rv > self.last_resource_version:
            self.last_resource_version = rv

    def _run(self) -> None:
        """The reflector's ListAndWatch restart loop, watch-cache aware:
        list (Replace semantics) → watch from the list rv → dispatch until
        the stream dies → RESUME the watch at last_resource_version. Every
        failure mode re-enters the loop instead of killing the informer
        thread:

          * list errors (transient 401/5xx) retry with backoff
          * Expired at the list rv ("resourceVersion too old" between the
            list and the first watch): re-list (reason=expired)
          * a watch stream that closes WITHOUT stop() (flapping
            connection, REST stream death): reconnect at the last seen rv
            — the watch cache replays the gap from its event window, so a
            flap costs NO re-list and NO handler churn
          * Expired on a RESUME attempt (a true 410-outside-window — the
            cache evicted events past our position): re-list with Replace
            semantics (reason=window_expired)

        BOOKMARK events advance last_resource_version WITHOUT invoking
        handlers, so an informer on a quiet selector still rides inside
        the replay window. The shared backoff grows across consecutive
        failures and resets to the floor once a re-established watch
        delivers an event (bookmarks count — they prove the stream)."""
        while not self._stop.is_set():
            fresh_list = False
            if not self._resume or not self.last_resource_version:
                try:
                    objs, rv = self._server.list(self.kind)  # graftlint: allow-blocking(the pump's own re-list; only this informer's handlers wait)
                except Exception:
                    logger.exception("list of %s failed; retrying", self.kind)
                    if self._backoff_failure("list-error"):
                        return
                    continue
                self._replace(objs)
                self._synced.set()
                self._advance_rv(rv)
                fresh_list = True
            self._resume = False
            try:
                self._watcher = self._server.watch(  # graftlint: allow-blocking(re-arming this informer's own stream)
                    self.kind, from_version=self.last_resource_version
                )
            except Expired:
                if fresh_list:
                    # the gap opened between our list and the watch —
                    # the historical relist cause
                    logger.warning(
                        "watch for %s expired at rv %d; re-listing",
                        self.kind,
                        self.last_resource_version,
                    )
                    reason = "expired"
                else:
                    # resume position fell out of the watch-cache window:
                    # the one case that still costs a full re-list
                    logger.warning(
                        "watch resume for %s at rv %d outside the cache "
                        "window; re-listing",
                        self.kind,
                        self.last_resource_version,
                    )
                    reason = "window_expired"
                if self._backoff_failure(reason):
                    return
                continue
            if not fresh_list:
                metrics.inc(COUNTER_RESUMES, {"kind": self.kind})
            delivered = False
            for ev in self._watcher:
                if self._stop.is_set():
                    return
                if not delivered:
                    delivered = True
                    self._relist_backoff = RELIST_BACKOFF_INITIAL
                if ev.type == BOOKMARK:
                    metrics.inc(COUNTER_BOOKMARKS, {"kind": self.kind})
                    self._advance_rv(
                        ev.resource_version
                        or getattr(
                            ev.object.metadata, "resource_version", 0
                        )
                    )
                    continue
                key = ev.object.metadata.key
                if ev.type == ADDED:
                    self.indexer.add(ev.object)
                    self.event_committed = ev.committed
                    try:
                        for h in self._handlers:
                            h.on_add(ev.object)
                    finally:
                        self.event_committed = 0.0
                elif ev.type == MODIFIED:
                    old = self.indexer.get(key)
                    self.indexer.update(ev.object)
                    for h in self._handlers:
                        h.on_update(old, ev.object)
                elif ev.type == DELETED:
                    self.indexer.delete(ev.object)
                    for h in self._handlers:
                        h.on_delete(ev.object)
                self._advance_rv(
                    ev.resource_version
                    or ev.object.metadata.resource_version
                    or 0
                )
            if self._stop.is_set():
                return
            # stream closed under us (watch flap): resume at the last rv —
            # the cache window makes reconnects cheap; a true 410 on the
            # reconnect falls into the window_expired relist above
            self._resume = True
            if self._sleep_backoff():
                return

    def has_synced(self) -> bool:
        return self._synced.is_set()

    def wait_for_sync(self, timeout: float = 10.0) -> bool:
        return self._synced.wait(timeout)

    def stop(self) -> None:
        self._stop.set()
        if self._watcher is not None:
            self._watcher.stop()

    # Lister surface
    def list(self) -> List[Any]:
        return self.indexer.list()

    def get(self, key: str) -> Optional[Any]:
        return self.indexer.get(key)


class SharedInformerFactory:
    """informers.NewSharedInformerFactory: one informer per kind, shared."""

    def __init__(self, server: APIServer):
        self._server = server
        self._informers: Dict[str, SharedInformer] = {}
        self._lock = threading.Lock()

    def informer(
        self, kind: str, indexers: Optional[Dict[str, IndexFunc]] = None
    ) -> SharedInformer:
        with self._lock:
            inf = self._informers.get(kind)
            if inf is None:
                inf = SharedInformer(self._server, kind, indexers)
                self._informers[kind] = inf
            return inf

    def start(self) -> None:
        with self._lock:
            informers = list(self._informers.values())
        for inf in informers:
            inf.start()

    def wait_for_cache_sync(self, timeout: float = 10.0) -> bool:
        with self._lock:
            informers = list(self._informers.values())
        return all(inf.wait_for_sync(timeout) for inf in informers)

    def stop(self) -> None:
        with self._lock:
            informers = list(self._informers.values())
        for inf in informers:
            inf.stop()
