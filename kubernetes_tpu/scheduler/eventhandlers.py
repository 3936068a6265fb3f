"""Informer event handlers: API events → cache + queue (+ device deltas).

Mirrors reference pkg/scheduler/eventhandlers.go:350-460 addAllEventHandlers:
scheduled-pod events maintain the cache (and therefore the device snapshot,
via the encoder); unscheduled-pod events maintain the queue; node events do
both and flush the unschedulable queue with the matching event name
(internal/queue/events.go) so pods retry when the cluster changes.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

from ..api import objects as v1
from .queue import events as qevents

if TYPE_CHECKING:
    from .scheduler import Scheduler


def _is_scheduled(pod: v1.Pod) -> bool:
    return bool(pod.spec.node_name)


def add_all_event_handlers(sched: "Scheduler") -> None:
    pods = sched.informer_factory.informer("pods")
    nodes = sched.informer_factory.informer("nodes")

    # -- scheduled pods -> cache (eventhandlers.go: assignedPod filter) ------
    pods.add_handler(
        on_add=lambda p: _on_scheduled_add(sched, p),
        on_update=lambda old, new: _on_scheduled_update(sched, old, new),
        on_delete=lambda p: _on_scheduled_delete(sched, p),
        filter_fn=_is_scheduled,
    )

    # -- unscheduled pods -> queue (responsibleForPod filter) ----------------
    def responsible(pod: v1.Pod) -> bool:
        return not _is_scheduled(pod) and sched.profiles.for_pod(pod) is not None

    pods.add_handler(
        on_add=lambda p: _on_pending_add(sched, p, pods.event_committed),
        on_update=lambda old, new: _on_pending_update(sched, old, new),
        on_delete=lambda p: sched.queue.delete(p),
        filter_fn=responsible,
    )

    # -- nodes ---------------------------------------------------------------
    nodes.add_handler(
        on_add=lambda n: _on_node_add(sched, n),
        on_update=lambda old, new: _on_node_update(sched, old, new),
        on_delete=lambda n: _on_node_delete(sched, n),
    )

    # -- services -> SelectorSpread's device columns -------------------------
    # A Service's selector is interned as a service-derived predicate so the
    # kernel's DefaultPodTopologySpread score can count same-service pods
    # through sel_counts; interning grows the vocab, which invalidates
    # cached templates (their fingerprints embed vocab lengths). Deletes
    # can't shrink the vocab — bump the template cache's external sig so
    # match_svc masks rebuild without the dead service.
    services = sched.informer_factory.informer("services")
    services.add_handler(
        on_add=lambda s: _on_service_add(sched, s),
        on_update=lambda old, new: _on_service_update(sched, old, new),
        on_delete=lambda s: _on_service_delete(sched, s),
    )


def _on_scheduled_add(sched, pod):
    sched.cache.add_pod(pod)
    sched.queue.delete(pod)  # it may still sit in a queue from a race
    sched.queue.move_all_to_active_or_backoff(qevents.ASSIGNED_POD_ADD)


def _on_scheduled_update(sched, old, new):
    sched.cache.update_pod(new)
    sched.queue.move_all_to_active_or_backoff(qevents.ASSIGNED_POD_UPDATE)


def _on_scheduled_delete(sched, pod):
    sched.cache.remove_pod(pod)
    sched.queue.move_all_to_active_or_backoff(qevents.ASSIGNED_POD_DELETE)


def _on_pending_add(sched, pod, committed=0.0):
    # skip pods this scheduler has already assumed (skipPodUpdate,
    # eventhandlers.go: the optimistic cache owns them now)
    if sched.cache.is_assumed(pod.metadata.key):
        return
    if pod.metadata.deletion_timestamp is None:
        # committed: the store's commit of this pod's create, when the
        # watch delivered it (a first admission); 0.0 from a relist
        sched.queue.add(pod, committed=committed)


def _on_pending_update(sched, old, new):
    if sched.cache.is_assumed(new.metadata.key):
        return
    sched.queue.update(old, new)


def _node_event(old: v1.Node, new: v1.Node) -> str:
    if old.spec.unschedulable != new.spec.unschedulable:
        return qevents.NODE_SPEC_UNSCHEDULABLE_CHANGE
    if old.status.allocatable != new.status.allocatable:
        return qevents.NODE_ALLOCATABLE_CHANGE
    if old.metadata.labels != new.metadata.labels:
        return qevents.NODE_LABEL_CHANGE
    if old.spec.taints != new.spec.taints:
        return qevents.NODE_TAINT_CHANGE
    return qevents.NODE_CONDITION_CHANGE


def _on_node_add(sched, node):
    sched.cache.add_node(node)
    sched.queue.move_all_to_active_or_backoff(qevents.NODE_ADD)


def _on_node_update(sched, old, new):
    sched.cache.update_node(new)
    sched.queue.move_all_to_active_or_backoff(_node_event(old, new))


def _on_node_delete(sched, node):
    sched.cache.remove_node(node.metadata.name)
    sched.queue.move_all_to_active_or_backoff(qevents.NODE_DELETE)


def _register_service(sched: "Scheduler", svc) -> bool:
    sel = getattr(svc.spec, "selector", None)
    if not sel:
        return False
    from ..api.selectors import selector_from_match_labels

    with sched.cache.lock:
        enc = sched.cache.encoder
        before = len(enc.service_sids)
        enc.register_service_predicate(
            svc.metadata.namespace, selector_from_match_labels(sel)
        )
        return len(enc.service_sids) != before


def _rebuild_service_sids(sched: "Scheduler") -> None:
    """Recompute the service-derived sid set from the LIVE services (the
    vocab can't shrink, but a deleted/retargeted service must drop out of
    the match_svc masks)."""
    from ..api.selectors import selector_from_match_labels

    try:
        services, _ = sched.server.list("services")
    except Exception:
        services = []
    with sched.cache.lock:
        enc = sched.cache.encoder
        enc.service_sids.clear()
        for s in services:
            sel = getattr(s.spec, "selector", None)
            if sel:
                enc.register_service_predicate(
                    s.metadata.namespace, selector_from_match_labels(sel)
                )
    sched._tpl_cache.extra_sig += 1  # cached match_svc masks are stale


def _on_service_add(sched, svc):
    if _register_service(sched, svc):
        sched._tpl_cache.extra_sig += 1
    sched.queue.move_all_to_active_or_backoff(qevents.SERVICE_ADD)


def _on_service_update(sched, old, new):
    if getattr(old.spec, "selector", None) != getattr(new.spec, "selector", None):
        _rebuild_service_sids(sched)
    sched.queue.move_all_to_active_or_backoff(qevents.SERVICE_UPDATE)


def _on_service_delete(sched, svc):
    _rebuild_service_sids(sched)
    sched.queue.move_all_to_active_or_backoff(qevents.SERVICE_DELETE)
