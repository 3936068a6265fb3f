"""Reflective JSON codec for the API object model.

The apimachinery serializer role (reference
staging/src/k8s.io/apimachinery/pkg/runtime/serializer/json): dataclasses
⇄ Kubernetes-style camelCase JSON. Field names convert snake_case →
lowerCamelCase; nested dataclasses, tuples, lists, dicts and Optionals
recurse; zero/empty values are omitted on output (omitempty).

The kind registry maps REST resource names ("pods") and JSON `kind`
strings ("Pod") to classes, standing in for runtime.Scheme's GVK mapping.
"""

from __future__ import annotations

import base64
import dataclasses
import typing
from typing import Any, Callable, Dict, Optional, Type, get_args, get_origin, get_type_hints

from ..utils.metrics import metrics
from . import objects as v1

# resource name -> (kind string, class)
RESOURCE_KINDS: Dict[str, Type] = {
    "pods": v1.Pod,
    "nodes": v1.Node,
    "services": v1.Service,
    "persistentvolumes": v1.PersistentVolume,
    "persistentvolumeclaims": v1.PersistentVolumeClaim,
    "storageclasses": v1.StorageClass,
    "csinodes": v1.CSINode,
    "bindings": v1.Binding,
    "namespaces": v1.Namespace,
    "replicasets": v1.ReplicaSet,
    "deployments": v1.Deployment,
    "jobs": v1.Job,
    "daemonsets": v1.DaemonSet,
    "statefulsets": v1.StatefulSet,
    "poddisruptionbudgets": v1.PodDisruptionBudget,
    "endpoints": v1.Endpoints,
    "priorityclasses": v1.PriorityClass,
    "configmaps": v1.ConfigMap,
    "secrets": v1.Secret,
    "serviceaccounts": v1.ServiceAccount,
    "horizontalpodautoscalers": v1.HorizontalPodAutoscaler,
    "cronjobs": v1.CronJob,
    "resourcequotas": v1.ResourceQuota,
    "customresourcedefinitions": v1.CustomResourceDefinition,
    "apiservices": v1.APIService,
    "endpointslices": v1.EndpointSlice,
    "volumeattachments": v1.VolumeAttachment,
    "replicationcontrollers": v1.ReplicationController,
    "certificatesigningrequests": v1.CertificateSigningRequest,
    "limitranges": v1.LimitRange,
    "clusterroles": v1.ClusterRole,
    "clusterrolebindings": v1.ClusterRoleBinding,
    "mutatingwebhookconfigurations": v1.MutatingWebhookConfiguration,
    "validatingwebhookconfigurations": v1.ValidatingWebhookConfiguration,
    "ingresses": v1.Ingress,
    "ingressclasses": v1.IngressClass,
    "networkpolicies": v1.NetworkPolicy,
    "podsecuritypolicies": v1.PodSecurityPolicy,
    "runtimeclasses": v1.RuntimeClass,
}

# Cluster-scoped resources: the store normalizes their namespace to ""
# ONCE at the write boundary (client/apiserver.py), so an object decoded
# from a plain manifest (ObjectMeta defaults namespace to "default") and
# one created namespace-less land under the SAME key — consumers never
# probe both spellings. kubectl shares this set for its path routing.
CLUSTER_SCOPED = frozenset(
    {
        "nodes",
        "persistentvolumes",
        "storageclasses",
        "csinodes",
        "namespaces",
        "priorityclasses",
        "customresourcedefinitions",
        "apiservices",
        "clusterroles",
        "clusterrolebindings",
        "mutatingwebhookconfigurations",
        "validatingwebhookconfigurations",
        "certificatesigningrequests",
        "runtimeclasses",
        "podsecuritypolicies",
        "ingressclasses",
        "scorepolicies",
    }
)

KIND_TO_RESOURCE = {
    cls.__name__: res for res, cls in RESOURCE_KINDS.items()
}


def register_kind(resource: str, cls: Type) -> None:
    RESOURCE_KINDS[resource] = cls
    KIND_TO_RESOURCE[cls.__name__] = resource


def _camel(name: str) -> str:
    parts = name.split("_")
    return parts[0] + "".join(p.capitalize() for p in parts[1:])


def _snake(name: str) -> str:
    out = []
    for ch in name:
        if ch.isupper():
            out.append("_")
            out.append(ch.lower())
        else:
            out.append(ch)
    return "".join(out)


def _resolve_optional(tp):
    if get_origin(tp) is typing.Union:
        args = [a for a in get_args(tp) if a is not type(None)]
        if len(args) == 1:
            return args[0]
    return tp


# -- the per-class plan -------------------------------------------------------

# Values of these types are their own wire form.
_PLAIN = frozenset({str, int, float, bool, type(None)})


class _Plan:
    """What the codec needs of one dataclass, worked out once.

    `encode`: one row per field in declaration order (the wire's key
    order): (attribute name, wire name, default or MISSING, whether an
    encoded "" is dropped). `decode`: (wire name, the snake_case spelling
    where it differs else None, attribute name, decoder or None for a
    value that passes through). `hints`: the class's resolved annotations,
    or None with `error` set when they do not resolve yet; such a plan
    still encodes (encoding never needed them), decodes by raising
    `error`, and is not kept."""

    __slots__ = ("encode", "decode", "hints", "error")

    def __init__(self, cls: Type):
        self.error: Optional[Exception] = None
        try:
            # every api/ module has `from __future__ import annotations`:
            # this parses and evaluates each annotation string of the class
            self.hints: Optional[Dict[str, Any]] = get_type_hints(cls)
        except Exception as e:  # a forward reference not importable yet
            self.hints, self.error = None, e
        fields = dataclasses.fields(cls)
        wires = [_camel(f.name) for f in fields]
        self.encode = tuple(
            (
                f.name,
                wire,
                f.default,
                f.default is dataclasses.MISSING or f.default == "",
            )
            for f, wire in zip(fields, wires)
        )
        self.decode = tuple(
            (
                wire,
                f.name if f.name != wire else None,
                f.name,
                _decoder(self.hints[f.name]),
            )
            for f, wire in zip(fields, wires)
        ) if self.hints is not None else ()


_PLANS: Dict[Type, _Plan] = {}


def _plan(cls: Type) -> _Plan:
    """The plan of a dataclass, built on first use and kept for the life
    of the process once its annotations resolved. No lock: two threads
    that build the same plan at once build equal plans, and the first to
    store its own is the one counted."""
    plan = _PLANS.get(cls)
    if plan is None:
        plan = _Plan(cls)
        if plan.error is None and _PLANS.setdefault(cls, plan) is plan:
            metrics.inc("api_codec_plans_built_total")
    return plan


def resolved_hints(cls: Type) -> Dict[str, Any]:
    """`typing.get_type_hints(cls)` of a dataclass, evaluated once per
    class: the one home of resolved annotations in api/ (this codec's
    plans and protocodec's schema read the same cache)."""
    plan = _plan(cls)
    if plan.error is not None:
        raise plan.error
    return plan.hints


def to_dict(obj: Any) -> Any:
    """Dataclass → JSON-ready dict (camelCase keys, omitempty)."""
    tp = type(obj)
    if tp in _PLAIN:
        return obj
    plan = _PLANS.get(tp)
    if plan is None:
        if tp is list or tp is tuple:
            return [x if type(x) in _PLAIN else to_dict(x) for x in obj]
        if tp is dict:
            return {
                k: x if type(x) in _PLAIN else to_dict(x)
                for k, x in obj.items()
            }
        if not dataclasses.is_dataclass(tp):
            return _to_dict_other(obj)
        plan = _plan(tp)
    out = {}
    for name, wire, default, drop_empty_str in plan.encode:
        val = getattr(obj, name)
        # omitempty: skip values equal to the field default (and empty
        # containers from default factories)
        if default is not dataclasses.MISSING and val == default:
            continue
        if type(val) in _PLAIN:
            enc = val
            if enc is None:
                continue
        else:
            enc = to_dict(val)
            if enc is None or enc == {} or enc == []:
                continue
        if enc == "" and drop_empty_str:
            # an explicit empty string that differs from a non-empty
            # default is meaningful (e.g. cluster-scoped namespace="")
            continue
        out[wire] = enc
    return out


def _to_dict_other(obj: Any) -> Any:
    """to_dict of what is neither plain, an exact list / tuple / dict,
    nor a dataclass: their subclasses, frozenset, bytes, anything else."""
    if isinstance(obj, (list, tuple)):
        return [to_dict(x) for x in obj]
    if isinstance(obj, frozenset):
        return sorted(obj)
    if isinstance(obj, dict):
        return {k: to_dict(val) for k, val in obj.items()}
    if isinstance(obj, bytes):
        # Secret.data wire form is base64 (the k8s []byte convention)
        return base64.b64encode(obj).decode("ascii")
    return obj


def _decode_float(data: Any) -> Any:
    return float(data) if isinstance(data, int) else data


def _decode_bytes(data: Any) -> Any:
    return base64.b64decode(data) if isinstance(data, str) else data


def _decoder(tp: Any) -> Optional[Callable[[Any], Any]]:
    """What from_dict does with a value of type `tp`, decided once: a
    function of the raw value, or None where the value passes through
    (str, int, bool, Any, a scalar union such as Quantity, a bare
    container). Every decoder maps None to None."""
    tp = _resolve_optional(tp)
    if isinstance(tp, str):  # unresolved forward ref — shouldn't happen

        def unresolved(data):
            if data is None:
                return None
            raise TypeError(f"unresolved type {tp}")

        return unresolved
    origin = get_origin(tp)
    if origin in (list, tuple):
        (item_tp, *_rest) = get_args(tp) or (Any,)
        item = _decoder(item_tp)
        as_tuple = origin is tuple

        def sequence(data):
            if data is None:
                return None
            seq = list(data) if item is None else [item(x) for x in data]
            return tuple(seq) if as_tuple else seq

        return sequence
    if origin is dict:
        _k, val_tp = get_args(tp) or (str, Any)
        val = _decoder(val_tp)

        def mapping(data):
            if data is None:
                return None
            if val is None:
                return dict(data.items())
            return {k: val(x) for k, x in data.items()}

        return mapping
    if origin is typing.Union:
        # scalar union (e.g. Quantity = str|int|float): pass through
        return None
    if dataclasses.is_dataclass(tp):
        # the nested class's plan is looked up when a value arrives, not
        # now: classes may refer to themselves and to each other

        def nested(data):
            return None if data is None else _decode_dataclass(tp, data)

        return nested
    if tp is float:
        return _decode_float
    if tp is bytes:
        return _decode_bytes
    return None


def _decode_dataclass(cls: Type, data: Any) -> Any:
    plan = _PLANS.get(cls) or _plan(cls)
    if plan.error is not None:
        raise plan.error
    kwargs = {}
    for wire, alias, name, dec in plan.decode:
        if wire in data:
            raw = data[wire]
        elif alias is not None and alias in data:
            raw = data[alias]
        else:
            continue
        kwargs[name] = raw if dec is None else dec(raw)
    return cls(**kwargs)


def from_dict(cls: Type, data: Any) -> Any:
    """JSON dict → dataclass instance (inverse of to_dict)."""
    if data is None:
        return None
    if isinstance(cls, type) and cls in _PLANS:
        return _decode_dataclass(cls, data)
    dec = _decoder(cls)
    return data if dec is None else dec(data)


def decode(resource: str, data: dict, allow_unstructured: bool = True) -> Any:
    """JSON body → typed object for a REST resource. Unknown resources
    decode as Unstructured (custom resources — the REST layer gates which
    unknown resources are actually served; the WAL replays them blindly)."""
    cls = RESOURCE_KINDS.get(resource)
    if cls is None:
        ensure_late_registration()  # import-order hole: see its docstring
        cls = RESOURCE_KINDS.get(resource)
    if cls is None:
        if allow_unstructured:
            return decode_unstructured(data)
        raise KeyError(f"unknown resource {resource!r}")
    return from_dict(cls, data)


def decode_any(data: dict) -> Any:
    """JSON body with a `kind` field → (resource, typed object). Documents
    at a registered NON-internal version (e.g. discovery.k8s.io/v1
    EndpointSlice) convert through the scheme's to-internal hop first
    (api/scheme.py)."""
    kind = data.get("kind", "")
    api_version = data.get("apiVersion", "")
    if api_version and "/" in api_version:
        from .scheme import scheme

        if scheme.recognizes(api_version, kind):
            return scheme.decode(data)
    resource = KIND_TO_RESOURCE.get(kind)
    if resource is None:
        ensure_late_registration()  # import-order hole: see its docstring
        resource = KIND_TO_RESOURCE.get(kind)
    if resource is None:
        raise KeyError(f"unknown kind {kind!r}")
    return resource, from_dict(RESOURCE_KINDS[resource], data)


def encode(obj: Any) -> dict:
    if isinstance(obj, v1.Unstructured):
        # custom resources round-trip their raw content; typed metadata is
        # re-attached under the standard key
        d = dict(obj.content)
        d["metadata"] = to_dict(obj.metadata)
        d["kind"] = obj.kind or "Unstructured"
        d["apiVersion"] = obj.api_version
        return d
    d = to_dict(obj)
    if isinstance(d, dict):
        d.setdefault("kind", type(obj).__name__)
        d.setdefault("apiVersion", "v1")
    return d


def decode_unstructured(data: dict) -> v1.Unstructured:
    """JSON body → Unstructured (dynamic-client path for CRD resources)."""
    meta = from_dict(v1.ObjectMeta, data.get("metadata", {}) or {})
    content = {
        k: val
        for k, val in data.items()
        if k not in ("metadata", "kind", "apiVersion")
    }
    return v1.Unstructured(
        metadata=meta,
        content=content,
        kind=data.get("kind", ""),
        api_version=data.get("apiVersion", "v1"),
    )


_late_registered = False


def ensure_late_registration() -> None:
    """Register the kinds that live in client/* (events, leases) —
    idempotent, safe to call from any lookup path. The import-time call
    below succeeds in most processes, but when THIS module is first
    imported via kubernetes_tpu.client's own import chain (e.g. a child
    process whose first touch is ``import kubernetes_tpu.client``), the
    client package is mid-import and the ImportError is swallowed — the
    lease kind would then silently decode as Unstructured forever (found
    by the netchaos multi-process suite: the REST elector's lease came
    back untyped and the renew thread died). Lookup paths (decode,
    decode_any, the REST serving gate) retry here on a miss."""
    global _late_registered
    if _late_registered:
        return
    try:
        from ..client.events import ClusterEvent
        from ..client.leaderelection import Lease
        from ..tuner.policy import ScorePolicy
    except ImportError:
        return
    RESOURCE_KINDS["events"] = ClusterEvent
    KIND_TO_RESOURCE["ClusterEvent"] = "events"
    KIND_TO_RESOURCE["Event"] = "events"
    RESOURCE_KINDS["leases"] = Lease
    KIND_TO_RESOURCE["Lease"] = "leases"
    RESOURCE_KINDS["scorepolicies"] = ScorePolicy
    KIND_TO_RESOURCE["ScorePolicy"] = "scorepolicies"
    _late_registered = True


ensure_late_registration()
