"""The JSON codec's per-class plans against the walk they replaced.

`ref_to_dict` / `ref_from_dict` below are api/serialization.py's
`to_dict` / `from_dict` as they stood before the plans (they re-evaluated
`typing.get_type_hints` on every decode), kept verbatim as the plain
reference: every kind must encode to the same bytes and decode to an
equal object through both.
"""

from __future__ import annotations

import base64
import builtins
import dataclasses
import glob
import json
import os
import sys
import threading
import typing
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple, get_args, get_origin, get_type_hints

import pytest

from kubernetes_tpu.api import objects as v1
from kubernetes_tpu.api import serialization as codec
from kubernetes_tpu.api.resources import Quantity
from kubernetes_tpu.utils.metrics import metrics

# -- the reference: the codec before the plans, verbatim ----------------------


def _ref_camel(name: str) -> str:
    parts = name.split("_")
    return parts[0] + "".join(p.capitalize() for p in parts[1:])


def ref_to_dict(obj: Any) -> Any:
    """Dataclass → JSON-ready dict (camelCase keys, omitempty)."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        out = {}
        for f in dataclasses.fields(obj):
            val = getattr(obj, f.name)
            # omitempty: skip values equal to the field default (and empty
            # containers from default factories)
            if f.default is not dataclasses.MISSING and val == f.default:
                continue
            enc = ref_to_dict(val)
            if enc is None or enc == {} or enc == []:
                continue
            if enc == "" and (
                f.default is dataclasses.MISSING or f.default == ""
            ):
                # an explicit empty string that differs from a non-empty
                # default is meaningful (e.g. cluster-scoped namespace="")
                continue
            out[_ref_camel(f.name)] = enc
        return out
    if isinstance(obj, (list, tuple)):
        return [ref_to_dict(x) for x in obj]
    if isinstance(obj, frozenset):
        return sorted(obj)
    if isinstance(obj, dict):
        return {k: ref_to_dict(val) for k, val in obj.items()}
    if isinstance(obj, bytes):
        # Secret.data wire form is base64 (the k8s []byte convention)
        return base64.b64encode(obj).decode("ascii")
    return obj


def _ref_resolve_optional(tp):
    if get_origin(tp) is typing.Union:
        args = [a for a in get_args(tp) if a is not type(None)]
        if len(args) == 1:
            return args[0]
    return tp


def ref_from_dict(cls, data: Any) -> Any:
    """JSON dict → dataclass instance (inverse of to_dict)."""
    if data is None:
        return None
    cls = _ref_resolve_optional(cls)
    if isinstance(cls, str):  # unresolved forward ref — shouldn't happen
        raise TypeError(f"unresolved type {cls}")
    origin = get_origin(cls)
    if origin in (list, tuple):
        (item_tp, *_rest) = get_args(cls) or (Any,)
        seq = [ref_from_dict(item_tp, x) for x in data]
        return tuple(seq) if origin is tuple else seq
    if origin is dict:
        _k, val_tp = get_args(cls) or (str, Any)
        return {k: ref_from_dict(val_tp, val) for k, val in data.items()}
    if origin is typing.Union:
        resolved = _ref_resolve_optional(cls)
        if get_origin(resolved) is typing.Union:
            # scalar union (e.g. Quantity = str|int|float): pass through
            return data
        return ref_from_dict(resolved, data)
    if dataclasses.is_dataclass(cls):
        hints = get_type_hints(cls)
        kwargs = {}
        for f in dataclasses.fields(cls):
            camel = _ref_camel(f.name)
            if camel in data:
                raw = data[camel]
            elif f.name in data:
                raw = data[f.name]
            else:
                continue
            kwargs[f.name] = ref_from_dict(hints[f.name], raw)
        return cls(**kwargs)
    if cls in (Any, object):
        return data
    if cls is float and isinstance(data, int):
        return float(data)
    if cls is bytes and isinstance(data, str):
        return base64.b64decode(data)
    return data


def ref_encode(obj: Any) -> dict:
    if isinstance(obj, v1.Unstructured):
        d = dict(obj.content)
        d["metadata"] = ref_to_dict(obj.metadata)
        d["kind"] = obj.kind or "Unstructured"
        d["apiVersion"] = obj.api_version
        return d
    d = ref_to_dict(obj)
    if isinstance(d, dict):
        d.setdefault("kind", type(obj).__name__)
        d.setdefault("apiVersion", "v1")
    return d


# -- instances ----------------------------------------------------------------


def _value(tp: Any, name: str) -> Any:
    """A non-default value of type `tp`, whatever its shape."""
    tp = _ref_resolve_optional(tp)
    origin = get_origin(tp)
    if origin is typing.Union:
        return "100m"  # Quantity
    if origin in (list, tuple):
        args = get_args(tp) or (Any,)
        if origin is tuple and Ellipsis not in args:
            return tuple(_value(a, name) for a in args)
        one = _value(args[0], name)
        return (one,) if origin is tuple else [one]
    if origin is dict:
        _k, val_tp = get_args(tp) or (str, Any)
        return {"k": _value(val_tp, name), "": _value(val_tp, name)}
    if dataclasses.is_dataclass(tp):
        return _populated(tp)
    if tp is str:
        return f"x-{name}"
    if tp is bool:
        return True
    if tp is int:
        return 7
    if tp is float:
        return 7.5
    if tp is bytes:
        return b"\x00\xffsecret"
    if tp is list:
        return ["bare", 1]
    if tp is dict:
        return {"bare": 1}
    # Any: what a custom resource's content may hold
    return {"nested": [1, 2.5, None, {"deep": ""}], "emptyList": []}


def _populated(cls):
    """An instance of `cls` with every field off its default (no kind
    of the object model contains itself)."""
    hints = get_type_hints(cls)
    return cls(**{
        f.name: _value(hints[f.name], f.name)
        for f in dataclasses.fields(cls)
    })


def _kinds() -> Dict[str, type]:
    codec.ensure_late_registration()
    kinds = dict(codec.RESOURCE_KINDS)
    kinds["evictions"] = v1.Eviction  # a subresource body, no REST kind
    assert kinds["bindings"] is v1.Binding
    return kinds


_KINDS = _kinds()


def _snake_keys(wire: Any) -> Any:
    if isinstance(wire, dict):
        return {codec._snake(k): _snake_keys(x) for k, x in wire.items()}
    if isinstance(wire, list):
        return [_snake_keys(x) for x in wire]
    return wire


_GENERATED = ("uid", "creation_timestamp", "first_timestamp", "last_timestamp")


def _scrub(val: Any) -> Any:
    """`val` as plain data without what a default factory generates anew
    in every instance (a uid, the time): two decodes of a body that leaves
    them out agree in everything else."""
    if dataclasses.is_dataclass(val):
        return {f.name: _scrub(getattr(val, f.name))
                for f in dataclasses.fields(val) if f.name not in _GENERATED}
    if isinstance(val, (list, tuple)):
        return [_scrub(x) for x in val]
    if isinstance(val, dict):
        return {k: _scrub(x) for k, x in val.items()}
    return val


def _outcome(fn, *args):
    try:
        return fn(*args)
    except Exception as e:
        return type(e), str(e)


def _same_both_ways(cls, obj) -> dict:
    """Encode `obj` and decode it again through the plans and through the
    reference; returns the wire form."""
    text = json.dumps(codec.encode(obj))
    assert text == json.dumps(ref_encode(obj))
    assert json.dumps(codec.to_dict(obj)) == json.dumps(ref_to_dict(obj))
    wire = json.loads(text)
    got = codec.from_dict(cls, wire)
    assert got == ref_from_dict(cls, wire)
    assert type(got) is cls
    # what was decoded encodes to the same bytes again
    assert json.dumps(codec.encode(got)) == json.dumps(ref_encode(got))
    return wire


@pytest.mark.parametrize("resource", sorted(_KINDS))
def test_populated_instance_matches_the_reference(resource):
    cls = _KINDS[resource]
    obj = _populated(cls)
    wire = _same_both_ways(cls, obj)
    got = codec.from_dict(cls, wire)
    if resource in codec.RESOURCE_KINDS:
        assert codec.decode(resource, wire) == got
    # snake_case keys are accepted beside camelCase
    snake = _snake_keys(wire)
    assert codec.from_dict(cls, snake) == ref_from_dict(cls, snake)
    # unknown keys are ignored, at the top and in a nested object
    noisy = json.loads(json.dumps(wire))
    noisy["noSuchField"] = {"a": 1}
    if isinstance(noisy.get("metadata"), dict):
        noisy["metadata"]["noSuchField"] = [1]
    assert codec.from_dict(cls, noisy) == got
    # a null where an object, a list or a scalar is expected
    nulled = {k: None for k in wire}
    assert _scrub(_outcome(codec.from_dict, cls, nulled)) == _scrub(
        _outcome(ref_from_dict, cls, nulled))


@pytest.mark.parametrize("resource", sorted(_KINDS))
def test_default_instance_matches_the_reference(resource):
    cls = _KINDS[resource]
    wire = _same_both_ways(cls, cls())
    if resource in codec.RESOURCE_KINDS:
        assert codec.decode_any(wire) == (resource, codec.decode(resource, wire))
    # missing keys take the dataclass default: metadata is pinned by the
    # wire (uid and creationTimestamp are generated, so always encoded)
    bare = {k: x for k, x in wire.items() if k == "metadata"}
    got = codec.from_dict(cls, bare)
    assert type(got) is cls
    assert _scrub(got) == _scrub(ref_from_dict(cls, bare))


def _benchmark_bodies():
    here = os.path.dirname(os.path.abspath(__file__))
    for path in sorted(glob.glob(
            os.path.join(here, "..", "benchmark", "configs", "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        yield pytest.param("nodes", cfg["nodes"]["manifest"],
                           id=f"{cfg['name']}-node")
        for name, body in sorted(cfg["pod_templates"].items()):
            yield pytest.param("pods", body, id=f"{cfg['name']}-{name}")


@pytest.mark.parametrize("resource,body", list(_benchmark_bodies()))
def test_benchmark_bodies_match_the_reference(resource, body):
    """The bodies the benchmark POSTs: the apiserver decodes each once a
    create and encodes it into the WAL record `harness/check.py` reads."""
    body = json.loads(json.dumps(body))
    body["metadata"].update(uid="uid-pinned", creationTimestamp=1700000000)
    cls = codec.RESOURCE_KINDS[resource]
    got = codec.decode(resource, body)
    assert got == ref_from_dict(cls, body)
    assert got.metadata.creation_timestamp == 1700000000.0
    assert isinstance(got.metadata.creation_timestamp, float)
    assert json.dumps(codec.encode(got)) == json.dumps(ref_encode(got))
    _same_both_ways(cls, got)


def test_unstructured_round_trip():
    body = {
        "kind": "Widget", "apiVersion": "example.com/v1",
        "metadata": {"name": "w", "uid": "u", "creationTimestamp": 5},
        "spec": {"size": 3, "tags": ["a"], "camelKey": {"snake_key": None}},
    }
    obj = codec.decode("widgets", body)
    assert isinstance(obj, v1.Unstructured)
    assert obj.content == {"spec": body["spec"]}
    assert json.dumps(codec.encode(obj)) == json.dumps(ref_encode(obj))
    assert codec.decode("widgets", codec.encode(obj)) == obj
    with pytest.raises(KeyError):
        codec.decode("widgets", body, allow_unstructured=False)


@pytest.mark.parametrize("tp,data", [
    (List[int], [1, 2]),
    (List[v1.Binding], [{"podName": "p"}, None]),
    (Optional[float], 3),
    (float, True),
    (Optional[v1.Binding], {"pod_name": "p", "podName": "camel wins"}),
    (Dict[str, bytes], {"k": "AP8=", "n": None}),
    (Dict[str, Quantity], {"cpu": 1, "memory": "1Gi"}),
    (Tuple[str, ...], ["a", "b"]),
    (Tuple[Tuple[str, str], ...], [["a", "b"], ["c", "d"]]),
    (Tuple[str, int], ["a", 1]),
    (typing.List, [{"a": 1}]),
    (typing.Dict, {"a": {"b": 1}}),
    (list, [1]),
    (Any, {"a": [1]}),
    (object, 5),
    (Quantity, 2.5),
    (bytes, "AP8="),
    (bytes, b"raw"),
    (int, "not an int"),
    (str, None),
    (v1.Binding, "a string where an object belongs"),
    (v1.Binding, ["podName"]),
], ids=str)
def test_any_type_decodes_as_the_reference(tp, data):
    want = _outcome(ref_from_dict, tp, data)
    got = _outcome(codec.from_dict, tp, data)
    assert got == want and type(got) is type(want)


def test_frozenset_is_sorted_and_containers_recurse():
    val = {"s": frozenset({"b", "a"}), "t": (1, (2, b"\x00")), "o": v1.Binding()}
    assert codec.to_dict(val) == ref_to_dict(val)
    assert json.dumps(codec.to_dict(val)) == json.dumps(ref_to_dict(val))
    assert codec.to_dict(v1.Binding) is v1.Binding  # a class is no instance


# -- what a plan saves, and when it exists --------------------------------------


def _counting(monkeypatch, owner, name):
    calls = []
    real = getattr(owner, name)

    def counted(*a, **kw):
        calls.append(a)
        return real(*a, **kw)

    monkeypatch.setattr(owner, name, counted)
    return calls


def test_a_warm_decode_and_encode_evaluate_nothing(monkeypatch):
    body = next(p.values[1] for p in _benchmark_bodies()
                if p.id == "perf5k-podaffinity-measured")
    codec.encode(codec.decode("pods", body))  # warm: every plan exists
    spies = [
        _counting(monkeypatch, typing, "get_type_hints"),
        _counting(monkeypatch, codec, "get_type_hints"),
        _counting(monkeypatch, builtins, "compile"),
        _counting(monkeypatch, codec, "_camel"),
        _counting(monkeypatch, dataclasses, "fields"),
    ]
    built = metrics.counter("api_codec_plans_built_total")
    for _ in range(100):
        pod = codec.decode("pods", body)
        codec.encode(pod)
    assert [len(s) for s in spies] == [0, 0, 0, 0, 0]
    assert metrics.counter("api_codec_plans_built_total") == built
    assert pod.spec.affinity.pod_affinity.required[0].topology_key == (
        "topology.kubernetes.io/zone")


def test_one_plan_per_class_and_one_home_for_hints():
    @dataclass
    class Fresh:
        some_field: Optional[float] = None
        items: List[v1.Binding] = field(default_factory=list)

    built = metrics.counter("api_codec_plans_built_total")
    assert Fresh not in codec._PLANS
    got = codec.from_dict(Fresh, {"someField": 1, "items": [{"podName": "p"}]})
    assert got == Fresh(some_field=1.0, items=[v1.Binding(pod_name="p")])
    assert codec.to_dict(got) == {"someField": 1.0, "items": [{"podName": "p"}]}
    assert metrics.counter("api_codec_plans_built_total") - built in (1, 2)
    # (2 where Binding's plan was not built yet in this process)
    plan = codec._PLANS[Fresh]
    assert codec.resolved_hints(Fresh) is plan.hints
    assert plan.hints == {"some_field": Optional[float],
                          "items": List[v1.Binding]}
    assert [row[:2] for row in plan.encode] == [
        ("some_field", "someField"), ("items", "items")]
    assert [row[:3] for row in plan.decode] == [
        ("someField", "some_field", "some_field"), ("items", None, "items")]
    # protocodec's schema reads the same resolved hints
    from kubernetes_tpu.api import protocodec

    assert [tp for _n, _name, tp in protocodec._schema(Fresh)] == list(
        plan.hints.values())


@dataclass
class _Early:
    """Refers to a class that does not exist when the test starts."""

    name: str = ""
    later: Optional[_LaterDefined] = None  # noqa: F821


def test_unresolvable_forward_reference_raises_until_it_resolves():
    globals().pop("_LaterDefined", None)
    codec._PLANS.pop(_Early, None)
    built = metrics.counter("api_codec_plans_built_total")
    for _ in range(2):
        with pytest.raises(NameError, match="_LaterDefined"):
            ref_from_dict(_Early, {"name": "n"})
        with pytest.raises(NameError, match="_LaterDefined"):
            codec.from_dict(_Early, {"name": "n"})
        with pytest.raises(NameError, match="_LaterDefined"):
            codec.resolved_hints(_Early)
        # encoding never needed the annotations, and still does not
        assert codec.to_dict(_Early(name="n")) == ref_to_dict(_Early(name="n"))
    assert _Early not in codec._PLANS
    assert metrics.counter("api_codec_plans_built_total") == built

    @dataclass
    class _LaterDefined:
        n: int = 0

    globals()["_LaterDefined"] = _LaterDefined
    try:
        want = _Early(name="n", later=_LaterDefined(n=3))
        body = {"name": "n", "later": {"n": 3}}
        assert codec.from_dict(_Early, body) == want
        assert ref_from_dict(_Early, body) == want
        assert _Early in codec._PLANS
        assert metrics.counter("api_codec_plans_built_total") == built + 2
    finally:
        del globals()["_LaterDefined"]
        codec._PLANS.pop(_Early, None)
    # a bare string where a type belongs: only a value raises
    assert codec.from_dict("Nowhere", None) is None
    with pytest.raises(TypeError, match="unresolved type Nowhere"):
        codec.from_dict("Nowhere", {})
    with pytest.raises(TypeError, match="unresolved type Nowhere"):
        ref_from_dict("Nowhere", {})


def test_eight_threads_planning_one_class_at_once():
    @dataclass
    class Raced:
        pod_name: str = ""
        ratio: float = 0.0
        children: List[v1.OwnerReference] = field(default_factory=list)

    body = {"podName": "p", "ratio": 2,
            "children": [{"name": "o", "blockOwnerDeletion": True}]}
    want = ref_from_dict(Raced, body)
    codec.from_dict(v1.OwnerReference, {})  # its plan is not the one raced for
    built = metrics.counter("api_codec_plans_built_total")
    barrier = threading.Barrier(8)
    got, errors = [None] * 8, []

    def work(i):
        try:
            barrier.wait(timeout=10)
            for _ in range(50):
                got[i] = codec.from_dict(Raced, body)
                assert codec.to_dict(got[i]) == ref_to_dict(want)
        except BaseException as e:  # reported below, in the test's thread
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    assert got == [want] * 8
    assert isinstance(got[0].ratio, float)
    # however many threads built it, one plan was kept and counted
    assert metrics.counter("api_codec_plans_built_total") == built + 1
