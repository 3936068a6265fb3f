"""One more filter of the plain reference: a pod's `DoNotSchedule`
topology spread constraints, as they stand at the moment a bind commits.

Plain Python over the manifests and the binds replayed so far; nothing of
the program is imported. State lives in
`cluster.rule_state["pod_topology_spread"]`.

For each `DoNotSchedule` constraint of the pod (upstream
podtopologyspread/filtering.go, v1.19): the node must carry the topology
key; among the bound pods of the pod's own namespace that match the
constraint's selector, count by the key's value, over EVERY value that
some node carries (a zone with no such pod counts 0); the bind is refused
if

    count[node's value] + (1 if the pod matches its own selector)
        - min(count) > maxSkew

Departures from upstream's filter, each because the configurations that
name this rule cannot show the difference:
  * the domains are not narrowed by the pod's nodeSelector / required
    nodeAffinity (upstream counts only nodes that pass them): the pods
    carry neither;
  * a selector is its `matchLabels` alone (no `matchExpressions`);
  * a constraint without `whenUnsatisfiable` is taken as `DoNotSchedule`,
    the stricter reading; `ScheduleAnyway` constraints are scores, not
    filters, and are not replayed;
  * no pod is terminating: a bind replayed is a pod counted.
"""

NAME = "pod_topology_spread"


def _hard_constraints(manifest: dict) -> list:
    """[(maxSkew, topologyKey, selector dict or None)] of the pod's
    `DoNotSchedule` constraints. A missing labelSelector matches no pod
    (upstream: a nil selector selects nothing)."""
    out = []
    spec = manifest.get("spec") or {}
    for c in spec.get("topologySpreadConstraints") or []:
        if c.get("whenUnsatisfiable", "DoNotSchedule") != "DoNotSchedule":
            continue
        sel = c.get("labelSelector")
        # matchLabels as the codec writes it (a list of pairs) or as a map
        labels = None if sel is None else dict(sel.get("matchLabels") or {})
        out.append((int(c.get("maxSkew", 1)), c.get("topologyKey", ""), labels))
    return out


def _matches(selector, labels: dict) -> bool:
    return selector is not None and all(
        labels.get(k) == v for k, v in selector.items())


def _state(cluster) -> dict:
    st = cluster.rule_state.get(NAME)
    if st is None:
        st = cluster.rule_state[NAME] = {
            "bound": [],    # (namespace, labels, node name) of every bind
            "counts": {},   # (namespace, selector items, key) -> {value: n}
            "domains": {},  # key -> the values some node carries
        }
    return st


def _domains(cluster, key: str) -> set:
    doms = _state(cluster)["domains"]
    if key not in doms:
        doms[key] = {nd["labels"][key] for nd in cluster.nodes.values()
                     if key in nd["labels"]}
    return doms[key]


def _counts(cluster, namespace: str, selector: dict, key: str) -> dict:
    st = _state(cluster)
    k = (namespace, frozenset(selector.items()), key)
    got = st["counts"].get(k)
    if got is None:
        got = {}
        for ns, labels, node in st["bound"]:
            if ns == namespace and _matches(selector, labels):
                value = cluster.nodes[node]["labels"].get(key)
                if value is not None:
                    got[value] = got.get(value, 0) + 1
        st["counts"][k] = got
    return got


def why_not(manifest: dict, node_name: str, cluster):
    """None if every DoNotSchedule constraint of the pod holds with the
    pod on `node_name` now, else the reason."""
    meta = manifest.get("metadata") or {}
    namespace, labels = meta.get("namespace", ""), meta.get("labels") or {}
    node_labels = cluster.nodes[node_name]["labels"]
    for max_skew, key, selector in _hard_constraints(manifest):
        value = node_labels.get(key)
        if value is None:
            return f"{NAME}: node lacks topology key {key}"
        counts = ({} if selector is None
                  else _counts(cluster, namespace, selector, key))
        least = min(counts.get(v, 0) for v in _domains(cluster, key))
        here = counts.get(value, 0) + (1 if _matches(selector, labels) else 0)
        if here - least > max_skew:
            return (f"{NAME}: {key}={value} would hold {here} matching pods "
                    f"against a least domain of {least}: skew "
                    f"{here - least} > maxSkew {max_skew}")
    return None


def bind(manifest: dict, node_name: str, cluster) -> None:
    if node_name not in cluster.nodes:
        return
    meta = manifest.get("metadata") or {}
    namespace, labels = meta.get("namespace", ""), meta.get("labels") or {}
    st = _state(cluster)
    st["bound"].append((namespace, labels, node_name))
    node_labels = cluster.nodes[node_name]["labels"]
    for (ns, sel, key), counts in st["counts"].items():
        if ns == namespace and _matches(dict(sel), labels):
            value = node_labels.get(key)
            if value is not None:
                counts[value] = counts.get(value, 0) + 1
