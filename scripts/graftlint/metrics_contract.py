"""Pass 3: metrics contract.

The registry (utils/metrics.py) is schemaless by design — any call can
mint a series — which is exactly how the drift the PR-6 review rounds
kept catching happened: the same logical series emitted under two label
key sets, counters named like gauges, series that exist in code but in
no documentation and no SIGUSR2 dump. This pass collects every
``metrics.inc`` / ``metrics.set_gauge`` / ``metrics.observe`` (and
``adjust_gauge`` / ``merge_histogram``) call in
the tree (series names resolved through module-level string constants,
the dominant idiom) and enforces:

  1. one series name = one instrument kind (counter XOR gauge XOR
     histogram);
  2. counters end in ``_total`` (Prometheus naming contract);
  3. one label KEY SET per series across every call site;
  4. every series appears in the README metrics reference
     (config.METRICS_DOC);
  5. series in config.DUMP_REQUIRED_FAMILIES are covered by a SIGUSR2
     dump section — a ``snapshot_gauges``/``snapshot_counters`` call
     whose literal prefix covers the name (prefixes iterated from a
     ``for prefix in (...)`` tuple are resolved too).

A series name the pass cannot resolve statically is itself a finding:
dynamic names are how undocumented series are born.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from core import Finding, Module, Tree, dotted_name
import config

PASS = "metrics"

_METHODS = {
    "inc": "counter",
    "set_gauge": "gauge",
    "observe": "histogram",
    # an in-flight count kept by +1/-1, and pre-aggregated observations
    # folded in by a batched publisher (utils/metrics.py)
    "adjust_gauge": "gauge",
    "merge_histogram": "histogram",
    # a pre-resolved family observed together (HistogramSet): the labels
    # dict is literal, one value may be the tuple of a varying label
    "histogram_set": "histogram",
}
# positional index of the labels argument per method (after name)
_LABELS_POS = {
    "inc": 1, "set_gauge": 2, "observe": 2,
    "adjust_gauge": 2, "merge_histogram": 1, "histogram_set": 1,
}


class Series:
    def __init__(self, name: str):
        self.name = name
        self.kinds: Set[str] = set()
        self.label_sets: Dict[frozenset, Tuple[str, int]] = {}
        self.first_site: Optional[Tuple[str, int]] = None


def _resolve_name(
    mod: Module, arg: ast.expr, global_consts: Dict[str, Optional[str]]
) -> Optional[str]:
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    if isinstance(arg, ast.Name):
        local = mod.str_constants.get(arg.id)
        if local is not None:
            return local
        # imported constant: resolve tree-wide when unambiguous (None in
        # the map = two modules define the name with different values)
        return global_consts.get(arg.id)
    return None


def _labels_arg(call: ast.Call, method: str) -> Optional[ast.expr]:
    pos = _LABELS_POS[method]
    if len(call.args) > pos:
        return call.args[pos]
    for kw in call.keywords:
        if kw.arg == "labels":
            return kw.value
    return None


def _label_keys(arg: Optional[ast.expr]) -> Optional[frozenset]:
    """frozenset of label keys; empty set for no labels; None when the
    labels expression is not a statically-known dict literal."""
    if arg is None or (
        isinstance(arg, ast.Constant) and arg.value is None
    ):
        return frozenset()
    if isinstance(arg, ast.Dict):
        keys = []
        for k in arg.keys:
            if isinstance(k, ast.Constant) and isinstance(k.value, str):
                keys.append(k.value)
            else:
                return None  # **spread / computed key
        return frozenset(keys)
    return None


def _dump_prefixes(tree: Tree) -> Set[str]:
    """Literal prefixes passed to snapshot_gauges/snapshot_counters,
    including loop variables iterated over a literal tuple/list."""
    prefixes: Set[str] = set()
    for mod, call in tree.walk_calls():
        f = call.func
        if not (
            isinstance(f, ast.Attribute)
            and f.attr in ("snapshot_gauges", "snapshot_counters")
        ):
            continue
        if not call.args:
            continue
        arg = call.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            prefixes.add(arg.value)
        elif isinstance(arg, ast.Name):
            # `for prefix in ("a_", "b_"): metrics.snapshot_gauges(prefix)`
            for anc in mod.ancestors(call):
                if (
                    isinstance(anc, ast.For)
                    and isinstance(anc.target, ast.Name)
                    and anc.target.id == arg.id
                    and isinstance(anc.iter, (ast.Tuple, ast.List))
                ):
                    for elt in anc.iter.elts:
                        if isinstance(elt, ast.Constant) and isinstance(
                            elt.value, str
                        ):
                            prefixes.add(elt.value)
    return prefixes


def collect(tree: Tree) -> Tuple[Dict[str, Series], List[Finding]]:
    registry: Dict[str, Series] = {}
    findings: List[Finding] = []
    global_consts: Dict[str, Optional[str]] = {}
    for mod in tree.modules:
        for cname, cval in mod.str_constants.items():
            if cname in global_consts and global_consts[cname] != cval:
                global_consts[cname] = None  # ambiguous across modules
            else:
                global_consts[cname] = cval
    for mod in tree.modules:
        if mod.rel.endswith(os.path.join("utils", "metrics.py")):
            continue  # the registry implementation itself
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not (
                isinstance(f, ast.Attribute) and f.attr in _METHODS
            ):
                continue
            recv = dotted_name(f.value)
            if not recv or recv.rsplit(".", 1)[-1] != "metrics":
                continue
            if mod.node_has(node, "metrics-exempt"):
                continue
            if not node.args:
                continue
            name = _resolve_name(mod, node.args[0], global_consts)
            if name is None:
                findings.append(
                    Finding(
                        mod.rel,
                        node.lineno,
                        PASS,
                        f"dynamic-name:{node.lineno}",
                        f"metrics.{f.attr} with a series name the lint "
                        "cannot resolve statically (literal or "
                        "module-level constant required)",
                    )
                )
                continue
            s = registry.setdefault(name, Series(name))
            s.kinds.add(_METHODS[f.attr])
            if s.first_site is None:
                s.first_site = (mod.rel, node.lineno)
            keys = _label_keys(_labels_arg(node, f.attr))
            if keys is not None:
                s.label_sets.setdefault(keys, (mod.rel, node.lineno))
            else:
                findings.append(
                    Finding(
                        mod.rel,
                        node.lineno,
                        PASS,
                        f"dynamic-labels:{name}",
                        f"series `{name}`: labels are not a literal dict "
                        "— label-set consistency is unverifiable here",
                    )
                )
    return registry, findings


def run(tree: Tree, repo_root: str, doc_path: str = None) -> List[Finding]:
    registry, findings = collect(tree)
    if doc_path is None:
        doc_path = os.path.join(repo_root, config.METRICS_DOC)
    try:
        with open(doc_path, "r", encoding="utf-8") as fh:
            doc = fh.read()
    except OSError:
        doc = ""
    doc_names = set(re.findall(r"[a-zA-Z_][a-zA-Z0-9_]*", doc))
    dump_prefixes = _dump_prefixes(tree)

    for name in sorted(registry):
        s = registry[name]
        path, line = s.first_site or ("?", 0)
        if len(s.kinds) > 1:
            findings.append(
                Finding(
                    path, line, PASS, f"kind-conflict:{name}",
                    f"series `{name}` used as more than one instrument "
                    f"kind: {sorted(s.kinds)}",
                )
            )
        if "counter" in s.kinds and not name.endswith("_total"):
            findings.append(
                Finding(
                    path, line, PASS, f"counter-suffix:{name}",
                    f"counter `{name}` must end in `_total`",
                )
            )
        if len(s.label_sets) > 1:
            desc = "; ".join(
                f"{{{', '.join(sorted(ks)) or 'no labels'}}} at {p}:{ln}"
                for ks, (p, ln) in sorted(
                    s.label_sets.items(), key=lambda kv: sorted(kv[0])
                )
            )
            findings.append(
                Finding(
                    path, line, PASS, f"label-drift:{name}",
                    f"series `{name}` emitted with {len(s.label_sets)} "
                    f"different label key sets: {desc}",
                )
            )
        if name not in doc_names:
            findings.append(
                Finding(
                    path, line, PASS, f"undocumented:{name}",
                    f"series `{name}` missing from the "
                    f"{config.METRICS_DOC} metrics reference",
                )
            )
        fam = next(
            (
                f
                for f in config.DUMP_REQUIRED_FAMILIES
                if name.startswith(f)
            ),
            None,
        )
        if fam and not any(name.startswith(p) for p in dump_prefixes):
            findings.append(
                Finding(
                    path, line, PASS, f"no-dump-section:{name}",
                    f"series `{name}` (family `{fam}`) is not covered by "
                    "any SIGUSR2 dump section "
                    "(snapshot_gauges/snapshot_counters prefix)",
                )
            )
    return findings
