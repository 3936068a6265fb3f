"""What decides `correct`: the plain reference of the placements, the
read-back of the WAL, and the scheduler's account of which path did the
work. Nothing here imports the program.

Every comparison is exact (limit 0): a count of answers that are wrong
or never came.
"""

from __future__ import annotations

import json
import os
import re
import zlib

_SUFFIX = {"Ki": 2**10, "Mi": 2**20, "Gi": 2**30, "Ti": 2**40,
           "k": 10**3, "M": 10**6, "G": 10**9, "T": 10**12}


def quantity(v, milli: bool = False) -> int:
    """A Kubernetes quantity as an integer: bytes (or plain units), or
    thousandths for `milli` (cpu)."""
    s = str(v).strip()
    scale = 1000 if milli else 1
    if s.endswith("m"):
        return int(float(s[:-1]) * scale / 1000)
    for suf, mult in _SUFFIX.items():
        if s.endswith(suf):
            return int(float(s[: -len(suf)]) * mult * scale)
    return int(float(s) * scale)


def pod_demand(manifest: dict) -> dict:
    cpu = mem = 0
    for c in (manifest.get("spec") or {}).get("containers") or []:
        req = c.get("requests") or {}
        cpu += quantity(req.get("cpu", 0), milli=True)
        mem += quantity(req.get("memory", 0))
    return {"cpu": cpu, "memory": mem}


def required_terms(manifest: dict, kind: str) -> list:
    """[(matchLabels dict, topologyKey)] of the pod's required
    podAffinity / podAntiAffinity terms."""
    aff = ((manifest.get("spec") or {}).get("affinity") or {}).get(kind) or {}
    out = []
    for term in aff.get("required") or []:
        ml = (term.get("labelSelector") or {}).get("matchLabels") or {}
        out.append((dict(ml), term.get("topologyKey", "")))
    return out


class ReferenceCluster:
    """The filter semantics, written plainly: a pod may be bound to a node
    if the node exists and is schedulable, the node's pod count, cpu and
    memory stay within its allocatable, every required pod-affinity term
    finds a matching pod in the node's topology domain (or none exists
    anywhere and the pod matches its own term), and no required
    anti-affinity term does. State is the binds replayed so far.

    Those four rules always run. `rules` are the further ones a
    configuration names under `reference_rules`, each a module of
    benchmark/reference_rules/ with two plain functions,
    `why_not(manifest, node_name, cluster) -> str | None` and
    `bind(manifest, node_name, cluster)`: asked after the four, in the
    configuration's order, and told of every bind. `cluster` is this
    object; a rule keeps its own state in `cluster.rule_state[<its name>]`."""

    def __init__(self, node_manifests: list, rules=()):
        self.rules = list(rules)
        self.rule_state: dict = {}
        self.nodes = {}
        for m in node_manifests:
            alloc = (m.get("status") or {}).get("allocatable") or {}
            self.nodes[m["metadata"]["name"]] = {
                "labels": m["metadata"].get("labels") or {},
                "unschedulable": bool((m.get("spec") or {}).get("unschedulable")),
                "cpu": quantity(alloc.get("cpu", 0), milli=True),
                "memory": quantity(alloc.get("memory", 0)),
                "pods": int(alloc.get("pods", 0)),
                "used": {"cpu": 0, "memory": 0, "pods": 0},
            }
        # (frozenset of label items, topology key) -> {domain value: count}
        self._sel_counts: dict = {}
        self._placed: list = []  # (labels, node name)

    def _count(self, selector: dict, topo_key: str) -> dict:
        k = (frozenset(selector.items()), topo_key)
        got = self._sel_counts.get(k)
        if got is None:
            got = {}
            for labels, node in self._placed:
                if all(labels.get(a) == b for a, b in selector.items()):
                    d = self.nodes[node]["labels"].get(topo_key)
                    got[d] = got.get(d, 0) + 1
            self._sel_counts[k] = got
        return got

    def why_not(self, manifest: dict, node: str):
        """None if the pod may be bound to `node` now, else the reason."""
        nd = self.nodes.get(node)
        if nd is None:
            return "unknown node"
        if nd["unschedulable"]:
            return "unschedulable node"
        need, used = pod_demand(manifest), nd["used"]
        if used["pods"] + 1 > nd["pods"]:
            return "over the node's pod limit"
        for r in ("cpu", "memory"):
            if used[r] + need[r] > nd[r]:
                return f"over the node's allocatable {r}"
        labels = manifest["metadata"].get("labels") or {}
        for sel, key in required_terms(manifest, "podAffinity"):
            counts = self._count(sel, key)
            dom = nd["labels"].get(key)
            if dom is None:
                return f"node lacks topology key {key}"
            if counts.get(dom, 0) > 0:
                continue
            matches_self = all(labels.get(a) == b for a, b in sel.items())
            if not (matches_self and not any(counts.values())):
                return f"no pod matching {sel} in {key}={dom}"
        for sel, key in required_terms(manifest, "podAntiAffinity"):
            dom = nd["labels"].get(key)
            if dom is not None and self._count(sel, key).get(dom, 0) > 0:
                return f"a pod matching {sel} already in {key}={dom}"
        for rule in self.rules:
            why = rule.why_not(manifest, node, self)
            if why is not None:
                return why
        return None

    def bind(self, manifest: dict, node: str) -> None:
        nd = self.nodes.get(node)
        if nd is None:
            return
        need = pod_demand(manifest)
        nd["used"]["pods"] += 1
        nd["used"]["cpu"] += need["cpu"]
        nd["used"]["memory"] += need["memory"]
        labels = manifest["metadata"].get("labels") or {}
        self._placed.append((labels, node))
        for (sel, key), counts in self._sel_counts.items():
            if all(labels.get(a) == b for a, b in sel):
                d = nd["labels"].get(key)
                counts[d] = counts.get(d, 0) + 1
        for rule in self.rules:
            rule.bind(manifest, node, self)


def check_placements(node_manifests: list, order: list, manifest_of,
                     rebinds: list, rules=()) -> list:
    """Replay every bind the watch saw, in the order it saw them, through
    the reference; the violations, as text. `manifest_of(key)` gives the
    manifest this benchmark created under that key (None: not ours).
    `rules`: the configuration's own reference rules (Catalog finds them)."""
    ref = ReferenceCluster(node_manifests, rules)
    out = [f"{k}: seen bound to {a} and then to {b}" for k, a, b in rebinds]
    for key, node in order:
        m = manifest_of(key)
        if m is None:
            out.append(f"{key}: a bind of a pod this run never created")
            continue
        why = ref.why_not(m, node)
        if why is not None:
            out.append(f"{key} on {node}: {why}")
        ref.bind(m, node)
    return out


def _wal_record(line: str):
    """One WAL line -> record dict, or None if it is damaged. v2 frames
    are `K2 <crc32 hex8> <json>`, v1 lines are bare JSON."""
    if line.startswith("K2 "):
        body = line[3:]
        if len(body) < 10 or body[8] != " ":
            return None
        payload = body[9:]
        try:
            if zlib.crc32(payload.encode("utf-8")) & 0xFFFFFFFF != int(body[:8], 16):
                return None
            return json.loads(payload)
        except ValueError:
            return None
    if line.startswith("{"):
        try:
            return json.loads(line)
        except ValueError:
            return None
    return None


def read_wal_pods(data_dir: str) -> tuple:
    """({"ns/name": nodeName} as the files under <data_dir> hold them,
    number of damaged lines). Snapshot first, then the log's records past
    the snapshot's rv, in order. Both children are stopped by now."""
    base = os.path.join(data_dir, "cluster")
    pods: dict = {}
    snap_rv = 0
    try:
        with open(base + ".snapshot.json", encoding="utf-8") as f:
            snap = json.load(f)
        snap_rv = int(snap.get("rv", 0))
        for obj in (snap.get("objects") or {}).get("pods", []):
            meta = obj.get("metadata") or {}
            key = f"{meta.get('namespace', '')}/{meta.get('name', '')}"
            pods[key] = (obj.get("spec") or {}).get("nodeName") or ""
    except FileNotFoundError:
        pass
    damaged = 0
    try:
        with open(base + ".wal", encoding="utf-8", errors="replace") as f:
            lines = f.read().split("\n")
    except FileNotFoundError:
        return pods, damaged
    for n, line in enumerate(lines):
        if not line:
            continue
        rec = _wal_record(line)
        if rec is None:
            # a torn tail (the last line, cut by the kill) loses nothing
            # that was acknowledged; damage before it does
            if n < len(lines) - 1 and any(lines[n + 1:]):
                damaged += 1
            continue
        if rec.get("kind") != "pods" or int(rec.get("rv", 0)) <= snap_rv:
            continue
        obj = rec.get("obj") or {}
        meta = obj.get("metadata") or {}
        key = f"{meta.get('namespace', '')}/{meta.get('name', '')}"
        if rec.get("verb") == "delete":
            pods.pop(key, None)
        else:
            pods[key] = (obj.get("spec") or {}).get("nodeName") or ""
    return pods, damaged


def check_wal(data_dir: str, bound: dict) -> list:
    """The guarantee: an acknowledged bind is on disk. Every bind the
    watch reported has to be read back, on the same node."""
    on_disk, damaged = read_wal_pods(data_dir)
    out = [
        f"WAL: {key} read back on {on_disk.get(key)!r}, the watch saw {node}"
        for key, node in bound.items()
        if on_disk.get(key) != node
    ]
    if damaged:
        out.append(f"WAL: {damaged} damaged record(s) before the tail")
    return out


FAILURE_LINES = (
    "scheduling batch failed",
    "scatter warmup failed",
    "Traceback (most recent call last)",
    "uses the jnp broadcast",
)
ZERO_COUNTERS = (
    "kernel_guard_trips_total",
    "scheduler_device_loss_total",
    "scheduler_device_retries_total",
    "scheduler_mesh_shrinks_total",
    "snapshot_drift_rows_total",
    "snapshot_rebuilds_total",
)
_RULE = "-" * 40
_HUNG_UP = ("BrokenPipeError", "ConnectionResetError")


def without_hung_up_clients(log: str) -> str:
    """`log` without the blocks that Python's socketserver prints when an
    HTTP client closed its socket before the answer was written:

        ----------------------------------------
        Exception occurred during processing of request from (...)
        Traceback (most recent call last):
          ...
        BrokenPipeError: [Errno 32] Broken pipe
        ----------------------------------------

    The only HTTP clients of the scheduler process are this harness's own
    polls of /healthz and /metrics; one that gave up on a slow answer (1
    run in 15 on the chip, PR 29, during the scheduler's start) leaves
    this block and says nothing about the scheduling path. A block that
    ends in any other exception stays, and so does every traceback
    outside such a block."""
    lines = log.split("\n")
    out, i = [], 0
    while i < len(lines):
        if (lines[i] == _RULE and i + 1 < len(lines) and lines[i + 1].startswith(
                "Exception occurred during processing of request")):
            try:
                end = lines.index(_RULE, i + 1)
            except ValueError:
                end = None
            if end is not None and lines[end - 1].startswith(_HUNG_UP):
                i = end + 1
                continue
        out.append(lines[i])
        i += 1
    return "\n".join(out)


# the small-batch host lane is BY DESIGN live on clusters this small; only
# a rehearsal can be that small, and only there is that lane tolerated
SMALL_CLUSTER_NODES = 256


def check_device_path(final, sched_log: str, expect_platform: str,
                      n_nodes: int, n_pods: int) -> tuple:
    """(pods or events that did not go the device's way, reasons).
    `final` is the scheduler's last Scrape."""
    info = final.labels_of("scheduler_device_info")
    out, off = [], 0
    if info.get("platform") != expect_platform:
        out.append(f"scheduler platform is {info.get('platform')!r}, "
                   f"not {expect_platform}")
        off += n_pods
    elif expect_platform == "tpu" and (
            info.get("pallas_fit") != "on"
            or info.get("pallas_interpret") != "false"):
        out.append("the Pallas fit mask did not run compiled: pallas_fit="
                   f"{info.get('pallas_fit')} "
                   f"interpret={info.get('pallas_interpret')}")
        off += n_pods
    tolerated = {"small_batch"} if n_nodes <= SMALL_CLUSTER_NODES else set()
    for lane, v in final.by_label("scheduler_host_path_pods_total",
                                  "lane").items():
        if v and lane not in tolerated:
            out.append(f"{int(v)} pod(s) placed by the host path, lane {lane}")
            off += int(v)
    if final.total("scheduler_device_down"):
        out.append("scheduler_device_down is set")
        off += 1
    for name in ZERO_COUNTERS:
        v = int(final.total(name))
        if v:
            out.append(f"{name} = {v}")
            off += v
    sched_log = without_hung_up_clients(sched_log)
    for needle in FAILURE_LINES:
        hits = len(re.findall(re.escape(needle), sched_log))
        if hits:
            out.append(f"scheduler log has {needle!r} {hits} time(s)")
            off += hits
    return off, out
