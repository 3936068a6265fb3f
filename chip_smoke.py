#!/usr/bin/env python3
"""chip_smoke.py — the served scheduling path on the chip, once, checked.

What it runs, through the entry points a deployment uses:

  this process            supervisor + REST client; pinned to JAX's CPU
                          platform before any import, never touches a chip
  cmd/apiserver           host-only child, durability on (--data-dir)
  cmd/scheduler           the ONE process that owns the chip
                          (--platform tpu: start-up fails without one)

  Stage A  cold start against a backlog: 5,000 upstream-shaped nodes and
           the SchedulingPodAffinity/5000 workload (1,000 init + 5,000
           measured pods) are loaded over REST BEFORE the scheduler
           exists; it starts, lists the cluster, and its first wave
           batches are as full as the batch bucket allows.
  Stage B  live trickle of the other kernel variants: 1,000
           SchedulingBasic pods (resources only) and 1,000
           SchedulingPodAntiAffinity pods (hard pairs, the full wave
           count), in chunks that wait for their binds.

What it checks (exit code non-zero, reason printed, on any miss):
every pod bound as this client's own watch saw it; placements feasible
under the host filter chain of scheduler/core.py against the final
cluster (plus whole-cluster capacity / anti-affinity / affinity
invariants); every watched bind in the apiserver's WAL after the
children stop; the scheduler on platform tpu with the Pallas fit mask
compiled; a full-bucket batch in stage A; nothing placed by the host
path; no guard trip, device loss, retry, mesh shrink, snapshot drift or
rebuild; a clean anti-entropy pass after the last bind; no compile in
stage B after a shape's first chunk; no failure line in the log.

The last stdout line is one JSON object with exactly these keys:
{"ok": true, "device": {"platform", "kind", "count"}}, the device as the
scheduler process reported it. The line before it is "observations: "
plus one JSON object of what the smoke saw (per stage pods bound, wall
seconds, batches; cache hits; counters). Those are smoke observations,
not benchmark metrics. Without an accelerator the script fails and prints
no result; a CPU rehearsal at a small size is an explicit argument
(--rehearse-cpu --nodes 64), never a default.
"""

from __future__ import annotations

import os

# the supervisor never takes the chip: pinned before anything imports JAX
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import random  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402
import urllib.request  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
NS_A, NS_BASIC, NS_ANTI = "smoke-a", "smoke-b-basic", "smoke-b-anti"
ZONE = "topology.kubernetes.io/zone"
# the small-batch host lane is BY DESIGN live on clusters this small
# (KubeSchedulerConfiguration.small_batch_host_node_max). Only a rehearsal
# can be that small, and only there is that one lane tolerated — and with
# it a shape whose first chunk went down the host lane and whose kernel
# variant therefore first compiles in a later chunk
SMALL_CLUSTER_NODES = 256
SAMPLE = 1200  # placements put through the host filter chain
STAGE_A_DEADLINE_S = 600.0  # scheduler start (compile included) -> all bound
STAGE_B_DEADLINE_S = 180.0  # one chunk posted -> all of it bound
FAILURE_LINES = (
    "scheduling batch failed",
    "scatter warmup failed",
    "Traceback (most recent call last)",
    "uses the jnp broadcast",
)
DIAGNOSE = (
    "scheduler_wave_", "schedule_attempts_total", "pending_pods",
    "scheduler_host_path", "kernel_guard", "scheduler_device_",
    "jax_backend_compiles_total", "snapshot_audit",
)
ZERO_COUNTERS = (
    "kernel_guard_trips_total",
    "scheduler_device_loss_total",
    "scheduler_device_retries_total",
    "scheduler_mesh_shrinks_total",
    "snapshot_drift_rows_total",
    "snapshot_rebuilds_total",
)


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


T0 = time.monotonic()


# -- children ---------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    def __init__(self, name: str, argv: list, env: dict, log_path: str):
        self.name, self.log_path = name, log_path
        self._log = open(log_path, "wb")
        self.t_start = time.monotonic()
        # faulthandler: SIGABRT makes a hung child print every thread's
        # stack into its log before it dies (diagnose())
        self.proc = subprocess.Popen(
            [sys.executable, "-X", "faulthandler", "-m", *argv],
            cwd=HERE,
            env=env,
            stdout=self._log,
            stderr=subprocess.STDOUT,
            start_new_session=True,
        )

    def check_alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise SmokeFailure(
                f"{self.name} exited early with code {rc}; its log ends:\n"
                + self.log_tail()
            )

    def log_text(self) -> str:
        with open(self.log_path, "rb") as f:
            return f.read().decode(errors="replace")

    def log_tail(self, n: int = 4000) -> str:
        # the CPU backend's AOT loader writes multi-KB warning lines
        lines = [ln[:400] for ln in self.log_text().splitlines()]
        return "\n".join(lines)[-n:]

    def diagnose(self, metrics_url: str) -> str:
        """Why is this child not making progress: the series that say
        what it did, then (it is about to be stopped anyway) its threads'
        stacks through faulthandler, then the end of its log."""
        try:
            page = http_get(metrics_url)
            series = "\n".join(
                ln for ln in page.splitlines()
                if not ln.startswith("#") and any(k in ln for k in DIAGNOSE)
            )
        except OSError as e:
            series = f"/metrics did not answer: {e}"
        if self.proc.poll() is None:
            os.kill(self.proc.pid, signal.SIGABRT)
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                pass
        return f"{series}\n{self.name} log ends:\n{self.log_tail(12000)}"

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGINT)
                self.proc.wait(timeout=20)
            except (subprocess.TimeoutExpired, ProcessLookupError):
                pass
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait(timeout=10)
        self._log.close()


def http_get(url: str, timeout: float = 5.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


_SERIES = re.compile(r"^([a-zA-Z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


class Scrape:
    """One parse of a Prometheus text page."""

    def __init__(self, text: str):
        self.series = []
        for line in text.splitlines():
            if line.startswith("#"):
                continue
            m = _SERIES.match(line)
            if m:
                labels = dict(_LABEL.findall(m.group(2) or ""))
                self.series.append((m.group(1), labels, float(m.group(3))))

    def total(self, name: str) -> float:
        return sum(v for n, _, v in self.series if n == name)

    def by_label(self, name: str, key: str) -> dict:
        out: dict = {}
        for n, labels, v in self.series:
            if n == name:
                out[labels.get(key, "")] = out.get(labels.get(key, ""), 0) + v
        return out

    def labels_of(self, name: str) -> dict:
        return next((lb for n, lb, _ in self.series if n == name), {})


# -- the run ----------------------------------------------------------------


class BindWatch:
    """This client's own pod watch: the node each pod was bound to, and
    when the last bind was seen."""

    def __init__(self, client, from_version: int):
        self.bound: dict = {}  # "ns/name" -> node
        self.counts: dict = {}  # namespace -> pods seen bound
        self.rebinds: list = []  # a pod seen bound to two different nodes
        self.t_last = time.monotonic()
        self._w = client.watch("pods", from_version=from_version)
        threading.Thread(target=self._run, daemon=True).start()

    def _run(self) -> None:
        for ev in self._w:
            pod = ev.object
            node = getattr(getattr(pod, "spec", None), "node_name", "")
            if not node:
                continue
            key = pod.metadata.key
            old = self.bound.get(key)
            if old is None:
                self.bound[key] = node
                ns = pod.metadata.namespace
                self.counts[ns] = self.counts.get(ns, 0) + 1
                self.t_last = time.monotonic()
            elif old != node:
                self.rebinds.append((key, old, node))

    @property
    def stopped(self) -> bool:
        return self._w.stopped

    def count(self, namespace: str) -> int:
        return self.counts.get(namespace, 0)

    def stop(self) -> None:
        self._w.stop()


def create_all(client, kind: str, objs: list, threads: int = 8) -> None:
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(lambda o: client.create(kind, o), objs))


def in_namespace(pods: list, namespace: str) -> list:
    for p in pods:
        p.metadata.namespace = namespace
    return pods


def run(args) -> dict:
    from kubernetes_tpu.apiserver.client import RESTClient
    from kubernetes_tpu.perf.workloads import WORKLOADS, build_workload

    n = args.nodes
    per_b = max(2, n // 5)
    wl_a = dataclasses.replace(
        WORKLOADS["SchedulingPodAffinity/5000"],
        num_nodes=n, num_init_pods=n // 5, num_measured_pods=n,
    )
    wl_basic = dataclasses.replace(
        WORKLOADS["SchedulingBasic/5000"],
        num_nodes=n, num_init_pods=0, num_measured_pods=per_b,
    )
    wl_anti = dataclasses.replace(
        WORKLOADS["SchedulingPodAntiAffinity/5000"],
        num_nodes=n, num_init_pods=0, num_measured_pods=per_b,
    )
    nodes, init_a, factory_a = build_workload(wl_a)
    pods_a = in_namespace(
        init_a + [factory_a(i) for i in range(wl_a.num_measured_pods)], NS_A
    )
    _, _, factory_basic = build_workload(wl_basic)
    _, _, factory_anti = build_workload(wl_anti)
    stage_b = [
        ("basic", NS_BASIC,
         in_namespace([factory_basic(i) for i in range(per_b)], NS_BASIC)),
        ("anti_affinity", NS_ANTI,
         in_namespace([factory_anti(i) for i in range(per_b)], NS_ANTI)),
    ]

    os.makedirs(args.out, exist_ok=True)
    data_dir = tempfile.mkdtemp(prefix="chip_smoke_wal_")
    children: list = []
    watch = None
    client = None
    result: dict = {"rehearsal": args.rehearse_cpu, "nodes": n, "seed": args.seed}
    try:
        # ---- apiserver: host-only, WAL on ---------------------------------
        api_port, health_port = free_port(), free_port()
        url = f"http://127.0.0.1:{api_port}"
        api = Child(
            "apiserver",
            ["kubernetes_tpu.cmd.apiserver", "--port", str(api_port),
             "--data-dir", data_dir],
            dict(os.environ, JAX_PLATFORMS="cpu"),
            os.path.join(args.out, "apiserver.log"),
        )
        children.append(api)
        deadline = time.monotonic() + 60
        while True:
            api.check_alive()
            try:
                http_get(url + "/healthz", timeout=1.0)
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise SmokeFailure("apiserver never answered /healthz")
                time.sleep(0.1)
        client = RESTClient(url)
        m = re.search(r"native sink: (True|False)", api.log_text())
        if m is None:
            raise SmokeFailure("apiserver did not report its WAL sink")
        result["native_sink"] = m.group(1) == "True"

        # ---- stage A load: the whole backlog, before any scheduler --------
        t = time.monotonic()
        create_all(client, "nodes", nodes)
        create_all(client, "pods", pods_a)
        say(f"loaded {len(nodes)} nodes + {len(pods_a)} pods over REST in "
            f"{time.monotonic() - t:.1f}s (no scheduler yet)")
        _, rv = client.list("pods", namespace=NS_A)
        watch = BindWatch(client, rv)

        # ---- the one chip-owning process ----------------------------------
        sched_env = dict(os.environ)
        if not args.rehearse_cpu:
            sched_env.pop("JAX_PLATFORMS", None)
        sched = Child(
            "scheduler",
            ["kubernetes_tpu.cmd.scheduler", "--server", url,
             "--platform", "cpu" if args.rehearse_cpu else "tpu",
             "--healthz-port", str(health_port)],
            sched_env,
            os.path.join(args.out, "scheduler.log"),
        )
        children.append(sched)
        metrics_url = f"http://127.0.0.1:{health_port}/metrics"

        def scrape() -> Scrape:
            return Scrape(http_get(metrics_url))

        def wait_bound(namespace: str, want: int, deadline_s: float) -> None:
            deadline = time.monotonic() + deadline_s
            while watch.count(namespace) < want:
                sched.check_alive()
                api.check_alive()
                if watch.stopped:
                    raise SmokeFailure("the client's pod watch was closed")
                if time.monotonic() > deadline:
                    raise SmokeFailure(
                        f"{want - watch.count(namespace)} of {want} pods in "
                        f"{namespace} unbound after {deadline_s:.0f}s\n"
                        + sched.diagnose(metrics_url)
                    )
                time.sleep(0.05)

        wait_bound(NS_A, len(pods_a), STAGE_A_DEADLINE_S)
        s_a = scrape()
        compiles_a = s_a.total("jax_backend_compiles_total")
        result["stage_a"] = {
            "pods_bound": watch.count(NS_A),
            "wall_s_scheduler_start_to_last_bound": round(
                watch.t_last - sched.t_start, 3),
            "wave_batches": int(s_a.total("scheduler_wave_batches_total")),
            "largest_batch": int(s_a.total("scheduler_wave_batch_pods_max")),
            "backend_compiles": int(compiles_a),
        }
        say(f"stage A: {result['stage_a']}")

        # ---- stage B: live trickle, chunks that wait for their binds ------
        result["stage_b"] = {}
        chunk = max(1, min(200, -(-per_b // 2)))
        for shape, namespace, pods in stage_b:
            before = scrape()
            t_post = time.monotonic()
            compiles_first = None
            for i in range(0, len(pods), chunk):
                create_all(client, "pods", pods[i:i + chunk], threads=4)
                wait_bound(namespace, min(i + chunk, len(pods)),
                           STAGE_B_DEADLINE_S)
                if compiles_first is None:
                    compiles_first = scrape().total("jax_backend_compiles_total")
            after = scrape()
            result["stage_b"][shape] = {
                "pods_bound": watch.count(namespace),
                "wall_s_first_post_to_last_bound": round(
                    watch.t_last - t_post, 3),
                "wave_batches": int(
                    after.total("scheduler_wave_batches_total")
                    - before.total("scheduler_wave_batches_total")),
                "largest_batch_so_far": int(
                    after.total("scheduler_wave_batch_pods_max")),
                "backend_compiles_first_chunk": int(
                    compiles_first - before.total("jax_backend_compiles_total")),
                "backend_compiles_after_first_chunk": int(
                    after.total("jax_backend_compiles_total") - compiles_first),
            }
            say(f"stage B {shape}: {result['stage_b'][shape]}")

        # ---- a clean anti-entropy pass AFTER the last bind ----------------
        passes0 = scrape().total("snapshot_audit_passes_total")
        deadline = time.monotonic() + 60
        while scrape().total("snapshot_audit_passes_total") <= passes0:
            sched.check_alive()
            if time.monotonic() > deadline:
                raise SmokeFailure(
                    "no anti-entropy pass within 60s of the last bind")
            time.sleep(0.5)
        final = scrape()
        sched_log = sched.log_text()
        pods_final, _ = client.list("pods")
        nodes_final, _ = client.list("nodes")
    finally:
        if watch is not None:
            watch.stop()
        if client is not None:
            client.close()
        for c in reversed(children):
            c.stop()

    try:
        failures = []
        failures += check_device(final, sched_log, result, args)
        failures += check_counters(final, result, args)
        failures += check_stages(result, len(pods_a), per_b, args)
        failures += check_log(sched_log)
        failures += check_placements(
            watch, pods_final, nodes_final, args.seed, result)
        failures += check_wal(data_dir, watch, result)
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    if failures:
        raise SmokeFailure(
            f"{len(failures)} check(s) failed:\n  " + "\n  ".join(failures[:40])
            + f"\nobservations: {json.dumps(result)}")
    return result


# -- checks -----------------------------------------------------------------


def check_device(final: Scrape, log: str, result: dict, args) -> list:
    info = final.labels_of("scheduler_device_info")
    runtime = re.search(r"runtime: (.*)", log)
    kv = dict(re.findall(r"(\w+)=(\S+)", runtime.group(1) if runtime else ""))
    placement = re.search(r"snapshot placement: (.*)", log)
    device_path = re.search(r"device path: (.*)", log)
    compiles = final.by_label("jax_backend_compiles_total", "persistent_cache")
    wave = {
        lb["persistent_cache"]: int(v)
        for n, lb, v in final.series
        if n == "jax_backend_compiles_total"
        and lb.get("program") == "jit(wave_kernel)"
    }
    hits, misses = int(compiles.get("hit", 0)), int(compiles.get("miss", 0))
    start = {(True, False): "warm", (False, True): "cold"}.get(
        (hits > 0, misses > 0), "mixed")
    result.update(
        device={
            "platform": info.get("platform"),
            "kind": info.get("device_kind"),
            "count": int(info.get("devices") or 0),
        },
        mesh=int(info.get("mesh") or 0),
        batch_bucket=int(info.get("batch_bucket") or 0),
        pallas_fit=info.get("pallas_fit"),
        pallas_interpret=info.get("pallas_interpret"),
        versions={k: kv.get(k) for k in ("jax", "jaxlib", "libtpu")},
        compile_cache={
            "dir": kv.get("compilation_cache"),
            "start": start,
            "hits": hits,
            "misses": misses,
            "wave_kernel": wave,
        },
        device_path=device_path.group(1) if device_path else None,
        snapshot_placement=placement.group(1) if placement else None,
    )
    out = []
    if not info:
        return ["the scheduler published no scheduler_device_info"]
    if not info.get("device_kind"):
        out.append("the scheduler reported no device_kind")
    if not placement:
        out.append("the scheduler logged no snapshot placement line")
    if args.rehearse_cpu:
        return out
    if info.get("platform") != "tpu":
        out.append(f"scheduler platform is {info.get('platform')!r}, not tpu")
    if info.get("pallas_fit") != "on" or info.get("pallas_interpret") != "false":
        out.append(
            "the Pallas fit mask did not run compiled: pallas_fit="
            f"{info.get('pallas_fit')} interpret={info.get('pallas_interpret')}")
    return out


def check_counters(final: Scrape, result: dict, args) -> list:
    out = []
    host = {k: int(v) for k, v in final.by_label(
        "scheduler_host_path_pods_total", "lane").items() if v}
    tolerated = {"small_batch"} if args.nodes <= SMALL_CLUSTER_NODES else set()
    result["host_path_pods"] = host
    for lane, v in host.items():
        if lane not in tolerated:
            out.append(f"{v} pod(s) placed by the host path, lane {lane}")
    if final.total("scheduler_device_down"):
        out.append("scheduler_device_down is set")
    result["safety_counters"] = {}
    for name in ZERO_COUNTERS:
        v = result["safety_counters"][name] = int(final.total(name))
        if v:
            series = [(lb, x) for n, lb, x in final.series if n == name and x]
            out.append(f"{name} = {v}: {series}")
    result["audit_passes"] = int(final.total("snapshot_audit_passes_total"))
    if result["audit_passes"] < 1:
        out.append("no anti-entropy audit pass")
    return out


def check_stages(result: dict, n_a: int, per_b: int, args) -> list:
    out = []
    a = result["stage_a"]
    if a["wave_batches"] < 1:
        out.append("scheduler_wave_batches_total is 0")
    want = min(result["batch_bucket"] or 0, n_a)
    if a["largest_batch"] < want:
        out.append(
            f"stage A's largest batch was {a['largest_batch']} pods; a "
            f"backlog of {n_a} should fill {want}")
    for shape, b in result["stage_b"].items():
        if b["pods_bound"] != per_b:
            out.append(f"stage B {shape}: {b['pods_bound']}/{per_b} bound")
        if (b["backend_compiles_after_first_chunk"]
                and args.nodes > SMALL_CLUSTER_NODES):
            out.append(
                f"stage B {shape}: {b['backend_compiles_after_first_chunk']} "
                "compile(s) after the shape's first chunk")
    return out


def check_log(log: str) -> list:
    return [
        f"scheduler log contains {needle!r}"
        for needle in FAILURE_LINES
        if needle in log
    ]


def check_placements(watch, pods, nodes, seed, result) -> list:
    """The plain reference: the host filter chain (the framework
    scheduler/core.py drives) for a seed-chosen sample of bound pods on
    the node each was bound to, against the final listed cluster with
    that pod removed — plus invariants over EVERY pod."""
    from kubernetes_tpu.scheduler.cache.nodeinfo import Snapshot
    from kubernetes_tpu.scheduler.framework.interface import (
        CycleState, is_success)
    from kubernetes_tpu.scheduler.framework.registry import (
        default_plugin_set, default_registry)
    from kubernetes_tpu.scheduler.framework.runtime import Framework

    out = []
    if watch.rebinds:
        out.append(f"pods seen bound to two nodes: {watch.rebinds[:3]}")
    by_key = {p.metadata.key: p for p in pods}
    node_names = {nd.metadata.name for nd in nodes}
    for key, node in watch.bound.items():
        p = by_key.get(key)
        if p is None or p.spec.node_name != node:
            out.append(
                f"{key}: watch saw {node}, final list has "
                f"{p.spec.node_name if p else 'no such pod'}")
    unbound = [p.metadata.key for p in pods if not p.spec.node_name]
    if unbound:
        out.append(f"{len(unbound)} pods unbound in the final list")
    strays = [p.metadata.key for p in pods
              if p.spec.node_name and p.spec.node_name not in node_names]
    if strays:
        out.append(f"{len(strays)} pods bound to unknown nodes: {strays[:3]}")

    snapshot = Snapshot.from_literals(pods, nodes)
    zone_has_bench = set()
    for ni in snapshot.node_info_list:
        if ni.node.spec.unschedulable and ni.pods:
            out.append(f"{ni.name}: unschedulable node holds pods")
        over = {k: v for k, v in ni.requested.items()
                if v > ni.allocatable.get(k, 0)}
        if over:
            out.append(f"{ni.name}: requested over allocatable: {over}")
        if len(ni.pods) > int(ni.allocatable.get("pods", 0)):
            out.append(f"{ni.name}: {len(ni.pods)} pods over its pod limit")
        anti = [p for p in ni.pods if p.metadata.namespace == NS_ANTI]
        if len(anti) > 1:
            out.append(f"{ni.name}: {len(anti)} anti-affinity pods on one host")
        if any(p.metadata.namespace == NS_A for p in ni.pods):
            zone_has_bench.add(ni.node.metadata.labels[ZONE])
    for p in pods:
        if p.metadata.namespace == NS_A and p.spec.affinity is not None:
            z = snapshot.get(p.spec.node_name).node.metadata.labels[ZONE]
            if z not in zone_has_bench:
                out.append(f"{p.metadata.key}: no app=bench pod in zone {z}")

    plugin_set = default_plugin_set()
    fw = Framework(
        default_registry(), plugin_set,
        {"snapshot_getter": lambda: snapshot,
         "hard_pod_affinity_weight": 1.0,
         "ignored_extended_resources": frozenset()},
    )
    rng = random.Random(seed)
    chosen = []
    for namespace in (NS_A, NS_BASIC, NS_ANTI):
        keys = sorted(k for k in watch.bound if k.startswith(namespace + "/"))
        chosen += rng.sample(keys, min(len(keys), SAMPLE // 3))
    t = time.monotonic()
    for key in chosen:
        pod = by_key[key]
        ni = snapshot.get(pod.spec.node_name)
        ni.remove_pod(key)
        try:
            candidate = pod.deep_copy()
            candidate.spec.node_name = ""
            state = CycleState()
            st = fw.run_pre_filter_plugins(state, candidate)
            if is_success(st):
                st = fw.run_filter_plugins(state, candidate, ni)
            if not is_success(st):
                out.append(
                    f"{key} on {ni.name}: host filter chain says "
                    f"{st.message or st.code}")
        finally:
            ni.add_pod(pod)
    result["reference"] = {
        "invariants_checked_on_pods": len(pods),
        "filter_chain_sample": len(chosen),
        "filter_plugins": list(plugin_set.filter),
        "host_seconds": round(time.monotonic() - t, 1),
    }
    say(f"reference: {len(chosen)} sampled placements through the host "
        f"filter chain, invariants over {len(pods)} pods: "
        f"{len(out)} violation(s)")
    return out


def check_wal(data_dir: str, watch, result: dict) -> list:
    """The guarantee: an acknowledged bind is on disk. Both children are
    stopped; recover the store from its WAL and find every bind the
    watch reported, on the same node."""
    from kubernetes_tpu.client.apiserver import APIServer

    t = time.monotonic()
    store = APIServer.recover(os.path.join(data_dir, "cluster"))
    try:
        pods, _ = store.list("pods")
    finally:
        store.wal.close()
    node_of = {p.metadata.key: p.spec.node_name for p in pods}
    out = [
        f"WAL: {key} recovered on {node_of.get(key)!r}, the watch saw {node}"
        for key, node in watch.bound.items()
        if node_of.get(key) != node
    ]
    if store.disk_corrupt:
        out.append("WAL recovery reported mid-log corruption")
    result["wal"] = {
        "binds_read_back": len(watch.bound) - len(out),
        "recover_seconds": round(time.monotonic() - t, 1),
    }
    say(f"WAL read-back: {result['wal']}")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="chooses the reference check's sample")
    ap.add_argument("--nodes", type=int, default=5000)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run the scheduler on JAX's CPU platform: a "
                    "rehearsal of the script, not a smoke of the chip")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out", "chip_smoke"),
                    help="directory for the children's logs")
    args = ap.parse_args()
    if args.rehearse_cpu and args.nodes >= 5000:
        ap.error("--rehearse-cpu is for a small --nodes, e.g. 64")
    sys.path.insert(0, HERE)
    try:
        import kubernetes_tpu  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: not in a checkout of the repo: {e}", file=sys.stderr)
        return 2
    # one budget for the whole run, children included
    signal.signal(signal.SIGALRM, _timed_out)
    signal.alarm(1150)
    try:
        result = run(args)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    device = result.pop("device")
    print(f"observations: {json.dumps(result)}", flush=True)
    # the contract's line: these keys and no others
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def _timed_out(_sig, _frame):
    raise SmokeFailure("the run exceeded its 1150 s budget")


if __name__ == "__main__":
    sys.exit(main())
