"""One run of one cell: the supervisor, the load generator and the
watching client. Never touches the chip (run.py pins JAX to the CPU
before anything is imported; nothing here imports JAX at all except the
reading of a finished trace).

  apiserver child   python -m kubernetes_tpu.cmd.apiserver --data-dir <tmp>
                    host-only, WAL and fsync on: the guarantee of every cell
  scheduler child   benchmark/harness/sched_child.py --server <url>
                    --platform tpu: the ONE process that holds the chip

Deadlines never raise: a missed one turns the pods still waiting into
failures, and the run still stops its children, checks what it has and
prints its line. Only a run with no result at all (no chip, a child that
never came up) raises RunFailure.
"""

from __future__ import annotations

import json
import os
import random
import shutil
import signal
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from . import check, loadgen
from .catalog import Catalog
from .children import RunFailure, http_get, start_serving
from .rest import BindWatch, Rest
from .scrape import Scrape

T_PROCESS_START = time.monotonic()
SCHED_CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "sched_child.py")
SCHEDULER_START_DEADLINE_S = 600.0  # the smoke's: a cold start compiles
WARMUP_BURST_DEADLINE_S = 600.0
WARMUP_TRICKLE_DEADLINE_S = 180.0
# past the drain, for the launcher to say that the profiler has written
# the trace; a trace that has not stopped by then is a run with no result
TRACE_STOP_DEADLINE_S = 120.0


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_PROCESS_START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _render(manifest: dict) -> str:
    return json.dumps(manifest, separators=(",", ":"))


def offered_rate(traffic: dict, scale: float) -> float:
    """The traffic file's rate; a rehearsal on a small cluster (`scale`
    under 1: its share of the configuration's nodes) offers the same share
    of it, and 20 pods/s at the least."""
    r = float(traffic["rate_per_s"])
    return r if scale == 1 else max(20.0, r * scale)


def warmup_burst(traffic: dict, scale: float) -> int:
    """The pods of the warm-up burst, scaled as the rate is; 10 at least."""
    return max(10, int(round(traffic["warmup"]["burst_pods"] * scale)))


class Cluster:
    """The configuration's objects, made from the seed: node manifests,
    the residents with the node each is created bound to, and pod bodies
    by template."""

    def __init__(self, config: dict, seed: int, nodes_override: int | None):
        self.config = config
        self.ns = config["namespace"]
        full = config["nodes"]["count"]
        self.n_nodes = nodes_override or full
        self.scale = self.n_nodes / full
        zones = config["nodes"]["zones"]
        text = _render(config["nodes"]["manifest"])
        self.node_manifests = [
            json.loads(text.replace("$NAME", f"node-{i}")
                       .replace("$ZONE", f"zone-{i % zones}"))
            for i in range(self.n_nodes)
        ]
        self._templates = {k: _render(v)
                           for k, v in config["pod_templates"].items()}
        self._made: dict = {}  # "ns/name" -> (template, node or None)
        res = config["residents"]
        allowed = [i for i in range(self.n_nodes)
                   if i % zones < res.get("zones", zones)]
        random.Random(seed).shuffle(allowed)  # the permutation from --seed
        n_res = max(1, int(round(res["count"] * self.scale)))
        self.residents = [
            (f"r-{j}", res["template"], f"node-{allowed[j % len(allowed)]}")
            for j in range(n_res)
        ]

    def _pod_text(self, name: str, template: str, node: str | None) -> str:
        text = self._templates[template].replace("$NAME", name)
        text = text.replace("$NAMESPACE", self.ns)
        return text if node is None else text.replace("$NODE", node)

    def pod_body(self, name: str, template: str, node: str | None) -> bytes:
        self._made[f"{self.ns}/{name}"] = (template, node)
        return self._pod_text(name, template, node).encode()

    def manifest_of(self, key: str):
        """The manifest this run created under `key`, or None."""
        made = self._made.get(key)
        if made is None:
            return None
        return json.loads(self._pod_text(key.split("/", 1)[1], *made))


class Run:
    """The children and the client of one process lifetime. `setup()`,
    then one or more `window()`s (the sweep makes several), then
    `finish()`; `close()` in a finally."""

    def __init__(self, catalog: Catalog, cell_name: str, seed: int,
                 out_dir: str, rehearse_cpu: bool = False,
                 nodes: int | None = None, fault: str | None = None,
                 control: str | None = None,
                 warmup_deadline_s: float | None = None):
        self.catalog = catalog
        self.warmup_deadline_s = warmup_deadline_s
        self.cell = catalog.cell(cell_name)
        self.config = catalog.config(self.cell["config"])
        self.traffic = catalog.traffic(self.cell)
        # found now: a rule that is named and not there ends the run here
        self.rules = catalog.reference_rules(self.config)
        self.seed = seed
        self.out = out_dir
        self.rehearse_cpu = rehearse_cpu
        self.fault, self.control = fault, control
        self.cluster = Cluster(self.config, seed, nodes)
        self.platform = "cpu" if rehearse_cpu else "tpu"
        self.children: list = []
        self.api = self.sched = self.watch = self.rest = None
        self.data_dir = None
        self.setup_s = None
        self.detail: dict = {"cell": cell_name, "seed": seed,
                             "rehearsal": rehearse_cpu,
                             "nodes": self.cluster.n_nodes}
        self._n_windows = 0
        os.makedirs(out_dir, exist_ok=True)

    # -- set-up --------------------------------------------------------------

    def setup(self) -> None:
        c = self.cluster
        # a fresh WAL directory under TMPDIR, removed in close()
        self.data_dir = tempfile.mkdtemp(prefix="bench_wal_")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("BENCH_RUN", None)
        api_argv = (["--data-dir", self.data_dir]
                    if self.control != "no-wal" else [])
        self.api, self.api_port = start_serving(
            "apiserver",
            lambda port: ["-m", "kubernetes_tpu.cmd.apiserver",
                          "--port", str(port), *api_argv],
            env, os.path.join(self.out, "apiserver.log"), "/healthz", 60.0)
        self.children.append(self.api)
        self.rest = Rest(self.api_port)
        # every connection the run will use, opened now and one by one
        self.rest.warm(max(8, self.senders), "/healthz")

        # the watch first, from rv 0 while no pod exists: every pod event
        # of the run then reaches it, the residents' too, in commit order
        self.watch = BindWatch(self.api_port)
        if not self.watch.opened.wait(30.0):
            raise RunFailure(f"the pod watch did not open: {self.watch.errors}")
        t = time.monotonic()
        self._create_all("/api/v1/nodes",
                         [_render(m).encode() for m in c.node_manifests])
        pods_path = f"/api/v1/namespaces/{c.ns}/pods"
        self._create_all(pods_path, [c.pod_body(name, tpl, node)
                                     for name, tpl, node in c.residents])
        self._wait_bound([f"{c.ns}/{n}" for n, _, _ in c.residents], 60.0,
                         "residents")
        say(f"loaded {c.n_nodes} nodes + {len(c.residents)} bound residents "
            f"over REST in {time.monotonic() - t:.1f}s (no scheduler yet)")

        # the one chip-owning process
        sched_env = dict(os.environ)
        sched_env.pop("BENCH_RUN", None)
        if self.rehearse_cpu:
            sched_env["JAX_PLATFORMS"] = "cpu"
        else:
            sched_env.pop("JAX_PLATFORMS", None)
        extra = ["--fault", self.fault] if self.fault else []
        sched_args = list(self.config.get("scheduler", {}).get("args", []))
        platform = "cpu" if self.control == "platform-cpu" else self.platform
        t = time.monotonic()
        self.sched, self.health_port = start_serving(
            "scheduler",
            lambda port: [SCHED_CHILD, "--server",
                          f"http://127.0.0.1:{self.api_port}",
                          "--platform", platform,
                          "--healthz-port", str(port), *sched_args, *extra],
            sched_env, os.path.join(self.out, "scheduler.log"), "/healthz",
            SCHEDULER_START_DEADLINE_S, stdin_pipe=True)
        self.children.append(self.sched)
        say(f"scheduler up in {time.monotonic() - t:.1f}s")
        devices = int(self.scrapes()["sched"].labels_of(
            "scheduler_device_info").get("devices") or 0)
        if devices < int(self.cell["chips"]):
            raise RunFailure(f"the cell asks for {self.cell['chips']} chip(s); "
                             f"the scheduler found {devices}")

        # warm-up with the cell's own pod template: one burst larger than
        # the small batch bucket, then a little of the cell's own arrivals
        w = self.traffic["warmup"]
        burst = warmup_burst(self.traffic, c.scale)
        t = time.monotonic()
        names = [f"u-{i}" for i in range(burst)]
        tpl = self.traffic["pod_template"]
        self._create_all(pods_path, [c.pod_body(n, tpl, None) for n in names])
        left = self._wait_bound([f"{c.ns}/{n}" for n in names],
                                self.warmup_deadline_s
                                or WARMUP_BURST_DEADLINE_S, "warm-up burst")
        say(f"warm-up burst: {burst - left}/{burst} bound in "
            f"{time.monotonic() - t:.1f}s")
        if w.get("trickle_s"):
            win, _ = self._offer(self.rate(), w["trickle_s"], self.seed,
                                 "t")
            late = self._wait_bound(win.keys, self.warmup_deadline_s
                                    or WARMUP_TRICKLE_DEADLINE_S,
                                    "warm-up trickle")
            say(f"warm-up trickle: {len(win.keys) - late}/{len(win.keys)} "
                f"bound")
            left += late
        self.detail["warmup_unbound"] = left

    def rate(self) -> float:
        return offered_rate(self.traffic, self.cluster.scale)

    @property
    def senders(self) -> int:
        return int(self.traffic.get("senders", 16))

    def _create_all(self, path: str, bodies: list, threads: int = 8) -> None:
        with ThreadPoolExecutor(threads) as pool:
            acked = list(pool.map(lambda b: self.rest.create(path, b), bodies))
        if not all(acked):
            raise RunFailure(f"{acked.count(False)} of {len(acked)} set-up "
                             f"creates on {path} were refused")

    def _children_alive(self) -> bool:
        return all(c.alive() for c in self.children)

    def _wait_bound(self, keys: list, deadline_s: float, what: str) -> int:
        """Wait until the watch saw every key bound; the number still
        unbound at the deadline (never raises)."""
        deadline = time.monotonic() + deadline_s
        bound = self.watch.bound
        waiting = list(keys)
        while True:
            waiting = [k for k in waiting if k not in bound]
            if not waiting:
                return 0
            if (time.monotonic() > deadline or self.watch.stopped
                    or not self._children_alive()):
                say(f"{what}: {len(waiting)} of {len(keys)} unbound; giving "
                    f"up (watch stopped: {self.watch.stopped} "
                    f"{self.watch.errors[-3:]}, children alive: "
                    f"{self._children_alive()})")
                return len(waiting)
            time.sleep(0.02)

    def _wait_for_file(self, path: str, deadline_s: float) -> bool:
        """Wait for a file the scheduler launcher writes (it renames it
        into place); False if it is not there by the deadline."""
        deadline = time.monotonic() + deadline_s
        while (not os.path.exists(path) and time.monotonic() < deadline
               and self.sched.alive()):
            time.sleep(0.05)
        return os.path.exists(path)

    def _wait_for_trace(self, trace_dir: str) -> None:
        """Wait until the launcher says the profiler has written the
        trace (`stopped`). One that has not by the deadline leaves the
        run without a result, and the reason names the trace: what the
        launcher wrote when the stop was decided, and how long ago."""
        t = time.monotonic()
        if self._wait_for_file(os.path.join(trace_dir, "stopped"),
                               TRACE_STOP_DEADLINE_S):
            return
        waited = time.monotonic() - t
        try:
            with open(os.path.join(trace_dir, "stopping")) as f:
                rec = json.load(f)
            age = time.time() - os.path.getmtime(f.name)
            why = (f"its stop was decided {age:.0f} s ago by "
                   f"{rec['stopped_by']!r} after {rec['launches']} launches "
                   f"in {rec['window_s']:.1f} s and the profiler is still "
                   f"writing it")
        except (OSError, ValueError, KeyError):
            why = "it was never stopped (the launcher wrote no `stopping`)"
        raise RunFailure(
            f"the device trace in {trace_dir} did not stop: waited "
            f"{waited:.0f} s past the drain for `stopped`; {why} "
            f"(scheduler alive: {self.sched.alive()})")

    # -- a window ------------------------------------------------------------

    def _offer(self, rate: float, seconds: float, seed: int, prefix: str,
               at_end=None):
        """Offer `seconds` of arrivals at `rate`, open loop, and return
        (Window, the instant the offering ended). `at_end()` is called
        from a thread of its own at the instant the window closes, however
        far behind its schedule the offering runs by then (past the knee
        the last creates leave many seconds late)."""
        c = self.cluster
        due = loadgen.schedule(rate, seconds, seed,
                               self.traffic.get("arrivals", "poisson"))
        tpl = self.traffic["pod_template"]
        names = [f"{prefix}-{i}" for i in range(len(due))]
        bodies = [c.pod_body(n, tpl, None) for n in names]
        path = f"/api/v1/namespaces/{c.ns}/pods"
        win = loadgen.Window(time.monotonic() + 0.05, seconds, due,
                             [f"{c.ns}/{n}" for n in names])
        closing = None
        if at_end is not None:
            closing = threading.Timer(
                win.t0 + seconds - time.monotonic(), at_end)
            closing.start()
        loadgen.send_open_loop(
            win, lambda i: self.rest.create(path, bodies[i]), self.senders)
        t_offered = time.monotonic()
        if closing is not None:
            closing.join()
        return win, t_offered

    def scrapes(self) -> dict:
        out = {}
        for name, url in (
                ("sched", f"http://127.0.0.1:{self.health_port}/metrics"),
                ("api", f"http://127.0.0.1:{self.api_port}/metrics")):
            try:
                out[name] = Scrape(http_get(url, timeout=10.0))
            except OSError as e:
                say(f"/metrics of {name} did not answer: {e}")
                out[name] = Scrape("")
        return out

    def long_passes(self, t0: float, floor_ms: float = 50.0) -> list:
        """[who, task, seconds from `t0`, ms] of every background pass of
        either child since `t0` that took `floor_ms` or more (anti-entropy,
        the queue's flushes, the locked part of a WAL compaction), from
        their `/debug/traces?stalls=1`: for detail.json, never a metric.
        All three processes read one monotonic clock."""
        out = []
        for who, port in (("sched", self.health_port), ("api", self.api_port)):
            try:
                ev = json.loads(http_get(
                    f"http://127.0.0.1:{port}/debug/traces?stalls=1",
                    timeout=10.0))
            except (OSError, ValueError):
                continue
            out += [[who, e["task"], round(e["t0"] - t0, 2), e["ms"]]
                    for e in ev.get("passes", [])
                    if e["ms"] >= floor_ms and e["t0"] >= t0]
        return out

    def window(self, seconds: float, rate: float | None = None,
               trace: bool = False, drain_deadline_s: float | None = None):
        """One measured window and its drain. Returns a dict with the
        client's stats, the two scrapes of each child and the trace
        directory (if traced)."""
        self._n_windows += 1
        prefix = f"w{self._n_windows}"
        rate = rate or self.rate()
        if drain_deadline_s is None:
            drain_deadline_s = float(self.traffic.get("drain_deadline_s", 60))
        trace_dir = None
        timers = []
        if trace:
            tr = self.traffic.get("trace", {})
            span = min(float(tr.get("seconds", 4.0)), seconds)
            start = min(float(tr.get("start_s", 3.0)), seconds - span)
            trace_dir = os.path.join(self.out, f"trace_{prefix}")
            os.makedirs(trace_dir, exist_ok=True)
            timers = [
                threading.Timer(0.05 + start, self.sched.command,
                                [f"trace-start {trace_dir} "
                                 f"{int(tr.get('max_launches', 0))}"]),
                threading.Timer(0.05 + start + span, self.sched.command,
                                ["trace-stop"]),
            ]
        start_scrapes = self.scrapes()
        if self.setup_s is None:
            # set-up ends where the first window's arrivals begin
            self.setup_s = time.monotonic() + 0.05 - T_PROCESS_START
        for t in timers:
            t.start()
        # the closing scrapes are taken AT the window's end, not when the
        # offering has ended: past the knee that is many seconds later
        closed: dict = {}

        def close_window():
            closed["scrapes"] = self.scrapes()
            closed["at"] = time.monotonic()

        win, t_offered = self._offer(rate, seconds, self.seed, prefix,
                                     at_end=close_window)
        end_scrapes = closed["scrapes"]
        acked = [k for k, a in zip(win.keys, win.acked) if a]
        left = self._wait_bound(
            acked, max(0.0, win.t0 + seconds + drain_deadline_s
                       - time.monotonic()), f"drain of {prefix}")
        t_gave_up = time.monotonic()
        for t in timers:
            t.join()
        if trace_dir is not None:
            self._wait_for_trace(trace_dir)
        lat_ms = loadgen.latencies_ms(win, self.watch.t_bound, t_gave_up)
        stats = loadgen.window_stats(win, self.watch.t_bound, t_gave_up,
                                     lat_ms)
        stats.update(rate_offered=rate, seconds=seconds,
                     offering_overran_s=max(0.0, t_offered - win.t0 - seconds),
                     drain_s=t_gave_up - (win.t0 + seconds),
                     end_scrape_s=closed["at"] - (win.t0 + seconds),
                     unbound_after_drain=left,
                     refused_why=self.rest.refused[-5:],
                     long_passes=self.long_passes(win.t0))
        compiles = (end_scrapes["sched"].total("jax_backend_compiles_total")
                    - start_scrapes["sched"].total("jax_backend_compiles_total"))
        stats["compiles_in_window"] = int(compiles)
        say(f"window {prefix}: {json.dumps(stats)}")
        return {"stats": stats, "start": start_scrapes, "end": end_scrapes,
                "trace_dir": trace_dir, "window": win,
                "latencies_ms": lat_ms}

    # -- the end -------------------------------------------------------------

    def finish(self) -> dict:
        """Read the chip-owning process's devices and peak memory, take
        the last scrape and the log, and stop both children."""
        device = {"platform": None, "kind": None, "count": 0,
                  "memory_peak_bytes": 0}
        path = os.path.join(self.out, "memstats.json")
        if os.path.exists(path):
            os.unlink(path)
        self.sched.command(f"memstats {path}")
        if self._wait_for_file(path, 15.0):
            with open(path) as f:
                ms = json.load(f)
            device = {"platform": ms["platform"], "kind": ms["kind"],
                      "count": ms["count"],
                      "memory_peak_bytes": max(ms["peak_bytes"] or [0])}
        final = self.scrapes()["sched"]
        self.watch.stop()
        self.rest.close()
        self.sched.stop(how=None, grace_s=10.0)  # stdin EOF: it interrupts itself
        self.api.stop(how=signal.SIGKILL)     # acknowledged means on disk NOW
        return {"device": device, "final": final,
                "sched_log": self.sched.log_text()}

    def close(self) -> None:
        """Always: no child outlives the run, nothing accumulates."""
        if self.watch is not None:
            self.watch.stop()
        for c in reversed(self.children):
            c.stop(how=signal.SIGKILL, grace_s=5.0)
        if self.data_dir is not None:
            shutil.rmtree(self.data_dir, ignore_errors=True)


def run_cell(root: str, cell_name: str, seed: int, seconds: float,
             trace: bool, out_dir: str, rehearse_cpu: bool = False,
             nodes: int | None = None, fault: str | None = None,
             control: str | None = None,
             drain_deadline_s: float | None = None,
             keep_trace: bool = False,
             warmup_deadline_s: float | None = None) -> dict:
    """One run: the contract's result object (and `detail.json` in
    `out_dir`). Raises RunFailure when there is no result."""
    catalog = Catalog(root)
    run = Run(catalog, cell_name, seed, out_dir, rehearse_cpu, nodes,
              fault, control, warmup_deadline_s)
    try:
        run.setup()
        w = run.window(seconds, trace=trace,
                       drain_deadline_s=drain_deadline_s)
        end = run.finish()
        # both children are gone: the reference and the read-back
        t = time.monotonic()
        c = run.cluster
        stats = w["stats"]
        infeasible = check.check_placements(
            c.node_manifests, run.watch.order, c.manifest_of,
            run.watch.rebinds, run.rules)
        wal = check.check_wal(run.data_dir, run.watch.bound)
        off_device, off_why = check.check_device_path(
            end["final"], end["sched_log"], run.platform, c.n_nodes,
            stats["attempted"])
        if end["device"]["platform"] is None:
            off_why.append("the scheduler process did not report its devices")
            off_device += stats["attempted"]
        say(f"reference replayed {len(run.watch.order)} binds, WAL read "
            f"back, in {time.monotonic() - t:.1f}s")
    finally:
        run.close()

    compared = {
        "unbound": [stats["failed"] + run.detail.get("warmup_unbound", 0), 0],
        "infeasible": [len(infeasible), 0],
        "wal_missing": [len(wal), 0],
        "off_device": [off_device, 0],
    }
    correct = all(v <= limit for v, limit in compared.values())
    for why in (infeasible + wal + off_why)[:10]:
        say(f"NOT CORRECT: {why}")

    ctx = {
        "client": dict(stats, setup_s=run.setup_s),
        "sched": (w["start"]["sched"], w["end"]["sched"]),
        "api": (w["start"]["api"], w["end"]["api"]),
        "trace": None,
        "config": run.config,
        "device": end["device"],
    }
    device = dict(end["device"])
    result = {"correct": correct, "attempted": stats["attempted"],
              "failed": stats["failed"]}
    if trace:
        from . import trace_reduce

        t = time.monotonic()
        reduced = trace_reduce.reduce_dir(w["trace_dir"])
        say(f"trace reduced in {time.monotonic() - t:.1f}s: "
            f"{json.dumps({k: v for k, v in reduced.items() if k != 'breakdown'})[:2000]}")
        ctx["trace"] = reduced
        device["busy_s"] = reduced.get("busy_s", 0.0)
        device["window_s"] = reduced.get("window_s", 0.0)
        result["metrics"] = catalog.read_layer_metrics(cell_name, ctx)
        result["device"] = device
        result["breakdown"] = reduced.get("breakdown",
                                          {"device_ops": [], "idle_gaps": []})
        if not keep_trace:
            shutil.rmtree(w["trace_dir"], ignore_errors=True)
    else:
        result["metrics"] = {
            m["name"]: {"value": ctx["client"][m["name"]], "unit": m["unit"]}
            for m in catalog.metrics("end_to_end", cell_name)
        }
        result["device"] = device
    result["compared"] = compared  # last: each number beside its limit

    run.detail.update(
        window=stats, setup_s=run.setup_s, device=end["device"],
        violations=(infeasible + wal + off_why)[:200],
        scheduler_info=end["final"].labels_of("scheduler_device_info"),
        compile_cache=end["final"].by_label("jax_backend_compiles_total",
                                            "persistent_cache"),
        wave_batches_in_window=int(
            w["end"]["sched"].total("scheduler_wave_batches_total")
            - w["start"]["sched"].total("scheduler_wave_batches_total")),
        largest_batch=int(end["final"].total("scheduler_wave_batch_pods_max")),
        audit_passes=int(end["final"].total("snapshot_audit_passes_total")),
        # the WAL's record count at the window's two ends: the log is
        # compacted (the store locked for the copy) every 50,000 records
        wal_records=[int(w[k]["api"].total("wal_records_appended_total"))
                     for k in ("start", "end")],
        # which rule ended the trace, after how many launches, how long
        # the profiler took to write it (the launcher's `stopped`)
        trace_stopped=(ctx["trace"] or {}).get("stopped"),
        result=result,
        # every pod of the window, in the order it was due: when it was
        # due (s from the window's start) and its create-to-bound (ms)
        due_s=[round(d, 4) for d in w["window"].due],
        create_to_bound_ms=[round(x, 1) for x in w["latencies_ms"]],
    )
    with open(os.path.join(out_dir, "detail.json"), "w") as f:
        json.dump(run.detail, f, indent=1)
    say("detail: " + json.dumps(
        {k: v for k, v in run.detail.items()
         if k not in ("result", "violations", "window", "due_s",
                      "create_to_bound_ms")}))
    return result
