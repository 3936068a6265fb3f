#!/usr/bin/env python3
"""One traced window of a benchmark cell, with what the benchmark's own
command does not print: the closure sums of the new series (loop phases
against the window, the store's stages against a request), the pod-stage
means, and — second by second of the window — the worst create->bound
beside every GC pause and background pass that either process recorded
in that second (/debug/traces?stalls=1), or this process itself (the
load generator and watching client: `client:`). Both children share this
machine's CLOCK_MONOTONIC with this process, so their stall events and
the window's seconds are on one clock.

  python scripts/stall_probe.py --workload perf5k-basic.steady --seed 7 \
      [--seconds 51] [--trace 1] [--out DIR] [--rehearse-cpu --nodes 300]

Uses benchmark/harness as a library (Run: set-up, window, finish); it is
a reading aid for PERF.md, not part of the benchmark. Needs the chip
unless --rehearse-cpu.
"""

from __future__ import annotations

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # this process never takes the chip

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

PHASE = "scheduler_loop_phase_seconds_total"
POD_STAGES = ("queue", "encode", "device", "readback", "guard", "assume",
              "bind")
COMMIT_STAGES = ("apply", "wal_append", "fsync", "notify")
REQUEST_STAGES = ("authz", "read", "admit", "store", "observe", "respond")


def _delta(pair, name, labels=None):
    return pair[1].total(name, labels) - pair[0].total(name, labels)


def _mean_ms(pair, name, labels=None):
    n = _delta(pair, name + "_count", labels)
    return _delta(pair, name + "_sum", labels) / n * 1e3 if n else None


def _requests(api) -> dict:
    out = {}
    for name, labels, value in api[1].series:
        if name == "apiserver_request_duration_seconds_count":
            n = value - api[0].total(name, labels)
            if n > 0:
                s = _delta(api, "apiserver_request_duration_seconds_sum",
                           labels)
                out[f"{labels['verb']} {labels['resource']}"] = {
                    "n": n, "mean_ms": s / n * 1e3}
    return out


def closure(w, seconds) -> dict:
    sched = (w["start"]["sched"], w["end"]["sched"])
    api = (w["start"]["api"], w["end"]["api"])
    phases = {}
    for name, labels, value in sched[1].series:
        if name == PHASE:
            k = f"{labels['phase']}/{labels['inflight']}"
            phases[k] = value - sched[0].total(name, labels)
    out = {
        "window_s": seconds,
        "sched_clock_delta_s": _delta(sched, "process_clock_seconds"),
        "api_clock_delta_s": _delta(api, "process_clock_seconds"),
        "loop_phase_total_s": sum(phases.values()),
        "loop_phases_s": dict(sorted(phases.items(), key=lambda kv: -kv[1])),
        "pod_stage_ms": {s: _mean_ms(
            sched, "scheduling_pod_stage_duration_seconds", {"stage": s})
            for s in POD_STAGES},
        "watch_delivery_ms": _mean_ms(
            api, "apiserver_watch_delivery_seconds", {"kind": "pods"}),
        "bind_post_ms": _mean_ms(
            sched, "rest_client_request_duration_seconds",
            {"verb": "POST", "resource": "pods/binding"}),
        "waves": _delta(sched, "scheduler_wave_batches_total"),
        # every request the apiserver answered in the window, by kind
        "requests": _requests(api),
    }
    for op, res in (("create", "pods"), ("bind", "pods/binding")):
        out[op] = {
            "request_ms": _mean_ms(
                api, "apiserver_request_duration_seconds",
                {"verb": "POST", "resource": res}),
            "lock_wait_ms": _mean_ms(
                api, "store_lock_wait_seconds", {"op": op, "kind": "pods"}),
            "commit_ms": {s: _mean_ms(
                api, "store_commit_stage_seconds",
                {"op": op, "kind": "pods", "stage": s})
                for s in COMMIT_STAGES},
            "request_stage_ms": {s: _mean_ms(
                api, "apiserver_request_stage_seconds",
                {"resource": res, "stage": s}) for s in REQUEST_STAGES},
        }
    return out


def per_second(win, lat_ms, stalls: dict, floor_ms: float) -> list:
    """[(second of the window, worst create->bound of the pods due in it,
    the stall events of >= floor_ms that began in it)]."""
    worst: dict = {}
    for due, ms in zip(win.due, lat_ms):
        s = int(due)
        worst[s] = max(worst.get(s, 0.0), ms)
    events: dict = {}
    for who, ev in stalls.items():
        for e in ev.get("gc", []):
            if e["ms"] >= floor_ms:
                events.setdefault(int(e["t0"] - win.t0), []).append(
                    f"{who}:gc{e['generation']}={e['ms']:.0f}ms"
                    f"@{e['t0'] - win.t0:.2f}")
        for e in ev.get("passes", []):
            if e["ms"] >= floor_ms:
                events.setdefault(int(e["t0"] - win.t0), []).append(
                    f"{who}:{e['task']}={e['ms']:.0f}ms"
                    f"@{e['t0'] - win.t0:.2f}")
    return [(s, round(worst.get(s, 0.0), 1), events.get(s, []))
            for s in range(int(win.seconds) + 1)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default=None)
    ap.add_argument("--floor-ms", type=float, default=5.0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the .xplane.pb of a traced window in --out "
                    "(tens of MB in a .steady cell)")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--nodes", type=int, default=None)
    args = ap.parse_args(argv)
    from harness import supervisor
    from harness.catalog import Catalog
    from harness.children import http_get

    # this process is the load generator and the watching client: its own
    # GC pauses delay what it measures, so they are listed too
    sys.path.insert(0, ROOT)
    from kubernetes_tpu.utils import tracing

    tracing.install_stall_probes()

    out = args.out or os.path.join(ROOT, "chiprun_out", "stall_probe",
                                   f"{args.workload}.{args.seed}")
    run = supervisor.Run(Catalog(ROOT), args.workload, args.seed, out,
                         rehearse_cpu=args.rehearse_cpu, nodes=args.nodes)
    try:
        run.setup()
        w = run.window(args.seconds, trace=bool(args.trace))
        stalls = {}
        for who, port in (("sched", run.health_port), ("api", run.api_port)):
            try:
                stalls[who] = json.loads(http_get(
                    f"http://127.0.0.1:{port}/debug/traces?stalls=1",
                    timeout=10.0))
            except (OSError, ValueError) as e:
                stalls[who] = {"error": repr(e)}
        stalls["client"] = tracing.stall_events()
        run.finish()
    finally:
        run.close()
    if w["trace_dir"] and not args.keep_trace:
        shutil.rmtree(w["trace_dir"], ignore_errors=True)
    win = w["window"]
    rows = per_second(win, w["latencies_ms"], stalls, args.floor_ms)
    result = {
        "cell": args.workload, "seed": args.seed,
        "stats": {k: w["stats"][k] for k in (
            "attempted", "failed", "bound_pods_per_s",
            "create_to_bound_mean_ms", "create_to_bound_p50_ms",
            "create_to_bound_p99_ms") if k in w["stats"]},
        "closure": closure(w, args.seconds),
        "per_second": rows,
        "trace_dir": w["trace_dir"] if args.keep_trace else None,
    }
    with open(os.path.join(out, "stall_probe.json"), "w") as f:
        json.dump(dict(result, stalls=stalls), f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
