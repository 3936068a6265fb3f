#!/usr/bin/env python3
"""The sweep that the rates of a configuration's traffic files come from:
one process lifetime, one set-up, the offered rates one after another
with a drain between. Run by a builder on the chip, once; not part of a
check.

  python3 benchmark/sweep.py --workload <cell> --rates 50,100,200,400,800,1600 --seconds 8

With `--trace 1` every step is traced with the `trace` block of the cell's
traffic file (span and launch cap) and its line also carries what ended
the trace, the launches the trace holds, how long the profiler took to
write it, the file's size, and how long after the window's end the
harness had its window: the numbers the launch cap rests on.

Saturation is the highest `bound_pods_per_s` any step showed; the knee is
the highest offered rate at whose end fewer pods were pending than one
second of arrivals. One JSON line per step on standard output, then one
with both.
"""

from __future__ import annotations

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _trace_facts(trace_dir: str) -> dict:
    """What a step's trace holds, and what it cost; the trace is removed."""
    from harness import trace_reduce

    path = trace_reduce.find_xplane(trace_dir)
    facts = {"xplane_bytes": os.path.getsize(path) if path else None}
    t = time.monotonic()
    reduced = trace_reduce.reduce_dir(trace_dir)
    facts["reduce_s"] = time.monotonic() - t
    facts.update({k: reduced.get(k) for k in (
        "stopped", "devices", "busy_s", "window_s", "idle_share")})
    facts["launches_in_trace"] = {
        name: p["launches"] for name, p in reduced.get("programs", {}).items()}
    facts["idle_gaps"] = reduced.get("breakdown", {}).get("idle_gaps", [])[:5]
    shutil.rmtree(trace_dir, ignore_errors=True)
    return facts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="50,100,200,400,800,1600")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--senders", type=int, default=None,
                    help="sender threads, in place of the traffic file's: "
                    "a sweep is made once per count before the file says one")
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--nodes", type=int, default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    from harness.catalog import Catalog
    from harness.children import RunFailure
    from harness.supervisor import Run

    out = args.out or os.path.join(ROOT, "chiprun_out", "sweep", args.workload)
    run = Run(Catalog(ROOT), args.workload, args.seed, out,
              rehearse_cpu=args.rehearse_cpu, nodes=args.nodes)
    if args.senders:
        run.traffic["senders"] = args.senders
    steps = []
    try:
        run.setup()
        for rate in (float(r) for r in args.rates.split(",")):
            w = run.window(args.seconds, rate=rate, drain_deadline_s=120.0,
                           trace=bool(args.trace))
            t_back = time.monotonic()
            s = w["stats"]
            step = {k: s[k] for k in (
                "rate_offered", "attempted", "failed", "refused_creates",
                "bound_pods_per_s",
                "pending_at_end", "create_to_bound_p50_ms",
                "create_to_bound_p99_ms", "loadgen_lag_p99_ms", "drain_s",
                "compiles_in_window")}
            step["wave_batches"] = int(
                w["end"]["sched"].total("scheduler_wave_batches_total")
                - w["start"]["sched"].total("scheduler_wave_batches_total"))
            if args.trace:
                step["window_returned_after_s"] = (
                    t_back - w["window"].t0 - args.seconds)
                step["trace"] = _trace_facts(w["trace_dir"])
            steps.append(step)
            print(json.dumps(step), flush=True)
        end = run.finish()
    except RunFailure as e:
        print(f"sweep: no result: {e}", file=sys.stderr)
        return 1
    finally:
        run.close()
    knee = max((s["rate_offered"] for s in steps
                if s["pending_at_end"] < s["rate_offered"]), default=None)
    print(json.dumps({
        "workload": args.workload, "device": end["device"],
        "senders": run.senders,
        "saturation_pods_per_s": max(s["bound_pods_per_s"] for s in steps),
        "knee_pods_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
