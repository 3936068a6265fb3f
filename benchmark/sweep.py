#!/usr/bin/env python3
"""The sweep that the rates of a configuration's traffic files come from:
one process lifetime, one set-up, the offered rates one after another
with a drain between. Run by a builder on the chip, once; not part of a
check.

  python3 benchmark/sweep.py --workload <cell> --rates 50,100,200,400,800,1600 --seconds 8

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
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="50,100,200,400,800,1600")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=0)
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
    steps = []
    try:
        run.setup()
        for rate in (float(r) for r in args.rates.split(",")):
            w = run.window(args.seconds, rate=rate, drain_deadline_s=120.0)
            s = w["stats"]
            step = {k: s[k] for k in (
                "rate_offered", "attempted", "failed", "bound_pods_per_s",
                "pending_at_end", "create_to_bound_p50_ms",
                "create_to_bound_p99_ms", "loadgen_lag_p99_ms", "drain_s",
                "compiles_in_window")}
            step["wave_batches"] = int(
                w["end"]["sched"].total("scheduler_wave_batches_total")
                - w["start"]["sched"].total("scheduler_wave_batches_total"))
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
        "saturation_pods_per_s": max(s["bound_pods_per_s"] for s in steps),
        "knee_pods_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
