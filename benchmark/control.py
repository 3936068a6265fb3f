#!/usr/bin/env python3
"""The controls of `correct`, run by a builder on the chip at the cell's
own size; the benchmark's own runs never run them. Each breaks one
guarantee that the configuration states, or plants one fault under the
timed path, and has to come out NOT correct by the number named:

  no-wal       the apiserver without --data-dir: nothing durable  -> wal_missing
  alter-bind   every bind altered where it is produced            -> infeasible
  drop-half    every second bind never sent, reported as sent     -> unbound
  host-lane    the program's own host path switched on for small
               batches                                            -> off_device

  python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 10

One JSON line per run. tests/benchmark/test_benchmark_rehearsal.py keeps
the same controls as tests at a size a test run can hold.
"""

from __future__ import annotations

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CONTROLS = {
    "no-wal": ({"control": "no-wal"}, "wal_missing"),
    "alter-bind": ({"fault": "alter-bind"}, "infeasible"),
    "drop-half": ({"fault": "drop-half"}, "unbound"),
    "host-lane": ({"fault": "host-lane"}, "off_device"),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--which", default=",".join(CONTROLS))
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--nodes", type=int, default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    from harness import supervisor
    from harness.children import RunFailure

    rc = 0
    for which in args.which.split(","):
        how, catches = CONTROLS[which]
        for seed in (int(s) for s in args.seeds.split(",")):
            out = os.path.join(ROOT, "chiprun_out", "control", args.workload,
                               which)
            try:
                r = supervisor.run_cell(
                    ROOT, args.workload, seed, args.seconds, False, out,
                    rehearse_cpu=args.rehearse_cpu, nodes=args.nodes,
                    drain_deadline_s=15.0, warmup_deadline_s=30.0, **how)
            except RunFailure as e:
                # a control that gives no number has failed, and sets no
                # upper reading
                print(json.dumps({"control": which, "seed": seed,
                                  "no_result": str(e)[:300]}), flush=True)
                rc = 1
                continue
            print(json.dumps({
                "control": which, "seed": seed, "workload": args.workload,
                "correct": r["correct"], "catches": catches,
                "reads": r["compared"][catches][0],
                "attempted": r["attempted"], "compared": r["compared"],
                "device": r["device"]}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
