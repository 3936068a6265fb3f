#!/usr/bin/env python3
"""The benchmark's one command.

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process is the supervisor, the load generator and the watching
client. It pins JAX to the CPU before anything is imported and never
touches the chip; the scheduler child it starts is the one process that
does (harness/supervisor.py). Without a chip that child refuses to start
(`--platform tpu`), and this command exits non-zero and prints no result.
A CPU rehearsal is an explicit argument for the tests
(`--rehearse-cpu --nodes 64`), never a default, and what it prints names
`"platform": "cpu"`.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device`, `breakdown` (traced runs), and
last `compared`: each number that decided `correct` beside its limit. The
same numbers are the last lines on standard error. A run that measured
exits 0 whether or not `correct` is true.
"""

from __future__ import annotations

import os

# the supervisor never takes the chip: pinned before anything imports JAX
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse  # noqa: E402
import json  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BUDGET_S = 1150  # one budget for the whole run, children included


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None,
                    help="directory for the children's logs and detail.json "
                    "(default chiprun_out/bench/<workload> in the checkout)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run the scheduler on JAX's CPU platform: a "
                    "rehearsal of the harness, not a measurement")
    ap.add_argument("--nodes", type=int, default=None,
                    help="rehearsal only: a small cluster, e.g. 64")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the .xplane.pb of a traced run in --out")
    args = ap.parse_args(argv)
    if args.rehearse_cpu != (args.nodes is not None):
        ap.error("--rehearse-cpu and a small --nodes go together")
    if args.nodes is not None and args.nodes > 1000:
        ap.error("--rehearse-cpu is for a small --nodes, e.g. 64")
    if not os.path.isdir(os.path.join(ROOT, "kubernetes_tpu")):
        print("benchmark: not in a checkout of the repo (no kubernetes_tpu/ "
              f"beside {HERE})", file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)
    from harness import supervisor
    from harness.children import RunFailure

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    out = args.out or os.path.join(ROOT, "chiprun_out", "bench", args.workload)

    def timed_out(_sig, _frame):
        raise RunFailure(f"the run exceeded its {BUDGET_S} s budget")

    signal.signal(signal.SIGALRM, timed_out)
    signal.alarm(BUDGET_S)
    try:
        result = supervisor.run_cell(
            ROOT, args.workload, args.seed, seconds, bool(args.trace), out,
            rehearse_cpu=args.rehearse_cpu, nodes=args.nodes,
            keep_trace=args.keep_trace)
    except (RunFailure, KeyError) as e:
        print(f"benchmark: no result: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        signal.alarm(0)
    sys.stdout.flush()
    # the numbers compared, each beside its limit: the last lines on stderr
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
