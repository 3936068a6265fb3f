#!/usr/bin/env python
"""Wave-kernel cost model: time the jitted kernel on realistic encoded
inputs (5k-node PodAffinity workload) across wave counts, batch sizes and
pair-axis sizes.

    python scripts/profile_kernel.py [--workload SchedulingBasic]
        [--nodes 5000] [--pods 256] [--m 32] [--waves 0,2] [--pairs 32,4,1]
        [--zones 3] [--unlevel 0,1] [--occupied 1000,2500,4700]
        [--platform tpu] [--trace-dir DIR]

The kernel is the variant the scheduler serves for that batch on this
backend (`Scheduler._batch_waves` and `_wave_variant`, has_pinned=False: on
a TPU the Pallas fit mask and the per-wave score refresh, on the CPU
neither; for a batch with a hard spread template the stratified candidate
columns), at each wave count asked for. --unlevel K starts from a snapshot
in which K pods of the measured kind already sit on the first node (zone
0): a hard zone spread is then over its skew there. --occupied K starts
from one in which K nodes (a seeded choice) hold one pod of the measured
kind each: how full a one-pod-a-node deployment (required hostname
anti-affinity, `--workload SchedulingPodAntiAffinity`) is when the launch
begins, so that 5,000 - K nodes are feasible. The n_waves
sweep isolates Stage A (n_waves=0 compiles the kernel with an empty
fori_loop) from the per-wave cost; the P sweep shows how much of the cycle
is batch-size-invariant (the [TPL, N] planes) vs per-pod; the --pairs sweep
pads the built pair table's J axis with dead slots (col -1, referenced by
no template) to each size, which is what `build_pair_table` did to 32
before PR 33. A size below the table's real pairs truncates it: a timing
of that shape, not a schedule. With --trace-dir each timed loop is also
profiled and the device's own time per launch and its ten longest
operations are printed (the host clock adds the dispatch).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# default to CPU: there is no chip in the sandbox. Pass --platform tpu
# explicitly to profile on hardware. Parsed pre-import (both
# --platform X and --platform=X forms) because JAX_PLATFORMS must be set
# before jax loads.
_plat = "cpu"
for _i, _a in enumerate(sys.argv):
    if _a == "--platform" and _i + 1 < len(sys.argv):
        _plat = sys.argv[_i + 1]
    elif _a.startswith("--platform="):
        _plat = _a.split("=", 1)[1]
os.environ["JAX_PLATFORMS"] = _plat

import jax  # noqa: E402
import numpy as np  # noqa: E402


def build_inputs(workload: str, n_nodes: int, n_pods: int, m_cand: int,
                 zones: int = 0, unlevel: int = 0, occupied: int = 0):
    import dataclasses

    from kubernetes_tpu.client.apiserver import APIServer
    from kubernetes_tpu.perf.workloads import WORKLOADS, build_workload
    from kubernetes_tpu.scheduler import KubeSchedulerConfiguration, Scheduler

    cfg = WORKLOADS.get(f"{workload}/{n_nodes}")
    if cfg is None:  # a rehearsal size: the family's 5000-node row, cut
        cfg = dataclasses.replace(
            WORKLOADS[f"{workload}/5000"], num_nodes=n_nodes
        )
    if zones:
        cfg = dataclasses.replace(cfg, zones=zones)
    server = APIServer()
    sched = Scheduler(server, KubeSchedulerConfiguration())
    sched.cache.encoder.presize_for_cluster(cfg.num_nodes)
    nodes, _init, factory = build_workload(cfg)
    for n in nodes:
        server.create("nodes", n)
    sched.start()
    try:
        deadline = time.monotonic() + 60
        while sched.cache.node_count < cfg.num_nodes:
            if time.monotonic() > deadline:
                raise TimeoutError("informer sync")
            time.sleep(0.05)
        for i in range(unlevel):
            p = factory(n_pods + i)
            p.spec.node_name = nodes[0].metadata.name
            sched.cache.add_pod(p)
        rows = np.random.default_rng(0).permutation(cfg.num_nodes)[:occupied]
        for i, row in enumerate(rows):
            p = factory(n_pods + unlevel + i)
            p.spec.node_name = nodes[int(row)].metadata.name
            sched.cache.add_pod(p)
        pods = [factory(i) for i in range(n_pods)]
        with sched.cache.lock:
            eb = sched._tpl_cache.encode(pods, pad_to=n_pods)
            ptab = sched._pair_table(eb)
            _n_waves, has_hard, stratify, _anti = sched._batch_waves(eb)
            snap = sched.cache.encoder.flush()
            enc_cfg = sched.cache.encoder.cfg
        weights = np.asarray(sched._weights)

        def variant(n_waves: int) -> tuple:
            return sched._wave_variant(
                enc_cfg, m_cand, n_waves, has_hard,
                has_pinned=False, stratify=stratify,
            )

        return snap, eb, ptab, variant, weights
    finally:
        sched.stop()


def resize_pairs(ptab, j: int):
    """`ptab` with its pair axis cut or padded to `j` slots; a padded slot
    is dead exactly as build_pair_table's own padding is."""
    import jax.numpy as jnp

    def fit(x, axis, fill):
        x = np.asarray(x)
        x = np.take(x, np.arange(min(j, x.shape[axis])), axis=axis)
        width = [(0, 0)] * x.ndim
        width[axis] = (0, j - x.shape[axis])
        return jnp.asarray(np.pad(x, width, constant_values=fill))

    return ptab._replace(
        is_eterm=fit(ptab.is_eterm, 0, False),
        col=fit(ptab.col, 0, -1),
        key=fit(ptab.key, 0, 0),
        elig_tpl=fit(ptab.elig_tpl, 0, -1),
        kind=fit(ptab.kind, 0, -1),
        contrib=fit(ptab.contrib, 1, 0.0),
        etm_match=fit(ptab.etm_match, 1, False),
    )


def device_times(trace_dir: str):
    """(device seconds of all launches, [(operation, seconds)] longest
    first) from the newest trace under `trace_dir`, first device plane."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        mods = sum(e.duration_ns for e in lines["XLA Modules"].events
                   if e.name.startswith("jit_wave_kernel"))
        ops: dict = {}
        for e in lines["XLA Ops"].events:
            ops[e.name[:140]] = ops.get(e.name[:140], 0.0) + e.duration_ns / 1e9
        return mods / 1e9, sorted(ops.items(), key=lambda kv: -kv[1])
    return None, []


def time_kernel(snap, eb, ptab, variant, weights, *, reps=20, trace_dir=None):
    from kubernetes_tpu.ops.wavelattice import make_wave_kernel

    kern = jax.jit(make_wave_kernel(*variant))  # NO donation: snap is reused
    rng = jax.random.PRNGKey(0)
    t0 = time.monotonic()
    out = kern(snap, eb.batch, ptab, weights, rng)
    jax.block_until_ready(out)
    compile_s = time.monotonic() - t0

    def loop():
        times = []
        for _ in range(reps):
            t0 = time.monotonic()
            jax.block_until_ready(kern(snap, eb.batch, ptab, weights, rng))
            times.append(time.monotonic() - t0)
        return sorted(times)

    times = loop()
    device = None
    if trace_dir:
        with jax.profiler.trace(trace_dir):
            loop()
        total, ops = device_times(trace_dir)
        if total is not None:
            device = (total / reps, [(n, s / reps) for n, s in ops[:10]])
    placed = int(np.asarray(out[1].placed).sum())
    return times[0], times[len(times) // 2], compile_s, placed, device


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="SchedulingPodAffinity",
                    help="a perf/workloads.py family, e.g. SchedulingBasic "
                    "(no pair at all)")
    ap.add_argument("--nodes", type=int, default=5000)
    ap.add_argument("--pods", default="256")
    ap.add_argument("--waves", default="0,2")
    ap.add_argument("--pairs", default="",
                    help="J sizes to cut or pad the built table to, e.g. "
                    "32,4,1 (default: the table as built)")
    ap.add_argument("--m", type=int, default=32)
    ap.add_argument("--zones", type=int, default=0,
                    help="zones of the cluster (default: the workload's)")
    ap.add_argument("--unlevel", default="0",
                    help="residents of the measured kind on the first "
                    "node, one snapshot each, e.g. 0,1")
    ap.add_argument("--occupied", default="0",
                    help="nodes (a seeded choice) that already hold one pod "
                    "of the measured kind each, one snapshot each, e.g. "
                    "1000,2500,4700: the fill of a one-pod-a-node "
                    "deployment (SchedulingPodAntiAffinity) at the launch")
    ap.add_argument("--platform", default="cpu")
    ap.add_argument("--trace-dir", default="")
    args = ap.parse_args()

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}")
    for P, unlevel, occupied in [
            (int(x), int(u), int(o)) for x in args.pods.split(",")
            for u in args.unlevel.split(",") for o in args.occupied.split(",")]:
        snap, eb, built, variant, weights = build_inputs(
            args.workload, args.nodes, P, args.m, args.zones, unlevel,
            occupied,
        )
        TPL = int(eb.batch.tpl.valid.shape[0])
        real = int((np.asarray(built.col) >= 0).sum())
        sizes = [int(x) for x in args.pairs.split(",") if x] or [
            int(built.col.shape[0])
        ]
        print(f"{args.workload} P={P} nodes={args.nodes} unlevel={unlevel} "
              f"occupied={occupied} TPL={TPL} real_pairs={real} "
              f"J_built={int(built.col.shape[0])} variant={variant(2)}")
        for J in sizes:
            ptab = resize_pairs(built, J)
            for w in [int(x) for x in args.waves.split(",")]:
                best, med, cs, placed, device = time_kernel(
                    snap, eb, ptab, variant(w), weights,
                    trace_dir=(os.path.join(args.trace_dir,
                                            f"P{P}-u{unlevel}-o{occupied}-J{J}-w{w}")
                               if args.trace_dir else None),
                )
                cut = " (TRUNCATED: a timing, not a schedule)" if J < real else ""
                print(
                    f"  J={J} waves={w} m={args.m}: host clock best "
                    f"{best*1e3:7.3f} ms, median {med*1e3:7.3f} ms "
                    f"(compile {cs:.1f}s, {placed} placed){cut}",
                    flush=True,
                )
                if device:
                    print(f"    device: {device[0]*1e3:7.3f} ms a launch")
                    for name, sec in device[1]:
                        print(f"      {sec*1e3:7.3f} ms  {name}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
