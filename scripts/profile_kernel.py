#!/usr/bin/env python
"""Wave-kernel cost model: time the jitted kernel on realistic encoded
inputs (5k-node PodAffinity workload) across wave counts and batch sizes.

    python scripts/profile_kernel.py [--nodes 5000] [--pods 1024,4096]

The n_waves sweep isolates Stage A (n_waves=0 compiles the kernel with an
empty fori_loop) from the per-wave cost; the P sweep shows how much of the
cycle is batch-size-invariant (the [TPL, N] planes) vs per-pod.
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


def build_inputs(n_nodes: int, n_pods: int):
    from kubernetes_tpu.client.apiserver import APIServer
    from kubernetes_tpu.perf.workloads import WORKLOADS, build_workload
    from kubernetes_tpu.scheduler import KubeSchedulerConfiguration, Scheduler

    cfg = WORKLOADS[f"SchedulingPodAffinity/{n_nodes}"]
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
        pods = [factory(i) for i in range(n_pods)]
        with sched.cache.lock:
            eb = sched._tpl_cache.encode(pods, pad_to=n_pods)
            ptab, _waves = sched._pair_table(eb)
            snap = sched.cache.encoder.flush()
            enc_cfg = sched.cache.encoder.cfg
        weights = np.asarray(sched._weights)
        return snap, eb, ptab, enc_cfg, weights
    finally:
        sched.stop()


def time_kernel(snap, eb, ptab, enc_cfg, weights, *, n_waves, score_refresh,
                m_cand=128, reps=3):
    from kubernetes_tpu.ops.wavelattice import make_wave_kernel

    kern = jax.jit(
        make_wave_kernel(
            enc_cfg.v_cap, m_cand, n_waves, 1.0, False, score_refresh
        )
    )  # NO donation: we reuse snap across reps
    rng = jax.random.PRNGKey(0)
    # compile
    t0 = time.monotonic()
    out = kern(snap, eb.batch, ptab, weights, rng)
    jax.block_until_ready(out)
    compile_s = time.monotonic() - t0
    best = float("inf")
    for _ in range(reps):
        t0 = time.monotonic()
        out = kern(snap, eb.batch, ptab, weights, rng)
        jax.block_until_ready(out)
        best = min(best, time.monotonic() - t0)
    return best, compile_s


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=5000)
    ap.add_argument("--pods", default="1024,4096")
    ap.add_argument("--waves", default="0,1,2,4,8")
    ap.add_argument("--m", type=int, default=128)
    ap.add_argument("--platform", default="cpu")
    args = ap.parse_args()

    for P in [int(x) for x in args.pods.split(",")]:
        snap, eb, ptab, enc_cfg, weights = build_inputs(args.nodes, P)
        TPL = int(eb.batch.tpl.valid.shape[0])
        J = int(ptab.col.shape[0])
        print(f"P={P} nodes={args.nodes} TPL={TPL} J={J} v_cap={enc_cfg.v_cap}")
        for w in [int(x) for x in args.waves.split(",")]:
            for sr in (True, False):
                dt, cs = time_kernel(
                    snap, eb, ptab, enc_cfg, weights,
                    n_waves=w, score_refresh=sr, m_cand=args.m,
                )
                print(
                    f"  waves={w} refresh={int(sr)} m={args.m}: "
                    f"{dt*1e3:8.1f} ms  (compile {cs:.1f}s, "
                    f"{dt/P*1e6:6.1f} us/pod)",
                    flush=True,
                )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
