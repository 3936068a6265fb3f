#!/usr/bin/env python
"""Headline benchmark: scheduling throughput on the scheduler_perf-equivalent
5k-node InterPodAffinity suite (reference harness:
test/integration/scheduler_perf/config/performance-config.yaml;
throughput metric definition: test/integration/scheduler_perf/util.go:210-251).

Prints ONE COMPACT JSON line (value, unit, platform, detail-file pointer):
  {"metric": ..., "value": N, "unit": "pods/s", "vs_baseline": N,
   "platform": ..., "detail_file": ...}
The full payload (stage breakdown, latency suite, every workload's line)
goes to detail_file (default bench_detail.json, override with
BENCH_DETAIL_FILE) — the driver parses the stdout tail, so the final line
must stay small enough to survive any tail window.

vs_baseline is measured throughput divided by the north-star target from
BASELINE.json (50,000 pods/s on the 5k-node InterPodAffinity suite), so
vs_baseline >= 1.0 means the target is met or beaten.

Runs in ONE process, on the chip: it fails, measuring nothing, when JAX's
default backend is not a TPU (a CPU number is never written under this
metric's name), and prints platform, device_kind and the device count. The
JSON line is always emitted; when the run or any of its phases failed it
carries an "error" field and the exit code is non-zero.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

TARGET_PODS_PER_S = 50_000.0  # BASELINE.json north-star, v5e-8


def main() -> int:
    out = {
        "metric": "scheduling_throughput_5k_node_interpodaffinity",
        "value": 0.0,
        "unit": "pods/s",
        "vs_baseline": 0.0,
    }
    failed_phases = []

    def phase_failed(name: str) -> None:
        traceback.print_exc()
        failed_phases.append(name)

    try:
        import jax

        from kubernetes_tpu.utils.compilation_cache import (
            enable_persistent_compilation_cache,
        )

        enable_persistent_compilation_cache()
        devices = jax.devices()
        platform = devices[0].platform
        device = {
            "platform": platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        }
        out["device"] = device
        if platform != "tpu":
            raise RuntimeError(
                f"bench.py measures the TPU; the default JAX backend here "
                f"is {platform!r} ({len(devices)} x {device['kind']})"
            )

        from kubernetes_tpu.perf.harness import (
            run_autoscaler_benchmark,
            run_benchmark,
            run_defrag_benchmark,
            run_hetero_benchmark,
            run_latency_benchmark,
            run_preemption_benchmark,
            run_readpath_benchmark,
            run_durability_benchmark,
            run_relay_serving_benchmark,
            run_tuner_benchmark,
        )
        from kubernetes_tpu.perf.workloads import WORKLOADS

        # pin the host<->device latency floor with a measured number: one
        # result readback per scheduling cycle is irreducible (bind needs
        # the chosen nodes host-side), so pod p99 >= this RTT. Measured
        # after a warmup readback.
        import numpy as np

        d = jax.device_put(np.zeros(16, np.float32))
        np.asarray(d + 1)  # warmup readback
        probe = jax.jit(lambda x: x + 1)  # hoisted: one compile, 5 runs
        rtts = []
        for _ in range(5):
            r = probe(d)
            t0 = time.monotonic()
            jax.device_get(r)
            rtts.append((time.monotonic() - t0) * 1e3)
        readback_rtt_ms = round(sorted(rtts)[len(rtts) // 2], 2)

        cfg = WORKLOADS["SchedulingPodAffinity/5000"]

        # Warm-up on a small instance of the same workload so XLA compile
        # time (one-off, cached) doesn't pollute the measured window;
        # presized to the measured cluster's capacities so the same kernel
        # variant compiles.
        warm = WORKLOADS["SchedulingPodAffinity/500"]
        run_benchmark(warm, quiet=True, presize_nodes=cfg.num_nodes)

        # BENCH_XPLANE_DIR=<dir>: dump a jax-profiler trace of the measured
        # window (per-batch device timeline: dispatch vs compute vs sync)
        res = run_benchmark(
            cfg, quiet=True, xplane_dir=os.environ.get("BENCH_XPLANE_DIR") or None
        )

        # steady-state latency: inject at ~30% of measured burst throughput
        # (capped) so queue depth stays ~0 and the percentiles measure the
        # scheduling machinery, not the backlog
        lat = None
        try:
            rate = max(10.0, min(res.throughput_pods_per_s * 0.3, 2000.0))
            lat = run_latency_benchmark(cfg, rate, n_pods=500)
        except Exception:
            phase_failed("steady_state_latency")

        # gang coscheduling burst (BASELINE.md: 15k pending pods in gangs of
        # 50 on 5k nodes, all-or-nothing via the Coscheduling Permit plugin).
        gang = None
        try:
            from kubernetes_tpu.ops import wavelattice
            from kubernetes_tpu.parallel import sharded
            from kubernetes_tpu.scheduler.config import (
                KubeSchedulerConfiguration,
                ProfileConfig,
            )
            from kubernetes_tpu.scheduler.framework.registry import (
                coscheduling_plugin_set,
            )

            gcfg = KubeSchedulerConfiguration(
                profiles=[ProfileConfig(plugin_set=coscheduling_plugin_set())]
            )
            v0 = (
                wavelattice.make_wave_kernel_jit.cache_info().misses
                + sharded.make_sharded_wave_kernel.cache_info().misses
            )
            gres = run_benchmark(
                WORKLOADS["Gang/5000"],
                sched_config=gcfg,
                quiet=True,
                timeout_s=600.0,
            )
            v1 = (
                wavelattice.make_wave_kernel_jit.cache_info().misses
                + sharded.make_sharded_wave_kernel.cache_info().misses
            )
            gang = {
                "workload": "Gang/5000 (300 gangs x 50, min-member 50)",
                "scheduled": gres.scheduled,
                "unscheduled": gres.unscheduled,
                "duration_s": round(gres.duration_s, 3),
                "pods_per_s": round(gres.throughput_pods_per_s, 1),
                # the r3 wedge was variant churn (one compile per gang
                # batch); effect-keyed fingerprints collapse it
                "kernel_variant_compiles": v1 - v0,
            }
        except Exception:
            phase_failed("gang")

        # autoscaler workload: 1k pending pods against an EMPTY cluster
        # with a 4-shape NodeGroup catalog — time until the what-if
        # scale-up loop (simulate → provision → queue flush → bind) has
        # every pod bound. Runs AFTER the throughput suites so its node
        # churn can't pollute their windows.
        autoscaler = None
        try:
            ares = run_autoscaler_benchmark(n_pods=1000)
            autoscaler = {
                "workload": "Autoscaler/1k-pending-4-shapes",
                "pods": ares.num_pods,
                "candidate_shapes": ares.num_shapes,
                "scheduled": ares.scheduled,
                "time_to_all_bound_s": round(ares.time_to_all_bound_s, 3),
                "nodes_provisioned": ares.nodes_provisioned,
                "nodes_by_group": ares.nodes_by_group,
                "simulation_passes": ares.simulation_passes,
                "simulation_p50_ms": round(ares.simulation_p50_ms, 2),
                "simulation_p99_ms": round(ares.simulation_p99_ms, 2),
            }
        except Exception:
            phase_failed("autoscaler")

        # readpath workload: 10k hollow informers fanned out from ONE
        # store watch through the watch cache — p99 watch-delivery latency
        # and fan-out throughput (the PR-6 read-path acceptance numbers).
        # Pure host-side (no kernel), so it runs on every backend.
        readpath = None
        try:
            rres = run_readpath_benchmark(n_informers=10000, n_events=200)
            readpath = {
                "workload": "Readpath/10k-hollow-informers",
                "informers": rres.n_informers,
                "events": rres.n_events,
                "fanout_deliveries": rres.fanout_deliveries,
                "fanout_deliveries_per_s": round(
                    rres.fanout_deliveries_per_s, 1
                ),
                "delivery_p50_ms": round(rres.delivery_p50_ms, 3),
                "delivery_p99_ms": round(rres.delivery_p99_ms, 3),
                "store_watchers": rres.store_watchers,
                "slow_evicted": rres.slow_evicted,
            }
        except Exception:
            phase_failed("readpath")

        # serving workload (ISSUE 20): 1M watchers over TLS through the
        # shared-memory watch relay — a primary + 2 frontend processes,
        # each with SO_REUSEPORT relay workers fanning its ring out to
        # hollow watchers plus sampled REAL TLS watch streams. A small
        # 100k warmup run first so the frontend-CPU-flat-vs-watchers
        # claim is measured, not asserted.
        serving = None
        try:
            small = run_relay_serving_benchmark(
                n_watchers=100_000, n_pods=100
            )
            sres = run_relay_serving_benchmark(
                n_watchers=1_000_000, n_pods=100
            )
            small_cpu = sum(small.frontend_cpu_s) or 1e-9
            serving = {
                "workload": "Serving/1M-watchers-relay-tls",
                "frontends": sres.n_frontends,
                "relay_workers": sres.n_relay_workers,
                "watchers": sres.n_watchers,
                "real_tls_clients": sres.n_real_clients,
                "tls": sres.tls,
                "events": sres.n_events,
                "binds": sres.n_binds,
                "bind_p50_ms": round(sres.bind_p50_ms, 3),
                "bind_p99_ms": round(sres.bind_p99_ms, 3),
                "watch_p50_ms": round(sres.watch_p50_ms, 3),
                "watch_p99_ms": round(sres.watch_p99_ms, 3),
                "fanout_deliveries": sres.fanout_deliveries,
                "fanout_deliveries_per_s": round(
                    sres.fanout_deliveries_per_s, 1
                ),
                "deliveries_measured": sres.deliveries_measured,
                "evicted_slow": sres.evicted_slow,
                "frontend_cpu_s": sres.frontend_cpu_s,
                "worker_cpu_s": sres.worker_cpu_s,
                # frontend CPU at 1M watchers vs 100k (same event count):
                # ~1.0 means the frontend pays per frame, not per client
                "frontend_cpu_x_at_10x_watchers": round(
                    sum(sres.frontend_cpu_s) / small_cpu, 2
                ),
                "watchers_small": small.n_watchers,
            }
        except Exception:
            phase_failed("serving")

        # preemption workload (ISSUE 15): a 1k-pending high-priority burst
        # over a FULL 1k-node cluster — nothing places without displacing
        # lower-priority victims, so the line measures the vectorized
        # victim-selection engine (batched preempt_select passes per wave,
        # zero full per-pod host walks on the happy path).
        preemption = None
        try:
            pres = run_preemption_benchmark(n_nodes=1000, burst=1000)
            preemption = {
                "workload": "Preemption/1k-burst-over-full-1k-nodes",
                "nodes": pres.num_nodes,
                "burst_pods": pres.burst_pods,
                "scheduled": pres.scheduled,
                "time_to_all_bound_s": round(pres.time_to_all_bound_s, 3),
                "victims_evicted": pres.victims_evicted,
                "select_batches": pres.select_batches,
                "vector_attempts": pres.vector_attempts,
                "host_walk_fallbacks": pres.host_walk_fallbacks,
                "guard_trips": pres.guard_trips,
                "oracle_divergences": pres.oracle_divergences,
                "select_p50_ms": round(pres.select_p50_ms, 2),
                "select_p99_ms": round(pres.select_p99_ms, 2),
            }
        except Exception:
            phase_failed("preemption")

        # hetero workload (ISSUE 15): the same pending burst autoscaled
        # twice on a mixed-cost catalog — cheapest-feasible-shape packing
        # vs cost-blind MostAllocated; acceptance is a strictly cheaper
        # fleet at equal feasibility.
        hetero = None
        try:
            hres = run_hetero_benchmark(n_pods=300)
            hetero = {
                "workload": "Hetero/300-pods-mixed-cost-4-shapes",
                "pods": hres.num_pods,
                "candidate_shapes": hres.num_shapes,
                "cost_aware": {
                    "scheduled": hres.cost_aware_scheduled,
                    "nodes_by_group": hres.cost_aware_nodes,
                    "fleet_per_hour": hres.cost_aware_fleet_per_hour,
                    "time_to_all_bound_s": hres.cost_aware_time_s,
                },
                "most_allocated": {
                    "scheduled": hres.blind_scheduled,
                    "nodes_by_group": hres.blind_nodes,
                    "fleet_per_hour": hres.blind_fleet_per_hour,
                    "time_to_all_bound_s": hres.blind_time_s,
                },
                "strictly_cheaper": hres.strictly_cheaper,
            }
        except Exception:
            phase_failed("hetero")

        # tuner workload (ISSUE 16): the policy gym through a workload-mix
        # flip on a mixed-cost fleet — pre-flip full-width waves must NOT
        # promote (no arm can win); the flip to small bursts must promote
        # a cost-aware vector. Reports re-convergence time + the
        # steady-state scheduling overhead of running the gym at all.
        tuner = None
        try:
            tres = run_tuner_benchmark()
            tuner = {
                "workload": "Tuner/mixed-cost-flip-8-nodes",
                "nodes": tres.num_nodes,
                "pre_flip_rounds": tres.pre_flip_rounds,
                "pre_flip_promotions": tres.pre_flip_promotions,
                "baseline_pods_per_s": tres.baseline_pods_per_s,
                "tuner_on_pods_per_s": tres.tuner_on_pods_per_s,
                "steady_state_overhead_pct": tres.overhead_pct,
                "converged": tres.converged,
                "time_to_converge_s": tres.time_to_converge_s,
                "promoted_policy": tres.promoted_policy,
                "promoted_cost_weight": tres.promoted_cost_weight,
                "promotions": tres.promotions,
                "waves_recorded": tres.waves_recorded,
                "gym_passes": tres.gym_passes,
                "gym_pass_p50_ms": tres.gym_pass_p50_ms,
                "gym_pass_p99_ms": tres.gym_pass_p99_ms,
            }
        except Exception:
            phase_failed("tuner")

        # durability workload (ISSUE 18): raw WAL economics — group-commit
        # append throughput with the fsync contract on/off, the fsync
        # latency distribution the stall watchdog monitors, and cold
        # recovery time for a 50k-record log (crash-restart MTTR)
        durability = None
        try:
            dres = run_durability_benchmark()
            durability = {
                "workload": "Durability/wal-50k-records",
                "n_records": dres.n_records,
                "batch": dres.batch,
                "append_fsync_per_s": dres.append_fsync_per_s,
                "append_nofsync_per_s": dres.append_nofsync_per_s,
                "fsync_p50_ms": dres.fsync_p50_ms,
                "fsync_p99_ms": dres.fsync_p99_ms,
                "recovery_s": dres.recovery_s,
                "recovery_records_per_s": dres.recovery_records_per_s,
                "native_sink": dres.native_sink,
            }
        except Exception:
            phase_failed("durability")

        # defrag workload (ISSUE 19): a deliberately fragmented fleet
        # (half nearly full, half nearly empty, all pods ReplicaSet-owned)
        # handed to the verified descheduler — acceptance is the
        # consolidation contract: node count AND fleet $/h strictly drop
        # with every replica still bound.
        defrag = None
        try:
            fres = run_defrag_benchmark()
            defrag = {
                "workload": "Defrag/8-nodes-half-fragmented",
                "pods": fres.num_pods,
                "nodes_before": fres.nodes_before,
                "nodes_after": fres.nodes_after,
                "fleet_per_hour_before": fres.fleet_per_hour_before,
                "fleet_per_hour_after": fres.fleet_per_hour_after,
                "fragmentation_before": fres.fragmentation_before,
                "fragmentation_after": fres.fragmentation_after,
                "plans": fres.plans,
                "evictions": fres.evictions,
                "aborts": fres.aborts,
                "bound_after": fres.bound_after,
                "time_to_quiesce_s": fres.time_to_quiesce_s,
                "strictly_tighter": fres.strictly_tighter,
            }
        except Exception:
            phase_failed("defrag")

        out.update(
            value=round(res.throughput_pods_per_s, 1),
            vs_baseline=round(res.throughput_pods_per_s / TARGET_PODS_PER_S, 4),
            detail={
                "platform": platform,
                "device_readback_rtt_ms": readback_rtt_ms,
                # the steady-state pod-p99 floor: every cycle needs >=1
                # device->host readback (bind consumes the chosen nodes
                # host-side)
                "latency_floor_note": (
                    f"pod p99 >= 1 readback RTT ({readback_rtt_ms} ms measured "
                    "on this backend); algo_device_p99_ms below reports the "
                    "algorithm-only device latency with that RTT subtracted"
                ),
                "workload": res.workload,
                "num_nodes": res.num_nodes,
                "scheduled": res.scheduled,
                "unscheduled": res.unscheduled,
                "duration_s": round(res.duration_s, 3),
                "e2e_p50_ms": round(res.e2e_p50_ms, 3),
                "e2e_p99_ms": round(res.e2e_p99_ms, 3),
                "algo_p99_ms": round(res.algo_p99_ms, 3),
                # generational wave pipelining: configured depth + the
                # high-water mark of batches concurrently in flight (≥2
                # = waves demonstrably overlapped instead of serializing)
                "pipeline": {
                    "depth": res.pipeline_depth,
                    "max_waves_inflight": res.max_waves_inflight,
                },
                "stage_breakdown_s": {
                    "encode_total": round(res.encode_total_s, 3),
                    "kernel_total": round(res.kernel_total_s, 3),
                    "n_batches": res.n_batches,
                    "n_readbacks": res.n_readbacks,
                    # < 1.0: the pipeline shares one readback across
                    # several batches (pipeline_depth amortization)
                    "readbacks_per_batch": round(res.readbacks_per_batch, 3),
                },
                # algo-only device latency (VERDICT r3 weak #7): kernel-stage
                # wall per readback cycle (device compute + ONE result sync);
                # algo_device_p99_ms subtracts the measured readback RTT so
                # the <10 ms target is adjudicable separately from the
                # host<->device link
                "algo_device_cycle_p50_ms": round(res.kernel_cycle_p50_ms, 3),
                "algo_device_cycle_p99_ms": round(res.kernel_cycle_p99_ms, 3),
                "algo_device_p99_ms": round(
                    max(res.kernel_cycle_p99_ms - readback_rtt_ms, 0.0), 3
                ),
                "algo_device_per_pod_ms": round(res.kernel_per_pod_ms, 4),
                "gang": gang,
                "autoscaler": autoscaler,
                "readpath": readpath,
                "serving": serving,
                "preemption": preemption,
                "hetero": hetero,
                "tuner": tuner,
                "durability": durability,
                "defrag": defrag,
                "steady_state_latency": (
                    {
                        "rate_pods_per_s": round(lat.rate_pods_per_s, 1),
                        "pod_p50_ms": round(lat.pod_p50_ms, 3),
                        "pod_p90_ms": round(lat.pod_p90_ms, 3),
                        "pod_p99_ms": round(lat.pod_p99_ms, 3),
                        "cycle_p50_ms": round(lat.cycle_p50_ms, 3),
                        "cycle_p99_ms": round(lat.cycle_p99_ms, 3),
                        # the pod latency split: queue wait (real per-pod
                        # queue spans) vs in-flight (the in-cycle e2e
                        # histogram) — which half owns the p99
                        "queue_wait_p50_ms": round(lat.queue_wait_p50_ms, 3),
                        "queue_wait_p99_ms": round(lat.queue_wait_p99_ms, 3),
                        "in_flight_p50_ms": round(lat.in_flight_p50_ms, 3),
                        "in_flight_p99_ms": round(lat.in_flight_p99_ms, 3),
                        # split-phase acceptance: host-blocking device
                        # syncs per bound pod over the measured window
                        "readbacks_per_bind": round(lat.readbacks_per_bind, 4),
                        "scheduled": lat.scheduled,
                        "pipeline_depth": lat.pipeline_depth,
                        "max_waves_inflight": lat.max_waves_inflight,
                        # the p99 hunt's raw material: per-stage waterfall
                        # from REAL per-pod spans (not ad-hoc timers), the
                        # span-sum/e2e reconciliation ratio, and the p99
                        # exemplar's full trace — retrievable by id via
                        # SIGUSR2 and /debug/traces on a live process
                        "stage_waterfall": lat.stage_waterfall,
                        "waterfall_vs_e2e": round(lat.waterfall_vs_e2e, 4),
                        "p99_trace_id": lat.p99_trace_id,
                        "p99_trace": lat.p99_trace,
                    }
                    if lat is not None
                    else None
                ),
            },
        )
    except Exception as e:  # noqa: BLE001 — the contract is "always one JSON line"
        traceback.print_exc()
        out["error"] = f"{type(e).__name__}: {e}"
    if failed_phases and "error" not in out:
        out["error"] = "phases failed: " + ", ".join(failed_phases)
    # Emit a COMPACT final stdout line and push the full payload (far past
    # any log tail window) to a detail file: the driver parses the last
    # line, so the headline number must never be truncated out of
    # existence.
    detail_path = os.environ.get(
        "BENCH_DETAIL_FILE",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench_detail.json"),
    )
    try:
        with open(detail_path, "w", encoding="utf-8") as f:
            json.dump(out, f, indent=1)
    except OSError:
        detail_path = None
    detail = out.get("detail") or {}
    compact = {
        "metric": out["metric"],
        "value": out["value"],
        "unit": out["unit"],
        "vs_baseline": out["vs_baseline"],
        "platform": (out.get("device") or {}).get("platform", "unknown"),
        "device": out.get("device"),
        "detail_file": detail_path,
    }
    # first-class headline fields (ISSUE 11): steady-state pod p99 and
    # pipeline depth/occupancy — the latency assault's acceptance metrics
    lat_d = detail.get("steady_state_latency") or {}
    if lat_d:
        compact["steady_pod_p99_ms"] = lat_d.get("pod_p99_ms")
        # split-phase readback headline pair (r17): blocking device syncs
        # per bound pod, and how many measured readback RTTs the steady
        # pod p99 spans (the one-RTT-per-bind floor broken means this can
        # approach — or on near-zero-RTT CPU, merely stop tracking — 1.0;
        # the RTT clamps at 10 µs so a local backend can't divide by ~0)
        compact["readbacks_per_bind"] = lat_d.get("readbacks_per_bind")
        rtt = detail.get("device_readback_rtt_ms")
        p99 = lat_d.get("pod_p99_ms")
        if rtt is not None and p99 is not None:
            compact["rtt_floor_ratio"] = round(p99 / max(rtt, 0.01), 2)
        # compact stage waterfall (p99 per stage, ms) + the reconciliation
        # ratio: the one-line answer to "where does the p99 pod spend it"
        wf = lat_d.get("stage_waterfall") or {}
        if wf:
            compact["waterfall_p99_ms"] = {
                k: v.get("p99_ms") for k, v in wf.items()
            }
            compact["waterfall_vs_e2e"] = lat_d.get("waterfall_vs_e2e")
            compact["p99_trace_id"] = lat_d.get("p99_trace_id")
    pipe_d = detail.get("pipeline") or {}
    if pipe_d:
        compact["pipeline"] = pipe_d
    asc = detail.get("autoscaler") or {}
    if asc:
        # one compact autoscaler line item: 1k pending pods, 4 candidate
        # shapes → time-to-all-bound (full breakdown in detail_file)
        compact["autoscaler"] = {
            "pods": asc.get("pods"),
            "shapes": asc.get("candidate_shapes"),
            "scheduled": asc.get("scheduled"),
            "time_to_all_bound_s": asc.get("time_to_all_bound_s"),
            "nodes": asc.get("nodes_provisioned"),
        }
    sv = detail.get("serving") or {}
    if sv:
        # compact serving line item: 1M watchers over TLS through the
        # shared-memory relay tier — frames-not-clients fan-out rate,
        # real-TLS-stream latency, and the frontend CPU flatness proof
        compact["serving"] = {
            "frontends": sv.get("frontends"),
            "relay_workers": sv.get("relay_workers"),
            "watchers": sv.get("watchers"),
            "tls": sv.get("tls"),
            "bind_p50_ms": sv.get("bind_p50_ms"),
            "bind_p99_ms": sv.get("bind_p99_ms"),
            "watch_p99_ms": sv.get("watch_p99_ms"),
            "fanout_deliveries_per_s": sv.get("fanout_deliveries_per_s"),
            "frontend_cpu_x_at_10x_watchers": sv.get(
                "frontend_cpu_x_at_10x_watchers"
            ),
        }
    rp = detail.get("readpath") or {}
    if rp:
        # compact readpath line item: 10k hollow informers on one store
        # watch — delivery p99 + fan-out rate (full breakdown in detail)
        compact["readpath"] = {
            "informers": rp.get("informers"),
            "events": rp.get("events"),
            "fanout_deliveries_per_s": rp.get("fanout_deliveries_per_s"),
            "delivery_p99_ms": rp.get("delivery_p99_ms"),
            "store_watchers": rp.get("store_watchers"),
        }
    pe = detail.get("preemption") or {}
    if pe:
        # compact preemption line item: high-priority burst over a full
        # cluster — victims resolve in batched passes, not per-pod walks
        compact["preemption"] = {
            "nodes": pe.get("nodes"),
            "burst_pods": pe.get("burst_pods"),
            "scheduled": pe.get("scheduled"),
            "time_to_all_bound_s": pe.get("time_to_all_bound_s"),
            "victims": pe.get("victims_evicted"),
            "select_batches": pe.get("select_batches"),
            "host_walk_fallbacks": pe.get("host_walk_fallbacks"),
            "select_p99_ms": pe.get("select_p99_ms"),
        }
    he = detail.get("hetero") or {}
    if he:
        # compact hetero line item: cost-aware vs cost-blind fleet bill
        # at equal feasibility (full per-arm breakdown in detail_file)
        compact["hetero"] = {
            "pods": he.get("pods"),
            "cost_aware_fleet_per_hour": (he.get("cost_aware") or {}).get(
                "fleet_per_hour"
            ),
            "most_allocated_fleet_per_hour": (
                he.get("most_allocated") or {}
            ).get("fleet_per_hour"),
            "strictly_cheaper": he.get("strictly_cheaper"),
        }
    tu = detail.get("tuner") or {}
    if tu:
        # compact tuner line item: workload-flip re-convergence + the
        # cost of running the gym (full segment breakdown in detail_file)
        compact["tuner"] = {
            "converged": tu.get("converged"),
            "time_to_converge_s": tu.get("time_to_converge_s"),
            "promoted_policy": tu.get("promoted_policy"),
            "pre_flip_promotions": tu.get("pre_flip_promotions"),
            "steady_state_overhead_pct": tu.get("steady_state_overhead_pct"),
            "gym_pass_p99_ms": tu.get("gym_pass_p99_ms"),
        }
    du = detail.get("durability") or {}
    if du:
        # compact durability line item: appends/s fsync on/off, fsync
        # p50/p99, and 50k-record recovery time (full detail in file)
        compact["durability"] = {
            "append_fsync_per_s": du.get("append_fsync_per_s"),
            "append_nofsync_per_s": du.get("append_nofsync_per_s"),
            "fsync_p50_ms": du.get("fsync_p50_ms"),
            "fsync_p99_ms": du.get("fsync_p99_ms"),
            "recovery_s": du.get("recovery_s"),
        }
    df = detail.get("defrag") or {}
    if df:
        # compact defrag line item: nodes + fleet bill before/after the
        # verified consolidation run (full breakdown in detail_file)
        compact["defrag"] = {
            "nodes_before": df.get("nodes_before"),
            "nodes_after": df.get("nodes_after"),
            "fleet_per_hour_before": df.get("fleet_per_hour_before"),
            "fleet_per_hour_after": df.get("fleet_per_hour_after"),
            "evictions": df.get("evictions"),
            "time_to_quiesce_s": df.get("time_to_quiesce_s"),
            "strictly_tighter": df.get("strictly_tighter"),
        }
    if "error" in out:
        compact["error"] = out["error"]
    print(json.dumps(compact))
    sys.stdout.flush()
    return 1 if "error" in out else 0


if __name__ == "__main__":
    sys.exit(main())
