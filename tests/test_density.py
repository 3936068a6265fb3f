"""Density threshold: the CI throughput floor the reference enforces
(test/integration/scheduler_perf/scheduler_test.go:40-42: fail below
30 pods/s, warn below 100 on the 3k-pod/100-node density config). Runs on
the CPU backend, so the floor guards against host-path regressions (queue,
encode, store) — device speed is bench.py's job."""

import logging

from kubernetes_tpu.perf.harness import run_benchmark
from kubernetes_tpu.perf.workloads import WorkloadConfig

logger = logging.getLogger(__name__)

THRESHOLD = 30.0  # hard floor (scheduler_test.go threshold3K)
WARNING = 100.0


import pytest


@pytest.mark.parametrize(
    "nodes,pods,timeout_s",
    [
        # the reference's 3k-pod/100-node gate (scheduler_test.go:71-90)
        (100, 3000, 240),
        # the 1000-node cluster of the 30k-pod gate (scheduler_test.go:
        # 93-103) at a CPU-scale pod count; the full 30k-pod config is
        # SchedulingDensity/1000 in perf/workloads.py
        (1000, 3000, 300),
    ],
    ids=["100n-3k", "1000n-3k"],
)
def test_density_min_throughput(nodes, pods, timeout_s):
    cfg = WorkloadConfig("SchedulingBasic", nodes, 0, pods)
    res = run_benchmark(cfg, quiet=True, timeout_s=timeout_s)
    assert res.unscheduled == 0, f"{res.unscheduled} pods unscheduled"
    if res.throughput_pods_per_s < WARNING:
        logger.warning(
            "density %dn throughput %.1f pods/s below warning level %.0f",
            nodes,
            res.throughput_pods_per_s,
            WARNING,
        )
    assert res.throughput_pods_per_s >= THRESHOLD, (
        f"density {nodes}n throughput {res.throughput_pods_per_s:.1f} "
        f"pods/s below the {THRESHOLD:.0f} pods/s floor"
    )


def test_secrets_and_intree_pv_workloads_schedule():
    """The remaining performance-config variants: secret-volume pods ride
    the device path; in-tree-PV pods take the host fallback lane — both
    must fully schedule."""
    from kubernetes_tpu.perf.workloads import WORKLOADS

    r = run_benchmark(
        WorkloadConfig("SchedulingSecrets", 50, 0, 200), quiet=True,
        timeout_s=240,
    )
    assert r.unscheduled == 0
    r = run_benchmark(
        WorkloadConfig("SchedulingInTreePVs", 50, 0, 100), quiet=True,
        timeout_s=240,
    )
    assert r.unscheduled == 0
    assert "SchedulingSecrets/5000" in WORKLOADS
