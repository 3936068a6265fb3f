"""Gang burst kernel-variant discipline (VERDICT r3 #5, [[template-
fingerprints]]): the r3 wedge was a compile storm — 300 gangs
differing only by group-name label produced a fresh XLA variant per batch.
Effect-keyed fingerprints collapse the burst to ONE template and the
kernel factory to ONE variant; this pins that at CPU scale so the
on-hardware gang run can't regress back into a storm."""

import jax

from kubernetes_tpu.ops import wavelattice
from kubernetes_tpu.parallel import sharded
from kubernetes_tpu.perf.harness import run_benchmark
from kubernetes_tpu.perf.workloads import WorkloadConfig
from kubernetes_tpu.scheduler.config import (
    KubeSchedulerConfiguration,
    ProfileConfig,
)
from kubernetes_tpu.scheduler.framework.registry import coscheduling_plugin_set


def test_gang_burst_compiles_one_kernel_variant():
    wavelattice.make_wave_kernel_jit.cache_clear()
    sharded.make_sharded_wave_kernel.cache_clear()
    gcfg = KubeSchedulerConfiguration(
        profiles=[ProfileConfig(plugin_set=coscheduling_plugin_set())]
    )
    r = run_benchmark(
        WorkloadConfig("Gang", 500, 0, 1500),
        sched_config=gcfg,
        quiet=True,
        timeout_s=240,
    )
    assert r.unscheduled == 0, f"{r.unscheduled} gang pods unscheduled"
    # the scheduler runs the sharded kernel under the test mesh (8 virtual
    # devices) and the single-chip kernel otherwise — count both factories
    variants = (
        wavelattice.make_wave_kernel_jit.cache_info().misses
        + sharded.make_sharded_wave_kernel.cache_info().misses
    )
    # 30 gangs x 50 members: at most TWO kernel factory variants for the
    # entire burst — the big-bucket kernel plus (when an early/tail batch
    # lands under 256 pods) the small latency bucket, which runs a
    # narrower candidate list (wave_m_cand_small) and therefore its own
    # factory key. Before r5 the small pad compiled a second XLA shape
    # anyway but shared the factory key, so "1" undercounted real
    # compiles. Each variant beyond these is template churn — one
    # multi-second compile per gang batch (the r3 wedge).
    assert variants <= 2, f"kernel variant churn: {variants} variants"
