"""Split-phase readback + continuous micro-waves (round 17).

Unit coverage for the data plane pieces the chaos suite exercises under
faults: the split-phase wave path end-to-end at every pipeline depth
(fast index payload drives assumes, trailing bulk validation drains, all
pods land, no generation pin outlives its wave), the fault injector's
seams, and the config: the trailing backlog bound, and the options that
no longer exist.
"""

import time

import jax
import pytest

from kubernetes_tpu.api import objects as v1
from kubernetes_tpu.client.apiserver import APIServer
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.scheduler.config import KubeSchedulerConfiguration
from kubernetes_tpu.utils.metrics import metrics


# -- end-to-end wave path ----------------------------------------------------


def _mk_server(n_nodes=10):
    server = APIServer()
    for i in range(n_nodes):
        server.create(
            "nodes",
            v1.Node(
                metadata=v1.ObjectMeta(name=f"n{i}", namespace=""),
                status=v1.NodeStatus(
                    capacity={"cpu": "16", "memory": "64Gi", "pods": "110"}
                ),
            ),
        )
    return server


def _run_pods(server, sched, n_pods, timeout_s=90.0):
    for i in range(n_pods):
        server.create(
            "pods",
            v1.Pod(
                metadata=v1.ObjectMeta(name=f"p{i}"),
                spec=v1.PodSpec(
                    containers=[v1.Container(requests={"cpu": "100m"})]
                ),
            ),
        )
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if server.count("pods", lambda p: bool(p.spec.node_name)) == n_pods:
            break
        time.sleep(0.05)
    assert server.count("pods", lambda p: bool(p.spec.node_name)) == n_pods
    assert sched.wait_for_idle(30.0)


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_split_phase_binds_all_and_drains_trailing(depth):
    """The one wave path at every pipeline depth — 1 resolves each wave
    synchronously, 2 is what cmd/ starts, 3 leaves room for the
    micro-wave early resolve (an older wave whose index payload already
    landed commits before the pipeline fills): every pod lands, the
    trailing validations all consume (counter advances), and idle means
    an EMPTY trailing backlog — no generation pin outlives its wave."""
    server = _mk_server()
    scfg = KubeSchedulerConfiguration(
        pipeline_depth=depth,
        device_batch_size=16,
        device_batch_window=0.02,
        use_mesh=False,
    )
    trailing0 = metrics.counter("scheduler_wave_trailing_readbacks_total")
    fast0 = metrics.counter("scheduler_wave_fast_readbacks_total")
    unwound0 = metrics.counter(
        "scheduler_wave_trailing_unwound_assumes_total"
    )
    sched = Scheduler(server, scfg)
    assert sched._pipeline_depth == depth
    early = []
    if depth == 3:
        # a device that finishes each wave before the host launches the
        # next (on the CPU the kernel is the slower one, and the early
        # resolve would depend on timing): the older wave's index payload
        # has landed when the second launch returns, so it must commit
        # THERE, with the pipeline not yet full
        launch, resolve = sched._launch_wave_kernel, sched._resolve_oldest

        def launch_and_wait(*args):
            new_snap, res = launch(*args)
            jax.block_until_ready((res.chosen, res.placed, res.deferred))
            return new_snap, res

        def resolve_spy(k):
            if k < len(sched._pending) < depth:
                early.append(k)
            return resolve(k)

        sched._launch_wave_kernel = launch_and_wait
        sched._resolve_oldest = resolve_spy
    sched.start()
    try:
        _run_pods(server, sched, 48)
    finally:
        sched.stop()
    assert early or depth != 3, "no wave took the micro-wave early resolve"
    assert sched._pending == []
    assert sched._trailing == []
    assert metrics.counter("scheduler_wave_fast_readbacks_total") > fast0
    trailing1 = metrics.counter("scheduler_wave_trailing_readbacks_total")
    assert trailing1 > trailing0, "no trailing bulk validation ran"
    # a clean run unwinds nothing
    assert (
        metrics.counter("scheduler_wave_trailing_unwound_assumes_total")
        == unwound0
    )
    assert metrics.gauge("scheduler_wave_trailing_backlog") in (None, 0.0)
    # every trailing entry released its generation pin
    enc = sched.cache.encoder
    assert enc._gen.pins == 0
    assert not enc._retiring


# -- the fault injector's seams ----------------------------------------------


def test_fault_injector_patches_exactly_the_schedulers_seams():
    """A seam renamed or removed on the Scheduler must fail HERE, not
    turn the chaos suite's injections into silent no-ops."""
    from kubernetes_tpu.testing.device_faults import DeviceFaultInjector

    seams = {
        "_launch_wave_kernel",
        "_fetch_wave_index",
        "_fetch_wave_bulk",
        "_run_serial_kernel",
    }
    for name in seams:
        assert callable(getattr(Scheduler, name)), name
    sched = Scheduler(
        _mk_server(1), KubeSchedulerConfiguration(use_mesh=False)
    )
    assert not vars(sched).keys() & seams
    inj = DeviceFaultInjector().install(sched)
    # install shadows the class's methods on the instance: those four
    # and nothing else
    patched = {
        k for k, v in vars(sched).items() if getattr(v, "__self__", None) is inj
    }
    assert patched == seams
    inj.uninstall()
    for name in seams:
        bound = getattr(sched, name)
        assert bound.__self__ is sched, name
        assert bound.__func__ is getattr(Scheduler, name), name


# -- config ------------------------------------------------------------------


def test_trailing_readback_max_validation():
    cfg = KubeSchedulerConfiguration(trailing_readback_max=0)
    with pytest.raises(ValueError, match="trailing_readback_max"):
        cfg.validate()
    KubeSchedulerConfiguration(trailing_readback_max=1).validate()


@pytest.mark.parametrize(
    "name", ["split_phase_readback", "host_callback_binds", "sync_batch_bind"]
)
def test_deleted_option_is_refused(name):
    """The wave's result has one way to the host and the bulk bind has
    no switch: a caller that still passes a deleted option hears of it."""
    with pytest.raises(TypeError, match=name):
        KubeSchedulerConfiguration(**{name: True})
