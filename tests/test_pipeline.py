"""Wave-pipeline readback amortization (VERDICT r3 #2).

The scheduler keeps up to pipeline_depth-1 launched wave batches in flight
and resolves them with ONE combined device->host readback, so the sync
is paid once per several batches instead of once per batch. These tests
pin (a) the amortization ratio under sustained load, (b) correctness under
a deep pipeline (every pod still lands exactly once), and (c) that depth=2
reproduces the old depth-1-pipeline behavior.
"""

from kubernetes_tpu.perf.harness import run_benchmark
from kubernetes_tpu.perf.workloads import WorkloadConfig
from kubernetes_tpu.scheduler.config import KubeSchedulerConfiguration


def _run(depth: int, batch: int = 64, pods: int = 1024):
    cfg = WorkloadConfig("SchedulingBasic", 50, 0, pods)
    scfg = KubeSchedulerConfiguration(
        pipeline_depth=depth,
        device_batch_size=batch,
        device_batch_window=0.05,
    )
    return run_benchmark(cfg, sched_config=scfg, quiet=True, timeout_s=240)


def test_deep_pipeline_amortizes_readbacks():
    res = _run(depth=6)
    assert res.unscheduled == 0
    assert res.n_batches >= 8, f"want a multi-batch run, got {res.n_batches}"
    # sustained-load target: 1/(depth-1) = 0.2; drains at burst edges can
    # only add readbacks, so assert the VERDICT threshold with headroom
    assert res.readbacks_per_batch < 0.7, (
        f"readbacks/batch {res.readbacks_per_batch:.2f} — pipeline is not "
        f"amortizing ({res.n_readbacks} readbacks / {res.n_batches} batches)"
    )


def test_depth2_matches_legacy_depth1_pipeline():
    res = _run(depth=2, pods=512)
    assert res.unscheduled == 0
    # one readback per batch (each launch resolves the previous batch)
    assert res.n_readbacks <= res.n_batches + 1


def test_synchronous_depth1_still_schedules_all():
    res = _run(depth=1, pods=256)
    assert res.unscheduled == 0


def test_deep_pipeline_device_host_convergence():
    """After a deep-pipelined burst fully resolves, the donated on-device
    snapshot must EQUAL a host-master re-encode (the device/host
    convergence invariant the per-batch replay maintains; any divergence
    means a batch's commits were erased or double-applied)."""
    import jax
    import numpy as np

    from kubernetes_tpu.api import objects as v1
    from kubernetes_tpu.client.apiserver import APIServer
    from kubernetes_tpu.scheduler import Scheduler

    server = APIServer()
    for i in range(20):
        server.create(
            "nodes",
            v1.Node(
                metadata=v1.ObjectMeta(name=f"n{i}", namespace=""),
                status=v1.NodeStatus(
                    capacity={"cpu": "32", "memory": "128Gi", "pods": "200"}
                ),
            ),
        )
    scfg = KubeSchedulerConfiguration(
        pipeline_depth=6,
        device_batch_size=32,
        device_batch_window=0.02,
        use_mesh=False,
    )
    sched = Scheduler(server, scfg)
    sched.start()
    try:
        for i in range(300):
            server.create(
                "pods",
                v1.Pod(
                    metadata=v1.ObjectMeta(
                        name=f"p{i}", labels={"app": f"a{i % 3}"}
                    ),
                    spec=v1.PodSpec(
                        containers=[v1.Container(requests={"cpu": "100m"})]
                    ),
                ),
            )
        import time as _time

        deadline = _time.monotonic() + 60.0
        while _time.monotonic() < deadline:
            if server.count("pods", lambda p: bool(p.spec.node_name)) == 300:
                break
            _time.sleep(0.05)
        assert server.count("pods", lambda p: bool(p.spec.node_name)) == 300
        assert sched.wait_for_idle(30.0)
        with sched.cache.lock:
            enc = sched.cache.encoder
            dev = jax.device_get(enc.flush())
            masters = enc._masters()
        for fld in ("requested", "sel_counts", "port_counts", "prio_req"):
            d = np.asarray(getattr(dev, fld))
            h = np.asarray(getattr(masters, fld))
            assert np.array_equal(d, h), (
                f"device/host diverged on {fld}: "
                f"{np.abs(d.astype(np.int64) - h.astype(np.int64)).max()}"
            )
    finally:
        sched.stop()


def test_readback_failure_requeues_and_recovers(monkeypatch):
    """A device error during the combined readback must requeue the
    in-flight pods, invalidate the device snapshot (HBM rebuilt from host
    masters), and let the next cycle schedule them — no pod lost, no
    double-commit."""
    import jax

    from kubernetes_tpu.api import objects as v1
    from kubernetes_tpu.client.apiserver import APIServer
    from kubernetes_tpu.scheduler import Scheduler

    server = APIServer()
    for i in range(5):
        server.create(
            "nodes",
            v1.Node(
                metadata=v1.ObjectMeta(name=f"n{i}", namespace=""),
                status=v1.NodeStatus(
                    capacity={"cpu": "16", "memory": "64Gi", "pods": "110"}
                ),
            ),
        )
    scfg = KubeSchedulerConfiguration(
        pipeline_depth=2, device_batch_size=64, use_mesh=False
    )
    sched = Scheduler(server, scfg)

    real_device_get = jax.device_get
    fail_once = {"armed": False, "fired": 0}

    def flaky_device_get(x):
        if fail_once["armed"]:
            fail_once["armed"] = False
            fail_once["fired"] += 1
            raise RuntimeError("injected readback failure")
        return real_device_get(x)

    monkeypatch.setattr(jax, "device_get", flaky_device_get)
    sched.start()
    try:
        for i in range(100):
            server.create(
                "pods",
                v1.Pod(
                    metadata=v1.ObjectMeta(name=f"p{i}"),
                    spec=v1.PodSpec(
                        containers=[v1.Container(requests={"cpu": "100m"})]
                    ),
                ),
            )
        import time as _time

        _time.sleep(0.3)
        fail_once["armed"] = True  # next readback dies
        deadline = _time.monotonic() + 60.0
        while _time.monotonic() < deadline:
            if server.count("pods", lambda p: bool(p.spec.node_name)) == 100:
                break
            _time.sleep(0.05)
        bound = server.count("pods", lambda p: bool(p.spec.node_name))
        assert bound == 100, f"only {bound}/100 scheduled after injected failure"
        assert fail_once["fired"] >= 1 or not fail_once["armed"]
    finally:
        sched.stop()
