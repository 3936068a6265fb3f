"""Multi-host (DCN) story: two OS processes form one JAX distributed system
and execute the PRODUCTION sharded wave kernel over a global mesh.

Reference analogue: the control plane's cross-host communication backend
(SURVEY §2.3 "Distributed communication backend": jax.distributed +
multi-host pjit across DCN stands in for etcd/gRPC fan-out on the data
plane). Each process contributes 4 virtual CPU devices; the 8-device global
mesh shards the snapshot over the node axis, so the kernel's segment-sum
psums and top-k gathers cross the process boundary.

Runs both processes under a hard timeout; skips (not fails) when the
image's jax build lacks distributed CPU support.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = textwrap.dedent(
    """
    import os, sys
    pid = int(sys.argv[1]); port = sys.argv[2]
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, {repo!r})
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{{port}}", num_processes=2, process_id=pid
    )
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 8, len(jax.devices())

    import numpy as np
    from kubernetes_tpu.ops.lattice import DEFAULT_WEIGHTS
    from kubernetes_tpu.ops.templates import TemplateCache, build_pair_table
    from kubernetes_tpu.parallel.mesh import (
        make_mesh, replicated, snapshot_shardings,
    )
    from kubernetes_tpu.parallel.sharded import make_sharded_wave_kernel
    sys.path.insert(0, {repo!r} + "/tests")
    from test_lattice_smoke import make_node, make_pod
    from kubernetes_tpu.ops.encoding import SnapshotEncoder

    # both processes build IDENTICAL host state (SPMD contract)
    enc = SnapshotEncoder()
    for i in range(32):
        enc.add_node(make_node(f"n{{i}}", cpu="4", labels={{"zone": f"z{{i%4}}"}}))
    for i in range(8):
        enc.add_pod(f"n{{i}}", make_pod(f"pre-{{i}}", cpu="1", labels={{"app": "w"}}))
    tc = TemplateCache(enc)
    pods = [make_pod(f"p{{i}}", cpu="500m") for i in range(8)]
    eb = tc.encode(pods, pad_to=8)
    ptab = build_pair_table(enc, eb.tpl_np, eb.num_templates)

    mesh = make_mesh()  # 8 global devices across the 2 processes
    enc.set_sharding(snapshot_shardings(mesh), replicated(mesh))
    snap = enc.flush()
    kern = make_sharded_wave_kernel(enc.cfg.v_cap, 32, 4, 1.0, mesh)
    new_snap, res = kern(
        snap, eb.batch, ptab, np.asarray(DEFAULT_WEIGHTS), jax.random.PRNGKey(0)
    )
    placed = jax.device_get(res.placed)
    chosen = jax.device_get(res.chosen)
    assert placed.all(), placed
    print("DCN_OK", pid, int(placed.sum()), list(map(int, chosen)))
    jax.distributed.shutdown()
    """
).format(repo=REPO)


def test_two_process_distributed_wave_kernel():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = {
        k: v
        for k, v in os.environ.items()
        if not k.startswith(("JAX_", "XLA_"))
    }
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, str(pid), str(port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            env=env,
            cwd=REPO,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=240)
            outs.append((p.returncode, out, err))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("distributed processes hung")
    for rc, out, err in outs:
        if rc != 0 and (
            "distributed" in err.lower() and "not" in err.lower()
            or "UNIMPLEMENTED" in err
            # this image's jaxlib CPU backend has no cross-process
            # collectives (no gloo/mpi): multi-host device_put fails with
            # INVALID_ARGUMENT "Multiprocess computations aren't
            # implemented on the CPU backend" — an environment limitation
            # (tracked: carried as tier-1's "1 pre-existing failure"
            # since PR 4; triaged in the ISSUE-10 multi-process PR), not
            # a regression. TPU/GPU runs exercise the real path.
            or "Multiprocess computations aren't implemented" in err
        ):
            pytest.skip(f"jax distributed CPU unsupported here: {err[-300:]}")
        assert rc == 0, err[-2000:]
        assert "DCN_OK" in out, out
    # SPMD determinism: both processes computed identical placements
    line0 = [l for l in outs[0][1].splitlines() if l.startswith("DCN_OK")][0]
    line1 = [l for l in outs[1][1].splitlines() if l.startswith("DCN_OK")][0]
    assert line0.split()[2:] == line1.split()[2:], (line0, line1)
