"""Node-axis sharded scheduling step.

jit-compiles the same lattice kernel (ops/lattice.py) with the snapshot
sharded over the mesh's "nodes" axis. The SPMD partitioner turns:
  * the feasible-mask AND / per-node filter math → purely local work,
  * topology-domain segment-sums → local scatter-adds + psum over ICI
    (domain ids are global, so partial sums reduce across shards),
  * score max / argmax select → local max + pmax/all-gather of candidates,
  * the scan carry scatter (.at[idx].add) → a one-shard update.
This is the TPU equivalent of the reference's "shard informer fan-out +
goroutines per node chunk" (SURVEY.md §2.3 table) with ICI instead of
channels, and of its multi-host story (DCN) when the mesh spans hosts via
jax.distributed.
"""

from __future__ import annotations

import functools
import random
import time
from typing import Callable, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.encoding import DeviceSnapshot, PodBatch
from ..ops.lattice import BatchResult, make_schedule_batch_raw
from ..ops.templates import PairTable, TemplateBatch
from ..ops.wavelattice import WaveResult, make_wave_kernel
from .mesh import NODES_AXIS, replicated, snapshot_shardings


# -- device-loss classification + bounded retry ------------------------------


class DeviceLossError(RuntimeError):
    """Raised (or re-classified) when a kernel launch/readback failed
    because the device itself is gone or unreachable — as opposed to a
    program bug. The fault injector (testing/device_faults.py) raises this
    directly; real XLA surfaces jaxlib.XlaRuntimeError, matched below."""


# substrings (lowercased) that mark an XLA runtime error as device loss
# rather than a program error; deliberately conservative — a false
# negative costs a wave (requeued, zero pod loss), a false positive would
# retry/reshard on a genuine kernel bug and mask it
_DEVICE_LOSS_MARKERS = (
    "device unavailable",
    "device is unavailable",
    "device lost",
    "device not found",
    "unable to reach device",
    "failed to connect",
    "connection reset",
    "socket closed",
    "deadline exceeded",
    "data transfer failed",
    "halted",
    "unavailable:",
)


def is_device_loss_error(exc: BaseException) -> bool:
    if isinstance(exc, DeviceLossError):
        return True
    if type(exc).__name__ != "XlaRuntimeError" and not isinstance(
        exc, RuntimeError
    ):
        return False
    msg = str(exc).lower()
    return any(m in msg for m in _DEVICE_LOSS_MARKERS)


def device_retry_delay(attempts: int, base_delay_s: float = 0.05) -> float:
    """Jittered exponential backoff for device-loss retries — ONE policy
    shared by this helper and the scheduler's launch/serial retry loops
    (which can't use call_with_device_retry itself: each of their retries
    must re-encode/re-flush first)."""
    return base_delay_s * (2 ** attempts) * (1.0 + random.uniform(-0.3, 0.3))


def call_with_device_retry(
    fn: Callable,
    attempts: int,
    base_delay_s: float = 0.05,
    on_retry: Optional[Callable] = None,
):
    """Run fn(), retrying device-loss errors up to `attempts` times with
    jittered exponential backoff (a transient blip heals in tens of ms; a
    dead chip won't, and the caller's ride-through takes over). Only safe
    for repeatable calls — a launch that DONATED its inputs must re-flush
    before retrying and cannot use this helper."""
    n = 0
    while True:
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 — classifier filters
            if not is_device_loss_error(e) or n >= attempts:
                raise
            n += 1
            if on_retry is not None:
                on_retry(n, e)
            time.sleep(device_retry_delay(n, base_delay_s))


def shard_snapshot(snap: DeviceSnapshot, mesh: Mesh) -> DeviceSnapshot:
    """Place a snapshot onto the mesh with node-axis sharding. Row counts are
    capacity-padded powers of two, so they divide evenly over the mesh."""
    shardings = snapshot_shardings(mesh)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, s), snap, shardings
    )


@functools.lru_cache(maxsize=8)
def make_sharded_schedule_batch(
    v_cap: int, mesh: Mesh, hard_pod_affinity_weight: float = 1.0
):
    """The lattice kernel jitted with explicit in/out shardings over `mesh`.

    Everything except the snapshot is replicated; results (chosen rows,
    scores, counts) are replicated so the host reads them without gathers.
    The resolvable [P, N] mask stays sharded on N (it is only consulted for
    failed pods, host-side, via per-row gathers).
    """
    base = make_schedule_batch_raw(v_cap, hard_pod_affinity_weight)
    rep = replicated(mesh)
    in_shardings = (
        snapshot_shardings(mesh),
        PodBatch(*([rep] * len(PodBatch._fields))),
        rep,
        rep,
    )
    out_shardings = BatchResult(
        chosen=rep,
        score=rep,
        feasible_count=rep,
        resolvable=NamedSharding(mesh, P(None, NODES_AXIS)),
    )
    return jax.jit(base, in_shardings=in_shardings, out_shardings=out_shardings)


@functools.lru_cache(maxsize=32)
def make_sharded_wave_kernel(
    v_cap: int,
    m_cand: int,
    n_waves: int,
    hard_pod_affinity_weight: float,
    mesh: Mesh,
    use_pallas_fit: bool = False,
    score_refresh: bool = True,
    rtc_shape: tuple = None,
    has_pinned: bool = True,
    pallas_interpret: bool = False,
    stratify: bool = False,
):
    """The PRODUCTION wave kernel (ops/wavelattice.py) jitted with the
    snapshot sharded over the mesh's node axis.

    Same program as make_wave_kernel_jit — the SPMD partitioner turns its
    node-axis math into local work + ICI collectives:
      * per-template filter masks / score matrices [TPL, N]: purely local
        (the Pallas fit mask, which the partitioner cannot split, runs
        per node shard under shard_map — make_wave_kernel's `mesh`),
      * topology-domain segment-sums [J, V]: local partial sums + psum
        (domain ids are global across shards),
      * top-M candidate selection per template: local top-k + cross-shard
        merge (all-gather of the [TPL, M] candidates),
      * wave-loop conflict resolution on the POD axis: replicated (small),
      * occupancy commit scatters (.at[rows].add): routed to the owning
        shard.
    The donated snapshot stays sharded across batches, so consecutive
    batches chain on-device exactly like the single-chip path. This is the
    multi-chip analogue of the reference's 16-way node fan-out
    (generic_scheduler.go:490) with ICI collectives instead of goroutines.
    """
    from ..ops.wavelattice import DEFAULT_RTC_SHAPE

    base = make_wave_kernel(
        v_cap,
        m_cand,
        n_waves,
        hard_pod_affinity_weight,
        use_pallas_fit,
        score_refresh,
        rtc_shape or DEFAULT_RTC_SHAPE,
        has_pinned,
        pallas_interpret,
        stratify,
        mesh,
    )
    rep = replicated(mesh)
    snap_sh = snapshot_shardings(mesh)
    in_shardings = (
        snap_sh,
        TemplateBatch(
            tpl=PodBatch(*([rep] * len(PodBatch._fields))),
            pod_tpl=rep,
            pod_valid=rep,
            pod_name_row=rep,
            pod_prio=rep,
            pod_band=rep,
        ),
        PairTable(*([rep] * len(PairTable._fields))),
        rep,
        rep,
    )
    out_shardings = (
        snap_sh,
        WaveResult(
            chosen=rep,
            placed=rep,
            deferred=rep,
            commit_wave=rep,
            feasible_count=rep,
            score=rep,
            resolvable_tpl=NamedSharding(mesh, P(None, NODES_AXIS)),
            feasible_tpl=NamedSharding(mesh, P(None, NODES_AXIS)),
        ),
    )
    return jax.jit(
        base,
        in_shardings=in_shardings,
        out_shardings=out_shardings,
        donate_argnums=(0,),
    )
