"""Pallas TPU kernels for the lattice's hottest inner op.

The wave kernel re-evaluates resource fit for every (template, node) each
conflict-resolution wave (`fits_w` in wavelattice.py) and folds scores over
the resource axis — a [TPL, N, R] broadcast XLA materializes per wave.
This module provides the fused alternative: one Pallas pass per node tile
computes the fit mask AND the least-allocated score without materializing
the [TPL, N, R] intermediate in HBM (SURVEY §2's "XLA/Mosaic-compiled
Pallas kernels" for the batched filter/score path).

Layout: resources ride the SUBLANE axis (R padded to 8) and nodes the LANE
axis (tiles of 128), per the TPU tiling table in the pallas guide; the
template axis is a small VMEM-resident broadcast.

Two entry points:
  * `fit_mask` — the mask alone, in the snapshot's natural [N, R] layout;
    THIS is what the wave kernel calls (config `use_pallas_fit`).
  * `fit_mask_least_alloc` — the mask fused with a least-allocated-style
    score in one pass; standalone and oracle-tested, but NOT wired into
    the wave kernel, deliberately: round 4 removed the score stage's only
    [TPL, N, R] intermediate (wavelattice now computes the cpu/mem
    fraction planes directly as [TPL, N] ops), so there is nothing heavy
    left for a fused score to save — the mask (`fit_mask`, re-evaluated
    every wave) remains the one op worth a Pallas pass. Kept as the
    template for future fused score work (e.g. extended-resource-heavy
    clusters where R grows past the pad).

`fit_mask_least_alloc(req, free, alloc)`:
    req   [TPL, R] i32   per-template requests
    free  [R, N]  i32    allocatable - requested, transposed
    alloc [R, N]  i32    allocatable, transposed
  ->
    mask  [TPL, N] bool  all-resources fit (req==0 columns always fit)
    score [TPL, N] f32   mean over requested resources of (free-req)/alloc

`interpret=True` runs a kernel in the Pallas interpreter (bit-accurate,
slow, any backend) instead of compiling it with Mosaic; tests on the CPU
ask for it to pin the kernels against the jnp reference. It is always the
caller's statement, never inferred from the platform here.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger("kubernetes_tpu.ops.pallas_ops")

BLOCK_N = 512  # nodes per tile (lane axis: multiple of 128)
R_PAD = 8  # resource sublanes


def _kernel(req_ref, free_ref, alloc_ref, mask_ref, score_ref):
    req = req_ref[:]  # [TPL, R]
    free = free_ref[:]  # [R, BN]
    alloc = alloc_ref[:]  # [R, BN]
    reqb = req[:, :, None]  # [TPL, R, 1]
    fits = (reqb == 0) | (reqb <= free[None, :, :])  # [TPL, R, BN]
    mask_ref[:] = jnp.all(fits, axis=1)  # [TPL, BN]
    # least-allocated: mean over REQUESTED resources of (free-req)/alloc
    a = jnp.maximum(alloc[None, :, :], 1).astype(jnp.float32)
    frac = (free[None, :, :] - reqb).astype(jnp.float32) / a
    w = (reqb > 0).astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(w, axis=1), 1.0)  # [TPL, BN]
    score_ref[:] = jnp.sum(frac * w, axis=1) / denom


@functools.partial(jax.jit, static_argnames=("interpret",))
def fit_mask_least_alloc(req, free, alloc, interpret: bool = False):
    """See module docstring. N must be a multiple of BLOCK_N (the callers'
    node capacity n_cap is a power of two >= 128)."""
    from jax.experimental import pallas as pl

    tpl = req.shape[0]
    r, n = free.shape
    assert r == R_PAD and req.shape[1] == R_PAD, (req.shape, free.shape)
    assert n % BLOCK_N == 0, n
    grid = (n // BLOCK_N,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tpl, R_PAD), lambda i: (0, 0)),
            pl.BlockSpec((R_PAD, BLOCK_N), lambda i: (0, i)),
            pl.BlockSpec((R_PAD, BLOCK_N), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((tpl, BLOCK_N), lambda i: (0, i)),
            pl.BlockSpec((tpl, BLOCK_N), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tpl, n), jnp.bool_),
            jax.ShapeDtypeStruct((tpl, n), jnp.float32),
        ],
        interpret=interpret,
    )(req, free, alloc)


def fit_mask_least_alloc_reference(req, free, alloc):
    """Pure-jnp oracle (what XLA runs today): identical math, materialized
    [TPL, R, N] intermediate."""
    reqb = jnp.asarray(req)[:, :, None]
    free = jnp.asarray(free)[None, :, :]
    alloc = jnp.asarray(alloc)[None, :, :]
    mask = jnp.all((reqb == 0) | (reqb <= free), axis=1)
    a = jnp.maximum(alloc, 1).astype(jnp.float32)
    frac = (free - reqb).astype(jnp.float32) / a
    w = (reqb > 0).astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(w, axis=1), 1.0)
    score = jnp.sum(frac * w, axis=1) / denom
    return mask, score


def _mask_kernel(req_ref, free_ref, mask_ref):
    reqb = req_ref[:][:, :, None]  # [TPL, R, 1]
    fits = (reqb == 0) | (reqb <= free_ref[:][None, :, :])
    mask_ref[:] = jnp.all(fits, axis=1)


def fit_mask_block(r: int, n: int) -> Optional[int]:
    """Node-tile width `fit_mask` uses for an [n, r] free matrix, or None
    when the shapes don't tile (R > 8 after extended-resource growth, or
    N not 128-divisible) and it takes the jnp broadcast instead."""
    if r > R_PAD:
        return None
    return next((b for b in (512, 256, 128) if n % b == 0), None)


def fit_mask(req, free, interpret: bool = False):
    """[TPL, N] resource-fit mask, fused over node tiles (the wave
    kernel's `fits0`/`fits_w` without the [TPL, N, R] HBM intermediate).
    req [TPL, R] i32, free [N, R] i32 (natural layout; transposed and
    padded here at trace time, static shapes). Shapes that don't tile
    (`fit_mask_block`) take the jnp broadcast, and say so in the log."""
    from jax.experimental import pallas as pl

    tpl, r = req.shape
    n = free.shape[0]
    block = fit_mask_block(r, n)
    if block is None:
        logger.warning(
            "pallas fit_mask: free [%d, %d] does not tile; this trace "
            "uses the jnp broadcast", n, r,
        )
        reqb = req[:, :, None]
        return jnp.all((reqb == 0) | (reqb <= free.T[None]), axis=1)
    tpl_pad = max(8, tpl)
    rq = jnp.zeros((tpl_pad, R_PAD), jnp.int32).at[:tpl, :r].set(req)
    fr = jnp.zeros((R_PAD, n), jnp.int32).at[:r, :].set(free.T)
    out = pl.pallas_call(
        _mask_kernel,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((tpl_pad, R_PAD), lambda i: (0, 0)),
            pl.BlockSpec((R_PAD, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((tpl_pad, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((tpl_pad, n), jnp.bool_),
        interpret=interpret,
    )(rq, fr)
    return out[:tpl]


def pad_inputs(req: np.ndarray, free: np.ndarray, alloc: np.ndarray):
    """Host helper: pad (req [TPL, R], free/alloc [N, R]) to the kernel's
    layout ([TPL, 8], [8, N'] transposed, N' multiple of BLOCK_N)."""
    tpl, r = req.shape
    n = free.shape[0]
    n_pad = ((n + BLOCK_N - 1) // BLOCK_N) * BLOCK_N
    rq = np.zeros((tpl, R_PAD), np.int32)
    rq[:, :r] = req
    fr = np.zeros((R_PAD, n_pad), np.int32)
    fr[:r, :n] = free.T
    al = np.zeros((R_PAD, n_pad), np.int32)
    al[:r, :n] = alloc.T
    return rq, fr, al, n
