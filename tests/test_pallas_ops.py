"""Pallas fused fit+score kernel vs the jnp oracle (interpret mode on CPU;
the same program compiles via Mosaic on TPU — ops/pallas_ops.py)."""

import numpy as np
import pytest

import jax

from kubernetes_tpu.ops.pallas_ops import (
    BLOCK_N,
    R_PAD,
    fit_mask_least_alloc,
    fit_mask_least_alloc_reference,
    pad_inputs,
)


def _interpret() -> bool:
    return jax.devices()[0].platform != "tpu"


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pallas_fit_matches_reference(seed):
    rng = np.random.default_rng(seed)
    tpl, r, n = 16, 6, 700  # ragged n exercises padding
    req = rng.integers(0, 2000, size=(tpl, r)).astype(np.int32)
    req[rng.random((tpl, r)) < 0.3] = 0  # sparse requests
    alloc = rng.integers(1000, 64000, size=(n, r)).astype(np.int32)
    used = (alloc * rng.random((n, r)) * 0.9).astype(np.int32)
    free = alloc - used

    rq, fr, al, n_real = pad_inputs(req, free, alloc)
    mask, score = fit_mask_least_alloc(rq, fr, al, interpret=_interpret())
    ref_mask, ref_score = fit_mask_least_alloc_reference(rq, fr, al)
    np.testing.assert_array_equal(
        np.asarray(mask)[:, :n_real], np.asarray(ref_mask)[:, :n_real]
    )
    np.testing.assert_allclose(
        np.asarray(score)[:, :n_real],
        np.asarray(ref_score)[:, :n_real],
        rtol=1e-5,
    )


def test_pallas_fit_edge_semantics():
    """Zero-request templates fit everywhere with score 0; a request one
    unit over free fails; exact fit passes."""
    req = np.zeros((4, 3), np.int32)
    req[1, 0] = 100  # exact
    req[2, 0] = 101  # over by one
    req[3, 1] = 50
    free = np.zeros((BLOCK_N, 3), np.int32)
    free[:, 0] = 100
    free[:, 1] = 49  # template 3 can't fit anywhere
    alloc = np.full((BLOCK_N, 3), 200, np.int32)

    rq, fr, al, n = pad_inputs(req, free, alloc)
    mask, score = fit_mask_least_alloc(rq, fr, al, interpret=_interpret())
    mask = np.asarray(mask)[:, :n]
    score = np.asarray(score)[:, :n]
    assert mask[0].all() and (score[0] == 0).all()
    assert mask[1].all()
    assert not mask[2].any()
    assert not mask[3].any()
    # template 1's score: consumed the whole resource -> (100-100)/200 = 0
    np.testing.assert_allclose(score[1], 0.0, atol=1e-6)


def test_fit_mask_fallback_and_pallas_agree():
    rng = np.random.default_rng(7)
    import jax.numpy as jnp

    from kubernetes_tpu.ops.pallas_ops import fit_mask

    req = rng.integers(0, 500, size=(12, 6)).astype(np.int32)
    free = rng.integers(0, 600, size=(640, 6)).astype(np.int32)
    got = np.asarray(fit_mask(jnp.asarray(req), jnp.asarray(free), interpret=True))
    reqb = req[:, :, None]
    want = ((reqb == 0) | (reqb <= free.T[None])).all(axis=1)
    np.testing.assert_array_equal(got, want)
    # non-tiling N falls back to the jnp path, same result
    free2 = free[:93]
    got2 = np.asarray(fit_mask(jnp.asarray(req), jnp.asarray(free2), interpret=True))
    want2 = ((reqb == 0) | (reqb <= free2.T[None])).all(axis=1)
    np.testing.assert_array_equal(got2, want2)


def test_wave_kernel_pallas_fit_parity():
    """The full wave kernel must place identically with the Pallas fit
    mask and the XLA broadcast (same rng, same batch)."""
    import jax
    import jax.numpy as jnp

    from kubernetes_tpu.ops.encoding import SnapshotEncoder
    from kubernetes_tpu.ops.lattice import DEFAULT_WEIGHTS
    from kubernetes_tpu.ops.templates import TemplateCache, build_pair_table
    from kubernetes_tpu.ops.wavelattice import make_wave_kernel_jit
    from test_lattice_smoke import make_node, make_pod

    def run(use_pallas):
        enc = SnapshotEncoder()
        for i in range(6):
            enc.add_node(make_node(f"n{i}", cpu="4"))
        cache = TemplateCache(enc)
        pods = [make_pod(f"p{i}", cpu="500m") for i in range(10)]
        eb = cache.encode(pods, pad_to=16)
        ptab = build_pair_table(enc, eb.tpl_np, eb.num_templates)
        snap = enc.flush()
        kern = make_wave_kernel_jit(
            enc.cfg.v_cap, 64, 4, use_pallas_fit=use_pallas,
            pallas_interpret=_interpret(),
        )
        _, res = kern(
            snap, eb.batch, ptab, jnp.asarray(DEFAULT_WEIGHTS),
            jax.random.PRNGKey(3),
        )
        enc.invalidate_device()
        return (
            np.asarray(jax.device_get(res.placed)),
            np.asarray(jax.device_get(res.chosen)),
        )

    placed_a, chosen_a = run(False)
    placed_b, chosen_b = run(True)
    np.testing.assert_array_equal(placed_a, placed_b)
    np.testing.assert_array_equal(chosen_a, chosen_b)


def test_sharded_wave_kernel_with_pallas_fit(caplog):
    """use_pallas_fit composes with the sharded mesh path. Mosaic kernels
    are not partitioned automatically (on a TPU the lowering refuses a
    sharded pallas_call outright), so under a mesh the fit mask runs per
    node shard through shard_map — and must place exactly what the
    single-device kernel places. n_cap is sized so every shard still
    tiles: the Pallas branch itself runs per shard, not its jnp stand-in."""
    import logging

    import jax.numpy as jnp

    from kubernetes_tpu.ops.encoding import EncodingConfig, SnapshotEncoder
    from kubernetes_tpu.ops.lattice import DEFAULT_WEIGHTS
    from kubernetes_tpu.ops.pallas_ops import fit_mask_block
    from kubernetes_tpu.ops.templates import TemplateCache, build_pair_table
    from kubernetes_tpu.ops.wavelattice import make_wave_kernel_jit
    from kubernetes_tpu.parallel.mesh import (
        make_mesh,
        replicated,
        snapshot_shardings,
    )
    from kubernetes_tpu.parallel.sharded import make_sharded_wave_kernel
    from test_lattice_smoke import make_node, make_pod

    mesh = make_mesh()
    n_cap = 128 * mesh.size

    def run(sharded):
        enc = SnapshotEncoder(EncodingConfig(n_cap=n_cap))
        assert fit_mask_block(enc.cfg.r_cap, n_cap // mesh.size) == 128
        for i in range(40):
            enc.add_node(make_node(f"n{i}", cpu="8" if i % 3 else "2"))
        cache = TemplateCache(enc)
        pods = [
            make_pod(f"p{i}", cpu="1500m" if i % 2 else "500m")
            for i in range(24)
        ]
        eb = cache.encode(pods, pad_to=32)
        pt = build_pair_table(enc, eb.tpl_np, eb.num_templates)
        if sharded:
            enc.set_sharding(snapshot_shardings(mesh), replicated(mesh))
            kern = make_sharded_wave_kernel(
                enc.cfg.v_cap, 64, 4, 1.0, mesh, True, pallas_interpret=True
            )
        else:
            kern = make_wave_kernel_jit(
                enc.cfg.v_cap, 64, 4, use_pallas_fit=True,
                pallas_interpret=True,
            )
        snap = enc.flush()
        new_snap, res = kern(
            snap, eb.batch, pt, jnp.asarray(DEFAULT_WEIGHTS),
            jax.random.PRNGKey(0),
        )
        out = jax.device_get(
            (res.placed, res.chosen, res.feasible_tpl, new_snap.requested)
        )
        if sharded:
            assert len(new_snap.requested.sharding.device_set) == mesh.size
        enc.invalidate_device()
        return [np.asarray(x) for x in out]

    with caplog.at_level(logging.WARNING, "kubernetes_tpu.ops.pallas_ops"):
        single = run(False)
        sharded = run(True)
    assert not caplog.records, [r.getMessage() for r in caplog.records]
    assert single[0].sum() == 24
    for a, b in zip(single, sharded):
        np.testing.assert_array_equal(a, b)
