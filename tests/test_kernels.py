"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Every Pallas kernel is validated over a sweep of shapes and dtypes; the
fp/bp kernels also over geometry variations.
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.geometry import ConeGeometry, circular_angles, \
    dominant_axis_mask
from repro.core.projector import forward_project_joseph
from repro.kernels import ref
from repro.kernels.bp_matched import bp_matched_pallas
from repro.kernels.bp_voxel import bp_voxel_pallas
from repro.kernels.fp_ray import (ROWS, angle_constants, edge_spans,
                                  fp_ray_pallas, lane_block, plane_centers,
                                  ray_fk, ray_frame, ray_plane, ray_rows,
                                  round_up, tile_windows, z_chunks_per_tile,
                                  z_origin)
from repro.kernels.tv_grad import tv_grad_pallas
from repro.kernels.flash_attention import flash_attention


def _xdom_angles(n):
    a = circular_angles(n)
    return a[np.nonzero(dominant_axis_mask(a))[0]]


@pytest.mark.parametrize("n,slab", [(16, 4), (32, 8), (32, 16), (48, 8)])
def test_fp_ray_shapes(n, slab):
    geo = ConeGeometry.nice(n)
    ax = _xdom_angles(8)
    vol = jax.random.normal(jax.random.PRNGKey(n), geo.n_voxel, jnp.float32)
    got = fp_ray_pallas(vol, geo, ax, slab_planes=slab, interpret=True)
    want = ref.fp_ray_ref(vol, geo, ax)
    # atol covers volume-boundary rays (one interpolation tap outside)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=5e-3)


@pytest.mark.parametrize("nv,nu", [(16, 32), (32, 16)])
def test_fp_ray_rect_detector(nv, nu):
    geo = ConeGeometry.nice(32, n_detector=(nv, nu))
    ax = _xdom_angles(4)
    vol = jax.random.normal(jax.random.PRNGKey(1), geo.n_voxel, jnp.float32)
    got = fp_ray_pallas(vol, geo, ax, slab_planes=8, interpret=True)
    want = ref.fp_ray_ref(vol, geo, ax)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-3)


@pytest.mark.parametrize("n,zb,ac", [(16, 4, 4), (32, 8, 4), (32, 16, 8)])
@pytest.mark.parametrize("weight", ["fdk", "pmatched", "none"])
def test_bp_voxel_shapes(n, zb, ac, weight):
    geo = ConeGeometry.nice(n)
    angles = circular_angles(8)
    proj = jax.random.normal(jax.random.PRNGKey(n), (8,) + geo.n_detector,
                             jnp.float32)
    got = bp_voxel_pallas(proj, geo, angles, z_block=zb, angle_chunk=ac,
                          weight=weight, interpret=True)
    want = ref.bp_voxel_ref(proj, geo, angles, weight=weight)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-3)


@pytest.mark.parametrize("shape", [(16, 16, 16), (32, 16, 24), (48, 8, 8)])
@pytest.mark.parametrize("zb", [4, 8])
def test_tv_grad_shapes(shape, zb):
    if shape[0] % zb:
        pytest.skip("nz % zb != 0")
    vol = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
    got = tv_grad_pallas(vol, z_block=zb, interpret=True)
    want = ref.tv_grad_ref(vol)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (1, 4, 4, 128, 32), (2, 8, 2, 256, 64), (1, 8, 1, 128, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_shapes(b, hq, hkv, s, d, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_kv=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window,softcap", [(64, None), (None, 30.0),
                                            (64, 30.0)])
def test_flash_attention_window_softcap(window, softcap):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 4, 256, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 2, 256, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 2, 256, 32), jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=window,
                          softcap=softcap, block_q=64, block_kv=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window,
                                   softcap=softcap)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 4, 128, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 4, 128, 64), jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.integers(1, 4))
def test_fp_slab_split_matches_kernel(seed, n_splits):
    """Hypothesis: the Pallas FP kernel's grid accumulation over marching
    slabs equals the oracle regardless of slab count."""
    n = 24
    geo = ConeGeometry.nice(n)
    ax = _xdom_angles(4)
    slab = n // n_splits if n % n_splits == 0 else n
    if n % slab:
        slab = n
    vol = jax.random.normal(jax.random.PRNGKey(seed), geo.n_voxel,
                            jnp.float32)
    got = fp_ray_pallas(vol, geo, ax, slab_planes=slab, interpret=True)
    want = ref.fp_ray_ref(vol, geo, ax)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-3)


# --------------------------------------------------------------------------
# ray kernels: per-lane-block z windows from the scalar geometry
# --------------------------------------------------------------------------

def _replay_windows(geo, angles, z0=0, nz_slab=None, planes=None):
    """The ray kernels' per-ray z rows and per-block windows at every
    (angle, plane, detector tile), replayed with the kernels' own helpers
    outside Pallas: fk (A, P, T, 8, Nu), valid (A, P, T, 1, Nu) and the
    windows' c_lo, n (A, P, T, blocks)."""
    n_rows = round_up(geo.n_voxel[0] if nz_slab is None else nz_slab, ROWS)
    consts = angle_constants(geo, angles).reshape(-1)
    xs = plane_centers(geo, geo.n_voxel[2]) if planes is None else planes
    n_vt = round_up(geo.n_detector[0], ROWS) // ROWS

    def one(a, x, t):
        fr = ray_frame(consts, a, geo)
        s_par, valid, _ = ray_plane(fr, x, geo)
        fk = ray_fk(fr, s_par, ray_rows(fr, t, geo), z0, geo)
        wins = tile_windows(edge_spans(fr, x), z_origin(fr.sz, z0, geo), t,
                            fr.sz, n_rows // ROWS, geo)
        return (fk, valid, jnp.stack([c for c, _ in wins]),
                jnp.stack([n for _, n in wins]))
    f = jax.vmap(jax.vmap(jax.vmap(one, (None, None, 0)), (None, 0, None)),
                 (0, None, None))
    out = jax.jit(f)(jnp.arange(len(angles)), jnp.asarray(xs),
                     jnp.arange(n_vt))
    return [np.asarray(o) for o in out]


def _tile_mask(geo, fk, valid):
    """Rays the kernels count: forward (valid) and on the detector."""
    nv = geo.n_detector[0]
    row = np.arange(fk.shape[2])[:, None] * ROWS + np.arange(ROWS)
    return (valid > 0) & (row < nv)[:, :, None]


def _old_rule_chunks(fk, mask, n_rows):
    """Chunks per tile under one window per tile from the masked min/max
    of fk (the rule ``bp_voxel`` keeps, ``chunk_window``)."""
    lo = np.where(mask, fk, np.inf).min(axis=(-2, -1))
    hi = np.where(mask, fk, -np.inf).max(axis=(-2, -1))
    lim = n_rows + 2 * ROWS
    c_lo = np.clip(np.floor(np.clip(lo, -lim, lim)), 0, n_rows) // ROWS
    c_hi = (np.clip(np.floor(np.clip(hi, -lim, lim)) + 2, 0, n_rows)
            + ROWS - 1) // ROWS
    return c_hi - c_lo


def _assert_windows_cover_taps(geo, angles, z0=0, nz_slab=None):
    """Every tap with nonzero weight of every counted ray lies in its lane
    block's window, and every window lies in the slab."""
    n_rows = round_up(geo.n_voxel[0] if nz_slab is None else nz_slab, ROWS)
    fk, valid, c_lo, n = _replay_windows(geo, angles, z0, nz_slab)
    mask = _tile_mask(geo, fk, valid)
    assert mask.any()
    assert (n >= 0).all() and (c_lo + n <= n_rows // ROWS).all()
    bw = lane_block(geo.n_detector[1])
    block = np.arange(geo.n_detector[1]) // bw
    lo = np.repeat(c_lo, bw, axis=-1)[..., None, :]     # per column
    hi = lo + np.repeat(n, bw, axis=-1)[..., None, :]
    taps = 0
    for k in (np.floor(fk), np.floor(fk) + 1):
        w = (np.maximum(0.0, 1.0 - np.abs(fk - k)) > 0) & mask \
            & (k >= 0) & (k < n_rows)
        chunk = k // ROWS
        bad = w & ((chunk < lo) | (chunk >= hi))
        assert not bad.any(), (
            f"{bad.sum()} taps outside their block's window, e.g. at "
            f"{np.argwhere(bad)[0]} (blocks {block.max() + 1})")
        taps += w.sum()
    assert taps > 0
    return fk, mask, n


def _xdom(angles):
    return angles[np.nonzero(dominant_axis_mask(angles))[0]]


def _ydom_rotated(angles):
    # the kernels see a y-dominant angle as theta - 90 deg of the
    # rotated scene (repro.core.backend)
    return angles[np.nonzero(~dominant_axis_mask(angles))[0]] - np.pi / 2


def _tall(geo):
    """``geo`` with a square detector face: few rows that still span the
    volume's height, so rays pass above and below every slab."""
    return dataclasses.replace(geo, s_detector=(409.6, 409.6))


_WIDE = _tall(ConeGeometry.nice(24, n_detector=(24, 256)))
_COVER_CASES = {
    "oblique": (_WIDE, _xdom(circular_angles(32)), 0, None),
    "45deg": (_WIDE, np.float32([np.pi / 4, 3 * np.pi / 4,
                                 -np.pi / 4, 5 * np.pi / 4]), 0, None),
    "ydom_rotated": (_WIDE, _ydom_rotated(circular_angles(32)), 0, None),
    "off_centre": (dataclasses.replace(
        _tall(ConeGeometry.nice(24, n_detector=(20, 256))),
        off_detector=(17.0, -23.0), off_origin=(9.0, 4.0, -6.0)),
        _xdom(circular_angles(16)), 0, None),
    "rect_tall": (ConeGeometry.nice(32, n_detector=(40, 128)),
                  _xdom(circular_angles(16)), 0, None),
    "rect_wide": (_tall(ConeGeometry.nice(24, n_detector=(16, 384))),
                  _xdom(circular_angles(16)), 0, None),
    "wide_fan": (ConeGeometry(n_voxel=(32, 16, 16), n_detector=(64, 256),
                              s_detector=(409.6, 2000.0)),
                 _xdom(circular_angles(16)), 0, None),
    "one_block": (ConeGeometry.nice(24, n_detector=(24, 48)),
                  _xdom(circular_angles(16)), 0, None),
    "slab_mid": (_WIDE, _xdom(circular_angles(16)), 8, 8),
    "slab_top": (_WIDE, _xdom(circular_angles(16)), 16, 8),
    "slab_odd": (_WIDE, _xdom(circular_angles(16)), 5, 11),
}


@pytest.mark.parametrize("case", sorted(_COVER_CASES))
def test_z_windows_cover_every_tap(case):
    geo, angles, z0, nz_slab = _COVER_CASES[case]
    _assert_windows_cover_taps(geo, angles, z0, nz_slab)


def test_z_chunks_per_tile_is_the_kernels_trip_count():
    """The host counter reads the kernels' own rule: its mean equals the
    replayed windows' mean trip count (max over lane blocks)."""
    geo, angles = _WIDE, circular_angles(16)
    rot = np.where(dominant_axis_mask(angles), angles, angles - np.pi / 2)
    _, _, _, n = _replay_windows(geo, rot.astype(np.float32))
    want = n.max(axis=-1).mean()
    assert abs(z_chunks_per_tile(geo, angles) - want) < 1e-2 * want


def test_z_chunks_per_tile_cbct512():
    """At the benchmark's geometry the per-block windows sweep at most 3
    chunks per tile on average, fewer than one window per tile."""
    geo, angles = ConeGeometry.nice(512), circular_angles(512)
    got = z_chunks_per_tile(geo, angles)
    assert got <= 3.0
    # the tile-wide rule, replayed on a subsample of angles and planes
    sub = _xdom(angles)[::64]
    xs = plane_centers(geo, 512)[::16]
    fk, valid, _, n = _replay_windows(geo, sub, planes=xs)
    old = _old_rule_chunks(fk, _tile_mask(geo, fk, valid), 512).mean()
    assert n.max(axis=-1).mean() <= 3.0 < old


_BLOCK_CASES = {
    "full": (_tall(ConeGeometry.nice(16, n_detector=(16, 256))), 0, None),
    # tiles at the slab's top edge where one block's window is cut short
    # by the slab and the other block's is not: the short block's extra
    # steps must add nothing
    "slab": (_tall(ConeGeometry(n_voxel=(32, 16, 16), n_detector=(64, 256))),
             8, 16),
}


@pytest.mark.parametrize("case", sorted(_BLOCK_CASES))
def test_ray_kernels_two_lane_blocks_parity_and_adjoint(case):
    """A 256-column detector runs two lane blocks with their own windows:
    FP and matched BP agree with the ref projector and its vjp, and the
    pair is an exact adjoint."""
    geo, z0, nz_slab = _BLOCK_CASES[case]
    nz = geo.n_voxel[0] if nz_slab is None else nz_slab
    ax = _xdom(circular_angles(16))
    kv, kp = jax.random.split(jax.random.PRNGKey(7))
    vol = jax.random.normal(kv, (nz,) + geo.n_voxel[1:], jnp.float32)
    proj = jax.random.normal(kp, (len(ax),) + geo.n_detector, jnp.float32)

    def fp_ref(v):
        return forward_project_joseph(v, geo, jnp.asarray(ax), z0=z0)
    got = fp_ray_pallas(vol, geo, ax, slab_planes=8, z0=z0)
    np.testing.assert_allclose(got, fp_ref(vol), rtol=2e-4, atol=5e-3)
    bp = bp_matched_pallas(proj, geo, ax, slab_planes=8, z0=z0,
                           z_planes=nz)
    _, vjp = jax.vjp(fp_ref, vol)
    np.testing.assert_allclose(bp, vjp(proj)[0], rtol=2e-4, atol=5e-3)
    lhs = np.vdot(np.asarray(got, np.float64), np.asarray(proj, np.float64))
    rhs = np.vdot(np.asarray(vol, np.float64), np.asarray(bp, np.float64))
    assert abs(lhs - rhs) < 1e-4 * max(abs(lhs), abs(rhs))
