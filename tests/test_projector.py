"""Projector correctness: analytic oracle, interp-vs-joseph agreement,
adjoint property, geometry edge cases."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
pytest.importorskip("hypothesis")
from hypothesis import example, given, settings, strategies as st

from repro.core import phantoms
from repro.core.geometry import ConeGeometry, circular_angles, \
    dominant_axis_mask
from repro.core.projector import (backproject_matched, backproject_voxel,
                                  forward_project, forward_project_interp)


GEO32 = ConeGeometry.nice(32)
ANGLES8 = circular_angles(8)


def test_joseph_matches_analytic_sphere():
    vol = jnp.asarray(phantoms.sphere(GEO32))
    got = forward_project(vol, GEO32, ANGLES8)
    want = phantoms.sphere_projection_analytic(GEO32, ANGLES8)
    rel = np.linalg.norm(np.asarray(got) - want) / np.linalg.norm(want)
    assert rel < 0.08, rel


def test_joseph_matches_interp():
    vol = jnp.asarray(phantoms.sphere(GEO32))
    pj = forward_project(vol, GEO32, ANGLES8)
    pi = forward_project_interp(vol, GEO32, jnp.asarray(ANGLES8))
    rel = float(jnp.linalg.norm(pj - pi) / jnp.linalg.norm(pi))
    assert rel < 0.03, rel


def test_shepp_logan_analytic():
    vol = jnp.asarray(phantoms.shepp_logan(GEO32))
    got = forward_project(vol, GEO32, ANGLES8)
    want = phantoms.shepp_logan_projection_analytic(GEO32, ANGLES8)
    rel = np.linalg.norm(np.asarray(got) - want) / np.linalg.norm(want)
    assert rel < 0.25, rel


@pytest.mark.parametrize("geo", [GEO32, ConeGeometry.nice(33),
                                 ConeGeometry.nice(32).with_voxels((20, 32, 24))])
def test_shepp_logan_matches_3d_rasterisation(geo):
    """The separable rasteriser gives exactly the voxels of the direct
    3-D quadric test over the full meshgrid (the same float64 sums)."""
    zz, yy, xx = np.meshgrid(geo.voxel_centers_1d(0), geo.voxel_centers_1d(1),
                             geo.voxel_centers_1d(2), indexing="ij")
    half = np.array([geo.s_voxel[2], geo.s_voxel[1], geo.s_voxel[0]]) / 2.0
    want = np.zeros(geo.n_voxel, np.float32)
    for value, (cx, cy, cz), (ax, ay, az), phi_deg in phantoms.SHEPP_LIKE:
        c, s = np.cos(np.deg2rad(phi_deg)), np.sin(np.deg2rad(phi_deg))
        xn, yn, zn = xx / half[0] - cx, yy / half[1] - cy, zz / half[2] - cz
        xr, yr = c * xn + s * yn, -s * xn + c * yn
        inside = (xr / ax) ** 2 + (yr / ay) ** 2 + (zn / az) ** 2 <= 1.0
        want += value * inside.astype(np.float32)
    np.testing.assert_array_equal(phantoms.shepp_logan(geo), want)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000))
@example(5653)       # <Ax, y> nearly cancels: a relative-to-|lhs| check fails
def test_adjoint_property(seed):
    """<Ax, y> == <x, A^T y> for the matched pair (hypothesis seeds).

    The defect is normalised by ``|Ax| |y|``, the scale of the fp32
    rounding in both inner products: for random ``x, y`` the inner product
    itself can cancel to near zero (seed 5653)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, GEO32.n_voxel)
    y = jax.random.normal(k2, (len(ANGLES8),) + GEO32.n_detector)
    ax = forward_project(x, GEO32, ANGLES8)
    lhs = float(jnp.vdot(ax, y))
    rhs = float(jnp.vdot(x, backproject_matched(y, GEO32,
                                                jnp.asarray(ANGLES8))))
    scale = float(jnp.linalg.norm(ax) * jnp.linalg.norm(y))
    assert abs(lhs - rhs) / scale < 1e-4


def test_fp_linearity():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(k1, GEO32.n_voxel)
    b = jax.random.normal(k2, GEO32.n_voxel)
    pab = forward_project(a + 2.0 * b, GEO32, ANGLES8)
    pa = forward_project(a, GEO32, ANGLES8)
    pb = forward_project(b, GEO32, ANGLES8)
    np.testing.assert_allclose(pab, pa + 2.0 * pb, rtol=1e-3, atol=1e-3)


def test_bp_additivity_over_angles():
    """BP is additive over angle subsets (the streaming invariant)."""
    proj = jax.random.normal(jax.random.PRNGKey(1),
                             (8,) + GEO32.n_detector)
    angles = jnp.asarray(ANGLES8)
    full = backproject_voxel(proj, GEO32, angles)
    parts = (backproject_voxel(proj[:4], GEO32, angles[:4])
             + backproject_voxel(proj[4:], GEO32, angles[4:]))
    np.testing.assert_allclose(full, parts, rtol=1e-4, atol=1e-4)


def test_offset_detector():
    geo = ConeGeometry.nice(32)
    import dataclasses
    geo = dataclasses.replace(geo, off_detector=(6.0, -8.0))
    vol = jnp.asarray(phantoms.sphere(geo))
    got = forward_project(vol, geo, ANGLES8)
    want = phantoms.sphere_projection_analytic(geo, ANGLES8)
    rel = np.linalg.norm(np.asarray(got) - want) / np.linalg.norm(want)
    assert rel < 0.1, rel


def test_fan_angle_guard():
    with pytest.raises(ValueError):
        ConeGeometry(DSD=500.0, DSO=400.0, s_detector=(2000.0, 2000.0))


def test_dominant_axis_mask():
    m = dominant_axis_mask(np.asarray([0.0, np.pi / 2, np.pi / 4 + 0.01]))
    assert m.tolist() == [True, False, False]
