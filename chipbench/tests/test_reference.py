"""The plain reference against the program's own fp32 projector (CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny  # noqa: F401  (puts src/ and the checkout on sys.path)
from chipbench.lib import reference as ref

N, A = 16, 16


@pytest.fixture(scope="module")
def setup():
    from repro.core.geometry import ConeGeometry
    geo = ConeGeometry.nice(N)
    rgeo = ref.Geometry(geo.DSD, geo.DSO, geo.n_voxel, geo.s_voxel,
                        geo.n_detector, geo.s_detector)
    angles = ref.scan_angles(A)
    vol = jax.random.uniform(jax.random.PRNGKey(0), geo.n_voxel)
    proj = jax.random.uniform(jax.random.PRNGKey(1), (A,) + geo.n_detector)
    return geo, rgeo, angles, vol, proj


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def test_dominance_matches_program_at_45_degrees(setup):
    from repro.core.geometry import dominant_axis_mask
    angles = ref.scan_angles(512)       # holds 45, 135, 225, 315 degrees
    assert np.array_equal(ref.x_dominant(angles), dominant_axis_mask(angles))


def test_forward_matches_program_ref_projector(setup):
    from repro.core import projector
    from repro.core.geometry import dominant_axis_mask
    geo, rgeo, angles, vol, _ = setup
    want = projector.forward_project(vol, geo, jnp.asarray(angles),
                                     dominant_axis_mask(angles))
    assert rel(ref.fp_angles(vol, rgeo, angles), want) < 1e-5
    sub = angles[[1, 6, 11]]
    assert rel(ref.fp_angles(vol, rgeo, sub), want[np.array([1, 6, 11])]) < 1e-5


@pytest.mark.parametrize("box", [(3, 5, 4), (0, 0, 16), (10, 2, 4)])
def test_box_adjoint_matches_program_vjp(setup, box):
    from repro.core import projector
    geo, rgeo, angles, _, proj = setup
    full = projector.backproject_matched(proj, geo, jnp.asarray(angles))
    y0, x0, n = box
    assert rel(ref.bp_box(proj, rgeo, angles, box),
               full[:, y0:y0 + n, x0:x0 + n]) < 1e-5


@pytest.mark.parametrize("box", [(3, 5, 4), (0, 0, 16), (10, 2, 4)])
def test_voxel_box_matches_program_voxel_backprojection(setup, box):
    from repro.core import projector
    geo, rgeo, angles, _, proj = setup
    full = projector.backproject_voxel(proj, geo, jnp.asarray(angles),
                                       weight="pmatched")
    y0, x0, n = box
    assert rel(ref.bp_voxel_box(proj, rgeo, angles, box),
               full[:, y0:y0 + n, x0:x0 + n]) < 1e-5


def test_pair_is_adjoint(setup):
    _, rgeo, angles, vol, proj = setup
    ax = ref.fp_angles(vol, rgeo, angles)
    aty = ref.bp_box(proj, rgeo, angles, (0, 0, N))
    lhs = float(jnp.vdot(ax, proj))
    rhs = float(jnp.vdot(vol, aty))
    assert abs(lhs - rhs) / abs(lhs) < 1e-5


@pytest.mark.parametrize("which", ["fp_angles", "bp_box", "bp_voxel_box"])
def test_bf16_departs_from_f32(setup, which):
    _, rgeo, angles, vol, proj = setup
    if which == "fp_angles":
        run = lambda prec: ref.fp_angles(vol, rgeo, angles, prec)
    elif which == "bp_box":
        run = lambda prec: ref.bp_box(proj, rgeo, angles, (3, 5, 8), prec)
    else:
        run = lambda prec: ref.bp_voxel_box(proj, rgeo, angles, (3, 5, 8),
                                            prec)
    assert 1e-4 < rel(run("bf16"), run("f32")) < 1e-1
