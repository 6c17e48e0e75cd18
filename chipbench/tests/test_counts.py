"""Kernel work counts depend on the geometry and the angles only."""

import inspect

import pytest

import tiny  # noqa: F401
from chipbench.lib import harness, reference as ref


@pytest.fixture(scope="module")
def cell():
    c = tiny.load_cell("cbct512.cgls")
    return ref.Geometry.from_config(c.config), ref.scan_angles(512)


def test_fp_counts_at_512(cell):
    geo, angles = cell
    k = harness.kernel_table()
    flops, nbytes = k["fp_ray"].counts(geo, angles)
    # 512 angles x 512^2 rays x 512 planes x 4 taps x (mul + add)
    assert flops == 8 * 512 ** 4
    assert nbytes == 4 * (512 ** 3 + 512 ** 3)
    assert k["bp_matched"].counts(geo, angles) == (flops, nbytes)


def test_bp_voxel_counts_at_512(cell):
    geo, angles = cell
    flops, nbytes = harness.kernel_table()["bp_voxel"].counts(geo, angles)
    # 512^3 voxels x 512 angles x (4 taps x (mul + add) + weight x accumulate)
    assert flops == 10 * 512 ** 4
    assert nbytes == 4 * (512 ** 3 + 512 ** 3)


def test_split_over_devices(cell):
    geo, angles = cell
    fp = harness.kernel_table()["fp_ray"]
    f1, b1 = fp.counts(geo, angles)
    f4, b4 = fp.counts(geo, angles, 4)
    assert f4 * 4 == f1
    assert b4 == 4 * (512 ** 3 + 512 ** 3 / 4)


def test_counts_ignore_the_block_config(cell, monkeypatch):
    """Counting the same work under two block configurations of the
    program's kernels gives the same numbers: nothing reads the blocks."""
    from repro.core.backend import get_backend
    from repro.core.geometry import ConeGeometry
    from repro.kernels import autotune
    geo, angles = cell
    pgeo = ConeGeometry.nice(512)
    kernels = harness.kernel_table()
    for k in kernels.values():
        params = inspect.signature(k.counts).parameters
        assert set(params) == {"geo", "angles", "n_devices"}
    seen = []
    for blocks in ({"slab_planes": 4, "angle_block": 24},
                   {"slab_planes": 16, "angle_block": 4}):
        monkeypatch.setattr(autotune, "get_blocks",
                            lambda kind, g, planes=None, interpret=None,
                            _b=blocks: dict(_b))
        cfg = get_backend("pallas").kernel_config(pgeo, planes=512)
        assert cfg["fp.slab_planes"] == blocks["slab_planes"]
        seen.append({n: k.counts(geo, angles) for n, k in kernels.items()})
    assert seen[0] == seen[1]


def test_trace_names_are_declared():
    for name, k in harness.kernel_table().items():
        assert k.TRACE_NAMES and all(isinstance(n, str) for n in k.TRACE_NAMES)
