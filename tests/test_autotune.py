"""Measured block-size autotuner: heuristic fallback, memoization, JSON
cache round-trip, the heuristic floor guarantee, and the dispatch/backend
integration (tuned blocks land in kernel_config and dispatch keys).

Measurement itself is monkeypatched to a deterministic cost model in most
tests (tune() would otherwise compile kernels per candidate); one smoke
test runs the real path on a tiny geometry.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backend import get_backend
from repro.core.geometry import ConeGeometry
from repro.kernels import autotune

GEO = ConeGeometry.nice(16)
GEO_ODD = ConeGeometry.nice(16).with_voxels((20, 25, 25))


@pytest.fixture(autouse=True)
def _reset_autotune(monkeypatch):
    """Isolate every test from env state and the process memo table."""
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    autotune.enable(None)
    autotune.clear()
    yield
    autotune.enable(None)
    autotune.clear()


@pytest.fixture
def fake_measure(monkeypatch):
    """Deterministic cost model: bigger slab/z blocks are 'faster', so the
    tuner must pick the largest candidate; records every call."""
    calls = []

    def _fake(kind, geo, planes, cfg, interpret, repeats):
        calls.append((kind, dict(cfg)))
        return 1.0 / sum(cfg.values())

    monkeypatch.setattr(autotune, "_measure", _fake)
    return calls


# --------------------------------------------------------------------------
# heuristic (pad-to-divisor escape hatch)
# --------------------------------------------------------------------------

def test_pick_block_divisor_and_pad_fallback():
    assert autotune.pick_block(32, 16) == 16     # exact divisor
    assert autotune.pick_block(18, 16) == 9      # divisor >= preferred/2
    assert autotune.pick_block(17, 16) == 16     # prime: pad, not block=1
    assert autotune.pick_block(25, 16) == 16     # 5 < 8: pad beats tiny
    assert autotune.pick_block(4, 16) == 4       # axis smaller than block


def test_heuristic_blocks_per_kind():
    # small geometry: VMEM never binds, the traffic block grows to its cap
    assert autotune.heuristic_blocks("fp", GEO) == \
        {"slab_planes": 16, "angle_block": 64}
    assert autotune.heuristic_blocks("bp_matched", GEO) == \
        {"slab_planes": 16, "angle_block": 8}
    assert autotune.heuristic_blocks("bp", GEO, planes=8) == \
        {"z_block": 8, "angle_chunk": 8, "y_block": 16}
    # prime x axis: the escape hatch keeps the preferred slab width
    assert autotune.heuristic_blocks(
        "fp", GEO.with_voxels((16, 16, 17)))["slab_planes"] == 16
    # N=512: every kernel's blocks are capped by the VMEM model
    big = ConeGeometry.nice(512)
    for kind in ("fp", "bp_matched", "bp"):
        cfg = autotune.heuristic_blocks(kind, big)
        assert autotune.fits(kind, big, cfg), (kind, cfg)
    assert autotune.heuristic_blocks("fp", big)["slab_planes"] < 16
    with pytest.raises(ValueError, match="unknown autotune kind"):
        autotune.heuristic_blocks("conv", GEO)


def test_disabled_returns_heuristic_and_never_measures(fake_measure):
    assert not autotune.enabled()
    got = autotune.get_blocks("fp", GEO)
    assert got == autotune.heuristic_blocks("fp", GEO)
    assert fake_measure == []          # no measurement when disabled
    assert autotune.table() == {}


def test_env_var_enables():
    os.environ["REPRO_AUTOTUNE"] = "1"
    assert autotune.enabled()
    os.environ["REPRO_AUTOTUNE"] = "0"
    assert not autotune.enabled()
    autotune.enable(True)              # explicit override beats env
    assert autotune.enabled()


# --------------------------------------------------------------------------
# tuning: memoization, floor guarantee, fingerprint
# --------------------------------------------------------------------------

def test_tune_memoizes_per_shape_class(fake_measure):
    autotune.enable(True)
    first = autotune.get_blocks("fp", GEO)
    n_measured = len(fake_measure)
    assert n_measured >= 1
    again = autotune.get_blocks("fp", GEO)
    assert again == first
    assert len(fake_measure) == n_measured, "cache hit re-measured"
    # same *shape*, different physical scale -> same memo entry
    import dataclasses
    geo2 = dataclasses.replace(GEO, DSO=900.0)
    assert autotune.get_blocks("fp", geo2) == first
    assert len(fake_measure) == n_measured


def test_tuned_blocks_never_below_heuristic(fake_measure):
    """Candidates are floored at the heuristic, so the winner is >= it
    even when the fake cost model is inverted to prefer small blocks."""
    autotune.enable(True)

    def prefer_small(kind, geo, planes, cfg, interpret, repeats):
        return float(sum(cfg.values()))          # smaller == faster

    import unittest.mock as mock
    with mock.patch.object(autotune, "_measure", prefer_small):
        got = autotune.get_blocks("bp", GEO_ODD, planes=20)
    heur = autotune.heuristic_blocks("bp", GEO_ODD, planes=20)
    for k, v in heur.items():
        assert got[k] >= v, f"{k}: tuned {got[k]} < heuristic {v}"


def test_stale_cache_entry_clamped_to_heuristic(tmp_path, fake_measure):
    """A foreign/stale persisted table with a too-small block must be
    clamped up to the heuristic, never trusted below it."""
    key = autotune.shape_class("fp", GEO, None)
    path = tmp_path / "blocks.json"
    path.write_text(json.dumps({
        "version": 1,
        "entries": {autotune._key_str(key): {"slab_planes": 1}},
    }))
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(path)
    autotune.enable(True)
    got = autotune.get_blocks("fp", GEO)
    assert got["slab_planes"] == 16            # clamped, not 1
    assert fake_measure == []                  # hit: no re-measure


def test_fingerprint_bumps_on_mutations(fake_measure):
    fp0 = autotune.fingerprint()
    autotune.enable(True)
    assert autotune.fingerprint() > fp0        # enable() bumps
    fp1 = autotune.fingerprint()
    autotune.get_blocks("fp", GEO)             # first tune bumps
    assert autotune.fingerprint() > fp1
    fp2 = autotune.fingerprint()
    autotune.get_blocks("fp", GEO)             # memo hit: no bump
    assert autotune.fingerprint() == fp2
    autotune.clear()
    assert autotune.fingerprint() > fp2


def test_cache_roundtrip(tmp_path, fake_measure):
    autotune.enable(True)
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(tmp_path / "blocks.json")
    tuned = autotune.warm(GEO, planes=16)
    assert set(tuned) == {"fp", "bp", "bp_matched"}
    n_measured = len(fake_measure)
    before = autotune.table()
    assert os.path.exists(os.environ["REPRO_AUTOTUNE_CACHE"])

    # a 'new process': empty table, same cache path -> loads, no measuring
    autotune.clear()
    got = autotune.get_blocks("fp", GEO, planes=16)
    assert got == tuned["fp"]
    assert len(fake_measure) == n_measured, "persisted hit re-measured"
    assert autotune.table() == before


def test_load_rejects_garbage(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text("not json {")
    assert autotune.load(str(p)) == 0
    p.write_text(json.dumps({"version": 99, "entries": {}}))
    assert autotune.load(str(p)) == 0
    p.write_text(json.dumps({"version": 1,
                             "entries": {"mangled-key": {"z_block": 4},
                                         "fp|cpu|16,16,16|16,16|None":
                                             {"slab_planes": 32}}}))
    assert autotune.load(str(p)) == 1          # good row taken, bad skipped


# --------------------------------------------------------------------------
# backend integration
# --------------------------------------------------------------------------

def test_backend_kernel_config_reports_blocks():
    bk = get_backend("pallas")
    cfg = bk.kernel_config(GEO, planes=16)
    assert cfg["fp.slab_planes"] == 16
    assert cfg["bp_matched.slab_planes"] == 16
    assert cfg["bp.z_block"] == 16
    assert cfg["bp.angle_chunk"] >= 1
    assert cfg["autotuned"] is False
    assert get_backend("ref").kernel_config(GEO) == {}


def test_kernel_config_reports_z_chunks_per_tile_for_its_angles():
    from repro.core.geometry import circular_angles
    from repro.core.operator import CTOperator
    from repro.kernels.fp_ray import z_chunks_per_tile
    angles = circular_angles(12)
    want = z_chunks_per_tile(GEO, angles)
    bk = get_backend("pallas")
    assert "z_chunks_per_tile" not in bk.kernel_config(GEO, planes=16)
    got = bk.kernel_config(GEO, planes=16, angles=angles)
    assert got["z_chunks_per_tile"] == want > 0
    op = CTOperator(GEO, angles, backend="pallas")
    assert op.kernel_config()["z_chunks_per_tile"] == want


def test_backend_uses_tuned_blocks_and_distinct_dispatch_keys(fake_measure):
    """Tuned blocks flow into the dispatch key: the same geometry tuned
    to a different slab width must compile a distinct entry."""
    from repro.core.backend import clear_dispatch_cache, dispatch_cache_keys
    clear_dispatch_cache()
    bk = get_backend("pallas")
    bk.fp(GEO, xdom=True)
    keys_heur = [k for k in dispatch_cache_keys()
                 if k[:2] == ("pallas", "fp")]
    assert len(keys_heur) == 1

    autotune.enable(True)              # fake model picks slab_planes=16->16
    cfg = bk.kernel_config(GEO, planes=16)
    assert cfg["autotuned"] is True
    # force a bigger tuned block via a loaded table
    key = autotune.shape_class("fp", GEO, None)
    with autotune._LOCK:
        autotune._TABLE[key] = {"slab_planes": 32}
    bk.fp(GEO, xdom=True)
    keys_now = [k for k in dispatch_cache_keys()
                if k[:2] == ("pallas", "fp")]
    assert len(keys_now) == 2, "tuned config reused the heuristic entry"


def test_real_tune_smoke():
    """End-to-end measured tuning on a tiny geometry (no monkeypatch):
    winner respects the floor and parity versus the heuristic holds."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.fp_ray import fp_ray_pallas
    geo = ConeGeometry.nice(16)
    autotune.enable(True)
    got = autotune.tune("fp", geo, repeats=1)
    assert got["slab_planes"] >= 16
    # tuned config computes the same forward projection
    ang = jnp.asarray(np.linspace(-0.3, 0.3, 4), jnp.float32)
    vol = jax.random.normal(jax.random.PRNGKey(0), geo.n_voxel, jnp.float32)
    a = fp_ray_pallas(vol, geo, ang, slab_planes=16, interpret=True)
    b = fp_ray_pallas(vol, geo, ang, slab_planes=got["slab_planes"],
                      interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# pad-to-divisor escape hatches (prime / awkward axes)
# --------------------------------------------------------------------------

def _xdom_angles(n):
    from repro.core.geometry import circular_angles, dominant_axis_mask
    a = circular_angles(n)
    return a[np.nonzero(dominant_axis_mask(a))[0]]


def test_fp_ray_prime_x_axis_pads():
    """nx=17 (prime) with slab_planes=16: the wrapper pads the marching
    axis with zero planes instead of rejecting non-divisible blocks."""
    import jax
    from repro.kernels import ref
    from repro.kernels.fp_ray import fp_ray_pallas
    geo = ConeGeometry.nice(16).with_voxels((16, 16, 17))
    ax = _xdom_angles(6)
    vol = jax.random.normal(jax.random.PRNGKey(7), geo.n_voxel, jnp.float32)
    got = fp_ray_pallas(vol, geo, ax, slab_planes=16, interpret=True)
    want = ref.fp_ray_ref(vol, geo, ax)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=5e-3)


def test_fp_ray_pad_matches_divisor_blocks():
    """Padding must be numerically invisible up to fp32 re-association:
    the padded x planes are zero and contribute zero, so a dividing
    block and a padding block agree to accumulation-order tolerance."""
    import jax
    from repro.kernels.fp_ray import fp_ray_pallas
    geo = ConeGeometry.nice(32)
    ax = _xdom_angles(4)
    vol = jax.random.normal(jax.random.PRNGKey(8), geo.n_voxel, jnp.float32)
    a = fp_ray_pallas(vol, geo, ax, slab_planes=8, interpret=True)   # 32%8==0
    b = fp_ray_pallas(vol, geo, ax, slab_planes=12, interpret=True)  # pads
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-4, atol=1e-4)


@pytest.mark.parametrize("planes,zb", [(13, 8), (7, 16)])
def test_bp_voxel_prime_z_planes_pads(planes, zb):
    """Prime slab heights used to force z_block=1 (or a ValueError);
    the kernel now pads the z grid and drops the tail planes."""
    import jax
    from repro.core.geometry import circular_angles
    from repro.kernels import ref
    from repro.kernels.bp_voxel import bp_voxel_pallas
    geo = ConeGeometry.nice(16).with_voxels((planes, 16, 16))
    angles = circular_angles(8)
    proj = jax.random.normal(jax.random.PRNGKey(planes),
                             (8,) + geo.n_detector, jnp.float32)
    got = bp_voxel_pallas(proj, geo, angles, z_block=zb, angle_chunk=4,
                          weight="fdk", interpret=True)
    want = ref.bp_voxel_ref(proj, geo, angles, weight="fdk")
    assert got.shape == (planes, 16, 16)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-3)


def test_bp_voxel_prime_angle_count_pads():
    """7 angles with angle_chunk=4: the padded angle rows carry zeroed
    projections, so they add nothing to the backprojection sums."""
    import jax
    from repro.core.geometry import circular_angles
    from repro.kernels import ref
    from repro.kernels.bp_voxel import bp_voxel_pallas
    geo = ConeGeometry.nice(16)
    angles = circular_angles(7)
    proj = jax.random.normal(jax.random.PRNGKey(11),
                             (7,) + geo.n_detector, jnp.float32)
    got = bp_voxel_pallas(proj, geo, angles, z_block=8, angle_chunk=4,
                          weight="fdk", interpret=True)
    want = ref.bp_voxel_ref(proj, geo, angles, weight="fdk")
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-3)
