"""The main path compiles for a TPU v5e at real width, without a chip.

Each test lowers and compiles for a *described* ``v5e:2x2`` topology (the
TPU compiler is installed even where no chip is attached): the three
Pallas kernels at N=512 with the heuristic's blocks, the dist FP
(``shard_map`` over a 4-chip mesh built from the topology's devices) and
one CGLS iteration of the Pallas operators.  Mosaic refuses here what
interpret mode accepts -- misaligned blocks, cross-vreg gathers, VMEM
overruns -- so this file guards every later change at no chip time.
Nothing runs: results are checked by the interpret-mode tests and by
``chip_smoke.py`` on the chip.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import backend as backend_mod
from repro.core.geometry import ConeGeometry, circular_angles, \
    dominant_axis_mask
from repro.kernels import autotune

N = 512
GEO = ConeGeometry.nice(N)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_pallas():
    """The registered pallas backend with Mosaic compilation forced on
    (off-TPU it would default to interpret mode)."""
    saved = backend_mod._REGISTRY["pallas"]
    yield backend_mod.register_backend(
        backend_mod.PallasBackend(interpret=False))
    backend_mod.register_backend(saved)


def _compile(fn, *args):
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of any cache that is configured
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", saved)
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    return compiled


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("kind", ["fp", "bp_matched", "bp"])
def test_kernel_compiles_at_n512(one_chip, kind):
    from repro.kernels.bp_matched import bp_matched_pallas
    from repro.kernels.bp_voxel import bp_voxel_pallas
    from repro.kernels.fp_ray import fp_ray_pallas
    cfg = autotune.heuristic_blocks(kind, GEO)
    assert autotune.fits(kind, GEO, cfg)
    n_ang = N // 2          # one dominance group of N angles
    angles = _spec((n_ang,), one_chip)
    if kind == "fp":
        _compile(lambda v, a: fp_ray_pallas(v, GEO, a, interpret=False,
                                            **cfg),
                 _spec(GEO.n_voxel, one_chip), angles)
        return
    proj = _spec((n_ang,) + GEO.n_detector, one_chip)
    if kind == "bp_matched":
        _compile(lambda p, a: bp_matched_pallas(p, GEO, a, interpret=False,
                                                **cfg), proj, angles)
    else:
        _compile(lambda p, a: bp_voxel_pallas(p, GEO, a, interpret=False,
                                              weight="fdk", **cfg),
                 proj, angles)


def test_dist_fp_compiles_on_4_chip_mesh(topo, compiled_pallas):
    from repro.core.distributed import dist_forward_project
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    fp = dist_forward_project(mesh, GEO, backend="pallas")
    vol = _spec(GEO.n_voxel, NamedSharding(mesh, P("model", None, None)))
    angles = _spec((N // 2,), NamedSharding(mesh, P("data")))
    compiled = _compile(fp.sharded(True), vol, angles)
    per_device = compiled.memory_analysis()
    assert per_device is not None


def test_cgls_iteration_compiles(one_chip, compiled_pallas):
    """One CGLS iteration on the Pallas operators (A, matched A^T and the
    fp32 inner products) as a single program."""
    from repro.core.algorithms.cgls import _sq
    angles = circular_angles(N)
    mask = dominant_axis_mask(angles)
    A = compiled_pallas.fp_mixed(GEO, mask)
    At = compiled_pallas.at_matched_mixed(GEO, mask)

    def step(x, r, p, gamma, ang):
        q = A(p, ang)
        alpha = gamma / (_sq(q) + 1e-30)
        x, r = x + alpha * p, r - alpha * q
        s = At(r, ang)
        gamma_new = _sq(s)
        return x, r, s + gamma_new / gamma * p, gamma_new

    vol = _spec(GEO.n_voxel, one_chip)
    proj = _spec((N,) + GEO.n_detector, one_chip)
    _compile(step, vol, proj, vol, _spec((), one_chip),
             _spec((N,), one_chip))
