"""Seeded input data: a Shepp-Logan-like phantom plus a seeded perturbation.

A copy kept with the benchmark, so that edits to the program's own phantom
cannot move the yardstick.  The ellipsoids rotate about z only, so each
one's quadric splits into an (Ny, Nx) in-plane term and an (Nz,) axial term
(the same separable form the program's phantom uses).  The whole volume is
made on the device in one jitted call; the seed changes the values, never
the sizes, so every seed asks the same work of the system.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# (value, centre (x, y, z), semi-axes (x, y, z), rotation about z in deg),
# coordinates in units of the half extent of the volume.
ELLIPSOIDS = (
    (1.00, (0.0, 0.0, 0.0), (0.69, 0.92, 0.81), 0.0),
    (-0.80, (0.0, -0.0184, 0.0), (0.6624, 0.874, 0.78), 0.0),
    (-0.20, (0.22, 0.0, 0.0), (0.11, 0.31, 0.22), -18.0),
    (-0.20, (-0.22, 0.0, 0.0), (0.16, 0.41, 0.28), 18.0),
    (0.10, (0.0, 0.35, -0.15), (0.21, 0.25, 0.41), 0.0),
    (0.10, (0.0, 0.1, 0.25), (0.046, 0.046, 0.05), 0.0),
    (0.10, (-0.08, -0.605, 0.0), (0.046, 0.023, 0.02), 0.0),
    (0.10, (0.06, -0.605, -0.1), (0.023, 0.046, 0.02), 90.0),
)
# Amplitude of the seeded perturbation, relative to the outer shell's 1.0.
PERTURBATION = 0.05


def seed_key(seed: int):
    """A JAX PRNG key from any non-negative integer seed (no 32-bit cap)."""
    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32))


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A host RNG for sampling decisions, independent per ``stream``."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _centres(n: int, extent: float):
    d = extent / n
    return (jnp.arange(n, dtype=jnp.float32) - (n - 1) / 2.0) * d


@functools.partial(jax.jit, static_argnums=(1, 2))
def _phantom(key, n_voxel, s_voxel):
    nz, ny, nx = n_voxel
    z = _centres(nz, s_voxel[0]) / (s_voxel[0] / 2.0)
    y = _centres(ny, s_voxel[1])[:, None] / (s_voxel[1] / 2.0)
    x = _centres(nx, s_voxel[2])[None, :] / (s_voxel[2] / 2.0)
    vol = jnp.zeros(n_voxel, jnp.float32)
    for value, (cx, cy, cz), (ax, ay, az), phi_deg in ELLIPSOIDS:
        phi = np.deg2rad(phi_deg)
        c, s = float(np.cos(phi)), float(np.sin(phi))
        xn, yn = x - cx, y - cy
        q_xy = ((c * xn + s * yn) / ax) ** 2 + ((-s * xn + c * yn) / ay) ** 2
        q_z = ((z - cz) / az) ** 2
        inside = q_xy[None, :, :] + q_z[:, None, None] <= 1.0
        vol = vol + jnp.where(inside, jnp.float32(value), 0.0)
    noise = jax.random.uniform(key, n_voxel, jnp.float32)
    return vol + PERTURBATION * noise


def phantom(seed: int, n_voxel, s_voxel) -> jax.Array:
    """The seeded volume ``(Nz, Ny, Nx)`` fp32, made on the default device."""
    return _phantom(seed_key(seed), tuple(int(v) for v in n_voxel),
                    tuple(float(v) for v in s_voxel))
