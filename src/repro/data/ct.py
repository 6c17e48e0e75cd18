"""CT projection-data generation (benchmarks / examples).

Builds (volume, projections) pairs from the analytic phantoms so every
reconstruction benchmark has a ground truth without shipping measured data
(the paper's coffee-bean / ichthyosaur scans are not redistributable)."""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from repro.core import phantoms
from repro.core.geometry import (ConeGeometry, circular_angles,
                                 dominant_axis_mask)

import jax.numpy as jnp


def make_ct_dataset(geo: ConeGeometry, n_angles: int,
                    phantom: str = "shepp", noise_rel: float = 0.0,
                    seed: int = 0, backend: str = "ref"):
    """Returns (vol, angles, proj).  ``noise_rel`` adds Gaussian noise of
    that relative magnitude (models low-dose scans, paper SS3.2).
    ``backend`` names the kernel backend that projects the phantom (the
    fp32 ``ref`` projector by default; a reconstruction on the chip
    projects with the kernels it reconstructs with)."""
    from repro.core.backend import get_backend
    angles = circular_angles(n_angles)
    if phantom == "shepp":
        vol = phantoms.shepp_logan(geo)
    elif phantom == "sphere":
        vol = phantoms.sphere(geo)
    else:
        raise ValueError(f"unknown phantom {phantom!r}")
    fp = get_backend(backend).fp_mixed(geo, dominant_axis_mask(angles))
    proj = np.asarray(fp(jnp.asarray(vol), jnp.asarray(angles)))
    if noise_rel > 0:
        rng = np.random.default_rng(seed)
        proj = proj + (noise_rel * proj.std()
                       * rng.standard_normal(proj.shape).astype(np.float32))
    return vol, angles, proj
