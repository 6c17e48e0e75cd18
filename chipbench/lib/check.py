"""The comparison that decides ``correct`` for a CGLS cell.

One CGLS iteration on the normal equations maps ``(x0, r0, p0, g0)`` to

    q  = A p0              alpha = g0 / |q|^2
    x1 = x0 + alpha p0     r1    = r0 - alpha q
    s  = A^T r1            g1    = |s|^2
    p1 = s + (g1 / g0) p0

The check takes the state before and after the window's last iteration,
exactly as the timed path left them (the state before is a host copy), and recomputes that iteration with the
plain fp32 reference (:mod:`reference`) on windows it can afford: ``A`` at a
few sampled angles and ``A^T`` on a box of voxel columns (every angle, every
z plane).  The scalars the reference needs but cannot afford to form from
its windows (``|q|^2`` and ``|s|^2`` over the whole arrays) are taken from
the program's own ``q`` and ``s``, which the recurrence determines: ``q``
from ``(r0 - r1) / alpha`` with ``alpha`` read off the x update, and ``s``
from ``p1 - (g1 / g0) p0``.  A program whose x, r, p or g updates disagree
with each other, or with the reference operators, moves one of the numbers:

* ``r_gap``  -- r1 at the sampled angles vs ``r0 - alpha A_ref p0``: the
  forward kernel, alpha, and the r update;
* ``x_gap``  -- x1 vs ``x0 + alpha p0`` over the whole volume: the x update;
* ``p_gap``  -- p1 on the box vs ``A_ref^T r1 + beta p0``: the matched
  backprojection kernel, the cross-chip sum of its partial volumes, g1 and
  the p update;
* ``res_gap`` -- r1 at the sampled angles vs ``b - A_ref x1``: the residual
  recurrence over *every* iteration since the start, so a fault in any
  earlier iteration of the window shows too.

Each is a max-abs difference over the window, relative to the max-abs of
the reference quantity.  The control (:func:`control_numbers`) puts the
reference itself in the program's place, computed at a lower precision.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import reference as ref
from .data import seed_rng

NUMBERS = ("r_gap", "x_gap", "p_gap", "res_gap")


@dataclasses.dataclass
class Iterate:
    """The CGLS recurrence variables at one iteration boundary."""
    x: jax.Array
    r: jax.Array
    p: jax.Array
    gamma: jax.Array
    it: int


def sample_angles(seed: int, angles: np.ndarray, per_dominance: int = 4,
                  n_shards: int = 1) -> np.ndarray:
    """Indices of the angles the forward check projects, drawn from the seed.

    Each dominance group is cut into ``max(per_dominance, n_shards)``
    contiguous chunks, in angle order -- the order in which a data-parallel
    mesh shards a group -- and one angle is drawn from each chunk, so every
    chip and both marching axes are sampled."""
    rng = seed_rng(seed, 1)
    xm = ref.x_dominant(angles)
    picks = []
    for xdom in (True, False):
        idx = np.nonzero(xm == xdom)[0]
        if not idx.size:
            continue
        for chunk in np.array_split(idx, min(idx.size,
                                             max(per_dominance, n_shards))):
            picks.append(int(rng.choice(chunk)))
    return np.asarray(sorted(picks), np.int64)


def sample_box(seed: int, n_voxel, size: int) -> Tuple[int, int, int]:
    """``(y0, x0, size)`` of the voxel-column box, inside the middle half of
    the slice (where the phantom is), drawn from the seed."""
    rng = seed_rng(seed, 2)
    _, ny, nx = n_voxel
    size = min(size, ny // 2, nx // 2)
    y0 = int(rng.integers(ny // 4, 3 * ny // 4 - size + 1))
    x0 = int(rng.integers(nx // 4, 3 * nx // 4 - size + 1))
    return y0, x0, size


def _relmax(d, scale) -> float:
    return float(jnp.max(jnp.abs(d)) / jnp.max(jnp.abs(scale)))


@dataclasses.dataclass
class Windows:
    """Reference quantities on the checked windows (one precision)."""
    q_s: jax.Array      # A p0 at the sampled angles
    ax_s: jax.Array     # A x1 at the sampled angles
    s_box: jax.Array    # A^T r1 on the box


class Check:
    """Holds the program's two iterates and the reference scalars."""

    def __init__(self, before: Iterate, after: Iterate, b, angles, geo,
                 sample: Sequence[int], box):
        dev = jax.devices()[0]
        put = lambda a: jax.device_put(a, dev)
        self.geo = geo
        self.angles = np.asarray(angles, np.float32)
        self.sample = np.asarray(sample)
        self.box = box
        x0, r0, p0 = put(before.x), put(before.r), put(before.p)
        x1, r1, p1 = put(after.x), put(after.r), put(after.p)
        g0 = put(jnp.asarray(before.gamma, jnp.float32))
        g1 = put(jnp.asarray(after.gamma, jnp.float32))
        self.advanced = after.it - before.it
        si = jnp.asarray(self.sample)
        y0, bx0, n = box
        # alpha as the x update applied it, q and s as the recurrence
        # determines them; then the reference's own scalars from those
        alpha_x = jnp.sum((x1 - x0) * p0) / jnp.sum(p0 * p0)
        q = (r0 - r1) / alpha_x
        self.alpha = g0 / (jnp.sum(q * q) + 1e-30)
        s = p1 - (g1 / (g0 + 1e-30)) * p0
        self.beta = jnp.sum(s * s) / (g0 + 1e-30)
        del q, s
        self.x0, self.p0, self.x1 = x0, p0, x1
        self.r0_s, self.r1_s = r0[si], r1[si]
        self.b_s = put(b)[si]
        self.p0_box = p0[:, y0:y0 + n, bx0:bx0 + n]
        self.p1_box = p1[:, y0:y0 + n, bx0:bx0 + n]
        self.r1 = r1
        self._win: Dict[str, Windows] = {}

    def windows(self, prec: str) -> Windows:
        if prec not in self._win:
            angles = self.angles
            fp = jax.jit(lambda v: ref.fp_angles(v, self.geo,
                                                 angles[self.sample], prec))
            q_s, ax_s = fp(self.p0), fp(self.x1)
            bp = jax.jit(lambda r: ref.bp_box(r, self.geo, angles, self.box,
                                              prec))
            s_box = bp(self.r1)
            self._win[prec] = Windows(q_s, ax_s, s_box)
        return self._win[prec]

    def _expected(self):
        w = self.windows("f32")
        return dict(
            r1_s=self.r0_s - self.alpha * w.q_s,
            x1=self.x0 + self.alpha * self.p0,
            p1_box=w.s_box + self.beta * self.p0_box,
            res_s=self.b_s - w.ax_s)

    def _numbers(self, out) -> Dict[str, float]:
        exp = self._expected()
        w = self.windows("f32")
        return {
            "r_gap": _relmax(out["r1_s"] - exp["r1_s"], self.alpha * w.q_s),
            "x_gap": _relmax(out["x1"] - exp["x1"], self.alpha * self.p0),
            "p_gap": _relmax(out["p1_box"] - exp["p1_box"], exp["p1_box"]),
            "res_gap": _relmax(out["res_s"] - exp["res_s"], self.b_s),
        }

    def program_numbers(self) -> Dict[str, float]:
        """The program's last iteration against the fp32 reference."""
        if self.advanced != 1:
            return {k: float("inf") for k in NUMBERS}
        return self._numbers(dict(r1_s=self.r1_s, x1=self.x1,
                                  p1_box=self.p1_box, res_s=self.r1_s))

    def control_numbers(self, prec: str) -> Dict[str, float]:
        """The reference at ``prec`` put in the program's place."""
        w = self.windows(prec)
        return self._numbers(dict(
            r1_s=self.r0_s - ref.mul(self.alpha, w.q_s, prec),
            x1=self.x0 + ref.mul(self.alpha, self.p0, prec),
            p1_box=w.s_box + ref.mul(self.beta, self.p0_box, prec),
            res_s=self.b_s - w.ax_s))


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True when every number is finite and within its limit."""
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in limits)
