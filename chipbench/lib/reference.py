"""Plain reference of the cone-beam Joseph operator pair, in fp32 JAX.

This is the yardstick the benchmark's ``correct`` is decided against.  It
imports nothing of the program under test: the geometry, the ray set-up and
the interpolation are written out here from the TIGRE cone-beam convention
(paper arXiv:1905.03748 SS2.1):

* volume ``(Nz, Ny, Nx)``, voxel ``(k, j, i)`` centred at
  ``((i - (Nx-1)/2) dx, (j - (Ny-1)/2) dy, (k - (Nz-1)/2) dz)``;
* source ``(DSO cos t, DSO sin t, 0)``, flat detector ``DSD - DSO`` behind
  the axis with ``e_u = (-sin t, cos t, 0)`` and ``e_v = z``;
* Joseph's method: each ray marches the voxel planes of its dominant axis
  (x where ``|cos t| >= |sin t|``, else y, by rotating the scene -90 deg)
  and takes one bilinear (z, y) sample per plane, zero outside the grid,
  weighted by the ray length per plane ``|d| / |d_x| * dx``; only samples
  between the source and the detector count.

The bilinear sample is written as the tent-weight sum
``sum_k sum_j hat(fk - k) hat(fj - j) I[k, j]``: the y sum is a dense matmul,
the z sum a dense weighted reduction.  That is slower than a gather but has
no index arithmetic to get wrong, and it lets the *control* run the same
arithmetic in a lower precision: every product of the interpolation (and of
the vector updates in :mod:`check`) goes through :func:`mul` / :func:`dot`,
which round their operands to bfloat16 (``"bf16"``) when asked.

Only windows of the full operators are computed, which is what makes an
fp32 reference affordable at 512^3: the forward projection of a whole
volume at a handful of angles (:func:`fp_angles`), and the exact adjoint
restricted to a box of voxel columns, over every angle (:func:`bp_box`);
and TIGRE's voxel-driven pseudo-matched backprojection, which the SART
family uses, on such a box (:func:`bp_voxel_box`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
V_CHUNK = 64          # detector rows per dense z-reduction block


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Circular cone-beam geometry, distances in mm (TIGRE names)."""
    DSD: float
    DSO: float
    n_voxel: Tuple[int, int, int]          # (Nz, Ny, Nx)
    s_voxel: Tuple[float, float, float]    # (z, y, x) extent
    n_detector: Tuple[int, int]            # (Nv, Nu)
    s_detector: Tuple[float, float]        # (v, u) extent

    @staticmethod
    def from_config(cfg: dict) -> "Geometry":
        g = cfg["geometry"]
        return Geometry(float(g["DSD"]), float(g["DSO"]),
                        tuple(int(v) for v in g["n_voxel"]),
                        tuple(float(v) for v in g["s_voxel"]),
                        tuple(int(v) for v in g["n_detector"]),
                        tuple(float(v) for v in g["s_detector"]))

    @property
    def d_voxel(self):
        return tuple(s / n for s, n in zip(self.s_voxel, self.n_voxel))

    @property
    def d_detector(self):
        return tuple(s / n for s, n in zip(self.s_detector, self.n_detector))


def scan_angles(n_angles: int) -> np.ndarray:
    """``n_angles`` equally spaced gantry angles over 360 degrees."""
    return np.linspace(0.0, 2.0 * math.pi, n_angles,
                       endpoint=False).astype(np.float32)


def x_dominant(angles: np.ndarray) -> np.ndarray:
    """True where the central ray marches x planes.

    Decided in fp32, the precision the angles are given in: at exactly
    45 degrees the tie goes to x, as fp32 ``cos`` and ``sin`` round equal."""
    a = np.asarray(angles, np.float32)
    return np.abs(np.cos(a)) >= np.abs(np.sin(a))


# --------------------------------------------------------------------------
# arithmetic at a chosen precision

def _bf(a):
    """``a`` rounded to bfloat16, kept as fp32.  ``reduce_precision`` is an
    op the compiler must honour: a convert pair may be elided on a TPU,
    where XLA allows excess precision."""
    return jax.lax.reduce_precision(jnp.asarray(a, jnp.float32),
                                    exponent_bits=8, mantissa_bits=7)


def mul(a, b, prec: str = "f32"):
    """Elementwise ``a * b`` with operands at ``prec``, result in fp32."""
    if prec == "f32":
        return a * b
    if prec == "bf16":
        return _bf(a) * _bf(b)
    raise ValueError(f"unknown precision {prec!r}")


def dot(a, b, prec: str = "f32"):
    """``a @ b`` with operands at ``prec``, accumulated in fp32.

    The rounded operands are still fp32 values, and a product of two
    bfloat16 values is exact in fp32, so every platform computes the same
    thing the MXU's bf16 passes would."""
    if prec == "f32":
        return jnp.dot(a, b, precision=HIGHEST)
    if prec == "bf16":
        return jnp.dot(_bf(a), _bf(b), precision=HIGHEST)
    raise ValueError(f"unknown precision {prec!r}")


def hat(t):
    """Tent weight of a grid point at distance ``t`` (0 beyond one step)."""
    return jnp.maximum(0.0, 1.0 - jnp.abs(t))


# --------------------------------------------------------------------------
# rays of one angle, in the x-marching frame

class _Rays:
    """Per-angle ray quantities for marching x planes (traced ``theta``)."""

    def __init__(self, geo: Geometry, theta):
        nv, nu = geo.n_detector
        dv, du = geo.d_detector
        c, s = jnp.cos(theta), jnp.sin(theta)
        self.sx, self.sy = geo.DSO * c, geo.DSO * s
        u = (jnp.arange(nu, dtype=jnp.float32) - (nu - 1) / 2.0) * du
        v = (jnp.arange(nv, dtype=jnp.float32) - (nv - 1) / 2.0) * dv
        dcx, dcy = -(geo.DSD - geo.DSO) * c, -(geo.DSD - geo.DSO) * s
        self.d_x = dcx + u * (-s) - self.sx                   # (Nu,)
        self.d_y = dcy + u * c - self.sy                      # (Nu,)
        self.d_z = v                                          # (Nv,) (Sz = 0)
        self.inv_dx = 1.0 / jnp.where(jnp.abs(self.d_x) < 1e-9, 1e-9,
                                      self.d_x)
        norm = jnp.sqrt(self.d_x[None, :] ** 2 + self.d_y[None, :] ** 2
                        + self.d_z[:, None] ** 2)
        self.seg = (norm / jnp.maximum(jnp.abs(self.d_x), 1e-9)[None, :]
                    * geo.d_voxel[2])                         # (Nv, Nu)
        self.geo = geo

    def plane(self, x):
        """Plane at world ``x``: float y index (Nu,), z index (Nv, Nu),
        and the forward-ray mask (Nu,)."""
        g = self.geo
        nz, ny, _ = g.n_voxel
        dz, dy, _ = g.d_voxel
        s = (x - self.sx) * self.inv_dx
        fj = (self.sy + s * self.d_y) / dy + (ny - 1) / 2.0
        fk = (s[None, :] * self.d_z[:, None]) / dz + (nz - 1) / 2.0
        valid = ((s > 0.0) & (s <= 1.0)).astype(jnp.float32)
        return fj, fk, valid


def _plane_x(geo: Geometry, i):
    nx = geo.n_voxel[2]
    return (jnp.asarray(i, jnp.float32) - (nx - 1) / 2.0) * geo.d_voxel[2]


def _z_sample(fk, col, prec):
    """``out[v, u] = sum_k hat(fk[v, u] - k) col[k, u]`` in row blocks."""
    nv, nu = fk.shape
    nz = col.shape[0]
    cv = min(V_CHUNK, nv)
    assert nv % cv == 0, (nv, cv)
    k = jnp.arange(nz, dtype=jnp.float32)[:, None, None]

    def block(fkb):
        w = hat(fkb[None, :, :] - k)                          # (Nz, cv, Nu)
        return jnp.sum(mul(w, col[:, None, :], prec), axis=0)
    return jax.lax.map(block, fk.reshape(nv // cv, cv, nu)).reshape(nv, nu)


def _z_scatter(fk, g, nz, prec):
    """Transpose of :func:`_z_sample`: ``out[k, u] = sum_v hat(...) g[v, u]``."""
    nv, nu = fk.shape
    cv = min(V_CHUNK, nv)
    k = jnp.arange(nz, dtype=jnp.float32)[:, None, None]

    def block(acc, blk):
        fkb, gb = blk
        w = hat(fkb[None, :, :] - k)                          # (Nz, cv, Nu)
        return acc + jnp.sum(mul(w, gb[None, :, :], prec), axis=1), None
    out, _ = jax.lax.scan(block, jnp.zeros((nz, nu), jnp.float32),
                          (fk.reshape(nv // cv, cv, nu),
                           g.reshape(nv // cv, cv, nu)))
    return out


# --------------------------------------------------------------------------
# forward projection of a whole volume at a few angles

def _fp_one(planes_t, geo: Geometry, theta, prec):
    """Joseph line integrals at one x-marching angle.

    ``planes_t[i]`` is marching plane ``i`` as a (Nz, Ny) image."""
    r = _Rays(geo, theta)
    ny = planes_t.shape[2]
    jj = jnp.arange(ny, dtype=jnp.float32)[:, None]

    def body(i, acc):
        fj, fk, valid = r.plane(_plane_x(geo, i))
        wy = hat(fj[None, :] - jj)                            # (Ny, Nu)
        col = dot(planes_t[i], wy, prec)                      # (Nz, Nu)
        return acc + _z_sample(fk, col, prec) * valid[None, :]

    acc = jax.lax.fori_loop(0, planes_t.shape[0], body,
                            jnp.zeros(geo.n_detector, jnp.float32))
    return acc * r.seg


def _marching_planes(vol, xdom: bool):
    """The volume as marching planes ``(n_planes, Nz, n_cols)``.

    y-dominant angles see the scene rotated by -90 deg about z, which
    turns their y planes into x planes: rotated voxel ``(k, j', i')`` is
    ``vol[k, i', Nx-1-j']``."""
    if xdom:
        return jnp.transpose(vol, (2, 0, 1))
    return jnp.flip(jnp.transpose(vol, (1, 0, 2)), axis=2)


def frame_angle(theta, xdom: bool):
    """The angle in the x-marching frame (fp32, as the operator is)."""
    t = jnp.asarray(theta, jnp.float32)
    return t if xdom else t - jnp.float32(math.pi / 2.0)


def fp_marching(vol, geo: Geometry, angles, xdom: bool, prec: str = "f32"):
    """Forward projection at ``angles`` that all march x planes
    (``xdom``) or all march y planes; ``angles`` may be traced."""
    planes_t = _marching_planes(vol, xdom)
    th = frame_angle(angles, xdom)
    return jax.lax.map(lambda t: _fp_one(planes_t, geo, t, prec), th)


def fp_angles(vol, geo: Geometry, angles: Sequence[float], prec: str = "f32"):
    """Forward projection of ``vol`` at ``angles``: ``(A, Nv, Nu)``."""
    angles = np.asarray(angles, np.float32)
    xm = x_dominant(angles)
    out = [None] * len(angles)
    for xdom in (True, False):
        idx = np.nonzero(xm == xdom)[0]
        if not idx.size:
            continue
        proj = fp_marching(vol, geo, jnp.asarray(angles[idx]), xdom, prec)
        for n, a in enumerate(idx):
            out[a] = proj[n]
    return jnp.stack(out)


# --------------------------------------------------------------------------
# exact adjoint on a box of voxel columns, over every angle

def _box_frame(geo: Geometry, box, xdom: bool):
    """Box ``(y0, x0, size)`` in the marching frame: first plane and first
    column there, and whether the plane/column axes of the result must be
    mapped back (y-dominant: planes are y, columns are reversed x)."""
    y0, x0, n = box
    nx = geo.n_voxel[2]
    if xdom:
        return x0, y0
    return y0, nx - x0 - n


def _bp_box_one(r, geo: Geometry, theta, p0, c0, n, prec):
    """Adjoint of the Joseph FP restricted to planes ``[p0, p0+n)`` and
    columns ``[c0, c0+n)`` at one x-marching angle: ``(Nz, n cols, n planes)``."""
    rays = _Rays(geo, theta)
    nz = geo.n_voxel[0]
    g = r * rays.seg
    cols = (jnp.arange(n, dtype=jnp.float32) + c0)[:, None]

    def body(il, out):
        fj, fk, valid = rays.plane(_plane_x(geo, p0 + il))
        colz = _z_scatter(fk, g * valid[None, :], nz, prec)  # (Nz, Nu)
        wy = hat(fj[None, :] - cols)                         # (n, Nu)
        upd = dot(colz, wy.T, prec)                          # (Nz, n)
        return out.at[:, :, il].add(upd)

    return jax.lax.fori_loop(0, n, body,
                             jnp.zeros((nz, n, n), jnp.float32))


def bp_box(proj, geo: Geometry, angles, box, prec: str = "f32"):
    """``(A^T proj)`` on the voxel box ``[:, y0:y0+n, x0:x0+n]``.

    ``box = (y0, x0, n)``; every z plane is included.  Returns
    ``(Nz, n, n)`` indexed ``[k, y - y0, x - x0]``."""
    angles = np.asarray(angles, np.float32)
    xm = x_dominant(angles)
    n = box[2]
    total = jnp.zeros((geo.n_voxel[0], n, n), jnp.float32)
    for xdom in (True, False):
        idx = np.nonzero(xm == xdom)[0]
        if not idx.size:
            continue
        p0, c0 = _box_frame(geo, box, xdom)
        th = frame_angle(jnp.asarray(angles[idx]), xdom)

        def one(acc, inp):
            t, r = inp
            return acc + _bp_box_one(r, geo, t, p0, c0, n, prec), None
        part, _ = jax.lax.scan(one, jnp.zeros_like(total),
                               (th, proj[jnp.asarray(idx)]))
        if not xdom:
            # [k, reversed-x column, y plane] -> [k, y, x]; x-marching
            # parts are [k, y column, x plane] already
            part = jnp.transpose(jnp.flip(part, axis=1), (0, 2, 1))
        total = total + part
    return total


# --------------------------------------------------------------------------
# voxel-driven backprojection on a box of voxel columns

def bp_voxel_box(proj, geo: Geometry, angles, box, prec: str = "f32"):
    """TIGRE's voxel-driven backprojection with the pseudo-matched weight,
    on the voxel box ``[:, y0:y0+n, x0:x0+n]`` (``box = (y0, x0, n)``).

    Each voxel centre is projected onto the detector from the source,
    ``fu = (q * mag) / du + (Nu-1)/2`` and ``fv = (z * mag) / dv + (Nv-1)/2``
    with ``mag = DSD / (DSO - p)``, ``p`` the voxel's depth along the
    source axis and ``q`` its coordinate along ``e_u``; the projection is
    sampled bilinearly there (zero off the detector) and weighted by
    ``(DSD / (DSO - p))^2 * DSO / DSD``.  The bilinear sample is the
    tent-weight sum, as in the forward projection: a dense matmul over
    ``u`` and a dense weighted reduction over ``v``.  ``angles`` may be
    traced, and so may the box's corner ``y0, x0`` (not its size ``n``).
    Returns ``(Nz, n, n)`` indexed ``[k, y - y0, x - x0]``."""
    y0, x0, n = box
    nz, ny, nx = geo.n_voxel
    dz, dy, dx = geo.d_voxel
    nv, nu = geo.n_detector
    dv, du = geo.d_detector
    span = jnp.arange(n, dtype=jnp.float32)
    xs = (span + x0 - (nx - 1) / 2.0) * dx
    ys = (span + y0 - (ny - 1) / 2.0) * dy
    zs = (jnp.arange(nz, dtype=jnp.float32) - (nz - 1) / 2.0) * dz
    xx, yy = xs[None, :], ys[:, None]                       # (n, n) [y, x]
    uu = jnp.arange(nu, dtype=jnp.float32)[:, None]

    def one(acc, inp):
        theta, p2d = inp
        c, s = jnp.cos(theta), jnp.sin(theta)
        p = (xx * c + yy * s).reshape(-1)                   # (n*n,)
        q = (-xx * s + yy * c).reshape(-1)
        depth = geo.DSO - p
        mag = geo.DSD / depth
        fu = (q * mag) / du + (nu - 1) / 2.0
        fv = (zs[:, None] * mag[None, :]) / dv + (nv - 1) / 2.0   # (Nz, n*n)
        w = (geo.DSD / depth) ** 2 * (geo.DSO / geo.DSD)
        col = dot(p2d, hat(fu[None, :] - uu), prec)        # (Nv, n*n)
        return acc + mul(_z_sample(fv, col, prec), w[None, :], prec), None

    th = jnp.asarray(angles, jnp.float32).reshape(-1)
    out, _ = jax.lax.scan(one, jnp.zeros((nz, n * n), jnp.float32),
                          (th, jnp.asarray(proj, jnp.float32)))
    return out.reshape(nz, n, n)
