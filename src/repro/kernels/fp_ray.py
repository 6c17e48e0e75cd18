"""Pallas TPU kernel: Joseph forward projector with marching-axis streaming.

TPU adaptation of TIGRE's texture-cached ray-driven projection kernel
(paper SS2.1, Fig 2):

* The volume is laid out as marching-axis planes ``(Nx, Nz, Ny)`` (a
  transpose of the (Nz, Ny, Nx) volume).  The Pallas grid iterates
  ``(angle_block, slab)`` with the slab dimension innermost, *accumulating*
  ``angle_block`` projections in the resident output block while the
  pipeline double-buffers the next slab's HBM->VMEM DMA -- the in-kernel
  image of the paper's two-projection-buffer overlap scheme.  Each volume
  slab read from HBM serves a whole block of angles.
* CUDA texture bilinear interpolation has no TPU analogue.  Joseph's method
  needs one bilinear (z, y) sample per ray and marching plane, which splits
  into two linear interpolations with tent ("hat") weights
  ``max(0, 1 - |f - k|)`` over the in-range grid rows ``k``:

  - y: the tap index depends on the detector column only, so the y pass is
    one MXU matmul per plane, ``plane(Nz, Ny) @ Wy(Ny, Nu)``;
  - z: the tap index ``fk(v, u)`` is affine in the detector row, so every
    8-row detector tile reads a narrow band of z rows.  The band is found
    from the tile's min/max ``fk`` and swept in aligned 8-row chunks on
    the VPU.

  Taps outside the grid get no weight, which is what makes partial
  projections of disjoint z slabs sum to the monolithic one exactly.
* Per-angle geometry scalars are precomputed on the host into a small
  ``(A, 8)`` table (the analogue of TIGRE's constant memory) held in SMEM.

The ray/plane index and weight math (:func:`ray_frame` ... :func:`hat`) is
shared with the matched adjoint in :mod:`repro.kernels.bp_matched`, which
replays it with the data movement transposed.

The kernel only handles x-dominant angles; callers rotate the scene by
-90 deg for y-dominant ones (repro.core.projector handles the split).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.geometry import ConeGeometry

ROWS = 8                    # sublane tile: detector rows / z rows per step
HIGHEST = jax.lax.Precision.HIGHEST
# Scoped VMEM requested from Mosaic.  A TPU v5e core has 128 MiB of VMEM;
# the block heuristic in repro.kernels.autotune budgets below this.
VMEM_LIMIT_BYTES = 96 * 2**20
_BIG = 1e9


def angle_constants(geo: ConeGeometry, angles) -> jnp.ndarray:
    """(A, 8) per-angle table: src(3), det_c(2), e_u(2), pad.

    Built with jnp so ``angles`` may be a *traced* array: the wrappers in
    :mod:`repro.core.backend` jit once per static key and reuse the
    compiled kernel across angle values instead of retracing per call.
    """
    a = jnp.asarray(angles, jnp.float32)
    c, s = jnp.cos(a), jnp.sin(a)
    z = jnp.zeros_like(a)
    return jnp.stack([
        geo.DSO * c,                    # Sx
        geo.DSO * s,                    # Sy
        z,                              # Sz
        -(geo.DSD - geo.DSO) * c,       # det_c x
        -(geo.DSD - geo.DSO) * s,       # det_c y
        -s,                             # e_u x
        c,                              # e_u y
        z,
    ], axis=-1)


# --------------------------------------------------------------------------
# shared in-kernel helpers (FP, matched BP and voxel BP)
# --------------------------------------------------------------------------

def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def balanced_block(n: int, block: int) -> Tuple[int, int]:
    """(block, count) covering ``n`` items with the least padding."""
    count = -(-n // max(1, min(int(block), n)))
    return -(-n // count), count


def iota_f32(shape, dim: int, offset=0) -> jnp.ndarray:
    """Float grid indices along ``dim`` (2-D: Mosaic has no 1-D iota)."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, dim)
            + offset).astype(jnp.float32)


def hat(t: jnp.ndarray) -> jnp.ndarray:
    """Linear-interpolation (tent) weight of a tap at distance ``t``."""
    return jnp.maximum(0.0, 1.0 - jnp.abs(t))


def chunk_window(f: jnp.ndarray, mask, n_chunks: int):
    """Aligned 8-row chunks ``[c_lo, c_hi)`` holding every tap of ``f``.

    ``f`` holds float row indices (one tile of samples); a sample at ``f``
    reads rows ``floor(f)`` and ``floor(f) + 1``.  Samples where ``mask``
    is False are ignored (their weight is zeroed by the caller).  Extra
    chunks are harmless -- their hat weights are exactly zero -- so only
    coverage matters.
    """
    lim = float(n_chunks * ROWS + 2 * ROWS)
    lo = f if mask is None else jnp.where(mask, f, _BIG)
    hi = f if mask is None else jnp.where(mask, f, -_BIG)
    k_lo = jnp.floor(jnp.clip(jnp.min(lo), -lim, lim)).astype(jnp.int32)
    k_hi = jnp.floor(jnp.clip(jnp.max(hi), -lim, lim)).astype(jnp.int32) + 2
    n_rows = n_chunks * ROWS
    c_lo = jnp.clip(k_lo, 0, n_rows) // ROWS
    c_hi = (jnp.clip(k_hi, 0, n_rows) + ROWS - 1) // ROWS
    return c_lo, c_hi


def chunk_rows(src_ref, c):
    """Aligned 8-row chunk ``c`` of a 2-D VMEM ref."""
    return src_ref[pl.ds(pl.multiple_of(c * ROWS, ROWS), ROWS), :]


def chunk_base(c, shape) -> jnp.ndarray:
    """First row index of chunk ``c`` as a float tile of ``shape``."""
    return jnp.full(shape, c * ROWS, jnp.int32).astype(jnp.float32)


def gather_rows(f: jnp.ndarray, src_ref, c_lo, c_hi) -> jnp.ndarray:
    """``out[i, l] = sum_k hat(f[i, l] - k) * src[k, l]`` over the window.

    Linear interpolation of the columns of ``src_ref`` at the float row
    indices ``f`` (one 8-row tile), restricted to chunks ``[c_lo, c_hi)``.
    """
    def body(c, acc):
        rows = chunk_rows(src_ref, c)
        t0 = f - chunk_base(c, f.shape)
        for r in range(ROWS):
            acc = acc + hat(t0 - float(r)) * rows[r:r + 1, :]
        return acc
    return jax.lax.fori_loop(c_lo, c_hi, body, jnp.zeros_like(f))


def scatter_rows(f: jnp.ndarray, g: jnp.ndarray, dst_ref, c_lo, c_hi):
    """Transpose of :func:`gather_rows`: ``dst[k] += sum_i hat(f[i]-k) g[i]``.

    The weights are bit-identical to :func:`gather_rows`' (same
    ``(f - base) - r`` expression), so the pair is an exact adjoint.
    """
    local = iota_f32((ROWS, 1), 0)

    def body(c, carry):
        t0 = f - chunk_base(c, f.shape)
        upd = jnp.zeros((ROWS, f.shape[1]), jnp.float32)
        for i in range(ROWS):
            upd = upd + hat(t0[i:i + 1, :] - local) * g[i:i + 1, :]
        sl = pl.ds(pl.multiple_of(c * ROWS, ROWS), ROWS)
        dst_ref[sl, :] += upd
        return carry
    jax.lax.fori_loop(c_lo, c_hi, body, 0)


class RayFrame(NamedTuple):
    """Per-angle ray geometry: source scalars and per-column directions."""
    sx: jnp.ndarray
    sy: jnp.ndarray
    sz: jnp.ndarray
    d_x: jnp.ndarray        # (1, Nu) ray direction x (pixel minus source)
    d_y: jnp.ndarray        # (1, Nu)
    inv_dx: jnp.ndarray     # (1, Nu) guarded 1 / d_x


def ray_frame(c_ref, a, geo: ConeGeometry) -> RayFrame:
    """Rays of angle ``a`` of the SMEM constant block ``c_ref``."""
    nv, nu = geo.n_detector
    dv, du = geo.d_detector
    offv, offu = geo.off_detector
    base = a * 8
    sx, sy, sz = c_ref[base], c_ref[base + 1], c_ref[base + 2]
    dcx, dcy = c_ref[base + 3], c_ref[base + 4]
    eux, euy = c_ref[base + 5], c_ref[base + 6]
    u = (iota_f32((1, nu), 1) - (nu - 1) / 2.0) * du + offu
    d_x = dcx + u * eux - sx
    d_y = dcy + u * euy - sy
    inv_dx = 1.0 / jnp.where(jnp.abs(d_x) < 1e-9, 1e-9, d_x)
    return RayFrame(sx, sy, sz, d_x, d_y, inv_dx)


def ray_rows(fr: RayFrame, t, geo: ConeGeometry):
    """Detector-row tile ``t``: ray z directions (8, 1), in-range mask."""
    nv = geo.n_detector[0]
    dv, offv = geo.d_detector[0], geo.off_detector[0]
    iv = iota_f32((ROWS, 1), 0, t * ROWS)
    d_z = (iv - (nv - 1) / 2.0) * dv + offv - fr.sz
    return d_z, iv < nv


def ray_seg(fr: RayFrame, d_z, geo: ConeGeometry) -> jnp.ndarray:
    """Ray length per marching plane, ``|d| / |d_x| * dx`` (8, Nu)."""
    norm = jnp.sqrt(fr.d_x ** 2 + fr.d_y ** 2 + d_z ** 2)
    return norm / jnp.maximum(jnp.abs(fr.d_x), 1e-9) * geo.d_voxel[2]


def ray_plane(fr: RayFrame, x, geo: ConeGeometry):
    """Marching plane at world ``x``: ray parameter, validity, y weights.

    Returns ``s_par`` (1, Nu), the forward-ray mask ``valid`` (1, Nu) as
    f32, and the y interpolation matrix ``wy`` (Ny, Nu).
    """
    ny = geo.n_voxel[1]
    nu = geo.n_detector[1]
    dy, offy = geo.d_voxel[1], geo.off_origin[1]
    s_par = (x - fr.sx) * fr.inv_dx
    yw = fr.sy + s_par * fr.d_y
    fj = (yw - offy) / dy + (ny - 1) / 2.0
    wy = hat(fj - iota_f32((ny, nu), 0))
    valid = ((s_par > 0.0) & (s_par <= 1.0)).astype(jnp.float32)
    return s_par, valid, wy


def ray_fk(fr: RayFrame, s_par, d_z, z0, geo: ConeGeometry) -> jnp.ndarray:
    """Slab-local float z row of every ray of a tile at one plane (8, Nu)."""
    nz = geo.n_voxel[0]
    dz, offz = geo.d_voxel[0], geo.off_origin[0]
    return ((fr.sz + s_par * d_z - offz) / dz + (nz - 1) / 2.0) - z0


def compiler_params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


# --------------------------------------------------------------------------
# forward projection kernel
# --------------------------------------------------------------------------

def _fp_kernel(c_ref, xc_ref, z0_ref, vol_ref, out_ref, colz_ref, seg_ref,
               *, geo: ConeGeometry, px: int, ab: int):
    """One (angle_block, slab) grid step: ``ab`` angles x ``px`` planes.

    ``vol_ref`` holds ``px`` marching planes of a z slab that starts at the
    (traced) global plane ``z0_ref[0]`` -- the full volume when the slab
    height is ``Nz``, a streamed axial slab otherwise.
    """
    a_first = pl.program_id(0) * ab
    s_idx = pl.program_id(1)
    z0 = z0_ref[0]
    n_kc = colz_ref.shape[0] // ROWS
    n_vt = seg_ref.shape[0] // ROWS

    @pl.when(s_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def angle_body(a, carry):
        fr = ray_frame(c_ref, a_first + a, geo)

        def seg_body(t, c):
            d_z, _ = ray_rows(fr, t, geo)
            seg_ref[pl.ds(pl.multiple_of(t * ROWS, ROWS), ROWS), :] = \
                ray_seg(fr, d_z, geo)
            return c
        jax.lax.fori_loop(0, n_vt, seg_body, 0)

        def plane_body(p, c):
            s_par, valid, wy = ray_plane(fr, xc_ref[s_idx * px + p], geo)
            colz_ref[...] = jnp.dot(vol_ref[p], wy, precision=HIGHEST,
                                    preferred_element_type=jnp.float32)

            def tile_body(t, c2):
                d_z, row_ok = ray_rows(fr, t, geo)
                fk = ray_fk(fr, s_par, d_z, z0, geo)
                c_lo, c_hi = chunk_window(fk, (valid > 0.0) & row_ok, n_kc)
                acc = gather_rows(fk, colz_ref, c_lo, c_hi)
                sl = pl.ds(pl.multiple_of(t * ROWS, ROWS), ROWS)
                out_ref[a, sl, :] += acc * seg_ref[sl, :] * valid
                return c2
            return jax.lax.fori_loop(0, n_vt, tile_body, c)
        return jax.lax.fori_loop(0, px, plane_body, carry)

    jax.lax.fori_loop(0, ab, angle_body, 0)


def plane_centers(geo: ConeGeometry, n_planes: int) -> jnp.ndarray:
    """World x of marching planes ``0..n_planes-1`` (continues past Nx)."""
    nx = geo.n_voxel[2]
    return jnp.asarray((np.arange(n_planes) - (nx - 1) / 2.0)
                       * geo.d_voxel[2] + geo.off_origin[2], jnp.float32)


def padded_angle_constants(geo: ConeGeometry, angles, n_pad: int):
    """Flat SMEM constant table, padded by repeating the last angle."""
    angles = jnp.asarray(angles, jnp.float32).reshape(-1)
    tail = n_pad - angles.shape[0]
    if tail:
        angles = jnp.concatenate(
            [angles, jnp.broadcast_to(angles[-1:], (tail,))], 0)
    return angle_constants(geo, angles).reshape(-1)


def fp_ray_pallas(vol: jnp.ndarray, geo: ConeGeometry, angles,
                  slab_planes: int = 16, interpret: bool = True,
                  z0=0, angle_block: int = 8) -> jnp.ndarray:
    """Forward-project x-dominant ``angles`` with the Pallas kernel.

    ``slab_planes`` marching planes are streamed per grid step and each
    serves ``angle_block`` angles.  VMEM holds two slab buffers
    (``slab_planes * Nz * Ny * 4`` bytes each), two ``angle_block``
    projection blocks and a few ``(Nz, Nu)`` / ``(Nv, Nu)`` scratch planes;
    :mod:`repro.kernels.autotune` sizes the blocks to fit.

    ``vol`` may be an axial slab of ``geo``'s volume: z planes
    ``[z0, z0 + vol.shape[0])`` -- the result is that slab's *partial*
    projection, and summing over a disjoint slab partition reproduces the
    monolithic projection exactly, which is how the out-of-core streaming
    executor drives this kernel.  ``angles`` and ``z0`` may be traced
    (the cached-jit dispatch in :mod:`repro.core.backend` relies on it).
    Non-divisor blocks pad: zero planes / rows contribute nothing, padded
    angles and detector rows are dropped.
    """
    nz, ny, nx = geo.n_voxel
    nv, nu = geo.n_detector
    sp = min(int(slab_planes), nx)
    n_slabs = -(-nx // sp)
    nx_pad = n_slabs * sp
    nz_slab = vol.shape[0]
    nz_rows, nv_rows = round_up(nz_slab, ROWS), round_up(nv, ROWS)
    n_angles = jnp.asarray(angles).reshape(-1).shape[0]
    ab, n_ab = balanced_block(n_angles, angle_block)

    # (nz_slab, Ny, Nx) -> (Nx_pad, Nz_rows, Ny): marching-axis planes,
    # zero-padded (zero voxels add nothing to any line integral)
    vol_t = jnp.transpose(jnp.asarray(vol, jnp.float32), (2, 0, 1))
    vol_t = jnp.pad(vol_t, ((0, nx_pad - nx), (0, nz_rows - nz_slab), (0, 0)))
    consts = padded_angle_constants(geo, angles, n_ab * ab)
    z0_arr = jnp.asarray(z0, jnp.float32).reshape(1)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)

    out = pl.pallas_call(
        functools.partial(_fp_kernel, geo=geo, px=sp, ab=ab),
        grid=(n_ab, n_slabs),
        in_specs=[
            smem(),
            smem(),
            smem(),
            pl.BlockSpec((sp, nz_rows, ny), lambda a_, s_: (s_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((ab, nv_rows, nu), lambda a_, s_: (a_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_ab * ab, nv_rows, nu), jnp.float32),
        scratch_shapes=[pltpu.VMEM((nz_rows, nu), jnp.float32),
                        pltpu.VMEM((nv_rows, nu), jnp.float32)],
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name="fp_ray",
    )(consts, plane_centers(geo, nx_pad), z0_arr, vol_t)
    return out[:n_angles, :nv]
