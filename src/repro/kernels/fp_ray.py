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
  - z: the tap index ``fk(v, u)`` is affine in the detector row and
    monotone in the column, so every 8-row detector tile reads a narrow
    band of z rows in each 128-lane block of columns.  Each block's band
    follows from its corner rays by scalar arithmetic
    (:func:`tile_windows`, no vector reduction), and one loop sweeps every
    block's band in aligned 8-row chunks on the VPU (:func:`gather_blocks`).

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

from repro.core.geometry import ConeGeometry, dominant_axis_mask

ROWS = 8                    # sublane tile: detector rows / z rows per step
LANES = 128                 # lane tile: detector columns per z window
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


def z_window(a, s_a, s_b, d0, d1, n_kc: int, xp=jnp):
    """Aligned 8-row chunks ``[c_lo, c_lo + n)`` holding every tap of one
    lane block of a detector tile, from scalars in chunks (rows / 8).

    A ray of the tile reads z row ``fk = 8 * (a + s * d)`` (:func:`ray_fk`):
    ``s`` is its ray parameter at the plane, ``d`` its row's z slope
    (:func:`z_slope`).  ``s_a``, ``s_b`` are the parameters of the block's
    edge columns, clipped to the valid ray range ``[0, 1]`` (``s`` is
    monotone in the column: the fan stays below 45 deg of the marching
    axis), and ``d0 <= d1`` the slopes of the tile's first and last row.
    ``fk`` is bilinear in ``(s, d)`` and ``s >= 0``, so its range is spanned
    by these corners.  Taps reach row ``floor(fk) + 1``; one row of margin
    on each side absorbs rounding between this and the per-ray expression.
    Scalars in the kernels, arrays on the host (``xp=np``): one rule for
    both.
    """
    lo = (a - 1.0 / ROWS) + xp.minimum(s_a * d0, s_b * d0)
    hi = (a + (ROWS + 2.0) / ROWS) + xp.maximum(s_a * d1, s_b * d1)
    c_lo = xp.clip(lo, 0.0, n_kc).astype(xp.int32)
    c_hi = xp.clip(hi, 0.0, n_kc + (ROWS - 1.0) / ROWS).astype(xp.int32)
    return c_lo, c_hi - c_lo


def z_origin(sz, z0, geo: ConeGeometry):
    """``a`` of :func:`z_window`: the slab-local z of the source, in
    chunks."""
    nz, dz, offz = geo.n_voxel[0], geo.d_voxel[0], geo.off_origin[0]
    return ((sz - offz) * (1.0 / dz) + (nz - 1) / 2.0 - z0) * (1.0 / ROWS)


def z_slope(iv, sz, geo: ConeGeometry):
    """``d`` of :func:`z_window`: z chunks per unit ray parameter of
    detector row ``iv``."""
    nv, dv, offv = geo.n_detector[0], geo.d_detector[0], geo.off_detector[0]
    per_row = 1.0 / (geo.d_voxel[0] * ROWS)
    return iv * (dv * per_row) + (offv - sz - (nv - 1) / 2.0 * dv) * per_row


def lane_block(nu: int) -> int:
    """Detector columns that share one z window: a 128-lane block where
    such blocks tile the width, else the whole width."""
    return LANES if nu % LANES == 0 else nu


def block_starts(windows, n_kc: int):
    """Shared trip count of a tile's lane-block sweep and each block's
    first chunk.

    Every block sweeps ``trips = max_b n_b`` chunks.  A block whose window
    ``[c_lo, c_lo + n)`` would then run past the slab starts lower, at
    ``n_kc - trips``: it still covers its window, and it visits in-bounds
    chunks, each once and with its true base row, so the extra chunks add
    exact zeros and nothing is counted twice.
    """
    trips = functools.reduce(jnp.maximum, [n for _, n in windows])
    return [jnp.minimum(c_lo, n_kc - trips) for c_lo, _ in windows], trips


def block_offsets(f: jnp.ndarray, starts, bw: int):
    """``f - base`` of each ``bw``-lane block at its first chunk.

    A step ``k`` later the offset is ``t0 - 8k``: for every tap with
    weight both subtractions are exact (``base <= f`` there), so the
    weights are bit-identical to ``f - (base + 8k)``.
    """
    return [f[:, b * bw:(b + 1) * bw] - (c * ROWS).astype(jnp.float32)
            for b, c in enumerate(starts)]


def gather_blocks(f: jnp.ndarray, src_ref, windows, bw: int):
    """:func:`gather_rows` with one window per ``bw``-lane block.

    ``windows[b] = (c_lo, n)`` (:func:`z_window`) covers block ``b``'s
    taps; one loop sweeps every block (:func:`block_starts`), so each step
    works on the whole tile.  Returns one ``(8, bw)`` accumulator per
    block.  Per lane the nonzero taps are summed in the same order, with
    the same weights, as :func:`gather_rows` with any covering window.
    """
    starts, trips = block_starts(windows, src_ref.shape[0] // ROWS)
    t0 = block_offsets(f, starts, bw)

    def body(k, accs):
        step = k * ROWS
        out = []
        for b, (c, acc) in enumerate(zip(starts, accs)):
            rows = src_ref[pl.ds(pl.multiple_of(c * ROWS + step, ROWS), ROWS),
                           b * bw:(b + 1) * bw]
            t = t0[b] - step.astype(jnp.float32)
            for r in range(ROWS):
                acc = acc + hat(t - float(r)) * rows[r:r + 1, :]
            out.append(acc)
        return tuple(out)
    zeros = tuple(jnp.zeros((ROWS, bw), jnp.float32) for _ in windows)
    return jax.lax.fori_loop(0, trips, body, zeros)


def scatter_blocks(f: jnp.ndarray, g: jnp.ndarray, dst_ref, windows,
                   bw: int):
    """Transpose of :func:`gather_blocks`: ``dst[k] += sum_i hat(f[i]-k)
    g[i]`` over the same per-block windows.

    The weights are bit-identical to :func:`gather_blocks`' (same
    ``(f - base) - r`` expression), so the pair is an exact adjoint.
    """
    local = iota_f32((ROWS, 1), 0)
    starts, trips = block_starts(windows, dst_ref.shape[0] // ROWS)
    t0 = block_offsets(f, starts, bw)
    gb = [g[:, b * bw:(b + 1) * bw] for b in range(len(windows))]

    def body(k, carry):
        step = k * ROWS
        for b, c in enumerate(starts):
            t = t0[b] - step.astype(jnp.float32)
            upd = jnp.zeros((ROWS, bw), jnp.float32)
            for i in range(ROWS):
                upd = upd + hat(t[i:i + 1, :] - local) * gb[b][i:i + 1, :]
            sl = pl.ds(pl.multiple_of(c * ROWS + step, ROWS), ROWS)
            dst_ref[sl, b * bw:(b + 1) * bw] += upd
        return carry
    jax.lax.fori_loop(0, trips, body, 0)


class RayFrame(NamedTuple):
    """Per-angle ray geometry: source scalars and per-column directions."""
    sx: jnp.ndarray
    sy: jnp.ndarray
    sz: jnp.ndarray
    d_x: jnp.ndarray        # (1, Nu) ray direction x (pixel minus source)
    d_y: jnp.ndarray        # (1, Nu)
    inv_dx: jnp.ndarray     # (1, Nu) guarded 1 / d_x
    inv_dx_edges: tuple     # scalar 1 / d_x at each lane block's edge columns


def guarded_inv(d):
    return 1.0 / jnp.where(jnp.abs(d) < 1e-9, 1e-9, d)


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
    edges = tuple(guarded_inv(dcx + ((col - (nu - 1) / 2.0) * du + offu)
                              * eux - sx)
                  for col in edge_columns(nu))
    return RayFrame(sx, sy, sz, d_x, d_y, guarded_inv(d_x), edges)


def edge_columns(nu: int):
    """First and last detector column of every lane block, in order."""
    bw = lane_block(nu)
    return [c for b in range(0, nu, bw) for c in (b, b + bw - 1)]


def edge_spans(fr: RayFrame, x):
    """Ray parameters of the lane blocks' edge columns at plane ``x``,
    clipped to ``[0, 1]``: ``((s_a, s_b), ...)`` per block (scalars)."""
    s = [jnp.clip((x - fr.sx) * inv, 0.0, 1.0) for inv in fr.inv_dx_edges]
    return tuple(zip(s[0::2], s[1::2]))


def tile_windows(spans, za, t, sz, n_kc: int, geo: ConeGeometry):
    """Per-lane-block z windows of detector-row tile ``t`` at one plane,
    ``((c_lo, n), ...)``, from the plane's :func:`edge_spans` and the
    source's slab-local z ``za`` (:func:`z_origin`): scalar work only."""
    nv = geo.n_detector[0]
    rows = (t * ROWS, jnp.minimum(t * ROWS + ROWS - 1, nv - 1))
    d0, d1 = (z_slope(r.astype(jnp.float32), sz, geo) for r in rows)
    return tuple(z_window(za, s_a, s_b, d0, d1, n_kc) for s_a, s_b in spans)


def ray_rows(fr: RayFrame, t, geo: ConeGeometry):
    """Detector-row tile ``t``: ray z directions (8, 1)."""
    nv = geo.n_detector[0]
    dv, offv = geo.d_detector[0], geo.off_detector[0]
    iv = iota_f32((ROWS, 1), 0, t * ROWS)
    return (iv - (nv - 1) / 2.0) * dv + offv - fr.sz


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
    bw = lane_block(out_ref.shape[2])

    @pl.when(s_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def angle_body(a, carry):
        fr = ray_frame(c_ref, a_first + a, geo)
        za = z_origin(fr.sz, z0, geo)

        def seg_body(t, c):
            seg_ref[pl.ds(pl.multiple_of(t * ROWS, ROWS), ROWS), :] = \
                ray_seg(fr, ray_rows(fr, t, geo), geo)
            return c
        jax.lax.fori_loop(0, n_vt, seg_body, 0)

        def plane_body(p, c):
            x = xc_ref[s_idx * px + p]
            s_par, valid, wy = ray_plane(fr, x, geo)
            spans = edge_spans(fr, x)
            colz_ref[...] = jnp.dot(vol_ref[p], wy, precision=HIGHEST,
                                    preferred_element_type=jnp.float32)

            def tile_body(t, c2):
                fk = ray_fk(fr, s_par, ray_rows(fr, t, geo), z0, geo)
                wins = tile_windows(spans, za, t, fr.sz, n_kc, geo)
                accs = gather_blocks(fk, colz_ref, wins, bw)
                sl = pl.ds(pl.multiple_of(t * ROWS, ROWS), ROWS)
                for b, acc in enumerate(accs):
                    cols = slice(b * bw, (b + 1) * bw)
                    out_ref[a, sl, cols] += (acc * seg_ref[sl, cols]
                                             * valid[:, cols])
                return c2
            return jax.lax.fori_loop(0, n_vt, tile_body, c)
        return jax.lax.fori_loop(0, px, plane_body, carry)

    jax.lax.fori_loop(0, ab, angle_body, 0)


def plane_centers(geo: ConeGeometry, n_planes: int) -> jnp.ndarray:
    """World x of marching planes ``0..n_planes-1`` (continues past Nx)."""
    nx = geo.n_voxel[2]
    return jnp.asarray((np.arange(n_planes) - (nx - 1) / 2.0)
                       * geo.d_voxel[2] + geo.off_origin[2], jnp.float32)


def z_chunks_per_tile(geo: ConeGeometry, angles) -> float:
    """Mean z chunks the ray kernels sweep per detector tile and plane.

    The sweep's trip count, ``max`` over lane blocks of each block's
    :func:`z_window`, on the full volume (``z0 = 0``), averaged over every
    marching plane and detector tile of a strided subsample of at most 16
    of ``angles``; y-dominant angles count as the x-dominant ones the
    kernels run after the -90 deg rotation.
    """
    nz, _, nx = geo.n_voxel
    nv, nu = geo.n_detector
    du, offu = geo.d_detector[1], geo.off_detector[1]
    a = np.asarray(angles, np.float32).reshape(-1)
    a = a[::-(-a.size // 16)]
    a = np.where(dominant_axis_mask(a), a, a - np.float32(np.pi / 2))
    k = np.asarray(angle_constants(geo, a), np.float64)[:, None, None, None]
    sx, sz, dcx, eux = k[..., 0], k[..., 2], k[..., 3], k[..., 5]
    u = (np.asarray(edge_columns(nu)) - (nu - 1) / 2.0) * du + offu
    x = np.asarray(plane_centers(geo, nx), np.float64)[:, None, None]
    span = np.clip((x - sx) / (dcx + u * eux - sx), 0.0, 1.0)  # (A,P,1,E)
    iv = np.arange(0, round_up(nv, ROWS), ROWS, dtype=np.float64)[:, None]
    d0 = z_slope(iv, sz, geo)
    d1 = z_slope(np.minimum(iv + ROWS - 1, nv - 1), sz, geo)
    _, n = z_window(z_origin(sz, 0.0, geo), span[..., 0::2], span[..., 1::2],
                    d0, d1, -(-nz // ROWS), xp=np)         # (A, P, T, B)
    return float(n.max(axis=-1).mean())


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
