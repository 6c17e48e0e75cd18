"""Pallas TPU kernel: voxel-driven backprojector with projection streaming.

TPU adaptation of TIGRE's backprojection kernel (paper SS2.2, Fig 4/5):

* The Pallas grid iterates ``(z_block, y_block, angle_chunk)`` with the
  angle chunk innermost; the volume block stays resident in VMEM and is
  *accumulated* across chunks while the next chunk's projections are DMA'd
  in by the pipeline -- the paper's Fig 5 timeline (projections copied to
  the device while the voxel-update kernel runs), realised by BlockSpec
  pipelining instead of CUDA streams.  The block is laid out
  ``(y, z, x)`` so one y row of it is a ``(z, x)`` tile.
* The bilinear projection fetch is split like the FP's: for one y row the
  detector column ``fu(x)`` does not depend on z, so the u interpolation
  is an MXU matmul ``proj(Nv, Nu) @ Wu(Nu, Nx)``; the row ``fv`` is affine
  in z, so the v interpolation is a banded sweep over 8-plane tiles
  (:func:`~repro.kernels.fp_ray.gather_rows`).  Detector taps outside the
  detector get no weight, as in the ref bilinear gather.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.geometry import ConeGeometry

from .fp_ray import (HIGHEST, ROWS, balanced_block, chunk_window,
                     compiler_params, gather_rows, hat, iota_f32,
                     padded_angle_constants, round_up)


def _bp_kernel(c_ref, ys_ref, zs_ref, proj_ref, out_ref, q_ref, *,
               geo: ConeGeometry, by: int, ca: int, zb: int, weight: str):
    """One (z_block, y_block, angle_chunk) grid step.

    ``zs_ref[0]`` is the (traced) global starting plane of the output
    slab: the kernel updates planes ``[z_start, z_start + z_planes)`` of
    ``geo``'s volume -- the full volume when ``z_planes == Nz``, one
    streamed axial slab otherwise (the angle axis is additive, so chunked
    accumulation reproduces the monolithic result exactly).
    """
    nz, ny, nx = geo.n_voxel
    nv, nu = geo.n_detector
    dz, dy, dx = geo.d_voxel
    dv, du = geo.d_detector
    offz, offy, offx = geo.off_origin
    offv, offu = geo.off_detector
    n_vc = q_ref.shape[0] // ROWS
    z_first = pl.program_id(0) * zb
    y_first = pl.program_id(1) * by
    a_first = pl.program_id(2) * ca

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xs = (iota_f32((1, nx), 1) - (nx - 1) / 2.0) * dx + offx
    z_start = zs_ref[0]

    def angle_body(i, carry):
        # cos/sin recovered from e_u = (-sin, cos)
        base = (a_first + i) * 8
        sth, cth = -c_ref[base + 5], c_ref[base + 6]

        def row_body(r, c):
            y = ys_ref[y_first + r]
            p = xs * cth + y * sth                     # (1, Nx)
            q = -xs * sth + y * cth
            depth = geo.DSO - p
            mag = geo.DSD / depth
            fu = (q * mag - offu) / du + (nu - 1) / 2.0
            fv_scale = mag / dv
            if weight == "fdk":
                w2d = (geo.DSO / depth) ** 2
            elif weight == "pmatched":
                w2d = (geo.DSD / depth) ** 2 * (geo.DSO / geo.DSD)
            else:
                w2d = jnp.ones_like(depth)
            # u interpolation of every detector row at this y row's columns
            q_ref[...] = jnp.dot(proj_ref[i], hat(fu - iota_f32((nu, nx), 0)),
                                 precision=HIGHEST,
                                 preferred_element_type=jnp.float32)

            def tile_body(t, c2):
                k = iota_f32((ROWS, 1), 0, z_first + t * ROWS) + z_start
                zs = (k - (nz - 1) / 2.0) * dz + offz
                fv = zs * fv_scale - (offv / dv) + (nv - 1) / 2.0   # (8, Nx)
                c_lo, c_hi = chunk_window(fv, None, n_vc)
                sl = pl.ds(pl.multiple_of(t * ROWS, ROWS), ROWS)
                out_ref[r, sl, :] += gather_rows(fv, q_ref, c_lo, c_hi) * w2d
                return c2
            return jax.lax.fori_loop(0, zb // ROWS, tile_body, c)
        return jax.lax.fori_loop(0, by, row_body, carry)

    jax.lax.fori_loop(0, ca, angle_body, 0)


def bp_voxel_pallas(proj: jnp.ndarray, geo: ConeGeometry, angles,
                    z_block: int = 16, angle_chunk: int = 8,
                    weight: str = "fdk", interpret: bool = True,
                    z_start=0, z_planes: int = None,
                    y_block: int = 8) -> jnp.ndarray:
    """Backproject with the Pallas kernel.

    VMEM working set: a ``(y_block, z_block, Nx)`` volume block (resident,
    accumulated, double-buffered) + double-buffered ``angle_chunk``
    projections -- the paper's Alg 2 budget ("two buffers of size
    N_angles ... plus the image piece") -- + one ``(Nv, Nx)`` scratch.

    ``z_start`` (traced OK) + ``z_planes`` (static) select an axial slab
    of ``geo``'s volume (the paper's per-device image pieces) -- the
    out-of-core streaming executor accumulates angle chunks into such
    slabs.  ``angles`` may be traced (see :mod:`repro.core.backend`).
    Every axis pads to its block: extra planes and rows are computed then
    dropped, extra angles carry zero projections (BP is linear in the
    data, so they add nothing).
    """
    if weight not in ("fdk", "pmatched", "none"):
        raise ValueError(f"unknown weight {weight!r}")
    nz, ny, nx = geo.n_voxel
    nv, nu = geo.n_detector
    planes = nz if z_planes is None else int(z_planes)
    n_angles = jnp.asarray(angles).reshape(-1).shape[0]
    zb = round_up(min(int(z_block), planes), ROWS)
    n_zb = -(-planes // zb)
    by = min(int(y_block), ny)
    n_yb = -(-ny // by)
    ca, n_ch = balanced_block(n_angles, angle_chunk)
    nv_rows = round_up(nv, ROWS)

    proj = jnp.pad(jnp.asarray(proj, jnp.float32),
                   ((0, n_ch * ca - n_angles), (0, nv_rows - nv), (0, 0)))
    consts = padded_angle_constants(geo, angles, n_ch * ca)
    ys = jnp.asarray((np.arange(n_yb * by) - (ny - 1) / 2.0) * geo.d_voxel[1]
                     + geo.off_origin[1], jnp.float32)
    zs_arr = jnp.asarray(z_start, jnp.float32).reshape(1)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)

    out = pl.pallas_call(
        functools.partial(_bp_kernel, geo=geo, by=by, ca=ca, zb=zb,
                          weight=weight),
        grid=(n_zb, n_yb, n_ch),
        in_specs=[
            smem(),
            smem(),
            smem(),
            pl.BlockSpec((ca, nv_rows, nu), lambda z_, y_, c_: (c_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((by, zb, nx), lambda z_, y_, c_: (y_, z_, 0)),
        out_shape=jax.ShapeDtypeStruct((n_yb * by, n_zb * zb, nx),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((nv_rows, nx), jnp.float32)],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="bp_voxel",
    )(consts, ys, zs_arr, proj)
    return jnp.transpose(out[:ny, :planes], (1, 0, 2))
