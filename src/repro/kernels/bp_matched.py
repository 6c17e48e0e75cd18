"""Pallas TPU kernel: exact transpose of the Joseph slab forward projector.

``fp_ray.py`` forward-projects by marching x planes and, per plane, doing a
y interpolation (an MXU matmul with the tent-weight matrix ``Wy``) followed
by a banded z interpolation over 8-row detector tiles.  A linear map's
transpose reuses the *same* weights with the data movement reversed, so
this kernel calls the same helpers (:func:`~repro.kernels.fp_ray.ray_frame`,
``ray_plane``, ``ray_fk``, and ``tile_windows``, which finds each 128-lane
block's z window from its corner rays on the scalar core) and transposes
the two steps:

* z gather :func:`~repro.kernels.fp_ray.gather_blocks`  ->
  :func:`~repro.kernels.fp_ray.scatter_blocks` (the same per-block
  windows, bit-identical weights);
* y matmul ``plane @ Wy``  ->  ``colz_bar @ Wy^T``.

Because every weight comes from the same fp32 expressions, the pair
satisfies <Ax, y> = <x, A^T y> to fp32 summation tolerance: exactly what
CGLS and FISTA need for their convergence guarantees (TIGRE paper SS2.2 --
the matched "A^T" pair, as opposed to the filtered/voxel-driven BP).

Grid is ``(slab, angle_block)`` with the angle dimension innermost: each
marching slab of the output volume stays resident and accumulates every
angle while the Pallas pipeline double-buffers the next projection block's
HBM->VMEM DMA -- the mirror image of the FP kernel's order.

Like ``fp_ray_pallas``, the wrapper pads the marching axis, the z rows,
the detector rows and the angle count (padded outputs are dropped, padded
inputs are zero: the exact transpose of the FP's padding), so any block
size is legal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.geometry import ConeGeometry

from .fp_ray import (HIGHEST, ROWS, balanced_block, compiler_params,
                     edge_spans, lane_block, padded_angle_constants,
                     plane_centers, ray_fk, ray_frame, ray_plane, ray_rows,
                     ray_seg, round_up, scatter_blocks, tile_windows,
                     z_origin)


def _bp_matched_kernel(c_ref, xc_ref, z0_ref, proj_ref, out_ref, colz_ref,
                       gseg_ref, *, geo: ConeGeometry, px: int, ab: int):
    """One (slab, angle_block) grid step: ``ab`` projections into ``px``
    planes, replaying ``_fp_kernel``'s weights with the data flow reversed.
    """
    s_idx = pl.program_id(0)
    a_first = pl.program_id(1) * ab
    z0 = z0_ref[0]
    n_kc = colz_ref.shape[0] // ROWS
    n_vt = gseg_ref.shape[0] // ROWS
    bw = lane_block(gseg_ref.shape[1])

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def angle_body(a, carry):
        fr = ray_frame(c_ref, a_first + a, geo)
        za = z_origin(fr.sz, z0, geo)

        def seg_body(t, c):
            # cotangent rays, pre-weighted by the FP's final ``acc * seg``
            sl = pl.ds(pl.multiple_of(t * ROWS, ROWS), ROWS)
            gseg_ref[sl, :] = proj_ref[a, sl, :] * ray_seg(
                fr, ray_rows(fr, t, geo), geo)
            return c
        jax.lax.fori_loop(0, n_vt, seg_body, 0)

        def plane_body(p, c):
            x = xc_ref[s_idx * px + p]
            s_par, valid, wy = ray_plane(fr, x, geo)
            spans = edge_spans(fr, x)
            colz_ref[...] = jnp.zeros_like(colz_ref)

            def tile_body(t, c2):
                fk = ray_fk(fr, s_par, ray_rows(fr, t, geo), z0, geo)
                wins = tile_windows(spans, za, t, fr.sz, n_kc, geo)
                sl = pl.ds(pl.multiple_of(t * ROWS, ROWS), ROWS)
                scatter_blocks(fk, gseg_ref[sl, :] * valid, colz_ref, wins,
                               bw)
                return c2
            jax.lax.fori_loop(0, n_vt, tile_body, 0)
            # transpose of the y matmul: colz_bar (Nz, Nu) @ Wy^T (Nu, Ny)
            out_ref[p] += jax.lax.dot_general(
                colz_ref[...], wy, (((1,), (1,)), ((), ())),
                precision=HIGHEST, preferred_element_type=jnp.float32)
            return c
        return jax.lax.fori_loop(0, px, plane_body, carry)

    jax.lax.fori_loop(0, ab, angle_body, 0)


def bp_matched_pallas(proj: jnp.ndarray, geo: ConeGeometry, angles,
                      slab_planes: int = 16, interpret: bool = True,
                      z0=0, z_planes: int | None = None,
                      angle_block: int = 8) -> jnp.ndarray:
    """Matched (exact-adjoint) backprojection of x-dominant ``angles``.

    Returns the slab ``(z_planes, Ny, Nx)`` such that for any volume slab
    ``x`` and projections ``y``::

        <fp_ray_pallas(x, geo, angles, z0=z0), y>
            == <x, bp_matched_pallas(y, geo, angles, z0=z0,
                                     z_planes=x.shape[0])>

    to fp32 tolerance.  ``z_planes`` defaults to the full ``Nz``; pass the
    slab height (with its ``z0``) to adjoint a streamed partial projection.
    ``angles`` and ``z0`` may be traced, mirroring ``fp_ray_pallas``.
    """
    nz, ny, nx = geo.n_voxel
    nv, nu = geo.n_detector
    nz_slab = nz if z_planes is None else int(z_planes)
    sp = min(int(slab_planes), nx)
    n_slabs = -(-nx // sp)
    nx_pad = n_slabs * sp
    nz_rows, nv_rows = round_up(nz_slab, ROWS), round_up(nv, ROWS)
    n_angles = jnp.asarray(angles).reshape(-1).shape[0]
    ab, n_ab = balanced_block(n_angles, angle_block)

    proj = jnp.pad(jnp.asarray(proj, jnp.float32),
                   ((0, n_ab * ab - n_angles), (0, nv_rows - nv), (0, 0)))
    consts = padded_angle_constants(geo, angles, n_ab * ab)
    z0_arr = jnp.asarray(z0, jnp.float32).reshape(1)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)

    out = pl.pallas_call(
        functools.partial(_bp_matched_kernel, geo=geo, px=sp, ab=ab),
        grid=(n_slabs, n_ab),
        in_specs=[
            smem(),
            smem(),
            smem(),
            pl.BlockSpec((ab, nv_rows, nu), lambda s_, a_: (a_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((sp, nz_rows, ny), lambda s_, a_: (s_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nx_pad, nz_rows, ny), jnp.float32),
        scratch_shapes=[pltpu.VMEM((nz_rows, nu), jnp.float32),
                        pltpu.VMEM((nv_rows, nu), jnp.float32)],
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name="bp_matched",
    )(consts, plane_centers(geo, nx_pad), z0_arr, proj)

    # (Nx_pad, Nz_rows, Ny) -> drop pad -> (Nz, Ny, Nx): the exact inverse
    # of fp_ray_pallas's input plane layout
    return jnp.transpose(out[:nx, :nz_slab], (1, 2, 0))
