"""Multi-device (pod-scale) projection operators via ``shard_map``.

This is the paper's multi-GPU layer generalised to TPU meshes (DESIGN.md SS5):

* forward projection: angles sharded over the ``data`` axis (paper SS2.1
  "each GPU will compute a set of independent projections"), the volume
  z-slab sharded over the ``model`` axis; per-device partial projections are
  reduced over ``model``.
* backprojection: projections sharded over ``data``, image slabs over
  ``model``; partial slab updates are reduced over ``data``.

The reductions are exact because the operators are additive over disjoint
z slabs / angle sets (tests/test_splitting.py, tests/test_distributed.py).

The communication decisions are no longer hard-coded at the call sites:
the plan IR's :class:`~repro.core.plan.CommSchedule` selects the
cross-shard reduction schedule (``"psum"`` baseline, ``"ppermute"``
ring, or a hierarchical two-level tree — intra-group ring then
cross-group hops, chosen from the mesh shape by
:func:`~repro.core.plan.choose_reduction`) and whether the FP angle set
is split by dominant axis on the host, so that non-ref backends run one
single-dominance kernel per shard instead of evaluating both variants
(the historical 2x local FP).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from .compat import axis_size as compat_axis_size, shard_map
from .geometry import ConeGeometry, dominant_axis_mask
from .plan import choose_reduction, hier_group_size
from .projector import (_joseph_xdom_one_angle, _rotate_vol_90,
                        backproject_voxel)


def _traced_dist(fn, op: str, mesh: Mesh, data_axis: str, model_axis: str,
                 **extra):
    """Wrap a jitted sharded op with a host-side ``op.dist.group`` span.

    Spans cannot be opened *inside* shard_map (the body is traced code),
    so each call gets one span carrying the shard layout.  The span times
    the host's dispatch of the call and never waits for the result (device
    time comes from the device trace); when tracing is disabled the raw
    async-dispatch fn runs."""
    n_data = mesh.shape[data_axis]
    n_model = mesh.shape[model_axis]

    def traced(*args):
        if not obs.enabled():
            return fn(*args)
        with obs.span("op.dist.group", obs.LAYER, op=op, data_shards=n_data,
                      model_shards=n_model, **extra):
            return fn(*args)
    return traced


def _reduce_partial(part, schedule: str, axis_name: str, n: int):
    """Cross-shard all-reduce of a partial result, per the plan's
    :func:`~repro.core.plan.choose_reduction` schedule.

    ``"psum"`` is the one-shot baseline; ``"ring"`` runs ``n - 1``
    ppermute hops each overlappable with compute; ``"hier"`` reduces
    within contiguous groups first (ring), then accumulates the group
    sums with group-stride hops — Petascale XCT's intra-node-before-
    inter-node tree mapped onto one mesh axis.  All three produce the
    full sum on every shard (summation order differs, so only ``psum``
    is bit-identical to the historical default)."""
    if schedule == "psum" or n == 1:
        return jax.lax.psum(part, axis_name)
    if schedule == "ring":
        perm = [(j, (j + 1) % n) for j in range(n)]

        def hop(_, acc_part):
            acc, p = acc_part
            p = jax.lax.ppermute(p, axis_name, perm)
            return acc + p, p
        acc, _ = jax.lax.fori_loop(0, n - 1, hop, (part, part))
        return acc
    if schedule == "hier":
        g = hier_group_size(n)
        intra = [(j, (j // g) * g + ((j % g) + 1) % g) for j in range(n)]
        inter = [(j, (j + g) % n) for j in range(n)]

        def hop1(_, acc_part):
            acc, p = acc_part
            p = jax.lax.ppermute(p, axis_name, intra)
            return acc + p, p
        group_sum, _ = jax.lax.fori_loop(0, g - 1, hop1, (part, part))

        def hop2(_, tot_rot):
            tot, rot = tot_rot
            rot = jax.lax.ppermute(rot, axis_name, inter)
            return tot + rot, rot
        total, _ = jax.lax.fori_loop(0, n // g - 1, hop2,
                                     (group_sum, group_sum))
        return total
    raise ValueError(f"unknown reduction schedule {schedule!r} "
                     f"(have psum | ring | hier)")


def _joseph_any_angle(vol, vol_rot, geo: ConeGeometry, theta, z0):
    """Joseph integral at one angle with a *traced* dominant-axis decision.

    Needed inside shard_map where an angle shard may mix x- and y-dominant
    angles.  ``lax.cond`` under ``lax.map`` stays a true branch (sequential
    scan), so only one projector runs per angle.
    """
    nz, ny, nx = geo.n_voxel
    x_centers = jnp.asarray(
        (np.arange(nx) - (nx - 1) / 2.0) * geo.d_voxel[2] + geo.off_origin[2],
        dtype=jnp.float32)
    xdom = jnp.abs(jnp.cos(theta)) >= jnp.abs(jnp.sin(theta))
    return jax.lax.cond(
        xdom,
        lambda: _joseph_xdom_one_angle(vol, geo, theta, x_centers, z0=z0),
        lambda: _joseph_xdom_one_angle(vol_rot, geo, theta - jnp.pi / 2,
                                       x_centers, z0=z0),
    )


def _fp_local(vol_slab, angles_local, geo: ConeGeometry, z0):
    """Partial FP of a z slab for a local angle set (any dominance mix)."""
    vol_rot = _rotate_vol_90(vol_slab)

    def one(theta):
        return _joseph_any_angle(vol_slab, vol_rot, geo, theta, z0)

    return jax.lax.map(one, angles_local)


def _fp_local_fn(geo: ConeGeometry, backend: Optional[str]):
    """Local slab-FP for an arbitrary-dominance angle shard, on the
    selected kernel backend.

    The dominant axis is a *static* host decision in the plain/stream
    paths, but a shard_map angle shard may mix dominances, and the
    Pallas FP kernel is single-dominance.  The ref backend keeps the
    per-angle ``lax.cond`` (one projector runs per angle); other
    backends evaluate both dominance variants for the shard and select
    per angle — 2x local FP compute.  This is only the *fallback* for
    ``dominance_split=False``: the default dist FP path regroups the
    angles by dominance on the host so every shard runs exactly one
    single-dominance kernel (see :func:`dist_forward_project`).
    """
    from .backend import get_backend, resolve
    if resolve(backend) == "ref":
        return lambda vol_slab, angles_local, z0: _fp_local(
            vol_slab, angles_local, geo, z0)
    bk = get_backend(backend)
    fpx = bk.fp(geo, xdom=True)
    fpy = bk.fp(geo, xdom=False)

    def f(vol_slab, angles_local, z0):
        px = fpx(vol_slab, angles_local, z0)
        py = fpy(vol_slab, angles_local, z0)
        xdom = jnp.abs(jnp.cos(angles_local)) >= jnp.abs(jnp.sin(angles_local))
        return jnp.where(xdom[:, None, None], px, py)
    return f


def dist_forward_project(mesh: Mesh, geo: ConeGeometry,
                         data_axis: str = "data", model_axis: str = "model",
                         reduce: Optional[str] = None,
                         backend: Optional[str] = None,
                         dominance_split: Optional[bool] = None,
                         comm=None):
    """Build a sharded FP: ``f(vol, angles) -> proj``.

    ``vol`` sharded ``P(model, None, None)`` (z slabs); ``angles`` sharded
    ``P(data)``; output sharded ``P(data, None, None)``.

    Both communication decisions come off the plan IR: ``reduce`` selects
    the cross-slab reduction schedule (``"psum"`` | ``"ring"`` |
    ``"hier"``; default ``None`` reads ``comm.reduction`` or derives it
    from the model-axis size via
    :func:`~repro.core.plan.choose_reduction`), and ``dominance_split``
    (default from ``comm``, else on) regroups the angle set by dominant
    axis on the host so each group runs one *single-dominance* sharded
    call — on non-ref backends this kills the 2x local FP of evaluating
    both kernel variants per shard (:func:`_fp_local_fn`; ref needs no
    split, its per-angle ``lax.cond`` already runs one projector).  Each
    group is padded to the data-axis size with
    :func:`pad_angles`-style duplicate angles and the rows scatter back
    to input order afterwards, so the wrapper is call-compatible with
    the plain sharded fn.
    """
    n_model = mesh.shape[model_axis]
    n_data = mesh.shape[data_axis]
    nz = geo.n_voxel[0]
    if nz % n_model:
        raise ValueError(f"Nz={nz} not divisible by model axis {n_model}")
    planes = nz // n_model
    if comm is not None:
        if reduce is None:
            reduce = comm.reduction
        if dominance_split is None:
            dominance_split = comm.dominance_split
    if reduce is None:
        reduce = choose_reduction(n_model)
    if dominance_split is None:
        dominance_split = True
    from .backend import get_backend, resolve
    split = dominance_split and resolve(backend) != "ref"

    def sharded(fp_local):
        @jax.named_scope("repro.op.fp")
        def body(vol_slab, angles_local):
            z0 = jax.lax.axis_index(model_axis) * planes
            part = fp_local(vol_slab, angles_local, z0)
            return _reduce_partial(part, reduce, model_axis, n_model)
        fn = shard_map(
            body, mesh=mesh,
            in_specs=(P(model_axis, None, None), P(data_axis)),
            out_specs=P(data_axis, None, None), check_vma=False)
        return jax.jit(fn)

    if not split:
        return _traced_dist(sharded(_fp_local_fn(geo, backend)), "dist_fp",
                            mesh, data_axis, model_axis, reduce=reduce)

    # Host-level dominance split: one single-dominance sharded call per
    # non-empty dominance group.  Built lazily so an all-one-dominance
    # workload never even fetches the other kernel variant from the
    # dispatch table (asserted via dispatch_cache_keys in the tests).
    bk = get_backend(backend)
    fns = {}

    def fn_for(xdom: bool):
        if xdom not in fns:
            fp1 = bk.fp(geo, xdom=xdom)
            fns[xdom] = _traced_dist(
                sharded(lambda vs, al, z0, _fp=fp1: _fp(vs, al, z0)),
                "dist_fp", mesh, data_axis, model_axis, reduce=reduce,
                xdom=xdom)
        return fns[xdom]

    nv, nu = geo.n_detector

    def call(vol, angles):
        angles_np = np.asarray(angles, np.float32)
        xm = dominant_axis_mask(angles_np)
        groups = [(True, np.nonzero(xm)[0]), (False, np.nonzero(~xm)[0])]
        groups = [(x, i) for x, i in groups if i.size]
        parts = []
        for xdom, idx in groups:
            padded, valid = pad_angles(angles_np[idx], n_data)
            outp = fn_for(xdom)(vol, jnp.asarray(padded))
            parts.append((idx, outp if valid.all() else outp[:idx.size]))
        if len(parts) == 1 and parts[0][0].size == len(angles_np):
            return parts[0][1]     # single dominance: rows already ordered
        out = jnp.zeros((len(angles_np), nv, nu), jnp.float32)
        with obs.span("reduce", "reduce", op="dist_fp", schedule=reduce,
                      groups=len(parts),
                      bytes=int(len(angles_np)) * nv * nu * 4):
            for idx, p in parts:
                out = out.at[jnp.asarray(idx)].set(p)
        return out
    # the per-dominance sharded FP, ``(vol, angles) -> proj`` with angles a
    # multiple of the data axis: lowerable for a described (absent) mesh
    call.sharded = fn_for
    return call


def dist_backproject(mesh: Mesh, geo: ConeGeometry, weight: str = "fdk",
                     data_axis: str = "data", model_axis: str = "model",
                     backend: Optional[str] = None, reduce: str = "psum",
                     comm=None):
    """Build a jitted sharded BP: ``g(proj, angles) -> vol``.

    ``proj``/``angles`` sharded over ``data``; output volume z-sharded over
    ``model`` (each device updates its own slab from its angle subset, then
    the partial updates are reduced over ``data`` -- additive in angles).
    ``backend`` selects the slab kernel (the voxel-driven BP is
    dominance-free, so the Pallas kernel drops straight in; no dominance
    split applies here).  ``reduce`` selects the data-axis reduction
    schedule; unlike the FP it defaults to ``"psum"`` regardless of the
    plan (``comm`` is accepted for API symmetry) because the historical
    reduction order is part of the bit-exactness contract the serving
    layer's preemption/restore tests rely on.
    """
    from .backend import get_backend
    n_model = mesh.shape[model_axis]
    n_data = mesh.shape[data_axis]
    nz = geo.n_voxel[0]
    if nz % n_model:
        raise ValueError(f"Nz={nz} not divisible by model axis {n_model}")
    planes = nz // n_model
    bp = get_backend(backend).bp(geo, planes=planes, weight=weight)

    @jax.named_scope("repro.op.bp")
    def body(proj_local, angles_local):
        z0 = jax.lax.axis_index(model_axis) * planes
        slab = bp(proj_local, angles_local, z0)
        return _reduce_partial(slab, reduce, data_axis, n_data)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(data_axis, None, None), P(data_axis)),
        out_specs=P(model_axis, None, None), check_vma=False)
    return _traced_dist(jax.jit(fn), "dist_bp", mesh, data_axis,
                        model_axis, weight=weight)


def dist_backproject_matched(mesh: Mesh, geo: ConeGeometry,
                             data_axis: str = "data",
                             model_axis: str = "model",
                             backend: Optional[str] = None):
    """Exact adjoint BP on the selected backend: ``f(proj, angles) -> vol``.

    Each device adjoints its angle shard's FP restricted to its z slab,
    then partial slab updates are summed over ``data`` — linearity over
    disjoint angle sets makes the stacked result the monolithic A^T
    exactly, so CGLS/FISTA keep their convergence guarantees on the
    distributed backend (same argument as the streaming matched adjoint).

    On the ref backend the per-shard adjoint is the historical
    ``jax.vjp`` of the mixed-dominance local FP.  Non-ref backends use
    the backend's native single-dominance ``bp_matched`` kernel and
    mirror :func:`dist_forward_project`'s host-level dominance split:
    one sharded call per non-empty dominance group (padded to the data
    axis with duplicate angles + zeroed projection rows — BP is linear,
    so they add nothing), group volumes summed.
    """
    from .backend import get_backend, resolve
    n_model = mesh.shape[model_axis]
    n_data = mesh.shape[data_axis]
    nz = geo.n_voxel[0]
    if nz % n_model:
        raise ValueError(f"Nz={nz} not divisible by model axis {n_model}")
    planes = nz // n_model

    if resolve(backend) == "ref":
        @jax.named_scope("repro.op.bp")
        def body(proj_local, angles_local):
            z0 = jax.lax.axis_index(model_axis) * planes
            zeros = jnp.zeros((planes,) + tuple(geo.n_voxel[1:]),
                              jnp.float32)

            def fwd(slab):
                return _fp_local(slab, angles_local, geo, z0)

            _, vjp = jax.vjp(fwd, zeros)
            return jax.lax.psum(vjp(proj_local)[0], data_axis)

        fn = shard_map(
            body, mesh=mesh,
            in_specs=(P(data_axis, None, None), P(data_axis)),
            out_specs=P(model_axis, None, None), check_vma=False)
        return _traced_dist(jax.jit(fn), "dist_bp_matched", mesh,
                            data_axis, model_axis)

    # Non-ref: lazily build one single-dominance sharded matched BP per
    # dominance group present in the workload (mirrors the dist FP's
    # host split; asserted via dispatch_cache_keys in the tests).
    bk = get_backend(backend)
    fns = {}

    def sharded(bm):
        @jax.named_scope("repro.op.bp")
        def body(proj_local, angles_local):
            z0 = jax.lax.axis_index(model_axis) * planes
            slab = bm(proj_local, angles_local, z0)
            return jax.lax.psum(slab, data_axis)
        fn = shard_map(
            body, mesh=mesh,
            in_specs=(P(data_axis, None, None), P(data_axis)),
            out_specs=P(model_axis, None, None), check_vma=False)
        return jax.jit(fn)

    def fn_for(xdom: bool):
        if xdom not in fns:
            bm = bk.bp_matched(geo, planes=planes, xdom=xdom)
            fns[xdom] = _traced_dist(sharded(bm), "dist_bp_matched", mesh,
                                     data_axis, model_axis, xdom=xdom)
        return fns[xdom]

    nv, nu = geo.n_detector

    def call(proj, angles):
        angles_np = np.asarray(angles, np.float32)
        xm = dominant_axis_mask(angles_np)
        groups = [(True, np.nonzero(xm)[0]), (False, np.nonzero(~xm)[0])]
        groups = [(x, i) for x, i in groups if i.size]
        proj = jnp.asarray(proj, jnp.float32)
        out = None
        for xdom, idx in groups:
            padded, valid = pad_angles(angles_np[idx], n_data)
            pj = proj[jnp.asarray(idx)]
            if not valid.all():
                pj = jnp.concatenate(
                    [pj, jnp.zeros((len(padded) - idx.size, nv, nu),
                                   jnp.float32)], 0)
            part = fn_for(xdom)(pj, jnp.asarray(padded))
            out = part if out is None else out + part
        if out is None:
            out = jnp.zeros(geo.n_voxel, jnp.float32)
        return out
    # the per-dominance sharded matched BP, ``(proj, angles) -> vol`` with
    # angles a multiple of the data axis (as the dist FP's ``sharded``)
    call.sharded = fn_for
    return call


def pad_angles(angles: np.ndarray, multiple: int):
    """Pad the angle set to a multiple of the data-axis size.

    Padded entries repeat the last angle; callers must consume the returned
    ``valid`` mask — drop the padded rows of a padded forward projection,
    and zero the padded rows before a backprojection (BP is linear, so zero
    rows add nothing to the slab sums).  ``CTOperator`` (mode="dist") does
    both automatically for non-divisible angle counts.
    """
    n = len(angles)
    n_pad = (-n) % multiple
    if n_pad == 0:
        return np.asarray(angles, np.float32), np.ones(n, bool)
    padded = np.concatenate([angles, np.full(n_pad, angles[-1])]).astype(np.float32)
    valid = np.concatenate([np.ones(n, bool), np.zeros(n_pad, bool)])
    return padded, valid


def halo_exchange(x: jnp.ndarray, depth: int, axis_name: str):
    """Exchange ``depth`` boundary planes with axis neighbours (paper SS2.3).

    ``x`` is a local z slab ``(planes, ...)``; returns ``x`` padded to
    ``planes + 2*depth`` with the neighbours' boundary planes (zeros at the
    global ends).  One ``ppermute`` pair per call -- this is the *only*
    communication the split TV regulariser performs every ``N_in`` inner
    iterations.
    """
    n = compat_axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    top = x[-depth:]      # send up (to idx+1)
    bot = x[:depth]       # send down (to idx-1)
    up_perm = [(i, i + 1) for i in range(n - 1)]
    down_perm = [(i + 1, i) for i in range(n - 1)]
    from_below = jax.lax.ppermute(top, axis_name, up_perm)     # neighbour idx-1's top
    from_above = jax.lax.ppermute(bot, axis_name, down_perm)   # neighbour idx+1's bottom
    pad_shape = (depth,) + x.shape[1:]
    from_below = jnp.where(idx > 0, from_below, jnp.zeros(pad_shape, x.dtype))
    from_above = jnp.where(idx < n - 1, from_above, jnp.zeros(pad_shape, x.dtype))
    return jnp.concatenate([from_below, x, from_above], axis=0)
