"""Unified CT operator: one object, three execution modes, any kernel backend.

The paper's point is that the *same* algorithms run regardless of how the
operators are executed ("TIGRE's architecture is modular, thus all of the
GPU code is independent from the algorithm that uses it").  ``CTOperator``
exposes ``A`` (forward) and ``At`` (back) and hides the execution:

* ``mode="plain"``   -- monolithic jitted operators (volume fits on device).
* ``mode="stream"``  -- the paper's out-of-core double-buffered executor
                         (host-resident arrays, slab streaming).
* ``mode="dist"``    -- shard_map over a device mesh (angles x z-slabs).

All three are built from one memoized :class:`~repro.core.plan.ExecutionPlan`
(``self.plan``) and draw their kernels from the backend registry
(:mod:`repro.core.backend`): ``backend="ref"`` runs the pure-JAX
projectors, ``backend="pallas"`` the Pallas TPU kernels, ``"auto"``
(default) picks per JAX backend.  The plan fixes the slab/chunk/device
structure; the backend fixes the kernel that executes each piece — either
can change without touching the other (or the algorithms).

All modes and backends produce matching results (tests/test_splitting.py,
tests/test_distributed.py, tests/test_backend.py); algorithms in
``repro.core.algorithms`` are written against this interface only.
Exact-adjoint ("matched") weighting follows the backend too: the ref
backend builds it from ``jax.vjp``, the pallas backend from its native
transpose-shaped scatter kernel — see :mod:`repro.core.backend` and
tests/test_adjoint.py.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .backend import get_backend, resolve as resolve_backend
from .geometry import ConeGeometry, dominant_axis_mask
from .plan import ExecutionPlan, plan as plan_execution
from .splitting import MemoryModel


class CTOperator:
    """``A`` / ``At`` with selectable execution mode and kernel backend.

    Parameters
    ----------
    geo, angles : geometry and the (static, numpy) gantry angles.
    mode : "plain" | "stream" | "dist".
    bp_weight : default backprojection weighting ("matched" uses the exact
        vjp adjoint; "fdk"/"pmatched"/"none" use the voxel-driven kernel).
    mesh : required for mode="dist".
    memory : memory model for mode="stream" (defaults to an 11 GiB device).
    backend : kernel backend name ("ref" | "pallas" | "auto"/None).
    plan : pre-computed :class:`~repro.core.plan.ExecutionPlan`; derived
        (memoized) from the other arguments when omitted.
    """

    def __init__(self, geo: ConeGeometry, angles: np.ndarray,
                 mode: str = "plain", bp_weight: str = "matched",
                 mesh=None, memory: Optional[MemoryModel] = None,
                 devices: Optional[Sequence] = None,
                 backend: Optional[str] = None,
                 plan: Optional[ExecutionPlan] = None):
        self.geo = geo
        self.angles_np = np.asarray(angles, np.float32)
        self.angles = jnp.asarray(self.angles_np)
        self.mode = mode
        self.bp_weight = bp_weight
        self.mesh = mesh
        self.devices = devices
        self.memory = memory or MemoryModel()
        self.backend_name = resolve_backend(backend)
        self._backend = get_backend(self.backend_name)
        self._xdom = dominant_axis_mask(self.angles_np)

        if mode not in ("plain", "stream", "dist"):
            raise ValueError(f"unknown mode {mode!r}")
        if mode == "dist" and mesh is None:
            raise ValueError("mode='dist' needs a mesh")

        # one plan drives every mode: the stream executors interpret its
        # CommSchedule step list verbatim, plain mode is its n_slabs == 1
        # fast path, and dist mode reads its reduction / dominance-split
        # decisions (n_devices = the mesh's model axis, so the schedule's
        # reduction tree reflects the actual shard count; the plan still
        # carries the footprint/pass model the serving layer prices with)
        if mode == "dist":
            n_dev = mesh.shape.get("model", 1)
        elif mode == "stream" and devices:
            n_dev = len(devices)
        else:
            n_dev = 1
        self.plan = plan if plan is not None else \
            plan_execution(geo, len(self.angles_np), n_dev, self.memory)

        if mode == "dist":
            from .distributed import (dist_backproject,
                                      dist_backproject_matched,
                                      dist_forward_project)
            comm = self.plan.comm
            self._a = dist_forward_project(mesh, geo,
                                           backend=self.backend_name,
                                           comm=comm)
            self._at_fdk = dist_backproject(mesh, geo, weight="fdk",
                                            backend=self.backend_name,
                                            comm=comm)
            self._at_none = dist_backproject(mesh, geo, weight="none",
                                             backend=self.backend_name,
                                             comm=comm)
            self._at_pm = dist_backproject(mesh, geo, weight="pmatched",
                                           backend=self.backend_name,
                                           comm=comm)
            self._at_matched = dist_backproject_matched(
                mesh, geo, backend=self.backend_name)
            self._data_axis_size = mesh.shape["data"]
        elif mode == "stream":
            # kept as attributes: the executors (and older callers) read
            # the per-operator schedules straight off the shared plan
            self.plan_f = self.plan.forward
            self.plan_b = self.plan.backward

    def warmup(self, weight: Optional[str] = None) -> None:
        """Materialise this operator's dispatch entries ahead of first use.

        Fetches every kernel callable the configured mode/weighting will
        ask the backend registry for (building + jit-wrapping them into
        the shared dispatch table; XLA compilation proper stays lazy).
        The serve layer's autoscaler pre-warm calls this during the
        predictive lead window so a freshly scaled-up pod admits its
        first job without the operator-build stall.  Dist mode builds
        its sharded fns in ``__init__`` — nothing lazy is left there.
        """
        weight = weight or self.bp_weight
        has = [(True, bool(self._xdom.any())),
               (False, bool((~self._xdom).any()))]
        if self.mode == "plain":
            self._plain_fp(self.angles_np)
            if weight == "matched":
                self._backend.at_matched_mixed(self.geo, self._xdom)
            else:
                self._backend.bp(self.geo, planes=self.geo.n_voxel[0],
                                 weight=weight)
            return
        if self.mode == "stream":
            for xd, present in has:
                if present:
                    self._backend.fp(self.geo, xdom=xd)
            for z0, z1 in self.plan.backward.slab_ranges:
                if weight == "matched":
                    for xd, present in has:
                        if present:
                            self._backend.bp_matched(self.geo,
                                                     planes=z1 - z0,
                                                     xdom=xd)
                else:
                    self._backend.bp(self.geo, planes=z1 - z0,
                                     weight=weight)

    def kernel_config(self) -> dict:
        """The backend's (possibly autotuned) block-size config for this
        operator's geometry and angles — empty on backends without
        tunable blocks.  Surfaced in serve init events and the operator
        benchmarks."""
        return self._backend.kernel_config(self.geo,
                                           planes=self.geo.n_voxel[0],
                                           angles=self.angles_np)

    def _plain_fp(self, angles_np: np.ndarray):
        """Compiled forward for a concrete angle subset: the backend's
        mixed-dominance dispatch, cached process-wide per (geo, mask)."""
        return self._backend.fp_mixed(self.geo, dominant_axis_mask(angles_np))

    # ---- forward ----------------------------------------------------------
    def A(self, vol, angles=None):
        with obs.span("op.A", obs.LAYER):
            return self._forward(vol, angles)

    def _forward(self, vol, angles):
        if self.mode == "stream":
            a = self.angles_np if angles is None else np.asarray(angles)
            from .streaming import stream_forward
            return stream_forward(np.asarray(vol), self.geo, a, self.plan,
                                  devices=self.devices,
                                  backend=self.backend_name)
        if self.mode == "dist":
            from .distributed import pad_angles
            angles_np = self.angles_np if angles is None else \
                np.asarray(angles, np.float32)
            # shard_map needs the angle count divisible by the data axis;
            # pad with duplicates and drop the padded projections afterwards
            padded, valid = pad_angles(angles_np, self._data_axis_size)
            out = self._a(vol, jnp.asarray(padded))
            if valid.all():
                return out
            return out[:len(angles_np)]   # padding is always a suffix
        angles_np = self.angles_np if angles is None else np.asarray(angles)
        return self._plain_fp(angles_np)(vol, jnp.asarray(angles_np))

    # ---- backward ---------------------------------------------------------
    def At(self, proj, angles=None, weight: Optional[str] = None):
        with obs.span("op.At", obs.LAYER):
            return self._back(proj, angles, weight)

    def _back(self, proj, angles, weight):
        angles = self.angles if angles is None else angles
        weight = weight or self.bp_weight
        if self.mode == "stream":
            from .streaming import stream_backward
            # "matched" streams the exact per-slab vjp adjoint (CGLS keeps
            # its convergence guarantees out-of-core)
            return stream_backward(np.asarray(proj), self.geo,
                                   np.asarray(angles), self.plan,
                                   weight=weight, devices=self.devices,
                                   backend=self.backend_name)
        if self.mode == "dist":
            from .distributed import pad_angles
            angles_np = np.asarray(angles, np.float32)
            padded, valid = pad_angles(angles_np, self._data_axis_size)
            if not valid.all():
                # zero the padded duplicate projections: BP is linear in the
                # projections, so zero rows contribute nothing to the sums
                n_pad = len(padded) - len(angles_np)
                proj = jnp.concatenate(
                    [jnp.asarray(proj),
                     jnp.zeros((n_pad,) + tuple(self.geo.n_detector),
                               jnp.float32)], axis=0)
            angles = jnp.asarray(padded)
            if weight == "fdk":
                return self._at_fdk(proj, angles)
            if weight == "none":
                return self._at_none(proj, angles)
            if weight == "matched":
                return self._at_matched(proj, angles)
            return self._at_pm(proj, angles)
        angles_np = np.asarray(angles)
        if weight == "matched":
            # exact adjoint of the compiled mixed-dominance forward (ref:
            # vjp; pallas: native matched scatter kernels per dominance)
            at = self._backend.at_matched_mixed(
                self.geo, dominant_axis_mask(angles_np))
            return at(proj, jnp.asarray(angles_np))
        bp = self._backend.bp(self.geo, planes=self.geo.n_voxel[0],
                              weight=weight)
        return bp(proj, jnp.asarray(angles_np), 0)

    # ---- spectral norm estimate (power iterations) -------------------------
    def norm_squared_est(self, n_iter: int = 8, seed: int = 0) -> float:
        """Estimate ||A||_2^2 with power iteration on A^T A (matched pair)."""
        x = jax.random.normal(jax.random.PRNGKey(seed), self.geo.n_voxel,
                              jnp.float32)
        x = x / jnp.linalg.norm(x.ravel())
        lam = 1.0
        for _ in range(n_iter):
            y = self.At(self.A(x), weight="matched")
            lam = float(jnp.linalg.norm(y.ravel()))
            x = y / (lam + 1e-30)
        return lam

    def subset_indices(self, subset_size: int):
        """Contiguous angle subsets for OS methods (paper SS3.2 OS-SART)."""
        n = len(self.angles_np)
        return [np.arange(s, min(s + subset_size, n))
                for s in range(0, n, subset_size)]
