"""Kernel-backend registry: named projector implementations, one dispatch.

The paper's modularity claim cuts both ways: the splitting plans
(:mod:`repro.core.plan`) are independent of the algorithms *and* of the
kernels that execute them.  This module is the kernel half of that
contract — a registry of named backends, each providing the same small
slab-operator surface:

* ``"ref"``    — the pure-JAX projectors in :mod:`repro.core.projector`
  (obviously correct, runs everywhere; the parity oracle).
* ``"pallas"`` — the Pallas TPU kernels in :mod:`repro.kernels`
  (``fp_ray``, ``bp_voxel``): Mosaic-compiled on real TPU backends,
  interpret mode elsewhere.
* ``"auto"``   — resolves per JAX backend: ``"pallas"`` on TPU hosts,
  ``"ref"`` otherwise.

Every executor (``CTOperator`` plain mode, the out-of-core streaming
loops, the shard_map distributed operators) obtains its kernels from
here, so selecting ``backend="pallas"`` routes the *same* execution plan
onto the optimized kernels — tomoCAM's observation that the plan/kernel
split is what makes drop-in kernel swaps possible.

Cached-jit dispatch
-------------------
Backends hand out **jit-compiled callables from a process-wide dispatch
table keyed by (backend, kind, geometry, static plan args)**.  The
returned callables take only traced arguments (arrays, angles, the slab
origin ``z0``), so repeated calls — every slab of every iteration of
every job — reuse one compiled executable instead of retracing
(:func:`dispatch_cache_info` exposes the hit counters the regression
tests assert on).  Exact-adjoint ("matched") operators follow the
selected backend too: ``pallas_call`` defines no transpose rule, so the
pallas backend pairs the ray-driven FP with a dedicated transpose-shaped
scatter kernel (:mod:`repro.kernels.bp_matched`) via ``jax.custom_vjp``
— the pair replays identical fp32 ray weights, keeping
``<Ax, y> == <x, At y>`` to float tolerance for CGLS/FISTA — while the
ref backend keeps its ``jax.vjp`` construction.

Block sizes come from :mod:`repro.kernels.autotune`: the measured
per-(kind, platform, geometry-shape) table when ``REPRO_AUTOTUNE`` is
on, the divisor-or-pad heuristic otherwise.  The chosen blocks are part
of every dispatch key, so differently-tuned configs never share a
compiled entry.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import projector as proj_mod
from .geometry import ConeGeometry


# --------------------------------------------------------------------------
# cached-jit dispatch table
# --------------------------------------------------------------------------

class _DispatchTable:
    """Process-wide (key -> compiled callable) map with hit/miss stats.

    Builders run outside the lock (they only trace lazily anyway); a
    racing double-build keeps the first entry, so callers always share
    one callable (and its jit cache) per key.
    """

    def __init__(self):
        self._fns: Dict[tuple, Callable] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(self, key: tuple, build: Callable[[], Callable]) -> Callable:
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                self.hits += 1
                obs.incr("dispatch_hits")
                return fn
            self.misses += 1
        obs.incr("dispatch_misses")
        # The "compile" span times the builder.  XLA compilation proper is
        # lazy (first invocation), so it lands in whichever compute/init
        # span makes that first call -- documented in docs/observability.md.
        with obs.span("compile", "compile", key=str(key[:2])):
            fn = build()
        with self._lock:
            return self._fns.setdefault(key, fn)

    def info(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "currsize": len(self._fns)}

    def keys(self) -> tuple:
        with self._lock:
            return tuple(self._fns)

    def clear(self) -> None:
        with self._lock:
            self._fns.clear()
            self.hits = self.misses = 0


_TABLE = _DispatchTable()


def dispatch_cache_info() -> Dict[str, int]:
    """Hit/miss/size counters of the shared dispatch table."""
    return _TABLE.info()


def dispatch_cache_keys() -> tuple:
    """The dispatch table's current ``(backend, kind, geometry, ...)``
    keys.  Regression tests assert on *which* kernels materialised —
    e.g. that the dominance-split dist FP never builds the unused
    dominance variant on a single-dominance workload."""
    return _TABLE.keys()


def clear_dispatch_cache() -> None:
    """Drop every cached callable (frees their compiled executables)."""
    _TABLE.clear()


def _divisor_at_most(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= ``cap`` (>= 1).

    Kept as the angle-axis fallback; the tiled volume axes now go through
    :func:`repro.kernels.autotune.get_blocks` (divisor-or-pad heuristic,
    measured table when tuning is enabled)."""
    for d in range(min(cap, n), 0, -1):
        if n % d == 0:
            return d
    return 1


# --------------------------------------------------------------------------
# backend interface + implementations
# --------------------------------------------------------------------------

class KernelBackend:
    """One named kernel implementation.

    The contract is three slab operators (all returned callables are
    jit-compiled, shared through the dispatch table, and close over the
    static plan args only):

    * ``fp(geo, xdom=...)``              -> ``f(slab, angles, z0) -> proj``
      partial forward projection of the z planes ``[z0, z0+len(slab))``
      for a single-dominance angle set;
    * ``bp(geo, planes=..., weight=...)``-> ``f(proj, angles, z0) -> slab``
      voxel-driven backprojection into an axial slab (weights
      ``fdk`` / ``pmatched`` / ``none``);
    * ``bp_matched(geo, planes=..., xdom=...)`` — the *exact* adjoint of
      the slab forward projection (``jax.vjp`` here; the pallas backend
      overrides it with its native transpose kernel).

    plus two full-volume conveniences for mixed-dominance angle sets
    (``fp_mixed`` / ``at_matched_mixed``), built on the slab operators.
    """

    name = "?"

    def kernel_config(self, geo: ConeGeometry, *,
                      planes: Optional[int] = None,
                      angles: Optional[np.ndarray] = None) -> Dict[str, int]:
        """Block-size configuration this backend would run ``geo`` with.

        Empty for backends without tunable blocks; the pallas backend
        reports the (possibly autotuned) slab/z/angle blocks, and with
        ``angles`` the ray kernels' mean z chunks per detector tile —
        surfaced in serve calibration attrs and the operator benchmarks."""
        return {}

    # -- slab operators ------------------------------------------------------

    def fp(self, geo: ConeGeometry, *, xdom: bool) -> Callable:
        raise NotImplementedError

    def bp(self, geo: ConeGeometry, *, planes: int,
           weight: str) -> Callable:
        raise NotImplementedError

    def bp_matched(self, geo: ConeGeometry, *, planes: int,
                   xdom: bool) -> Callable:
        """Exact slab adjoint: vjp of the ref slab FP, keeping
        <Ax, y> == <x, At y> to float precision for CGLS/FISTA.  The
        pallas backend overrides this with the transpose-shaped scatter
        kernel (:mod:`repro.kernels.bp_matched`)."""
        def build():
            @jax.jit
            def f(proj_chunk, angles, z0):
                def fwd(slab):
                    return proj_mod.forward_project_joseph(
                        slab, geo, angles, xdom=xdom, z0=z0)
                zeros = jnp.zeros((planes,) + tuple(geo.n_voxel[1:]),
                                  jnp.float32)
                _, vjp = jax.vjp(fwd, zeros)
                return vjp(proj_chunk)[0]
            return f
        return _TABLE.get(("ref", "bp_matched", geo, planes, xdom), build)

    # -- full-volume mixed-dominance conveniences ----------------------------

    def fp_mixed(self, geo: ConeGeometry, mask: np.ndarray) -> Callable:
        """Full forward projection ``f(vol, angles) -> proj`` for a static
        dominance ``mask`` (x-dominant entries True): the angle set is
        split per dominance, each subset runs the specialised slab FP,
        and the results scatter back — TIGRE's independent per-GPU angle
        queues, expressed as one compiled callable per mask."""
        mask = np.asarray(mask, bool)
        key = (self.name, "fp_mixed", geo, mask.tobytes())

        def build():
            idx_x = np.nonzero(mask)[0]
            idx_y = np.nonzero(~mask)[0]
            fpx = self.fp(geo, xdom=True) if idx_x.size else None
            fpy = self.fp(geo, xdom=False) if idx_y.size else None
            nv, nu = geo.n_detector

            @jax.jit
            @jax.named_scope("repro.op.fp")
            def f(vol, angles):
                out = jnp.zeros((len(mask), nv, nu), jnp.float32)
                if fpx is not None:
                    out = out.at[idx_x].set(fpx(vol, angles[idx_x], 0))
                if fpy is not None:
                    out = out.at[idx_y].set(fpy(vol, angles[idx_y], 0))
                return out
            return f
        return _TABLE.get(key, build)

    def at_matched_mixed(self, geo: ConeGeometry,
                         mask: np.ndarray) -> Callable:
        """Exact adjoint ``f(proj, angles) -> vol`` of the mixed-dominance
        full FP (ref-built vjp here; the pallas backend overrides it with
        per-dominance matched scatter kernels)."""
        mask = np.asarray(mask, bool)
        key = ("ref", "at_matched_mixed", geo, mask.tobytes())

        def build():
            ref_fp = get_backend("ref").fp_mixed(geo, mask)

            @jax.jit
            @jax.named_scope("repro.op.bp")
            def f(proj, angles):
                zeros = jnp.zeros(geo.n_voxel, jnp.float32)
                _, vjp = jax.vjp(lambda v: ref_fp(v, angles), zeros)
                return vjp(proj)[0]
            return f
        return _TABLE.get(key, build)


class RefBackend(KernelBackend):
    """Pure-JAX projectors (:mod:`repro.core.projector`)."""

    name = "ref"

    def fp(self, geo: ConeGeometry, *, xdom: bool) -> Callable:
        def build():
            @jax.jit
            def f(slab, angles, z0):
                return proj_mod.forward_project_joseph(
                    slab, geo, angles, xdom=xdom, z0=z0)
            return f
        return _TABLE.get(("ref", "fp", geo, xdom), build)

    def bp(self, geo: ConeGeometry, *, planes: int,
           weight: str) -> Callable:
        def build():
            @jax.jit
            @jax.named_scope("repro.op.bp")
            def f(proj, angles, z0):
                return proj_mod.backproject_voxel(
                    proj, geo, angles, weight=weight, z_start=z0,
                    z_planes=planes)
            return f
        return _TABLE.get(("ref", "bp", geo, planes, weight), build)


class PallasBackend(KernelBackend):
    """Pallas TPU kernels (:mod:`repro.kernels.fp_ray` /
    :mod:`repro.kernels.bp_voxel`).

    ``interpret`` defaults to auto-detection: Mosaic compiles the kernels
    on real TPU backends, interpret mode validates them everywhere else.
    Block sizes come from :mod:`repro.kernels.autotune` (measured table
    when enabled, divisor-or-pad heuristic otherwise); the kernels pad
    and mask non-divisor tails, so odd volume shapes stay runnable.

    Matched weighting is native here: ``fp`` pairs the ray kernel with
    the transpose-shaped scatter kernel through ``jax.custom_vjp``, and
    ``bp_matched`` / ``at_matched_mixed`` hand out that scatter kernel
    directly — no ref fallback anywhere on the matched path.
    """

    name = "pallas"

    def __init__(self, interpret: Optional[bool] = None):
        self._interpret = interpret

    @property
    def interpret(self) -> bool:
        if self._interpret is not None:
            return self._interpret
        return jax.default_backend() != "tpu"

    def _blocks(self, kind: str, geo: ConeGeometry,
                planes: Optional[int] = None) -> Dict[str, int]:
        from repro.kernels import autotune
        return autotune.get_blocks(kind, geo, planes=planes,
                                   interpret=self.interpret)

    def kernel_config(self, geo: ConeGeometry, *,
                      planes: Optional[int] = None,
                      angles: Optional[np.ndarray] = None) -> Dict[str, int]:
        from repro.kernels import autotune
        from repro.kernels.fp_ray import z_chunks_per_tile
        fp = self._blocks("fp", geo)
        bm = self._blocks("bp_matched", geo)
        bp = self._blocks("bp", geo, planes=planes)
        cfg = {f"{kind}.{k}": v for kind, blocks in
               (("fp", fp), ("bp_matched", bm), ("bp", bp))
               for k, v in blocks.items()}
        if angles is not None:
            cfg["z_chunks_per_tile"] = z_chunks_per_tile(geo, angles)
        return dict(cfg, autotuned=bool(autotune.enabled()),
                    interpret=self.interpret)

    @staticmethod
    def _check_rotation_trick(geo: ConeGeometry) -> None:
        # same transpose trick (and the same preconditions) as the ref
        # Joseph projector: rotate the scene -90 deg so the y-dominant
        # set becomes x-dominant
        nz, ny, nx = geo.n_voxel
        if nx != ny or abs(geo.d_voxel[1] - geo.d_voxel[2]) > 1e-12:
            raise ValueError(
                "y-dominant transpose trick needs square xy grid")
        if any(abs(o) > 0 for o in geo.off_origin[1:]):
            raise ValueError(
                "xy origin offsets unsupported with rotation trick")

    def fp(self, geo: ConeGeometry, *, xdom: bool) -> Callable:
        from repro.kernels.bp_matched import bp_matched_pallas
        from repro.kernels.fp_ray import fp_ray_pallas
        interpret = self.interpret
        fb = self._blocks("fp", geo)
        bb = self._blocks("bp_matched", geo)
        key = ("pallas", "fp", geo, xdom, tuple(fb.items()),
               tuple(bb.items()), interpret)

        def build():
            if not xdom:
                self._check_rotation_trick(geo)

            def make_core(planes):
                # one custom_vjp pair per slab height: forward runs the
                # ray kernel, backward the matched scatter kernel — the
                # two replay identical fp32 ray weights, so anything that
                # differentiates through this FP (norm estimation, CGLS's
                # A^T) gets the exact adjoint without leaving Pallas
                @jax.custom_vjp
                def core(s, ang, z0f):
                    return fp_ray_pallas(s, geo, ang, interpret=interpret,
                                         z0=z0f, **fb)

                def fwd(s, ang, z0f):
                    return core(s, ang, z0f), (ang, z0f)

                def bwd(res, ct):
                    ang, z0f = res
                    sbar = bp_matched_pallas(
                        ct, geo, ang, interpret=interpret, z0=z0f,
                        z_planes=planes, **bb)
                    return sbar, jnp.zeros_like(ang), jnp.zeros_like(z0f)
                core.defvjp(fwd, bwd)
                return core

            cores: Dict[int, Callable] = {}

            @jax.jit
            def f(slab, angles, z0):
                planes = slab.shape[0]
                if planes not in cores:
                    cores[planes] = make_core(planes)
                z0f = jnp.asarray(z0, jnp.float32)
                if not xdom:
                    # rotation stays outside the custom_vjp core: autodiff
                    # transposes the flip/transpose pair natively
                    slab = proj_mod._rotate_vol_90(slab)
                    angles = angles - jnp.pi / 2.0
                return cores[planes](slab, angles, z0f)
            return f
        return _TABLE.get(key, build)

    def bp(self, geo: ConeGeometry, *, planes: int,
           weight: str) -> Callable:
        from repro.kernels.bp_voxel import bp_voxel_pallas
        interpret = self.interpret
        cfg = self._blocks("bp", geo, planes=planes)
        key = ("pallas", "bp", geo, planes, weight, tuple(cfg.items()),
               interpret)

        def build():
            @jax.jit
            @jax.named_scope("repro.op.bp")
            def f(proj, angles, z0):
                # bp_voxel clamps + pads non-divisor chunks itself
                return bp_voxel_pallas(proj, geo, angles, weight=weight,
                                       interpret=interpret, z_start=z0,
                                       z_planes=planes, **cfg)
            return f
        return _TABLE.get(key, build)

    def bp_matched(self, geo: ConeGeometry, *, planes: int,
                   xdom: bool) -> Callable:
        """Native exact slab adjoint: the transpose-shaped scatter kernel
        replaying the ray kernel's fp32 weights (no ref vjp involved)."""
        from repro.kernels.bp_matched import bp_matched_pallas
        interpret = self.interpret
        bb = self._blocks("bp_matched", geo)
        key = ("pallas", "bp_matched", geo, planes, xdom, tuple(bb.items()),
               interpret)

        def build():
            if not xdom:
                self._check_rotation_trick(geo)

            @jax.jit
            def f(proj_chunk, angles, z0):
                ang = angles if xdom else angles - jnp.pi / 2.0
                slab = bp_matched_pallas(
                    proj_chunk, geo, ang, interpret=interpret, z0=z0,
                    z_planes=planes, **bb)
                if not xdom:
                    # adjoint (= inverse) of the -90 deg scene rotation
                    # the forward pass applies before the ray kernel
                    slab = jnp.transpose(jnp.flip(slab, axis=1), (0, 2, 1))
                return slab
            return f
        return _TABLE.get(key, build)

    def at_matched_mixed(self, geo: ConeGeometry,
                         mask: np.ndarray) -> Callable:
        """Exact adjoint of the mixed-dominance FP from the per-dominance
        matched scatter kernels: the dominance groups partition the angle
        rows, so summing each group's slab adjoint is the full A^T."""
        mask = np.asarray(mask, bool)
        interpret = self.interpret
        nz = geo.n_voxel[0]
        bb = self._blocks("bp_matched", geo)
        key = ("pallas", "at_matched_mixed", geo, mask.tobytes(),
               tuple(bb.items()), interpret)

        def build():
            idx_x = np.nonzero(mask)[0]
            idx_y = np.nonzero(~mask)[0]
            bmx = (self.bp_matched(geo, planes=nz, xdom=True)
                   if idx_x.size else None)
            bmy = (self.bp_matched(geo, planes=nz, xdom=False)
                   if idx_y.size else None)

            @jax.jit
            @jax.named_scope("repro.op.bp")
            def f(proj, angles):
                out = jnp.zeros(geo.n_voxel, jnp.float32)
                if bmx is not None:
                    out = out + bmx(proj[idx_x], angles[idx_x], 0)
                if bmy is not None:
                    out = out + bmy(proj[idx_y], angles[idx_y], 0)
                return out
            return f
        return _TABLE.get(key, build)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

_REGISTRY: Dict[str, KernelBackend] = {}


def register_backend(backend: KernelBackend) -> KernelBackend:
    """Add a named backend (replacing any previous holder of the name)."""
    _REGISTRY[backend.name] = backend
    return backend


register_backend(RefBackend())
register_backend(PallasBackend())


def available_backends() -> tuple:
    """Registered backend names plus the ``"auto"`` alias."""
    return tuple(sorted(_REGISTRY)) + ("auto",)


def resolve(name: Optional[str]) -> str:
    """Canonical backend name: ``None`` / ``"auto"`` pick per JAX backend
    (pallas on TPU hosts, ref elsewhere); unknown names raise."""
    name = name or "auto"
    if name == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if name not in _REGISTRY:
        raise ValueError(f"unknown kernel backend {name!r} "
                         f"(have {available_backends()})")
    return name


def get_backend(name: Optional[str] = None) -> KernelBackend:
    """Backend instance for ``name`` (default: auto-resolve)."""
    return _REGISTRY[resolve(name)]
