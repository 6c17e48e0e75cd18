#!/usr/bin/env python3
"""On-chip smoke test: the reconstruction service's main path on a TPU.

Runs in one process, through the entry points a user calls
(``repro.launch.recon.reconstruct``, which drives ``repro.serve.Scheduler``
and ``AsyncDriver``), on the paper's Fig 7 geometry ``ConeGeometry.nice(512)``:
a 512^3 volume, a 512^2 detector and 512 angles.

a. Device check: a TPU, or a non-zero exit (there is no CPU fallback).
b. Kernel parity at N=512 on 32 angles of both dominances: the Pallas FP,
   FDK BP and matched BP against the fp32 ``ref`` projectors, and the
   adjoint identity of the Pallas pair.
c. In-core CGLS, 3 iterations on the ``auto`` backend: it must resolve to
   compiled Pallas (``interpret`` off) and the residual must fall at every
   iteration.
d. Out-of-core OS-SART, 2 iterations streamed in >= 2 slabs over the host
   link, against the same iterations in plain mode.

``--chips 4`` runs only the multi-chip path: one CGLS iteration in
``--mode dist`` (the shard_map backend over every local chip) and the
same job on one chip.

Per-phase seconds are printed as information, not as metrics.  Any failed
check raises, so the exit code is non-zero.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Usage::

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # a four-chip host
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

N = 512
PARITY_ANGLES = 32
BP_WINDOW = 16               # z planes per ref-BP comparison window
# One CGLS iteration runs every dist operator (A, the matched A^T and its
# cross-chip psum, twice each); the four-chip comparison stops there.
DIST_ITERS = 1
# Every check below compares two fp32 computations of the same linear map
# that differ only in summation order (and, on the chip, in the multi-pass
# fp32 MXU contraction the kernels interpolate with); interpret-mode runs
# agree to ~1e-5 of the output's max.  1e-4 is that with a 10x margin,
# and two orders of magnitude below what a wrong weight, tap or slab
# offset produces (>= 1e-2).
TOL_PARITY = 1e-4
# Positive x and y make <Ax, y> a sum of positive terms (no cancellation);
# the Pallas pair replays bit-identical weights, so the relative defect is
# summation-order noise (~1e-6), while a mismatched BP sits at >= 1e-2.
TOL_ADJOINT = 1e-4
# Streamed vs in-core, 4 chips vs 1: the same operators summed in another
# order (per-slab partial projections; a cross-chip psum of the BP).
TOL_MODES = 1e-4


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def rel_max(got, want) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def rel_l2(got, want) -> float:
    import numpy as np
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


class Phase:
    """Times one phase (the work inside ends in ``block_until_ready``)."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        log(f"phase {self.name} ...")
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            log(f"phase {self.name}: passed in "
                f"{time.perf_counter() - self.t0:.1f} s")


def traced_reconstruct(alg: str, **kw):
    """``reconstruct`` with the tracer on; returns (rec, rel, events)."""
    from repro import obs
    from repro.launch.recon import reconstruct
    tracer = obs.get_tracer()
    tracer.clear()
    tracer.enable()
    try:
        rec, rel = reconstruct(alg, n=N, n_angles=N, **kw)
    finally:
        tracer.disable()
    check(rec is not None, f"{alg} job was parked (SIGTERM), not finished")
    return rec, rel, tracer.events()


@contextlib.contextmanager
def cgls_residuals(out: list):
    """Append |r| = |b - A x| to ``out`` after CGLS's init and after each
    step, read from the state the registered algorithm returns."""
    import jax.numpy as jnp
    from repro.core.algorithms import stepwise
    alg = stepwise.REGISTRY["cgls"]

    def residual(st):
        out.append(float(jnp.sqrt(jnp.sum(st.r * st.r))))
        return st

    stepwise.REGISTRY["cgls"] = dataclasses.replace(
        alg, init=lambda *a, **kw: residual(alg.init(*a, **kw)),
        step=lambda st: residual(alg.step(st)))
    try:
        yield out
    finally:
        stepwise.REGISTRY["cgls"] = alg


def check_pallas_compiled(events) -> None:
    cfg = [e.attrs for e in events if e.name == "kernel-config"]
    check(bool(cfg), "no kernel-config event: the job did not build "
                     "a Pallas operator")
    for attrs in cfg:
        check(attrs.get("backend") == "pallas" and attrs.get("interpret")
              is False, f"backend resolved to {attrs}, not compiled pallas")
    log(f"backend pallas, interpret=False, blocks "
        f"{ {k: v for k, v in cfg[0].items() if '.' in k} }")


# --------------------------------------------------------------------------
# phases

def phase_parity(geo) -> None:
    """b. Pallas vs ref at N=512 on 32 angles, and the adjoint identity."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import projector
    from repro.core.backend import get_backend
    from repro.core.geometry import circular_angles, dominant_axis_mask

    pal, ref = get_backend("pallas"), get_backend("ref")
    check(not pal.interpret, "pallas backend would run in interpret mode")
    angles = circular_angles(N)[:: N // PARITY_ANGLES]
    mask = dominant_axis_mask(angles)
    check(0 < mask.sum() < len(mask), "parity angles miss a dominance")
    ang = jnp.asarray(angles)
    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.uniform(kx, geo.n_voxel, jnp.float32)
    y = jax.random.uniform(ky, (len(angles),) + geo.n_detector, jnp.float32)

    ax = pal.fp_mixed(geo, mask)(x, ang).block_until_ready()
    err = rel_max(ax, ref.fp_mixed(geo, mask)(x, ang))
    log(f"FP  pallas vs ref: max|d|/max = {err:.3e} (tol {TOL_PARITY:g})")
    check(err <= TOL_PARITY, "FP parity")

    # the ref BP is one XLA gather per voxel and angle -- slow on the chip
    # at 512^3 -- so it is taken on z windows of the full Pallas BP: the
    # top planes (rays leave the detector) and the central ones
    bp = pal.bp(geo, planes=N, weight="fdk")(y, ang, 0)
    ref_bp = ref.bp(geo, planes=BP_WINDOW, weight="fdk")
    for z0 in (0, N // 2 - BP_WINDOW // 2):
        err = rel_max(bp[z0:z0 + BP_WINDOW], ref_bp(y, ang, z0))
        log(f"BP (fdk) pallas vs ref (z planes {z0}..{z0 + BP_WINDOW - 1}):"
            f" max|d|/max = {err:.3e} (tol {TOL_PARITY:g})")
        check(err <= TOL_PARITY, "FDK BP parity")

    # matched BP: the ref adjoint is jax.vjp of the ref FP (a scatter-add),
    # taken over 8 central marching planes of the x-dominant angles (the
    # kernel's output planes are independent of each other); the identity
    # below covers the rest.  The vjp is built inside the jit, so its
    # residuals are computed on the device, not captured as constants.
    aty = pal.at_matched_mixed(geo, mask)(y, ang)
    xi = np.nonzero(mask)[0]
    p0, p1 = N // 2 - 4, N // 2 + 4

    @jax.jit
    def ref_matched(r, a):
        def fp_window(s):
            return projector.forward_project_joseph(
                s, geo, a, xdom=True, x_planes=(p0, p1))
        _, vjp = jax.vjp(fp_window, jnp.zeros((N, N, p1 - p0), jnp.float32))
        return vjp(r)[0]
    want = ref_matched(y[xi], ang[xi])
    got = pal.bp_matched(geo, planes=N, xdom=True)(y[xi], ang[xi], 0)
    err = rel_max(got[:, :, p0:p1], want)
    log(f"matched BP pallas vs ref vjp (x planes {p0}..{p1 - 1}): "
        f"max|d|/max = {err:.3e} (tol {TOL_PARITY:g})")
    check(err <= TOL_PARITY, "matched BP parity")

    lhs = float(np.vdot(np.asarray(ax, np.float64), np.asarray(y, np.float64)))
    rhs = float(np.vdot(np.asarray(x, np.float64),
                        np.asarray(aty, np.float64)))
    err = abs(lhs - rhs) / abs(lhs)
    log(f"adjoint <Ax,y>={lhs:.8e} <x,Aty>={rhs:.8e}: rel {err:.3e} "
        f"(tol {TOL_ADJOINT:g})")
    check(err <= TOL_ADJOINT, "adjoint identity")


def phase_cgls() -> None:
    """c. In-core CGLS on the auto backend, residual falling."""
    import numpy as np
    with cgls_residuals([]) as res:
        rec, rel, events = traced_reconstruct("cgls", iters=3, mode="plain",
                                              backend="auto")
    check_pallas_compiled(events)
    log(f"CGLS residual per iteration: {res}; rel_err {rel:.4f}")
    check(len(res) == 4, f"expected 4 residuals (|b| + 3), got {len(res)}")
    check(all(b < a for a, b in zip(res, res[1:])), "residual did not fall")
    check(np.isfinite(rel) and rel < 1.0, f"rel_err {rel} not finite < 1")
    check(bool(np.all(np.isfinite(rec))), "non-finite reconstruction")


def phase_ossart_stream() -> None:
    """d. OS-SART streamed in >= 2 slabs vs the same in plain mode."""
    from repro import obs
    from repro.core.geometry import ConeGeometry
    from repro.core.plan import plan
    from repro.core.splitting import MemoryModel
    budget = 3 * N ** 3          # three quarters of the fp32 volume
    n_slabs = plan(ConeGeometry.nice(N), N, 1,
                   MemoryModel(device_bytes=budget)).forward.n_slabs
    check(n_slabs >= 2, f"budget gives {n_slabs} slab(s), need >= 2")
    log(f"stream budget {budget / 2**20:.0f} MiB -> {n_slabs} slabs")
    streamed, rel_s, events = traced_reconstruct(
        "ossart", iters=2, mode="stream", device_bytes=budget)
    check_pallas_compiled(events)
    check(any(s.cat == "h2d" for s in obs.get_tracer().spans()),
          "no h2d staging spans")
    plain, rel_p, _ = traced_reconstruct("ossart", iters=2, mode="plain")
    err = rel_l2(streamed, plain)
    log(f"OS-SART stream vs plain: |d|/|plain| = {err:.3e} "
        f"(tol {TOL_MODES:g}); rel_err {rel_s:.4f} / {rel_p:.4f}")
    check(err <= TOL_MODES, "stream vs plain")


def phase_dist(n_chips: int) -> None:
    """CGLS over every local chip (--mode dist) vs the same on one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.distributed import dist_forward_project
    from repro.core.geometry import (ConeGeometry, circular_angles,
                                     dominant_axis_mask)
    from repro.launch.mesh import make_host_mesh
    from repro.launch.recon import reconstruct

    check(jax.device_count() == n_chips,
          f"--chips {n_chips} but {jax.device_count()} devices")
    with Phase(f"dist CGLS on {n_chips} chips"):
        dist, rel_d = reconstruct("cgls", n=N, n_angles=N, iters=DIST_ITERS,
                                  mode="dist")
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) / 2**30
            for d in jax.devices()]
    log("peak GiB per chip: " + ", ".join(f"{p:.2f}" for p in peak))
    # where the work runs: each chip projects its shard of the angles
    # (shard_map over the mesh's data axis) and keeps the rows it made
    mesh = make_host_mesh(model_axis=1)
    geo = ConeGeometry.nice(N)
    angles = circular_angles(N)
    ax = jnp.asarray(angles[dominant_axis_mask(angles)][:2 * n_chips])
    fp = dist_forward_project(mesh, geo, backend="pallas").sharded(True)
    with mesh:
        out = fp(jnp.ones(geo.n_voxel, jnp.float32), ax)
    placed = {s.device.id: s.data.shape[0] for s in out.addressable_shards}
    log(f"dist FP angle rows per chip: {placed}")
    check(sorted(placed) == sorted(d.id for d in jax.devices())
          and set(placed.values()) == {2}, "work not split over chips")
    with Phase("CGLS on 1 chip"):
        one, rel_1 = reconstruct("cgls", n=N, n_angles=N, iters=DIST_ITERS,
                                 mode="plain")
    err = rel_l2(dist, one)
    log(f"dist vs 1 chip: |d|/|x1| = {err:.3e} (tol {TOL_MODES:g}); "
        f"rel_err {rel_d:.4f} / {rel_1:.4f}")
    check(err <= TOL_MODES and np.isfinite(rel_d), "dist vs 1 chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the dist-CGLS-vs-one-chip path")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    enable_compile_cache(ROOT)
    import jax

    with Phase("a (device)"):
        devs = jax.devices()
        dev = devs[0]
        if dev.platform != "tpu":
            sys.exit(f"chip_smoke: no TPU found (JAX platform "
                     f"{dev.platform!r}); refusing to fall back")
        log(f"device {dev.device_kind} x{len(devs)}")
    from repro.core.geometry import ConeGeometry
    from repro.core.splitting import MemoryModel
    log(f"planner device budget "
        f"{MemoryModel.from_device(dev).device_bytes / 2**30:.2f} GiB "
        f"(memory_stats bytes_limit)")

    if args.chips == 4:
        phase_dist(4)
    else:
        with Phase("b (kernel parity, N=512, 32 angles)"):
            phase_parity(ConeGeometry.nice(N))
        with Phase("c (in-core CGLS, 3 iterations)"):
            phase_cgls()
        with Phase("d (out-of-core OS-SART, 2 iterations)"):
            phase_ossart_stream()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
