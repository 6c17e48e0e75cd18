"""Measured block-size autotuner for the Pallas kernels.

The dispatch layer (:mod:`repro.core.backend`) historically chose kernel
block sizes with a static largest-divisor-<=-preferred heuristic.  That is
safe but blind: the best marching-slab width for ``fp_ray`` or z-block for
``bp_voxel`` depends on the geometry's shape and on the platform (interpret
mode on CPU amortises per-grid-step overhead very differently from Mosaic
on a real TPU).  This module times a small candidate grid per

    (kind, platform, geometry shape class)

on first use, memoises the winner into a process-wide table, and optionally
persists it as JSON so later processes skip the measurement:

* ``REPRO_AUTOTUNE=1`` (or :func:`enable`) turns tuning on; when off,
  :func:`get_blocks` returns the heuristic unchanged — zero behaviour
  change for existing callers.
* ``REPRO_AUTOTUNE_CACHE=/path/table.json`` loads the table on first use
  and rewrites it after every new measurement (``recon --autotune`` and
  ``tools/autotune.py`` pre-bake it).
* Candidates are floored at the heuristic block: the tuner only ever
  *grows* blocks (fewer grid steps, bigger VMEM windows), so a tuned
  config is always >= the heuristic one and the dispatch-table key —
  which includes the chosen blocks — stays distinct per config.  They
  are also bounded by the VMEM model (:func:`vmem_bytes`), and a
  candidate that fails to compile raises instead of being skipped.

The heuristic sizes blocks against that VMEM model, so every kernel
compiles for a TPU v5e at N=512 without tuning, and carries the
pad-to-divisor escape hatch: when the largest divisor degrades below half
the preferred block (prime axes used to force block=1), it returns the
preferred block and lets the kernels' pad-and-mask path absorb the
non-divisibility.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

_SCHEMA = 1
_KINDS = ("fp", "bp", "bp_matched")

_LOCK = threading.RLock()
_TABLE: Dict[Tuple, Dict[str, int]] = {}
_LOADED: set = set()          # cache paths already merged into _TABLE
_ENABLED: Optional[bool] = None   # None -> consult REPRO_AUTOTUNE
_FINGERPRINT = 0              # bumped on any table/state mutation


# --------------------------------------------------------------------------
# state

def enabled() -> bool:
    """True when measured tuning is active (env or :func:`enable`)."""
    if _ENABLED is not None:
        return _ENABLED
    return os.environ.get("REPRO_AUTOTUNE", "") not in ("", "0", "false")


def enable(on: Optional[bool]) -> None:
    """Force tuning on/off for this process (``None`` -> env-driven)."""
    global _ENABLED, _FINGERPRINT
    with _LOCK:
        _ENABLED = on
        _FINGERPRINT += 1


def cache_path() -> str:
    return os.environ.get("REPRO_AUTOTUNE_CACHE", "")


def fingerprint() -> int:
    """Monotone counter over table mutations.

    Folded into cache keys that must distinguish "same geometry, different
    tuned blocks" (e.g. the serve layer's operator cache).
    """
    return _FINGERPRINT


def clear() -> None:
    global _FINGERPRINT
    with _LOCK:
        _TABLE.clear()
        _LOADED.clear()
        _FINGERPRINT += 1


def table() -> Dict[str, Dict[str, int]]:
    """Copy of the current table, JSON-keyed (for inspection/tests)."""
    with _LOCK:
        return {_key_str(k): dict(v) for k, v in _TABLE.items()}


# --------------------------------------------------------------------------
# keys + persistence

def _platform() -> str:
    import jax
    return jax.default_backend()


def shape_class(kind: str, geo, planes: Optional[int]) -> Tuple:
    """The memo key: geometry *shape*, not its physical scale.

    Block sizes are about grid-step counts and VMEM windows, so only the
    integer shapes matter; two geometries with the same voxel/detector
    counts share a tuned entry.
    """
    return (kind, _platform(), tuple(geo.n_voxel), tuple(geo.n_detector),
            int(planes) if planes is not None else None)


def _key_str(key: Tuple) -> str:
    kind, plat, nvox, ndet, planes = key
    return "|".join([kind, plat,
                     ",".join(map(str, nvox)), ",".join(map(str, ndet)),
                     str(planes)])


def _key_parse(s: str) -> Optional[Tuple]:
    parts = s.split("|")
    if len(parts) != 5:
        return None
    kind, plat, nvox, ndet, planes = parts
    try:
        return (kind, plat, tuple(int(x) for x in nvox.split(",")),
                tuple(int(x) for x in ndet.split(",")),
                None if planes == "None" else int(planes))
    except ValueError:
        return None


def save(path: str) -> None:
    with _LOCK:
        doc = {"version": _SCHEMA, "entries": table()}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    os.replace(tmp, path)


def load(path: str) -> int:
    """Merge a persisted table; returns the number of entries taken."""
    global _FINGERPRINT
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return 0
    if not isinstance(doc, dict) or doc.get("version") != _SCHEMA:
        return 0
    n = 0
    with _LOCK:
        for ks, cfg in (doc.get("entries") or {}).items():
            key = _key_parse(ks)
            if key is None or not isinstance(cfg, dict):
                continue
            _TABLE[key] = {k: int(v) for k, v in cfg.items()}
            n += 1
        if n:
            _FINGERPRINT += 1
    return n


def _maybe_load() -> None:
    p = cache_path()
    if p and p not in _LOADED:
        _LOADED.add(p)
        if os.path.exists(p):
            load(p)


# --------------------------------------------------------------------------
# heuristic

# Block bytes the heuristic lets a kernel's VMEM estimate reach: two thirds
# of the scoped limit it requests (fp_ray.VMEM_LIMIT_BYTES), leaving room
# for Mosaic's own matmul and vector temporaries.
VMEM_BUDGET_BYTES = 64 * 2**20
# Cap on the "step" block (the one that only sets the grid-step count):
# the rest of the budget goes to the block that cuts HBM traffic.
_STEP_SHARE = 8
_MAX_ANGLE_BLOCK = 64
# Preferred step blocks before the VMEM caps: planes per slab, angles.
_PREF_PLANES = 16
_PREF_ANGLES = 8


def _divisor_at_most(n: int, cap: int) -> int:
    cap = max(1, min(cap, n))
    for c in range(cap, 0, -1):
        if n % c == 0:
            return c
    return 1


def pick_block(n: int, preferred: int) -> int:
    """Divisor-or-pad heuristic block for an axis of extent ``n``.

    Largest divisor <= ``preferred`` when that divisor is still at least
    half of ``preferred``; otherwise (prime/awkward axes) fall through to
    ``min(preferred, n)`` and rely on the kernels' pad-and-mask path.
    """
    d = _divisor_at_most(n, preferred)
    if d >= max(1, preferred // 2):
        return d
    return min(preferred, n)


def _r8(n: int) -> int:
    return -(-n // 8) * 8


def vmem_bytes(kind: str, geo, cfg: Dict[str, int],
               planes: Optional[int] = None) -> int:
    """Estimated VMEM of one ``kind`` kernel under block config ``cfg``.

    Double-buffered input/output blocks, the kernel's scratch planes, and
    its largest vector temporaries (the tent-weight matrix and the matmul
    result, counted three times for Mosaic's fp32 matmul passes).
    """
    nz, ny, nx = geo.n_voxel
    nv, nu = geo.n_detector
    zr, vr = _r8(nz if planes is None else int(planes)), _r8(nv)
    if kind in ("fp", "bp_matched"):
        blocks = cfg["slab_planes"] * zr * ny + cfg["angle_block"] * vr * nu
        scratch = zr * nu + vr * nu
        temps = ny * nu + zr * max(nu, ny)
    elif kind == "bp":
        blocks = (min(cfg["y_block"], ny) * _r8(min(cfg["z_block"], zr)) * nx
                  + cfg["angle_chunk"] * vr * nu)
        scratch = vr * nx
        temps = nu * nx + vr * nx
    else:
        raise ValueError(f"unknown autotune kind: {kind!r}")
    return 4 * (2 * blocks + scratch + 3 * temps)


def fits(kind: str, geo, cfg: Dict[str, int],
         planes: Optional[int] = None) -> bool:
    return vmem_bytes(kind, geo, cfg, planes) <= VMEM_BUDGET_BYTES


def _grow(kind, geo, cfg, key, cap, planes) -> int:
    """Largest ``cfg[key]`` <= ``cap`` that keeps the config in budget."""
    best = 1
    for v in range(2, max(1, cap) + 1):
        if not fits(kind, geo, dict(cfg, **{key: v}), planes):
            break
        best = v
    return best


def heuristic_blocks(kind: str, geo, *, planes: Optional[int] = None
                     ) -> Dict[str, int]:
    """VMEM-bounded default blocks for one kernel ``kind`` on ``geo``.

    Each kernel has *step* blocks, which only set how many grid steps
    there are, and one *traffic* block, which sets how often a large
    operand is re-read from HBM.  A step block starts from its preferred
    size and its double buffer is capped at an eighth of
    :data:`VMEM_BUDGET_BYTES`; the traffic block then takes what is left:

    * ``fp``: planes per slab (step, divisor-or-pad :func:`pick_block`);
      angles per block (traffic: one volume-slab read serves them all);
    * ``bp_matched``: angles per block (step); planes per slab (traffic:
      one projection read serves them all);
    * ``bp``: angles per chunk (step) and the whole z range per block
      when it fits; y rows per block (traffic).
    """
    nz, ny, nx = geo.n_voxel
    nv, nu = geo.n_detector
    zr = _r8(nz if planes is None else int(planes))
    step_cap = VMEM_BUDGET_BYTES // _STEP_SHARE
    per_plane = 2 * 4 * zr * ny            # one double-buffered plane
    per_angle = 2 * 4 * _r8(nv) * nu       # one double-buffered projection
    angles = max(1, min(_PREF_ANGLES, step_cap // per_angle))
    if kind == "fp":
        sp = pick_block(nx, max(1, min(_PREF_PLANES, step_cap // per_plane)))
        ab = _grow(kind, geo, {"slab_planes": sp, "angle_block": 1},
                   "angle_block", _MAX_ANGLE_BLOCK, planes)
        return {"slab_planes": sp, "angle_block": ab}
    if kind == "bp_matched":
        sp = _grow(kind, geo, {"slab_planes": 1, "angle_block": angles},
                   "slab_planes", nx, planes)
        return {"slab_planes": pick_block(nx, sp), "angle_block": angles}
    if kind == "bp":
        cfg = {"z_block": zr, "angle_chunk": angles, "y_block": 1}
        while cfg["z_block"] > 8 and not fits(kind, geo, cfg, planes):
            cfg["z_block"] = _r8(cfg["z_block"] // 2)
        cfg["z_block"] = min(cfg["z_block"],
                             nz if planes is None else int(planes))
        yb = _grow(kind, geo, cfg, "y_block", ny, planes)
        return dict(cfg, y_block=pick_block(ny, yb))
    raise ValueError(f"unknown autotune kind: {kind!r}")


def _candidates(kind: str, geo, planes: Optional[int],
                heur: Dict[str, int]) -> list:
    """Small candidate grid, floored at the heuristic config and bounded
    by the VMEM model (a candidate that cannot fit is never measured)."""
    nz, ny, nx = geo.n_voxel
    p = nz if planes is None else int(planes)
    extent = {"slab_planes": nx, "angle_block": _MAX_ANGLE_BLOCK,
              "z_block": p, "angle_chunk": _MAX_ANGLE_BLOCK, "y_block": ny}
    axes = [[(k, s) for s in sorted({min(extent[k], m * v)
                                     for m in (1, 2, 4)})]
            for k, v in heur.items()]
    out = []
    for combo in itertools.product(*axes):
        cfg = dict(combo)
        if cfg == heur or fits(kind, geo, cfg, planes):
            out.append(cfg)
    return out[:8]


# --------------------------------------------------------------------------
# measurement

def _measure(kind: str, geo, planes: Optional[int], cfg: Dict[str, int],
             interpret: bool, repeats: int) -> float:
    """Median wall seconds for one kernel call under ``cfg``."""
    import jax.numpy as jnp
    from .bp_matched import bp_matched_pallas
    from .bp_voxel import bp_voxel_pallas
    from .fp_ray import fp_ray_pallas

    nz, ny, nx = geo.n_voxel
    nv, nu = geo.n_detector
    p = nz if planes is None else int(planes)
    n_ang = 16
    # x-dominant angles only: the rotation trick means the kernels only
    # ever see x-dominant work, so that's the representative workload
    angles = jnp.asarray(np.linspace(-0.3, 0.3, n_ang), jnp.float32)
    rng = np.random.default_rng(0)

    if kind == "fp":
        vol = jnp.asarray(rng.standard_normal((p, ny, nx)), jnp.float32)

        def call():
            return fp_ray_pallas(vol, geo, angles, interpret=interpret,
                                 z0=0, **cfg)
    elif kind == "bp_matched":
        proj = jnp.asarray(rng.standard_normal((n_ang, nv, nu)), jnp.float32)

        def call():
            return bp_matched_pallas(proj, geo, angles, interpret=interpret,
                                     z0=0, z_planes=p, **cfg)
    else:
        proj = jnp.asarray(rng.standard_normal((n_ang, nv, nu)), jnp.float32)

        def call():
            return bp_voxel_pallas(proj, geo, angles, weight="fdk",
                                   interpret=interpret, z_start=0,
                                   z_planes=p, **cfg)

    try:
        call().block_until_ready()      # compile + warm
    except Exception as e:
        # a candidate the chip's compiler refuses is a bug in the VMEM
        # model or the kernel, never something to skip over quietly
        raise RuntimeError(f"autotune: {kind} candidate {cfg} failed to "
                           f"compile or run on {geo.n_voxel}") from e
    times = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        call().block_until_ready()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def tune(kind: str, geo, *, planes: Optional[int] = None,
         interpret: bool = True, repeats: int = 2) -> Dict[str, int]:
    """Measure the candidate grid and return (and memoise) the winner."""
    global _FINGERPRINT
    heur = heuristic_blocks(kind, geo, planes=planes)
    best_cfg, best_t = dict(heur), None
    for cfg in _candidates(kind, geo, planes, heur):
        t = _measure(kind, geo, planes, cfg, interpret, repeats)
        if best_t is None or t < best_t:
            best_cfg, best_t = dict(cfg), t
    key = shape_class(kind, geo, planes)
    with _LOCK:
        _TABLE[key] = best_cfg
        _FINGERPRINT += 1
    p = cache_path()
    if p:
        try:
            save(p)
        except OSError:
            pass
    return dict(best_cfg)


def get_blocks(kind: str, geo, *, planes: Optional[int] = None,
               interpret: bool = True, repeats: int = 2) -> Dict[str, int]:
    """Block config for a kernel ``kind`` on ``geo``.

    Heuristic when tuning is disabled; otherwise the memoised measured
    winner, measuring on first miss.  Thread-safe; measurement happens
    outside the table lock (concurrent first-misses may both measure —
    idempotent, last writer wins).
    """
    heur = heuristic_blocks(kind, geo, planes=planes)
    if not enabled():
        return heur
    with _LOCK:
        _maybe_load()
        hit = _TABLE.get(shape_class(kind, geo, planes))
    if hit is not None:
        # floor at the heuristic so a stale/foreign cache can never pick
        # a smaller block than the safe default
        return dict(heur, **{k: max(int(v), heur.get(k, 1))
                             for k, v in hit.items() if k in heur})
    return tune(kind, geo, planes=planes, interpret=interpret,
                repeats=repeats)


def warm(geo, *, planes: Optional[int] = None, kinds=_KINDS,
         interpret: bool = True, repeats: int = 2
         ) -> Dict[str, Dict[str, int]]:
    """Pre-bake tuned entries for every ``kind`` on ``geo``."""
    return {k: get_blocks(k, geo, planes=planes, interpret=interpret,
                          repeats=repeats)
            for k in kinds}
