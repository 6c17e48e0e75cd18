"""Step-wise algorithm registry: the serving layer's view of the catalogue.

Every reconstruction algorithm is exposed as a resumable iterator

    state = alg.init(proj, geo, angles, op=op, **params)
    state = alg.step(state)          # one outer iteration
    image = alg.finalize(state)

so that a scheduler (:mod:`repro.serve`) can interleave iterations of
competing jobs, preempt low-priority work between steps, and checkpoint /
restore long jobs.  The monolithic entry points (``cgls``, ``ossart`` ...)
are wrappers over the very same step functions, so step-wise execution is
bit-identical to the one-shot path.

``ckpt_fields`` names the fields of the state dataclass that constitute
the resumable part (iterate + recurrence scalars); everything else is
rebuilt deterministically by ``init`` on restore.  Each registered
``step`` runs under an ``alg.step`` span (:mod:`repro.obs`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ... import obs
from .asd_pocs import (ASDPOCSState, asd_pocs_finalize, asd_pocs_init,
                       asd_pocs_step)
from .cgls import CGLSState, cgls_finalize, cgls_init, cgls_step
from .fdk import fdk
from .fista import (FISTAState, fista_tv_finalize, fista_tv_init,
                    fista_tv_step)
from .sart import (OSSARTState, ossart_finalize, ossart_init, ossart_step)


@dataclasses.dataclass(frozen=True)
class StepwiseAlgorithm:
    """A reconstruction algorithm as a resumable (init, step, finalize)."""
    name: str
    init: Callable[..., Any]
    step: Callable[[Any], Any]
    finalize: Callable[[Any], Any]
    ckpt_fields: Tuple[str, ...]
    iterative: bool = True
    # operator weighting the algorithm assumes (mirrors launch.recon):
    # Krylov/gradient methods need the exact vjp adjoint.
    default_bp_weight: str = "pmatched"
    # checkpointed scalars that are also valid ``init`` kwargs: feeding
    # them back on restore skips recomputing them (e.g. FISTA's L comes
    # from a 6-round power iteration -- the dominant admission cost)
    resume_params: Tuple[str, ...] = ()


def _spanned(step: Callable[[Any], Any]) -> Callable[[Any], Any]:
    """``step`` under an ``alg.step`` layer span."""
    @functools.wraps(step)
    def traced(state):
        with obs.span("alg.step", obs.LAYER):
            return step(state)
    return traced


# ---- direct (single-step) algorithms ---------------------------------------

@dataclasses.dataclass
class FDKState:
    """One-shot FDK wrapped in the step-wise protocol (a single step)."""
    op: Any
    proj: Any
    geo: Any
    angles: np.ndarray
    x: Optional[jnp.ndarray] = None
    it: int = 0


def fdk_init(proj, geo, angles, op=None, **_ignored) -> FDKState:
    return FDKState(op=op, proj=proj, geo=geo,
                    angles=np.asarray(angles, np.float32))


def fdk_step(st: FDKState) -> FDKState:
    st.x = fdk(st.proj, st.geo, st.angles, op=st.op)
    st.it += 1
    return st


def fdk_finalize(st: FDKState):
    return st.x


# ---- aliases (SIRT / SART are OS-SART with fixed subset sizes) -------------

def _sirt_init(proj, geo, angles, **params):
    params["subset_size"] = len(np.asarray(angles))
    return ossart_init(proj, geo, angles, **params)


def _sart_init(proj, geo, angles, **params):
    params["subset_size"] = 1
    return ossart_init(proj, geo, angles, **params)


REGISTRY: Dict[str, StepwiseAlgorithm] = {
    "ossart": StepwiseAlgorithm(
        "ossart", ossart_init, _spanned(ossart_step), ossart_finalize,
        ckpt_fields=("x", "lmbda", "it"), resume_params=("lmbda",)),
    "sirt": StepwiseAlgorithm(
        "sirt", _sirt_init, _spanned(ossart_step), ossart_finalize,
        ckpt_fields=("x", "lmbda", "it"), resume_params=("lmbda",)),
    "sart": StepwiseAlgorithm(
        "sart", _sart_init, _spanned(ossart_step), ossart_finalize,
        ckpt_fields=("x", "lmbda", "it"), resume_params=("lmbda",)),
    "cgls": StepwiseAlgorithm(
        "cgls", cgls_init, _spanned(cgls_step), cgls_finalize,
        ckpt_fields=("x", "r", "p", "gamma", "it"),
        default_bp_weight="matched"),
    "fista": StepwiseAlgorithm(
        "fista", fista_tv_init, _spanned(fista_tv_step), fista_tv_finalize,
        ckpt_fields=("x", "y", "t", "L", "it"),
        default_bp_weight="matched", resume_params=("L",)),
    "asd_pocs": StepwiseAlgorithm(
        "asd_pocs", asd_pocs_init, _spanned(asd_pocs_step), asd_pocs_finalize,
        ckpt_fields=("x", "lmbda", "dtvg", "dp_first", "it"),
        resume_params=("lmbda",)),
    "fdk": StepwiseAlgorithm(
        "fdk", fdk_init, _spanned(fdk_step), fdk_finalize,
        ckpt_fields=("x", "it"), iterative=False),
}
REGISTRY["fista_tv"] = REGISTRY["fista"]


def get_algorithm(name: str) -> StepwiseAlgorithm:
    try:
        return REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown algorithm {name!r}; "
                         f"known: {sorted(REGISTRY)}") from None


# ---- checkpoint / restore ---------------------------------------------------

def checkpoint_state(alg: StepwiseAlgorithm, state) -> Dict[str, Any]:
    """Snapshot the resumable fields as host (numpy) values."""
    out: Dict[str, Any] = {}
    for f in alg.ckpt_fields:
        v = getattr(state, f)
        if isinstance(v, (jnp.ndarray, np.ndarray)):
            v = np.asarray(v)
        out[f] = v
    return out


def restore_state(alg: StepwiseAlgorithm, state, ckpt: Dict[str, Any]):
    """Overwrite a freshly-init'ed state with checkpointed fields."""
    for f, v in ckpt.items():
        if isinstance(v, np.ndarray) and v.dtype != object:
            v = jnp.asarray(v)
        setattr(state, f, v)
    return state


__all__ = ["StepwiseAlgorithm", "REGISTRY", "get_algorithm",
           "checkpoint_state", "restore_state",
           "FDKState", "fdk_init", "fdk_step", "fdk_finalize"]
