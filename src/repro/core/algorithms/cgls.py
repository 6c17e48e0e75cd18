"""CGLS -- conjugate gradient on the normal equations (paper SS3.2, coffee
bean reconstruction).  Requires the *matched* adjoint (exact vjp transpose);
with an unmatched backprojector CG loses its convergence guarantees, which
is why TIGRE ships "pseudo-matched" weights and we ship the exact adjoint.

Step-wise form (``cgls_init`` / ``cgls_step``): the Krylov recurrence is
carried in a :class:`CGLSState` so the serving scheduler can advance one
CG iteration at a time and checkpoint/preempt between iterations.  The
monolithic :func:`cgls` wrapper runs the identical recurrence.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from ..operator import CTOperator


def _sq(a) -> jnp.ndarray:
    """``<a, a>`` as an elementwise fp32 sum.  ``jnp.vdot`` is a dot
    product, which a TPU runs at its default (bf16-pass) matmul precision;
    CGLS's step sizes must not drift with the volume size."""
    return jnp.sum(a * a)


@dataclasses.dataclass
class CGLSState:
    """Resumable CGLS Krylov state (x, residual, search direction)."""
    op: CTOperator
    b: jnp.ndarray
    x: jnp.ndarray
    r: jnp.ndarray
    p: jnp.ndarray
    gamma: jnp.ndarray
    it: int = 0


def cgls_init(proj, geo, angles, op: Optional[CTOperator] = None,
              x0=None, **_ignored) -> CGLSState:
    angles = np.asarray(angles, np.float32)
    if op is None:
        op = CTOperator(geo, angles, mode="plain", bp_weight="matched")
    b = jnp.asarray(proj)
    x = jnp.zeros(geo.n_voxel, jnp.float32) if x0 is None else jnp.asarray(x0)
    r = b - op.A(x)
    p = op.At(r, weight="matched")
    s = p
    gamma = _sq(s)
    return CGLSState(op=op, b=b, x=x, r=r, p=p, gamma=gamma)


def cgls_step(st: CGLSState) -> CGLSState:
    """One CG iteration on the normal equations."""
    q = st.op.A(st.p)
    alpha = st.gamma / (_sq(q) + 1e-30)
    st.x = st.x + alpha * st.p
    st.r = st.r - alpha * q
    s = st.op.At(st.r, weight="matched")
    gamma_new = _sq(s)
    beta = gamma_new / (st.gamma + 1e-30)
    st.gamma = gamma_new
    st.p = s + beta * st.p
    st.it += 1
    return st


def cgls_finalize(st: CGLSState):
    return st.x


def cgls(proj, geo, angles, n_iter: int = 15,
         op: Optional[CTOperator] = None, x0=None,
         callback: Optional[Callable] = None):
    st = cgls_init(proj, geo, angles, op=op, x0=x0)
    for it in range(n_iter):
        st = cgls_step(st)
        if callback is not None:
            callback(it, st.x, float(jnp.linalg.norm(st.r.ravel())))
    return cgls_finalize(st)
