"""OS-SART as the harness sees it: its state and its check.

One iteration sweeps the traffic's angle subsets ``s`` in order (contiguous
runs of ``subset_size`` angles):

    x <- x + lmbda * V_s * A_s^T (W_s * (b_s - A_s x))

with ``A_s^T`` the voxel-driven backprojection with the pseudo-matched
weight, ``W_s = 1 / A_s 1`` and ``V_s = 1 / A_s^T 1`` (zero where the sum is
not above ``EPS``), made once by the algorithm's ``init``.

A reference sweep at full size (every angle forward-projected, the whole
volume backprojected) does not fit a run, so the check is a chain, each
link held to the plain fp32 reference (:mod:`chipbench.lib.reference`) on
what it can afford.  The sweep is replayed after the window from the host
copy ``x_k`` taken before the window's last iteration: plain updates, with
the subsets and ``lmbda`` from the traffic and ``b`` from the harness, over
the program's own operators and factors.  Then:

* ``fp_gap`` -- at one angle of each marching axis in each subset, drawn
  from the seed (so every forward launch of the sweep is sampled, a
  subset's one or two angles of the other dominance too), the program's
  ``A_s x`` at every ``x`` the sweep projects vs the reference forward
  projection of that ``x``;
* ``bp_gap`` -- the program's ``A_s^T`` of every subset's weighted
  residual vs the reference's pseudo-matched backprojection, on a box of
  voxel columns (every z plane) drawn from the seed;
* ``w_gap`` -- ``1 / W_s`` at the drawn angles vs the reference's ``A 1``;
* ``v_gap`` -- ``1 / V_s`` on the box vs the reference's ``A_s^T 1``;
* ``x_gap`` -- the timed ``x_{k+1}`` vs the replay's, over the whole
  volume, relative to the replay's change ``x_{k+1} - x_k``: a subset
  skipped or reordered, another ``lmbda``, factors of another subset or
  stale state.

Each gap is a max-abs difference relative to the max-abs of the reference
quantity, the largest over the subsets.  The control puts the reference at
a lower precision in the program's place: its forward and backward
projections, and a sweep whose update products are rounded to it.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.lib import check as chk
from chipbench.lib import reference as ref
from chipbench.lib.data import seed_rng
from chipbench.lib.harness import EchoOperator

NUMBERS = ("fp_gap", "bp_gap", "w_gap", "v_gap", "x_gap")
EPS = 1e-6      # W and V are zero where A 1, A^T 1 are not above this


def block(state) -> None:
    state.x.block_until_ready()


def warm(alg, st) -> None:
    """Run the step once on a copy of ``x`` with the operator echoed back
    (the state's own factors have the shapes and placements of the real
    operators' results): compiles each element-wise op of the sweep, leaves
    ``st`` untouched.  The operators themselves compiled in ``init``."""
    w = {int(f.shape[0]): f for f, _ in st.factors}
    echo = EchoOperator(lambda angles: w[len(angles)],
                        lambda angles: st.factors[0][1])
    block(alg.step(dataclasses.replace(st, op=echo, x=jnp.copy(st.x))))


@dataclasses.dataclass
class Sweep:
    """The iterate at one iteration boundary; after the window also the
    program's operator and factors, which the replay runs over."""
    x: object
    it: int
    op: object = None
    factors: list = None


def snapshot(state) -> Sweep:
    return Sweep(jax.device_get(state.x), int(state.it))


def current(state) -> Sweep:
    return Sweep(state.x, int(state.it), state.op, list(state.factors))


def subsets(n_angles: int, size: int):
    """Contiguous runs of ``size`` angles, in angle order."""
    return [np.arange(s, min(s + size, n_angles))
            for s in range(0, n_angles, size)]


def sample_angles(seed: int, angles, runs):
    """Per subset, one angle of each marching axis it holds, drawn from the
    seed: the program launches a forward projection per dominance group,
    so each launch of the sweep is sampled."""
    rng = seed_rng(seed, 3)
    xm = ref.x_dominant(angles)
    return [[int(rng.choice(idx[xm[idx] == xdom])) for xdom in (True, False)
             if (xm[idx] == xdom).any()] for idx in runs]


def make_check(before, after, b, angles, geo, seed, cfg, sizes, n_shards):
    params = cfg["params"]
    runs = subsets(len(angles), int(params["subset_size"]))
    picks = sample_angles(seed, angles, runs)
    box = chk.sample_box(seed, geo.n_voxel, sizes["box"])
    c = Check(before, after, b, angles, geo, runs, picks, box,
              float(params["lmbda"]), cfg["bp_weight"])
    return c, f"angles {picks}, box {box}"


@functools.lru_cache(maxsize=None)
def _fp(geo, xdom: bool, prec: str):
    return jax.jit(lambda v, a: ref.fp_marching(v, geo, a, xdom, prec))


@functools.lru_cache(maxsize=None)
def _bp_box(geo, n: int, prec: str):
    return jax.jit(lambda p, a, y0, x0: ref.bp_voxel_box(p, geo, a,
                                                         (y0, x0, n), prec))


def _relmax(d, scale) -> float:
    return float(jnp.max(jnp.abs(d)) / jnp.max(jnp.abs(scale)))


def _reciprocal(f):
    """``A 1`` (or ``A^T 1``) back from a factor ``1 / A 1``."""
    return jnp.where(f > 0, 1.0 / jnp.where(f > 0, f, 1.0), 0.0)


class Check:
    def __init__(self, before: Sweep, after: Sweep, b, angles, geo, runs,
                 picks, box, lmbda: float, weight: str):
        self.x0 = jax.device_put(before.x, jax.devices()[0])
        self.x1 = after.x
        self.advanced = after.it - before.it
        self.op, self.factors = after.op, after.factors
        self.b = b
        self.angles = np.asarray(angles, np.float32)
        self.geo, self.runs, self.picks = geo, runs, picks
        self.y0, self.x0_box, self.n = box
        self.lmbda, self.weight = lmbda, weight

    # -- reference windows -------------------------------------------------

    def _fp_at(self, vol, a: int, prec: str):
        """The reference projection of ``vol`` at angle index ``a``."""
        th = self.angles[a:a + 1]
        xdom = bool(ref.x_dominant(th)[0])
        return _fp(self.geo, xdom, prec)(vol, jnp.asarray(th))[0]

    def _bp_at(self, proj, idx, prec: str):
        return _bp_box(self.geo, self.n, prec)(
            proj, jnp.asarray(self.angles[idx]), jnp.float32(self.y0),
            jnp.float32(self.x0_box))

    def _box(self, vol):
        nz = vol.shape[0]
        return jax.lax.dynamic_slice(vol, (0, self.y0, self.x0_box),
                                     (nz, self.n, self.n))

    # -- the sweep -----------------------------------------------------------

    def _sweep(self, prec: str, visit=None):
        """One OS-SART sweep from ``x_k`` over the program's operators and
        factors, its update products at ``prec``; ``visit(s, x, Ax,
        resid, upd)`` sees each subset's quantities."""
        x = self.x0
        for s, idx in enumerate(self.runs):
            w, v = self.factors[s]
            a_sub = jnp.asarray(self.angles[idx])
            ax = self.op.A(x, a_sub)
            resid = ref.mul(w, self.b[jnp.asarray(idx)] - ax, prec)
            upd = self.op.At(resid, a_sub, weight=self.weight)
            if visit is not None:
                visit(s, x, ax, resid, upd)
            x = x + ref.mul(ref.mul(self.lmbda, v, prec), upd, prec)
        return x

    def _factor_gaps(self, prec):
        """``w_gap`` and ``v_gap``: the program's factors (``prec`` None)
        or the reference at ``prec`` against the fp32 reference."""
        nv, nu = self.geo.n_detector
        ones = jnp.ones(self.geo.n_voxel, jnp.float32)
        keep = lambda a: jnp.where(a > EPS, a, 0.0)
        w_gap = v_gap = 0.0
        for s, idx in enumerate(self.runs):
            w, v = self.factors[s]
            for a in self.picks[s]:
                a1 = keep(self._fp_at(ones, a, "f32"))
                got = (_reciprocal(w[a - idx[0]]) if prec is None
                       else keep(self._fp_at(ones, a, prec)))
                w_gap = max(w_gap, _relmax(got - a1, a1))
            flat = jnp.ones((len(idx), nv, nu), jnp.float32)
            v1 = keep(self._bp_at(flat, idx, "f32"))
            got = (_reciprocal(self._box(v)) if prec is None
                   else keep(self._bp_at(flat, idx, prec)))
            v_gap = max(v_gap, _relmax(got - v1, v1))
        return w_gap, v_gap

    def _numbers(self, prec):
        gaps = {"fp_gap": 0.0, "bp_gap": 0.0}

        def visit(s, x, ax, resid, upd):
            idx = self.runs[s]
            for a in self.picks[s]:
                want = self._fp_at(x, a, "f32")
                got = ax[a - idx[0]] if prec is None else \
                    self._fp_at(x, a, prec)
                gaps["fp_gap"] = max(gaps["fp_gap"],
                                     _relmax(got - want, want))
            want = self._bp_at(resid, idx, "f32")
            got = self._box(upd) if prec is None else \
                self._bp_at(resid, idx, prec)
            gaps["bp_gap"] = max(gaps["bp_gap"], _relmax(got - want, want))

        x_rep = self._sweep("f32", visit)
        x_out = self.x1 if prec is None else self._sweep(prec)
        gaps["x_gap"] = _relmax(x_out - x_rep, x_rep - self.x0)
        gaps["w_gap"], gaps["v_gap"] = self._factor_gaps(prec)
        return gaps

    def _shapes_agree(self) -> bool:
        return (len(self.factors) == len(self.runs)
                and all(w.shape[0] == len(idx)
                        for (w, _), idx in zip(self.factors, self.runs)))

    def program_numbers(self):
        """The program's last sweep against the replay and the reference."""
        if self.advanced != 1 or not self._shapes_agree():
            return {k: float("inf") for k in NUMBERS}
        return self._numbers(None)

    def control_numbers(self, prec: str):
        """The reference at ``prec`` put in the program's place."""
        return self._numbers(prec)
