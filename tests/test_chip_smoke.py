"""``chip_smoke.py``'s CGLS phase reads its residuals from the state.

The phase wraps the registered CGLS algorithm (``cgls_residuals``) for
the length of one ``reconstruct`` job; here the same job runs at 16^3 on
the Pallas operators in interpret mode.
"""

import dataclasses
import pathlib
import sys

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402
from repro.core.algorithms import stepwise  # noqa: E402


def test_cgls_residuals_are_those_of_the_state(monkeypatch):
    monkeypatch.setattr(chip_smoke, "N", 16)
    original = stepwise.REGISTRY["cgls"]
    states = []

    def keep(st):
        states.append((st.op, st.b, st.x))
        return st

    with chip_smoke.cgls_residuals([]) as res:
        wrapped = stepwise.REGISTRY["cgls"]
        assert wrapped is not original
        # restored to ``original`` by cgls_residuals on exit
        stepwise.REGISTRY["cgls"] = dataclasses.replace(
            wrapped, init=lambda *a, **kw: keep(wrapped.init(*a, **kw)),
            step=lambda st: keep(wrapped.step(st)))
        rec, rel, _ = chip_smoke.traced_reconstruct(
            "cgls", iters=3, mode="plain", backend="pallas")
    assert stepwise.REGISTRY["cgls"] is original

    # |b| after init (x = 0), then one reading per iteration, falling
    assert len(res) == 4 == len(states)
    assert all(b < a for a, b in zip(res, res[1:])), res
    b_norm = float(jnp.linalg.norm(states[0][1]))
    assert abs(res[0] - b_norm) <= 1e-6 * b_norm
    for got, (op, b, x) in zip(res, states):
        want = float(jnp.linalg.norm(b - op.A(x)))
        # the recurrence r -= alpha q against a fresh b - A x: fp32
        # rounding (~1e-7 of |b| here), far below what an iteration moves it
        assert abs(got - want) <= 1e-5 * b_norm, (got, want)
    np.testing.assert_array_equal(np.asarray(rec).ravel(),
                                  np.asarray(states[-1][2]).ravel())
    assert rel < 1.0
