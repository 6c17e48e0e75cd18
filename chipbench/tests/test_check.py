"""The check that decides ``correct``: it passes the program, and it fails
the control and each fault the cells can have (CPU, small size, the
harness's own run with the chip look skipped)."""

import dataclasses
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

import tiny
from chipbench.lib import check as chk
from chipbench.lib import harness

SEED = 2 ** 31 + 21


def run(cell="cbct512.cgls", controls=(), seconds=0.05, **size):
    readings = {}
    res = harness.run_cell(tiny.tiny_cell(cell, **size), SEED, seconds, False,
                           time.perf_counter(), controls=controls,
                           on_numbers=lambda k, v: readings.__setitem__(k, v))
    return res, readings


def test_program_is_correct_and_control_is_not():
    res, readings = run(controls=("bf16",))
    assert res.correct, res.checks
    limits = {k: v["limit"] for k, v in res.checks.items()}
    assert not chk.verdict(readings["control.bf16"], limits)
    assert list(res.checks) == list(chk.NUMBERS)
    assert res.attempted >= 1 and res.failed == 0


def _patch_step(monkeypatch, make_step):
    from repro.core.algorithms import stepwise
    alg = stepwise.REGISTRY["cgls"]
    monkeypatch.setitem(stepwise.REGISTRY, "cgls",
                        dataclasses.replace(alg, step=make_step(alg.step)))


def test_fault_state_unchanged(monkeypatch):
    _patch_step(monkeypatch, lambda real: (lambda st: st))
    res, _ = run()
    assert not res.correct


def test_fault_half_the_angles_mean_over_the_rest(monkeypatch):
    from repro.core.operator import CTOperator
    real = CTOperator.At

    def half(self, proj, angles=None, weight=None):
        keep = (np.arange(proj.shape[0]) % 2 == 0)[:, None, None]
        return 2.0 * real(self, proj * keep, angles, weight)
    monkeypatch.setattr(CTOperator, "At", half)
    res, _ = run()
    assert not res.correct


def test_fault_answer_altered(monkeypatch):
    def make(real):
        def step(st):
            st = real(st)
            st.x = st.x.at[3, 5, 7].add(1.0)
            return st
        return step
    _patch_step(monkeypatch, make)
    res, _ = run()
    assert not res.correct


def test_step_that_donates_its_state_is_still_checked(monkeypatch):
    """A step that frees the buffers of the state it was given, as a step
    that donates them does, leaves the check its copy and passes."""
    def make(real):
        def step(st):
            old = (st.x, st.r, st.p)
            st = real(st)
            for a in old:
                a.delete()
            return st
        return step
    _patch_step(monkeypatch, make)
    res, _ = run()
    assert res.correct, res.checks


@pytest.mark.parametrize("seconds,step_s", [(0.1, 0.02), (0.01, 0.02)])
def test_window_copies_the_state_only_for_its_last_iteration(
        seconds, step_s):
    copies = []
    real = harness.algorithm("cgls").snapshot
    st = types.SimpleNamespace(x=np.zeros(4), r=np.zeros(3), p=np.zeros(4),
                               gamma=np.float32(1.0), it=0)

    def step():
        time.sleep(step_s)
        st.it += 1
    target = harness.Target(step, lambda: st, None, {}, lambda: None, [])
    before, n_iter, timed = harness.run_window(
        target, seconds, lambda s: copies.append(s.it) or real(s))
    assert timed >= seconds
    assert n_iter == st.it >= 2
    assert before.it == st.it - 1
    assert 1 <= len(copies) <= 2 and len(copies) < n_iter


DIST = """
import sys, time, jax
sys.path.insert(0, {tests!r})
import tiny
from chipbench.lib import harness
if {drop!r}:
    jax.lax.psum = lambda x, axis_name, **kw: x
res = harness.run_cell(tiny.tiny_cell("cbct512-x4.cgls"), {seed}, 0.05,
                       False, time.perf_counter())
assert jax.device_count() == 4
print("CORRECT", res.correct)
"""


@pytest.mark.parametrize("drop,want", [(False, True), (True, False)])
def test_dist_exchange_between_chips(drop, want):
    """Four virtual CPU devices: the cell passes, and fails when the
    cross-chip sum of the backprojection is left out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = DIST.format(tests=tiny.TESTS, drop=drop, seed=SEED)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert f"CORRECT {want}" in out.stdout


# ---- OS-SART: 20 angles in subsets of 2, two of which hold one angle of
# each marching axis

OSSART = dict(cell="cbct256.ossart", n_angles=20)


def test_ossart_program_is_correct_and_control_is_not():
    res, readings = run(controls=("bf16",), **OSSART)
    assert res.correct, res.checks
    limits = {k: v["limit"] for k, v in res.checks.items()}
    assert not chk.verdict(readings["control.bf16"], limits)
    assert list(res.checks) == list(harness.algorithm("ossart").NUMBERS)
    assert res.attempted >= 1 and res.failed == 0


def _ossart_fault(monkeypatch, fault):
    from repro.core.algorithms import stepwise
    from repro.core.operator import CTOperator
    alg = stepwise.REGISTRY["ossart"]

    def during_step(change):
        """The real step on a state that ``change(st)`` altered; the
        returned state gets the original fields back, so only the step
        sees the change."""
        def step(st):
            kept = dict(vars(st))
            change(st)
            st = alg.step(st)
            for k in ("subsets", "factors", "lmbda"):
                setattr(st, k, kept[k])
            return st
        return step

    if fault == "state_unchanged":
        step = lambda st: st
    elif fault == "subset_skipped":
        def change(st):
            st.subsets = st.subsets[:3] + st.subsets[4:]
            st.factors = st.factors[:3] + st.factors[4:]
        step = during_step(change)
    elif fault == "lambda_halved":
        step = during_step(lambda st: setattr(st, "lmbda", 0.5 * st.lmbda))
    elif fault == "factors_of_another_subset":
        def init(*a, **kw):
            st = alg.init(*a, **kw)
            # the first two subsets trade factors (both of full size)
            st.factors = [st.factors[1], st.factors[0]] + st.factors[2:]
            return st
        monkeypatch.setitem(stepwise.REGISTRY, "ossart",
                            dataclasses.replace(alg, init=init))
        return
    elif fault == "half_the_angles":
        real = CTOperator.At

        def half(self, proj, angles=None, weight=None):
            keep = (np.arange(proj.shape[0]) % 2 == 0)[:, None, None]
            return 2.0 * real(self, proj * keep, angles, weight)
        monkeypatch.setattr(CTOperator, "At", half)
        return
    elif fault == "answer_altered":
        def step(st):
            st = alg.step(st)
            st.x = st.x.at[3, 5, 7].add(1.0)
            return st
    elif fault == "other_dominance_altered":
        # a subset's lone angle of the other marching axis, projected 1% off
        from chipbench.lib import reference as ref
        real = CTOperator.A

        def altered(self, vol, angles=None):
            out = real(self, vol, angles)
            xm = ref.x_dominant(np.asarray(angles))
            if 0 < xm.sum() < len(xm):
                lone = xm if 2 * xm.sum() < len(xm) else ~xm
                out = out * np.where(lone, 1.01, 1.0).astype(
                    np.float32)[:, None, None]
            return out
        monkeypatch.setattr(CTOperator, "A", altered)
        return
    monkeypatch.setitem(stepwise.REGISTRY, "ossart",
                        dataclasses.replace(alg, step=step))


@pytest.mark.parametrize("fault", [
    "state_unchanged", "subset_skipped", "lambda_halved",
    "factors_of_another_subset", "half_the_angles", "answer_altered",
    "other_dominance_altered"])
def test_ossart_fault(monkeypatch, fault):
    _ossart_fault(monkeypatch, fault)
    res, _ = run(**OSSART)
    assert not res.correct, res.checks
