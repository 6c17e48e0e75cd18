"""CGLS as the harness sees it: its recurrence state and its check.

Every ``algorithms/<name>.py`` defines the same names:

* ``NUMBERS`` -- the compared numbers, each with a limit in
  ``cells/<cell>.json``;
* ``block(state)`` -- wait for the state's arrays;
* ``warm(alg, state)`` -- compile, before the window, the update
  arithmetic the window runs and set-up has not (a mode's own placements
  are its target's to warm);
* ``snapshot(state)`` -- a host copy of what the check needs from before
  the checked iteration (taken with the clock paused);
* ``current(state)`` -- what the check needs from after it, as the timed
  path left it;
* ``make_check(before, after, b, angles, geo, seed, cfg, sizes,
  n_shards)`` -- ``(check, sampled)``: an object with
  ``program_numbers()`` and ``control_numbers(prec)``, and a line saying
  what was sampled.

CGLS's check is :mod:`chipbench.lib.check`.
"""

import dataclasses

from chipbench.lib import check as chk
from chipbench.lib.harness import EchoOperator

NUMBERS = chk.NUMBERS


def block(state) -> None:
    import jax
    for leaf in jax.tree_util.tree_leaves(
            [state.x, state.r, state.p, state.gamma]):
        leaf.block_until_ready()


def warm(alg, st) -> None:
    """Run the algorithm's step once on a copy of ``st`` with the operator
    echoed back: compiles the update arithmetic, leaves ``st`` untouched
    (the twin's arrays are copies, so a step may donate them)."""
    import jax.numpy as jnp
    echo = EchoOperator(lambda angles: st.r, lambda angles: st.p)
    twin = dataclasses.replace(st, op=echo, x=jnp.copy(st.x),
                               r=jnp.copy(st.r), p=jnp.copy(st.p))
    block(alg.step(twin))


def snapshot(state):
    """The recurrence variables of ``state``, copied to the host: the check
    then holds no device memory and no buffer the program may donate."""
    import jax
    x, r, p, g = jax.device_get([state.x, state.r, state.p, state.gamma])
    return chk.Iterate(x, r, p, g, int(state.it))


def current(state):
    return chk.Iterate(state.x, state.r, state.p, state.gamma, int(state.it))


def make_check(before, after, b, angles, geo, seed, cfg, sizes, n_shards):
    sample = chk.sample_angles(seed, angles, sizes["angles_per_dominance"],
                               n_shards=n_shards)
    box = chk.sample_box(seed, geo.n_voxel, sizes["box"])
    return (chk.Check(before, after, b, angles, geo, sample, box),
            f"angles {sample.tolist()}, box {box}")
