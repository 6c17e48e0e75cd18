"""Mode ``plain``: one job through the serving path, in-core.

A ``ReconJob`` (the traffic's algorithm and ``params``) admitted by
``repro.serve.Scheduler``, which must pick in-core execution from its own
footprint model, and stepped through ``Scheduler.claim_step`` /
``JobExecutor.step`` / ``Scheduler.finish_step``: the loop
``repro.launch.recon`` runs.
"""

import time

from chipbench.lib.harness import Target, algorithm, annotate, log_peak


def build(geo, angles, vol, cfg) -> Target:
    import jax
    import jax.numpy as jnp
    from repro.core.backend import get_backend
    from repro.core.geometry import dominant_axis_mask
    from repro.core.splitting import MemoryModel
    from repro.serve import JobStatus, ReconJob, Scheduler

    algo = algorithm(cfg["algorithm"])
    fp = get_backend(cfg["backend"]).fp_mixed(geo, dominant_axis_mask(angles))
    with annotate("chipbench.setup.project"):
        b = fp(vol, jnp.asarray(angles))
        b.block_until_ready()
    log_peak("data projection")
    sched = Scheduler(n_devices=1,
                      memory=MemoryModel.from_device(jax.devices()[0]))
    jid = sched.submit(ReconJob(cfg["algorithm"], geo, angles, b,
                                n_iter=10 ** 9, backend=cfg["backend"],
                                params=dict(cfg.get("params", {}))))
    with annotate("chipbench.setup.admit"):
        sched.admit()
    log_peak("admission and init")
    rec = sched.records[jid]
    if rec.status is not JobStatus.RUNNING:
        raise RuntimeError(f"job not admitted: {rec.status} {rec.error}")
    if rec.streamed:
        raise RuntimeError("the scheduler chose out-of-core execution; this "
                           "cell measures in-core (plain) execution")
    run = sched.running[jid]
    executor, slot = run.executor, run.slot
    weight = executor._state.op.bp_weight
    if weight != cfg["bp_weight"]:
        raise RuntimeError(f"the job backprojects with {weight!r}; the "
                           f"traffic says {cfg['bp_weight']!r}")
    with annotate("chipbench.setup.warm"):
        algo.warm(executor.alg, executor._state)
    log_peak("warm pass")

    def step():
        claimed = sched.claim_step(slot)
        t0 = time.monotonic()
        with annotate("chipbench.step"):
            claimed.executor.step()
        sched.finish_step(claimed, time.monotonic() - t0)

    def release():
        executor.release()
        sched.running.clear()

    return Target(step, lambda: executor._state, b,
                  executor._state.op.kernel_config(), release,
                  jax.devices()[:1])
