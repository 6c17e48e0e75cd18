"""Reconstruction driver: a thin client of the serving scheduler.

Builds a :class:`repro.serve.ReconJob` from the CLI arguments, submits it
to a :class:`repro.serve.Scheduler` and drives it with the threaded
:class:`repro.serve.AsyncDriver`; the scheduler picks the execution mode
(in-core "plain" vs out-of-core "stream") from the planned footprint
unless ``--mode`` forces one, and ``--backend`` selects the kernel
backend (ref | pallas | auto; see docs/operators.md).  ``--mode dist`` bypasses the
scheduler and runs the shard_map backend over the local device mesh.
``--snapshot-dir`` makes the run restart-safe: a SIGTERM parks the job's
step-wise checkpoint durably, and re-running the same command resumes it
bit-identically instead of starting over.  ``--pods N`` serves the job
through a simulated multi-pod fleet instead of a single scheduler
(routing + work stealing; see docs/serve.md); combined with
``--snapshot-dir`` the *fleet* is durable — each pod snapshots into its
own subdirectory, a ``fleet.json`` manifest records the membership, and
a re-run rebuilds the whole fleet with
``MultiPodScheduler.restore_fleet`` and resumes bit-identically.
``--pin-devices`` pins each pod to real local JAX devices through a
pod-axis mesh; the manifest records budgets only, so the restore path
hands the same mesh back to ``restore_fleet`` to re-derive the pins.

``--trace out.json`` enables the process tracer
(:mod:`repro.obs`) for the run and writes a Chrome-trace JSON —
load it at https://ui.perfetto.dev to see the per-slab
H2D / compute / D2H spans on per-device tracks (the paper's Fig 3/5
timelines); ``--prometheus out.prom`` writes a Prometheus-style text
snapshot at exit — the tracer's phase totals and counters plus the
calibration, SLO and memory-margin families.  ``--metrics-port N``
serves the same exposition live over HTTP for the duration of the run
(scrape ``/metrics``; 0 picks a free port), and
``--calibration-report`` prints the modeled-vs-measured calibration
ledger + SLO report as JSON at exit (see docs/observability.md).

Numerics are identical to the old monolithic driver: the scheduler steps
the same algorithm iterators the monolithic entry points wrap.

Usage::

    PYTHONPATH=src python -m repro.launch.recon --alg cgls --n 64 \
        --angles 96 --iters 10 --mode auto
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core.geometry import ConeGeometry
from repro.core.operator import CTOperator
from repro.core.splitting import MemoryModel
from repro.core import algorithms as alg
from repro.data import make_ct_dataset
from repro.serve import AsyncDriver, JobStatus, ReconJob, Scheduler


def _job_params(algname: str, n_angles: int) -> dict:
    if algname == "ossart":
        return {"subset_size": max(n_angles // 8, 1)}
    return {}


def reconstruct(algname: str = "cgls", n: int = 64, n_angles: int = 96,
                iters: int = 10, mode: str = "auto",
                device_bytes: int = 0, verbose: bool = True,
                snapshot_dir: str = "", pods: int = 1,
                backend: str = "auto", trace: str = "",
                prometheus: str = "", pin_devices: bool = False,
                metrics_port: int = -1, calibration_report: bool = False,
                autotune: bool = False):
    if autotune:
        # measured block-size tuning for the pallas kernels: first use of
        # each (kind, geometry shape) times a candidate grid and memoises
        # the winner (persisted via REPRO_AUTOTUNE_CACHE when set; pre-
        # bake with tools/autotune.py).  See docs/operators.md.
        from repro.kernels import autotune as _autotune
        _autotune.enable(True)
    # every observability output needs the tracer on: the trace/snapshot
    # exporters read its ring buffer, the live endpoint re-reads it per
    # scrape, and the calibration ledger folds its fleet event log
    if trace or prometheus or calibration_report or metrics_port >= 0:
        from repro import obs
        obs.get_tracer().enable()
        server = None
        if metrics_port >= 0:
            server = obs.MetricsServer(port=metrics_port)
            server.start()
            if verbose:
                print(f"[recon] live metrics at {server.url}")
        try:
            return _reconstruct(algname, n, n_angles, iters, mode,
                                device_bytes, verbose, snapshot_dir,
                                pods, backend, pin_devices)
        finally:
            # written even on a preempted exit: the partial timeline is
            # exactly what you want to look at after a preemption
            if trace:
                obs.write_chrome_trace(trace)
                if verbose:
                    print(f"[recon] chrome trace -> {trace} "
                          f"(load at https://ui.perfetto.dev)")
            if prometheus:
                # the full exposition: tracer families plus the
                # calibration / SLO / memory-margin families
                with open(prometheus, "w") as f:
                    f.write(obs.metrics_text())
                if verbose:
                    print(f"[recon] prometheus snapshot -> {prometheus}")
            if calibration_report:
                import json
                report = {
                    "calibration": obs.CalibrationLedger.from_events()
                                      .report(),
                    "memory": [m.as_dict()
                               for m in obs.memory_calibration()],
                    "slo": obs.slo_report(),
                }
                print(json.dumps(report, indent=2, sort_keys=True))
            if server is not None:
                server.stop()
    return _reconstruct(algname, n, n_angles, iters, mode, device_bytes,
                        verbose, snapshot_dir, pods, backend, pin_devices)


def _reconstruct(algname, n, n_angles, iters, mode, device_bytes,
                 verbose, snapshot_dir, pods, backend, pin_devices=False):
    geo = ConeGeometry.nice(n)
    job_backend = None if backend == "auto" else backend
    vol, angles, proj = make_ct_dataset(geo, n_angles, backend=backend)
    mem = (MemoryModel(device_bytes=device_bytes)
           if device_bytes else MemoryModel.from_device())
    if verbose:
        src = ("--device-bytes" if device_bytes else
               "device" if mem != MemoryModel() else "default")
        print(f"[recon] device memory budget {mem.device_bytes / 2**30:.2f} "
              f"GiB (from {src})")
    t0 = time.time()
    if pods > 1:
        # multi-pod fleet (simulated host groups): the job is routed to
        # the pod whose topology models the cheapest completion; idle
        # pods would steal parked work on a busier trace (bench_serve.py)
        if mode == "dist":
            raise ValueError("--mode dist bypasses the scheduler and "
                             "cannot be combined with --pods")
        import os
        from repro.checkpoint import PreemptionGuard
        from repro.serve import (MultiPodDriver, MultiPodScheduler, Pod,
                                 PodSpec)
        from repro.serve.pool import FLEET_MANIFEST
        guard = PreemptionGuard()
        root = snapshot_dir or None
        mesh = None
        if pin_devices:
            # real device handles: split the local devices into `pods`
            # groups along a leading "pod" mesh axis.  On restore the
            # same mesh re-derives the pins the manifest cannot record.
            from repro.launch.mesh import make_pod_mesh, pod_device_groups
            mesh = make_pod_mesh(pods)
        if root and os.path.isfile(os.path.join(root, FLEET_MANIFEST)):
            # a previous run left a fleet snapshot: rebuild membership +
            # parked jobs and resume them instead of starting over
            mps = MultiPodScheduler.restore_fleet(root, guard=guard,
                                                  mesh=mesh)
        elif mesh is not None:
            groups = pod_device_groups(mesh)
            mps = MultiPodScheduler(
                [Pod(PodSpec(f"pod{i}", n_devices=len(g), memory=mem,
                             jax_devices=tuple(g)), guard=guard)
                 for i, g in enumerate(groups)],
                snapshot_root=root)
        else:
            mps = MultiPodScheduler(
                [Pod(PodSpec(f"pod{i}", n_devices=1, memory=mem),
                     guard=guard) for i in range(pods)],
                snapshot_root=root)
        if mps.restored_jobs:
            jid = mps.restored_jobs[0]
            if verbose:
                done = mps.record(jid).iterations_done
                print(f"[recon] resuming {jid} on a restored "
                      f"{len(mps.pods)}-pod fleet "
                      f"({done} iterations already done)")
        else:
            jid = mps.submit(ReconJob(
                algname, geo, angles, proj, n_iter=iters,
                params=_job_params(algname, n_angles),
                mode=None if mode == "auto" else mode,
                backend=job_backend))
        # periodic per-pod snapshots make a kill -9 recoverable too
        MultiPodDriver(mps, snapshot_every_seconds=1.0 if root else 0.0
                       ).run()
        record = mps.record(jid)
        # parked states only: a FAILED job must fall through to
        # mps.result() below and raise its real error, not masquerade
        # as a resumable preemption
        if record.status in (JobStatus.PREEMPTED, JobStatus.PENDING):
            if verbose:
                where = (f"; fleet snapshot in {root} -- re-run to resume"
                         if root else " (no --snapshot-dir: progress lost)")
                print(f"[recon] fleet preempted after "
                      f"{record.iterations_done}/{iters} iterations{where}")
            return None, None
        if verbose:
            print(f"[recon] pod fleet x{len(mps.pods)}: job ran on "
                  f"{mps.owner(jid).name}")
        rec = mps.result(jid)
    elif mode == "dist":
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model_axis=1)
        op = CTOperator(geo, angles, mode="dist", mesh=mesh,
                        bp_weight="matched" if algname in ("cgls", "fista")
                        else "pmatched", backend=job_backend)
        with mesh:
            rec = _run_monolithic(algname, proj, geo, angles, iters, op)
    else:
        from repro.checkpoint import PreemptionGuard
        sched = Scheduler(n_devices=1, memory=mem,
                          guard=PreemptionGuard(),
                          snapshot_dir=snapshot_dir or None)
        if snapshot_dir and sched.restore(snapshot_dir):
            jid = next(iter(sched.records))   # resume the parked job
            if verbose:
                done = sched.records[jid].iterations_done
                print(f"[recon] resuming {jid} from snapshot "
                      f"({done} iterations already done)")
        else:
            jid = sched.submit(ReconJob(
                algname, geo, angles, proj, n_iter=iters,
                params=_job_params(algname, n_angles),
                mode=None if mode == "auto" else mode,
                backend=job_backend))
        AsyncDriver(sched).run()
        record = sched.records[jid]
        if record.status is JobStatus.PREEMPTED:   # SIGTERM parked it
            if verbose:
                where = (f"; snapshot in {snapshot_dir} -- re-run to resume"
                         if snapshot_dir
                         else " (no --snapshot-dir: progress lost)")
                print(f"[recon] preempted after "
                      f"{record.iterations_done}/{iters} iterations{where}")
            return None, None
        rec = sched.result(jid)
    dt = time.time() - t0
    rec = np.asarray(rec)
    rel = float(np.linalg.norm(rec - vol) / np.linalg.norm(vol))
    if verbose:
        print(f"[recon] {algname} N={n} angles={n_angles} iters={iters} "
              f"mode={mode}: rel_err={rel:.4f} ({dt:.1f}s)")
    return rec, rel


def _run_monolithic(algname, proj, geo, angles, iters, op):
    """Direct (non-scheduled) path for backends the scheduler doesn't own."""
    if algname == "cgls":
        return alg.cgls(proj, geo, angles, n_iter=iters, op=op)
    if algname == "ossart":
        return alg.ossart(proj, geo, angles, n_iter=iters,
                          subset_size=max(len(np.asarray(angles)) // 8, 1),
                          op=op)
    if algname == "sirt":
        return alg.sirt(proj, geo, angles, n_iter=iters, op=op)
    if algname == "fdk":
        return alg.fdk(proj, geo, angles, op=op)
    if algname == "fista":
        return alg.fista_tv(proj, geo, angles, n_iter=iters, op=op)
    if algname == "asd_pocs":
        return alg.asd_pocs(proj, geo, angles, n_iter=iters, op=op)
    raise ValueError(f"unknown algorithm {algname!r}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--alg", default="cgls")
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--angles", type=int, default=96)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--mode", default="auto",
                    choices=("auto", "plain", "stream", "dist"))
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "ref", "pallas"),
                    help="kernel backend for the operators: the pure-JAX "
                         "projectors (ref), the Pallas TPU kernels "
                         "(pallas; interpret mode off-TPU), or per-JAX-"
                         "backend auto-detection (see docs/operators.md)")
    ap.add_argument("--device-bytes", type=int, default=0,
                    help="per-device memory budget (streaming/placement)")
    ap.add_argument("--snapshot-dir", default="",
                    help="durable checkpoint directory: SIGTERM parks the "
                         "job there; re-running resumes bit-identically")
    ap.add_argument("--pods", type=int, default=1,
                    help="serve through a fleet of this many single-device "
                         "pods (multi-pod routing + work stealing; see "
                         "docs/serve.md); works with --snapshot-dir for "
                         "fleet-level durable resume")
    ap.add_argument("--pin-devices", action="store_true",
                    help="pin each pod to real local JAX devices via a "
                         "pod-axis mesh (local device count must divide "
                         "into --pods); on restore the same mesh "
                         "re-derives the pins the fleet manifest cannot "
                         "record")
    ap.add_argument("--trace", default="",
                    help="enable tracing and write a Chrome-trace JSON "
                         "here (open at https://ui.perfetto.dev; see "
                         "docs/observability.md)")
    ap.add_argument("--prometheus", default="",
                    help="write a Prometheus-style text snapshot (phase "
                         "totals, counters, calibration / SLO / memory-"
                         "margin families) here at exit")
    ap.add_argument("--metrics-port", type=int, default=-1,
                    help="serve the live Prometheus exposition over HTTP "
                         "on this port for the duration of the run "
                         "(0 = pick a free port); implies tracing")
    ap.add_argument("--calibration-report", action="store_true",
                    help="print the modeled-vs-measured calibration "
                         "ledger + SLO report as JSON at exit; implies "
                         "tracing (see docs/observability.md)")
    ap.add_argument("--autotune", action="store_true",
                    help="measure pallas kernel block sizes on first use "
                         "instead of the static heuristic (equivalent to "
                         "REPRO_AUTOTUNE=1; persist winners across runs "
                         "with REPRO_AUTOTUNE_CACHE=path or pre-bake with "
                         "tools/autotune.py)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    reconstruct(args.alg, args.n, args.angles, args.iters, args.mode,
                args.device_bytes, snapshot_dir=args.snapshot_dir,
                pods=args.pods, backend=args.backend, trace=args.trace,
                prometheus=args.prometheus, pin_devices=args.pin_devices,
                metrics_port=args.metrics_port,
                calibration_report=args.calibration_report,
                autotune=args.autotune)


if __name__ == "__main__":
    main()
