"""One benchmark run: set up a cell, time its window, check it, report.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix, ``configs/<config>.json`` holds the
deployment (geometry, angles, execution mode, precision),
``traffic/<traffic>.json`` the job mix, ``cells/<cell>.json`` the check's
sample sizes and limits, ``metrics/<metric>.py`` one reader per per-layer
metric and ``counts/<kernel>.py`` one work count per kernel.

The window drives the program the way its users do:

* ``mode: plain`` -- a ``ReconJob`` admitted by ``repro.serve.Scheduler``
  (which must pick in-core execution from its own footprint model) and
  stepped through ``Scheduler.claim_step`` / ``JobExecutor.step`` /
  ``Scheduler.finish_step``, the loop ``repro.launch.recon`` runs;
* ``mode: dist`` -- the step-wise algorithm over
  ``CTOperator(mode="dist")`` on ``make_host_mesh(model_axis=1)``, the path
  ``recon --mode dist`` runs.

Set-up is data, operator build, compilation, the algorithm's ``init`` and
one warm pass of the update arithmetic; the window then runs whole
iterations back to back and stops at the first boundary at or after the
requested seconds (:func:`run_window`).
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import tempfile
import time
from typing import Callable, Dict, List, Optional

from .trace import WINDOW

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)                 # chipbench/
ROOT = os.path.dirname(BENCH)                 # the checkout


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


# --------------------------------------------------------------------------
# finding things by name

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    params: dict                  # cells/<cell>.json
    end_to_end: List[dict]
    per_layer: List[dict]


def _reports(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def find_cell(bench: dict, name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files loaded."""
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(wl)})")
    w = wl[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    params = load_json(os.path.join(BENCH, "cells", name + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(w["chips"]), config, traffic, params, e2e,
                per_layer)


def kernel_table() -> Dict[str, object]:
    """kernel name -> its ``counts/<kernel>.py`` module (which carries the
    names the kernel has in a trace, ``TRACE_NAMES``)."""
    d = os.path.join(BENCH, "counts")
    return {f[:-3]: load_module(os.path.join(d, f), f"chipbench_counts_{f[:-3]}")
            for f in sorted(os.listdir(d)) if f.endswith(".py")}


# --------------------------------------------------------------------------
# compile events (JAX's own monitoring hooks)

class CompileCounter:
    """Counts JAX's compile-path events; ``snapshot()`` / ``since()``."""
    EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax
        self.counts = {v: 0 for v in self.EVENTS.values()}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, secs, **_):
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def _ev(self, event, **_):
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)

    def since(self, snap: Dict[str, int]) -> Dict[str, int]:
        return {k: v - snap.get(k, 0) for k, v in self.counts.items()}


# --------------------------------------------------------------------------
# the system under test

@dataclasses.dataclass
class Target:
    """The system under test as the window drives it: one step, and the
    current recurrence state."""
    step: Callable[[], None]
    state: Callable[[], object]
    b: object
    kernel_config: dict
    release: Callable[[], None]


def _block(state) -> None:
    import jax
    for leaf in jax.tree_util.tree_leaves(
            [state.x, state.r, state.p, state.gamma]):
        leaf.block_until_ready()


class _EchoOperator:
    """Stands in for the operator in a warm pass of the update arithmetic:
    returns arrays of the real operators' shapes and placements, so each
    element-wise op of the step compiles before the window."""

    def __init__(self, st):
        self._r, self._p = st.r, st.p

    def A(self, vol, angles=None):
        return self._r

    def At(self, proj, angles=None, weight=None):
        return self._p


def warm_update(alg, st) -> None:
    """Run the algorithm's step once on a copy of ``st`` with the operator
    echoed back: compiles the update arithmetic, leaves ``st`` untouched
    (the twin's arrays are copies, so a step may donate them)."""
    import jax.numpy as jnp
    twin = dataclasses.replace(st, op=_EchoOperator(st), x=jnp.copy(st.x),
                               r=jnp.copy(st.r), p=jnp.copy(st.p))
    _block(alg.step(twin))


def build_plain(geo, angles, vol, cfg) -> Target:
    import jax
    import jax.numpy as jnp
    from repro.core.backend import get_backend
    from repro.core.geometry import dominant_axis_mask
    from repro.core.splitting import MemoryModel
    from repro.serve import JobStatus, ReconJob, Scheduler

    fp = get_backend(cfg["backend"]).fp_mixed(geo, dominant_axis_mask(angles))
    with annotate("chipbench.setup.project"):
        b = fp(vol, jnp.asarray(angles))
        b.block_until_ready()
    log_peak("data projection")
    sched = Scheduler(n_devices=1,
                      memory=MemoryModel.from_device(jax.devices()[0]))
    jid = sched.submit(ReconJob(cfg["algorithm"], geo, angles, b,
                                n_iter=10 ** 9, backend=cfg["backend"]))
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
    with annotate("chipbench.setup.warm"):
        warm_update(executor.alg, executor._state)
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
                  executor._state.op.kernel_config(), release)


def build_dist(geo, angles, vol, cfg) -> Target:
    from repro.core.algorithms.stepwise import get_algorithm
    from repro.core.operator import CTOperator
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(model_axis=1)
    alg = get_algorithm(cfg["algorithm"])
    ctx = contextlib.ExitStack()
    ctx.enter_context(mesh)
    op = CTOperator(geo, angles, mode="dist", mesh=mesh,
                    bp_weight=cfg["bp_weight"], backend=cfg["backend"])
    with annotate("chipbench.setup.project"):
        b = op.A(vol)
        b.block_until_ready()
    log_peak("data projection")
    with annotate("chipbench.setup.init"):
        holder = [alg.init(b, geo, angles, op=op)]
        _block(holder[0])
    log_peak("init")
    with annotate("chipbench.setup.warm"):
        # init projected x = 0, made on one device; the iterations project
        # p, which lives on every chip of the mesh: a placement the forward
        # operator compiles for separately
        holder[0].op.A(holder[0].p).block_until_ready()
        warm_update(alg, holder[0])
    log_peak("warm pass")

    def step():
        with annotate("chipbench.step"):
            holder[0] = alg.step(holder[0])
            _block(holder[0])

    def release():
        holder.clear()
        ctx.close()

    return Target(step, lambda: holder[0], b, op.kernel_config(), release)


TARGETS = {"plain": build_plain, "dist": build_dist}


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def hbm_peaks(devices=None) -> List[int]:
    """Each device's peak bytes in use so far in this process."""
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in (devices or jax.devices())]


def log_peak(after: str) -> None:
    log(f"peak HBM after {after} (bytes, largest device): {max(hbm_peaks())}")


def snapshot(state):
    """The recurrence variables of ``state``, copied to the host: the check
    then holds no device memory and no buffer the program may donate."""
    import jax
    from . import check as chk
    x, r, p, g = jax.device_get([state.x, state.r, state.p, state.gamma])
    return chk.Iterate(x, r, p, g, int(state.it))


def run_window(target: Target, seconds: float):
    """Whole iterations back to back until ``seconds`` have passed.

    Before the iteration expected to close the window (the time so far
    plus the last iteration's reaches ``seconds``) the state is copied to
    the host for the check, with the clock and the ``chipbench.window``
    span paused.  The window stops at the first iteration boundary at or
    after ``seconds`` whose iteration was copied; where a quicker iteration
    falls short, the copy is taken again before the next one.  Returns the
    copy, the iterations run and the window's seconds."""
    n_iter, timed, last = 0, 0.0, 0.0
    before = None
    span = annotate(WINDOW)
    span.__enter__()
    t0 = time.perf_counter()
    while True:
        if timed + (time.perf_counter() - t0) + last >= seconds:
            timed += time.perf_counter() - t0
            span.__exit__(None, None, None)
            with annotate("chipbench.snapshot"):
                before = snapshot(target.state())
            span = annotate(WINDOW)
            span.__enter__()
            t0 = time.perf_counter()
        t = time.perf_counter()
        target.step()
        last = time.perf_counter() - t
        n_iter += 1
        if before is not None and \
                timed + (time.perf_counter() - t0) >= seconds:
            break
        before = None
    timed += time.perf_counter() - t0
    span.__exit__(None, None, None)
    return before, n_iter, timed


# --------------------------------------------------------------------------
# one run

@dataclasses.dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, dict]
    device: dict
    checks: Dict[str, dict]
    breakdown: Optional[dict] = None

    def line(self) -> str:
        out = {"correct": self.correct, "attempted": self.attempted,
               "failed": self.failed, "metrics": self.metrics,
               "device": self.device}
        if self.breakdown is not None:
            out["breakdown"] = self.breakdown
        out["checks"] = self.checks
        return json.dumps(out)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, controls=(), trace_dir: Optional[str] = None,
             on_numbers=None) -> Result:
    """Set up ``cell``, run its window, check it; returns the result.

    ``controls`` (proof runs only) also reads the check's numbers with the
    reference at those precisions in the program's place; ``on_numbers``
    receives ``(kind, numbers)`` for each reading."""
    import jax
    from . import check as chk
    from . import reference as ref
    from .data import phantom
    from repro.core.geometry import ConeGeometry
    from repro.kernels import autotune

    autotune.enable(False)        # the heuristic blocks, never a timing
    cfg = dict(cell.config, **cell.traffic)
    g = cfg["geometry"]
    geo = ConeGeometry(DSD=g["DSD"], DSO=g["DSO"],
                       n_voxel=tuple(g["n_voxel"]),
                       s_voxel=tuple(g["s_voxel"]),
                       n_detector=tuple(g["n_detector"]),
                       s_detector=tuple(g["s_detector"]))
    rgeo = ref.Geometry.from_config(cfg)
    angles = ref.scan_angles(cfg["n_angles"])
    devices = jax.devices()[:cell.chips] if cfg["mode"] == "plain" \
        else jax.devices()
    counter = CompileCounter()
    phases: Dict[str, float] = {}

    def phase(name, t0):
        phases[name] = time.perf_counter() - t0

    t = time.perf_counter()
    with annotate("chipbench.setup.data"):
        vol = phantom(seed, g["n_voxel"], g["s_voxel"])
        vol.block_until_ready()
    phase("data", t)
    t = time.perf_counter()
    target = TARGETS[cfg["mode"]](geo, angles, vol, cfg)
    del vol
    phase("build_project_init_warm", t)
    log("blocks " + json.dumps({k: v for k, v in target.kernel_config.items()
                                if "." in k}))

    setup_peak = hbm_peaks(devices)
    profiler, keep_trace = None, trace_dir is not None
    if trace:
        profiler = trace_dir or tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(profiler)
    snap = counter.snapshot()
    setup_s = time.perf_counter() - t_start
    before, n_iter, elapsed = run_window(target, seconds)
    if profiler:
        jax.profiler.stop_trace()
    in_window = counter.since(snap)
    s = target.state()
    after = chk.Iterate(s.x, s.r, s.p, s.gamma, int(s.it))
    del s
    peak = hbm_peaks(devices)
    b = target.b
    target.release()
    log(f"setup phases (s): {json.dumps(phases)}; setup_s {setup_s}")
    log(f"window: {n_iter} iterations in {elapsed} s")
    log(f"compile events inside the window: {json.dumps(in_window)}")
    log("peak HBM per device (bytes), set-up: "
        + ", ".join(str(p) for p in setup_peak)
        + "; whole run: " + ", ".join(str(p) for p in peak))

    # ---- correctness: the last iteration and the residual recurrence
    t = time.perf_counter()
    sample = chk.sample_angles(seed, angles,
                               cell.params["check"]["angles_per_dominance"],
                               n_shards=len(devices))
    box = chk.sample_box(seed, g["n_voxel"], cell.params["check"]["box"])
    with annotate("chipbench.check"):
        c = chk.Check(before, after, b, angles, rgeo, sample, box)
        del before, after, b
        numbers = c.program_numbers()
        if on_numbers:
            on_numbers("program", numbers)
        for prec in controls:
            on_numbers("control." + prec, c.control_numbers(prec))
        del c
    limits = cell.params["limits"]
    correct = chk.verdict(numbers, limits)
    log(f"check: angles {sample.tolist()}, box {box}, "
        f"{time.perf_counter() - t:.1f} s")

    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": int(max(peak) if peak else 0)}
    metrics: Dict[str, dict] = {}
    breakdown = None
    if not trace:
        values = {"iter_s": elapsed / n_iter, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        from . import trace as tr
        from .metric_context import MetricContext
        kernels = kernel_table()
        red = tr.reduce_trace(
            tr.load(profiler),
            {k: getattr(m, "TRACE_NAMES", ()) for k, m in kernels.items()})
        ctx = MetricContext(red, n_iter, rgeo, angles, len(devices),
                            dev0.device_kind, kernels, cfg)
        for m in cell.per_layer:
            reader = load_module(os.path.join(BENCH, "metrics",
                                              m["name"] + ".py"),
                                 "chipbench_metric_" + m["name"])
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        n = max(1, len(red.devices))
        device["busy_s"] = sum(d.busy_ns for d in red.devices.values()) \
            / n * 1e-9
        device["window_s"] = red.window_ns * 1e-9
        breakdown = tr.breakdown(red)
        for note in ctx.notes:
            log(note)
        if not keep_trace:
            import shutil
            shutil.rmtree(profiler, ignore_errors=True)
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    return Result(correct, n_iter, 0 if correct else n_iter, metrics,
                  device, checks, breakdown)
