"""One benchmark run: set up a cell, time its window, check it, report.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix, ``configs/<config>.json`` holds the
deployment (geometry, angles, execution ``mode``, precision),
``traffic/<traffic>.json`` the job mix (its ``algorithm``),
``cells/<cell>.json`` the check's sample sizes and limits,
``targets/<mode>.py`` how the window drives the program in that mode,
``algorithms/<algorithm>.py`` what the harness knows of an algorithm (the
state to wait on, the warm pass, the host copy and the check),
``metrics/<metric>.py`` one reader per per-layer metric and
``counts/<kernel>.py`` one work count per kernel.

Set-up is data, operator build, compilation, the algorithm's ``init`` and
one warm pass of the update arithmetic; the window then runs whole
iterations back to back and stops at the first boundary at or after the
requested seconds (:func:`run_window`).
"""

from __future__ import annotations

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


def _names(kind: str) -> List[str]:
    d = os.path.join(BENCH, kind)
    return sorted(f[:-3] for f in os.listdir(d) if f.endswith(".py"))


_MODULES: Dict[str, object] = {}


def _module(kind: str, name: str):
    """``<kind>/<name>.py`` (``targets`` or ``algorithms``), loaded once."""
    if name not in _names(kind):
        raise KeyError(f"no {kind}/{name}.py (have {_names(kind)})")
    key = f"{kind}/{name}"
    if key not in _MODULES:
        _MODULES[key] = load_module(os.path.join(BENCH, kind, name + ".py"),
                                    f"chipbench_{kind}_{name}")
    return _MODULES[key]


def target(mode: str):
    """The execution mode ``mode``: ``targets/<mode>.py``, whose
    ``build(geo, angles, vol, cfg)`` returns the :class:`Target`."""
    return _module("targets", mode)


def algorithm(name: str):
    """What the harness needs to know of one algorithm:
    ``algorithms/<name>.py`` (see ``algorithms/cgls.py``)."""
    return _module("algorithms", name)


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
    target(config["mode"])
    algorithm(traffic["algorithm"])
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
    """The system under test as the window drives it: one step, the
    current algorithm state, and the devices it runs on."""
    step: Callable[[], None]
    state: Callable[[], object]
    b: object
    kernel_config: dict
    release: Callable[[], None]
    devices: list


class EchoOperator:
    """Stands in for the operator in a warm pass of the update arithmetic:
    ``fp(angles)`` and ``bp(angles)`` return arrays of the real operators'
    shapes and placements, so each element-wise op of a step compiles
    before the window."""

    def __init__(self, fp, bp):
        self._fp, self._bp = fp, bp

    def A(self, vol, angles=None):
        return self._fp(angles)

    def At(self, proj, angles=None, weight=None):
        return self._bp(angles)


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


def run_window(target: Target, seconds: float, snapshot):
    """Whole iterations back to back until ``seconds`` have passed.

    Before the iteration expected to close the window (the time so far
    plus the last iteration's reaches ``seconds``) the state is copied to
    the host for the check (``snapshot(state)``), with the clock and the
    ``chipbench.window`` span paused.  The window stops at the first iteration boundary at or
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
    algo = algorithm(cfg["algorithm"])
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
    tgt = target(cfg["mode"]).build(geo, angles, vol, cfg)
    del vol
    phase("build_project_init_warm", t)
    log("blocks " + json.dumps({k: v for k, v in tgt.kernel_config.items()
                                if "." in k}))

    devices = tgt.devices
    setup_peak = hbm_peaks(devices)
    profiler, keep_trace = None, trace_dir is not None
    if trace:
        profiler = trace_dir or tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(profiler)
    snap = counter.snapshot()
    setup_s = time.perf_counter() - t_start
    before, n_iter, elapsed = run_window(tgt, seconds, algo.snapshot)
    if profiler:
        jax.profiler.stop_trace()
    in_window = counter.since(snap)
    after = algo.current(tgt.state())
    peak = hbm_peaks(devices)
    b = tgt.b
    tgt.release()
    del tgt
    log(f"setup phases (s): {json.dumps(phases)}; setup_s {setup_s}")
    log(f"window: {n_iter} iterations in {elapsed} s")
    log(f"compile events inside the window: {json.dumps(in_window)}")
    log("peak HBM per device (bytes), set-up: "
        + ", ".join(str(p) for p in setup_peak)
        + "; whole run: " + ", ".join(str(p) for p in peak))

    # ---- correctness: the window's last iteration against the reference
    t = time.perf_counter()
    with annotate("chipbench.check"):
        c, sampled = algo.make_check(before, after, b, angles, rgeo, seed,
                                     cfg, cell.params["check"],
                                     len(devices))
        del before, after, b
        numbers = c.program_numbers()
        if on_numbers:
            on_numbers("program", numbers)
        for prec in controls:
            on_numbers("control." + prec, c.control_numbers(prec))
        del c
    limits = cell.params["limits"]
    correct = chk.verdict(numbers, limits)
    log(f"check: {sampled}, {time.perf_counter() - t:.1f} s")

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
