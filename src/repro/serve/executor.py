"""Step-wise job executor: one placed job's operator + algorithm state.

The executor owns what the scheduler placed on a device: it builds the
:class:`~repro.core.operator.CTOperator` for the backend the placement
chose ("plain" for resident jobs packed next to other tenants, "stream"
for jobs routed through the paper's out-of-core path), instantiates the
algorithm's resumable state from the step-wise registry, and advances it
one outer iteration per call.  Between any two calls the scheduler may
checkpoint the executor (preemption) and later rebuild it from the
checkpoint — results are bit-identical to an uninterrupted run because
``init`` is deterministic and the checkpoint carries every recurrence
variable.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Any, Dict, Optional, Sequence

import jax
import numpy as np

from .. import obs
from ..core.algorithms.stepwise import (checkpoint_state, get_algorithm,
                                        restore_state)
from ..core.operator import CTOperator
from ..core.plan import plan as plan_execution
from ..core.splitting import MemoryModel
from .job import ReconJob

# Operator cache shared across jobs: tenants with the same acquisition
# (geometry + angles + backend + weighting + budget + device) reuse one
# CTOperator and therefore its jit-compiled kernels -- the dominant cost
# of admitting a job.  Bounded LRU so a long-lived scheduler serving many
# distinct geometries cannot grow without limit.
_OP_CACHE_MAX = 32
_op_cache: "OrderedDict[tuple, CTOperator]" = OrderedDict()
_op_cache_lock = threading.Lock()   # admission may run in several schedulers


def clear_operator_cache() -> None:
    """Drop all cached operators (frees their compiled executables)."""
    with _op_cache_lock:
        _op_cache.clear()


def _get_operator(geo, angles: np.ndarray, mode: str, bp_weight: str,
                  memory: MemoryModel, devices: Optional[Sequence],
                  backend: Optional[str] = None) -> CTOperator:
    from repro.core.backend import resolve
    from repro.kernels import autotune
    backend = resolve(backend)     # "auto"/None and its target share a key
    # autotune.fingerprint(): a retuned/reloaded block table must not
    # reuse operators compiled under the previous block config
    key = (geo, angles.tobytes(), mode, bp_weight, backend,
           memory.device_bytes, memory.usable_fraction,
           tuple(getattr(d, "id", id(d)) for d in devices or ()),
           autotune.fingerprint())
    with _op_cache_lock:
        op = _op_cache.get(key)
        if op is not None:
            _op_cache.move_to_end(key)
            return op
    op = CTOperator(geo, angles, mode=mode, bp_weight=bp_weight,
                    memory=memory, devices=devices, backend=backend)
    with _op_cache_lock:
        _op_cache[key] = op
        if len(_op_cache) > _OP_CACHE_MAX:
            _op_cache.popitem(last=False)
    return op


def prewarm_jobs(jobs: Sequence[ReconJob], memory: MemoryModel,
                 devices: Optional[Sequence] = None) -> int:
    """Warm the shared operator cache for ``jobs`` ahead of admission.

    Builds (or touches) each job's :class:`CTOperator` under the same
    cache key admission will use — mode mirrors the scheduler's
    ``stream-if-it-splits`` decision, weighting the algorithm's default —
    so the first admitted job on a freshly scaled-up pod skips the
    operator build/JIT stall.  Deduplicates by key, never raises (a job
    whose geometry cannot build fails admission later, with the error
    attributed to that job); returns the number of operators warmed.
    """
    from .scheduler import estimate_job_footprint
    warmed = 0
    seen = set()
    for job in jobs:
        try:
            alg = get_algorithm(job.algorithm)
            fp = estimate_job_footprint(job, memory)
            mode = "stream" if fp.streams else "plain"
            dedup = (job.geo, job.angles.tobytes(), mode,
                     alg.default_bp_weight, job.backend)
            if dedup in seen:
                continue
            seen.add(dedup)
            op = _get_operator(job.geo, job.angles, mode,
                               alg.default_bp_weight, memory, devices,
                               backend=job.backend)
            op.warmup()
            warmed += 1
        except Exception:
            continue
    return warmed


def operator_cache_keys() -> tuple:
    """Current operator-cache keys (regression tests assert pre-warm)."""
    with _op_cache_lock:
        return tuple(_op_cache)


def _block_on_state(state) -> None:
    """Wait for every device array reachable from ``state`` to finish.

    JAX dispatch is asynchronous: ``alg.step`` returns as soon as the work
    is *enqueued*, so any wall-clock measurement taken around it would time
    the enqueue, not the compute.  Blocking on the state's arrays makes the
    step boundary a real synchronisation point — step timings, per-device
    busy clocks, and the modeled makespan all depend on it.
    """
    for leaf in jax.tree_util.tree_leaves(vars(state)):
        block = getattr(leaf, "block_until_ready", None)
        if block is not None:
            block()


class JobExecutor:
    """Runs one :class:`ReconJob` step by step on its assigned backend."""

    def __init__(self, job: ReconJob, mode: str,
                 memory: Optional[MemoryModel] = None,
                 devices: Optional[Sequence] = None,
                 labels: Optional[Dict[str, Any]] = None):
        self.job = job
        self.alg = get_algorithm(job.algorithm)
        self.mode = mode
        self.memory = memory or MemoryModel()
        self.devices = devices
        # ambient trace identity (pod name, device slot) merged into every
        # span this executor's work opens — streaming-loop spans inherit
        # it without new plumbing through the operator call signatures
        self.labels = {k: v for k, v in (labels or {}).items()
                       if v is not None}
        self._state = None
        self.init_seconds = 0.0
        # span-category seconds from the most recent start()/step(),
        # drained by the scheduler into ServeMetrics.phase_seconds
        self._phase_delta: Dict[str, float] = {}

    def take_phase_seconds(self) -> Dict[str, float]:
        out, self._phase_delta = self._phase_delta, {}
        return out

    @property
    def step_transfer_bytes(self) -> int:
        """Schedule-modeled host<->device bytes one outer iteration of a
        *streamed* job moves (0 for in-core jobs — their operands stay
        resident).  Read off the plan's CommSchedule, so chunk reuse is
        reflected; the scheduler divides the step's observed staging
        phase seconds into this to feed its measured-bandwidth EMA."""
        if self.mode != "stream":
            return 0
        try:
            p = plan_execution(self.job.geo, len(self.job.angles), 1,
                               self.memory)
        except Exception:
            return 0
        return p.comm.bytes_moved()

    @staticmethod
    def _phase_diff(after: Dict[str, float],
                    before: Dict[str, float]) -> Dict[str, float]:
        return {k: v - before.get(k, 0.0) for k, v in after.items()
                if v - before.get(k, 0.0) > 0.0}

    @property
    def total_steps(self) -> int:
        return max(1, self.job.n_iter) if self.alg.iterative else 1

    @property
    def iterations_done(self) -> int:
        return 0 if self._state is None else int(self._state.it)

    @property
    def started(self) -> bool:
        return self._state is not None

    @property
    def done(self) -> bool:
        return self.started and self.iterations_done >= self.total_steps

    def start(self, checkpoint: Optional[Dict[str, Any]] = None) -> None:
        """Resolve data, build the operator, init (or restore) the state."""
        tracer = obs.get_tracer()
        before = (tracer.thread_phase_seconds() if tracer.enabled else None)
        t0 = time.monotonic()
        with obs.context(job=self.job.job_id, **self.labels), \
                obs.span("init", "init", alg=self.job.algorithm,
                         mode=self.mode):
            proj = self.job.resolve_projections()
            op = _get_operator(self.job.geo, self.job.angles, self.mode,
                               self.alg.default_bp_weight, self.memory,
                               self.devices, backend=self.job.backend)
            kcfg = op.kernel_config()
            if kcfg:
                # calibration attrs: which (possibly autotuned) block
                # config this job's kernels compiled under
                obs.event("kernel-config", backend=op.backend_name, **kcfg)
            params = dict(self.job.params)
            if checkpoint is not None:
                # feed checkpointed scalars back through init so restore
                # does not recompute them (e.g. FISTA's power-iteration L)
                for k in self.alg.resume_params:
                    if k in checkpoint:
                        params[k] = checkpoint[k]
            state = self.alg.init(proj, self.job.geo, self.job.angles,
                                  op=op, **params)
            if checkpoint is not None:
                state = restore_state(self.alg, state, checkpoint)
            _block_on_state(state)
        self._state = state
        self.init_seconds = time.monotonic() - t0
        if before is not None:
            self._phase_delta = self._phase_diff(
                tracer.thread_phase_seconds(), before)

    def step(self) -> int:
        """Advance one outer iteration; returns iterations done so far.

        Blocks until the iteration's compute has actually finished (not
        just been dispatched), so the caller's ``dt`` around this call is
        honest compute time."""
        if self._state is None:
            raise RuntimeError(f"{self.job.job_id}: step() before start()")
        tracer = obs.get_tracer()
        if not tracer.enabled:
            self._state = self.alg.step(self._state)
            _block_on_state(self._state)
            return self.iterations_done
        # Trace path: ambient job/pod/device context tags every span the
        # operators open underneath.  Streamed jobs emit their own
        # h2d/compute/d2h leaf spans; plain (in-core) steps are wrapped in
        # one compute span so phase attribution covers them too.
        before = tracer.thread_phase_seconds()
        with obs.context(job=self.job.job_id, **self.labels):
            if self.mode == "plain":
                with obs.span("step", "compute", alg=self.job.algorithm,
                              it=self.iterations_done):
                    self._state = self.alg.step(self._state)
                    with obs.span("sync", obs.LAYER):
                        _block_on_state(self._state)
            else:
                self._state = self.alg.step(self._state)
                with obs.span("sync", obs.LAYER):
                    _block_on_state(self._state)
        self._phase_delta = self._phase_diff(
            tracer.thread_phase_seconds(), before)
        return self.iterations_done

    def checkpoint(self) -> Dict[str, Any]:
        """Host-side snapshot of the resumable state (for preemption)."""
        if self._state is None:
            raise RuntimeError(f"{self.job.job_id}: no state to checkpoint")
        return checkpoint_state(self.alg, self._state)

    def result(self) -> np.ndarray:
        return np.asarray(self.alg.finalize(self._state))

    def release(self) -> None:
        """Drop the state so device buffers can be reclaimed."""
        self._state = None
