"""The tracing layer: span recording (nesting, cross-thread, ambient
context), the zero-cost disabled path, ring-buffer bounds, the Chrome
trace-event / Prometheus exporters, the fleet event taxonomy and its
ordering across a steal + drain, per-slab streaming spans, and the
phase-seconds plumbing through ServeMetrics / merge_metrics."""

import json
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.core import phantoms
from repro.core.geometry import ConeGeometry, circular_angles
from repro.core.operator import CTOperator
from repro.core.plan import plan as plan_execution
from repro.core.splitting import MemoryModel
from repro.obs.trace import _NULL, Tracer, chrome_trace
from repro.serve import (MultiPodScheduler, Pod, PodSpec, ReconJob,
                         Scheduler, ServeMetrics, merge_metrics)

GEO = ConeGeometry.nice(16)
ANGLES = circular_angles(12)
PROJ = phantoms.sphere_projection_analytic(GEO, ANGLES)

KIB = 1024


def _mem(kib, frac=1.0):
    return MemoryModel(device_bytes=kib * KIB, usable_fraction=frac)


def _job(alg="cgls", n_iter=2, **kw):
    return ReconJob(alg, GEO, ANGLES, PROJ, n_iter=n_iter, **kw)


@pytest.fixture
def tracer():
    """The process tracer, enabled and empty; restored disabled+empty."""
    t = obs.get_tracer()
    t.clear()
    t.enable()
    yield t
    t.disable()
    t.clear()


# --------------------------------------------------------------------------
# recorder semantics
# --------------------------------------------------------------------------

def test_span_nesting_records_both_with_attrs(tracer):
    with obs.span("outer", "compute", job="j1"):
        with obs.span("inner", "h2d", slab=3):
            pass
    spans = tracer.spans()
    assert [s.name for s in spans] == ["inner", "outer"]   # close order
    inner, outer = spans
    assert inner.cat == "h2d" and inner.attrs == {"slab": 3}
    assert outer.cat == "compute" and outer.attrs == {"job": "j1"}
    assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1   # true nesting
    assert all(s.duration >= 0 for s in spans)


def test_cross_thread_begin_end_attributed_to_opening_thread(tracer):
    h = obs.begin("init", "compile", job="j2")
    opener = threading.get_ident()

    def closer():
        obs.end(h, extra=1)

    t = threading.Thread(target=closer)
    t.start()
    t.join()
    (s,) = tracer.spans()
    assert s.thread == opener          # not the closing thread
    assert s.attrs == {"job": "j2", "extra": 1}


def test_abandoned_handle_records_nothing(tracer):
    obs.begin("never-closed", "compute")
    assert tracer.spans() == []
    assert tracer.phase_seconds() == {}


def test_clear_orphans_open_handles(tracer):
    h = obs.begin("stale", "compute")
    tracer.clear()
    obs.end(h)                          # generation mismatch: no-op
    assert tracer.spans() == []


def test_context_merges_ambient_attrs_and_explicit_wins(tracer):
    with obs.context(job="j3", pod="p0", device=1):
        with obs.span("work", "compute", device=7):
            pass
        obs.event("mark")
    with obs.span("outside", "compute"):
        pass
    work = tracer.spans(name="work")[0]
    assert work.attrs == {"job": "j3", "pod": "p0", "device": 7}
    (ev,) = tracer.events()
    assert ev.attrs == {"job": "j3", "pod": "p0", "device": 1}
    assert tracer.spans(name="outside")[0].attrs == {}   # ctx restored


def test_ring_buffer_bounds_and_counts_drops():
    t = Tracer(capacity=8, enabled=True)
    for i in range(20):
        with t.span(f"s{i}", "compute"):
            pass
    assert len(t.records()) == 8
    assert t.dropped() == 12
    # aggregate counters keep running past evictions
    assert sum(1 for _ in t.spans("compute")) == 8
    assert t.prometheus().count('repro_spans_total{cat="compute"} 20') == 1


def test_threaded_hammer_loses_nothing():
    t = Tracer(capacity=1 << 14, enabled=True)
    n_threads, per_thread = 8, 200

    def work(k):
        for i in range(per_thread):
            with t.span("w", "compute", thread=k, i=i):
                pass
            t.event("tick", thread=k)
            t.incr("hits")

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    total = n_threads * per_thread
    assert len(t.spans("compute")) == total
    assert len(t.events("tick")) == total
    assert t.counters()["hits"] == total
    assert t.dropped() == 0
    seqs = [r.seq for r in t.records()]
    assert len(set(seqs)) == len(seqs)              # unique, no torn writes


def test_phase_seconds_global_and_per_thread(tracer):
    def worker():
        with obs.span("w", "h2d"):
            time.sleep(0.01)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    with obs.span("m", "compute"):
        time.sleep(0.01)
    phases = tracer.phase_seconds()
    assert phases["h2d"] >= 0.01 and phases["compute"] >= 0.01
    # the calling thread's view excludes the worker's h2d time
    mine = tracer.thread_phase_seconds()
    assert "compute" in mine and "h2d" not in mine


# --------------------------------------------------------------------------
# disabled path: zero cost, shared no-ops
# --------------------------------------------------------------------------

def test_disabled_tracer_records_nothing_and_returns_singletons():
    t = obs.get_tracer()
    assert not t.enabled and not obs.enabled()
    assert obs.span("x", "h2d") is _NULL
    assert obs.context(job="j") is _NULL
    assert obs.begin("x") is None
    obs.end(None)
    obs.event("submit")
    obs.incr("c")
    with obs.span("y", "compute"):
        pass
    assert t.records() == []
    assert t.phase_seconds() == {}
    assert t.counters() == {}


def test_env_var_enables_at_construction(monkeypatch):
    monkeypatch.setenv("REPRO_TRACE", "1")
    assert Tracer().enabled
    monkeypatch.setenv("REPRO_TRACE", "0")
    assert not Tracer().enabled
    monkeypatch.delenv("REPRO_TRACE")
    assert not Tracer().enabled


# --------------------------------------------------------------------------
# exporters
# --------------------------------------------------------------------------

def test_chrome_trace_schema_tracks_and_rebase(tracer):
    with obs.context(pod="p0"):
        with obs.span("stage", "h2d", slab=0, device=0):
            pass
        with obs.span("fp_slab", "compute", slab=0, device=1):
            pass
    with obs.span("untracked", "compute"):       # no pod/device attrs
        pass
    obs.fleet_event("submit", job="j1", pod="p0")
    doc = tracer.chrome_trace()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    metas = [e for e in evs if e["ph"] == "M"]
    instants = [e for e in evs if e["ph"] == "i"]
    assert len(xs) == 3 and len(instants) == 1
    for e in xs:
        assert {"name", "cat", "ts", "dur", "pid", "tid", "args"} <= set(e)
        assert e["ts"] >= 0 and e["dur"] >= 0    # rebased to run start
    assert instants[0]["s"] == "t"
    # process per pod, thread track per device
    procs = {m["args"]["name"] for m in metas if m["name"] == "process_name"}
    tracks = {m["args"]["name"] for m in metas if m["name"] == "thread_name"}
    assert procs == {"p0", "proc"}
    assert {"device0", "device1"} <= tracks
    # the pod-attributed spans land on the pod's pid
    pod_pid = next(m["pid"] for m in metas
                   if m["name"] == "process_name"
                   and m["args"]["name"] == "p0")
    assert all(e["pid"] == pod_pid for e in xs if e["args"].get("device")
               is not None)
    json.dumps(doc)                              # serializable end to end


def test_chrome_trace_coerces_non_json_attrs(tracer):
    with obs.span("s", "compute", count=np.int64(3), arr=np.float32(1.5),
                  obj=object()):
        pass
    doc = chrome_trace(tracer.records())
    (x,) = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert x["args"]["count"] == 3 and x["args"]["arr"] == 1.5
    assert isinstance(x["args"]["obj"], str)
    json.dumps(doc)


def test_prometheus_text_format(tracer):
    with obs.span("s", "h2d"):
        pass
    obs.fleet_event("submit", job="j1")
    obs.incr("dispatch_hits", 3)
    text = tracer.prometheus()
    assert text.endswith("\n")
    assert 'repro_phase_seconds_total{phase="h2d"} ' in text
    assert 'repro_spans_total{cat="h2d"} 1' in text
    assert 'repro_events_total{kind="submit"} 1' in text
    assert "repro_dispatch_hits_total 3" in text
    assert "repro_trace_dropped_records 0" in text
    for line in text.splitlines():
        assert line.startswith(("#", "repro_"))


def test_validate_trace_tool_accepts_real_trace(tracer, tmp_path):
    with obs.context(pod="p0", device=0):
        for cat in ("h2d", "compute", "d2h"):
            with obs.span(cat, cat, slab=0):
                pass
    path = str(tmp_path / "t.json")
    tracer.write_chrome_trace(path)
    proc = subprocess.run(
        [sys.executable, "tools/validate_trace.py", path,
         "--require-phases"],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "TRACE OK" in proc.stdout


def test_validate_trace_checks_prefetch_reduce_bytes(tracer, tmp_path):
    """The CommSchedule executors' prefetch/reduce spans are optional in
    a trace, but any that appear must be sized (the serving layer's
    bandwidth EMA is priced from their bytes args)."""
    with obs.context(pod="p0", device=0):
        for cat in ("h2d", "compute", "d2h"):
            with obs.span(cat, cat, slab=0):
                pass
        with obs.span("staging", "prefetch", slab=1, bytes=4096):
            pass
        with obs.span("reduce", "reduce", op="dist_fp", bytes=2048):
            pass
    path = str(tmp_path / "t.json")
    tracer.write_chrome_trace(path)
    proc = subprocess.run(
        [sys.executable, "tools/validate_trace.py", path,
         "--require-phases"],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "prefetch" in proc.stdout and "reduce" in proc.stdout

    # an unsized prefetch span is an instrumentation regression
    bad = {"traceEvents": [
        {"ph": "X", "name": "staging", "cat": "prefetch", "pid": 1,
         "tid": 1, "ts": 0.0, "dur": 1.0, "args": {"slab": 1}}]}
    bad_path = str(tmp_path / "bad.json")
    with open(bad_path, "w") as f:
        json.dump(bad, f)
    proc = subprocess.run(
        [sys.executable, "tools/validate_trace.py", bad_path],
        capture_output=True, text=True)
    assert proc.returncode == 1
    assert "bytes" in proc.stdout


# --------------------------------------------------------------------------
# fleet events
# --------------------------------------------------------------------------

def test_fleet_event_rejects_unknown_kind(tracer):
    with pytest.raises(ValueError, match="unknown fleet event"):
        obs.fleet_event("reboot", pod="p0")
    obs.fleet_event("submit", job="j1", pod="p0")   # known kinds fine
    assert [e.name for e in obs.fleet_event_log()] == ["submit"]


def test_fleet_event_log_filters(tracer):
    obs.fleet_event("submit", job="a", pod="p0")
    obs.fleet_event("submit", job="b", pod="p1")
    obs.fleet_event("complete", job="a", pod="p0")
    assert len(obs.fleet_event_log(job="a")) == 2
    assert len(obs.fleet_event_log(kind="submit")) == 2
    assert [e.attrs["job"] for e in obs.fleet_event_log(pod="p1")] == ["b"]


def test_scheduler_emits_lifecycle_events_in_order(tracer):
    sched = Scheduler(n_devices=1, memory=_mem(220), name="solo")
    jid = sched.submit(_job(n_iter=2))
    sched.run()
    names = [e.name for e in obs.fleet_event_log(job=jid)]
    assert names[0] == "submit"
    assert names[-1] == "complete"
    for kind in ("place", "admit", "step"):
        assert kind in names
    # ordering: submit < place < admit < first step < complete
    idx = {k: names.index(k) for k in ("submit", "place", "admit", "step",
                                       "complete")}
    assert idx["submit"] < idx["place"] < idx["admit"] < idx["step"] \
        < idx["complete"]
    admit = obs.fleet_event_log(job=jid, kind="admit")[0]
    assert admit.attrs["pod"] == "solo"
    assert admit.attrs["measured_s"] > 0
    steps = obs.fleet_event_log(job=jid, kind="step")
    assert len(steps) == 2 and all(e.attrs["measured_s"] > 0
                                   for e in steps)


def test_fleet_event_order_across_steal_and_drain(tracer, tmp_path):
    """A stolen job's event trail reads submit -> export (victim) ->
    import (thief) -> ... -> complete, strictly ordered; the scale-down
    style drain leaves a drain event after the parks."""
    pods = [Pod(PodSpec(f"p{i}", n_devices=1, memory=_mem(800)))
            for i in range(2)]
    mps = MultiPodScheduler(pods, transfer_dir=str(tmp_path / "xfer"))
    jids = [mps.submit(_job(n_iter=2), pod="p0") for _ in range(3)]
    moved = mps.steal_pass()
    assert moved, "imbalanced fleet must steal"
    for jid in moved:
        names = [e.name for e in obs.fleet_event_log(job=jid)]
        assert "export" in names and "import" in names
        assert names.index("export") < names.index("import")
        exp = obs.fleet_event_log(job=jid, kind="export")[0]
        imp = obs.fleet_event_log(job=jid, kind="import")[0]
        assert exp.attrs["pod"] == "p0" and imp.attrs["pod"] == "p1"
        seqs = [e.seq for e in obs.fleet_event_log(job=jid)]
        assert seqs == sorted(seqs)
    mps.run()
    for jid in jids:
        assert obs.fleet_event_log(job=jid, kind="complete")
    # drain: park everything left queued on a fresh scheduler
    sched = Scheduler(n_devices=1, memory=_mem(800), name="drainee")
    sched.submit(_job(n_iter=8))
    sched.admit()
    sched.drain(None, timeout=30)
    drains = obs.fleet_event_log(kind="drain")
    assert drains and drains[-1].attrs["pod"] == "drainee"
    parks = obs.fleet_event_log(kind="park")
    assert parks and parks[-1].seq < drains[-1].seq


def test_autoscaler_scale_events_logged(tracer, tmp_path):
    from repro.serve import Autoscaler, AutoscalePolicy
    mps = MultiPodScheduler(
        [Pod(PodSpec("seed", n_devices=1, memory=_mem(220)))],
        transfer_dir=str(tmp_path / "xfer"))
    asc = Autoscaler(mps, [PodSpec("burst", n_devices=1, memory=_mem(220))],
                     AutoscalePolicy(scale_up_backlog_seconds=0.5,
                                     scale_down_backlog_seconds=0.05,
                                     down_window_seconds=0.0,
                                     cooldown_seconds=0.0))
    ev = asc._scale_up(0.0, 9.9)
    assert ev is not None
    (up,) = obs.fleet_event_log(kind="scale-up")
    assert up.attrs["pod"] == ev.pod and up.attrs["n_pods"] == 2
    adds = obs.fleet_event_log(kind="pod-add")
    assert adds and adds[-1].attrs["pod"] == ev.pod


# --------------------------------------------------------------------------
# streaming + executor instrumentation
# --------------------------------------------------------------------------

def test_streaming_emits_per_slab_phase_spans(tracer):
    geo = ConeGeometry.nice(16)
    angles = circular_angles(8)
    mem = _mem(24)                      # too small for 16^3 whole: splits
    p = plan_execution(geo, len(angles), 1, mem)
    assert p.forward.n_slabs >= 2, "budget must force a split"
    op = CTOperator(geo, angles, mode="stream", memory=mem)
    vol = np.asarray(phantoms.shepp_logan(geo))
    proj = np.asarray(op.A(vol))
    np.asarray(op.At(proj))
    fp = tracer.spans(name="fp_slab")
    assert {s.attrs["slab"] for s in fp} == set(range(p.forward.n_slabs))
    assert all(s.cat == "compute" and "device" in s.attrs for s in fp)
    h2d = tracer.spans("h2d")
    assert {s.attrs.get("op") for s in h2d} == {"fp", "bp"}
    assert tracer.spans("d2h")
    bp = [s for s in tracer.spans("compute") if s.attrs.get("op") == "bp"]
    assert bp and all("chunk" in s.attrs and "slab" in s.attrs for s in bp)


def test_executor_phase_seconds_cover_step_wall_time(tracer):
    from repro.serve.executor import JobExecutor
    ex = JobExecutor(_job(n_iter=3), mode="plain", memory=_mem(800),
                     labels={"pod": "p0", "device": 0})
    ex.start()
    ex.take_phase_seconds()
    ex.step()                           # burn in compile effects
    ex.take_phase_seconds()
    t0 = time.monotonic()
    ex.step()
    dt = time.monotonic() - t0
    phases = ex.take_phase_seconds()
    assert "compute" in phases
    total = sum(phases.values())
    # the step span wraps ~the whole step; allow scheduling noise
    assert 0.5 * dt <= total <= 1.05 * dt, (phases, dt)
    # spans carry the ambient identity
    step_spans = [s for s in tracer.spans(name="step")
                  if s.attrs.get("pod") == "p0"]
    assert step_spans and all(s.attrs["device"] == 0 for s in step_spans)


def test_executor_kernel_config_event_carries_z_chunks_per_tile(tracer):
    """The job's kernel-config event reports the ray kernels' mean z
    chunks per tile for the job's own geometry and angles."""
    from repro.kernels.fp_ray import z_chunks_per_tile
    from repro.serve.executor import JobExecutor
    ex = JobExecutor(_job(backend="pallas"), mode="plain", memory=_mem(800))
    ex.start()
    (ev,) = tracer.events("kernel-config")
    assert ev.attrs["z_chunks_per_tile"] == z_chunks_per_tile(GEO, ANGLES)
    assert ev.attrs["z_chunks_per_tile"] > 0


@pytest.mark.parametrize("mode,kib", [("plain", 800), ("stream", 24)])
def test_executor_step_spans_its_wait_for_the_state(tracer, mode, kib):
    """The executor's own wait for a step's state opens a ``sync`` layer
    span after the algorithm's step (inside the ``step`` span in plain
    mode), so a profiler trace can tell it from dispatch."""
    from repro.serve.executor import JobExecutor
    ex = JobExecutor(_job(n_iter=2), mode=mode, memory=_mem(kib))
    ex.start()
    tracer.clear()
    ex.step()
    spans = tracer.spans()
    sync = [s for s in spans if s.name == "sync"]
    alg = [s for s in spans if s.name == "alg.step"]
    assert len(sync) == 1 and len(alg) == 1 and sync[0].cat == obs.LAYER
    assert alg[0].t1 <= sync[0].t0
    step = [s for s in spans if s.name == "step"]
    assert len(step) == (mode == "plain")
    for outer in step:
        assert outer.t0 <= sync[0].t0 <= sync[0].t1 <= outer.t1
    assert "sync" not in tracer.phase_seconds()


def test_summary_reports_phase_seconds_and_disabled_is_empty(tracer):
    sched = Scheduler(n_devices=1, memory=_mem(800), name="s0")
    sched.submit(_job(n_iter=2))
    sched.run()
    s = sched.summary()
    assert s["phase_seconds"].get("compute", 0) > 0
    # phase attribution is within 10% of the measured step wall time
    # (plus init, which is attributed separately)
    busy = s["busy_seconds"]
    attributed = sum(v for k, v in s["phase_seconds"].items()
                     if k != "init")
    assert attributed <= 1.1 * (busy + s["phase_seconds"].get("init", 0))
    # disabled tracer -> empty phase dict (the zero-overhead default)
    obs.get_tracer().disable()
    sched2 = Scheduler(n_devices=1, memory=_mem(800))
    sched2.submit(_job(n_iter=1))
    sched2.run()
    assert sched2.summary()["phase_seconds"] == {}


def test_merge_metrics_phase_round_trip():
    a = ServeMetrics(phase_seconds={"h2d": 1.0, "compute": 2.0})
    b = ServeMetrics(phase_seconds={"compute": 3.0, "d2h": 0.5})
    m = merge_metrics([a, b])
    assert m.phase_seconds == {"h2d": 1.0, "compute": 5.0, "d2h": 0.5}
    assert m.summary()["phase_seconds"] == m.phase_seconds
    # and the round trip leaves the parts untouched
    assert a.phase_seconds == {"h2d": 1.0, "compute": 2.0}


def test_dispatch_counters_hit_and_miss(tracer):
    from repro.core.backend import get_backend
    tracer.clear()
    bk = get_backend("ref")
    geo = ConeGeometry.nice(16)
    bk.fp(geo, xdom=True)
    before = tracer.counters()
    bk.fp(geo, xdom=True)               # same key: a hit
    after = tracer.counters()
    assert after.get("dispatch_hits", 0) \
        == before.get("dispatch_hits", 0) + 1
    assert after.get("dispatch_misses", 0) \
        == before.get("dispatch_misses", 0)


# --------------------------------------------------------------------------
# profiler sink: spans on the device trace's clock, never a host sync
# --------------------------------------------------------------------------

def _profiled(tmp_path, body):
    """Run ``body`` under a CPU ``jax.profiler`` trace; returns the host
    events named ``repro.*`` as ``(name, start_ns, end_ns)``, by start."""
    import glob
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                             / "*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return sorted(out, key=lambda e: e[1])


def test_profiler_sink_writes_nested_spans(tracer, tmp_path):
    def body():
        tracer.enable(profiler=True)
        with obs.span("outer", "compute", job="j1"):
            with obs.span("inner", "h2d", slab=3):
                time.sleep(0.002)
            h = obs.begin("handle", "compute", device=0)
            obs.end(h)
        tracer.disable()

    events = _profiled(tmp_path, body)
    assert [e[0] for e in events] == ["repro.outer", "repro.inner",
                                      "repro.handle"]
    outer, inner, handle = events
    for child in (inner, handle):       # nested as they were opened
        assert outer[1] <= child[1] and child[2] <= outer[2]
    assert inner[2] <= handle[1]
    # attrs stay in the ring buffer, out of the trace event names
    assert tracer.spans(name="inner")[0].attrs == {"slab": 3}
    assert not tracer.profiler


def test_profiler_sink_off_writes_nothing(tracer, tmp_path):
    def body():
        with obs.span("ring-only", "compute"):   # enabled, no sink
            pass
        tracer.disable()
        with obs.span("off", "compute"):
            pass
        obs.end(obs.begin("off-handle"))

    assert _profiled(tmp_path, body) == []
    assert [s.name for s in tracer.spans()] == ["ring-only"]


def test_layer_spans_stay_out_of_phase_seconds(tracer):
    with obs.span("step", "compute"):
        with obs.span("op.A", obs.LAYER):
            pass
    assert set(tracer.phase_seconds()) == {"compute"}
    assert set(tracer.thread_phase_seconds()) == {"compute"}
    assert [s.name for s in tracer.spans(obs.LAYER)] == ["op.A"]


class _SyncCounter:
    """Counts the calls by which host code waits for a device array:
    blocking on it, fetching it, or reading it as a Python value."""

    def __init__(self, monkeypatch):
        import collections
        import jax
        from jax._src.array import ArrayImpl
        self.counts = collections.Counter()
        for name in ("block_until_ready", "__array__", "__float__",
                     "__int__", "__bool__", "item", "tolist"):
            monkeypatch.setattr(ArrayImpl, name,
                                self._counted(name, getattr(ArrayImpl, name)))
        for name in ("block_until_ready", "device_get"):
            monkeypatch.setattr(jax, name,
                                self._counted(name, getattr(jax, name)))

    def _counted(self, name, fn):
        def counted(*args, **kw):
            self.counts[name] += 1
            return fn(*args, **kw)
        return counted

    def during(self, fn):
        self.counts.clear()
        out = fn()
        return out, dict(self.counts)


@pytest.mark.parametrize("mode", ["plain", "dist"])
def test_tracing_adds_no_host_sync_to_a_cgls_step(tracer, monkeypatch, mode):
    """A CGLS step on the Pallas operators (interpret mode), the path the
    chip runs, waits for the device as often with the tracer and its
    profiler sink on as with them off; and none of its spans blocks."""
    import dataclasses
    import jax
    from jax.sharding import AxisType, Mesh
    from repro.core.algorithms.stepwise import get_algorithm
    mesh = None
    if mode == "dist":
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4, 1),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    op = CTOperator(GEO, ANGLES, mode=mode, mesh=mesh, backend="pallas")
    alg = get_algorithm("cgls")
    tracer.disable()
    st = alg.init(PROJ, GEO, ANGLES, op=op)
    jax.block_until_ready(alg.step(dataclasses.replace(st)).x)   # compile
    sync = _SyncCounter(monkeypatch)

    def step():
        return alg.step(dataclasses.replace(st))

    off, counts_off = sync.during(step)
    tracer.enable(profiler=True)
    on, counts_on = sync.during(step)
    tracer.disable()
    assert counts_on == counts_off
    for name in ("block_until_ready", "device_get", "__float__"):
        assert counts_on.get(name, 0) == 0, counts_on
    names = {s.name for s in tracer.spans()}
    assert {"alg.step", "op.A", "op.At"} <= names
    if mode == "dist":
        assert {"op.dist.group", "reduce"} <= names
    np.testing.assert_array_equal(np.asarray(on.x), np.asarray(off.x))


def _op_names(fn, *args):
    import re
    import jax
    hlo = jax.jit(fn).lower(*args).compiler_ir("hlo").as_hlo_module()
    return re.findall(r'op_name="([^"]*)"', hlo.to_string())


@pytest.mark.parametrize("mode", ["plain", "dist"])
def test_operator_bodies_carry_their_scope_in_hlo(mode):
    """The jitted FP and matched-BP bodies put their ops under
    ``repro.op.fp`` / ``repro.op.bp``: the name reaches the device
    trace's ``tf_op`` stat, which splits the operators' glue from the
    algorithm's eager updates."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, Mesh
    mesh = None
    if mode == "dist":
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4, 1),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    op = CTOperator(GEO, ANGLES, mode=mode, mesh=mesh, backend="pallas")
    vol = jnp.zeros(GEO.n_voxel, jnp.float32)
    if mode == "plain":
        proj = jnp.zeros((len(ANGLES),) + tuple(GEO.n_detector), jnp.float32)
        fp = _op_names(op.A, vol)
        bp = _op_names(lambda p: op.At(p, weight="matched"), proj)
    else:
        # one dominance group's sharded call: the dist operators' host
        # code regroups concrete angles, so it is not traceable whole
        angles = jnp.zeros(8, jnp.float32)
        proj = jnp.zeros((8,) + tuple(GEO.n_detector), jnp.float32)
        fp = _op_names(op._a.sharded(True), vol, angles)
        bp = _op_names(op._at_matched.sharded(True), proj, angles)
    assert any("repro.op.fp" in n for n in fp)
    assert any("repro.op.bp" in n for n in bp)
    assert not any("repro.op.bp" in n for n in fp)
    assert not any("repro.op.fp" in n for n in bp)


def test_profiler_sink_closes_a_span_ended_after_disable(tracer, tmp_path):
    def body():
        tracer.enable(profiler=True)
        h = obs.begin("crossing", "compute")
        tracer.disable()
        obs.end(h)                      # not recorded, but closed

    (ev,) = _profiled(tmp_path, body)
    assert ev[0] == "repro.crossing"
    assert tracer.spans() == []
