"""The program's spans and operator scopes in a trace (``lib/spans.py``).

On a synthetic trace: gap naming by the innermost program span, and the
readings (``glue_s``, ``dispatch_idle_s``, ``sync_idle_s``, ``host.op_s``,
``host.op_idle_s``).
On a CPU profiler trace: loading the ``repro.*`` host events.  On the
committed TPU traces: ``data/small.xplane.pb`` (no program spans) still
gives the five accepted per-layer metrics the values they had before the
program wrote spans, and reads nothing for the new numbers;
``data/small_spans.xplane.pb`` (the ``cbct512.cgls`` cell cut to 64^3 and
64 angles, recorded with ``tests/record_spans.py`` on one TPU v5e chip and
cut with its ``--cut``) names its gaps by program spans and reads glue.
"""

import os

import pytest

import tiny
from chipbench.lib import harness, reference as ref, spans, trace
from chipbench.lib.metric_context import MetricContext

DATA = os.path.join(tiny.TESTS, "data")
SMALL = os.path.join(DATA, "small.xplane.pb")
SMALL_SPANS = os.path.join(DATA, "small_spans.xplane.pb")
KERNELS = {"fp_ray": ("repro/kernels/fp_ray.py:",),
           "bp_matched": ("repro/kernels/bp_matched.py:",)}


def _op(name, s, e, tf_op="", source=""):
    text = " ".join([name, f"source={source}", f"tf_op={tf_op}"])
    full = f"%{name} = tpu_custom_call" if source else f"%{name} = op"
    return trace.Event(full, s, e, text, name)


def _synthetic():
    """Two devices and a window in two parts (the snapshot between them):

        window     [0, 600] [650, 1000]
        alg.step   [50, 940]  op.A [100, 300] > op.dist.group [150, 250]
                              op.At [500, 720] > op.A [520, 560]
                              reduce [800, 850]
        sync       [940, 980]
        device 0   fp kernel [300, 450] rev [450, 470] (repro.op.fp)
                   mul [470, 480] (eager)  bp kernel [700, 790]
                   psum [790, 800] copy [860, 900] (repro.op.bp)
        device 1   add [0, 990] (eager)
    """
    host = [trace.Event("chipbench.window", 0, 600),
            trace.Event("chipbench.window", 650, 1000),
            trace.Event("chipbench.step", 0, 1000)]
    program = [trace.Event(n, s, e) for n, s, e in (
        ("repro.alg.step", 50, 940), ("repro.op.A", 100, 300),
        ("repro.op.dist.group", 150, 250), ("repro.op.At", 500, 720),
        ("repro.op.A", 520, 560), ("repro.reduce", 800, 850),
        ("repro.sync", 940, 980))]
    fp_scope = "jit(f)/repro.op.fp/jit(f)"
    bp_scope = "jit(f)/repro.op.bp/jit(f)"
    dev0 = [_op("f.2", 300, 450, fp_scope + "/pallas_call",
                "/x/src/repro/kernels/fp_ray.py:346"),
            _op("rev.2", 450, 470, fp_scope + "/jit(_flip)/rev"),
            _op("mul.1", 470, 480, "jit(multiply)/mul"),
            _op("f.3", 700, 790, bp_scope + "/pallas_call",
                "/x/src/repro/kernels/bp_matched.py:129"),
            _op("psum.7", 790, 800, bp_scope + "/psum"),
            _op("copy.1", 860, 900, bp_scope + "/copy")]
    dev1 = [_op("add.2", 0, 990, "jit(add)/add")]
    return trace.Trace(host, {0: dev0, 1: dev1}), program


def _readings(monkeypatch, tr, program, n_iter=1):
    monkeypatch.setattr(trace, "load", lambda path: tr)
    monkeypatch.setattr(spans, "load_program", lambda path: program)
    return spans.readings("synthetic", KERNELS, n_iter)


def test_gaps_named_by_the_innermost_program_span(monkeypatch):
    tr, program = _synthetic()
    red = trace.reduce_trace(tr, KERNELS)
    prog = spans.ProgramSpans(program)
    named = {(round(s), round(e)): prog.name_at(red, (s + e) / 2)
             for s, e in red.devices[0].gaps}
    assert named == {(0, 300): "repro.op.dist.group",
                     (480, 600): "repro.op.A",      # inside op.At
                     (650, 700): "repro.op.At",
                     (800, 860): "repro.reduce",
                     (900, 1000): "repro.sync"}
    gaps = _readings(monkeypatch, tr, program)["idle_gaps"]
    assert gaps[0][0] == "repro.op.dist.group (device 0)"
    assert gaps[0][1] == pytest.approx(300e-9)
    # [990, 1000]: no program span
    assert "chipbench.step (device 1)" in [name for name, _ in gaps]


def test_the_readings(monkeypatch):
    tr, program = _synthetic()
    r = _readings(monkeypatch, tr, program, n_iter=2)
    # rev (repro.op.fp) + copy (repro.op.bp): the kernels, the psum and
    # the eager mul are not glue; mean over the two devices
    assert r["glue_s"] == pytest.approx((20 + 40) / 2 / 2 * 1e-9)
    assert r["glue_s_by_scope"] == pytest.approx(
        {"repro.op.fp": 5e-9, "repro.op.bp": 10e-9})
    # device 0's gaps whose middle is in a program span other than sync:
    # 300 + 120 + 50 + 60; the last gap, [900, 1000], is sync's
    assert r["dispatch_idle_s"] == pytest.approx(530 / 2 * 1e-9)
    assert r["sync_idle_s"] == pytest.approx(100 / 2 * 1e-9)
    # op.A [100, 300] and op.At [500, 720] clipped to the window (the
    # nested op.A counts once): 200 + 100 + 70; device 0 runs the bp
    # kernel from 700 on, so 20 of it is a wait on a busy device
    assert r["host.op_s"] == pytest.approx((200 + 100 + 70) / 2 * 1e-9)
    assert r["host.op_idle_s"] == pytest.approx((200 + 100 + 50) / 2
                                                * 1e-9)
    assert r["glue_s"] <= r["xla_ops_s"]
    assert (r["dispatch_idle_s"] + r["sync_idle_s"]
            <= r["worst_idle_s"] + 1e-18)


def test_nothing_to_read_without_spans_or_scopes(monkeypatch):
    tr, _ = _synthetic()
    for evs in tr.devices.values():
        for ev in evs:
            ev.text = ev.text.split(" tf_op=")[0]
    r = _readings(monkeypatch, tr, [])
    assert r["glue_s"] is None and r["glue_s_by_scope"] == {}
    assert r["dispatch_idle_s"] is None and r["sync_idle_s"] is None
    assert r["host.op_s"] is None and r["host.op_idle_s"] is None
    assert r["idle_gaps"][0][0] == "chipbench.step (device 0)"


def test_program_spans_load_from_a_profiler_trace(tmp_path):
    """A CPU profiler trace: the ``repro.*`` host events, any ``#...``
    metadata suffix stripped, in start order; harness spans apart."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        with jax.profiler.TraceAnnotation("repro.alg.step"):
            with jax.profiler.TraceAnnotation("repro.op.A#job=j1#"):
                pass
    jax.profiler.stop_trace()
    prog = spans.load_program(str(tmp_path))
    assert [ev.name for ev in prog] == ["repro.alg.step", "repro.op.A"]
    outer, inner = prog
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert [h.name for h in trace.load(str(tmp_path)).host] \
        == ["chipbench.window"]


def _metrics(path):
    red = trace.reduce_trace(trace.load(path), {
        k: m.TRACE_NAMES for k, m in harness.kernel_table().items()})
    steps = sum(1 for h in red.host if h.name == "chipbench.step")
    out = {}
    for chips, cell_name in ((1, "cbct512.cgls"), (4, "cbct512-x4.cgls")):
        cell = tiny.tiny_cell(cell_name, 32, 32)
        cfg = dict(cell.config, **cell.traffic)
        ctx = MetricContext(red, steps, ref.Geometry.from_config(cfg),
                            ref.scan_angles(32), chips, "TPU v5 lite",
                            harness.kernel_table(), cfg)
        for m in ("roofline.fp_ray", "roofline.bp_matched", "xla_ops_s",
                  "device.idle_share", "collective_s"):
            reader = harness.load_module(
                os.path.join(tiny.BENCH, "metrics", m + ".py"), "m_" + m)
            out[(chips, m)] = reader.read(ctx)
    return out


def test_accepted_metrics_unmoved_on_the_committed_trace():
    """The values the five accepted per-layer metrics gave on
    ``small.xplane.pb`` before the program wrote spans (32^3, 32 angles,
    59 iterations, read as on one chip and as on four)."""
    assert _metrics(SMALL) == {
        (1, "roofline.fp_ray"): 0.030586143059735033,
        (1, "roofline.bp_matched"): 0.028538141902933425,
        (1, "xla_ops_s"): 4.058120369492307e-05,
        (1, "device.idle_share"): 56.62021634375447,
        (1, "collective_s"): None,
        (4, "roofline.fp_ray"): 0.019116339412334395,
        (4, "roofline.bp_matched"): 0.01783633868933339,
        (4, "xla_ops_s"): 4.058120369492307e-05,
        (4, "device.idle_share"): 56.62021634375447,
        (4, "collective_s"): 0.0,
    }
    kernels = {k: m.TRACE_NAMES for k, m in harness.kernel_table().items()}
    r = spans.readings(SMALL, kernels, 59)
    assert r["glue_s"] is None and r["dispatch_idle_s"] is None
    assert r["sync_idle_s"] is None
    assert r["host.op_s"] is None and r["host.op_idle_s"] is None
    assert all(g[0].startswith("chipbench.") for g in r["idle_gaps"])


def test_cut_keeps_what_the_reductions_read(tmp_path):
    import record_spans
    dst = str(tmp_path / "cut.xplane.pb")
    record_spans.cut(SMALL, dst)
    assert os.path.getsize(dst) <= os.path.getsize(SMALL)
    kernels = {k: m.TRACE_NAMES for k, m in harness.kernel_table().items()}
    a = trace.reduce_trace(trace.load(SMALL), kernels)
    b = trace.reduce_trace(trace.load(dst), kernels)
    assert a.window == b.window and a.host == b.host
    assert a.devices == b.devices


def test_recorded_spans_name_gaps_and_read_glue():
    kernels = {k: m.TRACE_NAMES for k, m in harness.kernel_table().items()}
    tr = trace.load(SMALL_SPANS)
    red = trace.reduce_trace(tr, kernels)
    # the named kernels (``pallas_call(name=...)``) are still found by
    # their source, so no kernel launch is left to count as glue
    assert red.devices[0].kernel_ns["fp_ray"] > 0
    assert red.devices[0].kernel_ns["bp_matched"] > 0
    launches = [ev for evs in tr.devices.values() for ev in evs
                if trace.is_kernel_call(ev)]
    assert launches and all(
        any(trace.matches(ev, names) for names in kernels.values())
        for ev in launches)
    steps = sum(1 for h in red.host if h.name == "chipbench.step")
    prog = spans.load_program(SMALL_SPANS)
    names = {ev.name for ev in prog}
    assert {"repro.serve.claim", "repro.serve.finish", "repro.step",
            "repro.sync", "repro.alg.step", "repro.op.A",
            "repro.op.At"} <= names
    r = spans.readings(SMALL_SPANS, kernels, steps)
    assert r["glue_s"] > 0 and set(r["glue_s_by_scope"]) == {
        "repro.op.fp", "repro.op.bp"}
    assert r["glue_s"] <= r["xla_ops_s"]
    assert r["dispatch_idle_s"] is not None and r["sync_idle_s"] is not None
    assert 0 <= r["dispatch_idle_s"] + r["sync_idle_s"] <= r["worst_idle_s"]
    assert 0 <= r["host.op_idle_s"] <= r["host.op_s"] and r["host.op_s"] > 0
    assert any(g[0].startswith("repro.") for g in r["idle_gaps"])
