"""The trace reduction, on a trace recorded on a TPU v5e.

``data/small.xplane.pb`` is the profiler trace of the ``cbct512.cgls`` cell
cut to 32^3 and 32 angles, 0.3 s of window (59 iterations), recorded with
``tests/record_trace.py`` on one TPU v5e chip, and cut to the planes and
lines the reduction reads (the device's ``XLA Ops``, the host's python
thread)."""

import os

import pytest

import tiny
from chipbench.lib import harness, reference as ref, trace, xplane
from chipbench.lib.metric_context import MetricContext

PATH = os.path.join(tiny.TESTS, "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    kernels = {k: m.TRACE_NAMES for k, m in harness.kernel_table().items()}
    return trace.reduce_trace(trace.load(PATH), kernels)


def test_planes_and_window(reduced):
    names = [p.name for p in xplane.read(PATH)]
    assert "/device:TPU:0" in names
    assert list(reduced.devices) == [0]
    assert reduced.window_ns > 0
    assert any(h.name == "chipbench.step" for h in reduced.host)


def test_both_kernels_found_and_time_adds_up(reduced):
    d = reduced.devices[0]
    assert d.kernel_ns["fp_ray"] > 0 and d.kernel_ns["bp_matched"] > 0
    total = sum(d.kernel_ns.values()) + d.collective_ns + d.other_ns
    # ops on one device do not overlap: their sum is the busy union
    assert abs(total - d.busy_ns) <= 1e-6 * reduced.window_ns
    assert 0 < d.busy_ns <= reduced.window_ns
    assert d.collective_ns == 0          # one chip
    gaps = sum(e - s for s, e in d.gaps)
    assert abs(gaps + d.busy_ns - reduced.window_ns) <= 1.0


def test_kernel_launches_are_tpu_custom_calls(reduced):
    ops = trace.breakdown(reduced)["device_ops"]
    assert ops[0][0].split(" ")[0] in ("fp_ray", "bp_matched")
    assert all(v >= 0 for _, v in ops)


def test_metrics_from_the_trace(reduced):
    cell = tiny.tiny_cell("cbct512.cgls", 32, 32)
    cfg = dict(cell.config, **cell.traffic)
    steps = sum(1 for h in reduced.host if h.name == "chipbench.step")
    ctx = MetricContext(reduced, steps, ref.Geometry.from_config(cfg),
                        ref.scan_angles(32), 1, "TPU v5 lite",
                        harness.kernel_table(), cfg)
    for k in ("fp_ray", "bp_matched"):
        share = ctx.roofline_share(k)
        assert 0 < share < 100
    assert ctx.per_iteration_s("other_ns") > 0
    assert ctx.per_iteration_s("collective_ns") == 0


def test_unknown_device_kind_is_an_error(reduced):
    ctx = MetricContext(reduced, 1, None, [], 1, "TPU v9 imaginary",
                        harness.kernel_table(),
                        {"operator_applications_per_iteration": {"fp_ray": 1}})
    with pytest.raises(KeyError):
        ctx.roofline_share("fp_ray")


def test_a_custom_fusion_around_a_kernel_counts_as_the_kernel():
    """XLA may fuse a kernel launch with the write of its result into a
    larger array (on a TPU trace of OS-SART at 512^3: ``%fp_ray.4 = ...
    fusion(...), kind=kCustom``); the fusion keeps the ``pallas_call`` as
    its op and is the kernel's time.  Other ops from the kernel's file are
    not."""
    src = "source=/x/src/repro/kernels/fp_ray.py:514"
    call = "tf_op=jit(f)/repro.op.fp/jit(f)/fp_ray/pallas_call:"
    ops = [
        trace.Event('%fp_ray.1 = f32[66,512,512] custom-call(...), '
                    'custom_call_target="tpu_custom_call"', 0, 400,
                    f"fp_ray.1 {src} {call} hlo_category=custom-call",
                    "fp_ray.1"),
        trace.Event("%fp_ray.4 = f32[64,512,512] fusion(...), kind=kCustom, "
                    "calls=%fused_computation.3", 400, 450,
                    f"fp_ray.4 {src} {call} hlo_category=custom fusion",
                    "fp_ray.4"),
        trace.Event("%cosine_bitcast_fusion = fusion(...), kind=kLoop", 450,
                    460, "cosine_bitcast_fusion source=/x/src/repro/kernels/"
                    "fp_ray.py:70 tf_op=jit(f)/repro.op.fp/jit(f)/cos: "
                    "hlo_category=loop fusion", "cosine_bitcast_fusion"),
        trace.Event("%add.1 = add(...)", 460, 500,
                    "add.1 tf_op=jit(add)/add: hlo_category=non-fusion "
                    "elementwise", "add.1")]
    tr = trace.Trace([trace.Event(trace.WINDOW, 0, 1000)], {0: ops})
    assert [trace.is_kernel_call(e) for e in ops] == [True, True, False,
                                                      False]
    kernels = {k: m.TRACE_NAMES for k, m in harness.kernel_table().items()}
    d = trace.reduce_trace(tr, kernels).devices[0]
    assert d.kernel_ns["fp_ray"] == 450
    assert d.other_ns == 50 and d.busy_ns == 500
