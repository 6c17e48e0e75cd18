"""Reduction of a JAX profiler trace to per-device op times and idle gaps.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``, read
here with :mod:`xplane`.  Device planes are named ``/device:TPU:<n>``; their
``XLA Ops`` line holds one event per executed operation, named by its HLO
text; a Pallas kernel is a ``tpu_custom_call`` whose metadata ``source`` is
the ``pallas_call`` site, which is how the kernel table tells kernels
apart.  The harness's own ``jax.profiler.TraceAnnotation`` spans (names
starting ``chipbench.``) sit on the host plane, on the same clock.

Everything a per-layer metric reads comes from :func:`reduce_trace`:

* the traced window: the union of the ``chipbench.window`` spans;
* per device: the busy intervals (union of op events, clipped to the
  window), the summed time of the ops of each kernel in the kernel table,
  of the cross-chip collectives, and of every other op;
* the idle gaps inside the window, each attributed to the innermost
  ``chipbench.*`` host span that covers its middle.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "chipbench.window"
# XLA collective ops (and their async -start / -done halves)
COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|psum)", re.IGNORECASE)


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float
    text: str = ""          # the event's string stats, for name matching
    label: str = ""         # short op name (HLO instruction name)

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    host: List[Event]                   # chipbench.* annotation spans
    devices: Dict[int, List[Event]]     # device id -> op events


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file (or a directory holding one)."""
    from . import xplane
    if os.path.isdir(path):
        path = find_xplane(path)
    host: List[Event] = []
    devices: Dict[int, List[Event]] = {}
    for plane in xplane.read(path):
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = devices.setdefault(int(m.group(2)), [])
            text = {}
            for mid, md in plane.event_metadata.items():
                text[mid] = " ".join([md.display_name] + [
                    f"{k}={v}" for k, v in md.stats.items()
                    if k in ("source", "tf_op", "hlo_category")])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for mid, s, e in line.events:
                    md = plane.event_metadata.get(mid)
                    name = md.name if md else str(mid)
                    label = (md.display_name if md else "") or name[:80]
                    evs.append(Event(name, s, e, text.get(mid, ""), label))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for mid, s, e in line.events:
                    md = plane.event_metadata.get(mid)
                    if md and md.name.startswith("chipbench."):
                        host.append(Event(md.name, s, e))
    for evs in devices.values():
        evs.sort(key=lambda e: e.start_ns)
    return Trace(host, devices)


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, disjoint cover of ``intervals``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def is_kernel_call(ev: Event) -> bool:
    """A Pallas kernel launch: an XLA custom call to a Mosaic TPU kernel, or
    a custom fusion XLA built around one (the kernel with the write of its
    result into a larger array), which keeps the ``pallas_call`` as its op."""
    return "tpu_custom_call" in ev.name or (
        "hlo_category=custom fusion" in ev.text
        and "/pallas_call" in ev.text)


def matches(ev: Event, names: Sequence[str]) -> bool:
    return any(n in ev.name or n in ev.text for n in names)


@dataclasses.dataclass
class DeviceSummary:
    busy_ns: float
    kernel_ns: Dict[str, float]
    collective_ns: float
    other_ns: float
    gaps: List[Tuple[float, float]]
    op_ns: Dict[str, float]             # per op label, for the breakdown


@dataclasses.dataclass
class Reduced:
    window: List[Tuple[float, float]]   # the window's spans, disjoint
    devices: Dict[int, DeviceSummary]
    host: List[Event]

    @property
    def window_ns(self) -> float:
        return sum(e - s for s, e in self.window)

    def host_span_at(self, t: float) -> str:
        """Innermost ``chipbench.*`` span covering time ``t``."""
        best: Optional[Event] = None
        for h in self.host:
            if h.start_ns <= t <= h.end_ns and h.name != WINDOW:
                if best is None or h.dur_ns < best.dur_ns:
                    best = h
        return best.name if best else "(no harness span)"


def reduce_trace(trace: Trace, kernels: Dict[str, Sequence[str]]) -> Reduced:
    """Per-device summary of ``trace`` inside the harness's window.

    The window is the union of the ``chipbench.window`` spans: the harness
    closes the span while it copies the state the check needs, so that
    copy is neither window time nor idle device time.  ``kernels`` maps a
    kernel's name to the names it carries in a trace."""
    window = union([(h.start_ns, h.end_ns) for h in trace.host
                    if h.name == WINDOW])
    if not window:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    out: Dict[int, DeviceSummary] = {}
    for dev, evs in sorted(trace.devices.items()):
        clipped = []
        for ev in evs:
            for w0, w1 in window:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e > s:
                    clipped.append(Event(ev.name, s, e, ev.text, ev.label))
        busy = union([(e.start_ns, e.end_ns) for e in clipped])
        kern = {k: 0.0 for k in kernels}
        coll = other = 0.0
        op_ns: Dict[str, float] = {}
        for ev in clipped:
            hit = None
            if is_kernel_call(ev):
                hit = next((k for k, names in kernels.items()
                            if matches(ev, names)), None)
            key = f"{hit} ({ev.label})" if hit else ev.label
            op_ns[key] = op_ns.get(key, 0.0) + ev.dur_ns
            if hit is not None:
                kern[hit] += ev.dur_ns
            elif COLLECTIVE.search(ev.label):
                coll += ev.dur_ns
            else:
                other += ev.dur_ns
        gaps = []
        for w0, w1 in window:
            t = w0
            for s, e in busy:
                if e <= w0 or s >= w1:
                    continue
                if s > t:
                    gaps.append((t, s))
                t = max(t, e)
            if t < w1:
                gaps.append((t, w1))
        out[dev] = DeviceSummary(sum(e - s for s, e in busy), kern, coll,
                                 other, gaps, op_ns)
    return Reduced(window, out, trace.host)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device ops that took most time (summed over devices) and the
    longest idle gaps, each named by what the host was doing."""
    ops: Dict[str, float] = {}
    for d in red.devices.values():
        for name, ns in d.op_ns.items():
            ops[name] = ops.get(name, 0.0) + ns
    n = max(1, len(red.devices))
    dev_ops = sorted(((k, v / n * 1e-9) for k, v in ops.items()),
                     key=lambda kv: -kv[1])[:top]
    gaps = []
    for dev, d in red.devices.items():
        for s, e in d.gaps:
            gaps.append((f"{red.host_span_at((s + e) / 2)} (device {dev})",
                         (e - s) * 1e-9))
    gaps.sort(key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in dev_ops],
            "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def describe(path: str, limit: int = 12) -> str:
    """A readable summary of a trace's planes, lines and busiest events."""
    from . import xplane
    if os.path.isdir(path):
        path = find_xplane(path)
    rows = []
    for plane in xplane.read(path):
        rows.append(f"PLANE {plane.name}")
        for line in plane.lines:
            rows.append(f"  LINE {line.name!r}: {len(line.events)} events")
            tot: Dict[int, float] = {}
            for mid, s, e in line.events:
                tot[mid] = tot.get(mid, 0.0) + (e - s)
            for mid, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:limit]:
                md = plane.event_metadata.get(mid)
                name = md.name if md else str(mid)
                stats = {k: str(v)[:120] for k, v in (md.stats if md else {}).items()
                         if k in ("source", "tf_op", "hlo_category")}
                rows.append(f"    {ns * 1e-6:12.3f} ms  {name[:100]!r} {stats}")
    return "\n".join(rows)
