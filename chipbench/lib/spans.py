"""The program's own spans and operator scopes in a profiler trace.

Where the program's tracer writes to the profiler (``repro.obs``,
``get_tracer().enable(profiler=True)``), its spans sit on the trace's host
plane as ``repro.<span>`` events, on the device trace's clock.  An op that
a jitted operator body traced under a ``repro.op.*`` ``jax.named_scope``
carries the scope in its ``tf_op`` stat.  This module reads both beside
:mod:`trace`'s reduction, which it takes as it is:

* :func:`load_program`: the ``repro.*`` host events, names without any
  ``#...`` suffix, by start;
* :class:`ProgramSpans`: the innermost program span at a time, the idle
  gaps of a device named by it, and host time inside named spans;
* :func:`glue_ns`: per device and scope, the time of the ops under a
  ``repro.op.*`` scope that are neither kernels nor collectives;
* :func:`readings`: the per-iteration numbers ``glue_s``,
  ``dispatch_idle_s``, ``sync_idle_s``, ``host.op_s`` and
  ``host.op_idle_s``, and the longest idle gaps named by what the host
  was doing.

A device idles while the host works through the program's code (the
device waits for dispatch: ``dispatch_idle_s``), or while the host returns
from the executor's wait for a step it has just finished (``repro.sync``:
``sync_idle_s``).  The host's time in operator calls (``host.op_s``)
includes its waits for the device, when the device is busy; only the part
while the device idles (``host.op_idle_s``) is on the critical path.

The harness does not turn the program's tracer on: ``tests/record_spans.py``
does, around the traced window.
"""

from __future__ import annotations

import bisect
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import trace, xplane

PROGRAM = "repro."
# the outermost operator scope in an op's JAX name stack
OP_SCOPE = re.compile(r"repro\.op(?:\.\w+)*")
TF_OP = re.compile(r"(?:^| )tf_op=(\S*)")
OPERATOR_SPANS = ("repro.op.A", "repro.op.At")
# the executor's wait for a step's state (serve/executor.py)
SYNC = "repro.sync"


def load_program(path: str) -> List[trace.Event]:
    """The ``repro.*`` events of the trace's host planes, by start."""
    if os.path.isdir(path):
        path = trace.find_xplane(path)
    out = []
    for plane in xplane.read(path):
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for mid, s, e in line.events:
                md = plane.event_metadata.get(mid)
                if md and md.name.startswith(PROGRAM):
                    out.append(trace.Event(md.name.split("#")[0], s, e))
    return sorted(out, key=lambda ev: ev.start_ns)


def op_scope(ev: trace.Event) -> str:
    """The ``repro.op.*`` scope of a device op, or ``""``."""
    m = TF_OP.search(ev.text)
    s = OP_SCOPE.search(m.group(1)) if m else None
    return s.group(0) if s else ""


class ProgramSpans:
    """The program's spans, for naming what the host did at a time."""

    def __init__(self, spans: Sequence[trace.Event]):
        self.spans = sorted(spans, key=lambda ev: ev.start_ns)
        self._starts = [ev.start_ns for ev in self.spans]
        self._longest = max((ev.dur_ns for ev in self.spans), default=0.0)

    def at(self, t: float) -> Optional[str]:
        """Name of the innermost span covering time ``t``, if any."""
        best: Optional[trace.Event] = None
        # sorted by start: only spans that start in [t - longest, t] can
        # cover t
        for i in range(bisect.bisect_right(self._starts, t) - 1, -1, -1):
            ev = self.spans[i]
            if ev.start_ns < t - self._longest:
                break
            if t <= ev.end_ns and (best is None or ev.dur_ns < best.dur_ns):
                best = ev
        return best.name if best else None

    def name_at(self, red: trace.Reduced, t: float) -> str:
        """The innermost program span at ``t``, else the innermost harness
        span (as :func:`trace.breakdown` names a gap)."""
        return self.at(t) or red.host_span_at(t)

    def idle_ns(self, red: trace.Reduced, dev: int,
                names: Callable[[str], bool]) -> float:
        """Idle time of device ``dev`` in the window whose gaps have their
        middle inside a program span, the innermost of which passes
        ``names``."""
        return sum(e - s for s, e in red.devices[dev].gaps
                   if names(self.at((s + e) / 2) or ""))

    def host_spans(self, red: trace.Reduced,
                   names: Sequence[str]) -> List[Tuple[float, float]]:
        """The union of the spans named ``names`` (the outermost ones),
        clipped to the window."""
        spans = trace.union([(ev.start_ns, ev.end_ns) for ev in self.spans
                             if ev.name in names])
        return [(max(s, w0), min(e, w1)) for s, e in spans
                for w0, w1 in red.window if min(e, w1) > max(s, w0)]

    def idle_gaps(self, red: trace.Reduced, top: int = 10) -> List[list]:
        """The longest idle gaps (seconds), each named by what the host
        was doing at its middle."""
        gaps = [(f"{self.name_at(red, (s + e) / 2)} (device {dev})",
                 (e - s) * 1e-9)
                for dev, d in red.devices.items() for s, e in d.gaps]
        gaps.sort(key=lambda kv: -kv[1])
        return [[k, v] for k, v in gaps[:top]]


def glue_ns(tr: trace.Trace, red: trace.Reduced,
            kernels: Dict[str, Sequence[str]]) -> Dict[int, Dict[str, float]]:
    """Per device and ``repro.op.*`` scope, the device time inside the
    window of the ops that :func:`trace.reduce_trace` counts as neither a
    kernel of ``kernels`` nor a collective: the operators' glue."""
    out: Dict[int, Dict[str, float]] = {}
    for dev, evs in tr.devices.items():
        per: Dict[str, float] = {}
        for ev in evs:
            scope = op_scope(ev)
            if not scope:
                continue
            if trace.is_kernel_call(ev) and any(
                    trace.matches(ev, names) for names in kernels.values()):
                continue
            if trace.COLLECTIVE.search(ev.label):
                continue
            ns = sum(max(0.0, min(ev.end_ns, w1) - max(ev.start_ns, w0))
                     for w0, w1 in red.window)
            per[scope] = per.get(scope, 0.0) + ns
        out[dev] = per
    return out


def _overlap_ns(a: Sequence[Tuple[float, float]],
                b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    out, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            out += max(0.0, min(e, b[k][1]) - max(s, b[k][0]))
            k += 1
    return out


def readings(path: str, kernels: Dict[str, Sequence[str]],
             n_iter: int) -> dict:
    """The per-iteration numbers of a trace with the program's spans,
    ``None`` where the trace holds nothing for one (no scope, no span);
    ``xla_ops_s`` and the worst device's idle seconds per iteration beside
    them, and the longest idle gaps named by what the host was doing.

    ``glue_s``: device time of the operators' glue, mean over devices.
    ``dispatch_idle_s``: idle time whose gap middle falls in a program
    span other than ``repro.sync``; ``sync_idle_s``: in ``repro.sync``;
    each on the device where it is largest.  ``host.op_s``: host time in
    the outermost operator spans, waits included; ``host.op_idle_s``: the
    part of it during which the device idles, where that is largest."""
    tr = trace.load(path)
    red = trace.reduce_trace(tr, kernels)
    prog = ProgramSpans(load_program(path))
    glue = glue_ns(tr, red, kernels)
    n_dev = max(1, len(red.devices))
    per_iter = 1e-9 / max(1, n_iter)
    devs = list(red.devices)

    def worst_idle(names: Callable[[str], bool]) -> Optional[float]:
        if not (prog.spans and devs):
            return None
        return max(prog.idle_ns(red, d, names) for d in devs) * per_iter

    has_op = any(ev.name in OPERATOR_SPANS for ev in prog.spans)
    op_spans = prog.host_spans(red, OPERATOR_SPANS)
    return {
        "glue_s": (sum(sum(g.values()) for g in glue.values()) / n_dev
                   * per_iter if any(glue.values()) else None),
        "glue_s_by_scope": {
            k: sum(g.get(k, 0.0) for g in glue.values()) / n_dev * per_iter
            for k in sorted({k for g in glue.values() for k in g})},
        "dispatch_idle_s": worst_idle(lambda n: n not in ("", SYNC)),
        "sync_idle_s": worst_idle(lambda n: n == SYNC),
        "host.op_s": (sum(e - s for s, e in op_spans) * per_iter
                      if has_op else None),
        "host.op_idle_s": (max((_overlap_ns(op_spans, red.devices[d].gaps)
                                for d in devs), default=0.0) * per_iter
                           if has_op else None),
        "xla_ops_s": sum(d.other_ns for d in red.devices.values()) / n_dev
        * per_iter,
        "worst_idle_s": max((red.window_ns - d.busy_ns
                             for d in red.devices.values()), default=0.0)
        * per_iter,
        "idle_gaps": prog.idle_gaps(red),
    }
