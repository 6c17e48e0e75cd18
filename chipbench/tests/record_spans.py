"""Run one cell traced, with the program's own spans in it (needs a TPU).

    python3 chipbench/tests/record_spans.py --workload cbct512.cgls \
        --seconds 17 --out out/spans_cbct512
    python3 chipbench/tests/record_spans.py --workload cbct512.cgls \
        --n 64 --angles 64 --seconds 2 --out out/spans_small \
        --cut out/small_spans.xplane.pb

Runs ``harness.run_cell`` as a ``--trace 1`` run does, and turns the
program's tracer on with its profiler sink (``repro.obs``,
``enable(profiler=True)``) right after the profiler starts and off before it
stops, so only the traced window carries the spans.  Prints the result
line, then one JSON line of ``lib/spans.readings``.  ``--n`` / ``--angles``
cut the cell to a small size; ``--cut`` also writes the trace cut to what
the reductions read (the devices' ``XLA Ops`` lines and the host's
``chipbench.*`` / ``repro.*`` events), the size of a test fixture.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402
from chipbench.lib import trace, xplane  # noqa: E402

KEEP_HOST = ("chipbench.", "repro.")


def _fields(buf: bytes, i: int, end: int):
    """(field number, raw start, raw end, payload span) of one message;
    the payload span is that of a length-delimited value, else None."""
    while i < end:
        start = i
        key, i = xplane._varint(buf, i)
        wt = key & 7
        span = None
        if wt == 0:
            _, i = xplane._varint(buf, i)
        elif wt == 1:
            i += 8
        elif wt == 2:
            n, i = xplane._varint(buf, i)
            span = (i, i + n)
            i += n
        elif wt == 5:
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield key >> 3, start, i, span


def _varint_bytes(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _message(num: int, payload: bytes) -> bytes:
    return _varint_bytes(num << 3 | 2) + _varint_bytes(len(payload)) + payload


def _first_varint(buf: bytes, span) -> int:
    """Field 1 of a message, a varint: an event's metadata id, or the key
    of a map entry."""
    for num, s, _, _ in _fields(buf, *span):
        if num == 1:
            return xplane._varint(buf, s + 1)[0]
    return 0


def _cut_plane(buf: bytes, span) -> bytes:
    plane = xplane._plane(buf, span)
    device = trace.DEVICE_PLANE.match(plane.name) is not None
    if not device and not plane.name.startswith("/host"):
        return b""
    keep_ids = {mid for mid, md in plane.event_metadata.items()
                if device or md.name.startswith(KEEP_HOST)}
    out = []
    for num, s, e, pspan in _fields(buf, *span):
        if num == 3:                                   # a line
            line, n_ev, lname = [], 0, ""
            for n2, s2, e2, p2 in _fields(buf, *pspan):
                if n2 == 2:
                    lname = buf[p2[0]:p2[1]].decode("utf-8", "replace")
                if n2 != 4:
                    line.append(buf[s2:e2])
                    continue
                if _first_varint(buf, p2) in keep_ids:
                    line.append(buf[s2:e2])
                    n_ev += 1
            if n_ev and (not device or lname == trace.OPS_LINE):
                out.append(_message(3, b"".join(line)))
        elif num == 4:                                 # event metadata
            if _first_varint(buf, pspan) in keep_ids:
                out.append(buf[s:e])
        else:
            out.append(buf[s:e])
    return _message(1, b"".join(out))


def cut(src: str, dst: str) -> None:
    """Write the trace ``src`` cut to its device op lines and the host's
    harness and program spans."""
    with open(src, "rb") as f:
        buf = f.read()
    out = []
    for num, s, e, span in _fields(buf, 0, len(buf)):
        out.append(_cut_plane(buf, span) if num == 1 else buf[s:e])
    with open(dst, "wb") as f:
        f.write(b"".join(out))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="cbct512.cgls")
    ap.add_argument("--n", type=int, default=0)
    ap.add_argument("--angles", type=int, default=0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--cut", default="")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(tiny.ROOT)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_spans: no TPU", file=sys.stderr)
        return 3
    cell = (tiny.tiny_cell(args.workload, args.n, args.angles) if args.n
            else tiny.load_cell(args.workload))
    from chipbench.lib import harness, spans
    from repro import obs
    tracer = obs.get_tracer()
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace

    def start_with_spans(*a, **kw):
        start(*a, **kw)
        tracer.clear()
        tracer.enable(profiler=True)

    def stop_with_spans(*a, **kw):
        tracer.disable()
        tracer.clear()
        stop(*a, **kw)

    jax.profiler.start_trace = start_with_spans
    jax.profiler.stop_trace = stop_with_spans
    os.makedirs(args.out, exist_ok=True)
    try:
        res = harness.run_cell(cell, args.seed, args.seconds, True, T0,
                               trace_dir=os.path.abspath(args.out))
    finally:
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop
    print(res.line())
    kernels = {k: getattr(m, "TRACE_NAMES", ())
               for k, m in harness.kernel_table().items()}
    print(json.dumps(spans.readings(args.out, kernels, res.attempted)))
    if args.cut:
        cut(trace.find_xplane(args.out), args.cut)
        print(f"cut trace -> {args.cut} ({os.path.getsize(args.cut)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
