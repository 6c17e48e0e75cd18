"""Run one cell with the profiler on and keep the trace (needs a TPU).

    python3 chipbench/tests/record_trace.py --workload cbct512.cgls \
        --n 64 --angles 64 --seconds 2 --out out/trace_small

``--n`` / ``--angles`` cut the cell to a small size (the trace that
``test_trace.py`` reads was recorded this way).  Prints a summary of the
trace's planes, lines and busiest events, then the result line.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="cbct512.cgls")
    ap.add_argument("--n", type=int, default=0)
    ap.add_argument("--angles", type=int, default=0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(tiny.ROOT)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 3
    cell = (tiny.tiny_cell(args.workload, args.n, args.angles) if args.n
            else tiny.load_cell(args.workload))
    from chipbench.lib import harness, trace
    os.makedirs(args.out, exist_ok=True)
    res = harness.run_cell(cell, args.seed, args.seconds, True, T0,
                           trace_dir=os.path.abspath(args.out))
    print(trace.describe(args.out))
    print(res.line())
    return 0


if __name__ == "__main__":
    sys.exit(main())
