#!/usr/bin/env python3
"""Chip benchmark of the cone-beam reconstruction service.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in this process on the chips it finds:
set-up (data from the seed, operator build, compilation, the algorithm's
init), then whole iterations for ``--seconds``, then the comparison with the
plain fp32 reference that decides ``correct``.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device``, optionally ``breakdown``,
and last ``checks``: each compared number with its limit.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    src = os.path.join(ROOT, "src")
    if not os.path.isfile(bench_path) or not os.path.isdir(
            os.path.join(src, "repro")):
        print("chipbench: BENCHMARK.json and the repro package (src/repro) "
              "must be in the checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    sys.path.insert(0, ROOT)
    from chipbench.lib import harness
    with open(bench_path) as f:
        cell = harness.find_cell(json.load(f), args.workload)

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache(ROOT)
    import jax
    # every program of the cell goes into the cache, however fast it
    # compiled, so only the first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"chipbench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 3
    harness.log(f"device {devs[0].platform} {devs[0].device_kind} "
                f"x{len(devs)}; cell {cell.name}; seed {args.seed}; "
                f"compile cache {cache}")
    res = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)
    for k, v in res.checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(f"correct {res.correct}", file=sys.stderr, flush=True)
    print(res.line(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
