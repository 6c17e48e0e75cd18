"""Readings for the check's limits, many seeds in one process (needs a TPU).

    python3 chipbench/tests/prove.py --workload cbct512.cgls \
        --seeds 11,12,13 --seconds 5 --controls bf16 --out readings.jsonl

For each seed: a whole run of the cell (set-up from the seed, a short
window, the check), the program's compared numbers, and, with
``--controls bf16``, the same numbers with the reference in bfloat16 put
in the program's place.  One JSON line per reading goes to
``--out``.  The limits in ``cells/<cell>.json`` are set from these: above
the largest program reading, below the smallest bf16 control reading.
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="cbct512.cgls")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--controls", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(tiny.ROOT)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("prove: no TPU", file=sys.stderr)
        return 3
    from chipbench.lib import harness
    cell = tiny.load_cell(args.workload)
    controls = tuple(c for c in args.controls.split(",") if c)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as out:
        for seed in (int(s) for s in args.seeds.split(",")):
            t = time.perf_counter()

            def emit(kind, numbers, seed=seed):
                row = {"cell": cell.name, "seed": seed, "kind": kind,
                       "numbers": numbers}
                out.write(json.dumps(row) + "\n")
                out.flush()
                print(json.dumps(row), flush=True)
            res = harness.run_cell(cell, seed, args.seconds, False, t,
                                   controls=controls, on_numbers=emit)
            print(json.dumps({"seed": seed, "correct": res.correct,
                              "iterations": res.attempted,
                              "metrics": res.metrics,
                              "seconds": time.perf_counter() - t}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
