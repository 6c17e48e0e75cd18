"""Small cells for running the harness on the CPU (Pallas in interpret mode)."""

from __future__ import annotations

import copy
import json
import os
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench.lib import harness  # noqa: E402


def load_cell(name: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return harness.find_cell(json.load(f), name)


def tiny_cell(name: str = "cbct512.cgls", n: int = 16, n_angles: int = 16):
    """The named cell with its geometry cut to ``n``^3 and ``n_angles``:
    the same files, limits and traffic, a size a CPU can run (angle
    subsets keep their arc)."""
    cell = copy.deepcopy(load_cell(name))
    g = cell.config["geometry"]
    scale = n / g["n_voxel"][0]
    g["n_voxel"] = [n, n, n]
    g["n_detector"] = [n, n]
    params = cell.traffic.get("params", {})
    if "subset_size" in params:
        params["subset_size"] = max(1, round(
            params["subset_size"] * n_angles / cell.config["n_angles"]))
    cell.config["n_angles"] = n_angles
    assert scale <= 1.0
    cell.params["check"]["box"] = min(cell.params["check"]["box"], n // 4)
    return cell
