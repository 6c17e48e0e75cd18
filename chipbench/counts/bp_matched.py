"""Work of one matched (exact-adjoint) backprojection, from the algorithm.

The transpose of the Joseph forward projection: the same rays, planes and
bilinear taps, with the data flow reversed.  The projections are read once
and the volume is written once.  Nothing here depends on how a kernel
blocks the work.
"""

import importlib.util
import os

# What identifies the kernel's launches in a device trace: the Pallas kernel
# function, or the source file of its ``pallas_call`` (a TPU trace records
# the call site, not the kernel function, unless the call is named).
TRACE_NAMES = ("_bp_matched_kernel", "repro/kernels/bp_matched.py:")

_spec = importlib.util.spec_from_file_location(
    "chipbench_counts_fp_ray", os.path.join(os.path.dirname(__file__),
                                            "fp_ray.py"))
_fp = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_fp)


def counts(geo, angles, n_devices: int = 1):
    """(flops, bytes) per device for one application over ``angles``: the
    forward projection's work; its bytes are the same read/write pair."""
    return _fp.counts(geo, angles, n_devices)
