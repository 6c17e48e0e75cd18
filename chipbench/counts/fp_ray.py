"""Work of one forward projection, counted from the algorithm (Joseph).

Every ray of every angle takes one bilinear sample on each voxel plane of
its marching axis: four taps, each a multiply and an add.  The volume is
read once and the projections are written once.  Nothing here depends on
how a kernel blocks, tiles or interpolates the work.
"""

# What identifies the kernel's launches in a device trace: the Pallas kernel
# function, or the source file of its ``pallas_call`` (a TPU trace records
# the call site, not the kernel function, unless the call is named).
TRACE_NAMES = ("_fp_kernel", "repro/kernels/fp_ray.py:")

TAPS = 4            # bilinear (z, y) sample
FLOPS_PER_TAP = 2   # multiply + add
BYTES = 4           # fp32


def marching_steps(geo, angles) -> int:
    """Rays x marching planes over ``angles`` (x planes where |cos| >= |sin|,
    else y planes)."""
    import numpy as np
    nz, ny, nx = geo.n_voxel
    nv, nu = geo.n_detector
    a = np.asarray(angles, np.float64)
    xdom = np.abs(np.cos(a)) >= np.abs(np.sin(a))
    planes = int(xdom.sum()) * nx + int((~xdom).sum()) * ny
    return nv * nu * planes


def counts(geo, angles, n_devices: int = 1):
    """(flops, bytes) per device for one application over ``angles``, the
    angles split evenly over ``n_devices`` and the volume read by each."""
    nz, ny, nx = geo.n_voxel
    nv, nu = geo.n_detector
    flops = TAPS * FLOPS_PER_TAP * marching_steps(geo, angles) / n_devices
    nbytes = BYTES * (nz * ny * nx + len(angles) * nv * nu / n_devices)
    return flops, nbytes
