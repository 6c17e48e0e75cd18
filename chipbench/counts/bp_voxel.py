"""Work of one voxel-driven backprojection, counted from the algorithm.

Every voxel takes, at every angle, one bilinear sample of the projection
where its centre projects: four taps, each a multiply and an add; then
the depth weight: a multiply and the add into the voxel.  The projections
are read once and the volume is written once.  Nothing here depends on
how a kernel blocks the work.
"""

# What identifies the kernel's launches in a device trace: the Pallas kernel
# function, or the source file of its ``pallas_call`` (a TPU trace records
# the call site, not the kernel function, unless the call is named).
TRACE_NAMES = ("_bp_kernel", "repro/kernels/bp_voxel.py:")

TAPS = 4            # bilinear (v, u) sample
FLOPS_PER_TAP = 2   # multiply + add
FLOPS_WEIGHT = 2    # depth weight: multiply + accumulate
BYTES = 4           # fp32


def counts(geo, angles, n_devices: int = 1):
    """(flops, bytes) per device for one application over ``angles``, the
    angles split evenly over ``n_devices`` and the volume written by each."""
    nz, ny, nx = geo.n_voxel
    nv, nu = geo.n_detector
    voxels = nz * ny * nx
    flops = (TAPS * FLOPS_PER_TAP + FLOPS_WEIGHT) * voxels * len(angles) \
        / n_devices
    nbytes = BYTES * (voxels + len(angles) * nv * nu / n_devices)
    return flops, nbytes
