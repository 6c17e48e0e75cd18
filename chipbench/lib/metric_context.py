"""What a per-layer metric reader gets: the reduced trace and the cell.

A reader in ``metrics/<name>.py`` defines ``read(ctx)`` and returns a
number, or ``None`` where the trace holds nothing for it to read (the
harness then leaves the metric out of the line).  The shared arithmetic
lives here so every reader computes the same way.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from .peaks import peaks


class MetricContext:
    def __init__(self, red, n_iter: int, geo, angles, n_devices: int,
                 device_kind: str, kernels: Dict[str, object], cfg: dict):
        self.red = red                  # trace.Reduced
        self.n_iter = n_iter            # whole iterations in the window
        self.geo = geo                  # reference.Geometry
        self.angles = angles
        self.n_devices = n_devices
        self.device_kind = device_kind
        self.kernels = kernels          # kernel -> counts module
        self.cfg = cfg                  # configuration + traffic mix
        self.notes: List[str] = []

    def per_iteration_s(self, attr: str) -> Optional[float]:
        """Mean over devices of a summed device time, per iteration."""
        devs = list(self.red.devices.values())
        if not devs or self.n_iter == 0:
            return None
        tot = sum(getattr(d, attr) for d in devs) / len(devs)
        return tot * 1e-9 / self.n_iter

    def roofline_share(self, kernel: str) -> Optional[float]:
        """Percent of the chip's roofline the kernel's calls reached.

        The least time the work could take on each device -- the larger of
        its operations over peak FLOP/s and its bytes over peak bytes/s --
        summed over devices, over the kernel's summed device time there.
        The work is the algorithm's (``counts/<kernel>.py``), times the
        operator applications the traffic mix makes per iteration."""
        apps = self.cfg.get("operator_applications_per_iteration", {})
        if kernel not in apps or kernel not in self.kernels:
            return None
        pk = peaks(self.device_kind)
        flops, nbytes = self.kernels[kernel].counts(self.geo, self.angles,
                                                    self.n_devices)
        calls = apps[kernel] * self.n_iter
        t_f = flops * calls / pk["flops_per_s"]
        t_b = nbytes * calls / pk["bytes_per_s"]
        busy = [d.kernel_ns.get(kernel, 0.0) for d in
                self.red.devices.values()]
        ran = [t for t in busy if t > 0]
        if not ran:
            return None
        share = 100.0 * max(t_f, t_b) * len(ran) / (sum(ran) * 1e-9)
        self.notes.append(
            f"roofline.{kernel}: {flops * calls:.6e} flop, "
            f"{nbytes * calls:.6e} B per device over {calls} calls; bound by "
            f"{'compute' if t_f >= t_b else 'bandwidth'}; kernel time "
            f"{sum(ran) * 1e-9 / len(ran)} s per device")
        return share
