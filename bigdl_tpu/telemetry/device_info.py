"""Device capability table — the package's one copy of per-chip peaks
(the PerfAccountant's MFU and roofline math, serving goodput-per-chip).
The benchmark keeps its own v5e row in ``benchmark/peaks.json``;
``tests/test_tree.py::test_one_v5e_peak`` holds the two equal.

Numbers are public spec-sheet figures per **chip**:

* ``peak_flops_per_sec`` — dense bf16 peak, multiply-add counted as
  2 FLOPs (the MFU denominator convention).
* ``hbm_bytes`` / ``hbm_bytes_per_sec`` — HBM capacity and bandwidth
  (the roofline's memory axis; the ridge point is
  ``peak_flops / hbm_bw``).
* ``ici_bytes_per_sec`` — aggregate inter-chip interconnect
  bandwidth.  Interconnect counting conventions vary between spec
  sheets (per-link vs aggregate, per-direction vs bidirectional);
  these are order-of-magnitude figures for roofline *classification*
  (is this program collective-bound?), not for bandwidth accounting.

The CPU row is **nominal** (``nominal=True``): a placeholder peak so
MFU-family metrics stay computable (and testable) on the CPU backend;
absolute CPU MFU values are not meaningful.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

__all__ = [
    "DEVICE_SPECS", "DeviceSpec", "PEAK_FLOPS_TABLE",
    "current_device_spec", "device_spec", "peak_flops_per_sec",
]

GiB = 1024 ** 3


class DeviceSpec(NamedTuple):
    """Per-chip capability row (see module docstring for units)."""

    kind: str
    peak_flops_per_sec: float
    hbm_bytes: Optional[float]
    hbm_bytes_per_sec: Optional[float]
    ici_bytes_per_sec: Optional[float]
    nominal: bool = False

    @property
    def ridge_flops_per_byte(self) -> Optional[float]:
        """The roofline ridge point: arithmetic intensity above which
        the chip is compute-bound rather than HBM-bound."""
        if not self.hbm_bytes_per_sec:
            return None
        return self.peak_flops_per_sec / self.hbm_bytes_per_sec

    def to_dict(self) -> dict:
        return dict(self._asdict())


# substring-matched against jax's device_kind (lowercased), first hit
# wins
DEVICE_SPECS = (
    DeviceSpec("v6e", 918e12, 32 * GiB, 1640e9, 900e9),
    DeviceSpec("trillium", 918e12, 32 * GiB, 1640e9, 900e9),
    DeviceSpec("v5p", 459e12, 95 * GiB, 2765e9, 1200e9),
    DeviceSpec("v5e", 197e12, 16 * GiB, 819e9, 400e9),
    DeviceSpec("v5litepod", 197e12, 16 * GiB, 819e9, 400e9),
    DeviceSpec("v5 lite", 197e12, 16 * GiB, 819e9, 400e9),
    DeviceSpec("v4", 275e12, 32 * GiB, 1228e9, 1200e9),
    DeviceSpec("v3", 123e12, 32 * GiB, 900e9, 656e9),
    DeviceSpec("v2", 45e12, 16 * GiB, 700e9, 496e9),
)

#: nominal CPU row: ~a few f32 GEMM cores' worth of peak and one
#: DDR channel group of bandwidth — keeps MFU/roofline math exercised
#: on the CPU backend without pretending to measure the host
CPU_SPEC = DeviceSpec("cpu", 100e9, None, 20e9, None, nominal=True)

#: (kind substring, bf16 peak FLOP/s)
PEAK_FLOPS_TABLE = tuple(
    (s.kind, s.peak_flops_per_sec) for s in DEVICE_SPECS)


def peak_flops_per_sec(device_kind: str) -> Optional[float]:
    """bf16 peak FLOP/s per chip for a jax ``device_kind`` string, or
    None when unknown (a CPU/unknown device has no honest peak and
    reports no MFU)."""
    spec = device_spec(device_kind)
    return None if spec is None or spec.nominal \
        else spec.peak_flops_per_sec


def device_spec(device_kind: str) -> Optional[DeviceSpec]:
    """Capability row for a ``device_kind`` string: substring match
    against the table, the nominal CPU row for cpu/host kinds, None
    for anything else."""
    k = (device_kind or "").lower()
    for spec in DEVICE_SPECS:
        if spec.kind in k:
            return spec
    if "cpu" in k or "host" in k or "interpreter" in k:
        return CPU_SPEC
    return None


def current_device_spec(device=None) -> DeviceSpec:
    """Spec for a live jax device (default: ``jax.devices()[0]``).
    The CPU backend gets the nominal row; an accelerator whose
    ``device_kind`` is not in :data:`DEVICE_SPECS` RAISES — a made-up
    denominator under a real chip's MFU is worse than no number, so
    add the row (with its source) instead."""
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "") or str(device)
    spec = device_spec(kind)
    if spec is None or (spec.nominal
                        and getattr(device, "platform", "cpu") != "cpu"):
        raise LookupError(
            f"no DEVICE_SPECS row for accelerator device_kind={kind!r} "
            f"(platform {getattr(device, 'platform', '?')!r}): add its "
            "per-chip peaks to telemetry/device_info.py")
    return spec
