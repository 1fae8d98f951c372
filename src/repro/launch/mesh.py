"""Production mesh builders.

Functions (not module-level constants) so importing never touches jax
device state — the dry-run sets XLA_FLAGS before first jax init.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis
    carries either extra data parallelism (default) or pipeline stages."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over host CPU devices (tests / examples).  Axes are
    ``Auto``: params carry their shardings and XLA propagates the rest."""
    auto = jax.sharding.AxisType.Auto
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(auto, auto))


# Per-chip peaks keyed by ``jax.Device.device_kind``.  TPU v5e: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s ICI); the VMEM figure is a planning budget, not a peak.
PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,
        "hbm_bw": 819e9,        # bytes/s
        "ici_bw_per_link": 50e9,  # bytes/s/link (~ per direction)
        "ici_links": 4,
        "hbm_bytes": 16e9,
        "vmem_bytes": 16 * 2 ** 20,  # usable VMEM planning budget per core
    },
}

# The chip the dry-runs and CPU-host plans model.
HW = PEAKS["TPU v5 lite"]


def device_peaks(device=None) -> dict:
    """Peaks of ``device`` (default ``jax.devices()[0]``).

    A CPU host plans against :data:`HW`, the chip it models.  An
    accelerator is looked up by ``device_kind``; a kind missing from
    :data:`PEAKS` raises instead of borrowing another chip's numbers.
    """
    dev = device if device is not None else jax.devices()[0]
    if dev.platform == "cpu":
        return HW
    try:
        return PEAKS[dev.device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device_kind {dev.device_kind!r} "
                         f"(known: {sorted(PEAKS)})") from None
