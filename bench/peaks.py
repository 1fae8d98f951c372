"""Published peaks of the accelerators the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not listed is an error."""

from __future__ import annotations

_V5E = {
    "bf16_flops": 197e12,        # FLOP/s, bfloat16 matrix units
    "int8_ops": 393e12,          # OP/s
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "source": "Google Cloud documentation, 'TPU v5e' (per-chip peaks)",
}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
