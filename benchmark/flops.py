"""The chips' published peaks, the yardstick that utilisation is taken
against. The matrix-multiply operations of a configuration's step are
its reference module's to count (`train_step_flops`), from the
configuration's sizes.
"""

from __future__ import annotations

# Peak dense bf16 FLOP/s of one chip by `device.device_kind` (Google Cloud
# TPU documentation, the v5e page).
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,  # v5e
}


def peak_flops(device_kind: str) -> float:
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s for device_kind {device_kind!r}: add it to "
            f"benchmark/flops.py with its source"
        ) from None
