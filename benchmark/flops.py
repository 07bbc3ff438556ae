"""The chips' published peaks, the yardstick that utilisation is taken
against. The matrix-multiply operations of a configuration's step are
its reference module's to count (`train_step_flops`), from the
configuration's sizes, and so are the operations and bytes of one layer
scope (`scope_costs`), which `roofline_s` turns into the least time the
chip could take.
"""

from __future__ import annotations

from benchmark import trace_reduce

# Peak dense bf16 FLOP/s of one chip by `device.device_kind` (Google Cloud
# TPU documentation, the v5e page).
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,  # v5e
}


# Peak HBM bytes/s of one chip (the same page).
PEAK_HBM_BYTES_PER_S = {
    "TPU v5 lite": 819e9,  # v5e
}


def _peak(table: dict, device_kind: str, what: str) -> float:
    try:
        return table[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak {what} for device_kind {device_kind!r}: add it to "
            f"benchmark/flops.py with its source"
        ) from None


def peak_flops(device_kind: str) -> float:
    return _peak(PEAK_BF16_FLOPS, device_kind, "FLOP/s")


def roofline_s(device_kind: str, cost: dict) -> float:
    """The least seconds one chip could take over `cost["flops"]`
    operations and `cost["bytes"]` bytes to and from its memory: the
    larger of the two at the chip's peaks."""
    return max(cost["flops"] / peak_flops(device_kind),
               cost["bytes"] / _peak(PEAK_HBM_BYTES_PER_S, device_kind, "HBM bytes/s"))


def scope_roofline_pct(run: dict, scope: str):
    """For a metric's reader: the share of its roofline that the layer
    scope `scope` reaches, from the device's self time in it
    (`trace_reduce.scope_ms`) and what the configuration's reference counts
    for it (`scope_costs`). Nothing without either, and on a chip only."""
    ms = trace_reduce.scope_ms(run["trace"], scope)
    if not ms or run["device"]["platform"] != "tpu" or scope not in run["scope_costs"]:
        return None
    return 100.0 * 1e3 * roofline_s(run["device"]["kind"], run["scope_costs"][scope]) / ms
