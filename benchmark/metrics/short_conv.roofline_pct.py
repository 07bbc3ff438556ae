"""Device step: the scope `short_conv`'s share of its roofline. The least time the
chip could take over what the scope needs (counted by the configuration's
reference, `scope_costs`: the least matrix-multiply operations, the three input
products, the output product and the filter's taps, and the bytes of its matrices,
residuals, gated inputs and outputs passed once) is the larger of operations over
peak FLOP/s and bytes over peak bytes/s; the share is that over the device's self
time in the scope. Chip runs only."""

from benchmark import flops


def read(run):
    return flops.scope_roofline_pct(run, "short_conv")
