"""Device step: the scope `optimizer`'s share of its roofline. The least time
the chip could take over what the scope needs (Adam's 28 bytes a parameter;
counted by the configuration's reference, `scope_costs`) is the larger of
operations over peak FLOP/s and bytes over peak bytes/s; the share is that
over the device's self time in the scope. Chip runs only."""

from benchmark import flops


def read(run):
    return flops.scope_roofline_pct(run, "optimizer")
