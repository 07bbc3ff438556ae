"""Device step: the scope `attn_gated`'s share of its roofline. The least time the
chip could take over what the scope needs (counted by the configuration's
reference, `scope_costs`: the least matrix-multiply operations, the gate's columns among the
products' and attention over the pairs the causal mask keeps, and the
bytes of its matrices, residuals and heads passed once) is the larger of
operations over peak FLOP/s and bytes over peak bytes/s; the share is that
over the device's self time in the scope. Chip runs only."""

from benchmark import flops


def read(run):
    return flops.scope_roofline_pct(run, "attn_gated")
