"""Device step: the scope `attn_latent`'s share of its roofline. The least time the
chip could take over what the scope needs (counted by the configuration's
reference, `scope_costs`: the least matrix-multiply operations, attention in the expanded form
over the pairs the causal mask keeps, and the
bytes of its matrices, residuals and rows passed once) is the larger of
operations over peak FLOP/s and bytes over peak bytes/s; the share is that
over the device's self time in the scope. Chip runs only."""

from benchmark import flops


def read(run):
    return flops.scope_roofline_pct(run, "attn_latent")
