"""Device step: the scope `attn_linear`'s share of its roofline. The least time the
chip could take over what the scope needs (counted by the configuration's
reference, `scope_costs`: the layers' products and the gated delta rule counted by its
frame-by-frame recurrence, 3 x 2 d^2 operations a value head and frame forward, whatever
chunk size or kernel computes it, so the yardstick does not move with the implementation; the
bytes of its matrices, residuals and heads passed once) is the larger of
operations over peak FLOP/s and bytes over peak bytes/s; the share is that
over the device's self time in the scope. Chip runs only."""

from benchmark import flops


def read(run):
    return flops.scope_roofline_pct(run, "attn_linear")
