"""Device step: the device's self time in the scope `attn_linear`, the linear layers' gated-delta-rule parts (norm, the qkvz and ba products, the causal convolution, the rule in chunks with its scan over the chunks, the gated head norm, the output product, and
their backward), all linear layers together, ms a step; device
trace by scope (`trace_reduce.reduce`'s `scope_self_s`)."""

from benchmark import trace_reduce


def read(run):
    return trace_reduce.scope_ms(run["trace"], "attn_linear")
