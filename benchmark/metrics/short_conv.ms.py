"""Device step: the device's self time in the scope `short_conv`, the short-convolution layers' mixers (norm, the three products of B, C and x, B * x, the causal filter of three taps, C's gate, the output product, and their backward), ms a step; device
trace by scope (`trace_reduce.reduce`'s `scope_self_s`)."""

from benchmark import trace_reduce


def read(run):
    return trace_reduce.scope_ms(run["trace"], "short_conv")
