"""Device step: the device's self time in the scope `moe`, the routed-expert layers (norm, router, sort and gather of the held pairs, grouped products, combine, and their backward), all layers together, ms a step; device
trace by scope (`trace_reduce.reduce`'s `scope_self_s`)."""

from benchmark import trace_reduce


def read(run):
    return trace_reduce.scope_ms(run["trace"], "moe")
