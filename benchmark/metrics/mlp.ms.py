"""Device step: the device's self time in the scope `mlp`, the leading dense layers' feed-forward part (norm, the SwiGLU block, and their backward), ms a step; device
trace by scope (`trace_reduce.reduce`'s `scope_self_s`)."""

from benchmark import trace_reduce


def read(run):
    return trace_reduce.scope_ms(run["trace"], "mlp")
