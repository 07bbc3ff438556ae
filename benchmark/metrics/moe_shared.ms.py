"""Device step: the device's self time in the scope `moe_shared`, the sparse layers' shared expert (a SwiGLU every frame goes through, and its backward; its input is the norm of the scope `moe`), all layers together, ms a step; device
trace by scope (`trace_reduce.reduce`'s `scope_self_s`)."""

from benchmark import trace_reduce


def read(run):
    return trace_reduce.scope_ms(run["trace"], "moe_shared")
