"""Device step: the device's self time in the scope `heads` (the action and
value heads, forward and backward), ms a step; device trace by scope
(`trace_reduce.reduce`'s `scope_self_s`)."""

from benchmark import trace_reduce


def read(run):
    return trace_reduce.scope_ms(run["trace"], "heads")
