"""Device step: the device's self time in the scope `attn_full`, the full layers' attention parts (norm, q/k/v product, YaRN table, the causal scores and values, output product, and their backward), ms a step; device
trace by scope (`trace_reduce.reduce`'s `scope_self_s`)."""

from benchmark import trace_reduce


def read(run):
    return trace_reduce.scope_ms(run["trace"], "attn_full")
