"""Device step: the device's self time in the scope `attn_window`, the sliding layers' attention parts (norm, q/k/v product, rotary table, the windowed scores and values, output product, and their backward), all of them together, ms a step; device
trace by scope (`trace_reduce.reduce`'s `scope_self_s`)."""

from benchmark import trace_reduce


def read(run):
    return trace_reduce.scope_ms(run["trace"], "attn_window")
