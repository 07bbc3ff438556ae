"""Device step: the device's self time in the scope `attn_gated`, the gated softmax layers' attention parts (norm, the product for q, k, v and the gate, the per-head q and k norms, rotary, the causal scores and values, the output gate, the output product, and
their backward), ms a step; device
trace by scope (`trace_reduce.reduce`'s `scope_self_s`)."""

from benchmark import trace_reduce


def read(run):
    return trace_reduce.scope_ms(run["trace"], "attn_gated")
