"""Device step: the device's self time in the scope `attn_latent`, the layers' latent attention parts (norm, the query and key/value latents with their norms, the expanding products, rotary, the causal scores and values, output product, and their backward), all layers together, ms a step; device
trace by scope (`trace_reduce.reduce`'s `scope_self_s`)."""

from benchmark import trace_reduce


def read(run):
    return trace_reduce.scope_ms(run["trace"], "attn_latent")
