"""Routed-expert feed-forward layer: a router over all of the model's
experts, and the part of the layer's sum that the experts held here give.

A model with more experts than one chip holds is run expert-parallel:
every chip of a group holds a share of each layer's experts, scores each
frame against all of them, and computes its own experts' part. This
module is that one chip's layer. It is told how many experts it holds
and which is its first; what the absent experts would add is left out and
the partial sum goes on (with every expert held, the partial sum is the
layer). The exchange between the chips of a group is not here: on one
chip there is none, and no code stands in for the others.

Dropless, with static shapes: each of a frame's `top_k` choices is a
pair (frame, expert). The pairs are sorted so that those whose expert is
held here come first, in expert order; their rows are gathered once and
go through grouped matrix products (`grouped_dot`: on a TPU a kernel
that walks the groups' row tiles, so its work follows the number of
pairs routed here, not the buffer's length). The buffer holds
frames x top_k rows, the case in which every choice of every frame is
held here, so no pair is ever dropped; rows past the held pairs are
masked on both sides of the products.

Router product, softmax and top-k are float32 at the highest matmul
precision whatever the compute type: which experts a frame goes to
flips on rounding, and a flipped choice is a different function. The
caller may standardise the scores between `router_scores` and `route`
(`standardize`; models/transformer_policy.py ExpertLayer,
cfg.moe_standardize_router).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class Routing(NamedTuple):
    experts: jnp.ndarray  # [F, top_k] int32, over all of the model's experts
    weights: jnp.ndarray  # [F, top_k] f32, renormalised over the chosen


def router_scores(x: jnp.ndarray, w_router: jnp.ndarray) -> jnp.ndarray:
    """x [.., D] . w_router [D, E] in float32 at the highest matmul
    precision, whatever the compute type."""
    return jnp.matmul(
        x.astype(jnp.float32), w_router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
    )


ROUTER_EPS = 1e-6  # under the root that a standardised score is divided by


def standardize(scores: jnp.ndarray, seen, n: jnp.ndarray, axis=None):
    """Each expert's score less its running mean, over the root of the
    running mean of that difference's square: causal, and every term a
    small number (the variance as a difference of two large means loses
    its digits where the scores share a large constant, which is when it
    is wanted). `axis`: the time axis of scores [.., T, E], n [T] counting
    from 1 (the learner's unroll). Without it one frame [.., E] after
    `seen` = (sum of scores, sum of squared differences) of the n - 1
    frames before it (the actor's step). Returns the standardised scores
    and the two sums with this frame's."""
    if axis is not None:
        total = jnp.cumsum(scores, axis=axis)
        n = n[:, None]
    else:
        total = seen[0] + scores
    diff = scores - total / n
    squares = jnp.cumsum(diff * diff, axis=axis) if axis is not None else seen[1] + diff * diff
    return diff * jax.lax.rsqrt(squares / n + ROUTER_EPS), (total, squares)


def route(scores: jnp.ndarray, top_k: int) -> Routing:
    """scores [F, E] float32: softmax over all E, the `top_k` largest,
    their probabilities renormalised to sum to one."""
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    _, experts = jax.lax.top_k(jax.lax.stop_gradient(probs), top_k)
    # The chosen probabilities by a one-hot sum: its transpose is dense,
    # where top_k's own is a scatter of every pair.
    chosen = experts[..., None] == jnp.arange(probs.shape[-1], dtype=experts.dtype)
    weights = jnp.sum(jnp.where(chosen, probs[..., None, :], 0.0), axis=-1)
    return Routing(experts.astype(jnp.int32), weights / jnp.sum(weights, axis=-1, keepdims=True))


def _rows(a: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    return a.at[idx].get(mode="promise_in_bounds")


@jax.custom_vjp
def _take(a: jnp.ndarray, idx: jnp.ndarray, inv: jnp.ndarray) -> jnp.ndarray:
    """a[idx] for a permutation `idx` whose inverse is `inv`: the backward
    pass is the inverse gather, where the transpose of a gather would be a
    scatter-add, row by row."""
    return _rows(a, idx)


_take.defvjp(lambda a, idx, inv: (_rows(a, idx), inv), lambda inv, g: (_rows(g, inv), None, None))


@jax.custom_vjp
def _spread(x: jnp.ndarray, order: jnp.ndarray, inv: jnp.ndarray) -> jnp.ndarray:
    """x [F, D] -> [F * top_k, D]: the row of pair order[i]'s frame at i.
    Backward: each frame sums its top_k pairs' rows, found by `inv`."""
    return _rows(x, order // (order.shape[0] // x.shape[0]))


def _spread_bwd(res, g):
    inv, F = res
    return jnp.sum(_rows(g, inv).reshape(F, -1, g.shape[-1]), axis=1), None, None


_spread.defvjp(lambda x, order, inv: (_spread(x, order, inv), (inv, x.shape[0])), _spread_bwd)


def held_pairs(experts: jnp.ndarray, first: int, held: int):
    """The sort of a routing's pairs for the share [first, first + held).
    Returns (order, inverse, group_sizes, n_here): `order` [F * top_k]
    lists the flat pairs (frame * top_k + choice) with the held ones
    first, by expert; `group_sizes` [held] counts each held expert's
    pairs; `n_here` is their sum."""
    local = experts.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(held, dtype=key.dtype)[None, :], axis=0)
    return order, inverse, sizes.astype(jnp.int32), jnp.sum(sizes).astype(jnp.int32)


def _tiles(m: int, k: int, n: int):
    """Row, contraction and column tile of the grouped-matmul kernel: 512
    rows, and a contraction or column axis whole up to 1,152 and halved
    above (2,304 and 896 are the widths it was sized at: a tile of each
    operand and the float32 accumulator stay under 3 MB)."""
    return min(512, m), k if k <= 1152 else k // 2, n if n <= 1152 else n // 2


def grouped_dot(a: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray, impl: str) -> jnp.ndarray:
    """a [M, K] . w [groups, K, N], row i by the matrix of its group (the
    first sizes[0] rows by w[0], and so on); rows past sum(sizes) are
    left unwritten. Accumulates in float32 and gives a's type, as a dense
    layer does. "ragged_dot": jax.lax.ragged_dot; "megablox": the Pallas
    TPU grouped-matmul kernel that ships with JAX (its work follows the
    row tiles the groups touch, and it keeps its place in the program's
    named scopes, which XLA's own expansion of ragged_dot does not)."""
    if impl == "ragged_dot":
        return jax.lax.ragged_dot(a, w.astype(a.dtype), sizes, preferred_element_type=a.dtype)
    if impl not in ("megablox", "megablox_interpret"):
        raise ValueError(f"unknown moe_impl {impl!r} (auto|ragged_dot|megablox|megablox_interpret)")
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    return megablox.gmm(a, w.astype(a.dtype), sizes, a.dtype, _tiles, None, None, False,
                        impl == "megablox_interpret")


def expert_layer(
    x: jnp.ndarray,  # [F, D] in the compute type
    routing: Routing,
    w_gate: jnp.ndarray,  # [held, D, I]
    w_up: jnp.ndarray,  # [held, D, I]
    w_down: jnp.ndarray,  # [held, I, D]
    first: int,
    impl: str = "ragged_dot",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The held experts' part of sum_e w_e * (silu(x Wg_e) * (x Wu_e)) Wd_e
    for every frame, [F, D] float32, and the pairs per held expert [held]."""
    F, D = x.shape
    top_k = routing.experts.shape[-1]
    held = w_gate.shape[0]
    order, inverse, sizes, n_here = held_pairs(routing.experts, first, held)
    here = (jnp.arange(F * top_k, dtype=jnp.int32) < n_here)[:, None]

    # A row past the held pairs belongs to no group: the products leave it
    # unwritten, so it is zeroed going in and coming out of each.
    dot = lambda a, w: jnp.where(here, grouped_dot(a, w, sizes, impl), 0)
    rows = jnp.where(here, _spread(x, order, inverse), 0)  # each frame's row once per choice, sorted
    out = dot(jax.nn.silu(dot(rows, w_gate)) * dot(rows, w_up), w_down)
    out = _take(out, inverse, order).reshape(F, top_k, D).astype(jnp.float32)
    return jnp.sum(out * routing.weights[..., None], axis=1), sizes
