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
held here come first, in expert order; the rows of a window of them are
gathered once and go through grouped matrix products (`grouped_dot`: on a
TPU a kernel that walks the groups' row tiles), and each frame then
collects the rows its held choices hold. The layer works on the rows it
holds: its buffer has `buffer_rows` rows, the share of the pairs that an
even routing sends to the held experts and a quarter more, and not the
frames x top_k that only a chip holding every expert needs. The
collecting reads that buffer too (forward, and the frames' and weights'
cotangents backward): a scatter-add of each of its rows into its frame,
where a gather of each frame's slots would read all frames x top_k (on a
TPU both cost by the row, not the byte). How many pairs are held is
known on the device alone, so the device counts the passes: the one pass
over the first window of the sorted pairs, where the held pairs fit the
buffer, and where a routing skewed a loop over the further windows that
hold a held one, as many turns as it takes, so no pair is ever dropped.
Pass and loop stand in the forward and again in the backward rule of one
`custom_vjp`. It keeps the first window's gathered rows and its gate and
up products under the checkpoint name PASS_RESIDUALS, and the backward
rule transposes that window from them, computing again the window's
indices from the sort, and the activation and the last product (the
weights' cotangent reads its rows: a buffer's rows that the chip would
otherwise hold through the whole backward pass); the loop's windows it
computes again, from its inputs and the sort, so their intermediates
outlive no turn. The scores, the routing and the sort carry the same
name: a `jax.checkpoint` whose policy saves it (the transformer block's
remat) runs the layer forward once. Within a pass a row past the held
pairs is zeroed, or sent out of bounds, wherever it would be read. Where
the buffer would hold every pair anyway (every expert held, the actor's
step over a batch) there is no loop, no rule of the layer's own and no
name on the pass, which such a remat computes again, and the frames
collect by their slots.

Router product, softmax (or sigmoid) and top-k are float32 at the
highest matmul precision whatever the compute type: which experts a
frame goes to flips on rounding, and a flipped choice is a different
function. The caller may standardise the scores between `router_scores`
and `route` (`standardize`; models/transformer_policy.py ExpertLayer,
cfg.moe_standardize_router). A shared expert, which every chip computes
alike for its own frames, is no part of the share and not here (`Block`).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.layout import Layout, with_layout_constraint


PASS_RESIDUALS = "moe_pass_residuals"  # the checkpoint name of what the layer's backward pass reads


class Routing(NamedTuple):
    experts: jnp.ndarray  # [F, top_k] int32, over all of the model's experts
    weights: jnp.ndarray  # [F, top_k] f32, renormalised over the chosen


def router_scores(x: jnp.ndarray, w_router: jnp.ndarray) -> jnp.ndarray:
    """x [.., D] . w_router [D, E] in float32 at the highest matmul
    precision, whatever the compute type."""
    return checkpoint_name(jnp.matmul(
        x.astype(jnp.float32), w_router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
    ), PASS_RESIDUALS)


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
    return checkpoint_name(diff * jax.lax.rsqrt(squares / n + ROUTER_EPS), PASS_RESIDUALS), (total, squares)


SIGMOID_EPS = 1e-20  # beside the sum that the chosen sigmoid scores are renormalised by


def route(scores: jnp.ndarray, top_k: int, score: str = "softmax",
          bias: Optional[jnp.ndarray] = None, scale: float = 1.0) -> Routing:
    """scores [F, E] float32. `score` "softmax": softmax over all E, the
    `top_k` largest, their probabilities renormalised to sum to one.
    "sigmoid": a sigmoid of each score, the `top_k` largest of sigmoid +
    `bias` [E], the chosen sigmoids (the bias chooses and does not weigh)
    renormalised and times `scale`."""
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown moe_score {score!r} (softmax|sigmoid)")
    if score == "softmax":
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        ranked = probs
    else:
        probs = jax.nn.sigmoid(scores.astype(jnp.float32))
        ranked = probs + bias.astype(jnp.float32)
    _, experts = jax.lax.top_k(jax.lax.stop_gradient(ranked), top_k)
    experts = checkpoint_name(experts.astype(jnp.int32), PASS_RESIDUALS)  # before `chosen` reads it
    # The chosen probabilities by a one-hot sum: its transpose is dense,
    # where top_k's own is a scatter of every pair.
    chosen = experts[..., None] == jnp.arange(probs.shape[-1], dtype=experts.dtype)
    weights = jnp.sum(jnp.where(chosen, probs[..., None, :], 0.0), axis=-1)
    if score == "softmax":
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    else:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + SIGMOID_EPS) * scale
    return Routing(experts, checkpoint_name(weights, PASS_RESIDUALS))


def _rows(a: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    return a.at[idx].get(mode="promise_in_bounds")


def _sum_rows(a: jnp.ndarray, frame: jnp.ndarray, frames: int) -> jnp.ndarray:
    """a [rows, D] -> [frames, D] float32: each frame the sum of the rows
    that name it in `frame` [rows]; a row that names one out of bounds
    (past the held pairs) goes nowhere. Each row is read once."""
    return jnp.zeros((frames, a.shape[1]), jnp.float32).at[frame].add(a.astype(jnp.float32), mode="drop")


@jax.custom_vjp
def _spread(x: jnp.ndarray, frame: jnp.ndarray, slot: jnp.ndarray, taken: Optional[jnp.ndarray]):
    """x [F, D] -> [rows, D]: row i is that of `frame[i]`. Backward: each
    frame sums the rows its held choices point at, where the transpose of
    the gather would be a scatter-add of every row: by the slots (`slot`,
    `taken` [F, top_k]) or, where `taken` is None, by the rows (`slot`
    [rows], each row's frame: `_sum_rows`)."""
    return _rows(x, frame)


def _spread_bwd(res, g):
    slot, taken, frames = res
    if taken is None:
        return _sum_rows(g, slot, frames).astype(g.dtype), None, None, None
    return jnp.sum(jnp.where(taken[..., None], _rows(g, slot), 0), axis=1), None, None, None


_spread.defvjp(lambda x, frame, slot, taken: (_rows(x, frame), (slot, taken, x.shape[0])), _spread_bwd)


@jax.custom_vjp
def _combine(out, weights, pair, slot, taken):
    """out [rows, D] -> [F, D] float32: frame f's held choices j each give
    row slot[f, j] times weights[f, j], summed in choice order; where
    `taken` is None, row i times its pair's weight goes to frame slot[i]
    (`_sum_rows`). Backward, with no array of frames x top_k rows: row i,
    of pair `pair[i]`, takes its frame's cotangent times its weight, and
    the weight's cotangent is that row of the cotangent dotted with row i."""
    if taken is None:
        w = _rows(weights.reshape(-1), pair)[:, None]
        return _sum_rows(out.astype(jnp.float32) * w, slot, weights.shape[0])
    picked = jnp.where(taken[..., None], _rows(out, slot), 0).astype(jnp.float32)
    return jnp.sum(picked * weights[..., None], axis=1)


def _combine_bwd(res, dy):
    out, weights, pair, slot, taken = res
    frames, top_k = weights.shape
    if taken is None:
        live = (slot < frames)[:, None]
    else:
        live = _rows(taken.reshape(-1), pair)[:, None]  # row i holds a held pair
    dy_rows = _rows(dy, pair // top_k)
    d_out = jnp.where(live, dy_rows * _rows(weights.reshape(-1), pair)[:, None], 0).astype(out.dtype)
    dots = jnp.sum(jnp.where(live, dy_rows * out.astype(jnp.float32), 0), axis=-1)
    if taken is None:  # each held pair's weight once: the rows past them out of bounds, each its own
        to = jnp.where(live[:, 0], pair, weights.size + jnp.arange(pair.shape[0], dtype=pair.dtype))
        d_w = jnp.zeros(weights.size, dots.dtype).at[to].set(dots, mode="drop", unique_indices=True)
        return d_out, d_w.reshape(weights.shape), None, None, None
    return d_out, jnp.where(taken, _rows(dots, slot), 0), None, None, None


_combine.defvjp(lambda *a: (_combine(*a), a), _combine_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def by_expert(w: jnp.ndarray, dtype) -> jnp.ndarray:
    """A float32 parameter kept input axis first [D, held, I], as the
    products take it: `dtype` and [held, D, I]. Backward: the gradient
    swapped back, in float32 and held to the parameter's own row-major
    layout. The products' backward kernel writes it by expert, the swap
    back is free, and the optimizer's update would otherwise run in that
    order and copy each of its outputs (the parameter and both moments)
    back into the parameter's."""
    return jnp.swapaxes(w.astype(dtype), 0, 1)


def _by_expert_bwd(dtype, _, g):
    g = jnp.swapaxes(g, 0, 1).astype(jnp.float32)
    return (with_layout_constraint(g, Layout(major_to_minor=tuple(range(g.ndim)))),)


by_expert.defvjp(lambda w, dtype: (by_expert(w, dtype), None), _by_expert_bwd)


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


HEADROOM = 1.25  # the buffer's rows over the pairs that an even routing sends to the held experts


def buffer_rows(pairs: int, held: int, experts: int) -> int:
    """Rows of the buffer that `held` of `experts` experts' share of
    `pairs` pairs is computed in: that share with HEADROOM, in whole row
    tiles of the grouped-product kernel, and never more than the pairs
    (every expert held, or a few frames: the buffer of every pair, and
    no choice is compiled)."""
    tile = _tiles(pairs, 0, 0)[0]
    return min(pairs, math.ceil(pairs * held * HEADROOM / (experts * tile)) * tile)


def _window(rows: int, weights, order, inverse, sizes, n_here, start):
    """Of `rows` of the sorted pairs from `start` on (`order` reaches that
    far): each row's pair, the held experts' pairs among them, which rows
    hold a held pair, and where each frame collects its part. In a buffer
    of fewer rows than the pairs each frame collects from the rows (a
    scatter-add of each row into its frame), in the buffer of every pair
    by its frames x top_k slots."""
    pair = jax.lax.dynamic_slice_in_dim(order, start, rows)
    ends = jnp.cumsum(sizes)
    window = lambda at: jnp.clip(at, start, start + rows)
    sizes = window(ends) - window(ends - sizes)  # of each held expert's pairs, those in the window
    here = (jnp.arange(rows, dtype=jnp.int32) < n_here - start)[:, None]
    if rows < weights.size:  # row i to its pair's frame, a row past the held pairs out of bounds
        slot, taken = jnp.where(here[:, 0], pair // weights.shape[1], weights.shape[0]), None
    else:  # each frame's choices where the sort put them
        slot, taken = inverse.reshape(weights.shape), (inverse < n_here).reshape(weights.shape)
    return pair, sizes, here, slot, taken


def _first_two(impl: str, x, weights, w_gate, w_up, pair, sizes, here, slot, taken):
    """Each frame's row once per held choice, sorted, and its gate and up
    products."""
    a = _spread(x, pair // weights.shape[1], slot, taken)
    return a, grouped_dot(a, w_gate, sizes, impl), grouped_dot(a, w_up, sizes, impl)


def _last(impl: str, gate, up, weights, w_down, pair, sizes, here, slot, taken):
    """A pass from its first two products on: the activation, the last
    product, and each frame collecting its part. A row past the held pairs
    belongs to no group and the products leave it unwritten, so where such
    a row is read it is zeroed first: after the two products that feed
    arithmetic. The last product's rows go to `_combine`, which reads the
    held ones alone, as `_spread`'s backward pass does of the first two
    products' cotangent."""
    act = jax.nn.silu(jnp.where(here, gate, 0)) * jnp.where(here, up, 0)
    return _combine(grouped_dot(act, w_down, sizes, impl), weights, pair, slot, taken)


def _pass(rows: int, impl: str, x, weights, w_gate, w_up, w_down, order, inverse, sizes, n_here, start=0):
    """The layer's part from `rows` of the sorted pairs, from `start` on:
    all of it where the held pairs end before `start + rows`."""
    window = _window(rows, weights, order, inverse, sizes, n_here, start)
    _, gate, up = _first_two(impl, x, weights, w_gate, w_up, *window)
    return _last(impl, gate, up, weights, w_down, *window)


def _loop(rows: int, n_here, one, first):
    """`first`, the part of the first window of `rows` sorted pairs, and
    `one(start)` of each further window that holds a held pair, summed in
    window order: a loop on the device that takes no turn where the held
    pairs fit the buffer, and as many as they reach into where a routing
    skewed."""
    turn = lambda c: (c[0] + rows, jax.tree.map(jnp.add, c[1], one(c[0])))
    return jax.lax.while_loop(lambda c: c[0] < n_here, turn, (jnp.int32(rows), first))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _windowed(rows: int, impl: str, *args):
    """`_pass` in a buffer of `rows` rows, over as many windows of the
    sorted pairs as the held ones reach into. One differentiation rule
    around the loop, so nothing differentiates through `while_loop`: its
    forward keeps the first window's gathered rows and its gate and up
    products, under the checkpoint name PASS_RESIDUALS, and its backward
    transposes that window from them and walks the further ones again,
    differentiating each pass where it runs."""
    one = lambda start: _pass(rows, impl, *args, start)
    return _loop(rows, args[-1], one, one(0))


def _windowed_fwd(rows, impl, *args):
    x, weights, w_gate, w_up, w_down, *sort = args
    window = _window(rows, weights, *sort, 0)
    kept = checkpoint_name(_first_two(impl, x, weights, w_gate, w_up, *window), PASS_RESIDUALS)
    first = _last(impl, *kept[1:], weights, w_down, *window)
    return _loop(rows, sort[-1], lambda start: _pass(rows, impl, *args, start), first), (args, kept)


def _windowed_bwd(rows, impl, res, dy):
    args, (a, gate, up) = res
    (x, weights, w_gate, w_up, w_down), sort = args[:5], args[5:]
    window = _window(rows, weights, *sort, 0)
    # The activation and the last product are computed again: the
    # weights' cotangent reads the last product's rows, which kept would
    # be one more buffer of the model's width a layer, held through the
    # whole backward pass.
    d_gate, d_up, d_weights, d_down = jax.vjp(lambda *v: _last(impl, *v, *window), gate, up, weights, w_down)[1](dy)
    products = lambda a, w_gate, w_up: (grouped_dot(a, w_gate, window[1], impl), grouped_dot(a, w_up, window[1], impl))
    d_a, d_w_gate, d_w_up = jax.vjp(products, a, w_gate, w_up)[1]((d_gate, d_up))
    d_x, = jax.vjp(lambda x: _spread(x, window[0] // weights.shape[1], *window[3:]), x)[1](d_a)

    def grads(start):
        _, vjp = jax.vjp(lambda *floats: _pass(rows, impl, *floats, *sort, start), *args[:5])
        return vjp(dy)

    first = (d_x, d_weights, d_w_gate, d_w_up, d_down)
    return _loop(rows, sort[-1], grads, first) + (None,) * len(sort)


_windowed.defvjp(_windowed_fwd, _windowed_bwd)


def expert_layer(
    x: jnp.ndarray,  # [F, D] in the compute type
    routing: Routing,
    w_gate: jnp.ndarray,  # [held, D, I]
    w_up: jnp.ndarray,  # [held, D, I]
    w_down: jnp.ndarray,  # [held, I, D]
    first: int,
    impl: str = "ragged_dot",
    rows: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The held experts' part of sum_e w_e * (silu(x Wg_e) * (x Wu_e)) Wd_e
    for every frame, [F, D] float32; the pairs per held expert [held]; the
    passes it took over a buffer of `rows` rows (`buffer_rows`; None or
    frames x top_k: one pass over the buffer of every pair); and the rows
    its collecting read in them, forward (the buffer's where it has fewer
    than the pairs, else every slot). With a smaller buffer the backward
    pass transposes the first window from what its forward kept (the
    activation and the last product computed again), and computes the
    whole forward again only in the windows past it, where a routing
    skewed."""
    pairs = routing.experts.size
    order, inverse, sizes, n_here = checkpoint_name(held_pairs(routing.experts, first, w_gate.shape[0]),
                                                    PASS_RESIDUALS)
    floats = (x, routing.weights, w_gate, w_up, w_down)
    if rows is None or rows >= pairs:
        y = _pass(pairs, impl, *floats, order, inverse, sizes, n_here)
        return y, sizes, jnp.float32(1.0), jnp.float32(pairs)
    order = jnp.pad(order, (0, -pairs % rows))  # the last window whole; past the held pairs whatever it holds
    passes = jnp.ceil(n_here / rows)
    return _windowed(rows, impl, *floats, order, inverse, sizes, n_here), sizes, passes, passes * rows
