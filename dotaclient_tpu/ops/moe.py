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
collects the rows its choices point at. The layer works on the rows it
holds: its buffer has `buffer_rows` rows, the share of the pairs that an
even routing sends to the held experts and a quarter more, and not the
frames x top_k that only a chip holding every expert needs. How many
pairs are held is known on the device alone, so the device chooses: the
one pass where they fit the buffer, and where a routing skewed a loop
over the windows of the sorted pairs that hold a held one, as many turns
as it takes, so no pair is ever dropped. Choice and loop stand in the
forward and again in the backward rule of one `custom_vjp`, which keeps
nothing but its inputs and the sort, so no pass's intermediates outlive
it. Within a pass a row past the held pairs is zeroed wherever it would
be read. Where the buffer would hold every pair anyway (every expert
held, the actor's step over a batch) there is neither choice nor loop.

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
from jax.experimental.layout import Layout, with_layout_constraint


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
    # The chosen probabilities by a one-hot sum: its transpose is dense,
    # where top_k's own is a scatter of every pair.
    chosen = experts[..., None] == jnp.arange(probs.shape[-1], dtype=experts.dtype)
    weights = jnp.sum(jnp.where(chosen, probs[..., None, :], 0.0), axis=-1)
    if score == "softmax":
        return Routing(experts.astype(jnp.int32), weights / jnp.sum(weights, axis=-1, keepdims=True))
    total = jnp.sum(weights, axis=-1, keepdims=True) + SIGMOID_EPS
    return Routing(experts.astype(jnp.int32), weights / total * scale)


def _rows(a: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    return a.at[idx].get(mode="promise_in_bounds")


@jax.custom_vjp
def _spread(x: jnp.ndarray, frame: jnp.ndarray, slot: jnp.ndarray, taken: jnp.ndarray) -> jnp.ndarray:
    """x [F, D] -> [rows, D]: row i is that of `frame[i]`. Backward: each
    frame sums the rows its taken choices point at (`slot`, `taken`
    [F, top_k]), where the transpose of the gather would be a scatter-add,
    row by row."""
    return _rows(x, frame)


def _spread_bwd(res, g):
    slot, taken = res
    return jnp.sum(jnp.where(taken[..., None], _rows(g, slot), 0), axis=1), None, None, None


_spread.defvjp(lambda x, frame, slot, taken: (_rows(x, frame), (slot, taken)), _spread_bwd)


@jax.custom_vjp
def _combine(out, weights, pair, slot, taken):
    """out [rows, D] -> [F, D] float32: frame f's taken choices j each give
    row slot[f, j] times weights[f, j], summed in choice order. Backward,
    with no array of frames x top_k rows: row i, of pair `pair[i]`, takes
    its frame's cotangent times its weight, and the weight's cotangent is
    that row of the cotangent dotted with row i."""
    picked = jnp.where(taken[..., None], _rows(out, slot), 0).astype(jnp.float32)
    return jnp.sum(picked * weights[..., None], axis=1)


def _combine_bwd(res, dy):
    out, weights, pair, slot, taken = res
    live = _rows(taken.reshape(-1), pair)[:, None]  # row i holds a held pair
    dy_rows = _rows(dy, pair // weights.shape[1])
    d_out = jnp.where(live, dy_rows * _rows(weights.reshape(-1), pair)[:, None], 0).astype(out.dtype)
    dots = jnp.sum(jnp.where(live, dy_rows * out.astype(jnp.float32), 0), axis=-1)
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


def _pass(rows: int, impl: str, x, weights, w_gate, w_up, w_down, order, inverse, sizes, n_here, start=0):
    """The layer's part from `rows` of the sorted pairs, from `start` on:
    all of it where the held pairs end before `start + rows`. `order`
    reaches that far."""
    top_k = weights.shape[1]
    pair = jax.lax.dynamic_slice_in_dim(order, start, rows)
    ends = jnp.cumsum(sizes)
    window = lambda at: jnp.clip(at, start, start + rows)
    sizes = window(ends) - window(ends - sizes)  # of each held expert's pairs, those in the window
    at = inverse - start  # of each frame's choices, in the buffer
    inside = (at >= 0) & (at < rows)
    # A choice outside the window still reads a row (the gathers read every
    # slot): one of its own, because a hundred thousand reads of one row
    # queue on it (0.8 ms a gather on a v5e).
    slot = jnp.where(inside, at, jnp.arange(at.shape[0], dtype=at.dtype) % rows).reshape(weights.shape)
    taken = (inside & (inverse < n_here)).reshape(weights.shape)
    here = (jnp.arange(rows, dtype=jnp.int32) < n_here - start)[:, None]

    # A row past the held pairs belongs to no group and the products leave
    # it unwritten, so where such a row is read it is zeroed first: after
    # the two products that feed arithmetic. The last product's rows go to
    # `_combine`, which reads the held ones alone, as `_spread`'s backward
    # pass does of the first two products' cotangent.
    dot = lambda a, w: jnp.where(here, grouped_dot(a, w, sizes, impl), 0)
    a = _spread(x, pair // top_k, slot, taken)  # each frame's row once per held choice, sorted
    out = grouped_dot(jax.nn.silu(dot(a, w_gate)) * dot(a, w_up), w_down, sizes, impl)
    return _combine(out, weights, pair, slot, taken)


def _passes(rows: int, n_here, one, zero):
    """The sum of `one(start)` over the windows of `rows` sorted pairs that
    hold a held pair, chosen on the device: the one pass bare where the
    held pairs fit the buffer, and else a loop that adds up its turns from
    `zero`."""
    turn = lambda c: (c[0] + rows, jax.tree.map(jnp.add, c[1], one(c[0])))
    loop = lambda: jax.lax.while_loop(lambda c: c[0] < n_here, turn, (jnp.int32(0), zero))[1]
    return jax.lax.cond(n_here <= rows, lambda: one(0), loop)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _windowed(rows: int, impl: str, *args):
    """`_pass` in a buffer of `rows` rows, over as many windows of the
    sorted pairs as the held ones reach into. One differentiation rule
    around the choice and the loop: its backward rule has the inputs and
    the sort, walks the same windows and differentiates each pass where it
    runs, so nothing differentiates through `cond` or `while_loop` and no
    pass's intermediates outlive it."""
    x, weights, *_, n_here = args
    return _passes(rows, n_here, lambda start: _pass(rows, impl, *args, start),
                   jnp.zeros((weights.shape[0], x.shape[1]), jnp.float32))


def _windowed_bwd(rows, impl, args, dy):
    floats, sort = args[:5], args[5:]

    def grads(start):
        _, vjp = jax.vjp(lambda *floats: _pass(rows, impl, *floats, *sort, start), *floats)
        return vjp(dy)

    return _passes(rows, sort[-1], grads, tuple(map(jnp.zeros_like, floats))) + (None,) * len(sort)


_windowed.defvjp(lambda rows, impl, *args: (_windowed(rows, impl, *args), args), _windowed_bwd)


def expert_layer(
    x: jnp.ndarray,  # [F, D] in the compute type
    routing: Routing,
    w_gate: jnp.ndarray,  # [held, D, I]
    w_up: jnp.ndarray,  # [held, D, I]
    w_down: jnp.ndarray,  # [held, I, D]
    first: int,
    impl: str = "ragged_dot",
    rows: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The held experts' part of sum_e w_e * (silu(x Wg_e) * (x Wu_e)) Wd_e
    for every frame, [F, D] float32; the pairs per held expert [held]; and
    the passes it took over a buffer of `rows` rows (`buffer_rows`; None
    or frames x top_k: one pass over the buffer of every pair). With a
    smaller buffer the backward pass computes the forward again."""
    pairs = routing.experts.size
    order, inverse, sizes, n_here = held_pairs(routing.experts, first, w_gate.shape[0])
    floats = (x, routing.weights, w_gate, w_up, w_down)
    if rows is None or rows >= pairs:
        return _pass(pairs, impl, *floats, order, inverse, sizes, n_here), sizes, jnp.float32(1.0)
    order = jnp.pad(order, (0, -pairs % rows))  # the last window whole; past the held pairs whatever it holds
    return _windowed(rows, impl, *floats, order, inverse, sizes, n_here), sizes, jnp.ceil(n_here / rows)
