"""The routed-expert layer (ops/moe.py): the router against its written
equations, a share's partial sum against a loop over its experts, in a
buffer of every pair (each frame collecting by its slots) and in smaller
ones that take one pass or several (collecting from their rows), the
shares of an expert-parallel group adding up to the whole layer, no
pair dropped however skewed the routing, and the sigmoid router whose
per-expert bias chooses and does not weigh, with the shared expert
counted once beside the eight shares."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dotaclient_tpu.ops import attention as A
from dotaclient_tpu.ops import gated_delta as GD
from dotaclient_tpu.ops import moe

F, D, E, I, K = 37, 16, 8, 12, 3


def _weights(seed=0):
    r = np.random.RandomState(seed)
    f32 = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)
    return (f32(F, D), f32(D, E), f32(E, D, I) / 4, f32(E, D, I) / 4, f32(E, I, D) / 4)


def _written(x, wr, wg, wu, wd, lo, hi, top_k=K):
    """The layer as its equations read: every frame through every held
    expert, weighted by the renormalised probability where it was chosen."""
    p = jax.nn.softmax(x @ wr, axis=-1)
    w, chosen = jax.lax.top_k(p, top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(lo, hi):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        y = y + w_e[:, None] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return y


def _program(x, wr, wg, wu, wd, lo, hi, top_k=K, impl="ragged_dot", rows=None):
    routing = moe.route(moe.router_scores(x, wr), top_k)
    return moe.expert_layer(x, routing, wg[lo:hi], wu[lo:hi], wd[lo:hi], lo, impl, rows)


def test_router_against_its_written_equations():
    x, wr = _weights()[:2]
    got = moe.route(moe.router_scores(x, wr), K)
    p = np.asarray(jax.nn.softmax(np.asarray(x, np.float64) @ np.asarray(wr, np.float64), axis=-1))
    want = np.argsort(-p, axis=-1)[:, :K]
    assert (np.sort(np.asarray(got.experts), -1) == np.sort(want, -1)).all()
    w = np.take_along_axis(p, np.asarray(got.experts), -1)
    np.testing.assert_allclose(got.weights, w / w.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got.weights).sum(-1), 1.0, rtol=1e-6)
    assert got.experts.dtype == jnp.int32 and got.weights.dtype == jnp.float32
    # float32 whatever the caller's type: a bfloat16 residual is scored as float32
    half = moe.route(moe.router_scores(x.astype(jnp.bfloat16), wr), K)
    again = moe.route(moe.router_scores(x.astype(jnp.bfloat16).astype(jnp.float32), wr), K)
    assert (half.experts == again.experts).all() and half.weights.dtype == jnp.float32


def _against_the_written_layer(args, lo, hi, **how):
    """The program's forward and its gradients of x, the router and the
    three matrices against the written layer's; returns the program's
    counts and gradients."""
    got, *counts = jax.jit(lambda *a: _program(*a, lo, hi, **how))(*args)
    np.testing.assert_allclose(got, _written(*args, lo, hi), rtol=1e-5, atol=1e-5)
    g_got = jax.grad(lambda *a: jnp.sum(jnp.sin(_program(*a, lo, hi, **how)[0])), argnums=(0, 1, 2, 3, 4))(*args)
    g_want = jax.grad(lambda *a: jnp.sum(jnp.sin(_written(*a, lo, hi))), argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    return counts, g_got


@pytest.mark.parametrize("lo,hi", [(0, 8), (2, 4), (6, 8)])
def test_a_share_against_the_loop_over_its_experts(lo, hi):
    args = _weights(1)
    (sizes, *_), g_got = _against_the_written_layer(args, lo, hi)
    chosen = np.asarray(moe.route(moe.router_scores(args[0], args[1]), K).experts)
    assert sizes.tolist() == [(chosen == e).sum() for e in range(lo, hi)]
    # experts outside the share get no gradient from it
    assert not np.asarray(g_got[2][:lo]).any() and not np.asarray(g_got[2][hi:]).any()


# Experts 2 and 3 hold 32 of the 111 pairs (seed 1). Rows of the buffer: every pair (one pass, neither
# choice nor loop compiled); above the held pairs (the bare pass); exactly the held pairs; under them (two
# turns of the loop); windows that cut through both groups (five turns); and the kernel over two windows.
@pytest.mark.parametrize("rows,impl,passes", [
    (None, "ragged_dot", 1), (F * K, "ragged_dot", 1), (64, "ragged_dot", 1), (32, "ragged_dot", 1),
    (16, "ragged_dot", 2), (7, "ragged_dot", 5), (16, "megablox_interpret", 2),
])
def test_a_buffer_of_fewer_rows_against_the_loop_over_its_experts(rows, impl, passes):
    """Forward and the gradients of x, the router and the three matrices
    are the written layer's whatever the buffer's rows: where the held
    pairs overflow it the passes go on over the next windows. The
    collecting reads the buffer's rows a pass, or every frame x top_k slot
    in the buffer of every pair."""
    args = _weights(1)
    (sizes, took, read), _ = _against_the_written_layer(args, 2, 4, impl=impl, rows=rows)
    assert int(sizes.sum()) == 32 and float(took) == passes
    small = rows is not None and rows < F * K
    assert float(read) == passes * (rows if small else F * K)
    # the smaller buffer holds a loop on the device, the buffer of every pair does not
    text = str(jax.make_jaxpr(lambda *a: _program(*a, 2, 4, rows=rows)[0])(*args))
    assert ("while" in text) == (rows is not None and rows < F * K)


# The names the transformer block's remat saves where a layer has routed
# experts (models/transformer_policy.py TransformerCore).
BLOCK_NAMES = (A.FUSED_RESIDUALS, GD.RULE_RESIDUALS, moe.PASS_RESIDUALS)


def _ops(jaxpr, name, looped=False):
    """[outside any loop, inside one]: the equations of primitive `name`
    in a jaxpr and the programs under it whose results something reads
    (a `jax.vjp` computes its function forward, and where only the
    transpose is read the compiler drops that)."""
    found = [0, 0]
    for eqn in jaxpr.eqns:
        read = not all(isinstance(v, jax.core.DropVar) for v in eqn.outvars)
        found[looped] += eqn.primitive.name == name and read
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found = [a + b for a, b in zip(found, _ops(sub, name, looped or eqn.primitive.name == "while"))]
    return found


def _checkpointed_loss(names, rows):
    layer = lambda *a: _program(*a, 2, 4, rows=rows)[0]
    kept = jax.checkpoint(layer, policy=jax.checkpoint_policies.save_only_these_names(*names))
    return lambda *a: jnp.sum(jnp.sin(kept(*a))), layer


def _checkpointed_gradient(names, rows, args):
    loss, layer = _checkpointed_loss(names, rows)
    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)), layer


@pytest.mark.parametrize("rows,passes", [(64, 1), (32, 1), (16, 2), (7, 5)])
def test_under_the_blocks_remat_the_layer_runs_forward_once(rows, passes):
    """Under `jax.checkpoint` with the block's policy the layer's value and
    the gradients of x, the router and the three matrices are the
    un-checkpointed layer's (to a rounding where the router's backward
    reads the forward's scores and routing and not their recomputation)
    and the written layer's, where the held pairs fit the buffer and where
    they overflow it. Outside the loop over further windows the gradient's
    program holds the first window's three grouped products forward, the
    last of them once more (its rows, which the weights' cotangent reads,
    are computed again and not kept), their six transposes, and one router
    product, top_k and sort, the forward's: the remat runs none of them
    again. Where the held pairs overflow, the loop computes each further
    window forward (three products), and in the backward rule again with
    its transposes (nine)."""
    args = _weights(1)
    kept, layer = _checkpointed_gradient(BLOCK_NAMES, rows, args)
    bare = jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(layer(*a))), argnums=(0, 1, 2, 3, 4))
    written = jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(_written(*a, 2, 4))), argnums=(0, 1, 2, 3, 4))
    got, want, exact = (jax.tree.leaves(jax.jit(f)(*args)) for f in (kept, written, bare))
    for a, b, c in zip(got, want, exact):
        assert np.abs(np.asarray(a) - np.asarray(c)).max() <= 1e-6 * np.abs(np.asarray(c)).max()
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert float(_program(*args, 2, 4, rows=rows)[2]) == passes
    jaxpr = jax.make_jaxpr(kept)(*args).jaxpr
    assert _ops(jaxpr, "ragged_dot_general") == [3 + 1 + 6, 3 + 9]
    assert [_ops(jaxpr, name)[0] for name in ("dot_general", "top_k", "sort")] == [1 + 2, 1, 2]


@pytest.mark.parametrize("names,again", [(BLOCK_NAMES, 0), (BLOCK_NAMES[:2], 1)])
def test_a_remat_that_leaves_the_name_out_runs_the_pass_again(names, again):
    """The name is what keeps them: with it left out of the policy the
    remat computes the first window's gate and up products, the router
    product, top_k and the sort of the pairs a second time (the twelve
    products a pass that the layer took before it kept them)."""
    kept, _ = _checkpointed_gradient(names, 32, _weights(1))
    jaxpr = jax.make_jaxpr(kept)(*_weights(1)).jaxpr
    assert _ops(jaxpr, "ragged_dot_general")[0] == 10 + 2 * again
    assert [_ops(jaxpr, name)[0] for name in ("dot_general", "top_k", "sort")] == [3 + again, 1 + again, 2 + again]


@pytest.mark.parametrize("rows", [32, 16])
def test_under_the_blocks_remat_the_layer_keeps_its_buffers_rows_and_no_matrix(rows, capsys):
    """What the block's remat keeps of the layer, besides its inputs: the
    scores, the routing and the sort, and of the buffer's rows three
    arrays, the gathered rows and the gate and up products. No expert
    matrix (its cast to the compute type is computed again) and not the
    last product's rows."""
    jax.ad_checkpoint.print_saved_residuals(_checkpointed_loss(BLOCK_NAMES, rows)[0], *_weights(1))
    saved = [line.split(" ", 1) for line in capsys.readouterr().out.splitlines()]
    shapes = [shape for shape, source in saved if not source.startswith("from the argument")]
    assert sorted(s for s in shapes if s.startswith(f"f32[{rows},")) == [f"f32[{rows},{I}]"] * 2 + [f"f32[{rows},{D}]"]
    assert all(s.count(",") < 2 for s in shapes), shapes


def test_the_buffers_rows_follow_the_shapes():
    """The share of the pairs an even routing sends here and a quarter
    more, in whole row tiles: every pair for the uncut layer, for the
    actor's step over a batch and for the small shapes of these tests;
    40,960 of 131,072 in the benchmark's cell."""
    assert moe.buffer_rows(16384 * 8, 16, 64) == 40960
    assert moe.buffer_rows(16384 * 8, 64, 64) == 16384 * 8  # every expert held
    assert moe.buffer_rows(16 * 8, 16, 64) == 16 * 8  # step mode: the frames are the batch
    assert moe.buffer_rows(F * K, 2, E) == F * K
    assert moe.buffer_rows(2048, 2, 8) == 1024 and moe.buffer_rows(2048, 1, 8) == 512
    for pairs in (512, 1000, 4096, 30000, 131072):
        for held, experts in ((1, 8), (3, 8), (16, 64), (48, 64)):
            rows = moe.buffer_rows(pairs, held, experts)
            assert rows == pairs or (rows % 512 == 0 and pairs * held / experts * moe.HEADROOM <= rows < pairs)


def test_the_four_shares_add_up_to_the_uncut_layer():
    args = _weights(2)
    whole = _written(*args, 0, E)
    parts = [_program(*args, lo, lo + 2) for lo in range(0, E, 2)]
    np.testing.assert_allclose(sum(p[0] for p in parts), whole, rtol=1e-5, atol=1e-5)
    assert sum(int(p[1].sum()) for p in parts) == F * K  # every pair computed once, by one share


@pytest.mark.parametrize("rows", [None, 8])
def test_dropless_under_skew(rows):
    """Every frame to one expert: a share that gets every pair computes
    every pair, in the buffer of frames x top_k rows at once and in a
    small one by as many passes as it takes."""
    x, _, wg, wu, wd = _weights(3)
    wr = jnp.zeros((D, E)).at[:, 5].set(0.0)
    x = jnp.abs(x)
    wr = wr.at[:, 5].set(4.0)  # expert 5 first for every frame, by a wide margin
    got, sizes, passes, _ = _program(x, wr, wg, wu, wd, 4, 6, top_k=1, rows=rows)
    assert sizes.tolist() == [0, F] and float(passes) == (5 if rows else 1)
    np.testing.assert_allclose(got, (jax.nn.silu(x @ wg[5]) * (x @ wu[5])) @ wd[5], rtol=1e-5, atol=1e-5)
    none, sizes, passes, _ = _program(x, wr, wg, wu, wd, 0, 4, top_k=1, rows=rows)
    assert sizes.tolist() == [0, 0, 0, 0] and not np.asarray(none).any() and float(passes) == (0 if rows else 1)
    # all top_k choices of every frame held here: the buffer is full
    full, sizes, passes, _ = _program(*_weights(3), 0, E, top_k=E, rows=rows)
    assert int(sizes.sum()) == F * E and float(passes) == (F * E // rows if rows else 1)
    np.testing.assert_allclose(full, _written(*_weights(3), 0, E, top_k=E), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rows", [None, 16, 64])
def test_a_frame_that_holds_no_choice_gets_exact_zeros(rows):
    """Frames none of whose choices is held here: their part of the sum,
    their cotangent and their weights' cotangent are exact zeros, over
    several windows and in the bare pass, collecting from the buffer's
    rows, and by the slots in the buffer of every pair; the frames that
    hold one get some. Two calls give the same bits."""
    x, wr, wg, wu, wd = _weights(1)
    routing = moe.route(moe.router_scores(x, wr), K)
    held = np.isin(np.asarray(routing.experts), [2, 3])
    assert held.any(1).any() and not held.any(1).all()

    def layer(x, weights):
        y = moe.expert_layer(x, moe.Routing(routing.experts, weights), wg[2:4], wu[2:4], wd[2:4], 2, rows=rows)[0]
        return jnp.sum(jnp.sin(y)), y

    run = jax.jit(jax.grad(layer, argnums=(0, 1), has_aux=True))
    (dx, dw), y = run(x, routing.weights)
    none = ~held.any(1)
    assert not np.asarray(y)[none].any() and not np.asarray(dx)[none].any() and not np.asarray(dw)[~held].any()
    assert np.asarray(y)[~none].any(1).all() and np.asarray(dx)[~none].any(1).all() and np.asarray(dw)[held].all()
    for a, b in zip(run(x, routing.weights), ((dx, dw), y)):
        for p, q in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert np.asarray(p).tobytes() == np.asarray(q).tobytes()


def test_the_sort_puts_the_held_pairs_first_by_expert():
    experts = jnp.asarray([[0, 3], [2, 1], [3, 2], [1, 0]], jnp.int32)
    order, inverse, sizes, n_here = moe.held_pairs(experts, 1, 2)
    assert sizes.tolist() == [2, 2] and int(n_here) == 4
    flat = np.asarray(experts).reshape(-1)
    assert flat[np.asarray(order)[:4]].tolist() == [1, 1, 2, 2]
    assert (np.asarray(order)[np.asarray(inverse)] == np.arange(8)).all()


def test_the_kernel_and_ragged_dot_give_the_same_layer():
    """The Pallas grouped-matmul kernel in interpret mode against
    jax.lax.ragged_dot, forward and backward, at sizes its tiles do not
    divide (a ragged last tile, empty groups, rows past the groups)."""
    args = _weights(4)
    x, wr, wg, wu, wd = args
    routing = moe.route(moe.router_scores(x, wr), K)
    want, sizes, *_ = moe.expert_layer(x, routing, wg[1:5], wu[1:5], wd[1:5], 1, "ragged_dot")
    got, sizes_k, *_ = moe.expert_layer(x, routing, wg[1:5], wu[1:5], wd[1:5], 1, "megablox_interpret")
    assert sizes.tolist() == sizes_k.tolist()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    grads = [jax.grad(lambda x, wg, wd: jnp.sum(jnp.sin(
        moe.expert_layer(x, routing, wg[1:5], wu[1:5], wd[1:5], 1, impl)[0])), argnums=(0, 1, 2))(x, wg, wd)
        for impl in ("ragged_dot", "megablox_interpret")]
    for a, b in zip(*grads):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="moe_impl"):
        moe.grouped_dot(x, wg, sizes, "dense")


def test_standardised_scores_unroll_step_and_equations():
    """Causal: frame t's standardised score uses frames 0..t only, the
    step's carried sums give what the unroll gives, and a large constant
    that every frame's score shares goes without costing digits."""
    r = np.random.RandomState(5)
    T, E = 9, 4
    s = (r.randn(2, T, E) * 0.01 + 50.0 * r.randn(1, 1, E)).astype(np.float32)
    got, _ = moe.standardize(jnp.asarray(s), None, jnp.arange(1, T + 1, dtype=jnp.float32), axis=1)
    s64 = s.astype(np.float64)
    n = np.arange(1, T + 1)[None, :, None]
    diff = s64 - np.cumsum(s64, 1) / n
    want = diff / np.sqrt(np.cumsum(diff * diff, 1) / n + moe.ROUTER_EPS)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    assert not np.asarray(got[:, 0]).any()  # the first frame has nothing to differ from
    assert np.abs(np.asarray(got)[:, 2:]).max() < 3.0 and np.abs(np.asarray(got)[:, 2:]).mean() > 0.3
    seen = (jnp.zeros((2, E)), jnp.zeros((2, E)))
    for t in range(T):
        one, seen = moe.standardize(jnp.asarray(s[:, t]), seen, jnp.full((2, 1), t + 1.0))
        np.testing.assert_allclose(one, got[:, t], rtol=1e-4, atol=1e-5)
    later = s.copy()
    later[:, 5:] += 1.0
    again, _ = moe.standardize(jnp.asarray(later), None, jnp.arange(1, T + 1, dtype=jnp.float32), axis=1)
    np.testing.assert_array_equal(np.asarray(again)[:, :5], np.asarray(got)[:, :5])


def _sigmoid_written(scores, bias, top_k, scale, eps=1e-20):
    """The sigmoid router as its equations read, in float64: s = sigmoid,
    the top_k largest of s + b, w = scale * s_chosen / (sum + eps), eps
    1e-20 unless given."""
    s = 1.0 / (1.0 + np.exp(-np.asarray(scores, np.float64)))
    chosen = np.argsort(-(s + np.asarray(bias, np.float64)), axis=-1)[:, :top_k]
    picked = np.take_along_axis(s, chosen, -1)
    return chosen, scale * picked / (picked.sum(-1, keepdims=True) + eps)


def _by_expert(experts, weights, n):
    """[F, n]: each expert's weight for each frame, 0 where not chosen."""
    out = np.zeros((experts.shape[0], n))
    np.put_along_axis(out, np.asarray(experts), np.asarray(weights, np.float64), -1)
    return out


def test_the_sigmoid_router_against_its_written_equations():
    x, wr = _weights(6)[:2]
    scores = moe.router_scores(x, wr)
    bias = jnp.asarray(np.random.RandomState(6).randn(E) * 0.2, jnp.float32)
    got = moe.route(scores, K, "sigmoid", bias, 1.8)
    chosen, w = _sigmoid_written(scores, bias, K, 1.8)
    assert (np.sort(np.asarray(got.experts), -1) == np.sort(chosen, -1)).all()
    np.testing.assert_allclose(_by_expert(got.experts, got.weights, E), _by_expert(chosen, w, E), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got.weights).sum(-1), 1.8, rtol=1e-5)  # renormalised, then scaled
    assert got.experts.dtype == jnp.int32 and got.weights.dtype == jnp.float32
    # a zero bias is the sigmoid form still, not the softmax one
    zero = moe.route(scores, K, "sigmoid", jnp.zeros(E), 1.0)
    soft = moe.route(scores, K)
    assert (np.sort(zero.experts, -1) == np.sort(soft.experts, -1)).all()  # both monotone in the score
    assert np.abs(np.asarray(zero.weights) - np.asarray(soft.weights)).max() > 1e-3


def test_the_bias_changes_the_choice_and_not_the_weights():
    """An expert pushed by the bias is chosen where its score alone would
    not be; its weight, and every chosen expert's, is still its sigmoid
    over the chosen sigmoids' sum; and no gradient reaches the bias."""
    x, wr = _weights(7)[:2]
    scores = moe.router_scores(x, wr)
    plain = moe.route(scores, K, "sigmoid", jnp.zeros(E), 1.8)
    bias = jnp.zeros(E).at[5].set(10.0).at[0].set(-10.0)  # expert 5 always, expert 0 never
    got = moe.route(scores, K, "sigmoid", bias, 1.8)
    assert (np.asarray(got.experts) == 5).any(-1).all() and not (np.asarray(got.experts) == 0).any()
    assert not (np.asarray(plain.experts) == 5).any(-1).all()  # the bias did that
    s = np.asarray(jax.nn.sigmoid(scores), np.float64)
    picked = np.take_along_axis(s, np.asarray(got.experts), -1)
    np.testing.assert_allclose(got.weights, 1.8 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    assert np.asarray(got.weights).max() <= 1.8  # a bias of 10 among the weights would not be
    g_bias, g_scores = jax.grad(
        lambda b, s: jnp.sum(jnp.sin(moe.route(s, K, "sigmoid", jax.lax.stop_gradient(b), 1.8).weights)), argnums=(0, 1))(bias, scores)
    assert not np.asarray(g_bias).any() and np.asarray(g_scores).any()
    # and without the stop_gradient the choice still carries none: top_k's indices are integers
    g_bias = jax.grad(lambda b: jnp.sum(jnp.sin(moe.route(scores, K, "sigmoid", b, 1.8).weights)))(bias)
    assert not np.asarray(g_bias).any()


def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The published sparse layer, uncut: S(h) + sum over the chosen e of
    w_e E_e(h) with the sigmoid router. Cut into eight shares of one
    expert each, every share computes its routed part, and the shared
    expert, which every chip computes alike for its own frames, counts
    once: the sum is the uncut layer, and every pair is computed by one
    share."""
    x, wr, wg, wu, wd = _weights(8)
    r = np.random.RandomState(8)
    bias = jnp.asarray(r.randn(E) * 0.2, jnp.float32)
    sg, su, sd = (jnp.asarray(r.randn(*s) / 4, jnp.float32) for s in ((D, I), (D, I), (I, D)))
    swiglu = lambda h, g, u, d: (jax.nn.silu(h @ g) * (h @ u)) @ d
    chosen, w = _sigmoid_written(moe.router_scores(x, wr), bias, K, 1.8)
    w_all = jnp.asarray(_by_expert(chosen, w, E), jnp.float32)
    whole = swiglu(x, sg, su, sd) + sum(w_all[:, e:e + 1] * swiglu(x, wg[e], wu[e], wd[e]) for e in range(E))
    routing = moe.route(moe.router_scores(x, wr), K, "sigmoid", bias, 1.8)
    parts = [moe.expert_layer(x, routing, wg[e:e + 1], wu[e:e + 1], wd[e:e + 1], e) for e in range(E)]
    np.testing.assert_allclose(swiglu(x, sg, su, sd) + sum(p[0] for p in parts), whole, rtol=1e-4, atol=1e-5)
    assert sum(int(p[1].sum()) for p in parts) == F * K
    # eight times the shared expert, one from each share, is not the layer
    assert np.abs(np.asarray(8 * swiglu(x, sg, su, sd) + sum(p[0] for p in parts) - whole)).max() > 0.1


def test_the_eight_shares_add_up_to_the_uncut_layer_with_no_shared_expert():
    """LFM2-MoE's sparse layer, uncut: sum over the chosen e of w_e E_e(h)
    with the sigmoid router and its bias, the chosen sigmoids over their
    sum + eps, times 1.0, and no shared expert. Cut into eight shares of
    one expert each, the shares' routed parts add up to the uncut layer
    and every pair is computed by one share. The published model code's
    eps of 1e-6 in `route`'s 1e-20's place moves no weight by 1e-6 of
    itself, far below bfloat16's rounding."""
    x, wr, wg, wu, wd = _weights(9)
    bias = jnp.asarray(np.random.RandomState(9).randn(E) * 0.2, jnp.float32)
    swiglu = lambda h, g, u, d: (jax.nn.silu(h @ g) * (h @ u)) @ d
    scores = moe.router_scores(x, wr)
    chosen, w = _sigmoid_written(scores, bias, K, 1.0)
    w_all = jnp.asarray(_by_expert(chosen, w, E), jnp.float32)
    whole = sum(w_all[:, e:e + 1] * swiglu(x, wg[e], wu[e], wd[e]) for e in range(E))
    routing = moe.route(scores, K, "sigmoid", bias, 1.0)
    np.testing.assert_allclose(_by_expert(routing.experts, routing.weights, E), _by_expert(chosen, w, E), rtol=1e-5)
    parts = [moe.expert_layer(x, routing, wg[e:e + 1], wu[e:e + 1], wd[e:e + 1], e) for e in range(E)]
    np.testing.assert_allclose(sum(p[0] for p in parts), whole, rtol=1e-4, atol=1e-5)
    assert sum(int(p[1].sum()) for p in parts) == F * K
    published = _sigmoid_written(scores, bias, K, 1.0, 1e-6)[1]
    assert 0 < np.abs(published / w - 1).max() < 1e-6
