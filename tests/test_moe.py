"""The routed-expert layer (ops/moe.py): the router against its written
equations, a share's partial sum against a loop over its experts, the
shares of an expert-parallel group adding up to the whole layer, and no
pair dropped however skewed the routing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


def _program(x, wr, wg, wu, wd, lo, hi, top_k=K):
    routing = moe.route(moe.router_scores(x, wr), top_k)
    return moe.expert_layer(x, routing, wg[lo:hi], wu[lo:hi], wd[lo:hi], lo)


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


@pytest.mark.parametrize("lo,hi", [(0, 8), (2, 4), (6, 8)])
def test_a_share_against_the_loop_over_its_experts(lo, hi):
    args = _weights(1)
    got, sizes = _program(*args, lo, hi)
    np.testing.assert_allclose(got, _written(*args, lo, hi), rtol=1e-5, atol=1e-5)
    chosen = np.asarray(moe.route(moe.router_scores(args[0], args[1]), K).experts)
    assert sizes.tolist() == [(chosen == e).sum() for e in range(lo, hi)]
    g_got = jax.grad(lambda *a: jnp.sum(jnp.sin(_program(*a, lo, hi)[0])), argnums=(0, 1, 2, 3, 4))(*args)
    g_want = jax.grad(lambda *a: jnp.sum(jnp.sin(_written(*a, lo, hi))), argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    # experts outside the share get no gradient from it
    assert not np.asarray(g_got[2][:lo]).any() and not np.asarray(g_got[2][hi:]).any()


def test_the_four_shares_add_up_to_the_uncut_layer():
    args = _weights(2)
    whole = _written(*args, 0, E)
    parts = [_program(*args, lo, lo + 2) for lo in range(0, E, 2)]
    np.testing.assert_allclose(sum(p[0] for p in parts), whole, rtol=1e-5, atol=1e-5)
    assert sum(int(p[1].sum()) for p in parts) == F * K  # every pair computed once, by one share


def test_dropless_under_skew():
    """Every frame to one expert: the buffer holds frames x top_k rows, so
    a share that gets every pair computes every pair."""
    x, _, wg, wu, wd = _weights(3)
    wr = jnp.zeros((D, E)).at[:, 5].set(0.0)
    x = jnp.abs(x)
    wr = wr.at[:, 5].set(4.0)  # expert 5 first for every frame, by a wide margin
    got, sizes = _program(x, wr, wg, wu, wd, 4, 6, top_k=1)
    assert sizes.tolist() == [0, F]
    np.testing.assert_allclose(got, (jax.nn.silu(x @ wg[5]) * (x @ wu[5])) @ wd[5], rtol=1e-5, atol=1e-5)
    none, sizes = _program(x, wr, wg, wu, wd, 0, 4, top_k=1)
    assert sizes.tolist() == [0, 0, 0, 0] and not np.asarray(none).any()
    # all top_k choices of every frame held here: the buffer is full
    full, sizes = _program(*_weights(3), 0, E, top_k=E)
    assert int(sizes.sum()) == F * E
    np.testing.assert_allclose(full, _written(*_weights(3), 0, E, top_k=E), rtol=1e-5, atol=1e-5)


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
    want, sizes = moe.expert_layer(x, routing, wg[1:5], wu[1:5], wd[1:5], 1, "ragged_dot")
    got, sizes_k = moe.expert_layer(x, routing, wg[1:5], wu[1:5], wd[1:5], 1, "megablox_interpret")
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
