"""The gated delta rule (ops/gated_delta.py): the chunked form against the
frame-by-frame recurrence in value, final state and gradient, at row
lengths that are and are not multiples of the chunk; the causal depthwise
convolution against its written sum of shifted copies, and its one-frame
step with the tail."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dotaclient_tpu.ops import gated_delta as GD

B, Hk, R, D = 2, 2, 2, 8


def _row(T, seed=0):
    r = np.random.RandomState(seed)
    f32 = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)
    q, k = GD.l2norm(f32(B, T, Hk, D)) * D**-0.5, GD.l2norm(f32(B, T, Hk, D))
    return q, k, f32(B, T, Hk * R, D), jax.nn.sigmoid(f32(B, T, Hk * R)), -jax.nn.softplus(f32(B, T, Hk * R))


@pytest.mark.parametrize("chunk", [4, 8, 16])
@pytest.mark.parametrize("T", [32, 37, 16, 64])
def test_the_chunked_rule_is_the_recurrence(T, chunk):
    """Outputs, the state after the row and the gradients of q, k, v, beta
    and g, float32 to 1e-5: 37 frames pad the last chunk with frames of
    beta = 0 and g = 0, which write nothing and fade nothing."""
    row = _row(T, seed=T + chunk)
    want_o, want_S = GD.recurrent(*row)
    got_o, got_S = GD.chunked(*row, chunk)
    assert got_o.shape == (B, T, Hk * R, D) and got_S.shape == (B, Hk * R, D, D)
    np.testing.assert_allclose(got_o, want_o, atol=1e-5)
    np.testing.assert_allclose(got_S, want_S, atol=1e-5)
    scalar = lambda rule: lambda *a: (lambda o, S: jnp.sum(jnp.sin(o)) + jnp.sum(S * S))(*rule(*a))
    want = jax.grad(scalar(GD.recurrent), argnums=(0, 1, 2, 3, 4))(*row)
    got = jax.grad(scalar(lambda *a: GD.chunked(*a, chunk)), argnums=(0, 1, 2, 3, 4))(*row)
    for name, a, b in zip("q k v beta g".split(), got, want):
        np.testing.assert_allclose(a, b, atol=1e-5, err_msg=name)


def test_the_rule_against_its_written_equations():
    """`step` one frame at a time in numpy: S <- exp(g) S, u = beta (v -
    S^T k), S <- S + k u^T, o = S^T q, key head j serving value heads
    [j R, (j + 1) R); and a row from a state carried in is the two halves
    of the row one after the other."""
    q, k, v, beta, g = map(np.asarray, _row(6, seed=3))
    S = np.zeros((B, Hk * R, D, D), np.float32)
    want = np.zeros((B, 6, Hk * R, D), np.float32)
    for t in range(6):
        for b in range(B):
            for h in range(Hk * R):
                S[b, h] *= np.exp(g[b, t, h])
                u = beta[b, t, h] * (v[b, t, h] - S[b, h].T @ k[b, t, h // R])
                S[b, h] += np.outer(k[b, t, h // R], u)
                want[b, t, h] = S[b, h].T @ q[b, t, h // R]
    got_o, got_S = GD.recurrent(*map(jnp.asarray, (q, k, v, beta, g)))
    np.testing.assert_allclose(got_o, want, atol=1e-5)
    np.testing.assert_allclose(got_S, S, atol=1e-5)
    row = _row(24, seed=4)
    whole_o, whole_S = GD.chunked(*row, 8)
    first_o, first_S = GD.chunked(*(a[:, :16] for a in row), 8)
    rest_o, rest_S = GD.chunked(*(a[:, 16:] for a in row), 8, state=first_S)
    np.testing.assert_allclose(jnp.concatenate([first_o, rest_o], axis=1), whole_o, atol=1e-5)
    np.testing.assert_allclose(rest_S, whole_S, atol=1e-5)


@pytest.mark.parametrize("T", [1, 12, 63, 70])
def test_a_row_of_any_length_takes_the_programs_chunk(T):
    """At the program's chunk, CHUNK (64), a row shorter than a chunk, or
    a few frames over it, is padded with frames that write nothing and is
    the recurrence in value and state."""
    row = _row(T, seed=T)
    want_o, want_S = GD.recurrent(*row)
    got_o, got_S = GD.chunked(*row, GD.CHUNK)
    assert GD.CHUNK == 64 and got_o.shape == want_o.shape
    np.testing.assert_allclose(got_o, want_o, atol=1e-5)
    np.testing.assert_allclose(got_S, want_S, atol=1e-5)


@pytest.mark.parametrize("C", [5, 16, 32, 64])
def test_the_solve_is_the_inverse(C):
    """Random systems, and the one that seeded weights give: every frame
    of a chunk with nearly one key, so A is nearly -1 below the diagonal.
    Its inverse has entries of 1, -1 and 0; the product of (I + A^(2^m))
    is the same matrix in exact arithmetic and loses every digit of it in
    float32 (A^32 has entries of 1e17)."""
    r = np.random.RandomState(C)
    for A in (np.tril(r.randn(3, C, C) * 0.3, -1), np.tril(-np.ones((1, C, C)) + 0.01 * r.randn(1, C, C), -1)):
        A = jnp.asarray(A, jnp.float32)
        got = GD._solve(A)
        want = np.linalg.inv(np.eye(C) - np.asarray(A, np.float64))
        np.testing.assert_allclose(got, want, atol=2e-4 * max(1.0, np.abs(want).max()))
    assert np.abs(want).max() < 3  # and the hard one's inverse is a small matrix


def test_a_chunk_of_nearly_one_key_stays_finite_and_right():
    """Rows whose frames share nearly one key and write at full strength
    with no decay: the recurrence and the chunked form agree."""
    r = np.random.RandomState(0)
    T = 64
    base = r.randn(1, 1, Hk, D)
    k = GD.l2norm(jnp.asarray(base + 0.02 * r.randn(B, T, Hk, D), jnp.float32))
    q = GD.l2norm(jnp.asarray(base + 0.02 * r.randn(B, T, Hk, D), jnp.float32)) * D**-0.5
    v = jnp.asarray(r.randn(B, T, Hk * R, D), jnp.float32)
    beta = jnp.full((B, T, Hk * R), 0.99, jnp.float32)
    g = jnp.full((B, T, Hk * R), -1e-3, jnp.float32)
    want_o, want_S = GD.recurrent(q, k, v, beta, g)
    got_o, got_S = GD.chunked(q, k, v, beta, g, 64)
    np.testing.assert_allclose(got_o, want_o, atol=1e-4)
    np.testing.assert_allclose(got_S, want_S, atol=1e-4)


@pytest.mark.parametrize("K", [4, 2])
def test_the_convolution_is_its_shifted_sums_and_steps_with_its_tail(K):
    """out_t = sum_j w[j] x_(t - (K - 1) + j), zeros before the row, one
    filter a channel; stepping one frame at a time with the tail (the K - 1
    inputs before it) gives the row's outputs, and a row after a tail is
    the rest of a longer row."""
    r = np.random.RandomState(K)
    T, heads = 9, 3
    x = jnp.asarray(r.randn(B, T, heads, D), jnp.float32)
    w = jnp.asarray(r.randn(K, heads, D), jnp.float32)
    want = np.zeros((B, T, heads, D), np.float32)
    for t in range(T):
        for j in range(K):
            if t - (K - 1) + j >= 0:
                want[:, t] += np.asarray(w[j]) * np.asarray(x[:, t - (K - 1) + j])
    got, tail = GD.causal_conv(x, w)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(tail, x[:, T - (K - 1):])
    tail = jnp.zeros((B, K - 1, heads, D), jnp.float32)
    for t in range(T):
        out, tail = GD.causal_conv(x[:, t:t + 1], w, tail)
        np.testing.assert_allclose(out[:, 0], want[:, t], atol=1e-6)
        assert tail.shape == (B, K - 1, heads, D)
    first, tail = GD.causal_conv(x[:, :5], w)
    rest, _ = GD.causal_conv(x[:, 5:], w, tail)
    np.testing.assert_allclose(jnp.concatenate([first, rest], axis=1), want, atol=1e-6)
