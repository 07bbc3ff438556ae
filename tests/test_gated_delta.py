"""The gated delta rule (ops/gated_delta.py): the chunked form against the
frame-by-frame recurrence in value, final state and gradient, at row
lengths that are and are not multiples of the chunk; its own backward
pass against the recurrence's gradient, against autodiff through the same
forward, on the nearly-one-key chunk, in bfloat16, and by the scans it
leaves in a checkpointed caller's gradient; the causal depthwise
convolution against its written sum of shifted copies, and its one-frame
step with the tail."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dotaclient_tpu.ops import gated_delta as GD

B, Hk, R, D = 2, 2, 2, 8


def _row(T, seed=0, Hk=Hk):
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
    got_o, got_S = jax.jit(lambda *a: GD.chunked(*a, chunk))(*row)
    assert got_o.shape == (B, T, Hk * R, D) and got_S.shape == (B, Hk * R, D, D)
    np.testing.assert_allclose(got_o, want_o, atol=1e-5)
    np.testing.assert_allclose(got_S, want_S, atol=1e-5)
    scalar = lambda rule: lambda *a: (lambda o, S: jnp.sum(jnp.sin(o)) + jnp.sum(S * S))(*rule(*a))
    want = jax.grad(scalar(GD.recurrent), argnums=(0, 1, 2, 3, 4))(*row)
    got = jax.jit(jax.grad(scalar(lambda *a: GD.chunked(*a, chunk)), argnums=(0, 1, 2, 3, 4)))(*row)
    for name, a, b in zip("q k v beta g".split(), got, want):
        np.testing.assert_allclose(a, b, atol=1e-5, err_msg=name)


LEAVES = "q k v beta g state".split()


def _gradients(rule, row, state, seed):
    """The gradients of q, k, v, beta, g and the entering state under one
    random cotangent of o and one of the state after the row, jitted."""
    r = np.random.RandomState(seed)
    o, S = jax.eval_shape(rule, *row, state)
    do, dS = jnp.asarray(r.randn(*o.shape), jnp.float32), jnp.asarray(r.randn(*S.shape), jnp.float32)
    paired = lambda *a: (lambda o, S: jnp.sum(o * do) + jnp.sum(S * dS))(*rule(*a))
    return jax.jit(jax.grad(paired, argnums=tuple(range(6))))(*row, state)


def _state(seed, Hk=Hk):
    return jnp.asarray(np.random.RandomState(seed).randn(B, Hk * R, D, D), jnp.float32)


@pytest.mark.parametrize("chunk", [4, 16, 64])
@pytest.mark.parametrize("T", [32, 37, 70])
def test_the_rules_backward_pass_is_the_recurrences_gradient(T, chunk):
    """The hand-written backward pass (the module's equations: one reverse
    scan over the chunks, the triangular system's cotangent as T^T dT T^T)
    for all five inputs and the entering state, two value heads to a key
    head, against `jax.grad` through `recurrent`, float32 to 1e-4."""
    row, state = _row(T, seed=3 * T + chunk), _state(T)
    want = _gradients(GD.recurrent, row, state, seed=chunk)
    got = _gradients(lambda *a: GD.chunked(*a[:5], chunk, state=a[5]), row, state, seed=chunk)
    for name, a, b in zip(LEAVES, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("chunk", [4, 16, 64])
@pytest.mark.parametrize("T", [32, 37, 70])
def test_the_rules_backward_pass_is_the_transpose_of_what_runs(T, chunk, monkeypatch):
    """The same gradients against autodiff through the forward function
    itself (`_chunks`, the rule's primal, with the rule taken off), to 1e-5."""
    row, state = _row(T, seed=5 * T + chunk), _state(T + 1)
    rule = lambda *a: GD.chunked(*a[:5], chunk, state=a[5])
    got = _gradients(rule, row, state, seed=T)
    monkeypatch.setattr(GD, "_rule", GD._chunks)
    want = _gradients(rule, row, state, seed=T)
    for name, a, b in zip(LEAVES, got, want):
        np.testing.assert_allclose(a, b, atol=1e-5, err_msg=name)


def test_the_rule_in_bfloat16_is_no_further_from_the_recurrence_than_autodiff(monkeypatch):
    """q, k and v in bfloat16, so every product's operands are: the error
    of the rule's gradient against the float32 recurrence on the same
    inputs, leaf by leaf (the norm of the difference), is at most 1.25
    times that of autodiff through the same forward. (One key head: the
    CPU's backend has no bfloat16 product under two head axes.)"""
    T, chunk = 128, 16
    q, k, v, beta, g = _row(T, seed=11, Hk=1)
    row, state = (q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), beta, g), _state(12, Hk=1)
    rule = lambda *a: GD.chunked(*a[:5], chunk, state=a[5])
    want = _gradients(GD.recurrent, row, state, seed=13)
    own = _gradients(rule, row, state, seed=13)
    monkeypatch.setattr(GD, "_rule", GD._chunks)
    autodiff = _gradients(rule, row, state, seed=13)
    far = lambda a, b: float(jnp.linalg.norm((a.astype(jnp.float32) - b.astype(jnp.float32)).ravel()))
    for name, a, b, c in zip(LEAVES, own, autodiff, want):
        assert a.dtype == c.dtype and 0 < far(a, c) <= 1.25 * far(b, c), (name, far(a, c), far(b, c))


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


def _nearly_one_key():
    r = np.random.RandomState(0)
    T = 64
    base = r.randn(1, 1, Hk, D)
    k = GD.l2norm(jnp.asarray(base + 0.02 * r.randn(B, T, Hk, D), jnp.float32))
    q = GD.l2norm(jnp.asarray(base + 0.02 * r.randn(B, T, Hk, D), jnp.float32)) * D**-0.5
    v = jnp.asarray(r.randn(B, T, Hk * R, D), jnp.float32)
    return q, k, v, jnp.full((B, T, Hk * R), 0.99, jnp.float32), jnp.full((B, T, Hk * R), -1e-3, jnp.float32)


def test_a_chunk_of_nearly_one_key_stays_finite_and_right():
    """Rows whose frames share nearly one key and write at full strength
    with no decay: the recurrence and the chunked form agree."""
    row = _nearly_one_key()
    want_o, want_S = GD.recurrent(*row)
    got_o, got_S = jax.jit(lambda *a: GD.chunked(*a, 64))(*row)
    np.testing.assert_allclose(got_o, want_o, atol=1e-4)
    np.testing.assert_allclose(got_S, want_S, atol=1e-4)


def test_a_chunk_of_nearly_one_key_backward():
    """The same rows through the backward pass, where T^T dT T^T has
    entries of order one: finite, and the gradient of the recurrence in
    float64 (written out here: `step` is float32) to the forward's 1e-4."""
    row, state = _nearly_one_key(), _state(7)
    got = _gradients(lambda *a: GD.chunked(*a[:5], 64, state=a[5]), row, state, seed=8)

    def recurrent64(q, k, v, beta, g, S):
        def frame(S, xs):
            q, k, v, beta, g = xs
            q, k = jnp.repeat(q, R, axis=-2), jnp.repeat(k, R, axis=-2)
            S = jnp.exp(g)[..., None, None] * S
            u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", S, k))
            S = S + k[..., :, None] * u[..., None, :]
            return S, jnp.einsum("bhkv,bhk->bhv", S, q)

        S, o = jax.lax.scan(frame, S, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, beta, g)))
        return jnp.moveaxis(o, 0, 1), S

    with jax.enable_x64(True):
        wide = [jnp.asarray(np.asarray(a, np.float64)) for a in row + (state,)]
        r = np.random.RandomState(8)  # `_gradients`' cotangents, in float64
        do, dS = (jnp.asarray(r.randn(*shape)) for shape in ((B, 64, Hk * R, D), (B, Hk * R, D, D)))
        paired = lambda *a: (lambda o, S: jnp.sum(o * do) + jnp.sum(S * dS))(*recurrent64(*a))
        want = [np.asarray(a) for a in jax.grad(paired, argnums=tuple(range(6)))(*wide)]
        assert want[0].dtype == np.float64
    for name, a, b in zip(LEAVES, got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg=name)


def _scans(jaxpr):
    """The `scan` equations of a jaxpr and of every jaxpr inside it but a
    scan's own body, as (forward, reverse) counts: the rule's scans over
    segments, not the scan over a segment's chunks inside each."""
    forward = reverse = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            forward, reverse = forward + (not eqn.params["reverse"]), reverse + bool(eqn.params["reverse"])
            continue
        inside = lambda x: hasattr(x, "eqns") or hasattr(x, "jaxpr")
        for inner in filter(inside, jax.tree.leaves(list(eqn.params.values()), is_leaf=inside)):
            f, r = _scans(getattr(inner, "jaxpr", inner))
            forward, reverse = forward + f, reverse + r
    return forward, reverse


@pytest.mark.parametrize("names,forward", [((GD.RULE_RESIDUALS,), 1), ((), 2)])
def test_a_checkpoint_that_keeps_the_rules_name_runs_its_scan_once(names, forward):
    """What the block's rematerialisation policy is for, seen without a
    chip: under `jax.checkpoint` with `save_only_these_names(RULE_RESIDUALS)`
    the gradient holds one forward scan over the row (the forward pass's)
    and one reverse (the backward's), though what follows the rule needs
    o again; with the name left out of the policy the checkpoint runs the
    forward scan a second time."""
    row = _row(32, seed=2)
    policy = jax.checkpoint_policies.save_only_these_names(*names)
    part = jax.checkpoint(lambda *a: jnp.tanh(GD.chunked(*a, 8)[0]), policy=policy)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(part(*a)), argnums=(0, 1, 2, 3, 4)))(*row)
    assert _scans(jaxpr.jaxpr) == (forward, 1)


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
