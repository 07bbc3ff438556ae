"""Attention ops: oracle softmax, position masking, RoPE, and ring-vs-
single-device equivalence (forward + gradients) on the 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dotaclient_tpu.ops import attention as A
from dotaclient_tpu.ops import ring_attention as RA
from dotaclient_tpu.parallel import mesh as mesh_lib


def _rand(shape, seed, dtype=np.float32):
    return np.random.RandomState(seed).randn(*shape).astype(dtype)


def _naive_causal(q, k, v, q_pos, k_pos):
    """Dense-softmax oracle in NumPy float64."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    B, Tq, N, Dh = q.shape
    Tk = k.shape[1]
    out = np.zeros_like(q)
    for b in range(B):
        for n in range(N):
            s = q[b, :, n] @ k[b, :, n].T / np.sqrt(Dh)  # [Tq, Tk]
            valid = (np.asarray(k_pos)[b][None, :] <= np.asarray(q_pos)[b][:, None]) & (
                np.asarray(k_pos)[b][None, :] != int(A.EMPTY_POS)
            )
            s = np.where(valid, s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            p = np.where(valid, p, 0.0)
            denom = p.sum(-1, keepdims=True)
            p = np.divide(p, denom, out=np.zeros_like(p), where=denom > 0)
            out[b, :, n] = p @ v[b, :, n]
    return out


def _positions(B, T):
    return np.broadcast_to(np.arange(T, dtype=np.int32), (B, T)).copy()


class TestCausalAttention:
    def test_matches_naive_oracle(self):
        B, T, N, Dh = 2, 12, 3, 8
        q, k, v = _rand((B, T, N, Dh), 0), _rand((B, T, N, Dh), 1), _rand((B, T, N, Dh), 2)
        pos = _positions(B, T)
        got = A.causal_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), pos, pos)
        np.testing.assert_allclose(got, _naive_causal(q, k, v, pos, pos), rtol=1e-5, atol=1e-5)

    def test_causality_future_keys_ignored(self):
        # Changing a future key/value must not change a past query's output.
        B, T, N, Dh = 1, 8, 2, 4
        q, k, v = (jnp.asarray(_rand((B, T, N, Dh), s)) for s in (3, 4, 5))
        pos = _positions(B, T)
        base = A.causal_attention(q, k, v, pos, pos)
        k2 = k.at[:, -1].add(100.0)
        v2 = v.at[:, -1].add(100.0)
        pert = A.causal_attention(q, k2, v2, pos, pos)
        np.testing.assert_allclose(base[:, :-1], pert[:, :-1], rtol=1e-6)
        assert not np.allclose(base[:, -1], pert[:, -1])

    def test_empty_sentinel_slots_never_attended(self):
        # A cache of length 8 with only 3 written slots == attention over
        # just those 3 — garbage in the tail slots is invisible.
        B, C, N, Dh = 2, 8, 2, 4
        k_full = _rand((B, C, N, Dh), 6)
        v_full = _rand((B, C, N, Dh), 7)
        k_full[:, 3:] = 1e6  # garbage in unwritten slots
        v_full[:, 3:] = -1e6
        k_pos = np.full((B, C), int(A.EMPTY_POS), np.int32)
        k_pos[:, :3] = np.arange(3, dtype=np.int32)
        q = jnp.asarray(_rand((B, 1, N, Dh), 8))
        q_pos = np.full((B, 1), 2, np.int32)
        got = A.causal_attention(q, jnp.asarray(k_full), jnp.asarray(v_full), q_pos, k_pos)
        want = A.causal_attention(
            q,
            jnp.asarray(k_full[:, :3]),
            jnp.asarray(v_full[:, :3]),
            q_pos,
            k_pos[:, :3],
        )
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_bf16_inputs_f32_softmax(self):
        B, T, N, Dh = 2, 8, 2, 8
        q, k, v = (jnp.asarray(_rand((B, T, N, Dh), s), jnp.bfloat16) for s in (9, 10, 11))
        pos = _positions(B, T)
        got = A.causal_attention(q, k, v, pos, pos)
        assert got.dtype == jnp.bfloat16
        ref = _naive_causal(np.asarray(q, np.float32), np.asarray(k, np.float32),
                            np.asarray(v, np.float32), pos, pos)
        np.testing.assert_allclose(np.asarray(got, np.float32), ref, rtol=0.05, atol=0.05)


class TestRope:
    def test_position_zero_is_identity(self):
        x = jnp.asarray(_rand((2, 1, 2, 8), 12))
        pos = np.zeros((2, 1), np.int32)
        np.testing.assert_allclose(A.rope(x, pos), x, rtol=1e-6)

    def test_preserves_norm(self):
        x = jnp.asarray(_rand((2, 6, 2, 8), 13))
        pos = _positions(2, 6)
        np.testing.assert_allclose(
            jnp.linalg.norm(A.rope(x, pos), axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5
        )

    def test_scores_depend_only_on_relative_position(self):
        # <rope(q, p+d), rope(k, p)> must be invariant in p.
        q = jnp.asarray(_rand((1, 1, 1, 8), 14))
        k = jnp.asarray(_rand((1, 1, 1, 8), 15))

        def score(pq, pk):
            rq = A.rope(q, np.asarray([[pq]], np.int32))
            rk = A.rope(k, np.asarray([[pk]], np.int32))
            return float(jnp.sum(rq * rk))

        assert score(5, 2) == pytest.approx(score(105, 102), rel=1e-4)
        assert score(7, 7) == pytest.approx(score(0, 0), rel=1e-4)

    def test_sentinel_position_stays_finite(self):
        x = jnp.asarray(_rand((1, 3, 2, 8), 16))
        pos = np.full((1, 3), int(A.EMPTY_POS), np.int32)
        assert np.isfinite(np.asarray(A.rope(x, pos))).all()


class TestRingAttention:
    @pytest.fixture(scope="class")
    def sp_mesh(self):
        return mesh_lib.make_mesh("sp=8")

    def test_matches_single_device(self, sp_mesh):
        B, T, N, Dh = 2, 32, 2, 8
        q, k, v = (jnp.asarray(_rand((B, T, N, Dh), s)) for s in (20, 21, 22))
        pos = _positions(B, T)
        ring = RA.ring_causal_attention(q, k, v, pos, pos, sp_mesh)
        full = A.causal_attention(q, k, v, pos, pos)
        np.testing.assert_allclose(ring, full, rtol=1e-5, atol=1e-6)

    @pytest.mark.slow  # shard_map VJP compile (~8s) — default gate only
    def test_gradients_match_single_device(self, sp_mesh):
        B, T, N, Dh = 1, 16, 2, 4
        q, k, v = (jnp.asarray(_rand((B, T, N, Dh), s)) for s in (23, 24, 25))
        pos = _positions(B, T)
        cot = jnp.asarray(_rand((B, T, N, Dh), 26))  # fixed cotangent

        def loss_ring(q, k, v):
            return jnp.sum(RA.ring_causal_attention(q, k, v, pos, pos, sp_mesh) * cot)

        def loss_full(q, k, v):
            return jnp.sum(A.causal_attention(q, k, v, pos, pos) * cot)

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for gr, gf in zip(g_ring, g_full):
            np.testing.assert_allclose(gr, gf, rtol=1e-4, atol=1e-5)

    def test_composes_under_jit(self, sp_mesh):
        B, T, N, Dh = 2, 16, 2, 8
        q, k, v = (jnp.asarray(_rand((B, T, N, Dh), s)) for s in (27, 28, 29))
        pos = _positions(B, T)

        @jax.jit
        def f(q, k, v):
            return RA.ring_causal_attention(q, k, v, pos, pos, sp_mesh)

        np.testing.assert_allclose(
            f(q, k, v), A.causal_attention(q, k, v, pos, pos), rtol=1e-5, atol=1e-6
        )

    def test_rejects_indivisible_time_axis(self, sp_mesh):
        q = jnp.zeros((1, 12, 2, 4))
        pos = _positions(1, 12)
        with pytest.raises(ValueError, match="not divisible"):
            RA.ring_causal_attention(q, q, q, pos, pos, sp_mesh)

    def test_dispatch_helper(self, sp_mesh):
        B, T, N, Dh = 1, 16, 2, 4
        q, k, v = (jnp.asarray(_rand((B, T, N, Dh), s)) for s in (30, 31, 32))
        pos = _positions(B, T)
        via_ring = RA.attend(q, k, v, pos, pos, mesh=sp_mesh, sp_axis="sp")
        via_full = RA.attend(q, k, v, pos, pos)
        np.testing.assert_allclose(via_ring, via_full, rtol=1e-5, atol=1e-6)


class TestUlyssesAttention:
    @pytest.fixture(scope="class")
    def sp_mesh(self):
        return mesh_lib.make_mesh("sp=8")

    @pytest.mark.slow  # all-to-all shard_map compile — default gate only
    def test_matches_single_device(self, sp_mesh):
        B, T, N, Dh = 2, 32, 8, 8  # heads divisible by sp=8
        q, k, v = (jnp.asarray(_rand((B, T, N, Dh), s)) for s in (40, 41, 42))
        pos = _positions(B, T)
        uly = RA.ulysses_causal_attention(q, k, v, pos, pos, sp_mesh)
        full = A.causal_attention(q, k, v, pos, pos)
        np.testing.assert_allclose(uly, full, rtol=1e-5, atol=1e-6)

    @pytest.mark.nightly  # ring grads cover the default gate; this is the
    # ulysses-specific backward (compile-heavy shard_map VJP)
    @pytest.mark.slow  # nightly-heavy must ALSO be slow: tier-1's
    # -m 'not slow' REPLACES the addopts nightly exclusion
    def test_gradients_match_single_device(self, sp_mesh):
        B, T, N, Dh = 1, 16, 8, 4
        q, k, v = (jnp.asarray(_rand((B, T, N, Dh), s)) for s in (43, 44, 45))
        pos = _positions(B, T)
        cot = jnp.asarray(_rand((B, T, N, Dh), 46))

        g_uly = jax.grad(
            lambda q, k, v: jnp.sum(RA.ulysses_causal_attention(q, k, v, pos, pos, sp_mesh) * cot),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_full = jax.grad(
            lambda q, k, v: jnp.sum(A.causal_attention(q, k, v, pos, pos) * cot),
            argnums=(0, 1, 2),
        )(q, k, v)
        for gu, gf in zip(g_uly, g_full):
            np.testing.assert_allclose(gu, gf, rtol=1e-4, atol=1e-5)

    @pytest.mark.slow  # two shard_map compiles in one test — default gate only
    def test_matches_ring(self, sp_mesh):
        """Both SP patterns compute the same function."""
        B, T, N, Dh = 2, 16, 8, 4
        q, k, v = (jnp.asarray(_rand((B, T, N, Dh), s)) for s in (47, 48, 49))
        pos = _positions(B, T)
        uly = RA.ulysses_causal_attention(q, k, v, pos, pos, sp_mesh)
        ring = RA.ring_causal_attention(q, k, v, pos, pos, sp_mesh)
        np.testing.assert_allclose(uly, ring, rtol=1e-5, atol=1e-6)

    def test_rejects_indivisible_heads(self, sp_mesh):
        q = jnp.zeros((1, 16, 4, 8))  # 4 heads % sp=8 != 0
        pos = _positions(1, 16)
        with pytest.raises(ValueError, match="heads"):
            RA.ulysses_causal_attention(q, q, q, pos, pos, sp_mesh)

    @pytest.mark.slow  # ulysses compile — dispatch plumbing is covered by
    # TestRingAttention::test_dispatch_helper in tier-1
    def test_dispatch_mode(self, sp_mesh):
        B, T, N, Dh = 1, 16, 8, 4
        q, k, v = (jnp.asarray(_rand((B, T, N, Dh), s)) for s in (50, 51, 52))
        pos = _positions(B, T)
        via = RA.attend(q, k, v, pos, pos, mesh=sp_mesh, sp_axis="sp", sp_mode="ulysses")
        np.testing.assert_allclose(via, A.causal_attention(q, k, v, pos, pos), rtol=1e-5, atol=1e-6)
        with pytest.raises(ValueError, match="sp_mode"):
            RA.attend(q, k, v, pos, pos, mesh=sp_mesh, sp_axis="sp", sp_mode="bogus")


class TestBlockwiseAttention:
    @pytest.mark.parametrize("T,block", [(32, 8), (20, 8), (7, 16), (16, 16)])
    def test_matches_dense(self, T, block):
        """Including ragged tails (20 % 8), block >= T (degenerate), and
        exact multiples."""
        B, N, Dh = 2, 2, 8
        q, k, v = (jnp.asarray(_rand((B, T, N, Dh), s + T)) for s in (60, 61, 62))
        pos = _positions(B, T)
        got = A.blockwise_causal_attention(q, k, v, pos, pos, block)
        want = A.causal_attention(q, k, v, pos, pos)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    @pytest.mark.slow  # blockwise VJP compile — default gate only
    def test_gradients_match_dense(self):
        B, T, N, Dh = 1, 24, 2, 4
        q, k, v = (jnp.asarray(_rand((B, T, N, Dh), s)) for s in (63, 64, 65))
        pos = _positions(B, T)
        cot = jnp.asarray(_rand((B, T, N, Dh), 66))
        g_blk = jax.grad(
            lambda q, k, v: jnp.sum(A.blockwise_causal_attention(q, k, v, pos, pos, 8) * cot),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_dense = jax.grad(
            lambda q, k, v: jnp.sum(A.causal_attention(q, k, v, pos, pos) * cot),
            argnums=(0, 1, 2),
        )(q, k, v)
        for gb, gd in zip(g_blk, g_dense):
            np.testing.assert_allclose(gb, gd, rtol=1e-4, atol=1e-5)

    def test_dispatch_via_attend(self):
        B, T, N, Dh = 1, 32, 2, 4
        q, k, v = (jnp.asarray(_rand((B, T, N, Dh), s)) for s in (67, 68, 69))
        pos = _positions(B, T)
        via = RA.attend(q, k, v, pos, pos, kv_block=8)
        np.testing.assert_allclose(via, A.causal_attention(q, k, v, pos, pos), rtol=1e-5, atol=1e-6)


@pytest.mark.nightly  # blockwise-vs-dense parity is covered in the default
# gate at the op level (TestBlockwiseAttention); this is the ulysses composition
@pytest.mark.slow  # nightly-heavy must ALSO be slow (tier-1 -m override)
def test_ulysses_blockwise_matches_dense():
    """kv_block threading through the ulysses path changes memory only."""
    mesh = mesh_lib.make_mesh("sp=8")
    B, T, N, Dh = 2, 32, 8, 4
    q, k, v = (jnp.asarray(_rand((B, T, N, Dh), s)) for s in (70, 71, 72))
    pos = _positions(B, T)
    blk = RA.ulysses_causal_attention(q, k, v, pos, pos, mesh, kv_block=8)
    dense = RA.ulysses_causal_attention(q, k, v, pos, pos, mesh)
    np.testing.assert_allclose(blk, dense, rtol=1e-5, atol=1e-6)


def _dense_masked(q, k, v, window=0):
    """The written equations under a dense mask, float64: frame t at
    position t, key j kept for query i where 0 <= i - j (< window), query
    head n reading key/value head n // (N // G)."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    B, T, N, Dh = q.shape
    G = k.shape[2]
    d = np.arange(T)[:, None] - np.arange(T)[None, :]
    keep = (d >= 0) & ((d < window) if window else True)
    out = np.zeros_like(q)
    for b in range(B):
        for n in range(N):
            s = np.where(keep, q[b, :, n] @ k[b, :, n // (N // G)].T / np.sqrt(Dh), -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            out[b, :, n] = (p / p.sum(-1, keepdims=True)) @ v[b, :, n // (N // G)]
    return out


class TestBlockedWindowedGroupedAttention:
    """Both layer kinds of the blocked attention against the dense mask:
    grouped heads, a ragged last block, and the blocks it never computes."""

    @pytest.mark.parametrize("window", [0, 5, 8, 11], ids=lambda w: f"window{w}")
    @pytest.mark.parametrize("T,block", [(32, 8), (21, 8), (9, 4)])
    def test_matches_the_dense_mask(self, T, block, window):
        B, N, G, Dh = 2, 4, 2, 8
        q = jnp.asarray(_rand((B, T, N, Dh), 80 + T))
        k, v = (jnp.asarray(_rand((B, T, G, Dh), s + T)) for s in (81, 82))
        pos = _positions(B, T)
        want = _dense_masked(q, k, v, window)
        np.testing.assert_allclose(A.causal_attention(q, k, v, pos, pos, window), want, rtol=1e-5, atol=1e-6)
        got = A.blockwise_causal_attention(q, k, v, pos, pos, block, window)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        via = RA.attend(q, k, v, pos, pos, kv_block=block, window=window)
        np.testing.assert_allclose(via, want, rtol=1e-5, atol=1e-6)

    def test_grouped_heads_read_their_group(self):
        """Grouped K/V give what the same K/V repeated per query head give."""
        B, T, N, G, Dh = 1, 10, 6, 2, 4
        q = jnp.asarray(_rand((B, T, N, Dh), 90))
        k, v = (jnp.asarray(_rand((B, T, G, Dh), s)) for s in (91, 92))
        pos = _positions(B, T)
        rep = lambda x: jnp.repeat(x, N // G, axis=2)
        np.testing.assert_allclose(A.causal_attention(q, k, v, pos, pos),
                                   A.causal_attention(q, rep(k), rep(v), pos, pos), rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("T,block,window", [(32, 8, 0), (32, 8, 5), (21, 8, 9), (4096, 256, 1024)])
    def test_a_block_is_skipped_only_where_it_holds_no_pair(self, T, block, window):
        nb = -(-T // block)
        d = np.arange(T)[:, None] - np.arange(T)[None, :]
        keep = (d >= 0) & ((d < window) if window else True)
        computed = 0
        for i in range(nb):
            lo, hi = A.block_key_range(i, block, T, window)
            rows = slice(i * block, min((i + 1) * block, T))
            assert lo % block == 0 and hi == rows.stop
            assert not keep[rows, :lo].any() and not keep[rows, hi:].any()  # what is skipped holds nothing
            for j in range(lo // block, -(-hi // block)):  # what is computed holds a pair
                assert keep[rows, j * block:(j + 1) * block].any()
            computed += hi - lo
        if (T, window) == (4096, 1024):  # 1,280 keys a query block once the window is full
            assert computed == sum(min((i + 1) * 256, 1280) for i in range(16))

    def test_skipped_blocks_change_nothing(self):
        """Keys outside a query block's range may hold anything."""
        B, T, N, G, Dh, block, window = 1, 24, 2, 1, 4, 4, 6
        q = jnp.asarray(_rand((B, T, N, Dh), 93))
        k, v = (_rand((B, T, G, Dh), s) for s in (94, 95))
        pos = _positions(B, T)
        base = A.blockwise_causal_attention(q, jnp.asarray(k), jnp.asarray(v), pos, pos, block, window)
        last = slice(20, 24)  # the last query block meets keys [12, 24)
        assert A.block_key_range(5, block, T, window) == (12, 24)
        k2, v2 = k.copy(), v.copy()
        k2[:, :12], v2[:, :12] = 1e6, np.nan
        pert = A.blockwise_causal_attention(q, jnp.asarray(k2), jnp.asarray(v2), pos, pos, block, window)
        np.testing.assert_array_equal(np.asarray(pert)[:, last], np.asarray(base)[:, last])

    @pytest.mark.slow  # the blocked VJP compiles a program per query block
    def test_gradients_match_the_dense_mask(self):
        B, T, N, G, Dh = 1, 20, 4, 2, 4
        q = jnp.asarray(_rand((B, T, N, Dh), 96))
        k, v = (jnp.asarray(_rand((B, T, G, Dh), s)) for s in (97, 98))
        pos = _positions(B, T)
        cot = jnp.asarray(_rand((B, T, N, Dh), 99))
        for window in (0, 7):
            g_blk = jax.grad(lambda q, k, v: jnp.sum(
                A.blockwise_causal_attention(q, k, v, pos, pos, 8, window) * cot), argnums=(0, 1, 2))(q, k, v)
            g_dense = jax.grad(lambda q, k, v: jnp.sum(
                A.causal_attention(q, k, v, pos, pos, window) * cot), argnums=(0, 1, 2))(q, k, v)
            for gb, gd in zip(g_blk, g_dense):
                np.testing.assert_allclose(gb, gd, rtol=1e-4, atol=1e-5)

    def test_a_window_is_not_carried_over_the_sp_axis(self):
        mesh = mesh_lib.make_mesh("sp=8")
        q = jnp.zeros((1, 16, 2, 4))
        pos = _positions(1, 16)
        with pytest.raises(ValueError, match="window"):
            RA.attend(q, q, q, pos, pos, mesh=mesh, sp_axis="sp", window=4)


def split_rope(x, positions, base=10000.0, table=None):
    """`A.rope` as it was written until PR 37, kept as the plain reference:
    the head cut into halves, each rotated, and concatenated."""
    half = x.shape[-1] // 2
    freqs, scale = table if table is not None else A.rope_table(x.shape[-1], base)
    pos = jnp.where(positions == A.EMPTY_POS, 0, positions).astype(jnp.float32)
    ang = pos[..., None] * freqs
    cos = (jnp.cos(ang) * scale)[..., None, :]
    sin = (jnp.sin(ang) * scale)[..., None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


_YARN = (128, 500000.0, 16.0, 8192, 32.0, 1.0)


def _whole_head(table, scale=1.0):
    """(the rotation at full width, the split form) of a whole head of 128."""
    whole = lambda x, pos: A.rope(x, pos, table=table and A.rope_table(*table), scale=scale)
    folded = table and (A.rope_table(*table)[0], A.rope_table(*table)[1] * scale)
    return 128, whole, lambda x, pos: split_rope(x, pos, table=folded)


def _span_in_a_wider_head():
    """Latent q: 192 lanes that take the scale alone, then 64 that rotate."""
    table, scale = A.rope_table(64, 1000000.0), 256 ** -0.5
    whole = lambda x, pos: A.rope(x, pos, table=table, span=(192, 64), scale=scale)

    def split(x, pos):
        beside = (x[..., :192].astype(jnp.float32) * scale).astype(x.dtype)
        return jnp.concatenate([beside, split_rope(x[..., 192:], pos, table=(table[0], table[1] * scale))], axis=-1)

    return 256, whole, split


class TestFullWidthRope:
    """The rotation works on whole heads over full lanes (x * C +
    partner(x) * S): the same numbers as the half-split form."""

    CASES = {
        "default_table": lambda: _whole_head(None),
        "yarn_table": lambda: _whole_head(_YARN),
        "q_table_with_the_scale": lambda: _whole_head(_YARN, 128 ** -0.5),
        "span_in_a_wider_head": _span_in_a_wider_head,
    }

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("case", list(CASES))
    def test_equals_the_half_split_form(self, case, dtype):
        """Bit for bit in float32; in bfloat16 within the one rounding at
        the end (and, as it turns out, bit for bit too)."""
        width, whole, split = self.CASES[case]()
        x = jnp.asarray(_rand((2, 9, 3, width), 80), dtype)
        pos = jnp.asarray(np.random.RandomState(81).randint(0, 5000, (2, 9)), jnp.int32)
        got, want = whole(x, pos), split(x, pos)
        assert got.dtype == want.dtype == dtype and got.shape == x.shape
        if dtype == jnp.float32:
            np.testing.assert_array_equal(got, want)
        else:
            one_rounding = 2.0 ** -8 * np.abs(np.asarray(want, np.float32))
            assert (np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32)) <= one_rounding).all()
        # compiled, the CPU may contract a product and a sum into one rounding: one unit in the last place
        jitted = jax.jit(whole)(x, pos)
        np.testing.assert_allclose(np.asarray(jitted, np.float32), np.asarray(want, np.float32),
                                   rtol=2.0 ** (-23 if dtype == jnp.float32 else -8), atol=1e-6)

    def test_gradients_are_those_of_the_half_split_form(self):
        _, whole, split = _span_in_a_wider_head()
        x = jnp.asarray(_rand((1, 5, 2, 256), 82))
        pos, w = _positions(1, 5) + 7, jnp.asarray(_rand((1, 5, 2, 256), 83))
        got = jax.grad(lambda x: jnp.sum(whole(x, pos) * w))(x)
        np.testing.assert_allclose(got, jax.grad(lambda x: jnp.sum(split(x, pos) * w))(x), rtol=1e-6, atol=1e-7)

    def test_sentinel_positions_stay_finite_in_a_span(self):
        x = jnp.asarray(_rand((1, 3, 2, 16), 84))
        pos = np.full((1, 3), int(A.EMPTY_POS), np.int32)
        out = A.rope(x, pos, table=A.rope_table(4), span=(12, 4), scale=0.25)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_array_equal(out, x * 0.25)  # position 0: the scale alone

    def test_the_partner_is_a_swap_of_the_spans_halves(self):
        swap = A._partner(2, 4, 8)
        np.testing.assert_array_equal(np.arange(8.0) @ swap, [0, 0, 4, 5, 2, 3, 0, 0])
        assert (swap.sum(0) <= 1).all() and (swap.sum(1) <= 1).all()  # one input an output, times one


class TestRopeTable:
    def test_the_default_table_is_the_old_one(self):
        want = 10000.0 ** (-jnp.arange(16, dtype=jnp.float32) / 16)
        inv, scale = A.rope_table(32)
        np.testing.assert_array_equal(inv, np.asarray(want))
        assert scale == 1.0
        x = jnp.asarray(_rand((1, 6, 2, 32), 70))
        pos = _positions(1, 6)
        np.testing.assert_array_equal(A.rope(x, pos), A.rope(x, pos, table=(inv, 1.0)))

    def test_yarn_against_the_formula(self):
        """Head width 128, theta 500,000, factor 16, original context
        8,192, beta 32 and 1: the published full-attention section."""
        dim, theta, factor, orig = 128, 500000.0, 16.0, 8192
        inv, scale = A.rope_table(dim, theta, factor, orig, 32.0, 1.0)
        assert scale == pytest.approx(1.2772588722239782, rel=1e-12)
        base = theta ** (-np.arange(0, dim, 2) / dim)
        # the index at which a rotation makes r turns within the original context
        at = lambda r: dim * np.log(orig / (r * 2 * np.pi)) / (2 * np.log(theta))
        low, high = np.floor(at(32.0)), np.ceil(at(1.0))
        assert (low, high) == (18, 35)
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        np.testing.assert_allclose(inv, base / factor * ramp + base * (1 - ramp), rtol=1e-6)
        np.testing.assert_allclose(inv[:19], base[:19], rtol=1e-6)  # fast rotations keep their frequency
        np.testing.assert_allclose(inv[35:], base[35:] / 16, rtol=1e-6)  # slow ones are interpolated
        assert (np.diff(inv) < 0).all()
        # rope with the table: cos and sin carry the factor, so a vector's norm does
        x = jnp.asarray(_rand((1, 5, 1, dim), 71))
        out = A.rope(x, _positions(1, 5), table=(inv, scale))
        np.testing.assert_allclose(np.linalg.norm(out, axis=-1), scale * np.linalg.norm(x, axis=-1), rtol=1e-5)


def pallas_kernels(jaxpr) -> list:
    """The names of every Pallas kernel call in a jaxpr, sub-programs included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(str(eqn.params.get("name") or eqn.params["name_and_src_info"]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names.extend(pallas_kernels(sub))
    return names


class TestFusedKernel:
    """`fused_causal_attention` (the bundled splash kernel, in Pallas'
    interpreter here) against the dense masked softmax: a chunk of four
    tiles, grouped heads, a window that is a multiple of the tile and one
    that is not."""

    B, T, N, G, Dh = 2, 512, 8, 2, 128
    TILES = (128, 128)

    def _inputs(self, seed):
        B, T, N, G, Dh = self.B, self.T, self.N, self.G, self.Dh
        q = jnp.asarray(_rand((B, T, N, Dh), seed))
        k, v = (jnp.asarray(_rand((B, T, G, Dh), seed + s)) for s in (1, 2))
        return q, k, v, jnp.asarray(_rand((B, T, N, Dh), seed + 3)), _positions(B, T)

    # float32: both sides compute the same thing; bfloat16 inputs: the kernel's backward pass rounds the
    # probabilities and the scores' cotangent to bfloat16 as operands, the plain one keeps them float32
    @pytest.mark.parametrize("dtype,out_tol,grad_tol", [("float32", 2e-5, 1e-4), ("bfloat16", 2e-2, 5e-2)])
    @pytest.mark.parametrize("window", [0, 256, 200], ids=lambda w: f"window{w}")
    def test_output_and_gradients_match_causal_attention(self, window, dtype, out_tol, grad_tol):
        q32, k, v, cot, pos = self._inputs(100 + window)
        dt = jnp.dtype(dtype)
        k, v = k.astype(dt), v.astype(dt)

        def plain(q32, k, v):
            out = A.causal_attention(q32.astype(dt), k, v, pos, pos, window)
            return jnp.sum(out.astype(jnp.float32) * cot), out

        def fused(q32, k, v):
            # as the block does: the 1/sqrt(Dh) goes in while q is float32
            out = A.fused_causal_attention((q32 * self.Dh**-0.5).astype(dt), k, v, window, interpret=True,
                                           q_scaled=True, tiles=self.TILES)
            return jnp.sum(out.astype(jnp.float32) * cot), out

        (_, want), g_want = jax.value_and_grad(plain, (0, 1, 2), has_aux=True)(q32, k, v)
        (_, got), g_got = jax.value_and_grad(fused, (0, 1, 2), has_aux=True)(q32, k, v)
        assert got.dtype == dt and got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=out_tol)
        for a, b in zip(g_got, g_want):
            assert a.dtype == b.dtype
            scale = float(jnp.max(jnp.abs(b.astype(jnp.float32))))
            np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), atol=grad_tol * scale)

    def test_the_scale_is_applied_here_unless_q_holds_it(self):
        q, k, v, _, pos = self._inputs(7)
        q, k, v = q[:1, :256], k[:1, :256], v[:1, :256]
        want = A.causal_attention(q, k, v, pos[:1, :256], pos[:1, :256], 100)
        got = A.fused_causal_attention(q, k, v, 100, interpret=True, tiles=self.TILES)
        np.testing.assert_allclose(got, want, atol=2e-5)

    def test_the_code_chooses_the_tiles(self):
        assert A.fused_tiles(4096) == (1024, 512) and A.fused_tiles(4096, 1024) == (1024, 512)
        assert A.fused_tiles(1536) == (512, 512) and A.fused_tiles(384) == (128, 128)
        assert A.fused_tiles(4096, 200) == (128, 128)  # no tile far wider than the window
        assert A.fused_tiles(4095) == (0, 0) and not A.fused_takes(4095, 32, 4, 128)
        # heads of 64 are taken, at the tiles of 128 (the scratch is sized in whole lanes); 96 is not
        assert A.fused_takes(4096, 32, 4, 128) and A.fused_takes(4096, 32, 4, 64) and A.fused_takes(4096, 32, 8, 64)
        assert A.fused_tiles(4096, 0, 64) == A.fused_tiles(4096, 0, 128) == (1024, 512)
        assert not A.fused_takes(4096, 32, 4, 96) and not A.fused_takes(4096, 32, 5, 128)
        with pytest.raises(ValueError, match="fused kernel does not take"):
            A.fused_causal_attention(jnp.zeros((1, 256, 2, 96)), jnp.zeros((1, 256, 1, 96)),
                                     jnp.zeros((1, 256, 1, 96)), interpret=True)

    @pytest.mark.parametrize("keep", [False, True], ids=["remat_all", "residuals_kept"])
    def test_the_forward_kernel_runs_once_where_its_residuals_are_kept(self, keep):
        """Under a checkpoint the forward kernel is in the gradient's
        program twice (the forward pass and its rematerialisation); with a
        policy that keeps the kernel's named residuals, once."""
        q, k, v, cot, _ = self._inputs(11)
        q, k, v, cot = q[:1, :256], k[:1, :256], v[:1, :256], cot[:1, :256]

        def layer(q, k, v):
            out = A.fused_causal_attention(jnp.tanh(q), k, v, 0, interpret=True, tiles=self.TILES)
            return jnp.sum(out * cot)

        policy = jax.checkpoint_policies.save_only_these_names(A.FUSED_RESIDUALS) if keep else None
        grad = jax.grad(jax.checkpoint(layer, policy=policy), (0, 1, 2))
        names = pallas_kernels(jax.make_jaxpr(grad)(q, k, v).jaxpr)
        assert sum("fwd" in n for n in names) == (1 if keep else 2), names
        assert sum("dkv" in n for n in names) == 1 and len(names) == (2 if keep else 3), names
        for a, b in zip(grad(q, k, v), jax.grad(layer, (0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


class TestFusedKernelHeadsOf64(TestFusedKernel):
    """The same against heads of 64, the width of a head of the
    short-convolution cell's attention layer: the kernel's blocks are
    then half a lane register wide, and it has to agree all the same."""

    Dh = 64


# The transformer cell's shapes: 4 rows of 4,096 frames, 32 query heads on 4 of 128, blocks of 256.
_CELL_Q, _CELL_K = (4, 4096, 32, 128), (4, 4096, 4, 128)


@pytest.mark.parametrize("platform,q,k,kv_block,mesh,takes", [
    ("tpu", _CELL_Q, _CELL_K, 256, "", True),
    ("tpu", _CELL_Q, _CELL_K, 256, "dp=4", True),  # mapped over dp
    ("cpu", _CELL_Q, _CELL_K, 256, "", False),
    ("gpu", _CELL_Q, _CELL_K, 256, "", False),
    ("tpu", (4, 1, 32, 128), _CELL_K, 256, "", False),  # the actor's step over its cache
    ("tpu", (4, 4096, 32, 64), (4, 4096, 8, 64), 256, "", True),  # heads of 64 fill half the lanes
    ("tpu", (4, 4096, 32, 96), (4, 4096, 4, 96), 256, "", False),  # narrow heads of another width
    ("tpu", (4, 4095, 32, 128), (4, 4095, 4, 128), 256, "", False),  # a ragged chunk
    ("tpu", _CELL_Q, _CELL_K, 0, "", False),  # blocked attention is off
    ("tpu", (4, 256, 32, 128), (4, 256, 4, 128), 256, "", False),  # one block: the dense path
    ("tpu", _CELL_Q, _CELL_K, 256, "dp=1,sp=2", False),  # the time axis is sharded
    ("tpu", _CELL_Q, _CELL_K, 256, "dp=2,tp=2", False),  # heads may be sharded: not partitioned
    ("tpu", (6, 4096, 32, 128), (6, 4096, 4, 128), 256, "dp=4", False),  # rows do not divide
], ids=["cell", "cell_dp4", "cpu", "gpu", "step_with_cache", "head_width_64", "head_width_96", "ragged_T",
        "block_off",
        "one_block", "sp_mesh", "tp_mesh", "rows_not_by_dp"])
def test_where_the_fused_kernel_is_taken(platform, q, k, kv_block, mesh, takes):
    n = int(np.prod([int(a.split("=")[1]) for a in mesh.split(",")])) if mesh else 0
    mesh = mesh_lib.make_mesh(mesh, devices=jax.devices()[:n]) if mesh else None
    got = RA.fused_applies(platform, q, k, kv_block, mesh=mesh,
                           sp_axis="sp" if mesh is not None and "sp" in mesh.axis_names else "")
    assert got is takes


def test_the_fused_kernel_mapped_over_dp_matches_one_device(monkeypatch):
    """On a mesh of several devices the kernel is shard_mapped over the
    rows: output and gradients as on one device."""
    mesh = mesh_lib.make_mesh("dp=4", devices=jax.devices()[:4])
    B, T, N, G, Dh = 4, 256, 2, 1, 128
    q = jnp.asarray(_rand((B, T, N, Dh), 120))
    k, v = (jnp.asarray(_rand((B, T, G, Dh), s)) for s in (121, 122))
    pos = _positions(B, T)
    kernel = A.fused_causal_attention
    monkeypatch.setattr(A, "fused_causal_attention", lambda q, k, v, window=0, q_scaled=False: kernel(
        q, k, v, window, interpret=True, q_scaled=q_scaled, tiles=(128, 128)))
    f = lambda mesh: jax.jit(jax.value_and_grad(lambda q, k, v: jnp.sum(
        RA.attend(q, k, v, pos, pos, mesh=mesh, kv_block=64, window=100, fused=True) ** 2), (0, 1, 2)))
    want, g_want = f(None)(q, k, v)
    got, g_got = f(mesh)(q, k, v)
    plain = jnp.sum(A.causal_attention(q * Dh**0.5, k, v, pos, pos, 100) ** 2)  # fused=True: q holds the scale
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(got, plain, rtol=1e-4)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
