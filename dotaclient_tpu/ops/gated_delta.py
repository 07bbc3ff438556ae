"""The gated delta rule — the linear-attention layer's recurrence over the
time axis (Gated DeltaNet, Yang et al. 2024, as Qwen3-Next's published
block uses it), and the causal depthwise convolution in front of it.

Per value head the layer keeps a matrix S [d_k, d_v] and, frame by frame,

    S  <- exp(g_t) S                      decay, g_t <= 0
    u_t = beta_t (v_t - S^T k_t)          what the state does not yet say of v_t
    S  <- S + k_t u_t^T                   written at k_t with strength beta_t
    o_t = S^T q_t

so a row costs O(T) and the actor's state is S and nothing per frame.
Three forms of the one function:

- `step`: one frame, the actor's (S in, S out).
- `recurrent`: `lax.scan` of `step` over the row. Exact, sequential in
  every frame and an order slower than the chunked form on a TPU: the
  oracle of the tests and nothing the program runs.
- `chunked`: the learner's, whatever the row's length. The row is cut
  into chunks of C frames (CHUNK, 64: the published kernels'); inside
  a chunk the dependence of u on the earlier u is a unit lower-triangular
  system, solved for every chunk at once, and only the state passes from
  chunk to chunk (T / C sequential steps of two small products). With
  gamma_i the running sum of g inside a chunk and S the state entering it:

      A_ij = -beta_i (k_i . k_j) exp(gamma_i - gamma_j)   j < i, else 0
      T    = (I - A)^-1                                     A is strictly lower triangular
      V'   = T (beta v),  K' = T (beta k exp(gamma))
      V''  = V' - K' S                                      the chunk's u
      O    = (q exp(gamma)) S + tril((q k^T) exp(gamma_i - gamma_j)) V''
      S   <- exp(gamma_C) S + (k exp(gamma_C - gamma))^T V''

  Every decay is a difference gamma_i - gamma_j with i >= j, so no
  exponent is positive. A row that is no multiple of C is padded with
  frames of beta = 0 and g = 0, which leave the state as it is and whose
  outputs are cut off. Decays, beta, the system's solution and the state
  are float32; the operands of the products are the caller's compute
  type, accumulated in float32, as attention's are. The backward pass is
  autodiff's, through the solve's products and the scan over chunks.

Key head j serves value heads [j R, (j + 1) R), R = value heads / key
heads: q and k come in with the key heads' axis and are never repeated.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6  # under the root of a head's squared norm (the published constant)


def l2norm(x: jnp.ndarray) -> jnp.ndarray:
    """x over the root of its squared norm + eps, along the last axis, f32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def causal_conv(x: jnp.ndarray, w: jnp.ndarray, tail: Optional[jnp.ndarray] = None):
    """Causal depthwise convolution over the frame axis: x [B, T, ..ch],
    one filter a channel w [K, ..ch], out_t = sum_j w[j] x_(t - (K-1) + j)
    (the last tap is this frame's), with `tail` [B, K-1, ..ch] the K - 1
    frames before the row, zeros where None. Returns (out f32, the row's
    last K - 1 frames with the tail's before them where the row is
    shorter: the next call's tail, in x's type)."""
    K, T = w.shape[0], x.shape[1]
    if tail is None:
        tail = jnp.zeros(x.shape[:1] + (K - 1,) + x.shape[2:], x.dtype)
    padded = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # [B, K-1 + T, ..ch]
    w = w.astype(jnp.float32)
    out = sum(padded[:, j:j + T].astype(jnp.float32) * w[j] for j in range(K))
    return out, padded[:, T:]


def step(S, q, k, v, beta, g):
    """One frame of the rule. S [B, Hv, dk, dv] f32; q, k [B, Hk, dk];
    v [B, Hv, dv]; beta, g [B, Hv]. Returns (S, o [B, Hv, dv]), float32."""
    R = v.shape[-2] // q.shape[-2]
    q, k = (jnp.repeat(a.astype(jnp.float32), R, axis=-2) for a in (q, k))
    S = jnp.exp(g)[..., None, None] * S
    u = beta[..., None] * (v.astype(jnp.float32) - jnp.einsum("bhkv,bhk->bhv", S, k, precision=HI))
    S = S + k[..., :, None] * u[..., None, :]
    return S, jnp.einsum("bhkv,bhk->bhv", S, q, precision=HI)


def zero_state(B: int, Hv: int, dk: int, dv: int) -> jnp.ndarray:
    return jnp.zeros((B, Hv, dk, dv), jnp.float32)


def recurrent(q, k, v, beta, g, state=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The rule frame by frame: q, k [B, T, Hk, dk], v [B, T, Hv, dv],
    beta, g [B, T, Hv] -> (o [B, T, Hv, dv] f32, the state after the
    row's last frame [B, Hv, dk, dv] f32), from `state` or zero."""
    B, _, Hv, dv = v.shape
    S = zero_state(B, Hv, q.shape[-1], dv) if state is None else state
    by_frame = lambda a: jnp.moveaxis(a, 1, 0)
    S, o = jax.lax.scan(lambda S, xs: step(S, *xs), S, tuple(map(by_frame, (q, k, v, beta, g))))
    return jnp.moveaxis(o, 0, 1), S


SOLVE_BLOCK = 16  # rows solved by substitution before blocks are merged by products


def _diagonal_blocks(A: jnp.ndarray, size: int) -> jnp.ndarray:
    """[.., C, C] -> [.., C / size, size, size], the blocks on the diagonal."""
    return jnp.stack([A[..., i:i + size, i:i + size] for i in range(0, A.shape[-1], size)], axis=-3)


def _solve(A: jnp.ndarray) -> jnp.ndarray:
    """(I - A)^-1 of strictly lower-triangular A [.., C, C], float32.
    Forward substitution row by row inside diagonal blocks of SOLVE_BLOCK
    rows (all blocks at once), then pairs of inverted blocks merged,
    [[T1, 0], [T2 A21 T1, T2]], until one is left: 16 small steps and
    two rounds of products for C = 64, every step backward-stable. (The
    product (I + A)(I + A^2)(I + A^4)... is the same matrix in exact
    arithmetic and not in float32: where the frames of a chunk have
    nearly one key, which seeded weights give, A is nearly -1 below the
    diagonal, A^32 has entries of 1e17 whose sum is 1, and one seed of
    three read NaN on the chip. PERF.md, PR 38.)"""
    C = A.shape[-1]
    b = SOLVE_BLOCK if C % SOLVE_BLOCK == 0 and (C // SOLVE_BLOCK) & (C // SOLVE_BLOCK - 1) == 0 else C
    D = _diagonal_blocks(A, b)  # [.., C / b, b, b]
    eye = jnp.eye(b, dtype=A.dtype)
    rows = []  # row i of a block's inverse: e_i + sum over j < i of A_ij (row j)
    for i in range(b):
        above = jnp.einsum("...j,...jk->...k", D[..., i, :i], jnp.stack(rows, axis=-2), precision=HI) if i else 0.0
        rows.append(jnp.broadcast_to(eye[i], D.shape[:-2] + (b,)) + above)
    T = jnp.stack(rows, axis=-2)
    while b < C:
        below = _diagonal_blocks(A, 2 * b)[..., b:, :b]  # A21 of each pair
        T1, T2 = T[..., 0::2, :, :], T[..., 1::2, :, :]
        corner = jnp.matmul(jnp.matmul(T2, below, precision=HI), T1, precision=HI)
        T = jnp.concatenate([jnp.concatenate([T1, jnp.zeros_like(T1)], axis=-1),
                             jnp.concatenate([corner, T2], axis=-1)], axis=-2)
        b *= 2
    return T[..., 0, :, :]


CHUNK = 64  # frames solved as one triangular system (the published kernels' chunk)
SEGMENT = 8  # chunks worked on at once; a size of the computation: what the backward pass keeps is a segment's


def chunked(q, k, v, beta, g, chunk: int, state=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`recurrent` in chunks of `chunk` frames (the module's docstring has
    the equations; the program passes CHUNK, the tests smaller ones too). q, k, v in the compute type (q already scaled), beta
    and g float32. The row is worked on SEGMENT chunks at a time, each
    segment under `jax.checkpoint`: the backward pass keeps the state
    entering each segment and computes a segment's triangular systems
    and states again when it reaches it, so what lives at once is a
    segment's and not the row's (the TPU compiler's count of one block's
    scratch at 4 rows of 4,096 frames and 32 value heads of 128: 2.7 GB,
    where the row at once took 7.2)."""
    B, T, Hk, dk = q.shape
    Hv, dv = v.shape[-2:]
    n = -(-T // chunk)
    seg = min(n, SEGMENT) * chunk  # frames of a segment
    pad = -T % seg
    if pad:  # frames that write nothing and decay nothing; their outputs are cut off
        q, k, v, beta, g = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)) for a in (q, k, v, beta, g))
    by_segment = lambda a: jnp.moveaxis(a.reshape((B, -1, seg) + a.shape[2:]), 1, 0)
    S0 = zero_state(B, Hv, dk, dv) if state is None else state
    S, o = jax.lax.scan(jax.checkpoint(lambda S, xs: _segment(S, *xs, chunk)), S0,
                        tuple(map(by_segment, (q, k, v, beta, g))))
    return jnp.moveaxis(o, 0, 1).reshape(B, T + pad, Hv, dv)[:, :T], S


def _segment(state, q, k, v, beta, g, chunk: int):
    """A whole number of chunks from `state` on: (the state after them,
    o [B, T, Hv, dv] f32). Everything inside a chunk for all chunks at
    once, then the state from chunk to chunk, then the outputs."""
    B, T, Hk, dk = q.shape
    Hv, dv = v.shape[-2:]
    R, C, dt, f32 = Hv // Hk, chunk, v.dtype, jnp.float32
    n = T // C
    # chunks [B, n, C, ..]; per value head the axes are (Hk, R)
    q, k = q.reshape(B, n, C, Hk, dk), k.reshape(B, n, C, Hk, dk)
    v = v.reshape(B, n, C, Hk, R, dv)
    head_major = lambda a: jnp.moveaxis(a.reshape(B, n, C, Hk, R).astype(f32), 2, -1)  # [B, n, Hk, R, C]
    beta, gamma = head_major(beta), jnp.cumsum(head_major(g), axis=-1)
    lower = jnp.tril(jnp.ones((C, C), bool))
    diff = gamma[..., :, None] - gamma[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)  # exp(gamma_i - gamma_j), j <= i

    kk = jnp.einsum("bnihd,bnjhd->bnhij", k, k, preferred_element_type=f32)[:, :, :, None]  # [B, n, Hk, 1, C, C]
    A = -jnp.where(jnp.tril(lower, -1), beta[..., :, None] * kk * decay, 0.0)
    solve = _solve(A).astype(dt)  # [B, n, Hk, R, C, C]

    rows = lambda a: jnp.moveaxis(a, -1, 2)[..., None]  # [B, n, Hk, R, C] -> [B, n, C, Hk, R, 1]
    k_r = k[:, :, :, :, None].astype(f32)  # [B, n, C, Hk, 1, dk]
    v_new = jnp.einsum("bnhrij,bnjhrd->bnihrd", solve, (v.astype(f32) * rows(beta)).astype(dt),
                       preferred_element_type=f32)  # V'
    k_new = jnp.einsum("bnhrij,bnjhrd->bnihrd", solve, (k_r * rows(beta * jnp.exp(gamma))).astype(dt),
                       preferred_element_type=f32).astype(dt)  # K'
    k_left = (k_r * rows(jnp.exp(gamma[..., -1:] - gamma))).astype(dt)  # k exp(gamma_C - gamma)
    last = jnp.exp(gamma[..., -1])  # [B, n, Hk, R]

    def chunk_step(S, xs):  # S [B, Hk, R, dk, dv] f32: the only work that waits on the chunk before
        k_new, v_new, k_left, last = xs
        u = v_new - jnp.einsum("bihrk,bhrkv->bihrv", k_new, S.astype(dt), preferred_element_type=f32)  # V''
        S_next = last[..., None, None] * S + jnp.einsum("bihrk,bihrv->bhrkv", k_left, u.astype(dt),
                                                        preferred_element_type=f32)
        return S_next, (S.astype(dt), u.astype(dt))

    S0 = state.reshape(B, Hk, R, dk, dv)
    by_chunk = lambda a: jnp.moveaxis(a, 1, 0)
    S, (entering, u) = jax.lax.scan(chunk_step, S0, tuple(map(by_chunk, (k_new, v_new, k_left, last))))
    entering, u = jnp.moveaxis(entering, 0, 1), jnp.moveaxis(u, 0, 1)  # [B, n, Hk, R, dk, dv], [B, n, C, Hk, R, dv]

    q_in = (q[:, :, :, :, None].astype(f32) * rows(jnp.exp(gamma))).astype(dt)  # q exp(gamma)
    qk = jnp.einsum("bnihd,bnjhd->bnhij", q, k, preferred_element_type=f32)[:, :, :, None]
    o = jnp.einsum("bnihrk,bnhrkv->bnihrv", q_in, entering, preferred_element_type=f32)
    o = o + jnp.einsum("bnhrij,bnjhrv->bnihrv", (qk * decay).astype(dt), u, preferred_element_type=f32)
    return S.reshape(B, Hv, dk, dv), o.reshape(B, T, Hv, dv)
