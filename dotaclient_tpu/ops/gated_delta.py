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
  type, accumulated in float32, as attention's are.

  The backward pass is the rule's own (`jax.custom_vjp`), one reverse pass
  over the chunks. The forward keeps, under the checkpoint name
  RULE_RESIDUALS, the three arrays it has already rounded to the compute
  type: T, the state S entering each chunk, and V'' (and O, which the
  backward does not read, so that a caller's rematerialisation that saves
  the name computes nothing of the forward again). Everything else is
  one elementwise pass or one product from q, k, v, beta and g. With dO
  and dS the cotangents of O and of the state leaving a chunk, P the
  tril(..) above and k_C = k exp(gamma_C - gamma):

      dP   = tril(dO V''^T),   dq' = dO S^T                 q' = q exp(gamma)
      dV'' = P^T dO + k_C dS                                  dV' is dV''
      dk_C = V'' dS^T,   dgamma_C += exp(gamma_C) <S, dS>
      dS  <- exp(gamma_C) dS + q'^T dO - K'^T dV''            the only scan, last chunk first
      dK'  = -dV'' S^T
      dT   = dV' (beta v)^T + dK' (beta k exp(gamma))^T
      dA   = T^T dT T^T below the diagonal                    from T = (I - A)^-1, at HI

  and from dA, dP, T^T dV', T^T dK', dq' and dk_C the cotangents of q, k,
  v and beta by the product rule, gamma's from every exp it stands in
  (each exp's cotangent times the exp), and g's as gamma's summed from the
  chunk's end back. Products take the compute type and accumulate in
  float32, as forward; dS, the decays, beta and dA are float32.

Key head j serves value heads [j R, (j + 1) R), R = value heads / key
heads: q and k come in with the key heads' axis and are never repeated.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

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
SEGMENT = 2  # chunks worked on at once, forward and backward; a size of the computation: fewest ms a step on a v5e
RULE_RESIDUALS = "gated_delta_residuals"  # the checkpoint name of what `chunked` keeps for its backward pass


def chunked(q, k, v, beta, g, chunk: int, state=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`recurrent` in chunks of `chunk` frames (the module's docstring has
    the equations, forward and backward; the program passes CHUNK, the
    tests smaller ones too). q, k, v in the compute type (q already
    scaled), beta and g float32. The row is worked on SEGMENT chunks at a
    time, forward and backward, each a `lax.scan` over the segments: what
    lives at once is a segment's, small enough to stay in the chip's fast
    memory (PERF.md, PR 40: the whole row at once took half as long
    again). The backward pass is the rule's own (`_rule`): it keeps T, the
    state entering each chunk and u in the compute type and o in float32
    under the name RULE_RESIDUALS (at 4 rows of 4,096 frames and 32 value
    heads of 128 in bfloat16: 67 + 268 + 134 + 268 MB a layer), and a
    `jax.checkpoint` around the caller whose policy saves that name runs
    neither the solve nor the scans a second time."""
    T = q.shape[1]
    pad = -T % _segment_frames(T, chunk)
    if pad:  # frames that write nothing and decay nothing; their outputs are cut off
        q, k, v, beta, g = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)) for a in (q, k, v, beta, g))
    S0 = zero_state(q.shape[0], v.shape[2], q.shape[3], v.shape[3]) if state is None else state
    o, S = _rule(q, k, v, beta, g, S0, chunk)
    return o[:, :T], S


def _segment_frames(T: int, chunk: int) -> int:
    """The frames of a segment in a row of T: SEGMENT chunks, or the row's own where it has fewer."""
    return min(-(-T // chunk), SEGMENT) * chunk


def _within(q, k, v, beta, g, C: int, solve=None) -> SimpleNamespace:
    """Everything of a row of whole chunks that waits on no state, for all
    chunks at once (the backward pass hands in the `solve` it kept). Axes:
    chunks [B, n, C, ..], per value head (Hk, R); what is per frame and
    value head is head-major, [B, n, Hk, R, C]."""
    B, T, Hk, dk = q.shape
    Hv, dv = v.shape[-2:]
    R, dt, f32 = Hv // Hk, v.dtype, jnp.float32
    n = T // C
    q, k = q.reshape(B, n, C, Hk, dk), k.reshape(B, n, C, Hk, dk)
    v = v.reshape(B, n, C, Hk, R, dv)
    head_major = lambda a: jnp.moveaxis(a.reshape(B, n, C, Hk, R).astype(f32), 2, -1)  # [B, n, Hk, R, C]
    beta, gamma = head_major(beta), jnp.cumsum(head_major(g), axis=-1)
    lower = jnp.tril(jnp.ones((C, C), bool))
    diff = gamma[..., :, None] - gamma[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)  # exp(gamma_i - gamma_j), j <= i

    kk = jnp.einsum("bnihd,bnjhd->bnhij", k, k, preferred_element_type=f32)[:, :, :, None]  # [B, n, Hk, 1, C, C]
    if solve is None:
        A = -jnp.where(jnp.tril(lower, -1), beta[..., :, None] * kk * decay, 0.0)
        solve = _solve(A).astype(dt)  # T [B, n, Hk, R, C, C]

    rows = lambda a: jnp.moveaxis(a, -1, 2)[..., None]  # [B, n, Hk, R, C] -> [B, n, C, Hk, R, 1]
    k_r = k[:, :, :, :, None].astype(f32)  # [B, n, C, Hk, 1, dk]
    grown, left = jnp.exp(gamma), jnp.exp(gamma[..., -1:] - gamma)  # exp(gamma), exp(gamma_C - gamma)
    bv = (v.astype(f32) * rows(beta)).astype(dt)  # beta v
    bk = (k_r * rows(beta * grown)).astype(dt)  # beta k exp(gamma)
    v_new = jnp.einsum("bnhrij,bnjhrd->bnihrd", solve, bv, preferred_element_type=f32)  # V'
    k_new = jnp.einsum("bnhrij,bnjhrd->bnihrd", solve, bk, preferred_element_type=f32).astype(dt)  # K'
    k_left = (k_r * rows(left)).astype(dt)  # k exp(gamma_C - gamma)
    q_in = (q[:, :, :, :, None].astype(f32) * rows(grown)).astype(dt)  # q exp(gamma)
    qk = jnp.einsum("bnihd,bnjhd->bnhij", q, k, preferred_element_type=f32)[:, :, :, None]
    return SimpleNamespace(
        q=q, k=k, v=v, beta=beta, lower=lower, decay=decay, kk=kk, qk=qk, solve=solve, rows=rows, grown=grown,
        left=left, last=jnp.exp(gamma[..., -1]), bv=bv, bk=bk, v_new=v_new, k_new=k_new, k_left=k_left, q_in=q_in,
        within=(qk * decay).astype(dt))  # tril((q k^T) exp(gamma_i - gamma_j))


_by_chunk = lambda a: jnp.moveaxis(a, 1, 0)


def _by_segment(a, every: int):
    """[B, n every, ..] -> [n, B, every, ..]: a row's frames or chunks, `every` to a segment."""
    return _by_chunk(a.reshape((a.shape[0], -1, every) + a.shape[2:]))


def _row(a):
    """[n, B, every, ..] -> [B, n every, ..]: the segments' results side by side again."""
    a = _by_chunk(a)
    return a.reshape((a.shape[0], -1) + a.shape[3:])


def _forward(q, k, v, beta, g, state, chunk: int):
    """A row of whole segments from `state` on: (o [B, T, Hv, dv] f32, the
    state after it, what the backward pass keeps). Per segment: everything
    inside a chunk for its chunks at once, then the state from chunk to
    chunk, then the outputs. o carries the kept arrays' name too: where
    the name is saved, nothing of this function runs again."""
    B, T, Hk, dk = q.shape
    Hv, dv = v.shape[-2:]
    dt, f32 = v.dtype, jnp.float32

    def chunk_step(S, xs):  # S [B, Hk, R, dk, dv] f32: the only work that waits on the chunk before
        k_new, v_new, k_left, last = xs
        u = v_new - jnp.einsum("bihrk,bhrkv->bihrv", k_new, S.astype(dt), preferred_element_type=f32)  # V''
        S_next = last[..., None, None] * S + jnp.einsum("bihrk,bihrv->bhrkv", k_left, u.astype(dt),
                                                        preferred_element_type=f32)
        return S_next, (S.astype(dt), u.astype(dt))

    def segment(S, xs):
        w = _within(*xs, chunk)
        S, (entering, u) = jax.lax.scan(chunk_step, S, tuple(map(_by_chunk, (w.k_new, w.v_new, w.k_left, w.last))))
        entering, u = _by_chunk(entering), _by_chunk(u)  # [B, n, Hk, R, dk, dv], [B, n, C, Hk, R, dv]
        o = jnp.einsum("bnihrk,bnhrkv->bnihrv", w.q_in, entering, preferred_element_type=f32)
        o = o + jnp.einsum("bnhrij,bnjhrv->bnihrv", w.within, u, preferred_element_type=f32)
        return S, (w.solve, entering, u, o)

    S, kept = jax.lax.scan(segment, state.reshape(B, Hk, Hv // Hk, dk, dv),
                           tuple(_by_segment(a, _segment_frames(T, chunk)) for a in (q, k, v, beta, g)))
    solve, entering, u, o = checkpoint_name(tuple(map(_row, kept)), RULE_RESIDUALS)
    return o.reshape(B, T, Hv, dv), S.reshape(B, Hv, dk, dv), (solve, entering, u)


def _chunks(q, k, v, beta, g, state, chunk: int):
    return _forward(q, k, v, beta, g, state, chunk)[:2]


_rule = jax.custom_vjp(_chunks, nondiff_argnums=(6,))  # `_chunks` with the backward pass below for autodiff's


def _rule_fwd(q, k, v, beta, g, state, chunk: int):
    o, S, kept = _forward(q, k, v, beta, g, state, chunk)
    return (o, S), (q, k, v, beta, g) + kept


def _rule_bwd(chunk: int, kept, cotangents):
    """The module docstring's backward equations, a segment at a time from
    the row's last: the cotangents of q, k, v, beta, g and the entering
    state from those of o and the last state. Products take their
    operands in the compute type and accumulate in float32 (a float32
    cotangent against a bfloat16 array is what autodiff multiplies too,
    in one bfloat16 pass on a TPU); dS, the decays, beta and dA are
    float32, dA's products at HI."""
    do, dS = cotangents
    every = _segment_frames(kept[0].shape[1], chunk)
    frames = tuple(_by_segment(a, every) for a in kept[:5] + (do,))  # q, k, v, beta, g, dO
    systems = tuple(_by_segment(a, every // chunk) for a in kept[5:])  # T, the entering states, u
    dS, grads = jax.lax.scan(lambda dS, xs: _segment_bwd(chunk, dS, *xs), dS, frames + systems, reverse=True)
    return tuple(map(_row, grads)) + (dS,)


def _segment_bwd(chunk: int, dS_last, q, k, v, beta, g, do, solve, entering, u):
    """One segment of `_rule_bwd`: from the cotangent of the state leaving
    it to (that of the state entering it, those of its q, k, v, beta, g)."""
    B, T, Hk, Dk = q.shape
    Hv, Dv = v.shape[-2:]
    R, C, dt, f32 = Hv // Hk, chunk, v.dtype, jnp.float32
    dot = functools.partial(jnp.einsum, preferred_element_type=f32)
    w = _within(q, k, v, beta, g, C, solve)
    do = do.reshape(B, T // C, C, Hk, R, Dv).astype(dt)

    # what waits on no state
    d_within = dot("bnihrv,bnjhrv->bnhrij", do, u)  # of tril(..): dO u^T
    du_within = dot("bnhrij,bnihrv->bnjhrv", w.within, do)
    dq_in = dot("bnihrv,bnhrkv->bnihrk", do, entering)
    dS_read = dot("bnihrk,bnihrv->bnhrkv", w.q_in, do)  # what o reads of each chunk's entering state

    def chunk_step(dS, xs):  # dS [B, Hk, R, dk, dv] f32: of the state leaving this chunk
        k_new, k_left, last, entering, u, du_within, dS_read = xs
        dS_b = dS.astype(dt)
        du = (du_within + dot("bihrk,bhrkv->bihrv", k_left, dS_b)).astype(dt)
        dk_left = dot("bihrv,bhrkv->bihrk", u, dS_b)
        dlast = jnp.sum(entering.astype(f32) * dS, axis=(-2, -1))
        dS = last[..., None, None] * dS + dS_read - dot("bihrk,bihrv->bhrkv", k_new, du)
        return dS, (du, dk_left, dlast)

    dS, (du, dk_left, dlast) = jax.lax.scan(
        chunk_step, dS_last.reshape(B, Hk, R, Dk, Dv),
        tuple(map(_by_chunk, (w.k_new, w.k_left, w.last, entering, u, du_within, dS_read))), reverse=True)
    du, dk_left, dlast = (jnp.moveaxis(a, 0, 1) for a in (du, dk_left, dlast))  # du is dV' too

    # the triangular system, in closed form: T = (I - A)^-1, so dA = T^T dT T^T below the diagonal
    dk_new = (-dot("bnihrv,bnhrkv->bnihrk", du, entering)).astype(dt)
    d_solve = dot("bnihrv,bnjhrv->bnhrij", du, w.bv) + dot("bnihrk,bnjhrk->bnhrij", dk_new, w.bk)
    dbv = dot("bnhrij,bnihrv->bnjhrv", solve, du)
    dbk = dot("bnhrij,bnihrk->bnjhrk", solve, dk_new)
    solve = solve.astype(f32)
    dA = jnp.einsum("bnhrai,bnhrac->bnhric", solve, d_solve, precision=HI)
    dA = jnp.einsum("bnhric,bnhrjc->bnhrij", dA, solve, precision=HI)
    d_system = jnp.where(jnp.tril(w.lower, -1), -dA, 0.0)  # of beta_i (k_i . k_j) exp(gamma_i - gamma_j)

    # back through the decays, beta and the products of q and k
    cols = lambda a: jnp.moveaxis(a[..., 0], 2, -1)  # [B, n, C, Hk, R, 1] -> [B, n, Hk, R, C]
    over_width = lambda a, b: cols(jnp.sum(a * b, axis=-1, keepdims=True))
    k_r, q_r = w.k[:, :, :, :, None].astype(f32), w.q[:, :, :, :, None].astype(f32)
    d_decay = (d_system * w.beta[..., :, None] * w.kk + d_within * w.qk) * w.decay  # times the decay: of its exponent
    written = over_width(dbk, k_r)  # of beta exp(gamma), frame by frame
    d_left = over_width(dk_left, k_r) * w.left  # of gamma_C - gamma
    dgamma = (written * w.beta + over_width(dq_in, q_r)) * w.grown - d_left + d_decay.sum(-1) - d_decay.sum(-2)
    dgamma = dgamma.at[..., -1].add(d_left.sum(-1) + dlast * w.last)
    dg = jnp.flip(jnp.cumsum(jnp.flip(dgamma, -1), axis=-1), -1)
    dbeta = jnp.sum(d_system * w.kk * w.decay, axis=-1) + over_width(dbv, w.v.astype(f32)) + written * w.grown
    dkk = jnp.sum(d_system * w.beta[..., :, None] * w.decay, axis=3)  # [B, n, Hk, C, C], over a key head's value heads
    dqk = jnp.sum(d_within * w.decay, axis=3).astype(dt)
    dq = dot("bnhij,bnjhd->bnihd", dqk, w.k) + jnp.sum(dq_in * w.rows(w.grown), axis=4)
    dk = (dot("bnhij,bnihd->bnjhd", dqk, w.q)
          + dot("bnhij,bnjhd->bnihd", (dkk + jnp.swapaxes(dkk, -1, -2)).astype(dt), w.k)
          + jnp.sum(dbk * w.rows(w.beta * w.grown) + dk_left * w.rows(w.left), axis=4))
    dv = dbv * w.rows(w.beta)
    frames = lambda a: jnp.moveaxis(a, -1, 2).reshape(B, T, Hv)  # [B, n, Hk, R, C] -> [B, T, Hv]
    return dS.reshape(B, Hv, Dk, Dv), (
        dq.reshape(q.shape).astype(q.dtype), dk.reshape(k.shape).astype(k.dtype), dv.reshape(v.shape).astype(dt),
        frames(dbeta).astype(beta.dtype), frames(dg).astype(g.dtype))


_rule.defvjp(_rule_fwd, _rule_bwd)
