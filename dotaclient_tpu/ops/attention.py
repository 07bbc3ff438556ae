"""Causal attention over the time axis — the transformer family's core op.

The reference's only temporal model is the LSTM (SURVEY.md §3.3); its
"long-context / sequence parallelism" row is N/A because chunk length is
~16. This op exists for the scale path the reference never had: training
on long chunks (T in the hundreds-to-thousands) where the O(T²) attention
is the dominant FLOP/memory term and the time axis itself must shard over
devices (ops/ring_attention.py rides on the block primitive here).

Design, TPU-first:

- **Positions are data, masking is arithmetic.** Every variant takes
  absolute int32 positions for queries and keys and derives causality as
  `k_pos <= q_pos`. No Python control flow, no shape-dependent mask
  construction — the same compiled code serves full unroll, KV-cache
  stepping (empty cache slots carry a sentinel position that can never
  satisfy the inequality), and ring blocks (rotating K/V shards carry
  their positions with them, so no block-offset bookkeeping exists at
  all).
- **Streaming softmax as the shared primitive.** `accumulate_block` is
  the flash-attention inner step (running max `m`, normalizer `l`,
  unnormalized accumulator `acc`); full attention is the one-block
  special case and ring attention is the N-block loop. One set of
  numerics to test, f32 throughout the softmax regardless of the matmul
  dtype (bf16 inputs hit the MXU; the exp/normalizer math does not
  deserve bf16).
- **RoPE for positions.** Rotary embeddings commute with KV caching and
  with ring rotation (angles depend only on absolute positions, which
  travel with the tensors), unlike learned absolute embeddings which
  would pin the context length at init time.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Sentinel position for "no key here" (empty KV-cache slot). Any real
# query position is < this, so the causal test k_pos <= q_pos masks it.
EMPTY_POS = jnp.iinfo(jnp.int32).max

# Logit value for masked scores. Finite (not -inf) so a hypothetical
# all-masked row yields zeros after the explicit `where` in the exp, not
# NaN. (Causal attention always has >= 1 valid key — the query itself —
# but the primitive must not rely on its caller's geometry.)
_NEG = -1e30


def rope_table(
    head_dim: int,
    theta: float = 10000.0,
    yarn_factor: float = 0.0,
    yarn_original_context: int = 0,
    yarn_beta_fast: float = 32.0,
    yarn_beta_slow: float = 1.0,
) -> Tuple[np.ndarray, float]:
    """(inverse frequencies [head_dim // 2] float32, factor on cos and sin).

    The default table is theta ** (-2i / head_dim). With `yarn_factor`
    (YaRN, Peng et al. 2023, as the published configs state it): the
    default frequencies and those divided by the factor, blended per
    frequency by a linear ramp over the correction range, which is where
    a rotation makes `beta_fast` and `beta_slow` turns within the
    original context (floor and ceiling taken, clipped to the table); cos
    and sin are multiplied by 0.1 ln(factor) + 1. Worked out in float64
    at trace time: a constant of the program.
    """
    half = head_dim // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    if not yarn_factor:
        return inv.astype(np.float32), 1.0

    def turns_at(rotations):  # the (fractional) index whose frequency makes that many turns
        return (head_dim * math.log(yarn_original_context / (rotations * 2 * math.pi))) / (
            2 * math.log(theta)
        )

    low = max(math.floor(turns_at(yarn_beta_fast)), 0)
    high = min(math.ceil(turns_at(yarn_beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = inv / yarn_factor * ramp + inv * (1.0 - ramp)
    return inv.astype(np.float32), 0.1 * math.log(yarn_factor) + 1.0


def _partner(start: int, width: int, head_dim: int) -> np.ndarray:
    """The 0/1 matrix [head_dim, head_dim] that hands every lane of the
    rotary span [start, start + width) the lane half a span away, and
    the lanes outside it nothing: x @ it is the swap of the span's two
    halves."""
    half = width // 2
    swap = np.zeros((head_dim, head_dim), np.float32)
    lanes = start + np.arange(half)
    swap[lanes + half, lanes] = swap[lanes, lanes + half] = 1.0
    return swap


def rope(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    base: float = 10000.0,
    table=None,
    span: Optional[Tuple[int, int]] = None,
    scale: float = 1.0,
) -> jnp.ndarray:
    """Rotary position embedding, at the head's whole width.

    x [.., T, N, Dh], positions [.., T] int32 absolute positions.
    `table`: a `rope_table` in place of `base`'s default one. `span`:
    the (first lane, even width) of a head that rotates, lane i of its
    first half with lane i of its second; the whole head where None.
    `scale`: a factor on every lane, rotated or not (the fused kernel
    wants the scores' 1/sqrt(Dh) in q, and here q is still float32).
    Angle math in f32; result cast back to x.dtype, its one rounding.

    out = x * C + partner(x) * S, with C = [cos, cos] and S = [-sin, sin]
    over the span and (scale, 0) on the lanes beside it, tables of the
    head's width, and partner(x) the swap of the span's halves as a
    product with a constant 0/1 matrix (`_partner`; exact in any type:
    an output is one input times one). x comes in and goes out whole,
    [.., N, Dh], on full lanes. The split form (x[..., :half] and
    x[..., half:] rotated apart and concatenated, and for a span inside
    a wider head a split and a concatenation around that) computes the
    same numbers, and on a TPU each of its pieces is a copy of part of a
    head, 32 to 192 lanes of it, that no product hides, forward, in the
    rematerialisation and in the backward pass. With the splits around
    the projections they were 78 ms of the latent cell's 456-ms step and
    37 of the grouped-query cell's 358 (PERF.md, PR 37).
    """
    Dh = x.shape[-1]
    start, width = span or (0, Dh)
    freqs, factor = table if table is not None else rope_table(width, base)
    # Sentinel positions would produce garbage angles; they belong to
    # empty cache slots whose scores are masked anyway, so zero them to
    # keep the trig finite.
    pos = jnp.where(positions == EMPTY_POS, 0, positions).astype(jnp.float32)
    ang = pos[..., None] * freqs  # [.., T, width // 2]
    cos, sin = jnp.cos(ang) * (factor * scale), jnp.sin(ang) * (factor * scale)
    beside = [(0, 0)] * (ang.ndim - 1) + [(start, Dh - start - width)]
    C = jnp.pad(jnp.concatenate([cos, cos], axis=-1), beside, constant_values=scale)
    S = jnp.pad(jnp.concatenate([-sin, sin], axis=-1), beside)
    partner = jnp.dot(x, jnp.asarray(_partner(start, width, Dh), x.dtype),
                      precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
    # [.., T, 1, Dh] tables broadcast over heads
    out = x.astype(jnp.float32) * C[..., None, :] + partner * S[..., None, :]
    return out.astype(x.dtype)


def accumulate_block(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_pos: jnp.ndarray,
    k_pos: jnp.ndarray,
    m: jnp.ndarray,
    l: jnp.ndarray,
    acc: jnp.ndarray,
    window: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One streaming-softmax step over a K/V block.

    q [.., Tq, N, Dh]; k, v [.., Tk, G, Dh], G dividing N: query head n
    reads key/value head n // (N // G), and K/V are read once, not
    repeated; q_pos [.., Tq]; k_pos [.., Tk]. A key counts where
    k_pos <= q_pos and, with `window`, q_pos - k_pos < window.
    Carries (all f32): m [.., N, Tq] running max, l [.., N, Tq] running
    normalizer, acc [.., N, Tq, Dh] unnormalized output. Returns updated
    carries; `finalize_attention` turns them into the attention output.
    """
    Tq, N, Dh = q.shape[-3:]
    Tk, G = k.shape[-3:-1]
    lead = q.shape[:-3]
    scale = 1.0 / jnp.sqrt(jnp.asarray(Dh, jnp.float32))
    # [.., N, Tq, Tk] — matmul in the input dtype (MXU), scores in f32.
    if G == N:
        s = jnp.einsum("...qnd,...knd->...nqk", q, k, preferred_element_type=jnp.float32)
    else:
        qg = q.reshape(lead + (Tq, G, N // G, Dh))
        s = jnp.einsum("...qgrd,...kgd->...grqk", qg, k, preferred_element_type=jnp.float32)
        s = s.reshape(lead + (N, Tq, Tk))
    s = s.astype(jnp.float32) * scale
    kp, qp = k_pos[..., None, None, :], q_pos[..., None, :, None]
    valid = (kp <= qp) & (kp != EMPTY_POS)
    if window:
        valid = valid & (qp - kp < window)
    s = jnp.where(valid, s, _NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # Explicit where: if an entire row is masked, m_new == _NEG-ish and
    # exp(s - m_new) would be exp(0) = 1 for every masked slot.
    p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    if G == N:
        pv = jnp.einsum("...nqk,...knd->...nqd", p, v.astype(jnp.float32))
    else:
        pg = p.reshape(lead + (G, N // G, Tq, Tk))
        pv = jnp.einsum("...grqk,...kgd->...grqd", pg, v.astype(jnp.float32))
        pv = pv.reshape(lead + (N, Tq, Dh))
    return m_new, l_new, acc * corr[..., None] + pv


def init_carry(q: jnp.ndarray, v_width: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Zero-state (m, l, acc) for a streaming pass with query block `q`;
    `v_width`: the values' head width where it is not the queries'."""
    lead = q.shape[:-3]
    Tq, N, Dh = q.shape[-3:]
    m = jnp.full(lead + (N, Tq), _NEG, jnp.float32)
    l = jnp.zeros(lead + (N, Tq), jnp.float32)
    acc = jnp.zeros(lead + (N, Tq, v_width or Dh), jnp.float32)
    return m, l, acc


def finalize_attention(
    m: jnp.ndarray, l: jnp.ndarray, acc: jnp.ndarray, dtype=None
) -> jnp.ndarray:
    """(m, l, acc) carries → attention output [.., Tq, N, Dh]."""
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # all-masked rows → 0
    out = jnp.moveaxis(out, -3, -2)  # [.., N, Tq, Dh] → [.., Tq, N, Dh]
    return out.astype(dtype) if dtype is not None else out


def causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_pos: jnp.ndarray,
    k_pos: jnp.ndarray,
    window: int = 0,
) -> jnp.ndarray:
    """Position-masked causal attention, single block.

    q [.., Tq, N, Dh], k/v [.., Tk, G, Dh], q_pos [.., Tq], k_pos [.., Tk]
    → [.., Tq, N, Dh] in q.dtype; `window` as in `accumulate_block`.
    (v's head width may be another than q's and k's: the output has v's.)
    This is both the reference the ring and blocked paths are tested
    against and the shipping implementation whenever the whole time axis
    fits one device's memory densely.
    """
    m, l, acc = init_carry(q, v.shape[-1])
    m, l, acc = accumulate_block(q, k, v, q_pos, k_pos, m, l, acc, window)
    return finalize_attention(m, l, acc, dtype=q.dtype)


def absorbed_attention(
    q_c: jnp.ndarray,
    q_r: jnp.ndarray,
    c: jnp.ndarray,
    k_r: jnp.ndarray,
    q_pos: jnp.ndarray,
    k_pos: jnp.ndarray,
    scale: float,
) -> jnp.ndarray:
    """Latent attention over a cache of latents, in the absorbed form: the
    keys and values of a frame are never expanded. q_c [B, Tq, N, R] is a
    head's unrotated query already carried into the latent's space (times
    the key half of the expanding matrix), q_r [B, Tq, N, Dr] its rotated
    part; c [B, Tk, R] the cached latents and k_r [B, Tk, Dr] the cached
    rotated key that every head shares. Score (q_c . c + q_r . k_r) *
    `scale` where k_pos <= q_pos, softmax in float32, and the result is
    each head's weighted sum of latents [B, Tq, N, R] in float32, which
    the caller carries through the value half of the expanding matrix.
    """
    s = jnp.einsum("bqnr,bkr->bnqk", q_c, c, preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bqnd,bkd->bnqk", q_r, k_r, preferred_element_type=jnp.float32)
    kp, qp = k_pos[:, None, None, :], q_pos[:, None, :, None]
    valid = (kp <= qp) & (kp != EMPTY_POS)
    p = jax.nn.softmax(jnp.where(valid, s.astype(jnp.float32) * scale, _NEG), axis=-1)
    return jnp.einsum("bnqk,bkr->bqnr", jnp.where(valid, p, 0.0), c.astype(jnp.float32))


def block_key_range(i: int, block: int, T: int, window: int = 0) -> Tuple[int, int]:
    """The keys [lo, hi) that query block `i` (queries i*block up to
    (i+1)*block, cut at T) meets in a key block that holds an unmasked
    pair, where query t sits at position t: key blocks above the diagonal,
    and with `window` those wholly more than window - 1 behind the
    block's first query, hold none."""
    hi = min((i + 1) * block, T)
    lo = max(i * block - (window - 1), 0) // block * block if window else 0
    return lo, hi


def blockwise_causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_pos: jnp.ndarray,
    k_pos: jnp.ndarray,
    kv_block: int,
    window: int = 0,
) -> jnp.ndarray:
    """Flash-formulation local attention over query and key blocks of
    `kv_block`, for a chunk whose frame t is query and key t (the
    learner's unroll; positions still do the masking).

    Same function as `causal_attention`. A key block is computed for a
    query block only where it holds an unmasked pair (`block_key_range`):
    half of the blocks of a causal layer, and about
    (window + kv_block) / T of them in a windowed one. Each query block
    is one dense masked softmax over its contiguous key range under
    `jax.checkpoint`: what is kept for the backward pass is q, k and v,
    and the [N, kv_block, keys] scores exist for one query block at a
    time, forward and backward. The shapes are static and a ragged last
    block is a shorter slice. This is the plain formulation (static
    slices, no kernel): what runs on the CPU, on shapes the kernel below
    refuses and in every test, and the reference `fused_causal_attention`
    is tested against. On a TPU its float32 scores go to memory and are
    evaluated three times before the backward pass proper (forward, the
    block's rematerialisation, this function's own checkpoint): measured
    at 15-19% of the layer's roofline (PERF.md, PR 30), which is why the
    learner's unroll takes the kernel there (ops/ring_attention.py
    `fused_applies`).
    """
    T = q.shape[-3]
    if k.shape[-3] != T:
        raise ValueError(f"blocked attention is over one chunk: {T} queries, {k.shape[-3]} keys")
    one_block = jax.checkpoint(causal_attention, static_argnums=(5,))
    out = []
    for i in range(-(-T // kv_block)):
        lo, hi = block_key_range(i, kv_block, T, window)
        rows, keys = slice(i * kv_block, hi), slice(lo, hi)
        out.append(one_block(q[..., rows, :, :], k[..., keys, :, :], v[..., keys, :, :],
                             q_pos[..., rows], k_pos[..., keys], window))
    return jnp.concatenate(out, axis=-3)


# Name under which the fused kernel's residuals (its output and the
# log-sum-exp of its scores) go into a surrounding `jax.checkpoint`: a
# policy that saves this name recomputes everything of a block but the
# attention forward (models/transformer_policy.py TransformerCore).
FUSED_RESIDUALS = "attn_fused_residuals"


def fused_tiles(T: int, window: int = 0, head_dim: int = 128) -> Tuple[int, int]:
    """(tile of the query and key axes, keys computed at a time inside a
    key tile) of the fused kernel for a chunk of T frames, (0, 0) where it
    has none. The largest of 1,024, 512, 256, 128 that divides T and, in
    a windowed layer, is no larger than the window (a tile far wider than
    the window computes mostly masked pairs), 512 keys at a time. Measured
    on a TPU v5e at 4 rows of T 4,096, 32 heads on 4 of 128 (PERF.md,
    PR 33): forward and backward 14.5 ms at window 1,024 and 18.3 ms
    causal, against 15.5 and 21.1 with tiles of 512, 29.8 and 43.8 with
    tiles of 256, and 14.9 and 18.7 with all 1,024 keys at a time. Heads
    wider than 128 take as many fewer keys at a time (256 at a width of
    256): the backward kernel's scratch for a tile of 1,024 with 512 keys
    of 256 at a time is 17.4 MB of the 16 MB a kernel may use (the TPU's
    compiler, PR 36), and with 256 it fits and keeps the tile, whose
    partial dq take half the memory of a tile of 512's."""
    for tile in (1024, 512, 256, 128):
        if T % tile == 0 and (not window or tile <= max(window, 128)):
            # 512 keys at a time at a width of 128, fewer in proportion to a
            # wider head, in whole multiples of 128 (the kernel's lanes)
            return tile, min(tile, max(128, 512 * 128 // max(head_dim, 128) // 128 * 128))
    return 0, 0


def fused_takes(T: int, N: int, G: int, Dh: int) -> bool:
    """Whether `fused_causal_attention` takes a chunk of T frames with N
    query heads on G key/value heads of width Dh: the head width fills
    the lanes (a multiple of 128), T divides by a tile, N by G."""
    return Dh % 128 == 0 and N % G == 0 and fused_tiles(T)[0] > 0


def fused_causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    window: int = 0,
    interpret: bool = False,
    q_scaled: bool = False,
    tiles: Optional[Tuple[int, int]] = None,
) -> jnp.ndarray:
    """`causal_attention` for a chunk whose frame t is query and key t
    (q [B, T, N, Dh], k/v [B, T, G, Dh] -> [B, T, N, Dh] in q.dtype), by
    the Pallas TPU kernel that ships with JAX (splash attention), forward
    and backward: the scores of a tile are made, normalised and used in
    fast memory and never written out. The mask is static (causal, and
    with `window` the last `window` keys), a tile that holds no unmasked
    pair is never computed, and query head n reads key/value head
    n // (N // G) where it lies (K/V are not repeated). q, k, v go to the
    MXU in their own type; scores, running maximum, normaliser and
    accumulators are float32. The kernel's output and log-sum-exp carry
    the checkpoint name `FUSED_RESIDUALS`.

    Layout: the kernel reads and writes [B, heads, T, Dh]; this function
    takes and hands on [B, T, heads, Dh], and the swap of the two axes on
    either side (`head_major`) is one of logical axes only where each
    operand comes whole out of a product and the output goes whole into
    one: the TPU's compiler then has the product write, and read, the
    kernel's layout, and no copy stands between them. That is how the
    unroll calls it (models/transformer_policy.py: `_by_head`,
    `_from_heads`, `rope` at the head's whole width). An operand put
    together from parts of heads is copied part by part and once more
    into this layout: `transpose` and `concatenate` copies were 36.7 ms
    of the latent cell's step, and are gone (PERF.md, PR 37).

    `q_scaled`: q already holds the 1/sqrt(Dh) (the caller folded it in
    where q was still float32, `rope`'s table); otherwise it is applied
    here, which rounds a bfloat16 q a second time. `interpret`: run the
    kernel in Pallas' interpreter (the CPU's tests). `tiles`: in
    place of `fused_tiles`' choice (the tests', to cut a small chunk into
    several tiles).
    """
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as splash
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as masks

    T, N, Dh = q.shape[-3:]
    if k.shape[-3] != T or not fused_takes(T, N, k.shape[-2], Dh):
        raise ValueError(f"the fused kernel does not take q {q.shape} with k {k.shape}")
    tile, compute = tiles or fused_tiles(T, window, Dh)
    mask = masks.LocalMask((T, T), (window - 1, 0), 0) if window else masks.CausalMask((T, T))
    kernel = splash.make_splash_mha_single_device(
        masks.MultiHeadMask([mask] * N),
        # one backward kernel gives dq with dk and dv: the scores are recomputed once, not twice
        block_sizes=splash.BlockSizes(
            block_q=tile, block_kv=tile, block_kv_compute=compute,
            block_q_dkv=tile, block_kv_dkv=tile, block_kv_dkv_compute=compute,
            use_fused_bwd_kernel=True,
        ),
        residual_checkpoint_name=FUSED_RESIDUALS,
        interpret=interpret,
    )
    if not q_scaled:
        q = (q.astype(jnp.float32) * Dh**-0.5).astype(q.dtype)
    head_major = lambda x: jnp.swapaxes(x, -3, -2)  # [B, T, heads, Dh] <-> [B, heads, T, Dh]
    return head_major(jax.vmap(kernel)(head_major(q), head_major(k), head_major(v)))
