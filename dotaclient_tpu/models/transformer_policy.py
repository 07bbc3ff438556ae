"""Transformer actor-critic — the long-context policy family.

The flagship family is the LSTM (models/policy.py), matching the
reference's architecture (SURVEY.md §3.3 "Policy forward"). This family
exists for the scale regime the reference never reached: observation
histories of hundreds-to-thousands of steps, where an LSTM's fixed-width
carry is the bottleneck and the TPU-right design is a causal transformer
over the time axis with the O(T²) attention sharded over an `sp` mesh
axis (ops/ring_attention.py).

Interface contract — identical to the LSTM family, so the actor loop,
train step, staging and wire format are all family-agnostic:

- `unroll=False` (actor): the carried state is a `KVCache`; one step
  writes the new token's K/V at each row's slot and attends over the
  cache. Per-row write indices mean batched actors at different episode
  phases share one compiled step. What the carry holds follows the
  layers' kinds (`layer_kinds`): a full, sliding or gated layer keeps
  every frame's keys and values; a latent layer every frame's latent and
  the one rotated key; a linear layer keeps nothing per frame, only its
  rule's state (a matrix a value head) and the last frames its
  convolution still reads. Each kind's arrays have a slot for the layers
  of that kind alone.
- `unroll=True` (learner): teacher-forced causal attention over the
  whole [B, T, ...] chunk; the passed state is IGNORED — context is
  chunk-local by design, and the actor resets its cache at every chunk
  boundary (models.policy.reset_between_chunks) so acting-time and
  re-eval-time distributions are identical. This is the transformer's
  analogue of shipping the LSTM carry with each chunk (SURVEY.md §7
  "LSTM state handoff"); the trade — no cross-chunk memory — is bought
  back by making chunks long (seq_len 128+), which is exactly the
  regime attention wants and sequence parallelism pays for.

The observation trunk and every action head are the shared functions in
models/policy.py (`obs_trunk` / `action_heads`), so the two families
differ only in their temporal core.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.sharding import Mesh

from dotaclient_tpu.config import PolicyConfig
from dotaclient_tpu.ops import attention as A
from dotaclient_tpu.ops import gated_delta as GD
from dotaclient_tpu.ops import moe
from dotaclient_tpu.ops import ring_attention as RA


class KVCache(NamedTuple):
    """Actor-side attention state. Every leaf is BATCH-LEADING (like the
    LSTM's (c, h)) so the generic state plumbing — selfplay's per-side
    concat/slice batching, the actor's row resets — works unchanged:
    k/v [B, La, C, G, Dh] (G key/value heads) for the La layers that attend
    over their past (full, sliding, gated; all L without linear layers);
    with latent layers k is the rotated key that every head shares
    [B, L, C, 1, tf_qk_rope_dim] and v the normed latent
    [B, L, C, 1, tf_kv_lora_rank], and no head's keys or values are kept;
    pos [B, C] holds absolute positions with EMPTY_POS in unwritten slots
    (shared across layers — every layer sees the same timeline); idx [B]
    is each row's next write slot and the count of frames stepped. The Ll
    linear layers keep no frame: s [B, Ll, Hv, d, d] f32 is each value
    head's state of the gated delta rule and conv [B, Ll, K - 1, channels]
    the inputs of the last K - 1 frames that the convolution of q, k and v
    still reads; both None in a model without linear layers."""

    k: jnp.ndarray
    v: jnp.ndarray
    pos: jnp.ndarray
    idx: jnp.ndarray
    rsum: jnp.ndarray  # [B, L, 2, E] f32: each layer's sums of `ops.moe.standardize` over the frames so far
    s: Optional[jnp.ndarray] = None
    conv: Optional[jnp.ndarray] = None


def is_latent(cfg: PolicyConfig) -> bool:
    """Whether the layers' attention is the latent kind."""
    return "latent" in (cfg.tf_layer_kinds or "")


def latent_shape(cfg: PolicyConfig) -> Tuple[int, int, int, int, int]:
    """(query latent, key/value latent, a head's unrotated and rotary
    query/key dimensions, a head's value width) of latent attention."""
    return (cfg.tf_q_lora_rank, cfg.tf_kv_lora_rank, cfg.tf_qk_nope_dim, cfg.tf_qk_rope_dim,
            cfg.tf_v_head_dim)


def head_shape(cfg: PolicyConfig) -> Tuple[int, int, int]:
    """(query heads, key/value heads, head width) of the block; of latent
    attention's expanded form, whose keys have a head each, the query's
    and key's width."""
    N = cfg.tf_heads
    if is_latent(cfg):
        if min(latent_shape(cfg)) <= 0 or cfg.tf_qk_rope_dim % 2:
            raise ValueError(
                f"latent attention needs tf_q_lora_rank, tf_kv_lora_rank, tf_qk_nope_dim, an even "
                f"tf_qk_rope_dim and tf_v_head_dim: {latent_shape(cfg)}"
            )
        return N, N, cfg.tf_qk_nope_dim + cfg.tf_qk_rope_dim
    G = cfg.tf_kv_heads or N
    if not cfg.tf_head_dim and cfg.lstm_hidden % N:
        raise ValueError(
            f"transformer width lstm_hidden={cfg.lstm_hidden} must divide by tf_heads={N}"
        )
    Dh = cfg.tf_head_dim or cfg.lstm_hidden // N
    if N % G:
        raise ValueError(f"tf_heads={N} must divide by tf_kv_heads={G}")
    if Dh % 2:
        raise ValueError(f"head dim {Dh} must be even (RoPE rotates half-pairs)")
    return N, G, Dh


def linear_shape(cfg: PolicyConfig) -> Tuple[int, int, int, int]:
    """(key heads, value heads, head width, convolution taps) of a linear
    layer."""
    Hk, Hv, d, K = cfg.tf_lin_key_heads, cfg.tf_lin_value_heads, cfg.tf_lin_head_dim, cfg.tf_lin_conv
    if min(Hk, Hv, d) <= 0 or Hv % Hk or K < 1:
        raise ValueError(
            f"a linear layer needs tf_lin_key_heads dividing tf_lin_value_heads, tf_lin_head_dim and "
            f"tf_lin_conv >= 1: {(Hk, Hv, d, K)}"
        )
    return Hk, Hv, d, K


def layer_kinds(cfg: PolicyConfig) -> Tuple[str, ...]:
    """The kind of each of the tf_layers layers: cfg.tf_layer_kinds'
    comma list, repeated."""
    period = [k.strip() for k in (cfg.tf_layer_kinds or "full").split(",")]
    for k in period:
        if k not in ("full", "sliding", "latent", "gated", "linear"):
            raise ValueError(
                f"tf_layer_kinds: unknown kind {k!r} (full|sliding|gated: keys and values of every frame in "
                f"the carry; latent: every frame's latent; linear: a state matrix a head and no frame)")
        if k == "sliding" and cfg.tf_window <= 0:
            raise ValueError("a sliding layer needs tf_window > 0")
        if k == "linear":
            linear_shape(cfg)
    if "latent" in period and set(period) != {"latent"}:
        raise ValueError("tf_layer_kinds: latent layers share no KVCache with another kind")
    return tuple(period[i % len(period)] for i in range(cfg.tf_layers))


def init_cache(cfg: PolicyConfig, batch_shape) -> KVCache:
    B = int(batch_shape[0]) if len(batch_shape) else 1
    L, C = cfg.tf_layers, cfg.tf_context
    # Fail at config time, not as a confusing shape error deep in a later
    # trace: a host-side init_cache with indivisible width would silently
    # build a mis-shaped cache (ADVICE r3 item 1).
    _, G, Dh = head_shape(cfg)
    # K/V live in the COMPUTE dtype: the values written are Dense outputs
    # in that dtype anyway, so f32 storage was pure memory/H2D overhead
    # (2x actor cache bytes); scores still accumulate in f32 inside
    # attention (ADVICE r3 item 3). pos/idx stay int32.
    dt = jnp.dtype(cfg.dtype)
    n_linear = layer_kinds(cfg).count("linear")
    k_shape = v_shape = (B, L - n_linear, C, G, Dh)
    if is_latent(cfg):
        k_shape, v_shape = (B, L, C, 1, cfg.tf_qk_rope_dim), (B, L, C, 1, cfg.tf_kv_lora_rank)
    s = conv = None
    if n_linear:
        Hk, Hv, d, K = linear_shape(cfg)
        s = jnp.zeros((B, n_linear, Hv, d, d), jnp.float32)
        conv = jnp.zeros((B, n_linear, K - 1, (2 * Hk + Hv) * d), dt)
    return KVCache(
        k=jnp.zeros(k_shape, dt),
        v=jnp.zeros(v_shape, dt),
        pos=jnp.full((B, C), A.EMPTY_POS, jnp.int32),
        idx=jnp.zeros((B,), jnp.int32),
        rsum=jnp.zeros((B, L, 2, cfg.moe_experts), jnp.float32),
        s=s,
        conv=conv,
    )


class RMSNorm(nn.Module):
    """x / rms(x) * g in f32. The parameter is g - 1, zero at the start
    (g = 1), so that a seeded tree whose vectors are all zero, as the
    program's own initialiser and the benchmark's both make them, is the
    published initial state."""

    eps: float

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.zeros_init(), (x.shape[-1],))
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.eps) * (1.0 + scale)


def _norm(cfg: PolicyConfig, name: str) -> nn.Module:
    """The block's norm of the config's kind, in f32."""
    if cfg.tf_norm == "rmsnorm":
        return RMSNorm(cfg.tf_norm_eps, name=name)
    if cfg.tf_norm == "layernorm":
        return nn.LayerNorm(epsilon=cfg.tf_norm_eps, dtype=jnp.float32, name=name)
    raise ValueError(f"unknown tf_norm {cfg.tf_norm!r} (layernorm|rmsnorm)")


def _fan_in_first(key, shape, dtype=jnp.float32):
    """Normal with variance 1 / shape[0], whatever follows the input axis."""
    return jax.random.normal(key, shape, dtype) / jnp.sqrt(jnp.asarray(shape[0], dtype))


class ExpertLayer(nn.Module):
    """The routed-expert feed-forward layer (ops/moe.py): a router over
    all cfg.moe_experts (cfg.moe_score: softmax, or sigmoid with its
    per-expert bias), and the SwiGLU experts held here. The expert
    matrices are kept input axis first, [D, held, I] and [I, held, D]."""

    cfg: PolicyConfig
    platform: str = ""

    @nn.compact
    def __call__(self, h: jnp.ndarray, seen=None):
        """h [B, T, D] f32, normed. `seen` (step mode, T == 1): the
        sums `moe.standardize` carries over the frames before this one
        [B, 2, E], and their count [B]. Returns ([B, T, D] f32, (pairs
        per held expert, passes over the layer's buffer), the sums with
        this frame's or None)."""
        cfg = self.cfg
        D, E, I = h.shape[-1], cfg.moe_experts, cfg.moe_hidden
        held = cfg.moe_experts_held or E
        if not 0 <= cfg.moe_first_expert <= E - held or not 0 < cfg.moe_top_k <= E:
            raise ValueError(
                f"moe: experts [{cfg.moe_first_expert}, {cfg.moe_first_expert + held}) of {E}, "
                f"top_k={cfg.moe_top_k}"
            )
        dt = jnp.dtype(cfg.dtype)
        router = self.param("router", _fan_in_first, (D, E))
        w_gate = self.param("w_gate", _fan_in_first, (D, held, I))
        w_up = self.param("w_up", _fan_in_first, (D, held, I))
        w_down = self.param("w_down", _fan_in_first, (I, held, D))
        bias = None
        if cfg.moe_score == "sigmoid":
            # chooses and does not weigh; no gradient reaches it, so the optimizer leaves it
            bias = jax.lax.stop_gradient(self.param("router_bias", nn.initializers.zeros_init(), (E,)))
        scores = moe.router_scores(h, router)  # [B, T, E] f32
        sums = None
        if cfg.moe_standardize_router and seen is None:
            n = jnp.arange(1, h.shape[1] + 1, dtype=jnp.float32)
            scores, _ = moe.standardize(scores, None, n, axis=1)
        elif cfg.moe_standardize_router:
            n = (seen[1] + 1).astype(jnp.float32)[:, None]
            one, sums = moe.standardize(scores[:, 0], (seen[0][:, 0], seen[0][:, 1]), n)
            scores, sums = one[:, None], jnp.stack(sums, axis=1)
        elif seen is not None:
            sums = seen[0]
        impl = cfg.moe_impl
        if impl == "auto":
            impl = "megablox" if (self.platform or jax.default_backend()) == "tpu" else "ragged_dot"
        x = h.reshape(-1, D)
        y, *counts = moe.expert_layer(
            x.astype(dt), moe.route(scores.reshape(-1, E), cfg.moe_top_k, cfg.moe_score, bias, cfg.moe_route_scale),
            moe.by_expert(w_gate, dt), moe.by_expert(w_up, dt), moe.by_expert(w_down, dt),
            cfg.moe_first_expert, impl, moe.buffer_rows(x.shape[0] * cfg.moe_top_k, held, E),
        )
        return y.reshape(h.shape), tuple(counts), sums


def _dense(cfg: PolicyConfig, n: int, name: str) -> nn.Dense:
    """A projection of the block, made inside its compact method (these
    are functions and not methods of `Block`: a method would put its own
    name into the `op_name` of every operation under it)."""
    return nn.Dense(n, dtype=jnp.dtype(cfg.dtype), use_bias=cfg.tf_bias, name=name)


def _swiglu(cfg: PolicyConfig, h: jnp.ndarray, width: int, name: str) -> jnp.ndarray:
    """(silu(h Wg) * (h Wu)) Wd in the compute type, h [.., D] normed."""
    h = h.astype(jnp.dtype(cfg.dtype))
    act = nn.silu(_dense(cfg, width, name + "_gate")(h)) * _dense(cfg, width, name + "_up")(h)
    return _dense(cfg, h.shape[-1], name + "_down")(act)


class Kernel(nn.Module):
    """The matrix of a dense layer, and its bias or None, kept as
    `nn.Dense` keeps them (`kernel` [in, out] of variance 1 / in, `bias`
    [out] of zeros), for a layer that reads its matrix in more than one
    form or by its columns."""

    shape: Tuple[int, int]
    bias: bool = False

    @nn.compact
    def __call__(self):
        kernel = self.param("kernel", nn.initializers.lecun_normal(), self.shape)
        bias = self.param("bias", nn.initializers.zeros_init(), self.shape[1:]) if self.bias else None
        return kernel, bias


def _by_head(cfg: PolicyConfig, h: jnp.ndarray, n_heads: int, Dh: int, name: str):
    """A projection of the block whose output is `n_heads` heads of `Dh`,
    its parameters those of `_dense(cfg, n_heads * Dh, name)`: returns
    heads(first, n), h [B, T, D] times the columns that make heads
    [first, first + n), written by head [B, T, n, Dh]. Taking some heads
    is a slice of weights; a product that writes [B, T, n_heads * Dh]
    to be reshaped, or split into q, k and v, costs a copy of every
    frame's heads on a TPU."""
    dt = jnp.dtype(cfg.dtype)
    w, bias = Kernel((h.shape[-1], n_heads * Dh), cfg.tf_bias, name=name)()
    h = h.astype(dt)

    def heads(first: int, n: int) -> jnp.ndarray:
        cols = slice(first * Dh, (first + n) * Dh)
        y = jnp.einsum("btd,dnh->btnh", h, w[:, cols].astype(dt).reshape(-1, n, Dh))
        return y if bias is None else y + bias[cols].astype(dt).reshape(n, Dh)

    return heads


def _from_heads(cfg: PolicyConfig, attn: jnp.ndarray, name: str) -> jnp.ndarray:
    """The projection of every head's output back to the block's width,
    attn [B, T, N, Dv] -> [B, T, D], its parameters those of
    `_dense(cfg, D, name)` over the heads side by side: the product reads
    the heads as attention wrote them, not a copy that sets them side by
    side."""
    dt = jnp.dtype(cfg.dtype)
    N, Dv = attn.shape[-2:]
    w, bias = Kernel((N * Dv, cfg.lstm_hidden), cfg.tf_bias, name=name)()
    y = jnp.einsum("btnh,nhd->btd", attn.astype(dt), w.astype(dt).reshape(N, Dv, -1))
    return y if bias is None else y + bias.astype(dt)


def _attention(block: "Block", x, positions, cache):
    """The full, sliding or gated layer's attention part: what is added
    to x, and the new (k_cache, v_cache) or None. q, k and v leave their
    products as whole heads, [B, T, heads, Dh], and reach attention so.
    A gated layer is a full one with a norm of the config's kind on each
    head's q and k (one weight vector for all heads) and the heads'
    output times a sigmoid of a gate, N more heads of `qkv`'s columns
    after v's, applied to the heads as attention wrote them."""
    cfg = block.cfg
    N, G, Dh = head_shape(cfg)
    dt = jnp.dtype(cfg.dtype)
    sliding, gated = block.kind == "sliding", block.kind == "gated"
    window = cfg.tf_window if sliding else 0
    rotary = cfg.tf_rotary_dim or Dh
    span = (0, rotary) if rotary < Dh else None
    table = A.rope_table(rotary, cfg.tf_rope_theta) if sliding or not cfg.tf_yarn_factor else (
        A.rope_table(rotary, cfg.tf_rope_theta, cfg.tf_yarn_factor, cfg.tf_yarn_original_context,
                     cfg.tf_yarn_beta_fast, cfg.tf_yarn_beta_slow))
    heads = _by_head(cfg, _norm(cfg, "ln1")(x), N + 2 * G + (N if gated else 0), Dh, "qkv")
    normed = (lambda a, name: _norm(cfg, name)(a).astype(dt)) if gated else (lambda a, name: a)
    # RoPE at this token's absolute position; cached K were rotated
    # at write time, so angles are consistent across modes. The
    # fused kernel wants the scores' 1/sqrt(Dh) in q: it goes into
    # the rotation, which is still float32.
    q = A.rope(normed(heads(0, N), "q_norm"), positions, table=table, span=span,
               scale=Dh**-0.5 if block.fused else 1.0)
    k = A.rope(normed(heads(N, G), "k_norm"), positions, table=table, span=span)
    v = heads(N + G, G)

    new_cache = None
    if cache is None:
        attn = RA.attend(
            q, k, v, positions, positions,
            mesh=block.sp_mesh, sp_axis=cfg.tf_sp_axis, sp_mode=cfg.tf_sp_mode,
            kv_block=cfg.tf_attn_block, window=window, fused=block.fused,
        )
    else:
        k_cache, v_cache, cache_pos, onehot, _ = cache
        # Write in the cache's own dtype (compute dtype — init_cache):
        # jnp.where avoids the f32 promotion a mask-blend would cause.
        sel = onehot[:, :, None, None]  # [B, C, 1, 1] bool
        k_cache = jnp.where(sel, k.astype(k_cache.dtype), k_cache)
        v_cache = jnp.where(sel, v.astype(v_cache.dtype), v_cache)
        attn = RA.attend(q, k_cache, v_cache, positions, cache_pos, window=window)
        new_cache = (k_cache, v_cache)
    if gated:
        attn = attn.astype(jnp.float32) * jax.nn.sigmoid(heads(N + 2 * G, N).astype(jnp.float32))
    return _from_heads(cfg, attn, "attn_out"), new_cache


def _linear_attention(block: "Block", x, positions, cache):
    """The linear layer's part (ops/gated_delta.py has the rule): what is
    added to x, and the new (state, convolution tail) or None.

        [q | k | v | z] = n(x) W_qkvz   Hk, Hk, Hv, Hv heads of d, written by head
        [b | a]         = n(x) W_ba     one scalar each a value head
        q, k, v <- silu(conv(q), conv(k), conv(v))     causal, depthwise, K taps
        beta = sigmoid(b),  g = -exp(A_log) softplus(a + dt_bias) <= 0
        q <- q / |q| / sqrt(d),  k <- k / |k|
        o = rule(q, k, v, beta, g)      chunked in the unroll, one frame in the step
        y = (n_head(o) * silu(z)) W_o   n_head: one weight vector [d] for all heads

    The convolution's filter is one [K, channels] matrix over q's, k's
    and v's channels side by side, taken by its columns as the heads are;
    decays, beta, the norms and the state are float32."""
    cfg = block.cfg
    Hk, Hv, d, K = linear_shape(cfg)
    dt, f32 = jnp.dtype(cfg.dtype), jnp.float32
    h = _norm(cfg, "ln1")(x)
    heads = _by_head(cfg, h, 2 * Hk + 2 * Hv, d, "qkvz")
    ba = _dense(cfg, 2 * Hv, "ba")(h.astype(dt)).astype(f32)
    decay = -jnp.exp(block.param("A_log", nn.initializers.zeros_init(), (Hv,)))
    dt_bias = block.param("dt_bias", nn.initializers.zeros_init(), (Hv,))
    beta, g = jax.nn.sigmoid(ba[..., :Hv]), decay * jax.nn.softplus(ba[..., Hv:] + dt_bias)
    filt, _ = Kernel((K, (2 * Hk + Hv) * d), name="conv")()
    tail = None if cache is None else cache[1]
    mixed, tails = [], []
    for first, n in ((0, Hk), (Hk, Hk), (2 * Hk, Hv)):  # q, k, v: by head through the filter's columns
        cols = slice(first * d, (first + n) * d)
        before = None if tail is None else tail[..., cols].reshape(tail.shape[:2] + (n, d))
        out, last = GD.causal_conv(heads(first, n), filt[:, cols].reshape(K, n, d), before)
        mixed.append(nn.silu(out))
        tails.append(last.reshape(last.shape[:2] + (n * d,)))
    q, k, v = mixed
    q, k, v = (GD.l2norm(q) * d**-0.5).astype(dt), GD.l2norm(k).astype(dt), v.astype(dt)

    new_cache = None
    if cache is None:
        o, _ = GD.chunked(q, k, v, beta, g, GD.CHUNK)  # a short row is padded to a chunk
    else:
        S, o = GD.step(cache[0], q[:, 0], k[:, 0], v[:, 0], beta[:, 0], g[:, 0])
        o, new_cache = o[:, None], (S, jnp.concatenate(tails, axis=-1).astype(tail.dtype))
    o = RMSNorm(cfg.tf_norm_eps, name="out_norm")(o) * nn.silu(heads(2 * Hk + Hv, Hv).astype(f32))
    return _from_heads(cfg, o, "attn_out"), new_cache


def _latent_attention(block: "Block", x, positions, cache):
    """Latent attention's part. Queries go through a normed latent
    c_q; keys and values come from a normed latent c and one rotary
    key k_r that every head shares. The unroll expands c into every
    head's keys and values and attends as any causal layer does (the
    fused kernel where it applies). The step keeps c and the rotated
    k_r of each frame, nothing per head, and attends in the absorbed
    form: the query carried into c's space by the key half of the
    expanding matrix, and each head's weighted sum of latents
    carried out by the value half.

    What the unroll hands on is whole heads, [B, T, N, nope + rope] and
    [B, T, N, v_dim], each written once by a product: q by q_b's and
    rotated in place (`A.rope`'s span, which also scales the lanes
    beside it), k by one product of [c | k_r] with the key half of
    `kv_b` over constant rows that carry k_r into every head's rotary
    lanes (ones and zeros: exact), v by c's with the value half. The
    form it replaces split q at lane 192 of 256 and kv_b's output at 192
    of 448 and concatenated q's and k's parts again: copies of part of
    a head, in each of three passes, 15 ms a layer where the layer's
    products take 17 (PERF.md, PR 37)."""
    cfg = block.cfg
    N = cfg.tf_heads
    q_rank, kv_rank, nope, rope, v_dim = latent_shape(cfg)
    dt = jnp.dtype(cfg.dtype)
    table = A.rope_table(rope, cfg.tf_rope_theta)
    scale = (nope + rope) ** -0.5

    h = _norm(cfg, "ln1")(x).astype(dt)
    c_q = _norm(cfg, "q_norm")(_dense(cfg, q_rank, "q_a")(h))
    q = _by_head(cfg, c_q, N, nope + rope, "q_b")(0, N)
    c, k_r = jnp.split(_dense(cfg, kv_rank + rope, "kv_a")(h), [kv_rank], axis=-1)
    c = _norm(cfg, "kv_norm")(c).astype(dt)
    k_r = A.rope(k_r[..., None, :], positions, table=table)  # one head [B, T, 1, rope]
    w_kv, _ = Kernel((kv_rank, N * (nope + v_dim)), name="kv_b")()
    w_k, w_v = jnp.split(w_kv.astype(dt).reshape(kv_rank, N, nope + v_dim), [nope], axis=-1)  # of weights

    new_cache = None
    if cache is None:
        # the kernel wants the scores' scale in q (see `_attention`)
        q = A.rope(q, positions, table=table, span=(nope, rope), scale=scale if block.fused else 1.0)
        shared = np.zeros((rope, N, nope + rope), np.float32)  # k_r's lane r into lane nope + r of every head
        shared[np.arange(rope), :, nope + np.arange(rope)] = 1.0
        w_k = jnp.concatenate([jnp.pad(w_k, ((0, 0), (0, 0), (0, rope))), jnp.asarray(shared, dt)])
        k = jnp.einsum("btr,rnd->btnd", jnp.concatenate([c, k_r[..., 0, :]], axis=-1), w_k)
        v = jnp.einsum("btr,rnd->btnd", c, w_v)
        attn = RA.attend(
            q, k, v, positions, positions,
            mesh=block.sp_mesh, sp_axis=cfg.tf_sp_axis, sp_mode=cfg.tf_sp_mode,
            kv_block=cfg.tf_attn_block, fused=block.fused,
        )
    else:
        kr_cache, c_cache, cache_pos, onehot, _ = cache
        sel = onehot[:, :, None, None]  # [B, C, 1, 1] bool
        kr_cache = jnp.where(sel, k_r.astype(kr_cache.dtype), kr_cache)
        c_cache = jnp.where(sel, c[:, :, None, :].astype(c_cache.dtype), c_cache)
        q_n, q_r = jnp.split(q, [nope], axis=-1)
        q_c = jnp.einsum("bqnd,rnd->bqnr", q_n, w_k)
        u = A.absorbed_attention(q_c, A.rope(q_r, positions, table=table), c_cache[:, :, 0],
                                 kr_cache[:, :, 0], positions, cache_pos, scale)
        attn = jnp.einsum("bqnr,rnd->bqnd", u.astype(dt), w_v)
        new_cache = (kr_cache, c_cache)
    return _from_heads(cfg, attn, "attn_out"), new_cache


# The named scope of a layer kind's attention part ("full": attn_full).
SCOPES = {"latent": "attn_latent", "sliding": "attn_window", "gated": "attn_gated", "linear": "attn_linear"}


class Block(nn.Module):
    """Pre-norm transformer block: norm → causal attention (+residual) →
    norm → feed-forward (+residual). The sizes are the config's: grouped
    key/value heads, a head width of its own, a full, a sliding or a gated
    layer with that kind's rotary table, latent attention, or a linear
    layer (the gated delta rule in attention's place); LayerNorm or
    RMSNorm; a dense MLP (GELU of 4x, or SwiGLU of a width of its own) or
    a routed-expert layer with or without a shared expert beside it.
    Matmuls in cfg.dtype (MXU); norms, softmax, router and the residual
    stream in f32."""

    cfg: PolicyConfig
    kind: str = "full"
    sp_mesh: Optional[Mesh] = None
    platform: str = ""  # of the devices the surrounding program runs on, where known
    fused: bool = False  # the unroll's attention goes through the fused kernel (RA.fused_applies)
    sparse: bool = False  # the feed-forward part is the routed-expert layer (`ff_sparse`)

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,  # [B, T, D] f32 residual stream
        positions: jnp.ndarray,  # [B, T] int32 absolute positions
        cache: Optional[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]] = None,
    ):
        """cache=None: causal self-attention over the T axis (unroll
        mode; ring-sharded when sp_mesh and cfg.tf_sp_axis are set).
        Otherwise cache=(k_cache [B,C,G,Dh], v_cache, cache_pos [B,C]
        ALREADY including this token's position, write_onehot [B,C],
        the router's running sums [B,2,E]):
        T==1 stepping — the block writes its fresh K/V into the cache at
        write_onehot and attends over the merged cache; a sliding layer
        masks by position, a latent layer's two cache arrays are the
        rotated shared key [B,C,1,rope] and the latent [B,C,1,rank], a
        linear layer's its rule's state [B,Hv,d,d] and its convolution's
        tail [B,K-1,channels], which it replaces.
        Returns (x_out, new cache or None, the routed-expert layer's
        counts or None)."""
        cfg = self.cfg
        # Named scopes: the layer an operation belongs to, in its
        # `op_name`, the norm that feeds a part inside that part's scope.
        attention = {"latent": _latent_attention, "linear": _linear_attention}.get(self.kind, _attention)
        with jax.named_scope(SCOPES.get(self.kind, "attn_full")):
            out, new_cache = attention(self, x, positions, cache)
            x = x + out.astype(jnp.float32)

        if self.sparse:
            with jax.named_scope("moe"):
                seen = None if cache is None else (cache[4], positions[:, 0])
                h = _norm(cfg, "ln2")(x)
                y, counts, rsum = ExpertLayer(cfg, self.platform, name="moe")(h, seen)
            if cfg.moe_shared_hidden:
                with jax.named_scope("moe_shared"):
                    shared = _swiglu(cfg, h, cfg.moe_shared_hidden, "shared").astype(jnp.float32)
                    if cfg.moe_shared_gate:
                        gate = _dense(cfg, 1, "shared_expert_gate")(h.astype(jnp.dtype(cfg.dtype)))
                        shared = shared * jax.nn.sigmoid(gate.astype(jnp.float32))
                    y = y + shared
            return x + y, new_cache and new_cache + (rsum,), counts
        with jax.named_scope("mlp"):
            h = _norm(cfg, "ln2")(x)
            width = cfg.tf_mlp_hidden or 4 * cfg.lstm_hidden
            if cfg.tf_mlp_act == "swiglu":
                h = _swiglu(cfg, h, width, "mlp")
            elif cfg.tf_mlp_act == "gelu":
                h = nn.gelu(_dense(cfg, width, "mlp_up")(h.astype(jnp.dtype(cfg.dtype))))
                h = _dense(cfg, cfg.lstm_hidden, "mlp_down")(h)
            else:
                raise ValueError(f"unknown tf_mlp_act {cfg.tf_mlp_act!r} (gelu|swiglu)")
            return x + h.astype(jnp.float32), new_cache and new_cache + (cache[4],), None


def ff_sparse(cfg: PolicyConfig) -> Tuple[bool, ...]:
    """For each layer, whether its feed-forward part is the routed-expert
    layer: with experts, every layer after the cfg.tf_dense_layers dense
    ones."""
    return tuple(bool(cfg.moe_experts) and i >= cfg.tf_dense_layers for i in range(cfg.tf_layers))


def _moe_stats(counts) -> dict:
    """The step's routing counters from each layer's (pairs per held
    expert, passes over its buffer): the most loaded held expert over the
    mean, of the worst layer, the pairs computed here over all layers,
    and the passes over the buffers of `moe.buffer_rows` rows, all layers
    (one a layer where its held pairs fit). Empty without a routed-expert
    layer."""
    counts = [c for c in counts if c is not None]  # a dense layer has none
    if not counts:
        return {}
    sizes, passes = zip(*counts)
    per_layer = jnp.stack(sizes).astype(jnp.float32)  # [L, held]
    mean = jnp.maximum(jnp.mean(per_layer, axis=-1), 1e-9)
    return {
        "moe_load_max_over_mean": jnp.max(jnp.max(per_layer, axis=-1) / mean),
        "moe_local_pairs": jnp.sum(per_layer),
        "moe_passes": jnp.sum(jnp.stack(passes)),
    }


class TransformerCore(nn.Module):
    """Temporal core: trunk features → context features.

    Unroll: x [B, T, D] → [B, T, D], carry passed through untouched
    (chunk-local context). Step: x [B, D] → [B, D], carry is a KVCache.
    Third result: the unroll's counters (how many layers' attention took
    the fused kernel, and a routed-expert core's routing), or None.
    """

    cfg: PolicyConfig
    sp_mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, carry, x: jnp.ndarray, unroll: bool = False):
        cfg = self.cfg
        kinds, sparse = layer_kinds(cfg), ff_sparse(cfg)
        head_shape(cfg)  # refuses a bad shape before any block is traced
        platform = self.sp_mesh.devices.flat[0].platform if self.sp_mesh is not None else ""
        final = _norm(cfg, "ln_f") if cfg.tf_final_norm else (lambda h: h)

        if unroll:
            B, T = x.shape[0], x.shape[1]
            positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
            h = x.astype(jnp.float32)
            N, G, Dh = head_shape(cfg)
            fused = RA.fused_applies(
                platform or jax.default_backend(), (B, T, N, Dh), (B, T, G, Dh), cfg.tf_attn_block,
                mesh=self.sp_mesh, sp_axis=cfg.tf_sp_axis,
            ) and (not is_latent(cfg) or cfg.tf_v_head_dim == Dh)  # the kernel's values have the keys' width
            # cfg.tf_remat: recompute each block's activations in the
            # backward instead of storing them (jax.checkpoint) —
            # O(T·D) residuals per block instead of every intermediate.
            # Kept by name, so that each runs forward once a step: the
            # fused kernel's output and log-sum-exp, and a linear layer's
            # solved systems, chunk-entering states, u and o (GD.chunked).
            n_linear = kinds.count("linear")
            names = [A.FUSED_RESIDUALS] * bool(fused) + [GD.RULE_RESIDUALS] * bool(n_linear)
            keep = jax.checkpoint_policies.save_only_these_names(*names) if names else None
            block_cls = nn.remat(Block, policy=keep) if cfg.tf_remat else Block
            if n_linear and self.sp_mesh is not None and cfg.tf_sp_axis in self.sp_mesh.axis_names:
                raise ValueError("a linear layer's state is not carried over the sp axis")
            counts = []
            for i, kind in enumerate(kinds):
                h, _, n = block_cls(cfg, kind, self.sp_mesh, platform, fused, sparse[i], name=f"block{i}")(h, positions)
                counts.append(n)
            stats = {"attn_fused_layers": jnp.float32(len(kinds) - n_linear if fused else 0), **_moe_stats(counts)}
            return carry, final(h), stats

        assert isinstance(carry, KVCache), "transformer step mode needs a KVCache carry"
        C = carry.pos.shape[1]
        positions = carry.idx[:, None]  # [B, 1] — this step's absolute position
        # Ring-buffer write: past capacity the oldest slot is overwritten,
        # degrading gracefully to sliding-window attention over the last C
        # tokens (absolute positions keep the causal mask and RoPE exact).
        # The shipping actor never wraps — it resets the cache every chunk
        # and tf_context >= chunk frames — but an unconditional one-hot of
        # an out-of-range index would silently DROP the write instead.
        onehot = jax.nn.one_hot(carry.idx % C, C, dtype=jnp.float32)  # [B, C]
        new_pos = jnp.where(onehot > 0, positions, carry.pos).astype(jnp.int32)

        h = x.astype(jnp.float32)[:, None, :]  # [B, 1, D]
        # each kind's pair of arrays has a slot for the layers of that kind, in layer order
        old = {"linear": (carry.s, carry.conv), "attend": (carry.k, carry.v)}
        new = {"linear": [], "attend": []}
        rs = []
        for i, kind in enumerate(kinds):
            mine = "linear" if kind == "linear" else "attend"
            a, b = (x[:, len(new[mine])] for x in old[mine])
            h, (a, b, r), _ = Block(cfg, kind, platform=platform, sparse=sparse[i], name=f"block{i}")(
                h, positions, cache=(a, b, new_pos, onehot, carry.rsum[:, i]))
            new[mine].append((a, b))
            rs.append(r)

        def stacked(kind):  # a kind with no layer keeps what it came with (empty arrays, or None)
            return tuple(jnp.stack(x, axis=1) for x in zip(*new[kind])) if new[kind] else old[kind]

        (k, v), (s, conv) = stacked("attend"), stacked("linear")
        new_carry = KVCache(k=k, v=v, pos=new_pos, idx=carry.idx + 1, rsum=jnp.stack(rs, axis=1), s=s, conv=conv)
        return new_carry, final(h)[:, 0, :], None


class TransformerPolicyCore(nn.Module):
    """Shared trunk + transformer temporal core + shared heads — the
    drop-in alternative to models.policy.PolicyCore."""

    cfg: PolicyConfig
    sp_mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, carry, obs, unroll: bool = False):
        from dotaclient_tpu.models.policy import action_heads, obs_trunk

        # Named scopes, as models/policy.py PolicyCore has them.
        with jax.named_scope("trunk"):
            trunk, unit_emb = obs_trunk(self.cfg, obs)
        carry, out, stats = TransformerCore(self.cfg, self.sp_mesh, name="tf")(carry, trunk, unroll)
        with jax.named_scope("heads"):
            return carry, action_heads(self.cfg, out, unit_emb, obs)._replace(stats=stats)
