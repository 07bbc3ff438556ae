"""The plain reference of the transformer policy whose block is
Qwen3-Next's (`qwen3_next`), under PPO, which a configuration's file
names (`"reference": "gdn_moe_ppo"`): the train step in float32,
`jax.numpy`, precision "highest", no kernels, no cache, no chunks, no
sorting of pairs, no packing; the shapes of its parameters; and the least
operations and bytes one step needs. It imports nothing of the program
and is handed nothing the program made: the weights come from
`weights.py` (by `param_shapes`) and the rows from `frames.py`, both from
the seed.

The block, as the published config and model code give it (x the float32
residual of one row, [F, D], frame t at position t; n() an RMSNorm of eps
with its parameter kept as g - 1; no bias anywhere). Layers come in
periods of `full_attention_interval`: linear ones and then a gated one.

Gated DeltaNet layer ("linear"; Hk key heads serve Hv value heads, key
head j the value heads [j Hv/Hk, (j + 1) Hv/Hk); d_k = d_v = d):

  h = n(x)
  [q | k | v | z] = h W_qkvz          q, k: Hk heads of d; v, z: Hv heads of d
  [b | a]         = h W_ba            one scalar each a value head
  [q | k | v] <- silu(conv([q | k | v]))   causal depthwise convolution over the frames, K taps
                                      (this frame and the K - 1 before it, zeros before the row),
                                      one filter a channel, no bias
  beta_t = sigmoid(b_t),  g_t = -exp(A_log) softplus(a_t + dt_bias) <= 0     per value head
  q_t <- q_t / |q_t| / sqrt(d),  k_t <- k_t / |k_t|     (the root of the squared norm + 1e-6)
  per value head, S = 0 in R^(d x d) before the row, frame by frame:
      S  <- exp(g_t) S
      u_t = beta_t (v_t - S^T k_t)
      S  <- S + k_t u_t^T
      o_t = S^T q_t
  a = x + (n_d(o) * silu(z)) W_o      n_d: over each head's d, one weight vector for all heads

Gated attention layer ("gated"; N query heads on G key/value heads of Dh):

  h = n(x)
  [q | k | v | gate] = h W_qkv        q, gate: N heads of Dh; k, v: G heads
  q <- n_Dh(q), k <- n_Dh(k)          one weight vector each for all heads
  rotary on the first `tf_rotary_dim` lanes of q and k, theta, no scaling; the other lanes pass
  s_ij = q_i . k_j / sqrt(Dh), kept where j <= i; softmax; o_head = sum_j p_ij v_j
  a = x + (o * sigmoid(gate)) W_o

Feed-forward, every layer:  h2 = n(a);  p = softmax(h2 W_r) over all E
experts; the K largest, their p renormalised to sum 1;
  x' = a + sigmoid(h2 w_s) S(h2) + sum over the chosen e held here of p_e E_e(h2)
S (the shared expert) and every E_e a SwiGLU, w_s one column. Then after
the last layer a final n().

The rule is computed here as the recurrence above, one frame at a time
(`lax.scan` over the row, in segments under `jax.checkpoint` so that the
backward pass keeps a state a segment and not a frame); the program runs
a chunked form of it, so the two share no arithmetic. The convolution is
K shifted sums, attention a block of queries at a time against all keys
under a dense mask, and every frame goes through every held expert.

Departures from the published model, each also under `assumed` in the
configuration's file: trunk, heads and the PPO loss for embedding, output
head and next-token loss, the standardised router scores (before the
softmax), the share of the experts, a norm's parameter kept as g - 1
(n_d's too), the router in float32 and never rounded by `quant`, as
`references/moe_swa_ppo.py` has them and for its reasons; `A_log` and
`dt_bias` zero from the seed; the columns of W_qkvz and W_qkv in whole
blocks of heads (q's, k's, v's, then z's or the gate's) where the
published code interleaves them by key head; the filter kept [K,
channels]; `A.rope`'s pairing of the rotary lanes; no multi-token
prediction module.

What is not the block's is `moe_swa_ppo.py`'s, imported: the products,
the norm, the rotation, the advantages, the gathers of the loss. The
functions that call the block (a row's forward, its loss, the step, the
run) are written here over this module's block.

`quant`, where given, is applied to both operands of every matrix product
but the router's, the rule's three products a frame among them (see
`control.py`). Planted faults of this reference's own (`fault`):
`decay_left_out` (g = 0: the state never fades), `beta_left_out` (beta =
1), `conv_left_out` (q, k, v go to the silu as the product wrote them),
`out_gate_left_out` (neither kind's output is gated: no silu(z), no
sigmoid(gate)), `shared_gate_left_out` (the shared expert ungated),
`half_batch` (the second half of the rows left out of every mean).
"""

from __future__ import annotations

import json
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.moe_swa_ppo import (ACT_ATTACK, ACT_CAST, ACT_MOVE, BIG_NEG, HI, QUERY_BLOCK,
                                               ROUTER_EPS, _dense, _ent, _gather, _masked_log_softmax,
                                               _mm, _rmsnorm, _rope, advantages, attended_pairs, layer_kinds)
from benchmark.tree import first_gradient, flat_numbers, leaf_diff_norms, leaf_norms

FAULTS = (None, "decay_left_out", "beta_left_out", "conv_left_out", "out_gate_left_out",
          "shared_gate_left_out", "half_batch")
L2_EPS = 1e-6  # under the root of a head's squared norm (the published constant)
SEGMENT = 64  # frames of the recurrence kept under one checkpoint; a size of the computation, not of the model


def _sizes(config: dict):
    pol = config["policy"]
    return dict(
        D=int(pol["lstm_hidden"]), L=int(pol["tf_layers"]),
        N=int(pol["tf_heads"]), G=int(pol["tf_kv_heads"]), Dh=int(pol["tf_head_dim"]),
        rotary=int(pol["tf_rotary_dim"]), theta=float(pol["tf_rope_theta"]),
        Hk=int(pol["tf_lin_key_heads"]), Hv=int(pol["tf_lin_value_heads"]), d=int(pol["tf_lin_head_dim"]),
        taps=int(pol["tf_lin_conv"]),
        E=int(pol["moe_experts"]), held=int(pol["moe_experts_held"]),
        first=int(pol["moe_first_expert"]), K=int(pol["moe_top_k"]), I=int(pol["moe_hidden"]),
        S=int(pol["moe_shared_hidden"]), eps=float(pol["tf_norm_eps"]),
    )


def rope_table(config: dict):
    """(inverse frequencies [rotary / 2], factor on cos and sin): the
    default table of theta over the rotary lanes, no scaling."""
    z = _sizes(config)
    i = np.arange(z["rotary"] // 2, dtype=np.float64)
    return (z["theta"] ** (-2.0 * i / z["rotary"])).astype(np.float32), 1.0


def conv(x, w):
    """x [F, ch], one filter a channel w [K, ch]: the sum of K shifted
    copies, out_t = sum_j w[j] x_(t - (K-1) + j), zeros before the row."""
    K, F = w.shape[0], x.shape[0]
    return sum(w[j] * jnp.pad(x, [(K - 1 - j, 0), (0, 0)])[:F] for j in range(K))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, beta, g, quant):
    """The gated delta rule of one row, frame by frame: q, k [F, Hv, d]
    (each key head's already set beside its value heads), v [F, Hv, d],
    beta, g [F, Hv] -> o [F, Hv, d]."""
    F, Hv, d = v.shape
    mm = (lambda eq, a, b: jnp.einsum(eq, a, b, precision=HI)) if quant is None else (
        lambda eq, a, b: jnp.einsum(eq, quant(a), quant(b), precision=HI))

    def frame(S, xs):  # S [Hv, d, d]
        q_t, k_t, v_t, beta_t, g_t = xs
        S = jnp.exp(g_t)[:, None, None] * S
        u = beta_t[:, None] * (v_t - mm("hkv,hk->hv", S, k_t))
        S = S + mm("hk,hv->hkv", k_t, u)
        return S, mm("hkv,hk->hv", S, q_t)

    segment = jax.checkpoint(lambda S, xs: jax.lax.scan(frame, S, xs))
    pad = -F % SEGMENT  # frames of beta = 0 and g = 0 write nothing and fade nothing; cut off below
    xs = tuple(jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape((-1, SEGMENT) + a.shape[1:])
               for a in (q, k, v, beta, g))
    _, o = jax.lax.scan(segment, jnp.zeros((Hv, d, d), v.dtype), xs)
    return o.reshape(F + pad, Hv, d)[:F]


def linear_attention(p, x, config: dict, quant, fault=None):
    """One row's gated-delta-rule part of a layer: x [F, D] -> what is
    added to x."""
    z = _sizes(config)
    Hk, Hv, d = z["Hk"], z["Hv"], z["d"]
    F = x.shape[0]
    h = _rmsnorm(x, p["ln1"], z["eps"])
    qkv, gate = jnp.split(_mm(h, p["qkvz"]["kernel"], quant), [(2 * Hk + Hv) * d], axis=-1)
    b, a = jnp.split(_mm(h, p["ba"]["kernel"], quant), 2, axis=-1)
    if fault != "conv_left_out":
        qkv = conv(qkv, p["conv"]["kernel"])
    q, k, v = jnp.split(jax.nn.silu(qkv), [Hk * d, 2 * Hk * d], axis=-1)
    beta = jnp.ones_like(b) if fault == "beta_left_out" else jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    if fault == "decay_left_out":
        g = jnp.zeros_like(g)
    q = _l2norm(q.reshape(F, Hk, d)) / math.sqrt(d)
    k = _l2norm(k.reshape(F, Hk, d))
    q, k = (jnp.repeat(t, Hv // Hk, axis=1) for t in (q, k))  # key head j beside its value heads
    o = delta_rule(q, k, v.reshape(F, Hv, d), beta, g, quant)
    o = _rmsnorm(o, p["out_norm"], z["eps"])
    if fault != "out_gate_left_out":
        o = o * jax.nn.silu(gate.reshape(F, Hv, d))
    return _mm(o.reshape(F, Hv * d), p["attn_out"]["kernel"], quant)


def gated_attention(p, x, config: dict, quant, fault=None):
    """One row's gated softmax attention part of a layer: x [F, D] ->
    what is added to x."""
    z = _sizes(config)
    N, G, Dh, rotary = z["N"], z["G"], z["Dh"], z["rotary"]
    F = x.shape[0]
    h = _rmsnorm(x, p["ln1"], z["eps"])
    q, k, v, gate = jnp.split(_mm(h, p["qkv"]["kernel"], quant), [N * Dh, (N + G) * Dh, (N + 2 * G) * Dh], axis=-1)
    table = rope_table(config)
    rotate = lambda t: jnp.concatenate([_rope(t[..., :rotary], table), t[..., rotary:]], axis=-1)
    q = rotate(_rmsnorm(q.reshape(F, N, Dh), p["q_norm"], z["eps"])).reshape(F, G, N // G, Dh)
    k = rotate(_rmsnorm(k.reshape(F, G, Dh), p["k_norm"], z["eps"]))
    v = v.reshape(F, G, Dh)
    qk, vq = (quant(k), quant(v)) if quant is not None else (k, v)
    j = jnp.arange(F)

    @jax.checkpoint
    def block(q_blk, i_blk):  # [n, G, R, Dh] queries at rows i_blk against every key, dense mask
        qq = quant(q_blk) if quant is not None else q_blk
        s = jnp.einsum("qgrd,kgd->grqk", qq, qk, precision=HI) / math.sqrt(Dh)
        a = jax.nn.softmax(jnp.where(i_blk[:, None] >= j[None, :], s, -1e30), axis=-1)
        a = quant(a) if quant is not None else a
        return jnp.einsum("grqk,kgd->qgrd", a, vq, precision=HI)

    n = min(QUERY_BLOCK, F)
    pad = -F % n  # the last block's padding rows are queries at rows >= F: cut off below
    qp = jnp.pad(q, [(0, pad), (0, 0), (0, 0), (0, 0)]).reshape(-1, n, G, N // G, Dh)
    ip = jnp.arange(F + pad).reshape(-1, n)
    out = jax.lax.map(lambda a: block(*a), (qp, ip)).reshape(F + pad, N * Dh)[:F]
    if fault != "out_gate_left_out":
        out = out * jax.nn.sigmoid(gate)
    return _mm(out, p["attn_out"]["kernel"], quant)


def _swiglu(h, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(h, gate["kernel"], quant)) * _mm(h, up["kernel"], quant), down["kernel"], quant)


def experts(p, x, config: dict, quant, fault=None):
    """One row's feed-forward part: x [F, D] -> (what is added to x: the
    gated shared expert and the held experts' part of the routed sum, the
    pairs of each held expert [held])."""
    z = _sizes(config)
    h = _rmsnorm(x, p["ln2"], z["eps"])
    m = p["moe"]
    scores = jnp.matmul(h, m["router"], precision=HI)  # float32, never rounded
    if config["policy"].get("moe_standardize_router"):
        # less their running mean, over the root of the running mean of that difference's square
        n = jnp.arange(1, x.shape[0] + 1, dtype=x.dtype)[:, None]
        diff = scores - jnp.cumsum(scores, axis=0) / n
        scores = diff * jax.lax.rsqrt(jnp.cumsum(diff * diff, axis=0) / n + ROUTER_EPS)
    w, chosen = jax.lax.top_k(jax.nn.softmax(scores, axis=-1), z["K"])
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    held = z["first"] + jnp.arange(z["held"])
    here = chosen[:, :, None] == held[None, None, :]  # [F, K, held]
    w_here = jnp.sum(jnp.where(here, w[:, :, None], 0.0), axis=1)  # [F, held], 0 where not chosen
    # every frame through every held expert, then weighted
    mm = (lambda a, b, eq: jnp.einsum(eq, a, b, precision=HI)) if quant is None else (
        lambda a, b, eq: jnp.einsum(eq, quant(a), quant(b), precision=HI))
    act = jax.nn.silu(mm(h, m["w_gate"], "fd,dei->fei")) * mm(h, m["w_up"], "fd,dei->fei")
    y = jnp.sum(w_here[:, :, None] * mm(act, m["w_down"], "fei,ied->fed"), axis=1)
    shared = _swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"], quant)
    if fault != "shared_gate_left_out":
        shared = shared * jax.nn.sigmoid(_mm(h, p["shared_expert_gate"]["kernel"], quant))
    return y + shared, jnp.sum(here, axis=(0, 1))


def core(p, x, config: dict, quant, fault=None):
    """One row through the layers and the final norm: [F, D] -> [F, D]."""
    for i, kind in enumerate(layer_kinds(config)):
        blk = p[f"block{i}"]
        attention = linear_attention if kind == "linear" else gated_attention
        x = x + jax.checkpoint(lambda b, x, attention=attention: attention(b, x, config, quant, fault))(blk, x)
        x = x + jax.checkpoint(lambda b, x: experts(b, x, config, quant, fault)[0])(blk, x)
    return _rmsnorm(x, p["ln_f"], _sizes(config)["eps"])


def forward_row(params, row, config: dict, quant: Optional[Callable] = None, fault=None):
    """One row's seq_len+1 observations (leaves [T1, ...]; the shipped
    carry is not used: the context is the row). Returns the four heads'
    log-probs [T1, .] and the values [T1]. Trunk and heads as
    `moe_swa_ppo.forward_row` has them."""
    p = params["params"]["core"]
    D = int(config["policy"]["unit_embed_dim"])
    f32 = jnp.float32

    unit_mask = row["unit_mask"]
    x = jax.nn.relu(_dense(p["unit_mlp1"], row["unit_feats"].astype(f32), quant))
    unit_emb = _dense(p["unit_mlp2"], x, quant)  # [T1, U, D]
    m = unit_mask[..., None]
    pool_max = jnp.max(jnp.where(m, unit_emb, BIG_NEG), axis=-2)
    pool_max = jnp.where(jnp.any(unit_mask, axis=-1, keepdims=True), pool_max, 0.0)
    denom = jnp.maximum(jnp.sum(m, axis=-2), 1).astype(f32)
    pool_mean = jnp.sum(jnp.where(m, unit_emb, 0.0), axis=-2) / denom
    hero = jax.nn.relu(_dense(p["hero_mlp"], row["hero_feats"].astype(f32), quant))
    glob = jax.nn.relu(_dense(p["global_mlp"], row["global_feats"].astype(f32), quant))
    trunk = jnp.concatenate([hero, glob, pool_max, pool_mean], axis=-1)
    trunk = jax.nn.relu(_dense(p["trunk"], trunk, quant))  # [T1, H]

    out = core(p["tf"], trunk, config, quant, fault)

    type_logp = _masked_log_softmax(_dense(p["type_head"], out, quant), row["action_mask"])
    move_x_logp = jax.nn.log_softmax(_dense(p["move_x_head"], out, quant), axis=-1)
    move_y_logp = jax.nn.log_softmax(_dense(p["move_y_head"], out, quant), axis=-1)
    query = _dense(p["target_query"], out, quant)  # [T1, D]
    q, e = (quant(query), quant(unit_emb)) if quant is not None else (query, unit_emb)
    target_logits = jnp.einsum("td,tud->tu", q, e, precision=HI) / math.sqrt(D)
    target_logp = _masked_log_softmax(target_logits, row["target_mask"])
    value = _dense(p["value_head"], out, quant)[..., 0]
    return type_logp, move_x_logp, move_y_logp, target_logp, value


def row_loss(params, row, norm_adv, returns, mask, n, config: dict, quant=None, fault=None):
    """One row's part of the batch's PPO loss: sums over its steps over
    the batch's count `n`, so that the rows' parts add up to the loss."""
    c = config["ppo"]
    T = row["rewards"].shape[0]
    type_lp, mx_lp, my_lp, tg_lp, value = forward_row(params, row, config, quant, fault)

    def mean(x):
        return jnp.sum(x * mask) / n

    a_type = row["type"]
    lp = _gather(type_lp[:T], a_type)
    is_move = (a_type == ACT_MOVE).astype(lp.dtype)
    is_tgt = ((a_type == ACT_ATTACK) | (a_type == ACT_CAST)).astype(lp.dtype)
    lp = lp + is_move * (_gather(mx_lp[:T], row["move_x"]) + _gather(my_lp[:T], row["move_y"]))
    lp = lp + is_tgt * _gather(tg_lp[:T], row["target"])
    ratio = jnp.exp(lp - row["behavior_logp"])
    clipped = jnp.clip(ratio, 1.0 - c["clip_eps"], 1.0 + c["clip_eps"]) * norm_adv
    policy_loss = -mean(jnp.minimum(ratio * norm_adv, clipped))

    v_pred = value[:T]
    v_clip = row["behavior_value"] + jnp.clip(
        v_pred - row["behavior_value"], -c["value_clip"], c["value_clip"]
    )
    value_loss = 0.5 * mean(jnp.maximum((v_pred - returns) ** 2, (v_clip - returns) ** 2))

    pt = jnp.exp(type_lp[:T])
    ent = _ent(type_lp[:T])
    ent = ent + pt[..., ACT_MOVE] * (_ent(mx_lp[:T]) + _ent(my_lp[:T]))
    ent = ent + (pt[..., ACT_ATTACK] + pt[..., ACT_CAST]) * _ent(tg_lp[:T])
    return policy_loss + c["value_coef"] * value_loss - c["entropy_coef"] * mean(ent)


def loss_and_grad(params, rows, config: dict, quant=None, fault=None):
    """The PPO loss of one batch of full-length rows and its gradient, a
    row at a time: one forward pass without gradients gives every row's
    values (GAE and the batch's advantage statistics need them all), a
    second takes each row's part of the loss and its gradient."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    B = rows["rewards"].shape[0]
    mask = jnp.ones_like(rows["rewards"])
    if fault == "half_batch":
        mask = mask * (jnp.arange(B) < B // 2).astype(jnp.float32)[:, None]
    n = jnp.maximum(jnp.sum(mask), 1.0)
    value = jax.lax.map(lambda row: forward_row(params, row, config, quant, fault)[4], rows)
    norm_adv, returns = advantages(rows, value, mask, config["ppo"])

    def one(carry, xs):
        loss, grads = carry
        row, a, r, m = xs
        l, g = jax.value_and_grad(row_loss)(params, row, a, r, m, n, config, quant, fault)
        return (loss + l, jax.tree.map(jnp.add, grads, g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(one, zero, (rows, norm_adv, returns, mask))
    return loss, grads


def make_step(config: dict, quant=None, fault: Optional[str] = None):
    """One optimizer step as a pure function
    (params, mu, nu, count, rows) -> (params, mu, nu, count, loss, raw
    grad leaf norms, the gradient as the optimizer gets it): clip by
    global norm, then Adam."""
    c = config["ppo"]

    def step(params, mu, nu, count, rows):
        loss, grads = loss_and_grad(params, rows, config, quant, fault)
        raw = leaf_norms(grads)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        scale = jnp.where(gnorm < c["max_grad_norm"], 1.0, c["max_grad_norm"] / gnorm)
        grads = jax.tree.map(lambda g: g * scale, grads)
        count = count + 1
        mu = jax.tree.map(lambda m, g: c["adam_b1"] * m + (1 - c["adam_b1"]) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: c["adam_b2"] * v + (1 - c["adam_b2"]) * g * g, nu, grads)
        bc1 = 1 - c["adam_b1"] ** count
        bc2 = 1 - c["adam_b2"] ** count
        params = jax.tree.map(
            lambda p, m, v: p - c["lr"] * (m / bc1) / (jnp.sqrt(v / bc2) + c["adam_eps"]),
            params, mu, nu,
        )
        return params, mu, nu, count, loss, raw, grads

    return step


_steps: dict = {}


def _jitted_step(config: dict, quant, fault, shardings):
    """The step, traced and compiled once for a configuration, variant and
    placement, whatever the seed: `control.py` follows several seeds in
    one process, and this step takes minutes to compile at the cell's size."""
    key = (json.dumps(config, sort_keys=True), quant, fault, shardings)
    if key not in _steps:
        step = make_step(config, quant, fault)
        if shardings is not None:
            rep, by_rows = shardings
            step = jax.jit(step, in_shardings=(rep, rep, rep, rep, by_rows), donate_argnums=(1, 2))
        else:
            step = jax.jit(step, donate_argnums=(1, 2))
        _steps[key] = step
    return _steps[key]


def run_reference(config: dict, params0, batches, key, quant=None, fault=None,
                  shardings=None) -> dict:
    """Follow the first `len(batches)` optimizer steps from `params0` on
    the given row batches. Returns the readings that `check.py` compares,
    as host numbers: each step's loss, the first step's gradient per leaf
    (its norm raw, and its norm and its sketch under `key` as the
    optimizer gets it), and the norm of each leaf's change after the last
    step. `shardings` = (replicated, rows) names where the arguments
    live on several chips."""
    step = _jitted_step(config, quant, fault, shardings)
    diff_norms = jax.jit(leaf_diff_norms)
    # Adam's moments and its count placed as the parameters are, leaf by
    # leaf, so that the second step finds the first's trace and executable:
    # a jitted zeros_like gives uncommitted arrays and a bare scalar has no
    # mesh in its type, where the step's own outputs are committed and have
    # one, which is one more lowering and one more compile of minutes (and
    # one more 70 MB entry in a compile cache that keeps 192 MiB).
    like = lambda x: jax.device_put(jnp.zeros(x.shape, x.dtype), x.sharding)
    params, mu, nu = params0, jax.tree.map(like, params0), jax.tree.map(like, params0)
    count = jax.device_put(jnp.zeros((), jnp.float32), jax.tree.leaves(params0)[0].sharding)
    losses, raw1, clip1, sketch1 = [], None, None, None
    for i, rows in enumerate(batches):
        if shardings is not None:
            rows = jax.device_put(rows, shardings[1])
        params, mu, nu, count, loss, raw, grads = step(params, mu, nu, count, rows)
        losses.append(float(loss))
        if i == 0:
            raw1, (clip1, sketch1) = jax.device_get((raw, jax.jit(first_gradient)(grads, key)))
        del grads
    change = jax.device_get(diff_norms(params, params0))
    return {
        "losses": losses,
        "grad_raw": flat_numbers(raw1),
        "grad": flat_numbers(clip1),
        "grad_sketch": flat_numbers(sketch1),
        "change": flat_numbers(change),
    }


def param_shapes(config: dict) -> dict:
    """The parameter tree's shapes, from the configuration's sizes."""
    pol, f = config["policy"], config["features"]
    z = _sizes(config)
    Du, M, H = int(pol["unit_embed_dim"]), int(pol["mlp_hidden"]), z["D"]
    bins = int(pol["n_move_bins"])
    Hk, Hv, d, N, G, Dh = z["Hk"], z["Hv"], z["d"], z["N"], z["G"], z["Dh"]

    def dense(i, o):
        return {"bias": (o,), "kernel": (i, o)}

    def kernel(i, o):
        return {"kernel": (i, o)}

    attention = {
        "linear": {
            "qkvz": kernel(H, (2 * Hk + 2 * Hv) * d), "ba": kernel(H, 2 * Hv),
            "conv": kernel(z["taps"], (2 * Hk + Hv) * d), "A_log": (Hv,), "dt_bias": (Hv,),
            "out_norm": {"scale": (d,)}, "attn_out": kernel(Hv * d, H),
        },
        "gated": {
            "qkv": kernel(H, (2 * N + 2 * G) * Dh), "q_norm": {"scale": (Dh,)}, "k_norm": {"scale": (Dh,)},
            "attn_out": kernel(N * Dh, H),
        },
    }
    feed_forward = {
        "ln1": {"scale": (H,)}, "ln2": {"scale": (H,)},
        "moe": {
            "router": (H, z["E"]),
            "w_gate": (H, z["held"], z["I"]), "w_up": (H, z["held"], z["I"]), "w_down": (z["I"], z["held"], H),
        },
        "shared_gate": kernel(H, z["S"]), "shared_up": kernel(H, z["S"]), "shared_down": kernel(z["S"], H),
        "shared_expert_gate": kernel(H, 1),
    }
    tf = {f"block{i}": {**attention[kind], **feed_forward} for i, kind in enumerate(layer_kinds(config))}
    tf["ln_f"] = {"scale": (H,)}
    core_ = {
        "global_mlp": dense(int(f["global_features"]), M // 4),
        "hero_mlp": dense(int(f["hero_features"]), M),
        "tf": tf,
        "move_x_head": dense(H, bins),
        "move_y_head": dense(H, bins),
        "target_query": dense(H, Du),
        "trunk": dense(M + M // 4 + 2 * Du, H),
        "type_head": dense(H, int(f["n_action_types"])),
        "unit_mlp1": dense(int(f["unit_features"]), M),
        "unit_mlp2": dense(M, Du),
        "value_head": dense(H, 1),
    }
    return {"params": {"core": core_}}


def forward_flops_per_row(config: dict) -> dict:
    """The least matrix-multiply operations of one row's forward pass, by
    part, 2*M*N*K per [M,K]x[K,N]: a linear layer's products and its rule
    counted by the recurrence (S^T k, k u^T and S^T q: 3 x 2 d^2 a value
    head and frame, nothing recomputed, whatever chunk a program computes
    it in; the convolution multiplies no matrices), a gated layer's
    products (the gate's columns among them) and the pairs the causal mask
    keeps, and the pairs an even routing sends to the experts held here
    (top_k * held / experts a frame). Kept here so that the yardstick
    cannot move with the program (`dotaclient_tpu/ops/flops.py` has to
    agree)."""
    pol, f = config["policy"], config["features"]
    z = _sizes(config)
    U, UF = int(f["max_units"]), int(f["unit_features"])
    Du, M, H = int(pol["unit_embed_dim"]), int(pol["mlp_hidden"]), z["D"]
    Hk, Hv, d, N, G, Dh = z["Hk"], z["Hv"], z["d"], z["N"], z["G"], z["Dh"]
    frames = int(config["learner"]["seq_len"]) + 1
    trunk = 2.0 * U * UF * M + 2.0 * U * M * Du + 2.0 * int(f["hero_features"]) * M
    trunk += 2.0 * int(f["global_features"]) * (M // 4) + 2.0 * (M + M // 4 + 2 * Du) * H
    heads = 2.0 * H * (int(f["n_action_types"]) + 2 * int(pol["n_move_bins"]) + Du + 1) + 2.0 * U * Du
    kinds = layer_kinds(config)
    n_linear = kinds.count("linear")
    linear = 2.0 * H * (2 * Hk + 2 * Hv) * d + 2.0 * H * 2 * Hv + 2.0 * Hv * d * H + 3 * 2.0 * d * d * Hv
    gated = 2.0 * H * (2 * N + 2 * G) * Dh + 2.0 * N * Dh * H
    held_pairs = z["K"] * z["held"] / z["E"]
    return {
        "trunk": frames * trunk, "heads": frames * heads,
        "attn_linear": n_linear * frames * linear,
        "attn_gated": (len(kinds) - n_linear) * (frames * gated + attended_pairs(frames) * 4.0 * N * Dh),
        "moe": len(kinds) * frames * (2.0 * H * z["E"] + held_pairs * 3 * 2.0 * H * z["I"]),
        "moe_shared": len(kinds) * frames * (3 * 2.0 * H * z["S"] + 2.0 * H),
    }


def train_step_flops(config: dict, rows: int) -> float:
    """One optimizer step over `rows` rows of seq_len+1 observations: the
    backward pass is twice the forward; recomputed operations do not
    count; elementwise work and the optimizer are left out."""
    return 3.0 * rows * sum(forward_flops_per_row(config).values())


def n_params(config: dict) -> int:
    leaves = jax.tree.leaves(param_shapes(config), is_leaf=lambda x: isinstance(x, tuple))
    return sum(math.prod(shape) for shape in leaves)


def scope_costs(config: dict, rows: int) -> dict:
    """What one optimizer step over `rows` rows on one chip needs at the
    least inside a layer scope of `scopes/<config>.json`, for the scope's
    share of its roofline: matrix-multiply operations counted as
    `train_step_flops` counts them (forward and twice that backward; the
    rule by its recurrence, not by any chunk size, so that the yardstick
    reads the same work whatever implements it), and bytes to and from
    the chip's memory by `moe_swa_ppo.scope_costs`' rule, the same for
    every part of a layer: the part's matrices read once and their
    gradients written once in float32; its input and its output and their
    two cotangents passed once, a float32 residual each way ([frames, D]);
    in `attn_linear` q, k, v, z and the heads' output, in `attn_gated` q,
    k, v, the gate and the heads' output, and their cotangents once in the
    compute type; in `moe` each held pair's row in and out and their
    cotangents in the compute type. Nothing recomputed, no score and no
    state kept. `optimizer`: Adam's 7 float32 values a parameter; no
    matrix product."""
    z = _sizes(config)
    H, Hk, Hv, d, N, G, Dh = z["D"], z["Hk"], z["Hv"], z["d"], z["N"], z["G"], z["Dh"]
    frames = rows * (int(config["learner"]["seq_len"]) + 1)
    item = jnp.dtype(config["policy"]["dtype"]).itemsize
    per_row = forward_flops_per_row(config)
    kinds = layer_kinds(config)
    n_linear = kinds.count("linear")
    residual = 4.0 * frames * H * 4
    linear_params = (H + H * (2 * Hk + 2 * Hv) * d + H * 2 * Hv + z["taps"] * (2 * Hk + Hv) * d + 2 * Hv + d
                     + Hv * d * H)
    linear_bytes = 8.0 * linear_params + residual + 2.0 * frames * (2 * Hk + 3 * Hv) * d * item
    gated_params = H + H * (2 * N + 2 * G) * Dh + 2 * Dh + N * Dh * H
    gated_bytes = 8.0 * gated_params + residual + 2.0 * frames * (3 * N + 2 * G) * Dh * item
    moe_params = H + H * z["E"] + 3 * z["held"] * H * z["I"]
    held_pairs = frames * z["K"] * z["held"] / z["E"]
    moe_bytes = 8.0 * moe_params + residual + 4.0 * held_pairs * H * item
    flops = {k: 3.0 * rows * per_row[k] for k in ("attn_linear", "attn_gated", "moe", "moe_shared")}
    return {
        "attn_linear": {"flops": flops["attn_linear"], "bytes": n_linear * linear_bytes},
        "attn_gated": {"flops": flops["attn_gated"], "bytes": (len(kinds) - n_linear) * gated_bytes},
        "moe": {"flops": flops["moe"], "bytes": len(kinds) * moe_bytes},
        "moe_shared": {"flops": flops["moe_shared"], "bytes": len(kinds) * (8.0 * (3 * H * z["S"] + H) + residual)},
        "optimizer": {"flops": 0.0, "bytes": 28.0 * n_params(config)},
    }
