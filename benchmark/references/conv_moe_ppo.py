"""The plain reference of the transformer policy whose block is
LFM2-MoE's (`lfm2_moe`), under PPO, which a configuration's file names
(`"reference": "conv_moe_ppo"`): the train step in float32, `jax.numpy`,
precision "highest", no kernels, no cache, no sorting of pairs, no
packing; the shapes of its parameters; and the least operations and bytes
one step needs. It imports nothing of the program and is handed nothing
the program made: the weights come from `weights.py` (by `param_shapes`)
and the rows from `frames.py`, both from the seed.

The block, as the published config and model code give it (x the float32
residual of one row, [F, D], frame t at position t; n() an RMSNorm of eps
with its parameter kept as g - 1; no bias anywhere). Each layer is one of
two kinds, in the order `layer_types` gives them.

Short-convolution layer ("conv"; K = conv_L_cache taps):

  h = n(x)
  [B | C | x'] = h W_in               three blocks of D columns, in this order
  y_t = C_t * sum_j w_j * (B * x')_(t - (K-1) + j)   causal depthwise filter w [K, D], zeros before the row
  a = x + y W_out

Attention layer ("full"; N query heads on G key/value heads of Dh = D / N):

  h = n(x)
  [q | k | v] = h W_qkv
  q <- n_Dh(q), k <- n_Dh(k)          one weight vector each for all heads
  rotary over the whole head, theta, no scaling, lane i paired with lane i + Dh / 2
  s_ij = q_i . k_j / sqrt(Dh), kept where j <= i; softmax; o_head = sum_j p_ij v_j
  a = x + o W_o

Feed-forward, every layer held here (the leading dense layers are not):
h2 = n(a); s = sigmoid(h2 W_r) over all E experts; chosen = the K largest
of s + b (b the per-expert bias, which chooses and does not weigh);
w = scale * s_chosen / (sum of s_chosen + eps), eps 1e-6 (the published
model code's; the config has no key for it)
  x' = a + sum over the chosen e held here of w_e E_e(h2)
every E_e a SwiGLU; no shared expert. Then after the last layer a final
n(). The filter is K shifted sums (`gdn_moe_ppo.conv`), attention a block
of queries at a time against all keys under a dense mask, and every frame
goes through every held expert.

Departures from the published model, each also under `assumed` in the
configuration's file: trunk, heads and the PPO loss for embedding, output
head and next-token loss, the standardised router scores (before the
sigmoid), the share of the experts, a norm's parameter kept as g - 1, the
router in float32 and never rounded by `quant`, as
`references/moe_swa_ppo.py` has them and for its reasons; b is zero from
the seed and gets no gradient, as `references/mla_moe_ppo.py` has it; the
filter kept [K, D]; the two leading dense layers left out (they lie on the
pipeline stage before this one).

What is not the block's is `moe_swa_ppo.py`'s and `gdn_moe_ppo.py`'s,
imported: the products, the norm, the rotation, the shifted sums, the
advantages, the gathers of the loss. The functions that call the block (a
row's forward, its loss, the step, the run) are written here over this
module's block.

`quant`, where given, is applied to both operands of every matrix product
but the router's (see `control.py`); the filter's taps and the two gates
are elementwise and are not rounded. Planted faults of this reference's
own (`fault`): `filter_left_out` (y_t = C_t * w_(K-1) * (B * x')_t: the
frames before are not read), `gates_left_out` (neither B nor C
multiplies), `qk_norm_left_out` (q and k go on unnormed), `half_batch`
(the second half of the rows left out of every mean).
"""

from __future__ import annotations

import json
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.gdn_moe_ppo import conv
from benchmark.references.moe_swa_ppo import (ACT_ATTACK, ACT_CAST, ACT_MOVE, BIG_NEG, HI, QUERY_BLOCK,
                                               ROUTER_EPS, _dense, _ent, _gather, _masked_log_softmax,
                                               _mm, _rmsnorm, _rope, advantages, attended_pairs, layer_kinds)
from benchmark.tree import first_gradient, flat_numbers, leaf_diff_norms, leaf_norms

FAULTS = (None, "filter_left_out", "gates_left_out", "qk_norm_left_out", "half_batch")
ROUTE_EPS = 1e-6  # beside the sum that the chosen scores are renormalised by (the published model code's)


def _sizes(config: dict):
    pol = config["policy"]
    return dict(
        D=int(pol["lstm_hidden"]), L=int(pol["tf_layers"]),
        N=int(pol["tf_heads"]), G=int(pol["tf_kv_heads"]), Dh=int(pol["tf_head_dim"]),
        theta=float(pol["tf_rope_theta"]), taps=int(config["conv_L_cache"]),
        E=int(pol["moe_experts"]), held=int(pol["moe_experts_held"]),
        first=int(pol["moe_first_expert"]), K=int(pol["moe_top_k"]), I=int(pol["moe_hidden"]),
        scale=float(pol["moe_route_scale"]), eps=float(pol["tf_norm_eps"]),
    )


def rope_table(config: dict):
    """(inverse frequencies [Dh / 2], factor on cos and sin): the default
    table of theta over the whole head, no scaling."""
    z = _sizes(config)
    i = np.arange(z["Dh"] // 2, dtype=np.float64)
    return (z["theta"] ** (-2.0 * i / z["Dh"])).astype(np.float32), 1.0


def short_conv(p, x, config: dict, quant, fault=None):
    """One row's short-convolution part of a layer: x [F, D] -> what is
    added to x."""
    z = _sizes(config)
    h = _rmsnorm(x, p["ln1"], z["eps"])
    b, c, inner = jnp.split(_mm(h, p["in_proj"]["kernel"], quant), 3, axis=-1)
    w = p["conv"]["kernel"]
    if fault == "gates_left_out":
        b, c = jnp.ones_like(b), jnp.ones_like(c)
    gated = b * inner
    y = c * (w[-1] * gated if fault == "filter_left_out" else conv(gated, w))
    return _mm(y, p["out_proj"]["kernel"], quant)


def attention(p, x, config: dict, quant, fault=None):
    """One row's attention part of a layer: x [F, D] -> what is added to x."""
    z = _sizes(config)
    N, G, Dh = z["N"], z["G"], z["Dh"]
    F = x.shape[0]
    h = _rmsnorm(x, p["ln1"], z["eps"])
    q, k, v = jnp.split(_mm(h, p["qkv"]["kernel"], quant), [N * Dh, (N + G) * Dh], axis=-1)
    norm = (lambda a, g: a) if fault == "qk_norm_left_out" else (lambda a, g: _rmsnorm(a, g, z["eps"]))
    table = rope_table(config)
    q = _rope(norm(q.reshape(F, N, Dh), p["q_norm"]), table).reshape(F, G, N // G, Dh)
    k = _rope(norm(k.reshape(F, G, Dh), p["k_norm"]), table)
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
    return _mm(out, p["attn_out"]["kernel"], quant)


def experts(p, x, config: dict, quant, fault=None):
    """One row's feed-forward part: x [F, D] -> (what is added to x: the
    held experts' part of the routed sum, the pairs of each held expert
    [held])."""
    z = _sizes(config)
    h = _rmsnorm(x, p["ln2"], z["eps"])
    m = p["moe"]
    scores = jnp.matmul(h, m["router"], precision=HI)  # float32, never rounded
    if config["policy"].get("moe_standardize_router"):
        # less their running mean, over the root of the running mean of that difference's square
        n = jnp.arange(1, x.shape[0] + 1, dtype=x.dtype)[:, None]
        diff = scores - jnp.cumsum(scores, axis=0) / n
        scores = diff * jax.lax.rsqrt(jnp.cumsum(diff * diff, axis=0) / n + ROUTER_EPS)
    s = jax.nn.sigmoid(scores)
    _, chosen = jax.lax.top_k(s + m["router_bias"], z["K"])  # b chooses
    picked = jnp.take_along_axis(s, chosen, axis=-1)  # and does not weigh
    w = z["scale"] * picked / (jnp.sum(picked, axis=-1, keepdims=True) + ROUTE_EPS)
    held = z["first"] + jnp.arange(z["held"])
    here = chosen[:, :, None] == held[None, None, :]  # [F, K, held]
    w_here = jnp.sum(jnp.where(here, w[:, :, None], 0.0), axis=1)  # [F, held], 0 where not chosen
    # every frame through every held expert, then weighted
    mm = (lambda a, b, eq: jnp.einsum(eq, a, b, precision=HI)) if quant is None else (
        lambda a, b, eq: jnp.einsum(eq, quant(a), quant(b), precision=HI))
    act = jax.nn.silu(mm(h, m["w_gate"], "fd,dei->fei")) * mm(h, m["w_up"], "fd,dei->fei")
    y = jnp.sum(w_here[:, :, None] * mm(act, m["w_down"], "fei,ied->fed"), axis=1)
    return y, jnp.sum(here, axis=(0, 1))


def core(p, x, config: dict, quant, fault=None):
    """One row through the layers and the final norm: [F, D] -> [F, D]."""
    for i, kind in enumerate(layer_kinds(config)):
        blk = p[f"block{i}"]
        mixer = short_conv if kind == "conv" else attention
        x = x + jax.checkpoint(lambda b, x, mixer=mixer: mixer(b, x, config, quant, fault))(blk, x)
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
    live on several chips. The step compiles once for both steps, as in
    `gdn_moe_ppo.run_reference` and for its reason."""
    step = _jitted_step(config, quant, fault, shardings)
    diff_norms = jax.jit(leaf_diff_norms)
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
    N, G, Dh = z["N"], z["G"], z["Dh"]

    def dense(i, o):
        return {"bias": (o,), "kernel": (i, o)}

    def kernel(i, o):
        return {"kernel": (i, o)}

    mixer = {
        "conv": {"in_proj": kernel(H, 3 * H), "conv": kernel(z["taps"], H), "out_proj": kernel(H, H)},
        "full": {
            "qkv": kernel(H, (N + 2 * G) * Dh), "q_norm": {"scale": (Dh,)}, "k_norm": {"scale": (Dh,)},
            "attn_out": kernel(N * Dh, H),
        },
    }
    feed_forward = {
        "ln1": {"scale": (H,)}, "ln2": {"scale": (H,)},
        "moe": {
            "router": (H, z["E"]), "router_bias": (z["E"],),
            "w_gate": (H, z["held"], z["I"]), "w_up": (H, z["held"], z["I"]), "w_down": (z["I"], z["held"], H),
        },
    }
    tf = {f"block{i}": {**mixer[kind], **feed_forward} for i, kind in enumerate(layer_kinds(config))}
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
    part, 2*M*N*K per [M,K]x[K,N]: a short-convolution layer's two
    products and its filter's multiply-adds (2 K a channel and frame: a
    product with a diagonal matrix a tap; the two gates are elementwise
    and not counted), an attention layer's products and the pairs the
    causal mask keeps, and the router and the pairs an even routing sends
    to the experts held here (top_k * held / experts a frame). Kept here
    so that the yardstick cannot move with the program
    (`dotaclient_tpu/ops/flops.py` has to agree)."""
    pol, f = config["policy"], config["features"]
    z = _sizes(config)
    U, UF = int(f["max_units"]), int(f["unit_features"])
    Du, M, H = int(pol["unit_embed_dim"]), int(pol["mlp_hidden"]), z["D"]
    N, G, Dh = z["N"], z["G"], z["Dh"]
    frames = int(config["learner"]["seq_len"]) + 1
    trunk = 2.0 * U * UF * M + 2.0 * U * M * Du + 2.0 * int(f["hero_features"]) * M
    trunk += 2.0 * int(f["global_features"]) * (M // 4) + 2.0 * (M + M // 4 + 2 * Du) * H
    heads = 2.0 * H * (int(f["n_action_types"]) + 2 * int(pol["n_move_bins"]) + Du + 1) + 2.0 * U * Du
    kinds = layer_kinds(config)
    n_conv = kinds.count("conv")
    short = 2.0 * H * 3 * H + 2.0 * z["taps"] * H + 2.0 * H * H
    full = 2.0 * H * (N + 2 * G) * Dh + 2.0 * N * Dh * H
    held_pairs = z["K"] * z["held"] / z["E"]
    return {
        "trunk": frames * trunk, "heads": frames * heads,
        "short_conv": n_conv * frames * short,
        "attn_full": (len(kinds) - n_conv) * (frames * full + attended_pairs(frames) * 4.0 * N * Dh),
        "moe": len(kinds) * frames * (2.0 * H * z["E"] + held_pairs * 3 * 2.0 * H * z["I"]),
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
    `train_step_flops` counts them (forward and twice that backward), and
    bytes to and from the chip's memory by `moe_swa_ppo.scope_costs`'
    rule, the same for every part of a layer: the part's matrices read
    once and their gradients written once in float32; its input and its
    output and their two cotangents passed once, a float32 residual each
    way ([frames, D]); in `short_conv` [B | C | x'], B * x', the filter's
    output and y (6 D a frame) and their cotangents once in the compute
    type; in `attn_full` q, k, v and the heads' output and their
    cotangents once in the compute type; in `moe` each held pair's row in
    and out and their cotangents in the compute type. Nothing recomputed,
    no score kept. `optimizer`: Adam's 7 float32 values a parameter; no
    matrix product."""
    z = _sizes(config)
    H, N, G, Dh = z["D"], z["N"], z["G"], z["Dh"]
    frames = rows * (int(config["learner"]["seq_len"]) + 1)
    item = jnp.dtype(config["policy"]["dtype"]).itemsize
    per_row = forward_flops_per_row(config)
    kinds = layer_kinds(config)
    n_conv = kinds.count("conv")
    residual = 4.0 * frames * H * 4
    short_params = H + H * 3 * H + z["taps"] * H + H * H
    short_bytes = 8.0 * short_params + residual + 2.0 * frames * 6 * H * item
    full_params = H + H * (N + 2 * G) * Dh + 2 * Dh + N * Dh * H
    full_bytes = 8.0 * full_params + residual + 2.0 * frames * (2 * N + 2 * G) * Dh * item
    moe_params = H + H * z["E"] + z["E"] + 3 * z["held"] * H * z["I"]
    held_pairs = frames * z["K"] * z["held"] / z["E"]
    moe_bytes = 8.0 * moe_params + residual + 4.0 * held_pairs * H * item
    flops = {k: 3.0 * rows * per_row[k] for k in ("short_conv", "attn_full", "moe")}
    return {
        "short_conv": {"flops": flops["short_conv"], "bytes": n_conv * short_bytes},
        "attn_full": {"flops": flops["attn_full"], "bytes": (len(kinds) - n_conv) * full_bytes},
        "moe": {"flops": flops["moe"], "bytes": len(kinds) * moe_bytes},
        "optimizer": {"flops": 0.0, "bytes": 28.0 * n_params(config)},
    }
