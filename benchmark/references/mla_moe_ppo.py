"""The plain reference of the transformer policy whose block is
GLM-4.7-Flash's (`glm4_moe_lite`), under PPO, which a configuration's
file names (`"reference": "mla_moe_ppo"`): the train step in float32,
`jax.numpy`, precision "highest", no kernels, no cache, no sorting of
pairs, no packing; the shapes of its parameters; and the least operations
and bytes one step needs. It imports nothing of the program and is handed
nothing the program made: the weights come from `weights.py` (by
`param_shapes`) and the rows from `frames.py`, both from the seed.

The block, as the published config gives it (x the float32 residual of
one row, [F, D], frame t at position t; n() an RMSNorm of eps with its
parameter kept as g - 1; no bias anywhere; 20 heads):

  h   = n(x)
  c_q = n(h W_qa)                              query latent (768)
  q   = c_q W_qb -> per head [q_n (192) | q_r (64)]
  [c | k_r] = h W_kva; c = n(c)                key/value latent (512), one rotary key (64) for all heads
  [k_n (192) | v (256)]_head = c W_kvb
  q_head = [q_n | rope(q_r)], k_head = [k_n | rope(k_r)]   rotary over all 64, theta, no scaling
  s_ij = q_i . k_j / sqrt(192 + 64), kept where j <= i; softmax; o_head = sum_j p_ij v_j
  a   = x + concat(o) W_o
  h2  = n(a)
  layer 0 (first_k_dense_replace 1):  x' = a + (silu(h2 W_g) * (h2 W_u)) W_d        width 10,240
  layers 1..:  s = sigmoid(h2 W_r) over all 64 experts; chosen = the 4 largest of s + b
               (b the per-expert bias, which chooses and does not weigh; n_group = topk_group = 1,
               so no group step); w = 1.8 * s_chosen / (sum of s_chosen + 1e-20)
               x' = a + S(h2) + sum over the chosen e held here of w_e E_e(h2)
               S and every E_e a SwiGLU of width 1,536; S the one shared expert
then a final n(). The attention is computed in the expanded form above
(every head's keys and values out of the latent), a block of queries at a
time against all keys under a dense mask: the program's actor caches the
latent and attends in the absorbed form, and has to agree with this.

Departures from the published model, each also under `assumed` in the
configuration's file: trunk, heads and the PPO loss for embedding, output
head and next-token loss, the standardised router scores (before the
sigmoid here), the share of the experts, a norm's parameter kept as g - 1,
the router in float32 and never rounded by `quant`, as
`references/moe_swa_ppo.py` has them and for its reasons; b is zero from
the seed and gets no gradient (the published balance is b's update rule,
which is no part of the loss); `A.rope`'s pairing of the rotary
dimensions (i with i + 32); no multi-token prediction module.

What is not the block's is `moe_swa_ppo.py`'s, imported: the products,
the norm, the rotation, the advantages, the gathers of the loss. The
functions that call the block (a row's forward, its loss, the step, the
run) are written here over this module's block.

`quant`, where given, is applied to both operands of every matrix
product but the router's (see `control.py`). Planted faults of this
reference's own (`fault`): `latent_norm_left_out` (c_q and c go on
unnormed), `bias_weighs` (b added to the weights as well as to the
choice), `shared_left_out` (S left out of every sparse layer),
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
                                               _mm, _rmsnorm, _rope, advantages, attended_pairs)
from benchmark.tree import first_gradient, flat_numbers, leaf_diff_norms, leaf_norms

FAULTS = (None, "latent_norm_left_out", "bias_weighs", "shared_left_out", "half_batch")
SIGMOID_EPS = 1e-20  # beside the sum that the chosen scores are renormalised by (the published constant)


def _sizes(config: dict):
    pol = config["policy"]
    return dict(
        D=int(pol["lstm_hidden"]), N=int(pol["tf_heads"]), L=int(pol["tf_layers"]),
        q_rank=int(pol["tf_q_lora_rank"]), kv_rank=int(pol["tf_kv_lora_rank"]),
        nope=int(pol["tf_qk_nope_dim"]), rope=int(pol["tf_qk_rope_dim"]), v=int(pol["tf_v_head_dim"]),
        dense_layers=int(pol["tf_dense_layers"]), W=int(pol["tf_mlp_hidden"]),
        E=int(pol["moe_experts"]), held=int(pol["moe_experts_held"]),
        first=int(pol["moe_first_expert"]), K=int(pol["moe_top_k"]), I=int(pol["moe_hidden"]),
        S=int(pol["moe_shared_hidden"]), scale=float(pol["moe_route_scale"]),
        eps=float(pol["tf_norm_eps"]), theta=float(pol["tf_rope_theta"]),
    )


def rope_table(config: dict):
    """(inverse frequencies [rope / 2], factor on cos and sin): the
    default table of theta over the rotary dimensions, no scaling."""
    z = _sizes(config)
    i = np.arange(z["rope"] // 2, dtype=np.float64)
    return (z["theta"] ** (-2.0 * i / z["rope"])).astype(np.float32), 1.0


def attention(p, x, config: dict, quant, fault=None):
    """One row's latent attention: x [F, D] -> what is added to x."""
    z = _sizes(config)
    N, nope, rope, v_dim = z["N"], z["nope"], z["rope"], z["v"]
    F = x.shape[0]
    inner = (lambda a, g: a) if fault == "latent_norm_left_out" else (lambda a, g: _rmsnorm(a, g, z["eps"]))
    h = _rmsnorm(x, p["ln1"], z["eps"])
    c_q = inner(_mm(h, p["q_a"]["kernel"], quant), p["q_norm"])
    q_n, q_r = jnp.split(_mm(c_q, p["q_b"]["kernel"], quant).reshape(F, N, nope + rope), [nope], axis=-1)
    c, k_r = jnp.split(_mm(h, p["kv_a"]["kernel"], quant), [z["kv_rank"]], axis=-1)
    c = inner(c, p["kv_norm"])
    k_n, v = jnp.split(_mm(c, p["kv_b"]["kernel"], quant).reshape(F, N, nope + v_dim), [nope], axis=-1)
    table = rope_table(config)
    q = jnp.concatenate([q_n, _rope(q_r, table)], axis=-1)
    k = jnp.concatenate([k_n, jnp.broadcast_to(_rope(k_r[:, None, :], table), (F, N, rope))], axis=-1)
    qk, vq = (quant(k), quant(v)) if quant is not None else (k, v)
    j = jnp.arange(F)

    @jax.checkpoint
    def block(q_blk, i_blk):  # [n, N, nope + rope] queries at rows i_blk against every key, dense mask
        qq = quant(q_blk) if quant is not None else q_blk
        s = jnp.einsum("qnd,knd->nqk", qq, qk, precision=HI) / math.sqrt(nope + rope)
        a = jax.nn.softmax(jnp.where(i_blk[:, None] >= j[None, :], s, -1e30), axis=-1)
        a = quant(a) if quant is not None else a
        return jnp.einsum("nqk,knd->qnd", a, vq, precision=HI)

    n = min(QUERY_BLOCK, F)
    pad = -F % n  # the last block's padding rows are queries at rows >= F: cut off below
    qp = jnp.pad(q, [(0, pad), (0, 0), (0, 0)]).reshape(-1, n, N, nope + rope)
    ip = jnp.arange(F + pad).reshape(-1, n)
    out = jax.lax.map(lambda a: block(*a), (qp, ip)).reshape(F + pad, N * v_dim)[:F]
    return _mm(out, p["attn_out"]["kernel"], quant)


def _swiglu(h, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(h, gate["kernel"], quant)) * _mm(h, up["kernel"], quant), down["kernel"], quant)


def dense_block(p, x, config: dict, quant, fault=None):
    """One row's dense feed-forward part (the leading layers): x [F, D] ->
    what is added to x."""
    h = _rmsnorm(x, p["ln2"], _sizes(config)["eps"])
    return _swiglu(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"], quant)


def experts(p, x, config: dict, quant, fault=None):
    """One row's sparse feed-forward part: x [F, D] -> (what is added to
    x: the shared expert and the held experts' part of the routed sum,
    the pairs of each held expert [held])."""
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
    picked = jnp.take_along_axis(s + m["router_bias"] if fault == "bias_weighs" else s, chosen, axis=-1)
    w = z["scale"] * picked / (jnp.sum(picked, axis=-1, keepdims=True) + SIGMOID_EPS)  # and does not weigh
    held = z["first"] + jnp.arange(z["held"])
    here = chosen[:, :, None] == held[None, None, :]  # [F, K, held]
    w_here = jnp.sum(jnp.where(here, w[:, :, None], 0.0), axis=1)  # [F, held], 0 where not chosen
    # every frame through every held expert, then weighted
    mm = (lambda a, b, eq: jnp.einsum(eq, a, b, precision=HI)) if quant is None else (
        lambda a, b, eq: jnp.einsum(eq, quant(a), quant(b), precision=HI))
    act = jax.nn.silu(mm(h, m["w_gate"], "fd,dei->fei")) * mm(h, m["w_up"], "fd,dei->fei")
    y = jnp.sum(w_here[:, :, None] * mm(act, m["w_down"], "fei,ied->fed"), axis=1)
    if fault != "shared_left_out":
        y = y + _swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"], quant)
    return y, jnp.sum(here, axis=(0, 1))


def core(p, x, config: dict, quant, fault=None):
    """One row through the layers and the final norm: [F, D] -> [F, D]."""
    z = _sizes(config)
    for i in range(z["L"]):
        blk = p[f"block{i}"]
        x = x + jax.checkpoint(lambda b, x: attention(b, x, config, quant, fault))(blk, x)
        if i < z["dense_layers"]:
            x = x + jax.checkpoint(lambda b, x: dense_block(b, x, config, quant, fault))(blk, x)
        else:
            x = x + jax.checkpoint(lambda b, x: experts(b, x, config, quant, fault)[0])(blk, x)
    return _rmsnorm(x, p["ln_f"], z["eps"])


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
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    params, mu, nu = params0, zeros(params0), zeros(params0)
    count = jnp.zeros((), jnp.float32)
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

    def dense(i, o):
        return {"bias": (o,), "kernel": (i, o)}

    def kernel(i, o):
        return {"kernel": (i, o)}

    attn = {
        "ln1": {"scale": (H,)},
        "q_a": kernel(H, z["q_rank"]), "q_norm": {"scale": (z["q_rank"],)},
        "q_b": kernel(z["q_rank"], z["N"] * (z["nope"] + z["rope"])),
        "kv_a": kernel(H, z["kv_rank"] + z["rope"]), "kv_norm": {"scale": (z["kv_rank"],)},
        "kv_b": kernel(z["kv_rank"], z["N"] * (z["nope"] + z["v"])),
        "attn_out": kernel(z["N"] * z["v"], H),
        "ln2": {"scale": (H,)},
    }
    dense_ff = {"mlp_gate": kernel(H, z["W"]), "mlp_up": kernel(H, z["W"]), "mlp_down": kernel(z["W"], H)}
    sparse_ff = {
        "moe": {
            "router": (H, z["E"]), "router_bias": (z["E"],),
            "w_gate": (H, z["held"], z["I"]), "w_up": (H, z["held"], z["I"]),
            "w_down": (z["I"], z["held"], H),
        },
        "shared_gate": kernel(H, z["S"]), "shared_up": kernel(H, z["S"]), "shared_down": kernel(z["S"], H),
    }
    tf = {f"block{i}": {**attn, **(dense_ff if i < z["dense_layers"] else sparse_ff)} for i in range(z["L"])}
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
    part, 2*M*N*K per [M,K]x[K,N]: attention in the expanded form over the
    pairs the causal mask keeps and no others, and the pairs an even
    routing sends to the experts held here (top_k * held / experts a
    frame). Kept here so that the yardstick cannot move with the program
    (`dotaclient_tpu/ops/flops.py` has to agree)."""
    pol, f = config["policy"], config["features"]
    z = _sizes(config)
    U, UF = int(f["max_units"]), int(f["unit_features"])
    Du, M, H, N = int(pol["unit_embed_dim"]), int(pol["mlp_hidden"]), z["D"], z["N"]
    frames = int(config["learner"]["seq_len"]) + 1
    trunk = 2.0 * U * UF * M + 2.0 * U * M * Du + 2.0 * int(f["hero_features"]) * M
    trunk += 2.0 * int(f["global_features"]) * (M // 4) + 2.0 * (M + M // 4 + 2 * Du) * H
    heads = 2.0 * H * (int(f["n_action_types"]) + 2 * int(pol["n_move_bins"]) + Du + 1) + 2.0 * U * Du
    proj = 2.0 * (H * z["q_rank"] + z["q_rank"] * N * (z["nope"] + z["rope"]) + H * (z["kv_rank"] + z["rope"])
                  + z["kv_rank"] * N * (z["nope"] + z["v"]) + N * z["v"] * H)
    per_pair = 2.0 * N * (z["nope"] + z["rope"]) + 2.0 * N * z["v"]  # the scores, the values
    n_sparse = z["L"] - z["dense_layers"]
    held_pairs = z["K"] * z["held"] / z["E"]
    return {
        "trunk": frames * trunk, "heads": frames * heads,
        "attn_latent": z["L"] * (frames * proj + attended_pairs(frames) * per_pair),
        "mlp": z["dense_layers"] * frames * 3 * 2.0 * H * z["W"],
        "moe": n_sparse * frames * (2.0 * H * z["E"] + held_pairs * 3 * 2.0 * H * z["I"]),
        "moe_shared": n_sparse * frames * 3 * 2.0 * H * z["S"],
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
    way ([frames, D]); in `attn_latent` every head's q, k, v and output
    and their cotangents once in the compute type (the expanded form, the
    learner's); in `moe` each held pair's row in and out and their
    cotangents in the compute type. Nothing recomputed, no score kept.
    `optimizer`: Adam's 7 float32 values a parameter; no matrix product."""
    z = _sizes(config)
    H, N = z["D"], z["N"]
    frames = rows * (int(config["learner"]["seq_len"]) + 1)
    item = jnp.dtype(config["policy"]["dtype"]).itemsize
    per_row = forward_flops_per_row(config)
    residual = 4.0 * frames * H * 4
    n_sparse = z["L"] - z["dense_layers"]
    attn_params = (H + z["q_rank"] + z["kv_rank"] + H * z["q_rank"] + z["q_rank"] * N * (z["nope"] + z["rope"])
                   + H * (z["kv_rank"] + z["rope"]) + z["kv_rank"] * N * (z["nope"] + z["v"]) + N * z["v"] * H)
    attn_bytes = 8.0 * attn_params + residual + 2.0 * frames * N * (2 * (z["nope"] + z["rope"]) + 2 * z["v"]) * item
    moe_params = H + H * z["E"] + z["E"] + 3 * z["held"] * H * z["I"]
    held_pairs = frames * z["K"] * z["held"] / z["E"]
    moe_bytes = 8.0 * moe_params + residual + 4.0 * held_pairs * H * item
    flops = {k: 3.0 * rows * per_row[k] for k in ("attn_latent", "mlp", "moe", "moe_shared")}
    return {
        "attn_latent": {"flops": flops["attn_latent"], "bytes": z["L"] * attn_bytes},
        "mlp": {"flops": flops["mlp"], "bytes": z["dense_layers"] * (8.0 * (H + 3 * H * z["W"]) + residual)},
        "moe": {"flops": flops["moe"], "bytes": n_sparse * moe_bytes},
        "moe_shared": {"flops": flops["moe_shared"], "bytes": n_sparse * (8.0 * 3 * H * z["S"] + residual)},
        "optimizer": {"flops": 0.0, "bytes": 28.0 * n_params(config)},
    }
