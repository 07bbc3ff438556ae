"""The plain reference of the single-layer LSTM policy under PPO, which
a configuration's file names (`"reference": "lstm_ppo"`): the train step
in float32, `jax.numpy`, precision "highest", no kernels, no packing, no
sharding rules of the program's; the shapes of its parameters; and the
matrix-multiply operations one step needs. It imports nothing of the
program and is handed nothing the program made: the weights come from
`weights.py` (by `param_shapes`) and the rows from `frames.py`, both
from the seed.

It follows the published layer equations of this repo's policy (PERF.md
section 4 names what is the paper's and what is this repo's):

  unit MLP (2 dense, relu between) -> masked max and mean pooling over
  units; hero MLP, global MLP; concat -> trunk dense, relu;
  single-layer LSTM (x and h projections split, forget-gate bias +1,
  gate order i f g o) unrolled over seq_len+1 observations from the
  shipped carry;
  heads: action type, move x, move y, target query . unit embeddings /
  sqrt(D), value; illegal choices masked with -1e9 before log-softmax;
  loss: GAE(gamma, lambda) on the stop-gradient values, advantages
  normalised over the batch, PPO clipped surrogate, clipped value loss,
  entropy bonus of the factorised distribution;
  optimizer: clip by global norm, then Adam.

`quant`, where given, is applied to both operands of every matrix
product: it is how the control computes this same step in the precision
below the configuration's (see `control.py`).
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from benchmark.tree import first_gradient, flat_numbers, leaf_diff_norms, leaf_norms

BIG_NEG = -1e9
ACT_MOVE, ACT_ATTACK, ACT_CAST = 1, 2, 3
HI = jax.lax.Precision.HIGHEST


def _mm(a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=HI)


def _dense(p, x, quant):
    return _mm(x, p["kernel"], quant) + p["bias"]


def _masked_log_softmax(logits, mask):
    return jax.nn.log_softmax(jnp.where(mask, logits, BIG_NEG), axis=-1)


def forward(params, rows, config: dict, quant: Optional[Callable] = None):
    """Teacher-forced unroll over the rows' seq_len+1 observations.
    Returns the four heads' log-probs [B, T+1, .] and the values [B, T+1]."""
    p = params["params"]["core"]
    D = int(config["policy"]["unit_embed_dim"])
    H = int(config["policy"]["lstm_hidden"])
    f32 = jnp.float32

    unit_mask = rows["unit_mask"]
    x = jax.nn.relu(_dense(p["unit_mlp1"], rows["unit_feats"].astype(f32), quant))
    unit_emb = _dense(p["unit_mlp2"], x, quant)  # [B, T1, U, D]
    m = unit_mask[..., None]
    pool_max = jnp.max(jnp.where(m, unit_emb, BIG_NEG), axis=-2)
    pool_max = jnp.where(jnp.any(unit_mask, axis=-1, keepdims=True), pool_max, 0.0)
    denom = jnp.maximum(jnp.sum(m, axis=-2), 1).astype(f32)
    pool_mean = jnp.sum(jnp.where(m, unit_emb, 0.0), axis=-2) / denom
    hero = jax.nn.relu(_dense(p["hero_mlp"], rows["hero_feats"].astype(f32), quant))
    glob = jax.nn.relu(_dense(p["global_mlp"], rows["global_feats"].astype(f32), quant))
    trunk = jnp.concatenate([hero, glob, pool_max, pool_mean], axis=-1)
    trunk = jax.nn.relu(_dense(p["trunk"], trunk, quant))  # [B, T1, H]

    x_proj = _mm(trunk, p["lstm"]["w_x"], quant) + p["lstm"]["bias"]  # [B, T1, 4H]
    w_h = p["lstm"]["w_h"]

    def cell(carry, xp_t):
        c, h = carry
        z = xp_t + _mm(h, w_h, quant)
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (c, h), h

    _, h_seq = jax.lax.scan(
        cell, (rows["c0"].astype(f32), rows["h0"].astype(f32)), jnp.swapaxes(x_proj, 0, 1)
    )
    out = jnp.swapaxes(h_seq, 0, 1)  # [B, T1, H]
    assert out.shape[-1] == H

    type_logp = _masked_log_softmax(_dense(p["type_head"], out, quant), rows["action_mask"])
    move_x_logp = jax.nn.log_softmax(_dense(p["move_x_head"], out, quant), axis=-1)
    move_y_logp = jax.nn.log_softmax(_dense(p["move_y_head"], out, quant), axis=-1)
    query = _dense(p["target_query"], out, quant)  # [B, T1, D]
    q, e = (quant(query), quant(unit_emb)) if quant is not None else (query, unit_emb)
    target_logits = jnp.einsum("btd,btud->btu", q, e, precision=HI) / math.sqrt(D)
    target_logp = _masked_log_softmax(target_logits, rows["target_mask"])
    value = _dense(p["value_head"], out, quant)[..., 0]
    return type_logp, move_x_logp, move_y_logp, target_logp, value


def _gather(logp, idx):
    return jnp.take_along_axis(logp, idx[..., None], axis=-1)[..., 0]


def _ent(logp):
    return -jnp.sum(jnp.exp(logp) * logp, axis=-1)


def loss_fn(params, rows, config: dict, quant=None, row_weight=None):
    """The PPO loss of one batch of full-length rows. `row_weight`
    ([B], 0 or 1) leaves rows out of every mean: the planted fault
    "half of the batch left out, the mean taken over the rest"."""
    c = config["ppo"]
    T = rows["rewards"].shape[1]
    type_lp, mx_lp, my_lp, tg_lp, value = forward(params, rows, config, quant)
    mask = jnp.ones_like(rows["rewards"])
    if row_weight is not None:
        mask = mask * row_weight[:, None]
    n = jnp.maximum(jnp.sum(mask), 1.0)

    def mean(x):
        return jnp.sum(x * mask) / n

    # GAE on the values as data.
    v = jax.lax.stop_gradient(value)
    nonterm = 1.0 - rows["dones"]
    delta = (rows["rewards"] + c["gamma"] * nonterm * v[:, 1:] - v[:, :-1]) * mask
    adv_t = jnp.zeros_like(delta[:, 0])
    advs = []
    for t in range(T - 1, -1, -1):
        adv_t = (delta[:, t] + c["gamma"] * c["gae_lambda"] * nonterm[:, t] * adv_t) * mask[:, t]
        advs.append(adv_t)
    adv = jnp.stack(advs[::-1], axis=1)
    returns = adv + v[:, :-1] * mask
    adv_mean = mean(adv)
    adv_std = jnp.sqrt(mean((adv - adv_mean) ** 2) + 1e-8)
    norm_adv = jax.lax.stop_gradient((adv - adv_mean) / adv_std * mask)

    a_type = rows["type"]
    lp = _gather(type_lp[:, :T], a_type)
    is_move = (a_type == ACT_MOVE).astype(lp.dtype)
    is_tgt = ((a_type == ACT_ATTACK) | (a_type == ACT_CAST)).astype(lp.dtype)
    lp = lp + is_move * (_gather(mx_lp[:, :T], rows["move_x"]) + _gather(my_lp[:, :T], rows["move_y"]))
    lp = lp + is_tgt * _gather(tg_lp[:, :T], rows["target"])
    ratio = jnp.exp(lp - rows["behavior_logp"])
    clipped = jnp.clip(ratio, 1.0 - c["clip_eps"], 1.0 + c["clip_eps"]) * norm_adv
    policy_loss = -mean(jnp.minimum(ratio * norm_adv, clipped))

    v_pred = value[:, :T]
    v_clip = rows["behavior_value"] + jnp.clip(
        v_pred - rows["behavior_value"], -c["value_clip"], c["value_clip"]
    )
    value_loss = 0.5 * mean(jnp.maximum((v_pred - returns) ** 2, (v_clip - returns) ** 2))

    pt = jnp.exp(type_lp[:, :T])
    ent = _ent(type_lp[:, :T])
    ent = ent + pt[..., ACT_MOVE] * (_ent(mx_lp[:, :T]) + _ent(my_lp[:, :T]))
    ent = ent + (pt[..., ACT_ATTACK] + pt[..., ACT_CAST]) * _ent(tg_lp[:, :T])
    return policy_loss + c["value_coef"] * value_loss - c["entropy_coef"] * mean(ent)


def make_step(config: dict, quant=None, fault: Optional[str] = None):
    """One optimizer step as a pure function
    (params, mu, nu, count, rows) -> (params, mu, nu, count, loss, raw
    grad leaf norms, the gradient as the optimizer gets it).
    `fault`, for the control runs only: "half_batch" (second half of the
    rows left out of every mean)."""
    c = config["ppo"]

    def step(params, mu, nu, count, rows):
        B = rows["rewards"].shape[0]
        weight = None
        if fault == "half_batch":
            weight = (jnp.arange(B) < B // 2).astype(jnp.float32)
        elif fault is not None:
            raise ValueError(f"unknown fault {fault!r}")
        loss, grads = jax.value_and_grad(loss_fn)(params, rows, config, quant, weight)
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


def run_reference(config: dict, params0, batches, key, quant=None, fault=None,
                  shardings=None) -> dict:
    """Follow the first `len(batches)` optimizer steps from `params0` on
    the given row batches. Returns the readings that `check.py` compares,
    as host numbers: each step's loss, the first step's gradient per leaf
    (its norm raw, and its norm and its sketch under `key` as the
    optimizer gets it), and the norm of each leaf's change after the last
    step. `shardings` = (replicated, rows) puts the
    same plain step across chips by rows."""
    step = make_step(config, quant, fault)
    if shardings is not None:
        rep, by_rows = shardings
        step = jax.jit(step, in_shardings=(rep, rep, rep, rep, by_rows), donate_argnums=(1, 2))
    else:
        step = jax.jit(step, donate_argnums=(1, 2))
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
    D, M, H = int(pol["unit_embed_dim"]), int(pol["mlp_hidden"]), int(pol["lstm_hidden"])
    bins = int(pol["n_move_bins"])

    def dense(i, o):
        return {"bias": (o,), "kernel": (i, o)}

    core = {
        "global_mlp": dense(int(f["global_features"]), M // 4),
        "hero_mlp": dense(int(f["hero_features"]), M),
        "lstm": {"bias": (4 * H,), "w_h": (H, 4 * H), "w_x": (H, 4 * H)},
        "move_x_head": dense(H, bins),
        "move_y_head": dense(H, bins),
        "target_query": dense(H, D),
        "trunk": dense(M + M // 4 + 2 * D, H),
        "type_head": dense(H, int(f["n_action_types"])),
        "unit_mlp1": dense(int(f["unit_features"]), M),
        "unit_mlp2": dense(M, D),
        "value_head": dense(H, 1),
    }
    return {"params": {"core": core}}


def forward_flops_per_frame(config: dict) -> float:
    """Matrix-multiply operations of one observation's forward pass,
    2*M*N*K per [M,K]x[K,N]: a copy of `dotaclient_tpu/ops/flops.py`,
    kept here so that the yardstick cannot move with the program."""
    pol, f = config["policy"], config["features"]
    U, UF = int(f["max_units"]), int(f["unit_features"])
    D, M, H = int(pol["unit_embed_dim"]), int(pol["mlp_hidden"]), int(pol["lstm_hidden"])
    fl = 0.0
    fl += 2.0 * U * UF * M  # unit_mlp1
    fl += 2.0 * U * M * D  # unit_mlp2
    fl += 2.0 * int(f["hero_features"]) * M  # hero_mlp
    fl += 2.0 * int(f["global_features"]) * (M // 4)  # global_mlp
    fl += 2.0 * (M + M // 4 + 2 * D) * H  # trunk
    fl += 2.0 * H * 4 * H  # x projection
    fl += 2.0 * H * 4 * H  # recurrence
    head_out = int(f["n_action_types"]) + 2 * int(pol["n_move_bins"]) + D + 1
    fl += 2.0 * H * head_out
    fl += 2.0 * U * D  # target scores
    return fl


def train_step_flops(config: dict, rows: int) -> float:
    """One optimizer step over `rows` rows of seq_len+1 observations: the
    backward pass is twice the forward; recomputed operations do not
    count; elementwise work and the optimizer are left out (a few
    percent)."""
    frames = rows * (int(config["learner"]["seq_len"]) + 1)
    return 3.0 * frames * forward_flops_per_frame(config)


def n_params(config: dict) -> int:
    leaves = jax.tree.leaves(param_shapes(config), is_leaf=lambda x: isinstance(x, tuple))
    return sum(math.prod(shape) for shape in leaves)


def scope_costs(config: dict, rows: int) -> dict:
    """What one optimizer step over `rows` rows on one chip needs at the
    least inside a layer scope of `scopes/<config>.json`, for the scope's
    share of its roofline: matrix-multiply operations counted as
    `train_step_flops` counts them, and bytes to and from the chip's memory.

    `lstm`: the input and the recurrent product of every observation,
    forward and twice that backward; the two matrices and the bias read
    and their gradients written once in float32, the layer's input and
    output and their two cotangents passed once in the compute type.
    `optimizer`: Adam reads parameter, gradient and both moments and writes
    parameter and both moments, 7 float32 values a parameter; it multiplies
    no matrices, so its bytes alone bound it."""
    H = int(config["policy"]["lstm_hidden"])
    frames = rows * (int(config["learner"]["seq_len"]) + 1)
    itemsize = jnp.dtype(config["policy"]["dtype"]).itemsize
    lstm_params = 2 * H * 4 * H + 4 * H
    return {
        "lstm": {"flops": 3.0 * frames * 2 * (2.0 * H * 4 * H),
                 "bytes": 8.0 * lstm_params + 4.0 * frames * H * itemsize},
        "optimizer": {"flops": 0.0, "bytes": 28.0 * n_params(config)},
    }
