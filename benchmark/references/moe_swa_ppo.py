"""The plain reference of the transformer policy whose block is
Mellum2-12B-A2.5B's, under PPO, which a configuration's file names
(`"reference": "moe_swa_ppo"`): the train step in float32, `jax.numpy`,
precision "highest", no kernels, no blocks skipped, no sorting of pairs,
no packing; the shapes of its parameters; and the least operations and
bytes one step needs. It imports nothing of the program and is handed
nothing the program made: the weights come from `weights.py` (by
`param_shapes`) and the rows from `frames.py`, both from the seed.

The layer, as the published config gives it (x the float32 residual of
one row, [F, D], frame t at position t):

  h  = rmsnorm(x, g1)
  q = h Wq [D -> N x Dh], k = h Wk, v = h Wv [D -> G x Dh], no bias
  q, k = rope_kind(q, k, pos): sliding layers the default table of theta;
         full layers YaRN (inverse frequencies blended between the
         default and default / factor by the linear ramp over the
         correction range; cos and sin times 0.1 ln(factor) + 1)
  s_ij = q_i . k_j / sqrt(Dh), kept where 0 <= i - j and, in sliding
         layers, i - j < window; softmax; query head n reads key/value
         head n // (N // G)
  x  = x + attn Wo
  h2 = rmsnorm(x, g2)
  p  = softmax(h2 Wr) over all E experts; (chosen, w) = top_k(p); w = w / sum(w)
  y  = sum over the chosen e that are held here of
       w_e * (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e
  x  = x + y
then a final rmsnorm. Departures from the published model, each also
under `assumed` in the configuration's file:

- no vocabulary: this system's token is an env-step with continuous
  features, so the observation trunk and the action heads of this repo's
  policy (as in `lstm_ppo.py`) stand where the embedding and the output
  head are, and the loss is PPO's;
- the router's scores are standardised per expert before the softmax:
  less their running mean, over the root of the running mean of that
  difference's square, over the row's frames so far (`moe_standardize_router`; causal, so the
  actor's step agrees). Under seeded weights the residual stream is
  mostly one vector that every frame shares (three quarters of a relu
  trunk's output, then what the attention layers average) and what is
  left of it favours some experts several times over others, by the
  seed; a trained model's load-balancing loss prevents both, seeded
  weights have had none, and the standardising stands in for it; p above
  is the softmax of the standardised scores;
- the experts held are a share [first, first + held) of the E: what the
  others would add is left out and the partial sum goes on (the share of
  one chip of an expert-parallel group);
- a norm's stored parameter is g - 1 (zero from the seed, as every vector
  `weights.py` makes): the function and its training are the published
  ones;
- Wq, Wk, Wv are the column blocks of one matrix `qkv`; an expert matrix
  is kept input axis first ([D, held, I], [I, held, D]);
- the router's product, softmax and top-k are float32 and are not
  rounded by `quant`: the program keeps them so in every compute type;
- no per-head q/k norm and no multi-token head: the config names neither.

It runs a row at a time, each layer under `jax.checkpoint` and attention
a block of queries at a time against all keys under a dense mask, with
the gradients summed over the rows, so that it fits beside its own
parameters at the cell's size: one forward pass without gradients gives
every row's values (GAE and the batch's advantage statistics need them
all), a second pass takes each row's part of the loss and its gradient.

`quant`, where given, is applied to both operands of every matrix
product but the router's: it is how the control computes this same step
in the precision below the configuration's (see `control.py`).
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.tree import first_gradient, flat_numbers, leaf_diff_norms, leaf_norms

BIG_NEG = -1e9
ACT_MOVE, ACT_ATTACK, ACT_CAST = 1, 2, 3
HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512  # queries scored at a time; a size of the computation, not of the model
ROUTER_EPS = 1e-6  # under the root that a standardised score is divided by (the program's constant)


def _mm(a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=HI)


def _dense(p, x, quant):
    return _mm(x, p["kernel"], quant) + p["bias"]


def _masked_log_softmax(logits, mask):
    return jax.nn.log_softmax(jnp.where(mask, logits, BIG_NEG), axis=-1)


def _rmsnorm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + p["scale"])


def _sizes(config: dict):
    pol = config["policy"]
    return dict(
        D=int(pol["lstm_hidden"]), N=int(pol["tf_heads"]), G=int(pol["tf_kv_heads"]),
        Dh=int(pol["tf_head_dim"]), L=int(pol["tf_layers"]), W=int(pol["tf_window"]),
        E=int(pol["moe_experts"]), held=int(pol["moe_experts_held"]),
        first=int(pol["moe_first_expert"]), K=int(pol["moe_top_k"]), I=int(pol["moe_hidden"]),
        eps=float(pol["tf_norm_eps"]),
    )


def layer_kinds(config: dict):
    period = [k.strip() for k in config["policy"]["tf_layer_kinds"].split(",")]
    return [period[i % len(period)] for i in range(int(config["policy"]["tf_layers"]))]


def rope_table(config: dict, kind: str):
    """(inverse frequencies [Dh / 2], factor on cos and sin) of a layer
    kind, in float64 and then float32."""
    pol = config["policy"]
    dim, theta = int(pol["tf_head_dim"]), float(pol["tf_rope_theta"])
    i = np.arange(dim // 2, dtype=np.float64)
    inv = theta ** (-2.0 * i / dim)
    factor = float(pol["tf_yarn_factor"])
    if kind != "full" or not factor:
        return inv.astype(np.float32), 1.0
    original = float(pol["tf_yarn_original_context"])

    def index_of(turns):  # where a rotation makes `turns` turns within the original context
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(index_of(float(pol["tf_yarn_beta_fast"]))), 0)
    high = min(math.ceil(index_of(float(pol["tf_yarn_beta_slow"]))), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (inv / factor * ramp + inv * (1.0 - ramp)).astype(np.float32), 0.1 * math.log(factor) + 1.0


def _rope(x, table):
    """x [F, heads, Dh], frame t at position t; pairs (i, i + Dh / 2)."""
    inv, factor = table
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv  # [F, Dh / 2]
    cos, sin = (jnp.cos(ang) * factor)[:, None, :], (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def attention(p, x, kind: str, config: dict, quant, fault=None):
    """One row's attention part of a layer: x [F, D] -> what is added to x."""
    z = _sizes(config)
    N, G, Dh = z["N"], z["G"], z["Dh"]
    F = x.shape[0]
    h = _rmsnorm(x, p["ln1"], z["eps"])
    qkv = _mm(h, p["qkv"]["kernel"], quant)
    q, k, v = jnp.split(qkv, [N * Dh, (N + G) * Dh], axis=-1)
    table = rope_table(config, kind)
    q = _rope(q.reshape(F, N, Dh), table).reshape(F, G, N // G, Dh)
    k = _rope(k.reshape(F, G, Dh), table)
    v = v.reshape(F, G, Dh)
    window = z["W"] if kind == "sliding" and fault != "sliding_as_full" else 0
    qk, vq = (quant(k), quant(v)) if quant is not None else (k, v)
    j = jnp.arange(F)

    @jax.checkpoint
    def block(q_blk, i_blk):  # [n, G, R, Dh] queries at rows i_blk against every key, dense mask
        qq = quant(q_blk) if quant is not None else q_blk
        s = jnp.einsum("qgrd,kgd->grqk", qq, qk, precision=HI) / math.sqrt(Dh)
        d = i_blk[:, None] - j[None, :]
        keep = (d >= 0) & (d < window) if window else d >= 0
        a = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        a = quant(a) if quant is not None else a
        return jnp.einsum("grqk,kgd->qgrd", a, vq, precision=HI)

    n = min(QUERY_BLOCK, F)
    pad = -F % n  # the last block's padding rows are queries at rows >= F: cut off below
    qp = jnp.pad(q, [(0, pad), (0, 0), (0, 0), (0, 0)]).reshape(-1, n, G, N // G, Dh)
    ip = jnp.arange(F + pad).reshape(-1, n)
    out = jax.lax.map(lambda a: block(*a), (qp, ip)).reshape(F + pad, N * Dh)[:F]
    return _mm(out, p["attn_out"]["kernel"], quant)


def experts(p, x, config: dict, quant, fault=None):
    """One row's expert part of a layer, x [F, D] -> (what is added to x,
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
    probs = jax.nn.softmax(scores, axis=-1)
    w, chosen = jax.lax.top_k(probs, z["K"])
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    y = jnp.zeros_like(x)
    counts = []
    for e in range(z["held"]):  # a loop over the held experts, every frame through each
        w_e = jnp.sum(jnp.where(chosen == z["first"] + e, w, 0.0), axis=-1)  # 0 where not chosen
        counts.append(jnp.sum(chosen == z["first"] + e))
        if fault == "expert_left_out" and e == 0:
            continue
        act = jax.nn.silu(_mm(h, m["w_gate"][:, e], quant)) * _mm(h, m["w_up"][:, e], quant)
        y = y + w_e[:, None] * _mm(act, m["w_down"][:, e], quant)
    return y, jnp.stack(counts)


def core(p, x, config: dict, quant, fault=None):
    """One row through the layers and the final norm: [F, D] -> [F, D]."""
    for i, kind in enumerate(layer_kinds(config)):
        blk = p[f"block{i}"]
        x = x + jax.checkpoint(lambda b, x, kind=kind: attention(b, x, kind, config, quant, fault))(blk, x)
        x = x + jax.checkpoint(lambda b, x: experts(b, x, config, quant, fault)[0])(blk, x)
    return _rmsnorm(x, p["ln_f"], _sizes(config)["eps"])


def forward_row(params, row, config: dict, quant: Optional[Callable] = None, fault=None):
    """One row's seq_len+1 observations (leaves [T1, ...]; the shipped
    carry is not used: the context is the row). Returns the four heads'
    log-probs [T1, .] and the values [T1]."""
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


def _gather(logp, idx):
    return jnp.take_along_axis(logp, idx[..., None], axis=-1)[..., 0]


def _ent(logp):
    return -jnp.sum(jnp.exp(logp) * logp, axis=-1)


def advantages(rows, value, mask, c: dict):
    """GAE on the values as data, and the advantages normalised over the
    batch: ([B, T] normalised advantages, [B, T] returns)."""
    nonterm = 1.0 - rows["dones"]
    delta = (rows["rewards"] + c["gamma"] * nonterm * value[:, 1:] - value[:, :-1]) * mask

    def back(adv_next, xs):
        d_t, nt_t, m_t = xs
        adv_t = (d_t + c["gamma"] * c["gae_lambda"] * nt_t * adv_next) * m_t
        return adv_t, adv_t

    _, adv = jax.lax.scan(back, jnp.zeros_like(delta[:, 0]), (delta.T, nonterm.T, mask.T), reverse=True)
    adv = adv.T
    returns = adv + value[:, :-1] * mask
    n = jnp.maximum(jnp.sum(mask), 1.0)
    adv_mean = jnp.sum(adv * mask) / n
    adv_std = jnp.sqrt(jnp.sum((adv - adv_mean) ** 2 * mask) / n + 1e-8)
    return (adv - adv_mean) / adv_std * mask, returns


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
    """The PPO loss of one batch of full-length rows and its gradient,
    a row at a time. "half_batch" leaves the second half of the rows out
    of every mean; the other faults are planted in the layers."""
    B = rows["rewards"].shape[0]
    mask = jnp.ones_like(rows["rewards"])
    if fault == "half_batch":
        mask = mask * (jnp.arange(B) < B // 2).astype(jnp.float32)[:, None]
    elif fault not in (None, "sliding_as_full", "expert_left_out"):
        raise ValueError(f"unknown fault {fault!r}")
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


def run_reference(config: dict, params0, batches, key, quant=None, fault=None,
                  shardings=None) -> dict:
    """Follow the first `len(batches)` optimizer steps from `params0` on
    the given row batches. Returns the readings that `check.py` compares,
    as host numbers: each step's loss, the first step's gradient per leaf
    (its norm raw, and its norm and its sketch under `key` as the
    optimizer gets it), and the norm of each leaf's change after the last
    step. `shardings` = (replicated, rows) names where the arguments
    live on several chips."""
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
    z = _sizes(config)
    Du, M, H = int(pol["unit_embed_dim"]), int(pol["mlp_hidden"]), z["D"]
    bins = int(pol["n_move_bins"])

    def dense(i, o):
        return {"bias": (o,), "kernel": (i, o)}

    block = {
        "ln1": {"scale": (H,)},
        "qkv": {"kernel": (H, (z["N"] + 2 * z["G"]) * z["Dh"])},
        "attn_out": {"kernel": (z["N"] * z["Dh"], H)},
        "ln2": {"scale": (H,)},
        "moe": {
            "router": (H, z["E"]),
            "w_gate": (H, z["held"], z["I"]),
            "w_up": (H, z["held"], z["I"]),
            "w_down": (z["I"], z["held"], H),
        },
    }
    tf = {f"block{i}": block for i in range(z["L"])}
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


def attended_pairs(frames: int, window: int = 0) -> float:
    """The (query, key) pairs a row of `frames` frames keeps under the
    causal mask, and within `window` where given."""
    if not window or window >= frames:
        return frames * (frames + 1) / 2.0
    return window * (window + 1) / 2.0 + (frames - window) * float(window)


def forward_flops_per_row(config: dict) -> dict:
    """The least matrix-multiply operations of one row's forward pass, by
    part, 2*M*N*K per [M,K]x[K,N]: the pairs the masks keep and no others,
    and the pairs an even routing sends to the experts held here (top_k *
    held / experts a frame). Kept here so that the yardstick cannot move
    with the program (`dotaclient_tpu/ops/flops.py` has to agree)."""
    pol, f = config["policy"], config["features"]
    z = _sizes(config)
    U, UF = int(f["max_units"]), int(f["unit_features"])
    Du, M, H = int(pol["unit_embed_dim"]), int(pol["mlp_hidden"]), z["D"]
    frames = int(config["learner"]["seq_len"]) + 1
    trunk = 2.0 * U * UF * M + 2.0 * U * M * Du + 2.0 * int(f["hero_features"]) * M
    trunk += 2.0 * int(f["global_features"]) * (M // 4) + 2.0 * (M + M // 4 + 2 * Du) * H
    heads = 2.0 * H * (int(f["n_action_types"]) + 2 * int(pol["n_move_bins"]) + Du + 1) + 2.0 * U * Du
    proj = 2.0 * H * (z["N"] + 2 * z["G"]) * z["Dh"] + 2.0 * z["N"] * z["Dh"] * H
    out = {"trunk": frames * trunk, "heads": frames * heads, "attn_window": 0.0, "attn_full": 0.0}
    for kind in layer_kinds(config):
        pairs = attended_pairs(frames, z["W"] if kind == "sliding" else 0)
        out["attn_window" if kind == "sliding" else "attn_full"] += (
            frames * proj + pairs * 4.0 * z["N"] * z["Dh"])
    held_pairs = z["K"] * z["held"] / z["E"]
    out["moe"] = z["L"] * frames * (2.0 * H * z["E"] + held_pairs * 3 * 2.0 * H * z["I"])
    return out


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
    bytes to and from the chip's memory.

    Bytes, the same rule for the three parts of a layer: the part's
    matrices read once and their gradients written once in float32; its
    input and its output and their two cotangents passed once, a float32
    residual each way ([frames, D]); in the attention parts q, k, v and
    the heads' output and their cotangents once in the compute type; in
    `moe` each held pair's row in and out and their cotangents in the
    compute type. Nothing recomputed, no score kept. `optimizer`: Adam
    reads parameter, gradient and both moments and writes parameter and
    both moments, 7 float32 values a parameter; it multiplies no matrices."""
    z = _sizes(config)
    H = z["D"]
    frames = rows * (int(config["learner"]["seq_len"]) + 1)
    item = jnp.dtype(config["policy"]["dtype"]).itemsize
    per_row = forward_flops_per_row(config)
    kinds = layer_kinds(config)
    attn_params = H + H * (z["N"] + 2 * z["G"]) * z["Dh"] + z["N"] * z["Dh"] * H
    attn_bytes = (8.0 * attn_params + 4.0 * frames * H * 4
                  + 2.0 * frames * (2 * z["N"] + 2 * z["G"]) * z["Dh"] * item)
    moe_params = H + H * z["E"] + 3 * z["held"] * H * z["I"]
    held_pairs = frames * z["K"] * z["held"] / z["E"]
    moe_bytes = 8.0 * moe_params + 4.0 * frames * H * 4 + 4.0 * held_pairs * H * item
    n_window = sum(k == "sliding" for k in kinds)
    return {
        "attn_window": {"flops": 3.0 * rows * per_row["attn_window"], "bytes": n_window * attn_bytes},
        "attn_full": {"flops": 3.0 * rows * per_row["attn_full"],
                      "bytes": (len(kinds) - n_window) * attn_bytes},
        "moe": {"flops": 3.0 * rows * per_row["moe"], "bytes": len(kinds) * moe_bytes},
        "optimizer": {"flops": 0.0, "bytes": 28.0 * n_params(config)},
    }
