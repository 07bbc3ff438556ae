"""PPO clipped-surrogate loss over teacher-forced LSTM re-evaluation.

Mirrors the reference learner's loss (SURVEY.md §3.2): re-run the policy
over the shipped sequences with the shipped initial hidden state, form
ratio = exp(logp_new − logp_old) against the actor-side log-probs, and
combine clipped surrogate + value loss + entropy bonus — all masked means
over real steps. Value loss is clipped against the actor-side value
(PPO2-style) to bound value-function drift under stale experience.

Everything here is a pure function of (params, batch) — the train step
wrapper in parallel/train_step.py owns optax and the mesh.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax.numpy as jnp

from dotaclient_tpu.config import PPOConfig
from dotaclient_tpu.ops import action_dist as ad
from dotaclient_tpu.ops.batch import TrainBatch
from dotaclient_tpu.ops.gae import gae, masked_mean, masked_std

import jax


def _surrogate(
    out,
    actions,
    behavior_logp,
    behavior_value,
    advantages,
    returns,
    mask,
    aux_targets,
    cfg: PPOConfig,
    aux_coef: float,
    staleness=None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Clipped surrogate + value + entropy (+aux) given a completed unroll
    `out` and FIXED advantages/returns — shared by the one-update path
    (which derives them from the same forward) and the sample-reuse path
    (which precomputes them once per consumed batch). Advantages are
    normalized over whatever slice `mask` covers — the full batch in the
    one-update path, the minibatch in the reuse path (the PPO2
    convention).

    `staleness` ([B] f32 or None) is the replay reservoir's per-row
    behavior-policy staleness stamp (runtime/staging.py). Rows with
    staleness > 0 were sampled off-policy from the reservoir; their IS
    ratio is truncated at cfg.replay_rho_bar (ACER's c-bar, arxiv
    1611.01224) before entering the surrogate, bounding the one corner
    plain PPO clipping leaves unbounded (A < 0 with ratio >> 1, where
    min(unclipped, clipped) selects the unclipped term). Rows with
    staleness 0 — and the staleness=None replay-disabled path — use the
    raw ratio, so the loss is bit-identical to plain PPO there."""
    T = actions.type.shape[1]
    values = out.value  # [B, T+1]
    dist_t = jax.tree.map(lambda x: x[:, :T], out.dist)

    new_logp = ad.log_prob(dist_t, actions)
    ratio = jnp.exp(new_logp - behavior_logp)

    norm_adv = (advantages - masked_mean(advantages, mask)) / masked_std(advantages, mask)
    norm_adv = jax.lax.stop_gradient(norm_adv * mask)

    if staleness is not None:
        stale_row = (staleness > 0.0).astype(ratio.dtype)[:, None]  # [B, 1] over T
        surr_ratio = jnp.where(stale_row > 0, jnp.minimum(ratio, cfg.replay_rho_bar), ratio)
        trunc_frac = masked_mean(
            (stale_row * (ratio > cfg.replay_rho_bar)).astype(jnp.float32), mask
        )
    else:
        surr_ratio = ratio
        trunc_frac = jnp.zeros((), jnp.float32)

    unclipped = surr_ratio * norm_adv
    clipped = jnp.clip(surr_ratio, 1.0 - cfg.clip_eps, 1.0 + cfg.clip_eps) * norm_adv
    policy_loss = -masked_mean(jnp.minimum(unclipped, clipped), mask)

    v_pred = values[:, :T]
    v_clipped = behavior_value + jnp.clip(
        v_pred - behavior_value, -cfg.value_clip, cfg.value_clip
    )
    v_err = jnp.maximum((v_pred - returns) ** 2, (v_clipped - returns) ** 2)
    value_loss = 0.5 * masked_mean(v_err, mask)

    entropy = masked_mean(ad.entropy(dist_t), mask)

    loss = policy_loss + cfg.value_coef * value_loss - cfg.entropy_coef * entropy

    metrics = {
        "loss": loss,
        "policy_loss": policy_loss,
        "value_loss": value_loss,
        "entropy": entropy,
        "ratio_mean": masked_mean(ratio, mask),
        "ratio_clip_frac": masked_mean(
            (jnp.abs(ratio - 1.0) > cfg.clip_eps).astype(jnp.float32), mask
        ),
        "approx_kl": masked_mean(behavior_logp - new_logp, mask),
        "advantage_mean": masked_mean(advantages, mask),
        "return_mean": masked_mean(returns, mask),
        "value_mean": masked_mean(v_pred, mask),
        # Always present (0.0 when replay is off) so the metrics dict —
        # and the reuse scan's carried metric structure — never changes
        # shape with the replay flag.
        "replay_trunc_frac": trunc_frac,
    }

    if aux_targets is not None and out.aux is not None:
        aux_t = jax.tree.map(lambda x: x[:, :T], out.aux)
        win_prob_loss = masked_mean(
            # ±1 labels → BCE on the win logit; 0 labels mean "unknown yet"
            # and are masked out.
            jnp.where(
                aux_targets.win != 0.0,
                jnp.logaddexp(0.0, -aux_targets.win * aux_t.win_logit),
                0.0,
            ),
            mask,
        )
        lh_loss = masked_mean((aux_t.last_hit - aux_targets.last_hit) ** 2, mask)
        nw_loss = masked_mean((aux_t.net_worth - aux_targets.net_worth) ** 2, mask)
        aux_loss = win_prob_loss + lh_loss + nw_loss
        loss = loss + aux_coef * aux_loss
        metrics["loss"] = loss
        metrics["aux_loss"] = aux_loss

    return loss, metrics


def ppo_loss(
    params,
    apply_fn,
    batch: TrainBatch,
    cfg: PPOConfig,
    aux_coef: float = 0.25,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Returns (scalar loss, metrics dict). `apply_fn(params, state, obs,
    unroll=True)` is PolicyNet.apply. One forward serves both GAE (through
    a stop_gradient) and the surrogate — the single-update train path."""
    mask = batch.mask
    _, out = apply_fn(params, batch.initial_state, batch.obs, unroll=True)
    advantages, returns = gae(
        batch.rewards,
        jax.lax.stop_gradient(out.value),
        batch.dones,
        mask,
        cfg.gamma,
        cfg.gae_lambda,
    )
    loss, metrics = _surrogate(
        out,
        batch.actions,
        batch.behavior_logp,
        batch.behavior_value,
        advantages,
        returns,
        mask,
        batch.aux,
        cfg,
        aux_coef,
        staleness=batch.behavior_staleness,
    )
    metrics.update(out.stats or {})  # the forward pass's own counters
    return loss, metrics


class ReuseBatch(NamedTuple):
    """A consumed batch with advantages/returns FROZEN from the pre-update
    policy — what the epochs x minibatches reuse loop shuffles and slices.
    (Classic PPO computes GAE once per batch, not once per update.)"""

    obs: object
    actions: object
    behavior_logp: jnp.ndarray
    behavior_value: jnp.ndarray
    advantages: jnp.ndarray
    returns: jnp.ndarray
    mask: jnp.ndarray
    initial_state: object
    aux: object  # AuxTargets or None
    staleness: object = None  # [B] f32 replay staleness stamp, or None


def precompute_reuse(params, apply_fn, batch: TrainBatch, cfg: PPOConfig) -> ReuseBatch:
    """One forward with the CURRENT (pre-update) params → frozen
    advantages/returns for the whole reuse loop."""
    _, out = apply_fn(params, batch.initial_state, batch.obs, unroll=True)
    advantages, returns = gae(
        batch.rewards,
        jax.lax.stop_gradient(out.value),
        batch.dones,
        batch.mask,
        cfg.gamma,
        cfg.gae_lambda,
    )
    return ReuseBatch(
        obs=batch.obs,
        actions=batch.actions,
        behavior_logp=batch.behavior_logp,
        behavior_value=batch.behavior_value,
        advantages=jax.lax.stop_gradient(advantages),
        returns=jax.lax.stop_gradient(returns),
        mask=batch.mask,
        initial_state=batch.initial_state,
        aux=batch.aux,
        staleness=batch.behavior_staleness,
    )


def ppo_minibatch_loss(
    params, apply_fn, mb: ReuseBatch, cfg: PPOConfig, aux_coef: float = 0.25
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The reuse loop's per-update loss: fresh forward on the minibatch,
    surrogate against the frozen advantages/returns."""
    _, out = apply_fn(params, mb.initial_state, mb.obs, unroll=True)
    return _surrogate(
        out,
        mb.actions,
        mb.behavior_logp,
        mb.behavior_value,
        mb.advantages,
        mb.returns,
        mb.mask,
        mb.aux,
        cfg,
        aux_coef,
        staleness=mb.staleness,
    )
