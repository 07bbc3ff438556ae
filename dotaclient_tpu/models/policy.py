"""Flax LSTM actor-critic — the TPU-native re-design of the reference's
policy.py (SURVEY.md §2 "Policy net", §3.3 call stack).

Reference architecture (PyTorch): per-unit MLP embeddings pooled over
nearby units + hero stats → LSTM(~128) → heads {action-enum, move-x,
move-y (9-way grids), target-unit via dot-product attention over unit
embeddings, value}, with invalid-action masking and a joint log-prob over
selected sub-heads. TPU-first decisions here:

- **One module, two modes.** The actor needs a stateful single step, the
  learner a teacher-forced full unroll; both are the same `PolicyCore`
  applied directly or through `nn.scan` over the time axis (params
  broadcast), so step-vs-unroll equivalence is structural, not tested-in.
- **`lax.scan` over time, batch over devices.** The LSTM family's time
  axis stays inside one device (chunk length ~16, the reference regime —
  SURVEY.md §5); scaling is over the batch via the mesh. Long chunks are
  the transformer family's job (models/transformer_policy.py), where the
  time axis itself shards over an `sp` mesh axis.
- **bfloat16 compute, float32 params and heads.** Matmuls hit the MXU in
  bf16; logits/value are cast to f32 before masking/sampling/loss so the
  distribution math is stable.
- **Masks flow in as data** (from the featurizer) — no data-dependent
  Python control flow under jit.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from dotaclient_tpu.config import PolicyConfig
from dotaclient_tpu.env import featurizer as F
from dotaclient_tpu.ops import lstm as L
from dotaclient_tpu.ops.action_dist import BIG_NEG, Dist, masked_log_softmax

LSTMState = Tuple[jnp.ndarray, jnp.ndarray]  # (c, h), each [B, H]


class AuxOutputs(NamedTuple):
    """Auxiliary value heads (benchmark config 5): win-prob logit,
    predicted last-hit rate, predicted net-worth (both normalized)."""

    win_logit: jnp.ndarray  # [...]
    last_hit: jnp.ndarray  # [...]
    net_worth: jnp.ndarray  # [...]


class PolicyOutput(NamedTuple):
    dist: Dist
    value: jnp.ndarray  # [...] f32
    aux: Optional[AuxOutputs]
    # Counters of the forward pass for the step's metrics (scalars by
    # name; a routed-expert core's routing load), or None.
    stats: Optional[dict] = None


def _dtype(cfg: PolicyConfig):
    return jnp.dtype(cfg.dtype)


class LSTMCell(nn.Module):
    """LSTM with a split gate matmul: x and h project separately so the
    x half hoists out of the time loop entirely (ONE [B·T, in]×[in, 4H]
    MXU matmul per unroll), and the sequential remainder — the [B, H]
    hidden projection + gate tail — runs through ops/lstm.py, where a
    fused Pallas kernel serves the TPU path and lax.scan everything
    else. Forget-gate bias +1; gate math f32, matmuls in `dtype`.
    """

    features: int
    dtype: jnp.dtype = jnp.bfloat16
    impl: str = "auto"  # ops/lstm.py dispatcher: auto|scan|pallas|pallas_interpret
    mesh: Optional[object] = None  # mesh of the surrounding jit (unroll only)

    @nn.compact
    def __call__(
        self, carry: LSTMState, x: jnp.ndarray, unroll: bool = False
    ) -> Tuple[LSTMState, jnp.ndarray]:
        H = self.features
        dt = self.dtype
        w_x = self.param("w_x", nn.initializers.lecun_normal(), (x.shape[-1], 4 * H))
        w_h = self.param("w_h", nn.initializers.lecun_normal(), (H, 4 * H))
        bias = self.param("bias", nn.initializers.zeros_init(), (4 * H,))
        c, h = carry
        x_proj = x.astype(dt) @ w_x.astype(dt) + bias.astype(dt)
        if not unroll:
            z = x_proj + h.astype(dt) @ w_h.astype(dt)
            new_c, new_h = L.gates(z, c)
            return (new_c, new_h), new_h
        h_seq, (c_T, h_T) = L.lstm_recurrence(
            x_proj, w_h.astype(dt), c, h, impl=self.impl, mesh=self.mesh
        )
        return (c_T, h_T), h_seq


def obs_trunk(cfg: PolicyConfig, obs: F.Observation):
    """Embeddings + pooling + trunk MLP, shared by both policy families.

    Must be called inside a compact scope (Flax registers the Dense
    layers on the module whose scope is active), so layer names stay
    flat ("unit_mlp1", …) and the LSTM family's param tree is identical
    to the pre-refactor layout. Returns (trunk [.., H], unit_emb
    [.., U, D]) — position-independent, so in unroll mode everything
    here is one [B·T]-batched MXU matmul.
    """
    dt = _dtype(cfg)
    D = cfg.unit_embed_dim

    unit_mask = obs.unit_mask
    units = obs.unit_feats.astype(dt)
    x = nn.Dense(cfg.mlp_hidden, dtype=dt, name="unit_mlp1")(units)
    x = nn.relu(x)
    unit_emb = nn.Dense(D, dtype=dt, name="unit_mlp2")(x)  # [B, U, D]

    # Masked max+mean pooling to a fixed-size neighbourhood context.
    m = unit_mask[..., None]
    neg = jnp.asarray(BIG_NEG, dt)
    pool_max = jnp.max(jnp.where(m, unit_emb, neg), axis=-2)
    any_unit = jnp.any(unit_mask, axis=-1, keepdims=True)
    pool_max = jnp.where(any_unit, pool_max, 0.0)
    denom = jnp.maximum(jnp.sum(m, axis=-2), 1).astype(dt)
    pool_mean = jnp.sum(jnp.where(m, unit_emb, 0.0), axis=-2) / denom

    hero = nn.Dense(cfg.mlp_hidden, dtype=dt, name="hero_mlp")(obs.hero_feats.astype(dt))
    glob = nn.Dense(cfg.mlp_hidden // 4, dtype=dt, name="global_mlp")(obs.global_feats.astype(dt))
    trunk = jnp.concatenate([nn.relu(hero), nn.relu(glob), pool_max, pool_mean], axis=-1)
    trunk = nn.relu(nn.Dense(cfg.lstm_hidden, dtype=dt, name="trunk")(trunk))
    return trunk, unit_emb


def action_heads(
    cfg: PolicyConfig, out: jnp.ndarray, unit_emb: jnp.ndarray, obs: F.Observation
) -> PolicyOutput:
    """Masked action heads + value (+aux), shared by both families.
    `out` is the temporal core's output in f32; logits compute in f32
    for stable masking/softmax."""
    D = cfg.unit_embed_dim
    type_logits = nn.Dense(F.N_ACTION_TYPES, dtype=jnp.float32, name="type_head")(out)
    move_x = nn.Dense(cfg.n_move_bins, dtype=jnp.float32, name="move_x_head")(out)
    move_y = nn.Dense(cfg.n_move_bins, dtype=jnp.float32, name="move_y_head")(out)
    # Target selection = dot-product attention of a core-output query
    # against the unit embeddings (reference's target head).
    query = nn.Dense(D, dtype=jnp.float32, name="target_query")(out)
    target_logits = jnp.einsum("...d,...ud->...u", query, unit_emb.astype(jnp.float32))
    target_logits = target_logits / jnp.sqrt(jnp.asarray(D, jnp.float32))

    dist = Dist(
        type_logp=masked_log_softmax(type_logits, obs.action_mask),
        move_x_logp=jax.nn.log_softmax(move_x, axis=-1),
        move_y_logp=jax.nn.log_softmax(move_y, axis=-1),
        target_logp=masked_log_softmax(target_logits, obs.target_mask),
    )
    value = nn.Dense(1, dtype=jnp.float32, name="value_head")(out)[..., 0]

    aux = None
    if cfg.aux_heads:
        aux = AuxOutputs(
            win_logit=nn.Dense(1, dtype=jnp.float32, name="aux_win")(out)[..., 0],
            last_hit=nn.Dense(1, dtype=jnp.float32, name="aux_lh")(out)[..., 0],
            net_worth=nn.Dense(1, dtype=jnp.float32, name="aux_nw")(out)[..., 0],
        )
    return PolicyOutput(dist=dist, value=value, aux=aux)


class PolicyCore(nn.Module):
    """The LSTM policy network: featurized obs + LSTM state → action dist
    + value. One module, both modes — single step (obs leaves [B, ...])
    and teacher-forced unroll (obs leaves [B, T, ...]). Every layer here
    except the LSTM recurrence is position-independent, so in unroll mode
    the embeddings, trunk, and heads all run as single [B·T] batched MXU
    matmuls; only the recurrence (ops/lstm.py) walks the time axis."""

    cfg: PolicyConfig
    mesh: Optional[object] = None

    @nn.compact
    def __call__(
        self, carry: LSTMState, obs: F.Observation, unroll: bool = False
    ) -> Tuple[LSTMState, PolicyOutput]:
        cfg = self.cfg
        # Named scopes: the layer an operation belongs to, in its
        # `op_name` (parallel/train_step.py has the rest). They name
        # operations only; parameter paths are flax's and do not change.
        with jax.named_scope("trunk"):
            trunk, unit_emb = obs_trunk(cfg, obs)

        # LSTM output stays f32: every head computes in f32, so a bf16
        # round-trip here would be pure precision loss.
        with jax.named_scope("lstm"):
            carry, out = LSTMCell(
                cfg.lstm_hidden, dtype=_dtype(cfg), impl=cfg.lstm_impl, mesh=self.mesh, name="lstm"
            )(carry, trunk, unroll=unroll)
        with jax.named_scope("heads"):
            return carry, action_heads(cfg, out, unit_emb, obs)


class PolicyNet(nn.Module):
    """Public policy module — family-agnostic front door.

    - `apply(params, state, obs)` — single step, obs leaves [B, ...].
    - `apply(params, state, obs_seq, unroll=True)` — teacher-forced unroll,
      obs leaves [B, T, ...]; returns outputs with a [B, T] time axis and
      the final temporal state.
    Params are identical between the two modes (every layer is shared;
    the time axis only exists inside the temporal core). cfg.arch picks
    the core: "lstm" (flagship) or "transformer" (long-context family —
    models/transformer_policy.py; its unroll ignores `state`, context is
    chunk-local). `mesh` is the mesh of the jit the unroll is traced
    under (parallel/train_step.py); only the temporal cores read it —
    the LSTM family to shard_map its kernel over `dp`, the transformer
    family to shard the time axis over cfg.tf_sp_axis when that names
    one of its axes.
    """

    cfg: PolicyConfig
    mesh: Optional[object] = None  # jax.sharding.Mesh

    def _assert_shapes(self, obs: F.Observation) -> None:
        assert obs.unit_feats.shape[-2:] == (F.MAX_UNITS, F.UNIT_FEATURES)

    @nn.compact
    def __call__(self, state, obs: F.Observation, unroll: bool = False):
        self._assert_shapes(obs)
        if self.cfg.arch == "transformer":
            # Import here: transformer_policy imports this module's
            # shared trunk/heads.
            from dotaclient_tpu.models.transformer_policy import TransformerPolicyCore

            return TransformerPolicyCore(self.cfg, self.mesh, name="core")(state, obs, unroll)
        return PolicyCore(self.cfg, self.mesh, name="core")(state, obs, unroll)

def initial_state(cfg: PolicyConfig, batch_shape):
    """Fresh temporal state without needing a module instance (host-side
    use): LSTM (c, h) zeros, or the transformer family's empty KVCache
    (keys and values for the layers that attend over their past, the
    gated delta rule's state and convolution tail for the linear ones).
    Every leaf is batch-leading in both families."""
    if cfg.arch == "transformer":
        from dotaclient_tpu.models.transformer_policy import init_cache

        return init_cache(cfg, batch_shape)
    shape = tuple(batch_shape) + (cfg.lstm_hidden,)
    return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))


def wire_state(cfg: PolicyConfig, state):
    """The (c, h) [B, H] f32 pair the fixed wire format ships with each
    rollout (transport/serialize.py). The LSTM's state IS that pair; a
    transformer KVCache (a linear layer's state among it) maps to zeros —
    the learner's unroll is chunk-local and ignores initial state, so
    nothing real is lost and the wire format stays family-agnostic."""
    if cfg.arch == "transformer":
        import numpy as np

        B = state.idx.shape[0]
        z = np.zeros((B, cfg.lstm_hidden), np.float32)
        return (z, z)
    return state


def reset_between_chunks(cfg: PolicyConfig, state):
    """Chunk-boundary state transition for the actor. The LSTM carries
    its state across chunks (the learner receives it on the wire —
    SURVEY.md §7 "LSTM state handoff"); the transformer family resets to
    an empty cache so acting context matches the learner's chunk-local
    teacher-forced re-eval exactly: a linear layer's state and
    convolution tail go to zero with the keys and values, which is where
    the learner's unroll starts them."""
    if cfg.arch == "transformer":
        from dotaclient_tpu.models.transformer_policy import init_cache

        return init_cache(cfg, (state.idx.shape[0],))
    return state


@functools.partial(jax.jit, static_argnums=0)
def _init_params(cfg_fields: tuple, rng: jax.Array):
    cfg = PolicyConfig(*cfg_fields)
    obs = jax.tree.map(lambda x: jnp.asarray(x)[None], F.zeros_observation())
    return PolicyNet(cfg).init(rng, initial_state(cfg, (1,)), obs)


def init_params(cfg: PolicyConfig, rng: jax.Array):
    """Initialize parameters with a dummy single-step batch of 1. ONE
    jitted program per policy shape (the config's fields are the static
    key): run op by op, flax's init compiles a few hundred one-op
    programs — most of a binary's boot on any backend."""
    return _init_params(dataclasses.astuple(cfg), rng)
