"""The compiled PPO train step over a device mesh.

Reference flow (SURVEY.md §3.2): consume → pad/stack → teacher-forced
re-eval → GAE → PPO backward → Adam → grad clip → publish. Here the whole
device-side portion is ONE `jax.jit`-compiled SPMD program over the mesh:

- batch enters sharded over `dp` (leading axis), params/opt-state enter
  in their (possibly tp-sharded) layout;
- XLA inserts the gradient all-reduce over ICI — the explicit
  pmean/NCCL-allreduce of hand-written data-parallel learners is implicit
  in the sharding propagation;
- the optimizer update runs sharded in the same program (no separate
  host round-trip), and metrics come back as replicated scalars.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax

from dotaclient_tpu.config import LearnerConfig
from dotaclient_tpu.models.policy import PolicyNet, init_params
from dotaclient_tpu.ops import lstm as lstm_ops
from dotaclient_tpu.ops.batch import TrainBatch
from dotaclient_tpu.ops.ppo import ppo_loss
from dotaclient_tpu.parallel import mesh as mesh_lib

_log = logging.getLogger(__name__)


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array  # int32 scalar — doubles as the published model version


def make_optimizer(cfg: LearnerConfig) -> optax.GradientTransformation:
    return optax.chain(
        optax.clip_by_global_norm(cfg.ppo.max_grad_norm),
        optax.adam(cfg.ppo.lr, eps=cfg.ppo.adam_eps),
    )


def init_train_state(cfg: LearnerConfig, rng: jax.Array) -> TrainState:
    params = init_params(cfg.policy, rng)
    opt_state = make_optimizer(cfg).init(params)
    return TrainState(params=params, opt_state=opt_state, step=jnp.zeros((), jnp.int32))


def is_sequence_parallel(cfg: LearnerConfig, mesh) -> bool:
    """THE definition of 'sp is active' — owned here, used by both
    train-step builders and by the Learner's fused-vs-tree choice, so
    the predicate cannot fork. Raises on a tf_sp_axis that names no
    mesh axis (silent disablement would masquerade as a perf bug)."""
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    sp = cfg.policy.tf_sp_axis
    if sp and sp not in axis_sizes:
        raise ValueError(
            f"tf_sp_axis={sp!r} names no axis of mesh {dict(axis_sizes)!r} — "
            f"sequence parallelism would be silently disabled; add the axis "
            f"to --mesh_shape or clear tf_sp_axis"
        )
    return cfg.policy.arch == "transformer" and bool(sp)


def _resolve_lstm_impl(cfg: LearnerConfig, mesh) -> str:
    """Where the mesh is known: turn cfg.policy.lstm_impl into the
    implementation every unroll of this step runs, and say so once.
    The step unrolls seq_len+1 frames of the whole batch and, under
    sample reuse, of each minibatch; "auto" takes the kernel only if
    ops/lstm.py would for all of them."""
    pol = cfg.policy
    itemsize = jnp.dtype(pol.dtype).itemsize
    impls = {
        lstm_ops.resolve_impl(
            pol.lstm_impl, (rows, cfg.seq_len + 1, 4 * pol.lstm_hidden), itemsize, mesh
        )
        for rows in (cfg.batch_size, cfg.batch_size // cfg.ppo.minibatches)
    }
    impl = "scan" if "scan" in impls else impls.pop()
    _log.info(
        "lstm recurrence: impl=%s (asked %s) platform=%s mesh=%s",
        impl,
        pol.lstm_impl,
        mesh.devices.flat[0].platform,
        dict(mesh.shape),
    )
    return impl


def _build_core(cfg: LearnerConfig, mesh):
    """Shared guts of the two train-step builders: validated config,
    the un-jitted step_fn, and the state shardings."""
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = axis_sizes.get("dp", 1)
    if cfg.batch_size % max(dp, 1):
        raise ValueError(
            f"batch_size={cfg.batch_size} must be divisible by the mesh dp "
            f"axis ({dp}); adjust --batch_size or --mesh_shape"
        )
    # Sequence parallelism (transformer family only): shard the obs time
    # axis over cfg.policy.tf_sp_axis and run ring attention inside the
    # unroll. The unrolled chunk is seq_len+1 frames (bootstrap frame
    # included), so THAT count must divide by the axis.
    sp = cfg.policy.tf_sp_axis
    use_sp = is_sequence_parallel(cfg, mesh)
    if use_sp:
        if (cfg.seq_len + 1) % axis_sizes[sp]:
            raise ValueError(
                f"sequence parallelism: seq_len+1={cfg.seq_len + 1} frames must "
                f"divide by mesh axis {sp}={axis_sizes[sp]} (pick seq_len = k*{axis_sizes[sp]}-1)"
            )
        # Surface sp_mode misconfigurations at BUILD time like the
        # divisibility check above, not at first trace mid-run.
        if cfg.policy.tf_sp_mode not in ("ring", "ulysses"):
            raise ValueError(f"unknown tf_sp_mode {cfg.policy.tf_sp_mode!r} (ring|ulysses)")
        if cfg.policy.tf_sp_mode == "ulysses" and cfg.policy.tf_heads % axis_sizes[sp]:
            raise ValueError(
                f"ulysses: tf_heads={cfg.policy.tf_heads} not divisible by mesh "
                f"axis {sp}={axis_sizes[sp]} (use tf_sp_mode='ring')"
            )
    R, M = cfg.ppo.epochs, cfg.ppo.minibatches
    if R < 1 or M < 1:
        raise ValueError(f"ppo.epochs={R} and ppo.minibatches={M} must be >= 1")
    if cfg.batch_size % M:
        raise ValueError(
            f"batch_size={cfg.batch_size} must divide by ppo.minibatches={M}"
        )
    if (cfg.batch_size // M) % max(dp, 1):
        raise ValueError(
            f"minibatch size {cfg.batch_size // M} (batch_size/minibatches) must "
            f"divide by the mesh dp axis ({dp}) so each update stays dp-sharded"
        )

    policy = cfg.policy
    if policy.arch == "lstm":
        policy = dataclasses.replace(policy, lstm_impl=_resolve_lstm_impl(cfg, mesh))
    net = PolicyNet(policy, mesh=mesh)
    opt = make_optimizer(cfg)

    # Named scopes: every operation of the step carries its layer in its
    # `op_name`, forward and transpose alike, and keeps it across
    # compiles, where the compiler's own names (`fusion.437`) change.
    # `unpack` (below) and `optimizer` stand alone; `loss` encloses the
    # forward pass, whose `trunk`, `lstm` and `heads` are the policy's
    # (models/policy.py), so an operation's layer is the innermost of the
    # six in its name and what is under `loss` alone is GAE and PPO.
    if R * M == 1:

        def step_fn(state: TrainState, batch: TrainBatch) -> Tuple[TrainState, Dict]:
            with jax.named_scope("loss"):
                (loss, metrics), grads = jax.value_and_grad(ppo_loss, has_aux=True)(
                    state.params, net.apply, batch, cfg.ppo
                )
            with jax.named_scope("optimizer"):
                updates, opt_state = opt.update(grads, state.opt_state, state.params)
                params = optax.apply_updates(state.params, updates)
                metrics["grad_norm"] = optax.global_norm(grads)
                return TrainState(params, opt_state, state.step + 1), metrics

    else:
        step_fn = _build_reuse_step_fn(cfg, mesh, net, opt, use_sp, sp)

    # Shardings: derive from a concrete-shape template without materializing.
    state_template = jax.eval_shape(lambda: init_train_state(cfg, jax.random.PRNGKey(0)))
    state_shardings = TrainState(
        params=mesh_lib.param_shardings(mesh, state_template.params),
        opt_state=mesh_lib.param_shardings(mesh, state_template.opt_state),
        step=mesh_lib.replicated(mesh),
    )
    return step_fn, state_shardings, use_sp, sp


def _build_reuse_step_fn(cfg: LearnerConfig, mesh, net, opt, use_sp: bool, sp: str):
    """The sample-reuse train step (classic PPO: K epochs x M minibatches
    per consumed batch, approx-KL early stop — SURVEY §3.2 disposition +
    VERDICT r3 item 4).

    TPU-first shape: ONE compiled program per consumed batch. Advantages
    and returns are frozen from a single pre-update forward
    (ops/ppo.py precompute_reuse); a lax.scan over epochs draws a fresh
    batch permutation each epoch and an inner lax.scan walks the M
    minibatch slices. The KL early stop is a carried `active` flag: once
    a minibatch's approx_kl exceeds ppo.kl_stop, every later update body
    runs the lax.cond no-op branch — the classic mid-loop `break` with
    static shapes (skipped updates cost no real FLOPs; XLA executes only
    the taken branch).

    Minibatches stay dp-sharded: the [B, ...] leaves reshape to
    [M, B/M, ...] with a sharding constraint putting 'dp' on the B/M
    axis, so each device contributes its local share of every minibatch
    and the gradient all-reduce stays the same ICI collective as the
    single-update path. The per-epoch permutation is a global gather —
    at rollout-batch sizes (a few MB) the reshuffle cost is noise.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dotaclient_tpu.ops.ppo import ppo_minibatch_loss, precompute_reuse

    R, M = cfg.ppo.epochs, cfg.ppo.minibatches
    B = cfg.batch_size
    kl_stop = cfg.ppo.kl_stop
    has_dp = "dp" in mesh.axis_names

    metric_keys = [
        "loss",
        "policy_loss",
        "value_loss",
        "entropy",
        "ratio_mean",
        "ratio_clip_frac",
        "approx_kl",
        "advantage_mean",
        "return_mean",
        "value_mean",
        "replay_trunc_frac",
        "grad_norm",
    ] + (["aux_loss"] if cfg.policy.aux_heads else [])

    def constrain(mbs):
        """Pin [M, B/M, ...] leaves to dp (and the obs time axis to sp)."""
        if not has_dp:
            return mbs
        gen = NamedSharding(mesh, P(None, "dp"))
        con = lambda sh: (lambda x: jax.lax.with_sharding_constraint(x, sh))
        mbs = jax.tree.map(con(gen), mbs)
        if use_sp:
            obs_sh = NamedSharding(mesh, P(None, "dp", sp))
            mbs = mbs._replace(obs=jax.tree.map(con(obs_sh), mbs.obs))
        return mbs

    def update(params, opt_state, mb):
        with jax.named_scope("loss"):
            (_, metrics), grads = jax.value_and_grad(ppo_minibatch_loss, has_aux=True)(
                params, net.apply, mb, cfg.ppo
            )
        with jax.named_scope("optimizer"):
            updates, new_opt = opt.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            metrics["grad_norm"] = optax.global_norm(grads)
        return new_params, new_opt, metrics

    def step_fn(state: TrainState, batch: TrainBatch) -> Tuple[TrainState, Dict]:
        with jax.named_scope("loss"):
            rb = precompute_reuse(state.params, net.apply, batch, cfg.ppo)
        # Deterministic per-step shuffle stream; no rng carried in
        # TrainState (checkpoint layout unchanged).
        rng = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), state.step)

        def mb_body(carry, mb):
            params, opt_state, active, n_upd, metrics = carry

            def do(_):
                new_params, new_opt, m = update(params, opt_state, mb)
                if kl_stop > 0:
                    # Apply-then-stop (the cleanrl/PPO2 convention, checked
                    # per minibatch): the triggering update lands, the rest
                    # of the reuse loop is skipped.
                    still = jnp.logical_and(active, m["approx_kl"] <= kl_stop)
                else:
                    still = active
                # Carry a running SUM over executed updates (mean taken at
                # the end): last-minibatch metrics would be a different
                # statistic than the single-update path's batch mean,
                # skewing dashboards and reuse-vs-single A/Bs (ADVICE r4).
                summed = {k: metrics[k] + m[k] for k in metrics}
                return (new_params, new_opt, still, n_upd + 1, summed)

            def skip(_):
                return carry

            return jax.lax.cond(active, do, skip, None), None

        def epoch_body(carry, e_rng):
            perm = jax.random.permutation(e_rng, B)
            shuf = jax.tree.map(lambda x: jnp.take(x, perm, axis=0), rb)
            mbs = constrain(
                jax.tree.map(lambda x: x.reshape((M, B // M) + x.shape[1:]), shuf)
            )
            carry, _ = jax.lax.scan(mb_body, carry, mbs)
            return carry, None

        init = (
            state.params,
            state.opt_state,
            jnp.asarray(True),
            jnp.zeros((), jnp.int32),
            {k: jnp.zeros((), jnp.float32) for k in metric_keys},
        )
        (params, opt_state, active, n_upd, metrics), _ = jax.lax.scan(
            epoch_body, init, jax.random.split(rng, R)
        )
        # Mean over the updates that actually executed (KL stop can make
        # that fewer than R*M) — comparable to the single-update path.
        denom = jnp.maximum(n_upd.astype(jnp.float32), 1.0)
        metrics = {k: v / denom for k, v in metrics.items()}
        metrics["ppo_updates_done"] = n_upd.astype(jnp.float32)
        metrics["ppo_kl_stopped"] = 1.0 - active.astype(jnp.float32)
        return TrainState(params, opt_state, state.step + 1), metrics

    return step_fn


def build_train_step(cfg: LearnerConfig, mesh):
    """Returns (train_step, state_shardings, batch_shardings).

    `train_step(state, batch) -> (state', metrics)` is jit-compiled with
    explicit in/out shardings over `mesh`. `batch_shardings` is a
    TrainBatch-shaped PYTREE of NamedShardings — callers must device_put
    host batches with it verbatim (`jax.device_put(batch, batch_shardings)`):
    in sequence-parallel mode the obs leaves shard over (dp, sp) while
    the [B, T] scalars stay dp-only, so a single flat sharding would
    disagree with the jit's in_shardings and fail at dispatch.
    """
    step_fn, state_shardings, use_sp, sp = _build_core(cfg, mesh)
    batch_sh = mesh_lib.batch_sharding(mesh)
    batch_shardings = jax.tree.map(lambda _: batch_sh, _batch_template(cfg))
    if use_sp:
        # Only the obs leaves carry the (seq_len+1)-frame time axis the
        # ring shards; the [B, T] scalars (rewards, actions, masks) stay
        # dp-only — they are tiny and GAE scans them time-locally.
        obs_sh = mesh_lib.time_sharding(mesh, sp)
        batch_shardings = batch_shardings._replace(
            obs=jax.tree.map(lambda _: obs_sh, batch_shardings.obs)
        )
    metrics_sharding = mesh_lib.replicated(mesh)

    train_step = jax.jit(
        step_fn,
        in_shardings=(state_shardings, batch_shardings),
        out_shardings=(state_shardings, metrics_sharding),
        # Only the state is donated. The batch is NOT: callers (bench's
        # device-only loop, fixed-batch convergence tests) legitimately
        # reuse one batch across calls, and donation would delete it on
        # TPU while CPU runs silently ignore donation — a trap that
        # would only fire on silicon.
        donate_argnums=(0,),
    )
    return train_step, state_shardings, batch_shardings


def build_single_train_step(cfg: LearnerConfig, mesh):
    """Returns (fused_step, state_shardings, io: FusedBatchIO).

    Same compiled math as build_train_step, but the batch crosses the
    host→device boundary as ONE [B, row_bytes] u8 buffer instead of 17
    pytree leaves, so the per-transfer overhead is paid once
    (parallel/fused_io.py). Callers move a host TrainBatch with
    `jax.device_put(io.pack_transfer(batch), io.sharding)` (staging packs
    straight into `io.alloc_transfer()` views instead) and call
    `fused_step(state, buf)`; the unpack (byte-segment slices + free
    bitcasts) runs inside the jit and fuses into the first consumers.

    Refused in sequence-parallel mode (column-flattening would destroy
    the sp time-axis sharding) and with the replay reservoir (the per-row
    behavior_staleness stamp is not part of the row layout): the Learner
    takes build_train_step there, and a caller that gets the condition
    wrong is told so here.
    """
    step_fn, state_shardings, use_sp, _ = _build_core(cfg, mesh)
    if use_sp:
        raise ValueError(
            "fused H2D transfer is incompatible with sequence parallelism "
            "(tf_sp_axis set); use build_train_step"
        )
    if cfg.replay.enabled:
        raise ValueError(
            "fused H2D transfer is incompatible with the replay reservoir: "
            "the per-row behavior_staleness stamp is not part of the "
            "transfer layout; use build_train_step (the Learner takes the "
            "tree path automatically)"
        )
    from dotaclient_tpu.parallel.fused_io import FusedBatchIO
    from dotaclient_tpu.runtime.staging import cast_obs_to_compute_dtype

    import numpy as np

    # Template must match what staging actually emits — obs already in
    # the compute dtype when stage_obs_compute_dtype is on.
    template = cast_obs_to_compute_dtype(cfg, jax.tree.map(np.asarray, _batch_template(cfg)))
    io = FusedBatchIO(template, mesh)

    def fused_fn(state: TrainState, payload):
        with jax.named_scope("unpack"):
            batch = io.unpack_single(payload)
        return step_fn(state, batch)

    step = jax.jit(
        fused_fn,
        in_shardings=(state_shardings, io.sharding),
        out_shardings=(state_shardings, mesh_lib.replicated(mesh)),
        donate_argnums=(0,),
    )
    return step, state_shardings, io


def jit_cache_size(jitted) -> int:
    """Compiled-executable count of a jitted callable — XLA's own ground
    truth for 'how many programs has this step become', which the
    recompile sentinel (obs/compute.py) cross-checks its aval-hash count
    against in tests. Owned here next to the jits it describes. Returns
    -1 when this jax doesn't expose the private probe (the sentinel then
    stands alone — degraded, not broken)."""
    try:
        return int(jitted._cache_size())
    except Exception:
        return -1


def _batch_template(cfg: LearnerConfig):
    """A TrainBatch-shaped pytree for sharding derivation. With replay
    enabled the batch carries the [B] behavior_staleness stamp, so the
    template (and every sharding/jit treedef derived from it) must too."""
    from dotaclient_tpu.ops.batch import zeros_train_batch

    return zeros_train_batch(
        cfg.batch_size,
        cfg.seq_len,
        cfg.policy.lstm_hidden,
        cfg.policy.aux_heads,
        with_staleness=cfg.replay.enabled,
    )


def make_train_batch(cfg: LearnerConfig, rng_seed: int = 0) -> TrainBatch:
    """Random but self-consistent batch (tests / benchmarks / dry runs)."""
    import numpy as np

    from dotaclient_tpu.env import featurizer as F
    from dotaclient_tpu.ops.action_dist import Action
    from dotaclient_tpu.ops.batch import AuxTargets

    r = np.random.RandomState(rng_seed)
    B, T = cfg.batch_size, cfg.seq_len
    U = F.MAX_UNITS
    unit_mask = r.rand(B, T + 1, U) < 0.6
    target_mask = unit_mask & (r.rand(B, T + 1, U) < 0.5)
    action_mask = np.ones((B, T + 1, F.N_ACTION_TYPES), bool)
    action_mask[..., F.ACT_ATTACK] = target_mask.any(-1)
    action_mask[..., F.ACT_CAST] = False
    obs = F.Observation(
        global_feats=r.randn(B, T + 1, F.GLOBAL_FEATURES).astype(np.float32),
        hero_feats=r.randn(B, T + 1, F.HERO_FEATURES).astype(np.float32),
        unit_feats=r.randn(B, T + 1, U, F.UNIT_FEATURES).astype(np.float32),
        unit_mask=unit_mask,
        target_mask=target_mask,
        action_mask=action_mask,
    )
    lengths = r.randint(max(1, T // 2), T + 1, size=B)
    mask = (np.arange(T)[None, :] < lengths[:, None]).astype(np.float32)
    dones = np.zeros((B, T), np.float32)
    dones[r.rand(B) < 0.3, -1] = 1.0
    dones *= mask
    # Only legal actions, like a real actor: ATTACK only where a target
    # exists, and targets drawn from the valid slots.
    can_attack = target_mask[:, :T].any(-1)
    atype = r.randint(0, 2, size=(B, T)).astype(np.int32)
    atype = np.where(can_attack & (r.rand(B, T) < 0.33), F.ACT_ATTACK, atype).astype(np.int32)
    first_valid = np.argmax(target_mask[:, :T], axis=-1).astype(np.int32)
    target = np.where(can_attack, first_valid, 0).astype(np.int32)
    H = cfg.policy.lstm_hidden
    aux = (
        AuxTargets(
            win=np.sign(r.randn(B, T)).astype(np.float32),
            last_hit=r.rand(B, T).astype(np.float32),
            net_worth=r.rand(B, T).astype(np.float32),
        )
        if cfg.policy.aux_heads
        else None
    )
    return TrainBatch(
        obs=obs,
        actions=Action(
            type=atype,
            move_x=r.randint(0, cfg.policy.n_move_bins, (B, T)).astype(np.int32),
            move_y=r.randint(0, cfg.policy.n_move_bins, (B, T)).astype(np.int32),
            target=target,
        ),
        behavior_logp=(-1.5 + 0.1 * r.randn(B, T)).astype(np.float32),
        behavior_value=r.randn(B, T).astype(np.float32) * 0.1,
        rewards=r.randn(B, T).astype(np.float32) * 0.1 * mask,
        dones=dones,
        mask=mask,
        initial_state=(np.zeros((B, H), np.float32), np.zeros((B, H), np.float32)),
        aux=aux,
        # All-fresh stamp iff replay is on, so a random batch always
        # matches _batch_template's treedef for the same config.
        behavior_staleness=np.zeros((B,), np.float32) if cfg.replay.enabled else None,
    )
