"""Transformer policy family: KV-cache step vs causal unroll equivalence,
chunk-local semantics, SP (ring-attention) train-step parity on the mesh,
and actor-loop integration."""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from dotaclient_tpu.config import ActorConfig, LearnerConfig, PolicyConfig
from dotaclient_tpu.env import featurizer as F
from dotaclient_tpu.models import policy as P
from dotaclient_tpu.models.transformer_policy import KVCache
from dotaclient_tpu.parallel import mesh as mesh_lib
from dotaclient_tpu.parallel.train_step import (
    build_train_step,
    init_train_state,
    make_train_batch,
)

TF_SMALL = PolicyConfig(
    arch="transformer",
    unit_embed_dim=16,
    lstm_hidden=16,
    mlp_hidden=16,
    dtype="float32",
    tf_layers=2,
    tf_heads=2,
    tf_context=9,
)


def _obs(r, *lead):
    return F.Observation(
        global_feats=r.randn(*lead, F.GLOBAL_FEATURES).astype(np.float32),
        hero_feats=r.randn(*lead, F.HERO_FEATURES).astype(np.float32),
        unit_feats=r.randn(*lead, F.MAX_UNITS, F.UNIT_FEATURES).astype(np.float32),
        unit_mask=np.ones((*lead, F.MAX_UNITS), bool),
        target_mask=np.ones((*lead, F.MAX_UNITS), bool),
        action_mask=np.ones((*lead, F.N_ACTION_TYPES), bool),
    )


@pytest.fixture(scope="module")
def net_and_params():
    net = P.PolicyNet(TF_SMALL)
    params = P.init_params(TF_SMALL, jax.random.PRNGKey(0))
    return net, params


@pytest.mark.slow  # ~25s of transformer unroll/step compiles — the family
# ran ZERO tests in tier-1 before PR 3 (shard_map collection error), so the
# default gate owns these; tier-1 keeps the cheap state/reject/actor tests
class TestStepUnrollEquivalence:
    def test_kv_cache_step_matches_unroll(self, net_and_params):
        """T KV-cache steps must reproduce the teacher-forced unroll —
        the transformer analogue of the LSTM's step-vs-scan equivalence,
        and the property PPO's ratio correctness rests on."""
        net, params = net_and_params
        B, T = 2, 8
        obs_seq = jax.tree.map(jnp.asarray, _obs(np.random.RandomState(0), B, T))
        _, out_unroll = net.apply(params, P.initial_state(TF_SMALL, (B,)), obs_seq, unroll=True)

        state = P.initial_state(TF_SMALL, (B,))
        step = jax.jit(net.apply)  # one compile, T fast calls
        vals, tlogp, mlogp = [], [], []
        for t in range(T):
            obs_t = jax.tree.map(lambda x: x[:, t], obs_seq)
            state, out = step(params, state, obs_t)
            vals.append(out.value)
            tlogp.append(out.dist.type_logp)
            mlogp.append(out.dist.move_x_logp)
        np.testing.assert_allclose(jnp.stack(vals, 1), out_unroll.value, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            jnp.stack(tlogp, 1), out_unroll.dist.type_logp, rtol=1e-5, atol=1e-5
        )
        np.testing.assert_allclose(
            jnp.stack(mlogp, 1), out_unroll.dist.move_x_logp, rtol=1e-5, atol=1e-5
        )

    def test_unroll_ignores_initial_state(self, net_and_params):
        """Context is chunk-local: the learner's unroll must not read the
        wire-format (c, h) pair the LSTM family ships."""
        net, params = net_and_params
        B, T = 2, 4
        obs_seq = jax.tree.map(jnp.asarray, _obs(np.random.RandomState(1), B, T))
        zeros = (jnp.zeros((B, 16)), jnp.zeros((B, 16)))
        garbage = (jnp.full((B, 16), 1e6), jnp.full((B, 16), -1e6))
        _, out_a = net.apply(params, zeros, obs_seq, unroll=True)
        _, out_b = net.apply(params, garbage, obs_seq, unroll=True)
        np.testing.assert_array_equal(out_a.value, out_b.value)

    def test_unroll_is_causal(self, net_and_params):
        net, params = net_and_params
        B, T = 1, 6
        obs_seq = jax.tree.map(jnp.asarray, _obs(np.random.RandomState(2), B, T))
        _, base = net.apply(params, P.initial_state(TF_SMALL, (B,)), obs_seq, unroll=True)
        pert = obs_seq._replace(
            hero_feats=obs_seq.hero_feats.at[:, -1].add(100.0)
        )
        _, out = net.apply(params, P.initial_state(TF_SMALL, (B,)), pert, unroll=True)
        np.testing.assert_allclose(base.value[:, :-1], out.value[:, :-1], rtol=1e-6)
        assert not np.allclose(base.value[:, -1], out.value[:, -1])

    def test_one_param_set_serves_both_modes(self, net_and_params):
        """init_params builds via the step path; the unroll must find the
        identical layer set (no mode-only params)."""
        net, params = net_and_params
        B, T = 1, 3
        obs_seq = jax.tree.map(jnp.asarray, _obs(np.random.RandomState(3), B, T))
        # Would raise on missing/extra params if the modes diverged.
        net.apply(params, P.initial_state(TF_SMALL, (B,)), obs_seq, unroll=True)
        obs_t = jax.tree.map(lambda x: x[:, 0], obs_seq)
        net.apply(params, P.initial_state(TF_SMALL, (B,)), obs_t)


class TestStateHelpers:
    def test_initial_state_is_kv_cache(self):
        st = P.initial_state(TF_SMALL, (3,))
        assert isinstance(st, KVCache)
        assert st.k.shape[0] == 3  # batch-leading, like the LSTM (c, h)
        assert int(st.idx.sum()) == 0

    def test_wire_state_zeros(self):
        st = P.initial_state(TF_SMALL, (2,))
        c, h = P.wire_state(TF_SMALL, st)
        assert c.shape == (2, TF_SMALL.lstm_hidden) and not c.any()

    def test_reset_between_chunks_resets_cache(self, net_and_params):
        net, params = net_and_params
        state = P.initial_state(TF_SMALL, (1,))
        obs_t = jax.tree.map(lambda x: jnp.asarray(x)[:, 0], _obs(np.random.RandomState(4), 1, 1))
        state, _ = net.apply(params, state, obs_t)
        assert int(state.idx[0]) == 1
        state = P.reset_between_chunks(TF_SMALL, state)
        assert int(state.idx[0]) == 0 and not np.asarray(state.k).any()

    def test_lstm_family_unaffected(self):
        lstm_cfg = PolicyConfig(dtype="float32")
        st = P.initial_state(lstm_cfg, (2,))
        assert P.reset_between_chunks(lstm_cfg, st) is st
        assert P.wire_state(lstm_cfg, st) is st

    def test_cache_wraps_to_sliding_window(self, net_and_params):
        """Stepping past tf_context must overwrite the oldest slot (ring
        buffer → sliding window), never silently drop the write."""
        net, params = net_and_params
        C = TF_SMALL.tf_context
        state = P.initial_state(TF_SMALL, (1,))
        r = np.random.RandomState(5)
        step = jax.jit(net.apply)
        for t in range(C + 3):
            obs_t = jax.tree.map(lambda x: jnp.asarray(x)[:, 0], _obs(r, 1, 1))
            state, _ = step(params, state, obs_t)
        pos = np.sort(np.asarray(state.pos[0]))
        # the cache holds exactly the last C absolute positions
        np.testing.assert_array_equal(pos, np.arange(3, C + 3))
        assert int(state.idx[0]) == C + 3


def _tf_learner_cfg(mesh_shape, sp_axis, seq_len=7, batch_size=8):
    return LearnerConfig(
        batch_size=batch_size,
        seq_len=seq_len,
        mesh_shape=mesh_shape,
        policy=PolicyConfig(
            arch="transformer",
            unit_embed_dim=16,
            lstm_hidden=16,
            mlp_hidden=16,
            dtype="float32",
            tf_layers=2,
            tf_heads=2,
            tf_context=8,
            tf_sp_axis=sp_axis,
        ),
    )


def _run_one_step(cfg, seed=0):
    mesh = mesh_lib.make_mesh(cfg.mesh_shape)
    ts, state_sh, _ = build_train_step(cfg, mesh)
    st = jax.device_put(init_train_state(cfg, jax.random.PRNGKey(0)), state_sh)
    batch = jax.tree.map(np.asarray, make_train_batch(cfg, seed))
    _, metrics = ts(st, batch)
    jax.block_until_ready(metrics["loss"])
    return {k: float(v) for k, v in jax.device_get(metrics).items()}


class TestSequenceParallelTrainStep:
    @pytest.mark.slow  # two full train-step compiles — default gate only
    def test_sp_matches_dp_only(self):
        """dp=2×sp=4 (ring attention, time-sharded obs) must produce the
        same loss/grad-norm as dp=8 with local attention."""
        m_sp = _run_one_step(_tf_learner_cfg("dp=2,sp=4", "sp"))
        m_dp = _run_one_step(_tf_learner_cfg("dp=8", ""))
        for k in m_dp:
            assert m_sp[k] == pytest.approx(m_dp[k], rel=1e-4, abs=1e-5), k

    def test_sp_rejects_indivisible_frames(self):
        cfg = _tf_learner_cfg("dp=2,sp=4", "sp", seq_len=8)  # 9 frames % 4 != 0
        with pytest.raises(ValueError, match="seq_len"):
            build_train_step(cfg, mesh_lib.make_mesh(cfg.mesh_shape))

    @pytest.mark.slow  # sp train-step compile + 20 stepped iterations
    def test_transformer_trains_on_fixed_batch(self):
        """20 repeated steps on one batch: the loss must fall — the
        family is actually optimizable, not just shape-correct."""
        cfg = _tf_learner_cfg("dp=2,sp=4", "sp")
        mesh = mesh_lib.make_mesh(cfg.mesh_shape)
        ts, state_sh, _ = build_train_step(cfg, mesh)
        st = jax.device_put(init_train_state(cfg, jax.random.PRNGKey(0)), state_sh)
        batch = jax.tree.map(np.asarray, make_train_batch(cfg, 0))
        first = last = None
        for i in range(20):
            st, metrics = ts(st, batch)
            loss = float(jax.device_get(metrics["policy_loss"]))
            first = loss if first is None else first
            last = loss
        assert last < first


class TestActorIntegration:
    def test_actor_episode_with_transformer_policy(self):
        """The real actor loop runs the transformer family against the
        fake env: valid rollouts, zero wire states, cache resets at chunk
        boundaries (idx never exceeds rollout frames)."""
        from dotaclient_tpu.env.fake_dotaservice import FakeDotaService
        from dotaclient_tpu.env.service import serve
        from dotaclient_tpu.runtime.actor import Actor
        from dotaclient_tpu.transport import memory as mem
        from dotaclient_tpu.transport.base import connect as broker_connect
        from dotaclient_tpu.transport.serialize import deserialize_rollout

        server, port = serve(FakeDotaService())
        try:
            mem.reset("tf_actor")
            cfg = ActorConfig(
                env_addr=f"127.0.0.1:{port}",
                rollout_len=8,
                max_dota_time=30.0,
                policy=PolicyConfig(
                    arch="transformer",
                    unit_embed_dim=16,
                    lstm_hidden=16,
                    mlp_hidden=16,
                    dtype="float32",
                    tf_layers=1,
                    tf_heads=2,
                    tf_context=9,  # rollout_len + bootstrap frame
                ),
                seed=1,
            )
            broker = broker_connect("mem://tf_actor")
            actor = Actor(cfg, broker_connect("mem://tf_actor"), actor_id=7)
            asyncio.new_event_loop().run_until_complete(actor.run_episode())
            assert actor.rollouts_published >= 1
            frames = broker.consume_experience(1000, timeout=0.2)
            assert len(frames) == actor.rollouts_published
            for f in frames:
                r = deserialize_rollout(f)
                assert 1 <= r.length <= cfg.rollout_len
                assert not r.initial_state[0].any()  # transformer wire state is zeros
        finally:
            server.stop(0)


class TestRemat:
    @pytest.mark.slow  # two train-step compiles — default gate only
    def test_remat_identical_loss_and_grads(self):
        """tf_remat must change memory behavior only: loss and gradients
        bit-compare against the stored-activation path."""
        cfg_a = _tf_learner_cfg("dp=8", "")
        cfg_b = _tf_learner_cfg("dp=8", "")
        cfg_b.policy.tf_remat = True
        m_a = _run_one_step(cfg_a)
        m_b = _run_one_step(cfg_b)
        for k in m_a:
            assert m_b[k] == pytest.approx(m_a[k], rel=1e-6, abs=1e-8), k

    @pytest.mark.nightly  # remat bit-parity is in the default gate; this
    # is the remat x sp composition (second big compile)
    @pytest.mark.slow  # nightly-heavy must ALSO be slow (tier-1 -m override)
    def test_remat_composes_with_sequence_parallelism(self):
        cfg = _tf_learner_cfg("dp=2,sp=4", "sp")
        cfg.policy.tf_remat = True
        m = _run_one_step(cfg)
        ref = _run_one_step(_tf_learner_cfg("dp=8", ""))
        for k in ref:
            assert m[k] == pytest.approx(ref[k], rel=1e-4, abs=1e-5), k


class TestUlyssesTrainStep:
    @pytest.mark.nightly  # ring train-step parity guards the default gate;
    # ulysses parity at op level is default too — this is the composition
    @pytest.mark.slow  # nightly-heavy must ALSO be slow (tier-1 -m override)
    def test_ulysses_sp_matches_dp_only(self):
        """Full PPO step with all-to-all sequence parallelism == local
        attention (same batch, same init)."""
        cfg = _tf_learner_cfg("dp=2,sp=4", "sp")
        cfg.policy.tf_sp_mode = "ulysses"  # tf_heads=2... need divisible by 4
        cfg.policy.tf_heads = 4
        cfg.policy.tf_context = 8
        m_uly = _run_one_step(cfg)
        ref = _tf_learner_cfg("dp=8", "")
        ref.policy.tf_heads = 4
        m_ref = _run_one_step(ref)
        for k in m_ref:
            assert m_uly[k] == pytest.approx(m_ref[k], rel=1e-4, abs=1e-5), k


def test_ulysses_misconfig_rejected_at_build_time():
    cfg = _tf_learner_cfg("dp=2,sp=4", "sp")
    cfg.policy.tf_sp_mode = "ulysses"  # tf_heads=2 % 4 != 0
    with pytest.raises(ValueError, match="tf_heads"):
        build_train_step(cfg, mesh_lib.make_mesh(cfg.mesh_shape))
    cfg.policy.tf_sp_mode = "bogus"
    with pytest.raises(ValueError, match="tf_sp_mode"):
        build_train_step(cfg, mesh_lib.make_mesh(cfg.mesh_shape))


@pytest.mark.slow  # two train-step compiles — default gate only
def test_blockwise_local_attention_train_step_parity():
    """tf_attn_block changes memory shape only: same metrics as dense."""
    cfg_blk = _tf_learner_cfg("dp=8", "")
    cfg_blk.policy.tf_attn_block = 4  # 8 frames -> 2 key blocks
    m_blk = _run_one_step(cfg_blk)
    m_dense = _run_one_step(_tf_learner_cfg("dp=8", ""))
    for k in m_dense:
        assert m_blk[k] == pytest.approx(m_dense[k], rel=1e-5, abs=1e-7), k


# A published block's shape at a toy's widths: grouped key/value heads of a
# width of their own, three sliding layers to one full with YaRN, RMSNorm,
# no biases, a final norm, a routed-expert layer holding a share.
TF_MOE = PolicyConfig(
    arch="transformer", unit_embed_dim=16, lstm_hidden=32, mlp_hidden=16, dtype="float32",
    tf_layers=4, tf_heads=4, tf_kv_heads=2, tf_head_dim=8, tf_context=16,
    tf_layer_kinds="sliding,sliding,sliding,full", tf_window=5, tf_rope_theta=500000.0,
    tf_yarn_factor=16.0, tf_yarn_original_context=8, tf_norm="rmsnorm", tf_bias=False,
    tf_final_norm=True, moe_standardize_router=True, moe_experts=8, moe_experts_held=4,
    moe_first_expert=2, moe_top_k=3,
    moe_hidden=12, tf_attn_block=4,
)


def _handed_to_attention(monkeypatch) -> dict:
    """The q, k and v of the last call of the blocks' `RA.attend`, which
    runs as it would."""
    from dotaclient_tpu.models import transformer_policy as TP

    handed, attend = {}, TP.RA.attend
    monkeypatch.setattr(TP.RA, "attend", lambda q, k, v, *a, **kw: handed.update(q=q, k=k, v=v) or attend(q, k, v, *a, **kw))
    return handed


class TestPublishedBlockShape:
    @pytest.fixture(scope="class")
    def net_and_params(self):
        return P.PolicyNet(TF_MOE), P.init_params(TF_MOE, jax.random.PRNGKey(0))

    def test_the_parameter_tree(self, net_and_params):
        tf = net_and_params[1]["params"]["core"]["tf"]
        assert set(tf) == {"block0", "block1", "block2", "block3", "ln_f"}
        shapes = jax.tree.map(lambda x: x.shape, tf["block0"])
        assert shapes == {
            "ln1": {"scale": (32,)}, "qkv": {"kernel": (32, (4 + 2 * 2) * 8)},
            "attn_out": {"kernel": (32, 32)}, "ln2": {"scale": (32,)},
            "moe": {"router": (32, 8), "w_gate": (32, 4, 12), "w_up": (32, 4, 12),
                    "w_down": (12, 4, 32)}}
        # seeded expert matrices carry the scale of their fan-in, the first axis
        assert float(jnp.std(tf["block0"]["moe"]["w_gate"])) == pytest.approx(32 ** -0.5, rel=0.2)
        assert not np.asarray(tf["ln_f"]["scale"]).any()  # g - 1

    def test_step_mode_equals_unroll(self, net_and_params):
        """Through the one KVCache, [B, L, C, key/value heads, head width]:
        a sliding layer masks by position, the rotary table is its kind's."""
        net, params = net_and_params
        B, T = 2, 14
        obs = _obs(np.random.RandomState(3), B, T)
        _, unrolled = net.apply(params, P.initial_state(TF_MOE, (B,)), obs, unroll=True)
        state = P.initial_state(TF_MOE, (B,))
        assert state.k.shape == (B, 4, 16, 2, 8)
        values, logps = [], []
        for t in range(T):
            state, out = net.apply(params, state, jax.tree.map(lambda x: x[:, t], obs))
            values.append(out.value)
            logps.append(out.dist.type_logp)
            assert out.stats is None  # the counters are the learner's
        np.testing.assert_allclose(np.stack(values, 1), unrolled.value, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.stack(logps, 1), unrolled.dist.type_logp, rtol=1e-4, atol=1e-5)
        assert unrolled.stats["moe_local_pairs"] <= B * T * 3 * 4
        assert unrolled.stats["moe_load_max_over_mean"] >= 1.0

    def test_blocked_remat_and_dense_unrolls_agree(self, net_and_params):
        import dataclasses

        net, params = net_and_params
        obs = _obs(np.random.RandomState(4), 2, 14)
        state = P.initial_state(TF_MOE, (2,))
        _, want = net.apply(params, state, obs, unroll=True)
        for change in (dict(tf_attn_block=0), dict(tf_remat=True), dict(tf_attn_block=8)):
            _, got = P.PolicyNet(dataclasses.replace(TF_MOE, **change)).apply(
                params, state, obs, unroll=True)
            np.testing.assert_allclose(got.value, want.value, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("bias", [False, True])
    def test_the_unroll_hands_on_the_heads_of_one_product_and_a_split(self, monkeypatch, bias):
        """q, k and v come each from a product with its columns of the one
        `qkv` matrix, written by head: the numbers of one product split in
        three and rotated by the half-split rule, the form kept here."""
        from dotaclient_tpu.models import transformer_policy as TP
        from tests.test_attention import split_rope

        cfg = dataclasses.replace(TF_MOE, tf_bias=bias)
        block = TP.Block(cfg, "full")
        r = np.random.RandomState(9)
        x = jnp.asarray(r.randn(2, 14, 32), jnp.float32)
        positions = jnp.broadcast_to(jnp.arange(14, dtype=jnp.int32), (2, 14))
        params = block.init(jax.random.PRNGKey(1), x, positions)["params"]
        if bias:
            params["qkv"]["bias"] = jnp.asarray(r.randn(64), jnp.float32)
        assert jax.tree.map(jnp.shape, params["qkv"]) == {"kernel": (32, 64), **({"bias": (64,)} if bias else {})}
        handed = _handed_to_attention(monkeypatch)
        block.apply({"params": params}, x, positions)
        g = 1.0 + params["ln1"]["scale"]
        h = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + cfg.tf_norm_eps) * g
        qkv = h @ params["qkv"]["kernel"] + (params["qkv"]["bias"] if bias else 0.0)
        q, k, v = jnp.split(qkv, [4 * 8, 6 * 8], axis=-1)
        table = TP.A.rope_table(8, 500000.0, 16.0, 8, 32.0, 1.0)
        np.testing.assert_allclose(handed["q"], split_rope(q.reshape(2, 14, 4, 8), positions, table=table), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(handed["k"], split_rope(k.reshape(2, 14, 2, 8), positions, table=table), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(handed["v"], v.reshape(2, 14, 2, 8), rtol=1e-5, atol=1e-6)

    def test_a_sliding_layer_forgets_what_left_its_window(self, net_and_params):
        """With every layer sliding, frame t does not see frame t - window;
        with the published period's full layer it does."""
        import dataclasses

        obs = _obs(np.random.RandomState(5), 1, 12)
        moved = jax.tree.map(np.copy, obs)
        moved.hero_feats[:, 0] += 1.0
        for kinds, sees in (("sliding", False), ("sliding,sliding,sliding,full", True)):
            cfg = dataclasses.replace(TF_MOE, tf_layers=1 if not sees else 4, tf_layer_kinds=kinds,
                                      moe_experts=0)
            net, params = P.PolicyNet(cfg), P.init_params(cfg, jax.random.PRNGKey(1))
            state = P.initial_state(cfg, (1,))
            a = net.apply(params, state, obs, unroll=True)[1].value
            b = net.apply(params, state, moved, unroll=True)[1].value
            far = np.abs(np.asarray(a - b))[0, 5:]  # frames 5.. are more than 4 behind frame 0
            assert (far > 0).any() == sees and np.abs(np.asarray(a - b))[0, 0] > 0

    def test_bad_shapes_are_refused(self):
        import dataclasses

        for change, match in ((dict(tf_layer_kinds="sliding,local"), "unknown kind"),
                              (dict(tf_window=0), "tf_window"),
                              (dict(tf_kv_heads=3), "tf_kv_heads"),
                              (dict(moe_first_expert=6), "moe"),
                              (dict(tf_norm="batchnorm"), "tf_norm")):
            cfg = dataclasses.replace(TF_MOE, **change)
            with pytest.raises(ValueError, match=match):
                P.init_params(cfg, jax.random.PRNGKey(0))

    def test_the_step_reports_the_routing_counters(self):
        cfg = LearnerConfig(batch_size=4, seq_len=7, policy=TF_MOE, mesh_shape="dp=1")
        mesh = mesh_lib.make_mesh("dp=1", devices=jax.devices()[:1])
        step, state_sh, batch_sh = build_train_step(cfg, mesh)
        state = jax.device_put(init_train_state(cfg, jax.random.PRNGKey(0)), state_sh)
        _, metrics = step(state, jax.device_put(make_train_batch(cfg, 0), batch_sh))
        frames = 4 * 8
        assert 0 < float(metrics["moe_local_pairs"]) <= frames * 3 * 4
        assert float(metrics["moe_load_max_over_mean"]) >= 1.0
        assert np.isfinite(float(metrics["loss"]))


# The published block's shape over enough frames for the expert layer's buffer
# to be smaller than frames x top_k: 2 rows of 256 frames, 2 choices of 8 experts,
# 2 held: 1,024 pairs in a buffer of 512 rows (ops/moe.py buffer_rows).
TF_BUFFERED = dataclasses.replace(
    TF_MOE, tf_layers=2, tf_layer_kinds="sliding,full", tf_window=100, tf_attn_block=64,
    moe_experts_held=2, moe_first_expert=0, moe_top_k=2, tf_remat=True,
)


class TestExpertBufferInTheUnroll:
    B, T = 2, 256

    def _value_and_grad(self, obs):
        net = P.PolicyNet(TF_BUFFERED)
        state = P.initial_state(TF_BUFFERED, (self.B,))

        def loss(params):
            _, out = net.apply(params, state, obs, unroll=True)
            return jnp.sum(out.value ** 2) + jnp.sum(out.dist.type_logp[..., 0]), out.stats

        return jax.jit(jax.value_and_grad(loss, has_aux=True))

    def test_one_pass_a_layer_under_even_routing_and_more_under_skew(self, monkeypatch):
        """`moe_passes` in the unroll's counters: the layer count where each
        layer's held pairs fit its buffer; a layer whose router sends every
        pair to the held experts takes a second pass, drops nothing, and
        value and gradients are those of the buffer of every pair."""
        from dotaclient_tpu.ops import moe

        assert moe.buffer_rows(self.B * self.T * 2, 2, 8) == 512
        params = P.init_params(TF_BUFFERED, jax.random.PRNGKey(0))
        obs = _obs(np.random.RandomState(7), self.B, self.T)
        (_, stats), _ = self._value_and_grad(obs)(params)
        assert float(stats["moe_passes"]) == 2.0 and float(stats["moe_local_pairs"]) < 2 * 512

        # A router of zeros scores every expert alike, and the first two win
        # every frame: all 1,024 pairs of block0 are held here.
        skewed = jax.tree.map(lambda x: x, params)
        moe0 = skewed["params"]["core"]["tf"]["block0"]["moe"]
        moe0["router"] = jnp.zeros_like(moe0["router"])
        (got, stats), g_got = self._value_and_grad(obs)(skewed)
        assert float(stats["moe_passes"]) == 3.0 and float(stats["moe_local_pairs"]) > 1024

        monkeypatch.setattr(moe, "HEADROOM", 8.0)  # the buffer of every pair: one pass, no loop
        (want, want_stats), g_want = self._value_and_grad(obs)(skewed)
        assert float(want_stats["moe_passes"]) == 2.0
        assert float(want_stats["moe_local_pairs"]) == float(stats["moe_local_pairs"])
        np.testing.assert_allclose(got, want, rtol=1e-5)
        for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(b)) + 1e-6))


# The published block's shape with heads the fused kernel takes (a width of
# 128): on a TPU this unroll goes through the kernel, and on the CPU only the
# platform keeps it on the plain blocks.
TF_FUSABLE = PolicyConfig(
    arch="transformer", unit_embed_dim=16, lstm_hidden=32, mlp_hidden=16, dtype="float32",
    tf_layers=2, tf_heads=2, tf_kv_heads=1, tf_head_dim=128, tf_context=256,
    tf_layer_kinds="sliding,full", tf_window=100, tf_rope_theta=500000.0,
    tf_yarn_factor=16.0, tf_yarn_original_context=128, tf_norm="rmsnorm", tf_bias=False,
    tf_final_norm=True, moe_standardize_router=True, moe_experts=4, moe_experts_held=2,
    moe_top_k=2, moe_hidden=12, tf_attn_block=64, tf_remat=True,
)


class TestFusedAttentionInTheUnroll:
    B, T = 1, 256

    def _value_and_grad(self, params, obs):
        net = P.PolicyNet(TF_FUSABLE)
        state = P.initial_state(TF_FUSABLE, (self.B,))

        def loss(params):
            _, out = net.apply(params, state, obs, unroll=True)
            return jnp.sum(out.value ** 2) + jnp.sum(out.dist.type_logp[..., 0]), out.stats

        return jax.value_and_grad(loss, has_aux=True), loss

    def test_the_cpu_stays_on_the_plain_blocks(self):
        """A CPU trace of the cell's kind of policy: no layer takes the
        kernel, and there is no Pallas call in the gradient's program."""
        from tests.test_attention import pallas_kernels

        params = P.init_params(TF_FUSABLE, jax.random.PRNGKey(0))
        obs = _obs(np.random.RandomState(6), self.B, self.T)
        vg, _ = self._value_and_grad(params, obs)
        assert pallas_kernels(jax.make_jaxpr(vg)(params).jaxpr) == []
        (_, stats), _ = vg(params)
        assert float(stats["attn_fused_layers"]) == 0.0 and "moe_local_pairs" in stats

    def test_with_the_kernel_the_unroll_computes_the_same(self, monkeypatch):
        """The rule answered yes (as on a TPU) and the kernel run in Pallas'
        interpreter: value, counters and gradients as on the plain blocks,
        every layer counted, and under tf_remat one forward kernel a layer
        in the gradient's program."""
        from dotaclient_tpu.ops import attention as A
        from dotaclient_tpu.ops import ring_attention as RA
        from tests.test_attention import pallas_kernels

        params = P.init_params(TF_FUSABLE, jax.random.PRNGKey(0))
        obs = _obs(np.random.RandomState(6), self.B, self.T)
        vg, _ = self._value_and_grad(params, obs)
        (want, want_stats), g_want = vg(params)

        kernel = A.fused_causal_attention
        monkeypatch.setattr(RA, "fused_applies", lambda *a, **k: True)
        monkeypatch.setattr(A, "fused_causal_attention", lambda q, k, v, window=0, q_scaled=False: kernel(
            q, k, v, window, interpret=True, q_scaled=q_scaled, tiles=(128, 128)))
        vg, _ = self._value_and_grad(params, obs)
        names = pallas_kernels(jax.make_jaxpr(vg)(params).jaxpr)
        assert sum("fwd" in n for n in names) == 2 and sum("dkv" in n for n in names) == 2, names
        (got, stats), g_got = vg(params)
        assert float(stats["attn_fused_layers"]) == 2.0
        assert float(stats["moe_local_pairs"]) == float(want_stats["moe_local_pairs"])
        np.testing.assert_allclose(got, want, rtol=1e-4)
        flat_want, flat_got = jax.tree.leaves(g_want), jax.tree.leaves(g_got)
        assert len(flat_want) == len(flat_got)
        for a, b in zip(flat_got, flat_want):
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-4 * float(jnp.max(jnp.abs(b)) + 1e-6))


# The second published block's shape: latent attention (a value head of another
# width than the keys' here, which the published one has not), one dense SwiGLU
# layer before the sparse ones, a sigmoid router with its bias, a shared expert.
TF_LATENT = PolicyConfig(
    arch="transformer", unit_embed_dim=16, lstm_hidden=32, mlp_hidden=16, dtype="float32",
    tf_layers=3, tf_heads=4, tf_context=16, tf_layer_kinds="latent", tf_rope_theta=1000000.0,
    tf_q_lora_rank=12, tf_kv_lora_rank=10, tf_qk_nope_dim=6, tf_qk_rope_dim=4, tf_v_head_dim=8,
    tf_norm="rmsnorm", tf_norm_eps=1e-5, tf_bias=False, tf_final_norm=True,
    tf_mlp_act="swiglu", tf_mlp_hidden=40, tf_dense_layers=1,
    moe_experts=8, moe_experts_held=4, moe_first_expert=2, moe_top_k=3, moe_hidden=12,
    moe_shared_hidden=12, moe_score="sigmoid", moe_route_scale=1.8, moe_standardize_router=True,
    tf_attn_block=4,
)


def _written_latent_block(p, x, cfg):
    """Layer 0 of the published block as its equations read, in float64:
    x [T, D] -> x' = a + FF_0(n(a)), a = x + Attn(n(x))."""
    N, eps = cfg.tf_heads, cfg.tf_norm_eps
    nope, rope, v_dim, kv_rank = cfg.tf_qk_nope_dim, cfg.tf_qk_rope_dim, cfg.tf_v_head_dim, cfg.tf_kv_lora_rank
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    n = lambda a, g: a / np.sqrt((a * a).mean(-1, keepdims=True) + eps) * (1.0 + g["scale"])
    T = x.shape[0]

    def rot(a):  # [T, heads, rope], pairs (i, i + rope / 2), frame t at position t
        inv = cfg.tf_rope_theta ** (-np.arange(rope // 2) / (rope // 2))
        ang = np.arange(T)[:, None, None] * inv
        a1, a2 = a[..., : rope // 2], a[..., rope // 2:]
        return np.concatenate([a1 * np.cos(ang) - a2 * np.sin(ang), a1 * np.sin(ang) + a2 * np.cos(ang)], -1)

    h = n(x, p["ln1"])
    c_q = n(h @ p["q_a"]["kernel"], p["q_norm"])
    q = (c_q @ p["q_b"]["kernel"]).reshape(T, N, nope + rope)
    ckr = h @ p["kv_a"]["kernel"]
    c, k_r = n(ckr[:, :kv_rank], p["kv_norm"]), ckr[:, kv_rank:]
    kv = (c @ p["kv_b"]["kernel"]).reshape(T, N, nope + v_dim)
    q = np.concatenate([q[..., :nope], rot(q[..., nope:])], -1)
    k = np.concatenate([kv[..., :nope], np.broadcast_to(rot(k_r[:, None, :]), (T, N, rope))], -1)
    s = np.einsum("qnd,knd->nqk", q, k) / np.sqrt(nope + rope)
    s = np.where(np.tril(np.ones((T, T), bool))[None], s, -np.inf)
    prob = np.exp(s - s.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    o = np.einsum("nqk,knd->qnd", prob, kv[..., nope:]).reshape(T, N * v_dim)
    a = x + o @ p["attn_out"]["kernel"]
    h2 = n(a, p["ln2"])
    silu = lambda z: z / (1.0 + np.exp(-z))
    return a + (silu(h2 @ p["mlp_gate"]["kernel"]) * (h2 @ p["mlp_up"]["kernel"])) @ p["mlp_down"]["kernel"]


def _split_latent_block(p, x, positions, cfg, handed=None):
    """Layer 0 of the latent block as the unroll built it until PR 37, in
    the compute type, kept as the plain reference of the construction
    that took its place: q split at its unrotated width and concatenated
    again, kv_b's output split into every head's keys and values, the one
    rotary key broadcast and concatenated behind each head's keys."""
    from dotaclient_tpu.ops import attention as A
    from tests.test_attention import split_rope

    N, eps = cfg.tf_heads, cfg.tf_norm_eps
    nope, rope, v_dim, kv_rank = cfg.tf_qk_nope_dim, cfg.tf_qk_rope_dim, cfg.tf_v_head_dim, cfg.tf_kv_lora_rank
    n = lambda a, g: a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True) + eps) * (1.0 + g["scale"])
    table = A.rope_table(rope, cfg.tf_rope_theta)
    h = n(x, p["ln1"])
    c_q = n(h @ p["q_a"]["kernel"], p["q_norm"])
    q = (c_q @ p["q_b"]["kernel"]).reshape(x.shape[:-1] + (N, nope + rope))
    q_n, q_r = jnp.split(q, [nope], axis=-1)
    c, k_r = jnp.split(h @ p["kv_a"]["kernel"], [kv_rank], axis=-1)
    c = n(c, p["kv_norm"])
    k_r = split_rope(k_r[..., None, :], positions, table=table)
    q = jnp.concatenate([q_n, split_rope(q_r, positions, table=table)], axis=-1)
    kv = (c @ p["kv_b"]["kernel"]).reshape(c.shape[:-1] + (N, nope + v_dim))
    k_n, v = jnp.split(kv, [nope], axis=-1)
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r, k_n.shape[:-1] + (rope,))], axis=-1)
    if handed is not None:
        handed.update(q=q, k=k, v=v)
    attn = A.causal_attention(q, k, v, positions, positions)
    a = x + attn.reshape(attn.shape[:-2] + (N * v_dim,)) @ p["attn_out"]["kernel"]
    h2 = n(a, p["ln2"])
    return a + (jax.nn.silu(h2 @ p["mlp_gate"]["kernel"]) * (h2 @ p["mlp_up"]["kernel"])) @ p["mlp_down"]["kernel"]


def _latent_block0(net_and_params, seed):
    """(layer 0's parameters with norms that are not the identity's, x, positions)."""
    params = jax.tree.map(lambda a: a, net_and_params[1]["params"]["core"]["tf"]["block0"])
    r = np.random.RandomState(seed)
    for name in ("ln1", "q_norm", "kv_norm", "ln2"):
        params[name] = {"scale": jnp.asarray(0.3 * r.randn(*params[name]["scale"].shape), jnp.float32)}
    x = jnp.asarray(r.randn(2, 14, 32), jnp.float32)
    return params, x, jnp.broadcast_to(jnp.arange(14, dtype=jnp.int32), (2, 14))


class TestLatentBlockShape:
    @pytest.fixture(scope="class")
    def net_and_params(self):
        return P.PolicyNet(TF_LATENT), P.init_params(TF_LATENT, jax.random.PRNGKey(0))

    def test_the_parameter_tree(self, net_and_params):
        tf = net_and_params[1]["params"]["core"]["tf"]
        assert set(tf) == {"block0", "block1", "block2", "ln_f"}
        shapes = jax.tree.map(lambda x: x.shape, tf)
        attention = {
            "ln1": {"scale": (32,)}, "q_a": {"kernel": (32, 12)}, "q_norm": {"scale": (12,)},
            "q_b": {"kernel": (12, 4 * (6 + 4))}, "kv_a": {"kernel": (32, 10 + 4)},
            "kv_norm": {"scale": (10,)}, "kv_b": {"kernel": (10, 4 * (6 + 8))},
            "attn_out": {"kernel": (4 * 8, 32)}, "ln2": {"scale": (32,)}}
        assert shapes["block0"] == {**attention, "mlp_gate": {"kernel": (32, 40)},
                                    "mlp_up": {"kernel": (32, 40)}, "mlp_down": {"kernel": (40, 32)}}
        assert shapes["block1"] == shapes["block2"] == {
            **attention,
            "moe": {"router": (32, 8), "router_bias": (8,), "w_gate": (32, 4, 12), "w_up": (32, 4, 12),
                    "w_down": (12, 4, 32)},
            "shared_gate": {"kernel": (32, 12)}, "shared_up": {"kernel": (32, 12)},
            "shared_down": {"kernel": (12, 32)}}
        assert not np.asarray(tf["block1"]["moe"]["router_bias"]).any()  # zero from the seed
        assert float(jnp.std(tf["block0"]["kv_b"]["kernel"])) == pytest.approx(10 ** -0.5, rel=0.2)

    def test_latent_attention_against_its_written_equations(self, net_and_params):
        """The leading block (latent attention, then the dense SwiGLU) alone,
        unrolled, against the equations in float64; the plain blocks cut the
        14 frames into four query blocks."""
        from dotaclient_tpu.models.transformer_policy import Block

        params = jax.tree.map(lambda a: a, net_and_params[1]["params"]["core"]["tf"]["block0"])
        r = np.random.RandomState(8)
        for name in ("ln1", "q_norm", "kv_norm", "ln2"):  # norms that are not the identity's
            params[name] = {"scale": jnp.asarray(0.3 * r.randn(*params[name]["scale"].shape), jnp.float32)}
        x = r.randn(2, 14, 32).astype(np.float32)
        positions = jnp.broadcast_to(jnp.arange(14, dtype=jnp.int32), (2, 14))
        got, cache, counts = Block(TF_LATENT, "latent").apply({"params": params}, jnp.asarray(x), positions)
        assert cache is None and counts is None
        for b in range(2):
            np.testing.assert_allclose(got[b], _written_latent_block(params, x[b].astype(np.float64), TF_LATENT),
                                       rtol=2e-4, atol=2e-5)

    def test_the_unroll_hands_on_whole_heads_with_the_split_constructions_numbers(self, net_and_params, monkeypatch):
        """What reaches attention: q [B, T, N, 10], k [B, T, N, 10] and
        v [B, T, N, 8], whole heads, with the numbers of the split form;
        the constant rows carry the one rotary key into every head's last
        lanes exactly."""
        from dotaclient_tpu.models import transformer_policy as TP

        params, x, positions = _latent_block0(net_and_params, 8)
        handed, want = _handed_to_attention(monkeypatch), {}
        got = TP.Block(TF_LATENT, "latent").apply({"params": params}, x, positions)[0]
        np.testing.assert_allclose(got, _split_latent_block(params, x, positions, TF_LATENT, want), rtol=1e-5, atol=1e-6)
        assert [handed[n].shape for n in "qkv"] == [(2, 14, 4, 10), (2, 14, 4, 10), (2, 14, 4, 8)]
        for name in "qkv":
            np.testing.assert_allclose(handed[name], want[name], rtol=1e-5, atol=1e-6)
        rotary = np.asarray(handed["k"][..., 6:])
        np.testing.assert_array_equal(rotary, np.broadcast_to(rotary[:, :, :1], rotary.shape))  # one key, every head
        np.testing.assert_array_equal(rotary, np.asarray(want["k"][..., 6:]))

    @pytest.mark.parametrize("change", [dict(), dict(tf_remat=True)], ids=["plain", "remat"])
    def test_gradients_are_those_of_the_split_construction(self, net_and_params, change):
        """Every parameter's and the input's: `kv_b` stays one matrix with
        one gradient of its shape, whatever forms the unroll reads it in,
        and the rows of ones and zeros that extend its key half are
        constants of the program, no leaf of the tree and of no gradient."""
        from dotaclient_tpu.models.transformer_policy import Block

        cfg = dataclasses.replace(TF_LATENT, **change)
        params, x, positions = _latent_block0(net_and_params, 10)
        w = jnp.asarray(np.random.RandomState(11).randn(2, 14, 32), jnp.float32)
        block = nn.remat(Block) if cfg.tf_remat else Block
        got = jax.grad(lambda p, x: jnp.sum(block(cfg, "latent").apply({"params": p}, x, positions)[0] * w), (0, 1))(params, x)
        want = jax.grad(lambda p, x: jnp.sum(_split_latent_block(p, x, positions, cfg) * w), (0, 1))(params, x)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        assert got[0]["kv_b"]["kernel"].shape == params["kv_b"]["kernel"].shape == (10, 4 * (6 + 8))
        for (path, g), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(g, b, rtol=2e-4, atol=2e-5 * float(jnp.max(jnp.abs(b))), err_msg=str(path))

    def test_step_mode_equals_unroll_through_the_latent_cache(self, net_and_params):
        """The unroll attends in the expanded form (every head's keys and
        values out of the latent), the step in the absorbed form over a
        cache of the latent and the one rotated key: the same values."""
        net, params = net_and_params
        B, T = 2, 14
        obs = _obs(np.random.RandomState(3), B, T)
        _, unrolled = net.apply(params, P.initial_state(TF_LATENT, (B,)), obs, unroll=True)
        state = P.initial_state(TF_LATENT, (B,))
        assert state.k.shape == (B, 3, 16, 1, 4) and state.v.shape == (B, 3, 16, 1, 10)
        step = jax.jit(net.apply)
        values, logps = [], []
        for t in range(T):
            state, out = step(params, state, jax.tree.map(lambda x: x[:, t], obs))
            values.append(out.value)
            logps.append(out.dist.type_logp)
            assert out.stats is None
        np.testing.assert_allclose(np.stack(values, 1), unrolled.value, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.stack(logps, 1), unrolled.dist.type_logp, rtol=1e-4, atol=1e-5)
        assert np.asarray(state.k[:, :, :T]).any() and not np.asarray(state.k[:, :, T:]).any()
        # a dense layer has no routing to count: the two sparse layers' pairs and one pass each
        assert 0 < float(unrolled.stats["moe_local_pairs"]) <= B * T * 3 * 2
        assert float(unrolled.stats["moe_passes"]) == 2.0 and float(unrolled.stats["attn_fused_layers"]) == 0.0
        # chunk boundaries reset it like any cache
        again = P.reset_between_chunks(TF_LATENT, state)
        assert int(again.idx.sum()) == 0 and not np.asarray(again.v).any()

    def test_blocked_remat_and_dense_unrolls_agree(self, net_and_params):
        net, params = net_and_params
        obs = _obs(np.random.RandomState(4), 2, 14)
        state = P.initial_state(TF_LATENT, (2,))
        _, want = net.apply(params, state, obs, unroll=True)
        for change in (dict(tf_attn_block=0), dict(tf_remat=True), dict(tf_attn_block=8)):
            _, got = P.PolicyNet(dataclasses.replace(TF_LATENT, **change)).apply(params, state, obs, unroll=True)
            np.testing.assert_allclose(got.value, want.value, rtol=1e-5, atol=1e-6)

    def test_the_caches_bytes_a_frame(self):
        """At the published sizes the actor keeps the 512-wide latent and the
        64-wide rotary key of a frame and layer, 1,152 B in bfloat16, where
        the expanded keys and values of 20 heads would be 20,480 B."""
        from dotaclient_tpu.models.transformer_policy import init_cache

        cfg = dataclasses.replace(
            TF_LATENT, dtype="bfloat16", lstm_hidden=2048, tf_heads=20, tf_layers=5, tf_context=4096,
            tf_q_lora_rank=768, tf_kv_lora_rank=512, tf_qk_nope_dim=192, tf_qk_rope_dim=64, tf_v_head_dim=256)
        cache = jax.eval_shape(lambda: init_cache(cfg, (3,)))
        assert cache.k.shape == (3, 5, 4096, 1, 64) and cache.v.shape == (3, 5, 4096, 1, 512)
        per_frame_and_layer = (cache.k.size * cache.k.dtype.itemsize + cache.v.size * cache.v.dtype.itemsize) / (3 * 5 * 4096)
        assert per_frame_and_layer == 1152
        assert 20 * (192 + 64 + 256) * 2 == 20480

    def test_bad_shapes_are_refused(self):
        for change, match in ((dict(tf_layer_kinds="latent,full"), "no KVCache"),
                              (dict(tf_qk_rope_dim=3), "latent attention needs"),
                              (dict(tf_kv_lora_rank=0), "latent attention needs"),
                              (dict(moe_score="tanh"), "moe_score"),
                              (dict(tf_mlp_act="relu"), "tf_mlp_act")):
            with pytest.raises(ValueError, match=match):
                P.init_params(dataclasses.replace(TF_LATENT, **change), jax.random.PRNGKey(0))


@pytest.mark.parametrize("cell,count,leaves,digest", [
    ("learner-lstm4096-wire", 136_584_631, 23, "bb9966d3313eb21e"),
    ("learner-mellum2-ep4-wire", 483_239_607, 53, "873687eec0b03b99"),
    ("learner-glm47flash-ep8-wire", 513_183_671, 101, "a55da48890cbb943"),
    ("learner-qwen3next-ep32-wire", 347_736_567, 86, "f725b3687b64af7f"),  # as PR 38 made it
])
def test_the_cells_parameter_trees_are_what_they_were(cell, count, leaves, digest):
    """Names, shapes and types of every parameter of the three benchmark
    cells' policies, as commit 83325ac (PR 36) made them: the published
    weight frame and every checkpoint are laid out by this tree, so a
    projection that reads its matrix another way keeps the matrix."""
    import hashlib

    from benchmark import cells, harness

    cfg = harness.learner_config(cells.load_cell(cells.load_benchmark(), cell), seed=0, broker_url="mem://x").policy
    tree = jax.eval_shape(lambda: P.init_params(cfg, jax.random.PRNGKey(0)))
    lines = sorted(f"{jax.tree_util.keystr(k)}:{v.shape}:{v.dtype}" for k, v in jax.tree_util.tree_leaves_with_path(tree))
    assert sum(int(np.prod(v.shape)) for v in jax.tree.leaves(tree)) == count and len(lines) == leaves
    assert hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16] == digest, lines
