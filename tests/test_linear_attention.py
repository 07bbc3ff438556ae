"""The transformer family with linear (gated delta rule) and gated layers
side by side: the actor's step frame by frame against the learner's
unroll with both kinds of state in the carry, the carry's shapes and its
reset between chunks, and the experts' shares with the gated shared
expert counted once against the uncut reference layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dotaclient_tpu.config import PolicyConfig
from dotaclient_tpu.env import featurizer as F
from dotaclient_tpu.models import policy as P
from dotaclient_tpu.models import transformer_policy as TP
from dotaclient_tpu.ops import gated_delta as GD

CFG = PolicyConfig(
    arch="transformer", dtype="float32", lstm_hidden=32, unit_embed_dim=32, mlp_hidden=32, tf_layers=4,
    tf_layer_kinds="linear,linear,linear,gated", tf_heads=4, tf_kv_heads=2, tf_head_dim=8, tf_rotary_dim=4,
    tf_lin_key_heads=2, tf_lin_value_heads=4, tf_lin_head_dim=8, tf_context=12, tf_attn_block=4,
    tf_norm="rmsnorm", tf_bias=False, tf_final_norm=True, moe_experts=8, moe_experts_held=4, moe_first_expert=2,
    moe_top_k=3, moe_hidden=12, moe_shared_hidden=12, moe_shared_gate=True, moe_standardize_router=True)


def _obs(r, B, T):
    z = F.zeros_observation()
    make = lambda x: (jnp.asarray(r.randn(B, T, *np.shape(x)), jnp.float32) if np.asarray(x).dtype == np.float32
                      else jnp.ones((B, T) + np.shape(x), np.asarray(x).dtype))
    return jax.tree.map(make, z)


@pytest.fixture(scope="module")
def params():
    p = P.init_params(CFG, jax.random.PRNGKey(0))
    r = np.random.RandomState(1)
    for blk in p["params"]["core"]["tf"].values():  # not the zeros a seed gives them
        if "A_log" in blk:
            blk["A_log"], blk["dt_bias"] = (jnp.asarray(0.3 * r.randn(4), jnp.float32) for _ in range(2))
    return p


def test_the_carry_holds_each_kinds_state_for_its_layers_only():
    cache = TP.init_cache(CFG, (3,))
    assert cache.k.shape == cache.v.shape == (3, 1, 12, 2, 8)  # the one gated layer's keys and values
    assert cache.s.shape == (3, 3, 4, 8, 8) and cache.s.dtype == jnp.float32  # a matrix a value head, three layers
    assert cache.conv.shape == (3, 3, 3, (2 * 2 + 4) * 8)  # the three frames before, q's, k's and v's channels
    assert cache.rsum.shape == (3, 4, 2, 8)
    assert all(leaf.shape[0] == 3 for leaf in jax.tree.leaves(cache))  # batch-leading, every leaf
    plain = TP.init_cache(dataclasses.replace(CFG, tf_layer_kinds="full,gated"), (3,))
    assert plain.s is None and plain.conv is None and plain.k.shape == (3, 4, 12, 2, 8)
    assert len(jax.tree.leaves(plain)) == 5  # a model without linear layers carries what it carried
    every = TP.init_cache(dataclasses.replace(CFG, tf_layer_kinds="linear"), (3,))
    assert every.k.shape == (3, 0, 12, 2, 8) and every.s.shape == (3, 4, 4, 8, 8)
    c, h = P.wire_state(CFG, cache)
    assert c.shape == h.shape == (3, 32) and not c.any()


@pytest.mark.parametrize("kinds,chunk", [("linear,linear,linear,gated", 4), ("linear,gated", 64), ("linear", 4)])
def test_the_actors_step_is_the_learners_unroll(params, kinds, chunk, monkeypatch):
    """Frame by frame over a chunk with the rule's state, the
    convolution's tail and the gated layer's keys and values in the carry,
    against the unroll from zero (the rule in chunks of 4, three to the
    row, or in the program's one chunk of 64 padded out); then after
    `reset_between_chunks` the next chunk is again the unroll from zero."""
    monkeypatch.setattr(GD, "CHUNK", chunk)
    cfg = dataclasses.replace(CFG, tf_layer_kinds=kinds)
    p = params if kinds == CFG.tf_layer_kinds else P.init_params(cfg, jax.random.PRNGKey(2))
    net = P.PolicyNet(cfg)
    r = np.random.RandomState(6)  # a seed with no near-tie among the router's standardised scores
    B, T = 2, 10
    state = P.initial_state(cfg, (B,))
    for _ in range(2):
        obs = _obs(r, B, T)
        _, want = net.apply(p, P.initial_state(cfg, (B,)), obs, unroll=True)
        values, logp = [], []
        for t in range(T):
            state, out = net.apply(p, state, jax.tree.map(lambda x: x[:, t], obs))
            values.append(out.value)
            logp.append(out.dist.type_logp)
        np.testing.assert_allclose(jnp.stack(values, 1), want.value, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(jnp.stack(logp, 1), want.dist.type_logp, rtol=1e-4, atol=1e-4)
        assert np.asarray(state.s).any() and np.asarray(state.conv).any() and int(state.idx[0]) == T
        state = P.reset_between_chunks(cfg, state)
        assert not np.asarray(state.s).any() and not np.asarray(state.conv).any() and int(state.idx[0]) == 0
        assert jax.tree.map(jnp.shape, state) == jax.tree.map(jnp.shape, P.initial_state(cfg, (B,)))


def test_chunked_recurrent_and_rematerialised_unrolls_agree(params, monkeypatch):
    """The unroll at the program's chunk against itself at chunks of 4,
    rematerialised, with plain attention, and with the frame-by-frame
    recurrence (the oracle) in the chunked rule's place; the gradients of
    the rematerialised unroll against the oracle's."""
    net = lambda **change: P.PolicyNet(dataclasses.replace(CFG, **change))
    obs = _obs(np.random.RandomState(4), 2, 11)
    state = P.initial_state(CFG, (2,))
    value = lambda p, **change: net(**change).apply(p, state, obs, unroll=True)[1].value
    grad = lambda **change: jax.grad(lambda p: jnp.sum(value(p, **change) ** 2))(params)
    want, got_grad = value(params), grad(tf_remat=True)
    for change in (dict(tf_remat=True), dict(tf_attn_block=0)):
        np.testing.assert_allclose(value(params, **change), want, rtol=1e-4, atol=2e-5)
    monkeypatch.setattr(GD, "CHUNK", 4)
    np.testing.assert_allclose(value(params), want, rtol=1e-4, atol=2e-5)
    monkeypatch.setattr(GD, "chunked", lambda q, k, v, beta, g, chunk, state=None: GD.recurrent(q, k, v, beta, g, state))
    np.testing.assert_allclose(value(params), want, rtol=1e-4, atol=2e-5)
    for a, b in zip(jax.tree.leaves(got_grad), jax.tree.leaves(grad())):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-4 * max(float(jnp.abs(b).max()), 1e-3))


def test_bad_shapes_are_refused():
    for change, match in ((dict(tf_layer_kinds="linear,latent"), "latent attention needs|no KVCache"),
                          (dict(tf_lin_value_heads=3), "a linear layer needs"),
                          (dict(tf_lin_head_dim=0), "a linear layer needs"),
                          (dict(tf_layer_kinds="linear,softmax"), "keys and values of every frame")):
        with pytest.raises(ValueError, match=match):
            P.init_params(dataclasses.replace(CFG, **change), jax.random.PRNGKey(0))


def test_the_four_shares_and_the_gated_shared_expert_once_add_up_to_the_uncut_reference_layer():
    """32 experts in 4 shares of 8. Each share's block computes its routed
    part and, as every chip does for its own frames, the whole gated shared
    expert; a share whose experts' output matrices are zero gives the
    shared expert alone. The shares' routed parts and the shared expert
    counted once are what the plain reference (`gdn_moe_ppo.experts`)
    gives with all 32 held; four times the shared expert is not."""
    from benchmark import cells

    bench = cells.load_benchmark()
    ref = cells.load_module(bench, "references", "gdn_moe_ppo")
    cfg = dataclasses.replace(CFG, tf_layers=1, tf_layer_kinds="gated", moe_experts=32, moe_experts_held=8, moe_top_k=5)
    B, T, D = 2, 9, cfg.lstm_hidden
    r = np.random.RandomState(7)
    x = jnp.asarray(r.randn(B, T, D), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    whole_cfg = dataclasses.replace(cfg, moe_experts_held=32, moe_first_expert=0)
    whole = TP.Block(whole_cfg, "gated", sparse=True).init(jax.random.PRNGKey(5), x, positions)["params"]
    whole["attn_out"]["kernel"] = jnp.zeros_like(whole["attn_out"]["kernel"])  # the feed-forward part alone: a = x

    def share(first, keep_routed=True):
        p = dict(whole, moe={k: (v if k == "router" else v[:, first:first + 8]) for k, v in whole["moe"].items()})
        if not keep_routed:
            p["moe"] = dict(p["moe"], w_down=jnp.zeros_like(p["moe"]["w_down"]))
        block = TP.Block(dataclasses.replace(cfg, moe_first_expert=first), "gated", sparse=True)
        y, _, counts = block.apply({"params": p}, x, positions)
        return y - x, int(counts[0].sum())

    parts = [share(first) for first in (0, 8, 16, 24)]
    shared, _ = share(0, keep_routed=False)
    config = {"policy": dict(dataclasses.asdict(whole_cfg), moe_standardize_router=True)}
    want = jax.vmap(lambda row: ref.experts(whole, row, config, None)[0])(x)
    got = sum(p[0] for p in parts) - 3 * shared
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)
    assert sum(p[1] for p in parts) == B * T * 5  # every pair computed by one share
    assert np.abs(np.asarray(sum(p[0] for p in parts) - want)).max() > 1e-2  # the shared expert four times over
    assert np.abs(np.asarray(shared)).max() > 1e-2
