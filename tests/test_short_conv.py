"""The transformer family with short-convolution layers beside an
attention layer whose heads carry q/k norms: the short-convolution mixer
and the normed full layer against the plain reference
(`benchmark/references/conv_moe_ppo.py`) on seeded weights, the actor's
step frame by frame through the carry's tail against the learner's
unroll, the mixer's causality, and the carry's shapes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from dotaclient_tpu.config import PolicyConfig
from dotaclient_tpu.env import featurizer as F
from dotaclient_tpu.models import policy as P
from dotaclient_tpu.models import transformer_policy as TP

CFG = PolicyConfig(
    arch="transformer", dtype="float32", lstm_hidden=32, unit_embed_dim=32, mlp_hidden=32, tf_layers=4,
    tf_layer_kinds="full,conv,conv,conv", tf_heads=4, tf_kv_heads=2, tf_head_dim=8, tf_qk_norm=True,
    tf_rope_theta=1e6, tf_context=12, tf_attn_block=4, tf_norm="rmsnorm", tf_norm_eps=1e-5,
    tf_bias=False, tf_final_norm=True, moe_experts=8, moe_experts_held=4, moe_first_expert=2, moe_top_k=3,
    moe_hidden=12, moe_score="sigmoid", moe_standardize_router=True)


def _reference():
    from benchmark import cells

    return cells.load_module(cells.load_benchmark(), "references", "conv_moe_ppo")


def _config(cfg: PolicyConfig) -> dict:
    """The reference's view of the policy: the configuration file's
    `policy` section, and the published key of the filter's taps."""
    return {"policy": dataclasses.asdict(cfg), "conv_L_cache": TP.CONV_TAPS}


class Mixer(nn.Module):
    """A layer's part in attention's place alone, as `Block` calls it."""

    cfg: PolicyConfig
    kind: str
    fused: bool = False
    sp_mesh: object = None

    @nn.compact
    def __call__(self, x, positions, cache=None):
        part = TP._short_conv if self.kind == "conv" else TP._attention
        return part(self, x, positions, cache)


def _seeded(tree, seed, scale=0.3):
    """Every leaf of a parameter tree from the seed: the norms' vectors
    not zero, so that their gradient is seen."""
    leaves, treedef = jax.tree.flatten(tree)
    r = np.random.RandomState(seed)
    return jax.tree.unflatten(treedef, [jnp.asarray(scale * r.randn(*x.shape), jnp.float32) for x in leaves])


def _inputs(seed, B=2, T=11):
    x = jnp.asarray(np.random.RandomState(seed).randn(B, T, CFG.lstm_hidden), jnp.float32)
    return x, jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))


@pytest.mark.parametrize("kind", ["conv", "full"])
def test_the_mixer_is_the_references_on_seeded_weights(kind):
    """The short-convolution mixer ([B | C | x] from one matrix's column
    blocks, the causal filter of three taps over B * x, C's gate, the
    output product) and the full layer with per-head q/k norms, value and
    gradient of every parameter, against the reference's `short_conv` and
    `attention` row by row."""
    ref = _reference()
    x, positions = _inputs(3)
    mixer = Mixer(CFG, kind)
    params = _seeded(mixer.init(jax.random.PRNGKey(0), x, positions)["params"], 4)
    want_names = {"conv": {"ln1", "in_proj", "conv", "out_proj"},
                  "full": {"ln1", "qkv", "q_norm", "k_norm", "attn_out"}}[kind]
    assert set(params) == want_names
    part = ref.short_conv if kind == "conv" else ref.attention

    def program(p):
        return mixer.apply({"params": p}, x, positions)[0]

    def reference(p):
        return jax.vmap(lambda row: part(p, row, _config(CFG), None))(x)

    np.testing.assert_allclose(program(params), reference(params), rtol=1e-5, atol=1e-5)
    cot = jnp.asarray(np.random.RandomState(5).randn(*x.shape), jnp.float32)
    g_got = jax.grad(lambda p: jnp.sum(program(p) * cot))(params)
    g_want = jax.grad(lambda p: jnp.sum(reference(p) * cot))(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_got), jax.tree.leaves(g_want)):
        assert np.abs(np.asarray(b)).max() > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4 * float(jnp.abs(b).max()), err_msg=jax.tree_util.keystr(path))
    if kind == "full":  # without the norms it is another function of the same tree
        plain = Mixer(dataclasses.replace(CFG, tf_qk_norm=False), kind)
        assert np.abs(np.asarray(plain.apply({"params": params}, x, positions)[0] - program(params))).max() > 1e-2


def test_the_mixers_output_depends_on_no_later_frame():
    """Changing the frames from t on changes no output before t, and
    changes the output at t and at the two frames after it through the
    filter alone (a frame's gated input reaches three outputs)."""
    x, positions = _inputs(7, T=10)
    mixer = Mixer(CFG, "conv")
    params = _seeded(mixer.init(jax.random.PRNGKey(0), x, positions)["params"], 8)
    out = mixer.apply({"params": params}, x, positions)[0]
    for t in (0, 4, 9):
        y = mixer.apply({"params": params}, x.at[:, t:].add(1.0), positions)[0]
        np.testing.assert_array_equal(y[:, :t], out[:, :t])
        assert np.abs(np.asarray(y[:, t] - out[:, t])).min() > 0
    only = x.at[:, 4].add(1.0)  # one frame: outputs 4, 5, 6 move, no other
    y = mixer.apply({"params": params}, only, positions)[0]
    moved = np.abs(np.asarray(y - out)).max(axis=(0, 2)) > 0
    assert moved.tolist() == [t in (4, 5, 6) for t in range(10)]


def test_the_carry_holds_each_kinds_state_for_its_layers_only():
    cache = TP.init_cache(CFG, (3,))
    assert cache.k.shape == cache.v.shape == (3, 1, 12, 2, 8)  # the one attention layer's keys and values
    assert cache.short.shape == (3, 3, 2, 32) and cache.short.dtype == jnp.float32  # two gated inputs, three layers
    assert cache.s is None and cache.conv is None and cache.rsum.shape == (3, 4, 2, 8)
    assert all(leaf.shape[0] == 3 for leaf in jax.tree.leaves(cache))  # batch-leading, every leaf
    plain = TP.init_cache(dataclasses.replace(CFG, tf_layer_kinds="full"), (3,))
    assert plain.short is None and plain.k.shape == (3, 4, 12, 2, 8)
    bf16 = TP.init_cache(dataclasses.replace(CFG, dtype="bfloat16"), (3,))
    assert bf16.short.shape == (3, 3, 2, 32) and bf16.short.dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="conv: the last 2 gated inputs"):
        TP.layer_kinds(dataclasses.replace(CFG, tf_layer_kinds="full,shortconv"))


def _obs(r, B, T):
    z = F.zeros_observation()
    make = lambda x: (jnp.asarray(r.randn(B, T, *np.shape(x)), jnp.float32) if np.asarray(x).dtype == np.float32
                      else jnp.ones((B, T) + np.shape(x), np.asarray(x).dtype))
    return jax.tree.map(make, z)


@pytest.mark.parametrize("kinds", ["full,conv,conv,conv", "conv"])
def test_the_actors_step_is_the_learners_unroll(kinds):
    """Frame by frame over a chunk with the attention layer's keys and
    values and each short-convolution layer's last two gated inputs in the
    carry, against the unroll from zero; then after `reset_between_chunks`
    the next chunk is again the unroll from zero."""
    cfg = dataclasses.replace(CFG, tf_layer_kinds=kinds)
    p = P.init_params(cfg, jax.random.PRNGKey(2))
    net = P.PolicyNet(cfg)
    r = np.random.RandomState(11)  # a seed with no near-tie among the router's standardised scores (6 has one)
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
        assert np.asarray(state.short).any() and int(state.idx[0]) == T
        state = P.reset_between_chunks(cfg, state)
        assert not np.asarray(state.short).any() and int(state.idx[0]) == 0
        assert jax.tree.map(jnp.shape, state) == jax.tree.map(jnp.shape, P.initial_state(cfg, (B,)))


def test_the_rematerialised_and_plain_unrolls_agree():
    """The unroll under `tf_remat` and with plain attention in place of
    the blocks: the same values, and the same gradients under remat."""
    net = lambda **change: P.PolicyNet(dataclasses.replace(CFG, **change))
    p = P.init_params(CFG, jax.random.PRNGKey(3))
    obs = _obs(np.random.RandomState(4), 2, 11)
    state = P.initial_state(CFG, (2,))
    value = lambda p, **change: net(**change).apply(p, state, obs, unroll=True)[1].value
    want = value(p)
    for change in (dict(tf_remat=True), dict(tf_attn_block=0)):
        np.testing.assert_allclose(value(p, **change), want, rtol=1e-5, atol=1e-5)
    g_remat = jax.grad(lambda p: jnp.sum(value(p, tf_remat=True) ** 2))(p)
    g_plain = jax.grad(lambda p: jnp.sum(value(p) ** 2))(p)
    for a, b in zip(jax.tree.leaves(g_remat), jax.tree.leaves(g_plain)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5 * max(float(jnp.abs(b).max()), 1e-3))
