"""The short-convolution family through the benchmark: the program against
`references/conv_moe_ppo.py` through the harness on a tiny cell on the
CPU, the planted faults that have to come out as not correct, the
configuration's file against the published config key for key, and the
counts behind the new roofline."""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import ROOT, make_tiny_root  # noqa: F401  (puts the root on the path)

from benchmark import cells, check, frames, harness, weights

CONFIG, CELL = "lfm2-24b-a2b-ep8", "learner-lfm2-ep8-wire"
LAYER_TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9 + ["full_attention", "conv"]
# The published `config.json` of LFM2-24B-A2B (the configuration's
# `source`), the keys that state its shape, copied here so that the test
# needs no file outside the repository.
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
    "layer_types": LAYER_TYPES, "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}
REDUCED = {"num_hidden_layers": 4, "num_experts": 8, "num_dense_layers": 0}
TINY = dict(
    lstm_hidden=32, unit_embed_dim=32, mlp_hidden=32, tf_layers=4, tf_heads=4, tf_kv_heads=2, tf_head_dim=8,
    tf_context=12, tf_attn_block=4, moe_experts=8, moe_experts_held=4, moe_first_expert=2, moe_top_k=3,
    moe_hidden=12)


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


@pytest.fixture(scope="module")
def config(bench):
    return cells.load_cell(bench, CELL)["config_data"]


def make_conv_root(dst: str, dtype: str = "float32") -> str:
    """The tiny root with one more configuration, the shipped one cut to
    a toy's widths (tests only: a cell never cuts a width), and its cell."""
    root = make_tiny_root(dst)
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg["name"] = "conv-moe-tiny"
    cfg["policy"].update(TINY, dtype=dtype)
    cfg["learner"].update(rows_per_chip=8, seq_len=11, publish_every=4)
    cfg["ppo"].update(max_staleness=12)
    # float32 compute on the CPU: the program and the reference are the same
    # mathematics and read 1e-5 apart or less; each planted fault reads above
    # 1e-2 on grad_error (the tests below).
    cfg["check"]["limits"] = {"loss_gap_1": 1e-5, "grad_norm_gap": 1e-3, "grad_error": 1e-3,
                              "update_norm_gap": 1e-3}
    with open(os.path.join(b, "configs", "conv-moe-tiny.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "scopes", CONFIG + ".json")) as f:
        scopes = json.load(f)
    with open(os.path.join(b, "scopes", "conv-moe-tiny.json"), "w") as f:
        json.dump(dict(scopes, config="conv-moe-tiny"), f)
    bench = cells.load_benchmark(root)
    bench["configs"].append({"name": "conv-moe-tiny", "source": "test", "reduced": [],
                             "file": "benchmark/configs/conv-moe-tiny.json", "why": "test"})
    bench["workloads"].append({"name": "tiny-conv", "config": "conv-moe-tiny", "traffic": "wire-tiny",
                               "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-conv")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def conv_root(tmp_path_factory):
    return make_conv_root(str(tmp_path_factory.mktemp("bench") / "root"))


@pytest.fixture(scope="module")
def tiny(conv_root):
    """(cell, configuration, traffic, reference module) of the tiny cell."""
    bench = cells.load_benchmark(conv_root)
    cell = cells.load_cell(bench, "tiny-conv", conv_root)
    ref = cells.load_module(bench, "references", cell["config_data"]["reference"], conv_root)
    return cell, cell["config_data"], cell["traffic_data"], ref


def drive(root, seed, break_step=None, seconds=1.0):
    bench = cells.load_benchmark(root)
    return harness.run_cell(bench, "tiny-conv", seed, seconds, False, time.time(),
                            jax.devices()[:1], root, break_step=break_step)


def test_the_program_agrees_with_the_reference_at_a_tiny_size(conv_root):
    """The timed path's own first steps (the learner's step: the filter
    over the row, blocked attention with q/k norms, sorted pairs, fused
    buffer) against the reference's `loss_and_grad` and Adam: the loss,
    the first gradient leaf by leaf and the change after two steps."""
    res = drive(conv_root, seed=2**31 + 3)
    assert res["correct"] is True and res["attempted"] > 0
    assert res["check"]["loss_gap_1"]["value"] < 1e-5
    assert res["check"]["grad_error"]["value"] < 1e-4
    assert res["check"]["grad_norm_gap"]["value"] < 1e-4
    assert res["check"]["update_norm_gap"]["value"] < 1e-3  # a bias leaf's two Adam steps turn on the signs of tiny gradients


def _replaced(root, **policy):
    """The step of the same learner with fields of its policy replaced."""
    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.parallel.train_step import build_single_train_step

    def fault(inner):
        cell = cells.load_cell(cells.load_benchmark(root), "tiny-conv", root)
        cfg = harness.learner_config(cell, 0, "mem://x")
        cfg = dataclasses.replace(cfg, policy=dataclasses.replace(cfg.policy, **policy))
        step, _, _ = build_single_train_step(cfg, mesh_lib.make_mesh(cfg.mesh_shape, jax.devices()[:1]))
        return step

    return fault


def filter_left_out(inner):
    """The filter reads this frame alone: the taps of the two frames
    before it are zero while the step runs, and what the step did to the
    filter is put back on the weights it had."""
    def step(state, batch):
        layers = lambda p: [b["conv"] for b in p["params"]["core"]["tf"].values() if "conv" in b]
        kept = [m["kernel"] for m in layers(state.params)]
        params = jax.tree.map(lambda x: x, state.params)
        for m in layers(params):
            m["kernel"] = m["kernel"].at[:-1].set(0.0)
        new, metrics = inner(state._replace(params=params), batch)
        for m, w in zip(layers(new.params), kept):
            m["kernel"] = m["kernel"].at[:-1].add(w[:-1])
        return new, metrics

    return step


@pytest.mark.parametrize("fault", ["filter_left_out", "scale_of_another_size"])
def test_planted_faults_read_not_correct(conv_root, fault):
    """Faults planted in the program's step, under the harness: the
    filter's earlier taps zeroed, and the routing weights scaled so that a
    frame's no longer sum to one (the same tree, another function). The
    q/k norms left out change the tree, so that fault is the reference's
    (below)."""
    plant = {"filter_left_out": filter_left_out,
             "scale_of_another_size": _replaced(conv_root, moe_route_scale=2.0)}[fault]
    res = drive(conv_root, seed=2**31 + 11, break_step=plant)
    assert res["correct"] is False
    assert res["check"]["grad_error"]["value"] > 10 * res["check"]["grad_error"]["limit"]


def seeded(tiny, seed, n_steps=2):
    """Weights and row batches from the seed."""
    _, config, traffic, ref = tiny
    B = int(config["learner"]["rows_per_chip"])
    rows = frames.make_rows(config, traffic["rows"], n_steps * B, seed)
    params = weights.make_params(ref.param_shapes(config), seed)
    return params, [frames.rows_slice(rows, i * B, (i + 1) * B) for i in range(n_steps)]


@pytest.mark.parametrize("fault", ["filter_left_out", "gates_left_out", "qk_norm_left_out", "half_batch"])
def test_the_references_own_faults_read_not_correct(tiny, fault):
    """What `control.py` runs on the chip: the reference with a fault
    planted in it, in the program's place, against the reference."""
    _, config, _, ref = tiny
    params, batches = seeded(tiny, 31)
    key = check.sketch_key(31)
    want = ref.run_reference(config, params, batches, key)
    got = ref.run_reference(config, params, batches, key, fault=fault)
    judged = check.compare(config, got, want)
    assert any(v > lim for v, lim in judged.values()), judged
    same = check.compare(config, want, want)
    assert all(v == 0 for v, _ in same.values())
    with pytest.raises(ValueError, match="unknown fault"):
        ref.loss_and_grad(params, batches[0], config, fault="decay_left_out")


def test_the_unroll_is_the_references_forward(tiny):
    """The program's unroll (the filter over the row, blocked attention,
    sorted pairs) against the reference's forward (shifted sums, dense
    mask, every frame through every held expert); and the gradient of one
    scalar of the values on both sides, leaf by leaf."""
    from dotaclient_tpu.env import featurizer as F
    from dotaclient_tpu.models import policy as P

    cell, config, _, ref = tiny
    cfg = harness.learner_config(cell, 0, "mem://x").policy
    params, (rows,) = seeded(tiny, 5, n_steps=1)
    obs = {k: jnp.asarray(rows[k]) for k in ("global_feats", "hero_feats", "unit_feats", "unit_mask",
                                             "target_mask", "action_mask")}

    def program(p):
        _, out = P.PolicyNet(cfg).apply(p, P.initial_state(cfg, (obs["unit_mask"].shape[0],)),
                                        F.Observation(**obs), unroll=True)
        return out

    def reference(p):
        return jax.vmap(lambda row: ref.forward_row(p, row, config))(obs)

    got, want = program(params), reference(params)
    np.testing.assert_allclose(got.value, want[4], rtol=1e-4, atol=2e-4)
    np.testing.assert_allclose(got.dist.type_logp, want[0], rtol=1e-4, atol=2e-4)
    np.testing.assert_allclose(got.dist.target_logp, want[3], rtol=1e-4, atol=2e-4)
    g_got = jax.grad(lambda p: jnp.sum(program(p).value ** 2))(params)
    g_want = jax.grad(lambda p: jnp.sum(reference(p)[4] ** 2))(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_got), jax.tree.leaves(g_want)):
        scale = max(float(jnp.abs(b).max()), 1e-6)
        np.testing.assert_allclose(a / scale, b / scale, atol=2e-3, err_msg=jax.tree_util.keystr(path))
    assert np.asarray(g_want["params"]["core"]["tf"]["block1"]["conv"]["kernel"]).any()


def test_the_configuration_keeps_every_published_width(bench, config):
    """Key for key against the published config: every key is there and
    equal, except the three that BENCHMARK.json lists as reduced, which
    the file states beside their published values."""
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == list(REDUCED)
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value and config[key] == REDUCED[key] < value
        else:
            assert config[key] == value, key
    assert config["source"] == entry["source"] and "eight chips share each layer" in config["deployment"].lower()
    assert "stage 1" in config["deployment"] and "experts 0-7" in config["deployment"]
    assert "2,048 pairs" in config["deployment"] and "layers 2-5" in config["deployment"]


def test_the_policy_section_is_the_published_sizes(config):
    pol = config["policy"]
    assert pol["arch"] == "transformer" and pol["dtype"] == "bfloat16"
    assert pol["lstm_hidden"] == config["hidden_size"] == 2048
    assert pol["tf_layers"] == config["num_hidden_layers"] == 4
    # the held depth is layers 2-5 of the published pattern: one whole period
    kinds = {"full_attention": "full", "conv": "conv"}
    assert pol["tf_layer_kinds"] == ",".join(kinds[k] for k in config["layer_types"][2:6]) == "full,conv,conv,conv"
    assert config["layer_types"].count("full_attention") == 10 and config["layer_types"][6:10] == config["layer_types"][2:6]
    assert (pol["tf_heads"], pol["tf_kv_heads"]) == (config["num_attention_heads"], config["num_key_value_heads"])
    assert pol["tf_head_dim"] == config["hidden_size"] // config["num_attention_heads"] == 64
    assert pol["tf_qk_norm"] is True and "tf_rotary_dim" not in pol and "tf_yarn_factor" not in pol
    assert pol["tf_rope_theta"] == config["rope_parameters"]["rope_theta"] == 1000000
    assert config["rope_parameters"]["rope_type"] == "default"
    from dotaclient_tpu.models.transformer_policy import CONV_TAPS
    assert CONV_TAPS == config["conv_L_cache"] == 3 and "tf_conv_taps" not in pol
    assert pol["tf_bias"] is config["conv_bias"] is False and pol["tf_final_norm"] is True
    assert (pol["tf_norm"], pol["tf_norm_eps"]) == ("rmsnorm", config["norm_eps"])
    assert "tf_dense_layers" not in pol and config["num_dense_layers"] == 0
    assert pol["moe_experts"] == config["published"]["num_experts"] == 64  # the router's width
    assert pol["moe_experts_held"] == config["num_experts"] == 8 and pol["moe_first_expert"] == 0
    assert pol["moe_top_k"] == config["num_experts_per_tok"] == 4
    assert pol["moe_hidden"] == config["moe_intermediate_size"] == 1536
    assert "moe_shared_hidden" not in pol and "moe_shared_gate" not in pol  # no shared expert
    assert pol["moe_score"] == "sigmoid" and config["use_expert_bias"] is True and config["norm_topk_prob"] is True
    assert pol["moe_route_scale"] == config["routed_scaling_factor"] == 1
    assert "moe_route_eps" not in pol and "1e-6" in config["assumed"]["route_eps"]  # the model code's, a stated departure
    assert pol["moe_standardize_router"] is True and config["assumed"]["standardize_router"]  # a stated departure
    assert config["learner"] == {"rows_per_chip": 8, "seq_len": 4095, "publish_every": 16, "mesh_shape": "dp=-1"}
    assert pol["tf_context"] == config["learner"]["seq_len"] + 1 and pol["tf_remat"] is True
    glm = cells.load_cell(cells.load_benchmark(), "learner-glm47flash-ep8-wire")["config_data"]
    assert config["ppo"] == glm["ppo"] and config["features"] == glm["features"]
    assert config["ppo"]["max_staleness"] == 3 * config["learner"]["publish_every"]
    for key in ("trunk_and_heads", "policy_section", "route_eps", "router", "standardize_router", "norm_scale",
                "conv_filter", "dense_layers", "share", "rows_per_chip", "publish_every", "ppo", "dtype"):
        assert config["assumed"][key], key
    assert set(config["check"]["why"]) >= set(config["check"]["limits"])


def test_the_programs_tree_and_counts_are_the_references(bench, config):
    from dotaclient_tpu.models.policy import init_params
    from dotaclient_tpu.ops.flops import train_step_flops

    cell = cells.load_cell(bench, CELL)
    ref = cells.load_module(bench, "references", config["reference"])
    cfg = harness.learner_config(cell, seed=0, broker_url="mem://x")
    program = jax.eval_shape(lambda key: init_params(cfg.policy, key), jax.random.PRNGKey(0))
    shapes = jax.tree.map(lambda x: tuple(x.shape), program)
    assert shapes == ref.param_shapes(config)
    size = lambda name: sum(x.size for x in jax.tree.leaves(program["params"]["core"]["tf"][name]))
    # ln1, ln2, router and its bias, the 8 held experts
    feed_forward = 2 * 2048 + 2048 * 64 + 64 + 8 * 9_437_184
    # in_proj (B, C, x), the filter, out_proj
    assert size("block1") == size("block3") == 16_783_360 + feed_forward == 92_416_064
    # the one product for q, k, v, the two head norms, out
    assert size("block0") == 10_485_760 + 2 * 64 + feed_forward == 86_118_592
    assert sum(size(f"block{i}") for i in range(4)) == 363_366_784  # the period
    assert ref.n_params(config) == 364_556_087  # with the final norm, trunk and heads
    assert 4 * ref.n_params(config) < 2**31  # the weight frame's float32 values
    rows = cfg.batch_size
    assert ref.train_step_flops(config, rows) == pytest.approx(train_step_flops(cfg), rel=1e-12)
    per_row = ref.forward_flops_per_row(config)
    per_frame = {k: v / 4096 / 1e6 for k, v in per_row.items()}
    assert per_frame["short_conv"] == pytest.approx(3 * 33.567, rel=1e-4)  # three mixers: in, out, the filter's taps
    assert per_frame["attn_full"] == pytest.approx(20.97 + 16.78, rel=1e-3)  # products, scores and values
    assert per_frame["moe"] == pytest.approx(4 * (0.262 + 9.437), rel=1e-3)  # router, half a held pair a frame
    # the mixers are most of the blocks' work
    blocks = per_row["short_conv"] + per_row["attn_full"] + per_row["moe"]
    assert 0.56 < per_row["short_conv"] / blocks < 0.57  # 56.8%
    # 8 rows: the blocks 17.4 TFLOP a step, 17.7 with trunk and heads, 90 ms at the chip's peak
    assert 3.0 * rows * blocks == pytest.approx(17.43e12, rel=1e-3)
    assert ref.train_step_flops(config, rows) == pytest.approx(17.71e12, rel=1e-3)
    costs = ref.scope_costs(config, rows)
    assert set(costs) <= set(cells.load_scopes(bench, CONFIG))
    assert set(costs) == {"short_conv", "attn_full", "moe", "optimizer"}
    assert costs["optimizer"] == {"flops": 0.0, "bytes": 28.0 * 364_556_087}
    core = sum(costs[k]["flops"] for k in ("short_conv", "attn_full", "moe"))
    assert core == pytest.approx(
        ref.train_step_flops(config, rows) - 3.0 * rows * (per_row["trunk"] + per_row["heads"]), rel=1e-12)
    # the mixers are bound by operations: 3 x 8 x 4,096 x 33.6 MFLOP x 3 at 197 TFLOP/s against their bytes
    short = costs["short_conv"]
    assert short["flops"] / 197e12 > 4 * short["bytes"] / 819e9
    # the held pairs of an even routing: 1/8 of the whole layer's routed work
    whole = dict(config, policy=dict(config["policy"], moe_experts_held=64))
    moe, router = per_row["moe"], 4 * 4096 * 2.0 * 2048 * 64
    assert (ref.forward_flops_per_row(whole)["moe"] - router) == pytest.approx(8 * (moe - router))
    from dotaclient_tpu.ops import moe as M
    assert M.buffer_rows(8 * 4096 * 4, 8, 64) >= 8 * 2048  # 2,048 pairs a held expert under even routing


def test_the_new_readers_and_their_entries(bench):
    new = ("short_conv.ms", "short_conv.roofline_pct")
    nothing = {"trace": None, "scope_costs": {}, "syncs": [], "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    for name in new:
        assert cells.load_reader(bench, name)(nothing) is None  # the parent's program, an untraced run
    entries = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert names[names.index(new[0]):][:2] == list(new)  # one run, wherever later entries leave it
    assert all(entries[n]["workloads"] == [CELL] for n in new)
    assert all(entries[n]["moves"] == "env_steps_per_s" and entries[n]["layer"] == "device step" for n in new)
    assert all(entries[n]["source"] == "device_trace" for n in new)
    reported = {m["name"] for m in cells.metrics_for(bench, CELL, "per_layer")}
    assert set(new) <= reported
    assert {"loss.ms", "optimizer.ms", "optimizer.roofline_pct", "step.unscoped_pct", "step.mfu_pct",
            "device.longest_gap_ms", "device.peak_hbm_gb"} <= reported
    # the shared scopes' entries list the accepted cells alone: a later benchmark PR appends this one
    assert not {"attn_full.ms", "moe.ms", "trunk.ms", "heads.ms", "attn_latent.ms", "lstm.ms"} & reported
    assert {"short_conv", "attn_full", "moe", "trunk", "heads"} <= set(cells.load_scopes(bench, CONFIG))
    trace = {"scope_self_s": {"short_conv": 0.3, "moe": 0.1, "": 0.01}, "step_count": 2, "step_busy_s": 0.41}
    run = dict(nothing, trace=trace, scope_costs={"short_conv": {"flops": 197e12 * 0.075, "bytes": 0.0}})
    read = {n: cells.load_reader(bench, n)(run) for n in new}
    assert read["short_conv.ms"] == pytest.approx(150.0)
    assert read["short_conv.roofline_pct"] == pytest.approx(50.0)
    new_cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert new_cell["chips"] == 1 and new_cell["traffic"] == "wire-saturated" and len(new_cell["why"]) <= 200


def test_the_tiny_steps_operations_carry_their_scopes(tiny):
    """The tiny cell's step as the harness builds it, compiled here: every
    declared layer scope of the configuration is in some operation's
    `op_name`, and nothing of the attention layer's under `short_conv` nor
    of the mixers' under `attn_full`."""
    import re

    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.parallel.train_step import build_single_train_step, init_train_state

    cell, config, _, _ = tiny
    cfg = harness.learner_config(cell, 0, "mem://x")
    step, _, io = build_single_train_step(cfg, mesh_lib.make_mesh(cfg.mesh_shape, jax.devices()[:1]))
    state = jax.eval_shape(lambda: init_train_state(cfg, jax.random.PRNGKey(0)))
    payload, _ = io.alloc_transfer()
    text = step.lower(state, jax.ShapeDtypeStruct(payload.shape, payload.dtype)).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    scopes = ["unpack", "loss", "trunk", "short_conv", "attn_full", "moe", "heads", "optimizer"]
    for scope in scopes:
        assert any(f"/{scope}/" in n or n.endswith("/" + scope) for n in names), scope
    assert not [n for n in names if "/short_conv/" in n and "/block0/" in n]
    assert not [n for n in names if "/attn_full/" in n and re.search(r"/block[1-3]/", n)]
