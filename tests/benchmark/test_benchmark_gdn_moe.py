"""The linear-attention family through the benchmark: the program against
`references/gdn_moe_ppo.py` through the harness on a tiny cell on the CPU,
the planted faults that have to come out as not correct, the
configuration's file against the published config key for key, and the
counts behind the new rooflines."""

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

CONFIG, CELL = "qwen3-next-80b-a3b-ep32", "learner-qwen3next-ep32-wire"

# The `config` of the catalog row Qwen3-Next-80B-A3B-Instruct (its
# source_url is the configuration's `source`), copied here so that the test
# needs no file outside the repository; where the catalog is at hand it is
# compared.
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}
REDUCED = {"num_hidden_layers": 4, "num_experts": 16}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = dict(
    lstm_hidden=32, unit_embed_dim=32, mlp_hidden=32, tf_layers=4, tf_heads=4, tf_kv_heads=2, tf_head_dim=8,
    tf_rotary_dim=4, tf_lin_key_heads=2, tf_lin_value_heads=4, tf_lin_head_dim=8, tf_context=12,
    tf_attn_block=4, moe_experts=8, moe_experts_held=4, moe_first_expert=2, moe_top_k=3, moe_hidden=12,
    moe_shared_hidden=12)


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


@pytest.fixture(scope="module")
def config(bench):
    return cells.load_cell(bench, CELL)["config_data"]


def make_gdn_root(dst: str, dtype: str = "float32") -> str:
    """The tiny root with one more configuration, the shipped one cut to
    a toy's widths (tests only: a cell never cuts a width), and its cell."""
    root = make_tiny_root(dst)
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg["name"] = "gdn-moe-tiny"
    cfg["policy"].update(TINY, dtype=dtype)
    cfg["learner"].update(rows_per_chip=8, seq_len=11, publish_every=4)
    cfg["ppo"].update(max_staleness=12)
    # float32 compute on the CPU: the program and the reference are the same
    # mathematics and read 1e-5 apart or less; each planted fault reads above
    # 1e-2 on grad_error (the tests below).
    cfg["check"]["limits"] = {"loss_gap_1": 1e-5, "grad_norm_gap": 1e-3, "grad_error": 1e-3,
                               "update_norm_gap": 1e-3}
    with open(os.path.join(b, "configs", "gdn-moe-tiny.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "scopes", CONFIG + ".json")) as f:
        scopes = json.load(f)
    with open(os.path.join(b, "scopes", "gdn-moe-tiny.json"), "w") as f:
        json.dump(dict(scopes, config="gdn-moe-tiny"), f)
    bench = cells.load_benchmark(root)
    bench["configs"].append({"name": "gdn-moe-tiny", "source": "test", "reduced": [],
                             "file": "benchmark/configs/gdn-moe-tiny.json", "why": "test"})
    bench["workloads"].append({"name": "tiny-gdn", "config": "gdn-moe-tiny", "traffic": "wire-tiny",
                               "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-gdn")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def gdn_root(tmp_path_factory):
    """The tiny root; while it lives the rule's chunk is 4 frames, so that
    a row of 12 is three chunks with the state passed between them."""
    from dotaclient_tpu.ops import gated_delta as GD

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(GD, "CHUNK", 4)
        yield make_gdn_root(str(tmp_path_factory.mktemp("bench") / "root"))


@pytest.fixture(scope="module")
def tiny(gdn_root):
    """(cell, configuration, traffic, reference module) of the tiny cell."""
    bench = cells.load_benchmark(gdn_root)
    cell = cells.load_cell(bench, "tiny-gdn", gdn_root)
    ref = cells.load_module(bench, "references", cell["config_data"]["reference"], gdn_root)
    return cell, cell["config_data"], cell["traffic_data"], ref


def drive(root, seed, break_step=None, seconds=1.0):
    bench = cells.load_benchmark(root)
    return harness.run_cell(bench, "tiny-gdn", seed, seconds, False, time.time(),
                            jax.devices()[:1], root, break_step=break_step)


def test_the_program_agrees_with_the_reference_at_a_tiny_size(gdn_root):
    """The timed path's own first steps (the learner's step: chunked rule,
    sorted pairs, fused buffer) against the reference's `loss_and_grad`
    and Adam: the loss, the first gradient leaf by leaf and the change
    after two steps."""
    res = drive(gdn_root, seed=2**31 + 3)
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
        cell = cells.load_cell(cells.load_benchmark(root), "tiny-gdn", root)
        cfg = harness.learner_config(cell, 0, "mem://x")
        cfg = dataclasses.replace(cfg, policy=dataclasses.replace(cfg.policy, **policy))
        step, _, _ = build_single_train_step(cfg, mesh_lib.make_mesh(cfg.mesh_shape, jax.devices()[:1]))
        return step

    return fault


def conv_left_out(inner):
    """The convolution sees this frame alone: the taps of the three frames
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


@pytest.mark.parametrize("fault", ["whole_head_rotates", "conv_left_out"])
def test_planted_faults_read_not_correct(gdn_root, fault):
    """Faults planted in the program's step, under the harness: rotary
    over the whole head where the published quarter rotates (the same
    tree, another function), and the convolution's earlier taps zeroed."""
    plant = _replaced(gdn_root, tf_rotary_dim=0) if fault == "whole_head_rotates" else conv_left_out
    res = drive(gdn_root, seed=2**31 + 11, break_step=plant)
    assert res["correct"] is False
    assert res["check"]["grad_error"]["value"] > 10 * res["check"]["grad_error"]["limit"]


def seeded(tiny, seed, n_steps=2, vectors=0.0):
    """Weights and row batches from the seed; with `vectors`, every linear
    layer's A_log and dt_bias set from the seed too (zero otherwise, as a
    run has them)."""
    _, config, traffic, ref = tiny
    B = int(config["learner"]["rows_per_chip"])
    rows = frames.make_rows(config, traffic["rows"], n_steps * B, seed)
    params = weights.make_params(ref.param_shapes(config), seed)
    r = np.random.RandomState(seed)
    for blk in params["params"]["core"]["tf"].values():
        if vectors and "A_log" in blk:
            blk["A_log"] = jnp.asarray(vectors * r.randn(*blk["A_log"].shape), jnp.float32)
            blk["dt_bias"] = jnp.asarray(vectors * r.randn(*blk["dt_bias"].shape), jnp.float32)
    return params, [frames.rows_slice(rows, i * B, (i + 1) * B) for i in range(n_steps)]


@pytest.mark.parametrize("fault", ["decay_left_out", "beta_left_out", "conv_left_out", "out_gate_left_out",
                                   "shared_gate_left_out", "half_batch"])
def test_the_references_own_faults_read_not_correct(tiny, fault):
    """What `control.py` runs on the chip: the reference with a fault
    planted in it, in the program's place, against the reference."""
    _, config, _, ref = tiny
    params, batches = seeded(tiny, 31, vectors=0.3)
    key = check.sketch_key(31)
    want = ref.run_reference(config, params, batches, key)
    got = ref.run_reference(config, params, batches, key, fault=fault)
    judged = check.compare(config, got, want)
    assert any(v > lim for v, lim in judged.values()), judged
    same = check.compare(config, want, want)
    assert all(v == 0 for v, _ in same.values())
    with pytest.raises(ValueError, match="unknown fault"):
        ref.loss_and_grad(params, batches[0], config, fault="sliding_as_full")


def test_the_unroll_is_the_references_forward(tiny):
    """The program's unroll (chunked rule, blocked attention, sorted
    pairs) against the reference's forward (frame-by-frame recurrence,
    dense mask, every frame through every held expert), with A_log and
    dt_bias not zero; and the gradient of one scalar of the values on
    both sides, leaf by leaf."""
    from dotaclient_tpu.env import featurizer as F
    from dotaclient_tpu.models import policy as P

    cell, config, _, ref = tiny
    cfg = harness.learner_config(cell, 0, "mem://x").policy
    params, (rows,) = seeded(tiny, 5, n_steps=1, vectors=0.3)
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
    for name in ("A_log", "dt_bias"):
        assert np.asarray(g_want["params"]["core"]["tf"]["block0"][name]).any()


def test_the_configuration_keeps_every_published_width(bench, config):
    """Key for key against the published config: every key is there and
    equal, except the two that BENCHMARK.json lists as reduced, which the
    file states beside their published values."""
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == list(REDUCED)
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value and config[key] == REDUCED[key] < value
        else:
            assert config[key] == value, key
    assert config["source"] == entry["source"] and "thirty-two chips" in config["deployment"].lower()
    assert "first stage" in config["deployment"] and "experts 0-15" in config["deployment"]
    assert "2,560 pairs" in config["deployment"] and "320 pairs" in config["deployment"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert row["config"] == PUBLISHED and row["source_url"] == entry["source"]


def test_the_policy_section_is_the_published_sizes(config):
    pol = config["policy"]
    assert pol["arch"] == "transformer" and pol["dtype"] == "bfloat16"
    assert pol["lstm_hidden"] == config["hidden_size"] == 2048
    assert pol["tf_layers"] == config["num_hidden_layers"] == config["full_attention_interval"] == 4
    assert pol["tf_layer_kinds"] == "linear,linear,linear,gated"  # one period: the interval's last layer attends
    assert (pol["tf_heads"], pol["tf_kv_heads"], pol["tf_head_dim"]) == (
        config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]) == (16, 2, 256)
    assert pol["tf_rotary_dim"] == config["partial_rotary_factor"] * config["head_dim"] == 64
    assert pol["tf_rope_theta"] == config["rope_theta"] == 10000000 and config["rope_scaling"] is None
    assert "tf_yarn_factor" not in pol and "tf_window" not in pol and config["use_sliding_window"] is False
    assert (pol["tf_lin_key_heads"], pol["tf_lin_value_heads"], pol["tf_lin_conv"]) == (
        config["linear_num_key_heads"], config["linear_num_value_heads"], config["linear_conv_kernel_dim"]) == (16, 32, 4)
    assert pol["tf_lin_head_dim"] == config["linear_key_head_dim"] == config["linear_value_head_dim"] == 128
    assert "tf_lin_chunk" not in pol and config["assumed"]["chunk"]  # a size of the computation (GD.CHUNK), stated
    assert (pol["tf_norm"], pol["tf_norm_eps"]) == ("rmsnorm", config["rms_norm_eps"])
    assert pol["tf_bias"] is False and pol["tf_final_norm"] is True
    assert "tf_dense_layers" not in pol and config["mlp_only_layers"] == [] and config["decoder_sparse_step"] == 1
    assert pol["moe_experts"] == config["published"]["num_experts"] == 512  # the router's width
    assert pol["moe_experts_held"] == config["num_experts"] == 16 and pol["moe_first_expert"] == 0
    assert pol["moe_top_k"] == config["num_experts_per_tok"] == 10
    assert pol["moe_hidden"] == config["moe_intermediate_size"] == 512
    assert pol["moe_shared_hidden"] == config["shared_expert_intermediate_size"] == 512
    assert pol["moe_shared_gate"] is True and pol["moe_score"] == "softmax" and config["norm_topk_prob"] is True
    assert pol["moe_standardize_router"] is True and config["assumed"]["standardize_router"]  # a stated departure
    assert config["hidden_act"] == "silu" and "prediction" in config["assumed"]["absent"]
    assert config["learner"] == {"rows_per_chip": config["learner"]["rows_per_chip"], "seq_len": 4095,
                                 "publish_every": config["learner"]["publish_every"], "mesh_shape": "dp=-1"}
    assert pol["tf_context"] == config["learner"]["seq_len"] + 1 and pol["tf_remat"] is True
    mellum = cells.load_cell(cells.load_benchmark(), "learner-mellum2-ep4-wire")["config_data"]
    assert config["ppo"] == mellum["ppo"] and config["features"] == mellum["features"]
    for key in ("trunk_and_heads", "policy_section", "router", "standardize_router", "norm_scale", "decay_vectors",
                "conv_filter", "column_order", "model_code", "rotary_pairing", "absent", "share", "rows_per_chip",
                "chunk", "publish_every", "ppo", "dtype"):
        assert config["assumed"][key], key
    assert set(config["check"]["limits"]) == {"loss_gap_1", "grad_error", "grad_error_worst", "grad_norm_gap",
                                              "update_norm_gap"}  # the first loss too: the one number 13 times apart
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
    assert ref.n_params(config) == 347_736_567
    size = lambda name: sum(x.size for x in jax.tree.leaves(program["params"]["core"]["tf"][name]))
    # ln2, router, the 16 held experts, the shared expert and its gate's column
    feed_forward = 2048 + 2048 * 512 + 16 * 3_145_728 + 3_145_728 + 2048
    # qkvz, ba, the filter, A_log and dt_bias, the head norm, out; and ln1
    assert size("block0") == size("block2") == 33_718_464 + 2048 + feed_forward
    # the one product for q, gate, k and v, the two head norms, out; and ln1
    assert size("block3") == 27_263_488 + 2048 + feed_forward
    assert sum(size(f"block{i}") for i in range(4)) + 2048 == 346_549_312  # the period, with the final norm
    # the weight frame's float32 values stay under 2**31 bytes
    assert 4 * ref.n_params(config) < 2**31
    rows = cfg.batch_size
    assert ref.train_step_flops(config, rows) == pytest.approx(train_step_flops(cfg), rel=1e-12)
    per_row = ref.forward_flops_per_row(config)
    frames_ = 4096
    per_frame = {k: v / frames_ / 1e6 for k, v in per_row.items()}
    assert per_frame["attn_linear"] == pytest.approx(3 * (67.37 + 3.146), rel=1e-3)  # products, the rule
    assert per_frame["attn_gated"] == pytest.approx(54.53 + 33.56, rel=1e-3)  # products, scores and values
    assert per_frame["moe"] == pytest.approx(4 * (2.097 + 1.966), rel=1e-3)  # router, 0.3125 held pairs a frame
    assert per_frame["moe_shared"] == pytest.approx(4 * 6.296, rel=1e-3)
    # the linear layers are most of the work
    assert 0.61 < per_row["attn_linear"] / sum(per_row.values()) < 0.62
    costs = ref.scope_costs(config, rows)
    assert set(costs) <= set(cells.load_scopes(bench, CONFIG))
    assert set(costs) == {"attn_linear", "attn_gated", "moe", "moe_shared", "optimizer"}
    assert costs["optimizer"] == {"flops": 0.0, "bytes": 28.0 * 347_736_567}
    core = sum(costs[k]["flops"] for k in ("attn_linear", "attn_gated", "moe", "moe_shared"))
    assert core == pytest.approx(
        ref.train_step_flops(config, rows) - 3.0 * rows * (per_row["trunk"] + per_row["heads"]), rel=1e-12)
    # the rule is counted by its recurrence, whatever chunk computes it: 3 x 2 x 128 x 128 a value head and frame
    assert 3 * 32 * 3 * 2 * 128 * 128 / 1e6 == pytest.approx(3 * 3.146, rel=1e-3)
    # the held pairs of an even routing: 1/32 of the whole layer's routed work
    whole = dict(config, policy=dict(config["policy"], moe_experts_held=512))
    moe, router = per_row["moe"], 4 * frames_ * 2.0 * 2048 * 512
    assert (ref.forward_flops_per_row(whole)["moe"] - router) == pytest.approx(32 * (moe - router))
    from dotaclient_tpu.ops import moe as M
    assert M.buffer_rows(4 * frames_ * 10, 16, 512) == 6656  # 5,120 pairs under even routing, 13 row tiles


def test_the_new_readers_and_their_entries(bench):
    new = ("attn_linear.ms", "attn_linear.roofline_pct", "attn_gated.ms", "attn_gated.roofline_pct")
    nothing = {"trace": None, "scope_costs": {}, "syncs": [], "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    for name in new:
        assert cells.load_reader(bench, name)(nothing) is None  # the parent's program, an untraced run
    entries = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert names[names.index(new[0]):][:4] == list(new)  # one run, wherever later entries leave it
    assert all(entries[n]["workloads"] == [CELL] for n in new)
    assert all(entries[n]["moves"] == "env_steps_per_s" and entries[n]["layer"] == "device step" for n in new)
    assert all(entries[n]["source"] == "device_trace" for n in new)
    reported = {m["name"] for m in cells.metrics_for(bench, CELL, "per_layer")}
    assert set(new) <= reported
    assert {"loss.ms", "optimizer.ms", "optimizer.roofline_pct", "step.unscoped_pct", "step.mfu_pct",
            "device.longest_gap_ms"} <= reported
    # the scopes this configuration shares with the other transformer cells, which its scope file declares
    shared = {"moe.ms", "moe.roofline_pct", "moe.load_max_over_mean", "moe.local_pairs_pct",
              "moe_shared.ms", "moe_shared.roofline_pct", "trunk.ms", "heads.ms"}
    assert shared <= reported
    assert {n.split(".")[0] for n in shared} <= set(cells.load_scopes(bench, CONFIG))
    assert not {"attn_full.ms", "attn_latent.ms", "mlp.ms", "lstm.ms"} & reported
    # a scoped trace of this configuration's step reads through them
    trace = {"scope_self_s": {"attn_linear": 0.4, "attn_gated": 0.06, "": 0.01}, "step_count": 2, "step_busy_s": 0.52}
    run = dict(nothing, trace=trace, scope_costs={"attn_linear": {"flops": 197e12 * 0.05, "bytes": 0.0},
                                                  "attn_gated": {"flops": 0.0, "bytes": 819e9 * 0.006}})
    read = {n: cells.load_reader(bench, n)(run) for n in new}
    assert read["attn_linear.ms"] == pytest.approx(200.0) and read["attn_gated.ms"] == pytest.approx(30.0)
    assert read["attn_linear.roofline_pct"] == pytest.approx(25.0)
    assert read["attn_gated.roofline_pct"] == pytest.approx(20.0)


def test_the_tiny_steps_operations_carry_their_scopes(tiny):
    """The tiny cell's step as the harness builds it, compiled here: every
    declared layer scope of the configuration is in some operation's
    `op_name`, the rule's loops over segments and chunks sit under
    `attn_linear`, and nothing of the linear layers under `attn_gated`."""
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
    scopes = ["unpack", "loss", "trunk", "attn_linear", "attn_gated", "moe", "moe_shared", "heads", "optimizer"]
    for scope in scopes:
        assert any(f"/{scope}/" in n or n.endswith("/" + scope) for n in names), scope
    loops = [n for n in names if "while" in n and "/block" in n]
    assert loops and all("/attn_linear/" in n for n in loops if re.search(r"/block[0-2]/", n))
    assert not [n for n in names if "/attn_gated/" in n and re.search(r"/block[0-2]/", n)]
