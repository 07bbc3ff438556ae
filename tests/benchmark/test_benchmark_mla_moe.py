"""The latent-attention, shared-expert family through the benchmark: the
program against `references/mla_moe_ppo.py` through the harness on a tiny
cell on the CPU, the planted faults that have to come out as not correct,
the configuration's file against the published config key for key, and
the counts behind the new rooflines."""

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

CONFIG, CELL = "glm-4.7-flash-ep8", "learner-glm47flash-ep8-wire"

# The `config` of the catalog row GLM-4.7-Flash (its source_url is the
# configuration's `source`), copied here so that the test needs no file
# outside the repository; where the catalog is at hand it is compared.
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10240,
    "max_position_embeddings": 202752, "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True, "num_attention_heads": 20, "n_group": 1,
    "topk_group": 1, "n_routed_experts": 64, "n_shared_experts": 1, "routed_scaling_factor": 1.8,
    "num_experts_per_tok": 4, "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000, "tie_word_embeddings": False,
    "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880,
}
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 8, "num_nextn_predict_layers": 0}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


@pytest.fixture(scope="module")
def config(bench):
    return cells.load_cell(bench, CELL)["config_data"]


def make_mla_root(dst: str, dtype: str = "float32") -> str:
    """The tiny root with one more configuration, the shipped one cut to
    a toy's widths (tests only: a cell never cuts a width; a value head
    of another width than the keys' here, which the shipped one has
    not), and its cell."""
    root = make_tiny_root(dst)
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg["name"] = "mla-moe-tiny"
    cfg["policy"].update(
        lstm_hidden=32, unit_embed_dim=32, mlp_hidden=32, dtype=dtype, tf_layers=3, tf_heads=4,
        tf_q_lora_rank=12, tf_kv_lora_rank=10, tf_qk_nope_dim=6, tf_qk_rope_dim=4, tf_v_head_dim=8,
        tf_mlp_hidden=40, tf_context=12, tf_attn_block=4, moe_experts=8, moe_experts_held=4,
        moe_first_expert=2, moe_top_k=3, moe_hidden=12, moe_shared_hidden=12)
    cfg["learner"].update(rows_per_chip=8, seq_len=11, publish_every=4)
    cfg["ppo"].update(max_staleness=12)
    # float32 compute on the CPU: the program and the reference are the same
    # mathematics and read 1e-6 apart or less; each planted fault reads above
    # 1e-2 on grad_error (the tests below).
    cfg["check"]["limits"] = {"loss_gap_1": 1e-5, "grad_norm_gap": 1e-3, "grad_error": 1e-3,
                               "update_norm_gap": 1e-3}
    with open(os.path.join(b, "configs", "mla-moe-tiny.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "scopes", CONFIG + ".json")) as f:
        scopes = json.load(f)
    with open(os.path.join(b, "scopes", "mla-moe-tiny.json"), "w") as f:
        json.dump(dict(scopes, config="mla-moe-tiny"), f)
    bench = cells.load_benchmark(root)
    bench["configs"].append({"name": "mla-moe-tiny", "source": "test", "reduced": [],
                             "file": "benchmark/configs/mla-moe-tiny.json", "why": "test"})
    bench["workloads"].append({"name": "tiny-mla", "config": "mla-moe-tiny", "traffic": "wire-tiny",
                               "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-mla")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def mla_root(tmp_path_factory):
    return make_mla_root(str(tmp_path_factory.mktemp("bench") / "root"))


@pytest.fixture(scope="module")
def tiny(mla_root):
    """(cell, configuration, traffic, reference module) of the tiny cell."""
    bench = cells.load_benchmark(mla_root)
    cell = cells.load_cell(bench, "tiny-mla", mla_root)
    ref = cells.load_module(bench, "references", cell["config_data"]["reference"], mla_root)
    return cell, cell["config_data"], cell["traffic_data"], ref


def drive(root, seed, break_step=None, seconds=1.0):
    bench = cells.load_benchmark(root)
    return harness.run_cell(bench, "tiny-mla", seed, seconds, False, time.time(),
                            jax.devices()[:1], root, break_step=break_step)


def test_the_program_agrees_with_the_reference_at_a_tiny_size(mla_root):
    res = drive(mla_root, seed=2**31 + 3)
    assert res["correct"] is True and res["attempted"] > 0
    assert res["check"]["loss_gap_1"]["value"] < 1e-5
    assert res["check"]["grad_error"]["value"] < 1e-4
    assert res["check"]["grad_norm_gap"]["value"] < 1e-4
    assert res["check"]["update_norm_gap"]["value"] < 1e-4


def scale_left_out(root):
    """The step of the same learner with the routed weights' factor of
    1.8 left out."""
    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.parallel.train_step import build_single_train_step

    def fault(inner):
        cell = cells.load_cell(cells.load_benchmark(root), "tiny-mla", root)
        cfg = harness.learner_config(cell, 0, "mem://x")
        cfg = dataclasses.replace(cfg, policy=dataclasses.replace(cfg.policy, moe_route_scale=1.0))
        step, _, _ = build_single_train_step(cfg, mesh_lib.make_mesh(cfg.mesh_shape, jax.devices()[:1]))
        return step

    return fault


def shared_left_out(inner):
    """The shared expert left out of every sparse layer's sum: its down
    projection is zero while the step runs, and what the step did to it is
    put back on the weights it had."""
    def step(state, batch):
        layers = lambda p: [b["shared_down"] for b in p["params"]["core"]["tf"].values() if "shared_down" in b]
        kept = [m["kernel"] for m in layers(state.params)]
        params = jax.tree.map(lambda x: x, state.params)
        for m in layers(params):
            m["kernel"] = jnp.zeros_like(m["kernel"])
        new, metrics = inner(state._replace(params=params), batch)
        for m, w in zip(layers(new.params), kept):
            m["kernel"] = m["kernel"] + w
        return new, metrics

    return step


@pytest.mark.parametrize("fault", ["scale_left_out", "shared_left_out"])
def test_planted_faults_read_not_correct(mla_root, fault):
    plant = scale_left_out(mla_root) if fault == "scale_left_out" else shared_left_out
    res = drive(mla_root, seed=2**31 + 11, break_step=plant)
    assert res["correct"] is False
    assert res["check"]["grad_error"]["value"] > 10 * res["check"]["grad_error"]["limit"]


def seeded(tiny, seed, n_steps=2, bias=0.0):
    """Weights and row batches from the seed; with `bias`, every sparse
    layer's per-expert bias set from the seed too (zero otherwise, as a
    run has it: a zero bias weighs nothing whether it may or not)."""
    _, config, traffic, ref = tiny
    B = int(config["learner"]["rows_per_chip"])
    rows = frames.make_rows(config, traffic["rows"], n_steps * B, seed)
    params = weights.make_params(ref.param_shapes(config), seed)
    r = np.random.RandomState(seed)
    for blk in params["params"]["core"]["tf"].values():
        if bias and "moe" in blk:
            blk["moe"]["router_bias"] = jnp.asarray(bias * r.randn(*blk["moe"]["router_bias"].shape), jnp.float32)
    return params, [frames.rows_slice(rows, i * B, (i + 1) * B) for i in range(n_steps)]


@pytest.mark.parametrize("fault", ["latent_norm_left_out", "bias_weighs", "shared_left_out", "half_batch"])
def test_the_references_own_faults_read_not_correct(tiny, fault):
    """What `control.py` runs on the chip: the reference with a fault
    planted in it, in the program's place, against the reference."""
    _, config, _, ref = tiny
    params, batches = seeded(tiny, 31, bias=0.3)
    key = check.sketch_key(31)
    want = ref.run_reference(config, params, batches, key)
    got = ref.run_reference(config, params, batches, key, fault=fault)
    judged = check.compare(config, got, want)
    assert any(v > lim for v, lim in judged.values()), judged
    same = check.compare(config, want, want)
    assert all(v == 0 for v, _ in same.values())
    with pytest.raises(ValueError, match="unknown fault"):
        ref.loss_and_grad(params, batches[0], config, fault="sliding_as_full")


def test_the_worst_leafs_error_is_compared_where_the_file_limits_it(tiny, config):
    """The committed file limits `grad_error_worst`, between its two
    readings (PERF.md section 6: the one number that sees half of the
    rows left out on the chip). `check.compare` judges by a number
    exactly where the file gives it a limit, and the half batch fails by
    this one alone."""
    _, tiny_config, _, ref = tiny
    assert 0.1225 < config["check"]["limits"]["grad_error_worst"] < 0.2517
    only = dict(tiny_config, check=dict(tiny_config["check"], limits={"grad_error_worst": 1e-3}))
    params, batches = seeded(tiny, 31, bias=0.3)
    key = check.sketch_key(31)
    want = ref.run_reference(tiny_config, params, batches, key)
    judged = check.compare(only, ref.run_reference(tiny_config, params, batches, key, fault="half_batch"), want)
    assert set(judged) == {"grad_error_worst"} and judged["grad_error_worst"][0] > 10 * judged["grad_error_worst"][1]
    assert check.compare(only, want, want)["grad_error_worst"][0] == 0


def test_the_bias_chooses_in_the_program_as_in_the_reference(tiny):
    """With a bias that is not zero (a run's is): it changes which experts
    a frame goes to, the program's unroll still gives the reference's
    values, and no gradient reaches it on either side."""
    from dotaclient_tpu.models import policy as P

    from dotaclient_tpu.env import featurizer as F

    cell, config, _, ref = tiny
    cfg = harness.learner_config(cell, 0, "mem://x").policy
    params, (rows,) = seeded(tiny, 5, n_steps=1, bias=0.3)
    plain, _ = seeded(tiny, 5, n_steps=1)
    obs = {k: jnp.asarray(rows[k]) for k in ("global_feats", "hero_feats", "unit_feats", "unit_mask",
                                             "target_mask", "action_mask")}

    def program(p):
        _, out = P.PolicyNet(cfg).apply(p, P.initial_state(cfg, (obs["unit_mask"].shape[0],)),
                                        F.Observation(**obs), unroll=True)
        return out

    def reference(p):
        return jax.vmap(lambda row: ref.forward_row(p, row, config)[4])(obs)

    np.testing.assert_allclose(program(params).value, reference(params), rtol=1e-4, atol=1e-5)
    assert np.abs(np.asarray(reference(params) - reference(plain))).max() > 1e-3  # the choice moved
    assert float(program(params).stats["moe_local_pairs"]) != float(program(plain).stats["moe_local_pairs"])
    for side in (lambda p: jnp.sum(program(p).value ** 2), lambda p: jnp.sum(reference(p) ** 2)):
        g = jax.grad(side)(params)["params"]["core"]["tf"]
        assert not np.asarray(g["block1"]["moe"]["router_bias"]).any()
        assert np.asarray(g["block1"]["moe"]["router"]).any()


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
    assert config["source"] == entry["source"] and "eight chips" in config["deployment"].lower()
    assert "first stage" in config["deployment"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "GLM-4.7-Flash")
        assert row["config"] == PUBLISHED and row["source_url"] == entry["source"]


def test_the_policy_section_is_the_published_sizes(config):
    pol = config["policy"]
    assert pol["arch"] == "transformer" and pol["dtype"] == "bfloat16"
    assert pol["lstm_hidden"] == config["hidden_size"] == 2048
    assert pol["tf_heads"] == config["num_attention_heads"] == config["num_key_value_heads"] == 20
    assert pol["tf_layer_kinds"] == "latent" and "tf_kv_heads" not in pol and "tf_head_dim" not in pol
    assert (pol["tf_q_lora_rank"], pol["tf_kv_lora_rank"], pol["tf_qk_nope_dim"], pol["tf_qk_rope_dim"],
            pol["tf_v_head_dim"]) == (config["q_lora_rank"], config["kv_lora_rank"], config["qk_nope_head_dim"],
                                      config["qk_rope_head_dim"], config["v_head_dim"]) == (768, 512, 192, 64, 256)
    assert pol["tf_layers"] == config["num_hidden_layers"] == 5
    assert pol["tf_dense_layers"] == config["first_k_dense_replace"] == 1
    assert (pol["tf_mlp_act"], pol["tf_mlp_hidden"]) == ("swiglu", config["intermediate_size"]) and config["hidden_act"] == "silu"
    assert pol["tf_rope_theta"] == config["rope_theta"] == 1000000 and config["rope_scaling"] is None
    assert config["partial_rotary_factor"] == 1 and "tf_yarn_factor" not in pol
    assert (pol["tf_norm"], pol["tf_norm_eps"]) == ("rmsnorm", config["rms_norm_eps"])
    assert pol["tf_bias"] is config["attention_bias"] is False and pol["tf_final_norm"] is True
    assert pol["moe_experts"] == config["published"]["n_routed_experts"] == 64  # the router's width
    assert pol["moe_experts_held"] == config["n_routed_experts"] == 8 and pol["moe_first_expert"] == 0
    assert pol["moe_top_k"] == config["num_experts_per_tok"] == 4
    assert pol["moe_hidden"] == config["moe_intermediate_size"] == 1536
    assert pol["moe_shared_hidden"] == config["n_shared_experts"] * config["moe_intermediate_size"] == 1536
    assert pol["moe_score"] == "sigmoid" and config["topk_method"] == "noaux_tc" and config["norm_topk_prob"] is True
    assert pol["moe_route_scale"] == config["routed_scaling_factor"] == 1.8
    assert config["n_group"] == config["topk_group"] == 1  # no group step to leave out
    assert pol["moe_standardize_router"] is True and config["assumed"]["standardize_router"]  # a stated departure
    assert config["num_nextn_predict_layers"] == 0 and "prediction" in config["assumed"]["absent"]
    assert config["learner"] == {"rows_per_chip": config["learner"]["rows_per_chip"], "seq_len": 4095,
                                 "publish_every": config["learner"]["publish_every"], "mesh_shape": "dp=-1"}
    assert pol["tf_context"] == config["learner"]["seq_len"] + 1 and pol["tf_remat"] is True
    mellum = cells.load_cell(cells.load_benchmark(), "learner-mellum2-ep4-wire")["config_data"]
    assert config["ppo"] == mellum["ppo"] and config["features"] == mellum["features"]
    for key in ("trunk_and_heads", "router", "rotary_pairing", "absent", "rows_per_chip", "publish_every",
                "ppo", "share", "dtype"):
        assert config["assumed"][key]
    assert set(config["check"]["limits"]) >= {"grad_error", "grad_norm_gap", "update_norm_gap"}
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
    assert ref.n_params(config) == 513_183_671
    size = lambda name: sum(x.size for x in jax.tree.leaves(program["params"]["core"]["tf"][name]))
    attention = 21_757_952 + 2048 + 768 + 512  # the five matrices, ln1 and the two inner norms
    assert size("block0") == attention + 2048 + 62_914_560  # ln2, the dense block
    # ln2, router and bias, the 8 held experts, the shared expert
    assert size("block1") == attention + 2048 + 2048 * 64 + 64 + 8 * 9_437_184 + 9_437_184
    # the weight frame's float32 values stay under 2**31 bytes
    assert 4 * ref.n_params(config) < 2**31
    rows = cfg.batch_size
    assert ref.train_step_flops(config, rows) == pytest.approx(train_step_flops(cfg), rel=1e-12)
    per_row = ref.forward_flops_per_row(config)
    frames_ = 4096
    per_frame = {k: v / frames_ / 1e6 for k, v in per_row.items()}
    assert per_frame["attn_latent"] == pytest.approx(5 * (43.5 + 42.0), rel=2e-3)
    assert per_frame["mlp"] == pytest.approx(125.8, rel=1e-3)
    assert per_frame["moe_shared"] == pytest.approx(4 * 18.9, rel=2e-3)
    assert per_frame["moe"] == pytest.approx(4 * (9.44 + 0.26), rel=2e-3)
    # latent attention is most of the work
    assert 0.63 < per_row["attn_latent"] / sum(per_row.values()) < 0.65
    costs = ref.scope_costs(config, rows)
    assert set(costs) <= set(cells.load_scopes(bench, CONFIG))
    assert set(costs) == {"attn_latent", "mlp", "moe", "moe_shared", "optimizer"}
    assert costs["optimizer"] == {"flops": 0.0, "bytes": 28.0 * 513_183_671}
    core = sum(costs[k]["flops"] for k in ("attn_latent", "mlp", "moe", "moe_shared"))
    assert core == pytest.approx(
        ref.train_step_flops(config, rows) - 3.0 * rows * (per_row["trunk"] + per_row["heads"]), rel=1e-12)
    # the held pairs of an even routing: half a pair a frame, an eighth of the whole layer's routed work
    whole = dict(config, policy=dict(config["policy"], moe_experts_held=64))
    moe, router = per_row["moe"], 4 * frames_ * 2.0 * 2048 * 64
    assert (ref.forward_flops_per_row(whole)["moe"] - router) == pytest.approx(8 * (moe - router))


def test_the_new_readers_and_their_entries(bench):
    new = ("attn_latent.ms", "attn_latent.roofline_pct", "mlp.ms", "moe_shared.ms", "moe_shared.roofline_pct")
    nothing = {"trace": None, "scope_costs": {}, "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    for name in new:
        assert cells.load_reader(bench, name)(nothing) is None  # the parent's program, an untraced run
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert all(entries[n]["workloads"] == [CELL] for n in new)
    assert all(entries[n]["moves"] == "env_steps_per_s" and entries[n]["layer"] == "device step" for n in new)
    reported = {m["name"] for m in cells.metrics_for(bench, CELL, "per_layer")}
    assert set(new) <= reported
    assert {"loss.ms", "optimizer.ms", "optimizer.roofline_pct", "step.unscoped_pct",
            "device.longest_gap_ms"} <= reported
    assert not {"moe.ms", "trunk.ms", "heads.ms", "attn_full.ms", "lstm.ms"} & reported
    # a scoped trace of this configuration's step reads through them
    trace = {"scope_self_s": {"attn_latent": 0.4, "mlp": 0.06, "moe_shared": 0.05, "": 0.01},
             "step_count": 2, "step_busy_s": 0.52}
    run = dict(nothing, trace=trace, scope_costs={"attn_latent": {"flops": 197e12 * 0.1, "bytes": 0.0},
                                                  "moe_shared": {"flops": 0.0, "bytes": 819e9 * 0.005}})
    read = {n: cells.load_reader(bench, n)(run) for n in new}
    assert read["attn_latent.ms"] == pytest.approx(200.0) and read["mlp.ms"] == pytest.approx(30.0)
    assert read["attn_latent.roofline_pct"] == pytest.approx(50.0)
    assert read["moe_shared.ms"] == pytest.approx(25.0) and read["moe_shared.roofline_pct"] == pytest.approx(20.0)
