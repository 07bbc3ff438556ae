"""The routed-expert, sliding-window family through the benchmark: the
program against `references/moe_swa_ppo.py` through the harness on a tiny
cell on the CPU, the planted faults that have to come out as not correct,
the configuration's file against the published config key for key, and
the counts behind the new rooflines."""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from bench_tiny import ROOT, make_tiny_root  # noqa: F401  (puts the root on the path)

from benchmark import cells, check, frames, harness

CONFIG, CELL = "mellum2-12b-a2.5b-ep4", "learner-mellum2-ep4-wire"

# The `config` of the catalog row Mellum2-12B-A2.5B-Instruct (its source_url
# is the configuration's `source`), copied here so that the test needs no
# file outside the repository; where the catalog is at hand it is compared.
PERIOD = ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"]
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2304,
    "intermediate_size": 7168, "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                           "original_max_position_embeddings": 8192, "beta_fast": 32,
                           "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False, "vocab_size": 98304,
    "use_sliding_window": True,
}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


@pytest.fixture(scope="module")
def config(bench):
    return cells.load_cell(bench, CELL)["config_data"]


def make_moe_root(dst: str, dtype: str = "float32") -> str:
    """The tiny root with one more configuration, the shipped one cut to
    a toy's widths (tests only: a cell never cuts a width), and its cell."""
    root = make_tiny_root(dst)
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg["name"] = "moe-swa-tiny"
    cfg["policy"].update(
        lstm_hidden=32, unit_embed_dim=32, mlp_hidden=32, dtype=dtype, tf_heads=4, tf_kv_heads=2,
        tf_head_dim=8, tf_window=5, tf_yarn_original_context=8, tf_context=12, tf_attn_block=4,
        moe_experts=8, moe_experts_held=4, moe_first_expert=2, moe_top_k=3, moe_hidden=12)
    cfg["learner"].update(rows_per_chip=8, seq_len=11, publish_every=4)
    cfg["ppo"].update(max_staleness=12)
    # float32 compute on the CPU: the program and the reference are the same
    # mathematics and read 1e-6 apart or less; each planted fault reads above
    # 1e-2 on grad_error (test_planted_faults below).
    cfg["check"]["limits"] = {"loss_gap_1": 1e-5, "grad_norm_gap": 1e-3, "grad_error": 1e-3,
                               "update_norm_gap": 1e-3}
    with open(os.path.join(b, "configs", "moe-swa-tiny.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "scopes", CONFIG + ".json")) as f:
        scopes = json.load(f)
    with open(os.path.join(b, "scopes", "moe-swa-tiny.json"), "w") as f:
        json.dump(dict(scopes, config="moe-swa-tiny"), f)
    bench = cells.load_benchmark(root)
    bench["configs"].append({"name": "moe-swa-tiny", "source": "test", "reduced": [],
                             "file": "benchmark/configs/moe-swa-tiny.json", "why": "test"})
    bench["workloads"].append({"name": "tiny-moe", "config": "moe-swa-tiny", "traffic": "wire-tiny",
                               "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-moe")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    return make_moe_root(str(tmp_path_factory.mktemp("bench") / "root"))


def drive(root, seed, break_step=None, seconds=1.0):
    bench = cells.load_benchmark(root)
    return harness.run_cell(bench, "tiny-moe", seed, seconds, False, time.time(),
                            jax.devices()[:1], root, break_step=break_step)


def test_the_program_agrees_with_the_reference_at_a_tiny_size(moe_root):
    res = drive(moe_root, seed=2**31 + 3)
    assert res["correct"] is True and res["attempted"] > 0
    assert res["check"]["loss_gap_1"]["value"] < 1e-5
    assert res["check"]["grad_error"]["value"] < 1e-4
    assert res["check"]["grad_norm_gap"]["value"] < 1e-4
    assert res["check"]["update_norm_gap"]["value"] < 1e-4


def sliding_as_full(root):
    """The step of the same learner with every layer run as a full one."""
    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.parallel.train_step import build_single_train_step

    def fault(inner):
        cell = cells.load_cell(cells.load_benchmark(root), "tiny-moe", root)
        cfg = harness.learner_config(cell, 0, "mem://x")
        cfg = dataclasses.replace(cfg, policy=dataclasses.replace(cfg.policy, tf_layer_kinds="full"))
        step, _, _ = build_single_train_step(cfg, mesh_lib.make_mesh(cfg.mesh_shape, jax.devices()[:1]))
        return step

    return fault


def expert_left_out(inner):
    """The first held expert's part left out of every layer's sum: its down
    projection is zero while the step runs, and what the step did to it is
    put back on the weights it had."""
    def step(state, batch):
        layers = lambda p: [b["moe"] for n, b in p["params"]["core"]["tf"].items() if n != "ln_f"]
        kept = [m["w_down"] for m in layers(state.params)]
        params = jax.tree.map(lambda x: x, state.params)
        for m in layers(params):
            m["w_down"] = m["w_down"].at[:, 0, :].set(0.0)
        new, metrics = inner(state._replace(params=params), batch)
        for m, w in zip(layers(new.params), kept):
            m["w_down"] = m["w_down"].at[:, 0, :].add(w[:, 0, :])
        return new, metrics

    return step


@pytest.mark.parametrize("fault", ["sliding_as_full", "expert_left_out"])
def test_planted_faults_read_not_correct(moe_root, fault):
    plant = sliding_as_full(moe_root) if fault == "sliding_as_full" else expert_left_out
    res = drive(moe_root, seed=2**31 + 11, break_step=plant)
    assert res["correct"] is False
    assert res["check"]["grad_error"]["value"] > 10 * res["check"]["grad_error"]["limit"]


@pytest.mark.parametrize("fault", ["sliding_as_full", "expert_left_out", "half_batch"])
def test_the_references_own_faults_read_not_correct(moe_root, fault):
    """What `control.py` runs on the chip: the reference with a fault
    planted in it, in the program's place, against the reference."""
    bench = cells.load_benchmark(moe_root)
    cfg = cells.load_cell(bench, "tiny-moe", moe_root)
    config, traffic = cfg["config_data"], cfg["traffic_data"]
    rows = frames.make_rows(config, traffic["rows"], 3 * 8, 31)
    want = check.reference_readings(config, 31, rows, 3, bench=bench, root=moe_root)
    got = check.reference_readings(config, 31, rows, 3, fault=fault, bench=bench, root=moe_root)
    judged = check.compare(config, got, want)
    assert any(v > lim for v, lim in judged.values()), judged
    same = check.compare(config, want, want)
    assert all(v == 0 for v, _ in same.values())


def test_the_configuration_keeps_every_published_width(bench, config):
    """Key for key against the published config: every key is there and
    equal, except the two that BENCHMARK.json lists as reduced, which the
    file states beside their published values."""
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts"]
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value and config[key] < value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"]) == (4, 16)
    assert config["source"] == entry["source"] and "four chips" in config["deployment"].lower()
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert row["config"] == PUBLISHED and row["source_url"] == entry["source"]


def test_the_policy_section_is_the_published_sizes(config):
    pol, rope = config["policy"], config["rope_parameters"]
    assert pol["arch"] == "transformer" and pol["dtype"] == "bfloat16"
    assert pol["lstm_hidden"] == config["hidden_size"] == 2304
    assert (pol["tf_heads"], pol["tf_kv_heads"], pol["tf_head_dim"]) == (
        config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]) == (32, 4, 128)
    assert pol["tf_layers"] == config["num_hidden_layers"] == 4
    kinds = [k + "_attention" for k in pol["tf_layer_kinds"].split(",")]
    assert kinds == config["layer_types"][:4] == PERIOD
    assert set(config["mlp_layer_types"]) == {"sparse"}
    assert pol["tf_window"] == config["sliding_window"] == 1024
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    assert pol["tf_rope_theta"] == full["rope_theta"] == sliding["rope_theta"] == 500000
    assert (pol["tf_yarn_factor"], pol["tf_yarn_original_context"], pol["tf_yarn_beta_fast"],
            pol["tf_yarn_beta_slow"]) == (full["factor"], full["original_max_position_embeddings"],
                                          full["beta_fast"], full["beta_slow"])
    assert (pol["tf_norm"], pol["tf_norm_eps"]) == ("rmsnorm", config["rms_norm_eps"])
    assert pol["tf_bias"] is config["attention_bias"] is False and pol["tf_final_norm"] is True
    assert pol["moe_standardize_router"] is True and config["assumed"]["standardize_router"]  # a stated departure
    assert pol["moe_experts"] == config["published"]["num_experts"] == 64
    assert pol["moe_experts_held"] == config["num_experts"] == 16
    assert pol["moe_top_k"] == config["num_experts_per_tok"] == 8
    assert pol["moe_hidden"] == config["moe_intermediate_size"] == 896
    assert config["learner"] == {"rows_per_chip": config["learner"]["rows_per_chip"], "seq_len": 4095,
                                 "publish_every": config["learner"]["publish_every"],
                                 "mesh_shape": "dp=-1"}
    assert pol["tf_context"] == config["learner"]["seq_len"] + 1 and pol["tf_remat"] is True
    for key in ("trunk_and_heads", "router", "absent", "rows_per_chip", "publish_every", "ppo", "share"):
        assert config["assumed"][key]
    assert set(config["check"]["limits"]) >= {"grad_error", "grad_norm_gap", "update_norm_gap"}
    # the YaRN factor the published config states is the table's own
    from dotaclient_tpu.ops.attention import rope_table

    assert rope_table(128, 500000.0, 16.0, 8192)[1] == pytest.approx(full["attention_factor"], rel=1e-12)


def test_the_programs_tree_and_counts_are_the_references(bench, config):
    from dotaclient_tpu.models.policy import init_params
    from dotaclient_tpu.ops.flops import train_step_flops

    cell = cells.load_cell(bench, CELL)
    ref = cells.load_module(bench, "references", config["reference"])
    cfg = harness.learner_config(cell, seed=0, broker_url="mem://x")
    program = jax.eval_shape(lambda key: init_params(cfg.policy, key), jax.random.PRNGKey(0))
    shapes = jax.tree.map(lambda x: tuple(x.shape), program)
    assert shapes == ref.param_shapes(config)
    assert ref.n_params(config) == 483_239_607
    layer = sum(x.size for x in jax.tree.leaves(program["params"]["core"]["tf"]["block0"]))
    assert layer == 21_233_664 + 147_456 + 4_608 + 16 * 6_193_152  # attention, router, norms, experts
    rows = cfg.batch_size
    assert ref.train_step_flops(config, rows) == pytest.approx(train_step_flops(cfg), rel=1e-12)
    per_row = ref.forward_flops_per_row(config)
    frames_ = 4096
    assert sum(per_row.values()) / frames_ == pytest.approx(351.0e6, rel=2e-3)
    # a sliding layer keeps 896 keys a query on average, a full one 2,048
    assert ref.attended_pairs(frames_, 1024) / frames_ == pytest.approx(896, rel=2e-3)
    assert ref.attended_pairs(frames_) / frames_ == 2048.5
    costs = ref.scope_costs(config, rows)
    assert set(costs) <= set(cells.load_scopes(bench, CONFIG))
    assert set(costs) == {"attn_window", "attn_full", "moe", "optimizer"}
    assert costs["optimizer"] == {"flops": 0.0, "bytes": 28.0 * 483_239_607}
    core = sum(costs[k]["flops"] for k in ("attn_window", "attn_full", "moe"))
    assert core == pytest.approx(
        ref.train_step_flops(config, rows) - 3.0 * rows * (per_row["trunk"] + per_row["heads"]), rel=1e-12)
    assert costs["attn_window"]["flops"] / 3 < costs["attn_full"]["flops"]  # a layer of each kind
    # the held pairs of an even routing: 2 a frame, so a quarter of the whole layer's expert work
    whole = dict(config, policy=dict(config["policy"], moe_experts_held=64))
    moe, router = per_row["moe"], 4 * frames_ * 2.0 * 2304 * 64
    assert (ref.forward_flops_per_row(whole)["moe"] - router) == pytest.approx(4 * (moe - router))


def test_the_counters_readers(bench):
    run = {"syncs": [(0.0, 10, {"moe_load_max_over_mean": 1.1, "moe_local_pairs": 131072.0}),
                     (1.0, 20, {"moe_load_max_over_mean": 1.3, "moe_local_pairs": 131072.0 * 1.02})],
           "rows_per_step": 4,
           "config": {"policy": {"moe_top_k": 8, "tf_layers": 4}, "learner": {"seq_len": 4095}}}
    assert cells.load_reader(bench, "moe.load_max_over_mean")(run) == pytest.approx(1.2)
    assert cells.load_reader(bench, "moe.local_pairs_pct")(run) == pytest.approx(25.25)
    # the parent's program has no such counter, an LSTM cell no such layer: nothing, and no error
    other = dict(run, syncs=[(0.0, 10, {"loss": 1.0})])
    lstm = dict(run, config={"policy": {"lstm_hidden": 4096}, "learner": {"seq_len": 16}})
    for name in ("moe.load_max_over_mean", "moe.local_pairs_pct"):
        assert cells.load_reader(bench, name)(other) is None
    assert cells.load_reader(bench, "moe.local_pairs_pct")(lstm) is None
    for name in ("attn_window.ms", "attn_full.ms", "moe.ms", "attn_window.roofline_pct",
                 "attn_full.roofline_pct", "moe.roofline_pct"):
        assert cells.load_reader(bench, name)({"trace": None, "scope_costs": {},
                                               "device": {"platform": "tpu", "kind": "TPU v5 lite"}}) is None
    entries = {m["name"]: m for m in bench["per_layer"]}
    new = [n for n in entries if n.split(".")[0] in ("attn_window", "attn_full", "moe")]
    assert len(new) == 8 and all(CELL in entries[n]["workloads"] for n in new)
    # attention of this configuration's kinds is its own; the expert layer is every transformer cell's
    assert all(entries[n]["workloads"] == [CELL] for n in new if not n.startswith("moe."))
    assert all({"learner-glm47flash-ep8-wire", "learner-qwen3next-ep32-wire"} <= set(entries[n]["workloads"])
               for n in new if n.startswith("moe."))
    assert all(entries[n]["moves"] == "env_steps_per_s" and entries[n]["layer"] == "device step"
               for n in new)
    # the family's own trunk and heads, which its scope file declares
    reported = {m["name"] for m in cells.metrics_for(bench, CELL, "per_layer")}
    assert {"trunk.ms", "heads.ms"} <= reported
    assert {"trunk", "heads"} <= set(cells.load_scopes(bench, CONFIG))
