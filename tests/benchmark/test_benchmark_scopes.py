"""Device time by scope: the reduction on a recorded trace worked out by
hand (data/scoped_trace.json, times in microseconds times 1,000), the
readers that take their metrics from it, the counting functions behind the
two roofline shares, and a configuration of another family that brings its
own scopes and reader by adding files alone."""

import json
import os

import jax
import pytest

from bench_tiny import ROOT, make_tiny_root, snapshot  # noqa: F401  (puts the root on the path)

from benchmark import cells, flops, harness, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1e-6
SCOPES = ["unpack", "loss", "trunk", "lstm", "heads", "optimizer"]
NEW = ("trunk.ms", "lstm.ms", "heads.ms", "loss.ms", "optimizer.ms", "step.unscoped_pct",
       "lstm.roofline_pct", "optimizer.roofline_pct", "device.longest_gap_ms")


def recorded(name="scoped_trace.json"):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(recorded(), chips=2, scopes=SCOPES)


def as_run(trace, costs=None):
    return {"trace": trace, "chips": 2, "scope_costs": costs or {},
            "device": {"platform": "tpu", "kind": "TPU v5 lite"}}


def test_a_while_counts_its_body_once():
    # chip 0, first step: the while runs [1500,4500] and spans fusion.2
    # [1600,2600] and fusion.3 [2700,4200]: 3000 - 1000 - 1500 = 500 its own
    ops = recorded()["planes"][0]["lines"][1]["events"][:7]
    own = dict(zip((trace_reduce.short_name(e[0]) for e in ops), trace_reduce.self_times(ops)))
    assert own["while.1"] == 500_000
    assert own["fusion.2"] == 1_000_000 and own["fusion.3"] == 1_500_000
    assert sum(own.values()) == trace_reduce.length(trace_reduce.union(trace_reduce._intervals(ops)))


def test_self_times_of_operations_that_overlap_without_nesting():
    # the older recorded trace: all-reduce.7 [2500,3500] and fusion.2
    # [3000,5000] overlap by 500: the later start is the innermost
    ops = recorded("small_trace.json")["planes"][0]["lines"][1]["events"][:3]
    assert trace_reduce.self_times(ops).tolist() == [1_500_000, 500_000, 2_000_000]


def test_the_innermost_declared_scope_of_a_path():
    path = "jit(fused_fn)/loss/transpose(jvp(PolicyNet))/core/lstm/lstm/while/body/closed_call/mul:"
    assert trace_reduce.scope_of(path, SCOPES) == "lstm"
    assert trace_reduce.scope_of("jit(fused_fn)/loss/jvp(jit(take_along_axis))/gather:", SCOPES) == "loss"
    assert trace_reduce.scope_of("jit(fused_fn)/loss/jvp(PolicyNet)/core/trunk/trunk/dot_general:", SCOPES) == "trunk"
    # the last component is the primitive, whatever it is called
    assert trace_reduce.scope_of("jit(fused_fn)/loss/lstm:", SCOPES) == "loss"
    assert trace_reduce.scope_of("jit(flat_fn)/concatenate:", SCOPES) == ""
    assert trace_reduce.scope_of(None, SCOPES) == "" and trace_reduce.scope_of("", SCOPES) == ""
    assert trace_reduce.scope_of(path, ["heads"]) == ""


def test_self_time_by_scope_over_the_steps_averaged_over_chips(reduced):
    # chip 0, two steps of 5000: trunk 500 + 500; lstm (500 + 1000 + 1500)
    # x 2; loss alone 500 + 500 + 300 (fusion.8's primitive is called lstm);
    # copy.5 has no op_name: 200; optimizer 700 + 700; fusion.9 runs in the
    # flatten program and counts nowhere. chip 1, two steps of 4000: trunk
    # 400 x 2, lstm (400 + 2000) x 2, optimizer 1200 x 2.
    got = reduced["scope_self_s"]
    assert got["trunk"] == pytest.approx((1000 + 800) / 2 * US)
    assert got["lstm"] == pytest.approx((6000 + 4800) / 2 * US)
    assert got["loss"] == pytest.approx((1300 + 0) / 2 * US)
    assert got["optimizer"] == pytest.approx((1400 + 2400) / 2 * US)
    assert got[""] == pytest.approx((200 + 0) / 2 * US)
    assert got["heads"] == 0 and got["unpack"] == 0
    assert set(got) == {"", *SCOPES}
    # the sum against the step program's own time: chip 0 has 100 of its
    # 10,000 in which no operation ran, chip 1 none of its 8,000
    assert reduced["step_busy_s"] == pytest.approx(9000 * US) and reduced["step_count"] == 2
    assert sum(got.values()) == pytest.approx(8950 * US)


def test_the_readers_of_the_scope_table(bench, reduced):
    run = as_run(reduced, {"lstm": {"flops": 197e12 * 1350 * US, "bytes": 819e9 * 100 * US},
                           "optimizer": {"flops": 0.0, "bytes": 819e9 * 760 * US}})
    read = {n: cells.load_reader(bench, n)(run) for n in NEW}
    assert read["trunk.ms"] == pytest.approx(0.45) and read["lstm.ms"] == pytest.approx(2.7)
    assert read["loss.ms"] == pytest.approx(0.325) and read["optimizer.ms"] == pytest.approx(0.95)
    assert read["heads.ms"] == 0.0
    # everything of the step that no declared scope carries: 9000 - 8850
    assert read["step.unscoped_pct"] == pytest.approx(100 * 150 / 9000)
    # the larger of operations over peak (1350 us) and bytes over peak (100 us), a step
    assert read["lstm.roofline_pct"] == pytest.approx(100 * 1350 / 2700)
    assert read["optimizer.roofline_pct"] == pytest.approx(100 * 760 / 950)
    # device 0 idles [500,1000] [5900,6500] [7000,8000] [13000,13500]
    assert read["device.longest_gap_ms"] == pytest.approx(1.0)
    assert reduced["longest_gaps"][0] == [pytest.approx(6500 * US), pytest.approx(1000 * US),
                                          "python3:loop.sync"]
    assert [g[1] for g in reduced["longest_gaps"]] == pytest.approx([1000 * US, 600 * US, 500 * US, 500 * US])
    ms = sum(read[n] for n in ("trunk.ms", "lstm.ms", "heads.ms", "loss.ms", "optimizer.ms"))
    assert ms + read["step.unscoped_pct"] / 100 * 4.5 == pytest.approx(4.5)  # step.device_ms


@pytest.mark.parametrize("why", ["no_trace", "no_scopes_declared", "under_nine_tenths",
                                 "three_element_events"])
def test_readers_return_nothing_rather_than_a_wrong_split(bench, why):
    if why == "no_trace":
        trace = None
    elif why == "no_scopes_declared":
        trace = trace_reduce.reduce(recorded(), chips=2)
    elif why == "under_nine_tenths":
        # trunk and optimizer carry 900 + 1900 of 9000: an executable whose
        # names are another tree's reads like this
        trace = trace_reduce.reduce(recorded(), chips=2, scopes=["trunk", "optimizer"])
    else:
        trace = trace_reduce.reduce(recorded("small_trace.json"), chips=2, scopes=SCOPES)
    if trace is not None:
        assert trace["scope_self_s"] is None
    run = as_run(trace, {"lstm": {"flops": 1e9, "bytes": 1e6}, "optimizer": {"flops": 0.0, "bytes": 1e6}})
    for name in NEW[:-1]:
        assert cells.load_reader(bench, name)(run) is None, name
    assert (cells.load_reader(bench, "device.longest_gap_ms")(run) is None) == (trace is None)


def test_just_over_nine_tenths_gives_the_table():
    got = trace_reduce.reduce(recorded(), chips=2, scopes=["trunk", "lstm", "optimizer"])
    assert got["scope_self_s"] is not None  # 900 + 5400 + 1900 = 8200 of 9000
    assert got["scope_self_s"][""] == pytest.approx((100 + 650) * US)  # loss alone joins the unscoped


def test_a_share_of_a_roofline_is_a_chip_number(bench, reduced):
    run = as_run(reduced, {"lstm": {"flops": 1e9, "bytes": 1e6}})
    run["device"] = {"platform": "cpu", "kind": "cpu"}
    assert cells.load_reader(bench, "lstm.roofline_pct")(run) is None
    assert cells.load_reader(bench, "device.longest_gap_ms")(run) is None
    run["device"] = {"platform": "tpu", "kind": "TPU v99"}
    with pytest.raises(ValueError, match="TPU v99"):
        cells.load_reader(bench, "lstm.roofline_pct")(run)
    run["device"]["kind"] = "TPU v5 lite"
    assert cells.load_reader(bench, "optimizer.roofline_pct")(run) is None  # the reference counts no such scope


def test_the_old_form_of_a_trace_reduces_to_its_old_numbers(reduced):
    """Events of three elements (data/small_trace.json, which
    test_benchmark_trace.py works out by hand) and the same events with
    paths give every number that was there before the same."""
    old = trace_reduce.reduce(recorded("small_trace.json"), chips=2)
    with_paths = recorded("small_trace.json")
    for plane in with_paths["planes"]:
        for ln in plane["lines"]:
            if ln["name"] == trace_reduce.OPS_LINE:
                ln["events"] = [e + ["jit(fused_fn)/loss/mul:"] for e in ln["events"]]
    new = trace_reduce.reduce(with_paths, chips=2, scopes=SCOPES)
    for key in ("chips_traced", "window_s", "busy_s", "step_name", "step_busy_s", "step_count",
                "allreduce_exposed_s", "breakdown", "longest_gaps"):
        assert old[key] == new[key], key
    assert old["scope_self_s"] is None
    # chip 0's two steps are busy [1000,5000] and [7000,11000], chip 1's [1000,3000], [7000,10000]
    assert new["scope_self_s"]["loss"] == pytest.approx(6500 * US)
    # the window span cuts the paths' events as it cut the others
    with_paths["planes"][2]["lines"][0]["events"].append(
        [trace_reduce.WINDOW_SPAN, 2000 * 1000, 8000 * 1000])
    cut = trace_reduce.reduce(with_paths, chips=2, scopes=SCOPES)
    assert cut["step_count"] == 0.5 and cut["scope_self_s"]["loss"] == pytest.approx(1500 * US)


def test_the_scope_file_of_each_configuration(bench):
    for c in bench["configs"]:
        scopes = cells.load_scopes(bench, c["name"])
        assert scopes and len(set(scopes)) == len(scopes) and "" not in scopes
        assert all("/" not in s and ":" not in s for s in scopes)
    assert cells.load_scopes(bench, "lstm4096-openai-five") == SCOPES
    assert cells.load_scopes(bench, "no-such-configuration") is None


def test_the_new_entries(bench):
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(NEW) <= set(entries)
    for n in NEW:
        assert entries[n]["source"] == "device_trace" and entries[n]["moves"] == "env_steps_per_s"
        assert entries[n]["layer"] == ("device" if n.startswith("device.") else "device step")
    # the scopes that only the LSTM family's core names list its cell; what
    # parallel/train_step.py names for every family lists none
    assert {n for n in NEW if "workloads" in entries[n]} == {
        "trunk.ms", "lstm.ms", "heads.ms", "lstm.roofline_pct"}
    assert all(n.endswith("_roofline") or n.endswith(".roofline_pct")
               for n in entries if "roofline" in n)


def test_the_counts_behind_the_roofline_shares(bench):
    """The LSTM layer's operations are its share of `train_step_flops`,
    and Adam's bytes are 28 a parameter of the program's own tree."""
    from dotaclient_tpu.models.policy import init_params

    cell = cells.load_cell(bench, "learner-lstm4096-wire")
    config = cell["config_data"]
    ref = cells.load_module(bench, "references", config["reference"])
    rows, frames, H = 512, 512 * 17, 4096
    costs = ref.scope_costs(config, rows)
    assert set(costs) <= set(cells.load_scopes(bench, cell["config"]))
    per_frame = 2.0 * (2.0 * H * 4 * H)  # the input product and the recurrent one
    assert costs["lstm"]["flops"] == 3.0 * frames * per_frame
    rest = ref.forward_flops_per_frame(config) - per_frame
    assert costs["lstm"]["flops"] == pytest.approx(
        ref.train_step_flops(config, rows) - 3.0 * frames * rest, rel=1e-12)
    assert 0.98 < costs["lstm"]["flops"] / ref.train_step_flops(config, rows) < 0.99  # 7.009 of 7.146 TFLOP
    # two matrices and a bias read and their gradients written in float32,
    # four passes over [frames, H] in bfloat16
    assert costs["lstm"]["bytes"] == 8.0 * (2 * H * 4 * H + 4 * H) + 4.0 * frames * H * 2
    cfg = harness.learner_config(cell, seed=0, broker_url="mem://x")
    program = jax.eval_shape(lambda key: init_params(cfg.policy, key), jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(program))
    assert ref.n_params(config) == n == 136_584_631
    assert costs["optimizer"] == {"flops": 0.0, "bytes": 28.0 * n}
    # operations bound the LSTM layer, bytes the optimizer
    assert flops.roofline_s("TPU v5 lite", costs["lstm"]) == costs["lstm"]["flops"] / 197e12
    assert flops.roofline_s("TPU v5 lite", costs["optimizer"]) == costs["optimizer"]["bytes"] / 819e9
    half = ref.scope_costs(config, rows // 2)
    assert half["lstm"]["flops"] == costs["lstm"]["flops"] / 2
    assert half["optimizer"] == costs["optimizer"]  # every chip of a data-parallel step runs all of Adam


TF_REFERENCE = '''"""A reference a later family might bring: the functions the harness asks
a reference for, and the counts of its own scopes."""


def param_shapes(config):
    d = int(config["policy"]["lstm_hidden"])
    return {"params": {"core": {"attention": {"qkv": (d, 3 * d)}}}}


def train_step_flops(config, rows):
    return 3.0 * rows * (int(config["learner"]["seq_len"]) + 1) * 2.0 * 3 * int(config["policy"]["lstm_hidden"]) ** 2


def run_reference(config, params0, batches, key, quant=None, fault=None, shardings=None):
    raise NotImplementedError


def scope_costs(config, rows):
    return {"attention": {"flops": train_step_flops(config, rows), "bytes": 0.0}}
'''

TF_READER = '''"""Device step: self time in the scope `attention`, ms a step."""

from benchmark import trace_reduce


def read(run):
    return trace_reduce.scope_ms(run["trace"], "attention")
'''


def test_another_family_adds_files_and_edits_nothing(tmp_path):
    """A configuration with `arch` "transformer" and rows of 64 steps is
    found by name, loaded and configures the learner; its scope file and a
    reader file make a per-scope metric of its own. Nothing that was there,
    `trace_reduce.py` and `harness.py` among it, is edited."""
    from dotaclient_tpu.models.policy import init_params

    root = make_tiny_root(str(tmp_path / "root"))
    before = snapshot(root)
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "configs", "lstm64-tiny.json")) as f:
        cfg = json.load(f)
    cfg["name"], cfg["reference"] = "tf32-tiny", "tiny_tf"
    del cfg["policy"]["lstm_layers"], cfg["program_constants"]["policy.lstm_layers"]
    cfg["policy"].update(arch="transformer", lstm_hidden=32, tf_layers=2, tf_heads=2, tf_context=65)
    cfg["learner"].update(seq_len=64, rows_per_chip=8)
    added = {
        os.path.join(b, "configs", "tf32-tiny.json"): json.dumps(cfg),
        os.path.join(b, "references", "tiny_tf.py"): TF_REFERENCE,
        os.path.join(b, "scopes", "tf32-tiny.json"): json.dumps(
            {"config": "tf32-tiny", "scopes": ["trunk", "attention", "optimizer"]}),
        os.path.join(b, "metrics", "tiny.attention_ms.py"): TF_READER,
    }
    for path, text in added.items():
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(text)
    bench = cells.load_benchmark(root)
    bench["configs"].append({"name": "tf32-tiny", "source": "test", "reduced": [],
                             "file": "benchmark/configs/tf32-tiny.json", "why": "test"})
    bench["workloads"].append({"name": "tiny-tf", "config": "tf32-tiny", "traffic": "wire-tiny",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "tiny.attention_ms", "unit": "ms", "better": "lower",
                               "source": "device_trace", "layer": "device step",
                               "moves": "env_steps_per_s", "workloads": ["tiny-tf"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = snapshot(root)
    assert [k for k in before if k != "BENCHMARK.json" and after[k] != before[k]] == []

    cell = cells.load_cell(bench, "tiny-tf", root)
    assert cell["config_data"]["policy"]["arch"] == "transformer"
    harness.check_features(cell["config_data"])
    lc = harness.learner_config(cell, seed=2**31 + 7, broker_url="mem://x")
    assert (lc.policy.arch, lc.policy.tf_layers, lc.policy.tf_heads, lc.policy.tf_context) == (
        "transformer", 2, 2, 65)
    assert (lc.seq_len, lc.batch_size, lc.policy.lstm_hidden) == (64, 8, 32)
    # the program takes it: the transformer family's own parameter tree
    shapes = jax.eval_shape(lambda key: init_params(lc.policy, key), jax.random.PRNGKey(0))
    core = shapes["params"]["core"]
    assert set(core["tf"]) == {"block0", "block1"} and "lstm" not in core
    assert core["tf"]["block0"]["qkv"]["kernel"].shape == (32, 96)
    ref = cells.load_module(bench, "references", cell["config_data"]["reference"], root)
    assert ref.scope_costs(cell["config_data"], 8)["attention"]["flops"] == ref.train_step_flops(
        cell["config_data"], 8)

    scopes = cells.load_scopes(bench, cell["config"], root)
    assert scopes == ["trunk", "attention", "optimizer"]
    events = json.loads(json.dumps(recorded()).replace("lstm", "attention"))
    trace = trace_reduce.reduce(events, chips=2, scopes=scopes)
    names = [m["name"] for m in cells.metrics_for(bench, "tiny-tf", "per_layer")]
    assert "tiny.attention_ms" in names and "lstm.ms" not in names and "optimizer.ms" in names
    run = as_run(trace)
    assert cells.load_reader(bench, "tiny.attention_ms", root)(run) == pytest.approx(2.7)
    assert cells.load_reader(bench, "optimizer.ms", root)(run) == pytest.approx(0.95)
    assert cells.load_reader(bench, "lstm.ms", root)(run) is None  # not a scope of this configuration
