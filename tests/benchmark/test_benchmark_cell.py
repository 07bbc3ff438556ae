"""One cell's command end to end on the CPU at a tiny size, the plain
reference against the program there, and the planted faults that have to
come out as not correct. The measuring command itself still fails
without a TPU."""

import io
import json
import os
import subprocess
import sys
import time
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import pytest

from bench_tiny import ROOT, make_tiny_root  # noqa: F401  (puts the root on the path)

from benchmark import cells, control, harness, run



@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("bench") / "root"))


def drive(root, workload="tiny", seed=7, trace=False, break_step=None, seconds=1.0):
    """A run past the look for a chip: everything else as `run.py` has it."""
    bench = cells.load_benchmark(root)
    cell = cells.load_cell(bench, workload, root)
    return harness.run_cell(bench, workload, seed, seconds, trace, time.time(),
                            jax.devices()[: int(cell["chips"])], root, break_step=break_step)


def test_command_prints_the_contract_last_line(tiny_root):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", "tiny", "--seed", str(2**31 + 11), "--seconds", "1.5",
                       "--trace", "0"], platform="cpu", root=tiny_root)
    assert rc == 0
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(last)[-1] == "check"
    assert last["correct"] is True
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"env_steps_per_s", "setup_s"}
    assert last["metrics"]["env_steps_per_s"]["value"] > 0
    assert last["metrics"]["env_steps_per_s"]["unit"] == "env-steps/s"
    assert last["metrics"]["setup_s"]["value"] > 0
    assert last["device"]["platform"] == "cpu" and last["device"]["count"] == 1
    for name, entry in last["check"].items():
        assert entry["value"] <= entry["limit"], name
    assert {"loss_gap_1", "loss_gap_2", "loss_gap_3", "grad_norm_gap", "update_norm_gap"} <= set(
        last["check"])


def test_measuring_command_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "learner-lstm4096-wire", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_same_seed_same_inputs_and_weights(tiny_root):
    from benchmark import frames, weights

    bench = cells.load_benchmark(tiny_root)
    cell = cells.load_cell(bench, "tiny", tiny_root)
    cfg, rows_spec = cell["config_data"], cell["traffic_data"]["rows"]
    wire = cells.load_module(bench, "wire", cell["traffic_data"]["frames"]["module"], tiny_root)
    shapes = cells.load_module(bench, "references", cfg["reference"], tiny_root).param_shapes(cfg)
    big = 2**31 + 5
    a = frames.make_rows(cfg, rows_spec, 4, big)
    b = frames.make_rows(cfg, rows_spec, 4, big)
    c = frames.make_rows(cfg, rows_spec, 4, big + 1)
    assert all((a[k] == b[k]).all() for k in a)
    assert not (a["unit_feats"] == c["unit_feats"]).all()
    assert wire.serialize_rows(a, {}) == wire.serialize_rows(b, {})
    wa, wb = weights.make_params(shapes, big), weights.make_params(shapes, big)
    wc = weights.make_params(shapes, big + 1)
    assert all(bool((x == y).all()) for x, y in zip(jax.tree.leaves(wa), jax.tree.leaves(wb)))
    assert not all(bool((x == y).all()) for x, y in zip(jax.tree.leaves(wa), jax.tree.leaves(wc)))


def test_frames_decode_to_the_rows_the_reference_is_given(tiny_root):
    from dotaclient_tpu.transport.serialize import deserialize_rollout

    from benchmark import frames

    bench = cells.load_benchmark(tiny_root)
    cell = cells.load_cell(bench, "tiny", tiny_root)
    wire = cells.load_module(bench, "wire", "dtr1", tiny_root)
    rows = frames.make_rows(cell["config_data"], cell["traffic_data"]["rows"], 3, 5)
    sent = wire.serialize_rows(rows, cell["traffic_data"]["frames"])
    r = deserialize_rollout(wire.stamp_version(sent[2], 77))
    assert r.version == 77 and r.actor_id == 2
    assert (r.obs.unit_feats == rows["unit_feats"][2]).all()
    assert (r.obs.target_mask == rows["target_mask"][2]).all()
    assert (r.actions.type == rows["type"][2]).all()
    assert (r.initial_state[1] == rows["h0"][2]).all()
    assert (r.behavior_logp == rows["behavior_logp"][2]).all()


def test_reference_agrees_with_the_program_at_a_tiny_size(tiny_root):
    """At float32 compute the program and the plain reference are the
    same mathematics: the gaps are rounding."""
    res = drive(tiny_root, seed=3)
    assert res["correct"] is True
    assert max(res["check"][f"loss_gap_{i}"]["value"] for i in (1, 2, 3)) < 1e-6
    assert res["check"]["grad_norm_gap"]["value"] < 1e-5
    assert res["check"]["grad_error"]["value"] < 1e-4
    assert res["check"]["update_norm_gap"]["value"] < 1e-5


def test_a_mix_with_a_generator_module_of_its_own_runs(tiny_root):
    """`wire-tiny-bursty` names `generators/tiny_bursty.py`, which only
    the temporary copy has: the harness finds it by name and runs it."""
    res = drive(tiny_root, workload="tiny-bursty", seed=5)
    assert res["correct"] is True and res["attempted"] > 0
    assert res["metrics"]["env_steps_per_s"]["value"] > 0


def test_bfloat16_compute_runs_and_stays_near_the_reference(tmp_path):
    root = make_tiny_root(str(tmp_path / "root"), dtype="bfloat16")
    res = drive(root, seed=3)
    assert max(res["check"][f"loss_gap_{i}"]["value"] for i in (1, 2, 3)) < 2e-3
    assert res["check"]["update_norm_gap"]["value"] < 0.1


def unchanged_state(inner):
    def step(state, batch):
        keep = jax.tree.map(jnp.copy, state)
        _, metrics = inner(state, batch)
        return keep, metrics

    return step


def half_batch(inner):
    def step(state, batch):
        half = batch.shape[0] // 2
        return inner(state, jnp.concatenate([batch[:half], batch[:half]]))

    return step


def no_exchange(inner):
    def step(state, batch):
        quarter = batch.shape[0] // 4
        alone = jnp.tile(batch[:quarter], (4, 1))
        return inner(state, jax.device_put(alone, batch.sharding))

    return step


@pytest.mark.parametrize("fault", [unchanged_state, half_batch], ids=lambda f: f.__name__)
def test_planted_fault_reads_not_correct(tiny_root, fault):
    res = drive(tiny_root, seed=11, break_step=fault)
    assert res["correct"] is False
    assert any(e["value"] > e["limit"] for e in res["check"].values())


def test_four_chip_cell_and_its_exchange_left_out(tiny_root):
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    ok = drive(tiny_root, workload="tiny-dp4", seed=13)
    assert ok["correct"] is True and ok["device"]["count"] == 4
    bad = drive(tiny_root, workload="tiny-dp4", seed=13, break_step=no_exchange)
    assert bad["correct"] is False


def test_control_in_lower_precision_reads_not_correct(tiny_root, capsys):
    """The control (the reference in the program's place, computed in
    the precision below the tiny configuration's float32: bfloat16) has to
    fail one of the cell's numbers at the tiny size's own limits, on
    three seeds."""
    rc = control.main(["--workload", "tiny", "--seeds", "21,22,23", "--variants", "bf16"],
                      platform="cpu", root=tiny_root)
    assert rc == 0  # every variant on every seed came out as not correct
    cell = cells.load_cell(cells.load_benchmark(tiny_root), "tiny", tiny_root)
    limits = cell["config_data"]["check"]["limits"]
    lines = [json.loads(ln) for ln in capsys.readouterr().out.strip().splitlines()]
    assert len(lines) == 3
    for line in lines:
        got = line["bf16"]
        assert got["verdict"] == "not correct" and got["failed"], line
        assert all(got["numbers"][k] > limits[k] for k in got["failed"])


def test_a_control_that_passes_is_reported(tiny_root, capsys):
    """The control's own verdict is a judgement, not a print-out: with
    every operand left as it is (`quant` the identity) it reads correct,
    and the exit code says so."""
    control.VARIANTS["same"] = dict(quant=lambda x: x)
    try:
        rc = control.main(["--workload", "tiny", "--seeds", "21", "--variants", "same"],
                          platform="cpu", root=tiny_root)
    finally:
        del control.VARIANTS["same"]
    assert rc == 1
    assert json.loads(capsys.readouterr().out.strip())["same"]["verdict"] == "CORRECT"
