"""BENCHMARK.json keeps to the contract's form, every cell's files are
found by name, and a later PR can add a configuration, a traffic mix, a
metric and a cell without editing a file that is there."""

import json
import os
import re

import pytest

from bench_tiny import ROOT, make_tiny_root  # noqa: F401  (puts the root on the path)

from benchmark import cells


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


def line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16 and all(PATH.match(p) for p in bench["paths"])
    assert 1 <= len(bench["command"]) <= 32 and all(line(w) for w in bench["command"])
    assert not any(w.startswith("/") or ".." in w for w in bench["command"])


def test_names_and_units(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    metric_names = [e["name"] for g in ("end_to_end", "per_layer") for e in bench[g]]
    assert len(metric_names) == len(set(metric_names))
    for e in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(e["unit"]), e["unit"]
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES


def test_entries_have_just_the_keys_shown(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert line(c["source"]) and line(c["why"]) and len(c["reduced"]) <= 16
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
    assert len({c["file"] for c in bench["configs"]}) == len(bench["configs"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line(w["why"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)
    cells_ = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and line(m["layer"])
        assert set(m.get("workloads", [])) <= cells_


def test_mfu_beside_the_step(bench):
    assert any("mfu" in re.split(r"[._\-]", m["name"]) for m in bench["per_layer"])


def test_every_cell_reports_what_it_must(bench):
    for w in bench["workloads"]:
        e2e = [m["name"] for m in cells.metrics_for(bench, w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cells.metrics_for(bench, w["name"], "per_layer")


def test_files_are_found_by_name(bench):
    for w in bench["workloads"]:
        cell = cells.load_cell(bench, w["name"])
        cfg = cell["config_data"]
        assert cfg["name"] == w["config"]
        assert cell["traffic_data"]["name"] == w["traffic"]
        assert cfg["source"] and cfg["deployment"] and cfg["assumed"]
        assert cfg["ppo"]["max_staleness"] == 3 * cfg["learner"]["publish_every"]
        traffic = cell["traffic_data"]
        assert cells.load_module(bench, "references", cfg["reference"]).run_reference
        assert cells.load_module(bench, "generators", traffic["generator"]["module"]).open_feed
        assert cells.load_module(bench, "wire", traffic["frames"]["module"]).serialize_rows
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(cells.load_reader(bench, m["name"]))


def test_published_widths_and_rows(bench):
    by = {c["name"]: json.load(open(os.path.join(ROOT, c["file"]))) for c in bench["configs"]}
    cfg = by["lstm4096-openai-five"]
    assert cfg["policy"]["lstm_hidden"] == 4096 and cfg["policy"]["lstm_layers"] == 1
    assert cfg["learner"]["rows_per_chip"] == 512 and cfg["learner"]["seq_len"] == 16
    assert cfg["policy"]["dtype"] == "bfloat16"
    # every compared number has a limit, and the limits are the file's own
    assert set(cfg["check"]["limits"]) >= {"grad_error", "grad_norm_gap", "update_norm_gap"}


def test_the_yardstick_agrees_with_the_programs_arithmetic(bench):
    """The benchmark's copy of the step's operation count against the
    original it was copied from (PERF.md lists the original to delete)."""
    from dotaclient_tpu.ops.flops import train_step_flops

    from benchmark import flops, harness

    for w in bench["workloads"]:
        cell = cells.load_cell(bench, w["name"])
        cfg = harness.learner_config(cell, seed=0, broker_url="mem://x")
        ref = cells.load_module(bench, "references", cell["config_data"]["reference"])
        assert ref.train_step_flops(cell["config_data"], cfg.batch_size) == pytest.approx(
            train_step_flops(cfg), rel=1e-12)
    assert flops.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError):
        flops.peak_flops("TPU v99")


def test_the_learner_is_configured_from_data(tmp_path):
    """Every field the configuration's and the traffic mix's files state
    reaches the program's `LearnerConfig`; a misspelt one is refused."""
    from benchmark import harness

    root = make_tiny_root(str(tmp_path / "root"))
    bench = cells.load_benchmark(root)
    cell = cells.load_cell(bench, "tiny-bursty", root)
    cfg = harness.learner_config(cell, seed=2**31 + 3, broker_url="mem://x")
    assert (cfg.batch_size, cfg.seq_len, cfg.publish_every) == (64, 16, 4)
    assert (cfg.policy.lstm_hidden, cfg.policy.dtype) == (64, "float32")
    assert (cfg.ppo.max_staleness, cfg.ppo.lr) == (1000000, 1e-4)  # the mix's over the file's 12
    assert cfg.staging.transfer_depth == 3  # the configuration's override
    assert cfg.metrics_every == 5  # the traffic mix's override
    assert cfg.broker_url == "mem://x" and 0 <= cfg.seed < 2**31
    four = harness.learner_config(cells.load_cell(bench, "tiny-dp4", root), 1, "mem://x")
    assert four.batch_size == 4 * 64
    cell["config_data"]["policy"]["lstm_hiden"] = 32
    with pytest.raises(ValueError, match="lstm_hiden"):
        harness.learner_config(cell, 1, "mem://x")
    del cell["config_data"]["policy"]["lstm_hiden"]
    cell["traffic_data"]["learner_overrides"] = {"staging.no_such_field": 1}
    with pytest.raises(ValueError, match="no_such_field"):
        harness.learner_config(cell, 1, "mem://x")


def test_adding_a_cell_edits_nothing(tmp_path):
    root = make_tiny_root(str(tmp_path / "root"))  # asserts no file changed
    bench = cells.load_benchmark(root)
    cell = cells.load_cell(bench, "tiny", root)
    assert cell["config_data"]["name"] == "lstm64-tiny"
    assert cell["traffic_data"]["name"] == "wire-tiny"
    own = cells.load_cell(bench, "tiny-bursty", root)["traffic_data"]["generator"]["module"]
    assert cells.load_module(bench, "generators", own, root).open_feed
    with pytest.raises(FileNotFoundError):
        cells.load_module(bench, "generators", own)  # not in the repository's own copy
    names = [m["name"] for m in cells.metrics_for(bench, "tiny", "per_layer")]
    assert "tiny.steps" in names and "allreduce.exposed_ms" not in names
    assert cells.load_reader(bench, "tiny.steps", root)({"steps": 5}) == 5.0
    assert "tiny.steps" not in [
        m["name"] for m in cells.metrics_for(bench, "learner-lstm4096-wire", "per_layer")]
