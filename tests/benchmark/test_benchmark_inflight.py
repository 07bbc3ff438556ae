"""The per-layer metrics that read what the loop knows of the device's
queue (ISSUE 41): each reader against a hand-made `run["syncs"]`, nothing
where the program has no such scalars (the parent commit) or the window
holds fewer than two metrics windows, the ten entries, and the tiny cell's
traced run on the CPU reporting every one of them.

The readers are in `benchmark/metrics/`; their entries are `ENTRIES` below
and not yet in `BENCHMARK.json`: a PR that changes the program may only
append to `per_layer`, and `test_benchmark_gdn_moe.py` holds that list's
last four to be the `attn_*` ones (PERF.md section 7). The tests that need
the entries put them at the end of a temporary copy's list, as the
`benchmark` PR that takes them will."""

import json
import os

import pytest

from bench_tiny import ROOT, make_tiny_root  # noqa: F401  (puts the root on the path)

from benchmark import cells, trace_reduce

NEW = ("publish.ready_wait_ms", "publish.copy_ms", "publish.age_ms", "loop.sync_ready_ms",
       "loop.sync_get_ms", "loop.inflight_max", "loop.starved_pct", "loop.starved_ms",
       "loop.starved_by_sync_ms", "loop.starved_by_feed_ms")
CAUSES = ("take", "sync", "publish", "checkpoint", "other")
ENTRIES = [
    {"name": n, "unit": {"loop.inflight_max": "steps", "loop.starved_pct": "%"}.get(n, "ms"), "better": "lower",
     "source": "program_span" if i < 5 else "program_counter",
     "layer": "weight publish" if n.startswith("publish.") else "learner loop", "moves": "env_steps_per_s"}
    for i, n in enumerate(NEW)]


def _syncs():
    """Four metrics windows of ten steps, 2 s apart, of a run that sends a
    publish in the second and two in the fourth: (host time, version,
    scalars)."""
    rows = [
        # t, publishes, wait s, copy s, age s, syncs, ready s, get s, in flight, steps, starved n, s by cause
        (10.0, 1, 0.30, 0.10, 0.90, 5, 2.50, 0.0010, 9, 50, 5, (0.004, 0.050, 0.001, 0.0, 0.002)),
        (12.0, 2, 0.68, 0.22, 1.84, 6, 3.02, 0.0012, 7, 60, 6, (0.004, 0.061, 0.001, 0.0, 0.002)),
        (14.0, 2, 0.68, 0.22, 1.84, 7, 3.53, 0.0015, 8, 70, 8, (0.024, 0.070, 0.001, 0.0, 0.002)),
        (16.0, 4, 1.32, 0.43, 3.60, 8, 4.03, 0.0016, 6, 80, 9, (0.024, 0.080, 0.003, 0.0, 0.005)),
    ]
    out = []
    for i, (t, pn, ws, cs, ages, yn, rs, gs, flight, steps, sn, by_cause) in enumerate(rows):
        scalars = {
            "span_publish_ready_wait_n_total": pn, "span_publish_ready_wait_s_total": ws,
            "span_publish_copy_n_total": pn, "span_publish_copy_s_total": cs,
            "span_publish_age_n_total": pn, "span_publish_age_s_total": ages,
            "span_loop_sync_ready_n_total": yn, "span_loop_sync_ready_s_total": rs,
            "span_loop_sync_get_n_total": yn, "span_loop_sync_get_s_total": gs,
            "loop_inflight_max": flight, "loop_inflight_mean": flight / 2,
            "span_loop_dispatch_n_total": steps, "loop_starved_n_total": sn,
        }
        scalars.update({f"loop_starved_{c}_s_total": s for c, s in zip(CAUSES, by_cause)})
        out.append((t, 10 * (i + 1), scalars))
    return out


# Worked out by hand from the rows above: last minus first, over the count's
# rise (three publishes, three syncs, thirty steps).
BY_HAND = {
    "publish.ready_wait_ms": 1e3 * (1.32 - 0.30) / 3,
    "publish.copy_ms": 1e3 * (0.43 - 0.10) / 3,
    "publish.age_ms": 1e3 * (3.60 - 0.90) / 3,
    "loop.sync_ready_ms": 1e3 * (4.03 - 2.50) / 3,
    "loop.sync_get_ms": 1e3 * (0.0016 - 0.0010) / 3,
    "loop.inflight_max": 8,  # the first window began before the run's window: its 9 is left out
    "loop.starved_pct": 100 * (9 - 5) / 30,
    "loop.starved_ms": 1e3 * (0.020 + 0.030 + 0.002 + 0.0 + 0.003) / 30,
    "loop.starved_by_sync_ms": 1e3 * 0.030 / 30,
    "loop.starved_by_feed_ms": 1e3 * 0.020 / 30,
}


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


@pytest.mark.parametrize("name", NEW)
def test_reader_against_hand_made_syncs(bench, name):
    assert cells.load_reader(bench, name)({"syncs": _syncs()}) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_nothing_at_the_parents_keys_or_on_fewer_than_two_windows(bench, name):
    """What the parent commit gives: metrics windows with its spans and
    none of the new ones; and a window too short to take a difference in."""
    read = cells.load_reader(bench, name)
    parent = [(t, v, {"loss": 0.1, "span_publish_d2h_n_total": i, "span_publish_d2h_s_total": 0.4 * i,
                      "span_loop_sync_n_total": i, "span_loop_sync_s_total": 0.5 * i,
                      "span_loop_dispatch_n_total": 10 * i, "loop_dispatch_gap_max_s": 0.05})
              for i, (t, v, _) in enumerate(_syncs())]
    assert read({"syncs": parent}) is None
    assert read({"syncs": _syncs()[:1]}) is None
    assert read({"syncs": []}) is None


def test_a_count_that_did_not_rise_reads_as_nothing(bench):
    """No publish between the first and the last metrics window: no time
    per publish. The loop's readers need only the steps to rise."""
    quiet = _syncs()[1:3]
    for name in ("publish.ready_wait_ms", "publish.copy_ms", "publish.age_ms"):
        assert cells.load_reader(bench, name)({"syncs": quiet}) is None, name
    assert cells.load_reader(bench, "loop.sync_ready_ms")({"syncs": quiet}) == pytest.approx(510.0)
    assert cells.load_reader(bench, "loop.starved_pct")({"syncs": quiet}) == pytest.approx(20.0)
    assert cells.load_reader(bench, "loop.starved_by_feed_ms")({"syncs": quiet}) == pytest.approx(2.0)
    assert cells.load_reader(bench, "loop.inflight_max")({"syncs": quiet}) == 8
    stalled = [(t, v, dict(s, span_loop_dispatch_n_total=50)) for t, v, s in _syncs()]
    for name in NEW[6:]:
        assert cells.load_reader(bench, name)({"syncs": stalled}) is None, name


def test_the_parts_add_up_to_what_the_old_readers_read(bench):
    """`publish.d2h_ms` and `loop.sync_ms` are the sums their spans always
    were: wait and copy, ready and get, and the clock reads between them."""
    syncs = _syncs()
    for _, _, s in syncs:
        s["span_publish_d2h_n_total"] = s["span_publish_copy_n_total"]
        s["span_publish_d2h_s_total"] = s["span_publish_ready_wait_s_total"] + s["span_publish_copy_s_total"]
        s["span_loop_sync_n_total"] = s["span_loop_sync_get_n_total"]
        s["span_loop_sync_s_total"] = s["span_loop_sync_ready_s_total"] + s["span_loop_sync_get_s_total"]
    read = {n: cells.load_reader(bench, n)({"syncs": syncs})
            for n in NEW[:5] + ("publish.d2h_ms", "loop.sync_ms")}
    assert read["publish.ready_wait_ms"] + read["publish.copy_ms"] == pytest.approx(read["publish.d2h_ms"])
    assert read["loop.sync_ready_ms"] + read["loop.sync_get_ms"] == pytest.approx(read["loop.sync_ms"])


def test_the_ten_entries(bench):
    """The table of ISSUE 41, each with a reader's file beside the accepted
    ones; appended to the accepted list they are reported in every cell."""
    assert [sorted(e) for e in ENTRIES] == [sorted(bench["per_layer"][0])] * 10  # just the keys an entry has
    assert [(e["name"], e["unit"], e["source"], e["layer"]) for e in ENTRIES] == [
        ("publish.ready_wait_ms", "ms", "program_span", "weight publish"),
        ("publish.copy_ms", "ms", "program_span", "weight publish"),
        ("publish.age_ms", "ms", "program_span", "weight publish"),
        ("loop.sync_ready_ms", "ms", "program_span", "learner loop"),
        ("loop.sync_get_ms", "ms", "program_span", "learner loop"),
        ("loop.inflight_max", "steps", "program_counter", "learner loop"),
        ("loop.starved_pct", "%", "program_counter", "learner loop"),
        ("loop.starved_ms", "ms", "program_counter", "learner loop"),
        ("loop.starved_by_sync_ms", "ms", "program_counter", "learner loop"),
        ("loop.starved_by_feed_ms", "ms", "program_counter", "learner loop")]
    accepted = {m["layer"] for m in bench["per_layer"]}
    assert {e["layer"] for e in ENTRIES} <= accepted  # layers the benchmark already names, letter for letter
    assert all(os.path.exists(os.path.join(ROOT, "benchmark", "metrics", e["name"] + ".py")) for e in ENTRIES)
    with_them = dict(bench, per_layer=[m for m in bench["per_layer"] if m["name"] not in NEW] + ENTRIES)
    assert len({m["name"] for m in with_them["per_layer"]}) == len(with_them["per_layer"])
    for cell in bench["workloads"]:  # no `workloads` key: every cell reports env_steps_per_s
        assert set(NEW) <= {m["name"] for m in cells.metrics_for(with_them, cell["name"], "per_layer")}


def test_tiny_cell_reports_each_new_metric_in_a_traced_run(tmp_path, monkeypatch):
    """A traced run of the tiny cell on the CPU (the device's part of the
    reduction is the recorded trace's, as in test_benchmark_spans.py): the
    ten read the run's own scalars, and hold the relations the chip's runs
    are held to."""
    from test_benchmark_cell import drive

    with open(os.path.join(ROOT, "tests", "benchmark", "data", "small_trace.json")) as f:
        recorded = json.load(f)
    reduce = trace_reduce.reduce
    monkeypatch.setattr(trace_reduce, "reduce", lambda events, chips, scopes=None: reduce(recorded, chips=2))
    root = make_tiny_root(str(tmp_path / "root"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"] = [m for m in bench["per_layer"] if m["name"] not in NEW] + ENTRIES
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    result = drive(root, trace=True, seconds=2.0)
    assert result["correct"] is True
    for name in NEW:
        assert name in result["metrics"], name
    values = {k: v["value"] for k, v in result["metrics"].items()}
    assert values["publish.ready_wait_ms"] + values["publish.copy_ms"] <= values["publish.d2h_ms"]
    assert values["loop.sync_ready_ms"] + values["loop.sync_get_ms"] <= values["loop.sync_ms"]
    assert values["publish.age_ms"] <= values["publish.latency_ms"]
    assert 0 <= values["loop.starved_pct"] <= 100
    by_cause = values["loop.starved_by_sync_ms"] + values["loop.starved_by_feed_ms"]
    assert by_cause <= values["loop.starved_ms"] + 1e-9
    from dotaclient_tpu.config import LearnerConfig

    assert 0 <= values["loop.inflight_max"] < LearnerConfig().metrics_every  # the tiny cell's is the default
