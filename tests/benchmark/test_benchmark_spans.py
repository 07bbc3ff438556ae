"""The per-layer metrics that read the program's spans (ISSUE 27): each
reader against a hand-made `run["syncs"]`, nothing where the program has
no such scalars (the parent commit), and the tiny cell's traced run on the
CPU reporting every one of them."""

import json
import os

import pytest

from bench_tiny import ROOT, make_tiny_root  # noqa: F401  (puts the root on the path)

from benchmark import cells, harness, trace_reduce

NEW = ("publish.d2h_ms", "publish.serialize_ms", "publish.send_ms", "publish.latency_ms",
       "loop.dispatch_gap_max_ms", "loop.sync_ms", "h2d.lane_blocked_pct", "staging.busy_pct",
       "step.compiles_in_window", "setup.learner_init_s", "setup.compile_s", "setup.publish0_s")


def _syncs():
    """Four metrics windows, 2 s apart, of a run that submits a publish in
    the second (sent in the third) and in the fourth (sent in it): (host
    time, version, scalars)."""
    rows = [
        # t,  d2h n/s,  ser n/s,  send n/s,  lat n/s,  submit n, gap, sync n/s, handoff s, ingest s, pack s, compiles
        (10.0, 1, 0.10, 1, 0.40, 1, 0.002, 1, 0.60, 1, 0.07, 5, 0.050, 1.5, 0.010, 0.020, 7),
        (12.0, 2, 0.30, 2, 0.78, 2, 0.006, 1, 0.60, 2, 0.45, 6, 0.056, 3.3, 0.030, 0.060, 7),
        (14.0, 2, 0.30, 2, 0.78, 2, 0.006, 2, 1.30, 2, 0.07, 7, 0.062, 5.1, 0.050, 0.100, 7),
        (16.0, 3, 0.54, 3, 1.18, 3, 0.011, 3, 2.04, 3, 0.47, 8, 0.071, 6.9, 0.070, 0.140, 7),
    ]
    out = []
    for i, (t, dn, ds, sn, ss, nn, ns, ln, ls, sub, gap, yn, ys, hand, ing, pack, comp) in enumerate(rows):
        out.append((t, 10 * (i + 1), {
            "span_publish_d2h_n_total": dn, "span_publish_d2h_s_total": ds,
            "span_publish_serialize_n_total": sn, "span_publish_serialize_s_total": ss,
            "span_publish_send_n_total": nn, "span_publish_send_s_total": ns,
            "span_publish_latency_n_total": ln, "span_publish_latency_s_total": ls,
            "span_loop_publish_submit_n_total": sub, "loop_dispatch_gap_max_s": gap,
            "span_loop_sync_n_total": yn, "span_loop_sync_s_total": ys,
            "span_lane_handoff_s_total": hand,
            "span_staging_ingest_s_total": ing, "span_staging_pack_s_total": pack,
            "compile_count_total": comp,
            "span_setup_learner_init_s_total": 1.5, "compile_s_total": 9.25,
            "span_setup_publish0_s_total": 2.5,
        }))
    return out


# Worked out by hand from the rows above: last minus first, over the count's
# rise (two publishes, three syncs) or over the 6 s between the two.
BY_HAND = {
    "publish.d2h_ms": 1e3 * (0.54 - 0.10) / 2,
    "publish.serialize_ms": 1e3 * (1.18 - 0.40) / 2,
    "publish.send_ms": 1e3 * (0.011 - 0.002) / 2,
    "publish.latency_ms": 1e3 * (2.04 - 0.60) / 2,
    "loop.dispatch_gap_max_ms": 1e3 * 0.47,  # a publish in flight: 2nd, 3rd, 4th (0.45, 0.07, 0.47)
    "loop.sync_ms": 1e3 * (0.071 - 0.050) / 3,
    "h2d.lane_blocked_pct": 100 * (6.9 - 1.5) / 6.0,
    "staging.busy_pct": 100 * ((0.070 - 0.010) + (0.140 - 0.020)) / 6.0,
    "step.compiles_in_window": 0.0,
    "setup.learner_init_s": 1.5,
    "setup.compile_s": 9.25,
    "setup.publish0_s": 2.5,
}


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


@pytest.mark.parametrize("name", NEW)
def test_reader_against_hand_made_syncs(bench, name):
    assert cells.load_reader(bench, name)({"syncs": _syncs()}) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_nothing_without_the_programs_scalars(bench, name):
    """What the parent commit gives: metric windows that hold none of the
    span scalars, or no metric window at all."""
    read = cells.load_reader(bench, name)
    older = [(t, v, {"loss": 0.1, "time_wait_batch_s": 0.004}) for t, v, _ in _syncs()]
    assert read({"syncs": older}) is None
    assert read({"syncs": []}) is None


def test_a_count_that_did_not_rise_reads_as_nothing(bench):
    """No publish's host read, framing or send between the first and the
    last metrics window (one was sent: its latency counts): no time per
    publish; and one window alone is no window to take the dispatch gap
    from."""
    quiet = _syncs()[1:3]
    for name in ("publish.d2h_ms", "publish.serialize_ms", "publish.send_ms"):
        assert cells.load_reader(bench, name)({"syncs": quiet}) is None, name
    assert cells.load_reader(bench, "publish.latency_ms")({"syncs": quiet}) == pytest.approx(700.0)
    assert cells.load_reader(bench, "loop.dispatch_gap_max_ms")({"syncs": quiet[:1]}) is None
    assert cells.load_reader(bench, "loop.sync_ms")({"syncs": quiet}) == pytest.approx(6.0)


def test_a_failed_or_superseded_publish_is_no_longer_in_flight(bench):
    read = cells.load_reader(bench, "loop.dispatch_gap_max_ms")
    syncs = _syncs()
    for s, sent in zip(syncs[1:], (1, 1, 2)):  # the publish of the second window failed in it
        s[2]["weights_publish_failed"], s[2]["span_publish_latency_n_total"] = 1, sent
    syncs[2][2]["loop_dispatch_gap_max_s"] = 0.9  # nothing in flight there: not a publish's
    assert read({"syncs": syncs}) == pytest.approx(1e3 * 0.47)
    for s in syncs[1:]:  # ... or was superseded
        s[2]["weights_publish_failed"], s[2]["weights_coalesced"] = 0, 1
    assert read({"syncs": syncs}) == pytest.approx(1e3 * 0.47)


def test_a_compile_inside_the_window_is_counted(bench):
    syncs = _syncs()
    syncs[-1][2]["compile_count_total"] = 9
    assert cells.load_reader(bench, "step.compiles_in_window")({"syncs": syncs}) == 2


def test_the_twelve_entries(bench):
    entries = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert names[names.index(NEW[0]):][:12] == list(NEW)
    assert all("workloads" not in entries[n] for n in NEW)
    assert sorted(n for n in NEW if entries[n]["moves"] == "setup_s") == [
        "setup.compile_s", "setup.learner_init_s", "setup.publish0_s"]
    assert sorted(n for n in NEW if entries[n]["source"] == "program_counter") == [
        "setup.compile_s", "step.compiles_in_window"]
    assert all(entries[n]["source"] == "program_span" for n in NEW if "compile" not in n)


def test_tiny_cell_reports_each_new_metric_in_a_traced_run(tmp_path, monkeypatch):
    """A traced run of the tiny cell on the CPU. There is no device plane
    here, so the device's part of the reduction is the recorded trace's;
    the program's spans and scalars are the run's own, and its spans lie
    in the profiler's trace on lines named after the program's threads."""
    from test_benchmark_cell import drive

    with open(os.path.join(ROOT, "tests", "benchmark", "data", "small_trace.json")) as f:
        recorded = json.load(f)
    seen = {}
    reduce = trace_reduce.reduce

    def reduce_recorded(events, chips, scopes=None):
        seen["events"] = events
        return reduce(recorded, chips=2)

    monkeypatch.setattr(trace_reduce, "reduce", reduce_recorded)
    # a profile directory of this test's own: the harness empties its directory before and
    # after a trace, and another file's traced run may be in another worker at the same time
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    result = drive(make_tiny_root(str(tmp_path / "root")), trace=True, seconds=2.0)
    assert result["correct"] is True
    for name in NEW:
        assert name in result["metrics"], name
    values = {k: v["value"] for k, v in result["metrics"].items()}
    assert values["step.compiles_in_window"] == 0
    assert values["setup.learner_init_s"] > 0 and values["setup.compile_s"] > 0
    assert 0 <= values["staging.busy_pct"] <= 100 and 0 <= values["h2d.lane_blocked_pct"] <= 100
    where = {}
    for plane in seen["events"]["planes"]:
        for line in plane["lines"]:
            for name, *_ in line["events"]:
                where.setdefault(name, set()).add(line["name"])
    assert where["publish.serialize"] == {"weight-publishe"}
    assert where["lane.device_put"] == {"learner-prefetc"}
    assert where["staging.pack"] == {"staging-consume"}
    assert "loop.dispatch" in where and where["loop.dispatch"].isdisjoint(
        {"weight-publishe", "learner-prefetc", "staging-consume"})
