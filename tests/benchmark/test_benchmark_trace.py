"""The reduction from a trace to numbers, on a small recorded trace whose
every value is worked out by hand (times in the file are microseconds
times 1,000; see the comments)."""

import json
import os

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the root on the path)

from benchmark import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small_trace.json")
US = 1e-6


@pytest.fixture(scope="module")
def reduced():
    with open(DATA) as f:
        return trace_reduce.reduce(json.load(f), chips=2)


def test_window_is_the_span_of_everything_traced(reduced):
    # the loop thread's root span runs 0..12,000 us; nothing starts
    # earlier or ends later
    assert reduced["window_s"] == pytest.approx(12000 * US)
    assert reduced["chips_traced"] == 2


def test_busy_is_the_union_of_operations_averaged_over_chips(reduced):
    # chip 0: [1000,2500] [2500,3500] [3000,5000] merge to 4000; the copy
    # 500; the second step 4000 -> 8500. chip 1: 2000 + 3000 = 5000.
    assert reduced["busy_s"] == pytest.approx((8500 + 5000) / 2 * US)


def test_idle_share(reduced):
    idle = 1.0 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(1.0 - 6750 / 12000)


def test_step_program_is_the_module_with_most_time(reduced):
    assert reduced["step_name"] == "jit_fused_fn(123)"
    assert reduced["step_count"] == 2
    # chip 0: 4000 + 4000, chip 1: 2000 + 3000 -> mean 6500 over 2 steps
    assert reduced["step_busy_s"] == pytest.approx(6500 * US)
    assert reduced["step_busy_s"] / reduced["step_count"] == pytest.approx(3250 * US)


def test_exposed_allreduce_is_what_no_other_operation_covers(reduced):
    # chip 0, step 1: all-reduce [2500,3500], fusion.2 starts at 3000 ->
    # 500 exposed; step 2: [8500,9500] with nothing beside it -> 1000.
    # chip 1 has none. Mean over the two chips: 750.
    assert reduced["allreduce_exposed_s"] == pytest.approx(750 * US)


def test_no_allreduce_reads_as_nothing_not_zero():
    with open(DATA) as f:
        events = json.load(f)
    for p in events["planes"]:
        for ln in p["lines"]:
            ln["events"] = [e for e in ln["events"] if "all-reduce" not in e[0]]
    assert trace_reduce.reduce(events, chips=2)["allreduce_exposed_s"] is None


def test_top_operations_by_time(reduced):
    ops = dict(reduced["breakdown"]["device_ops"])
    assert ops["fusion.2"] == pytest.approx((3500 + 3000) / 2 * US)
    assert ops["fusion.1"] == pytest.approx((3000 + 2000) / 2 * US)
    assert ops["all-reduce.7"] == pytest.approx(2000 / 2 * US)
    assert ops["copy.3"] == pytest.approx(250 * US)
    assert reduced["breakdown"]["device_ops"][0][0] == "fusion.2"


def test_gaps_go_to_the_shortest_host_span_covering_half(reduced):
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    # chip 0 idles [0,1000], [5000,6000], [6500,7000], [11000,12000].
    # [5000,6000]: serialize_weights covers 900 of it (the root frame all
    # of it, but it is longer); [6500,7000]: a worker's sleep covers it;
    # the first and the last have only the loop's root frame.
    assert gaps["python3:$serialize.py:682 serialize_weights"] == pytest.approx(1000 * US)
    assert gaps["worker:$time sleep"] == pytest.approx(500 * US)
    assert gaps["python3:$learner.py:1337 _run_pipelined"] == pytest.approx(2000 * US)
    assert sum(gaps.values()) == pytest.approx((12000 - 8500) * US)


def test_interval_arithmetic():
    a = trace_reduce.union(np.array([[5, 7], [1, 3], [2, 4], [7, 9]]))
    assert a.tolist() == [[1, 4], [5, 9]]
    assert trace_reduce.length(a) == 7
    b = trace_reduce.union(np.array([[0, 2], [3, 6], [8, 20]]))
    assert trace_reduce.subtract(a, b).tolist() == [[2, 3], [6, 8]]
    assert trace_reduce.subtract(a, np.zeros((0, 2), dtype=np.int64)).tolist() == a.tolist()


def test_a_trace_without_device_operations_is_refused():
    with open(DATA) as f:
        events = json.load(f)
    events["planes"] = [p for p in events["planes"] if p["name"].startswith("/host")]
    with pytest.raises(ValueError, match="no device plane"):
        trace_reduce.reduce(events, chips=1)


def test_metric_readers_return_nothing_without_a_trace():
    from benchmark import cells

    bench = cells.load_benchmark()
    run = {"trace": None, "device": {"platform": "cpu", "kind": "cpu"}, "chips": 1,
           "syncs": [], "peak_bytes": 0, "steps": 0, "window_s": 1.0,
           "open": {"consumed": 0, "steps": 0, "coalesced": 0}, "close": {"consumed": 0, "steps": 0, "coalesced": 0},
           "publish_every": 8}
    for name in ("step.device_ms", "step.mfu_pct", "device.idle_pct", "allreduce.exposed_ms",
                 "h2d.put_ms", "feed.wait_batch_ms", "feed.exposed_wait_ms", "device.peak_hbm_gb",
                 "step.host_ms", "staging.stale_drop_pct", "publish.coalesced_pct"):
        assert cells.load_reader(bench, name)(run) is None, name


def test_the_window_span_cuts_the_trace():
    """With a `bench_window` host span of [2000, 10000] us the trace is cut
    to it: chip 0 is busy [2000,5000] [6000,6500] [7000,10000] = 6500,
    chip 1 [2000,3000] and [7000,10000] = 4000. No step lies whole inside
    on chip 0 (both straddle an end); on chip 1 the second does, the first
    straddles: half a step a chip, 3000 us on one chip of two."""
    with open(DATA) as f:
        events = json.load(f)
    events["planes"][2]["lines"][0]["events"].append(
        [trace_reduce.WINDOW_SPAN, 2000 * 1000, 8000 * 1000])
    red = trace_reduce.reduce(events, chips=2)
    assert red["window_s"] == pytest.approx(8000 * US)
    assert red["busy_s"] == pytest.approx((6500 + 4000) / 2 * US)
    assert red["step_count"] == 0.5
    assert red["step_busy_s"] == pytest.approx(1500 * US)
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert trace_reduce.WINDOW_SPAN not in " ".join(gaps)
    assert sum(gaps.values()) == pytest.approx(1500 * US)


def test_operation_names_lose_their_hlo_text():
    assert trace_reduce.short_name("%while.12 = (s32[]{:T(128)}, bf16[4096]) while(...)") == "while.12"
    assert trace_reduce.short_name("fusion.1") == "fusion.1"
