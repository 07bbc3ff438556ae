"""obs/spans.py and what the learner process records with it: the table
(sums and counts, across threads), the flight-recorder mirror, the
scalars a learner emits, one clock with the profiler's trace, the named
scopes inside the compiled step, the publisher's failure count; and
(ISSUE 41) the two blocking reads split where the device finishes, the
steps in flight at every dispatch, the dispatches that found none."""

import glob
import threading
import time

import jax
import pytest

from dotaclient_tpu.config import LearnerConfig, PolicyConfig, PPOConfig, parse_config
from dotaclient_tpu.obs import registry, spans
from dotaclient_tpu.obs.flight_recorder import FlightRecorder
from dotaclient_tpu.obs.spans import span
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import connect

from test_pipeline import POL, _cfg, _feed

# What a learner on the default path records (the table of ISSUE 27) ...
DEFAULT_SPANS = (
    "loop.dispatch", "loop.publish_submit", "loop.sync", "loop.sync_ready", "loop.sync_get",
    "lane.handoff", "staging.pop", "staging.ingest", "staging.pack", "staging.ready_wait",
    "publish.d2h", "publish.ready_wait", "publish.copy", "publish.serialize", "publish.send",
    "publish.latency", "publish.age",
    "setup.learner_init", "setup.init_params", "setup.publish0",
)
# ... what needs a ring lease or a checkpoint directory to happen ...
OPTIONAL_SPANS = ("lane.retire", "loop.checkpoint", "setup.restore")
# ... and what goes onto the profiler's timeline alone, because the program
# sums it already (pipeline_device_idle_s, time_wait_batch_s, time_device_put_s).
TIMELINE_ONLY = ("loop.take", "lane.wait_batch", "lane.device_put")


# ... and what the loop knows of the device's queue: two gauges a metrics
# window, one count and five cumulative seconds of starved dispatches.
CAUSES = ("take", "sync", "publish", "checkpoint", "other")
FLIGHT_KEYS = ("loop_inflight_max", "loop_inflight_mean", "loop_starved_n_total",
               *(f"loop_starved_{c}_s_total" for c in CAUSES))


def _keys(name):
    key = "span_" + name.replace(".", "_")
    return key + "_n_total", key + "_s_total"


def _read(name):
    scalars = spans.scalars()
    return tuple(scalars.get(k, 0.0) for k in _keys(name))


def test_sums_and_counts_per_name_across_two_threads():
    def work(name, sleeps):
        for s in sleeps:
            with span(name):
                time.sleep(s)

    threads = [
        threading.Thread(target=work, args=("t.alpha", (0.01, 0.03))),
        threading.Thread(target=work, args=("t.beta", (0.02,))),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    scalars = spans.scalars()
    n, s = (scalars[k] for k in _keys("t.alpha"))
    assert n == 2 and 0.04 <= s < 0.2
    n, s = (scalars[k] for k in _keys("t.beta"))
    assert n == 1 and 0.02 <= s < 0.1


def test_the_sums_are_cumulative_and_reading_them_changes_nothing():
    with span("t.window"):
        time.sleep(0.02)
    n1, s1 = _read("t.window")
    assert n1 == 1 and s1 >= 0.02
    assert _read("t.window") == (n1, s1)
    with span("t.window"):
        pass
    n3, s3 = _read("t.window")
    assert n3 == 2 and s1 < s3 < s1 + 0.02


def test_nesting_counts_parent_and_child_each_under_its_name():
    with span("t.parent", step=3):
        with span("t.child", step=3):
            time.sleep(0.01)
        with span("t.child", step=3):
            pass
    pn, ps = _read("t.parent")
    cn, cs = _read("t.child")
    assert (pn, cn) == (1, 2) and ps >= cs >= 0.01


def test_add_counts_a_duration_that_is_no_timeline_span():
    spans.add("t.latency", 5_000_000)
    spans.add("t.latency", 7_000_000)
    assert _read("t.latency") == (2, pytest.approx(0.012))


def test_timeline_only_annotations_leave_the_table_alone():
    with spans.timeline("t.timeline", step=1):
        pass
    assert not [k for k in spans.scalars() if k.startswith("span_t_timeline")]


def test_ids_reach_the_flight_recorder_only_when_one_is_installed(monkeypatch):
    monkeypatch.setattr(spans, "MIRROR_MIN_S", 0.01)
    recorder = FlightRecorder("test")
    with span("t.mirror", version=1):
        time.sleep(0.01)
    assert recorder.events_recorded == 0
    spans.mirror_to(recorder)
    try:
        t_before = time.time()
        with span("t.mirror", version=2):
            time.sleep(0.01)
    finally:
        spans.mirror_to(None)
    with span("t.mirror", version=3):
        time.sleep(0.01)
    events = recorder.snapshot()["events"]
    assert [(e["ev"], e["version"]) for e in events] == [("t.mirror", 2)]
    assert events[0]["ms"] >= 10.0
    assert abs(events[0]["t"] - t_before) < 0.5  # the span's start, on the recorder's wall clock


def test_only_a_long_span_is_mirrored_so_routine_ones_do_not_fill_the_ring():
    """A step's twenty-odd spans a second would push the ring's rare
    events (compile, watchdog, fence) out within seconds: the ring gets
    the stalls, the table gets everything."""
    recorder = FlightRecorder("test")
    spans.mirror_to(recorder)
    try:
        for _ in range(50):
            with span("t.routine", step=1):
                pass
        with span("t.stall", version=9):
            time.sleep(spans.MIRROR_MIN_S)
    finally:
        spans.mirror_to(None)
    assert [e["ev"] for e in recorder.snapshot()["events"]] == ["t.stall"]
    assert _read("t.routine")[0] == 50


def test_scalar_keys_are_the_registered_family():
    with span("t.some.name"):
        pass
    keys = [k for k in spans.scalars() if k.startswith("span_t_some_name")]
    assert sorted(keys) == sorted(_keys("t.some.name"))
    assert not registry.unregistered(
        keys + ["loop_dispatch_gap_max_s", "weights_publish_failed", "compile_count_total",
                "compile_s_total"])


def test_the_flight_keys_and_the_new_spans_are_registered():
    new = [k for name in ("publish.ready_wait", "publish.copy", "publish.age",
                          "loop.sync_ready", "loop.sync_get") for k in _keys(name)]
    assert not registry.unregistered(new + list(FLIGHT_KEYS))
    assert "loop_inflight_max" in registry.SCALARS and "loop_starved_" in registry.PREFIXES
    assert registry.unregistered(["loop_inflight_min", "loop_starving"])  # the family is no catch-all


def test_thread_name_reaches_the_os():
    seen = {}

    def work():
        spans.name_thread("weight-publisher")
        with open(f"/proc/self/task/{threading.get_native_id()}/comm") as f:
            seen["comm"] = f.read().strip()

    t = threading.Thread(target=work)
    t.start()
    t.join(timeout=10)
    assert seen["comm"] == "weight-publishe"  # 15 characters are the kernel's limit


def test_compile_counters_count_a_new_program():
    spans.count_compiles()
    spans.count_compiles()  # registers once
    x = jax.numpy.ones((7, 3))
    before = spans.scalars()
    jax.jit(lambda x: x * 3 + 41)(x)
    after = spans.scalars()
    assert after["compile_count_total"] == before["compile_count_total"] + 1
    assert after["compile_s_total"] > before["compile_s_total"]
    assert "compile_cache_hits_total" not in after  # runtime/device.py's CompileCache counts those


def _logged(learner):
    """Every scalar dict the learner hands to its metrics logger."""
    seen = []
    log = learner.metrics.log

    def keep(step, scalars):
        seen.append(dict(scalars))
        return log(step, scalars)

    learner.metrics.log = keep
    return seen


def test_default_learner_emits_every_span_scalar(tmp_path):
    from dotaclient_tpu.runtime.learner import Learner

    mem.reset("sp_default")
    _feed(connect("mem://sp_default"), 8 * 6)
    learner = Learner(_cfg("sp_default", tmp_path, publish_every=2), connect("mem://sp_default"))
    seen = _logged(learner)
    try:
        assert learner.run(num_steps=6, batch_timeout=60.0, max_idle=3) == 6
    finally:
        learner.close()
    first, last = seen[0], seen[-1]
    for name in DEFAULT_SPANS:
        for key in _keys(name):
            assert key in last, key
        assert last[_keys(name)[0]] >= 1, name
    assert last["span_loop_dispatch_n_total"] - first["span_loop_dispatch_n_total"] == 4
    # one set of scalars for the lane's wait and put and the loop's take: PR 15's
    for key in ("time_wait_batch_s", "time_device_put_s", "pipeline_device_idle_s",
                "loop_dispatch_gap_max_s", "weights_publish_failed", "compile_count_total",
                "compile_s_total", *FLIGHT_KEYS):
        assert key in last, key
    for name in TIMELINE_ONLY:
        assert not set(_keys(name)) & set(last), name
    assert not [k for k in last if k.endswith("_max_s") and k.startswith("span_")]
    # the step's and the flatten's first calls are behind the first window: set-up's seconds
    assert first["compile_s_total"] > 0
    assert last["weights_publish_failed"] == 0
    assert last["loop_dispatch_gap_max_s"] > 0
    assert last["compile_count_total"] == seen[1]["compile_count_total"]  # none in steady state
    assert not registry.unregistered(set().union(*seen))


def test_leased_checkpointing_learner_emits_retire_checkpoint_and_restore(tmp_path):
    from dotaclient_tpu.runtime.learner import Learner

    mem.reset("sp_ckpt")
    _feed(connect("mem://sp_ckpt"), 8 * 2)
    cfg = _cfg("sp_ckpt", tmp_path, checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=2)
    cfg.staging.pack_workers = 2
    learner = Learner(cfg, connect("mem://sp_ckpt"))
    seen = _logged(learner)
    try:
        assert learner.run(num_steps=2, batch_timeout=60.0, max_idle=3) == 2
    finally:
        learner.close()
    for name in OPTIONAL_SPANS:
        assert seen[-1][_keys(name)[0]] >= 1, name
    assert not registry.unregistered(seen[-1])


@pytest.fixture(scope="module")
def default_run(tmp_path_factory):
    """One default learner, six steps with a publish every second one: the
    scalars of each metrics window, those after the publisher has stopped,
    and the run's wall seconds."""
    from dotaclient_tpu.runtime.learner import Learner

    tmp_path = tmp_path_factory.mktemp("sp_split")
    mem.reset("sp_split")
    _feed(connect("mem://sp_split"), 8 * 6)
    learner = Learner(_cfg("sp_split", tmp_path, publish_every=2), connect("mem://sp_split"))
    seen = _logged(learner)
    before = spans.scalars()
    t0 = time.perf_counter()
    try:
        assert learner.run(num_steps=6, batch_timeout=60.0, max_idle=3) == 6
        wall = time.perf_counter() - t0
    finally:
        learner.close()
    after = spans.scalars()
    return seen, {k: after[k] - before.get(k, 0.0) for k in after}, wall


@pytest.mark.parametrize("whole, parts", [
    ("publish.d2h", ("publish.ready_wait", "publish.copy")),
    ("loop.sync", ("loop.sync_ready", "loop.sync_get")),
    ("publish.latency", ("publish.age",)),
])
def test_a_blocking_read_splits_where_the_device_finishes(default_run, whole, parts):
    """The old span covers what it covered; the new ones lie inside it, one
    of each for every one of it, and leave out only the clock reads between
    them."""
    _, total, _ = default_run
    n, seconds = (total[k] for k in _keys(whole))
    assert n >= 3
    for part in parts:
        assert total[_keys(part)[0]] == n, part
    inside = sum(total[_keys(part)[1]] for part in parts)
    assert 0 < inside <= seconds
    if len(parts) == 2:
        assert seconds - inside < 0.005 * n  # nothing else happens inside the old span


def test_the_loop_counts_the_steps_in_flight_and_the_dispatches_that_found_none(default_run):
    seen, _, wall = default_run
    assert len(seen) == 3
    for window in seen:
        # a sync empties the queue, so a dispatch finds at most the window's other step
        assert 0 <= window["loop_inflight_mean"] <= window["loop_inflight_max"] <= 1
    last = seen[-1]
    assert 0 <= last["loop_starved_n_total"] <= 5  # six dispatches, the run's first is none
    by_cause = [last[f"loop_starved_{c}_s_total"] for c in CAUSES]
    assert all(v >= 0 for v in by_cause) and sum(by_cause) <= wall
    assert (sum(by_cause) > 0) == (last["loop_starved_n_total"] > 0)
    for a, b in zip(seen, seen[1:]):  # cumulative
        assert all(b[k] >= a[k] for k in FLIGHT_KEYS[2:])


def _slow_results(learner, seconds_about=0.5):
    """Make each step's `loss` the result of a long device computation
    dispatched after the step: the step itself stays the program's own."""
    import jax.numpy as jnp

    a = jnp.full((512, 512), 1e-3, jnp.float32)
    reps = max(int(seconds_about / 0.0025), 1)

    @jax.jit
    def late(x):
        y = jax.lax.fori_loop(0, reps, lambda i, m: jnp.tanh(m @ a), a)
        return x + 0 * y[0, 0]

    late(jnp.float32(0)).block_until_ready()
    step = learner.train_step

    def slow_step(state, batch):
        state, metrics = step(state, batch)
        return state, dict(metrics, loss=late(metrics["loss"]))

    learner.train_step = slow_step


def test_inflight_reaches_the_steps_since_the_last_sync_and_stays_under_metrics_every(tmp_path):
    from dotaclient_tpu.runtime.learner import Learner

    mem.reset("sp_slow")
    _feed(connect("mem://sp_slow"), 8 * 8)
    cfg = _cfg("sp_slow", tmp_path, publish_every=1000)
    cfg.metrics_every = 4
    learner = Learner(cfg, connect("mem://sp_slow"))
    _slow_results(learner)
    seen = _logged(learner)
    try:
        assert learner.run(num_steps=8, batch_timeout=60.0, max_idle=3) == 8
    finally:
        learner.close()
    assert len(seen) == 2
    # the fourth dispatch of a window finds the three before it, and no more: the sync emptied the rest
    assert [w["loop_inflight_max"] for w in seen] == [3, 3]
    assert all(1.0 <= w["loop_inflight_mean"] <= 1.5 for w in seen)  # 0, 1, 2, 3 where none completes
    assert all(w["loop_inflight_max"] < cfg.metrics_every for w in seen)
    # only the dispatch after the sync found the queue empty, and the sync did the waiting
    assert seen[-1]["loop_starved_n_total"] == 1
    ready_n, ready_s = (seen[-1][k] for k in _keys("loop.sync_ready"))
    get_n, get_s = (seen[-1][k] for k in _keys("loop.sync_get"))
    assert ready_s / ready_n > 20 * get_s / get_n


def test_a_late_lane_starves_the_dispatch_and_it_is_charged_to_take(tmp_path):
    from dotaclient_tpu.runtime.learner import Learner

    mem.reset("sp_late")
    broker = connect("mem://sp_late")
    _feed(broker, 8 * 2)
    cfg = _cfg("sp_late", tmp_path, publish_every=1000)
    cfg.metrics_every = 1000  # one window, at the run's last step: no sync in between
    learner = Learner(cfg, connect("mem://sp_late"))
    seen = _logged(learner)
    late = threading.Timer(1.0, _feed, args=(broker, 8 * 2, 100))
    t0 = time.perf_counter()
    try:
        step = learner.train_step

        def feed_late(state, batch):  # the third batch comes a second after the second step
            out = step(state, batch)
            if learner.version == 1:
                late.start()
            return out

        learner.train_step = feed_late
        assert learner.run(num_steps=4, batch_timeout=60.0, max_idle=3) == 4
        wall = time.perf_counter() - t0
    finally:
        late.cancel()
        learner.close()
    (window,) = seen
    by_cause = {c: window[f"loop_starved_{c}_s_total"] for c in CAUSES}
    assert by_cause["take"] >= 0.9 and by_cause["sync"] == 0 and by_cause["checkpoint"] == 0
    assert 1 <= window["loop_starved_n_total"] <= 3
    assert sum(by_cause.values()) <= wall
    # the old scalar counts the same wait, and every other take with it
    assert window["pipeline_device_idle_s"] * 4 >= by_cause["take"] - 0.2


def test_the_first_dispatch_after_a_sync_is_charged_to_sync(tmp_path):
    from dotaclient_tpu.runtime.learner import Learner

    mem.reset("sp_sync")
    _feed(connect("mem://sp_sync"), 8 * 6)
    learner = Learner(_cfg("sp_sync", tmp_path, publish_every=1000), connect("mem://sp_sync"))
    seen = _logged(learner)
    log = learner.metrics.log

    def slow_log(step, scalars):  # what follows the read, inside the window's bookkeeping
        time.sleep(0.1)
        return log(step, scalars)

    learner.metrics.log = slow_log
    t0 = time.perf_counter()
    try:
        assert learner.run(num_steps=6, batch_timeout=60.0, max_idle=3) == 6
        wall = time.perf_counter() - t0
    finally:
        learner.close()
    first, last = seen[0], seen[-1]
    # syncs after steps 2, 4 and 6; dispatches 3 and 5 follow one
    assert last["loop_starved_n_total"] - first["loop_starved_n_total"] >= 2
    assert last["loop_starved_sync_s_total"] >= 0.2
    assert first["loop_starved_sync_s_total"] == 0  # nothing is charged before a dispatch finds it
    assert sum(last[f"loop_starved_{c}_s_total"] for c in CAUSES) <= wall


def test_a_runs_first_dispatch_is_charged_to_nothing(tmp_path):
    from dotaclient_tpu.runtime.learner import Learner

    mem.reset("sp_first")
    _feed(connect("mem://sp_first"), 8 * 2)
    learner = Learner(_cfg("sp_first", tmp_path, publish_every=1000), connect("mem://sp_first"))
    seen = _logged(learner)
    try:
        for _ in range(2):  # a phased driver's second run starts as the first did
            assert learner.run(num_steps=1, batch_timeout=60.0, max_idle=3) == 1
    finally:
        learner.close()
    assert len(seen) == 2
    for window in seen:
        assert window["loop_starved_n_total"] == 0
        assert all(window[f"loop_starved_{c}_s_total"] == 0 for c in CAUSES)
        assert window["loop_inflight_max"] == 0 and window["loop_inflight_mean"] == 0


class _Result:
    """A step's result as the loop holds it: ready when told."""

    def __init__(self):
        self.ready = False

    def is_ready(self):
        return self.ready


@pytest.mark.parametrize("cause", CAUSES)
def test_a_starved_dispatch_goes_to_what_the_loop_spent_most_of_the_interval_in(cause, monkeypatch):
    from dotaclient_tpu.runtime import learner as learner_mod

    clock = [100.0]
    monkeypatch.setattr(learner_mod.time, "perf_counter", lambda: clock[0])
    flight = learner_mod._InFlight()
    flight.begin_run()
    flight.poll()  # the run's first dispatch: nothing in flight, nothing charged
    first = _Result()
    flight.dispatched(first)
    assert flight.starved_n == 0
    clock[0] += 0.5
    flight.charge("take", 0.4)
    flight.poll()  # the step is still running: busy, whatever the loop waited for
    assert flight.starved_n == 0 and len(flight.pending) == 1
    second = _Result()
    flight.dispatched(second)
    first.ready = second.ready = True
    clock[0] += 1.0
    for name in CAUSES[:-1]:
        flight.charge(name, 0.6 if name == cause else 0.1)  # `other` gets the 0.7 that is left
    flight.poll()
    assert flight.starved_n == 1 and not flight.pending
    assert flight.starved_s == {c: (1.0 if c == cause else 0.0) for c in CAUSES}
    scalars = flight.window_scalars()
    assert scalars["loop_inflight_max"] == 1 and scalars["loop_inflight_mean"] == pytest.approx(1 / 3)
    assert scalars[f"loop_starved_{cause}_s_total"] == 1.0 and scalars["loop_starved_n_total"] == 1
    assert flight.window_scalars()["loop_inflight_max"] == 0  # the gauges are the window's, the totals stay


def test_a_result_that_is_not_ready_hides_the_ready_ones_behind_it():
    """Steps complete in order: the loop asks the oldest first and stops at
    the first that is pending, one call where nothing has finished."""
    from dotaclient_tpu.runtime.learner import _InFlight

    flight = _InFlight()
    results = [_Result() for _ in range(3)]
    for r in results:
        flight.dispatched(r)
    results[1].ready = True
    flight.poll()
    assert len(flight.pending) == 3
    results[0].ready = True
    flight.poll()
    assert list(flight.pending) == [results[2]]
    flight.synced()
    assert not flight.pending and flight.starved_n == 0


def test_a_publisher_given_a_host_pytree_still_publishes_and_age_is_under_latency(monkeypatch):
    from dotaclient_tpu.models.policy import init_params

    # A table of this test's own: its publishes have no loop's submit, and
    # counted in the process's table they would outnumber the submits of a
    # later learner run in this process, whose in-flight publishes are told
    # by the two counts.
    monkeypatch.setattr(spans, "_table", dict(spans._table))
    from dotaclient_tpu.runtime.learner import WeightPublisher

    class Keeping:
        def __init__(self):
            self.frames = []

        def publish_weights(self, frame):
            self.frames.append(frame)

    names = ("publish.d2h", "publish.ready_wait", "publish.copy", "publish.latency", "publish.age")
    params = jax.device_get(init_params(PolicyConfig(**POL), jax.random.PRNGKey(0)))
    broker = Keeping()
    pub = WeightPublisher(broker).start()
    each = []
    try:
        for version in (1, 2, 3):
            before = {name: _read(name) for name in names}
            pub.submit(params, version)
            deadline = time.monotonic() + 10
            while _read("publish.age")[0] == before["publish.age"][0] and time.monotonic() < deadline:
                time.sleep(0.005)
            each.append({name: tuple(b - a for a, b in zip(before[name], _read(name))) for name in names})
    finally:
        pub.stop(flush=True)
    assert (pub.published, pub.failed, len(broker.frames)) == (3, 0, 3)
    for one in each:  # every publish, not the sums alone
        assert all(one[name][0] == 1 for name in names)
        assert one["publish.age"][1] <= one["publish.latency"][1]
        assert one["publish.ready_wait"][1] + one["publish.copy"][1] <= one["publish.d2h"][1]
        assert one["publish.ready_wait"][1] < 0.05  # nothing to wait for


def test_one_clock_a_span_lies_inside_its_parent_on_the_profilers_timeline(tmp_path):
    from jax.profiler import ProfileData

    from dotaclient_tpu.runtime.learner import Learner

    mem.reset("sp_clock")
    _feed(connect("mem://sp_clock"), 8 * 4)
    learner = Learner(_cfg("sp_clock", tmp_path, publish_every=2), connect("mem://sp_clock"))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("t.enclosing"):
            with span("x", step=7):
                time.sleep(0.02)
        # the learner's two blocking reads, each split inside its old span
        assert learner.run(num_steps=4, batch_timeout=60.0, max_idle=3) == 4
    finally:
        jax.profiler.stop_trace()
        learner.close()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    inside = {"x": "t.enclosing",
              "publish.ready_wait": "publish.d2h", "publish.copy": "publish.d2h",
              "loop.sync_ready": "loop.sync", "loop.sync_get": "loop.sync"}
    names = set(inside) | set(inside.values())
    lines = {}  # child's name -> the lines that hold it: (line's name, [(name, start, end, stats)])
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                      for ev in line.events if ev.name in names]
            for child in {e[0] for e in events} & set(inside):
                lines.setdefault(child, []).append((line.name, events))
    for child, parent in inside.items():
        assert len(lines.get(child, [])) == 1, child  # one thread's line holds every one of them
        line_name, events = lines[child][0]
        parents = [e for e in events if e[0] == parent]
        children = [e for e in events if e[0] == child]
        assert children and len(children) == len(parents), child
        for _, start, end, stats in children:  # each inside a parent of its own, on that line
            assert [1 for _, a, b, ids in parents
                    if a <= start and end <= b and all(stats[k] == v for k, v in ids.items() if k in stats)
                    ] == [1], (child, start, end)
        if child.startswith("publish."):
            assert line_name == "weight-publishe"
    (x,) = [e for e in lines["x"][0][1] if e[0] == "x"]
    assert x[2] - x[1] >= 20e6 and x[3]["step"] == 7
    # the wait comes first and the read after it, without overlap
    events = lines["loop.sync_ready"][0][1]
    for (_, _, ready_end, a), (_, get_start, _, b) in zip(
            [e for e in events if e[0] == "loop.sync_ready"], [e for e in events if e[0] == "loop.sync_get"]):
        assert a["step"] == b["step"] and ready_end <= get_start


@pytest.mark.parametrize("path", ["single", "tree", "reuse"])
def test_lowered_step_carries_its_layers_in_its_locations(path):
    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.parallel.train_step import (
        build_single_train_step,
        build_train_step,
        init_train_state,
        make_train_batch,
    )

    cfg = LearnerConfig(batch_size=8, seq_len=4, policy=PolicyConfig(**POL),
                        ppo=PPOConfig(epochs=2, minibatches=2) if path == "reuse" else PPOConfig())
    mesh = mesh_lib.make_mesh(cfg.mesh_shape, devices=jax.devices()[:1])
    state = jax.eval_shape(lambda: init_train_state(cfg, jax.random.PRNGKey(0)))
    if path == "tree":
        step, _, _ = build_train_step(cfg, mesh)
        batch = jax.eval_shape(lambda: make_train_batch(cfg))
        layers = ("trunk", "lstm", "heads", "loss", "optimizer")
    else:
        step, _, io = build_single_train_step(cfg, mesh)
        payload, _ = io.alloc_transfer()
        batch = jax.ShapeDtypeStruct(payload.shape, payload.dtype)
        layers = ("unpack", "trunk", "lstm", "heads", "loss", "optimizer")
    text = step.lower(state, batch).as_text(debug_info=True)
    for layer in layers:
        assert f"/{layer}/" in text, layer
    # forward and transpose alike, the policy's layers inside the loss
    assert "/loss/jvp(PolicyNet)/core/trunk/" in text
    assert "/loss/transpose(jvp(PolicyNet))/core/lstm/" in text


def test_a_publish_the_broker_refuses_counts_as_failed():
    from dotaclient_tpu.runtime.learner import WeightPublisher
    from dotaclient_tpu.models.policy import init_params

    class Refusing:
        def __init__(self):
            self.frames = []
            self.refuse = True

        def publish_weights(self, frame):
            if self.refuse:
                raise ConnectionError("broker refused the weight frame")
            self.frames.append(frame)

    params = jax.device_get(init_params(PolicyConfig(**POL), jax.random.PRNGKey(0)))
    broker = Refusing()
    before = spans.scalars().get("span_publish_send_n_total", 0.0)
    pub = WeightPublisher(broker).start()
    try:
        pub.submit(params, 1)
        deadline = time.monotonic() + 10
        while pub.failed < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert (pub.failed, pub.published) == (1, 0)
        broker.refuse = False
        pub.submit(params, 2)
    finally:
        pub.stop(flush=True)
    assert (pub.failed, pub.published, len(broker.frames)) == (1, 1, 1)
    # the refused send was timed too: two sends, one latency (only what was sent has one)
    assert spans.scalars()["span_publish_send_n_total"] == before + 2


def test_profile_port_is_gone():
    assert not hasattr(LearnerConfig(), "profile_port")
    with pytest.raises(SystemExit):
        parse_config(LearnerConfig(), ["--profile_port", "9999"])
