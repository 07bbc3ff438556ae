"""obs/spans.py and what the learner process records with it: the table
(sums and counts, across threads), the flight-recorder mirror, the
scalars a learner emits, one clock with the profiler's trace, the named
scopes inside the compiled step, the publisher's failure count."""

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
    "loop.dispatch", "loop.publish_submit", "loop.sync", "lane.handoff",
    "staging.pop", "staging.ingest", "staging.pack", "staging.ready_wait",
    "publish.d2h", "publish.serialize", "publish.send", "publish.latency",
    "setup.learner_init", "setup.init_params", "setup.publish0",
)
# ... what needs a ring lease or a checkpoint directory to happen ...
OPTIONAL_SPANS = ("lane.retire", "loop.checkpoint", "setup.restore")
# ... and what goes onto the profiler's timeline alone, because the program
# sums it already (pipeline_device_idle_s, time_wait_batch_s, time_device_put_s).
TIMELINE_ONLY = ("loop.take", "lane.wait_batch", "lane.device_put")


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
                "compile_s_total"):
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


def test_one_clock_a_span_lies_inside_its_parent_on_the_profilers_timeline(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("t.enclosing"):
            with span("x", step=7):
                time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    found = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = {ev.name: ev for ev in line.events if ev.name in ("x", "t.enclosing")}
            if "x" in events:
                found.append(events)
    assert len(found) == 1 and set(found[0]) == {"x", "t.enclosing"}  # one line holds both
    x, outer = found[0]["x"], found[0]["t.enclosing"]
    assert x.duration_ns >= 20e6
    assert outer.start_ns <= x.start_ns
    assert x.start_ns + x.duration_ns <= outer.start_ns + outer.duration_ns
    assert dict(x.stats)["step"] == 7


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
