"""WeightPublisher: off-thread weight fanout with latest-wins coalescing
(runtime/learner.py — the r3 pipelining change that moved serialize +
broker I/O off the train loop's critical path)."""

import threading
import time

import numpy as np

from dotaclient_tpu.runtime.learner import WeightPublisher
from dotaclient_tpu.transport.base import Broker
from dotaclient_tpu.transport.serialize import deserialize_weights


def _params(v: float):
    return {"dense": {"kernel": np.full((4, 4), v, np.float32)}}


class _RecordingBroker(Broker):
    def __init__(self, publish_delay: float = 0.0):
        self.frames = []
        self.publish_delay = publish_delay
        self.fail_next = 0

    def publish_weights(self, data: bytes) -> None:
        if self.fail_next > 0:
            self.fail_next -= 1
            raise ConnectionError("injected broker outage")
        if self.publish_delay:
            time.sleep(self.publish_delay)
        self.frames.append(data)

    def publish_experience(self, data: bytes) -> None:
        raise AssertionError("publisher must not touch experience")

    def consume_experience(self, max_items, timeout=None):
        raise AssertionError("publisher must not consume")

    def poll_weights(self):
        return self.frames[-1] if self.frames else None


def test_publishes_in_order_and_stop_flushes():
    broker = _RecordingBroker()
    pub = WeightPublisher(broker).start()
    for v in range(1, 4):
        pub.submit(_params(float(v)), version=v)
        # wait for the drain rather than sleeping a fixed interval — a
        # descheduled publisher thread must not fake a coalesce
        deadline = time.monotonic() + 10.0
        while pub.published < v and time.monotonic() < deadline:
            time.sleep(0.005)
    pub.stop()  # default flush=True drains any pending slot
    assert pub.published == 3 and pub.coalesced == 0
    versions = [deserialize_weights(f)[1] for f in broker.frames]
    assert versions == [1, 2, 3]


def test_coalesces_to_latest_under_slow_broker():
    broker = _RecordingBroker(publish_delay=0.15)
    pub = WeightPublisher(broker).start()
    # submit faster than the broker drains: intermediate versions must be
    # superseded, never queued (actors only want the newest weights)
    for v in range(1, 8):
        pub.submit(_params(float(v)), version=v)
        time.sleep(0.01)
    pub.stop()
    versions = [deserialize_weights(f)[1] for f in broker.frames]
    assert versions[-1] == 7, "newest version must always be delivered"
    assert pub.coalesced > 0, "slow broker must coalesce, not queue"
    assert len(versions) < 7
    assert versions == sorted(versions), "never deliver out of order"
    named, _, _ = deserialize_weights(broker.frames[-1])
    np.testing.assert_array_equal(dict(named)["dense/kernel"], np.full((4, 4), 7.0, np.float32))


def test_broker_error_does_not_kill_publisher():
    broker = _RecordingBroker()
    broker.fail_next = 1
    pub = WeightPublisher(broker).start()
    pub.submit(_params(1.0), version=1)  # eaten by the injected outage
    deadline = time.monotonic() + 5.0
    while pub.published == 0 and time.monotonic() < deadline:
        pub.submit(_params(2.0), version=2)
        time.sleep(0.02)
    pub.stop()
    assert pub.published >= 1, "publisher thread must survive a broker error"
    assert deserialize_weights(broker.frames[-1])[1] == 2


def test_restartable_after_stop():
    broker = _RecordingBroker()
    pub = WeightPublisher(broker).start()
    pub.submit(_params(1.0), version=1)
    pub.stop()
    pub.start()  # phased drivers restart (same contract as StagingBuffer)
    pub.submit(_params(2.0), version=2)
    pub.stop()
    assert [deserialize_weights(f)[1] for f in broker.frames] == [1, 2]


def test_param_flattener_matches_flatten_params():
    """The fused single-buffer publish layout must reproduce
    flatten_params' canonical named list exactly — the wire consumers
    (actor hot-swap, league snapshots) see identical frames."""
    import jax

    from dotaclient_tpu.config import PolicyConfig
    from dotaclient_tpu.models.policy import init_params
    from dotaclient_tpu.runtime.learner import ParamFlattener
    from dotaclient_tpu.transport.serialize import flatten_params

    for arch in ("lstm", "transformer"):
        cfg = PolicyConfig(
            arch=arch,
            unit_embed_dim=16,
            lstm_hidden=16,
            mlp_hidden=16,
            dtype="float32",
            tf_layers=1,
            tf_heads=2,
            tf_context=4,
        )
        params = init_params(cfg, jax.random.PRNGKey(3))
        fl = ParamFlattener(params)
        got = fl.to_named(fl.flatten_on_device(params))
        want = flatten_params(jax.device_get(params))
        assert [n for n, _ in got] == [n for n, _ in want]
        for (n, a), (_, b) in zip(got, want):
            assert a.shape == b.shape, n
            np.testing.assert_array_equal(a, b, err_msg=n)


def test_learner_publishes_correct_weights_via_fused_path():
    """End of a short run: the newest broadcast frame deserializes to the
    learner's CURRENT params (async flatten + publisher-thread read did
    not tear or reorder)."""
    import jax

    from dotaclient_tpu.config import LearnerConfig, PolicyConfig
    from dotaclient_tpu.runtime.learner import Learner
    from dotaclient_tpu.transport import memory as mem
    from dotaclient_tpu.transport.base import connect
    from dotaclient_tpu.transport.serialize import flatten_params, serialize_rollout
    from tests.test_transport import make_rollout

    mem.reset("fpub")

    broker = connect("mem://fpub")
    for i in range(16):
        broker.publish_experience(serialize_rollout(make_rollout(L=4, H=16, version=0, seed=i)))
    cfg = LearnerConfig(
        batch_size=8,
        seq_len=4,
        policy=PolicyConfig(unit_embed_dim=16, lstm_hidden=16, mlp_hidden=16, dtype="float32"),
        publish_every=1,
    )
    learner = Learner(cfg, connect("mem://fpub"))
    sub = connect("mem://fpub")
    learner.run(num_steps=2, batch_timeout=60.0)
    frame = sub.poll_weights()
    assert frame is not None
    named, version, boot_epoch = deserialize_weights(frame)
    assert version == learner.version == 2
    assert boot_epoch == learner.boot_epoch != 0
    want = dict(flatten_params(jax.device_get(learner.state.params)))
    got = dict(named)
    assert set(got) == set(want)
    for n in want:
        np.testing.assert_array_equal(got[n], want[n], err_msg=n)


def test_legacy_dtw1_transition_flag():
    """ADVICE r4: LearnerConfig.publish_legacy_dtw1 routes through the
    publisher so a rolling upgrade can keep old subscribers parsing —
    frames go out as DTW1 (no boot_epoch) and still round-trip."""
    broker = _RecordingBroker()
    pub = WeightPublisher(broker, boot_epoch=1234, legacy_dtw1=True).start()
    pub.submit(_params(2.5), version=6)
    deadline = time.monotonic() + 10.0
    while pub.published < 1 and time.monotonic() < deadline:
        time.sleep(0.005)
    pub.stop()
    assert broker.frames and broker.frames[-1][:4] == b"DTW1"
    named, version, boot_epoch = deserialize_weights(broker.frames[-1])
    assert version == 6 and boot_epoch == 0  # DTW1 carries no epoch
    np.testing.assert_array_equal(named[0][1], np.full((4, 4), 2.5, np.float32))


def test_frame_through_the_memory_broker_reads_back_equal():
    """Whatever type the frame has, `mem://` takes it as it is and a
    subscriber reads every leaf back."""
    from dotaclient_tpu.transport import memory as mem
    from dotaclient_tpu.transport.base import connect
    from dotaclient_tpu.transport.serialize import flatten_params

    mem.reset("pubframe")
    params = {
        "core": {"kernel": np.arange(48, dtype=np.float32).reshape(6, 8), "bias": np.ones(8, np.float32)},
        "head": {"kernel": np.linspace(-1, 1, 24, dtype=np.float32).reshape(8, 3)},
    }
    pub = WeightPublisher(connect("mem://pubframe"), boot_epoch=77).start()
    sub = connect("mem://pubframe")
    try:
        pub.submit(params, version=5)
        deadline = time.monotonic() + 10.0
        while pub.published < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
    finally:
        pub.stop()
    assert pub.published == 1 and pub.failed == 0
    frame = sub.poll_weights()
    assert frame is not None and sub.poll_weights() is None
    named, version, boot_epoch = deserialize_weights(frame)
    assert (version, boot_epoch) == (5, 77)
    want = flatten_params(params)
    assert [n for n, _ in named] == [n for n, _ in want]
    for (_, got), (_, leaf) in zip(named, want):
        np.testing.assert_array_equal(got, leaf)
    mem.reset("pubframe")
