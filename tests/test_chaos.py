"""Chaos layer (dotaclient_tpu/chaos/): seeded determinism, fault
mechanics, production inertness, degradation paths (quarantine, shed
throttle, kill/restart recovery), and the nightly soak wrapper."""

import asyncio
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from dotaclient_tpu.chaos import (
    BrokerIncarnations,
    ChaosBroker,
    FaultSchedule,
    ScheduleRunner,
)
from dotaclient_tpu.chaos.schedule import corrupt_bytes, truncate_bytes
from dotaclient_tpu.config import ChaosConfig, LearnerConfig, PolicyConfig
from dotaclient_tpu.runtime.staging import StagingBuffer
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import BrokerShedError, RetryPolicy, connect
from dotaclient_tpu.transport.memory import MemoryBroker
from tests.test_transport import make_rollout

SMALL = PolicyConfig(unit_embed_dim=8, lstm_hidden=8, mlp_hidden=8, dtype="float32")


# ------------------------------------------------------------- schedule


def test_schedule_decisions_are_deterministic():
    """Same seed + spec ⇒ identical faults at identical op indices —
    the property that makes a chaos failure replayable."""
    spec = "corrupt:0.1,dup:0.05,reset:0.02,latency:0.002~0.001"
    a = FaultSchedule.parse(spec, seed=11)
    b = FaultSchedule.parse(spec, seed=11)
    rows_a = [(f.corrupt, f.dup, f.reset, round(f.latency_s, 9)) for f in map(a.decide, range(500))]
    rows_b = [(f.corrupt, f.dup, f.reset, round(f.latency_s, 9)) for f in map(b.decide, range(500))]
    assert rows_a == rows_b
    assert any(r[0] for r in rows_a) and any(r[1] for r in rows_a)
    # a different seed moves the faults
    c = FaultSchedule.parse(spec, seed=12)
    assert rows_a != [
        (f.corrupt, f.dup, f.reset, round(f.latency_s, 9)) for f in map(c.decide, range(500))
    ]


def test_schedule_decisions_stable_under_spec_extension():
    """Adding an unrelated clause must not shift the other draws (the
    fixed canonical draw order): corrupt decisions are identical with
    and without a dup clause."""
    a = FaultSchedule.parse("corrupt:0.1", seed=5)
    b = FaultSchedule.parse("corrupt:0.1,dup:0.3", seed=5)
    assert [a.decide(i).corrupt for i in range(300)] == [
        b.decide(i).corrupt for i in range(300)
    ]


def test_schedule_grammar_and_events():
    s = FaultSchedule.parse("kill@10:2,stall@5:1.5,kill@20:3,latency:0.01~0.002", seed=0)
    assert [(e.at_s, e.duration_s) for e in s.kills()] == [(10.0, 2.0), (20.0, 3.0)]
    assert s.stall_remaining(5.5) == pytest.approx(1.0)
    assert s.stall_remaining(7.0) == 0.0
    with pytest.raises(ValueError):
        FaultSchedule.parse("explode:0.5")
    with pytest.raises(ValueError):
        FaultSchedule.parse("corrupt:1.5")
    with pytest.raises(ValueError):
        FaultSchedule.parse("melt@3:1")


def test_kill_target_selector_grammar():
    """kill@T:D@TGT[:SIG] — broker default, learner with SIGKILL/SIGTERM
    variants; selectors on anything else are spec errors."""
    s = FaultSchedule.parse(
        "kill@10:3,kill@20:2@learner:term,kill@30:2@learner,kill@40:1@broker", seed=0
    )
    rows = [(e.at_s, e.duration_s, e.target, e.signal) for e in s.kills()]
    assert rows == [
        (10.0, 3.0, "broker", "kill"),
        (20.0, 2.0, "learner", "term"),
        (30.0, 2.0, "learner", "kill"),
        (40.0, 1.0, "broker", "kill"),
    ]
    with pytest.raises(ValueError):
        FaultSchedule.parse("stall@5:1@learner")  # selector is kill-only
    with pytest.raises(ValueError):
        FaultSchedule.parse("kill@5:1@broker:term")  # broker has no signal
    with pytest.raises(ValueError):
        FaultSchedule.parse("kill@5:1@learner:hup")
    with pytest.raises(ValueError):
        FaultSchedule.parse("kill@5:1@evaluator")


# Golden decision sequence: (corrupt, truncate, dup, reset, shed) for op
# indices 0..47 under seed=3 and the spec below, one 5-char 0/1 group per
# index. Pinned VALUES, not just self-consistency: any change to the
# canonical draw order — including one smuggled in by a grammar
# extension — breaks replayability of every recorded chaos run.
_GOLDEN_SPEC = "corrupt:0.04,truncate:0.03,dup:0.05,reset:0.02,shed:0.03,latency:0.002~0.001"
_GOLDEN_SEQ = (
    "010000000001000001000000000000000000000000100000000000000000"
    "100000000000000000000000000000000000000000000000000000000000"
    "000000000010000000000000001000000000000000000000000000000000"
    "000000000000000000001010000100000000000000000000000000000000"
)


def test_golden_decision_sequence_pinned():
    flags = ("corrupt", "truncate", "dup", "reset", "shed")

    def seq(spec):
        s = FaultSchedule.parse(spec, seed=3)
        return "".join(
            "".join(str(int(getattr(s.decide(i), n))) for n in flags) for i in range(48)
        )

    assert seq(_GOLDEN_SPEC) == _GOLDEN_SEQ
    # Kill-target selectors are timed events: they must not consume (or
    # shift) a single rate draw — for EVERY target the grammar knows,
    # including the PR-10 server target (the ARG-side extension rule).
    assert seq(_GOLDEN_SPEC + ",kill@10:2@learner:term,kill@20:2@learner") == _GOLDEN_SEQ
    assert seq(_GOLDEN_SPEC + ",kill@5:1") == _GOLDEN_SEQ
    assert seq(_GOLDEN_SPEC + ",kill@7:2@server") == _GOLDEN_SEQ
    assert seq(_GOLDEN_SPEC + ",kill@3:1@server,kill@9:2@broker,kill@12:1@server") == _GOLDEN_SEQ
    # the PR-13 rolling-restart grammar is ARG-side too: zero rate draws
    assert seq(_GOLDEN_SPEC + ",rolling@6:1@server") == _GOLDEN_SEQ
    assert seq(_GOLDEN_SPEC + ",rolling@2:0.5@server,kill@9:2@server,rolling@15:1@server") == _GOLDEN_SEQ
    # and the broker-fabric rolling target (PR 14): still ARG-side only
    assert seq(_GOLDEN_SPEC + ",rolling@4:1@broker") == _GOLDEN_SEQ
    assert seq(_GOLDEN_SPEC + ",rolling@2:0.5@broker,kill@9:2@broker,rolling@15:1@server") == _GOLDEN_SEQ
    # scale set-points (PR 16) are ARG-side topology events: zero rate
    # draws for every tier the grammar knows, alone or mixed with the
    # kill-class clauses they script alongside
    assert seq(_GOLDEN_SPEC + ",scale@5:4@server") == _GOLDEN_SEQ
    assert seq(_GOLDEN_SPEC + ",scale@2:3@broker,scale@8:2@actor,scale@11:2@server") == _GOLDEN_SEQ
    assert seq(_GOLDEN_SPEC + ",scale@3:4@server,rolling@6:1@server,kill@9:2@broker") == _GOLDEN_SEQ
    # latency draw position pinned too (it follows the five rate draws)
    s = FaultSchedule.parse(_GOLDEN_SPEC + ",kill@9:1@learner", seed=3)
    assert round(s.decide(0).latency_s, 9) == 0.00253577
    assert round(s.decide(47).latency_s, 9) == 0.002151729


def test_rolling_grammar_parses_and_rejects():
    """rolling@T:P@server|broker — staggered sequential restarts across
    a replicated tier (the serve tier, or the broker fabric's shard
    fleet). The learner is a singleton where rolling degenerates to
    kill and stays rejected; bare form defaults to server, and kills()
    returns rolling events (they are kill-class work for the
    ScheduleRunner)."""
    s = FaultSchedule.parse("rolling@5:1.5@server,kill@10:2", seed=0)
    ev, kv = s.kills()
    assert (ev.kind, ev.at_s, ev.duration_s, ev.target) == ("rolling", 5.0, 1.5, "server")
    assert kv.kind == "kill" and kv.target == "broker"
    assert FaultSchedule.parse("rolling@1:2", seed=0).kills()[0].target == "server"
    # the PR-14 broker-fabric target
    bv = FaultSchedule.parse("rolling@3:1@broker", seed=0).kills()[0]
    assert (bv.kind, bv.target, bv.at_s, bv.duration_s) == ("rolling", "broker", 3.0, 1.0)
    for bad in (
        "rolling@1:2@learner",
        "rolling@1:2@server:term",
        "rolling@1:2@broker:term",
        "stall@1:2@server",
    ):
        with pytest.raises(ValueError):
            FaultSchedule.parse(bad)


def test_scale_grammar_parses_and_rejects():
    """scale@T:N@broker|server|actor — deterministic topology
    set-points for the control tier. N rides the duration slot (whole
    replica counts >= 1 only), the tier selector is MANDATORY, and the
    events surface through scales() — NOT kills(), so every existing
    ScheduleRunner routes exactly what it did before."""
    s = FaultSchedule.parse(
        "scale@5:4@server,kill@10:2,scale@20:2@broker,scale@30:8@actor", seed=0
    )
    rows = [(e.at_s, int(e.duration_s), e.target) for e in s.scales()]
    assert rows == [(5.0, 4, "server"), (20.0, 2, "broker"), (30.0, 8, "actor")]
    assert all(e.kind == "scale" for e in s.scales())
    # kills() is untouched by scale clauses
    assert [(e.kind, e.at_s) for e in s.kills()] == [("kill", 10.0)]
    for bad in (
        "scale@5:4",  # tier is mandatory
        "scale@5:4@learner",  # singleton tier — not scalable
        "scale@5:4@server:term",  # no signal selector
        "scale@5:0@server",  # scale-to-zero is a kill
        "scale@5:1.5@server",  # fractional replicas
    ):
        with pytest.raises(ValueError):
            FaultSchedule.parse(bad)


def test_rolling_broker_runner_routes_to_broker_controller_with_probe():
    """rolling@T:P@broker fans kill→down→restart→probe across the BROKER
    controller's replicas (a replica_count() router over fabric shards,
    or a bare BrokerIncarnations = 1), using the first-enqueue probe —
    and refuses to start with no broker controller at all."""
    import time as _time

    from dotaclient_tpu.chaos.controller import ScheduleRunner

    class ShardRouter:
        def __init__(self, n):
            self.n = n
            self.kills = []
            self.restarts = []
            self.probes = 0

        def replica_count(self):
            return self.n

        def kill(self):
            self.kills.append(_time.monotonic())

        def restart(self):
            self.restarts.append(_time.monotonic())

        def wait_first_enqueue(self, timeout=30.0, stop=None):
            self.probes += 1
            return _time.monotonic()

    router = ShardRouter(3)
    runner = ScheduleRunner(
        FaultSchedule.parse("rolling@0.02:0.03@broker", seed=0),
        broker=router,
        t0=_time.monotonic(),
    ).start()
    deadline = _time.monotonic() + 10
    while len(router.restarts) < 3 and _time.monotonic() < deadline:
        _time.sleep(0.01)
    runner.stop()
    assert len(router.kills) == 3 and len(router.restarts) == 3
    assert router.probes == 3
    assert [e["replica"] for e in runner.recovery] == [0, 1, 2]
    assert all(e["kind"] == "rolling" and e["target"] == "broker" for e in runner.recovery)
    for i in range(2):
        assert router.restarts[i] <= router.kills[i + 1], "two shards down at once"

    with pytest.raises(ValueError, match="broker"):
        ScheduleRunner(
            FaultSchedule.parse("rolling@1:1@broker", seed=0), broker=None, t0=0.0
        )


def test_rolling_runner_fans_kills_across_replicas_sequentially():
    """The rolling executor asks the controller for replica_count() and
    runs kill→down-window→restart per replica SEQUENTIALLY — restart i
    always precedes kill i+1, so at most one replica is ever down (the
    property the zero-abandon handoff soak rides on)."""
    import time as _time

    from dotaclient_tpu.chaos.controller import ScheduleRunner

    class Router:
        def __init__(self, n):
            self.n = n
            self.kills = []
            self.restarts = []

        def replica_count(self):
            return self.n

        def kill(self):
            self.kills.append(_time.monotonic())

        def restart(self):
            self.restarts.append(_time.monotonic())

    router = Router(3)
    runner = ScheduleRunner(
        FaultSchedule.parse("rolling@0.02:0.03@server", seed=0),
        broker=None,
        t0=_time.monotonic(),
        server=router,
    ).start()
    deadline = _time.monotonic() + 10
    while len(router.restarts) < 3 and _time.monotonic() < deadline:
        _time.sleep(0.01)
    runner.stop()
    assert len(router.kills) == 3 and len(router.restarts) == 3
    assert [e["replica"] for e in runner.recovery] == [0, 1, 2]
    assert all(e["kind"] == "rolling" and e["target"] == "server" for e in runner.recovery)
    for i in range(2):
        assert router.restarts[i] <= router.kills[i + 1], "two replicas down at once"
    # down windows honored: each replica stayed down ~duration_s
    for kt, rt in zip(router.kills, router.restarts):
        assert rt - kt >= 0.028

    # a rolling schedule against no server controller refuses loudly
    with pytest.raises(ValueError, match="server"):
        ScheduleRunner(
            FaultSchedule.parse("rolling@1:1@server", seed=0), broker=None, t0=0.0
        )


def test_corrupt_hits_magic_truncate_shortens():
    import random

    data = b"DTR1" + bytes(range(200))
    bad = corrupt_bytes(data, random.Random(3))
    assert len(bad) == len(data) and bad[:4] != b"DTR1"
    cut = truncate_bytes(data, random.Random(3))
    assert len(data) // 2 <= len(cut) < len(data)


# ---------------------------------------------------------- chaos broker


def _chaos(name, spec, seed=0, maxlen=64, **hub_kw):
    mem.reset(name)
    return ChaosBroker(MemoryBroker(name, maxlen=maxlen, **hub_kw), FaultSchedule.parse(spec, seed=seed))


def test_chaos_broker_reset_and_shed_faults_raise():
    cb = _chaos("cx-rs", "reset:1.0")
    with pytest.raises(ConnectionResetError):
        cb.publish_experience(b"frame")
    assert cb.meters["chaos_resets"] == 1
    cb2 = _chaos("cx-sh", "shed:1.0")
    with pytest.raises(BrokerShedError):
        cb2.publish_experience(b"frame")
    assert cb2.meters["chaos_sheds"] == 1
    # nothing reached the inner broker
    assert cb.experience_depth() == 0 and cb2.experience_depth() == 0


def test_chaos_broker_corrupts_deliver_and_count():
    cb = _chaos("cx-c", "corrupt:1.0")
    cb.publish_experience(b"DTR1" + b"\x00" * 64)
    assert cb.meters["chaos_corrupted"] == 1
    (frame,) = cb.consume_experience(10, timeout=0.2)
    assert frame[:4] != b"DTR1"  # poison delivered — quarantine's job now


def test_chaos_broker_dup_counts_only_delivered_extras():
    """A duplicate that the broker refuses must not be claimed by the
    conservation ledger's dup-extras meter."""
    mem.reset("cx-dup")
    # maxlen 2 with watermarks 2/1: the dup of the second frame is shed
    inner = MemoryBroker("cx-dup", maxlen=8, shed_high=2, shed_low=1)
    cb = ChaosBroker(inner, FaultSchedule.parse("dup:1.0", seed=0))
    cb.publish_experience(b"a")  # a + dup(a) -> depth 2
    assert cb.meters["chaos_duplicated"] == 1
    with pytest.raises(BrokerShedError):
        cb.publish_experience(b"b")  # original already refused
    assert cb.meters["chaos_duplicated"] == 1  # no phantom extra
    assert inner._hub.shed_total >= 1


def test_chaos_off_is_import_free_and_wire_identical():
    """The inertness contract: chaos disabled ⇒ the chaos package is
    never imported by the binaries' import graph, and connect() hands
    back the bare production broker object."""
    code = (
        "import sys\n"
        "import dotaclient_tpu.runtime.actor, dotaclient_tpu.runtime.learner\n"
        "import dotaclient_tpu.transport.tcp, dotaclient_tpu.transport.memory\n"
        "assert not any(m.startswith('dotaclient_tpu.chaos') for m in sys.modules), "
        "sorted(m for m in sys.modules if m.startswith('dotaclient_tpu.chaos'))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 0, proc.stderr
    mem.reset("cx-off")
    assert type(connect("mem://cx-off")) is MemoryBroker
    assert ChaosConfig().enabled is False  # the default that keeps it so


# ------------------------------------------------- shed throttle (actor)


def test_memory_broker_watermark_hysteresis():
    mem.reset("wm")
    b = MemoryBroker("wm", maxlen=16, shed_high=4, shed_low=2)
    for i in range(4):
        b.publish_experience(bytes([i]))
    with pytest.raises(BrokerShedError):
        b.publish_experience(b"over")  # at high watermark: refused
    assert b.shed_observed == 1
    b.consume_experience(1, timeout=0.1)  # depth 3: still shedding (hysteresis)
    with pytest.raises(BrokerShedError):
        b.publish_experience(b"still")
    b.consume_experience(10, timeout=0.1)  # drained to 0 <= low: resume
    b.publish_experience(b"ok")
    assert b.experience_depth() == 1


def test_shed_throttle_drops_backs_off_and_recovers():
    from dotaclient_tpu.runtime.actor import ShedThrottle

    mem.reset("thr")
    b = MemoryBroker("thr", maxlen=16, shed_high=2, shed_low=1)
    thr = ShedThrottle(RetryPolicy(window_s=5, backoff_base_s=0.01, backoff_cap_s=0.05, jitter=0.5))

    async def go():
        assert await thr.publish(b, b"f1") is True
        assert await thr.publish(b, b"f2") is True
        ok = await thr.publish(b, b"f3")  # depth 2 = high -> shed
        assert ok is False
        b.consume_experience(10, timeout=0.1)
        assert await thr.publish(b, b"f4") is True

    asyncio.new_event_loop().run_until_complete(go())
    assert thr.shed == 1 and thr.published == 3
    assert thr.throttle_s > 0.0
    s = thr.stats()
    assert s["broker_shed_observed_total"] == 1.0


def test_shed_throttle_survives_transport_failure():
    from dotaclient_tpu.runtime.actor import ShedThrottle

    class DeadBroker:
        def publish_experience(self, data):
            raise ConnectionResetError("injected")

    thr = ShedThrottle(RetryPolicy(window_s=1, backoff_base_s=0.01, backoff_cap_s=0.02))

    async def go():
        assert await thr.publish(DeadBroker(), b"x") is False

    asyncio.new_event_loop().run_until_complete(go())
    assert thr.failed == 1


# -------------------------------------------------------- chaos env stub


def test_chaos_env_stub_session_loss_is_survivable():
    """ChaosEnvStub faults stay INSIDE the env protocol: a seeded
    session-loss observe() returns RESOURCE_EXHAUSTED, which the actor
    already survives by abandoning the episode — no new exception
    taxonomy, latency metered."""
    from dotaclient_tpu.chaos import wrap_env_stub
    from dotaclient_tpu.config import ActorConfig
    from dotaclient_tpu.env.fake_dotaservice import FakeDotaService
    from dotaclient_tpu.env.service import LocalDotaServiceStub
    from dotaclient_tpu.eval.evaluator import NullBroker
    from dotaclient_tpu.runtime.actor import Actor

    cfg = ActorConfig(
        env_addr="local", rollout_len=4, max_dota_time=2.0, policy=SMALL, max_weight_age_s=0.0
    )
    stub = wrap_env_stub(
        LocalDotaServiceStub(FakeDotaService()),
        ChaosConfig(enabled=True, seed=1, spec="reset:1.0,latency:0.001"),
    )
    actor = Actor(cfg, NullBroker(), stub=stub)
    asyncio.new_event_loop().run_until_complete(actor.run_episode())
    assert actor.episodes_done == 1  # abandoned gracefully, not crashed
    assert stub.sessions_lost >= 1
    assert stub.latency_s > 0.0


# ------------------------------------------------ staging quarantine


def test_staging_quarantines_poison_with_evidence():
    """Parse- and layout-poison frames land in the dead-letter ring with
    reason + header prefix, count as staging_quarantined, and ride
    flight-recorder dumps as a section."""
    from dotaclient_tpu.obs.flight_recorder import FlightRecorder
    from dotaclient_tpu.transport.serialize import serialize_rollout

    mem.reset("quar")
    broker = connect("mem://quar")
    cfg = LearnerConfig(batch_size=4, seq_len=8, policy=SMALL)
    rec = FlightRecorder("test-quar", dump_dir="/tmp")
    st = StagingBuffer(cfg, broker, recorder=rec)
    good = serialize_rollout(make_rollout(L=4, H=8, version=0))
    poison_parse = b"GARBAGE-NOT-A-FRAME" * 3
    # layout poison: valid frame built with the WRONG lstm width
    poison_layout = serialize_rollout(make_rollout(L=4, H=16, version=0))
    for f in (good, poison_parse, poison_layout):
        broker.publish_experience(f)
    st.start()
    deadline = time.time() + 10
    while st.stats()["consumed"] < 3 and time.time() < deadline:
        time.sleep(0.05)
    st.stop()
    stats = st.stats()
    assert stats["quarantined"] == 2
    assert stats["dropped_bad"] == 2  # the aggregate counter still ticks
    ring = st.quarantine()
    assert [e["reason"] for e in ring] == ["parse", "layout"]
    assert ring[0]["head"].startswith(poison_parse[:8].hex())
    assert ring[1]["bytes"] == len(poison_layout)
    path = rec.dump("quarantine_test")
    try:
        payload = json.load(open(path))
        assert payload["sections"]["staging_quarantine"] == ring
    finally:
        os.unlink(path)


# ---------------------------------------- kill/restart (controller)


def test_broker_incarnations_kill_restart_and_ledger_identity():
    from dotaclient_tpu.transport.tcp import TcpBroker

    inc = BrokerIncarnations(port=0, maxlen=32)
    client = TcpBroker(port=inc.port, retry=RetryPolicy(window_s=10, backoff_base_s=0.05))
    client.publish_experience(b"f1")
    client.publish_experience(b"f2")
    got = client.consume_experience(10, timeout=1.0)
    assert got == [b"f1", b"f2"]
    client.publish_experience(b"dies-with-broker")
    led = inc.kill()
    assert led["enqueued"] == 3 and led["popped"] == 2 and led["resident"] == 1
    inc.restart()
    client.publish_experience(b"after-restart")  # retry loop reconnects
    assert inc.server.first_enqueue_t is not None
    total = inc.final_ledger()
    assert total["incarnations"] == 2
    assert total["enqueued"] == total["popped"] + total["dropped_oldest"] + total["resident"]
    client.close()


def test_schedule_runner_executes_kills_and_reports_recovery():
    from dotaclient_tpu.transport.tcp import TcpBroker

    inc = BrokerIncarnations(port=0, maxlen=32)
    schedule = FaultSchedule.parse("kill@0.3:0.4", seed=0)
    t0 = time.monotonic()
    runner = ScheduleRunner(schedule, inc, t0).start()
    client = TcpBroker(port=inc.port, retry=RetryPolicy(window_s=15, backoff_base_s=0.05))
    stop = threading.Event()

    def publisher():
        while not stop.is_set():
            try:
                client.publish_experience(b"beat")
            except (ConnectionError, OSError, BrokerShedError):
                pass
            time.sleep(0.05)

    t = threading.Thread(target=publisher, daemon=True)
    t.start()
    deadline = time.monotonic() + 30
    while len(runner.recovery) < 1 and time.monotonic() < deadline:
        time.sleep(0.1)
    stop.set()
    t.join(timeout=5)
    runner.stop()
    assert len(inc.kill_times) == 1
    assert len(runner.recovery) == 1
    rec = runner.recovery[0]
    assert rec["recovery_s"] is not None and rec["recovery_s"] < 20
    inc.final_ledger()
    client.close()


_LINC_SCRIPT = r"""
import json, os, threading, time, tempfile
import jax
jax.config.update("jax_platforms", "cpu")
from dotaclient_tpu.chaos import LearnerIncarnations
from dotaclient_tpu.config import LearnerConfig, PolicyConfig
from dotaclient_tpu.runtime.learner import Learner
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import connect
from dotaclient_tpu.transport.serialize import serialize_rollout
from tests.test_transport import make_rollout

SMALL = PolicyConfig(unit_embed_dim=8, lstm_hidden=8, mlp_hidden=8, dtype="float32")
mem.reset("linc")
ckpt = tempfile.mkdtemp()

def make_learner():
    cfg = LearnerConfig(batch_size=8, seq_len=4, policy=SMALL, checkpoint_dir=ckpt,
                        checkpoint_every=5, publish_every=1, metrics_every=1)
    cfg.ckpt.full_state = True
    cfg.ckpt.async_save = True
    return Learner(cfg, connect("mem://linc"))

inc = LearnerIncarnations(make_learner, run_kwargs={"batch_timeout": 1.0}).start()
pub = connect("mem://linc")
stop_feed = threading.Event()

def feeder():
    i = 0
    while not stop_feed.is_set():
        learner = inc.learner
        pub.publish_experience(serialize_rollout(
            make_rollout(L=4, H=8, version=learner.version if learner else 0, seed=i)))
        i += 1
        time.sleep(0.002)

threading.Thread(target=feeder, daemon=True).start()
deadline = time.monotonic() + 120
while inc.learner.version < 2 and time.monotonic() < deadline:
    time.sleep(0.05)
assert inc.learner.version >= 2, "warm-up never trained"

led1 = inc.kill(sig="term")
assert led1["exit_clean"] and led1["sig"] == "term", led1
term_version = led1["version"]
inc.restart()
assert inc.boots[-1]["resume_version"] == term_version, (inc.boots[-1], term_version)
assert inc.wait_first_step(timeout=60.0) is not None, "no post-drain step"

led2 = inc.kill(sig="kill")
assert led2["sig"] == "kill" and not led2["exit_clean"], led2
inc.restart()
# SIGKILL resume: the version counter never rolls back past the
# published front (hwm file), even though the params may.
assert inc.boots[-1]["resume_version"] == led2["version"], (inc.boots[-1], led2)
assert inc.wait_first_step(timeout=60.0) is not None, "no post-kill step"

stop_feed.set()
totals = inc.final_ledger()
assert totals["incarnations"] == 3, totals
for l in inc.lives:  # per-life intake identity: every frame has a fate
    fresh = l["rows_packed"] - l["rows_replayed"]
    assert (l["consumed"] + l["resume_pending"]
            == l["dropped_stale"] + l["dropped_bad"] + fresh
            + l["pending_at_death"] + l["replay_admitted"]), l
print("LINC_OK", json.dumps({"lives": len(inc.lives), "consumed": totals["consumed"]}))
# os._exit: lingering jax/orbax C++ worker threads can abort a normal
# interpreter teardown; the proof is the printed verdict + assertions.
os._exit(0)
"""


def test_learner_incarnations_term_then_kill_and_ledgers():
    """LearnerIncarnations drives both death variants end-to-end on one
    checkpoint dir: SIGTERM drains (clean exit, durable full state, next
    boot resumes it), SIGKILL aborts (nothing saved at death, restore
    from the periodic cadence + hwm file), and every life's intake
    ledger is harvested exactly. Runs in a SINGLE-DEVICE subprocess: the
    8-virtual-device pytest harness piles enough XLA/orbax thread pools
    that three learner lives wedge thread creation in-process — the same
    scenario the resume soak runs (and passes) at 1 device."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "").replace(
        " --xla_force_host_platform_device_count=8", ""
    )
    proc = subprocess.run(
        [sys.executable, "-c", _LINC_SCRIPT],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True,
        text=True,
        timeout=420,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "LINC_OK" in proc.stdout, proc.stdout[-1000:]


# ------------------------------------------------- nightly soak wrapper


@pytest.mark.nightly
@pytest.mark.slow
def test_chaos_soak_quick_schema_and_invariants(tmp_path):
    """Run scripts/chaos_soak.py --quick and hold it to the same
    invariants as the committed CHAOS_SOAK.json: zero unaccounted
    frames, kills recovered, sheds at admission, clean learner finish.
    Marked BOTH nightly and slow: `-m 'not slow'` must not drag this
    ~40s closed loop into quick iteration (the marker-override gotcha).
    """
    out = tmp_path / "CHAOS_SOAK.json"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "chaos_soak.py"), "--quick", "--out", str(out)],
        cwd=repo,
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    artifact = json.loads(out.read_text())
    for key in ("phase_1_baseline", "phase_2_chaos", "phase_3_overload", "conservation", "learner", "verdict"):
        assert key in artifact, key
    v = artifact["verdict"]
    assert v["conservation_zero_unaccounted"]
    assert v["per_incarnation_identity_holds"] and v["producer_ledgers_balance"]
    assert v["kills_executed"] >= 1 and v["recovered_after_all_kills"]
    assert v["sheds_at_admission"] and v["producers_observed_shed_and_throttled"]
    assert v["overload_no_bad_growth"] and v["overload_no_stale_growth"]
    assert v["learner_clean_finish"]
    assert artifact["conservation"]["unaccounted_frames"] == 0


def test_committed_artifact_verdicts_hold():
    """The committed CHAOS_SOAK.json must carry an all-green verdict —
    a regenerated artifact with a red verdict must not land silently."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    artifact = json.load(open(os.path.join(repo, "CHAOS_SOAK.json")))
    assert artifact["verdict"]["kills_executed"] >= 3
    bad = [k for k, val in artifact["verdict"].items() if isinstance(val, bool) and not val]
    assert not bad, f"committed CHAOS_SOAK.json has red verdicts: {bad}"
