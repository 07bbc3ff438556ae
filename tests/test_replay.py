"""Replay reservoir (dotaclient_tpu/replay/) — ISSUE 1 test checklist:
admission/bypass split, priority eviction order, byte-budget
enforcement, spill round-trip, truncated-IW loss parity with plain PPO
at replay ratio 0, layout-error propagation, and a threaded
producer/consumer soak reusing the single-writer discipline asserted in
test_staging.py. The A/B harness (scripts/ab_replay.py) rides the
nightly tier alongside ab_ppo_reuse.py."""

import threading
import time

import numpy as np
import pytest

from dotaclient_tpu.config import LearnerConfig, PolicyConfig, ReplayConfig
from dotaclient_tpu.ops.batch import BatchLayoutError
from dotaclient_tpu.replay import ReplayReservoir, td_error_priority
from dotaclient_tpu.runtime.staging import StagingBuffer
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import connect
from dotaclient_tpu.transport.serialize import (
    deserialize_rollout,
    serialize_rollout,
)

from tests.test_transport import make_rollout

SMALL = PolicyConfig(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16)


def replay_cfg(**kw) -> ReplayConfig:
    base = dict(enabled=True, ratio=0.5, max_staleness=16, byte_budget=64 << 20)
    base.update(kw)
    return ReplayConfig(**base)


def learner_cfg(native_on=False, **replay_kw) -> LearnerConfig:
    cfg = LearnerConfig(batch_size=4, seq_len=8, policy=SMALL, native_packer=native_on)
    cfg.replay = replay_cfg(**replay_kw)
    return cfg


# ---------------------------------------------------------------- reservoir


def test_reservoir_priority_eviction_order():
    """Over-budget eviction removes the LOWEST effective priority first."""
    res = ReplayReservoir(
        ReplayConfig(enabled=True, byte_budget=250, max_staleness=64, spill_compress=False)
    )
    for i, pri in enumerate([5.0, 0.1, 3.0]):
        res.offer(bytes([i]) * 100, version=50, priority=pri, nbytes=100, current_version=50)
    assert res.occupancy == 2  # third offer pushed over budget → one evicted
    assert res.stats()["evicted"] == 1
    kept = {p[0] for p, _, _ in (res.sample(2, 50))}
    assert kept == {0, 2}  # the pri=0.1 entry is gone


def test_reservoir_age_decays_priority():
    """Equal |TD| priority: the OLDER entry must lose the eviction."""
    res = ReplayReservoir(
        ReplayConfig(
            enabled=True, byte_budget=250, max_staleness=64,
            spill_compress=False, age_half_life=4.0,
        )
    )
    res.offer(b"old" * 40, version=10, priority=1.0, nbytes=100, current_version=40)
    res.offer(b"new" * 40, version=39, priority=1.0, nbytes=100, current_version=40)
    res.offer(b"mid" * 40, version=30, priority=1.0, nbytes=100, current_version=40)
    assert res.occupancy == 2
    kept = {p for p, _, _ in res.sample(2, 40)}
    assert b"old" * 40 not in kept


def test_reservoir_byte_budget_enforced():
    res = ReplayReservoir(
        ReplayConfig(enabled=True, byte_budget=1000, max_staleness=64, spill_compress=False)
    )
    for i in range(50):
        res.offer(bytes([i % 250]) * 300, version=5, priority=float(i), nbytes=300,
                  current_version=5)
    assert res.occupancy_bytes <= 1000
    assert res.occupancy == 3  # 3 * 300 <= 1000 < 4 * 300
    s = res.stats()
    assert s["admitted"] == 50 and s["evicted"] == 47


def test_reservoir_staleness_window():
    res = ReplayReservoir(ReplayConfig(enabled=True, max_staleness=8))
    assert not res.offer(b"x", version=0, priority=1.0, nbytes=1, current_version=9)
    assert res.offer(b"y", version=1, priority=1.0, nbytes=1, current_version=9)
    # advancing the version expires the whole bucket
    assert res.expire(20) == 1
    assert res.occupancy == 0
    s = res.stats()
    assert s["rejected_stale"] == 1 and s["expired"] == 1


def test_reservoir_spill_round_trip_rollout():
    """Cold entries compress via encode/decode (the python staging path
    stores Rollout objects); a sampled spilled entry must round-trip to
    the exact same arrays."""
    r0 = make_rollout(L=6, H=8, version=7, seed=3)
    raw = serialize_rollout(r0)
    res = ReplayReservoir(
        ReplayConfig(enabled=True, byte_budget=1 << 20, max_staleness=32,
                     spill_threshold=0.0),  # everything is cold
        encode=serialize_rollout,
        decode=deserialize_rollout,
    )
    res.offer(r0, version=7, priority=1.0, nbytes=len(raw), current_version=8)
    s = res.stats()
    assert s["spilled_entries"] == 1
    assert s["bytes_spilled"] == len(raw)
    assert res.occupancy_bytes < len(raw)  # actually smaller in store
    (got, version, _), = res.sample(1, 8)
    assert version == 7
    np.testing.assert_array_equal(got.rewards, r0.rewards)
    np.testing.assert_array_equal(got.obs.unit_feats, r0.obs.unit_feats)
    np.testing.assert_array_equal(got.initial_state[0], r0.initial_state[0])


def test_reservoir_max_replays_retires():
    res = ReplayReservoir(ReplayConfig(enabled=True, max_staleness=64, max_replays=2))
    res.offer(b"x", version=5, priority=1.0, nbytes=1, current_version=5)
    assert res.sample(1, 5) and res.sample(1, 5)
    assert res.occupancy == 0  # retired after 2 uses
    assert res.stats()["retired"] == 1


def test_td_error_priority_proxy():
    # zero TD residual → zero priority; any surprise → positive
    v = np.asarray([1.0, 1.0, 1.0], np.float32)
    r = np.zeros(3, np.float32)
    d = np.zeros(3, np.float32)
    assert td_error_priority(r, v, d, gamma=1.0) == 0.0
    assert td_error_priority(np.ones(3, np.float32), v, d, gamma=1.0) == pytest.approx(1.0)
    assert td_error_priority(np.zeros(0, np.float32), v[:0], d[:0], 0.98) == 0.0


# ------------------------------------------------------- staging integration


@pytest.mark.parametrize("native_on", [False, True])
def test_staging_admission_bypass_split_and_mixing(native_on):
    """Fresh frames bypass to the packer, near-stale frames land in the
    reservoir instead of dropped_stale, too-stale frames still drop; a
    packed batch mixes fresh + replayed rows with per-row staleness
    stamps."""
    name = f"replay_mix_{native_on}"
    mem.reset(name)
    broker = connect(f"mem://{name}")
    cfg = learner_cfg(native_on=native_on, ratio=0.5, max_staleness=16)
    version = [20]
    buf = StagingBuffer(cfg, connect(f"mem://{name}"), version_fn=lambda: version[0]).start()
    try:
        if native_on and not buf.native:
            pytest.skip("native packer unavailable")
        # min fresh version = 20 - 4 = 16; reservoir window = 20 - 16 = 4
        for i in range(3):
            broker.publish_experience(
                serialize_rollout(make_rollout(L=4, H=8, version=10, seed=i))  # near-stale
            )
        broker.publish_experience(
            serialize_rollout(make_rollout(L=4, H=8, version=1, seed=9))  # too stale
        )
        deadline = time.time() + 10
        while buf.stats()["consumed"] < 4 and time.time() < deadline:
            time.sleep(0.05)
        s = buf.stats()
        assert s["dropped_stale"] == 1
        assert s["replay_admitted"] == 3
        assert s["replay_occupancy"] == 3
        assert s["pending_rollouts"] == 0
        # exactly ONE batch of fresh material: 2 fresh + 2 replayed
        # (ratio 0.5) — no leftovers, so the stats below are not racing a
        # second batch forming in the background
        for i in range(2):
            broker.publish_experience(
                serialize_rollout(make_rollout(L=4, H=8, version=19, seed=20 + i))
            )
        batch = buf.get_batch(timeout=10)
        assert batch is not None
        assert batch.behavior_staleness is not None
        stamps = np.sort(np.asarray(batch.behavior_staleness))
        np.testing.assert_array_equal(stamps, [0.0, 0.0, 10.0, 10.0])
        s = buf.stats()
        assert s["rows_packed"] == 4 and s["rows_replayed"] == 2
        assert s["replay_hit_ratio"] == pytest.approx(0.5)
        assert s["replay_sampled"] == 2
        # both replayed rows are deterministically age 10 → the le_16 bucket
        assert s["replay_age_le_16"] == 2
    finally:
        buf.stop()


def test_staging_replay_disabled_unchanged():
    """Default-off: no reservoir, no staleness stamp, no replay_* stats —
    the pre-replay contract exactly."""
    mem.reset("replay_off")
    broker = connect("mem://replay_off")
    cfg = LearnerConfig(batch_size=2, seq_len=8, policy=SMALL, native_packer=False)
    assert not cfg.replay.enabled
    buf = StagingBuffer(cfg, connect("mem://replay_off")).start()
    try:
        for i in range(2):
            broker.publish_experience(serialize_rollout(make_rollout(L=4, H=8, seed=i)))
        batch = buf.get_batch(timeout=10)
        assert batch is not None
        assert batch.behavior_staleness is None
        assert not any(k.startswith("replay_") for k in buf.stats())
    finally:
        buf.stop()


def test_staging_replay_rejects_fused_io():
    cfg = learner_cfg()
    with pytest.raises(ValueError, match="mutually exclusive"):
        StagingBuffer(cfg, connect("mem://replay_fused"), fused_io=object())


def test_staging_replay_window_validation():
    cfg = learner_cfg(max_staleness=2)  # <= ppo.max_staleness (4)
    with pytest.raises(ValueError, match="must exceed"):
        StagingBuffer(cfg, connect("mem://replay_bad"))


def test_reservoir_never_starves_fresh_batches():
    """An empty reservoir must not block batch formation (a short
    reservoir just means more fresh rows)."""
    mem.reset("replay_fresh")
    broker = connect("mem://replay_fresh")
    buf = StagingBuffer(learner_cfg(), connect("mem://replay_fresh")).start()
    try:
        for i in range(4):
            broker.publish_experience(serialize_rollout(make_rollout(L=4, H=8, version=0, seed=i)))
        batch = buf.get_batch(timeout=10)
        assert batch is not None
        np.testing.assert_array_equal(np.asarray(batch.behavior_staleness), np.zeros(4))
    finally:
        buf.stop()


# ------------------------------------------------ layout-error propagation


@pytest.mark.filterwarnings("ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_layout_error_kills_consumer_and_surfaces():
    """A BatchLayoutError from the packer is a persistent config
    mismatch: the consumer thread must die (not count dropped_bad
    forever) and the learner-side getter must re-raise instead of
    starving silently (ADVICE r5 item 1)."""
    mem.reset("layout_fatal")
    broker = connect("mem://layout_fatal")
    cfg = LearnerConfig(batch_size=2, seq_len=8, policy=SMALL, native_packer=False)
    buf = StagingBuffer(cfg, connect("mem://layout_fatal"))

    def bad_pack(items):
        raise BatchLayoutError("synthetic layout mismatch")

    buf._pack = bad_pack
    buf.start()
    try:
        for i in range(2):
            broker.publish_experience(serialize_rollout(make_rollout(L=4, H=8, seed=i)))
        deadline = time.time() + 10
        while buf._thread.is_alive() and time.time() < deadline:
            time.sleep(0.05)
        assert not buf._thread.is_alive(), "consumer must die on a layout error"
        assert buf.stats()["dropped_bad"] == 0  # NOT swallowed as a frame drop
        assert buf.stats()["consumer_errors"] == 0  # NOT a generic consumer error
        with pytest.raises(RuntimeError, match="layout/config mismatch"):
            buf.get_batch(timeout=0.1)
        with pytest.raises(RuntimeError, match="layout/config mismatch"):
            buf.get_batch_groups(timeout=0.1)
    finally:
        buf.stop()


def test_fused_pack_row_mismatch_is_layout_error():
    from tests.test_staging import _fused_io_for

    cfg = LearnerConfig(batch_size=4, seq_len=8, policy=SMALL)
    io = _fused_io_for(cfg)
    from dotaclient_tpu.runtime.staging import pack_rollouts

    small = pack_rollouts([make_rollout(L=3, H=8, seed=i) for i in range(2)], 8, False)
    with pytest.raises(BatchLayoutError):
        io.pack_transfer(small)


def test_malformed_frame_still_just_drops():
    """The frame-level ValueError path is NOT fatal: garbage frames keep
    counting dropped_bad and the consumer keeps serving (the pre-ADVICE
    behavior, now reserved for genuinely per-frame errors)."""
    mem.reset("layout_nonfatal")
    broker = connect("mem://layout_nonfatal")
    cfg = LearnerConfig(batch_size=2, seq_len=8, policy=SMALL, native_packer=False)
    buf = StagingBuffer(cfg, connect("mem://layout_nonfatal")).start()
    try:
        broker.publish_experience(b"not a rollout")
        for i in range(2):
            broker.publish_experience(serialize_rollout(make_rollout(L=4, H=8, seed=i)))
        assert buf.get_batch(timeout=10) is not None
        assert buf.stats()["dropped_bad"] == 1
        assert buf._thread.is_alive()
    finally:
        buf.stop()


# ------------------------------------------------------------ loss parity


def _loss_setup():
    import jax
    import jax.numpy as jnp

    from dotaclient_tpu.models.policy import PolicyNet, init_params
    from dotaclient_tpu.parallel.train_step import make_train_batch

    cfg = LearnerConfig(
        batch_size=4,
        seq_len=6,
        policy=PolicyConfig(unit_embed_dim=16, lstm_hidden=16, mlp_hidden=16, dtype="float32"),
    )
    params = init_params(cfg.policy, jax.random.PRNGKey(0))
    net = PolicyNet(cfg.policy)
    batch = jax.tree.map(jnp.asarray, make_train_batch(cfg, rng_seed=1))
    return cfg, params, net, batch


def test_truncated_iw_parity_when_no_replayed_rows():
    """Replay ratio 0 (all rows fresh, staleness stamp all-zero) must
    produce the SAME loss as the replay-disabled (staleness=None) path,
    and the disabled path is literally the pre-replay code."""
    import jax.numpy as jnp

    from dotaclient_tpu.ops.ppo import ppo_loss

    cfg, params, net, batch = _loss_setup()
    assert batch.behavior_staleness is None  # make_train_batch: replay off
    loss_off, m_off = ppo_loss(params, net.apply, batch, cfg.ppo)
    stamped = batch._replace(behavior_staleness=jnp.zeros((4,), jnp.float32))
    loss_zero, m_zero = ppo_loss(params, net.apply, stamped, cfg.ppo)
    np.testing.assert_allclose(float(loss_off), float(loss_zero), rtol=1e-6)
    assert float(m_off["replay_trunc_frac"]) == 0.0
    assert float(m_zero["replay_trunc_frac"]) == 0.0
    for k in m_off:
        np.testing.assert_allclose(float(m_off[k]), float(m_zero[k]), rtol=1e-5, err_msg=k)


def test_truncated_iw_engages_on_stale_rows():
    """Stale rows with ratio > rho_bar must change the policy loss (the
    ACER truncation binding) while fresh rows are untouched."""
    import jax.numpy as jnp

    from dotaclient_tpu.ops.ppo import ppo_loss

    cfg, params, net, batch = _loss_setup()
    # Force huge ratios: behavior_logp far below the policy's logp.
    batch = batch._replace(behavior_logp=batch.behavior_logp - 3.0)
    zero = batch._replace(behavior_staleness=jnp.zeros((4,), jnp.float32))
    stale = batch._replace(behavior_staleness=jnp.asarray([0.0, 5.0, 9.0, 0.0], jnp.float32))
    loss_zero, m_zero = ppo_loss(params, net.apply, zero, cfg.ppo)
    loss_stale, m_stale = ppo_loss(params, net.apply, stale, cfg.ppo)
    assert float(m_stale["replay_trunc_frac"]) > 0.0
    assert float(m_zero["replay_trunc_frac"]) == 0.0
    assert float(m_stale["policy_loss"]) != float(m_zero["policy_loss"])
    # the raw-ratio diagnostics are computed pre-truncation → identical
    np.testing.assert_allclose(
        float(m_stale["ratio_mean"]), float(m_zero["ratio_mean"]), rtol=1e-6
    )


def test_train_step_with_replay_template():
    """build_train_step under replay.enabled: the batch template grows
    the [B] staleness leaf, shardings line up, the step runs, and the
    replay_trunc_frac metric is present (reuse path included)."""
    import jax

    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.parallel.train_step import (
        build_train_step,
        init_train_state,
        make_train_batch,
    )

    cfg = LearnerConfig(
        batch_size=4,
        seq_len=6,
        policy=PolicyConfig(unit_embed_dim=16, lstm_hidden=16, mlp_hidden=16, dtype="float32"),
    )
    cfg.replay = replay_cfg(ratio=0.25)
    cfg.ppo.epochs = 2
    cfg.ppo.minibatches = 2
    mesh = mesh_lib.make_mesh("dp=2", devices=jax.devices()[:2])
    train_step, state_sh, batch_sh = build_train_step(cfg, mesh)
    assert batch_sh.behavior_staleness is not None
    state = jax.device_put(init_train_state(cfg, jax.random.PRNGKey(0)), state_sh)
    batch = make_train_batch(cfg, rng_seed=3)
    batch = batch._replace(
        behavior_staleness=np.asarray([0.0, 0.0, 6.0, 12.0], np.float32),
        behavior_logp=batch.behavior_logp - 2.0,
    )
    batch = jax.device_put(batch, batch_sh)
    state2, metrics = train_step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["replay_trunc_frac"]) > 0.0


def test_fused_build_refuses_replay():
    import jax

    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.parallel.train_step import build_single_train_step

    cfg = LearnerConfig(batch_size=2, seq_len=8, policy=SMALL)
    cfg.replay = replay_cfg()
    mesh = mesh_lib.make_mesh("dp=1", devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="replay"):
        build_single_train_step(cfg, mesh)


# ------------------------------------------------------------------- soak


@pytest.mark.slow
def test_replay_soak_threaded_producers():
    """Single-writer soak (mirrors test_staging's stress): N producer
    threads publish frames whose versions straggle behind a moving
    learner version while the consumer ingests, admits near-stale frames
    to the reservoir, mixes batches, and a stats reader polls the whole
    time. Asserts conservation (every frame consumed exactly once, every
    frame accounted: packed, resident, pending, dropped, or replay-
    retired/expired/evicted) and that replayed rows actually flow."""
    mem.reset("replay_soak")
    broker = connect("mem://replay_soak")
    n_producers, frames_each = 6, 50
    version = [0]
    cfg = learner_cfg(native_on=False, ratio=0.25, max_staleness=24)
    cfg.ppo.max_staleness = 2
    staging = StagingBuffer(cfg, broker, version_fn=lambda: version[0]).start()

    rng = np.random.RandomState(0)

    def produce(k):
        conn = connect("mem://replay_soak")
        r = np.random.RandomState(k)
        for i in range(frames_each):
            lag = int(r.choice([0, 1, 2, 5, 10, 30]))  # fresh / near-stale / too-stale
            v = max(version[0] - lag, 0)
            conn.publish_experience(
                serialize_rollout(make_rollout(L=8, H=8, version=v, seed=k * 997 + i, actor_id=k))
            )
            if i % 10 == 9:
                time.sleep(0.01)

    stop_stats = threading.Event()
    stats_errors = []

    def stats_reader():
        while not stop_stats.is_set():
            try:
                s = staging.stats()
                assert s["replay_occupancy"] >= 0
                assert 0.0 <= s["replay_hit_ratio"] <= 1.0
            except Exception as e:  # pragma: no cover — the assertion IS the test
                stats_errors.append(e)
                return

    threads = [threading.Thread(target=produce, args=(k,)) for k in range(n_producers)]
    reader = threading.Thread(target=stats_reader, daemon=True)
    reader.start()
    for t in threads:
        t.start()

    total = n_producers * frames_each
    batches = rows = replayed = 0
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        b = staging.get_batch(timeout=2.0)
        if b is None:
            if all(not t.is_alive() for t in threads) and staging.stats()["consumed"] >= total:
                break
            continue
        version[0] += 1  # the learner's version marches with each batch
        batches += 1
        assert b.mask.shape == (cfg.batch_size, cfg.seq_len)
        st = np.asarray(b.behavior_staleness)
        assert st.shape == (cfg.batch_size,) and (st >= 0).all()
        rows += len(st)
        replayed += int((st > 0).sum())
    for t in threads:
        t.join(timeout=30)
    stop_stats.set()
    reader.join(timeout=10)
    staging.stop()

    assert not stats_errors, stats_errors
    s = staging.stats()
    assert s["consumed"] == total
    assert s["consumer_errors"] == 0 and s["dropped_bad"] == 0
    assert batches == s["batches"] and rows == s["rows_packed"]
    assert replayed == s["rows_replayed"]
    # Conservation: every consumed frame is packed fresh, pending,
    # dropped, or went through the reservoir (resident/expired/evicted/
    # retired — sampling doesn't consume).
    fresh_packed = s["rows_packed"] - s["rows_replayed"]
    accounted = (
        fresh_packed
        + s["pending_rollouts"]
        + s["dropped_stale"]
        + s["replay_admitted"]
    )
    assert accounted == total, s
    in_reservoir = s["replay_occupancy"] + s["replay_expired"] + s["replay_evicted"] + s["replay_retired"]
    assert in_reservoir == s["replay_admitted"], s
    assert s["replay_admitted"] > 0, "soak never produced a near-stale frame"


# ---------------------------------------------------------------- nightly


@pytest.mark.nightly
@pytest.mark.slow  # ALSO slow: the tier-1 gate runs `-m 'not slow'`,
# which overrides the addopts nightly exclusion — without this marker the
# multi-minute closed-loop A/B would ride the fast tier.
def test_ab_replay_nightly(tmp_path):
    """The replay A/B harness in the nightly tier alongside
    ab_ppo_reuse.py: replay-on must recover previously-dropped stale
    rollouts (or the host produced no staleness at all, recorded in the
    artifact)."""
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "ab_replay_under_test", os.path.join(repo, "scripts", "ab_replay.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    out = tmp_path / "REPLAY_AB.json"
    rc = module.main(["--updates", "12", "--seeds", "1", "--out", str(out)])
    assert rc == 0, "replay A/B verdict failed — see artifact"
    import json

    artifact = json.loads(out.read_text())
    assert artifact["stale_drops_recovered"]
