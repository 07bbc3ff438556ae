import time

import numpy as np
import pytest

from dotaclient_tpu.config import LearnerConfig, PolicyConfig
from dotaclient_tpu.runtime.staging import StagingBuffer, pack_rollouts
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import connect
from dotaclient_tpu.transport.serialize import serialize_rollout

from tests.test_transport import make_rollout

CFG = LearnerConfig(
    batch_size=4,
    seq_len=8,
    policy=PolicyConfig(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16),
)


def test_pack_pads_and_masks():
    rollouts = [make_rollout(L=L, H=8, seed=L) for L in (3, 8, 5, 1)]
    batch = pack_rollouts(rollouts, seq_len=8, with_aux=False)
    assert batch.mask.shape == (4, 8)
    np.testing.assert_array_equal(batch.mask.sum(1), [3, 8, 5, 1])
    # row 0: data matches up to L, zero beyond
    r0 = rollouts[0]
    np.testing.assert_array_equal(batch.rewards[0, :3], r0.rewards)
    assert (batch.rewards[0, 3:] == 0).all()
    np.testing.assert_array_equal(batch.obs.unit_feats[0, :4], r0.obs.unit_feats)
    assert (batch.obs.unit_feats[0, 4:] == 0).all()
    # padded action_mask rows keep NOOP legal (uniform-safe under masking)
    assert batch.obs.action_mask[0, 5:, 0].all()
    np.testing.assert_array_equal(batch.initial_state[0][0], r0.initial_state[0])


def test_pack_rejects_overlong():
    with pytest.raises(ValueError):
        pack_rollouts([make_rollout(L=9)], seq_len=8, with_aux=False)


def test_pack_aux_fill():
    rollouts = [make_rollout(L=4, aux=True), make_rollout(L=2, aux=False)]
    batch = pack_rollouts(rollouts, seq_len=6, with_aux=True)
    assert batch.aux is not None
    np.testing.assert_array_equal(batch.aux.win[0, :4], rollouts[0].aux.win)
    assert (batch.aux.win[1] == 0).all()  # missing aux → zeros (unknown)


def test_staging_end_to_end_with_staleness():
    mem.reset("stage")
    broker = connect("mem://stage")
    version = [10]
    buf = StagingBuffer(CFG, connect("mem://stage"), version_fn=lambda: version[0]).start()
    try:
        # 2 stale (version 1 < 10-4), 6 fresh → exactly one batch of 4
        for v, n in ((1, 2), (9, 6)):
            for i in range(n):
                broker.publish_experience(serialize_rollout(make_rollout(L=4, H=8, version=v, seed=v * 10 + i)))
        batch = buf.get_batch(timeout=5)
        assert batch is not None
        assert batch.mask.shape == (4, 8)
        deadline = time.time() + 5
        while buf.stats()["consumed"] < 8 and time.time() < deadline:
            time.sleep(0.05)
        stats = buf.stats()
        assert stats["consumed"] == 8
        assert stats["dropped_stale"] == 2
        assert stats["batches"] == 1
        assert stats["pending_rollouts"] == 2  # 6 fresh - 4 packed
    finally:
        buf.stop()


def test_staging_drops_garbage_frames():
    mem.reset("stage2")
    broker = connect("mem://stage2")
    buf = StagingBuffer(CFG, connect("mem://stage2")).start()
    try:
        broker.publish_experience(b"not a rollout")
        for i in range(4):
            broker.publish_experience(serialize_rollout(make_rollout(L=2, H=8, version=0, seed=i)))
        batch = buf.get_batch(timeout=5)
        assert batch is not None
        assert buf.stats()["dropped_bad"] == 1
    finally:
        buf.stop()


@pytest.mark.parametrize("native_on", [True, False])
def test_staging_dtr3_corrupt_dtype_map_quarantined_distinctly(native_on):
    """ISSUE 8 satellite: a truncated/corrupt DTR3 dtype-map must
    dead-letter under its own 'dtype_map' reason — on the native intake
    (python pre-check before the C parse) AND the python fallback — and
    never crash the consumer; good frames keep flowing."""
    from dotaclient_tpu.transport.serialize import cast_rollout_obs_bf16

    name = f"stage_dtr3q_{native_on}"
    mem.reset(name)
    broker = connect(f"mem://{name}")
    buf = StagingBuffer(CFG, connect(f"mem://{name}"))
    if not native_on:
        buf._lib = None
    buf.start()
    try:
        good = serialize_rollout(cast_rollout_obs_bf16(make_rollout(L=4, H=8, version=0, seed=9)))
        corrupt = bytes(good[:38]) + b"\x07" + bytes(good[39:])  # bad obs code
        truncated = good[:40]  # cut inside the dtype-map
        broker.publish_experience(corrupt)
        broker.publish_experience(truncated)
        for i in range(4):
            broker.publish_experience(
                serialize_rollout(cast_rollout_obs_bf16(make_rollout(L=3, H=8, version=0, seed=i)))
            )
        batch = buf.get_batch(timeout=10)
        assert batch is not None
        stats = buf.stats()
        assert stats["dropped_bad"] == 2 and stats["quarantined"] == 2
        assert stats["consumer_errors"] == 0
        reasons = [e["reason"] for e in buf.quarantine()]
        assert reasons == ["dtype_map", "dtype_map"]
        # evidence is the ORIGINAL corrupt bytes, not the emptied slot
        assert buf.quarantine()[0]["bytes"] == len(corrupt)
        assert buf.quarantine()[0]["head"].startswith(b"DTR3".hex())
    finally:
        buf.stop()


@pytest.mark.parametrize("native_on", [True, False])
def test_staging_wire_meters_split_by_obs_dtype(native_on):
    """wire_bytes / wire_frames_obs_{bf16,f32} count consumed bytes and
    the per-frame wire dtype — the rolling-upgrade progress gauge."""
    from dotaclient_tpu.transport.serialize import cast_rollout_obs_bf16

    name = f"stage_wirem_{native_on}"
    mem.reset(name)
    broker = connect(f"mem://{name}")
    buf = StagingBuffer(CFG, connect(f"mem://{name}"))
    if not native_on:
        buf._lib = None
    buf.start()
    try:
        frames = []
        for i in range(2):
            frames.append(serialize_rollout(make_rollout(L=3, H=8, version=0, seed=i)))
        for i in range(2):
            frames.append(
                serialize_rollout(cast_rollout_obs_bf16(make_rollout(L=3, H=8, version=0, seed=10 + i)))
            )
        for f in frames:
            broker.publish_experience(f)
        batch = buf.get_batch(timeout=10)
        assert batch is not None
        stats = buf.stats()
        assert stats["wire_bytes"] == sum(len(f) for f in frames)
        assert stats["wire_frames_obs_f32"] == 2
        assert stats["wire_frames_obs_bf16"] == 2
    finally:
        buf.stop()


def test_staging_dtr3_bf16_wire_batch_bitwise_equals_f32_wire():
    """Cast-at-actor vs cast-at-staging through the python packer at
    this file's small config: bitwise-equal TrainBatch (the native-path
    twin lives in test_native.py; the full-shape A/B in
    WIRE_QUANT_AB.json)."""
    from dotaclient_tpu.transport.serialize import cast_rollout_obs_bf16

    rollouts = [make_rollout(L=4, H=8, version=0, seed=i) for i in range(CFG.batch_size)]
    batches = {}
    for tag, frames in (
        ("f32", [serialize_rollout(r) for r in rollouts]),
        ("bf16", [serialize_rollout(cast_rollout_obs_bf16(r)) for r in rollouts]),
    ):
        name = f"stage_par_{tag}"
        mem.reset(name)
        broker = connect(f"mem://{name}")
        cfg = LearnerConfig(
            batch_size=CFG.batch_size, seq_len=CFG.seq_len,
            policy=PolicyConfig(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16, dtype="bfloat16"),
        )
        buf = StagingBuffer(cfg, connect(f"mem://{name}"))
        buf._lib = None  # python packer
        buf.start()
        try:
            for f in frames:
                broker.publish_experience(f)
            batches[tag] = buf.get_batch(timeout=10)
            assert batches[tag] is not None
        finally:
            buf.stop()
    import jax

    for a, b in zip(jax.tree.leaves(batches["f32"]), jax.tree.leaves(batches["bf16"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_staging_double_buffer_bounded():
    mem.reset("stage3")
    broker = connect("mem://stage3")
    buf = StagingBuffer(CFG, connect("mem://stage3")).start()
    try:
        for i in range(CFG.batch_size * 10):
            broker.publish_experience(serialize_rollout(make_rollout(L=3, H=8, version=0, seed=i)))
        time.sleep(1.0)
        stats = buf.stats()
        assert stats["ready_batches"] <= 2  # bounded: packing waits for consumer
        got = 0
        while buf.get_batch(timeout=1) is not None:
            got += 1
        assert got >= 3
    finally:
        buf.stop()


def test_misconfigured_actor_frames_dropped_not_fatal():
    # frames that deserialize fine but violate learner config (L > seq_len,
    # wrong lstm H) must be counted dropped_bad, and good frames still flow.
    mem.reset("stage4")
    broker = connect("mem://stage4")
    buf = StagingBuffer(CFG, connect("mem://stage4")).start()
    try:
        broker.publish_experience(serialize_rollout(make_rollout(L=12, H=8)))  # L > 8
        broker.publish_experience(serialize_rollout(make_rollout(L=4, H=32)))  # H != 8
        for i in range(4):
            broker.publish_experience(serialize_rollout(make_rollout(L=3, H=8, seed=i)))
        batch = buf.get_batch(timeout=5)
        assert batch is not None
        assert buf.stats()["dropped_bad"] == 2
        assert buf.stats()["consumer_errors"] == 0
    finally:
        buf.stop()


def test_staging_sustains_north_star_rate():
    """Host packing headroom vs the north star (VERDICT r2 item 5,
    SURVEY.md §7 "Throughput of host-side packing").

    Feeds the StagingBuffer pre-serialized flagship-shape frames
    (full featurizer dims, H=128, T=16) from 2 producer threads and
    drains packed batches with no device in the loop. The sustained
    rate must clear 2× the per-chip north-star share (6,250 env-steps/s
    per v5e-8 chip) even on a 1-core CI host — the measured rate there
    is ~1.1M steps/s (BENCH r3), so 12.5k is a regression tripwire, not
    a tight bound.
    """
    import bench as bench_mod

    cfg = LearnerConfig(batch_size=64, seq_len=16)
    # reuse the bench's depth-throttled producers — one copy of the
    # throttling policy, shared by bench and tripwire
    stop = bench_mod._start_producers(cfg, "ns_rate", n_threads=2)
    staging = StagingBuffer(cfg, connect("mem://ns_rate"), version_fn=lambda: 0).start()
    try:
        assert staging.get_batch(timeout=30.0) is not None  # pipe warm
        steps = 0
        t0 = time.monotonic()
        while time.monotonic() - t0 < 2.0:
            b = staging.get_batch(timeout=10.0)
            assert b is not None
            steps += int(b.mask.sum())
        rate = steps / (time.monotonic() - t0)
    finally:
        stop.set()
        staging.stop()
    assert rate >= 12_500, f"host packing {rate:.0f} env-steps/s < 2x per-chip north star"


def test_staging_stress_many_producers_with_stats_reader():
    """Race-surface stress (SURVEY.md §5): N producer threads hammer the
    broker while the consumer thread ingests/packs and a separate thread
    polls stats() the whole time (the learner's metrics path). Checks
    conservation — every frame is consumed exactly once, every batch well
    formed — and that stats() never throws or corrupts the heartbeat map."""
    import threading

    mem.reset("stress")
    broker = connect("mem://stress")
    n_producers, frames_each = 8, 60
    cfg = LearnerConfig(
        batch_size=4,
        seq_len=8,
        policy=PolicyConfig(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16),
        native_packer=False,  # python path: exercises the pure-python ingest
    )
    staging = StagingBuffer(cfg, broker, version_fn=lambda: 0)
    staging.start()

    def produce(k):
        conn = connect("mem://stress")
        for i in range(frames_each):
            conn.publish_experience(
                serialize_rollout(make_rollout(L=8, H=8, version=0, seed=k * 1000 + i, actor_id=k))
            )

    stop_stats = threading.Event()
    stats_errors = []

    def stats_reader():
        while not stop_stats.is_set():
            try:
                s = staging.stats()
                assert 0 <= s["active_actors"] <= n_producers
            except Exception as e:  # pragma: no cover - the assertion IS the test
                stats_errors.append(e)
                return

    threads = [threading.Thread(target=produce, args=(k,)) for k in range(n_producers)]
    reader = threading.Thread(target=stats_reader, daemon=True)
    reader.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)

    total = n_producers * frames_each
    batches, seen_steps = 0, 0
    deadline = time.monotonic() + 60
    while seen_steps < (total // cfg.batch_size) * cfg.batch_size * 8 and time.monotonic() < deadline:
        b = staging.get_batch(timeout=5.0)
        if b is None:
            break
        batches += 1
        assert b.mask.shape == (cfg.batch_size, cfg.seq_len)
        seen_steps += int(b.mask.sum())
    stop_stats.set()
    reader.join(timeout=10)
    staging.stop()

    assert not stats_errors, stats_errors
    stats = staging.stats()
    assert stats["consumed"] == total
    assert stats["dropped_bad"] == 0 and stats["dropped_stale"] == 0
    assert batches == total // cfg.batch_size
    assert stats["active_actors"] == n_producers  # every producer heartbeated


def test_staging_casts_obs_to_compute_dtype():
    """bf16-policy learners receive obs floats already in bf16 (host-side
    cast, halves the H2D transfer) — numerically identical to the
    device-side cast the policy would do, so metrics must match a
    f32-staged batch exactly."""
    import jax
    import ml_dtypes

    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.parallel.train_step import build_train_step, init_train_state

    def staged_batch(stage_cast):
        mem.reset("stage_cast")
        broker = connect("mem://stage_cast")
        cfg = LearnerConfig(
            batch_size=2,
            seq_len=8,
            policy=PolicyConfig(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16),
            stage_obs_compute_dtype=stage_cast,
        )
        for i in range(2):
            broker.publish_experience(serialize_rollout(make_rollout(L=8, H=8, seed=i)))
        buf = StagingBuffer(cfg, connect("mem://stage_cast"), version_fn=lambda: 0).start()
        try:
            batch = buf.get_batch(timeout=30.0)
        finally:
            buf.stop()
        assert batch is not None
        return cfg, batch

    cfg, cast_batch = staged_batch(True)
    assert cast_batch.obs.unit_feats.dtype == ml_dtypes.bfloat16
    assert cast_batch.obs.unit_mask.dtype == np.bool_  # masks untouched
    assert cast_batch.rewards.dtype == np.float32  # loss scalars untouched
    _, f32_batch = staged_batch(False)
    assert f32_batch.obs.unit_feats.dtype == np.float32

    mesh = mesh_lib.make_mesh("dp=2", devices=jax.devices()[:2])
    train_step, state_sh, _ = build_train_step(cfg, mesh)
    state = jax.device_put(init_train_state(cfg, jax.random.PRNGKey(0)), state_sh)
    _, m_cast = train_step(state, cast_batch)
    state2 = jax.device_put(init_train_state(cfg, jax.random.PRNGKey(0)), state_sh)
    _, m_f32 = train_step(state2, f32_batch)
    for k in m_f32:
        assert float(m_cast[k]) == pytest.approx(float(m_f32[k]), rel=1e-5, abs=1e-6), k


def test_float32_policy_staging_not_cast():
    mem.reset("stage_f32")
    broker = connect("mem://stage_f32")
    cfg = LearnerConfig(
        batch_size=1,
        seq_len=8,
        policy=PolicyConfig(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16, dtype="float32"),
    )
    broker.publish_experience(serialize_rollout(make_rollout(L=8, H=8, seed=0)))
    buf = StagingBuffer(cfg, connect("mem://stage_f32"), version_fn=lambda: 0).start()
    try:
        batch = buf.get_batch(timeout=30.0)
    finally:
        buf.stop()
    assert batch.obs.unit_feats.dtype == np.float32


def _fused_io_for(cfg):
    import jax

    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.parallel.fused_io import FusedBatchIO
    from dotaclient_tpu.parallel.train_step import _batch_template
    from dotaclient_tpu.runtime.staging import cast_obs_to_compute_dtype

    mesh = mesh_lib.make_mesh("dp=1", devices=jax.devices()[:1])
    template = cast_obs_to_compute_dtype(
        cfg, jax.tree.map(np.asarray, _batch_template(cfg))
    )
    return FusedBatchIO(template, mesh)


def _bitwise_equal(a, b):
    import jax

    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(
            np.ascontiguousarray(x).view(np.uint8), np.ascontiguousarray(y).view(np.uint8)
        )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("native_on", [False, True])
def test_staging_fused_payload_matches_dense_path(dtype, native_on):
    """A fused staging buffer (packs into transfer-buffer views, native
    OR python fallback) must emit bitwise the batch a dense buffer emits
    through pack+cast, and its payload must equal io.pack_transfer of
    that dense batch — packing in place ships identical bytes. Salted
    with NaN/RNE-tie obs so the fallback's assignment-cast is pinned to
    astype on the hard cases."""
    cfg = LearnerConfig(
        batch_size=4,
        seq_len=8,
        native_packer=native_on,  # the public knob; the env var is load-time-only
        policy=PolicyConfig(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16, dtype=dtype),
    )
    rollouts = [make_rollout(L=3 + i, H=8, seed=i, actor_id=i) for i in range(4)]
    for r in rollouts:
        r.obs.global_feats[0, :3] = [np.nan, 1.00390625, -1.00390625]
    frames = [serialize_rollout(r) for r in rollouts]

    io = _fused_io_for(cfg)
    name_a, name_b = f"fg_{dtype}_{native_on}", f"fd_{dtype}_{native_on}"
    mem.reset(name_a), mem.reset(name_b)
    fused = StagingBuffer(cfg, connect(f"mem://{name_a}"), fused_io=io).start()
    dense = StagingBuffer(cfg, connect(f"mem://{name_b}")).start()
    try:
        if native_on and not fused.native:
            pytest.skip("native packer unavailable")
        assert fused.native == dense.native == native_on
        pub_a, pub_b = connect(f"mem://{name_a}"), connect(f"mem://{name_b}")
        for f in frames:
            pub_a.publish_experience(f)
            pub_b.publish_experience(f)
        batch_f, buf = fused.get_batch_groups(timeout=30.0)
        # dense buffers answer get_batch_groups too, with payload None —
        # read the dense batch THROUGH that API so the tuple contract is
        # actually pinned (not just the empty-queue timeout path).
        batch_d, buf_d = dense.get_batch_groups(timeout=30.0)
        assert buf_d is None
        assert batch_f is not None and batch_d is not None
        assert isinstance(buf, np.ndarray) and buf.dtype == np.uint8
        assert buf.shape == (cfg.batch_size, io.row_bytes)
        _bitwise_equal(batch_f, batch_d)
        np.testing.assert_array_equal(buf, io.pack_transfer(batch_d))
        # the batch leaves genuinely alias the transfer buffer (no copy)
        assert np.may_share_memory(np.asarray(batch_f.mask), buf)
        # empty queue: the timeout path returns (None, None)
        assert dense.get_batch_groups(timeout=0.1) == (None, None)
    finally:
        fused.stop(), dense.stop()


def test_staging_fused_payload_unpacks_on_device_to_dense():
    """The u8 payload a fused staging buffer hands over, unpacked inside
    a jit as the train step does, is bitwise the batch a dense buffer
    emits: host packer and device unpack agree on every byte offset."""
    import jax

    cfg = LearnerConfig(
        batch_size=4,
        seq_len=8,
        policy=PolicyConfig(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16, dtype="bfloat16"),
    )
    rollouts = [make_rollout(L=3 + i, H=8, seed=i, actor_id=i) for i in range(4)]
    for r in rollouts:
        r.obs.global_feats[0, :3] = [np.nan, 1.00390625, -1.00390625]
    frames = [serialize_rollout(r) for r in rollouts]

    io = _fused_io_for(cfg)
    mem.reset("fsb_a"), mem.reset("fsb_b")
    fused = StagingBuffer(cfg, connect("mem://fsb_a"), fused_io=io).start()
    dense = StagingBuffer(cfg, connect("mem://fsb_b")).start()
    try:
        pub_a, pub_b = connect("mem://fsb_a"), connect("mem://fsb_b")
        for f in frames:
            pub_a.publish_experience(f)
            pub_b.publish_experience(f)
        batch_f, buf = fused.get_batch_groups(timeout=30.0)
        batch_d = dense.get_batch(timeout=30.0)
        assert batch_f is not None
        _bitwise_equal(jax.device_get(jax.jit(io.unpack_single)(buf)), batch_d)
    finally:
        fused.stop(), dense.stop()


# --- parallel host feed (ISSUE 11): sharded pack + transfer ring -------


def _pooled_cfg(workers, native_on=True, dtype="bfloat16"):
    cfg = LearnerConfig(
        batch_size=4,
        seq_len=8,
        native_packer=native_on,
        policy=PolicyConfig(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16, dtype=dtype),
    )
    cfg.staging.pack_workers = workers
    return cfg


def _mixed_wire_frames(n, with_traced=True):
    """Mixed-wire frame list: DTR1 (f32), DTR3 (bf16 wire), and — when
    tracing-era frames are wanted — DTR2 (traced f32, normalized to
    DTR1 at the intake). Partial batches: varying L < seq_len."""
    from dotaclient_tpu.transport.serialize import (
        cast_rollout_obs_bf16,
        serialize_rollout,
        stamp_rollout_trace,
    )

    frames = []
    for i in range(n):
        r = make_rollout(L=3 + (i % 5), H=8, version=0, seed=i, actor_id=i)
        if i % 3 == 0:
            frames.append(serialize_rollout(cast_rollout_obs_bf16(r)))  # DTR3
        elif i % 3 == 1 and with_traced:
            frames.append(stamp_rollout_trace(serialize_rollout(r), i + 1, 123.0))  # DTR2
        else:
            frames.append(serialize_rollout(r))  # DTR1
    return frames


def _drain_batches(cfg, frames, fused, n_batches):
    """Run one staging buffer to completion; returns materialized batch
    copies (+ a copy of the u8 transfer payload per batch when fused)."""
    import copy as _copy

    import jax

    tag = f"pf_{cfg.staging.pack_workers}_{cfg.native_packer}_{fused}_{len(frames)}"
    mem.reset(tag)
    pub = connect(f"mem://{tag}")
    for f in frames:
        pub.publish_experience(f)
    io = _fused_io_for(cfg) if fused else None
    sb = StagingBuffer(cfg, connect(f"mem://{tag}"), version_fn=lambda: 0, fused_io=io)
    if not cfg.native_packer:
        sb._lib = None
    sb.start()
    batches, payloads = [], []
    try:
        for _ in range(n_batches):
            b, payload = sb.get_batch_groups(timeout=30)
            assert b is not None
            batches.append(jax.tree.map(lambda a: np.array(a), b))
            if payload is not None:
                payloads.append(np.array(payload))
            lease = sb.last_batch_lease
            if lease is not None:
                lease.release()
        stats = sb.stats()
    finally:
        sb.stop()
    return batches, payloads, stats


@pytest.mark.parametrize("native_on", [True, False])
@pytest.mark.parametrize("workers", [2, 3, 4])
def test_pack_workers_sharded_fused_bitwise_parity(native_on, workers):
    """THE tentpole proof at staging level: N-worker sharded pack into
    ring slots emits transfer buffers BITWISE identical to the
    single-thread pack — native C packer AND python fallback, mixed
    DTR1/DTR2/DTR3 frames, partial (L < T) rows, across several batches
    (so reused, re-zeroed slots are covered), including workers=3 (an
    uneven row split over B=4)."""
    frames = _mixed_wire_frames(12)
    base_b, base_p, _ = _drain_batches(_pooled_cfg(1, native_on), list(frames), True, 3)
    got_b, got_p, stats = _drain_batches(
        _pooled_cfg(workers, native_on), list(frames), True, 3
    )
    for a, b in zip(base_b, got_b):
        _bitwise_equal(a, b)
    assert len(base_p) == len(got_p) == 3
    for pa, pb in zip(base_p, got_p):
        np.testing.assert_array_equal(pa, pb)
    # scoreboard meters exist only in pool mode
    assert stats["pack_workers"] == workers
    assert stats["pack_ring_depth"] == 2.0
    assert stats["pack_rows_per_s"] > 0
    assert f"pack_worker_busy_s_{workers - 1}" in stats


@pytest.mark.parametrize("native_on", [True, False])
def test_pack_workers_sharded_dense_bitwise_parity(native_on):
    """Dense (non-fused) pooled pack — fresh per-batch allocation, same
    classic cast semantics — matches the single-thread batch bitwise."""
    frames = _mixed_wire_frames(8)
    base_b, _, _ = _drain_batches(_pooled_cfg(1, native_on), list(frames), False, 2)
    got_b, _, stats = _drain_batches(_pooled_cfg(3, native_on), list(frames), False, 2)
    for a, b in zip(base_b, got_b):
        _bitwise_equal(a, b)
    assert "pack_ring_depth" not in stats  # no ring without fused buffers


def test_transfer_ring_lease_backpressure_and_reuse():
    """Ring ownership handoff: with transfer_depth=2 and no lease
    releases, the feed stalls after 2 batches (the ring IS the
    backpressure); releasing a lease hands its buffers back to the
    packers, and the reused slot serves a later batch (same backing
    payload object, re-zeroed)."""
    cfg = _pooled_cfg(2)
    frames = _mixed_wire_frames(20, with_traced=False)
    tag = "ring_lease"
    mem.reset(tag)
    pub = connect(f"mem://{tag}")
    for f in frames:
        pub.publish_experience(f)
    io = _fused_io_for(cfg)
    sb = StagingBuffer(cfg, connect(f"mem://{tag}"), version_fn=lambda: 0, fused_io=io).start()
    try:
        held = []
        ids = []
        for _ in range(2):
            b, payload = sb.get_batch_groups(timeout=30)
            assert b is not None
            ids.append(id(payload))
            held.append(sb.last_batch_lease)
            assert held[-1] is not None
        # both slots leased: no third batch can form
        b3, _ = sb.get_batch_groups(timeout=1.0)
        assert b3 is None
        held[0].release()
        held[0].release()  # idempotent: a double release must not fork the slot
        b3, payload3 = sb.get_batch_groups(timeout=30)
        assert b3 is not None
        # the freed slot's buffer is REUSED, not reallocated
        assert id(payload3) == ids[0]
        lease3 = sb.last_batch_lease
        assert lease3 is not None
        lease3.release()
        held[1].release()
    finally:
        sb.stop()


def test_pack_workers_default_inert_subprocess():
    """Inertness proof (the PR-8 pattern): at the default
    --staging.pack_workers=1 a StagingBuffer builds NONE of the parallel
    feed — no pool threads, no assembler, no intake queue, no ring —
    and the only thread is the classic staging-consumer. Subprocess so
    the thread enumeration sees a clean interpreter."""
    import subprocess
    import sys

    from tests.conftest import clean_subprocess_env

    code = """
import threading
import jax
jax.config.update("jax_platforms", "cpu")
from dotaclient_tpu.config import LearnerConfig, PolicyConfig
from dotaclient_tpu.runtime.staging import StagingBuffer
from dotaclient_tpu.transport.base import connect

cfg = LearnerConfig(batch_size=4, seq_len=8,
    policy=PolicyConfig(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16))
assert cfg.staging.pack_workers == 1 and cfg.staging.transfer_depth == 2
sb = StagingBuffer(cfg, connect("mem://inert"), version_fn=lambda: 0).start()
try:
    assert sb._pool is None and sb._ring is None
    assert sb._intake is None and sb._assembler is None
    names = sorted(t.name for t in threading.enumerate() if t.name.startswith("staging"))
    assert names == ["staging-consumer"], names
    assert not any(k.startswith("pack_") for k in sb.stats()), sb.stats()
finally:
    sb.stop()
print("INERT_OK")
"""
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=120,
        env=clean_subprocess_env(extra={"JAX_PLATFORMS": "cpu"}),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "INERT_OK" in proc.stdout


def test_pack_workers_quiesce_drains_every_station():
    """SIGTERM-drain visibility in pool mode: frames mid-pipeline (pop
    locals, intake queue, pending) must all be trained out before
    drained() turns true, and sub-batch leftovers stay snapshottable —
    the PR-7 zero-loss drain contract extended to the parallel feed."""
    cfg = _pooled_cfg(2)
    tag = "pool_drain"
    mem.reset(tag)
    pub = connect(f"mem://{tag}")
    io = _fused_io_for(cfg)
    sb = StagingBuffer(cfg, connect(f"mem://{tag}"), version_fn=lambda: 0, fused_io=io).start()
    try:
        # one full batch + 3 leftovers
        for f in _mixed_wire_frames(7, with_traced=False):
            pub.publish_experience(f)
        b, _ = sb.get_batch_groups(timeout=30)
        assert b is not None
        lease = sb.last_batch_lease
        if lease is not None:
            lease.release()
        sb.quiesce()
        deadline = time.monotonic() + 10
        while not sb.drained() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert sb.drained()
        snap = sb.snapshot_state()
        assert snap is not None and len(snap["pending"]) == 3
    finally:
        sb.stop()


def test_pack_workers_lockcheck_zero_inversions(lockcheck):
    """Concurrency soak under the instrumented-lock harness: producers
    hammer a pooled fused staging while the consumer loop pops and
    stats() scrapes — the pool/ring/assembler lock graph must show zero
    acquisition-order inversions."""
    import threading

    cfg = _pooled_cfg(3)
    tag = "pool_lock"
    mem.reset(tag)
    io = _fused_io_for(cfg)
    sb = StagingBuffer(cfg, connect(f"mem://{tag}"), version_fn=lambda: 0, fused_io=io).start()
    stop = threading.Event()
    frames = _mixed_wire_frames(16, with_traced=False)

    def produce():
        conn = connect(f"mem://{tag}")
        i = 0
        while not stop.is_set():
            if conn.experience_depth() > 32:
                time.sleep(0.001)
                continue
            conn.publish_experience(frames[i % len(frames)])
            i += 1

    threads = [threading.Thread(target=produce, daemon=True) for _ in range(2)]
    for t in threads:
        t.start()
    try:
        got = 0
        deadline = time.monotonic() + 15
        while got < 6 and time.monotonic() < deadline:
            b, _ = sb.get_batch_groups(timeout=5)
            if b is None:
                continue
            sb.stats()
            lease = sb.last_batch_lease
            if lease is not None:
                lease.release()
            got += 1
        assert got >= 6
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
        sb.stop()
    assert not lockcheck.inversions, lockcheck.report()
    assert sb.stats()["consumer_errors"] == 0


def test_pack_scale_ab_artifact_verdict():
    """Guard the COMMITTED PACK_SCALE_AB.json: bitwise-identical
    transfer buffers, ring overlap observed, pack_workers=1 inert, and
    the scaling verdict — ≥ 2× at 4 workers wherever the independent
    host memcpy probe shows the host can express parallel copy at all;
    on hosts where it cannot (the 2-core bench box: one core saturates
    the memory controller), the raw ratio is committed and excused BY
    THE PROBE, in-artifact (the SERVE_BENCH disclosure pattern)."""
    import json
    import pathlib

    path = pathlib.Path(__file__).resolve().parent.parent / "PACK_SCALE_AB.json"
    data = json.loads(path.read_text())
    v = data["verdict"]
    assert v["all_green"], v
    assert v["transfer_buffers_bitwise_identical"]
    assert v["ring_overlap_observed"]
    assert v["pack_workers_1_inert"]
    assert data["parity"]["native"]["bitwise_identical"]
    assert data["parity"]["python"]["bitwise_identical"]
    # the probe-keyed scaling judgment, exactly as the script computes it
    if v["host_can_express_parallel_copy"]:
        assert v["scaling_1_to_4_x"] >= 2.0
    else:
        assert data["host_copy_scaling"]["copy_scaling_4t"] < 1.5
        assert v["scaling_caveat"]


@pytest.mark.nightly
@pytest.mark.slow  # nightly AND slow: the tier-1 -m 'not slow' override
def test_ab_pack_scale_quick_nightly(tmp_path):
    """Re-run the pack-scale A/B (--quick) in a clean subprocess and
    assert the committed-artifact schema + verdict invariants live. On a
    capable host (memcpy probe ≥ 1.5× at 4 threads) this REQUIRES the
    full ≥ 2× scaling bar — the bar arms itself on real learner-class
    hardware."""
    import json
    import os
    import pathlib
    import subprocess
    import sys

    from tests.conftest import clean_subprocess_env

    script = pathlib.Path(__file__).resolve().parent.parent / "scripts" / "ab_pack_scale.py"
    out = tmp_path / "pack_ab.json"
    proc = subprocess.run(
        [sys.executable, str(script), "--quick", "--out", str(out)],
        capture_output=True,
        text=True,
        timeout=570,
        env=clean_subprocess_env(extra={"JAX_PLATFORMS": "cpu"}),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    data = json.loads(out.read_text())
    for key in ("host_copy_scaling", "packer_scale", "parity", "e2e", "verdict"):
        assert key in data, key
    v = data["verdict"]
    assert v["all_green"], v
    assert v["transfer_buffers_bitwise_identical"] and v["pack_workers_1_inert"]
    if v["host_can_express_parallel_copy"]:
        assert v["scaling_1_to_4_x"] >= 2.0
