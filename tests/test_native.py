"""Native (C++) batch packer tests: build, parity with the python
packer, validation, and the staging-buffer native path (SURVEY.md §2
native-component note, §7 "Throughput of host-side packing")."""

import numpy as np
import pytest

from dotaclient_tpu import native
from dotaclient_tpu.config import LearnerConfig, PolicyConfig
from dotaclient_tpu.runtime.staging import StagingBuffer, pack_rollouts
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import connect
from dotaclient_tpu.transport.serialize import serialize_rollout
from tests.test_transport import make_rollout

lib = native.load_packer()
pytestmark = pytest.mark.skipif(lib is None, reason="native packer unavailable")

SMALL = PolicyConfig(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16, dtype="float32")


def leaves_equal(a, b):
    import jax

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("aux", [False, True])
def test_pack_parity_with_python(aux):
    rollouts = [make_rollout(L=L, H=8, version=i, seed=i, aux=aux) for i, L in enumerate([4, 8, 1, 8])]
    frames = [serialize_rollout(r) for r in rollouts]
    py = pack_rollouts(rollouts, seq_len=8, with_aux=aux)
    nat = native.pack_frames(lib, frames, seq_len=8, lstm_hidden=8, with_aux=aux)
    leaves_equal(py, nat)


def test_pack_aux_frames_into_no_aux_batch():
    """Frames carrying aux targets pack cleanly into a batch that doesn't
    want them (the aux block is skipped, not misparsed)."""
    rollouts = [make_rollout(L=4, H=8, seed=s, aux=True) for s in range(2)]
    frames = [serialize_rollout(r) for r in rollouts]
    py = pack_rollouts(rollouts, seq_len=8, with_aux=False)
    nat = native.pack_frames(lib, frames, seq_len=8, lstm_hidden=8, with_aux=False)
    leaves_equal(py, nat)


def test_padding_preserved():
    """Rows beyond L keep the zeros-batch padding (NOOP-legal masks)."""
    r = make_rollout(L=2, H=8, seed=1)
    nat = native.pack_frames(lib, [serialize_rollout(r)], seq_len=8, lstm_hidden=8, with_aux=False)
    assert nat.mask[0, :2].sum() == 2.0 and nat.mask[0, 2:].sum() == 0.0
    # padded action_mask rows stay NOOP-legal (uniform-safe log-softmax)
    assert np.all(nat.obs.action_mask[0, 3:, 0])


def test_malformed_frame_rejected():
    good = serialize_rollout(make_rollout(L=4, H=8, seed=0))
    with pytest.raises(ValueError, match="frame 1"):
        native.pack_frames(lib, [good, good[:-5]], seq_len=8, lstm_hidden=8, with_aux=False)
    with pytest.raises(ValueError):
        native.pack_frames(lib, [b"DTR1" + b"\x00" * 40], seq_len=8, lstm_hidden=8, with_aux=False)
    # L exceeding the learner seq_len is a config mismatch, not packable
    with pytest.raises(ValueError):
        native.pack_frames(lib, [serialize_rollout(make_rollout(L=9, H=8))], seq_len=8, lstm_hidden=8, with_aux=False)


def test_mask_bytes_normalized_to_bool():
    """Wire mask bytes >1 (hostile/buggy peer) must land as clean bools,
    matching the python path's astype(bool)."""
    r = make_rollout(L=2, H=8, seed=0)
    frame = bytearray(serialize_rollout(r))
    # unit_mask starts right after the three f32 obs arrays
    import dotaclient_tpu.env.featurizer as F

    T1 = 3
    off = 21 + T1 * (F.GLOBAL_FEATURES + F.HERO_FEATURES + F.MAX_UNITS * F.UNIT_FEATURES) * 4
    frame[off] = 255  # a "true" that isn't 1
    nat = native.pack_frames(lib, [bytes(frame)], seq_len=8, lstm_hidden=8, with_aux=False)
    m = np.asarray(nat.obs.unit_mask)
    assert m.dtype == bool
    assert m[0, 0, 0] == True  # normalized, not raw 255
    assert set(np.unique(m.view(np.uint8))) <= {0, 1}


def test_frame_header_fields():
    r = make_rollout(L=5, H=8, version=7, actor_id=42, seed=3)
    hdr = native.frame_header(lib, serialize_rollout(r))
    version, L, H, flags, actor_id, ep_ret, last_done = hdr
    assert (version, L, H, actor_id) == (7, 5, 8, 42)
    assert ep_ret == pytest.approx(1.25)
    assert last_done == 1.0  # make_rollout ends the episode
    assert native.frame_header(lib, b"") is None
    assert native.frame_header(lib, b"XXXX" + b"\x00" * 30) is None


def test_staging_buffer_native_path_matches_python():
    def run(native_packer):
        name = f"nat{int(native_packer)}"
        mem.reset(name)
        broker = connect(f"mem://{name}")
        cfg = LearnerConfig(batch_size=4, seq_len=8, policy=SMALL, native_packer=native_packer)
        st = StagingBuffer(cfg, broker, version_fn=lambda: 100)
        for i in range(4):
            broker.publish_experience(serialize_rollout(make_rollout(L=4 + i, H=8, version=100, seed=i)))
        # one corrupt + one stale frame must be dropped in both paths
        broker.publish_experience(b"DTR1 corrupt")
        stale = make_rollout(L=4, H=8, version=3, seed=9)  # 100-4 > 3
        broker.publish_experience(serialize_rollout(stale))
        st.start()
        batch = st.get_batch(timeout=30.0)
        # the batch can be ready before the trailing bad/stale frames are
        # consumed — wait for all 6 frames to be accounted for
        import time

        deadline = time.time() + 10
        while st.stats()["consumed"] < 6 and time.time() < deadline:
            time.sleep(0.05)
        stats = st.stats()
        st.stop()
        return batch, stats

    nat_batch, nat_stats = run(True)
    py_batch, py_stats = run(False)
    assert nat_stats["dropped_bad"] == py_stats["dropped_bad"] == 1
    assert nat_stats["dropped_stale"] == py_stats["dropped_stale"] == 1
    assert nat_stats["episodes"] == py_stats["episodes"]
    assert nat_stats["episode_return_sum"] == pytest.approx(py_stats["episode_return_sum"])
    leaves_equal(nat_batch, py_batch)


def test_staging_reports_native_flag():
    mem.reset("natflag")
    cfg = LearnerConfig(batch_size=4, seq_len=8, policy=SMALL)
    st = StagingBuffer(cfg, connect("mem://natflag"))
    assert st.native is True


def test_bf16_in_copy_cast_bitwise_matches_numpy():
    """r5 host-packing: obs_bf16=True fuses the f32->bf16 cast into the C
    copy loop. Must be BITWISE equal to the python path (pack then
    numpy astype via cast_obs_to_compute_dtype), including NaN/inf and
    round-to-nearest-even ties."""
    import ml_dtypes

    from dotaclient_tpu.runtime.staging import cast_obs_to_compute_dtype

    rollouts = [make_rollout(L=L, H=8, version=i, seed=i, aux=False) for i, L in enumerate([4, 8, 3])]
    # Salt the obs with cast edge cases: specials, a tie that RNE rounds
    # down (0x1.01p0 -> low bits 0x8000 with even target), denormals.
    specials = np.array(
        [np.nan, np.inf, -np.inf, 0.0, -0.0, 1.0 + 2 ** -8, 1.0 + 2 ** -9, 3.0 + 2 ** -8, 1e-40, -1e-40],
        np.float32,
    )
    # Non-canonical NaNs (payload bits set): ml_dtypes canonicalizes to
    # sign|0x7fc0, dropping the payload — the C path must match (r5
    # review finding), not preserve bits.
    payload_nans = np.array([0x7FA00000, 0xFFA00001, 0x7F800001], np.uint32).view(np.float32)
    specials = np.concatenate([specials, payload_nans])
    g = rollouts[0].obs.global_feats
    g.flat[: specials.size] = specials
    frames = [serialize_rollout(r) for r in rollouts]

    cfg = LearnerConfig(
        batch_size=3, seq_len=8,
        policy=PolicyConfig(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16, dtype="bfloat16"),
    )
    py = cast_obs_to_compute_dtype(cfg, pack_rollouts(rollouts, seq_len=8, with_aux=False))
    nat = native.pack_frames(lib, frames, seq_len=8, lstm_hidden=8, with_aux=False, obs_bf16=True)
    for field in ("global_feats", "hero_feats", "unit_feats"):
        a, b = getattr(py.obs, field), getattr(nat.obs, field)
        assert a.dtype == ml_dtypes.bfloat16 and b.dtype == ml_dtypes.bfloat16
        np.testing.assert_array_equal(a.view(np.uint16), b.view(np.uint16))
    # non-obs floats stay f32 and identical
    np.testing.assert_array_equal(py.rewards, nat.rewards)
    assert nat.rewards.dtype == np.float32


def test_frame_headers_batched_matches_per_frame():
    """The one-call header parse must agree with dt_frame_header on every
    field and flag malformed frames without poisoning neighbors."""
    rollouts = [make_rollout(L=L, H=8, version=10 + i, actor_id=100 + i, seed=i, aux=(i % 2 == 0))
                for i, L in enumerate([4, 8, 1])]
    frames = [serialize_rollout(r) for r in rollouts]
    frames.insert(1, b"DTR1 corrupt")      # malformed in the middle
    frames.append(frames[0][: len(frames[0]) // 2])  # truncated at the end

    ok, versions, Ls, Hs, flags, actor_ids, ep_rets, last_dones = native.frame_headers(lib, frames)
    assert ok == [1, 0, 1, 1, 0]
    for i, f in enumerate(frames):
        single = native.frame_header(lib, f)
        if not ok[i]:
            assert single is None
            continue
        assert single == (versions[i], Ls[i], Hs[i], flags[i], actor_ids[i],
                          pytest.approx(ep_rets[i]), last_dones[i])


def test_staging_native_bf16_path_matches_python_fallback():
    """End-to-end through StagingBuffer with a bf16 policy: the native
    in-copy cast path and the python fallback (deserialize + numpy pack +
    astype) must produce bitwise-identical batches."""
    policy = PolicyConfig(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16, dtype="bfloat16")
    cfg = LearnerConfig(batch_size=4, seq_len=8, policy=policy)
    rollouts = [make_rollout(L=8, H=8, version=0, actor_id=i, seed=i) for i in range(4)]
    frames = [serialize_rollout(r) for r in rollouts]

    batches = {}
    for name in ("native", "python"):
        mem.reset(f"bf16_{name}")
        broker = connect(f"mem://bf16_{name}")
        st = StagingBuffer(cfg, broker, version_fn=lambda: 0)
        if name == "python":
            st._lib = None
        assert st.native == (name == "native")
        for f in frames:
            broker.publish_experience(f)
        st.start()
        batches[name] = st.get_batch(timeout=30)
        st.stop()
    nat, py = batches["native"], batches["python"]
    import ml_dtypes

    assert nat.obs.global_feats.dtype == ml_dtypes.bfloat16
    for field in ("global_feats", "hero_feats", "unit_feats"):
        np.testing.assert_array_equal(
            getattr(nat.obs, field).view(np.uint16), getattr(py.obs, field).view(np.uint16)
        )
    leaves_equal(nat.actions, py.actions)
    np.testing.assert_array_equal(nat.mask, py.mask)


# --- DTR3 quantized wire (ISSUE 8): the cast-free native pack path -----


def test_dtr3_pack_bitwise_matches_f32_wire_convert():
    """THE tentpole parity proof at the C level: packing bf16-wire
    (DTR3) frames into the bf16 batch — a strided memcpy — must be
    BITWISE identical to packing the same rollouts' f32 frames through
    the in-copy convert, NaN canonicalization and RNE ties included
    (the source cast and the pack-time cast are the same function)."""
    from dotaclient_tpu.transport.serialize import cast_rollout_obs_bf16

    rollouts = [make_rollout(L=L, H=8, version=i, seed=i, aux=(i == 0)) for i, L in enumerate([4, 8, 3])]
    specials = np.array([np.nan, np.inf, -np.inf, -0.0, 1.0 + 2 ** -8, 1e-40], np.float32)
    payload_nans = np.array([0x7FA00000, 0xFFA00001], np.uint32).view(np.float32)
    rollouts[0].obs.global_feats.flat[:8] = np.concatenate([specials, payload_nans])
    f32 = [serialize_rollout(r) for r in rollouts]
    bf = [serialize_rollout(cast_rollout_obs_bf16(r)) for r in rollouts]
    a = native.pack_frames(lib, f32, seq_len=8, lstm_hidden=8, with_aux=True, obs_bf16=True)
    b = native.pack_frames(lib, bf, seq_len=8, lstm_hidden=8, with_aux=True, obs_bf16=True)
    import ml_dtypes

    assert b.obs.global_feats.dtype == ml_dtypes.bfloat16
    # obs leaves BITWISE via u16 views (value-compare would choke on the
    # NaNs we salted in — and bit equality is the actual claim)
    for field in ("global_feats", "hero_feats", "unit_feats"):
        np.testing.assert_array_equal(
            getattr(a.obs, field).view(np.uint16), getattr(b.obs, field).view(np.uint16)
        )

    def sans_float_obs(batch):
        return batch._replace(
            obs=batch.obs._replace(global_feats=0, hero_feats=0, unit_feats=0)
        )

    leaves_equal(sans_float_obs(a), sans_float_obs(b))


def test_dtr3_pack_into_f32_batch_upcasts_exactly():
    """bf16 wire consumed by an f32-batch config (obs_bf16=0): the C
    widening must equal numpy's exact bf16->f32 upcast — a mixed fleet
    mid-roll must not corrupt an f32-compute learner."""
    from dotaclient_tpu.transport.serialize import cast_rollout_obs_bf16

    r = make_rollout(L=4, H=8, seed=5)
    rb = cast_rollout_obs_bf16(r)
    nat = native.pack_frames(
        lib, [serialize_rollout(rb)], seq_len=8, lstm_hidden=8, with_aux=False, obs_bf16=False
    )
    assert nat.obs.global_feats.dtype == np.float32
    np.testing.assert_array_equal(
        nat.obs.global_feats[0, :5], np.asarray(rb.obs.global_feats).astype(np.float32)
    )


def test_dtr3_strided_pack_bitwise_matches_dense():
    """DTR3 frames through the fused-H2D strided views (row_strides
    path) — the production landing zone — must match the dense pack."""
    import jax

    from dotaclient_tpu.parallel.fused_io import FusedBatchIO
    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.transport.serialize import cast_rollout_obs_bf16

    policy = PolicyConfig(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16, dtype="bfloat16")
    cfg = LearnerConfig(batch_size=4, seq_len=8, policy=policy)
    rollouts = [make_rollout(L=3 + i, H=8, seed=i, actor_id=i) for i in range(4)]
    frames = [serialize_rollout(cast_rollout_obs_bf16(r)) for r in rollouts]
    dense = native.pack_frames(lib, frames, seq_len=8, lstm_hidden=8, with_aux=False, obs_bf16=True)
    from dotaclient_tpu.parallel.train_step import _batch_template
    from dotaclient_tpu.runtime.staging import cast_obs_to_compute_dtype

    template = cast_obs_to_compute_dtype(cfg, jax.tree.map(np.asarray, _batch_template(cfg)))
    io = FusedBatchIO(template, mesh_lib.make_mesh("dp=-1"))
    _, out = io.alloc_transfer()
    native.pack_frames(lib, frames, seq_len=8, lstm_hidden=8, with_aux=False, obs_bf16=True, out=out)
    leaves_equal(dense, out)


def test_dtr3_malformed_maps_rejected_cleanly():
    """Corrupt/truncated dtype-maps: error code (frame index named),
    never a fault — and the accept set matches the python parser."""
    from dotaclient_tpu.transport.serialize import (
        WireDtypeError,
        cast_rollout_obs_bf16,
        deserialize_rollout,
    )

    good = serialize_rollout(cast_rollout_obs_bf16(make_rollout(L=4, H=8, seed=0)))
    mutants = {
        "bad_code": bytes(good[:38]) + b"\x07" + bytes(good[39:]),
        "mixed_obs": bytes(good[:39]) + b"\x00" + bytes(good[40:]),  # codes[1] f32
        "bad_count": bytes(good[:37]) + b"\x05" + bytes(good[38:]),
        "truncated_map": good[:40],
    }
    for name, m in mutants.items():
        assert native.frame_header(lib, m) is None, name
        with pytest.raises((ValueError, WireDtypeError)):
            deserialize_rollout(m)
        with pytest.raises(ValueError):
            native.pack_frames(lib, [m], seq_len=8, lstm_hidden=8, with_aux=False, obs_bf16=True)


def test_isa_fingerprint_invalidates_foreign_so(tmp_path, monkeypatch):
    """A cached -march=native .so from a DIFFERENT host must be rebuilt,
    not loaded (mtime alone would reuse it and risk SIGILL mid-pack)."""
    import shutil

    src = tmp_path / "packer.cc"
    so = tmp_path / "_packer.so"
    shutil.copy(native._SRC, src)
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_LIB", str(so))
    monkeypatch.setattr(native, "_LIB_HOST", str(so) + ".host")
    monkeypatch.setattr(native, "_DIR", str(tmp_path))

    assert native._build() and so.exists()
    assert (tmp_path / "_packer.so.host").read_text() == native._host_isa()
    first_build = so.stat().st_mtime_ns

    # Same host, valid fingerprint: cache hit, no rebuild.
    assert native._build()
    assert so.stat().st_mtime_ns == first_build

    # Forge a foreign host's fingerprint: must rebuild even though the
    # .so is newer than the source.
    (tmp_path / "_packer.so.host").write_text("deadbeefdeadbeef")
    assert native._build()
    assert so.stat().st_mtime_ns != first_build
    assert (tmp_path / "_packer.so.host").read_text() == native._host_isa()


def _template_from(batch):
    """FusedBatchIO needs a mesh; 1-device CPU mesh suffices for layout."""
    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.parallel.fused_io import FusedBatchIO

    import jax

    mesh = mesh_lib.make_mesh("dp=1", devices=jax.devices()[:1])
    return FusedBatchIO(batch, mesh)


@pytest.mark.parametrize("aux", [False, True])
@pytest.mark.parametrize("obs_bf16", [False, True])
def test_strided_pack_bitwise_matches_dense(aux, obs_bf16):
    """dt_pack_batch with row strides (writing into the fused-H2D
    transfer buffer through leaf views: byte-offset strides into one
    [B, row_bytes] u8 buffer) must produce BITWISE the batch the dense
    path does, and the buffer must equal io.pack_transfer(dense) — i.e.
    packing in place changes no byte of what ships. Frames
    salted with NaNs and RNE ties so the bf16 in-copy cast is exercised
    on its hard cases through the strided path too."""
    rollouts = [make_rollout(L=3 + (i % 4), H=8, seed=i, aux=aux, actor_id=i) for i in range(6)]
    for i, r in enumerate(rollouts):
        r.obs.global_feats[0, :3] = [np.nan, 1.00390625, -1.00390625]  # NaN + tie cases
        r.obs.hero_feats[0, 0] = np.float32.__call__(2.0) ** -130  # denormal-ish
    frames = [serialize_rollout(r) for r in rollouts]

    dense = native.pack_frames(lib, frames, 8, 8, aux, obs_bf16=obs_bf16)
    io = _template_from(dense)
    buf, out = io.alloc_transfer()
    native.pack_frames(lib, frames, 8, 8, aux, obs_bf16=obs_bf16, out=out)
    # bitwise: view raw bytes so canonicalized NaNs compare EQUAL (the
    # point of the salt) instead of tripping float NaN != NaN.
    import jax

    for a, b in zip(jax.tree.leaves(dense), jax.tree.leaves(out)):
        np.testing.assert_array_equal(
            np.ascontiguousarray(a).view(np.uint8), np.ascontiguousarray(b).view(np.uint8)
        )
    np.testing.assert_array_equal(buf, io.pack_transfer(dense))


def test_strided_pack_rejects_wrong_rows():
    frames = [serialize_rollout(make_rollout(L=3, H=8, seed=i)) for i in range(4)]
    dense = native.pack_frames(lib, frames, 8, 8, False)
    io = _template_from(dense)
    _, out = io.alloc_transfer()
    with pytest.raises(ValueError, match="rows"):
        native.pack_frames(lib, frames[:3], 8, 8, False, out=out)


@pytest.mark.parametrize("obs_bf16", [False, True])
def test_strided_pack_unpacks_on_device_to_dense(obs_bf16):
    """What the C packer wrote through the transfer buffer's leaf views,
    unpacked inside a jit as the train step does (segment slices and
    bitcasts), is bitwise the dense pack: the packer's byte offsets and
    the device's agree."""
    rollouts = [make_rollout(L=3 + (i % 4), H=8, seed=i, actor_id=i) for i in range(6)]
    for r in rollouts:
        r.obs.global_feats[0, :3] = [np.nan, 1.00390625, -1.00390625]
    frames = [serialize_rollout(r) for r in rollouts]

    dense = native.pack_frames(lib, frames, 8, 8, False, obs_bf16=obs_bf16)
    io = _template_from(dense)
    buf, out = io.alloc_transfer()
    native.pack_frames(lib, frames, 8, 8, False, obs_bf16=obs_bf16, out=out)
    import jax

    on_device = jax.device_get(jax.jit(io.unpack_single)(buf))
    for a, b in zip(jax.tree.leaves(dense), jax.tree.leaves(on_device)):
        np.testing.assert_array_equal(
            np.ascontiguousarray(a).view(np.uint8), np.ascontiguousarray(b).view(np.uint8)
        )


# --- sharded pack (ISSUE 11): row_offset C path + PackPlan -------------


def test_row_offset_sharded_pack_bitwise_matches_dense():
    """N dt_pack_batch calls over disjoint row ranges of ONE out batch
    (incl. an uneven split) must equal the one-call pack bitwise — the
    C half of the --staging.pack_workers contract, through the fused
    strided views (the production target)."""
    from dotaclient_tpu.runtime.staging import shard_rows

    rollouts = [make_rollout(L=3 + (i % 4), H=8, seed=i, actor_id=i) for i in range(7)]
    for r in rollouts:
        r.obs.global_feats[0, :3] = [np.nan, 1.00390625, -1.00390625]
    frames = [serialize_rollout(r) for r in rollouts]
    dense = native.pack_frames(lib, frames, 8, 8, False, obs_bf16=True)
    io = _template_from(dense)
    for workers in (2, 3):  # 3 over 7 rows = uneven (3/2/2)
        _, out = io.alloc_transfer()
        for off, cnt in shard_rows(len(frames), workers):
            native.pack_frames(
                lib, frames[off : off + cnt], 8, 8, False, obs_bf16=True,
                out=out, row_offset=off, total_rows=len(frames),
            )
        import jax

        for a, b in zip(jax.tree.leaves(dense), jax.tree.leaves(out)):
            np.testing.assert_array_equal(
                np.ascontiguousarray(a).view(np.uint8),
                np.ascontiguousarray(b).view(np.uint8),
            )


def test_row_offset_validation():
    """row_offset/total_rows misuse fails loudly at the pack boundary:
    shards outside the out batch and row_offset without an out are
    config errors, never silent memory stomps."""
    from dotaclient_tpu.ops.batch import BatchLayoutError

    frames = [serialize_rollout(make_rollout(L=3, H=8, seed=i)) for i in range(4)]
    dense = native.pack_frames(lib, frames, 8, 8, False)
    io = _template_from(dense)
    _, out = io.alloc_transfer()
    with pytest.raises(BatchLayoutError):
        native.pack_frames(lib, frames, 8, 8, False, out=out, row_offset=2, total_rows=4)
    with pytest.raises(BatchLayoutError):
        native.pack_frames(lib, frames[:2], 8, 8, False, out=out, row_offset=0, total_rows=8)
    with pytest.raises(ValueError, match="out"):
        native.pack_frames(lib, frames, 8, 8, False, row_offset=1)


def test_pack_plan_matches_pack_frames_and_reports_absolute_row():
    """PackPlan (the prebuilt per-shard call template the ring path
    reuses every batch) must byte-match pack_frames across REPEATED
    packs of different frames into the same buffer, and name the
    ABSOLUTE batch row when a shard frame is malformed."""
    from dotaclient_tpu.ops.batch import BatchLayoutError
    from dotaclient_tpu.runtime.staging import shard_rows

    B = 6
    frame_sets = []
    for s in range(2):
        rollouts = [
            make_rollout(L=2 + ((i + s) % 5), H=8, seed=100 * s + i, actor_id=i)
            for i in range(B)
        ]
        frame_sets.append([serialize_rollout(r) for r in rollouts])
    io = _template_from(native.pack_frames(lib, frame_sets[0], 8, 8, False, obs_bf16=True))
    buf_ref, out_ref = io.alloc_transfer()
    buf_plan, out_plan = io.alloc_transfer()
    plans = [
        native.PackPlan(lib, out_plan, cnt, 8, 8, False, True, off, B)
        for off, cnt in shard_rows(B, 2)
    ]
    for frames in frame_sets:  # reuse: same plans, new frames
        native.pack_frames(lib, frames, 8, 8, False, obs_bf16=True, out=out_ref)
        for p in plans:
            p.pack(frames[p.row_offset : p.row_offset + p.n])
        np.testing.assert_array_equal(buf_ref, buf_plan)
    # malformed frame in the SECOND shard: error names the absolute row
    bad = list(frame_sets[0])
    bad_row = plans[1].row_offset
    bad[bad_row] = bad[bad_row][:-3]
    with pytest.raises(ValueError, match=f"frame {bad_row}"):
        plans[1].pack(bad[plans[1].row_offset : plans[1].row_offset + plans[1].n])
    # wrong shard size is a layout error, not a silent partial pack
    with pytest.raises(BatchLayoutError):
        plans[0].pack(frame_sets[0][: plans[0].n - 1])
