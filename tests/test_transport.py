import struct
import threading
import time
import tracemalloc

import numpy as np
import pytest

from dotaclient_tpu.env import featurizer as F
from dotaclient_tpu.ops.action_dist import Action
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import connect
from dotaclient_tpu.transport.serialize import (
    Rollout,
    RolloutAux,
    deserialize_rollout,
    deserialize_weights,
    flatten_params,
    peek_rollout_trace,
    serialize_rollout,
    serialize_weights,
    stamp_rollout_trace,
    strip_rollout_trace,
    unflatten_params,
)
from dotaclient_tpu.transport.tcp import BrokerServer, TcpBroker


def make_rollout(L=5, H=8, version=3, actor_id=11, aux=False, seed=0):
    r = np.random.RandomState(seed)
    T1 = L + 1
    obs = F.Observation(
        global_feats=r.randn(T1, F.GLOBAL_FEATURES).astype(np.float32),
        hero_feats=r.randn(T1, F.HERO_FEATURES).astype(np.float32),
        unit_feats=r.randn(T1, F.MAX_UNITS, F.UNIT_FEATURES).astype(np.float32),
        unit_mask=r.rand(T1, F.MAX_UNITS) < 0.5,
        target_mask=r.rand(T1, F.MAX_UNITS) < 0.3,
        action_mask=r.rand(T1, F.N_ACTION_TYPES) < 0.8,
    )
    return Rollout(
        obs=obs,
        actions=Action(
            type=r.randint(0, 4, L).astype(np.int32),
            move_x=r.randint(0, 9, L).astype(np.int32),
            move_y=r.randint(0, 9, L).astype(np.int32),
            target=r.randint(0, F.MAX_UNITS, L).astype(np.int32),
        ),
        behavior_logp=r.randn(L).astype(np.float32),
        behavior_value=r.randn(L).astype(np.float32),
        rewards=r.randn(L).astype(np.float32),
        dones=np.concatenate([np.zeros(L - 1, np.float32), np.ones(1, np.float32)]),
        initial_state=(r.randn(H).astype(np.float32), r.randn(H).astype(np.float32)),
        version=version,
        actor_id=actor_id,
        episode_return=1.25,
        aux=RolloutAux(
            win=np.sign(r.randn(L)).astype(np.float32),
            last_hit=r.rand(L).astype(np.float32),
            net_worth=r.rand(L).astype(np.float32),
        )
        if aux
        else None,
    )


@pytest.mark.parametrize("aux", [False, True])
def test_rollout_roundtrip(aux):
    r0 = make_rollout(aux=aux)
    data = serialize_rollout(r0)
    r1 = deserialize_rollout(data)
    assert r1.version == 3 and r1.actor_id == 11 and r1.length == 5
    assert abs(r1.episode_return - 1.25) < 1e-6
    for a, b in zip(
        [*r0.obs, *r0.actions, r0.behavior_logp, r0.rewards, *r0.initial_state],
        [*r1.obs, *r1.actions, r1.behavior_logp, r1.rewards, *r1.initial_state],
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if aux:
        np.testing.assert_array_equal(r0.aux.win, r1.aux.win)
    else:
        assert r1.aux is None


def test_rollout_rejects_garbage():
    with pytest.raises(ValueError):
        deserialize_rollout(b"garbage")
    good = serialize_rollout(make_rollout())
    with pytest.raises(ValueError):
        deserialize_rollout(good[: len(good) // 2])
    with pytest.raises(ValueError):
        deserialize_rollout(good + b"x")


# --- rollout-frame golden bytes: DTR1 / DTR2 rolling upgrade ------------
#
# serialize.py's module docstring is the wire SPEC; these freeze the
# rollout layouts the same way the DTW goldens below freeze the weight
# layouts. The frames are ~2.5 KB (featurizer-schema arrays), so the
# array tail is pinned by sha256 and the header — the layout-bearing
# part — by exact hex.
#
# DTR1 header: 44545231   magic b'DTR1'
#              07000000   u32 version=7
#              0100 0200  u16 L=1, u16 H=2
#              00         u8 flags=0
#              0b000000   u32 actor_id=11
#              0000a03f   f32 episode_return=1.25
ROLLOUT_DTR1_HEADER_HEX = "445452310700000001000200000b0000000000a03f"
ROLLOUT_DTR1_SHA256 = "7ae3c118d28965b3caed639768188b0d4ac05ee30ab2b8bce5009c7df4d9b183"
# DTR2 = the same header under magic b'DTR2', then the trace extension:
#              0df0fecaefbeadde   u64 trace_id=0xDEADBEEFCAFEF00D
#              00000060b813da41   f64 birth_time=1.75e9
# then the arrays, byte-identical to DTR1.
ROLLOUT_DTR2_HEADER_HEX = (
    "445452320700000001000200000b0000000000a03f0df0fecaefbeadde00000060b813da41"
)
ROLLOUT_DTR2_SHA256 = "f1d0c9d4e45fb1127d9f3ac4848de136e3f34406088d03dcb7751585a70f6498"

GOLDEN_TRACE_ID = 0xDEADBEEFCAFEF00D
GOLDEN_BIRTH = 1.75e9


def make_golden_rollout():
    """Fully deterministic rollout (arange/constant arrays, no RNG) so
    the frozen hashes are reproducible everywhere."""
    L, H = 1, 2
    T1 = L + 1

    def ar(shape, dtype, scale=0.125):
        n = int(np.prod(shape))
        return (np.arange(n, dtype=np.float64) * scale).astype(dtype).reshape(shape)

    obs = F.Observation(
        global_feats=ar((T1, F.GLOBAL_FEATURES), np.float32),
        hero_feats=ar((T1, F.HERO_FEATURES), np.float32),
        unit_feats=ar((T1, F.MAX_UNITS, F.UNIT_FEATURES), np.float32),
        unit_mask=(np.arange(T1 * F.MAX_UNITS).reshape(T1, F.MAX_UNITS) % 2).astype(bool),
        target_mask=(np.arange(T1 * F.MAX_UNITS).reshape(T1, F.MAX_UNITS) % 3 == 0),
        action_mask=np.ones((T1, F.N_ACTION_TYPES), bool),
    )
    return Rollout(
        obs=obs,
        actions=Action(
            type=np.array([1], np.int32),
            move_x=np.array([2], np.int32),
            move_y=np.array([3], np.int32),
            target=np.array([4], np.int32),
        ),
        behavior_logp=np.array([-1.5], np.float32),
        behavior_value=np.array([0.25], np.float32),
        rewards=np.array([0.5], np.float32),
        dones=np.array([1.0], np.float32),
        initial_state=(np.array([0.1, 0.2], np.float32), np.array([0.3, 0.4], np.float32)),
        version=7,
        actor_id=11,
        episode_return=1.25,
    )


def test_rollout_frame_golden_bytes_dtr1():
    """An UNTRACED rollout serializes to byte-identical legacy DTR1 —
    the 'new producer, obs off → old consumer' leg of the rolling
    upgrade: a default-config actor's frames never change."""
    import hashlib

    data = serialize_rollout(make_golden_rollout())
    assert data[:21].hex() == ROLLOUT_DTR1_HEADER_HEX
    assert hashlib.sha256(data).hexdigest() == ROLLOUT_DTR1_SHA256


def test_rollout_frame_golden_bytes_dtr2():
    """The trace-extended frame: frozen header + tail, and the stamped
    frame is exactly stamp_rollout_trace(DTR1 frame)."""
    import hashlib

    r = make_golden_rollout()._replace(trace_id=GOLDEN_TRACE_ID, birth_time=GOLDEN_BIRTH)
    data = serialize_rollout(r)
    assert data[:37].hex() == ROLLOUT_DTR2_HEADER_HEX
    assert hashlib.sha256(data).hexdigest() == ROLLOUT_DTR2_SHA256
    assert data == stamp_rollout_trace(serialize_rollout(make_golden_rollout()),
                                       GOLDEN_TRACE_ID, GOLDEN_BIRTH)


# DTR3 (quantized wire): the DTR2 header under magic b'DTR3' with the
# trace fields ZERO when untraced, then the dtype-map:
#              10         u8 n_dtypes=16 (no aux)
#              030303     obs floats bf16 (code 3)
#              020202     masks u8
#              01010101   action heads i32
#              000000000000  scalars + init state f32
# then the arrays, float obs leaves as bf16 (RNE cast at the SOURCE).
ROLLOUT_DTR3_HEADER_HEX = (
    "445452330700000001000200000b0000000000a03f00000000000000000000000000000000"
    "1003030302020201010101000000000000"
)
ROLLOUT_DTR3_SHA256 = "bea27b302ba4190adf4c42782b750f199c358293b0c08133c4f9400c389ae07d"
# Traced DTR3: same frame with the golden trace fields in place of zeros.
ROLLOUT_DTR3_TRACED_HEADER_HEX = (
    "445452330700000001000200000b0000000000a03f0df0fecaefbeadde00000060b813da41"
    "1003030302020201010101000000000000"
)
ROLLOUT_DTR3_TRACED_SHA256 = (
    "3e4624a9906408e26fa71ede2add4d5a258455b3a02636376d4d9b0d92933215"
)
_DTR3_HDR_LEN = 37 + 1 + 16  # DTR2 header + count byte + 16 dtype codes


def test_rollout_frame_golden_bytes_dtr3():
    """The quantized-wire frame: frozen header+dtype-map and tail, for
    the untraced AND traced forms (ONE format either way — DTR3 carries
    the trace fields unconditionally, zeros when untraced)."""
    import hashlib

    from dotaclient_tpu.transport.serialize import cast_rollout_obs_bf16

    r = cast_rollout_obs_bf16(make_golden_rollout())
    data = serialize_rollout(r)
    assert data[:_DTR3_HDR_LEN].hex() == ROLLOUT_DTR3_HEADER_HEX
    assert hashlib.sha256(data).hexdigest() == ROLLOUT_DTR3_SHA256
    traced = serialize_rollout(r._replace(trace_id=GOLDEN_TRACE_ID, birth_time=GOLDEN_BIRTH))
    assert traced[:_DTR3_HDR_LEN].hex() == ROLLOUT_DTR3_TRACED_HEADER_HEX
    assert hashlib.sha256(traced).hexdigest() == ROLLOUT_DTR3_TRACED_SHA256
    assert peek_rollout_trace(traced) == (GOLDEN_TRACE_ID, GOLDEN_BIRTH)
    assert peek_rollout_trace(data) == (0, 0.0)


def test_rollout_dtr3_roundtrip_and_cast_semantics():
    """bf16 frames decode to bf16 obs leaves (no silent upcast),
    re-serialize byte-identically (the reservoir's python-path spill
    codec), and the source cast is EXACTLY numpy's RNE astype — the
    same rounding staging applies to f32 frames."""
    import ml_dtypes

    from dotaclient_tpu.transport.serialize import (
        cast_rollout_obs_bf16,
        rollout_obs_bf16,
    )

    r0 = make_rollout(L=5, H=8, aux=True, seed=3)
    rb = cast_rollout_obs_bf16(r0)
    assert rollout_obs_bf16(rb) and not rollout_obs_bf16(r0)
    np.testing.assert_array_equal(
        np.asarray(rb.obs.unit_feats), r0.obs.unit_feats.astype(ml_dtypes.bfloat16)
    )
    # masks and non-obs leaves untouched by the cast
    assert rb.obs.unit_mask.dtype == r0.obs.unit_mask.dtype
    assert rb.rewards.dtype == np.float32
    data = serialize_rollout(rb)
    assert data[:4] == b"DTR3"
    r1 = deserialize_rollout(data)
    assert rollout_obs_bf16(r1)
    assert serialize_rollout(r1) == data
    np.testing.assert_array_equal(np.asarray(r1.rewards), r0.rewards)
    # idempotent: casting a bf16 rollout is a no-op
    np.testing.assert_array_equal(
        np.asarray(cast_rollout_obs_bf16(rb).obs.hero_feats), np.asarray(rb.obs.hero_feats)
    )


def test_wire_cast_fn_resolution():
    from dotaclient_tpu.transport.serialize import cast_rollout_obs_bf16, wire_cast_fn

    r = make_rollout()
    assert wire_cast_fn("f32")(r) is r  # identity, not a copy
    assert serialize_rollout(wire_cast_fn("bf16")(r)) == serialize_rollout(
        cast_rollout_obs_bf16(r)
    )
    with pytest.raises(ValueError):
        wire_cast_fn("int8")


def _old_reader_magic_check(data: bytes) -> str:
    """The frozen accept logic of a PRE-DTR3 consumer (this build's own
    DTR1/DTR2 goldens pin those magics): exact-match DTR1 or DTR2, else
    the loud 'bad rollout frame' ValueError. Emulated here because the
    live parsers now speak DTR3 — this is the 'old consumer' half of the
    rolling-upgrade contract."""
    if data[:4] in (b"DTR1", b"DTR2"):
        return "accepted"
    raise ValueError("bad rollout frame")


def test_rollout_dtr3_rolling_upgrade_both_directions():
    """new producer (bf16 wire) → old consumer: rejected LOUDLY (magic
    mismatch — never a silent misparse), which is why the upgrade order
    is consumers-first. old producer → new consumer and new-f32 →
    old consumer: unchanged bytes, still accepted. new consumer accepts
    all three magics."""
    from dotaclient_tpu.transport.serialize import cast_rollout_obs_bf16

    plain = serialize_rollout(make_golden_rollout())
    traced = stamp_rollout_trace(plain, GOLDEN_TRACE_ID, GOLDEN_BIRTH)
    quant = serialize_rollout(cast_rollout_obs_bf16(make_golden_rollout()))
    # old consumer: accepts DTR1/DTR2 (frozen), rejects DTR3 loudly
    assert _old_reader_magic_check(plain) == "accepted"
    assert _old_reader_magic_check(traced) == "accepted"
    with pytest.raises(ValueError):
        _old_reader_magic_check(quant)
    # new consumer: accepts ALL THREE, with consistent decoded values
    r1, r2, r3 = map(deserialize_rollout, (plain, traced, quant))
    np.testing.assert_array_equal(r1.rewards, r3.rewards)
    np.testing.assert_array_equal(r1.rewards, r2.rewards)
    assert r3.version == r1.version == 7
    # DTR3 is NOT strippable to DTR1 (the arrays are re-encoded, not
    # suffixed): strip passes it through untouched for the native packer
    assert strip_rollout_trace(quant) is quant


def test_native_packer_accepts_all_three_formats():
    """The native C parser is the new consumer's fast path: DTR1 direct,
    DTR2 via the intake strip, DTR3 whole — same header values out of
    each."""
    from dotaclient_tpu import native
    from dotaclient_tpu.transport.serialize import cast_rollout_obs_bf16

    lib = native.load_packer()
    if lib is None:
        pytest.skip("native packer unavailable")
    plain = serialize_rollout(make_golden_rollout())
    traced = stamp_rollout_trace(plain, 1, 1.0)
    quant = serialize_rollout(cast_rollout_obs_bf16(make_golden_rollout()))
    h1 = native.frame_header(lib, plain)
    h3 = native.frame_header(lib, quant)
    assert h1 is not None and h3 is not None and h1 == h3
    assert native.frame_header(lib, traced) is None  # DTR2 needs the strip
    assert native.frame_header(lib, strip_rollout_trace(traced)) == h1
    # corrupt dtype-map: rejected at the header, same accept set as python
    bad = bytearray(quant)
    bad[38] = 7
    assert native.frame_header(lib, bytes(bad)) is None


def test_wire_quant_ab_artifact_verdict():
    """Guard the COMMITTED WIRE_QUANT_AB.json: the acceptance verdict
    (obs wire bytes ~2x, h2d obs share ~2x, packer >= 1.5x, bitwise
    TrainBatch parity) must be all-green — a regressed re-run must not
    land silently. The nightly wrapper below re-proves it live."""
    import json
    import pathlib

    path = pathlib.Path(__file__).resolve().parent.parent / "WIRE_QUANT_AB.json"
    data = json.loads(path.read_text())
    assert data["verdict"]["all_green"], data["verdict"]
    assert data["parity"]["native"]["bitwise_identical"]
    assert data["parity"]["python"]["bitwise_identical"]
    assert data["wire_bytes"]["obs_share_reduction_x"] >= 1.9
    assert data["h2d"]["obs_share_reduction_x"] >= 1.9
    assert data["packer_only"]["speedup_x"] >= 1.5


def test_wire_soak_artifact_verdict():
    """Guard the COMMITTED WIRE_SOAK.json — the sign-off PR 8 gated the
    prod bf16 flip on (k8s/actors.yaml now pins bf16; test_k8s ties the
    pin to this verdict). All three fleet states must be green: zero
    quarantines/bad drops, training through every phase, wire meters
    walking exactly with the fleet, and the bytes-per-frame ratio in
    the quantization band."""
    import json
    import pathlib

    path = pathlib.Path(__file__).resolve().parent.parent / "WIRE_SOAK.json"
    data = json.loads(path.read_text())
    assert data["verdict"]["ok"] is True, data["verdict"]
    for phase in ("phase_1_all_f32", "phase_2_mixed", "phase_3_all_bf16"):
        checks = data[phase]["checks"]
        assert all(checks.values()), f"{phase}: {checks}"
        assert data[phase]["quarantined_delta"] == 0
    assert data["phase_2_mixed"]["frames_bf16"] > 0
    assert data["phase_2_mixed"]["frames_f32"] > 0
    assert 0.4 <= data["wire_bytes_per_frame_ratio_bf16_vs_f32"] <= 0.8


@pytest.mark.nightly
@pytest.mark.slow  # nightly AND slow: the tier-1 -m 'not slow' override
def test_wire_soak_quick_nightly(tmp_path):
    """Re-run the bf16 wire soak (--quick) in a clean subprocess: the
    same invariants the committed artifact froze, at nightly scale."""
    import json
    import os
    import pathlib
    import subprocess
    import sys

    from tests.conftest import clean_subprocess_env

    script = pathlib.Path(__file__).resolve().parent.parent / "scripts" / "soak_wire_bf16.py"
    out = tmp_path / "wire_soak.json"
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
    assert data["verdict"]["ok"] is True, data["verdict"]


@pytest.mark.nightly
@pytest.mark.slow  # nightly AND slow: the tier-1 -m 'not slow' override
def test_ab_wire_quant_nightly():
    """Re-run the wire-quant A/B (--quick) in a clean subprocess and
    assert the same invariants the committed artifact froze. Parity and
    the byte reductions are deterministic; the packer ratio gets slack
    for CI host noise (the committed artifact pins >= 1.5 from a quiet
    run)."""
    import json
    import os
    import pathlib
    import subprocess
    import sys
    import tempfile

    from tests.conftest import clean_subprocess_env

    script = pathlib.Path(__file__).resolve().parent.parent / "scripts" / "ab_wire_quant.py"
    env = clean_subprocess_env()
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "ab.json")
        proc = subprocess.run(
            [sys.executable, str(script), "--quick", "--out", out],
            capture_output=True,
            text=True,
            timeout=570,
            env=env,
        )
        # rc 1 = the script's own strict >=1.5x packer gate failed; the
        # JSON is still written and judged below with CI-noise slack.
        # Anything else is a real crash.
        assert proc.returncode in (0, 1), proc.stderr[-2000:]
        data = json.loads(pathlib.Path(out).read_text())
    assert data["parity"]["native"]["bitwise_identical"]
    assert data["parity"]["python"]["bitwise_identical"]
    assert data["wire_bytes"]["obs_share_reduction_x"] >= 1.9
    assert data["h2d"]["obs_share_reduction_x"] >= 1.9
    assert data["packer_only"]["speedup_x"] >= 1.3  # CI-noise slack


def test_rollout_rolling_upgrade_both_directions():
    """old producer → new consumer: a plain DTR1 frame decodes with zero
    trace fields. new producer → old consumer: strip_rollout_trace
    recovers the byte-identical DTR1 frame an old parser (python or the
    native C packer) speaks — the staging intake's normalization."""
    plain = serialize_rollout(make_golden_rollout())
    r_old = deserialize_rollout(plain)  # old producer, new consumer
    assert r_old.trace_id == 0 and r_old.birth_time == 0.0 and not r_old.traced
    traced = stamp_rollout_trace(plain, GOLDEN_TRACE_ID, GOLDEN_BIRTH)
    r_new = deserialize_rollout(traced)  # new producer, new consumer
    assert r_new.trace_id == GOLDEN_TRACE_ID and r_new.birth_time == GOLDEN_BIRTH
    np.testing.assert_array_equal(r_new.rewards, r_old.rewards)
    # new producer → old consumer, via the intake normalization
    assert strip_rollout_trace(traced) == plain
    assert strip_rollout_trace(plain) is plain  # legacy frames: no copy
    assert peek_rollout_trace(traced) == (GOLDEN_TRACE_ID, GOLDEN_BIRTH)
    assert peek_rollout_trace(plain) == (0, 0.0)


def test_rollout_trace_survives_reserialize():
    """deserialize → serialize round-trips the trace extension (the
    replay reservoir's python-path spill encode/decode)."""
    traced = serialize_rollout(
        make_golden_rollout()._replace(trace_id=5, birth_time=2.5)
    )
    assert serialize_rollout(deserialize_rollout(traced)) == traced


def test_native_packer_rejects_dtr2_but_accepts_stripped():
    """The native C header parser is the in-repo stand-in for an OLD
    consumer: it must reject the extended frame outright (never
    misparse it), and accept the stripped normalization."""
    from dotaclient_tpu import native

    lib = native.load_packer()
    if lib is None:
        pytest.skip("native packer unavailable")
    plain = serialize_rollout(make_golden_rollout())
    traced = stamp_rollout_trace(plain, 1, 1.0)
    assert native.frame_header(lib, traced) is None
    hdr = native.frame_header(lib, strip_rollout_trace(traced))
    assert hdr is not None and hdr[0] == 7 and hdr[1] == 1


# --- weight-frame golden bytes (VERDICT r4 item 5) ----------------------
#
# serialize.py's module docstring is the wire SPEC a native (non-Python)
# reader is written from; these bytes freeze it. Layout, annotated:
#
# DTW2 header: 44545732       magic b'DTW2'
#              07000000       u32 version=7
#              efbeadde       u32 boot_epoch=0xDEADBEEF
#              02000000       u32 n_leaves=2
# leaf "w":    0100 77        u16 name_len=1, name=b'w'
#              01 02000000    u8 ndim=1, u32 dim0=2
#              00             u8 dtype_code=0 (f32)
#              0000803f 000000c0    [1.0, -2.0]
# leaf "b":    0100 62        u16 name_len=1, name=b'b'
#              01 01000000    u8 ndim=1, u32 dim0=1 (0-d input lands 1-d:
#                             ascontiguousarray promotes scalars)
#              02 05          u8 dtype_code=2 (u8), value 5
WEIGHTS_DTW2_GOLDEN_HEX = (
    "4454573207000000efbeadde020000000100770102000000000000803f000000c0"
    "01006201010000000205"
)
# Legacy DTW1 (rolling-upgrade emission, LearnerConfig.publish_legacy_dtw1):
# same layout minus the boot_epoch word.
WEIGHTS_DTW1_GOLDEN_HEX = (
    "4454573107000000020000000100770102000000000000803f000000c0"
    "01006201010000000205"
)


def test_weight_frame_golden_bytes():
    leaves = [("w", np.array([1.0, -2.0], np.float32)), ("b", np.array(5, np.uint8))]
    data = serialize_weights(leaves, version=7, boot_epoch=0xDEADBEEF)
    assert data.hex() == WEIGHTS_DTW2_GOLDEN_HEX
    named, version, boot_epoch = deserialize_weights(data)
    assert version == 7 and boot_epoch == 0xDEADBEEF
    np.testing.assert_array_equal(named[0][1], [1.0, -2.0])
    np.testing.assert_array_equal(named[1][1], [5])


def test_weight_frame_legacy_dtw1_golden_bytes():
    leaves = [("w", np.array([1.0, -2.0], np.float32)), ("b", np.array(5, np.uint8))]
    data = serialize_weights(leaves, version=7, boot_epoch=0xDEADBEEF, legacy_dtw1=True)
    assert data.hex() == WEIGHTS_DTW1_GOLDEN_HEX
    named, version, boot_epoch = deserialize_weights(data)
    # DTW1 carries no epoch: readers must see 0, and the boot-epoch
    # resync feature is deliberately inert while the transition flag is on.
    assert version == 7 and boot_epoch == 0
    np.testing.assert_array_equal(named[0][1], [1.0, -2.0])


def _serialize_weights_reference(named_arrays, version, boot_epoch=0, legacy_dtw1=False) -> bytes:
    """The frame as it was built before it was written once: one
    `tobytes` per leaf, then a join. Kept as the plain reference."""
    if legacy_dtw1:
        parts = [struct.pack("<4sII", b"DTW1", version, len(named_arrays))]
    else:
        parts = [
            struct.pack("<4sIII", b"DTW2", version, boot_epoch & 0xFFFFFFFF, len(named_arrays))
        ]
    codes = {np.dtype(np.float32): 0, np.dtype(np.int32): 1, np.dtype(np.uint8): 2}
    for name, arr in named_arrays:
        arr = np.ascontiguousarray(arr)
        nb = name.encode()
        parts.append(struct.pack("<H", len(nb)))
        parts.append(nb)
        parts.append(struct.pack("<B", arr.ndim))
        parts.append(struct.pack(f"<{arr.ndim}I", *arr.shape) if arr.ndim else b"")
        parts.append(struct.pack("<B", codes[arr.dtype]))
        parts.append(arr.tobytes())
    return b"".join(parts)


def _f32(*shape):
    return np.arange(int(np.prod(shape)), dtype=np.float32).reshape(shape) - 3.5


_WEIGHT_FRAME_CASES = {
    "dtw2": ([("core/kernel", _f32(3, 4)), ("core/bias", _f32(4))], {}),
    "legacy_dtw1": ([("core/kernel", _f32(3, 4)), ("core/bias", _f32(4))], {"legacy_dtw1": True}),
    "zero_d_leaf": ([("scale", np.array(2.5, np.float32)), ("w", _f32(2, 2))], {}),
    "non_contiguous_leaf": ([("t", _f32(3, 4).T), ("strided", _f32(10)[::3])], {}),
    "int32_leaf": ([("steps", np.arange(-3, 4, dtype=np.int32).reshape(7, 1)), ("w", _f32(2))], {}),
    "uint8_leaf": ([("mask", np.arange(250, 256, dtype=np.uint8)), ("w", _f32(3))], {}),
    "empty_list": ([], {}),
    "boot_epoch_above_u32": ([("w", _f32(5))], {"boot_epoch": 2**32 + 0xDEADBEEF}),
    "zero_size_leaf": ([("none", np.zeros((0, 3), np.float32)), ("w", _f32(2, 3))], {}),
    "unicode_name": ([("h\u00e9ro/\u6838", _f32(2))], {}),
}


@pytest.mark.parametrize("case", sorted(_WEIGHT_FRAME_CASES))
def test_weight_frame_is_byte_for_byte_the_reference(case):
    leaves, kw = _WEIGHT_FRAME_CASES[case]
    frame = serialize_weights(leaves, version=41, **kw)
    want = _serialize_weights_reference(leaves, version=41, **kw)
    assert bytes(frame) == want
    assert frame == want  # and compares as the buffer it is, no copy asked of the caller
    named, version, boot_epoch = deserialize_weights(frame)
    assert version == 41
    legacy = kw.get("legacy_dtw1", False)
    assert boot_epoch == (0 if legacy else kw.get("boot_epoch", 0) & 0xFFFFFFFF)
    assert [n for n, _ in named] == [n for n, _ in leaves]
    for (_, got), (_, leaf) in zip(named, leaves):
        sent = np.ascontiguousarray(leaf)  # a 0-d leaf travels as shape (1,)
        assert got.dtype == sent.dtype and got.shape == sent.shape
        np.testing.assert_array_equal(got, sent)
        assert not got.flags.writeable  # subscribers share one frame


def test_weight_frame_is_allocated_once():
    """The mechanism, not a speed: building the frame allocates the
    frame and nothing else of its size (per-leaf `tobytes` and a join
    allocated it twice)."""
    tree = [(f"layer{i}/kernel", np.full((2048, 2048), i, np.float32)) for i in range(4)]  # 64 MB
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        frame = serialize_weights(tree, version=1)
        peak = tracemalloc.get_traced_memory()[1] - before
    finally:
        tracemalloc.stop()
    assert len(frame) > 64 * 2**20
    # the lower bound shows the frame's own buffer is among what was traced
    assert len(frame) <= peak < 1.25 * len(frame), (peak, len(frame))


def test_weight_frame_copies_release_the_gil():
    """The mechanism, not a speed: while another thread builds a frame
    with a 256 MB leaf, this thread keeps running. Its longest wait for
    the interpreter is under a third of the time the frame took; best
    of three, so that a loaded machine does not fail it."""
    tree = [("core/kernel", np.zeros((8192, 8192), np.float32)), ("core/bias", _f32(64))]
    shares = []
    for _ in range(3):
        go, done, took = threading.Event(), threading.Event(), []

        def build():
            go.wait(10)
            t0 = time.perf_counter()
            frame = serialize_weights(tree, version=1)
            took.append(time.perf_counter() - t0)
            assert len(frame) > 256 * 2**20
            done.set()

        worker = threading.Thread(target=build)
        worker.start()
        longest, last = 0.0, time.perf_counter()
        go.set()
        while not done.is_set():
            time.sleep(0.0005)
            now = time.perf_counter()
            longest, last = max(longest, now - last), now
        worker.join(timeout=30)
        assert not worker.is_alive() and took
        shares.append(longest / took[0])
        if shares[-1] < 1 / 3:
            break
    assert min(shares) < 1 / 3, shares


def test_weights_roundtrip_with_params_tree():
    import jax

    from dotaclient_tpu.config import PolicyConfig
    from dotaclient_tpu.models.policy import init_params

    cfg = PolicyConfig(unit_embed_dim=16, lstm_hidden=16, mlp_hidden=16)
    params = init_params(cfg, jax.random.PRNGKey(0))
    flat = flatten_params(params)
    data = serialize_weights(flat, version=42, boot_epoch=9001)
    named, version, boot_epoch = deserialize_weights(data)
    assert version == 42
    assert boot_epoch == 9001
    rebuilt = unflatten_params(named, params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(rebuilt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestMemoryBroker:
    def setup_method(self):
        mem.reset("t")

    def test_pub_consume(self):
        b = connect("mem://t")
        b.publish_experience(b"a")
        b.publish_experience(b"b")
        assert b.consume_experience(10, timeout=0.1) == [b"a", b"b"]
        assert b.consume_experience(10, timeout=0.05) == []

    def test_bounded_drop_oldest(self):
        b = mem.MemoryBroker("t", maxlen=2)
        for x in (b"1", b"2", b"3"):
            b.publish_experience(x)
        assert b.consume_experience(10, timeout=0.1) == [b"2", b"3"]

    def test_weights_latest_wins(self):
        pub, sub = connect("mem://t"), connect("mem://t")
        assert sub.poll_weights() is None
        pub.publish_weights(b"v1")
        pub.publish_weights(b"v2")
        assert sub.poll_weights() == b"v2"
        assert sub.poll_weights() is None  # nothing newer
        pub.publish_weights(b"v3")
        assert sub.poll_weights() == b"v3"

    def test_consume_blocks_until_publish(self):
        b = connect("mem://t")
        got = []

        def consumer():
            got.extend(b.consume_experience(1, timeout=5))

        t = threading.Thread(target=consumer)
        t.start()
        time.sleep(0.1)
        b.publish_experience(b"x")
        t.join(timeout=5)
        assert got == [b"x"]


class TestTcpBroker:
    @pytest.fixture(scope="class")
    def server(self):
        s = BrokerServer(port=0, maxlen=64).start()
        yield s
        s.stop()

    def test_roundtrip(self, server):
        a = TcpBroker(port=server.port)
        b = TcpBroker(port=server.port)
        a.publish_experience(b"hello")
        a.publish_experience(b"world" * 1000)
        frames = b.consume_experience(10, timeout=1)
        assert frames == [b"hello", b"world" * 1000]
        assert b.consume_experience(10, timeout=0.05) == []
        a.close(), b.close()

    def test_weights(self, server):
        pub = TcpBroker(port=server.port)
        sub = TcpBroker(port=server.port)
        assert sub.poll_weights() is None
        pub.publish_weights(b"W1")
        pub.publish_weights(b"W2")
        assert sub.poll_weights() == b"W2"
        assert sub.poll_weights() is None
        pub.close(), sub.close()

    def test_weight_frame_as_serialize_weights_builds_it(self, server):
        # the frame is a read-only buffer, not `bytes`: the client takes
        # it as it is and a subscriber reads the same bytes back
        pub = TcpBroker(port=server.port)
        sub = TcpBroker(port=server.port)
        frame = serialize_weights([("w", np.arange(6, dtype=np.float32))], version=9, boot_epoch=3)
        pub.publish_weights(frame)
        got = sub.poll_weights()
        assert got == bytes(frame)
        named, version, boot_epoch = deserialize_weights(got)
        assert (version, boot_epoch) == (9, 3)
        np.testing.assert_array_equal(named[0][1], np.arange(6, dtype=np.float32))
        pub.close(), sub.close()

    def test_consume_blocks_for_first_frame(self, server):
        pub = TcpBroker(port=server.port)
        sub = TcpBroker(port=server.port)
        sub.consume_experience(100, timeout=0.05)  # drain
        got = []

        def consumer():
            got.extend(sub.consume_experience(1, timeout=5))

        t = threading.Thread(target=consumer)
        t.start()
        time.sleep(0.2)
        pub.publish_experience(b"late")
        t.join(timeout=5)
        assert got == [b"late"]
        pub.close(), sub.close()

    def test_depth(self, server):
        c = TcpBroker(port=server.port)
        c.consume_experience(1000, timeout=0.05)
        c.publish_experience(b"d1")
        c.publish_experience(b"d2")
        time.sleep(0.05)
        assert c.experience_depth() == 2
        c.consume_experience(10, timeout=0.5)
        c.close()

    def test_bounded_drop_oldest(self, server):
        c = TcpBroker(port=server.port)
        c.consume_experience(1000, timeout=0.05)
        for i in range(server.maxlen + 10):
            c.publish_experience(f"{i}".encode())
        time.sleep(0.1)
        frames = []
        while True:
            got = c.consume_experience(1000, timeout=0.2)
            if not got:
                break
            frames.extend(got)
        assert len(frames) == server.maxlen
        assert frames[0] == b"10"  # oldest 10 dropped
        c.close()

    def test_concurrent_producers(self, server):
        brokers = [TcpBroker(port=server.port) for _ in range(4)]
        sub = TcpBroker(port=server.port)
        sub.consume_experience(1000, timeout=0.05)

        def produce(br, i):
            # 4×15 = 60 < server.maxlen, so nothing is dropped
            for j in range(15):
                br.publish_experience(f"{i}:{j}".encode())

        threads = [threading.Thread(target=produce, args=(br, i)) for i, br in enumerate(brokers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        got = []
        deadline = time.time() + 5
        while len(got) < 60 and time.time() < deadline:
            got.extend(sub.consume_experience(100, timeout=0.5))
        assert len(got) == 60
        assert len(set(got)) == 60
        for br in brokers:
            br.close()
        sub.close()


def test_connect_unknown_scheme():
    with pytest.raises(ValueError):
        connect("bogus://x")


# --- admission control: the SHED reply + rolling upgrade ----------------


class TestBrokerShed:
    @pytest.fixture()
    def shedding(self):
        s = BrokerServer(port=0, maxlen=16, shed_high=4, shed_low=2).start()
        yield s
        s.stop()

    def test_new_client_sheds_with_explicit_reply_and_hysteresis(self, shedding):
        from dotaclient_tpu.transport.base import BrokerShedError

        c = TcpBroker(port=shedding.port)
        for i in range(4):
            c.publish_experience(bytes([i]))
        with pytest.raises(BrokerShedError):
            c.publish_experience(b"over")
        assert c.shed_observed == 1
        # connection stayed healthy: no reconnect happened, and the
        # next request on the same socket works
        c.consume_experience(1, timeout=0.5)  # depth 3: hysteresis holds
        with pytest.raises(BrokerShedError):
            c.publish_experience(b"still-shedding")
        c.consume_experience(10, timeout=0.5)  # drain to <= low
        c.publish_experience(b"resumed")
        assert shedding.shed_total == 2 and shedding.dropped == 0
        c.close()

    def test_legacy_client_sees_shed_as_retryable_and_recovers(self, shedding):
        """Rolling upgrade (MIGRATION.md): a pre-SHED client publishes
        with opcode PUB_EXP and cannot parse 0x86 — the broker sheds it
        by CLOSING the connection, which the old client's existing
        reconnect loop already treats as a retryable error: it backs
        off, resends, and succeeds once the queue drains. The old
        client's own code path (_Conn.request with PUB_EXP) is the
        emulation."""
        from dotaclient_tpu.transport.tcp import PUB_EXP, R_ACK, _Conn

        new_client = TcpBroker(port=shedding.port)
        for i in range(4):
            new_client.publish_experience(bytes([i]))
        legacy = _Conn(("127.0.0.1", shedding.port), connect_timeout=5.0, retry_window=20.0)

        # drain the queue after a delay, while the legacy publish is
        # parked in its reconnect/backoff loop
        def drain_later():
            time.sleep(0.8)
            new_client.consume_experience(100, timeout=0.5)

        t = threading.Thread(target=drain_later, daemon=True)
        t.start()
        t0 = time.monotonic()
        legacy.request(PUB_EXP, b"legacy-frame", R_ACK)  # retries through the sheds
        assert time.monotonic() - t0 > 0.5  # it genuinely waited out the shed
        t.join(timeout=5)
        assert shedding.shed_closes >= 1
        frames = new_client.consume_experience(10, timeout=1.0)
        assert b"legacy-frame" in frames
        legacy.close()
        new_client.close()

    def test_stats_roundtrip_and_ledger(self, shedding):
        c = TcpBroker(port=shedding.port)
        c.publish_experience(b"a")
        c.publish_experience(b"b")
        c.consume_experience(1, timeout=0.5)
        st = c.stats()
        assert st["enqueued"] == 2 and st["popped"] == 1 and st["depth"] == 1
        assert st["shed"] == 0 and st["reply_lost"] == 0
        assert st["enqueued"] == st["popped"] + st["dropped_oldest"] + st["depth"]
        c.close()


def test_shed_off_by_default_wire_unchanged():
    """Without watermarks the admission path is inert: no shed state,
    publishes ack exactly as before (the golden-bytes tests above pin
    the frame layouts themselves)."""
    s = BrokerServer(port=0, maxlen=4).start()
    c = TcpBroker(port=s.port)
    for i in range(8):  # past maxlen: drop-oldest, never shed
        c.publish_experience(bytes([i]))
    time.sleep(0.1)
    assert s.shed_total == 0 and s.dropped == 4
    assert c.shed_observed == 0
    c.close()
    s.stop()


def test_retry_policy_jitter_bounds():
    import random

    from dotaclient_tpu.transport.base import RetryPolicy

    p = RetryPolicy(backoff_base_s=0.1, backoff_cap_s=2.0, jitter=0.5, rng=random.Random(1))
    draws = {p.sleep_for(1.0) for _ in range(200)}
    assert all(0.5 <= d <= 1.5 for d in draws)
    assert len(draws) > 100  # actually jittered, not constant
    assert p.next_backoff(1.5) == 2.0  # capped
    # jitter 0 = deterministic (the pre-chaos ladder)
    assert RetryPolicy(jitter=0.0).sleep_for(0.4) == 0.4
