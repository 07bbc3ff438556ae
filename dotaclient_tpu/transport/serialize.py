"""Wire format for experience rollouts and weight broadcasts.

The reference pickles rollout dicts and state_dicts onto RabbitMQ
(SURVEY.md §2 "Experience/weight transport"). We deliberately do NOT use
pickle: the format below is a fixed-layout binary framing of numpy arrays —
faster to pack/unpack at 50k steps/s, safe to parse from untrusted peers,
and language-neutral so the native (C++) batch packer can read it without
a Python runtime.

Rollout frame layout (little-endian):
  magic  b'DTR1'
  u32    model_version
  u16    L          — number of action steps (obs arrays carry L+1 rows)
  u16    lstm_hidden
  u8     flags      — bit0: aux targets present; other bits reserved (0)
  u32    actor_id
  f32    episode_return (metrics only)
  then the arrays, in fixed order, raw bytes (shapes derivable from L/H).

Traced rollout frame (DTR2, emitted ONLY for trace-stamped rollouts —
the obs/ pipeline-tracing extension):
  magic  b'DTR2'
  then the DTR1 header fields unchanged (u32 version … f32 episode_return)
  u64    trace_id   — pipeline trace id stamped by the publishing actor
  f64    birth_time — time.time() at publish (e2e latency origin)
  then the arrays, identical to DTR1.

Quantized rollout frame (DTR3, emitted whenever the float obs leaves
travel in a non-f32 wire dtype — the --wire.obs_dtype bf16 experience
quantization, HEPPO-GAE-style):
  magic  b'DTR3'
  then the FULL DTR2 header (DTR1 fields + u64 trace_id + f64
  birth_time; both zero when untraced — one format either way)
  u8     n_dtypes   — number of arrays in the frame (16, or 19 with aux;
         must match the flags byte)
  u8[n]  dtype-map  — per-array wire dtype code, serialization order
         (codes: 0=f32, 1=i32, 2=u8, 3=bf16)
  then the arrays in their WIRE dtypes. This build constrains the map:
  every non-obs-float entry must be canonical, and the three float obs
  entries must be uniformly f32 or uniformly bf16 — both the python
  parser and the native C packer enforce the same accept set, and a
  frame violating it is a WireDtypeError (staging quarantines it with
  the distinct "dtype_map" reason). The bf16 cast happens AT THE SOURCE
  (cast_rollout_obs_bf16, the exact round-to-nearest-even of staging's
  cast_obs_to_compute_dtype), so a bf16-wire TrainBatch is bitwise
  identical to the f32-wire + cast-at-staging batch.

Rolling-upgrade contract, the publish_legacy_dtw1 precedent: compat is
one-directional — NEW readers (deserialize_rollout, the staging intake's
strip_rollout_trace normalization, the native packer's parse_header)
accept DTR1+DTR2+DTR3, old readers reject DTR2/DTR3 loudly (unknown
magic). Tracing (--obs.enabled) and wire quantization
(--wire.obs_dtype bf16) are therefore opt-in per actor and default-off:
with both off the frames are byte-identical DTR1, so a fleet rolls
consumers first, then turns either on — exactly the DTW1→DTW2 ordering.
Golden bytes for all three layouts are frozen in tests/test_transport.py.

Weight frame layout (current, DTW2 — the authoritative spec any native
or non-Python reader is written from; golden bytes frozen in
tests/test_transport.py):
  magic  b'DTW2'
  u32    version
  u32    boot_epoch — identifies the publishing learner PROCESS (drawn
         once at learner boot); subscribers resync on epoch change
  u32    n_leaves
  per leaf: u16 name_len, name bytes, u8 ndim, u32 dims…, u8 dtype_code,
            raw data.

Legacy weight frame (DTW1, read-compat only; emitted only under the
LearnerConfig.publish_legacy_dtw1 rolling-upgrade flag):
  magic  b'DTW1'
  u32    version
  u32    n_leaves
  per leaf: same as DTW2. Readers treat boot_epoch as 0.
"""

from __future__ import annotations

import struct
from typing import Any, List, NamedTuple, Optional, Tuple

import numpy as np

from dotaclient_tpu.env import featurizer as F
from dotaclient_tpu.ops.action_dist import Action

_ROLLOUT_MAGIC = b"DTR1"
_ROLLOUT_MAGIC2 = b"DTR2"  # trace-extended (obs/): header + trace_id/birth
_ROLLOUT_MAGIC3 = b"DTR3"  # quantized wire: DTR2 header + per-array dtype-map
_WEIGHTS_MAGIC = b"DTW1"  # legacy: no boot_epoch (read-compat only)
_WEIGHTS_MAGIC2 = b"DTW2"
_HDR = struct.Struct("<4sIHHBIf")
# DTR2 = the DTR1 header + u64 trace_id + f64 birth_time, arrays unchanged.
_HDR2 = struct.Struct("<4sIHHBIfQd")

_FLAG_AUX = 1

# Wire dtype codes for the DTR3 dtype-map (the rollout-side analog of the
# weight-frame _DTYPES table below; 3=bf16 is rollout-only).
_WIRE_F32, _WIRE_I32, _WIRE_U8, _WIRE_BF16 = 0, 1, 2, 3


class WireDtypeError(ValueError):
    """A DTR3 frame whose dtype-map is truncated, malformed, or names a
    wire layout this build does not speak. Distinct from the plain
    ValueError of a generally-corrupt frame so the staging quarantine
    can file it under its own reason ("dtype_map") — a fleetwide stream
    of these means a producer is ahead of this consumer, not that the
    wire is flipping bits."""


def _bf16_dtype():
    import ml_dtypes  # deferred: only DTR3/bf16 paths need it

    return np.dtype(ml_dtypes.bfloat16)


def _canonical_codes(flags: int, obs_code: int) -> bytes:
    """The dtype-map this build accepts, in serialization order: 3 float
    obs leaves (f32 or bf16, uniform), 3 u8 masks, 4 i32 action heads,
    6 f32 scalars/state, +3 f32 aux when flagged."""
    codes = [obs_code] * 3 + [_WIRE_U8] * 3 + [_WIRE_I32] * 4 + [_WIRE_F32] * 6
    if flags & _FLAG_AUX:
        codes += [_WIRE_F32] * 3
    return bytes(codes)


def check_dtr3_dtype_map(data: bytes) -> Optional[str]:
    """None when `data` (magic already known to be DTR3) carries a
    well-formed dtype-map this build speaks, else the quarantine reason.
    Constant-time header peek — no array parsing, shared by the python
    parser and the staging intake's native-path pre-check so both paths
    accept the exact same frames."""
    if len(data) < _HDR2.size + 1:
        return "dtype_map"
    flags = data[12]
    n = data[_HDR2.size]
    if len(data) < _HDR2.size + 1 + n:
        return "dtype_map"
    m = data[_HDR2.size + 1 : _HDR2.size + 1 + n]
    if m != _canonical_codes(flags, _WIRE_F32) and m != _canonical_codes(
        flags, _WIRE_BF16
    ):
        return "dtype_map"
    return None


def peek_rollout_actor_id(data: bytes) -> Optional[int]:
    """Constant-time header peek of the actor_id a rollout frame was
    stamped with (None for short/foreign frames) — the broker fabric's
    routing key (transport/fabric.py): every chunk of one trajectory
    carries one actor_id, so hashing it pins the whole trajectory to one
    shard. The field sits at the same offset in all three layouts
    (DTR1/2/3 share the _HDR prefix)."""
    if len(data) < _HDR.size or data[:4] not in (
        _ROLLOUT_MAGIC,
        _ROLLOUT_MAGIC2,
        _ROLLOUT_MAGIC3,
    ):
        return None
    # _HDR = <4sIHHBIf: magic(4) version(4) L(2) H(2) flags(1) actor_id(4)
    (actor_id,) = struct.unpack_from("<I", data, 13)
    return actor_id


def wire_obs_is_bf16(data: bytes) -> bool:
    """True iff `data` is a DTR3 frame shipping its float obs leaves as
    bf16 (map code 3 at entry 0). Cheap per-frame meter for the staging
    wire_* scalars; garbage-safe (short/foreign frames are False)."""
    return (
        len(data) > _HDR2.size + 1
        and data[:4] == _ROLLOUT_MAGIC3
        and data[_HDR2.size + 1] == _WIRE_BF16
    )


class RolloutAux(NamedTuple):
    win: np.ndarray  # [L] f32 ±1 final result, 0 unknown
    last_hit: np.ndarray  # [L] f32
    net_worth: np.ndarray  # [L] f32


class Rollout(NamedTuple):
    """One variable-length trajectory chunk as shipped by an actor.

    `obs` leaves have L+1 rows — the extra row is the bootstrap
    observation after the last action (TrainBatch convention).
    """

    obs: F.Observation  # leaves [L+1, ...]
    actions: Action  # leaves [L] i32
    behavior_logp: np.ndarray  # [L] f32
    behavior_value: np.ndarray  # [L] f32
    rewards: np.ndarray  # [L] f32
    dones: np.ndarray  # [L] f32
    initial_state: Tuple[np.ndarray, np.ndarray]  # (c, h) each [H] f32
    version: int
    actor_id: int = 0
    episode_return: float = 0.0
    aux: Optional[RolloutAux] = None
    # Pipeline-tracing extension (dotaclient_tpu/obs/): both zero means
    # untraced — serialize_rollout then emits byte-identical legacy DTR1.
    trace_id: int = 0
    birth_time: float = 0.0

    @property
    def length(self) -> int:
        return int(self.rewards.shape[0])

    @property
    def traced(self) -> bool:
        return bool(self.trace_id or self.birth_time)


def rollout_obs_bf16(r: Rollout) -> bool:
    """True when the rollout's float obs leaves are already bf16 — the
    cast-at-source wire form. Serialization keys the frame format off
    the ACTUAL leaf dtype, so a producer opts in simply by casting."""
    return np.dtype(getattr(r.obs.global_feats, "dtype", np.float32)).name == "bfloat16"


def cast_rollout_obs_bf16(r: Rollout) -> Rollout:
    """Cast the float obs leaves f32→bf16 at the SOURCE (the actor),
    with numpy's astype round-to-nearest-even — bit-for-bit the rounding
    staging's cast_obs_to_compute_dtype (and the native packer's fused
    convert) applies to f32 wire frames, so the TrainBatch built from a
    frame cast here is provably identical to one cast downstream. Masks
    and every non-obs leaf keep their types; already-bf16 leaves pass
    through (idempotent)."""
    dt = _bf16_dtype()
    # Same untrusted-float story as the staging cast: NaN/inf propagate,
    # out-of-range saturates — never a per-publish RuntimeWarning.
    with np.errstate(invalid="ignore", over="ignore"):
        obs = r.obs._replace(
            **{
                f: v.astype(dt)
                for f, v in r.obs._asdict().items()
                if getattr(v, "dtype", None) == np.float32
            }
        )
    return r._replace(obs=obs)


def wire_cast_fn(obs_dtype: str):
    """The publish-side cast for a --wire.obs_dtype value: identity for
    "f32" (byte-identical legacy frames), cast_rollout_obs_bf16 for
    "bf16". The ONE place config values map to wire behavior — actors,
    self-play, and benches all resolve through here."""
    if obs_dtype in ("f32", "float32"):
        return lambda r: r
    if obs_dtype in ("bf16", "bfloat16"):
        return cast_rollout_obs_bf16
    raise ValueError(
        f"wire.obs_dtype must be 'f32' or 'bf16', got {obs_dtype!r}"
    )


def _obs_arrays(obs: F.Observation, obs_bf16: bool = False) -> List[np.ndarray]:
    fdt = _bf16_dtype() if obs_bf16 else np.float32
    return [
        np.ascontiguousarray(obs.global_feats, fdt),
        np.ascontiguousarray(obs.hero_feats, fdt),
        np.ascontiguousarray(obs.unit_feats, fdt),
        np.ascontiguousarray(obs.unit_mask, np.uint8),
        np.ascontiguousarray(obs.target_mask, np.uint8),
        np.ascontiguousarray(obs.action_mask, np.uint8),
    ]


def serialize_rollout(r: Rollout) -> bytes:
    L = r.length
    H = r.initial_state[0].shape[-1]
    flags = _FLAG_AUX if r.aux is not None else 0
    obs_bf16 = rollout_obs_bf16(r)
    if obs_bf16:
        # Quantized wire: DTR3 carries the trace fields unconditionally
        # (zeros when untraced) plus the dtype-map — ONE format whether
        # or not the chunk is trace-stamped.
        hdr = _HDR2.pack(
            _ROLLOUT_MAGIC3, r.version, L, H, flags, r.actor_id,
            r.episode_return, r.trace_id, r.birth_time,
        )
        codes = _canonical_codes(flags, _WIRE_BF16)
        parts = [hdr, struct.pack("<B", len(codes)), codes]
    elif r.traced:
        parts = [
            _HDR2.pack(
                _ROLLOUT_MAGIC2, r.version, L, H, flags, r.actor_id,
                r.episode_return, r.trace_id, r.birth_time,
            )
        ]
    else:
        # Untraced rollouts stay byte-identical legacy DTR1 — old
        # consumers keep parsing every frame a default-config actor emits.
        parts = [_HDR.pack(_ROLLOUT_MAGIC, r.version, L, H, flags, r.actor_id, r.episode_return)]
    arrays = _obs_arrays(r.obs, obs_bf16)
    arrays += [np.ascontiguousarray(a, np.int32) for a in r.actions]
    arrays += [
        np.ascontiguousarray(r.behavior_logp, np.float32),
        np.ascontiguousarray(r.behavior_value, np.float32),
        np.ascontiguousarray(r.rewards, np.float32),
        np.ascontiguousarray(r.dones, np.float32),
        np.ascontiguousarray(r.initial_state[0], np.float32),
        np.ascontiguousarray(r.initial_state[1], np.float32),
    ]
    if r.aux is not None:
        arrays += [np.ascontiguousarray(a, np.float32) for a in r.aux]
    parts.extend(a.tobytes() for a in arrays)
    return b"".join(parts)


def _expected_layout(L: int, H: int, flags: int, obs_bf16: bool = False):
    """(shape, dtype) per array, in serialization order."""
    T1 = L + 1
    fdt = _bf16_dtype() if obs_bf16 else np.float32
    layout = [
        ((T1, F.GLOBAL_FEATURES), fdt),
        ((T1, F.HERO_FEATURES), fdt),
        ((T1, F.MAX_UNITS, F.UNIT_FEATURES), fdt),
        ((T1, F.MAX_UNITS), np.uint8),
        ((T1, F.MAX_UNITS), np.uint8),
        ((T1, F.N_ACTION_TYPES), np.uint8),
    ]
    layout += [((L,), np.int32)] * 4
    layout += [((L,), np.float32)] * 4
    layout += [((H,), np.float32)] * 2
    if flags & _FLAG_AUX:
        layout += [((L,), np.float32)] * 3
    return layout


def peek_rollout_trace(data: bytes) -> Tuple[int, float]:
    """(trace_id, birth_time) of a DTR2/DTR3 frame, (0, 0.0) for DTR1 or
    any frame too short to carry the extension. Constant-time header
    peek — no array parsing. (DTR3 stores the trace fields at the same
    offsets as DTR2, zeros when untraced.)"""
    if len(data) >= _HDR2.size and data[:4] in (_ROLLOUT_MAGIC2, _ROLLOUT_MAGIC3):
        trace_id, birth = struct.unpack_from("<Qd", data, _HDR.size)
        return trace_id, birth
    return 0, 0.0


def strip_rollout_trace(data: bytes) -> bytes:
    """DTR2 frame → the byte-identical DTR1 frame (trace extension
    removed). DTR1 frames pass through untouched (same object, no copy)
    — and so do DTR3 frames: their arrays are RE-ENCODED (bf16), not
    merely suffixed, and the native packer parses DTR3 whole.

    This is the staging intake's rolling-upgrade normalization: the
    native C packer (native/packer.cc) speaks the DTR1 and DTR3
    layouts, so DTR2 traced frames are normalized once at ingest — paid
    only for frames a producer chose to stamp, never on the legacy
    path."""
    if len(data) >= _HDR2.size and data[:4] == _ROLLOUT_MAGIC2:
        return _ROLLOUT_MAGIC + data[4:_HDR.size] + data[_HDR2.size:]
    return data


def stamp_rollout_trace(data: bytes, trace_id: int, birth_time: float) -> bytes:
    """DTR1 frame → the DTR2 frame carrying the given trace extension.
    Inverse of strip_rollout_trace, for producers that re-publish
    already-serialized frames (bench.py's synthetic actors, tests) —
    real actors stamp the Rollout before serializing instead."""
    if len(data) < _HDR.size or data[:4] != _ROLLOUT_MAGIC:
        raise ValueError("can only stamp a DTR1 rollout frame")
    return (
        _ROLLOUT_MAGIC2
        + data[4:_HDR.size]
        + struct.pack("<Qd", trace_id, birth_time)
        + data[_HDR.size:]
    )


def deserialize_rollout(data: bytes) -> Rollout:
    trace_id, birth_time = 0, 0.0
    obs_bf16 = False
    if data[:4] == _ROLLOUT_MAGIC3:
        # check_dtr3_dtype_map also rejects frames truncated inside the
        # header, so both python and native intakes file ANY short/bad
        # DTR3 under the same distinct quarantine reason.
        if check_dtr3_dtype_map(data) is not None:
            raise WireDtypeError("bad DTR3 dtype-map")
        magic, version, L, H, flags, actor_id, ep_ret, trace_id, birth_time = (
            _HDR2.unpack_from(data)
        )
        n_map = data[_HDR2.size]
        obs_bf16 = data[_HDR2.size + 1] == _WIRE_BF16
        off = _HDR2.size + 1 + n_map
    elif len(data) >= _HDR2.size and data[:4] == _ROLLOUT_MAGIC2:
        magic, version, L, H, flags, actor_id, ep_ret, trace_id, birth_time = (
            _HDR2.unpack_from(data)
        )
        off = _HDR2.size
    elif len(data) >= _HDR.size and data[:4] == _ROLLOUT_MAGIC:
        magic, version, L, H, flags, actor_id, ep_ret = _HDR.unpack_from(data)
        off = _HDR.size
    else:
        raise ValueError("bad rollout frame")
    arrays = []
    for shape, dtype in _expected_layout(L, H, flags, obs_bf16):
        n = int(np.prod(shape)) * np.dtype(dtype).itemsize
        if off + n > len(data):
            raise ValueError("truncated rollout frame")
        arrays.append(np.frombuffer(data, dtype, count=int(np.prod(shape)), offset=off).reshape(shape))
        off += n
    if off != len(data):
        raise ValueError("trailing bytes in rollout frame")
    obs = F.Observation(
        global_feats=arrays[0],
        hero_feats=arrays[1],
        unit_feats=arrays[2],
        unit_mask=arrays[3].astype(bool),
        target_mask=arrays[4].astype(bool),
        action_mask=arrays[5].astype(bool),
    )
    aux = RolloutAux(*arrays[16:19]) if flags & _FLAG_AUX else None
    return Rollout(
        obs=obs,
        actions=Action(*arrays[6:10]),
        behavior_logp=arrays[10],
        behavior_value=arrays[11],
        rewards=arrays[12],
        dones=arrays[13],
        initial_state=(arrays[14], arrays[15]),
        version=version,
        actor_id=actor_id,
        episode_return=ep_ret,
        aux=aux,
        trace_id=trace_id,
        birth_time=birth_time,
    )


# --- single-observation frames (inference-service wire) ---------------
#
# The serve tier (dotaclient_tpu/serve/) ships ONE featurized
# observation per request — no time axis, no actions/rewards — on the
# same dtype-code convention as the DTR3 rollout wire: float leaves
# travel f32 (exact) or bf16 (the PR-8 cast, halving request bandwidth;
# the server upcasts bf16→f32 exactly, so one jit signature serves a
# mixed fleet). Array order matches the rollout wire's obs block.


def obs_wire_layout(obs_bf16: bool = False):
    """(shape, dtype) per array of a single-observation frame, in
    serialization order (the rollout obs block minus the time axis)."""
    fdt = _bf16_dtype() if obs_bf16 else np.float32
    return [
        ((F.GLOBAL_FEATURES,), fdt),
        ((F.HERO_FEATURES,), fdt),
        ((F.MAX_UNITS, F.UNIT_FEATURES), fdt),
        ((F.MAX_UNITS,), np.uint8),
        ((F.MAX_UNITS,), np.uint8),
        ((F.N_ACTION_TYPES,), np.uint8),
    ]


def obs_wire_nbytes(obs_bf16: bool = False) -> int:
    return sum(
        int(np.prod(shape)) * np.dtype(dt).itemsize
        for shape, dt in obs_wire_layout(obs_bf16)
    )


def serialize_obs(obs: F.Observation, obs_bf16: bool = False) -> bytes:
    """One unbatched Observation → raw wire bytes. The bf16 cast is the
    exact RNE astype of cast_rollout_obs_bf16, so a bf16-wire request
    stepped by a bf16-compute policy is bitwise identical to the local
    f32 step (the serve parity contract, tests/test_serve.py)."""
    if obs_bf16:
        with np.errstate(invalid="ignore", over="ignore"):
            return b"".join(a.tobytes() for a in _obs_arrays(obs, True))
    return b"".join(a.tobytes() for a in _obs_arrays(obs, False))


def deserialize_obs(
    data: bytes, offset: int = 0, obs_bf16: bool = False
) -> Tuple[F.Observation, int]:
    """(Observation, next offset) from raw wire bytes. Float leaves come
    back in their WIRE dtype — the serve server upcasts bf16→f32 (exact)
    at intake to keep one jit signature."""
    arrays = []
    for shape, dtype in obs_wire_layout(obs_bf16):
        n = int(np.prod(shape)) * np.dtype(dtype).itemsize
        if offset + n > len(data):
            raise ValueError("truncated observation frame")
        arrays.append(
            np.frombuffer(data, dtype, count=int(np.prod(shape)), offset=offset).reshape(shape)
        )
        offset += n
    obs = F.Observation(
        global_feats=arrays[0],
        hero_feats=arrays[1],
        unit_feats=arrays[2],
        unit_mask=arrays[3].astype(bool),
        target_mask=arrays[4].astype(bool),
        action_mask=arrays[5].astype(bool),
    )
    return obs, offset


# --- weights -----------------------------------------------------------

# --------------------------------------------------------------------------
# DTB1: pre-assembled batch-shard blocks (ISSUE 20 in-network assembly).
#
# A fabric shard running --broker.assemble packs each admitted frame ONCE
# into the native packer's exact single-buffer row layout
# (parallel/fused_io.py RowLayout) and serves consumers whole blocks of
# rows plus a per-row sidecar, so the learner's host side is memcpy-only.
#
# Block layout (little-endian):
#   magic  b'DTB1'
#   u8     fmt        — format revision (1)
#   u16    n_rows
#   u16    seq_len    — T (row padded to T steps; obs carry T+1)
#   u16    lstm_hidden
#   u8     flags      — bit0: aux targets; bit1: obs leaves staged bf16
#   u32    row_bytes  — bytes per packed row (RowLayout.row_bytes)
#   u32    layout_crc — RowLayout.layout_crc; the consumer REFUSES a
#          block whose crc differs from its own layout (a schema or
#          segment-order drift would otherwise scramble silently)
#   n_rows × 52-byte sidecar (_BLK_SIDE below): model_version, actor_id,
#          episode_return, trace_id, birth_time, priority, the fabric
#          fence stamp (boot/epoch/seq — boot 0 marks a row from an
#          un-enveloped producer: always admitted, like an un-enveloped
#          PUB frame), and row_flags (bit0: the row's final step ended
#          an episode — the learner's episode accounting)
#   n_rows × row_bytes packed row payload.

BLOCK_MAGIC = b"DTB1"
_BLK = struct.Struct("<4sBHHHBII")
_BLK_SIDE = struct.Struct("<IIfQdfQIII")
_BLK_FLAG_AUX = 1
_BLK_FLAG_OBS_BF16 = 2
_BLK_ROW_DONE = 1  # row_flags bit0: last real step completed an episode
_BLK_FMT = 1


class BlockSpec(NamedTuple):
    """Everything two processes must agree on for a packed row to be
    byte-portable between them. The consumer sends its spec in the
    GET_BLOCK request; the shard embeds its own in every block header."""

    seq_len: int
    lstm_hidden: int
    with_aux: bool
    obs_bf16: bool
    row_bytes: int
    layout_crc: int


class AssembledRow(NamedTuple):
    """One pre-packed batch row + its sidecar (what a DTR frame becomes
    after shard-side assembly). `payload` is exactly RowLayout.row_bytes
    long; the fence stamp mirrors the FAB1 envelope the frame arrived
    under (boot=0 = un-enveloped, always admitted)."""

    payload: bytes
    version: int
    actor_id: int = 0
    episode_return: float = 0.0
    trace_id: int = 0
    birth_time: float = 0.0
    priority: float = 0.0
    boot: int = 0
    epoch: int = 0
    seq: int = 0
    last_done: bool = False


def block_spec_flags(spec: BlockSpec) -> int:
    """The u8 flags byte a BlockSpec serializes to (block header and
    GET_BLOCK request share the encoding)."""
    return (_BLK_FLAG_AUX if spec.with_aux else 0) | (
        _BLK_FLAG_OBS_BF16 if spec.obs_bf16 else 0
    )


def serialize_block(spec: BlockSpec, rows: List[AssembledRow]) -> bytes:
    flags = block_spec_flags(spec)
    parts = [
        _BLK.pack(
            BLOCK_MAGIC,
            _BLK_FMT,
            len(rows),
            spec.seq_len,
            spec.lstm_hidden,
            flags,
            spec.row_bytes,
            spec.layout_crc,
        )
    ]
    for r in rows:
        parts.append(
            _BLK_SIDE.pack(
                r.version & 0xFFFFFFFF,
                r.actor_id & 0xFFFFFFFF,
                float(r.episode_return),
                r.trace_id & 0xFFFFFFFFFFFFFFFF,
                float(r.birth_time),
                float(r.priority),
                r.boot & 0xFFFFFFFFFFFFFFFF,
                r.epoch & 0xFFFFFFFF,
                r.seq & 0xFFFFFFFF,
                _BLK_ROW_DONE if r.last_done else 0,
            )
        )
    for r in rows:
        if len(r.payload) != spec.row_bytes:
            raise ValueError(
                f"block row payload {len(r.payload)}B != row_bytes {spec.row_bytes}"
            )
        parts.append(bytes(r.payload))
    return b"".join(parts)


def peek_block_spec(data: bytes) -> Optional[BlockSpec]:
    """BlockSpec from a DTB1 header, or None if `data` is not a block."""
    if len(data) < _BLK.size or data[:4] != BLOCK_MAGIC:
        return None
    magic, fmt, n, T, H, flags, row_bytes, crc = _BLK.unpack_from(data)
    if fmt != _BLK_FMT:
        return None
    return BlockSpec(
        seq_len=T,
        lstm_hidden=H,
        with_aux=bool(flags & _BLK_FLAG_AUX),
        obs_bf16=bool(flags & _BLK_FLAG_OBS_BF16),
        row_bytes=row_bytes,
        layout_crc=crc,
    )


def deserialize_block(data: bytes) -> Tuple[BlockSpec, List[AssembledRow]]:
    spec = peek_block_spec(data)
    if spec is None:
        raise ValueError("not a DTB1 block")
    n = _BLK.unpack_from(data)[2]
    need = _BLK.size + n * _BLK_SIDE.size + n * spec.row_bytes
    if len(data) != need:
        raise ValueError(f"block length {len(data)} != expected {need} ({n} rows)")
    rows: List[AssembledRow] = []
    pay0 = _BLK.size + n * _BLK_SIDE.size
    for i in range(n):
        version, actor_id, ep_ret, trace_id, birth, prio, boot, epoch, seq, rflags = (
            _BLK_SIDE.unpack_from(data, _BLK.size + i * _BLK_SIDE.size)
        )
        off = pay0 + i * spec.row_bytes
        rows.append(
            AssembledRow(
                payload=data[off : off + spec.row_bytes],
                version=version,
                actor_id=actor_id,
                episode_return=ep_ret,
                trace_id=trace_id,
                birth_time=birth,
                priority=prio,
                boot=boot,
                epoch=epoch,
                seq=seq,
                last_done=bool(rflags & _BLK_ROW_DONE),
            )
        )
    return spec, rows


_DTYPES = {0: np.float32, 1: np.int32, 2: np.uint8}


def _dtype_code(dt) -> int:
    dt = np.dtype(dt)
    if dt == np.float32:
        return 0
    if dt == np.int32:
        return 1
    if dt == np.uint8:
        return 2
    raise ValueError(f"unsupported weight dtype {dt}")


def serialize_weights(
    named_arrays: List[Tuple[str, np.ndarray]],
    version: int,
    boot_epoch: int = 0,
    legacy_dtw1: bool = False,
) -> memoryview:
    """Weight fanout frame. `boot_epoch` identifies the publishing
    learner PROCESS (drawn once at learner boot): subscribers resync on
    an epoch change — the deterministic learner-restart signal that
    replaced the consecutive-older-frames heuristic (VERDICT r3 item 9).
    Header is DTW2 <magic, version, boot_epoch, n>; readers also accept
    legacy DTW1 (no epoch → 0). Compat is one-directional: NEW readers
    accept OLD frames, but old readers reject DTW2 — so a rolling
    upgrade either updates subscribers (actors/evaluators) before the
    learner starts emitting DTW2, or runs the learner with
    LearnerConfig.publish_legacy_dtw1 (→ `legacy_dtw1=True` here) until
    the fleet has rolled (ADVICE r4). Either way the actors' default-on
    stale-weights kill switch turns a botched ordering into loud pod
    restarts instead of a silent cluster-wide policy freeze."""
    if legacy_dtw1:
        top = struct.pack("<4sII", _WEIGHTS_MAGIC, version, len(named_arrays))
    else:
        top = struct.pack(
            "<4sIII", _WEIGHTS_MAGIC2, version, boot_epoch & 0xFFFFFFFF, len(named_arrays)
        )
    leaves = []  # (small header, contiguous array) per leaf
    total = len(top)
    for name, arr in named_arrays:
        arr = np.ascontiguousarray(arr)
        nb = name.encode()
        head = (
            struct.pack("<H", len(nb))
            + nb
            + struct.pack(f"<B{arr.ndim}IB", arr.ndim, *arr.shape, _dtype_code(arr.dtype))
        )
        leaves.append((head, arr))
        total += len(head) + arr.nbytes
    # One buffer of the frame's length, written once, by copies that
    # drop the GIL (`np.copyto` between plain dtypes does; `tobytes`,
    # `bytes(...)` and a memoryview slice assignment hold it), so the
    # train loop keeps dispatching under a 546 MB publish. `np.empty`
    # touches no page: `bytearray(n)` is a memset with the GIL held,
    # 0.6 s at that size on a v5e's host (PERF.md, PR 28). A fresh
    # buffer per frame: the broker's slot and any subscriber may still
    # hold the last one.
    buf = np.empty(total, np.uint8)
    buf[: len(top)] = np.frombuffer(top, np.uint8)
    off = len(top)
    for head, arr in leaves:
        buf[off : off + len(head)] = np.frombuffer(head, np.uint8)
        off += len(head)
        np.copyto(buf[off : off + arr.nbytes], arr.reshape(-1).view(np.uint8))
        off += arr.nbytes
    # Read-only, as `bytes` was: in-process subscribers share one frame,
    # and `deserialize_weights` hands out views of it.
    return memoryview(buf).toreadonly()


def deserialize_weights(data: bytes) -> Tuple[List[Tuple[str, np.ndarray]], int, int]:
    """Returns (named_arrays, version, boot_epoch). Accepts the current
    DTW2 frames and legacy DTW1 (which carried no epoch → 0)."""
    magic = data[:4]
    if magic == _WEIGHTS_MAGIC2:
        _, version, boot_epoch, n = struct.unpack_from("<4sIII", data)
        off = struct.calcsize("<4sIII")
    elif magic == _WEIGHTS_MAGIC:
        _, version, n = struct.unpack_from("<4sII", data)
        boot_epoch = 0
        off = struct.calcsize("<4sII")
    else:
        raise ValueError("bad weights frame")
    out = []
    for _ in range(n):
        (name_len,) = struct.unpack_from("<H", data, off)
        off += 2
        name = str(data[off : off + name_len], "utf-8")  # any buffer, not bytes alone
        off += name_len
        (ndim,) = struct.unpack_from("<B", data, off)
        off += 1
        shape = struct.unpack_from(f"<{ndim}I", data, off) if ndim else ()
        off += 4 * ndim
        (code,) = struct.unpack_from("<B", data, off)
        off += 1
        dtype = _DTYPES[code]
        count = int(np.prod(shape)) if shape else 1
        arr = np.frombuffer(data, dtype, count=count, offset=off).reshape(shape)
        off += count * np.dtype(dtype).itemsize
        out.append((name, arr))
    return out, version, boot_epoch


def named_param_leaves(params) -> List[Tuple[str, Any]]:
    """(path-name, leaf) pairs in the CANONICAL sorted order every
    params consumer shares (wire format, checkpoint diffing, and the
    learner's fused single-buffer publish layout). Leaves are returned
    as-is — works on concrete arrays and on tracers inside jit."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    out = []
    for path, leaf in flat:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out.append((name, leaf))
    return sorted(out, key=lambda kv: kv[0])


def flatten_params(params) -> List[Tuple[str, np.ndarray]]:
    """Flax params pytree → sorted (path, f32 array) list."""
    return [(name, np.asarray(leaf, np.float32)) for name, leaf in named_param_leaves(params)]


def unflatten_params(named_arrays, template):
    """Inverse of flatten_params given a params template pytree."""
    import jax

    lookup = dict(named_arrays)
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in flat:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        arr = lookup[name]
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"shape mismatch for {name}: {arr.shape} vs {leaf.shape}")
        leaves.append(arr.astype(np.asarray(leaf).dtype))
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(template), leaves)
