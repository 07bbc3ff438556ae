"""Native (C++) host components, loaded via ctypes.

The compute path is JAX/XLA; the host runtime around it is native where
the throughput demands it. Currently: the rollout batch packer
(packer.cc), built on demand with g++ into this directory and loaded
with ctypes (the image has no pybind11 — the C ABI needs none).

`load_packer()` returns None when native is unavailable (no compiler,
build failure, or DOTACLIENT_TPU_NO_NATIVE=1); callers fall back to the
pure-python path. Never raises at import time.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import tempfile
import threading
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

_log = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "packer.cc")
_LIB = os.path.join(_DIR, "_packer.so")
_LIB_HOST = _LIB + ".host"  # ISA fingerprint of the host that built _LIB


def _host_isa() -> str:
    """Fingerprint of this host's ISA. The .so is built -march=native, so
    a cached binary is only valid on a host with the same instruction
    set — mtime alone would happily reuse an AVX-512 build on a host
    without it (snapshotted image / shared mount) and SIGILL mid-pack."""
    import hashlib
    import platform

    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    flags = " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        pass
    return hashlib.sha256(f"{platform.machine()}|{flags}".encode()).hexdigest()[:16]

_lock = threading.Lock()
_cached: Optional[ctypes.CDLL] = None
_load_failed = False

_u8p = ctypes.POINTER(ctypes.c_uint8)
_i64p = ctypes.POINTER(ctypes.c_int64)
_f32p = ctypes.POINTER(ctypes.c_float)
_u32p = ctypes.POINTER(ctypes.c_uint32)


def _build() -> bool:
    """(Re)build _packer.so when missing or older than the source.
    Atomic: compile to a temp file, then os.replace — concurrent
    processes race harmlessly."""
    tmp = None
    try:
        if os.path.exists(_LIB) and os.path.getmtime(_LIB) >= os.path.getmtime(_SRC):
            try:
                with open(_LIB_HOST) as f:
                    cached_host = f.read().strip()
            except OSError:
                cached_host = ""
            if cached_host == _host_isa():
                return True
            # Built on a different host (or pre-fingerprint): rebuild.
        fd, tmp = tempfile.mkstemp(suffix=".so.tmp", dir=_DIR)
        os.close(fd)
        # -march=native is safe here BECAUSE the .so is built on demand on
        # the host that runs it (never shipped): it unlocks vectorization
        # of the f32->bf16 convert loop (~2.2x measured on this host vs
        # plain -O3). Unknown-flag/old-gcc failures retry without it.
        base = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp, _SRC]
        proc = subprocess.run(
            base[:2] + ["-march=native"] + base[2:],
            capture_output=True, text=True, timeout=120,
        )
        if proc.returncode != 0:
            proc = subprocess.run(base, capture_output=True, text=True, timeout=120)
        if proc.returncode != 0:
            _log.warning("native packer build failed:\n%s", proc.stderr)
            return False
        # Publish the .so FIRST, then the fingerprint — atomically (temp
        # + replace) so a concurrent loader can never observe a
        # truncated/partial .host. The order matters: a crash between
        # the two replaces leaves the NEW .so next to the OLD fingerprint
        # → ISA mismatch → spurious rebuild (benign). The inverse order
        # would be unsafe on the ISA-mismatch rebuild path: current-host
        # fingerprint stamped next to a foreign-ISA .so whose mtime is
        # FRESH, so the next loader would reuse it and SIGILL mid-pack.
        os.replace(tmp, _LIB)
        tmp = None
        fd, tmp_host = tempfile.mkstemp(suffix=".host.tmp", dir=_DIR)
        try:
            with os.fdopen(fd, "w") as f:
                f.write(_host_isa())
            os.replace(tmp_host, _LIB_HOST)
        except Exception:
            if os.path.exists(tmp_host):
                os.unlink(tmp_host)
            raise
        return True
    except Exception as e:
        _log.warning("native packer build error: %s", e)
        return False
    finally:
        if tmp is not None and os.path.exists(tmp):
            os.unlink(tmp)


def load_packer() -> Optional[ctypes.CDLL]:
    """The compiled packer library, or None (python fallback)."""
    global _cached, _load_failed
    if _cached is not None:
        return _cached
    if _load_failed or os.environ.get("DOTACLIENT_TPU_NO_NATIVE", "") not in ("", "0"):
        return None
    with _lock:
        if _cached is not None:
            return _cached
        if not _build():
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(_LIB)
        except OSError as e:
            _log.warning("native packer load failed: %s", e)
            _load_failed = True
            return None
        lib.dt_pack_batch.restype = ctypes.c_int64
        lib.dt_frame_header.restype = ctypes.c_int64
        lib.dt_frame_headers.restype = ctypes.c_int64
        _cached = lib
        return lib


# ---------------------------------------------------------------------------
# High-level wrappers (numpy in, numpy out).


_schema_dims_cached = None


def _schema_dims():
    # Featurizer dims are process constants; caching keeps this helper
    # off the per-batch pack profile (it sat at ~1% of pack_frames).
    global _schema_dims_cached
    if _schema_dims_cached is None:
        from dotaclient_tpu.env import featurizer as F

        _schema_dims_cached = (
            F.GLOBAL_FEATURES, F.HERO_FEATURES, F.MAX_UNITS, F.UNIT_FEATURES, F.N_ACTION_TYPES
        )
    return _schema_dims_cached


_expect_dtypes_cached = {}


def _expect_dtypes(obs_bf16: bool):
    """np.dtype objects per `out` leaf, C-ABI order, cached: dtype-object
    comparison in the per-batch stride validation is ~10x cheaper than
    the `np.dtype(x).name` string path it replaced (the validation loop
    was a measurable slice of the pack call at flagship shapes)."""
    got = _expect_dtypes_cached.get(obs_bf16)
    if got is None:
        if obs_bf16:
            import ml_dtypes

            obs_dt = np.dtype(ml_dtypes.bfloat16)
        else:
            obs_dt = np.dtype(np.float32)
        got = (
            [obs_dt] * 3
            + [np.dtype(np.bool_)] * 3
            + [np.dtype(np.int32)] * 4
            + [np.dtype(np.float32)] * 10
        )
        _expect_dtypes_cached[obs_bf16] = got
    return got


def frame_header(lib: ctypes.CDLL, frame: bytes) -> Optional[Tuple[int, int, int, int, int, float, float]]:
    """(version, L, H, flags, actor_id, episode_return, last_done) or None
    if the frame is malformed. Validates the full frame size."""
    G, HF, U, UF, A = _schema_dims()
    version = ctypes.c_int64()
    L = ctypes.c_int64()
    H = ctypes.c_int64()
    flags = ctypes.c_int64()
    actor_id = ctypes.c_int64()
    ep_ret = ctypes.c_float()
    last_done = ctypes.c_float()
    rc = lib.dt_frame_header(
        ctypes.cast(ctypes.c_char_p(frame), _u8p),
        ctypes.c_int64(len(frame)),
        *(ctypes.c_int64(d) for d in (G, HF, U, UF, A)),
        ctypes.byref(version),
        ctypes.byref(L),
        ctypes.byref(H),
        ctypes.byref(flags),
        ctypes.byref(actor_id),
        ctypes.byref(ep_ret),
        ctypes.byref(last_done),
    )
    if rc != 0:
        return None
    return (
        version.value,
        L.value,
        H.value,
        flags.value,
        actor_id.value,
        ep_ret.value,
        last_done.value,
    )


class FrameHeaders(NamedTuple):
    """Struct-of-(python-)arrays result of a batched header parse —
    parallel lists by ctypes necessity, named so an added field can't
    silently shift positional consumers. ok[i] falsy marks a malformed
    frame (its other slots are unspecified)."""

    ok: List[int]
    versions: List[int]
    Ls: List[int]
    Hs: List[int]
    flags: List[int]
    actor_ids: List[int]
    ep_returns: List[float]
    last_dones: List[float]


def frame_headers(lib: ctypes.CDLL, frames: List[bytes]) -> FrameHeaders:
    """Batched header parse: ONE ctypes call for a whole ingest drain.

    The per-frame `frame_header` call costs ~5us of FFI overhead —
    1.3ms/batch at 256 frames, a third of the host packing budget
    (r5 profile); this is the same validation at one call's cost.
    """
    G, HF, U, UF, A = _schema_dims()
    n = len(frames)
    frame_ptrs = (ctypes.c_char_p * n)(*frames)
    frame_lens = np.fromiter((len(f) for f in frames), np.int64, count=n)
    versions = np.zeros(n, np.int64)
    Ls = np.zeros(n, np.int64)
    Hs = np.zeros(n, np.int64)
    flags = np.zeros(n, np.int64)
    actor_ids = np.zeros(n, np.int64)
    ep_rets = np.zeros(n, np.float32)
    last_dones = np.zeros(n, np.float32)
    ok = np.zeros(n, np.uint8)

    # Same bare-address pointer args as pack_frames (the staging ingest
    # calls this once per drain; data_as cost ~7us per array).
    def ptr(a):
        return ctypes.c_void_p(a.ctypes.data)

    lib.dt_frame_headers(
        ctypes.cast(frame_ptrs, ctypes.POINTER(_u8p)),
        ptr(frame_lens),
        ctypes.c_int64(n),
        *(ctypes.c_int64(d) for d in (G, HF, U, UF, A)),
        ptr(versions),
        ptr(Ls),
        ptr(Hs),
        ptr(flags),
        ptr(actor_ids),
        ptr(ep_rets),
        ptr(last_dones),
        ptr(ok),
    )
    # .tolist() once: the consumer's python filter loop then touches only
    # plain ints/floats (numpy scalar extraction per element is ~10x slower)
    return FrameHeaders(
        ok.tolist(),
        versions.tolist(),
        Ls.tolist(),
        Hs.tolist(),
        flags.tolist(),
        actor_ids.tolist(),
        ep_rets.tolist(),
        last_dones.tolist(),
    )


def _ordered_out_leaves(batch):
    """The 20 output arrays in C-ABI order (aux slots None-padded)."""
    aux_leaves = (
        (batch.aux.win, batch.aux.last_hit, batch.aux.net_worth)
        if batch.aux is not None
        else (None, None, None)
    )
    return (
        batch.obs.global_feats, batch.obs.hero_feats, batch.obs.unit_feats,
        batch.obs.unit_mask, batch.obs.target_mask, batch.obs.action_mask,
        batch.actions.type, batch.actions.move_x, batch.actions.move_y,
        batch.actions.target,
        batch.behavior_logp, batch.behavior_value, batch.rewards,
        batch.dones, batch.mask,
        batch.initial_state[0], batch.initial_state[1],
    ) + aux_leaves


def _validate_out_strides(batch, obs_bf16: bool, n: int, row_offset: int, want_rows: int):
    """Validate a caller-owned `out` batch against the C writer's fixed
    widths and return the 20-entry row-stride ctypes array. Raises
    BatchLayoutError (fatal to staging — a template/config mismatch
    fails every batch, not this one) on any disagreement."""
    from dotaclient_tpu.ops.batch import BatchLayoutError

    if row_offset < 0 or row_offset + n > want_rows:
        raise BatchLayoutError(
            f"row shard [{row_offset}, {row_offset + n}) outside the "
            f"{want_rows}-row out batch"
        )
    # Row stride in ELEMENTS per output, C-ABI order. Rows must be
    # internally contiguous; only the row-to-row distance may differ
    # from dense (the group-buffer column-block case).
    ordered = _ordered_out_leaves(batch)
    # Expected dtype per output, same order as `ordered` — the C
    # writer's widths are fixed, so a template/flag mismatch (e.g. an
    # uncast f32 template with obs_bf16=True) must fail HERE, not
    # silently reinterpret the storage and ship garbage obs.
    expect_dtypes = _expect_dtypes(obs_bf16)
    stride_vals = []
    for arr, want in zip(ordered, expect_dtypes):
        if arr is None:
            stride_vals.append(0)
            continue
        if arr.dtype != want:
            raise BatchLayoutError(
                f"out leaf dtype {np.dtype(arr.dtype).name} != {want} "
                f"(obs_bf16={obs_bf16}; template/flag mismatch)"
            )
        if arr.shape[0] != want_rows:
            raise BatchLayoutError(
                f"out batch rows {arr.shape[0]} != {want_rows} "
                f"({n} frames at row_offset {row_offset})"
            )
        stride_elems, rem = divmod(arr.strides[0], arr.itemsize)
        if rem:
            raise BatchLayoutError("out leaf row stride not a multiple of itemsize")
        # within-row contiguity: trailing dims must be C-contiguous
        expect = arr.itemsize
        for dim, st_b in zip(arr.shape[:0:-1], arr.strides[:0:-1]):
            if st_b != expect:
                raise BatchLayoutError("out leaf rows must be internally contiguous")
            expect *= dim
        stride_vals.append(stride_elems)
    return (ctypes.c_int64 * 20)(*stride_vals)


class PackPlan:
    """Prebuilt dt_pack_batch call template: pack exactly `n` frames
    into rows [row_offset, row_offset+n) of ONE long-lived `out` batch,
    repeatedly.

    The sharded host feed (--staging.pack_workers) packs every batch
    into reused TransferRing slots, so the expensive per-call glue —
    the 20-leaf stride/dtype validation and the 24 output-pointer
    marshals (~0.06 ms per shard call, GIL-held, measured on the bench
    host) — is identical call after call. A plan pays it ONCE; pack()
    only marshals the per-batch frame pointers/lengths and makes the
    (GIL-released) C call. Output is byte-identical to pack_frames with
    the same arguments.

    The plan holds references to `out`'s leaves; the caller must not
    resize/replace them (ring slots never do — their buffers live as
    long as the ring)."""

    def __init__(
        self,
        lib: ctypes.CDLL,
        out,
        n: int,
        seq_len: int,
        lstm_hidden: int,
        with_aux: bool,
        obs_bf16: bool,
        row_offset: int,
        total_rows: int,
    ):
        self._lib = lib
        self.n = n
        self.row_offset = row_offset
        strides_arg = _validate_out_strides(out, obs_bf16, n, row_offset, total_rows)
        G, HF, U, UF, A = _schema_dims()
        versions = np.empty(n, np.uint32)
        actor_ids = np.empty(n, np.uint32)
        ep_returns = np.empty(n, np.float32)

        def ptr(a):
            return ctypes.c_void_p(a.ctypes.data)

        ordered = _ordered_out_leaves(out)
        self._tail = (
            ctypes.c_int64(n),
            ctypes.c_int64(row_offset),
            ctypes.c_int64(seq_len),
            ctypes.c_int64(lstm_hidden),
            ctypes.c_int64(1 if with_aux else 0),
            ctypes.c_int64(1 if obs_bf16 else 0),
            *(ctypes.c_int64(d) for d in (G, HF, U, UF, A)),
            strides_arg,
            *(ptr(a) if a is not None else None for a in ordered),
            ptr(versions),
            ptr(actor_ids),
            ptr(ep_returns),
        )
        # keepalive: everything the prebuilt pointers reference
        self._keep = (out, strides_arg, versions, actor_ids, ep_returns)

    def pack(self, frames: List[bytes]) -> None:
        """One C pack of len(frames)==n frames into the planned rows.
        ValueError names the offending ABSOLUTE batch row on a malformed
        frame (same contract as pack_frames)."""
        n = len(frames)
        if n != self.n:
            from dotaclient_tpu.ops.batch import BatchLayoutError

            raise BatchLayoutError(f"plan packs {self.n} frames, got {n}")
        frame_ptrs = (ctypes.c_char_p * n)(*frames)
        frame_lens = np.fromiter((len(f) for f in frames), np.int64, count=n)
        rc = self._lib.dt_pack_batch(
            ctypes.cast(frame_ptrs, ctypes.POINTER(_u8p)),
            ctypes.c_void_p(frame_lens.ctypes.data),
            *self._tail,
        )
        if rc != 0:
            raise ValueError(
                f"native packer rejected frame {self.row_offset - rc - 1}"
            )


def pack_frames(
    lib: ctypes.CDLL,
    frames: List[bytes],
    seq_len: int,
    lstm_hidden: int,
    with_aux: bool,
    obs_bf16: bool = False,
    out=None,
    row_offset: int = 0,
    total_rows: Optional[int] = None,
):
    """Pack B wire frames into one padded TrainBatch (numpy leaves).

    Raises ValueError naming the offending frame index if any frame is
    malformed — mirroring the python packer's contract.

    `obs_bf16=True` allocates the float obs leaves as bf16 and converts
    f32→bf16 (RNE) inside the C copy loop — fusing staging's
    cast_obs_to_compute_dtype pass (1.1ms/batch of numpy astype at
    flagship shapes, r5 profile) into the pack for free, bitwise equal.

    `out`: a pre-allocated, pre-zeroed TrainBatch to fill instead of
    allocating one. Leaves may be row-strided views (the fused-H2D
    transfer buffer, FusedBatchIO.alloc_transfer) as long as each row's
    data is contiguous — per-leaf row strides are passed to C. The
    caller owns initialization (zeros + NOOP-legal action-mask padding,
    exactly zeros_train_batch's contract).

    `row_offset`/`total_rows` (require `out`): write the n frames at
    batch rows [row_offset, row_offset+n) of an `out` holding
    total_rows rows — the sharded host feed (--staging.pack_workers)
    runs N such calls CONCURRENTLY against one buffer, each shard a
    disjoint contiguous row range. Rows never overlap and each row
    depends only on its own frame, so any split is bitwise identical to
    the one-call pack. Defaults (0, None) are the classic whole-batch
    call: total_rows=None means `out` must hold exactly n rows.

    Exception contract: a malformed FRAME raises plain ValueError (the
    staging consumer drops the batch and continues); an `out` template
    LAYOUT/CONFIG mismatch raises BatchLayoutError (a ValueError
    subclass), which staging treats as fatal — it would fail every
    batch, not this one.
    """
    from dotaclient_tpu.ops.batch import BatchLayoutError, zeros_train_batch

    n = len(frames)
    if out is None:
        if row_offset or total_rows is not None:
            raise ValueError(
                "row_offset/total_rows require a caller-owned `out` batch "
                "(the sharded pack targets one shared buffer)"
            )
        obs_dtype = None
        if obs_bf16:
            import ml_dtypes

            obs_dtype = ml_dtypes.bfloat16
        batch = zeros_train_batch(n, seq_len, lstm_hidden, with_aux, obs_dtype=obs_dtype)
        strides_arg = None
    else:
        batch = out
        want_rows = n + row_offset if total_rows is None else total_rows
        strides_arg = _validate_out_strides(batch, obs_bf16, n, row_offset, want_rows)
    G, HF, U, UF, A = _schema_dims()

    args, _keepalive = _pack_batch_args(
        frames, batch, seq_len, lstm_hidden, with_aux, obs_bf16, strides_arg,
        (G, HF, U, UF, A), row_offset=row_offset,
    )
    rc = lib.dt_pack_batch(*args)
    if rc != 0:
        # absolute batch row (= shard-local index + row_offset), so a
        # sharded-pack rejection points at the right frame in the batch
        raise ValueError(f"native packer rejected frame {row_offset - rc - 1}")
    return batch


def _pack_batch_args(frames, batch, seq_len, lstm_hidden, with_aux, obs_bf16,
                     strides_arg, dims, row_offset=0):
    """The dt_pack_batch argument vector for a (frames, batch) pair →
    (args, keepalive). Split from pack_frames so the ctypes glue — a
    fixed per-call cost the wire dtype cannot change — is separately
    buildable/timed from the C pack itself (scripts/ab_wire_quant.py);
    `keepalive` must outlive the call (it owns the marshaled buffers).

    Bare-address pointer args: `c_void_p(a.ctypes.data)` is ~5x cheaper
    than `data_as(POINTER(...))` and this call passes 24 of them — the
    data_as path alone was ~0.15 ms of the ~1 ms flagship pack
    (dt_pack_batch declares no argtypes, so a void* passes through like
    any typed pointer; the arrays stay referenced by `batch`/keepalive
    for the duration of the call). dtype checking is not lost — the
    caller's validation (or zeros_train_batch allocation) already fixed
    every leaf's dtype. The obs leaves serve f32 AND bf16 storage; the
    C side reinterprets by the obs_bf16 flag."""
    n = len(frames)
    frame_ptrs = (ctypes.c_char_p * n)(*frames)
    # np.fromiter beats a ctypes-array(*listcomp) ~3x for the length
    # vector; the C side reads it as const int64_t* either way.
    frame_lens = np.fromiter((len(f) for f in frames), np.int64, count=n)
    # np.empty: dt_pack_batch writes every row before returning 0, and
    # the caller discards all three on a nonzero rc.
    versions = np.empty(n, np.uint32)
    actor_ids = np.empty(n, np.uint32)
    ep_returns = np.empty(n, np.float32)

    def ptr(a):
        return ctypes.c_void_p(a.ctypes.data)

    obs, acts, aux = batch.obs, batch.actions, batch.aux
    args = (
        ctypes.cast(frame_ptrs, ctypes.POINTER(_u8p)),
        ptr(frame_lens),
        ctypes.c_int64(n),
        ctypes.c_int64(row_offset),
        ctypes.c_int64(seq_len),
        ctypes.c_int64(lstm_hidden),
        ctypes.c_int64(1 if with_aux else 0),
        ctypes.c_int64(1 if obs_bf16 else 0),
        *(ctypes.c_int64(d) for d in dims),
        strides_arg,
        ptr(obs.global_feats),
        ptr(obs.hero_feats),
        ptr(obs.unit_feats),
        ptr(obs.unit_mask),
        ptr(obs.target_mask),
        ptr(obs.action_mask),
        ptr(acts.type),
        ptr(acts.move_x),
        ptr(acts.move_y),
        ptr(acts.target),
        ptr(batch.behavior_logp),
        ptr(batch.behavior_value),
        ptr(batch.rewards),
        ptr(batch.dones),
        ptr(batch.mask),
        ptr(batch.initial_state[0]),
        ptr(batch.initial_state[1]),
        ptr(aux.win) if aux is not None else None,
        ptr(aux.last_hit) if aux is not None else None,
        ptr(aux.net_worth) if aux is not None else None,
        ptr(versions),
        ptr(actor_ids),
        ptr(ep_returns),
    )
    return args, (frame_ptrs, frame_lens, versions, actor_ids, ep_returns, batch)
