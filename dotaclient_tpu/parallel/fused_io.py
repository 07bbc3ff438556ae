"""Fused host→device batch transfer: 17 pytree leaves → 1 buffer.

Motivation (BENCH_TPU_20260730T0510.json, the one chip record of the
per-leaf loop: `split.device_put_s` was 11.97 ms of a 13.25 ms
iteration on the 17-leaf tree path): a put pays a per-transfer overhead
on top of its bytes. The TPU mandate is "minimize host↔device
transfers"; this module makes the transfer count 1 regardless of how
many leaves the batch grows.

Mechanics:
- Every TrainBatch leaf is batch-leading, so each flattens to
  [B, cols]; leaves of one dtype (f32 / bf16 / int32 / bool-as-uint8)
  sit side by side in a segment, and a row is the byte-concatenation of
  its segments in a fixed order, each padded to 4 bytes (RowLayout).
  The batch crosses H2D as ONE [B, row_bytes] u8 array. The leading
  axis stays intact, so the buffer shards over dp EXACTLY like the tree
  did — this is not a dp=1 special case.
- Packing runs on the staging thread, straight into leaf VIEWS of the
  transfer buffer (alloc_transfer); unpacking (segment slice, a free
  bitcast, slice + reshape per leaf) runs INSIDE the jit train step,
  where XLA fuses it into the first consumers.
- Sequence-parallel mode is the one exclusion: sp shards the obs TIME
  axis, which column-flattening would destroy. The learner takes the
  per-leaf tree path when sp is active (parallel/train_step.py).
"""

from __future__ import annotations

import queue
import zlib
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Stable segment keys, by dtype NAME (ml_dtypes.bfloat16 has no stable
# np.dtype singleton). Bool packs as uint8 (XLA preds are byte-wide on
# the wire anyway); everything else transfers in its native dtype.
_GROUP_OF = {"float32": "f32", "int32": "i32", "bool": "u8", "uint8": "u8", "bfloat16": "bf16"}


_GROUP_DTYPES = {"f32": np.float32, "i32": np.int32, "u8": np.uint8, "bf16": "bfloat16"}


class _LeafSlot(NamedTuple):
    index: int  # position in the flattened batch
    shape: Tuple[int, ...]  # full leaf shape (incl. batch dim)
    dtype: Any  # ORIGINAL dtype (bool restored on unpack)
    start: int  # column offset inside the group buffer
    cols: int


class RowLayout:
    """The single-buffer row layout, mesh-free and jax-free.

    Extracted from FusedBatchIO so the broker shards (ISSUE 20 in-network
    assembly) can compute the EXACT byte layout of a staged batch row —
    group segments in the fixed ("f32","i32","bf16","u8") order, each
    padded to 4 bytes, leaves at their column offsets — without touching
    jax or a device mesh. Built from the flattened template's
    (shape, dtype) list; FusedBatchIO delegates its single-buffer layout
    here, so shard-side and learner-side offsets can never diverge
    (`layout_crc` pins the whole descriptor and travels in every DTB1
    block header)."""

    def __init__(self, specs: List[Tuple[Tuple[int, ...], Any]]):
        self.slots: Dict[str, List[_LeafSlot]] = {}
        cols: Dict[str, int] = {}
        for i, (shape, dtype) in enumerate(specs):
            key = _GROUP_OF.get(np.dtype(dtype).name)
            if key is None:
                raise TypeError(f"fused_io: unsupported batch leaf dtype {np.dtype(dtype)}")
            n = int(np.prod(shape[1:], dtype=np.int64)) if len(shape) > 1 else 1
            self.slots.setdefault(key, []).append(
                _LeafSlot(i, tuple(shape), dtype, cols.get(key, 0), n)
            )
            cols[key] = cols.get(key, 0) + n
        self.group_cols = cols
        self.n_leaves = len(specs)
        self.seg_off: Dict[str, int] = {}
        off = 0
        for key in ("f32", "i32", "bf16", "u8"):
            if key not in cols:
                continue
            self.seg_off[key] = off
            nbytes = cols[key] * np.dtype(_GROUP_DTYPES[key]).itemsize
            off += (nbytes + 3) & ~3
        self.row_bytes = off
        # Canonical descriptor → crc32: every quantity a row copy depends
        # on. Two processes agreeing on the crc agree on every byte
        # position of every leaf.
        desc = ";".join(
            f"{s.index}:{','.join(map(str, s.shape[1:]))}:"
            f"{np.dtype(s.dtype).name}:{key}:{s.start}"
            for key in ("f32", "i32", "bf16", "u8")
            if key in self.slots
            for s in self.slots[key]
        )
        desc += "|" + ",".join(
            f"{k}={self.seg_off[k]}" for k in sorted(self.seg_off)
        )
        desc += f"|row_bytes={self.row_bytes}"
        self.layout_crc = zlib.crc32(desc.encode()) & 0xFFFFFFFF

    def views_into(self, buf: np.ndarray, rows: int) -> List[np.ndarray]:
        """Leaf views (flat order) into a [rows, row_bytes] u8 buffer —
        FusedBatchIO.alloc_transfer's layout-only core. Bool leaves come back
        as bool views; every view is asserted to share memory with buf
        (a silent copy would disconnect the batch from the transfer
        bytes and ship zeros)."""
        leaves: List[Any] = [None] * self.n_leaves
        for key, slots in self.slots.items():
            gdt = np.dtype(_GROUP_DTYPES[key])
            for s in slots:
                dt = np.dtype(np.bool_) if np.dtype(s.dtype) == np.bool_ else gdt
                rev = []
                acc = dt.itemsize
                for d in reversed(s.shape[1:]):
                    rev.append(acc)
                    acc *= d
                strides = (self.row_bytes,) + tuple(reversed(rev))
                v = np.ndarray(
                    shape=(rows,) + s.shape[1:],
                    dtype=dt,
                    buffer=buf,
                    offset=self.seg_off[key] + s.start * gdt.itemsize,
                    strides=strides,
                )
                if not np.may_share_memory(v, buf):
                    raise AssertionError("RowLayout.views_into: leaf view detached")
                leaves[s.index] = v
        return leaves


class FusedBatchIO:
    """Pack/unpack between a TrainBatch pytree and the one
    [B, row_bytes] u8 transfer buffer of RowLayout. Built once per
    (config, mesh) from a template batch; the layout is static, so the
    jit unpack is pure slicing and bitcasts."""

    def __init__(self, template, mesh: Mesh):
        leaves, self.treedef = jax.tree.flatten(template)
        B = leaves[0].shape[0]
        if any(leaf.shape[0] != B for leaf in leaves):
            raise ValueError("fused_io: every batch leaf must be batch-leading")
        self.batch = B
        # The mesh-free layout core (shared with the broker-side row
        # assembler — transport/assemble.py builds the SAME RowLayout
        # from the same template specs, so layout_crc pins parity).
        self.layout = RowLayout([(tuple(l.shape), l.dtype) for l in leaves])
        self.slots = self.layout.slots
        self.group_cols = self.layout.group_cols
        self.seg_off = self.layout.seg_off
        self.row_bytes = self.layout.row_bytes
        # pack_transfer() accepts exactly this many rows; defaults to the
        # template (global) batch. Multihost learners set it to their
        # per-process share so a mis-sized batch still fails AT THE PACK
        # BOUNDARY with a named count, not downstream as an opaque
        # jit/assembly shape error.
        self.local_rows = B
        # Rows stay intact in the buffer, so it shards over dp as the
        # tree's leading axis did.
        dp = "dp" if "dp" in mesh.axis_names else None
        self.sharding = NamedSharding(mesh, P(dp, None))

    # ----------------------------------------------------------- host side

    def alloc_transfer(self):
        """(buf, batch): ONE zeroed [rows, row_bytes] u8 transfer buffer +
        a TrainBatch whose leaves are row-strided VIEWS into it.

        The staging packer fills the views (numpy fallback transparently;
        the C packer via per-leaf row strides), after which `buf` is
        already the device-transfer layout and ships as a single
        device_put — no regroup copy runs. Leaf views sit at their
        segment's byte offset; within a row every leaf block is
        contiguous, so only the row-to-row stride differs from dense.
        Initialization contract matches zeros_train_batch: all-zero
        leaves, NOOP-legal action-mask padding rows."""
        from dotaclient_tpu.env import featurizer as F

        rows = self.local_rows
        buf = np.zeros((rows, self.row_bytes), np.uint8)
        leaves = self.layout.views_into(buf, rows)
        batch = jax.tree.unflatten(self.treedef, leaves)
        batch.obs.action_mask[:] = F.zeros_observation().action_mask
        return buf, batch

    def pack_transfer(self, batch):
        """Dense batch → transfer buffer: the fallback the learner's
        fetch takes when staging hands over a batch it did not pack into
        views. Rows come from `local_rows`, not the template: in
        multihost mode each process packs its LOCAL share and the learner
        stitches the shares into the global array (_fetch_next).

        A mis-sized or structurally different batch must fail HERE with
        a named error, not silently truncate the leaf zip or broadcast
        one row across the buffer. BatchLayoutError marks it as a
        persistent config mismatch — staging crashes its consumer loudly
        instead of logging dropped_bad forever (ops/batch.py)."""
        from dotaclient_tpu.ops.batch import BatchLayoutError

        leaves, treedef = jax.tree.flatten(batch)
        if treedef != self.treedef:
            raise BatchLayoutError(
                f"fused pack: batch structure {treedef} != template {self.treedef}"
            )
        rows = np.asarray(leaves[0]).shape[0]
        if rows != self.local_rows:
            raise BatchLayoutError(
                f"fused pack: got {rows} rows, expected {self.local_rows} "
                f"(template batch {self.batch}; multihost learners set "
                f"local_rows to their per-process share)"
            )
        buf, views = self.alloc_transfer()
        for v, ref in zip(jax.tree.leaves(views), leaves):
            v[...] = ref
        return buf

    def make_ring(self, depth: int) -> "TransferRing":
        """A ring of `depth` preallocated transfer buffers. See
        TransferRing."""
        return TransferRing(self, depth)

    # --------------------------------------------------------- device side

    def unpack_single(self, buf: jnp.ndarray):  # graftlint: jit-region
        """[B, row_bytes] u8 → TrainBatch, inside jit: slice each
        segment's bytes, bitcast u8[..., k] to the segment dtype, then
        slice + reshape per leaf — XLA fuses them into the first
        consumers. Bitcasts are free on device (layout reinterpretation;
        both sides little-endian)."""
        B = buf.shape[0]
        leaves: List[Any] = [None] * self.layout.n_leaves
        for key, slots in self.slots.items():
            gdt = np.dtype(_GROUP_DTYPES[key])
            k = gdt.itemsize
            cols = self.group_cols[key]
            seg = jax.lax.slice_in_dim(
                buf, self.seg_off[key], self.seg_off[key] + cols * k, axis=1
            )
            if k > 1:
                seg = jax.lax.bitcast_convert_type(seg.reshape(B, cols, k), gdt)
            for s in slots:
                x = jax.lax.slice_in_dim(seg, s.start, s.start + s.cols, axis=1)
                x = x.reshape(s.shape)
                if np.dtype(s.dtype) == np.bool_:
                    x = x != 0
                leaves[s.index] = x
        return jax.tree.unflatten(self.treedef, leaves)


class RingSlot:
    """One preallocated transfer-buffer set with explicit ownership.

    Lifecycle (TransferRing docstring): acquire() hands the slot to the
    packer freshly RE-ZEROED to the alloc_transfer contract (all-zero
    leaves + NOOP-legal action-mask padding — a reused buffer must not
    leak the previous batch into this batch's padding); release() hands
    it back to the free queue. release() is idempotent — a double
    release must not duplicate the slot in the free queue (two packers
    would then write one buffer concurrently)."""

    __slots__ = ("_ring", "index", "payload", "batch", "_held")

    def __init__(self, ring: "TransferRing", index: int, payload, batch):
        self._ring = ring
        self.index = index
        self.payload = payload  # the [rows, row_bytes] u8 transfer buffer
        self.batch = batch  # TrainBatch of leaf VIEWS into payload
        self._held = False

    def _reset(self) -> None:
        """Zero the backing buffer and restore the NOOP action-mask
        padding — exactly zeros_train_batch's initialization contract,
        so a reused slot packs bitwise like a fresh allocation."""
        from dotaclient_tpu.env import featurizer as F

        self.payload[...] = 0
        self.batch.obs.action_mask[:] = F.zeros_observation().action_mask

    def release(self) -> None:
        """Return the slot to the free queue (in-transfer → free). Call
        only after the device_put of `payload` has RETIRED
        (jax.block_until_ready on the put result): jax may defer the
        host read of a put'd numpy buffer, and re-zeroing a buffer whose
        transfer is still in flight ships garbage (observed on the CPU
        backend — runtime/learner.py _fetch_next is the release site)."""
        if self._held:
            self._held = False
            self._ring._free.put(self)


class TransferRing:
    """Ring of preallocated transfer buffers with explicit ownership
    handoff: free → packing (acquire) → ready/in-transfer (staging ready
    queue → learner fetch → device_put) → free (release).

    Replaces the one-shot alloc_transfer per batch on the parallel host
    feed (--staging.pack_workers > 1): pack of batch N+1 proceeds into a
    free slot while batch N's buffers are crossing H2D and batch N-1 is
    still on device — the pipeline-overlap gap OPPO (PAPERS.md
    2509.25762) names for PPO loops. Depth 2 (default) is classic double
    buffering; the learner's fetch returns the slot as a lease and
    releases it once the device_put retires, which is what makes buffer
    REUSE safe (RingSlot.release).

    Thread contract: acquire() is called by the ONE staging assembler
    thread; release() by the ONE learner loop thread; the free queue is
    the synchronization point. A starved acquire (every slot ready or
    in transfer) blocks — that is the ring's backpressure, bounded by
    depth, exactly like the ready queue's maxsize."""

    def __init__(self, io: FusedBatchIO, depth: int):
        if depth < 1:
            raise ValueError(f"transfer ring depth must be >= 1, got {depth}")
        self.io = io
        self.depth = depth
        self._free: "queue.Queue[RingSlot]" = queue.Queue()
        self.slots = []
        for i in range(depth):
            payload, batch = io.alloc_transfer()
            slot = RingSlot(self, i, payload, batch)
            self.slots.append(slot)
            self._free.put(slot)

    def acquire(self, timeout: Optional[float] = None) -> Optional[RingSlot]:
        """Next free slot, re-zeroed and ready to pack into; None on
        timeout (caller re-checks its stop flag and retries)."""
        try:
            slot = self._free.get(timeout=timeout)
        except queue.Empty:
            return None
        slot._held = True
        slot._reset()
        return slot

    @property
    def occupancy(self) -> int:
        """Slots currently out of the free queue (packing, ready, or in
        transfer) — the staging_pack_ring_occupancy gauge."""
        return self.depth - self._free.qsize()
