"""Host-side staging buffer: broker frames → padded device batches.

This is the piece the north star adds to the reference design: the
consumer side of the RMQ pipe gains a TPU host-staging buffer that packs
variable-length trajectories into fixed [B, T] padded, masked,
version-filtered batches (BASELINE.json north_star; SURVEY.md §3.2
device-boundary note). Structure:

- a consumer thread drains the broker and deserializes frames;
- rollouts older than `max_staleness` learner versions are dropped here,
  on the host, before they cost any device time (SURVEY.md §7
  "Staleness/backpressure") — unless the replay reservoir is enabled
  (LearnerConfig.replay, dotaclient_tpu/replay/), in which case
  near-stale rollouts are RETAINED in a prioritized reservoir and mixed
  back into batches at a configurable ratio, each row stamped with its
  behavior-policy staleness for the ACER truncated importance weights
  in ops/ppo.py;
- a packer assembles ready batches into a bounded queue (depth 2) so
  packing the next batch overlaps the device step on the current one
  (double buffering);
- single-writer ownership: only the consumer thread touches the pending
  list AND the reservoir, only get_batch pops ready batches (SURVEY.md
  §5 race-detection note — structural avoidance, mirrored from the
  reference's single-threaded consumers).

Failure split (ADVICE r5 item 1): a malformed FRAME costs its own batch
at worst (dropped_bad, consumer continues) — and since the chaos era it
also leaves EVIDENCE: parse/layout failures are filed in a bounded
dead-letter quarantine ring (reason + size + header prefix, the
`staging_quarantined` scalar, dumped by the flight recorder as a
section) so a corrupt wire is distinguishable from a misbuilt actor
post-mortem. A batch/template LAYOUT or CONFIG mismatch
(ops.batch.BatchLayoutError from the native packer or the fused
transfer pack) is a persistent builder/staging disagreement that would
fail every batch forever — the consumer thread dies loudly and
get_batch/get_batch_groups re-raise instead of starving the learner
behind per-batch warnings.
"""

from __future__ import annotations

import collections
import logging
import queue
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from dotaclient_tpu.config import LearnerConfig
from dotaclient_tpu.ops.batch import BatchLayoutError, TrainBatch, zeros_train_batch

_log = logging.getLogger(__name__)
from dotaclient_tpu.obs import spans
from dotaclient_tpu.obs.spans import span
from dotaclient_tpu.obs.trace import TraceRef
from dotaclient_tpu.transport.base import Broker
from dotaclient_tpu.transport.serialize import (
    Rollout,
    WireDtypeError,
    check_dtr3_dtype_map,
    deserialize_rollout,
    peek_rollout_trace,
    rollout_obs_bf16,
    strip_rollout_trace,
    wire_obs_is_bf16,
)


def fill_rollouts(
    batch: TrainBatch, rollouts: List[Rollout], seq_len: int, row_offset: int = 0
) -> None:
    """Fill a pre-zeroed TrainBatch (zeros_train_batch contract) with B
    variable-length rollouts, in place. The leaves may be strided views
    (the fused-H2D transfer buffer) or dense arrays; numpy assignment
    handles both, including the f32→bf16 cast when the obs leaves are
    staged in the compute dtype.

    `row_offset`: rollout i lands at batch row row_offset+i — the
    python-fallback half of the sharded pack (--staging.pack_workers):
    N workers fill disjoint contiguous row ranges of the SAME batch
    concurrently; rows never overlap and each row depends only on its
    own rollout, so any split is bitwise identical to one call."""
    T = seq_len
    obs, actions, aux = batch.obs, batch.actions, batch.aux
    # np.errstate: same untrusted-float story as cast_obs_to_compute_dtype
    # — on the fused path the obs destinations are bf16 views and this
    # assignment IS the f32→bf16 cast, so NaN/inf/out-of-range wire
    # values would emit per-batch RuntimeWarnings here.
    with np.errstate(invalid="ignore", over="ignore"):
        for i, r in enumerate(rollouts):
            b = row_offset + i
            L = r.length
            if L > T:
                raise ValueError(f"rollout length {L} exceeds learner seq_len {T}")
            for field in range(len(obs)):
                obs[field][b, : L + 1] = r.obs[field][: L + 1]
            for field in range(len(actions)):
                actions[field][b, :L] = r.actions[field][:L]
            batch.behavior_logp[b, :L] = r.behavior_logp
            batch.behavior_value[b, :L] = r.behavior_value
            batch.rewards[b, :L] = r.rewards
            batch.dones[b, :L] = r.dones
            batch.mask[b, :L] = 1.0
            batch.initial_state[0][b] = r.initial_state[0]
            batch.initial_state[1][b] = r.initial_state[1]
            if aux is not None and r.aux is not None:
                aux.win[b, :L] = r.aux.win
                aux.last_hit[b, :L] = r.aux.last_hit
                aux.net_worth[b, :L] = r.aux.net_worth


def shard_rows(total: int, workers: int) -> List[tuple]:
    """Contiguous (offset, count) row shards, as even as possible: the
    first total%workers shards get one extra row. Fewer rows than
    workers degenerates to one-row shards (never empty ones)."""
    n = max(1, min(workers, total))
    base, rem = divmod(total, n)
    shards = []
    off = 0
    for i in range(n):
        cnt = base + (1 if i < rem else 0)
        shards.append((off, cnt))
        off += cnt
    return shards


class _StagingStopped(Exception):
    """Internal: a sharded pack was abandoned because stop() landed
    mid-batch (ring acquire or pool join interrupted). Not a frame
    error — the pack loop exits without counting dropped_bad."""


class _ShardJob:
    """Countdown latch for one sharded batch: N tasks share one event,
    the last finisher sets it — the dispatcher pays ONE wait, not N."""

    __slots__ = ("event", "errors", "_remaining", "_lock")

    def __init__(self, n: int):
        self.event = threading.Event()
        self.errors: List[BaseException] = []
        self._remaining = n
        self._lock = threading.Lock()

    def done_one(self, error: Optional[BaseException]) -> None:
        with self._lock:
            if error is not None:
                self.errors.append(error)
            self._remaining -= 1
            last = self._remaining == 0
        if last:
            self.event.set()


class _PackPool:
    """--staging.pack_workers packer threads executing row-shard tasks.

    Each task packs a disjoint row range of ONE shared output buffer
    (native: dt_pack_batch with row_offset, GIL released → real
    parallelism; python fallback: fill_rollouts with row_offset). The
    meters feed the registry-pinned staging_pack_* scalars: per-worker
    busy seconds (executing a shard) and stall seconds (idle, waiting
    for work) — a pool whose stall dwarfs busy is oversized for the
    offered batch rate. All meters live under one lock; workers touch it
    twice per task, microseconds against a ~ms pack."""

    def __init__(self, workers: int, name: str = "staging-pack"):
        self.n = workers
        self._tasks: "queue.Queue" = queue.Queue()
        self._stop = threading.Event()
        self._meters_lock = threading.Lock()
        self._busy_s = [0.0] * workers
        self._stall_s = [0.0] * workers
        self._tasks_done = 0
        self._threads = [
            threading.Thread(target=self._run, args=(i,), daemon=True, name=f"{name}-{i}")
            for i in range(workers)
        ]
        for t in self._threads:
            t.start()

    def _run(self, i: int) -> None:
        spans.name_thread(threading.current_thread().name)
        while True:
            t0 = time.perf_counter()
            try:
                task = self._tasks.get(timeout=0.2)
            except queue.Empty:
                with self._meters_lock:
                    self._stall_s[i] += time.perf_counter() - t0
                if self._stop.is_set():
                    return
                continue
            with self._meters_lock:
                self._stall_s[i] += time.perf_counter() - t0
            fn, job = task
            t1 = time.perf_counter()
            # (workers never see a None task: dispatch is run_tasks only,
            # and shutdown rides the _stop event + empty-queue check)
            error = None
            try:
                fn()
            except BaseException as e:  # the dispatcher re-raises, typed
                error = e
            finally:
                with self._meters_lock:
                    self._busy_s[i] += time.perf_counter() - t1
                    self._tasks_done += 1
                job.done_one(error)

    def run_tasks(self, thunks, stop: threading.Event):
        """Dispatch the thunks (one per row shard) and wait for all.
        Returns None on success, the most severe error otherwise
        (BatchLayoutError outranks ValueError — fatal beats drop), or
        _StagingStopped when teardown interrupted the batch."""
        job = _ShardJob(len(thunks))
        for fn in thunks:
            self._tasks.put((fn, job))
        while not job.event.wait(timeout=0.2):
            # Workers only exit when stopped AND the task queue was
            # empty at their last check; a task enqueued after every
            # worker exited would wait forever — detect and abandon.
            if stop.is_set() and not any(t.is_alive() for t in self._threads):
                return _StagingStopped()
        layout = other = None
        for e in job.errors:
            if isinstance(e, BatchLayoutError):
                layout = layout or e
            else:
                other = other or e
        return layout or other

    def run_sharded(self, task_fn, shards, stop: threading.Event):
        """run_tasks over task_fn(offset, count) thunks — the
        convenience entry benches/tests use."""
        return self.run_tasks(
            [(lambda o=off, c=cnt: task_fn(o, c)) for off, cnt in shards], stop
        )

    def meters(self):
        """(busy_s list, stall_s list, tasks_done) — one locked snapshot."""
        with self._meters_lock:
            return list(self._busy_s), list(self._stall_s), self._tasks_done

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=5)


def pack_rollouts(rollouts: List[Rollout], seq_len: int, with_aux: bool) -> TrainBatch:
    """Pad B variable-length rollouts into one fixed [B, T] TrainBatch.

    Rollouts longer than `seq_len` are a config mismatch and rejected.
    Padding rows reuse zero observations; `mask` marks real steps. All
    leaves are numpy — `jax.device_put` with the dp sharding happens at
    the caller.
    """
    B = len(rollouts)
    H = rollouts[0].initial_state[0].shape[-1]
    batch = zeros_train_batch(B, seq_len, H, with_aux)
    fill_rollouts(batch, rollouts, seq_len)
    return batch


def cast_obs_to_compute_dtype(cfg: LearnerConfig, batch: TrainBatch) -> TrainBatch:
    """Cast float obs leaves to the policy compute dtype ON THE HOST
    (runs on the staging thread — off the train loop's critical path).

    The policy's first op on every obs float is `.astype(bf16)`, so
    pre-casting is numerically IDENTICAL (same round-to-nearest) and
    halves the bytes of the dominant host→device transfer — measured on
    silicon as the e2e bottleneck (BENCH_TPU_20260730T0510.json:
    device_put 12.0ms/iter vs 1.3ms of everything else; obs floats are
    5.1 of the batch's 5.65 MB). Casting selects by dtype, so every
    float32 obs leaf — present or future — is covered. GAE/loss scalars
    (rewards, logp, values, mask) stay f32 — their precision is
    load-bearing and their bytes are not. bench.py routes its synthetic
    batches through this same function so its device-only section times
    the executable production actually runs."""
    if not cfg.stage_obs_compute_dtype or cfg.policy.dtype == "float32":
        return batch
    import ml_dtypes

    dt = {"bfloat16": ml_dtypes.bfloat16}.get(cfg.policy.dtype)
    if dt is None:  # unknown compute dtype: ship f32, the policy casts
        return batch
    # Wire frames are untrusted: fuzzed/corrupt obs floats (NaN, inf,
    # beyond-bf16 magnitudes) reach this cast before any validation that
    # could reject them, and numpy's per-cast RuntimeWarning would spam
    # the gate output (VERDICT r5 item 9). The cast itself is total —
    # NaN/inf propagate, out-of-range saturates to inf — and the learner
    # masks or drops such rows downstream, so silence the warning here
    # rather than pay a pre-scan of every batch.
    with np.errstate(invalid="ignore", over="ignore"):
        obs = batch.obs._replace(
            **{
                f: v.astype(dt)
                for f, v in batch.obs._asdict().items()
                if getattr(v, "dtype", None) == np.float32
            }
        )
    return batch._replace(obs=obs)


class StagingBuffer:
    """Consume → filter → pack pipeline feeding the train loop.

    Two packing paths, identical output:
    - native (default): frames are header-validated in C and kept as raw
      bytes; a whole batch packs in one C call (one memcpy per field,
      GIL released — packing overlaps the device step);
    - python fallback: full deserialize + per-field numpy copies
      (DOTACLIENT_TPU_NO_NATIVE=1, no compiler, or native_packer=False).
    """

    def __init__(
        self,
        cfg: LearnerConfig,
        broker: Broker,
        version_fn: Callable[[], int] = lambda: 0,
        fused_io=None,
        tracer=None,
        recorder=None,
    ):
        self.cfg = cfg
        self.broker = broker
        self.version_fn = version_fn
        # Pipeline observability (dotaclient_tpu/obs/), both optional:
        # `tracer` records per-hop latency for trace-stamped frames,
        # `recorder` receives pipeline events and dumps its ring on the
        # fatal BatchLayoutError path. None (the default) keeps every
        # pre-obs code path byte-for-byte: no per-row hop work, no
        # parallel trace bookkeeping.
        self._tracer = tracer
        self._recorder = recorder
        # Parallel to _pending, ONLY maintained when tracer is set: the
        # TraceRef (or None) for each pending item, same single-writer
        # discipline.
        self._pending_traces: List = []
        # Trace refs of the batch most recently returned by
        # get_batch_groups (learner-thread-read; None when untraced).
        self.last_batch_trace = None
        # Fused-H2D mode (parallel/fused_io.FusedBatchIO): the packer
        # fills leaf VIEWS of the one u8 transfer buffer, so the
        # learner ships that buffer without a regroup copy. The caller must
        # pass the SAME io the train step was built with (layouts must
        # agree) and read via get_batch_groups.
        self._fused_io = fused_io
        # python path: Rollout objects; native path: raw frame bytes
        self._pending: List = []
        # queue items: (TrainBatch, transfer-buffer-or-None, traces, lease)
        self._ready: "queue.Queue" = queue.Queue(maxsize=2)
        self._stop = threading.Event()
        # Parallel host feed (--staging.pack_workers > 1): a dedicated
        # pop thread drains the broker into a bounded intake queue, an
        # ASSEMBLER thread owns everything the consumer thread owned
        # (parse/filter/_pending/reservoir — the single-writer
        # discipline moves wholesale, it never splits), and a pool of
        # pack workers fills disjoint row shards of one output buffer
        # concurrently. In fused mode the outputs come from a
        # TransferRing of cfg.staging.transfer_depth preallocated
        # buffer sets (pack N+1 overlaps H2D of N); the learner's fetch
        # carries the slot as a lease (last_batch_lease) released after
        # its device_put retires. pack_workers=1 (default) builds NONE
        # of this — the classic one-consumer-thread path, byte-for-byte
        # (the inertness contract, proven in a subprocess in
        # tests/test_staging.py).
        from dotaclient_tpu.config import StagingConfig

        self._staging_cfg = getattr(cfg, "staging", None) or StagingConfig()
        if self._staging_cfg.pack_workers < 1:
            raise ValueError(
                f"staging.pack_workers must be >= 1, got "
                f"{self._staging_cfg.pack_workers}"
            )
        self._pool: Optional[_PackPool] = None
        self._ring = None
        # slot.index → per-shard native.PackPlan list (ring mode only)
        self._slot_plans: Dict[int, List] = {}
        self._intake: Optional["queue.Queue"] = None
        self._assembler: Optional[threading.Thread] = None
        # True while the pop thread holds a popped-but-not-yet-enqueued
        # drain in its locals (set under _mutate_lock, the _packing
        # pattern) — drained() must see those frames.
        self._popping = False
        # Lease of the batch most recently returned by a getter (None on
        # the classic path). Single-consumer contract, like
        # last_batch_trace: only the learner loop pops batches.
        self.last_batch_lease = None
        # Downstream prefetch-lane station: the learner's PrefetchLane
        # pops batches off _ready and holds them (locals or its handoff
        # queue) until the loop trains them. drained() must see those
        # popped-but-untrained frames or a SIGTERM drain could declare
        # victory one batch early. None = no lane (a non-learner
        # consumer).
        self._prefetch_probe = None
        # SIGTERM drain: once set, the consumer stops popping the broker
        # but keeps packing already-pending frames into full batches —
        # the learner trains those out, then checkpoints the (< B)
        # leftover pending frames in the full-state aux manifest so a
        # drain loses ZERO popped frames. Cleared by start() (the
        # restartable-buffer contract phased drivers rely on).
        self._quiesce = threading.Event()
        # True while the consumer holds a popped-but-not-yet-queued batch
        # in its locals (set under _mutate_lock in the pop, cleared after
        # the ready-queue put) — drained() must see that batch.
        self._packing = False
        # Full-state snapshot exclusion: the consumer thread holds this
        # around its two mutation sites (_ingest, _next_batch_items) —
        # two uncontended acquires per LOOP ITERATION, never per frame —
        # and snapshot_state() takes it from the checkpoint worker, so a
        # snapshot is always a consistent cut (never a half-formed
        # batch: take-pending and the reservoir sample live inside one
        # held region) regardless of whether the consumer is running,
        # stopped, or being restarted by a phased driver.
        self._mutate_lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        # Set when the consumer thread dies on a BatchLayoutError; the
        # learner-side getters re-raise it so the mismatch surfaces as a
        # fast failure, not silent starvation.
        self._fatal: Optional[BaseException] = None
        self._lib = None
        if getattr(cfg, "native_packer", True):
            from dotaclient_tpu import native

            self._lib = native.load_packer()
        # Wire-bytes codec for pending items (full-state checkpoints):
        # the native path stages raw frame bytes (identity), the python
        # path stages Rollout objects (serialize/deserialize) — the same
        # split the replay reservoir uses, so snapshots re-enter the SAME
        # packer unchanged on restore.
        if self._lib is not None:
            self._item_encode = lambda it: it
            self._item_decode = lambda b: b
        else:
            from dotaclient_tpu.transport.serialize import serialize_rollout

            self._item_encode = serialize_rollout
            self._item_decode = deserialize_rollout
        # In-network batch assembly (--staging.assemble, transport/
        # assemble.py): the fabric shards pre-pack every admitted frame
        # into the native packer's exact row layout and this host
        # consumes DTB1 blocks of finished rows. _ingest_assembled
        # meters the per-row sidecars (version/trace/priority/episode)
        # and _pack_assembled lands payload bytes into a TransferRing
        # slot with memcpy only — the whole learner-host pack cost
        # collapses to the fan-in concat. The spec handed to the broker
        # is derived FROM the fused layout, so a shard whose template
        # disagrees fails the layout_crc handshake at connect, never
        # mid-batch.
        self._assemble_spec = None
        if self._staging_cfg.assemble:
            if fused_io is None:
                raise ValueError(
                    "staging.assemble requires the fused H2D path: the "
                    "assembled rows ARE the transfer layout (build the "
                    "learner with fused staging)"
                )
            if self._staging_cfg.pack_workers > 1:
                raise ValueError(
                    "staging.assemble replaces the host pack pool (the "
                    "learner-side pack is concat-only) — set "
                    "staging.pack_workers=1"
                )
            enable = getattr(broker, "enable_assembled_consume", None)
            if enable is None:
                raise ValueError(
                    "staging.assemble needs a broker that serves DTB1 "
                    "blocks (transport.fabric.FabricBroker over tcp:// "
                    "shards running --broker.assemble)"
                )
            from dotaclient_tpu.transport.serialize import (
                BlockSpec,
                deserialize_block,
                serialize_block,
            )

            spec = BlockSpec(
                seq_len=cfg.seq_len,
                lstm_hidden=cfg.policy.lstm_hidden,
                with_aux=cfg.policy.aux_heads,
                obs_bf16=(
                    cfg.stage_obs_compute_dtype
                    and cfg.policy.dtype == "bfloat16"
                ),
                row_bytes=fused_io.row_bytes,
                layout_crc=fused_io.layout.layout_crc,
            )
            enable(spec)
            self._assemble_spec = spec
            # Snapshot codec: a pending AssembledRow checkpoints as a
            # 1-row DTB1 block (payload + full sidecar), so restored
            # rows re-enter the same memcpy landing unchanged.
            self._item_encode = lambda row: serialize_block(spec, [row])
            self._item_decode = lambda b: deserialize_block(b)[1][0]
        # Replay reservoir (dotaclient_tpu/replay/): owned and touched by
        # the consumer thread only, same single-writer discipline as
        # _pending. Payloads match the pending-item type — raw frame
        # bytes on the native path, Rollout objects on the python path —
        # so sampled entries re-enter the SAME packer unchanged.
        self._reservoir = None
        self._replay_target = 0
        if cfg.replay.enabled:
            if fused_io is not None:
                raise ValueError(
                    "replay reservoir and fused H2D staging are mutually "
                    "exclusive: the behavior_staleness stamp is not part of "
                    "the fused transfer layout (the Learner builds "
                    "the tree-path train step when replay.enabled)"
                )
            if cfg.replay.max_staleness <= cfg.ppo.max_staleness:
                raise ValueError(
                    f"replay.max_staleness={cfg.replay.max_staleness} must "
                    f"exceed ppo.max_staleness={cfg.ppo.max_staleness} — a "
                    f"smaller window can never retain a frame the fresh "
                    f"filter would drop"
                )
            from dotaclient_tpu.replay import ReplayReservoir

            if self._lib is not None:
                enc = dec = None  # native items ARE serialized frames
            else:
                from dotaclient_tpu.transport.serialize import serialize_rollout

                enc, dec = serialize_rollout, deserialize_rollout
            self._reservoir = ReplayReservoir(cfg.replay, encode=enc, decode=dec, seed=cfg.seed)
            # Cap at B-1: every batch keeps at least one fresh row, so
            # batch formation always drains the broker and the loop can
            # never spin on a reservoir-only diet.
            self._replay_target = min(
                int(round(cfg.batch_size * cfg.replay.ratio)), cfg.batch_size - 1
            )
        # actor heartbeats: actor_id → last time a frame from it arrived
        # (written only by the consumer thread; stats() reads a snapshot)
        self._actor_seen: Dict[int, float] = {}
        self.heartbeat_window_s = 60.0
        # Poison-frame quarantine: a bounded dead-letter ring of frames
        # that failed parse or per-frame layout validation. Before this
        # ring, a poison frame was a `dropped_bad` tick and GONE — no
        # way to tell a corrupt wire from a misbuilt actor from a fuzzer
        # after the fact. Entries keep the evidence (reason + length +
        # header-prefix hex) bounded; the flight recorder dumps the ring
        # as a section on any fatal. Written only by the consumer
        # thread, same single-writer discipline as _pending.
        self._quarantine: collections.deque = collections.deque(maxlen=64)
        if recorder is not None:
            recorder.add_section("staging_quarantine", self.quarantine)
        self._stats_lock = threading.Lock()
        self._stats = {
            "consumed": 0,
            "dropped_stale": 0,
            "dropped_bad": 0,
            "quarantined": 0,
            "batches": 0,
            "rows_packed": 0,
            "rows_replayed": 0,
            "episode_return_sum": 0.0,
            "episodes": 0,
            "consumer_errors": 0,
            # Experience-wire meters (the DTR3 quantized-wire rollout):
            # cumulative serialized bytes entering the intake, and frames
            # split by the wire dtype of their float obs leaves. The
            # learner re-emits these as the registry-pinned wire_*_total
            # scalars — the fleetwide "who has flipped to bf16" gauge a
            # consumers-first rolling upgrade is steered by.
            "wire_bytes": 0,
            "wire_frames_obs_bf16": 0,
            "wire_frames_obs_f32": 0,
        }
        if self._staging_cfg.pack_workers > 1 or self._assemble_spec is not None:
            # Parallel-feed meters, present ONLY in pool or assembled
            # mode so default runs emit no new scalars (stats() copies
            # this dict and the learner re-emits pack_* as the
            # registry-pinned staging_pack_* family). In assembled mode
            # pack_wall_s measures the concat-only landing — the
            # headline "host pack CPU collapsed" number.
            self._stats["pack_wall_s"] = 0.0
            self._stats["pack_ring_wait_s"] = 0.0

    @property
    def native(self) -> bool:
        return self._lib is not None

    # -- consumer thread -------------------------------------------------

    def start(self) -> "StagingBuffer":
        # restartable: a prior stop() leaves _stop set — clear it so
        # phased drivers (train N steps → eval → train again, e.g.
        # scripts/train_north_star.py) can reuse one buffer
        self._stop.clear()
        self._quiesce.clear()
        if self._staging_cfg.pack_workers > 1:
            # Pool mode: fresh intake/pool/ring per start — stop() joins
            # the old threads, and ring slots may still be leased by a
            # finished learner loop, so reuse would alias live buffers.
            self._intake = queue.Queue(maxsize=4)
            self._pool = _PackPool(self._staging_cfg.pack_workers)
            if self._fused_io is not None:
                self._ring = self._fused_io.make_ring(self._staging_cfg.transfer_depth)
                self._slot_plans = {}  # plans point into the OLD ring's buffers
            self._assembler = threading.Thread(
                target=self._run_assembler, daemon=True, name="staging-assembler"
            )
            self._assembler.start()
            self._thread = threading.Thread(
                target=self._run_pop, daemon=True, name="staging-consumer"
            )
            self._thread.start()
            return self
        if self._assemble_spec is not None:
            # Assembled intake lands rows into ring slots even on the
            # single-consumer path (memcpy of batch N+1 overlaps the H2D
            # of batch N; lease protocol identical to pool mode). Fresh
            # ring per start — a finished learner loop may still hold a
            # lease on an old slot, exactly the pool-mode hazard.
            self._ring = self._fused_io.make_ring(self._staging_cfg.transfer_depth)
        self._thread = threading.Thread(target=self._run, daemon=True, name="staging-consumer")
        self._thread.start()
        return self

    def _die_on_layout(self, e: BaseException) -> None:
        """Persistent builder/staging config disagreement: crash the
        consumer LOUDLY (ADVICE r5 item 1). The learner-side getters
        re-raise _fatal so the failure is fast, not a silent per-batch
        dropped_bad starvation."""
        _log.critical("staging layout/config mismatch; consumer dying: %s", e)
        if self._recorder is not None:
            # Soak/nightly BatchLayoutError deaths were unreproducible —
            # dump the recent pipeline events (incl. the offending
            # chunks' trace hops) before dying.
            self._recorder.record("batch_layout_error", error=str(e))
            self._recorder.dump("batch_layout_error")
        self._fatal = e
        self._stop.set()

    def _pack_pending_loop(self, B: int) -> None:
        """Pack as many full batches as _pending affords into the ready
        queue. Runs on the consumer thread (classic) or the assembler
        thread (pool mode) — the thread that owns _pending either way."""
        while not self._stop.is_set():
            with self._mutate_lock:
                items, staleness, traces = self._next_batch_items(B)
                # In-flight marker, set under the SAME lock hold that
                # popped the frames: between here and the ready-queue put
                # the batch lives only in this thread's locals, and a
                # quiesced drained() that ignored it would let a SIGTERM
                # drain stop one batch early — silently losing popped
                # frames.
                self._packing = items is not None
            if items is None:
                break
            t_pack = time.perf_counter()
            try:
                with span("staging.pack"):
                    batch, payload, lease = self._pack(items)
            except BatchLayoutError:
                # layout/config mismatch: fails every batch, not this
                # batch — propagate to the fatal handler in the caller
                raise
            except _StagingStopped:
                # stop() landed mid-batch (ring acquire / pool join
                # interrupted): not a frame error, just exit
                self._packing = False
                break
            except ValueError:
                # a frame passed ingest validation but failed the
                # packer — drop the batch, never livelock on it
                _log.exception("packer rejected a batch; dropping %d frames", len(items))
                with self._stats_lock:
                    self._stats["dropped_bad"] += len(items)
                self._packing = False
                continue
            if staleness is not None:
                batch = batch._replace(
                    behavior_staleness=np.asarray(staleness, np.float32)
                )
            if self._tracer is not None and traces is not None:
                self._tracer.hop_batch("pack", traces)
            with self._stats_lock:
                self._stats["batches"] += 1
                self._stats["rows_packed"] += len(items)
                if "pack_wall_s" in self._stats:
                    self._stats["pack_wall_s"] += time.perf_counter() - t_pack
                if staleness is not None:
                    self._stats["rows_replayed"] += sum(1 for s in staleness if s > 0)
            # The time in here is this thread blocked on the full ready
            # queue: the lane and the loop pushing back.
            with span("staging.ready_wait"):
                while not self._stop.is_set():
                    try:
                        self._ready.put((batch, payload, traces, lease), timeout=0.2)
                        break
                    except queue.Full:
                        continue
            self._packing = False  # batch visible in _ready (or dead with _stop)

    def _drain_residual(self, max_items: int, sink) -> None:
        """Quiesced-mode residual drain shared by the classic consumer
        and the pool-mode pop thread: fetch the fabric fan-in residual
        (already-popped frames) and hand it to `sink`, pacing the loop
        in place of the consume timeout. _popping makes the locals-held
        residual visible to drained() — between the fabric queue and the
        sink the frames live only in this thread's locals. The flag
        region covers ONLY the non-blocking fetch+sink — the pacing
        sleep must run with it clear, or the drain's drained() polls
        livelock against a flag that is true for 99% of every loop
        iteration."""
        with self._mutate_lock:
            self._popping = True
        try:
            frames = self._residual_frames(max_items)
            if frames:
                sink(frames)
        finally:
            with self._mutate_lock:
                self._popping = False
        if frames is None:
            time.sleep(0.02)

    def _run(self) -> None:
        """Classic single consumer thread (pack_workers=1): pop → parse →
        pack, all here — byte-for-byte the pre-pool behavior."""
        spans.name_thread("staging-consumer")
        B = self.cfg.batch_size

        def _ingest_sink(frames):
            with self._mutate_lock, span("staging.ingest"):
                self._ingest(frames)

        while not self._stop.is_set():
            try:
                if self._quiesce.is_set():
                    # Draining: no new broker pops; ingest any fabric
                    # fan-in residual (already-popped frames) and pack
                    # out what is pending (flag/pacing protocol in
                    # _drain_residual).
                    self._drain_residual(B, _ingest_sink)
                    frames = None
                else:
                    with span("staging.pop"):
                        frames = self.broker.consume_experience(max_items=B, timeout=0.2)
                if frames:
                    _ingest_sink(frames)
                self._pack_pending_loop(B)
            except BatchLayoutError as e:
                self._die_on_layout(e)
                raise
            except Exception:
                # The consumer thread must never die silently — a dead
                # consumer hangs the learner in get_batch forever.
                _log.exception("staging consumer error; continuing")
                with self._stats_lock:
                    self._stats["consumer_errors"] += 1

    def _run_pop(self) -> None:
        """Pool-mode pop thread: drain the broker into the intake queue
        and NOTHING else — broker pops never sit behind parse or pack
        (the single-consumer serialization the parallel feed removes).
        The intake bound (4 drains) is the backpressure that stops an
        outrun learner from buffering the broker into learner RAM."""
        spans.name_thread("staging-consumer")
        B = self.cfg.batch_size

        def _intake_sink(frames):
            while not self._stop.is_set():
                try:
                    self._intake.put(frames, timeout=0.2)
                    break
                except queue.Full:
                    continue

        while not self._stop.is_set():
            try:
                if self._quiesce.is_set():
                    # Same residual drain as the classic consumer: the
                    # fabric's already-popped frames flow on to the
                    # intake queue; the assembler ingests them as usual
                    # (flag/pacing protocol in _drain_residual).
                    self._drain_residual(B, _intake_sink)
                    continue
                with self._mutate_lock:
                    # drained() must account a drain held in this
                    # thread's locals between pop and intake put — the
                    # same visibility contract as _packing.
                    self._popping = True
                try:
                    with span("staging.pop"):
                        frames = self.broker.consume_experience(max_items=B, timeout=0.2)
                    if frames:
                        _intake_sink(frames)
                finally:
                    with self._mutate_lock:
                        self._popping = False
            except Exception:
                _log.exception("staging pop error; continuing")
                with self._stats_lock:
                    self._stats["consumer_errors"] += 1

    def _run_assembler(self) -> None:
        """Pool-mode assembler: the single-writer owner of _pending, the
        reservoir, heartbeats, and quarantine (the whole consumer role
        minus the broker pop). Parses each intake drain (the batched C
        header parse releases the GIL, so this genuinely overlaps the
        pop thread and the pack workers), forms batches, and dispatches
        row-sharded packs to the worker pool."""
        spans.name_thread("staging-assembler")
        B = self.cfg.batch_size
        while not self._stop.is_set():
            try:
                try:
                    frames = self._intake.get(timeout=0.2)
                except queue.Empty:
                    frames = None
                if frames is not None:
                    try:
                        with self._mutate_lock, span("staging.ingest"):
                            self._ingest(frames)
                    finally:
                        # unfinished_tasks hits 0 only after the frames
                        # are visible in _pending — the drained() handoff
                        self._intake.task_done()
                self._pack_pending_loop(B)
            except BatchLayoutError as e:
                self._die_on_layout(e)
                raise
            except Exception:
                _log.exception("staging assembler error; continuing")
                with self._stats_lock:
                    self._stats["consumer_errors"] += 1

    def _take_pending(self, n: int):
        """Pop the first n pending items (+ their trace refs when the
        tracer maintains the parallel list)."""
        items = self._pending[:n]
        del self._pending[:n]
        traces = None
        if self._tracer is not None:
            traces = self._pending_traces[:n]
            del self._pending_traces[:n]
        return items, traces

    def _next_batch_items(self, B: int):
        """(items, staleness-list-or-None, trace-refs-or-None) for one
        batch, or (None, None, None) when not enough material is pending.
        Replay mode fills up to `replay.ratio` of the batch from the
        reservoir — never blocking on it (a short reservoir just means
        more fresh rows) — and stamps per-row behavior-policy staleness;
        fresh rows stamp 0."""
        if self._reservoir is None:
            if len(self._pending) < B:
                return None, None, None
            items, traces = self._take_pending(B)
            return items, None, traces
        now_v = self.version_fn()
        self._reservoir.expire(now_v)
        k = min(self._replay_target, self._reservoir.occupancy)
        if len(self._pending) < B - k:
            return None, None, None
        items, traces = self._take_pending(B - k)
        staleness = [0.0] * len(items)
        for payload, version, meta in self._reservoir.sample(k, now_v):
            items.append(payload)
            staleness.append(float(max(now_v - version, 0)))
            if self._tracer is not None:
                ref = None
                if meta is not None:
                    # Fresh per-re-emit TraceRef COPY: a resident entry can
                    # be sampled into several in-flight batches (classic
                    # PER reuse, max_replays), and the learner thread hops
                    # each batch's refs concurrently with this thread —
                    # sharing one mutable ref would race on last_t and
                    # corrupt the very histograms replay debugging needs.
                    # The resident meta keeps its admit-time last_t, so
                    # every re-emit measures time-in-reservoir.
                    ref = TraceRef(meta.trace_id, meta.birth, last_t=meta.last_t)
                    self._tracer.hop("replay_reemit", ref)
                traces.append(ref)
        return items, staleness, traces

    def _pack(self, items: List):
        """(TrainBatch, payload-or-None, lease-or-None). Fused mode packs
        straight into leaf views of the u8 transfer buffer (no regroup
        copy later); dense mode matches the original layout.
        Pool mode (pack_workers > 1) row-shards the same copy across the
        worker pool — bitwise identical output for any split — and in
        fused mode targets a TransferRing slot, returned as the lease."""
        if self._assemble_spec is not None:
            return self._pack_assembled(items)
        # Fuse the compute-dtype obs cast into the copy when staging
        # targets bf16 (bitwise equal to the separate numpy astype pass
        # it replaces; ~1.1ms/batch at flagship shapes).
        obs_bf16 = (
            self.cfg.stage_obs_compute_dtype and self.cfg.policy.dtype == "bfloat16"
        )
        if self._pool is not None:
            return self._pack_sharded(items, obs_bf16)
        if self._fused_io is not None:
            # payload: the ONE u8 transfer buffer the views live in —
            # opaque here; the learner ships it with io.sharding
            payload, out = self._fused_io.alloc_transfer()
            if self._lib is not None:
                from dotaclient_tpu import native

                native.pack_frames(
                    self._lib,
                    items,
                    self.cfg.seq_len,
                    self.cfg.policy.lstm_hidden,
                    self.cfg.policy.aux_heads,
                    obs_bf16=obs_bf16,
                    out=out,
                )
            else:
                # numpy handles the strided views (and the f32→bf16
                # assignment cast) transparently; no post-cast — it
                # would detach the leaves from the transfer buffers.
                fill_rollouts(out, items, self.cfg.seq_len)
            return out, payload, None
        if self._lib is not None:
            from dotaclient_tpu import native

            batch = native.pack_frames(
                self._lib,
                items,
                self.cfg.seq_len,
                self.cfg.policy.lstm_hidden,
                self.cfg.policy.aux_heads,
                obs_bf16=obs_bf16,
            )
            if obs_bf16:
                return batch, None, None  # cast already applied in-copy
            return cast_obs_to_compute_dtype(self.cfg, batch), None, None
        batch = pack_rollouts(items, self.cfg.seq_len, self.cfg.policy.aux_heads)
        return cast_obs_to_compute_dtype(self.cfg, batch), None, None

    def _pack_sharded(self, items: List, obs_bf16: bool):
        """Pool-mode pack: N workers each fill a disjoint contiguous row
        range of ONE output buffer (native: dt_pack_batch row_offset,
        GIL released; python: fill_rollouts row_offset). Fused mode
        targets a re-zeroed TransferRing slot — returned as the lease
        the learner releases once the device_put retires; dense mode
        allocates fresh (exactly the classic layout/cast semantics)."""
        B = len(items)
        T = self.cfg.seq_len
        H = self.cfg.policy.lstm_hidden
        aux = self.cfg.policy.aux_heads
        lease = None
        if self._fused_io is not None:
            t0 = time.perf_counter()
            slot = None
            while slot is None:
                if self._stop.is_set():
                    raise _StagingStopped()
                # Ring backpressure: every slot packing/ready/in-transfer.
                slot = self._ring.acquire(timeout=0.2)
            with self._stats_lock:
                self._stats["pack_ring_wait_s"] += time.perf_counter() - t0
            out, payload, lease = slot.batch, slot.payload, slot
            if self._lib is not None:
                # Ring slots are long-lived: the per-shard ctypes glue
                # (20-leaf stride validation + 24 pointer marshals,
                # ~0.06 ms GIL-held per call) is identical every batch —
                # prebuild one PackPlan per (slot, shard) and pay only
                # the frame-pointer marshal per call (native.PackPlan).
                plans = self._slot_plans.get(slot.index)
                if plans is None:
                    from dotaclient_tpu import native

                    plans = [
                        native.PackPlan(
                            self._lib, out, cnt, T, H, aux, obs_bf16, off, B
                        )
                        for off, cnt in shard_rows(B, self._pool.n)
                    ]
                    self._slot_plans[slot.index] = plans
                err = self._pool.run_tasks(
                    [
                        (lambda p=p: p.pack(items[p.row_offset : p.row_offset + p.n]))
                        for p in plans
                    ],
                    self._stop,
                )
                if err is not None:
                    lease.release()
                    raise err
                return out, payload, lease
        else:
            payload = None
            obs_dtype = None
            if obs_bf16 and self._lib is not None:
                import ml_dtypes

                obs_dtype = ml_dtypes.bfloat16
            from dotaclient_tpu.ops.batch import zeros_train_batch

            out = zeros_train_batch(B, T, H, aux, obs_dtype=obs_dtype)
        if self._lib is not None:
            from dotaclient_tpu import native

            lib = self._lib

            def task(off, cnt):
                native.pack_frames(
                    lib, items[off : off + cnt], T, H, aux,
                    obs_bf16=obs_bf16, out=out, row_offset=off, total_rows=B,
                )
        else:

            def task(off, cnt):
                fill_rollouts(out, items[off : off + cnt], T, row_offset=off)

        err = self._pool.run_sharded(task, shard_rows(B, self._pool.n), self._stop)
        if err is not None:
            if lease is not None:
                # failed batch: the slot goes straight back to free —
                # nothing downstream will ever release it
                lease.release()
            raise err
        if self._fused_io is not None:
            return out, payload, lease
        if self._lib is not None and obs_bf16:
            return out, None, None  # cast applied in-copy
        return cast_obs_to_compute_dtype(self.cfg, out), None, None

    def _pack_assembled(self, items: List):
        """Assembled-intake landing: every pending item is an
        AssembledRow whose payload already holds the exact RowLayout
        bytes, so "packing" a batch is a ring-slot acquire plus one
        C-level row concat and one bulk copy — no parse, no per-field
        scatter, no cast.
        Bitwise identical to the classic pack of the same wire
        frames: the shard ran the SAME row encoder over the SAME bytes
        (scripts/ab_inet_pack.py pins this, INET_PACK_AB.json)."""
        t0 = time.perf_counter()
        slot = None
        while slot is None:
            if self._stop.is_set():
                raise _StagingStopped()
            # Ring backpressure: every slot ready or in transfer.
            slot = self._ring.acquire(timeout=0.2)
        with self._stats_lock:
            self._stats["pack_ring_wait_s"] += time.perf_counter() - t0
        payload = slot.payload
        n_rows = len(items)
        # One C-level concat of the row payloads into a [rows, row_bytes]
        # matrix (b"".join is a single allocation+memcpy pass), then
        # bulk-land it — per-row python slicing costs more than the pack
        # it replaces at B=256 (the AB's landing-strategy measurement).
        raw = np.frombuffer(
            b"".join(row.payload for row in items), np.uint8
        ).reshape(n_rows, self._fused_io.row_bytes)
        payload[:n_rows] = raw
        return slot.batch, payload, slot

    def _parse(self, frame: bytes):
        """PYTHON-fallback frame parse → ((Rollout, version, L, H,
        actor_id, ep_return, last_done), None) or (None, reason) if
        malformed — reason is the quarantine label ("dtype_map" for a
        DTR3 dtype-map failure, "parse" otherwise). The native path
        never comes through here — _ingest parses a whole drain in one
        `native.frame_headers` call and keeps raw frame bytes for the C
        packer."""
        try:
            r = deserialize_rollout(frame)
        except WireDtypeError:
            return None, "dtype_map"
        except (ValueError, KeyError):
            return None, "parse"
        last_done = float(r.dones[-1]) if r.length else 0.0
        return (
            r,
            r.version,
            r.length,
            r.initial_state[0].shape[-1],
            r.actor_id,
            r.episode_return,
            last_done,
        ), None

    def _offer_replay(
        self, item, frame: bytes, version: int, current_version: int, ref=None
    ) -> bool:
        """Consumer-thread-only: admit one would-be-stale item into the
        reservoir. Priority is the PER |TD-error| proxy computed from the
        actor-stamped behavior values — the native path pays a full
        deserialize here, but only for frames that were pure waste
        before, so any admitted frame is recovered value. `ref` (the
        chunk's TraceRef) rides the reservoir entry as opaque meta so a
        later re-emit can keep the hop chain going."""
        try:
            rollout = item if isinstance(item, Rollout) else deserialize_rollout(frame)
        except (ValueError, KeyError):
            return False
        from dotaclient_tpu.replay import td_error_priority

        priority = td_error_priority(
            rollout.rewards, rollout.behavior_value, rollout.dones, self.cfg.ppo.gamma
        )
        admitted = self._reservoir.offer(
            item, version, priority, len(frame), current_version, meta=ref
        )
        if admitted and ref is not None and self._tracer is not None:
            self._tracer.hop("replay_admit", ref)
        return admitted

    def _quarantine_put(self, frame: bytes, reason: str) -> None:
        """Consumer-thread-only: file one poison frame in the dead-letter
        ring. Bounded evidence, not storage: reason + size + the first
        64 bytes as hex (covers the header of every wire layout) — a
        whole corrupt frame can be megabytes and the ring must stay
        O(64) small."""
        self._quarantine.append(
            {
                "t": time.time(),
                "reason": reason,
                "bytes": len(frame),
                "head": bytes(frame[:64]).hex(),
            }
        )
        if self._recorder is not None:
            self._recorder.record(
                "staging_quarantine", reason=reason, size=len(frame)
            )

    def quarantine(self) -> List[dict]:
        """Snapshot of the dead-letter ring (newest last). One GIL-atomic
        deque copy; the flight recorder dumps this as a section."""
        return list(self._quarantine)  # graftlint: disable=THR001(one GIL-atomic deque-snapshot copy; appends live in _ingest on the sole writer thread)

    def _ingest_assembled(self, rows: List) -> None:
        """Assembled-intake twin of _ingest: items are AssembledRows the
        fabric fan-in already fence-checked, so admission here is pure
        sidecar bookkeeping — staleness filter on the shard-stamped
        version, episode accounting from the last_done row flag, trace
        hops from the sidecar ids, heartbeats from actor_id. No parse:
        a row that reached this host was already validated (and its
        layout_crc handshake pinned) by the shard; the one defensive
        check left is the payload length, which dead-letters under the
        classic "layout" reason rather than poisoning the memcpy."""
        version_now = self.version_fn()
        min_version = version_now - self.cfg.ppo.max_staleness
        spec = self._assemble_spec
        consumed = len(rows)
        dropped_stale = dropped_bad = quarantined = episodes = 0
        ep_ret = 0.0
        now = time.monotonic()
        tr = self._tracer
        wire_bytes = 0
        wire_bf16 = wire_f32 = 0
        for row in rows:
            wire_bytes += len(row.payload)
            if len(row.payload) != spec.row_bytes:
                dropped_bad += 1
                quarantined += 1
                self._quarantine_put(row.payload, "layout")
                continue
            # The wire dtype is a block-level fact in assembled mode
            # (every row of a block shares the negotiated layout), but
            # the fleetwide bf16-rollout gauges must keep counting.
            if spec.obs_bf16:
                wire_bf16 += 1
            else:
                wire_f32 += 1
            self._actor_seen[row.actor_id] = now
            if len(self._actor_seen) > 4096:
                cutoff = now - self.heartbeat_window_s
                self._actor_seen = {
                    a: t for a, t in self._actor_seen.items() if t >= cutoff
                }
            ref = None
            if tr is not None and (row.trace_id or row.birth_time):
                ref = TraceRef(row.trace_id, row.birth_time)
                # covers serialize + shard assembly + block wire
                tr.hop("consume", ref)
            if row.version < min_version:
                dropped_stale += 1
                continue
            if row.last_done:
                episodes += 1
                ep_ret += row.episode_return
            self._pending.append(row)
            if tr is not None:
                if ref is not None:
                    tr.hop("staging_admit", ref)
                self._pending_traces.append(ref)
        with self._stats_lock:
            self._stats["consumed"] += consumed
            self._stats["dropped_stale"] += dropped_stale
            self._stats["dropped_bad"] += dropped_bad
            self._stats["quarantined"] += quarantined
            self._stats["episodes"] += episodes
            self._stats["episode_return_sum"] += ep_ret
            self._stats["wire_bytes"] += wire_bytes
            self._stats["wire_frames_obs_bf16"] += wire_bf16
            self._stats["wire_frames_obs_f32"] += wire_f32

    def _ingest(self, frames: List[bytes]) -> None:
        if self._assemble_spec is not None:
            return self._ingest_assembled(frames)
        version_now = self.version_fn()
        min_version = version_now - self.cfg.ppo.max_staleness
        H = self.cfg.policy.lstm_hidden
        consumed = len(frames)
        dropped_stale = dropped_bad = quarantined = episodes = 0
        ep_ret = 0.0
        now = time.monotonic()
        tr = self._tracer
        wire_bytes = sum(len(f) for f in frames)
        wire_bf16 = wire_f32 = 0
        # Rolling-upgrade intake for the native path: trace-stamped DTR2
        # frames are normalized here to the byte-identical DTR1 layout
        # the C packer speaks (transport.serialize.strip_rollout_trace),
        # independent of whether THIS process traces — a consumer must
        # parse every producer's frames mid-roll. Quantized DTR3 frames
        # pass through WHOLE (the C packer parses the dtype-map itself —
        # stripping would change the array encoding); only their
        # dtype-map is pre-checked here, in constant time per frame, so
        # a truncated/corrupt map dead-letters under its own "dtype_map"
        # reason instead of the generic native parse failure. An
        # all-DTR1 drain (the default-off fleet) pays one 4-byte prefix
        # check per frame and keeps the exact frame objects (no copies —
        # asserted in tests/test_obs.py). The python fallback needs
        # none of this: deserialize_rollout speaks all three magics.
        frame_traces: Optional[List] = None
        bad_maps: Dict[int, bytes] = {}
        if self._lib is not None:
            for i, f in enumerate(frames):
                pfx = f[:4]
                if pfx == b"DTR2":
                    if tr is not None:
                        if frame_traces is None:
                            frame_traces = [None] * consumed
                        tid, birth = peek_rollout_trace(f)
                        frame_traces[i] = TraceRef(tid, birth)
                    frames[i] = strip_rollout_trace(f)
                elif pfx == b"DTR3":
                    if check_dtr3_dtype_map(f) is not None:
                        # Keep the original bytes as quarantine evidence;
                        # the emptied slot fails the native header parse
                        # below, which routes it to the poison branch.
                        bad_maps[i] = f
                        frames[i] = b""
                    elif tr is not None:
                        tid, birth = peek_rollout_trace(f)
                        if tid or birth:
                            if frame_traces is None:
                                frame_traces = [None] * consumed
                            frame_traces[i] = TraceRef(tid, birth)
            # ONE ctypes call parses/validates every frame of the drain
            # (the per-frame FFI loop cost 1.3ms/batch at 256 frames —
            # r5 profile); the python loop below then touches only plain
            # ints/floats.
            from dotaclient_tpu import native

            ok, versions, Ls, Hs, _flags, actor_ids, ep_rets, last_dones = (
                native.frame_headers(self._lib, frames)
            )
            parsed_iter = (
                (
                    (frames[i], versions[i], Ls[i], Hs[i], actor_ids[i], ep_rets[i], last_dones[i])
                    if ok[i]
                    else None,
                    "dtype_map" if i in bad_maps else "parse",
                )
                for i in range(consumed)
            )
        else:
            parsed_iter = (self._parse(f) for f in frames)
        for i, (parsed, bad_reason) in enumerate(parsed_iter):
            if parsed is None:
                # Poison frame (bad magic, truncated arrays, corrupt
                # header, unsupported dtype-map): dead-letter it WITH
                # evidence instead of only ticking a counter.
                dropped_bad += 1
                quarantined += 1
                self._quarantine_put(bad_maps.get(i, frames[i]), bad_reason)
                continue
            item, version, L, frame_h, actor_id, frame_ret, last_done = parsed
            # Wire-dtype meter: native items are raw frame bytes (magic +
            # map byte check), python items are Rollouts (leaf dtype).
            if (
                wire_obs_is_bf16(item)
                if not isinstance(item, Rollout)
                else rollout_obs_bf16(item)
            ):
                wire_bf16 += 1
            else:
                wire_f32 += 1
            self._actor_seen[actor_id] = now  # heartbeat (consumer thread only)
            # Prune long-gone ids here, on the sole writer thread, so the
            # dict stays bounded without stats() ever mutating shared state.
            if len(self._actor_seen) > 4096:
                cutoff = now - self.heartbeat_window_s
                self._actor_seen = {
                    a: t for a, t in self._actor_seen.items() if t >= cutoff
                }
            # Per-frame config validation happens HERE so one misconfigured
            # actor can only ever cost its own frames, never the pack step.
            if L > self.cfg.seq_len or frame_h != H:
                dropped_bad += 1
                quarantined += 1
                self._quarantine_put(frames[i], "layout")
                continue
            ref = None
            if tr is not None:
                if frame_traces is not None:
                    ref = frame_traces[i]
                elif isinstance(item, Rollout) and item.traced:
                    # python fallback: the trace rode through deserialize
                    ref = TraceRef(item.trace_id, item.birth_time)
                if ref is not None:
                    # covers serialize + broker queueing + the wire
                    tr.hop("consume", ref)
            if version < min_version:
                # Pre-replay behavior: pure waste (dropped_stale). With
                # the reservoir on, near-stale frames are retained for
                # off-policy reuse instead; the reservoir itself rejects
                # anything past replay.max_staleness (still a stale drop).
                if self._reservoir is not None and self._offer_replay(
                    item, frames[i], version, version_now, ref=ref
                ):
                    continue
                dropped_stale += 1
                continue
            if L and last_done > 0:
                episodes += 1
                ep_ret += frame_ret
            self._pending.append(item)
            if tr is not None:
                if ref is not None:
                    tr.hop("staging_admit", ref)
                self._pending_traces.append(ref)
        with self._stats_lock:
            self._stats["consumed"] += consumed
            self._stats["dropped_stale"] += dropped_stale
            self._stats["dropped_bad"] += dropped_bad
            self._stats["quarantined"] += quarantined
            self._stats["episodes"] += episodes
            self._stats["episode_return_sum"] += ep_ret
            self._stats["wire_bytes"] += wire_bytes
            self._stats["wire_frames_obs_bf16"] += wire_bf16
            self._stats["wire_frames_obs_f32"] += wire_f32

    # -- learner side ----------------------------------------------------

    def _check_fatal(self) -> None:
        # Single atomic read: _fatal is rebound once by the dying consumer
        # thread; binding it to a local means the check and the raise can
        # never observe two different values of the attribute.
        fatal = self._fatal
        if fatal is not None:
            raise RuntimeError(
                "staging consumer died on a layout/config mismatch — every "
                "batch would fail; fix the builder/staging config disagreement"
            ) from fatal

    def _get_ready(self, timeout: Optional[float], cancel=None):
        """queue.get that stays responsive to a consumer death: waits in
        short slices and re-checks _fatal between them, so a learner
        already blocked when the consumer dies on a BatchLayoutError
        fails within ~0.2s instead of sitting out its full batch timeout
        against a queue nothing will ever fill again. `cancel` (an
        Event) aborts the wait within one slice — the prefetch lane's
        teardown hook, so a stopping lane never sits out a full batch
        timeout (and never overlaps a successor lane's pops)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            self._check_fatal()
            if cancel is not None and cancel.is_set():
                raise queue.Empty
            if self._quiesce.is_set() and self.drained(include_prefetch=False):
                # SIGTERM drain: nothing left to pack and nothing queued —
                # waiting out the full batch timeout would only burn the
                # drain budget against a queue nothing will ever fill.
                # UPSTREAM stations only: the caller here IS the consumer
                # (the learner's prefetch lane), and its own
                # mid-fetch _inflight flag covers this very wait — the
                # full-station drained() would read False forever and the
                # fast-exit would never fire, burning the whole
                # batch_timeout of the k8s drain budget (review catch;
                # regression-pinned in test_pipeline). Anything already
                # past this pop (handoff queue) is trained out by the
                # loop regardless — the "exhausted" sentinel lands
                # FIFO-last.
                raise queue.Empty
            if deadline is None:
                step = 0.2
            else:
                step = min(0.2, deadline - time.monotonic())
                if step <= 0:
                    raise queue.Empty
            try:
                return self._ready.get(timeout=step)
            except queue.Empty:
                continue

    def get_batch(
        self, timeout: Optional[float] = None, cancel=None
    ) -> Optional[TrainBatch]:
        """One packed batch (or None on timeout). On the ring path
        (pack_workers > 1 with fused_io) the batch's leaves are views
        into a leased ring slot — the caller must release
        `last_batch_lease` once done, exactly like get_batch_groups, or
        the ring stalls after transfer_depth batches."""
        try:
            item = self._get_ready(timeout, cancel=cancel)
        except queue.Empty:
            self.last_batch_lease = None
            return None
        self.last_batch_lease = item[3]
        return item[0]

    def get_batch_groups(self, timeout: Optional[float] = None, cancel=None):
        """(TrainBatch, payload) — `payload` is the ready-to-ship u8
        transfer buffer when staging was built with fused_io, else None
        (caller falls back to io.pack_transfer). The batch's leaves are
        views into `payload`.

        Classic path (pack_workers=1): every batch allocates fresh
        buffers, so no aliasing hazard. Ring path (pack_workers>1):
        `payload` is a leased TransferRing slot — the caller must release
        `self.last_batch_lease` AFTER the device_put of `payload` has
        retired (jax.block_until_ready), at which point the slot may be
        re-zeroed and repacked; holding leases is the ring's
        backpressure.

        Side channels: `self.last_batch_trace` is set to the returned
        batch's trace refs (or None) — the learner records the h2d/apply
        hops from it — and `self.last_batch_lease` to the ring lease (or
        None). Single-consumer by contract (only the learner loop pops
        batches), so the attribute reads are race-free."""
        try:
            batch, payload, traces, lease = self._get_ready(timeout, cancel=cancel)
        except queue.Empty:
            self.last_batch_trace = None
            self.last_batch_lease = None
            return None, None
        self.last_batch_trace = traces
        self.last_batch_lease = lease
        return batch, payload

    # -- checkpoint / drain support --------------------------------------

    def _take_snapshot(self) -> dict:
        """Build the serializable staging image: pending (popped but not
        yet packed) frames as wire bytes, in order, plus the reservoir's
        own snapshot. Caller holds _mutate_lock."""
        snap: dict = {"pending": [bytes(self._item_encode(it)) for it in self._pending]}  # graftlint: disable=THR001(caller holds _mutate_lock, the same lock the consumer's two mutation sites hold)
        if self._reservoir is not None:
            snap["reservoir"] = self._reservoir.snapshot()
        return snap

    def snapshot_state(self, timeout: float = 10.0) -> Optional[dict]:
        """Checkpoint-worker side: a consistent image of the staging host
        state for the full-state aux manifest. The mutate lock excludes
        the consumer's two mutation sites, so the cut never contains a
        half-formed batch — whether the consumer is live, stopped, or
        mid-restart (phased drivers stop/start the buffer around every
        run() call). `timeout` bounds the wait against a consumer
        wedged inside a mutation (e.g. a ready-queue put stuck behind a
        stalled learner): the checkpoint degrades to state-only rather
        than stalling durability.

        Pool mode: the cut covers _pending + the reservoir (the
        assembler holds this same lock at both its mutation sites).
        Frames mid-flight in the intake queue are NOT snapshotted —
        bounded by the intake depth (4 drains), the same exposure class
        as the classic path's pop-to-ingest window; the SIGTERM drain is
        unaffected (drained() accounts every upstream station, so a
        drain trains those frames out before the final save)."""
        if not self._mutate_lock.acquire(timeout=timeout):
            return None
        try:
            return self._take_snapshot()
        finally:
            self._mutate_lock.release()

    def restore_state(self, snap: dict) -> Dict[str, int]:
        """PRE-START only (the learner restores in __init__, before any
        consumer thread exists): re-inject checkpointed pending frames —
        ahead of anything the broker will deliver, preserving the exact
        pre-kill batch-formation order — and rebuild the reservoir.
        Returns counts for the resume_* scalars."""
        restored = [self._item_decode(b) for b in snap.get("pending", [])]
        self._pending = restored  # graftlint: disable=THR001(pre-start contract: runs in Learner.__init__ before the consumer thread exists)
        if self._tracer is not None:
            # Restored frames re-enter untraced (TraceRefs are
            # process-local); the parallel list must stay aligned.
            self._pending_traces = [None] * len(restored)
        restored_reservoir = 0
        if self._reservoir is not None and "reservoir" in snap:
            restored_reservoir = self._reservoir.restore(snap["reservoir"])
        return {"pending": len(restored), "reservoir": restored_reservoir}

    def _residual_frames(self, max_items: int):
        """Quiesced-intake residual: frames a fabric broker's fan-in pop
        threads already took OFF the shards before quiesce landed
        (transport/fabric.py consume_residual). They are POPPED frames —
        the PR-7 zero-loss drain contract owns them — so the quiesced
        consumer keeps ingesting them instead of new broker pops. None
        on classic brokers (no such station exists)."""
        residual = getattr(self.broker, "consume_residual", None)
        if residual is None:
            return None
        frames = residual(max_items)
        return frames or None

    def quiesce(self) -> None:
        """Stop popping the broker; keep packing already-pending frames.
        The SIGTERM drain's first act — see _quiesce in __init__. A
        fabric broker quiesces WITH us (its shard pop threads stop
        pulling new frames), and its already-popped residual is drained
        through _residual_frames so no popped frame strands between the
        shards and staging."""
        broker_quiesce = getattr(self.broker, "quiesce", None)
        if broker_quiesce is not None:
            broker_quiesce()
        self._quiesce.set()

    def attach_prefetch_probe(self, probe: Callable[[], bool]) -> None:
        """Register the learner's prefetch-lane station
        (runtime/learner.py PrefetchLane.holding): a callable that is
        True while the lane holds popped-but-untrained frames — in its
        thread locals mid-fetch or in its handoff queue. drained()
        checks it LAST (the lane sits downstream of the ready queue;
        frames only move downstream, the upstream-first rule)."""
        self._prefetch_probe = probe

    def drained(self, include_prefetch: bool = True) -> bool:
        """True once a quiesced buffer can produce no further batch: the
        ready queue is empty and pending holds fewer frames than the
        next batch's fresh-row requirement. Learner-thread gauge reads
        of consumer-owned counters (len/occupancy) are single GIL-atomic
        calls; a one-frame drift only delays the verdict by one poll.

        `include_prefetch=False` is the prefetch lane's OWN exhaustion
        check ("will anything more ever arrive upstream?") — the lane
        must not count its already-delivered holdings against itself, or
        a drain would livelock on the batch the loop is about to train.
        Every external caller keeps the default: the full zero-loss
        verdict includes the lane station."""
        if not self._quiesce.is_set():
            return False
        # Pool mode adds two upstream stations frames can occupy: the
        # pop thread's locals (_popping, the _packing pattern) and the
        # intake queue (unfinished_tasks stays nonzero until the
        # assembler's ingest has made the frames visible in _pending).
        # Check stations UPSTREAM-first — frames only move downstream
        # (pop → intake → pending → in-flight pack → ready), so a frame
        # crossing a boundary mid-check is seen at the later station.
        # A fabric broker adds the MOST upstream station: frames its
        # fan-in threads popped off the shards before quiesce (they are
        # popped — the zero-loss contract owns them; the quiesced
        # consumer drains them via _residual_frames).
        fanin_residual = getattr(self.broker, "fanin_residual", None)
        if fanin_residual is not None and fanin_residual():
            return False
        with self._mutate_lock:
            if self._popping:
                return False
        if self._intake is not None and self._intake.unfinished_tasks:
            return False
        # (packing, pending) must be observed atomically with the
        # consumer's pop — it sets _packing under this same lock hold
        # that empties _pending, so a batch is ALWAYS visible as one of:
        # pending frames, the in-flight flag, or a ready-queue entry.
        # Check _ready LAST (that is the direction batches move).
        with self._mutate_lock:
            if self._packing:
                return False
            need = self.cfg.batch_size
            if self._reservoir is not None:
                need -= min(self._replay_target, self._reservoir.occupancy)
            if len(self._pending) >= need:  # graftlint: disable=THR001(read is under _mutate_lock; the consumer's mutation call sites (_ingest/_next_batch_items in _run) hold the same lock — lexically outside the mutating functions, so the rule cannot see it)
                return False
        if not self._ready.empty():
            return False
        # The most DOWNSTREAM station: a batch the prefetch lane popped
        # off _ready but the loop has not trained yet.
        if include_prefetch:
            probe = self._prefetch_probe
            if probe is not None and probe():
                return False
        return True

    def stats(self) -> Dict[str, float]:
        with self._stats_lock:
            out = dict(self._stats)
        out["ready_batches"] = self._ready.qsize()
        # len() of a list the consumer thread appends/deletes is one
        # GIL-atomic C call; a gauge that drifts by one in-flight frame
        # is acceptable and a lock here would serialize every scrape
        # against the packer.
        out["pending_rollouts"] = len(self._pending)  # graftlint: disable=THR001(one GIL-atomic len read; gauge may drift by one in-flight frame)
        # heartbeat gauge: actors heard from within the window (dict reads
        # are atomic enough; values drift by at most one frame)
        cutoff = time.monotonic() - self.heartbeat_window_s
        # dict() of the consumer-written heartbeat map is a single
        # GIL-atomic snapshot copy; item writes land entirely before or
        # entirely after it.
        seen = dict(self._actor_seen)  # graftlint: disable=THR001(one GIL-atomic dict-copy snapshot; pruning lives in _ingest on the sole writer thread)
        out["active_actors"] = sum(1 for t in seen.values() if t >= cutoff)
        if self._reservoir is not None:
            for k, v in self._reservoir.stats().items():
                out[f"replay_{k}"] = v
            # Fraction of packed rows served from the reservoir — the
            # headline "how much previously-wasted work is being reused".
            out["replay_hit_ratio"] = out["rows_replayed"] / max(out["rows_packed"], 1)
        if self._pool is not None:
            # Parallel-feed scoreboard (staging_pack_* once the learner
            # re-emits them): per-worker busy/stall seconds, ring
            # occupancy, and packer-proper rows/s (rows over the summed
            # per-batch pack walls — the sharded-pack rate itself, not
            # the e2e rate).
            busy, stall, _done = self._pool.meters()
            out["pack_workers"] = float(self._pool.n)
            for i in range(self._pool.n):
                out[f"pack_worker_busy_s_{i}"] = round(busy[i], 4)
                out[f"pack_worker_stall_s_{i}"] = round(stall[i], 4)
            if self._ring is not None:
                out["pack_ring_depth"] = float(self._ring.depth)
                out["pack_ring_occupancy"] = float(self._ring.occupancy)
            out["pack_rows_per_s"] = out["rows_packed"] / max(
                out.get("pack_wall_s", 0.0), 1e-9
            )
        elif self._ring is not None:
            # Assembled intake: ring gauges without a pool (the concat
            # landing runs on the one consumer thread).
            out["pack_ring_depth"] = float(self._ring.depth)
            out["pack_ring_occupancy"] = float(self._ring.occupancy)
        return out

    def stop(self) -> None:
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=5)
        if self._assembler is not None:
            self._assembler.join(timeout=5)
            self._assembler = None
        if self._pool is not None:
            self._pool.stop()
