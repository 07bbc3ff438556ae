"""Learner loop — the re-design of the reference's optimizer.py
(SURVEY.md §2 "Learner", §3.2 call stack).

Reference flow per iteration: consume N rollouts → pad/stack →
teacher-forced re-eval → GAE → PPO step → publish versioned weights →
checkpoint → TensorBoard. Here the device-side middle is ONE compiled
SPMD program over the mesh (parallel/train_step.py) and the host side
is the staging buffer (runtime/staging.py); this module owns the loop:

    staging.get_batch → device_put(dp-sharded) → train_step
    → every publish_every steps: device_get params → weight fanout
    → every checkpoint_every steps: orbax checkpoint
    → metrics (reference scalar names) + steps/s + staleness stats

The python-side `version` counter mirrors state.step without forcing a
device sync every iteration; it is the version actors stamp on their
rollouts and the learner's staleness filter reads.

The feed is two boxes, lane -> loop, and the loop never blocks on the
device except where semantics require it:
- a dedicated PREFETCH LANE thread runs the whole host side of batch
  N+1 — staging pop, pack wait, device_put dispatch, transfer retire,
  ring-lease release — WHILE the device executes train step N, so the
  loop thread's per-iteration host cost is one queue pop plus the async
  train-step dispatch (double buffering with a real second lane, not
  just jax async dispatch). The lane is the one staging consumer and
  pops FIFO, so the loop trains the batches in arrival order
  (tests/test_pipeline.py steps the same batches by hand and holds the
  parameters bitwise);
- a batch crosses to the device as ONE [B, row_bytes] u8 buffer
  (parallel/fused_io.py), unpacked inside the compiled step. Where that
  layout cannot apply — a sequence-parallel mesh, the replay reservoir —
  the Learner takes the per-leaf tree (build_train_step), chosen from
  the mesh and the config; same compiled math;
- metrics are device_get only every `metrics_every` steps (each fetch is
  a full device sync);
- weight publishes dispatch ONE on-device flatten (ParamFlattener) and
  hand the device buffer to a dedicated publisher thread, which pays
  the blocking single-transfer host read + serialize + broker I/O with
  latest-wins coalescing. Stream ordering keeps this safe against the
  train step's state donation (flatten is dispatched first, on the loop
  thread — the lane never touches the state).
"""

from __future__ import annotations

import collections
import contextlib
import logging
import os
import queue
import threading
import time
from typing import NamedTuple, Optional

import jax
import numpy as np

from dotaclient_tpu.config import LearnerConfig
from dotaclient_tpu.obs import spans
from dotaclient_tpu.obs.spans import span, timeline
from dotaclient_tpu.parallel import mesh as mesh_lib
from dotaclient_tpu.parallel.train_step import (
    TrainState,
    build_single_train_step,
    build_train_step,
    init_train_state,
    is_sequence_parallel,
)
from dotaclient_tpu.runtime.metrics import MetricsLogger
from dotaclient_tpu.runtime.staging import StagingBuffer
from dotaclient_tpu.transport import serialize as serialize_mod
from dotaclient_tpu.transport.base import Broker
from dotaclient_tpu.transport.serialize import flatten_params, serialize_weights

_log = logging.getLogger(__name__)


class ParamFlattener:
    """ONE device→host transfer per weight publish instead of one per
    param leaf.

    The flagship params tree has ~30 leaves, and every D2H read pays a
    per-transfer overhead (the same one parallel/fused_io.py removes on
    the H2D side), so a per-leaf device_get would pay it ~30 times — ON
    THE LOOP THREAD, every publish_every steps. Instead a tiny jit
    concatenates the raveled leaves into one f32 buffer ON DEVICE
    (async dispatch, one copy: 0.9 MB at the 128-wide policy, 546 MB =
    136,584,631 f32 at the 4096-wide one); the blocking host read of
    that single buffer happens on the publisher thread (`publish.d2h`:
    at 4096 on a v5e 0.32 s of waiting for the steps the loop had
    dispatched ahead of the flatten, `publish.ready_wait`, then 0.16 s
    of copy, `publish.copy`; PERF.md section 5), and
    `serialize_weights` then copies each leaf's slice of it once into
    the frame. Stream ordering makes this donation-safe: the flatten
    program is dispatched BEFORE the next (state-donating) train step,
    so it reads the params before donation can reuse them.
    """

    def __init__(self, params_template):
        self._slots = []  # (name, shape, start, size) in canonical order
        off = 0
        for name, leaf in serialize_mod.named_param_leaves(params_template):
            n = int(np.prod(leaf.shape, dtype=np.int64)) if leaf.ndim else 1
            self._slots.append((name, tuple(leaf.shape), off, n))
            off += n

        def flat_fn(params):
            import jax.numpy as jnp

            leaves = serialize_mod.named_param_leaves(params)
            return jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for _, l in leaves])

        self._jit = jax.jit(flat_fn)

    def flatten_on_device(self, params):
        """Async-dispatched; returns the device buffer immediately."""
        return self._jit(params)

    def to_named(self, flat_dev) -> list:
        """Blocking host read + split — publisher-thread side. Output
        matches transport.serialize.flatten_params exactly."""
        flat = np.asarray(flat_dev, dtype=np.float32)
        return [
            (name, flat[start : start + size].reshape(shape))
            for name, shape, start, size in self._slots
        ]


class WeightPublisher:
    """Serialize + fanout weights off the train-loop thread.

    Latest-wins single slot: if the loop submits version v+1 while v is
    still serializing, v is superseded — actors only ever want the
    newest weights (transport/base.py fanout semantics), so coalescing
    is correct, not lossy. The expensive work (host read of the fused
    param buffer + wire framing + broker I/O) happens here; the loop
    thread only pays an async jit dispatch.

    `materialize(payload) -> named (name, f32 array) list` converts
    whatever the loop submitted on THIS thread; the default handles a
    host params pytree (tests, simple drivers), the Learner passes
    `ParamFlattener.to_named` with a device buffer payload.
    """

    def __init__(
        self,
        broker: Broker,
        materialize=None,
        boot_epoch: int = 0,
        legacy_dtw1: bool = False,
        on_published=None,
    ):
        self._materialize = materialize if materialize is not None else flatten_params
        self._broker = broker
        self._boot_epoch = boot_epoch
        self._legacy_dtw1 = legacy_dtw1
        # Post-send hook, called on THIS thread with the version just
        # fanned out. The full-state checkpointer persists its version
        # high-water mark here (runtime/checkpoint.py) — off the train
        # loop by construction. None = no extra work per publish.
        self._on_published = on_published
        self._cond = threading.Condition()
        self._slot = None  # (np_params, version, submit time) — latest pending
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self.published = 0  # versions actually sent (telemetry/tests)
        self.coalesced = 0  # versions superseded before sending
        self.failed = 0  # publishes that raised (the broker refused, a bad buffer)

    def start(self) -> "WeightPublisher":
        # restartable after stop(), same contract as StagingBuffer.start.
        # If a previous thread is still draining (stop()'s bounded join
        # timed out on a hung broker), it stays the active thread — it
        # will see _stop=False and keep serving; spawning a second one
        # would race two publishers and could deliver stale versions
        # after newer ones.
        with self._cond:
            self._stop = False
            if self._thread is not None and self._thread.is_alive():
                self._cond.notify()
                return self
            # Publish the new handle under the SAME lock hold that decided
            # a new thread is needed — an old thread's exit path nulls
            # _thread under this lock, so assigning outside it could let
            # that late null clobber the fresh handle.
            t = threading.Thread(target=self._run, daemon=True, name="weight-publisher")
            self._thread = t
            # start under the same hold: a stop() sneaking in after the
            # release would otherwise join an unstarted thread
            # (RuntimeError), and a second start() would see
            # is_alive()==False and spawn a duplicate publisher. The
            # worker's first act is acquiring this cond, so it simply
            # blocks until we release.
            t.start()
        return self

    def submit(self, np_params, version: int) -> None:
        with self._cond:
            if self._slot is not None:
                self.coalesced += 1
            self._slot = (np_params, version, time.perf_counter_ns())
            self._cond.notify()

    def _run(self) -> None:
        spans.name_thread("weight-publisher")
        while True:
            with self._cond:
                while self._slot is None and not self._stop:
                    self._cond.wait()
                if self._stop and self._slot is None:
                    # clear the handle under the SAME lock hold as the
                    # exit decision, so a concurrent start() never sees a
                    # thread that is alive but already committed to exit
                    self._thread = None
                    return
                np_params, version, t_submit = self._slot
                self._slot = None
            try:
                with span("publish.d2h", version=version):
                    # The wait for every step dispatched before the
                    # flatten, and the flatten: the device's time, not
                    # the copy's. A host pytree passes through at once.
                    with span("publish.ready_wait", version=version):
                        jax.block_until_ready(np_params)
                    t_ready = time.perf_counter_ns()
                    with span("publish.copy", version=version):
                        named = self._materialize(np_params)
                with span("publish.serialize", version=version):
                    frame = serialize_weights(
                        named,
                        version=version,
                        boot_epoch=self._boot_epoch,
                        legacy_dtw1=self._legacy_dtw1,
                    )
                del named  # the host copy goes before the send, as it always did
                with span("publish.send", version=version):
                    self._broker.publish_weights(frame)
                # Submit on the loop thread to sent (the loop submits
                # ahead of the device, so this counts the host's lead
                # too), and the weights existing on the device to sent:
                # how old the version is when an actor can first read it.
                # No timeline spans: the first starts on another thread.
                t_sent = time.perf_counter_ns()
                spans.add("publish.latency", t_sent - t_submit)
                spans.add("publish.age", t_sent - t_ready)
                self.published += 1
                if self._on_published is not None:
                    self._on_published(version)
            except Exception:
                self.failed += 1
                _log.exception("weight publish failed (version %d); continuing", version)

    def stop(self, flush: bool = True) -> None:
        """Stop the thread; by default drains a pending slot first."""
        with self._cond:
            if not flush:
                self._slot = None
            self._stop = True
            self._cond.notify()
            t = self._thread  # local ref: the thread nulls the handle on exit
        if t is not None:
            t.join(timeout=10)


class CheckpointWorker:
    """Off-critical-path full-state saver (--ckpt.async_save).

    The loop thread pays ONE async jit dispatch per checkpoint — an
    on-device copy of the TrainState, donation-safe for the same
    stream-ordering reason as ParamFlattener (the copy is dispatched
    before the next state-donating train step, so it reads the params
    before donation can reuse them). This thread then pays everything
    expensive: the blocking host read of the copy, the staging snapshot
    handshake, the manifest pickle, and the orbax/aux submit.

    Latest-wins single slot, the WeightPublisher coalescing argument:
    durability only ever needs the newest state, so if the loop submits
    step v+k while v is still saving, v is superseded — counted, never
    silently dropped.
    """

    def __init__(self, save_fn):
        self._save_fn = save_fn  # (host_state, version) -> None
        self._cond = threading.Condition()
        self._slot = None  # (state_copy_dev, version) — latest pending
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self.saved = 0  # checkpoints actually written (telemetry/tests)
        self.coalesced = 0  # checkpoints superseded before writing

    def start(self) -> "CheckpointWorker":
        with self._cond:
            self._stop = False
            if self._thread is not None and self._thread.is_alive():
                self._cond.notify()
                return self
            # Same handle-publish-under-the-lock discipline as
            # WeightPublisher.start (the late-null-clobber race).
            t = threading.Thread(target=self._run, daemon=True, name="ckpt-saver")
            self._thread = t
            t.start()
        return self

    def submit(self, state_copy_dev, version: int) -> None:
        with self._cond:
            if self._slot is not None:
                self.coalesced += 1
            self._slot = (state_copy_dev, version)
            self._cond.notify()

    def _run(self) -> None:
        while True:
            with self._cond:
                while self._slot is None and not self._stop:
                    self._cond.wait()
                if self._stop and self._slot is None:
                    self._thread = None
                    return
                state_dev, version = self._slot
                self._slot = None
            try:
                host_state = jax.device_get(state_dev)
                del state_dev  # release the device copy before the slow write
                self._save_fn(host_state, version)
                self.saved += 1
            except Exception:
                _log.exception("async checkpoint of step %d failed; continuing", version)

    def stop(self, flush: bool = True) -> None:
        """Stop the thread; by default drains a pending slot first."""
        with self._cond:
            if not flush:
                self._slot = None
            self._stop = True
            self._cond.notify()
            t = self._thread
        if t is not None:
            t.join(timeout=60)


class _InFlight:
    """What the loop thread knows of the device's queue of steps, from
    the results it holds: no fence, no callback, no thread.

    `pending` holds one leaf of the metrics of each dispatched step not
    yet known complete. `poll()`, just before a dispatch, drops from its
    left those whose `is_ready()` is true and counts what is left: the
    steps ahead of this one (`loop_inflight_max` / `_mean` over a metrics
    window). `synced()` empties it at `loop.sync_ready`'s exit, where
    every step is complete.

    `t_known` is when the loop last knew the device busy (a dispatch) or
    done (`synced`). A dispatch, other than a run's first, that finds
    nothing pending is starved: `loop_starved_n_total` counts it and
    `loop_starved_<cause>_s_total` takes the seconds since `t_known`,
    under what the loop spent most of them in (`charge`, `during`). After
    a sync those seconds are the device's idle time as the host can see
    it; after a dispatch they are an upper bound, since the step may have
    ended at any time since. Totals are the process's, the rest the
    run's (`begin_run`)."""

    CAUSES = ("take", "sync", "publish", "checkpoint", "other")

    def __init__(self):
        self.pending = collections.deque()
        self.t_known: Optional[float] = None
        self._spent = dict.fromkeys(self.CAUSES[:-1], 0.0)
        self._win = [0, 0, 0]  # polls, sum and max of their counts, this metrics window
        self.starved_n = 0
        self.starved_s = dict.fromkeys(self.CAUSES, 0.0)

    def begin_run(self) -> None:
        self.pending.clear()
        self.t_known = None
        self._win = [0, 0, 0]

    def _know(self) -> None:
        self.t_known = time.perf_counter()
        for cause in self._spent:
            self._spent[cause] = 0.0

    def charge(self, cause: str, seconds: float) -> None:
        self._spent[cause] += seconds

    @contextlib.contextmanager
    def during(self, cause: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.charge(cause, time.perf_counter() - t0)

    def poll(self) -> None:
        pending = self.pending
        while pending and pending[0].is_ready():
            pending.popleft()
        n = len(pending)
        win = self._win
        win[0] += 1
        win[1] += n
        win[2] = max(win[2], n)
        if n == 0 and self.t_known is not None:
            gap = time.perf_counter() - self.t_known
            spent = dict(self._spent, other=max(gap - sum(self._spent.values()), 0.0))
            self.starved_n += 1
            self.starved_s[max(spent, key=spent.get)] += gap

    def dispatched(self, leaf) -> None:
        self.pending.append(leaf)
        self._know()

    def synced(self) -> None:
        self.pending.clear()
        self._know()

    def window_scalars(self) -> dict:
        """The window's two gauges (then reset) and the cumulative six."""
        polls, total, most = self._win
        self._win = [0, 0, 0]
        return {
            "loop_inflight_max": float(most),
            "loop_inflight_mean": total / max(polls, 1),
            "loop_starved_n_total": float(self.starved_n),
            **{f"loop_starved_{cause}_s_total": s for cause, s in self.starved_s.items()},
        }


class _LaneItem(NamedTuple):
    """One prefetch-lane handoff: kind ∈ {"batch", "idle", "exhausted",
    "error"}. `wait_s`/`put_s` are the lane's own fetch-wait and
    device-put attribution for the window accumulators (an "idle" item
    carries the empty wait so starvation stays visible)."""

    kind: str
    batch: object
    env_steps: int
    wait_s: float
    put_s: float
    trace: object
    error: Optional[BaseException]


class PrefetchLane:
    """The prefetch stage of the learner loop: runs the WHOLE host side
    of batch N+1 — staging pop, pack wait, device_put dispatch, transfer
    retire, ring lease release — on its own thread while the loop
    thread keeps the device busy with step N, handing finished batches
    over a queue of one (classic double buffering).

    Ownership rules:
    - the lane is the ONE staging consumer, popping FIFO, so the loop
      trains the batches in arrival order and its parameters are
      BITWISE those of the same batches stepped by hand
      (tests/test_pipeline.py);
    - a ring lease is released only after ITS device_put retired
      (inside Learner._fetch_next — the donation-safety rule; the lane
      keeps the release off the loop thread, never before the retire);
    - `holding()` makes a popped-but-untrained batch visible to
      staging.drained() as the prefetch station, so the SIGTERM
      zero-loss contract extends through the lane: a drain trains the
      in-flight prefetched batch out, never drops it.

    Budget (`limit` = the run's num_steps): the lane never fetches more
    batches than the loop will train, so a finite phased run
    (train → eval → train, scripts/train_north_star.py) cannot eat and
    discard a trailing batch. Empty waits ("idle" items) consume no
    budget. Fetch errors surface on the loop thread via "error" items
    (the staging _check_fatal fast-failure contract survives the lane).
    """

    def __init__(
        self,
        fetch_fn,
        limit: Optional[int] = None,
        drain: Optional[threading.Event] = None,
        abort: Optional[threading.Event] = None,
        upstream_drained=None,
        stop_event: Optional[threading.Event] = None,
    ):
        self._fetch = fetch_fn  # () -> (batch, env_steps, wait_s, put_s, trace)
        # One batch ahead and no more: every queued batch ages a version.
        self._out: "queue.Queue[_LaneItem]" = queue.Queue(maxsize=1)
        self._limit = limit
        self._drain = drain
        self._abort = abort
        self._upstream_drained = upstream_drained
        # Doubles as the staging-getter cancel hook (the caller threads
        # it into _fetch_next): a stopping lane aborts its in-flight
        # wait within one 0.2s slice instead of sitting out a full
        # batch timeout (and overlapping a successor lane's pops on a
        # phased driver's next run()).
        self.stop_event = stop_event if stop_event is not None else threading.Event()
        # True from just before a fetch (which may pop a batch into this
        # thread's locals) until the item is in the handoff queue — the
        # drained() visibility contract (the _popping/_packing pattern,
        # one station further downstream). Atomically-rebound bool,
        # read once by holding().
        self._inflight = False
        self._thread: Optional[threading.Thread] = None
        self.fetched = 0  # successful batches delivered (telemetry/tests)

    def start(self) -> "PrefetchLane":
        t = threading.Thread(target=self._run, daemon=True, name="learner-prefetch")
        self._thread = t
        t.start()
        return self

    def holding(self) -> bool:
        """True while the lane holds popped-but-untrained frames — in
        its thread locals (mid-fetch) or the handoff queue. This is
        staging's prefetch drained() station; single reads of a
        rebound bool + one queue empty-check (gauge semantics: a
        False->True flicker only delays a drain verdict one poll)."""
        inflight = self._inflight
        return inflight or not self._out.empty()

    def get(self, timeout: float) -> _LaneItem:
        """Next handoff item (the loop thread's side). Raises
        queue.Empty on timeout — callers poll in short slices so
        abort/deadline stay responsive."""
        return self._out.get(timeout=timeout)

    def _put(self, item: _LaneItem) -> None:
        # The time in here is the lane blocked on the full queue: the
        # loop and the device pushing back (next to nothing otherwise).
        with span("lane.handoff"):
            while not self.stop_event.is_set():
                try:
                    self._out.put(item, timeout=0.2)
                    return
                except queue.Full:
                    continue

    def _run(self) -> None:
        spans.name_thread("learner-prefetch")
        while not self.stop_event.is_set():
            if self._limit is not None and self.fetched >= self._limit:
                # Budget consumed: every batch the loop will train is
                # fetched (or queued) — never eat a trailing batch.
                return
            self._inflight = True
            try:
                try:
                    batch, env_steps, wait_s, put_s, trace = self._fetch()
                except BaseException as e:  # surfaces on the loop thread
                    self._put(_LaneItem("error", None, 0, 0.0, 0.0, None, e))
                    return
                if batch is None:
                    if self._abort is not None and self._abort.is_set():
                        return
                    if (
                        self._drain is not None
                        and self._drain.is_set()
                        and (
                            self._upstream_drained is None
                            or self._upstream_drained()
                        )
                    ):
                        # SIGTERM drain: nothing upstream will ever
                        # arrive again. FIFO guarantees this lands
                        # AFTER any still-queued batch, so the loop
                        # trains everything out first.
                        self._put(_LaneItem("exhausted", None, 0, wait_s, 0.0, None, None))
                        return
                    self._put(_LaneItem("idle", None, 0, wait_s, 0.0, None, None))
                    continue
                self.fetched += 1
                self._put(_LaneItem("batch", batch, env_steps, wait_s, put_s, trace, None))
            finally:
                # Cleared AFTER the handoff put: the queue's own
                # non-emptiness covers the item from here, so holding()
                # never has a gap a drain could slip through.
                self._inflight = False

    def stop(self) -> None:
        self.stop_event.set()
        t = self._thread
        if t is not None:
            t.join(timeout=10)


class Learner:
    def __init__(self, cfg: LearnerConfig, broker: Broker, mesh=None):
        spans.count_compiles()
        with span("setup.learner_init"):
            self._init(cfg, broker, mesh)

    def _init(self, cfg: LearnerConfig, broker: Broker, mesh) -> None:
        self.cfg = cfg
        self.broker = broker
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh(cfg.mesh_shape)
        # The live lane of the CURRENT run() (None between runs);
        # staging's prefetch drained() station reads it through
        # _prefetch_holding.
        self._prefetch_lane: Optional[PrefetchLane] = None
        # One fused u8 buffer per batch (fused_io.py), unless the layout
        # cannot apply: sp shards the obs TIME axis, which the row layout
        # would destroy, and the replay reservoir's per-row
        # behavior_staleness stamp is not part of it (replay targets
        # data-starved regimes where the transfer count is not the
        # bottleneck anyway). There the batch crosses as its per-leaf
        # tree. Same compiled math.
        self.fused_io = None
        self.batch_sharding = None
        if is_sequence_parallel(cfg, self.mesh) or cfg.replay.enabled:
            self.train_step, self.state_shardings, self.batch_sharding = build_train_step(
                cfg, self.mesh
            )
        else:
            self.train_step, self.state_shardings, self.fused_io = build_single_train_step(
                cfg, self.mesh
            )
        self.version = 0
        # Drawn once per learner process and stamped into every weight
        # frame: subscribers detect a restart by the epoch CHANGING, not
        # by counting suspicious frames (runtime/actor.py
        # apply_weight_frame). Time ^ pid so two boots in the same second
        # still differ.
        self.boot_epoch = (int(time.time()) << 8 ^ os.getpid()) & 0xFFFFFFFF
        with span("setup.init_params"):
            state = init_train_state(cfg, jax.random.PRNGKey(cfg.seed))
            self.state: TrainState = jax.device_put(state, self.state_shardings)
        # Multi-process (--multihost over DCN): batch_size stays GLOBAL;
        # each process's staging packs its share and _fetch_next stitches
        # the shares into one global array (standard multihost DP). The
        # broker is a SHARED cluster service (k8s: one broker every actor
        # and every learner host connects to): experience consumption
        # splits the shared queue across hosts, and weight publishing is
        # gated to process 0 so the fanout carries ONE frame per version
        # — a topology with per-host private brokers would starve
        # non-primary hosts' actors of weights and, once the version
        # outran max_staleness, deadlock the cluster in the collectives.
        self._n_proc = jax.process_count()
        self._primary = jax.process_index() == 0
        staging_cfg = cfg
        if self._n_proc > 1:
            import copy

            if cfg.batch_size % self._n_proc:
                raise ValueError(
                    f"batch_size={cfg.batch_size} must divide by the process "
                    f"count ({self._n_proc}) — each host stages an equal share"
                )
            # The dp axis must span the processes: each process's
            # addressable dp shards are where its local rows land. A
            # tp-only / replicated-batch mesh would make the per-process
            # shares incoherent under one 'replicated' global array.
            dp_size = dict(zip(self.mesh.axis_names, self.mesh.devices.shape)).get("dp", 1)
            if dp_size % self._n_proc:
                raise ValueError(
                    f"multihost needs the mesh dp axis to span the processes: "
                    f"dp={dp_size} not divisible by process count {self._n_proc} "
                    f"(mesh {cfg.mesh_shape!r})"
                )
            # dp must be the MAJOR mesh axis: jax.devices() orders
            # process-major, so a dp-major mesh gives each process a
            # contiguous block of dp shards (its local batch rows land on
            # its own devices) and any minor axis (tp/sp) stays WITHIN a
            # process — make_array_from_process_local_data is only
            # assembling along dp. A mesh like "sp=4,dp=2" would
            # interleave processes along sp and scatter each host's rows
            # across hosts. The invariant is "no axis of size > 1 ahead
            # of dp", not dp-literally-first: "tp=1,dp=8" is fine.
            names = list(self.mesh.axis_names)
            sizes = list(self.mesh.devices.shape)
            ahead = 1
            for n, s in zip(names, sizes):
                if n == "dp":
                    break
                ahead *= s
            if ahead != 1:
                raise ValueError(
                    f"multihost needs 'dp' as the MAJOR mesh axis (no axis of "
                    f"size > 1 ahead of it); got {dict(zip(names, sizes))} — "
                    f"write --mesh_shape dp=...,<rest>"
                )
            if cfg.broker_url.startswith("mem://"):
                _log.warning(
                    "multihost with mem:// broker: in-process queues cannot span "
                    "hosts — fine for tests, wrong for production (use tcp://"
                    "or amqp:// shared by all hosts)"
                )
            staging_cfg = copy.deepcopy(cfg)
            staging_cfg.batch_size = cfg.batch_size // self._n_proc
            if self.fused_io is not None:
                self.fused_io.local_rows = staging_cfg.batch_size
        # Observability (dotaclient_tpu/obs/, --obs.*): None when off —
        # every obs touchpoint below is a single `is not None` check, so
        # the disabled hot path is unchanged.
        from dotaclient_tpu.obs import ObsRuntime

        self.obs = ObsRuntime.create(cfg.obs, role="learner")
        # Long closed spans also go into the flight recorder's ring,
        # where one exists: a crash dump then holds the last stalls.
        spans.mirror_to(self.obs.recorder if self.obs is not None else None)
        self._flight = _InFlight()
        self.staging = StagingBuffer(
            staging_cfg,
            broker,
            version_fn=lambda: self.version,
            fused_io=self.fused_io,
            tracer=self.obs.tracer if self.obs is not None else None,
            recorder=self.obs.recorder if self.obs is not None else None,
        )
        # The prefetch station of the zero-loss drain contract: a batch
        # the lane popped but the loop has not trained is visible to
        # staging.drained().
        self.staging.attach_prefetch_probe(self._prefetch_holding)
        self.flattener = ParamFlattener(state.params)
        # Full-state mode: every fanned-out version is persisted as a
        # high-water mark (tiny atomic file, publisher thread) so a
        # SIGKILL between periodic checkpoints can never roll the
        # restored version counter below versions actors have already
        # stamped on rollouts. Lazy closure: the checkpointer is
        # constructed further down.
        on_pub = None
        if cfg.ckpt.full_state and cfg.checkpoint_dir:

            def on_pub(version):
                ck = self.checkpointer
                if ck is not None:
                    ck.record_published_version(version)

        self.publisher = WeightPublisher(
            broker,
            materialize=self.flattener.to_named,
            boot_epoch=self.boot_epoch,
            legacy_dtw1=cfg.publish_legacy_dtw1,
            on_published=on_pub,
        )
        self.metrics = MetricsLogger(cfg.log_dir)
        self._boot_monotonic = time.monotonic()
        if self.obs is not None:
            # Compute observability (obs/compute.py): the train step gets
            # the recompile sentinel (aval-signature hash + compile wall
            # + shape-diff to the flight recorder), MFU accounting gets
            # the analytic FLOPs model against the platform peak table,
            # and — when cfg.obs.step_phases — the phase timer: the lane
            # records its fetch/pack/h2d (fenced there, hidden behind the
            # device step), the loop its take-wait/residual/host; no
            # per-step fence on the loop. With obs off, self.train_step
            # stays the raw jit object: byte-identical hot path, asserted
            # in test_obs.
            from dotaclient_tpu.ops.flops import aggregate_peak_flops, train_step_flops

            compute = self.obs.attach_compute(
                train_step_flops(cfg), aggregate_peak_flops(jax.devices())
            )
            self.train_step = compute.wrap_train_step(self.train_step)
            # (The liveness watchdog attaches at the END of __init__,
            # after checkpoint restore — the restore's version write must
            # not read as the first train-step heartbeat, or boot grace
            # ends before the first step. serve_metrics binds the
            # watchdog's gauges late, so the ordering is safe.)
            # Scrape surface (obs/http.py): the latest logged scalars plus
            # live gauges sampled per scrape — queue depth straight from
            # the broker, staging/replay occupancy from stats(). Runs for
            # the process lifetime (run() is re-entrant); close() stops it.
            # /healthz serves the structured health body (503 once the
            # watchdog trips — the k8s liveness-probe contract) and POST
            # /profile captures on-demand jax.profiler traces.
            self.obs.serve_metrics(
                [self.metrics.latest, self._obs_gauges], health_provider=self._health
            )
        self.env_steps_done = 0  # total real (unmasked) env steps trained on
        # The version the next fetched batch is trained into (the lane's
        # n-th batch is the loop's n-th step, FIFO): the `step` that feed,
        # lane and loop spans of one batch share. Set at each run()'s start.
        self._fetch_step = 0
        # SIGTERM drain / kill plumbing (--ckpt.*): `_drain` asks run()
        # to stop fetching, train out already-staged batches, and return
        # (the caller then drain_save()s); `_abort` asks run() to return
        # IMMEDIATELY, discarding staged work — the chaos controller's
        # SIGKILL emulation. Both default-unset: the steady-state loop
        # pays one Event.is_set() per iteration.
        self._drain = threading.Event()
        self._abort = threading.Event()
        # Budget timer armed by the SIGTERM handler, cancelled by
        # drain_save() once the final save is durable.
        self._drain_timer: Optional[threading.Timer] = None
        # resume_* scalars (obs/registry.py): merged into the FIRST
        # metrics window after a restore so the resume is visible on the
        # dashboard, then cleared.
        self._resume_scalars = {}
        self._ckpt_worker: Optional[CheckpointWorker] = None
        self._state_copy_jit = None
        if cfg.ckpt.async_save and cfg.checkpoint_dir:
            # Built ONLY in async mode: with the flag off no extra jit
            # object exists and checkpoint() is the pre-existing
            # synchronous path (the inertness proof's contract).
            import jax.numpy as jnp

            self._state_copy_jit = jax.jit(
                lambda s: jax.tree.map(jnp.copy, s)
            )
            self._ckpt_worker = CheckpointWorker(self._save_full)
        self.checkpointer = None
        if cfg.checkpoint_dir:
            from dotaclient_tpu.runtime.checkpoint import Checkpointer

            # Every process can PULL the shared mirror (a restarted
            # non-primary pod must restore the same step or the
            # consistency check below trips); only process 0 PUSHES —
            # per-host duplicate uploads would race on the remote paths.
            self.checkpointer = Checkpointer(
                cfg.checkpoint_dir,
                remote_dir=cfg.checkpoint_remote_dir,
                remote_push=self._primary,
            )
            t_restore = time.monotonic()
            with span("setup.restore"):
                restored = self.checkpointer.restore_latest(self.state)
                if restored is not None:
                    self.state = jax.device_put(restored, self.state_shardings)
                    self.version = int(jax.device_get(restored.step))
                    _log.info("restored checkpoint at step %d", self.version)
                    if cfg.ckpt.full_state:
                        self._restore_full_state(t_restore)
        if self._n_proc > 1:
            # Restore is per-process and a partial host restart (one pod
            # with a fresh disk) would leave processes at DIFFERENT
            # steps/params inside one SPMD program — divergent reuse-loop
            # permutations, garbage gradients, no error. Refuse to start
            # unless every process agrees on the resume step.
            from jax.experimental import multihost_utils

            steps = np.asarray(
                multihost_utils.process_allgather(np.int64(self.version))
            ).reshape(-1)
            if len(set(int(s) for s in steps)) != 1:
                raise RuntimeError(
                    f"multihost restore mismatch: per-process resume steps "
                    f"{steps.tolist()} — restore every host from the same "
                    f"checkpoint (shared checkpoint_dir or remote mirror) "
                    f"before starting"
                )
            if cfg.ckpt.full_state:
                # Published-high-water bump, global max: only process 0
                # writes the hwm file, but every process must resume the
                # SAME version counter (staleness filtering is
                # per-process host work inside one SPMD program).
                hwm = int(
                    np.asarray(
                        multihost_utils.process_allgather(
                            np.int64(getattr(self, "_pending_hwm", self.version))
                        )
                    ).max()
                )
                if hwm > self.version:
                    self._resume_scalars["resume_version_hwm_bump"] = float(
                        hwm - self.version
                    )
                    _log.info(
                        "resume: version counter %d -> %d (global published "
                        "high-water)", self.version, hwm,
                    )
                    self.version = hwm
        if self.obs is not None:
            # Liveness watchdog (obs/watchdog.py, --obs.watchdog.*): reads
            # the telemetry the loop already produces; trips /healthz.
            # Attached LAST — after checkpoint restore has written
            # self.version — so the restore is the watchdog's baseline,
            # not its first heartbeat: a heartbeat-counted restore would
            # drop the stall threshold from boot_grace_s to stall_s
            # before the first (minutes-long) compile+first-batch wait,
            # and the k8s liveness probe would crashloop every restored
            # learner. latest_step keys the per-check freshness/dedup of
            # the metrics-window detectors.
            self.obs.attach_watchdog(
                self.metrics.latest, lambda: self.version, self.metrics.latest_step
            )

    # ---------------------------------------------------------------- ops

    def _prefetch_holding(self) -> bool:
        """staging.drained()'s prefetch station: True while the current
        run's lane holds popped-but-untrained frames. Single read of a
        rebound attribute — safe from any thread."""
        lane = self._prefetch_lane
        return lane is not None and lane.holding()

    def _obs_gauges(self):
        """Live gauges for the /metrics scrape (obs_ prefix = the
        scrape-only family in obs/registry.py). Sampled per scrape, off
        the train loop."""
        out = {"obs_learner_version": float(self.version)}
        depth = self.broker.experience_depth()
        if depth >= 0:  # -1 = this transport can't know it cheaply
            out["obs_broker_experience_depth"] = float(depth)
        for k, v in self.staging.stats().items():
            out[f"obs_staging_{k}"] = float(v)
        return out

    def _health(self):
        """The /healthz body (obs/http.py contract: "ok" selects the
        status code). A learner without a watchdog is healthy by virtue
        of serving; with one, the watchdog verdict decides."""
        # Runs on scrape handler threads while close() may null
        # obs.watchdog — bind once so the None-check and the verdict()
        # call observe the same object.
        obs = self.obs
        watchdog = obs.watchdog if obs is not None else None
        wd = (
            watchdog.verdict()
            if watchdog is not None
            else {"enabled": False, "ok": True}
        )
        return {
            "ok": bool(wd.get("ok", True)),
            "role": "learner",
            "version": int(self.version),
            "uptime_s": round(time.monotonic() - self._boot_monotonic, 1),
            "watchdog": wd,
        }

    def publish_weights(self) -> None:
        if not self._primary:
            return  # one fanout per version — process 0 publishes
        params = jax.device_get(self.state.params)
        frame = serialize_weights(
            flatten_params(params),
            version=self.version,
            boot_epoch=self.boot_epoch,
            legacy_dtw1=self.cfg.publish_legacy_dtw1,
        )
        self.broker.publish_weights(frame)

    def checkpoint(self, wait: bool = False) -> None:
        if self.checkpointer is None:
            return
        cfg = self.cfg.ckpt
        if not cfg.full_state and not cfg.async_save:
            # Pre-existing path, byte-identical on disk (the resume
            # soak's inertness proof pins this).
            self.checkpointer.save(jax.device_get(self.state), step=self.version)
            return
        if self._ckpt_worker is not None and not wait:
            # Loop thread pays one async on-device copy dispatch; the
            # worker pays the host read + snapshot + write. Dispatched
            # BEFORE the next (state-donating) train step, so stream
            # ordering makes the copy donation-safe (CheckpointWorker
            # docstring).
            self._ckpt_worker.start()
            self._ckpt_worker.submit(self._state_copy_jit(self.state), self.version)
            return
        self._save_full(jax.device_get(self.state), self.version, wait=wait)

    def _save_full(self, host_state, version: int, wait: bool = False) -> None:
        """Write one transactional full-state checkpoint: orbax step +
        aux manifest (RNG streams, reservoir, pending frames, publisher
        high-water mark). Runs on the CheckpointWorker thread in async
        mode, on the caller otherwise."""
        aux = None
        if self.cfg.ckpt.full_state:
            aux = self._build_aux(version)
        self.checkpointer.save(host_state, step=version, wait=wait, aux=aux)

    def _build_aux(self, version: int) -> bytes:
        """The aux manifest payload. Versioned and pickled — everything
        in it is host-side state the orbax arrays cannot carry:

        - the staging snapshot: pending (popped-but-untrained) frames in
          arrival order + the replay reservoir's entries, priorities,
          ABSOLUTE staleness stamps, and its numpy Generator state (the
          only host RNG stream the learner owns — the device-side
          shuffle rng is a pure fold_in(seed, state.step) and needs no
          capture, and a restored state.step replays it exactly);
        - the weight-publisher version high-water AS OF this step (the
          authoritative per-publish watermark is the hwm side-file,
          which the mirror also carries — restore takes the max of all
          three sources);
        - metrics/env-step high-water marks so the restored learner's
          telemetry continues instead of rewinding."""
        import pickle

        staging_snap = self.staging.snapshot_state() or {}
        manifest = {
            "manifest_version": 1,
            "step": int(version),
            "version_hwm": int(version),
            "boot_epoch": int(self.boot_epoch),
            "staging": staging_snap,
            "metrics_last_step": int(self.metrics.latest_step()),
            "env_steps_done": int(self.env_steps_done),
        }
        return pickle.dumps(manifest, protocol=4)

    def _restore_full_state(self, t_restore: float) -> None:
        """Rehydrate the host-side state the aux manifest carries and
        bump the version counter to the published high-water mark —
        rollouts already in flight are stamped with every version the
        fleet has seen, and a counter that restarted BELOW those stamps
        would compute negative staleness: under-aged experience passing
        the max_staleness filter and entering ACER with staleness 0.
        Monotonic-never-under-aged is the contract; over-aging (frames
        from the dead incarnation's last steps looking older than the
        redone steps they interleave with) is the safe direction, same
        as the PR-5 chunk-boundary version stamping."""
        import pickle

        step = self.checkpointer.latest_step()
        aux_bytes = self.checkpointer.load_aux(step)
        aux = None
        if aux_bytes is not None:
            try:
                aux = pickle.loads(aux_bytes)
            except Exception:
                _log.exception("aux manifest for step %s unreadable; state-only restore", step)
        counts = {"pending": 0, "reservoir": 0}
        hwm = self.version
        if step is not None:
            hwm = max(hwm, int(step))  # save labels track the version counter
        if aux is not None:
            counts = self.staging.restore_state(aux.get("staging", {}))
            hwm = max(hwm, int(aux.get("version_hwm", 0)))
            self.env_steps_done = int(aux.get("env_steps_done", 0))
        file_hwm = self.checkpointer.published_hwm()
        if file_hwm is not None:
            hwm = max(hwm, file_hwm)
        if self._n_proc > 1:
            # Non-primary processes never publish, so only process 0
            # holds the hwm file. Defer the bump: the resume-step
            # equality check must compare the UN-bumped checkpoint
            # steps, and then every process applies the same global-max
            # bump (allgather in __init__).
            self._pending_hwm = hwm
            hwm = self.version
        bump = hwm - self.version
        if bump > 0:
            _log.info(
                "resume: version counter %d -> %d (published high-water; "
                "staleness stamps stay monotonic)", self.version, hwm,
            )
            self.version = hwm
        self._resume_scalars = {
            "resume_restored_step": float(step if step is not None else -1),
            "resume_version_hwm_bump": float(max(bump, 0)),
            "resume_reservoir_entries": float(counts["reservoir"]),
            "resume_pending_frames": float(counts["pending"]),
            "resume_restore_wall_s": round(time.monotonic() - t_restore, 3),
        }

    # ------------------------------------------------------ drain / abort

    @property
    def resume_info(self) -> dict:
        """The resume_* scalars of this boot's restore (empty for a
        fresh start, or after the first metrics window consumed them) —
        the chaos controller snapshots this at incarnation boot."""
        return dict(self._resume_scalars)

    def discard_unsaved(self) -> None:
        """SIGKILL-emulation teardown (chaos controller): drop queued
        async-checkpoint and aux/mirror work, exactly as a real kill -9
        would — durable state is whatever already hit the disk."""
        if self._ckpt_worker is not None:
            self._ckpt_worker.stop(flush=False)
        if self.checkpointer is not None:
            self.checkpointer.discard_pending()

    @property
    def drain_requested(self) -> bool:
        return self._drain.is_set()

    @property
    def aborted(self) -> bool:
        return self._abort.is_set()

    def request_drain(self) -> None:
        """SIGTERM semantics: run() stops fetching new broker frames,
        finishes the in-flight step, trains out already-staged batches,
        and returns; the caller then drain_save()s and exits 0."""
        self._drain.set()
        # Wake a fetch blocked on its full batch timeout: quiesce stops
        # intake and lets staging's getter raise Empty once drained.
        self.staging.quiesce()

    def abort(self) -> None:
        """SIGKILL emulation for the chaos controller: run() returns as
        soon as possible, staged work is DISCARDED, nothing is saved —
        recovery must come from the last periodic checkpoint, exactly as
        a real kill -9 would leave things."""
        self._abort.set()
        self.staging.quiesce()

    def drain_save(self) -> None:
        """Final act of the SIGTERM drain, called AFTER run() returned
        (staging/publisher threads already stopped): persist the full
        state — including the sub-batch leftover pending frames the
        quiesced staging could not pack — with wait=True, so a zero exit
        certifies durability."""
        if self.checkpointer is None:
            return
        if self._ckpt_worker is not None:
            self._ckpt_worker.stop(flush=False)  # superseded by this final save
        self._save_full(jax.device_get(self.state), self.version, wait=True)
        # The state is durable — disarm the budget timer. The budget
        # covers drain + save, not obs/metrics teardown: a timer left
        # running could os._exit(1) mid-close after a fully successful
        # drain and mis-signal a dirty shutdown to the supervisor.
        timer = self._drain_timer
        if timer is not None:
            timer.cancel()

    def install_drain_handler(self, budget_s: Optional[float] = None) -> None:
        """Learner-main wiring for --ckpt.drain_on_sigterm: SIGTERM →
        request_drain() + a budget timer that force-exits nonzero if the
        drain wedges — the pod must never coast past its k8s grace
        period into SIGKILL with a half-written step. Replaces any
        flight-recorder SIGTERM dump trigger: a drain is a CLEAN exit
        (the recorder's excepthook stays armed for dirty ones)."""
        import signal

        budget = self.cfg.ckpt.drain_budget_s if budget_s is None else budget_s

        def _on_term(signum, frame):
            _log.warning("SIGTERM: draining (budget %.0fs)", budget)
            self.request_drain()
            if self._drain_timer is None:  # repeated SIGTERMs arm ONE timer
                t = threading.Timer(budget, self._drain_budget_blown)
                t.daemon = True
                t.start()
                self._drain_timer = t

        signal.signal(signal.SIGTERM, _on_term)

    def _drain_budget_blown(self) -> None:
        _log.critical("SIGTERM drain exceeded its budget; forcing exit(1)")
        if self.obs is not None:
            try:
                self.obs.recorder.record("drain_budget_blown")
                self.obs.recorder.dump("drain_budget_blown")
            except Exception:
                pass
        os._exit(1)

    # --------------------------------------------------------------- loop

    def _dispatch(self, batch_dev):
        """The loop's one call of the compiled step (async: returns once
        the step is handed to the device), between a count of the steps
        still ahead of it and the note that the device now has this one."""
        self._flight.poll()
        with span("loop.dispatch", step=self.version + 1):
            out = self.train_step(self.state, batch_dev)
        self._flight.dispatched(out[1]["loss"])
        return out

    def _submit_publish(self) -> None:
        """One async on-device flatten dispatch; the blocking host read
        of the single buffer happens on the publisher thread.
        Donation-safe because this dispatch precedes the next
        (state-donating) train step in the loop thread's stream order
        (ParamFlattener docstring; the lane only ever touches batch
        buffers, never the state)."""
        with self._flight.during("publish"), span("loop.publish_submit", version=self.version):
            self.publisher.submit(
                self.flattener.flatten_on_device(self.state.params), self.version
            )

    def _fetch_next(self, batch_timeout: float, cancel=None):
        """Pull one batch off staging and device_put it (dp-sharded).

        Called on the PrefetchLane thread, so the host wait and the
        transfer overlap the running device step; phase attribution goes
        to the timer's lane sums (add_lane) and the staging wait is
        cancellable at lane teardown.
        Returns (batch_dev, env_steps, wait_s, put_s, trace) or
        (None, 0, w, 0.0, None); `trace` is the batch's obs trace refs
        (staging.last_batch_trace) with the h2d hop already recorded —
        at DISPATCH time, like every hop this loop records (the loop
        never syncs the device per step). On the fused path the pack
        happened on the STAGING thread (straight into the transfer
        buffer), so wait_s is queue wait; only the dense-staging
        fallback pays io.pack_transfer here (still charged to wait_s,
        never to put_s — that bucket is the pure H2D transfer).
        """
        timer = self.obs.compute.timer if self.obs is not None and self.obs.compute else None
        # The lane's own fenced wall, hidden behind the device step.
        add = timer.add_lane if timer is not None else None
        step = self._fetch_step
        t0 = time.perf_counter()
        with timeline("lane.wait_batch", step=step):
            batch, buf = self.staging.get_batch_groups(timeout=batch_timeout, cancel=cancel)
        t1 = time.perf_counter()
        if add is not None:
            add("fetch", t1 - t0)
        if batch is None:
            return None, 0, t1 - t0, 0.0, None
        self._fetch_step = step + 1
        trace = self.staging.last_batch_trace
        # Ring lease (--staging.pack_workers > 1, fused path): the batch
        # lives in a TransferRing slot that must go back to the packers
        # once — and only once — its device_put has retired. None on the
        # classic path.
        lease = self.staging.last_batch_lease
        env_steps = int(np.sum(batch.mask))
        if self.fused_io is not None:
            # Staging packed straight into the transfer buffer (buf
            # non-None); the pack_transfer fallback only runs if a caller
            # wired a dense staging buffer to a fused learner. Host memcpy
            # is charged to the WAIT bucket, not the put bucket:
            # time_device_put_s exists to attribute the H2D transfer
            # specifically (the on-silicon bottleneck).
            if buf is None:
                buf = self.fused_io.pack_transfer(batch)
            t2 = time.perf_counter()
            if add is not None:
                add("pack", t2 - t1)
            sharding = self.fused_io.sharding
            with timeline("lane.device_put", step=step):
                if self._n_proc > 1:
                    # Each process contributes its local rows; the result is
                    # ONE global array whose dp shards live where each host
                    # put them — no cross-host data movement.
                    batch_dev = jax.make_array_from_process_local_data(sharding, buf)
                else:
                    batch_dev = jax.device_put(buf, sharding)
            if add is not None:
                # Fence: the phase is the real transfer, not its dispatch.
                # It blocks only the lane — attribution costs no overlap.
                jax.block_until_ready(batch_dev)
                add("h2d", time.perf_counter() - t2)
            if lease is not None:
                # Release the ring slot only after the device_put RETIRES:
                # jax may defer the host read of a put numpy buffer, and a
                # released slot is re-zeroed and repacked immediately —
                # an in-flight transfer would ship the next batch's bytes
                # (or zeros) to the device. The block waits on the H2D
                # stream only, on the lane, so the wait hides behind
                # compute (the ParamFlattener stream-ordering argument,
                # applied on the host side).
                with span("lane.retire", step=step):
                    jax.block_until_ready(batch_dev)
                    lease.release()
            if self.obs is not None and trace is not None:
                self.obs.tracer.hop_batch("h2d", trace)
            return batch_dev, env_steps, t2 - t0, time.perf_counter() - t2, trace
        with timeline("lane.device_put", step=step):
            if self._n_proc > 1:
                batch_dev = jax.tree.map(
                    lambda arr, sh: jax.make_array_from_process_local_data(sh, np.asarray(arr)),
                    batch,
                    self.batch_sharding,
                )
            else:
                batch_dev = jax.device_put(batch, self.batch_sharding)
        if add is not None:
            jax.block_until_ready(batch_dev)
            add("h2d", time.perf_counter() - t1)
        if self.obs is not None and trace is not None:
            self.obs.tracer.hop_batch("h2d", trace)
        return batch_dev, env_steps, t1 - t0, time.perf_counter() - t1, trace

    def run(
        self,
        num_steps: Optional[int] = None,
        batch_timeout: float = 60.0,
        max_idle: Optional[int] = None,
        max_seconds: Optional[float] = None,
    ) -> int:
        """Train until num_steps (None = forever); returns steps done.

        `max_idle`: raise TimeoutError after this many CONSECUTIVE empty
        batch waits (None = retry forever, the service default). Drivers
        with a finite budget set it so dead producers surface as an error
        instead of an infinite 'no batch; waiting' loop.

        `max_seconds`: stop cleanly once this much wall clock has elapsed
        (checked between steps) — for soak/bench drivers with a time
        budget rather than a step budget.

        Loop shape: a PrefetchLane thread stages batch N+1 while the
        device executes step N (_loop).
        """
        self.staging.start()
        self.publisher.start()
        done_steps = 0
        # The latest dispatched metrics handle, shared with the finally
        # fence: an exception mid-loop must still drain the in-flight
        # device step before the staging/publisher teardown.
        metrics_box = [None]
        try:
            # Inside the try so a failed publish or first fetch still
            # stops the staging/publisher threads (a leaked consumer
            # would silently eat broker frames for the process lifetime).
            self._fetch_step = self.version + 1
            with span("setup.publish0", version=self.version):
                self.publish_weights()  # synchronous, so actors align immediately
            deadline = time.monotonic() + max_seconds if max_seconds is not None else None

            def _bt() -> float:
                # Fetch waits must respect the wall-clock budget, or the
                # final batch wait overshoots the deadline by up to
                # batch_timeout (observed: a 35s soak window returning
                # 120s late because producers had exited).
                if self._drain.is_set() or self._abort.is_set():
                    # Draining/aborting: never park against the full
                    # batch timeout — the drain budget is wall clock.
                    return 0.2
                if deadline is None:
                    return batch_timeout
                return max(0.05, min(batch_timeout, deadline - time.monotonic()))

            done_steps = self._loop(
                num_steps, batch_timeout, max_idle, deadline, _bt, metrics_box
            )
        finally:
            if metrics_box[0] is not None:
                jax.block_until_ready(metrics_box[0])
            self.staging.stop()
            self.publisher.stop()
            # flush, don't close: run() is re-entrant (phased drivers call
            # it repeatedly); close() below releases the logger for good
            self.metrics.flush()
        return done_steps

    def _loop(
        self, num_steps, batch_timeout, max_idle, deadline, _bt, metrics_box
    ) -> int:
        """The loop of one run(): a PrefetchLane thread runs the whole
        host side of batch N+1 — staging pop, pack wait, device_put
        dispatch, retire, ring-lease release — while the device executes
        step N, so the loop thread's per-iteration host cost is one
        queue pop + the async train-step dispatch. The lane is the single
        staging consumer, so batches are trained FIFO. The SIGTERM drain
        trains out every batch the lane holds (the "exhausted" sentinel
        lands FIFO-last), and the lane's fetch budget is capped at
        num_steps so a phased run never eats a trailing batch."""
        cfg = self.cfg
        compute = self.obs.compute if self.obs is not None else None
        timer = compute.timer if compute is not None else None
        flight = self._flight
        flight.begin_run()
        # The lane's staging wait is cancellable at teardown via the
        # lane's stop event — a stopping lane must never sit out a full
        # batch timeout (nor overlap a successor lane's pops on a phased
        # driver's next run()).
        cancel = threading.Event()
        lane = PrefetchLane(
            lambda: self._fetch_next(_bt(), cancel=cancel),
            limit=num_steps,
            drain=self._drain,
            abort=self._abort,
            upstream_drained=lambda: self.staging.drained(include_prefetch=False),
            stop_event=cancel,
        )
        self._prefetch_lane = lane
        lane.start()
        done_steps = 0
        win_wait = win_put = win_take = 0.0
        # The longest interval between two consecutive dispatches in the
        # metrics window, less what the loop spent blocked in a metrics
        # sync inside it (that is the device's time, and `loop.sync` has
        # it): how long a publish, or anything else on the host, kept the
        # loop from handing the device its next step.
        win_gap = 0.0
        t_dispatch = None
        win_env_steps = 0
        win_steps = 0
        t_win = time.perf_counter()
        metrics = None
        idle = 0
        try:
            while num_steps is None or done_steps < num_steps:
                # Take the next prefetched item, staying responsive to
                # abort/deadline in 0.2s slices (the lane's fetch waits
                # park against _bt() on its own thread).
                item = None
                t_take0 = time.perf_counter()
                with timeline("loop.take", step=self.version + 1):
                    while item is None:
                        if self._abort.is_set():
                            break
                        if deadline is not None and time.monotonic() >= deadline:
                            break
                        try:
                            item = lane.get(timeout=0.2)
                        except queue.Empty:
                            continue
                if item is None:
                    break  # abort / deadline
                take_s = time.perf_counter() - t_take0
                flight.charge("take", take_s)
                if item.kind == "error":
                    raise item.error
                if item.kind == "exhausted":
                    # Drain complete: the lane emits this sentinel ONLY
                    # under a set _drain (budget exhaustion ends the
                    # lane silently — the loop's own step bound ends
                    # us), it proved nothing more can arrive upstream,
                    # and FIFO put every remaining batch ahead of it —
                    # everything the drain owed is trained out.
                    break
                if item.kind == "idle":
                    # Starvation must read LOUD: the wall spent polling for
                    # this (empty) item is exposed loop wait — charge it
                    # to the take accumulator and the timer's fetch
                    # phase (compute_phase_fetch_frac is the watchdog's
                    # starvation signal), not the device residual. A
                    # starved window's fetch mean may exceed its wall
                    # mean — the documented, intended read.
                    win_take += take_s
                    win_wait += item.wait_s
                    if timer is not None:
                        timer.add("fetch", take_s)
                    if self._drain.is_set():
                        continue  # the lane signals "exhausted" when done
                    idle += 1
                    if max_idle is not None and idle >= max_idle:
                        raise TimeoutError(
                            f"no batch for {idle} consecutive {batch_timeout:.0f}s waits "
                            f"— producers dead or stalled"
                        )
                    _log.warning("no batch within %.0fs; waiting", batch_timeout)
                    continue
                idle = 0
                win_take += take_s
                win_wait += item.wait_s
                win_put += item.put_s
                if timer is not None:
                    # Loop-lane "fetch" = the EXPOSED wait for a
                    # prefetched batch: host time the lane failed to
                    # hide (the device idles in it only where no step is
                    # queued: `flight` counts that part).
                    timer.add("fetch", take_s)
                batch_dev, env_steps, batch_trace = item.batch, item.env_steps, item.trace
                t_pass = time.perf_counter()
                if t_dispatch is not None:
                    win_gap = max(win_gap, t_pass - t_dispatch)
                t_dispatch = t_pass
                # Async dispatch: returns immediately, device runs the
                # step; the lane is already staging batch N+1 beside it.
                self.state, metrics = self._dispatch(batch_dev)
                metrics_box[0] = metrics
                if self.obs is not None and batch_trace is not None:
                    self.obs.tracer.hop_batch("apply", batch_trace)
                    self.obs.tracer.e2e(batch_trace)
                self.version += 1
                done_steps += 1
                self.env_steps_done += env_steps
                win_env_steps += env_steps
                win_steps += 1
                last = num_steps is not None and done_steps >= num_steps

                t_host = time.perf_counter()
                if self.version % cfg.publish_every == 0 and self._primary:
                    self._submit_publish()
                if self.checkpointer is not None and self.version % cfg.checkpoint_every == 0:
                    with flight.during("checkpoint"), span("loop.checkpoint", version=self.version):
                        self.checkpoint()

                if timer is not None:
                    # No per-step fence. device_step is
                    # the UNFENCED residual — the in-flight device
                    # window from the loop's clock — so the loop-lane
                    # phases tile the wall by construction; the causal
                    # fetch/pack/h2d split lives in the lane's own
                    # pipeline_* sums (recorded fenced, on the lane).
                    t_end = time.perf_counter()
                    host_s = t_end - t_host
                    timer.add("host", host_s)
                    wall = t_end - t_take0
                    timer.add("device_step", max(wall - take_s - host_s, 0.0))
                    timer.step(wall)

                if self.version % cfg.metrics_every == 0 or last:
                    now = time.perf_counter()
                    t_dispatch += self._log_window(
                        metrics, now, t_win, win_steps, win_env_steps,
                        win_wait, win_put, win_take, win_gap,
                    )
                    win_wait = win_put = win_take = win_gap = 0.0
                    win_env_steps = win_steps = 0
                    t_win = now
                    # The device has had nothing since the sync found it
                    # done: the read and the window's bookkeeping.
                    flight.charge("sync", time.perf_counter() - flight.t_known)
        finally:
            lane.stop()
            self._prefetch_lane = None
        return done_steps

    def _log_window(
        self,
        metrics,
        now: float,
        t_win: float,
        win_steps: int,
        win_env_steps: int,
        win_wait: float,
        win_put: float,
        win_take: float,
        win_gap: float,
    ) -> float:
        """One metrics window — the ONLY routine device sync in the loop
        (the wait for the newest step's metrics, then their read).
        `win_wait`/`win_put` are
        the lane's fetch wait and device_put, `win_take` the loop's
        exposed take-wait and `win_gap` its longest interval between two
        dispatches. Returns the seconds the sync blocked."""
        compute = self.obs.compute if self.obs is not None else None
        t_sync = time.perf_counter()
        with span("loop.sync", step=self.version):
            # The wait for the newest dispatched step, then the read of
            # its few scalars: the device's time and the host's, apart.
            with span("loop.sync_ready", step=self.version):
                jax.block_until_ready(metrics)
            self._flight.synced()
            with span("loop.sync_get", step=self.version):
                scalars = {k: float(v) for k, v in jax.device_get(metrics).items()}
        sync_s = time.perf_counter() - t_sync
        stats = self.staging.stats()
        dt = max(now - t_win, 1e-9)
        n = max(win_steps, 1)
        scalars["env_steps_per_sec"] = win_env_steps / dt
        # per-stage split (SURVEY.md §5): window averages. time_step_s is
        # the residual — device step + dispatch + publish-get — since the
        # loop never syncs per step.
        scalars["time_wait_batch_s"] = win_wait / n
        scalars["time_device_put_s"] = win_put / n
        # wait/put were paid on the prefetch lane, overlapping the device
        # step — only the take-wait is exposed loop time, so the residual
        # subtracts just that. The pipeline_* family carries the overlap
        # accounting (the phase timer refines these with fenced lane sums
        # when step_phases is on — same keys, logged after).
        lane_s = win_wait + win_put
        scalars["time_step_s"] = max(dt - win_take, 0.0) / n
        scalars["pipeline_prefetch_s"] = lane_s / n
        scalars["pipeline_device_idle_s"] = win_take / n
        scalars["pipeline_overlap_ratio"] = (
            max(0.0, min(1.0, 1.0 - win_take / lane_s)) if lane_s > 0 else 1.0
        )
        scalars["loop_dispatch_gap_max_s"] = win_gap
        scalars.update(self._flight.window_scalars())
        scalars["active_actors"] = stats["active_actors"]
        scalars["staleness_dropped"] = stats["dropped_stale"]
        scalars["staging_quarantined"] = stats["quarantined"]
        scalars["queue_ready"] = stats["ready_batches"]
        scalars["episodes"] = stats["episodes"]
        # Experience-wire meters (DTR3 quantized wire): bytes
        # entering the staging intake and the fleet's frame
        # split by obs wire dtype — the consumers-first
        # rolling upgrade's progress gauge.
        scalars["wire_bytes_consumed_total"] = stats["wire_bytes"]
        scalars["wire_frames_obs_bf16_total"] = stats["wire_frames_obs_bf16"]
        scalars["wire_frames_obs_f32_total"] = stats["wire_frames_obs_f32"]
        # Broker-fabric scoreboard (broker_shard_* / fanin_* registry
        # prefix families): per-shard pop/starve meters and the
        # fence/dedup ledgers. Pure local counters (no RPC); present
        # only when --broker_url is a shard list, so classic runs emit
        # nothing new.
        fabric_stats = getattr(self.broker, "fabric_stats", None)
        if fabric_stats is not None:
            for k, v in fabric_stats().items():
                scalars[k] = float(v)
        # Parallel host feed scoreboard (staging_pack_*, registry prefix
        # family): per-worker busy/stall seconds, ring occupancy/wait,
        # packer-proper rows/s. The pack_* keys exist only when
        # --staging.pack_workers > 1, so default runs emit nothing new.
        for k, v in stats.items():
            if k.startswith("pack_"):
                scalars[f"staging_{k}"] = float(v)
        # Replay reservoir health (replay.enabled only): occupancy, hit
        # ratio, replayed-frame age histogram buckets, bytes spilled —
        # all pre-flattened scalars.
        for k, v in stats.items():
            if k.startswith("replay_"):
                scalars[k] = v
        scalars["weights_published"] = self.publisher.published
        scalars["weights_coalesced"] = self.publisher.coalesced
        scalars["weights_publish_failed"] = self.publisher.failed
        # Every span of this process (obs/spans.py), cumulative, and the
        # compile counters: loop, lane, staging, publisher, set-up.
        scalars.update(spans.scalars())
        if self.checkpointer is not None:
            # Remote-mirror health (ADVICE r4): a growing lag means
            # uploads can't keep the checkpoint cadence and durability
            # is silently behind.
            for k, v in self.checkpointer.mirror_stats().items():
                if isinstance(v, (int, float)):
                    scalars[f"ckpt_mirror_{k}"] = v
            # Full-state save health (ckpt_* in obs/registry): empty
            # dict (no keys emitted) until the first aux save, so
            # default runs log nothing new.
            for k, v in self.checkpointer.save_stats().items():
                scalars[f"ckpt_{k}"] = float(v)
            if self._ckpt_worker is not None:
                scalars["ckpt_async_saves_total"] = float(self._ckpt_worker.saved)
                scalars["ckpt_async_coalesced_total"] = float(
                    self._ckpt_worker.coalesced
                )
        if self._resume_scalars:
            # One-shot: the restore's provenance rides the first logged
            # window, then clears.
            scalars.update(self._resume_scalars)
            self._resume_scalars = {}
        if stats["episodes"] > 0:
            scalars["mean_episode_return"] = stats["episode_return_sum"] / stats["episodes"]
        if self.obs is not None:
            # Per-stage pipeline latency histograms + the e2e
            # actor→apply decomposition (obs/trace.py). Empty until
            # traced frames flow (actors opted in).
            scalars.update(self.obs.tracer.scalars())
        if compute is not None:
            # compute_* families (obs/compute.py): phase means over this
            # window (every pass fully closed — the loop closes the pass
            # before logging), the fenced pipeline_* lane sums, cumulative
            # recompile counters, cumulative MFU.
            scalars.update(compute.window_scalars(win_steps, dt))
        self.metrics.log(self.version, scalars)
        _log.info(
            "step %d: loss=%.6g env_steps_per_sec=%.1f time_step_s=%.5f",
            self.version,
            scalars["loss"],
            scalars["env_steps_per_sec"],
            scalars["time_step_s"],
        )
        return sync_s

    def close(self) -> None:
        if self._ckpt_worker is not None:
            # Drain (not discard) a pending async save: close() after a
            # normal finish must leave the newest submitted step durable.
            self._ckpt_worker.stop(flush=True)
        if self.checkpointer is not None:
            self.checkpointer.close()  # drains the aux + mirror workers
        if self.obs is not None:
            self.obs.close()
        self.metrics.close()


def main(argv=None):
    from dotaclient_tpu.config import parse_config
    from dotaclient_tpu.runtime.device import init_devices, use_compile_cache
    from dotaclient_tpu.transport.base import connect as broker_connect

    logging.basicConfig(level=logging.INFO)
    cfg = parse_config(LearnerConfig(), argv)
    cache = use_compile_cache()
    if cfg.multihost:
        # Must run before any backend touch: after this, jax.devices()
        # spans every process's chips and the existing mesh/shardings
        # scale across hosts with zero further changes. Each kwarg is
        # passed independently — an unset flag ("" / -1) defers to jax's
        # cluster-env/metadata auto-detection, a set one overrides it.
        kw = {}
        if cfg.coordinator:
            kw["coordinator_address"] = cfg.coordinator
        if cfg.num_processes >= 0:
            kw["num_processes"] = cfg.num_processes
        if cfg.process_id >= 0:
            kw["process_id"] = cfg.process_id
        jax.distributed.initialize(**kw)
    init_devices(cfg.platform, "learner")
    from dotaclient_tpu.transport.base import RetryPolicy

    broker = broker_connect(cfg.broker_url, retry=RetryPolicy.from_config(cfg.retry))
    if cfg.broker_shards:
        # Multi-learner fan-in (--broker_shards "0,1"): pin this learner
        # to a disjoint shard subset of the fabric. Only meaningful
        # against a shard-list broker_url — anything else is a deploy
        # mistake that must fail boot loudly, not silently consume the
        # whole queue.
        restrict = getattr(broker, "restrict_consume_shards", None)
        if restrict is None:
            raise ValueError(
                f"--broker_shards={cfg.broker_shards!r} needs a broker fabric "
                f"(comma-separated --broker_url shard list); got "
                f"{cfg.broker_url!r}"
            )
        restrict([int(s) for s in cfg.broker_shards.split(",") if s.strip()])
    if cfg.chaos.enabled:
        # Gated import — chaos off means the package never loads and the
        # broker is the production object (tests/test_chaos.py).
        from dotaclient_tpu.chaos import wrap_broker

        broker = wrap_broker(broker, cfg.chaos)
    learner = Learner(cfg, broker)
    if cfg.ckpt.drain_on_sigterm:
        # SIGTERM → drain: stop fetching, finish the in-flight step,
        # train out staged batches, save full state, exit 0 — inside
        # --ckpt.drain_budget_s (k8s pairs terminationGracePeriodSeconds
        # with it). Installed AFTER Learner.__init__ so it supersedes the
        # flight recorder's SIGTERM dump trigger (a drain is clean).
        learner.install_drain_handler()
    _log.info(
        "learner ready: mesh=%s batch=%dx%d packer=%s",
        dict(learner.mesh.shape),
        cfg.batch_size,
        cfg.seq_len,
        "native" if learner.staging.native else "python",
    )
    try:
        learner.run(num_steps=cfg.train_steps or None)
        if learner.drain_requested and not learner.aborted:
            learner.drain_save()
            _log.info("SIGTERM drain complete at version %d; exiting 0", learner.version)
    finally:
        learner.close()
        stats = learner.staging.stats()
        _log.info(
            "learner done: version=%d env_steps=%d wire_frames=%d weights_published=%d "
            "compile_cache=%s hits=%d misses=%d attn_fused_layers=%d moe_passes=%d",
            learner.version,
            learner.env_steps_done,
            stats["wire_frames_obs_f32"] + stats["wire_frames_obs_bf16"],
            learner.publisher.published,
            cache.dir,
            cache.hits,
            cache.misses,
            # layers of the compiled unroll whose attention took the fused
            # kernel, as the last metrics window had it (transformer family)
            learner.metrics.latest().get("attn_fused_layers", 0),
            # passes of the routed-expert layers over their buffers (ops/moe.py
            # buffer_rows; one a layer where the held pairs fit), same window
            learner.metrics.latest().get("moe_passes", 0),
        )


if __name__ == "__main__":
    main()
