"""Benchmark: end-to-end PPO learner throughput (host pipeline + TPU step).

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "...", "vs_baseline": N}

Metric of record (BASELINE.md): learner env-steps/sec. This measures the
FULL learner path — broker consume → deserialize → staleness filter →
pack/pad → device_put (dp-sharded) → compiled SPMD PPO train step — fed
by an in-process producer republishing pre-serialized rollout frames, at
the flagship configuration (128-hidden LSTM policy, bf16 compute, batch
256 × seq 16). The device-only step rate is reported inside `unit` for
context; the headline value is the end-to-end rate, which is what
saturating actors could actually achieve against this learner host.

Baseline: 50k aggregate env-steps/sec on a v5e-8 (north star), scaled to
the visible chip count (50k/8 per chip).

Runs on a TPU only: where `jax.devices()` is another platform it exits
non-zero and prints no rate. Every result names its device (`platform`,
`device_kind`, `device_count`).
"""

from __future__ import annotations

import json
import threading
import time

import jax
import numpy as np

from dotaclient_tpu.config import LearnerConfig
from dotaclient_tpu.parallel import mesh as mesh_lib
from dotaclient_tpu.parallel.train_step import (
    init_train_state,
    make_train_batch,
)
from dotaclient_tpu.runtime.device import init_devices, use_compile_cache
from dotaclient_tpu.runtime.staging import StagingBuffer
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import connect

BASELINE_AGGREGATE = 50_000.0  # env-steps/sec on a v5e-8 (BASELINE.md)
BASELINE_PER_CHIP = BASELINE_AGGREGATE / 8.0


def _make_frames(cfg: LearnerConfig, n_frames: int):
    """Pre-serialized realistic rollout frames (length = seq_len)."""
    from dotaclient_tpu.ops.batch import TrainBatch  # noqa: F401
    from dotaclient_tpu.transport.serialize import Rollout, serialize_rollout
    from dotaclient_tpu.env import featurizer as F
    from dotaclient_tpu.ops.action_dist import Action

    frames = []
    T = cfg.seq_len
    H = cfg.policy.lstm_hidden
    r = np.random.RandomState(0)
    for i in range(n_frames):
        T1 = T + 1
        obs = F.Observation(
            global_feats=r.randn(T1, F.GLOBAL_FEATURES).astype(np.float32),
            hero_feats=r.randn(T1, F.HERO_FEATURES).astype(np.float32),
            unit_feats=r.randn(T1, F.MAX_UNITS, F.UNIT_FEATURES).astype(np.float32),
            unit_mask=r.rand(T1, F.MAX_UNITS) < 0.6,
            target_mask=r.rand(T1, F.MAX_UNITS) < 0.3,
            action_mask=np.ones((T1, F.N_ACTION_TYPES), bool),
        )
        rollout = Rollout(
            obs=obs,
            actions=Action(
                type=r.randint(0, 2, T).astype(np.int32),
                move_x=r.randint(0, 9, T).astype(np.int32),
                move_y=r.randint(0, 9, T).astype(np.int32),
                target=np.zeros(T, np.int32),
            ),
            behavior_logp=(-1.5 + 0.1 * r.randn(T)).astype(np.float32),
            behavior_value=r.randn(T).astype(np.float32) * 0.1,
            rewards=(r.randn(T) * 0.1).astype(np.float32),
            dones=np.zeros(T, np.float32),
            initial_state=(np.zeros(H, np.float32), np.zeros(H, np.float32)),
            version=0,
            actor_id=i,
        )
        frames.append(serialize_rollout(rollout))
    return frames


def _start_producers(cfg, broker_name: str, n_threads: int = 2):
    """Producer threads republishing pre-serialized frames.

    Depth-throttled: keep the queue comfortably full (≥2 batches ready),
    then yield. Unthrottled spin-publishing into a bounded drop-oldest
    queue models nothing real: actors never outrun the learner a
    hundredfold, and it burns the host cores the feed pipeline runs on.
    """
    mem.reset(broker_name)
    producer_conn = connect(f"mem://{broker_name}", maxlen=cfg.batch_size * 4)
    frames = _make_frames(cfg, 512)
    stop = threading.Event()
    high_water = cfg.batch_size * 3

    def producer():
        i = 0
        while not stop.is_set():
            if producer_conn.experience_depth() >= high_water:
                time.sleep(0.001)
                continue
            producer_conn.publish_experience(frames[i % len(frames)])
            i += 1

    threads = [threading.Thread(target=producer, daemon=True) for _ in range(n_threads)]
    for t in threads:
        t.start()
    return stop


def main() -> None:
    import os

    use_compile_cache()
    # The chip this benchmark measures: without a TPU the backend init
    # fails here — a rate taken on a CPU is not this metric.
    devices = init_devices("tpu", "bench")
    n_dev = len(devices)
    cfg = LearnerConfig(batch_size=256, seq_len=16, mesh_shape="dp=-1")
    # Parallel host feed (--staging.pack_workers): opt-in via env so the
    # number of record stays comparable across rounds until the flag
    # flips in production; scripts/ab_pack_scale.py owns the scaling
    # artifact.
    pack_workers = int(os.environ.get("DOTACLIENT_TPU_BENCH_PACK_WORKERS", "1") or 1)
    cfg.staging.pack_workers = pack_workers
    mesh = mesh_lib.make_mesh(cfg.mesh_shape)
    # The production flagship path, exactly what the Learner runs with
    # default config: fused single-buffer H2D (one [B, row_bytes] u8
    # put per batch) + host-side bf16 obs cast.
    from dotaclient_tpu.parallel.train_step import build_single_train_step
    from dotaclient_tpu.runtime.staging import cast_obs_to_compute_dtype

    train_step, state_sh, io = build_single_train_step(cfg, mesh)
    state = jax.device_put(init_train_state(cfg, jax.random.PRNGKey(0)), state_sh)

    # ---- device-only rate (context): pre-packed batch, no host pipeline.
    # Routed through the same cast+pack as staging, so this section times
    # the ONE executable production runs (and the e2e section below hits
    # the already-compiled program instead of compiling a second one).
    host_batch = cast_obs_to_compute_dtype(cfg, jax.tree.map(np.asarray, make_train_batch(cfg, 0)))
    batch = jax.device_put(io.pack_transfer(host_batch), io.sharding)
    state, metrics = train_step(state, batch)
    jax.block_until_ready(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(20):
        state, metrics = train_step(state, batch)
    jax.block_until_ready(metrics["loss"])
    device_rate = cfg.batch_size * cfg.seq_len * 20 / (time.perf_counter() - t0)

    # ---- host-pipeline-only rate: broker → staging → packed batches,
    # no device work (VERDICT r2 item 5: prove host packing headroom)
    stop = _start_producers(cfg, "bench_pack")
    # fused_io=io: staging packs straight into the dtype-grouped transfer
    # buffers (the production path), so this rate covers pack+regroup.
    staging = StagingBuffer(
        cfg, connect("mem://bench_pack"), version_fn=lambda: 0, fused_io=io
    ).start()
    def _release_lease():
        # Ring mode (pack_workers > 1): a popped batch carries a
        # TransferRing lease that must return to the packers, or the
        # host-pipeline rate would stall at transfer_depth batches. The
        # batch is not device_put in this section, so release directly.
        lease = staging.last_batch_lease
        if lease is not None:
            lease.release()

    staging.get_batch(timeout=120.0)  # pipe warm
    _release_lease()
    pack_steps = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 3.0:
        b = staging.get_batch(timeout=120.0)
        # read BEFORE releasing: b's leaves are views into the slot, and
        # a released slot may be re-zeroed/repacked immediately
        pack_steps += int(np.sum(b.mask))
        _release_lease()
    packer_rate = pack_steps / (time.perf_counter() - t0)
    stop.set()
    staging.stop()

    # ---- in-network assembly headline pair (ISSUE 20): classic host
    # pack CPU vs the concat-only landing --staging.assemble leaves on
    # this host, for the SAME wire frames and the SAME transfer layout.
    # The classic arm is the production pack (C packer into the fused
    # transfer views, python fill fallback); the concat arm lands rows a
    # shard-side RowAssembler pre-packed — one memcpy per row (single
    # buffer) or one per dtype-group segment. scripts/ab_inet_pack.py
    # owns the bitwise-parity/scaling artifact (INET_PACK_AB.json);
    # this pair is the at-a-glance cost collapse.
    from dotaclient_tpu.runtime.staging import fill_rollouts
    from dotaclient_tpu.transport.assemble import RowAssembler
    from dotaclient_tpu.transport.serialize import deserialize_rollout

    asm_frames = _make_frames(cfg, cfg.batch_size)
    _obs_bf16 = cfg.stage_obs_compute_dtype and cfg.policy.dtype == "bfloat16"
    _asm = RowAssembler(
        cfg.seq_len, cfg.policy.lstm_hidden, cfg.policy.aux_heads, _obs_bf16
    )
    _rows = [np.frombuffer(_asm.assemble(f).payload, np.uint8) for f in asm_frames]
    _lib = None
    if cfg.native_packer:
        from dotaclient_tpu import native as _native

        _lib = _native.load_packer()
    _pack_items = (
        asm_frames
        if _lib is not None
        else [deserialize_rollout(f) for f in asm_frames]
    )

    def _time_arm(fn, reps=7):
        walls = []
        for _ in range(reps):
            t = time.perf_counter()
            fn()
            walls.append(time.perf_counter() - t)
        return float(np.median(walls))

    def _classic_pack():
        payload, outb = io.alloc_transfer()
        if _lib is not None:
            _native.pack_frames(
                _lib, _pack_items, cfg.seq_len, cfg.policy.lstm_hidden,
                cfg.policy.aux_heads, obs_bf16=_obs_bf16, out=outb,
            )
        else:
            fill_rollouts(outb, _pack_items, cfg.seq_len)

    _payloads = [bytes(r) for r in _rows]

    def _concat_land():
        payload, _outb = io.alloc_transfer()
        raw = np.frombuffer(b"".join(_payloads), np.uint8).reshape(
            len(_payloads), io.row_bytes
        )
        payload[: len(_payloads)] = raw

    host_pack_cpu_s = _time_arm(_classic_pack)
    host_concat_s = _time_arm(_concat_land)

    # ---- end-to-end rate: producers → broker → staging → device, with
    # the learner's loop: the SAME PrefetchLane the Learner runs stages batch N+1
    # — staging pop, device_put dispatch, transfer retire, lease release
    # — on its own thread while step N executes, INCLUDING the per-step
    # weight publish exactly as Learner.run does it at the default
    # publish_every=1 (one async on-device flatten dispatch on the loop
    # thread; single-buffer host read + serialize on the publisher
    # thread) — the headline covers the full production loop.
    from dotaclient_tpu.runtime.learner import (
        ParamFlattener,
        PrefetchLane,
        WeightPublisher,
    )

    stop = _start_producers(cfg, "bench")
    staging = StagingBuffer(
        cfg, connect("mem://bench"), version_fn=lambda: 0, fused_io=io
    ).start()
    flattener = ParamFlattener(state.params)
    publisher = WeightPublisher(connect("mem://bench"), materialize=flattener.to_named).start()

    def fetch():
        # staging already packed into the transfer buffers; wait bucket
        # = queue wait, device_put_s stays a pure H2D-transfer
        # attribution (mirrors learner._fetch_next — this closure runs
        # on the PrefetchLane thread in the timed loop below)
        t0 = time.perf_counter()
        b, payload = staging.get_batch_groups(timeout=120.0)
        if b is None:
            # a starved pipe must be a diagnosable error, not b.mask
            # on None
            raise RuntimeError("staging starved (timeout)")
        steps = int(np.sum(b.mask))
        lease = staging.last_batch_lease
        t1 = time.perf_counter()
        dev = jax.device_put(payload, io.sharding)
        if lease is not None:
            # ring mode: the slot may be repacked the moment it is
            # released — wait for the transfer to retire first
            # (runtime/learner.py _fetch_next is the production twin)
            jax.block_until_ready(dev)
            lease.release()
        return dev, steps, t1 - t0, time.perf_counter() - t1, None

    warm, _, _, _, _ = fetch()
    state, metrics = train_step(state, warm)
    jax.block_until_ready(metrics["loss"])
    jax.block_until_ready(flattener.flatten_on_device(state.params))  # compile outside the window
    n_iters = 12
    env_steps = 0
    t_wait = t_put = t_take = 0.0
    # t0 BEFORE lane.start(): the lane's first fetch begins immediately,
    # and its wait/put land in the accumulators below — the window must
    # cover that work or lane_work_s counts out-of-window seconds and
    # inflates pipeline_overlap_ratio (item 1's fetch is genuinely
    # exposed — the device has nothing to run yet — and reads as take).
    t0 = time.perf_counter()
    lane = PrefetchLane(fetch, limit=n_iters).start()
    for i in range(n_iters):
        tb = time.perf_counter()
        item = lane.get(timeout=150.0)  # the lane's own fetch bounds at 120s
        t_take += time.perf_counter() - tb
        if item.kind == "error":
            raise item.error
        state, metrics = train_step(state, item.batch)  # async dispatch
        publisher.submit(flattener.flatten_on_device(state.params), i + 1)
        env_steps += item.env_steps
        t_wait += item.wait_s
        t_put += item.put_s
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0
    lane.stop()  # teardown outside the timed window
    publisher.stop()  # outside the timed window: drain is teardown, not loop cost
    stop.set()
    staging.stop()

    e2e_rate = env_steps / dt
    # Overlap accounting (the pipelined loop's scoreboard): lane work =
    # fetch wait + put, exposed loop time = the take-wait; device idle
    # per step is bounded from the measured device-only rate.
    lane_work_s = t_wait + t_put
    pipeline_overlap_ratio = (
        max(0.0, min(1.0, 1.0 - t_take / lane_work_s)) if lane_work_s > 0 else 1.0
    )
    device_s_per_iter = cfg.batch_size * cfg.seq_len / device_rate
    device_idle_s_per_iter = max(dt / n_iters - device_s_per_iter, 0.0)

    # --- per-stage pipeline trace breakdown (dotaclient_tpu/obs/): a
    # short run of the SAME pipeline with trace-stamped (DTR2) frames,
    # reported as mean latency per hop plus the e2e actor→apply scalar.
    # Deliberately OUTSIDE the timed headline window: tracing is opt-in
    # in production and the number of record must stay comparable across
    # rounds. Best-effort — a failure degrades to a missing field.
    trace_breakdown = None
    t_stop = t_staging = None
    try:
        from dotaclient_tpu.obs.trace import PipelineTracer
        from dotaclient_tpu.transport.serialize import stamp_rollout_trace

        mem.reset("bench_trace")
        t_conn = connect("mem://bench_trace", maxlen=cfg.batch_size * 4)
        t_frames = _make_frames(cfg, 256)
        t_stop = threading.Event()

        def traced_producer():
            i = 0
            while not t_stop.is_set():
                if t_conn.experience_depth() >= cfg.batch_size * 3:
                    time.sleep(0.001)
                    continue
                # fresh trace id + birth per publish — the per-frame
                # stamp copy is exactly what a traced actor pays
                t_conn.publish_experience(
                    stamp_rollout_trace(t_frames[i % len(t_frames)], i + 1, time.time())
                )
                i += 1

        tracer = PipelineTracer()
        t_staging = StagingBuffer(
            cfg, connect("mem://bench_trace"), version_fn=lambda: 0,
            fused_io=io, tracer=tracer,
        ).start()
        t_threads = [threading.Thread(target=traced_producer, daemon=True) for _ in range(2)]
        for t in t_threads:
            t.start()
        for _ in range(6):
            b, payload = t_staging.get_batch_groups(timeout=120.0)
            if b is None:
                raise RuntimeError("traced staging starved (timeout)")
            trace = t_staging.last_batch_trace
            dev = jax.device_put(payload, io.sharding)
            if trace is not None:
                tracer.hop_batch("h2d", trace)
            state, metrics = train_step(state, dev)
            if trace is not None:
                tracer.hop_batch("apply", trace)
                tracer.e2e(trace)
        jax.block_until_ready(metrics["loss"])
        sc = tracer.scalars()
        trace_breakdown = {
            k.replace("trace_", "").replace("_mean_ms", "_ms"): round(v, 3)
            for k, v in sc.items()
            if k.endswith("_mean_ms")
        }
        if "trace_e2e_actor_apply_s" in sc:
            trace_breakdown["e2e_actor_apply_s"] = round(sc["trace_e2e_actor_apply_s"], 4)
    except Exception as e:
        trace_breakdown = {"error": f"{type(e).__name__}: {e}"[:200]}
    finally:
        if t_stop is not None:
            t_stop.set()
        if t_staging is not None:
            t_staging.stop()

    # --- compute decomposition (obs/compute.py, ISSUE 3): fenced
    # per-phase timing of the SAME compiled step plus the recompile
    # sentinel — OUTSIDE the timed headline window, because the fencing
    # deliberately destroys the prefetch overlap the headline measures.
    # recompiles MUST read 0 here (one steady batch shape feeds the
    # section); a nonzero count means the bench itself has a shape bug.
    compute_section = None
    try:
        from dotaclient_tpu.obs.compute import RecompileSentinel, StepPhaseTimer

        sentinel = RecompileSentinel(train_step, label="bench_train_step")
        ph = StepPhaseTimer()
        for _ in range(4):
            t0p = time.perf_counter()
            groups_p = io.pack_transfer(host_batch)
            t1p = time.perf_counter()
            ph.add("pack", t1p - t0p)
            dev_p = jax.device_put(groups_p, io.sharding)
            jax.block_until_ready(dev_p)
            t2p = time.perf_counter()
            ph.add("h2d", t2p - t1p)
            state, metrics = sentinel(state, dev_p)
            jax.block_until_ready(metrics["loss"])
            t3p = time.perf_counter()
            ph.add("device_step", t3p - t2p)
            ph.step(t3p - t0p)
        sc = ph.window_scalars()
        compute_section = {
            "phase_pack_s": round(sc["compute_phase_pack_s"], 5),
            "phase_h2d_s": round(sc["compute_phase_h2d_s"], 5),
            "phase_device_step_s": round(sc["compute_phase_device_step_s"], 5),
            "phase_wall_s": round(sc["compute_phase_wall_s"], 5),
            "recompiles": sentinel.recompiles,
            "first_call_s": round(sentinel.last_compile_s, 4),
            "note": "fenced per-phase split outside the headline window; "
            "fetch/host are learner-loop phases a pre-packed bench batch "
            "does not exercise",
        }
    except Exception as e:
        compute_section = {"error": f"{type(e).__name__}: {e}"[:200]}

    # --- transfer-layout A/B (informational, best-effort): the same
    # batch H2D as 17 pytree leaves (the tree path sp and replay take)
    # vs the ONE [B, row_bytes] u8 buffer.
    transfer_ab = None
    try:
        one_buf = io.pack_transfer(host_batch)  # the host batch from the device-only section
        sh = io.sharding

        def _time_put(payload, shardings, reps=8):
            jax.block_until_ready(jax.device_put(payload, shardings))  # warm
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(jax.device_put(payload, shardings))
            return (time.perf_counter() - t0) / reps

        transfer_ab = {
            "tree_17_leaves_ms": round(
                _time_put(host_batch, jax.tree.map(lambda _: sh, host_batch)) * 1e3, 3
            ),
            "single_buffer_ms": round(_time_put(one_buf, sh) * 1e3, 3),
            "bytes": int(one_buf.nbytes),
            "note": "blocked device_put of the same batch, dp-sharded rows",
        }
    except Exception:
        pass

    # --- FLOPs / MFU / boundary-bytes accounting (SURVEY §6: normalize
    # steps/s into utilization). Analytic matmul model + XLA's own count.
    # The WHOLE block is best-effort: by this point the e2e measurement is
    # complete, and an exception in informational accounting must degrade
    # to missing fields, never lose a measured number.
    model_flops = achieved_flops = peak = h2d_bytes = d2h_bytes = None
    h2d_obs_bytes = wire_step_bytes = wire_step_bytes_bf16 = pack_obs_dtype = None
    try:
        # Experience-wire accounting (ISSUE 8): serialized bytes per env
        # step for the frames these producers actually shipped (default
        # f32 wire) and for the DTR3 bf16 wire at the same shapes — the
        # broker/TCP/staging-intake cost per step, distinct from h2d.
        from dotaclient_tpu.transport.serialize import (
            cast_rollout_obs_bf16,
            deserialize_rollout,
            serialize_rollout,
        )

        _wire_frame = _make_frames(cfg, 1)[0]
        wire_step_bytes = len(_wire_frame) / cfg.seq_len
        wire_step_bytes_bf16 = (
            len(serialize_rollout(cast_rollout_obs_bf16(deserialize_rollout(_wire_frame))))
            / cfg.seq_len
        )
    except Exception:
        pass
    try:
        from dotaclient_tpu.ops import flops as flops_mod

        model_flops = flops_mod.train_step_flops(cfg)
        # ADVICE r4: derive achieved FLOP/s from the CONSUMED learner-step
        # rate (n_iters / dt), not by back-dividing the masked env-step
        # rate — the device computes all B*(T+1) frames regardless of
        # mask, so padding in the replayed rollouts would systematically
        # underreport MFU.
        updates_per_sec = n_iters / dt
        achieved_flops = model_flops * updates_per_sec
        peak = flops_mod.peak_flops_for(devices[0])
        # From the ACTUAL staged transfer payload, never an assumed f32
        # layout: `batch` is the dtype-grouped buffers the loop really
        # ships, so the obs floats count at their staged width (bf16
        # under the default compute-dtype cast, f32 only when staging
        # ships f32) — assuming f32 here would overreport the obs share
        # 2x and hide the bf16-at-rest win.
        h2d_bytes = sum(
            np.dtype(b.dtype).itemsize * int(np.prod(b.shape)) for b in jax.tree.leaves(batch)
        )
        obs_float_leaves = (
            host_batch.obs.global_feats,
            host_batch.obs.hero_feats,
            host_batch.obs.unit_feats,
        )
        h2d_obs_bytes = sum(int(l.nbytes) for l in obs_float_leaves)
        pack_obs_dtype = np.dtype(obs_float_leaves[0].dtype).name
        d2h_bytes = 4 * sum(
            int(np.prod(l.shape, dtype=np.int64)) if l.ndim else 1
            for l in jax.tree.leaves(state.params)
        )  # fused f32 publish buffer (ParamFlattener)
    except Exception:
        pass

    baseline = BASELINE_PER_CHIP * n_dev
    out = {
        "metric": "ppo_learner_env_steps_per_sec",
        # The device this rate was measured on, as JAX reports it.
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": n_dev,
        "value": round(e2e_rate, 1),
        "unit": (
            f"env-steps/sec end-to-end ({n_dev} chip(s), "
            f"batch {cfg.batch_size}x{cfg.seq_len}; device-step-only rate "
            f"{round(device_rate, 1)}; host-packer-only rate {round(packer_rate, 1)})"
        ),
        "vs_baseline": round(e2e_rate / baseline, 3),
        # per-stage split, seconds per iteration averaged over the run.
        # Pipelined loop: wait/put are PREFETCH-LANE time (overlapping
        # the device step); residual = wall minus the exposed take-wait.
        "split": {
            "wait_batch_s": round(t_wait / n_iters, 5),
            "device_put_s": round(t_put / n_iters, 5),
            "take_wait_s": round(t_take / n_iters, 5),
            "residual_step_s": round(max(dt - t_take, 0.0) / n_iters, 5),
        },
        "device_only_steps_per_sec": round(device_rate, 1),
        "packer_only_steps_per_sec": round(packer_rate, 1),
        # host-feed topology of this run (scripts/ab_pack_scale.py owns
        # the 1/2/4-worker scaling artifact, PACK_SCALE_AB.json)
        "pack_workers": pack_workers,
        # In-network assembly cost pair (ISSUE 20): classic host pack
        # CPU per batch vs the concat-only landing left on this host
        # when the fabric shards pre-pack (--broker.assemble +
        # --staging.assemble); same frames, same transfer layout
        # (INET_PACK_AB.json is the bitwise-parity artifact).
        "host_pack_cpu_s_per_batch": round(host_pack_cpu_s, 6),
        "host_concat_s_per_batch": round(host_concat_s, 6),
        "e2e_over_device_only": round(e2e_rate / device_rate, 3),
        # Prefetch-lane scoreboard:
        # share of prefetch-lane work hidden behind the device step, the
        # lane's per-iteration busy time, the loop's exposed take-wait,
        # and device idle bounded from the measured device-only rate.
        "pipeline_overlap_ratio": round(pipeline_overlap_ratio, 3),
        "pipeline": {
            "prefetch_s_per_iter": round(lane_work_s / n_iters, 5),
            "take_wait_s_per_iter": round(t_take / n_iters, 5),
            "device_idle_s_per_iter": round(device_idle_s_per_iter, 5),
        },
        # Utilization accounting (SURVEY §6): analytic matmul FLOPs/step
        # (ops/flops.py, fwd+bwd), achieved FLOP/s at the e2e rate, and
        # MFU against the device's public peak (keyed by device_kind).
        "flops_per_step_model": round(model_flops) if model_flops else None,
        "achieved_flops_per_sec": round(achieved_flops) if achieved_flops else None,
        "mfu_pct": round(100.0 * achieved_flops / (peak * n_dev), 3)
        if peak and achieved_flops
        else None,
        "h2d_bytes_per_iter": int(h2d_bytes) if h2d_bytes else None,
        # obs-float share of h2d at the ACTUAL staged dtype, and that
        # dtype by name — the BENCH_r0N trajectory for the bf16-at-rest
        # transfer win (pack_path_obs_dtype "bfloat16" = the cast-free
        # native pack + halved obs transfer; "float32" = staging cast off)
        "h2d_obs_bytes_per_iter": int(h2d_obs_bytes) if h2d_obs_bytes else None,
        "pack_path_obs_dtype": pack_obs_dtype,
        # serialized wire bytes per env step: as shipped by these
        # producers (f32 default wire) and at the DTR3 bf16 wire for the
        # same shapes (the --wire.obs_dtype bf16 broker/intake saving)
        "wire_bytes_per_env_step": round(wire_step_bytes, 1) if wire_step_bytes else None,
        "wire_bytes_per_env_step_bf16": round(wire_step_bytes_bf16, 1)
        if wire_step_bytes_bf16
        else None,
        "d2h_bytes_per_iter": int(d2h_bytes) if d2h_bytes else None,
        "transfer_layout_ab": transfer_ab,
        # mean ms per pipeline hop from the traced section (obs/trace.py
        # hop chain: consume → staging_admit → pack → h2d → apply) + e2e
        "trace_stage_breakdown": trace_breakdown,
        # fenced pack/h2d/device-step split + recompile sentinel count
        # from the post-headline compute section (obs/compute.py)
        "compute_breakdown": compute_section,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
