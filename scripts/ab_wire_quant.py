"""A/B: quantized experience wire (DTR3 bf16) vs the legacy f32 wire.

ISSUE 8 acceptance artifact. At matched seeds (the SAME seeded rollouts
feed every arm), measures the four claims that make the bf16 wire a pure
win rather than a numerics trade:

1. wire_bytes   — serialized bytes per env step, f32 vs bf16 frames:
                  the obs share (the only part the cast touches) must
                  drop ~2x; this is the broker-queue/TCP/staging-intake
                  saving, per-frame, format-exact.
2. packer_only  — native dt_pack_batch throughput into the production
                  bf16 batch: f32 wire pays the convert loop, bf16 wire
                  is the cast-free strided memcpy and reads half the
                  bytes. Acceptance: >= 1.5x steps/s on the bf16 path.
3. h2d_bytes    — per-iteration H2D bytes from the ACTUAL dtype-grouped
                  transfer layouts (parallel/fused_io.py) for an
                  f32-staged vs bf16-staged learner: the obs share drops
                  ~2x when obs rest in bf16 (with the default
                  stage_obs_compute_dtype both wires land here — the
                  wire changes WHERE the cast happens, not the layout).
4. parity       — the tentpole proof: TrainBatch built from
                  cast-at-actor (DTR3) frames is BITWISE IDENTICAL
                  (sha256 over every leaf) to the batch built from f32
                  frames with the cast at staging — through the full
                  StagingBuffer, on the native C packer AND the python
                  fallback.

Plus an informational closed-loop e2e section (small fused learner fed
by frame republishers, f32-wire vs bf16-wire arms): on a CPU smoke the
device step dominates so the arms read ~equal — the wire win is a
bandwidth/host effect, which sections 1-3 measure directly; on a
data-starved TPU host the intake saving is the bottleneck saving.

Writes WIRE_QUANT_AB.json (committed; tests/test_transport.py guards
the verdict and a nightly+slow wrapper re-runs --quick).

Run: python scripts/ab_wire_quant.py [--quick]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")  # a host-path A/B, pinned to the CPU

import numpy as np

from dotaclient_tpu.config import LearnerConfig, PolicyConfig
from dotaclient_tpu.runtime.staging import StagingBuffer
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import connect
from dotaclient_tpu.transport.serialize import (
    Rollout,
    cast_rollout_obs_bf16,
    deserialize_rollout,
    serialize_rollout,
)

FLAGSHIP_B, FLAGSHIP_T, FLAGSHIP_H = 256, 16, 128


def make_rollouts(n: int, T: int, H: int, seed: int = 0):
    """Seeded synthetic rollouts at learner shapes (mirrors bench.py's
    producer frames; the SAME list feeds both arms of every section)."""
    from dotaclient_tpu.env import featurizer as F
    from dotaclient_tpu.ops.action_dist import Action

    r = np.random.RandomState(seed)
    out = []
    T1 = T + 1
    for i in range(n):
        obs = F.Observation(
            global_feats=r.randn(T1, F.GLOBAL_FEATURES).astype(np.float32),
            hero_feats=r.randn(T1, F.HERO_FEATURES).astype(np.float32),
            unit_feats=r.randn(T1, F.MAX_UNITS, F.UNIT_FEATURES).astype(np.float32),
            unit_mask=r.rand(T1, F.MAX_UNITS) < 0.6,
            target_mask=r.rand(T1, F.MAX_UNITS) < 0.3,
            action_mask=np.ones((T1, F.N_ACTION_TYPES), bool),
        )
        out.append(
            Rollout(
                obs=obs,
                actions=Action(
                    type=r.randint(0, 2, T).astype(np.int32),
                    move_x=r.randint(0, 9, T).astype(np.int32),
                    move_y=r.randint(0, 9, T).astype(np.int32),
                    target=np.zeros(T, np.int32),
                ),
                behavior_logp=(-1.5 + 0.1 * r.randn(T)).astype(np.float32),
                behavior_value=(r.randn(T) * 0.1).astype(np.float32),
                rewards=(r.randn(T) * 0.1).astype(np.float32),
                dones=np.zeros(T, np.float32),
                initial_state=(np.zeros(H, np.float32), np.zeros(H, np.float32)),
                version=0,
                actor_id=i,
            )
        )
    return out


def obs_float_bytes(r: Rollout) -> int:
    return sum(
        int(np.asarray(a).nbytes)
        for a in (r.obs.global_feats, r.obs.hero_feats, r.obs.unit_feats)
    )


def section_wire_bytes(rollouts):
    f32 = serialize_rollout(rollouts[0])
    bf = serialize_rollout(cast_rollout_obs_bf16(rollouts[0]))
    T = rollouts[0].length
    obs_f32 = obs_float_bytes(rollouts[0])
    obs_bf16 = obs_float_bytes(cast_rollout_obs_bf16(rollouts[0]))
    return {
        "frame_bytes_f32": len(f32),
        "frame_bytes_bf16": len(bf),
        "wire_bytes_per_env_step_f32": round(len(f32) / T, 1),
        "wire_bytes_per_env_step_bf16": round(len(bf) / T, 1),
        "obs_share_bytes_f32": obs_f32,
        "obs_share_bytes_bf16": obs_bf16,
        "obs_share_reduction_x": round(obs_f32 / obs_bf16, 3),
        "total_reduction_x": round(len(f32) / len(bf), 3),
    }


def section_packer_only(rollouts, reps: int):
    """Native pack throughput into the production bf16 batch, f32-wire
    (convert) vs bf16-wire (cast-free memcpy). Timed as the pack call
    staging pays per batch, into a preallocated out so the comparison
    isolates the copy path; best-quartile mean defends against host
    noise (shared-CPU container)."""
    import ml_dtypes

    from dotaclient_tpu import native
    from dotaclient_tpu.ops.batch import zeros_train_batch

    lib = native.load_packer()
    if lib is None:
        return {"skipped": "native packer unavailable"}
    f32 = [serialize_rollout(r) for r in rollouts]
    bf = [serialize_rollout(cast_rollout_obs_bf16(r)) for r in rollouts]
    B, T, H = len(rollouts), rollouts[0].length, rollouts[0].initial_state[0].shape[-1]
    out = zeros_train_batch(B, T, H, False, obs_dtype=ml_dtypes.bfloat16)

    # PACKER PROPER: prebuilt dt_pack_batch argument vectors, so each
    # timed call is the C pack itself — the thing the wire dtype
    # changes (convert loop vs strided memcpy over half the read
    # bytes). The per-call ctypes glue (frame-pointer marshal, length
    # vector, 24 leaf pointers) is wire-dtype-INDEPENDENT — ~0.25 ms
    # flat on this host — and is reported separately via the full
    # pack_frames call below, not folded into the packer ratio it
    # cannot change.
    dims = native._schema_dims()
    args_f32, keep1 = native._pack_batch_args(f32, out, T, H, False, True, None, dims)
    args_bf, keep2 = native._pack_batch_args(bf, out, T, H, False, True, None, dims)
    assert lib.dt_pack_batch(*args_f32) == 0 and lib.dt_pack_batch(*args_bf) == 0

    def one(args):
        t0 = time.perf_counter()
        lib.dt_pack_batch(*args)
        return time.perf_counter() - t0

    # INTERLEAVED pairs: on a shared-CPU host, timing one arm's whole
    # window then the other's lets a contention burst land on a single
    # arm and swing the ratio ±20% run to run (observed). Back-to-back
    # pairs see the same host weather; the median of per-pair ratios is
    # stable, and the per-arm rates report the best-quartile mean.
    pairs = [(one(args_f32), one(args_bf)) for _ in range(reps)]
    ratios = sorted(a / b for a, b in pairs)
    speedup = ratios[len(ratios) // 2]

    def best_quartile(ts):
        ts = sorted(ts)
        q = max(len(ts) // 4, 1)
        return sum(ts[:q]) / q

    ms_f32 = best_quartile([a for a, _ in pairs])
    ms_bf = best_quartile([b for _, b in pairs])

    # Context: the full python-visible pack call including the glue.
    def one_call(frames):
        t0 = time.perf_counter()
        native.pack_frames(lib, frames, T, H, False, obs_bf16=True, out=out)
        return time.perf_counter() - t0

    one_call(f32), one_call(bf)
    call_pairs = [(one_call(f32), one_call(bf)) for _ in range(max(reps // 4, 5))]
    call_f32 = best_quartile([a for a, _ in call_pairs])
    call_bf = best_quartile([b for _, b in call_pairs])
    return {
        "batch": [B, T],
        "pack_ms_f32_wire": round(ms_f32 * 1e3, 4),
        "pack_ms_bf16_wire": round(ms_bf * 1e3, 4),
        "packer_only_steps_per_sec_f32_wire": round(B * T / ms_f32, 1),
        "packer_only_steps_per_sec_bf16_wire": round(B * T / ms_bf, 1),
        "speedup_x": round(speedup, 3),
        "speedup_method": (
            "median of per-pair (interleaved) dt_pack_batch time ratios; "
            "ctypes glue excluded (wire-dtype-independent, see pack_call_*)"
        ),
        "pack_call_ms_f32_wire": round(call_f32 * 1e3, 4),
        "pack_call_ms_bf16_wire": round(call_bf * 1e3, 4),
        "pack_call_speedup_x": round(call_f32 / call_bf, 3),
    }


def section_h2d():
    """Per-iteration H2D bytes from the ACTUAL fused transfer layouts:
    group buffers for an f32-staged vs bf16-staged flagship config. No
    device needed — the layout fully determines the bytes."""
    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.parallel.fused_io import _GROUP_DTYPES, FusedBatchIO
    from dotaclient_tpu.parallel.train_step import _batch_template
    from dotaclient_tpu.runtime.staging import cast_obs_to_compute_dtype

    mesh = mesh_lib.make_mesh("dp=-1")
    out = {}
    for tag, stage in (("f32_staged", False), ("bf16_staged", True)):
        cfg = LearnerConfig(batch_size=FLAGSHIP_B, seq_len=FLAGSHIP_T)
        cfg.stage_obs_compute_dtype = stage
        template = cast_obs_to_compute_dtype(cfg, jax.tree.map(np.asarray, _batch_template(cfg)))
        io = FusedBatchIO(template, mesh)
        total = sum(
            cfg.batch_size * cols * np.dtype(_GROUP_DTYPES[k]).itemsize
            for k, cols in io.group_cols.items()
        )
        obs_leaves = (
            template.obs.global_feats, template.obs.hero_feats, template.obs.unit_feats
        )
        out[tag] = {
            "h2d_bytes_per_iter": int(total),
            "h2d_obs_bytes_per_iter": int(sum(l.nbytes for l in obs_leaves)),
            "pack_path_obs_dtype": np.dtype(obs_leaves[0].dtype).name,
        }
    out["obs_share_reduction_x"] = round(
        out["f32_staged"]["h2d_obs_bytes_per_iter"]
        / out["bf16_staged"]["h2d_obs_bytes_per_iter"],
        3,
    )
    return out


def batch_sha256(batch) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(batch):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


def staged_batch_hash(tag: str, frames, native_packer: bool) -> str:
    """One batch through the full StagingBuffer (consume → ingest →
    pack, default bf16 compute-dtype staging) → leaf-bytes sha256."""
    name = f"abwq_{tag}"
    mem.reset(name)
    cfg = LearnerConfig(batch_size=len(frames), seq_len=FLAGSHIP_T)
    cfg.native_packer = native_packer
    pub = connect(f"mem://{name}")
    for f in frames:
        pub.publish_experience(f)
    sb = StagingBuffer(cfg, connect(f"mem://{name}"), version_fn=lambda: 0).start()
    try:
        batch = sb.get_batch(timeout=60.0)
        if batch is None:
            raise RuntimeError(f"{tag}: staging produced no batch")
        return batch_sha256(batch)
    finally:
        sb.stop()


def section_parity(rollouts):
    """Cast-at-actor (DTR3 wire) vs cast-at-staging (f32 wire): the
    TrainBatch hashes must be EQUAL, per packer. Matched seeds by
    construction — both arms serialize the same Rollout objects."""
    rollouts = rollouts[:32]  # one batch is proof; keep the section fast
    f32_frames = [serialize_rollout(r) for r in rollouts]
    bf_frames = [serialize_rollout(cast_rollout_obs_bf16(r)) for r in rollouts]
    out = {}
    for packer, use_native in (("native", True), ("python", False)):
        h_staging = staged_batch_hash(f"{packer}_f32", list(f32_frames), use_native)
        h_actor = staged_batch_hash(f"{packer}_bf16", list(bf_frames), use_native)
        out[packer] = {
            "cast_at_staging_sha256": h_staging,
            "cast_at_actor_sha256": h_actor,
            "bitwise_identical": h_staging == h_actor,
        }
    out["all_identical"] = all(v["bitwise_identical"] for v in out.values() if isinstance(v, dict))
    return out


def section_e2e(rollouts, n_iters: int, seed: int):
    """Closed loop: republishing producers → staging → fused device
    step, one arm per wire dtype at matched seeds. Small policy so the
    CPU compile stays in budget; informational (see module docstring)."""
    import threading

    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.parallel.train_step import build_single_train_step, init_train_state

    policy = PolicyConfig(unit_embed_dim=32, lstm_hidden=32, mlp_hidden=32)
    cfg = LearnerConfig(batch_size=64, seq_len=FLAGSHIP_T, policy=policy, seed=seed)
    mesh = mesh_lib.make_mesh("dp=-1")
    train_step, state_sh, io = build_single_train_step(cfg, mesh)
    small = make_rollouts(256, FLAGSHIP_T, policy.lstm_hidden, seed=seed + 1)
    arms = {
        "f32_wire": [serialize_rollout(r) for r in small],
        "bf16_wire": [serialize_rollout(cast_rollout_obs_bf16(r)) for r in small],
    }
    out = {}
    for tag, frames in arms.items():
        name = f"abwq_e2e_{tag}"
        mem.reset(name)
        pub = connect(f"mem://{name}", maxlen=cfg.batch_size * 4)
        stop = threading.Event()

        def producer():
            i = 0
            while not stop.is_set():
                if pub.experience_depth() >= cfg.batch_size * 3:
                    time.sleep(0.001)
                    continue
                pub.publish_experience(frames[i % len(frames)])
                i += 1

        thread = threading.Thread(target=producer, daemon=True)
        thread.start()
        sb = StagingBuffer(cfg, connect(f"mem://{name}"), version_fn=lambda: 0, fused_io=io).start()
        # Fresh per arm: the train step DONATES its state argument, so a
        # shared initial state would be a deleted buffer in arm two.
        state = jax.device_put(init_train_state(cfg, jax.random.PRNGKey(seed)), state_sh)

        def fetch():
            b, payload = sb.get_batch_groups(timeout=120.0)
            if b is None:
                raise RuntimeError("staging starved")
            return jax.device_put(payload, io.sharding), int(np.sum(b.mask))

        try:
            dev, _ = fetch()
            state, metrics = train_step(state, dev)
            jax.block_until_ready(metrics["loss"])
            env_steps = 0
            nxt, n_next = fetch()
            t0 = time.perf_counter()
            for _ in range(n_iters):
                dev, n_now = nxt, n_next
                state, metrics = train_step(state, dev)
                env_steps += n_now
                nxt, n_next = fetch()
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            out[tag] = {
                "env_steps_per_sec": round(env_steps / dt, 1),
                "loss": float(jax.device_get(metrics["loss"])),
            }
        finally:
            stop.set()
            sb.stop()
    out["note"] = (
        "CPU smoke: the device step dominates, so the arms read ~equal; "
        "the wire win is the bytes/packer effect sections 1-3 measure"
    )
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer reps, skip the e2e loop")
    ap.add_argument("--reps", type=int, default=0, help="packer timing reps (0 = auto)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "WIRE_QUANT_AB.json"))
    args = ap.parse_args()
    reps = args.reps or (20 if args.quick else 120)

    rollouts = make_rollouts(FLAGSHIP_B, FLAGSHIP_T, FLAGSHIP_H, seed=0)
    t_start = time.time()
    result = {
        "config": {
            "flagship_batch": [FLAGSHIP_B, FLAGSHIP_T, FLAGSHIP_H],
            "seed": 0,
            "quick": bool(args.quick),
            "reps": reps,
        },
        "wire_bytes": section_wire_bytes(rollouts),
        "packer_only": section_packer_only(rollouts, reps),
        "h2d": section_h2d(),
        "parity": section_parity(rollouts),
    }
    if not args.quick:
        result["e2e"] = section_e2e(rollouts, n_iters=12, seed=0)
    pk = result["packer_only"]
    result["verdict"] = {
        "obs_wire_bytes_halved": result["wire_bytes"]["obs_share_reduction_x"] >= 1.9,
        "h2d_obs_bytes_halved": result["h2d"]["obs_share_reduction_x"] >= 1.9,
        "packer_speedup_ge_1p5x": bool(pk.get("speedup_x", 0) >= 1.5),
        "trainbatch_bitwise_identical": result["parity"]["all_identical"],
    }
    result["verdict"]["all_green"] = all(result["verdict"].values())
    result["wall_s"] = round(time.time() - t_start, 1)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result["verdict"]))
    if not result["verdict"]["all_green"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
