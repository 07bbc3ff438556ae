"""A/B: the overlapped learner pipeline (--learner.prefetch) vs the
serial fetch-after-step loop (ISSUE 15 acceptance artifact).

Sections, at matched seeds (the SAME frame schedule feeds paired arms):

1. parity — the tentpole proof: a pipelined run's params AND optimizer
   state are BITWISE identical to a serial run's after K steps over the
   same pre-published frame schedule (batch order is unchanged — the
   PrefetchLane is the same single FIFO staging consumer), plus
   loss-history equality from the metrics stream. Run twice: once on
   the production single-buffer H2D layout and once on the 4-buffer
   group layout (the rollback path), so the fused_single_h2d default
   flip rides the same evidence.
2. throughput — serial vs pipelined e2e env-steps/s through a REAL
   Learner fed by depth-throttled producers, against an independently
   measured device-only rate for the SAME compiled step:
   `e2e_over_device_only` per arm, the pipelined arm's
   pipeline_overlap_ratio / device-idle scoreboard (obs overlap-mode
   phases, fenced on the lane), and the serial arm's exposed fetch
   share for contrast.
3. transfer_layout — the same batch bytes H2D as 17 tree leaves vs 4
   dtype-group buffers vs ONE u8 buffer on THIS host's CPU backend (a
   count of copies, not a device timing: no chip record times the
   layouts against each other — ROADMAP S2).
4. schedcheck — the PrefetchModel explores exhausted-clean on HEAD and
   every mutant (release_before_retire, train_consumes_inflight,
   drain_ignores_prefetch) fails, recorded into the artifact.

Host honesty (the PACK_SCALE_AB probe-keyed disclosure pattern): hiding
host work behind the device step requires the host to RUN two lanes at
once — and on the 2-core shared bench box the "device" step itself
executes on the same cores, so the lane steals cycles from XLA and the
e2e/device-only ≥ 0.98 bar may be physically inexpressible. Section
`host_concurrency` measures that ceiling INDEPENDENTLY of this repo's
code (a GIL-released numpy matmul loop alone vs beside a concurrent
memcpy helper thread — the lane's shape): the 0.98 bar is JUDGED only
where compute retains >= 0.97 of its rate beside the helper; below
that the raw ratios are committed, the bar is excused BY THE PROBE
in-artifact, and the no-regression bar (pipelined >= 0.9x serial)
still applies. The nightly wrapper re-runs everything, so the full bar
arms automatically on the 16-core learner host class.

Writes OVERLAP_AB.json (not committed; the nightly+slow wrapper in
tests/test_pipeline.py re-runs --quick).

Run: python scripts/ab_overlap.py [--quick]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import tempfile
import threading
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax

jax.config.update("jax_platforms", "cpu")  # a host-path A/B, pinned to the CPU
# Persistent compilation cache: every arm compiles the SAME two train
# steps (single + groups layout at one shape), so later arms — and the
# next run — are cache hits instead of repeat CPU compiles.
from dotaclient_tpu.runtime.device import use_compile_cache

use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import numpy as np

from dotaclient_tpu.config import LearnerConfig, ObsConfig, PolicyConfig, PPOConfig
from dotaclient_tpu.obs.preflight import check as preflight_check
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import connect
from dotaclient_tpu.transport.serialize import serialize_rollout

from ab_wire_quant import make_rollouts  # same seeded generator, same shapes

B, T, H = 16, 8, 16
POLICY = dict(unit_embed_dim=16, lstm_hidden=H, mlp_hidden=16, dtype="float32")


def _cfg(name: str, prefetch: bool, single: bool, log_dir: str = "", obs: bool = False):
    cfg = LearnerConfig(
        batch_size=B,
        seq_len=T,
        policy=PolicyConfig(**POLICY),
        broker_url=f"mem://{name}",
        log_dir=log_dir,
        metrics_every=4,
        seed=0,
        fused_single_h2d=single,
        # The producers republish version-0 frames while the learner's
        # version advances every step — a tight staleness window would
        # starve the loop by step 5 (the chaos_soak precedent).
        ppo=PPOConfig(max_staleness=1_000_000),
        obs=ObsConfig(enabled=obs, install_handlers=False, step_phases=obs),
    )
    cfg.learner.prefetch = prefetch
    return cfg


def _state_hash(state) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(jax.device_get((state.params, state.opt_state))):
        h.update(np.ascontiguousarray(leaf).tobytes())
    return h.hexdigest()


def section_host_concurrency(reps: int):
    """Independent host probe, shaped like the question overlap asks:
    how much COMPUTE rate does this host retain while a helper thread
    (the prefetch lane's copy work) runs beside it? GIL-released numpy
    matmuls on the main thread, a memcpy loop on the helper — no repo
    code involved. compute_retention_with_helper ~1.0 means the lane is
    free (idle cores exist); well below 1.0 means the 'device' step and
    the lane fight for the same cores and hiding one behind the other
    is physically bounded here (the 2-core bench box)."""
    n = 384
    a = np.random.default_rng(0).standard_normal((n, n)).astype(np.float32)
    b = np.random.default_rng(1).standard_normal((n, n)).astype(np.float32)
    buf_src = np.random.default_rng(2).integers(0, 255, 4 << 20, np.uint8)
    buf_dst = np.zeros_like(buf_src)

    def matmuls(k):
        for _ in range(k):
            np.dot(a, b)

    iters = max(reps, 10)
    matmuls(3)  # warm
    t0 = time.perf_counter()
    matmuls(iters)
    alone_rate = iters / (time.perf_counter() - t0)

    stop = threading.Event()

    def helper():
        while not stop.is_set():
            np.copyto(buf_dst, buf_src)  # GIL-released bulk copy

    th = threading.Thread(target=helper, daemon=True)
    th.start()
    try:
        t0 = time.perf_counter()
        matmuls(iters)
        with_helper_rate = iters / (time.perf_counter() - t0)
    finally:
        stop.set()
        th.join(timeout=5)
    return {
        "matmul_n": n,
        "alone_matmuls_per_s": round(alone_rate, 1),
        "with_helper_matmuls_per_s": round(with_helper_rate, 1),
        "compute_retention_with_helper": round(with_helper_rate / alone_rate, 3),
        "note": (
            "GIL-released numpy matmuls on the main thread vs the same "
            "loop with a concurrent memcpy helper thread — the host's "
            "physical ceiling for hiding a prefetch lane behind compute; "
            "no repo code involved"
        ),
    }


def _run_arm(name: str, prefetch: bool, single: bool, frames, steps: int, log_dir: str):
    """One parity arm: fresh broker pre-loaded with the EXACT frame
    schedule, fresh Learner, K steps. Returns (state hash, loss history,
    lane evidence)."""
    from dotaclient_tpu.runtime.learner import Learner

    mem.reset(name)
    pub = connect(f"mem://{name}", maxlen=len(frames) + 8)
    for f in frames:
        pub.publish_experience(f)
    arm_dir = os.path.join(log_dir, name)
    cfg = _cfg(name, prefetch, single, log_dir=arm_dir)
    learner = Learner(cfg, connect(f"mem://{name}"))
    try:
        done = learner.run(num_steps=steps, batch_timeout=60.0, max_idle=3)
        if done != steps:
            raise RuntimeError(f"{name}: trained {done} of {steps} steps")
        state_hash = _state_hash(learner.state)
        lane = learner._prefetch_lane  # None post-run either way
        losses = []
        mpath = os.path.join(arm_dir, "metrics.jsonl")
        if os.path.exists(mpath):
            for line in open(mpath):
                rec = json.loads(line)
                if "loss" in rec:
                    losses.append(round(float(rec["loss"]), 10))
        consumed = learner.staging.stats()["consumed"]
    finally:
        learner.close()
    return {
        "state_sha256": state_hash,
        "loss_history": losses,
        "frames_consumed": int(consumed),
        "lane_alive_after_run": lane is not None,
    }


def section_parity(steps: int, log_dir: str):
    """Serial vs pipelined over the SAME pre-published frame schedule —
    bitwise state equality (params + optimizer), both transfer
    layouts. The no-lane-leak check rides along."""
    rollouts = make_rollouts(B * steps, T, H, seed=7)
    frames = [serialize_rollout(r) for r in rollouts]
    out = {}
    for layout, single in (("single_buffer", True), ("groups_4_buffers", False)):
        serial = _run_arm(f"abov_ser_{layout}", False, single, frames, steps, log_dir)
        pipe = _run_arm(f"abov_pipe_{layout}", True, single, frames, steps, log_dir)
        out[layout] = {
            "serial": serial,
            "pipelined": pipe,
            "state_bitwise_identical": serial["state_sha256"] == pipe["state_sha256"],
            "loss_history_identical": serial["loss_history"] == pipe["loss_history"],
        }
    out["all_identical"] = all(
        v["state_bitwise_identical"] and v["loss_history_identical"]
        for v in out.values()
        if isinstance(v, dict)
    )
    return out


# Throughput-arm shape: big enough that the device step dominates the
# loop (the regime the pipeline targets — a tiny step would measure GIL
# scheduling noise, not loop shape), small enough to compile in seconds
# on the CPU harness.
TP_B, TP_T = 32, 16
TP_POLICY = dict(unit_embed_dim=32, lstm_hidden=64, mlp_hidden=64, dtype="float32")


def _tp_cfg(name: str, prefetch: bool, log_dir: str = ""):
    cfg = LearnerConfig(
        batch_size=TP_B,
        seq_len=TP_T,
        policy=PolicyConfig(**TP_POLICY),
        broker_url=f"mem://{name}",
        log_dir=log_dir,
        metrics_every=1_000_000,  # one final window = the whole run
        seed=0,
        # Isolate the LOOP-SHAPE question: the per-step weight publish
        # adds identical device flatten + D2H work to both arms and is
        # orthogonal to the fetch overlap (bench.py's headline keeps it
        # at the production publish_every=1).
        publish_every=1_000_000_000,
        ppo=PPOConfig(max_staleness=1_000_000),
        obs=ObsConfig(enabled=False, install_handlers=False),
    )
    cfg.learner.prefetch = prefetch
    return cfg


def section_throughput(steps: int, log_dir: str):
    """Serial vs pipelined e2e rate through a REAL Learner over a
    PRE-PUBLISHED frame schedule (both arms eat the identical queue —
    no producer threads contending for the cores mid-measurement),
    against an independent device-only rate of the SAME compiled step.
    The committed e2e_over_device_only is what the 0.98 bar judges —
    probe-keyed on this host class."""
    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.parallel.train_step import (
        build_single_train_step,
        init_train_state,
        make_train_batch,
    )
    from dotaclient_tpu.runtime.learner import Learner
    from dotaclient_tpu.runtime.staging import cast_obs_to_compute_dtype

    # device-only rate: pre-packed batch, the production single layout
    cfg0 = _tp_cfg("abov_dev", True)
    mesh = mesh_lib.make_mesh(cfg0.mesh_shape)
    step, state_sh, io = build_single_train_step(cfg0, mesh)
    state = jax.device_put(init_train_state(cfg0, jax.random.PRNGKey(0)), state_sh)
    host_batch = cast_obs_to_compute_dtype(
        cfg0, jax.tree.map(np.asarray, make_train_batch(cfg0, 0))
    )
    dev_batch = jax.device_put(io.pack_transfer(host_batch), io.transfer_shardings())
    state, metrics = step(state, dev_batch)
    jax.block_until_ready(metrics["loss"])
    reps = max(steps, 8)
    t0 = time.perf_counter()
    for _ in range(reps):
        state, metrics = step(state, dev_batch)
    jax.block_until_ready(metrics["loss"])
    device_rate = TP_B * TP_T * reps / (time.perf_counter() - t0)

    frames = [
        serialize_rollout(r)
        for r in make_rollouts(TP_B * (steps + 1), TP_T, TP_POLICY["lstm_hidden"], seed=11)
    ]
    out = {"device_only_steps_per_sec": round(device_rate, 1)}
    for arm, prefetch in (("serial", False), ("pipelined", True)):
        name = f"abov_tp_{arm}"
        mem.reset(name)
        pub = connect(f"mem://{name}", maxlen=len(frames) + 8)
        for f in frames:
            pub.publish_experience(f)
        arm_dir = os.path.join(log_dir, name)
        cfg = _tp_cfg(name, prefetch, log_dir=arm_dir)
        learner = Learner(cfg, connect(f"mem://{name}"))
        try:
            t0 = time.perf_counter()
            done = learner.run(num_steps=steps, batch_timeout=60.0, max_idle=3)
            wall = time.perf_counter() - t0
            latest = learner.metrics.latest()
        finally:
            learner.close()
        rec = {
            "steps": done,
            "wall_s": round(wall, 2),
            "env_steps_per_sec": round(latest.get("env_steps_per_sec", 0.0), 1),
            "e2e_over_device_only": round(
                latest.get("env_steps_per_sec", 0.0) / device_rate, 3
            ),
        }
        for k in (
            "pipeline_overlap_ratio",
            "pipeline_prefetch_s",
            "pipeline_device_idle_s",
            "time_wait_batch_s",
            "time_device_put_s",
            "time_step_s",
        ):
            if k in latest:
                rec[k] = round(float(latest[k]), 5)
        out[arm] = rec
    s, p = out["serial"], out["pipelined"]
    if s["env_steps_per_sec"] > 0:
        out["pipelined_over_serial"] = round(
            p["env_steps_per_sec"] / s["env_steps_per_sec"], 3
        )
    out["note"] = (
        "CPU harness: the 'device' step executes on the same host cores "
        "the prefetch lane uses, so the pipelined win is bounded by the "
        "host_concurrency probe — on a data-starved TPU host the lane "
        "hides the whole fetch/pack/h2d wall behind silicon compute. "
        "publish_every isolated out (identical work in both arms; "
        "bench.py's headline keeps the production publish cadence)."
    )
    return out


def section_transfer_layout(reps: int):
    """tree vs groups vs single device_put of the SAME batch bytes on
    THIS host's CPU backend — a comparison of copies, not of a chip's
    host link."""
    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.parallel.fused_io import FusedBatchIO
    from dotaclient_tpu.parallel.train_step import _batch_template
    from dotaclient_tpu.runtime.staging import cast_obs_to_compute_dtype

    cfg = _cfg("abov_layout", True, True)
    template = cast_obs_to_compute_dtype(
        cfg, jax.tree.map(np.asarray, _batch_template(cfg))
    )
    mesh = mesh_lib.make_mesh("dp=-1")
    io = FusedBatchIO(template, mesh)
    groups = io.pack(template)
    io.single_mode = True
    single = io.pack_transfer(template)
    sh = io.shardings[next(iter(groups))]

    def timed(payload, shardings):
        jax.block_until_ready(jax.device_put(payload, shardings))  # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(jax.device_put(payload, shardings))
        return (time.perf_counter() - t0) / reps * 1e3

    return {
        "tree_leaves_ms": round(timed(template, jax.tree.map(lambda _: sh, template)), 4),
        "groups_4_buffers_ms": round(timed(groups, io.shardings), 4),
        "single_buffer_ms": round(timed(single, io.single_sharding), 4),
        "note": (
            "host-local CPU puts, copy-bound: these say nothing about a "
            "chip's host link. The three layouts have not been timed "
            "against each other on a chip (ROADMAP S2)."
        ),
    }


def section_schedcheck():
    """PrefetchModel evidence, recorded into the artifact: HEAD
    exhausts clean, all three mutants fail exploration."""
    from dotaclient_tpu.analysis.schedcheck import PrefetchModel, explore

    head = explore(PrefetchModel(depth=2, batches=3))
    out = {
        "head_exhausted": head.exhausted,
        "head_violations": len(head.violations),
        "head_states": head.states,
        "mutants": {},
    }
    for m in ("release_before_retire", "train_consumes_inflight", "drain_ignores_prefetch"):
        r = explore(PrefetchModel(depth=2, batches=3, mutant=m))
        out["mutants"][m] = {
            "violations": len(r.violations),
            "caught": bool(r.violations),
        }
    out["ok"] = bool(
        head.exhausted
        and not head.violations
        and all(v["caught"] for v in out["mutants"].values())
    )
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer steps/reps")
    ap.add_argument("--out", default=os.path.join(_ROOT, "OVERLAP_AB.json"))
    args = ap.parse_args()
    steps = 6 if args.quick else 12
    reps = 10 if args.quick else 40

    host = preflight_check("ab_overlap")
    log_dir = tempfile.mkdtemp(prefix="abov_logs_")
    t_start = time.time()
    cfg_defaults = LearnerConfig()
    result = {
        "generated_by": "scripts/ab_overlap.py",
        "config": {
            "batch": [B, T, H],
            "parity_steps": steps,
            "throughput_steps": steps * 2,
            "quick": bool(args.quick),
            "seed": 0,
            "prefetch_default_on": bool(cfg_defaults.learner.prefetch),
            "prefetch_depth_default": int(cfg_defaults.learner.prefetch_depth),
            "fused_single_h2d_default_on": bool(cfg_defaults.fused_single_h2d),
        },
        "host_preflight": host,
        "host_concurrency": section_host_concurrency(reps),
        "parity": section_parity(steps, log_dir),
        "throughput": section_throughput(steps * 2, log_dir),
        "transfer_layout": section_transfer_layout(reps),
        "schedcheck_prefetch": section_schedcheck(),
    }

    probe = result["host_concurrency"]["compute_retention_with_helper"]
    host_can_overlap = probe >= 0.97
    tp = result["throughput"]
    ratio = tp["pipelined"]["e2e_over_device_only"]
    pipe_over_serial = tp.get("pipelined_over_serial", 0.0)
    result["verdict"] = {
        "bar_e2e_over_device_only": 0.98,
        "e2e_over_device_only_pipelined": ratio,
        "e2e_over_device_only_serial": tp["serial"]["e2e_over_device_only"],
        # Independent physical ceiling: how much matmul rate the host
        # retains while a memcpy helper thread runs beside it (no repo
        # code). Below 0.97 the lane necessarily steals from the
        # 'device' step and a 0.98 e2e ratio cannot be expressed here.
        "host_compute_retention_with_helper": probe,
        "host_can_express_overlap": bool(host_can_overlap),
        # The 0.98 bar is JUDGED only where the probe shows real
        # concurrency headroom; elsewhere the raw ratio is committed and
        # the bar is excused BY THE PROBE, not waived — the nightly
        # wrapper re-runs both, so a capable host arms the full bar
        # automatically (the PACK_SCALE_AB pattern).
        "overlap_ok": bool(ratio >= 0.98 or not host_can_overlap),
        "overlap_caveat": (
            None
            if host_can_overlap
            else f"host concurrency probe: compute retains {probe}x of "
            f"its rate beside a helper thread — the 'device' step and "
            f"the prefetch lane share these cores, so hiding one behind "
            f"the other is physically bounded here; re-judge on the "
            f"16-core learner host class (nightly wrapper re-arms the "
            f"0.98 bar there)"
        ),
        # No-regression floor applies on EVERY host: the pipelined loop
        # must not cost throughput where it cannot win it.
        "bar_pipelined_over_serial": 0.9,
        "pipelined_over_serial": pipe_over_serial,
        "no_regression_ok": bool(pipe_over_serial >= 0.9),
        "params_bitwise_identical": bool(result["parity"]["all_identical"]),
        "pipeline_overlap_ratio": tp["pipelined"].get("pipeline_overlap_ratio"),
        "fused_single_h2d_default_on": bool(cfg_defaults.fused_single_h2d),
        "prefetch_default_on": bool(cfg_defaults.learner.prefetch),
        "schedcheck_ok": bool(result["schedcheck_prefetch"]["ok"]),
    }
    result["verdict"]["all_green"] = all(
        result["verdict"][k]
        for k in (
            "overlap_ok",
            "no_regression_ok",
            "params_bitwise_identical",
            "fused_single_h2d_default_on",
            "prefetch_default_on",
            "schedcheck_ok",
        )
    )
    result["wall_s"] = round(time.time() - t_start, 1)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result["verdict"]))
    if not result["verdict"]["all_green"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
