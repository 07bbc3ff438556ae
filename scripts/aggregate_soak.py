"""Many-actor aggregate soak (VERDICT r3 item 5; BASELINE ladder rungs
2/4): dozens of REAL OS processes offering >= 50k env-steps/s into one
consumer over the real `tcp://` broker, plus a minutes-long closed loop
under a live learner. Writes AGGREGATE_SOAK.json.

Methodology — the host constraint, stated up front: this box has ONE
CPU core. A real actor's featurize+policy loop measured ~1,000
env-steps/s per core (ROUND3_NOTES), so 50k aggregate of GENUINE
inference needs ~50 actor cores — cores BASELINE's production fleet has
and this box does not; likewise 64 sender processes and an XLA learner
cannot each get real CPU time simultaneously on one core. So the soak
splits the claim into the two halves one core CAN evidence:

PHASE A — aggregate fan-in at the bar: 64 replayer PROCESSES (each
publishing REAL pre-serialized rollout frames over its own tcp
connection, throttled near the measured real-actor per-core rate) into
the broker process and a staging consumer. No learner compute competes,
so the measurement isolates transport + staging + many-process fan-in:
offered >= 50k env-steps/s, consumed rate, per-actor heartbeats
(active_actors == process count).

PHASE B — closed-loop stability under sustained overload: a smaller
replayer cohort + fully-genuine actors (fake env -> featurizer ->
policy -> rollout -> weight hot-swap) against a LIVE learner for
minutes: staleness drops, drop-oldest backpressure, queue depth,
heartbeats, and learner progress, all sampled mid-run.

Round-5 additions (VERDICT r4 items 1 and 4):
- `--phase {all,a,b}` runs one phase alone. On a chip run
  `--phase b --platform tpu`: with the train step on the chip, the host
  cores are freed for transport and phase B can chase the 50k CONSUMED
  bar — the true north-star topology (producers saturating a learner
  that is simultaneously training) that a CPU-only host cannot show.
- `--platform tpu` asserts devices[0] is a real TPU (refuses to mislabel
  a CPU run); this process holds the chip, so every child is pinned to
  the CPU through its environment.
- `--batch-size 64 --phase b` is the host-ceiling variant: a
  deliberately tiny device step maximizes the consumed rate one core can
  reach, documenting the host-side ceiling the silicon run must beat.
- verdict keys renamed to say exactly what each phase showed:
  `offered_50k_bar_no_learner` (phase A has no competing learner
  compute) and `closed_loop_live_rate_env_steps_per_sec` +
  `closed_loop_consumed_ge_50k` (phase B).

Run: python scripts/aggregate_soak.py [--replayers 64] [--real-actors 4]
     [--duration 180] [--out AGGREGATE_SOAK.json] [--phase all|a|b]
     [--platform cpu|tpu] [--policy tiny|flagship] [--batch-size 256]
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import struct
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PORT = 13971


def _policy_for(name: str):
    """ONE policy-config source for the parent learner AND the genuine-
    actor children: a drifted copy on either side gets every actor frame
    quarantined as dropped_bad and the hot-swap ignored (H mismatch),
    silently degrading the closed loop to replayers-only."""
    from dotaclient_tpu.config import PolicyConfig

    if name == "flagship":
        return PolicyConfig()  # bench.py's production config: 128-hidden bf16
    return PolicyConfig(unit_embed_dim=16, lstm_hidden=16, mlp_hidden=16, dtype="float32")


# --------------------------------------------------------------- replayer
def run_replayer(args) -> int:
    """One load-cohort process: publish pre-serialized rollout frames at
    --rate frames/s, stamping each with the newest learner version from
    the live weight fanout (so staleness filtering sees realistic
    versions). Prints 'SENT <n>' at exit."""
    from dotaclient_tpu.transport.base import connect

    with open(args.frames_file, "rb") as f:
        blob = f.read()
    frames, off = [], 0
    while off < len(blob):
        (ln,) = struct.unpack_from("<I", blob, off)
        off += 4
        frames.append(bytearray(blob[off : off + ln]))
        off += ln
    # Rollout header is <4sIHHBIf (transport/serialize.py _HDR): version
    # u32 at offset 4, actor_id u32 at offset 13. Patch actor_id once,
    # version per publish.
    for fr in frames:
        struct.pack_into("<I", fr, 13, args.actor_id)

    broker = connect(args.broker)
    # Startup barrier: interpreter startup is ~2s SERIALIZED on the one
    # core, so the parent cannot guess when all N children are ready —
    # each child declares readiness, the parent releases them together.
    with open(f"{args.go_file}.ready.{args.actor_id}", "w") as f:
        f.write("ready")
    while not os.path.exists(args.go_file):  # barrier: parent releases
        time.sleep(0.2)
    version = 0
    sent = 0
    t0 = time.time()
    last_wpoll = 0.0
    interval = 1.0 / args.rate
    nxt = time.time()
    while time.time() - t0 < args.duration:
        now = time.time()
        if now - last_wpoll > 1.0:
            w = broker.poll_weights()
            if w and len(w) >= 12 and w[:4] in (b"DTW2", b"DTW1"):
                version = struct.unpack_from("<I", w, 4)[0]
            last_wpoll = now
        fr = frames[sent % len(frames)]
        struct.pack_into("<I", fr, 4, version)
        broker.publish_experience(bytes(fr))
        sent += 1
        nxt += interval
        delay = nxt - time.time()
        if delay > 0:
            time.sleep(delay)
    print(f"SENT {sent}", flush=True)
    return 0


# ------------------------------------------------------------- real actor
def run_real_actor(args) -> int:
    """Fully-genuine actor: fake env -> featurize -> policy step ->
    rollout publish -> weight hot-swap, over the tcp broker. Prints
    'EPISODES <n> STEPS <m>' at exit."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import asyncio

    from dotaclient_tpu.config import ActorConfig
    from dotaclient_tpu.env.fake_dotaservice import FakeDotaService
    from dotaclient_tpu.env.service import LocalDotaServiceStub
    from dotaclient_tpu.runtime.actor import Actor
    from dotaclient_tpu.transport.base import connect

    policy = _policy_for(args.policy)  # must match the learner's; see helper
    acfg = ActorConfig(
        env_addr="local", rollout_len=16, max_dota_time=30.0, policy=policy, seed=args.actor_id
    )
    actor = Actor(
        acfg,
        connect(args.broker),
        actor_id=args.actor_id,
        stub=LocalDotaServiceStub(FakeDotaService()),
    )
    with open(f"{args.go_file}.ready.{args.actor_id}", "w") as f:
        f.write("ready")
    while not os.path.exists(args.go_file):
        time.sleep(0.2)

    episodes = 0
    t0 = time.time()

    async def go():
        nonlocal episodes
        while time.time() - t0 < args.duration:
            await actor.run_episode()
            episodes += 1

    asyncio.run(go())
    print(f"EPISODES {episodes} STEPS {actor.steps_done}", flush=True)
    return 0


# ----------------------------------------------------------------- parent
def _wait_ready(go_file: str, n: int, timeout_s: float = 900.0) -> None:
    """Block until all n children have written `<go_file>.ready.<id>`."""
    import glob as _glob

    t0 = time.time()
    while time.time() - t0 < timeout_s:
        ready = len(_glob.glob(f"{go_file}.ready.*"))
        if ready >= n:
            print(f"all {n} children ready after {time.time() - t0:.0f}s", flush=True)
            return
        time.sleep(1.0)
    raise RuntimeError(f"only {len(_glob.glob(f'{go_file}.ready.*'))}/{n} children ready "
                       f"after {timeout_s:.0f}s")


def _spawn_children(n_replayers, n_real, rate, duration, frames_file, go_file, first_id,
                    policy="tiny"):
    broker_url = f"tcp://127.0.0.1:{PORT}"
    common = ["--broker", broker_url, "--go-file", go_file, "--duration", str(duration)]
    # One process per chip: the parent's learner may hold a TPU, so the
    # children are pinned to the CPU before their JAX starts.
    child_env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = []
    for i in range(n_replayers):
        procs.append(
            subprocess.Popen(
                [sys.executable, __file__, "--replayer", "--actor-id", str(first_id + i),
                 "--frames-file", frames_file, "--rate", str(rate)] + common,
                stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL,
                env=child_env,
            )
        )
    for i in range(n_real):
        procs.append(
            subprocess.Popen(
                [sys.executable, __file__, "--real-actor", "--actor-id", str(i),
                 "--policy", policy] + common,
                stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL,
                env=child_env,
            )
        )
    return procs


def _collect_children(procs, seq_len):
    offered_steps = real_eps = real_steps = senders_reporting = 0
    for pr in procs:
        try:
            out = pr.communicate(timeout=120)[0].decode()
        except subprocess.TimeoutExpired:
            pr.kill()
            out = pr.communicate()[0].decode()
        for line in out.splitlines():
            if line.startswith("SENT "):
                offered_steps += int(line.split()[1]) * seq_len
                senders_reporting += 1
            elif line.startswith("EPISODES "):
                parts = line.split()
                real_eps += int(parts[1])
                real_steps += int(parts[3])
    return offered_steps, real_eps, real_steps, senders_reporting


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--replayers", type=int, default=64)
    p.add_argument("--real-actors", type=int, default=4)
    p.add_argument("--duration", type=float, default=180.0, help="phase B window")
    p.add_argument("--phase-a-duration", type=float, default=75.0)
    p.add_argument("--rate", type=float, default=60.0, help="frames/s per phase-A replayer")
    p.add_argument("--out", default="AGGREGATE_SOAK.json")
    p.add_argument("--phase", choices=["all", "a", "b"], default="all")
    p.add_argument(
        "--platform",
        choices=["cpu", "tpu"],
        default="cpu",
        help="tpu = learner step on the chip (asserted real); children stay CPU",
    )
    p.add_argument(
        "--policy",
        choices=["tiny", "flagship"],
        default="tiny",
        help="flagship = the bench's production policy (128-hidden bf16)",
    )
    p.add_argument("--batch-size", type=int, default=256)
    p.add_argument(
        "--replayers-b",
        type=int,
        default=0,
        help="phase-B replayer count (0 = replayers//4, min 8 — the r4 default)",
    )
    # subprocess modes
    p.add_argument("--replayer", action="store_true")
    p.add_argument("--real-actor", dest="real_actor", action="store_true")
    p.add_argument("--actor-id", type=int, default=0)
    p.add_argument("--broker", default="")
    p.add_argument("--frames-file", default="")
    p.add_argument("--go-file", default="")
    args = p.parse_args(argv)
    if args.replayer:
        return run_replayer(args)
    if args.real_actor:
        return run_real_actor(args)

    import jax

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import bench as bench_mod
    from dotaclient_tpu.config import LearnerConfig
    from dotaclient_tpu.runtime.learner import Learner
    from dotaclient_tpu.runtime.staging import StagingBuffer
    from dotaclient_tpu.transport.base import connect

    if args.platform == "tpu" and jax.devices()[0].platform != "tpu":
        # The caller asked for the chip; refuse to produce an artifact
        # that mislabels a CPU run as the on-chip closed loop.
        raise RuntimeError(
            f"--platform tpu but devices are {jax.devices()[0].platform!r}"
        )
    # Stray-listener preflight (obs/preflight): this soak binds a FIXED
    # broker port — an already-listening stray would swallow the spawn
    # below and the soak would measure a foreign process. Fail loudly
    # with the pid; the disclosure rides the artifact.
    from dotaclient_tpu.obs.preflight import check as preflight_check

    host_preflight = preflight_check("aggregate_soak", ports=[PORT])

    policy = _policy_for(args.policy)
    lcfg = LearnerConfig(
        batch_size=args.batch_size, seq_len=16, policy=policy, publish_every=1
    )
    broker_url = f"tcp://127.0.0.1:{PORT}"
    frames_file = f"/tmp/soak_frames_{os.getpid()}.bin"

    # Pre-serialize realistic frames once (bench's generator, H=16 policy).
    frames = bench_mod._make_frames(lcfg, 64)
    with open(frames_file, "wb") as f:
        for fr in frames:
            f.write(struct.pack("<I", len(fr)))
            f.write(fr)
    frame_bytes = sum(len(f) for f in frames) / len(frames)

    server = subprocess.Popen(
        [sys.executable, "-m", "dotaclient_tpu.transport.tcp_server", "--port", str(PORT),
         "--maxlen", "4096"],
        start_new_session=True,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    all_procs = []
    artifact = {
        "host": "1 CPU core — see module docstring for why the claim splits "
        "into phases A (fan-in at the bar, no competing learner compute) and "
        "B (closed-loop stability under a live learner)",
        "host_preflight": host_preflight,
        "learner_platform": args.platform,
        "policy": args.policy,
        "batch": f"{lcfg.batch_size}x{lcfg.seq_len}",
        "phases_run": args.phase,
        "frame_bytes_mean": round(frame_bytes),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    try:
        for _ in range(240):
            try:
                socket.create_connection(("127.0.0.1", PORT), timeout=0.5).close()
                break
            except OSError:
                time.sleep(0.5)
        else:
            raise RuntimeError("broker server never listened")

        # ---------------- PHASE 0: measured transport calibration --------
        # One in-process publisher + one consumer through the real broker
        # for a few seconds: the transport-headroom number in the artifact
        # is MEASURED in the same run, not asserted from a notebook.
        cal_pub = connect(broker_url)
        cal_sub = connect(broker_url)
        cal_frame = frames[0]
        cal_recv = [0]
        cal_stop = threading.Event()

        def cal_consumer():
            while not cal_stop.is_set():
                cal_recv[0] += len(cal_sub.consume_experience(64, timeout=0.2))

        t_cal = threading.Thread(target=cal_consumer, daemon=True)
        t_cal.start()
        sent = 0
        t0 = time.time()
        while time.time() - t0 < 5.0:
            cal_pub.publish_experience(cal_frame)
            sent += 1
        cal_dt = time.time() - t0
        cal_stop.set()
        t_cal.join(timeout=2)
        # The CONSUMED rate is the deliverable-throughput claim (the
        # publish side alone would overstate it exactly when transport is
        # the bottleneck and the drop-oldest queue eats the difference).
        consumed_rate = cal_recv[0] / cal_dt
        artifact["phase_0_transport_calibration"] = {
            "topology": "1 publisher + 1 consumer through the tcp broker, this host, this run",
            "published_frames_per_sec": round(sent / cal_dt, 1),
            "consumed_frames_per_sec": round(consumed_rate, 1),
            "env_steps_per_sec_equiv_consumed": round(consumed_rate * lcfg.seq_len, 1),
            "headroom_over_50k_bar": round(consumed_rate * lcfg.seq_len / 50_000.0, 2),
        }
        print(json.dumps(artifact["phase_0_transport_calibration"]), flush=True)
        # Drain any calibration backlog so phase A starts from an EMPTY
        # queue — residual frames would inflate phase A's staged counts
        # and register a phantom heartbeat from the unpatched cal frame.
        while cal_sub.consume_experience(256, timeout=0.2):
            pass

        # ---------------- PHASE A: 64-process fan-in at the 50k bar ------
        if args.phase in ("all", "a"):
            _run_phase_a(args, artifact, lcfg, frames_file, all_procs, broker_url, np)

        # ---------------- PHASE B: closed loop under a live learner ------
        if args.phase in ("all", "b"):
            _run_phase_b(
                args, artifact, lcfg, frames, frames_file, all_procs, broker_url, np,
                Learner, connect,
            )

        verdict = {}
        if "phase_a_fan_in" in artifact:
            # Key says what phase A is: fan-in at the bar with NO learner
            # compute competing for the core (VERDICT r4 weak item 3).
            verdict["offered_50k_bar_no_learner"] = artifact["phase_a_fan_in"]["meets_50k_bar"]
        if "phase_b_closed_loop" in artifact:
            pb = artifact["phase_b_closed_loop"]
            verdict["closed_loop_live"] = bool(
                pb["genuine_actor_liveness"]["episodes_completed"] > 0
                and pb["learner_versions_published"] > 1
            )
            verdict["closed_loop_live_rate_env_steps_per_sec"] = pb[
                "consumed_env_steps_per_sec"
            ]
            verdict["closed_loop_consumed_ge_50k"] = bool(
                pb["consumed_env_steps_per_sec"] >= 50_000
            )
        artifact["verdict"] = verdict
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=2)
        print(json.dumps(artifact, indent=2))
        ok = all(
            v for k, v in verdict.items()
            if k in ("offered_50k_bar_no_learner", "closed_loop_live")
        )
        return 0 if ok else 1
    finally:
        for pr in all_procs:
            if pr.poll() is None:
                pr.kill()
        try:
            os.killpg(server.pid, 9)
        except ProcessLookupError:
            pass
        import glob as _glob

        for path in [frames_file] + _glob.glob(f"/tmp/soak_go?_{os.getpid()}*"):
            try:
                os.unlink(path)
            except OSError:
                pass


def _run_phase_a(args, artifact, lcfg, frames_file, all_procs, broker_url, np):
    from dotaclient_tpu.runtime.staging import StagingBuffer
    from dotaclient_tpu.transport.base import connect

    go_a = f"/tmp/soak_goA_{os.getpid()}"
    procs = _spawn_children(
        args.replayers, 0, args.rate, args.phase_a_duration, frames_file, go_a, 1000
    )
    all_procs += procs
    # Staging consumer only — drain into packed batches and discard
    # (version pinned at 0: staleness belongs to phase B).
    staging = StagingBuffer(lcfg, connect(broker_url), version_fn=lambda: 0).start()
    drained = [0]
    stop_drain = threading.Event()

    def drain():
        while not stop_drain.is_set():
            b = staging.get_batch(timeout=0.5)
            if b is not None:
                drained[0] += int(np.sum(b.mask))

    threading.Thread(target=drain, daemon=True).start()
    print(f"phase A: waiting for {len(procs)} replayers' READY files "
          f"(serialized interpreter startup, one core)...", flush=True)
    _wait_ready(go_a, len(procs))
    with open(go_a, "w") as f:
        f.write("go")
    t0 = time.time()
    active_peak = 0
    depth_a = []
    mon = connect(broker_url)
    while time.time() - t0 < args.phase_a_duration + 5:
        time.sleep(5.0)
        try:
            depth_a.append(mon.experience_depth())
        except Exception:
            pass
        st = staging.stats()
        active_peak = max(active_peak, st["active_actors"])
        print(
            f"  phaseA t={time.time() - t0:5.1f}s consumed={st['consumed']} "
            f"active={st['active_actors']} depth={depth_a[-1] if depth_a else '?'}",
            flush=True,
        )
    offered_a, _, _, senders = _collect_children(procs, lcfg.seq_len)
    stop_drain.set()
    st_a = staging.stats()
    staging.stop()
    wall_a = args.phase_a_duration  # each child sends for exactly this long
    artifact["phase_a_fan_in"] = {
        "topology": f"{args.replayers} replayer procs -> tcp broker proc -> "
        f"staging consumer (no learner compute)",
        "senders_reporting": senders,
        "duration_s": wall_a,
        "offered_env_steps_per_sec": round(offered_a / wall_a, 1),
        "meets_50k_bar": bool(offered_a / wall_a >= 50_000),
        "staged_env_steps_per_sec": round(drained[0] / wall_a, 1),
        "frames_consumed": st_a["consumed"],
        "dropped_bad": st_a["dropped_bad"],
        "active_actors_peak": int(active_peak),
        "broker_depth_mean": round(float(np.mean(depth_a)), 1) if depth_a else None,
        "broker_depth_max": int(np.max(depth_a)) if depth_a else None,
    }
    print(json.dumps(artifact["phase_a_fan_in"], indent=2), flush=True)


def _run_phase_b(
    args, artifact, lcfg, frames, frames_file, all_procs, broker_url, np, Learner, connect
):
    go_b = f"/tmp/soak_goB_{os.getpid()}"
    n_rep_b = args.replayers_b or max(args.replayers // 4, 8)
    procs = _spawn_children(
        n_rep_b, args.real_actors, args.rate, args.duration, frames_file, go_b, 2000,
        policy=args.policy,
    )
    all_procs += procs
    mon = connect(broker_url)
    learner = Learner(lcfg, connect(broker_url))
    # Warm the compile BEFORE the measured window: feed one batch of
    # frames directly and take one step, so phase B measures a hot
    # learner, not XLA's compiler. Warm frames carry a sentinel
    # actor_id so they can't inflate the phase-B heartbeat gauge.
    warm_pub = connect(broker_url)
    for i in range(lcfg.batch_size + 8):
        fr = bytearray(frames[i % len(frames)])
        struct.pack_into("<I", fr, 13, 999_999)
        warm_pub.publish_experience(bytes(fr))
    learner.run(num_steps=1, batch_timeout=120.0)
    print("phase B: learner warm; releasing cohort", flush=True)

    depth_b = []
    active_b = 0
    stale_sampler_stop = threading.Event()

    def sampler_b():
        nonlocal active_b
        while not stale_sampler_stop.is_set():
            time.sleep(5.0)
            try:
                depth_b.append(mon.experience_depth())
                # Count heartbeats directly, excluding the warm-up
                # sentinel id.
                cutoff = time.monotonic() - learner.staging.heartbeat_window_s
                seen = dict(learner.staging._actor_seen)
                live = sum(1 for a, t in seen.items() if t >= cutoff and a != 999_999)
                active_b = max(active_b, live)
            except Exception:
                pass

    threading.Thread(target=sampler_b, daemon=True).start()
    _wait_ready(go_b, len(procs))
    with open(go_b, "w") as f:
        f.write("go")
    steps_before = learner.env_steps_done
    t0 = time.time()
    learner.run(max_seconds=args.duration, batch_timeout=30.0)
    wall_b = time.time() - t0
    stale_sampler_stop.set()
    st_b = learner.staging.stats()
    offered_b, real_eps, real_steps, _ = _collect_children(procs, lcfg.seq_len)
    offered_b += real_steps
    artifact["phase_b_closed_loop"] = {
        "topology": f"{n_rep_b} replayer + {args.real_actors} genuine actor procs -> "
        f"tcp broker -> LIVE learner (batch {lcfg.batch_size}x{lcfg.seq_len}, "
        f"publish_every=1, device={args.platform})",
        "duration_s": round(wall_b, 1),
        "offered_env_steps_per_sec": round(offered_b / max(wall_b, 1), 1),
        "consumed_env_steps_per_sec": round(
            (learner.env_steps_done - steps_before) / max(wall_b, 1), 1
        ),
        "learner_versions_published": learner.version,
        "staleness": {
            "frames_consumed": st_b["consumed"],
            "dropped_stale": st_b["dropped_stale"],
            "dropped_bad": st_b["dropped_bad"],
            "stale_drop_rate": round(st_b["dropped_stale"] / max(st_b["consumed"], 1), 5),
        },
        "active_actors_peak": int(active_b),
        "broker_depth": {
            "bound": 4096,
            "mean": round(float(np.mean(depth_b)), 1) if depth_b else None,
            "max": int(np.max(depth_b)) if depth_b else None,
        },
        "genuine_actor_liveness": {
            "processes": args.real_actors,
            "episodes_completed": real_eps,
            "env_steps": real_steps,
        },
    }
    print(json.dumps(artifact["phase_b_closed_loop"], indent=2), flush=True)




if __name__ == "__main__":
    raise SystemExit(main())
