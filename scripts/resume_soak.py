"""Resume soak: the preemption-tolerance proof → RESUME_SOAK.json.

Three parts, one artifact:

PART A — determinism (lockstep, mem transport, replay reservoir ON).
A reference run and a kill run train on the IDENTICAL deterministic
frame schedule, one chunk (= one train step) at a time, so every batch's
composition — fresh rows, reservoir rows, reservoir RNG draws — is a
pure function of restored state. The kill run dies twice:

  - SIGTERM at step T1: the drain path saves FULL state (params/opt,
    reservoir contents + priorities + RNG stream, 5 deliberately-staged
    pending frames, version high-water) with wait=True. The proof is
    the strongest claim a resume can make: param/opt-state hashes and
    losses are BIT-EXACT against the uninterrupted run for K post-resume
    steps — the restart is indistinguishable from not having happened.
  - SIGKILL at step T2: nothing is saved at death (queued saves
    discarded); the successor restores the last periodic checkpoint,
    and the publisher's version high-water file bumps its counter back
    to T2 so staleness stamps stay monotonic. The proof here is bounded
    divergence (the dead incarnation's post-checkpoint steps are lost,
    never silently re-counted) + exact frame conservation.

Conservation: every acked frame is accounted across ALL incarnations —
consumed + broker-resident at end; per-incarnation staging intake
identities and reservoir identities hold exactly (in-process kills keep
the dead incarnation's counters readable, the PR-6 BrokerIncarnations
argument applied to the learner).

PART B — wall-clock ride-through (tcp transport, real actors, the PR-6
mold). A genuine actor pool publishes through a live BrokerServer while
a ScheduleRunner executes `kill@T:D@learner:term` and
`kill@T:D@learner:kill` against LearnerIncarnations. Actors must ride
through both deaths via queue depth + ShedThrottle (their ledgers
balance, nobody crashes), the broker must shed — never silently drop —
during downtime, recovery must land inside the budget, and the broker
ledger must account every popped frame to a learner incarnation.

PART C — inertness (subprocess proof, PR-6 style). With --ckpt.*
defaults, a learner's checkpoint directory holds exactly the legacy
artifacts (no aux manifests, no version_hwm), no chaos import happens,
no SIGTERM handler is installed, and no async-save machinery exists —
the upgrade is invisible until a deployment opts in.

Run: python scripts/resume_soak.py                       # committed artifact
     python scripts/resume_soak.py --quick --out /tmp/x  # nightly wrapper
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import struct
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPLAY_TARGET = 2  # reservoir rows per batch in part A (ratio 2/16)


def _tiny_policy():
    from dotaclient_tpu.config import PolicyConfig

    return PolicyConfig(unit_embed_dim=16, lstm_hidden=16, mlp_hidden=16, dtype="float32")


def _state_hash(state) -> str:
    import jax
    import numpy as np

    h = hashlib.sha256()
    for leaf in jax.tree.leaves(jax.device_get((state.params, state.opt_state))):
        h.update(np.ascontiguousarray(leaf).tobytes())
    return h.hexdigest()


def _staging_ledger(learner, resume: dict) -> dict:
    """One incarnation's intake ledger (harvested while the object is
    still alive — the in-process-kill advantage)."""
    s = learner.staging.stats()
    return {
        "consumed": int(s["consumed"]),
        "dropped_stale": int(s["dropped_stale"]),
        "dropped_bad": int(s["dropped_bad"]),
        "rows_packed": int(s["rows_packed"]),
        "rows_replayed": int(s.get("rows_replayed", 0)),
        "replay_admitted": int(s.get("replay_admitted", 0)),
        "replay_evicted": int(s.get("replay_evicted", 0)),
        "replay_expired": int(s.get("replay_expired", 0)),
        "replay_retired": int(s.get("replay_retired", 0)),
        "reservoir_occupancy": int(s.get("replay_occupancy", 0)),
        "pending": int(s["pending_rollouts"]),
        "resume_pending": int(resume.get("resume_pending_frames", 0)),
        "resume_reservoir": int(resume.get("resume_reservoir_entries", 0)),
        "version": int(learner.version),
    }


def _intake_balance(led: dict) -> int:
    """consumed + restored pending == every counted fate. Zero or bust."""
    fresh_rows = led["rows_packed"] - led["rows_replayed"]
    return (
        led["consumed"]
        + led["resume_pending"]
        - led["dropped_stale"]
        - led["dropped_bad"]
        - fresh_rows
        - led["pending"]
        - led["replay_admitted"]
    )


def _reservoir_balance(led: dict) -> int:
    """admitted + restored == resident + evicted + expired + retired."""
    return (
        led["replay_admitted"]
        + led["resume_reservoir"]
        - led["reservoir_occupancy"]
        - led["replay_evicted"]
        - led["replay_expired"]
        - led["replay_retired"]
    )


# ---------------------------------------------------------------- part A


def _make_cfg_a(args, ckpt_dir):
    from dotaclient_tpu.config import (
        LearnerConfig,
        ObsConfig,
        PPOConfig,
        ReplayConfig,
        WatchdogConfig,
    )

    cfg = LearnerConfig(
        batch_size=args.batch_size,
        seq_len=args.seq_len,
        policy=_tiny_policy(),
        ppo=PPOConfig(max_staleness=4),
        replay=ReplayConfig(
            enabled=True,
            ratio=REPLAY_TARGET / args.batch_size,
            max_staleness=100_000,  # the soak's stale seeds must never expire
            max_replays=0,  # entries stay resident: occupancy (and k) constant
        ),
        checkpoint_dir=ckpt_dir,
        checkpoint_every=args.checkpoint_every,
        publish_every=1,
        metrics_every=1,
        obs=ObsConfig(
            enabled=True,
            install_handlers=False,  # the soak owns its signal handling
            step_phases=False,
            watchdog=WatchdogConfig(enabled=True, interval_s=2.0, stall_s=60.0),
        ),
    )
    cfg.ckpt.full_state = True
    cfg.ckpt.async_save = True
    return cfg


class _Feeder:
    """Deterministic lockstep publisher: frame content is a pure function
    of the frame pool index, stamped with the learner's CURRENT version —
    so the reference run and the kill run see the identical stream."""

    def __init__(self, broker, frames):
        self.broker = broker
        self.frames = frames
        self.cursor = 0
        self.attempted = 0
        self.acked = 0

    def publish(self, n: int, version: int, stamp_version=None):
        for _ in range(n):
            fr = bytearray(self.frames[self.cursor % len(self.frames)])
            self.cursor += 1
            struct.pack_into("<I", fr, 4, version if stamp_version is None else stamp_version)
            self.attempted += 1
            self.broker.publish_experience(bytes(fr))
            self.acked += 1


def _run_part_a_once(args, frames, kills: bool) -> dict:
    """One lockstep run over the canonical frame schedule; kills=True
    executes the SIGTERM drain at step T1 and the SIGKILL at step T2."""
    import jax

    from dotaclient_tpu.runtime.learner import Learner
    from dotaclient_tpu.transport import memory as mem
    from dotaclient_tpu.transport.memory import MemoryBroker

    name = f"resume-{'kills' if kills else 'ref'}"
    mem.reset(name)
    ckpt_dir = tempfile.mkdtemp(prefix=f"resume_soak_{'k' if kills else 'r'}_")
    cfg = _make_cfg_a(args, ckpt_dir)
    feeder = _Feeder(MemoryBroker(name, maxlen=65536), frames)

    out = {
        "hashes": {},
        "losses": {},
        "lives": [],
        "boots": [],
        "watchdog": None,
        "ckpt_dir": ckpt_dir,
    }
    t0 = time.monotonic()
    learner = Learner(cfg, MemoryBroker(name, maxlen=65536))
    out["boots"].append(
        {"construct_s": round(time.monotonic() - t0, 3), "resume": learner.resume_info}
    )

    def step_chunk(publish_n: int):
        feeder.publish(publish_n, learner.version)
        done = learner.run(num_steps=1, batch_timeout=60.0)
        assert done == 1, f"lockstep chunk trained {done} steps"
        out["hashes"][learner.version] = _state_hash(learner.state)
        out["losses"][learner.version] = float(learner.metrics.latest().get("loss", float("nan")))

    B = args.batch_size
    warm = args.warm_steps
    # Warm: reservoir empty, every batch is B fresh rows.
    for _ in range(warm):
        step_chunk(B)
    # Seed the reservoir: stale-stamped frames (version 1, learner is
    # `warm` versions ahead of them) route through the staleness filter
    # into the reservoir, never into a batch as fresh rows.
    feeder.publish(args.reservoir_seed, learner.version, stamp_version=1)
    # From here every batch is (B - REPLAY_TARGET) fresh + REPLAY_TARGET
    # reservoir re-emissions (occupancy is constant: max_replays=0).
    fresh_n = B - REPLAY_TARGET
    for step in range(warm + 1, args.steps + 1):
        if kills and step == args.term_at + 1:
            # ---- SIGTERM drain between chunks -------------------------
            # Stage (but do not train) a sub-batch of frames so the drain
            # has real pending state to preserve; the reference run gets
            # the IDENTICAL publishes at the identical point.
            feeder.publish(args.pending_extras, learner.version)
            _ingest_pending(learner, args.pending_extras)
            t_kill = time.monotonic()
            learner.drain_save()
            led = _staging_ledger(learner, out["boots"][-1]["resume"])
            led.update(sig="term", death_wall_s=round(time.monotonic() - t_kill, 3))
            out["lives"].append(led)
            learner.close()
            t_boot = time.monotonic()
            learner = Learner(cfg, MemoryBroker(name, maxlen=65536))
            out["boots"].append(
                {
                    "construct_s": round(time.monotonic() - t_boot, 3),
                    "resume": learner.resume_info,
                }
            )
            fresh_first = fresh_n - args.pending_extras
            feeder.publish(fresh_first, learner.version)
            done = learner.run(num_steps=1, batch_timeout=60.0)
            assert done == 1
            out["hashes"][learner.version] = _state_hash(learner.state)
            out["losses"][learner.version] = float(
                learner.metrics.latest().get("loss", float("nan"))
            )
            continue
        if not kills and step == args.term_at + 1:
            # Reference run: the same extras + ingest pause (stream
            # symmetry), just no death in between.
            feeder.publish(args.pending_extras, learner.version)
            _ingest_pending(learner, args.pending_extras)
            feeder.publish(fresh_n - args.pending_extras, learner.version)
            done = learner.run(num_steps=1, batch_timeout=60.0)
            assert done == 1
            out["hashes"][learner.version] = _state_hash(learner.state)
            out["losses"][learner.version] = float(
                learner.metrics.latest().get("loss", float("nan"))
            )
            continue
        if kills and step == args.kill_at + 1:
            # ---- SIGKILL between chunks -------------------------------
            # Nothing saved: queued aux/mirror/async work discarded; the
            # successor restores the last periodic checkpoint and the
            # version high-water file bumps its counter back to the
            # published front.
            led = _staging_ledger(learner, out["boots"][-1]["resume"])
            led.update(sig="kill", death_wall_s=0.0)
            out["lives"].append(led)
            learner.discard_unsaved()
            learner.close()
            t_boot = time.monotonic()
            learner = Learner(cfg, MemoryBroker(name, maxlen=65536))
            out["boots"].append(
                {
                    "construct_s": round(time.monotonic() - t_boot, 3),
                    "resume": learner.resume_info,
                }
            )
            assert learner.version == args.kill_at, (
                f"hwm bump must land the restored counter at the published "
                f"front: {learner.version} != {args.kill_at}"
            )
        step_chunk(fresh_n)

    wd = learner.obs.watchdog.verdict() if learner.obs and learner.obs.watchdog else {}
    out["watchdog"] = wd
    led = _staging_ledger(learner, out["boots"][-1]["resume"])
    led.update(sig="end", death_wall_s=0.0)
    out["lives"].append(led)
    out["feeder"] = {"attempted": feeder.attempted, "acked": feeder.acked}
    out["broker_depth_end"] = feeder.broker.experience_depth()
    learner.close()
    return out


def _ingest_pending(learner, n: int, timeout: float = 20.0) -> None:
    """Run the staging consumer just long enough to pull exactly the n
    staged frames out of the broker into _pending, then stop it."""
    learner.staging.start()
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if learner.staging.stats()["pending_rollouts"] >= n:
            break
        time.sleep(0.02)
    learner.staging.stop()
    got = learner.staging.stats()["pending_rollouts"]
    assert got == n, f"staged {got} != {n} pending frames"


def run_part_a(args) -> dict:
    import bench as bench_mod

    from dotaclient_tpu.config import LearnerConfig

    frames = bench_mod._make_frames(
        LearnerConfig(batch_size=args.batch_size, seq_len=args.seq_len, policy=_tiny_policy()),
        256,
    )
    ref = _run_part_a_once(args, frames, kills=False)
    kil = _run_part_a_once(args, frames, kills=True)

    K = args.parity_steps
    parity_versions = list(range(args.term_at + 1, args.term_at + 1 + K))
    bit_exact = all(ref["hashes"][v] == kil["hashes"][v] for v in parity_versions)
    loss_parity = all(ref["losses"][v] == kil["losses"][v] for v in parity_versions)
    post_kill = list(range(args.kill_at + 1, args.steps + 1))
    divergence = [abs(ref["losses"][v] - kil["losses"][v]) for v in post_kill]
    finite = all(d == d and d != float("inf") for d in divergence)

    conservation = _part_a_conservation(ref), _part_a_conservation(kil)
    term_life = next(l for l in kil["lives"] if l["sig"] == "term")
    kill_boot = kil["boots"][2]
    result = {
        "frame_schedule": {
            "steps": args.steps,
            "warm": args.warm_steps,
            "batch": f"{args.batch_size}x{args.seq_len}",
            "replay_rows_per_batch": REPLAY_TARGET,
            "reservoir_seed_frames": args.reservoir_seed,
            "term_kill_after_step": args.term_at,
            "sigkill_after_step": args.kill_at,
            "checkpoint_every": args.checkpoint_every,
        },
        "sigterm": {
            "drain_wall_s": term_life["death_wall_s"],
            "pending_preserved": term_life["pending"],
            "resume": kil["boots"][1]["resume"],
            "restart_construct_s": kil["boots"][1]["construct_s"],
            "parity_versions": parity_versions,
            "bit_exact_param_opt_hashes": bit_exact,
            "loss_parity": loss_parity,
        },
        "sigkill": {
            "resume": kill_boot["resume"],
            "restart_construct_s": kill_boot["construct_s"],
            "restored_step": kill_boot["resume"].get("resume_restored_step"),
            "version_hwm_bump": kill_boot["resume"].get("resume_version_hwm_bump"),
            "steps_lost_to_kill": int(
                args.kill_at - kill_boot["resume"].get("resume_restored_step", args.kill_at)
            ),
            "post_kill_loss_divergence_max": max(divergence) if divergence else 0.0,
            "divergence_finite": finite,
        },
        "reference": {"lives": ref["lives"], "feeder": ref["feeder"], "watchdog": ref["watchdog"]},
        "killed": {
            "lives": kil["lives"],
            "boots": kil["boots"],
            "feeder": kil["feeder"],
            "watchdog": kil["watchdog"],
        },
        "conservation_reference": conservation[0],
        "conservation_killed": conservation[1],
    }
    return result


def _part_a_conservation(run: dict) -> dict:
    lives = run["lives"]
    consumed = sum(l["consumed"] for l in lives)
    unaccounted = run["feeder"]["acked"] - consumed - run["broker_depth_end"]
    return {
        "acked": run["feeder"]["acked"],
        "consumed_all_incarnations": consumed,
        "broker_resident_end": run["broker_depth_end"],
        "unaccounted_frames": unaccounted,
        "intake_balances": [_intake_balance(l) for l in lives],
        "reservoir_balances": [_reservoir_balance(l) for l in lives],
    }


# ---------------------------------------------------------------- part B


def run_part_b(args) -> dict:
    from dotaclient_tpu.chaos import FaultSchedule, LearnerIncarnations, ScheduleRunner
    from dotaclient_tpu.config import (
        ActorConfig,
        LearnerConfig,
        ObsConfig,
        PPOConfig,
        ReplayConfig,
        WatchdogConfig,
    )
    from dotaclient_tpu.env.fake_dotaservice import FakeDotaService
    from dotaclient_tpu.env.service import LocalDotaServiceStub
    from dotaclient_tpu.runtime.actor import Actor
    from dotaclient_tpu.runtime.harness import ActorPool
    from dotaclient_tpu.runtime.learner import Learner
    from dotaclient_tpu.transport.base import RetryPolicy
    from dotaclient_tpu.transport.tcp import BrokerServer, TcpBroker

    server = BrokerServer(
        port=0, maxlen=4096, shed_high=args.shed_high, shed_low=args.shed_low
    ).start()
    ckpt_dir = tempfile.mkdtemp(prefix="resume_soak_b_")
    policy = _tiny_policy()
    # Part B sizes its batch to the actor fleet's offered rate: a
    # 2-actor pool fills an 8x4 batch in well under a second, so the
    # recovery probe (restart -> first post-restore trained step) is a
    # transport/restore measurement, not a data-starvation one.
    b_batch, b_seq = 8, 4

    def make_learner():
        cfg = LearnerConfig(
            batch_size=b_batch,
            seq_len=b_seq,
            policy=policy,
            ppo=PPOConfig(max_staleness=64),
            replay=ReplayConfig(
                enabled=True, ratio=0.25, max_staleness=100_000, byte_budget=16 << 20
            ),
            checkpoint_dir=ckpt_dir,
            checkpoint_every=20,
            publish_every=1,
            metrics_every=5,
            obs=ObsConfig(
                enabled=True,
                install_handlers=False,
                step_phases=False,
                watchdog=WatchdogConfig(enabled=True, interval_s=2.0, stall_s=60.0),
            ),
        )
        cfg.ckpt.full_state = True
        cfg.ckpt.async_save = True
        return Learner(cfg, TcpBroker(port=server.port, retry=RetryPolicy(window_s=8.0)))

    inc = LearnerIncarnations(make_learner, run_kwargs={"batch_timeout": 1.0}).start()

    def make_actor(i):
        acfg = ActorConfig(
            env_addr="local",
            rollout_len=b_seq,
            max_dota_time=4.0,
            policy=policy,
            seed=300 + i,
            max_weight_age_s=0.0,  # learner deaths legitimately pause broadcasts
        )
        return Actor(
            acfg,
            TcpBroker(port=server.port, retry=RetryPolicy(window_s=8.0)),
            actor_id=300 + i,
            stub=LocalDotaServiceStub(FakeDotaService()),
        )

    pool = ActorPool(make_actor, args.actors).start()
    # Warm gate: the schedule epoch starts only once the first
    # incarnation has demonstrably compiled and trained (version >= 2) —
    # otherwise this host's variable first-compile wall (5-20s under
    # load) eats the kill offsets and the phase measures XLA, not
    # recovery.
    warm_deadline = time.monotonic() + 180.0
    while inc.learner.version < 2 and time.monotonic() < warm_deadline:
        time.sleep(0.1)
    warm_version = int(inc.learner.version)
    t0 = time.monotonic()
    spec = (
        f"kill@{args.b_term_at}:{args.b_down_s}@learner:term,"
        f"kill@{args.b_kill_at}:{args.b_down_s}@learner:kill"
    )
    schedule = FaultSchedule.parse(spec, seed=args.seed)
    runner = ScheduleRunner(schedule, None, t0, learner=inc).start()
    time.sleep(args.b_duration_s)
    # Let the runner finish any in-flight kill + recovery probe before
    # teardown — compile jitter must slip the schedule, never truncate it.
    if runner._thread is not None:
        runner._thread.join(timeout=150.0)
    runner.stop()
    pool.stop(timeout=30.0)
    actor_ledger = pool.publish_stats()
    actor_ledger["attempted"] = (
        actor_ledger["published"] + actor_ledger["shed"] + actor_ledger["failed"]
    )
    totals = inc.final_ledger()
    final_life = inc.lives[-1]
    server.stop()
    broker = server.ledger()

    unaccounted = (
        broker["popped"]
        - broker["reply_lost"]
        - totals["consumed"]
    )
    return {
        "spec": spec,
        "duration_s": args.b_duration_s,
        "actors": args.actors,
        "batch": f"{b_batch}x{b_seq}",
        "warm_gate_version": warm_version,
        "watermarks": {"maxlen": 4096, "shed_high": args.shed_high, "shed_low": args.shed_low},
        "kills": runner.recovery,
        "lives": inc.lives,
        "boots": inc.boots,
        "actor_ledger": actor_ledger,
        "broker_ledger": broker,
        "conservation": {
            "unaccounted_frames": unaccounted,
            "intake_balances": [_intake_balance_b(l) for l in inc.lives],
            "broker_identity": broker["enqueued"]
            == broker["popped"] + broker["dropped_oldest"] + broker["resident"],
            "actor_ledger_balances": actor_ledger["attempted"]
            == actor_ledger["published"] + actor_ledger["shed"] + actor_ledger["failed"],
        },
        "watchdog_final": final_life.get("watchdog", {}),
    }


def _intake_balance_b(led: dict) -> int:
    fresh_rows = led["rows_packed"] - led["rows_replayed"]
    return (
        led["consumed"]
        + led["resume_pending"]
        - led["dropped_stale"]
        - led["dropped_bad"]
        - fresh_rows
        - led["pending_at_death"]
        - led["replay_admitted"]
    )


# ---------------------------------------------------------------- part C


_INERTNESS_SCRIPT = r"""
import json, os, signal, sys, tempfile
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
from dotaclient_tpu.config import LearnerConfig, PolicyConfig
from dotaclient_tpu.runtime.learner import Learner
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import connect
from dotaclient_tpu.transport.serialize import Rollout, serialize_rollout
import bench as bench_mod

policy = PolicyConfig(unit_embed_dim=16, lstm_hidden=16, mlp_hidden=16, dtype="float32")
cfg = LearnerConfig(batch_size=8, seq_len=4, policy=policy,
                    checkpoint_dir=tempfile.mkdtemp(), checkpoint_every=1,
                    metrics_every=1)
assert not cfg.ckpt.full_state and not cfg.ckpt.async_save and not cfg.ckpt.drain_on_sigterm
mem.reset("inert")
learner = Learner(cfg, connect("mem://inert"))
pub = connect("mem://inert")
for fr in bench_mod._make_frames(cfg, 16):
    pub.publish_experience(fr)
learner.run(num_steps=2, batch_timeout=30.0)
learner.checkpoint()
learner.close()
files = sorted(os.listdir(cfg.checkpoint_dir))
print(json.dumps({
    "chaos_imported": any(m.startswith("dotaclient_tpu.chaos") for m in sys.modules),
    "ckpt_files": files,
    "aux_or_hwm_files": [f for f in files if f.startswith("aux_") or f == "version_hwm"],
    "sigterm_handler_default": signal.getsignal(signal.SIGTERM) is signal.SIG_DFL,
    "async_worker_built": learner._ckpt_worker is not None,
    "state_copy_jit_built": learner._state_copy_jit is not None,
    "publish_hook_wired": learner.publisher._on_published is not None,
    "version": learner.version,
}))
"""


def run_part_c() -> dict:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", _INERTNESS_SCRIPT],
        cwd=repo,
        capture_output=True,
        text=True,
        timeout=600,
    )
    if proc.returncode != 0:
        return {"error": f"inertness subprocess failed: {proc.stderr[-2000:]}"}
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    report["ok"] = (
        not report["chaos_imported"]
        and not report["aux_or_hwm_files"]
        and report["sigterm_handler_default"]
        and not report["async_worker_built"]
        and not report["state_copy_jit_built"]
        and not report["publish_hook_wired"]
        and report["version"] == 2
    )
    return report


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="RESUME_SOAK.json")
    p.add_argument("--seed", type=int, default=11)
    p.add_argument("--batch-size", dest="batch_size", type=int, default=16)
    p.add_argument("--seq-len", dest="seq_len", type=int, default=8)
    p.add_argument("--steps", type=int, default=46)
    p.add_argument("--warm-steps", dest="warm_steps", type=int, default=6)
    p.add_argument("--term-at", dest="term_at", type=int, default=20)
    p.add_argument("--kill-at", dest="kill_at", type=int, default=40)
    p.add_argument("--parity-steps", dest="parity_steps", type=int, default=5)
    p.add_argument("--checkpoint-every", dest="checkpoint_every", type=int, default=7)
    p.add_argument("--pending-extras", dest="pending_extras", type=int, default=5)
    p.add_argument("--reservoir-seed", dest="reservoir_seed", type=int, default=4)
    p.add_argument("--recovery-budget-s", dest="recovery_budget_s", type=float, default=30.0)
    p.add_argument("--drain-budget-s", dest="drain_budget_s", type=float, default=45.0)
    # part B
    p.add_argument("--actors", type=int, default=2)
    p.add_argument("--b-duration-s", dest="b_duration_s", type=float, default=34.0)
    p.add_argument("--b-term-at", dest="b_term_at", type=float, default=6.0)
    p.add_argument("--b-kill-at", dest="b_kill_at", type=float, default=16.0)
    p.add_argument("--b-down-s", dest="b_down_s", type=float, default=2.0)
    p.add_argument("--shed-high", dest="shed_high", type=int, default=48)
    p.add_argument("--shed-low", dest="shed_low", type=int, default=16)
    p.add_argument("--quick", action="store_true", help="nightly-wrapper scale, same invariants")
    args = p.parse_args(argv)
    if args.quick:
        args.steps, args.warm_steps = 26, 6
        # kill_at must not be a checkpoint-cadence multiple, or the
        # periodic save landing on the kill step makes steps_lost 0 and
        # the hwm-bump assertion vacuous.
        args.term_at, args.kill_at = 12, 22
        args.parity_steps = 3
        args.checkpoint_every = 5
        args.b_duration_s, args.b_term_at, args.b_kill_at = 27.0, 4.0, 12.0

    import jax

    jax.config.update("jax_platforms", "cpu")

    from dotaclient_tpu.obs.preflight import check as preflight_check

    artifact = {
        "host": "single host, CPU learner (tiny policy); part A mem transport, part B tcp",
        "host_preflight": preflight_check("resume_soak"),
        "seed": args.seed,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "budgets": {
            "recovery_s": args.recovery_budget_s,
            "drain_s": args.drain_budget_s,
        },
    }
    part_a = run_part_a(args)
    artifact["part_a_determinism"] = part_a
    print(json.dumps({"part_a": {"sigterm": part_a["sigterm"], "sigkill": part_a["sigkill"]}}), flush=True)
    part_b = run_part_b(args)
    artifact["part_b_ride_through"] = part_b
    print(json.dumps({"part_b_kills": part_b["kills"]}), flush=True)
    part_c = run_part_c()
    artifact["part_c_inertness"] = part_c

    cons_k = part_a["conservation_killed"]
    cons_r = part_a["conservation_reference"]
    b_kills = part_b["kills"]
    restarts = [b["construct_s"] for b in part_a["killed"]["boots"][1:]]
    verdict = {
        "sigterm_resume_bit_exact": bool(part_a["sigterm"]["bit_exact_param_opt_hashes"]),
        "sigterm_loss_parity": bool(part_a["sigterm"]["loss_parity"]),
        "sigterm_pending_preserved": part_a["sigterm"]["pending_preserved"]
        == args.pending_extras,
        "sigkill_hwm_bump_monotonic": part_a["sigkill"]["version_hwm_bump"]
        == part_a["sigkill"]["steps_lost_to_kill"]
        and part_a["sigkill"]["steps_lost_to_kill"] > 0,
        "sigkill_divergence_bounded": bool(part_a["sigkill"]["divergence_finite"])
        and part_a["sigkill"]["post_kill_loss_divergence_max"] < 10.0,
        "part_a_zero_unaccounted": cons_k["unaccounted_frames"] == 0
        and cons_r["unaccounted_frames"] == 0,
        "part_a_intake_balanced": all(b == 0 for b in cons_k["intake_balances"])
        and all(b == 0 for b in cons_r["intake_balances"]),
        "part_a_reservoir_balanced": all(b == 0 for b in cons_k["reservoir_balances"])
        and all(b == 0 for b in cons_r["reservoir_balances"]),
        "part_a_recovery_in_budget": all(r < args.recovery_budget_s for r in restarts),
        "part_a_drain_in_budget": next(
            l["death_wall_s"] for l in part_a["killed"]["lives"] if l["sig"] == "term"
        )
        < args.drain_budget_s,
        "part_a_watchdog_clean": not part_a["killed"]["watchdog"].get("tripped", False)
        and not part_a["reference"]["watchdog"].get("tripped", False),
        "part_b_kills_executed": len(b_kills) == 2
        and {k["sig"] for k in b_kills} == {"term", "kill"},
        "part_b_recovered_in_budget": all(
            k["recovery_s"] is not None and k["recovery_s"] < args.recovery_budget_s
            for k in b_kills
        ),
        "part_b_term_exit_clean": any(
            l["sig"] == "term" and l["exit_clean"] for l in part_b["lives"]
        ),
        "part_b_actors_rode_through": bool(
            part_b["conservation"]["actor_ledger_balances"]
        ),
        "part_b_zero_unaccounted": part_b["conservation"]["unaccounted_frames"] == 0
        and all(b == 0 for b in part_b["conservation"]["intake_balances"]),
        "part_b_no_silent_drop_oldest": part_b["broker_ledger"]["dropped_oldest"] == 0,
        "part_b_broker_identity": bool(part_b["conservation"]["broker_identity"]),
        "part_b_watchdog_clean": not part_b["watchdog_final"].get("tripped", False),
        "inertness_chaos_off": bool(part_c.get("ok", False)),
    }
    artifact["verdict"] = verdict
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
    print(json.dumps(artifact, indent=2))
    return 0 if all(v for v in verdict.values() if isinstance(v, bool)) else 1


if __name__ == "__main__":
    raise SystemExit(main())
