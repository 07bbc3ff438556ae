"""A/B: host-side prioritized replay reservoir on vs off (ISSUE 1
acceptance: rollouts that would previously be dropped as stale are
instead admitted and sampled — drop-stale decreases, hit ratio > 0 —
at equal-or-better learning).

Both arms run the SAME closed loop as scripts/ab_ppo_reuse.py (fake env
→ 3 actors → mem broker → learner) with the SAME number of consumed
learner batches, under a deliberately tight ppo.max_staleness so the
CPU smoke reproduces the regime where the learner's version counter
outruns the frames in flight. The arms
differ only in LearnerConfig.replay: off (reference drop-on-stale
behavior) vs on at ratio 0.25 with ACER truncated importance weights.

Writes REPLAY_AB.json: per-arm env-steps/s, learner-steps/s, staging
drop/replay counters, return windows, and the verdict. Nightly-tier
alongside ab_ppo_reuse.py (tests/test_replay.py::test_ab_replay_nightly).

Run: python scripts/ab_replay.py [--updates 30] [--seeds 2]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")  # actors and learner in one process, on the CPU

import numpy as np

from dotaclient_tpu.config import ActorConfig, LearnerConfig, PolicyConfig
from dotaclient_tpu.env.fake_dotaservice import FakeDotaService
from dotaclient_tpu.env.service import LocalDotaServiceStub
from dotaclient_tpu.runtime.actor import Actor
from dotaclient_tpu.runtime.harness import ActorPool
from dotaclient_tpu.runtime.learner import Learner
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import connect as broker_connect

SMALL = PolicyConfig(unit_embed_dim=16, lstm_hidden=16, mlp_hidden=16, dtype="float32")


def run_arm(tag: str, n_updates: int, seed: int, replay_on: bool, ratio: float):
    """One closed-loop run; returns (episode returns, staging stats,
    env_steps, wall_s). Mirrors ab_ppo_reuse.run_arm."""
    broker = f"abr_{tag}_{seed}"
    service = FakeDotaService()
    mem.reset(broker)
    lcfg = LearnerConfig(batch_size=16, seq_len=16, policy=SMALL, publish_every=1, seed=seed)
    lcfg.ppo.lr = 1e-3
    lcfg.ppo.entropy_coef = 0.005
    # Tight staleness bound: reproduces a fast learner's regime on the
    # CPU smoke — the version counter outruns frames in flight, so
    # the off arm actually drops and the on arm actually replays.
    lcfg.ppo.max_staleness = 1
    lcfg.replay.enabled = replay_on
    lcfg.replay.ratio = ratio
    lcfg.replay.max_staleness = 16
    returns, lock = [], threading.Lock()

    def make_actor(i):
        acfg = ActorConfig(
            env_addr="local", rollout_len=16, max_dota_time=30.0, policy=SMALL, seed=seed * 1000 + i
        )
        return Actor(
            acfg, broker_connect(f"mem://{broker}"), actor_id=i, stub=LocalDotaServiceStub(service)
        )

    def on_episode(i, actor, ret):
        with lock:
            returns.append(ret)

    pool = ActorPool(make_actor, 3, on_episode).start()
    learner = Learner(lcfg, broker_connect(f"mem://{broker}"))
    t0 = time.time()
    done = learner.run(num_steps=n_updates, batch_timeout=300.0)
    wall = time.time() - t0
    stats = learner.staging.stats()
    env_steps = learner.env_steps_done
    pool.stop(timeout=60, raise_on_dead=True)
    with lock:
        return np.asarray(returns, float), stats, env_steps, wall, done


def window_stats(rets: np.ndarray) -> dict:
    if len(rets) == 0:
        return {"episodes": 0, "early_mean": 0.0, "late_mean": 0.0, "improvement": 0.0}
    k = max(len(rets) // 3, 1)
    return {
        "episodes": len(rets),
        "early_mean": round(float(rets[:k].mean()), 4),
        "late_mean": round(float(rets[-k:].mean()), 4),
        "improvement": round(float(rets[-k:].mean() - rets[:k].mean()), 4),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="REPLAY_AB.json")
    p.add_argument("--updates", type=int, default=30)
    p.add_argument("--seeds", type=int, default=2)
    p.add_argument("--ratio", type=float, default=0.25)
    args = p.parse_args(argv)

    t0 = time.time()
    arms = {"replay_off": False, "replay_on": True}
    runs = {name: [] for name in arms}
    for name, on in arms.items():
        for seed in range(args.seeds):
            rets, stats, env_steps, wall, done = run_arm(name, args.updates, seed, on, args.ratio)
            row = {
                "seed": seed,
                "learner_steps": done,
                "env_steps": int(env_steps),
                "env_steps_per_sec": round(env_steps / max(wall, 1e-9), 1),
                "learner_steps_per_sec": round(done / max(wall, 1e-9), 3),
                "dropped_stale": int(stats["dropped_stale"]),
                "consumed": int(stats["consumed"]),
                **window_stats(rets),
            }
            if on:
                row["replay_admitted"] = int(stats["replay_admitted"])
                row["replay_sampled"] = int(stats["replay_sampled"])
                row["replay_hit_ratio"] = round(float(stats["replay_hit_ratio"]), 4)
                row["replay_occupancy"] = int(stats["replay_occupancy"])
                row["replay_bytes_spilled"] = int(stats["replay_bytes_spilled"])
            runs[name].append(row)
            print(f"{name} seed={seed}: {row}", flush=True)

    def arm_mean(name, key):
        return float(np.mean([r[key] for r in runs[name]]))

    off_dropped = arm_mean("replay_off", "dropped_stale")
    on_dropped = arm_mean("replay_on", "dropped_stale")
    on_hit = arm_mean("replay_on", "replay_hit_ratio")
    # Acceptance: previously-wasted frames are recovered — the stale-drop
    # counter decreases and the reservoir actually serves rows. If the
    # off arm never dropped anything (no staleness on this host), the A/B
    # has nothing to show and passes vacuously (noted in the artifact).
    no_staleness = off_dropped == 0
    verdict_ok = no_staleness or (on_dropped < off_dropped and on_hit > 0)
    artifact = {
        "updates_per_arm": args.updates,
        "replay_ratio": args.ratio,
        "runs": runs,
        "arm_mean": {
            "dropped_stale": {"replay_off": off_dropped, "replay_on": on_dropped},
            "env_steps_per_sec": {n: round(arm_mean(n, "env_steps_per_sec"), 1) for n in arms},
            "learner_steps_per_sec": {
                n: round(arm_mean(n, "learner_steps_per_sec"), 3) for n in arms
            },
            "late_return": {n: round(arm_mean(n, "late_mean"), 4) for n in arms},
            "replay_hit_ratio": round(on_hit, 4),
        },
        "no_staleness_observed": bool(no_staleness),
        "stale_drops_recovered": bool(verdict_ok),
        "wall_s": round(time.time() - t0, 1),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
    print(json.dumps(artifact, indent=2))
    return 0 if verdict_ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
