"""A/B: PPO sample reuse (epochs x minibatches + KL stop) vs single-update
(VERDICT r3 item 4 "Done" criterion: a learning-smoke A/B showing
equal-or-better return per env-step).

Both arms run the SAME closed loop as the default-gate learning smoke
(fake env → 3 actors → mem broker → learner) with the SAME number of
consumed learner batches — identical env-step budget — differing only in
ppo.epochs/minibatches/kl_stop. The reuse arm takes more gradient steps
per consumed env-step; at TPU speed those steps are otherwise-idle FLOPs,
so equal-or-better return per env-step means the knob is pure win.

Writes PPO_REUSE_AB.json: per-run early/late return windows, per-arm
means, and the verdict. ~6 min on one CPU core for 2 seeds x 2 arms.

Run: python scripts/ab_ppo_reuse.py [--updates 45] [--seeds 2]
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


def run_arm(tag: str, n_updates: int, seed: int, epochs: int, minibatches: int, kl_stop: float):
    """One closed-loop run; returns episode returns in completion order.
    Mirrors tests/test_learning.py::_run_smoke (the calibrated smoke)."""
    broker = f"ab_{tag}_{seed}"
    service = FakeDotaService()
    mem.reset(broker)
    lcfg = LearnerConfig(batch_size=16, seq_len=16, policy=SMALL, publish_every=1, seed=seed)
    lcfg.ppo.lr = 1e-3
    lcfg.ppo.entropy_coef = 0.005
    lcfg.ppo.epochs = epochs
    lcfg.ppo.minibatches = minibatches
    lcfg.ppo.kl_stop = kl_stop
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
    learner.run(num_steps=n_updates, batch_timeout=300.0)
    pool.stop(timeout=60, raise_on_dead=True)
    with lock:
        return np.asarray(returns, float)


def window_stats(rets: np.ndarray) -> dict:
    k = max(len(rets) // 3, 1)
    return {
        "episodes": len(rets),
        "early_mean": round(float(rets[:k].mean()), 4),
        "late_mean": round(float(rets[-k:].mean()), 4),
        "improvement": round(float(rets[-k:].mean() - rets[:k].mean()), 4),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="PPO_REUSE_AB.json")
    p.add_argument("--updates", type=int, default=45)
    p.add_argument("--seeds", type=int, default=2)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--minibatches", type=int, default=2)
    p.add_argument("--kl_stop", type=float, default=0.05)
    args = p.parse_args(argv)

    t0 = time.time()
    arms = {
        "single_update": dict(epochs=1, minibatches=1, kl_stop=0.0),
        "reuse": dict(epochs=args.epochs, minibatches=args.minibatches, kl_stop=args.kl_stop),
    }
    runs = {name: [] for name in arms}
    for name, knobs in arms.items():
        for seed in range(args.seeds):
            rets = run_arm(name, args.updates, seed, **knobs)
            stats = window_stats(rets)
            runs[name].append({"seed": seed, **stats})
            print(f"{name} seed={seed}: {stats}", flush=True)

    arm_late = {n: float(np.mean([r["late_mean"] for r in rs])) for n, rs in runs.items()}
    arm_impr = {n: float(np.mean([r["improvement"] for r in rs])) for n, rs in runs.items()}
    # Equal-or-better with a noise allowance: the smoke's seed noise is
    # ~0.2 return (test_learning.py calibration), so "not worse than
    # baseline minus 0.2" is the fairness bar; anything above baseline is
    # a straight win.
    verdict_ok = arm_late["reuse"] >= arm_late["single_update"] - 0.2
    artifact = {
        "knobs": arms,
        "updates_per_arm": args.updates,
        "env_steps_per_arm": args.updates * 16 * 16,
        "runs": runs,
        "arm_late_mean": {k: round(v, 4) for k, v in arm_late.items()},
        "arm_improvement_mean": {k: round(v, 4) for k, v in arm_impr.items()},
        "equal_or_better_per_env_step": bool(verdict_ok),
        "wall_s": round(time.time() - t0, 1),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
    print(json.dumps(artifact, indent=2))
    return 0 if verdict_ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
