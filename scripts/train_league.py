"""BASELINE config-5 demonstration artifact: league self-play (PFSP)
with auxiliary value heads.

The benchmark ladder's top rung (BASELINE.md configs: "5v5 league
self-play (PFSP) + aux value heads"). This driver runs the full
config-5 machinery end-to-end at a CPU-feasible scale — SelfPlayActor
in league mode (frozen PFSP snapshots from the weight fanout, live side
publishes experience), aux heads (win-prob, last-hit, net-worth) on the
policy and in the loss — and writes `<out_dir>/metrics.jsonl` plus a
`LEAGUE.md` summary proving the pieces run TOGETHER, not just in unit
tests. Team size defaults to 1 (CPU-feasible); pass --team_size 5 for
the full 5v5 shape on capable hardware.

Run: python scripts/train_league.py --out_dir league_run
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")  # an all-in-one-process CPU driver

import numpy as np

from dotaclient_tpu.config import ActorConfig, LearnerConfig, PolicyConfig
from dotaclient_tpu.env.fake_dotaservice import FakeDotaService
from dotaclient_tpu.env.service import LocalDotaServiceStub
from dotaclient_tpu.runtime.harness import ActorPool
from dotaclient_tpu.runtime.learner import Learner
from dotaclient_tpu.runtime.selfplay import SelfPlayActor
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import connect as broker_connect

BROKER = "league_run"


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--out_dir", default="league_run")
    p.add_argument("--updates", type=int, default=150)
    p.add_argument("--team_size", type=int, default=1)
    p.add_argument("--n_actors", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def train_config5(
    seed: int,
    updates: int,
    team_size: int,
    n_actors: int,
    out_dir: str,
    ppo_reuse: bool = False,
):
    """Run the config-5 training topology (league-mode SelfPlayActors +
    aux-head learner over a mem broker) and return everything a grader
    needs: frozen INIT and FINAL params plus run-liveness evidence.
    Factored out of main() so scripts/grade_5v5.py trains each seed
    through the exact artifact path, not a drifting copy."""
    policy = PolicyConfig(
        unit_embed_dim=16, lstm_hidden=16, mlp_hidden=16, dtype="float32",
        aux_heads=True,  # config 5: win-prob / last-hit / net-worth heads
    )
    service = FakeDotaService()
    mem.reset(BROKER)
    lcfg = LearnerConfig(
        batch_size=16, seq_len=16, policy=policy, mesh_shape="dp=-1",
        publish_every=1, seed=seed,
        log_dir=os.path.join(out_dir, "learner_logs"),
    )
    lcfg.ppo.lr = 1e-3
    if ppo_reuse:
        # The r4 sample-reuse knob (3.4x fewer env steps to the same
        # skill on the north star) — the 5v5 grader trains with it.
        lcfg.ppo.epochs = 2
        lcfg.ppo.minibatches = 2
        lcfg.ppo.kl_stop = 0.05

    def make_actor(i: int):
        acfg = ActorConfig(
            env_addr="local", rollout_len=16, max_dota_time=30.0,
            opponent="league", team_size=team_size, policy=policy,
            league_capacity=8, league_snapshot_every=10, pfsp_mode="hard",
            seed=seed * 577 + i,
        )
        return SelfPlayActor(
            acfg, broker_connect(f"mem://{BROKER}"), actor_id=i,
            stub=LocalDotaServiceStub(service),
        )

    pool = ActorPool(make_actor, n_actors).start()
    actors = pool.actors
    learner = Learner(lcfg, broker_connect(f"mem://{BROKER}"))
    init_params = jax.device_get(learner.state.params)  # frozen yardstick twin
    try:
        learner.run(num_steps=updates, batch_timeout=120.0, max_idle=3)
    except TimeoutError as e:
        print(f"[league] aborted: {e}", flush=True)
    finally:
        pool.stop(timeout=30)
        learner.close()

    mlines = []
    mpath = os.path.join(out_dir, "learner_logs", "metrics.jsonl")
    if os.path.exists(mpath):
        mlines = [json.loads(l) for l in open(mpath)]
    aux_keys = [k for k in (mlines[-1] if mlines else {}) if k.startswith("aux_")]
    return {
        "policy": policy,
        "init_params": init_params,
        "final_params": jax.device_get(learner.state.params),
        "aux_keys": aux_keys,
        "league_sizes": [len(a.league) for a in actors if a.league is not None],
        "episodes": sum(a.episodes_done for a in actors),
        "pool_dead": pool.dead,
        "version": learner.version,
        "env_steps": learner.env_steps_done,
        "ppo": f"{lcfg.ppo.epochs}x{lcfg.ppo.minibatches} kl_stop {lcfg.ppo.kl_stop}",
    }


def main(argv=None) -> int:
    args = parse_args(argv)
    os.makedirs(args.out_dir, exist_ok=True)
    t_start = time.time()
    res = train_config5(args.seed, args.updates, args.team_size, args.n_actors, args.out_dir)
    wall_min = (time.time() - t_start) / 60.0
    aux_keys, league_sizes, episodes = res["aux_keys"], res["league_sizes"], res["episodes"]
    ok = (
        res["pool_dead"] == 0
        and res["version"] >= args.updates
        and bool(aux_keys)
        and any(s > 0 for s in league_sizes)
        and episodes > 0
    )
    summary = [
        "# League self-play + aux heads artifact (BASELINE config 5)",
        "",
        f"- result: **{'OK' if ok else 'INCOMPLETE'}**",
        f"- learner updates: {res['version']} (aux-head loss terms in metrics: {aux_keys})",
        f"- league pools (PFSP '{'hard'}'): {league_sizes} frozen snapshots per actor",
        f"- self-play episodes: {episodes} (team_size {args.team_size}; "
        f"live side publishes, frozen side from the pool)",
        f"- env steps trained: {res['env_steps']}  |  wall-clock: {wall_min:.1f} min (1 CPU core)",
        "",
        f"Reproduce: `python scripts/train_league.py --seed {args.seed} "
        f"--updates {args.updates} --team_size {args.team_size}`",
    ]
    with open(os.path.join(args.out_dir, "LEAGUE.md"), "w") as f:
        f.write("\n".join(summary) + "\n")
    print("\n".join(summary))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
