"""BASELINE config-3 demonstration artifact: 1v1 hero-pool self-play
with ONE shared policy.

The ladder's third rung: both sides draw per episode from a hero pool
(Nevermore / Sniper / Viper — different stats, same policy net), the
shared LSTM conditioning on the 8-dim hashed hero-identity code in the
hero features (env/heroes.py). This driver runs mirror self-play over
the pool end-to-end and writes `<out_dir>/HERO_POOL.md` plus
`metrics.jsonl` with PER-HERO return curves — the evidence config 3
asks for: one policy, three heroes, improving together.

Measurement design (learned the hard way — the first version graded
self-play EPISODE RETURNS and they are the wrong metric): in mirror
self-play the opponent improves in lockstep, so a hero's in-training
return can FALL while its absolute skill rises (observed: sniper's
curve inverted at 240 updates while the policy got better). Skill in
self-play must be judged against a FIXED yardstick, so this driver
trains on the pool via mirror self-play, then EVALUATES the frozen
final policy per hero vs the scripted bot and compares with the frozen
INITIAL policy on the same eval protocol. Success bar: every hero's
final eval return beats its initial eval return (3/3, fixed opponent,
paired seeds). The in-training per-hero curves are still written to
metrics.jsonl for inspection, unbarred.

Run: python scripts/train_hero_pool.py --out_dir hero_pool_run
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

jax.config.update("jax_platforms", "cpu")  # an all-in-one-process CPU driver


from dotaclient_tpu.config import ActorConfig, LearnerConfig, PolicyConfig
from dotaclient_tpu.env.fake_dotaservice import FakeDotaService
from dotaclient_tpu.env.service import LocalDotaServiceStub
from dotaclient_tpu.runtime.harness import ActorPool
from dotaclient_tpu.runtime.learner import Learner
from dotaclient_tpu.runtime.selfplay import SelfPlayActor
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import connect as broker_connect

BROKER = "hero_pool_run"
POOL = "npc_dota_hero_nevermore,npc_dota_hero_sniper,npc_dota_hero_viper"


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--out_dir", default="hero_pool_run")
    p.add_argument("--updates", type=int, default=150)
    p.add_argument("--n_actors", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--eval_episodes", type=int, default=24, help="per hero, per policy")
    p.add_argument("--ppo_epochs", type=int, default=2)
    p.add_argument("--ppo_minibatches", type=int, default=2)
    p.add_argument("--ppo_kl_stop", type=float, default=0.05)
    return p.parse_args(argv)


def eval_per_hero(params, policy_cfg, heroes_list, episodes, seed):
    """Frozen-policy eval: `episodes` per hero vs the SCRIPTED bot (the
    fixed yardstick), fresh env per hero. Returns {hero: mean_return}.
    Rides the standard Evaluator (eval/evaluator.py) — same frozen-param
    episode loop the north-star artifact uses — and reads its
    mean_return, ignoring the rating side."""
    from dotaclient_tpu.eval.evaluator import Evaluator

    out = {}
    for hero in heroes_list:
        acfg = ActorConfig(
            env_addr="local", rollout_len=16, max_dota_time=30.0,
            opponent="scripted_hard", hero=hero, policy=policy_cfg, seed=seed,
        )
        ev = Evaluator(acfg, stub=LocalDotaServiceStub(FakeDotaService()))
        out[hero] = float(ev.evaluate(params, n_episodes=episodes).mean_return)
        ev.close()
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    os.makedirs(args.out_dir, exist_ok=True)
    t_start = time.time()

    policy = PolicyConfig(unit_embed_dim=16, lstm_hidden=16, mlp_hidden=16, dtype="float32")
    service = FakeDotaService()
    mem.reset(BROKER)
    lcfg = LearnerConfig(
        batch_size=16, seq_len=16, policy=policy, mesh_shape="dp=-1",
        publish_every=1, seed=args.seed,
        log_dir=os.path.join(args.out_dir, "learner_logs"),
    )
    lcfg.ppo.lr = 1e-3
    lcfg.ppo.entropy_coef = 0.005
    lcfg.ppo.epochs = args.ppo_epochs
    lcfg.ppo.minibatches = args.ppo_minibatches
    lcfg.ppo.kl_stop = args.ppo_kl_stop
    records = []  # (hero_name, episode_return) in completion order
    lock = threading.Lock()

    def make_actor(i: int):
        acfg = ActorConfig(
            env_addr="local", rollout_len=16, max_dota_time=30.0,
            opponent="self", hero=POOL, policy=policy, seed=args.seed * 733 + i,
        )
        return SelfPlayActor(
            acfg, broker_connect(f"mem://{BROKER}"), actor_id=i,
            stub=LocalDotaServiceStub(service),
        )

    def on_episode(i, actor, ret):
        with lock:
            records.append((actor.last_heroes[0], float(ret)))

    pool = ActorPool(make_actor, args.n_actors, on_episode).start()
    learner = Learner(lcfg, broker_connect(f"mem://{BROKER}"))
    init_params = jax.device_get(learner.state.params)  # frozen yardstick twin
    try:
        learner.run(num_steps=args.updates, batch_timeout=120.0, max_idle=3)
    except TimeoutError as e:
        print(f"[hero-pool] aborted: {e}", flush=True)
    finally:
        pool.stop(timeout=30)
        learner.close()

    final_params = jax.device_get(learner.state.params)
    with lock:
        recs = list(records)
    with open(os.path.join(args.out_dir, "metrics.jsonl"), "w") as f:
        for hero, ret in recs:
            f.write(json.dumps({"hero": hero, "return": ret}) + "\n")
    heroes_seen = sorted({h for h, _ in recs})
    drawn = {h: sum(1 for hh, _ in recs if hh == h) for h in heroes_seen}

    # ---- fixed-yardstick eval: init vs final policy, per hero ----------
    pool_list = POOL.split(",")
    print("[hero-pool] eval phase: initial policy vs scripted_hard...", flush=True)
    init_eval = eval_per_hero(init_params, policy, pool_list, args.eval_episodes, args.seed + 7)
    print("[hero-pool] eval phase: final policy vs scripted_hard...", flush=True)
    final_eval = eval_per_hero(final_params, policy, pool_list, args.eval_episodes, args.seed + 7)
    deltas = {h: final_eval[h] - init_eval[h] for h in pool_list}

    wall_min = (time.time() - t_start) / 60.0
    ok = (
        pool.dead == 0
        and learner.version >= args.updates
        and len(heroes_seen) == 3
        and all(d > 0 for d in deltas.values())
    )
    lines = [
        "# Hero-pool self-play artifact (BASELINE config 3)",
        "",
        f"- result: **{'OK' if ok else 'INCOMPLETE'}**",
        f"- pool: {POOL} (both sides draw per episode; ONE shared policy, "
        f"hero-id conditioning in the features)",
        f"- learner updates: {learner.version} "
        f"(ppo reuse {args.ppo_epochs}x{args.ppo_minibatches}, kl_stop {args.ppo_kl_stop}); "
        f"{len(recs)} self-play episodes, draws per hero: "
        + ", ".join(f"{h.split('_')[-1]} {n}" for h, n in drawn.items()),
        f"- bar: FINAL policy beats INITIAL policy for EVERY hero on the fixed "
        f"yardstick (scripted_hard, {args.eval_episodes} eval eps/hero, paired seeds) — "
        f"self-play training curves are not graded (the opponent improves too; "
        f"see module docstring)",
    ] + [
        f"- {h.split('_')[-1]}: init {init_eval[h]:+.3f} -> final {final_eval[h]:+.3f} "
        f"({deltas[h]:+.3f})"
        for h in pool_list
    ] + [
        f"- wall-clock: {wall_min:.1f} min (1 CPU core, incl. both eval phases)",
        "",
        f"Reproduce: `python scripts/train_hero_pool.py --seed {args.seed} "
        f"--updates {args.updates}`",
    ]
    with open(os.path.join(args.out_dir, "HERO_POOL.md"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
