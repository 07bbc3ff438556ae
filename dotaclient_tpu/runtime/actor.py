"""Asyncio actor loop — the re-design of the reference's agent.py
(SURVEY.md §2 "Actor loop", §3.1 call stack).

Per-step hot loop, exactly the reference's shape: observe() over gRPC →
featurize → policy step with carried LSTM state → mask/sample →
act() over gRPC → shaped reward from worldstate deltas → append to the
rollout chunk; every `rollout_len` steps (or at episode end) the chunk
ships to the broker with the chunk-start LSTM state and the model
version; fresh weights hot-swap in from the weight fanout at chunk
boundaries.

TPU-first differences from the reference:
- inference is ONE jit-compiled function (featurized obs + LSTM state +
  rng → action ints, log-prob, value, new state) — sampling happens
  inside jit so no logits ever cross the host boundary;
- the actor initializes params deterministically from the same seed as
  the learner, so it can act from step zero without waiting for the
  first weight broadcast (the reference downloads a pretrained
  state_dict or waits);
- rollouts go out in the pickle-free wire format (transport/serialize).

Vectorized fleet mode (`--envs_per_process M`, the SEED RL / Sample
Factory inference-server move): one process drives M env sessions on a
single asyncio loop. Each env runs the SAME episode loop as the classic
actor, but its per-tick policy step is submitted to a shared
`InferenceBatcher` that gathers up to M requests (bounded by
`--gather_window_s` so one slow observe() can't stall the batch), pads
partial batches to capacity, and runs ONE jit call per tick — the
batch-1 dispatch overhead that dominates the classic path amortizes
across all M envs. Per-env rng streams and a lax.map row layout keep
the batched step bit-identical to stepping each env alone
(tests/test_actor_fleet.py); scripts/bench_actors.py measures the
offered-rate curve into ACTOR_FLEET.json.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import List, Optional, Tuple

import grpc
import jax
import jax.numpy as jnp
import numpy as np

from dotaclient_tpu.config import ActorConfig
from dotaclient_tpu.env import featurizer as F
from dotaclient_tpu.env import heroes
from dotaclient_tpu.env import rewards as R
from dotaclient_tpu.env.service import AsyncDotaServiceStub, connect_async
from dotaclient_tpu.models import policy as P
from dotaclient_tpu.ops import action_dist as ad
from dotaclient_tpu.protos import dotaservice_pb2 as ds
from dotaclient_tpu.protos import worldstate_pb2 as ws
from dotaclient_tpu.transport.base import Broker, BrokerShedError, RetryPolicy
from dotaclient_tpu.transport.serialize import (
    Rollout,
    RolloutAux,
    deserialize_weights,
    serialize_rollout,
    unflatten_params,
    wire_cast_fn,
)

_log = logging.getLogger(__name__)


class StaleWeightsError(RuntimeError):
    """Raised by the actor kill switch: no weight broadcast arrived for
    longer than `max_weight_age_s`. The actor exits non-zero so its
    supervisor (k8s) replaces it with a fresh pod that re-subscribes —
    on-policy data from an ancient policy is worse than none
    (SURVEY.md §5 "stale-version kill switch")."""


def apply_weight_frame(agent, frame: bytes, log_name: str, on_applied=None) -> bool:
    """Shared weight hot-swap for Actor / SelfPlayActor / Evaluator.

    - malformed frames are logged and ignored (a bad broadcast must
      never kill a subscriber);
    - within one learner boot (same frame boot_epoch), frames OLDER than
      what the agent runs are rejected — a publish that sat blocked
      through a broker outage must not regress weights;
    - a boot_epoch CHANGE is the deterministic learner-restart signal
      (the epoch is drawn once at learner boot and stamped into every
      DTW2 frame): the agent resyncs to the new boot's version
      unconditionally, even if lower. This replaced the r3
      consecutive-older-frames counter, whose threshold a jittery broker
      at publish_every=1 could reach with merely-delayed frames
      (VERDICT r3 weak item 5). Worst case under the epoch scheme: ONE
      delayed frame from a dead previous boot swaps in once, and the
      next live broadcast (epoch differs again) swaps it right back;
    - `on_applied(named_params, version)` runs after a successful swap
      (league snapshotting hook).
    """
    try:
        named, version, boot_epoch = deserialize_weights(frame)
    except Exception as e:  # truncated frames raise struct.error etc.
        _log.warning("%s: bad weight frame: %s", log_name, e)
        return False
    last_epoch = getattr(agent, "weight_epoch", None)
    if last_epoch is not None and boot_epoch != last_epoch:
        _log.warning(
            "%s: weight boot_epoch %d -> %d — learner restarted, resyncing to v%d",
            log_name,
            last_epoch,
            boot_epoch,
            version,
        )
    elif version < agent.version:
        _log.warning(
            "%s: ignoring stale weight frame v%d (< v%d, same boot)",
            log_name,
            version,
            agent.version,
        )
        return False
    try:
        # a frame that deserializes but doesn't match the agent's param
        # template (learner restarted with a different PolicyConfig)
        # must ALSO never kill the subscriber
        agent.params = unflatten_params(named, agent.params)
    except Exception as e:
        _log.warning("%s: weight frame does not fit params (%s); ignoring", log_name, e)
        return False
    agent.version = version
    agent.weight_epoch = boot_epoch
    agent.last_weight_time = time.monotonic()
    if on_applied is not None:
        on_applied(named, version)
    return True


def check_weight_freshness(actor) -> None:
    """Shared kill-switch check for Actor and SelfPlayActor (both carry
    cfg.max_weight_age_s and last_weight_time)."""
    age = time.monotonic() - actor.last_weight_time
    if 0 < actor.cfg.max_weight_age_s < age:
        raise StaleWeightsError(
            f"actor {actor.actor_id}: no weight update for {age:.0f}s "
            f"(limit {actor.cfg.max_weight_age_s:.0f}s) — exiting for restart"
        )


class ShedThrottle:
    """Adaptive publish throttle: honor broker admission control
    (BrokerShedError — transport/tcp.py watermarks) and survive transient
    broker failures with jittered exponential backoff instead of either
    crashing the actor or hammering an overloaded broker in lockstep
    with 255 siblings.

    Policy on refusal/failure: the CHUNK IS DROPPED, not queued for
    retry — by the time an overloaded broker would accept it the chunk
    is staler (and the learner's staleness filter or the drop-oldest
    eviction would eat it anyway); what matters is that the PRODUCER
    slows down, which the backoff does. Backoff resets on the first
    accepted publish. One instance per publishing agent; counters feed
    the broker_shed_* scalars (obs/registry.py).

    Backoff state is PER ENDPOINT (the broker-fabric surgery): against
    a routing broker (one exposing `route_endpoint`, transport/fabric),
    a shed/failure arms a not-before stamp for THAT shard only, paid
    just before the next publish that routes there — so one shedding
    shard never pauses publishes to healthy shards (regression-pinned
    in tests/test_fabric.py with two in-process brokers). Against a
    classic single broker there is no routing key: the one shared
    ladder pays its backoff immediately, byte-for-byte the pre-fabric
    behavior.
    """

    def __init__(self, retry: Optional[RetryPolicy] = None):
        self.retry = retry if retry is not None else RetryPolicy()
        # endpoint key (None = the classic unrouted broker) → ladder
        # position / earliest next publish to that endpoint.
        self._backoff: dict = {}
        self._not_before: dict = {}
        self.published = 0
        self.shed = 0
        self.failed = 0
        self.throttle_s = 0.0

    def _endpoint_key(self, broker: Broker, data: bytes):
        route = getattr(broker, "route_endpoint", None)
        if route is None:
            return None
        try:
            return route(data)
        except Exception:  # routing must never break publishing
            return None

    async def publish(
        self, broker: Broker, data: bytes, priority: Optional[float] = None
    ) -> bool:
        """True = accepted; False = shed/failed (chunk dropped, backoff
        paid/armed). Raising is reserved for programming errors —
        transport failure must degrade the actor, not kill it (the
        broker outlives no one in the k8s model; an actor that dies on
        every broker hiccup turns one restart into a fleet crashloop).
        `priority` is the |TD-error| admission stamp, forwarded when the
        broker wants it (fabric priority-shed admission)."""
        key = self._endpoint_key(broker, data)
        pending = self._not_before.get(key, 0.0) - time.monotonic()
        if pending > 0:
            # this endpoint's armed backoff comes due now — healthy
            # endpoints' publishes never enter this branch
            self.throttle_s += pending
            await asyncio.sleep(pending)
        try:
            if priority is not None and getattr(broker, "wants_priority", False):
                broker.publish_experience_prioritized(data, priority)
            else:
                broker.publish_experience(data)
        except BrokerShedError as e:
            self.shed += 1
            await self._pay_backoff(getattr(e, "endpoint", key))
            return False
        except (ConnectionError, OSError) as e:
            self.failed += 1
            _log.warning("publish failed (%s: %s); dropping chunk and backing off", type(e).__name__, e)
            await self._pay_backoff(key)
            return False
        self.published += 1
        self._backoff.pop(key, None)
        self._not_before.pop(key, None)
        return True

    async def _pay_backoff(self, key) -> None:
        backoff = self._backoff.get(key, self.retry.backoff_base_s)
        delay = self.retry.sleep_for(backoff)
        self._backoff[key] = self.retry.next_backoff(backoff)
        if key is None:
            # classic broker: the pre-fabric immediate await
            self.throttle_s += delay
            await asyncio.sleep(delay)
        else:
            # routed broker: arm the endpoint's not-before; the next
            # publish routed THERE pays it, siblings stay at full rate
            self._not_before[key] = time.monotonic() + delay

    def stats(self) -> dict:
        return {
            "broker_shed_observed_total": float(self.shed),
            "broker_shed_publish_failed_total": float(self.failed),
            "broker_shed_throttle_s": self.throttle_s,
        }


# Discount used for the publish-time |TD-error| admission priority. The
# stamp is a RANKING heuristic consumed by the fabric shards' priority
# shed (transport/fabric.py), not a loss term — the PPOConfig default is
# close enough that actors need not carry the learner's gamma.
_PRIORITY_GAMMA = 0.98


def rollout_priority_fn(broker: Broker):
    """The publish-time priority stamp, resolved ONCE at agent boot:
    None against classic brokers (no replay import, zero per-chunk
    work); against a fabric broker (`wants_priority`), the PR-1
    |TD-error| priority computed from the chunk the agent just built —
    the producer holds the arrays, so the transport never parses a
    frame to rank it."""
    if not getattr(broker, "wants_priority", False):
        return None
    from dotaclient_tpu.replay import td_error_priority

    def fn(rollout: Rollout) -> float:
        return float(
            td_error_priority(
                rollout.rewards, rollout.behavior_value, rollout.dones, _PRIORITY_GAMMA
            )
        )

    return fn


def connect_env_async(cfg: ActorConfig) -> AsyncDotaServiceStub:
    """Dialect-aware env stub factory shared by Actor and SelfPlayActor:
    'valve' speaks a real dotaservice's wire schema through the adapter,
    anything else the internal protos."""
    if getattr(cfg, "env_dialect", "internal") == "valve":
        from dotaclient_tpu.env.valve_adapter import connect_valve_async

        return connect_valve_async(cfg.env_addr)
    return connect_async(cfg.env_addr)


async def reset_env_stub(actor) -> None:
    """Tear down the env channel after an RPC failure so the next episode
    reconnects from scratch (shared by Actor and SelfPlayActor; both keep
    the lazily-created stub in `_stub`).

    Required for convergent recovery: a kept channel reuses its dead
    subchannel, whose internal gRPC reconnect backoff grows to ~2 min —
    far past our own retry cadence — so a revived env server would sit
    unused while the actor's "retries" all fail against the stale
    subchannel."""
    stub = actor._stub
    actor._stub = None
    if stub is not None:
        try:
            await stub.channel.close()
        except Exception:  # a half-dead aio channel may throw on close
            pass


def _check_actor_policy(cfg: ActorConfig) -> None:
    """Shared validation for both actor-step builders."""
    if cfg.policy.arch == "transformer" and cfg.policy.tf_context < cfg.rollout_len:
        # The cache is reset every chunk (next_chunk), so a capacity >=
        # rollout_len means it never wraps mid-chunk. A wrap would slide
        # the acting context window while the learner re-evaluates with
        # full chunk context — silently wrong PPO ratios, so refuse.
        raise ValueError(
            f"tf_context={cfg.policy.tf_context} < rollout_len={cfg.rollout_len}: "
            f"the KV cache would wrap mid-chunk and acting context would no "
            f"longer match the learner's chunk-local re-eval"
        )


def _actor_step_row(net):
    """The per-tick inference body shared by the B=1 step and the
    vectorized fleet's batched step: rng split + policy apply + masked
    sample + joint log-prob, all inside the compiled program."""

    def row(params, state, obs, rng):
        rng, key = jax.random.split(rng)
        new_state, out = net.apply(params, state, obs)
        action = ad.sample(key, out.dist)
        logp = ad.log_prob(out.dist, action)
        return new_state, action, logp, out.value, rng

    return row


def make_actor_step(cfg: ActorConfig):
    """jit'd single-step inference: sampling stays on device.

    The rng split happens INSIDE the compiled program and the advanced
    rng is returned as a carry — a host-side jax.random.split per tick
    is a second compiled dispatch that costs ~35% of the whole actor
    step at B=1 (measured r3: 925 → 1,424 steps/s fused, 1 CPU core).
    """
    _check_actor_policy(cfg)
    step = jax.jit(_actor_step_row(P.PolicyNet(cfg.policy)))
    return step


def make_batched_actor_step(cfg: ActorConfig):
    """jit'd M-row inference tick for the vectorized fleet: stacked
    per-env (state, obs, rng) rows in, per-row (state', action, logp,
    value, rng') out, ONE dispatch for the whole fleet.

    Rows keep the single-path's exact [1, ...] inner shapes and run
    through `lax.map` — sequentially INSIDE one compiled program — so
    every row is bit-identical to make_actor_step's B=1 call on the same
    inputs regardless of which other envs share the tick (the
    occupancy-invariance partial batches rely on). vmap was measured
    ~25% faster at M=8 but shifts f32 matmul accumulation by last-ULP
    per batch size on CPU, breaking that contract; the dominant win —
    amortizing the batch-1 dispatch overhead M× — survives lax.map
    (539 → 3,512 steps/s at flagship shapes, M=8, 1 CPU core).
    """
    _check_actor_policy(cfg)
    row = _actor_step_row(P.PolicyNet(cfg.policy))

    @jax.jit
    def step(params, state, obs, rngs):
        return jax.lax.map(lambda sor: row(params, *sor), (state, obs, rngs))

    return step


def build_action(
    cfg: ActorConfig,
    action: ad.Action,
    handles: np.ndarray,
    hero: Optional[ws.Unit],
    player_id: int,
    batch_index: int = 0,
) -> ds.Action:
    """Map one batch row of sampled head indices to an Action proto."""
    a = ds.Action(player_id=player_id)
    i = batch_index
    atype = int(action.type[i])
    if atype == F.ACT_MOVE and hero is not None:
        n = cfg.policy.n_move_bins
        grid = (np.arange(n) - n // 2) / max(n // 2, 1)
        a.type = ds.Action.MOVE
        a.move_x = hero.x + float(grid[int(action.move_x[i])]) * cfg.policy.move_step
        a.move_y = hero.y + float(grid[int(action.move_y[i])]) * cfg.policy.move_step
    elif atype == F.ACT_ATTACK:
        a.type = ds.Action.ATTACK
        a.target_handle = int(handles[int(action.target[i])])
    elif atype == F.ACT_CAST:
        a.type = ds.Action.CAST
        a.ability_slot = 0
        a.target_handle = int(handles[int(action.target[i])])
    else:
        a.type = ds.Action.NOOP
    return a


def build_actions_proto(
    cfg: ActorConfig,
    action: ad.Action,
    handles: np.ndarray,
    hero: Optional[ws.Unit],
    team_id: int,
    player_id: int,
    dota_time: float,
) -> ds.Actions:
    """Map sampled head indices back to a concrete Actions proto."""
    a = build_action(cfg, action, handles, hero, player_id)
    return ds.Actions(actions=[a], team_id=team_id, dota_time=dota_time)


def next_chunk(policy_cfg, state):
    """Chunk-boundary transition shared by Actor and SelfPlayActor:
    returns (state', fresh chunk). The LSTM carries state across chunks
    (shipped on the wire as the learner's initial carry); the
    transformer family resets its KV cache here so acting context is
    chunk-local, exactly like the learner's re-eval
    (models.policy.reset_between_chunks)."""
    state = P.reset_between_chunks(policy_cfg, state)
    return state, _Chunk(P.wire_state(policy_cfg, state))


class _Chunk:
    """Accumulates one rollout chunk between broker publishes. Takes the
    wire-format (c, h) [1, H] pair (models.policy.wire_state)."""

    def __init__(self, initial_state: Tuple[np.ndarray, np.ndarray]):
        self.initial_state = (np.asarray(initial_state[0][0]), np.asarray(initial_state[1][0]))
        self.obs: List[F.Observation] = []
        self.actions: List[ad.Action] = []
        self.logp: List[float] = []
        self.value: List[float] = []
        self.rewards: List[float] = []
        self.dones: List[float] = []
        self.aux_lh: List[float] = []
        self.aux_nw: List[float] = []

    def __len__(self) -> int:
        return len(self.actions)

    def to_rollout(
        self,
        bootstrap_obs: F.Observation,
        version: int,
        actor_id: int,
        episode_return: float,
        win: float,
        with_aux: bool,
    ) -> Rollout:
        L = len(self)
        obs = F.stack(self.obs + [bootstrap_obs])
        acts = ad.Action(
            type=np.asarray([int(a.type[0]) for a in self.actions], np.int32),
            move_x=np.asarray([int(a.move_x[0]) for a in self.actions], np.int32),
            move_y=np.asarray([int(a.move_y[0]) for a in self.actions], np.int32),
            target=np.asarray([int(a.target[0]) for a in self.actions], np.int32),
        )
        aux = None
        if with_aux:
            aux = RolloutAux(
                win=np.full(L, win, np.float32),
                last_hit=np.asarray(self.aux_lh, np.float32),
                net_worth=np.asarray(self.aux_nw, np.float32),
            )
        return Rollout(
            obs=obs,
            actions=acts,
            behavior_logp=np.asarray(self.logp, np.float32),
            behavior_value=np.asarray(self.value, np.float32),
            rewards=np.asarray(self.rewards, np.float32),
            dones=np.asarray(self.dones, np.float32),
            initial_state=self.initial_state,
            version=version,
            actor_id=actor_id,
            episode_return=episode_return,
            aux=aux,
        )


class Actor:
    """One self-play actor process (player_id 0 on team radiant)."""

    # Episode failures the run loop retries with backoff instead of
    # dying: env RPC outages for the local paths; the serve tier's
    # RemoteActor extends this with its RemoteInferenceError (a lost
    # server carry abandons the episode exactly like a lost env
    # session). Class attr so subclasses extend without forking run().
    _RETRYABLE_EPISODE_ERRORS: tuple = (grpc.aio.AioRpcError,)

    def __init__(
        self,
        cfg: ActorConfig,
        broker: Broker,
        actor_id: int = 0,
        stub: Optional[AsyncDotaServiceStub] = None,
        params=None,
    ):
        self.cfg = cfg
        self.broker = broker
        self.actor_id = actor_id
        # grpc.aio channels bind to the running event loop — create lazily
        # inside run_episode, not here (__init__ runs outside the loop).
        self._stub = stub
        # `params` lets an owning VectorActor share one param tree across
        # its env workers instead of re-tracing init_params per env.
        self.params = (
            params if params is not None else P.init_params(cfg.policy, jax.random.PRNGKey(cfg.seed))
        )
        self.version = 0
        self.step_fn = make_actor_step(cfg)
        self.rng = jax.random.PRNGKey(cfg.seed * 9973 + actor_id)
        # all host-side randomness (per-episode env seeds) flows from here,
        # so identical --seed/--actor_id replays identical episode sequences
        self.np_rng = np.random.RandomState(cfg.seed * 1000003 + actor_id)
        self.player_id = 0
        self.team_id = 2
        self.steps_done = 0
        self.episodes_done = 0
        self.rollouts_published = 0
        # Publish degradation: honors broker SHED + transient failures
        # with jittered backoff (config.py RetryConfig is the policy).
        retry_cfg = getattr(cfg, "retry", None)
        self.publish_throttle = ShedThrottle(
            RetryPolicy.from_config(retry_cfg) if retry_cfg is not None else None
        )
        # Quantized experience wire (--wire.obs_dtype): resolved ONCE at
        # boot so a bad value fails the actor loudly at startup, not per
        # chunk. "f32" (default) is the identity — byte-identical legacy
        # frames, no ml_dtypes import on the publish path.
        wire_cfg = getattr(cfg, "wire", None)
        self._wire_cast = wire_cast_fn(wire_cfg.obs_dtype if wire_cfg is not None else "f32")
        # Fabric priority stamp (None against classic brokers).
        self._priority_fn = rollout_priority_fn(broker)
        self.obs = self._make_obs_runtime()
        # ±1 result of the last finished episode, 0.0 for a decided draw
        # (episode ended with no winning team), None while in flight or
        # after an abandoned episode — read by the evaluator and the
        # self-play league.
        self.last_win: Optional[float] = None
        # kill-switch clock: boot counts as "fresh" so a learner that is
        # still compiling doesn't kill its actors
        self.last_weight_time = time.monotonic()

    @property
    def rollouts_shed(self) -> int:
        """Chunks refused by broker admission control (dropped + backoff
        paid) — the producer side of the conservation ledger."""
        return self.publish_throttle.shed

    @property
    def rollouts_failed(self) -> int:
        """Chunks dropped on transport failure (broker down past the
        retry window, injected resets)."""
        return self.publish_throttle.failed

    def _make_obs_runtime(self):
        """Observability (--obs.*, dotaclient_tpu/obs/): when enabled the
        actor trace-stamps each published chunk (DTR2 wire extension)
        and keeps a flight-recorder ring; None = byte-identical legacy
        DTR1 frames and zero extra work. The vector fleet's env workers
        override this to share their owner's single runtime (one ring,
        one set of process handlers — not M)."""
        from dotaclient_tpu.obs import ObsRuntime

        return ObsRuntime.create(self.cfg.obs, role=f"actor{self.actor_id}")

    # ------------------------------------------------------------- weights

    def maybe_update_weights(self) -> bool:
        frame = self.broker.poll_weights()
        if frame is None:
            return False
        return apply_weight_frame(self, frame, f"actor {self.actor_id}")

    def check_weight_freshness(self) -> None:
        """Kill switch: raise if broadcasts stopped (cfg.max_weight_age_s
        > 0 enables it)."""
        check_weight_freshness(self)

    # ------------------------------------------------------------- episode

    @property
    def stub(self) -> AsyncDotaServiceStub:
        if self._stub is None:
            self._stub = connect_env_async(self.cfg)
        return self._stub

    def _featurize(self, world):
        """The ONE featurization choke point for this actor: worldstate →
        (Observation, handles), with per-actor observation policy (the
        disable_cast ablation mask) applied here so every consumer of an
        observation — step, chunk, bootstrap frame — sees the same view."""
        obs, handles = F.featurize_with_handles(world, self.player_id)
        if self.cfg.disable_cast:
            obs.action_mask[F.ACT_CAST] = False
        return obs, handles

    async def _policy_step(
        self, state, obs: F.Observation, chunk_len: int = 0, episode_start: bool = False
    ):
        """ONE policy inference for the current (unbatched) obs →
        (state', action, logp, value), each with the [1, ...] batch axis
        the chunk format stores. The base actor dispatches its own B=1
        jit call and advances its own rng carry; the vector fleet's env
        workers override this to await the shared InferenceBatcher, and
        the serve tier's RemoteActor routes it over the wire —
        run_episode is otherwise identical in all modes.

        `chunk_len`/`episode_start` describe the loop position (steps
        already in the current chunk; first step of the episode). The
        local paths ignore them; the remote path needs them to drive the
        server-resident carry protocol (reset on episode start, carry
        return at chunk-fill steps) without forking run_episode."""
        obs_b = jax.tree.map(lambda x: jnp.asarray(x)[None], obs)
        state, action, logp, value, self.rng = self.step_fn(self.params, state, obs_b, self.rng)
        return state, action, logp, value

    async def run_episode(self) -> float:
        cfg = self.cfg
        self.last_win = None
        # cfg.hero is one name or a comma-separated pool (config 3: shared
        # LSTM across a hero pool) — both sides draw independently
        pool = heroes.parse_pool(cfg.hero)
        config = ds.GameConfig(
            host_timescale=cfg.host_timescale,
            ticks_per_observation=cfg.ticks_per_observation,
            max_dota_time=cfg.max_dota_time,
            seed=self.np_rng.randint(1 << 30),
            hero_picks=[
                ds.HeroPick(team_id=2, hero_name=pool[self.np_rng.randint(len(pool))], control_mode=1),
                ds.HeroPick(
                    team_id=3,
                    hero_name=pool[self.np_rng.randint(len(pool))],
                    # 0 = passive scripted, 2 = hard scripted (farms/retreats)
                    control_mode={"scripted": 0, "scripted_hard": 2}.get(cfg.opponent, 1),
                ),
            ],
        )
        resp = await self.stub.reset(config)
        world = resp.world_state
        state, chunk = next_chunk(cfg.policy, P.initial_state(cfg.policy, (1,)))
        last_hero: Optional[ws.Unit] = None
        episode_return = 0.0
        done = False
        # each worldstate is featurized exactly once; the pair rolls forward
        obs, handles = self._featurize(world)

        episode_start = True
        while not done:
            state, action, logp, value = await self._policy_step(
                state, obs, chunk_len=len(chunk), episode_start=episode_start
            )
            episode_start = False

            hero = F.find_hero(world, self.player_id)
            if hero is not None:
                snap = ws.Unit()
                snap.CopyFrom(hero)
                last_hero = snap
            await self.stub.act(
                build_actions_proto(cfg, jax.device_get(action), handles, hero, self.team_id, self.player_id, world.dota_time)
            )
            resp = await self.stub.observe(ds.ObserveRequest(team_id=self.team_id))
            if resp.status == ds.Observation.RESOURCE_EXHAUSTED:
                # session lost (server restart/eviction): abandon the episode
                # and the partial chunk instead of publishing garbage steps
                _log.warning("actor %d: env session lost; abandoning episode", self.actor_id)
                self.episodes_done += 1
                return episode_return
            next_world = resp.world_state
            next_obs, next_handles = self._featurize(next_world)
            done = resp.status == ds.Observation.EPISODE_DONE
            r = R.reward(world, next_world, self.player_id, last_hero)
            episode_return += r

            chunk.obs.append(obs)
            chunk.actions.append(jax.device_get(action))
            chunk.logp.append(float(logp[0]))
            chunk.value.append(float(value[0]))
            chunk.rewards.append(r)
            chunk.dones.append(1.0 if done else 0.0)
            if cfg.policy.aux_heads:
                chunk.aux_lh.append(F.norm_last_hits(hero.last_hits) if hero else 0.0)
                chunk.aux_nw.append(F.norm_gold(hero.gold) if hero else 0.0)
            self.steps_done += 1

            if len(chunk) >= cfg.rollout_len or done:
                win = 0.0
                if done and next_world.winning_team:
                    win = 1.0 if next_world.winning_team == self.team_id else -1.0
                if done:
                    self.last_win = win
                rollout = chunk.to_rollout(
                    next_obs,
                    self.version,
                    self.actor_id,
                    episode_return if done else 0.0,
                    win,
                    cfg.policy.aux_heads,
                )
                if self.obs is not None:
                    rollout = self.obs.stamp(rollout, self.actor_id)
                # Cast-at-source wire quantization (identity under the
                # default f32), then shed/failed publishes drop the chunk
                # and pay a jittered backoff (ShedThrottle docstring);
                # the episode continues. Against a fabric broker the
                # publish carries the |TD-error| admission priority.
                if await self.publish_throttle.publish(
                    self.broker,
                    serialize_rollout(self._wire_cast(rollout)),
                    priority=(
                        self._priority_fn(rollout)
                        if self._priority_fn is not None
                        else None
                    ),
                ):
                    self.rollouts_published += 1
                state, chunk = next_chunk(cfg.policy, state)
                self.maybe_update_weights()

            world = next_world
            obs, handles = next_obs, next_handles

        self.episodes_done += 1
        return episode_return

    async def run(self, num_episodes: Optional[int] = None) -> None:
        """Episode loop with env-outage resilience: a gRPC failure (env
        server restarting, pod eviction) abandons the episode and retries
        with capped backoff instead of killing the actor — the k8s model
        is that actors outlive individual env instances."""
        backoff = 1.0
        while num_episodes is None or self.episodes_done < num_episodes:
            self.check_weight_freshness()
            try:
                ret = await self.run_episode()
                backoff = 1.0
            except self._RETRYABLE_EPISODE_ERRORS as e:
                _log.warning(
                    "actor %d: episode failed (%s: %s); retrying in %.1fs",
                    self.actor_id,
                    type(e).__name__,
                    e.code() if isinstance(e, grpc.aio.AioRpcError) else e,
                    backoff,
                )
                await reset_env_stub(self)  # drop the dead subchannel
                self.maybe_update_weights()  # stay fresh while waiting
                await asyncio.sleep(backoff)
                backoff = min(backoff * 2.0, 30.0)
                continue
            _log.info(
                "actor %d: episode %d return %.2f (version %d, %d steps)",
                self.actor_id,
                self.episodes_done,
                ret,
                self.version,
                self.steps_done,
            )


class InferenceBatcher:
    """Per-process batched inference server for the vector fleet.

    Env coroutines submit one (state, obs, rng) step request each via
    `step()`; the `run()` driver coroutine gathers requests into a tick:
    it fires as soon as `capacity` requests are pending, and no later
    than `window_s` after the tick's FIRST request — a slow gRPC
    observe() stalls only its own env, never the batch. Partial ticks
    are padded to capacity (ONE jit signature, zero recompiles) with the
    pad rows masked out of the scatter; occupancy, gather wait, and jit
    latency are metered into the `actor_*` scalars (obs/registry.py).

    Everything here runs on one asyncio loop (requests, gather, the jit
    call itself), so there is no locking; `stats()` may be read from
    another thread and takes single-read snapshots of the counters.
    """

    # Queue sentinel: stop() pushes it so a driver blocked on get() wakes
    # even when its Task.cancel is swallowed by the Python 3.10 wait_for
    # race (inner future completing concurrently with the cancel leaves
    # the task "un-cancelled" — observed as a teardown deadlock here).
    _SENTINEL = object()

    def __init__(self, cfg: ActorConfig, params_fn, capacity: int, window_s: Optional[float] = None):
        if capacity < 1:
            raise ValueError(f"InferenceBatcher capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.window_s = cfg.gather_window_s if window_s is None else window_s
        self._params_fn = params_fn
        self._step = make_batched_actor_step(cfg)
        self._queue: "asyncio.Queue" = asyncio.Queue()
        self._stopped = False
        # Fixed pad row: zero obs/state and a constant rng whose advanced
        # value is never written back anywhere — pad rows burn compute
        # (lax.map walks them too) but cannot perturb any real row.
        self._pad_state = jax.tree.map(np.asarray, P.initial_state(cfg.policy, (1,)))
        self._pad_obs = F.zeros_observation()
        self._pad_rng = np.asarray(jax.random.PRNGKey(0))
        # Meters (driver-coroutine-written; stats() snapshots).
        self._ticks = 0
        self._rows = 0
        # Rows-per-fired-tick occupancy HISTOGRAM (index k = ticks that
        # carried exactly k real rows; k=0 never fires — a tick starts
        # from its first request). The mean alone hid the distribution:
        # a 0.5 mean could be "every tick half full" (window too short)
        # or "alternating full/single" (bursty arrivals) — different
        # tuning moves. The serve tier exports the same family, so the
        # serve bench and the PR-5 fleet report comparable shapes.
        self._tick_rows = [0] * (capacity + 1)
        self._gather_wait_s = 0.0
        self._jit_s = 0.0
        self._first_tick_t: Optional[float] = None
        self._last_tick_t: Optional[float] = None

    async def step(self, state, obs: F.Observation, rng):
        """Submit one env's tick → (state', action, logp, value, rng'),
        shaped exactly like make_actor_step's return for that env alone
        (bit-identical to it, by the lax.map row contract)."""
        if self._stopped:
            # after stop() nothing will ever serve the queue — failing
            # loudly beats an await that can never resolve
            raise RuntimeError("InferenceBatcher is stopped")
        fut = asyncio.get_running_loop().create_future()
        self._queue.put_nowait((state, obs, rng, fut))
        return await fut

    def stop(self) -> None:
        """Flag the driver down and wake it if it's blocked on the queue.
        Cancellation alone is NOT sufficient: Python 3.10's wait_for can
        swallow a Task.cancel that races an arriving request, leaving the
        driver live forever and deadlocking the caller's teardown join."""
        self._stopped = True
        self._queue.put_nowait(self._SENTINEL)

    async def run(self) -> None:
        """Driver loop: gather → pad → ONE jit call → scatter. Stop via
        stop() (or task cancellation); in-flight futures are failed so no
        env worker can await a result that will never come."""
        reqs: list = []
        try:
            while not self._stopped:
                first = await self._queue.get()
                if first is self._SENTINEL:
                    break
                reqs = [first]
                t0 = time.monotonic()
                deadline = t0 + self.window_s
                while len(reqs) < self.capacity:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    try:
                        item = await asyncio.wait_for(self._queue.get(), remaining)
                    except asyncio.TimeoutError:
                        break
                    if item is self._SENTINEL:
                        self._stopped = True
                        break
                    reqs.append(item)
                if self._stopped:
                    break
                t1 = time.monotonic()
                self._run_tick(reqs, gather_wait=t1 - t0)
                reqs = []
        finally:
            exc = RuntimeError("InferenceBatcher driver stopped")
            for _, _, _, fut in reqs:
                if not fut.done():
                    fut.set_exception(exc)
            self._fail_pending(exc)

    def _tick_bundle(self):
        """One ATOMIC read of everything a tick steps with. The base
        batcher only needs the param tree; the serve tier's subclass
        returns (params, version, tick_id) so every row of a tick is
        provably served by one tree — the no-mixed-batch-tick hot-swap
        invariant rides on this being a single read per tick."""
        return (self._params_fn(),)

    def _row_result(self, out, i: int, bundle):
        """Per-row future payload: the base contract is the bare row
        tree (state', action, logp, value, rng'); the serve subclass
        attaches the tick's (version, tick_id) from the bundle."""
        return jax.tree.map(lambda x: x[i], out)

    def _run_tick(self, reqs, gather_wait: float) -> None:
        K = len(reqs)
        M = self.capacity
        pad = M - K
        states = [r[0] for r in reqs] + [self._pad_state] * pad
        rngs = [r[2] for r in reqs] + [self._pad_rng] * pad
        obs_rows = [r[1] for r in reqs] + [self._pad_obs] * pad
        # Stack M unbatched rows leaf-wise, then restore the [1, ...]
        # inner batch axis the single-env path uses — row i of the
        # compiled program sees byte-identical shapes to a B=1 call.
        obs_b = jax.tree.map(lambda *xs: np.stack(xs)[:, None], *obs_rows)
        state_b = jax.tree.map(lambda *xs: np.stack(xs), *states)
        rng_b = np.stack([np.asarray(r) for r in rngs])
        bundle = self._tick_bundle()
        t1 = time.monotonic()
        out = self._step(bundle[0], state_b, obs_b, rng_b)
        # ONE transfer for the whole tick; per-env slices are then cheap
        # numpy views (the env loop re-device_gets them as no-ops).
        out = jax.device_get(out)
        t2 = time.monotonic()
        for i, (_, _, _, fut) in enumerate(reqs):
            if not fut.cancelled():
                fut.set_result(self._row_result(out, i, bundle))
        self._ticks += 1
        self._rows += K
        self._tick_rows[K] += 1
        self._gather_wait_s += gather_wait
        self._jit_s += t2 - t1
        if self._first_tick_t is None:
            self._first_tick_t = t1
        self._last_tick_t = t2

    def reset_meters(self) -> None:
        """Zero the meters (bench use: exclude the compile/warmup ticks
        from the measured window). Driver-loop-thread only."""
        self._ticks = 0
        self._rows = 0
        self._tick_rows = [0] * (self.capacity + 1)
        self._gather_wait_s = 0.0
        self._jit_s = 0.0
        self._first_tick_t = None
        self._last_tick_t = None

    def _fail_pending(self, exc: BaseException) -> None:
        while not self._queue.empty():
            item = self._queue.get_nowait()
            if item is self._SENTINEL:
                continue
            fut = item[3]
            if not fut.done():
                fut.set_exception(exc)

    def stats(self) -> dict:
        """The actor_* scalar family (obs/registry.py): offered rate,
        mean occupancy, mean gather wait, mean jit tick latency. Single
        reads of driver-written counters — a gauge that drifts by one
        in-flight tick is fine, a lock on the tick path is not."""
        ticks, rows = self._ticks, self._rows
        first, last = self._first_tick_t, self._last_tick_t
        elapsed = (last - first) if (first is not None and last is not None and last > first) else 0.0
        out = {
            "actor_offered_steps_per_sec": rows / elapsed if elapsed > 0 else 0.0,
            "actor_batch_occupancy": rows / float(max(ticks, 1) * self.capacity),
            "actor_gather_wait_s": self._gather_wait_s / max(ticks, 1),
            "actor_jit_step_s": self._jit_s / max(ticks, 1),
        }
        # Occupancy histogram (actor_tick_rows_<k> family, registry
        # PREFIXES): count of fired ticks that carried exactly k real
        # rows, k in 1..capacity. list(...) = one GIL-atomic snapshot of
        # the driver-written counters.
        for k, n in enumerate(list(self._tick_rows)):
            if k == 0:
                continue  # a tick fires from its first request; k=0 can't occur
            out[f"actor_tick_rows_{k}"] = float(n)
        return out


class _BatchedEnvActor(Actor):
    """One env slot of a VectorActor: the classic Actor episode loop with
    its per-tick inference routed through the owner's InferenceBatcher
    and its weight/freshness state delegated to the owner (ONE broker
    poll and ONE param tree per process, not M)."""

    def __init__(self, owner: "VectorActor", actor_id: int):
        self.owner = owner  # before super().__init__: _make_obs_runtime reads it
        super().__init__(owner.cfg, owner.broker, actor_id=actor_id, params=owner.params)

    def _make_obs_runtime(self):
        return self.owner.obs

    async def _policy_step(
        self, state, obs: F.Observation, chunk_len: int = 0, episode_start: bool = False
    ):
        state, action, logp, value, self.rng = await self.owner.batcher.step(state, obs, self.rng)
        return state, action, logp, value

    def maybe_update_weights(self) -> bool:
        """One poll for the whole fleet — but each env syncs its OWN
        stamped version here, i.e. only at its own chunk boundaries
        (run_episode calls this right after each publish). The shared
        params swap immediately for every env's next tick, so an env
        mid-chunk samples its tail under the new policy while still
        stamping the version its chunk STARTED under — staleness is
        over-estimated for those rows, never under-aged (the stamp feeds
        max_staleness drops and the ACER truncated importance weights)."""
        updated = self.owner.maybe_update_weights()
        self.version = self.owner.version
        return updated

    def check_weight_freshness(self) -> None:
        check_weight_freshness(self.owner)


class VectorActor:
    """M env sessions, one process, one batched jit inference per tick.

    Construction mirrors Actor (cfg, broker, actor_id); `envs` defaults
    to cfg.envs_per_process. Env slot j runs with actor_id
    `actor_id * M + j`, so its rng / env-seed streams (and therefore its
    episodes and published frames) are exactly those of a standalone
    Actor with that id — the property the fleet bit-equivalence test
    pins. Drive it with `run()` (actor binary) or `episode_stream()`
    (ActorPool envs-per-actor mode).
    """

    def __init__(
        self,
        cfg: ActorConfig,
        broker: Broker,
        actor_id: int = 0,
        envs: Optional[int] = None,
        params=None,
        obs_runtime=None,
    ):
        M = int(envs if envs is not None else getattr(cfg, "envs_per_process", 1))
        if M < 1:
            raise ValueError(f"envs_per_process must be >= 1, got {M}")
        self.cfg = cfg
        self.broker = broker
        self.actor_id = actor_id
        self.params = (
            params if params is not None else P.init_params(cfg.policy, jax.random.PRNGKey(cfg.seed))
        )
        self.version = 0
        self.last_weight_time = time.monotonic()
        self.last_win: Optional[float] = None
        if obs_runtime is not None:
            self.obs = obs_runtime
        else:
            from dotaclient_tpu.obs import ObsRuntime

            self.obs = ObsRuntime.create(cfg.obs, role=f"vector{actor_id}")
        self.batcher = InferenceBatcher(cfg, lambda: self.params, capacity=M)
        self.envs = [_BatchedEnvActor(self, actor_id * M + j) for j in range(M)]

    @classmethod
    def from_actor(cls, actor: Actor, envs: Optional[int] = None) -> "VectorActor":
        """Wrap a constructed classic Actor (ActorPool's envs-per-actor
        mode): same cfg/broker/actor_id/params, M env slots. The actor's
        ObsRuntime rides along too — it already installed the
        process-wide crash handlers when obs is enabled, and creating a
        second runtime would chain a duplicate recorder into them."""
        return cls(
            actor.cfg,
            actor.broker,
            actor_id=actor.actor_id,
            envs=envs,
            params=actor.params,
            obs_runtime=actor.obs,
        )

    # aggregate counters, so drivers' on_episode callbacks keep working
    @property
    def steps_done(self) -> int:
        return sum(e.steps_done for e in self.envs)

    @property
    def episodes_done(self) -> int:
        return sum(e.episodes_done for e in self.envs)

    @property
    def rollouts_published(self) -> int:
        return sum(e.rollouts_published for e in self.envs)

    @property
    def rollouts_shed(self) -> int:
        return sum(e.publish_throttle.shed for e in self.envs)

    @property
    def rollouts_failed(self) -> int:
        return sum(e.publish_throttle.failed for e in self.envs)

    def stats(self) -> dict:
        out = self.batcher.stats()
        # Fleet-wide publish-degradation meters (broker_shed_* family):
        # each env slot throttles itself, the gauges sum the fleet.
        shed = failed = published = 0
        throttle_s = 0.0
        for e in self.envs:
            t = e.publish_throttle
            shed += t.shed
            failed += t.failed
            published += e.rollouts_published
            throttle_s += t.throttle_s
        out["broker_shed_observed_total"] = float(shed)
        out["broker_shed_publish_failed_total"] = float(failed)
        out["broker_shed_throttle_s"] = throttle_s
        # Producer conservation ledger (obs/fleet.py "producer"):
        # attempted = published + shed + failed, derived from the SAME
        # per-slot reads so the identity holds exactly per scrape — the
        # fleet auditor's zero-unaccounted baseline for this tier.
        out["actor_rollouts_published_total"] = float(published)
        out["actor_publish_attempted_total"] = float(published + shed + failed)
        return out

    def maybe_update_weights(self) -> bool:
        """Apply a pending weight frame to the SHARED param tree (the
        batcher serves it to every env's next tick). Env slots pick the
        new version stamp up individually at their own chunk boundaries
        (_BatchedEnvActor.maybe_update_weights) — pushing it here would
        mis-stamp chunks whose early steps were sampled under the old
        params."""
        frame = self.broker.poll_weights()
        if frame is None:
            return False
        return apply_weight_frame(self, frame, f"vector actor {self.actor_id}")

    def check_weight_freshness(self) -> None:
        check_weight_freshness(self)

    async def _env_loop(self, env: _BatchedEnvActor, results: "asyncio.Queue") -> None:
        """Per-env worker: the same episode/retry/backoff shape as
        Actor.run, reporting completed episodes (or a fatal error) to
        the stream queue instead of logging-and-looping."""
        backoff = 1.0
        while True:
            try:
                self.check_weight_freshness()
                ret = await env.run_episode()
                backoff = 1.0
            except env._RETRYABLE_EPISODE_ERRORS as e:
                _log.warning(
                    "vector env %d: episode failed (%s: %s); retrying in %.1fs",
                    env.actor_id,
                    type(e).__name__,
                    e.code() if isinstance(e, grpc.aio.AioRpcError) else e,
                    backoff,
                )
                await reset_env_stub(env)  # drop the dead subchannel
                self.maybe_update_weights()  # stay fresh while waiting
                await asyncio.sleep(backoff)
                backoff = min(backoff * 2.0, 30.0)
                continue
            except asyncio.CancelledError:
                raise
            except BaseException as e:  # incl. StaleWeightsError: surface it
                await results.put((env, e))
                return
            await results.put((env, float(ret)))

    async def episode_stream(self):
        """Async generator yielding each completed episode's return (any
        env). Starts the batcher driver + M env workers on the current
        loop; closing the generator tears them all down."""
        results: "asyncio.Queue" = asyncio.Queue()
        driver = asyncio.create_task(self.batcher.run())
        workers = [asyncio.create_task(self._env_loop(e, results)) for e in self.envs]
        try:
            while True:
                env, ret = await results.get()
                if isinstance(ret, BaseException):
                    raise ret
                self.last_win = env.last_win
                yield ret
        finally:
            # stop() BEFORE cancel: a cancel swallowed by the 3.10
            # wait_for race would otherwise leave the driver looping and
            # this gather waiting on it forever.
            self.batcher.stop()
            for t in workers:
                t.cancel()
            driver.cancel()
            await asyncio.gather(*workers, driver, return_exceptions=True)

    async def run(self, num_episodes: Optional[int] = None) -> None:
        """Run the fleet; `num_episodes` bounds TOTAL completed episodes
        across all envs (None = forever). With --obs.enabled and a
        metrics_port, the actor_* batcher gauges (offered rate,
        occupancy, gather wait, jit latency) export on /metrics."""
        if self.obs is not None:
            self.obs.serve_metrics([self.stats])
        try:
            done = 0
            async for _ in self.episode_stream():
                done += 1
                if num_episodes is not None and done >= num_episodes:
                    return
        finally:
            if self.obs is not None:
                self.obs.close()


def main(argv=None):
    from dotaclient_tpu.config import parse_config
    from dotaclient_tpu.runtime.device import init_devices, use_compile_cache
    from dotaclient_tpu.transport.base import connect as broker_connect

    logging.basicConfig(level=logging.INFO)
    cfg = parse_config(ActorConfig(), argv)
    use_compile_cache()
    init_devices(cfg.platform, "actor")
    broker = broker_connect(cfg.broker_url, retry=RetryPolicy.from_config(cfg.retry))
    if cfg.chaos.enabled:
        # Gated IMPORT, not just gated construction: with chaos off the
        # package never loads and the broker object is exactly the
        # production one (the inertness contract, tests/test_chaos.py).
        from dotaclient_tpu.chaos import wrap_broker

        broker = wrap_broker(broker, cfg.chaos)
    M = max(int(cfg.envs_per_process), 1)
    # League-through-serve mode: opponent sessions step the serve tier's
    # resident model slots (one --serve.models N server), matched by the
    # standing league service — the SelfPlayActor branch below handles it
    # (live side steps locally off the broker weight fan-out).
    remote_league = cfg.opponent == "league" and bool(cfg.serve.league)
    if cfg.serve.endpoint and not remote_league:
        # Centralized inference service mode (dotaclient_tpu/serve/):
        # featurized obs ship to the batching server, no local policy
        # step. Gated IMPORT (the chaos/ckpt precedent): with the
        # endpoint empty the serve package never loads and the actor hot
        # path is byte-identical to the local build.
        if cfg.opponent in ("self", "league"):
            raise ValueError(
                "--serve.endpoint does not serve mirror/league sessions "
                "directly: live self-play sides step the training params. "
                "League actors ARE supported through the multi-model serve "
                "tier — run the server with --serve.models N, point this "
                "actor at the league service with --serve.league "
                "<host:port> (opponents then step serve-resident slots "
                "via their matched --serve.model id); plain evaluation "
                "fleets pin one slot with --serve.model <id>"
            )
        from dotaclient_tpu.serve.client import RemoteFleet

        fleet = RemoteFleet(cfg, broker, actor_id=cfg.actor_id, envs=M)
        asyncio.run(fleet.run())
        return
    if cfg.opponent in ("self", "league"):
        from dotaclient_tpu.runtime.selfplay import SelfPlayActor

        if M > 1:
            # Self-play already batches all of a session's heroes into
            # one jit call per tick; envs_per_process here consolidates M
            # such sessions onto one loop (their env RPC waits overlap),
            # without cross-session batching — sessions step different
            # param sets (league snapshots), which can't share one call.
            actors = [SelfPlayActor(cfg, broker, actor_id=cfg.actor_id * M + j) for j in range(M)]

            async def run_all():
                await asyncio.gather(*(a.run() for a in actors))

            asyncio.run(run_all())
            return
        actor = SelfPlayActor(cfg, broker, actor_id=cfg.actor_id)
    elif M > 1:
        actor = VectorActor(cfg, broker, actor_id=cfg.actor_id)
    else:
        actor = Actor(cfg, broker, actor_id=cfg.actor_id)
    asyncio.run(actor.run())


if __name__ == "__main__":
    main()
