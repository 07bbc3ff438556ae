"""Broker-fabric soak: the closed-loop sharded-transport proof →
BROKER_FABRIC_SOAK.json.

Four phases against the real fabric (transport/fabric.py):

1. KILL + ROLLING CONSERVATION — 3 tcp shards (priority admission on)
   behind a ShardRouter of BrokerIncarnations; 4 producer fleets
   publish uniquely-stamped rollout chunks through FabricBroker routers
   while a fan-in consumer drains, and a seeded ScheduleRunner executes
   a `kill@T:D@broker` and a `rolling@T:P@broker` event (the PR-13
   at-most-one-down pattern, fanned across the shards). Invariants:
   every shard GENERATION's ledger sums exactly
   (enqueued = popped + dropped_oldest + evicted_low + resident), the
   fleet-wide pop ledger has ZERO unaccounted frames
   (Σpopped − Σreply_lost = delivered + fence_dropped + dup_dropped),
   no unique chunk is ever delivered twice, and every producer's
   longest publish gap (actor-visible recovery) stays inside the
   budget.

2. STALE-SHARD RESURRECTION — a publish fails over (epoch bump) and the
   dead primary resurrects still holding the old-epoch copy of the SAME
   chunk: the fan-in fence must drop it (fence counter > 0 proves the
   fence fired) and deliver the chunk exactly once.

3. 2-LEARNER FAN-IN + SIGTERM RESUME — two real Learners consume
   DISJOINT shard subsets of one 4-shard fabric (--broker_shards
   semantics); learner B is SIGTERM-drained mid-run (the PR-7
   request_drain → train-out → drain_save path), restarted from its
   full-state checkpoint, and must finish with params/opt-state
   BIT-EXACT against an uninterrupted arm over the identical frame
   schedule; learner A's disjoint stream is never cross-contaminated.

4. OFFERED-RATE SCALING — aggregate publish throughput through 1 shard
   vs 3. The verdict is keyed on an INDEPENDENT host probe (parallel
   socket-echo throughput, the PACK_SCALE precedent): this bench host
   has 2 cores and cannot parallelize independent event loops, so the
   scaling bar arms only when the probe shows the host capable — the
   nightly wrapper re-runs with the same rule on whatever host it gets,
   and the disclosure rides the artifact either way.

Plus the default-config inertness subprocess proof (single-endpoint
--broker_url never imports the fabric module).

Run: python scripts/soak_broker_fabric.py                   # committed artifact
     python scripts/soak_broker_fabric.py --quick --out /tmp/x  # nightly wrapper
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import struct
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_rollout(L, H, version, actor_id, uid, rng):
    """One synthetic rollout chunk, uniquely stamped: episode_return
    carries `uid` (exact in f32 below 2^24) so the consumer can prove
    no chunk is ever delivered twice without trusting the fence it is
    auditing."""
    from dotaclient_tpu.env import featurizer as F
    from dotaclient_tpu.ops.action_dist import Action
    from dotaclient_tpu.transport.serialize import Rollout

    T1 = L + 1
    obs = F.Observation(
        global_feats=rng.randn(T1, F.GLOBAL_FEATURES).astype(np.float32),
        hero_feats=rng.randn(T1, F.HERO_FEATURES).astype(np.float32),
        unit_feats=rng.randn(T1, F.MAX_UNITS, F.UNIT_FEATURES).astype(np.float32),
        unit_mask=rng.rand(T1, F.MAX_UNITS) < 0.5,
        target_mask=rng.rand(T1, F.MAX_UNITS) < 0.3,
        action_mask=np.ones((T1, F.N_ACTION_TYPES), bool),
    )
    return Rollout(
        obs=obs,
        actions=Action(
            type=rng.randint(0, 4, L).astype(np.int32),
            move_x=rng.randint(0, 9, L).astype(np.int32),
            move_y=rng.randint(0, 9, L).astype(np.int32),
            target=rng.randint(0, F.MAX_UNITS, L).astype(np.int32),
        ),
        behavior_logp=rng.randn(L).astype(np.float32),
        behavior_value=rng.randn(L).astype(np.float32),
        rewards=rng.randn(L).astype(np.float32),
        dones=np.zeros(L, np.float32),
        initial_state=(rng.randn(H).astype(np.float32), rng.randn(H).astype(np.float32)),
        version=version,
        actor_id=actor_id,
        episode_return=float(uid),
    )


def _uid_of(frame: bytes) -> float:
    """The unique stamp back out of a serialized frame (header peek:
    episode_return at offset 17 in every DTR layout)."""
    return struct.unpack_from("<f", frame, 17)[0]


# --------------------------------------------------------------- host probe


def _cpu_probe(threads_n: int, seconds: float) -> float:
    """Aggregate crc32 MB/s over `threads_n` worker threads, each
    hashing its own 1 MiB buffer in a loop — zlib.crc32 releases the
    GIL, so this measures how many CPU-bound worker threads this host
    can genuinely run in parallel. Pure stdlib, none of the fabric's
    own code, so a scaling verdict keyed on it is independent of the
    thing being measured (the PACK_SCALE raw-memcpy rule). Deliberately
    CPU-bound, not latency-bound: an idle-socket echo probe scales with
    event-loop latency and flaps on loaded hosts."""
    import zlib

    stop = threading.Event()
    counts = [0] * threads_n
    buf = os.urandom(1 << 20)

    def work(i):
        while not stop.is_set():
            zlib.crc32(buf)
            counts[i] += 1

    threads = [threading.Thread(target=work, args=(i,), daemon=True) for i in range(threads_n)]
    for t in threads:
        t.start()
    time.sleep(seconds)
    stop.set()
    for t in threads:
        t.join(timeout=3)
    return sum(counts) / seconds  # MiB/s


def host_probe(quick: bool) -> dict:
    window = 0.5 if quick else 1.0
    r1 = _cpu_probe(1, window)
    r3 = _cpu_probe(3, window)
    scaling = r3 / max(r1, 1e-9)
    return {
        "disclosed": True,
        "what": "aggregate GIL-released crc32 MiB/s, 1 vs 3 worker "
        "threads — none of the fabric's own code (the PACK_SCALE rule)",
        "cpu_count": os.cpu_count(),
        "crc_mibs_1thread": round(r1, 1),
        "crc_mibs_3threads": round(r3, 1),
        "scaling_3_over_1": round(scaling, 3),
        # 3 shard event loops + producers need ≥3 genuinely-parallel
        # cores; a 2-core host tops out at 2.0 on this probe by
        # construction, so 2.2 can only be cleared where the scaling
        # bar is actually winnable
        "capable": scaling >= 2.2,
    }


# ------------------------------------------------------------ phase 1: kill


class ShardRouter:
    """Round-robin kill/restart fan-out over N BrokerIncarnations — the
    rolling@T:P@broker execution contract (replica_count + the
    first-enqueue recovery probe on the replica just restarted)."""

    def __init__(self, incs):
        self.incs = incs
        self._next = 0
        self._cur = 0

    def replica_count(self) -> int:
        return len(self.incs)

    def kill(self):
        self._cur = self._next
        self._next = (self._next + 1) % len(self.incs)
        return self.incs[self._cur].kill()

    def restart(self):
        self.incs[self._cur].restart()

    def wait_first_enqueue(self, timeout=30.0, stop=None):
        return self.incs[self._cur].wait_first_enqueue(timeout, stop)


def phase_kill(quick: bool) -> dict:
    from dotaclient_tpu.chaos.controller import BrokerIncarnations, ScheduleRunner
    from dotaclient_tpu.chaos.schedule import FaultSchedule
    from dotaclient_tpu.transport.base import BrokerShedError, RetryPolicy
    from dotaclient_tpu.transport.fabric import FabricBroker
    from dotaclient_tpu.transport.serialize import serialize_rollout

    n_shards = 3
    incs = [
        BrokerIncarnations(port=0, maxlen=4096, shed_high=1024, shed_low=256, priority_shed=True)
        for _ in range(n_shards)
    ]
    urls = [f"tcp://127.0.0.1:{inc.port}" for inc in incs]
    retry = RetryPolicy(window_s=1.0, backoff_base_s=0.05, backoff_cap_s=0.4, jitter=0.5)

    duration = 8.0 if quick else 14.0
    spec = (
        "kill@1.5:1@broker,rolling@4:0.6@broker"
        if quick
        else "kill@2:1.5@broker,kill@6:1@broker,rolling@8:0.8@broker"
    )
    recovery_budget_s = 5.0

    stop = threading.Event()
    producers = []
    prod_stats = []

    def producer(pid: int):
        fb = FabricBroker(urls, retry=retry, failover_window_s=1.0, cooldown_s=1.0)
        rng = np.random.RandomState(1000 + pid)
        st = {
            "attempted": 0, "acked": 0, "shed": 0, "failed": 0,
            "max_gap_s": 0.0, "failovers": 0,
        }
        prod_stats.append(st)
        last_ok = time.monotonic()
        uid = pid * 1_000_000
        while not stop.is_set():
            uid += 1
            r = _make_rollout(2, 8, 0, actor_id=pid * 8 + (uid % 8), uid=uid, rng=rng)
            st["attempted"] += 1
            try:
                fb.publish_experience(serialize_rollout(r), priority=float(uid % 7))
                st["acked"] += 1
                now = time.monotonic()
                st["max_gap_s"] = max(st["max_gap_s"], now - last_ok)
                last_ok = now
            except BrokerShedError:
                st["shed"] += 1
            except (ConnectionError, OSError):
                st["failed"] += 1
            time.sleep(0.008)
        st["failovers"] = fb.failovers_total
        fb.close()

    consumer_fb = FabricBroker(urls, retry=retry, failover_window_s=1.0, cooldown_s=1.0)
    seen_uids: dict = {}
    consumed = {"n": 0}

    def consumer():
        while not stop.is_set():
            for f in consumer_fb.consume_experience(64, timeout=0.2):
                uid = _uid_of(bytes(f))
                seen_uids[uid] = seen_uids.get(uid, 0) + 1
                consumed["n"] += 1

    for pid in range(4):
        t = threading.Thread(target=producer, args=(pid,), daemon=True)
        producers.append(t)
        t.start()
    cons = threading.Thread(target=consumer, daemon=True)
    cons.start()

    router = ShardRouter(incs)
    t0 = time.monotonic()
    runner = ScheduleRunner(FaultSchedule.parse(spec, seed=7), broker=router, t0=t0).start()
    time.sleep(duration)
    # let the schedule COMPLETE (a rolling event's restart+probe tail can
    # outlast the nominal window) before tearing the fleet down — a roll
    # cut short would under-count restarts and fail the at-most-one-down
    # verdict for the wrong reason
    runner._thread.join(timeout=60)
    stop.set()
    for t in producers:
        t.join(timeout=10)
    cons.join(timeout=10)
    runner.stop()
    # settle: stop new shard pops, wait out any mid-pop thread, then
    # drain the fan-in queue to zero — after this the fence counters are
    # final and every client-popped frame is in exactly one of
    # (delivered→seen_uids, fence_dropped, dup_dropped)
    consumer_fb.quiesce()
    deadline = time.monotonic() + 10
    while any(consumer_fb._mid_pop) and time.monotonic() < deadline:
        time.sleep(0.02)
    for f in consumer_fb.consume_residual(1_000_000):
        uid = _uid_of(bytes(f))
        seen_uids[uid] = seen_uids.get(uid, 0) + 1
        consumed["n"] += 1
    fence = consumer_fb._fence
    fanin_left = consumer_fb.fanin_residual()
    consumer_fb.close()

    generations = []
    for i, inc in enumerate(incs):
        inc.final_ledger()  # folds the live incarnation into .ledgers
        for g, led in enumerate(inc.ledgers):
            generations.append({"shard": i, "generation": g, **{
                k: led[k] for k in (
                    "enqueued", "popped", "dropped_oldest", "shed",
                    "reply_lost", "evicted_low", "resident",
                )
            }})
    sum_popped = sum(g["popped"] for g in generations)
    sum_reply_lost = sum(g["reply_lost"] for g in generations)
    # fence.delivered counts frames admitted INTO the fan-in queue; the
    # settle loop above drained that queue to zero, so delivered ==
    # frames the consumer actually holds and the identity is exact:
    #   Σpopped − Σreply_lost = delivered + fence_dropped + dup_dropped
    delivered = fence.delivered
    unaccounted = sum_popped - sum_reply_lost - (
        delivered + fence.fence_dropped + fence.dup_dropped
    )
    duplicates = sum(1 for c in seen_uids.values() if c > 1)
    per_gen_ok = all(
        g["enqueued"] == g["popped"] + g["dropped_oldest"] + g["evicted_low"] + g["resident"]
        for g in generations
    )
    acked = sum(s["acked"] for s in prod_stats)
    return {
        "shards": n_shards,
        "schedule": spec,
        "duration_s": duration,
        "shard_generations": generations,
        "per_generation_ledgers_sum_exactly": per_gen_ok,
        "producers": prod_stats,
        "producer_acked_total": acked,
        "consumer": {
            "delivered": delivered,
            "fence_dropped": fence.fence_dropped,
            "dup_dropped": fence.dup_dropped,
            "fanin_residual_after_drain": fanin_left,
            "unique_chunks": len(seen_uids),
        },
        "recovery": runner.recovery,
        "rolling_replicas_restarted": sum(
            1 for e in runner.recovery if e.get("kind") == "rolling"
        ),
        "max_publish_gap_s": round(max(s["max_gap_s"] for s in prod_stats), 3),
        "recovery_budget_s": recovery_budget_s,
        "unaccounted": int(unaccounted),
        "duplicates_delivered": duplicates,
    }


# --------------------------------------------- phase 2: stale resurrection


def phase_resurrection() -> dict:
    from dotaclient_tpu.transport.base import RetryPolicy
    from dotaclient_tpu.transport.fabric import (
        FabricBroker, peek_fabric, rendezvous_order, wrap_fabric,
    )
    from dotaclient_tpu.transport.serialize import peek_rollout_actor_id, serialize_rollout
    from dotaclient_tpu.transport.tcp import BrokerServer, TcpBroker

    s = [BrokerServer(port=0).start(), BrokerServer(port=0).start()]
    urls = [f"tcp://127.0.0.1:{srv.port}" for srv in s]
    fb = FabricBroker(
        urls,
        retry=RetryPolicy(window_s=0.4, backoff_base_s=0.02, backoff_cap_s=0.1, jitter=0.0),
        failover_window_s=0.4,
        cooldown_s=0.5,
    )
    rng = np.random.RandomState(0)
    frames = [
        serialize_rollout(_make_rollout(2, 8, 0, actor_id=5, uid=9000 + i, rng=rng))
        for i in range(6)
    ]
    key = peek_rollout_actor_id(frames[0])
    order = rendezvous_order(key, urls)
    primary = s[order[0]]
    # steady state: 5 chunks through the primary, drained by the
    # consumer BEFORE the kill (frames resident in a killed in-process
    # broker vaporize with its memory; this phase is about the fence,
    # not kill-resident loss — phase 1 ledgers that)
    for f in frames[:5]:
        fb.publish_experience(f)
    got = []
    deadline = time.monotonic() + 8
    while len(got) < 5 and time.monotonic() < deadline:
        got.extend(bytes(f) for f in fb.consume_experience(32, timeout=0.2))
    assert len(got) == 5, f"steady state only delivered {len(got)}/5"
    # partition: the primary dies; chunk 5 fails over with an epoch bump
    primary.stop()
    fb.publish_experience(frames[5])
    # resurrection: the primary returns STILL HOLDING the old-epoch copy
    # of chunk 5 (the ack-lost-but-landed fate — re-injected verbatim,
    # since an in-process restart cannot retain queue memory)
    deadline = time.monotonic() + 15
    reborn = None
    while reborn is None:
        try:
            reborn = BrokerServer(port=primary.port).start()
        except (RuntimeError, OSError):
            if time.monotonic() >= deadline:
                raise
            time.sleep(0.05)
    stale_copy = wrap_fabric(frames[5], key=key, boot=fb._boot, epoch=0, seq=5)
    direct = TcpBroker(port=reborn.port)
    direct.publish_experience(stale_copy)
    time.sleep(0.6)  # cooldown expiry: the reborn primary re-enters rotation

    deadline = time.monotonic() + 8
    while (len(got) < 6 or fb._fence.fence_dropped < 1) and time.monotonic() < deadline:
        got.extend(bytes(f) for f in fb.consume_experience(32, timeout=0.2))
    uids = [_uid_of(f) for f in got]
    dup_delivered = len(uids) - len(set(uids))
    out = {
        "chunks_published": 6,
        "delivered": len(got),
        "delivered_unique": len(set(uids)),
        "duplicates_delivered": dup_delivered,
        "fence_dropped": fb._fence.fence_dropped,
        "dup_dropped": fb._fence.dup_dropped,
        "failovers": fb.failovers_total,
        "fence_fired": fb._fence.fence_dropped >= 1,
        "republished_chunk_delivered_exactly_once": uids.count(9005.0) == 1,
    }
    direct.close()
    fb.close()
    reborn.stop()
    s[order[1]].stop()
    return out


# ------------------------------------ phase 3: 2-learner fan-in + resume


def _state_hash(learner) -> str:
    import jax

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(jax.device_get(learner.state)):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def phase_two_learner(quick: bool, tmpdir: str) -> dict:
    import jax

    jax.config.update("jax_platforms", "cpu")
    from dotaclient_tpu.config import LearnerConfig, PolicyConfig
    from dotaclient_tpu.runtime.learner import Learner
    from dotaclient_tpu.transport import memory as mem
    from dotaclient_tpu.transport.fabric import FabricBroker, rendezvous_order
    from dotaclient_tpu.transport.serialize import serialize_rollout

    urls = ["mem://fab0", "mem://fab1", "mem://fab2", "mem://fab3"]
    K = 4 if quick else 6  # steps per learner
    B, L, H = 8, 4, 8
    small = PolicyConfig(unit_embed_dim=8, lstm_hidden=H, mlp_hidden=8, dtype="float32")

    # actor ids by rendezvous primary: A-stream → shards {0,1};
    # B-stream → shard 3 ONLY (shard 2 stays empty, so learner B's
    # 2-shard subset still has a deterministic fan-in order — the
    # bit-exactness arm needs one).
    ids_a, ids_b = [], []
    for aid in range(4096):
        p = rendezvous_order(aid, urls)[0]
        if p in (0, 1) and len(ids_a) < K * B:
            ids_a.append(aid)
        elif p == 3 and len(ids_b) < K * B:
            ids_b.append(aid)
        if len(ids_a) == K * B and len(ids_b) == K * B:
            break
    assert len(ids_a) == K * B and len(ids_b) == K * B

    def frames_for(ids, seed0):
        out = []
        for i, aid in enumerate(ids):
            rng = np.random.RandomState(seed0 + i)
            out.append(
                serialize_rollout(_make_rollout(L, H, 0, actor_id=aid, uid=seed0 + i, rng=rng))
            )
        return out

    frames_a = frames_for(ids_a, 50_000)
    frames_b = frames_for(ids_b, 90_000)
    k1 = max(1, K // 2)
    # B's schedule arrives in two tranches with a 3-frame partial tail
    # on the first: the SIGTERM drain lands with k1 trained steps plus 3
    # popped-but-untrainable pending frames, which the full-state
    # checkpoint must carry across the restart (the PR-7 pending
    # contract) — tranche 2 only exists for life 2.
    cut = k1 * B + 3
    tranche1_b, tranche2_b = frames_b[:cut], frames_b[cut:]

    def reset_hubs():
        for u in urls:
            mem.reset(u[len("mem://"):])

    def publish(frames):
        pub = FabricBroker(urls)
        for f in frames:
            pub.publish_experience(f)
        pub.close()

    def make_learner(tag: str, shards, full_state: bool):
        cfg = LearnerConfig(
            batch_size=B, seq_len=L, policy=small, publish_every=1,
            metrics_every=1, checkpoint_every=10_000,
            checkpoint_dir=os.path.join(tmpdir, tag) if full_state else "",
        )
        cfg.ppo.max_staleness = 100_000
        if full_state:
            cfg.ckpt.full_state = True
        fb = FabricBroker(urls, consume_shards=shards)
        return Learner(cfg, fb), fb

    # --- arm 1: uninterrupted learner B' over the full schedule
    reset_hubs()
    publish(frames_a + tranche1_b + tranche2_b)
    lb1, fb1 = make_learner("arm1", [2, 3], full_state=False)
    lb1.run(num_steps=K, batch_timeout=30.0, max_idle=4)
    hash_arm1 = _state_hash(lb1)
    consumed_arm1 = lb1.staging.stats()["consumed"]
    lb1.close()
    fb1.close()

    # --- arm 2: learner A (disjoint shards) + learner B with a SIGTERM
    # drain mid-run and a full-state resume; B's tranche 2 lands only
    # after the restart, so life 1 genuinely stops mid-schedule
    reset_hubs()
    publish(frames_a + tranche1_b)
    la, fba = make_learner("arm2a", [0, 1], full_state=False)
    a_result = {}

    def run_a():
        a_result["steps"] = la.run(num_steps=K, batch_timeout=60.0, max_idle=8)

    ta = threading.Thread(target=run_a, daemon=True)
    ta.start()

    lb2, fbb = make_learner("arm2b", [2, 3], full_state=True)
    b_thread_done = {}

    def run_b():
        b_thread_done["steps"] = lb2.run(num_steps=K, batch_timeout=60.0, max_idle=8)

    tb = threading.Thread(target=run_b, daemon=True)
    tb.start()
    deadline = time.monotonic() + 300
    while lb2.version < k1 and time.monotonic() < deadline:
        time.sleep(0.02)
    lb2.request_drain()  # the real SIGTERM path
    tb.join(timeout=180)
    assert not tb.is_alive(), "learner B drain wedged"
    lb2.drain_save()
    drained_version = lb2.version
    pending_saved = lb2.staging.stats()["pending_rollouts"]
    lb2.close()
    fbb.close()

    # life 2: restore (incl. the pending partial tail) and train out the
    # remaining schedule, whose tranche-2 frames arrive only now
    publish(tranche2_b)
    lb3, fbb3 = make_learner("arm2b", [2, 3], full_state=True)
    resumed_version = lb3.version
    remaining = K - resumed_version
    if remaining > 0:
        lb3.run(num_steps=remaining, batch_timeout=60.0, max_idle=8)
    hash_arm2 = _state_hash(lb3)
    lb3.close()
    fbb3.close()

    ta.join(timeout=300)
    a_steps = a_result.get("steps", -1)
    a_consumed = la.staging.stats()["consumed"]
    la.close()
    fba.close()

    return {
        "steps_per_learner": K,
        "frames_per_learner": K * B,
        "arm1_hash": hash_arm1,
        "arm1_consumed": int(consumed_arm1),
        "drained_at_version": int(drained_version),
        "pending_frames_saved": int(pending_saved),
        "resumed_at_version": int(resumed_version),
        "arm2_hash": hash_arm2,
        "bit_exact": hash_arm1 == hash_arm2,
        "learner_a": {
            "steps": int(a_steps),
            "consumed": int(a_consumed),
            # disjoint fan-in: A consumed exactly its own stream
            "cross_contaminated": bool(a_consumed != K * B),
        },
        "resume_note": "params/opt/step sha256 over every leaf, arm1 vs "
        "arm2 (drain at ~K/2 + full-state restore), identical frame "
        "schedule per the PR-7 lockstep contract",
    }


# ----------------------------------------------- phase 4: offered scaling


def phase_scaling(quick: bool) -> dict:
    from dotaclient_tpu.transport.base import RetryPolicy
    from dotaclient_tpu.transport.fabric import FabricBroker
    from dotaclient_tpu.transport.serialize import serialize_rollout
    from dotaclient_tpu.transport.tcp import BrokerServer, TcpBroker

    window = 1.0 if quick else 2.0
    rng = np.random.RandomState(0)
    payloads = [
        serialize_rollout(_make_rollout(2, 8, 0, actor_id=a, uid=a, rng=rng))
        for a in range(32)
    ]

    def offered_rate(n_shards: int) -> float:
        servers = [BrokerServer(port=0, maxlen=200_000).start() for _ in range(n_shards)]
        urls = [f"tcp://127.0.0.1:{s.port}" for s in servers]
        stop = threading.Event()
        counts = [0] * 4

        def pump(i):
            if n_shards == 1:
                cli = TcpBroker(port=servers[0].port)
                pub = cli.publish_experience
            else:
                cli = FabricBroker(urls, retry=RetryPolicy(window_s=1.0))
                pub = cli.publish_experience
            j = i
            while not stop.is_set():
                pub(payloads[j % len(payloads)])
                counts[i] += 1
                j += 1
            cli.close()

        threads = [threading.Thread(target=pump, args=(i,), daemon=True) for i in range(4)]
        for t in threads:
            t.start()
        time.sleep(window)
        stop.set()
        for t in threads:
            t.join(timeout=5)
        for s in servers:
            s.stop()
        return sum(counts) / window

    r1 = offered_rate(1)
    r3 = offered_rate(3)
    return {
        "window_s": window,
        "producers": 4,
        "rate_1_shard_fps": round(r1, 1),
        "rate_3_shards_fps": round(r3, 1),
        "scaling_3_over_1": round(r3 / max(r1, 1e-9), 3),
    }


# ------------------------------------------------------------- inertness


def inertness_proof() -> dict:
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from dotaclient_tpu.transport.base import connect\n"
        "b = connect('mem://soak_inert'); b.publish_experience(b'x')\n"
        "assert b.consume_experience(1, timeout=0.5) == [b'x']\n"
        "assert 'dotaclient_tpu.transport.fabric' not in sys.modules\n"
        "print('INERT_OK')\n" % REPO_ROOT
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120
    )
    return {
        "fabric_imported_on_classic_path": "INERT_OK" not in proc.stdout,
        "rc": proc.returncode,
    }


# ------------------------------------------------------------------- main


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="BROKER_FABRIC_SOAK.json")
    p.add_argument("--quick", action="store_true")
    args = p.parse_args(argv)

    from dotaclient_tpu.obs.preflight import check as preflight_check

    import tempfile

    artifact = {
        "created": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "quick": bool(args.quick),
        "host_preflight": preflight_check("soak_broker_fabric"),
        "host": {"cpu_count": os.cpu_count(), "platform": sys.platform},
    }
    print("== host probe", flush=True)
    artifact["host_probe"] = host_probe(args.quick)
    print(json.dumps(artifact["host_probe"]), flush=True)

    print("== phase 1: shard kills + rolling restart conservation", flush=True)
    artifact["phase_kill"] = phase_kill(args.quick)
    print(json.dumps({k: v for k, v in artifact["phase_kill"].items()
                      if k not in ("shard_generations", "recovery", "producers")}), flush=True)

    print("== phase 2: stale-shard resurrection fence", flush=True)
    artifact["phase_resurrection"] = phase_resurrection()
    print(json.dumps(artifact["phase_resurrection"]), flush=True)

    print("== phase 3: 2-learner disjoint fan-in + SIGTERM resume", flush=True)
    with tempfile.TemporaryDirectory() as td:
        artifact["phase_two_learner"] = phase_two_learner(args.quick, td)
    print(json.dumps(artifact["phase_two_learner"]), flush=True)

    print("== phase 4: offered-rate scaling (probe-keyed)", flush=True)
    artifact["phase_scaling"] = phase_scaling(args.quick)
    probe = artifact["host_probe"]
    scaling = artifact["phase_scaling"]["scaling_3_over_1"]
    artifact["phase_scaling"]["bar"] = 1.5
    artifact["phase_scaling"]["required"] = probe["capable"]
    artifact["phase_scaling"]["met"] = scaling >= 1.5
    artifact["phase_scaling"]["excused_by_probe"] = (not probe["capable"]) and scaling < 1.5
    artifact["phase_scaling"]["note"] = (
        "the %d-core bench host's probe scaling is %.2fx — shard scaling "
        "is %s here; the nightly wrapper re-arms the bar on capable hosts"
        % (os.cpu_count() or 0, probe["scaling_3_over_1"],
           "required" if probe["capable"] else "excused by the probe")
    )
    print(json.dumps(artifact["phase_scaling"]), flush=True)

    print("== inertness", flush=True)
    artifact["inertness"] = inertness_proof()

    pk = artifact["phase_kill"]
    pr = artifact["phase_resurrection"]
    tl = artifact["phase_two_learner"]
    sc = artifact["phase_scaling"]
    verdict = {
        "per_shard_generation_ledgers_sum_exactly": pk["per_generation_ledgers_sum_exactly"],
        "unaccounted_frames": int(pk["unaccounted"]),
        "duplicate_applied_chunks": int(
            pk["duplicates_delivered"] + pr["duplicates_delivered"]
        ),
        "fence_fired_under_resurrection": bool(pr["fence_fired"]),
        "resurrected_chunk_exactly_once": bool(pr["republished_chunk_delivered_exactly_once"]),
        "actor_recovery_bounded": pk["max_publish_gap_s"] <= pk["recovery_budget_s"],
        "rolling_at_most_one_down": pk["rolling_replicas_restarted"] == pk["shards"],
        "two_learner_resume_bit_exact": bool(tl["bit_exact"]),
        "fanin_disjoint_no_cross_contamination": not tl["learner_a"]["cross_contaminated"],
        "scaling_met_or_excused": bool(sc["met"] or sc["excused_by_probe"]),
        "inert_on_classic_path": not artifact["inertness"]["fabric_imported_on_classic_path"],
    }
    verdict["all_green"] = all(
        (v is True) if isinstance(v, bool) else (v == 0) for v in verdict.values()
    )
    artifact["verdict"] = verdict
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
    print(json.dumps(verdict, indent=2), flush=True)
    return 0 if verdict["all_green"] else 1


if __name__ == "__main__":
    sys.exit(main())
