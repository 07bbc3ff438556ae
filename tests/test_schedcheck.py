"""Schedcheck tests (dotaclient_tpu/analysis/schedcheck.py): bounded
exhaustive exploration of the protocol models, the failing-then-fixed
regression schedules for the two shipped bug classes (PR-11
early-lease-release H2D corruption, PR-7 drained()-while-in-locals
loss), and cross-validation of the ring model against the real
TransferRing/RingSlot. Pure stdlib except the cross-validation — the
no-JAX subprocess proof pins that."""

from __future__ import annotations

import subprocess
import sys
import textwrap
from types import SimpleNamespace

import pytest

from dotaclient_tpu.analysis.schedcheck import (
    CoalesceModel,
    DrainedModel,
    HandoffModel,
    HotSwapModel,
    RingLeaseModel,
    ShardEpochModel,
    explore,
    head_models,
    random_walks,
)
from tests.conftest import clean_subprocess_env

REPO_ROOT = __file__.rsplit("/tests/", 1)[0]


# ----------------------------------------------------- HEAD protocols


def test_head_protocols_exhaust_clean():
    """Acceptance bar: every HEAD protocol model explores its ENTIRE
    bounded interleaving set with zero violations — ring-lease and
    drained() included. `exhausted` is asserted explicitly: a clean but
    truncated search proves nothing."""
    for name, model in head_models().items():
        result = explore(model)
        assert result.exhausted, f"{name}: truncated at {result.states} states"
        assert result.violations == [], f"{name}: {result.violations}"
        assert result.states > 10, f"{name}: vacuous model ({result.states} states)"


def test_require_exhausted_clean_raises_on_truncation():
    result = explore(RingLeaseModel(depth=2, batches=3), max_states=5)
    assert not result.exhausted
    with pytest.raises(AssertionError, match="truncated"):
        result.require_exhausted_clean()


# -------------------------------------- shipped bug class 1: ring lease


def test_early_lease_release_schedule_found_then_fixed():
    """The PR-11 regression as a failing-then-fixed schedule pair: with
    the lease released at put-dispatch, exploration FINDS the schedule
    where the packer repacks the slot under the in-flight H2D read; with
    the HEAD protocol (release after retire) the same bounded set is
    exhausted clean. (The static half of this pin is LIF001,
    tests/test_graftlint.py::test_early_lease_release_mutant_fails_lint.)"""
    broken = explore(RingLeaseModel(depth=2, batches=3, mutant="early_release"))
    assert any("early-lease-release corruption" in v for v in broken.violations)
    fixed = explore(RingLeaseModel(depth=2, batches=3))
    assert fixed.exhausted and fixed.violations == []


def test_double_release_schedule_found():
    """Losing RingSlot._held (non-idempotent release) duplicates the
    slot in the free queue; exploration finds the acquire that hands out
    a non-free slot."""
    broken = explore(RingLeaseModel(depth=2, batches=4, mutant="double_release"))
    assert any("double release" in v for v in broken.violations)


# ---------------------------------------- shipped bug class 2: drained()


@pytest.mark.parametrize(
    "mutant",
    ["no_packing_check", "downstream_first", "clear_flag_before_put"],
)
def test_drained_loss_schedules_found_then_fixed(mutant):
    """The PR-7 regression: each mutant re-introduces a way for
    drained() to declare victory over in-flight frames — the missing
    _packing check (the shipped bug), downstream-first station reads,
    and clearing the flag before the ready-queue put. Exploration finds
    the losing schedule for each; the HEAD protocol (upstream-first,
    flag-set-under-the-pop-lock) is exhausted clean."""
    broken = explore(DrainedModel(frames=2, mutant=mutant))
    assert any("PR-7 bug class" in v for v in broken.violations), (
        mutant,
        broken.violations,
    )
    fixed = explore(DrainedModel(frames=2))
    assert fixed.exhausted and fixed.violations == []


# -------------------------------- ISSUE 15: the prefetch-lane lifecycle


def test_prefetch_head_exhausts_clean():
    """The overlapped-loop protocol (PrefetchLane + _fetch_next:
    take → put-dispatch → retire → release → handoff → train, with the
    drain stations one hop further downstream) explores its entire
    bounded interleaving set clean — including schedules where the
    drain quiesces mid-lifecycle."""
    from dotaclient_tpu.analysis.schedcheck import PrefetchModel

    explore(PrefetchModel(depth=2, batches=3)).require_exhausted_clean()


@pytest.mark.parametrize(
    "mutant, needle",
    [
        ("release_before_retire", "early-release corruption"),
        ("train_consumes_inflight", "had not retired"),
        ("drain_ignores_prefetch", "prefetch station"),
    ],
)
def test_prefetch_mutants_found_then_fixed(mutant, needle):
    """Each mutant re-introduces a bug class the pipelined loop must
    exclude: the PR-11 early lease release (now one thread further from
    the loop), training a batch whose H2D never retired (the handoff
    ordering rule), and a drain that cannot see the lane's holdings
    (the PR-7 loss class at the new station). Exploration finds each;
    HEAD is exhausted clean (the test above)."""
    from dotaclient_tpu.analysis.schedcheck import PrefetchModel

    broken = explore(PrefetchModel(depth=2, batches=3, mutant=mutant))
    assert any(needle in v for v in broken.violations), (mutant, broken.violations)


def test_prefetch_model_matches_real_lane():
    """Cross-validate the model's lane semantics against the REAL
    PrefetchLane: the holding() flag covers the whole pop-to-handoff
    window (no gap a drain could slip through), FIFO order is
    preserved, the fetch budget caps deliveries, and idle results
    consume no budget."""
    import queue as _q
    import threading
    import time

    from dotaclient_tpu.runtime.learner import PrefetchLane

    source = _q.Queue()
    for i in range(3):
        source.put(i)

    observed_holding_during_fetch = []

    lane_box = []

    def fetch():
        try:
            item = source.get(timeout=0.3)
        except _q.Empty:
            return None, 0, 0.3, 0.0, None
        # mid-fetch, after the pop: holding() must already be True
        observed_holding_during_fetch.append(lane_box[0].holding())
        return item, 1, 0.0, 0.0, None

    lane = PrefetchLane(fetch, limit=2)
    lane_box.append(lane)
    lane.start()
    got = []
    deadline = time.monotonic() + 5
    while len(got) < 2 and time.monotonic() < deadline:
        try:
            item = lane.get(timeout=0.2)
        except _q.Empty:
            continue
        if item.kind == "batch":
            got.append(item.batch)
    lane.stop()
    assert got == [0, 1]  # FIFO, budget-capped at limit=2
    assert lane.fetched == 2
    assert source.qsize() == 1  # the third batch was never eaten
    assert all(observed_holding_during_fetch)
    assert not lane.holding()


# --------------------------------------------- the other two protocols


def test_coalesce_lost_newest_schedule_found():
    broken = explore(CoalesceModel(versions=3, mutant="no_resubmit"))
    assert any("latest-wins contract broke" in v for v in broken.violations)


def test_hot_swap_mixed_tick_schedule_found():
    broken = explore(HotSwapModel(swaps=2, ticks=2, rows=2, mutant="per_row_read"))
    assert any("mixed tick" in v for v in broken.violations)


# ------------------------------------------- carry-handoff lifecycle


@pytest.mark.parametrize(
    "mutant,needle",
    [
        ("handoff_after_ack", "abandoned"),
        ("resume_from_stale", "diverge"),
        ("single_entry", "abandoned"),
        ("dup_shift", "abandoned"),
    ],
)
def test_handoff_mutants_found_then_fixed(mutant, needle):
    """The PR-13 session-continuity protocol, failing-then-fixed: each
    mutant re-introduces a losing order — ack-before-durable-write, a
    stale (non-exact-match) restore, a single-entry store, and the
    duplicate-boundary shift that exploration of THIS model caught
    during development (CarryStore.put replaces on equal episode_step
    because of it). Exploration finds every one; the HEAD protocol
    (write-ahead + keep-two + replace-on-dup + exact-match) exhausts
    its entire bounded interleaving set clean."""
    broken = explore(HandoffModel(steps=5, chunk=2, kills=2, mutant=mutant))
    assert any(needle in v for v in broken.violations), (mutant, broken.violations)
    fixed = explore(HandoffModel(steps=5, chunk=2, kills=2))
    assert fixed.exhausted and fixed.violations == []


def test_handoff_resharding_walk_clean_and_primary_only_found():
    """The sharded-store extension: with a reshard thread that adds a
    shard mid-episode (adversarially becoming the key's new rendezvous
    primary), the full-preference-order walk read exhausts clean —
    kills before/after the topology change included. The
    reshard_primary_only mutant (read consults only the NEW primary)
    loses exactly the schedule sharding introduces: boundary durable on
    the old primary, reshard, kill, resume finds nothing → abandon.
    ShardedCarryStore.get walks the full order because of this."""
    fixed = explore(HandoffModel(steps=5, chunk=2, kills=2, shards=2))
    assert fixed.exhausted and fixed.violations == []
    broken = explore(
        HandoffModel(steps=5, chunk=2, kills=2, shards=2, mutant="reshard_primary_only")
    )
    assert any("abandoned" in v for v in broken.violations), broken.violations
    # the mutant is meaningless without a possible reshard — the model
    # refuses the degenerate configuration rather than passing vacuously
    with pytest.raises(AssertionError):
        HandoffModel(shards=1, mutant="reshard_primary_only")


def test_handoff_model_matches_real_carry_store():
    """Cross-validation against the REAL CarryStore (serve/handoff.py):
    the four semantics the model's store component encodes — exact-match
    restore only, the previous boundary retained (the lost-ack resume),
    same-boundary puts replacing instead of shifting (the dup_shift
    catch), and stale/miss refusals — asserted on the shipped class."""
    import numpy as np

    from dotaclient_tpu.serve.handoff import ST_MISS, ST_OK, ST_STALE, CarryStore

    store = CarryStore()
    z = np.zeros(8, np.float32)
    # exact-match only: an unknown key is MISS, a known key with no
    # matching boundary is STALE — never a silently-served wrong entry
    assert store.get(1, 2)[0] == ST_MISS
    store.put(1, 2, 1, z, z)
    assert store.get(1, 2)[0] == ST_OK
    assert store.get(1, 4)[0] == ST_STALE
    # keep-two: after the next boundary lands, the previous one still
    # resumes (the model's write-landed-ack-lost schedule)
    store.put(1, 4, 1, z, z)
    assert store.get(1, 2)[0] == ST_OK and store.get(1, 4)[0] == ST_OK
    # replace-on-duplicate: the re-issued chunk-fill re-write must NOT
    # evict the previous entry (the dup_shift mutant's losing schedule)
    store.put(1, 4, 2, z, z)
    assert store.get(1, 2)[0] == ST_OK, (
        "duplicate-boundary put evicted the previous entry — the "
        "dup_shift bug the model exploration caught"
    )
    # and a third distinct boundary finally rotates the oldest out
    store.put(1, 6, 2, z, z)
    assert store.get(1, 2)[0] == ST_STALE
    # the model refuses keep<2 for the same reason the class does
    with pytest.raises(ValueError):
        CarryStore(keep=1)


def test_deadlock_is_a_violation():
    """No enabled thread + not done = deadlock, reported — the
    cancel-swallow teardown class is a search outcome, not a hang."""

    class Stuck:
        threads = ("a",)

        def init(self):
            return {"pc": 0, "violations": []}

        def enabled(self, st, tid):
            return st["pc"] == 0

        def step(self, st, tid):
            st["pc"] = 1  # now waits forever on a condition never set

        def is_local(self, st, tid):
            return False

        def invariant(self, st):
            return st["violations"]

        def done(self, st):
            return False

        def final_check(self, st):
            return []

        def describe(self, st):
            return str(st)

    result = explore(Stuck())
    assert any("deadlock" in v for v in result.violations)


def test_random_walks_are_seed_deterministic():
    a = random_walks(DrainedModel(frames=2), runs=30, seed=7)
    b = random_walks(DrainedModel(frames=2), runs=30, seed=7)
    assert a.states == b.states and a.violations == b.violations
    assert not a.exhausted  # walks never claim exhaustion
    # walks through a mutant find the bug too (the soak's teeth)
    c = random_walks(
        DrainedModel(frames=2, mutant="no_packing_check"), runs=300, seed=7
    )
    assert c.violations


# ------------------------------------------ cross-validation vs real code


def _stub_ring(depth=2):
    """A real TransferRing over a stub io — the lifecycle semantics the
    model assumes, exercised on the shipped class."""
    import numpy as np

    from dotaclient_tpu.env import featurizer as F
    from dotaclient_tpu.parallel.fused_io import TransferRing

    def alloc_transfer():
        payload = np.ones((2, 32), np.uint8)
        batch = SimpleNamespace(
            obs=SimpleNamespace(
                action_mask=np.zeros((2, 3, F.N_ACTION_TYPES), bool)
            )
        )
        return payload, batch

    io = SimpleNamespace(alloc_transfer=alloc_transfer)
    return TransferRing(io, depth)


def test_ring_model_matches_real_transfer_ring():
    """The three semantics the ring model encodes, asserted against the
    REAL TransferRing/RingSlot: acquire hands out only free slots (and
    re-zeros them), release is idempotent (no free-queue duplicate — the
    model's double_release mutant is UNREACHABLE through the real API),
    and a released slot round-trips back through acquire."""
    ring = _stub_ring(depth=2)
    a = ring.acquire(timeout=1)
    b = ring.acquire(timeout=1)
    assert a is not None and b is not None and a is not b
    assert ring.acquire(timeout=0.05) is None  # backpressure: all leased
    assert (a.payload == 0).all()  # acquire re-zeroed the buffer
    a.payload[:] = 7
    a.release()
    a.release()  # idempotent: must NOT duplicate the slot
    assert ring.occupancy == 1
    c = ring.acquire(timeout=1)
    assert c is a and (c.payload == 0).all()
    assert ring.acquire(timeout=0.05) is None  # no phantom second copy
    b.release()
    c.release()
    assert ring.occupancy == 0


def test_drained_model_station_order_matches_staging_source():
    """The model's station list IS StagingBuffer.drained()'s check
    order — pin the real method's upstream-first reads so a reorder
    there invalidates the model loudly instead of silently."""
    import ast
    import os

    path = os.path.join(REPO_ROOT, "dotaclient_tpu", "runtime", "staging.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    drained = next(
        n
        for cls in ast.walk(tree)
        if isinstance(cls, ast.ClassDef) and cls.name == "StagingBuffer"
        for n in cls.body
        if isinstance(n, ast.FunctionDef) and n.name == "drained"
    )
    tagged = []
    for node in ast.walk(drained):
        if isinstance(node, ast.Attribute):
            if node.attr in ("_popping", "unfinished_tasks", "_packing"):
                tagged.append((node.lineno, node.col_offset, node.attr))
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            if node.func.attr == "empty":
                tagged.append((node.lineno, node.col_offset, "ready"))
    src_order = []
    for _, _, label in sorted(tagged):  # ast.walk is BFS; sort by position
        if label not in src_order:
            src_order.append(label)
    assert src_order == ["_popping", "unfinished_tasks", "_packing", "ready"], (
        "StagingBuffer.drained() station order changed — update "
        "DrainedModel._stations to match, or the model checks a protocol "
        "the code no longer runs"
    )


def test_schedcheck_runs_without_jax_in_subprocess():
    """Schedule exploration is pure stdlib: a subprocess (env stripped
    of the pytest XLA cache + 8-device flag per the known wedge) runs
    the full HEAD model set and never imports jax or numpy."""
    code = textwrap.dedent(
        f"""
        import sys
        sys.path.insert(0, {REPO_ROOT!r})
        from dotaclient_tpu.analysis.schedcheck import head_models, explore
        for name, m in head_models().items():
            r = explore(m)
            assert r.exhausted and not r.violations, (name, r.violations)
        assert "jax" not in sys.modules, "schedcheck imported jax"
        assert "numpy" not in sys.modules, "schedcheck imported numpy"
        """
    )
    subprocess.run(
        [sys.executable, "-c", code],
        check=True,
        timeout=120,
        env=clean_subprocess_env(),
    )


# --------------------------------------- broker-fabric shard epoch fence


def test_shard_epoch_head_exhausts_clean_both_partition_fates():
    """The fabric routing/failover protocol (route → publish →
    fence-check → apply) explores its full bounded interleaving set
    clean under BOTH partition-publish fates: the frame landing with
    the ack lost (duplicate hazard) and the frame lost with it
    (liveness hazard)."""
    for land in (True, False):
        r = explore(ShardEpochModel(chunks=3, land_on_partition=land))
        assert r.exhausted, f"land={land}: truncated at {r.states}"
        assert r.violations == [], (land, r.violations)
        assert r.states > 50, f"vacuous model ({r.states} states)"


def test_shard_epoch_mutants_all_fail_exploration():
    """Each mutant re-introduces a bug class the shipped protocol
    excludes; exploration must FIND every one (the failing half of the
    failing-then-fixed pair — HEAD clean is the fixed half)."""
    expect = {
        "no_fence": "applied twice",
        "reroute_before_drain": "UNACCOUNTED",
        "shed_newest": "lower-priority",
    }
    for mutant, needle in expect.items():
        hits = []
        for land in (True, False):
            r = explore(ShardEpochModel(chunks=3, land_on_partition=land, mutant=mutant))
            hits.extend(r.violations)
        assert hits, f"mutant {mutant} explored clean — the model lost its teeth"
        assert any(needle in v for v in hits), (mutant, hits[:3])


def test_shard_epoch_model_cross_validated_against_real_fence():
    """The model's fence-decision table IS ShardFence.admit (single
    producer boot): replay representative (epoch, seq) arrival
    sequences — including the resurrection orderings the model
    explores — through the REAL fence and assert identical verdicts."""
    from dotaclient_tpu.transport.fabric import ShardFence

    # (epoch, seq) arrival order → expected admit verdicts, from the
    # model's _apply rules. Cases: in-order, failover republish, stale
    # copy after the republish (fenced), stale copy BEFORE the republish
    # (applied; republish then dup-dropped), ancient epoch.
    cases = [
        ([(0, 0), (0, 1), (1, 1), (0, 2)], [True, True, False, False]),
        ([(0, 0), (1, 1), (0, 1)], [True, True, False]),
        ([(0, 1), (1, 1)], [True, False]),  # stale-first: seq dedup holds
        ([(0, 0), (2, 3), (1, 2)], [True, True, False]),
    ]
    for arrivals, expected in cases:
        fence = ShardFence()
        model = ShardEpochModel()
        st = model.init()
        got_real = [fence.admit(7, 100, e, s) for e, s in arrivals]
        got_model = []
        for e, s in arrivals:
            before = len(st["applied"])
            model._apply(st, e, s)
            got_model.append(len(st["applied"]) == before + 1)
        assert got_real == expected, (arrivals, got_real)
        assert got_model == expected, (arrivals, got_model)


def test_shard_epoch_model_cross_validated_against_real_router():
    """The model's A-primary/B-successor shape is the real rendezvous
    router's: for any key, every seq routes to ONE shard (the pinning
    contract), and removing the primary makes the model's successor the
    real router's next choice."""
    from dotaclient_tpu.transport.fabric import rendezvous_order

    endpoints = ["tcp://shard-a:1", "tcp://shard-b:2", "tcp://shard-c:3"]
    for key in range(64):
        order = rendezvous_order(key, endpoints)
        assert sorted(order) == [0, 1, 2]
        assert rendezvous_order(key, endpoints) == order  # deterministic
        # consistency: dropping the primary leaves the survivors' order
        survivors = [e for i, e in enumerate(endpoints) if i != order[0]]
        sub = rendezvous_order(key, survivors)
        expect = [e for e in (endpoints[j] for j in order[1:])]
        assert [survivors[i] for i in sub] == expect, key


# ------------------------------------------------------------- nightly lane


@pytest.mark.nightly
@pytest.mark.slow
def test_schedule_soak_deeper_bounds():
    """The nightly schedule soak: wider bounds on every protocol
    (deeper rings, more frames/versions/ticks) explored exhaustively,
    plus long seeded random walks — still zero violations."""
    deep = {
        "ring_lease": RingLeaseModel(depth=3, batches=5),
        "drained": DrainedModel(frames=3, intake_cap=2, ready_cap=2),
        "coalesce": CoalesceModel(versions=5),
        "hot_swap": HotSwapModel(swaps=3, ticks=3, rows=3),
        "carry_handoff": HandoffModel(steps=9, chunk=3, kills=4),
        "carry_handoff_sharded": HandoffModel(steps=7, chunk=2, kills=3, shards=3),
    }
    for name, model in deep.items():
        result = explore(model, max_states=2_000_000)
        assert result.exhausted, f"{name}: truncated at {result.states}"
        assert result.violations == [], f"{name}: {result.violations}"
    for name, model in deep.items():
        walks = random_walks(model, runs=500, seed=11, max_steps=20_000)
        assert walks.violations == [], f"{name}: {walks.violations}"
