"""Overlapped learner pipeline (ISSUE 15, --learner.prefetch): the
PrefetchLane loop's bitwise parity with the serial loop, the PR-7
zero-loss drain contract through the new prefetch station, the overlap
phase accounting and the flag-off inertness."""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import jax

from dotaclient_tpu.config import (
    CkptConfig,
    LearnerConfig,
    ObsConfig,
    PolicyConfig,
    PPOConfig,
)
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import connect
from dotaclient_tpu.transport.serialize import serialize_rollout

from conftest import clean_subprocess_env
from test_transport import make_rollout

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

POL = dict(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16, dtype="float32")


def _cfg(name, tmp_path, prefetch=True, obs=False, **kw):
    cfg = LearnerConfig(
        batch_size=8,
        seq_len=4,
        policy=PolicyConfig(**POL),
        broker_url=f"mem://{name}",
        log_dir=str(tmp_path / name),
        metrics_every=2,
        ppo=PPOConfig(max_staleness=1_000_000),
        obs=ObsConfig(enabled=obs, install_handlers=False),
        **kw,
    )
    cfg.learner.prefetch = prefetch
    return cfg


def _feed(broker, n, seed0=0):
    for i in range(n):
        broker.publish_experience(
            serialize_rollout(
                make_rollout(L=4, H=8, version=0, seed=seed0 + i, actor_id=i)
            )
        )


def _state_hash(state):
    import hashlib

    h = hashlib.sha256()
    for leaf in jax.tree.leaves(jax.device_get((state.params, state.opt_state))):
        h.update(np.ascontiguousarray(leaf).tobytes())
    return h.hexdigest()


def _run_arm(name, tmp_path, prefetch, steps):
    from dotaclient_tpu.runtime.learner import Learner

    mem.reset(name)
    broker = connect(f"mem://{name}")
    _feed(broker, 8 * steps)
    learner = Learner(_cfg(name, tmp_path, prefetch=prefetch), connect(f"mem://{name}"))
    try:
        done = learner.run(num_steps=steps, batch_timeout=60.0, max_idle=3)
        assert done == steps
        return _state_hash(learner.state), learner
    finally:
        learner.close()


# ------------------------------------------------------- bitwise parity


def test_pipelined_bitwise_identical_to_serial(tmp_path):
    """The tentpole contract: the PrefetchLane is the same single FIFO
    staging consumer, so batch order is unchanged and K pipelined steps
    produce BITWISE the serial params + optimizer state over the same
    frame schedule (the RESUME_SOAK-style lockstep argument;
    scripts/ab_overlap.py runs the same proof on both transfer
    layouts)."""
    h_serial, _ = _run_arm("pf_par_ser", tmp_path, False, 3)
    h_pipe, learner = _run_arm("pf_par_pipe", tmp_path, True, 3)
    assert h_serial == h_pipe
    # lane torn down with the run; the staging probe stays attached and
    # reads "nothing held"
    assert learner._prefetch_lane is None
    assert learner.staging._prefetch_probe is not None
    assert not learner._prefetch_holding()


# ------------------------------------------------- drain through the lane


def test_sigterm_drain_trains_out_inflight_prefetch(tmp_path):
    """PR-7 zero-loss through the new station: a drain landing while the
    lane holds a prefetched batch TRAINS that batch (never drops it) and
    leaves only the sub-batch leftovers pending for the aux snapshot —
    consumed == trained rows + pending, exactly."""
    from dotaclient_tpu.runtime.learner import Learner

    mem.reset("pf_drain")
    broker = connect("mem://pf_drain")
    B = 8
    _feed(broker, 3 * B + 3)
    cfg = _cfg(
        "pf_drain",
        tmp_path,
        prefetch=True,
        checkpoint_dir=str(tmp_path / "ck"),
        ckpt=CkptConfig(full_state=True),
    )
    learner = Learner(cfg, connect("mem://pf_drain"))
    done = []
    t = threading.Thread(
        target=lambda: done.append(learner.run(num_steps=None, batch_timeout=30.0))
    )
    t.start()
    try:
        deadline = time.monotonic() + 30
        while learner.version < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert learner.version >= 1, "learner never trained a step"
        t_drain = time.monotonic()
        learner.request_drain()
        t.join(timeout=60)
        assert not t.is_alive(), "run() wedged under drain"
        # The quiesce fast-exit must fire THROUGH the lane: _get_ready's
        # drained() check uses include_prefetch=False because the waiter
        # IS the lane and its own mid-fetch flag would otherwise hold
        # the exit hostage for the full batch_timeout (review catch —
        # with batch_timeout=30 the drain took ~28s before the fix; the
        # k8s drain_budget_s=45 would have been blown at the production
        # batch_timeout=60). Generous bound: well under batch_timeout.
        assert time.monotonic() - t_drain < 15.0, "drain burned the batch timeout"
        # all three full batches trained (any of them may have been
        # in-flight in the lane when the drain landed), leftovers pend
        assert done and done[0] == 3
        stats = learner.staging.stats()
        assert learner.staging.drained()  # incl. the prefetch station
        assert stats["consumed"] == done[0] * B + stats["pending_rollouts"]
        assert stats["pending_rollouts"] == 3
        # and the leftovers are checkpointable (the aux-manifest path)
        snap = learner.staging.snapshot_state()
        assert snap is not None and len(snap["pending"]) == 3
        learner.drain_save()
    finally:
        learner.close()


def test_drained_false_while_lane_holds():
    """The prefetch station in isolation: staging.drained() must read
    False while the attached probe reports held frames, and the lane's
    own upstream check (include_prefetch=False) must ignore them."""
    from dotaclient_tpu.runtime.staging import StagingBuffer

    mem.reset("pf_station")
    cfg = LearnerConfig(batch_size=2, seq_len=4, policy=PolicyConfig(**POL))
    sb = StagingBuffer(cfg, connect("mem://pf_station"), version_fn=lambda: 0)
    holding = [True]
    sb.attach_prefetch_probe(lambda: holding[0])
    sb.quiesce()
    assert not sb.drained()  # the lane holds a batch downstream
    assert sb.drained(include_prefetch=False)  # upstream is empty
    holding[0] = False
    assert sb.drained()


# -------------------------------------------------- flag-off inertness


def test_prefetch_off_builds_no_lane(tmp_path, monkeypatch):
    """--learner.prefetch false: the serial loop never constructs a
    PrefetchLane (monkeypatch-proof), attaches no staging probe, and
    emits no pipeline_* scalars."""
    from dotaclient_tpu.runtime import learner as learner_mod

    class _Boom:
        def __init__(self, *a, **kw):
            raise AssertionError("PrefetchLane constructed with prefetch off")

    monkeypatch.setattr(learner_mod, "PrefetchLane", _Boom)
    mem.reset("pf_off")
    broker = connect("mem://pf_off")
    _feed(broker, 16)
    learner = learner_mod.Learner(
        _cfg("pf_off", tmp_path, prefetch=False), connect("mem://pf_off")
    )
    try:
        assert learner.staging._prefetch_probe is None
        steps = learner.run(num_steps=2, batch_timeout=60.0, max_idle=3)
    finally:
        learner.close()
    assert steps == 2
    recs = [
        json.loads(l)
        for l in (tmp_path / "pf_off" / "metrics.jsonl").read_text().splitlines()
    ]
    assert recs
    assert all(not any(k.startswith("pipeline_") for k in r) for r in recs)


@pytest.mark.slow  # full subprocess learner boot
def test_prefetch_off_subprocess_inertness(tmp_path):
    """Subprocess proof: a --learner.prefetch false learner runs with no
    'learner-prefetch' thread ever observed and logs no pipeline_*
    scalar — the serial rollback path is structurally the pre-ISSUE-15
    loop."""
    code = textwrap.dedent(
        f"""
        import json, os, sys, threading
        sys.path.insert(0, {REPO_ROOT!r})
        sys.path.insert(0, os.path.join({REPO_ROOT!r}, "tests"))
        import jax
        jax.config.update("jax_platforms", "cpu")
        from test_transport import make_rollout
        from dotaclient_tpu.config import LearnerConfig, PolicyConfig, PPOConfig
        from dotaclient_tpu.runtime.learner import Learner
        from dotaclient_tpu.transport.base import connect
        from dotaclient_tpu.transport.serialize import serialize_rollout

        seen = set()
        stop = False
        def sampler():
            while not stop:
                seen.update(t.name for t in threading.enumerate())
        th = threading.Thread(target=sampler, daemon=True)
        th.start()
        cfg = LearnerConfig(
            batch_size=8, seq_len=4,
            policy=PolicyConfig(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16,
                                dtype="float32"),
            broker_url="mem://pf_sub", log_dir={str(tmp_path / "sub")!r},
            metrics_every=1, ppo=PPOConfig(max_staleness=1_000_000),
        )
        cfg.learner.prefetch = False
        broker = connect("mem://pf_sub")
        for i in range(16):
            broker.publish_experience(serialize_rollout(
                make_rollout(L=4, H=8, version=0, seed=i, actor_id=i)))
        learner = Learner(cfg, connect("mem://pf_sub"))
        try:
            assert learner.run(num_steps=2, batch_timeout=60.0, max_idle=3) == 2
        finally:
            stop = True
            learner.close()
        assert "learner-prefetch" not in seen, sorted(seen)
        recs = [json.loads(l) for l in open(os.path.join({str(tmp_path / "sub")!r},
                                            "metrics.jsonl"))]
        assert all(not any(k.startswith("pipeline_") for k in r) for r in recs)
        print("INERT_OK")
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=300,
        env=clean_subprocess_env(),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "INERT_OK" in proc.stdout


# ------------------------------------------------ overlap phase accounting


def test_step_phase_timer_overlap_mode_unit():
    """StepPhaseTimer(overlap=True): lane sums live apart from the loop
    sums, phases still tile the wall, and the pipeline_* scalars carry
    the overlap arithmetic (ratio = share of lane work not exposed as
    loop take-wait)."""
    from dotaclient_tpu.obs.compute import StepPhaseTimer

    t = StepPhaseTimer(overlap=True)
    for _ in range(2):
        t.add("fetch", 0.1)  # loop lane: exposed take-wait
        t.add("device_step", 0.8)
        t.add("host", 0.1)
        t.add_overlap("fetch", 0.3)  # prefetch lane, hidden
        t.add_overlap("pack", 0.1)
        t.add_overlap("h2d", 0.1)
        t.step(1.0)
    sc = t.window_scalars()
    assert sc["compute_phase_wall_s"] == pytest.approx(1.0)
    phase_sum = sum(
        sc[f"compute_phase_{p}_s"] for p in StepPhaseTimer.PHASES
    )
    assert phase_sum == pytest.approx(1.0)  # tiles the wall
    assert sc["pipeline_prefetch_s"] == pytest.approx(0.5)
    assert sc["pipeline_prefetch_fetch_s"] == pytest.approx(0.3)
    assert sc["pipeline_device_idle_s"] == pytest.approx(0.1)
    # exposed 0.1 of 0.5 lane seconds -> 80% hidden
    assert sc["pipeline_overlap_ratio"] == pytest.approx(0.8)
    # reset cleared the lane sums too
    assert t.window_scalars()["pipeline_prefetch_s"] == 0.0


def test_pipelined_phases_tile_wall_and_emit_pipeline_family(tmp_path):
    """The satellite-1 acceptance: under the pipelined loop with
    step_phases on, compute_phase_* still tiles the wall (overlap mode,
    no per-step fence) and the pipeline_* lane family is emitted."""
    from dotaclient_tpu.runtime.learner import Learner

    mem.reset("pf_phases")
    broker = connect("mem://pf_phases")
    _feed(broker, 32)
    learner = Learner(
        _cfg("pf_phases", tmp_path, prefetch=True, obs=True), connect("mem://pf_phases")
    )
    try:
        assert learner.obs.compute.timer.overlap  # overlap mode armed
        steps = learner.run(num_steps=4, batch_timeout=60.0, max_idle=3)
    finally:
        learner.close()
    assert steps == 4
    recs = [
        json.loads(l)
        for l in (tmp_path / "pf_phases" / "metrics.jsonl").read_text().splitlines()
    ]
    last = recs[-1]
    phase_sum = sum(
        last[f"compute_phase_{p}_s"]
        for p in ("fetch", "pack", "h2d", "device_step", "host")
    )
    wall = last["compute_phase_wall_s"]
    assert wall > 0.0
    assert phase_sum <= wall * 1.05 + 1e-4
    assert phase_sum >= wall * 0.6
    for k in (
        "pipeline_prefetch_s",
        "pipeline_prefetch_fetch_s",
        "pipeline_prefetch_h2d_s",
        "pipeline_device_idle_s",
        "pipeline_overlap_ratio",
    ):
        assert k in last, k
    assert 0.0 <= last["pipeline_overlap_ratio"] <= 1.0


def test_pipeline_family_registered():
    """Registry pins for the new family: every pipeline_* scalar the
    pipelined loop emits resolves through the documented prefix."""
    from dotaclient_tpu.obs import registry

    for name in (
        "pipeline_prefetch_s",
        "pipeline_prefetch_fetch_s",
        "pipeline_prefetch_pack_s",
        "pipeline_prefetch_h2d_s",
        "pipeline_device_idle_s",
        "pipeline_overlap_ratio",
    ):
        assert registry.is_registered(name), name


# ------------------------------------------------------ nightly re-run


@pytest.mark.nightly  # full A/B re-run: two learners x two layouts + compiles
@pytest.mark.slow  # nightly-heavy must ALSO be slow (tier-1 -m override)
def test_overlap_ab_quick_all_green(tmp_path):
    """Nightly lane: re-run scripts/ab_overlap.py --quick and assert the
    same invariants hold live (on a capable host the probe re-arms the
    full 0.98 bar automatically)."""
    out = tmp_path / "OVERLAP_AB.json"
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO_ROOT, "scripts", "ab_overlap.py"),
            "--quick",
            "--out",
            str(out),
        ],
        capture_output=True,
        text=True,
        timeout=1800,
        cwd=REPO_ROOT,
        env=clean_subprocess_env(),
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    art = json.loads(out.read_text())
    assert art["verdict"]["all_green"] is True
    assert art["parity"]["all_identical"] is True
