"""Preemption-tolerant training (PR 7): transactional full-state
checkpoints (aux manifests), SIGTERM drain, version high-water
monotonicity, LearnerIncarnations, and the resume-soak wrapper.

The committed proof artifact is RESUME_SOAK.json (scripts/resume_soak.py
docstring); tier-1 here covers each mechanism in isolation plus the
real-signal subprocess drain, and the nightly wrapper re-runs the soak
--quick asserting the same verdict."""

import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from dotaclient_tpu.config import (
    LearnerConfig,
    PolicyConfig,
    PPOConfig,
    ReplayConfig,
    ObsConfig,
    WatchdogConfig,
)
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import connect
from dotaclient_tpu.transport.serialize import serialize_rollout
from tests.test_transport import make_rollout

ROOT = pathlib.Path(__file__).resolve().parent.parent
SMALL = PolicyConfig(unit_embed_dim=16, lstm_hidden=16, mlp_hidden=16, dtype="float32")


def _subprocess_env():
    """Env for child python processes: drop the 8-virtual-device flag
    (children pick their own count)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "").replace(
        " --xla_force_host_platform_device_count=8", ""
    )
    return env


def _cfg(tmp_path, name="ck", *, replay=False, async_save=False, obs=False, **kw):
    cfg = LearnerConfig(
        batch_size=8,
        seq_len=4,
        policy=SMALL,
        checkpoint_dir=str(tmp_path / name),
        checkpoint_every=kw.pop("checkpoint_every", 2),
        publish_every=1,
        metrics_every=1,
        **kw,
    )
    if replay:
        cfg.ppo = PPOConfig(max_staleness=4)
        cfg.replay = ReplayConfig(
            enabled=True, ratio=0.25, max_staleness=100_000, max_replays=0
        )
    if obs:
        cfg.obs = ObsConfig(
            enabled=True,
            install_handlers=False,
            step_phases=False,
            watchdog=WatchdogConfig(enabled=True, interval_s=5.0, stall_s=60.0),
        )
    cfg.ckpt.full_state = True
    cfg.ckpt.async_save = async_save
    return cfg


def _publish(broker, n, version, seed0=0, L=4, H=16):
    for i in range(n):
        broker.publish_experience(
            serialize_rollout(make_rollout(L=L, H=H, version=version, seed=seed0 + i))
        )


def _params_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ----------------------------------------------------------- reservoir


def test_reservoir_snapshot_restore_continues_rng_stream():
    """A restored reservoir is indistinguishable from the original:
    entries, priorities, use counts, AND the sampling RNG stream — the
    property the soak's bit-exact SIGTERM resume rides on."""
    from dotaclient_tpu.replay import ReplayReservoir

    rc = ReplayConfig(enabled=True, max_staleness=1000, byte_budget=1 << 20, max_replays=0)
    r1 = ReplayReservoir(rc, seed=7)
    for i in range(6):
        r1.offer(bytes([i]) * 100, version=i, priority=0.5 + i * 0.1, nbytes=100,
                 current_version=5)
    r1.sample(2, 8)  # advance the stream before the snapshot
    snap = r1.snapshot()
    r2 = ReplayReservoir(rc, seed=999)  # wrong seed on purpose: state must win
    assert r2.restore(snap) == 6
    assert r2.occupancy == r1.occupancy
    assert r2.occupancy_bytes == r1.occupancy_bytes
    draws1 = [[v for _, v, _ in r1.sample(2, 10)] for _ in range(6)]
    draws2 = [[v for _, v, _ in r2.sample(2, 10)] for _ in range(6)]
    assert draws1 == draws2
    # uses survived: sample() bumped them identically on both sides
    s1, s2 = r1.stats(), r2.stats()
    assert s1["occupancy"] == s2["occupancy"]


def test_reservoir_snapshot_preserves_compressed_entries():
    from dotaclient_tpu.replay import ReplayReservoir

    rc = ReplayConfig(
        enabled=True, max_staleness=1000, byte_budget=4000,
        spill_compress=True, spill_threshold=0.25, max_replays=0,
    )
    r1 = ReplayReservoir(rc, seed=1)
    for i in range(4):
        r1.offer(bytes(1000), version=i, priority=0.1 * (i + 1), nbytes=1000,
                 current_version=3)
    assert r1.stats()["spilled_entries"] > 0
    snap = r1.snapshot()
    r2 = ReplayReservoir(rc, seed=1)
    r2.restore(snap)
    payloads = sorted(p for p, _, _ in r2.sample(r2.occupancy, 5))
    assert all(p == bytes(1000) for p in payloads)  # decompresses intact


# ------------------------------------------------- staging snapshot/drain


def test_staging_snapshot_restore_preserves_pending_order(tmp_path):
    """Pending (popped-but-untrained) frames round-trip the aux snapshot
    in arrival order, ahead of new broker frames — the exact-batch
    contract the drain relies on."""
    from dotaclient_tpu.runtime.staging import StagingBuffer

    mem.reset("snapord")
    cfg = LearnerConfig(batch_size=8, seq_len=4, policy=SMALL)
    buf = StagingBuffer(cfg, connect("mem://snapord"))
    pub = connect("mem://snapord")
    _publish(pub, 5, 0)
    buf.start()
    deadline = time.monotonic() + 10
    while buf.stats()["pending_rollouts"] < 5 and time.monotonic() < deadline:
        time.sleep(0.02)
    snap = buf.snapshot_state()
    buf.stop()
    assert len(snap["pending"]) == 5

    mem.reset("snapord2")
    buf2 = StagingBuffer(cfg, connect("mem://snapord2"))
    counts = buf2.restore_state(snap)
    assert counts["pending"] == 5
    # restored frames must be byte-identical and in order
    enc = [bytes(buf2._item_encode(it)) for it in buf2._pending]
    assert enc == snap["pending"]


def test_drain_trains_out_staged_batches_then_preserves_leftovers(tmp_path):
    """request_drain(): intake stops, already-staged batches train out,
    run() returns, drain_save persists the sub-batch leftover — and a
    restored learner re-injects it (quick in-process version of the
    soak's SIGTERM leg)."""
    from dotaclient_tpu.runtime.learner import Learner

    mem.reset("drain")
    cfg = _cfg(tmp_path, "drain_ck")
    learner = Learner(cfg, connect("mem://drain"))
    pub = connect("mem://drain")
    stop_feed = threading.Event()

    def feeder():
        i = 0
        while not stop_feed.is_set():
            _publish(pub, 1, learner.version, seed0=i)
            i += 1
            time.sleep(0.002)

    th = threading.Thread(target=feeder, daemon=True)
    th.start()
    done = {}
    rt = threading.Thread(target=lambda: done.update(n=learner.run(batch_timeout=10.0)))
    rt.start()
    deadline = time.monotonic() + 120
    while learner.version < 3 and time.monotonic() < deadline:
        time.sleep(0.05)
    learner.request_drain()
    rt.join(timeout=30)
    assert not rt.is_alive(), "drain did not stop the loop"
    stop_feed.set()
    th.join(timeout=5)
    assert learner.staging.drained()
    learner.drain_save()
    ver = learner.version
    leftover = learner.staging.stats()["pending_rollouts"]
    assert done["n"] >= 3
    learner.close()

    restored = Learner(_cfg(tmp_path, "drain_ck"), connect("mem://drain"))
    assert restored.version == ver
    assert restored.resume_info["resume_pending_frames"] == leftover
    assert restored.staging.stats()["pending_rollouts"] == leftover
    restored.close()


# -------------------------------------------- full-state restore + hwm


def test_full_state_restore_bit_exact_with_reservoir_and_hwm_bump(tmp_path):
    """The soak's core mechanics in miniature: full checkpoint with live
    reservoir, params/opt restore bit-exactly, reservoir rehydrates, and
    a version high-water file ahead of the checkpoint bumps the restored
    counter (staleness stamps stay monotonic — never under-aged)."""
    from dotaclient_tpu.runtime.learner import Learner

    mem.reset("fullstate")
    cfg = _cfg(tmp_path, "fs_ck", replay=True)
    learner = Learner(cfg, connect("mem://fullstate"))
    pub = connect("mem://fullstate")
    for step in range(6):
        _publish(pub, 8, learner.version, seed0=step * 8)
        assert learner.run(num_steps=1, batch_timeout=30.0) == 1
    # stale frames -> reservoir (staleness 5 > ppo.max_staleness 4)
    _publish(pub, 3, 1, seed0=900)
    _publish(pub, 8, learner.version, seed0=950)
    assert learner.run(num_steps=1, batch_timeout=30.0) == 1
    assert learner.staging.stats()["replay_occupancy"] == 3
    learner.checkpoint(wait=True)
    params = jax.device_get(learner.state.params)
    opt = jax.device_get(learner.state.opt_state)
    saved_ver = learner.version
    # SIGKILL window emulation: the publisher got 5 more versions out
    learner.checkpointer.record_published_version(saved_ver + 5)
    learner.close()

    restored = Learner(_cfg(tmp_path, "fs_ck", replay=True), connect("mem://fullstate"))
    assert restored.version == saved_ver + 5, "hwm bump must win over the step label"
    info = restored.resume_info
    assert info["resume_version_hwm_bump"] == 5
    assert info["resume_restored_step"] == saved_ver
    assert info["resume_reservoir_entries"] == 3
    assert restored.staging.stats()["replay_occupancy"] == 3
    _params_equal(params, jax.device_get(restored.state.params))
    _params_equal(opt, jax.device_get(restored.state.opt_state))
    restored.close()


def test_async_checkpoint_worker_coalesces_and_close_drains(tmp_path):
    """CheckpointWorker is latest-wins (durability only needs the newest
    state) and Learner.close() drains — the final submitted step must be
    durable after close returns."""
    from dotaclient_tpu.runtime.learner import CheckpointWorker

    entered, release = threading.Event(), threading.Event()
    written = []

    def slow_save(host_state, version):
        entered.set()
        assert release.wait(timeout=30)
        written.append(version)

    w = CheckpointWorker(slow_save).start()
    w.submit({"s": 1}, 1)
    assert entered.wait(timeout=30)
    w.submit({"s": 2}, 2)
    w.submit({"s": 3}, 3)  # supersedes 2
    release.set()
    w.stop(flush=True)
    assert written == [1, 3]
    assert w.coalesced == 1
    assert w.saved == 2


def test_inertness_ckpt_defaults(tmp_path):
    """PR-6-style subprocess proof: with --ckpt defaults the checkpoint
    directory is byte-identical legacy (no aux manifests, no hwm file),
    chaos never imports, no SIGTERM handler, no async machinery."""
    spec = importlib.util.spec_from_file_location(
        "resume_soak", str(ROOT / "scripts" / "resume_soak.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    report = mod.run_part_c()
    assert report.get("ok"), report


def test_sigterm_drain_subprocess_exits_zero(tmp_path):
    """The REAL signal path: a learner process with drain_on_sigterm
    receives SIGTERM mid-training and must exit 0 with a durable
    full-state checkpoint inside the drain budget."""
    ckpt = tmp_path / "sig_ck"
    script = f"""
import os, threading, time
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
from dotaclient_tpu.config import LearnerConfig, PolicyConfig
from dotaclient_tpu.runtime.learner import Learner
from dotaclient_tpu.transport.base import connect
from dotaclient_tpu.transport.serialize import serialize_rollout
from tests.test_transport import make_rollout

cfg = LearnerConfig(batch_size=8, seq_len=4,
                    policy=PolicyConfig(unit_embed_dim=16, lstm_hidden=16, mlp_hidden=16, dtype="float32"),
                    checkpoint_dir={str(ckpt)!r}, checkpoint_every=100,
                    publish_every=1, metrics_every=1)
cfg.ckpt.full_state = True
cfg.ckpt.drain_on_sigterm = True
cfg.ckpt.drain_budget_s = 60.0
learner = Learner(cfg, connect("mem://sig"))
learner.install_drain_handler()
pub = connect("mem://sig")
stop = threading.Event()
def feeder():
    i = 0
    while not stop.is_set():
        pub.publish_experience(serialize_rollout(make_rollout(L=4, H=16, version=learner.version, seed=i)))
        i += 1
        time.sleep(0.002)
threading.Thread(target=feeder, daemon=True).start()
def killer():
    while learner.version < 2:
        time.sleep(0.05)
    os.kill(os.getpid(), __import__("signal").SIGTERM)
threading.Thread(target=killer, daemon=True).start()
learner.run(batch_timeout=10.0)
assert learner.drain_requested
learner.drain_save()
stop.set()
print("DRAINED_AT", learner.version)
learner.close()
"""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-c", script], cwd=str(ROOT), capture_output=True, text=True,
        timeout=300, env=_subprocess_env(),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "DRAINED_AT" in proc.stdout
    version = int(proc.stdout.split("DRAINED_AT")[1].split()[0])
    assert version >= 2
    # the drained step is durable WITH its aux manifest
    files = os.listdir(ckpt)
    assert f"aux_{version}.bin" in files, files
    assert str(version) in files
    assert time.monotonic() - t0 < 300


def test_watchdog_boot_grace_survives_full_state_restore(tmp_path):
    """PR-3 regression, extended to full-state restores: the version
    writes a restore performs (step restore AND the hwm bump, now with
    reservoir rehydration in between) land before the watchdog attaches,
    so they must read as the counter's starting point — boot grace holds
    and a slow post-restore first step cannot crashloop the pod."""
    from dotaclient_tpu.obs.watchdog import Watchdog
    from dotaclient_tpu.runtime.learner import Learner

    mem.reset("wdres")
    cfg = _cfg(tmp_path, "wd_ck", replay=True, obs=True)
    learner = Learner(cfg, connect("mem://wdres"))
    pub = connect("mem://wdres")
    for step in range(6):
        _publish(pub, 8, learner.version, seed0=step * 8)
        learner.run(num_steps=1, batch_timeout=30.0)
    _publish(pub, 3, 1, seed0=700)
    _publish(pub, 8, learner.version, seed0=750)
    learner.run(num_steps=1, batch_timeout=30.0)
    learner.checkpoint(wait=True)
    learner.checkpointer.record_published_version(learner.version + 4)
    learner.close()

    restored = Learner(_cfg(tmp_path, "wd_ck", replay=True, obs=True), connect("mem://wdres"))
    assert restored.resume_info["resume_reservoir_entries"] == 3
    assert restored.resume_info["resume_version_hwm_bump"] == 4
    assert restored.obs is not None and restored.obs.watchdog is not None
    # Drive a fake-clock watchdog wired exactly like the learner's: the
    # restored (bumped) version is the baseline, never a heartbeat.
    clock = {"t": 1000.0}
    wd = Watchdog(
        WatchdogConfig(enabled=True, stall_s=10.0, boot_grace_s=300.0),
        restored.metrics.latest,
        lambda: restored.version,
        time_fn=lambda: clock["t"],
        latest_seq_fn=restored.metrics.latest_step,
    )
    clock["t"] += 60.0  # way past stall_s, inside boot grace, no step yet
    wd.check()
    assert not wd.tripped and wd.strikes == 0, wd.reasons
    restored.close()


# ------------------------------------------------------ soak wrappers


def test_committed_resume_soak_verdicts_hold():
    """The committed artifact's verdict must be all-green — a regression
    that flips one shows up as a tier-1 diff, not a stale JSON."""
    art = json.loads((ROOT / "RESUME_SOAK.json").read_text())
    bad = {k: v for k, v in art["verdict"].items() if isinstance(v, bool) and not v}
    assert not bad, bad
    assert art["part_a_determinism"]["sigterm"]["bit_exact_param_opt_hashes"] is True
    assert art["part_c_inertness"]["ok"] is True


@pytest.mark.nightly
@pytest.mark.slow
def test_resume_soak_quick_all_green(tmp_path):
    """Nightly: re-run the soak at --quick scale and hold the same
    verdict (marked slow too: heavy nightly tests must stay out of a
    `-m 'not slow'` tier-1 run — the marker-override gotcha)."""
    out = tmp_path / "RESUME_SOAK_quick.json"
    proc = subprocess.run(
        [sys.executable, "scripts/resume_soak.py", "--quick", "--out", str(out)],
        cwd=str(ROOT),
        capture_output=True,
        text=True,
        timeout=560,
        env=_subprocess_env(),
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    verdict = json.loads(out.read_text())["verdict"]
    bad = {k: v for k, v in verdict.items() if isinstance(v, bool) and not v}
    assert not bad, bad
