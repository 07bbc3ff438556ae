"""The learner loop and its prefetch lane: bitwise parity of
`Learner.run` with the same batches stepped by hand through the per-leaf
tree step, the zero-loss drain contract through the prefetch station,
the phase accounting, and the refusal of the flags that went with the
serial loop and the grouped layout."""

import json
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

from test_transport import make_rollout

POL = dict(unit_embed_dim=16, lstm_hidden=8, mlp_hidden=16, dtype="float32")


def _cfg(name, tmp_path, obs=False, dtype="float32", **kw):
    return LearnerConfig(
        batch_size=8,
        seq_len=4,
        policy=PolicyConfig(**{**POL, "dtype": dtype}),
        broker_url=f"mem://{name}",
        log_dir=str(tmp_path / name),
        metrics_every=2,
        ppo=PPOConfig(max_staleness=1_000_000),
        obs=ObsConfig(enabled=obs, install_handlers=False),
        **kw,
    )


def _frames(n, seed0=0):
    return [
        serialize_rollout(make_rollout(L=4, H=8, version=0, seed=seed0 + i, actor_id=i))
        for i in range(n)
    ]


def _feed(broker, n, seed0=0):
    for frame in _frames(n, seed0):
        broker.publish_experience(frame)


def _state_hash(state):
    import hashlib

    h = hashlib.sha256()
    for leaf in jax.tree.leaves(jax.device_get((state.params, state.opt_state))):
        h.update(np.ascontiguousarray(leaf).tobytes())
    return h.hexdigest()


# ------------------------------------------------------- bitwise parity


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_loop_matches_hand_stepped_tree_path(tmp_path, dtype):
    """The loop's contract, against a reference that shares none of it:
    the lane is the single FIFO staging consumer, so N steps of
    `Learner.run` (prefetch lane, fused u8 buffer, unpack inside the
    step) leave BITWISE the params + optimizer state of the same frames,
    batched in arrival order by hand and stepped through
    `build_train_step` (dense leaves, per-leaf device_put, no loop)."""
    from dotaclient_tpu.ops.batch import zeros_train_batch
    from dotaclient_tpu.parallel.train_step import build_train_step, init_train_state
    from dotaclient_tpu.runtime.learner import Learner
    from dotaclient_tpu.runtime.staging import cast_obs_to_compute_dtype, fill_rollouts
    from dotaclient_tpu.transport.serialize import deserialize_rollout

    name, steps = f"pf_par_{dtype}", 3
    mem.reset(name)
    cfg = _cfg(name, tmp_path, dtype=dtype)
    B, T = cfg.batch_size, cfg.seq_len
    frames = _frames(B * steps)
    broker = connect(f"mem://{name}")
    for frame in frames:
        broker.publish_experience(frame)
    learner = Learner(cfg, connect(f"mem://{name}"))
    try:
        assert learner.fused_io is not None
        assert learner.run(num_steps=steps, batch_timeout=60.0, max_idle=3) == steps
        h_loop = _state_hash(learner.state)
        # lane torn down with the run; the staging probe stays attached
        # and reads "nothing held"
        assert learner._prefetch_lane is None
        assert learner.staging._prefetch_probe is not None
        assert not learner._prefetch_holding()
    finally:
        learner.close()

    step, state_sh, batch_sh = build_train_step(cfg, learner.mesh)
    state = jax.device_put(init_train_state(cfg, jax.random.PRNGKey(cfg.seed)), state_sh)
    for i in range(steps):
        batch = zeros_train_batch(B, T, cfg.policy.lstm_hidden, cfg.policy.aux_heads)
        fill_rollouts(batch, [deserialize_rollout(f) for f in frames[B * i : B * (i + 1)]], T)
        state, _ = step(state, jax.device_put(cast_obs_to_compute_dtype(cfg, batch), batch_sh))
    assert _state_hash(state) == h_loop


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


# ------------------------------------------------------ removed flags


@pytest.mark.parametrize(
    "flag",
    ["--learner.prefetch", "--learner.prefetch_depth", "--fused_h2d", "--fused_single_h2d"],
)
def test_removed_flags_are_refused(flag, capsys):
    """A manifest that still passes a flag of the serial loop or the
    grouped layout fails at boot, naming the flag — not silently."""
    from dotaclient_tpu.config import parse_config

    with pytest.raises(SystemExit) as e:
        parse_config(LearnerConfig(), [flag, "1"])
    assert e.value.code == 2
    assert f"unrecognized arguments: {flag}" in capsys.readouterr().err


# -------------------------------------------------------- phase accounting


def test_step_phase_timer_overlap_mode_unit():
    """StepPhaseTimer: lane sums live apart from the loop
    sums, phases still tile the wall, and the pipeline_* scalars carry
    the overlap arithmetic (ratio = share of lane work not exposed as
    loop take-wait)."""
    from dotaclient_tpu.obs.compute import StepPhaseTimer

    t = StepPhaseTimer()
    for _ in range(2):
        t.add("fetch", 0.1)  # loop lane: exposed take-wait
        t.add("device_step", 0.8)
        t.add("host", 0.1)
        t.add_lane("fetch", 0.3)  # prefetch lane, hidden
        t.add_lane("pack", 0.1)
        t.add_lane("h2d", 0.1)
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
    """With step_phases on, compute_phase_* tiles the wall (no per-step
    fence) and the pipeline_* lane family is emitted."""
    from dotaclient_tpu.runtime.learner import Learner

    mem.reset("pf_phases")
    broker = connect("mem://pf_phases")
    _feed(broker, 32)
    learner = Learner(
        _cfg("pf_phases", tmp_path, obs=True), connect("mem://pf_phases")
    )
    try:
        assert learner.obs.compute.timer is not None
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
    """Registry pins: every pipeline_* scalar the loop emits resolves
    through the documented prefix."""
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
