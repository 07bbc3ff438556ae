"""End-to-end minimum slice (SURVEY.md §7 step 6): fake dotaservice →
actors → broker → staging → SPMD learner on the 8-virtual-device CPU
mesh → weight fanout → actor hot-swap."""

import asyncio
import threading

import numpy as np
import pytest

from dotaclient_tpu.config import ActorConfig, LearnerConfig, PolicyConfig
from dotaclient_tpu.env.fake_dotaservice import FakeDotaService
from dotaclient_tpu.env.service import serve
from dotaclient_tpu.runtime.actor import Actor
from dotaclient_tpu.runtime.learner import Learner
from dotaclient_tpu.transport import memory as mem
from dotaclient_tpu.transport.base import connect as broker_connect

SMALL = PolicyConfig(unit_embed_dim=16, lstm_hidden=16, mlp_hidden=16, dtype="float32")


@pytest.fixture()
def env_addr():
    server, port = serve(FakeDotaService(), max_workers=8)
    yield f"127.0.0.1:{port}"
    server.stop(0)


def run_actor_thread(cfg, broker_name, actor_id, stop_event):
    async def go():
        actor = Actor(cfg, broker_connect(f"mem://{broker_name}"), actor_id=actor_id)
        while not stop_event.is_set():
            await actor.run_episode()

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(go())
    except RuntimeError:
        pass  # loop shut down at stop
    finally:
        loop.close()


def test_e2e_slice(env_addr, tmp_path):
    broker_name = "e2e"
    mem.reset(broker_name)
    lcfg = LearnerConfig(
        batch_size=8,
        seq_len=8,
        policy=SMALL,
        mesh_shape="dp=-1",
        publish_every=1,
        metrics_every=1,  # one metrics line per step for the assertions below
        log_dir=str(tmp_path / "logs"),
    )
    acfg = ActorConfig(
        env_addr=env_addr,
        broker_url=f"mem://{broker_name}",
        rollout_len=8,
        max_dota_time=20.0,
        policy=SMALL,
        seed=1,
    )

    stop = threading.Event()
    actors = [
        threading.Thread(target=run_actor_thread, args=(acfg, broker_name, i, stop), daemon=True)
        for i in range(2)
    ]
    for t in actors:
        t.start()

    learner = Learner(lcfg, broker_connect(f"mem://{broker_name}"))
    try:
        steps = learner.run(num_steps=6, batch_timeout=120.0)
    finally:
        stop.set()
    assert steps == 6
    assert learner.version == 6

    # metrics jsonl written with reference scalar names
    import json

    lines = [json.loads(l) for l in open(tmp_path / "logs" / "metrics.jsonl")]
    assert len(lines) == 6
    for rec in lines:
        for key in ("loss", "policy_loss", "value_loss", "entropy", "grad_norm", "env_steps_per_sec"):
            assert key in rec and np.isfinite(rec[key]), key

    # staleness accounting: nothing should be stale in 6 steps with fanout
    stats = learner.staging.stats()
    assert stats["batches"] >= 6
    assert stats["consumed"] >= 6 * 8
    assert stats["consumer_errors"] == 0


def test_e2e_weights_reach_actor(env_addr):
    broker_name = "e2e_w"
    mem.reset(broker_name)
    lcfg = LearnerConfig(batch_size=8, seq_len=8, policy=SMALL, mesh_shape="dp=-1", publish_every=1)
    acfg = ActorConfig(
        env_addr=env_addr,
        broker_url=f"mem://{broker_name}",
        rollout_len=8,
        max_dota_time=15.0,
        policy=SMALL,
        seed=2,
    )
    learner = Learner(lcfg, broker_connect(f"mem://{broker_name}"))
    actor = Actor(acfg, broker_connect(f"mem://{broker_name}"), actor_id=0)

    async def interleave():
        # one actor feeding; learner steps in a thread
        t = threading.Thread(target=lambda: learner.run(num_steps=3, batch_timeout=120.0), daemon=True)
        t.start()
        while t.is_alive():
            await actor.run_episode()
        # one more episode to pick up the final published weights
        await actor.run_episode()
        return actor.version

    final_version = asyncio.new_event_loop().run_until_complete(interleave())
    assert learner.version == 3
    assert final_version == 3


def test_checkpoint_resume(tmp_path):
    import jax

    lcfg = LearnerConfig(
        batch_size=8,
        seq_len=4,
        policy=SMALL,
        mesh_shape="dp=-1",
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_every=2,
    )
    mem.reset("ck")
    learner = Learner(lcfg, broker_connect("mem://ck"))
    from dotaclient_tpu.parallel.train_step import make_train_batch
    from dotaclient_tpu.transport.serialize import serialize_rollout
    from tests.test_transport import make_rollout

    broker = broker_connect("mem://ck")
    for i in range(16):
        broker.publish_experience(serialize_rollout(make_rollout(L=4, H=16, version=0, seed=i)))
    learner.run(num_steps=2, batch_timeout=60.0)
    learner.checkpoint()
    if learner.checkpointer is not None:
        learner.checkpointer._mngr.wait_until_finished()
    params_before = jax.device_get(learner.state.params)

    # a fresh learner restores step counter and params
    learner2 = Learner(lcfg, broker_connect("mem://ck"))
    assert learner2.version == 2
    for a, b in zip(jax.tree.leaves(params_before), jax.tree.leaves(jax.device_get(learner2.state.params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_resume_transformer_family(tmp_path):
    """Orbax round-trips the transformer family's TrainState (different
    param tree than the LSTM): params and step counter restore exactly."""
    import jax

    tf_policy = PolicyConfig(
        arch="transformer",
        unit_embed_dim=16,
        lstm_hidden=16,
        mlp_hidden=16,
        dtype="float32",
        tf_layers=2,
        tf_heads=2,
        tf_context=5,
    )
    lcfg = LearnerConfig(
        batch_size=8,
        seq_len=4,
        policy=tf_policy,
        mesh_shape="dp=-1",
        checkpoint_dir=str(tmp_path / "ckpt_tf"),
        checkpoint_every=2,
    )
    mem.reset("ck_tf")
    learner = Learner(lcfg, broker_connect("mem://ck_tf"))
    from dotaclient_tpu.transport.serialize import serialize_rollout
    from tests.test_transport import make_rollout

    broker = broker_connect("mem://ck_tf")
    for i in range(16):
        broker.publish_experience(serialize_rollout(make_rollout(L=4, H=16, version=0, seed=i)))
    learner.run(num_steps=2, batch_timeout=60.0)
    learner.checkpoint()
    if learner.checkpointer is not None:
        learner.checkpointer._mngr.wait_until_finished()
    params_before = jax.device_get(learner.state.params)

    learner2 = Learner(lcfg, broker_connect("mem://ck_tf"))
    assert learner2.version == 2
    for a, b in zip(
        jax.tree.leaves(params_before), jax.tree.leaves(jax.device_get(learner2.state.params))
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_e2e_single_buffer_h2d(env_addr):
    """The ONE-u8-buffer H2D layout end-to-end: actors → broker →
    staging packing into the transfer buffer → bitcast-unpack train step. Three steps with
    finite losses prove the learner glue (transfer shardings, staged
    payload dispatch, step input) — the layout itself is bitwise-pinned
    in test_fused_io/test_native/test_staging."""
    broker_name = "e2e_single"
    mem.reset(broker_name)
    lcfg = LearnerConfig(
        batch_size=8, seq_len=8, policy=SMALL, mesh_shape="dp=-1",
        publish_every=1,
    )
    acfg = ActorConfig(
        env_addr=env_addr, broker_url=f"mem://{broker_name}",
        rollout_len=8, max_dota_time=20.0, policy=SMALL, seed=5,
    )
    stop = threading.Event()
    actors = [
        threading.Thread(target=run_actor_thread, args=(acfg, broker_name, i, stop), daemon=True)
        for i in range(2)
    ]
    for t in actors:
        t.start()
    learner = Learner(lcfg, broker_connect(f"mem://{broker_name}"))
    try:
        assert learner.fused_io is not None
        steps = learner.run(num_steps=3, batch_timeout=120.0)
    finally:
        stop.set()
    assert steps == 3 and learner.version == 3
    assert learner.staging.stats()["consumer_errors"] == 0
