"""Fused single-buffer H2D path: pack/unpack roundtrip, metric parity
with the per-leaf tree path, dp shardability, the sp exclusion, and the
Learner's choice between the fused layout and the tree."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dotaclient_tpu.config import LearnerConfig, PolicyConfig
from dotaclient_tpu.parallel import mesh as mesh_lib
from dotaclient_tpu.parallel.fused_io import FusedBatchIO
from dotaclient_tpu.parallel.train_step import (
    build_single_train_step,
    build_train_step,
    init_train_state,
    make_train_batch,
)
from dotaclient_tpu.runtime.staging import cast_obs_to_compute_dtype


def _cfg(aux=False, dtype="float32", **kw):
    return LearnerConfig(
        batch_size=8,
        seq_len=8,
        policy=PolicyConfig(
            unit_embed_dim=16, lstm_hidden=16, mlp_hidden=16, dtype=dtype, aux_heads=aux
        ),
        **kw,
    )


def _sp_cfg():
    """A transformer learner over a dp x sp mesh: seq_len+1 = 8 frames
    divide by sp=4."""
    return LearnerConfig(
        batch_size=8,
        seq_len=7,
        mesh_shape="dp=2,sp=4",
        policy=PolicyConfig(arch="transformer", tf_sp_axis="sp", tf_context=8,
                            unit_embed_dim=16, lstm_hidden=16, mlp_hidden=16, tf_heads=4),
    )


def _host_batch(cfg, seed=0):
    return cast_obs_to_compute_dtype(cfg, jax.tree.map(np.asarray, make_train_batch(cfg, seed)))


class TestRoundtrip:
    @pytest.mark.parametrize("aux", [False, True])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_pack_unpack_identity(self, aux, dtype):
        cfg = _cfg(aux=aux, dtype=dtype)
        mesh = mesh_lib.make_mesh("dp=-1")
        batch = _host_batch(cfg)
        io = FusedBatchIO(batch, mesh)
        # the fallback pack _fetch_next takes when staging hands over a
        # dense batch
        buf = io.pack_transfer(batch)
        assert buf.shape == (cfg.batch_size, io.row_bytes) and buf.dtype == np.uint8
        # bf16-staged configs carry 4 segments; pure-f32 configs 3
        assert set(io.seg_off) == ({"f32", "i32", "u8", "bf16"} if dtype == "bfloat16" else {"f32", "i32", "u8"})
        out = jax.jit(io.unpack_single)(buf)
        in_leaves, in_def = jax.tree.flatten(batch)
        out_leaves, out_def = jax.tree.flatten(out)
        assert in_def == out_def
        for a, b in zip(in_leaves, out_leaves):
            assert a.shape == b.shape and np.dtype(a.dtype) == np.dtype(b.dtype)
            np.testing.assert_array_equal(np.asarray(b), a)

    def test_non_batch_leading_leaf_rejected(self):
        cfg = _cfg()
        mesh = mesh_lib.make_mesh("dp=-1")
        batch = _host_batch(cfg)
        bad = batch._replace(mask=batch.mask[:4])
        with pytest.raises(ValueError, match="batch-leading"):
            FusedBatchIO(bad, mesh)


class TestLearnerLayout:
    @pytest.mark.parametrize("case", ["default", "sp", "replay"])
    def test_layout_is_chosen_from_mesh_and_replay(self, case):
        """No flag: the fused layout unless the mesh is sequence-parallel
        or the replay reservoir is on, where the per-leaf tree runs."""
        from dotaclient_tpu.config import ReplayConfig
        from dotaclient_tpu.runtime.learner import Learner
        from dotaclient_tpu.transport import memory as mem
        from dotaclient_tpu.transport.base import connect

        cfg = {
            "default": _cfg,
            "sp": _sp_cfg,
            "replay": lambda: _cfg(replay=ReplayConfig(enabled=True)),
        }[case]()
        mem.reset(f"layout_{case}")
        learner = Learner(cfg, connect(f"mem://layout_{case}"))
        try:
            if case == "default":
                assert learner.fused_io is not None and learner.batch_sharding is None
            else:
                assert learner.fused_io is None and learner.batch_sharding is not None
        finally:
            learner.close()


class TestSingleBuffer:
    @pytest.mark.parametrize("aux", [False, True])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_alloc_fill_unpack_roundtrip_bitwise(self, aux, dtype):
        """Fill the single-buffer leaf views from a reference batch, jit
        unpack_single the u8 buffer, require bitwise equality — pins the
        byte-segment layout AND the bitcast byte order."""
        cfg = _cfg(aux=aux, dtype=dtype)
        mesh = mesh_lib.make_mesh("dp=-1")
        batch = _host_batch(cfg)
        io = FusedBatchIO(batch, mesh)
        buf, views = io.alloc_transfer()
        assert buf.shape == (cfg.batch_size, io.row_bytes) and buf.dtype == np.uint8
        for v, ref in zip(jax.tree.leaves(views), jax.tree.leaves(batch)):
            v[...] = ref
        out = jax.jit(io.unpack_single)(buf)
        in_leaves, in_def = jax.tree.flatten(batch)
        out_leaves, out_def = jax.tree.flatten(out)
        assert in_def == out_def
        for a, b in zip(in_leaves, out_leaves):
            assert a.shape == b.shape and np.dtype(a.dtype) == np.dtype(b.dtype)
            np.testing.assert_array_equal(
                np.ascontiguousarray(np.asarray(a)).view(np.uint8),
                np.ascontiguousarray(np.asarray(b)).view(np.uint8),
            )

    def test_segment_alignment(self):
        cfg = _cfg(dtype="bfloat16")
        mesh = mesh_lib.make_mesh("dp=-1")
        io = FusedBatchIO(_host_batch(cfg), mesh)
        for key, off in io.seg_off.items():
            itemsize = {"f32": 4, "i32": 4, "bf16": 2, "u8": 1}[key]
            assert off % itemsize == 0, (key, off)
        assert io.row_bytes % 4 == 0

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_single_step_metrics_match_tree_path(self, dtype):
        """The single-buffer step computes the identical function."""
        cfg = _cfg(aux=True, dtype=dtype)
        mesh = mesh_lib.make_mesh("dp=2,tp=4")
        batch = _host_batch(cfg)

        tree_step, state_sh, batch_sh = build_train_step(cfg, mesh)
        state0 = jax.device_put(init_train_state(cfg, jax.random.PRNGKey(0)), state_sh)
        _, m_tree = tree_step(state0, jax.device_put(batch, batch_sh))

        single_step, state_sh2, io = build_single_train_step(cfg, mesh)
        state1 = jax.device_put(init_train_state(cfg, jax.random.PRNGKey(0)), state_sh2)
        buf = io.pack_transfer(batch)
        _, m_single = single_step(state1, jax.device_put(buf, io.sharding))
        # Input bits are identical (the roundtrip test is bitwise); the
        # residual is bf16 fusion-order noise between two different XLA
        # programs (~5e-5 observed on the CPU backend). A layout bug
        # would produce garbage, not 1e-4-scale drift.
        for k in m_tree:
            np.testing.assert_allclose(
                np.asarray(m_single[k]), np.asarray(m_tree[k]), rtol=1e-4, atol=1e-5
            ), k

    def test_buffer_shards_over_dp(self):
        cfg = _cfg()
        mesh = mesh_lib.make_mesh("dp=8")
        _, _, io = build_single_train_step(cfg, mesh)
        buf = jax.device_put(io.pack_transfer(_host_batch(cfg)), io.sharding)
        assert len(buf.sharding.device_set) == 8
        # leading (batch) axis split 8 ways, rows whole
        shard_shapes = {s.data.shape for s in buf.addressable_shards}
        assert shard_shapes == {(cfg.batch_size // 8, io.row_bytes)}

    def test_refused_under_sequence_parallelism(self):
        cfg = _sp_cfg()
        mesh = mesh_lib.make_mesh(cfg.mesh_shape)
        with pytest.raises(ValueError, match="sequence parallelism"):
            build_single_train_step(cfg, mesh)
