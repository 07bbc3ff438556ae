"""Ask the TPU's compiler, without a chip: the kernels and steps of the
main path at their real sizes, compiled for a described `v5e:2x2`
topology (libtpu ships the compiler; nothing executes, so this says
nothing about results or times — a compile that passes is not a chip
run). It refuses what the chip would refuse: a Mosaic kernel the
partitioner cannot split, a slice off the tiling, a program that does
not fit. The file took 40 s until it compiled a whole cell's step (PR
36: 100 s more, the one guard that the 513M-parameter step fits a chip).

The code under test sees `jax.default_backend() == "cpu"` here; what it
is compiled FOR comes from the described devices handed to it (a mesh
for the train steps, argument shardings for the rest).
"""

import dataclasses
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from dotaclient_tpu.config import ActorConfig, LearnerConfig, PolicyConfig
from dotaclient_tpu.env import featurizer as F
from dotaclient_tpu.models import policy as P
from dotaclient_tpu.ops import lstm as L
from dotaclient_tpu.parallel import mesh as mesh_lib
from dotaclient_tpu.parallel.train_step import build_single_train_step, init_train_state
from dotaclient_tpu.runtime.actor import make_batched_actor_step

HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever libtpu raises where it cannot describe one
        pytest.skip(f"no v5e:2x2 topology description here: {type(e).__name__}: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described TPU is written to the persistent cache
    but cannot be read back without a chip: the next run would warn and
    compile again. Keep these compiles out of it."""
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    """Shapes of `tree` placed by `sharding` (one sharding, or a
    matching tree of them) — described devices hold no arrays."""
    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree, sharding
        )
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def _fits(compiled) -> bool:
    m = compiled.memory_analysis()
    return (
        m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes < HBM_BYTES
    )


# (B, T, H): the learner's unroll with and without the bootstrap frame,
# and the one-step shapes of an actor row and a small tick.
@pytest.mark.parametrize("B,T,H", [(256, 17, 128), (256, 16, 128), (1, 1, 128), (8, 1, 128)])
def test_lstm_kernel_forward_and_vjp_compile(topo, B, T, H):
    one = SingleDeviceSharding(topo.devices[0])
    args = _on(
        one,
        (
            jax.ShapeDtypeStruct((B, T, 4 * H), jnp.bfloat16),
            jax.ShapeDtypeStruct((H, 4 * H), jnp.bfloat16),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
        ),
    )

    def forward(x_proj, w_h, c0, h0):
        return L.lstm_recurrence(x_proj, w_h, c0, h0, impl="pallas")

    def loss(*a):
        h_seq, (c_T, h_T) = forward(*a)
        return jnp.sum(h_seq) + jnp.sum(c_T) + jnp.sum(h_T)

    for fn in (forward, jax.grad(loss, argnums=(0, 1, 2, 3))):
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()


def _compile_train_step(cfg: LearnerConfig, devices):
    mesh = mesh_lib.make_mesh(cfg.mesh_shape, devices=devices)
    step, state_shardings, io = build_single_train_step(cfg, mesh)
    state = jax.eval_shape(lambda: init_train_state(cfg, jax.random.PRNGKey(0)))
    payload, _ = io.alloc_transfer()
    return step.lower(
        _on(state_shardings, state), _on(io.sharding, payload)
    ).compile()


@pytest.mark.parametrize("n_devices", [1, 4])
def test_flagship_train_step_compiles_with_the_kernel(topo, n_devices):
    """LearnerConfig() as it stands — 256x16, H=128, bf16, lstm_impl
    "auto", mesh "dp=-1" — on one described chip and on all four. "auto"
    must find the kernel from the mesh's devices, and on dp=4 the kernel
    must survive partitioning (it is shard_mapped over dp)."""
    compiled = _compile_train_step(LearnerConfig(), topo.devices[:n_devices])
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("all-reduce" in text) == (n_devices > 1)
    assert _fits(compiled)


def test_explicit_scan_compiles_without_the_kernel(topo):
    """What was asked is what runs: lstm_impl="scan" on a TPU mesh puts
    no Mosaic kernel into the program."""
    cfg = LearnerConfig(policy=PolicyConfig(lstm_impl="scan"))
    assert "tpu_custom_call" not in _compile_train_step(cfg, topo.devices).as_text()


def test_serve_tick_compiles_at_default_capacity(topo):
    """The batched serve tick at serve.max_batch (16): `lax.map` over
    [1, ...] rows — on a TPU one program holding a sequential loop of 16
    one-row steps, none of them the Pallas kernel (single steps run the
    gate math inline)."""
    from dotaclient_tpu.config import ServeConfig

    one = SingleDeviceSharding(topo.devices[0])
    cfg = ActorConfig()
    M = ServeConfig().max_batch
    rows = lambda x: jax.ShapeDtypeStruct((M, 1) + np.shape(x), np.asarray(x).dtype)
    params = jax.eval_shape(lambda: P.init_params(cfg.policy, jax.random.PRNGKey(0)))
    H = cfg.policy.lstm_hidden
    state = (jax.ShapeDtypeStruct((M, 1, H), jnp.float32),) * 2
    obs = jax.tree.map(rows, F.zeros_observation())
    rngs = jax.ShapeDtypeStruct((M, 2), jnp.uint32)
    compiled = (
        make_batched_actor_step(cfg).lower(*_on(one, (params, state, obs, rngs))).compile()
    )
    text = compiled.as_text()
    assert " while(" in text and "tpu_custom_call" not in text
    assert _fits(compiled)


def test_transformer_train_step_compiles(topo):
    """The second family builds for the chip too (the smoke does not run
    it): --policy.arch transformer --seq_len 127 --policy.tf_context 128
    on one device."""
    cfg = LearnerConfig(seq_len=127, policy=PolicyConfig(arch="transformer", tf_context=128))
    assert _fits(_compile_train_step(cfg, topo.devices[:1]))


@pytest.mark.parametrize("impl", ["megablox", "ragged_dot"])
def test_expert_layer_compiles_at_published_widths(topo, impl):
    """The routed-expert layer of the benchmark's transformer cell, forward
    and backward at its real size (16,384 frames of 2,304, 16 of 64 experts
    of 896 held, 8 a frame) in its buffer of 40,960 rows, with either
    grouped product: a kernel on the chip (twelve to a pass: forward, and
    in the backward rule the forward again and its transpose; the bare
    pass and the loop's turn each hold them), the choice between the two
    and the loop on the device, no row scattered, and with both in it the
    scratch well under what the buffer of every pair took (2.66 GB)."""
    from dotaclient_tpu.ops import moe

    one = SingleDeviceSharding(topo.devices[0])
    frames, D, E, held, I, K = 16384, 2304, 64, 16, 896, 8
    rows = moe.buffer_rows(frames * K, held, E)
    assert rows == 40960

    def loss(x, wr, wg, wu, wd):
        y, sizes, _ = moe.expert_layer(x.astype(jnp.bfloat16), moe.route(moe.router_scores(x, wr), K),
                                       wg, wu, wd, 0, impl, rows)
        return jnp.sum(y * y), sizes

    shapes = [(frames, D), (D, E), (held, D, I), (held, D, I), (held, I, D)]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one) for s in shapes]
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 24 and " conditional(" in text and " while(" in text
    # no row of frames or of pairs is scattered (the kernel's own tile table, a few hundred integers, is)
    assert not [ln for ln in text.splitlines()
                if " scatter(" in ln and any(n in ln for n in ("16384", "40960", "131072"))]
    assert ("ragged-dot" in text) == (impl == "ragged_dot")
    assert _fits(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 1024**3


def test_the_expert_matrices_update_copies_nothing_of_their_shape(topo):
    """`ExpertLayer` under `jax.checkpoint` with the repo's optimizer, at
    a size that compiles in seconds: the products' backward kernel writes
    an expert matrix's gradient by expert, and `moe.by_expert` hands it on
    in the parameter's own layout. Without that the update runs in the
    gradient's order and copies each of its outputs back (parameter and
    both moments: copies that carry no `op_name`, so no layer's time)."""
    from dotaclient_tpu.models.transformer_policy import ExpertLayer
    from dotaclient_tpu.parallel.train_step import make_optimizer

    one = SingleDeviceSharding(topo.devices[0])
    cfg = LearnerConfig(policy=PolicyConfig(
        arch="transformer", lstm_hidden=256, dtype="bfloat16", moe_experts=8, moe_experts_held=4,
        moe_top_k=2, moe_hidden=128, moe_impl="megablox"))
    layer, tx = ExpertLayer(cfg.policy, "tpu"), make_optimizer(cfg)
    h = jax.ShapeDtypeStruct((4, 512, 256), jnp.float32)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros(h.shape, h.dtype)))
    opt_state = jax.eval_shape(tx.init, params)

    def step(params, opt_state, h):
        loss = lambda p: jnp.sum(jax.checkpoint(lambda p, h: layer.apply(p, h)[0])(p, h) ** 2)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(jax.grad(loss)(params), opt_state, params)
        return jax.tree.map(jnp.add, params, updates), opt_state

    text = jax.jit(step, donate_argnums=(0, 1)).lower(*_on(one, (params, opt_state, h))).compile().as_text()
    assert text.count("tpu_custom_call") >= 9
    unnamed = [ln.strip()[:160] for ln in text.splitlines()
               if re.search(r"= f32\[(256,4,128|128,4,256)\]\S* copy\(", ln) and "op_name" not in ln]
    assert not unnamed, unnamed


@pytest.mark.parametrize("cell,kind,scope", [
    ("learner-glm47flash-ep8-wire", "latent", "attn_latent"),
    ("learner-mellum2-ep4-wire", "sliding", "attn_window"),
])
def test_attention_moves_no_part_of_a_head(topo, cell, kind, scope):
    """One block of the cell's attention shapes (4 rows of 4,096 frames;
    its feed-forward part a SwiGLU of 256, so that it compiles in 15 s),
    forward and backward under `nn.remat` with the step's policy: between
    the projections and the fused kernel, and in their mirror, no array of
    activations is sliced, padded, concatenated or copied along a head's
    width. The split construction left 26 such operations in the latent
    block and 20 in the sliding one (halves of 64 and 32 lanes, 192 and 64
    of q's 256, 192 of kv_b's 448), each a pass over every frame's heads
    that no product hides (PERF.md, PR 37)."""
    from flax import linen as nn

    from benchmark import cells, harness
    from dotaclient_tpu.models.transformer_policy import Block
    from dotaclient_tpu.ops import attention as A

    one = SingleDeviceSharding(topo.devices[0])
    policy = harness.learner_config(cells.load_cell(cells.load_benchmark(), cell), seed=0, broker_url="mem://x").policy
    assert policy.tf_remat
    cfg = dataclasses.replace(policy, moe_experts=0, moe_experts_held=0, moe_shared_hidden=0, tf_dense_layers=0,
                              tf_mlp_act="swiglu", tf_mlp_hidden=256)
    B, T, D = 4, 4096, cfg.lstm_hidden
    keep = jax.checkpoint_policies.save_only_these_names(A.FUSED_RESIDUALS)
    block = nn.remat(Block, policy=keep)(cfg, kind, None, "tpu", True, False)
    positions = lambda: jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = jax.ShapeDtypeStruct((B, T, D), jnp.float32)
    params = jax.eval_shape(lambda: block.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype), positions()))

    def loss(params, x):
        return jnp.sum(block.apply(params, x, positions())[0] ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*_on(one, (params, x))).compile().as_text()
    part_of_a_head = re.compile(
        r"= (bf16|f32)\[4,(4096,(20|32|4)|(20|32|4),4096),(32|64|192|448)\]\S* (slice|dynamic-slice|pad|concatenate|copy)\(")
    found = [ln.strip()[:200] for ln in text.splitlines() if part_of_a_head.search(ln)]
    assert not found, found
    kernels = [n for n in re.findall(r'op_name="([^"]*)"', text) if n.endswith("pallas_call")]
    assert sum("splash_mha_fwd" in n for n in kernels) >= 1 and sum("splash_mha_dkv" in n for n in kernels) >= 1
    assert all(f"/{scope}/" in n for n in kernels), kernels


@pytest.mark.parametrize("n_devices", [1, 4])
def test_fused_attention_compiles_at_the_cells_shapes(topo, n_devices):
    """The fused attention kernel as the learner's unroll calls it, forward
    and backward at the transformer cell's shapes (4 rows of 4,096 frames,
    32 query heads on 4 of 128, window 1,024), on one described chip and
    mapped over dp=4: two kernels (the forward and the one backward
    kernel), each with the layer's scope in its `op_name`, no row gathered
    across chips, and the whole fits."""
    from jax.sharding import NamedSharding, PartitionSpec

    from dotaclient_tpu.ops import ring_attention as RA

    mesh = mesh_lib.make_mesh("dp=-1", devices=topo.devices[:n_devices])
    B, T, N, G, Dh = 4, 4096, 32, 4, 128
    assert RA.fused_applies("tpu", (B, T, N, Dh), (B, T, G, Dh), 256, mesh=mesh)

    def loss(q, k, v):
        with jax.named_scope("attn_window"):
            out = RA.attend(q, k, v, None, None, mesh=mesh, kv_block=256, window=1024, fused=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    rows = NamedSharding(mesh, PartitionSpec("dp"))
    args = [jax.ShapeDtypeStruct((B, T, h, Dh), jnp.bfloat16, sharding=rows) for h in (N, G, G)]
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    kernels = [n for n in re.findall(r'op_name="([^"]*)"', text) if n.endswith("pallas_call")]
    assert len(kernels) >= 2 and all("attn_window" in n for n in kernels), kernels
    assert "all-gather" not in text
    assert _fits(compiled)


def test_fused_attention_takes_heads_of_256(topo):
    """The fused kernel at the latent-attention cell's expanded shapes (4
    rows of 4,096 frames, 20 heads of 256 with keys and values of their
    own), forward and backward on one described chip: the tiles
    `fused_tiles` gives a width of 256 (1,024 with 256 keys at a time) fit
    the kernel's fast memory, where Mellum2's (512 keys at a time) do not,
    and Mellum2's shapes keep the tiles they had."""
    from dotaclient_tpu.ops import attention as A
    from dotaclient_tpu.ops import ring_attention as RA

    one = SingleDeviceSharding(topo.devices[0])
    B, T, N, Dh = 4, 4096, 20, 256
    assert A.fused_takes(T, N, N, Dh) and RA.fused_applies("tpu", (B, T, N, Dh), (B, T, N, Dh), 256)
    assert A.fused_tiles(T, 0, Dh) == (1024, 256)
    assert A.fused_tiles(T, 0, 128) == A.fused_tiles(T) == A.fused_tiles(T, 1024, 128) == (1024, 512)

    def loss(tiles, q, k, v):
        with jax.named_scope("attn_latent"):
            out = A.fused_causal_attention(q, k, v, q_scaled=True, tiles=tiles)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    args = [jax.ShapeDtypeStruct((B, T, N, Dh), jnp.bfloat16, sharding=one)] * 3
    compiled = jax.jit(jax.grad(lambda *a: loss(None, *a), argnums=(0, 1, 2))).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    kernels = [n for n in re.findall(r'op_name="([^"]*)"', text) if n.endswith("pallas_call")]
    assert len(kernels) >= 2 and all("attn_latent" in n for n in kernels), kernels
    assert _fits(compiled)
    with pytest.raises(Exception, match="vmem"):
        jax.jit(jax.grad(lambda *a: loss((1024, 512), *a), argnums=(0, 1, 2))).lower(*args).compile()


def test_the_latent_cells_step_compiles_and_fits(topo):
    """The whole step of `learner-glm47flash-ep8-wire` from its
    configuration's file (4 rows of 4,096 frames, five layers of the
    published widths, 513M parameters) for one described chip, as the
    benchmark's harness builds it: every layer's attention through the
    fused kernel (heads of 256), the four sparse layers' grouped products
    through theirs, and arguments, scratch and the two flat buffers of a
    weight publish together inside the chip's memory. About 110 s."""
    from benchmark import cells, harness

    bench = cells.load_benchmark()
    cell = cells.load_cell(bench, "learner-glm47flash-ep8-wire")
    cfg = harness.learner_config(cell, seed=0, broker_url="mem://x")
    assert (cfg.batch_size, cfg.seq_len, cfg.policy.tf_layers) == (4, 4095, 5)
    compiled = _compile_train_step(cfg, topo.devices[:1])
    text = compiled.as_text()
    kernels = [n for n in re.findall(r'op_name="([^"]*)"', text) if n.endswith("pallas_call")]
    assert sum("attn_latent" in n and "splash_mha_fwd" in n for n in kernels) >= 5
    assert sum("attn_latent" in n and "splash_mha_dkv" in n for n in kernels) >= 5
    assert sum("/moe/" in n for n in kernels) >= 4 * 12 and not [n for n in kernels if "moe_shared" in n]
    m = compiled.memory_analysis()
    params = 4 * 513_183_671
    assert m.argument_size_in_bytes > 3 * params  # the parameters and Adam's two moments
    assert m.temp_size_in_bytes + m.argument_size_in_bytes + 2 * params < HBM_BYTES


def _loops(text: str) -> list:
    """The `op_name` of every `while` operation of a compiled program."""
    return [re.search(r'op_name="([^"]*)"', ln).group(1) for ln in text.splitlines()
            if " while(" in ln and "condition=" in ln]


def test_the_linear_cells_step_compiles_and_fits(topo):
    """The whole step of `learner-qwen3next-ep32-wire` from its
    configuration's file (4 rows of 4,096 frames, three linear layers to
    one gated, 348M parameters) for one described chip, as the benchmark's
    harness builds it: per linear layer one scan over the row's segments
    in the forward pass and one reverse scan in the backward, none in the
    rematerialised forward (the rule's solved systems, entering states,
    u and o are kept by name: 2.2 GB of the 7.8 GB of scratch), and
    arguments, scratch and the two flat buffers of a weight publish
    together inside the chip's memory. About 115 s, as the latent cell's."""
    from benchmark import cells, harness

    cell = cells.load_cell(cells.load_benchmark(), "learner-qwen3next-ep32-wire")
    cfg = harness.learner_config(cell, seed=0, broker_url="mem://x")
    assert (cfg.batch_size, cfg.seq_len, cfg.policy.tf_layers) == (4, 4095, 4)
    compiled = _compile_train_step(cfg, topo.devices[:1])
    rule = [n for n in _loops(compiled.as_text()) if "/attn_linear/" in n]
    assert sum("/while/body/" not in n for n in rule) == 6 and not [n for n in rule if "rematted_computation" in n], rule
    m = compiled.memory_analysis()
    params = 4 * 347_736_567
    assert m.argument_size_in_bytes > 3 * params  # the parameters and Adam's two moments
    assert m.temp_size_in_bytes + m.argument_size_in_bytes + 2 * params < HBM_BYTES


@pytest.mark.parametrize("kind,scope", [("linear", "attn_linear"), ("gated", "attn_gated")])
def test_the_linear_cells_blocks_compile_and_fit(topo, kind, scope):
    """One linear and one gated block of `learner-qwen3next-ep32-wire` at
    its shapes (4 rows of 4,096 frames; the feed-forward part a SwiGLU of
    256, so that each compiles in 10-25 s), forward and backward under
    `nn.remat` with the step's policy, for one described chip. The linear
    block: no kernel, and the rule's loops under `attn_linear` by count,
    6 `while` operations where the parent of PR 40 had 15: one scan over
    the row's 32 segments in the forward pass and one reverse scan in the
    backward (the rule's own, `GD._rule_bwd`), inside each the scan of
    the state over a segment's chunks, inside the forward's two two-step
    loops that a `gather` of `_solve` becomes, and none in the
    rematerialised forward, which finds the solved systems, the entering
    states, u and o kept by name; the scratch 2.6 GB by the compiler's
    count (0.74 GB of it the kept arrays; with the whole row's float32
    intermediates kept for autodiff it was 7.2 GB, and 2.7 GB eight
    chunks at a time under a checkpoint of their own, at three
    forwards a step). The gated block: the two splash kernels under `attn_gated`
    at 16 query heads on 2 of 256, and between the one product and the
    kernel no slice, pad, concatenation or copy of part of a head (the
    rotary span is lanes 0-63 of whole heads; the gate reaches the heads
    as attention wrote them)."""
    from flax import linen as nn

    from benchmark import cells, harness
    from dotaclient_tpu.models.transformer_policy import Block
    from dotaclient_tpu.ops import attention as A
    from dotaclient_tpu.ops import gated_delta as GD

    one = SingleDeviceSharding(topo.devices[0])
    cell = cells.load_cell(cells.load_benchmark(), "learner-qwen3next-ep32-wire")
    policy = harness.learner_config(cell, seed=0, broker_url="mem://x").policy
    assert policy.tf_remat and GD.CHUNK == 64
    cfg = dataclasses.replace(policy, moe_experts=0, moe_experts_held=0, moe_shared_hidden=0,
                              tf_mlp_act="swiglu", tf_mlp_hidden=256)
    B, T, D = 4, 4096, cfg.lstm_hidden
    keep = jax.checkpoint_policies.save_only_these_names(A.FUSED_RESIDUALS, GD.RULE_RESIDUALS)
    block = nn.remat(Block, policy=keep)(cfg, kind, None, "tpu", True, False)
    positions = lambda: jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = jax.ShapeDtypeStruct((B, T, D), jnp.float32)
    params = jax.eval_shape(lambda: block.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype), positions()))

    def loss(params, x):
        return jnp.sum(block.apply(params, x, positions())[0] ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*_on(one, (params, x))).compile()
    text = compiled.as_text()
    kernels = [n for n in re.findall(r'op_name="([^"]*)"', text) if n.endswith("pallas_call")]
    assert _fits(compiled)
    if kind == "linear":
        loops = _loops(text)
        assert not kernels and all(f"/{scope}/" in n for n in loops), loops
        assert len(loops) <= 6 and sum("/while/body/" not in n for n in loops) == 2, loops  # one forward, one reverse
        assert not [n for n in loops if "rematted_computation" in n], loops
        assert compiled.memory_analysis().temp_size_in_bytes < 3.0e9
        return
    assert sum("splash_mha_fwd" in n for n in kernels) >= 1 and sum("splash_mha_dkv" in n for n in kernels) >= 1
    assert all(f"/{scope}/" in n for n in kernels), kernels
    part_of_a_head = re.compile(
        r"= (bf16|f32)\[4,(4096,(16|2)|(16|2),4096),(32|64|192)\]\S* (slice|dynamic-slice|pad|concatenate|copy)\(")
    found = [ln.strip()[:200] for ln in text.splitlines() if part_of_a_head.search(ln)]
    assert not found, found
