"""Analytic FLOPs model of the PPO train step (SURVEY.md §6: perf numbers
must be normalizable — steps/s alone can't say how much of the chip is
used, so the bench reports achieved FLOP/s and MFU alongside).

Counts matmul FLOPs only (2·M·N·K per [M,K]x[K,N]) — the architecture is
matmul-dominated and elementwise/softmax work rides along fused, so this
undercounts by a few percent; XLA's own `compiled.cost_analysis()['flops']`
is reported next to it in the bench JSON as the compiler's ground truth
(tests pin the two within a bracket so the model can't rot silently).

Backward pass ≈ 2x the forward matmul FLOPs (each forward matmul spawns
two in the backward: d/dx and d/dW) — the standard 3x-forward total for
train steps. The optimizer update is elementwise (O(params), ~1M FLOPs vs
~10G matmul FLOPs/step) and is ignored.
"""

from __future__ import annotations

from dotaclient_tpu.config import LearnerConfig, PolicyConfig
from dotaclient_tpu.env import featurizer as F


def attended_pairs(frames: int, window: int = 0) -> float:
    """(query, key) pairs a chunk of `frames` frames keeps under the causal
    mask, and within `window` where a sliding layer has one."""
    if not window or window >= frames:
        return frames * (frames + 1) / 2.0
    return window * (window + 1) / 2.0 + (frames - window) * float(window)


def policy_forward_flops_per_frame(cfg: PolicyConfig, chunk_frames: int = 0) -> float:
    """Matmul FLOPs for ONE batch element, ONE time frame, forward only.

    Mirrors models/policy.py layer-for-layer (trunk + temporal core +
    heads). The LSTM recurrence's per-frame cost is the [1,H]x[H,4H]
    hidden projection; the hoisted x-projection is counted in the cell's
    input matmul. The transformer family instead pays its projections
    and feed-forward per frame plus attention over its (chunk-local)
    context: the least work, which is the pairs the causal mask and a
    sliding layer's window keep, averaged over a chunk of `chunk_frames`
    (0 = tf_context), and for a routed-expert layer the router and the
    pairs an even routing sends to the experts held here.
    """
    U, UF = F.MAX_UNITS, F.UNIT_FEATURES
    D, M, H = cfg.unit_embed_dim, cfg.mlp_hidden, cfg.lstm_hidden

    fl = 0.0
    # obs_trunk (models/policy.py:obs_trunk)
    fl += 2.0 * U * UF * M  # unit_mlp1
    fl += 2.0 * U * M * D  # unit_mlp2
    fl += 2.0 * F.HERO_FEATURES * M  # hero_mlp
    fl += 2.0 * F.GLOBAL_FEATURES * (M // 4)  # global_mlp
    trunk_in = M + M // 4 + 2 * D  # hero ++ glob ++ pool_max ++ pool_mean
    fl += 2.0 * trunk_in * H  # trunk dense

    # temporal core
    if cfg.arch == "transformer":
        from dotaclient_tpu.models.transformer_policy import (ff_sparse, head_shape, is_latent, latent_shape,
                                                              layer_kinds, linear_shape)

        N, G, Dh = head_shape(cfg)
        frames = chunk_frames or cfg.tf_context
        proj = 2.0 * H * (N + 2 * G) * Dh + 2.0 * N * Dh * H  # qkv, out
        per_pair = 4.0 * N * Dh  # QK^T + attn·V
        if is_latent(cfg):
            # the expanded form, the learner's: q through its latent, the latent and the
            # shared rotary key, every head's keys and values out of the latent, out
            q_rank, kv_rank, nope, rope, v_dim = latent_shape(cfg)
            proj = 2.0 * (H * q_rank + q_rank * N * (nope + rope) + H * (kv_rank + rope)
                          + kv_rank * N * (nope + v_dim) + N * v_dim * H)
            per_pair = 2.0 * N * (nope + rope) + 2.0 * N * v_dim
        held = cfg.moe_experts_held or cfg.moe_experts
        pairs_here = cfg.moe_top_k * held / max(cfg.moe_experts, 1)
        # router, the held pairs of an even routing, the shared expert and its gate's one column
        sparse = (2.0 * H * cfg.moe_experts + pairs_here * 3 * 2.0 * H * cfg.moe_hidden
                  + 3 * 2.0 * H * cfg.moe_shared_hidden + (2.0 * H if cfg.moe_shared_gate else 0.0))
        width = cfg.tf_mlp_hidden or 4 * H
        dense = (3 if cfg.tf_mlp_act == "swiglu" else 2) * 2.0 * H * width  # gate, up, down | up, down
        for kind, is_sparse in zip(layer_kinds(cfg), ff_sparse(cfg)):
            fl += sparse if is_sparse else dense
            if kind == "linear":
                # qkvz, ba, out; the rule by its recurrence, whatever form computes it: S^T k,
                # k u^T and S^T q a value head and frame (the convolution multiplies no matrices)
                Hk, Hv, d, _ = linear_shape(cfg)
                fl += 2.0 * H * (2 * Hk + 2 * Hv) * d + 2.0 * H * 2 * Hv + 2.0 * Hv * d * H + 3 * 2.0 * d * d * Hv
                continue
            pairs = attended_pairs(frames, cfg.tf_window if kind == "sliding" else 0)
            gate = 2.0 * H * N * Dh if kind == "gated" else 0.0  # the output gate's columns of qkv
            fl += proj + gate + pairs * per_pair / frames  # of the kept pairs
    else:
        fl += 2.0 * H * 4 * H  # x-projection (input is the trunk's H)
        fl += 2.0 * H * 4 * H  # recurrence hidden projection

    # heads (models/policy.py:action_heads)
    head_out = F.N_ACTION_TYPES + 2 * cfg.n_move_bins + D + 1
    if cfg.aux_heads:
        head_out += 3
    fl += 2.0 * H * head_out
    fl += 2.0 * U * D  # target dot-product attention scores
    return fl


def train_step_flops(cfg: LearnerConfig) -> float:
    """Total matmul FLOPs of one compiled PPO train step (fwd + bwd).

    The teacher-forced re-eval unrolls seq_len+1 frames (bootstrap frame
    included) for the whole batch; backward doubles the forward.

    Sample reuse (ppo.epochs R x ppo.minibatches M > 1) changes the step
    to 1 precompute forward (frozen GAE) + R epochs of full-data
    fwd+bwd (each epoch's M minibatches together cover the batch once):
    (3R + 1) x forward. With kl_stop enabled this is the no-early-stop
    upper bound — the bench reports ppo_updates_done so a stopped run
    is visible.

    NOTE: XLA's cost_analysis() counts a lax.scan/while BODY once,
    ignoring trip count (measured r4: the R=2,M=2 program reports FEWER
    flops than R=1,M=1), so the PRODUCTION reuse step can't be pinned
    directly. tests/test_flops.py instead pins the reuse model against a
    Python-UNROLLED compile of the same math (every update counted), so
    the (3R+1) trip-count structure is compiler-verified after all.
    """
    frames = cfg.batch_size * (cfg.seq_len + 1)
    fwd = frames * policy_forward_flops_per_frame(cfg.policy, cfg.seq_len + 1)
    R, M = cfg.ppo.epochs, cfg.ppo.minibatches
    if R * M == 1:
        return 3.0 * fwd
    return (3.0 * R + 1.0) * fwd


# Peak dense bf16 FLOP/s of one chip, keyed by `device.device_kind` as
# JAX reports it (Google Cloud TPU documentation, per-generation pages).
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5": 459e12,  # v5p
    "TPU v6 lite": 918e12,  # v6e (Trillium)
}


def peak_flops_for(device) -> float | None:
    """Peak bf16 FLOP/s of one jax device. None off the TPU, where MFU
    is not asked for; a TPU whose device_kind has no entry is an error —
    a silent None there reads as "MFU not wanted" in every artifact."""
    if device.platform != "tpu":
        return None
    try:
        return PEAK_BF16_FLOPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s entry for TPU device_kind {device.device_kind!r}: "
            f"add it to ops/flops.py PEAK_BF16_FLOPS with its source"
        ) from None


def aggregate_peak_flops(devices) -> float | None:
    """Total peak FLOP/s over a device list — the MFU denominator for a
    program spanning all of them (obs/compute.py MfuAccountant, bench).
    None when the devices are not TPUs (CPU smoke)."""
    peaks = [peak_flops_for(d) for d in devices]
    if not peaks or None in peaks:
        return None
    return sum(peaks)
