"""Sequence-parallel causal attention: the TIME axis sharded over a
mesh axis, in both canonical collective patterns — the ppermute RING
(default) and the all-to-all ULYSSES variant (`ulysses_causal_attention`
below; trade-offs in its docstring). `attend` dispatches.

The reference never needed this (LSTM, chunk length ~16 — SURVEY.md §5
"Long-context / sequence parallelism"); it exists for the transformer
family's long-context training, where a chunk of T steps no longer fits
(or no longer should fit) one device. Mechanics, per the standard ring
formulation (Liu et al., blockwise parallel attention over a ring):

- Each of the `n` devices on the `sp` axis holds a [B, T/n, N, Dh] shard
  of Q, K and V plus the matching absolute-position shard.
- Q stays put. K/V (and their positions) rotate one hop per ring step
  via `jax.lax.ppermute` over ICI, so after n steps every query shard
  has streamed over every key shard. The heavy O(T²·Dh) score/value
  matmuls never leave the devices; the bytes on the wire per step are
  exactly one K/V shard — the collective rides the ring neighbours, the
  natural ICI topology.
- Accumulation is the flash-style streaming softmax from ops/attention
  (`accumulate_block`), so the math is bit-comparable to the one-block
  reference path and needs no [T, T] materialization anywhere.
- Causality needs NO block-index bookkeeping: positions travel with the
  K/V shards, and `accumulate_block` masks by `k_pos <= q_pos`. A ring
  step whose K block lies entirely in the local queries' future simply
  contributes nothing. (The compute for such blocks is not skipped —
  with causal chunking over a ring, skipping would halve FLOPs at the
  cost of load imbalance across the ring; a rebalancing schedule is a
  later optimization, noted here so the choice is visible.)
- The whole thing is `shard_map`ped and differentiable: the backward of
  `ppermute` is the reverse rotation, so gradients stream around the
  ring the same way — no hand-written VJP.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dotaclient_tpu.ops import attention as A


def _sp_shard_map(body_factory, mesh: Mesh, axis_name: str, q):
    """Shared shard_map plumbing for both SP patterns: time-divisibility
    check, dp-aware specs, vma-check opt-out (the streaming carries and
    collective re-shards are manual by design; correctness is pinned by
    the single-device parity tests). `body_factory(n)` receives the axis
    size — the single place it is derived."""
    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    if q.shape[1] % n:
        raise ValueError(f"time axis {q.shape[1]} not divisible by {axis_name}={n}")
    b_ax = "dp" if "dp" in mesh.axis_names else None
    seq = P(b_ax, axis_name, None, None)
    pos = P(b_ax, axis_name)
    return shard_map(
        body_factory(n),
        mesh=mesh,
        in_specs=(seq, seq, seq, pos, pos),
        out_specs=seq,
        check_vma=False,
    ), n


def _ring_body(q, k, v, q_pos, k_pos, *, axis_name: str, n: int):  # graftlint: jit-region
    """Runs inside shard_map: all arrays are the local shards."""
    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(carry, _):
        m, l, acc, k, v, k_pos = carry
        m, l, acc = A.accumulate_block(q, k, v, q_pos, k_pos, m, l, acc)
        # Rotate AFTER accumulating so the local block is counted once.
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        k_pos = jax.lax.ppermute(k_pos, axis_name, perm)
        return (m, l, acc, k, v, k_pos), None

    m, l, acc = A.init_carry(q)
    (m, l, acc, _, _, _), _ = jax.lax.scan(step, (m, l, acc, k, v, k_pos), None, length=n)
    return A.finalize_attention(m, l, acc, dtype=q.dtype)


def ring_causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_pos: jnp.ndarray,
    k_pos: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "sp",
) -> jnp.ndarray:
    """Causal attention with time sharded over `mesh[axis_name]`.

    q/k/v [B, T, N, Dh], q_pos/k_pos [B, T] — GLOBAL shapes; T must be
    divisible by the axis size. Computes the same function as
    `ops.attention.causal_attention` (tested for exact-shard-count
    equivalence, forward and gradients) with the time axis distributed.
    Composable under an outer jit: shard_map with an explicit mesh
    inlines into the surrounding SPMD program.
    """
    # The batch axis rides dp when the mesh has one (learner meshes are
    # dp×sp): the body is elementwise over batch, so dp needs no
    # collectives — but omitting it from the specs would declare the
    # inputs dp-replicated and force an all-gather of the dp shards.
    mapped, _ = _sp_shard_map(
        lambda n: functools.partial(_ring_body, axis_name=axis_name, n=n), mesh, axis_name, q
    )
    return mapped(q, k, v, q_pos, k_pos)


def _ulysses_body(q, k, v, q_pos, k_pos, *, axis_name: str, kv_block: int):  # graftlint: jit-region
    """Runs inside shard_map: time-sharded inputs → head-sharded
    attention → time-sharded output, via two all_to_alls."""
    # [B, T/n, N, Dh] → [B, T, N/n, Dh]: every device trades its time
    # shard of (N/n) head groups for the full time axis of one group.
    a2a = lambda x: jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)
    qg, kg, vg = a2a(q), a2a(k), a2a(v)
    q_pos_full = jax.lax.all_gather(q_pos, axis_name, axis=1, tiled=True)  # [B, T]
    k_pos_full = jax.lax.all_gather(k_pos, axis_name, axis=1, tiled=True)
    # Unlike the ring (blockwise by construction), the local attention
    # here sees the FULL time axis — at long T the dense score matrix is
    # exactly what sequence parallelism exists to avoid, so honor
    # kv_block and stream over key blocks.
    if kv_block and kg.shape[1] > kv_block:
        out = A.blockwise_causal_attention(qg, kg, vg, q_pos_full, k_pos_full, kv_block)
    else:
        out = A.causal_attention(qg, kg, vg, q_pos_full, k_pos_full)
    # [B, T, N/n, Dh] → [B, T/n, N, Dh]
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_pos: jnp.ndarray,
    k_pos: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "sp",
    kv_block: int = 0,
) -> jnp.ndarray:
    """All-to-all (Ulysses-style) sequence parallelism: the dual of the
    ring. Instead of streaming K/V blocks past stationary queries, two
    `all_to_all` collectives re-shard the tensors from time-sharded to
    HEAD-sharded, each device runs ordinary full-context attention for
    its head group, and a second all_to_all restores time sharding.

    Trade-offs vs the ring (both ship; pick per topology via
    PolicyConfig.tf_sp_mode): Ulysses moves each tensor twice in two
    bursts (good when all-to-all bandwidth is plentiful, e.g. a single
    ICI pod slice) and needs tf_heads % axis_size == 0; the ring moves
    K/V n times point-to-point to nearest neighbours (rides any ring
    topology, no head-count constraint) and never materializes the full
    time axis on a device. Same function computed either way — both are
    tested for exact parity against single-device attention.
    """
    mapped, n = _sp_shard_map(
        lambda n: functools.partial(_ulysses_body, axis_name=axis_name, kv_block=kv_block),
        mesh,
        axis_name,
        q,
    )
    if q.shape[2] % n:
        raise ValueError(
            f"ulysses: heads {q.shape[2]} not divisible by {axis_name}={n} "
            f"(use tf_sp_mode='ring', which has no head constraint)"
        )
    return mapped(q, k, v, q_pos, k_pos)


def fused_applies(
    platform: str,
    q_shape,
    k_shape,
    kv_block: int,
    mesh: Optional[Mesh] = None,
    sp_axis: str = "",
) -> bool:
    """Whether local attention over q [B, T, N, Dh] and k [B, Tk, G, Dh]
    goes through the fused kernel (`A.fused_causal_attention`) where it
    would go through the plain blocks: blocked attention is on and the
    key axis longer than its block, the program runs on a TPU
    (`platform`: of the mesh's devices where a mesh is known, else the
    default backend), queries and keys are one chunk (the actor's step
    over its cache stays dense), the time axis is not sharded, and the
    shapes are ones the kernel takes (`A.fused_takes`). On a mesh of
    several devices only `dp` may be larger than 1 and has to divide the
    rows: the kernel cannot be partitioned automatically and is mapped
    over `dp`. One function computed two ways; the choice follows what
    the program can observe, and no option names it."""
    B, T, N, Dh = q_shape
    Tk, G = k_shape[-3:-1]
    axes = dict(mesh.shape) if mesh is not None else {}
    return (
        platform == "tpu"
        and bool(kv_block)
        and T == Tk > kv_block
        and A.fused_takes(T, N, G, Dh)
        and not (sp_axis and sp_axis in axes)  # `attend` sends such a mesh to the ring
        and all(n == 1 for a, n in axes.items() if a != "dp")
        and B % axes.get("dp", 1) == 0
    )


def attend(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_pos: jnp.ndarray,
    k_pos: jnp.ndarray,
    mesh: Optional[Mesh] = None,
    sp_axis: str = "",
    sp_mode: str = "ring",
    kv_block: int = 0,
    window: int = 0,
    fused: bool = False,
) -> jnp.ndarray:
    """Dispatch: sequence-parallel attention when a mesh with an `sp`
    axis is supplied (learner long-context mode) — `sp_mode` picks the
    collective pattern ("ring" ppermute streaming | "ulysses"
    all-to-all head re-sharding). Otherwise local attention: blockwise
    flash formulation when `kv_block` is set and the key axis exceeds
    it (long single-device chunks), dense single-block else (actor
    stepping, short chunks, tests). `window`: a sliding layer's (local
    paths only). `fused`: the caller found `fused_applies` true for
    these shapes and has folded the 1/sqrt(Dh) into q; the blocked case
    then goes through the fused kernel, mapped over `dp` on a mesh of
    several devices."""
    if mesh is not None and sp_axis and sp_axis in mesh.axis_names:
        if window:
            raise ValueError("a sliding layer's window is not carried over the sp axis yet")
        if sp_mode == "ulysses":
            return ulysses_causal_attention(q, k, v, q_pos, k_pos, mesh, sp_axis, kv_block)
        if sp_mode != "ring":
            raise ValueError(f"unknown sp_mode {sp_mode!r} (ring|ulysses)")
        return ring_causal_attention(q, k, v, q_pos, k_pos, mesh, sp_axis)
    if fused:
        kernel = functools.partial(A.fused_causal_attention, window=window, q_scaled=True)
        if mesh is not None and mesh.size > 1:
            # check_vma off: pallas_call declares no varying-axes rule (ops/lstm.py does the same)
            kernel = shard_map(kernel, mesh=mesh, in_specs=(P("dp"),) * 3, out_specs=P("dp"),
                               check_vma=False)
        return kernel(q, k, v)
    if kv_block and k.shape[-3] > kv_block:
        return A.blockwise_causal_attention(q, k, v, q_pos, k_pos, kv_block, window)
    return A.causal_attention(q, k, v, q_pos, k_pos, window)
