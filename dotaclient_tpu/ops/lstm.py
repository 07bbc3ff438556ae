"""LSTM time recurrence — the one truly sequential op in the model.

TPU-first structure (SURVEY.md §3.3; models/policy.py): everything else
in the policy is batched over [B, T] on the MXU; only this recurrence
walks the time axis. The x-projection (input half of the gate matmul) is
hoisted out of the loop by the caller into ONE large [B·T, in]×[in, 4H]
matmul, so each step here is just the [B, H]×[H, 4H] hidden matmul plus
the elementwise gate tail:

    z_t = x_proj_t + h_{t-1} @ W_h
    i, f, g, o = split(z_t);  c_t = σ(f+1)·c_{t-1} + σ(i)·tanh(g)
    h_t = σ(o)·tanh(c_t)

Two interchangeable implementations with identical math:
- `impl="scan"`: lax.scan, differentiable by autodiff — the reference
  path and the CPU/debug fallback;
- `impl="pallas"`: a fused TPU kernel (W_h resident in VMEM, carries
  never touch HBM between steps, time loop inside the kernel), wrapped
  in jax.custom_vjp with a recompute-gates backward: z_t is rebuilt from
  the saved h/c sequences, so the 4H-wide f32 gate activations are never
  stored (the residuals are x_proj — compute-dtype, already live — plus
  the f32 h/c sequences).

Gate math is float32 in both paths; matmuls run in the caller's compute
dtype (bfloat16 on TPU hits the MXU).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

LSTMState = Tuple[jnp.ndarray, jnp.ndarray]  # (c, h), each [B, H] f32

# Pallas blocks over the batch axis: each grid step runs the full time
# loop for one batch slab (slabs are independent). The slab size adapts
# to VMEM: ~16 MB/core, and the working set per slab is
# x_proj[bb,T,4H] + (h_seq+c_seq)[bb,T,H] + W_h[H,4H] (+ carries).
_VMEM_BUDGET = 14 * 1024 * 1024
# Slabs below 32 rows make the grid long and sequential (and tickle
# mosaic tiling limits at very large H) — not worth running.
_MIN_BLOCK_B = 32


def _block_b(B: int, T: int, H: int, itemsize: int) -> int:
    """Largest batch slab (divisor of B, multiple of 8) whose working set
    fits VMEM; 0 if none exists. Grid-mapped blocks are DOUBLE-buffered
    by the pipeline whenever there is more than one grid step, so a
    multi-slab launch pays 2× per blocked operand; W_h is fetched once
    (constant index map)."""
    bb = B
    min_bb = min(_MIN_BLOCK_B, B)  # a small batch is one (padded) slab
    while bb >= min_bb:
        if B % bb == 0 and (bb == B or bb % _MIN_BLOCK_B == 0):
            mult = 1 if bb == B else 2
            blocked = (
                bb * T * 4 * H * itemsize  # x_proj slab
                + 2 * bb * T * H * 4  # h_seq + c_seq outputs (f32)
                + 4 * bb * H * 4  # c0/h0 in + c_T/h_T out
            )
            vmem = mult * blocked + H * 4 * H * itemsize
            if vmem <= _VMEM_BUDGET:
                return bb
        bb //= 2
    return 0


def gates(z: jnp.ndarray, c: jnp.ndarray):
    """f32 gate tail shared verbatim by every implementation."""
    i, f, g, o = jnp.split(z.astype(jnp.float32), 4, axis=-1)
    new_c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    new_h = jax.nn.sigmoid(o) * jnp.tanh(new_c)
    return new_c, new_h


# ---------------------------------------------------------------------------
# Reference / fallback: lax.scan (autodiff handles the backward).


def lstm_scan(x_proj: jnp.ndarray, w_h: jnp.ndarray, c0: jnp.ndarray, h0: jnp.ndarray):
    """x_proj [B, T, 4H] (bias already added), w_h [H, 4H], c0/h0 [B, H]
    → (h_seq [B, T, H] f32, (c_T, h_T))."""

    def step(carry, xp_t):
        c, h = carry
        # f32 accumulation, same as the pallas kernel — the two impls must
        # compute the identical function in bf16 too
        z = xp_t + jnp.dot(h.astype(w_h.dtype), w_h, preferred_element_type=jnp.float32)
        c, h = gates(z, c)
        return (c, h), h

    (c_T, h_T), h_seq = jax.lax.scan(step, (c0, h0), jnp.swapaxes(x_proj, 0, 1))
    return jnp.swapaxes(h_seq, 0, 1), (c_T, h_T)


# ---------------------------------------------------------------------------
# Pallas TPU kernel.


def _lstm_kernel(xp_ref, wh_ref, c0_ref, h0_ref, hseq_ref, cseq_ref, cT_ref, hT_ref):
    # Sequences are TIME-MAJOR in the kernel ([T, B, ...]): Mosaic allows
    # dynamic indexing only on the leading (untiled) axis — the [B, T]
    # layout would need a dynamic index on a sublane-tiled dimension.
    T = xp_ref.shape[0]
    w = wh_ref[:]

    def body(t, carry):
        c, h = carry
        z = xp_ref[t] + jnp.dot(h.astype(w.dtype), w, preferred_element_type=jnp.float32)
        c, h = gates(z, c)
        hseq_ref[t] = h
        cseq_ref[t] = c
        return c, h

    c, h = jax.lax.fori_loop(0, T, body, (c0_ref[:], h0_ref[:]))
    cT_ref[:] = c
    hT_ref[:] = h


def _pallas_forward(x_proj, w_h, c0, h0, interpret: bool = False):
    """Returns (h_seq, c_seq, c_T, h_T), sequences [B, T, H]; c_seq is
    kept for the backward."""
    B, T, H4 = x_proj.shape
    H = H4 // 4
    bb = _block_b(B, T, H, x_proj.dtype.itemsize)
    if not bb:
        raise ValueError(f"lstm pallas: no batch slab of {x_proj.shape} fits VMEM")
    grid = (B // bb,)
    seq_block = lambda last: pl.BlockSpec(  # [T, bb, last], blocked over B
        (T, bb, last), lambda i: (0, i, 0), memory_space=pltpu.VMEM
    )
    state_block = pl.BlockSpec((bb, H), lambda i: (i, 0), memory_space=pltpu.VMEM)
    h_seq, c_seq, c_T, h_T = pl.pallas_call(
        _lstm_kernel,
        grid=grid,
        in_specs=[
            seq_block(H4),
            pl.BlockSpec((H, H4), lambda i: (0, 0), memory_space=pltpu.VMEM),
            state_block,
            state_block,
        ],
        out_specs=[
            seq_block(H),
            seq_block(H),
            state_block,
            state_block,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H), jnp.float32),
            jax.ShapeDtypeStruct((T, B, H), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.swapaxes(x_proj, 0, 1), w_h, c0, h0)
    return jnp.swapaxes(h_seq, 0, 1), jnp.swapaxes(c_seq, 0, 1), c_T, h_T


def _recompute_backward(res, grads):
    """Gate recompute backward: rebuild z_t from saved h/c, walk time in
    reverse with lax.scan. Pure jnp — XLA compiles it alongside the rest
    of the train step."""
    x_proj, w_h, c0, h0, h_seq, c_seq = res
    dh_seq, (dc_T, dh_T) = grads
    B, T, H = h_seq.shape
    w_f32 = w_h.astype(jnp.float32)

    # previous-step carries per t (t=0 uses the initial state)
    h_prev = jnp.concatenate([h0[:, None], h_seq[:, :-1]], axis=1)
    c_prev = jnp.concatenate([c0[:, None], c_seq[:, :-1]], axis=1)

    def step(carry, xs):
        dc_next, dh_next = carry
        xp_t, hp_t, cp_t, c_t, dh_out_t = xs
        # identical accumulation to the forward kernel: the VJP must
        # differentiate the function the forward actually computed
        z = xp_t.astype(jnp.float32) + jnp.dot(
            hp_t.astype(w_h.dtype), w_h, preferred_element_type=jnp.float32
        )
        zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
        i = jax.nn.sigmoid(zi)
        f = jax.nn.sigmoid(zf + 1.0)
        g = jnp.tanh(zg)
        o = jax.nn.sigmoid(zo)
        tanh_c = jnp.tanh(c_t)

        dh = dh_out_t + dh_next
        do = dh * tanh_c
        dc = dc_next + dh * o * (1.0 - tanh_c**2)
        di = dc * g
        df = dc * cp_t
        dg = dc * i
        dz = jnp.concatenate(
            [
                di * i * (1.0 - i),
                df * f * (1.0 - f),
                dg * (1.0 - g**2),
                do * o * (1.0 - o),
            ],
            axis=-1,
        )
        dh_prev = dz @ w_f32.T
        dc_prev = dc * f
        dw_t = hp_t.T.astype(jnp.float32) @ dz
        return (dc_prev, dh_prev), (dz, dw_t)

    xs = (
        jnp.swapaxes(x_proj, 0, 1),
        jnp.swapaxes(h_prev, 0, 1),
        jnp.swapaxes(c_prev, 0, 1),
        jnp.swapaxes(c_seq, 0, 1),
        jnp.swapaxes(dh_seq.astype(jnp.float32), 0, 1),
    )
    (dc0, dh0), (dz_seq, dw_seq) = jax.lax.scan(step, (dc_T, dh_T), xs, reverse=True)
    dx_proj = jnp.swapaxes(dz_seq, 0, 1).astype(x_proj.dtype)
    dw_h = jnp.sum(dw_seq, axis=0).astype(w_h.dtype)
    return dx_proj, dw_h, dc0, dh0


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _lstm_pallas(x_proj, w_h, c0, h0, interpret=False):
    h_seq, _c_seq, c_T, h_T = _pallas_forward(x_proj, w_h, c0, h0, interpret)
    return h_seq, (c_T, h_T)


def _lstm_pallas_fwd(x_proj, w_h, c0, h0, interpret):
    h_seq, c_seq, c_T, h_T = _pallas_forward(x_proj, w_h, c0, h0, interpret)
    return (h_seq, (c_T, h_T)), (x_proj, w_h, c0, h0, h_seq, c_seq)


def _lstm_pallas_bwd(interpret, res, grads):
    return _recompute_backward(res, grads)


_lstm_pallas.defvjp(_lstm_pallas_fwd, _lstm_pallas_bwd)


# ---------------------------------------------------------------------------
# Dispatcher.


def resolve_impl(impl: str, x_shape, itemsize: int, mesh=None) -> str:
    """What "auto" means for a GLOBAL x_proj shape [B, T, 4H]: the fused
    kernel when the program runs on a TPU (the mesh's devices when a
    mesh is given, else the default backend), H lies in the window
    LSTM_BENCH.json covers (128 <= H < 512, B=256 T=16 bf16 on a
    TPU v5 lite), W_h is whole on every device (a `tp` axis > 1 shards
    its 4H axis, parallel/mesh.py) and the per-device batch slab fits
    VMEM; lax.scan otherwise. Any other `impl` is returned as asked —
    an explicit choice never gives way."""
    if impl != "auto":
        return impl
    B, T, H4 = x_shape
    H = H4 // 4
    axes = dict(mesh.shape) if mesh is not None else {}
    platform = mesh.devices.flat[0].platform if mesh is not None else jax.default_backend()
    fits = _block_b(B // axes.get("dp", 1), T, H, itemsize) > 0
    kernel = platform == "tpu" and 128 <= H < 512 and axes.get("tp", 1) == 1 and fits
    return "pallas" if kernel else "scan"


def lstm_recurrence(x_proj, w_h, c0, h0, impl: str = "auto", mesh=None):
    """Dispatch on `impl` (auto|scan|pallas|pallas_interpret; "auto" per
    resolve_impl, "pallas_interpret" runs the kernel in interpret mode
    for CPU tests). `mesh` is the mesh of the surrounding jit, if any:
    Mosaic kernels cannot be partitioned automatically, and the kernel's
    batch slabs are independent, so under a mesh the kernel is
    shard_mapped over `dp` with W_h replicated."""
    impl = resolve_impl(impl, x_proj.shape, x_proj.dtype.itemsize, mesh)
    if impl == "scan":
        return lstm_scan(x_proj, w_h, c0, h0)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown lstm impl {impl!r}")
    interpret = impl == "pallas_interpret"

    def kernel(x_proj, w_h, c0, h0):
        return _lstm_pallas(x_proj, w_h, c0, h0, interpret)

    if mesh is not None and mesh.size > 1:
        if mesh.shape.get("tp", 1) > 1:
            raise ValueError(
                f"lstm impl {impl!r} needs W_h whole on every device, and mesh "
                f"{dict(mesh.shape)} shards its 4H axis over tp; use 'auto' or 'scan'"
            )
        rows = P("dp") if "dp" in mesh.axis_names else P()
        # check_vma off: pallas_call declares no varying-axes rule; the
        # dp=4 parity test pins forward and gradients to one device.
        kernel = jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=(rows, P(), rows, rows),
            out_specs=(rows, (rows, rows)),
            check_vma=False,
        )
    return kernel(x_proj, w_h, c0, h0)
