"""LSTM recurrence tests: scan reference vs Pallas kernel (interpret
mode on CPU), forward + custom-VJP backward parity, dispatcher."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dotaclient_tpu.ops import lstm as L


def make_inputs(B=4, T=6, H=8, seed=0, dtype=jnp.float32):
    r = np.random.RandomState(seed)
    x_proj = jnp.asarray(r.randn(B, T, 4 * H), dtype)
    w_h = jnp.asarray(r.randn(H, 4 * H) * 0.1, dtype)
    c0 = jnp.asarray(r.randn(B, H), jnp.float32)
    h0 = jnp.asarray(r.randn(B, H), jnp.float32)
    return x_proj, w_h, c0, h0


def test_scan_shapes_and_finiteness():
    x_proj, w_h, c0, h0 = make_inputs()
    h_seq, (c_T, h_T) = L.lstm_scan(x_proj, w_h, c0, h0)
    assert h_seq.shape == (4, 6, 8)
    assert c_T.shape == h_T.shape == (4, 8)
    assert np.all(np.isfinite(h_seq))
    # last h in the sequence IS the final carry
    np.testing.assert_allclose(np.asarray(h_seq[:, -1]), np.asarray(h_T), rtol=1e-6)


def test_scan_matches_manual_single_steps():
    x_proj, w_h, c0, h0 = make_inputs(T=3)
    h_seq, _ = L.lstm_scan(x_proj, w_h, c0, h0)
    c, h = c0, h0
    for t in range(3):
        z = x_proj[:, t] + h @ w_h
        c, h = L.gates(z, c)
        np.testing.assert_allclose(np.asarray(h_seq[:, t]), np.asarray(h), rtol=1e-5)


def test_pallas_interpret_matches_scan_forward():
    x_proj, w_h, c0, h0 = make_inputs(B=4, T=6, H=8, seed=1)
    ref, (rc, rh) = L.lstm_scan(x_proj, w_h, c0, h0)
    out, (oc, oh) = L.lstm_recurrence(x_proj, w_h, c0, h0, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(oc), np.asarray(rc), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(oh), np.asarray(rh), rtol=1e-5, atol=1e-6)


def test_pallas_backward_matches_scan_grads():
    """The hand-written recompute VJP must agree with autodiff through
    the scan on every input gradient."""
    x_proj, w_h, c0, h0 = make_inputs(B=2, T=5, H=8, seed=2)

    def loss(fn):
        def go(xp, w, c, h):
            h_seq, (c_T, h_T) = fn(xp, w, c, h)
            # touch sequence outputs AND final carries so every grad path runs
            return jnp.sum(h_seq**2) + jnp.sum(c_T * 0.3) + jnp.sum(h_T * 0.7)

        return jax.grad(go, argnums=(0, 1, 2, 3))

    ref_grads = loss(L.lstm_scan)(x_proj, w_h, c0, h0)
    pal_grads = loss(lambda *a: L.lstm_recurrence(*a, impl="pallas_interpret"))(
        x_proj, w_h, c0, h0
    )
    for name, a, b in zip(("x_proj", "w_h", "c0", "h0"), ref_grads, pal_grads):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5, err_msg=name
        )


def test_backward_with_carried_state_chain():
    """Grads flow through c0/h0 when chunks chain (state handoff)."""
    x_proj, w_h, c0, h0 = make_inputs(B=2, T=4, H=8, seed=3)

    def go(c, h):
        h_seq, (c_T, h_T) = L.lstm_recurrence(x_proj, w_h, c, h, impl="pallas_interpret")
        return jnp.sum(h_seq)

    g_c, g_h = jax.grad(go, argnums=(0, 1))(c0, h0)
    assert np.any(np.asarray(g_c) != 0) and np.any(np.asarray(g_h) != 0)


def test_dispatcher_auto_on_cpu_is_scan():
    x_proj, w_h, c0, h0 = make_inputs()
    # on the CPU test backend auto must not try to lower a TPU kernel
    out, _ = L.lstm_recurrence(x_proj, w_h, c0, h0, impl="auto")
    ref, _ = L.lstm_scan(x_proj, w_h, c0, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_dispatcher_rejects_unknown():
    x_proj, w_h, c0, h0 = make_inputs()
    with pytest.raises(ValueError):
        L.lstm_recurrence(x_proj, w_h, c0, h0, impl="bogus")


def test_vmem_guard():
    # shape-only probes: nothing materializes the 32 GB "too big" case
    def probe(shape):
        B, T, H4 = shape
        return L._block_b(B, T, H4 // 4, 4) > 0

    assert probe((128, 16, 512))
    # an odd batch still fits as one (padded) slab
    assert probe((130, 16, 512))
    # too big for VMEM at any slab size
    assert not probe((1024, 2048, 4096))
    # slab sizing: divisor of B, multiple of 32 (or the whole batch)
    assert L._block_b(256, 16, 256, 2) in (32, 64, 128, 256)


def test_bf16_inputs_stay_finite():
    x_proj, w_h, c0, h0 = make_inputs(dtype=jnp.bfloat16, seed=4)
    h_seq, (c_T, h_T) = L.lstm_recurrence(x_proj, w_h, c0, h0, impl="pallas_interpret")
    assert h_seq.dtype == jnp.float32  # gate math promotes
    assert np.all(np.isfinite(np.asarray(h_seq, np.float32)))


def test_bf16_scan_and_pallas_compute_identical_function():
    """All impls use f32 matmul accumulation, so bf16 inputs give the
    SAME forward outputs and closely matching grads — flipping lstm_impl
    must not perturb actor-vs-learner logp consistency."""
    x_proj, w_h, c0, h0 = make_inputs(B=4, T=5, H=8, seed=5, dtype=jnp.bfloat16)
    ref, (rc, rh) = L.lstm_scan(x_proj, w_h, c0, h0)
    out, (oc, oh) = L.lstm_recurrence(x_proj, w_h, c0, h0, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(oc), np.asarray(rc), rtol=1e-6, atol=1e-7)

    def g(fn):
        return jax.grad(
            lambda xp, w: jnp.sum(fn(xp, w, c0, h0)[0] ** 2), argnums=(0, 1)
        )(x_proj, w_h)

    for a, b in zip(g(L.lstm_scan), g(lambda *s: L.lstm_recurrence(*s, impl="pallas_interpret"))):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=2e-2, atol=1e-3
        )


# ------------------------------------------------------------------ mesh


class _FakeDevice:
    def __init__(self, platform):
        self.platform = platform


class _FakeMesh:
    """resolve_impl reads a mesh's axis sizes and its devices' platform
    and nothing else — enough to ask what a TPU mesh would get."""

    def __init__(self, platform, **axes):
        self.shape = axes
        self.devices = np.array([_FakeDevice(platform)], dtype=object)


@pytest.mark.parametrize(
    "asked,shape,mesh,expect",
    [
        ("auto", (256, 17, 512), None, "scan"),  # CPU default backend
        ("auto", (256, 17, 512), _FakeMesh("cpu", dp=4), "scan"),
        ("auto", (256, 17, 512), _FakeMesh("tpu", dp=1), "pallas"),
        ("auto", (256, 17, 512), _FakeMesh("tpu", dp=4), "pallas"),
        ("auto", (256, 17, 512), _FakeMesh("tpu", dp=2, tp=2), "scan"),  # w_h sharded
        ("auto", (256, 17, 256), _FakeMesh("tpu", dp=4), "scan"),  # H=64 below the window
        ("auto", (256, 17, 2048), _FakeMesh("tpu", dp=4), "scan"),  # H=512 above it
        ("auto", (4096, 2048, 1024), _FakeMesh("tpu", dp=1), "scan"),  # no slab fits VMEM
        ("pallas", (256, 17, 256), _FakeMesh("cpu", dp=2, tp=2), "pallas"),  # never gives way
        ("scan", (256, 17, 512), _FakeMesh("tpu", dp=4), "scan"),
    ],
)
def test_resolve_impl(asked, shape, mesh, expect):
    assert L.resolve_impl(asked, shape, 2, mesh) == expect


def _dp_mesh(n, names=("dp",), shape=None):
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape or (n,)), names)


def test_kernel_shard_maps_over_dp_forward_and_grads():
    """Under a dp=4 mesh the kernel runs shard_mapped (Mosaic kernels
    cannot be partitioned automatically): forward and every input
    gradient — W_h's is a psum over dp — match the one-device kernel."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _dp_mesh(4)
    args = make_inputs(B=8, T=5, H=8, seed=6)
    rows, rep = NamedSharding(mesh, P("dp")), NamedSharding(mesh, P())
    sharded = jax.device_put(args, (rows, rep, rows, rows))

    def loss(mesh):
        def go(xp, w, c, h):
            h_seq, (c_T, h_T) = L.lstm_recurrence(xp, w, c, h, "pallas_interpret", mesh)
            return jnp.sum(h_seq**2) + jnp.sum(c_T * 0.3) + jnp.sum(h_T * 0.7), h_seq

        return jax.jit(jax.value_and_grad(go, argnums=(0, 1, 2, 3), has_aux=True))

    (ref_l, ref_h), ref_g = loss(None)(*args)
    (out_l, out_h), out_g = loss(mesh)(*sharded)
    assert len(out_h.sharding.device_set) == 4
    np.testing.assert_allclose(np.asarray(out_h), np.asarray(ref_h), rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(float(out_l), float(ref_l), rtol=1e-5)
    for name, a, b in zip(("x_proj", "w_h", "c0", "h0"), ref_g, out_g):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6, err_msg=name
        )


def test_explicit_kernel_refuses_tp_mesh():
    """Asked for by name the kernel never turns into scan without a
    word: a mesh that shards W_h is an error, not a quiet fallback."""
    mesh = _dp_mesh(4, ("dp", "tp"), (2, 2))
    with pytest.raises(ValueError, match="tp"):
        L.lstm_recurrence(*make_inputs(B=8), "pallas_interpret", mesh)
