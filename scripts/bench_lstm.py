"""LSTM recurrence micro-bench: lax.scan vs the fused Pallas kernel
(VERDICT r2 item 6 — the dispatcher's thresholds must be backed by an
in-repo artifact, not commit prose).

Writes one JSON artifact (default LSTM_BENCH.json) with per-config
timings for H in {128, 256, 512} at the flagship B=256, T=16:
forward-only and forward+backward (the train-step path), scan vs
pallas, plus the implied crossover. Pallas rows are recorded ONLY on a
real TPU backend — interpret-mode timings are meaningless and are
refused, so a CPU run documents scan-only numbers and says why.

Run: python scripts/bench_lstm.py [--out LSTM_BENCH.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, iters: int = 50) -> float:
    """Median-of-3 timing runs of `iters` compiled calls, seconds/call."""
    out = fn(*args)
    jax.block_until_ready(out)
    runs = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        runs.append((time.perf_counter() - t0) / iters)
    return sorted(runs)[1]


def bench_config(B: int, T: int, H: int, dtype, on_tpu: bool) -> dict:
    from dotaclient_tpu.ops import lstm as L

    r = np.random.RandomState(0)
    x_proj = jnp.asarray(r.randn(B, T, 4 * H), dtype)
    w_h = jnp.asarray(r.randn(H, 4 * H) / np.sqrt(H), dtype)
    c0 = jnp.zeros((B, H), jnp.float32)
    h0 = jnp.zeros((B, H), jnp.float32)

    def fwd(impl):
        return jax.jit(lambda xp, w, c, h: L.lstm_recurrence(xp, w, c, h, impl)[0])

    def fwdbwd(impl):
        def loss(xp, w, c, h):
            h_seq, (cT, hT) = L.lstm_recurrence(xp, w, c, h, impl)
            return jnp.sum(h_seq) + jnp.sum(cT) + jnp.sum(hT)

        return jax.jit(jax.grad(loss, argnums=(0, 1)))

    row = {
        "B": B,
        "T": T,
        "H": H,
        "dtype": str(dtype.dtype if hasattr(dtype, "dtype") else dtype),
        "scan_fwd_us": round(_time(fwd("scan"), x_proj, w_h, c0, h0) * 1e6, 1),
        "scan_fwdbwd_us": round(_time(fwdbwd("scan"), x_proj, w_h, c0, h0) * 1e6, 1),
    }
    if on_tpu:
        row["pallas_fwd_us"] = round(_time(fwd("pallas"), x_proj, w_h, c0, h0) * 1e6, 1)
        row["pallas_fwdbwd_us"] = round(_time(fwdbwd("pallas"), x_proj, w_h, c0, h0) * 1e6, 1)
        row["pallas_wins_fwdbwd"] = row["pallas_fwdbwd_us"] < row["scan_fwdbwd_us"]
    return row


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="LSTM_BENCH.json")
    p.add_argument("--iters", type=int, default=50)
    args = p.parse_args(argv)

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    rows = [bench_config(256, 16, H, dtype, on_tpu) for H in (128, 256, 512)]

    crossover = None
    if on_tpu:
        for row in rows:
            if row.get("pallas_wins_fwdbwd"):
                crossover = row["H"]
                break
    artifact = {
        "backend": backend,
        "device": str(jax.devices()[0]),
        "valid_for_dispatcher": on_tpu,
        "note": (
            "pallas rows omitted: non-TPU backend (interpret-mode timings "
            "refused; see module docstring)" if not on_tpu else
            f"pallas wins fwd+bwd from H={crossover}" if crossover else
            "pallas never wins at these shapes"
        ),
        "rows": rows,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
    print(json.dumps(artifact, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
