"""Transformer-family train-step micro-bench: device-only fwd+bwd rates
across context lengths, dense vs blockwise attention, remat on/off.

Prices the long-context family next to the LSTM flagship: BENCH_TPU_*
covers the e2e LSTM loop, LSTM_BENCH the recurrence kernel, and this
artifact (TF_BENCH.json) the transformer step — env-steps/s, ms/step,
and the analytic MFU at each shape (ops/flops.py transformer model).

A CPU run (JAX_PLATFORMS=cpu) writes the artifact too, rates labeled by
backend — a relative shape study, never a device number.

Run: python scripts/bench_tf.py [--out TF_BENCH.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np


def bench_config(tf_context: int, attn_block: int, remat: bool, batch: int, iters: int) -> dict:
    from dotaclient_tpu.config import LearnerConfig, PolicyConfig
    from dotaclient_tpu.ops import flops as flops_mod
    from dotaclient_tpu.parallel import mesh as mesh_lib
    from dotaclient_tpu.parallel.train_step import (
        build_train_step,
        init_train_state,
        make_train_batch,
    )

    seq_len = tf_context - 1  # chunk fills the context (bootstrap frame incl.)
    cfg = LearnerConfig(
        batch_size=batch,
        seq_len=seq_len,
        mesh_shape="dp=-1",
        policy=PolicyConfig(
            arch="transformer",
            tf_layers=2,
            tf_heads=4,
            tf_context=tf_context,
            tf_attn_block=attn_block,
            tf_remat=remat,
        ),
    )
    mesh = mesh_lib.make_mesh("dp=-1", devices=jax.devices()[:1])
    train_step, state_sh, batch_sh = build_train_step(cfg, mesh)
    state = jax.device_put(init_train_state(cfg, jax.random.PRNGKey(0)), state_sh)
    batch_dev = jax.device_put(
        jax.tree.map(np.asarray, make_train_batch(cfg, 0)), batch_sh
    )
    t_compile = time.perf_counter()
    state, metrics = train_step(state, batch_dev)
    jax.block_until_ready(metrics["loss"])
    compile_s = time.perf_counter() - t_compile
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = train_step(state, batch_dev)
    jax.block_until_ready(metrics["loss"])
    dt = (time.perf_counter() - t0) / iters
    model_flops = flops_mod.train_step_flops(cfg)
    peak = flops_mod.peak_flops_for(jax.devices()[0])
    return {
        "tf_context": tf_context,
        "seq_len": seq_len,
        "batch": batch,
        "attn_block": attn_block,
        "remat": remat,
        "step_ms": round(dt * 1e3, 2),
        "env_steps_per_sec": round(batch * seq_len / dt, 1),
        "flops_per_step_model": round(model_flops),
        "mfu_pct": round(100.0 * model_flops / dt / peak, 3) if peak else None,
        "compile_s": round(compile_s, 1),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="TF_BENCH.json")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--batch", type=int, default=64)
    args = p.parse_args(argv)

    backend = jax.default_backend()
    rows = []
    # Shape ladder: the flagship-dryrun context, then 2x and 4x — where
    # blockwise attention and remat start paying. Dense rows at every
    # length; blockwise + remat variants from 128 up.
    for ctx in (64, 128, 256):
        variants = [(0, False)]
        if ctx >= 128:
            variants += [(64, False), (64, True)]
        for attn_block, remat in variants:
            try:
                rows.append(bench_config(ctx, attn_block, remat, args.batch, args.iters))
                print(json.dumps(rows[-1]), flush=True)
            except Exception as e:  # one failed shape must not void the rest
                rows.append(
                    {"tf_context": ctx, "attn_block": attn_block, "remat": remat,
                     "error": f"{type(e).__name__}: {e}"[:300]}
                )
    artifact = {
        "backend": backend,
        "device": str(jax.devices()[0]),
        "valid_as_silicon_evidence": backend == "tpu",
        "config": "transformer d_model=128 L=2 H=4, device-only train step, 1 device",
        "rows": rows,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
    print(json.dumps(artifact, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
