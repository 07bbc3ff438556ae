"""The control and the planted faults of a cell's check, read on the
chip at the cell's own size. Not part of a benchmark run:

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 --variants int8,fp8,half_batch

For each seed it follows the check's steps with the plain reference, and
again with the reference put in the program's place (a) computed in the
precision below the configuration's (for bfloat16 that is 8 bits: both
operands of every matrix product, and the cotangents on the way back,
rounded to int8 or to float8_e4m3 under a per-tensor scale, accumulated
in float32; for float32, bfloat16), (b) with half of the batch left out
and the mean taken over the rest. Each is judged as a run is, by
`check.compare` at the limits of the configuration's file: the line of a
seed gives every number read, the ones that failed, and the verdict. The
exit code is 1 where a variant came out as correct on some seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _rounder(dtype, top: float):
    """x rounded to `dtype` under a per-tensor scale (the largest entry
    lands on `top`), straight through: the cotangent is rounded the same
    way on its way back, as a step computed in that precision would."""
    import jax
    import jax.numpy as jnp

    def rnd(x):
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        return (x / scale).astype(dtype).astype(jnp.float32) * scale

    @jax.custom_vjp
    def q(x):
        return rnd(x)

    q.defvjp(lambda x: (rnd(x), None), lambda _, g: (rnd(g),))
    return q


def fp8(x):
    import jax.numpy as jnp

    return _rounder(jnp.float8_e4m3fn, 448.0)(x)


def int8(x):
    """Symmetric per-tensor int8, the 8-bit type this chip multiplies in
    (393 TOP/s on a v5e against 197 TFLOP/s in bf16)."""
    import jax
    import jax.numpy as jnp

    def rnd(v):
        scale = jnp.maximum(jnp.max(jnp.abs(v)), 1e-30) / 127.0
        return jnp.clip(jnp.round(v / scale), -127.0, 127.0) * scale

    @jax.custom_vjp
    def q(v):
        return rnd(v)

    q.defvjp(lambda v: (rnd(v), None), lambda _, g: (rnd(g),))
    return q(x)


def bf16(x):
    import jax.numpy as jnp

    return _rounder(jnp.bfloat16, 1.0)(x)


VARIANTS = {
    "fp8": dict(quant=fp8),
    "int8": dict(quant=int8),
    "bf16": dict(quant=bf16),
    "half_batch": dict(fault="half_batch"),
}


def main(argv=None, platform: str = "tpu", root: str = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", required=True)
    args = ap.parse_args(argv)

    from benchmark import cells, check, frames, harness
    from benchmark.run import setup_jax

    root = root or cells.ROOT
    bench = cells.load_benchmark(root)
    cell = cells.load_cell(bench, args.workload, root)
    config, traffic = cell["config_data"], cell["traffic_data"]
    setup_jax(harness.CACHE_DIR)
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < int(cell["chips"]):
        print("not the cell's devices", file=sys.stderr)
        return 3
    chips = int(cell["chips"])
    mesh = Mesh(np.array(devices[:chips]), ("dp",))
    n = int(config["check"]["reference_steps"])
    B = int(config["learner"]["rows_per_chip"]) * chips
    passed = 0
    for seed in [int(s) for s in args.seeds.split(",")]:
        rows = frames.make_rows(config, traffic["rows"], n * B, seed)
        t = time.perf_counter()
        want = check.reference_readings(config, seed, rows, n, mesh, bench=bench, root=root)
        line = {"seed": seed, "reference_s": time.perf_counter() - t, "losses": want["losses"]}
        for name in args.variants.split(","):
            got = check.reference_readings(config, seed, rows, n, mesh, bench=bench, root=root,
                                           **VARIANTS[name])
            judged = check.compare(config, got, want)
            failed = sorted(k for k, (v, lim) in judged.items() if v > lim)
            passed += not failed
            line[name] = {
                "verdict": "not correct" if failed else "CORRECT",
                "failed": failed,
                "numbers": check.all_numbers(got, want),
                "leaves": {k: v for k, v in check.notes(got, want).items()
                           if "worst leaf" in v or k == "leaves"},
            }
        print(json.dumps(line), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
