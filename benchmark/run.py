"""One run of one cell of the benchmark:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Measures on a TPU and nowhere else: where JAX finds another platform, or
fewer chips than the cell asks for, it exits non-zero and prints no
result. The last line of standard output is the result's JSON object.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def setup_jax(cache_dir: str) -> None:
    """The compile cache at its fixed place inside the checkout (or
    where JAX_COMPILATION_CACHE_DIR says), every program kept, and keyed
    with its metadata: an executable that another tree compiled carries
    that tree's `op_name`s and source lines into the trace, and the
    per-scope metrics would read them as this tree's."""
    import jax

    os.makedirs(cache_dir, exist_ok=True)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def main(argv=None, platform: str = "tpu", root: str = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import cells, harness

    root = root or cells.ROOT
    bench = cells.load_benchmark(root)
    cell = cells.load_cell(bench, args.workload, root)
    setup_jax(harness.CACHE_DIR)
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        print(f"this benchmark measures on a {platform}; jax.devices() are "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 3
    if len(devices) < int(cell["chips"]):
        print(f"cell {args.workload} needs {cell['chips']} chips; {len(devices)} found",
              file=sys.stderr)
        return 3
    result = harness.run_cell(bench, args.workload, args.seed, args.seconds, bool(args.trace),
                              T_START, devices, root)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
