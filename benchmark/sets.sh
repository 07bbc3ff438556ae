#!/bin/bash
# sets.sh <dir> <workload> <seconds> <seed>...: the two sets of runs that a bound is set from.
# The first set starts from an empty compile cache (as the driver's first set does), both
# use the same seeds, and one traced run on a fresh seed follows.
dir=$1; wl=$2; secs=$3; shift 3
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}"
rm -rf .jax_cache
if [ -n "$JAX_COMPILATION_CACHE_DIR" ]; then rm -rf "$JAX_COMPILATION_CACHE_DIR"/*; fi
for set in A B; do
  bash benchmark/trial.sh $dir/set$set $wl $secs 0 "$@"
done
python3 - "$dir" "$wl" <<'PY'
import glob, json, statistics, sys
d, wl = sys.argv[1], sys.argv[2]
for name in ("env_steps_per_s", "setup_s"):
    spreads = []
    for s in "AB":
        vals = []
        for f in sorted(glob.glob(f"chiprun_out/{d}/set{s}/{wl}.*.t0.out")):
            vals.append(json.loads(open(f).read().strip().splitlines()[-1])["metrics"][name]["value"])
        q = statistics.quantiles(vals, n=4)
        med = statistics.median(vals)
        spreads.append((q[2] - q[0]) / med)
        print(name, "set", s, "median %.6g" % med, "iqr/median %.4f%%" % (100 * spreads[-1]), ["%.6g" % v for v in vals])
    print(name, "wider spread %.4f%%" % (100 * max(spreads)), "x5 = %.3f%%" % (500 * max(spreads)))
PY
