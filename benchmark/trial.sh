#!/bin/bash
# trial.sh <dir> <workload> <seconds> <trace> <seed>...: runs of one cell, one after another,
# their last lines and the harness's own lines kept under chiprun_out/<dir>
dir=chiprun_out/$1; wl=$2; secs=$3; trace=$4; shift 4
mkdir -p $dir
for s in "$@"; do
  python3 benchmark/run.py --workload $wl --seed $s --seconds $secs --trace $trace > $dir/$wl.$s.t$trace.out 2> $dir/$wl.$s.t$trace.err
  echo "rc=$? $wl seed=$s trace=$trace"
  grep -E "^(setup|window|check|trace)" $dir/$wl.$s.t$trace.err | cut -c1-200
  tail -n 1 $dir/$wl.$s.t$trace.out | cut -c1-1500
done
