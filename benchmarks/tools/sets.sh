#!/bin/bash
# Runs of one cell in one call, each with another --seed: what the
# contract's two sets of 6 (same seeds in both) and the traced runs need.
# Each run's line, stderr and record (latency by second, counters) stay in <out dir>.
# (EXTRA in the environment: further flags of run.py for a trial, such as "--rate 80")
#   benchmarks/tools/sets.sh <cell> <out dir> <trace 0|1> <control seeds, comma list or -> <seed> [<seed> ...]
cell=$1; out=$2; trace=$3; ctl=$4; shift 4
mkdir -p "$out"
for seed in "$@"; do
  c=0; case ",$ctl," in *",$seed,"*) c=1;; esac
  n="$out/$cell.$seed.t$trace.$(date +%s)"
  python3 benchmarks/run.py --workload "$cell" --seed "$seed" --trace "$trace" --control $c $EXTRA > "$n.out" 2> "$n.err"
  echo "seed=$seed trace=$trace rc=$? $(tail -c 1400 "$n.out" | cut -c1-1400)"
  grep -a "control (the\|warm-up: {.phase.: .pool\|the last line would" "$n.err" | cut -c1-300
  cp "benchmarks/out/$cell/$seed.json" "$n.record.json" 2>/dev/null
done
