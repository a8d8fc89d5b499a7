#!/bin/bash
# Runs of benchmark cells on the chip, parent beside change, one process a
# run, all in ONE chip call (PERF.md's pairs are made by this loop):
#
#   chiprun --timeout 3300 -- env PR=<n> bash scripts/bench_pairs.sh <tag> <seed0> \
#       "<sides>" <seconds> <cell> [<cell> ...]
#
# <sides>, in the order they run: "parent" (_checkout/parent: `git archive
# HEAD | tar -x -C _checkout/parent`), "change" (the working tree), "final"
# (_checkout/final: `git archive $(git write-tree)`, the committed files
# alone), and "ptraced" / "traced" / "ftraced", the same three with --trace 1.
# Runs (1, 2) share seed0, (3, 4) seed0 + 1, ... (GROUP=n: n runs a seed);
# the next cell starts at seed0 + 100.  Logs go to
# chiprun_out/pr$PR/<tag>/<cell>_<n>_<side>_<seed>.{log,err}; a line a run
# is printed: cell, n, side, seed, exit code, seconds, the reference's two
# comparisons and the result line.
tag=$1; seed=$2; sides=$3; secs=$4; shift 4
root=$(cd "$(dirname "$0")/.." && pwd)
out=$root/chiprun_out/pr${PR:?the PR number}/$tag; mkdir -p $out
for cell in "$@"; do
  n=0
  for side in $sides; do
    n=$((n+1)); s=$((seed + (n-1)/${GROUP:-2}))
    dir=$root; trace=0
    case $side in
      parent) dir=$root/_checkout/parent;;
      ptraced) trace=1; dir=$root/_checkout/parent;;
      final) dir=$root/_checkout/final;;
      ftraced) trace=1; dir=$root/_checkout/final;;
      traced) trace=1;;
    esac
    log=$out/${cell}_${n}_${side}_$s
    t0=$(date +%s)
    (cd $dir && timeout 1500 python3 benchmark/run.py --workload $cell --seed $s --seconds $secs --trace $trace) > $log.log 2> $log.err
    rc=$?
    echo "$cell $n $side seed=$s rc=$rc $(( $(date +%s) - t0 ))s $(grep -h '^witness:\|^reference:' $log.log | cut -c1-300 | tr '\n' ' ') $(tail -n 1 $log.log | cut -c1-1600)"
    [ $trace = 1 ] && grep -h 'time_share:\|roofline:\|^scope map' $log.log | cut -c1-500
  done
  seed=$((seed + 100))
done
