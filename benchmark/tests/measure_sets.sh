#!/bin/bash
# Two sets of N runs of one cell (the same seeds in both sets, each run of a
# set another seed), then one traced run: what a benchmark PR measures its
# bounds from.  Run on the chip, all in one call:
#   chiprun [--chips 4] --timeout 3000 -- bash benchmark/tests/measure_sets.sh <cell> <N> <seconds>
# It stops at the first run that fails or is not correct.  Its last lines are
# benchmark/spread.py's: for each metric each set's median and spread (less
# the run farthest from the median), the mean of the two spreads, which may be
# at most half of the metric's bound, and the second median over the first.
# Every run's output lands in $BENCH_OUT/sets/<cell>/set{A,B}/run<i>.out
# (BENCH_OUT defaults to chiprun_out, which the chip tool brings back), the
# traced run's in $BENCH_OUT/sets/<cell>/traced.out.
cell=$1; n=${2:-6}; seconds=${3:-51}
out=${BENCH_OUT:-chiprun_out}/sets/$cell
mkdir -p "$out/setA" "$out/setB"
for set in A B; do
  for i in $(seq 1 "$n"); do
    seed=$((2147480000 + 7919 * i))
    python3 benchmark/run.py --workload "$cell" --seed "$seed" \
      --seconds "$seconds" --trace 0 \
      > "$out/set$set/run$i.out" 2> "$out/set$set/run$i.err"
    rc=$?
    echo "set$set run$i seed=$seed rc=$rc $(tail -n 1 "$out/set$set/run$i.out" | cut -c1-400)"
    grep -h '"phase": "window"' "$out/set$set/run$i.out" | cut -c1-900
    if [ "$rc" != 0 ] || ! tail -n 1 "$out/set$set/run$i.out" | grep -q '"correct": true'; then
      # a cell that does not run, or answers wrongly, is not worth more chip time
      tail -n 20 "$out/set$set/run$i.out" "$out/set$set/run$i.err" | cut -c1-600
      exit 1
    fi
  done
done
python3 benchmark/run.py --workload "$cell" --seed 2147483659 \
  --seconds "$seconds" --trace 1 --keep-trace "$out/trace" \
  > "$out/traced.out" 2> "$out/traced.err"
echo "traced rc=$? $(tail -n 1 "$out/traced.out" | cut -c1-3000)"
rm -f "$out"/trace/*.xplane.pb   # the raw trace is too large to bring back
grep -h '"phase": "\(warmup\|window\|trace\)"' "$out/traced.out" | cut -c1-700
tail -n 3 "$out/traced.err"
python3 benchmark/spread.py "$out/setA" "$out/setB"
