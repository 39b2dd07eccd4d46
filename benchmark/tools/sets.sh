#!/bin/bash
# Runs of one cell in one call, as the builder's instructions ask for them:
#   bash benchmark/tools/sets.sh <cell> <seconds> "<seeds>" <nsets> [trace_seed]
# nsets sets of runs with the same seeds, then one --trace 1 run. The full
# log goes to chiprun_out/<cell>_sets.log; stdout keeps the lines that
# matter. Stops after the first run if that run printed no result line.
cell=$1; secs=$2; seeds=$3; nsets=$4; tseed=$5
mkdir -p chiprun_out
log=chiprun_out/${cell}_sets.log
: > $log
keep="check:\|set-up\|window:\|run-in\|roofline\|RUN COUNTS\|^{\|rror\|Traceback"
drop='^W0\|^I0\|warn\|donated\|See an expl'
first=1
for s in $(seq 1 $nsets); do for seed in $seeds; do
  echo "=== set $s seed $seed" | tee -a $log
  python3 benchmark/run.py --workload $cell --seed $seed --seconds $secs --trace 0 2>&1 | grep -v "$drop" | tee -a $log | grep "$keep"
  if [ $first = 1 ] && ! grep -q '^{"correct"' $log; then
    echo "first run printed no result line: stopping"; tail -40 $log; exit 1
  fi
  first=0
done; done
if [ -n "$tseed" ]; then
  echo "=== trace seed $tseed" | tee -a $log
  python3 benchmark/run.py --workload $cell --seed $tseed --seconds $secs --trace 1 2>&1 | grep -v "$drop" | tee -a $log | grep "$keep"
fi
