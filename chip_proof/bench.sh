#!/bin/bash
# usage: bench.sh <plan-file>; plan lines: "<label> <side> <workload> <seed> <trace> [extra run.py args / ENV=VAL]"
# both sides live under chip_proof/ and share one .cache (data, compile cache)
set -u
cd /root/repo
PLAN=$1
BUDGET=${BUDGET:-3250}   # seconds this call may take in all
START=$(date +%s)
mkdir -p chip_proof/.cache chiprun_out
for side in parent change; do [ -d chip_proof/$side ] && ln -sfn ../.cache chip_proof/$side/.cache; done
# the parent runs under this PR's benchmark files, as the driver lays them
cp BENCHMARK.json chip_proof/parent/BENCHMARK.json
cp -r chipbench/. chip_proof/parent/chipbench/
while read -r label side wl seed trace rest; do
  [ -z "$label" ] && continue
  envs=""; args=""
  for w in $rest; do case "$w" in *=*) envs="$envs $w";; *) args="$args $w";; esac; done
  t0=$(date +%s)
  left=$((BUDGET - (t0 - START)))
  if [ $left -lt ${MIN_LEFT:-150} ]; then echo "== $label SKIPPED: $left s left"; continue; fi
  [ $left -gt 1500 ] && left=1500
  (cd chip_proof/$side && env $envs timeout -k 10 $left python3 ../cell.py $label --workload $wl --seed $seed --seconds 40 --trace $trace $args) > chiprun_out/$label.out 2> chiprun_out/$label.err
  rc=$?
  t1=$(date +%s)
  echo "== $label $side $wl $seed trace=$trace $rest rc=$rc took=$((t1-t0))s"
  grep -E "^\+.*\[(data|warm|setup|window|check|control)\]" chiprun_out/$label.out | cut -c1-200
  tail -n 1 chiprun_out/$label.out | cut -c1-4000
  if [ $rc -ne 0 ]; then tail -n 15 chiprun_out/$label.err; fi
  free -g | sed -n 2p
done < "$PLAN"
