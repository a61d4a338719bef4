#!/bin/sh
# One chip call: run cells once each, in processes of their own, and keep
# every line. Usage: chip_probe.sh <out-name> "<args for cell 1>" "<args 2>" ...
# "control:<args>" runs tests/control_chip.py; "checkout:<args>" runs the cell
# from chip_checkout/, an unpacked `git archive` of the tree (git-ignored).
out=chiprun_out/$1; shift
mkdir -p "$out"
i=0
for a in "$@"; do
  i=$((i+1))
  echo "=== run $i: $a" | tee -a "$out/summary.txt"
  case "$a" in
    checkout:*) (cd chip_checkout && python3 benchmark/run.py ${a#checkout:}) > "$out/run$i.out" 2> "$out/run$i.err" ;;
    control:*) python3 benchmark/tests/control_chip.py ${a#control:} > "$out/run$i.out" 2> "$out/run$i.err" ;;
    *) python3 benchmark/run.py $a > "$out/run$i.out" 2> "$out/run$i.err" ;;
  esac
  echo "rc=$?" | tee -a "$out/summary.txt"
  tail -n 9 "$out/run$i.out" | cut -c1-1500 | tee -a "$out/summary.txt"
  grep -v "^E1001\|^I0000\|^W0000" "$out/run$i.err" | tail -n 9 | cut -c1-400 | tee -a "$out/summary.txt"
done
