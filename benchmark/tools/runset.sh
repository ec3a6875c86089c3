#!/bin/bash
# One set of runs of a cell on the chip, gathered for tools/spread.py:
#   benchmark/tools/runset.sh <workload> <tag> <trace 0|1> <seeds...>   (from the repo root)
wl=$1; tag=$2; tr=$3; shift 3
mkdir -p chiprun_out
for s in "$@"; do
  python3 benchmark/run.py --workload $wl --seed $s --seconds 40 --trace $tr > chiprun_out/${tag}_$s.log 2> chiprun_out/${tag}_$s.err
  echo "rc=$? seed=$s trace=$tr" >> chiprun_out/${tag}.jsonl
  grep -h '"setup_s"\|"step_ms"' chiprun_out/${tag}_$s.log | head -2 | cut -c1-400 >> chiprun_out/${tag}.jsonl
  grep '"compared"' chiprun_out/${tag}_$s.log | grep -v '"limit": 0,' | tr '\n' ' ' | cut -c1-900 >> chiprun_out/${tag}.jsonl; echo >> chiprun_out/${tag}.jsonl
  tail -n 1 chiprun_out/${tag}_$s.log >> chiprun_out/${tag}.jsonl
done
