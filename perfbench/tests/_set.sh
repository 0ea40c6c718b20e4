# usage: _set.sh <cell> <tag> <seconds> <seed>...   one set of runs of one cell, --trace 0
cell=$1; tag=$2; secs=$3; shift 3
mkdir -p chiprun_out/sets
for seed in "$@"; do
  python3 perfbench/run.py --workload $cell --seed $seed --seconds $secs --trace 0 > chiprun_out/sets/_last.out 2> chiprun_out/sets/_last.err
  echo "rc=$? seed=$seed $(tail -n 1 chiprun_out/sets/_last.out)" | tee -a chiprun_out/sets/$cell.$tag.txt
  tail -n 1 chiprun_out/sets/_last.err | cut -c1-400 >> chiprun_out/sets/$cell.$tag.err.txt
done
