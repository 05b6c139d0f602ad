# PR 31, call 1 (one chip): the new cell for the first time. A traced run (a checkout's first: every program
# compiles), an untraced run on another seed (warm), then the tolerance's readings on a third seed: the engine as
# stated and the two controls (benchmarks/tools/joyai_precision.py).
O=chiprun_out; mkdir -p $O
N=joyai-llm-flash-serve.long-ctx-decode
run() {  # seed, trace, tag
  python3 benchmarks/run.py --workload $N --seed $1 --seconds 30 --trace $2 > $O/pr31c1_$3.log 2> $O/pr31c1_$3.err
  echo "rc=$? seed $1 trace $2"; grep -E '^\[(run|check|warm)\]' $O/pr31c1_$3.log | cut -c1-6000
  tail -n 1 $O/pr31c1_$3.log | cut -c1-6000; tail -n 5 $O/pr31c1_$3.err | cut -c1-600
}
run 3100000019 1 traced
run 2147483777 0 plain
python3 benchmarks/tools/joyai_precision.py stated,rows_through_int8,weights_through_int8 4000000063 \
  > $O/pr31c1_precision.jsonl 2> $O/pr31c1_precision.err
echo "precision rc=$?"; grep '^{' $O/pr31c1_precision.jsonl | cut -c1-2500; tail -n 5 $O/pr31c1_precision.err | cut -c1-600
