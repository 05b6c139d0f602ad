# PR 33, call 3 (one chip): the state's two controls (bfloat16 by lax.reduce_precision, since XLA folds a pair of converts
# away; int8 codes a row), two seeds each; then the new cell, six seeds, each run a process of its own.
#   chiprun --timeout 3500 -- sh benchmarks/tools/calls/pr33_call3.sh
mkdir -p chiprun_out
timeout 1500 python3 benchmarks/tools/nemotron_precision.py state_in_bf16,state_through_int8 3000000101 4100000203 > chiprun_out/pr33_precision2.jsonl 2>chiprun_out/pr33_precision2.err
echo "precision rc=$?"; grep '^{' chiprun_out/pr33_precision2.jsonl | cut -c1-1700; tail -3 chiprun_out/pr33_precision2.err | cut -c1-300
python3 benchmarks/tools/sets.py nemotron3-nano-serve.short-chat-decode 30 pr33set 0 6100000411 7200000517 8300000623 9400000729 10500000839 11600000941 2>&1 | cut -c1-1200
