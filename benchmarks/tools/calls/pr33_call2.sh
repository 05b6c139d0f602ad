# PR 33, call 2 (one chip): the readings the tolerance lies between (two seeds, the engine as stated and the two controls),
# then the cell once through scripts/decode_ahead_microbench.py, traced: decode_pure's time by kind of operation.
#   chiprun --timeout 3400 -- sh benchmarks/tools/calls/pr33_call2.sh
mkdir -p chiprun_out
timeout 2200 python3 benchmarks/tools/nemotron_precision.py stated,state_in_bf16,weights_through_int8 3000000101 4100000203 > chiprun_out/pr33_precision.jsonl 2>chiprun_out/pr33_precision.err
echo "precision rc=$?"; grep '^{' chiprun_out/pr33_precision.jsonl | cut -c1-1800; tail -5 chiprun_out/pr33_precision.err | cut -c1-400
timeout 1200 python3 scripts/decode_ahead_microbench.py --workload nemotron3-nano-serve.short-chat-decode --seed 5200000307 --trace 1 > chiprun_out/pr33_ahead.txt 2>chiprun_out/pr33_ahead.err
echo "ahead rc=$?"; tail -1 chiprun_out/pr33_ahead.txt | cut -c1-9000; tail -5 chiprun_out/pr33_ahead.err | cut -c1-400
