# PR 33, call 1 (one chip): the two new kernels alone (and the gated expert shapes against the parent's kernel, unpacked
# by `git archive` into .archive_check/parent), then the new cell once, traced: the first sight of its programs on the chip.
#   chiprun --timeout 3000 -- sh benchmarks/tools/calls/pr33_call1.sh
mkdir -p chiprun_out
timeout 900 python3 scripts/mamba2_microbench.py --parent .archive_check/parent > chiprun_out/pr33_micro.txt 2>chiprun_out/pr33_micro.err
echo "micro rc=$?"; cat chiprun_out/pr33_micro.txt; tail -5 chiprun_out/pr33_micro.err
timeout 1800 python3 benchmarks/run.py --workload nemotron3-nano-serve.short-chat-decode --seed 3000000019 --seconds 30 --trace 1 > chiprun_out/pr33_cell_first.txt 2>chiprun_out/pr33_cell_first.err
echo "cell rc=$?"; grep -v "^\[run\]" chiprun_out/pr33_cell_first.txt | tail -20 | cut -c1-3000; grep "^\[run\]" chiprun_out/pr33_cell_first.txt | cut -c1-2500; tail -25 chiprun_out/pr33_cell_first.err | cut -c1-600
