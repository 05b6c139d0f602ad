# PR 33, the last call (one chip). (1) The committed files are enough: the new cell's `--trace 1` run from
# .archive_check/final, an unpacked `git archive $(git write-tree)`. (2) The parent with this PR's benchmark files laid over
# it (.archive_check/parent_bench: `git archive 9e42f02` + BENCHMARK.json, benchmarks/, tests/benchmarks/ of the change): the
# new cell's command has to fail at once and non-zero there, and an old cell's traced run has to succeed.
#   chiprun --timeout 2400 -- sh benchmarks/tools/calls/pr33_final.sh
R=$PWD; O=$R/chiprun_out; mkdir -p $O
N=nemotron3-nano-serve.short-chat-decode
cd $R/.archive_check/final
timeout 1500 python3 benchmarks/run.py --workload $N --seed 12700001051 --seconds 30 --trace 1 > $O/pr33_final.log 2> $O/pr33_final.err
echo "rc=$? final traced $N"; grep -E '^\[check\]' $O/pr33_final.log | cut -c1-400; tail -n 1 $O/pr33_final.log | cut -c1-2600
cd $R/.archive_check/parent_bench
t0=$(date +%s)
timeout 300 python3 benchmarks/run.py --workload $N --seed 12700001051 --seconds 30 --trace 0 > $O/pr33_parent_new.log 2> $O/pr33_parent_new.err
echo "rc=$? parent + this PR's benchmark files, the new cell, after $(( $(date +%s) - t0 )) s"; tail -n 3 $O/pr33_parent_new.err | cut -c1-300
timeout 900 python3 benchmarks/run.py --workload mistral7b-serve.decode-sat --seed 12800001063 --seconds 30 --trace 1 > $O/pr33_parent_sat.log 2> $O/pr33_parent_sat.err
echo "rc=$? parent + this PR's benchmark files, decode-sat traced"; tail -n 1 $O/pr33_parent_sat.log | cut -c1-1500
