# PR 24, chip call 38 (2,609 s): as run, then from benchmarks_out/tools/; .archive_check/ held the
# unpacked `git archive` of the tree of that hour, chat-open still in it.
# Final call: everything runs from the unpacked `git archive` of the final tree.
T0=$(date +%s)
SEEDS="11 2147483659 4000000007 305419896 77 3123456789"
export SETS_OUT=$PWD/chiprun_out/final
TOOLS=$PWD/benchmarks_out/tools
mkdir -p $SETS_OUT
# a directory with only BENCHMARK.json and the paths must fail and print no result
mkdir -p benchmarks_out/onlypaths/tests && cp -r .archive_check/BENCHMARK.json .archive_check/benchmarks benchmarks_out/onlypaths/ && cp -r .archive_check/tests/benchmarks benchmarks_out/onlypaths/tests/
( cd benchmarks_out/onlypaths && python3 benchmarks/run.py --workload mistral7b-train.pretrain-4k --seed 1 --seconds 5 --trace 0 > $SETS_OUT/onlypaths.out 2> $SETS_OUT/onlypaths.err; echo "onlypaths rc=$? result lines: $(grep -c '^{' $SETS_OUT/onlypaths.out)"; tail -2 $SETS_OUT/onlypaths.err )
cd .archive_check
ls
run_cell () {  # workload, number of plain sets
  W=$1
  echo "=== $W traced at $(( $(date +%s) - T0 )) s"
  python3 $TOOLS/sets.py $W 30 trace 1 2147483659
  if ! grep -q '"rc": 0' $SETS_OUT/$W.trace.jsonl; then
    python3 $TOOLS/sets.py $W 30 probe 0 11
    if ! grep -q '"rc": 0' $SETS_OUT/$W.probe.jsonl; then return; fi
  fi
}
run_cell mistral7b-serve.decode-sat
for SET in a b; do echo "=== decode-sat set $SET at $(( $(date +%s) - T0 )) s"; python3 $TOOLS/sets.py mistral7b-serve.decode-sat 30 set$SET 0 $SEEDS; done
run_cell mistral7b-train.pretrain-4k
python3 $TOOLS/sets.py mistral7b-train.pretrain-4k 30 seta 0 11 2147483659 4000000007
if [ $(date -u +%H%M) -gt 2245 ]; then echo "=== too late for chat-open"; echo "=== done at $(( $(date +%s) - T0 )) s"; exit 0; fi
run_cell mistral7b-serve.chat-open
for SET in a b; do
  EL=$(( $(date +%s) - T0 ))
  if [ $EL -gt 2850 ]; then echo "=== out of time for chat-open set $SET ($EL s)"; break; fi
  echo "=== chat-open set $SET at $EL s"; python3 $TOOLS/sets.py mistral7b-serve.chat-open 30 set$SET 0 $SEEDS
done
echo "=== done at $(( $(date +%s) - T0 )) s"
