# PR 24, review round, second call: the proof from the committed files. Everything runs from
# .archive_check/, the unpacked `git archive $(git write-tree)` of the final tree.
T0=$(date +%s)
export SETS_OUT=$PWD/chiprun_out/proof
TOOLS=benchmarks/tools
mkdir -p $SETS_OUT
# a directory with only BENCHMARK.json and the paths must fail and print no result
rm -rf benchmarks_out/onlypaths; mkdir -p benchmarks_out/onlypaths/tests
cp -r .archive_check/BENCHMARK.json .archive_check/benchmarks benchmarks_out/onlypaths/
cp -r .archive_check/tests/benchmarks benchmarks_out/onlypaths/tests/
( cd benchmarks_out/onlypaths && python3 benchmarks/run.py --workload mistral7b-train.pretrain-4k --seed 1 --seconds 5 --trace 0 > $SETS_OUT/onlypaths.out 2> $SETS_OUT/onlypaths.err; echo "onlypaths rc=$? result lines: $(grep -c '^{' $SETS_OUT/onlypaths.out)"; tail -2 $SETS_OUT/onlypaths.err )
cd .archive_check
keep_trace () {  # the traced run's .xplane.pb, to be reduced again off the chip
  F=$(ls benchmarks_out/$1/trace/plugins/profile/*/*.xplane.pb | tail -1)
  [ $(stat -c %s $F) -lt 30000000 ] && gzip -c $F > $SETS_OUT/$1.$2.xplane.pb.gz
}
# decode-sat: the first traced run compiles in this checkout, the second loads from the cache
for S in 2147483659 305419896; do
  echo "=== decode-sat traced seed $S at $(( $(date +%s) - T0 )) s"
  python3 $TOOLS/sets.py mistral7b-serve.decode-sat 30 trace 1 $S
  keep_trace mistral7b-serve.decode-sat $S
done
echo "=== decode-sat plain at $(( $(date +%s) - T0 )) s"
python3 $TOOLS/sets.py mistral7b-serve.decode-sat 30 plain 0 11 4000000007 3123456789
echo "=== pretrain-4k traced at $(( $(date +%s) - T0 )) s"
python3 $TOOLS/sets.py mistral7b-train.pretrain-4k 30 trace 1 2147483659
keep_trace mistral7b-train.pretrain-4k 2147483659
echo "=== pretrain-4k plain at $(( $(date +%s) - T0 )) s"
python3 $TOOLS/sets.py mistral7b-train.pretrain-4k 30 plain 0 11 4000000007 3123456789
echo "=== done at $(( $(date +%s) - T0 )) s"
