# PR 25, first chip call. The change runs from the working tree; .archive_check/parent is the
# unpacked `git archive` of the parent commit (b8df27c), .archive_check/overlay the same with this
# PR's BENCHMARK.json, benchmarks/ and tests/benchmarks/ laid over it (how the driver traces the
# parent). One compile cache for all three, so that programs both sides share compile once.
T0=$(date +%s)
ROOT=$PWD
export SETS_OUT=$ROOT/chiprun_out/pr25_call1
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache
TOOLS=benchmarks/tools
SERVE=mistral7b-serve.decode-sat
TRAIN=mistral7b-train.pretrain-4k
mkdir -p $SETS_OUT
at () { echo "=== $1 at $(( $(date +%s) - T0 )) s"; }
keep_trace () {  # the traced run's .xplane.pb, to be reduced again off the chip
  F=$(ls benchmarks_out/$1/trace/plugins/profile/*/*.xplane.pb | tail -1)
  [ $(stat -c %s $F) -lt 25000000 ] && gzip -c $F > $SETS_OUT/$1.$2.xplane.pb.gz
}
# 1. the change, decode-sat traced, three processes: the phases, the five new metrics, the names
for S in 2147483659 305419896 3123456789; do
  at "change decode-sat traced seed $S"
  python3 $TOOLS/sets.py $SERVE 30 change.trace 1 $S
done
keep_trace $SERVE 3123456789
python3 $TOOLS/dump_xplane.py benchmarks_out/$SERVE/trace > $SETS_OUT/dump_xplane.decode-sat.txt 2>&1
# 2. the parent under this PR's benchmark files: the traced run must work and leave the new metrics out
at "overlay decode-sat traced"
( cd .archive_check/overlay && python3 $TOOLS/sets.py $SERVE 30 overlay.trace 1 77 )
# 3. what the spans cost with the tracer on
at "tracer cost"
python3 $TOOLS/calls/pr25_tracer_cost.py 11 12 50 > $SETS_OUT/tracer_cost.json 2> $SETS_OUT/tracer_cost.err
tail -1 $SETS_OUT/tracer_cost.json; tail -2 $SETS_OUT/tracer_cost.err
# 4. end to end, parent, change, change, parent; the two sides of a comparison share a seed
at "decode-sat plain"
( cd .archive_check/parent && python3 $TOOLS/sets.py $SERVE 30 parent.plain 0 11 )
python3 $TOOLS/sets.py $SERVE 30 change.plain 0 11 4000000007
( cd .archive_check/parent && python3 $TOOLS/sets.py $SERVE 30 parent.plain 0 4000000007 )
# 5. the training cell: the flash kernels' names in the trace, then end to end
at "change pretrain-4k traced"
python3 $TOOLS/sets.py $TRAIN 30 change.trace 1 2147483659
python3 $TOOLS/dump_xplane.py benchmarks_out/$TRAIN/trace > $SETS_OUT/dump_xplane.pretrain-4k.txt 2>&1
at "pretrain-4k plain"
( cd .archive_check/parent && python3 $TOOLS/sets.py $TRAIN 30 parent.plain 0 11 )
python3 $TOOLS/sets.py $TRAIN 30 change.plain 0 11 4000000007
( cd .archive_check/parent && python3 $TOOLS/sets.py $TRAIN 30 parent.plain 0 4000000007 )
at "done"
