# PR 31, call 4 (one chip): the committed files are enough. From the unpacked `git archive $(git write-tree)` of the
# final tree (.archive_check/final): the new cell once traced and three times untraced, each a seed of its own, and
# the tolerance's two controls under the final tolerance on two seeds. From the parent's archive with this PR's
# BENCHMARK.json, benchmarks/ and tests/benchmarks/ laid over it (.archive_check/parent_overlay): the new cell, which
# has to fail at once (no such model), and one old cell traced (the new benchmark files must work with a program that
# lacks what this PR adds).
R=$PWD
if [ -z "$JAX_COMPILATION_CACHE_DIR" ]; then
  export JAX_COMPILATION_CACHE_DIR=$R/.jax_cache_call; mkdir -p $JAX_COMPILATION_CACHE_DIR
fi
F=$R/.archive_check/final; V=$R/.archive_check/parent_overlay
O=$R/chiprun_out; mkdir -p $O
N=joyai-llm-flash-serve.long-ctx-decode
cd $V
t0=$(date +%s); timeout 300 python3 benchmarks/run.py --workload $N --seed 5 --seconds 30 --trace 0 > $O/pr31c4_overlay_new.log 2> $O/pr31c4_overlay_new.err
echo "PARENT+OVERLAY new cell: rc=$? after $(( $(date +%s) - t0 )) s"; tail -n 2 $O/pr31c4_overlay_new.err | cut -c1-300
python3 benchmarks/run.py --workload mistral7b-serve.decode-sat --seed 2800000001 --seconds 30 --trace 1 > $O/pr31c4_overlay_old.log 2> $O/pr31c4_overlay_old.err
echo "PARENT+OVERLAY decode-sat traced: rc=$?"; tail -n 1 $O/pr31c4_overlay_old.log | cut -c1-1800
cd $F
SETS_OUT=$O python3 benchmarks/tools/sets.py $N 30 pr31c4t 1 2900000039 2>&1 | cut -c1-3500
SETS_OUT=$O python3 benchmarks/tools/sets.py $N 30 pr31c4 0 3600000071 2700000023 4200000037 2>&1 | cut -c1-600
python3 benchmarks/tools/joyai_precision.py rows_through_int8,weights_through_int8 2147483867 3700000051 \
  > $O/pr31c4_precision.jsonl 2> $O/pr31c4_precision.err
echo "precision rc=$?"; grep '^{' $O/pr31c4_precision.jsonl | python3 -c "
import sys, json
for ln in sys.stdin:
    d = json.loads(ln)
    errs = [round(r[2], 5) for r in d['by_margin'] if r[0][0] != 'mtp']
    print(d['seed'], d['variant'], 'ok', d['ok'], 'tolerance', d['tolerance'], 'worst', round(d['worst'], 5), 'best', min(errs), 'mtp', round(d['worst_mtp'], 5), 'routed otherwise', d['routed_otherwise'])
"
tail -n 3 $O/pr31c4_precision.err | cut -c1-400
