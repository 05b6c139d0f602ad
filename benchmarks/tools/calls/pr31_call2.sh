# PR 31, call 2 (one chip), the latent kernel's full chunks issued in written-out groups: a set of six untraced
# runs of the new cell on seeds not used before (benchmarks/tools/sets.py), the cell once traced in one process
# through scripts/decode_ahead_microbench.py (section 5's breakdown), and the tolerance's readings on two more seeds.
O=chiprun_out; mkdir -p $O
N=joyai-llm-flash-serve.long-ctx-decode
python3 benchmarks/tools/sets.py $N 30 pr31c2 0 2147483659 3000000017 3500000011 4100000023 2500000033 3900000007 2>&1 | cut -c1-700
python3 scripts/decode_ahead_microbench.py --workload $N --seed 2147485003 --trace 1 > $O/pr31c2_mb.log 2> $O/pr31c2_mb.err
echo "MICROBENCH rc=$?"; tail -n 1 $O/pr31c2_mb.log | cut -c1-9000; tail -n 3 $O/pr31c2_mb.err | cut -c1-400
python3 benchmarks/tools/joyai_precision.py stated,rows_through_int8,weights_through_int8 2147483867 3700000051 \
  > $O/pr31c2_precision.jsonl 2> $O/pr31c2_precision.err
echo "precision rc=$?"; grep '^{' $O/pr31c2_precision.jsonl | python3 -c "
import sys, json
for ln in sys.stdin:
    d = json.loads(ln)
    errs = [round(r[2], 5) for r in d['by_margin'] if r[0][0] != 'mtp']
    print(d['seed'], d['variant'], 'ok', d['ok'], 'worst', round(d['worst'], 5), 'best', min(errs), 'mtp', round(d['worst_mtp'], 5), 'routed otherwise', d['routed_otherwise'])
"
tail -n 3 $O/pr31c2_precision.err | cut -c1-400
