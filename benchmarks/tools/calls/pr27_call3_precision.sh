python3 benchmarks/tools/mimo_precision.py 2147483651 77 4000000007 > chiprun_out/mimo_precision.jsonl 2> chiprun_out/mimo_precision.err
echo rc=$?
grep '^{' chiprun_out/mimo_precision.jsonl | python3 -c "
import sys, json
for ln in sys.stdin:
    d = json.loads(ln)
    errs = [round(r[2], 5) for r in d['by_margin']]
    print(d['seed'], d['variant'], 'worst', round(d['worst'], 5), 'min', min(errs), 'routed otherwise', d['routed_otherwise'], errs)
"
tail -c 1500 chiprun_out/mimo_precision.err
