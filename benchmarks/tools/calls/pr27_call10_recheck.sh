# the check after the deeper search: the seed that failed (3333333333) and two fresh ones through
# the precision tool, then that seed's run of the cell from the unpacked archive of the staged tree
set -x
R=$PWD
python3 benchmarks/tools/mimo_precision.py 3333333333 2222222223 4111111111 > chiprun_out/mimo_precision2.jsonl 2> chiprun_out/mimo_precision2.err
echo rc=$?
grep '^{' chiprun_out/mimo_precision2.jsonl | python3 -c "
import sys, json
for ln in sys.stdin:
    d = json.loads(ln)
    print(d['seed'], d['variant'], 'ok', d['ok'], 'worst', round(d['worst'], 5), 'routed otherwise', d['routed_otherwise'], 'left out', d['left_out'], [round(r[2], 4) for r in d['by_margin']])
"
cd $R/.archive_check/final
python3 benchmarks/run.py --workload mimo-v2-flash-serve.mixed-len-decode --seed 3333333333 --seconds 30 --trace 0 > $R/chiprun_out/final_new0.log 2> $R/chiprun_out/final_new0.err
echo "final new cell plain rc=$?"; tail -n 1 $R/chiprun_out/final_new0.log | cut -c1-600
grep "^\[check\]" $R/chiprun_out/final_new0.log | cut -c1-400
