# PR 33, call 8 (one chip; the last): the limits as committed (tolerance 0.021, margin_limit 0.02) through the committed
# verdict, from .archive_check/final (an unpacked `git archive $(git write-tree)` of the final tree): the cell once more on a
# seed of its own, then the engine as stated and the two controls that must read not correct on another.
#   chiprun --timeout 1000 -- sh benchmarks/tools/calls/pr33_call8.sh
R=$PWD; O=$R/chiprun_out; mkdir -p $O
N=nemotron3-nano-serve.short-chat-decode
cd $R/.archive_check/final
SETS_OUT=$O python3 benchmarks/tools/sets.py $N 30 pr33last 0 1388888917 2>&1 | cut -c1-500
grep -o '"check": {[^}]*' $O/$N.pr33last.jsonl | cut -c1-300
timeout 600 python3 benchmarks/tools/nemotron_precision.py stated,scan_inputs_through_int8,weights_through_int8 1199999989 > $O/pr33_precision8.jsonl 2> $O/pr33_precision8.err
echo "precision rc=$?"; grep '^{' $O/pr33_precision8.jsonl | cut -c1-700
