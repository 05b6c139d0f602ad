# review round, last call: the guard cell parent against change on one chip (parent, change, change, parent,
# shared seeds), the change being the unpacked `git archive` of the staged tree; the new cell traced from that
# tree; then a second set of six plain runs of the new cell from it, a process and a seed each
R=$PWD
W=mistral7b-serve.decode-sat
i=0
for side in parent change change parent; do
  i=$((i+1))
  if [ $side = parent ]; then cd $R/.archive_check/parent; else cd $R/.archive_check/final; fi
  python3 benchmarks/run.py --workload $W --seed $((3100000000 + (i+1)/2)) --seconds 30 --trace 0 > $R/chiprun_out/guard2_$i.$side.log 2> $R/chiprun_out/guard2_$i.$side.err
  echo "rc=$? $side"; tail -n 1 $R/chiprun_out/guard2_$i.$side.log | cut -c1-400
done
cd $R/.archive_check/final
N=mimo-v2-flash-serve.mixed-len-decode
SETS_OUT=$R/chiprun_out python3 benchmarks/tools/sets.py $N 30 pr27w 1 2147483801
SETS_OUT=$R/chiprun_out python3 benchmarks/tools/sets.py $N 30 pr27x 0 2300000003 2500000009 2700000011 3300000017 3700000027 4200000037
