# the guard cell, parent against change on one chip (parent, change, change,
# parent), then the overlay trial: the new cell on the parent's program must
# fail soon and cleanly, an old cell traced with this PR's benchmark files must run
set -x
R=$PWD
W=mistral7b-serve.decode-sat
i=0
for side in parent change change parent; do
  i=$((i+1))
  if [ $side = parent ]; then cd $R/.archive_check/parent; else cd $R; fi
  python3 benchmarks/run.py --workload $W --seed $((3000000000 + (i+1)/2)) --seconds 30 --trace 0 > $R/chiprun_out/guard_$i.$side.log 2> $R/chiprun_out/guard_$i.$side.err
  echo "rc=$? $side"; tail -n 1 $R/chiprun_out/guard_$i.$side.log | cut -c1-600
done
cd $R/.archive_check/parent_overlay
( time python3 benchmarks/run.py --workload mimo-v2-flash-serve.mixed-len-decode --seed 5 --seconds 30 --trace 0 ) > $R/chiprun_out/overlay_new.log 2> $R/chiprun_out/overlay_new.err
echo "overlay new cell on parent rc=$?"; tail -n 4 $R/chiprun_out/overlay_new.err
python3 benchmarks/run.py --workload $W --seed 3000000009 --seconds 30 --trace 1 > $R/chiprun_out/overlay_old.log 2> $R/chiprun_out/overlay_old.err
echo "overlay old cell traced rc=$?"; tail -n 1 $R/chiprun_out/overlay_old.log | cut -c1-1500
