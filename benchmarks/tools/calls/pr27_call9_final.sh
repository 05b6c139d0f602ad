# the committed files are enough: the unpacked `git archive` of the staged tree
# runs the new cell traced and the guard cell plain; the parent's program under
# this PR's benchmark files fails the new cell soon and cleanly
set -x
R=$PWD
cd $R/.archive_check/final
python3 benchmarks/run.py --workload mimo-v2-flash-serve.mixed-len-decode --seed 2147483693 --seconds 30 --trace 1 > $R/chiprun_out/final_new.log 2> $R/chiprun_out/final_new.err
echo "final new cell traced rc=$?"; tail -n 1 $R/chiprun_out/final_new.log | cut -c1-1800
python3 benchmarks/run.py --workload mimo-v2-flash-serve.mixed-len-decode --seed 3333333333 --seconds 30 --trace 0 > $R/chiprun_out/final_new0.log 2> $R/chiprun_out/final_new0.err
echo "final new cell plain rc=$?"; tail -n 1 $R/chiprun_out/final_new0.log | cut -c1-600
python3 benchmarks/run.py --workload mistral7b-serve.decode-sat --seed 3000000011 --seconds 30 --trace 0 > $R/chiprun_out/final_old.log 2> $R/chiprun_out/final_old.err
echo "final guard cell rc=$?"; tail -n 1 $R/chiprun_out/final_old.log | cut -c1-600
cd $R/.archive_check/parent_overlay
( time python3 benchmarks/run.py --workload mimo-v2-flash-serve.mixed-len-decode --seed 5 --seconds 30 --trace 1 ) > $R/chiprun_out/overlay_new.log 2> $R/chiprun_out/overlay_new.err
echo "overlay new cell on parent rc=$?"; tail -n 5 $R/chiprun_out/overlay_new.err
