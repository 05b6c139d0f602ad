# PR 33, call 4 (one chip): the four cells the benchmark had, parent against change, two pairs each: parent / change /
# change / parent, each pair on a seed only the pair shares. The parent is .archive_check/parent (`git archive 9e42f02 |
# tar -x -C .archive_check/parent`); the change is this tree, or $CHANGE (an unpacked `git archive $(git write-tree)`).
#   chiprun --timeout 3500 -- sh benchmarks/tools/calls/pr33_call4.sh [joyai mimo sat train]
R=$PWD; O=$R/chiprun_out; mkdir -p $O
C=${CHANGE:-$R}; P=$R/.archive_check/parent
run() {  # side, seed, tag: one untraced run of cell N
  if [ $1 = parent ]; then cd $P; else cd $C; fi
  timeout 900 python3 benchmarks/run.py --workload $N --seed $2 --seconds 30 --trace 0 \
    > $O/pr33_$N.$3.$1.log 2> $O/pr33_$N.$3.$1.err
  echo "rc=$? $1 $N seed $2"; grep -E '^\[check\]' $O/pr33_$N.$3.$1.log | cut -c1-120
  tail -n 1 $O/pr33_$N.$3.$1.log | cut -c1-330
}
[ $# -eq 0 ] && set -- joyai mimo sat train
for cell in "$@"; do case $cell in
  joyai) N=joyai-llm-flash-serve.long-ctx-decode
    run parent 3300000029 1; run change 3300000029 2; run change 2330000111 3; run parent 2330000111 4;;
  mimo) N=mimo-v2-flash-serve.mixed-len-decode
    run parent 3300000067 1; run change 3300000067 2; run change 2330000147 3; run parent 2330000147 4;;
  sat) N=mistral7b-serve.decode-sat
    run parent 3300000101 1; run change 3300000101 2; run change 2430000019 3; run parent 2430000019 4;;
  train) N=mistral7b-train.pretrain-4k
    run parent 2630000089 1; run change 2630000089 2; run change 2730000013 3; run parent 2730000013 4;;
esac; done
