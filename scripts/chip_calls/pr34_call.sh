# PR 34, the chip calls (one chip each), by phase: `chiprun --timeout <s> -- sh scripts/chip_calls/pr34_call.sh <phase> [...]`.
# The parent is .archive_check/parent (`git archive a63d4e1 | tar -x -C .archive_check/parent`); the change is this tree, or
# $CHANGE (an unpacked `git archive $(git write-tree)`). Both sides share one compile cache and their programs are the same
# texts (scripts/chip_calls/pr34_lowered_text.sh), but a Mosaic kernel's module names its source's path: a checkout's first
# run of a cell compiles anew (`setup_s` 137-337 s), so compare `setup_s` between later runs only.
# Logs go to chiprun_out/pr34_<TAG>_*.
#   cells [names]    nemo joyai mimo sat train (default nemo): a pair of the cell, parent then change, on a seed only the pair
#                    shares (nemo: three pairs, parent / change / change / parent / parent / change); joyai2 mimo2 sat2: a second
#                    pair, change then parent; nemo2: three more pairs, change / parent / parent / change / change / parent
#   traced [sides]   parent change (default both): short-chat-decode traced in one process through
#                    scripts/decode_ahead_microbench.py: idle share, idle seconds by host span, the engage shares
#   final            `benchmarks/run.py --trace 1` of the claimed cell from $CHANGE: the committed files are enough
R=$PWD; O=$R/chiprun_out; mkdir -p $O
if [ -z "$JAX_COMPILATION_CACHE_DIR" ]; then
  export JAX_COMPILATION_CACHE_DIR=$R/.jax_cache_call; mkdir -p $JAX_COMPILATION_CACHE_DIR
fi
C=${CHANGE:-$R}; P=$R/.archive_check/parent; T=pr34_${TAG:-$1}
phase=$1; shift
name() {  # a cell's short name -> N
  case $1 in
    nemo*) N=nemotron3-nano-serve.short-chat-decode;;
    joyai*) N=joyai-llm-flash-serve.long-ctx-decode;;
    mimo*) N=mimo-v2-flash-serve.mixed-len-decode;;
    sat*) N=mistral7b-serve.decode-sat;;
    train) N=mistral7b-train.pretrain-4k;;
  esac
}
run() {  # side, seed, tag: one untraced run of cell N
  if [ $1 = parent ]; then cd $P; else cd $C; fi
  timeout 900 python3 benchmarks/run.py --workload $N --seed $2 --seconds 30 --trace 0 \
    > $O/${T}_$N.$3.$1.log 2> $O/${T}_$N.$3.$1.err
  echo "rc=$? $1 $N seed $2"; grep -E '^\[(run|check)\]' $O/${T}_$N.$3.$1.log | cut -c1-160
  tail -n 1 $O/${T}_$N.$3.$1.log | cut -c1-330
}
case $phase in
cells)
  echo "cache $JAX_COMPILATION_CACHE_DIR"
  [ $# -eq 0 ] && set -- nemo
  for cell in "$@"; do name $cell; case $cell in
  nemo)
    run parent 3400000033 1; run change 3400000033 2; run change 2340000071 3; run parent 2340000071 4
    run parent 2540000029 5; run change 2540000029 6;;
  nemo2)
    run change 2740000049 7; run parent 2740000049 8; run parent 2940000013 9; run change 2940000013 10
    run change 3140000087 11; run parent 3140000087 12;;
  joyai) run parent 3400000079 1; run change 3400000079 2;;
  joyai2) run change 2340000123 3; run parent 2340000123 4;;
  mimo) run parent 3400000117 1; run change 3400000117 2;;
  mimo2) run change 2340000161 3; run parent 2340000161 4;;
  sat) run parent 3400000151 1; run change 3400000151 2;;
  sat2) run change 2440000037 3; run parent 2440000037 4;;
  train) run parent 2640000091 1; run change 2640000091 2;;
  esac; done;;
traced)
  [ $# -eq 0 ] && set -- parent change
  name nemo
  for side in "$@"; do
    if [ $side = parent ]; then cd $P; else cd $C; fi
    timeout 900 python3 scripts/decode_ahead_microbench.py --workload $N --seed 2147485211 --trace 1 \
      > $O/${T}_$N.$side.log 2> $O/${T}_$N.$side.err
    echo "rc=$? traced $side $N"; tail -n 1 $O/${T}_$N.$side.log | cut -c1-7000
  done;;
final)  # the benchmark's own traced run of the claimed cell, from $CHANGE
  name nemo; cd $C
  timeout 900 python3 benchmarks/run.py --workload $N --seed 2147483951 --seconds 30 --trace 1 \
    > $O/${T}_$N.log 2> $O/${T}_$N.err
  echo "rc=$? traced run $N"; tail -n 1 $O/${T}_$N.log | cut -c1-4000;;
esac
