# PR 37, the chip calls (one chip each), by phase: `chiprun --timeout <s> -- sh scripts/chip_calls/pr37_call.sh <phase> [...]`.
# The parent is .archive_check/parent (`git archive f0e7940 | tar -x -C .archive_check/parent`); the change is this tree, or
# $CHANGE (an unpacked `git archive $(git write-tree)`). All sides share one compile cache. Logs go to chiprun_out/pr37_*.
#   first            the two forms of the delta rule alone (scripts/gated_delta_microbench.py, with the profile probe), then the
#                    new cell once, traced: the first sight of its programs on the chip
#   precision V S..  benchmarks/tools/qwen3_next_precision.py <variants> <seeds...>: the readings the check's limits lie between
#   cell [seeds]     the new cell untraced on each seed (default six), then once traced, from $CHANGE
#   pairs [cells]    untraced `benchmarks/run.py`, parent and change on a seed only the pair shares: sat mimo joyai nemo train;
#                    `qwen`: the new cell once more, traced, from $CHANGE (the committed files as they are handed in)
#   last             the new cell traced from $CHANGE, its peak host memory printed; then `parent_new`
#   parent_new       the parent with this PR's benchmark files laid over it (.archive_check/parent_bench), asked for the new
#                    cell: it has to fail at once and non-zero; then an old cell traced there, which has to succeed
R=$PWD; O=$R/chiprun_out; mkdir -p $O
if [ -z "$JAX_COMPILATION_CACHE_DIR" ]; then
  export JAX_COMPILATION_CACHE_DIR=$R/.jax_cache_call; mkdir -p $JAX_COMPILATION_CACHE_DIR
fi
C=${CHANGE:-$R}; P=$R/.archive_check/parent; T=pr37_${TAG:-$1}
Q=qwen3-next-serve.long-doc-decode
phase=$1; shift
name() {  # a cell's short name -> N
  case $1 in
    qwen*) N=$Q;;
    nemo*) N=nemotron3-nano-serve.short-chat-decode;;
    joyai*) N=joyai-llm-flash-serve.long-ctx-decode;;
    mimo*) N=mimo-v2-flash-serve.mixed-len-decode;;
    sat*) N=mistral7b-serve.decode-sat;;
    train) N=mistral7b-train.pretrain-4k;;
  esac
}
bench() {  # side, seed, trace, tag, [chars of the last line]: one run of cell N through the benchmark's command
  case $1 in parent) cd $P;; parent_bench) cd $R/.archive_check/parent_bench;; *) cd $C;; esac
  t0=$(date +%s)
  timeout 1500 python3 benchmarks/run.py --workload $N --seed $2 --seconds 30 --trace $3 \
    > $O/${T}_$N.$4.$1.log 2> $O/${T}_$N.$4.$1.err
  echo "rc=$? $1 $N seed $2 trace $3 after $(( $(date +%s) - t0 )) s"
  grep -E '^\[(warm|check)\]' $O/${T}_$N.$4.$1.log | cut -c1-420
  grep -E '^\[run\]' $O/${T}_$N.$4.$1.log | cut -c1-${6:-200}
  tail -n 1 $O/${T}_$N.$4.$1.log | cut -c1-${5:-400}; tail -n 2 $O/${T}_$N.$4.$1.err | cut -c1-300
}
case $phase in
first)
  cd $C
  timeout 900 python3 scripts/gated_delta_microbench.py --probe $O/pr37_probe > $O/pr37_micro.txt 2> $O/pr37_micro.err
  echo "micro rc=$?"; cat $O/pr37_micro.txt | cut -c1-1500; tail -5 $O/pr37_micro.err | cut -c1-400
  rm -rf $O/pr37_probe
  N=$Q; bench change ${1:-3700000019} 1 first 6000 4000;;
precision)
  cd $C; V=$1; shift
  timeout 3300 python3 benchmarks/tools/qwen3_next_precision.py $V "$@" > $O/${T}_$V.jsonl 2> $O/${T}_$V.err
  echo "rc=$? precision $V $*"; grep '^{' $O/${T}_$V.jsonl | cut -c1-900; tail -n 3 $O/${T}_$V.err | cut -c1-300;;
cell)
  [ $# -eq 0 ] && set -- 3700000101 2370000113 3700000127 2370000139 3700000151 2370000163
  N=$Q; i=0
  for seed in "$@"; do i=$((i + 1)); bench change $seed 0 run$i 700; done
  bench change 3700000177 1 traced 6000 3000;;
pairs)
  [ $# -eq 0 ] && set -- nemo mimo joyai sat train
  for cell in "$@"; do name $cell; case $cell in
  nemo) bench parent 3700000211 0 1; bench change 3700000211 0 2;;
  mimo) bench change 3700000223 0 1; bench parent 3700000223 0 2;;
  joyai) bench parent 3700000239 0 1; bench change 3700000239 0 2;;
  sat) bench change 3700000251 0 1; bench parent 3700000251 0 2;;
  train) bench parent 3700000263 0 1; bench change 3700000263 0 2;;
  qwen) bench change 3700000277 1 final 6000 3000;;   # this one was ended for the machine's 40 GiB of host memory: `last` below
  esac; done;;
parent_new)
  N=$Q; bench parent_bench 3700000301 0 new
  N=mistral7b-serve.decode-sat; bench parent_bench 3700000313 1 old 1500;;
last)  # the new cell traced from $CHANGE under a launcher that prints the run's peak host memory, then `parent_new`
  cd $C; t0=$(date +%s)
  timeout 1500 python3 -c 'import resource, subprocess, sys
rc = subprocess.call(sys.argv[1:])
print("maxrss_kb", resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss, file=sys.stderr)
sys.exit(rc)' python3 benchmarks/run.py --workload $Q --seed 3700000291 --seconds 30 --trace 1 \
    > $O/pr37_last_$Q.log 2> $O/pr37_last_$Q.err
  echo "rc=$? change $Q seed 3700000291 trace 1 after $(( $(date +%s) - t0 )) s"; grep maxrss_kb $O/pr37_last_$Q.err
  grep -E '^\[(warm|check)\]' $O/pr37_last_$Q.log | cut -c1-420; tail -n 1 $O/pr37_last_$Q.log | cut -c1-3000
  cd $R; sh scripts/chip_calls/pr37_call.sh parent_new;;
esac
