# The chunk attention kernel's running max and sum in 128 lanes, measured on the chip (one chip), by phase:
#   sh scripts/chip_calls/chunk_lanes_call.sh <phase> [...]   (from the repo root, on a host with one TPU v5e)
# The parent is .archive_check/parent (`git archive <parent commit> | tar -x -C .archive_check/parent`); the change is this
# tree, or $CHANGE (an unpacked `git archive $(git write-tree)`). The benchmark's files are the same on both sides. All sides
# share one compile cache. Logs go to $O/chunk_lanes_<TAG>_*. Every run prints its peak host memory (ru_maxrss).
#   micro            scripts/chunk_attention_microbench.py --parent: the kernel alone at each serving configuration's chunk
#                    shape, change and parent
#   traced [cells]   the change through `benchmarks/run.py --trace 1` (default joyai)
#   pairs [cells]    untraced `benchmarks/run.py`, parent and change on a seed only the pair shares (joyai: two pairs, parent /
#                    change / change / parent; the others one pair)
#   qwen [seed]      qwen3-next-serve.long-doc-decode untraced: the change (which compiles its own chunk programs), the parent,
#                    the change again, one seed, so the last two are both warm: peak HBM (memory_peak_bytes) and peak host memory
#   one cell side seed tag   a single untraced run of one side
#   final            in order of need, each step only while $BUDGET seconds (default 1950) leave room for it: joyai traced,
#                    joyai pairs, qwen, the mimo pair
R=$PWD; O=$R/chiprun_out; mkdir -p $O
if [ -z "$JAX_COMPILATION_CACHE_DIR" ]; then
  export JAX_COMPILATION_CACHE_DIR=$R/.jax_cache_call; mkdir -p $JAX_COMPILATION_CACHE_DIR
fi
C=${CHANGE:-$R}; P=$R/.archive_check/parent; T=chunk_lanes_${TAG:-$1}
T0=${T0:-$(date +%s)}; export T0
phase=$1; shift
name() {  # a cell's short name -> N
  case $1 in
    qwen*) N=qwen3-next-serve.long-doc-decode;;
    nemo*) N=nemotron3-nano-serve.short-chat-decode;;
    joyai*) N=joyai-llm-flash-serve.long-ctx-decode;;
    mimo*) N=mimo-v2-flash-serve.mixed-len-decode;;
    sat*) N=mistral7b-serve.decode-sat;;
    train) N=mistral7b-train.pretrain-4k;;
  esac
}
side() {  # parent | change -> cd there
  case $1 in parent) cd $P;; *) cd $C;; esac
}
room() {  # seconds: true while that many are left of $BUDGET since $T0
  left=$(( ${BUDGET:-1950} - ($(date +%s) - T0) ))
  [ $left -ge $1 ] || { echo "skipped: $1 s asked, $left s left"; return 1; }
}
# a launcher: runs its arguments, then prints their peak host memory on stderr
RSS='import resource, subprocess, sys
rc = subprocess.call(sys.argv[1:])
print("maxrss_kb", resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss, file=sys.stderr)
sys.exit(rc)'
bench() {  # side, seed, trace, tag, [chars of the last line]: one run of cell N through the benchmark's command
  side $1; t0=$(date +%s)
  timeout 1500 python3 -c "$RSS" python3 benchmarks/run.py --workload $N --seed $2 --seconds 30 --trace $3 \
    > $O/${T}_$N.$4.$1.log 2> $O/${T}_$N.$4.$1.err
  echo "rc=$? $1 $N seed $2 trace $3 after $(( $(date +%s) - t0 )) s"; grep maxrss_kb $O/${T}_$N.$4.$1.err
  grep -E '^\[(warm|check)\]' $O/${T}_$N.$4.$1.log | cut -c1-300
  tail -n 1 $O/${T}_$N.$4.$1.log | cut -c1-${5:-500}; tail -n 2 $O/${T}_$N.$4.$1.err | grep -v maxrss | cut -c1-300
}
case $phase in
micro)
  cd $C; t0=$(date +%s)
  timeout 1200 python3 scripts/chunk_attention_microbench.py --parent $P > $O/${T}.jsonl 2> $O/${T}.err
  echo "rc=$? after $(( $(date +%s) - t0 )) s"; cut -c1-400 $O/${T}.jsonl; tail -n 3 $O/${T}.err | cut -c1-300;;
traced)
  [ $# -eq 0 ] && set -- joyai
  for cell in "$@"; do name $cell; bench change 3900000611 1 traced 6000; done;;
pairs)
  [ $# -eq 0 ] && set -- joyai
  for cell in "$@"; do name $cell; case $cell in
  joyai) room 300 && bench parent 3900000631 0 1; room 300 && bench change 3900000631 0 2
         room 300 && bench change 2390000653 0 3; room 300 && bench parent 2390000653 0 4;;
  mimo) room 380 && bench parent 3900000691 0 1; room 200 && bench change 3900000691 0 2;;
  sat) bench change 3900000357 0 1; bench parent 3900000357 0 2;;
  nemo) bench parent 3900000373 0 1; bench change 3900000373 0 2;;
  train) bench change 3900000377 0 1; bench parent 3900000377 0 2;;
  esac; done;;
qwen)
  name qwen; s=${1:-3900000677}
  room 420 && bench change $s 0 mem1; room 300 && bench parent $s 0 mem; room 200 && bench change $s 0 mem2;;
one)  # cell side seed tag: a single untraced run
  name $1; bench $2 $3 0 $4;;
final)
  sh $R/scripts/chip_calls/chunk_lanes_call.sh traced joyai
  cd $R; sh $R/scripts/chip_calls/chunk_lanes_call.sh pairs joyai
  cd $R; sh $R/scripts/chip_calls/chunk_lanes_call.sh qwen
  cd $R; sh $R/scripts/chip_calls/chunk_lanes_call.sh pairs mimo;;
esac
