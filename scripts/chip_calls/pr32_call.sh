# PR 32, the chip calls (one chip each), by phase: `chiprun -- sh scripts/chip_calls/pr32_call.sh <phase> [...]`.
# Every chip command runs under `timeout`: a DMA wait without its bytes hangs. The parent is .archive_check/parent
# (`git archive 13c4a0a | tar -x -C .archive_check/parent`); the change is this tree, or $CHANGE (an unpacked
# `git archive $(git write-tree)`). Logs go to chiprun_out/pr32_<TAG>_*.
#   probe            step 0 of the issue, alone: does one DMA wait as large as all the page copies on its semaphore wait
#                    for them all? (scripts/dma_wait_probe.py: smallest shape first, a line a case before it starts,
#                    so a hang names its case; 124 = it hung and `timeout` ended it)
#   kernel           chip_smoke's decode-kernel phase alone (every chunk edge at Mistral's geometry against the lax
#                    fallback, NaN in every dead slot), then the kernel alone at the four cells' shapes and a tp4
#                    shard's, parent against change and against whatever variants lie in .archive_check/v*_*/ (copies
#                    of paged_attention.py with another way to start or to wait), one process a shape on the same pools
#   cells [names]    joyai mimo sat train (default all; joyai2: two more pairs of joyai): the cell's pairs, parent /
#                    change / change / parent, each pair on a seed only the pair shares
#   traced [names]   joyai mimo sat: the change's cell traced in one process through scripts/decode_ahead_microbench.py
#   final            `benchmarks/run.py --trace 1` of the claimed cell from $CHANGE: the committed files are enough
R=$PWD; O=$R/chiprun_out; mkdir -p $O
if [ -z "$JAX_COMPILATION_CACHE_DIR" ]; then
  export JAX_COMPILATION_CACHE_DIR=$R/.jax_cache_call; mkdir -p $JAX_COMPILATION_CACHE_DIR
fi
C=${CHANGE:-$R}; P=$R/.archive_check/parent; T=pr32_${TAG:-$1}
phase=$1; shift
name() {  # a cell's short name -> N, and the seed S of its traced run
  case $1 in
    joyai) N=joyai-llm-flash-serve.long-ctx-decode; S=2147485017;;
    mimo) N=mimo-v2-flash-serve.mixed-len-decode; S=2147484163;;
    sat) N=mistral7b-serve.decode-sat; S=2147484001;;
    train) N=mistral7b-train.pretrain-4k;;
  esac
}
mb() {  # tag, arguments
  tag=$1; shift
  timeout 420 python3 scripts/paged_decode_microbench.py $V "$@" > $O/${T}_$tag.log 2> $O/${T}_$tag.err
  echo "MICROBENCH $tag rc=$?"; cut -c1-420 $O/${T}_$tag.log; tail -c 600 $O/${T}_$tag.err | grep -v hugepages
}
run() {  # side, seed, tag: one untraced run of cell N
  if [ $1 = parent ]; then cd $P; else cd $C; fi
  timeout 900 python3 benchmarks/run.py --workload $N --seed $2 --seconds 30 --trace 0 \
    > $O/${T}_$N.$3.$1.log 2> $O/${T}_$N.$3.$1.err
  echo "rc=$? $1 $N seed $2"; grep -E '^\[(run|check)\]' $O/${T}_$N.$3.$1.log | cut -c1-160
  tail -n 1 $O/${T}_$N.$3.$1.log | cut -c1-330
}
case $phase in
probe)
  timeout 150 python3 scripts/dma_wait_probe.py > $O/${T}.log 2> $O/${T}.err
  echo "PROBE rc=$?"; grep -v '"started"' $O/${T}.log | cut -c1-300; tail -c 1500 $O/${T}.err;;
kernel)
  cd $C
  timeout 300 python3 -c "import chip_smoke as c; c.phase_decode_kernel(); print('decode kernel phase: ok')" \
    > $O/${T}_smoke.log 2> $O/${T}_smoke.err
  echo "SMOKE rc=$?"; tail -n 3 $O/${T}_smoke.log | cut -c1-300; tail -c 600 $O/${T}_smoke.err | grep -v hugepages
  V="--parent $P"
  for v in $R/.archive_check/v*_*; do [ -d $v ] && V="$V --parent $v"; done
  mb latent --kind latent; mb global --kind global; mb window --kind window
  mb gqa; mb tp4 --heads 8 --kv-heads 2;;
cells)
  echo "cache $JAX_COMPILATION_CACHE_DIR"
  [ $# -eq 0 ] && set -- joyai mimo sat train
  for cell in "$@"; do name $cell; case $cell in
  joyai)
    run parent 3200000021 1; run change 3200000021 2; run change 2300000069 3; run parent 2300000069 4
    run parent 2500000043 5; run change 2500000043 6; run change 2700000011 7; run parent 2700000011 8;;
  joyai2) name joyai
    run parent 2900000017 9; run change 2900000017 10; run change 3100000093 11; run parent 3100000093 12;;
  mimo) run parent 3200000051 1; run change 3200000051 2; run change 2300000101 3; run parent 2300000101 4;;
  sat) run parent 3200000087 1; run change 3200000087 2; run change 2400000023 3; run parent 2400000023 4;;
  train) run parent 2600000071 1; run change 2600000071 2;;
  esac; done;;
traced)
  [ $# -eq 0 ] && set -- joyai mimo sat
  cd $C
  for cell in "$@"; do name $cell
    timeout 900 python3 scripts/decode_ahead_microbench.py --workload $N --seed $S --trace 1 \
      > $O/${T}_$N.log 2> $O/${T}_$N.err
    echo "rc=$? traced $N"; tail -n 1 $O/${T}_$N.log | cut -c1-6000
  done;;
final)  # the benchmark's own traced run of the claimed cell, from $CHANGE
  name joyai; cd $C
  timeout 900 python3 benchmarks/run.py --workload $N --seed 2147483777 --seconds 30 --trace 1 \
    > $O/${T}_$N.log 2> $O/${T}_$N.err
  echo "rc=$? traced run $N"; tail -n 1 $O/${T}_$N.log | cut -c1-3000;;
esac
