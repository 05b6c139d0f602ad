# PR 28, call 1 (one chip): the change traced through scripts/decode_ahead_microbench.py in both serving cells
# (engage share, step time, the benchmark's per-layer readings), then parent against change on shared seeds,
# parent / change / change / parent, untraced, through benchmarks/run.py. The parent is the `git archive` of
# ca9c47e under .archive_check/parent. One compile cache for both sides, so that what they share compiles once.
R=$PWD
export JAX_COMPILATION_CACHE_DIR=$R/.jax_cache_call; mkdir -p $JAX_COMPILATION_CACHE_DIR
mkdir -p $R/chiprun_out
pairs() {  # workload, seed base
  i=0
  for side in parent change change parent; do
    i=$((i+1))
    if [ $side = parent ]; then cd $R/.archive_check/parent; else cd $R; fi
    python3 benchmarks/run.py --workload $1 --seed $(($2 + (i+1)/2)) --seconds 30 --trace 0 \
      > $R/chiprun_out/pr28c1_$1.$i.$side.log 2> $R/chiprun_out/pr28c1_$1.$i.$side.err
    echo "rc=$? $side seed $(($2 + (i+1)/2))"; grep '^\[run\]' $R/chiprun_out/pr28c1_$1.$i.$side.log | cut -c1-1400
    tail -n 1 $R/chiprun_out/pr28c1_$1.$i.$side.log | cut -c1-300
  done
  cd $R
}
for W in mimo-v2-flash-serve.mixed-len-decode mistral7b-serve.decode-sat; do
  python3 scripts/decode_ahead_microbench.py --workload $W --seed 2147483907 --trace 1 \
    > $R/chiprun_out/pr28c1_mb_$W.log 2> $R/chiprun_out/pr28c1_mb_$W.err
  rc=$?
  echo "MICROBENCH $W rc=$rc"; tail -n 1 $R/chiprun_out/pr28c1_mb_$W.log | cut -c1-6000
  if [ $rc != 0 ] || ! tail -n 1 $R/chiprun_out/pr28c1_mb_$W.log | grep -q '"correct": true'; then
    tail -c 3000 $R/chiprun_out/pr28c1_mb_$W.err; exit 1
  fi
  if [ $W = mistral7b-serve.decode-sat ]; then pairs $W 3500000000; else pairs $W 2900000000; fi
done
