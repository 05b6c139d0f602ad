# PR 30, call 2 (one chip). Everything of the change from the unpacked `git archive $(git write-tree)` of the final
# tree (.archive_check/final), the parent from the archive of 0ae21f3 (.archive_check/parent). mixed-len-decode
# parent / change / change / parent on two shared seeds (the first run of each side compiles: its setup_s is a
# checkout's first, the second's is warm), then the change once traced through scripts/decode_ahead_microbench.py
# (engage share, experts hit over weight passes, the step against decode_pure's device time, the kernel's device
# time a step, the per-layer readings). The machine's compile cache if it brings one, else one directory for both.
R=$PWD
if [ -z "$JAX_COMPILATION_CACHE_DIR" ]; then
  export JAX_COMPILATION_CACHE_DIR=$R/.jax_cache_call; mkdir -p $JAX_COMPILATION_CACHE_DIR
fi
echo "cache $JAX_COMPILATION_CACHE_DIR"
F=$R/.archive_check/final; P=$R/.archive_check/parent
O=$R/chiprun_out; mkdir -p $O
run() {  # side, workload, seed, tag
  if [ $1 = parent ]; then cd $P; else cd $F; fi
  python3 benchmarks/run.py --workload $2 --seed $3 --seconds 30 --trace 0 \
    > $O/pr30c2_$2.$4.$1.log 2> $O/pr30c2_$2.$4.$1.err
  echo "rc=$? $1 $2 seed $3"; grep -E '^\[(run|check)\]' $O/pr30c2_$2.$4.$1.log | cut -c1-1100
  tail -n 1 $O/pr30c2_$2.$4.$1.log | cut -c1-300
}
N=mimo-v2-flash-serve.mixed-len-decode
run parent $N 3000000019 1; run change $N 3000000019 2; run change $N 3000000037 3; run parent $N 3000000037 4
cd $F && python3 scripts/decode_ahead_microbench.py --workload $N --seed 2147484157 --trace 1 \
  > $O/pr30c2_mb.log 2> $O/pr30c2_mb.err
echo "MICROBENCH rc=$?"; tail -n 1 $O/pr30c2_mb.log | cut -c1-7000
