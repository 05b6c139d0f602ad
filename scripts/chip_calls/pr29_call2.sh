# PR 29, call 2 (one chip), as call 1: mixed-len-decode parent / change / change / parent on two shared seeds from the
# two archives, then the change once traced through scripts/decode_ahead_microbench.py.
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
    > $O/pr29c2_$2.$4.$1.log 2> $O/pr29c2_$2.$4.$1.err
  echo "rc=$? $1 $2 seed $3"; grep -E '^\[(run|check)\]' $O/pr29c2_$2.$4.$1.log | cut -c1-1100
  tail -n 1 $O/pr29c2_$2.$4.$1.log | cut -c1-300
}
N=mimo-v2-flash-serve.mixed-len-decode
run parent $N 2900000059 1; run change $N 2900000059 2; run change $N 2900000061 3; run parent $N 2900000061 4
cd $F && python3 scripts/decode_ahead_microbench.py --workload $N --seed 2147484037 --trace 1 \
  > $O/pr29c2_mb.log 2> $O/pr29c2_mb.err
echo "MICROBENCH rc=$?"; tail -n 1 $O/pr29c2_mb.log | cut -c1-6000
