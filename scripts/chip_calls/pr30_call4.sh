# PR 30, call 4 (one chip), as call 3: decode-sat once more, change / parent on a fresh shared seed. Call 3's parent
# run of that cell was its checkout's first and stalled (the machine's ~3-s stall, one run in 13-20: 1,326 tokens/s
# with the decode-only step at 13.42 ms as ever and the check's worst row equal to the change's to the last digit).
R=$PWD
if [ -z "$JAX_COMPILATION_CACHE_DIR" ]; then
  export JAX_COMPILATION_CACHE_DIR=$R/.jax_cache_call; mkdir -p $JAX_COMPILATION_CACHE_DIR
fi
F=$R/.archive_check/final; P=$R/.archive_check/parent
O=$R/chiprun_out; mkdir -p $O
run() {  # side, workload, seed, tag
  if [ $1 = parent ]; then cd $P; else cd $F; fi
  python3 benchmarks/run.py --workload $2 --seed $3 --seconds 30 --trace 0 \
    > $O/pr30c4_$2.$4.$1.log 2> $O/pr30c4_$2.$4.$1.err
  echo "rc=$? $1 $2 seed $3"; grep -E '^\[(run|check)\]' $O/pr30c4_$2.$4.$1.log | cut -c1-1100
  tail -n 1 $O/pr30c4_$2.$4.$1.log | cut -c1-300
}
D=mistral7b-serve.decode-sat
run change $D 3000000061 1; run parent $D 3000000061 2
