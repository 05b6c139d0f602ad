# PR 30, call 3 (one chip), as call 2: the cells whose graphs this PR does not touch, one parent / change pair each on
# a shared seed (decode-sat, pretrain-4k), then mixed-len-decode again on fresh seeds, change / parent / parent /
# change, so that the claimed cell has four pairs in all and every run a seed that only its pair shares.
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
    > $O/pr30c3_$2.$4.$1.log 2> $O/pr30c3_$2.$4.$1.err
  echo "rc=$? $1 $2 seed $3"; grep -E '^\[(run|check)\]' $O/pr30c3_$2.$4.$1.log | cut -c1-1100
  tail -n 1 $O/pr30c3_$2.$4.$1.log | cut -c1-300
}
D=mistral7b-serve.decode-sat
run parent $D 3000000041 1; run change $D 3000000041 2
T=mistral7b-train.pretrain-4k
run change $T 3000000043 1; run parent $T 3000000043 2
N=mimo-v2-flash-serve.mixed-len-decode
run change $N 3000000047 5; run parent $N 3000000047 6; run parent $N 3000000059 7; run change $N 3000000059 8
