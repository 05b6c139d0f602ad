# PR 31, call 3 (one chip): the guards. Everything of the change from the unpacked `git archive $(git write-tree)`
# of the final tree (.archive_check/final), the parent from the archive of c2d12c2 (.archive_check/parent). Each
# existing cell parent / change / change / parent (the training cell parent / change), a pair on a shared seed.
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
    > $O/pr31c3_$2.$4.$1.log 2> $O/pr31c3_$2.$4.$1.err
  echo "rc=$? $1 $2 seed $3"; grep -E '^\[(run|check)\]' $O/pr31c3_$2.$4.$1.log | cut -c1-160
  tail -n 1 $O/pr31c3_$2.$4.$1.log | cut -c1-330
}
N=mimo-v2-flash-serve.mixed-len-decode
run parent $N 3100000037 1; run change $N 3100000037 2; run change $N 2200000013 3; run parent $N 2200000013 4
N=mistral7b-serve.decode-sat
run parent $N 3300000011 1; run change $N 3300000011 2; run change $N 2400000059 3; run parent $N 2400000059 4
N=mistral7b-train.pretrain-4k
run parent $N 2600000003 1; run change $N 2600000003 2
