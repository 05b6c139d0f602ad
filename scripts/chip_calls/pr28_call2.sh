# PR 28, call 2 (one chip), everything of the change from the unpacked `git archive $(git write-tree)` of the
# final tree (.archive_check/final), the parent from the archive of ca9c47e (.archive_check/parent): a set of six
# plain runs of the claimed cell, a seed and a process each; the parent on two of those seeds (two more pairs);
# the claimed cell once traced through benchmarks/run.py; decode-sat and pretrain-4k parent against change on
# shared seeds. The machine's compile cache if it brings one, else one directory for both sides.
R=$PWD
if [ -z "$JAX_COMPILATION_CACHE_DIR" ]; then
  export JAX_COMPILATION_CACHE_DIR=$R/.jax_cache_call; mkdir -p $JAX_COMPILATION_CACHE_DIR
fi
echo "cache $JAX_COMPILATION_CACHE_DIR"
F=$R/.archive_check/final; P=$R/.archive_check/parent
N=mimo-v2-flash-serve.mixed-len-decode
export SETS_OUT=$R/chiprun_out
cd $F && python3 benchmarks/tools/sets.py $N 30 pr28set 0 2350000019 2550000023 2750000027 3150000029 3350000033 3950000039
cd $P && python3 benchmarks/tools/sets.py $N 30 pr28par 0 2350000019 2550000023
cd $F && python3 benchmarks/tools/sets.py $N 30 pr28traced 1 2147483999
pairs() {  # workload, seed base, tag
  i=0
  for side in parent change change parent; do
    i=$((i+1))
    if [ $side = parent ]; then cd $P; else cd $F; fi
    python3 benchmarks/run.py --workload $1 --seed $(($2 + (i+1)/2)) --seconds 30 --trace 0 \
      > $R/chiprun_out/pr28c2_$1.$i.$side.log 2> $R/chiprun_out/pr28c2_$1.$i.$side.err
    echo "rc=$? $side seed $(($2 + (i+1)/2))"; grep '^\[run\]' $R/chiprun_out/pr28c2_$1.$i.$side.log | cut -c1-900
    tail -n 1 $R/chiprun_out/pr28c2_$1.$i.$side.log | cut -c1-300
  done
}
pairs mistral7b-train.pretrain-4k 3700000000
pairs mistral7b-serve.decode-sat 3600000000
