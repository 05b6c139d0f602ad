# PR 28, call 3 (one chip), the review round: chip_smoke.py whole (it lowers the decode executable through
# LLMEngine.decode_abstract_args and exports pages beside a step in flight), then everything of the change from the
# unpacked `git archive $(git write-tree)` of the final tree (.archive_check/final) against the parent's archive
# (ca9c47e, .archive_check/parent): the claimed cell parent / change / change / parent on two fresh seeds, the claimed
# cell once traced through scripts/decode_ahead_microbench.py (engage share), decode-sat parent / change on one seed.
R=$PWD
if [ -z "$JAX_COMPILATION_CACHE_DIR" ]; then
  export JAX_COMPILATION_CACHE_DIR=$R/.jax_cache_call; mkdir -p $JAX_COMPILATION_CACHE_DIR
fi
echo "cache $JAX_COMPILATION_CACHE_DIR"
F=$R/.archive_check/final; P=$R/.archive_check/parent
mkdir -p $R/chiprun_out
cd $F && python3 chip_smoke.py > $R/chiprun_out/pr28c3_smoke.log 2> $R/chiprun_out/pr28c3_smoke.err
echo "SMOKE rc=$?"; grep -E "Mosaic calls|round trip|FAILED|logits vs|compiles;" $R/chiprun_out/pr28c3_smoke.log | cut -c1-400
tail -n 1 $R/chiprun_out/pr28c3_smoke.log | cut -c1-300; tail -c 1500 $R/chiprun_out/pr28c3_smoke.err
run() {  # side, workload, seed, tag
  if [ $1 = parent ]; then cd $P; else cd $F; fi
  python3 benchmarks/run.py --workload $2 --seed $3 --seconds 30 --trace 0 \
    > $R/chiprun_out/pr28c3_$2.$4.$1.log 2> $R/chiprun_out/pr28c3_$2.$4.$1.err
  echo "rc=$? $1 $2 seed $3"; grep '^\[run\]' $R/chiprun_out/pr28c3_$2.$4.$1.log | cut -c1-1100
  tail -n 1 $R/chiprun_out/pr28c3_$2.$4.$1.log | cut -c1-300
}
N=mimo-v2-flash-serve.mixed-len-decode
run parent $N 4100000041 1; run change $N 4100000041 2; run change $N 4300000043 3; run parent $N 4300000043 4
cd $F && python3 scripts/decode_ahead_microbench.py --workload $N --seed 2147484011 --trace 1 \
  > $R/chiprun_out/pr28c3_mb.log 2> $R/chiprun_out/pr28c3_mb.err
echo "MICROBENCH rc=$?"; tail -n 1 $R/chiprun_out/pr28c3_mb.log | cut -c1-5000
D=mistral7b-serve.decode-sat
run parent $D 3800000051 1; run change $D 3800000051 2
