# PR 29, call 1 (one chip). Everything of the change from the unpacked `git archive $(git write-tree)` of the final
# tree (.archive_check/final), the parent from the archive of 5b7788c (.archive_check/parent). chip_smoke.py whole on
# the change; decode-sat parent / change / change / parent on two shared seeds; pretrain-4k parent / change on one;
# decode-sat once traced through scripts/decode_ahead_microbench.py (engage share, the step against decode_pure's
# device time, the per-layer readings). The machine's compile cache if it brings one, else one directory for both.
R=$PWD
if [ -z "$JAX_COMPILATION_CACHE_DIR" ]; then
  export JAX_COMPILATION_CACHE_DIR=$R/.jax_cache_call; mkdir -p $JAX_COMPILATION_CACHE_DIR
fi
echo "cache $JAX_COMPILATION_CACHE_DIR"
F=$R/.archive_check/final; P=$R/.archive_check/parent
O=$R/chiprun_out; mkdir -p $O
cd $F && timeout 900 python3 chip_smoke.py > $O/pr29c1_smoke.log 2> $O/pr29c1_smoke.err
echo "SMOKE rc=$?"; grep -E "Mosaic calls|round trip|FAILED|logits vs|compiles;" $O/pr29c1_smoke.log | cut -c1-400
tail -n 1 $O/pr29c1_smoke.log | cut -c1-300; tail -c 1200 $O/pr29c1_smoke.err
run() {  # side, workload, seed, tag
  if [ $1 = parent ]; then cd $P; else cd $F; fi
  python3 benchmarks/run.py --workload $2 --seed $3 --seconds 30 --trace 0 \
    > $O/pr29c1_$2.$4.$1.log 2> $O/pr29c1_$2.$4.$1.err
  echo "rc=$? $1 $2 seed $3"; grep -E '^\[(run|check)\]' $O/pr29c1_$2.$4.$1.log | cut -c1-1100
  tail -n 1 $O/pr29c1_$2.$4.$1.log | cut -c1-300
}
D=mistral7b-serve.decode-sat
run parent $D 2900000029 1; run change $D 2900000029 2; run change $D 2900000047 3; run parent $D 2900000047 4
T=mistral7b-train.pretrain-4k
run parent $T 2900000053 1; run change $T 2900000053 2
cd $F && python3 scripts/decode_ahead_microbench.py --workload $D --seed 2147484029 --trace 1 \
  > $O/pr29c1_mb.log 2> $O/pr29c1_mb.err
echo "MICROBENCH rc=$?"; tail -n 1 $O/pr29c1_mb.log | cut -c1-6000
