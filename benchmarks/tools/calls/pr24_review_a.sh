# PR 24, review round, first call (1,159 s): from the working tree. logit_margin.py then printed both
# measures (max and Euclidean) against the reference at float32 and rounded to bf16; PERF.md has them.
# 1. the logits check's margin: the engine as stated and with int8 KV (findings 2)
# 2. pretrain-4k traced, with the last span closed after the last step (finding 1)
# 3. decode-sat traced three times, 10-s windows: what differs between processes (finding 3)
# 4. decode-sat, one set of 6 at a 50-s window (finding 3)
T0=$(date +%s)
SEEDS="11 2147483659 4000000007 305419896 77 3123456789"
export SETS_OUT=$PWD/chiprun_out/review
TOOLS=benchmarks/tools
mkdir -p $SETS_OUT
env | grep -i -E "jax|xla|tpu" > $SETS_OUT/env.txt
for SW in as-stated kv_dtype=int8; do
  echo "=== logit margin $SW at $(( $(date +%s) - T0 )) s"
  python3 $TOOLS/logit_margin.py mistral7b-serve 11 $SW 11 4000000007 77 305419896 > $SETS_OUT/margin.$SW.log 2> $SETS_OUT/margin.$SW.err
  echo "rc=$?"; grep SUMMARY $SETS_OUT/margin.$SW.log; tail -c 1500 $SETS_OUT/margin.$SW.err
done
echo "=== pretrain-4k traced at $(( $(date +%s) - T0 )) s"
python3 $TOOLS/sets.py mistral7b-train.pretrain-4k 30 trace 1 2147483659
echo "=== decode-sat traced x3 at $(( $(date +%s) - T0 )) s"
python3 $TOOLS/sets.py mistral7b-serve.decode-sat 10 trace 1 2147483659 11 77
echo "=== decode-sat 6 x 50 s at $(( $(date +%s) - T0 )) s"
python3 $TOOLS/sets.py mistral7b-serve.decode-sat 50 s50 0 $SEEDS
echo "=== done at $(( $(date +%s) - T0 )) s"
