# PR 24, chip call 23 (2,869 s): as run, then from benchmarks_out/tools/ (git-ignored) with sets.py and
# dump_xplane.py beside it; the chat-open draft it copies is gone with the cell.
# The whole proof in one call: each cell's traced run (cold), then two sets of 6
# runs with the same seeds in both sets; then the chat-open knee sweep if time is left.
T0=$(date +%s)
SEEDS="11 2147483659 4000000007 305419896 77 3123456789"
mkdir -p chiprun_out
env | grep -i -E "jax|xla|tpu" > chiprun_out/env.txt
for W in mistral7b-serve.decode-sat mistral7b-train.pretrain-4k; do
  echo "=== $W traced (first run in this checkout: compiles) at $(( $(date +%s) - T0 )) s"
  python3 benchmarks_out/tools/sets.py $W 30 trace 1 2147483659
  python3 benchmarks_out/tools/dump_xplane.py benchmarks_out/$W/trace > chiprun_out/$W.xplane.txt 2>&1
  if ! grep -q '"rc": 0' chiprun_out/$W.trace.jsonl; then
    echo "=== $W traced run failed; one plain run for the error, then next cell"
    python3 benchmarks_out/tools/sets.py $W 30 probe 0 11
    if ! grep -q '"rc": 0' chiprun_out/$W.probe.jsonl; then continue; fi
  fi
  for SET in a b; do
    echo "=== $W set $SET at $(( $(date +%s) - T0 )) s"
    python3 benchmarks_out/tools/sets.py $W 30 set$SET 0 $SEEDS
  done
done
EL=$(( $(date +%s) - T0 ))
echo "=== elapsed $EL s"
if [ $EL -lt 2700 ]; then
  python3 - <<'PY'
import json
m = json.load(open("BENCHMARK.json"))
m["workloads"].append({"name": "mistral7b-serve.chat-open", "config": "mistral7b-serve", "traffic": "chat-open", "chips": 1, "why": "sweep"})
json.dump(m, open("BENCHMARK.json", "w"))
PY
  cp benchmarks_out/chat-open.json.draft benchmarks/traffic/chat-open.json
  python3 benchmarks/run.py --workload mistral7b-serve.chat-open --seed 11 --seconds 30 --trace 0 --rates 1.5,2.0,2.5,3.0,3.5,4.0,5.0 > chiprun_out/sweep.log 2> chiprun_out/sweep.err
  echo "sweep rc=$?"; grep -E "^\[sweep\]|^\[check\]" chiprun_out/sweep.log; tail -c 1500 chiprun_out/sweep.err
fi
echo "=== done at $(( $(date +%s) - T0 )) s"
