# review round: the check as it now is (every held expert near the edge searched, no row left out,
# rows taken beside a full batch) on the seed whose row stayed unplaced and on new ones, and the three controls
python3 benchmarks/tools/mimo_precision.py as_stated 3333333333 > chiprun_out/mimo_precision3.jsonl 2> chiprun_out/mimo_precision3.err
echo rc=$?; tail -c 1500 chiprun_out/mimo_precision3.err | tail -5
python3 benchmarks/tools/mimo_precision.py as_stated,weights_through_int8,router_in_bf16 2600000001 3100000007 >> chiprun_out/mimo_precision3.jsonl 2>> chiprun_out/mimo_precision3.err
echo rc=$?
python3 benchmarks/tools/mimo_precision.py kv_through_int8,weights_through_int8 3500000011 >> chiprun_out/mimo_precision3.jsonl 2>> chiprun_out/mimo_precision3.err
echo rc=$?
python3 - <<'PY'
import json
for l in open("chiprun_out/mimo_precision3.jsonl"):
    if not l.startswith("{"): continue
    d = json.loads(l)
    print(d["seed"], d["variant"], "ok", d["ok"], "worst", round(d["worst"], 5), "routed otherwise", d["routed_otherwise"],
          "s", d["engine_s"], d["reference_s"], [(round(m, 5), round(e, 4), r) for _, m, e, r in d["by_margin"]])
PY
