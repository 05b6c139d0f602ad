# refusal round (the check refused the new cell as too noisy): after the host's part of a decode step was cut
# (greedy steps fetch tokens, CountingJit asks jax's cache, label keys kept), from the unpacked `git archive` of
# the staged tree: the new cell traced (stop there if it fails or is not correct), a set of six plain runs, a
# process and a seed each, then the guard cell parent against change on one chip (parent, change, change,
# parent, shared seeds)
R=$PWD
N=mimo-v2-flash-serve.mixed-len-decode
cd $R/.archive_check/final
SETS_OUT=$R/chiprun_out python3 benchmarks/tools/sets.py $N 30 pr27y 1 2147483907
python3 - $R <<'PY' || exit 1
import json, sys
d = json.loads(open(f"{sys.argv[1]}/chiprun_out/mimo-v2-flash-serve.mixed-len-decode.pr27y.jsonl").readlines()[-1])
ok = d["rc"] == 0 and d.get("line", {}).get("correct") is True
print("TRACED", d["rc"], d.get("line", {}).get("correct"), (d.get("stderr_tail") or "")[-3000:], d["detail"][:6000])
sys.exit(0 if ok else 1)
PY
SETS_OUT=$R/chiprun_out python3 benchmarks/tools/sets.py $N 30 pr27z 0 2250000013 2450000029 2850000031 3250000037 3650000041 4150000043
python3 - $R <<'PY'
import json, sys
for l in open(f"{sys.argv[1]}/chiprun_out/mimo-v2-flash-serve.mixed-len-decode.pr27z.jsonl"):
    d = json.loads(l); s = d["detail"]
    try:
        det = json.loads(s[s.find("{"):])
    except Exception:
        print(d["seed"], "no detail", d.get("stderr_tail", "")[-1500:]); continue
    print(d["seed"], round(d["wall_s"]), round(det["values"]["serve_tokens_per_s"], 1),
          {k: round(v, 2) for k, v in det["series_p50"].items()}, round(det["setup_s"], 1),
          det["check"]["ok"], round(det["check"]["worst"], 4), det["check"]["routed_otherwise"],
          det["compiles_in_window"], det["failed"], det["counters"]["host_syncs"], det["counters"]["tokens_out"])
PY
W=mistral7b-serve.decode-sat
i=0
for side in parent change change parent; do
  i=$((i+1))
  if [ $side = parent ]; then cd $R/.archive_check/parent; else cd $R/.archive_check/final; fi
  python3 benchmarks/run.py --workload $W --seed $((3400000000 + (i+1)/2)) --seconds 30 --trace 0 > $R/chiprun_out/guard3_$i.$side.log 2> $R/chiprun_out/guard3_$i.$side.err
  echo "rc=$? $side"; tail -n 1 $R/chiprun_out/guard3_$i.$side.log | cut -c1-400
done
