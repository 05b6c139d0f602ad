# refusal round, last call: a second set of six plain runs of the new cell, a process and a seed each, from the
# unpacked `git archive` of the final staged tree (call 15 was `python3 scripts/fetch_latency_microbench.py`)
R=$PWD
N=mimo-v2-flash-serve.mixed-len-decode
cd $R/.archive_check/final
SETS_OUT=$R/chiprun_out python3 benchmarks/tools/sets.py $N 30 pr27q 0 2350000021 2550000023 2950000033 3350000039 3750000047 4250000051
python3 - $R <<'PY'
import json, sys
for l in open(f"{sys.argv[1]}/chiprun_out/mimo-v2-flash-serve.mixed-len-decode.pr27q.jsonl"):
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
