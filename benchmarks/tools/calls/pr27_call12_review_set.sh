# review round: one traced run, then a set of six plain runs of the new cell, a process and a seed each,
# after the host trims (one joined table put, signature string cached, argmax without the float64 copy)
# and with the check's rows taken beside a full batch and its reference after the window
W=mimo-v2-flash-serve.mixed-len-decode
python3 benchmarks/tools/sets.py $W 30 pr27v 1 2147483777
python3 benchmarks/tools/sets.py $W 30 pr27s 0 2200000011 2400000013 2800000017 3200000019 3600000023 4100000029
python3 - <<'PY'
import json
for tag in ("pr27v", "pr27s"):
    for l in open(f"chiprun_out/mimo-v2-flash-serve.mixed-len-decode.{tag}.jsonl"):
        d = json.loads(l); s = d["detail"]
        try:
            det = json.loads(s[s.find("{"):])
        except Exception:
            print(tag, d["seed"], "no detail", d.get("stderr_tail", "")[-800:]); continue
        print(tag, d["seed"], round(d["wall_s"]), round(det["values"]["serve_tokens_per_s"], 1),
              {k: round(v, 2) for k, v in det["series_p50"].items()}, round(det["setup_s"], 1),
              det["check"]["ok"], round(det["check"]["worst"], 4), det["check"]["routed_otherwise"])
PY
