"""Run a cell several times, each run a process of its own (this parent
never touches JAX), and keep each run's last line and [run] line in
``$SETS_OUT/<workload>.<tag>.jsonl`` (``chiprun_out/`` by default). Prints
each metric's median and spread (distance between quartiles over the
median, ``statistics.quantiles``). Run from the root of a checkout:
    python3 benchmarks/tools/sets.py <workload> <seconds> <tag> <trace> <seed> [<seed> ...]
The chip calls that made PERF.md's numbers are under ``calls/``."""
import json, os, statistics, subprocess, sys, time

w, seconds, tag, trace = sys.argv[1], sys.argv[2], sys.argv[3], sys.argv[4]
seeds = sys.argv[5:]
OUT = os.environ.get("SETS_OUT", "chiprun_out"); os.makedirs(OUT, exist_ok=True)
out = open(f"{OUT}/{w}.{tag}.jsonl", "a")
vals = {}
for s in seeds:
    t = time.time()
    r = subprocess.run([sys.executable, "benchmarks/run.py", "--workload", w, "--seed", s,
                        "--seconds", seconds, "--trace", trace], capture_output=True, text=True)
    lines = r.stdout.strip().splitlines()
    last = lines[-1] if lines else ""
    detail = next((ln for ln in lines if ln.startswith("[run]")), "")
    rec = {"seed": s, "rc": r.returncode, "wall_s": time.time() - t}
    try:
        rec["line"] = json.loads(last)
        for k, v in rec["line"]["metrics"].items():
            vals.setdefault(k, []).append(v["value"])
    except Exception:
        rec["stdout_tail"] = r.stdout[-1500:]
        rec["stderr_tail"] = r.stderr[-3000:]
    rec["detail"] = detail[:20000]
    out.write(json.dumps(rec) + "\n"); out.flush()
    print(json.dumps({k: rec.get(k) for k in ("seed", "rc", "wall_s")}),
          json.dumps(rec.get("line", {}).get("metrics", rec.get("stderr_tail", "")))[:1500], flush=True)
    if trace == "1" and "line" in rec:
        print("BREAKDOWN", json.dumps(rec["line"].get("breakdown"))[:3000], json.dumps(rec["line"]["device"]), flush=True)
for k, v in vals.items():
    if len(v) >= 2:
        q = statistics.quantiles(v, n=4)
        print(f"SUMMARY {w} {tag} {k}: n={len(v)} median={statistics.median(v):.6g} "
              f"min={min(v):.6g} max={max(v):.6g} iqr/median={(q[2]-q[0])/statistics.median(v):.5f}", flush=True)
