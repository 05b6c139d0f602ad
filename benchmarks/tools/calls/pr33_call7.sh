# PR 33, call 7 (one chip; after the review). (1) From the committed files (.archive_check/final, an unpacked
# `git archive $(git write-tree)`): the new cell four times plain and once traced, a seed each, the check as committed
# (reference routed as the engine routed, worst of twelve rows under 0.020 when this ran, 0.021 since: the middle once these readings were in; widest turn under 0.02). (2) The parent with
# this PR's benchmark files laid over it (.archive_check/parent_bench: `git archive HEAD` + BENCHMARK.json, benchmarks/,
# tests/benchmarks/ of the change): the new cell's command fails at once and non-zero; an old cell's traced run succeeds.
#   chiprun --timeout 1900 -- sh benchmarks/tools/calls/pr33_call7.sh
R=$PWD; O=$R/chiprun_out; mkdir -p $O
N=nemotron3-nano-serve.short-chat-decode
cd $R/.archive_check/final
SETS_OUT=$O python3 benchmarks/tools/sets.py $N 30 pr33review 0 2222222243 2033333377 1844444489 1655555521 2>&1 | cut -c1-700
SETS_OUT=$O python3 benchmarks/tools/sets.py $N 30 pr33reviewT 1 2144444459 2>&1 | cut -c1-3500
python3 - <<PY
import json
for tag in ("pr33review", "pr33reviewT"):
    for line in open("$O/$N.%s.jsonl" % tag):
        r = json.loads(line)
        try:
            d = json.loads(r["detail"][6:])
        except ValueError:
            d = {}
        c = d.get("check") or {}
        print(tag, r["seed"], "rc", r["rc"], "correct", (r.get("line") or {}).get("correct"), "worst", c.get("worst"),
              "gap", c.get("largest_gap"), "turned", c.get("pairs_turned"), "setup_s", d.get("setup_s"),
              "attempted", d.get("attempted"), "failed", d.get("failed"), "compiles", d.get("compiles_in_window"))
PY
cd $R/.archive_check/parent_bench
t0=$(date +%s)
timeout 300 python3 benchmarks/run.py --workload $N --seed 1466666603 --seconds 30 --trace 0 > $O/pr33_parent_new.log 2> $O/pr33_parent_new.err
echo "rc=$? parent + this PR's benchmark files, the new cell, after $(( $(date +%s) - t0 )) s"; tail -n 2 $O/pr33_parent_new.err | cut -c1-300
timeout 600 python3 benchmarks/run.py --workload mistral7b-serve.decode-sat --seed 1277777701 --seconds 30 --trace 1 > $O/pr33_parent_sat.log 2> $O/pr33_parent_sat.err
echo "rc=$? parent + this PR's benchmark files, decode-sat traced"; tail -n 1 $O/pr33_parent_sat.log | cut -c1-1200
