# PR 35, the chip calls (one chip each), by phase: `chiprun --timeout <s> -- sh scripts/chip_calls/pr35_call.sh <phase> [...]`.
# The parent is .archive_check/parent (`git archive 1904ded | tar -x -C .archive_check/parent`); .archive_check/overlay is a
# second copy of it over which this script lays the benchmark as this PR leaves it (BENCHMARK.json, benchmarks/,
# tests/benchmarks/, and scripts/decode_ahead_microbench.py for its printing), as the driver does for its traced runs of the
# parent; the change is this tree, or $CHANGE (an unpacked `git archive $(git write-tree)`). All sides share one compile cache.
# Logs go to chiprun_out/pr35_<TAG>_*. The calls made: 1 `probe`; 2 `traced`, `cost sat nemo`, `spancost` (one call);
# 3 `pairs nemo sat mimo joyai`; 4 `final` with CHANGE=.archive_check/final.
#   probe            decode-sat through `benchmarks/run.py --trace 1` at the change, then what its profile holds (planes and
#                    lines by benchmarks/tools/dump_xplane.py, which call 1 did inline; the new spans with their statistics, the
#                    chunk program's executions, the pairs), then the same cell at the overlaid parent: the new readers read
#                    nothing there and do not raise
#   traced [cells]   sat mimo joyai nemo (default all): `scripts/decode_ahead_microbench.py --trace 1` at the change: the
#                    benchmark's per-layer readings, the window-long prefill_padded_tokens / prefill_tokens, the traced
#                    stretch's median call
#   cost [cells]     the same at the overlaid parent (default sat nemo): the traced stretch's median call without the args
#   spancost         a span with four args against one without, under a profiler session and under the tracer alone
#   pairs [cells]    untraced `benchmarks/run.py`, parent and change on a seed only the pair shares: nemo (two pairs,
#                    parent / change / change / parent), sat mimo joyai train (one pair each; sat2: a second, change first)
#   final [cells]    `benchmarks/run.py --trace 1` from $CHANGE (default all four serving cells, then pretrain-4k untraced)
R=$PWD; O=$R/chiprun_out; mkdir -p $O
if [ -z "$JAX_COMPILATION_CACHE_DIR" ]; then
  export JAX_COMPILATION_CACHE_DIR=$R/.jax_cache_call; mkdir -p $JAX_COMPILATION_CACHE_DIR
fi
C=${CHANGE:-$R}; P=$R/.archive_check/parent; V=$R/.archive_check/overlay; T=pr35_${TAG:-$1}
phase=$1; shift
name() {  # a cell's short name -> N
  case $1 in
    nemo*) N=nemotron3-nano-serve.short-chat-decode;;
    joyai*) N=joyai-llm-flash-serve.long-ctx-decode;;
    mimo*) N=mimo-v2-flash-serve.mixed-len-decode;;
    sat*) N=mistral7b-serve.decode-sat;;
    train) N=mistral7b-train.pretrain-4k;;
  esac
}
overlay() {  # the benchmark as this PR leaves it, over the second copy of the parent
  cp $R/BENCHMARK.json $V/; cp -r $R/benchmarks/. $V/benchmarks/; cp -r $R/tests/benchmarks/. $V/tests/benchmarks/
  cp $R/scripts/decode_ahead_microbench.py $V/scripts/
  rm -rf $V/benchmarks_out
}
side() {  # parent | overlay | change -> cd there
  case $1 in parent) cd $P;; overlay) cd $V;; *) cd $C;; esac
}
bench() {  # side, seed, trace, tag: one run of cell N through the benchmark's command
  side $1
  timeout 1200 python3 benchmarks/run.py --workload $N --seed $2 --seconds 30 --trace $3 \
    > $O/${T}_$N.$4.$1.log 2> $O/${T}_$N.$4.$1.err
  echo "rc=$? $1 $N seed $2 trace $3"; grep -E '^\[(run|check)\]' $O/${T}_$N.$4.$1.log | cut -c1-200
  tail -n 1 $O/${T}_$N.$4.$1.log | cut -c1-${5:-400}
}
micro() {  # side, seed: cell N traced in one process through the microbench
  side $1
  timeout 1200 python3 scripts/decode_ahead_microbench.py --workload $N --seed $2 --trace 1 \
    > $O/${T}_$N.$1.log 2> $O/${T}_$N.$1.err
  echo "rc=$? micro $1 $N seed $2"
  tail -n 1 $O/${T}_$N.$1.log | python3 -c '
import json, sys
d = json.loads(sys.stdin.read())
t = d.get("traced", {})
print(json.dumps({k: d.get(k) for k in ("correct", "compiles_in_window", "serve_tokens_per_s", "decode_step_ms_p50")}))
print("window", json.dumps({k: d["window"].get(k) for k in ("prefill_ends", "prefill_padding", "engage_share")}))
print("account", json.dumps(d["account"]))
print("traced", json.dumps({k: t.get(k) for k in ("calls", "call_ms_p50", "decode_steps", "decode_step_ms_p50",
      "decode_pure_device_ms_a_step", "idle_share", "idle_s", "idle_gaps", "device_s_by_program", "window_s",
      "chunk_attention_device_s")}))
print("chunk_pure_by_kind", json.dumps(t.get("chunk_pure_by_kind", [])[:10]))
print("per_layer", json.dumps(d.get("per_layer")))
'
}
case $phase in
probe)
  echo "cache $JAX_COMPILATION_CACHE_DIR"; overlay; name sat
  bench change 3500000011 1 probe 6000
  cd $C; python3 benchmarks/tools/dump_xplane.py benchmarks_out/$N/trace | cut -c1-300 > $O/${T}_$N.xplane.txt
  grep -E '^PLANE|^  LINE' $O/${T}_$N.xplane.txt
  python3 - <<'E'
import glob, os, sys
sys.path.insert(0, os.getcwd())
from benchmarks.harness import prefill_spans as ps
path = max(glob.glob("benchmarks_out/*/trace/plugins/profile/*/*.xplane.pb"), key=os.path.getmtime)
got = ps.load(path)
print("window", got["window"], "host_args", len(got["host_args"]), "chunk_runs", len(got["chunk_runs"]))
for e in got["host_args"][:12]:
    print("  ", e)
for r in got["chunk_runs"][:12]:
    print("   run", r)
chunks = ps.spans(got["host_args"], ps.CHUNK)
t0, t1 = got["window"]
for st, a, b in ps.pairs(chunks, got["chunk_runs"], t0, t1)[:12]:
    print("   pair", st, round(a - t0, 6), round(b - t0, 6))
print("spans before/in/after window", sum(c[0] < t0 for c in chunks), sum(t0 <= c[0] < t1 for c in chunks), sum(c[0] >= t1 for c in chunks))
E
  bench overlay 3500000011 1 probe 6000;;
traced)
  [ $# -eq 0 ] && set -- sat mimo joyai nemo
  for cell in "$@"; do name $cell; micro change 3500000131; done;;
cost)
  [ $# -eq 0 ] && set -- sat nemo
  overlay
  for cell in "$@"; do name $cell; micro overlay 3500000131; done;;
spancost)
  cd $C; JAX_PLATFORMS=cpu python3 - <<'E'
import json, os, statistics, sys, tempfile, time
sys.path.insert(0, os.getcwd())
import jax
from paddle_tpu.observability import trace
four = {"rid": 3, "start": 4096, "tokens": 1500, "padded": 2048}
def cost(args, n=20000):
    out = []
    for _ in range(7):
        t = time.perf_counter_ns()
        for _ in range(n):
            with trace.span("engine.prefill.chunk", cat="engine", args=args):
                pass
        out.append((time.perf_counter_ns() - t) / n)
    return statistics.median(out)
res = {"off_ns": cost(None)}
opts = jax.profiler.ProfileOptions(); opts.python_tracer_level = 0; opts.host_tracer_level = 2
with tempfile.TemporaryDirectory() as d:
    jax.profiler.start_trace(d, profiler_options=opts)
    res["profile_plain_ns"], res["profile_four_args_ns"] = cost(None, 5000), cost(four, 5000)
    jax.profiler.stop_trace()
trace.enable()
res["tracer_plain_ns"], res["tracer_four_args_ns"] = cost(None), cost(four)
trace.disable(); trace.clear()
print("spancost", json.dumps(res))
E
  ;;
pairs)
  [ $# -eq 0 ] && set -- sat nemo
  for cell in "$@"; do name $cell; case $cell in
  sat) bench parent 3500000251 0 1; bench change 3500000251 0 2;;
  sat2) bench change 2350000271 0 3; bench parent 2350000271 0 4;;
  nemo) bench parent 3500000293 0 1; bench change 3500000293 0 2; bench change 2350000311 0 3; bench parent 2350000311 0 4;;
  mimo) bench parent 3500000339 0 1; bench change 3500000339 0 2;;
  joyai) bench change 3500000357 0 1; bench parent 3500000357 0 2;;
  train) bench parent 3500000377 0 1; bench change 3500000377 0 2;;
  esac; done;;
final)
  [ $# -eq 0 ] && set -- sat mimo joyai nemo train
  seed=3500000401
  for cell in "$@"; do name $cell; seed=$((seed + 30))
    if [ $cell = train ]; then bench change $seed 0 final; else bench change $seed 1 final 6000; fi
  done;;
esac
