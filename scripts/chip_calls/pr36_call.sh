# PR 36, the chip calls (one chip each), by phase: `chiprun --timeout <s> -- sh scripts/chip_calls/pr36_call.sh <phase> [...]`.
# The parent is .archive_check/parent (`git archive 5a0c0b9 | tar -x -C .archive_check/parent`); the change is this tree, or
# $CHANGE (an unpacked `git archive $(git write-tree)`). This PR adds nothing to the benchmark, so the parent runs as it is
# (scripts/decode_ahead_microbench.py alone is laid over it, for its printing). All sides share one compile cache.
# Logs go to chiprun_out/pr36_<TAG>_*. The calls made: 1 `traced sat` + `pairs sat`; 2 `check` + `pairs sat2` + chip_smoke.py;
# 3 `pairs mimo joyai nemo train`; 4 `pairs sat3` + `final sat`, both with CHANGE=.archive_check/final.
#   traced [cells]   sat (default) mimo joyai nemo: `scripts/decode_ahead_microbench.py --trace 1` at the parent, then at the
#                    change, one seed: the benchmark's per-layer readings, the window-long share of chunks read in a row,
#                    the padded share, the chunk program's device time by kind of operation
#   pairs [cells]    untraced `benchmarks/run.py`, parent and change on a seed only the pair shares: sat (two pairs, parent /
#                    change / change / parent), sat2 (two more), sat3 (six more), mimo joyai nemo train (one pair each)
#   check            the cell's own check (runners/serve.py check_logits: three requests across the prefill buckets, every
#                    sampled-from row against the float32 reference) on six seeds, at the parent and at the change, one
#                    process a side: the worst row of each
#   final [cells]    `benchmarks/run.py --trace 1` from $CHANGE (default sat, then the other three serving cells)
R=$PWD; O=$R/chiprun_out; mkdir -p $O
if [ -z "$JAX_COMPILATION_CACHE_DIR" ]; then
  export JAX_COMPILATION_CACHE_DIR=$R/.jax_cache_call; mkdir -p $JAX_COMPILATION_CACHE_DIR
fi
C=${CHANGE:-$R}; P=$R/.archive_check/parent; T=pr36_${TAG:-$1}
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
side() {  # parent | change -> cd there
  case $1 in parent) cd $P;; *) cd $C;; esac
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
print(json.dumps({k: d.get(k) for k in ("correct", "compiles_in_window", "serve_tokens_per_s", "decode_step_ms_p50", "setup_s")}))
print("window", json.dumps({k: d["window"].get(k) for k in ("prefill_ends", "prefill_padding", "prefill_in_a_row", "engage_share")}))
print("account", json.dumps(d["account"]))
print("traced", json.dumps({k: t.get(k) for k in ("calls", "call_ms_p50", "decode_steps", "decode_step_ms_p50",
      "decode_pure_device_ms_a_step", "idle_share", "device_s_by_program", "window_s", "chunk_attention_device_s")}))
print("chunk_pure_by_kind", json.dumps(t.get("chunk_pure_by_kind", [])[:10]))
print("device_ops", json.dumps(t.get("device_ops", [])[:12]))
print("per_layer", json.dumps(d.get("per_layer")))
'
}
check() {  # side, seeds...: the cell's own check alone, one process
  side $1; s=$1; shift
  timeout 1500 python3 - "$@" > $O/${T}_check.$s.log 2> $O/${T}_check.$s.err <<'E'
import gc, json, os, sys
sys.path.insert(0, os.getcwd())
from benchmarks.runners import common, serve
from paddle_tpu.inference.serving import LLMEngine
config = json.load(open("benchmarks/configs/mistral7b-serve.json"))
common.require_tpu(1)
print("cache", common.place_cache(), flush=True)
model = common.model_sizes(config)
for seed in map(int, sys.argv[1:]):
    net = common.build_model(model, seed, config.get("dtype", "bfloat16"))
    net.eval()
    eng = LLMEngine(net, capture_logits=True, **config["engine"])
    try:
        got = serve.check_logits(eng, net, model, seed, config["check"])
        chunks = {k: eng.metrics().get(k) for k in ("prefill_chunks", "prefill_chunks_in_a_row")}
    finally:
        eng.close()
    print("check", seed, json.dumps(got), json.dumps(chunks), flush=True)
    del eng, net
    gc.collect()
E
  echo "rc=$? check $s"; grep '^check' $O/${T}_check.$s.log
}
case $phase in
traced)
  [ $# -eq 0 ] && set -- sat
  cp $R/scripts/decode_ahead_microbench.py $P/scripts/
  for cell in "$@"; do name $cell; micro parent 3600000131; micro change 3600000131; done;;
pairs)
  [ $# -eq 0 ] && set -- sat
  for cell in "$@"; do name $cell; case $cell in
  sat) bench parent 3600000251 0 1; bench change 3600000251 0 2; bench change 2360000271 0 3; bench parent 2360000271 0 4;;
  sat2) bench change 3600000293 0 5; bench parent 3600000293 0 6; bench parent 2360000311 0 7; bench change 2360000311 0 8;;
  sat3) bench parent 3600000521 0 9; bench change 3600000521 0 10; bench change 2360000541 0 11; bench parent 2360000541 0 12
        bench change 3600000557 0 13; bench parent 3600000557 0 14; bench parent 2360000571 0 15; bench change 2360000571 0 16
        bench parent 3600000593 0 17; bench change 3600000593 0 18; bench change 2360000607 0 19; bench parent 2360000607 0 20;;
  mimo) bench parent 3600000339 0 1; bench change 3600000339 0 2;;
  joyai) bench change 3600000357 0 1; bench parent 3600000357 0 2;;
  nemo) bench parent 3600000373 0 1; bench change 3600000373 0 2;;
  train) bench change 3600000377 0 1; bench parent 3600000377 0 2;;
  esac; done;;
check)
  [ $# -eq 0 ] && set -- 3600000401 2360000419 3600000433 2360000449 3600000461 2360000479
  check parent "$@"; check change "$@";;
final)
  [ $# -eq 0 ] && set -- sat mimo joyai nemo
  seed=3600000501
  for cell in "$@"; do name $cell; seed=$((seed + 30)); bench change $seed 1 final 6000; done;;
esac
