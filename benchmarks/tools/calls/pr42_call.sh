# Every chip call behind PERF.md's lfm2-8b-a1b-serve numbers, in order (one chip each; .archive_check/final holds
# `git archive $(git write-tree)` of the committed files):
#   1  sh benchmarks/tools/calls/pr42_call.sh first      (2,700 s allowed, 1,238 s held; its microbench failed on a pad width)
#   2  sh benchmarks/tools/calls/pr42_call.sh micro      (600 s allowed, 79 s held)
#   3  sh benchmarks/tools/calls/pr42_call.sh cell .archive_check/final \
#        4200001009 4200001021 4200001033 4200001049 4200001061 4200001087 4200001091   (2,100 s allowed, 1,364 s held)
# The phases, run from the root of a checkout:
#   first      the new cell traced once (cold), the head-64 kernels alone (scripts/h64_attention_microbench.py),
#              then the check's readings with their controls (benchmarks/tools/lfm2_precision.py) on two seeds
#   micro      the head-64 kernels alone, again (call 1's run of the script failed on a pad width)
#   cell DIR   from DIR (`git archive $(git write-tree)` of the committed files): the cell traced once and untraced on
#              the seeds given after DIR, each run a process of its own (benchmarks/tools/sets.py's way)
# Output under chiprun_out/pr42_*.
set -u
CELL=lfm2-8b-a1b-serve.doc-qa-decode
mkdir -p chiprun_out
case "$1" in
first)
  python3 benchmarks/run.py --workload $CELL --seed 4200000017 --seconds 30 --trace 1 \
    > chiprun_out/pr42_first.out 2> chiprun_out/pr42_first.err
  rc=$?; echo "cell traced rc=$rc"
  grep -a '^\[check\]\|^\[warm\]' chiprun_out/pr42_first.out | cut -c1-600; tail -n 1 chiprun_out/pr42_first.out
  [ $rc -eq 0 ] || tail -n 30 chiprun_out/pr42_first.err
  python3 scripts/h64_attention_microbench.py > chiprun_out/pr42_micro.json 2> chiprun_out/pr42_micro.err
  echo "micro rc=$?"; tail -n 1 chiprun_out/pr42_micro.json; tail -n 5 chiprun_out/pr42_micro.err
  [ $rc -eq 0 ] || exit $rc
  for seed in 4200000101 4200000203; do
    python3 benchmarks/tools/lfm2_precision.py \
      stated,kv_through_int8,conv_through_int8,weights_through_int8,tail_zeroed_at_chunks $seed \
      >> chiprun_out/pr42_precision.jsonl 2>> chiprun_out/pr42_precision.err
    echo "precision $seed rc=$?"
  done
  python3 -c "
import json
for l in open('chiprun_out/pr42_precision.jsonl'):
    if l.startswith('{'):
        d = json.loads(l); print(d['seed'], d['variant'], d['ok'], round(d['worst'], 5), round(d['largest_gap'], 5), d['pairs_turned'], d['pairs'], d['engine_s'], d['reference_s'])"
  ;;
micro)
  python3 scripts/h64_attention_microbench.py > chiprun_out/pr42_micro.json 2> chiprun_out/pr42_micro.err
  echo "micro rc=$?"; tail -n 1 chiprun_out/pr42_micro.json; tail -n 5 chiprun_out/pr42_micro.err
  ;;
cell)
  dir=$2; shift 2; cd "$dir"
  python3 benchmarks/run.py --workload $CELL --seed $1 --seconds 30 --trace 1 > ../../chiprun_out/pr42_cell_t$1.out 2>&1
  echo "traced $1 rc=$?"; tail -n 1 ../../chiprun_out/pr42_cell_t$1.out
  shift
  for seed in "$@"; do
    python3 benchmarks/run.py --workload $CELL --seed $seed --seconds 30 --trace 0 > ../../chiprun_out/pr42_cell_$seed.out 2>&1
    echo "untraced $seed rc=$?"; grep -a '^\[check\]' ../../chiprun_out/pr42_cell_$seed.out | cut -c1-200; tail -n 1 ../../chiprun_out/pr42_cell_$seed.out
  done
  ;;
esac
