# PR 26, third chip call (scripts/chip_calls/pr26_call3.sh): the proof from the committed files.
# .archive_check/final is the unpacked `git archive $(git write-tree)` of the staged tree,
# .archive_check/parent that of the parent commit (34839ea). Pairs share a seed and alternate
# which side runs first. Run from the root of the working tree.
T0=$(date +%s)
export SETS_OUT=$PWD/chiprun_out/pr26_call3
TOOLS=benchmarks/tools
SERVE=mistral7b-serve.decode-sat
TRAIN=mistral7b-train.pretrain-4k
mkdir -p $SETS_OUT
at () { echo "=== $1 at $(( $(date +%s) - T0 )) s"; }
side () { ( cd .archive_check/$1 && python3 $TOOLS/sets.py $2 30 $1.$3 $4 ${@:5} ); }
at "decode-sat pairs"; side final $SERVE plain 0 2222222223
side parent $SERVE plain 0 2222222223 3456789013
side final $SERVE plain 0 3456789013 4100000021
side parent $SERVE plain 0 4100000021
at "final decode-sat traced";  side final $SERVE trace 1 2718281829
at "pretrain-4k pair";         side final $TRAIN plain 0 3456789013
side parent $TRAIN plain 0 3456789013
at "microbench from the archive, int8 (the per-page kernel on both sides)"
( cd .archive_check/final && python3 scripts/paged_decode_microbench.py --parent ../parent --dtype int8 2>&1 | grep '^{' )
at "done"
