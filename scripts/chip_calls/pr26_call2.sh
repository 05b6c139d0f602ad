# PR 26, second chip call (scripts/chip_calls/pr26_call2.sh): the working tree against the parent commit (34839ea, unpacked by
# `git archive` into .archive_check/parent), same seeds on both sides of a pair, one traced run a
# side, then chip_smoke.py. Run from the root of the working tree.
T0=$(date +%s)
export SETS_OUT=$PWD/chiprun_out/pr26_call2
TOOLS=benchmarks/tools
SERVE=mistral7b-serve.decode-sat
TRAIN=mistral7b-train.pretrain-4k
mkdir -p $SETS_OUT
at () { echo "=== $1 at $(( $(date +%s) - T0 )) s"; }
side () { ( cd $1 && python3 $TOOLS/sets.py $2 30 $(basename $1).$3 $4 ${@:5} ); }
at "change decode-sat traced (compiles)"; side . $SERVE trace 1 2900000001
python3 $TOOLS/kernel_names.py benchmarks_out/$SERVE/trace
at "parent decode-sat traced (compiles)"; side .archive_check/parent $SERVE trace 1 2900000001
at "decode-sat pairs";                    side .archive_check/parent $SERVE plain 0 2600000011
side . $SERVE plain 0 2600000011 3000000019
side .archive_check/parent $SERVE plain 0 3000000019 2147483777
side . $SERVE plain 0 2147483777
at "pretrain-4k pair";                    side .archive_check/parent $TRAIN plain 0 2600000011
side . $TRAIN plain 0 2600000011
at "chip_smoke"; python3 chip_smoke.py > $SETS_OUT/chip_smoke.txt 2>&1; echo "chip_smoke rc=$?"
grep -v "^W0\|^I0" $SETS_OUT/chip_smoke.txt | tail -40
at "done"
