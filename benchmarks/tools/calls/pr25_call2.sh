# PR 25, second chip call: the proof from the committed files. .archive_check/final is the unpacked
# `git archive $(git write-tree)` of the final tree, .archive_check/parent that of the parent commit
# (b8df27c). Each checkout keeps its own compile cache (<checkout>/.jax_cache), so a side's first run
# compiles and its later runs are warm; the two sides of a pair share a seed.
T0=$(date +%s)
export SETS_OUT=$PWD/chiprun_out/pr25_call2
TOOLS=benchmarks/tools
SERVE=mistral7b-serve.decode-sat
TRAIN=mistral7b-train.pretrain-4k
mkdir -p $SETS_OUT
at () { echo "=== $1 at $(( $(date +%s) - T0 )) s"; }
side () { ( cd .archive_check/$1 && python3 $TOOLS/sets.py $2 30 $1.$3 $4 ${@:5} ); }
at "final decode-sat traced (compiles)";  side final $SERVE trace 1 77001
( cd .archive_check/final && python3 $TOOLS/kernel_names.py benchmarks_out/$SERVE/trace )
at "parent decode-sat plain (compiles)";  side parent $SERVE cold 0 5
at "decode-sat pairs";                    side final $SERVE plain 0 2147484001
side parent $SERVE plain 0 2147484001 3999999979
side final $SERVE plain 0 3999999979 1234567891
side parent $SERVE plain 0 1234567891
at "final pretrain-4k traced (compiles)"; side final $TRAIN trace 1 77001
( cd .archive_check/final && python3 $TOOLS/kernel_names.py benchmarks_out/$TRAIN/trace )
at "parent pretrain-4k plain (compiles)"; side parent $TRAIN cold 0 5
at "pretrain-4k pairs";                   side final $TRAIN plain 0 2147484001
side parent $TRAIN plain 0 2147484001 3999999979
side final $TRAIN plain 0 3999999979
at "done"
