# PR 24, review round, third call: after trace_reduce.attribute_gaps changed (a gap is split among
# the host spans by overlap), one traced run of each cell from .archive_check/, the unpacked
# `git archive $(git write-tree)` of the final tree.
export SETS_OUT=$PWD/chiprun_out/proof_c
mkdir -p $SETS_OUT
cd .archive_check
python3 benchmarks/tools/sets.py mistral7b-serve.decode-sat 30 trace 1 77
python3 benchmarks/tools/sets.py mistral7b-train.pretrain-4k 30 trace 1 77
