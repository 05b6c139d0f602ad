# PR 24, chip call 43 (328 s): as run, then from benchmarks_out/tools/; .archive_check/ held the
# unpacked `git archive` of the tree the first review read.
export SETS_OUT=$PWD/chiprun_out/last
TOOLS=$PWD/benchmarks_out/tools
mkdir -p $SETS_OUT
cd .archive_check
python3 $TOOLS/sets.py mistral7b-serve.decode-sat 30 trace 1 2147483659
python3 $TOOLS/sets.py mistral7b-serve.decode-sat 30 seta 0 11
python3 $TOOLS/sets.py mistral7b-train.pretrain-4k 30 seta 0 11
