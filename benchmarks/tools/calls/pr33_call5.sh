# PR 33, call 5 (one chip): the check with the reference's routing of the context aligned to the program's, on the seeds
# that read worst in call 3's set, and the controls under it.
#   chiprun --timeout 3000 -- sh benchmarks/tools/calls/pr33_call5.sh
mkdir -p chiprun_out
timeout 2400 python3 benchmarks/tools/nemotron_precision.py $VARIANTS $SEEDS > chiprun_out/pr33_precision3.jsonl 2>chiprun_out/pr33_precision3.err
echo "precision rc=$?"; grep '^{' chiprun_out/pr33_precision3.jsonl | cut -c1-1500; tail -3 chiprun_out/pr33_precision3.err | cut -c1-600
