# PR 33, call 6 (one chip; after the review): the check as rewritten (the reference routed as the engine routed, the worst
# of twelve rows, the widest turn), the engine as stated on four seeds and the controls through the same verdict.
#   chiprun --timeout 1700 -- sh benchmarks/tools/calls/pr33_call6.sh
mkdir -p chiprun_out; O=chiprun_out/pr33_precision6
: > $O.jsonl; : > $O.err
run() { timeout 900 python3 benchmarks/tools/nemotron_precision.py "$@" >> $O.jsonl 2>> $O.err; echo "rc=$? $*"; }
run stated,weights_through_int8,scan_inputs_through_int8 2147483659 1900000019
run stated 1700000111 1500000233
run state_in_bf16,scan_products_highest 2147483659
grep '^{' $O.jsonl | cut -c1-1400; tail -4 $O.err | cut -c1-500
