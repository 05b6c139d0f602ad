# PR 37: every chip call made (one chip each), in order. The phases live in scripts/chip_calls/pr37_call.sh, which says what
# each does; the parent is .archive_check/parent (`git archive f0e7940`), the committed files .archive_check/final
# (`git archive $(git write-tree)`), the parent under this PR's benchmark files .archive_check/parent_bench.
#   1  chiprun --timeout 2700 -- sh scripts/chip_calls/pr37_call.sh first
#   2  chiprun --timeout 3500 -- sh scripts/chip_calls/pr37_call.sh precision \
#        stated,weights_through_int8,delta_inputs_through_int8,kv_through_int8 3700000401 2370000419 3700000433
#   3  chiprun --timeout 3550 -- env CHANGE=$PWD/.archive_check/final sh scripts/chip_calls/pr37_call.sh cell
#   4  chiprun --timeout 3550 -- env CHANGE=$PWD/.archive_check/final sh scripts/chip_calls/pr37_call.sh pairs nemo mimo joyai sat train qwen
#      (its last run, the new cell traced once more, was ended at the machine's 40 GiB of host memory after ten runs in one call)
#   5  chiprun --timeout 3000 -- env CHANGE=$PWD/.archive_check/final sh scripts/chip_calls/pr37_call.sh last
# No machine was free on four askings (nothing charged); /root/scratch had the loop that asked again.
sh scripts/chip_calls/pr37_call.sh "$@"
