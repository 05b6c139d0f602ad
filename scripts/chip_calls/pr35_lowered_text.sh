# PR 35, no chip: the decode step and the 2048-token prefill chunk of the four serving configurations, lowered for a v5e at
# the parent and at the change and compared by hash. All eight texts must be byte-identical: the change is spans, their args
# and two host-side counters, and builds no graph differently. scripts/chip_calls/pr33_lowered_text.sh does it; this gives it
# its own directory, chiprun_out/pr35_lowered.
#   sh scripts/chip_calls/pr35_lowered_text.sh [<the parent's checkout>]
PR33_OUT=${PR35_OUT:-$PWD/chiprun_out/pr35_lowered} exec sh scripts/chip_calls/pr33_lowered_text.sh "$@"
