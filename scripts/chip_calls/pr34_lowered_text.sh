# PR 34, no chip: the decode step and the 2048-token prefill chunk of the four serving configurations, lowered for a v5e at
# the parent and at the change and compared by hash. All eight texts must be byte-identical: the change is an order of calls on
# the host and builds no graph differently. scripts/chip_calls/pr33_lowered_text.sh does it (its .py lowers the fourth
# configuration too where a checkout has its runner); this gives it its own directory, chiprun_out/pr34_lowered.
#   sh scripts/chip_calls/pr34_lowered_text.sh [<the parent's checkout>]
PR33_OUT=${PR34_OUT:-$PWD/chiprun_out/pr34_lowered} exec sh scripts/chip_calls/pr33_lowered_text.sh "$@"
