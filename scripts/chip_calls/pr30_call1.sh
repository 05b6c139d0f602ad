# PR 30, call 1 (one chip): one expert layer alone at the published widths, before the engine is touched: the tile
# loop (this tree's, the kernel's gate held shut, and the parent's own file from .archive_check/parent) against the
# grouped kernel, as a us + b us x experts hit, T = 64 and T = 2048 (scripts/moe_ffn_microbench.py).
R=$PWD; O=$R/chiprun_out; mkdir -p $O
timeout 800 python3 scripts/moe_ffn_microbench.py --parent $R/.archive_check/parent \
  > $O/pr30c1_mb.log 2> $O/pr30c1_mb.err
echo "MICROBENCH rc=$?"; cat $O/pr30c1_mb.log | cut -c1-300; tail -c 1500 $O/pr30c1_mb.err
