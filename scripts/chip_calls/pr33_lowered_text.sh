# PR 33, no chip: the decode step and the 2048-token prefill chunk of the three serving configurations the benchmark had
# (since PR 34 of the fourth too, where both checkouts have its runner: pr34_lowered_text.sh), lowered for a v5e at the
# parent and at the change (scripts/chip_calls/pr33_lowered_text.py says how; both sides imported through ONE path). All texts must be byte-identical: the expert kernel's optional gate, the graphs' slot operand and
# the cache's new kinds must leave the programs of models without them as they were.
# Nothing is read or written outside the checkout: the parent is HEAD, unpacked by `git archive` into .archive_check/parent
# (or the directory given), and the texts go under chiprun_out/pr33_lowered (both are in .gitignore).
#   sh scripts/chip_calls/pr33_lowered_text.sh [<the parent's checkout>]
set -e
R=$PWD
if [ -n "$1" ]; then P=$(cd "$1" && pwd); else
  P=$R/.archive_check/parent; rm -rf "$P"; mkdir -p "$P"; git archive HEAD | tar -x -C "$P"
fi
S=${PR33_OUT:-$R/chiprun_out/pr33_lowered}
mkdir -p "$S"
for side in parent change; do
  if [ $side = parent ]; then T=$P; else T=$R; fi
  ln -sfn "$T" "$S/tree"
  JAX_PLATFORMS=cpu python3 "$R/scripts/chip_calls/pr33_lowered_text.py" --repo "$S/tree" --out "$S/$side" \
    2> "$S/$side.err" | tee "$S/$side.log"
done
if cmp "$S/parent/sha256.json" "$S/change/sha256.json"; then
  echo "IDENTICAL: $(grep -c : "$S/change/sha256.json") texts, parent and change"
else
  diff "$S/parent/sha256.json" "$S/change/sha256.json"; exit 1
fi
