# PR 29, no chip: the decode step and the 2048-token prefill chunk of both serving configurations, lowered for
# a v5e at the parent and at the change (scripts/chip_calls/pr29_lowered_text.py says how), and their hashes
# compared. Both sides are imported through ONE path (a link that is turned from one checkout to the other), so
# that no file name inside a program tells them apart.
#   sh scripts/chip_calls/pr29_lowered_text.sh <the parent's checkout> [--compile]
set -e
R=$PWD
P=$(cd "$1" && pwd); shift
S=${PR29_OUT:-/root/scratch/pr29_lowered}
mkdir -p "$S"
for side in parent change; do
  if [ $side = parent ]; then T=$P; else T=$R; fi
  ln -sfn "$T" "$S/tree"
  JAX_PLATFORMS=cpu python3 "$R/scripts/chip_calls/pr29_lowered_text.py" --repo "$S/tree" --out "$S/$side" "$@" \
    2> "$S/$side.err" | tee "$S/$side.log"
done
if cmp "$S/parent/sha256.json" "$S/change/sha256.json"; then
  echo "IDENTICAL: $(grep -c : "$S/change/sha256.json") texts, parent and change"
else
  diff "$S/parent/sha256.json" "$S/change/sha256.json"; exit 1
fi
