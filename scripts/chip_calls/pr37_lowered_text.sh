# PR 37, no chip: the decode step and the 2048-token prefill chunk of the four accepted serving configurations, lowered for a
# v5e at the parent and at the change (scripts/chip_calls/pr33_lowered_text.py says how) and compared by hash. ALL EIGHT must
# be byte-identical: PR 37 cut `scan` into pieces it shares with `delta` and handed moe_dropless its score function, and
# neither may move an accepted program by one operation.
# Nothing is read or written outside the checkout: the parent is HEAD, unpacked by `git archive` into .archive_check/parent
# (or the directory given), and the texts go under chiprun_out/pr37_lowered (both are in .gitignore). ~45 min and ~15 GB of
# host memory on Nemotron's side.
#   sh scripts/chip_calls/pr37_lowered_text.sh [<the parent's checkout>]
set -e
R=$PWD
if [ -n "$1" ]; then P=$(cd "$1" && pwd); else
  P=$R/.archive_check/parent; rm -rf "$P"; mkdir -p "$P"; git archive HEAD | tar -x -C "$P"
fi
S=${PR37_OUT:-$R/chiprun_out/pr37_lowered}
mkdir -p "$S"
for side in parent change; do
  if [ $side = parent ]; then T=$P; else T=$R; fi
  ln -sfn "$T" "$S/tree"
  JAX_PLATFORMS=cpu python3 "$R/scripts/chip_calls/pr33_lowered_text.py" --repo "$S/tree" --out "$S/$side" \
    2> "$S/$side.err" | tee "$S/$side.log"
done
python3 - "$S" <<'E'
import json, sys
s = sys.argv[1]
parent, change = (json.load(open(f"{s}/{side}/sha256.json")) for side in ("parent", "change"))
assert set(parent) == set(change) and len(change) == 8, sorted(change)
for k in sorted(change):
    print("DIFFERENT" if parent[k] != change[k] else "identical", k)
wrong = [k for k in sorted(change) if parent[k] != change[k]]
if wrong:
    sys.exit(f"NOT AS EXPECTED: {wrong}")
print("AS EXPECTED: eight sha256 equal")
E
