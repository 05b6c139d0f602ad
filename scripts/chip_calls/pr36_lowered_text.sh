# PR 36, no chip: the decode step and the 2048-token prefill chunk of the four serving configurations, lowered for a v5e at
# the parent and at the change (scripts/chip_calls/pr33_lowered_text.py says how) and compared by hash, a text at a time.
# Seven of the eight must be byte-identical: the three later models' six (their layers are prefill="linear" already) and
# Mistral's decode step. ONE must differ, Mistral's chunk: it now gathers the request's pages in a row and carries
# chunk_attention where it carried paged_prefill_attention.
# Nothing is read or written outside the checkout: the parent is HEAD, unpacked by `git archive` into .archive_check/parent
# (or the directory given), and the texts go under chiprun_out/pr36_lowered (both are in .gitignore). ~45 min and ~15 GB of
# host memory on Nemotron's side.
#   sh scripts/chip_calls/pr36_lowered_text.sh [<the parent's checkout>]
set -e
R=$PWD
if [ -n "$1" ]; then P=$(cd "$1" && pwd); else
  P=$R/.archive_check/parent; rm -rf "$P"; mkdir -p "$P"; git archive HEAD | tar -x -C "$P"
fi
S=${PR36_OUT:-$R/chiprun_out/pr36_lowered}
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
moved = "mistral7b-serve chunk_pure@2048 lowered"
assert set(parent) == set(change) and len(change) == 8 and moved in change, sorted(change)
wrong = [k for k in sorted(change) if (parent[k] != change[k]) != (k == moved)]
for k in sorted(change):
    print("DIFFERENT" if parent[k] != change[k] else "identical", k)
text = open(f"{s}/change/{moved.replace(' ', '.').replace('@', '_')}.txt").read()
old = open(f"{s}/parent/{moved.replace(' ', '.').replace('@', '_')}.txt").read()
print("mistral chunk: chunk_attention", text.count("chunk_attention"), "paged_prefill_attention",
      text.count("paged_prefill_attention"), "| parent:", old.count("chunk_attention"), old.count("paged_prefill_attention"))
if wrong or "chunk_attention" not in text or "paged_prefill_attention" in text:
    sys.exit(f"NOT AS EXPECTED: {wrong}")
print("AS EXPECTED: 7 texts identical, Mistral's 2048 chunk different")
E
