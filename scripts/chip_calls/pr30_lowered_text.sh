# PR 30, no chip: the decode step and the 2048-token prefill chunk of both serving configurations, lowered for a v5e
# at the parent and at the change (scripts/chip_calls/pr29_lowered_text.py says how; both sides imported through ONE
# path). Mistral runs models/llama.py and never imports the expert block: its four texts must be byte-identical.
# MiMo's must hold the grouped kernel once, called once an expert layer (six), at the change and not at all at the parent.
#   sh scripts/chip_calls/pr30_lowered_text.sh <the parent's checkout> [--compile]
set -e
R=$PWD
P=$(cd "$1" && pwd); shift
S=${PR30_OUT:-/root/scratch/pr30_lowered}
mkdir -p "$S"
for side in parent change; do
  if [ $side = parent ]; then T=$P; else T=$R; fi
  ln -sfn "$T" "$S/tree"
  for c in mistral7b-serve mimo-v2-flash-serve; do
    JAX_PLATFORMS=cpu python3 "$R/scripts/chip_calls/pr29_lowered_text.py" --repo "$S/tree" --out "$S/$side.$c" \
      --config $c "$@" 2> "$S/$side.$c.err" | tee "$S/$side.$c.log"
  done
done
if cmp "$S/parent.mistral7b-serve/sha256.json" "$S/change.mistral7b-serve/sha256.json"; then
  echo "IDENTICAL: $(grep -c : "$S/change.mistral7b-serve/sha256.json") Mistral texts, parent and change"
else
  diff "$S/parent.mistral7b-serve/sha256.json" "$S/change.mistral7b-serve/sha256.json"; exit 1
fi
# the kernel's call is a jit of its own (grouped_ffn._call): its body stands once in a program's text, called once
# an expert layer
for side in parent change; do
  for g in decode_pure chunk_pure_2048; do
    T="$S/$side.mimo-v2-flash-serve/mimo-v2-flash-serve.$g.lowered.txt"
    echo "$side $g: the grouped kernel's body $(grep -c 'kernel_name = "moe_grouped_swiglu"' "$T") time(s), called $(grep -c 'call @_call(' "$T") times"
  done
done
