"""How a serving configuration's ``check.tolerance`` is measured: the
correctness check's logits rows from the configuration's engine as the
file states it, or with one engine switch changed (``kv_dtype=int8``: the
lower precision the tolerance has to tell from bf16), against the plain
reference by ``reference.row_error``. One process per engine; one check
per prompt seed, all under the one set of weights. The tolerance goes
between the worst reading as stated and the least worst-row reading with
the switch, and both go into the file's ``check.tolerance_from``.

    python3 benchmarks/tools/logit_margin.py <config> <seed> \
        <engine switch>=<value>|as-stated <prompt seed> [<prompt seed> ...]

Prints one JSON line per prompt seed and a SUMMARY line; needs the chip.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(config_name, seed, switch, *prompt_seeds):
    from benchmarks.harness import reference
    from benchmarks.runners import common, serve

    common.require_tpu(1)
    common.place_cache()
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           config_name + ".json")) as f:
        config = json.load(f)
    engine = dict(config["engine"])
    if switch != "as-stated":
        key, value = switch.split("=")
        engine[key] = value
    model = common.model_sizes(config)
    net = common.build_model(model, int(seed), config["dtype"])
    net.eval()

    from paddle_tpu.inference.serving import LLMEngine

    eng = LLMEngine(net, capture_logits=True, **engine)
    worst = []
    try:
        for ps in prompt_seeds:
            prompts, toks, rows, agree = serve.logit_rows(
                eng, model, int(ps), config["check"])
            want = serve.reference_rows(net, model, prompts, toks, rows)
            errs = [reference.row_error(rows[k], want[k]) for k in sorted(rows)]
            worst.append(max(errs))
            print(json.dumps({"switch": switch, "prompt_seed": ps,
                              "agree": agree, "row_error": errs}), flush=True)
    finally:
        eng.close()
    print(f"SUMMARY {switch}: worst row of each check {worst}", flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
