"""What the engine's spans cost when the tracer is ON (PR 25): the decode-sat
engine, built and warmed as the serve runner does it, stepped in alternating
blocks with ``TRACER.enable()`` (no profiler) and with it off. Prints one
JSON line: the median decode-only step both ways, every block's median, and
the events a traced step records. From the root of a checkout, on the chip:
    python3 benchmarks/tools/calls/pr25_tracer_cost.py [seed] [blocks] [steps]
"""
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import schedule  # noqa: E402
from benchmarks.runners import common, serve  # noqa: E402


def main(seed=11, blocks=12, steps=50, *, config=None, traffic=None,
         require_chip=True):
    """``config``, ``traffic`` and ``require_chip`` are for the CPU
    rehearsal at the tests' tiny configuration."""
    from paddle_tpu.inference.serving import LLMEngine
    from paddle_tpu.observability.trace import TRACER

    config = config or bench_run.load_json(
        "benchmarks", "configs", "mistral7b-serve.json")
    traffic = traffic or bench_run.load_json(
        "benchmarks", "traffic", "decode-sat.json")
    if require_chip:
        common.require_tpu(1)
        common.place_cache()
    model = common.model_sizes(config)
    net = common.build_model(model, seed, config.get("dtype", "bfloat16"))
    net.eval()
    eng = LLMEngine(net, capture_logits=True, **config["engine"])
    try:
        items = schedule.build(traffic)
        loop = serve.Loop(eng, seed, model["vocab_size"])
        serve.warm_shapes(loop, items, config["engine"]["prefill_buckets"])
        src = schedule.cycled(items)
        loop.on_finish = lambda lv: loop.submit(next(src))
        for _ in range(int(traffic["clients"])):
            loop.submit(next(src))
        for _ in range(int(traffic["warmup_steps"])):
            loop.step()
        medians = {"on": [], "off": []}
        all_ms = {"on": [], "off": []}
        events = []
        for b in range(blocks):
            arm = "on" if b % 2 == 0 else "off"
            TRACER.clear()
            (TRACER.enable if arm == "on" else TRACER.disable)()
            loop.steps.clear()
            for _ in range(steps):
                loop.step()
            TRACER.disable()
            ms = [(s[1] - s[0]) * 1e3 for s in loop.steps if not s[3]]
            medians[arm].append(statistics.median(ms))
            all_ms[arm] += ms
            if arm == "on":
                events.append(len(TRACER.events()) / steps)
        TRACER.clear()
        on, off = (statistics.median(all_ms[k]) for k in ("on", "off"))
        out = {"decode_step_ms_p50_tracer_on": on,
               "decode_step_ms_p50_tracer_off": off,
               "cost_percent": 100.0 * (on - off) / off,
               "block_medians_on": medians["on"],
               "block_medians_off": medians["off"],
               "events_a_step": statistics.median(events),
               "decode_steps": {k: len(v) for k, v in all_ms.items()}}
        print(json.dumps(out), flush=True)
        return out
    finally:
        eng.close()


if __name__ == "__main__":
    main(*(int(a) for a in sys.argv[1:4]))
