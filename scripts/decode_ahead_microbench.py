"""How often the serving engine runs a decode step ahead (ISSUE 28), and what
a decode-only step then costs beside the device's own time for it.

Runs one serving cell of ``BENCHMARK.json`` in this process through its own
runner, as ``benchmarks/run.py`` does (same engine, same closed loop, same
warm-up, a full batch), and keeps every ``LLMEngine.metrics()`` the runner
takes: the last two are the measured window's ends. Prints one JSON object:
the window's tokens/s and decode-only step p50 on the host's clock, the
engage share ``ahead / (ahead + synchronous)`` of the window's decode steps
with the synchronous ones by reason and the rows discarded, for an expert
model the experts hit over the weight passes made (decode steps and
prefill chunks apart), for a latent cache the cached rows a decode step
walked and a chunk expanded (a layer), for a state kind (ISSUE 33) the
states a decode step updated and the tokens a chunk scanned (a layer), the
window's prefill completions and of them the share whose logits were
fetched behind the call's decode dispatch (ISSUE 34:
``prefill_ends_behind_decode / prefills``) and, from the same deltas, what
the chunk graphs computed over what they were asked for (ISSUE 35:
``prefill_padded_tokens / prefill_tokens``, and the padded share of the
first) and the share of the chunks that read the request's keys in a row
through the chunk kernel (ISSUE 36: ``prefill_chunks_in_a_row /
prefill_chunks``), the window's seconds by kind of call,
and with ``--trace 1`` the traced stretch's median call, the device's busy time in ``decode_pure`` a traced
decode step (and the grouped expert kernel's, the latent decode kernel's and
the state update's parts of it), the largest device operations and
``decode_pure``'s time by kind of operation, the device's idle share and its
idle seconds by what the host was doing meanwhile (``idle_gaps``: the host
span or call over each gap, as the ledger's ``breakdown`` lists them), and for
each kind of decode kernel the share of the chunks it walked in the traced
steps whose every page was live (ISSUE 32: those are started written out and
waited for with one descriptor a pool; from the loop's own context lengths),
and for each kind of layer a chunk attends in, the chunk kernel's live and
dead grid steps over the traced chunks (``chunk_tiles``, from the
``engine.prefill.chunk`` spans' ``start``, ``tokens`` and ``padded`` through
``chunk_tile_counts``).
Run on the chip, any serving cell:

    python3 scripts/decode_ahead_microbench.py \
        --workload joyai-llm-flash-serve.long-ctx-decode --seed 7 --trace 1
"""
import argparse
import importlib
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

KEYS = ("decode_steps_ahead", "decode_steps_sync", "decode_rows_discarded",
        "host_syncs", "tokens_out")


def window_counts(snaps, layers):
    """The counters' change between the last two snapshots, and the
    synchronous steps of that stretch by reason; ``layers`` is the model's
    depth."""
    m0, m1 = snaps[-2], snaps[-1]
    out = {k: m1[k] - m0[k] for k in KEYS}
    r0, r1 = (m["decode_steps_sync_by_reason"] for m in (m0, m1))
    out["sync_by_reason"] = {k: v - r0.get(k, 0) for k, v in r1.items()
                             if v - r0.get(k, 0)}
    steps = out["decode_steps_ahead"] + out["decode_steps_sync"]
    out["engage_share"] = out["decode_steps_ahead"] / steps if steps else None
    d = lambda k: m1.get(k, 0) - m0.get(k, 0)  # noqa: E731
    # prefills that ended in the window, and of them those whose logits
    # were fetched with the next decode step enqueued behind the chunk
    # (ISSUE 34; an engine from before it counts none)
    ends = d("prefills")
    out["prefill_ends"] = {
        "prefills": ends, "behind_decode": d("prefill_ends_behind_decode"),
        "engage_share": d("prefill_ends_behind_decode") / ends if ends
        else None}
    # what the window's chunk graphs computed over the real tokens they
    # were asked for (ISSUE 35; an engine from before it counts neither)
    real, padded = d("prefill_tokens"), d("prefill_padded_tokens")
    out["prefill_padding"] = {
        "tokens": real, "padded_tokens": padded,
        "padded_over_tokens": padded / real if real else None,
        "padded_share": 100.0 * (padded - real) / padded if padded else None}
    # of the window's chunks, those whose program read the request's keys
    # in a row through the chunk kernel (ISSUE 36; an engine from before it
    # counts none)
    chunks, in_a_row = d("prefill_chunks"), d("prefill_chunks_in_a_row")
    out["prefill_in_a_row"] = {
        "chunks": chunks, "in_a_row": in_a_row,
        "share": in_a_row / chunks if chunks else None}
    # an expert model's grouped kernel (ISSUE 30): of the times an expert's
    # weights were streamed, the share that was that expert's only read
    # that layer-step
    for kind in ("decode", "prefill"):
        hit, passes = (d(f"moe_{k}_{kind}")
                       for k in ("experts_hit", "weight_passes"))
        if passes:
            out[f"moe_{kind}"] = {"experts_hit": hit, "weight_passes": passes,
                                  "single_read_share": hit / passes}
    # a latent cache (ISSUE 31): the cached rows a decode step's kernel
    # walked and a prefill chunk expanded to heads, in one layer
    if d("mla_latent_tokens_read_decode"):
        out["mla"] = {
            "rows_read_a_decode_step": d("mla_latent_tokens_read_decode")
            / layers / max(out["host_syncs"], 1),
            "rows_expanded_a_chunk": d("mla_context_tokens_expanded_prefill")
            / layers / max(d("prefill_chunks"), 1)}
    # a state kind (ISSUE 33): the states a decode step's kernel read and
    # wrote and the tokens a prefill chunk scanned, in one state layer
    if d("ssm_state_rows_updated_decode"):
        state_layers = max(sum(
            1 for sp in snaps[-1].get("_layout", ()) if sp == "state"), 1)
        out["ssm"] = {
            "states_updated_a_decode_step": d("ssm_state_rows_updated_decode")
            / state_layers / max(out["host_syncs"], 1),
            "tokens_scanned_a_chunk": d("ssm_tokens_scanned_prefill")
            / state_layers / max(d("prefill_chunks"), 1),
            "state_bytes_now": m1.get("state_bytes"),
            "state_slots_in_use": m1.get("state_slots_in_use")}
    return out


def by_kind(events, n):
    """``[(kind, seconds, calls)]``: device time by kind of operation, a
    Mosaic kernel by its name and an XLA operation by its opcode and result
    (``fusion bf16[192,2688]``), the ``n`` largest."""
    import re

    kinds = {}
    for name, _, dur, _ in events:
        head, _, rest = name.partition(" ")
        kind = re.sub(r"\.\d+$", "", head) if " custom-call" in " " + rest \
            else rest
        sec, calls = kinds.get(kind, (0.0, 0))
        kinds[kind] = (sec + dur, calls + 1)
    return sorted(((k, s, c) for k, (s, c) in kinds.items()),
                  key=lambda r: -r[1])[:n]


def full_chunk_shares(cache, heads, max_model_len, lens_by_step):
    """``full_chunk_share`` of ``scripts/paged_decode_microbench.py`` over
    the decode rows' context lengths of every step of ``lens_by_step``, for
    each distinct layer spec of ``cache`` (a ``PagedKVCache``), with the
    chunk the decode kernel plans for it."""
    from paged_decode_microbench import full_chunk_share
    from paddle_tpu.ops.pallas.paged_attention import _decode_chunk

    block = cache.block_size
    lens = [n for step in lens_by_step for n in step]
    out = {}
    for spec in dict.fromkeys(sp for sp in cache.layout if sp.paged):
        pages = cache.window.ring if spec.kind == "window" \
            else -(-max_model_len // block)
        hkv, dv = (1, 0) if spec.kind == "latent" \
            else (spec.num_kv_heads, spec.v_dim)
        chunk = _decode_chunk(block, hkv, heads, spec.k_store, 2, pages,
                              dv)[0]
        out[spec.kind] = {
            "chunk": chunk, "layers": cache.layout.count(spec),
            "full_chunk_share": full_chunk_share(lens, block, chunk,
                                                 spec.window)}
    return out


def chunk_tile_shares(eng, config, chunks):
    """For each kind of layer a prefill chunk attends in, the chunk kernel's
    live and dead grid steps over the traced chunks (``engine.prefill.chunk``
    statistics: ``start``, ``tokens``, and ``padded``, the rung), by
    ``chunk_tile_counts``. A latent layer's chunk attends over expanded
    heads; a window layer's row is the ring's tail before the chunk and the
    chunk."""
    from paddle_tpu.ops.pallas.paged_attention import chunk_tile_counts

    cache, bs = eng.cache, eng.cache.block_size
    heads = config["num_attention_heads"]
    out = {}
    for spec in dict.fromkeys(sp for sp in cache.layout if sp.paged):
        window = spec.window if spec.kind == "window" else None
        hkv = heads if spec.kind == "latent" else spec.num_kv_heads
        tail = cache.window.n_tail * bs if window else 0
        live = dead = 0
        for st in chunks:
            start, tokens, rung = (int(st[k]) for k in
                                   ("start", "tokens", "padded"))
            got = chunk_tile_counts(
                start, tokens, rung, config.get("swa_num_attention_heads",
                                                heads) if window else heads,
                hkv, tail + rung if window else eng.max_pages * bs, window,
                start - tail if window else 0)
            live, dead = live + got[0], dead + got[1]
        out[spec.kind] = {"layers": cache.layout.count(spec), "live": live,
                          "dead": dead}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks import run as bench
    from benchmarks.harness import prefill_spans, stats, trace_reduce
    from benchmarks.runners import common
    from paddle_tpu.inference.serving import LLMEngine

    manifest = bench.load_json("BENCHMARK.json")
    cell = bench.find_cell(manifest, args.workload)
    config = bench.load_json("benchmarks", "configs", cell["config"] + ".json")
    traffic = bench.load_json("benchmarks", "traffic", cell["traffic"] + ".json")
    common.require_tpu(cell["chips"])
    common.place_cache()

    snaps = []
    plain = LLMEngine.metrics

    def kept(self):
        m = plain(self)
        snaps.append(dict(m, _layout=[sp.kind for sp in self.cache.layout]))
        return m

    LLMEngine.metrics = kept
    # the context lengths of every step's decode rows, from the loop's own
    # books (``serve.Loop.step`` keeps only their sum), keyed by the step's
    # start
    from benchmarks.runners import serve

    lens_of, engines, plain_step = {}, [], serve.Loop.step

    def step(self):
        before = [(lv, len(lv.token_ts)) for lv in self.live.values()]
        plain_step(self)
        engines[:] = [self.eng]
        lens_of[self.steps[-1][0]] = [
            lv.item.prompt_len + j for lv, j in before
            if j and len(lv.token_ts) > j]

    serve.Loop.step = step
    runner = importlib.import_module("benchmarks.runners." + config["kind"])
    run = runner.run(config, traffic, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace),
                     out_dir=os.path.join(ROOT, "benchmarks_out",
                                          args.workload, "trace"),
                     t_start=T_START, chips=cell["chips"])
    out = {"workload": args.workload, "seed": args.seed,
           "correct": bool(run["correct"]),
           "compiles_in_window": run["compiles_in_window"],
           "setup_s": run["setup_s"],
           "serve_tokens_per_s": run["values"]["serve_tokens_per_s"],
           "decode_step_ms_p50": stats.percentile(
               run["series"]["decode_step_ms"], 50),
           "window": window_counts(snaps, config["num_hidden_layers"]),
           # where the window's seconds went: calls that prefilled
           # nothing, and calls that ended a prefill (chunk + decode)
           "account": {k: {"calls": len(v), "seconds": sum(v) / 1e3,
                           "p50_ms": stats.percentile(v, 50) if v else None}
                       for k, v in run["series"].items()
                       if k in ("decode_step_ms", "prefill_step_ms")}}
    tr = run.get("trace")
    if tr:
        decode = trace_reduce.select(tr["events"], None, "decode_pure")
        steps = sum(1 for s in run["traced_steps"] if s[4])
        out["traced"] = {
            "calls": len(run["traced_steps"]),
            "call_ms_p50": stats.percentile(
                [(s[1] - s[0]) * 1e3 for s in run["traced_steps"]], 50),
            "decode_steps": steps,
            "decode_pure_device_ms_a_step":
                1e3 * trace_reduce.busy_seconds(decode) / steps,
            "decode_step_ms_p50": stats.percentile(
                [(s[1] - s[0]) * 1e3 for s in run["traced_steps"]
                 if s[4] and not s[3]], 50),
            "grouped_ffn_device_ms_a_step": 1e3 * trace_reduce.op_seconds(
                tr["events"], "moe_grouped_(swiglu|relu2)", "decode_pure")
            / steps,
            "latent_decode_device_ms_a_step": 1e3 * trace_reduce.op_seconds(
                tr["events"], "paged_decode_attention_latent",
                "decode_pure") / steps,
            "ssm_decode_device_ms_a_step": 1e3 * trace_reduce.op_seconds(
                tr["events"], "mamba2_decode_update", "decode_pure") / steps,
            "decode_pure_by_kind": by_kind(decode, 40),
            "chunk_pure_by_kind": by_kind(trace_reduce.select(
                tr["events"], None, "chunk_pure"), 24),
            # a chunk's attention kernels, whatever implements them (the
            # benchmark's own pattern: ``prefill_spans.ATTENTION``)
            "chunk_attention_device_s": trace_reduce.op_seconds(
                tr["events"], "(paged_prefill_attention|chunk_attention)",
                "chunk_pure"),
            "idle_share": 100.0 * (1.0 - tr["busy_s"] / tr["window_s"]),
            "idle_s": tr["window_s"] - tr["busy_s"],
            "idle_gaps": tr["breakdown"]["idle_gaps"],
            "device_ops": trace_reduce.top_ops(tr["events"], 16),
            # seconds of the traced window by program
            "device_s_by_program": {
                prog: trace_reduce.busy_seconds(
                    trace_reduce.select(tr["events"], None, prog))
                for prog in ("decode_pure", "chunk_pure")},
            "window_s": tr["window_s"],
            "decode_chunks": full_chunk_shares(
                engines[0].cache, config["num_attention_heads"],
                config["engine"]["max_model_len"],
                [lens_of[s[0]] for s in run["traced_steps"]]),
        }
        # the chunk kernel's grid over the traced chunks
        spans = prefill_spans.from_record(run)
        if spans:
            out["traced"]["chunk_tiles"] = chunk_tile_shares(
                engines[0], config, [
                    st for a, _, st in prefill_spans.spans(
                        spans[0], prefill_spans.CHUNK)
                    if tr["t0"] <= a < tr["t1"]])
        # the benchmark's own per-layer readings of this run
        out["per_layer"] = {k: v["value"] for k, v in bench.result_line(
            manifest, args.workload, run, True)["metrics"].items()}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
