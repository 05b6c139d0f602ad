"""Every Pallas kernel on the default path, taken as far toward a TPU as a
machine without one allows (ISSUE 21).

Two stages, at the shapes ``chip_smoke.py`` runs:

1. **Lowering** (always): ``jit(...).trace(...).lower(lowering_platforms=
   ("tpu",))`` turns the kernel's jaxpr into Mosaic MLIR. This is the stage
   that rejected the old decode kernel (a batched mat-vec with no lhs free
   dimension). It proves nothing about what Mosaic or XLA then accept.
2. **Deviceless compile** (when the installed libtpu can describe a v5e
   topology without a chip): the full XLA:TPU + Mosaic compile against
   ``jax.experimental.topologies``. This is the stage that refused the
   whole-bucket multi-query kernel (VMEM), refuses Mosaic calls under GSPMD
   outside a manual region, and where the rope op on its own aborted the
   compiler. It runs in a subprocess: a compiler abort is a SIGABRT.

Neither stage executes a kernel; numbers and numerics come from the chip
(``python chip_smoke.py``).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import grouped_ffn as gf
from paddle_tpu.ops.pallas import paged_attention as pa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# llama_1b serving geometry: 16 heads x 128, 512 pages of 16 tokens, 128
# pages per request; llama_125m training: 16 x 12 heads, seq 1024, d 64
H, D, BLK, N, P = 16, 128, 16, 512, 128


def _paged_cases():
    """(name, fn, abstract args) for decode and every multi-query rung the
    smoke touches, fp and int8 pools; decode also at the benchmark's
    serving geometry (Mistral-7B: 32 requests, 32 heads over 8 kv heads,
    256 pages a request, 6145 pages) and at one tp4 shard of it."""
    sds = jax.ShapeDtypeStruct
    cases = []

    def decode(q, k, v, t, l, *s):
        return pa.paged_decode_attention_pallas(
            q, k, v, t, l, 0.088, **dict(zip(("k_scale", "v_scale"), s)))

    def mq(q, k, v, t, l, st, *s):
        return pa.paged_multiquery_attention_pallas(
            q, k, v, t, l, st, 0.088,
            **dict(zip(("k_scale", "v_scale"), s)))

    def pools(n, hkv, kv):
        pool = sds((n, BLK, hkv, D), kv)
        return (pool, pool), ((sds((n, BLK, hkv), jnp.float32),) * 2
                              if kv == jnp.int8 else ())

    for kv in (jnp.bfloat16, jnp.int8):
        name = jnp.dtype(kv).name
        for geo, b, h, hkv, p, n in (("", 8, H, H, P, N),
                                     ("-mistral", 32, 32, 8, 256, 6145),
                                     ("-tp4shard", 32, 8, 2, 256, 6145)):
            (k, v), scales = pools(n, hkv, kv)
            cases.append((f"decode{geo}-{name}", decode,
                          (sds((b, h, D), jnp.bfloat16), k, v,
                           sds((b, p), jnp.int32), sds((b,), jnp.int32))
                          + scales))
        (k, v), scales = pools(N, H, kv)
        for t in (64, 128, 2048):
            cases.append((f"mq{t}-{name}", mq,
                          (sds((1, t, H, D), jnp.bfloat16), k, v,
                           sds((1, P), jnp.int32), sds((1,), jnp.int32),
                           sds((1,), jnp.int32)) + scales))
    return cases


def _chunk_row_cases():
    """ISSUE 36: a Llama-form layer's prefill chunk, the request's pages
    gathered in a row for the chunk kernel, at the benchmark's serving
    geometry (Mistral-7B: a 2,048-token chunk, 256 pages of 16 a request out
    of 6,145, 32 heads over 8 kv heads of 128), beside ``mq2048``: the
    multi-query kernel stays int8 pools' and the verify step's."""
    from paddle_tpu.inference.serving import paged_attention as spa

    sds = jax.ShapeDtypeStruct

    def chunk(q, k, v, t, start, upto):
        return spa._pages_in_a_row(q, k, v, t, start, upto, scale=0.088,
                                   interpret=False)

    pool = sds((6145, BLK, 8, D), jnp.bfloat16)
    return [("chunk-row2048-mistral", chunk, (
        sds((1, 2048, 32, D), jnp.bfloat16), pool, pool,
        sds((256,), jnp.int32), sds((), jnp.int32), sds((), jnp.int32)))]


def _flash_cases():
    sds = jax.ShapeDtypeStruct
    cases = []
    for bh, sq, d in ((192, 1024, 64), (16, 2048, 128)):
        q = sds((bh, sq, d), jnp.bfloat16)
        bq, bk = fa._block_sizes(sq, sq)

        def fwd(q, k, v, bq=bq, bk=bk):
            return fa._flash_mha(q, k, v, 0.125, True, bq, bk)

        def bwd(q, k, v, fwd=fwd):
            return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                            argnums=(0, 1, 2))(q, k, v)

        cases.append((f"flash-fwd-{sq}x{d}", fwd, (q, q, q)))
        cases.append((f"flash-bwd-{sq}x{d}", bwd, (q, q, q)))
    return cases


def _grouped_ffn_cases():
    """The grouped expert kernel at MiMo-V2-Flash's published widths and the
    benchmark's share (16 held experts, 48 arrays left in HBM): a decode
    step's 64 rows a pass, a 2,048-token chunk's 128."""
    sds = jax.ShapeDtypeStruct
    d, f, held, top_k = 4096, 2048, 16, 8

    def ffn(rows):
        def run(x, order, item_expert, item_start, item_rows, n_items, *w):
            return gf.grouped_swiglu(
                x, order, item_expert, item_start, item_rows, n_items,
                [tuple(w[3 * e:3 * e + 3]) for e in range(held)], rows=rows,
                top_k=top_k)
        return run

    cases = []
    for t, rows in ((64, 64), (2048, 128)):
        items = sds((held + t * top_k // rows,), jnp.int32)
        cases.append((f"grouped-ffn-{t}", ffn(rows), (
            sds((t, d), jnp.bfloat16), sds((t * top_k,), jnp.int32),
            items, items, items, sds((), jnp.int32)) + tuple(
                sds(shape, jnp.bfloat16) for _ in range(held)
                for shape in ((d, f), (d, f), (f, d)))))
    return cases


def _nemotron_cases():
    """ISSUE 33's two kernels at the published widths and the benchmark's
    batch: the state update over 192 rows of 193 slots (64 heads of 64 over a
    state of 128, two heads a lane row), and the grouped expert kernel
    WITHOUT a gate at hidden 2,688, an expert 1,856 wide stored 1,920, a
    decode step's 192 tokens and a 2,048-token chunk."""
    from paddle_tpu.ops.pallas import mamba2

    sds = jax.ShapeDtypeStruct
    b, heads, p, n, groups = 192, 64, 64, 128, 8
    def update(*operands):      # the kernel itself, whatever the backend
        return mamba2._call(*operands, interpret=False)

    cases = [("ssm-decode-update", update, (
        sds((b + 1, heads // 2, n, 2 * p), jnp.float32), sds((b,), jnp.int32),
        sds((b, heads, p), jnp.bfloat16), sds((b, heads), jnp.float32),
        sds((heads,), jnp.float32), sds((b, groups, n), jnp.bfloat16),
        sds((b, groups, n), jnp.bfloat16)))]
    d, f, held, top_k = 2688, 1920, 16, 6

    def ffn(rows):
        def run(x, order, item_expert, item_start, item_rows, n_items, *w):
            return gf.grouped_swiglu(
                x, order, item_expert, item_start, item_rows, n_items,
                [tuple(w[2 * e:2 * e + 2]) for e in range(held)], rows=rows,
                top_k=top_k)
        return run

    for t in (192, 2048):
        rows = gf.rows_for(t)
        items = sds((held + t * top_k // rows,), jnp.int32)
        cases.append((f"grouped-relu2-{t}", ffn(rows), (
            sds((t, d), jnp.bfloat16), sds((t * top_k,), jnp.int32),
            items, items, items, sds((), jnp.int32)) + tuple(
                sds(shape, jnp.bfloat16) for _ in range(held)
                for shape in ((d, f), (f, d)))))
    return cases


def _qwen3_next_cases():
    """ISSUE 37's kernel and the two attention kernels at V 256, at the
    published widths and the benchmark's batch: the gated delta update over
    96 rows of 97 slots (32 value heads of 128 over 16 key heads of 128, a
    head a lane row), the decode kernel over pools held as rows (16 query
    heads over 2 kv heads, K and V 256 wide, 640 pages a request of 61,441)
    and the chunk kernel over a 10,240-token row at a 2,048-token chunk."""
    from paddle_tpu.ops.pallas import gated_delta

    sds = jax.ShapeDtypeStruct
    b, heads, key_heads, n, p = 96, 32, 16, 128, 128

    def update(*operands):      # the kernel itself, whatever the backend
        return gated_delta._call(*operands, interpret=False)

    def decode(q, k, v, t, l):
        return pa.paged_decode_attention_pallas(
            q, k, v, t, l, 0.0625, num_kv_heads=2,
            name="paged_decode_attention_global")

    def chunk(q, k, v):
        return pa.chunk_attention_pallas(q, k, v, 8192, 0, 10240, 0.0625,
                                         name="chunk_attention_global")

    pool = sds((61441, BLK * 2, 256), jnp.bfloat16)
    row = sds((10240, 2, 256), jnp.bfloat16)
    return [
        ("delta-decode-update", update, (
            sds((b + 1, heads, n, p), jnp.float32), sds((b,), jnp.int32),
            sds((b, key_heads, n), jnp.bfloat16),
            sds((b, key_heads, n), jnp.bfloat16),
            sds((b, heads, p), jnp.bfloat16), sds((b, heads), jnp.float32),
            sds((b, heads), jnp.float32))),
        ("decode-v256-qwen3next", decode, (
            sds((b, 16, 256), jnp.bfloat16), pool, pool,
            sds((b, 640), jnp.int32), sds((b,), jnp.int32))),
        ("chunk-v256-qwen3next", chunk, (
            sds((2048, 16, 256), jnp.bfloat16), row, row))]


def _joyai_chunk_cases():
    """The chunk kernel at JoyAI-LLM-Flash's expanded heads (32 query heads
    over 32, K 192 stored 256, V 128), a 2,048-token chunk at offset 8,192
    of a 12,288-key row: most of its key tiles fold without a mask."""
    sds = jax.ShapeDtypeStruct

    def chunk(q, k, v):
        return pa.chunk_attention_pallas(q, k, v, 8192, 0, 10240, 0.072,
                                         name="chunk_attention_global")

    return [("chunk-k256v128-joyai", chunk, (
        sds((2048, 32, 256), jnp.bfloat16), sds((12288, 32, 256), jnp.bfloat16),
        sds((12288, 32, 128), jnp.bfloat16)))]


def _lfm2_cases():
    """Heads of 64 held two kv heads a lane row, at
    lfm2-8b-a1b-serve's geometry: the decode kernel over 128 requests of up
    to 1,280 pages of 81,921 (32 query heads over 8 kv heads), and a 2,048-row
    chunk over a request's 20,480 keys in a row."""
    sds = jax.ShapeDtypeStruct
    pool = sds((81921, BLK * 4, 128), jnp.bfloat16)

    def decode(q, k, v, t, ln):
        return pa.paged_decode_attention_pairs_pallas(q, k, v, t, ln, 0.125,
                                                      num_kv_heads=8)

    def chunk(q, k, v):
        return pa.chunk_attention_pairs_pallas(q, k, v, 18432, 0, 20480, 0.125,
                                               num_kv_heads=8)

    keys = sds((20480, 4, 128), jnp.bfloat16)
    return [("decode-h64-lfm2", decode, (
        sds((128, 32, 64), jnp.bfloat16), pool, pool,
        sds((128, 1280), jnp.int32), sds((128,), jnp.int32))),
        ("chunk-h64-lfm2", chunk, (sds((2048, 32, 64), jnp.bfloat16),
                                   keys, keys))]


CASES = _flash_cases() + _paged_cases() + _grouped_ffn_cases() \
    + _nemotron_cases() + _chunk_row_cases() + _qwen3_next_cases() \
    + _joyai_chunk_cases() + _lfm2_cases()
#: stage 2 keeps tier-1 short: the backward cases (a grad compiles the
#: forward kernel too), decode, the top rung that VMEM decides, and the
#: grouped expert kernel (48 operands left in HBM, 48 MiB of VMEM asked for)
COMPILED_CASES = [c for c in CASES if c[0].startswith(
    ("flash-bwd", "decode", "mq2048", "grouped-ffn", "ssm-decode",
     "grouped-relu2", "chunk-row", "delta-decode", "chunk-v256",
     "chunk-k256v128", "chunk-h64"))]


@pytest.mark.parametrize("name,fn,args", CASES, ids=[c[0] for c in CASES])
def test_lowers_to_mosaic(name, fn, args, monkeypatch):
    monkeypatch.setenv("PT_PALLAS_INTERPRET", "0")
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


_COMPILE = textwrap.dedent("""
    import sys
    sys.path.insert(0, {repo!r})
    sys.path.insert(0, {tests!r})
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        print("NO_TOPOLOGY", repr(e)[:300])
        sys.exit(0)
    import test_pallas_tpu_lowering as T
    from paddle_tpu.models import llama

    one = SingleDeviceSharding(topo.devices[0])

    def on(sharding, args):
        return [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
                for a in args]

    for name, fn, args in T.COMPILED_CASES:
        text = jax.jit(fn).trace(*on(one, args)).lower().compile().as_text()
        print("COMPILED", name, flush=True)
        # the HLO instructions that are Mosaic kernels, by their own names
        # (XLA has custom calls of its own, e.g. ``ConcatBitcast``)
        print("KERNELS", name, *sorted(set(
            ln.split(" = ")[0].split("%")[-1].rsplit(".", 1)[0]
            for ln in text.splitlines() if " custom-call(" in ln
            and 'custom_call_target="tpu_custom_call"' in ln)), flush=True)

    # the rope op on its own, at head-dim 128 (the split-and-concatenate
    # form aborted the compiler here)
    x = jax.ShapeDtypeStruct((7, 200, 16, 128), jnp.bfloat16, sharding=one)
    t = jax.ShapeDtypeStruct((200, 64), jnp.float32, sharding=one)
    jax.jit(llama._rope_apply.raw_fn).trace(x, t, t).lower().compile()
    print("COMPILED rope", flush=True)

    # a Mosaic call under a 4-device GSPMD jit: refused outside a manual
    # region, accepted per shard under an active plan
    from paddle_tpu.distributed.plan import Plan, compile_step_with_plan
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))
    plan = Plan.build(mesh, ["dp", "tp"])
    q = jax.ShapeDtypeStruct((16, 1024, 12, 64), jnp.bfloat16,
                             sharding=NamedSharding(
                                 mesh, P("dp", None, "tp", None)))
    attn = lambda q, k, v: T.fa._flash_attention_arrays.raw_fn(q, k, v)
    try:
        jax.jit(attn).trace(q, q, q).lower().compile()
        print("UNEXPECTED: GSPMD partitioned a Mosaic call")
    except NotImplementedError:
        print("REFUSED bare mosaic under gspmd", flush=True)
    compile_step_with_plan(attn, plan).trace(q, q, q).lower().compile()
    print("COMPILED per-shard under plan", flush=True)

    # ISSUE 36: a Llama-form chunk under a plan gathers the request's pages
    # in a row INSIDE the manual region, each shard its own kv heads (the
    # dispatcher asks the backend, which is the CPU here: told it is a TPU)
    from paddle_tpu.inference.serving import paged_attention as spa
    T.pa.use_pallas_paged = lambda *a: True
    name, _, args = T._chunk_row_cases()[0]
    heads = NamedSharding(mesh, P(None, None, "tp", None))
    rep = NamedSharding(mesh, P())
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                 sharding=heads if a.ndim == 4 else rep)
            for a in args]
    text = compile_step_with_plan(
        lambda *a: spa.paged_chunk_attention(*a, 0.088), plan).trace(
            *args).lower().compile().as_text()
    assert "chunk_attention" in text and "all-gather" not in text
    print("COMPILED chunk in a row per-shard under plan", flush=True)
""")


def test_compiles_for_v5e_without_a_chip():
    """Stage 2 (see module docstring). Skips when libtpu cannot describe a
    topology on this machine."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PT_PALLAS_INTERPRET="0")
    code = _COMPILE.format(repo=REPO, tests=os.path.dirname(__file__))
    try:
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=240)
    except subprocess.TimeoutExpired:
        # ~12 s here; a libtpu that stalls looking for a chip is the
        # machine's business, not a kernel regression
        pytest.skip("deviceless compile did not finish in 240 s")
    if "NO_TOPOLOGY" in r.stdout:
        pytest.skip("libtpu cannot describe a v5e topology here: "
                    + r.stdout.strip()[-300:])
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    done = {ln.split(" ", 1)[1] for ln in r.stdout.splitlines()
            if ln.startswith("COMPILED ")}
    assert done == {c[0] for c in COMPILED_CASES} | {
        "rope", "per-shard under plan",
        "chunk in a row per-shard under plan"}
    assert "REFUSED bare mosaic under gspmd" in r.stdout
    # a pallas_call's ``name`` becomes its HLO instruction's own name (under
    # jax.grad wrapped: ``transpose_jvp_flash_attention_bwd_dq__``), which is
    # what a profile's device line and the benchmark's reduction show
    kernels = {ln.split(" ")[1]: ln.split(" ")[2:] for ln in
               r.stdout.splitlines() if ln.startswith("KERNELS ")}
    for case, want in (("decode", ["paged_decode_attention"]),
                       ("mq2048", ["paged_prefill_attention"]),
                       ("chunk-row", ["chunk_attention"]),
                       ("grouped-ffn", ["moe_grouped_swiglu"]),
                       ("grouped-relu2", ["moe_grouped_relu2"]),
                       ("ssm-decode", ["mamba2_decode_update"]),
                       ("delta-decode", ["gated_delta_decode_update"]),
                       ("chunk-v256", ["chunk_attention_global"]),
                       ("chunk-k256v128", ["chunk_attention_global"]),
                       ("decode-h64", ["paged_decode_attention_h64"]),
                       ("chunk-h64", ["chunk_attention_h64"]),
                       ("flash-bwd", ["flash_attention_fwd",
                                      "flash_attention_bwd_dq",
                                      "flash_attention_bwd_dkv"])):
        for name, got in kernels.items():
            if name.startswith(case):
                assert len(got) == len(want) and all(
                    any(w in g for g in got) for w in want), (name, got)
    assert set(kernels) == {c[0] for c in COMPILED_CASES}


# --- the decode kernel's chunk, and its parity in interpret mode ------------

def test_decode_chunk_fits_its_vmem_budget():
    """The pages a decode chunk holds follow from the operands' shapes; at
    the benchmark's geometry that is 16 pages (256 tokens), and the plan
    stays inside the stated budget, itself well inside Mosaic's 16 MiB."""
    assert pa._DECODE_VMEM_BUDGET <= 16 * 2 ** 20 // 4
    for shape, want in (((16, 8, 32, 128, 2, 256), 16),    # Mistral-7B
                        ((16, 2, 8, 128, 2, 256), 64),     # a tp4 shard
                        ((16, 16, 16, 128, 2, 128), 8),    # llama_1b
                        ((16, 8, 32, 128, 2, 4), 4),       # a 4-page table
                        ((16, 2, 16, 256, 2, 640, 256), 32),   # Qwen3-Next
                        ((16, 4, 32, 128, 2, 1280), 32)):  # LFM2, lane pairs
        chunk, nbytes = pa._decode_chunk(*shape)
        assert chunk == want, (shape, chunk)
        assert nbytes <= pa._DECODE_VMEM_BUDGET, (shape, nbytes)


@pytest.mark.parametrize("groups", [1, 4])
@pytest.mark.parametrize("pool", ["float32-2pages", "float32",
                                  "bfloat16-2pages", "int8",
                                  "float32-8pages"])
def test_decode_interpret_matches_lax_fallback(groups, pool, monkeypatch):
    """One batch with a context at every edge of a page and of a chunk
    (1, block - 1, block, C*block - 1, C*block, C*block + 1, the cap),
    unused table slots on the null page, and NaN in every pool slot that
    holds no live token: a dead column has p = 0, and 0 x NaN is NaN. With 8
    pages a chunk under a table of 20: full chunks, whose 8 copies a pool
    are started written out and waited for with one descriptor, and last
    chunks of 1, 3 and 4 pages, started in a loop and waited for by the
    binary digits of their number."""
    import numpy as np

    from paddle_tpu.inference.serving.kv_cache import quantize_kv_rows
    from paddle_tpu.inference.serving.paged_attention import _lax_fallback

    monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
    hkv, d, blk = 2, 16, 4
    h = hkv * groups
    dtype = jnp.bfloat16 if pool.startswith("bfloat16") else jnp.float32
    pages = {"2pages": 2, "8pages": 8}.get(pool.split("-")[-1], 16)
    p = 20 if pages == 8 else 10
    if pages < 16:
        # the budget that fits a chunk of that many pages and not twice it
        for budget in range(256, 1 << 20, 64):
            monkeypatch.setattr(pa, "_DECODE_VMEM_BUDGET", budget)
            if pa._decode_chunk(blk, hkv, h, d, 4, p)[0] == pages:
                break
    assert pa._decode_chunk(blk, hkv, h, d, 4, p)[0] == pages
    # the chunk edge of the cases cut to a chunk; a page edge otherwise
    c = (pages if pages < 16 else 2) * blk
    lens = np.array([1, blk - 1, blk, c - 1, c, c + 1, 2 * c + 2 * blk + 2,
                     p * blk], np.int32)
    b, n = len(lens), len(lens) * p + 1
    rng = np.random.default_rng(groups)
    order = rng.permutation(np.arange(1, n))
    tables = np.zeros((b, p), np.int32)
    live = np.zeros((n, blk), bool)
    at = 0
    for i, ctx in enumerate(lens):
        pages = -(-ctx // blk)
        tables[i, :pages] = order[at:at + pages]
        at += pages
        live[tables[i, :pages]] = True
        live[tables[i, pages - 1], ctx - (pages - 1) * blk:] = False
    q = jnp.asarray(rng.standard_normal((b, h, d)), dtype)
    k_pool = jnp.asarray(rng.standard_normal((n, blk, hkv, d)), dtype)
    v_pool = jnp.asarray(rng.standard_normal((n, blk, hkv, d)), dtype)
    scales, dirty_scales = {}, {}
    if pool == "int8":
        k_pool, ks = quantize_kv_rows(k_pool)
        v_pool, vs = quantize_kv_rows(v_pool)
        scales = {"k_scale": ks, "v_scale": vs}
        dirty_scales = {key: jnp.where(jnp.asarray(live)[..., None], s,
                                       jnp.nan) for key, s in scales.items()}
        dirty = (k_pool, v_pool)     # codes cannot hold NaN; scales do
    else:
        dirty = tuple(jnp.where(jnp.asarray(live)[..., None, None], x,
                                jnp.nan) for x in (k_pool, v_pool))
    want = _lax_fallback(q[:, None], k_pool, v_pool, jnp.asarray(tables),
                         jnp.asarray(lens), 0.3, **scales)[:, 0]
    got = pa.paged_decode_attention_pallas(
        q, *dirty, jnp.asarray(tables), jnp.asarray(lens), 0.3,
        **dirty_scales)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)
