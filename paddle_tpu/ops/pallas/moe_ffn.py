"""Blockwise MoE expert FFN — Pallas TPU kernel.

The SURVEY §7.1 "MoE dispatch" kernel, scoped the TPU-native way: the
dispatch/combine scatter-gathers are already XLA's strength (sort-free
one-hot/scatter lowering; under GSPMD they become the all_to_all the
reference's global_scatter/global_gather collective ops implement by hand —
paddle/fluid/operators/collective/global_scatter_op.*). What XLA does NOT do
for the expert computation is avoid materializing the [E, C, I] SwiGLU
intermediates in HBM (I = intermediate ≈ 4h, so that round-trip is the
dominant MoE memory traffic). This kernel computes, per (expert, token
block), the full SwiGLU FFN

    out = (silu(x @ Wg) * (x @ Wu)) @ Wd

with the [bc, bi] intermediates living only in VMEM, accumulating the down
projection across I tiles in an f32 output block. Backward is
recompute-style in XLA (same policy as ops/pallas/rms_norm.py: the fwd
kernel saves only the inputs).

Routing contract: h % 128 == 0 and I % 128 == 0; callers fall back to the
einsum composition otherwise. Opt-in via ``PT_FUSED_MOE=1`` (measure before
flipping any default — PERF.md).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _interpret, _pick_block

__all__ = ["moe_expert_ffn", "use_fused_moe_ffn", "moe_ffn_shapes_ok"]


def use_fused_moe_ffn():
    return os.environ.get("PT_FUSED_MOE", "0") == "1"


def moe_ffn_shapes_ok(h, i):
    return h % 128 == 0 and i % 128 == 0


def _blocks(c, i):
    return (_pick_block("PT_MOE_BC", 256, c),
            _pick_block("PT_MOE_BI", 512, i, floor=128))


def _ffn_kernel(x_ref, gw_ref, uw_ref, dw_ref, out_ref):
    it = pl.program_id(2)
    x = x_ref[0].astype(jnp.float32)                       # [bc, h]
    g = jax.lax.dot(x, gw_ref[0].astype(jnp.float32))      # [bc, bi]
    u = jax.lax.dot(x, uw_ref[0].astype(jnp.float32))
    act = jax.nn.silu(g) * u
    part = jax.lax.dot(act, dw_ref[0].astype(jnp.float32))  # [bc, h]

    @pl.when(it == 0)
    def _init():
        out_ref[0] = part

    @pl.when(it > 0)
    def _acc():
        out_ref[0] += part


def _ffn_fwd_arrays(x, gate_w, up_w, down_w):
    e, c, h = x.shape
    i = gate_w.shape[-1]
    bc, bi = _blocks(c, i)
    out = pl.pallas_call(
        _ffn_kernel,
        grid=(e, c // bc, i // bi),
        in_specs=[
            pl.BlockSpec((1, bc, h), lambda ei, ci, ii: (ei, ci, 0)),
            pl.BlockSpec((1, h, bi), lambda ei, ci, ii: (ei, 0, ii)),
            pl.BlockSpec((1, h, bi), lambda ei, ci, ii: (ei, 0, ii)),
            pl.BlockSpec((1, bi, h), lambda ei, ci, ii: (ei, ii, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, h), lambda ei, ci, ii: (ei, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((e, c, h), jnp.float32),
        interpret=_interpret(),
        name="moe_ffn",
    )(x, gate_w, up_w, down_w)
    return out.astype(x.dtype)


@jax.custom_vjp
def moe_expert_ffn(x, gate_w, up_w, down_w):
    """SwiGLU expert FFN over dispatched tokens.

    x: [E, C, h]; gate_w/up_w: [E, h, I]; down_w: [E, I, h] → [E, C, h],
    without HBM-materializing the [E, C, I] intermediates.
    """
    return _ffn_fwd_arrays(x, gate_w, up_w, down_w)


def _ffn_fwd(x, gate_w, up_w, down_w):
    return _ffn_fwd_arrays(x, gate_w, up_w, down_w), (x, gate_w, up_w, down_w)


def _ffn_bwd(res, dout):
    x, gate_w, up_w, down_w = res
    xf = x.astype(jnp.float32)
    do = dout.astype(jnp.float32)
    g = jnp.einsum("ech,ehi->eci", xf, gate_w.astype(jnp.float32))
    u = jnp.einsum("ech,ehi->eci", xf, up_w.astype(jnp.float32))
    sg = jax.nn.sigmoid(g)
    s = g * sg                                  # silu(g)
    act = s * u
    d_act = jnp.einsum("ech,eih->eci", do, down_w.astype(jnp.float32))
    d_down = jnp.einsum("eci,ech->eih", act, do)
    du = d_act * s
    ds = d_act * u
    dg = ds * (sg * (1.0 + g * (1.0 - sg)))     # d silu
    dx = (jnp.einsum("eci,ehi->ech", dg, gate_w.astype(jnp.float32))
          + jnp.einsum("eci,ehi->ech", du, up_w.astype(jnp.float32)))
    d_gate = jnp.einsum("ech,eci->ehi", xf, dg)
    d_up = jnp.einsum("ech,eci->ehi", xf, du)
    return (dx.astype(x.dtype), d_gate.astype(gate_w.dtype),
            d_up.astype(up_w.dtype), d_down.astype(down_w.dtype))


moe_expert_ffn.defvjp(_ffn_fwd, _ffn_bwd)
