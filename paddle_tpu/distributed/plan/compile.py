"""``compile_step_with_plan`` — the one compile layer for planned steps.

Everything data-parallel-ish lowers through ``jax.jit`` with
``in_shardings``/``out_shardings`` built from the plan (GSPMD partitions
the body); only the attention collectives — the ppermute ring rotation and
the Ulysses all_to_all head/seq re-shard, which GSPMD cannot express —
drop to ``shard_map``, and they do so INSIDE the model ops
(``ring_flash_attention`` / ``sep_all_to_all_attention``), not here: a
planned step containing sep attention is still one ``jax.jit`` whose trace
embeds the manual region. That split (pjit outside, shard_map only for
collectives) is the SNIPPETS [1][3] pattern and is documented in
DESIGN_DECISIONS.md "Sharding plans".

Spec trees passed here are *prefix pytrees* of the function arguments (the
``jax.jit`` contract): a leaf may be ``None`` (leave jax to infer from the
committed argument placement), a ``PartitionSpec`` (resolved over the plan
mesh) or a ready ``Sharding``.
"""

from __future__ import annotations

import functools

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["compile_step_with_plan"]


def _resolve_tree(plan, tree):
    """Map ``PartitionSpec`` leaves to ``NamedSharding`` over the plan
    mesh; ``None`` holes and ready ``Sharding`` leaves pass through.
    Tuples/lists/dicts are containers (the jax.jit prefix-pytree
    convention) — spec leaves must be ``PartitionSpec``, never bare
    tuples, so containers and specs cannot be confused."""
    if tree is None:
        return None

    def is_leaf(x):
        return x is None or isinstance(x, (P, jax.sharding.Sharding))

    def conv(x):
        if isinstance(x, P):
            return NamedSharding(plan.mesh, x)
        return x

    return jax.tree.map(conv, tree, is_leaf=is_leaf)


def _traced_under(plan, fn):
    """``fn`` with ``plan`` marked active while jax traces it, so the
    Pallas kernel dispatchers inside can run per shard (``active_plan``)."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with plan.tracing():
            return fn(*args, **kwargs)
    return traced


def compile_step_with_plan(fn, plan=None, *, in_specs=None, out_specs=None,
                           donate_argnums=(), static_argnums=(), name=None):
    """Compile ``fn`` under a :class:`~.plan.Plan`.

    - ``plan=None`` (or a 1-device mesh): plain ``jax.jit`` — single-device
      deployments and the planned path share this one entry point, so there
      is no strategy-specific compile fork at the call sites.
    - ``in_specs``/``out_specs``: prefix pytrees of PartitionSpecs (or
      ``None`` holes) resolved over ``plan.mesh``.
    - ``name``: register compile/hit telemetry for this executable under
      ``paddle.jit.cache_stats()[name]`` (the serving engine's CountingJit
      contract). The returned object then exposes ``__call__`` with
      counting; without ``name`` the raw ``jax.jit`` function (with
      ``.lower``) is returned.
    """
    kwargs = dict(donate_argnums=tuple(donate_argnums),
                  static_argnums=tuple(static_argnums))
    if plan is not None and plan.mesh.devices.size > 1:
        fn = _traced_under(plan, fn)
        ins = _resolve_tree(plan, in_specs)
        outs = _resolve_tree(plan, out_specs)
        if ins is not None:
            kwargs["in_shardings"] = ins
        if outs is not None:
            kwargs["out_shardings"] = outs
    if name is None:
        return jax.jit(fn, **kwargs)
    from ...jit.cache import CountingJit

    return CountingJit(fn, name,
                       static_argnums=kwargs.pop("static_argnums"),
                       donate_argnums=kwargs.pop("donate_argnums"),
                       jit_kwargs=kwargs)
