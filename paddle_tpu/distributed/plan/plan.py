"""The sharding ``Plan``: parallelism as a declarative datum.

A Plan is (mesh, param rules, activation rules, strategy entries):

- **param rules** — ordered ``(name-pattern, dim spec)`` pairs, matched
  with :mod:`fnmatch` against the structured parameter names PR 2
  introduced (``llama.layers.0.self_attn.q_proj.weight``). First match
  wins; a dim whose size the axis does not divide is silently replicated
  for that param (the same degrade rule the graft dryrun used), so one
  rule table serves every model size.
- **activation rules** — a dim→axis map for data batches (``{0: "dp",
  1: "sep"}``), applied by the adopters when staging inputs.
- **strategy entries** — the named, parameterized builders registered in
  :mod:`.strategies` (``dp``/``zero1..3``/``tp``/``sep``/``ep``/``pp``).
  A strategy is a table row that appends rules and sets plan fields; it is
  NOT a code path: every strategy lowers through the same
  :func:`paddle_tpu.distributed.plan.compile_step_with_plan`.

The fingerprint (mesh shape + rule digest) is what
``CheckpointManager`` records per step so a restore onto an incompatible
mesh fails with a typed error instead of mis-sharding silently.
"""

from __future__ import annotations

import contextlib
import contextvars
import fnmatch
import hashlib

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import make_mesh, mesh_axes

__all__ = ["Plan", "PlanError", "active_plan"]

_ACTIVE = contextvars.ContextVar("paddle_tpu_active_plan", default=None)


def active_plan():
    """The multi-device Plan whose step is being traced right now, else
    ``None``. GSPMD partitions everything in a planned step except Mosaic
    custom calls ("Mosaic kernels cannot be automatically partitioned"),
    so the Pallas kernel dispatchers read this to run themselves per
    shard (:meth:`Plan.per_shard`)."""
    return _ACTIVE.get()


class PlanError(ValueError):
    """A plan declaration that cannot be realized (unknown axis, unknown
    strategy, malformed rule)."""


def _as_dims(spec):
    """Normalize a rule spec to a tuple of per-dim entries (axis name,
    tuple of axis names, or None). Accepts PartitionSpec, tuple/list, or a
    dict {dim: axis} (the ``tp_partition_spec`` shape)."""
    if spec is None:
        return ()
    if isinstance(spec, P):
        return tuple(spec)
    if isinstance(spec, dict):
        if not spec:
            return ()
        hi = max(spec)
        return tuple(spec.get(d) for d in range(hi + 1))
    return tuple(spec)


class Plan:
    """Declarative parallelism over one mesh. Build directly or through
    :meth:`Plan.build`'s strategy table::

        plan = Plan.build({"dp": 2, "tp": 2, "ep": 2},
                          ["dp", "tp", "ep", ("zero1", {"axis": "dp"})])

    and hand it to ``FusedTrainStep(plan=...)``, hapi
    ``Model.prepare(plan=...)`` or ``LLMEngine(plan=...)`` — all three
    compile through ``compile_step_with_plan``.
    """

    def __init__(self, mesh):
        self.mesh = mesh
        self.param_rules: list[tuple[str, tuple]] = []
        self.data_dims: dict[int, str] = {}
        # moment (optimizer-state) layout override: ("axis", dim) — the
        # zeroN strategies shard moments along dim 0 of every param whose
        # dim 0 the axis divides (DygraphShardingOptimizer stage-1 layout)
        self.moment_axis: str | None = None
        # parameter fallback sharding axis (zero3): applied after the rule
        # table for params no rule matched
        self.param_fallback_axis: str | None = None
        # mesh axis the attention heads are split over (set by ``tp``)
        self.head_axis: str | None = None
        self.sep_impl: str | None = None       # "ring" | "ulysses"
        self.sep_axis: str = "sep"
        self.pp_stages: int | None = None
        self.strategies: list[tuple[str, dict]] = []

    # -- construction ---------------------------------------------------
    @classmethod
    def build(cls, axes, strategies=(), devices=None):
        """Mesh from ``axes`` (dict / pair list / an existing Mesh), then
        apply ``strategies``: each entry a registered name or ``(name,
        kwargs)``."""
        from .strategies import apply as _apply

        mesh = axes if isinstance(axes, jax.sharding.Mesh) \
            else make_mesh(axes, devices=devices)
        plan = cls(mesh)
        for entry in strategies:
            if isinstance(entry, str):
                name, kwargs = entry, {}
            else:
                name, kwargs = entry
            _apply(plan, name, **(kwargs or {}))
        return plan

    def add_param_rule(self, pattern, spec):
        """Append ``(fnmatch pattern, per-dim spec)``; earlier rules win."""
        dims = _as_dims(spec)
        axes = mesh_axes(self.mesh)
        for d in dims:
            for ax in (d if isinstance(d, (tuple, list)) else (d,)):
                if ax is not None and ax not in axes:
                    raise PlanError(
                        f"rule {pattern!r}: axis {ax!r} not on mesh "
                        f"{tuple(axes)}")
        self.param_rules.append((str(pattern), dims))
        return self

    def shard_data_dim(self, dim, axis):
        if axis not in mesh_axes(self.mesh):
            raise PlanError(f"data dim {dim}: axis {axis!r} not on mesh")
        self.data_dims[int(dim)] = axis
        return self

    def _record(self, name, **kwargs):
        self.strategies.append((name, dict(kwargs)))

    # -- resolution -----------------------------------------------------
    def _axis_size(self, entry):
        axes = mesh_axes(self.mesh)
        if isinstance(entry, (tuple, list)):
            n = 1
            for ax in entry:
                n *= axes[ax]
            return n
        return axes[entry]

    def spec_for(self, name, shape):
        """PartitionSpec for a parameter: first matching rule, with
        non-divisible (or degree-1) dims degraded to replication, then the
        zero3 fallback axis on dim 0."""
        dims = None
        for pattern, spec in self.param_rules:
            if fnmatch.fnmatchcase(name, pattern):
                dims = spec
                break
        out = [None] * len(shape)
        if dims is not None:
            for i, ax in enumerate(dims[:len(shape)]):
                if ax is None:
                    continue
                size = self._axis_size(ax)
                if size > 1 and shape[i] % size == 0:
                    out[i] = tuple(ax) if isinstance(ax, list) else ax
        if (dims is None and self.param_fallback_axis is not None
                and len(shape)):
            size = self._axis_size(self.param_fallback_axis)
            if size > 1 and shape[0] % size == 0:
                out[0] = self.param_fallback_axis
        return P(*out)

    def sharding_for(self, name, shape):
        return NamedSharding(self.mesh, self.spec_for(name, shape))

    def rule_dims(self, name):
        """Raw matched rule dims for ``name`` (``None`` when no rule
        matches) — the shape-free per-dim tuple the pp stage-scan's
        ``block_param_spec`` callback consumes (it applies its own
        divisibility handling on the stacked block shapes)."""
        for pattern, spec in self.param_rules:
            if fnmatch.fnmatchcase(name, pattern):
                return tuple(spec) or None
        return None

    def moment_spec_for(self, name, shape):
        """Optimizer-moment layout: the zeroN axis on dim 0 when it
        divides, else the param's own spec (moments follow their param)."""
        if self.moment_axis is not None and len(shape):
            size = self._axis_size(self.moment_axis)
            if size > 1 and shape[0] % size == 0:
                return P(self.moment_axis, *([None] * (len(shape) - 1)))
        return self.spec_for(name, shape)

    def moment_sharding_for(self, name, shape):
        return NamedSharding(self.mesh, self.moment_spec_for(name, shape))

    def data_spec(self, ndim, shape=None):
        """PartitionSpec for a data input of rank ``ndim`` from the
        activation rules (dims beyond the map replicate). With ``shape``,
        dims the axis does not divide degrade to replication — the same
        rule the param table uses, so odd-sized label/aux inputs ride
        along instead of erroring."""
        out = [None] * ndim
        for dim, axis in self.data_dims.items():
            if not (0 <= dim < ndim and self._axis_size(axis) > 1):
                continue
            if shape is not None and shape[dim] % self._axis_size(axis):
                continue
            out[dim] = axis
        return P(*out)

    def data_sharding(self, ndim, shape=None):
        return NamedSharding(self.mesh, self.data_spec(ndim, shape))

    def place_data(self, arr):
        """Commit a host/device array to its activation sharding (rank-0
        scalars pass through)."""
        if not getattr(arr, "ndim", 0):
            return arr
        return jax.device_put(arr, self.data_sharding(arr.ndim, arr.shape))

    # -- per-shard kernels ----------------------------------------------
    @contextlib.contextmanager
    def tracing(self):
        """Mark this plan active while one of its steps is traced
        (``compile_step_with_plan`` wraps every planned step in it)."""
        token = _ACTIVE.set(self)
        try:
            yield self
        finally:
            _ACTIVE.reset(token)

    def batch_axis_for(self, n):
        """The data-parallel axis if it splits a batch of ``n``, else
        ``None`` (the kernel then sees the whole batch on every shard)."""
        ax = self.data_dims.get(0)
        if ax is None or self._axis_size(ax) == 1 or n % self._axis_size(ax):
            return None
        return ax

    def head_axis_for(self, *head_counts):
        """The head axis if it splits every one of ``head_counts``."""
        ax = self.head_axis
        if ax is None or self._axis_size(ax) == 1 or any(
                h % self._axis_size(ax) for h in head_counts):
            return None
        return ax

    def per_shard(self, fn, in_specs, out_specs):
        """``fn`` as a manual region over the whole mesh: each device runs
        it on its block of the operands. Operands that arrive laid out
        otherwise are re-laid-out by GSPMD at the region's edge, so the
        specs decide cost, never correctness."""
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def place_params(self, named_arrays, moments=False):
        """device_put a ``{name: array}`` tree onto the plan's layout."""
        pick = self.moment_sharding_for if moments else self.sharding_for
        return {n: jax.device_put(a, pick(n, a.shape))
                for n, a in named_arrays.items()}

    def apply_to_model(self, model):
        """Adopt the plan on a live Layer: commit every parameter Tensor's
        array to its plan sharding IN PLACE (autograd identity preserved),
        and wire the sequence-parallel mesh onto attention layers that
        carry the ``_ring_mesh`` socket when a ``sep`` strategy is armed.
        Returns the model."""
        for name, p in model.named_parameters():
            spec = self.spec_for(name, p.shape)
            if any(s is not None for s in spec):
                p._data = jax.device_put(
                    p._data, NamedSharding(self.mesh, spec))
        if self.sep_impl is not None:
            for _, sub in model.named_sublayers(include_self=True):
                if hasattr(sub, "_ring_mesh"):
                    sub._ring_mesh = self.mesh
        return model

    # -- identity -------------------------------------------------------
    def describe(self):
        """Stable human-readable description (also the fingerprint
        preimage)."""
        axes = mesh_axes(self.mesh)
        lines = ["mesh: " + ",".join(f"{a}={n}" for a, n in axes.items())]
        for pattern, spec in self.param_rules:
            lines.append(f"param {pattern} -> {spec!r}")
        if self.data_dims:
            lines.append("data " + ",".join(
                f"{d}:{a}" for d, a in sorted(self.data_dims.items())))
        if self.moment_axis:
            lines.append(f"moments dim0 -> {self.moment_axis}")
        if self.param_fallback_axis:
            lines.append(f"param fallback dim0 -> "
                         f"{self.param_fallback_axis}")
        if self.sep_impl:
            lines.append(f"sep: {self.sep_impl} over {self.sep_axis}")
        if self.pp_stages:
            lines.append(f"pp: {self.pp_stages} stages")
        for name, kwargs in self.strategies:
            lines.append(f"strategy {name} "
                         + ",".join(f"{k}={v}" for k, v in
                                    sorted(kwargs.items())))
        return "\n".join(lines)

    def fingerprint(self):
        """``{"mesh": {...}, "digest": sha1}`` — what the checkpoint layer
        records; the digest covers mesh shape AND the full rule/strategy
        table."""
        digest = hashlib.sha1(self.describe().encode()).hexdigest()
        return {"mesh": mesh_axes(self.mesh), "digest": digest}

    def __repr__(self):
        axes = mesh_axes(self.mesh)
        strat = ",".join(n for n, _ in self.strategies) or "none"
        return (f"Plan(mesh={{{', '.join(f'{a}:{n}' for a, n in axes.items())}}}, "
                f"strategies=[{strat}], rules={len(self.param_rules)})")

    def scoped(self, prefix):
        """A view of this plan for a model whose parameter names carry an
        extra ``prefix``: name-keyed rule lookups strip the prefix before
        matching, so a rule table anchored at the network root
        (``"llama.layers.*"``) keeps matching when an adopter wraps the
        network in an outer module (hapi's planned path wraps network +
        loss in one ``_NetLoss``, prefixing every name with ``"net."``).
        Mesh, rules, strategies and fingerprint are the wrapped plan's own
        (shared, not copied)."""
        return _ScopedPlanView(self, str(prefix))


class _ScopedPlanView(Plan):
    """See :meth:`Plan.scoped`. Shares ALL state with the wrapped plan —
    attribute reads fall through via ``__getattr__`` — and overrides only
    the two name-pattern matchers; every inherited method
    (``moment_spec_for``, ``sharding_for``, ``apply_to_model``, ...)
    resolves names through those overrides."""

    def __init__(self, base, prefix):   # deliberately no Plan.__init__
        self._base_plan = base
        self._name_prefix = prefix

    def __getattr__(self, attr):
        return getattr(object.__getattribute__(self, "_base_plan"), attr)

    def _strip(self, name):
        p = self._name_prefix
        return name[len(p):] if name.startswith(p) else name

    def spec_for(self, name, shape):
        return self._base_plan.spec_for(self._strip(name), shape)

    def rule_dims(self, name):
        return self._base_plan.rule_dims(self._strip(name))

    def scoped(self, prefix):
        return _ScopedPlanView(self._base_plan,
                               str(prefix) + self._name_prefix)

    def __repr__(self):
        return (f"{Plan.__repr__(self)}.scoped({self._name_prefix!r})")
