"""Unified sharding plans: one mesh/spec API driving training, hapi and
serving (ROADMAP item 3).

    from paddle_tpu.distributed.plan import Plan

    plan = Plan.build({"dp": 2, "tp": 2}, ["dp", "tp", "zero1"])
    step = FusedTrainStep(model, opt, plan=plan)          # training
    Model(net).prepare(opt, loss, plan=plan).fit(ds)      # hapi
    LLMEngine(model, plan=plan)                           # serving

See DESIGN_DECISIONS.md "Sharding plans" for the why, and
README.md's multichip recipe for the CPU-virtual-device workflow.
"""

from .compile import compile_step_with_plan  # noqa: F401
from .mesh import AXES, make_mesh, mesh_axes  # noqa: F401
from .plan import Plan, PlanError, active_plan  # noqa: F401
from .strategies import STRATEGIES, apply, register_strategy  # noqa: F401

__all__ = [
    "AXES", "Plan", "PlanError", "STRATEGIES", "active_plan", "apply",
    "compile_step_with_plan", "make_mesh", "mesh_axes",
    "register_strategy",
]
