"""Unified sharding Plan subsystem (ISSUE 8 / ROADMAP item 3).

Covers the plan layer itself (mesh declaration, name-pattern rules,
strategy table, one compile entry point), its three adopters
(``FusedTrainStep(plan=)``, hapi ``Model.prepare(plan=)``,
``LLMEngine(plan=)``), the checkpoint plan-fingerprint gate, the
plan-coverage lint, the MULTICHIP loss tripwire — and the Ulysses SP
parity regression that motivated the subsystem.

**The r05 Ulysses root cause, pinned here**: ``MULTICHIP_r05``'s
"ULYSSES SP ... loss=1834.9071" line was never a llama loss. The old
hand-wired dryrun arm computed ``(out*out).sum()`` of a random q=k=v
tensor — 1834.9071 is the CORRECT value of that diagnostic (the dense
reference produces the same number bit-for-bit) — printed beside real
CE losses near 6.26, so it read as a silent divergence for two rounds.
The attention kernel itself is bit-exact; the harness compared
incomparable quantities. ``TestUlyssesParityRegression`` pins both
facts, and the plan-table dryrun + tripwire make the failure mode
structurally impossible (every strategy row prints ``loss= baseline=``
for the same config/seed/data and drift fails tier-1).
"""

import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed.plan import (
    AXES, Plan, PlanError, STRATEGIES, compile_step_with_plan, make_mesh,
    mesh_axes)
from paddle_tpu.incubate.fused_train_step import FusedTrainStep

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# mesh
# ---------------------------------------------------------------------------

class TestMesh:
    def test_dict_axes_reorder_to_canonical(self):
        mesh = make_mesh({"tp": 2, "dp": 2})
        assert mesh.axis_names == ("dp", "tp")  # AXES order, not dict order
        assert mesh_axes(mesh) == {"dp": 2, "tp": 2}

    def test_pair_sequence_keeps_caller_order(self):
        mesh = make_mesh([("tp", 2), ("dp", 2)])
        assert mesh.axis_names == ("tp", "dp")

    def test_degree_one_axes_are_kept(self):
        mesh = make_mesh({"dp": 2, "tp": 1})
        assert mesh_axes(mesh) == {"dp": 2, "tp": 1}

    def test_too_many_devices_names_the_env_trick(self):
        with pytest.raises(ValueError,
                           match="xla_force_host_platform_device_count"):
            make_mesh({"dp": 64})

    def test_duplicate_and_invalid_degrees(self):
        with pytest.raises(ValueError, match="duplicate"):
            make_mesh([("dp", 2), ("dp", 2)])
        with pytest.raises(ValueError, match=">= 1"):
            make_mesh({"dp": 0})

    def test_canonical_axis_vocabulary(self):
        assert AXES == ("pp", "dp", "fsdp", "tp", "sep", "ep")


# ---------------------------------------------------------------------------
# plan rules / resolution
# ---------------------------------------------------------------------------

class TestPlanRules:
    def _plan(self):
        return Plan(make_mesh({"dp": 2, "tp": 2}))

    def test_first_matching_rule_wins(self):
        plan = self._plan()
        plan.add_param_rule("*q_proj*", {1: "tp"})
        plan.add_param_rule("*proj*", {0: "tp"})
        assert plan.spec_for("x.q_proj.weight", (8, 8)) == P(None, "tp")
        assert plan.spec_for("x.o_proj.weight", (8, 8)) == P("tp", None)

    def test_non_divisible_dim_degrades_to_replicated(self):
        plan = self._plan()
        plan.add_param_rule("*w*", {0: "tp", 1: "tp"})
        assert plan.spec_for("w", (3, 8)) == P(None, "tp")
        assert plan.spec_for("w", (3, 5)) == P(None, None)

    def test_zero3_fallback_applies_only_without_a_rule(self):
        plan = self._plan()
        plan.param_fallback_axis = "dp"
        plan.add_param_rule("*head*", {1: "tp"})
        assert plan.spec_for("body.weight", (8, 4)) == P("dp", None)
        assert plan.spec_for("head.weight", (8, 4)) == P(None, "tp")
        assert plan.spec_for("body.odd", (3,)) == P(None)  # non-divisible

    def test_unknown_axis_is_a_plan_error(self):
        plan = self._plan()
        with pytest.raises(PlanError, match="not on mesh"):
            plan.add_param_rule("*", {0: "sep"})
        with pytest.raises(PlanError, match="not on mesh"):
            plan.shard_data_dim(0, "nope")

    def test_data_spec_shape_aware_degrade(self):
        plan = self._plan()
        plan.shard_data_dim(0, "dp")
        plan.shard_data_dim(1, "tp")
        assert plan.data_spec(2) == P("dp", "tp")
        assert plan.data_spec(2, (4, 6)) == P("dp", "tp")
        assert plan.data_spec(2, (3, 6)) == P(None, "tp")  # odd batch
        assert plan.data_spec(1, (4,)) == P("dp")  # dims beyond rank drop

    def test_moment_spec_zero1_layout_with_param_fallthrough(self):
        plan = self._plan()
        plan.moment_axis = "dp"
        plan.add_param_rule("*w*", {1: "tp"})
        assert plan.moment_spec_for("w", (8, 4)) == P("dp", None)
        # dim 0 the axis cannot divide: moments follow the param's spec
        assert plan.moment_spec_for("w", (3, 4)) == P(None, "tp")

    def test_scoped_view_strips_prefix_and_shares_identity(self):
        # root-anchored rules (no leading "*") must keep matching when an
        # adopter wraps the network in an outer module that prefixes
        # parameter names (hapi's _NetLoss adds "net.")
        plan = self._plan()
        plan.add_param_rule("fc1.weight", {1: "tp"})
        plan.moment_axis = "dp"
        view = plan.scoped("net.")
        assert view.spec_for("net.fc1.weight", (4, 4)) == \
            plan.spec_for("fc1.weight", (4, 4)) == P(None, "tp")
        # unprefixed names pass through unchanged
        assert view.spec_for("fc1.weight", (4, 4)) == P(None, "tp")
        assert view.rule_dims("net.fc1.weight") == \
            plan.rule_dims("fc1.weight")
        # inherited resolvers route through the strip too
        assert view.moment_spec_for("net.fc1.weight", (4, 4)) == \
            P("dp", None)
        # the view IS the plan identity-wise: same mesh, same fingerprint
        assert view.mesh is plan.mesh
        assert view.fingerprint() == plan.fingerprint()
        assert isinstance(view, Plan)

    def test_fingerprint_covers_mesh_and_rules(self):
        p1 = Plan.build({"dp": 2, "tp": 2}, ["dp", "tp"])
        p2 = Plan.build({"tp": 2, "dp": 2}, ["dp", "tp"])  # dict order
        assert p1.fingerprint() == p2.fingerprint()
        p3 = Plan.build({"dp": 2, "tp": 2}, ["dp"])
        assert p1.fingerprint()["digest"] != p3.fingerprint()["digest"]
        p4 = Plan.build({"dp": 4}, ["dp"])
        assert p1.fingerprint()["mesh"] != p4.fingerprint()["mesh"]


# ---------------------------------------------------------------------------
# the strategy table
# ---------------------------------------------------------------------------

class TestStrategyTable:
    def test_unknown_strategy_lists_registry(self):
        with pytest.raises(PlanError, match="registered"):
            Plan.build({"dp": 2}, ["warp"])

    def test_sep_impl_validated(self):
        with pytest.raises(PlanError, match="ring.*ulysses"):
            Plan.build({"sep": 4}, [("sep", {"impl": "megatron"})])

    def test_dp_shards_batch_dim(self):
        plan = Plan.build({"dp": 2}, ["dp"])
        assert plan.data_spec(2, (4, 6)) == P("dp", None)

    def test_zero1_zero2_shard_moments_not_params(self):
        p1 = Plan.build({"dp": 2}, ["dp", ("zero1", {"axis": "dp"})])
        p2 = Plan.build({"dp": 2}, ["dp", ("zero2", {"axis": "dp"})])
        for plan in (p1, p2):
            assert plan.moment_spec_for("w", (8, 4)) == P("dp", None)
            assert plan.spec_for("w", (8, 4)) == P(None, None)

    def test_zero3_shards_params_too(self):
        plan = Plan.build({"dp": 2}, ["dp", ("zero3", {"axis": "dp"})])
        assert plan.spec_for("w", (8, 4)) == P("dp", None)
        assert plan.moment_spec_for("w", (8, 4)) == P("dp", None)

    def test_tp_llama_rules_column_row_vocab(self):
        plan = Plan.build({"tp": 2}, ["tp"])
        get = lambda n, shape=(8, 8): plan.spec_for(n, shape)  # noqa: E731
        assert get("llama.embed_tokens.weight") == P("tp", None)
        assert get("x.q_proj.weight") == P(None, "tp")
        assert get("x.o_proj.weight") == P("tp", None)
        assert get("lm_head.weight") == P(None, "tp")

    def test_sep_ring_and_ulysses_entries(self):
        ring = Plan.build({"sep": 4}, [("sep", {"impl": "ring"})])
        uly = Plan.build({"sep": 4}, [("sep", {"impl": "ulysses"})])
        assert (ring.sep_impl, uly.sep_impl) == ("ring", "ulysses")
        assert ring.data_spec(2, (2, 32)) == P(None, "sep")

    def test_ep_expert_stack_rules(self):
        plan = Plan.build({"ep": 2}, ["ep"])
        assert plan.spec_for("moe.gate_w", (4, 8, 16)) == P(
            "ep", None, None)

    def test_pp_records_stages(self):
        plan = Plan.build({"pp": 2}, [("pp", {"stages": 2})])
        assert plan.pp_stages == 2
        with pytest.raises(PlanError):
            Plan.build({"pp": 2}, [("pp", {"stages": 0})])

    def test_zeroN_axis_validated_at_declaration(self):
        # a bad zeroN axis must fail TYPED at Plan.build, not as a raw
        # KeyError deep in the first adopter's moment placement
        for strat in ("zero1", "zero2", "zero3"):
            with pytest.raises(PlanError, match="not on mesh"):
                Plan.build({"tp": 2}, [(strat, {"axis": "dp"})])

    def test_strategy_entries_recorded_for_fingerprint(self):
        plan = Plan.build({"dp": 2}, ["dp", ("zero1", {"axis": "dp"})])
        assert ("dp", {}) in plan.strategies
        assert ("zero1", {"axis": "dp"}) in plan.strategies


# ---------------------------------------------------------------------------
# plan-coverage lint (tier-1 wiring of scripts/check_plan_coverage.py)
# ---------------------------------------------------------------------------

class TestPlanCoverageLint:
    def test_every_registered_strategy_is_exercised(self):
        mod = _script("check_plan_coverage")
        names = mod.registered_strategies()
        assert set(names) == set(STRATEGIES)  # source parse == registry
        used = mod.exercised_strategies()
        missing = [s for s in names if s not in used]
        assert missing == [], (
            f"registered strategies with no exercising test: {missing}")

    def test_lint_catches_an_untested_strategy(self, tmp_path):
        mod = _script("check_plan_coverage")
        # a corpus that builds plans but never names the strategy
        f = tmp_path / "test_x.py"
        f.write_text("Plan.build({'dp': 2}, ['dp'])\n")
        used = mod.exercised_strategies(paths=[str(f)])
        assert "dp" in used and "zero1" not in used

    def test_axes_dict_mention_is_not_an_exercise(self, tmp_path):
        mod = _script("check_plan_coverage")
        # sizing a 'sep' mesh axis builds no sep strategy — only the
        # strategies argument counts, else deleting the last real
        # ('sep', ...) entry would leave the lint green
        f = tmp_path / "test_x.py"
        f.write_text("Plan.build({'dp': 2, 'sep': 4}, ['dp'])\n")
        used = mod.exercised_strategies(paths=[str(f)])
        assert "dp" in used
        assert "sep" not in used
        # keyword form still counts
        g = tmp_path / "test_y.py"
        g.write_text("Plan.build({'sep': 4}, strategies=[('sep', "
                     "{'impl': 'ring'})])\n")
        assert "sep" in mod.exercised_strategies(paths=[str(g)])
        # strategy-kwarg VALUES don't count either: ('zero1',
        # {'axis': 'dp'}) exercises zero1, not dp
        h = tmp_path / "test_z.py"
        h.write_text("Plan.build({'x': 2}, [('zero1', {'axis': 'dp'})])\n")
        used = mod.exercised_strategies(paths=[str(h)])
        assert "zero1" in used
        assert "dp" not in used


# ---------------------------------------------------------------------------
# compile_step_with_plan
# ---------------------------------------------------------------------------

class TestCompileStep:
    def test_plan_none_is_plain_jit(self):
        fn = compile_step_with_plan(lambda x: x * 2.0, None)
        out = fn(jax.numpy.ones((4,)))
        np.testing.assert_allclose(np.asarray(out), 2.0)
        assert hasattr(fn, "lower")  # jit object, not a wrapper

    def test_out_specs_pin_declared_layout(self):
        plan = Plan.build({"dp": 2}, ["dp"])
        fn = compile_step_with_plan(
            lambda x: x + 1.0, plan,
            in_specs=(P("dp", None),), out_specs=P("dp", None))
        x = jax.device_put(np.zeros((4, 3), np.float32),
                           NamedSharding(plan.mesh, P("dp", None)))
        out = fn(x)
        assert out.sharding.spec == P("dp", None)
        np.testing.assert_allclose(np.asarray(out), 1.0)

    def test_named_compile_registers_cache_stats_row(self):
        from paddle_tpu.jit.cache import cache_stats

        fn = compile_step_with_plan(lambda x: x - 1.0, None,
                                    name="test_plan_counting#1")
        fn(jax.numpy.ones((2,)))
        row = cache_stats()["test_plan_counting#1"]
        assert row["compiles"] == 1


# ---------------------------------------------------------------------------
# FusedTrainStep(plan=) — parity and declared layouts
# ---------------------------------------------------------------------------

class _MLP(nn.Layer):
    def __init__(self, din=8, h=8):
        super().__init__()
        self.fc1 = nn.Linear(din, h)
        self.fc2 = nn.Linear(h, 1)

    def forward(self, x, y):
        pred = self.fc2(paddle.tanh(self.fc1(x)))[:, 0]
        d = pred - y
        return (d * d).mean()


def _mlp_losses(plan, steps=3):
    paddle.seed(7)
    model = _MLP()
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=model.parameters())
    fstep = FusedTrainStep(model, opt, plan=plan)
    rng = np.random.RandomState(3)
    x = paddle.to_tensor(rng.randn(4, 8).astype("float32"))
    y = paddle.to_tensor(rng.randn(4).astype("float32"))
    return [float(fstep(x, y)) for _ in range(steps)], fstep


MLP_TP_RULES = (("*fc1*", {1: "tp"}), ("*fc2*", {0: "tp"}))


class TestFusedStepPlan:
    def test_zero1_parity_and_layouts(self):
        base, _ = _mlp_losses(None)
        plan = Plan.build({"dp": 2, "tp": 2},
                          ["dp", ("tp", {"rules": MLP_TP_RULES}),
                           ("zero1", {"axis": "dp"})])
        got, fstep = _mlp_losses(plan)
        np.testing.assert_allclose(got, base, atol=1e-6)
        # declared layouts survive the donated round-trips: zero1 keeps
        # params on their tp layout while moments shard dim 0 over dp
        w1 = fstep._params["fc1.weight"]
        assert w1.sharding.spec == P(None, "tp")
        m1 = fstep._m1["fc1.weight"]
        assert m1.sharding.spec == P("dp", None)

    def test_zero3_shards_params_dim0(self):
        base, _ = _mlp_losses(None)
        plan = Plan.build({"dp": 2}, ["dp", ("zero3", {"axis": "dp"})])
        got, fstep = _mlp_losses(plan)
        np.testing.assert_allclose(got, base, atol=1e-6)
        assert fstep._params["fc1.weight"].sharding.spec == P("dp", None)

    def test_plan_property_and_ep_strategy_row(self):
        # ep as a table row on a non-MoE net: rules simply match nothing
        plan = Plan.build({"dp": 2, "ep": 2}, ["dp", "ep"])
        got, fstep = _mlp_losses(plan)
        assert fstep.plan is plan
        base, _ = _mlp_losses(None)
        np.testing.assert_allclose(got, base, atol=1e-6)


# ---------------------------------------------------------------------------
# Ulysses SP parity — the r05 regression, pinned
# ---------------------------------------------------------------------------

class TestUlyssesParityRegression:
    def test_kernel_bitexact_and_r05_diagnostic_explained(self):
        """The r05 harness quantity ``(out*out).sum()`` of the seed-7
        random q=k=v tensor IS ~1834.9 — for the DENSE reference too:
        the number was correct, the comparison was not. And the Ulysses
        output is bit-exact against dense attention."""
        import math

        import paddle_tpu.nn.functional as F
        from paddle_tpu.nn.functional.flash_attention import _sdpa_ref

        mesh = make_mesh({"dp": 2, "sep": 4})
        qn = np.random.RandomState(7).randn(2, 64, 4, 8).astype(np.float32)
        uq = paddle.to_tensor(qn)
        uout = F.sep_all_to_all_attention(uq, uq, uq, mesh=mesh,
                                          axis="sep", causal=True)
        dout = np.asarray(_sdpa_ref.raw_fn(
            qn, qn, qn, causal=True, scale=1.0 / math.sqrt(8)))
        assert np.abs(uout.numpy() - dout).max() == 0.0  # bit-exact
        diag_u = float((uout * uout).sum().numpy())
        diag_d = float((dout * dout).sum())
        assert abs(diag_u - 1834.9071) < 0.05  # the r05 number...
        assert abs(diag_u - diag_d) < 1e-3     # ...matched by dense

    def test_llama_ring_vs_ulysses_vs_dense_losses(self):
        """One hybrid dp x sep plan drives llama through BOTH attention
        layouts: CE losses bit-equal ring-vs-ulysses, and within 1e-3 of
        the single-device dense baseline — the acceptance criterion that
        replaces the r05 incomparable-diagnostic line."""
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny

        rng = np.random.RandomState(0)
        ids = rng.randint(0, 512, (2, 32)).astype(np.int32)
        labels = rng.randint(0, 512, (2, 32)).astype(np.int32)

        def losses(cfg_kw, plan):
            paddle.seed(0)
            model = LlamaForCausalLM(llama_tiny(**cfg_kw))
            model.train()
            opt = paddle.optimizer.AdamW(
                learning_rate=1e-3, parameters=model.parameters())
            fstep = FusedTrainStep(model, opt, plan=plan)
            t = (paddle.to_tensor(ids), paddle.to_tensor(labels))
            return [float(fstep(*t)) for _ in range(2)]

        base = losses({}, None)
        ring = losses({"use_ring_attention": True},
                      Plan.build({"dp": 2, "sep": 4},
                                 ["dp", ("sep", {"impl": "ring"})]))
        uly = losses({"use_sep_attention": True},
                     Plan.build({"dp": 2, "sep": 4},
                                ["dp", ("sep", {"impl": "ulysses"})]))
        assert ring == uly, f"ring {ring} != ulysses {uly}"
        np.testing.assert_allclose(ring, base, atol=1e-3)
        assert all(l < 10.0 for l in uly)  # nothing 1834.9-shaped


# ---------------------------------------------------------------------------
# checkpoint plan fingerprint
# ---------------------------------------------------------------------------

class TestCheckpointPlanFingerprint:
    def _trained(self, plan, tmp_path):
        paddle.seed(7)
        model = _MLP()
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=model.parameters())
        fstep = FusedTrainStep(model, opt, plan=plan)
        rng = np.random.RandomState(3)
        x = paddle.to_tensor(rng.randn(4, 8).astype("float32"))
        y = paddle.to_tensor(rng.randn(4).astype("float32"))
        for _ in range(2):
            fstep(x, y)
        mgr = paddle.CheckpointManager(str(tmp_path / "ckpt"),
                                       keep_last_n=2)
        mgr.save(2, model=model, optimizer=fstep, plan=plan)
        return model, fstep, mgr

    def test_fingerprint_recorded_and_compatible_restore(self, tmp_path):
        plan = Plan.build({"dp": 2}, ["dp", ("zero1", {"axis": "dp"})])
        model, fstep, mgr = self._trained(plan, tmp_path)
        fp = mgr.plan_fingerprint(2)
        assert fp is not None and fp == plan.fingerprint()
        want = {n: np.asarray(t._data)
                for n, t in model.named_parameters()}

        paddle.seed(1)  # different init — restore must overwrite it
        model2 = _MLP()
        opt2 = paddle.optimizer.AdamW(learning_rate=1e-2,
                                      parameters=model2.parameters())
        plan2 = Plan.build({"dp": 2}, ["dp", ("zero1", {"axis": "dp"})])
        fstep2 = FusedTrainStep(model2, opt2, plan=plan2)
        step = mgr.auto_resume(model=model2, optimizer=fstep2, plan=plan2)
        assert step == 2
        for n, t in model2.named_parameters():
            np.testing.assert_array_equal(np.asarray(t._data), want[n])

    def test_mesh_mismatch_raises_typed_before_touching_state(
            self, tmp_path):
        plan = Plan.build({"dp": 2}, ["dp", ("zero1", {"axis": "dp"})])
        _model, _fstep, mgr = self._trained(plan, tmp_path)

        paddle.seed(1)
        model2 = _MLP()
        before = {n: np.asarray(t._data)
                  for n, t in model2.named_parameters()}
        plan_bad = Plan.build({"dp": 4}, ["dp", ("zero1", {"axis": "dp"})])
        with pytest.raises(paddle.PlanMismatchError, match="mesh"):
            mgr.auto_resume(model=model2, plan=plan_bad)
        for n, t in model2.named_parameters():  # untouched on failure
            np.testing.assert_array_equal(np.asarray(t._data), before[n])

    def test_rule_table_mismatch_raises_on_same_mesh(self, tmp_path):
        plan = Plan.build({"dp": 2}, ["dp", ("zero1", {"axis": "dp"})])
        _model, _fstep, mgr = self._trained(plan, tmp_path)
        plan_bad = Plan.build({"dp": 2}, ["dp", ("zero3", {"axis": "dp"})])
        with pytest.raises(paddle.PlanMismatchError, match="digest"):
            mgr.auto_resume(model=_MLP(), plan=plan_bad)

    def test_plan_none_overrides_the_gate(self, tmp_path):
        plan = Plan.build({"dp": 2}, ["dp", ("zero1", {"axis": "dp"})])
        _model, _fstep, mgr = self._trained(plan, tmp_path)
        model2 = _MLP()
        assert mgr.auto_resume(model=model2, plan=None) == 2

    def test_planless_checkpoint_restores_under_a_plan(self, tmp_path):
        _model, _fstep, mgr = self._trained(None, tmp_path)
        assert mgr.plan_fingerprint(2) is None
        plan = Plan.build({"dp": 2}, ["dp"])
        assert mgr.auto_resume(model=_MLP(), plan=plan) == 2


# ---------------------------------------------------------------------------
# hapi Model.prepare(plan=)
# ---------------------------------------------------------------------------

class _XYDataset(paddle.io.Dataset):
    def __init__(self):
        rng = np.random.RandomState(11)
        self.x = rng.randn(16, 8).astype("float32")
        w = rng.randn(8, 1).astype("float32")
        self.y = (self.x @ w).astype("float32")

    def __len__(self):
        return 16

    def __getitem__(self, i):
        return self.x[i], self.y[i]


class TestHapiPlan:
    def _fit(self, plan, **prep_kw):
        paddle.seed(1)
        net = nn.Linear(8, 1)
        model = paddle.Model(net)
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=net.parameters())
        model.prepare(opt, nn.MSELoss(), plan=plan, **prep_kw)
        model.fit(_XYDataset(), batch_size=8, epochs=1, verbose=0,
                  shuffle=False, prefetch=False)
        return model, np.asarray(net.weight._data)

    def test_planned_fit_routes_through_fused_step_and_matches(self):
        _m0, w_base = self._fit(None)
        plan = Plan.build({"dp": 2}, ["dp", ("zero1", {"axis": "dp"})])
        m, w_plan = self._fit(plan)
        # the planned loop really took the one compile layer (under a
        # scoped view of the SAME plan — it strips _NetLoss's "net."
        # name prefix before rule matching)
        assert m._planned_step is not None
        assert m._planned_step.plan._base_plan is plan
        assert m._planned_step.plan.fingerprint() == plan.fingerprint()
        np.testing.assert_allclose(w_plan, w_base, atol=1e-5)

    def test_amp_prepared_falls_back_eager_with_warning(self):
        plan = Plan.build({"dp": 2}, ["dp"])
        with pytest.warns(RuntimeWarning, match="eager"):
            m, _w = self._fit(plan, amp_configs="O1")
        assert m._planned_step is None

    def test_root_anchored_rule_matches_through_net_prefix(self):
        # a rule WITHOUT a leading "*" (anchored at the network root):
        # the fused planned step sees "net.weight" but must resolve the
        # "weight" rule, or the declared tp layout silently degrades to
        # replicated in its in/out sharding pins
        plan = Plan.build({"tp": 2},
                          [("tp", {"rules": (("weight", {0: "tp"}),)})])
        m, _w = self._fit(plan)
        step = m._planned_step
        assert step is not None
        assert step.plan.spec_for("net.weight", (8, 1)) == P("tp", None)
        # the committed layout survived the planned fit (out-sharding
        # pins did not force it back to replicated)
        arr = m.network.weight._data
        assert "tp" in str(arr.sharding)

    def test_load_into_eager_fallback_does_not_silently_drop_opt_state(
            self, tmp_path):
        # planned save → reload into an AMP-prepared (eager-fallback)
        # session: the planned-format moments cannot be adopted by the
        # eager optimizer — warn loudly instead of silently training
        # with zeroed moments/step count
        m0, _w = self._fit(Plan.build({"dp": 2}, ["dp"]))
        path = str(tmp_path / "ck")
        m0.save(path)

        net = nn.Linear(8, 1)
        model = paddle.Model(net)
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=net.parameters())
        model.prepare(opt, nn.MSELoss(),
                      plan=Plan.build({"dp": 2}, ["dp"]),
                      amp_configs="O1")
        model.load(path)
        assert model._pending_opt_state is not None
        x = paddle.to_tensor(np.ones((4, 8), "float32"))
        y = paddle.to_tensor(np.ones((4, 1), "float32"))
        with pytest.warns(RuntimeWarning, match="CANNOT be applied"):
            model.train_batch([x], [y])
        assert model._pending_opt_state is None  # drained, not leaked

    def test_plain_opt_state_into_planned_step_warns(self, tmp_path):
        # planless save → planned session: the fused step cannot adopt
        # "<tensor>_moment1" keys — warn instead of restoring nothing
        m0, _w = self._fit(None)
        path = str(tmp_path / "ck")
        m0.save(path)

        net = nn.Linear(8, 1)
        model = paddle.Model(net)
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=net.parameters())
        model.prepare(opt, nn.MSELoss(),
                      plan=Plan.build({"dp": 2}, ["dp"]))
        model.load(path)
        x = paddle.to_tensor(np.ones((4, 8), "float32"))
        y = paddle.to_tensor(np.ones((4, 1), "float32"))
        with pytest.warns(RuntimeWarning, match="plain-optimizer"):
            model.train_batch([x], [y])

    def test_plain_opt_state_into_built_planned_step_warns(self, tmp_path):
        # same mismatch, but with the fused step ALREADY built: the
        # Model.load call itself must warn, not silently restore nothing
        m0, _w = self._fit(None)
        path = str(tmp_path / "ck")
        m0.save(path)
        m1, _w = self._fit(Plan.build({"dp": 2}, ["dp"]))
        assert m1._planned_step is not None
        with pytest.warns(RuntimeWarning, match="plain-optimizer"):
            m1.load(path)

    def test_fused_opt_state_into_planless_session_warns(self, tmp_path):
        # the fourth cross-format path: planned save → plan-less session
        m0, _w = self._fit(Plan.build({"dp": 2}, ["dp"]))
        path = str(tmp_path / "ck")
        m0.save(path)
        m1, _w = self._fit(None)
        with pytest.warns(RuntimeWarning, match="fused planned-step"):
            m1.load(path)

    def test_save_before_first_planned_batch_roundtrips_opt_state(
            self, tmp_path):
        # load-then-save with no planned batch in between: the restored
        # state sits in the pending stash — save must round-trip it, not
        # write the fresh optimizer's empty state
        m0, _w = self._fit(Plan.build({"dp": 2}, ["dp"]))
        p0 = str(tmp_path / "ck0")
        m0.save(p0)
        orig = m0._planned_step.state_dict()

        net = nn.Linear(8, 1)
        model = paddle.Model(net)
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=net.parameters())
        model.prepare(opt, nn.MSELoss(),
                      plan=Plan.build({"dp": 2}, ["dp"]))
        model.load(p0)
        p1 = str(tmp_path / "ck1")
        model.save(p1)  # planned step not built yet — stash is the state
        resaved = paddle.load(p1 + ".pdopt")
        assert resaved["step_count"] == orig["step_count"] > 0
        m1_keys = [k for k in orig if k.startswith("m1.")]
        assert m1_keys
        for k in m1_keys:
            np.testing.assert_array_equal(np.asarray(resaved[k]),
                                          np.asarray(orig[k]))

    def test_grad_accumulation_after_planned_steps_is_an_error(self):
        x = paddle.to_tensor(np.ones((4, 8), "float32"))
        y = paddle.to_tensor(np.ones((4, 1), "float32"))

        def _prepared():
            net = nn.Linear(8, 1)
            model = paddle.Model(net)
            opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                         parameters=net.parameters())
            model.prepare(opt, nn.MSELoss(),
                          plan=Plan.build({"dp": 2}, ["dp"]))
            return model

        # before any planned step ran: degrade to eager with the warning
        m_fresh = _prepared()
        with pytest.warns(RuntimeWarning, match="eager"):
            m_fresh.train_batch([x], [y], update=False)
        assert m_fresh._planned_step is None

        # after the fused step holds moments/step count: an error, not a
        # silent fallback that would discard that optimizer state
        m_run = _prepared()
        m_run.train_batch([x], [y])
        assert m_run._planned_step is not None
        with pytest.raises(RuntimeError, match="update=False"):
            m_run.train_batch([x], [y], update=False)


# ---------------------------------------------------------------------------
# LLMEngine(plan=)
# ---------------------------------------------------------------------------

class TestEnginePlan:
    def _tokens(self, plan, kv_dtype=None):
        from paddle_tpu.inference.serving import LLMEngine
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny

        paddle.seed(5)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        eng = LLMEngine(model, num_blocks=16, block_size=8,
                        max_batch_size=2, max_model_len=64,
                        ingest_async=False, plan=plan, kv_dtype=kv_dtype)
        try:
            toks = eng.generate([list(range(1, 9))])[0]
            return toks, eng.cache.k[0].sharding
        finally:
            eng.close()

    def test_tp_planned_decode_bitexact_vs_unplanned(self):
        base, _ = self._tokens(None)
        plan = Plan.build({"tp": 2}, ["tp"])
        got, _ = self._tokens(plan)
        assert list(got) == list(base)

    def test_paged_kernels_run_per_shard_under_a_plan(self, monkeypatch):
        """On a TPU a Mosaic call inside a GSPMD step must sit in a manual
        region (ISSUE 21). Interpret mode puts the Pallas paged kernels on
        the planned path here (int8: scale pools ride along): pools stay
        head-sharded through donated round-trips and the tokens match the
        unplanned engine's."""
        monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
        base, _ = self._tokens(None, "int8")
        plan = Plan.build({"tp": 2}, ["tp"])
        got, pool = self._tokens(plan, "int8")
        assert list(got) == list(base)
        assert pool.spec == P(None, None, "tp", None)
        assert len(pool.device_set) == 2


class TestFlashPerShard:
    def test_flash_kernel_under_plan_matches_unsharded(self, monkeypatch):
        """The flash kernels run per shard (batch over dp, heads over tp)
        when a multi-device plan traces the step; forward and gradients
        equal the single-device call."""
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas.flash_attention import (
            _flash_attention_arrays)

        monkeypatch.setenv("PT_PALLAS_INTERPRET", "1")
        attn = _flash_attention_arrays.raw_fn
        rng = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(4, 128, 4, 64), jnp.float32)
                   for _ in range(3))

        def loss(q, k, v):
            return (attn(q, k, v, causal=True) ** 2).sum()

        plan = Plan.build({"dp": 2, "tp": 2}, ["dp", "tp"])
        want = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
        got = compile_step_with_plan(
            jax.value_and_grad(loss, argnums=(0, 1, 2)), plan)(
                *(plan.place_data(x) for x in (q, k, v)))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)

    def test_axes_that_do_not_divide_stay_whole(self):
        plan = Plan.build({"dp": 2, "tp": 2}, ["dp", "tp"])
        assert plan.batch_axis_for(4) == "dp"
        assert plan.batch_axis_for(3) is None
        assert plan.head_axis_for(4, 2) == "tp"
        assert plan.head_axis_for(4, 1) is None
        assert Plan.build({"dp": 4}, ["dp"]).head_axis_for(4) is None


# ---------------------------------------------------------------------------
# MULTICHIP loss tripwire (check_bench_regression)
# ---------------------------------------------------------------------------

class TestMultichipTripwire:
    def test_repo_artifacts_pass_and_latest_is_plan_format(self):
        cbr = _script("check_bench_regression")
        rounds = cbr.load_multichip_rounds(_REPO)
        assert rounds, "no MULTICHIP_r*.json artifacts in the repo"
        latest = max(rounds)
        assert latest >= 6  # the plan-format artifact exists
        anchored = [l for l in rounds[latest]["lines"]
                    if l["baseline"] is not None]
        assert len(anchored) >= 4  # dp/zero/ring/ulysses at minimum
        assert cbr.check_multichip(rounds) == []

    def test_would_have_caught_the_r05_ulysses_line(self):
        cbr = _script("check_bench_regression")
        rounds = {5: {"ok": True, "lines": [
            {"name": "RING ATTENTION sep=4", "loss": 6.2564,
             "baseline": 6.25},
            {"name": "ULYSSES SP sep=4", "loss": 1834.9071,
             "baseline": 6.25},
        ]}}
        fails = cbr.check_multichip(rounds)
        assert any("ULYSSES" in f and "drifts" in f for f in fails)
        assert not any("RING" in f for f in fails)

    def test_unanchored_latest_round_is_an_unarmed_tripwire(self):
        cbr = _script("check_bench_regression")
        rounds = {5: {"ok": True, "lines": [
            {"name": "ULYSSES SP sep=4", "loss": 1834.9071,
             "baseline": None}]}}
        fails = cbr.check_multichip(rounds)
        assert any("unarmed" in f for f in fails)

    def test_vanished_strategy_row_fails(self):
        cbr = _script("check_bench_regression")
        rounds = {
            6: {"ok": True, "lines": [
                {"name": "ULYSSES SP", "loss": 6.2, "baseline": 6.2}]},
            7: {"ok": True, "lines": [
                {"name": "RING", "loss": 6.2, "baseline": 6.2}]},
        }
        fails = cbr.check_multichip(rounds)
        assert any("ULYSSES SP" in f and "missing" in f for f in fails)

    def test_real_artifact_parses_the_plan_lines(self):
        cbr = _script("check_bench_regression")
        rounds = cbr.load_multichip_rounds(_REPO)
        latest = max(rounds)
        names = {l["name"] for l in rounds[latest]["lines"]}
        assert any("ULYSSES" in n for n in names)
        assert any("RING" in n for n in names)

    def test_crashed_latest_round_cannot_hide_behind_prior_good_round(
            self, tmp_path):
        import json

        cbr = _script("check_bench_regression")
        (tmp_path / "MULTICHIP_r06.json").write_text(json.dumps(
            {"ok": True,
             "tail": "dryrun_multichip: PLAN X loss=1.0 baseline=1.0"}))
        # r07's dryrun died before printing a single anchored line
        (tmp_path / "MULTICHIP_r07.json").write_text(json.dumps(
            {"ok": False, "tail": "Traceback (most recent call last):"}))
        rounds = cbr.load_multichip_rounds(str(tmp_path))
        assert 7 in rounds  # the lineless round is NOT silently dropped
        fails = cbr.check_multichip(rounds)
        assert any("r7" in f and "not ok" in f for f in fails)
        assert any("unarmed" in f for f in fails)

    def test_corrupt_latest_artifact_fails(self, tmp_path):
        import json

        cbr = _script("check_bench_regression")
        (tmp_path / "MULTICHIP_r06.json").write_text(json.dumps(
            {"ok": True,
             "tail": "dryrun_multichip: PLAN X loss=1.0 baseline=1.0"}))
        (tmp_path / "MULTICHIP_r07.json").write_text("{not json")
        rounds = cbr.load_multichip_rounds(str(tmp_path))
        fails = cbr.check_multichip(rounds)
        assert any("r7" in f and "not ok" in f for f in fails)

    def test_nan_loss_is_a_drift_failure(self):
        cbr = _script("check_bench_regression")
        rounds = {6: {"ok": True, "lines": [
            {"name": "PLAN X", "loss": float("nan"), "baseline": 6.0}]}}
        fails = cbr.check_multichip(rounds)
        assert any("PLAN X" in f and "drifts" in f for f in fails)
        rounds = {6: {"ok": True, "lines": [
            {"name": "PLAN X", "loss": 6.0, "baseline": float("nan")}]}}
        assert cbr.check_multichip(rounds)

    def test_inf_loss_parses_and_fails(self, tmp_path):
        import json

        cbr = _script("check_bench_regression")
        (tmp_path / "MULTICHIP_r06.json").write_text(json.dumps(
            {"ok": True, "tail":
             "dryrun_multichip: PLAN X loss=inf baseline=5.0\n"
             "dryrun_multichip: PLAN Y loss=5.0 baseline=5.0"}))
        rounds = cbr.load_multichip_rounds(str(tmp_path))
        assert rounds[6]["lines"][0]["loss"] == float("inf")
        fails = cbr.check_multichip(rounds)
        assert any("PLAN X" in f and "drifts" in f for f in fails)
        assert not any("PLAN Y" in f for f in fails)

    def test_row_that_loses_its_baseline_fails(self):
        # the r05 failure shape: the row still PRINTS (so a plain vanish
        # check passes) but stopped being compared to a baseline
        cbr = _script("check_bench_regression")
        rounds = {
            6: {"ok": True, "lines": [
                {"name": "ULYSSES SP", "loss": 6.2, "baseline": 6.2},
                {"name": "OTHER", "loss": 6.0, "baseline": 6.0}]},
            7: {"ok": True, "lines": [
                {"name": "ULYSSES SP", "loss": 1834.9, "baseline": None},
                {"name": "OTHER", "loss": 6.0, "baseline": 6.0}]},
        }
        fails = cbr.check_multichip(rounds)
        assert any("ULYSSES SP" in f and "without baseline" in f
                   for f in fails)
        assert not any("OTHER" in f for f in fails)


# ---------------------------------------------------------------------------
# the dryrun is a plan table
# ---------------------------------------------------------------------------

class TestDryrunIsPlanTable:
    def test_dryrun_source_constructs_plans_with_baselines(self):
        import inspect

        sys.path.insert(0, _REPO)
        import __graft_entry__ as ge

        src = inspect.getsource(ge._dryrun_multichip_impl)
        assert "Plan.build" in src
        assert "baseline=" in src          # the tripwire format
        assert "ULYSSES" in src and "RING" in src
        # the old bespoke wiring is gone: no hand-rolled spec function
        assert "def spec_for" not in src
