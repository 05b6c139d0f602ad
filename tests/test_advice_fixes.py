"""Regression tests for round-1 advisor findings (ADVICE.md).

Covers: bf16-safe distributed checkpoint storage, rank-namespaced shard
keys + per-rank metadata merge, GradScaler double-unscale, boolean-mask
indexing staying on the autograd tape, and the Pallas/XLA causal-mask
alignment gate.
"""

import os
import tempfile

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn


class TestCheckpointDtypes:
    def test_bf16_roundtrip(self):
        from paddle_tpu.distributed.checkpoint import (
            load_state_dict,
            save_state_dict,
        )

        t = paddle.to_tensor(
            np.random.randn(8, 4).astype("float32")
        ).astype("bfloat16")
        d = tempfile.mkdtemp()
        save_state_dict({"w": t}, d)
        # npz must not contain void-typed data
        raw = np.load(os.path.join(d, "rank0.npz"))
        for k in raw.files:
            assert raw[k].dtype.kind != "V", f"{k} stored as void"
        out = {"w": paddle.zeros([8, 4], dtype="bfloat16")}
        load_state_dict(out, d)
        np.testing.assert_array_equal(
            np.asarray(out["w"]._data, dtype="float32"),
            np.asarray(t._data, dtype="float32"),
        )

    def test_shard_keys_rank_namespaced_and_merged_metadata(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from paddle_tpu.distributed.checkpoint import (
            load_state_dict,
            save_state_dict,
        )

        mesh = jax.make_mesh((8,), ("x",))
        src = np.arange(64, dtype="float32").reshape(8, 8)
        arr = jax.device_put(src, NamedSharding(mesh, P("x")))
        t = paddle.zeros([8, 8])
        t._rebind(arr)
        d = tempfile.mkdtemp()
        save_state_dict({"s": t}, d)
        raw = np.load(os.path.join(d, "rank0.npz"))
        assert all("@r0s" in k for k in raw.files), raw.files
        assert os.path.exists(os.path.join(d, "rank0.meta.json"))

        # reshard-on-load onto a different mesh/layout
        mesh2 = jax.make_mesh((4, 2), ("a", "b"))
        tgt = jax.device_put(
            np.zeros((8, 8), "float32"), NamedSharding(mesh2, P("b", "a"))
        )
        out = paddle.zeros([8, 8])
        out._rebind(tgt)
        load_state_dict({"s": out}, d)
        np.testing.assert_array_equal(np.asarray(out._data), src)


class TestGradScalerUnscaleOnce:
    def test_unscale_then_step_divides_once(self):
        lin = nn.Linear(4, 4)
        opt = paddle.optimizer.SGD(
            learning_rate=0.0, parameters=lin.parameters()
        )
        scaler = paddle.amp.GradScaler(init_loss_scaling=1024.0)
        x = paddle.to_tensor(np.ones((2, 4), "float32"))
        loss = lin(x).sum()
        scaled = scaler.scale(loss)
        scaled.backward()
        # reference AMP pattern: unscale -> (clip) -> step -> update
        scaler.unscale_(opt)
        g_after_unscale = np.asarray(lin.weight.grad._data).copy()
        scaler.step(opt)
        scaler.update()
        g_after_step = np.asarray(lin.weight.grad._data)
        # grads must be the true (unscaled-once) gradient: d(sum(xW+b))/dW = 2
        np.testing.assert_allclose(g_after_unscale, 2.0, rtol=1e-5)
        np.testing.assert_allclose(g_after_step, 2.0, rtol=1e-5)

    def test_update_resets_unscaled_flag(self):
        lin = nn.Linear(2, 2)
        opt = paddle.optimizer.SGD(
            learning_rate=0.0, parameters=lin.parameters()
        )
        scaler = paddle.amp.GradScaler(init_loss_scaling=8.0)
        for _ in range(2):
            loss = lin(paddle.to_tensor(np.ones((1, 2), "float32"))).sum()
            scaler.scale(loss).backward()
            scaler.unscale_(opt)
            scaler.step(opt)
            scaler.update()
            np.testing.assert_allclose(
                np.asarray(lin.weight.grad._data), 1.0, rtol=1e-5
            )
            opt.clear_grad()


class TestBoolMaskAutograd:
    def test_getitem_bool_mask_keeps_grad(self):
        x = paddle.to_tensor(
            np.arange(6, dtype="float32"), stop_gradient=False
        )
        mask = paddle.to_tensor(
            np.array([True, False, True, False, True, False])
        )
        y = x[mask]
        assert not y.stop_gradient
        np.testing.assert_array_equal(y.numpy(), [0.0, 2.0, 4.0])
        y.sum().backward()
        np.testing.assert_array_equal(
            x.grad.numpy(), [1.0, 0.0, 1.0, 0.0, 1.0, 0.0]
        )

    def test_getitem_2d_bool_mask(self):
        x = paddle.to_tensor(
            np.arange(12, dtype="float32").reshape(3, 4), stop_gradient=False
        )
        m = np.zeros((3, 4), bool)
        m[0, 1] = m[2, 3] = True
        y = x[paddle.to_tensor(m)]
        np.testing.assert_array_equal(y.numpy(), [1.0, 11.0])
        y.sum().backward()
        expect = np.zeros((3, 4), "float32")
        expect[0, 1] = expect[2, 3] = 1.0
        np.testing.assert_array_equal(x.grad.numpy(), expect)

    def test_setitem_bool_mask(self):
        x = paddle.to_tensor(np.zeros(4, "float32"))
        x[paddle.to_tensor(np.array([True, False, True, False]))] = 5.0
        np.testing.assert_array_equal(x.numpy(), [5.0, 0.0, 5.0, 0.0])


class TestFlashAttnGate:
    def test_pallas_refused_for_kv_prefill(self):
        from paddle_tpu.nn.functional.flash_attention import _use_pallas

        q = np.zeros((1, 128, 8, 64), "float32")
        k = np.zeros((1, 256, 8, 64), "float32")
        # seq_k != seq_q → must take the XLA path regardless of backend
        assert _use_pallas(q, k) is False

    def test_sdpa_causal_bottom_right_aligned(self):
        # seq_k > seq_q: query i attends keys [0, i + (sk - sq)]
        from paddle_tpu.nn.functional import scaled_dot_product_attention

        q = paddle.to_tensor(np.random.randn(1, 2, 1, 8).astype("float32"))
        k = paddle.to_tensor(np.random.randn(1, 4, 1, 8).astype("float32"))
        v = paddle.to_tensor(np.random.randn(1, 4, 1, 8).astype("float32"))
        out = scaled_dot_product_attention(q, k, v, is_causal=True)
        # manual bottom-right-aligned reference
        qn = np.transpose(q.numpy(), (0, 2, 1, 3))
        kn = np.transpose(k.numpy(), (0, 2, 1, 3))
        vn = np.transpose(v.numpy(), (0, 2, 1, 3))
        logits = qn @ kn.transpose(0, 1, 3, 2) / np.sqrt(8.0)
        mask = np.tril(np.ones((2, 4), bool), k=2)
        logits = np.where(mask, logits, -1e30)
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        ref = np.transpose(p @ vn, (0, 2, 1, 3))
        np.testing.assert_allclose(out.numpy(), ref, rtol=2e-4, atol=2e-4)


class TestRound3AdviceFixes:
    def test_grad_scaler_single_fused_finite_check(self):
        """unscale_ must detect inf AND only sync the host once (fused
        all-finite accumulator), not once per parameter."""
        model = nn.Linear(4, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        scaler = paddle.amp.GradScaler(init_loss_scaling=2.0)
        x = paddle.to_tensor(np.ones((2, 4), "float32"))
        loss = model(x).sum()
        scaler.scale(loss).backward()
        # poison one grad with inf
        p = model.parameters()[0]
        bad = np.array(p.grad.numpy())
        bad[0, 0] = np.inf
        p.grad._rebind(paddle.to_tensor(bad)._data)
        before = model.parameters()[1].numpy().copy()
        scaler.step(opt)
        scaler.update()
        # step skipped on inf
        np.testing.assert_allclose(model.parameters()[1].numpy(), before)
        assert scaler.get_loss_scaling().numpy() < 2.0

    def test_profiler_transit_teardown_on_custom_scheduler(self):
        """A scheduler that drops RECORD -> READY without RECORD_AND_RETURN
        must still finish the window (recorder off, callback fired)."""
        from paddle_tpu import profiler as prof
        from paddle_tpu.observability.trace import TRACER
        from paddle_tpu.profiler.profiler import ProfilerState

        fired = []

        def sched(step):
            return (ProfilerState.RECORD if step < 2
                    else ProfilerState.READY)

        p = prof.Profiler(scheduler=sched,
                          on_trace_ready=lambda pr: fired.append(1))
        p.start()
        assert TRACER.enabled is True
        p.step()
        p.step()  # transition RECORD -> READY
        assert TRACER.enabled is False
        assert fired == [1]
        p.stop()

    def test_eager_send_recv_raise_multiprocess(self, monkeypatch):
        import jax
        import paddle_tpu.distributed as dist

        monkeypatch.setattr(jax, "process_count", lambda: 2)
        t = paddle.to_tensor([1.0])
        with pytest.raises(NotImplementedError):
            dist.send(t, dst=1)
        with pytest.raises(NotImplementedError):
            dist.recv(t, src=0)

    def test_fused_step_scheduler_opt_out(self):
        sched = paddle.optimizer.lr.StepDecay(learning_rate=0.1, step_size=1,
                                              gamma=0.5)
        model = nn.Linear(4, 4)
        opt = paddle.optimizer.SGD(learning_rate=sched,
                                   parameters=model.parameters())
        step = paddle.incubate.fused_train_step(
            model, opt, loss_fn=lambda o: o.sum(), step_lr_scheduler=False)
        x = paddle.to_tensor(np.ones((2, 4), "float32"))
        step(x)
        assert sched.get_lr() == pytest.approx(0.1)  # untouched
        sched.step()
        assert sched.get_lr() == pytest.approx(0.05)


class TestRound4AdviceFixes:
    def test_engine_predict_multi_input_unlabeled(self):
        """ADVICE r3: Engine.predict must not drop a real input of a
        multi-input unlabeled dataset (e.g. DeepFM's (ids, dense))."""
        from paddle_tpu.distributed.auto_parallel import Engine
        from paddle_tpu.io import Dataset

        class TwoIn(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(4, 1)

            def forward(self, a, b):
                return self.fc(a + b)

        class DS(Dataset):
            def __len__(self):
                return 4

            def __getitem__(self, i):
                return (np.ones(4, "float32") * i,
                        np.ones(4, "float32"))

        m = TwoIn()
        eng = Engine(model=m, loss=nn.MSELoss(),
                     optimizer=paddle.optimizer.SGD(
                         learning_rate=0.1, parameters=m.parameters()))
        outs = eng.predict(DS(), batch_size=2)
        assert len(outs) == 2 and outs[0].shape == (2, 1)

    def test_engine_predict_labeled_still_drops_label(self):
        from paddle_tpu.distributed.auto_parallel import Engine
        from paddle_tpu.io import Dataset

        class OneIn(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(4, 1)

            def forward(self, a):
                return self.fc(a)

        class DS(Dataset):
            def __len__(self):
                return 4

            def __getitem__(self, i):
                return (np.ones(4, "float32"), np.float32(1.0))

        m = OneIn()
        eng = Engine(model=m, loss=nn.MSELoss(),
                     optimizer=paddle.optimizer.SGD(
                         learning_rate=0.1, parameters=m.parameters()))
        outs = eng.predict(DS(), batch_size=2)
        assert len(outs) == 2 and outs[0].shape == (2, 1)

    def test_vjp_none_grad_slot_matches_primal_shape(self):
        """ADVICE r3: float0/None grad slots must carry primal-shaped zeros,
        not 0-d scalars."""
        from paddle_tpu.core.dispatch import _op_vjp_fn
        import jax.numpy as jnp

        # where(cond, a, b): cond is boolean -> float0 grad slot
        cond = jnp.array([True, False, True])
        a = jnp.ones(3, jnp.float32)
        b = jnp.zeros(3, jnp.float32)
        ct = jnp.ones(3, jnp.float32)
        grads = _op_vjp_fn(cond, a, b, ct, op_name="where", n_primals=3,
                           op_kwargs=(), out_tuple=False)
        assert grads[0].shape == cond.shape  # not a 0-d scalar
        assert grads[1].shape == a.shape


class TestAmpDebugging:
    def test_operator_stats_collection(self, capsys):
        """amp.debugging collects a per-op dtype histogram from dispatch
        (VERDICT r4 item 8; reference amp/debugging.py:459)."""
        with paddle.amp.debugging.collect_operator_stats():
            a = paddle.to_tensor(np.ones((4, 4), "float32"))
            b = a.astype("bfloat16")
            _ = b @ b
            _ = a + a
        out = capsys.readouterr().out
        assert "Op Name" in out and "BF16 Calls" in out
        stats = paddle.amp.debugging.operator_stats()
        assert any(v[1] > 0 for v in stats.values())  # a bf16 call counted
        assert any(v[2] > 0 for v in stats.values())  # an fp32 call counted
        # collection is off again
        from paddle_tpu.core import dispatch
        assert dispatch.OP_STATS is None

    def test_compare_accuracy(self, tmp_path):
        model = nn.Linear(8, 8)

        def fn(x):
            return model(x)

        x = paddle.to_tensor(np.random.randn(16, 8).astype("float32"))
        csvf = str(tmp_path / "cmp.csv")
        report = paddle.amp.debugging.compare_accuracy(
            fn, [x], amp_level="O1", dtype="bfloat16", output_filename=csvf)
        assert report[0]["max_rel_err"] < 0.2
        assert report[0]["max_abs_err"] > 0.0  # bf16 really differs
        import os
        assert os.path.exists(csvf)


class TestRound6AdviceFixes:
    def test_row_conv_per_feature_filter(self):
        """row_conv must use the reference [future_context+1, D] filter:
        each feature dim has its own context weights."""
        from paddle_tpu.static import nn as snn
        from paddle_tpu.static.nn import common as snn_common

        snn.reset_parameters()
        B, T, D, fc_size = 2, 6, 4, 2
        x = paddle.to_tensor(np.random.randn(B, T, D).astype("float32"))
        out = snn.row_conv(x, fc_size)
        assert out.shape == [B, T, D]
        params = snn_common.parameters()
        assert len(params) == 1
        w = params[0]
        assert list(w.shape) == [fc_size + 1, D]
        # oracle: out[b, t, d] = sum_i x[b, t+i, d] * w[i, d]
        xn, wn = x.numpy(), w.numpy()
        k = fc_size + 1
        pad = np.concatenate([xn, np.zeros((B, k - 1, D), np.float32)], 1)
        ref = sum(pad[:, i:i + T] * wn[i] for i in range(k))
        np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5, atol=1e-6)
        snn.reset_parameters()

    def test_to_device_preserves_flags(self):
        t = paddle.to_tensor(np.random.randn(3, 3).astype("float32"))
        t.stop_gradient = False
        t.persistable = True
        moved = t.cpu()
        assert moved.stop_gradient is False
        assert moved.persistable is True
        assert moved.name == t.name
        np.testing.assert_array_equal(moved.numpy(), t.numpy())

    def test_fused_mha_keeps_explicit_head_dim(self):
        """Non-transpose qkv layout: head_dim comes from qkv_weight.shape
        and may differ from embed_dim // num_heads."""
        import paddle_tpu.incubate.nn.functional as IF

        b, s, e = 2, 5, 8
        n_heads, head_dim = 2, 6  # != e // n_heads
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(b, s, e).astype("float32"))
        qkv_w = paddle.to_tensor(
            rng.randn(3, n_heads, head_dim, e).astype("float32") * 0.1)
        lin_w = paddle.to_tensor(
            rng.randn(n_heads * head_dim, e).astype("float32") * 0.1)
        out = IF.fused_multi_head_attention(
            x, qkv_w, lin_w, pre_layer_norm=True,
            dropout_rate=0.0, attn_dropout_rate=0.0, training=False)
        assert out.shape == [b, s, e]
        assert np.isfinite(out.numpy()).all()

    def test_builder_registry_distinguishes_attrs(self):
        """Same-shape unnamed builder calls with different initializers
        must NOT share parameters."""
        from paddle_tpu.static import nn as snn
        from paddle_tpu.static.nn import common as snn_common

        snn.reset_parameters()
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        zeros = paddle.ParamAttr(
            initializer=nn.initializer.Constant(0.0))
        ones = paddle.ParamAttr(
            initializer=nn.initializer.Constant(1.0))
        out0 = snn.fc(x, 3, weight_attr=zeros, bias_attr=False)
        out1 = snn.fc(x, 3, weight_attr=ones, bias_attr=False)
        assert len(snn_common.parameters()) == 2
        np.testing.assert_array_equal(out0.numpy(), 0.0)
        np.testing.assert_allclose(out1.numpy(), 4.0, rtol=1e-6)
        # repeat call with the SAME attr config still reuses its layer
        out0b = snn.fc(x, 3, weight_attr=zeros, bias_attr=False)
        assert len(snn_common.parameters()) == 2
        np.testing.assert_array_equal(out0b.numpy(), out0.numpy())
        snn.reset_parameters()
