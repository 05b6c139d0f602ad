"""hapi Model — the Keras-style high-level loop.

Reference: python/paddle/hapi/model.py (``Model`` :1054, ``fit`` :1756,
``prepare`` :1676). The reference maintains parallel dygraph/static adapter
classes; here there is one path — eager steps over the jit-cached dispatch
layer — so train_batch is already a compiled XLA program after the first
step. Data flows host numpy -> device per batch (the TPU input pipeline).
"""

from __future__ import annotations

import os
import warnings

import numpy as np

from .. import amp as _amp
from ..core.tensor import Tensor
from ..framework.io import load as _load, save as _save
from ..io import DataLoader, Dataset
from ..metric import Metric
from .callbacks import config_callbacks

__all__ = ["Model", "DeferredScalar"]


class DeferredScalar:
    """Lazy device scalar returned by ``train_batch``/``eval_batch``: holds
    the device value and materializes (ONE blocking host round-trip,
    measured in PERF.md) only when converted via ``float()`` /
    ``numpy()`` / formatting. Until then it rides through logs dicts and
    callback plumbing without forcing a per-step device→host sync; the
    logging boundary (``log_freq``) is where conversion actually happens."""

    __slots__ = ("_data",)

    def __init__(self, value):
        self._data = value._data if isinstance(value, Tensor) else value

    def numpy(self):
        return np.asarray(self._data)

    def __array__(self, dtype=None):
        a = np.asarray(self._data)
        return a.astype(dtype) if dtype is not None else a

    def __float__(self):
        return float(np.asarray(self._data))

    def item(self):
        return float(self)

    def __format__(self, spec):
        return format(float(self), spec)

    def __repr__(self):
        return repr(float(self))

    # arithmetic/comparison compatibility with the plain float these APIs
    # used to return — each materializes (the caller chose the boundary)
    def __add__(self, o):
        return float(self) + o

    def __radd__(self, o):
        return o + float(self)

    def __sub__(self, o):
        return float(self) - o

    def __rsub__(self, o):
        return o - float(self)

    def __mul__(self, o):
        return float(self) * o

    def __rmul__(self, o):
        return o * float(self)

    def __truediv__(self, o):
        return float(self) / o

    def __rtruediv__(self, o):
        return o / float(self)

    def __neg__(self):
        return -float(self)

    def __abs__(self):
        return abs(float(self))

    def __lt__(self, o):
        return float(self) < o

    def __le__(self, o):
        return float(self) <= o

    def __gt__(self, o):
        return float(self) > o

    def __ge__(self, o):
        return float(self) >= o

    def __eq__(self, o):
        return float(self) == o

    def __ne__(self, o):
        return float(self) != o

    __hash__ = None  # mutable-ish device handle; hash like a list, not a float


def _to_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _tensorize(batch):
    out = []
    for b in _to_list(batch):
        out.append(b if isinstance(b, Tensor) else Tensor(np.asarray(b)))
    return out


class Model:
    """paddle.Model(network) -> prepare/fit/evaluate/predict/save/load."""

    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._inputs = inputs
        self._labels = labels
        self._optimizer = None
        self._loss = None
        self._metrics = []
        self._scaler = None
        self._plan = None
        self._planned_step = None
        self._planned_disabled = False
        self._planned_fallback_warned = False
        self.stop_training = False

    # -- setup -----------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None, plan=None):
        """ref model.py:1676.

        ``plan``: a :class:`paddle_tpu.distributed.plan.Plan`. The
        network's parameters are committed to the plan's layouts and
        ``fit``/``train_batch`` route each update through a
        ``FusedTrainStep(plan=...)`` — i.e. the hapi loop compiles through
        the same ``compile_step_with_plan`` layer as fused training and
        serving (ROADMAP item 3). The planned fused path needs a prepared
        ``loss``; prepared Metrics or an AMP level fall back to the eager
        step (with the plan's parameter placement still applied) because
        metric update needs the forward outputs on the host."""
        self._optimizer = optimizer
        self._loss = loss
        self._plan = plan
        self._planned_step = None
        self._planned_disabled = False
        self._planned_fallback_warned = False
        if plan is not None:
            plan.apply_to_model(self.network)
        self._metrics = _to_list(metrics)
        for m in self._metrics:
            assert isinstance(m, Metric), (
                f"metrics must be paddle.metric.Metric, got {type(m)}")
        if amp_configs:
            level = (amp_configs.get("level", "O1")
                     if isinstance(amp_configs, dict) else str(amp_configs))
            self._amp_level = level
            if level in ("O1", "O2"):
                self._scaler = _amp.GradScaler()
        else:
            self._amp_level = None
        return self

    # -- single-batch APIs ----------------------------------------------
    def train_batch(self, inputs, labels=None, update=True):
        """ref model.py train_batch — one fwd/bwd(/step); returns
        ([loss], [metric results]). The loss is a :class:`DeferredScalar`
        — a lazy device value that materializes on ``float()`` — so a
        tight loop over train_batch does not pay a device→host round-trip
        per step (fetch happens at the logging boundary)."""
        assert self._optimizer is not None, "call prepare() first"
        self.network.train()
        inputs = _tensorize(inputs)
        labels = _tensorize(labels)

        if self._plan is not None:
            if not update:
                # gradient accumulation mixes eager grad state with the
                # fused step's in-graph update — incoherent. Before the
                # fused step ever runs, the session degrades to the eager
                # path; once it HAS run, its Adam moments and step count
                # live inside the fused step and an eager fallback would
                # silently discard them (bias correction restarting from
                # zero) — that is an error, not a degrade
                if self._planned_step is not None:
                    raise RuntimeError(
                        "Model.prepare(plan=...): train_batch(update="
                        "False) after planned steps have run would "
                        "discard the optimizer moments/step count held "
                        "by the fused planned step. prepare() without "
                        "plan= for gradient accumulation, or keep "
                        "update=True under the plan")
                self._planned_disabled = True
            step = self._planned_train_step(len(labels))
            if step is not None:
                loss = step(*inputs, *labels)
                return [DeferredScalar(loss)], []

        if self._amp_level in ("O1", "O2"):
            with _amp.auto_cast(level=self._amp_level):
                outs = self.network(*inputs)
            loss = self._compute_loss(outs, labels)
            scaled = self._scaler.scale(loss)
            scaled.backward()
            if update:
                self._scaler.step(self._optimizer)
                self._scaler.update()
                self._optimizer.clear_grad()
        else:
            outs = self.network(*inputs)
            loss = self._compute_loss(outs, labels)
            loss.backward()
            if update:
                self._optimizer.step()
                self._optimizer.clear_grad()
        metrics = self._update_metrics(outs, labels)
        return [DeferredScalar(loss)], metrics

    def _planned_train_step(self, n_labels):
        """The ``FusedTrainStep(plan=...)`` the planned fit path
        dispatches through — built once, so the whole hapi loop compiles
        through ``compile_step_with_plan`` like fused training and the
        serving engine. Returns ``None`` (eager fallback, parameters
        still on the plan's layouts) when the prepared config cannot take
        the fused route: AMP, prepared Metrics (they need the forward
        outputs host-side), no prepared loss, or gradient accumulation."""
        if (self._planned_disabled or self._amp_level is not None
                or self._loss is None or self._metrics):
            pending = getattr(self, "_pending_opt_state", None)
            if pending is not None:
                # a Model.load stash destined for the fused step, but the
                # eager path owns optimizer state from here on — hand it
                # over (or say loudly why we can't) instead of silently
                # training with zeroed moments/step count
                self._pending_opt_state = None
                if self._fused_opt_format(pending):
                    warnings.warn(
                        "Model.load restored optimizer state in the "
                        "fused planned-step format, but this session "
                        "takes the eager fallback (AMP/metrics/gradient "
                        "accumulation) — the restored moments/step "
                        "count CANNOT be applied to the eager optimizer "
                        "and it starts fresh",
                        RuntimeWarning, stacklevel=3)
                else:
                    self._optimizer.set_state_dict(pending)
            if not self._planned_fallback_warned:
                self._planned_fallback_warned = True
                warnings.warn(
                    "Model.prepare(plan=...): the fused planned step "
                    "needs a prepared loss and no AMP/metrics/gradient "
                    "accumulation; falling back to the eager step "
                    "(parameters stay on the plan's layouts)",
                    RuntimeWarning, stacklevel=3)
            return None
        if self._planned_step is None:
            from ..incubate.fused_train_step import FusedTrainStep
            from ..nn.layer.layers import Layer

            net, loss_layer, k = self.network, self._loss, int(n_labels)

            class _NetLoss(Layer):
                """network + prepared loss as ONE forward so the fused
                step differentiates end to end (the label rides as the
                trailing ``k`` call arguments)."""

                def __init__(self):
                    super().__init__()
                    self.net = net
                    self.loss = loss_layer

                def forward(self, *args):
                    outs = self.net(*(args[:len(args) - k] if k else args))
                    labels = list(args[len(args) - k:]) if k else []
                    return loss_layer(*(_to_list(outs) + labels))

            # scoped("net."): _NetLoss prefixes every parameter name with
            # "net.", so rule tables anchored at the network root
            # ("llama.layers.*") would silently stop matching in the
            # fused step's in/out sharding pins — the scoped view strips
            # the prefix before rule matching (same mesh/fingerprint)
            self._planned_step = FusedTrainStep(
                _NetLoss(), self._optimizer, step_lr_scheduler=False,
                plan=self._plan.scoped("net."))
            self._planned_n_labels = k
            pending = getattr(self, "_pending_opt_state", None)
            if pending is not None:
                # optimizer state from Model.load that arrived before
                # this step existed (moments keyed "m1.net.<param>"
                # match because _NetLoss prefixes the SAME "net." path)
                if not self._fused_opt_format(pending):
                    # a plain-optimizer .pdopt (saved without a planned
                    # step): its "<tensor>_moment1" keys mean nothing to
                    # the fused step — say so instead of silently
                    # restoring nothing
                    warnings.warn(
                        "Model.load restored optimizer state in the "
                        "plain-optimizer format; the fused planned step "
                        "cannot adopt it and moments/step count start "
                        "fresh", RuntimeWarning, stacklevel=3)
                self._planned_step.set_state_dict(pending)
                self._pending_opt_state = None
        if self._planned_n_labels != n_labels:
            raise ValueError(
                f"planned train_batch was compiled for "
                f"{self._planned_n_labels} label(s), got {n_labels}")
        return self._planned_step

    def eval_batch(self, inputs, labels=None):
        self.network.eval()
        inputs = _tensorize(inputs)
        labels = _tensorize(labels)
        outs = self.network(*inputs)
        loss = self._compute_loss(outs, labels)
        metrics = self._update_metrics(outs, labels)
        return ([DeferredScalar(loss)] if loss is not None else [], metrics)

    def predict_batch(self, inputs):
        self.network.eval()
        outs = self.network(*_tensorize(inputs))
        return [o.numpy() for o in _to_list(outs)]

    def _compute_loss(self, outs, labels):
        if self._loss is None:
            out0 = _to_list(outs)[0]
            return out0 if out0.ndim == 0 or out0.size == 1 else None
        return self._loss(*(_to_list(outs) + labels))

    def _update_metrics(self, outs, labels):
        results = []
        pred = _to_list(outs)[0]
        for m in self._metrics:
            inp = m.compute(pred, *labels)
            if not isinstance(inp, (list, tuple)):
                inp = (inp,)
            m.update(*inp)
            results.append(m.accumulate())
        return results

    def _metric_logs(self, prefix=""):
        logs = {}
        for m in self._metrics:
            names = m.name()
            vals = m.accumulate()
            if isinstance(names, str):
                names, vals = [names], [vals]
            elif not isinstance(vals, (list, tuple)):
                vals = [vals]
            for n, v in zip(names, vals):
                logs[prefix + n] = v
        return logs

    def _reset_metrics(self):
        for m in self._metrics:
            m.reset()

    def _split_batch(self, batch):
        """Split a collated batch into (inputs, labels) by the prepared
        loss: the last element is the label. Raise clearly when a loss is
        prepared but the dataset yields no label slot."""
        if self._loss is None:
            return batch, []
        if len(batch) < 2:
            raise ValueError(
                "a loss was prepared, so each batch must be (inputs..., "
                f"label); the dataset yielded {len(batch)} element(s)")
        return batch[:-1], batch[-1:]

    def _as_loader(self, data, batch_size, shuffle, num_workers, drop_last):
        from ..io.streaming import StreamingDataset

        # a StreamingDataset already yields collated BATCHES (its own
        # batch_size, sharding and resume cursor) — wrapping it in a
        # DataLoader would re-batch batches; pass it through like a
        # loader so fit() streams it via the DevicePrefetcher unchanged
        if data is None or isinstance(data, (DataLoader, StreamingDataset)):
            return data
        return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                          num_workers=num_workers, drop_last=drop_last)

    # -- loops -----------------------------------------------------------
    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None, prefetch=True):
        """ref model.py:1756.

        Host–device overlap: train batches stream through a
        ``paddle.io.DevicePrefetcher`` (``prefetch=False`` disables) so
        host batch production + H2D transfer overlap the step's compute,
        and per-step losses stay lazy (:class:`DeferredScalar`) so the
        loop pays a device→host round-trip only at logging boundaries
        (``log_freq``; prepared Metrics still fetch per step — metric
        update is host-side accumulation by contract).

        Graceful preemption: a SIGTERM received while fitting stops at the
        next batch boundary, runs ``on_train_end`` callbacks (so a
        configured ModelCheckpoint saves), and raises
        ``SystemExit(123)`` — the elastic launcher's clean-preemption
        contract (relaunch without consuming restart budget)."""
        assert self._optimizer is not None, "call prepare() first"
        loader = self._as_loader(train_data, batch_size, shuffle,
                                 num_workers, drop_last)
        eval_loader = self._as_loader(eval_data, batch_size, False,
                                      num_workers, False)
        stream = loader
        if prefetch and loader is not None:
            from ..io.prefetch import DevicePrefetcher

            if not isinstance(loader, DevicePrefetcher):
                stream = DevicePrefetcher(
                    loader,
                    name=f"hapi.fit[{type(self.network).__name__}]"
                         ".prefetch")
        try:
            steps = len(loader)
        except TypeError:
            steps = None
        # divergence sentinel (FLAGS_sentinel_action != 'none'): fit
        # exposes the same window-level spike detector drive() runs, as a
        # callback — an explicitly passed DivergenceSentinel wins
        from ..core.flags import flag_value
        from .callbacks import DivergenceSentinel, ModelCheckpoint

        callbacks = list(callbacks or [])
        if (str(flag_value("sentinel_action", "none")) != "none"
                and not any(isinstance(c, DivergenceSentinel)
                            for c in callbacks)):
            # a managed ModelCheckpoint in the same run provides the
            # rollback target store — without it, action=rollback would
            # escalate to raise at the first spike
            manager = None
            for c in callbacks:
                if isinstance(c, ModelCheckpoint) and c.save_dir \
                        and c.keep_last_n is not None:
                    manager = c._get_manager()
                    break
            callbacks.append(DivergenceSentinel(window=log_freq,
                                                manager=manager))
        cbks = config_callbacks(
            callbacks, model=self, epochs=epochs, steps=steps,
            log_freq=log_freq, verbose=verbose, save_freq=save_freq,
            save_dir=save_dir, metrics=self._metrics)

        from ..distributed.launch import heartbeat as _hb

        self.stop_training = False
        cbks.on_train_begin()
        it = 0
        logs = {}
        # graceful preemption: a scheduler SIGTERM stops the loop at the
        # next batch boundary, runs the callbacks' end-of-training hooks
        # (ModelCheckpoint saves), and exits with the clean-preemption code
        # the elastic launcher relaunches budget-free
        with _hb.trap_preemption() as _preempt:
            try:
                from ..observability import trace as _obs_trace

                for epoch in range(epochs):
                    # epoch boundaries are host-side control flow — an
                    # allowed span site (ISSUE 10: spans only where the
                    # host already blocks); batches inside stay span-free
                    _epoch_span = _obs_trace.span(
                        "hapi.epoch", cat="train", args={"epoch": epoch})
                    try:
                        cbks.on_epoch_begin(epoch)
                        self._reset_metrics()
                        logs = {}
                        for step, batch in enumerate(stream):
                            cbks.on_train_batch_begin(step)
                            batch = _to_list(batch)
                            ins, labs = self._split_batch(batch)
                            update = (step + 1) % \
                                accumulate_grad_batches == 0
                            losses, _ = self.train_batch(ins, labs,
                                                         update=update)
                            logs = {"loss": losses[0],
                                    **self._metric_logs()}
                            cbks.set_params({**cbks.callbacks[0].params,
                                             "last_step": step})
                            cbks.on_train_batch_end(step, logs)
                            it += 1
                            # feed the launcher's hang watchdog (no-op
                            # when unsupervised: one env lookup)
                            _hb.write(step=it)
                            if _preempt.triggered:
                                self.stop_training = True
                                break
                            if num_iters is not None and it >= num_iters:
                                break
                        cbks.on_epoch_end(epoch, logs)
                    finally:
                        # the failing epoch must still land in the trace
                        _epoch_span.end()

                    if eval_loader is not None and not _preempt.triggered \
                            and (epoch + 1) % eval_freq == 0:
                        with _obs_trace.span("hapi.eval", cat="train",
                                             args={"epoch": epoch}):
                            self._run_eval(eval_loader, cbks)
                    if self.stop_training:
                        break
                    if num_iters is not None and it >= num_iters:
                        break
            finally:
                # a consumer abandoning iteration (error, num_iters cap,
                # preemption) must not leak the prefetcher's staging
                # thread — close() drains and joins it
                if stream is not loader and hasattr(stream, "close"):
                    stream.close()
            cbks.on_train_end(logs)
            if _preempt.triggered:
                raise SystemExit(_hb.PREEMPT_EXIT_CODE)
        return self

    def _run_eval(self, loader, cbks):
        self._reset_metrics()
        cbks.on_eval_begin()
        losses = []
        for step, batch in enumerate(loader):
            cbks.on_eval_batch_begin(step)
            batch = _to_list(batch)
            ins, labs = self._split_batch(batch)
            l, _ = self.eval_batch(ins, labs)
            losses.extend(l)
            cbks.on_eval_batch_end(step)
        # lazy eval losses materialize HERE, at the eval logging boundary —
        # stacked on device first so the whole eval pays ONE host
        # round-trip, not one per batch
        if losses:
            import jax.numpy as jnp

            stacked = np.asarray(jnp.stack(
                [jnp.asarray(l._data if isinstance(l, DeferredScalar)
                             else float(l), jnp.float32) for l in losses]))
            eval_loss = {"eval_loss": float(stacked.mean())}
        else:
            eval_loss = {}
        logs = {**eval_loss, **self._metric_logs("eval_")}
        # EarlyStopping monitors unprefixed names too
        logs.update({k[len("eval_"):]: v for k, v in logs.items()
                     if k.startswith("eval_")})
        cbks.on_eval_end(logs)
        return logs

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_samples=None):
        """ref model.py evaluate — returns dict of eval metrics."""
        loader = self._as_loader(eval_data, batch_size, False, num_workers,
                                 False)
        cbks = config_callbacks(callbacks, model=self, epochs=1,
                                steps=None, verbose=verbose,
                                metrics=self._metrics)
        return self._run_eval(loader, cbks)

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, verbose=1, callbacks=None):
        """ref model.py predict — list (per output) of per-batch arrays."""
        loader = self._as_loader(test_data, batch_size, False, num_workers,
                                 False)
        # datasets often yield (x, label) even for predict; feed only as many
        # leading elements as the network's forward takes (the reference
        # resolves this via its `inputs` specs)
        import inspect

        try:
            sig = inspect.signature(self.network.forward)
            npos = len([p for p in sig.parameters.values()
                        if p.kind in (p.POSITIONAL_ONLY,
                                      p.POSITIONAL_OR_KEYWORD)
                        and p.default is p.empty])
        except (TypeError, ValueError):
            npos = None
        outputs = None
        for batch in loader:
            batch = _to_list(batch)
            if npos:
                batch = batch[:npos]
            outs = self.predict_batch(batch)
            if outputs is None:
                outputs = [[] for _ in outs]
            for slot, o in zip(outputs, outs):
                slot.append(o)
        if outputs is None:
            return []
        if stack_outputs:
            return [np.concatenate(slot) for slot in outputs]
        return outputs

    # -- persistence / introspection -------------------------------------
    def save(self, path, training=True):
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        _save(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            # while a planned fit trains, the moments / bias-correction
            # step live in the FusedTrainStep (in-graph, donated), not in
            # the wrapped optimizer's accumulators — the step object is
            # the authoritative optimizer state (same contract as
            # CheckpointManager.save(optimizer=fused_step))
            pending = getattr(self, "_pending_opt_state", None)
            if self._planned_step is not None:
                sd = self._planned_step.state_dict()
            elif pending is not None:
                # loaded under a plan but no planned batch has run yet:
                # the restored state is still in the stash — round-trip
                # it instead of writing the fresh optimizer's empty state
                sd = pending
            else:
                sd = self._optimizer.state_dict()
            _save(sd, path + ".pdopt")

    @staticmethod
    def _fused_opt_format(sd):
        """Whether an optimizer state dict is in the FusedTrainStep
        format ("step_count" / "m1.<param>" keys) vs the plain-optimizer
        one ("<tensor>_moment1" / "global_step")."""
        return "step_count" in sd or any(
            k.startswith(("m1.", "m2.")) for k in sd)

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        self.network.set_state_dict(_load(path + ".pdparams"))
        opt_path = path + ".pdopt"
        if (not reset_optimizer and self._optimizer is not None
                and os.path.exists(opt_path)):
            sd = _load(opt_path)
            if self._planned_step is not None:
                if not self._fused_opt_format(sd):
                    # same mismatch the pre-build stash path warns on:
                    # the fused step silently matches none of the plain
                    # "<tensor>_moment1" keys
                    warnings.warn(
                        "Model.load: optimizer state is in the plain-"
                        "optimizer format; the fused planned step "
                        "cannot adopt it and moments/step count start "
                        "fresh", RuntimeWarning, stacklevel=2)
                self._planned_step.set_state_dict(sd)
            elif self._plan is not None:
                # planned checkpoint restored before the first planned
                # batch built the fused step: stash it —
                # _planned_train_step applies it on construction
                self._pending_opt_state = sd
            else:
                if self._fused_opt_format(sd):
                    # fourth cross-format path: a planned save's
                    # "m1.net.*"/"step_count" keys mean nothing to the
                    # plain optimizer — warn like the mirror cases
                    warnings.warn(
                        "Model.load: optimizer state is in the fused "
                        "planned-step format; the plain optimizer "
                        "cannot adopt it and moments/step count start "
                        "fresh", RuntimeWarning, stacklevel=2)
                self._optimizer.set_state_dict(sd)
        return self

    def parameters(self, *args, **kwargs):
        return self.network.parameters(*args, **kwargs)

    def summary(self, input_size=None, dtype=None):
        n_params = sum(int(np.prod(p.shape))
                       for p in self.network.parameters())
        trainable = sum(int(np.prod(p.shape))
                        for p in self.network.parameters()
                        if not p.stop_gradient)
        lines = [f"{type(self.network).__name__}:"]
        for name, sub in self.network.named_sublayers():
            cnt = sum(int(np.prod(p.shape))
                      for p in sub.parameters(include_sublayers=False))
            if cnt:
                lines.append(f"  {name} ({type(sub).__name__}): {cnt:,}")
        lines.append(f"Total params: {n_params:,}")
        lines.append(f"Trainable params: {trainable:,}")
        text = "\n".join(lines)
        print(text)
        return {"total_params": n_params, "trainable_params": trainable}
