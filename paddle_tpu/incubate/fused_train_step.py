"""Fused training step: forward + backward + optimizer update in ONE
donated XLA executable.

TPU-native extension (no single reference counterpart — the reference's
equivalent is the fused CUDA optimizer kernels + multi-stream executor,
e.g. paddle/fluid/operators/fused/ and DistributedFusedLamb in
python/paddle/incubate/optimizer/). The eager path runs three dispatches
per step (to_static forward, backward, optimizer); this collapses them
into one jit with parameter/moment buffer donation, so weights are
updated in place in HBM and per-step dispatch overhead is one call.

Host–device overlap: loss, the finite flag and the bias-correction step
count are device-resident (threaded through the executable as one donated
accumulator), so nothing forces a device→host round-trip per step. The
``drive(loader, steps, log_every=...)`` multi-step driver exploits that:
batches stream through a ``paddle.io.DevicePrefetcher`` (H2D overlapped
with compute), dispatches queue back-to-back, and metrics are fetched
every ``log_every`` steps (``FLAGS_metric_fetch_interval``) — amortizing
the blocking host sync (PERF.md has its measured cost), with a trajectory
bit-identical to per-step fetch (skip-step semantics are in-graph).

Supported optimizers: SGD, Momentum, Adam, AdamW (the bench/optimizer
hot set). Learning-rate schedulers are honored by passing the current lr
as a traced scalar. ClipGradByGlobalNorm is fused in-graph when set on
the optimizer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..observability import metrics as _obs_metrics
from ..observability import trace as _obs_trace
from ..utils import functional_call, params_dict

__all__ = ["FusedTrainStep", "fused_train_step"]

# how long the train.stall chaos site blocks: long enough that either the
# in-process stall guard (FLAGS_step_timeout_s) or the launcher's heartbeat
# watchdog (FLAGS_worker_hang_timeout_s) must be the thing that ends it
_STALL_SLEEP_S = 3600.0

# drive() observability (ISSUE 10): every series is labeled by this step
# instance's stats name, recorded ONLY at window boundaries from values
# the host already holds — zero added host syncs (the A/B in
# tests/test_observability.py asserts host_syncs and losses bit-identical
# with observability on vs off). The guard gauges are the registry mirror
# behind guard_stats()' backward-compatible dict.
_M_TRAIN_STEPS = _obs_metrics.counter(
    "train_steps_total", "fused train steps dispatched through drive()")
_M_TRAIN_SKIPPED = _obs_metrics.counter(
    "train_skipped_steps_total",
    "updates discarded in-graph for non-finite loss/grads")
_M_TRAIN_ROLLBACKS = _obs_metrics.counter(
    "train_rollbacks_total", "divergence-sentinel rollbacks performed")
_H_WINDOW_S = _obs_metrics.histogram(
    "train_window_seconds", "wall time of one metric-fetch window",
    buckets=_obs_metrics.DEFAULT_SECONDS_BUCKETS)
_G_ITEMS_PER_S = _obs_metrics.gauge(
    "train_items_per_sec",
    "tokens-or-examples/s over the last recorded window (tokens when the "
    "leading input is 2-D integer ids, else leading-dim examples)")
_G_GUARD = {
    "total": _obs_metrics.gauge(
        "train_guard_total", "steps dispatched through the anomaly guard"),
    "skipped": _obs_metrics.gauge(
        "train_guard_skipped", "guard-discarded steps (host mirror)"),
    "consecutive_skips": _obs_metrics.gauge(
        "train_guard_consecutive_skips", "current non-finite skip streak"),
    "warned": _obs_metrics.gauge(
        "train_guard_warned", "warn-mode non-finite events"),
}


def _f32(x):
    return x.astype(jnp.float32)


class FusedTrainStep:
    """``step_lr_scheduler=True`` (default) means the fused step OWNS
    scheduler stepping: it calls ``optimizer._learning_rate.step()`` once per
    invocation, and the caller must NOT also call ``lr_scheduler.step()`` in
    the training loop (that would advance the schedule twice per step). Pass
    ``step_lr_scheduler=False`` to keep the standard paddle pattern where the
    loop steps the scheduler itself.

    Checkpointing: while a FusedTrainStep trains, the moment buffers and
    bias-correction step live HERE (in-graph, donated), not in the wrapped
    optimizer's accumulators — so checkpoint the step object itself:
    ``CheckpointManager.save(step, model=model, optimizer=fused_step)`` and
    ``auto_resume(model, fused_step)`` (state_dict/set_state_dict are
    duck-type compatible, keyed by structured parameter names). Externally
    restored weights (any ``_rebind`` outside the step) are adopted on the
    next call."""

    _instance_count = 0

    def __init__(self, model, optimizer, loss_fn=None, step_lr_scheduler=True,
                 shape_buckets=None, bucket_args=None, grad_scaler=None,
                 plan=None):
        from ..jit.cache import BucketSpec

        from ..optimizer.optimizers import SGD, Adam, AdamW, Momentum

        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self._step_lr_scheduler = step_lr_scheduler
        # sharding plan (distributed.plan.Plan): parameters are committed
        # to their plan shardings IN PLACE before capture below, moments
        # take the plan's moment layout (zeroN dim-0 sharding), data
        # inputs are placed per the activation rules at dispatch, and the
        # step compiles through compile_step_with_plan — the ONE compile
        # layer shared with hapi fit and LLMEngine. plan=None keeps the
        # exact single-device program (same entry point, no fork).
        self._plan = plan
        if plan is not None:
            plan.apply_to_model(model)
        # step anomaly guard (FLAGS_check_nan_inf_action) + optional fused
        # dynamic loss scaling: with a grad_scaler the loss is scaled and the
        # grads unscaled in-graph (one executable, same as the reference's
        # check_finite_and_unscale fusion), the step OWNS scaler bookkeeping
        # (do not also call scaler.step/update in the loop), and a non-finite
        # step both skips the update and backs off the scale
        self._scaler = grad_scaler
        self._guard = {"total": 0, "skipped": 0, "consecutive_skips": 0,
                       "warned": 0}
        # pad-up shape buckets (paddle.jit semantics): data inputs are
        # zero-padded to the nearest registered boundary before dispatch so
        # a variable-length stream costs O(buckets) compiles, and the
        # compile/hit counters surface in paddle.jit.cache_stats().
        # bucket_args (positional indices / kw names) pins WHICH inputs pad;
        # default is the dominant-length rule — see paddle.jit.to_static.
        self._shape_buckets = BucketSpec.normalize(shape_buckets)
        self._bucket_args = (None if bucket_args is None
                             else frozenset(bucket_args))
        # per-instance stats row: each FusedTrainStep owns its own jax.jit
        # cache, so merging instances of one model class would both blur the
        # counters and false-trigger the recompile-cliff warning (9 steps
        # compiling once each is not a cliff)
        FusedTrainStep._instance_count += 1
        self._stats_name = (f"fused_train_step[{type(model).__name__}"
                            f"#{FusedTrainStep._instance_count}]")
        self._seen_sigs = set()
        self._names = sorted(params_dict(model))
        self._tensors = dict(model.named_parameters())
        # trainable params only (stop_gradient=True params stay frozen)
        self._names = [n for n in self._names
                       if n in self._tensors
                       and not self._tensors[n].stop_gradient]
        self._params = {n: self._tensors[n]._data for n in self._names}
        self._step_count = 0
        # device-resident step metrics, threaded through the executable as
        # one donated tuple: (bias-correction step count, running loss sum,
        # skipped-step count, window peak global grad norm). The step count
        # lives ON DEVICE — in protect mode it advances only on finite
        # steps IN-GRAPH — so a deferred metric fetch (drive/log_every) is
        # bit-identical to per-step fetch even across NaN-skipped windows.
        # The grad-norm peak feeds the divergence sentinel
        # (FLAGS_sentinel_grad_norm_ceiling) and is fetched/reset only at
        # window boundaries — zero per-step host syncs. self._step_count
        # stays as the host mirror for telemetry (synced at fetch
        # boundaries).
        self._acc = (jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0),
                     jnp.float32(0.0))
        # divergence-rollback LR cooldown: a scale on top of the
        # optimizer's own schedule, multiplied by FLAGS_sentinel_lr_cooldown
        # at each sentinel rollback and persisted in state_dict
        self._lr_scale = 1.0
        self._scaler_fallback_warned = False
        # FLAGS_sentinel_action-created TrainingSentinel, cached across
        # drive() calls so budget/history/EMA accumulate over epochs
        self._flag_sentinel = None

        opt = optimizer
        if isinstance(opt, AdamW):
            self._kind = "adamw"
        elif isinstance(opt, Adam):
            self._kind = "adam"
        elif isinstance(opt, Momentum):
            self._kind = "momentum"
        elif isinstance(opt, SGD):
            self._kind = "sgd"
        else:
            raise TypeError(
                f"fused_train_step supports SGD/Momentum/Adam/AdamW, got "
                f"{type(opt).__name__}")
        # row-sparse lazy route (Adam/AdamW lazy_mode=True): embedding-table
        # params skip the dense vocab-sized gradient entirely — the lookup
        # is captured (ops/sparse_grad.py), its backward yields
        # (row_ids, row_grads) at batchxfields size, and the update is a
        # gather→update→scatter over touched rows only. Zero model-code
        # change: any SparseEmbedding / sparse nn.Embedding parameter
        # qualifies automatically.
        self._sparse_names = ()
        if self._kind in ("adam", "adamw") and \
                bool(getattr(opt, "_lazy_mode", False)):
            self._sparse_names = tuple(sorted(
                self._find_sparse_param_names(model)))

        if self._kind in ("adam", "adamw"):
            z = {n: jnp.zeros(self._params[n].shape, jnp.float32)
                 for n in self._names}
            self._m1 = z
            self._m2 = {n: jnp.zeros_like(v) for n, v in z.items()}
        elif self._kind == "momentum":
            self._m1 = {n: jnp.zeros(self._params[n].shape, jnp.float32)
                        for n in self._names}
            self._m2 = {}
        else:
            self._m1, self._m2 = {}, {}
        if plan is not None:
            # zeroN moment layout (dim-0 over the sharding axis when it
            # divides, else the param's own spec) — committed up front so
            # the first dispatch compiles for it
            self._m1 = {n: jax.device_put(
                v, plan.moment_sharding_for(n, v.shape))
                for n, v in self._m1.items()}
            self._m2 = {n: jax.device_put(
                v, plan.moment_sharding_for(n, v.shape))
                for n, v in self._m2.items()}

        if self._kind in ("adam", "adamw"):
            # per-param decoupled decay honoring apply_decay_param_fun
            base_wd = float(opt._wd_coeff())
            fun = getattr(opt, "_apply_decay_param_fun", None)
            self._wds = {
                n: (base_wd if fun is None or fun(self._tensors[n].name)
                    else 0.0)
                for n in self._names
            }
            ratio_fun = getattr(opt, "_lr_ratio", None)
            self._lr_ratios = {
                n: (float(ratio_fun(self._tensors[n]))
                    if ratio_fun is not None else 1.0)
                for n in self._names
            }
        else:
            # coupled-L2 coefficients (SGD/Momentum regularizer path)
            self._wds = {n: float(opt._weight_decay_value(self._tensors[n]))
                         for n in self._names}
            self._lr_ratios = {n: 1.0 for n in self._names}

        clip = getattr(opt, "_grad_clip", None)
        from ..nn.clip import ClipGradByGlobalNorm

        if clip is None:
            self._clip_norm = None
        elif isinstance(clip, ClipGradByGlobalNorm):
            self._clip_norm = float(clip.clip_norm)
        else:
            raise TypeError(
                f"fused_train_step fuses ClipGradByGlobalNorm only; the "
                f"optimizer has {type(clip).__name__} — use the eager step "
                "for other clip types")
        # guard mode is a static arg ("off": no finite check in the graph
        # at all, "flag": compute the all-finite flag only, "protect": flag
        # + skip-step select): flipping FLAGS_check_nan_inf_action between
        # modes mid-run costs one recompile, steady state costs none and
        # the guard-off path stays exactly the pre-guard program. The same
        # holds for track_gnorm (the sentinel's grad-norm ceiling): off
        # compiles out both the norm reduction (unless grad clipping
        # already pays it) and the peak update
        from ..distributed.plan import compile_step_with_plan

        # the one compile layer (ROADMAP item 3): plan=None lowers to the
        # identical plain jax.jit; a real plan lets GSPMD partition the
        # step from the committed param/moment/data placements (shard_map
        # regions for the sep attention collectives ride inside the trace).
        # out_shardings pin the updated params/moments to their DECLARED
        # layouts: without them GSPMD propagates the dp-sharded moment
        # layout into the new params, and after one donation round-trip a
        # zero1 plan silently creeps into a zero3 one.
        in_specs = out_specs = None
        if self._plan is not None:
            p_specs = {n: self._plan.spec_for(n, self._params[n].shape)
                       for n in self._params}
            m1_specs = {n: self._plan.moment_spec_for(n, self._m1[n].shape)
                        for n in self._m1}
            m2_specs = {n: self._plan.moment_spec_for(n, self._m2[n].shape)
                        for n in self._m2}
            # params/moments pinned on BOTH sides: inputs so GSPMD cannot
            # re-layout an uncommitted buffer away from its declared spec,
            # outputs so the donated round-trip hands back the same layout
            # (otherwise propagation leaks the dp moment sharding into the
            # new params and a zero1 plan creeps into zero3 — and the
            # donation aliaser rejects the input/output layout mismatch).
            # acc/lr/scale/data/kwdata stay None: committed data placement
            # (activation rules) already says everything the plan knows.
            in_specs = (p_specs, m1_specs, m2_specs,
                        None, None, None, None, None)
            out_specs = (None, None, None, p_specs, m1_specs, m2_specs)
        self._jitted = compile_step_with_plan(
            self._step_impl, self._plan, in_specs=in_specs,
            out_specs=out_specs,
            donate_argnums=(0, 1, 2, 3), static_argnums=(8, 9))

    def _find_sparse_param_names(self, model):
        """Trainable params that are embedding tables: the weights of
        ``distributed.ps.SparseEmbedding`` layers and of ``nn.Embedding``
        layers constructed with ``sparse=True`` (the reference's
        SelectedRows-gradient markers)."""
        from ..distributed.ps import SparseEmbedding
        from ..nn.layer.common import Embedding

        by_id = {id(self._tensors[n]): n for n in self._names}
        names = set()
        for _, sub in model.named_sublayers(include_self=True):
            if isinstance(sub, SparseEmbedding):
                w = sub.weight
            elif isinstance(sub, Embedding) and getattr(sub, "_sparse",
                                                        False):
                w = sub.weight
            else:
                continue
            n = by_id.get(id(w))
            if n is not None:
                names.add(n)
        return names

    # -- pure step ------------------------------------------------------
    def _loss(self, params, data, kwdata, scale):
        all_params = dict(params)
        # frozen params participate in forward with their current values
        for n, t in self._tensors.items():
            if n not in all_params:
                all_params[n] = t._data
        out = functional_call(self.model, all_params, *data, **kwdata)
        if self.loss_fn is not None:
            out = self.loss_fn(out)
        elif isinstance(out, (tuple, list)):
            out = out[0]
        return out * scale  # loss scaling fused in-graph (scale==1 => no-op)

    def _sparse_value_and_grad(self, params, data, kwdata, scale, sparse):
        """Differentiate the loss with embedding tables on the row-sparse
        path: the tables enter through ``stop_gradient`` and each captured
        lookup's rows ride a zeros ``[n_ids, dim]`` delta, so the backward
        emits per-occurrence row grads instead of a vocab-sized
        scatter-add. Returns ``(loss, dense_grads, sparse_grads)`` where
        ``sparse_grads[name] = (uniq_ids, row_grads, valid)`` — duplicate
        ids already segment-summed into unique slots at the static
        batchxfields bound (shapes stay bucket-stable for the jit cache)."""
        from ..ops import sparse_grad

        registry = {id(params[n]): n for n in sparse}
        # discovery: one abstract forward (jax.make_jaxpr — no FLOPs, no
        # executable, runs at trace time only) records each lookup's
        # flattened id count so the deltas exist before differentiation,
        # and yields the jaxpr for the lookup-only safety analysis
        with sparse_grad.capture(registry, "discover") as cap:
            closed = jax.make_jaxpr(
                lambda: self._loss(params, data, kwdata, scale))()
        # safety gate: a table consumed by anything other than the
        # capture's stop_gradient route (tied weights, direct matmul, a
        # cast that broke identity matching) would silently LOSE that
        # gradient on the row-sparse path — fall it back to dense
        safe = sparse_grad.lookup_only_tables(
            closed, {n: params[n] for n in sparse})
        unsafe = [n for n in sparse if n not in safe]
        if unsafe:
            import warnings

            warnings.warn(
                f"{self._stats_name}: sparse table(s) {sorted(unsafe)} are "
                "used outside embedding lookups in this loss (tied "
                "weights / direct reads) — taking the DENSE gradient path "
                "for them; lazy_mode row-sparse updates apply only to "
                "lookup-only tables", stacklevel=2)
            sparse = [n for n in sparse if n in safe]
            if not sparse:
                loss, grads = jax.value_and_grad(self._loss)(
                    params, data, kwdata, scale)
                return loss, grads, {}
            registry = {id(params[n]): n for n in sparse}
        sparse_set = set(sparse)
        deltas = {n: [jnp.zeros((k, params[n].shape[-1]), jnp.float32)
                      for k in cap.counts.get(n, [])] for n in sparse}
        dense_params = {n: v for n, v in params.items()
                        if n not in sparse_set}

        def loss_fn(dp, deltas_):
            full = dict(dp)
            for n in sparse:
                full[n] = params[n]
            with sparse_grad.capture(registry, "apply", deltas_) as c:
                out = self._loss(full, data, kwdata, scale)
                ids = {n: list(c.ids.get(n, [])) for n in sparse}
            return out, ids

        (loss, ids_rec), (dgrads, delta_grads) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(dense_params, deltas)
        sgrads = {}
        for n in sparse:
            chunks = ids_rec.get(n, [])
            if not chunks:
                # registered table the forward never looked up: no rows
                # touched, no update this step
                dim = params[n].shape[-1]
                sgrads[n] = (jnp.zeros((0,), jnp.int32),
                             jnp.zeros((0, dim), jnp.float32),
                             jnp.zeros((0,), jnp.bool_))
                continue
            ids_all = (chunks[0] if len(chunks) == 1
                       else jnp.concatenate(chunks))
            g_all = (delta_grads[n][0] if len(delta_grads[n]) == 1
                     else jnp.concatenate(delta_grads[n]))
            sgrads[n] = sparse_grad.segment_rows(ids_all, g_all,
                                                 combine="add")
        return loss, dgrads, sgrads

    def _step_impl(self, params, m1, m2, acc, lr, scale, data, kwdata,
                   guard, track_gnorm):
        step_prev, loss_sum, skips, gpeak = acc
        step = step_prev + 1.0  # bias-correction count for THIS step
        sparse = [n for n in self._sparse_names if n in params]
        if sparse:
            loss, grads, sgrads = self._sparse_value_and_grad(
                params, data, kwdata, scale, sparse)
        else:
            loss, grads = jax.value_and_grad(self._loss)(params, data,
                                                         kwdata, scale)
            sgrads = {}
        # unscale: grads of the scaled loss divided by scale are the true
        # grads (reference check_finite_and_unscale), and the finite check
        # runs post-unscale exactly like AmpScaler.unscale_
        inv = 1.0 / scale
        loss = loss * inv
        grads = jax.tree.map(lambda g: (_f32(g) * inv).astype(g.dtype),
                             grads)
        sgrads = {n: (ids, g * inv, valid)
                  for n, (ids, g, valid) in sgrads.items()}
        sgrad_leaves = [g for _, g, _ in sgrads.values()]
        if guard == "off":
            all_finite = jnp.bool_(True)  # constant: no reduction in-graph
        else:
            all_finite = jnp.all(jnp.isfinite(loss))
            for g in jax.tree.leaves(grads) + sgrad_leaves:
                all_finite = jnp.logical_and(all_finite,
                                             jnp.all(jnp.isfinite(g)))
        gnorm = None  # pre-clip global grad norm (the explosion signal)
        if self._clip_norm is not None or track_gnorm:
            # dead dedup slots hold zero rows, so the row-grad squares sum
            # to exactly the dense table-grad norm contribution
            gnorm = jnp.sqrt(sum(
                jnp.sum(_f32(g) ** 2)
                for g in jax.tree.leaves(grads) + sgrad_leaves))
        if self._clip_norm is not None:
            factor = jnp.minimum(1.0, self._clip_norm / (gnorm + 1e-12))
            grads = jax.tree.map(lambda g: (_f32(g) * factor).astype(g.dtype),
                                 grads)
            sgrads = {n: (ids, g * factor, valid)
                      for n, (ids, g, valid) in sgrads.items()}
        opt = self.optimizer
        kind = self._kind
        if kind in ("adam", "adamw"):
            b1 = jnp.float32(opt._beta1)
            b2 = jnp.float32(opt._beta2)
            eps = jnp.float32(opt._epsilon)
            b1p = jnp.power(b1, step)
            b2p = jnp.power(b2, step)

            def upd(p, g, m1_, m2_, wd, lr_ratio):
                gf, pf = _f32(g), _f32(p)
                if kind == "adam":
                    gf = gf + wd * pf
                m1n = b1 * m1_ + (1 - b1) * gf
                m2n = b2 * m2_ + (1 - b2) * gf * gf
                m1h = m1n / (1 - b1p)
                m2h = m2n / (1 - b2p)
                step_lr = lr * lr_ratio
                new = pf - step_lr * m1h / (jnp.sqrt(m2h) + eps)
                if kind == "adamw":
                    new = new - step_lr * wd * pf
                return new.astype(p.dtype), m1n, m2n

            out = {n: upd(params[n], grads[n], m1[n], m2[n],
                          self._wds[n], self._lr_ratios[n])
                   for n in params if n not in sgrads}
            new_p = {n: v[0] for n, v in out.items()}
            new_m1 = {n: v[1] for n, v in out.items()}
            new_m2 = {n: v[2] for n, v in out.items()}
            if sgrads:
                from ..optimizer.optimizers import lazy_adam_rows

                for n, (ids, row_g, valid) in sgrads.items():
                    # protect mode gates the scatter itself: a non-finite
                    # step masks every slot, and masked slots write back
                    # current values — the dense path's vocab-sized
                    # jnp.where select is never needed here
                    upd_mask = (jnp.logical_and(valid, all_finite)
                                if guard == "protect" else valid)
                    np_, nm1, nm2 = lazy_adam_rows(
                        params[n], m1[n], m2[n], ids, row_g, upd_mask,
                        lr, b1, b2, eps, b1p, b2p, kind,
                        jnp.float32(self._wds[n]),
                        jnp.float32(self._lr_ratios[n]))
                    new_p[n] = np_
                    new_m1[n] = nm1
                    new_m2[n] = nm2
        elif kind == "momentum":
            mu = jnp.float32(opt._momentum)

            def updm(p, g, v, wd):
                gf = _f32(g) + wd * _f32(p)
                vn = mu * v + gf
                return (_f32(p) - lr * vn).astype(p.dtype), vn

            out = {n: updm(params[n], grads[n], m1[n], self._wds[n])
                   for n in params}
            new_p = {n: v[0] for n, v in out.items()}
            new_m1 = {n: v[1] for n, v in out.items()}
            new_m2 = m2
        else:  # sgd
            new_p = {n: (_f32(params[n])
                         - lr * (_f32(grads[n])
                                 + self._wds[n] * _f32(params[n]))
                         ).astype(params[n].dtype)
                     for n in params}
            new_m1, new_m2 = m1, m2
        if guard == "protect":
            # skip-step semantics: a non-finite step leaves params AND
            # moments untouched (one jnp.where per buffer — XLA fuses the
            # select into the update, no extra memory traffic), and the
            # bias-correction count does not advance — all in-graph, so no
            # host fetch is needed for the discard to be correct
            def keep(new, old):
                # sparse-route entries were already gated at scatter time
                # (upd_mask) — a vocab-sized select here would reintroduce
                # the full-table traffic the lazy path removes
                return {n: (new[n] if n in sgrads
                            else jnp.where(all_finite, new[n], old[n]))
                        for n in new}

            new_p = keep(new_p, params)
            new_m1 = keep(new_m1, m1) if new_m1 is not m1 else m1
            new_m2 = keep(new_m2, m2) if new_m2 is not m2 else m2
            new_step = jnp.where(all_finite, step, step_prev)
            new_skips = skips + jnp.where(all_finite, 0.0, 1.0)
            # a skipped step must not poison the running loss sum with NaN
            loss_inc = jnp.where(all_finite, _f32(loss), 0.0)
        else:
            new_step = step
            new_skips = skips
            loss_inc = _f32(loss)
        if track_gnorm:
            # window peak; a non-finite norm is the NaN guard's domain,
            # not the sentinel's ceiling — excluded so a skipped NaN step
            # cannot wedge the peak at inf/NaN for the rest of the window
            new_gpeak = jnp.maximum(gpeak, jnp.where(
                jnp.isfinite(gnorm), _f32(gnorm), 0.0))
        else:
            new_gpeak = gpeak
        new_acc = (new_step, loss_sum + loss_inc, new_skips, new_gpeak)
        return loss, all_finite, new_acc, new_p, new_m1, new_m2

    # -- public ---------------------------------------------------------
    def _lower(self, *data, **kwdata):
        """Lower (but do not run) the fused executable for these inputs —
        guard off, gnorm tracking off: the plain steady-state program.
        When the step already compiled for these shapes, ``.compile()`` on
        the result is a cache hit, not a second compile."""
        darrs, karrs = self._prepare_arrays(data, kwdata, record=False)
        return self._jitted.lower(
            self._params, self._m1, self._m2,
            (jnp.float32(0), jnp.float32(0), jnp.float32(0),
             jnp.float32(0)),
            jnp.float32(1e-3), jnp.float32(1), darrs, karrs, "off",
            False)

    def lowered_flops(self, *data, **kwdata):
        """FLOPs of one full fused step (forward + backward + update) from
        XLA's HLO cost analysis on the lowered program — self-measured, no
        hand-derived formula. Returns None when the backend provides no
        estimate. Used by bench.py for MFU accounting."""
        try:
            lowered = self._lower(*data, **kwdata)
            cost = lowered.cost_analysis()
            if not (hasattr(cost, "get") and cost.get("flops")):
                # some backends only report cost post-compile
                cost = lowered.compile().cost_analysis()
            flops = cost.get("flops") if hasattr(cost, "get") else None
            return float(flops) if flops and flops > 0 else None
        except Exception:
            return None

    def hlo_cost_report(self, *data, top_n=None, **kwdata):
        """Per-op cost ledger of this step's OPTIMIZED HLO for the given
        inputs: each entry-computation op with its bytes accessed (result
        + operands — a fusion's external traffic) and estimated FLOPs,
        ranked by bytes. See ``paddle.jit.hlo_audit`` for the method and
        ``scripts/audit_hlo.py`` for the per-workload reports."""
        from ..jit import hlo_audit

        compiled = self._lower(*data, **kwdata).compile()
        return hlo_audit.audit(compiled, top_n=top_n)

    def _prepare_arrays(self, data, kwdata, record=True):
        """Unwrap call inputs to jax arrays, padding each up to its shape
        bucket when buckets are registered (per-step or global).
        ``record=False`` keeps estimation-only callers (lowered_flops) out
        of the dispatch telemetry."""
        from ..jit import cache as jit_cache

        darrs = tuple(d._data if isinstance(d, Tensor) else jnp.asarray(d)
                      for d in data)
        karrs = {k: (v._data if isinstance(v, Tensor) else jnp.asarray(v))
                 for k, v in kwdata.items()}
        spec = (self._shape_buckets if self._shape_buckets is not None
                else jit_cache.get_shape_buckets())
        if spec is not None:
            # selection: bucket_args pins the padded inputs explicitly;
            # otherwise the dominant-length rule (jit_cache
            # .infer_call_lengths) — the first input carrying the bucketed
            # axis defines the call's length and only matching inputs pad,
            # so [B, 1] labels / [B, n_features] dense vectors pass through
            # instead of gaining fabricated zeros. Use bucket_args when a
            # fixed field's width can coincide with a sequence length.
            sel = self._bucket_args
            lengths = (jit_cache.infer_call_lengths(
                list(darrs) + list(karrs.values()), spec)
                if sel is None else None)
            n_pad = 0
            padded = []
            for i, a in enumerate(darrs):
                if sel is None or i in sel:
                    a, p = jit_cache.pad_array_to_bucket(a, spec, lengths)
                    n_pad += p
                padded.append(a)
            darrs = tuple(padded)
            for k, a in karrs.items():
                if sel is None or k in sel:
                    a, p = jit_cache.pad_array_to_bucket(a, spec, lengths)
                    n_pad += p
                    karrs[k] = a
            if record:
                jit_cache.record_bucket_pads(self._stats_name, n_pad)
        if self._plan is not None:
            # activation rules: commit each data input to its plan
            # sharding (batch over dp, seq over sep, ...) so GSPMD sees
            # the intended layout instead of inferring replication
            darrs = tuple(self._plan.place_data(a) for a in darrs)
            karrs = {k: self._plan.place_data(a) for k, a in karrs.items()}
        return darrs, karrs

    def _count_dispatch(self, darrs, karrs):
        """Compile-vs-hit telemetry: a shape signature not seen before means
        jax.jit traces + XLA-compiles a fresh executable this dispatch."""
        from ..jit import cache as jit_cache

        sig = jit_cache.shape_signature(
            list(darrs) + [karrs[k] for k in sorted(karrs)])
        if sig in self._seen_sigs:
            jit_cache.record_hit(self._stats_name)
        else:
            self._seen_sigs.add(sig)
            jit_cache.record_compile(self._stats_name, sig)

    def state_dict(self):
        """Checkpointable state of the fused step: the in-graph moment
        buffers, the bias-correction step count and the sentinel's LR
        cooldown scale (weights live in the model; this object is the
        optimizer-state owner while it trains). Duck-type-compatible with
        ``CheckpointManager.save(optimizer=...)`` /
        ``auto_resume(optimizer=...)``."""
        import numpy as np

        # the authoritative step count is the device accumulator (the host
        # mirror can lag inside a deferred-fetch window) — guard_stats
        # (sync=True) flushes the host mirrors from it in one host sync
        # here, at the checkpoint boundary, so checkpoint-time telemetry
        # is as authoritative as the checkpoint itself
        self.guard_stats(sync=True)
        sd = {"step_count": self._step_count,
              "lr_scale": float(self._lr_scale)}
        # the LR scheduler advanced once per dispatched step; without its
        # state a restore (crash-resume OR divergence rollback) would
        # resume the schedule N steps ahead of the restored trajectory
        sched = getattr(self.optimizer, "_learning_rate", None)
        if hasattr(sched, "state_dict"):
            sd["lr_sched"] = sched.state_dict()
        for prefix, store in (("m1", self._m1), ("m2", self._m2)):
            for n, v in store.items():
                sd[f"{prefix}.{n}"] = np.asarray(v)
        return sd

    def set_state_dict(self, sd):
        self._step_count = int(sd.get("step_count", self._step_count))
        self._lr_scale = float(sd.get("lr_scale", 1.0))
        sched = getattr(self.optimizer, "_learning_rate", None)
        if "lr_sched" in sd and hasattr(sched, "set_state_dict"):
            sched.set_state_dict(sd["lr_sched"])
        self._acc = (jnp.float32(self._step_count), self._acc[1],
                     self._acc[2], self._acc[3])
        for prefix, store in (("m1", self._m1), ("m2", self._m2)):
            for n in store:
                key = f"{prefix}.{n}"
                if key in sd:
                    v = sd[key]
                    arr = jnp.asarray(
                        v._data if isinstance(v, Tensor) else v)
                    if self._plan is not None:
                        arr = jax.device_put(
                            arr,
                            self._plan.moment_sharding_for(n, arr.shape))
                    store[n] = arr

    load_state_dict = set_state_dict

    @property
    def plan(self):
        """The sharding Plan this step compiles under (None on the
        single-device path)."""
        return self._plan

    def _adopt_external_rebinds(self):
        """A checkpoint resume (``CheckpointManager.auto_resume`` /
        ``set_state_dict``) rebinds the model's parameter Tensors outside
        this step's control; detect that (pointer comparison per param) and
        adopt the new arrays, else the next dispatch would clobber the
        restored weights with this step's stale internal copies."""
        for n in self._names:
            t = self._tensors[n]._data
            if t is not self._params[n]:
                if self._plan is not None:
                    # a restore loads host arrays; re-commit to the plan
                    # layout or the next dispatch would compile/reshard
                    # for a replicated input
                    t = jax.device_put(
                        t, self._plan.sharding_for(n, t.shape))
                    self._tensors[n]._rebind(t)
                self._params[n] = t

    def device_metrics(self):
        """The device-resident accumulator, fetched in ONE host sync:
        ``{"step_count", "loss_sum", "skipped", "gnorm_peak"}``.
        ``loss_sum`` is the running sum of applied per-step losses
        (non-finite skipped steps excluded in protect mode), ``skipped``
        counts in-graph discards, ``gnorm_peak`` the peak global grad norm
        since the last window reset (0.0 unless the sentinel's grad-norm
        tracking is armed). Authoritative at any time — including inside a
        deferred-fetch window, where the host mirrors (``guard_stats``)
        lag until the next boundary or an explicit
        ``guard_stats(sync=True)``."""
        import numpy as np

        vals = np.asarray(jnp.stack([jnp.asarray(a, jnp.float32)
                                     for a in self._acc]))
        return {"step_count": int(vals[0]), "loss_sum": float(vals[1]),
                "skipped": int(vals[2]), "gnorm_peak": float(vals[3])}

    def guard_stats(self, sync=False):
        """Step-anomaly-guard counters: ``total`` dispatched steps,
        ``skipped`` updates discarded for non-finite loss/grads,
        ``consecutive_skips`` current streak (a growing streak means the run
        is in a NaN spiral, not a one-off overflow), ``warned`` warn-mode
        events.

        Inside a deferred-fetch window (``drive``) the host mirrors lag
        the device until the next boundary replays the bookkeeping;
        ``sync=True`` flushes them NOW from the authoritative device
        accumulator (one host sync — ``step_count``/``skipped`` become
        exact; ``consecutive_skips`` is inherently boundary-resolution and
        is left untouched). ``state_dict`` uses this, so checkpoint-time
        stats are authoritative."""
        if sync:
            dm = self.device_metrics()
            self._step_count = dm["step_count"]
            self._guard["skipped"] = dm["skipped"]
        self._publish_guard_metrics()
        return dict(self._guard)

    def _publish_guard_metrics(self):
        """Mirror the guard's host counters into the registry
        (``train_guard_*{instance=...}``) — guard_stats() keeps its dict
        shape, the registry carries the same numbers for scraping."""
        for k, g in _G_GUARD.items():
            g.set(self._guard[k], instance=self._stats_name)

    @staticmethod
    def _batch_items(args, kw):
        """Items one batch contributes to the throughput gauge: tokens
        (rows x length) when the leading input is a 2-D integer array
        (token ids), else leading-dim examples. A heuristic, stated as
        one — the gauge is `train_items_per_sec`, not a benchmark."""
        for x in list(args) + list(kw.values()):
            arr = x._data if isinstance(x, Tensor) else x
            shape = getattr(arr, "shape", None)
            if shape is None or len(shape) == 0:
                continue
            if len(shape) == 2 and jnp.issubdtype(arr.dtype, jnp.integer):
                return int(shape[0]) * int(shape[1])
            return int(shape[0])
        return 1

    def _record_window_obs(self, obs_state, n_steps, n_bad, t_end):
        """Accumulate one flushed window into the pending observability
        state and publish at the ``metrics_every`` cadence. Pure host
        arithmetic over values already fetched — never a device sync."""
        every = obs_state["every"]
        if every == 0:
            return
        obs_state["steps"] += n_steps
        obs_state["bad"] += n_bad
        if every is not None and obs_state["steps"] < every:
            return
        self._publish_window_obs(obs_state, t_end)

    def _publish_window_obs(self, obs_state, t_end):
        """Publish the pending accumulation. Also called once at drive
        exit with whatever remains: a `*_total` counter that silently
        dropped the trailing sub-``metrics_every`` window would
        undercount every drive whose step count is not a multiple."""
        if obs_state["every"] == 0 or not obs_state["steps"]:
            return
        wall = max(t_end - obs_state["t0"], 1e-9)
        inst = self._stats_name
        _M_TRAIN_STEPS.inc(obs_state["steps"], instance=inst)
        if obs_state["bad"]:
            _M_TRAIN_SKIPPED.inc(obs_state["bad"], instance=inst)
        _H_WINDOW_S.observe(wall, instance=inst)
        if obs_state["items_per_step"]:
            _G_ITEMS_PER_S.set(
                obs_state["items_per_step"] * obs_state["steps"] / wall,
                instance=inst)
        self._publish_guard_metrics()
        obs_state["steps"] = 0
        obs_state["bad"] = 0
        obs_state["t0"] = t_end

    @staticmethod
    def _poison_first_float(darrs, karrs, fn):
        """Apply ``fn`` to the first floating-point call input (shape/
        dtype signature unchanged — no recompile). Shared walker for the
        input-poisoning fault sites."""
        darrs = list(darrs)
        for i, a in enumerate(darrs):
            if jnp.issubdtype(a.dtype, jnp.inexact):
                darrs[i] = fn(a)
                return tuple(darrs), karrs
        for k in sorted(karrs):
            if jnp.issubdtype(karrs[k].dtype, jnp.inexact):
                karrs = dict(karrs)
                karrs[k] = fn(karrs[k])
                return tuple(darrs), karrs
        return tuple(darrs), karrs

    def _poison_nan(self, darrs, karrs):
        """train.grad_nan injection: NaN-fill the first floating-point
        input so loss/grads go non-finite this step."""
        return self._poison_first_float(
            darrs, karrs, lambda a: jnp.full_like(a, jnp.nan))

    _SPIKE_SCALE = 1e3

    def _poison_spike(self, darrs, karrs):
        """train.spike injection: scale the first floating-point input by
        1e3 so loss/grads go finite-but-huge — the NaN guard stays silent
        and only the divergence sentinel can catch it."""
        return self._poison_first_float(
            darrs, karrs,
            lambda a: a * jnp.asarray(self._SPIKE_SCALE, a.dtype))

    def _dispatch(self, data, kwdata, guard, scale_val, track_gnorm=False):
        """One asynchronous dispatch of the fused executable: prepare and
        bucket-pad inputs, fire, rebind donated buffers. Returns the lazy
        (loss, finite) device values — NO host sync happens here; that is
        the caller's choice (per-step in ``__call__``, per-window in
        ``drive``)."""
        from ..utils import fault_injection

        lr = jnp.float32(self.optimizer.get_lr() * self._lr_scale)
        self._adopt_external_rebinds()
        darrs, karrs = self._prepare_arrays(data, kwdata)
        if fault_injection.should_fire("train.grad_nan"):
            darrs, karrs = self._poison_nan(darrs, karrs)
        if fault_injection.should_fire("train.spike"):
            darrs, karrs = self._poison_spike(darrs, karrs)
        self._count_dispatch(darrs, karrs)
        loss, finite, self._acc, self._params, self._m1, self._m2 = \
            self._jitted(self._params, self._m1, self._m2, self._acc, lr,
                         jnp.float32(scale_val), darrs, karrs, guard,
                         track_gnorm)
        # donation invalidated the old buffers — rebind the live Tensors
        for n in self._names:
            self._tensors[n]._rebind(self._params[n])
        return loss, finite

    def __call__(self, *data, **kwdata):
        from ..core.flags import flag_value

        self._step_count += 1
        self._guard["total"] += 1
        action = str(flag_value("check_nan_inf_action", "none"))
        # a disabled scaler (GradScaler(enable=False)) must behave exactly
        # like no scaler: no host sync, no silent skip semantics
        scaler = (self._scaler if self._scaler is not None
                  and self._scaler.is_enable() else None)
        # guard host-syncs the finite flag when an action wants it or a
        # scaler needs the signal; "protect" discards non-finite updates
        # in-graph (always on with a scaler: GradScaler.step semantics);
        # "off" compiles the guard out entirely
        guard_active = action != "none" or scaler is not None
        protect = scaler is not None or action in ("skip", "raise")
        guard = "protect" if protect else ("flag" if guard_active else "off")
        scale_val = 1.0 if scaler is None else float(scaler._scale)
        loss, finite = self._dispatch(data, kwdata, guard, scale_val)
        skipped = False
        if guard_active:
            ok = bool(finite)  # the guard's single host sync
            if not ok:
                if action == "warn":
                    import warnings

                    self._guard["warned"] += 1
                    warnings.warn(
                        f"non-finite loss/grads at step {self._step_count}"
                        + ("" if protect else " — update applied anyway "
                           "(FLAGS_check_nan_inf_action=warn)"),
                        stacklevel=2)
                if protect:
                    skipped = True
                    self._guard["skipped"] += 1
                    self._guard["consecutive_skips"] += 1
                    # the discarded step must not advance bias correction
                    self._step_count -= 1
                if scaler is not None:
                    # found_inf -> dynamic backoff (scale decays, good-step
                    # streak resets), mirroring scaler.update() after a
                    # skipped scaler.step()
                    scaler._found_inf = True
                    scaler.update()
                if action == "raise":
                    raise FloatingPointError(
                        f"non-finite loss/grads at step "
                        f"{self._step_count + 1}; update discarded "
                        "(FLAGS_check_nan_inf_action=raise)")
            else:
                self._guard["consecutive_skips"] = 0
                if scaler is not None:
                    scaler._found_inf = False
                    scaler.update()  # good-step bookkeeping (may grow scale)
        if self._step_lr_scheduler and not skipped:
            sched = getattr(self.optimizer, "_learning_rate", None)
            if hasattr(sched, "step"):
                sched.step()
        return Tensor._wrap(loss)

    # -- multi-step driver ----------------------------------------------
    @staticmethod
    def _call_form(batch):
        """A loader batch as this step's call arguments: tuples/lists are
        positional, dicts travel by keyword, anything else is one arg."""
        if isinstance(batch, dict):
            return (), batch
        if isinstance(batch, (list, tuple)):
            return tuple(batch), {}
        return (batch,), {}

    def drive(self, data, steps=None, log_every=None, prefetch=None,
              prefetch_depth=None, on_window=None, checkpoint=None,
              sampler=None, heartbeat=True, handle_preemption=True,
              sentinel=None, metrics_every=None):
        """Multi-step driver: dispatch fused steps back-to-back with NO
        per-step host sync, so the device executable queue stays deep while
        the input side is double-buffered by a :class:`DevicePrefetcher`.

        Per step the host does only: pull a staged batch, dispatch, enqueue
        the lazy (loss, finite) handles. Every ``log_every`` steps
        (default ``FLAGS_metric_fetch_interval``) the window is fetched in
        O(1) host round-trips — one ``jnp.stack`` of the window losses (+
        one of the finite flags when the guard is armed) — and the guard's
        host bookkeeping (warn/skip counters, ``raise``) is replayed.
        Skip-step semantics need no host involvement at all: a non-finite
        step's update AND its bias-correction advance are discarded
        in-graph, so the deferred trajectory is bit-identical to per-step
        fetch.

        ``data`` is any batch iterable (DataLoader, list of batches, or an
        existing DevicePrefetcher). ``prefetch=False`` disables the
        wrapping; by default batches are staged through a prefetcher that
        inherits this step's shape buckets / bucket_args so pre-padded
        shapes hit the same executables (zero extra compiles).

        Deferred-mode differences, stated honestly: an attached enabled
        GradScaler forces the per-step-fetch path (the scale for step N+1
        depends on step N's finite flag); an LR scheduler advances every
        step including ones later found non-finite (the skip signal is not
        on host until the boundary); ``action='raise'`` raises at the fetch
        boundary, with the offending updates already discarded in-graph.
        Checkpoint at fetch boundaries (e.g. from ``on_window``) —
        ``state_dict`` reads the authoritative device step count.

        Supervision (the elastic-launcher contract):

        - **Heartbeats** (``heartbeat=True``): when launched under
          ``paddle_tpu.distributed.launch`` (``PADDLE_HEARTBEAT_DIR``
          set), a heartbeat file is written at drive start and at every
          window boundary, feeding the launcher's hang watchdog
          (``FLAGS_worker_hang_timeout_s``). Unsupervised runs pay one
          env lookup.
        - **Graceful preemption** (``handle_preemption=True``): SIGTERM is
          trapped; the loop finishes the in-flight fetch window, writes a
          committed checkpoint through ``checkpoint`` (a
          ``CheckpointManager`` — saving this step's model, its own
          optimizer state, and ``sampler``'s stream cursor), then raises
          ``SystemExit(PREEMPT_EXIT_CODE)`` (123), which the launcher
          relaunches WITHOUT consuming restart budget. Stopping only at
          window boundaries keeps multi-process ranks checkpointing at the
          same global step (windows are step-aligned across ranks).
        - **Stall detection** (``FLAGS_step_timeout_s`` > 0): a wall-clock
          guard around the fetch points raises a typed
          :class:`~paddle_tpu.core.exceptions.TrainStallError` when a step
          wedges, so a dead collective becomes a restartable crash instead
          of an infinite block.
        - **Resumable data** (``sampler=``, or auto-detected from ``data``
          when ``checkpoint`` is given): each trained batch advances the
          sampler's consumed-batch cursor, so a checkpoint written at a
          window boundary (``on_window`` or the preemption save) resumes
          the *exact* remaining batch sequence — prefetch read-ahead never
          skews it.
        - **Divergence sentinel** (``FLAGS_sentinel_action`` != 'none', or
          an explicit ``sentinel=`` :class:`TrainingSentinel`): every
          fetched window is judged by the loss-spike / grad-explosion /
          trend detectors — a pure host computation over the values the
          deferred fetch brings over anyway, so arming it adds ZERO
          per-step host syncs. On a spike verdict the response ladder
          runs: ``warn`` (RuntimeWarning), ``skip`` (also drop the next
          window of batches — a contiguous poisoned input region),
          ``rollback`` (restore model + this step's optimizer state from
          ``checkpoint.latest_healthy_step()`` while the sampler cursor
          stays exactly where the spike left it — every batch consumed
          since the healthy step, the poisoned window included, is never
          replayed and the in-flight epoch keeps its recorded shuffle
          seed; reset the prefetcher's read-ahead, apply the
          ``FLAGS_sentinel_lr_cooldown`` scale, drop newer poisoned
          checkpoints, and continue — budgeted by a leaky bucket that
          raises :class:`TrainDivergenceError` on exhaustion), ``raise``
          (typed error at the first verdict).
          Health metadata: each clean window credits the checkpoints
          ``checkpoint`` has committed (``note_window``), so a step only
          becomes a rollback target ``FLAGS_sentinel_healthy_windows``
          clean windows after it was written. Multi-process runs
          cross-check the verdict through the jax.distributed
          coordination service before responding, so every rank rolls
          back identically (a disagreeing rank is a split brain and
          raises).

        **Observability** (``metrics_every=``, ISSUE 10): every window
        boundary records registry metrics (``train_steps_total``,
        ``train_skipped_steps_total``, ``train_window_seconds``,
        ``train_items_per_sec`` — see ``paddle.observability.metrics``)
        and, when the tracer is enabled, emits per-window spans
        (``train.window`` / ``train.dispatch`` / ``train.fetch`` /
        ``train.guard`` / ``train.sentinel`` / ``train.checkpoint``).
        Everything is host-side arithmetic over values the deferred fetch
        already brought over, so instrumentation adds ZERO host syncs and
        the loss trajectory is bit-identical with observability on or
        off. ``metrics_every=N`` thins the registry updates to boundaries
        at least ``N`` steps apart; ``0`` disables them for this drive;
        ``None`` (default) records every window.

        Returns ``{"steps", "loss" (per-step floats), "skipped",
        "windows", "host_syncs", "log_every", "deferred", "prefetch",
        "rollbacks", "skipped_windows", "sentinel"}`` (``sentinel`` is the
        sentinel's ``stats()`` snapshot, or None when unarmed). (A
        preempted drive never returns: it exits via
        ``SystemExit(PREEMPT_EXIT_CODE)`` after its checkpoint.)
        """
        from ..core.flags import flag_value
        from ..io.prefetch import DevicePrefetcher

        if log_every is None:
            log_every = int(flag_value("metric_fetch_interval", 10))
        log_every = max(1, int(log_every))
        # divergence sentinel: explicit instance wins; else armed from
        # FLAGS_sentinel_action. Detection rides the window fetch, so an
        # armed sentinel costs zero additional per-step host syncs. The
        # flag-created instance is CACHED on this step across drive()
        # calls — the epoch-loop pattern (one drive per epoch) must keep
        # accumulating the rollback budget, spike history and EMA
        # baseline, or the leaky-bucket loop breaker could never fire
        if sentinel is None:
            if str(flag_value("sentinel_action", "none")) != "none":
                from .sentinel import TrainingSentinel

                cached = getattr(self, "_flag_sentinel", None)
                if cached is None or cached.action != str(
                        flag_value("sentinel_action", "none")):
                    cached = TrainingSentinel()
                    self._flag_sentinel = cached
                sentinel = cached
        elif not sentinel.armed:
            sentinel = None
        rollback_armed = sentinel is not None and \
            sentinel.action == "rollback"
        stream = data
        made_prefetcher = None
        if prefetch is None:
            prefetch = not isinstance(data, DevicePrefetcher)
        if prefetch and not isinstance(data, DevicePrefetcher):
            import itertools

            # cap the SOURCE at steps too: otherwise the transfer thread
            # reads ahead of the cap and discards up to depth+1 batches a
            # one-shot iterator's owner still wanted. A rollback-armed
            # sentinel needs the source RE-ITERABLE from the restored
            # cursor instead (islice would pin one half-consumed pass),
            # so there the while-loop's own cap does the bounding
            source = (itertools.islice(iter(data), steps)
                      if steps is not None and not rollback_armed else data)
            made_prefetcher = DevicePrefetcher(
                source, depth=prefetch_depth,
                shape_buckets=self._shape_buckets,
                bucket_args=self._bucket_args,
                name=f"{self._stats_name}.prefetch")
            stream = made_prefetcher
        history = {"steps": 0, "loss": [], "skipped": 0, "windows": 0,
                   "host_syncs": 0, "log_every": log_every,
                   "deferred": True, "prefetch": None, "rollbacks": 0,
                   "skipped_windows": 0, "sentinel": None}
        # window observability state: metrics_every=None records every
        # boundary, N thins to >=N-step gaps, 0 disables for this drive.
        # When the registry itself is disabled, recording is a no-op by
        # construction (every mutate checks the registry switch).
        import time as _obs_time

        obs_state = {
            "every": (None if metrics_every is None
                      else max(0, int(metrics_every))),
            "steps": 0, "bad": 0, "items_per_step": None,
            "t0": _obs_time.perf_counter()}

        # resumable-stream cursor: only armed on the resume-enabled path
        # (an explicit sampler=, or a checkpoint manager to persist into) —
        # plain perf-driving loops keep their batch streams untouched
        resumable = None
        if sampler is not None or checkpoint is not None:
            from ..io import resolve_resumable

            resumable = resolve_resumable(
                sampler if sampler is not None else data)
            if sampler is not None and resumable is None:
                raise TypeError(
                    f"sampler={type(sampler).__name__} is not a resumable "
                    "stream: it must expose (or wrap something exposing) "
                    "state_dict/set_state_dict/advance")
        step_timeout = float(flag_value("step_timeout_s", 0) or 0)

        scaler = (self._scaler if self._scaler is not None
                  and self._scaler.is_enable() else None)
        if scaler is not None:
            # dynamic loss scaling consumes the finite flag every step —
            # fall back to the per-step path (prefetch still overlaps H2D)
            import os as _os
            import signal as _signal
            import time as _time

            import numpy as np

            from ..core.exceptions import stall_guard
            from ..distributed.launch import heartbeat as hb
            from ..jit import cache as jit_cache
            from ..utils import fault_injection

            history["deferred"] = False
            # degrade-once semantics (mirroring io.prefetch): say WHY the
            # deferred fetch is off exactly once per step instance, and
            # count every degraded drive in jit.cache_stats() so an A/B
            # bench can see the fallback without scraping warnings
            jit_cache.record_scaler_fallback(self._stats_name)
            if not self._scaler_fallback_warned:
                import warnings

                self._scaler_fallback_warned = True
                warnings.warn(
                    "FusedTrainStep.drive: an enabled GradScaler forces "
                    "per-step metric fetch (the scale for step N+1 "
                    "consumes step N's finite flag on host), so the "
                    "FLAGS_metric_fetch_interval deferred-window path is "
                    "inactive for this drive. Detach the scaler (or "
                    "construct it with enable=False) and use "
                    "FLAGS_check_nan_inf_action=skip to keep non-finite "
                    "protection with deferred fetch; see jit.cache_stats()"
                    f"['{self._stats_name}']['scaler_fallbacks']",
                    RuntimeWarning, stacklevel=2)
            skipped_before = self._guard["skipped"]
            win_start, win_skips = 0, self._guard["skipped"]
            win_start_ns = _obs_time.perf_counter_ns()
            it = iter(stream)

            def scaler_window_end(final=False):
                # on_window still fires at every log boundary (it is the
                # documented checkpoint hook), just with per-step-fetched
                # values instead of a deferred stack
                nonlocal win_start, win_skips, win_start_ns, it
                from .sentinel import make_window

                history["windows"] += 1
                n_steps = len(history["loss"]) - win_start
                n_bad = self._guard["skipped"] - win_skips
                win = make_window(
                    history["loss"][win_start:],
                    non_finite=n_bad,
                    step=history["steps"])
                now_ns = _obs_time.perf_counter_ns()
                _obs_trace.add_complete(
                    "train.window", win_start_ns, now_ns, cat="train",
                    args={"instance": self._stats_name, "steps": n_steps,
                          "non_finite": n_bad})
                win_start_ns = now_ns
                self._record_window_obs(obs_state, n_steps, n_bad,
                                        _obs_time.perf_counter())
                if on_window is not None:
                    with _obs_trace.span("train.checkpoint", cat="train",
                                         args={"instance":
                                               self._stats_name}):
                        on_window(win)
                win_start = len(history["loss"])
                win_skips = self._guard["skipped"]
                if heartbeat:
                    hb.write(step=self._step_count)
                if sentinel is not None:
                    # trailing window: no stream left to rewind/skip —
                    # pass it=None like the deferred path, so a rollback
                    # only restores state for the NEXT drive
                    with _obs_trace.span("train.sentinel", cat="train",
                                         args={"instance":
                                               self._stats_name}):
                        new_it = self._sentinel_check(
                            sentinel, win, history, checkpoint, resumable,
                            stream, None if final else it, log_every,
                            scaler=scaler)
                    if new_it is not None:
                        it = new_it

            with hb.trap_preemption(enable=handle_preemption) as preempt:
                if heartbeat:
                    hb.write(step=self._step_count)
                try:
                    while steps is None or history["steps"] < steps:
                        if (preempt.triggered
                                and len(history["loss"]) == win_start):
                            break  # window boundary: ranks stop aligned
                        if fault_injection.should_fire("proc.kill"):
                            _os.kill(_os.getpid(), _signal.SIGKILL)
                        try:
                            with stall_guard(step_timeout,
                                             f"batch fetch after step "
                                             f"{history['steps']}"):
                                if fault_injection.should_fire(
                                        "train.stall"):
                                    _time.sleep(_STALL_SLEEP_S)
                                batch = next(it)
                        except StopIteration:
                            break
                        args, kw = self._call_form(batch)
                        if obs_state["items_per_step"] is None:
                            obs_state["items_per_step"] = \
                                self._batch_items(args, kw)
                        loss = self(*args, **kw)
                        if resumable is not None:
                            resumable.advance(1)
                        history["steps"] += 1
                        with stall_guard(step_timeout, "loss fetch"):
                            history["loss"].append(float(loss.numpy()))
                        history["host_syncs"] += 2  # finite flag + loss
                        if history["steps"] % log_every == 0:
                            scaler_window_end()
                    if len(history["loss"]) > win_start:
                        scaler_window_end(final=True)
                    history["skipped"] = (self._guard["skipped"]
                                          - skipped_before)
                finally:
                    # an exception (dataset error, action='raise') must
                    # not leak the staging thread parked on the queue,
                    # and the trailing sub-metrics_every accumulation
                    # must still count — *_total counters undercounting
                    # on a raise would misreport exactly the runs one
                    # debugs with these metrics
                    self._publish_window_obs(obs_state,
                                             _obs_time.perf_counter())
                    if made_prefetcher is not None:
                        made_prefetcher.close()
                        history["prefetch"] = made_prefetcher.stats()
                if preempt.triggered:
                    self._preempt_exit(checkpoint, resumable, heartbeat)
            if sentinel is not None:
                history["sentinel"] = sentinel.stats()
            return history

        # guard mode is pinned for the whole drive (one executable); flag
        # changes take effect at the next drive()/__call__
        import os as _os
        import signal as _signal
        import time as _time

        from ..core.exceptions import stall_guard
        from ..distributed.launch import heartbeat as hb
        from ..utils import fault_injection

        action = str(flag_value("check_nan_inf_action", "none"))
        protect = action in ("skip", "raise")
        guard = "protect" if protect else ("flag" if action != "none"
                                           else "off")
        # grad-norm tracking is a static graph choice (like guard): only
        # paid when the sentinel's ceiling is armed, and free when grad
        # clipping already computes the norm
        track_gnorm = bool(sentinel is not None
                           and sentinel.wants_grad_norm())
        window = []
        sched = (getattr(self.optimizer, "_learning_rate", None)
                 if self._step_lr_scheduler else None)
        win_start_ns = _obs_time.perf_counter_ns()

        def flush_and_observe(buf):
            """Flush one window and record its observability: dispatch +
            window spans bracketing timestamps the host already took, and
            the registry metrics at the metrics_every cadence."""
            nonlocal win_start_ns
            pre_ns = _obs_time.perf_counter_ns()
            _obs_trace.add_complete(
                "train.dispatch", win_start_ns, pre_ns, cat="train",
                args={"instance": self._stats_name, "steps": len(buf)})
            win = self._flush_window(buf, action, protect, history,
                                     on_window,
                                     stall_timeout=step_timeout,
                                     track_gnorm=track_gnorm)
            now_ns = _obs_time.perf_counter_ns()
            _obs_trace.add_complete(
                "train.window", win_start_ns, now_ns, cat="train",
                args={"instance": self._stats_name, "steps": len(buf),
                      "non_finite": win["non_finite"]})
            win_start_ns = now_ns
            self._record_window_obs(obs_state, len(buf),
                                    win["non_finite"],
                                    _obs_time.perf_counter())
            return win
        with hb.trap_preemption(enable=handle_preemption) as preempt:
            if heartbeat:
                hb.write(step=self._step_count)
            try:
                it = iter(stream)
                # count checked BEFORE pulling: a one-shot iterator keeps
                # its remaining batches when steps caps the run
                while steps is None or history["steps"] < steps:
                    if preempt.triggered and not window:
                        # stop only at window boundaries: every rank of a
                        # multi-process job reaches the same boundary, so
                        # the preemption checkpoint lands at one global
                        # step (windows are step-aligned across ranks)
                        break
                    if fault_injection.should_fire("proc.kill"):
                        # chaos site: simulate the OOM-killer/node loss
                        _os.kill(_os.getpid(), _signal.SIGKILL)
                    try:
                        with stall_guard(step_timeout,
                                         f"batch fetch after step "
                                         f"{history['steps']}"):
                            if fault_injection.should_fire("train.stall"):
                                _time.sleep(_STALL_SLEEP_S)
                            batch = next(it)
                    except StopIteration:
                        break
                    args, kw = self._call_form(batch)
                    if obs_state["items_per_step"] is None:
                        obs_state["items_per_step"] = \
                            self._batch_items(args, kw)
                    self._step_count += 1
                    self._guard["total"] += 1
                    loss, finite = self._dispatch(args, kw, guard, 1.0,
                                                  track_gnorm)
                    if resumable is not None:
                        resumable.advance(1)
                    window.append((loss, finite))
                    history["steps"] += 1
                    if hasattr(sched, "step"):
                        sched.step()
                    if len(window) >= log_every:
                        # swap-clear BEFORE flushing: if the flush raises
                        # (action='raise'), the trailing flush below must
                        # not replay the same window's bookkeeping
                        full, window = window, []
                        win = flush_and_observe(full)
                        if heartbeat:
                            hb.write(step=self._step_count)
                        if sentinel is not None:
                            with _obs_trace.span(
                                    "train.sentinel", cat="train",
                                    args={"instance": self._stats_name}):
                                new_it = self._sentinel_check(
                                    sentinel, win, history, checkpoint,
                                    resumable, stream, it, log_every)
                            if new_it is not None:
                                it = new_it
                # trailing partial window: flushed only on clean exit — an
                # exception escaping the loop must propagate, not be
                # replaced by a boundary FloatingPointError (the device
                # state is already correct either way; in-graph semantics
                # never needed the host)
                if window:
                    win = flush_and_observe(window)
                    if heartbeat:
                        hb.write(step=self._step_count)
                    if sentinel is not None:
                        # the loop is over, so a skip/rollback response
                        # has no iterator to rewind — but the restore /
                        # warn / raise / health bookkeeping still applies
                        # (the NEXT drive continues from the rolled-back
                        # state and cursor)
                        with _obs_trace.span(
                                "train.sentinel", cat="train",
                                args={"instance": self._stats_name}):
                            self._sentinel_check(
                                sentinel, win, history, checkpoint,
                                resumable, stream, None, log_every)
            except BaseException:
                # the unfetched window's finite flags are lost with the
                # exception — resync the host mirrors from the
                # authoritative device accumulator so guard_stats()/step
                # numbering stay exact for the rest of the process
                if protect:
                    try:
                        self.guard_stats(sync=True)
                    except Exception:
                        pass
                raise
            finally:
                # the trailing sub-metrics_every accumulation must still
                # count even when the loop exits on an exception
                self._publish_window_obs(obs_state,
                                         _obs_time.perf_counter())
                if made_prefetcher is not None:
                    made_prefetcher.close()
                    history["prefetch"] = made_prefetcher.stats()
            if preempt.triggered:
                self._preempt_exit(checkpoint, resumable, heartbeat)
        if sentinel is not None:
            history["sentinel"] = sentinel.stats()
        return history

    def _preempt_exit(self, checkpoint, resumable, heartbeat):
        """Graceful-preemption epilogue: the in-flight window is already
        flushed and the batch cursor is exact, so write one committed
        checkpoint (model + this step's optimizer state + data-stream
        cursor), heartbeat a final time, and exit with the distinguished
        code the supervisor treats as *clean* — relaunch without consuming
        restart budget."""
        from ..distributed.launch import heartbeat as hb

        if checkpoint is not None:
            step_now = self.device_metrics()["step_count"]
            handle = checkpoint.save(step_now, model=self.model,
                                     optimizer=self, sampler=resumable,
                                     plan=self._plan)
            if handle is not None:  # async save: the exit must not tear it
                checkpoint.wait()
        else:
            # the 123 contract promises the supervisor a lossless eviction;
            # without a manager here that promise rests entirely on the
            # caller's own on_window checkpointing — say so, loudly, so a
            # job that never saves cannot silently preempt-loop at step 0
            import warnings

            warnings.warn(
                "preempted without checkpoint=: exiting "
                f"{hb.PREEMPT_EXIT_CODE} (budget-free relaunch) but drive "
                "saved NOTHING — progress since your last own checkpoint "
                "(e.g. from on_window) will be retrained after the "
                "relaunch", RuntimeWarning, stacklevel=2)
        if heartbeat:
            hb.write(step=self._step_count)
        raise SystemExit(hb.PREEMPT_EXIT_CODE)

    def _sentinel_check(self, sentinel, win, history, checkpoint,
                        resumable, stream, it, log_every, scaler=None):
        """Judge one fetched window and run the divergence-response
        ladder. Returns a replacement batch iterator when the response
        rewound or skipped the stream (rollback restarts it from the
        restored-and-advanced cursor), else ``None``.

        The verdict is deterministic from replicated device values, so
        every rank computes it identically; multi-process runs still
        cross-check through the jax.distributed coordination service (the
        PR-4 checkpoint-barrier transport) — a rank whose replicated
        arithmetic diverged is exactly the failure under supervision and
        must not roll back alone."""
        import warnings

        verdict = sentinel.observe(win)
        spiked = sentinel.agree_verdict(verdict["verdict"] == "spike")
        # health bookkeeping: every clean window credits the committed
        # checkpoints; a bad window resets their pending counts — a step
        # becomes a rollback target only FLAGS_sentinel_healthy_windows
        # clean windows after it was written
        if checkpoint is not None and hasattr(checkpoint, "note_window"):
            checkpoint.note_window(clean=not spiked,
                                   k=sentinel.healthy_windows)
        if not spiked:
            return None
        why, where = sentinel.describe(verdict)
        if sentinel.action == "raise":
            sentinel.raise_divergence(
                f"divergence detected ({why}) at {where}")
        warnings.warn(
            f"divergence sentinel: spike verdict ({why}) at {where} — "
            f"responding with FLAGS_sentinel_action={sentinel.action}",
            RuntimeWarning, stacklevel=3)
        if sentinel.action == "warn":
            return None
        if sentinel.action == "skip":
            # bad-window skip: assume the poisoned input region continues
            # and drop the NEXT window of batches untrained (the cursor
            # advances over them — they are consumed, never replayed).
            # The offending window's updates stay applied: without a
            # checkpoint there is nothing to rewind to
            if it is None:
                return None  # trailing window: no stream left to skip
            from ..core.flags import flag_value
            from ..core.exceptions import stall_guard

            dropped = 0
            # the drain pulls from the same loader/collective path as a
            # normal fetch — keep it under the stall guard, or a wedge
            # while draining would block forever (FLAGS_step_timeout_s)
            with stall_guard(float(flag_value("step_timeout_s", 0) or 0),
                             "sentinel skip-window drain"):
                try:
                    for _ in range(log_every):
                        next(it)
                        dropped += 1
                        if resumable is not None:
                            resumable.advance(1)
                except StopIteration:
                    pass
            if dropped:
                history["skipped_windows"] += 1
            return it if dropped else None
        # rollback: restore the last HEALTHY checkpoint and skip every
        # batch consumed since it, so the poisoned window is not replayed
        if checkpoint is None or resumable is None:
            sentinel.raise_divergence(
                "FLAGS_sentinel_action=rollback needs drive(checkpoint=a "
                "CheckpointManager, sampler=/data=a resumable stream); "
                f"got checkpoint={type(checkpoint).__name__}, "
                f"resumable={type(resumable).__name__}")
        healthy = checkpoint.latest_healthy_step()
        admit = sentinel.agree_rollback(healthy)
        if healthy is None:
            sentinel.raise_divergence(
                "no HEALTHY checkpoint to roll back to (a step is tagged "
                "healthy only after FLAGS_sentinel_healthy_windows clean "
                "windows pass beyond it — the spike hit before any "
                "checkpoint earned the tag)")
        sentinel.acquire_rollback(admit=admit)  # raises on exhaustion
        # restore model + this step's optimizer state — but NOT the
        # sampler: its cursor already sits just past the poisoned window
        # (one advance() per trained batch), which IS the skip — every
        # batch consumed since the healthy checkpoint is never replayed,
        # and the in-flight epoch keeps its recorded shuffle seed (a
        # restore-then-re-advance round trip would re-draw an unseeded
        # epoch seed and resume a DIFFERENT permutation than the one the
        # consumed batches came from)
        pre_scale = self._lr_scale
        checkpoint.auto_resume(model=self.model, optimizer=self,
                               scaler=scaler, step=healthy,
                               plan=self._plan)
        # checkpoints written past the divergence point hold poisoned
        # states — they must never win a latest_valid_step race against
        # the healthy restore point on a later crash-restart
        checkpoint.drop_steps_after(healthy)
        if sentinel.lr_cooldown < 1.0:
            # compound on top of the PRE-restore scale: repeated spikes
            # in the same region restore the same (pre-cooldown)
            # checkpoint, and cooling down after EACH rollback must keep
            # escalating — 0.5, 0.25, ... — not reset to 0.5 every time
            self._lr_scale = pre_scale * sentinel.lr_cooldown
        # the rewind puts the trajectory at an earlier, higher-loss point;
        # re-baseline the detector or the rollback itself reads as the
        # next spike (budget-draining rollback loop)
        sentinel.notify_rollback()
        history["rollbacks"] += 1
        _M_TRAIN_ROLLBACKS.inc(instance=self._stats_name)
        if it is None:
            # trailing window: the loop is already over — params, moments
            # and cursor are rolled back, and the NEXT drive()/epoch
            # continues from the restored position
            return None
        # restart the stream: drop the prefetcher's read-ahead (staged
        # past the rollback point) and begin a fresh pass that honors the
        # untouched cursor (already just past the poisoned window)
        if hasattr(stream, "reset"):
            stream.reset()
        new_it = iter(stream)
        if new_it is it:
            sentinel.raise_divergence(
                "rollback needs a re-iterable batch stream (a DataLoader "
                "or DevicePrefetcher), got a bare one-shot iterator")
        return new_it

    def _flush_window(self, window, action, protect, history, on_window,
                      stall_timeout=0, track_gnorm=False):
        """Fetch one deferred window (O(1) host round-trips) and replay the
        per-step guard bookkeeping that per-step fetch would have done.
        Returns the window dict handed to ``on_window`` (the divergence
        sentinel judges it). With ``track_gnorm`` the accumulator's
        grad-norm peak rides in the SAME stacked fetch as the losses —
        same host-sync count armed or not — and the device-side peak is
        re-zeroed for the next window. ``stall_timeout`` arms the stall
        guard over the device fetches ONLY — ``on_window`` (user code:
        checkpointing, logging) runs outside it, so a slow checkpoint save
        is never mistaken for a wedge."""
        import warnings

        import numpy as np

        from ..core.exceptions import stall_guard

        with stall_guard(stall_timeout, "window metric fetch"), \
                _obs_trace.span("train.fetch", cat="train",
                                args={"instance": self._stats_name,
                                      "steps": len(window)}):
            vals = [jnp.asarray(l, jnp.float32) for l, _ in window]
            if track_gnorm:
                vals.append(jnp.asarray(self._acc[3], jnp.float32))
            stacked = np.asarray(jnp.stack(vals))
            history["host_syncs"] += 1
            gnorm_peak = None
            if track_gnorm:
                gnorm_peak = float(stacked[-1])
                losses = stacked[:-1]
                # fresh zero for the next window's peak (host-side tuple
                # rebuild — no device round-trip)
                self._acc = self._acc[:3] + (jnp.float32(0.0),)
            else:
                losses = stacked
            finite = None
            if action != "none":
                finite = np.asarray(jnp.stack([f for _, f in window]))
                history["host_syncs"] += 1
        n_bad = 0
        if finite is not None:
            with _obs_trace.span("train.guard", cat="train",
                                 args={"instance": self._stats_name}):
                for ok in finite:
                    if ok:
                        self._guard["consecutive_skips"] = 0
                    else:
                        n_bad += 1
                        if action == "warn":
                            self._guard["warned"] += 1
                        if protect:
                            self._guard["skipped"] += 1
                            self._guard["consecutive_skips"] += 1
                            # device step did not advance
                            self._step_count -= 1
            if n_bad and action == "warn":
                warnings.warn(
                    f"non-finite loss/grads on {n_bad} step(s) in the last "
                    f"{len(window)}-step window — updates applied anyway "
                    "(FLAGS_check_nan_inf_action=warn, deferred fetch)",
                    stacklevel=3)
        history["loss"].extend(float(v) for v in losses)
        if protect:
            history["skipped"] += n_bad
        history["windows"] += 1
        from .sentinel import make_window

        win = make_window(losses, non_finite=n_bad,
                          step=history["steps"], gnorm_peak=gnorm_peak)
        if on_window is not None:
            with _obs_trace.span("train.checkpoint", cat="train",
                                 args={"instance": self._stats_name}):
                on_window(win)
        if n_bad and action == "raise":
            raise FloatingPointError(
                f"non-finite loss/grads on {n_bad} step(s) detected at the "
                "metric-fetch boundary; the updates were already discarded "
                "in-graph (FLAGS_check_nan_inf_action=raise, deferred "
                "fetch)")
        return win


def fused_train_step(model, optimizer, loss_fn=None, step_lr_scheduler=True,
                     shape_buckets=None, bucket_args=None, grad_scaler=None):
    """Build a fused (single-dispatch, donated) train step callable:
    ``step(*inputs) -> loss``. See FusedTrainStep — with the default
    ``step_lr_scheduler=True`` the step owns LR-scheduler stepping; do not
    also step it in the loop. ``shape_buckets`` pads inputs up to registered
    boundaries before dispatch (paddle.jit bucket semantics) so variable
    shapes cost O(buckets) compiles; ``bucket_args`` (positional indices /
    kw names) pins which inputs pad when the dominant-length auto rule is
    ambiguous. ``grad_scaler`` fuses dynamic loss scaling in-graph and arms
    the step anomaly guard (see FLAGS_check_nan_inf_action): a non-finite
    step is discarded and the scale backs off, all inside the single
    dispatch plus one host sync for the finite flag."""
    return FusedTrainStep(model, optimizer, loss_fn, step_lr_scheduler,
                          shape_buckets=shape_buckets,
                          bucket_args=bucket_args, grad_scaler=grad_scaler)
