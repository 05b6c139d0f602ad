"""Runtime flag registry — ``paddle.set_flags`` / ``paddle.get_flags``.

Reference: the self-hosted flag registry ``paddle/utils/flags_native.h:112``
(``PD_DEFINE_VARIABLE``) with ~120 exported flags in
``paddle/phi/core/flags.cc``, env-overridable as ``FLAGS_*`` and settable via
``paddle.set_flags``.

Here flags are plain Python state consulted by the dispatch layer and
subsystems. Registered flags are the ones with real effect in this framework;
reference flags that govern machinery XLA owns (allocator strategy, cudnn
knobs, executor toggles) are registered as accepted-but-inert so reference
scripts keep running, and marked ``inert=True`` for honesty.
"""

from __future__ import annotations

import os

__all__ = ["set_flags", "get_flags", "register_flag", "flag_value"]


class _Flag:
    __slots__ = ("name", "default", "type", "help", "inert", "on_change",
                 "value")

    def __init__(self, name, default, help="", inert=False, on_change=None):
        self.name = name
        self.default = default
        self.type = type(default)
        self.help = help
        self.inert = inert
        self.on_change = on_change
        self.value = self._from_env()

    def _from_env(self):
        env = os.environ.get(f"FLAGS_{self.name}")
        if env is None:
            return self.default
        return self._coerce(env)

    def _coerce(self, v):
        if self.type is bool:
            if isinstance(v, str):
                return v.lower() in ("1", "true", "yes", "on")
            return bool(v)
        return self.type(v)

    def set(self, v):
        old = self.value
        self.value = self._coerce(v)
        if self.on_change is not None:
            try:
                self.on_change(self.value)
            except BaseException:
                self.value = old  # a rejecting validator must not leave
                raise             # the invalid value installed


_REGISTRY: dict[str, _Flag] = {}


def register_flag(name, default, help="", inert=False, on_change=None):
    """Register a flag (PD_DEFINE_VARIABLE analog). Env FLAGS_<name>
    overrides the default at registration time (and fires on_change, so
    env-set flags get the same side effects as paddle.set_flags)."""
    name = name.removeprefix("FLAGS_")
    f = _Flag(name, default, help, inert, on_change)
    _REGISTRY[name] = f
    if on_change is not None and os.environ.get(f"FLAGS_{name}") is not None:
        on_change(f.value)
    return f


def _lookup(name):
    key = name.removeprefix("FLAGS_")
    if key not in _REGISTRY:
        raise ValueError(
            f"unknown flag {name!r}; registered flags: "
            f"{sorted(_REGISTRY)}")
    return _REGISTRY[key]


def set_flags(flags):
    """paddle.set_flags({'FLAGS_check_nan_inf': 1})."""
    if not isinstance(flags, dict):
        raise TypeError("set_flags takes a dict of {flag_name: value}")
    for k, v in flags.items():
        _lookup(k).set(v)


def get_flags(flags):
    """paddle.get_flags('FLAGS_x') or (['FLAGS_x', ...]) -> dict."""
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for k in flags:
        f = _lookup(k)
        key = k if k.startswith("FLAGS_") else f"FLAGS_{f.name}"
        out[key] = f.value
    return out


def flag_value(name, default=None):
    """Internal fast read used by dispatch/subsystems."""
    f = _REGISTRY.get(name.removeprefix("FLAGS_"))
    return f.value if f is not None else default


# ---- flags with real effect ------------------------------------------------

def _sync_debug_nans(_):
    # bridge into jax for traced/jit code (covers to_static + fused steps);
    # only in raise mode (level 0) — debug_nans cannot warn-and-continue
    import jax

    enabled = bool(flag_value("check_nan_inf", False)) and \
        int(flag_value("check_nan_inf_level", 0)) == 0
    try:
        jax.config.update("jax_debug_nans", enabled)
    except Exception:
        pass


register_flag(
    "check_nan_inf", False,
    help="scan every eager op's outputs for NaN/Inf and raise with the op "
         "name (ref paddle/phi/core/flags.cc:74); also enables "
         "jax_debug_nans for compiled code",
    on_change=_sync_debug_nans)
register_flag(
    "check_nan_inf_level", 0,
    help="0: raise on NaN/Inf; 1: warn only (ref flags.cc:88 levels)",
    on_change=_sync_debug_nans)
register_flag(
    "benchmark", False,
    help="block on every eager op (device sync) for accurate per-op timing")

register_flag(
    "ckpt_save_retries", 3,
    help="retries for transient OSErrors on checkpoint writes (paddle.save, "
         "distributed shard writes, LocalFS renames) with exponential "
         "backoff + jitter; 0 disables retrying")
register_flag(
    "ckpt_quarantine_keep", -1,
    help="CheckpointManager retention bound on *.replaced.* quarantine "
         "dirs that still hold the only committed copy of their step "
         "(redundant quarantines are always swept): -1 (default) keeps "
         "all — the PR-2 never-delete behavior — while N >= 0 keeps only "
         "the N newest such quarantines. N >= 1 is recommended when "
         "bounding: 0 sweeps even the newest, which can discard the only "
         "committed copy of a step whose re-save keeps getting torn")


def _validate_nan_action(v):
    if v not in ("none", "warn", "skip", "raise"):
        raise ValueError(
            f"FLAGS_check_nan_inf_action must be one of "
            f"none/warn/skip/raise, got {v!r}")


def _validate_positive_int(name):
    def check(v):
        if int(v) < 1:
            raise ValueError(f"FLAGS_{name} must be >= 1, got {v!r}")
    return check


register_flag(
    "prefetch_depth", 2,
    help="DevicePrefetcher double-buffer depth: how many batches the "
         "transfer thread stages ahead (host bucket-pad + device_put) "
         "while the device computes the current one; >= 1. Depth 2 is the "
         "classic double buffer — batch N+1 transfers during batch N's "
         "compute",
    on_change=_validate_positive_int("prefetch_depth"))
register_flag(
    "metric_fetch_interval", 10,
    help="default log_every for FusedTrainStep.drive: loss/guard metrics "
         "accumulate on device and are fetched every N steps (each fetch "
         "is a blocking device-to-host round-trip that drains the "
         "dispatch queue; N=1 restores per-step fetch)",
    on_change=_validate_positive_int("metric_fetch_interval"))

def _validate_non_negative(name):
    def check(v):
        if float(v) < 0:
            raise ValueError(f"FLAGS_{name} must be >= 0, got {v!r}")
    return check


# ---- supervision / elastic restart flags -----------------------------------
# The launcher-side flags are read by the supervisor PROCESS (the
# `python -m paddle_tpu.distributed.launch` parent), so set them via the
# FLAGS_* environment variable of the launch command — paddle.set_flags in
# the training script runs in a different process and cannot reach them.

register_flag(
    "worker_hang_timeout_s", 0.0,
    help="launcher watchdog: kill + restart the local worker group when the "
         "stalest worker heartbeat (written by FusedTrainStep.drive at every "
         "metric-fetch window boundary) is older than this many seconds; "
         "0 disables hang detection. Launcher-side: set via env on the "
         "launch command",
    on_change=_validate_non_negative("worker_hang_timeout_s"))
register_flag(
    "step_timeout_s", 0.0,
    help="in-process stall watchdog: FusedTrainStep.drive arms a wall-clock "
         "timer around its fetch points and raises TrainStallError when a "
         "step makes no progress for this many seconds (a wedged collective "
         "surfaces as a crash the supervisor can restart); 0 disables",
    on_change=_validate_non_negative("step_timeout_s"))
register_flag(
    "restart_window_s", 3600.0,
    help="rolling window of the launcher's leaky-bucket restart budget: "
         "--max_restart crash restarts are allowed per this many seconds "
         "(old crashes age out instead of consuming budget forever); "
         "0 makes the budget lifetime-scoped. Launcher-side env flag",
    on_change=_validate_non_negative("restart_window_s"))
register_flag(
    "restart_backoff_s", 1.0,
    help="base delay of the launcher's exponential restart backoff "
         "(doubled per crash currently in the budget window, capped at "
         "30s); clean preemptions relaunch immediately. Launcher-side "
         "env flag",
    on_change=_validate_non_negative("restart_backoff_s"))
register_flag(
    "worker_term_grace_s", 10.0,
    help="grace period between the launcher's SIGTERM and SIGKILL when "
         "killing a worker group, and the wait for remaining workers to "
         "finish their preemption checkpoint after one exits preempted. "
         "Launcher-side env flag",
    on_change=_validate_non_negative("worker_term_grace_s"))

# ---- divergence sentinel flags ---------------------------------------------
# Configure the TrainingSentinel layer (paddle.incubate.TrainingSentinel):
# loss-spike / grad-explosion detection at metric-fetch window boundaries in
# FusedTrainStep.drive (zero added per-step host syncs — detection rides the
# deferred-window fetch) and its graceful-degradation response ladder.

def _validate_sentinel_action(v):
    if v not in ("none", "warn", "skip", "rollback", "raise"):
        raise ValueError(
            f"FLAGS_sentinel_action must be one of "
            f"none/warn/skip/rollback/raise, got {v!r}")


def _validate_unit_interval(name):
    def check(v):
        if not (0.0 < float(v) < 1.0):
            raise ValueError(f"FLAGS_{name} must be in (0, 1), got {v!r}")
    return check


def _validate_unit_interval_inclusive_one(v):
    if not (0.0 < float(v) <= 1.0):
        raise ValueError(
            f"FLAGS_sentinel_lr_cooldown must be in (0, 1], got {v!r}")


register_flag(
    "sentinel_action", "none",
    help="divergence-sentinel response when a training window is judged a "
         "spike: 'none' (sentinel off), 'warn' (RuntimeWarning, continue), "
         "'skip' (warn + drop the next window of batches — assumes a "
         "contiguous poisoned input region; the bad window's updates stay "
         "applied), 'rollback' (restore model+optimizer+sampler from the "
         "last HEALTHY checkpoint, skip the offending batches, optional LR "
         "cooldown, budgeted), 'raise' (typed TrainDivergenceError at the "
         "first verdict)",
    on_change=_validate_sentinel_action)
register_flag(
    "sentinel_zscore", 6.0,
    help="spike threshold: a window whose mean loss sits more than this "
         "many EMA standard deviations ABOVE the running EMA mean is a "
         "spike (one-sided; armed after FLAGS_sentinel_warmup_windows "
         "clean windows); <= 0 disables the z-score detector",
)
register_flag(
    "sentinel_ema_beta", 0.9,
    help="EMA decay for the sentinel's running mean/variance of window "
         "mean losses (higher = longer memory, slower to absorb genuine "
         "regime changes); spike windows never update the EMA, so one "
         "spike cannot normalize the next",
    on_change=_validate_unit_interval("sentinel_ema_beta"))
register_flag(
    "sentinel_warmup_windows", 3,
    help="clean windows the sentinel observes before the z-score detector "
         "arms (the EMA baseline must exist before deviations from it mean "
         "anything); the grad-norm ceiling and patience detectors are "
         "active from the first window",
    on_change=_validate_positive_int("sentinel_warmup_windows"))
register_flag(
    "sentinel_grad_norm_ceiling", 0.0,
    help="absolute ceiling on the window's peak global grad norm (tracked "
         "device-side in the fused step's donated accumulator — no extra "
         "per-step host sync): any window whose peak exceeds it is a "
         "spike; 0 disables and skips the in-graph norm reduction when "
         "grad clipping is not already computing it",
    on_change=_validate_non_negative("sentinel_grad_norm_ceiling"))
register_flag(
    "sentinel_patience", 0,
    help="divergence-trend detector: this many CONSECUTIVE windows of "
         "strictly rising mean loss is a spike verdict even when no "
         "single window clears the z-score bar (slow divergence); 0 "
         "disables",
    on_change=_validate_non_negative("sentinel_patience"))
register_flag(
    "sentinel_rollback_budget", 3,
    help="leaky-bucket cap on sentinel rollbacks: at most this many "
         "rollbacks per rolling FLAGS_sentinel_budget_window_s window "
         "(mirroring the launcher's RestartBudget); exhaustion raises "
         "TrainDivergenceError carrying the spike history",
    on_change=_validate_positive_int("sentinel_rollback_budget"))
register_flag(
    "sentinel_budget_window_s", 3600.0,
    help="rolling window of the sentinel's rollback budget (old rollbacks "
         "age out instead of consuming budget forever); 0 makes the "
         "budget lifetime-scoped",
    on_change=_validate_non_negative("sentinel_budget_window_s"))
register_flag(
    "sentinel_lr_cooldown", 1.0,
    help="learning-rate multiplier applied after each sentinel rollback "
         "(the restored step's LR scale times this; e.g. 0.5 halves the "
         "LR past the spike region); 1.0 disables. Applied as a scale on "
         "top of the optimizer's own schedule, persisted in the fused "
         "step's state dict",
    on_change=_validate_unit_interval_inclusive_one)
register_flag(
    "sentinel_healthy_windows", 2,
    help="clean windows that must pass beyond a committed checkpoint step "
         "before CheckpointManager tags it HEALTHY (rollback only ever "
         "targets healthy steps, so a checkpoint written during an "
         "undetected spike cannot become a rollback target); a bad window "
         "resets every pending count",
    on_change=_validate_positive_int("sentinel_healthy_windows"))

register_flag(
    "check_nan_inf_action", "none",
    help="FusedTrainStep step-guard action when loss/grads go non-finite: "
         "'none' (guard off, no per-step host sync), 'warn' (warn and apply "
         "the update), 'skip' (discard the update, keep params/moments, "
         "back off an attached GradScaler), 'raise' (discard the update and "
         "raise FloatingPointError)",
    on_change=_validate_nan_action)

# ---- accepted-but-inert reference flags (XLA owns this machinery) ----------

for _name, _default in [
    ("allocator_strategy", "auto_growth"),
    ("fraction_of_gpu_memory_to_use", 0.92),
    ("cudnn_deterministic", False),
    ("embedding_deterministic", 0),
    ("conv_workspace_size_limit", 512),
    ("cudnn_exhaustive_search", False),
    ("use_pinned_memory", True),
    ("init_allocated_mem", False),
    ("eager_delete_tensor_gb", 0.0),
]:
    register_flag(_name, _default, inert=True,
                  help="accepted for reference-script compatibility; the "
                       "equivalent machinery is owned by XLA on TPU")
