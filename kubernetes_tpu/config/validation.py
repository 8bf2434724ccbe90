"""Configuration validation — the reference's apis/config/validation
(validation.go ValidateKubeSchedulerConfiguration) re-derived for this
config surface: scalar ranges, feature gates, profile uniqueness +
queue-sort uniformity (profile/profile.go:47-66 NewMap), per-profile
plugin existence/weights, scoring-strategy args, and extender entries."""

from __future__ import annotations

from kubernetes_tpu.config.types import (
    PLUGIN_SET_FIELDS as _POINTS,
    SchedulerConfiguration,
)

_FIT_STRATEGIES = ("LeastAllocated", "MostAllocated",
                   "RequestedToCapacityRatio")


def _validate_fit_args(prefix: str, args: dict, errs: list[str]) -> None:
    """NodeResourcesFitArgs (validation/validation_pluginargs.go); key
    spelling matches what Framework.fit_scoring actually reads
    (snake_case, runtime.py)."""
    ss = args.get("scoring_strategy")
    if ss is None:
        return
    stype = ss.get("type", "LeastAllocated")
    if stype not in _FIT_STRATEGIES:
        errs.append(f"{prefix}: scoring_strategy.type {stype!r} must be one "
                    f"of {', '.join(_FIT_STRATEGIES)}")
    shape = (ss.get("requested_to_capacity_ratio") or {}).get("shape", [])
    if stype == "RequestedToCapacityRatio" and not shape:
        errs.append(f"{prefix}: RequestedToCapacityRatio requires a "
                    "non-empty shape")
    last = None
    for pt in shape:
        u, s = pt.get("utilization", 0), pt.get("score", 0)
        if not 0 <= u <= 100:
            errs.append(f"{prefix}: shape utilization {u} not in [0, 100]")
        if not 0 <= s <= 10:
            errs.append(f"{prefix}: shape score {s} not in [0, 10]")
        if last is not None and u <= last:
            errs.append(f"{prefix}: shape utilization must be strictly "
                        "increasing")
        last = u


def _validate_extenders(cfg: SchedulerConfiguration,
                        errs: list[str]) -> None:
    """validation.go validateExtenders: url required; weight must be
    positive only when a prioritize verb makes it meaningful."""
    for i, e in enumerate(cfg.extenders):
        prefix = f"extenders[{i}]"
        if not getattr(e, "url_prefix", ""):
            errs.append(f"{prefix}: url_prefix is required")
        if (getattr(e, "prioritize_verb", "")
                and getattr(e, "weight", 1.0) <= 0):
            errs.append(f"{prefix}: weight must be positive")
        if getattr(e, "timeout_seconds", 1.0) <= 0:
            errs.append(f"{prefix}: timeout_seconds must be positive")


def validate_config(cfg: SchedulerConfiguration,
                    registry: dict | None = None) -> list[str]:
    """Returns a list of error strings (empty = valid)."""
    errs: list[str] = []
    if cfg.batch_size <= 0:
        errs.append("batch_size must be positive")
    if cfg.binding_workers <= 0:
        errs.append("binding_workers must be positive")
    if cfg.node_capacity <= 0 or cfg.pod_table_capacity <= 0:
        errs.append("mirror capacities must be positive")
    if cfg.flight_recorder_capacity < 0:
        errs.append("flight_recorder_capacity must be >= 0 (0 disables)")
    if getattr(cfg, "trace_export_max_bytes", 0) < 0:
        errs.append("trace_export_max_bytes must be >= 0 (0 = unbounded)")
    if not 0 <= getattr(cfg, "tie_break_seed", 0) < 2 ** 32:
        errs.append("tie_break_seed must fit in uint32")
    from kubernetes_tpu.config.types import KNOWN_FEATURE_GATES

    for gate in cfg.feature_gates:
        if gate not in KNOWN_FEATURE_GATES:
            errs.append(f"unknown feature gate {gate!r}")
    if cfg.pod_initial_backoff_seconds <= 0:
        errs.append("pod_initial_backoff_seconds must be positive")
    if cfg.pod_max_backoff_seconds < cfg.pod_initial_backoff_seconds:
        errs.append("pod_max_backoff_seconds must be >= initial backoff")
    if (cfg.percentage_of_nodes_to_score is not None
            and not 0 <= cfg.percentage_of_nodes_to_score <= 100):
        errs.append("percentage_of_nodes_to_score must be in [0, 100]")
    if not cfg.profiles:
        errs.append("at least one profile is required")
    names = [p.scheduler_name for p in cfg.profiles]
    if len(set(names)) != len(names):
        errs.append("duplicate profile schedulerName")
    for p in cfg.profiles:
        if not p.scheduler_name:
            errs.append("profile schedulerName must be non-empty")
    if registry is not None and len(cfg.profiles) > 1:
        # queue-sort uniformity: one shared queue across profiles requires
        # one sort order (profile.go:57 "different queue sort plugins");
        # resolved with the runtime's own MultiPoint expansion so disabled
        # sets and custom sorts are honored
        from kubernetes_tpu.framework.runtime import expand_point

        sorts = {tuple(name for name, _ in
                       expand_point(prof, registry, "queue_sort"))
                 for prof in cfg.profiles}
        if len(sorts) > 1:
            errs.append("all profiles must use the same queueSort plugin set")
    _validate_extenders(cfg, errs)
    if registry is not None:
        for prof in cfg.profiles:
            for pt in _POINTS:
                for pl in getattr(prof.plugins, pt).enabled:
                    if pl.name not in registry:
                        errs.append(
                            f"profile {prof.scheduler_name}: unknown plugin "
                            f"{pl.name}")
                    if pl.weight < 0:
                        errs.append(f"plugin {pl.name}: negative weight")
                    if pl.weight > 100 and pt in ("score", "multi_point"):
                        # MaxWeight guard (validation.go); weight is inert
                        # on every other point (types.py Plugin)
                        errs.append(f"plugin {pl.name}: weight > 100")
            fit_args = prof.plugin_config.get("NodeResourcesFit")
            if fit_args:
                _validate_fit_args(
                    f"profile {prof.scheduler_name}: NodeResourcesFit",
                    fit_args, errs)
    return errs
