"""Component config file loading.

The slice of cmd/kube-scheduler's options/config plumbing
(app/server.go:89 Setup + apis/config loading) this build needs: a JSON
(or YAML, when available) KubeSchedulerConfiguration-shaped document maps
onto SchedulerConfiguration — profiles with per-point plugin sets,
plugin args, extenders, and the TPU-build knobs.
"""

from __future__ import annotations

import json

from kubernetes_tpu.config.types import (
    Plugin,
    Plugins,
    PluginSet,
    SchedulerConfiguration,
    SchedulerProfile,
    default_plugins,
)
from kubernetes_tpu.config.types import PLUGIN_SET_FIELDS as _POINTS
from kubernetes_tpu.extender import ExtenderConfig


def _plugin_set(doc: dict) -> PluginSet:
    def entries(items):
        return [Plugin(name=e["name"], weight=e.get("weight", 0.0))
                for e in items or []]

    return PluginSet(enabled=entries(doc.get("enabled")),
                     disabled=entries(doc.get("disabled")))


def _profile(doc: dict) -> SchedulerProfile:
    plugins = default_plugins()
    pdoc = doc.get("plugins") or {}
    if pdoc.get("multi_point", {}).get("replace_defaults"):
        plugins = Plugins()
    for point in _POINTS:
        if point in pdoc:
            ps = _plugin_set(pdoc[point])
            cur = getattr(plugins, point)
            cur.enabled.extend(ps.enabled)
            cur.disabled.extend(ps.disabled)
    cfg = {}
    for entry in doc.get("plugin_config") or []:
        cfg[entry["name"]] = entry.get("args") or {}
    return SchedulerProfile(
        scheduler_name=doc.get("scheduler_name", "default-scheduler"),
        plugins=plugins, plugin_config=cfg)


def config_from_dict(doc: dict) -> SchedulerConfiguration:
    cfg = SchedulerConfiguration()
    for key in ("percentage_of_nodes_to_score",
                "pod_initial_backoff_seconds", "pod_max_backoff_seconds",
                "async_binding", "binding_workers", "batch_size",
                "node_capacity", "pod_table_capacity",
                "flight_recorder_capacity", "trace_export_path",
                "trace_export_max_bytes", "trace_export_features",
                "trace_export_alts", "tie_break_seed"):
        if key in doc:
            setattr(cfg, key, doc[key])
    profiles = [_profile(p) for p in doc.get("profiles") or []]
    if not profiles:
        profiles = [SchedulerProfile(plugins=default_plugins())]
    cfg.profiles = profiles
    cfg.feature_gates = dict(doc.get("feature_gates") or {})
    cfg.extenders = [ExtenderConfig(
        url_prefix=e["url_prefix"],
        filter_verb=e.get("filter_verb", ""),
        prioritize_verb=e.get("prioritize_verb", ""),
        bind_verb=e.get("bind_verb", ""),
        preempt_verb=e.get("preempt_verb", ""),
        weight=e.get("weight", 1.0),
        managed_resources=e.get("managed_resources") or [],
        ignorable=e.get("ignorable", False),
        node_cache_capable=e.get("node_cache_capable", False),
        timeout_seconds=e.get("timeout_seconds", 5.0))
        for e in doc.get("extenders") or []]
    return cfg


def load_config(path: str) -> SchedulerConfiguration:
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        try:
            import yaml

            doc = yaml.safe_load(text)
        except ImportError as e:
            raise ValueError(
                f"{path}: not valid JSON and no YAML support") from e
    return config_from_dict(doc or {})
