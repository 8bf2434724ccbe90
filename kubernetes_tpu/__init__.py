"""kubernetes_tpu — a TPU-native pod-scheduling framework.

A brand-new scheduling framework with the capabilities of the Kubernetes
kube-scheduler (reference: /root/reference, pkg/scheduler), re-designed
TPU-first:

- Host (Python + C-extension hot paths) owns API ingestion (list/watch),
  the three-tier pending queue with queueing hints, the authoritative
  generation-tracked cluster cache, preemption orchestration, binding I/O,
  metrics and config.
- Device (JAX/XLA on TPU) owns the per-cycle math: Filter predicates,
  Score, normalization, weighted aggregation and masked argmax over a dense
  ``nodes x features`` tensor resident in HBM, with pending pods batched
  along a second axis so one XLA launch schedules a whole batch
  (as-if-serial semantics via a commit loop over the batch's rows).

Layer map (mirrors SURVEY.md section 1, scheduler-internal layering):

    kubernetes_tpu.api        — object model (Pod/Node/...), quantities, labels
    kubernetes_tpu.utils      — interner, clock, misc
    kubernetes_tpu.backend    — cache, snapshot, node_tree, queue, heap, mirror
    kubernetes_tpu.framework  — extension points, CycleState, runtime, registry
    kubernetes_tpu.plugins    — in-tree plugins (device kernels + host logic)
    kubernetes_tpu.ops        — the JAX kernels behind the device plugins
    kubernetes_tpu.models     — the flagship batched scheduling pipeline
    kubernetes_tpu.parallel   — mesh/sharding for the node axis (ICI scale-out)
    kubernetes_tpu.config     — SchedulerConfiguration types/defaults/validation
    kubernetes_tpu.scheduler  — the Scheduler: event handlers + scheduling loop
    kubernetes_tpu.hub        — in-process API hub (list/watch/bind) for tests+bench
"""

__version__ = "0.1.0"

# The staged public API (the reference publishes its plugin-facing types as
# staging/src/k8s.io/kube-scheduler): everything an out-of-tree plugin,
# embedding host, or operator needs, importable from the package root.
# Heavy modules (jax-backed) load lazily so `import kubernetes_tpu` stays
# cheap for config-only consumers.

_PUBLIC = {
    # runtime surface
    "Scheduler": ("kubernetes_tpu.scheduler", "Scheduler"),
    "Hub": ("kubernetes_tpu.hub", "Hub"),
    "ServingEndpoints": ("kubernetes_tpu.serving", "ServingEndpoints"),
    "LeaderElector": ("kubernetes_tpu.leaderelection", "LeaderElector"),
    "HTTPExtender": ("kubernetes_tpu.extender", "HTTPExtender"),
    "ExtenderConfig": ("kubernetes_tpu.extender", "ExtenderConfig"),
    # configuration
    "SchedulerConfiguration": ("kubernetes_tpu.config.types",
                               "SchedulerConfiguration"),
    "SchedulerProfile": ("kubernetes_tpu.config.types", "SchedulerProfile"),
    "default_config": ("kubernetes_tpu.config.types", "default_config"),
    "load_config": ("kubernetes_tpu.config.load", "load_config"),
    "validate_config": ("kubernetes_tpu.config.validation",
                        "validate_config"),
    # plugin authoring (framework/interface.go's staged types)
    "Status": ("kubernetes_tpu.framework.interface", "Status"),
    "Code": ("kubernetes_tpu.framework.interface", "Code"),
    "ClusterEvent": ("kubernetes_tpu.framework.interface", "ClusterEvent"),
    "QueueingHint": ("kubernetes_tpu.framework.interface", "QueueingHint"),
    "PreFilterPlugin": ("kubernetes_tpu.framework.interface",
                        "PreFilterPlugin"),
    "FilterPlugin": ("kubernetes_tpu.framework.interface", "FilterPlugin"),
    "PostFilterPlugin": ("kubernetes_tpu.framework.interface",
                         "PostFilterPlugin"),
    "ScorePlugin": ("kubernetes_tpu.framework.interface", "ScorePlugin"),
    "ReservePlugin": ("kubernetes_tpu.framework.interface",
                      "ReservePlugin"),
    "PermitPlugin": ("kubernetes_tpu.framework.interface", "PermitPlugin"),
    "PreBindPlugin": ("kubernetes_tpu.framework.interface",
                      "PreBindPlugin"),
    "BindPlugin": ("kubernetes_tpu.framework.interface", "BindPlugin"),
    "PostBindPlugin": ("kubernetes_tpu.framework.interface",
                       "PostBindPlugin"),
    "PluginDescriptor": ("kubernetes_tpu.plugins.registry",
                         "PluginDescriptor"),
    "in_tree_registry": ("kubernetes_tpu.plugins.registry",
                         "in_tree_registry"),
}

__all__ = sorted(_PUBLIC) + ["api"]


def __getattr__(name: str):
    import importlib

    if name == "api":
        value = importlib.import_module("kubernetes_tpu.api")
        globals()[name] = value
        return value
    entry = _PUBLIC.get(name)
    if entry is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    mod = importlib.import_module(entry[0])
    value = getattr(mod, entry[1])
    globals()[name] = value
    return value

