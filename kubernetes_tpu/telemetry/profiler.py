"""DeviceProfiler: XLA-compile / launch-walltime / HBM-footprint meter.

Why the device path stalls is exactly what the flight recorder's phase
timings cannot say: ``device_launch`` covers compile time, queue wait,
and execution indistinguishably. This instrument attributes it:

* **Compiles per bucket shape.** Every launch computes its *shape key*
  (batch bucket, node/pod capacity buckets, topology domain bucket,
  group bucket, commit mode, optional-term flags). The jitted entry
  point's executable-cache size (``pipeline.launch_cache_size()``) is
  read after each launch: growth = one real XLA compile, attributed to
  this launch's shape and to the TRANSITION from the previous shape —
  re-bucket churn (a capacity field doubled) vs batch-bucket drift vs a
  flag flip. A compile whose shape was already seen is counted
  ``unattributed`` — the signal that something OUTSIDE the tracked key
  is forcing recompiles.
* **Per-launch walltime** per shape (count/total/max), so "one shape is
  slow" and "one shape keeps recompiling" read differently.
* **Pods and fill** per shape: the pods its launches carried, and their
  share of the rows the program was compiled for (launches times the
  batch bucket). A launch costs its full width whatever it carries, so a
  shape that runs 1% full is where a narrower program would pay.
* **Scan steps** per shape: the commit-scan steps its serial launches
  ran (the scan stops after the last row that carries a pod,
  ``pipeline.scan_steps_for``), and the share of the rows launched that
  it skipped. Near 0 in a backlog, near 1 where launches run almost
  empty: what the scan no longer pays for of the width above.
* **Table blocks** per shape: the pod-table blocks phase 1b of its
  topology launches read (the passes stop after the last block that
  holds a live slot, ``pipeline.table_blocks_for``), and the share of the
  blocks launched that they skipped: near 1 where a big table holds few
  pods, near 0 where it is full. What the table passes no longer pay for
  of the table's capacity.
* **Live buffer bytes** — the HBM footprint of what the scheduler keeps
  resident: the nodes×resources cluster tensors, the per-batch pod
  tensors, the dense DRA inventories (``.nbytes`` over the pytrees;
  metadata reads, no device sync).

Surfaced as ``scheduler_device_*`` metrics, the ``device_compile``
flight-recorder view phase (a compiling launch's walltime, double-
counted next to ``device_launch`` on purpose — the attribution view
discipline from the DRA phases), and the ``device`` block of
``/debug/trace`` and of a ``perf/harness.run_workload`` result.
"""

from __future__ import annotations

from typing import Optional

# the capacity fields whose growth is re-bucket churn (mirror._grow
# doubles one of these and rebuilds; kernels recompile once per bucket)
_CAP_FIELDS = ("nodes", "pods", "pod_labels", "node_labels", "domains",
               "ext_resources", "domain_cap")


# learned, with_feats and alts are always False: benchmark code pins them
def shape_key(caps, b_bucket: int, enable_topology: bool, d_cap,
              g_cap: int, serial_scan: bool, dra: bool, learned: bool,
              with_feats: bool, gang: int = 0,
              alts: bool = False, soft: bool = False,
              active: tuple[str, ...] = ()) -> tuple:
    """The launch's compile-relevant shape: static jit args + input
    shape buckets, as a flat hashable tuple. ``gang`` is the gang-pack
    launch's gang-row bucket (0 for the normal scheduling launch) — a
    gang-shape recompile attributes to its own row instead of landing
    in "unattributed". ``soft`` is the topo_soft
    static flag (the reduced soft-topology program);
    ``active`` the launch features compiled into the program
    (``LaunchSpec.active``: "nodeaffinity", "taints", ...), last, so a
    key built without it is the older tuple with ("active", ())
    appended."""
    cap_t = tuple((f, getattr(caps, f)) for f in _CAP_FIELDS
                  if hasattr(caps, f))
    return (("b", b_bucket), ("topo", bool(enable_topology)),
            ("d_cap", d_cap), ("g_cap", g_cap),
            ("serial", bool(serial_scan)), ("dra", bool(dra)),
            ("learned", bool(learned)), ("feats", bool(with_feats)),
            ("gang", gang), ("alts", bool(alts)), ("soft", bool(soft)),
            *cap_t, ("active", tuple(active)))


def shape_label(shape: tuple) -> str:
    """The shape as /debug/trace and the fill gauge name it: the batch
    and capacity buckets, and what tells one topology program from
    another (the domain bucket, the serial scan, the soft-only scan)."""
    d = dict(shape)
    base = (f"b={d.get('b')} nodes={d.get('nodes')} "
            f"pods={d.get('pods')} topo={int(d.get('topo', 0))} "
            f"d_cap={d.get('d_cap')} serial={int(d.get('serial', 0))} "
            f"soft={int(d.get('soft', 0))} dra={int(d.get('dra', 0))}")
    gang = d.get("gang", 0)
    return f"{base} gang={gang}" if gang else base


def _rows(shape: tuple, rec: dict) -> int:
    """Rows the shape's launches were compiled for: launches times the
    batch bucket."""
    return rec["launches"] * (dict(shape).get("b") or 0)


def _fill(shape: tuple, rec: dict) -> float:
    """Pods carried over rows launched, 0.0 to 1.0."""
    rows = _rows(shape, rec)
    return rec["pods"] / rows if rows else 0.0


def _steps_skipped(shape: tuple, rec: dict) -> float:
    """Share of the rows launched that the commit scan did not step
    over, 0.0 to 1.0 (0.0 for a shape that runs no scan, an auction or a
    gang pack: nothing to skip)."""
    d = dict(shape)
    rows = _rows(shape, rec)
    if not rows or not d.get("serial") or d.get("gang"):
        return 0.0
    return 1.0 - rec["steps"] / rows


def _table_size(shape: tuple) -> int:
    """Blocks of the shape's pod table that a topology launch could read
    (0 for a shape without topology, which reads no table)."""
    from kubernetes_tpu.models.pipeline import table_blocks_for

    d = dict(shape)
    pt = d.get("pods") or 0
    return table_blocks_for(pt, pt) if d.get("topo") and pt else 0


def _table_skipped(shape: tuple, rec: dict) -> float:
    """Share of the pod-table blocks launched (launches times the blocks
    of the shape's table) that phase 1b did not read, 0.0 to 1.0 (0.0 for
    a shape without topology)."""
    blocks = rec["launches"] * _table_size(shape)
    return 1.0 - rec["table_blocks"] / blocks if blocks else 0.0


def _diff_cause(prev: Optional[tuple], cur: tuple) -> str:
    """Attribute a compile to what changed since the previous launch."""
    if prev is None:
        return "first"
    changed = {k for (k, v) in cur} - {k for (k, v) in prev}
    changed |= {k for (k, v) in cur if dict(prev).get(k) != v}
    if changed & set(_CAP_FIELDS):
        return "rebucket"                 # capacity growth recompile
    if "gang" in changed:
        return "gang"                     # gang-pack bucket transition
    if "b" in changed:
        return "batch_bucket"             # pod-batch bucket transition
    if changed & {"topo", "d_cap", "g_cap"}:
        return "topology_bucket"
    if changed:
        return "flags"                    # dra/commit mode
    return "unattributed"                 # same shape, cache still grew


class DeviceProfiler:
    """Per-scheduler launch profiler. Single-threaded like the flight
    recorder (note_launch runs on the scheduling-loop thread only);
    readers (`/debug/trace`, `perf/harness.run_workload`) take cheap
    snapshots."""

    MAX_COMPILE_EVENTS = 256              # bounded ring discipline (PR 4)

    def __init__(self, metrics=None, cache_size_fn=None,
                 now=None):
        import time

        if cache_size_fn is None:
            from kubernetes_tpu.models.pipeline import launch_cache_size
            cache_size_fn = launch_cache_size
        self._cache_size_fn = cache_size_fn
        self._metrics = metrics
        self._now = now or time.time
        # baseline BEFORE any of this scheduler's launches: warm cache
        # entries from an earlier run in this process are not ours
        self._last_cache: int = cache_size_fn()
        self._last_shape: Optional[tuple] = None
        self.launches = 0
        self.compiles = 0
        self.compile_causes: dict[str, int] = {}
        self.compile_events: list[dict] = []   # ring, newest last
        # shape -> {"launches", "pods", "steps", "table_blocks", "compiles",
        #           "walltime_s", "max_s"}
        self.shapes: dict[tuple, dict] = {}
        self.buffer_bytes: dict[str, int] = {}

    # ------------- recording (loop thread) -------------

    def note_launch(self, shape: tuple, pods: int = 0,
                    steps: Optional[int] = None,
                    table_blocks: Optional[int] = None) -> bool:
        """Record one dispatched launch that carried ``pods`` rows of its
        batch bucket (pods; gang units for a gang-pack launch), where it
        ran the serial commit scan the ``steps`` the scan took
        (pipeline.scan_steps_for; None for an auction or a gang pack), and
        where it ran topology the pod-table ``table_blocks`` phase 1b read
        (pipeline.table_blocks_for; None without topology); returns True
        when the jit executable cache grew (a real XLA compile happened
        while tracing this launch)."""
        self.launches += 1
        rec = self.shapes.get(shape)
        first_of_shape = rec is None
        if rec is None:
            rec = self.shapes[shape] = {"launches": 0, "pods": 0,
                                        "steps": 0, "table_blocks": 0,
                                        "compiles": 0, "walltime_s": 0.0,
                                        "max_s": 0.0}
        rec["launches"] += 1
        rec["pods"] += pods
        rec["steps"] += steps or 0
        rec["table_blocks"] += table_blocks or 0
        cache = self._cache_size_fn()
        compiled = cache > self._last_cache
        if compiled:
            # a NEW shape's compile attributes to the transition that
            # produced it (re-bucket / batch bucket / flags); a compile
            # while RE-launching a known shape means something outside
            # the tracked key changed — surfaced as "unattributed", the
            # regression signal the MixedChurn acceptance gate reads
            cause = _diff_cause(self._last_shape, shape) \
                if first_of_shape else "unattributed"
            self.compiles += 1
            rec["compiles"] += 1
            self.compile_causes[cause] = \
                self.compile_causes.get(cause, 0) + 1
            self.compile_events.append({
                "at": self._now(), "cause": cause,
                "shape": dict(shape),
                "from": dict(self._last_shape)
                if self._last_shape else None})
            del self.compile_events[:-self.MAX_COMPILE_EVENTS]
            if self._metrics is not None:
                self._metrics.device_compiles.inc(cause=cause)
        self._last_cache = cache
        self._last_shape = shape
        if self._metrics is not None:
            self._metrics.device_launch_shapes.set(
                float(len(self.shapes)))
            self._metrics.device_launch_fill.set(
                _fill(shape, rec), shape=shape_label(shape))
            if steps is not None:
                self._metrics.device_scan_steps.inc(steps, result="run")
                self._metrics.device_scan_steps.inc(
                    (dict(shape).get("b") or 0) - steps, result="skipped")
            if table_blocks is not None:
                self._metrics.device_table_blocks.inc(table_blocks,
                                                      result="run")
                self._metrics.device_table_blocks.inc(
                    _table_size(shape) - table_blocks, result="skipped")
        return compiled

    def observe_walltime(self, shape: tuple, secs: float) -> None:
        rec = self.shapes.get(shape)
        if rec is not None:
            rec["walltime_s"] += secs
            rec["max_s"] = max(rec["max_s"], secs)

    def note_buffers(self, buffers: dict[str, int]) -> None:
        """Record the live device-buffer footprint by buffer family
        (cluster / pods / dra), bytes."""
        self.buffer_bytes = dict(buffers)
        if self._metrics is not None:
            for name, nbytes in buffers.items():
                self._metrics.device_live_buffer_bytes.set(
                    float(nbytes), buffer=name)

    # ------------- reading -------------

    def snapshot(self, events: int = 16) -> dict:
        """The /debug/trace and perf-harness payload."""
        return {
            "launches": self.launches,
            "compiles": self.compiles,
            "compile_causes": dict(self.compile_causes),
            "unattributed_compiles":
                self.compile_causes.get("unattributed", 0),
            "shapes": [
                {"shape": shape_label(s), **rec,
                 "fill": round(_fill(s, rec), 4),
                 "steps_skipped": round(_steps_skipped(s, rec), 4),
                 "table_skipped": round(_table_skipped(s, rec), 4),
                 "walltime_s": round(rec["walltime_s"], 4),
                 "max_s": round(rec["max_s"], 4)}
                for s, rec in self.shapes.items()],
            "buffer_bytes": dict(self.buffer_bytes),
            "buffer_total_mib": round(
                sum(self.buffer_bytes.values()) / (1 << 20), 2),
            "recent_compiles": self.compile_events[-max(0, events):],
        }


def tree_nbytes(tree) -> int:
    """Total .nbytes over a pytree's array leaves (metadata only — no
    device sync, no transfer)."""
    if tree is None:
        return 0
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is not None:
            total += int(nbytes)
    return total
