"""Incident autopsy: bounded black-box bundles + per-pod critical path.

When the SLO watchdog (telemetry/watchdog.py) raises an incident — a
rule trip on the maintenance cadence or a containment site firing
directly through ``telemetry.incident(...)`` — the evidence that
explains it is about to evaporate: the flight-recorder ring rolls over,
the journal suffix advances, /debug surfaces show only the present.
The :class:`AutopsyStore` freezes that evidence to disk as ONE atomic
JSON bundle per incident:

* the flight-recorder ring suffix + phase percentiles,
* the last-K pod timelines (events, wire stamps, joined latency),
* the hub journal's ``list_changes`` suffix,
* queue / gang / job-queue debug snapshots + the stats dict,
* the DeviceProfiler compile-event ring,
* a FleetView scrape (when a fleet view is attached),
* live time-to-bind stats and the trigger rule + metric values.

Bounded by construction: retention caps on bundle count AND total
bytes (oldest pruned first), per-incident-class rate limiting so a
storm of identical faults files one bundle per window, and atomic
tmp+``os.replace`` writes so a reader never sees a torn bundle (a
killed writer leaves only a ``.tmp`` the reader skips).

The offline half lives here too: ``list_bundles`` / ``load_bundle``
(torn-tolerant), ``diff_bundles``, and ``critical_path`` — the per-pod
span breakdown (created → enqueued → popped → bound → acked) that
attributes wait time to the queue, device+commit, binder/hub, and
fabric legs from the timeline + wire stamps already in every bundle.
``python -m kubernetes_tpu.telemetry autopsy ...`` fronts them.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Callable, Optional

logger = logging.getLogger("kubernetes_tpu.autopsy")

BUNDLE_FORMAT = 1
BUNDLE_PREFIX = "autopsy-"
BUNDLE_SUFFIX = ".json"

# bundle bounds (per capture): ring/timeline/journal suffix sizes. The
# point is a BOUNDED black box — enough tail to reconstruct the minutes
# before the trigger, never the whole history.
RING_SUFFIX_CYCLES = 32
TIMELINE_SUFFIX_PODS = 16
JOURNAL_SUFFIX_EVENTS = 128
PROFILER_SUFFIX_EVENTS = 32


def _slug(s: str) -> str:
    out = "".join(c if c.isalnum() or c in "-_" else "-"
                  for c in (s or "incident").lower())
    return out[:48] or "incident"


class AutopsyStore:
    """Bounded on-disk bundle store: atomic writes, per-class rate
    limiting, count+bytes retention. Thread-safe (containment sites and
    the maintenance poll may race on a storm)."""

    def __init__(self, directory: str, max_bundles: int = 32,
                 max_bytes: int = 16 * 1024 * 1024,
                 rate_limit_s: float = 30.0,
                 now: Callable[[], float] = time.time,
                 metrics=None):
        self.directory = directory
        self.max_bundles = max(1, max_bundles)
        self.max_bytes = max(4096, max_bytes)
        self.rate_limit_s = max(0.0, rate_limit_s)
        self._now = now
        self._metrics = metrics
        self._lock = threading.Lock()
        self._last_by_kind: dict[str, float] = {}
        os.makedirs(directory, exist_ok=True)
        # resume the sequence after a restart so retention ordering
        # (oldest-first pruning) survives the process
        self._seq = 0
        for name in self._names():
            try:
                self._seq = max(self._seq,
                                int(name[len(BUNDLE_PREFIX):].split("-")[0]))
            except (ValueError, IndexError):
                continue

    # ------------- capture -------------

    def capture(self, trigger: dict,
                collect: Callable[[], dict]) -> Optional[str]:
        """File one bundle for ``trigger`` (a dict with at least
        ``kind``). ``collect`` is called ONLY after the rate-limit gate
        admits the class — a storm of identical incidents costs one
        bundle (and one collection walk) per window. Returns the bundle
        path, or None when rate-limited or the write failed."""
        kind = str(trigger.get("kind", "incident"))
        now = self._now()
        with self._lock:
            last = self._last_by_kind.get(kind)
            if last is not None and self.rate_limit_s > 0 \
                    and now - last < self.rate_limit_s:
                self._drop("rate_limited")
                return None
            self._last_by_kind[kind] = now
            self._seq += 1
            seq = self._seq
        try:
            body = collect()
        except Exception:  # noqa: BLE001 — the autopsy must never take
            # down the path it is observing; a failed collection still
            # files the trigger so the incident is not silently lost
            logger.exception("autopsy collection failed for %s", kind)
            body = {"collect_errors": ["collection raised; "
                                       "trigger-only bundle"]}
        doc = {"format": BUNDLE_FORMAT, "seq": seq,
               "captured_at": round(now, 6), "trigger": trigger}
        doc.update(body)
        name = f"{BUNDLE_PREFIX}{seq:06d}-{_slug(kind)}{BUNDLE_SUFFIX}"
        path = os.path.join(self.directory, name)
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, default=str)
            os.replace(tmp, path)
        except OSError:
            logger.exception("autopsy bundle write failed: %s", path)
            self._drop("write_error")
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return None
        if self._metrics is not None:
            self._metrics.autopsy_bundles.inc(trigger=_slug(kind))
        self._prune()
        return path

    def _drop(self, reason: str) -> None:
        if self._metrics is not None:
            self._metrics.autopsy_bundles_dropped.inc(reason=reason)

    def _names(self) -> list[str]:
        try:
            return sorted(n for n in os.listdir(self.directory)
                          if n.startswith(BUNDLE_PREFIX)
                          and n.endswith(BUNDLE_SUFFIX))
        except OSError:
            return []

    def _prune(self) -> None:
        """Retention: newest max_bundles bundles / max_bytes total.
        Lexicographic name order IS seq order (zero-padded)."""
        with self._lock:
            names = self._names()
            sizes = {}
            for n in names:
                try:
                    sizes[n] = os.path.getsize(
                        os.path.join(self.directory, n))
                except OSError:
                    sizes[n] = 0
            total = sum(sizes.values())
            while names and (len(names) > self.max_bundles
                             or total > self.max_bytes):
                victim = names.pop(0)
                try:
                    os.unlink(os.path.join(self.directory, victim))
                except OSError:
                    pass
                total -= sizes.get(victim, 0)
                self._drop("retention")
            if self._metrics is not None:
                self._metrics.autopsy_store_bytes.set(float(total))

    # ------------- reading (also /debug/autopsy) -------------

    def list(self) -> list[dict]:
        return list_bundles(self.directory)

    def load(self, name: str) -> dict:
        return load_bundle(os.path.join(self.directory, name))


# ------------- collection (called on the scheduler's thread) -------------


def collect_bundle(sched, trigger: dict) -> dict:
    """Walk the scheduler's live debug surfaces into one bundle body.
    Every section is individually guarded: a down hub or detached fleet
    view yields a partial bundle with the failure named in
    ``collect_errors``, never a lost incident."""
    body: dict = {}
    errors: list[str] = []

    def section(name: str, fn):
        try:
            v = fn()
            if v is not None:
                body[name] = v
        except Exception as e:  # noqa: BLE001 — partial bundles beat
            errors.append(f"{name}: {e!r}")       # lost incidents

    flight = getattr(sched, "flight", None)
    if flight is not None:
        section("flight", lambda: {
            "cycles": flight.last(RING_SUFFIX_CYCLES),
            "phases": flight.phase_percentiles(),
            "host_tail_share": round(flight.host_tail_share(), 4),
        })
    timelines = getattr(sched, "timelines", None)
    if timelines is not None:
        def _timelines():
            uids = timelines.uids()[-TIMELINE_SUFFIX_PODS:]
            return [t for t in (timelines.get(uid=u) for u in uids)
                    if t is not None]
        section("timelines", _timelines)

        def _slo_stats():
            from kubernetes_tpu.telemetry.slo import time_to_bind_stats
            return time_to_bind_stats(timelines)
        section("slo_stats", _slo_stats)
    section("queue", lambda: {
        "pending": sched.queue.pending_counts(),
        "stats": dict(sched.stats),
    })
    gang = getattr(sched, "_gang", None)
    if gang is not None:
        section("gangs", gang.debug_state)
    jq = getattr(sched, "jobqueue", None)
    if jq is not None and getattr(jq, "active", False):
        section("job_queue", jq.debug_state)
    prof = getattr(sched, "profiler", None)
    if prof is not None:
        section("profiler",
                lambda: prof.snapshot(events=PROFILER_SUFFIX_EVENTS))
    bs_fn = getattr(sched, "brownout_state", None)
    if bs_fn is not None:
        section("brownout", bs_fn)
    fleet = getattr(sched, "fleet", None)
    if fleet is not None:
        section("fleet", fleet.summary)

    def _journal():
        js_fn = getattr(sched.hub, "get_journal_stats", None)
        lc_fn = getattr(sched.hub, "list_changes", None)
        if js_fn is None or lc_fn is None:
            return None
        rv = int(js_fn().get("rv", 0) or 0)
        since = max(0, rv - JOURNAL_SUFFIX_EVENTS)
        res = lc_fn(since)
        return {"rv": res.get("rv"), "since": since,
                "too_old": res.get("too_old", False),
                "changes": [
                    {"rv": c.get("rv"), "kind": c.get("kind"),
                     "type": c.get("type"),
                     "name": getattr(getattr(c.get("obj"), "metadata",
                                             None), "name", None)}
                    for c in res.get("changes", [])]}
    section("journal", _journal)
    if errors:
        body["collect_errors"] = errors
    return body


# ------------- offline readers (CLI + tests) -------------


def list_bundles(directory: str) -> list[dict]:
    """One summary row per bundle, oldest first. Torn/unparseable files
    are listed with an ``error`` field instead of aborting the listing
    (a kill -9 mid-replace leaves at worst a ``.tmp`` we never match)."""
    rows = []
    try:
        names = sorted(n for n in os.listdir(directory)
                       if n.startswith(BUNDLE_PREFIX)
                       and n.endswith(BUNDLE_SUFFIX))
    except OSError:
        return rows
    for name in names:
        path = os.path.join(directory, name)
        row: dict = {"name": name}
        try:
            row["bytes"] = os.path.getsize(path)
            doc = load_bundle(path)
            trig = doc.get("trigger", {})
            row.update({
                "seq": doc.get("seq"),
                "captured_at": doc.get("captured_at"),
                "kind": trig.get("kind"),
                "rule": trig.get("rule"),
                "reason": trig.get("reason"),
            })
        except (OSError, ValueError) as e:
            row["error"] = str(e)
        rows.append(row)
    return rows


def load_bundle(path: str) -> dict:
    """Parse one bundle strictly; raises ValueError on torn/invalid
    files (the CLI turns that into a non-zero exit — a bundle that does
    not parse is itself an incident)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except json.JSONDecodeError as e:
        raise ValueError(f"torn or invalid bundle {path}: {e}") from e
    if not isinstance(doc, dict) or "trigger" not in doc:
        raise ValueError(f"not an autopsy bundle: {path}")
    if int(doc.get("format", 0)) > BUNDLE_FORMAT:
        raise ValueError(
            f"bundle format {doc.get('format')} is newer than this "
            f"reader ({BUNDLE_FORMAT}): {path}")
    return doc


def diff_bundles(a: dict, b: dict) -> dict:
    """What changed between two bundles: stats-counter deltas, phase
    p99 shifts, SLO stat movement, and the trigger pair. The operator
    question it answers: what did the system DO between these two
    incidents."""
    out: dict = {
        "a": {"seq": a.get("seq"), "kind":
              a.get("trigger", {}).get("kind")},
        "b": {"seq": b.get("seq"), "kind":
              b.get("trigger", {}).get("kind")},
        "seconds_apart": round((b.get("captured_at") or 0)
                               - (a.get("captured_at") or 0), 3),
    }
    sa = (a.get("queue") or {}).get("stats") or {}
    sb = (b.get("queue") or {}).get("stats") or {}
    deltas = {}
    for k in sorted(set(sa) | set(sb)):
        va, vb = sa.get(k, 0), sb.get(k, 0)
        if isinstance(va, (int, float)) and isinstance(vb, (int, float)) \
                and vb != va:
            deltas[k] = vb - va
    out["stats_delta"] = deltas
    pa = (a.get("flight") or {}).get("phases") or {}
    pb = (b.get("flight") or {}).get("phases") or {}
    phases = {}
    for ph in sorted(set(pa) | set(pb)):
        p99a = (pa.get(ph) or {}).get("p99_ms")
        p99b = (pb.get(ph) or {}).get("p99_ms")
        if p99a != p99b:
            phases[ph] = {"p99_ms_a": p99a, "p99_ms_b": p99b}
    out["phase_p99_delta"] = phases
    slo_a = a.get("slo_stats") or {}
    slo_b = b.get("slo_stats") or {}
    out["slo_delta"] = {
        k: {"a": slo_a.get(k), "b": slo_b.get(k)}
        for k in sorted(set(slo_a) | set(slo_b))
        if slo_a.get(k) != slo_b.get(k)}
    return out


# the per-pod span legs, in lifecycle order: (leg name, from-stamp,
# to-stamp, attribution). Stamps resolve against the merged event/wire
# map built by critical_path; absent stamps skip the leg.
_CRITICAL_LEGS = (
    ("watch", "wire:created", "enqueued", "fabric"),
    ("queue", "enqueued", "popped:first", "queue"),
    ("retries", "popped:first", "popped:last", "queue"),
    ("schedule", "popped:last", "bound", "device"),
    ("hub_commit", "bound", "wire:bound", "binder"),
    ("fabric_relay", "wire:bound", "wire:kubelet_recv", "fabric"),
    ("kubelet_ack", "wire:kubelet_recv", "wire:acked", "fabric"),
)


def critical_path(timeline: dict) -> dict:
    """Per-pod span breakdown from one timeline record (as stored in
    bundles / returned by ``PodTimelines.get``): created → watched →
    queued → popped → bound → acked, with each wait attributed to the
    queue, device (schedule+commit), binder (hub write), or fabric
    (relay + kubelet) leg. Missing stamps (pod never bound, wire trace
    disabled) skip their legs and are named in ``missing``."""
    stamps: dict[str, float] = {}
    for ev in timeline.get("events", []):
        t, name = ev.get("t"), ev.get("event")
        if t is None or not name:
            continue
        if name == "popped":
            stamps.setdefault("popped:first", t)
            stamps["popped:last"] = t
        else:
            stamps.setdefault(name, t)
    for stamp, rec in (timeline.get("wire") or {}).items():
        t = rec.get("t") if isinstance(rec, dict) else None
        if t is not None:
            stamps.setdefault(f"wire:{stamp}", t)
    legs, missing = [], []
    attributed: dict[str, float] = {}
    for leg, frm, to, attr in _CRITICAL_LEGS:
        t0, t1 = stamps.get(frm), stamps.get(to)
        if t0 is None or t1 is None:
            missing.append(leg)
            continue
        ms = max(0.0, (t1 - t0) * 1e3)
        legs.append({"leg": leg, "from": frm, "to": to,
                     "ms": round(ms, 3), "attribution": attr})
        attributed[attr] = attributed.get(attr, 0.0) + ms
    first = stamps.get("wire:created", stamps.get("enqueued"))
    last_candidates = [stamps[k] for k in
                       ("wire:acked", "wire:kubelet_recv", "wire:bound",
                        "bound") if k in stamps]
    total_ms = (round((last_candidates[0] - first) * 1e3, 3)
                if first is not None and last_candidates else None)
    return {
        "pod": f"{timeline.get('namespace', '?')}/"
               f"{timeline.get('name', '?')}",
        "uid": timeline.get("uid"),
        "legs": legs,
        "attributed_ms": {k: round(v, 3)
                          for k, v in sorted(attributed.items())},
        "total_ms": total_ms,
        "missing": missing,
    }
