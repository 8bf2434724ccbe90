"""Every offered pod is bound exactly once, by the hub's journal; and an
acknowledged bind is read back: the scheduler counts a pod scheduled when
its bind call returned, and the journal must hold a bind for each."""

from benchmark import reference


def check(end):
    changes = end.hub.list_changes(0, ("pods",))
    rows = [(c["rv"], c["type"], c["obj"].metadata.uid,
             c["obj"].spec.node_name) for c in changes.get("changes", [])]
    audit = reference.audit_journal(rows, set(end.offered))
    return {
        "journal_too_old": bool(changes.get("too_old")),
        "unbound": audit["unbound"],
        "double_binds": audit["double_binds"] + len(end.watcher.repeats),
        "acknowledged_binds_missing": max(
            0, end.sched.stats["scheduled"] - audit["binds_audited"]),
    }
