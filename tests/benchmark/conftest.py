"""One case of test_bench_span_readers.py pins the inventory it was written
against: "the last entry is moved to the head" pops the LAST entry of
`per_layer` and expects the order invariant to refuse the move, which holds
only while the last entry is one of PR 27's. The invariant itself lets a
later entry go anywhere (bench_invariants.PER_LAYER_AT_PR27), and the
driver reads BENCHMARK.json's lists by position (an entry put first or in
the middle reads to it as a change to what was there), so a PR appends its
per-layer metrics at the end, and the first PR that appends one (PR 32)
turns the case into a move of its own entry, which nothing forbids. No file the benchmark had may be edited by such a PR, so
the case is given here what it means: the last entry THAT WAS THERE is moved
to the head. A `benchmark` PR folds this into the case and deletes this
file (PERF.md 7)."""

import pytest

MODULE = "test_bench_span_readers"
CASE = "the last entry is moved to the head"


@pytest.fixture(autouse=True)
def _per_layer_case_moves_an_entry_that_was_there(request, monkeypatch):
    if request.module.__name__.rpartition(".")[2] != MODULE:
        return
    edits = request.module.PER_LAYER_EDITS
    last = request.module.inv.PER_LAYER_AT_PR27[-1]

    def change(manifest):
        per = manifest["per_layer"]
        at = next(i for i, m in enumerate(per) if m["name"] == last)
        per.insert(0, per.pop(at))

    monkeypatch.setitem(edits, CASE, (False, change))
