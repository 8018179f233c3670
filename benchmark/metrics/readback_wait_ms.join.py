"""Host time spent waiting on audited readbacks: the union of every
``crdt.readback.<site>`` span within the window (a readback nested in
another, or overlapping one on another thread, counts once), per slice
merged into a joiner. ``host_crossings_per_round.join`` is their count."""

from benchmark.trace import union_ns


def read(run, _data):
    tr, slices = run.trace, run.slices_merged("joiners")
    if tr is None or not slices:
        return None
    spans = [(s, e) for s, e, n in tr.spans if n.startswith("crdt.readback.")]
    if not spans:
        return None
    return union_ns(spans, *tr.window) / slices / 1e6
