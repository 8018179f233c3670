"""Host time building slices for the wire: the union of the
``crdt.slice_extract`` (extraction dispatch) and ``crdt.slice_wire``
(payload-dot readback, payload dict, columns fetched or placed) spans
within the window, per slice merged into a joiner."""

from benchmark.trace import union_ns

SPANS = ("crdt.slice_extract", "crdt.slice_wire")


def read(run, _data):
    tr, slices = run.trace, run.slices_merged("joiners")
    if tr is None or not slices:
        return None
    spans = [(s, e) for s, e, n in tr.spans if n in SPANS]
    if not spans:
        return None
    return union_ns(spans, *tr.window) / slices / 1e6
