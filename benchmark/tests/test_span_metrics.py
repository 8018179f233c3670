"""The per-layer metrics that read the program's ``crdt.*`` spans, on a
handmade trace kept here (``data/trace_spans_handmade.json``).

Window [0, 1000] ns; the device runs [100, 200] and [400, 500], so it
is idle 800 ns (80%). Host spans, on any thread:

- ``crdt.sync_tick`` [0, 50] and ``crdt.drain`` [150, 300], half of it
  while the device runs;
- ``crdt.walk`` [600, 650] and [900, 1100], the second cut at the
  window's end;
- ``crdt.slice_extract`` [610, 620] overlapping ``crdt.slice_wire``'s
  [615, 640];
- readbacks [620, 630], and [700, 760] with [710, 720] nested in it;
- ``bench.join_poll`` [300, 400], which is not the program's.
"""

import json
import os

import pytest

from benchmark import harness, trace

DATA = os.path.join(os.path.dirname(__file__), "data")

SPAN_METRICS = [
    "walk_ms.join", "slice_build_ms.join", "readback_wait_ms.join",
    "idle_host_busy_pct.join", "idle_host_waiting_pct.join",
]


class FakeRun:
    def __init__(self, tr, slices=10):
        self.trace, self._slices = tr, slices

    def slices_merged(self, role):
        assert role == "joiners"
        return self._slices


def spans_trace():
    with open(os.path.join(DATA, "trace_spans_handmade.json")) as f:
        return trace.from_json(json.load(f))


def metric(name, run):
    return harness.read_metric(name, run)


def test_walk_sums_its_spans_clipped_to_the_window_per_slice():
    # 50 + 100 (the second walk cut at 1000) ns over 10 slices, in ms
    assert metric("walk_ms.join", FakeRun(spans_trace())) == pytest.approx(15e-6)


def test_slice_build_is_the_union_of_extraction_and_wire_spans():
    # [610, 640]: 30 ns over 10 slices
    assert metric("slice_build_ms.join", FakeRun(spans_trace())) == pytest.approx(3e-6)


def test_nested_readbacks_count_once():
    # [620, 630] and [700, 760], the nested [710, 720] not again: 70 ns
    assert metric("readback_wait_ms.join", FakeRun(spans_trace())) == pytest.approx(7e-6)


def test_the_two_idle_shares_split_device_idle():
    run = FakeRun(spans_trace())
    busy = metric("idle_host_busy_pct.join", run)
    waiting = metric("idle_host_waiting_pct.join", run)
    idle = metric("device_idle_pct.join", run)
    assert idle == pytest.approx(80.0)
    # idle with a crdt span open: [0, 50], [200, 300], [600, 650],
    # [700, 760], [900, 1000] = 360 ns; the poll span does not count
    assert busy == pytest.approx(36.0)
    assert waiting == pytest.approx(44.0)
    assert busy + waiting == pytest.approx(idle)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_no_trace_reads_nothing(name):
    assert metric(name, FakeRun(None)) is None


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_program_without_the_spans_reads_nothing(name):
    """The parent program has only ``crdt.merge_group``-style spans: no
    walk, slice, readback or loop spans, so every reader is silent."""
    tr = spans_trace()
    tr.spans = [(0, 1000, "bench.window"), (150, 300, "crdt.merge_group")]
    assert metric(name, FakeRun(tr)) is None
