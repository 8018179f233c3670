"""The reduction from a trace to metrics, on small traces kept here."""

import json
import os

import pytest

from benchmark import roofline, trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def handmade():
    with open(os.path.join(DATA, "trace_handmade.json")) as f:
        return trace.from_json(json.load(f))


def test_busy_is_the_union_of_device_ops_inside_the_window():
    tr = handmade()
    # [100, 300] + [500, 600] + [950, 1000] clipped at the window's end
    assert tr.busy_s() == pytest.approx(350e-9)
    assert tr.window_s == pytest.approx(1000e-9)


def test_idle_gaps_and_their_labels():
    tr = handmade()
    gaps = trace.idle_gaps([(s, e) for s, e, _n, _m in tr.ops["/device:TPU:0"]], *tr.window)
    assert gaps == [(0, 100), (300, 500), (600, 950)]
    # crdt.* wins over an enclosing bench.* span; the window span never labels
    assert trace.span_at(tr.spans, 350) == "crdt.merge"
    assert trace.span_at(tr.spans, 50) == "bench.read"
    assert trace.span_at(tr.spans, 920) == "none"
    b = tr.breakdown()
    assert b["idle_gaps"][0] == ["bench.join_poll", pytest.approx(350e-9)]
    assert b["device_ops"][0] == ["jit_merge_rows(7)", pytest.approx(450e-9)]


def test_module_time_counts_whole_runs_inside_the_window_by_name():
    tr = handmade()
    assert tr.module_s(["jit_merge_rows"]) == pytest.approx(200e-9)
    assert tr.module_s(["jit_winners_for_keys"]) == pytest.approx(100e-9)
    assert tr.module_s(["jit_merge"]) == 0
    assert trace.module_matches("jit_merge_rows.3(12)", ["jit_merge_rows"])


def test_merge_bytes_count_entries_not_capacity():
    assert roofline.ENTRY_BYTES == 29
    assert roofline.merge_bytes(1000) == 3 * 29 * 1000
    assert roofline.merge_bytes(0) == 0


def test_peaks_are_keyed_by_device_kind():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_a_recorded_chip_trace_reduces_within_its_window():
    """What ``trace.load`` read from a traced window of the join cell
    on one TPU v5e: a 200 ms slice of it (the device idle there) and the
    whole window's reduction."""
    with open(os.path.join(DATA, "trace_join_small.json")) as f:
        d = json.load(f)
    tr = trace.from_json(d)
    assert 0 <= tr.busy_s() <= tr.window_s
    assert len(tr.breakdown()["idle_gaps"]) <= 10
    full = d["full"]
    assert full["planes"] == ["/device:TPU:0"]
    assert 0 < full["busy_s"] <= full["window_s"]
    assert full["breakdown"]["device_ops"][0][0].startswith("jit_merge_rows")
    assert len(full["breakdown"]["idle_gaps"]) <= 10
