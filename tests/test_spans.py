"""Program spans and the merge's flag readback.

The replica names its host work ``crdt.<step>`` with
``jax.profiler.TraceAnnotation`` spans, and every audited readback opens
``crdt.readback.<site>``. A small two-replica join runs here on the CPU
under the profiler, inside the benchmark's ``bench.window`` span, and is
read back with ``benchmark.trace.load`` — the reader the benchmark's
per-layer metrics use — so a span that goes missing or is renamed fails
here, not as a ``null`` metric on the chip. The merge's escape flags are
read through the transfer ledger (one counted crossing per kernel call),
and the kernel metrics find the merge program by its module name, which
is pinned below against the names their data files list.
"""

from __future__ import annotations

import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark import trace
from delta_crdt_ex_tpu import AWLWWMap
from delta_crdt_ex_tpu.api import start_link
from delta_crdt_ex_tpu.models import binned_map
from delta_crdt_ex_tpu.models.binned import BinnedStore
from delta_crdt_ex_tpu.ops import binned as binned_ops
from delta_crdt_ex_tpu.runtime.clock import LogicalClock
from delta_crdt_ex_tpu.runtime.transport import LocalTransport
from delta_crdt_ex_tpu.utils import transfers

METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark", "metrics")

#: every span a join emits on the joiner's and the source's threads
JOIN_SPANS = [
    "crdt.sync_tick",
    "crdt.drain",
    "crdt.walk",
    "crdt.slice_extract",
    "crdt.slice_wire",
    "crdt.payloads",
    "crdt.combine",
    "crdt.commit",
    "crdt.merge_group",
    "crdt.gc",
    "crdt.readback.replica.digest_levels",
    "crdt.readback.binned_map.merge_flags",
]


#: the audited site each store reads its merge flags through
FLAG_SITES = {"binned": "binned_map.merge_flags", "hash": "hash_store.merge_flags"}


def _mk(transport, store, name, node_id, **kw):
    return start_link(
        AWLWWMap, threaded=False, transport=transport, clock=LogicalClock(),
        store=store, name=name, node_id=node_id, capacity=256, tree_depth=6,
        sync_timeout=600.0, **kw,
    )


def _inside(tr, name, lo, hi) -> list:
    return [(s, e) for s, e, n in tr.spans if n == name and s >= lo and e <= hi]


@pytest.fixture(scope="module")
def traced_join(tmp_path_factory):
    """An empty replica joins a loaded one, traced: the source pushes
    4-bucket slices three ticks at a time, so the joiner's drain finds
    runs of slices and merges them grouped. Then a payload prune, and
    five drains of an empty mailbox inside a ``bench.idle`` span."""
    t = LocalTransport()
    src = _mk(t, "binned", "span_src", 1, max_sync_size=4)
    joiner = _mk(t, "binned", "span_joiner", 2, max_sync_size=4)
    src.mutate_batch("add", [[k, 7 * k] for k in range(200)])
    log_dir = str(tmp_path_factory.mktemp("spans"))
    with trace.capture(log_dir):
        src.set_neighbours([joiner])
        joiner.set_neighbours([src])
        for _ in range(60):
            for _tick in range(3):
                src.sync_to_all()
            joiner.process_pending()
            joiner.sync_to_all()
            src.process_pending()
            joiner.process_pending()
            if joiner.read() == src.read():
                break
        joiner.gc()  # the payload prune a drain runs once enough arrived
        with jax.profiler.TraceAnnotation("bench.idle"):
            for _ in range(5):
                joiner.process_pending()
    converged = joiner.read() == src.read()
    src.stop()
    joiner.stop()
    assert converged, "the traced join did not converge"
    return trace.load(log_dir)


@pytest.mark.parametrize("name", JOIN_SPANS)
def test_join_emits_span_inside_the_window(traced_join, name):
    tr = traced_join
    assert _inside(tr, name, *tr.window), f"no {name} span in the traced window"


def test_an_idle_drain_emits_no_span(traced_join):
    tr = traced_join
    idle = [(s, e) for s, e, n in tr.spans if n == "bench.idle"]
    assert len(idle) == 1
    assert _inside(tr, "crdt.drain", *idle[0]) == []


def test_site_get_opens_a_readback_span(tmp_path):
    site = transfers.register("testonly.span_probe")
    assert site.span == "crdt.readback.testonly.span_probe"
    with trace.capture(str(tmp_path)):
        got = site.get(jnp.arange(4))
    assert got.tolist() == [0, 1, 2, 3]
    tr = trace.load(str(tmp_path))
    assert len(_inside(tr, "crdt.readback.testonly.span_probe", *tr.window)) == 1


@pytest.mark.parametrize("store", ["binned", "hash"])
def test_a_merge_reads_its_flags_in_one_counted_crossing(store):
    """One slice merged solo is one kernel call, so one crossing at the
    store's ``merge_flags`` site, and nothing else at that site."""
    t = LocalTransport()
    src = _mk(t, store, f"flags_src_{store}", 21)
    dst = _mk(t, store, f"flags_dst_{store}", 22)
    src.set_neighbours([dst])
    src.mutate("add", ["warm", 1])
    src.sync_to_all()
    dst.process_pending()  # first merge: the writer table and tiers settle
    src.mutate("add", ["measured", 2])
    src.sync_to_all()
    dispatches = dst.stats()["ingress"]["dispatches"]
    pre = transfers.snapshot()
    dst.process_pending()
    d = transfers.delta(pre, transfers.snapshot())
    merges = dst.stats()["ingress"]["dispatches"] - dispatches
    assert dst.read() == {"warm": 1, "measured": 2}
    assert merges == 1
    assert d[FLAG_SITES[store]]["count"] == 1
    src.stop()
    dst.stop()


@pytest.mark.parametrize("metric", ["merge_device_ms.join", "merge_roofline_pct.join"])
def test_merge_program_module_name_is_one_the_kernel_metrics_list(metric):
    """The kernel metrics match device time by HLO module name: a rename
    of the jitted merge would turn them to ``null`` without an error."""
    st = jax.eval_shape(lambda: BinnedStore.new(64, 8, 2))
    sl = jax.eval_shape(
        binned_ops.extract_rows, st, jax.ShapeDtypeStruct((8,), jnp.int32)
    )
    hlo = binned_map.jit_merge_rows.lower(st, sl).as_text(dialect="hlo")
    module = re.match(r"HloModule (\S+?),", hlo).group(1)
    with open(os.path.join(METRICS, metric + ".json")) as f:
        listed = json.load(f)["modules"]
    assert trace.module_matches(module, listed), (module, listed)
