"""Port of ``bench/propagation.exs``: propagation latency into a
pre-synced 2-replica pair.

Prepare: fill c1 with N keys, wait until c2 converges (BenchRecorder
sentinel on c2's ``on_diffs``), then ``hibernate`` + ``ping`` both
replicas (reference ``propagation.exs:61-64``). Measure: wall-clock for
10 adds / 10 removes at c1 to be observed at c2, with real background
sync threads at ``sync_interval`` 5 ms (reference ``:38-44``).

Run: ``python -m benchmarks.propagation [N ...]``  (default 20000 30000)

``PROP_DEVICE_PLANE=1`` pins both replicas to the first jax device, so
sync slices ride the device data plane (on one real chip: same-device
puts — slice columns never take the host round trip). The emitted
result rows gain a ``@dev`` suffix so the two planes never mix in the
results file.
"""

from __future__ import annotations

import os
import sys
import time

from delta_crdt_ex_tpu import AWLWWMap
from delta_crdt_ex_tpu.api import start_link
from delta_crdt_ex_tpu.runtime.transport import LocalTransport
from benchmarks.common import BenchRecorder, emit, emit_partial, load_partial, log

DEVICE_PLANE = os.environ.get("PROP_DEVICE_PLANE") == "1"


def _pin_device():
    if not DEVICE_PLANE:
        return None
    import jax

    return jax.devices()[0]


def prepare(number):
    transport = LocalTransport()
    rec = BenchRecorder()
    dev = _pin_device()
    c1 = start_link(AWLWWMap, transport=transport, sync_interval=0.005,
                    capacity=max(4096, 4 * number), tree_depth=12, max_sync_size=500,
                    device=dev)
    c2 = start_link(AWLWWMap, transport=transport, sync_interval=0.005,
                    on_diffs=rec.on_diffs, device=dev,
                    capacity=max(4096, 4 * number), tree_depth=12, max_sync_size=500)
    c1.set_neighbours([c2])
    c2.set_neighbours([c1])
    for x in range(1, number + 1):
        c1.mutate_async("add", [x, x])
    assert rec.wait(number, "add", timeout=120), "initial convergence timed out"
    # the sentinel key can arrive while truncated sync rounds are still
    # draining the backlog (max_sync_size bounds each round) — wait for
    # REAL convergence so the timed phase measures only the 10-op
    # propagation, not leftover backlog
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline and len(c2.read()) != number:
        time.sleep(0.02)
    assert len(c2.read()) == number, "full convergence timed out"
    # warm the small-tier sync kernels with round trips matching the
    # timed phase's shapes (1-op and 10-op rounds → the 8/16-row slice
    # tiers): first-time jit compiles must not land inside the timing
    c1.mutate("add", [0, 0])
    assert rec.wait(0, "add"), "warm add timed out"
    for x in range(-10, 0):
        c1.mutate("add", [x, x])
    for x in range(-10, 0):
        assert rec.wait(x, "add"), "warm adds timed out"
    for x in list(range(-10, 0)) + [0]:
        c1.mutate("remove", [x])
    for x in list(range(-10, 0)) + [0]:
        assert rec.wait(x, "remove"), "warm removes timed out"
    c1.hibernate(), c2.hibernate()
    c1.ping(), c2.ping()
    return transport, rec, c1, c2


def perform(pair, op):
    transport, rec, c1, c2 = pair
    t0 = time.perf_counter()
    if op == "add":
        for x in range(100_000, 100_011):
            c1.mutate("add", [x, x])
        assert rec.wait(100_010, "add"), "add propagation timed out"
    else:
        for x in range(1, 11):
            c1.mutate("remove", [x])
        assert rec.wait(10, "remove"), "remove propagation timed out"
    dt = time.perf_counter() - t0
    c1.stop()
    c2.stop()
    return dt


def main(sizes=(20_000, 30_000)):
    results = {}
    tag = "@dev" if DEVICE_PLANE else ""
    # separate results file per plane — emit() rewrites whole files, and
    # a device-plane run must not clobber the host-plane rows
    name = "propagation_devplane" if DEVICE_PLANE else "propagation"
    # each cell converges tens of thousands of keys through the
    # (possibly slow) backend before its timed 10 ops — resume a
    # killed run's finished cells and checkpoint after every cell
    results.update(load_partial(name))
    todo = [
        (n, op)
        for n in sizes
        for op in ("add", "remove")
        if f"{op}10@{n}{tag}" not in results
    ]
    for i, (n, op) in enumerate(todo):
        log(f"preparing {n}-key pair for {op}{tag}…")
        dt = perform(prepare(n), op)
        results[f"{op}10@{n}{tag}"] = round(dt * 1000, 2)
        log(f"{op} 10 into {n}-key pair{tag}: {dt*1000:.1f} ms")
        if i + 1 < len(todo):
            emit_partial(name, results)
    emit(name, results)
    return results


if __name__ == "__main__":
    sizes = tuple(int(a) for a in sys.argv[1:]) or (20_000, 30_000)
    main(sizes)
