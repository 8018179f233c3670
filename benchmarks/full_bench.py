"""Port of ``bench/full_bench.exs``: 2-replica convergence wall-clock.

Add N keys at c1, wait until c2 observes key N via ``on_diffs``; then
remove all N, wait until c2 observes the removal of N — with
``sync_interval`` 20 ms and ``max_sync_size`` 500, background sync
threads (reference ``full_bench.exs:1-63``).

Run: ``python -m benchmarks.full_bench [N ...]``
(default 10 100 1000 10000 20000 30000)
"""

from __future__ import annotations

import sys
import time

from delta_crdt_ex_tpu import AWLWWMap
from delta_crdt_ex_tpu.api import start_link
from delta_crdt_ex_tpu.runtime.transport import LocalTransport
from benchmarks.common import emit, emit_partial, load_partial, log


def do_test(number):
    transport = LocalTransport()
    seen = {"add": False, "remove": False}

    def on_diffs(diffs):
        for d in diffs:
            if d[0] == "add" and d[1] == number:
                seen["add"] = True
            if d[0] == "remove" and d[1] == number:
                seen["remove"] = True

    kw = dict(transport=transport, sync_interval=0.02, max_sync_size=500,
              capacity=max(4096, 4 * number), tree_depth=12)
    c1 = start_link(AWLWWMap, **kw)
    c2 = start_link(AWLWWMap, on_diffs=on_diffs, **kw)
    c1.set_neighbours([c2])
    c2.set_neighbours([c1])

    t0 = time.perf_counter()
    for x in range(1, number + 1):
        c1.mutate_async("add", [x, x])
    deadline = time.monotonic() + 120
    while not seen["add"] and time.monotonic() < deadline:
        time.sleep(0.005)
    assert seen["add"], f"add convergence timed out at N={number}"
    t_add = time.perf_counter() - t0

    t0 = time.perf_counter()
    for x in range(1, number + 1):
        c1.mutate_async("remove", [x])
    while not seen["remove"] and time.monotonic() < deadline:
        time.sleep(0.005)
    assert seen["remove"], f"remove convergence timed out at N={number}"
    t_remove = time.perf_counter() - t0

    c1.stop()
    c2.stop()
    return t_add, t_remove


def main(sizes=(10, 100, 1000, 10_000, 20_000, 30_000)):
    # resume a killed run's cells, and checkpoint after every size: a
    # watchdog kill on a slow backend keeps the finished cells
    results = load_partial("full_bench")
    todo = [
        n for n in sizes
        if not (f"add@{n}" in results and f"remove@{n}" in results)
    ]
    for i, n in enumerate(todo):
        t_add, t_remove = do_test(n)
        results[f"add@{n}"] = round(t_add, 3)
        results[f"remove@{n}"] = round(t_remove, 3)
        log(f"N={n}: add+converge {t_add:.3f}s, remove+converge {t_remove:.3f}s")
        if i + 1 < len(todo):
            emit_partial("full_bench", results)
    emit("full_bench", results)
    return results


if __name__ == "__main__":
    sizes = tuple(int(a) for a in sys.argv[1:]) or (10, 100, 1000, 10_000, 20_000, 30_000)
    main(sizes)
