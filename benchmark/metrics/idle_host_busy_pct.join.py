"""Share of the traced window in which the device is idle while some
``crdt.*`` span is open on a host thread: the chip waits on the
replicas' host work. ``idle_host_waiting_pct.join`` is the rest of
``device_idle_pct.join``; the two sum to it. Read only where the
program spans its replica loop (``crdt.sync_tick``, ``crdt.drain``),
since only then does no open span mean the replica threads wait."""

from benchmark.trace import idle_gaps, union_ns

LOOP_SPANS = ("crdt.sync_tick", "crdt.drain")


def read(run, _data):
    tr = run.trace
    if tr is None or not tr.ops or not any(n in LOOP_SPANS for _s, _e, n in tr.spans):
        return None
    lo, hi = tr.window
    host = [(s, e) for s, e, n in tr.spans if n.startswith("crdt.")]
    both = 0
    for ops in tr.ops.values():
        idle = idle_gaps([(s, e) for s, e, _n, _m in ops], lo, hi)
        # |idle ∩ host| = |idle| + |host| - |idle ∪ host|
        both += union_ns(idle, lo, hi) + union_ns(host, lo, hi) - union_ns(idle + host, lo, hi)
    return 100.0 * both / len(tr.ops) / (hi - lo)
