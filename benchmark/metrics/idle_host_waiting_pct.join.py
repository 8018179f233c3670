"""Share of the traced window in which the device is idle and no
``crdt.*`` span is open on any host thread: chip and replicas both wait
on the anti-entropy tick or the benchmark's poll. With
``idle_host_busy_pct.join`` it sums to ``device_idle_pct.join``. Read
only where the program spans its replica loop (``crdt.sync_tick``,
``crdt.drain``), since only then does no open span mean the replica
threads wait."""

from benchmark.trace import idle_gaps, union_ns

LOOP_SPANS = ("crdt.sync_tick", "crdt.drain")


def read(run, _data):
    tr = run.trace
    if tr is None or not tr.ops or not any(n in LOOP_SPANS for _s, _e, n in tr.spans):
        return None
    lo, hi = tr.window
    host = [(s, e) for s, e, n in tr.spans if n.startswith("crdt.")]
    alone = 0
    for ops in tr.ops.values():
        idle = idle_gaps([(s, e) for s, e, _n, _m in ops], lo, hi)
        # |idle \ host| = |idle ∪ host| - |host|
        alone += union_ns(idle + host, lo, hi) - union_ns(host, lo, hi)
    return 100.0 * alone / len(tr.ops) / (hi - lo)
