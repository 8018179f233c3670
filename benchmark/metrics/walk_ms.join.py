"""Host time in the digest walk (``crdt.walk`` spans: the tree build,
the digest-level readbacks, the next frame's blocks and its send),
summed over the replica threads within the window, per slice merged
into a joiner."""


def read(run, _data):
    tr, slices = run.trace, run.slices_merged("joiners")
    if tr is None or not slices:
        return None
    lo, hi = tr.window
    walks = [(s, e) for s, e, n in tr.spans if n == "crdt.walk"]
    if not walks:
        return None
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in walks) / slices / 1e6
