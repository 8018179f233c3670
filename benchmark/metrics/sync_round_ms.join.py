"""Mean time a joiner takes to merge one received slice (``SYNC_ROUND``
``duration_s``) over the window."""


def read(run, _data):
    ev = run.events("SYNC_ROUND", "joiners")
    return sum(m["duration_s"] for _t, m, _n in ev) / len(ev) * 1e3 if ev else None
