"""Share of the HBM roofline the merge programs reach: the least bytes
of merging the window's entries into the joiners, over peak HBM
bandwidth, over the merge programs' device time."""

from benchmark.roofline import merge_bytes


def read(run, data):
    if run.trace is None:
        return None
    s = run.trace.module_s(data["modules"])
    entries = run.entries_merged("joiners")
    if s <= 0 or not entries:
        return None
    return 100.0 * merge_bytes(entries) / run.peaks["hbm_bytes_per_s"] / s
