"""Device time of the merge programs (modules named in the data file),
per slice merged into a joiner."""


def read(run, data):
    slices = run.slices_merged("joiners")
    if run.trace is None or not slices:
        return None
    s = run.trace.module_s(data["modules"])
    return s / slices * 1e3 if s > 0 else None
