"""Host-device crossings over every audited site of the transfer ledger
in the window, per slice merged into a joiner."""


def read(run, _data):
    slices = run.slices_merged("joiners")
    return run.crossings() / slices if slices else None
