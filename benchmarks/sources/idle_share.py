"""1 minus the union of device operations over the traced window, in
percent; the worst (idlest) device."""


def read(run):
    if run.trace is None:
        return None
    shares = run.trace.idle_share_by_device()
    return 100.0 * max(shares.values()) if shares else None
