"""Device time of the operations whose family matches ``pattern``, as a
percentage of the device's busy time (share=true) or in seconds over the
traced window."""


def read(run, pattern, share=True):
    if run.trace is None:
        return None
    secs = run.trace.op_seconds(pattern)
    if not share:
        return secs
    return 100.0 * secs / run.trace.busy_s if run.trace.busy_s else None
