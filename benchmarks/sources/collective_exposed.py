"""Time inside collective operations during which no other operation
runs on that device, as a percentage of the traced window; the worst
device."""


def read(run):
    if run.trace is None or len(run.trace.devices) < 2:
        return None
    exposed = run.trace.collective_exposed_by_device()
    return 100.0 * max(exposed.values()) / run.trace.window_s
