"""Sum of the engine counters' growth inside the window, per second."""


import importlib

_w = importlib.import_module("harness.window")


def read(run, counters):
    if run.window is None:
        return None
    return _w.counter_rate(run.window, counters)
