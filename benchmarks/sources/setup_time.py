"""Seconds of set-up: ``part`` = 'total' (process start to the window's
first instant) or 'compile' (backend compile seconds heard from
jax.monitoring before the window opened; a cache hit counts its few
milliseconds)."""


def read(run, part="total"):
    if run.setup_s is None:
        return None
    if part == "total":
        return run.setup_s
    if part == "compile":
        return run.meter.seconds_before(run.setup_parts["window_opened_at"])
    raise ValueError(part)
