"""Peak bytes in use after the window (before the reference runs),
the fullest device, times ``scale`` (1e-9 = GB)."""


def read(run, scale=1e-9):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes * scale
