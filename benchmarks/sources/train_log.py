"""A key of the training log's records inside the window: its median
(or 'mean', 'last'), times ``scale``."""
import statistics


def read(run, key, stat="median", scale=1.0):
    w = run.train_window
    if not w or not w.get("steps"):
        return None
    rows = run.train_log[w["warm_steps"]:w["warm_steps"] + w["steps"]]
    values = [r[key] for r in rows if r.get(key) is not None]
    if not values:
        return None
    pick = {"median": statistics.median, "mean": statistics.fmean,
            "last": lambda v: v[-1]}[stat]
    return pick(values) * scale
