"""Tokens per optimizer step times the steps completed in the window,
over the window's seconds (first to last step boundary), all chips."""


def read(run):
    w = run.train_window
    if not w or not w.get("steps") or w["seconds"] <= 0:
        return None
    return w["steps"] * w["tokens_per_step"] / w["seconds"]
