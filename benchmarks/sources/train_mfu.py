"""Model FLOP/s utilisation: the benchmark's own FLOPs per token
(forward and backward, no recomputation) times tokens per second, over
chips times the chip's peak."""
import importlib

_roof = importlib.import_module("harness.roofline")
_probe = importlib.import_module("harness.probe")


def read(run):
    w = run.train_window
    if not w or not w.get("steps") or run.peaks is None:
        return None
    cfg = _probe.reference_cfg(run)
    seq = int(w["seq_length"])
    rate = w["steps"] * w["tokens_per_step"] / w["seconds"]
    return (100.0 * _roof.train_flops_per_token(cfg, seq) * rate
            / (w["chips"] * run.peaks["bf16_flops_per_s"]))
