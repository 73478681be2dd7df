"""Pluggable validation metrics.

Reference: ``megatron/metrics.py:11-110`` — a ``METRICS`` registry mapping
name -> callable(MetricInput) -> dict, selected with ``--metrics
[all|names]`` (arguments.py:550) and evaluated inside ``loss_func`` during
validation (finetune.py:211-217).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional

import jax.numpy as jnp

from megatron_llm_tpu.ops.cross_entropy import vocab_parallel_max_indices


@dataclass
class MetricInput:
    """reference: metrics.py MetricInput."""

    batch: dict                 # tokens/labels/loss_mask (+ masks)
    logits: jnp.ndarray         # [b, s, V]
    avg_loss: jnp.ndarray       # scalar masked-mean CE


def perplexity(inp: MetricInput) -> Dict[str, jnp.ndarray]:
    return {"perplexity": jnp.exp(inp.avg_loss)}


def accuracy(inp: MetricInput) -> Dict[str, jnp.ndarray]:
    """Top-1 next-token accuracy over unmasked positions
    (reference uses vocab_parallel_max_indices, metrics.py)."""
    pred = vocab_parallel_max_indices(inp.logits)
    labels = inp.batch["labels"]
    mask = inp.batch.get("loss_mask")
    correct = (pred == labels).astype(jnp.float32)
    if mask is not None:
        mask = (mask > 0).astype(jnp.float32)
        return {"accuracy": jnp.sum(correct * mask)
                / jnp.maximum(jnp.sum(mask), 1.0)}
    return {"accuracy": jnp.mean(correct)}


def count_loss_mask(inp: MetricInput) -> Dict[str, jnp.ndarray]:
    mask = inp.batch.get("loss_mask")
    if mask is None:
        return {"count_loss_mask": jnp.float32(0.0)}
    return {"count_loss_mask": jnp.mean(jnp.sum(mask > 0, axis=-1)
                                        .astype(jnp.float32))}


METRICS: Dict[str, Callable[[MetricInput], Dict[str, jnp.ndarray]]] = {
    "perplexity": perplexity,
    "accuracy": accuracy,
    "count_loss_mask": count_loss_mask,
}


def recovery_counters() -> Dict[str, int]:
    """Host-side fault-tolerance counters (rewinds, save_retries,
    watchdog_fires, signal_saves) — merged into the training log and
    the TB/W&B stream.  Re-exported here so
    metrics consumers need not import resilience."""
    from megatron_llm_tpu.resilience import recovery_counters as rc

    return rc()


def telemetry_summary() -> Optional[Dict[str, float]]:
    """The active run's aggregate telemetry (mean MFU, mean
    tokens/sec/device, mean step time) from the --structured_log_dir
    stream; None when no stream is installed.  Re-exported here (like
    ``recovery_counters``) so metrics consumers need not import
    telemetry."""
    from megatron_llm_tpu.telemetry import run_summary

    return run_summary()


def get_metric(name: str):
    if name not in METRICS:
        raise KeyError(
            f"unknown metric {name!r}; available: {sorted(METRICS)}"
        )
    return METRICS[name]


def resolve_metric_names(names):
    if names and "all" in names:
        return sorted(METRICS)
    return list(names or [])
