"""The share of their roofline of the kernels of the selection over
latents: the least time the chip could take for what the launches of one
``program`` ('decode' or 'prefill') that began in the traced stretch had
to read and compute (``harness/dsa_latent_roofline.py``, from each launch
record's own ``dsa_keys_live`` / ``dsa_keys_selected``, or its chunk's
``start`` / ``valid``) over the device seconds of the operations matching
``pattern`` in the trace.  The kernels carry their program in their
names (``..._decode``, ``..._prefill*``), so each pattern's seconds are
one program's.

Reads as nothing where there is no trace, no ring, records without the
two fields, a configuration without a latent or without an indexer's
flat keys (``kv_lora_rank``, ``index_topk``), a model that selects
nothing (both fields 0 throughout), or no operation of that name."""
import importlib

_cost = importlib.import_module("harness.dsa_latent_roofline")
_probe = importlib.import_module("harness.probe")
_loop = importlib.import_module("harness.spec").load_module(
    "sources", "loop_phase")

KINDS = {"decode": ("decode",), "prefill": ("prefill",)}


def least_total(run, records, program):
    """Summed least seconds of the records' selection over latents, or
    None where a record lacks the fields or nothing selected."""
    cfg = _probe.reference_cfg(run)
    if not cfg.get("kv_lora_rank") or not cfg.get("index_topk"):
        return None
    total = 0.0
    for r in records:
        live = getattr(r, "dsa_keys_live", None)
        chosen = getattr(r, "dsa_keys_selected", None)
        if live is None or chosen is None:
            return None
        if not live:
            continue
        if program == "decode":
            total += _cost.decode_least_seconds(cfg, live, chosen, run.peaks)
        else:
            total += _cost.prefill_least_seconds(cfg, r.start, r.valid,
                                                 run.peaks)
    return total or None


def read(run, program, pattern):
    t0, t1 = run.setup_parts.get("traced", (None, None))
    if run.trace is None or t0 is None or t1 is None or run.peaks is None:
        return None
    measured = run.trace.op_seconds(pattern)
    least = least_total(run, _loop.launches(t0, t1, KINDS[program]),
                        program)
    if not measured or least is None:
        return None
    return 100.0 * least / measured
