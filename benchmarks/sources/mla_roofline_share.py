"""The latent-attention kernels' share of their roofline: the least time
the chip could take for what the launches of one ``program`` ('decode' or
'prefill') that began in the traced stretch had to read and compute
(``harness/mla_roofline.py``, from each launch record's own
``mla_keys_live`` / ``mla_pairs`` and its chunk's ``start`` / ``valid``)
over the device seconds of the operations matching ``pattern`` in the
trace.  The kernels carry their program in their names
(``mla_attention_decode``, ``mla_attention_prefill``), so each pattern's
seconds are one program's.

Reads as nothing where there is no trace, no ring, records without the
fields (the parent of the PR that brought them), a model without a
latent pool (the fields 0 throughout, or a configuration without
``kv_lora_rank``), or no operation of that name."""
import importlib

_mla = importlib.import_module("harness.mla_roofline")
_probe = importlib.import_module("harness.probe")
_loop = importlib.import_module("harness.spec").load_module(
    "sources", "loop_phase")

KINDS = {"decode": ("decode",), "prefill": ("prefill",)}


def least_total(run, records, program):
    """Summed least seconds of the records' latent attention, or None
    where a record lacks the fields or no launch attended a latent."""
    cfg = _probe.reference_cfg(run)
    if not cfg.get("kv_lora_rank"):
        return None
    layers = int(cfg["num_hidden_layers"])
    total = 0.0
    for r in records:
        keys = getattr(r, "mla_keys_live", None)
        pairs = getattr(r, "mla_pairs", None)
        if keys is None or pairs is None:
            return None
        if program == "decode" and keys:
            total += _mla.decode_least_seconds(cfg, keys, run.peaks)
        elif program == "prefill" and pairs:
            total += _mla.prefill_least_seconds(
                cfg, pairs, r.start + r.valid, layers, run.peaks)
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
