"""A fingerprint of the programs each standing served family TRACES:
for a tiny engine of the family, the jaxpr of its prefill chunk and of
its decode step, hashed.  Run as a script (a process of its own: no mesh
another test left behind, one CPU device) it prints ``{family: {program:
hash}}``; ``tests/test_program_fingerprints.py`` holds what it prints to
the hashes recorded when the family's programs were last meant to
change."""
import hashlib
import importlib
import json
import os
import re
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FAMILIES = ("mistral", "mixtral", "olmoe", "keye", "mellum", "kanana",
            "granite", "nemotron_h", "trinity", "lfm2", "brumby",
            "qwen3_next", "glm5", "ouro")


def fingerprints(name: str) -> dict:
    import jax

    from megatron_llm_tpu.models import MODEL_REGISTRY
    from megatron_llm_tpu.serving import EngineConfig, InferenceEngine

    # through the registry: a family built lazily (granite) is no
    # attribute of the package
    config = getattr(importlib.import_module(
        "megatron_llm_tpu.models." + name), name + "_config")
    model = MODEL_REGISTRY[name](config("tiny", use_flash_attn=False))
    eng = InferenceEngine(
        model, model.init(jax.random.PRNGKey(0)),
        EngineConfig(num_slots=2, block_size=16, max_model_len=64,
                     prefill_chunk=16, preemption=False))
    eng.warmed_up = True        # the arguments as a launch builds them
    out = {}
    for program, impl in (("engine_prefill", eng._prefill_impl),
                          ("engine_decode", eng._decode_impl)):
        text = str(jax.make_jaxpr(impl)(*eng._program_arguments()[program]))
        out[program] = hashlib.sha256(
            re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()[:16]
    return out


if __name__ == "__main__":
    print(json.dumps({name: fingerprints(name) for name in FAMILIES}))
