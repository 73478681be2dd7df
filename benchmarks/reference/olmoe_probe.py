"""What the serving probe loads for OLMoE: ``olmoe.py``'s plain forward,
and beside it a comparison of LOGITS.

The probe (``harness/probe.py``) sees the engine's tokens, not its
logits: it asks that the reference's logit of each token the engine
chose lie within a margin of the reference's largest.  That sees a fault
only where it turns a choice, and read on the chip (PERF.md, section 6,
PR 26) a per-head QK-norm, a dropped assignment or float8 products pass
nine probes of ten that way.  The engine hands out no logits and the
probe may not be edited, so the tight comparison is made here, where
the probe calls the reference: the PROGRAM's own forward pass (the model
``build_server`` built, rebuilt from the process's parsed arguments, on
the engine's own weights, in the precision it serves in) runs the
probe's whole sequence, and its logits are held against the plain
reference's at EVERY position.  It is the model's code that is compared
(``models/transformer.py``, ``models/moe.py``: the QK-norm, the router,
the gates, the dropless expert layer, which the engine's programs share
with the plain forward); the paged cache, the chunks and the sampler
stay the token comparison's.

The reading is one number: the root mean square, over positions and
vocabulary, of program minus reference, each position's difference
centred first (a constant added to a position's logits changes no
probability), as a share of the reference logits' standard deviation.
Beyond ``probe.logits_apart_tolerance`` of the configuration file (which
gives the readings it rests on) the logits come back as NaN, which the
probe takes for a failure: there is no other way to tell it.

``position_losses`` and ``cross_entropy`` are ``olmoe.py``'s.
"""

from __future__ import annotations

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_olmoe_plain",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "olmoe.py"))
plain = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(plain)

position_losses = plain.position_losses
cross_entropy = plain.cross_entropy

# the program's forward wants a sequence it can tile: padded to this
# (causal attention: what follows a position does not reach it)
PAD_TO = 128


def program_logits(params, tokens) -> jax.Array:
    """tokens [s] -> the program's own logits [s, vocab] (float32): the
    model this process serves, built as ``build_server`` builds it."""
    from finetune import model_provider
    from megatron_llm_tpu import global_vars

    model = model_provider(global_vars.get_args())
    s = len(tokens)
    padded = np.zeros((1, -(-s // PAD_TO) * PAD_TO), np.int32)
    padded[0, :s] = tokens

    @jax.jit
    def forward(p, t):
        out = model(p, t, rng_key=None, train=False)
        out = out[0] if isinstance(out, tuple) else out
        return out[0, :s].astype(jnp.float32)

    return forward(params, jnp.asarray(padded))


@jax.jit
def logits_apart(program, reference) -> jax.Array:
    """Root mean square of program minus reference, each position centred
    over the vocabulary, as a share of the reference's standard
    deviation."""
    d = program - reference
    d = d - jnp.mean(d, axis=-1, keepdims=True)
    return jnp.sqrt(jnp.mean(d * d)) / jnp.std(reference)


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None) -> jax.Array:
    """``olmoe.forward_logits``; on the probe's first pass (no choice
    turned) also the program's logits against them, NaN when apart."""
    tokens = np.asarray(tokens, np.int32)
    ours = None if turned else program_logits(weights.p, tokens)
    logits = plain.forward_logits(weights, cfg, tokens,
                                  router_margins=router_margins,
                                  turned=turned)
    if ours is None:
        return logits
    tolerance = float(cfg["probe"]["logits_apart_tolerance"])
    apart = float(logits_apart(ours, logits))
    print(json.dumps({"note": "probe_logits", "positions": len(tokens),
                      "logits_apart": apart, "tolerance": tolerance,
                      "within": apart <= tolerance}), flush=True)
    return logits if apart <= tolerance else logits * jnp.nan
