"""The standing served families trace the programs they traced before:
``tests/_program_fingerprints.py`` (a process of its own) hashes the jaxpr
of each family's prefill chunk and decode step, and ``TRACED`` holds what
it printed when the family's programs were last MEANT to change.  A PR
that adds a family records its two hashes here; a PR that means to
change a family's program records the new ones and says so.
"""

import json
import os
import subprocess
import sys

import pytest

# What ``tests/_program_fingerprints.py`` prints since PR 42, which MEANT
# to change every family's decode step and nothing of a chunk
# (``engine_prefill`` is PR 41's hash in every family): the step takes
# the key chain with the keys the host gave since the last launch and
# the rows they are for, and gives the chain back advanced only for the
# slots that decoded (``InferenceEngine._split_keys``: two selects over
# [S, 2] words).  PR 41 had changed both programs (the cache's write
# keeps the pool's own shape).  A PR that MEANS to change a family's
# program runs the script and records what it prints here.
#
# PR 60 MEANT every family's ``engine_prefill`` and no ``engine_decode``
# (all twelve decode hashes are the ones recorded before it): a chunk
# takes the stack's hidden states (``compute_logits=False``), and the
# output head (``language_model.lm_head_logits``) runs on its one live
# last row inside a ``cond`` on ``CachePlan.chunk_tables``'s ``LAST``.
TRACED = {
    "mistral": {"engine_prefill": "e89f5bbb8546232a",
                "engine_decode": "1271bc7d18f7d88f"},
    # PR 46 MEANT both programs of the seven sparse families and nothing
    # else (the dense family's are PR 44's): the dropless layer's combine
    # gathers the experts' rows once, in their own dtype, and adds the
    # choices in turn under the gates (``models/moe.py``, scope
    # ``moe_combine``)
    "mixtral": {"engine_prefill": "c42e7642cd9827a6",
                "engine_decode": "d108ab43dbc7ce8b"},
    "olmoe": {"engine_prefill": "072d0c6c379aad9b",
              "engine_decode": "971de090d62b6706"},
    # PR 57 MEANT Keye's attention under the choice (the shared paged walk
    # with a mask in place of ``dsa_attention.py``'s own) and these two
    # stand all the same: this script traces on the CPU, where every
    # family takes the dense path and no kernel is in the jaxpr.  What
    # the walk's OTHER callers trace is held kernel by kernel in
    # ``tests/test_paged_attention_kernel.py::WALKS_TRACED``
    "keye": {"engine_prefill": "9719fbef0eb96345",
             "engine_decode": "3c0e2663546ddcad"},
    "mellum": {"engine_prefill": "c863267696bf4033",
               "engine_decode": "1ac80699ca25a3e1"},
    "kanana": {"engine_prefill": "d64ba14dd7389876",
               "engine_decode": "9dbbd4c35a3d9c06"},
    "granite": {"engine_prefill": "46639ba62aa5adb6",
                "engine_decode": "76573d9f960581e5"},
    "nemotron_h": {"engine_prefill": "8fe97af4846e763a",
                   "engine_decode": "b085bbfea1bc2bd2"},
    # PR 47 brought this family and changed no other's: the gate, the
    # output norms and the types that rotate are off for every other
    # model, whose programs are the ones above
    "trinity": {"engine_prefill": "af57bfee8a1f2b1d",
                "engine_decode": "a30d0d0cca6cdb4d"},
    # PR 51 brought this family and changed no other's: the state group's
    # arrays by the layer's kind, the router's normaliser as data and the
    # pool's two-heads-a-row layout at 64-wide heads leave every program
    # above as it was
    "lfm2": {"engine_prefill": "99c411012370ea36",
             "engine_decode": "913f73debd40aff4"},
    # PR 54 brought this family and changed no other's: the queries, keys
    # and values of an attention layer come from ``qkv_heads`` now, which
    # a retention layer calls too, the same operations in the same order;
    # a paged model's chunk is still lent its pool
    "brumby": {"engine_prefill": "12a9bb620075f7ef",
               "engine_decode": "d8a3caf89969587f"},
    # PR 58 brought this family and changed no other's: Mamba's
    # convolution is a function both kinds call (``causal_conv_silu``: the
    # same operations in the same order), the shared MLP's gate, the
    # partial rotary of a typed layer and the chunk's q-block by a head's
    # lane rows are off, or as they were, for every model above
    "qwen3_next": {"engine_prefill": "84eb24dbccf04c14",
                   "engine_decode": "bb999ef74dc237e8"},
    # recorded by PR 61, which brought the family: a compressed query and
    # the selection over latents; ``attend_latent``'s ``index`` is None
    # without an indexer and ``rot_d`` None without
    # ``dsa_index_rope_dim``, so Kanana's and Keye's above are as they
    # were
    "glm5": {"engine_prefill": "cd0bdca723895f61",
             "engine_decode": "3f2a1ce8782fedb1"},
    # recorded by PR 63, which brought the family: a stack run
    # ``loop_steps`` times over a pool a pass.  With ``loop_steps`` 1 the
    # serving loop is one pass of no scope and ``init_pools`` builds
    # ``num_layers`` pools, so every program above is as it was
    "ouro": {"engine_prefill": "b3641cd2d3e4e285",
             "engine_decode": "b008b4e8e9d4e81c"},
}


@pytest.fixture(scope="module")
def traced():
    proc = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "_program_fingerprints.py")],
        capture_output=True, text=True, timeout=280)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert proc.returncode == 0 and lines, proc.stderr[-2000:]
    return json.loads(lines[-1])


@pytest.mark.parametrize("name", sorted(TRACED))
def test_a_standing_family_traces_the_program_it_traced_before(traced, name):
    assert traced[name] == TRACED[name], (
        f"{name}'s engine programs are not the recorded ones: if that was "
        "meant, record tests/_program_fingerprints.py's output in TRACED")
