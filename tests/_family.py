"""One harness behind the served families' test files.

A family that is served through the engine and held to a plain reference
under ``benchmarks/reference/`` is a ROW of ``FAMILIES``: how its tiny
model is built, how the reference reads its config, its tolerance, its
engine's default shape and what its mixer leaves in a slot.  The helpers
every family's file used to copy (``load``, ``tokens``, ``serve``,
``tapped``, the engine's keywords) are here once, and so are the three
questions every family is asked under the same name:

* ``full_forward_is_the_references``: the plain forward's logits;
* ``chunked_prefill_then_decode_is_one_forward``: the ENGINE's own
  programs, chunk after chunk and step after step, against ONE forward;
* ``a_slot_is_reused``: a slot taken again answers as one never taken;

and a fourth that six of them are: ``a_named_fault_is_told``.

Each family's file keeps its own test functions, ids and parameter lists
and calls them (``--dist loadfile`` balances by file); what is a
family's own stays in its file, on the fixtures below.

ENGINES ARE KEPT for the module (``Engines``, the ``engines`` fixture of
``conftest.py``): an ``InferenceEngine`` is new jitted closures, so a
second engine of a shape traces, lowers and compiles what the first one
did (15-25 s of a 20-30 s test).  ``engines(name, **kw)`` hands back the
module's engine of that family and those keywords, drained, and builds
it where there is none.  A test that asserts what only a NEW engine can
show (``engines.fresh``) builds one.

A new family: a row here, a file of its own mechanism's tests on these
fixtures, and its hashes in ``tests/test_program_fingerprints.py``.
"""

import contextlib
import dataclasses
import functools
import importlib
import importlib.util
import copy
import os
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from megatron_llm_tpu import config as C
from megatron_llm_tpu.models.language_model import language_model_forward
from megatron_llm_tpu.ops import paged_kv
from megatron_llm_tpu.ops.pallas import grouped_matmul as gm
from megatron_llm_tpu.ops.pallas import paged_attention as pa
from megatron_llm_tpu.serving import (EngineConfig, InferenceEngine,
                                      SamplingParams)

REFERENCE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "reference")


@functools.lru_cache(maxsize=None)
def load(name):
    """``benchmarks/reference/<name>.py``, the file the benchmark's probe
    loads, by path."""
    spec = importlib.util.spec_from_file_location(
        "ref_" + name, os.path.join(REFERENCE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tokens(n, seed=3, vocab=512):
    return np.random.default_rng(seed).integers(1, vocab - 1, n).tolist()


def shake(params, key, wide=(), noisy=("scale",),
          kernels=("kernel", "w_in", "w_out"), router=2.0):
    """Seeded N(0, 0.02) weights make attention nearly uniform and every
    norm's scale is 1 at init: a test that must tell a window from the
    whole context, selected keys from all keys, one rotary variant or one
    norm from another needs larger projections and scales that differ.
    The leaves named in ``noisy`` get noise of 0.3, those in ``wide``
    (the embedding, an untied head) 8 times their size, the ``kernels``
    6 times: the experts too, or the MLP adds next to nothing; the
    router ``router`` times: gates that are nearly one-hot would hide
    whether the chosen ones are renormalised.  A convolution's taps and
    a choice bias stay as wide as they were drawn."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        names = [getattr(p, "key", None) for p in path]
        if set(noisy) & set(names):
            leaf = leaf + 0.3 * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape, leaf.dtype)
        elif set(wide) & set(names):
            leaf = leaf * 8.0
        elif set(kernels) & set(names) and "conv" not in names[-2:]:
            leaf = leaf * (router if "router" in names else 6.0)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


# ---------------------------------------------------------------------------
# how each reference reads the program's config
# ---------------------------------------------------------------------------

_HF_TYPES = {"sliding": "sliding_attention", "full": "full_attention",
             "attention": "full_attention", "conv": "conv"}


def _whole_depth(cfg, names=None):
    period = [names[t] if names else t for t in cfg.layer_types]
    return period * (cfg.num_layers // len(period))


def _granite_cfg(cfg, chunk):
    return {"num_hidden_layers": cfg.num_layers,
            "layer_types": _whole_depth(cfg),
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_attention_heads_kv,
            "rms_norm_eps": cfg.layernorm_epsilon,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "logits_scaling": cfg.logits_scaling,
            "attention_multiplier": cfg.attention_multiplier,
            "mamba_n_heads": cfg.mamba_n_heads,
            "mamba_d_head": cfg.mamba_d_head,
            "mamba_d_state": cfg.mamba_d_state,
            "mamba_n_groups": cfg.mamba_n_groups,
            "mamba_d_conv": cfg.mamba_d_conv,
            "num_experts_per_tok": cfg.moe_top_k,
            "num_local_experts": cfg.num_experts,
            "experts_first": cfg.moe_experts_first,
            "vocab_size": cfg.padded_vocab_size,
            "fault_chunk": chunk}


def _nemotron_h_cfg(cfg, chunk):
    letters = {v: k for k, v in C.PATTERN_LETTERS.items()}
    return {"num_hidden_layers": cfg.num_layers,
            "hybrid_override_pattern": "".join(
                letters[t] for t in cfg.layer_types),
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_attention_heads_kv,
            "layer_norm_epsilon": cfg.layernorm_epsilon,
            "rope_theta": cfg.rope_theta,
            "mamba_num_heads": cfg.mamba_n_heads,
            "mamba_head_dim": cfg.mamba_d_head,
            "ssm_state_size": cfg.mamba_d_state,
            "n_groups": cfg.mamba_n_groups,
            "conv_kernel": cfg.mamba_d_conv,
            "num_experts_per_tok": cfg.moe_top_k,
            "n_routed_experts": cfg.num_experts,
            "routed_scaling_factor": cfg.moe_routed_scale,
            "experts_first": cfg.moe_experts_first,
            "vocab_size": cfg.padded_vocab_size,
            "fault_chunk": chunk}


def _trinity_cfg(cfg, chunk):
    return {"num_hidden_layers": cfg.num_layers,
            "hidden_size": cfg.hidden_size,
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_attention_heads_kv,
            "rms_norm_eps": cfg.layernorm_epsilon,
            "rope_theta": cfg.rope_theta,
            "sliding_window": cfg.sliding_window_size,
            "layer_types": _whole_depth(cfg, _HF_TYPES),
            "num_dense_layers": cfg.moe_first_dense_layers,
            "num_experts": cfg.num_experts,
            "experts_first": cfg.moe_experts_first,
            "num_experts_per_tok": cfg.moe_top_k,
            "route_norm": cfg.norm_topk_prob,
            "route_scale": cfg.moe_routed_scale,
            "mup_enabled": True,
            "vocab_size": cfg.padded_vocab_size}


def _lfm2_cfg(cfg, chunk):
    return {"num_hidden_layers": cfg.num_layers,
            "layer_types": [_HF_TYPES[t] for t in cfg.layer_types],
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_attention_heads_kv,
            "norm_eps": cfg.layernorm_epsilon,
            "rope_theta": cfg.rope_theta,
            "conv_L_cache": cfg.conv_taps,
            "num_dense_layers": cfg.moe_first_dense_layers,
            "num_experts_per_tok": cfg.moe_top_k,
            "routed_scaling_factor": cfg.moe_routed_scale,
            "vocab_size": cfg.padded_vocab_size,
            "fault_chunk": chunk}


def _brumby_cfg(cfg, chunk):
    return dict(num_attention_heads=cfg.num_attention_heads,
                num_key_value_heads=cfg.num_attention_heads_kv,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                rms_norm_eps=cfg.layernorm_epsilon,
                num_hidden_layers=cfg.num_layers,
                intermediate_size=cfg.ffn_hidden_size,
                vocab_size=cfg.padded_vocab_size,
                bytes={"phi_rows": (cfg.head_dim // 2 + 1) * cfg.head_dim})


def _qwen3_next_cfg(cfg, chunk):
    return {"num_hidden_layers": cfg.num_layers,
            "full_attention_interval": len(cfg.layer_types),
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_attention_heads_kv,
            "head_dim": cfg.head_dim,
            "partial_rotary_factor": cfg.rotary_percent,
            "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.layernorm_epsilon,
            "linear_num_key_heads": cfg.delta_key_heads,
            "linear_num_value_heads": cfg.delta_value_heads,
            "linear_key_head_dim": cfg.delta_key_dim,
            "linear_value_head_dim": cfg.delta_value_dim,
            "linear_conv_kernel_dim": cfg.delta_conv_taps,
            "num_experts": cfg.num_experts,
            "experts_first": cfg.moe_experts_first,
            "num_experts_per_tok": cfg.moe_top_k,
            "vocab_size": cfg.padded_vocab_size,
            "fault_chunk": chunk}


def _ouro_cfg(cfg, chunk):
    return {"num_hidden_layers": cfg.num_layers,
            "total_ut_steps": cfg.loop_steps,
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_attention_heads_kv,
            "rms_norm_eps": cfg.layernorm_epsilon,
            "rope_theta": cfg.rope_theta,
            "vocab_size": cfg.padded_vocab_size}


def _kanana_cfg(cfg, chunk):
    return {"num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_attention_heads,
            "rms_norm_eps": cfg.layernorm_epsilon,
            "num_experts_per_tok": cfg.moe_top_k,
            "norm_topk_prob": cfg.norm_topk_prob,
            "vocab_size": cfg.padded_vocab_size,
            "first_k_dense_replace": cfg.moe_first_dense_layers,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim,
            "rope_theta": cfg.rope_theta,
            "routed_scaling_factor": cfg.moe_routed_scale}


def _glm5_cfg(cfg, chunk):
    return {"num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_attention_heads,
            "rms_norm_eps": cfg.layernorm_epsilon,
            "num_experts_per_tok": cfg.moe_top_k,
            "n_routed_experts": cfg.num_experts,
            "experts_first": cfg.moe_experts_first,
            "norm_topk_prob": cfg.norm_topk_prob,
            "vocab_size": cfg.padded_vocab_size,
            "first_k_dense_replace": cfg.moe_first_dense_layers,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim,
            "index_n_heads": cfg.dsa_index_heads,
            "index_topk": cfg.dsa_topk,
            "rope_parameters": {"rope_theta": cfg.rope_theta},
            "routed_scaling_factor": cfg.moe_routed_scale}


def _keye_cfg(cfg, chunk):
    return {"num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_attention_heads_kv,
            "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.layernorm_epsilon,
            "num_local_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.moe_top_k,
            "vocab_size": cfg.padded_vocab_size,
            "sa_config": {"topk": cfg.dsa_topk},
            "rope_scaling": {"mrope_section": list(cfg.rope_sections)}}


def _mellum_cfg(cfg, chunk):
    f, orig, fast, slow, att = cfg.rope_yarn_scaling
    return {"num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_attention_heads_kv,
            "rms_norm_eps": cfg.layernorm_epsilon,
            "num_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.moe_top_k,
            "norm_topk_prob": cfg.norm_topk_prob,
            "vocab_size": cfg.padded_vocab_size,
            "sliding_window": cfg.sliding_window_size,
            "layer_types": _whole_depth(cfg, _HF_TYPES),
            "mlp_layer_types": ["sparse"] * cfg.num_layers,
            "rope_parameters": {
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": cfg.rope_theta,
                    "factor": f, "original_max_position_embeddings": orig,
                    "beta_fast": fast, "beta_slow": slow,
                    "attention_factor": att},
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": cfg.rope_theta}}}


# ---------------------------------------------------------------------------
# what a family's mixer leaves in a slot, beside what the reference carries
# ---------------------------------------------------------------------------

def _slot_arrays(name):
    """Each state layer's array ``name`` in a slot against the list the
    reference's ``states=`` fills, a layer each (a state-space layer's
    ``ssm_state`` after its last token, a conv layer's columns)."""
    def held(b, eng, slot, seq):
        theirs = []
        b.ref.forward_logits(b.weights, b.cfg, seq, rows=[len(seq) - 1],
                             states=theirs)
        mine = [np.asarray(p[name][slot], np.float32)
                for p in eng._st.pages if paged_kv.is_state(p)]
        assert len(mine) == len(theirs) > 0
        return list(zip(mine, theirs))
    return held


def _retention_state(b, eng, slot, seq):
    """The first layer's ``ret_state`` and ``ret_sum`` against the state
    of the keys, values and gates its mixer saw in the reference, in the
    program's order of a key's columns."""
    kept = {0: {}}
    b.ref.forward_logits(b.weights, b.cfg, seq, rows=[len(seq) - 1],
                         kept=kept)
    k = kept[0]
    columns = load("brumby_from_program").state_columns(
        int(b.cfg["head_dim"]))
    S, z = b.ref.state_of(k["k"][..., columns], k["v"], k["a"])
    pool = eng._st.pages[0]
    return [(np.asarray(pool["ret_state"][slot]), S),
            (np.asarray(pool["ret_sum"][slot]), z)]


# ---------------------------------------------------------------------------
# the table
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Family:
    """A row: the module under ``megatron_llm_tpu/models/`` and the
    reference under ``benchmarks/reference/`` carry the family's name."""

    ref_cfg: Callable           # (program's config, chunk) -> reference's
    tol: float                  # logits, float32 on both sides
    chunk: int = 32
    # keywords of the ``tiny`` preset beside ``use_flash_attn=False``
    tiny: dict = dataclasses.field(default_factory=dict)
    # another size of the tiny model a file asks for by name: its
    # keywords in ``tiny``'s place
    sizes: dict = dataclasses.field(default_factory=dict)
    shake: Optional[dict] = dataclasses.field(default_factory=dict)
    # engine keywords of the default shape, over ``ENGINE``
    engine: dict = dataclasses.field(default_factory=dict)
    # what a finished request's slot holds: (row, eng, slot, seq) ->
    # [(the engine's array, the reference's)]; None: pages only
    state: Optional[Callable] = None
    vocab: int = 512
    # the precision the plain forward is run at (None: the default)
    precision: Optional[str] = None
    # the modules whose kernels the ``on`` cases run in interpret mode
    interpret: tuple = (pa,)


# a long deadline, not the default 120 s: a request served through an
# interpreted kernel must not expire by the wall clock of a loaded
# machine, and a hang must still fail
ENGINE = dict(num_slots=2, block_size=8, max_model_len=192,
              default_deadline_secs=600.0)
BS = ENGINE["block_size"]
_SHARE = dict(moe_experts_first=2)      # experts 2-5 of the router's 8
_WIDE = dict(wide=("embedding", "lm_head"))

FAMILIES = {
    "granite": Family(_granite_cfg, 5e-5, tiny=_SHARE, shake=_WIDE,
                      engine=dict(preemption=False),
                      state=_slot_arrays("ssm_state")),
    "nemotron_h": Family(_nemotron_h_cfg, 1e-4, tiny=_SHARE, shake=_WIDE,
                         engine=dict(preemption=False),
                         state=_slot_arrays("ssm_state"),
                         interpret=(pa, gm)),
    # (depth, experts held of the router's 8): the cell's own shape and a
    # deeper one that holds all
    "trinity": Family(_trinity_cfg, 5e-4, chunk=16,
                      tiny=dict(num_layers=8, num_experts=4,
                                moe_router_experts=8),
                      sizes={"depth12_whole": dict(num_layers=12)}),
    "lfm2": Family(_lfm2_cfg, 1e-4, shake=_WIDE,
                   engine=dict(preemption=False),
                   state=_slot_arrays("conv_state")),
    "brumby": Family(_brumby_cfg, 2e-5, shake=None, vocab=256,
                     precision="highest", state=_retention_state,
                     engine=dict(num_slots=3, max_model_len=256,
                                 preemption=False)),
    # experts 4-11 of the router's 16: a share that starts mid-way
    "qwen3_next": Family(_qwen3_next_cfg, 2e-4,
                         tiny=dict(moe_experts_first=4), shake=_WIDE,
                         engine=dict(preemption=False),
                         state=_slot_arrays("delta_state")),
    "kanana": Family(_kanana_cfg, 2e-4, chunk=16),
    # experts 2-5 of the router's 8; the indexer's projections and norms
    # shaken as Keye's, or every score is near zero and the choice is by
    # position
    "glm5": Family(_glm5_cfg, 2e-4, chunk=16,
                   tiny=dict(num_experts=4, moe_router_experts=8,
                             moe_experts_first=2),
                   shake=dict(noisy=("scale", "bias")),
                   engine=dict(max_model_len=192)),
    "keye": Family(_keye_cfg, 2e-4, chunk=16,
                   shake=dict(noisy=("scale", "bias"), kernels=("kernel",),
                              router=6.0),
                   engine=dict(max_model_len=96)),
    "mellum": Family(_mellum_cfg, 2e-4, chunk=16),
    # three layers four times; the same three layers three times
    "ouro": Family(_ouro_cfg, 2e-4, chunk=16, shake=_WIDE,
                   sizes={"two_layers_three_passes": dict(num_layers=2,
                                                          loop_steps=3)}),
}


class Built(NamedTuple):
    """A family's tiny model and its reference: what every file's
    ``family`` fixture gives."""

    model: Any
    params: Any
    ref: Any            # benchmarks/reference/<name>.py
    weights: Any        # <name>_from_program.ProgramWeights of ``params``
    cfg: dict           # the reference's config of ``model.cfg``


def config(name, size=None, **kw):
    """The family's tiny config as its tests build it (``size``: another
    of the row's ``sizes``), with ``kw`` over it."""
    fam = FAMILIES[name]
    preset = getattr(importlib.import_module(
        "megatron_llm_tpu.models." + name), name + "_config")
    return preset("tiny", **{"use_flash_attn": False,
                             **(fam.sizes[size] if size else fam.tiny), **kw})


def shaken(name, model):
    """``model``'s seeded weights as the family's tests shake them."""
    params = model.init(jax.random.PRNGKey(0))
    fam = FAMILIES[name]
    return params if fam.shake is None else shake(
        params, jax.random.PRNGKey(1), **fam.shake)


@functools.lru_cache(maxsize=None)
def built(name, size=None) -> Built:
    from megatron_llm_tpu.models import MODEL_REGISTRY

    fam = FAMILIES[name]
    model = MODEL_REGISTRY[name](config(name, size))
    params = shaken(name, model)
    cfg = fam.ref_cfg(model.cfg, fam.chunk)
    weights = load(name + "_from_program").ProgramWeights(params, cfg)
    return Built(model, params, load(name), weights, cfg)


@functools.lru_cache(maxsize=None)
def _plain_forward(name, size):
    b = built(name, size)
    return jax.jit(lambda toks: b.model(b.params, toks, train=False)[0])


@functools.lru_cache(maxsize=None)
def _plain_logits(name, size, toks):
    fam = FAMILIES[name]
    with (jax.default_matmul_precision(fam.precision) if fam.precision
          else contextlib.nullcontext()):
        return np.asarray(_plain_forward(name, size)(
            jnp.asarray([toks], jnp.int32)))


def plain_logits(name, toks, size=None):
    """The program's plain (cache-less) forward over ``toks``, [n, vocab]:
    one program a length (run eagerly the stack is dispatched an
    operation at a time, 2-3 s a call), and one call a sequence: the
    named faults all read the same."""
    return _plain_logits(name, size, tuple(toks))


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def engine(model, params, **kw) -> InferenceEngine:
    """A new engine of ``ENGINE``'s shape over any model, ``kw`` over
    it."""
    return InferenceEngine(model, params, EngineConfig(**{**ENGINE, **kw}))


def serve(eng, prompt, new, each_step=None):
    """One greedy request stepped by hand, the block manager's invariants
    checked after every step (``each_step(eng, req)`` after them).  The
    request comes back with the slot it held (the scheduler takes a
    finished request's away)."""
    req = eng.submit(prompt, SamplingParams(max_new_tokens=new,
                                            temperature=0.0))
    slot = None
    while req.finish_reason is None:
        assert eng.step()
        eng.blocks.check_invariants()
        slot = req.slot if req.slot is not None else slot
        if each_step is not None:
            each_step(eng, req)
    req.slot = slot
    return req


def is_greedy(model, params, prompt, answer) -> bool:
    """Whether ``answer`` is the plain forward's greedy continuation of
    ``prompt``: each of its tokens the forward's choice after the ones
    before it, read off ONE forward over both (a token at a time is a
    compile a length)."""
    seq = list(prompt) + list(answer)
    logits = model(params, jnp.asarray([seq[:-1]], jnp.int32),
                   train=False)[0]
    return np.asarray(logits[len(prompt) - 1:].argmax(-1)).tolist() == list(
        answer)


def counted(eng):
    """What ``eng`` counts from here on (a kept engine has counted
    before): call the result for ``stats()`` less what it read now, field
    by field where they are numbers, and the launches' records since."""
    before, seen = eng.stats(), eng.loop_profiler.launches()

    def since():
        stats = {k: v - before[k] if type(v) in (int, float) else v
                 for k, v in eng.stats().items()}
        return stats, eng.loop_profiler.records()[seen:]

    return since


def kernels(kernel) -> dict:
    """The engine keywords of both programs ``on`` or ``off`` the
    kernels' path."""
    return dict(paged_kernel=kernel, prefill_kernel=kernel)


def _drained(eng) -> bool:
    """No request live or waiting, every slot and page free, the pool
    held (a launch that raised may have consumed it), the tables sound."""
    stats = eng.blocks.stats()
    try:
        eng.blocks.check_invariants()
    except AssertionError:
        return False
    return not (eng.scheduler.has_work() or stats["slots_in_use"]
                or stats["blocks_in_use"]
                or stats.get("window_blocks_in_use")
                or eng._pool_consumed(eng._st))


class Engines:
    """A module's engines of the families' tiny models, by family, size
    and keywords; ``patch`` is the running test's ``monkeypatch``."""

    def __init__(self, patch=None):
        self.kept = {}          # (family, size, keywords) -> engine
        self.step_logits = {}   # id(engine) -> its tap's program
        self.patch = patch

    def during(self, patch) -> "Engines":
        """The same engines for one test: what it lays over an engine (a
        tap, interpret mode) is undone when it ends."""
        view = copy.copy(self)
        view.patch = patch
        return view

    def _interpreted(self, name, kw):
        """The kernels of an ``on`` engine in interpret mode for as long
        as the test that took it runs (a program of a new shape may be
        traced at any launch).  Not for the module: a neighbour that
        builds an engine of its own under ``auto``, or a cache by hand,
        asks ``kernel_available()``."""
        if "on" in (kw.get("paged_kernel"), kw.get("prefill_kernel")):
            for module in FAMILIES[name].interpret:
                self.patch.setattr(module, "_INTERPRET", True)

    def fresh(self, name, size=None, **kw) -> InferenceEngine:
        """A new engine of the family's default shape, ``kw`` over it."""
        self._interpreted(name, kw)
        fam, b = FAMILIES[name], built(name, size)
        return engine(b.model, b.params, **{
            "prefill_chunk": fam.chunk, **fam.engine, **kw})

    def __call__(self, name, size=None, **kw) -> InferenceEngine:
        """The module's engine of this shape, drained; built where there
        is none, and again where a test left it with work, pages or a
        consumed pool: that test has failed, its neighbours must not."""
        self._interpreted(name, kw)
        key = (name, size, tuple(sorted(kw.items())))
        eng = self.kept.get(key)
        if eng is None or not _drained(eng):
            eng = self.kept[key] = self.fresh(name, size, **kw)
        return eng

    def tapped(self, eng, by_slot=False) -> dict:
        """The engine's programs with their logits kept, for this test:
        the prefill step returns its chunk's last live row, kept by its
        position (the tap tells EVERY chunk it ends its context, so each
        runs the head the engine asks only of a request's last:
        ``CachePlan.chunk_tables``); the decode step is run without its
        sampler on the step's own arguments, as the benchmark's probe
        does, each live row kept by its position (``by_slot``: by its
        slot and position, where rows of two requests may stand at
        one)."""
        got = {}
        prefill, decode = eng._prefill_step, eng._decode_step
        step_logits = self.step_logits.get(id(eng))
        if step_logits is None:
            # one program an engine: run eagerly, the step's forward is
            # compiled an operation at a time, 13 s where this takes 2
            def step_logits(params, pages, last, ctx, tables, active):
                caches = paged_kv.step_caches(pages, tables, ctx, active,
                                              eng.paged_kernel,
                                              eng._layer_groups)
                return language_model_forward(
                    params, last[:, None], ctx[:, None], None,
                    eng.model.cfg, rng_key=None, train=False,
                    kv_caches=caches)[0][:, 0]

            step_logits = self.step_logits[id(eng)] = jax.jit(step_logits)

        def tapped_prefill(params, pages, toks, start, valid, table):
            out = prefill(params, pages, toks, start, valid,
                          {**table, paged_kv.LAST: np.bool_(True)})
            got[int(start) + int(valid) - 1] = np.asarray(out[0])
            return out

        def tapped_decode(params, pages, last, ctx, tables, active, *rest):
            logits = np.asarray(step_logits(params, pages, last, ctx, tables,
                                            active))
            for s in np.flatnonzero(np.asarray(active) > 0):
                at = int(np.asarray(ctx)[s])
                got[(s, at) if by_slot else at] = logits[s]
            return decode(params, pages, last, ctx, tables, active, *rest)

        self.patch.setattr(eng, "_prefill_step", tapped_prefill)
        self.patch.setattr(eng, "_decode_step", tapped_decode)
        return got

    def drop(self):
        self.kept.clear()
        self.step_logits.clear()


# ---------------------------------------------------------------------------
# the three standing questions
# ---------------------------------------------------------------------------

def full_forward_is_the_references(name, n, size=None, seed=3):
    """The program's plain (cache-less) forward: logits at every position
    of ``n`` seeded tokens against the reference."""
    fam, b = FAMILIES[name], built(name, size)
    toks = tokens(n, seed, fam.vocab)
    want = np.asarray(b.ref.forward_logits(b.weights, b.cfg, toks))
    assert want.std() > 0.1
    np.testing.assert_allclose(plain_logits(name, toks, size), want,
                               atol=fam.tol, rtol=0)


def a_named_fault_is_told(name, fault, n=70, beyond=40):
    """The plain forward against the reference with ``fault`` planted, at
    a context of ``n`` tokens: more than a hundred tolerances apart at
    some position from ``beyond`` on.  Returns each position's distance."""
    b = built(name)
    toks = tokens(n, seed=5)
    faulty = np.asarray(b.ref.forward_logits(b.weights, b.cfg, toks,
                                             faults={fault}))
    apart = np.abs(plain_logits(name, toks) - faulty).max(axis=-1)
    assert apart[beyond:].max() > 100 * FAMILIES[name].tol, apart.max()
    return apart


def state_apart(name, eng, slot, seq):
    """Each array the family's mixer leaves in ``slot`` after ``seq``
    against the reference's: the root mean square of the difference over
    the reference's."""
    return [float(np.linalg.norm(mine - np.asarray(theirs))
                  / np.linalg.norm(np.asarray(theirs)))
            for mine, theirs in FAMILIES[name].state(built(name), eng, slot,
                                                     seq)]


def chunked_prefill_then_decode_is_one_forward(
        engines, name, prompt, new, kernel=None, size=None, seed=5,
        each_step=None):
    """Chunked prefill (the last chunk padded) then decode through the
    engine's own programs, whatever the family carries handed on across
    every chunk boundary and step, against the reference's ONE forward:
    logits at every chunk's last row and every step, the greedy tokens
    the reference's choices, and the state the request's slot is left
    with the reference's; ``kernel`` ``off`` through the dense gather,
    ``on`` through the kernels in interpret mode.  Returns the engine,
    what it counted (``counted``) and the sequence (the prompt and every
    answer token but the last)."""
    fam, b = FAMILIES[name], built(name, size)
    eng = engines(name, size, **({} if kernel is None else kernels(kernel)))
    if kernel is not None:
        assert eng.paged_kernel == ("pallas" if kernel == "on" else "xla")
    since, got = counted(eng), engines.tapped(eng)
    toks = tokens(prompt, seed, fam.vocab)
    req = serve(eng, toks, new, each_step)
    seq = toks + list(req.out_tokens)[:-1]
    want = np.asarray(b.ref.forward_logits(b.weights, b.cfg, seq))
    rows = sorted(got)
    assert rows[-1] == prompt + new - 2 and prompt - 1 in rows
    assert len(rows) == -(-prompt // fam.chunk) + new - 1
    np.testing.assert_allclose(np.stack([got[t] for t in rows]), want[rows],
                               atol=fam.tol, rtol=0)
    assert list(req.out_tokens) == [int(t) for t in
                                    want[prompt - 1:].argmax(-1)]
    if fam.state is not None:
        apart = state_apart(name, eng, req.slot, seq)
        assert max(apart) < 1e-5, apart
    return eng, since, seq


def a_slot_is_reused(engines, name, first=None, **kw):
    """A request of 150 + 6 tokens, then a short one in the slot it left
    (the last freed is the first taken) with no clearing launch.  A
    family that carries a state a slot: the slot held one, and the
    second answers as a FRESH engine does, logits and all.  A family of
    pages: as the plain forward does, over pages the first filled.
    ``first(eng, since)``: what the file asserts of the long request."""
    fam, b = FAMILIES[name], built(name)
    eng = engines(name, **kw)
    since = counted(eng)
    slot = serve(eng, tokens(150, seed=7), 6).slot
    if first is not None:
        first(eng, since)
    assert eng.blocks.stats()["blocks_in_use"] == 0
    prompt = tokens(40, seed=8)
    if fam.state is None:
        second = serve(eng, prompt, 5)
        assert second.slot == slot
        assert is_greedy(b.model, b.params, prompt, second.out_tokens)
        return
    assert any(np.asarray(a[slot]).any() for p in eng._st.pages
               if paged_kv.is_state(p) for a in p.values())
    got = engines.tapped(eng)
    second = serve(eng, prompt, 5)
    assert second.slot == slot
    fresh_eng = engines.fresh(name, **kw)
    fresh = engines.tapped(fresh_eng)
    again = serve(fresh_eng, prompt, 5)
    assert list(second.out_tokens) == list(again.out_tokens)
    assert sorted(got) == sorted(fresh)
    for t in got:
        np.testing.assert_allclose(got[t], fresh[t], atol=1e-6, rtol=0)
