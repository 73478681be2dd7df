"""granite-4.0-h-small (``model_type`` ``granitemoehybrid``: Mamba-2
mixers and attention with no positions by ``layer_types``, a per-slot
recurrent state beside the pages, a share of the router's experts, four
multipliers), against the benchmark's plain reference.

Seeded random weights, CPU, float32 on both sides, small size: 8 layers
(two periods of mamba, mamba, attention, mamba), hidden 128, 4 query and
2 key/value heads of 32, 8 state-space heads of 32 over a state of 16,
the router's 8 experts of which 4 are held at 3 a token, a shared MLP;
contexts of 5 to 156 tokens over pages of 8 and chunks of 32.  The
reference is the file the benchmark's probe loads
(``benchmarks/reference/granite.py``: the recurrence one token at a time,
no chunk, no cache), loaded here by path.  The family's row, the helpers
and the three standing questions are ``tests/_family.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _family
from _family import BS, kernels, serve, tokens
from megatron_llm_tpu.config import PositionEmbeddingType
from megatron_llm_tpu.models import transformer as tfm
from megatron_llm_tpu.models.granite import GraniteModel, granite_config
from megatron_llm_tpu.models.language_model import language_model_forward
from megatron_llm_tpu.ops import paged_kv
from megatron_llm_tpu.serving import SamplingParams

# float32 on both sides, the same mathematics summed in another order (a
# chunked scan and a step against a recurrence over tokens); the logits'
# deviation is some 0.3 and every named fault moves them by hundredths
LOGIT_TOL = _family.FAMILIES["granite"].tol
FAULTS = ("embedding_one", "residual_one", "logits_one", "scale_sqrt_head",
          "rope_on", "no_D", "gate_after_norm", "silu_second",
          "no_conv_bias", "no_shared", "state_dropped_at_chunks", "float8")


@pytest.fixture(scope="module")
def family():
    return _family.built("granite")


@pytest.mark.parametrize("n", [5, 16, 17, 70])
def test_full_forward_matches_the_reference(n):
    """The program's plain (cache-less) forward, a scan over PERIODS with
    each layer's mixer taken by its index among its kind: logits at every
    position against the reference."""
    _family.full_forward_is_the_references("granite", n)


@pytest.mark.parametrize("prompt,new,kernel", [
    (5, 14, "off"), (64, 10, "off"), (150, 6, "off"), (45, 5, "on")])
def test_the_engine_over_the_state_group_matches_one_full_forward(
        engines, prompt, new, kernel):
    """Chunked prefill (chunks of 32, the last one padded) then decode
    through the engine's own programs, the state carried in its slot
    across every chunk boundary and step, against the reference's ONE
    forward: logits at every chunk's last row and every step, and the
    state the slot is left with; the attention layers through the dense
    gather and (``on``) through the walk's kernels in interpret mode."""
    _family.chunked_prefill_then_decode_is_one_forward(
        engines, "granite", prompt, new, kernel)


@pytest.mark.parametrize("fault", FAULTS)
def test_each_named_fault_fails_by_many_tolerances(fault):
    """Each multiplier left at 1, the rotation left on, the score scale
    1/sqrt(d), ``D`` dropped, the gate moved behind the norm, an
    expert's ``silu`` on the other half, the bias or the shared MLP left
    out, a chunk's state not handed on, float8."""
    _family.a_named_fault_is_told("granite", fault)


def test_the_rotation_left_on_in_the_program_fails(family):
    """The same from the other side: the PROGRAM with the rotary
    embedding on is not the reference."""
    model, params, ref, weights, cfg = family
    rotary = model.cfg.replace(
        position_embedding_type=PositionEmbeddingType.rotary)
    toks = tokens(70, seed=5)
    got = np.asarray(language_model_forward(
        params, jnp.asarray([toks], jnp.int32), None, None, rotary)[0][0])
    want = np.asarray(ref.forward_logits(weights, cfg, toks))
    assert np.abs(got - want).max() > 100 * LOGIT_TOL


def test_a_slot_is_reused_by_a_second_request(engines):
    """A request of 150 + 6 tokens, then a short one in the same slot
    with no clearing launch: the second answers as a fresh engine does,
    logits and all."""
    _family.a_slot_is_reused(engines, "granite", **kernels("off"))


def test_two_requests_decode_side_by_side(family, engines):
    """Continuous batching over the state: two requests in two slots,
    one admitted while the other decodes, each as if alone."""
    model, params, ref, weights, cfg = family
    eng = engines("granite", **kernels("off"))
    a = eng.submit(tokens(70, seed=1), SamplingParams(max_new_tokens=12,
                                                      temperature=0.0))
    for _ in range(6):
        eng.step()
    b = eng.submit(tokens(37, seed=2), SamplingParams(max_new_tokens=8,
                                                      temperature=0.0))
    while a.finish_reason is None or b.finish_reason is None:
        assert eng.step()
    for req, seed, n in ((a, 1, 70), (b, 2, 37)):
        seq = tokens(n, seed=seed) + list(req.out_tokens)
        want = np.asarray(ref.forward_logits(weights, cfg, seq))
        assert list(req.out_tokens) == [int(t) for t in
                                        want[n - 1:-1].argmax(-1)]


@pytest.mark.parametrize("prompt,new", [(70, 6), (150, 3)])
def test_a_finished_requests_slot_holds_the_references_state(engines, prompt,
                                                             new):
    """What the benchmark's probe reads: after a request of chunks (the
    last one padded) and steps, its slot holds the state the reference
    is left with by the prompt and every answer token but the last."""
    eng = engines("granite", **kernels("off"))
    toks = tokens(prompt, seed=9)
    req = serve(eng, toks, new)
    apart = _family.state_apart("granite", eng, req.slot,
                                toks + list(req.out_tokens)[:-1])
    assert len(apart) == 6 and max(apart) < 1e-5, apart


def test_a_state_kept_in_bf16_is_not_the_references(engines, monkeypatch):
    """The ASSUMPTION of a float32 state, from the other side: rounded
    to bf16 in its slot at every launch, the state stands hundreds of
    float32 tolerances from the reference's."""
    monkeypatch.setattr(paged_kv, "SSM_STATE_DTYPE", jnp.bfloat16)
    eng = engines.fresh("granite", num_slots=1)
    assert eng._st.pages[0]["ssm_state"].dtype == jnp.bfloat16
    toks = tokens(70, seed=9)
    req = serve(eng, toks, 6)
    apart = _family.state_apart("granite", eng, req.slot,
                                toks + list(req.out_tokens)[:-1])
    assert min(apart) > 1e-3, apart


@pytest.mark.parametrize("kernel", ["off", "on"])
def test_the_engine_counts_what_its_state_space_layers_do(engines, kernel):
    eng = engines("granite", **kernels(kernel))
    since = _family.counted(eng)
    serve(eng, tokens(70, seed=5), 4)
    s, records = since()
    # whose state a decode launch's program reads and writes: the step's
    # kernel the one live row's, the XLA step both slots' and the garbage
    # row's, a layer; a prefill launch counts none
    assert s["ssm_rows_moved"] == 3 * 6 * (1 if kernel == "on" else 2 + 1)
    steps = [r for r in records if r.kind == "decode"]
    assert len(steps) == 3 and all(
        r.ssm_rows_moved == (r.ssm_rows_live if kernel == "on" else 18)
        for r in steps)
    assert not any(r.ssm_rows_moved for r in records if r.kind == "prefill")
    per_slot = 6 * (8 * 32 * 16 * 4 + 3 * (8 * 32 + 2 * 16) * 4)
    assert eng.blocks.stats()["state_bytes_per_slot"] == per_slot
    # 3 chunks and 3 steps, one live row each, six state-space layers
    assert s["ssm_rows_live"] == 6 * (3 + 3)
    assert s["ssm_tokens"] == 6 * (70 + 3)
    assert s["ssm_state_bytes_held"] == 6 * per_slot
    # experts 2-5 of the router's 8 are held: about half the assignments
    assert s["moe_assignments"] == 8 * 3 * (70 + 3)
    assert 0.3 < s["moe_assignments_held"] / s["moe_assignments"] < 0.7
    rec = records[-1]
    assert rec.ssm_rows_live == 6 and rec.ssm_tokens == 6
    assert rec.moe_assignments_held <= rec.moe_assignments == 8 * 3
    assert "a prefix's recurrent state" in eng.blocks.cache_stats()[
        "adopts_no_prefix"]
    assert s["prefill_tokens_cached"] == 0


def test_the_page_programs_do_not_see_the_state(engines):
    """Copy-on-write and ``block_bytes`` run over the attention layers'
    pools only; the state's arrays have a role of their own."""
    eng = engines("granite", **kernels("off"))
    pages = eng._st.pages
    assert [paged_kv.is_state(p) for p in pages] == [
        True, True, False, True] * 2
    assert len(paged_kv.paged_pools(pages)) == 2
    assert paged_kv.block_bytes(pages) == 2 * 2 * BS * 2 * 32 * 4
    before = [np.asarray(p["ssm_state"]) for p in pages
              if paged_kv.is_state(p)]
    copied = eng._copy_page(pages, 1, 2)
    assert [paged_kv.is_state(p) for p in copied] == [
        paged_kv.is_state(p) for p in pages]
    for p, b in zip((p for p in copied if paged_kv.is_state(p)), before):
        assert p["ssm_state"] is not None and (
            np.asarray(p["ssm_state"]) == b).all()
    shapes = paged_kv.array_shapes(pages)
    state = paged_kv.state_shapes(pages)
    assert ("float32", (3, 8, 32, 16)) in state
    assert ("float32", (2, 8, 32, 16)) in state
    assert not shapes & state


def test_the_programs_tables_give_the_state_a_role_and_the_mixer_scopes(
        engines):
    eng = engines("granite", **kernels("off"))
    eng.warmup()
    tables = eng.program_tables()
    for name in ("engine_prefill", "engine_decode"):
        scopes = tables[name].summary()["scopes"]
        assert {"ssm_in_proj", "ssm_conv", "ssm_gate_norm",
                "ssm_out_proj"} <= set(scopes), scopes
    assert "ssm_scan" in tables["engine_prefill"].summary()["scopes"]
    assert "ssm_step" in tables["engine_decode"].summary()["scopes"]
    assert "ssm_scan" not in tables["engine_decode"].summary()["scopes"]
    roles = {r["role"] for t in tables.values() for r in t.rows}
    assert "kv_pool" in roles


def test_what_state_space_layers_do_not_support_is_refused_by_name(
        family, engines, monkeypatch):
    model, params = family[:2]
    for kw, what in ((dict(preemption=True), "preemption"),
                     (dict(int8_kv_cache=True), "int8 KV pool"),
                     (dict(speculative=True, draft_k=2), "speculative"),
                     (dict(host_cache_bytes=1 << 20), "host KV tier")):
        with pytest.raises(ValueError, match="state-space.*" + what):
            engines.fresh("granite", max_model_len=32, **kw)
    with pytest.raises(ValueError, match="int8 KV pool"):
        paged_kv.init_pools(model.cfg, 4, BS, quantized=True, num_slots=2)
    with pytest.raises(ValueError, match="num_slots"):
        paged_kv.init_pools(model.cfg, 4, BS)
    from megatron_llm_tpu.serving.kv_blocks import BlockManager

    with pytest.raises(ValueError, match="adopts no prefix"):
        BlockManager(8, BS, 2, 4, prefix_cache=True,
                     state_bytes_per_slot=1024)
    toks = jnp.asarray([tokens(16)], jnp.int32)
    with pytest.raises(NotImplementedError, match="training"):
        model(params, toks, train=True)
    with pytest.raises(NotImplementedError, match="attention mask"):
        model(params, toks, attention_mask=jnp.zeros((1, 1, 16, 16), bool))
    with pytest.raises(ValueError, match="'attention' layers only"):
        granite_config("tiny", layer_types=("mamba", "sliding"),
                       sliding_window_size=16)
    with pytest.raises(ValueError, match="latent attention"):
        granite_config("tiny", kv_lora_rank=32)
    with pytest.raises(ValueError, match="must lie among"):
        granite_config("tiny", moe_experts_first=6)
    from megatron_llm_tpu.models import gpt

    monkeypatch.setattr(gpt, "_vocab_unsharded", lambda: False)
    with pytest.raises(ValueError, match="state-space.*tensor or pipeline"):
        GraniteModel(granite_config("tiny"))


def test_the_family_wrapper_asserts_its_flags():
    cfg = granite_config("tiny")
    for bad in (dict(norm_topk_prob=False), dict(moe_shared_experts=0),
                dict(position_embedding_type="rotary"),
                dict(tie_embed_logits=False),
                dict(layer_types=("attention",))):
        with pytest.raises(AssertionError):
            GraniteModel(cfg.replace(**bad))
    full = granite_config("h-small")
    assert (full.num_layers, full.hidden_size, full.num_attention_heads,
            full.num_attention_heads_kv, full.head_dim) == (
                40, 4096, 32, 8, 128)
    assert full.layer_types == ("mamba",) * 5 + ("attention",) + (
        "mamba",) * 4
    assert full.mixer_counts == {"mamba": 36, "attention": 4}
    assert [full.mixer_index(i) for i in (0, 5, 6, 15, 39)] == [
        ("mamba", 0), ("attention", 0), ("mamba", 5), ("attention", 1),
        ("mamba", 35)]
    assert (full.mamba_d_inner, full.mamba_conv_dim) == (8192, 8448)
    assert (full.num_experts, full.routed_experts, full.moe_top_k,
            full.expert_hidden_size, full.moe_shared_experts) == (
                72, 72, 10, 768, 2)
    assert (full.attention_multiplier, full.embedding_multiplier,
            full.residual_multiplier, full.logits_scaling) == (
                0.0078125, 12.0, 0.22, 16.0)
    # the cut the benchmark serves: one period, 36 of the 72 experts
    cut = granite_config("h-small", num_layers=10, num_experts=36,
                         moe_router_experts=72, padded_vocab_size=50176)
    stack = jax.eval_shape(
        lambda k: tfm.init_stack_params(k, cut, jnp.bfloat16),
        jax.random.PRNGKey(0))["layers"]

    def size(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))

    assert stack["mamba"]["in_proj"]["kernel"].shape == (9, 4096, 16768)
    assert stack["attention"]["query_key_value"]["kernel"].shape == (
        1, 4096, 6144)
    assert stack["mlp"]["router"]["kernel"].shape == (10, 4096, 72)
    assert stack["mlp"]["experts"]["w_in"].shape == (10, 36, 4096, 1536)
    assert stack["input_norm"]["scale"].shape == (10, 4096)
    # a Mamba mixer 102.29 M, an attention mixer 41.94 M parameters
    assert size(stack["mamba"]) // 9 == (
        4096 * 16768 + 8192 * 4096 + 8448 * 5 + 3 * 128 + 8192)
    assert size(stack["attention"]) == 4096 * 6144 + 4096 * 4096
    pools = jax.eval_shape(lambda: paged_kv.init_pools(
        cut, 13313, 16, dtype=jnp.bfloat16, num_slots=24))
    assert paged_kv.state_bytes_per_slot(pools) == 9 * (
        128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert paged_kv.block_bytes(pools) == 16 * 2 * 8 * 128 * 2


def test_the_flags_lower_into_the_config():
    from megatron_llm_tpu.arguments import (parse_args,
                                            transformer_config_from_args,
                                            validate_args)

    args = validate_args(parse_args(args_list=[
        "--num_layers=4", "--hidden_size=128", "--num_attention_heads=4",
        "--seq_length=64", "--max_position_embeddings=64",
        "--micro_batch_size=1", "--global_batch_size=1",
        "--position_embedding_type=none", "--no_bias", "--use_rms_norm",
        "--glu_activation=swiglu", "--num_experts=4",
        "--moe_router_experts=8", "--moe_experts_first=4",
        "--moe_shared_experts=2", "--layer_types", "mamba", "attention",
        "--mamba_n_heads=8", "--mamba_d_head=32", "--mamba_d_state=16",
        "--mamba_chunk_size=16", "--attention_multiplier=0.03125",
        "--embedding_multiplier=12", "--residual_multiplier=0.22",
        "--logits_scaling=16", "--padded_vocab_size=512"]), world_size=1)
    cfg = transformer_config_from_args(args)
    assert cfg.position_embedding_type == PositionEmbeddingType.none
    assert cfg.state_space and cfg.holds_a_share
    assert (cfg.routed_experts, cfg.num_experts, cfg.moe_experts_first) == (
        8, 4, 4)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_chunk_size, cfg.mamba_d_conv) == (8, 32, 16, 16, 4)
    assert (cfg.attention_multiplier, cfg.embedding_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == (
                0.03125, 12.0, 0.22, 16.0)
