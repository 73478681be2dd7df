"""Mellum 2 (a layer type per layer: three sliding-window layers to each
full layer, YaRN on the full layers only; 8 of 64 small experts with
gates renormalised), against the benchmark's plain reference.

Seeded random weights, CPU, float32 on both sides, small size: 8 layers
(two periods), hidden 128, 4 query and 2 KV heads of 32, a window of 16
over contexts of 5 to 150 tokens (several windows), 8 experts of 64 at 4
a token.  The reference is the file the benchmark's probe loads
(``benchmarks/reference/mellum.py``), loaded here by path.
"""

import jax
import jax.numpy as jnp
import pytest

import _family
from _family import BS, serve, tokens
from megatron_llm_tpu.models import transformer as tfm
from megatron_llm_tpu.models.mellum import MellumModel, mellum_config
from megatron_llm_tpu.ops import paged_kv
from megatron_llm_tpu.serving import SamplingParams

# float32 on both sides, the same mathematics summed in another order;
# every named fault moves the logits by whole tenths
ROW = _family.FAMILIES["mellum"]
LOGIT_TOL, CHUNK = ROW.tol, ROW.chunk
WINDOW = 16
BOUND = paged_kv.window_pages_bound(WINDOW, CHUNK, BS)      # 5 pages
FAULTS = ("all_full", "all_window", "plain_rope", "no_attention_factor",
          "gates_as_they_are", "float8")


@pytest.fixture(scope="module")
def family():
    return _family.built("mellum")


@pytest.mark.parametrize("n", [5, 16, 17, 70])
def test_full_forward_matches_the_reference(n):
    """The program's plain (cache-less) forward, a scan over periods of
    four layers: logits at every position against the reference, at
    contexts under the window (5), at it (16), one past it (17) and
    several windows long (70)."""
    _family.full_forward_is_the_references("mellum", n)


def test_the_trace_holds_one_period_whatever_the_depth():
    """The cache-less forward of 8 layers and of 4 trace the same number
    of equations outside the scan: the stack scans periods."""
    def eqns(layers):
        model = MellumModel(mellum_config("tiny", num_layers=layers,
                                          use_flash_attn=False))
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        jaxpr = jax.make_jaxpr(
            lambda p, t: model(p, t, train=False))(
                params, jnp.zeros((1, 8), jnp.int32))
        scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
        assert len(scans) == 1 and scans[0].params["length"] == layers // 4
        return len(jaxpr.jaxpr.eqns)

    assert eqns(8) == eqns(4)


def _within_the_bound(held):
    """``each_step`` of a served request: the window pages in use."""
    return lambda eng, req: held.append(
        eng.blocks.stats()["window_blocks_in_use"])


@pytest.mark.parametrize("prompt,new", [(5, 14), (64, 10), (150, 6)])
def test_the_engine_over_two_groups_matches_one_full_forward(
        engines, prompt, new):
    """Chunked prefill then decode through the engine's own programs
    over the two-group cache against the reference's ONE full forward:
    a prompt under the window whose decode steps cross it (5 -> 19), a
    prompt that ends exactly on a page's and the window's edge (64 = 4
    windows = 8 pages), one of nine windows (150).  Window pages have
    gone back to the allocator before most compared positions."""
    _, since, _ = _family.chunked_prefill_then_decode_is_one_forward(
        engines, "mellum", prompt, new)
    if prompt > 2 * WINDOW:
        assert since()[0]["kv_window_pages_returned"] > 0


@pytest.mark.parametrize("fault", FAULTS)
def test_each_named_fault_fails_by_many_tolerances(fault):
    """The same comparison against each FAULTY reference, at a context of
    nine windows: every one is whole tenths of a logit away."""
    apart = _family.a_named_fault_is_told("mellum", fault, n=150,
                                          beyond=2 * WINDOW)
    if fault == "all_full":
        # nothing lies behind a window yet
        assert apart[:WINDOW].max() < LOGIT_TOL


def test_window_pages_go_back_and_a_slot_is_reused(family, engines):
    """A request of 150 + 6 tokens holds at most the bound of window
    pages at any step (5 of the 20 its context spans) while the full
    group keeps all 20; its pages go back, and a second request in the
    reused slot answers as the plain forward does."""
    cfg = family.model.cfg

    def first(eng, since):
        stats, records = since()
        assert stats["window_blocks_in_use"] == 0 == stats["blocks_in_use"]
        assert eng.stats()["window_blocks_total"] == 2 * BOUND
        spanned, returned = (stats["kv_window_pages_spanned"],
                             stats["kv_window_pages_returned"])
        assert spanned == -(-(150 + 5) // BS) == 20
        assert spanned - BOUND <= returned < spanned
        assert sum(r.kv_window_pages_returned for r in records) == returned
        assert all(r.kv_held_bytes > 0 for r in records)
        # at the last launch: 20 full pages on 2 layers, <= 5 window
        # pages on 6
        per_layer_page = BS * 2 * cfg.num_query_groups * cfg.head_dim * 4
        last = records[-1]
        assert last.kv_full_pages_held == 20
        assert last.kv_held_bytes <= (20 * 2 + BOUND * 6) * per_layer_page
        assert last.kv_live_tokens == 150 + 4    # as the launch begins
        # one table a slot would hold 8 layers of every page
        assert last.kv_held_bytes < 20 * 8 * per_layer_page / 2

    _family.a_slot_is_reused(engines, "mellum", first)


def test_two_requests_share_the_window_group(engines):
    """Two long requests decode side by side, each within its bound, the
    invariants held after every step; both answer as when alone."""
    eng, sized = engines("mellum"), ((90, 11), (70, 12))
    alone = []
    for n, s in sized:
        held = []
        alone.append(list(serve(eng, tokens(n, seed=s), 8,
                                _within_the_bound(held)).out_tokens))
        assert max(held) <= BOUND
    reqs = [eng.submit(tokens(n, seed=s),
                       SamplingParams(max_new_tokens=8, temperature=0.0))
            for n, s in sized]
    while any(r.finish_reason is None for r in reqs):
        assert eng.step()
        eng.blocks.check_invariants()
        assert eng.blocks.stats()["window_blocks_in_use"] <= 2 * BOUND
    assert [list(r.out_tokens) for r in reqs] == alone


def test_an_engine_a_test_left_with_work_is_not_handed_on(engines):
    """The harness's own promise (``tests/_family.py::Engines``): the
    module's engine of a shape comes back as long as it is drained, and a
    test that dies with a request in it costs its neighbours a new
    engine, not their result."""
    eng = engines("mellum", num_slots=1)
    assert engines("mellum", num_slots=1) is eng
    eng.submit(tokens(5), SamplingParams(max_new_tokens=2))
    again = engines("mellum", num_slots=1)
    assert again is not eng and not again.scheduler.has_work()
    assert engines("mellum", num_slots=1) is again


def test_the_pools_are_sized_by_group_and_named_in_the_kernels(family):
    model = family[0]
    cfg = model.cfg
    groups = paged_kv.layer_groups(cfg)
    assert groups == ("window", "window", "window", "full") * 2
    pools = paged_kv.init_pools(cfg, 41, BS, window_blocks=11)
    assert [p["k_pages"].shape[0] for p in pools] == [11, 11, 11, 41] * 2
    tables = {"full": jnp.zeros((1, 4), jnp.int32),
              "window": jnp.ones((1, 4), jnp.int32)}
    caches = paged_kv.step_caches(pools, tables, jnp.zeros(1, jnp.int32),
                                  jnp.ones(1, jnp.int32), "xla", groups)
    assert [c.group for c in caches] == list(groups)
    assert int(caches[0].block_tables[0, 0]) == 1
    assert int(caches[3].block_tables[0, 0]) == 0
    with pytest.raises(ValueError, match="window_blocks"):
        paged_kv.init_pools(cfg, 41, BS)
    # a model of one type has one group, whatever its window
    from megatron_llm_tpu.models.mistral import mistral_config

    assert paged_kv.layer_groups(mistral_config("tiny")) is None


def test_what_the_pattern_does_not_support_is_refused_by_name(family, engines,
                                                              capsys):
    model = family.model
    with pytest.raises(ValueError, match="int8 KV pool"):
        paged_kv.init_pools(model.cfg, 4, BS, quantized=True,
                            window_blocks=4)
    for kw, what in ((dict(int8_kv_cache=True), "int8 KV pool"),
                     (dict(speculative=True, draft_k=2), "speculative"),
                     (dict(host_cache_bytes=1 << 20), "host KV tier")):
        with pytest.raises(ValueError, match=what):
            engines.fresh("mellum", max_model_len=32, **kw)
    eng = engines.fresh("mellum", max_model_len=32)
    assert "the prefix cache adopts nothing" in capsys.readouterr().out
    assert not eng.blocks.prefix_cache_enabled
    with pytest.raises(ValueError, match="whole periods"):
        mellum_config("tiny", num_layers=6)
    with pytest.raises(ValueError, match="sliding_window_size"):
        mellum_config("tiny", sliding_window_size=None)
    with pytest.raises(ValueError, match="gives its layers no type"):
        tfm.attention(jnp.zeros((1, 2, 128)), {}, model.cfg, freqs=None,
                      attention_mask=None, position_ids=None,
                      dropout_key=None, train=False)


def test_parallelism_is_refused_at_construction(monkeypatch):
    from megatron_llm_tpu.models import mellum

    monkeypatch.setattr(mellum, "_vocab_unsharded", lambda: False)
    with pytest.raises(ValueError, match="tensor or pipeline"):
        MellumModel(mellum_config("tiny"))


def test_the_family_wrapper_asserts_its_flags():
    cfg = mellum_config("tiny")
    for bad in (dict(norm_topk_prob=False), dict(qk_norm_per_head=True),
                dict(layer_types=None, rope_yarn_layer_types=None),
                dict(num_experts=0)):
        with pytest.raises(AssertionError):
            MellumModel(cfg.replace(**bad))
    full = mellum_config("12B-A2.5B")
    assert (full.num_layers, full.hidden_size, full.num_attention_heads,
            full.num_attention_heads_kv, full.head_dim) == (28, 2304, 32, 4,
                                                            128)
    assert (full.num_experts, full.moe_top_k, full.expert_hidden_size,
            full.ffn_hidden_size) == (64, 8, 896, 7168)
    assert full.padded_vocab_size == 98304 and full.rope_theta == 500000.0
    assert full.layer_types == ("sliding", "sliding", "sliding", "full")
    assert full.sliding_window_size == 1024
    assert full.attention_of("sliding") == (1024, None)
    assert full.attention_of("full") == (
        None, (16.0, 8192, 32.0, 1.0, 1.2772588722239782))
    # a layer's parameters: attention 21.23 M, router 0.15 M, experts
    # 396.4 M (417.8 M with them)
    layer = jax.eval_shape(
        lambda k: tfm.init_layer_params(k, full, jnp.bfloat16),
        jax.random.PRNGKey(0))
    sizes = {k: sum(x.size for x in jax.tree_util.tree_leaves(v))
             for k, v in layer["attention"].items()}
    assert sizes["query_key_value"] + sizes["dense"] == 21_233_664
    mlp = sum(x.size for x in jax.tree_util.tree_leaves(layer["mlp"]))
    assert mlp == 64 * 3 * 2304 * 896 + 2304 * 64
