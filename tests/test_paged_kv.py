"""``ops/paged_kv.py`` owns the paged KV cache: which kernel reads it is
resolved in one function, the engine's programs are static in the
result through the cache they hand the model, and no other module knows
the pool's layout."""

import os
import re
import subprocess

import jax
import numpy as np
import pytest

from megatron_llm_tpu.models.llama import LlamaModel, llama_config
from megatron_llm_tpu.ops import paged_kv
from megatron_llm_tpu.ops.pallas import paged_attention as pa
from megatron_llm_tpu.serving import EngineConfig, InferenceEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# what --serve_paged_kernel / --serve_prefill_kernel have always meant:
# (requested, kernel available, programs on one device) -> path
RESOLUTION = [
    ("auto", True, True, "pallas"),
    ("auto", True, False, "xla"),      # several devices: GSPMD cannot
    ("auto", False, True, "xla"),      # partition a Mosaic call
    ("on", True, True, "pallas"),
    ("on", True, False, "pallas"),     # 'on' insists
    ("on", False, True, "xla"),        # nothing to insist on
    ("off", True, True, "xla"),
    ("off", True, False, "xla"),
]


@pytest.mark.parametrize("requested,available,one_device,want", RESOLUTION)
def test_resolve_kernel(monkeypatch, requested, available, one_device, want):
    monkeypatch.setattr(pa, "_INTERPRET", available)
    monkeypatch.delenv("MLT_FORCE_PALLAS", raising=False)
    assert paged_kv.resolve_kernel(requested, one_device) == want


def test_resolve_kernel_refuses_another_word():
    with pytest.raises(ValueError, match="auto|on|off"):
        paged_kv.resolve_kernel("maybe", True)


@pytest.fixture(scope="module")
def tiny():
    cfg = llama_config("tiny", num_layers=2, seq_length=64,
                       max_position_embeddings=64, padded_vocab_size=64,
                       use_flash_attn=False)
    model = LlamaModel(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(tiny, monkeypatch, **kw):
    monkeypatch.setattr(pa, "_INTERPRET", True)
    model, params = tiny
    return InferenceEngine(model, params, EngineConfig(
        num_slots=2, block_size=8, max_model_len=64, prefill_chunk=16,
        speculative=True, draft_k=2, **kw))


@pytest.mark.parametrize("decode,prefill", [("on", "off"), ("off", "on")])
def test_the_engine_resolves_each_program_once(tiny, monkeypatch, decode,
                                               prefill):
    """The two flags resolve independently, the decode program takes the
    first, the prefill and verify programs the second, and each traced
    program holds a ``pallas_call`` exactly when its path says so: the
    path reaches the model as static data of the cache, through no
    config field."""
    eng = _engine(tiny, monkeypatch, paged_kernel=decode,
                  prefill_kernel=prefill)
    want = {"on": "pallas", "off": "xla"}
    assert (eng.paged_kernel, eng.prefill_kernel) == (want[decode],
                                                      want[prefill])
    stats = eng.stats()
    assert (stats["paged_kernel"], stats["prefill_kernel"]) == (
        want[decode], want[prefill])
    st, S, i32 = eng._st, 2, np.int32
    tables = np.zeros((S, eng._max_blocks_per_slot), i32)
    knobs = (np.ones(S, np.float32), np.zeros(S, i32),
             np.zeros(S, np.float32), np.full(S, -1, i32),
             np.full(S, -1, i32), np.zeros((S, 2), np.uint32),
             np.zeros((S, 2), np.uint32), np.zeros(S, bool))
    jaxprs = {
        "decode": jax.make_jaxpr(eng._decode_step)(
            eng.params, st.pages, np.zeros(S, i32), np.zeros(S, i32),
            tables, np.ones(S, i32), *knobs),
        "verify": jax.make_jaxpr(eng._verify_step)(
            eng.params, st.pages, np.zeros((S, 3), i32), np.zeros(S, i32),
            tables, np.ones(S, i32), *knobs),
        "prefill": jax.make_jaxpr(eng._prefill_step)(
            eng.params, st.pages, np.zeros((1, 16), i32), i32(0), i32(16),
            {paged_kv.FULL: tables[:1], paged_kv.LAST: np.bool_(True)}),
    }
    has_kernel = {k: "pallas_call" in str(j) for k, j in jaxprs.items()}
    assert has_kernel == {"decode": decode == "on",
                          "verify": prefill == "on",
                          "prefill": prefill == "on"}


def test_a_callers_default_is_auto(monkeypatch):
    """A caller outside the engine that names no path gets what ``auto``
    means on one device: the kernel where it can run."""
    pools = [{}]
    monkeypatch.setattr(pa, "_INTERPRET", True)
    assert paged_kv.step_caches(pools, None, None, None)[0].kernel == "pallas"
    monkeypatch.setattr(pa, "_INTERPRET", False)
    monkeypatch.delenv("MLT_FORCE_PALLAS", raising=False)
    if jax.default_backend() != "tpu":
        assert paged_kv.step_caches(pools, None, None, None)[0].kernel == "xla"


def _grep(pattern, *paths):
    return subprocess.run(
        ["grep", "-rlE", "--include=*.py", pattern, *paths],
        cwd=ROOT, capture_output=True, text=True).stdout.split()


def test_the_pool_has_one_owner():
    """The pool's key names occur in one module of the package, the
    engine takes nothing from the legacy decode stack, and the model
    carries no kernel choice in its config."""
    assert _grep(r"[\"'][kv]_pages", "megatron_llm_tpu") == [
        "megatron_llm_tpu/ops/paged_kv.py"]
    engine = open(os.path.join(
        ROOT, "megatron_llm_tpu", "serving", "engine.py")).read()
    assert not re.search(r"text_generation\.generation|\"pages\" in", engine)
    from megatron_llm_tpu.config import TransformerConfig
    assert not [f for f in TransformerConfig.__dataclass_fields__
                if "paged" in f]


# ---------------------------------------------------------------------------
# a pool with a third array: the sparse-attention indexer's keys
# ---------------------------------------------------------------------------

def _keye_cfg():
    from megatron_llm_tpu.models.keye import keye_config

    return keye_config("tiny", use_flash_attn=False)


def test_an_indexed_pool_holds_the_indexers_keys_beside_k_and_v():
    """``init_pools`` for a model with an indexer: three arrays a layer,
    the third 128 wide whatever the indexer's head size; its name occurs
    in the one module that owns the pool; a dense model's pool is as it
    was."""
    import jax.numpy as jnp

    cfg = _keye_cfg()
    pools = paged_kv.init_pools(cfg, 5, 8, dtype=jnp.bfloat16)
    assert len(pools) == cfg.num_layers
    assert {k: (v.shape, v.dtype.name) for k, v in pools[0].items()} == {
        "k_pages": ((5, 8, 2, 32), "bfloat16"),
        "v_pages": ((5, 8, 2, 32), "bfloat16"),
        "index_pages": ((5, 8, 128), "bfloat16")}
    assert paged_kv.block_bytes(pools) == cfg.num_layers * 8 * (
        2 * 2 * 32 + 128) * 2
    assert _grep(r"[\"']index_pages", "megatron_llm_tpu") == [
        "megatron_llm_tpu/ops/paged_kv.py"]
    dense = paged_kv.init_pools(llama_config("tiny"), 5, 8)
    assert set(dense[0]) == {"k_pages", "v_pages"}


@pytest.mark.parametrize("n,ctx", [(1, 0), (1, 13), (6, 0), (6, 11)])
def test_attend_writes_the_indexers_key_where_it_writes_k_and_v(n, ctx):
    """The indexer's key lands at the same (page, offset) as the call's
    keys and values, zero-filled to the pool's width; a padded row goes
    to the garbage block; nothing else of the pool moves."""
    import jax.numpy as jnp

    cfg = _keye_cfg()
    pool = paged_kv.init_pools(cfg, 6, 8)[0]
    bt = jnp.asarray([[3, 1, 4, 0]], jnp.int32)
    valid = n if n == 1 else n - 2
    cache = paged_kv.step_caches([pool], bt, jnp.asarray([ctx], jnp.int32),
                                 jnp.asarray([valid], jnp.int32), "xla")[0]
    key = jax.random.PRNGKey(n + ctx)
    q = jax.random.normal(key, (1, n, 4, 32))
    k = jax.random.normal(key, (1, n, 2, 32)) + 1.0
    iq = jax.random.normal(key, (1, n, 4, 16))
    ik = jax.random.normal(key, (1, n, 16)) + 2.0
    iw = jax.random.normal(key, (1, n, 4))
    _, new = cache.attend(q, k, k, None, index=(iq, ik, iw, 8))
    table = np.asarray(bt[0])
    written = set()
    for j in range(valid):
        page, off = table[(ctx + j) // 8], (ctx + j) % 8
        written.add((int(page), int(off)))
        got = np.asarray(new.pool["index_pages"][page, off])
        assert (got[:16] == np.asarray(ik[0, j])).all()
        assert (got[16:] == 0).all()
        assert (np.asarray(new.pool["k_pages"][page, off])
                == np.asarray(k[0, j])).all()
    for page in range(1, 6):
        for off in range(8):
            if (page, off) not in written:
                assert not np.asarray(
                    new.pool["index_pages"][page, off]).any()
    assert int(new.context_lens[0]) == ctx + valid


# ---------------------------------------------------------------------------
# a model of one layer type keeps ONE group, whatever its window
# ---------------------------------------------------------------------------

def test_a_one_type_window_model_allocates_attends_and_frees_as_before():
    """Mistral's tiny shape (its window on EVERY layer, here 16 so that a
    context passes it): no window group, one table a slot that keeps the
    request's pages for its whole length, the walk under its plain names,
    no page given back before the request ends, and the answer the
    cache-less forward's."""
    import jax.numpy as jnp

    from megatron_llm_tpu.models.gpt import GPTModel
    from megatron_llm_tpu.models.mistral import mistral_config
    from megatron_llm_tpu.serving import SamplingParams
    from megatron_llm_tpu.serving.loop_profiler import KV_FIELDS

    cfg = mistral_config("tiny", sliding_window_size=16, seq_length=128,
                         max_position_embeddings=128, padded_vocab_size=256,
                         use_flash_attn=False)
    assert cfg.layer_types is None and paged_kv.layer_groups(cfg) is None
    assert cfg.attention_of(None) == (16, None)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, EngineConfig(
        num_slots=2, block_size=8, max_model_len=128, prefill_chunk=16,
        prefix_cache=False))
    assert eng.blocks.window is None and eng._layer_groups is None
    assert len({p["k_pages"].shape for p in eng._st.pages}) == 1
    prompt = np.random.default_rng(0).integers(1, 255, 70).tolist()
    req = eng.submit(prompt, SamplingParams(max_new_tokens=6,
                                            temperature=0.0))
    held = []
    while req.finish_reason is None:
        assert eng.step()
        eng.blocks.check_invariants()
        tables = eng._cache.tables(eng.blocks)
        assert isinstance(tables, np.ndarray)
        assert (tables == eng.blocks.tables).all()
        if req.finish_reason is None:
            held.append(eng.blocks.stats()["blocks_in_use"])
    # the request's worst case (70 + 6 tokens: 10 pages) from admission on
    assert set(held) == {10}
    stats = eng.stats()
    assert stats["blocks_in_use"] == 0
    assert not [k for k in stats if k.startswith("window_")]
    assert all(stats[f] == 0 for f in KV_FIELDS)
    toks = list(prompt)
    for _ in range(6):
        logits = model(params, jnp.asarray([toks], jnp.int32), train=False)
        toks.append(int(jnp.argmax(logits[0, -1])))
    assert toks[70:] == list(req.out_tokens)
    # and the caches the programs hand the model carry the plain names
    caches = paged_kv.step_caches(eng._st.pages, eng.blocks.tables,
                                  np.zeros(2, np.int32), np.ones(2, np.int32),
                                  "xla", eng._layer_groups)
    assert {c.group for c in caches} == {paged_kv.FULL}


# ---------------------------------------------------------------------------
# a latent pool: one array a layer, a token's latent and rotary key
# ---------------------------------------------------------------------------

def _kanana_cfg():
    from megatron_llm_tpu.models.kanana import kanana_config

    return kanana_config("tiny", use_flash_attn=False)


def test_a_latent_pool_is_one_array_of_rows_a_layer():
    """``init_pools`` for a model with latent attention: ONE array a
    layer, a row the latent and the rotary key filled up to whole lanes
    (32 + 8 -> 128), whatever the KV head count; ``block_bytes`` and
    ``array_shapes`` follow from the array; its name occurs in the one
    module that owns the pool; the int8 pool is refused; a model without
    ``kv_lora_rank`` has the pools it always had."""
    import jax.numpy as jnp

    cfg = _kanana_cfg()
    assert paged_kv.latent_width(cfg) == 128
    pools = paged_kv.init_pools(cfg, 5, 8, dtype=jnp.bfloat16)
    assert len(pools) == cfg.num_layers
    assert {k: (v.shape, v.dtype.name) for k, v in pools[0].items()} == {
        "latent_pages": ((5, 8, 128), "bfloat16")}
    assert paged_kv.block_bytes(pools) == cfg.num_layers * 8 * 128 * 2
    assert paged_kv.array_shapes(pools) == {
        ("bfloat16", (5, 8, 128)), ("bfloat16", (40, 128))}
    assert _grep(r"[\"']latent_pages", "megatron_llm_tpu") == [
        "megatron_llm_tpu/ops/paged_kv.py"]
    with pytest.raises(ValueError, match="int8 KV pool"):
        paged_kv.init_pools(cfg, 5, 8, quantized=True)
    dense = paged_kv.init_pools(llama_config("tiny"), 5, 8)
    assert set(dense[0]) == {"k_pages", "v_pages"}
    assert paged_kv.layer_groups(cfg) is None


@pytest.mark.parametrize("kernel,n,interpret,want", [
    ("pallas", 6, True, True), ("pallas", 1, True, False),
    ("xla", 6, True, False), ("pallas", 6, False, False)])
def test_a_chunk_on_the_kernel_path_and_nothing_else_expands_its_latents(
        kernel, n, interpret, want, monkeypatch):
    """The choice of form is by what the code sees: the query length and
    the resolved path (off the chip the kernel is there in interpret mode
    only).  Where it expands, ``attend_latent`` takes the model's own
    ``q_nope`` and the up-projection and answers per head what the
    absorbed read answers through the dense gather."""
    import jax.numpy as jnp

    from megatron_llm_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_INTERPRET", interpret)
    assert paged_kv.expands_latents(kernel, n) is want
    cfg = _kanana_cfg()
    r, dr, dn, dv, nh = (cfg.kv_lora_rank, cfg.qk_rope_head_dim,
                         cfg.qk_nope_head_dim, cfg.v_head_dim, 4)
    rng = np.random.default_rng(1)
    pool = {"latent_pages": jnp.asarray(
        rng.standard_normal((6, 8, 128)), jnp.float32)}
    tables = jnp.asarray([[3, 1, 4, 0]], jnp.int32)
    lens = (jnp.asarray([5], jnp.int32), jnp.asarray([n], jnp.int32))
    cache = paged_kv.PagedKVCache(pool, tables, *lens, kernel=kernel)
    assert cache.expands_latents(n) is want
    lat, rope = (jnp.asarray(rng.standard_normal((1, n, w)), jnp.float32)
                 for w in (r, dr))
    qn, qr = (jnp.asarray(rng.standard_normal((1, n, nh, w)), jnp.float32)
              for w in (dn, dr))
    w_up = jnp.asarray(rng.standard_normal((r, nh, dn + dv)) / r ** 0.5,
                       jnp.float32)
    if not want:
        with pytest.raises(AssertionError):
            cache.attend_latent(qn, qr, lat, rope, 0.2, kv_up=w_up)
        return
    got, after = cache.attend_latent(qn, qr, lat, rope, 0.2, kv_up=w_up)
    assert got.shape == (1, n, nh, dv)
    dense = paged_kv.PagedKVCache(pool, tables, *lens, kernel="xla")
    q_lat = jnp.einsum("bsnd,rnd->bsnr", qn, w_up[..., :dn])
    ctx, after_dense = dense.attend_latent(q_lat, qr, lat, rope, 0.2)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(jnp.einsum("bsnr,rnd->bsnd", ctx, w_up[..., dn:])),
        atol=1e-5)
    np.testing.assert_array_equal(np.asarray(after.pool["latent_pages"]),
                                  np.asarray(after_dense.pool["latent_pages"]))


@pytest.mark.parametrize("n,ctx", [(1, 11), (6, 5)])
def test_attend_latent_writes_a_row_and_reads_it_back(n, ctx):
    """``attend_latent`` writes ``[latent ; rotary key ; zeros]`` at the
    row's next positions of its table and attends the absorbed queries
    over history and chunk; rows that are not live go to the garbage
    page; the context comes back in the latent."""
    import jax.numpy as jnp

    cfg = _kanana_cfg()
    r, dr, nh = cfg.kv_lora_rank, cfg.qk_rope_head_dim, 4
    rng = np.random.default_rng(0)
    pool = {"latent_pages": jnp.asarray(
        rng.standard_normal((6, 8, 128)), jnp.float32)}
    tables = jnp.asarray([[3, 1, 4, 0]], jnp.int32)
    cache = paged_kv.PagedKVCache(
        pool, tables, jnp.asarray([ctx], jnp.int32),
        jnp.asarray([n], jnp.int32), kernel="xla")
    lat, rope = (jnp.asarray(rng.standard_normal((1, n, w)), jnp.float32)
                 for w in (r, dr))
    ql, qr = (jnp.asarray(rng.standard_normal((1, n, nh, w)), jnp.float32)
              for w in (r, dr))
    out, after = cache.attend_latent(ql, qr, lat, rope, 0.2)
    assert out.shape == (1, n, nh, r)
    assert int(after.context_lens[0]) == ctx + n
    pages = np.asarray(after.pool["latent_pages"])
    order = np.asarray(tables[0])
    for j in range(n):
        page, at = order[(ctx + j) // 8], (ctx + j) % 8
        np.testing.assert_array_equal(pages[page, at, :r], lat[0, j])
        np.testing.assert_array_equal(pages[page, at, r:r + dr], rope[0, j])
        assert (pages[page, at, r + dr:] == 0).all()
    # the last query against every key up to itself, by hand
    keys = pages[order].reshape(-1, 128)[:ctx + n]
    q = np.concatenate([ql[0, -1], qr[0, -1]], axis=-1)      # [nh, r + dr]
    sc = q @ keys[:, :r + dr].T * 0.2
    p = np.exp(sc - sc.max(-1, keepdims=True))
    want = (p / p.sum(-1, keepdims=True)) @ keys[:, :r]
    np.testing.assert_allclose(np.asarray(out[0, -1]), want, atol=1e-5)


# ---------------------------------------------------------------------------
# a third kind of per-request state: a state-space layer's arrays a slot
# ---------------------------------------------------------------------------

def _granite_cfg(**kw):
    from megatron_llm_tpu.models.granite import granite_config

    return granite_config("tiny", use_flash_attn=False, **kw)


def test_a_state_space_layer_is_of_the_state_group():
    cfg = _granite_cfg()
    groups = paged_kv.layer_groups(cfg)
    assert groups == (paged_kv.STATE, paged_kv.STATE, paged_kv.FULL,
                      paged_kv.STATE) * 2
    pools = paged_kv.init_pools(cfg, 5, 8, num_slots=3)
    assert [sorted(p) for p in pools] == [
        ["conv_state", "ssm_state"], ["conv_state", "ssm_state"],
        ["k_pages", "v_pages"], ["conv_state", "ssm_state"]] * 2
    # slots + 1 rows: the last is the garbage row
    assert pools[0]["ssm_state"].shape == (4, 8, 32, 16)
    assert pools[0]["conv_state"].shape == (4, 3, 8 * 32 + 2 * 16)
    assert pools[2]["k_pages"].shape == (5, 8, 2, 32)
    # the page programs and a block's bytes are the paged pools' alone
    paged = paged_kv.paged_pools(pools)
    assert len(paged) == 2 and not any(paged_kv.is_state(p) for p in paged)
    assert paged_kv.block_bytes(pools) == paged_kv.block_bytes(paged) == (
        2 * 2 * 8 * 2 * 32 * 4)
    copied = paged_kv.with_paged(pools, paged_kv.copy_page(paged, 1, 2))
    assert [paged_kv.is_state(p) for p in copied] == [
        paged_kv.is_state(p) for p in pools]
    assert copied[0] is pools[0]
    assert paged_kv.state_bytes_per_slot(pools) == 6 * 4 * (
        8 * 32 * 16 + 3 * 288)
    assert not paged_kv.array_shapes(pools) & paged_kv.state_shapes(pools)
    # a model without state-space layers has none of it
    llama = paged_kv.init_pools(llama_config("tiny"), 5, 8)
    assert paged_kv.paged_pools(llama) == llama
    assert paged_kv.state_bytes_per_slot(llama) == 0
    assert paged_kv.state_shapes(llama) == set()


@pytest.mark.parametrize("slots", [None, [2, 0]])
def test_a_states_rows_are_read_and_written_by_slot(slots):
    """``read_state`` gives a row its slot's arrays, zeros where its
    ``context_lens`` is 0; ``write_state`` writes a live row at its slot
    and an idle one at the garbage row.  A decode step's rows ARE the
    slots (``slots`` None), a chunk's carry theirs."""
    cfg = _granite_cfg()
    pool = jax.tree_util.tree_map(
        lambda a: jax.random.normal(jax.random.PRNGKey(0), a.shape, a.dtype),
        paged_kv.init_pools(cfg, 5, 8, num_slots=3)[0])
    rows = [0, 1] if slots is None else slots
    tables = {paged_kv.FULL: np.zeros((2, 2), np.int32)}
    if slots is not None:
        tables[paged_kv.STATE] = np.asarray(slots, np.int32)
    cache = paged_kv.step_caches(
        [pool], tables, np.asarray([0, 9], np.int32),
        np.asarray([1, 0], np.int32), "xla", (paged_kv.STATE,))[0]
    assert cache.group == paged_kv.STATE
    conv, ssm = cache.read_state()
    assert (np.asarray(ssm[0]) == 0).all() and (np.asarray(conv[0]) == 0).all()
    assert (np.asarray(ssm[1]) == np.asarray(pool["ssm_state"][rows[1]])).all()
    after = cache.write_state(conv + 1.0, ssm + 1.0)
    got = np.asarray(after.pool["ssm_state"])
    assert (got[rows[0]] == 1.0).all()                  # the live row's slot
    others = [s for s in range(3) if s != rows[0]]      # the idle row's kept
    assert (got[others] == np.asarray(pool["ssm_state"])[others]).all()
    assert np.asarray(after.context_lens).tolist() == [1, 9]
    assert after.pool["conv_state"].dtype == pool["conv_state"].dtype


def test_the_states_names_have_one_owner():
    """As the pages' names: a state-space layer's arrays are named in
    ``ops/paged_kv.py`` and nowhere else in the package (the ROLE
    ``ssm_state`` of a compiled program's rows is ``hlo_collectives``'s
    word, not a key of the pool)."""
    assert _grep(r"\[[\"'](ssm|conv)_state[\"']\]|[\"']conv_state[\"']",
                 "megatron_llm_tpu") == ["megatron_llm_tpu/ops/paged_kv.py"]
