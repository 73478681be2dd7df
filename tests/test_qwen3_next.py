"""Qwen3-Next (``model_type`` ``qwen3_next``: three gated delta-rule
layers to every gated attention layer by ``layer_types``, a state a slot
that is updated by what it holds beside the pages, a partial rotary, a
share of a softmax router's experts beside a shared expert under its own
gate), against the benchmark's plain reference.

Seeded random weights, CPU, float32 on both sides, small size: 8 layers
(two periods of gated_delta x 3, attention), hidden 128, 4 query and 2
key-value heads of 32 whose first 8 dimensions rotate, 2 key and 4 value
heads of 16 in a delta layer, the router's 16 experts of which 8 are
held (4-11) at 4 a token; contexts of 5 to 156 tokens over pages of 8
and chunks of 32.  The reference is the file the benchmark's probe loads
(``benchmarks/reference/qwen3_next.py``: the delta rule one token at a
time, no block, no cache), loaded here by path.  The family's row, the
helpers and the three standing questions are ``tests/_family.py``'s.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _family
from _family import BS, kernels, load, serve, tokens
from megatron_llm_tpu import config as C
from megatron_llm_tpu.models.gated_delta import (BLOCK, gated_delta_chunk,
                                                 gated_delta_mixer,
                                                 init_gated_delta_params)
from megatron_llm_tpu.models.qwen3_next import qwen3_next_config
from megatron_llm_tpu.ops import paged_kv
from megatron_llm_tpu.ops.pallas import delta_chunk, delta_step
from megatron_llm_tpu.ops.pallas import paged_attention as pa
from megatron_llm_tpu.ops.rope import apply_rotary_at

NAME = "qwen3_next"
FAULTS = ("no_delta", "no_beta", "no_decay", "decay_after", "no_l2norm",
          "no_q_scale", "kv_neighbour", "state_dropped_at_chunks",
          "conv_dropped_at_chunks", "no_z_gate", "norm_after_gate",
          "full_rotary", "no_attn_gate", "no_shared_gate", "scale_is_w",
          "nine_experts", "float8")
TINY = dict(use_flash_attn=False)


@pytest.fixture(scope="module")
def family():
    return _family.built(NAME)


# --- the mixer alone --------------------------------------------------------

def _mixer(seed=0):
    cfg = qwen3_next_config("tiny", hidden_size=64, **TINY)
    p = init_gated_delta_params(jax.random.PRNGKey(seed), cfg, jnp.float32)
    p = jax.tree_util.tree_map(lambda a: a * 4.0, p)
    # gates that differ by head and decays a block can tell
    p["A_log"] = jnp.log(jnp.linspace(0.5, 4.0, cfg.delta_value_heads))
    p["dt_bias"] = jnp.linspace(-3.0, 0.5, cfg.delta_value_heads)
    p["norm"]["scale"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), p["norm"]["scale"].shape)
    return cfg, p


def _token_by_token(h, p, cfg):
    """The reference's recurrence for one sequence h [n, hidden] of unit
    mean square a row (``_unit``: the reference's own norm, under a scale
    of one, then leaves it as it is to a part in a million): the mixer's
    output, the state and the columns it leaves."""
    ref = load(NAME)
    w = {"mixer_norm": jnp.zeros((h.shape[1],)),
         "in_proj": p["in_proj"]["kernel"], "ba_proj": p["ba_proj"]["kernel"],
         "conv_kernel": p["conv"]["kernel"], "dt_bias": p["dt_bias"],
         "A_log": p["A_log"], "gate_norm": p["norm"]["scale"],
         "out_proj": p["out_proj"]["kernel"]}
    return ref.delta_out(
        h, w, kh=cfg.delta_key_heads, hv=cfg.delta_value_heads,
        dk=cfg.delta_key_dim, dv=cfg.delta_value_dim,
        taps=cfg.delta_conv_taps, eps=cfg.layernorm_epsilon,
        faults=frozenset())


def _unit(h):
    """Rows of unit mean square: what the reference's norm leaves alone."""
    return h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True))


def _cache(cfg, slots, ctx, valid, rows=None, pool=None, kernel="xla"):
    pool = pool or paged_kv.init_pools(cfg, 4, BS, num_slots=slots)[0]
    assert set(pool) == {"conv_state", "delta_state"}
    return paged_kv.PagedKVCache(
        pool, jnp.zeros((len(ctx), 1), jnp.int32), jnp.asarray(ctx),
        jnp.asarray(valid), kernel=kernel, group=paged_kv.STATE,
        slots=None if rows is None else jnp.asarray(rows))


@pytest.mark.parametrize("n", [1, 2, BLOCK, BLOCK + 1, 3 * BLOCK + 7])
def test_a_chunk_from_zeros_is_the_recurrence(n):
    """The block form (a triangular system a block of 64 rows, the state
    handed on between blocks) against the token-by-token recurrence:
    lengths under, at and over a block's edge."""
    cfg, p = _mixer()
    h = _unit(jax.random.normal(jax.random.PRNGKey(2), (2, n, 64)))
    got = gated_delta_mixer(h, p, cfg)
    for b in range(2):
        np.testing.assert_allclose(got[b], _token_by_token(h[b], p, cfg)[0],
                                   atol=2e-5, rtol=0)


@pytest.mark.parametrize("first", [1, 3, 70])
def test_two_chunks_with_the_carried_state_equal_one(first):
    """A chunk from zeros, then the rest from what the slot holds: the
    state and the convolution's columns carried across the boundary, and
    the slot left with the recurrence's own."""
    cfg, p = _mixer()
    n = 2 * BLOCK + 9
    h = _unit(jax.random.normal(jax.random.PRNGKey(3), (1, n, 64)))
    whole, S, tail = _token_by_token(h[0], p, cfg)
    a, c = gated_delta_mixer(h[:, :first], p, cfg,
                             kv_cache=_cache(cfg, 2, [0], [first], rows=[1]))
    assert int(c.context_lens[0]) == first
    b, c = gated_delta_mixer(
        h[:, first:], p, cfg, kv_cache=dataclasses.replace(
            c, valid_lens=jnp.asarray([n - first])))
    np.testing.assert_allclose(jnp.concatenate([a, b], axis=1)[0], whole,
                               atol=2e-5, rtol=0)
    np.testing.assert_allclose(c.pool["delta_state"][1], S, atol=2e-5)
    np.testing.assert_allclose(c.pool["conv_state"][1], tail, atol=1e-5)
    assert not np.asarray(c.pool["delta_state"][0]).any()


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_n_steps_equal_a_chunk(kernel, monkeypatch):
    """The step form ([S, 1, h], row s is slot s) token after token, one
    row idle throughout, against the recurrence; ``pallas``: through
    ``delta_state_step`` in interpret mode."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    cfg, p = _mixer()
    n = 9
    h = _unit(jax.random.normal(jax.random.PRNGKey(4), (1, n, 64)))
    whole, S, tail = _token_by_token(h[0], p, cfg)
    cache = _cache(cfg, 3, [0, 0, 0], [1, 0, 1], kernel=kernel)
    outs = []
    for t in range(n):
        x = jnp.stack([h[0, t], jnp.ones((64,)), h[0, t]])[:, None]
        out, cache = gated_delta_mixer(x, p, cfg, kv_cache=cache)
        outs.append(out[0, 0])
    np.testing.assert_allclose(jnp.stack(outs), whole, atol=2e-5, rtol=0)
    assert np.asarray(cache.context_lens).tolist() == [n, 0, n]
    np.testing.assert_allclose(cache.pool["delta_state"][0], S, atol=2e-5)
    np.testing.assert_allclose(cache.pool["delta_state"][2], S, atol=2e-5)
    np.testing.assert_allclose(cache.pool["conv_state"][0], tail, atol=1e-5)
    # the idle row wrote nothing, into its slot or the garbage row's
    for name in ("delta_state", "conv_state"):
        assert not np.asarray(cache.pool[name][1]).any()
        assert not np.asarray(cache.pool[name][3]).any()


def test_padding_rows_and_idle_rows_are_exact():
    """A chunk padded past its valid tokens leaves the state and the
    columns at its last VALID token (a padded token neither decays nor
    writes); an idle row keeps what its slot held."""
    cfg, p = _mixer()
    n, valid = 2 * BLOCK, BLOCK + 11
    h = _unit(jax.random.normal(jax.random.PRNGKey(5), (2, n, 64)))
    pool = paged_kv.init_pools(cfg, 4, BS, num_slots=2)[0]
    held = jax.random.normal(jax.random.PRNGKey(6),
                             pool["delta_state"].shape[1:])
    pool = {**pool, "delta_state": pool["delta_state"].at[1].set(held)}
    out, c = gated_delta_mixer(
        h, p, cfg, kv_cache=_cache(cfg, 2, [0, 7], [valid, 0], rows=[0, 1],
                                   pool=pool))
    want, S, tail = _token_by_token(h[0, :valid], p, cfg)
    np.testing.assert_allclose(out[0, :valid], want, atol=2e-5, rtol=0)
    np.testing.assert_allclose(c.pool["delta_state"][0], S, atol=2e-5)
    np.testing.assert_allclose(c.pool["conv_state"][0], tail, atol=1e-5)
    np.testing.assert_array_equal(c.pool["delta_state"][1], held)
    assert np.asarray(c.context_lens).tolist() == [valid, 7]


def test_the_steps_kernel_is_the_dense_step(monkeypatch):
    """``delta_state_step`` under ``interpret`` against
    ``dense_gated_delta_step``: a live row, an idle row that moves
    nothing and a fresh row that starts from zeros whatever its slot
    held, the pool written in place at the live rows only."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    ks = jax.random.split(jax.random.PRNGKey(7), 7)
    b, kh, hv, d = 3, 2, 4, 16
    pool = jax.random.normal(ks[0], (b + 1, hv, d, d))
    q, k = (jax.random.normal(x, (b, kh, d)) for x in ks[1:3])
    v = jax.random.normal(ks[3], (b, hv, d))
    g = -jax.random.uniform(ks[4], (b, hv))
    beta = jax.random.uniform(ks[5], (b, hv))
    live = jnp.asarray([True, False, True])
    fresh = jnp.asarray([False, False, True])
    o, got = delta_step.delta_state_step(pool, q, k, v, g, beta, live, fresh)
    rows = jnp.where(fresh[:, None, None, None], 0.0, pool[:b])
    want_o, new = delta_step.dense_gated_delta_step(rows, q, k, v, g, beta)
    keep = live[:, None, None, None]
    np.testing.assert_allclose(
        got, pool.at[:b].set(jnp.where(keep, new, pool[:b])), atol=1e-5)
    np.testing.assert_allclose(o[live], want_o[live], atol=1e-5)
    assert not np.asarray(o[1]).any()


# --- the chunk's kernel ------------------------------------------------------

def _operands(b, n, *, kh=2, r=2, dk=16, dv=16, seed=0, parallel=0.0,
              gate=None, beta=None, drawn=True):
    """A chunk's operands as the mixer forms them: ``q`` scaled and ``k``
    of unit length a head, gates by head; ``parallel``: how much of every
    key is one common direction; ``gate`` / ``beta``: the same for every
    token."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    hv = kh * r

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = unit(jax.random.normal(ks[0], (b, n, kh, dk))) * dk ** -0.5
    k = jax.random.normal(ks[1], (b, n, kh, dk))
    k = unit((1 - parallel) * k
             + parallel * 4.0 * jax.random.normal(ks[6], (b, 1, kh, dk)))
    v = jax.random.normal(ks[2], (b, n, hv, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (b, n, hv), minval=-5.0,
                                    maxval=0.0))
    bt = jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (b, n, hv)))
    if gate is not None:
        g = jnp.full_like(g, gate)
    if beta is not None:
        bt = jnp.full_like(bt, beta)
    S = (jax.random.normal(ks[5], (b, hv, dk, dv)) if drawn
         else jnp.zeros((b, hv, dk, dv)))
    return q, k, v, g, bt, S


def _padded(g, beta, valid):
    """The gates as the mixer hands them on: 0 past a row's real tokens."""
    live = (jnp.arange(g.shape[1])[None] < jnp.asarray(valid)[:, None])
    return jnp.where(live[..., None], g, 0.0), jnp.where(live[..., None],
                                                         beta, 0.0)


@jax.jit
def _a_token_at_a_time(q, k, v, g, beta, S):
    """The recurrence itself in float32 (``dense_gated_delta_step``, every
    product at full precision): ``o`` and the state after the last
    token."""
    def token(S, x):
        o, S = delta_step.dense_gated_delta_step(S, *x)
        return S, o

    S, o = jax.lax.scan(token, S, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


# a chunk's length, each row's real tokens, whether the rows start from
# zeros or a drawn state
CHUNK_CASES = {
    "one_block_from_zeros": dict(n=BLOCK, drawn=False),
    "eight_blocks_from_a_drawn_state": dict(n=8 * BLOCK),
    "a_length_no_multiple_of_the_block": dict(n=3 * BLOCK + 7),
    "a_chunk_shorter_than_a_block": dict(n=12, valid=[5, 12]),
    "padding_past_valid_len_and_an_idle_row": dict(
        n=2 * BLOCK + 30, valid=[BLOCK + 11, 0, 2 * BLOCK + 30]),
    "one_value_head_a_key_head": dict(n=BLOCK + 3, r=1),
    "four_value_heads_a_key_head": dict(n=BLOCK + 3, r=4, kh=1),
    "wider_values_than_keys": dict(n=2 * BLOCK, dv=32),
}


@pytest.mark.parametrize("case", sorted(CHUNK_CASES))
def test_the_chunks_kernel_is_gated_delta_chunk_and_the_recurrence(
        case, monkeypatch):
    """``delta_state_chunk`` under interpret against ``gated_delta_chunk``
    on the same rows' state, and both against the recurrence a token at
    a time in float32: ``o`` at the real tokens, the state a row leaves;
    an idle row's state bit for bit."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    c = dict(CHUNK_CASES[case])
    n, valid = c.pop("n"), c.pop("valid", None)
    b = len(valid) if valid else 2
    valid = valid or [n] * b
    q, k, v, g, beta, S = _operands(b, n, seed=21, **c)
    g, beta = _padded(g, beta, valid)
    o, new = delta_chunk.delta_state_chunk(q, k, v, g, beta, S, jnp.float32)
    with jax.default_matmul_precision("highest"):
        wo, wnew = gated_delta_chunk(q, k, v, g, beta, S, jnp.float32)
    assert o.shape == wo.shape and new.shape == wnew.shape
    assert o.dtype == new.dtype == jnp.float32
    real = np.arange(n)[None] < np.asarray(valid)[:, None]
    np.testing.assert_allclose(o[real], wo[real], atol=2e-5)
    np.testing.assert_allclose(new, wnew, atol=2e-5)
    ro, rnew = _a_token_at_a_time(q, k, v, g, beta, S)
    np.testing.assert_allclose(o[real], ro[real], atol=5e-5)
    np.testing.assert_allclose(new, rnew, atol=5e-5)
    for row, tokens_ in enumerate(valid):
        if not tokens_:
            np.testing.assert_array_equal(new[row], S[row])


@pytest.mark.parametrize("cdtype", ["float32", "bfloat16"])
def test_the_worst_conditioned_system_is_solved_as_well_as_xla_solves_it(
        cdtype, monkeypatch):
    """Gates near 0 with ``beta`` near 1 over nearly parallel keys: ``A``
    is nearly the full strictly lower triangle of ones, the worst ``I +
    A`` the delta rule forms.  The kernel's inverse by blocks stands no
    further from the recurrence in float32 than 1.5 times XLA's forward
    substitution does."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    cd = jnp.dtype(cdtype)
    q, k, v, g, beta, S = _operands(2, 4 * BLOCK, seed=22, parallel=0.9,
                                    gate=-1e-4, beta=0.999)
    # what both forms are handed: operands in the compute dtype
    q, k, v = (x.astype(cd).astype(jnp.float32) for x in (q, k, v))
    assert float(jnp.einsum("bnhd,bmhd->bhnm", k, k).min()) > 0.85
    ro, rnew = _a_token_at_a_time(q, k, v, g, beta, S)
    apart = {}
    for name, chunk in (("kernel", delta_chunk.delta_state_chunk),
                        ("xla", gated_delta_chunk)):
        with jax.default_matmul_precision("highest"):
            o, new = chunk(q.astype(cd), k.astype(cd), v.astype(cd), g,
                           beta, S, cd)
        assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(new).all())
        apart[name] = (float(jnp.abs(o - ro).max()),
                       float(jnp.abs(new - rnew).max()))
    floor = 1e-5 if cd == jnp.float32 else 0.0
    for mine, xlas in zip(apart["kernel"], apart["xla"]):
        assert mine <= 1.5 * max(xlas, floor), apart
    assert apart["kernel"][0] < (1e-4 if cd == jnp.float32 else 5e-2), apart


def test_in_the_kernel_a_key_heads_two_value_heads_read_it_alone(
        monkeypatch):
    """Another key head 0 through the chunk's kernel: exactly its two
    value heads' outputs and states move, key head 1's not a bit."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    q, k, v, g, beta, S = _operands(1, BLOCK + 20, seed=23)
    other = k.at[:, :, 0].set(k[:, ::-1, 0])
    one = delta_chunk.delta_state_chunk(q, k, v, g, beta, S, jnp.float32)
    two = delta_chunk.delta_state_chunk(q, other, v, g, beta, S, jnp.float32)
    assert one[0].shape == (1, BLOCK + 20, 4, 16)
    # the value heads' axis of ``o`` and of the state
    for a, b, heads in zip(one, two, (2, 1)):
        moved = jnp.abs(a - b).max(axis=tuple(
            i for i in range(a.ndim) if i != heads))
        assert float(moved[:2].min()) > 1e-3, moved
        assert not np.asarray(moved[2:]).any(), moved


def test_chunks_in_the_kernel_then_steps_in_the_steps_are_one_long_chunk(
        monkeypatch):
    """Two rows through the mixer under a cache on the kernel path: a
    chunk of 150 tokens from zeros over dirty slots, one of 70 over what
    it left, then six steps through ``delta_state_step`` (row s is slot
    s), against ONE chunk of 226 tokens in XLA's form with no cache; the
    slots are left with the recurrence's state."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    cfg, p = _mixer()
    h = _unit(jax.random.normal(jax.random.PRNGKey(24), (2, 226, 64)))
    whole = gated_delta_mixer(h, p, cfg)
    pool = paged_kv.init_pools(cfg, 4, BS, num_slots=2)[0]
    pool = {name: jax.random.normal(jax.random.PRNGKey(25), a.shape
                                    ).astype(a.dtype)
            for name, a in pool.items()}
    dirty = pool["delta_state"][2]
    outs, context = [], 0
    for lo, hi in ((0, 150), (150, 220)):
        out, c = gated_delta_mixer(
            h[:, lo:hi], p, cfg, kv_cache=_cache(
                cfg, 2, [context] * 2, [hi - lo] * 2, rows=[0, 1],
                pool=pool, kernel="pallas"))
        outs.append(out)
        pool, context = c.pool, hi
        assert c.context_lens.tolist() == [hi, hi]
    for t in range(220, 226):
        out, c = gated_delta_mixer(
            h[:, t:t + 1], p, cfg, kv_cache=_cache(
                cfg, 2, [t] * 2, [1] * 2, pool=pool, kernel="pallas"))
        outs.append(out)
        pool = c.pool
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), whole,
                               atol=2e-5, rtol=0)
    for b in range(2):
        S = _token_by_token(h[b], p, cfg)[1]
        np.testing.assert_allclose(pool["delta_state"][b], S, atol=2e-5)
    np.testing.assert_array_equal(pool["delta_state"][2], dirty)


def test_the_mixer_on_the_kernel_path_is_the_mixer_on_xlas(monkeypatch):
    """The chunk branch under a ``'pallas'`` cache against the same under
    an ``'xla'`` one and the reference's recurrence: a row padded past
    its valid tokens beside an idle row, whose slot is left bit for bit."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    cfg, p = _mixer()
    n, valid = 2 * BLOCK, BLOCK + 11
    h = _unit(jax.random.normal(jax.random.PRNGKey(26), (2, n, 64)))
    pool = paged_kv.init_pools(cfg, 4, BS, num_slots=2)[0]
    held = jax.random.normal(jax.random.PRNGKey(27),
                             pool["delta_state"].shape[1:])
    pool = {**pool, "delta_state": pool["delta_state"].at[1].set(held)}
    got = {kernel: gated_delta_mixer(
        h, p, cfg, kv_cache=_cache(cfg, 2, [0, 7], [valid, 0], rows=[0, 1],
                                   pool=pool, kernel=kernel))
        for kernel in ("pallas", "xla")}
    (out, c), (xout, xc) = got["pallas"], got["xla"]
    np.testing.assert_allclose(out[0, :valid], xout[0, :valid], atol=2e-5)
    np.testing.assert_allclose(c.pool["delta_state"][0],
                               xc.pool["delta_state"][0], atol=2e-5)
    want, S, tail = _token_by_token(h[0, :valid], p, cfg)
    np.testing.assert_allclose(out[0, :valid], want, atol=2e-5, rtol=0)
    np.testing.assert_allclose(c.pool["delta_state"][0], S, atol=2e-5)
    np.testing.assert_allclose(c.pool["conv_state"][0], tail, atol=1e-5)
    np.testing.assert_array_equal(c.pool["delta_state"][1], held)
    assert np.asarray(c.context_lens).tolist() == [valid, 7]


@pytest.mark.parametrize("path", ["no_cache", "xla", "pallas"])
def test_the_chunks_kernel_is_taken_only_under_a_cache_on_the_pallas_path(
        path, monkeypatch):
    """The cache-less forward (what differentiates through the model)
    and a cache whose ``kernel`` is ``'xla'`` (the CPU, several devices)
    trace no ``pallas_call`` and keep ``triangular_solve``; a cache on
    the ``'pallas'`` path traces ``delta_state_chunk`` and no solve."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    cfg, p = _mixer()
    h = jnp.ones((1, 40, 64))
    if path == "no_cache":
        text = str(jax.make_jaxpr(lambda p: gated_delta_mixer(h, p, cfg))(p))
    else:
        cache = _cache(cfg, 2, [0], [40], rows=[1], kernel=path)
        text = str(jax.make_jaxpr(
            lambda p, c: gated_delta_mixer(h, p, cfg, kv_cache=c)[0])(
                p, cache))
    assert ("pallas_call" in text) == (path == "pallas"), path
    assert ("delta_state_chunk" in text) == (path == "pallas"), path
    assert ("triangular_solve" in text) == (path != "pallas"), path


# --- the stack --------------------------------------------------------------

@pytest.mark.parametrize("n", [5, 16, 17, 70])
def test_full_forward_matches_the_reference(n):
    """The program's plain (cache-less) forward, a scan over PERIODS of
    four with each layer's mixer taken by its index among its kind:
    logits at every position against the reference."""
    _family.full_forward_is_the_references(NAME, n)


@pytest.mark.parametrize("prompt,new,kernel", [
    (5, 14, "off"), (64, 10, "off"), (150, 6, "off"), (75, 6, "on")])
def test_the_engine_over_pool_and_state_group_matches_one_full_forward(
        engines, prompt, new, kernel):
    """Chunked prefill (chunks of 32, the last one padded) then decode
    through the engine's own programs, the state and the columns carried
    in their slot across every chunk boundary and step and the keys of
    the two attention layers in their pages, against the reference's ONE
    forward: logits at every chunk's last row and every step, and the
    state the slot is left with; (``on``) the walk and the step's kernel
    in interpret mode."""
    eng, since, _ = _family.chunked_prefill_then_decode_is_one_forward(
        engines, NAME, prompt, new, kernel)
    stats, records = since()
    # six delta layers: every chunk and step of one live row
    launches = -(-prompt // 32) + new - 1
    assert stats["delta_rows_live"] == 6 * launches
    assert stats["delta_tokens"] == 6 * (prompt + new - 1)
    # the kernel moves the live row, the XLA step every slot and the
    # garbage row
    moved = 1 if kernel == "on" else eng.config.num_slots + 1
    assert stats["delta_rows_moved"] == 6 * moved * (new - 1)
    assert stats["ssm_state_bytes_held"] > 0
    # the chunks ran in the chunk's kernel, every token of every layer,
    # or none of them; a step's tokens are never the chunk's
    chunks = [r for r in records if r.kind == "prefill"]
    assert eng.prefill_kernel == ("pallas" if kernel == "on" else "xla")
    assert len(chunks) == -(-prompt // 32)
    assert all(r.delta_tokens > 0 and r.delta_chunk_tokens_kernel
               == (r.delta_tokens if kernel == "on" else 0) for r in chunks)
    assert stats["delta_chunk_tokens_kernel"] \
        == (6 * prompt if kernel == "on" else 0)
    assert not any(r.delta_chunk_tokens_kernel for r in records
                   if r.kind != "prefill")


@pytest.mark.parametrize("fault", FAULTS)
def test_each_named_fault_fails_by_many_tolerances(fault):
    """The term that reads the state left out, ``beta`` or the decay left
    out or misplaced, the l2 norms or the query's scale left out, a value
    head on its neighbour's key, a chunk's state or columns not handed
    on, the gate left out or before the norm, the whole head rotated,
    either sigmoid gate left out, ``1 + w`` read as ``w``, nine experts
    for ten... four here, float8."""
    _family.a_named_fault_is_told(NAME, fault)


def test_a_slot_is_reused_by_a_second_request(engines):
    """A request of 150 + 6 tokens, then a short one in the same slot
    with no clearing launch: the second answers as a fresh engine does,
    logits and all."""
    _family.a_slot_is_reused(engines, NAME, **kernels("off"))


def test_a_state_kept_in_bf16_is_not_the_references(engines, monkeypatch):
    """The ASSUMPTION of a float32 state, from the other side: rounded to
    bf16 in its slot at every launch, the state stands hundreds of
    float32 tolerances from the reference's."""
    monkeypatch.setattr(paged_kv, "SSM_STATE_DTYPE", jnp.bfloat16)
    eng = engines.fresh(NAME, num_slots=1)
    assert eng._st.pages[0]["delta_state"].dtype == jnp.bfloat16
    toks = tokens(70, seed=9)
    req = serve(eng, toks, 6)
    apart = _family.state_apart(NAME, eng, req.slot,
                                toks + list(req.out_tokens)[:-1])
    assert min(apart) > 1e-3, apart


def test_the_four_shares_add_up_to_the_uncut_layer(family):
    """16 experts in 4 shares: the four shares' routed parts plus the
    shared expert (under its gate) ONCE equal the uncut reference's
    layer, and every share's histogram is the ROUTER's, over all 16."""
    from megatron_llm_tpu.models import moe

    model, params, ref, weights, cfg = family
    mcfg = model.cfg.replace(num_experts=4, moe_router_experts=16)
    mlp = jax.tree_util.tree_map(lambda a: a[0],
                                 params["transformer"]["layers"]["mlp"])
    key = jax.random.PRNGKey(11)
    # all 16 experts of the uncut layer (the tree holds 8 of them)
    w_in = 0.3 * jax.random.normal(
        key, (16,) + mlp["experts"]["w_in"].shape[1:])
    w_out = 0.3 * jax.random.normal(
        jax.random.fold_in(key, 1), (16,) + mlp["experts"]["w_out"].shape[1:])
    x = _unit(jax.random.normal(jax.random.fold_in(key, 2), (1, 24, 128)))

    def share(first):
        p = {"router": mlp["router"],
             "experts": {"w_in": w_in[first:first + 4],
                         "w_out": w_out[first:first + 4]}}
        if first == 0:
            p["shared"] = mlp["shared"]
        out, _, counts = moe.moe_mlp_dropless(x, p, mcfg.replace(
            moe_experts_first=first, moe_shared_experts=int(first == 0),
            moe_shared_expert_gate=first == 0))
        return out[0], counts

    parts = [share(first) for first in (0, 4, 8, 12)]
    for _, counts in parts[1:]:
        np.testing.assert_array_equal(counts, parts[0][1])
    assert parts[0][1].shape == (16,)
    assert int(parts[0][1].sum()) == 24 * mcfg.moe_top_k

    class Whole:
        def expert(self, i, e):
            f = w_in.shape[2] // 2
            return {"w1": w_in[e][:, :f], "w3": w_in[e][:, f:],
                    "w2": w_out[e]}

    # the reference's own norm over rows of unit mean square, scale one
    w = {**weights.layer(0), "ffn_norm": jnp.zeros((128,))}
    want = ref.moe_out(x[0], w, Whole(), {**cfg, "rms_norm_eps": 0.0}, 0, {},
                       frozenset(), held=range(16))[0]
    assert float(jnp.std(want)) > 0.05
    np.testing.assert_allclose(sum(out for out, _ in parts), want,
                               atol=1e-4, rtol=0)
    # and the shared expert counted twice is another layer
    twice = parts[0][0] + sum(out for out, _ in parts)
    assert np.abs(np.asarray(twice - want)).max() > 1e-2


def test_a_partial_rotary_is_the_references_halves(family):
    """A typed attention layer rotates the first ``head_dim *
    rotary_percent`` dimensions of a head and passes the rest: the
    program's interleaved pairs against the reference's halves through
    ``from_program``'s permutation of the columns."""
    ref = load(NAME)
    fp = load(NAME + "_from_program")
    d, rot, heads, s = 32, 8, 3, 11
    x = jax.random.normal(jax.random.PRNGKey(12), (1, s, heads, d))
    pos = jnp.arange(5, 5 + s)[None]
    got = apply_rotary_at(x, pos, 1e7, rot_d=rot)[0]
    # the reference's column c holds the program's column within[c]
    within = fp.partial_rotate_half_columns(1, d, rot)
    theirs = ref._rotate_half(x[0][..., within], pos[0], 1e7, rot)
    np.testing.assert_allclose(got[..., within], theirs, atol=1e-5)
    # the last 24 dimensions pass, the whole head's rotation is another
    np.testing.assert_array_equal(got[..., rot:], x[0][..., rot:])
    whole = apply_rotary_at(x, pos, 1e7)[0]
    assert np.abs(np.asarray(whole - got)).max() > 0.1
    mcfg = family.model.cfg
    assert mcfg.rotary_percent == 0.25
    assert int(mcfg.head_dim * mcfg.rotary_percent) == rot


def test_two_requests_decode_side_by_side(family, engines):
    """Continuous batching over the state: two requests in two slots, one
    admitted while the other decodes, each as if alone."""
    from megatron_llm_tpu.serving import SamplingParams

    model, params, ref, weights, cfg = family
    eng = engines(NAME, **kernels("off"))
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


def test_the_wrapper_asserts_its_flags():
    """``--model_name=qwen3_next`` is the assert-the-flags wrapper its
    siblings are: a config without the shared expert's gate, the
    attention gate or a delta layer is another model."""
    from megatron_llm_tpu.models import MODEL_REGISTRY

    MODEL_REGISTRY[NAME](qwen3_next_config("tiny", **TINY))
    for wrong in (dict(moe_shared_expert_gate=False),
                  dict(attention_output_gate=False),
                  dict(layer_types=("attention",))):
        with pytest.raises(AssertionError):
            MODEL_REGISTRY[NAME](qwen3_next_config("tiny", **TINY, **wrong))
    with pytest.raises(ValueError, match="moe_shared_expert_gate gates"):
        qwen3_next_config("tiny", **TINY, moe_shared_experts=0)
    full = qwen3_next_config("80b-a3b")
    assert full.delta_conv_dim == 8192 and full.head_dim == 256
    assert full.mixer_counts == {"gated_delta": 36, "attention": 12}
    assert C.refusal(full) is None


def test_the_flags_build_the_wrappers_config():
    """The command line reaches every new field: the cell's rehearsal
    flags build the tiny preset's config, and ``--model_name=qwen3_next``'s
    presets are the wrapper's."""
    import json
    import os

    from megatron_llm_tpu.arguments import (parse_args,
                                            transformer_config_from_args,
                                            validate_args)
    from megatron_llm_tpu.models.qwen3_next import Qwen3NextModel

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    flags = json.load(open(os.path.join(
        root, "benchmarks", "configs", "qwen3-next-80b-a3b-serve.json")))[
            "program"]["rehearsal_flags"]
    flags = [f for f in flags if not f.startswith(("--serve_", "--model_name",
                                                   "--tokenizer_type",
                                                   "--vocab_size"))]
    args = validate_args(parse_args(args_list=flags + [
        "--position_embedding_type=rotary", "--glu_activation=swiglu",
        "--no_bias", "--use_rms_norm", "--no_tie_embed_logits",
        "--padded_vocab_size=512"]), world_size=1)
    cfg = transformer_config_from_args(args)
    want = qwen3_next_config("tiny", seq_length=256,
                             max_position_embeddings=512)
    for field in ("layer_types", "num_experts", "moe_router_experts",
                  "moe_top_k", "moe_shared_experts", "moe_shared_expert_gate",
                  "attention_output_gate", "qk_norm_per_head",
                  "rotary_percent", "rope_theta", "delta_key_heads",
                  "delta_value_heads", "delta_key_dim", "delta_value_dim",
                  "delta_conv_taps", "head_dim", "layernorm_epsilon"):
        assert getattr(cfg, field) == getattr(want, field), field
    Qwen3NextModel(cfg)
    import finetune

    preset = finetune.MODEL_DEFAULTS[NAME]
    assert preset["moe_shared_expert_gate"] and preset["attention_output_gate"]
    assert preset["layer_types"] == ["gated_delta"] * 3 + ["attention"]
    assert (preset["rotary_percent"], preset["kv_channels"]) == (0.25, 256)
