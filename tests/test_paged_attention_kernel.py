"""The ragged paged-attention Pallas kernel (decode and chunked prefill
are one walk over each slot's live pages), run in interpret mode on CPU:
kernel vs the XLA dense-gather reference vs a per-slot numpy oracle,
across ragged context lengths, GQA group counts, sliding window, and
int8 KV quantization — plus model-level parity of the transformer's
paged branch with the kernels forced on vs off.  Decode cases cover a
table far longer than what is live, contexts around a compute-block
boundary, windows that start inside a block, slots that are not
decoding, and the serving cells' own shapes.  Prefill cases cover the
ragged edges: chunks straddling page and block boundaries, context 0,
cached-prefix tail chunks starting mid-page, windows shorter than the
chunk, and multi-q-block grids."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.models.language_model import language_model_forward
from megatron_llm_tpu.models.llama import LlamaModel, llama_config
from megatron_llm_tpu.ops import paged_kv
from megatron_llm_tpu.ops.pallas import paged_attention as pa
from megatron_llm_tpu.quantization import absmax_quantize_int8


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = pa._INTERPRET
    pa._INTERPRET = True
    yield
    pa._INTERPRET = old


def _dense(q, kp, vp, bt, lens, ks, vs, scale, window):
    """The dense reference, every row real; q [S, nh, d] is decode."""
    q4 = q if q.ndim == 4 else q[:, None]
    out = pa.dense_paged_attention(q4, kp, vp, bt, lens, None, ks, vs,
                                   scale, window)
    return out if q.ndim == 4 else out[:, 0]


def _build_case(rng, S, M, bs, g, nh, d, lens):
    """Linear per-slot K/V [S, M*bs, g, d] scattered into a shared page
    pool through ragged block tables.  Unowned pages (including the
    reserved garbage block 0 that pads every table) are filled with
    large garbage so a kernel that reads or fails to mask them diverges
    loudly from the oracle."""
    L = M * bs
    q = rng.standard_normal((S, nh, d)).astype(np.float32)
    k_lin = rng.standard_normal((S, L, g, d)).astype(np.float32)
    v_lin = rng.standard_normal((S, L, g, d)).astype(np.float32)
    P = 1 + S * M
    k_pages = (rng.standard_normal((P, bs, g, d)) * 100.0).astype(np.float32)
    v_pages = (rng.standard_normal((P, bs, g, d)) * 100.0).astype(np.float32)
    bt = np.zeros((S, M), np.int32)
    nxt = 1
    for s in range(S):
        for j in range(int(lens[s]) // bs + 1):   # pages live at decode pos
            bt[s, j] = nxt
            k_pages[nxt] = k_lin[s, j * bs:(j + 1) * bs]
            v_pages[nxt] = v_lin[s, j * bs:(j + 1) * bs]
            nxt += 1
    return q, k_lin, v_lin, k_pages, v_pages, bt


def _oracle(q, k_lin, v_lin, lens, scale, window):
    """Per-(slot, head) dense softmax attention over the linear K/V —
    independent of both the kernel and the jnp reference."""
    S, L, g, d = k_lin.shape
    nh = q.shape[1]
    qpg = nh // g
    out = np.zeros((S, nh, d), np.float32)
    pos = np.arange(L)
    for s in range(S):
        valid = pos <= lens[s]
        if window is not None:
            valid &= pos > lens[s] - window
        for h in range(nh):
            grp = h // qpg
            sc = (k_lin[s, :, grp] @ q[s, h]) * scale
            sc = np.where(valid, sc, -np.inf)
            p = np.exp(sc - sc[valid].max())
            p = np.where(valid, p, 0.0)
            p /= p.sum()
            out[s, h] = p @ v_lin[s, :, grp]
    return out


S, M, BS, D = 4, 4, 8, 16
LENS = np.asarray([0, 5, 17, 31], np.int32)   # ragged: 1/1/3/4 live pages


@pytest.mark.parametrize("window", [None, 12])
@pytest.mark.parametrize("g,nh", [(1, 1), (2, 4), (4, 4)])
def test_kernel_matches_oracle_and_reference(g, nh, window):
    rng = np.random.default_rng(7 * g + nh + (window or 0))
    q, k_lin, v_lin, kp, vp, bt = _build_case(rng, S, M, BS, g, nh, D, LENS)
    scale = 1.0 / math.sqrt(D)
    got = np.asarray(pa.paged_attention_decode(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(LENS), sliding_window=window))
    ref = np.asarray(_dense(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(LENS), None, None, scale, window))
    want = _oracle(q, k_lin, v_lin, LENS, scale, window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(ref, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [None, 12])
def test_kernel_int8_dequant(window):
    """int8 pools + per-(page, position, group) scales: the in-kernel
    dequant matches the reference dequant bit-for-bit-ish (same
    quantized inputs), and both stay within the quantization drift
    bound of the float oracle."""
    g, nh = 2, 4
    rng = np.random.default_rng(42 + (window or 0))
    q, k_lin, v_lin, kp, vp, bt = _build_case(rng, S, M, BS, g, nh, D, LENS)
    scale = 1.0 / math.sqrt(D)
    kq, ks = absmax_quantize_int8(jnp.asarray(kp), axis=-1)
    vq, vs = absmax_quantize_int8(jnp.asarray(vp), axis=-1)
    got = np.asarray(pa.paged_attention_decode(
        jnp.asarray(q), kq, vq, jnp.asarray(bt), jnp.asarray(LENS),
        k_scales=ks, v_scales=vs, sliding_window=window))
    ref = np.asarray(_dense(
        jnp.asarray(q), kq, vq, jnp.asarray(bt), jnp.asarray(LENS),
        ks, vs, scale, window))
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)
    want = _oracle(q, k_lin, v_lin, LENS, scale, window)
    drift = np.max(np.abs(got - want)) / (np.std(want) + 1e-6)
    assert drift < 0.2, drift


# ---------------------------------------------------------------------------
# decode: the walk follows what is live, not the table
# ---------------------------------------------------------------------------

LONG_M = 64               # a table far longer than anything live


def _decode(q, kp, vp, bt, lens, **kw):
    return np.asarray(pa.paged_attention_decode(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
        jnp.asarray(lens), **kw))


def _with_dead_entries(bt, lens, bs, page):
    """The table with every entry past a slot's last live page pointed
    at ``page``."""
    dead = np.arange(bt.shape[1])[None, :] > (np.asarray(lens) // bs)[:, None]
    return np.where(dead, page, bt).astype(np.int32)


@pytest.fixture
def two_page_blocks(monkeypatch):
    """Compute blocks of two 8-token pages, so that a few dozen tokens
    cross block boundaries."""
    monkeypatch.setattr(pa, "_BLOCK_TOKENS", 2 * BS)
    assert pa._pages_per_block(BS, 2, D, np.float32, LONG_M) == 2


@pytest.mark.parametrize("window", [None, 12])
@pytest.mark.parametrize("live_pages", [1, 2, 3, 5])
def test_decode_long_table_never_reads_dead_entries(live_pages, window,
                                                    two_page_blocks):
    """M = 64 with 1-5 live pages a slot.  Dead entries pointing at a
    page of huge values, at a page of NaN and at the last pool page give
    bit-equal outputs that match the oracle: nothing of the table
    outside first..last is fetched."""
    g, nh = 2, 4
    rng = np.random.default_rng(100 + live_pages + (window or 0))
    lens = np.asarray([live_pages * BS - 1, (live_pages - 1) * BS,
                       live_pages * BS - 3, (live_pages - 1) * BS + 2],
                      np.int32)
    q, k_lin, v_lin, kp, vp, bt = _build_case(rng, S, LONG_M, BS, g, nh, D,
                                              lens)
    P = kp.shape[0]
    huge, nan = P - 3, P - 2          # unowned pages (S * LONG_M >> live)
    kp[huge], vp[huge] = 1e30, -1e30
    kp[nan], vp[nan] = np.nan, np.nan
    outs = [_decode(q, kp, vp, _with_dead_entries(bt, lens, BS, page), lens,
                    sliding_window=window) for page in (huge, nan, P - 1)]
    want = _oracle(q, k_lin, v_lin, lens, 1.0 / math.sqrt(D), window)
    np.testing.assert_allclose(outs[0], want, atol=2e-5, rtol=2e-5)
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[0], outs[2])


@pytest.mark.parametrize("ctx", [2 * BS - 2, 2 * BS - 1, 2 * BS,
                                 4 * BS - 2, 4 * BS - 1, 4 * BS])
def test_decode_context_at_block_boundaries(ctx, two_page_blocks):
    """A query position just before, on and just after the last key of a
    two-page block, at the first and the second boundary."""
    g, nh = 2, 4
    rng = np.random.default_rng(200 + ctx)
    lens = np.asarray([ctx, 0, ctx, 3], np.int32)
    q, k_lin, v_lin, kp, vp, bt = _build_case(rng, S, LONG_M, BS, g, nh, D,
                                              lens)
    got = _decode(q, kp, vp, bt, lens)
    want = _oracle(q, k_lin, v_lin, lens, 1.0 / math.sqrt(D), None)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [5, 12, 20, 37])
def test_decode_window_starts_mid_block(window, two_page_blocks):
    """The window's first key lies inside a page and inside a block: the
    walk starts at that page and masks the keys before it by position."""
    g, nh = 2, 4
    rng = np.random.default_rng(300 + window)
    lens = np.asarray([50, 45, 61, 38], np.int32)
    assert all((int(c) - window + 1) % BS for c in lens)
    q, k_lin, v_lin, kp, vp, bt = _build_case(rng, S, LONG_M, BS, g, nh, D,
                                              lens)
    kp[0], vp[0] = np.nan, np.nan     # nobody's page: never fetched
    got = _decode(q, kp, vp, _with_dead_entries(bt, lens, BS, 0), lens,
                  sliding_window=window)
    want = _oracle(q, k_lin, v_lin, lens, 1.0 / math.sqrt(D), window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("decoding", [(1, 0, 1, 0), (0, 1, 1, 0),
                                      (0, 0, 0, 1), (1, 1, 0, 1)])
def test_decode_skips_slots_that_are_not_decoding(decoding, two_page_blocks):
    """Slots with ``valid_lens`` 0 between live ones: the live rows are
    exact, the others come back as zeros though their tables point at
    NaN pages, and nothing anywhere is NaN."""
    g, nh = 2, 4
    rng = np.random.default_rng(400 + sum(decoding))
    lens = np.asarray([40, 17, 33, 9], np.int32)
    q, k_lin, v_lin, kp, vp, bt = _build_case(rng, S, LONG_M, BS, g, nh, D,
                                              lens)
    vlen = np.asarray(decoding, np.int32)
    for s in np.flatnonzero(vlen == 0):
        kp[bt[s, 0]], vp[bt[s, 0]] = np.nan, np.nan
    got = _decode(q, kp, vp, bt, lens, valid_lens=jnp.asarray(vlen))
    want = _oracle(q, k_lin, v_lin, lens, 1.0 / math.sqrt(D), None)
    live = vlen > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=2e-5)
    assert np.isfinite(got).all()
    assert not got[~live].any()


@pytest.mark.parametrize("window", [None, 12])
def test_decode_int8_long_table(window, two_page_blocks):
    """int8 pools over several blocks of a long table: the scales ride
    on the scores and the probabilities, and match dequantizing first."""
    g, nh = 2, 4
    rng = np.random.default_rng(500 + (window or 0))
    lens = np.asarray([0, 15, 16, 37], np.int32)
    q, k_lin, v_lin, kp, vp, bt = _build_case(rng, S, LONG_M, BS, g, nh, D,
                                              lens)
    kq, ks = absmax_quantize_int8(jnp.asarray(kp), axis=-1)
    vq, vs = absmax_quantize_int8(jnp.asarray(vp), axis=-1)
    got = _decode(q, kq, vq, bt, lens, k_scales=ks, v_scales=vs,
                  sliding_window=window)
    ref = np.asarray(_dense(
        jnp.asarray(q), kq, vq, jnp.asarray(bt), jnp.asarray(lens),
        ks, vs, 1.0 / math.sqrt(D), window))
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)
    want = _oracle(q, k_lin, v_lin, lens, 1.0 / math.sqrt(D), window)
    drift = np.max(np.abs(got - want)) / (np.std(want) + 1e-6)
    assert drift < 0.2, drift


@pytest.mark.parametrize("dtype,block_tokens", [(np.float32, 128),
                                                (jnp.bfloat16, 256)])
def test_decode_at_the_cells_shapes(dtype, block_tokens):
    """Pages of [16, 8, 128] under 32 query heads, the serving cells'
    shapes: blocks of 8 pages in fp32 and 16 in bf16, contexts around
    the first block boundary, one slot not decoding."""
    bs, g, nh, d, M = 16, 8, 32, 128, LONG_M
    assert pa._pages_per_block(bs, g, d, dtype, M) * bs == block_tokens
    rng = np.random.default_rng(600 + block_tokens)
    lens = np.asarray([block_tokens - 2, block_tokens - 1, block_tokens, 70],
                      np.int32)
    q, k_lin, v_lin, kp, vp, bt = _build_case(rng, 4, M, bs, g, nh, d, lens)
    cast = lambda x: np.asarray(jnp.asarray(x, dtype).astype(jnp.float32))
    q, k_lin, v_lin = cast(q), cast(k_lin), cast(v_lin)
    vlen = np.asarray([1, 1, 1, 0], np.int32)
    got = np.asarray(pa.paged_attention_decode(
        jnp.asarray(q, dtype), jnp.asarray(kp, dtype), jnp.asarray(vp, dtype),
        jnp.asarray(_with_dead_entries(bt, lens, bs, kp.shape[0] - 1)),
        jnp.asarray(lens), valid_lens=jnp.asarray(vlen),
        sliding_window=4096).astype(jnp.float32))
    want = _oracle(q, k_lin, v_lin, lens, 1.0 / math.sqrt(d), 4096)
    tol = 2e-5 if dtype == np.float32 else 1e-2
    np.testing.assert_allclose(got[:3], want[:3], atol=tol, rtol=tol)
    assert not got[3].any()


def test_availability_tracks_backend(monkeypatch):
    assert pa.kernel_available()          # interpret fixture is on
    monkeypatch.setattr(pa, "_INTERPRET", False)
    monkeypatch.delenv("MLT_FORCE_PALLAS", raising=False)
    if jax.default_backend() != "tpu":
        assert not pa.kernel_available()


# ---------------------------------------------------------------------------
# chunked prefill: ragged-edge parity
# ---------------------------------------------------------------------------

# chunk C = 16 on bs = 8 pages; contexts hit the ragged edges: 0 (no
# history), 3 (chunk straddles the page-0/1 boundary mid-chunk), 8
# (chunk starts exactly on a page boundary), 17 (cached-prefix tail
# chunk starting mid-page, spilling into a 5th page)
CTX = np.asarray([0, 3, 8, 17], np.int32)
C = 16
MP = 6                    # pages per table; max live = 5, so dead tails


def _build_prefill_case(rng, S, M, bs, g, nh, d, ctx, C):
    """Engine-shaped prefill state: each slot's history (ctx keys) AND
    its in-flight chunk (C keys, scatter-before-read) live in the pool;
    linear positions past ctx+C — including tail positions of live
    pages — hold amplified garbage so an unmasked read diverges
    loudly."""
    L = M * bs
    q = rng.standard_normal((S, C, nh, d)).astype(np.float32)
    k_lin = rng.standard_normal((S, L, g, d)).astype(np.float32)
    v_lin = rng.standard_normal((S, L, g, d)).astype(np.float32)
    for s in range(S):
        k_lin[s, int(ctx[s]) + C:] *= 100.0
        v_lin[s, int(ctx[s]) + C:] *= 100.0
    P = 1 + S * M
    k_pages = (rng.standard_normal((P, bs, g, d)) * 100.0).astype(np.float32)
    v_pages = (rng.standard_normal((P, bs, g, d)) * 100.0).astype(np.float32)
    bt = np.zeros((S, M), np.int32)
    nxt = 1
    for s in range(S):
        for j in range((int(ctx[s]) + C + bs - 1) // bs):
            bt[s, j] = nxt
            k_pages[nxt] = k_lin[s, j * bs:(j + 1) * bs]
            v_pages[nxt] = v_lin[s, j * bs:(j + 1) * bs]
            nxt += 1
    return q, k_lin, v_lin, k_pages, v_pages, bt


def _prefill_oracle(q, k_lin, v_lin, ctx, scale, window):
    """Per-(slot, row, head) dense causal attention: row j of a chunk
    attends keys 0..ctx+j (window-clipped) — independent of both the
    kernel and the jnp reference."""
    S, Cq, nh, d = q.shape
    L, g = k_lin.shape[1], k_lin.shape[2]
    qpg = nh // g
    out = np.zeros((S, Cq, nh, d), np.float32)
    kpos = np.arange(L)
    for s in range(S):
        for j in range(Cq):
            pos = int(ctx[s]) + j
            valid = kpos <= pos
            if window is not None:
                valid &= kpos > pos - window
            for h in range(nh):
                grp = h // qpg
                sc = (k_lin[s, :, grp] @ q[s, j, h]) * scale
                sc = np.where(valid, sc, -np.inf)
                p = np.exp(sc - sc[valid].max())
                p = np.where(valid, p, 0.0)
                p /= p.sum()
                out[s, j, h] = p @ v_lin[s, :, grp]
    return out


@pytest.mark.parametrize("block_q", [None, 8])
@pytest.mark.parametrize("window", [None, 5])
@pytest.mark.parametrize("g,nh", [(1, 1), (2, 4), (4, 4)])
def test_prefill_kernel_matches_oracle_and_reference(g, nh, window,
                                                     block_q):
    """window=5 < C exercises windows shorter than the chunk;
    block_q=8 splits C=16 across two q-grid steps so the online-softmax
    scratch carries across both page and q-block boundaries."""
    rng = np.random.default_rng(11 * g + nh + (window or 0)
                                + (block_q or 0))
    q, k_lin, v_lin, kp, vp, bt = _build_prefill_case(
        rng, len(CTX), MP, BS, g, nh, D, CTX, C)
    scale = 1.0 / math.sqrt(D)
    got = np.asarray(pa.paged_attention_prefill(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(CTX), sliding_window=window,
        block_q=block_q))
    ref = np.asarray(_dense(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(CTX), None, None, scale, window))
    want = _prefill_oracle(q, k_lin, v_lin, CTX, scale, window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(ref, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [None, 5])
def test_prefill_kernel_int8_dequant(window):
    g, nh = 2, 4
    rng = np.random.default_rng(99 + (window or 0))
    q, k_lin, v_lin, kp, vp, bt = _build_prefill_case(
        rng, len(CTX), MP, BS, g, nh, D, CTX, C)
    scale = 1.0 / math.sqrt(D)
    kq, ks = absmax_quantize_int8(jnp.asarray(kp), axis=-1)
    vq, vs = absmax_quantize_int8(jnp.asarray(vp), axis=-1)
    got = np.asarray(pa.paged_attention_prefill(
        jnp.asarray(q), kq, vq, jnp.asarray(bt), jnp.asarray(CTX),
        k_scales=ks, v_scales=vs, sliding_window=window, block_q=8))
    ref = np.asarray(_dense(
        jnp.asarray(q), kq, vq, jnp.asarray(bt), jnp.asarray(CTX),
        ks, vs, scale, window))
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)
    want = _prefill_oracle(q, k_lin, v_lin, CTX, scale, window)
    drift = np.max(np.abs(got - want)) / (np.std(want) + 1e-6)
    assert drift < 0.2, drift


@pytest.mark.parametrize("block_q", [None, 8])
@pytest.mark.parametrize("window", [None, 5, 20])
def test_prefill_chunk_crosses_blocks(window, block_q, two_page_blocks):
    """A 16-token chunk over up to five pages walked two pages at a
    time: the running softmax carries across blocks, window 20 starts
    inside a block for some rows and before the table for others, and
    the last block stops short of its second page."""
    g, nh = 2, 4
    rng = np.random.default_rng(700 + (window or 0) + (block_q or 0))
    q, k_lin, v_lin, kp, vp, bt = _build_prefill_case(
        rng, len(CTX), MP, BS, g, nh, D, CTX, C)
    got = np.asarray(pa.paged_attention_prefill(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(CTX), sliding_window=window,
        block_q=block_q))
    want = _prefill_oracle(q, k_lin, v_lin, CTX, 1.0 / math.sqrt(D), window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("quantized", [False, True])
def test_prefill_skips_slots_with_no_token(quantized, two_page_blocks):
    """The verify step's idle rows: ``valid_lens`` 0 gives a row of
    zeros and touches no page (theirs hold NaN), the others are exact."""
    g, nh = 2, 4
    rng = np.random.default_rng(800 + quantized)
    q, k_lin, v_lin, kp, vp, bt = _build_prefill_case(
        rng, len(CTX), MP, BS, g, nh, D, CTX, C)
    vlen = np.asarray([C, 0, 0, 7], np.int32)
    kw = {}
    if quantized:
        kp, ks = absmax_quantize_int8(jnp.asarray(kp), axis=-1)
        vp, vs = absmax_quantize_int8(jnp.asarray(vp), axis=-1)
        kw = dict(k_scales=np.array(ks), v_scales=np.array(vs))
        for s in (1, 2):
            kw["k_scales"][bt[s, :3]] = np.nan
            kw["v_scales"][bt[s, :3]] = np.nan
    else:
        for s in (1, 2):
            kp[bt[s, :3]], vp[bt[s, :3]] = np.nan, np.nan
    got = np.asarray(pa.paged_attention_prefill(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
        jnp.asarray(CTX), valid_lens=jnp.asarray(vlen),
        **{k: jnp.asarray(v) for k, v in kw.items()}))
    want = _prefill_oracle(q, k_lin, v_lin, CTX, 1.0 / math.sqrt(D), None)
    assert np.isfinite(got).all()
    assert not got[1:3].any()
    if quantized:
        drift = np.max(np.abs(got[[0, 3]] - want[[0, 3]])) / np.std(want)
        assert drift < 0.2, drift
    else:
        np.testing.assert_allclose(got[[0, 3]], want[[0, 3]],
                                   atol=2e-5, rtol=2e-5)


def test_prefill_decode_consistency():
    """The decode entry point is literally the C == 1 instance of the
    ragged prefill: a one-row chunk through paged_attention_prefill
    equals paged_attention_decode on the same state."""
    g, nh = 2, 4
    rng = np.random.default_rng(5)
    q, _, _, kp, vp, bt = _build_case(rng, S, M, BS, g, nh, D, LENS)
    dec = np.asarray(pa.paged_attention_decode(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(LENS)))
    pre = np.asarray(pa.paged_attention_prefill(
        jnp.asarray(q)[:, None], jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(bt), jnp.asarray(LENS)))[:, 0]
    np.testing.assert_allclose(pre, dec, atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------------------
# model-level: transformer paged branch, kernel on vs off
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model_and_params():
    cfg = llama_config("tiny", num_layers=2, seq_length=64,
                       max_position_embeddings=64, padded_vocab_size=64,
                       use_flash_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _prefilled_pages(model, params, bt, lens, quantized):
    """A prefill on the dense path filling the shared pools through the
    block tables."""
    Sl, C = bt.shape[0], 16
    pages = paged_kv.init_pools(model.cfg, 1 + int(bt.max()), BS,
                                quantized=quantized)
    toks = jnp.asarray(np.arange(Sl * C).reshape(Sl, C) % 60 + 1, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(C)[None, :], (Sl, C))
    caches = paged_kv.step_caches(pages, bt, jnp.zeros((Sl,), jnp.int32),
                                  lens, "xla")
    _, caches = language_model_forward(params, toks, positions, None,
                                       model.cfg, rng_key=None, train=False,
                                       kv_caches=caches)
    return paged_kv.pools_of(caches)


@pytest.mark.parametrize("quantized", [False, True])
def test_transformer_paged_kernel_parity(model_and_params, quantized):
    """A decode step through the paged branch with the Pallas kernel
    forced on (interpret) produces the same logits as the XLA gather
    branch, on plain and int8 pools."""
    model, params = model_and_params
    Sl = 2
    bt = jnp.asarray(
        np.arange(1, 1 + Sl * M).reshape(Sl, M), jnp.int32)
    lens = jnp.asarray([5, 9], jnp.int32)
    pages = _prefilled_pages(model, params, bt, lens, quantized)
    nxt = jnp.asarray([[7], [11]], jnp.int32)
    outs = []
    for kernel in ("xla", "pallas"):
        caches = paged_kv.step_caches(pages, bt, lens,
                                      jnp.ones((Sl,), jnp.int32), kernel)
        logits, _ = language_model_forward(params, nxt, lens[:, None],
                                           None, model.cfg, rng_key=None,
                                           train=False, kv_caches=caches)
        outs.append(np.asarray(logits[:, 0], np.float32))
    np.testing.assert_allclose(outs[1], outs[0], atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("quantized", [False, True])
def test_transformer_prefill_kernel_parity(model_and_params, quantized):
    """Two engine-shaped prefill chunks — a ragged first chunk from
    empty caches, then a ragged cached-prefix tail chunk — through the
    paged branch with the Pallas prefill kernel forced on (interpret)
    match the XLA gather branch per valid row, on plain and int8 pools.
    Padded tail rows (j >= valid_lens) are garbage in both paths and
    excluded."""
    model, params = model_and_params
    cfg = model.cfg
    Sl, Cc = 2, 16
    bt = jnp.asarray(np.arange(1, 1 + Sl * M).reshape(Sl, M), jnp.int32)
    v0 = jnp.asarray([5, 16], jnp.int32)     # ragged first chunk
    v1 = jnp.asarray([9, 7], jnp.int32)      # ragged tail chunk
    toks0 = jnp.asarray(np.arange(Sl * Cc).reshape(Sl, Cc) % 60 + 1,
                        jnp.int32)
    toks1 = jnp.asarray((np.arange(Sl * Cc).reshape(Sl, Cc) * 3) % 60 + 1,
                        jnp.int32)
    outs = []
    for kernel in ("xla", "pallas"):
        pages = paged_kv.init_pools(cfg, 1 + int(bt.max()), BS,
                                    quantized=quantized)
        caches = paged_kv.step_caches(
            pages, bt, jnp.zeros((Sl,), jnp.int32), v0, kernel)
        pos0 = jnp.broadcast_to(jnp.arange(Cc)[None, :], (Sl, Cc))
        lg0, caches = language_model_forward(params, toks0, pos0, None,
                                             cfg, rng_key=None,
                                             train=False,
                                             kv_caches=caches)
        caches = [dataclasses.replace(c, valid_lens=v1) for c in caches]
        pos1 = v0[:, None] + jnp.arange(Cc)[None, :]
        lg1, _ = language_model_forward(params, toks1, pos1, None, cfg,
                                        rng_key=None, train=False,
                                        kv_caches=caches)
        outs.append((np.asarray(lg0, np.float32),
                     np.asarray(lg1, np.float32)))
    (a0, a1), (b0, b1) = outs
    for s in range(Sl):
        np.testing.assert_allclose(b0[s, :int(v0[s])], a0[s, :int(v0[s])],
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(b1[s, :int(v1[s])], a1[s, :int(v1[s])],
                                   atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# learned sparse attention: scores, choice and attention under it
# (ops/pallas/dsa_attention.py) against the dense gather-and-mask path
# (ops/dsa.py, kernel='xla')
# ---------------------------------------------------------------------------

def _selected_case(rng, S, n, M, bs, g, nh, d, hi, ctx, valid, ties):
    """A pool of K, V and indexer keys (128 wide, as the pool holds
    them) with every row's context scattered through ragged tables, and
    this call's queries.  ``ties``: the indexer's keys and queries take
    few distinct values, so equal scores are everywhere."""
    P = 1 + S * M
    kp = (rng.standard_normal((P, bs, g, d))).astype(np.float32)
    vp = (rng.standard_normal((P, bs, g, d))).astype(np.float32)
    draw = ((lambda *sh: rng.integers(-1, 2, sh).astype(np.float32))
            if ties else
            (lambda *sh: rng.standard_normal(sh).astype(np.float32)))
    ip = np.zeros((P, bs, 128), np.float32)
    ip[..., :16] = draw(P, bs, 16)
    iq = np.zeros((S, n, hi, 128), np.float32)
    iq[..., :16] = draw(S, n, hi, 16)
    iw = draw(S, n, hi) if ties else rng.standard_normal(
        (S, n, hi)).astype(np.float32)
    bt = np.zeros((S, M), np.int32)
    order = rng.permutation(np.arange(1, P))
    for s in range(S):
        live = -(-(ctx[s] + valid[s]) // bs)
        bt[s, :live] = order[s * M:s * M + live]
    q = rng.standard_normal((S, n, nh, d)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (q, iq, iw, kp, vp, ip, bt)) + (
        jnp.asarray(ctx, jnp.int32), jnp.asarray(valid, jnp.int32))


def _selected_both(case, topk, d):
    from megatron_llm_tpu.ops.pallas import dsa_attention

    q, iq, iw, kp, vp, ip, bt, ctx, valid = case
    pool = {"k_pages": kp, "v_pages": vp, "index_pages": ip}
    index = (iq, None, iw, topk)
    scale = 1.0 / math.sqrt(d)
    dense = paged_kv.PagedKVCache(pool, bt, ctx, valid, kernel="xla")
    want = dense._attend_selected(q, pool, index, scale)
    got = dsa_attention.paged_selected_attention(
        q, iq, iw, kp, vp, ip, bt, ctx, valid, topk=topk,
        softmax_scale=scale)
    return np.asarray(got), np.asarray(want)


@pytest.fixture
def choices(monkeypatch):
    """Every call of the choice, as it was made: (scores, row_pos, n_live,
    mask)."""
    from megatron_llm_tpu.ops.pallas import dsa_attention

    real, calls = dsa_attention._select, []

    def recorded(scores, row_pos, n_live, **kw):
        mask = real(scores, row_pos, n_live, **kw)
        calls.append(tuple(np.asarray(a) for a in (scores, row_pos, n_live,
                                                   mask)))
        return mask

    monkeypatch.setattr(dsa_attention, "_select", recorded)
    return calls


def _assert_the_choice_is_select_masks(calls, topk):
    """On each step's live blocks the kernel's mask is ``dsa.select_mask``
    of the same scores, bit for bit (0.0 chosen, NEG_INF not), and no
    query may see a key past them."""
    from megatron_llm_tpu.ops import dsa

    assert calls
    for scores, row_pos, n_live, mask in calls:
        G, nblk, N, TB = scores.shape
        rows = N // n_live.shape[1]
        flat = scores.transpose(0, 2, 1, 3).reshape(G, N, nblk * TB)
        valid = np.arange(nblk * TB)[None, None] <= row_pos[..., None]
        want = np.asarray(dsa.select_mask(jnp.asarray(flat),
                                          jnp.asarray(valid), topk))
        got = mask.transpose(0, 2, 1, 3).reshape(G, N, nblk * TB)
        for g in range(G):
            for i, n in enumerate(n_live[g]):
                step = slice(i * rows, (i + 1) * rows)
                np.testing.assert_array_equal(
                    got[g, step, :n * TB],
                    np.where(want[g, step, :n * TB], np.float32(0.0),
                             np.float32(pa.NEG_INF)))
                assert not want[g, step, n * TB:].any()


@pytest.fixture(params=[None, 3], ids=["loop_default", "loop_3_blocks"])
def loop_blocks(request, monkeypatch):
    """The choice's loop over live blocks takes as many a step as fit
    ``_SELECT_LOOP_BYTES`` and what is left one by one; with 3 a step a
    table of 12 blocks runs both loops."""
    from megatron_llm_tpu.ops.pallas import dsa_attention

    if request.param:
        monkeypatch.setattr(dsa_attention, "_SELECT_LOOP_BYTES",
                            request.param * 8 * 16 * 4)


DECODE_BATCHES = {
    # contexts under the top-k, at it, past it, across several compute
    # blocks, and one row that is not decoding
    "five_rows": lambda topk: ([3, topk - 1, topk, 150, 60],
                               [1, 1, 1, 1, 0]),
    # 8 rows from one key to the whole table of 12 blocks of 16
    "one_key_to_the_table": lambda topk: (
        [0, 15, 16, 31, 47, 100, 175, 191], [1] * 8),
    # one live block of many, an idle slot beside it
    "one_live_block": lambda topk: ([100, 9], [0, 1]),
    # a context ending on a block's edge and one key past it (the new key
    # is the block's last, then the next block's first)
    "a_blocks_edge": lambda topk: ([62, 63, 64, 0], [1, 1, 1, 0]),
    # a slot with no token in the call between two live ones, twice: the
    # step that walks nothing puts its successor's first block on its way
    # (the shared walk's hand-over, under the mask since PR 57)
    "idle_between_live": lambda topk: ([150, 60, 100, 9, 175],
                                       [1, 0, 1, 0, 1]),
    # contexts that end mid-page, on a page's last key, on the next page's
    # first and on the table's last, the top-k larger than the first three
    "mid_page_and_edges": lambda topk: ([7, 15, 16, 40, 190], [1] * 5),
}


@pytest.mark.parametrize("batch", sorted(DECODE_BATCHES))
@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("topk", [8, 40])
def test_selected_decode_kernels_match_the_dense_path(topk, ties, batch,
                                                      two_page_blocks,
                                                      choices, loop_blocks):
    """The decode step over a table of 12 blocks of 16 keys; with equal
    scores everywhere (``ties``) the earlier position wins on both paths,
    and the choice is ``select_mask``'s on every block it counted."""
    rng = np.random.default_rng(3)
    ctx, valid = DECODE_BATCHES[batch](topk)
    case = _selected_case(rng, len(ctx), 1, 12, 16, 2, 4, 32, 4, ctx, valid,
                          ties)
    got, want = _selected_both(case, topk, 32)
    live = np.asarray(valid) > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=1e-5)
    assert np.abs(got[~live]).max(initial=0.0) == 0.0
    _assert_the_choice_is_select_masks(choices, topk)
    (_, _, n_live, _), = choices
    newest = [c + v for c, v in zip(ctx, valid) if v]
    assert n_live.tolist() == [[-(-max(newest) // 16)]]


@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("ctx,valid", [
    (0, 24), (5, 24), (70, 11), (150, 24),
    (0, 7),               # one live block of twelve, valid short of the chunk
    (8, 24), (9, 24),     # ends on a block's edge, and one key past it
    (40, 24),             # ends on an edge, straddling two blocks before it
    (168, 24),            # through the table's last key
    (180, 12),            # the padded tail rows point past the table
    (186, 5),             # and the last live row ends mid-page before it
])
def test_selected_prefill_kernels_match_the_dense_path(ctx, valid, ties,
                                                       two_page_blocks,
                                                       choices):
    """A chunk of 24 rows over a table of 12 blocks of 16 keys: at context
    0 (its rows straddle the top-k of 8), mid-page, short and padded, far
    past the top-k across compute blocks; every select step of the chunk
    counts through the chunk's last live block and no further."""
    rng = np.random.default_rng(4)
    case = _selected_case(rng, 1, 24, 12, 16, 2, 4, 32, 4, [ctx], [valid],
                          ties)
    got, want = _selected_both(case, 8, 32)
    np.testing.assert_allclose(got[0, :valid], want[0, :valid], atol=2e-5,
                               rtol=1e-5)
    _assert_the_choice_is_select_masks(choices, 8)
    (_, _, n_live, _), = choices
    assert n_live.tolist() == [[-(-(ctx + valid) // 16)]]


CHUNK_BATCHES = {
    # three slots, the middle one with no token in the call: its grid
    # steps walk nothing and hand the next slot's first block and mask
    # slice on
    "idle_between_live": ([70, 30, 150], [24, 0, 11]),
    # a slot whose later q-blocks hold no live row, before a live slot
    "dead_q_blocks_then_live": ([40, 9], [5, 24]),
    # padded tail rows past the table's end, then a slot from key 0 whose
    # rows straddle the top-k
    "tail_past_the_table": ([180, 0], [12, 24]),
    # idle slots first and last, the live one ending on a block's edge
    "idle_first_and_last": ([5, 104, 60], [0, 24, 0]),
}


@pytest.mark.parametrize("block_q", [8, 32])
@pytest.mark.parametrize("batch", sorted(CHUNK_BATCHES))
def test_selected_chunks_of_several_slots_and_q_blocks(batch, block_q,
                                                       monkeypatch,
                                                       two_page_blocks,
                                                       choices):
    """Chunks of 24 rows (padded to 32) a slot, in q-blocks of 8 rows and
    in one: every (slot, q-block) fetches its own slice of the mask with
    its pages, a q-block with no live row walks nothing, and the grid
    step before a live one has put that one's first block AND mask slice
    in flight, whether or not it walked anything itself."""
    from megatron_llm_tpu.ops.pallas import dsa_attention

    monkeypatch.setattr(dsa_attention, "_PREFILL_BLOCK_Q", block_q)
    rng = np.random.default_rng(8)
    ctx, valid = CHUNK_BATCHES[batch]
    case = _selected_case(rng, len(ctx), 24, 12, 16, 2, 4, 32, 4, ctx, valid,
                          False)
    got, want = _selected_both(case, 8, 32)
    for s, v in enumerate(valid):
        np.testing.assert_allclose(got[s, :v], want[s, :v], atol=2e-5,
                                   rtol=1e-5)
        assert np.abs(got[s, v:]).max(initial=0.0) == 0.0
    _assert_the_choice_is_select_masks(choices, 8)
    (_, _, n_live, _), = choices
    assert n_live.tolist() == [[-(-(c + v) // 16) if v else 0]
                               for c, v in zip(ctx, valid)]


def test_selected_attention_is_not_dense_attention(two_page_blocks):
    """Past the top-k the chosen keys are fewer than the context and the
    output differs from the plain walk's by whole tenths; under it they
    are the same."""
    rng = np.random.default_rng(5)
    case = _selected_case(rng, 2, 1, 12, 16, 2, 4, 32, 4, [5, 150], [1, 1],
                          False)
    got, _ = _selected_both(case, 8, 32)
    q, _, _, kp, vp, _, bt, ctx, valid = case
    dense = np.asarray(pa.paged_attention_decode(
        q[:, 0], kp, vp, bt, ctx, valid_lens=valid))
    np.testing.assert_allclose(got[0, 0], dense[0], atol=2e-5)
    assert np.abs(got[1, 0] - dense[1]).max() > 0.1


@pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf, 3e38])
@pytest.mark.parametrize("n", [1, 24])
def test_selection_never_reads_scores_nobody_wrote(n, poison, monkeypatch,
                                                   two_page_blocks):
    """The scores kernel writes a row's live blocks and no other: blocks
    past its last live page, an idle row's every block, and the lanes of
    its last block past the newest key hold whatever the chip's memory
    held (in interpret mode: zeros, which hides it).  Filled with NaN,
    infinities or huge numbers they change nothing: the choice counts
    only what a query may see."""
    from megatron_llm_tpu.ops.pallas import dsa_attention

    real = dsa_attention._index_scores

    def poisoned(iq, iw, ip, bt, ctx, valid, **kw):
        out = real(iq, iw, ip, bt, ctx, valid, **kw)
        nblk, tb = out.shape[1], out.shape[3]
        newest = jnp.where(valid > 0, ctx + valid - 1, -1)
        kpos = (jnp.arange(nblk)[:, None] * tb + jnp.arange(tb)[None, :])
        dead = kpos[None] > newest[:, None, None]            # [S, nblk, TB]
        return jnp.where(dead[:, :, None, :], poison, out)

    rng = np.random.default_rng(6)
    if n == 1:
        ctx, valid = [3, 150, 60, 40], [1, 1, 0, 1]
    else:
        ctx, valid = [70], [11]
    case = _selected_case(rng, len(ctx), n, 12, 16, 2, 4, 32, 4, ctx, valid,
                          False)
    clean, want = _selected_both(case, 8, 32)
    monkeypatch.setattr(dsa_attention, "_index_scores", poisoned)
    got, _ = _selected_both(case, 8, 32)
    for s, v in enumerate(valid):
        np.testing.assert_array_equal(got[s, :v], clean[s, :v])
        np.testing.assert_allclose(got[s, :v], want[s, :v], atol=2e-5,
                                   rtol=1e-5)


@pytest.mark.parametrize("poison", [0.0, np.nan, 3e38])
@pytest.mark.parametrize("n,ctx,valid", [
    (1, [3, 150, 60, 40], [1, 1, 0, 1]),
    (1, [20, 21], [0, 0]),
    (24, [70], [11]),
    (24, [9], [24]),
    (24, [70, 30, 150], [24, 0, 11]),
])
def test_attention_never_reads_a_mask_block_the_choice_did_not_write(
        n, ctx, valid, poison, monkeypatch, two_page_blocks):
    """The choice writes each step's blocks ``0 .. n_live - 1`` of the
    mask and no other; past them the mask holds whatever the buffer held
    (in interpret mode: zeros, which read as "chosen").  Filled with 0.0,
    NaN or a huge number between the choice and the walk they change
    nothing, rows past ``valid`` included: a chunk's walk stops at its
    last LIVE query's block, the decode step's at each row's own."""
    from megatron_llm_tpu.ops.pallas import dsa_attention

    real = dsa_attention._select

    def poisoned(scores, row_pos, n_live, **kw):
        mask = real(scores, row_pos, n_live, **kw)
        G, nblk, N, _ = mask.shape
        per_row = jnp.repeat(n_live, N // n_live.shape[1], axis=1)  # [G, N]
        dead = jnp.arange(nblk)[None, :, None] >= per_row[:, None, :]
        return jnp.where(dead[..., None], poison, mask)

    rng = np.random.default_rng(7)
    case = _selected_case(rng, len(ctx), n, 12, 16, 2, 4, 32, 4, ctx, valid,
                          False)
    clean, want = _selected_both(case, 8, 32)
    monkeypatch.setattr(dsa_attention, "_select", poisoned)
    got, _ = _selected_both(case, 8, 32)
    np.testing.assert_array_equal(got, clean)
    for s, v in enumerate(valid):
        np.testing.assert_allclose(got[s, :v], want[s, :v], atol=2e-5,
                                   rtol=1e-5)


# ---------------------------------------------------------------------------
# the window group's walk (a model with a layer type per layer): the same
# walk under its own names, over a table whose pages behind the window
# have gone back to the allocator
# ---------------------------------------------------------------------------

def _behind_the_window_gone(bt, first_query, window, bs, page):
    """The table with every page wholly behind the window of each slot's
    first query pointed at ``page``: what ``WindowGroup.advance_locked``
    leaves (it points them at the garbage block)."""
    first = np.maximum(np.asarray(first_query) - window + 1, 0) // bs
    gone = np.arange(bt.shape[1])[None, :] < first[:, None]
    return np.where(gone, page, bt).astype(np.int32)


@pytest.mark.parametrize("window", [5, 16, 19])
def test_window_walk_never_reads_a_page_given_back(window, two_page_blocks):
    """Decode rows at contexts of several windows, and a 16-token chunk:
    the pages behind the window of the launch's first query point at a
    page of NaN, at a page of huge values and at the garbage block, and
    the outputs are bit-equal and the oracle's.  The walk launches under
    the window group's names."""
    g, nh = 2, 4
    rng = np.random.default_rng(900 + window)
    lens = np.asarray([5 * BS - 1, 3 * BS, 7 * BS - 3, 2], np.int32)
    q, k_lin, v_lin, kp, vp, bt = _build_case(rng, S, LONG_M, BS, g, nh, D,
                                              lens)
    P = kp.shape[0]
    kp[P - 3], vp[P - 3] = 1e30, -1e30
    kp[P - 2], vp[P - 2] = np.nan, np.nan
    outs = [_decode(q, kp, vp,
                    _behind_the_window_gone(bt, lens, window, BS, page),
                    lens, sliding_window=window, name_suffix="_window")
            for page in (P - 2, P - 3, 0)]
    want = _oracle(q, k_lin, v_lin, lens, 1.0 / math.sqrt(D), window)
    np.testing.assert_allclose(outs[0], want, atol=2e-5, rtol=2e-5)
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[0], outs[2])
    # a chunk: the first query of each slot stands at its context
    ctx = np.asarray([0, 3, 24, 33], np.int32)
    q, k_lin, v_lin, kp, vp, bt = _build_prefill_case(
        rng, len(ctx), 8, BS, g, nh, D, ctx, C)
    P = kp.shape[0]
    kp[P - 2], vp[P - 2] = np.nan, np.nan
    outs = [np.asarray(pa.paged_attention_prefill(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(_behind_the_window_gone(bt, ctx, window, BS, page)),
        jnp.asarray(ctx), sliding_window=window, name_suffix="_window"))
        for page in (P - 2, 0)]
    want = _prefill_oracle(q, k_lin, v_lin, ctx, 1.0 / math.sqrt(D), window)
    np.testing.assert_allclose(outs[0], want, atol=2e-5, rtol=2e-5)
    assert np.array_equal(outs[0], outs[1])


def test_window_groups_kernel_names_reach_the_lowered_program():
    rng = np.random.default_rng(5)
    lens = np.asarray([20, 3], np.int32)
    q, _, _, kp, vp, bt = _build_case(rng, 2, 4, BS, 2, 4, D, lens)

    def lowered(suffix):
        return jax.jit(lambda q, kp, vp, bt, lens: pa.paged_attention_decode(
            q, kp, vp, bt, lens, sliding_window=8, name_suffix=suffix)
        ).lower(q, kp, vp, bt, lens).as_text(debug_info=True)

    assert "paged_attention_decode_window" in lowered("_window")
    assert "paged_attention_decode_window" not in lowered("")


def test_prefill_q_block_is_held_to_the_scratch_it_needs():
    """A chunk of 512 rows of 32 heads takes q-blocks of 64 rows (2,048
    (row, head) pairs: more is refused by the TPU's compiler, which
    tests/test_tpu_aot_compile.py holds); a chunk of 64 rows keeps its
    one block of 64 at 32 heads and at 16."""
    seen = []
    real = pa._walk_call

    def spy(*args, **kw):
        seen.append(kw["block_q"])
        return jnp.zeros(args[0].shape, args[0].dtype)

    pa._walk_call = spy
    try:
        for C_, nh_ in ((512, 32), (64, 32), (64, 16), (5, 32), (512, 8)):
            q = jnp.zeros((1, C_, nh_, 128), jnp.bfloat16)
            pools = jnp.zeros((4, 16, 4, 128), jnp.bfloat16)
            pa.paged_attention_prefill(q, pools, pools,
                                       jnp.zeros((1, 2), jnp.int32),
                                       jnp.zeros((1,), jnp.int32))
    finally:
        pa._walk_call = real
    assert seen == [64, 64, 64, 5, 128]


# ---------------------------------------------------------------------------
# a latent pool: one array of rows, every head attends the row and takes
# its first columns for values
# ---------------------------------------------------------------------------

def _latent_case(rng, lens, M, W=24, nh=3):
    """Rows [S, M*BS, W] scattered into a pool [P, BS, W] through ragged
    tables, garbage in every page nobody owns; a decode step's queries
    [S, 1, nh, W]."""
    S = len(lens)
    rows = rng.standard_normal((S, M * BS, W)).astype(np.float32)
    pages = (rng.standard_normal((1 + S * M, BS, W)) * 100.0).astype(
        np.float32)
    bt = np.zeros((S, M), np.int32)
    nxt = 1
    for s in range(S):
        for j in range(int(lens[s]) // BS + 1):
            bt[s, j] = nxt
            pages[nxt] = rows[s, j * BS:(j + 1) * BS]
            nxt += 1
    q = rng.standard_normal((S, 1, nh, W)).astype(np.float32)
    return q, rows, pages, bt


def _latent_oracle(q, rows, lens, scale, dv):
    """Per-(slot, row, head) softmax over the rows up to the query's own
    position, values the rows' first ``dv`` columns."""
    S, C, nh, _ = q.shape
    out = np.zeros((S, C, nh, dv), np.float32)
    for s in range(S):
        for j in range(C):
            keys = rows[s, :lens[s] + j + 1]
            for h in range(nh):
                sc = keys @ q[s, j, h] * scale
                p = np.exp(sc - sc.max())
                out[s, j, h] = (p / p.sum()) @ keys[:, :dv]
    return out


@pytest.mark.parametrize("blocks", ["one_block", "two_page_blocks"])
def test_latent_decode_matches_oracle_and_reference(blocks, monkeypatch):
    """The walk over a latent pool: ONE array fetched, scores over a
    row's whole width and values its first columns, over ragged contexts
    and (two pages a block) across block boundaries; a slot that is not
    decoding reads nothing."""
    if blocks == "two_page_blocks":
        monkeypatch.setattr(pa, "_BLOCK_TOKENS", 2 * BS)
    lens = np.asarray([0, 5, 17, 37], np.int32)
    q, rows, pages, bt = _latent_case(np.random.default_rng(0), lens, 6)
    want = _latent_oracle(q, rows, lens, 0.3, 16)[:, 0]
    args = [jnp.asarray(a) for a in (q[:, 0], pages, bt, lens)]
    got = pa.latent_attention_decode(*args, value_width=16,
                                     softmax_scale=0.3)
    assert got.shape == (4, 3, 16)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=1e-5)
    dense = pa.dense_latent_attention(jnp.asarray(q), *args[1:], None, 0.3,
                                      16)[:, 0]
    np.testing.assert_allclose(np.asarray(dense), want, atol=2e-5, rtol=1e-5)
    active = jnp.asarray([1, 0, 1, 0], jnp.int32)
    some = np.asarray(pa.latent_attention_decode(
        *args, valid_lens=active, value_width=16, softmax_scale=0.3))
    np.testing.assert_allclose(some[[0, 2]], want[[0, 2]], atol=2e-5,
                               rtol=1e-5)
    assert (some[[1, 3]] == 0).all()


def _absorbed(q_nope, q_rope, w, pages, bt, lens, valid, scale):
    """The expanded chunk's answer by the ABSORBED form in float32
    through the dense gather: the up-projection's key half folded into
    the queries, its value half applied to the latent context."""
    nope, W = q_nope.shape[-1], pages.shape[-1]
    f32 = [jnp.asarray(a, jnp.float32) for a in (q_nope, q_rope, w, pages)]
    q_lat = jnp.einsum("scnd,rnd->scnr", f32[0], f32[2][..., :nope])
    q = jnp.concatenate([q_lat, f32[1]], axis=-1)
    q = jnp.pad(q, [(0, 0)] * 3 + [(0, W - q.shape[-1])])
    ctx = pa.dense_latent_attention(
        q, f32[3], jnp.asarray(bt), jnp.asarray(lens),
        None if valid is None else jnp.asarray(valid), scale, w.shape[0])
    return np.asarray(jnp.einsum("scnr,rnd->scnd", ctx, f32[2][..., nope:]))


def _expanded_case(rng, ctx, live, C, M, r, dr, nh, nope, dv, W, bs=BS):
    """A latent pool ``[P, bs, W]`` of rows ``[latent r ; rotary key dr ;
    zeros]`` behind ragged tables (a slot's live pages are those of its
    ``ctx + live`` tokens; every other table entry points at a page of
    NaN, the garbage block 0 holds large values), queries of ``nope`` and
    ``dr`` a head and an up-projection ``[r, nh, nope + dv]``."""
    S = len(ctx)
    pages = np.zeros((2 + S * M, bs, W), np.float32)
    pages[0] = rng.standard_normal((bs, W)) * 100.0
    pages[1] = np.nan
    bt = np.ones((S, M), np.int32)
    nxt = 2
    for s in range(S):
        for j in range(-(-(int(ctx[s]) + int(live[s])) // bs)):
            bt[s, j] = nxt
            pages[nxt, :, :r + dr] = rng.standard_normal((bs, r + dr))
            nxt += 1
    # queries wide enough that a softmax over a thousand keys is peaked
    q_nope = 3.0 * rng.standard_normal((S, C, nh, nope)).astype(np.float32)
    q_rope = 3.0 * rng.standard_normal((S, C, nh, dr)).astype(np.float32)
    w = (rng.standard_normal((r, nh, nope + dv)) / np.sqrt(r)).astype(
        np.float32)
    return q_nope, q_rope, w, pages, bt


# the published head widths (kanana-2-30b-a3b: 32 heads of 128 + 64 over
# a latent of 512 in rows of 640, values of 128), pages of 16
PUBLISHED = dict(r=512, dr=64, nh=32, nope=128, dv=128, W=640, bs=16)


@pytest.mark.parametrize("case", [
    "chunk_512_over_blocks", "first_chunk", "ragged_past_the_table",
    "a_slot_without_tokens", "bf16"])
def test_the_expanded_latent_chunk_at_the_published_widths(case):
    """``mla_attention_prefill``: each block of latents expanded into
    per-head keys and values inside the kernel, against the absorbed form
    through the dense gather.  A chunk of 512 live rows over a context of
    three blocks of 1,024 whose last page is partial; the first chunk (no
    context); a ragged chunk whose padded rows point past the table's
    end; a slot with no token beside a live one (zeros out, its table of
    NaN pages never fetched); bf16 operands with fp32 accumulators."""
    dtype, valid = jnp.float32, None
    if case == "chunk_512_over_blocks":
        ctx, C, M = [2200], 512, 176          # 2,712 tokens: 169.5 pages
    elif case == "first_chunk":
        ctx, C, M = [0], 128, 12
    elif case == "ragged_past_the_table":
        ctx, C, M, valid = [600], 128, 40, [40]     # the table ends at 640
    elif case == "a_slot_without_tokens":
        ctx, C, M, valid = [70, 530], 64, 40, [0, 64]
    else:
        ctx, C, M, dtype = [520], 128, 48, jnp.bfloat16
    live = valid if valid is not None else [C] * len(ctx)
    ctx, live = np.asarray(ctx, np.int32), np.asarray(live, np.int32)
    q_nope, q_rope, w, pages, bt = _expanded_case(
        np.random.default_rng(len(case)), ctx, live, C, M, **PUBLISHED)
    cast = [jnp.asarray(a, dtype) for a in (q_nope, q_rope, w, pages)]
    got = pa.latent_attention_prefill(
        *cast, jnp.asarray(bt), jnp.asarray(ctx),
        valid_lens=None if valid is None else jnp.asarray(live),
        softmax_scale=192 ** -0.5)
    assert got.shape == (len(ctx), C, 32, 128) and got.dtype == dtype
    got = np.asarray(got, np.float32)
    # the reference reads the whole table: the NaN pages become the
    # garbage block, which the mask drops
    want = _absorbed(*cast, np.where(bt == 1, 0, bt), ctx, live, 192 ** -0.5)
    assert np.abs(want).max() > 0.5
    tol = dict(atol=2e-5, rtol=1e-5) if dtype == jnp.float32 else dict(
        atol=0.03, rtol=0)
    for s in range(len(ctx)):
        np.testing.assert_allclose(got[s, :live[s]], want[s, :live[s]], **tol)
        assert np.isfinite(got[s]).all()
        if live[s] == 0:
            assert (got[s] == 0).all()


@pytest.mark.parametrize("heads", ["all_heads", "a_head_a_step"])
def test_latent_prefill_chunk_matches_oracle(heads, monkeypatch):
    """A chunk of 8 rows a slot over histories of 0 to 29 tokens at a
    tiny width, blocks of two pages: causal within the chunk on top of
    the paged history, across block boundaries; every head under one
    fetch and a head a grid step."""
    monkeypatch.setattr(pa, "_CHUNK_BLOCK_TOKENS", 2 * BS)
    if heads == "a_head_a_step":
        monkeypatch.setattr(pa, "_CHUNK_VMEM_BYTES", 0)
    ctx = np.asarray([0, 3, 16, 29], np.int32)
    dims = dict(r=16, dr=4, nh=3, nope=8, dv=12, W=24)
    q_nope, q_rope, w, pages, bt = _expanded_case(
        np.random.default_rng(1), ctx, np.full(4, 8), 8, 6, **dims)
    seen = []
    real = pa._chunk_heads
    monkeypatch.setattr(pa, "_chunk_heads",
                        lambda *a: seen.append(real(*a)) or seen[-1])
    got = pa.latent_attention_prefill(
        *(jnp.asarray(a) for a in (q_nope, q_rope, w, pages, bt, ctx)),
        softmax_scale=0.25)
    assert seen == [1 if heads == "a_head_a_step" else 3]
    assert got.shape == (4, 8, 3, 12)
    want = _absorbed(q_nope, q_rope, w, pages, np.where(bt == 1, 0, bt), ctx,
                     None, 0.25)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=1e-5)


def test_latent_chunk_in_bf16_rounds_the_expansion_to_the_operands_dtype():
    """bf16 queries over a bf16 pool: every product takes bf16 operands
    and accumulates in fp32, the expanded keys and values and the
    probabilities rounded to bf16 between them, and the answer is the
    same mathematics in float32 with those three roundings put in."""
    ctx = np.asarray([21], np.int32)
    dims = dict(r=16, dr=4, nh=3, nope=8, dv=12, W=24)
    q_nope, q_rope, w, pages, bt = _expanded_case(
        np.random.default_rng(2), ctx, np.full(1, 8), 8, 5, **dims)
    b16 = [jnp.asarray(a, jnp.bfloat16) for a in (q_nope, q_rope, w, pages)]
    got = pa.latent_attention_prefill(
        *b16, jnp.asarray(bt), jnp.asarray(ctx), softmax_scale=0.25)
    assert got.dtype == jnp.bfloat16
    qn, qr, w32, p32 = (np.asarray(a, np.float32) for a in b16)

    def rounded(a):
        return np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32)

    rows = p32[bt[0]].reshape(-1, 24)[:29]
    kv = rounded(np.einsum("tr,rnd->tnd", rows[:, :16], w32))
    want = np.zeros((8, 3, 12), np.float32)
    for j in range(8):
        for h in range(3):
            sc = (kv[:22 + j, h, :8] @ qn[0, j, h]
                  + rows[:22 + j, 16:20] @ qr[0, j, h]) * 0.25
            p = np.exp(sc - sc.max())
            want[j, h] = rounded(p) @ kv[:22 + j, h, 8:] / p.sum()
    np.testing.assert_allclose(np.asarray(got[0], np.float32), want,
                               atol=0.02)


# ---------------------------------------------------------------------------
# the first block of a grid step is put on its way by the step before it
# (which buffer half holds it is carried across steps): what that makes new
# ---------------------------------------------------------------------------

def _dead_from(bt, tokens, page, skipped=None):
    """The table with every entry past a slot's live tokens, and the whole
    row of a slot that takes no part, pointed at ``page``."""
    live = -(-np.asarray(tokens) // BS)
    if skipped is not None:
        live = np.where(skipped, 0, live)
    dead = np.arange(bt.shape[1])[None, :] >= live[:, None]
    return np.where(dead, page, bt).astype(np.int32)


def _handed_decode(rng, M, lens, vlen, window, int8=False):
    lens, vlen = np.asarray(lens, np.int32), np.asarray(vlen, np.int32)
    q, k_lin, v_lin, kp, vp, bt = _build_case(rng, len(lens), M, BS, 2, 4, D,
                                              lens)
    P, kw = kp.shape[0], {}
    want = _oracle(q, k_lin, v_lin, lens, 1.0 / math.sqrt(D), window)
    if int8:
        kp, ks = absmax_quantize_int8(jnp.asarray(kp), axis=-1)
        vp, vs = absmax_quantize_int8(jnp.asarray(vp), axis=-1)
        ks, vs = np.array(ks), np.array(vs)
        want = np.asarray(_dense(
            jnp.asarray(q), kp, vp, jnp.asarray(bt), jnp.asarray(lens),
            jnp.asarray(ks), jnp.asarray(vs), 1.0 / math.sqrt(D), window))
        ks[P - 2], vs[P - 2], ks[P - 3], vs[P - 3] = np.nan, np.nan, 1e30, 1e30
        kw = dict(k_scales=jnp.asarray(ks), v_scales=jnp.asarray(vs))
    else:
        kp[P - 2], vp[P - 2], kp[P - 3], vp[P - 3] = np.nan, np.nan, 1e30, -1e30
    outs = []
    for page in (P - 2, P - 3):
        table = _dead_from(bt, lens + 1, page, vlen == 0)
        if window is not None:
            table = _behind_the_window_gone(table, lens, window, BS, page)
        outs.append(_decode(q, kp, vp, table, lens, sliding_window=window,
                            valid_lens=jnp.asarray(vlen), **kw))
    return outs, want, vlen


def _handed_chunk(rng, M, ctx, vlen, window, block_q):
    ctx, vlen = np.asarray(ctx, np.int32), np.asarray(vlen, np.int32)
    q, k_lin, v_lin, kp, vp, bt = _build_prefill_case(
        rng, len(ctx), M, BS, 2, 4, D, ctx, C)
    P = kp.shape[0]
    kp[P - 2], vp[P - 2], kp[P - 3], vp[P - 3] = np.nan, np.nan, 1e30, -1e30
    outs = []
    for page in (P - 2, P - 3):
        table = _dead_from(bt, ctx + C, page, vlen == 0)
        if window is not None:
            table = _behind_the_window_gone(table, ctx, window, BS, page)
        outs.append(np.asarray(pa.paged_attention_prefill(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(table), jnp.asarray(ctx), sliding_window=window,
            valid_lens=jnp.asarray(vlen), block_q=block_q)))
    return outs, _prefill_oracle(q, k_lin, v_lin, ctx, 1.0 / math.sqrt(D),
                                 window), vlen


def _handed_latent(rng, M, lens, vlen):
    lens, vlen = np.asarray(lens, np.int32), np.asarray(vlen, np.int32)
    q, rows, pages, bt = _latent_case(rng, lens, M)
    P = pages.shape[0]
    pages[P - 2], pages[P - 3] = np.nan, 1e30
    outs = [np.asarray(pa.latent_attention_decode(
        jnp.asarray(q[:, 0]), jnp.asarray(pages),
        jnp.asarray(_dead_from(bt, lens + 1, page, vlen == 0)),
        jnp.asarray(lens), valid_lens=jnp.asarray(vlen), value_width=16,
        softmax_scale=0.3)) for page in (P - 2, P - 3)]
    return outs, _latent_oracle(q, rows, lens, 0.3, 16)[:, 0], vlen


# blocks are two pages of 8 tokens: contexts of 40, 17, 33, 9 walk 3, 2, 3
# and 1 blocks, the last of each partial or exactly full
HANDED = {
    "a_live_slot_between_two_skipped":
        lambda r: _handed_decode(r, LONG_M, [40, 17, 33, 9], [0, 1, 0, 0],
                                 None),
    "a_skipped_slot_between_two_live":
        lambda r: _handed_decode(r, LONG_M, [40, 17, 33, 9], [1, 0, 1, 1],
                                 None),
    "every_walk_one_partial_block":
        lambda r: _handed_decode(r, LONG_M, [3, 0, 7, 5], [1, 1, 1, 1],
                                 None),
    "walks_of_one_whole_block_and_of_an_odd_count":
        lambda r: _handed_decode(r, LONG_M, [15, 47, 15, 31], [1, 1, 1, 1],
                                 None),
    "the_last_slot_after_skipped_ones":
        lambda r: _handed_decode(r, LONG_M, [9, 17, 33, 61], [1, 0, 0, 1],
                                 None),
    "the_last_slot_alone":
        lambda r: _handed_decode(r, LONG_M, [9, 17, 33, 61], [0, 0, 0, 1],
                                 None),
    "the_last_slot_skipped":
        lambda r: _handed_decode(r, LONG_M, [9, 17, 33, 61], [1, 1, 1, 0],
                                 None),
    # slot 0 walks all 8 pages of its table; slot 1's window of 20 opens
    # at key 26, inside page 3 (a block is two pages from there on)
    "a_window_walk_mid_block_after_a_whole_table":
        lambda r: _handed_decode(r, 8, [63, 45, 63, 38], [1, 1, 1, 1], 20),
    "a_whole_table_then_a_short_walk":
        lambda r: _handed_decode(r, 8, [63, 2, 63, 63], [1, 1, 0, 1], None),
    "a_chunk_of_two_q_blocks_in_two_slots":
        lambda r: _handed_chunk(r, MP, [3, 17], [C, C], None, 8),
    "a_chunk_of_two_q_blocks_under_a_window":
        lambda r: _handed_chunk(r, 8, [33, 0, 24], [C, C, 7], 5, 8),
    "a_chunk_of_four_q_blocks_one_slot_skipped":
        lambda r: _handed_chunk(r, MP, [0, 8, 17, 3], [C, 0, C, 9], None, 4),
    "the_latent_pool":
        lambda r: _handed_latent(r, 6, [37, 5, 0, 17], [1, 0, 1, 1]),
    "the_latent_pool_last_slot_skipped":
        lambda r: _handed_latent(r, 6, [16, 37, 15, 5], [1, 1, 1, 0]),
    "the_int8_pools":
        lambda r: _handed_decode(r, LONG_M, [40, 17, 33, 9], [1, 0, 1, 1],
                                 None, int8=True),
    "the_int8_pools_under_a_window":
        lambda r: _handed_decode(r, LONG_M, [40, 17, 33, 61], [1, 1, 0, 1],
                                 12, int8=True),
}


@pytest.mark.parametrize("case", sorted(HANDED))
def test_the_first_block_is_handed_across_grid_steps(case, two_page_blocks):
    """Each grid step's first block is started by the step before it (the
    grid's first step starts its own, a step that walks nothing starts its
    successor's, the last step starts none).  Live rows are the oracle's,
    skipped ones zeros, and tables whose dead entries (past a slot's live
    tokens, behind its window, a skipped slot's whole row) point at a page
    of NaN and at a page of 1e30 give bit-equal outputs: no step fetches
    for its successor anything the successor would not fetch itself."""
    outs, want, vlen = HANDED[case](np.random.default_rng(len(case)))
    assert np.isfinite(outs[0]).all()
    assert np.array_equal(outs[0], outs[1])
    assert not outs[0][vlen == 0].any()
    np.testing.assert_allclose(outs[0][vlen > 0], want[vlen > 0], atol=2e-5,
                               rtol=2e-5)


# ---------------------------------------------------------------------------
# the walk in the pool's dtype (PR 62): bf16 queries over a bf16 pool
# multiply as they lie under an fp32 accumulator, a step's and a chunk's;
# the probabilities meet the values as TWO bf16 terms in a chunk's block
# of many rows (``_TWO_TERM_ROWS``, set to 16 for these small shapes) at
# heads of 128 or less, and as one (the bits the walk always gave on the
# chip, where an fp32 product is one pass of bf16 operands) in a decode
# step's, at heads of 256 and where the rows are not whole bf16 tiles
# ---------------------------------------------------------------------------

def _rounded(x):
    """``x`` rounded to bf16, as float32."""
    return np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))


# rows a slot (1: the decode step), query heads, kv groups, a head's width,
# a window, the share of keys a mask chooses, a chunk's q-block
NATIVE = {
    "decode_one_kv_group": dict(n=1, nh=4, g=1),
    "decode_four_kv_groups": dict(n=1, nh=8, g=4),
    "decode_eight_kv_groups_under_a_window": dict(n=1, nh=8, g=8, window=37),
    "decode_heads_of_256": dict(n=1, nh=4, g=2, d=256),
    "decode_heads_of_64_two_a_lane_row": dict(n=1, nh=8, g=4, d=64),
    "decode_under_a_mask_of_chosen_keys": dict(n=1, nh=8, g=4, chosen=0.4),
    "chunk_of_three_q_blocks": dict(n=24, nh=8, g=4, block_q=8),
    "chunk_one_kv_group_under_a_window": dict(n=16, nh=2, g=1, window=21,
                                              block_q=8),
    "chunk_of_eight_kv_groups": dict(n=16, nh=16, g=8, block_q=8),
    "chunk_whose_rows_are_half_a_tile": dict(n=16, nh=8, g=8, block_q=8),
    "chunk_heads_of_256": dict(n=16, nh=4, g=2, d=256, block_q=8),
    "chunk_heads_of_64_two_a_lane_row": dict(n=16, nh=8, g=4, d=64,
                                             block_q=8),
    "chunk_under_a_mask_of_chosen_keys": dict(n=16, nh=8, g=4, chosen=0.4,
                                              block_q=8),
    "verify_step_of_five_rows": dict(n=5, nh=8, g=8, valid=[5, 0, 3]),
}


def _native_walk(name, monkeypatch):
    """-> (the walk's output over a bf16 pool, float32 [S, n, nh, d]; the
    same attention in float64 over the same bf16 numbers; the live rows
    [S, n]).  Three slots of 70, 9 and 41 cached tokens in pages of 8,
    blocks of four pages."""
    case = dict(dict(d=128, window=None, chosen=None, block_q=None,
                     valid=None), **NATIVE[name])
    n, nh, g, d, window = (case[k] for k in ("n", "nh", "g", "d", "window"))
    monkeypatch.setattr(pa, "_BLOCK_TOKENS", 4 * BS)
    monkeypatch.setattr(pa, "_TWO_TERM_ROWS", 16)
    rng = np.random.default_rng(sum(map(ord, name)))
    ctx = np.asarray([70, 9, 41], np.int32)
    valid = np.asarray(case["valid"] or [n] * 3, np.int32)
    Mt = 12
    q, k_lin, v_lin, kp, vp, bt = _build_prefill_case(
        rng, 3, Mt, BS, g, nh, d, ctx, n)
    q, k_lin, v_lin, kp, vp = (_rounded(a) for a in (q, k_lin, v_lin, kp, vp))
    L = Mt * BS
    kpos, qpos = np.arange(L), ctx[:, None] + np.arange(n)[None, :]
    sees = kpos[None, None, :] <= qpos[:, :, None]              # [S, n, L]
    if window is not None:
        sees &= kpos[None, None, :] > qpos[:, :, None] - window
    b16 = [jnp.asarray(a, jnp.bfloat16) for a in (q, kp, vp)]
    tables = (jnp.asarray(bt), jnp.asarray(ctx), jnp.asarray(valid))
    scale = 1.0 / math.sqrt(d)
    if case["chosen"] is not None:
        # a query's own key is always chosen: no live row attends nothing
        chosen = rng.random((3, n, L)) < case["chosen"]
        chosen |= kpos[None, None, :] == qpos[:, :, None]
        sees &= chosen
        per = pa._pages_per_block(BS, g, d, jnp.bfloat16, Mt) * BS
        mask = np.where(chosen, 0.0, pa.NEG_INF).astype(np.float32)
        mask = mask.reshape(3, n, L // per, per).transpose(0, 2, 1, 3)
        if n == 1:      # lane c of a block is key c // g, group c % g
            mask = np.repeat(mask[:, :, 0], g, axis=-1)
        got = pa._walk_call(
            b16[0], b16[1], b16[2], *tables, None, None, scale=scale,
            window=None, block_q=case["block_q"] or n,
            name="paged_attention_prefill_masked", mask=jnp.asarray(mask))
    elif d == 64:
        # two heads a lane row, as ``PagedKVCache.attend`` hands them on
        pools = [a.reshape(a.shape[:2] + (g // 2, 128)) for a in b16[1:]]
        wide = paged_kv._in_own_part(b16[0], g // 2, 2)
        entry = (pa.paged_attention_decode if n == 1 else
                 functools.partial(pa.paged_attention_prefill,
                                   block_q=case["block_q"]))
        got = entry(wide[:, 0] if n == 1 else wide, *pools, *tables[:2],
                    valid_lens=tables[2], softmax_scale=scale)
        got = paged_kv._own_part(got[:, None] if n == 1 else got, g // 2, 2)
    elif n == 1:
        got = pa.paged_attention_decode(
            b16[0][:, 0], *b16[1:], *tables[:2], valid_lens=tables[2],
            sliding_window=window)[:, None]
    else:
        got = pa.paged_attention_prefill(
            *b16, *tables[:2], valid_lens=tables[2], sliding_window=window,
            block_q=case["block_q"])
    assert got.dtype == jnp.bfloat16
    qg = q.astype(np.float64).reshape(3, n, g, nh // g, d)
    sc = np.einsum("sjgpd,slgd->sjgpl", qg, k_lin.astype(np.float64)) * scale
    sc = np.where(sees[:, :, None, None, :], sc, -np.inf)
    pr = np.exp(sc - sc.max(axis=-1, keepdims=True))
    want = np.einsum("sjgpl,slgd->sjgpd", pr / pr.sum(axis=-1, keepdims=True),
                     v_lin.astype(np.float64)).reshape(3, n, nh, d)
    return (np.asarray(got.astype(jnp.float32)), want,
            np.arange(n)[None, :] < valid[:, None])


def _two_terms(name):
    """Whether ``pa._value_terms`` gives the case two terms: a q-block of
    whole bf16 tiles of rows a kv group, 16 or more of them here, at
    heads of 128 or less (64-wide heads lie two a row of 128 lanes)."""
    case = dict(dict(d=128, block_q=1), **NATIVE[name])
    rows = case["block_q"] * case["nh"] // (case["g"] // (128 // case["d"]
                                                         or 1))
    return case["d"] <= 128 and rows >= 16 and rows % 16 == 0


@pytest.mark.parametrize("name", sorted(NATIVE))
def test_the_walk_in_bf16_keeps_sixteen_bits_of_the_probabilities(
        name, monkeypatch):
    """Against the same attention in float64 over the same bf16 numbers:
    the scores are exact products under an fp32 accumulator, and with the
    probabilities in two bf16 terms what is left is the output's own
    rounding, so all but a few elements in a hundred ARE the reference
    rounded to bf16, to the bit.  One bf16 term (a decode step, heads of
    256, the verify step's five rows a group, half a tile of rows) leaves
    a third of them a unit off, inside the loose tolerance a bf16 kernel
    is usually held to: this pins which form a shape takes."""
    pa._walk_kernel.clear_cache()
    try:
        got, want, live = _native_walk(name, monkeypatch)
    finally:
        pa._walk_kernel.clear_cache()
    np.testing.assert_allclose(got[live], want[live], atol=8e-3, rtol=8e-3)
    equal = np.mean(got[live] == _rounded(want[live]))
    assert equal > 0.98 if _two_terms(name) else 0.5 < equal < 0.9, equal
    assert not got[~live.any(axis=1)].any()     # a slot with no token


@pytest.mark.parametrize("name", ["chunk_of_eight_kv_groups",
                                  "chunk_of_three_q_blocks"])
def test_one_bf16_term_of_the_probabilities_is_told_from_two(name,
                                                             monkeypatch):
    """The same chunks with ``p`` rounded to bf16 ONCE: inside the loose
    tolerance, and far from the share of bit-equal elements the two-term
    walk is held to above."""
    assert _two_terms(name)
    monkeypatch.setattr(pa, "_value_terms", lambda *shape: 1)
    pa._walk_kernel.clear_cache()
    try:
        got, want, live = _native_walk(name, monkeypatch)
    finally:
        pa._walk_kernel.clear_cache()
    np.testing.assert_allclose(got[live], want[live], atol=8e-3, rtol=8e-3)
    assert np.mean(got[live] == _rounded(want[live])) < 0.9


def test_the_plan_counts_no_native_walk_under_an_int8_pool(model_and_params):
    """The plan's own count, as the engine's launches ask it (the engine
    hands it its ``int8_kv_cache``): three live rows of four, two layers,
    and an int8 pool's walks multiply in fp32."""
    from megatron_llm_tpu.serving.loop_profiler import LoopProfiler

    d = LoopProfiler().begin()
    paged_kv.plan(model_and_params[0].cfg, BS, 4, 8, 16, "pallas", "pallas",
                  int8_pool=True).account(
        d, np.asarray([5, 0, 9, 30]), np.asarray([1, 0, 1, 1]), 1, 3)
    assert (d.walks, d.walks_native) == (6, 0)


@pytest.mark.parametrize("case", ["kernels_on", "kernels_off", "verify_step"])
def test_the_engine_counts_the_walks_that_multiplied_in_the_pools_dtype(
        case, model_and_params):
    """``walks`` is live rows x layers of every launch on either path;
    ``walks_native`` all of them where the kernel walks a pool of the
    queries' dtype (a chunk, a decode step, the verify step), none on the
    dense path."""
    from megatron_llm_tpu.serving import (EngineConfig, InferenceEngine,
                                          SamplingParams)

    model, params = model_and_params
    kernel = "off" if case == "kernels_off" else "on"
    eng = InferenceEngine(model, params, EngineConfig(
        num_slots=2, block_size=BS, max_model_len=64, prefill_chunk=16,
        paged_kernel=kernel, prefill_kernel=kernel,
        speculative=case == "verify_step", draft_k=2))
    reqs = [eng.submit([(7 * i + j) % 60 + 1 for j in range(n)],
                       SamplingParams(max_new_tokens=3, temperature=0.0))
            for i, n in enumerate((21, 9))]
    while any(r.finish_reason is None for r in reqs):
        assert eng.step()
    stats, records = eng.stats(), eng.loop_profiler.records()
    # a chunk is one live row, a step one a decoding request, two layers
    assert stats["walks"] == sum(r.walks for r in records) >= 2 * (3 + 2)
    assert {r.kind for r in records} == {
        "prefill", "verify" if case == "verify_step" else "decode"}
    assert all(r.walks > 0 for r in records)
    assert stats["walks_native"] == (
        stats["walks"] if case in ("kernels_on", "verify_step") else 0)


def _kernel_equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _kernel_equations(sub)


@pytest.mark.parametrize("name", ["decode", "decode_window", "chunk",
                                  "chunk_window", "decode_int8",
                                  "chunk_int8"])
def test_the_walk_multiplies_in_the_pools_dtype(name):
    """The kernel's jaxpr over a bf16 pool (blocks of 512 keys of 4 kv
    heads of 128): every ``dot_general`` takes bf16 operands and gives
    fp32, and no block of K or V (whole ``[2048, 128]``, by keys ``[512,
    4, 128]`` or one group's ``[512, 128]``) is converted to fp32.  Over
    an int8 pool what was there before: fp32 operands, each block
    widened once."""
    fn, args = _walk_shapes(name)
    eqns = list(_kernel_equations(jax.make_jaxpr(fn)(*args).jaxpr))
    dots = [tuple(str(v.aval.dtype) for v in e.invars)
            + (str(e.outvars[0].aval.dtype),)
            for e in eqns if e.primitive.name == "dot_general"]
    widened = [e.invars[0].aval for e in eqns
               if e.primitive.name == "convert_element_type"
               and e.params["new_dtype"] == jnp.float32
               and e.invars[0].aval.shape[-1:] == (128,)
               and e.invars[0].aval.size >= 512 * 128]
    # a decode step's block is laid out four times, a chunk's twice, each
    # with its two products (a chunk's a kv group)
    assert len(dots) >= 4
    if "int8" in name:
        assert set(dots) == {("float32",) * 3}
        # (and a chunk's queries, which fp32 keys must meet in fp32)
        assert {str(a.dtype) for a in widened
                if a.shape[-2:] == (8, 128)} == {"int8"}
        return
    assert set(dots) == {("bfloat16", "bfloat16", "float32")}
    assert widened == []
    # the rows that meet the values (``p x v`` contracts the keys, its
    # left operand's second dimension with its right's first) against the
    # rows that met the keys: twice them for a chunk, whose probabilities
    # go stacked in two terms, and the same for a decode step's one
    rows = {kind: {e.invars[0].aval.shape[0] for e in eqns
                   if e.primitive.name == "dot_general"
                   and e.params["dimension_numbers"][0] == contracts}
            for kind, contracts in (("q x k", ((1,), (1,))),
                                    ("p x v", ((1,), (0,))))}
    (met_keys,), (met_values,) = rows["q x k"], rows["p x v"]
    assert met_values == (2 if "chunk" in name else 1) * met_keys


# ---------------------------------------------------------------------------
# the walk WITHOUT a mask traces the kernel it traced before (PR 57: the
# families' program fingerprints run on the CPU's dense path and never saw
# a kernel; a mask, like a window or a latent pool, is a trace-time fact
# and must leave every other caller's kernel alone)
# ---------------------------------------------------------------------------

def _walk_shapes(name):
    """-> (entry, abstract arguments) of one caller of the shared walk."""
    def sd(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    i32, P, bs, M = jnp.int32, 129, 16, 40
    g = 1 if "latent" in name else 8 if "int8" in name else 4
    pool = sd((P, bs, g, 128), jnp.int8 if "int8" in name else jnp.bfloat16)
    rows = 2 if "chunk" in name else 4
    q = sd((rows, 64, 32, 128) if "chunk" in name else (rows, 32, 128))
    tables = (sd((rows, M), i32), sd((rows,), i32))
    window = 64 if "window" in name else None
    if "latent" in name:
        return (lambda q, p, t, l, v: pa.latent_attention_decode(
            q, p, t, l, valid_lens=v, value_width=512, softmax_scale=0.07),
            (sd((rows, 32, 640)), sd((P, bs, 640))) + tables
            + (sd((rows,), i32),))
    entry = (pa.paged_attention_prefill if "chunk" in name
             else pa.paged_attention_decode)
    if "int8" in name:
        scales = sd((P, bs, g), jnp.float32)
        return (lambda q, k, v, t, l, ks, vs: entry(
            q, k, v, t, l, k_scales=ks, v_scales=vs),
            (q, pool, pool) + tables + (scales, scales))
    return (lambda q, k, v, t, l, vl: entry(
        q, k, v, t, l, valid_lens=vl, sliding_window=window,
        name_suffix="_window" if window else ""),
        (q, pool, pool) + tables + (sd((rows,), i32),))


# what these print since PR 48 (the walk's fetches); PR 57 added the mask
# and left them as they were.  A PR that MEANS to change the unmasked walk
# records the new ones and says so.  PR 62 meant to: over a bf16 pool the
# walk multiplies in the pool's dtype (a chunk's queries and keys are no
# longer widened, no walk's values are; a chunk's probabilities go in two
# bf16 terms and its kernel asks ``_TWO_TERMS_VMEM_LIMIT``), so the four
# bf16 walks are re-recorded; a chunk's rows are cut by kv group once a
# grid step and no longer once a block, which moves equations of the int8
# chunk too (its arithmetic is what it was: fp32 operands, the test
# above); the int8 step and the latent walk are the ones PR 48 recorded,
# letter for letter
WALKS_TRACED = {
    "decode": "e1cc87579f76917b",
    "decode_window": "903be56ce90465c7",
    "decode_int8": "fbb9b1183fe730bb",
    "decode_latent": "70b89cd7c5203819",
    "chunk": "fd73f8961c985883",
    "chunk_window": "dc368a001ca5009f",
    "chunk_int8": "96c238ac3a36f65f",
}


@pytest.mark.parametrize("name", sorted(WALKS_TRACED))
def test_the_walk_without_a_mask_traces_the_kernel_it_traced_before(name):
    import hashlib
    import re

    fn, args = _walk_shapes(name)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))
    assert "pallas_call" in text
    got = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert got == WALKS_TRACED[name], (
        f"the unmasked walk's kernel ({name}) is not the recorded one, "
        f"{got}: if that was meant, record it in WALKS_TRACED")
