"""The Mamba-2 mixer (``models/mamba.py``): the chunked scan against the
plain recurrence, its two forms against each other, its padding.

CPU, float32, small sizes: 8 heads of 16 over a state of 12 (and two
groups of heads), sequences that do and do not divide the block, a state
that does not start at zero.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.models import mamba
from megatron_llm_tpu.models.granite import granite_config
from megatron_llm_tpu.ops import paged_kv

TOL = 2e-5


def _recurrence(x, delta, A, B, C, state):
    """Step 4 one token at a time: x [n, nh, dh], delta [n, nh], A [nh],
    B, C [n, g, ds], state [nh, dh, ds] -> (y [n, nh, dh], last state)."""
    n, nh, _ = x.shape
    rep = nh // B.shape[1]
    S, ys = np.array(state, np.float64), []
    for t in range(n):
        Bt, Ct = np.repeat(B[t], rep, 0), np.repeat(C[t], rep, 0)
        S = (np.exp(delta[t] * A)[:, None, None] * S
             + (delta[t][:, None] * x[t])[:, :, None] * Bt[:, None, :])
        ys.append(np.einsum("hdn,hn->hd", S, Ct))
    return np.stack(ys), S


def _inputs(n, nh=8, dh=16, ds=12, g=1, b=2, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (b, n, nh, dh))
    delta = jax.nn.softplus(jax.random.normal(k[1], (b, n, nh)) - 2.0)
    A = -jnp.exp(jax.random.uniform(k[2], (nh,), minval=0.0, maxval=2.5))
    B = jax.random.normal(k[3], (b, n, g, ds))
    C = jax.random.normal(k[4], (b, n, g, ds))
    state = jax.random.normal(k[5], (b, nh, dh, ds))
    return x, delta, A, B, C, state


@pytest.mark.parametrize("n,block,g", [
    (64, 16, 1), (70, 16, 1), (37, 64, 1), (33, 32, 1), (1, 16, 1),
    (96, 32, 2), (50, 16, 4)])
def test_the_chunked_scan_is_the_plain_recurrence(n, block, g):
    """Blocks that do and do not divide the sequence, a block longer than
    the sequence, one token, groups of heads; a NON-ZERO initial state."""
    x, delta, A, B, C, state = _inputs(n, g=g)
    y, last = mamba.chunked_scan(x, delta, A, B, C, state, block)
    for r in range(x.shape[0]):
        want_y, want_S = _recurrence(*(np.asarray(a[r]) for a in
                                       (x, delta)), np.asarray(A),
                                     np.asarray(B[r]), np.asarray(C[r]),
                                     np.asarray(state[r]))
        assert np.abs(want_y).max() > 1.0
        np.testing.assert_allclose(np.asarray(y[r]), want_y, atol=TOL,
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(last[r]), want_S, atol=TOL,
                                   rtol=1e-5)


def test_a_token_with_delta_zero_changes_nothing():
    """What padding rests on: tokens whose delta is 0 leave the state
    where the last real token left it and add nothing to later ones."""
    x, delta, A, B, C, state = _inputs(48)
    live = jnp.arange(48) < 29
    delta = jnp.where(live[None, :, None], delta, 0.0)
    y, last = mamba.chunked_scan(x, delta, A, B, C, state, 16)
    y29, last29 = mamba.chunked_scan(x[:, :29], delta[:, :29], A, B[:, :29],
                                     C[:, :29], state, 16)
    np.testing.assert_allclose(np.asarray(last), np.asarray(last29),
                               atol=TOL, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y[:, :29]), np.asarray(y29),
                               atol=TOL, rtol=1e-5)


@pytest.fixture(scope="module")
def layer():
    cfg = granite_config("tiny", num_layers=4, use_flash_attn=False)
    params = mamba.init_mamba_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    # projections large enough that the recurrence matters
    params = {**params, "in_proj": {"kernel": 8 * params["in_proj"]["kernel"]},
              "D": params["D"] + 0.5}
    return cfg, params


def _cache(cfg, slots, context, valid, rows=None, pool=None, total=3,
           kernel="xla"):
    pool = pool or paged_kv.init_pools(
        cfg, 4, 8, num_slots=total)[0]
    assert paged_kv.is_state(pool)
    tables = {paged_kv.FULL: jnp.zeros((len(context), 2), jnp.int32)}
    if rows is not None:
        tables[paged_kv.STATE] = jnp.asarray(rows, jnp.int32)
    return paged_kv.step_caches(
        [pool], tables, jnp.asarray(context, jnp.int32),
        jnp.asarray(valid, jnp.int32), kernel, (paged_kv.STATE,))[0]


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_chunks_then_steps_are_the_whole_sequence(layer, kernel, monkeypatch):
    """ONE function in two forms: a sequence through chunks of 16 (the
    last one padded) and then steps, the state carried in the pool,
    against the cache-less chunk from zeros over the whole of it.  The
    steps on either path: XLA's, and the in-place kernel's
    (``ops/pallas/ssm_step.py``, interpret mode)."""
    from megatron_llm_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_INTERPRET", kernel == "pallas")
    cfg, params = layer
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 50, cfg.hidden_size))
    whole = np.asarray(mamba.mamba_mixer(h, params, cfg))
    assert np.abs(whole).max() > 0.05
    pool, out, at = None, [], 0
    for n in (16, 16, 8):                       # 40 tokens in chunks
        chunk = jnp.pad(h[:, at:at + n], [(0, 0), (0, 16 - n), (0, 0)])
        got, cache = mamba.mamba_mixer(
            chunk, params, cfg,
            kv_cache=_cache(cfg, 3, [at], [n], rows=[1], pool=pool))
        pool = cache.pool
        out.append(np.asarray(got[:, :n]))
        at += n
    for t in range(40, 50):                     # then steps, slot 1 of 3
        got, cache = mamba.mamba_mixer(
            jnp.tile(h[:, t:t + 1], (3, 1, 1)), params, cfg,
            kv_cache=_cache(cfg, 3, [0, t, 0], [0, 1, 0], pool=pool,
                            kernel=kernel))
        pool = cache.pool
        out.append(np.asarray(got[1:2]))
    np.testing.assert_allclose(np.concatenate(out, axis=1), whole,
                               atol=TOL, rtol=1e-4)
    # idle rows wrote to the garbage row: slots 0 and 2 are untouched
    assert (np.asarray(pool["ssm_state"][0]) == 0).all()
    assert (np.asarray(pool["ssm_state"][2]) == 0).all()
    assert np.abs(np.asarray(pool["ssm_state"][1])).max() > 0


def test_a_padded_chunk_leaves_the_state_at_its_last_valid_token(layer):
    cfg, params = layer
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 16, cfg.hidden_size))
    _, padded = mamba.mamba_mixer(
        h, params, cfg, kv_cache=_cache(cfg, 3, [0], [11], rows=[2]))
    _, exact = mamba.mamba_mixer(
        h[:, :11], params, cfg, kv_cache=_cache(cfg, 3, [0], [11], rows=[2]))
    for name in ("ssm_state", "conv_state"):
        np.testing.assert_allclose(np.asarray(padded.pool[name][2]),
                                   np.asarray(exact.pool[name][2]),
                                   atol=1e-6, rtol=0)
    # the convolution's columns are the last three VALID tokens'
    assert np.abs(np.asarray(padded.pool["conv_state"][2])).min() > 0
    assert int(padded.context_lens[0]) == 11


def test_a_first_launch_reads_zeros_whatever_the_slot_held(layer):
    """A slot is reused with no clearing launch: ``context_lens`` 0 reads
    zeros over a pool filled with another request's state."""
    cfg, params = layer
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 16, cfg.hidden_size))
    dirty = jax.tree_util.tree_map(
        lambda a: jnp.full(a.shape, 3.0, a.dtype),
        paged_kv.init_pools(cfg, 4, 8, num_slots=3)[0])
    fresh, _ = mamba.mamba_mixer(
        h, params, cfg, kv_cache=_cache(cfg, 3, [0], [16], rows=[1],
                                        pool=dirty))
    np.testing.assert_allclose(np.asarray(fresh),
                               np.asarray(mamba.mamba_mixer(h, params, cfg)),
                               atol=TOL, rtol=1e-4)
    carried, _ = mamba.mamba_mixer(
        h, params, cfg, kv_cache=_cache(cfg, 3, [16], [16], rows=[1],
                                        pool=dirty))
    assert np.abs(np.asarray(carried) - np.asarray(fresh)).max() > 1e-2


def test_the_state_pool_is_two_arrays_a_slot(layer):
    cfg, _ = layer
    pools = paged_kv.init_pools(cfg, 4, 8, num_slots=5, dtype=jnp.bfloat16)
    assert [paged_kv.is_state(p) for p in pools] == [True, True, False, True]
    s = pools[0]
    assert s["conv_state"].shape == (6, 3, cfg.mamba_conv_dim)
    assert s["conv_state"].dtype == jnp.bfloat16
    assert s["ssm_state"].shape == (6, 8, 32, 16)
    assert s["ssm_state"].dtype == jnp.float32      # whatever the compute


def test_the_legacy_decode_caches_are_refused_by_name(layer):
    cfg, params = layer
    with pytest.raises(NotImplementedError, match="legacy decode caches"):
        mamba.mamba_mixer(jnp.zeros((1, 1, cfg.hidden_size)), params, cfg,
                          kv_cache={"k": None, "v": None, "index": 0})
