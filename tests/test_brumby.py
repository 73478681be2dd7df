"""Brumby-14B-Base (``model_type`` ``brumby``: every layer a power
retention of degree 2, no key and no value kept, a recurrent state a
key-value head shared by its query heads, the first pool with no page),
against the benchmark's plain reference.

Seeded random weights, CPU, float32 on both sides, tiny widths with
grouped queries kept: 4 query heads over 2 key-value heads of 16, so
``phi`` has 9 rotations of 16 (144 rows in the program's layout, where
the least a symmetric square of 16 takes is 136).  The reference is the
file the benchmark's probe loads (``benchmarks/reference/brumby.py``:
the QUADRATIC form, no ``phi``, no state, no chunk), loaded here by
path; the engine is held to it by the probe's own comparison
(``brumby_probe.py``: tapped logits and one layer's carried state).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _family
from _family import kernels
from megatron_llm_tpu.models import retention
from megatron_llm_tpu.models.brumby import brumby_config
from megatron_llm_tpu.models.mistral import MistralModel, mistral_config
from megatron_llm_tpu.ops import paged_kv
from megatron_llm_tpu.ops.pallas import paged_attention as pa
from megatron_llm_tpu.ops.pallas import retention_step as rs
from megatron_llm_tpu.serving import SamplingParams

# float32 on both sides, the same sums in another order
TOL = _family.FAMILIES["brumby"].tol


@pytest.fixture(scope="module")
def family():
    return _family.built("brumby")


@pytest.fixture(scope="module")
def served(family):
    return family.model, family.params


def _operands(b, n, g=2, r=2, d=16, seed=0, gate_shift=0.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, n, g, r, d))
    k = jax.random.normal(ks[1], (b, n, g, d))
    v = jax.random.normal(ks[2], (b, n, g, d))
    a = jax.nn.log_sigmoid(jax.random.normal(ks[3], (b, n, g)) + gate_shift)
    return q, k, v, a


def _zeros(b, g=2, d=16):
    O = rs.rotations(d)
    return jnp.zeros((b, g, O, d, d)), jnp.zeros((b, g, O, d))


def _quadratic(q, k, v, a):
    """o_t = sum_j exp(A_t - A_j) (q_t . k_j)^2 v_j / the same sum."""
    n = q.shape[1]
    A = jnp.cumsum(a, axis=1)
    seen = jnp.tril(jnp.ones((n, n), bool))[None, :, :, None]
    qk = jnp.einsum("btgrd,bsgd->btsgr", q, k, precision="highest")
    w = jnp.where(seen, jnp.exp(jnp.where(
        seen, A[:, :, None] - A[:, None], 0.0)), 0.0)[..., None] * qk ** 2
    return jnp.einsum("btsgr,bsgd->btgrd", w, v,
                      precision="highest") / w.sum(2)[..., None]


def _chunk(q, k, v, a, S, z):
    with jax.default_matmul_precision("highest"):
        num, den, S, z = retention.retention_chunk(q, k, v, a, S, z,
                                                   jnp.float32)
    return num / den[..., None], S, z


@pytest.mark.parametrize("d", [8, 16, 128])
def test_phi_of_two_vectors_multiplies_to_their_products_square(d):
    x, y = jax.random.normal(jax.random.PRNGKey(d), (2, 5, d))
    assert rs.phi(x).shape == (5, d // 2 + 1, d)
    np.testing.assert_allclose((rs.phi(x) * rs.phi(y)).sum((-1, -2)),
                               (x * y).sum(-1) ** retention.DEGREE,
                               rtol=2e-5, atol=1e-5)


def test_the_chunk_is_the_quadratic_form_and_two_chunks_are_one():
    q, k, v, a = _operands(2, 300)
    whole, S, z = _chunk(q, k, v, a, *_zeros(2))
    np.testing.assert_allclose(whole, _quadratic(q, k, v, a), atol=TOL)
    first, S1, z1 = _chunk(q[:, :130], k[:, :130], v[:, :130], a[:, :130],
                           *_zeros(2))
    rest, S2, z2 = _chunk(q[:, 130:], k[:, 130:], v[:, 130:], a[:, 130:],
                          S1, z1)
    np.testing.assert_allclose(jnp.concatenate([first, rest], 1), whole,
                               atol=TOL)
    np.testing.assert_allclose(S2, S, atol=TOL * float(jnp.abs(S).max()))
    np.testing.assert_allclose(z2, z, atol=TOL * float(jnp.abs(z).max()))


def test_steps_one_by_one_are_the_chunk():
    q, k, v, a = _operands(2, 40, seed=1)
    whole, S, z = _chunk(q, k, v, a, *_zeros(2))
    Ss, zs = _zeros(2)
    for t in range(40):
        num, den, Ss, zs = rs.dense_retention_step(
            Ss, zs, q[:, t], k[:, t], v[:, t], a[:, t])
        np.testing.assert_allclose(num / den[..., None], whole[:, t],
                                   atol=TOL)
    np.testing.assert_allclose(Ss, S, atol=TOL * float(jnp.abs(S).max()))
    np.testing.assert_allclose(zs, z, atol=TOL * float(jnp.abs(z).max()))


def test_gates_near_zero_over_a_long_row_do_not_underflow():
    """exp(a) of 1e-9 a token over 260 tokens: a product of the decays is
    0 in float32 after five, the differences of the running sum are not."""
    q, k, v, a = _operands(1, 260, seed=2, gate_shift=-20.0)
    assert float(a.max()) < -15 and float(jnp.cumsum(a, 1).min()) < -4000
    out, S, z = _chunk(q, k, v, a, *_zeros(1))
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(S).all())
    np.testing.assert_allclose(out, _quadratic(q, k, v, a), atol=TOL)


def test_a_key_value_heads_state_is_read_by_its_own_query_heads_alone():
    """Another key-value head 0: exactly its query heads' outputs move,
    in the chunk and in the step."""
    q, k, v, a = _operands(1, 20, seed=3)
    other = k.at[:, :, 0].set(k[:, ::-1, 0])
    one, S, z = _chunk(q, k, v, a, *_zeros(1))
    two, S2, z2 = _chunk(q, other, v, a, *_zeros(1))
    assert float(jnp.abs(one[:, :, 0] - two[:, :, 0]).max()) > 1e-2
    np.testing.assert_array_equal(one[:, :, 1], two[:, :, 1])
    step = [rs.dense_retention_step(s_, z_, q[:, 19], k[:, 19], v[:, 19],
                                    a[:, 19])[0]
            for s_, z_ in ((S, z), (S2, z2))]
    assert float(jnp.abs(step[0][:, 0] - step[1][:, 0]).max()) > 1e-2
    np.testing.assert_array_equal(step[0][:, 1], step[1][:, 1])


def _caches(cfg, pools, slots, context, valid, kernel="xla"):
    tables = {} if slots is None else {
        paged_kv.STATE: jnp.asarray(slots, jnp.int32)}
    return paged_kv.step_caches(
        pools, tables, jnp.asarray(context, jnp.int32),
        jnp.asarray(valid, jnp.int32), kernel, paged_kv.layer_groups(cfg))


def test_padding_and_idle_rows_leave_the_state_exact(served):
    """A chunk of two rows, one of 5 real tokens of 12 and one idle: the
    first row's slot holds the state of its 5 tokens (a padded token
    neither decays nor adds), the idle row's slot keeps its own, and a
    row whose context is 0 starts from zeros whatever its slot held."""
    model, params = served
    cfg = model.cfg
    layer = jax.tree_util.tree_map(
        lambda p: p[0], params["transformer"]["layers"]["retention"])
    pools = paged_kv.init_pools(cfg, 1, 8, num_slots=3)
    dirty = jax.tree_util.tree_map(lambda x: x + 0.5, pools[0])
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 12, cfg.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(12)[None], (2, 12))

    def run(pool, rows, slots, context, valid):
        cache = _caches(cfg, [pool], slots, context, valid)[0]
        with jax.default_matmul_precision("highest"):
            return retention.retention_mixer(
                rows, layer, cfg, kv_cache=cache,
                position_ids=pos[:rows.shape[0], :rows.shape[1]])

    out, cache = run(dirty, h, [2, 0], [0, 7], [5, 0])
    alone, only = run(pools[0], h[:1, :5], [1], [0], [5])
    np.testing.assert_allclose(out[0, :5], alone[0], atol=TOL)
    for name in ("ret_state", "ret_sum"):
        np.testing.assert_allclose(cache.pool[name][2], only.pool[name][1],
                                   atol=TOL)
        np.testing.assert_array_equal(cache.pool[name][0], dirty[name][0])
        np.testing.assert_array_equal(cache.pool[name][1], dirty[name][1])
    assert cache.context_lens.tolist() == [5, 7]


@pytest.mark.parametrize("block_bytes", [1 << 20, 3 * 16 * 16 * 4])
def test_the_steps_kernel_is_the_dense_step_on_live_rows(block_bytes,
                                                         monkeypatch):
    """``retention_state_step`` under interpret against
    ``dense_retention_step``: live rows advanced, a fresh row from zeros,
    idle rows, a slot no row has and the garbage row bit for bit; one
    block a head and three."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    monkeypatch.setattr(rs, "_BLOCK_BYTES", block_bytes)
    jax.clear_caches()
    g, r, d, slots = 2, 2, 16, 5
    O = rs.rotations(d)
    assert O // rs.rotation_block(O, d) == (1 if block_bytes > 4096 else 3)
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    pool = jax.random.normal(ks[0], (slots + 2, g, O, d, d))
    q = jax.random.normal(ks[1], (slots, g, r, d))
    k, v = jax.random.normal(ks[2], (2, slots, g, d))
    a = jax.nn.log_sigmoid(jax.random.normal(ks[3], (slots, g)))
    live = jnp.array([True, False, True, True, False])
    fresh = jnp.array([False, False, True, False, True])
    num, after = rs.retention_state_step(pool, q, k, v, a, live, fresh)
    before = jnp.where(fresh[:, None, None, None, None], 0.0, pool[:slots])
    want, _, S, _ = rs.dense_retention_step(
        before, jnp.zeros((slots, g, O, d)), q, k, v, a)
    rows = np.flatnonzero(live)
    np.testing.assert_allclose(num[rows], want[rows], atol=TOL * 10)
    np.testing.assert_allclose(after[rows], S[rows], atol=1e-6)
    np.testing.assert_array_equal(num[~np.asarray(live)], 0.0)
    for idle in (1, 4, 5, 6):
        np.testing.assert_array_equal(after[idle], pool[idle])
    jax.clear_caches()


def _pool(slots, g=2, d=16, seed=7):
    """A state group's two arrays of one layer, every slot dirty."""
    O = rs.rotations(d)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(ks[0], (slots + 1, g, O, d, d)),
            jax.random.normal(ks[1], (slots + 1, g, O, d)))


def _xla_rows(q, k, v, a, state, sums, slots, valid, fresh):
    """What the mixer does on the XLA path: the rows' state read (zeros
    where fresh), the tokens past ``valid`` neither decaying nor adding,
    ``retention_chunk``."""
    n = q.shape[1]
    live = (jnp.arange(n)[None] < jnp.asarray(valid)[:, None])[..., None]
    new = jnp.asarray(fresh)
    S = jnp.where(new[:, None, None, None, None], 0.0,
                  state[jnp.asarray(slots)])
    z = jnp.where(new[:, None, None, None], 0.0, sums[jnp.asarray(slots)])
    with jax.default_matmul_precision("highest"):
        return retention.retention_chunk(
            q, jnp.where(live[..., None], k, 0.0), v, jnp.where(live, a, 0.0),
            S, z, jnp.float32)


def _in_the_kernel(q, k, v, a, state, sums, slots, valid, fresh):
    """The same through ``PagedKVCache.chunk_retention`` on the kernel
    path (a fresh row's context is 0): numerators, normalisers and the
    pool's two arrays as the call leaves them."""
    cache = paged_kv.PagedKVCache(
        {"ret_state": state, "ret_sum": sums}, None,
        jnp.where(jnp.asarray(fresh), 0, 7).astype(jnp.int32),
        jnp.asarray(valid, jnp.int32), kernel="pallas",
        group=paged_kv.STATE, slots=jnp.asarray(slots, jnp.int32))
    num, den, cache = cache.chunk_retention(q, k, v, a, jnp.float32)
    return num, den, cache.pool["ret_state"], cache.pool["ret_sum"]


# rows' lengths, slots, real tokens and who starts here, over a pool of
# five dirty slots and the garbage row; r query heads a key-value head
CHUNK_CASES = {
    "one_block_from_zeros": dict(n=128, slots=[2], valid=[128],
                                 fresh=[True]),
    "four_blocks_from_a_drawn_state": dict(n=512, slots=[4], valid=[512],
                                           fresh=[False]),
    "a_length_no_multiple_of_the_block": dict(n=300, slots=[0], valid=[300],
                                              fresh=[False]),
    "a_fresh_row_over_a_dirty_slot_beside_a_carried_one": dict(
        n=130, slots=[3, 1], valid=[130, 130], fresh=[True, False]),
    "an_idle_row_and_padding_past_valid_len": dict(
        n=300, slots=[1, 3, 0], valid=[45, 0, 200],
        fresh=[False, True, True]),
    "a_chunk_shorter_than_a_block": dict(n=12, slots=[2, 0], valid=[5, 12],
                                         fresh=[True, False]),
    "gates_near_zero_over_a_long_row": dict(
        n=260, slots=[1], valid=[260], fresh=[False], gate_shift=-20.0),
    "five_query_heads_a_key_value_head": dict(
        n=140, slots=[0], valid=[140], fresh=[False], r=5),
}


@pytest.mark.parametrize("case", sorted(CHUNK_CASES))
def test_the_chunks_kernel_is_retention_chunk_on_the_slots_state(
        case, monkeypatch):
    """``retention_state_chunk`` under interpret, through the cache that
    calls it, against ``retention_chunk`` on the rows' own state:
    numerators and
    normalisers at the real tokens, the state and the sums a live row
    leaves in its slot; a fresh row starts from zeros whatever its slot
    held; an idle row's slot, every slot no row has and the garbage row
    bit for bit."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    c = dict(CHUNK_CASES[case])
    n, slots, valid, fresh = c["n"], c["slots"], c["valid"], c["fresh"]
    b, r = len(slots), c.get("r", 2)
    q, k, v, a = _operands(b, n, r=r, seed=11,
                           gate_shift=c.get("gate_shift", 0.0))
    state, sums = _pool(5)
    num, den, S, z = _in_the_kernel(q, k, v, a, state, sums, slots, valid,
                                    fresh)
    wnum, wden, wS, wz = _xla_rows(q, k, v, a, state, sums, slots, valid,
                                   fresh)
    assert num.shape == wnum.shape and den.shape == wden.shape
    real = np.arange(n)[None] < np.asarray(valid)[:, None]
    assert bool(jnp.isfinite(num).all()) and bool(jnp.isfinite(S).all())
    np.testing.assert_allclose(
        (num / den[..., None])[real], (wnum / wden[..., None])[real],
        rtol=1e-5, atol=TOL)
    np.testing.assert_allclose(num[real], wnum[real],
                               atol=TOL * float(jnp.abs(wnum).max()))
    np.testing.assert_allclose(den[real], wden[real],
                               atol=TOL * float(jnp.abs(wden).max()))
    for row, slot in enumerate(slots):
        if valid[row]:
            np.testing.assert_allclose(
                S[slot], wS[row], atol=TOL * float(jnp.abs(wS).max()))
            np.testing.assert_allclose(
                z[slot], wz[row], atol=TOL * float(jnp.abs(wz).max()))
    untouched = [s_ for s_ in range(5) if s_ not in
                 [slot for row, slot in enumerate(slots) if valid[row]]]
    for slot in untouched:
        np.testing.assert_array_equal(S[slot], state[slot])
        np.testing.assert_array_equal(z[slot], sums[slot])
    # the kernel writes a live row's slot and nothing else (an idle
    # row's sums go to the garbage row, as on the XLA path)
    np.testing.assert_array_equal(S[5], state[5])


def test_in_the_kernel_a_heads_state_is_read_by_its_own_five_heads_alone(
        monkeypatch):
    """Another key-value head 0 through the chunk's kernel: exactly its
    five query heads' outputs and its own state move."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    q, k, v, a = _operands(1, 200, r=5, seed=12)
    other = k.at[:, :, 0].set(k[:, ::-1, 0])
    state, sums = _pool(1)
    one = _in_the_kernel(q, k, v, a, state, sums, [0], [200], [False])
    two = _in_the_kernel(q, other, v, a, state, sums, [0], [200], [False])
    out = [num / den[..., None] for num, den, _, _ in (one, two)]
    assert out[0].shape == (1, 200, 2, 5, 16)
    assert float(jnp.abs(out[0][:, :, 0] - out[1][:, :, 0]).min(
        axis=(0, 1, 3)).max()) > 0      # every one of the five moved
    assert float(jnp.abs(out[0][:, :, 0] - out[1][:, :, 0]).max()) > 1e-2
    np.testing.assert_array_equal(out[0][:, :, 1], out[1][:, :, 1])
    assert float(jnp.abs(one[2][0, 0] - two[2][0, 0]).max()) > 1e-2
    np.testing.assert_array_equal(one[2][0, 1], two[2][0, 1])
    np.testing.assert_array_equal(one[3][0, 1], two[3][0, 1])


def test_chunks_in_the_kernel_then_steps_in_the_steps_are_one_long_chunk(
        monkeypatch):
    """Two rows through ``PagedKVCache`` on the kernel path: a chunk of
    150 tokens from zeros over dirty slots, one of 70 over what it left,
    then six steps through the step's kernel (row s is slot s), against
    ONE chunk of 226 tokens in XLA's form; the caches' lengths
    advance."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    q, k, v, a = _operands(2, 226, seed=13)
    whole, S, z = _chunk(q, k, v, a, *_zeros(2))
    state, sums = _pool(2)
    cfg = brumby_config("tiny", use_flash_attn=False)
    pools = [{"ret_state": state, "ret_sum": sums}]
    outs, context = [], 0
    for lo, hi in ((0, 150), (150, 220)):
        cache = _caches(cfg, pools, [0, 1], [context] * 2, [hi - lo] * 2,
                        kernel="pallas")[0]
        num, den, cache = cache.chunk_retention(
            q[:, lo:hi], k[:, lo:hi], v[:, lo:hi], a[:, lo:hi], jnp.float32)
        cache = cache.write_state()
        outs.append(num / den[..., None])
        pools, context = [cache.pool], hi
        assert cache.context_lens.tolist() == [hi, hi]
    for t in range(220, 226):
        cache = _caches(cfg, pools, None, [t] * 2, [1] * 2,
                        kernel="pallas")[0]
        num, den, cache = cache.step_retention(q[:, t], k[:, t], v[:, t],
                                               a[:, t])
        outs.append((num / den[..., None])[:, None])
        pools = [cache.write_state().pool]
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), whole,
                               atol=TOL)
    np.testing.assert_allclose(pools[0]["ret_state"][:2], S,
                               atol=TOL * float(jnp.abs(S).max()))
    np.testing.assert_allclose(pools[0]["ret_sum"][:2], z,
                               atol=TOL * float(jnp.abs(z).max()))
    np.testing.assert_array_equal(pools[0]["ret_state"][2], state[2])


@pytest.mark.parametrize("path", ["no_cache", "xla", "pallas"])
def test_the_kernel_is_taken_only_under_a_cache_on_the_pallas_path(
        path, served, monkeypatch):
    """The cache-less forward (what differentiates through the model)
    and a cache whose ``kernel`` is ``'xla'`` (the CPU, several devices)
    trace no ``pallas_call``; a cache on the ``'pallas'`` path traces the
    chunk's kernel once a layer."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    model, params = served
    cfg = model.cfg
    tokens = jnp.ones((1, 40), jnp.int32)
    if path == "no_cache":
        text = str(jax.make_jaxpr(
            lambda p: model(p, tokens, train=False))(params))
    else:
        pools = paged_kv.init_pools(cfg, 1, 8, num_slots=2)

        def chunk(p, pools):
            from megatron_llm_tpu.models.language_model import (
                language_model_forward)

            caches = _caches(cfg, pools, [1], [0], [40], kernel=path)
            return language_model_forward(
                p, tokens, jnp.arange(40)[None], None, cfg, rng_key=None,
                train=False, kv_caches=caches)[0]

        text = str(jax.make_jaxpr(chunk)(params, pools))
    calls = text.count("retention_state_chunk")
    assert ("pallas_call" in text) == (path == "pallas"), path
    assert (calls > 0) == (path == "pallas"), (path, calls)


def test_the_plain_forward_is_the_references():
    _family.full_forward_is_the_references("brumby", 150, seed=0)


PROBE = dict(prompt_tokens=80, answer_tokens=12, tapped_chunks=[2, 3],
             state_layer=0, live_rows=3, margin=1e-3,
             logits_apart_tolerance=1e-4, decode_median_tolerance=1e-4,
             position_apart_tolerance=1e-4,
             state_apart_tolerance=1e-4, sum_apart_tolerance=1e-4,
             state_float32_share_floor=0.9)
# each named fault of the reference moves the tapped logits by hundredths
# of their deviation at least; float8 and bf16 are the precisions below
FAULTS = ("degree_one", "no_normaliser", "no_sqrt2", "no_gate",
          "own_term_decayed", "sum_not_decayed", "state_dropped_at_chunks",
          "kv_neighbour", "no_rope", "no_qk_norm", "bf16")


brumby_probe = _family.load("brumby_probe")


@pytest.fixture(scope="module")
def probed(kept_engines):
    """The probe's sequence served by a started engine on the XLA path,
    tapped: what ``engine_against_reference`` reads, once for the sound
    reference and every faulty one."""
    eng = kept_engines("brumby", **kernels("off")).start()
    try:
        prompt = _family.tokens(80, seed=1, vocab=256)
        req = eng.submit(prompt, SamplingParams(max_new_tokens=12,
                                                temperature=0.0))
        req.result(timeout=300)
        tokens = np.asarray(prompt + list(req.out_tokens)[:-1], np.int32)
        run = brumby_probe.engine_run(eng, tokens, 80, [32, 64], 0,
                                      live_rows=3)
    finally:
        eng.stop()
    return eng, tokens, run


@pytest.mark.time_limit(600)
@pytest.mark.parametrize("fault", ("sound",) + FAULTS)
def test_the_engines_logits_and_state_are_the_references(family, probed,
                                                        fault):
    """Chunks of 32 then steps through the state group: the ENGINE's own
    logits at the first rows of two chunks, the prompt's last row and
    every decode step, and the state its first layer's slot is left
    with, against the reference's full forward; a slot taken again (the
    prefixes reuse the first request's) starts from zeros.  Every named
    fault of the reference is told by the same limits."""
    eng, tokens, run = probed
    report, within, _, _ = brumby_probe.engine_against_reference(
        eng, family.weights, {**family.cfg, "fault_chunk": 32}, PROBE,
        tokens, run=run, faults=frozenset([fault]) - {"sound"})
    assert within == (fault == "sound"), report
    if fault == "sound":
        assert report["state"]["state_apart"] < 1e-5, report["state"]


@pytest.mark.time_limit(600)
def test_the_engine_on_the_kernel_path_is_the_references(family, engines):
    """The same through the chunk's kernel and the step's in interpret
    mode: the standing question (chunks of 32 then steps stepped by hand,
    the logits and the first layer's state and sums in the slot), then
    the probe's own comparison of what that request decoded: rows moved
    are the live rows, chunk tokens the kernel's."""
    eng, since, seq = _family.chunked_prefill_then_decode_is_one_forward(
        engines, "brumby", 80, 12, "on", seed=2)
    eng.start()
    try:
        report, within, _, _ = brumby_probe.engine_against_reference(
            eng, family.weights, family.cfg, PROBE, np.asarray(seq, np.int32))
    finally:
        eng.stop()
    assert within, report
    stats, records = since()
    assert eng.paged_kernel == "pallas"
    rows_live = sum(r.retention_rows_live for r in records
                    if r.kind == "decode")
    assert stats["retention_rows_moved"] == rows_live > 0
    # steps of one, two and three live rows, each held to the reference
    assert report["rows"]["live_rows"] == 3, report["rows"]
    assert set(report["rows"]["steps_by_live_rows"]) == {"1", "2", "3"}
    assert report["rows"]["states_held"] == 2
    assert stats["retention_tokens"] \
        == 2 * stats["prefill_tokens_computed"] + rows_live
    # the chunks ran in the chunk's kernel, every token of every layer
    assert eng.prefill_kernel == "pallas"
    chunks = [r for r in records if r.kind == "prefill"]
    assert chunks and all(
        r.retention_chunk_tokens_kernel == r.retention_tokens > 0
        for r in chunks)
    assert stats["retention_chunk_tokens_kernel"] \
        == 2 * stats["prefill_tokens_computed"]
    assert not any(r.retention_chunk_tokens_kernel for r in records
                   if r.kind == "decode")


def test_on_the_xla_path_no_chunk_token_is_the_kernels(probed):
    eng = probed[0]
    stats = eng.stats()
    assert eng.prefill_kernel == "xla"
    assert stats["retention_tokens"] > 0
    assert stats["retention_chunk_tokens_kernel"] == 0


def test_a_model_with_no_paged_layer_is_admitted_by_slots(engines):
    """No group has pages: no block is counted, no table built, no page
    program compiled, three long requests take the three slots and a
    fourth waits for a slot and for nothing else; the chunk OWNS the pool
    it writes (a paged model's is lent its pool)."""
    eng = engines("brumby", **kernels("off"))
    assert not eng._cache.paged and eng._cache.tables(eng.blocks) == {}
    assert set(eng._cache.tables(eng.blocks, slice(0, 1))) == {
        paged_kv.STATE}
    assert eng._num_blocks == 1 and paged_kv.paged_pools(eng._st.pages) == []
    assert not any(name in eng._jitted for name in (
        "engine_cow_copy", "engine_fetch_block", "engine_host_load"))
    reqs = [eng.submit([1 + i] * 200, SamplingParams(max_new_tokens=3,
                                                     temperature=0.0))
            for i in range(4)]
    held = eng._st.pages
    assert eng.step()
    assert sorted(eng.scheduler.active) == [0, 1, 2]
    assert eng.queue.depth() == 1
    assert all(a.is_deleted() for a in jax.tree_util.tree_leaves(held))
    stats = eng.blocks.stats()
    assert (stats["blocks_total"], stats["slots_in_use"]) == (0, 3)
    assert stats["state_bytes_held"] == 3 * eng._cache.state_bytes_per_slot
    while any(r.finish_reason is None for r in reqs):
        assert eng.step()
    eng.blocks.check_invariants()
    eng.warmup()
    assert set(eng.program_tables()) == {"engine_prefill",
                                         "engine_sample_first",
                                         "engine_decode"}
    with pytest.raises(ValueError, match="sizes a pool of pages"):
        engines.fresh("brumby", num_blocks=64)
    # a model with pages: its chunk is lent the pool and deletes nothing
    model = MistralModel(mistral_config("tiny", use_flash_attn=False))
    paged = _family.engine(model, model.init(jax.random.PRNGKey(0)),
                           max_model_len=64, prefill_chunk=16)
    assert paged._cache.paged and "engine_cow_copy" in paged._jitted
    paged.submit([1] * 20, SamplingParams(max_new_tokens=2))
    held = paged._st.pages
    assert paged.step()
    assert not any(a.is_deleted() for a in jax.tree_util.tree_leaves(held))
