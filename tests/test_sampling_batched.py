"""The serving engine's sampler (``text_generation/sampling.py``:
``modify_logits_batched`` / ``sample_batched``) does the work the step's
live rows ask for, inside one program: an argmax alone when they are all
greedy, a draw with no sort when none of the sampling rows filters, ONE
sort when one does.  Held here against the two-sort sampler it replaced,
kept below as the oracle: filtered logits and tokens equal bit for bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hlo_text import sorts_and_their_guards

from megatron_llm_tpu.models.llama import LlamaModel, llama_config
from megatron_llm_tpu.serving import (
    EngineConfig,
    InferenceEngine,
    SamplingParams,
)
from megatron_llm_tpu.serving import engine as engine_module
from megatron_llm_tpu.text_generation.sampling import (
    NEG_INF,
    modify_logits_batched,
    sample_batched,
)


# ---------------------------------------------------------------------------
# the oracle: the sampler as it stood before (two full sorts, every row of
# every step filtered and drawn, then thrown away for the greedy rows)
# ---------------------------------------------------------------------------

def oracle_modify(logits, top_k, top_p, temperature):
    logits = logits.astype(jnp.float32)
    V = logits.shape[-1]
    t = temperature[:, None]
    logits = jnp.where(t > 0.0, logits / jnp.maximum(t, 1e-6), logits)
    sorted_l = jnp.sort(logits, axis=-1)[..., ::-1]
    kth = jnp.take_along_axis(
        sorted_l, jnp.clip(top_k - 1, 0, V - 1)[:, None], axis=-1)
    k_active = (top_k > 0) & (top_k < V)
    logits = jnp.where(k_active[:, None] & (logits < kth), NEG_INF, logits)
    sorted_p = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_p, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.sum((cum - probs) < top_p[:, None], axis=-1,
                         keepdims=True) - 1
    cutoff = jnp.take_along_axis(sorted_p, jnp.maximum(cutoff_idx, 0),
                                 axis=-1)
    p_active = (top_p > 0.0) & (top_p < 1.0)
    return jnp.where(p_active[:, None] & (logits < cutoff), NEG_INF, logits)


def oracle_sample(logits, keys, top_k, top_p, temperature, live=None):
    greedy = (temperature <= 0.0) | (top_k == 1)
    filtered = oracle_modify(logits, top_k, top_p, temperature)
    drawn = jax.vmap(lambda l, k: jax.random.categorical(k, l))(
        filtered, keys)
    return jnp.where(greedy,
                     jnp.argmax(logits.astype(jnp.float32), axis=-1),
                     drawn).astype(jnp.int32)


# ---------------------------------------------------------------------------
# cases: name -> (V, per-row (top_k, top_p, temperature, live), what to do
# to the logits)
# ---------------------------------------------------------------------------

V_ODD = 37          # not a power of two
GREEDY_ROW = (0, 0.0, 0.0, True)


def _tie_at_kth(x):
    """Quantised rows: the k-th logit has equals on both sides of k."""
    return np.round(x * 2.0) / 2.0


def _ban(x):
    x = x.copy()
    x[:, 3] = NEG_INF           # the engine's ban pair writes this
    x[1, 7] = NEG_INF
    return x


CASES = {
    "top_k_only": (V_ODD, [(5, 0.0, 1.0, True), (3, 0.0, 0.7, True),
                           (36, 0.0, 1.3, True), (2, 0.0, 0.5, True)], None),
    "top_p_only": (V_ODD, [(0, 0.9, 1.0, True), (0, 0.5, 0.7, True),
                           (0, 0.05, 1.3, True), (0, 0.999, 2.0, True)],
                   None),
    "top_k_and_top_p": (V_ODD, [(10, 0.5, 0.9, True), (5, 0.9, 1.0, True),
                                (20, 0.3, 0.6, True), (3, 0.99, 1.5, True)],
                        None),
    "neither": (V_ODD, [(0, 0.0, 1.0, True), (0, 0.0, 0.7, True),
                        (0, 0.0, 1.3, True), (0, 0.0, 1e-3, True)], None),
    "top_k_at_and_over_v": (V_ODD, [(V_ODD, 0.0, 1.0, True),
                                    (V_ODD + 5, 0.0, 0.8, True),
                                    (V_ODD, 0.7, 1.0, True),
                                    (V_ODD - 1, 0.0, 1.0, True)], None),
    "top_p_zero_and_one": (V_ODD, [(0, 0.0, 0.9, True), (0, 1.0, 0.9, True),
                                   (6, 1.0, 1.0, True), (6, 0.0, 1.0, True)],
                           None),
    "ties_at_the_kth": (V_ODD, [(5, 0.0, 1.0, True), (9, 0.8, 1.0, True),
                                (2, 0.0, 0.5, True), (17, 0.6, 2.0, True)],
                        _tie_at_kth),
    "banned_neg_inf_entry": (V_ODD, [(5, 0.0, 1.0, True), (0, 0.9, 0.5, True),
                                     (8, 0.7, 2.0, True), (0, 0.0, 0.5, True),
                                     GREEDY_ROW], _ban),
    "greedy_and_sampled_mixed": (V_ODD, [GREEDY_ROW, (5, 0.9, 0.8, True),
                                         (1, 0.0, 1.0, True),
                                         (0, 0.0, 1.0, True), GREEDY_ROW,
                                         (0, 0.6, 1.0, True)], None),
    "stale_sampled_values_in_dead_slots": (
        V_ODD, [GREEDY_ROW, (7, 0.9, 0.8, False), (0, 0.0, 1.0, False),
                (1, 0.0, 1.0, True), (0, 0.5, 1.0, False), GREEDY_ROW],
        None),
    "one_live_filtering_row": (
        V_ODD, [(0, 0.0, 1.0, False), (4, 0.8, 0.9, True),
                (0, 0.9, 1.0, False), GREEDY_ROW], None),
    "one_live_plain_draw": (
        V_ODD, [(0, 0.0, 1.0, True), (4, 0.8, 0.9, False), GREEDY_ROW,
                (0, 0.9, 1.0, False)], None),
    "v_power_of_two": (64, [(5, 0.0, 1.0, True), (0, 0.9, 0.7, True),
                            (10, 0.5, 0.9, True), (0, 0.0, 1.0, True)],
                       None),
    "v_large_odd": (1001, [(50, 0.0, 1.0, True), (0, 0.95, 0.7, True),
                           (200, 0.5, 0.9, True), GREEDY_ROW], None),
}


def _case(name):
    V, rows, shape = CASES[name]
    rng = np.random.RandomState(sorted(CASES).index(name))
    x = (rng.randn(len(rows), V) * 2.0).astype(np.float32)
    if shape is not None:
        x = shape(x).astype(np.float32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(len(rows)) + 17)
    return (jnp.asarray(x), keys,
            jnp.asarray([r[0] for r in rows], jnp.int32),
            jnp.asarray([r[1] for r in rows], jnp.float32),
            jnp.asarray([r[2] for r in rows], jnp.float32),
            jnp.asarray([r[3] for r in rows], bool))


@pytest.mark.parametrize("name", sorted(CASES))
def test_filter_equals_the_two_sort_oracle_bit_for_bit(name):
    logits, _, top_k, top_p, temps, live = _case(name)
    want = np.asarray(oracle_modify(logits, top_k, top_p, temps))
    for fn in (modify_logits_batched, jax.jit(modify_logits_batched)):
        got = np.asarray(fn(logits, top_k, top_p, temps))
        assert got.dtype == np.float32
        rows = np.asarray(live)
        np.testing.assert_array_equal(got[rows], want[rows])


@pytest.mark.parametrize("name", sorted(CASES))
def test_tokens_equal_the_oracles_with_the_same_keys(name):
    logits, keys, top_k, top_p, temps, live = _case(name)
    rows = np.asarray(live)
    # several key sets, so that a draw that went wrong cannot hide behind
    # one lucky argmax
    for fold in range(4):
        ks = jax.vmap(lambda k: jax.random.fold_in(k, fold))(keys)
        want = np.asarray(oracle_sample(logits, ks, top_k, top_p, temps))
        for fn in (sample_batched, jax.jit(sample_batched)):
            got = np.asarray(fn(logits, ks, top_k, top_p, temps, live))
            assert got.dtype == np.int32
            np.testing.assert_array_equal(got[rows], want[rows])


def test_a_sampled_rows_token_is_the_same_alone_and_cobatched():
    logits, keys, top_k, top_p, temps, _ = _case("top_k_and_top_p")
    S, V = logits.shape
    fn = jax.jit(sample_batched)
    for row in range(S):
        alone = fn(logits[row:row + 1], keys[row:row + 1],
                   top_k[row:row + 1], top_p[row:row + 1],
                   temps[row:row + 1], jnp.ones(1, bool))
        # the same row among greedy rows, and among rows that sample
        # without a filter
        for other_temp in (0.0, 1.0):
            mixed_k = jnp.zeros(S, jnp.int32).at[row].set(top_k[row])
            mixed_p = jnp.zeros(S, jnp.float32).at[row].set(top_p[row])
            mixed_t = jnp.full(S, other_temp).at[row].set(temps[row])
            both = fn(logits, keys, mixed_k, mixed_p, mixed_t,
                      jnp.ones(S, bool))
            assert int(both[row]) == int(alone[0])


def test_an_all_greedy_live_set_is_the_argmax_and_draws_nothing():
    """Dead slots hold ``temps`` 1.0 (the engine's initial value) and a
    released request's top-p: they must not make the step draw.  A step
    that drew would give the dead rows drawn tokens, not their argmax."""
    rng = np.random.RandomState(5)
    S, V = 32, V_ODD
    logits = jnp.asarray(rng.randn(S, V).astype(np.float32))
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(S))
    live = jnp.arange(S) < 3
    temps = jnp.where(live, 0.0, 1.0)
    top_p = jnp.where(live, 0.0, 0.9)
    top_k = jnp.zeros(S, jnp.int32)
    best = np.asarray(jnp.argmax(logits, axis=-1))
    got = np.asarray(jax.jit(sample_batched)(logits, keys, top_k, top_p,
                                             temps, live))
    np.testing.assert_array_equal(got, best)
    # the control: the same arrays with every slot live do draw
    drew = np.asarray(jax.jit(sample_batched)(logits, keys, top_k, top_p,
                                              temps, jnp.ones(S, bool)))
    assert (drew != best).any()
    np.testing.assert_array_equal(drew[:3], best[:3])


# ---------------------------------------------------------------------------
# the compiled program: one sort, inside a conditional's branch
# ---------------------------------------------------------------------------

def test_the_compiled_sampler_holds_one_sort_inside_a_conditional():
    logits, keys, top_k, top_p, temps, live = _case("v_large_odd")
    new = jax.jit(sample_batched).lower(
        logits, keys, top_k, top_p, temps, live).compile().as_text()
    assert sorts_and_their_guards(new) == [True]
    # the reader, held against the sampler it replaced: two sorts, neither
    # under a conditional
    old = jax.jit(oracle_sample).lower(
        logits, keys, top_k, top_p, temps).compile().as_text()
    assert sorts_and_their_guards(old) == [False, False]


# ---------------------------------------------------------------------------
# through the engine (tiny model, CPU)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model_and_params():
    cfg = llama_config("tiny", num_layers=2, seq_length=64,
                       max_position_embeddings=64, padded_vocab_size=64,
                       use_flash_attn=False)
    model = LlamaModel(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model_and_params, **kw):
    model, params = model_and_params
    eng = InferenceEngine(model, params, EngineConfig(
        num_slots=4, block_size=8, prefill_chunk=16, max_model_len=64,
        max_queue_depth=32, default_deadline_secs=0.0, **kw))
    eng.warmup()
    eng.start()
    return eng


@pytest.fixture(scope="module")
def engine(model_and_params):
    eng = _engine(model_and_params)
    yield eng
    eng.stop()


def _serve(eng, requests):
    """Submit all at once (they co-batch), wait for all."""
    reqs = [eng.submit(prompt, sp) for prompt, sp in requests]
    return [r.result(timeout=180).out_tokens for r in reqs]


def _steps(eng):
    s = eng.stats()
    return s["decode_steps"], s["sample_draw_steps"], s["sample_sort_steps"]


def _decode_records_since(eng, seq):
    return [r for r in eng.loop_profiler.records()
            if r.seq > seq and r.kind in ("decode", "verify")]


def _last_seq(eng):
    recs = eng.loop_profiler.records()
    return recs[-1].seq if recs else -1


GREEDY = dict(temperature=0.0, eod_id=None)
PROMPTS = [[5, 6, 7, 8, 9], [1, 2, 3], [9, 8, 7, 6], [2, 3, 2, 3, 2, 3]]


def test_engine_counts_what_the_sampler_was_asked_for(engine):
    # warm-up's request is greedy: nothing drew yet
    assert _steps(engine)[1:] == (0, 0)

    # (1) all greedy: dead slots hold temps 1.0, and no step draws
    seq, before = _last_seq(engine), _steps(engine)
    _serve(engine, [(p, SamplingParams(max_new_tokens=6, **GREEDY))
                    for p in PROMPTS])
    after = _steps(engine)
    assert after[0] > before[0] and after[1:] == (0, 0)
    recs = _decode_records_since(engine, seq)
    assert recs and all(r.sampler_rows_drawn == 0
                        and r.sampler_rows_filtered == 0 for r in recs)

    # (2) the engine's default request (temperature 1.0, no filter) draws
    # and never sorts
    seq, before = _last_seq(engine), after
    _serve(engine, [(PROMPTS[0], SamplingParams(max_new_tokens=6, seed=3)),
                    (PROMPTS[1], SamplingParams(max_new_tokens=6,
                                                **GREEDY))])
    after = _steps(engine)
    assert after[1] > before[1] and after[2] == 0
    recs = _decode_records_since(engine, seq)
    assert sum(r.sampler_rows_drawn > 0 for r in recs) == \
        after[1] - before[1]
    assert all(r.sampler_rows_drawn <= 1 and r.sampler_rows_filtered == 0
               for r in recs)

    # (3) a top-p request sorts, in the steps it is live in and no others
    seq, before = _last_seq(engine), after
    _serve(engine, [
        (PROMPTS[0], SamplingParams(max_new_tokens=4, temperature=0.8,
                                    top_p=0.9, seed=5)),
        (PROMPTS[1], SamplingParams(max_new_tokens=12, seed=3)),
        (PROMPTS[2], SamplingParams(max_new_tokens=12, **GREEDY))])
    after = _steps(engine)
    assert after[2] > before[2] and after[1] > before[1]
    assert after[1] - before[1] > after[2] - before[2]
    recs = _decode_records_since(engine, seq)
    assert sum(r.sampler_rows_filtered > 0 for r in recs) == \
        after[2] - before[2]
    assert sum(r.sampler_rows_drawn > 0 for r in recs) == \
        after[1] - before[1]
    assert all(r.sampler_rows_filtered <= r.sampler_rows_drawn <= r.rows
               for r in recs)
    # the records' JSON copy (postmortem bundles) carries them
    row = engine.loop_profiler.ring_records(last=1)[0]
    assert {"sampler_rows_drawn", "sampler_rows_filtered"} <= set(row)


# sampled, filtered, decaying and greedy requests in one batch; the
# greedy prompts repeat themselves, so with speculation on they draft
# while the sampled slots ride the verify program draft-less
STREAMS = [
    ([1, 2, 3, 4, 1, 2, 3], SamplingParams(max_new_tokens=14, **GREEDY)),
    ([5, 6, 7], SamplingParams(max_new_tokens=10, seed=3)),
    ([5, 6, 7], SamplingParams(max_new_tokens=10, temperature=0.8,
                               top_p=0.9, seed=5)),
    ([9, 8, 7, 6], SamplingParams(max_new_tokens=10, temperature=0.9,
                                  top_k=20, seed=7)),
    ([2, 3, 2, 3, 2, 3], SamplingParams(max_new_tokens=12, **GREEDY)),
    ([4, 4, 5], SamplingParams(max_new_tokens=10, temperature=1.2, top_k=8,
                               top_p=0.7, top_p_decay=0.9, top_p_bound=0.2,
                               seed=11, ban_pair=(5, 6))),
]


def _streams_with_the_oracle_sampler(model_and_params, monkeypatch, **kw):
    with monkeypatch.context() as m:
        m.setattr(engine_module, "sample_batched", oracle_sample)
        eng = _engine(model_and_params, **kw)     # traces at warm-up
        try:
            return _serve(eng, STREAMS)
        finally:
            eng.stop()


def test_engine_streams_equal_the_two_sort_samplers(engine,
                                                    model_and_params,
                                                    monkeypatch):
    """The decode program: every request's stream is what the engine gave
    with the old sampler in its programs."""
    want = _streams_with_the_oracle_sampler(model_and_params, monkeypatch)
    assert _serve(engine, STREAMS) == want
    # and a sampled request's stream does not depend on its batch-mates
    for i in (1, 2, 3, 5):
        assert _serve(engine, [STREAMS[i]]) == [want[i]]


def test_verify_program_streams_equal_the_two_sort_samplers(
        engine, model_and_params, monkeypatch):
    """Speculation on: the sampled, non-drafting slots of the verify
    program give the streams they gave with the old sampler, which are
    the plain decode program's, while greedy slots draft beside them."""
    want = _streams_with_the_oracle_sampler(
        model_and_params, monkeypatch, speculative=True, draft_k=4)
    spec = _engine(model_and_params, speculative=True, draft_k=4)
    try:
        seq = _last_seq(spec)
        got = _serve(spec, STREAMS)
        recs = _decode_records_since(spec, seq)
        stats = spec.stats()
    finally:
        spec.stop()
    assert got == want == _serve(engine, STREAMS)
    assert recs and all(r.kind == "verify" for r in recs)
    assert stats["drafted_tokens"] > 0
    assert stats["sample_sort_steps"] > 0
    assert stats["sample_draw_steps"] >= stats["sample_sort_steps"]
    assert sum(r.sampler_rows_filtered > 0 for r in recs) == \
        stats["sample_sort_steps"]
