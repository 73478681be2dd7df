"""Who owns the KV pool (serving/engine.py): the programs of a decode
step, ``engine_decode`` and ``engine_verify``, take the pool donated and
give a pool back; the chunk and the page programs are lent theirs.

Held here on tiny models on the CPU: the arrays a step was given are
deleted and ``st.pages`` is live; what is served is bit for bit what
programs built without donation serve (tokens and the pool's contents),
family by family; a host-tier spill that races the launches reads the
page's true bytes; a launch that raises holding the pool ends in a
restart; and ``program_tables()`` asked for mid-run lowers nothing and
keeps no array of the pool.
"""

import threading
import time

import jax
import numpy as np
import pytest

import _family
from megatron_llm_tpu import models
from megatron_llm_tpu.serving import (EngineConfig, InferenceEngine,
                                      SamplingParams)
from megatron_llm_tpu.serving import engine as engine_mod
from megatron_llm_tpu.serving.kv_blocks import chain_block_digests
from megatron_llm_tpu.serving.request import EngineError

GREEDY = dict(temperature=0.0)


def _model(family):
    """(model, params): the harness's where the family has a row."""
    if family in _family.FAMILIES:
        return _family.built(family)[:2]
    model = getattr(models, family.capitalize() + "Model")(
        getattr(models, family + "_config")("tiny", use_flash_attn=False))
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model, params, **kw):
    kw = dict(dict(num_slots=3, block_size=8, max_model_len=64,
                   prefill_chunk=16, preemption=False,
                   default_deadline_secs=0.0, restart_backoff_secs=0.0),
              **kw)
    return InferenceEngine(model, params, EngineConfig(**kw))


@pytest.fixture(scope="module")
def dense():
    return _model("mistral")


def _leaves(pages):
    return jax.tree_util.tree_leaves(pages)


def _prompts(vocab, n=3, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab - 1, 19 + 6 * i).tolist()
            for i in range(n)]


def _serve(eng, prompts, new=6):
    reqs = [eng.submit(p, SamplingParams(max_new_tokens=new, **GREEDY))
            for p in prompts]
    while any(r.finish_reason is None for r in reqs):
        assert eng.step()
    return [list(r.out_tokens) for r in reqs]


def _given(eng, attr):
    """Lay a tap over program ``attr`` that keeps the arrays of every
    pool the program is given; returns the list it fills."""
    inner, seen = getattr(eng, attr), []

    def tapped(params, pages, *rest):
        seen.append(_leaves(pages))
        return inner(params, pages, *rest)

    setattr(eng, attr, tapped)
    return seen


# -- a step consumes the pool it is given ----------------------------------

@pytest.mark.parametrize("step, kw", [
    ("_decode_step", {}),
    ("_verify_step", dict(speculative=True, draft_k=3)),
], ids=["decode", "verify"])
def test_a_step_consumes_the_pool_it_is_given(dense, step, kw):
    eng = _engine(*dense, **kw)
    eng.warmup()
    stepped, chunked = _given(eng, step), _given(eng, "_prefill_step")
    _serve(eng, _prompts(dense[0].cfg.padded_vocab_size))
    assert len(stepped) > 3 and len(chunked) > 3
    # what a step was given is gone; what a chunk was lent stands for
    # as long as somebody holds it
    assert all(a.is_deleted() for pool in stepped for a in pool)
    assert not any(a.is_deleted() for pool in chunked for a in pool)
    live = _leaves(eng._st.pages)
    assert not any(a.is_deleted() for a in live)
    assert sum(a.nbytes for a in live) == eng.kv_pool_bytes
    eng.blocks.check_invariants()


def test_a_chunk_and_the_page_programs_are_lent_the_pool(dense):
    eng = _engine(*dense, host_cache_bytes=1 << 20)
    try:
        eng.warmup()
        st = eng._st
        before = _leaves(st.pages)
        eng._prefill_step(eng.params, st.pages, np.zeros((1, 16), np.int32),
                          np.int32(0), np.int32(0),
                          eng._cache.chunk_tables(st.blocks, 0, True))
        eng._copy_page(st.pages, 0, 0)
        page = eng._fetch_block(st.pages, np.int32(0))
        eng._host_load(st.pages, page, np.int32(0))
        assert not any(a.is_deleted() for a in before)
        assert all(a is b for a, b in zip(before, _leaves(st.pages)))
    finally:
        eng.stop()


# -- the same work as programs built without donation ----------------------

def _lend(eng):
    """The two step programs built as the chunk is: lent the pool."""
    eng._decode_step = engine_mod._program(eng._decode_impl, "engine_decode")
    eng._verify_step = engine_mod._program(eng._verify_impl, "engine_verify")
    return eng


@pytest.mark.parametrize("family, kw", [
    ("mistral", {}),
    ("olmoe", {}),
    ("kanana", {}),
    ("mellum", {}),
    ("granite", {}),
    ("mistral", dict(int8_kv_cache=True)),
    ("mistral", dict(speculative=True, draft_k=3)),
], ids=["dense", "sparse", "latent_pool", "two_groups", "state_space",
        "int8_pool", "speculative"])
def test_served_as_programs_built_without_donation_serve(family, kw):
    model, params = _model(family)
    prompts = _prompts(model.cfg.padded_vocab_size)
    owned, lent = _engine(model, params, **kw), _lend(
        _engine(model, params, **kw))
    given = _given(lent, "_verify_step" if kw.get("speculative")
                   else "_decode_step")
    answers = [_serve(e, prompts) for e in (owned, lent)]
    assert answers[0] == answers[1] and all(len(a) == 6 for a in answers[0])
    assert given and not any(a.is_deleted() for pool in given for a in pool)
    # the pool as the run leaves it, every array of every layer (a
    # state-space layer's per-slot arrays among them), bit for bit
    a, b = _leaves(owned._st.pages), _leaves(lent._st.pages)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for name in ("decode_steps", "prefill_chunks", "tokens_generated"):
        assert getattr(owned, name) == getattr(lent, name)
    np.testing.assert_array_equal(owned._st.keys, lent._st.keys)


# -- a reader off the engine's thread --------------------------------------

def test_a_spill_racing_the_launches_reads_the_pages_true_bytes(dense):
    """The spill thread's read (``_spill_fetch``) goes through the
    pool's lock: hammered from this thread while the engine's thread
    launches decode steps, it never raises and never returns anything
    but the registered page's bytes; the host tier's own thread spills
    the run's pages meanwhile."""
    model, params = dense
    eng = _engine(model, params, num_slots=2, host_cache_bytes=4 << 20,
                  num_blocks=40)
    eng.warmup()
    eng.start()
    try:
        prompt = _prompts(model.cfg.padded_vocab_size, n=1, seed=11)[0][:17]
        eng.submit(prompt, SamplingParams(max_new_tokens=2, **GREEDY)
                   ).result(timeout=120)
        assert eng.host_cache.drain()
        digest = chain_block_digests(prompt, 8, 2)[1]
        block, epoch = eng.blocks.host_spill_check(digest)
        truth = eng._spill_fetch(eng.blocks, block)
        assert any(np.abs(np.asarray(a, np.float32)).max() > 0
                   for a in _leaves(truth))

        steps_before = eng.decode_steps
        busy = [eng.submit(_prompts(model.cfg.padded_vocab_size, n=1,
                                    seed=20 + i)[0][:9],
                           SamplingParams(max_new_tokens=40, **GREEDY))
                for i in range(2)]
        reads = 0
        while any(r.finish_reason is None for r in busy):
            got = eng._spill_fetch(eng.blocks, block)
            assert got is not None
            for x, y in zip(_leaves(got), _leaves(truth)):
                np.testing.assert_array_equal(x, y)
            reads += 1
        assert all(r.finish_reason == "length" for r in busy)
        assert eng.decode_steps - steps_before >= 40 and reads >= 10
        assert eng.blocks.host_spill_check(digest) == (block, epoch)
        # the tier's own thread ran beside the launches and is alive
        assert eng.host_cache.drain()
        host = eng.host_cache.stats()
        assert host["spills_completed"] >= 2 and eng.host_cache.contains(
            digest)
        assert eng.host_cache._thread.is_alive()
        eng.host_cache.check_invariants()
        # a manager a restart abandoned is answered with nothing
        assert eng._spill_fetch(object(), block) is None
    finally:
        eng.stop()


def test_a_spill_gives_up_on_a_state_a_restart_replaced(dense):
    """A thread wedged inside a launch never gives the pool's lock back:
    the spill's read stops waiting for it once the state is replaced."""
    eng = _engine(*dense, host_cache_bytes=1 << 20)
    try:
        eng.warmup()
        old = eng._st
        assert old.pool_lock.acquire(timeout=1)       # the wedged launch
        got = []
        reader = threading.Thread(
            target=lambda: got.append(eng._spill_fetch(old.blocks, 0)))
        reader.start()
        time.sleep(0.2)
        assert reader.is_alive()
        eng.restart("test")
        reader.join(timeout=10)
        assert not reader.is_alive() and got == [None]
        assert eng._st is not old and eng._st.pool_lock is not old.pool_lock
    finally:
        eng.stop()


# -- a launch that raises holding the pool ---------------------------------

@pytest.mark.parametrize("when", ["after_dispatch", "before_dispatch"])
def test_a_launch_that_raises(dense, when):
    """After dispatch the pool the launch was given is gone: the state
    goes the way of a wedged one (``restart()``: a fresh pool) and the
    requeued request completes with the tokens of an undisturbed run.
    Before dispatch the pool stands, and the engine fails the batch and
    carries on as it always has."""
    model, params = dense
    prompt = _prompts(model.cfg.padded_vocab_size, n=1, seed=7)[0]
    sp = SamplingParams(max_new_tokens=8, **GREEDY)
    clean = _engine(model, params)
    clean.warmup()
    (expected,) = _serve(clean, [prompt], new=8)

    eng = _engine(model, params)
    eng.warmup()
    inner, calls = eng._decode_step, []

    def faulty(*args):
        calls.append(1)
        if len(calls) == 3:
            if when == "after_dispatch":
                inner(*args)
            raise RuntimeError("the launch failed")
        return inner(*args)

    eng._decode_step = faulty
    first = eng._st
    eng.start()
    try:
        req = eng.submit(prompt, sp)
        if when == "after_dispatch":
            req.result(timeout=120)
            assert eng.engine_restarts == 1 and eng._st is not first
            assert all(a.is_deleted() for a in _leaves(first.pages))
            assert req.finish_reason == "length"
            assert list(req.out_tokens) == expected
        else:
            with pytest.raises(EngineError, match="the launch failed"):
                req.result(timeout=120)
            assert eng.engine_restarts == 0 and eng._st is first
            assert req.finish_reason == "error"
            again = eng.submit(prompt, sp)
            again.result(timeout=120)
            assert list(again.out_tokens) == expected
        assert not any(a.is_deleted() for a in _leaves(eng._st.pages))
        eng.blocks.check_invariants()
    finally:
        eng.stop()


# -- the tables mid-run ----------------------------------------------------

def test_the_tables_asked_for_mid_run_lower_nothing_and_keep_no_array(dense):
    from test_program_tables import _Heard

    model, params = dense
    eng = _engine(model, params)
    eng.warmup()
    weights = {id(a) for a in jax.tree_util.tree_leaves(params)}
    heard = _Heard()
    eng.start()
    try:
        busy = [eng.submit(p[:9], SamplingParams(max_new_tokens=40, **GREEDY))
                for p in _prompts(model.cfg.padded_vocab_size)]
        asked = 0
        while any(r.finish_reason is None for r in busy):
            for args in eng._program_arguments().values():
                kept = [a for a in jax.tree_util.tree_leaves(args)
                        if isinstance(a, jax.Array) and id(a) not in weights]
                assert kept == []
            eng._program_tables = None      # build them again
            tables = eng.program_tables()
            asked += 1
        assert asked >= 2 and heard.take() == []
        assert all(r.finish_reason == "length" for r in busy)
    finally:
        heard.events = None
        eng.stop()
    assert tables["engine_decode"].kv_pool_copy_bytes() == 0
    assert tables["engine_prefill"].kv_pool_copy_bytes() >= eng.kv_pool_bytes
