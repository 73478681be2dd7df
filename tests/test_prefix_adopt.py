"""The match when prefill begins (``BlockManager.adopt_committed``,
``InferenceEngine._adopt_committed``) and the digests a request carries
(``kv_blocks.TokenChain``).

Admission matches a prompt against what the prefix cache holds when the
request is admitted; requests admitted TOGETHER over one prefix commit
their pages afterwards.  The cache is therefore asked again before every
prefill chunk, from chain digests each request hashes once.
"""

import random
import sys
import threading
import time

import jax
import pytest

from megatron_llm_tpu import tracing
from megatron_llm_tpu.models.llama import LlamaModel, llama_config
from megatron_llm_tpu.serving import (
    BlockManager,
    EngineConfig,
    InferenceEngine,
    SamplingParams,
    chain_block_digests,
)
from megatron_llm_tpu.serving import kv_blocks
from megatron_llm_tpu.serving.kv_blocks import (
    GARBAGE_BLOCK,
    TokenChain,
    WindowGroup,
)
from megatron_llm_tpu.serving.request import Request

BS = 4
DOC = list(range(1, 23))                # 22 tokens: 5 full blocks + 2


def _bm(num_blocks=33, num_slots=4, **kw):
    kw.setdefault("prefix_cache", True)
    return BlockManager(num_blocks=num_blocks, block_size=BS,
                        num_slots=num_slots, max_blocks_per_slot=8, **kw)


def _two_admitted_together(bm, prompt=DOC, total=24):
    """Two slots over one prompt, neither finding anything at admission;
    the first then writes and commits the whole prompt."""
    s0 = bm.alloc(total, prompt_tokens=prompt)
    s1 = bm.alloc(total, prompt_tokens=prompt)
    assert bm.slot_cached_tokens(s0) == bm.slot_cached_tokens(s1) == 0
    bm.commit_prefix(s0, prompt, n_written=len(prompt))
    return s0, s1


# ---------------------------------------------------------------------------
# the chain a request carries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_tokens", [3, 4, 9, 16, 23])
def test_incremental_chain_equals_the_chain_hashed_at_once(n_tokens):
    toks = [(7 * i + 3) % 50 for i in range(n_tokens)]
    full = n_tokens // BS
    whole = chain_block_digests(toks, BS, full)
    assert len(whole) == full
    for have in range(full + 1):
        assert chain_block_digests(toks, BS, full, whole[:have]) == whole
    # a chain longer than asked for is cut, not extended
    assert chain_block_digests(toks, BS, max(full - 1, 0), whole) \
        == whole[:max(full - 1, 0)]


def test_token_chain_hashes_each_block_once_and_extends(monkeypatch):
    calls = []
    real = kv_blocks.digest_link
    monkeypatch.setattr(kv_blocks, "digest_link",
                        lambda prev, payload: calls.append(1) or
                        real(prev, payload))
    toks = list(range(1, 14))
    chain = TokenChain(toks)
    assert len(chain) == 13
    assert chain.digests(BS, 2) == chain_block_digests(toks, BS, 2)
    n = len(calls)
    assert chain.digests(BS, 1) == chain.digests(BS, 2)[:1]
    assert chain.digests(BS, 2) == chain.digests(BS, 2)
    assert len(calls) == n == 4         # 2 for the chain, 2 for the check
    toks.extend(range(50, 57))          # the context grows at its end
    assert len(chain) == 20
    got = chain.digests(BS, 5)
    assert len(calls) == n + 3          # blocks 2, 3, 4 alone
    del calls[:]
    assert got == chain_block_digests(toks, BS, 5)
    # another block size is another chain
    assert chain.digests(2, 3) == chain_block_digests(toks, 2, 3)


def test_a_chain_asked_from_many_threads_while_it_grows():
    """Whoever registers or releases a request's pages extends its
    chain: the digests are the sequence's own whatever the
    interleaving."""
    toks = list(range(1, 5))
    chain = TokenChain(toks)
    stop = threading.Event()
    wrong = []

    def ask():
        while not stop.is_set():
            n = len(chain) // BS
            got = chain.digests(BS, n)
            if got != chain_block_digests(toks, BS, n):
                wrong.append(n)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    workers = [threading.Thread(target=ask) for _ in range(16)]
    try:
        for w in workers:
            w.start()
        deadline = time.monotonic() + 1.0
        while len(toks) < 2000 and time.monotonic() < deadline:
            toks.append(len(toks) % 61)
    finally:
        stop.set()
        for w in workers:
            w.join(timeout=30)
        sys.setswitchinterval(old)
    assert not any(w.is_alive() for w in workers)
    assert not wrong
    assert chain.digests(BS, len(toks) // BS) \
        == chain_block_digests(toks, BS, len(toks) // BS)


def test_a_request_carries_its_chain_over_prompt_and_answer():
    req = Request(list(range(1, 8)), SamplingParams(max_new_tokens=4))
    assert len(req.context_tokens()) == len(req.chain) == 7
    d1 = req.chain.digests(BS, 1)
    req._emit_token(40)
    assert len(req.context_tokens()) == len(req.chain) == 8
    assert req.chain.digests(BS, 2) == chain_block_digests(
        list(range(1, 8)) + [40], BS, 2)
    assert req.chain.digests(BS, 2)[:1] == d1


# ---------------------------------------------------------------------------
# block manager: adoption
# ---------------------------------------------------------------------------

def test_adoption_repoints_the_slot_and_returns_its_own_blocks():
    bm = _bm()
    s0, s1 = _two_admitted_together(bm)
    own = list(bm._slot_blocks[s1])
    free_before = bm.stats()["blocks_free"]
    hits = bm.stats()["prefix_cache_hits"]
    assert bm.adopt_committed(s1, DOC, 0) == 20       # 5 blocks, tail left
    assert bm._slot_blocks[s1][:5] == bm._slot_blocks[s0][:5]
    assert bm.tables[s1][:5].tolist() == bm.tables[s0][:5].tolist()
    assert bm._slot_blocks[s1][5] == own[5]           # the tail stays its own
    assert bm.tables[s1][5] == own[5]
    assert (bm.tables[s1][6:] == GARBAGE_BLOCK).all()
    for b in bm._slot_blocks[s0][:5]:
        assert bm._refcounts[b] == 2
    # its own five unwritten blocks are on the free list again
    assert bm.stats()["blocks_free"] == free_before + 5
    assert set(own[:5]) <= set(bm._free_blocks)
    assert not set(own[:5]) & set(bm._refcounts)
    # admission's accounting
    assert bm.slot_cached_tokens(s1) == 20
    st = bm.stats()
    assert st["prefix_cache_hits"] == hits + 5
    assert st["prefix_cache_hit_tokens"] == 20
    assert bm.cache_stats()["adopted_at_prefill"] == 5
    bm.check_invariants()
    # asked again, nothing is left to adopt
    assert bm.adopt_committed(s1, DOC, 20) == 0
    bm.free(s0, token_ids=DOC, n_written=22)
    bm.check_invariants()
    for b in bm._slot_blocks[s1][:5]:
        assert bm._refcounts[b] == 1                  # s1 keeps them alive
    bm.free(s1, token_ids=DOC, n_written=22)
    bm.check_invariants()
    assert bm.stats()["blocks_in_use"] == 0


def test_a_parked_block_leaves_the_lru_when_adopted():
    bm = _bm()
    s0, s1 = _two_admitted_together(bm)
    bm.free(s0, token_ids=DOC, n_written=22)          # five pages parked
    assert bm.stats()["blocks_cached_reusable"] == 5
    assert bm.adopt_committed(s1, DOC, 0) == 20
    assert bm.stats()["blocks_cached_reusable"] == 0
    for b in bm._slot_blocks[s1][:5]:
        assert b not in bm._lru and bm._refcounts[b] == 1
    bm.check_invariants()
    bm.free(s1)
    bm.check_invariants()
    assert bm.stats()["blocks_cached_reusable"] == 5  # parked again


@pytest.mark.parametrize("n_tokens,adopted", [(8, 4), (9, 8), (5, 4),
                                              (4, 0), (3, 0)])
def test_the_cap_leaves_one_token_to_compute(n_tokens, adopted):
    """A prompt that is committed whole is adopted up to the block that
    holds its last token, as at admission."""
    bm = _bm()
    prompt = list(range(1, n_tokens + 1))
    s0, s1 = _two_admitted_together(bm, prompt, total=12)
    assert bm.adopt_committed(s1, prompt, 0) == adopted
    assert bm.slot_cached_tokens(s1) == adopted
    assert adopted < n_tokens
    bm.check_invariants()


@pytest.mark.parametrize("n_written", [1, 2, 3, 5, 6, 7])
def test_nothing_is_adopted_off_a_block_boundary(n_written):
    bm = _bm()
    s0, s1 = _two_admitted_together(bm)
    before = list(bm._slot_blocks[s1])
    assert bm.adopt_committed(s1, DOC, n_written) == 0
    assert bm._slot_blocks[s1] == before
    bm.check_invariants()


def test_adoption_follows_the_other_request_chunk_by_chunk():
    """Asked before every chunk: what the sibling has committed by then,
    from the block prefill has reached, up to the first digest missing."""
    bm = _bm()
    s0 = bm.alloc(24, prompt_tokens=DOC)
    s1 = bm.alloc(24, prompt_tokens=DOC)
    assert bm.adopt_committed(s1, DOC, 0) == 0        # nothing committed yet
    bm.commit_prefix(s0, DOC, n_written=9)            # two full blocks
    assert bm.adopt_committed(s1, DOC, 0) == 8
    assert bm.adopt_committed(s1, DOC, 8) == 0        # the third is not there
    bm.commit_prefix(s0, DOC, n_written=22)
    # s1 computed block 2 itself meanwhile: the walk starts behind it
    bm.commit_prefix(s1, DOC, n_written=12)
    assert bm.adopt_committed(s1, DOC, 12) == 8       # blocks 3 and 4
    assert bm.slot_cached_tokens(s1) == 16
    assert bm._slot_blocks[s1][2] != bm._slot_blocks[s0][2]   # its own copy
    assert bm._slot_blocks[s1][3:5] == bm._slot_blocks[s0][3:5]
    bm.check_invariants()
    bm.free(s1, token_ids=DOC, n_written=22)
    bm.free(s0, token_ids=DOC, n_written=22)
    bm.check_invariants()


def test_a_longer_question_adopts_the_shorter_ones_document():
    """A member's length is its own draw: the later question extends the
    document and computes what lies beyond the earlier one."""
    bm = _bm()
    long = DOC + list(range(60, 70))                  # 32 tokens
    s0 = bm.alloc(24, prompt_tokens=DOC)
    s1 = bm.alloc(32, prompt_tokens=long)
    bm.commit_prefix(s0, DOC, n_written=22)
    assert bm.adopt_committed(s1, long, 0) == 20
    bm.check_invariants()
    # and a shorter one adopts a cut of it, one token left
    s2 = bm.alloc(16, prompt_tokens=DOC[:12])
    assert bm.slot_cached_tokens(s2) == 8             # admission's own hit
    assert bm.adopt_committed(s2, DOC[:12], 8) == 0   # the cap
    bm.check_invariants()


def test_a_slot_does_not_adopt_its_own_committed_blocks():
    bm = _bm()
    s0 = bm.alloc(24, prompt_tokens=DOC)
    bm.commit_prefix(s0, DOC, n_written=22)
    before = list(bm._slot_blocks[s0])
    assert bm.adopt_committed(s0, DOC, 0) == 0
    assert bm._slot_blocks[s0] == before
    bm.check_invariants()


@pytest.mark.parametrize("how", ["flag_off", "window_group", "state_space"])
def test_adoption_is_a_no_op_where_admission_matches_nothing(how):
    kw = {"prefix_cache": False}
    if how == "window_group":
        kw["window"] = WindowGroup(33, BS, 4, 8, window=8, bound=5)
    if how == "state_space":
        kw["state_bytes_per_slot"] = 1024
    bm = _bm(**kw)
    s0, s1 = _two_admitted_together(bm)
    before = list(bm._slot_blocks[s1])
    assert bm.adopt_committed(s1, DOC, 0) == 0
    assert bm._slot_blocks[s1] == before
    assert bm.stats()["prefix_cache_hits"] == 0
    bm.check_invariants()


def test_with_the_cache_off_no_digest_is_computed(monkeypatch):
    monkeypatch.setattr(kv_blocks, "digest_link", lambda *a: 1 / 0)
    bm = _bm(prefix_cache=False)
    req = Request(DOC, SamplingParams(max_new_tokens=2))
    s = bm.alloc(24, prompt_tokens=req.chain)
    bm.commit_prefix(s, req.chain, n_written=22)
    assert bm.adopt_committed(s, req.chain, 0) == 0
    bm.free(s, token_ids=req.chain, n_written=22)
    bm.check_invariants()


def test_the_ghost_tiers_adopt_what_they_hold():
    """Every capacity adopts from its own cache: here all hold the
    document, so each gives up five private blocks for references."""
    bm = _bm(num_blocks=17)
    s0, s1 = _two_admitted_together(bm)
    assert bm.adopt_committed(s1, DOC, 0) == 20
    cache = bm.cache_stats()
    assert cache["adopted_at_prefill"] == 5
    assert cache["hits"] == 5 and cache["hit_tokens"] == 20
    for tier in cache["ghost"].values():
        assert tier["adopted_at_prefill"] == 5
        assert tier["hits"] == 5 and tier["hit_tokens"] == 20
    assert cache["heat_top"][0]["hits"] == 1
    assert cache["heat_top"][0]["peak_refcount"] == 2
    bm.check_invariants()
    bm.free(s0, token_ids=DOC, n_written=22)
    bm.free(s1, token_ids=DOC, n_written=22)
    bm.check_invariants()


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_invariants_hold_under_churn_with_adoption(seed):
    """Requests over a few documents admitted in bursts, prefilled in
    chunks with the cache asked before each, released in any order."""
    rng = random.Random(seed)
    bm = _bm(num_blocks=41, num_slots=6)
    docs = [[rng.randrange(1, 50) for _ in range(28)] for _ in range(3)]
    live = {}                           # slot -> [prompt, pos]
    adopted = 0
    for _ in range(300):
        op = rng.random()
        if op < 0.3 and len(live) < 6:
            doc = rng.choice(docs)
            prompt = doc[:rng.randrange(5, 29)]
            try:
                s = bm.alloc(len(prompt) + 4, prompt_tokens=prompt)
            except kv_blocks.NoCapacity:
                continue
            live[s] = [prompt, bm.slot_cached_tokens(s)]
        elif op < 0.85 and live:
            s = rng.choice(list(live))
            prompt, pos = live[s]
            if pos >= len(prompt):
                continue
            got = bm.adopt_committed(s, prompt, pos)
            adopted += got
            pos += got
            assert pos < len(prompt)    # a token is always left
            end = min(pos + rng.choice((4, 6, 8)), len(prompt))
            for bi in range(pos // BS, (end - 1) // BS + 1):
                assert bm.ensure_writable(s, bi) is None
            pos = end
            bm.commit_prefix(s, prompt, pos)
            live[s][1] = pos
        elif live:
            s = rng.choice(list(live))
            prompt, pos = live.pop(s)
            bm.free(s, token_ids=prompt, n_written=pos)
        bm.check_invariants()
    for s, (prompt, pos) in list(live.items()):
        bm.free(s, token_ids=prompt, n_written=pos)
    bm.check_invariants()
    assert adopted > 0
    assert bm.cache_stats()["adopted_at_prefill"] * BS == adopted
    assert bm.stats()["blocks_in_use"] == 0


# ---------------------------------------------------------------------------
# scheduler: a refusal stands until the pool can answer otherwise
# ---------------------------------------------------------------------------

def _scheduler(**kw):
    from megatron_llm_tpu.serving.request import RequestQueue
    from megatron_llm_tpu.serving.scheduler import Scheduler
    bm = _bm(num_blocks=9, **kw)                      # 8 usable blocks
    sched = Scheduler(RequestQueue(8), bm, max_model_len=32)
    allocs = []
    real = bm.alloc
    bm.alloc = lambda *a, **k: allocs.append(1) or real(*a, **k)
    return sched, bm, allocs


def _req(tokens, n_new=2):
    return Request(tokens, SamplingParams(max_new_tokens=n_new))


@pytest.mark.parametrize("prefix_cache", [True, False])
def test_a_refused_head_is_asked_again_only_when_blocks_come_back(
        prefix_cache):
    sched, bm, allocs = _scheduler(prefix_cache=prefix_cache)
    a, b = _req(DOC), _req([t + 100 for t in DOC])    # 6 blocks each
    sched.queue.put_many([a, b])
    assert sched.admit() == [a]
    assert len(allocs) == 2                           # a granted, b refused
    for _ in range(10):
        assert sched.admit() == []
    assert len(allocs) == 2                           # the refusal stood
    # a commit of pages b does not want changes nothing for b
    bm.commit_prefix(a.slot, a.chain, 22)
    assert sched.admit() == [] and len(allocs) == 2
    sched.evict(a, token_ids=a.chain, n_written=22)   # blocks come back
    assert sched.admit() == [b]
    assert len(allocs) == 3
    bm.check_invariants()


def test_a_refused_head_is_asked_again_when_its_prefix_is_committed():
    """The head shares the running request's document: each page that
    request commits shortens what the head needs fresh."""
    sched, bm, allocs = _scheduler()
    a, b = _req(DOC), _req(DOC[:21])                  # 6 blocks each
    sched.queue.put_many([a, b])
    assert sched.admit() == [a]
    assert sched.admit() == [] and len(allocs) == 2
    bm.commit_prefix(a.slot, a.chain, 8)              # 2 of b's 5: 4 > 2
    assert sched.admit() == [] and len(allocs) == 3   # asked, refused
    assert sched.admit() == [] and len(allocs) == 3   # and that one stands
    bm.commit_prefix(a.slot, a.chain, 16)             # 4 of 5: 2 fresh fit
    assert sched.admit() == [b] and len(allocs) == 4
    assert b.cached_prompt_tokens == 16
    bm.check_invariants()


def test_another_head_is_asked_at_once():
    """The memory is of one request: when the refused head leaves the
    queue (its deadline) the next one is asked."""
    sched, bm, allocs = _scheduler()
    a = _req(DOC)
    b = Request([t + 100 for t in DOC], SamplingParams(max_new_tokens=2),
                deadline_secs=0.2)
    c = _req([7, 8, 9], n_new=1)                      # one block: it fits
    sched.queue.put_many([a, b, c])
    assert sched.admit() == [a]                       # b refused
    assert sched.admit() == []
    time.sleep(0.3)
    assert sched.admit() == [c]                       # b expired, c asked
    assert b.finish_reason == "deadline"
    bm.check_invariants()


# ---------------------------------------------------------------------------
# engine (tiny model, stepped by hand)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model_and_params():
    cfg = llama_config("tiny", num_layers=2, seq_length=64,
                       max_position_embeddings=64, padded_vocab_size=64,
                       use_flash_attn=False)
    model = LlamaModel(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model_and_params, prefix_cache, **kw):
    model, params = model_and_params
    eng = InferenceEngine(model, params, EngineConfig(
        num_slots=4, block_size=8, prefill_chunk=16, max_model_len=64,
        max_queue_depth=32, default_deadline_secs=0.0,
        prefix_cache=prefix_cache, **kw))
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def eng_off(model_and_params):
    return _engine(model_and_params, False)


GREEDY = dict(temperature=0.0, eod_id=63)
DOCUMENT = [(5 * i + 2) % 60 + 1 for i in range(56)]


def _run_together(eng, prompts, n=6):
    """Submit every prompt, then step the engine until all are done."""
    before = eng.stats()
    reqs = [eng.submit(p, SamplingParams(max_new_tokens=n, **GREEDY))
            for p in prompts]
    for _ in range(2000):
        if not eng.step():
            break
    assert all(r.finish_reason in ("length", "stop") for r in reqs)
    after = eng.stats()
    since = {k: after[k] - before[k] for k in (
        "prefill_tokens_computed", "prefill_tokens_cached",
        "prefill_tokens_cached_at_prefill", "prefill_tokens_submitted")}
    return reqs, since


@pytest.mark.parametrize("n_requests", [2, 4])
def test_requests_submitted_together_compute_one_prompt(
        model_and_params, eng_off, n_requests):
    """One prompt n times at once: admission finds nothing for any (none
    has committed a page), the first computes the prompt, each later one
    adopts it when its prefill begins and computes the one token left."""
    eng = _engine(model_and_params, True)
    prompt = DOCUMENT[:33]                       # 4 blocks of 8 and a token
    reqs, since = _run_together(eng, [prompt] * n_requests)
    base = _run_together(eng_off, [prompt])[0][0].tokens
    for r in reqs:
        assert r.tokens == base                  # token for token
    assert [r.cached_prompt_tokens for r in reqs] \
        == [0] + [32] * (n_requests - 1)
    assert since["prefill_tokens_computed"] == 33 + (n_requests - 1)
    assert since["prefill_tokens_cached_at_prefill"] \
        == since["prefill_tokens_cached"] == 32 * (n_requests - 1)
    # the harness's counters_account_for, exactly
    assert since["prefill_tokens_computed"] + since["prefill_tokens_cached"] \
        == since["prefill_tokens_submitted"] == 33 * n_requests
    assert eng.stats()["prefix_cache_hit_tokens"] == 32 * (n_requests - 1)
    eng._st.blocks.check_invariants()
    assert eng._st.blocks.stats()["blocks_in_use"] == 0


def test_a_documents_questions_telescope_to_its_longest_member(
        model_and_params, eng_off):
    """Members of one document cut or extended to their own lengths,
    admitted together: each computes what lies beyond the longest before
    it, from a block boundary."""
    eng = _engine(model_and_params, True)
    lengths = [40, 24, 56, 41]
    prompts = [DOCUMENT[:n] for n in lengths]
    reqs, since = _run_together(eng, prompts)
    for r, p in zip(reqs, prompts):
        assert r.tokens == _run_together(eng_off, [p])[0][0].tokens
    # 40 computed; 24 adopts 16 (its cap) of them; 56 adopts 40;
    # 41 adopts 40
    assert [r.cached_prompt_tokens for r in reqs] == [0, 16, 40, 40]
    assert since["prefill_tokens_computed"] == 40 + 8 + 16 + 1
    assert since["prefill_tokens_cached_at_prefill"] == 96
    assert since["prefill_tokens_computed"] + since["prefill_tokens_cached"] \
        == sum(lengths)
    eng._st.blocks.check_invariants()


def test_admissions_part_and_prefills_part_are_told_apart(model_and_params):
    """A request that arrives after the document is cached hits at
    admission; one admitted beside its writer hits at prefill.  The
    instants, the chunk records and stats() say which."""
    eng = _engine(model_and_params, True)
    tracer = tracing.SpanTracer()
    tracing.install_tracing(tracing.Tracing(tracer=tracer))
    try:
        prompt = DOCUMENT[:33]
        together, since = _run_together(eng, [prompt, prompt])
        (later,), since_later = _run_together(eng, [prompt])
    finally:
        tracing.install_tracing(None)
    assert since["prefill_tokens_cached_at_prefill"] == 32
    assert since_later["prefill_tokens_cached"] == 32
    assert since_later["prefill_tokens_cached_at_prefill"] == 0
    assert later.cached_prompt_tokens == 32
    hits = [e["args"] for e in tracer.chrome_trace()["traceEvents"]
            if e.get("name") == "prefix_cache_hit"]
    assert [(h["request"], h["at"], h["tokens"]) for h in hits] == [
        (together[1].id, "prefill", 32), (later.id, "admission", 32)]
    # the chunk that follows an adoption starts behind it and says so
    chunks = [r for r in eng.loop_profiler.records()
              if r.kind == "prefill" and together[1].id in r.requests]
    assert [(c.start, c.valid, c.cached_tokens) for c in chunks] \
        == [(32, 1, 32)]


@pytest.mark.parametrize("prefix_cache", [True, False])
@pytest.mark.parametrize("n_prompt", [33, 50])
def test_each_block_of_a_request_is_hashed_once(
        model_and_params, monkeypatch, prefix_cache, n_prompt):
    """Ten refused admission retries, a whole prefill in chunks with a
    commit after each, decode and the release's registration: one
    ``digest_link`` a block of each request's context, none with the
    cache off."""
    eng = _engine(model_and_params, prefix_cache, num_blocks=9)
    calls = []
    real = kv_blocks.digest_link
    monkeypatch.setattr(kv_blocks, "digest_link",
                        lambda prev, payload: calls.append(1) or
                        real(prev, payload))
    sp = SamplingParams(max_new_tokens=6, **GREEDY)
    a = eng.submit(DOCUMENT[:n_prompt], sp)
    b = eng.submit([61 - t for t in DOCUMENT[:n_prompt]], sp)
    assert eng.step()                    # a is admitted, b finds no room
    sched = eng._st.scheduler
    assert a.slot is not None and b.slot is None
    n = len(calls)
    for _ in range(10):
        assert sched.admit() == []
    assert len(calls) == n               # a refused head is looked up
    for _ in range(2000):
        if not eng.step():
            break
    assert a.finish_reason == b.finish_reason == "length"
    # registered at release: the tokens with keys and values written
    blocks = sum((len(r.tokens) - 1) // 8 for r in (a, b))
    assert len(calls) == (blocks if prefix_cache else 0)
    eng._st.blocks.check_invariants()
