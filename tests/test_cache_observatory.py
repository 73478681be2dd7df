"""Cache observatory (serving/cache_observatory.py).

The load-bearing test is the ghost oracle: a churny operation trace is
recorded against a 1x BlockManager (whose observatory simulates 2x/4x
ghost tiers synchronously), then the SAME trace is replayed against a
real BlockManager with 2x (resp. 4x) the usable blocks — the ghost's
hit/hit-token/eviction counters must equal the real big manager's
lifetime counters EXACTLY.  The ghost is not an estimate.

Also covered: per-prefix heat attribution + salted-key privacy,
eviction forensics (capacity vs churn) and the evicted-then-wanted
regret counter, heat-table bounding, fleet heat merge, the periodic
cache_stats emission cadence, and the <2% dispatch-overhead gate
(slow tier).
"""

import json
import random
import time

import pytest

from megatron_llm_tpu import telemetry
from megatron_llm_tpu.serving import BlockManager, merge_heat_tops
from megatron_llm_tpu.serving.cache_observatory import (
    CacheObservatory,
    _GhostTier,
)

BS = 4


def _bm(num_blocks=13, num_slots=3, **kw):
    kw.setdefault("prefix_cache", True)
    return BlockManager(num_blocks=num_blocks, block_size=BS,
                        num_slots=num_slots, max_blocks_per_slot=8, **kw)


# ---------------------------------------------------------------------------
# the ghost oracle: ghost xN counters == a real Nx manager, exactly
# ---------------------------------------------------------------------------

def _record_trace(steps=600, seed=7, num_blocks=13, num_slots=3):
    """Drive a 1x manager with random churn, recording every operation.
    Allocations are pre-gated on a conservative fit test so the trace
    never raises NoCapacity — a failed alloc counts match probes but
    admits nothing, which a replay cannot reproduce op-for-op."""
    rng = random.Random(seed)
    bm = _bm(num_blocks=num_blocks, num_slots=num_slots)
    prompts = [[rng.randrange(1, 6) for _ in range(rng.randrange(3, 17))]
               for _ in range(6)]
    trace = []
    live = {}
    for _ in range(steps):
        op = rng.random()
        if op < 0.45 and len(live) < num_slots:
            p = rng.choice(prompts)
            total = len(p) + rng.randrange(1, 8)
            st = bm.stats()
            if bm.blocks_needed(total) > (st["blocks_free"]
                                          + st["blocks_cached_reusable"]):
                continue                        # would raise NoCapacity
            s = bm.alloc(total, prompt_tokens=p)
            trace.append(("alloc", s, total, p))
            live[s] = (p, bm.slot_cached_tokens(s))
        elif op < 0.65 and live:
            s = rng.choice(list(live))
            p, cached = live[s]
            n_written = rng.randrange(cached, len(p) + 1)
            bm.commit_prefix(s, p, n_written)
            trace.append(("commit", s, p, n_written))
        elif op < 0.8 and live:
            s = rng.choice(list(live))
            p, _ = live[s]
            idx = rng.randrange(0, bm.blocks_needed(len(p)))
            bm.ensure_writable(s, idx)
            trace.append(("cow", s, idx))
        elif live:
            s = rng.choice(list(live))
            p, _ = live[s]
            n_written = rng.randrange(0, len(p) + 1)
            bm.free(s, token_ids=p, n_written=n_written)
            trace.append(("free", s, p, n_written))
            del live[s]
        bm.check_invariants()
    for s, (p, _) in list(live.items()):
        bm.free(s, token_ids=p, n_written=len(p))
        trace.append(("free", s, p, len(p)))
    bm.check_invariants()
    return bm, trace


def _replay(trace, mult, num_blocks=13, num_slots=3):
    """Apply a recorded trace to a real manager with ``mult`` times the
    usable blocks.  Slot ids are remapped (the big manager hands out
    its own)."""
    big = _bm(num_blocks=mult * (num_blocks - 1) + 1, num_slots=num_slots)
    slot_map = {}
    for rec in trace:
        if rec[0] == "alloc":
            _, s, total, p = rec
            slot_map[s] = big.alloc(total, prompt_tokens=p)
        elif rec[0] == "commit":
            _, s, p, n_written = rec
            big.commit_prefix(slot_map[s], p, n_written)
        elif rec[0] == "cow":
            _, s, idx = rec
            big.ensure_writable(slot_map[s], idx)
        else:
            _, s, p, n_written = rec
            big.free(slot_map.pop(s), token_ids=p, n_written=n_written)
        big.check_invariants()
    return big


@pytest.mark.parametrize("mult", [2, 4])
def test_ghost_oracle_exact_vs_real_big_manager(mult):
    """Acceptance: ghost x2 (x4) hit counters equal a REAL 2x (4x)
    BlockManager's lifetime counters on the same operation trace —
    exact equality, not approximation."""
    bm, trace = _record_trace()
    assert any(r[0] == "cow" for r in trace)     # the hard cases ran
    assert bm.stats()["prefix_cache_evictions"] > 0
    ghost = bm.cache_stats()["ghost"][f"x{mult}"]
    big = _replay(trace, mult)
    st = big.stats()
    assert ghost["hits"] == st["prefix_cache_hits"]
    assert ghost["hit_tokens"] == st["prefix_cache_hit_tokens"]
    assert ghost["evictions"] == st["prefix_cache_evictions"]
    # a bigger pool can only help on this trace
    assert ghost["hits"] >= bm.stats()["prefix_cache_hits"]


def test_ghost_oracle_many_seeds():
    """The x2 oracle across a spread of churn seeds — guards against a
    single-seed fluke hiding an economy-rule mismatch."""
    for seed in (0, 1, 2, 3, 11):
        bm, trace = _record_trace(steps=300, seed=seed)
        ghost = bm.cache_stats()["ghost"]["x2"]
        st = _replay(trace, 2).stats()
        assert ghost["hits"] == st["prefix_cache_hits"], f"seed {seed}"
        assert ghost["evictions"] == st["prefix_cache_evictions"], \
            f"seed {seed}"


# ---------------------------------------------------------------------------
# heat attribution + privacy
# ---------------------------------------------------------------------------

def test_heat_attribution_and_salted_privacy(monkeypatch):
    monkeypatch.setenv("MEGATRON_CACHE_SALT", "fleet-salt")
    bm = _bm(num_blocks=33)
    hot = list(range(1, 10))                     # 2 full blocks
    cold = list(range(21, 30))
    s = bm.alloc(16, prompt_tokens=hot)
    bm.commit_prefix(s, hot, n_written=9)
    bm.free(s, token_ids=hot, n_written=9)
    for _ in range(3):                           # 3 warm hits on `hot`
        s = bm.alloc(16, prompt_tokens=hot)
        bm.free(s, token_ids=hot, n_written=9)
    s = bm.alloc(16, prompt_tokens=cold)
    bm.free(s, token_ids=cold, n_written=9)
    stats = bm.cache_stats()
    top = stats["heat_top"]
    assert top and top[0]["hits"] == 3           # hottest first
    # heat entries are per BLOCK digest: 3 warm allocs x one block each
    assert top[0]["hit_tokens"] == 3 * BS
    assert top[0]["peak_refcount"] >= 1
    assert "last_access_age" in top[0]
    # privacy: keys are 16-hex-char salted digests; no token ids, no
    # raw chain digests anywhere in the exported record
    dumped = json.dumps(stats)
    for e in top:
        assert len(e["prefix"]) == 16 and int(e["prefix"], 16) >= 0
    assert "token" not in dumped.replace("hit_tokens", "")
    # same salt => same keyspace (fleet-mergeable); different salt
    # => unlinkable keys for the same digest
    obs_a = CacheObservatory(8, BS, salt=b"a")
    obs_b = CacheObservatory(8, BS, salt=b"b")
    obs_fleet = CacheObservatory(8, BS)          # env salt
    d = b"\x01" * 16
    assert obs_a.salted_key(d) != obs_b.salted_key(d)
    assert obs_fleet.salted_key(d) == CacheObservatory(4, BS).salted_key(d)


def test_heat_table_bounded_evicts_coldest():
    obs = CacheObservatory(8, BS, heat_cap=4)
    digests = [bytes([i]) * 16 for i in range(8)]
    for i, d in enumerate(digests):
        # touch digest i (i+1) times so later digests are hotter
        obs.record_match([d], 1)
        for _ in range(i):
            obs.record_match([d], 1)
    assert len(obs.heat_top(k=100)) == 4
    st = obs.stats()
    assert st["heat_entries"] == 4
    assert st["heat_evicted"] == 4
    # the survivors are the hottest tail
    keys = {e["prefix"] for e in obs.heat_top(k=100)}
    assert keys == {obs.salted_key(d) for d in digests[-4:]}


# ---------------------------------------------------------------------------
# eviction forensics + regret
# ---------------------------------------------------------------------------

def test_eviction_forensics_churn_and_regret():
    """One-shot prefixes cycling an idle pool are churn evictions; a
    re-request of an evicted prefix is a miss_evicted (regret), not a
    cold miss."""
    bm = _bm(num_blocks=9, num_slots=2)          # 8 usable blocks
    pa = list(range(1, 9))                       # 2 full blocks each
    pb = list(range(11, 19))
    pc = list(range(21, 29))
    for p in (pa, pb, pc):
        s = bm.alloc(8, prompt_tokens=p)
        bm.commit_prefix(s, p, n_written=8)
        bm.free(s, token_ids=p, n_written=8)
    # 6 of 8 blocks parked; demand 8 fresh -> evicts pa (LRU oldest)
    s = bm.alloc(32, prompt_tokens=list(range(90, 98)))
    st = bm.cache_stats()
    assert st["evictions_churn"] >= 2            # parked pages dominated
    bm.free(s)
    # want pa again: the miss is classified as regret (the match cap
    # probes (8-1)//4 = 1 block of the 2-block chain)
    s = bm.alloc(8, prompt_tokens=pa)
    st = bm.cache_stats()
    assert st["miss_evicted"] >= 1
    assert st["miss_cold"] > 0                   # the genuinely new ones
    assert st["miss_cold"] + st["miss_evicted"] == st["misses"]
    bm.free(s, token_ids=pa, n_written=8)
    bm.check_invariants()


def test_eviction_forensics_capacity_reason():
    """Evictions while live refcounted blocks dominate the pool are
    capacity evictions — the pool is genuinely too small."""
    bm = _bm(num_blocks=9, num_slots=3)
    pa = list(range(1, 9))
    s0 = bm.alloc(8, prompt_tokens=pa)
    bm.commit_prefix(s0, pa, n_written=8)
    pb = list(range(11, 19))
    s1 = bm.alloc(8, prompt_tokens=pb)
    bm.commit_prefix(s1, pb, n_written=8)
    bm.free(s1, token_ids=pb, n_written=8)       # 2 parked, 2 live+held
    # 4 free; demand 6 -> evicts pb's pages with live blocks majority
    s2 = bm.alloc(24, prompt_tokens=list(range(41, 47)))
    st = bm.cache_stats()
    assert st["evictions_capacity"] >= 2
    bm.free(s0, token_ids=pa, n_written=8)
    bm.free(s2)
    bm.check_invariants()


def test_slot_miss_causes_feed_request_records():
    bm = _bm(num_blocks=33)
    p = list(range(1, 14))                       # 3 full blocks + tail
    s = bm.alloc(16, prompt_tokens=p)
    assert bm.slot_miss_causes(s) == (3, 0)      # all cold
    bm.commit_prefix(s, p, n_written=13)
    bm.free(s, token_ids=p, n_written=13)
    s = bm.alloc(16, prompt_tokens=p)
    assert bm.slot_miss_causes(s) == (0, 0)      # warm
    bm.free(s, token_ids=p, n_written=13)


# ---------------------------------------------------------------------------
# fleet merge
# ---------------------------------------------------------------------------

def test_merge_heat_tops_sums_same_salt_keys():
    a = [{"prefix": "aa", "hits": 5, "hit_tokens": 40, "residency": 2,
          "evictions": 1, "regret": 0, "peak_refcount": 3,
          "last_access_age": 10},
         {"prefix": "bb", "hits": 2, "hit_tokens": 16, "residency": 1,
          "evictions": 0, "regret": 1, "peak_refcount": 1,
          "last_access_age": 4}]
    b = [{"prefix": "aa", "hits": 7, "hit_tokens": 56, "residency": 1,
          "evictions": 0, "regret": 2, "peak_refcount": 5,
          "last_access_age": 2}]
    merged = merge_heat_tops([a, b], k=16)
    assert merged[0]["prefix"] == "aa"           # 12 hits, hottest first
    assert merged[0]["hits"] == 12
    assert merged[0]["hit_tokens"] == 96
    assert merged[0]["peak_refcount"] == 5       # max, not sum
    assert merged[0]["last_access_age"] == 2     # most recent wins
    assert merged[0]["regret"] == 2
    assert merged[1]["prefix"] == "bb" and merged[1]["hits"] == 2
    # top-K truncation + junk tolerance
    assert merge_heat_tops([a, b], k=1) == [merged[0]]
    assert merge_heat_tops([None, "x", [{"nope": 1}], a], k=16)[0][
        "prefix"] == "aa"


# ---------------------------------------------------------------------------
# cache_stats emission cadence (schema 11)
# ---------------------------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_cache_stats_emit_cadence(tmp_path):
    clock = _Clock()
    obs = CacheObservatory(8, BS, emit_every_matches=4,
                           emit_interval_secs=15.0, clock=clock)
    stream = telemetry.TelemetryStream(str(tmp_path))
    telemetry.install_stream(stream)
    try:
        d = b"\x02" * 16
        assert obs.maybe_emit() is False         # nothing fresh
        for _ in range(4):
            obs.record_match([d], 0)
        assert obs.maybe_emit() is True          # count cadence
        assert obs.maybe_emit() is False
        obs.record_match([d], 0)
        clock.t += 20.0
        assert obs.maybe_emit() is True          # time cadence, fresh
        clock.t += 20.0
        assert obs.maybe_emit() is False         # time alone, no traffic
        assert obs.maybe_emit(force=True) is True
    finally:
        telemetry.install_stream(None)
        stream.close()
    recs = []
    for f in tmp_path.glob("*.jsonl"):
        with open(f) as fh:
            recs += [json.loads(ln) for ln in fh if ln.strip()]
    cache = [r for r in recs if r.get("event") == "cache_stats"]
    assert len(cache) == 3
    rec = cache[-1]
    assert rec["kind"] == "serve"
    assert rec["schema"] == telemetry.TELEMETRY_SCHEMA_VERSION
    for key in ("probes", "hits", "miss_cold", "miss_evicted",
                "evictions_capacity", "evictions_churn", "heat_top",
                "ghost", "inclusion_divergences"):
        assert key in rec, key
    assert set(rec["ghost"]) == {"x2", "x4", "x10"}


def test_emit_survives_broken_stream(monkeypatch):
    class _Boom:
        def emit(self, rec):
            raise RuntimeError("boom")

    obs = CacheObservatory(8, BS)
    obs.record_match([b"\x03" * 16], 0)
    monkeypatch.setattr(telemetry, "_ACTIVE_STREAM", _Boom())
    assert obs.maybe_emit(force=True) is False   # swallowed, loop lives


def test_pool_reset_keeps_ghost_residency():
    """Engine restart: ghost tiers release every slot but keep parked
    digests resident (a host-RAM tier would survive the restart), and
    the strict-inclusion asserts disarm."""
    obs = CacheObservatory(8, BS, ghost_multiples=(2,))
    d = [b"\x04" * 16, b"\x05" * 16]
    t = obs.record_match(d, 0)
    obs.record_admit(0, t, 3, [])
    obs.record_commit(0, d, ["reg", "reg"])
    obs.on_pool_reset()
    obs.check_invariants()
    assert obs.stats()["pool_resets"] == 1
    tier = obs._tiers[0]
    assert not tier.slots and set(tier.lru) == set(d)
    # next epoch still matches what the tier retained
    assert len(tier.lookup_locked(d)) == 2


# ---------------------------------------------------------------------------
# overhead gate (slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serve_cache_overhead_under_2pct():
    """Per-alloc observatory bookkeeping (match + ghost lookups + admit
    + commit + free across 3 tiers, with a live telemetry stream — the
    worst case) must cost < 2% of a real CPU dispatch of the tiny
    engine.  The observatory may not become the overhead it meters."""
    import jax

    from megatron_llm_tpu.models.llama import LlamaModel, llama_config
    from megatron_llm_tpu.serving import (EngineConfig, InferenceEngine,
                                          SamplingParams)

    # arm A: the real engine under traffic — mean dispatch wall-clock
    cfg = llama_config("tiny", num_layers=2, seq_length=64,
                       max_position_embeddings=64, padded_vocab_size=64,
                       use_flash_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, EngineConfig(
        num_slots=4, block_size=8, prefill_chunk=16, max_model_len=64,
        max_queue_depth=32, default_deadline_secs=0.0))
    eng.warmup()
    eng.start()
    try:
        reqs = [eng.submit([1 + i, 2, 3, 4],
                           SamplingParams(max_new_tokens=12,
                                          temperature=0.0, eod_id=63))
                for i in range(8)]
        for r in reqs:
            r.result(timeout=180)
        loop = eng.stats()["loop"]
    finally:
        eng.stop()
    assert loop["dispatches"] > 0
    mean_dispatch_secs = loop["wall_secs"] / loop["dispatches"]

    # arm B: the observatory alone, one full request lifecycle per
    # iteration (match -> admit -> commit -> free), warm-hit path
    stream = telemetry.TelemetryStream(None)    # no file, worst-case code
    telemetry.install_stream(stream)
    try:
        obs = CacheObservatory(255, 8)
        digests = [bytes([i, 0]) * 8 for i in range(4)]
        tok = obs.record_match(digests, 0)
        obs.record_admit(0, tok, 6, [])
        obs.record_commit(0, digests, ["reg"] * 4)
        obs.record_free(0)
        n = 2000
        t0 = time.perf_counter()
        for _ in range(n):
            tok = obs.record_match(digests, len(digests))
            obs.record_admit(0, tok, 6, [2, 2, 2, 2])
            obs.record_commit(0, digests, ["live"] * 4)
            obs.record_free(0)
            obs.maybe_emit()
        cost_per_alloc = (time.perf_counter() - t0) / n
    finally:
        telemetry.install_stream(None)
        stream.close()
    frac = cost_per_alloc / mean_dispatch_secs
    assert frac < 0.02, (
        f"observatory bookkeeping {cost_per_alloc * 1e6:.1f}us/alloc "
        f"= {frac * 100:.2f}% of a {mean_dispatch_secs * 1e3:.2f}ms "
        f"CPU dispatch (gate: < 2%)")


def test_ghost_tier_unit_economy():
    """Micro-checks on one tier: lookup counts at match time, admit
    adopts, commit registers, release parks in insertion order, a
    take beyond free evicts LRU-oldest."""
    t = _GhostTier(1, 4)
    d = [bytes([i]) * 16 for i in range(3)]
    assert t.lookup_locked(d) == [] and t.misses == 3
    t.admit_locked(0, [], 3, BS)
    assert t.free == 1
    t.commit_locked(0, d)
    assert set(t.table) == set(d)
    t.release_locked(0)                                 # parks d0, d1, d2 (oldest first)
    assert list(t.lru) == d
    assert t.free == 1
    # a 4-block demand: 1 free + evict d0, d1, d2 in LRU order
    m = t.lookup_locked([bytes([9]) * 16])
    t.admit_locked(1, m, 4, BS)
    assert t.evictions == 3 and not t.table and t.free == 0
    assert t.lookup_locked(d) == []                     # the chains are gone
