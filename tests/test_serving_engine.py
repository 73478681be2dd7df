"""Continuous-batching serving engine: block manager, admission queue,
batched sampling, and the engine acceptance properties — single-request
parity with ``generate_and_post_process``, decode co-batching
(occupancy > 1), streaming, deadlines, and zero recompiles after warmup.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu import tracing
from megatron_llm_tpu.models.llama import LlamaModel, llama_config
from megatron_llm_tpu.ops import paged_kv
from megatron_llm_tpu.serving import (
    BlockManager,
    EngineConfig,
    InferenceEngine,
    NoCapacity,
    QueueFull,
    Request,
    RequestQueue,
    SamplingParams,
    derive_num_blocks,
)
from megatron_llm_tpu.serving.drafter import lookup_draft
from megatron_llm_tpu.serving.kv_blocks import GARBAGE_BLOCK
from megatron_llm_tpu.text_generation.api import generate_and_post_process
from megatron_llm_tpu.text_generation.sampling import (
    modify_logits,
    modify_logits_batched,
    sample_batched,
)


# ---------------------------------------------------------------------------
# block manager (pure host-side, no model)
# ---------------------------------------------------------------------------

def test_block_manager_alloc_free_roundtrip():
    bm = BlockManager(num_blocks=9, block_size=4, num_slots=2,
                      max_blocks_per_slot=4)
    s0 = bm.alloc(total_tokens=10)          # 3 blocks
    assert bm.stats()["blocks_in_use"] == 3
    row = bm.tables[s0]
    assert (row[:3] > 0).all()              # real blocks, never the garbage
    assert (row[3:] == GARBAGE_BLOCK).all()
    s1 = bm.alloc(total_tokens=4)           # 1 block
    assert s1 != s0
    with pytest.raises(NoCapacity):         # no slots left
        bm.alloc(total_tokens=4)
    bm.free(s0)
    assert (bm.tables[s0] == GARBAGE_BLOCK).all()
    assert bm.stats()["blocks_in_use"] == 1
    s2 = bm.alloc(total_tokens=16)          # 4 blocks fit again
    assert bm.stats()["slots_in_use"] == 2
    bm.free(s1)
    bm.free(s2)
    end = bm.stats()
    assert end["blocks_total"] == 8
    assert end["blocks_in_use"] == 0
    assert end["blocks_free"] == 8
    assert end["slots_total"] == 2
    assert end["slots_in_use"] == 0


def test_block_manager_block_exhaustion():
    bm = BlockManager(num_blocks=4, block_size=4, num_slots=4,
                      max_blocks_per_slot=4)
    bm.alloc(total_tokens=12)               # 3 of 3 usable blocks
    with pytest.raises(NoCapacity):
        bm.alloc(total_tokens=4)
    # needs more blocks than a slot can ever hold: permanent, not capacity
    with pytest.raises(ValueError):
        bm.alloc(total_tokens=100)


def test_derive_num_blocks():
    # full backing: every slot can hold max_model_len, + garbage block
    assert derive_num_blocks(4, 8, 64) == 4 * 8 + 1
    assert derive_num_blocks(4, 8, 64, requested=10) == 10


def test_request_queue_bounded_and_atomic():
    q = RequestQueue(max_depth=2)
    r = [Request([1], SamplingParams()) for _ in range(3)]
    q.put(r[0])
    with pytest.raises(QueueFull):
        q.put_many([r[1], r[2]])            # atomic: neither admitted
    assert q.depth() == 1
    q.put(r[1])
    with pytest.raises(QueueFull):
        q.put(r[2])
    assert [q.pop().id for _ in range(2)] == [r[0].id, r[1].id]


# ---------------------------------------------------------------------------
# per-slot batched sampling
# ---------------------------------------------------------------------------

def test_modify_logits_batched_matches_scalar_rows():
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(4, 32).astype(np.float32))
    knobs = [(0, 0.0, 1.0), (5, 0.0, 0.7), (0, 0.8, 1.3), (10, 0.5, 0.9)]
    got = modify_logits_batched(
        logits,
        jnp.asarray([k for k, _, _ in knobs], jnp.int32),
        jnp.asarray([p for _, p, _ in knobs], jnp.float32),
        jnp.asarray([t for _, _, t in knobs], jnp.float32))
    for i, (k, p, t) in enumerate(knobs):
        want = modify_logits(logits[i:i + 1], top_k=k, top_p=p,
                             temperature=t)
        np.testing.assert_allclose(np.asarray(got[i:i + 1]),
                                   np.asarray(want), atol=1e-5)


def test_sample_batched_greedy_rows_argmax():
    logits = jnp.asarray([[0.1, 2.0, -1.0], [3.0, 0.0, 1.0]])
    keys = jnp.zeros((2, 2), jnp.uint32)
    # row 0 greedy via temperature 0, row 1 via top_k 1
    out = sample_batched(logits, keys,
                         jnp.asarray([0, 1], jnp.int32),
                         jnp.asarray([0.0, 0.0], jnp.float32),
                         jnp.asarray([0.0, 1.0], jnp.float32),
                         jnp.ones(2, bool))
    assert out.tolist() == [1, 0]


# ---------------------------------------------------------------------------
# engine (tiny model)
# ---------------------------------------------------------------------------

class _FakeTokenizer:
    vocab_size = 64
    eod = 63
    pad = 0

    def tokenize(self, text):
        return [int(t) % 64 for t in text.split()]

    def detokenize(self, ids):
        return " ".join(str(i) for i in ids)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = llama_config("tiny", num_layers=2, seq_length=64,
                       max_position_embeddings=64, padded_vocab_size=64,
                       use_flash_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(scope="module")
def legacy_tokens(model_and_params):
    """Legacy greedy baseline — ALSO compiles the legacy jit programs
    before the recompile test marks steady state."""
    model, params = model_and_params
    _, _, _, tokens = generate_and_post_process(
        model, params, _FakeTokenizer(), ["5 6 7 8 9"],
        tokens_to_generate=12, top_k_sampling=1)
    return tokens[0]


@pytest.fixture(scope="module")
def engine(model_and_params, legacy_tokens):
    model, params = model_and_params
    eng = InferenceEngine(model, params, EngineConfig(
        num_slots=4, block_size=8, prefill_chunk=16, max_model_len=64,
        max_queue_depth=32, default_deadline_secs=0.0))
    eng.warmup()
    eng.start()
    yield eng
    eng.stop()


GREEDY = dict(temperature=0.0, eod_id=63)


def test_engine_parity_with_generate(engine, legacy_tokens):
    """Acceptance: single-request engine response token-identical to
    generate_and_post_process (prompt + generated, stop token
    included)."""
    r = engine.submit(_FakeTokenizer().tokenize("5 6 7 8 9"),
                      SamplingParams(max_new_tokens=12, **GREEDY))
    r.result(timeout=120)
    assert r.tokens == legacy_tokens


def test_engine_cobatching_occupancy_and_isolation(engine):
    """Acceptance: under concurrent load the decode batch runs more than
    one request per step, and co-batching does not change any request's
    tokens (vs running the same prompt alone)."""
    occ0, dec0 = engine.occupancy_sum, engine.decode_steps
    results = [None] * 8

    def client(i):
        r = engine.submit([1 + i, 2, 3, 4],
                          SamplingParams(max_new_tokens=16, **GREEDY))
        results[i] = r.result(timeout=180)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    occ = (engine.occupancy_sum - occ0) / max(engine.decode_steps - dec0, 1)
    assert occ > 1.0, f"no co-batching: mean occupancy {occ}"
    solo = engine.submit([1, 2, 3, 4],
                         SamplingParams(max_new_tokens=16, **GREEDY))
    solo.result(timeout=120)
    assert solo.out_tokens == results[0].out_tokens


def test_engine_seed_determinism(engine):
    sp = SamplingParams(max_new_tokens=8, temperature=0.9, top_k=20,
                        seed=7, eod_id=63)
    a = engine.submit([5, 6, 7], sp).result(timeout=120)
    b = engine.submit([5, 6, 7], sp).result(timeout=120)
    assert a.out_tokens == b.out_tokens


def test_engine_streaming_yields_incremental_chunks(engine):
    """Acceptance: streaming yields per-token events, then a final
    done."""
    r = engine.submit([3, 4, 5], SamplingParams(max_new_tokens=5, **GREEDY),
                      stream=True)
    events = list(r.events(timeout=60))
    kinds = [k for k, _ in events]
    assert kinds[-1] == "done"
    assert kinds[:-1] == ["token"] * (len(events) - 1)
    assert len(events) - 1 == len(r.out_tokens) >= 1


def test_engine_deadline_eviction(engine):
    r = engine.submit([1, 2, 3, 4, 5, 6, 7, 8],
                      SamplingParams(max_new_tokens=32, **GREEDY),
                      deadline_secs=1e-4)
    r.result(timeout=60)
    assert r.finish_reason == "deadline"


def test_engine_rejects_over_length(engine):
    with pytest.raises(ValueError):
        engine.submit(list(range(1, 50)),
                      SamplingParams(max_new_tokens=32, **GREEDY))


def test_engine_admission_control_queue_full(model_and_params):
    model, params = model_and_params
    eng = InferenceEngine(model, params, EngineConfig(
        num_slots=2, block_size=8, prefill_chunk=16, max_model_len=64,
        max_queue_depth=2))
    # engine never started: the queue only fills
    eng.submit([1, 2], SamplingParams(max_new_tokens=4))
    eng.submit([1, 2], SamplingParams(max_new_tokens=4))
    with pytest.raises(QueueFull) as ei:
        eng.submit([1, 2], SamplingParams(max_new_tokens=4))
    assert ei.value.retry_after_secs > 0
    eng.stop()


def test_engine_zero_recompiles_after_warmup(engine, model_and_params,
                                             tmp_path):
    """Acceptance: after warmup, arbitrary traffic (ragged prompt
    lengths, mixed sampling params, churn through slots) triggers ZERO
    XLA compiles — the continuous-batching property the fixed-shape
    step design exists for.  The full observability stack (JSONL stream,
    per-request phase attribution, SLO histograms, and the cache
    observatory's heat/forensics/ghost-tier bookkeeping — prefix
    caching is on by default) runs during the traffic: it is
    host-side-only bookkeeping and must stay free."""
    from megatron_llm_tpu import telemetry
    from megatron_llm_tpu.text_generation_server import ServerMetrics

    tracer = tracing.SpanTracer()
    det = tracing.RecompileDetector(tracer)
    tr = tracing.Tracing(tracer=tracer, recompile=det)
    tracing.install_tracing(tr)
    stream = telemetry.TelemetryStream(str(tmp_path))
    telemetry.install_stream(stream)
    metrics = ServerMetrics()
    engine.request_done_hook = metrics.observe_request_done
    metrics.engine_stats_fn = engine.stats
    # the SLO sentinel rides along: its evaluator is pure host-side
    # arithmetic over metrics snapshots and must also stay compile-free
    from megatron_llm_tpu.serving.alerts import AlertEngine
    sentinel = AlertEngine(metrics_fn=metrics.snapshot)
    metrics.alert_engine = sentinel
    try:
        det.mark_steady()
        reqs = []
        for i in range(10):
            sp = SamplingParams(
                max_new_tokens=3 + (i % 5),
                temperature=0.0 if i % 2 == 0 else 0.8,
                top_k=0 if i % 3 == 0 else 5 + i,
                top_p=0.0 if i % 2 == 0 else 0.9,
                seed=i, eod_id=63)
            reqs.append(engine.submit(list(range(1, 2 + (i % 7))), sp,
                                      trace_id=f"{i:016x}"))
        for r in reqs:
            r.result(timeout=180)
            sentinel.evaluate()     # pump the alert evaluator mid-traffic
        assert det.recompiles == 0, \
            f"{det.recompiles} recompiles after warmup: {list(det.events)}"
        # and no launch of the traffic traced, lowered or loaded anything
        # (the launch ring's compile_secs, from the same ledger)
        served = engine.loop_profiler.records()[-20:]
        assert [r.compile_secs for r in served] == [0.0] * len(served)
        assert sentinel.counters["evaluations"] == 10
        assert not sentinel.snapshot()["firing"]
        # the observability stack saw every request while staying free
        # (results signal before the engine thread finishes retiring the
        # request, so give the last hook call a moment to land)
        for _ in range(100):
            if metrics.histograms["e2e_secs"].count == 10:
                break
            time.sleep(0.05)
        assert metrics.histograms["e2e_secs"].count == 10
        assert metrics.histograms["ttft_secs"].count == 10
        snap = metrics.snapshot()
        assert snap["slo"]["e2e_secs_p95"] > 0
        # the loop profiler tiled dispatch sub-spans onto the trace
        # (category serve_loop), also without costing a compile
        loop_evs = [e for e in tracer.chrome_trace()["traceEvents"]
                    if str(e.get("name", "")).startswith("loop.")]
        assert loop_evs
        assert all(e["cat"] == "serve_loop" for e in loop_evs)
    finally:
        engine.request_done_hook = None
        tracing.install_tracing(None)
        telemetry.install_stream(None)
        stream.close()
    import json as _json
    records = [_json.loads(line) for line in
               (tmp_path / "telemetry.jsonl").read_text().splitlines()]
    done = [r for r in records if r.get("event") == "request_done"]
    assert len(done) == 10
    assert {r["trace_id"] for r in done} == {f"{i:016x}"
                                             for i in range(10)}
    for r in done:
        assert r["phases"]["prefill_secs"] > 0


def test_request_done_schema_golden(engine, tmp_path):
    """Golden record for the serve JSONL contract: bumping the schema or
    the request_done shape must be a conscious act (update this test AND
    the schema history comment in telemetry.py)."""
    from megatron_llm_tpu import telemetry

    assert telemetry.TELEMETRY_SCHEMA_VERSION == 24
    captured = []
    engine.request_done_hook = captured.append
    stream = telemetry.TelemetryStream(str(tmp_path))
    telemetry.install_stream(stream)
    try:
        engine.submit([7, 8, 9], SamplingParams(max_new_tokens=4, **GREEDY),
                      trace_id="aaaabbbbccccdddd").result(timeout=120)
        for _ in range(100):        # retire (and the hook) lands async
            if captured:
                break
            time.sleep(0.05)
    finally:
        engine.request_done_hook = None
        telemetry.install_stream(None)
        stream.close()
    assert len(captured) == 1
    rec = captured[0]
    assert frozenset(rec) == frozenset((
        "kind", "event", "request", "trace_id", "prompt_tokens",
        "cached_prompt_tokens", "prefill_computed_tokens", "new_tokens",
        "decode_tokens", "drafted_tokens", "accepted_tokens",
        "accept_rate", "finish_reason", "ttft_secs", "latency_secs",
        "tpot_secs", "phases", "paged_kernel", "prefill_kernel",
        "queue_depth", "blocks_free", "blocks_in_use",
        "blocks_cached_reusable", "miss_cold_blocks",
        "miss_evicted_blocks", "host_hit_blocks", "swap_in_secs"))
    assert frozenset(rec["phases"]) == frozenset((
        "queue_secs", "admission_secs", "prefill_secs", "decode_secs",
        "stream_write_secs"))
    # the streamed form gains exactly the envelope stamps
    import json as _json
    line = [_json.loads(ln) for ln in
            (tmp_path / "telemetry.jsonl").read_text().splitlines()
            if "request_done" in ln][0]
    assert frozenset(line) == frozenset(rec) | {"schema", "time_unix"}
    assert line["schema"] == telemetry.TELEMETRY_SCHEMA_VERSION


def test_engine_int8_kv_cache_serves(model_and_params):
    model, params = model_and_params
    eng = InferenceEngine(model, params, EngineConfig(
        num_slots=2, block_size=8, prefill_chunk=16, max_model_len=64,
        int8_kv_cache=True))
    eng.warmup()
    eng.start()
    try:
        r = eng.submit([5, 6, 7, 8],
                       SamplingParams(max_new_tokens=6, **GREEDY))
        r.result(timeout=120)
        assert r.finish_reason in ("stop", "length")
        assert 1 <= len(r.out_tokens) <= 6
        assert r.tokens[:4] == [5, 6, 7, 8]
    finally:
        eng.stop()


def test_engine_stats_shape(engine):
    s = engine.stats()
    for key in ("queue_depth", "mean_batch_occupancy", "decode_steps",
                "prefill_chunks", "tokens_generated", "prefill_secs",
                "decode_secs", "blocks_in_use", "finished", "warmed_up",
                "paged_kernel", "prefill_kernel", "speculative",
                "draft_k", "drafted_tokens", "accepted_tokens"):
        assert key in s
    assert s["warmed_up"] is True
    # resolved attention paths, not the requested modes
    assert s["paged_kernel"] in ("pallas", "xla")
    assert s["prefill_kernel"] in ("pallas", "xla")
    assert s["speculative"] is False and s["draft_k"] == 0
    assert "moe_expert_tiles" not in s      # a sparse model's alone
    # the engine-loop goodput block (loop_profiler.py) rides along,
    # populated by the traffic the earlier tests pushed through
    loop = s["loop"]
    assert loop["dispatches"] > 0
    assert loop["dispatches_by_kind"]["prefill"] > 0
    assert loop["dispatches_by_kind"]["decode"] > 0
    assert set(loop["phase_secs"]) == {"schedule", "draft", "build_inputs",
                                       "dispatch", "fetch", "emit"}
    assert loop["wait_secs"] > 0
    # marks tile each dispatch: phases sum to dispatch wall-clock
    assert sum(loop["phase_secs"].values()) == \
        pytest.approx(loop["wall_secs"], rel=0.05)
    assert 0.0 <= loop["wait_pct"] <= 100.0
    assert loop["wait_pct"] + loop["host_bubble_pct"] == \
        pytest.approx(100.0, abs=0.01)
    assert loop["window"]["dispatches"] > 0
    assert "loop_fetch_secs" in loop["histograms"]
    # the cache observatory block (cache_observatory.py) rides along too
    cache = s["cache"]
    assert cache["probes"] == cache["hits"] + cache["misses"]
    assert cache["misses"] == cache["miss_cold"] + cache["miss_evicted"]
    assert set(cache["ghost"]) == {"x2", "x4", "x10"}
    for tier in cache["ghost"].values():
        assert tier["hits"] >= 0 and tier["capacity_blocks"] > 0
    assert isinstance(cache["heat_top"], list)


# ---------------------------------------------------------------------------
# in-engine speculative decoding (serving/drafter.py + the [S, K+1]
# verify step; docs/guide/serving.md "Speculative decoding")
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def spec_engine(model_and_params):
    model, params = model_and_params
    eng = InferenceEngine(model, params, EngineConfig(
        num_slots=4, block_size=8, prefill_chunk=16, max_model_len=64,
        max_queue_depth=32, default_deadline_secs=0.0,
        speculative=True, draft_k=4))
    eng.warmup()
    eng.start()
    yield eng
    eng.stop()


# repetitive greedy prompts (prompt-lookup fires), a non-repeating
# greedy prompt (usually no usable draft), and a sampled slot (drafts
# K=0 by design) — all co-batched into the same verify steps
SPEC_MIX = [
    ([1, 2, 3, 4, 1, 2, 3], SamplingParams(max_new_tokens=16, **GREEDY)),
    ([2, 3, 2, 3, 2, 3], SamplingParams(max_new_tokens=12, **GREEDY)),
    ([5, 6, 7, 8, 9], SamplingParams(max_new_tokens=16, **GREEDY)),
    ([5, 6, 7], SamplingParams(max_new_tokens=8, temperature=0.9,
                               top_k=20, seed=7, eod_id=63)),
]


def _run_spec_mix(eng):
    outs = [None] * len(SPEC_MIX)

    def client(i):
        prompt, sp = SPEC_MIX[i]
        outs[i] = eng.submit(prompt, sp).result(timeout=180).out_tokens

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(SPEC_MIX))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return outs


def test_engine_speculative_greedy_parity_cobatched(engine, spec_engine):
    """Acceptance: engine greedy output with speculation on is token-
    identical to spec-off for the same seeds/prompts at occupancy > 1,
    co-batched with sampled + non-drafting slots — and the speculative
    arm really drafted and accepted (the parity is not vacuous)."""
    occ0, dec0 = spec_engine.occupancy_sum, spec_engine.decode_steps
    drafted0 = spec_engine.drafted_tokens
    accepted0 = spec_engine.accepted_tokens
    want = _run_spec_mix(engine)
    got = _run_spec_mix(spec_engine)
    assert got == want
    occ = ((spec_engine.occupancy_sum - occ0)
           / max(spec_engine.decode_steps - dec0, 1))
    assert occ > 1.0, f"no co-batching: mean occupancy {occ}"
    assert spec_engine.drafted_tokens > drafted0
    assert spec_engine.accepted_tokens > accepted0
    assert spec_engine.accepted_tokens <= spec_engine.drafted_tokens
    s = spec_engine.stats()
    assert s["speculative"] is True and s["draft_k"] == 4


def _drafting_prompt(eng, seed_prompt):
    """A prompt whose first decode step drafts by construction, whatever
    the model answers: ``seed_prompt`` continued by the model's own
    greedy tokens as far as the first one that closes a bigram the
    history has seen before.  Greedy decoding repeats itself, so that
    token is the first this prompt samples, and the drafter's lookup at
    the step after it hits."""
    out = eng.submit(seed_prompt, SamplingParams(
        max_new_tokens=32, temperature=0.0)).result(timeout=180).out_tokens
    for n in range(1, len(out) + 1):
        if lookup_draft(list(seed_prompt) + out[:n], 1):
            return list(seed_prompt) + out[:n - 1]
    raise AssertionError(f"no bigram of {seed_prompt} + {out} repeats")


def test_engine_speculative_zero_recompiles(spec_engine, tmp_path):
    """The zero-recompile guard with speculation on: mixed drafting /
    non-drafting / sampled traffic all rides the one [S, K+1] verify
    program — per-slot draft tokens and valid counts are traced inputs,
    so proposal churn never compiles — and the request_done records
    carry the accept attribution."""
    from megatron_llm_tpu import telemetry

    seeds = [[1 + i, 2, 1 + i, 2, 1 + i] if i % 2 == 0
             else list(range(1, 2 + (i % 7))) for i in range(10)]
    sampled = (2, 5, 8)         # draft K=0 by design
    drafting = {i: _drafting_prompt(spec_engine, seeds[i])
                for i in (0, 4, 6)}
    tracer = tracing.SpanTracer()
    det = tracing.RecompileDetector(tracer)
    tracing.install_tracing(tracing.Tracing(tracer=tracer, recompile=det))
    stream = telemetry.TelemetryStream(str(tmp_path))
    telemetry.install_stream(stream)
    try:
        det.mark_steady()
        reqs = []
        for i in range(10):
            if i in sampled:
                sp = SamplingParams(max_new_tokens=3 + (i % 5),
                                    temperature=0.8, top_k=5 + i,
                                    seed=i, eod_id=63)
            else:   # a drafting prompt's first token may be the stop token
                sp = SamplingParams(max_new_tokens=3 + (i % 5),
                                    temperature=0.0,
                                    eod_id=None if i in drafting else 63)
            prompt = drafting.get(i, seeds[i])
            reqs.append(spec_engine.submit(prompt, sp,
                                           trace_id=f"{i:016x}"))
        for r in reqs:
            r.result(timeout=180)
        assert det.recompiles == 0, \
            f"{det.recompiles} recompiles after warmup: {list(det.events)}"
        # the loop profiler ran through the same traffic (verify-step
        # dispatches with a draft phase) without costing a compile
        loop = spec_engine.stats()["loop"]
        assert loop["dispatches_by_kind"]["verify"] > 0
        assert loop["phase_secs"]["draft"] > 0
    finally:
        tracing.install_tracing(None)
        telemetry.install_stream(None)
        stream.close()
    import json as _json
    done = [_json.loads(ln) for ln in
            (tmp_path / "telemetry.jsonl").read_text().splitlines()
            if "request_done" in ln]
    assert len(done) == 10
    for r in done:
        assert r["accepted_tokens"] <= r["drafted_tokens"]
        assert (r["accept_rate"] is None) == (r["drafted_tokens"] == 0)
    drafted = [r for r in done if r["drafted_tokens"] > 0]
    assert drafted, "no request drafted — the guard run is vacuous"
    for r in drafted:
        assert 0.0 <= r["accept_rate"] <= 1.0


def test_engine_paged_kernel_token_identity(model_and_params):
    """Acceptance: greedy decode through the Pallas ragged kernel
    (interpret mode on CPU) is token-identical to the XLA gather
    branch, the engine reports the resolved path, and the kernel-on
    engine stays zero-recompile after warmup."""
    from megatron_llm_tpu.ops.pallas import paged_attention as pa
    model, params = model_and_params
    prompts = [[5, 6, 7, 8, 9], [1, 2, 3]]
    outs = []
    old = pa._INTERPRET
    try:
        for mode in ("off", "on"):
            pa._INTERPRET = mode == "on"
            eng = InferenceEngine(model, params, EngineConfig(
                num_slots=2, block_size=8, prefill_chunk=16,
                max_model_len=64, default_deadline_secs=0.0,
                paged_kernel=mode))
            assert eng.paged_kernel == ("pallas" if mode == "on" else "xla")
            eng.warmup()
            eng.start()
            det = None
            if mode == "on":
                tracer = tracing.SpanTracer()
                det = tracing.RecompileDetector(tracer)
                tracing.install_tracing(
                    tracing.Tracing(tracer=tracer, recompile=det))
                det.mark_steady()
            try:
                rs = [eng.submit(p, SamplingParams(max_new_tokens=8,
                                                   **GREEDY))
                      for p in prompts]
                outs.append([r.result(timeout=180).tokens for r in rs])
                if det is not None:
                    # loop profiler accounted the kernel-path dispatches
                    loop = eng.stats()["loop"]
                    assert loop["dispatches_by_kind"]["decode"] > 0
                    assert loop["wait_secs"] > 0
            finally:
                eng.stop()
                if det is not None:
                    tracing.install_tracing(None)
            if det is not None:
                assert det.recompiles == 0, \
                    f"{det.recompiles} recompiles: {list(det.events)}"
    finally:
        pa._INTERPRET = old
    assert outs[0] == outs[1]


def test_engine_prefill_kernel_token_identity(model_and_params):
    """Acceptance: greedy generation with the Pallas ragged *prefill*
    kernel (interpret mode on CPU) is token-identical to the XLA dense
    branch, the engine reports the resolved prefill path, and with BOTH
    kernels enabled the engine stays zero-recompile after warmup —
    prompts here straddle prefill chunks (len > prefill_chunk) so the
    cached-prefix tail-chunk shape is exercised, not just chunk 0."""
    from megatron_llm_tpu.ops.pallas import paged_attention as pa
    model, params = model_and_params
    prompts = [list(range(1, 12)), [5, 6, 7], list(range(3, 13))]
    outs = []
    old = pa._INTERPRET
    try:
        for mode in ("off", "on"):
            pa._INTERPRET = mode == "on"
            eng = InferenceEngine(model, params, EngineConfig(
                num_slots=2, block_size=8, prefill_chunk=8,
                max_model_len=64, default_deadline_secs=0.0,
                paged_kernel=mode, prefill_kernel=mode))
            assert eng.prefill_kernel == \
                ("pallas" if mode == "on" else "xla")
            eng.warmup()
            eng.start()
            det = None
            if mode == "on":        # both kernels live: still 0 recompiles
                tracer = tracing.SpanTracer()
                det = tracing.RecompileDetector(tracer)
                tracing.install_tracing(
                    tracing.Tracing(tracer=tracer, recompile=det))
                det.mark_steady()
            try:
                rs = [eng.submit(p, SamplingParams(max_new_tokens=8,
                                                   **GREEDY))
                      for p in prompts]
                outs.append([r.result(timeout=180).tokens for r in rs])
                if det is not None:
                    # both kernels live: the loop profiler saw prefill
                    # AND decode dispatches without costing a compile
                    loop = eng.stats()["loop"]
                    assert loop["dispatches_by_kind"]["prefill"] > 0
                    assert loop["dispatches_by_kind"]["decode"] > 0
            finally:
                eng.stop()
                if det is not None:
                    tracing.install_tracing(None)
            if det is not None:
                assert det.recompiles == 0, \
                    f"{det.recompiles} recompiles: {list(det.events)}"
    finally:
        pa._INTERPRET = old
    assert outs[0] == outs[1]


def test_granite_serves_through_the_server_cli():
    """``--model_name=granite`` through ``tools/run_text_generation_server
    .py`` and the engine, with no side script: a hybrid of state-space and
    attention layers that holds half its router's experts answers over
    HTTP, and says what state its slots hold."""
    import json as _json
    import os
    import socket
    import subprocess
    import sys
    import urllib.request

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable,
         os.path.join(root, "tools", "run_text_generation_server.py"),
         "--model_name=granite", "--num_layers=4", "--hidden_size=64",
         "--num_attention_heads=4", "--num_attention_heads_kv=2",
         "--kv_channels=16", "--ffn_hidden_size=32", "--num_experts=4",
         "--moe_router_experts=8", "--moe_experts_first=4", "--moe_top_k=3",
         "--layer_types", "mamba", "attention", "--mamba_n_heads=4",
         "--mamba_d_head=32", "--mamba_d_state=16", "--mamba_chunk_size=16",
         "--attention_multiplier=0.0625", "--seq_length=128",
         "--max_position_embeddings=128", "--micro_batch_size=1",
         "--global_batch_size=1", "--tokenizer_type=NullTokenizer",
         "--vocab_size=255", "--serve_engine", "--serve_num_slots=2",
         "--serve_prefill_chunk=16", "--serve_preemption=0",
         "--serve_alerts=0", f"--port={port}", "--host=127.0.0.1"],
        cwd=root, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    chunks = []
    drain = threading.Thread(
        target=lambda: chunks.extend(iter(proc.stdout.readline, "")),
        daemon=True)
    drain.start()
    out = metrics = last = None
    try:
        body = _json.dumps({"prompts": [" ".join(
            str(3 + i % 200) for i in range(40))],
            "tokens_to_generate": 5}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api", data=body,
            headers={"Content-Type": "application/json"}, method="PUT")
        deadline = time.time() + 540
        while time.time() < deadline and proc.poll() is None:
            try:
                with urllib.request.urlopen(req, timeout=30) as r:
                    out = _json.loads(r.read())
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics", timeout=30) as r:
                    metrics = r.read().decode()
                break
            except Exception as e:  # server still compiling/binding
                last = e
                time.sleep(3)
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except Exception:
            proc.kill()
        drain.join(timeout=10)
    text = "".join(chunks)
    assert out is not None, f"server never answered: {last}\n{text[-3000:]}"
    assert len(out["text"][0].split()) == 45, out
    assert "adopts nothing" in text
    # three chunks and four steps over two state-space layers
    assert "ssm_tokens" in metrics and "moe_assignments_held" in metrics


# ---------------------------------------------------------------------------
# the output head once a request and for one row (PR 60): a chunk that
# does not end its context runs no head, the one that does runs it on its
# last live row.  One family of each form of a chunk's tables: ``mistral``
# (one type: ``{FULL: table}``) and ``granite`` (a typed stack with
# ``STATE``, and a ``logits_scaling``)
# ---------------------------------------------------------------------------

HEAD_FAMILIES = ["mistral", "granite"]
# the old chunk multiplied all C rows by the head and kept one; a product
# of one row adds its terms in another order: float32 on the CPU, logits
# of a few units
HEAD_ATOL = 2e-5


@pytest.fixture(scope="module")
def head_engines():
    """A hand-stepped tiny engine a family, chunks of 16, built once."""
    import _family
    from megatron_llm_tpu.models import MODEL_REGISTRY
    from megatron_llm_tpu.models.mistral import mistral_config

    kept = {}

    def get(name):
        if name not in kept:
            if name == "mistral":
                model = MODEL_REGISTRY[name](mistral_config(
                    "tiny", use_flash_attn=False))
                params, kw = model.init(jax.random.PRNGKey(0)), {}
            else:
                b = _family.built(name)
                model, params = b.model, b.params
                kw = _family.FAMILIES[name].engine
            kept[name] = _family.engine(model, params, prefill_chunk=16,
                                        **kw)
        return kept[name]

    return get


def _head_prompt(eng, n, seed):
    import _family

    return _family.tokens(n, seed, int(eng.model.cfg.padded_vocab_size))


def _old_chunk(eng):
    """The chunk's program as it was before PR 60: the forward WITH its
    logits over all C rows, then row ``valid - 1``; on the arguments a
    launch hands ``engine._prefill_step`` today."""
    from megatron_llm_tpu.models.language_model import (
        language_model_forward)

    def program(params, pages, tokens, start, valid, table):
        table, _ = eng._cache.chunk_given(table)
        positions = (start + jnp.arange(tokens.shape[1]))[None, :]
        caches = paged_kv.step_caches(
            pages, table, jnp.full((1,), start, jnp.int32),
            jnp.full((1,), valid, jnp.int32), eng.prefill_kernel,
            eng._layer_groups)
        logits, new = language_model_forward(
            params, tokens, positions, None, eng.model.cfg, rng_key=None,
            train=False, kv_caches=caches)
        last = jax.lax.dynamic_index_in_dim(logits[0], valid - 1, axis=0,
                                            keepdims=False)
        return (last.astype(jnp.float32), paged_kv.pools_of(new),
                paged_kv.routing_of(new))

    return jax.jit(program)


def _dots_to_vocab(jaxpr, V, inside=()):
    """(the primitives a ``dot_general`` whose result is ``V`` wide lies
    under, its result's shape), through every inner jaxpr."""
    for eqn in jaxpr.eqns:
        shape = eqn.outvars[0].aval.shape
        if eqn.primitive.name == "dot_general" and shape[-1:] == (V,):
            yield inside, shape
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _dots_to_vocab(sub, V, inside + (eqn.primitive.name,))


@pytest.mark.parametrize("family", HEAD_FAMILIES)
def test_a_chunks_program_multiplies_one_row_by_the_head(head_engines,
                                                         family):
    """The jaxpr of ``engine_prefill`` holds no product of C rows by V
    columns: the only ``dot_general`` with a V-wide result sits inside
    the ``cond`` and has one row.  The decode step's still has a row a
    slot, outside any ``cond``."""
    eng = head_engines(family)
    V, args = int(eng.model.cfg.padded_vocab_size), eng._program_arguments()
    assert V not in (eng.model.cfg.hidden_size, eng.config.prefill_chunk)
    chunk = jax.make_jaxpr(eng._prefill_impl)(*args["engine_prefill"])
    assert list(_dots_to_vocab(chunk.jaxpr, V)) == [(("cond",), (1, 1, V))]
    # and nothing else of the program is C rows by V columns
    C = eng.config.prefill_chunk
    assert f"{C},{V}]" not in str(chunk)
    step = jax.make_jaxpr(eng._decode_impl)(*args["engine_decode"])
    assert list(_dots_to_vocab(step.jaxpr, V)) == [
        ((), (eng.config.num_slots, 1, V))]


@pytest.mark.parametrize("family", HEAD_FAMILIES)
def test_the_last_chunks_one_row_is_the_old_chunks_row(head_engines, family,
                                                       monkeypatch):
    """A prompt of four chunks (the last of 5 live rows): the ``[V]``
    logits handed to ``engine_sample_first`` are the old program's row
    ``valid - 1`` on the same arguments within ``HEAD_ATOL``, the first
    token is its argmax, and the greedy answer is the one the engine gives
    with the OLD program in the chunk's place."""
    import _family

    eng = head_engines(family)
    prompt = _head_prompt(eng, 53, seed=11)
    old, new_step = _old_chunk(eng), eng._prefill_step
    olds, sampled = [], []

    def tapped(params, pages, tokens, start, valid, table):
        olds.append(np.asarray(
            old(params, pages, tokens, start, valid, table)[0]))
        return new_step(params, pages, tokens, start, valid, table)

    first = eng._sample_first
    monkeypatch.setattr(eng, "_prefill_step", tapped)
    monkeypatch.setattr(eng, "_sample_first", lambda logits, *rest: (
        sampled.append(np.asarray(logits)), first(logits, *rest))[1])
    answer = list(_family.serve(eng, prompt, 6).out_tokens)
    assert len(olds) == 4 and len(sampled) == 1
    assert np.abs(olds[-1]).max() > 0.1
    np.testing.assert_allclose(sampled[0], olds[-1], atol=HEAD_ATOL, rtol=0)
    assert answer[0] == int(olds[-1].argmax())
    # the tree before the change: every chunk's logits, the same answer
    monkeypatch.setattr(eng, "_prefill_step", old)
    monkeypatch.setattr(eng, "_sample_first", first)
    assert list(_family.serve(eng, prompt, 6).out_tokens) == answer


@pytest.mark.parametrize("family", HEAD_FAMILIES)
def test_only_the_chunk_that_ends_its_context_runs_the_head(head_engines,
                                                            family,
                                                            monkeypatch):
    """Three requests of three, one and two chunks: a chunk that is not
    its context's last returns zeros and counts ``prefill_head_rows`` 0,
    the last returns logits and counts 1, and ``stats()['prefill_heads']``
    is the requests prefilled."""
    import _family

    eng = head_engines(family)
    inner, got = eng._prefill_step, []

    def tapped(params, pages, tokens, start, valid, table):
        out = inner(params, pages, tokens, start, valid, table)
        got.append((bool(table[paged_kv.LAST]), np.asarray(out[0])))
        return out

    monkeypatch.setattr(eng, "_prefill_step", tapped)
    since = _family.counted(eng)
    for n, seed in ((40, 21), (9, 22), (32, 23)):
        _family.serve(eng, _head_prompt(eng, n, seed), 3)
    stats, records = since()
    chunks = [r for r in records if r.kind == "prefill"]
    assert [last for last, _ in got] == [False, False, True, True,
                                         False, True]
    assert [r.prefill_head_rows for r in chunks] == [0, 0, 1, 1, 0, 1]
    for last, logits in got:
        assert logits.shape == (eng.model.cfg.padded_vocab_size,)
        assert bool(np.abs(logits).max() > 0.1) == last
        assert last or not logits.any()
    assert stats["prefill_chunks"] == 6
    assert stats["prefill_heads"] == stats["prefill_head_rows"] == 3


def test_a_request_preempted_mid_prefill_still_ends_on_a_head(head_engines):
    """A request of four chunks preempted after its second and requeued
    prefills its context again (its own pages adopted) and ends on a
    chunk that runs the head: the answer is the plain forward's."""
    import _family

    eng = head_engines("mistral")
    prompt = _head_prompt(eng, 60, seed=31)
    since = _family.counted(eng)
    req = eng.submit(prompt, SamplingParams(max_new_tokens=5,
                                            temperature=0.0))
    assert eng.step() and eng.step() and req.prefill_pos == 32
    eng._preempt(eng._st, req)
    assert req.slot is None and req.preempt_count == 1
    while req.finish_reason is None:
        assert eng.step()
    assert _family.is_greedy(eng.model, eng.params, prompt, req.out_tokens)
    stats, records = since()
    heads = [r.prefill_head_rows for r in records if r.kind == "prefill"]
    assert heads[:2] == [0, 0] and heads[-1] == 1 and sum(heads) == 1
    assert stats["prefill_heads"] == 1
    assert not any(r.prefill_head_rows for r in records
                   if r.kind != "prefill")
