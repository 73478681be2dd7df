"""What lives on the device between launches (serving/engine.py,
``_EngineState``): the five sampling arrays, uploaded again only after
the host wrote one, and the PRNG key chain, which the decode and verify
programs advance where it lies; a launch's results cross to the host
together.

Held here on a tiny model on the CPU: what is served (greedy and seeded
sampled requests side by side) is token for token what the HOST's
discipline serves, the one the loop had before (every array uploaded
whole on every launch, the keys fetched after it and advanced by the host
for the slots that decoded), across an admission into a running batch,
``top_p_decay``, a preemption, a restart, and with speculation on, which
also serves what it serves off; a launch with no host write since the
last uploads the per-step arrays only and waits once; and with the
weights PLACED, as a server places them, one executable a program serves
every launch and ``program_tables()`` lowers nothing.
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from megatron_llm_tpu.models.llama import LlamaModel, llama_config
from megatron_llm_tpu.serving import (EngineConfig, InferenceEngine,
                                      SamplingParams)
from megatron_llm_tpu.serving import engine as engine_mod
from test_program_tables import _Heard


@pytest.fixture(scope="module")
def dense():
    # a vocabulary of 64: greedy decoding soon repeats itself, which is
    # what gives the prompt-lookup drafter something to propose
    model = LlamaModel(llama_config(
        "tiny", num_layers=2, seq_length=64, max_position_embeddings=64,
        padded_vocab_size=64, use_flash_attn=False))
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model, params, **kw):
    kw = dict(dict(num_slots=4, block_size=8, max_model_len=64,
                   prefill_chunk=16, preemption=False,
                   default_deadline_secs=0.0, restart_backoff_secs=0.0),
              **kw)
    return InferenceEngine(model, params, EngineConfig(**kw))


def _the_host_keeps_the_keys(eng):
    """The discipline the loop had before, laid over ``eng``: before
    every step the host's sampling arrays are uploaded whole and so is
    its array of keys, every row given; after it the host reads the keys
    back and advances its own for the slots that decoded."""
    for name in ("_run_decode", "_run_verify"):
        def run(st, slots, d, inner=getattr(eng, name)):
            st.stale()
            st.keys_given[:] = True
            inner(st, slots, d)
            st.keys[slots] = np.asarray(st.key_chain)[slots]
        setattr(eng, name, run)
    return eng


def _run(eng, reqs):
    for _ in range(2000):
        if all(r.finish_reason is not None for r in reqs):
            return
        eng.step()
    raise AssertionError("the requests did not finish")


def _drive(eng, first, later, between=None):
    """Submit ``first``; once each of them has three tokens do
    ``between`` and submit ``later``; every request's tokens."""
    reqs = [eng.submit(p, sp) for p, sp in first]
    for _ in range(2000):
        if all(len(r.out_tokens) >= 3 for r in reqs):
            break
        assert eng.step()
    if between is not None:
        between(eng)
    reqs += [eng.submit(p, sp) for p, sp in later]
    _run(eng, reqs)
    eng.blocks.check_invariants()
    return [list(r.out_tokens) for r in reqs]


def _prompt(n, seed, vocab=60):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


GREEDY = dict(temperature=0.0)
REPEATS = [5, 6, 7, 8, 9] * 4       # what a prompt-lookup drafter finds


def _admission():
    return dict(first=[(_prompt(19, 1), SamplingParams(
                            max_new_tokens=12, **GREEDY)),
                       (_prompt(23, 2), SamplingParams(
                            max_new_tokens=14, seed=3, top_k=5))],
                later=[(_prompt(21, 3), SamplingParams(
                            max_new_tokens=9, seed=9, top_p=0.8)),
                       (_prompt(17, 4), SamplingParams(
                            max_new_tokens=8, seed=9))])


def _top_p_decay():
    decaying = dict(top_p=0.9, top_p_decay=0.7, top_p_bound=0.2)
    return dict(first=[(_prompt(19, 1), SamplingParams(
                            max_new_tokens=12, seed=4, **decaying)),
                       (_prompt(23, 2), SamplingParams(
                            max_new_tokens=10, **GREEDY))],
                later=[(_prompt(21, 3), SamplingParams(
                            max_new_tokens=9, seed=5, **decaying))])


def _preemption():
    # six usable pages: the long request's reservation (20 + 28 tokens)
    # takes them all, so the short one gets in only over a preemption
    return dict(first=[(_prompt(20, 1), SamplingParams(
                            max_new_tokens=28, seed=11, top_k=8))],
                later=[([1, 2], SamplingParams(max_new_tokens=4, seed=5))])


def _restart():
    plan = _admission()
    plan["between"] = lambda eng: eng.restart("the test asked")
    return plan


def _speculative():
    return dict(first=[(REPEATS, SamplingParams(max_new_tokens=14,
                                                **GREEDY)),
                       (_prompt(23, 2), SamplingParams(
                            max_new_tokens=12, seed=3, top_k=5))],
                later=[(REPEATS[1:], SamplingParams(max_new_tokens=10,
                                                    **GREEDY)),
                       (_prompt(17, 4), SamplingParams(
                            max_new_tokens=8, seed=9))])


@pytest.mark.parametrize("plan, kw, happened", [
    (_admission, {}, lambda e: e.scheduler.admitted == 4),
    (_top_p_decay, {}, lambda e: e.sample_sort_steps > 0),
    (_preemption, dict(num_blocks=7, preemption=True),
     lambda e: e.scheduler.preemptions >= 1),
    (_restart, {}, lambda e: e.engine_restarts == 1),
    (_speculative, dict(speculative=True, draft_k=3),
     lambda e: e.accepted_tokens > 0),
], ids=["admission", "top_p_decay", "preemption", "restart",
        "speculative"])
def test_served_as_the_hosts_own_keys_and_arrays_serve(dense, plan, kw,
                                                       happened):
    """A request's tokens depend on its seed alone: where its keys live
    and when its sampling values travel changes none of them."""
    resident = _engine(*dense, **kw)
    served = _drive(resident, **plan())
    assert happened(resident)
    assert all(served) and len({tuple(s) for s in served}) == len(served)
    hosts = _the_host_keeps_the_keys(_engine(*dense, **kw))
    assert _drive(hosts, **plan()) == served
    assert happened(hosts)
    if kw.get("speculative"):
        plain = _engine(*dense)
        assert _drive(plain, **plan()) == served
        assert plain.drafted_tokens == 0


def _moved(eng, since=0):
    return [(d.kind, d.host_uploads, d.host_reads)
            for d in eng.loop_profiler.records()[since:]]


@pytest.mark.parametrize("kw, step", [
    ({}, "decode"), (dict(speculative=True, draft_k=3), "verify"),
], ids=["decode", "verify"])
def test_a_launch_uploads_what_changed_and_waits_once(dense, kw, step):
    eng = _engine(*dense, **kw)
    sp = SamplingParams(max_new_tokens=6, seed=2, top_k=4)
    first = eng.submit(_prompt(19, 1), sp)      # two chunks of 16
    _run(eng, [first])
    # tokens, context lengths, the one table, the live mask: what a step
    # is handed every time; a chunk's tokens, start, length, table and
    # whether it ends its context, and with its prompt's last the
    # sampler's seven scalars
    per_step, chunk, sampler = 4, 5, 7
    assert _moved(eng) == [
        ("prefill", chunk, 1),
        ("prefill", chunk + sampler, 1),
        # the admission's five sampling arrays, once, and the key that
        # sampling the first token left: the host's keys and their rows
        (step, per_step + 5 + 2, 1),
    ] + [(step, per_step, 1)] * 4
    # an admission into the running batch: the stale arrays once more
    second = eng.submit(_prompt(30, 5), SamplingParams(max_new_tokens=5,
                                                       **GREEDY))
    eng.step()
    third = eng.submit(_prompt(9, 6), sp)       # one chunk
    n = len(_moved(eng))
    _run(eng, [second, third])
    moved = _moved(eng, n)
    steps = [m for m in moved if m[0] == step]
    assert all(reads == 1 for _, _, reads in moved)
    # every step but the one after a host's write uploads four arrays
    writes = [m for m in steps if m[1] != per_step]
    assert {m[1] for m in writes} <= {per_step + 2, per_step + 5 + 2}
    assert 2 <= len(writes) <= 3 and len(steps) - len(writes) >= 3
    loop = eng.stats()["loop"]
    everything = _moved(eng)
    assert loop["host_uploads"] == sum(m[1] for m in everything)
    assert loop["host_reads"] == len(everything) == loop["dispatches"]
    record = eng.loop_profiler.ring_records(1)[0]
    assert (record["host_uploads"], record["host_reads"]) == (per_step, 1)


def test_a_decaying_top_p_is_the_one_array_uploaded(dense):
    eng = _engine(*dense)
    req = eng.submit(_prompt(10, 1), SamplingParams(
        max_new_tokens=6, seed=1, top_p=0.9, top_p_decay=0.8))
    _run(eng, [req])
    steps = [m for m in _moved(eng) if m[0] == "decode"]
    assert steps[0][1] == 4 + 5 + 2 and {m[1] for m in steps[1:]} == {5}
    # the device's copy is what the host wrote
    eng._resident(eng._st, eng.loop_profiler.begin())
    np.testing.assert_array_equal(np.asarray(eng._st.placed["top_ps"]),
                                  eng._st.top_ps)


# -- one executable a program, whatever the weights' placement -------------

def _placed(params, how):
    device = jax.devices()[0]
    if how == "named":
        return jax.device_put(params, NamedSharding(
            Mesh(np.array([device]), ("x",)), PartitionSpec()))
    return jax.device_put(params, device)


@pytest.mark.parametrize("how", ["named", "single_device", "not_placed"])
@pytest.mark.parametrize("kw, step", [
    ({}, "_decode_step"), (dict(speculative=True, draft_k=3),
                           "_verify_step"),
], ids=["decode", "verify"])
def test_one_executable_serves_every_launch(dense, how, kw, step):
    """Warm-up hands each program the kinds of arrays (the host's, or
    placed where the program's own results lie) every later launch
    hands it, and ``_program_arguments`` describes the same: a second
    kind would be a second executable, compiled inside a served window,
    and ``program_tables()`` would lower a program that never ran."""
    model, params = dense
    if how != "not_placed":
        params = _placed(params, how)
    eng = _engine(model, params, **kw)
    chain = eng._st.key_chain
    assert chain.committed == (how != "not_placed")
    heard = _Heard()
    try:
        eng.warmup()
        assert heard.take()
        plan = _admission()
        _drive(eng, plan["first"], plan["later"],
               between=lambda e: e.restart("the test asked"))
        assert heard.take() == []           # nothing lowered or compiled
        for name in (step, "_sample_first"):
            assert getattr(eng, name)._cache_size() == 1, name
        # the chain a step gives back is of the kind the state began with
        after = eng._st.key_chain
        assert after is not chain
        assert (after.committed, after.sharding) == (chain.committed,
                                                     chain.sharding)
        tables = eng.program_tables()
        assert heard.take() == []
        assert {"engine_sample_first",
                "engine" + step[:-len("_step")]} <= set(tables)
    finally:
        heard.events = None


def test_where_the_small_arrays_are_placed(dense):
    _, params = dense
    assert engine_mod._beside(params) is None
    assert engine_mod._beside(jax.eval_shape(lambda: params)) is None
    device = jax.devices()[0]
    single = engine_mod._beside(_placed(params, "single_device"))
    assert single.device_set == {device}
    named = engine_mod._beside(_placed(params, "named"))
    assert isinstance(named, NamedSharding)
    assert named.spec == PartitionSpec() and named.device_set == {device}
