"""The traffic generator: a file fixes the multiset and its order; the
run's seed fills the token ids."""
import json
import os
import threading

import numpy as np
import pytest

from conftest import BENCH
from harness import traffic


def _spec(name, config=None):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        spec = json.load(f)
    if config:
        spec.update(spec["per_config"][config])
    return spec


@pytest.mark.parametrize("section", ["lead_in", "window"])
def test_two_order_seeds_same_multisets_other_order(section):
    """Another ``order_seed`` (another traffic file) shuffles the same
    multiset; it never resamples."""
    spec = _spec("chat", "mistral-7b-serve")
    other = dict(spec, order_seed=spec["order_seed"] + 1)
    a = traffic.open_loop_schedule(spec, 40, 3, 32000)
    b = traffic.open_loop_schedule(other, 40, 2 ** 31 + 11, 32000)
    ma, mb = traffic.multiset(a, section), traffic.multiset(b, section)
    assert ma["requests"] == mb["requests"] > 0
    for key in ("prompt_tokens", "answer_tokens", "arrival_gaps"):
        assert ma[key] == mb[key], key
    order = lambda plan: [len(r.prompt) for r in plan if r.section == section]
    assert order(a) != order(b)
    ids = lambda plan: [r.prompt[:4] for r in plan if r.section == section]
    assert ids(a) != ids(b)


def test_order_seed_fixes_the_schedule_and_the_seed_fills_the_ids():
    """The chat file fixes its order: two seeds replay one schedule
    (same lengths, same due times, in the same order) with other ids."""
    spec = _spec("chat", "mistral-7b-serve")
    assert "order_seed" in spec
    a = traffic.open_loop_schedule(spec, 40, 3, 32000)
    b = traffic.open_loop_schedule(spec, 40, 2 ** 31 + 11, 32000)
    assert [(r.due, len(r.prompt), r.answer_tokens) for r in a] == \
        [(r.due, len(r.prompt), r.answer_tokens) for r in b]
    assert traffic.multiset(a, "window") == traffic.multiset(b, "window")
    assert [r.prompt[:4] for r in a] != [r.prompt[:4] for r in b]


def test_a_traced_run_offers_the_same_window_and_goes_on_after_it():
    spec = _spec("chat", "mistral-7b-serve")
    plain = traffic.open_loop_schedule(spec, 40, 3, 32000)
    traced = traffic.open_loop_schedule(spec, 40, 3, 32000, after_seconds=4)
    assert traced[:len(plain)] == plain
    after = traced[len(plain):]
    lead = spec["lead_in_seconds"]
    assert len(after) == round(4 * spec["requests_per_second"])
    assert all(r.section == "after" and lead + 40 <= r.due < lead + 44
               for r in after)


def test_same_seed_same_inputs():
    spec = _spec("chat", "mistral-7b-serve")
    a = traffic.open_loop_schedule(spec, 20, 5, 32000)
    b = traffic.open_loop_schedule(spec, 20, 5, 32000)
    assert [(r.due, r.prompt, r.answer_tokens) for r in a] == \
        [(r.due, r.prompt, r.answer_tokens) for r in b]


def test_window_section_offers_exactly_the_rate():
    spec = _spec("chat", "mistral-7b-serve")
    plan = traffic.open_loop_schedule(spec, 40, 9, 32000)
    win = [r for r in plan if r.section == "window"]
    lead = spec["lead_in_seconds"]
    assert len(win) == round(spec["requests_per_second"] * 40)
    assert all(lead <= r.due < lead + 40 for r in win)
    assert sum(r.gap for r in win) == pytest.approx(40.0)
    lens = traffic.multiset(plan, "window")["prompt_tokens"]
    assert spec["prompt_tokens"]["min"] <= lens[0]
    assert lens[-1] <= spec["prompt_tokens"]["max"]


def test_stratified_order_is_a_permutation_with_even_stretches():
    rng = np.random.default_rng(0)
    order = traffic.stratified_order(96, 16, rng)
    assert sorted(order) == list(range(96))
    # every stretch of 16 positions draws from the whole sorted range
    for k in range(0, 96, 16):
        stretch = order[k:k + 16]
        assert stretch.min() < 12 and stretch.max() >= 84


def test_quantiles_of_the_stated_distributions():
    v = traffic.quantile_values({"dist": "lognormal", "median": 256,
                                 "sigma": 1.0}, 1001)
    assert v[500] == pytest.approx(256, rel=1e-6)
    g = traffic.quantile_values({"dist": "gamma", "shape": 0.5}, 20000)
    assert g.mean() == pytest.approx(1.0, rel=0.02)   # burstier than Poisson:
    assert g.std() == pytest.approx(2 ** 0.5, rel=0.1)  # cv = 1/sqrt(shape)
    u = traffic.quantile_values({"dist": "loguniform", "min": 2048,
                                 "max": 8192}, 3)
    assert u[1] == pytest.approx(4096)


def test_closed_loop_deals_the_same_multiset_every_cycle():
    spec = _spec("docqa")
    a = traffic.ClosedLoopSource(spec, 1, 32000)
    b = traffic.ClosedLoopSource(dict(spec, order_seed=7), 2, 32000)
    n = spec["documents_per_cycle"]
    cycles = [[a.next() for _ in range(n)] for _ in range(2)] + \
        [[b.next() for _ in range(n)]]
    sets = [sorted(len(p.prompt) for p in c) for c in cycles]
    assert sets[0] == sets[1] == sets[2]
    assert [len(p.prompt) for p in cycles[0]] != \
        [len(p.prompt) for p in cycles[2]]
    assert all(2048 <= s <= 8192 for s in sets[0])
    assert len({tuple(p.prompt[:8]) for c in cycles for p in c}) == 3 * n


def _fake_engine_classes():
    class Req:
        def __init__(self):
            import queue
            self._events = queue.Queue()
            self.queue_wait_secs = 0.0

    class Engine:
        decode_steps = prefill_chunks = tokens_generated = 0
        prefill_tokens_submitted = prefill_tokens_computed = 0
        prefill_tokens_cached = occupancy_sum = 0
        decode_secs = prefill_secs = 0.0

        def __init__(self):
            self.now, self.most, self.lock = 0, 0, threading.Lock()

        def submit(self, prompt, sampling, stream=False):
            req = Req()
            with self.lock:
                self.now += 1
                self.most = max(self.most, self.now)

            def answer():
                for i in range(sampling.max_new_tokens):
                    req._events.put(("token", i))
                with self.lock:
                    self.now -= 1
                req._events.put(("done", "length"))
            threading.Timer(0.005, answer).start()
            return req

    class Sampling:
        def __init__(self, max_new_tokens, temperature):
            self.max_new_tokens = max_new_tokens

    return Engine, Sampling


def test_closed_loop_never_exceeds_its_callers():
    """The driver's closed loop against an engine that answers after a
    moment: never more in flight than callers."""
    from harness import spec as spec_mod
    from harness.context import Run
    from harness.driver import Driver

    Engine, Sampling = _fake_engine_classes()
    spec = dict(_spec("docqa"), callers=3, open_after_answers=3,
                documents_per_cycle=6,
                prompt_tokens={"dist": "loguniform", "min": 4, "max": 9},
                answer_tokens={"dist": "constant", "value": 2})
    run = Run(cell=None, seed=1, seconds=0.3, traced=False, rehearsal=True,
              process_start=0.0)
    engine = Engine()
    driver = Driver(run, engine, Sampling)
    spec_mod.load_module("loops", "closed_loop").drive(run, driver, spec,
                                                       100, None)
    assert engine.most <= 3
    assert run.checks["closed_loop_within_callers"]
    assert len(run.records) > 6
    assert all(r.finish_reason == "length" and len(r.token_times) == 2
               for r in run.records)


def test_traced_stretch_is_laid_from_the_profilers_start(monkeypatch):
    """However long the profiler takes to start, the arrivals of the
    stretch after the window are due from the instant it records, so the
    traced stretch holds the same requests in every run."""
    import jax
    from harness import driver as driver_mod, spec as spec_mod
    from harness.context import Run

    Engine, Sampling = _fake_engine_classes()
    slow = 0.7
    monkeypatch.setattr(driver_mod, "start_trace",
                        lambda d: driver_mod.time.sleep(slow))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    spec = dict(_spec("chat", "mistral-7b-serve"), requests_per_second=10,
                lead_in_seconds=0.2, trace_seconds=1.0,
                prompt_tokens={"dist": "constant", "value": 5},
                answer_tokens={"dist": "constant", "value": 2})
    run = Run(cell=None, seed=1, seconds=0.5, traced=True, rehearsal=True,
              process_start=0.0)
    drv = driver_mod.Driver(run, Engine(), Sampling)
    spec_mod.load_module("loops", "open_loop").drive(run, drv, spec, 100,
                                                     "unused")
    t0, t1 = run.setup_parts["traced"]
    assert run.setup_parts["profiler_start_s"] >= slow
    plan = traffic.open_loop_schedule(spec, 0.5, 1, 100, after_seconds=2.0)
    after = [p for p in plan if p.section == "after"]
    recs = run.records[-len(after):]
    assert len(after) == 20 and len(run.records) == len(plan)
    for p, r in zip(after, recs):
        assert r.due - t0 == pytest.approx(p.due - 0.7, abs=1e-6)
        assert r.due >= run.window.closed.at + slow
    inside = [r for r in recs if t0 <= r.submitted <= t1]
    assert len(inside) == sum(1 for p in after if p.due - 0.7 <= 1.0)
