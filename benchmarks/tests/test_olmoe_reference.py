"""``reference/olmoe.py`` against numbers worked out by hand: the gates a
token's experts get (the softmax over ALL experts, not renormalised), a
turned routing choice, and the QK-norm over the whole projection; and
``reference/olmoe_probe.py``'s comparison of logits."""
import importlib.util
import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "ref_" + name, os.path.join(BENCH, "reference", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("olmoe")
probe = _load("olmoe_probe")
EPS = 1e-5


def _gate_case():
    """One token whose normed input is (1, -1, 1, -1) (its mean square is
    1) and a router whose logits for it are (0, ln 2, ln 5): the softmax
    over all three experts is (1/8, 2/8, 5/8)."""
    x = jnp.asarray([[1.0, -1.0, 1.0, -1.0]])
    gate = np.zeros((4, 3), np.float32)
    gate[0] = [0.0, math.log(2.0), math.log(5.0)]
    return x, jnp.ones((4,)), jnp.asarray(gate)


def test_gates_are_the_softmax_over_all_experts_as_it_is():
    x, norm, gate = _gate_case()
    hn, dense, margin = ref.moe_gates(x, norm, gate, jnp.asarray([False]),
                                      eps=0.0, top_k=2)
    np.testing.assert_allclose(np.asarray(hn), np.asarray(x), atol=1e-6)
    # experts 2 and 1 are chosen and weigh 5/8 and 2/8: 7/8 together, not
    # the 5/7 and 2/7 a renormalised router (Mixtral's) would give
    np.testing.assert_allclose(np.asarray(dense), [[0.0, 0.25, 0.625]],
                               atol=1e-6)
    np.testing.assert_allclose(float(margin[0]), math.log(2.0), atol=1e-6)


def test_a_turned_choice_seats_the_first_rejected_expert_with_its_own_gate():
    x, norm, gate = _gate_case()
    _, dense, _ = ref.moe_gates(x, norm, gate, jnp.asarray([True]),
                                eps=0.0, top_k=2)
    np.testing.assert_allclose(np.asarray(dense), [[0.125, 0.0, 0.625]],
                               atol=1e-6)


def _attention_by_hand(x, w, per_head):
    """Two heads of two columns over two positions, every step written
    out: QK-norm over the whole 4-wide projection (or, ``per_head``, over
    each head's two columns: the mistake the reference must not make),
    rotate-half rotary at theta 100, causal softmax."""
    n1 = np.asarray(w["attention_norm"])
    hn = np.stack([r / math.sqrt(np.mean(r * r) + EPS) * n1 for r in x])

    def qk_norm(p, g):
        out = np.zeros_like(p)
        for t in range(2):
            if per_head:
                for h in range(2):
                    c = p[t, 2 * h:2 * h + 2]
                    out[t, 2 * h:2 * h + 2] = c / math.sqrt(
                        np.mean(c * c) + EPS)
            else:
                out[t] = p[t] / math.sqrt(np.mean(p[t] * p[t]) + EPS)
        return out * g

    def rope(p):
        out = np.zeros_like(p)
        for t in range(2):
            for h in range(2):
                a, b = p[t, 2 * h], p[t, 2 * h + 1]   # d = 2: one pair
                ang = t * 100.0 ** 0.0                 # theta^(-0/2) = 1
                out[t, 2 * h] = a * math.cos(ang) - b * math.sin(ang)
                out[t, 2 * h + 1] = b * math.cos(ang) + a * math.sin(ang)
        return out

    q = rope(qk_norm(hn @ np.asarray(w["wq"]), np.asarray(w["q_norm"])))
    k = rope(qk_norm(hn @ np.asarray(w["wk"]), np.asarray(w["k_norm"])))
    v = hn @ np.asarray(w["wv"])
    ctx = np.zeros((2, 4))
    for h in range(2):
        cols = slice(2 * h, 2 * h + 2)
        ctx[0, cols] = v[0, cols]                      # position 0 sees itself
        s = np.asarray([q[1, cols] @ k[0, cols], q[1, cols] @ k[1, cols]]
                       ) / math.sqrt(2.0)
        p = np.exp(s - s.max())
        p /= p.sum()
        ctx[1, cols] = p[0] * v[0, cols] + p[1] * v[1, cols]
    return x + ctx @ np.asarray(w["wo"])


def test_qk_norm_runs_over_the_whole_projection_before_heads_and_rotary():
    rng = np.random.default_rng(26)
    x = rng.standard_normal((2, 4))
    w = {k: jnp.asarray(rng.standard_normal((4, 4)), jnp.float32)
         for k in ("wq", "wk", "wv", "wo")}
    for k in ("attention_norm", "q_norm", "k_norm"):
        w[k] = jnp.asarray(1.0 + 0.5 * rng.standard_normal(4), jnp.float32)
    got = np.asarray(ref.attention_block(
        jnp.asarray(x, jnp.float32), w, n_heads=2, n_kv=2, theta=100.0,
        eps=EPS))
    whole = _attention_by_hand(x, w, per_head=False)
    heads = _attention_by_hand(x, w, per_head=True)
    np.testing.assert_allclose(got, whole, atol=2e-5)
    assert np.abs(whole - heads).max() > 0.05


def test_logits_apart_is_the_centred_rms_over_the_references_spread():
    rng = np.random.default_rng(26)
    reference = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)
    # a constant added to a position's logits changes no probability
    shifted = reference + jnp.arange(5.0)[:, None]
    assert float(probe.logits_apart(shifted, reference)) < 1e-6
    # +-0.1 in alternate columns: centred rms 0.1
    wave = 0.1 * jnp.asarray(np.tile([1.0, -1.0], 32), jnp.float32)
    np.testing.assert_allclose(
        float(probe.logits_apart(reference + wave, reference)),
        0.1 / float(jnp.std(reference)), rtol=1e-4)


def test_the_probes_logits_come_back_nan_when_the_program_is_apart(
        monkeypatch, capsys):
    rng = np.random.default_rng(26)
    reference = jnp.asarray(rng.standard_normal((7, 32)), jnp.float32)
    cfg = {"probe": {"logits_apart_tolerance": 0.013}}
    monkeypatch.setattr(probe.plain, "forward_logits",
                        lambda *a, **k: reference)
    calls = []

    def program(params, tokens, apart=0.0):
        calls.append(len(tokens))
        return reference * (1.0 + apart)

    class Weights:
        p = None

    monkeypatch.setattr(probe, "program_logits", program)
    got = probe.forward_logits(Weights(), cfg, list(range(7)))
    assert np.array_equal(np.asarray(got), np.asarray(reference))
    monkeypatch.setattr(probe, "program_logits",
                        lambda p, t: program(p, t, apart=0.02))
    got = probe.forward_logits(Weights(), cfg, list(range(7)))
    assert np.all(np.isnan(np.asarray(got)))
    # a pass with a routing choice turned is the reference's alone
    got = probe.forward_logits(Weights(), cfg, list(range(7)),
                               turned={0: [3]})
    assert np.array_equal(np.asarray(got), np.asarray(reference))
    assert calls == [7, 7]
    notes = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [n["within"] for n in notes] == [True, False]
    assert notes[1]["logits_apart"] == pytest.approx(0.02, rel=0.02)
