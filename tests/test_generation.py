"""Inference tests: KV-cache decode == full forward, greedy generation,
ragged prompts, sampling filters, beam search, REST server contract."""

import json
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.models.llama import LlamaModel, llama_config
from megatron_llm_tpu.text_generation.generation import (
    beam_search,
    generate_tokens,
    greedy_generate,
    init_kv_caches,
    _forward_with_cache,
)
from megatron_llm_tpu.text_generation.sampling import modify_logits, sample


@pytest.fixture(scope="module")
def model_and_params():
    cfg = llama_config("tiny", num_layers=2, seq_length=64,
                       max_position_embeddings=64, padded_vocab_size=64,
                       use_flash_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def test_kv_cache_matches_full_forward(model_and_params):
    """Incremental decode logits == one-shot causal forward logits
    (the core inference-correctness property; reference verifies this
    implicitly through generation quality)."""
    model, params = model_and_params
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, 64, (2, 10)))

    full_logits = model(params, toks, train=False)

    caches = init_kv_caches(model.cfg, 2, 16)
    # prefill 4, then 6 single-token steps
    logits_p, caches = _forward_with_cache(model, params, toks[:, :4],
                                           caches, 0)
    parts = [logits_p]
    for t in range(4, 10):
        lg, caches = _forward_with_cache(model, params, toks[:, t:t + 1],
                                         caches, t)
        parts.append(lg)
    inc_logits = jnp.concatenate(parts, axis=1)
    np.testing.assert_allclose(np.asarray(inc_logits),
                               np.asarray(full_logits), atol=2e-4)


def test_greedy_generation_deterministic(model_and_params):
    model, params = model_and_params
    toks = jnp.asarray([[1, 2, 3, 4]])
    lens = jnp.asarray([4])
    out1, _, _ = greedy_generate(model, params, toks, lens, 8)
    out2, _, _ = greedy_generate(model, params, toks, lens, 8)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert out1.shape == (1, 12)
    np.testing.assert_array_equal(np.asarray(out1)[0, :4], [1, 2, 3, 4])


def test_ragged_prompts_keep_prompt_tokens(model_and_params):
    """Rows with longer prompts must keep their prompt tokens while shorter
    rows are already generating (reference: generation.py:160+)."""
    model, params = model_and_params
    toks = jnp.asarray([[1, 2, 0, 0], [5, 6, 7, 8]])
    lens = jnp.asarray([2, 4])
    out, _, _ = greedy_generate(model, params, toks, lens, 4)
    np.testing.assert_array_equal(np.asarray(out)[1, :4], [5, 6, 7, 8])
    np.testing.assert_array_equal(np.asarray(out)[0, :2], [1, 2])


def test_top_k_filter():
    logits = jnp.asarray([[1.0, 5.0, 3.0, 2.0]])
    out = modify_logits(logits, top_k=2)
    assert out[0, 1] == 5.0 and out[0, 2] == 3.0
    assert out[0, 0] < -1e9 and out[0, 3] < -1e9


def test_top_p_filter():
    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    out = modify_logits(logits, top_p=0.7)
    # 0.5 + 0.3 >= 0.7 -> keep first two only
    assert np.isfinite(out[0, 0]) and out[0, 1] > -1e9
    assert out[0, 2] < -1e9 and out[0, 3] < -1e9


def test_sample_greedy_matches_argmax():
    logits = jnp.asarray([[0.1, 2.0, -1.0]])
    assert int(sample(logits, jax.random.PRNGKey(0), greedy=True)[0]) == 1


def test_beam_search_returns_sorted(model_and_params):
    model, params = model_and_params
    toks = jnp.asarray([[1, 2, 3]])
    beams, scores = beam_search(model, params, toks, beam_size=3,
                                max_new_tokens=5, eod_id=63)
    assert beams.shape[0] == 3
    s = np.asarray(scores)
    assert np.all(s[:-1] >= s[1:])  # descending


class _FakeTokenizer:
    vocab_size = 64
    eod = 63
    pad = 0

    def tokenize(self, text):
        return [int(t) % 64 for t in text.split()]

    def detokenize(self, ids):
        return " ".join(str(i) for i in ids)


def test_server_contract(model_and_params):
    from megatron_llm_tpu.text_generation_server import MegatronServer

    model, params = model_and_params
    server = MegatronServer(model, params, _FakeTokenizer())
    import http.server

    httpd_holder = {}

    def run():
        # bind to an ephemeral port
        gen = server.generator

        class H(http.server.BaseHTTPRequestHandler):
            def do_PUT(self):
                n = int(self.headers.get("Content-Length", 0))
                code, body = gen.handle(json.loads(self.rfile.read(n)))
                data = json.dumps(body).encode()
                self.send_response(code)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def log_message(self, *a):
                pass

        httpd = http.server.HTTPServer(("127.0.0.1", 0), H)
        httpd_holder["port"] = httpd.server_address[1]
        httpd_holder["srv"] = httpd
        httpd.serve_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    import time

    for _ in range(100):
        if "port" in httpd_holder:
            break
        time.sleep(0.05)
    port = httpd_holder["port"]

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api",
        data=json.dumps({"prompts": ["1 2 3"],
                         "tokens_to_generate": 4}).encode(),
        method="PUT",
    )
    with urllib.request.urlopen(req) as resp:
        out = json.loads(resp.read())
    assert "text" in out and len(out["text"]) == 1

    # validation error path
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api",
        data=json.dumps({"prompts": [], "tokens_to_generate": 4}).encode(),
        method="PUT",
    )
    try:
        urllib.request.urlopen(req)
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400
    httpd_holder["srv"].shutdown()


def test_server_demo_page_and_real_handler(model_and_params):
    """The REAL MegatronServer.run handler (not a test stub): GET /
    serves the demo page (reference serves megatron/static/index.html),
    PUT /api generates, unknown paths 404."""
    from megatron_llm_tpu.text_generation_server import MegatronServer

    model, params = model_and_params
    server = MegatronServer(model, params, _FakeTokenizer())
    t = threading.Thread(
        target=server.run, kwargs={"host": "127.0.0.1", "port": 0},
        daemon=True)
    t.start()
    import time

    for _ in range(100):
        if getattr(server, "httpd", None) is not None:
            break
        time.sleep(0.05)
    assert getattr(server, "httpd", None) is not None, \
        "server.run() never bound (thread died during startup?)"
    port = server.httpd.server_address[1]

    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/") as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/html")
            page = resp.read().decode()
        assert "playground" in page and '"api"' in page

        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api",
            data=json.dumps({"prompts": ["1 2 3"],
                             "tokens_to_generate": 4}).encode(),
            method="PUT")
        with urllib.request.urlopen(req) as resp:
            out = json.loads(resp.read())
        assert "text" in out and len(out["text"]) == 1

        # a null knob (cleared UI field) must be a 400, not a dead socket
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api",
            data=json.dumps({"prompts": ["1 2 3"], "top_k": None}).encode(),
            method="PUT")
        try:
            urllib.request.urlopen(req)
            assert False, "expected 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400

        try:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope")
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        server.httpd.shutdown()


def test_extra_stop_ids_and_pairs(model_and_params):
    """stop_on_eol/double-eol semantics: a row stops at an extra stop id
    or a (prev, cur) bigram exactly like eod."""
    model, params = model_and_params
    toks = jnp.asarray([[1, 2, 3, 4]])
    lens = jnp.asarray([4])
    base, n_base, _ = generate_tokens(
        model, params, toks, lens, jax.random.PRNGKey(0),
        max_new_tokens=8, min_prompt_len=4, greedy=True)
    base_row = np.asarray(base)[0]
    first_gen = int(base_row[4])
    assert first_gen != 0

    # stopping on the first generated token: generation freezes there
    out, n_stop, _ = generate_tokens(
        model, params, toks, lens, jax.random.PRNGKey(0),
        max_new_tokens=8, min_prompt_len=4, greedy=True,
        extra_stop_ids=(first_gen,))
    row = np.asarray(out)[0]
    assert int(row[4]) == first_gen
    # generation stopped right after the stop token: the rest of the row
    # is never written (stays at the zero initialization)
    assert int(n_stop) == 5 and all(int(t) == 0 for t in row[5:])

    # bigram stop: (prompt-last, first-gen) matches immediately
    out2, n2, _ = generate_tokens(
        model, params, toks, lens, jax.random.PRNGKey(0),
        max_new_tokens=8, min_prompt_len=4, greedy=True,
        stop_pairs=((4, first_gen),))
    row2 = np.asarray(out2)[0]
    assert int(n2) == 5 and all(int(t) == 0 for t in row2[5:])


def test_ban_pairs_changes_sampling(model_and_params):
    """prevent_newline_after_colon semantics: the banned token can never
    follow the trigger token."""
    model, params = model_and_params
    toks = jnp.asarray([[1, 2, 3, 4]])
    lens = jnp.asarray([4])
    base, _, _ = generate_tokens(
        model, params, toks, lens, jax.random.PRNGKey(0),
        max_new_tokens=8, min_prompt_len=4, greedy=True)
    row = np.asarray(base)[0]
    first_gen = int(row[4])
    # ban exactly what greedy would pick after the prompt's last token
    out, _, _ = generate_tokens(
        model, params, toks, lens, jax.random.PRNGKey(0),
        max_new_tokens=8, min_prompt_len=4, greedy=True,
        ban_pairs=((4, first_gen),))
    assert int(np.asarray(out)[0][4]) != first_gen


def test_top_p_decay_runs_and_bounds():
    """Dynamic (traced) top_p filter: decayed top_p must floor at bound
    and still produce valid samples."""
    from megatron_llm_tpu.text_generation.sampling import modify_logits

    logits = jnp.asarray(np.random.RandomState(0).randn(2, 16), jnp.float32)
    # tiny traced top_p keeps exactly the top-1 token per row
    out = jax.jit(lambda l, p: modify_logits(l, top_p=p))(
        logits, jnp.float32(1e-6))
    kept = (np.asarray(out) > -1e9).sum(axis=-1)
    np.testing.assert_array_equal(kept, [1, 1])
    # a permissive traced top_p (0.9) keeps more than greedy but not all
    out9 = jax.jit(lambda l, p: modify_logits(l, top_p=p))(
        logits, jnp.float32(0.9))
    kept9 = (np.asarray(out9) > -1e9).sum(axis=-1)
    assert (kept9 >= 1).all() and (kept9 < 16).all()
    # inactive traced top_p (0.0) leaves logits unchanged
    out0 = jax.jit(lambda l, p: modify_logits(l, top_p=p))(
        logits, jnp.float32(0.0))
    np.testing.assert_allclose(np.asarray(out0), np.asarray(logits))


def test_top_p_decay_through_decode(model_and_params):
    """top_p_decay/bound wired through the while-loop body: the decode
    must run, produce valid ids, and differ structurally from no-decay
    only in sampling (shapes/lengths identical)."""
    model, params = model_and_params
    toks = jnp.asarray([[1, 2, 3, 4]])
    lens = jnp.asarray([4])
    out, n, _ = generate_tokens(
        model, params, toks, lens, jax.random.PRNGKey(1),
        max_new_tokens=6, min_prompt_len=4,
        top_p=0.9, top_p_decay=0.8, top_p_bound=0.2)
    row = np.asarray(out)[0]
    assert int(n) == 10 and ((row >= 0) & (row < 64)).all()


@pytest.mark.parametrize("tp,sp", [(2, False), (4, True)])
def test_sharded_generation_matches_unsharded(model_and_params, utils,
                                              tp, sp):
    """Decode with tp-sharded params (vocab-sharded head, heads-sharded
    attention, tp-sharded KV caches) must produce the same tokens as the
    unsharded loop (reference serves under TP x PP:
    megatron/text_generation/forward_step.py:17-204)."""
    model, params = model_and_params
    toks = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 0]])
    lens = jnp.asarray([4, 3])

    want, want_n, _ = generate_tokens(
        model, params, toks, lens, jax.random.PRNGKey(0),
        max_new_tokens=8, min_prompt_len=3, greedy=True)

    from megatron_llm_tpu.parallel import sharding as sh

    utils.initialize_model_parallel(tp=tp)
    try:
        params_sh = sh.shard_params(params, model.param_specs(params))
        got, got_n, _ = generate_tokens(
            model, params_sh, toks, lens, jax.random.PRNGKey(0),
            max_new_tokens=8, min_prompt_len=3, greedy=True)
        spec = params_sh["lm_head"]["weight"].sharding.spec
        assert "tp" in spec, f"head not vocab-sharded: {spec}"
    finally:
        utils.destroy_model_parallel()
    assert int(got_n) == int(want_n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("tp", [2])
def test_sharded_beam_search_matches_unsharded(model_and_params, utils, tp):
    """Beam search with tp-sharded params must return the same beams and
    scores as the unsharded run (the reference serves beams through the
    same TP x PP path as sampling: megatron/text_generation/api.py:147-201
    -> forward_step.py)."""
    model, params = model_and_params
    toks = jnp.asarray([[1, 2, 3]])

    want_beams, want_scores = beam_search(
        model, params, toks, beam_size=3, max_new_tokens=5, eod_id=63)

    from megatron_llm_tpu.parallel import sharding as sh

    utils.initialize_model_parallel(tp=tp)
    try:
        params_sh = sh.shard_params(params, model.param_specs(params))
        got_beams, got_scores = beam_search(
            model, params_sh, toks, beam_size=3, max_new_tokens=5,
            eod_id=63)
        spec = params_sh["lm_head"]["weight"].sharding.spec
        assert "tp" in spec, f"head not vocab-sharded: {spec}"
    finally:
        utils.destroy_model_parallel()
    np.testing.assert_array_equal(np.asarray(got_beams),
                                  np.asarray(want_beams))
    np.testing.assert_allclose(np.asarray(got_scores),
                               np.asarray(want_scores), atol=2e-5)


def test_microbatched_prefill_matches_monolithic(model_and_params):
    """batch_times_seqlen_threshold splits the prefill forward into
    micro-batches (reference forward_step.py:17-204); the generated
    tokens and log-probs must be identical to the monolithic path."""
    model, params = model_and_params
    rng = np.random.RandomState(1)
    toks = jnp.asarray(rng.randint(1, 64, (4, 8)))
    lens = jnp.asarray([8, 8, 8, 8], jnp.int32)
    kw = dict(max_new_tokens=6, min_prompt_len=8, greedy=True,
              return_log_probs=True)
    out_a, len_a, lp_a = generate_tokens(
        model, params, toks, lens, jax.random.PRNGKey(0), **kw)
    # 4*8=32 > 8 -> 4 chunks of batch 1
    out_b, len_b, lp_b = generate_tokens(
        model, params, toks, lens, jax.random.PRNGKey(0),
        batch_times_seqlen_threshold=8, **kw)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))
    np.testing.assert_array_equal(np.asarray(len_a), np.asarray(len_b))
    np.testing.assert_allclose(np.asarray(lp_a), np.asarray(lp_b),
                               atol=2e-5)


def test_cache_len_padding_is_invisible(model_and_params):
    """A padded KV cache (cache_len > prompt+max_new) is masked out:
    tokens, lengths, and log-probs match the exact-size cache bit for
    bit."""
    from megatron_llm_tpu.text_generation.generation import generate_tokens
    model, params = model_and_params
    toks = jnp.array([[3, 5, 7, 9], [2, 4, 0, 0]], jnp.int32)
    lens = jnp.array([4, 2], jnp.int32)
    key = jax.random.PRNGKey(1)
    kw = dict(max_new_tokens=6, min_prompt_len=2, greedy=True,
              return_log_probs=True)
    t0, l0, p0 = generate_tokens(model, params, toks, lens, key, **kw)
    t1, l1, p1 = generate_tokens(model, params, toks, lens, key,
                                 cache_len=4 + 6 + 17, **kw)
    np.testing.assert_array_equal(np.asarray(t0), np.asarray(t1))
    np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))
    np.testing.assert_allclose(np.asarray(p0), np.asarray(p1), atol=1e-5)
