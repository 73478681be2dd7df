"""tools/run_text_generation_server.py --int8_weights --int8_kv_cache e2e:
model presets applied from --model_name, weights quantized at load,
REST API serves generation."""

import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_server_int8_cli(tmp_path):
    vocab = tmp_path / "vocab.txt"
    toks = (["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]", "hello",
             "world", "##s"] + [f"tok{i}" for i in range(120)])
    vocab.write_text("\n".join(toks))
    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # the pytest conftest forces an 8-device CPU mesh via XLA_FLAGS;
    # this server smoke is the single-device case (dp=8 would demand
    # global_batch_size % 8 == 0)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable,
         os.path.join(ROOT, "tools", "run_text_generation_server.py"),
         "--model_name=llama2", "--num_layers=2", "--hidden_size=64",
         "--num_attention_heads=4", "--seq_length=64",
         "--max_position_embeddings=64", "--micro_batch_size=1",
         "--global_batch_size=1",
         "--tokenizer_type=BertWordPieceLowerCase",
         f"--vocab_file={vocab}", "--int8_weights", "--int8_kv_cache",
         f"--port={port}", "--host=127.0.0.1"],
        cwd=ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    # drain the merged output continuously: chatty XLA compilation can
    # fill the ~64KB pipe buffer and deadlock the child before it binds
    chunks = []
    drain = threading.Thread(
        target=lambda: chunks.extend(iter(proc.stdout.readline, "")),
        daemon=True)
    drain.start()
    out = last = None
    try:
        body = json.dumps({"prompts": ["hello world"],
                           "tokens_to_generate": 4}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api", data=body,
            headers={"Content-Type": "application/json"}, method="PUT")
        deadline = time.time() + 540
        while time.time() < deadline and proc.poll() is None:
            try:
                with urllib.request.urlopen(req, timeout=10) as r:
                    out = json.loads(r.read())
                break
            except Exception as e:  # server still compiling/binding
                last = e
                time.sleep(5)
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except Exception:
            proc.kill()
        drain.join(timeout=10)
        out_text = "".join(chunks)
    assert out is not None, (
        f"server never answered: {last}\n--- server output ---\n"
        f"{out_text[-3000:]}")
    assert isinstance(out["text"][0], str) and len(out["tokens"][0]) > 2
    assert "int8 weights:" in out_text, out_text[-2000:]
