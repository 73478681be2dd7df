"""Megatron mp_rank checkpoint interop (reference checkpointing.py layout):
export -> re-import round trip, TP-shard merge, PP-stage merge, v<2.0 QKV
fixups, and logit parity through the model."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

torch = pytest.importorskip("torch")

from megatron_llm_tpu.models.llama import LlamaModel, llama_config
from weights_conversion.megatron_ckpt import (
    fix_qkv_ordering,
    load_reference_checkpoint,
    read_tracker,
    save_reference_checkpoint,
    )


def _tiny_model():
    cfg = llama_config("tiny", num_layers=2, hidden_size=64,
                       num_attention_heads=4, ffn_hidden_size=96,
                       padded_vocab_size=128, seq_length=32,
                       max_position_embeddings=32)
    return cfg, LlamaModel(cfg)


def _leaves_equal(a, b):
    la = jax.tree_util.tree_leaves_with_path(a)
    lb = dict(jax.tree_util.tree_leaves_with_path(b))
    assert len(la) == len(lb)
    for path, leaf in la:
        np.testing.assert_allclose(np.asarray(leaf, np.float32),
                                   np.asarray(lb[path], np.float32),
                                   rtol=0, atol=1e-6, err_msg=str(path))


def test_export_import_round_trip(tmp_path):
    cfg, model = _tiny_model()
    params = model.init(jax.random.PRNGKey(0))
    save_reference_checkpoint(str(tmp_path), 7, params, cfg)
    assert read_tracker(str(tmp_path)) == "7"
    assert (tmp_path / "iter_0000007" / "mp_rank_00"
            / "model_optim_rng.pt").exists()

    loaded, config, meta = load_reference_checkpoint(str(tmp_path))
    assert meta["checkpoint_version"] == 3.0
    assert config["num_layers"] == 2
    assert config["padded_vocab_size"] == 128
    assert not config["tie_embed_logits"]
    _leaves_equal(params, loaded)


def test_logit_parity_after_round_trip(tmp_path):
    cfg, model = _tiny_model()
    params = model.init(jax.random.PRNGKey(1))
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, 128, (1, 32)))
    ref_logits = model(params, toks)

    save_reference_checkpoint(str(tmp_path), 3, params, cfg)
    loaded, _, _ = load_reference_checkpoint(str(tmp_path))
    out = model(loaded, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_logits),
                               rtol=0, atol=1e-5)


def test_tp_sharded_export_imports_identically(tmp_path):
    cfg, model = _tiny_model()
    params = model.init(jax.random.PRNGKey(2))
    save_reference_checkpoint(str(tmp_path / "tp2"), 1, params, cfg,
                              tensor_parallel=2)
    names = sorted(p.name for p in (tmp_path / "tp2"
                                    / "iter_0000001").iterdir())
    assert names == ["mp_rank_00", "mp_rank_01"]
    loaded, _, _ = load_reference_checkpoint(str(tmp_path / "tp2"))
    _leaves_equal(params, loaded)


def test_pp_sharded_import(tmp_path):
    """Synthesize a pp=2 reference checkpoint by re-filing a pp=1 export's
    layers into mp_rank_00_000 / mp_rank_00_001 with local indices."""
    cfg, model = _tiny_model()
    params = model.init(jax.random.PRNGKey(3))
    save_reference_checkpoint(str(tmp_path / "flat"), 1, params, cfg)
    sd = torch.load(tmp_path / "flat" / "iter_0000001" / "mp_rank_00"
                    / "model_optim_rng.pt", weights_only=False)
    lm = sd["model"]["language_model"]

    def stage_sd(stage):
        enc = {}
        for k, v in lm["encoder"].items():
            if k.startswith(f"layers.{stage}."):
                enc[k.replace(f"layers.{stage}.", "layers.0.")] = v
        out = {"model": {"language_model": {"encoder": enc}},
               "checkpoint_version": 3.0, "iteration": 1, "args": sd["args"]}
        if stage == 0:
            out["model"]["language_model"]["embedding"] = lm["embedding"]
        else:
            out["model"]["language_model"]["lm_head"] = lm["lm_head"]
            enc["final_layernorm.weight"] = \
                lm["encoder"]["final_layernorm.weight"]
        return out

    pp_dir = tmp_path / "pp2" / "iter_0000001"
    for stage in (0, 1):
        d = pp_dir / f"mp_rank_00_{stage:03d}"
        d.mkdir(parents=True)
        torch.save(stage_sd(stage), d / "model_optim_rng.pt")
    with open(tmp_path / "pp2" / "latest_checkpointed_iteration.txt",
              "w") as f:
        f.write("1")

    loaded, config, _ = load_reference_checkpoint(str(tmp_path / "pp2"))
    assert config["num_layers"] == 2
    _leaves_equal(params, loaded)


@pytest.mark.parametrize("version", [0, 1.0])
def test_qkv_version_fixup_import(tmp_path, version):
    """A v<2.0 checkpoint (old interleaved qkv row order) must import to
    the same params as its v2 counterpart."""
    cfg, model = _tiny_model()
    params = model.init(jax.random.PRNGKey(4))
    save_reference_checkpoint(str(tmp_path), 1, params, cfg)
    path = tmp_path / "iter_0000001" / "mp_rank_00" / "model_optim_rng.pt"
    sd = torch.load(path, weights_only=False)
    enc = sd["model"]["language_model"]["encoder"]
    nh, hd = 4, 64 // 4
    for k in list(enc):
        if k.endswith("attention.query_key_value.weight"):
            w = enc[k].numpy()          # v2 grouped layout [np,3,hn,...]
            x = w.reshape(nh, 3, hd, -1)
            if version == 0:            # v0 stored [3, np, hn, ...]
                old = np.swapaxes(x, 0, 1).reshape(w.shape)
            else:                       # v1 stored [np, hn, 3, ...]
                old = np.transpose(x, (0, 2, 1, 3)).reshape(w.shape)
            enc[k] = torch.from_numpy(np.ascontiguousarray(old))
    sd["checkpoint_version"] = version
    torch.save(sd, path)

    loaded, _, meta = load_reference_checkpoint(str(tmp_path))
    assert meta["checkpoint_version"] == float(version)
    _leaves_equal(params, loaded)


def test_fix_qkv_ordering_skips_gqa():
    w = np.arange(4 * 3 * 2 * 5, dtype=np.float32).reshape(-1, 5)
    out = fix_qkv_ordering(w, 1.0, num_heads=4, num_heads_kv=2, head_dim=2)
    np.testing.assert_array_equal(w, out)


def test_checkpoint_util_format_bridge(tmp_path):
    """tools/checkpoint_util.py converts megatron torch <-> orbax in one
    CLI call: megatron -> orbax -> megatron with identical weights."""
    import os
    import subprocess
    import sys

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg, model = _tiny_model()
    params = model.init(jax.random.PRNGKey(0))
    meg1 = tmp_path / "meg1"
    save_reference_checkpoint(str(meg1), 7, params, cfg)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"

    def run(src_fmt, dst_fmt, src, dst):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "checkpoint_util.py"),
             "--load_dir", str(src), "--save_dir", str(dst),
             "--input_format", src_fmt, "--output_format", dst_fmt],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]

    orb = tmp_path / "orb"
    run("megatron", "orbax", meg1, orb)
    meg2 = tmp_path / "meg2"
    run("orbax", "megatron", orb, meg2)

    got, _, meta = load_reference_checkpoint(str(meg2))
    _leaves_equal(got, params)
    assert int(meta["iteration"]) == 7


def test_qkv_bias_export_import_round_trip(tmp_path):
    """qwen2-style QKV biases survive the reference-layout export/import
    (TP-sharded both ways)."""
    from megatron_llm_tpu.models.qwen2 import Qwen2Model, qwen2_config

    cfg = qwen2_config("tiny", num_layers=2, hidden_size=64,
                       num_attention_heads=4, num_attention_heads_kv=4,
                       ffn_hidden_size=96, padded_vocab_size=128,
                       seq_length=32, max_position_embeddings=32)
    model = Qwen2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    d = tmp_path / "meg"
    save_reference_checkpoint(str(d), 3, params, cfg, tensor_parallel=2)
    got, conf, meta = load_reference_checkpoint(str(d))
    _leaves_equal(got, params)
