"""Tensor-parallel layers: vocab-parallel embedding, column/row parallel linear.

Reference: ``megatron/core/tensor_parallel/layers.py`` —
``VocabParallelEmbedding`` (:128-210), ``ColumnParallelLinear`` (:410-563),
``RowParallelLinear`` (:566-701), and the fused autograd function
``LinearWithGradAccumulationAndAsyncCommunication`` (:213-317) that
(a) all-gathers sequence-parallel inputs in forward, (b) overlaps the
backward grad allreduce / reduce-scatter with the weight-grad GEMM, and
(c) optionally accumulates wgrad straight into the fp32 main-grad buffer
with a CUDA kernel.

TPU design: the layers are pure functions over param pytrees; placement is
declared with logical-axis sharding constraints (``parallel/sharding.py``)
and GSPMD inserts the collectives:

* ColumnParallel: kernel sharded ('hidden','ffn'→tp).  With sequence
  parallelism the input activation is sharded ('batch','seq_tp',None) and
  XLA materialises the same all-gather-then-GEMM forward / reduce-scatter
  backward as the reference's fused function — and *schedules it to overlap*
  with neighbouring compute, replacing the CUDA-stream trick that required
  CUDA_DEVICE_MAX_CONNECTIONS=1 (layers.py:344-351).
* RowParallel: kernel sharded ('ffn'→tp,'hidden'); output constrained to
  replicated (allreduce) or sequence-sharded (reduce-scatter, the SP path).
* Gradient accumulation into fp32 main grads is the optimizer's job here
  (grads are computed in fp32 master space by jax.grad with a cast), so no
  wgrad-fusion kernel is needed.

The math ignores mesh entirely — the same functions run unsharded in unit
tests and golden comparisons.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from megatron_llm_tpu.parallel.sharding import constrain
from megatron_llm_tpu.quantization import dequantize_kernel


# ---------------------------------------------------------------------------
# Init methods (reference: megatron/model/utils.py init_method_normal /
# scaled_init_method_normal; full-tensor init then slice semantics in
# layers.py:79-125 — with a single-controller mesh we just init the full
# tensor, so TP-size-invariant initialization holds by construction).
# ---------------------------------------------------------------------------

def init_method_normal(std: float):
    def init(key, shape, dtype=jnp.float32):
        return std * jax.random.normal(key, shape, dtype=jnp.float32).astype(dtype)

    return init


def init_method_for(cfg):
    """Trunk weight init from config: xavier-uniform when the reference's
    ``--init_method_xavier_uniform`` is set, else normal(std)."""
    if getattr(cfg, "init_method_xavier_uniform", False):
        glorot = jax.nn.initializers.glorot_uniform()

        def init(key, shape, dtype=jnp.float32):
            if len(shape) >= 2:
                return glorot(key, shape, dtype)
            return jnp.zeros(shape, dtype)

        return init
    return init_method_normal(cfg.init_method_std)


def scaled_init_method_normal(std: float, num_layers: int):
    scaled = std / math.sqrt(2.0 * num_layers)
    return init_method_normal(scaled)


def init_linear_params(
    key,
    in_dim: int,
    out_dim: int,
    *,
    bias: bool = True,
    init_method=None,
    dtype=jnp.float32,
):
    if init_method is None:
        init_method = init_method_normal(0.02)
    params = {"kernel": init_method(key, (in_dim, out_dim), dtype)}
    if bias:
        params["bias"] = jnp.zeros((out_dim,), dtype=dtype)
    return params


def init_embedding_params(
    key, vocab_size: int, hidden: int, *, init_method=None, dtype=jnp.float32
):
    if init_method is None:
        init_method = init_method_normal(0.02)
    return {"embedding": init_method(key, (vocab_size, hidden), dtype)}


# ---------------------------------------------------------------------------
# Apply functions.
# ---------------------------------------------------------------------------

def vocab_parallel_embedding(
    tokens: jax.Array, params, compute_dtype=None
) -> jax.Array:
    """Embedding lookup over a vocab-sharded table.

    Reference (layers.py:128-210) masks out-of-shard ids, looks up locally
    and allreduces.  Under GSPMD a gather from a ('vocab'→tp,'hidden') table
    lowers to exactly that masked-lookup + allreduce; we just write the
    gather.
    """
    table = params["embedding"]
    if compute_dtype is not None:
        table = table.astype(compute_dtype)
    out = jnp.take(table, tokens, axis=0)
    return constrain(out, "batch", "seq", None)


def _lora_delta(x, params):
    """LoRA low-rank path (lora.py): two thin matmuls, never the
    materialized [in, out] update.  lora_scale is a CONSTANT (alpha/r):
    stop_gradient keeps it out of training even though it rides in the
    trainable tree for structure (the optimizer also WD-excludes it)."""
    a = params["lora_A"].astype(x.dtype)
    b = params["lora_B"].astype(x.dtype)
    scale = jax.lax.stop_gradient(params["lora_scale"]).astype(x.dtype)
    return jnp.einsum("...r,ro->...o",
                      jnp.einsum("...i,ir->...r", x, a), b) * scale


def column_parallel_linear(
    x: jax.Array,
    params,
    *,
    out_logical: str = "ffn",
    sequence_parallel: bool = False,
    compute_dtype=None,
    skip_bias_add: bool = False,
):
    """y = x @ W (+ b); W is output-dim sharded over tp.

    Reference: ColumnParallelLinear.forward (layers.py:531-563).  When
    ``sequence_parallel`` the incoming x is sequence-sharded and GSPMD
    all-gathers it (the reference's explicit fwd all-gather,
    layers.py:225-243).

    A kernel of rank 3 is a gated MLP's first projection held paired,
    ``[2, h, F]`` (``parallel/glu_pairs.py``; never with LoRA leaves or
    int8 scales): y is ``[..., 2, F]``, ``out_logical`` on ``F``.
    """
    kernel = dequantize_kernel(params, compute_dtype)
    bias = params.get("bias")
    if compute_dtype is not None:
        bias = bias.astype(compute_dtype) if bias is not None else None
    if sequence_parallel:
        x = constrain(x, "batch", "seq_tp", None)
    if kernel.ndim == 3:
        y = jnp.einsum("...h,ghf->...gf", x, kernel)
        y = constrain(y, "batch", "seq", None, out_logical)
    else:
        y = jnp.einsum("...h,hf->...f", x, kernel)
        if "lora_A" in params:
            y = y + _lora_delta(x, params)
        y = constrain(y, "batch", "seq", out_logical)
    if bias is not None and not skip_bias_add:
        y = y + bias
    if skip_bias_add:
        return y, bias
    return y


def row_parallel_linear(
    x: jax.Array,
    params,
    *,
    in_logical: str = "ffn",
    sequence_parallel: bool = False,
    compute_dtype=None,
    skip_bias_add: bool = False,
):
    """y = x @ W (+ b); W is input-dim sharded over tp, so the partial
    products are summed across tp.

    Reference: RowParallelLinear.forward (layers.py:665-701) — allreduce of
    the output, or reduce-scatter along sequence when sequence-parallel.
    GSPMD derives the same from the constraint on y: ('batch','seq',None)
    forces allreduce; ('batch','seq_tp',None) forces reduce-scatter.
    Bias is added *after* the reduction, on the full output (reference adds
    bias post-reduction so it is applied once, not tp times).
    """
    kernel = dequantize_kernel(params, compute_dtype)
    bias = params.get("bias")
    if compute_dtype is not None:
        bias = bias.astype(compute_dtype) if bias is not None else None
    x = constrain(x, "batch", "seq", in_logical)
    y = jnp.einsum("...f,fh->...h", x, kernel)
    if "lora_A" in params:
        y = y + _lora_delta(x, params)
    if sequence_parallel:
        y = constrain(y, "batch", "seq_tp", None)
    else:
        y = constrain(y, "batch", "seq", None)
    if bias is not None and not skip_bias_add:
        y = y + bias
    if skip_bias_add:
        return y, bias
    return y


def parallel_lm_logits(
    hidden: jax.Array,
    word_embedding_or_head: jax.Array,
    *,
    sequence_parallel: bool = False,
    compute_dtype=None,
) -> jax.Array:
    """Logits = hidden @ E^T over the (tied or untied) vocab-sharded matrix.

    Reference: ``parallel_lm_logits`` (megatron/model/language_model.py:24-53)
    — a column-parallel matmul against the embedding transpose, output kept
    vocab-parallel (logits feed the vocab-parallel CE).
    """
    w = word_embedding_or_head
    if compute_dtype is not None:
        w = w.astype(compute_dtype)
    if sequence_parallel:
        hidden = constrain(hidden, "batch", "seq_tp", None)
    logits = jnp.einsum("...h,vh->...v", hidden, w)
    return constrain(logits, "batch", "seq", "vocab")
