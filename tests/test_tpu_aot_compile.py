"""The main path's Pallas kernels, compiled for a TPU v5e that is described,
not attached (on-chip-measurement guide, section 2.3).

The TPU's own compiler is installed with jaxlib and needs no chip: it
refuses what interpret mode lets through (a slice off the tiling, too
much fast memory).  Every case compiles one kernel at Mistral-7B widths
— 32 query heads, 8 kv heads, head_dim 128, hidden 4096 — or, for the
experts' grouped matmul, at each served sparse model's, and asserts
that the result holds a Mosaic call (``tpu_custom_call``), i.e. that the
kernel was taken and not its jnp reference.  Compiles, not chip runs:
they say nothing about results or speed.  One more program rides the
same child: the engine's sampler at OLMoE's 64 slots x 50,304 logits,
whose vocabulary-wide sort must be one and sit under a conditional.

All cases compile in ONE child process (this file run as a script): the
compiler library admits one process at a time and is held until that
process ends, so loading it into the pytest process would lock out every
later test that compiles this way.  The child sets no compilation cache
(an entry written for a described device cannot be read back).
"""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BF16 = jnp.bfloat16
HEADS, KV_HEADS, HEAD_DIM, HIDDEN, SEQ = 32, 8, 128, 4096, 4096
SLOTS, MAX_LEN = 8, 4096        # the engine's default decode batch


def _flash(window=None, grad=False):
    from megatron_llm_tpu.ops.pallas.flash_attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, sliding_window=window)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    return fn, [((1, SEQ, HEADS, HEAD_DIM), BF16),
                ((1, SEQ, KV_HEADS, HEAD_DIM), BF16),
                ((1, SEQ, KV_HEADS, HEAD_DIM), BF16)]


def _rms_norm(rows):
    from megatron_llm_tpu.ops.pallas.rmsnorm import fused_rms_norm

    def loss(x, scale):
        return fused_rms_norm(x, scale).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1)), [((rows, HIDDEN), BF16),
                                            ((HIDDEN,), BF16)]


def _layer_norm(rows):
    from megatron_llm_tpu.ops.pallas.layernorm import fused_layer_norm

    def loss(x, scale, bias):
        return fused_layer_norm(x, scale, bias).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2)), [
        ((rows, HIDDEN), BF16), ((HIDDEN,), BF16), ((HIDDEN,), BF16)]


def _paged(q_tokens, slots, page=16, int8=False, window=None,
           heads=HEADS, kv_heads=KV_HEADS, tokens=MAX_LEN,
           head_dim=HEAD_DIM):
    """Decode (q_tokens=1), a prefill chunk or the K+1 verify step over a
    pool sized as the engine sizes it: full backing for 8 slots of 4096
    tokens plus the garbage page.  ``heads`` / ``kv_heads`` 16 / 16 is
    OLMoE's layout: one query head a key-value group; 32 / 4 with 48
    slots of up to 20,992 ``tokens`` (a table of 1,312 entries, the walk's
    largest in SMEM) and a window of 2,048 is Trinity-Mini's two groups,
    32 / 2 with 64 slots of 6,144 Nemotron-3-Nano's; 16 / 2 at a
    ``head_dim`` of 256, a head two lane rows, with 32 slots of 33,792
    Qwen3-Next's."""
    from megatron_llm_tpu.ops.pallas import paged_attention as pa

    pages = SLOTS * (MAX_LEN // page) + 1
    pool = ((pages, page, kv_heads, head_dim), jnp.int8 if int8 else BF16)
    scale = ((pages, page, kv_heads), jnp.float32)
    shapes = [pool, pool, ((slots, tokens // page), jnp.int32),
              ((slots,), jnp.int32)] + ([scale, scale] if int8 else [])

    def fn(q, k_pages, v_pages, tables, lens, k_scales=None, v_scales=None):
        kw = dict(k_scales=k_scales, v_scales=v_scales,
                  sliding_window=window)
        if q_tokens == 1:
            return pa.paged_attention_decode(q[:, 0], k_pages, v_pages,
                                             tables, lens, **kw)
        return pa.paged_attention_prefill(q, k_pages, v_pages, tables, lens,
                                          **kw)

    return fn, [((slots, q_tokens, heads, head_dim), BF16)] + shapes


def _paged_packed(q_tokens, slots, heads=32, kv_heads=8, head_dim=64,
                  tokens=4352, pages=16385, page=16):
    """The same over a pool of 64-WIDE heads as ``ops/paged_kv.py`` holds
    it, two heads a 128-lane row, through ``PagedKVCache.attend`` (the
    write, the queries set in their own half of a row, the walk, the own
    half of its output): LFM2's 32 / 8 heads of 64, 128 slots of up to
    4,352 tokens over 16,385 pages.  Straight through the walk at
    ``[pages, 16, 8, 64]`` Mosaic refuses both kernels ("Slice shape along
    dimension 3 must be aligned to tiling (128), but is 64")."""
    from megatron_llm_tpu.ops import paged_kv

    pool = ((pages, page, kv_heads // 2, 2 * head_dim), BF16)
    kv = ((slots, q_tokens, kv_heads, head_dim), BF16)

    def fn(q, k, v, k_pages, v_pages, tables, lens, valid):
        cache = paged_kv.PagedKVCache(
            {"k_pages": k_pages, "v_pages": v_pages}, tables, lens, valid,
            kernel="pallas")
        ctx, cache = cache.attend(q, k, v, None)
        return ctx, cache.pool

    return fn, [((slots, q_tokens, heads, head_dim), BF16), kv, kv, pool,
                pool, ((slots, tokens // page), jnp.int32),
                ((slots,), jnp.int32), ((slots,), jnp.int32)]


def _experts(rows, experts, hidden, ffn, layers=8):
    """The dropless expert layer's two grouped matmuls over a model's
    stacked experts (``models/moe.py``) at the four served widths
    (OLMoE's 64 experts of 1024, Mixtral's 8 of 14336, Keye's 128 of 768,
    Mellum's 64 of 896) and a decode step's, a chunk's and a verify
    step's rows.  The compiler's own VMEM check is the guard of the
    kernel's budget (``grouped_matmul._VMEM_BUDGET``): the blocks
    ``tiles`` chooses must fit beside what Mosaic itself needs."""
    from megatron_llm_tpu.ops.pallas.grouped_matmul import grouped_matmul

    def fn(x, w_in, w_out, sizes):
        mid = grouped_matmul(x, w_in, sizes)
        mid = jax.nn.silu(mid[:, :ffn]) * mid[:, ffn:]
        return grouped_matmul(mid, w_out, sizes)

    groups = layers * experts
    return fn, [((rows, hidden), BF16), ((groups, hidden, 2 * ffn), BF16),
                ((groups, ffn, hidden), BF16), ((groups,), jnp.int32)]


def _state_step(rows, heads, groups, d_head=64, d_state=128):
    """A decode step's state-space recurrence in place over a pool of
    ``rows`` slots and the garbage row, at the two served widths
    (Nemotron-3-Nano's 64 heads in eight groups: a row one block of 2
    MiB; Granite-4.0-H-Small's 128 in one: two)."""
    from megatron_llm_tpu.ops.pallas.ssm_step import ssm_state_step

    f32 = jnp.float32
    return ssm_state_step, [
        ((rows + 1, heads, d_head, d_state), f32), ((rows, heads), f32),
        ((rows, heads, d_head), f32), ((rows, groups, d_state), f32),
        ((rows, groups, d_state), f32), ((rows,), jnp.bool_),
        ((rows,), jnp.bool_)]


def _delta_step(rows, key_heads=16, value_heads=32, d=128):
    """A decode step's gated delta rule in place over a pool of ``rows``
    slots and the garbage row at Qwen3-Next's widths (32 value heads of
    [128, 128] float32: a row one block of 2 MiB)."""
    from megatron_llm_tpu.ops.pallas.delta_step import delta_state_step

    f32 = jnp.float32
    return delta_state_step, [
        ((rows + 1, value_heads, d, d), f32), ((rows, key_heads, d), BF16),
        ((rows, key_heads, d), BF16), ((rows, value_heads, d), BF16),
        ((rows, value_heads), f32), ((rows, value_heads), f32),
        ((rows,), jnp.bool_), ((rows,), jnp.bool_)]


def _delta_chunk(rows, tokens, key_heads=16, value_heads=32, d=128):
    """A prefill chunk's gated delta rule at Qwen3-Next's widths (16 key
    heads serving 32 value heads of [128, 128] float32; a key head's two
    value heads a program, 128 rows of systems): the cell's ``[1, 512]``
    chunk, eight blocks, and rows whose length is no multiple of the
    block."""
    import functools

    from megatron_llm_tpu.ops.pallas.delta_chunk import delta_state_chunk

    f32 = jnp.float32
    return functools.partial(delta_state_chunk, cdtype=BF16), [
        ((rows, tokens, key_heads, d), BF16),
        ((rows, tokens, key_heads, d), BF16),
        ((rows, tokens, value_heads, d), BF16),
        ((rows, tokens, value_heads), f32), ((rows, tokens, value_heads), f32),
        ((rows, value_heads, d, d), f32)]


def _retention_chunk(rows, tokens, slots=16, g=8, r=5, d=128):
    """A prefill chunk's power retention in place over a state group of
    ``slots`` slots and the garbage row at Brumby's widths (8 key-value
    heads of 5 query heads of 128, 65 rotations: a head's state 4.26 MB,
    in and out through the pipeline's two buffers each): the cell's
    ``[1, 512]`` chunk, four blocks, and rows whose length is no multiple
    of the block."""
    import functools

    from megatron_llm_tpu.ops.pallas.retention_chunk import (
        retention_state_chunk)

    f32, O = jnp.float32, d // 2 + 1
    return functools.partial(retention_state_chunk, cdtype=BF16), [
        ((slots + 1, g, O, d, d), f32), ((rows, g, O, d), f32),
        ((rows, tokens, g, r, d), BF16), ((rows, tokens, g, d), BF16),
        ((rows, tokens, g, d), BF16), ((rows, tokens, g), f32),
        ((rows,), jnp.int32), ((rows,), jnp.int32), ((rows,), jnp.bool_)]


def _selected(q_tokens, slots, tokens=33792, page=16):
    """Keye's sparse attention through the paged pool (scores and choice:
    ``ops/pallas/dsa_attention.py``; attention under the choice: the
    shared walk of ``paged_attention.py`` with a mask, under the
    selection's two kernel names, since PR 57) at the cell's
    shapes: 32 query and 4 kv heads of 128, an indexer of 16 heads of 64
    (held 128 wide, as the pool holds its keys),
    top-k 2,048, 8 slots of up to 33,792 tokens over a pool of 8,193
    pages; the decode step and the [1, 512] chunk."""
    from megatron_llm_tpu.ops.pallas import dsa_attention as dsa

    pages, table = 8193, tokens // page
    pool = ((pages, page, 4, HEAD_DIM), BF16)

    def fn(q, iq, iw, k_pages, v_pages, index_pages, tables, lens, valid):
        return dsa.paged_selected_attention(
            q, iq, iw, k_pages, v_pages, index_pages, tables, lens, valid,
            topk=2048, softmax_scale=HEAD_DIM ** -0.5)

    return fn, [((slots, q_tokens, HEADS, HEAD_DIM), BF16),
                ((slots, q_tokens, 16, 128), BF16),
                ((slots, q_tokens, 16), jnp.float32), pool, pool,
                ((pages, page, 128), BF16), ((slots, table), jnp.int32),
                ((slots,), jnp.int32), ((slots,), jnp.int32)]


def _paged_window(q_tokens, slots, tokens=33792, page=16):
    """Mellum's window group at the cell's shapes: 32 query and 4 kv heads
    of 128, a window of 1,024, 32 slots of up to 33,792 tokens (a table of
    2,112 entries) over the group's 32 x 97 + 1 pages; the decode step
    and the [1, 512] chunk, under the window group's kernel names."""
    from megatron_llm_tpu.ops.pallas import paged_attention as pa

    pool = ((32 * 97 + 1, page, 4, HEAD_DIM), BF16)

    def fn(q, k_pages, v_pages, tables, lens):
        kw = dict(sliding_window=1024, name_suffix="_window")
        if q_tokens == 1:
            return pa.paged_attention_decode(q[:, 0], k_pages, v_pages,
                                             tables, lens, **kw)
        return pa.paged_attention_prefill(q, k_pages, v_pages, tables, lens,
                                          **kw)

    return fn, [((slots, q_tokens, 32, HEAD_DIM), BF16), pool, pool,
                ((slots, tokens // page), jnp.int32), ((slots,), jnp.int32)]


def _latent(q_tokens, slots, tokens=32768, page=16):
    """Kanana's latent pool at the cell's shapes: 32 query heads over rows
    of 640 (a latent of 512, a rotary key of 64, zeros), 16 slots of up
    to 32,768 tokens (a table of 2,048 entries) over 12,289 pages.  The
    decode step: absorbed queries of the row's width, the values the
    rows' first 512 columns.  The [1, 512] chunk: queries of 128 + 64 a
    head and the up-projection [512, 32, 128 + 128], each block of
    latents expanded in the kernel."""
    from megatron_llm_tpu.ops.pallas import paged_attention as pa

    pool = [((12289, page, 640), BF16), ((slots, tokens // page), jnp.int32),
            ((slots,), jnp.int32), ((slots,), jnp.int32)]
    if q_tokens == 1:
        def step(q, pages, tables, lens, valid):
            return pa.latent_attention_decode(
                q, pages, tables, lens, valid_lens=valid, value_width=512,
                softmax_scale=192 ** -0.5)

        return step, [((slots, 32, 640), BF16)] + pool

    def chunk(q_nope, q_rope, kv_up, pages, tables, lens, valid):
        return pa.latent_attention_prefill(
            q_nope, q_rope, kv_up, pages, tables, lens, valid_lens=valid,
            softmax_scale=192 ** -0.5)

    return chunk, [((slots, q_tokens, 32, 128), BF16),
                   ((slots, q_tokens, 32, 64), BF16),
                   ((512, 32, 256), BF16)] + pool


def _selected_latent(q_tokens, slots, tokens=66560, page=16):
    """GLM-5's selection over its latent pool at the cell's shapes: 64
    query heads over rows of 640 (a latent of 512, a rotary key of 64,
    zeros) beside indexer keys of 128, an indexer of 32 heads of 128,
    top-k 2,048, 10 slots of up to 66,560 tokens (a table of 4,160
    entries) over 20,481 pages.  The decode step: absorbed queries of the
    row's width under the mask of chosen rows.  The [1, 512] chunk:
    queries of 192 + 64 a head and the up-projection [512, 64, 192 +
    256], each block of latents expanded in the kernel, a block's slice
    of the mask riding with it."""
    from megatron_llm_tpu.ops.pallas import dsa_attention as dsa

    pool = [((20481, page, 640), BF16), ((20481, page, 128), BF16),
            ((slots, tokens // page), jnp.int32), ((slots,), jnp.int32),
            ((slots,), jnp.int32)]
    index = [((slots, q_tokens, 32, 128), BF16),
             ((slots, q_tokens, 32), jnp.float32)]
    kw = dict(topk=2048, softmax_scale=256 ** -0.5, value_width=512)
    if q_tokens == 1:
        def step(q, iq, iw, pages, index_pages, tables, lens, valid):
            return dsa.paged_selected_latent_attention(
                q, None, None, iq, iw, pages, index_pages, tables, lens,
                valid, **kw)

        return step, [((slots, 1, 64, 640), BF16)] + index + pool

    def chunk(q_nope, q_rope, kv_up, iq, iw, pages, index_pages, tables,
              lens, valid):
        return dsa.paged_selected_latent_attention(
            q_nope, q_rope, kv_up, iq, iw, pages, index_pages, tables, lens,
            valid, **kw)

    return chunk, [((slots, q_tokens, 64, 192), BF16),
                   ((slots, q_tokens, 64, 64), BF16),
                   ((512, 64, 448), BF16)] + index + pool


CASES = {
    "dsa_selected_latent_decode_10_slots": lambda: _selected_latent(1, 10),
    "dsa_selected_latent_prefill_chunk_512":
        lambda: _selected_latent(512, 1),
    "latent_decode_16_slots": lambda: _latent(1, 16),
    "latent_prefill_chunk_512": lambda: _latent(512, 1),
    "paged_window_decode_32_slots": lambda: _paged_window(1, 32),
    "paged_window_prefill_chunk_512": lambda: _paged_window(512, 1),
    "moe_experts_mellum_32_rows": lambda: _experts(32 * 8, 64, 2304, 896),
    "moe_experts_mellum_chunk_512_rows":
        lambda: _experts(512 * 8, 64, 2304, 896),
    "ssm_state_step_nemotron_64_rows": lambda: _state_step(64, 64, 8),
    "ssm_state_step_granite_24_rows": lambda: _state_step(24, 128, 1),
    "delta_state_step_qwen3_next_32_rows": lambda: _delta_step(32),
    "delta_chunk_qwen3_next_512_tokens": lambda: _delta_chunk(1, 512),
    "delta_chunk_qwen3_next_two_rows_of_300": lambda: _delta_chunk(2, 300),
    "paged_decode_2_kv_heads_of_256_32_slots":
        lambda: _paged(1, 32, heads=16, kv_heads=2, tokens=33792,
                       head_dim=256),
    "paged_prefill_chunk_512_2_kv_heads_of_256":
        lambda: _paged(512, 1, heads=16, kv_heads=2, tokens=33792,
                       head_dim=256),
    "moe_experts_qwen3_next_32_rows":
        lambda: _experts(32 * 10, 128, 2048, 512),
    "moe_experts_qwen3_next_chunk_512_rows":
        lambda: _experts(512 * 10, 128, 2048, 512),
    "retention_chunk_brumby_512_tokens": lambda: _retention_chunk(1, 512),
    "retention_chunk_brumby_two_rows_of_300":
        lambda: _retention_chunk(2, 300),
    "dsa_selected_decode_8_slots": lambda: _selected(1, 8),
    "dsa_selected_prefill_chunk_512": lambda: _selected(512, 1),
    "moe_experts_keye_8_rows": lambda: _experts(8 * 8, 128, 2048, 768,
                                                layers=7),
    "moe_experts_keye_chunk_512_rows":
        lambda: _experts(512 * 8, 128, 2048, 768, layers=7),
    "flash_fwd": lambda: _flash(),
    "flash_fwd_window_1024": lambda: _flash(window=1024),
    "flash_fused_bwd": lambda: _flash(grad=True),
    "flash_fused_bwd_window_1024": lambda: _flash(window=1024, grad=True),
    "rms_norm_1_row": lambda: _rms_norm(1),
    "rms_norm_8_rows": lambda: _rms_norm(8),
    "rms_norm_64_rows": lambda: _rms_norm(64),
    "rms_norm_4096_rows": lambda: _rms_norm(4096),
    "layer_norm_8_rows": lambda: _layer_norm(8),
    "layer_norm_4096_rows": lambda: _layer_norm(4096),
    "paged_decode_bf16": lambda: _paged(1, SLOTS),
    "paged_decode_bf16_page_32": lambda: _paged(1, SLOTS, page=32),
    "paged_decode_int8": lambda: _paged(1, SLOTS, int8=True),
    "paged_decode_window_4096": lambda: _paged(1, SLOTS, window=4096),
    "paged_decode_int8_window_4096":
        lambda: _paged(1, SLOTS, int8=True, window=4096),
    "paged_prefill_chunk_64": lambda: _paged(64, 1),
    "paged_prefill_chunk_64_page_32": lambda: _paged(64, 1, page=32),
    "paged_prefill_chunk_64_int8": lambda: _paged(64, 1, int8=True),
    "paged_verify_k_plus_1": lambda: _paged(5, SLOTS),
    "paged_verify_k_plus_1_window_4096":
        lambda: _paged(5, SLOTS, window=4096),
    "paged_decode_16_kv_heads":
        lambda: _paged(1, 64, heads=16, kv_heads=16),
    "paged_prefill_chunk_64_16_kv_heads":
        lambda: _paged(64, 1, heads=16, kv_heads=16),
    "paged_decode_4_kv_heads_48_slots":
        lambda: _paged(1, 48, kv_heads=4, tokens=20992),
    "paged_decode_4_kv_heads_48_slots_window_2048":
        lambda: _paged(1, 48, kv_heads=4, tokens=20992, window=2048),
    "paged_prefill_chunk_512_4_kv_heads":
        lambda: _paged(512, 1, kv_heads=4, tokens=20992),
    "paged_decode_2_kv_heads_64_slots":
        lambda: _paged(1, 64, kv_heads=2, tokens=6144),
    "paged_prefill_chunk_512_2_kv_heads":
        lambda: _paged(512, 1, kv_heads=2, tokens=6144),
    "paged_decode_8_kv_heads_of_64_128_slots":
        lambda: _paged_packed(1, 128),
    "paged_prefill_chunk_512_8_kv_heads_of_64":
        lambda: _paged_packed(512, 1),
    "moe_experts_olmoe_512_rows": lambda: _experts(512, 64, 2048, 1024),
    "moe_experts_olmoe_verify_320_rows":
        lambda: _experts(8 * 5 * 8, 64, 2048, 1024),
    "moe_experts_mixtral_64_rows":
        lambda: _experts(64, 8, 4096, 14336, layers=3),
    "moe_experts_mixtral_chunk_128_rows":
        lambda: _experts(128, 8, 4096, 14336, layers=3),
    "moe_experts_mixtral_verify_80_rows":
        lambda: _experts(80, 8, 4096, 14336, layers=3),
}


SAMPLER = "sampler_64_slots_50304_logits"
SELECTION = "dsa_kernels_by_name"
WALK_OPERANDS = "paged_walk_operands"
# the shared walk over a bf16 pool of K and V (not the int8 pools, whose
# scales make the operands fp32, nor a latent pool, whose walks are their
# own lines)
BF16_WALKS = sorted(
    case for case in CASES
    if case.startswith(("paged_", "dsa_selected_")) and "int8" not in case
    and "latent" not in case)
# bytes of HLO temporaries the parent's programs took (PR 61's tree,
# compiled here): the walk's kernels hold theirs in VMEM and take none,
# the selection's chunk its scores and its mask
PARENT_TEMP_BYTES = {"dsa_selected_prefill_chunk_512": 138541056}
ENGINE_TABLES = "engine_tables_tiny_sparse_model"
GRANITE = "granite_cell_programs"
NEMOTRON = "nemotron_cell_programs"
TRINITY = "trinity_cell_programs"
LFM2 = "lfm2_cell_programs"
BRUMBY = "brumby_cell_programs"
QWEN3_NEXT = "qwen3_next_cell_programs"
GLM5 = "glm5_cell_programs"
OURO = "ouro_cell_programs"


def _walk_operands(lowered_text, shapes):
    """What the Mosaic kernels of a lowered program multiply and widen,
    read off their own text (a ``tpu_custom_call`` carries its kernel as
    MLIR bytecode): the operand types of every matmul, and every block of
    K or V (a bf16 vector of 256 rows or more whose last dimension is the
    pool's) that is extended to fp32."""
    import base64

    from jax._src.lib.mlir import ir

    width = max(shape[-1] for shape, dtype in shapes
                if len(shape) == 4 and dtype == BF16)
    matmuls, widened = set(), []
    for body in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                           lowered_text):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True  # the text is all we read
        with ctx:
            text = str(ir.Module.parse(base64.b64decode(body)))
        matmuls |= {" x ".join(t.rsplit("x", 1)[1] for t in m)
                    for m in re.findall(
            r'tpu\.matmul"[^\n]*: \(vector<([^>]+)>, vector<([^>]+)>,',
            text)}
        for dims in re.findall(
                rf'arith\.extf"[^\n]*: \(vector<([0-9x]+)x{width}xbf16>\)',
                text):
            if np.prod([int(n) for n in dims.split("x")]) >= 256:
                widened.append(f"{dims}x{width}")
    return {"matmuls": sorted(matmuls), "widened": widened}


def _compile_all(only: str = ""):
    """The child: compile every case for one described v5e device and
    print ``{case: true | false | "error"}`` (or ``{"skip": why}`` where
    this jaxlib cannot describe the topology).  A cell's two programs
    (``only``: a name of ``CELLS``) take a child each, under ``-m slow``:
    at their real sizes they cost as much as all the kernels together."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - any failure means "cannot"
        print(json.dumps({"skip": f"{type(e).__name__}: {e}"[:300]}))
        return
    chip = SingleDeviceSharding(topo.devices[0])
    if only:
        key, cell = CELLS[only]
        print(json.dumps({key: _cell_programs(chip, *cell())}))
        return
    found = {}
    for case in sorted(CASES):
        fn, shapes = CASES[case]()
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
                for shape, dtype in shapes]
        try:
            lowered = jax.jit(fn).lower(*args)
            compiled = lowered.compile()
            text = compiled.as_text()
            found[case] = "tpu_custom_call" in text
            if case in BF16_WALKS:
                found.setdefault(WALK_OPERANDS, {})[case] = dict(
                    _walk_operands(lowered.as_text(), shapes),
                    temp_bytes=compiled.memory_analysis().temp_size_in_bytes)
            if case.startswith("dsa_"):
                found.setdefault(SELECTION, {})[case] = sorted(re.findall(
                    r"^\s*(?:ROOT )?%?((?:dsa|paged_attention|mla_attention)_\w+?)"
                    r"(?:\.\d+)? = ", text, re.M))
        except Exception as e:  # noqa: BLE001 - the compiler's refusal
            found[case] = f"{type(e).__name__}: {e}"[:2000]
    found[SAMPLER] = _sampler_sorts(chip)
    found[ENGINE_TABLES] = _engine_tables(chip)
    print(json.dumps(found))


def _sampler_sorts(chip):
    """``sample_batched`` as the TPU's compiler leaves it: for each
    ``sort`` of the compiled text, whether a conditional guards it."""
    from _hlo_text import sorts_and_their_guards

    from megatron_llm_tpu.text_generation.sampling import sample_batched

    S, V = 64, 50304
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in (((S, V), jnp.float32), ((S, 2), jnp.uint32),
                                 ((S,), jnp.int32), ((S,), jnp.float32),
                                 ((S,), jnp.float32), ((S,), jnp.bool_))]
    try:
        return sorts_and_their_guards(
            jax.jit(sample_batched).lower(*args).compile().as_text())
    except Exception as e:      # noqa: BLE001 - the compiler's refusal
        return f"{type(e).__name__}: {e}"[:2000]


def _engine_tables(chip):
    """A tiny sparse engine warmed up here, on the CPU, and then its own
    programs' instruction tables as the TPU's compiler leaves them
    (``program_tables(sharding=)`` lowers the programs warm-up ran for
    the described chip): for each program its summary and its kv_pool
    copies, beside the bytes of the pool and of one of its arrays."""
    from megatron_llm_tpu import hlo_collectives
    from megatron_llm_tpu.models.olmoe import OlmoeModel, olmoe_config
    from megatron_llm_tpu.serving import EngineConfig, InferenceEngine

    # what runs here runs on the CPU: no kernel until the tables
    forced = os.environ.pop("MLT_FORCE_PALLAS", None)
    try:
        model = OlmoeModel(olmoe_config("tiny", use_flash_attn=False))
        eng = InferenceEngine(
            model, model.init(jax.random.PRNGKey(0)),
            EngineConfig(num_slots=4, block_size=16, max_model_len=64,
                         prefill_chunk=16))
        eng.warmup()
        tables = eng.program_tables(sharding=chip)
        return {
            "pool_bytes": eng.kv_pool_bytes,
            "array_bytes": sorted({a.nbytes for a in
                                   jax.tree_util.tree_leaves(
                                       eng._st.pages)}),
            "built_for_the_engine": eng.stats()["programs"],
            "programs": {
                name: dict(t.summary(), kv_pool_copies=sorted(
                    {r["root"] for r in t.rows if r["role"] == "kv_pool"
                     and r["root"] in hlo_collectives.COPIES}))
                for name, t in tables.items()}}
    except Exception as e:      # noqa: BLE001 - the compiler's refusal
        return f"{type(e).__name__}: {e}"[:2000]
    finally:
        if forced is not None:
            os.environ["MLT_FORCE_PALLAS"] = forced


def _granite_cell():
    """The benchmark's Granite cell: one period of nine state-space layers
    and one attention layer at the published widths, 36 of 72 experts,
    half the vocabulary, 24 slots of state and 13,313 pages.  (How to
    build its model, its engine's keywords): what ``_cell_plan`` counts
    with no compiler and ``_cell_programs`` compiles for a described
    chip."""
    from megatron_llm_tpu.models.granite import GraniteModel, granite_config

    return lambda: GraniteModel(granite_config(
        "h-small", num_layers=10, num_experts=36, moe_router_experts=72,
        padded_vocab_size=50176, params_dtype="bf16",
        compute_dtype="bf16", seq_length=17408)), dict(
        num_slots=24, num_blocks=13313, max_model_len=17408)


def _nemotron_cell():
    """The same of the benchmark's Nemotron cell: the published pattern's
    first 14 layers (six Mamba-2 mixers of eight groups, two attention
    layers, six expert layers alone) at the published widths, 64 of 128
    experts of 2688 x 1856, half the vocabulary, 64 slots of state and
    24,577 pages."""
    from megatron_llm_tpu.config import pattern_layer_types
    from megatron_llm_tpu.models.nemotron_h import (NANO_PATTERN,
                                                    NemotronHModel,
                                                    nemotron_h_config)

    return lambda: NemotronHModel(nemotron_h_config(
        "nano-30b-a3b", num_layers=14,
        layer_types=pattern_layer_types(NANO_PATTERN[:14]), num_experts=64,
        moe_router_experts=128, padded_vocab_size=65536,
        params_dtype="bf16", compute_dtype="bf16", seq_length=6144)), dict(
        num_slots=64, num_blocks=24577, max_model_len=6144)


def _trinity_cell():
    """The same of the benchmark's Trinity cell: the published model's
    first 8 layers (two dense, six sparse, the period twice) at the
    published widths, 64 of 128 experts of 2048 x 1024, the whole
    vocabulary, 48 slots over a full group of 32,769 pages and a window
    group of 48 x 161."""
    from megatron_llm_tpu.models.trinity import TrinityModel, trinity_config

    return lambda: TrinityModel(trinity_config(
        "mini", num_layers=8, num_experts=64, moe_router_experts=128,
        params_dtype="bf16", compute_dtype="bf16", seq_length=20992)), dict(
        num_slots=48, num_blocks=32769, max_model_len=20992)


def _lfm2_cell():
    """The same of the benchmark's LFM2 cell: the published model's first
    14 layers as they stand (both dense layers, eleven gated short
    convolutions and three attention layers of 64-wide heads) at the
    published widths, all 32 experts of 2048 x 1792, the whole vocabulary
    under a tied head, 128 slots of state and 16,385 pages."""
    from megatron_llm_tpu.models.lfm2 import (PUBLISHED_LAYER_TYPES,
                                              Lfm2Model, lfm2_config)

    return lambda: Lfm2Model(lfm2_config(
        "8b-a1b", num_layers=14, layer_types=PUBLISHED_LAYER_TYPES[:14],
        params_dtype="bf16", compute_dtype="bf16", seq_length=4352)), dict(
        num_slots=128, num_blocks=16385, max_model_len=4352)


def _brumby_cell():
    """The same of the benchmark's Brumby cell: 8 of the published 40
    layers, every one a power retention, at the published widths (40
    query heads over 8 key-value heads of 128, an MLP of 17,408), the
    whole vocabulary under an untied head, 16 slots of state and NO
    page."""
    from megatron_llm_tpu.models.brumby import BrumbyModel, brumby_config

    return lambda: BrumbyModel(brumby_config(
        "14b", num_layers=8, params_dtype="bf16", compute_dtype="bf16",
        seq_length=17920)), dict(num_slots=16, max_model_len=17920)


def _qwen3_next_cell():
    """The same of the benchmark's Qwen3-Next cell: 8 of the published 48
    layers (two periods: three gated delta-rule layers and a gated
    attention layer of 2 key-value heads of 256, twice), 128 of 512
    experts of width 512 held, the whole vocabulary under an untied head,
    32 slots of state and a pool of 32,769 pages."""
    from megatron_llm_tpu.models.qwen3_next import (Qwen3NextModel,
                                                    qwen3_next_config)

    return lambda: Qwen3NextModel(qwen3_next_config(
        "80b-a3b", num_layers=8, num_experts=128, moe_router_experts=512,
        params_dtype="bf16", compute_dtype="bf16", seq_length=33792)), dict(
            num_slots=32, num_blocks=32769, max_model_len=33792)


def _glm5_cell():
    """The same of the benchmark's GLM-5 cell: 5 of the published 78
    layers (one dense and four sparse), 16 of 256 experts of width 2048
    held, an eighth of the vocabulary under an untied head, 10 slots of
    up to 66,560 tokens and a pool of 20,481 pages of latent rows and
    indexer keys."""
    from megatron_llm_tpu.models.glm5 import Glm5Model, glm5_config

    return lambda: Glm5Model(glm5_config(
        "744B-A40B", num_layers=5, moe_first_dense_layers=1, num_experts=16,
        moe_router_experts=256, padded_vocab_size=19456,
        params_dtype="bf16", compute_dtype="bf16", seq_length=66560)), dict(
            num_slots=10, num_blocks=20481, max_model_len=66560)


def _ouro_cell():
    """The same of the benchmark's Ouro cell: 12 of the published 48
    layers, run four times over a pool a pass (48 pools), the whole
    vocabulary under an untied head, 12 slots of up to 3,584 tokens and a
    pool of 897 pages of 48 planes."""
    from megatron_llm_tpu.models.ouro import OuroModel, ouro_config

    return lambda: OuroModel(ouro_config(
        "2.6B", num_layers=12, params_dtype="bf16", compute_dtype="bf16",
        seq_length=3584)), dict(num_slots=12, num_blocks=897,
                                max_model_len=3584)


# a cell's name among the child's arguments, its key in what the child
# prints, and the cell
CELLS = {"granite": (GRANITE, _granite_cell),
         "nemotron": (NEMOTRON, _nemotron_cell),
         "trinity": (TRINITY, _trinity_cell),
         "lfm2": (LFM2, _lfm2_cell),
         "brumby": (BRUMBY, _brumby_cell),
         "qwen3_next": (QWEN3_NEXT, _qwen3_next_cell),
         "glm5": (GLM5, _glm5_cell),
         "ouro": (OURO, _ouro_cell)}
# every cell's engine beside its own keywords
_CELL_ENGINE = dict(block_size=16, prefill_chunk=512, preemption=False,
                    paged_kernel="on", prefill_kernel="on")


# rows of a compiled program that move or compute nothing
_NO_WORK = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
            "custom-call")


def _cell_plan(build, engine):
    """What the engine's plan knows of a cell with no compiler, no pool
    and no weight (``ops/paged_kv.py::plan`` and ``init_pools`` under
    ``jax.eval_shape``, ``model.init`` likewise): a slot's state, the
    pools' bytes as the engine sizes them, the parameters and the
    experts' tiles, under the names ``_cell_programs`` gives what the
    engine itself holds."""
    from megatron_llm_tpu.ops import paged_kv
    from megatron_llm_tpu.ops.pallas import grouped_matmul
    from megatron_llm_tpu.serving import EngineConfig
    from megatron_llm_tpu.serving.kv_blocks import derive_num_blocks

    model = build()
    cfg = EngineConfig(**_CELL_ENGINE, **engine)
    length = min(cfg.max_model_len, model.cfg.max_position_embeddings)
    plan = paged_kv.plan(model.cfg, cfg.block_size, cfg.num_slots,
                         -(-length // cfg.block_size), cfg.prefill_chunk,
                         "pallas", "pallas")
    blocks = derive_num_blocks(cfg.num_slots, cfg.block_size, length,
                               cfg.num_blocks or None) if plan.paged else 1
    leaves = jax.tree_util.tree_leaves
    return {"state_bytes_per_slot": plan.state_bytes_per_slot,
            "pool_bytes": sum(a.size * a.dtype.itemsize for a in leaves(
                jax.eval_shape(lambda: plan.init_pools(blocks)))),
            "parameters": sum(a.size for a in leaves(jax.eval_shape(
                model.init, jax.random.PRNGKey(0)))),
            "moe_expert_tiles": grouped_matmul.moe_expert_tiles(model.cfg)}


def _cell_programs(chip, build, engine):
    """``engine_prefill`` and ``engine_decode`` of a cell over abstract
    weights, compiled for the described chip: what each holds, and which
    of the mixers' scopes and kernels reach the optimised text."""
    from megatron_llm_tpu import hlo_collectives
    from megatron_llm_tpu.ops import paged_kv
    from megatron_llm_tpu.serving import EngineConfig, InferenceEngine

    try:
        model = build()
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        eng = InferenceEngine(model, params, EngineConfig(**_CELL_ENGINE,
                                                          **engine))
        # the recurrent state's shapes (a state-space layer's
        # ``ssm_state``, a retention layer's ``ret_state``), every slot's
        # or every row's
        state = {(hlo_collectives._HLO_DTYPE[a.dtype.name], sh)
                 for p in eng._st.pages for name, a in p.items()
                 if name in ("ssm_state", "ret_state", "delta_state")
                 for sh in (tuple(a.shape), (a.shape[0] - 1,) + a.shape[1:])}
        found = {"state_bytes_per_slot": paged_kv.state_bytes_per_slot(
            eng._st.pages), "pool_bytes": eng.kv_pool_bytes,
            "parameters": sum(a.size for a in
                              jax.tree_util.tree_leaves(params)),
            "moe_expert_tiles": eng.moe_expert_tiles}
        for name, args in eng._program_arguments().items():
            if name not in ("engine_prefill", "engine_decode"):
                continue
            args = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    np.shape(a), a.dtype, sharding=chip), args)
            comp = eng._jitted[name].lower(*args).compile()
            m, text = comp.memory_analysis(), comp.as_text()
            # what else than the step's kernel writes an array of the
            # recurrent state's shape (every slot's, or every row's)
            rows = hlo_collectives.instructions(text)
            rewrites = sorted(
                f"{r['scope']} {r['root']}" for r in rows
                if r["opcode"] not in _NO_WORK and state & set(r["shapes"]))
            found[name] = {
                # a block's phi: a compute-dtype array whose last
                # dimensions are a head size of 128's 65 rotations
                "phi_arrays": sorted({
                    f"{dt}{list(sh)}" for r in rows for dt, sh in r["shapes"]
                    if dt == "bf16" and tuple(sh[-2:]) == (65, 128)}),
                "retention_chunk_calls": len(re.findall(
                    r"retention_state_chunk(?:\.\d+)? = ", text)),
                "delta_chunk_calls": len(re.findall(
                    r"delta_state_chunk(?:\.\d+)? = ", text)),
                # XLA's form of the delta chunk: its solve, and a
                # block's right side and solution [.., 64, 256] float32
                "triangular_solves": len(re.findall(
                    r"triangular.solve", text, re.I)),
                "solve_arrays": sorted({
                    f"{dt}{list(sh)}" for r in rows for dt, sh in r["shapes"]
                    if dt == "f32" and tuple(sh[-2:]) == (64, 256)}),
                "argument_bytes": m.argument_size_in_bytes,
                "output_bytes": m.output_size_in_bytes,
                "alias_bytes": m.alias_size_in_bytes,
                "temp_bytes": m.temp_size_in_bytes,
                "state_rewrites": rewrites,
                "kernels": sorted(set(re.findall(
                    r"(paged_attention_\w+?|mla_attention_\w+?|dsa_\w+?"
                    r"|moe_experts\w*?|ssm_state_step"
                    r"|retention_state_step|retention_state_chunk"
                    r"|delta_state_step|delta_state_chunk)"
                    r"(?:\.\d+)? = ", text))),
                "scopes": sorted({s for s in (
                    "ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_step",
                    "ssm_gate_norm", "ssm_out_proj", "attn_gate",
                    "post_attn_norm", "post_mlp_norm", "conv_in_proj",
                    "short_conv", "conv_out_proj", "retention_gate",
                    "retention_chunk", "retention_step", "delta_proj",
                    "delta_conv", "delta_gate", "delta_chunk", "delta_step",
                    "delta_norm", "mla_query_down", "mla_query_up",
                    "mla_absorb", "dsa_indexer", "loop_pass",
                    "loop_pass_norm")
                    if f"/{s}/" in text})}
        return found
    except Exception as e:      # noqa: BLE001 - the compiler's refusal
        return f"{type(e).__name__}: {e}"[:2000]


def _child(*argv):
    # code that asks jax.default_backend() sees the CPU under such a
    # compile and would take its jnp branch; steer it here, in the test
    env = dict(os.environ, JAX_PLATFORMS="cpu", MLT_FORCE_PALLAS="1",
               PYTHONPATH=ROOT)
    env.setdefault("TPU_LOG_DIR", "disabled")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, os.path.abspath(__file__), *argv],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert proc.returncode == 0 and lines, proc.stderr[-3000:]
    found = json.loads(lines[-1])
    if "skip" in found:
        pytest.skip(f"cannot describe a v5e:2x2 topology here: "
                    f"{found['skip']}")
    return found


@pytest.fixture(scope="module")
def compiled():
    return _child()


def _cell_compiled(name):
    """A cell's two programs as its child compiled them, and what the
    ENGINE it built says of its bytes and counts: the plan's own, which
    the cell's tier-1 test holds to the hand count."""
    key, cell = CELLS[name]
    found = _child(name)[key]
    assert isinstance(found, dict), found
    plan = _cell_plan(*cell())
    assert {k: found[k] for k in plan} == plan
    return found


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, compiled):
    assert compiled[case] is True, (
        f"{case}: compiled without its Mosaic kernel" if not compiled[case]
        else f"{case}: the TPU compiler refused it: {compiled[case]}")


@pytest.mark.parametrize("case", BF16_WALKS)
def test_the_walk_multiplies_in_the_pools_dtype_for_v5e(case, compiled):
    """PR 62: over a bf16 pool every matmul of the walk's kernel (and of
    the selection's two beside it) takes bf16 operands, no block of K or
    V is extended to fp32 in the chunk's text or the step's, and the
    program takes no more HLO temporaries than the parent's did."""
    found = compiled[WALK_OPERANDS][case]
    assert found["matmuls"] == ["bf16 x bf16"], found
    assert found["widened"] == [], found
    assert found["temp_bytes"] <= PARENT_TEMP_BYTES.get(case, 0), found


# the paged chunks whose q-block is 512 rows a kv group or more: with the
# probabilities in two terms their kernel's stack on the chip (Mosaic's,
# which ``temp_size_in_bytes`` above does not see) is 18.92 MiB (16.92 at
# 8 kv heads of 64), over the 16 a kernel gets unasked
TWO_TERM_STACKS = ["paged_prefill_chunk_512_4_kv_heads",
                   "paged_prefill_chunk_512_8_kv_heads_of_64",
                   "paged_window_prefill_chunk_512"]


@pytest.mark.parametrize("case", TWO_TERM_STACKS)
def test_a_two_term_chunks_stack_is_held_to_its_recorded_ceiling(
        case, compiled):
    """PR 62: a paged chunk that carries ``p`` in two terms asks the
    compiler for ``_TWO_TERMS_VMEM_LIMIT`` and for no more, so the ask is
    the ceiling: a block whose temporaries grow past it is refused HERE
    ("Scoped allocation with size ... exceeded scoped vmem limit").  Who
    raises the ceiling records the new stack beside it."""
    from megatron_llm_tpu.ops.pallas import paged_attention as pa

    assert pa._TWO_TERMS_VMEM_LIMIT == 20 * 1024 * 1024
    assert compiled[case] is True, compiled[case]


def test_selection_is_one_kernel_a_name_for_v5e(compiled):
    """The decode step and the chunk each hold ONE scores walk, ONE
    choice and ONE walk under it, under the names a profile's ``XLA Ops``
    line (and the benchmark's ``dsa_*`` metrics) knows: the choice that
    counts over a row's live blocks is one kernel, not a ladder of sizes.
    Over a latent pool (PR 61) the same two of the selection and latent
    attention's own two walks under the mask, by names of their own."""
    assert compiled[SELECTION] == {
        "dsa_selected_latent_decode_10_slots": [
            "dsa_index_scores_decode", "dsa_select_decode",
            "mla_attention_sparse_decode"],
        "dsa_selected_latent_prefill_chunk_512": [
            "dsa_index_scores_prefill", "dsa_select_prefill",
            "mla_attention_prefill_masked"],
        "dsa_selected_decode_8_slots": [
            "dsa_index_scores_decode", "dsa_select_decode",
            "paged_attention_sparse_decode"],
        "dsa_selected_prefill_chunk_512": [
            "dsa_index_scores_prefill", "dsa_select_prefill",
            "paged_attention_prefill_masked"]}


def test_sampler_compiles_to_one_guarded_sort_for_v5e(compiled):
    """What the decode program pays in an all-greedy step is decided by
    this: the TPU's compiler keeps the sampler's one sort inside the
    conditional's branch (it neither hoists nor duplicates it)."""
    assert compiled[SAMPLER] == [True], compiled[SAMPLER]


def test_engine_tables_of_programs_compiled_for_v5e(compiled):
    """The engine's own programs as the TPU's compiler leaves them: the
    decode step owns the pool it is handed (donated: it moves none of
    it, the scatter writes in place), the chunk is lent its pool and
    copies it (whole arrays), and the sampler, the cache's write and the
    three routing scopes reach the optimised instructions."""
    found = compiled[ENGINE_TABLES]
    assert isinstance(found, dict), found
    # lowering for another placement keeps nothing
    assert found["built_for_the_engine"] is None
    programs = found["programs"]
    assert {"engine_decode", "engine_prefill", "engine_sample_first",
            "engine_cow_copy"} <= set(programs)
    (array,) = found["array_bytes"]
    step = programs["engine_decode"]
    assert step["kv_pool_copy_bytes_per_launch"] == 0, step
    assert step["kv_pool_copies"] == [], step
    for name in ("engine_prefill", "engine_cow_copy"):
        moved = programs[name]["kv_pool_copy_bytes_per_launch"]
        assert moved >= found["pool_bytes"] and moved % array == 0, name
        assert programs[name]["kv_pool_copies"], name
    for name in ("engine_decode", "engine_prefill"):
        assert {"kv_write", "attention", "moe_route", "moe_dispatch",
                "moe_combine"} <= set(programs[name]["scopes"]), name
    assert "sampler" in programs["engine_decode"]["scopes"]
    assert set(programs["engine_sample_first"]["scopes"]) == {"sampler"}


# Each cell is asked twice.  What the engine's plan knows with no compiler
# (a slot's state, the pools, the parameters, the experts' tiles, each
# against its hand count) is tier-1's, under the name the whole test had
# when no PR saw a chip.  What needs the TPU's compiler is ``slow``: the
# driver compiles and runs every cell on a real v5e for the parent and
# the change of every PR, and a program that does not compile or fit is
# a ``witness`` line of the ledger.  A ``model_config`` PR runs its own
# (``-m slow -k "cells_programs and <family>"``) before it asks for the
# chip.

def test_the_granite_cells_programs_compile_and_fit_a_v5e():
    """The Granite cell's bytes as the engine's plan counts them: nine
    state-space layers' state a slot (float32) and three columns of the
    convolution's width, 24 slots and the garbage row of them beside
    13,313 pages of one attention layer."""
    found = _cell_plan(*_granite_cell())
    assert found["state_bytes_per_slot"] == 9 * (128 * 64 * 128 * 4
                                                 + 3 * 8448 * 2)
    state = 25 * found["state_bytes_per_slot"]
    assert found["pool_bytes"] == state + 13313 * 16 * 4096


@pytest.mark.slow
@pytest.mark.time_limit(900)
def test_the_granite_cells_programs_compile_for_a_described_v5e():
    """The cell's two programs at its real sizes, for a described v5e:
    both compile with the attention layer's walk as a kernel and the
    state-space mixer's scopes in the text, and what each holds (the
    weights, the pools and the state; the chunk holds them twice, the
    step owns its own and gives them back) fits the chip's 16 GB with
    room for the probe."""
    found = _cell_compiled("granite")
    for name, recurrence in (("engine_prefill", "ssm_scan"),
                             ("engine_decode", "ssm_step")):
        got = found[name]
        held = (got["argument_bytes"] + got["output_bytes"]
                + got["temp_bytes"])
        # 9.51 GB of weights; the pools in and out
        assert 11.2e9 < got["argument_bytes"] < 11.5e9, (name, got)
        assert got["output_bytes"] >= found["pool_bytes"], (name, got)
        # the step's pools and state come back in the buffers they came
        # in (donated); the chunk's are a second set
        if name == "engine_decode":     # in the chip's padded layout
            assert got["alias_bytes"] >= found["pool_bytes"], (name, got)
        else:
            assert got["alias_bytes"] == 0, (name, got)
        assert held - got["alias_bytes"] < 14.5e9, (name, held)
        assert recurrence in got["scopes"] and "ssm_in_proj" in got["scopes"]
        assert any(k.startswith("paged_attention") for k in got["kernels"])
        _the_step_is_the_kernel(name, got)


def _the_step_is_the_kernel(name, got):
    """The decode step advances the recurrent state by the in-place
    kernel and by nothing else: no pad, maximum, select or copy of an
    array of the state's shape is left in it.  A chunk has no such
    kernel (its scan and its write of the state are XLA's)."""
    if name == "engine_decode":
        assert "ssm_state_step" in got["kernels"], got["kernels"]
        assert got["state_rewrites"] == [], got["state_rewrites"]
    else:
        assert "ssm_state_step" not in got["kernels"], got["kernels"]
        assert got["state_rewrites"], name


def test_the_nemotron_cells_programs_compile_and_fit_a_v5e():
    """The Nemotron cell's bytes and counts as the engine's plan has
    them: six Mamba-2 mixers' state a slot, 64 slots and the garbage row
    beside 24,577 pages of two attention layers, the parameters at the
    published widths, the experts' grouped matmul at 2688 x 1856 (a
    block the whole 1856 wide, ``w_in`` laid out at 1920)."""
    found = _cell_plan(*_nemotron_cell())
    assert found["state_bytes_per_slot"] == 6 * (64 * 64 * 128 * 4
                                                 + 3 * 6144 * 2)
    assert found["pool_bytes"] == (65 * found["state_bytes_per_slot"]
                                   + 24577 * 16 * 2048)
    # 4,584.9 M parameters at the published widths (9.17 GB in bf16) and
    # 66.1 M zeros of the experts' first matrices' layout (1856 -> 1920)
    assert found["parameters"] == 4_584_903_936 + 6 * 64 * 2688 * 64
    tiles = found["moe_expert_tiles"]
    assert (tiles["w_in"]["n"], tiles["w_out"]["tk"]) == (1920, 1856)


@pytest.mark.slow
@pytest.mark.time_limit(900)
def test_the_nemotron_cells_programs_compile_for_a_described_v5e():
    """The Nemotron cell's two programs at its real sizes, for a
    described v5e: both compile with the experts' grouped matmul and the
    attention layers' walk (16 query heads a KV head) as kernels and the
    mixer's scopes in the text, and what each holds fits the chip's 16
    GB with room for the probe."""
    found = _cell_compiled("nemotron")
    for name, recurrence in (("engine_prefill", "ssm_scan"),
                             ("engine_decode", "ssm_step")):
        got = found[name]
        held = (got["argument_bytes"] + got["output_bytes"]
                + got["temp_bytes"])
        assert got["output_bytes"] >= found["pool_bytes"], (name, got)
        if name == "engine_decode":
            assert got["alias_bytes"] >= found["pool_bytes"], (name, got)
        assert held - got["alias_bytes"] < 13.5e9, (name, held)
        # no launch moves the experts into another layout (3.83 GB of
        # temporaries a launch at a 1856-wide last dimension)
        assert got["temp_bytes"] < 0.5e9, (name, got)
        assert recurrence in got["scopes"] and "ssm_in_proj" in got["scopes"]
        assert "moe_experts" in got["kernels"], got["kernels"]
        assert any(k.startswith("paged_attention") for k in got["kernels"])
        _the_step_is_the_kernel(name, got)


def test_the_trinity_cells_programs_compile_and_fit_a_v5e():
    """The Trinity cell's bytes and counts as the engine's plan has
    them: no state, two groups of pages, ISSUE 47's parameters, the
    experts' grouped matmul at 2048 x 1024."""
    found = _cell_plan(*_trinity_cell())
    assert found["state_bytes_per_slot"] == 0
    # a full group of 32,769 pages over 2 layers, a window group of
    # 48 x 161 + 1 over 6, 16 tokens of 2,048 B a page and a layer
    assert found["pool_bytes"] == (32769 * 2 + (48 * 161 + 1) * 6) * 16 * 2048
    # ISSUE 47's arithmetic: 2 x 65.0 M + 6 x 436.3 M + 820.0 M
    assert found["parameters"] == 3_568_898_816
    tiles = found["moe_expert_tiles"]
    assert (tiles["w_in"]["k"], tiles["w_in"]["n"]) == (2048, 2048)
    assert (tiles["w_out"]["k"], tiles["w_out"]["n"]) == (1024, 2048)


@pytest.mark.slow
@pytest.mark.time_limit(900)
def test_the_trinity_cells_programs_compile_for_a_described_v5e():
    """The Trinity cell's two programs at its real sizes, for a described
    v5e: both compile with the experts' grouped matmul, BOTH groups'
    walks as kernels and the gate's and the output norms' scopes in the
    text; the step owns its pools and gives them back, the chunk holds
    them twice, and that fits the chip's 16 GB."""
    found = _cell_compiled("trinity")
    for name, walk in (("engine_prefill", "paged_attention_prefill"),
                       ("engine_decode", "paged_attention_decode")):
        got = found[name]
        held = (got["argument_bytes"] + got["output_bytes"]
                + got["temp_bytes"])
        assert got["output_bytes"] >= found["pool_bytes"], (name, got)
        if name == "engine_decode":
            assert got["alias_bytes"] >= found["pool_bytes"], (name, got)
        else:
            assert got["alias_bytes"] == 0, (name, got)
        # 7.14 GB of weights and 3.67 GB of pools, the chunk's twice
        assert held - got["alias_bytes"] < 14.8e9, (name, held)
        assert got["temp_bytes"] < 0.5e9, (name, got)
        assert {"attn_gate", "post_attn_norm", "post_mlp_norm"} <= set(
            got["scopes"]), got["scopes"]
        assert {"moe_experts", walk, walk + "_window"} <= set(
            got["kernels"]), got["kernels"]


def test_the_lfm2_cells_programs_compile_and_fit_a_v5e():
    """The LFM2 cell's bytes and counts as the engine's plan has them: a
    pool of 64-wide heads held two a row (2,048 B a token an attention
    layer, not the 4,096 a last dimension of 64 is laid out at), eleven
    conv layers' columns a slot, ISSUE 51's parameters, the experts'
    grouped matmul at 2048 x 1792."""
    found = _cell_plan(*_lfm2_cell())
    # eleven conv layers of two columns of 2,048 in bf16 a slot
    assert found["state_bytes_per_slot"] == 11 * 2 * 2048 * 2 == 90112
    # 16,385 pages of 16 tokens of 2,048 B over three attention layers,
    # and 128 slots and the garbage row of state
    assert found["pool_bytes"] == (16385 * 3 * 16 * 2048 + 129 * 90112)
    # ISSUE 51's arithmetic: 2 x 60.8 M + 9 x 369.2 M + 3 x 362.9 M +
    # 134.2 M, the head tied
    assert found["parameters"] == 4_667_077_376
    tiles = found["moe_expert_tiles"]
    assert (tiles["w_in"]["k"], tiles["w_in"]["n"]) == (2048, 3584)
    assert (tiles["w_out"]["k"], tiles["w_out"]["n"]) == (1792, 2048)


@pytest.mark.slow
@pytest.mark.time_limit(900)
def test_the_lfm2_cells_programs_compile_for_a_described_v5e():
    """The LFM2 cell's two programs at its real sizes, for a described
    v5e: both compile with the experts' grouped matmul and BOTH walks as
    kernels over the packed pool, the three scopes of the convolution's
    mixer in the text; the step owns its pools and gives them back, the
    chunk holds them twice, and that fits the chip's 15.75 GB at 128
    slots."""
    found = _cell_compiled("lfm2")
    for name, walk in (("engine_prefill", "paged_attention_prefill"),
                       ("engine_decode", "paged_attention_decode")):
        got = found[name]
        held = (got["argument_bytes"] + got["output_bytes"]
                + got["temp_bytes"])
        assert got["output_bytes"] >= found["pool_bytes"], (name, got)
        if name == "engine_decode":
            assert got["alias_bytes"] >= found["pool_bytes"], (name, got)
        else:
            assert got["alias_bytes"] == 0, (name, got)
        # 9.33 GB of weights and 1.62 GB of pools, the chunk's twice
        assert held - got["alias_bytes"] < 15.75e9 * 0.85, (name, held)
        assert got["temp_bytes"] < 0.5e9, (name, got)
        assert {"conv_in_proj", "short_conv", "conv_out_proj"} <= set(
            got["scopes"]), got["scopes"]
        assert {"moe_experts", walk} <= set(got["kernels"]), got["kernels"]


def test_the_brumby_cells_programs_compile_and_fit_a_v5e():
    """The Brumby cell's bytes and counts as the engine's plan has them
    (8 retention layers at the published widths, the whole vocabulary, 16
    slots, no page): 4.67 GB of state that ``jax.eval_shape`` lays out
    and nobody allocates."""
    found = _cell_plan(*_brumby_cell())
    # 8 key-value heads of 65 rotations of [128, 128] and [128], float32
    layer = 8 * (65 * 128 * 128 + 65 * 128) * 4
    assert found["state_bytes_per_slot"] == 8 * layer == 274_759_680
    # 16 slots and the garbage row, and nothing else: no page
    assert found["pool_bytes"] == 17 * 8 * layer
    # ISSUE 54's arithmetic: 8 x 330.35 M + 2 x 777.9 M + the final norm
    assert found["parameters"] == 4_198_652_928
    assert found["moe_expert_tiles"] is None


@pytest.mark.slow
@pytest.mark.time_limit(900)
def test_the_brumby_cells_programs_compile_for_a_described_v5e():
    """The Brumby cell's two programs at its real sizes, for a described
    v5e: the decode step holds a Mosaic call for the recurrence
    (``retention_state_step``), the chunk one a layer
    (``retention_state_chunk``, PR 55: before it the chunk was XLA's,
    wrote one slot's state by a dynamic-update-slice a layer and held a
    block's ``phi``, ``bf16[1, 128, 8, 5, 65, 128]``, among 0.64 GB of
    temporaries), and neither rewrites an array of the state's shape
    outside its kernel; the chunk OWNS its pool, so both programs alias
    the whole state group, and weights, one state and a chunk's
    temporaries fit the chip's 15.75 GB."""
    found = _cell_compiled("brumby")
    for name, scope in (("engine_prefill", "retention_chunk"),
                        ("engine_decode", "retention_step")):
        got = found[name]
        held = (got["argument_bytes"] + got["output_bytes"]
                + got["temp_bytes"])
        assert got["alias_bytes"] >= found["pool_bytes"], (name, got)
        # 8.40 GB of weights, 4.67 GB of state held ONCE, and under 1 GB
        # of temporaries
        assert held - got["alias_bytes"] < 15.75e9 * 0.9, (name, held)
        assert got["temp_bytes"] < 1.0e9, (name, got)
        assert {"retention_gate", scope} <= set(got["scopes"]), got["scopes"]
    assert found["engine_decode"]["kernels"] == ["retention_state_step"]
    assert found["engine_decode"]["state_rewrites"] == []
    chunk = found["engine_prefill"]
    assert chunk["kernels"] == ["retention_state_chunk"]
    # once a layer, the slot written in place, and no phi in HBM
    assert chunk["retention_chunk_calls"] == 8, chunk
    assert found["engine_decode"]["retention_chunk_calls"] == 0
    assert chunk["state_rewrites"] == [], chunk
    assert chunk["phi_arrays"] == [], chunk
    # 0.606 GB (the logits of 512 rows are 0.31 of it) where XLA's chunk
    # held 0.64
    assert chunk["temp_bytes"] < 0.62e9, chunk


def test_the_qwen3_next_cells_programs_compile_and_fit_a_v5e():
    """The Qwen3-Next cell's bytes and counts as the engine's plan has
    them (8 layers at the published widths: six gated delta-rule layers
    and two attention layers of 2 key-value heads of 256, 128 of 512
    experts, the whole vocabulary, 32 slots and 32,769 pages): ISSUE
    58's arithmetic, which ``jax.eval_shape`` lays out and nobody
    allocates."""
    found = _cell_plan(*_qwen3_next_cell())
    # six layers of S [32, 128, 128] float32 and three columns of 8,192
    assert found["state_bytes_per_slot"] == 6 * (32 * 128 * 128 * 4
                                                 + 3 * 8192 * 2) == 12_877_824
    # 33 rows of state, and pages of 4,096 B a token over two layers
    assert found["pool_bytes"] == (33 * 12_877_824
                                   + 32769 * 16 * 2 * 2 * 256 * 2 * 2)
    # 8 x 406.85 M + 2 x 27.26 M + 6 x 33.72 M + 2 x 311.2 M + a norm
    assert found["parameters"] == 4_133_998_720
    # an expert's three matrices are one tile each way: a visit reads
    # whole matrices
    tiles = found["moe_expert_tiles"]
    assert (tiles["w_in"]["tk"], tiles["w_in"]["tn"]) == (2048, 1024)
    assert (tiles["w_out"]["tk"], tiles["w_out"]["tn"]) == (512, 2048)


@pytest.mark.slow
@pytest.mark.time_limit(900)
def test_the_qwen3_next_cells_programs_compile_for_a_described_v5e():
    """The Qwen3-Next cell's two programs at its real sizes, for a
    described v5e: the decode step holds a Mosaic call for the delta
    rule (``delta_state_step``), for the experts and for the walk at
    pages of ``[16, 2, 256]``, owns its pool and rewrites no array of
    the state's shape outside the step's kernel; the chunk holds one
    Mosaic call a delta layer (``delta_state_chunk``, PR 59: before it
    the chunk was XLA's, a ``triangular_solve`` and ``f32[1, 8, 32, 64,
    256]`` right sides and solutions in HBM), is LENT the pool, as every
    paged model's, holds it twice and still fits the chip's 15.75 GB."""
    found = _cell_compiled("qwen3_next")
    step, chunk = found["engine_decode"], found["engine_prefill"]
    assert step["kernels"] == ["delta_state_step", "moe_experts",
                               "paged_attention_decode"]
    assert chunk["kernels"] == ["delta_state_chunk", "moe_experts",
                                "paged_attention_prefill"]
    # once a delta layer, no solve of XLA's and nothing of its shape
    assert chunk["delta_chunk_calls"] == 6, chunk
    assert step["delta_chunk_calls"] == 0
    assert chunk["triangular_solves"] == 0, chunk
    assert chunk["solve_arrays"] == [], chunk
    assert step["alias_bytes"] >= found["pool_bytes"], step
    assert step["state_rewrites"] == [], step
    assert chunk["alias_bytes"] == 0
    for name, got, scope in (("engine_prefill", chunk, "delta_chunk"),
                             ("engine_decode", step, "delta_step")):
        held = (got["argument_bytes"] + got["output_bytes"]
                + got["temp_bytes"] - got["alias_bytes"])
        # 8.27 GB of weights, 2.57 GB of pool (twice in a chunk) and
        # under 0.3 GB of temporaries
        assert held < 15.75e9 * 0.9, (name, held)
        assert got["temp_bytes"] < 0.3e9, (name, got)
        assert {"delta_proj", "delta_conv", "delta_gate", "delta_norm",
                "attn_gate", scope} <= set(got["scopes"]), got["scopes"]


def test_the_glm5_cells_programs_compile_and_fit_a_v5e():
    """The GLM-5 cell's bytes and counts as the engine's plan has them (5
    layers at the published widths: one dense and four sparse, 16 of 256
    experts of 2048, an eighth of the vocabulary, 10 slots and 20,481
    pages of two arrays): ISSUE 61's arithmetic, which ``jax.eval_shape``
    lays out and nobody allocates."""
    found = _cell_plan(*_glm5_cell())
    assert found["state_bytes_per_slot"] == 0
    # a token a layer: 1,280 B of latent row and 256 B of indexer key
    assert found["pool_bytes"] == 20481 * 16 * 5 * (1280 + 256)
    attention = (6144 * 2048 + 2048 + 2048 * 16384 + 6144 * 576 + 512
                 + 512 * 64 * 448 + 16384 * 6144)
    indexer = 2048 * 4096 + 6144 * 128 + 6144 * 32 + 2 * 128
    sparse = (16 + 1) * 3 * 6144 * 2048 + 6144 * 256 + 256
    # 400.9 M + 4 x 817.7 M + 239.1 M, and eleven norms of 6144
    assert found["parameters"] == (
        5 * (attention + indexer + 2 * 6144) + 3 * 6144 * 12288 + 4 * sparse
        + 2 * 19456 * 6144 + 6144) == 3_910_812_416
    # the widest held expert the grouped matmul has met: 6144 x 4096 in,
    # 2048 x 6144 out
    tiles = found["moe_expert_tiles"]
    assert tiles["w_in"]["tk"] * tiles["w_in"]["tn"] * 2 <= 12 * 2 ** 20
    assert tiles["w_out"]["tk"] * tiles["w_out"]["tn"] * 2 <= 12 * 2 ** 20


@pytest.mark.slow
@pytest.mark.time_limit(900)
def test_the_glm5_cells_programs_compile_for_a_described_v5e():
    """The GLM-5 cell's two programs at its real sizes, for a described
    v5e: the decode step holds a Mosaic call for the indexer's scores,
    for the choice, for the shared walk over latent rows under the mask
    and for the experts, and owns its pool; the chunk holds the same
    three of the selection under their chunk's names, the latent chunk's
    own walk under the mask among them, is LENT the pool, holds it twice
    and still fits the chip's 15.75 GB."""
    found = _cell_compiled("glm5")
    step, chunk = found["engine_decode"], found["engine_prefill"]
    assert step["kernels"] == ["dsa_index_scores_decode",
                               "dsa_select_decode",
                               "mla_attention_sparse_decode", "moe_experts"]
    assert chunk["kernels"] == ["dsa_index_scores_prefill",
                                "dsa_select_prefill",
                                "mla_attention_prefill_masked",
                                "moe_experts"]
    assert step["alias_bytes"] >= found["pool_bytes"], step
    assert chunk["alias_bytes"] == 0
    for name, got in (("engine_prefill", chunk), ("engine_decode", step)):
        held = (got["argument_bytes"] + got["output_bytes"]
                + got["temp_bytes"] - got["alias_bytes"])
        # 7.82 GB of weights, 2.52 GB of pool (twice in a chunk), and a
        # chunk's scores and mask over a table of 66,560 keys (0.27 GB)
        assert held < 15.75e9 * 0.92, (name, held)
        assert {"mla_query_down", "mla_query_up",
                "dsa_indexer"} <= set(got["scopes"]), got["scopes"]
    assert "mla_absorb" in step["scopes"]
    assert "mla_absorb" not in chunk["scopes"]


def test_the_ouro_cells_programs_compile_and_fit_a_v5e():
    """The Ouro cell's bytes and counts as the engine's plan has them (12
    layers at the published widths run four times, the whole vocabulary,
    12 slots and 897 pages of 48 planes): ISSUE 63's arithmetic, which
    ``jax.eval_shape`` lays out and nobody allocates."""
    found = _cell_plan(*_ouro_cell())
    assert found["state_bytes_per_slot"] == 0
    # a token a layer A PASS: 16 heads of 128 of K and of V in bf16
    assert found["pool_bytes"] == 897 * 16 * 12 * 4 * 8192 == 5_643_436_032
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    # twelve layers ONCE (the passes share them), embedding and head, the
    # final norm and the exit gate's 2,049
    assert found["parameters"] == (
        12 * layer + 2 * 49152 * 2048 + 2048 + 2049) == 817_991_681
    assert found["moe_expert_tiles"] is None


@pytest.mark.slow
@pytest.mark.time_limit(900)
def test_the_ouro_cells_programs_compile_for_a_described_v5e():
    """The Ouro cell's two programs at its real sizes, UNROLLED (48 layer
    bodies a program), for a described v5e: each holds the paged walk's
    kernel, the scope of a pass and of the norm that ends it; the decode
    step owns its 48 pools, the chunk is LENT them, holds them twice and
    still fits the chip's 15.75 GB."""
    found = _cell_compiled("ouro")
    step, chunk = found["engine_decode"], found["engine_prefill"]
    assert step["kernels"] == ["paged_attention_decode"]
    assert chunk["kernels"] == ["paged_attention_prefill"]
    assert step["alias_bytes"] >= found["pool_bytes"], step
    assert chunk["alias_bytes"] == 0
    for name, got in (("engine_prefill", chunk), ("engine_decode", step)):
        held = (got["argument_bytes"] + got["output_bytes"]
                + got["temp_bytes"] - got["alias_bytes"])
        # 1.64 GB of weights, 5.64 GB of pool (twice in a chunk)
        assert held < 15.75e9 * 0.92, (name, held)
        assert {"loop_pass", "loop_pass_norm", "post_attn_norm",
                "post_mlp_norm"} <= set(got["scopes"]), got["scopes"]


if __name__ == "__main__":
    _compile_all(only=(sys.argv[1:] or [""])[0])
