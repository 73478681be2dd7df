"""Fused RMSNorm Pallas TPU kernel with custom VJP.

Replaces the reference's mixed-precision fused LayerNorm/RMSNorm CUDA
kernels (``megatron/fused_kernels/layer_norm_cuda_kernel.cu``,
``megatron/model/fused_layer_norm.py:125-139``): one pass over VMEM rows,
fp32 accumulation, bf16 I/O.

Forward: y = x * rsqrt(mean(x^2) + eps) * scale, computed per row-block.
Backward (hand-derived, matching the CUDA kernel's two-reduction form):
  dx = rstd * (g*scale - x * rstd^2 * mean(g*scale*x))
  dscale = sum over rows of g * x * rstd

Dispatch: TPU backend -> kernel; elsewhere -> jnp reference
(``ops.layernorm.rms_norm``).  Tested in interpret mode on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_llm_tpu.ops.layernorm import rms_norm

_INTERPRET = False
_BLOCK_ROWS = 256


def _use_pallas() -> bool:
    from megatron_llm_tpu import topology
    from megatron_llm_tpu.ops.pallas import pallas_backend_available

    if topology.sharded_auto_mesh_active():
        # GSPMD cannot auto-partition Mosaic kernels; unlike flash
        # attention (head/batch-local, shard_map-wrapped), the norm
        # kernels see a [tokens, hidden] view that mixes batch and
        # sharded-seq axes, so under auto sharding they defer to the
        # XLA norm (which fuses well and partitions).  Fully-manual
        # regions (pp-only pipelines) keep the pallas kernel.
        return False
    return _INTERPRET or pallas_backend_available()


def _pick_rows(n: int, h: int, itemsize: int) -> int:
    """Row-block height: <=1 MiB per (rows, h) block so the handful of
    double-buffered VMEM blocks (x, g, dx...) stay inside the ~16 MiB
    scoped-vmem budget at any hidden size; multiple of 8 sublanes."""
    budget = 1 << 20
    rows = max(8, min(_BLOCK_ROWS, budget // max(1, h * itemsize) // 8 * 8))
    return min(rows, max(8, n))


def _fwd_kernel(x_ref, s_ref, y_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    y = x * rstd * s_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    rstd_ref[:] = rstd                      # [rows, 1]


def _bwd_kernel(x_ref, s_ref, g_ref, rstd_ref, dx_ref, ds_ref, ds_scr,
                *, eps, n, rows):
    i = pl.program_id(0)
    nblocks = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        ds_scr[:] = jnp.zeros_like(ds_scr)

    # mask padded rows of the final block (block padding is undefined
    # memory; it must not leak into the cross-row dscale reduction)
    row_valid = (i * rows + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0)) < n
    x = jnp.where(row_valid, x_ref[:].astype(jnp.float32), 0.0)
    g = jnp.where(row_valid, g_ref[:].astype(jnp.float32), 0.0)
    s = s_ref[:].astype(jnp.float32)        # [1, h]
    rstd = jnp.where(row_valid, rstd_ref[:], 0.0)  # [rows, 1]
    gs = g * s
    h = x.shape[-1]
    m = jnp.sum(gs * x, axis=-1, keepdims=True) / h
    dx = rstd * (gs - x * (rstd * rstd) * m)
    dx_ref[:] = dx.astype(dx_ref.dtype)
    # dscale accumulates across the (sequential) TPU grid in VMEM scratch
    ds_scr[:] += jnp.sum(g * x * rstd, axis=0, keepdims=True)

    @pl.when(i == nblocks - 1)
    def _finish():
        ds_ref[:] = ds_scr[:]


def _fwd_call(x2d, scale, eps):
    n, h = x2d.shape
    rows = _pick_rows(n, h, x2d.dtype.itemsize)
    grid = (pl.cdiv(n, rows),)
    y, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        name="rmsnorm_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x2d.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=_INTERPRET,
    )(x2d, scale.reshape(1, h))
    return y, rstd


def _bwd_call(x2d, scale, g2d, rstd, eps):
    n, h = x2d.shape
    rows = _pick_rows(n, h, x2d.dtype.itemsize)
    nblocks = pl.cdiv(n, rows)
    dx, ds = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, n=n, rows=rows),
        name="rmsnorm_bwd",
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, h), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x2d.dtype),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, h), jnp.float32)],
        interpret=_INTERPRET,
    )(x2d, scale.reshape(1, h), g2d, rstd)
    return dx, ds[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def fused_rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5):
    if not _use_pallas():
        return rms_norm(x, scale, eps=eps, fp32_compute=True)
    shape = x.shape
    y, _ = _fwd_call(x.reshape(-1, shape[-1]), scale, eps)
    return y.reshape(shape)


def _vjp_fwd(x, scale, eps):
    if not _use_pallas():
        return rms_norm(x, scale, eps=eps, fp32_compute=True), (x, scale, None)
    shape = x.shape
    y, rstd = _fwd_call(x.reshape(-1, shape[-1]), scale, eps)
    return y.reshape(shape), (x, scale, rstd)


def _vjp_bwd(eps, res, g):
    x, scale, rstd = res
    shape = x.shape
    if rstd is None:
        # jnp fallback backward
        _, vjp = jax.vjp(
            lambda xx, ss: rms_norm(xx, ss, eps=eps, fp32_compute=True),
            x, scale,
        )
        return vjp(g)
    dx, ds = _bwd_call(
        x.reshape(-1, shape[-1]), scale, g.reshape(-1, shape[-1]), rstd, eps
    )
    return dx.reshape(shape), ds.astype(scale.dtype)


fused_rms_norm.defvjp(_vjp_fwd, _vjp_bwd)
