"""Fused LayerNorm (mean+variance, scale+bias) Pallas TPU kernel.

Completes the reference's mixed-precision fused LayerNorm family
(``megatron/fused_kernels/layer_norm_cuda_kernel.cu``,
``megatron/model/fused_layer_norm.py``) alongside the RMSNorm kernel
(``rmsnorm.py`` — same Mosaic-legal layout rules: (1, h) row-vector
blocks for the affine params and their grads, (n, 1) per-row stats,
cross-row grad reductions accumulated in VMEM scratch across the
sequential TPU grid, padded rows masked out of reductions).

Forward:  y = (x - mu) * rstd * gamma + beta,  rstd = 1/sqrt(var + eps)
Backward (two-reduction form of the CUDA kernel):
  xhat   = (x - mu) * rstd
  ggam   = g * gamma
  dx     = rstd * (ggam - mean(ggam) - xhat * mean(ggam * xhat))
  dgamma = sum over rows of g * xhat ;  dbeta = sum over rows of g

Dispatch: TPU backend -> kernel; elsewhere -> jnp reference
(``ops.layernorm.layer_norm``).  Interpret-mode tests run on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_llm_tpu.ops.layernorm import layer_norm
# shared with the RMSNorm kernel: the VMEM-budgeted row-block heuristic
# (see rmsnorm._pick_rows's docstring for the 1 MiB / 8-sublane invariants)
from megatron_llm_tpu.ops.pallas.rmsnorm import _pick_rows

_INTERPRET = False


def _use_pallas() -> bool:
    from megatron_llm_tpu import topology
    from megatron_llm_tpu.ops.pallas import pallas_backend_available

    if topology.sharded_auto_mesh_active():
        # see rmsnorm.py: norm kernels defer to the partitionable XLA
        # norm under GSPMD auto sharding (manual-only regions keep it)
        return False
    return _INTERPRET or pallas_backend_available()


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, mu_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    rstd = jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    y = xc * rstd * g_ref[:].astype(jnp.float32) \
        + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mu_ref[:] = mu
    rstd_ref[:] = rstd


def _bwd_kernel(x_ref, g_ref, gr_ref, mu_ref, rstd_ref,
                dx_ref, dg_ref, db_ref, dg_scr, db_scr, *, n, rows):
    i = pl.program_id(0)
    nblocks = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        dg_scr[:] = jnp.zeros_like(dg_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    row_valid = (i * rows + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0)) < n
    x = jnp.where(row_valid, x_ref[:].astype(jnp.float32), 0.0)
    g = jnp.where(row_valid, gr_ref[:].astype(jnp.float32), 0.0)
    gamma = g_ref[:].astype(jnp.float32)            # [1, h]
    mu = jnp.where(row_valid, mu_ref[:], 0.0)       # [rows, 1]
    rstd = jnp.where(row_valid, rstd_ref[:], 0.0)
    xhat = (x - mu) * rstd
    ggam = g * gamma
    m1 = jnp.mean(ggam, axis=-1, keepdims=True)
    m2 = jnp.mean(ggam * xhat, axis=-1, keepdims=True)
    dx = rstd * (ggam - m1 - xhat * m2)
    dx_ref[:] = dx.astype(dx_ref.dtype)
    dg_scr[:] += jnp.sum(g * xhat, axis=0, keepdims=True)
    db_scr[:] += jnp.sum(g, axis=0, keepdims=True)

    @pl.when(i == nblocks - 1)
    def _finish():
        dg_ref[:] = dg_scr[:]
        db_ref[:] = db_scr[:]


def _fwd_call(x2d, scale, bias, eps):
    n, h = x2d.shape
    rows = _pick_rows(n, h, x2d.dtype.itemsize)
    y, mu, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        name="layernorm_fwd",
        grid=(pl.cdiv(n, rows),),
        in_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x2d.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=_INTERPRET,
    )(x2d, scale.reshape(1, h), bias.reshape(1, h))
    return y, mu, rstd


def _bwd_call(x2d, scale, g2d, mu, rstd, eps):
    n, h = x2d.shape
    rows = _pick_rows(n, h, x2d.dtype.itemsize)
    dx, dg, db = pl.pallas_call(
        functools.partial(_bwd_kernel, n=n, rows=rows),
        name="layernorm_bwd",
        grid=(pl.cdiv(n, rows),),
        in_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, h), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x2d.dtype),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, h), jnp.float32),
                        pltpu.VMEM((1, h), jnp.float32)],
        interpret=_INTERPRET,
    )(x2d, scale.reshape(1, h), g2d, mu, rstd)
    return dx, dg[0], db[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
                     eps: float = 1e-5):
    if not _use_pallas():
        return layer_norm(x, scale, bias, eps=eps, fp32_compute=True)
    shape = x.shape
    y, _, _ = _fwd_call(x.reshape(-1, shape[-1]), scale, bias, eps)
    return y.reshape(shape)


def _vjp_fwd(x, scale, bias, eps):
    if not _use_pallas():
        return (layer_norm(x, scale, bias, eps=eps, fp32_compute=True),
                (x, scale, bias, None, None))
    shape = x.shape
    y, mu, rstd = _fwd_call(x.reshape(-1, shape[-1]), scale, bias, eps)
    return y.reshape(shape), (x, scale, bias, mu, rstd)


def _vjp_bwd(eps, res, g):
    x, scale, bias, mu, rstd = res
    shape = x.shape
    if mu is None:
        _, vjp = jax.vjp(
            lambda xx, ss, bb: layer_norm(xx, ss, bb, eps=eps,
                                          fp32_compute=True),
            x, scale, bias,
        )
        return vjp(g)
    dx, dg, db = _bwd_call(
        x.reshape(-1, shape[-1]), scale, g.reshape(-1, shape[-1]),
        mu, rstd, eps,
    )
    return (dx.reshape(shape), dg.astype(scale.dtype),
            db.astype(bias.dtype))


fused_layer_norm.defvjp(_vjp_fwd, _vjp_bwd)
