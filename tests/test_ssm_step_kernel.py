"""The decode step's state-space recurrence as one in-place kernel
(``ops/pallas/ssm_step.py``), in interpret mode on the CPU, against the
XLA step: ``PagedKVCache.step_state`` on the ``'xla'`` path, which reads
every row's state, advances it and puts every slot back.

The two published shapes (Nemotron-3-Nano's 64 heads in eight groups,
Granite-4.0-H-Small's 128 in one), cut in rows only: a row of the first
is one block of heads, of the second two.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.ops import paged_kv
from megatron_llm_tpu.ops.pallas import paged_attention as pa
from megatron_llm_tpu.ops.pallas import ssm_step

F32 = jnp.float32
# heads, d_head, d_state, groups
SHAPES = {"nemotron_64_heads_8_groups": (64, 64, 128, 8),
          "granite_128_heads_1_group": (128, 64, 128, 1)}
ROWS, SLOTS = 5, 6          # a slot no row of the step has, and the garbage row
# which rows have a token this step, and which of them start a request
PATTERNS = {
    "every_row_live": ([1, 1, 1, 1, 1], [0, 0, 0, 0, 0]),
    "idle_rows_between_live_ones": ([0, 1, 0, 1, 0], [0, 0, 0, 0, 0]),
    "idle_rows_first_and_a_fresh_row": ([0, 0, 1, 1, 1], [0, 0, 1, 0, 0]),
    "one_live_row_then_idle_ones": ([1, 0, 0, 0, 0], [0, 0, 0, 0, 0]),
    "live_idle_and_fresh_rows_mixed": ([1, 0, 1, 0, 1], [1, 1, 0, 0, 1]),
    "no_row_live": ([0, 0, 0, 0, 0], [0, 0, 0, 0, 0]),
}


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(pa, "_INTERPRET", True)


def _operands(shape):
    nh, dh, ds, g = SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(len(shape)), 5)
    return (jax.random.normal(ks[0], (SLOTS + 1, nh, dh, ds), F32),
            jax.random.uniform(ks[1], (ROWS, nh), F32, 0.2, 1.0),
            jax.random.normal(ks[2], (ROWS, nh, dh), F32),
            jax.random.normal(ks[3], (ROWS, g, ds), F32),
            jax.random.normal(ks[4], (ROWS, g, ds), F32))


def _step(kernel, pool, live, fresh, *step):
    """``step_state`` of a decode step's cache (row s is slot s) over
    ``pool``: ``y`` and the pool's ``ssm_state`` as the step leaves it."""
    cache = paged_kv.PagedKVCache(
        {"ssm_state": pool, "conv_state": jnp.zeros((SLOTS + 1, 3, 8))},
        None, jnp.where(jnp.asarray(fresh) > 0, 0, 7).astype(jnp.int32),
        jnp.asarray(live, jnp.int32), kernel=kernel, group=paged_kv.STATE)
    y, cache = cache.step_state(*step)
    return np.asarray(y), np.asarray(cache.pool["ssm_state"])


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernel_is_the_xla_step_on_the_live_rows(shape, pattern,
                                                     interpret):
    live, fresh = (np.asarray(x, bool) for x in PATTERNS[pattern])
    fresh &= live
    pool, decay, dx, B, C = _operands(shape)
    # what a fresh row's slot held must not matter: not even a NaN
    pool = pool.at[np.flatnonzero(fresh)].set(jnp.nan)
    before = np.asarray(pool)
    want_y, want = _step("xla", pool, live, fresh, decay, dx, B, C)
    got_y, got = _step("pallas", pool, live, fresh, decay, dx, B, C)

    # a live row: float32 rounding (the CPU contracts the reference's
    # multiply and add; the kernel sums y's products in another order)
    np.testing.assert_allclose(got[:ROWS][live], want[:ROWS][live],
                               rtol=0, atol=4e-6)
    np.testing.assert_allclose(got_y[live], want_y[live], rtol=0, atol=2e-4)
    assert np.isfinite(got[:ROWS][live]).all()
    # an idle row's slot, the slot no row has and the garbage row come
    # back bit for bit; an idle row's y is zeros
    np.testing.assert_array_equal(got[:ROWS][~live], before[:ROWS][~live])
    np.testing.assert_array_equal(got[ROWS:], before[ROWS:])
    assert not got_y[~live].any()
    # a fresh row starts from zeros whatever the slot held: decay * 0 +
    # dx B is dx B to the bit
    heads = decay.shape[1] // B.shape[1]
    outer = np.asarray(dx)[..., None] * np.repeat(
        np.asarray(B), heads, axis=1)[:, :, None, :]
    np.testing.assert_array_equal(got[:ROWS][fresh], outer[fresh])


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_lowered_call_is_one_named_kernel_that_aliases_the_pool(shape):
    """Lowered for a TPU (no chip, no compiler: the Mosaic call as the
    program would hold it): ONE custom call, named as a trace's
    ``breakdown`` prints it, whose second result is its pool operand's
    buffer, and the donated pool is the program's own second result."""
    pool, decay, dx, B, C = _operands(shape)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype)
            for a in (pool, decay, dx, B, C)]
    args += [jax.ShapeDtypeStruct((ROWS,), jnp.bool_)] * 2
    text = jax.jit(ssm_step.ssm_state_step, donate_argnums=0).trace(
        *args).lower(lowering_platforms=("tpu",)).as_text()
    calls = re.findall(r"stablehlo\.custom_call @tpu_custom_call.*", text)
    assert len(calls) == 1
    assert 'kernel_name = "ssm_state_step"' in calls[0]
    # four prefetched scalars, delta x, B and C come before the pool
    assert re.search(r"output_operand_aliases = \[#stablehlo\."
                     r"output_operand_alias<output_tuple_indices = \[1\], "
                     r"operand_index = 7,", calls[0]), calls[0][-400:]
    assert re.findall(r"tf\.aliasing_output = (\d+)", text) == ["1"]


@pytest.mark.parametrize("heads,d_head,d_state,block", [
    (64, 64, 128, 64), (128, 64, 128, 64), (8, 16, 8, 8), (96, 64, 128, 48),
    (128, 128, 128, 32)])
def test_a_block_is_the_most_heads_that_fit(heads, d_head, d_state, block):
    assert ssm_step.head_block(heads, d_head, d_state) == block
