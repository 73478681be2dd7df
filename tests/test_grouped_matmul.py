"""The experts' grouped matmul (``ops/pallas/grouped_matmul.py``): the
kernel in interpret mode against a plain dot a group, and the tile rule
as a table over the served configurations' widths.

Interpret mode says the kernel's visits, masks and accumulation are
right at every tiling the rule produces; that the TPU's compiler takes
the same tiles under its VMEM limit is ``test_tpu_aot_compile.py``'s
(``-k moe_experts``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.models.keye import keye_config
from megatron_llm_tpu.models.mellum import mellum_config
from megatron_llm_tpu.models.mixtral import mixtral_config
from megatron_llm_tpu.models.olmoe import olmoe_config
from megatron_llm_tpu.ops.pallas import grouped_matmul as gm
from megatron_llm_tpu.ops.pallas.grouped_matmul import moe_expert_tiles

BF16 = jnp.bfloat16


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(gm, "_INTERPRET", True)


# (k, n) -> (k tiles, n tiles) the rule must give there: Mellum's and
# OLMoE's two matrices, widths that take several n tiles, and a width
# with no divisor of 128
SHAPES = {
    "mellum_w_in": ((2304, 1792), (2, 1)),
    "mellum_w_out": ((896, 2304), (1, 1)),
    "olmoe_w_in": ((2048, 2048), (2, 1)),
    "olmoe_w_out": ((1024, 2048), (1, 1)),
    "two_n_tiles": ((1024, 4096), (1, 2)),
    "two_by_two": ((2048, 4096), (2, 2)),
    "tiny_whole": ((64, 96), (1, 1)),
}

# m, group sizes: empty groups, a group over two row tiles (rows 100-159
# of tiles of 128), rows past the groups' sum; fewer rows than a tile;
# a group that owns a whole tile and more
ROWS = {
    "straddle_and_unowned": (300, [0, 100, 60, 0, 90]),
    "below_128_rows": (40, [3, 0, 20, 10, 0]),
    "whole_tile_and_more": (256, [130, 0, 0, 126, 0]),
}


@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernel_matches_a_dot_a_group(interpret, shape, rows):
    """Every owned row against its group's dot.  Rows past the groups'
    sum come back undefined (interpret mode leaves NaN there; the caller
    masks them with a select) and are not looked at."""
    (k, n), (k_tiles, n_tiles) = SHAPES[shape]
    m, sizes = ROWS[rows]
    tm, tk, tn = gm.tiles(k, n, m, BF16)
    assert (k // tk, n // tn) == (k_tiles, n_tiles)
    assert tm == (128 if m >= 128 else 48)
    kx, kw = jax.random.split(jax.random.PRNGKey(k + n + m))
    x = jax.random.normal(kx, (m, k), BF16)
    w = jax.random.normal(kw, (len(sizes), k, n), BF16) * k ** -0.5
    got = np.asarray(gm.grouped_matmul(x, w, jnp.asarray(sizes, jnp.int32)),
                     np.float32)
    assert got.shape == (m, n)
    start = 0
    for g, size in enumerate(sizes):
        # fp32 products of bf16 operands are exact; one rounding
        want = jnp.dot(x[start:start + size].astype(jnp.float32),
                       w[g].astype(jnp.float32),
                       precision="highest").astype(BF16)
        # the sum's order differs, so a result may land one bf16 step
        # (2**-8 of its value) from the reference's
        np.testing.assert_allclose(got[start:start + size],
                                   np.asarray(want, np.float32),
                                   rtol=2 ** -7, atol=2 ** -9)
        start += size


def test_every_group_empty_is_one_visit_that_owns_nothing(interpret):
    """The grid has at least one visit; with no group owning a row it
    must store nothing over what a caller would keep."""
    x = jnp.ones((160, 256), BF16)
    w = jnp.ones((3, 256, 128), BF16)
    sizes = jnp.asarray([0, 0, 0], jnp.int32)
    assert gm.grouped_matmul(x, w, sizes).shape == (160, 128)
    _, _, _, visits = gm._visits(sizes, 2, 128, 256)
    assert int(visits) == 1


# the four served configurations (benchmarks/configs/*.json run these
# widths): w_in [H, 2F] and w_out [F, H] -> block, grid steps a visit
TABLE = {
    "olmoe": (lambda: olmoe_config("1B-7B"),
              {"w_in": ((2048, 2048), (1024, 2048), 2),
               "w_out": ((1024, 2048), (1024, 2048), 1)}),
    "mixtral": (lambda: mixtral_config("8x7B"),
                {"w_in": ((4096, 28672), (1024, 2048), 56),
                 "w_out": ((14336, 4096), (1024, 2048), 28)}),
    "mellum": (lambda: mellum_config("12B-A2.5B"),
               {"w_in": ((2304, 1792), (1152, 1792), 2),
                "w_out": ((896, 2304), (896, 2304), 1)}),
    "keye": (lambda: keye_config("30B-A3B"),
             {"w_in": ((2048, 1536), (1024, 1536), 2),
              "w_out": ((768, 2048), (768, 2048), 1)}),
}


@pytest.mark.parametrize("matrix", ["w_in", "w_out"])
@pytest.mark.parametrize("family", sorted(TABLE))
def test_tiles_of_the_served_configurations(family, matrix):
    """What the engine reports (``stats()['moe_expert_tiles']``) for each
    served width.  OLMoE's and Mixtral's are pinned to the 1024 x 2048
    they have always had: their compiled programs must not move."""
    make, want = TABLE[family]
    (k, n), (tk, tn), steps = want[matrix]
    cfg = make().replace(compute_dtype="bf16")
    got = moe_expert_tiles(cfg)[matrix]
    assert (got["k"], got["n"]) == (k, n)
    assert (got["tk"], got["tn"], got["steps_per_visit"]) == (tk, tn, steps)
    assert got["vmem_bytes"] == gm.vmem_bytes(128, tk, tn, BF16) \
        <= gm._VMEM_BUDGET
    # one tiling a model: a decode step of few rows takes the chunk's
    for rows in (8, 64, 512, 4096):
        assert gm.tiles(k, n, rows, BF16)[1:] == (tk, tn)


def test_the_stated_vmem_bytes():
    """The count by hand, at OLMoE's call: two [1024, 2048] weight
    blocks, two [128, 1024] rows blocks, two [128, 2048] output blocks
    in bf16 and the fp32 accumulator."""
    assert gm.vmem_bytes(128, 1024, 2048, BF16) == (
        2 * 1024 * 2048 * 2 + 2 * 128 * 1024 * 2 + 2 * 128 * 2048 * 2
        + 128 * 2048 * 4) == 11_010_048
    assert gm._VMEM_BUDGET == 12 * 2 ** 20
    # a whole [2304, 1792] block twice is not asked for
    assert gm.vmem_bytes(128, 2304, 1792, BF16) > gm._VMEM_BUDGET
    # fp32 operands hold twice the bytes, so take smaller blocks
    assert gm.tiles(2304, 1792, 128, jnp.float32)[1:] == (1152, 896)


@pytest.mark.parametrize("k,n", [(128, 128), (384, 640), (2304, 7168),
                                 (5120, 1536), (7168, 4096), (100, 2048),
                                 (2048, 72)])
def test_a_tile_divides_its_width_and_fits(k, n):
    _, tk, tn = gm.tiles(k, n, 128, BF16)
    assert k % tk == 0 and n % tn == 0
    assert tk % 128 == 0 or tk == k
    assert tn % 128 == 0 or tn == n
    assert gm.vmem_bytes(128, tk, tn, BF16) <= gm._VMEM_BUDGET
    # no pair of divisors that fits makes fewer steps
    steps = (k // tk) * (n // tn)
    for a in gm._divisors(k):
        for b in gm._divisors(n):
            if gm.vmem_bytes(128, a, b, BF16) <= gm._VMEM_BUDGET:
                assert (k // a) * (n // b) >= steps


def test_a_dense_model_reports_no_tiles():
    assert moe_expert_tiles(olmoe_config("tiny").replace(num_experts=0)) \
        is None
