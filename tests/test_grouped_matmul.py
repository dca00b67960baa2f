"""ops/grouped_matmul in interpret mode at tiny widths, against the plain
formulation it replaced (``grouped_matmul_reference``:
``jax.lax.ragged_dot``), and ``held_experts`` through it.

The interpreter fills what a kernel leaves unwritten with NaN, so "the
rows behind every group are not written" is visible here, and so is a NaN
that leaks from them. Tolerance: float32 products of ~16-64 terms of size
~1 in another order, 1e-5; bfloat16 outputs agree to the last bit or one
(one rounding of a float32 sum either way), 2e-2 on values of size ~4.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpc_patterns_tpu.ops import grouped_matmul as gm
from hpc_patterns_tpu.ops import tiling
from hpc_patterns_tpu.parallel import moe


def _operands(m, k, n, groups, dtype=jnp.float32, seed=0):
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(ka, (m, k), dtype),
            jax.random.normal(kb, (groups, k, n), dtype) * k ** -0.5)


# (m, k, n, sizes): the row tile is 128
GROUPS = {
    "even": (192, 16, 24, [48, 48, 48, 48]),
    "empty_between_full": (192, 16, 24, [0, 70, 0, 0, 90, 0, 32]),
    "one_holds_all": (200, 16, 24, [0, 0, 200, 0]),
    "a_group_over_three_tiles": (384, 16, 24, [5, 300, 1, 78]),
    "rows_a_tile_multiple": (256, 16, 24, [100, 28, 128]),
    "rows_no_tile_multiple": (200, 16, 24, [100, 28, 60]),
    "fewer_rows_than_a_tile": (40, 16, 24, [3, 0, 2, 30]),
    "decode_like_tail": (704, 16, 24, [1, 0, 2, 0, 0, 3, 1, 0] * 4),
    "no_pick_at_all": (256, 16, 24, [0, 0, 0]),
    "n_21_lanes_wide": (160, 32, 2688, [3, 0, 120, 20]),
    "n_three_tiles": (160, 4096, 384, [3, 0, 120, 20]),
    "n_no_lane_multiple": (160, 32, 200, [3, 0, 120, 20]),
}


@pytest.mark.parametrize("case", sorted(GROUPS))
def test_groups_match_the_plain_formulation(case):
    m, k, n, sizes = GROUPS[case]
    lhs, rhs = _operands(m, k, n, len(sizes))
    sizes = jnp.asarray(sizes, jnp.int32)
    got = jax.jit(gm.grouped_matmul)(lhs, rhs, sizes)
    want = gm.grouped_matmul_reference(lhs, rhs, sizes)
    live = int(sizes.sum())
    np.testing.assert_allclose(got[:live], want[:live], atol=1e-5, rtol=0)
    assert got.shape == (m, n) and got.dtype == lhs.dtype


def test_rows_behind_every_group_cost_nothing_and_poison_nothing():
    """> 90 % of the rows sort behind the groups and are NaN: a live row
    that read one, or a tile visited for them, would show."""
    m, k, n, sizes = GROUPS["decode_like_tail"]
    lhs, rhs = _operands(m, k, n, len(sizes))
    sizes = jnp.asarray(sizes, jnp.int32)
    live = int(sizes.sum())
    assert live < 0.1 * m
    lhs = lhs.at[live:].set(jnp.nan)
    got = jax.jit(gm.grouped_matmul)(lhs, rhs, sizes)
    want = gm.grouped_matmul_reference(lhs.at[live:].set(0.0), rhs, sizes)
    assert np.isfinite(got[:live]).all()
    np.testing.assert_allclose(got[:live], want[:live], atol=1e-5, rtol=0)


@pytest.mark.parametrize("out", ["bfloat16", "float32"])
@pytest.mark.parametrize("activation", [None, moe.relu2],
                         ids=["plain", "relu2"])
def test_bfloat16_operands_round_where_the_plain_formulation_does(
        out, activation):
    m, k, n, sizes = 256, 64, 256, [3, 0, 130, 1, 90]
    lhs, rhs = _operands(m, k, n, len(sizes), jnp.bfloat16)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = jax.jit(lambda a, b, s: gm.grouped_matmul(
        a, b, s, preferred_element_type=jnp.dtype(out),
        activation=activation))(lhs, rhs, sizes)
    want = gm.grouped_matmul_reference(
        lhs, rhs, sizes, preferred_element_type=jnp.dtype(out),
        activation=activation)
    assert got.dtype == want.dtype == jnp.dtype(out)
    live = int(sizes.sum())
    np.testing.assert_allclose(
        np.asarray(got[:live], np.float32),
        np.asarray(want[:live], np.float32),
        atol=2e-2 if out == "bfloat16" else 1e-5, rtol=0)


# what the serving programs are traced with (bfloat16): a weight tile of
# megabytes, two in flight, whole in the contraction
@pytest.mark.parametrize("k,n,tn", [(1024, 2688, 896), (2688, 1024, 512),
                                    (16, 24, 24), (32, 200, 200),
                                    (8192, 384, 128), (65536, 256, 128)])
def test_weight_tile_comes_from_the_shapes(k, n, tn):
    assert gm._tiles(k, n, 2) == tn
    assert n % tn == 0 and (tn % 128 == 0 or tn == n)


@pytest.mark.parametrize("bad", ["lhs_rank", "contraction", "sizes_len",
                                 "sizes_dtype", "dtypes"])
def test_refusals_name_the_kernel(bad):
    lhs, rhs = _operands(32, 16, 24, 3)
    sizes = jnp.asarray([3, 4, 5], jnp.int32)
    args = {"lhs_rank": (lhs[0], rhs, sizes),
            "contraction": (lhs[:, :8], rhs, sizes),
            "sizes_len": (lhs, rhs, sizes[:2]),
            "sizes_dtype": (lhs, rhs, sizes.astype(jnp.float32)),
            "dtypes": (lhs.astype(jnp.bfloat16), rhs, sizes)}[bad]
    with pytest.raises(ValueError, match="grouped_matmul"):
        gm.grouped_matmul(*args)


def test_the_wrapper_records_its_mode():
    before = tiling.kernel_modes().get(
        "grouped_matmul", {"interpret": 0})["interpret"]
    lhs, rhs = _operands(32, 16, 24, 3)
    gm.grouped_matmul(lhs, rhs, jnp.asarray([3, 4, 5], jnp.int32))
    assert tiling.kernel_modes()["grouped_matmul"]["interpret"] == before + 1


# -- held_experts through the kernel --------------------------------------------

E_ALL, K_TOP, D_LAT, F_EXP = 16, 4, 16, 24


@pytest.fixture(scope="module")
def layer():
    k = iter(jax.random.split(jax.random.PRNGKey(11), 6))
    n = lambda *s: jax.random.normal(next(k), s) * s[-2] ** -0.5
    tokens = 48
    scores = jax.random.uniform(next(k), (tokens, E_ALL))
    gates, idx = jax.lax.top_k(scores, K_TOP)
    return {"x": jax.random.normal(next(k), (tokens, D_LAT)),
            "idx": idx.astype(jnp.int32), "gates": gates,
            "w1": n(E_ALL, D_LAT, F_EXP), "w2": n(E_ALL, F_EXP, D_LAT)}


def _held(L, products, x=None, gates=None, w1=None, valid=None):
    """``held_experts`` on experts 4..12 with the two products done by
    ``products``: the kernel, or the plain formulation in its place."""
    saved = moe.grouped_matmul
    moe.grouped_matmul = products
    try:
        return moe.held_experts(
            L["x"] if x is None else x, L["idx"],
            L["gates"] if gates is None else gates,
            (L["w1"] if w1 is None else w1)[4:12], L["w2"][4:12],
            held_start=4, valid=valid)[0]
    finally:
        moe.grouped_matmul = saved


def test_nothing_undefined_reaches_y(layer):
    """Seven tokens in eight are idle rows whose input is NaN (with the
    picks of absent experts, 94 % of the rows): their picks
    sort behind every group, where the kernel reads and writes nothing."""
    valid = jnp.arange(48) % 8 == 0
    x = jnp.where(valid[:, None], layer["x"], jnp.nan)
    got = _held(layer, gm.grouped_matmul, x=x, valid=valid)
    want = _held(layer, gm.grouped_matmul_reference,
                 x=jnp.where(valid[:, None], layer["x"], 0.0), valid=valid)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    assert not np.asarray(got[~valid]).any()


@pytest.mark.parametrize("wrt", ["x", "gates", "w1"])
def test_gradient_through_held_experts_is_the_plain_formulations(layer, wrt):
    def loss(products, value):
        y = _held(layer, products, **{wrt: value})
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape)))

    got = jax.grad(lambda v: loss(gm.grouped_matmul, v))(layer[wrt])
    want = jax.grad(lambda v: loss(gm.grouped_matmul_reference, v))(
        layer[wrt])
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
