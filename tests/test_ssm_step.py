"""ops/ssm_step in interpret mode at tiny widths against the plain
formulation it replaced (``ssm_step_reference``: one fused pass over all
of S with a select at its end).

The interpreter fills what a kernel leaves unwritten with NaN, so "the y
of a row not visited is not written" is visible here, and so is a NaN that
leaks from it. Tolerance: float32 sums of 16-128 products of size ~1 in
another order (the kernel's read-out is a matrix product with ones), 5e-5
on y of size ~40 and 2e-6 on S (one multiply-add); holding S in bfloat16
moves both by more than 1e-2, which has to fail.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpc_patterns_tpu.ops import ssm_step as ss
from hpc_patterns_tpu.ops import tiling

Y_TOL, S_TOL = 5e-5, 2e-6

# (rows, H, P, N, G): the second has heads that the tile's cap (set in the
# test) does not divide, three heads a group, and a lane-wide state
SHAPES = {"8x16x16_g2": (6, 8, 16, 16, 2), "6x8x128_g3": (7, 6, 8, 128, 3)}

ACTIVE = {
    "none_given": lambda b: None,
    "all_true": lambda b: np.ones(b, bool),
    "all_false": lambda b: np.zeros(b, bool),
    "one_row": lambda b: np.arange(b) == 2,
    "a_scattered_third": lambda b: np.arange(b) % 3 == 1,
    "a_prefix": lambda b: np.arange(b) < 3,
}


def _operands(shape, dtype=jnp.float32, seed=0):
    b, H, P, N, G = SHAPES[shape]
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (b, H, P, N)).astype(dtype),
            jax.random.normal(k[1], (b, H, P)),
            jax.nn.softplus(jax.random.normal(k[2], (b, H))),
            -jnp.exp(jax.random.normal(k[3], (H,))),
            jax.random.normal(k[4], (b, G, N)),
            jax.random.normal(k[5], (b, G, N)))


@pytest.fixture(params=[1, 4], ids=["a_head_a_tile", "four_heads_at_most"])
def heads_a_tile(request, monkeypatch):
    """The cap on a tile's heads: 4 divides the 8 heads of one shape and
    not the 6 of the other (which then takes 3)."""
    monkeypatch.setattr(
        ss, "_head_block",
        lambda heads, slab: tiling.fit_block_divisor(heads, request.param))
    ss._call.clear_cache()
    yield request.param
    ss._call.clear_cache()


@pytest.mark.parametrize("active", sorted(ACTIVE))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_visited_rows_match_and_the_others_keep_their_bits(
        shape, active, heads_a_tile):
    S, *rest = _operands(shape)
    mask = ACTIVE[active](S.shape[0])
    a = None if mask is None else jnp.asarray(mask)
    y, S_new = jax.jit(ss.ssm_step)(S, *rest, a)
    y_ref, S_ref = ss.ssm_step_reference(S, *rest, a)
    assert y.shape == y_ref.shape and y.dtype == jnp.float32
    assert S_new.shape == S.shape and S_new.dtype == S.dtype
    np.testing.assert_allclose(y, y_ref, atol=Y_TOL, rtol=0)
    np.testing.assert_allclose(S_new, S_ref, atol=S_TOL, rtol=0)
    if mask is not None:
        np.testing.assert_array_equal(np.asarray(S_new)[~mask],
                                      np.asarray(S)[~mask])
        assert not np.asarray(y)[~mask].any()
        if mask.any():
            assert np.abs(np.asarray(S_new)[mask]
                          - np.asarray(S)[mask]).max() > 0.1


def test_the_tile_follows_the_states_bytes():
    # nemotron3-super-ep4: a head's slab is 64 x 128 float32 = 32 KiB
    assert ss._head_block(128, 64 * 128 * 4) * 64 * 128 * 4 == ss._TILE_BYTES
    assert ss._head_block(6, ss._TILE_BYTES // 4) == 3
    assert ss._head_block(8, 2 * ss._TILE_BYTES) == 1


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_bfloat16_state_is_updated_in_float32_and_rounded_once(shape):
    S, *rest = _operands(shape, jnp.bfloat16)
    a = jnp.asarray(ACTIVE["a_scattered_third"](S.shape[0]))
    y, S_new = jax.jit(ss.ssm_step)(S, *rest, a)
    y_ref, S_ref = ss.ssm_step_reference(S, *rest, a)
    assert S_new.dtype == jnp.bfloat16
    np.testing.assert_allclose(y, y_ref, atol=Y_TOL, rtol=0)
    # one rounding of a float32 value either way: equal or a neighbour
    np.testing.assert_allclose(S_new.astype(jnp.float32),
                               S_ref.astype(jnp.float32), atol=0, rtol=2**-7)
    # and it is not the float32 state's answer: the tolerance sees it
    y32, S32 = ss.ssm_step_reference(S.astype(jnp.float32), *rest, a)
    assert np.abs(S_new.astype(jnp.float32) - S32).max() > 1e-2 > S_TOL


def test_steps_chain_in_place_under_a_loop():
    """What the server's chunk does: the state carried by a loop, another
    set of rows live at each step."""
    S, x, dt, A, B, C = _operands("8x16x16_g2")
    live = jnp.asarray([[1, 0, 1, 0, 0, 1], [0, 0, 1, 1, 0, 1],
                        [0, 0, 0, 0, 0, 0], [1, 1, 1, 0, 0, 1]], bool)

    def chain(step):
        def body(S, a):
            y, S = step(S, x, dt, A, B, C, a)
            return S, y
        return jax.jit(lambda S: jax.lax.scan(body, S, live))(S)

    S_end, ys = chain(ss.ssm_step)
    S_want, ys_want = chain(ss.ssm_step_reference)
    np.testing.assert_allclose(ys, ys_want, atol=4 * Y_TOL, rtol=0)
    np.testing.assert_allclose(S_end, S_want, atol=4 * S_TOL, rtol=0)
    np.testing.assert_array_equal(np.asarray(S_end)[4], np.asarray(S)[4])


@pytest.mark.parametrize("bad", ["x", "dt", "groups", "active_dtype",
                                 "active_shape"])
def test_it_says_what_it_wants(bad):
    S, x, dt, A, B, C = _operands("8x16x16_g2")
    a = jnp.ones((S.shape[0],), bool)
    args = {"x": (S, x[:, :-1], dt, A, B, C, a),
            "dt": (S, x, dt[:-1], A, B, C, a),
            "groups": (S, x, dt, A, B[:, :1].repeat(3, 1),
                       C[:, :1].repeat(3, 1), a),
            "active_dtype": (S, x, dt, A, B, C, a.astype(jnp.int32)),
            "active_shape": (S, x, dt, A, B, C, a[:-1])}[bad]
    with pytest.raises(ValueError, match="ssm_step: .* want"):
        ss.ssm_step(*args)


def test_the_mode_is_recorded():
    before = tiling.kernel_modes().get("ssm_step", {}).get("interpret", 0)
    ss.ssm_step(*_operands("8x16x16_g2"))
    assert tiling.kernel_modes()["ssm_step"]["interpret"] == before + 1
