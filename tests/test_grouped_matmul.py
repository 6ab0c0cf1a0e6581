"""``ops/grouped_matmul.py``: the Pallas kernels in interpret mode against
a product written group by group, forward and both backward products;
empty groups, one group owning every row (nothing dropped), rows past the
sum, tiles two groups share; the XLA fallback computes the same."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.grouped_matmul import (_tile, _width_tile, grouped_matmul,
                                          row_tile)


def by_groups(lhs, rhs, sizes):
    offs = np.concatenate([[0], np.cumsum(sizes)])
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for g in range(len(sizes)):
        rows = slice(int(offs[g]), int(offs[g + 1]))
        out = out.at[rows].set(lhs[rows].astype(jnp.float32)
                               @ rhs[g].astype(jnp.float32))
    return out


def operands(m, k, n, groups, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    return (jax.random.normal(ks[0], (m, k), dtype),
            jax.random.normal(ks[1], (groups, k, n), dtype) * 0.1,
            jax.random.normal(ks[2], (m, n), jnp.float32))


CASES = {
    "ragged": (256, [100, 0, 56, 30]),
    "empty_groups_at_both_ends": (256, [0, 120, 0, 0]),
    "every_token_on_one_expert": (256, [0, 0, 256, 0]),
    "nothing_routed": (256, [0, 0, 0, 0]),
    "full_and_aligned": (512, [128, 128, 128, 128]),
    "tiles_shared_by_three_groups": (384, [3, 200, 0, 100, 1]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_and_both_backward_products(case):
    m, sizes = CASES[case]
    lhs, rhs, w = operands(m, 64, 32, len(sizes))
    gs = jnp.asarray(sizes, jnp.int32)
    valid = (jnp.arange(m) < sum(sizes))[:, None]

    def kernel(l, r):
        return jnp.where(valid, grouped_matmul(l, r, gs, interpret=True), 0)

    want = by_groups(lhs, rhs, sizes)
    np.testing.assert_allclose(kernel(lhs, rhs), want, atol=2e-5)
    got = jax.grad(lambda l, r: jnp.sum(kernel(l, r) * w), (0, 1))(lhs, rhs)
    ref = jax.grad(lambda l, r: jnp.sum(by_groups(l, r, sizes) * w),
                   (0, 1))(lhs, rhs)
    np.testing.assert_allclose(jnp.where(valid, got[0], 0), ref[0], atol=5e-5)
    np.testing.assert_allclose(got[1], ref[1], atol=5e-5)
    # an empty group's weights get a gradient of exactly zero
    for g, size in enumerate(sizes):
        if size == 0:
            assert not np.any(np.asarray(got[1][g]))


def test_a_width_with_no_large_power_of_two_tile_is_taken_whole():
    """1408 = 11 x 128 (the width of ``moonlight-16b-a3b``'s experts): the
    whole width is the tile, where the power-of-two rule walked eleven of
    128; the widths ``trinity-mini`` has keep their tile of 1024."""
    assert _tile(1408, 1024) == 128 and _width_tile(1408) == 1408
    assert _width_tile(1024) == _width_tile(2048) == 1024
    assert _width_tile(512) == 512 and _width_tile(64) == 64
    assert _width_tile(4224) == 128             # past 2048: the rule's own


@pytest.mark.parametrize("k, n", [(384, 128), (128, 384), (384, 640)])
def test_the_products_at_a_whole_width_tile(k, n):
    """Widths of 3 x 128 and 5 x 128: one whole-width tile each way."""
    assert _width_tile(384) == 384 and _width_tile(640) == 640
    m, sizes = CASES["tiles_shared_by_three_groups"]
    lhs, rhs, w = operands(m, k, n, len(sizes))
    gs = jnp.asarray(sizes, jnp.int32)
    valid = (jnp.arange(m) < sum(sizes))[:, None]
    kernel = lambda l, r: jnp.where(  # noqa: E731
        valid, grouped_matmul(l, r, gs, interpret=True), 0)
    np.testing.assert_allclose(kernel(lhs, rhs), by_groups(lhs, rhs, sizes),
                               atol=1e-4)
    got = jax.grad(lambda l, r: jnp.sum(kernel(l, r) * w), (0, 1))(lhs, rhs)
    ref = jax.grad(lambda l, r: jnp.sum(by_groups(l, r, sizes) * w),
                   (0, 1))(lhs, rhs)
    np.testing.assert_allclose(jnp.where(valid, got[0], 0), ref[0], atol=2e-4)
    np.testing.assert_allclose(got[1], ref[1], atol=2e-4)


def test_nothing_is_dropped_under_any_imbalance():
    """Every row a group owns is computed with that group's weights,
    wherever the boundaries fall."""
    rng = np.random.default_rng(0)
    lhs, rhs, _ = operands(512, 128, 128, 8)
    for _ in range(4):
        cuts = np.sort(rng.integers(0, 400, size=7))
        sizes = np.diff(np.concatenate([[0], cuts, [400]])).tolist()
        out = grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32),
                             interpret=True)
        np.testing.assert_allclose(out[:400], by_groups(lhs, rhs, sizes)[:400],
                                   atol=1e-4)


def test_the_xla_fallback_and_bf16_agree_with_the_kernels():
    m, sizes = CASES["ragged"]
    lhs, rhs, _ = operands(m, 64, 32, len(sizes), jnp.bfloat16)
    gs = jnp.asarray(sizes, jnp.int32)
    kernel = grouped_matmul(lhs, rhs, gs, interpret=True)
    fallback = grouped_matmul(lhs, rhs, gs)           # off the TPU: ragged_dot
    assert kernel.dtype == fallback.dtype == jnp.bfloat16
    total = sum(sizes)
    np.testing.assert_allclose(kernel[:total].astype(jnp.float32),
                               fallback[:total].astype(jnp.float32),
                               atol=0.05)
    assert not np.any(np.asarray(fallback[total:].astype(jnp.float32)))


def test_shapes_that_are_not_a_grouped_product_are_refused():
    lhs, rhs, _ = operands(256, 64, 32, 4)
    with pytest.raises(ValueError):
        grouped_matmul(lhs, rhs[:, :32], jnp.zeros((4,), jnp.int32))
    with pytest.raises(ValueError):
        grouped_matmul(lhs, rhs, jnp.zeros((3,), jnp.int32))
    assert row_tile(131072) == 512 and row_tile(256) == 256


def test_rows_past_the_sum_may_hold_anything():
    """The buffers are sized for the worst case and the rows past the
    routed ones are never written: NaN there (in either operand of any of
    the three products) reaches no row in use and no weight gradient."""
    m, sizes = 256, [60, 0, 70, 20]
    total = sum(sizes)
    lhs, rhs, w = operands(m, 64, 32, len(sizes))
    gs = jnp.asarray(sizes, jnp.int32)
    poison = (jnp.arange(m) >= total)[:, None]
    lhs_bad = jnp.where(poison, jnp.nan, lhs)
    w_bad = jnp.where(poison, jnp.nan, w)

    def direct(l, r, cot):
        # the cotangent handed to the kernels is NaN past the sum
        out, vjp = jax.vjp(lambda l, r: grouped_matmul(
            l, r, gs, interpret=True), l, r)
        return out, vjp(cot.astype(out.dtype))

    out, (dl, dr) = direct(lhs_bad, rhs, w_bad)
    clean_out, (clean_dl, clean_dr) = direct(lhs, rhs, jnp.where(
        poison, 0.0, w))
    assert np.all(np.isfinite(np.asarray(out[:total])))
    assert np.all(np.isfinite(np.asarray(dr)))
    np.testing.assert_allclose(out[:total], clean_out[:total], atol=1e-6)
    np.testing.assert_allclose(dl[:total], clean_dl[:total], atol=1e-6)
    np.testing.assert_allclose(dr, clean_dr, atol=1e-5)
