"""Tests for apex_tpu.ops.multi_tensor — mirrors
``tests/L0/run_amp/test_multi_tensor_scale.py`` etc.: op-vs-eager-math plus
overflow-flag cases."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import (
    multi_tensor_axpby,
    multi_tensor_l2norm,
    multi_tensor_scale,
    multi_tensor_unscale_l2norm,
    update_scale_hysteresis,
)


def _tree(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "a": jnp.asarray(rng.randn(4, 5), jnp.float32),
        "b": [jnp.asarray(rng.randn(37), jnp.float32), jnp.asarray(rng.randn(2, 3, 4), jnp.float32)],
    }


def test_scale_matches_eager():
    t = _tree()
    out, found = jax.jit(lambda x: multi_tensor_scale(x, 0.125))(t)
    for o, i in zip(jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(t)):
        np.testing.assert_allclose(o, np.asarray(i) * 0.125, rtol=1e-6)
    assert not bool(found)


@pytest.mark.parametrize("bad", [np.inf, -np.inf, np.nan])
def test_scale_flags_overflow(bad):
    t = _tree()
    t["a"] = t["a"].at[1, 2].set(bad)
    _, found = multi_tensor_scale(t, 1.0)
    assert bool(found)


def test_scale_cross_dtype():
    t = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), _tree())
    out, _ = multi_tensor_scale(t, 2.0, out_dtype=jnp.float32)
    assert all(o.dtype == jnp.float32 for o in jax.tree_util.tree_leaves(out))


def test_axpby():
    x, y = _tree(0), _tree(1)
    out, found = multi_tensor_axpby(2.0, -3.0, x, y)
    for o, a, b in zip(*(jax.tree_util.tree_leaves(t) for t in (out, x, y))):
        np.testing.assert_allclose(o, 2.0 * np.asarray(a) - 3.0 * np.asarray(b), rtol=1e-6)
    assert not bool(found)


def test_l2norm_global_and_per_tensor():
    t = _tree()
    leaves = jax.tree_util.tree_leaves(t)
    gnorm, per = multi_tensor_l2norm(t, per_tensor=True)
    expect = np.sqrt(sum(float(np.sum(np.asarray(l) ** 2)) for l in leaves))
    np.testing.assert_allclose(float(gnorm), expect, rtol=1e-6)
    assert per.shape == (len(leaves),)
    for p, l in zip(np.asarray(per), leaves):
        np.testing.assert_allclose(p, np.linalg.norm(np.asarray(l).ravel()), rtol=1e-5)


def test_unscale_l2norm_flags_inf():
    t = _tree()
    t["a"] = t["a"].at[0, 0].set(np.inf)
    gnorm, _, found = multi_tensor_unscale_l2norm(t, 0.5)
    assert bool(found)


class TestUpdateScaleHysteresis:
    def run(self, scale, growth, hyst, found, **kw):
        s, g, h = update_scale_hysteresis(
            jnp.float32(scale), jnp.int32(growth), jnp.int32(hyst),
            jnp.asarray(found), **kw
        )
        return float(s), int(g), int(h)

    def test_clean_step_grows_at_interval(self):
        s, g, h = self.run(1024.0, 1999, 2, False, growth_interval=2000, hysteresis=2)
        assert s == 2048.0 and g == 0 and h == 2

    def test_clean_step_increments(self):
        s, g, h = self.run(1024.0, 10, 2, False, growth_interval=2000, hysteresis=2)
        assert s == 1024.0 and g == 11 and h == 2

    def test_overflow_consumes_hysteresis_before_backoff(self):
        # hysteresis=2: first overflow only decrements
        s, g, h = self.run(1024.0, 500, 2, True, hysteresis=2)
        assert s == 1024.0 and g == 0 and h == 1
        # second overflow backs off
        s, g, h = self.run(1024.0, 0, 1, True, hysteresis=2)
        assert s == 512.0 and g == 0 and h == 0

    def test_growth_clamps_to_finite(self):
        big = float(np.float32(3.0e38))
        s, _, _ = self.run(big, 1999, 1, False, growth_interval=2000)
        assert s == big  # growing would overflow fp32 -> unchanged


# ---------------------------------------------------------------------------
# flat-buffer ops + the packing bookkeeping behind them
# ---------------------------------------------------------------------------
from apex_tpu.multi_tensor_apply import (  # noqa: E402
    MultiTensorApply,
    PackSpec,
    ROW,
)
from apex_tpu.ops import (  # noqa: E402
    multi_tensor_axpby_flat,
    multi_tensor_l2norm_flat,
    multi_tensor_scale_flat,
)


class TestPackSpec:
    def test_roundtrip(self):
        t = _tree()
        spec = PackSpec(t)
        flat = spec.pack(t)
        assert flat.shape == (spec.total,)
        assert spec.total % spec.chunk_size == 0
        out = spec.unpack(flat)
        for a, b in zip(jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(t)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_padding_is_zero_and_rows_leaf_aligned(self):
        t = _tree()
        spec = PackSpec(t)
        flat = np.asarray(spec.pack(t))
        mask = spec.valid_mask()
        assert not flat[~mask].any()  # padding strictly zero
        # every ROW-sized row belongs to at most one leaf
        ids = spec.row_leaf_ids()
        assert ids.shape == (spec.n_rows,)
        for i, (o, n) in enumerate(zip(spec.offsets, spec.sizes)):
            assert o % ROW == 0
            assert (ids[o // ROW] == i)

    def test_mixed_dtype_falls_back_to_f32(self):
        t = {"a": jnp.ones((4,), jnp.bfloat16), "b": jnp.ones((4,), jnp.float32)}
        spec = PackSpec(t)
        assert spec.pack(t).dtype == jnp.float32
        out = spec.unpack(spec.pack(t))
        assert out["a"].dtype == jnp.bfloat16

    def test_shape_mismatch_raises(self):
        spec = PackSpec(_tree())
        with pytest.raises(ValueError):
            spec.pack({"a": jnp.zeros((3, 3))})

    def test_spec_hashable_static(self):
        s1, s2 = PackSpec(_tree()), PackSpec(_tree())
        assert s1 == s2 and hash(s1) == hash(s2)


@pytest.mark.parametrize("interpret", [False, True])
@pytest.mark.parametrize("n", [ROW * 3, ROW * 3 - 5])  # aligned + ragged
def test_flat_scale(n, interpret):
    x = jnp.asarray(np.random.RandomState(0).randn(n), jnp.float32)
    out, found = multi_tensor_scale_flat(x, 0.125, interpret=interpret)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 0.125, rtol=1e-6)
    assert out.shape == x.shape and not bool(found)
    bad = x.at[7].set(np.inf)
    _, found = multi_tensor_scale_flat(bad, 1.0, interpret=interpret)
    assert bool(found)


@pytest.mark.parametrize("interpret", [False, True])
def test_flat_scale_cross_dtype(interpret):
    x = jnp.asarray(np.random.RandomState(0).randn(ROW), jnp.bfloat16)
    out, _ = multi_tensor_scale_flat(
        x, 2.0, out_dtype=jnp.float32, interpret=interpret)
    assert out.dtype == jnp.float32


@pytest.mark.parametrize("interpret", [False, True])
def test_flat_axpby(interpret):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2 * ROW + 3), jnp.float32)
    y = jnp.asarray(rng.randn(2 * ROW + 3), jnp.float32)
    out, found = multi_tensor_axpby_flat(2.0, -3.0, x, y, interpret=interpret)
    np.testing.assert_allclose(
        np.asarray(out), 2.0 * np.asarray(x) - 3.0 * np.asarray(y), rtol=1e-6)
    assert not bool(found)


@pytest.mark.parametrize("interpret", [False, True])
def test_flat_l2norm(interpret):
    x = jnp.asarray(np.random.RandomState(2).randn(3 * ROW), jnp.float32)
    norm, row_sq = multi_tensor_l2norm_flat(x, interpret=interpret)
    np.testing.assert_allclose(
        float(norm), np.linalg.norm(np.asarray(x)), rtol=1e-5)
    assert row_sq.shape == (3,)


def test_flat_ops_block_rows_are_tileable_for_awkward_lengths():
    """Mosaic takes a block's sublane dim only as a tile multiple (16
    covers bf16) or the array's own. The old rule shrank blocks to the
    largest divisor of the row count — 4 rows for a 4-row chunk, 1 for a
    prime count — which the chip refuses; now the block is the chunk
    rounded up to 16 rows (or the whole buffer) and the grid's ragged
    last block covers an odd remainder."""
    from apex_tpu.ops.packed_optimizer import _block_rows

    assert _block_rows(16, 4 * ROW) == 16   # small chunk: whole buffer
    assert _block_rows(13, 64 * ROW) == 13  # prime, under one block
    assert _block_rows(37, 4 * ROW) == 16   # odd R//B: grid of 3, ragged
    assert _block_rows(5681, 64 * ROW) == 64
    # end-to-end through the kernel bodies: 37 rows in 16-row blocks, the
    # last block 5 rows, with the only non-finite value inside it
    rng = np.random.RandomState(7)
    v = jnp.asarray(rng.randn(37 * ROW - 5), jnp.float32)
    out, found = multi_tensor_scale_flat(
        v, 0.5, chunk_size=4 * ROW, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(v) * 0.5,
                               rtol=1e-6)
    assert out.shape == v.shape and not bool(found)
    _, found, row_bad = multi_tensor_scale_flat(
        v.at[36 * ROW + 3].set(np.inf), 0.5, chunk_size=4 * ROW,
        per_row_flags=True, interpret=True)
    assert bool(found) and np.flatnonzero(np.asarray(row_bad)).tolist() == [36]
    norm, row_sq = multi_tensor_l2norm_flat(
        v, chunk_size=4 * ROW, interpret=True)
    np.testing.assert_allclose(float(norm), np.linalg.norm(np.asarray(v)),
                               rtol=1e-5)
    assert row_sq.shape == (37,)


def test_chunk_size_is_honored():
    """Different chunk sizes tile the same buffer to identical results —
    and the grid actually changes (the kernel runs per chunk)."""
    x = jnp.asarray(np.random.RandomState(3).randn(8 * ROW), jnp.float32)
    outs = [
        multi_tensor_scale_flat(x, 0.5, chunk_size=c, interpret=True)[0]
        for c in (ROW, 2 * ROW, 8 * ROW, 2048 * 32)
    ]
    for o in outs[1:]:
        np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(o))


def test_applier_forwards_chunk_size():
    """MultiTensorApply(chunk_size=...) injects its chunk size into flat
    ops (the reference contract, previously accepted-and-ignored)."""
    seen = {}

    def spy_op(x, *, chunk_size=None):
        seen["chunk"] = chunk_size
        return x

    spy_op.accepts_chunk_size = True
    applier = MultiTensorApply(chunk_size=4 * ROW)
    applier(spy_op, jnp.zeros((8,)))
    assert seen["chunk"] == 4 * ROW

    # pytree ops (no accepts_chunk_size) are called untouched
    out, found = applier(multi_tensor_scale, _tree(), 2.0)
    assert not bool(found)

    # end-to-end with a real flat op
    x = jnp.asarray(np.random.RandomState(4).randn(8 * ROW), jnp.float32)
    out, _ = applier(multi_tensor_scale_flat, x, 0.25)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 0.25, rtol=1e-6)


def test_flat_clip_grad_norm_matches_tree():
    from apex_tpu.contrib.clip_grad import clip_grad_norm_, clip_grad_norm_flat

    t = _tree()
    spec = PackSpec(t)
    flat = spec.pack(t)
    clipped_t, norm_t = clip_grad_norm_(t, 0.5)
    clipped_f, norm_f = clip_grad_norm_flat(flat, 0.5)
    np.testing.assert_allclose(float(norm_f), float(norm_t), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(spec.unpack(clipped_f)),
                    jax.tree_util.tree_leaves(clipped_t)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)
