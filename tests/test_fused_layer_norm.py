"""Fused LayerNorm/RMSNorm vs torch references — mirrors
``tests/L0/run_fused_layer_norm/test_fused_layer_norm.py`` tolerance asserts,
plus Pallas-interpret vs XLA equivalence and memory_efficient grad parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

from apex_tpu.normalization import (
    FusedLayerNorm,
    FusedRMSNorm,
    fused_layer_norm_affine,
    fused_rms_norm_affine,
    manual_rms_norm,
)
from apex_tpu.ops.layer_norm import layer_norm as ln_op
from apex_tpu.ops.layer_norm import rms_norm as rms_op

H = 256
SHAPES = [(4, H), (2, 3, H)]


def _np(seed, shape):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


@pytest.mark.parametrize("shape", SHAPES)
def test_layer_norm_matches_torch(shape):
    x = _np(0, shape)
    w = _np(1, (H,)) * 0.1 + 1.0
    b = _np(2, (H,)) * 0.1
    got = fused_layer_norm_affine(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b), H)
    expect = torch.nn.functional.layer_norm(
        torch.tensor(x), (H,), torch.tensor(w), torch.tensor(b)
    ).numpy()
    np.testing.assert_allclose(np.asarray(got), expect, rtol=1e-5, atol=1e-5)


def test_layer_norm_grads_match_torch():
    x = _np(0, (8, H))
    w = _np(1, (H,)) * 0.1 + 1.0
    b = _np(2, (H,)) * 0.1

    def loss(x, w, b):
        return jnp.sum(fused_layer_norm_affine(x, w, b, H) ** 2)

    gx, gw, gb = jax.grad(loss, argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(b)
    )

    tx = torch.tensor(x, requires_grad=True)
    tw = torch.tensor(w, requires_grad=True)
    tb = torch.tensor(b, requires_grad=True)
    tloss = (torch.nn.functional.layer_norm(tx, (H,), tw, tb) ** 2).sum()
    tloss.backward()
    np.testing.assert_allclose(np.asarray(gx), tx.grad.numpy(), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), tw.grad.numpy(), rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gb), tb.grad.numpy(), rtol=1e-4, atol=1e-3)


def test_rms_norm_matches_torch():
    x = _np(3, (8, H))
    w = _np(4, (H,)) * 0.1 + 1.0
    got = fused_rms_norm_affine(jnp.asarray(x), jnp.asarray(w), H, eps=1e-6)
    expect = torch.nn.functional.rms_norm(
        torch.tensor(x), (H,), torch.tensor(w), eps=1e-6
    ).numpy()
    np.testing.assert_allclose(np.asarray(got), expect, rtol=1e-5, atol=1e-5)


def test_rms_norm_grads_match_torch():
    x = _np(3, (8, H))
    w = _np(4, (H,)) * 0.1 + 1.0

    def loss(x, w):
        return jnp.sum(fused_rms_norm_affine(x, w, H, eps=1e-6) ** 2)

    gx, gw = jax.grad(loss, argnums=(0, 1))(jnp.asarray(x), jnp.asarray(w))
    tx = torch.tensor(x, requires_grad=True)
    tw = torch.tensor(w, requires_grad=True)
    tloss = (torch.nn.functional.rms_norm(tx, (H,), tw, eps=1e-6) ** 2).sum()
    tloss.backward()
    np.testing.assert_allclose(np.asarray(gx), tx.grad.numpy(), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), tw.grad.numpy(), rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("memory_efficient", [False, True])
def test_memory_efficient_grads_equal(memory_efficient):
    """memory_efficient recompute path must produce identical grads."""
    x = jnp.asarray(_np(5, (8, H)))
    w = jnp.asarray(_np(6, (H,)) * 0.1 + 1.0)
    b = jnp.asarray(_np(7, (H,)) * 0.1)

    def loss(x, w, b, me):
        return jnp.sum(ln_op(x, w, b, 1, 1e-5, me) ** 2)

    g_ref = jax.grad(loss, argnums=(0, 1, 2))(x, w, b, False)
    g_me = jax.grad(loss, argnums=(0, 1, 2))(x, w, b, memory_efficient)
    for a, e in zip(g_me, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e), rtol=1e-4, atol=1e-5)


def test_rms_memory_efficient_grads_equal():
    x = jnp.asarray(_np(5, (8, H)))
    w = jnp.asarray(_np(6, (H,)) * 0.1 + 1.0)

    def loss(x, w, me):
        return jnp.sum(rms_op(x, w, 1, 1e-6, me) ** 2)

    g_ref = jax.grad(loss, argnums=(0, 1))(x, w, False)
    g_me = jax.grad(loss, argnums=(0, 1))(x, w, True)
    for a, e in zip(g_me, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e), rtol=1e-4, atol=1e-5)


def test_row_block_is_a_sublane_multiple_or_the_whole_array():
    """Mosaic's rule for a block's second-to-last dim; the fused-block
    tails share this helper. (The old rule returned 4/2/1-row blocks.)"""
    from apex_tpu.ops.layer_norm import _row_block

    for rows in (8192, 4096, 24, 12, 7, 1, 520):
        for hidden in (128, 1024, 4096, 65536):
            br = _row_block(rows, hidden)
            assert rows % br == 0 and (br % 8 == 0 or br == rows), (
                rows, hidden, br)
    assert _row_block(8192, 1024) == 256
    assert _row_block(12, 1024) == 12


class TestPallasKernelInterpret:
    """Run the Pallas kernels in interpreter mode on CPU and compare with XLA."""

    @pytest.mark.parametrize("rows", [16, 12])  # 12: one whole-array block
    def test_ln_fwd_bwd(self, rows):
        x = jnp.asarray(_np(8, (rows, H)))
        w = jnp.asarray(_np(9, (H,)) * 0.1 + 1.0)
        b = jnp.asarray(_np(10, (H,)) * 0.1)

        def loss(x, w, b, interp):
            return jnp.sum(ln_op(x, w, b, 1, 1e-5, False, interp) ** 2)

        ref = jax.grad(loss, argnums=(0, 1, 2))(x, w, b, False)
        pal = jax.grad(loss, argnums=(0, 1, 2))(x, w, b, True)
        np.testing.assert_allclose(
            float(loss(x, w, b, True)), float(loss(x, w, b, False)), rtol=1e-5
        )
        for a, e in zip(pal, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e), rtol=1e-4, atol=1e-5)

    def test_rms_fwd_bwd(self):
        x = jnp.asarray(_np(11, (16, H)))
        w = jnp.asarray(_np(12, (H,)) * 0.1 + 1.0)

        def loss(x, w, interp):
            return jnp.sum(rms_op(x, w, 1, 1e-6, False, interp) ** 2)

        ref = jax.grad(loss, argnums=(0, 1))(x, w, False)
        pal = jax.grad(loss, argnums=(0, 1))(x, w, True)
        for a, e in zip(pal, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e), rtol=1e-4, atol=1e-5)


class TestModules:
    def test_fused_layer_norm_module(self):
        m = FusedLayerNorm(normalized_shape=H)
        x = jnp.asarray(_np(13, (4, H)))
        params = m.init(jax.random.PRNGKey(0), x)
        y = m.apply(params, x)
        expect = torch.nn.functional.layer_norm(torch.tensor(np.asarray(x)), (H,)).numpy()
        np.testing.assert_allclose(np.asarray(y), expect, rtol=1e-5, atol=1e-5)

    def test_mixed_fused_rms_norm_bf16_input_fp32_params(self):
        from apex_tpu.normalization import MixedFusedRMSNorm

        m = MixedFusedRMSNorm(normalized_shape=H)
        x = jnp.asarray(_np(14, (4, H)), jnp.bfloat16)
        params = m.init(jax.random.PRNGKey(0), x)
        assert params["params"]["weight"].dtype == jnp.float32
        y = m.apply(params, x)
        assert y.dtype == jnp.bfloat16

    def test_non_affine(self):
        m = FusedLayerNorm(normalized_shape=H, elementwise_affine=False)
        x = jnp.asarray(_np(15, (4, H)))
        params = m.init(jax.random.PRNGKey(0), x)
        y = m.apply(params, x)
        assert np.allclose(np.asarray(y).mean(axis=-1), 0.0, atol=1e-5)


def test_manual_rms_norm_matches_fused():
    x = jnp.asarray(_np(16, (4, H)))
    w = jnp.asarray(_np(17, (H,)) * 0.1 + 1.0)
    np.testing.assert_allclose(
        np.asarray(manual_rms_norm(x, (H,), w, 1e-6)),
        np.asarray(fused_rms_norm_affine(x, w, H, eps=1e-6)),
        rtol=1e-5,
        atol=1e-5,
    )


def test_shape_mismatch_raises():
    x = jnp.zeros((4, 256))
    with pytest.raises(ValueError, match="normalized_shape"):
        fused_layer_norm_affine(x, jnp.ones((512,)), jnp.zeros((512,)), 512)


def test_memory_efficient_zero_gamma_no_nan():
    """Zero-init gamma (common for residual norms) must not NaN under
    memory_efficient (clamped inverse-affine)."""
    x = jnp.asarray(_np(18, (8, H)))
    w = jnp.zeros((H,))
    b = jnp.zeros((H,))

    def loss(x, w, b):
        return jnp.sum(ln_op(x, w, b, 1, 1e-5, True) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g)))


def test_memory_efficient_bf16_grads_close():
    """me path keeps xhat in fp32 — bf16 grads should track the non-me path."""
    x = jnp.asarray(_np(19, (8, H)), jnp.bfloat16)
    w = jnp.asarray(_np(20, (H,)) * 0.1 + 1.0, jnp.bfloat16)
    b = jnp.asarray(_np(21, (H,)) * 0.1, jnp.bfloat16)

    def loss(x, w, b, me):
        return jnp.sum(ln_op(x, w, b, 1, 1e-5, me).astype(jnp.float32) ** 2)

    g_me = jax.grad(loss, argnums=(0, 1, 2))(x, w, b, True)
    g_ref = jax.grad(loss, argnums=(0, 1, 2))(x, w, b, False)
    for a, e in zip(g_me, g_ref):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(e, np.float32), rtol=0.05, atol=0.05
        )


def test_mixed_pins_param_dtype():
    from apex_tpu.normalization import MixedFusedLayerNorm

    with pytest.raises(ValueError, match="pins param_dtype"):
        MixedFusedLayerNorm(normalized_shape=H, param_dtype=jnp.bfloat16)
