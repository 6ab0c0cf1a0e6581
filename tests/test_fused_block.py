"""Fused transformer-block tail kernels (``apex_tpu/ops/fused_block.py``).

The ISSUE-9 parity contract:

- forward/backward vs the unfused reference — f32 EXACT on the XLA
  fallback path (the fallback IS the reference math, backward via
  ``jax.vjp`` of it), bf16/interpret-kernel tolerance elsewhere;
- dropout determinism: a fixed seed reproduces the identical keep mask
  across kernel (interpret) and fallback, forward and backward;
- grad-of-remat equivalence: ``selective_elementwise`` vs ``full`` give
  the same loss and the same grads, with fewer saved residuals than the
  no-remat trace (measured via jaxpr);
- analysis rule 6: an unscoped kernel invocation trips
  ``unscoped_kernel``; the public (scoped) entry points do not.
"""
import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from apex_tpu.analysis import assert_step_clean, audit_step  # noqa: E402
from apex_tpu.ops import layer_norm as ln_mod  # noqa: E402
from apex_tpu.ops.fused_block import (  # noqa: E402
    bias_dropout_residual,
    bias_gelu,
    dropout_mask_reference,
    residual_add_layer_norm,
)
from apex_tpu.transformer.testing import (  # noqa: E402
    GPTConfig,
    gpt_loss,
    init_gpt_params,
)
from apex_tpu.transformer.testing.standalone_transformer_lm import (  # noqa: E402
    _selective_elementwise_policy,
    transformer_layer,
)


def _data(h=128, rows=(4, 8), dtype=jnp.float32, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    x = jax.random.normal(ks[0], rows + (h,), dtype)
    b = (jax.random.normal(ks[1], (h,)) * 0.1).astype(dtype)
    r = jax.random.normal(ks[2], rows + (h,), dtype)
    return x, b, r


# ---------------------------------------------------------------------------
# bias_gelu
# ---------------------------------------------------------------------------

def test_bias_gelu_fallback_bitwise():
    x, b, _ = _data()
    y = bias_gelu(x, b)
    ref = jax.nn.gelu(x + b, approximate=True)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))


def test_bias_gelu_fallback_grads_bitwise():
    x, b, _ = _data(key=1)
    gx, gb = jax.grad(lambda x, b: (bias_gelu(x, b) ** 2).sum(),
                      argnums=(0, 1))(x, b)
    rx, rb = jax.grad(
        lambda x, b: (jax.nn.gelu(x + b, approximate=True) ** 2).sum(),
        argnums=(0, 1))(x, b)
    np.testing.assert_array_equal(np.asarray(gx), np.asarray(rx))
    np.testing.assert_array_equal(np.asarray(gb), np.asarray(rb))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 2e-2)])
def test_bias_gelu_kernel_parity(dtype, tol):
    x, b, _ = _data(dtype=dtype, key=2)
    y = bias_gelu(x, b, interpret=True)
    ref = jax.nn.gelu(x.astype(jnp.float32) + b.astype(jnp.float32),
                      approximate=True)
    assert jnp.abs(y.astype(jnp.float32) - ref).max() < tol


def test_bias_gelu_kernel_grads_close():
    x, b, _ = _data(key=3)
    gk = jax.grad(lambda x, b: (bias_gelu(x, b, interpret=True) ** 2).sum(),
                  argnums=(0, 1))(x, b)
    gr = jax.grad(
        lambda x, b: (jax.nn.gelu(x + b, approximate=True) ** 2).sum(),
        argnums=(0, 1))(x, b)
    for a, c in zip(gk, gr):
        assert jnp.abs(a - c).max() < 1e-4


def test_bias_gelu_rejects_bad_bias_shape():
    x, b, _ = _data()
    with pytest.raises(ValueError, match="bias must be"):
        bias_gelu(x, b[:64])


# ---------------------------------------------------------------------------
# bias_dropout_residual
# ---------------------------------------------------------------------------

def test_bdr_p0_fallback_exact():
    x, b, r = _data(key=4)
    out = bias_dropout_residual(x, b, r)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(r + (x + b)))


def test_bdr_p0_kernel_matches_fallback():
    x, b, r = _data(key=5)
    out = bias_dropout_residual(x, b, r)
    outk = bias_dropout_residual(x, b, r, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(outk))


def test_bdr_dropout_deterministic_kernel_vs_fallback():
    x, b, r = _data(key=6)
    args = dict(dropout_p=0.3, seed=42)
    out = bias_dropout_residual(x, b, r, **args)
    outk = bias_dropout_residual(x, b, r, interpret=True, **args)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(outk))
    # a different seed is a different mask
    out2 = bias_dropout_residual(x, b, r, dropout_p=0.3, seed=43)
    assert not np.array_equal(np.asarray(out), np.asarray(out2))


def test_bdr_dropout_matches_reference_mask():
    x, b, r = _data(key=7)
    p, seed = 0.25, 1234
    out = bias_dropout_residual(x, b, r, dropout_p=p, seed=seed)
    keep = dropout_mask_reference(seed, 32, 128, p).reshape(x.shape)
    ref = r + keep * (x + b) * (1.0 / (1.0 - p))
    assert jnp.abs(out - ref).max() < 1e-6
    # drop fraction is ~p
    assert abs((1.0 - keep.mean()) - p) < 0.03


def test_bdr_dropout_backward_regenerates_mask():
    x, b, r = _data(key=8)
    p, seed = 0.4, 99
    for interp in (False, True):
        gx = jax.grad(lambda x: bias_dropout_residual(
            x, b, r, dropout_p=p, seed=seed, interpret=interp).sum())(x)
        keep = dropout_mask_reference(seed, 32, 128, p).reshape(x.shape)
        np.testing.assert_allclose(
            np.asarray(gx), np.asarray(keep / (1.0 - p)), atol=1e-6)
        gr = jax.grad(lambda r: bias_dropout_residual(
            x, b, r, dropout_p=p, seed=seed, interpret=interp).sum())(r)
        np.testing.assert_allclose(np.asarray(gr), 1.0, atol=1e-6)


def test_bdr_requires_seed_when_dropout_on():
    x, b, r = _data()
    with pytest.raises(ValueError, match="seed"):
        bias_dropout_residual(x, b, r, dropout_p=0.1)


# ---------------------------------------------------------------------------
# residual_add_layer_norm
# ---------------------------------------------------------------------------

def _raln_reference(x, b, r, w, lb, eps=1e-5):
    """The unfused chain the fused op replaces (p=0): bias add + residual
    add + the repo's own fused_layer_norm on the rounded sum."""
    s = (r + (x + b)).astype(r.dtype)
    y = ln_mod.layer_norm(
        s.astype(jnp.float32), w.astype(jnp.float32),
        lb.astype(jnp.float32), eps=eps).astype(r.dtype)
    return s, y


@pytest.mark.parametrize("interp", [False, True])
def test_raln_matches_unfused_chain(interp):
    x, b, r = _data(key=9)
    w = jnp.ones((128,)) * 1.1
    lb = jnp.full((128,), 0.2)
    s, y = residual_add_layer_norm(x, b, r, w, lb, interpret=interp)
    s_ref, y_ref = _raln_reference(x, b, r, w, lb)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))
    tol = 0.0 if not interp else 1e-6
    assert jnp.abs(y - y_ref).max() <= tol


# 12 rows: no multiple of 8 divides them — the old block rule picked
# 4-row blocks, which Mosaic refuses; now they run as one block
@pytest.mark.parametrize("rows", [(4, 8), (3, 4)])
@pytest.mark.parametrize("interp", [False, True])
def test_raln_grads_match_unfused_chain(interp, rows):
    x, b, r = _data(key=10, rows=rows)
    w = jnp.ones((128,)) * 0.9
    lb = jnp.zeros((128,))

    def loss_fused(x, b, r, w, lb):
        s, y = residual_add_layer_norm(x, b, r, w, lb, interpret=interp)
        return ((s * y) ** 2).sum()

    def loss_ref(x, b, r, w, lb):
        s, y = _raln_reference(x, b, r, w, lb)
        return ((s * y) ** 2).sum()

    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3, 4))(x, b, r, w, lb)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(x, b, r, w, lb)
    for a, c in zip(gf, gr):
        scale = max(1.0, float(jnp.abs(c).max()))
        assert jnp.abs(a - c).max() / scale < 2e-5


def test_raln_bf16_kernel_close_to_fallback():
    x, b, r = _data(dtype=jnp.bfloat16, key=11)
    w = jnp.ones((128,))
    lb = jnp.zeros((128,))
    s, y = residual_add_layer_norm(x, b.astype(jnp.float32), r, w, lb)
    sk, yk = residual_add_layer_norm(x, b.astype(jnp.float32), r, w, lb,
                                     interpret=True)
    assert jnp.abs(s.astype(jnp.float32) - sk.astype(jnp.float32)).max() < 2e-2
    assert jnp.abs(y.astype(jnp.float32) - yk.astype(jnp.float32)).max() < 2e-2


def test_raln_dropout_deterministic():
    x, b, r = _data(key=12)
    w = jnp.ones((128,))
    lb = jnp.zeros((128,))
    kw = dict(dropout_p=0.2, seed=7)
    s, y = residual_add_layer_norm(x, b, r, w, lb, **kw)
    sk, yk = residual_add_layer_norm(x, b, r, w, lb, interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sk))
    assert jnp.abs(y - yk).max() < 1e-6


# ---------------------------------------------------------------------------
# the gate, and the XLA form it hands a compiled program (PR 34)
# ---------------------------------------------------------------------------

def _op_call(op, dtype=jnp.float32, dropout_p=0.0, interpret=False):
    """``(fn, args)``: one tail op as a function of the arguments it is
    differentiated by."""
    x, b, r = _data(dtype=dtype, key=20)
    w = (jnp.ones((128,)) * 1.1).astype(dtype)
    lb = jnp.full((128,), 0.2, dtype)
    drop = dict(dropout_p=dropout_p, seed=11) if dropout_p else {}
    if op == "bias_gelu":
        return (lambda x, b: bias_gelu(x, b, interpret=interpret)), (x, b)
    if op == "bias_dropout_residual":
        return (lambda x, b, r: bias_dropout_residual(
            x, b, r, interpret=interpret, **drop)), (x, b, r)
    return (lambda x, b, r, w, lb: residual_add_layer_norm(
        x, b, r, w, lb, interpret=interpret, **drop)), (x, b, r, w, lb)


_OPS = ["bias_gelu", "bias_dropout_residual", "residual_add_layer_norm"]


def _kernel_names(fn, args):
    """Names of the pallas_calls in the forward-and-backward jaxpr."""
    from apex_tpu.analysis import kernel_inventory

    def scalar(*a):
        out = fn(*a)
        return sum(o.astype(jnp.float32).sum()
                   for o in jax.tree_util.tree_leaves(out))

    grad = jax.grad(scalar, argnums=tuple(range(len(args))))
    return sorted(rec.name for rec in kernel_inventory(grad, *args))


@pytest.mark.parametrize("op", _OPS)
@pytest.mark.parametrize("mode", ["tpu", "tpu_interpret", "tpu_forced",
                                  "tpu_disabled_interpret", "cpu"])
def test_gate_table(monkeypatch, op, mode):
    """``_use_pallas``' table, per operation: a program compiled for a TPU
    holds XLA's form of every tail (what the chip said, PR 34), the kernel
    bodies run where ``interpret=True`` asks, and ``fused_block_available``
    says what engages."""
    from apex_tpu.ops import fused_block_available

    monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)
    monkeypatch.delenv("APEX_TPU_FORCE_PALLAS_LN", raising=False)
    if mode != "cpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if mode == "tpu_forced":
        monkeypatch.setenv("APEX_TPU_FORCE_PALLAS_LN", "1")
    if mode == "tpu_disabled_interpret":
        monkeypatch.setenv("APEX_TPU_DISABLE_PALLAS", "1")
    interpret = mode.endswith("interpret")
    fn, args = _op_call(op, interpret=interpret)
    names = _kernel_names(fn, args)
    stem = {"bias_gelu": "apex_tpu_bias_gelu",
            "bias_dropout_residual": "apex_tpu_bias_dropout_residual",
            "residual_add_layer_norm": "apex_tpu_residual_ln"}[op]
    kernels = mode in ("tpu_interpret", "tpu_forced")
    assert names == ([stem + "_bwd", stem + "_fwd"] if kernels else [])
    # the introspection hook answers for a compiled (not interpreted) call
    assert fused_block_available(128) is (mode == "tpu_forced")
    assert fused_block_available(100) is False


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("op,dropout_p", [
    ("bias_gelu", 0.0), ("bias_dropout_residual", 0.0),
    ("bias_dropout_residual", 0.3), ("residual_add_layer_norm", 0.0),
    ("residual_add_layer_norm", 0.3)])
def test_xla_form_matches_interpreted_kernel(op, dropout_p, dtype):
    """The form a compiled program holds against the kernel body: every
    output and every gradient within one rounding of the I/O dtype (both
    compute in float32 inside and round where the kernel writes)."""
    outs, grads = {}, {}
    for interpret in (False, True):
        fn, args = _op_call(op, dtype, dropout_p, interpret)

        def scalar(*a, fn=fn):
            out = jax.tree_util.tree_leaves(fn(*a))
            # weights that differ by output, so no gradient cancels
            return sum(((i + 1) * o.astype(jnp.float32) ** 2).sum()
                       for i, o in enumerate(out))

        outs[interpret] = jax.tree_util.tree_leaves(fn(*args))
        grads[interpret] = jax.grad(
            scalar, argnums=tuple(range(len(args))))(*args)
    eps = float(jnp.finfo(dtype).eps)

    def close(a, c, what):
        a, c = a.astype(jnp.float32), c.astype(jnp.float32)
        assert a.shape == c.shape, what
        # bf16: one rounding (both forms round a float32 result once);
        # float32: the few ulps two orders of summation over 128 columns
        # differ by. A reduced gradient (dbias, dgamma) sums 32 rows, each
        # within that.
        ulps = (1.0 if dtype == jnp.bfloat16 else 8.0) * (
            32.0 if a.ndim == 1 else 1.0)
        tol = ulps * eps * jnp.maximum(jnp.abs(c), 1.0)
        assert bool(jnp.all(jnp.abs(a - c) <= tol)), what

    for a, c in zip(outs[False], outs[True]):
        assert a.dtype == c.dtype == dtype
        close(a, c, f"{op} output")
    for i, (a, c) in enumerate(zip(grads[False], grads[True])):
        assert a.dtype == c.dtype
        close(a, c, f"{op} gradient {i}")


def test_bias_gelu_xla_form_computes_in_float32():
    """bf16 in, bf16 out, float32 between: the XLA form is the float32
    result rounded ONCE. Adding the bias and applying GeLU in bf16 (the
    form before PR 34) rounds twice more and lands an ulp away on part of
    any tensor; this input holds such elements."""
    x, b, _ = _data(dtype=jnp.bfloat16, key=21)
    once = jax.nn.gelu(x.astype(jnp.float32) + b.astype(jnp.float32),
                       approximate=True).astype(jnp.bfloat16)
    in_bf16 = jax.nn.gelu(x + b, approximate=True)
    assert in_bf16.dtype == jnp.bfloat16
    differ = np.asarray(once != in_bf16)
    assert differ.any()  # the case exists in this input
    y = bias_gelu(x, b)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(y.astype(jnp.float32)),
                                  np.asarray(once.astype(jnp.float32)))
    # and so does the backward: dx is dy * gelu'(x + b) in float32,
    # rounded once
    dy = jnp.ones_like(x)
    dx = jax.vjp(lambda x: bias_gelu(x, b), x)[1](dy)[0]
    ref = jax.vjp(lambda xf: jax.nn.gelu(xf + b.astype(jnp.float32),
                                         approximate=True),
                  x.astype(jnp.float32))[1](dy.astype(jnp.float32))[0]
    assert dx.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(dx.astype(jnp.float32)),
        np.asarray(ref.astype(jnp.bfloat16).astype(jnp.float32)))


# ---------------------------------------------------------------------------
# model-level parity (GPTConfig.fused_block)
# ---------------------------------------------------------------------------

_CFG = GPTConfig(num_layers=2, hidden_size=64, num_attention_heads=4,
                 vocab_size=128, max_position_embeddings=32,
                 hidden_dropout=0.0, attention_dropout=0.0)


def _tok(key=1, b=2, s=32):
    tokens = jax.random.randint(jax.random.PRNGKey(key), (b, s), 0,
                                _CFG.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=1)


def test_model_fused_matches_unfused_f32():
    params = init_gpt_params(_CFG, jax.random.PRNGKey(0))
    tokens, labels = _tok()
    cfg_f = dataclasses.replace(_CFG, fused_block=True)
    l0, g0 = jax.value_and_grad(
        lambda p: gpt_loss(_CFG, p, tokens, labels))(params)
    l1, g1 = jax.value_and_grad(
        lambda p: gpt_loss(cfg_f, p, tokens, labels))(params)
    # forward: the fallback is the reference math — bitwise
    assert float(l0) == float(l1)
    for a, c in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        assert jnp.abs(a - c).max() < 2e-6


def test_model_fused_kernels_match_fallback():
    params = init_gpt_params(_CFG, jax.random.PRNGKey(0))
    tokens, labels = _tok()
    cfg_f = dataclasses.replace(_CFG, fused_block=True)
    cfg_i = dataclasses.replace(_CFG, fused_block=True,
                                fused_block_interpret=True)
    l1, g1 = jax.value_and_grad(
        lambda p: gpt_loss(cfg_f, p, tokens, labels))(params)
    l2, g2 = jax.value_and_grad(
        lambda p: gpt_loss(cfg_i, p, tokens, labels))(params)
    assert abs(float(l1) - float(l2)) < 1e-6
    for a, c in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        assert jnp.abs(a - c).max() < 1e-5


def test_model_fused_bf16_close():
    cfg16 = dataclasses.replace(_CFG, compute_dtype=jnp.bfloat16)
    cfg16_f = dataclasses.replace(cfg16, fused_block=True,
                                  fused_block_interpret=True)
    params = init_gpt_params(cfg16, jax.random.PRNGKey(0))
    tokens, labels = _tok()
    l0 = gpt_loss(cfg16, params, tokens, labels)
    l1 = gpt_loss(cfg16_f, params, tokens, labels)
    assert abs(float(l0) - float(l1)) / abs(float(l0)) < 2e-2


def test_model_fused_dropout_deterministic_given_key():
    cfg = dataclasses.replace(_CFG, fused_block=True,
                              fused_block_interpret=True,
                              hidden_dropout=0.1)
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    tokens, labels = _tok()
    key = jax.random.PRNGKey(5)
    l1 = gpt_loss(cfg, params, tokens, labels, dropout_key=key,
                  deterministic=False)
    l2 = gpt_loss(cfg, params, tokens, labels, dropout_key=key,
                  deterministic=False)
    assert float(l1) == float(l2)
    l3 = gpt_loss(cfg, params, tokens, labels,
                  dropout_key=jax.random.PRNGKey(6), deterministic=False)
    assert float(l1) != float(l3)


# ---------------------------------------------------------------------------
# selective_elementwise remat
# ---------------------------------------------------------------------------

def test_grad_of_remat_equivalence():
    """selective_elementwise replays less but must compute the SAME loss
    and grads as full-layer remat (and as no remat)."""
    cfg_i = dataclasses.replace(_CFG, fused_block=True,
                                fused_block_interpret=True)
    params = init_gpt_params(cfg_i, jax.random.PRNGKey(0))
    tokens, labels = _tok()
    results = {}
    for rg in (None, "full", "selective_elementwise"):
        cfg = dataclasses.replace(cfg_i, recompute_granularity=rg)
        l, g = jax.value_and_grad(
            lambda p, cfg=cfg: gpt_loss(cfg, p, tokens, labels))(params)
        results[rg] = (float(l), g)
    for rg in ("full", "selective_elementwise"):
        assert results[rg][0] == results[None][0]
        for a, c in zip(jax.tree_util.tree_leaves(results[rg][1]),
                        jax.tree_util.tree_leaves(results[None][1])):
            assert jnp.abs(a - c).max() < 1e-7


def test_selective_elementwise_saves_fewer_residuals():
    """Measured via jaxpr (jax's own saved-residuals accounting of the
    checkpointed layer): the policy saves strictly less than running
    without remat, strictly more than full-layer remat (it keeps the
    matmul/attention/fused-tail outputs), and among the kept residuals
    are the fused-block kernel outputs."""
    saved_residuals = pytest.importorskip(
        "jax._src.ad_checkpoint").saved_residuals

    cfg = dataclasses.replace(_CFG, fused_block=True,
                              fused_block_interpret=True)
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    lp = jax.tree_util.tree_map(lambda x: x[0], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(3), (32, 2, 64))

    def layer(lp, h):
        return transformer_layer(cfg, lp, h, None, None, None, True)

    def res_bytes(fn):
        res = saved_residuals(fn, lp, h)
        return sum(int(np.prod(aval.shape)) * aval.dtype.itemsize
                   for aval, _ in res if hasattr(aval, "shape"))

    b_none = res_bytes(layer)
    b_full = res_bytes(jax.checkpoint(layer))
    b_sel = res_bytes(
        jax.checkpoint(layer, policy=_selective_elementwise_policy))
    assert b_full < b_sel < b_none


def test_selective_elementwise_over_the_xla_form_saves_what_selective_saves(
        monkeypatch):
    """With no tail ``pallas_call`` in the trace (a program compiled for a
    TPU since PR 34) ``selective_elementwise`` keeps exactly what
    ``selective`` keeps: the GEMM outputs and the layer's input, no output
    of a tail."""
    saved_residuals = pytest.importorskip(
        "jax._src.ad_checkpoint").saved_residuals
    from apex_tpu.transformer.testing.standalone_transformer_lm import (
        _selective_policy,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(_CFG, fused_block=True,
                              use_flash_attention=False)
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    lp = jax.tree_util.tree_map(lambda x: x[0], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(3), (32, 2, 64))

    def layer(lp, h):
        return transformer_layer(cfg, lp, h, None, None, None, True)

    def kept(policy):
        res = saved_residuals(jax.checkpoint(layer, policy=policy), lp, h)
        return sorted((tuple(aval.shape), str(aval.dtype), why)
                      for aval, why in res if hasattr(aval, "shape"))

    sel, sel_el = kept(_selective_policy), kept(_selective_elementwise_policy)
    assert sel_el == sel
    # the interpreted kernels' outputs are what the policy adds: the same
    # layer with them in the trace keeps more
    cfg_i = dataclasses.replace(cfg, fused_block_interpret=True)

    def layer_i(lp, h):
        return transformer_layer(cfg_i, lp, h, None, None, None, True)

    res_i = saved_residuals(
        jax.checkpoint(layer_i, policy=_selective_elementwise_policy), lp, h)
    assert len([1 for aval, _ in res_i if hasattr(aval, "shape")]) > len(sel)


# ---------------------------------------------------------------------------
# analysis rule 6 (scopes) + headline-step cleanliness
# ---------------------------------------------------------------------------

def test_unscoped_kernel_variant_trips_rule6():
    """Seeded red test: a variant that launches the fused-tail kernel
    body WITHOUT the apex_tpu.* named scope (the mistake the public
    entry points exist to prevent) must trip the scopes rule."""
    from jax.experimental import pallas as pl

    from apex_tpu.ops.fused_block import (
        _bias_gelu_fwd_kernel, _row_spec, _vec_spec,
    )

    x = jnp.ones((8, 128))
    b = jnp.ones((1, 128))

    def unscoped(x, b):
        y = pl.pallas_call(
            _bias_gelu_fwd_kernel,
            name="apex_tpu_bias_gelu_fwd_unscoped_variant",
            grid=(1,),
            in_specs=[_row_spec(8, 128), _vec_spec(128)],
            out_specs=_row_spec(8, 128),
            out_shape=jax.ShapeDtypeStruct((8, 128), x.dtype),
            interpret=True,
        )(x, b)
        return y.sum()

    rep = audit_step(jax.jit(unscoped), x, b, rules=("scopes",))
    assert "unscoped_kernel" in [f.code for f in rep.findings]


def test_scoped_public_entry_points_clean():
    x = jnp.ones((8, 128))
    b = jnp.ones((128,))
    r = jnp.ones((8, 128))
    w = jnp.ones((128,))

    def scoped(x, b, r, w):
        y = bias_gelu(x, b, interpret=True)
        y = bias_dropout_residual(y, b, r, interpret=True)
        s, y2 = residual_add_layer_norm(y, b, r, w, b, interpret=True)
        return (s * y2).sum()

    rep = audit_step(jax.jit(scoped), x, b, r, w, rules=("scopes",))
    assert [f.code for f in rep.findings] == []


def test_fused_headline_step_audits_clean():
    """The acceptance gate: the REAL fused_block + selective_elementwise
    headline-shaped train step (tools/static_audit.py's 5th self-audit
    target) passes assert_step_clean — donation covered, kernels scoped,
    no error-severity dtype findings."""
    from tools.static_audit import TARGETS

    fn, args, kw = TARGETS["fused_block_step"]()
    rep = assert_step_clean(fn, *args, name="fused_block_step", **kw)
    # and specifically: none of the fused kernels are unscoped, and the
    # kernels introduced no NEW double-cast (the one pre-existing
    # warning is the remat'd XLA-softmax chain, present for any
    # recompute mode since PR 4 — see docs/fused_block.md)
    assert "unscoped_kernel" not in [f.code for f in rep.findings]
    assert sum(f.code == "double_cast" for f in rep.findings) <= 1
