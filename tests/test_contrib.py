"""Tests for the contrib kernel pack.

Mirrors reference contrib suites (``apex/contrib/test/``): each component
vs an independent reference implementation — torch CPU where the reference
compares against torch modules (group_norm, clip_grad), hand numpy math
elsewhere (xentropy, focal_loss, sparsity).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

from apex_tpu.contrib.clip_grad import clip_grad_norm_
from apex_tpu.contrib.focal_loss import FocalLoss, focal_loss
from apex_tpu.contrib.group_norm import GroupNorm, group_norm_nhwc
from apex_tpu.contrib.index_mul_2d import index_mul_2d
from apex_tpu.contrib.layer_norm import FastLayerNorm, FastLayerNormFN
from apex_tpu.contrib.sparsity import ASP, create_mask
from apex_tpu.contrib.xentropy import SoftmaxCrossEntropyLoss, softmax_cross_entropy_loss


# ---------------------------------------------------------------- clip_grad


def _rand_tree(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {
        "w": jax.random.normal(ks[0], (5, 7)),
        "b": jax.random.normal(ks[1], (7,)) * 3.0,
        "nested": [jax.random.normal(ks[2], (2, 3, 4))],
    }


def test_clip_grad_norm_matches_torch():
    grads = _rand_tree()
    tleaves = [torch.tensor(np.asarray(g), requires_grad=True)
               for g in jax.tree_util.tree_leaves(grads)]
    for t in tleaves:
        t.grad = t.detach().clone()
    max_norm = 1.7
    tnorm = torch.nn.utils.clip_grad_norm_(tleaves, max_norm)

    clipped, norm = clip_grad_norm_(grads, max_norm)
    np.testing.assert_allclose(float(norm), float(tnorm), rtol=1e-6)
    for ours, t in zip(jax.tree_util.tree_leaves(clipped), tleaves):
        np.testing.assert_allclose(np.asarray(ours), t.grad.numpy(), rtol=1e-5)


def test_clip_grad_norm_no_clip_below_threshold():
    grads = {"a": jnp.ones((2, 2)) * 0.1}
    clipped, norm = clip_grad_norm_(grads, 100.0)
    np.testing.assert_allclose(np.asarray(clipped["a"]), 0.1, rtol=1e-6)
    np.testing.assert_allclose(float(norm), 0.2, rtol=1e-6)


def test_clip_grad_norm_inf_norm():
    grads = {"a": jnp.array([1.0, -5.0]), "b": jnp.array([[3.0]])}
    clipped, norm = clip_grad_norm_(grads, 1.0, norm_type=math.inf)
    assert float(norm) == 5.0
    np.testing.assert_allclose(np.asarray(clipped["a"]),
                               np.array([0.2, -1.0]), rtol=1e-5)


def test_clip_grad_norm_jits():
    grads = _rand_tree(1)
    f = jax.jit(lambda g: clip_grad_norm_(g, 1.0))
    clipped, norm = f(grads)
    ref_norm = math.sqrt(sum(float(jnp.sum(g.astype(jnp.float32) ** 2))
                             for g in jax.tree_util.tree_leaves(grads)))
    np.testing.assert_allclose(float(norm), ref_norm, rtol=1e-5)
    del clipped


# ----------------------------------------------------------------- xentropy


def _np_smoothed_ce(logits, labels, smoothing, padding_idx):
    x = np.asarray(logits, np.float64)
    lse = np.log(np.sum(np.exp(x - x.max(-1, keepdims=True)), -1)) + x.max(-1)
    picked = x[np.arange(len(labels)), labels]
    loss = smoothing * (lse - x.mean(-1)) + (1 - smoothing) * (lse - picked)
    loss[np.asarray(labels) == padding_idx] = 0.0
    return loss


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_xentropy_vs_numpy(smoothing):
    k = jax.random.PRNGKey(3)
    logits = jax.random.normal(k, (9, 13)) * 4.0
    labels = jnp.array([0, 1, 5, 12, 3, 0, 7, 2, 9])
    ours = softmax_cross_entropy_loss(logits, labels, smoothing, padding_idx=0)
    ref = _np_smoothed_ce(logits, labels, smoothing, 0)
    np.testing.assert_allclose(np.asarray(ours), ref, rtol=1e-5, atol=1e-6)
    # padding rows (label==0) give zero loss AND zero gradient
    g = jax.grad(lambda lg: jnp.sum(
        softmax_cross_entropy_loss(lg, labels, smoothing, 0)))(logits)
    assert float(jnp.abs(g[0]).max()) == 0.0 and float(jnp.abs(g[5]).max()) == 0.0
    assert float(jnp.abs(g[1]).max()) > 0.0


def test_xentropy_apply_shim_and_torch_parity():
    # smoothing=0, no padding hit -> plain torch F.cross_entropy(reduction=none)
    logits = jax.random.normal(jax.random.PRNGKey(0), (6, 11))
    labels = jnp.array([1, 2, 3, 4, 5, 10])
    ours = SoftmaxCrossEntropyLoss.apply(logits, labels, 0.0, padding_idx=-100)
    ref = torch.nn.functional.cross_entropy(
        torch.tensor(np.asarray(logits)),
        torch.tensor(np.asarray(labels), dtype=torch.long),
        reduction="none")
    np.testing.assert_allclose(np.asarray(ours), ref.numpy(), rtol=1e-5)


# --------------------------------------------------------------- group_norm


@pytest.mark.parametrize("act", [None, "swish"])
def test_group_norm_nhwc_vs_torch(act):
    n, h, w, c, g = 2, 5, 6, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(7), (n, h, w, c))
    weight = jax.random.normal(jax.random.PRNGKey(8), (c,)) * 0.2 + 1.0
    bias = jax.random.normal(jax.random.PRNGKey(9), (c,)) * 0.1
    y = group_norm_nhwc(x, g, weight, bias, eps=1e-5, act=act)

    tx = torch.tensor(np.asarray(x)).permute(0, 3, 1, 2)  # NHWC -> NCHW
    gn = torch.nn.GroupNorm(g, c, eps=1e-5)
    with torch.no_grad():
        gn.weight.copy_(torch.tensor(np.asarray(weight)))
        gn.bias.copy_(torch.tensor(np.asarray(bias)))
    ty = gn(tx)
    if act == "swish":
        ty = ty * torch.sigmoid(ty)
    ty = ty.permute(0, 2, 3, 1).detach().numpy()
    np.testing.assert_allclose(np.asarray(y), ty, rtol=1e-4, atol=1e-5)


def test_group_norm_module_and_grads():
    m = GroupNorm(num_groups=2, num_channels=8, act="silu")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 3, 8))
    v = m.init(jax.random.PRNGKey(1), x)
    y, grads = jax.value_and_grad(
        lambda vv: jnp.sum(m.apply(vv, x) ** 2))(v)
    assert np.isfinite(float(y))
    for leaf in jax.tree_util.tree_leaves(grads):
        assert np.all(np.isfinite(np.asarray(leaf)))
    with pytest.raises(ValueError):
        m.apply(v, jnp.zeros((1, 2, 2, 4)))


def test_group_norm_bad_args():
    x = jnp.zeros((1, 2, 2, 6))
    with pytest.raises(ValueError):
        group_norm_nhwc(x, 4, None, None)  # 6 % 4 != 0
    with pytest.raises(ValueError):
        group_norm_nhwc(x, 2, None, None, act="relu")


# --------------------------------------------------------------- focal_loss


def _np_focal(logits, targets, npos, num_real, alpha, gamma, smoothing):
    p = np.asarray(logits, np.float64)
    y = np.asarray(targets)
    ncls = p.shape[-1]
    ids = np.arange(ncls)
    is_pos = (y[..., None] == ids) & (y[..., None] >= 0)
    t = np.where(is_pos, 1 - smoothing + smoothing / 2, smoothing / 2)
    sig = 1 / (1 + np.exp(-p))
    bce = -t * np.log(sig) - (1 - t) * np.log1p(-sig)
    coeff = np.where(is_pos, alpha * (1 - sig) ** gamma, (1 - alpha) * sig ** gamma)
    elem = coeff * bce
    valid = (y[..., None] != -2) & (ids < num_real)
    return np.sum(np.where(valid, elem, 0.0)) / npos


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_focal_loss_vs_numpy(smoothing):
    k = jax.random.PRNGKey(11)
    logits = jax.random.normal(k, (4, 6, 8)) * 2.0  # padded to 8, 7 real
    targets = jnp.array([[0, 3, -1, 6, -2, 2],
                         [1, -1, -1, 5, 0, -2],
                         [-1, -1, -1, -1, -1, -1],
                         [4, 4, 4, -2, -2, 0]])
    npos = 9.0
    ours = focal_loss(logits, targets, jnp.float32(npos), 7, 0.25, 2.0, smoothing)
    ref = _np_focal(logits, targets, npos, 7, 0.25, 2.0, smoothing)
    np.testing.assert_allclose(float(ours), ref, rtol=1e-5)


def test_focal_loss_ignore_and_padding_have_no_grad():
    logits = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 8))
    targets = jnp.array([[0, -2, 1], [-2, -2, 2]])
    g = jax.grad(lambda lg: FocalLoss.apply(
        lg, targets, jnp.float32(3.0), 6, 0.25, 2.0))(logits)
    # ignored examples (-2): zero grad everywhere
    assert float(jnp.abs(g[0, 1]).max()) == 0.0
    assert float(jnp.abs(g[1, 0]).max()) == 0.0
    # padded classes (>= num_real_classes=6): zero grad
    assert float(jnp.abs(g[..., 6:]).max()) == 0.0
    assert float(jnp.abs(g[0, 0, :6]).max()) > 0.0


# ------------------------------------------------------------- index_mul_2d


def test_index_mul_2d_forward_and_grads():
    in1 = jax.random.normal(jax.random.PRNGKey(0), (5, 4))
    in2 = jax.random.normal(jax.random.PRNGKey(1), (7, 4))
    idx = jnp.array([0, 2, 2, 4, 1, 0, 3])
    out = index_mul_2d(in1, in2, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(in1)[np.asarray(idx)]
                               * np.asarray(in2), rtol=1e-6)

    # backward: grad_in1 is a scatter-add over duplicate indices
    g1, g2 = jax.grad(lambda a, b: jnp.sum(index_mul_2d(a, b, idx) ** 2),
                      argnums=(0, 1))(in1, in2)
    n1, n2, nidx = map(np.asarray, (in1, in2, idx))
    ref_g1 = np.zeros_like(n1)
    for i, j in enumerate(nidx):
        ref_g1[j] += 2 * (n1[j] * n2[i]) * n2[i]
    np.testing.assert_allclose(np.asarray(g1), ref_g1, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g2), 2 * n1[nidx] * n2 * n1[nidx],
                               rtol=1e-5, atol=1e-6)

    # double backward exists (reference ships a dedicated kernel for it)
    h = jax.grad(lambda a: jnp.sum(jax.grad(
        lambda aa: jnp.sum(index_mul_2d(aa, in2, idx) ** 2))(a) ** 2))(in1)
    assert np.all(np.isfinite(np.asarray(h)))


def test_index_mul_2d_contract_checks():
    with pytest.raises(RuntimeError):
        index_mul_2d(jnp.zeros((2, 2, 2)), jnp.zeros((2, 2)), jnp.array([0]))
    with pytest.raises(RuntimeError):
        index_mul_2d(jnp.zeros((2, 2)), jnp.zeros((3, 2)), jnp.array([0, 1]))
    with pytest.raises(RuntimeError):
        index_mul_2d(jnp.zeros((2, 2)), jnp.zeros((2, 2), jnp.bfloat16),
                     jnp.array([0, 1]))


# ----------------------------------------------------------------- sparsity


def test_create_mask_m4n2_keeps_two_largest_of_four():
    w = jnp.array([[0.1, -5.0, 3.0, 0.2, 1.0, 2.0, -3.0, 0.0]])
    mask = create_mask(w, "m4n2_1d")
    np.testing.assert_array_equal(
        np.asarray(mask), [[0, 1, 1, 0, 0, 1, 1, 0]])
    assert mask.dtype == w.dtype


@pytest.mark.parametrize("shape", [(8,), (6, 8), (6, 8, 3), (6, 8, 3, 3)])
def test_create_mask_density_and_rank_dispatch(shape):
    w = jax.random.normal(jax.random.PRNGKey(2), shape)
    mask = create_mask(w, "m4n2_1d")
    assert mask.shape == w.shape
    np.testing.assert_allclose(float(jnp.mean(mask)), 0.5)
    # every 4-group along the input-channel direction (axis 1 for rank>=2,
    # axis 0 for rank 1) has exactly 2 kept
    m = np.asarray(mask)
    if m.ndim >= 2:
        m = np.moveaxis(m, 1, -1)  # channel dim last
    groups = m.reshape(-1, 4)
    np.testing.assert_array_equal(groups.sum(1), 2)


def test_asp_workflow_and_wrapped_step():
    from apex_tpu.optimizers import FusedSGD

    params = {"dense": jax.random.normal(jax.random.PRNGKey(0), (8, 8)),
              "bias": jnp.ones((8,))}
    asp = ASP(mask_calculator="m4n2_1d",
              whitelist=lambda path, p: p.ndim == 2)
    masks = asp.compute_sparse_masks(params)
    np.testing.assert_allclose(float(jnp.mean(masks["dense"])), 0.5)
    np.testing.assert_allclose(np.asarray(masks["bias"]), 1.0)  # not whitelisted

    pruned = asp.apply_masks(params, masks)
    assert float(jnp.sum(pruned["dense"] == 0)) >= 32

    opt = FusedSGD(lr=0.5)
    state = opt.init(pruned)
    grads = jax.tree_util.tree_map(jnp.ones_like, pruned)
    step = asp.wrap_step(opt.step, masks)
    new_params, _ = step(grads, state, pruned)
    # masked slots stay exactly zero after the update
    np.testing.assert_array_equal(
        np.asarray(new_params["dense"] == 0), np.asarray(masks["dense"] == 0))
    # unmasked slots moved
    moved = np.asarray(new_params["dense"] != pruned["dense"])
    assert moved[np.asarray(masks["dense"]) == 1].all()


def test_asp_rejects_permutation():
    with pytest.raises(NotImplementedError):
        ASP(allow_permutation=True)


# --------------------------------------------------------- contrib layer_norm


def test_fast_layer_norm_vs_torch():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32)) * 2.0
    gamma = jax.random.normal(jax.random.PRNGKey(1), (32,)) * 0.1 + 1.0
    beta = jax.random.normal(jax.random.PRNGKey(2), (32,)) * 0.1
    y = FastLayerNormFN.apply(x, gamma, beta, 1e-5)
    ref = torch.nn.functional.layer_norm(
        torch.tensor(np.asarray(x)), (32,),
        torch.tensor(np.asarray(gamma)), torch.tensor(np.asarray(beta)), 1e-5)
    np.testing.assert_allclose(np.asarray(y), ref.numpy(), rtol=1e-4, atol=1e-5)


def test_fast_layer_norm_module():
    m = FastLayerNorm(hidden_size=16, memory_efficient=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 16))
    v = m.init(jax.random.PRNGKey(1), x)
    y = m.apply(v, x)
    np.testing.assert_allclose(float(jnp.mean(y)), 0.0, atol=1e-5)


# ------------------------------------------------------------- import smoke


def test_all_public_names_import():
    import importlib
    import apex_tpu

    for name in ("amp", "optimizers", "normalization", "multi_tensor_apply",
                 *apex_tpu._LAZY_SUBMODULES):
        assert getattr(apex_tpu, name) is not None
    contrib = importlib.import_module("apex_tpu.contrib")
    # EVERY contrib subpackage must import (round-2 regression: a stub
    # __init__ made `import apex_tpu.contrib` itself raise)
    for sub in ("optimizers",) + contrib._LAZY:
        mod = importlib.import_module(f"apex_tpu.contrib.{sub}")
        for name in getattr(mod, "__all__", ()):
            assert hasattr(mod, name), f"contrib.{sub}.{name}"


def test_lm_head_cross_entropy_matches_unfused():
    """Chunk-fused head GEMM + CE == full-logits reference, loss AND grads
    (incl. d(head_weight) accumulated across chunks by the scan transpose)."""
    from apex_tpu.contrib.xentropy import lm_head_cross_entropy

    n, h, v = 64, 16, 96
    hid = jax.random.normal(jax.random.PRNGKey(0), (n, h))
    w = jax.random.normal(jax.random.PRNGKey(1), (v, h)) * 0.3
    labels = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, v)

    def fused(hid, w):
        return jnp.mean(lm_head_cross_entropy(hid, w, labels, chunk_size=16))

    def unfused(hid, w):
        logits = hid @ w.T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return jnp.mean(-jnp.take_along_axis(logp, labels[:, None], 1)[:, 0])

    np.testing.assert_allclose(
        float(fused(hid, w)), float(unfused(hid, w)), rtol=1e-6)
    gf = jax.grad(fused, argnums=(0, 1))(hid, w)
    gr = jax.grad(unfused, argnums=(0, 1))(hid, w)
    for a, b, name in zip(gf, gr, ("d_hidden", "d_head_weight")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6, err_msg=name)

    with pytest.raises(ValueError, match="divisible"):
        lm_head_cross_entropy(hid, w, labels, chunk_size=24)


def _ce_case(n=64, h=16, v=96, dtype=jnp.float32):
    hid = jax.random.normal(jax.random.PRNGKey(0), (n, h)).astype(dtype)
    w = (jax.random.normal(jax.random.PRNGKey(1), (v, h)) * 0.3).astype(dtype)
    labels = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, v)
    return hid, w, labels


def _row_weights(kind, n):
    if kind == "uniform":
        return jnp.full((n,), 1.0 / n)
    if kind == "mask":          # a masked mean: zero rows and 1 / count
        m = (jax.random.uniform(jax.random.PRNGKey(3), (n,)) > 0.4)
        return m.astype(jnp.float32) / jnp.sum(m)
    return jax.random.uniform(jax.random.PRNGKey(4), (n,), minval=0.1,
                              maxval=3.0)


@pytest.mark.parametrize("chunk", [64, 16], ids=["one_chunk", "four_chunks"])
@pytest.mark.parametrize("weights", ["uniform", "mask", "arbitrary"])
def test_lm_head_cross_entropy_sum_matches_full_logits(weights, chunk):
    """The weighted sum with its gradient from the forward chunk loop
    against the full-logits float32 reference: the loss, d(hidden),
    d(head_weight) and d(weights)."""
    from apex_tpu.contrib.xentropy import lm_head_cross_entropy_sum

    hid, w, labels = _ce_case()
    rw = _row_weights(weights, hid.shape[0])

    def fused(hid, w, rw):
        return lm_head_cross_entropy_sum(hid, w, labels, rw, chunk_size=chunk)

    def reference(hid, w, rw):
        logp = jax.nn.log_softmax(hid @ w.T, axis=-1)
        return jnp.sum(rw * -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0])

    with jax.default_matmul_precision("highest"):
        lf, gf = jax.value_and_grad(fused, argnums=(0, 1, 2))(hid, w, rw)
        lr, gr = jax.value_and_grad(reference, argnums=(0, 1, 2))(hid, w, rw)
    np.testing.assert_allclose(float(lf), float(lr), rtol=1e-6)
    for a, b, name in zip(gf, gr, ("d_hidden", "d_head_weight", "d_weights")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6, err_msg=name)
    with pytest.raises(ValueError, match="divisible"):
        lm_head_cross_entropy_sum(hid, w, labels, rw, chunk_size=24)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16],
                         ids=["bfloat16", "float16"])
def test_lm_head_cross_entropy_sum_in_half_precision_with_a_loss_scale(dtype):
    """Half-precision rows and head under amp's 2**16 loss scale and a
    mean's 1/n weights: the gradients keep the operands' dtype and agree
    with the float32 reference to the dtype's epsilon in norm. A flat
    softmax over 4096 classes at n = 2048 puts ``softmax * weight`` near
    1e-7, in float16's subnormals: a gradient rounded to float16 before
    the scale reaches it reads 3-4 times the epsilon off."""
    from apex_tpu.contrib.xentropy import lm_head_cross_entropy_sum

    hid, w, labels = _ce_case(n=2048, h=32, v=4096, dtype=dtype)
    rw = _row_weights("uniform", hid.shape[0])
    scale = 2.0 ** 16
    dh, dw = jax.grad(lambda a, b: scale * lm_head_cross_entropy_sum(
        a, b, labels, rw, chunk_size=512), argnums=(0, 1))(hid, w)
    assert dh.dtype == dw.dtype == dtype

    def reference(a, b):
        logp = jax.nn.log_softmax(a @ b.T, axis=-1)
        return scale * jnp.mean(
            -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0])

    with jax.default_matmul_precision("highest"):
        rh, rwt = jax.grad(reference, argnums=(0, 1))(
            hid.astype(jnp.float32), w.astype(jnp.float32))
    for a, b, name in ((dh, rh, "d_hidden"), (dw, rwt, "d_head_weight")):
        err = jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b)
        assert float(err) <= float(jnp.finfo(dtype).eps), name


def test_lm_head_cross_entropy_sum_traces_alike_at_any_vocabulary():
    """The differentiated loss is the same number of equations at 96 and
    at 192 vocabulary rows: nothing walks the head's rows at trace time
    (cell 1's 50,304 rows once cost ten seconds of set-up so)."""
    from apex_tpu.analysis import walk
    from apex_tpu.contrib.xentropy import lm_head_cross_entropy_sum

    def equations(v):
        hid, w, labels = _ce_case(v=v)
        rw = _row_weights("uniform", hid.shape[0])
        jaxpr = jax.make_jaxpr(jax.value_and_grad(
            lambda a, b: lm_head_cross_entropy_sum(a, b, labels, rw,
                                                   chunk_size=16),
            argnums=(0, 1)))(hid, w)
        return sum(1 for _ in walk(jaxpr.jaxpr))

    assert equations(96) == equations(192)


def _head_gemms(jaxpr, v, h):
    """dot_generals of a jaxpr (sub-jaxprs included) that take the
    ``[V, h]`` head weight with a block of hidden rows ``[*, h]``."""
    from apex_tpu.analysis import walk

    found = 0
    for eqn, _ in walk(jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        shapes = sorted(tuple(x.aval.shape) for x in eqn.invars)
        found += (v, h) in shapes and any(
            s != (v, h) and len(s) == 2 and s[1] == h for s in shapes)
    return found


@pytest.mark.parametrize("fn, forward, backward", [
    ("lm_head_cross_entropy_sum", 1, 0),
    ("lm_head_cross_entropy", 1, 1),
])
def test_the_head_gemm_runs_once_with_the_sum_and_is_replayed_per_row(
        fn, forward, backward):
    """Counted in the jaxprs of ``jax.vjp``: the weighted sum's backward
    holds no product of the head weight with the hidden rows, where the
    per-row loss's backward replays it."""
    from apex_tpu.contrib import xentropy

    hid, w, labels = _ce_case()
    rw = _row_weights("uniform", hid.shape[0])
    if fn == "lm_head_cross_entropy_sum":
        def loss(hid, w):
            return xentropy.lm_head_cross_entropy_sum(hid, w, labels, rw,
                                                      chunk_size=16)
    else:
        def loss(hid, w):
            return jnp.mean(xentropy.lm_head_cross_entropy(
                hid, w, labels, chunk_size=16))
    v, h = w.shape
    fwd = jax.make_jaxpr(lambda a, b: jax.vjp(loss, a, b)[0])(hid, w)
    _, vjp_fn = jax.vjp(loss, hid, w)
    bwd = jax.make_jaxpr(vjp_fn)(jnp.float32(1.0))
    assert _head_gemms(fwd.jaxpr, v, h) == forward
    assert _head_gemms(bwd.jaxpr, v, h) == backward


# ---------------------------------------------------------------------------
# sparsity: channel-permutation search (permutation_search_kernels)
# ---------------------------------------------------------------------------


def test_apply_2_to_4_structure_and_kept_sum():
    from apex_tpu.contrib.sparsity import apply_2_to_4, sum_after_2_to_4

    m = jax.random.normal(jax.random.PRNGKey(0), (16, 12))
    pruned = apply_2_to_4(m)
    groups = np.asarray(pruned).reshape(16, 3, 4)
    assert ((groups != 0).sum(-1) <= 2).all()
    # kept sum equals the brute-force top-2 magnitude per group
    a = np.abs(np.asarray(m)).reshape(16, 3, 4)
    top2 = np.sort(a, axis=-1)[..., 2:].sum()
    assert abs(float(sum_after_2_to_4(m)) - top2) < 1e-4
    with pytest.raises(ValueError, match="multiple of 4"):
        apply_2_to_4(jnp.zeros((4, 6)))


def test_channel_swap_search_improves_and_is_valid():
    from apex_tpu.contrib.sparsity import (
        channel_swap_search,
        sum_after_2_to_4,
    )

    m = jax.random.normal(jax.random.PRNGKey(1), (24, 16))
    base = float(sum_after_2_to_4(m))
    perm, kept = channel_swap_search(np.asarray(m), max_iters=100)
    assert sorted(perm.tolist()) == list(range(16))
    permuted_kept = float(sum_after_2_to_4(m[:, perm]))
    assert abs(permuted_kept - kept) < 1e-3
    assert permuted_kept >= base - 1e-5  # never worse than identity


def test_channel_swap_search_escape_needs_key():
    from apex_tpu.contrib.sparsity import channel_swap_search

    m = np.random.default_rng(2).standard_normal((8, 8)).astype(np.float32)
    with pytest.raises(ValueError, match="requires key"):
        channel_swap_search(m, max_iters=50, escape_attempts=2)
    perm, _ = channel_swap_search(
        m, max_iters=50, escape_attempts=2, key=jax.random.PRNGKey(3)
    )
    assert sorted(perm.tolist()) == list(range(8))


def test_permutation_C_K_pair_preserves_composition():
    """Consumer-C + producer-K permutation leaves the composed network
    function unchanged (the identity the reference's fx graph pass
    maintains, permutation_lib.py apply_permutation_in_{C,K}_dim)."""
    from apex_tpu.contrib.sparsity import (
        apply_permutation_C,
        apply_permutation_K,
        channel_swap_search,
    )

    rng = np.random.default_rng(4)
    W1 = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)  # producer
    W2 = jnp.asarray(rng.standard_normal((4, 16)), jnp.float32)  # consumer
    x = jnp.asarray(rng.standard_normal((8,)), jnp.float32)
    perm, _ = channel_swap_search(np.asarray(W2), max_iters=50)
    y = W2 @ (W1 @ x)
    y_perm = apply_permutation_C(W2, perm) @ (apply_permutation_K(W1, perm) @ x)
    assert jnp.abs(y - y_perm).max() < 1e-4


def test_exhaustive_search_canonical_perm_counts():
    """The unique-combination generator matches the reference's counts
    (exhaustive_search.py: 35 for 8 cols / width 4, 5775 for 12)."""
    from apex_tpu.contrib.sparsity.permutation_search import (
        _canonical_group_perms,
    )

    p8 = _canonical_group_perms(8)
    assert p8.shape == (35, 8)
    # first entry is the identity (greedy gain baseline relies on it)
    np.testing.assert_array_equal(p8[0], np.arange(8))
    assert _canonical_group_perms(12).shape == (5775, 12)


def test_exhaustive_search_finds_global_optimum_single_window():
    """With c == window_size the search IS a global exhaustive search:
    check against direct enumeration of all 35 assignments."""
    from apex_tpu.contrib.sparsity.permutation_search import (
        _canonical_group_perms,
        exhaustive_search,
        sum_after_2_to_4,
    )

    m = np.random.default_rng(42).normal(size=(8, 8)).astype(np.float32)
    perm, kept = exhaustive_search(m, escape_attempts=0)
    best = max(
        float(sum_after_2_to_4(jnp.asarray(m)[:, p]))
        for p in _canonical_group_perms(8)
    )
    np.testing.assert_allclose(kept, best, rtol=1e-6)
    np.testing.assert_allclose(
        float(sum_after_2_to_4(jnp.asarray(m)[:, perm])), kept, rtol=1e-6)


def test_exhaustive_search_beats_greedy_on_seeded_cases():
    """VERDICT round-3 item 6 done-criterion: warm-started from the greedy
    channel-swap result, the exhaustive window search never loses and
    strictly improves on several seeds."""
    from apex_tpu.contrib.sparsity import (
        channel_swap_search,
        exhaustive_search,
        sum_after_2_to_4,
    )

    strict_wins = 0
    for seed in range(8):
        m = np.random.default_rng(seed).normal(size=(16, 16)).astype(
            np.float32)
        pg, kg = channel_swap_search(np.asarray(m), max_iters=200)
        pe, ke = exhaustive_search(
            m, escape_attempts=4, key=jax.random.PRNGKey(seed),
            initial_permutation=pg,
        )
        # the reported kept is achieved by the returned permutation
        np.testing.assert_allclose(
            float(sum_after_2_to_4(jnp.asarray(m)[:, pe])), ke, rtol=1e-5)
        assert ke >= kg - 1e-4, (seed, kg, ke)
        strict_wins += ke > kg + 1e-4
    assert strict_wins >= 2, strict_wins


def test_exhaustive_search_validation_and_small_inputs():
    from apex_tpu.contrib.sparsity import exhaustive_search

    with pytest.raises(ValueError, match="multiple"):
        exhaustive_search(np.ones((4, 6)))
    with pytest.raises(ValueError, match="window_size"):
        exhaustive_search(np.ones((4, 8)), window_size=6)
    with pytest.raises(ValueError, match="requires key"):
        exhaustive_search(np.ones((4, 16)), escape_attempts=2)
    # fewer stripes than the window: identity, no search
    perm, kept = exhaustive_search(np.ones((4, 4)), escape_attempts=0)
    np.testing.assert_array_equal(perm, np.arange(4))
