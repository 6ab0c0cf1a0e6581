"""Tests for apex_tpu.parallel: DDP grad sync, SyncBatchNorm, LARC.

Mirrors the reference's distributed test strategy (SURVEY.md §4):
cross-rank equality after sync, SyncBN vs single-device BN equivalence
(``tests/distributed/synced_batchnorm/``), LARC behavioural checks
(``tests/L0/run_amp/test_larc.py``) — on an 8-virtual-device CPU mesh.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from apex_tpu.parallel import (
    DistributedDataParallel,
    LARC,
    flatten,
    larc_adjust_gradients,
    sync_batch_norm,
    sync_gradients,
    unflatten,
)
from apex_tpu.optimizers import FusedSGD


def _mesh():
    return Mesh(np.array(jax.devices()), ("data",))


def test_flatten_unflatten_roundtrip():
    tree = {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "b": (jnp.ones((4,), jnp.bfloat16), jnp.zeros((2, 2), jnp.float32)),
    }
    flat = flatten(tree)
    assert flat.ndim == 1 and flat.size == 6 + 4 + 4
    out = jax.tree_util.tree_map(np.asarray, unflatten(flat, tree))
    ref = jax.tree_util.tree_map(np.asarray, tree)
    for o, r in zip(jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(o, np.asarray(r, dtype=o.dtype))


@pytest.mark.parametrize("fp32,predivide", [(False, 1.0), (True, 4.0)])
def test_sync_gradients_mean(fp32, predivide):
    mesh = _mesh()
    grads = jnp.arange(8 * 3, dtype=jnp.float32).reshape(8, 3)

    f = shard_map(
        functools.partial(
            sync_gradients,
            axis_name="data",
            gradient_average=True,
            allreduce_always_fp32=fp32,
            gradient_predivide_factor=predivide,
        ),
        mesh=mesh,
        in_specs=P("data", None),
        out_specs=P("data", None),
    )
    out = np.asarray(f(grads))
    expected = np.broadcast_to(np.asarray(grads).mean(0), (1, 3))
    for r in range(8):
        np.testing.assert_allclose(out[r], expected[0], rtol=1e-6)


def test_sync_gradients_sum():
    mesh = _mesh()
    grads = jnp.ones((8, 4), jnp.float32)
    f = shard_map(
        functools.partial(sync_gradients, axis_name="data", gradient_average=False),
        mesh=mesh, in_specs=P("data", None), out_specs=P("data", None),
    )
    np.testing.assert_allclose(np.asarray(f(grads)), 8.0)


def test_ddp_wrap_grad_fn_and_broadcast():
    mesh = _mesh()
    ddp = DistributedDataParallel(axis_name="data")

    def loss_fn(w, x):
        return jnp.sum((x @ w) ** 2)

    w = jnp.ones((4, 2), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 4))

    def per_shard(w, x):
        g = ddp.wrap_grad_fn(jax.grad(loss_fn))(w, x)
        return g

    g_sync = shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(None, None), P("data", None)),
        out_specs=P(None, None), check_vma=False,
    )(w, x)
    # synced grads equal the mean of per-shard grads
    per = [np.asarray(jax.grad(loss_fn)(w, x[i : i + 1])) for i in range(8)]
    np.testing.assert_allclose(np.asarray(g_sync), np.mean(per, 0), rtol=1e-5)

    # broadcast_params makes shards identical to shard 0's value
    p = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)
    out = shard_map(
        ddp.broadcast_params, mesh=mesh, in_specs=P("data", None),
        out_specs=P("data", None),
    )(p)
    np.testing.assert_allclose(np.asarray(out).ravel(), 0.0)


@pytest.mark.parametrize("channel_last", [True, False])
def test_syncbn_matches_global_bn(channel_last):
    """Stats over 8 shards must equal single-device stats over the full batch
    (reference tests/distributed/synced_batchnorm/)."""
    mesh = _mesh()
    key = jax.random.PRNGKey(1)
    n, h, w, c = 16, 4, 4, 6
    x = jax.random.normal(key, (n, h, w, c), jnp.float32) * 3 + 1
    if not channel_last:
        x = jnp.transpose(x, (0, 3, 1, 2))
    weight = jnp.linspace(0.5, 1.5, c)
    bias = jnp.linspace(-1, 1, c)
    rm, rv = jnp.zeros((c,)), jnp.ones((c,))

    def local(xs):
        return sync_batch_norm(
            xs, weight, bias, rm, rv, training=True, axis_name="data",
            channel_last=channel_last,
        )

    y, new_rm, new_rv = shard_map(
        local, mesh=mesh, in_specs=P("data"),
        out_specs=(P("data"), P(), P()),
    )(x)

    y_ref, rm_ref, rv_ref = sync_batch_norm(
        x, weight, bias, rm, rv, training=True, axis_name=None,
        channel_last=channel_last,
    )
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(new_rm), np.asarray(rm_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(new_rv), np.asarray(rv_ref), atol=1e-4)


def test_syncbn_eval_and_fuse_relu():
    c = 3
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 5, c))
    rm = jnp.array([0.1, -0.2, 0.3])
    rv = jnp.array([1.0, 2.0, 0.5])
    y, rm2, rv2 = sync_batch_norm(
        x, None, None, rm, rv, training=False, axis_name=None, fuse_relu=True
    )
    ref = (x - rm) / np.sqrt(np.asarray(rv) + 1e-5)
    np.testing.assert_allclose(np.asarray(y), np.maximum(np.asarray(ref), 0), atol=1e-5)
    assert rm2 is rm and rv2 is rv


def test_syncbn_flax_module():
    import flax.linen as nn  # noqa: F401
    from apex_tpu.parallel import SyncBatchNorm

    m = SyncBatchNorm(num_features=4, axis_name=None)
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 4))
    vars0 = m.init(jax.random.PRNGKey(0), x)
    y, mutated = m.apply(vars0, x, mutable=["batch_stats"])
    assert y.shape == x.shape
    # batch-normalised output: near zero mean / unit var per channel
    np.testing.assert_allclose(np.asarray(y).mean(0), 0.0, atol=1e-5)
    assert not np.allclose(
        np.asarray(mutated["batch_stats"]["mean"]), 0.0
    )


def test_larc_clip_scales_small_grads():
    params = {"w": jnp.ones((10,)) * 2.0}
    grads = {"w": jnp.full((10,), 1e-4)}
    lr = 0.1
    out = larc_adjust_gradients(
        grads, params, lr, trust_coefficient=0.02, clip=True
    )
    # adaptive_lr = 0.02*||p||/||g|| >> lr → clip to 1 → unchanged
    np.testing.assert_allclose(np.asarray(out["w"]), 1e-4, rtol=1e-6)

    big = {"w": jnp.full((10,), 100.0)}
    out2 = larc_adjust_gradients(big, params, lr, trust_coefficient=0.02, clip=True)
    p_norm = np.linalg.norm(np.asarray(params["w"]))
    g_norm = np.linalg.norm(np.asarray(big["w"]))
    adaptive = 0.02 * p_norm / (g_norm + 1e-8)
    np.testing.assert_allclose(
        np.asarray(out2["w"]), 100.0 * adaptive / lr, rtol=1e-5
    )


def test_larc_no_clip_uses_adaptive_lr_directly():
    # clip=False: grads scaled by adaptive_lr itself (effective lr =
    # lr * adaptive_lr), matching reference apex/parallel/LARC.py:97-99.
    params = {"w": jnp.full((10,), 2.0)}
    grads = {"w": jnp.full((10,), 100.0)}
    out = larc_adjust_gradients(
        grads, params, lr=0.1, trust_coefficient=0.02, clip=False
    )
    p_norm = np.linalg.norm(np.asarray(params["w"]))
    g_norm = np.linalg.norm(np.asarray(grads["w"]))
    adaptive = 0.02 * p_norm / (g_norm + 1e-8)
    np.testing.assert_allclose(np.asarray(out["w"]), 100.0 * adaptive, rtol=1e-5)


def test_larc_zero_grad_left_untouched():
    # zero-norm branch leaves grads alone — no weight-decay fold
    # (reference LARC.py:84 guards the whole adjustment).
    params = {"w": jnp.full((4,), 3.0)}
    grads = {"w": jnp.zeros((4,))}
    out = larc_adjust_gradients(
        grads, params, lr=0.1, trust_coefficient=0.02, clip=True,
        weight_decay=0.1,
    )
    np.testing.assert_array_equal(np.asarray(out["w"]), 0.0)


def test_convert_syncbn_model():
    import flax.linen as nn
    from apex_tpu.parallel import SyncBatchNorm, convert_syncbn_model

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = nn.Dense(8)(x)
            x = nn.BatchNorm(use_running_average=not train)(x)
            return x

    class Outer(nn.Module):
        body: nn.Module

        @nn.compact
        def __call__(self, x):
            return self.body(x)

    converted = convert_syncbn_model(Outer(body=Net()), axis_name=None)
    assert isinstance(converted.body, nn.Module)
    # a bare BatchNorm converts to SyncBatchNorm and initialises fine
    bn = convert_syncbn_model(nn.BatchNorm(use_running_average=False), axis_name=None)
    assert isinstance(bn, SyncBatchNorm)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 5))
    variables = bn.init(jax.random.PRNGKey(1), x)
    assert variables["params"]["scale"].shape == (5,)
    y, _ = bn.apply(variables, x, mutable=["batch_stats"])
    assert y.shape == x.shape


def test_larc_wrapper_steps():
    opt = LARC(FusedSGD(lr=0.1, momentum=0.9), trust_coefficient=0.02)
    params = {"w": jnp.ones((4,), jnp.float32)}
    state = opt.init(params)
    grads = {"w": jnp.full((4,), 0.5)}
    new_params, state = opt.step(grads, state, params)
    assert not np.allclose(np.asarray(new_params["w"]), 1.0)
    # momentum state advanced
    new_params2, _ = opt.step(grads, state, new_params)
    assert not np.allclose(np.asarray(new_params2["w"]), np.asarray(new_params["w"]))
