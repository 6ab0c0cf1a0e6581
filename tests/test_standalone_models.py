"""Tests for the standalone GPT/BERT models.

Key check (reference idiom from ``test_pipeline_parallel_fwd_bwd.py`` and
the GPT/BERT minimal tests): the TP=8 sharded forward/loss must equal the
dense single-device computation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing import (
    DistributedTestBase,
    GPTConfig,
    LayerKind,
    bert_model_provider,
    gpt_loss,
    gpt_model_provider,
    gpt_partition_specs,
    init_gpt_params,
    set_random_seed,
)

TP = 8


def _small_cfg(**kw):
    defaults = dict(
        num_layers=2,
        hidden_size=32,
        num_attention_heads=8,
        vocab_size=128,
        max_position_embeddings=32,
        hidden_dropout=0.0,
        attention_dropout=0.0,
        tensor_model_parallel_size=1,
    )
    defaults.update(kw)
    return GPTConfig(**defaults)


def test_gpt_forward_shapes_and_loss():
    cfg = _small_cfg()
    key = set_random_seed(1234)
    params, fwd, loss = gpt_model_provider(cfg, key)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 128)
    logits = fwd(params, tokens)
    assert logits.shape == (2, 16, 128)
    labels = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128)
    l = loss(params, tokens, labels)
    assert np.isfinite(float(l)) and float(l) > 0


def test_gpt_tp_matches_dense():
    cfg_dense = _small_cfg()
    cfg_tp = _small_cfg(tensor_model_parallel_size=TP)
    parallel_state.initialize_model_parallel(tensor_model_parallel_size_=TP)
    mesh = parallel_state.get_mesh()
    key = jax.random.PRNGKey(7)
    params = init_gpt_params(cfg_dense, key)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 128)
    labels = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, 128)

    dense_loss = gpt_loss(cfg_dense, params, tokens, labels)
    dense_grads = jax.grad(
        lambda p: gpt_loss(cfg_dense, p, tokens, labels)
    )(params)

    specs = gpt_partition_specs(cfg_tp)

    def local_loss(p, t, lab):
        return gpt_loss(cfg_tp, p, t, lab, axis_name="tensor")

    tp_loss = jax.shard_map(
        local_loss, mesh=mesh, in_specs=(specs, P(), P()), out_specs=P(),
        check_vma=True,
    )(params, tokens, labels)
    np.testing.assert_allclose(float(tp_loss), float(dense_loss), rtol=2e-4)

    # gradients of the sharded model match the dense ones (shard-for-shard)
    tp_grads = jax.shard_map(
        jax.grad(local_loss), mesh=mesh,
        in_specs=(specs, P(), P()), out_specs=specs, check_vma=True,
    )(params, tokens, labels)
    for name in ("qkv_w", "fc2_w", "input_ln_w"):
        np.testing.assert_allclose(
            np.asarray(tp_grads["layers"][name]),
            np.asarray(dense_grads["layers"][name]),
            atol=5e-4, err_msg=name,
        )
    np.testing.assert_allclose(
        np.asarray(tp_grads["embedding"]["word"]),
        np.asarray(dense_grads["embedding"]["word"]),
        atol=5e-4,
    )
    parallel_state.destroy_model_parallel()


def test_gpt_recompute_matches_plain():
    cfg = _small_cfg()
    cfg_r = _small_cfg(recompute_granularity="full")
    params = init_gpt_params(cfg, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 8), 0, 128)
    labels = jax.random.randint(jax.random.PRNGKey(7), (2, 8), 0, 128)
    l1 = gpt_loss(cfg, params, tokens, labels)
    l2 = gpt_loss(cfg_r, params, tokens, labels)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    g1 = jax.grad(lambda p: gpt_loss(cfg, p, tokens, labels))(params)
    g2 = jax.grad(lambda p: gpt_loss(cfg_r, p, tokens, labels))(params)
    np.testing.assert_allclose(
        np.asarray(g1["layers"]["qkv_w"]), np.asarray(g2["layers"]["qkv_w"]),
        atol=1e-6,
    )


@pytest.mark.parametrize("masked", [False, True], ids=["mean", "loss_mask"])
def test_gpt_loss_matches_the_replaying_ce(masked):
    """``gpt_loss`` (the mean handed to the chunked CE as row weights, the
    head's gradient from its forward loop) against the per-row chunked CE
    that replays the head GEMM in backward, then the mean or the masked
    mean: equal loss and gradients at float32."""
    from apex_tpu.contrib.xentropy import lm_head_cross_entropy
    from apex_tpu.transformer.testing import standalone_transformer_lm as lm

    cfg = _small_cfg()
    params = init_gpt_params(cfg, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 8), 0, 128)
    labels = jax.random.randint(jax.random.PRNGKey(7), (2, 8), 0, 128)
    mask = (jax.random.uniform(jax.random.PRNGKey(8), (2, 8)) > 0.3
            if masked else None)

    def replaying(p):
        hidden = lm.gpt_hidden(cfg, p, tokens, None, None, True)
        s, b, h = hidden.shape
        losses = lm_head_cross_entropy(
            hidden.reshape(s * b, h), lm._head_weight(cfg, p),
            labels.T.reshape(s * b), chunk_size=s * b).reshape(s, b).T
        if mask is None:
            return jnp.mean(losses)
        m = mask.astype(jnp.float32)
        return jnp.sum(losses * m) / jnp.maximum(jnp.sum(m), 1.0)

    with jax.default_matmul_precision("highest"):
        l1, g1 = jax.value_and_grad(
            lambda p: gpt_loss(cfg, p, tokens, labels, loss_mask=mask))(params)
        l2, g2 = jax.value_and_grad(replaying)(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g1),
                            jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))


def test_gpt_cpu_offload_matches():
    cfg = _small_cfg()
    params, fwd, loss = gpt_model_provider(
        cfg, jax.random.PRNGKey(8), cpu_offload=True
    )
    params2, fwd2, loss2 = gpt_model_provider(cfg, jax.random.PRNGKey(8))
    tokens = jax.random.randint(jax.random.PRNGKey(9), (1, 8), 0, 128)
    labels = jax.random.randint(jax.random.PRNGKey(10), (1, 8), 0, 128)
    np.testing.assert_allclose(
        float(loss(params, tokens, labels)),
        float(loss2(params2, tokens, labels)),
        rtol=1e-6,
    )


def test_gpt_dropout_determinism():
    cfg = _small_cfg(hidden_dropout=0.1, attention_dropout=0.1)
    params = init_gpt_params(cfg, jax.random.PRNGKey(11))
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 8), 0, 128)
    labels = jnp.zeros_like(tokens)
    k = jax.random.PRNGKey(13)
    l1 = gpt_loss(cfg, params, tokens, labels, dropout_key=k, deterministic=False)
    l2 = gpt_loss(cfg, params, tokens, labels, dropout_key=k, deterministic=False)
    l3 = gpt_loss(
        cfg, params, tokens, labels, dropout_key=jax.random.PRNGKey(99),
        deterministic=False,
    )
    assert float(l1) == float(l2)  # same key -> identical
    assert float(l1) != float(l3)  # different key -> different dropout


def test_bert_forward_and_loss():
    cfg = _small_cfg(add_binary_head=True)
    params, fwd, loss_fn = bert_model_provider(cfg, jax.random.PRNGKey(14))
    tokens = jax.random.randint(jax.random.PRNGKey(15), (2, 12), 0, 128)
    padding = jnp.concatenate(
        [jnp.ones((2, 8), jnp.int32), jnp.zeros((2, 4), jnp.int32)], axis=1
    )
    lm_logits, bin_logits = fwd(params, tokens, padding)
    assert lm_logits.shape == (2, 12, 128)
    assert bin_logits.shape == (2, 2)

    labels = jax.random.randint(jax.random.PRNGKey(16), (2, 12), 0, 128)
    loss_mask = padding
    l = loss_fn(
        params, tokens, labels, loss_mask,
        padding_mask=padding, binary_labels=jnp.array([0, 1]),
    )
    assert np.isfinite(float(l))

    # padding tokens must not influence unpadded positions' logits
    tokens2 = tokens.at[:, 8:].set(7)  # change padded region
    lm_logits2, _ = fwd(params, tokens2, padding)
    np.testing.assert_allclose(
        np.asarray(lm_logits[:, :8]), np.asarray(lm_logits2[:, :8]), atol=1e-5
    )


def test_distributed_test_base():
    class MyTest(DistributedTestBase):
        MAX_WORLD_SIZE = 4

        def test_world(self):
            assert self.world_size == 4
            mesh = self.initialize_model_parallel(tp=2, pp=2)
            assert parallel_state.get_tensor_model_parallel_world_size() == 2
            assert parallel_state.get_pipeline_model_parallel_world_size() == 2

    import unittest

    suite = unittest.TestLoader().loadTestsFromTestCase(MyTest)
    result = unittest.TextTestRunner(verbosity=0).run(suite)
    assert result.wasSuccessful()
    assert not parallel_state.model_parallel_is_initialized()


def test_arguments_parse_and_validate():
    from apex_tpu.transformer.testing.arguments import parse_args

    args = parse_args(args=[
        "--num-layers", "4", "--hidden-size", "64",
        "--num-attention-heads", "4", "--seq-length", "32",
        "--max-position-embeddings", "32",
        "--micro-batch-size", "2", "--global-batch-size", "16",
        "--tensor-model-parallel-size", "2", "--bf16",
        "--world-size", "8",
    ])
    assert args.data_parallel_size == 4
    assert args.params_dtype == "bfloat16"
    assert args.ffn_hidden_size == 256
    assert args.kv_channels == 16

    with pytest.raises(ValueError):
        parse_args(args=["--hidden-size", "64", "--num-attention-heads", "4",
                         "--fp16", "--bf16", "--world-size", "8"])
    with pytest.raises(ValueError):
        parse_args(args=[
            "--hidden-size", "64", "--num-attention-heads", "4",
            "--tensor-model-parallel-size", "3", "--world-size", "8",
        ])


def test_global_vars_lifecycle():
    from apex_tpu.transformer.testing import global_vars as gv

    gv.destroy_global_vars()
    args = gv.set_global_variables(override_args=[
        "--hidden-size", "64", "--num-attention-heads", "4",
        "--micro-batch-size", "2", "--global-batch-size", "8",
        "--world-size", "2",
    ])
    assert gv.get_args() is args
    assert gv.get_num_microbatches() == 2  # 8 / (mbs 2 * dp 2)
    timers = gv.get_timers()
    timers("step").start()
    timers("step").stop()
    assert timers("step").elapsed() >= 0
    gv.destroy_global_vars()


def test_selective_policy_saves_named_pallas_outputs():
    """The flash-aware selective remat policy matches pallas kernels by
    their pallas_call `name` param — a JAX upgrade that renames that param
    would silently degrade selective remat back to replaying every flash
    forward. Pin that the named kernel outputs appear in saved residuals."""
    from jax._src.ad_checkpoint import saved_residuals

    from apex_tpu.ops.flash_attention import flash_attention
    from apex_tpu.transformer.testing.standalone_transformer_lm import (
        _selective_policy,
    )

    def body(q, k, v):
        o = flash_attention(
            q, k, v, causal=True, interpret=True, block_q=16, block_k=16
        )
        return jnp.sum(o.astype(jnp.float32) ** 2)

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 32, 16), jnp.float32)
    fn = jax.checkpoint(body, policy=_selective_policy)
    res = saved_residuals(fn, q, q, q)
    # the flash fwd kernel outputs must be saved, not rematted
    pallas_saved = [d for _, d in res if "output of pallas_call" in str(d)]
    assert pallas_saved, [str(d) for _, d in res]


# ---------------------------------------------------------------------------
# the scanned block and the block by kind agree where they overlap
# ---------------------------------------------------------------------------
_OVERLAP = dict(num_layers=3, hidden_size=32, num_attention_heads=4,
                vocab_size=96, max_position_embeddings=16,
                hidden_dropout=0.0, attention_dropout=0.0)


def _by_kind_layout(cfg, scanned):
    """The scanned block's stacked parameters in the ``layer_kinds``
    layout: every leaf unstacked into its layer's dict, and ``qkv_w [L, 3h,
    h]`` (rows laid out per head as ``[q | k | v]``) split into ``q_w``,
    ``k_w``, ``v_w`` (their biases likewise)."""
    n, d = cfg.num_attention_heads, cfg.kv_channels
    stacked = dict(scanned["layers"])
    w = stacked.pop("qkv_w").reshape(cfg.num_layers, n, 3, d, -1)
    b = stacked.pop("qkv_b").reshape(cfg.num_layers, n, 3, d)
    for i, name in enumerate("qkv"):
        stacked[f"{name}_w"] = w[:, :, i].reshape(
            cfg.num_layers, n * d, -1)
        stacked[f"{name}_b"] = b[:, :, i].reshape(cfg.num_layers, n * d)
    return {**scanned, "layers": [
        {k: v[l] for k, v in stacked.items()}
        for l in range(cfg.num_layers)]}


def _scanned_layout(cfg, by_kind):
    """``_by_kind_layout`` the other way round (for the gradients)."""
    n, d = cfg.num_attention_heads, cfg.kv_channels
    stacked = {k: jnp.stack([lp[k] for lp in by_kind["layers"]])
               for k in by_kind["layers"][0]}
    w = [stacked.pop(f"{name}_w").reshape(cfg.num_layers, n, d, -1)
         for name in "qkv"]
    b = [stacked.pop(f"{name}_b").reshape(cfg.num_layers, n, d)
         for name in "qkv"]
    stacked["qkv_w"] = jnp.stack(w, axis=2).reshape(
        cfg.num_layers, 3 * n * d, -1)
    stacked["qkv_b"] = jnp.stack(b, axis=2).reshape(
        cfg.num_layers, 3 * n * d)
    return {**by_kind, "layers": stacked}


@pytest.mark.parametrize("unroll", [1, -1])
@pytest.mark.parametrize("recompute", [None, "full", "selective"])
def test_scanned_block_equals_block_by_kind_where_they_overlap(
        recompute, unroll):
    """The net under ROADMAP D1 (one model definition): plain
    full-attention layers with LayerNorm, biases, a GeLU MLP, learned
    positions and a tied head through ``layer_by_kind`` give the scanned
    ``transformer_layer``'s loss and gradients on the same weights, in
    float32 without dropout, under every recompute policy both take."""
    scanned_cfg = GPTConfig(**_OVERLAP, recompute_granularity=recompute,
                            layer_unroll=unroll)
    kind_cfg = GPTConfig(
        **_OVERLAP, recompute_granularity=recompute, layer_unroll=unroll,
        layer_kinds=(LayerKind(None, False, False),) * 3, norm="layernorm",
        linear_bias=True, gated_mlp=False, learned_positions=True,
        untied_head=False)
    params = init_gpt_params(scanned_cfg, jax.random.PRNGKey(3))
    # biases and gains away from their 0 / 1 start, so that each is felt
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0, 96)
    labels = jnp.roll(tokens, -1, axis=1)

    loss_s, grads_s = jax.value_and_grad(
        lambda p: gpt_loss(scanned_cfg, p, tokens, labels))(params)
    loss_k, grads_k = jax.value_and_grad(
        lambda p: gpt_loss(kind_cfg, p, tokens, labels))(
            _by_kind_layout(scanned_cfg, params))
    grads_k = _scanned_layout(scanned_cfg, grads_k)

    np.testing.assert_allclose(float(loss_k), float(loss_s), rtol=1e-6)
    assert (jax.tree_util.tree_structure(grads_k)
            == jax.tree_util.tree_structure(grads_s))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads_k),
                            jax.tree_util.tree_leaves(grads_s)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6,
            err_msg=jax.tree_util.keystr(path))


# ---------------------------------------------------------------------------
# the dense block's attention runs batch-major inside: the flash kernels read
# q, k and v where the projection wrote them
# ---------------------------------------------------------------------------
def _attention_case(heads, padding):
    from apex_tpu.transformer.enums import AttnMaskType
    from apex_tpu.transformer.testing import standalone_transformer_lm as lm

    cfg = _small_cfg(
        num_layers=1, hidden_size=64 * heads, num_attention_heads=heads,
        use_flash_attention=True,
        attn_mask_type=(AttnMaskType.padding if padding
                        else AttnMaskType.causal))
    s, b = 32, 2
    lp = jax.tree_util.tree_map(
        lambda x: x[0], init_gpt_params(cfg, jax.random.PRNGKey(0))["layers"])
    # biases start at zero: give every parameter of the block a gradient
    lp = {k: v + 0.02 * jax.random.normal(jax.random.PRNGKey(i), v.shape)
          for i, (k, v) in enumerate(sorted(lp.items()))}
    hidden = jax.random.normal(jax.random.PRNGKey(1),
                               (s, b, cfg.hidden_size), jnp.float32)
    mask = None
    if padding:    # nonzero = masked out, [b, 1, 1, s]
        mask = (jnp.arange(s)[None, :] >= jnp.array([s, 20])[:, None]
                )[:, None, None, :].astype(jnp.int32)
    return lm, cfg, lp, hidden, mask


def _split_path_attention(cfg, lp, hidden, mask):
    """``parallel_attention`` as it was before the kernels read the
    projection's output: one fused GEMM in Megatron's row order, its
    ``[s, b, heads, 3 x hn]`` split and every part transposed to the
    head-major kernels, the context transposed back."""
    from apex_tpu.ops.flash_attention import flash_attention

    s, b, h = hidden.shape
    n, hn = cfg.num_attention_heads, cfg.kv_channels
    qkv = jnp.einsum("sbh,oh->sbo", hidden, lp["qkv_w"]) + lp["qkv_b"]
    q, k, v = (jnp.transpose(x, (1, 2, 0, 3))
               for x in jnp.split(qkv.reshape(s, b, n, 3 * hn), 3, axis=-1))
    ctx = flash_attention(
        q, k, v, causal=mask is None,
        kv_mask=None if mask is None else mask[:, 0, 0, :] == 0,
        scale=1.0 / hn ** 0.5)
    ctx = jnp.transpose(ctx, (2, 0, 1, 3)).reshape(s, b, h)
    return jnp.einsum("sbo,ho->sbh", ctx, lp["proj_w"]) + lp["proj_b"]


@pytest.mark.parametrize("heads,padding", [(4, False), (4, True), (3, False)])
def test_parallel_attention_equals_split_path(heads, padding):
    """Output and parameter gradients of the batch-major path (one GEMM over
    the rows of ``qkv_w``, Megatron's ``[head, (q, k, v), hn]``, reordered
    in the weight; q, k, v three views of what it writes) to the
    split-and-transpose path's, to float32 rounding; three heads of 64 do
    not pair and reach the head-major kernels."""
    lm, cfg, lp, hidden, mask = _attention_case(heads, padding)
    w = jax.random.normal(jax.random.PRNGKey(2), hidden.shape)

    def new(lp, hidden):
        return jnp.sum(w * lm.parallel_attention(
            cfg, lp, hidden, mask, None, None, True))

    def old(lp, hidden):
        return jnp.sum(w * _split_path_attention(cfg, lp, hidden, mask))

    got, g_got = jax.value_and_grad(new, (0, 1))(lp, hidden)
    want, g_want = jax.value_and_grad(old, (0, 1))(lp, hidden)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name in ("qkv_w", "qkv_b", "proj_w", "proj_b"):
        np.testing.assert_allclose(g_got[0][name], g_want[0][name],
                                   rtol=1e-4, atol=1e-5, err_msg=name)
    np.testing.assert_allclose(g_got[1], g_want[1], rtol=1e-4, atol=1e-5)


def test_no_transpose_between_qkv_gemm_and_flash_kernel():
    """The dense layer's trace: from each of q, k, v of the
    ``apex_tpu_flash_fwd`` call back to the GEMM that wrote it, and from
    the kernel's context on to the GEMM that reads it, nothing moves an
    activation: reshapes and the bias add only."""
    from jax.extend.core import Var

    lm, cfg, lp, hidden, _ = _attention_case(4, False)
    jaxpr = jax.make_jaxpr(lambda lp, x: lm.transformer_layer(
        cfg, lp, x, None, None, None, True))(lp, hidden).jaxpr

    producer, consumers, alias = {}, {}, {}

    def resolve(v):
        while isinstance(v, Var) and v in alias:
            v = alias[v]
        return v

    def walk(jp):   # sub-jaxprs inlined; a kernel's own body left alone
        for eqn in jp.eqns:
            subs = [getattr(p, "jaxpr", p) for p in eqn.params.values()
                    if hasattr(getattr(p, "jaxpr", p), "eqns")]
            if eqn.primitive.name != "pallas_call" and subs:
                sub = subs[0]
                for inner, outer in zip(sub.invars, eqn.invars):
                    alias[inner] = outer
                walk(sub)
                for outer, inner in zip(eqn.outvars, sub.outvars):
                    alias[outer] = inner
                continue
            ins = [resolve(v) for v in eqn.invars]
            for v in eqn.outvars:
                producer[v] = (eqn, ins)
            for v in ins:
                if isinstance(v, Var):
                    consumers.setdefault(v, []).append(eqn)

    walk(jaxpr)
    kernel, ins = next(
        (e, i) for e, i in producer.values()
        if e.primitive.name == "pallas_call"
        and e.params["name"] == "apex_tpu_flash_fwd")
    still = {"reshape", "add", "convert_element_type", "name"}
    for v in ins[:3]:                       # q, k, v back to their GEMMs
        seen = []
        while True:
            eqn, srcs = producer[resolve(v)]
            seen.append(eqn.primitive.name)
            if eqn.primitive.name == "dot_general":
                break
            assert eqn.primitive.name in still, seen
            v = max(srcs, key=lambda x: getattr(x.aval, "size", 0))
        assert seen[-1] == "dot_general" and "transpose" not in seen
    v, seen = kernel.outvars[0], []         # the context on to its GEMM
    while True:
        eqn = consumers[v][0]
        seen.append(eqn.primitive.name)
        if eqn.primitive.name == "dot_general":
            break
        assert eqn.primitive.name in still, seen
        v = eqn.outvars[0]
    assert "transpose" not in seen
