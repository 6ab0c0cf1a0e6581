"""Flash attention kernel vs materialised-score reference (fwd + grads),
plus GPT/BERT model equivalence between the flash and XLA attention paths.

Mirrors the reference's contrib test style (``apex/contrib/test/fmha/``,
``apex/contrib/test/multihead_attn/``): kernel-vs-reference tolerance
asserts including backward.
"""
import dataclasses

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from apex_tpu.ops.flash_attention import (
    flash_attention,
    mha_reference,
)


def _qkv(key, b=2, n=2, s=64, d=32, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    mk = lambda k: jax.random.normal(k, (b, n, s, d), dtype)
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    o = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = mha_reference(q, k, v, causal=causal)
    assert jnp.abs(o - ref).max() < 2e-5


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(1))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    gf = jax.grad(
        loss(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=16, block_k=16
        )),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        loss(lambda q, k, v: mha_reference(q, k, v, causal=causal)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gf, gr):
        assert jnp.abs(a - b).max() < 5e-4


def test_flash_key_padding_mask():
    key = jax.random.PRNGKey(2)
    q, k, v = _qkv(key)
    mask = jax.random.bernoulli(jax.random.fold_in(key, 9), 0.75, (2, 64))
    o = flash_attention(q, k, v, kv_mask=mask, block_q=16, block_k=16)
    ref = mha_reference(q, k, v, kv_mask=mask)
    assert jnp.abs(o - ref).max() < 2e-5
    gf = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, kv_mask=mask, block_q=16, block_k=16)
            ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda q, k, v: jnp.sum(mha_reference(q, k, v, kv_mask=mask) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gf, gr):
        assert jnp.abs(a - b).max() < 5e-4


def test_flash_uneven_blocks():
    # seq not a multiple of the requested block: block shrinks to divide
    q, k, v = _qkv(jax.random.PRNGKey(3), s=48)
    o = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = mha_reference(q, k, v, causal=True)
    assert jnp.abs(o - ref).max() < 2e-5


def test_flash_rectangular_qk():
    key = jax.random.PRNGKey(4)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, 2, 32, 32))
    k = jax.random.normal(ks[1], (2, 2, 64, 32))
    v = jax.random.normal(ks[2], (2, 2, 64, 32))
    o = flash_attention(q, k, v, block_q=16, block_k=16)
    ref = mha_reference(q, k, v)
    assert jnp.abs(o - ref).max() < 2e-5


def test_gpt_flash_matches_xla_path():
    """Model-level: forward+grads identical between flash and XLA scores."""
    from apex_tpu.transformer.testing import (
        GPTConfig,
        gpt_loss,
        init_gpt_params,
    )

    base = GPTConfig(
        num_layers=2,
        hidden_size=64,
        num_attention_heads=2,
        vocab_size=128,
        max_position_embeddings=32,
        hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    params = init_gpt_params(base, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
    labels = jnp.roll(tokens, -1, axis=1)

    def run(use_flash):
        cfg = dataclasses.replace(base, use_flash_attention=use_flash)
        return jax.value_and_grad(
            lambda p: gpt_loss(cfg, p, tokens, labels)
        )(params)

    loss_f, grads_f = run(True)
    loss_x, grads_x = run(False)
    assert jnp.abs(loss_f - loss_x) < 1e-5
    flat_f = jax.tree_util.tree_leaves(grads_f)
    flat_x = jax.tree_util.tree_leaves(grads_x)
    for a, b in zip(flat_f, flat_x):
        assert jnp.abs(a - b).max() < 1e-4


def test_bert_flash_matches_xla_path():
    """BERT padding-mask path: flash consumes the [b,1,1,s] key-padding
    mask; results match the materialised-mask XLA path."""
    from apex_tpu.transformer.testing import GPTConfig
    from apex_tpu.transformer.testing.standalone_transformer_lm import (
        bert_forward,
        init_gpt_params,
    )

    base = GPTConfig(
        num_layers=2,
        hidden_size=64,
        num_attention_heads=2,
        vocab_size=128,
        max_position_embeddings=32,
        hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    params = init_gpt_params(base, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
    padding = jnp.concatenate(
        [jnp.ones((2, 24), jnp.int32), jnp.zeros((2, 8), jnp.int32)], axis=1
    )

    def run(use_flash):
        cfg = dataclasses.replace(base, use_flash_attention=use_flash)
        logits, _ = bert_forward(cfg, params, tokens, padding)
        return logits

    lf = run(True)
    lx = run(False)
    # compare only non-padded query positions (padded queries attend to
    # everything in both paths but their logits are irrelevant)
    assert jnp.abs(lf[:, :24] - lx[:, :24]).max() < 1e-4


# ---------------------------------------------------------------------- new
# in-kernel dropout + varlen (cu_seqlens)


def test_flash_dropout_matches_reference_mask():
    """The kernel's hash dropout must equal mha_reference's materialised
    mask elementwise (same counters), at any block size."""
    from apex_tpu.ops.flash_attention import mha_reference

    q, k, v = _qkv(jax.random.PRNGKey(0), s=64)
    for blocks in ((512, 512), (16, 32)):
        out = flash_attention(
            q, k, v, dropout_p=0.3, dropout_seed=123,
            block_q=blocks[0], block_k=blocks[1],
        )
        ref = mha_reference(q, k, v, dropout_p=0.3, dropout_seed=123)
        assert jnp.abs(out - ref).max() < 2e-5, blocks


def test_flash_dropout_zero_p_equals_no_dropout():
    q, k, v = _qkv(jax.random.PRNGKey(1))
    a = flash_attention(q, k, v)
    b = flash_attention(q, k, v, dropout_p=0.0, dropout_seed=7)
    assert jnp.array_equal(a, b)


def test_flash_dropout_requires_seed():
    q, k, v = _qkv(jax.random.PRNGKey(2))
    with pytest.raises(ValueError, match="dropout_seed"):
        flash_attention(q, k, v, dropout_p=0.1)


def test_flash_dropout_rate_and_seed_dependence():
    from apex_tpu.ops.flash_attention import dropout_mask_reference

    m1 = dropout_mask_reference(11, 1, 2, 128, 128, 0.25)
    m2 = dropout_mask_reference(12, 1, 2, 128, 128, 0.25)
    rate = 1.0 - float(m1.mean())
    assert abs(rate - 0.25) < 0.02
    assert not jnp.array_equal(m1, m2)  # seed changes the mask
    # heads get distinct masks
    assert not jnp.array_equal(m1[0, 0], m1[0, 1])


def test_flash_dropout_grads_match_reference():
    """Backward regenerates the identical mask: grads must equal autodiff
    through the materialised-mask reference."""
    from apex_tpu.ops.flash_attention import mha_reference

    q, k, v = _qkv(jax.random.PRNGKey(3), s=32)

    def f_flash(q, k, v):
        return (flash_attention(
            q, k, v, causal=True, dropout_p=0.2, dropout_seed=99,
        ) ** 2).sum()

    def f_ref(q, k, v):
        return (mha_reference(
            q, k, v, causal=True, dropout_p=0.2, dropout_seed=99,
        ) ** 2).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        assert jnp.abs(gf - gr).max() < 5e-4, name


def _packed(key, lens, n=2, d=16, pad_to=None):
    total = sum(lens)
    if pad_to:
        total = pad_to
    cu = jnp.asarray(np_cumsum0(lens), jnp.int32)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (total, n, d), jnp.float32)
    k = jax.random.normal(kk, (total, n, d), jnp.float32)
    v = jax.random.normal(kv, (total, n, d), jnp.float32)
    return q, k, v, cu


def np_cumsum0(lens):
    import numpy as np

    return np.concatenate([[0], np.cumsum(lens)])


@pytest.mark.parametrize("causal", [False, True])
def test_flash_varlen_matches_per_sequence_reference(causal):
    from apex_tpu.ops.flash_attention import (
        flash_attention_varlen,
        mha_reference,
    )
    import numpy as np

    lens = [24, 8, 32]  # total 64
    q, k, v, cu = _packed(jax.random.PRNGKey(4), lens)
    out = flash_attention_varlen(q, k, v, cu, causal=causal)

    # reference: run each sequence separately through dense attention
    for i, L in enumerate(lens):
        s, e = int(cu[i]), int(cu[i + 1])
        ref = mha_reference(
            q[s:e].transpose(1, 0, 2)[None],
            k[s:e].transpose(1, 0, 2)[None],
            v[s:e].transpose(1, 0, 2)[None],
            causal=causal,
        )[0].transpose(1, 0, 2)
        np.testing.assert_allclose(
            np.asarray(out[s:e]), np.asarray(ref), atol=2e-5,
            err_msg=f"sequence {i}",
        )


def test_flash_varlen_grads_match_reference():
    from apex_tpu.ops.flash_attention import (
        flash_attention_varlen,
        mha_reference_varlen,
    )
    import numpy as np

    lens = [16, 48]
    q, k, v, cu = _packed(jax.random.PRNGKey(5), lens)

    g_flash = jax.grad(
        lambda q, k, v: (flash_attention_varlen(q, k, v, cu, causal=True) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: (mha_reference_varlen(q, k, v, cu, causal=True) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, err_msg=name
        )


def test_flash_varlen_padding_tail_isolated():
    """Tokens past cu_seqlens[-1] form their own padding segment and must
    not influence real sequences."""
    from apex_tpu.ops.flash_attention import flash_attention_varlen
    import numpy as np

    lens = [24, 24]  # 48 real tokens, padded buffer of 64
    q, k, v, cu = _packed(jax.random.PRNGKey(6), lens, pad_to=64)
    out = flash_attention_varlen(q, k, v, cu)
    q2 = q.at[48:].set(1e3)  # poison the padding tokens
    k2 = k.at[48:].set(1e3)
    v2 = v.at[48:].set(1e3)
    out2 = flash_attention_varlen(q2, k2, v2, cu)
    np.testing.assert_allclose(
        np.asarray(out[:48]), np.asarray(out2[:48]), atol=1e-6
    )


def test_segment_ids_from_cu_seqlens():
    from apex_tpu.ops.flash_attention import segment_ids_from_cu_seqlens
    import numpy as np

    cu = jnp.asarray([0, 3, 3, 7], jnp.int32)  # empty middle sequence
    segs = segment_ids_from_cu_seqlens(cu, 9)
    np.testing.assert_array_equal(
        np.asarray(segs), [0, 0, 0, 2, 2, 2, 2, 3, 3]
    )


def test_gpt_flash_with_attention_dropout():
    """Attention dropout now runs in-kernel on the flash path: a forced-on
    flash config with attention_dropout > 0 must train (no raise), be
    deterministic per key, and vary across keys."""
    from apex_tpu.transformer.testing import GPTConfig, gpt_loss, init_gpt_params

    cfg = GPTConfig(
        num_layers=2, hidden_size=64, num_attention_heads=2, vocab_size=128,
        max_position_embeddings=32, hidden_dropout=0.0,
        attention_dropout=0.25, use_flash_attention=True,
    )
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
    labels = jnp.roll(tokens, -1, axis=1)

    k = jax.random.PRNGKey(5)
    l1 = gpt_loss(cfg, params, tokens, labels, dropout_key=k, deterministic=False)
    l2 = gpt_loss(cfg, params, tokens, labels, dropout_key=k, deterministic=False)
    l3 = gpt_loss(cfg, params, tokens, labels,
                  dropout_key=jax.random.PRNGKey(9), deterministic=False)
    ld = gpt_loss(cfg, params, tokens, labels, deterministic=True)
    assert float(l1) == float(l2)      # same key -> same in-kernel mask
    assert float(l1) != float(l3)      # key changes the mask
    assert float(l1) != float(ld)      # dropout actually active
    # grads flow through the dropped kernel
    g = jax.grad(lambda p: gpt_loss(cfg, p, tokens, labels, dropout_key=k,
                                    deterministic=False))(params)
    assert all(jnp.isfinite(x).all() for x in jax.tree_util.tree_leaves(g))


# ---------------------------------------------------------------------------
# additive logit bias (AlphaFold pair bias / ALiBi; reference openfold MHA's
# ``bias=`` argument, apex/contrib/openfold_triton/mha.py:133)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("bias_shape", [(2, 2, 64, 64), (1, 2, 64, 64),
                                        (2, 1, 64, 64), (1, 1, 64, 64),
                                        (2, 1, 1, 64), (1, 1, 1, 64)])
def test_flash_bias_forward_matches_reference(causal, bias_shape):
    key = jax.random.PRNGKey(11)
    q, k, v = _qkv(key)
    bias = jax.random.normal(jax.random.fold_in(key, 1), bias_shape) * 0.5
    o = flash_attention(q, k, v, bias=bias, causal=causal,
                        block_q=16, block_k=16)
    ref = mha_reference(q, k, v, bias=bias, causal=causal)
    assert jnp.abs(o - ref).max() < 2e-5


@pytest.mark.parametrize("bias_shape", [(2, 2, 64, 64), (1, 2, 64, 64),
                                        (2, 1, 1, 64)])
def test_flash_bias_grads_match_reference(bias_shape):
    """dq/dk/dv/dbias vs the materialised reference — incl. the broadcast
    reduction of dbias over a collapsed batch dim."""
    key = jax.random.PRNGKey(12)
    q, k, v = _qkv(key)
    bias = jax.random.normal(jax.random.fold_in(key, 2), bias_shape) * 0.5

    def loss(fn):
        return lambda q, k, v, bias: jnp.sum(fn(q, k, v, bias) ** 2)

    gf = jax.grad(
        loss(lambda q, k, v, b: flash_attention(
            q, k, v, bias=b, block_q=16, block_k=16)),
        argnums=(0, 1, 2, 3),
    )(q, k, v, bias)
    gr = jax.grad(
        loss(lambda q, k, v, b: mha_reference(q, k, v, bias=b)),
        argnums=(0, 1, 2, 3),
    )(q, k, v, bias)
    for a, b in zip(gf, gr):
        assert a.shape == b.shape
        assert jnp.abs(a - b).max() < 5e-4


def test_flash_bias_causal_grads_zero_above_diagonal():
    """Causal-skipped tiles must leave dbias zero-filled (the dq kernel
    writes the zero block before the masked compute)."""
    key = jax.random.PRNGKey(13)
    q, k, v = _qkv(key, s=64)
    bias = jax.random.normal(jax.random.fold_in(key, 3), (2, 2, 64, 64))
    db = jax.grad(
        lambda b: jnp.sum(flash_attention(
            q, k, v, bias=b, causal=True, block_q=16, block_k=16) ** 2)
    )(bias)
    qi = jnp.arange(64)[:, None]
    ki = jnp.arange(64)[None, :]
    above = jnp.broadcast_to(ki > qi, db.shape)
    assert jnp.abs(jnp.where(above, db, 0.0)).max() == 0.0


def test_flash_bias_with_dropout_matches_reference():
    key = jax.random.PRNGKey(14)
    q, k, v = _qkv(key)
    bias = jax.random.normal(jax.random.fold_in(key, 4), (1, 2, 64, 64)) * 0.3
    o = flash_attention(q, k, v, bias=bias, dropout_p=0.2, dropout_seed=21,
                        block_q=16, block_k=16)
    ref = mha_reference(q, k, v, bias=bias, dropout_p=0.2, dropout_seed=21)
    assert jnp.abs(o - ref).max() < 2e-5


def test_flash_bias_shape_validation():
    q, k, v = _qkv(jax.random.PRNGKey(15))
    with pytest.raises(ValueError, match="bias shape"):
        flash_attention(q, k, v, bias=jnp.zeros((3, 2, 64, 64)))
    with pytest.raises(ValueError, match="bias shape"):
        flash_attention(q, k, v, bias=jnp.zeros((2, 2, 32, 64)))


def test_lane_block_picks():
    """Mosaic lane-dim rule for mask/seg/bias blocks: %128 or whole dim
    (regression for varlen totals like 320 failing to lower on TPU)."""
    from apex_tpu.ops.flash_attention import _lane_block
    assert _lane_block(320, 64) == 320      # no %128 divisor -> whole dim
    assert _lane_block(384, 64) == 128      # closest %128 divisor
    assert _lane_block(1024, 512) == 512    # already legal
    assert _lane_block(1024, 1024) == 1024  # whole dim always legal
    assert _lane_block(72, 8) == 72         # small odd seq -> whole dim


@pytest.mark.parametrize("block_k,bias_grad", [
    (16, True),   # generic two-kernel backward (n_k=2)
    (32, True),   # n_k=1 but dbias emission keeps the two-kernel path
    (32, False),  # the fused single-k-block backward, bias streamed
])
def test_bias_folded_full_row_mask_returns_zeros(block_k, bias_grad):
    """A bias row folded to the library's own _NEG_INF (-1e30) fully masks
    that query row: the kernel must keep the zeros/-inf lse convention
    (guards stay active on the bias path), matching mha_reference — on
    the generic AND fused backward paths."""
    b, n, s, d = 1, 2, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (b, n, s, d), jnp.float32) for kk in ks)
    bias = jnp.zeros((1, 1, s, s), jnp.float32).at[:, :, 5, :].set(-1e30)
    out = flash_attention(q, k, v, bias=bias, block_q=16, block_k=block_k,
                          bias_grad=bias_grad)
    ref = mha_reference(q, k, v, bias=bias)
    assert jnp.abs(out[:, :, 5]).max() == 0.0
    assert jnp.abs(out - ref).max() < 2e-5
    # backward: the bwd-kernel guards must keep masked-row grads at exact
    # zero and everything finite (lse = -inf rows flow through exp)
    grads = jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, bias=bias, block_q=16, block_k=block_k,
            bias_grad=bias_grad) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for g in grads:
        assert jnp.all(jnp.isfinite(g))
    assert jnp.abs(grads[0][:, :, 5]).max() == 0.0  # dq of the masked row


@pytest.mark.parametrize("causal", [False, True])
def test_explicit_bwd_blocks_match_default(causal):
    """The bwd_block_q/bwd_block_k hooks (round-5: fwd and bwd tiles can
    diverge) must produce the same gradients as the default tiling —
    guards the custom-vjp nondiff-arg plumbing."""
    q, k, v = _qkv(jax.random.PRNGKey(9), s=64, d=32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g_def = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=32, block_k=32)),
        argnums=(0, 1, 2))(q, k, v)
    g_exp = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=32, block_k=32,
        bwd_block_q=16, bwd_block_k=16)), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_def, g_exp, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, err_msg=name)


# ---------------------------------------------------------------------------
# the banded path: a sliding window and grouped K/V heads
# ---------------------------------------------------------------------------
from apex_tpu.ops.flash_attention import band_tiles  # noqa: E402

BANDED = {
    # b, n, n_kv, s, d, window, block_q, block_k
    "window_and_groups": (2, 4, 2, 64, 16, 24, 16, 16),
    "one_kv_head": (1, 4, 1, 64, 16, 40, 16, 8),
    "window_with_all_heads": (1, 4, 4, 64, 16, 24, 32, 16),
    "window_as_long_as_the_sequence": (1, 4, 2, 64, 16, 64, 16, 16),
    "window_longer_than_the_sequence": (1, 2, 1, 32, 16, 100, 16, 16),
    "groups_without_a_window": (1, 4, 2, 64, 16, None, 16, 16),
    "sequence_no_multiple_of_the_window": (1, 8, 2, 48, 16, 20, 16, 16),
    "window_of_one": (1, 2, 2, 32, 16, 1, 16, 16),
}


@pytest.mark.parametrize("case", sorted(BANDED))
def test_banded_forward_and_backward_match_the_reference(case):
    b, n, n_kv, s, d, window, bq, bk = BANDED[case]
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, n, s, d))
    k = jax.random.normal(ks[1], (b, n_kv, s, d))
    v = jax.random.normal(ks[2], (b, n_kv, s, d))
    w = jax.random.normal(ks[3], (b, n, s, d))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=bq, block_k=bk, interpret=True)

    def ref(q, k, v):
        return mha_reference(q, k, v, causal=True, window=window)

    np.testing.assert_allclose(flash(q, k, v), ref(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(ref(*a) * w), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert g.shape == r.shape       # dk, dv summed over the group
        np.testing.assert_allclose(g, r, atol=5e-5)
    if window is not None and window >= s:
        np.testing.assert_allclose(
            flash(q, k, v), mha_reference(q, k, v, causal=True), atol=2e-5)


def _all_eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _all_eqns(inner)


def test_tiles_outside_the_band_cost_no_grid_step():
    """The banded kernels' grid is the list of tiles that touch the band:
    at the cell's shape a sliding layer walks 21 of the 36 causal tiles
    (64 in the square), and every listed tile holds a pair of the band."""
    s, blk, window = 8192, 1024, 2048
    causal, banded = band_tiles(s, blk, blk, None), band_tiles(
        s, blk, blk, window)
    assert len(causal) == 36 and len(banded) == 21
    for iq, ik in banded:
        i = np.arange(iq * blk, (iq + 1) * blk)[:, None]
        j = np.arange(ik * blk, (ik + 1) * blk)[None, :]
        assert np.any((j <= i) & (i - j < window))
    # the grid of a compiled call is the list's length: per head, forward
    jaxpr = jax.make_jaxpr(lambda q, k: flash_attention(
        q, k, k, causal=True, window=24, block_q=16, block_k=16,
        interpret=True))(jnp.zeros((1, 2, 64, 16)), jnp.zeros((1, 1, 64, 16)))
    grids = [e.params["grid_mapping"].grid
             for e in _all_eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    assert grids and grids[0] == (1, 2, len(band_tiles(64, 16, 16, 24)))


def test_the_banded_path_refuses_what_it_does_not_compute():
    q = jnp.zeros((1, 4, 32, 16))
    kv = jnp.zeros((1, 2, 32, 16))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, kv, kv, causal=False)
    with pytest.raises(ValueError, match="kv_mask, bias or dropout"):
        flash_attention(q, kv, kv, causal=True,
                        kv_mask=jnp.ones((1, 32), bool))
    with pytest.raises(ValueError, match="do not divide"):
        flash_attention(q, jnp.zeros((1, 3, 32, 16)),
                        jnp.zeros((1, 3, 32, 16)), causal=True)
    with pytest.raises(ValueError, match="at least 1"):
        flash_attention(q, q, q, causal=True, window=0)


# ---------------------------------------------------------------------------
# the batch-major layout: the kernels read [b, s, n x d] where a projection
# wrote it, hp heads side by side in a 128-lane block
# ---------------------------------------------------------------------------
from apex_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention_bshd,
    flash_attention_qkv,
    flash_attention_sbhd,
    heads_per_block,
)


def _bnsd(x):
    return jnp.swapaxes(x, 1, 2)


def _bshd_case(key, n, d, b=2, s=64):
    ks = jax.random.split(key, 4)
    q, k, v, w = (jax.random.normal(kk, (b, s, n, d), jnp.float32)
                  for kk in ks)
    return q, k, v, w


def test_heads_per_block():
    assert heads_per_block(16, 64) == 2 and heads_per_block(8, 32) == 4
    assert heads_per_block(32, 128) == 1 and heads_per_block(2, 256) == 1
    # one head of 64 on a tensor-parallel rank, an odd count, a head size
    # that is no divisor or multiple of 128: the head-major route
    assert heads_per_block(1, 64) == 0 and heads_per_block(3, 64) == 0
    assert heads_per_block(8, 96) == 0 and heads_per_block(8, 16) == 0


@pytest.mark.parametrize("block", [16, 64])     # two kernels / the fused one
@pytest.mark.parametrize("causal,masked",
                         [(True, False), (False, True), (False, False)])
@pytest.mark.parametrize("n,d", [(4, 64), (2, 128), (4, 32)])
def test_flash_bshd_matches_reference(n, d, causal, masked, block):
    """Outputs and all three gradients of the batch-major entry point
    against the materialised reference: two heads of 64 (four of 32) share
    a 128-lane block, a head of 128 has its own."""
    q, k, v, w = _bshd_case(jax.random.PRNGKey(n * d + block), n, d)
    kv_mask = None
    if masked:
        kv_mask = jnp.arange(64)[None, :] < jnp.array([64, 40])[:, None]

    def flash(q, k, v):
        return jnp.sum(w * flash_attention_bshd(
            q, k, v, causal=causal, kv_mask=kv_mask, block_q=block,
            block_k=block))

    def ref(q, k, v):
        return jnp.sum(w * _bnsd(mha_reference(
            _bnsd(q), _bnsd(k), _bnsd(v), causal=causal, kv_mask=kv_mask)))

    got, g_got = jax.value_and_grad(flash, (0, 1, 2))(q, k, v)
    want, g_want = jax.value_and_grad(ref, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)
    for a, b, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, err_msg=f"d{name}")


@pytest.mark.parametrize("bias_shape", [(2, 4, 64, 64), (1, 1, 64, 64),
                                        (1, 4, 1, 64)])
def test_flash_bshd_bias_and_dropout(bias_shape):
    """A bias block holds the tiles of the heads of a q block (or one,
    broadcast over heads), dbias comes out by head, and the dropout
    counters stay ``batch x heads + head``: the reference's mask."""
    q, k, v, w = _bshd_case(jax.random.PRNGKey(5), 4, 64)
    bias = jax.random.normal(jax.random.PRNGKey(6), bias_shape, jnp.float32)
    kw = dict(causal=True, dropout_p=0.2, dropout_seed=21)

    def flash(q, k, v, bias):
        return jnp.sum(w * flash_attention_bshd(
            q, k, v, bias=bias, block_q=32, block_k=32, **kw))

    def ref(q, k, v, bias):
        return jnp.sum(w * _bnsd(mha_reference(
            _bnsd(q), _bnsd(k), _bnsd(v), bias=bias, **kw)))

    got = jax.grad(flash, (0, 1, 2, 3))(q, k, v, bias)
    want = jax.grad(ref, (0, 1, 2, 3))(q, k, v, bias)
    for a, b, name in zip(got, want, ("q", "k", "v", "bias")):
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("n,d", [(4, 64), (2, 128), (3, 64)])
def test_flash_sbhd_matches_reference(n, d):
    """Megatron's ``[s, b, n, d]`` through the batch-major kernels; three
    heads of 64 do not pair and take the head-major route."""
    q, k, v, w = (jnp.swapaxes(x, 0, 1)
                  for x in _bshd_case(jax.random.PRNGKey(7), n, d))
    to_bnsd = lambda x: jnp.transpose(x, (1, 2, 0, 3))

    def flash(q, k, v):
        return jnp.sum(w * flash_attention_sbhd(q, k, v, causal=True))

    def ref(q, k, v):
        o = mha_reference(to_bnsd(q), to_bnsd(k), to_bnsd(v), causal=True)
        return jnp.sum(w * jnp.transpose(o, (2, 0, 1, 3)))

    got, g_got = jax.value_and_grad(flash, (0, 1, 2))(q, k, v)
    want, g_want = jax.value_and_grad(ref, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("n,d,block", [(4, 64, 16), (4, 64, 64),
                                       (2, 128, 32), (3, 64, 64)])
def test_flash_qkv_matches_reference(n, d, block):
    """q, k and v as three views of one ``[b, s, 3, n, d]`` array (what a
    fused projection writes): the context and the array's gradient against
    the reference over its three parts; three heads of 64 do not pair and
    are sliced out for the head-major route."""
    qkv = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 3, n, d))
    w = jax.random.normal(jax.random.PRNGKey(10), (2, 64, n, d))
    mask = jnp.arange(64)[None, :] < jnp.array([64, 40])[:, None]

    def flash(x):
        return jnp.sum(w * flash_attention_qkv(
            x, kv_mask=mask, block_q=block, block_k=block))

    def ref(x):
        return jnp.sum(w * _bnsd(mha_reference(
            *(_bnsd(x[:, :, i]) for i in range(3)), kv_mask=mask)))

    got, g_got = jax.value_and_grad(flash)(qkv)
    want, g_want = jax.value_and_grad(ref)(qkv)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(g_got, g_want, atol=5e-5)


def test_flash_bshd_moves_nothing():
    """Between the arguments and the kernel, and between the kernel and
    the result, the batch-major entry point only reshapes: no transpose,
    slice or concatenation of q, k, v or the context, forward or backward."""
    q, k, v, w = _bshd_case(jax.random.PRNGKey(8), 4, 64)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(w * flash_attention_bshd(
            q, k, v, causal=True)), (0, 1, 2)))(q, k, v)
    kernels, prims = set(), set()

    def walk(jp):       # every equation outside the kernels' own bodies
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                kernels.add(eqn.params["name"])
                continue
            if any(getattr(x.aval, "size", 0) >= q.size for x in eqn.invars):
                prims.add(eqn.primitive.name)   # reads a whole activation
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jaxpr.jaxpr)
    assert kernels == {"apex_tpu_flash_fwd", "apex_tpu_flash_bwd_dkv"}
    assert not prims & {"transpose", "concatenate", "slice", "gather",
                        "dynamic_slice", "split", "copy"}, prims


# ---------------------------------------------------------------------------
# the triangular walk: a causal square that is one tile computes only the
# chunks of rows on or below its diagonal; every other call walks its tiles
# whole, as before
# ---------------------------------------------------------------------------
import importlib  # noqa: E402

from apex_tpu.ops.flash_attention import (  # noqa: E402
    dense_walk_share,
    flash_attention_varlen,
    mha_reference_varlen,
)

fa = importlib.import_module("apex_tpu.ops.flash_attention")

WALK_LAYOUTS = {            # kind, heads, head size
    "bnsd": ("bnsd", 2, 64),            # one head a block
    "bshd": ("bshd", 4, 64),            # two heads of 64 share a block
    "bshd-d128": ("bshd", 2, 128),      # one head of 128 a block
    "qkv": ("qkv", 4, 64),              # three views of one array
}
ENTRY = {"bnsd": flash_attention, "bshd": flash_attention_bshd,
         "qkv": flash_attention_qkv}


def _walk_args(layout, s, dtype, b=1, key=0):
    kind, n, d = WALK_LAYOUTS[layout]
    k1, k2 = jax.random.split(jax.random.PRNGKey(key))
    if kind == "qkv":
        args = (jax.random.normal(k1, (b, s, 3, n, d)).astype(dtype),)
    else:
        shape = (b, n, s, d) if kind == "bnsd" else (b, s, n, d)
        args = tuple(jax.random.normal(kk, shape).astype(dtype)
                     for kk in jax.random.split(k1, 3))
    w = jax.random.normal(k2, (b, n, s, d) if kind == "bnsd" else (b, s, n, d))
    return kind, args, w


def _as_bnsd(kind, args):
    if kind == "qkv":
        return tuple(_bnsd(args[0][:, :, i]) for i in range(3))
    return args if kind == "bnsd" else tuple(_bnsd(x) for x in args)


def _reference_in(kind, args, **kw):
    o = mha_reference(*_as_bnsd(kind, args), **kw)
    return o if kind == "bnsd" else _bnsd(o)


def _value_and_grads(fn, w, args):
    return jax.value_and_grad(
        lambda *a: jnp.sum(w * fn(*a).astype(jnp.float32)),
        tuple(range(len(args))))(*args)


def _close(got, want, tol, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = np.abs(got - want).max()
    assert err <= tol * max(1.0, np.abs(want).max()), (what, err)


def _lse(kind, args, block, **kw):
    q, k, v = args if kind != "qkv" else (args[0], None, None)
    return fa._fwd(q, k, v, None, kw.get("kv_mask"), None, None,
                   kw.get("seed"), 1.0 / q.shape[-1] ** 0.5, True,
                   kw.get("dropout_p", 0.0), block, block, True, kind)[1]


@pytest.mark.parametrize("layout,dtype,s", [
    ("bnsd", "float32", 256), ("bnsd", "float32", 512),
    ("bnsd", "float32", 1024), ("bnsd", "bfloat16", 512),
    ("bshd", "float32", 512), ("bshd", "bfloat16", 1024),
    ("bshd-d128", "float32", 512), ("qkv", "float32", 512),
    ("qkv", "bfloat16", 1024),
])
def test_walk_matches_the_square_and_the_reference(layout, dtype, s):
    """``n_c`` chunks (1 at 256 rows, 2 at 512, 4 at 1024): the context,
    ``lse`` and every gradient against the same call in two tiles of
    ``s / 2`` (the online multi-tile path, its own ``bwd_dq`` kernel: it
    never walks) and against the materialised reference."""
    n_c = {256: 1, 512: 2, 1024: 4}[s]
    assert dense_walk_share(s, s, causal=True) == (n_c + 1) / (2 * n_c)
    kind, args, w = _walk_args(layout, s, jnp.dtype(dtype))
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    entry = ENTRY[kind]
    walk = _value_and_grads(
        lambda *a: entry(*a, causal=True), w, args)
    square = _value_and_grads(
        lambda *a: entry(*a, causal=True, block_q=s // 2, block_k=s // 2),
        w, args)
    ref = _value_and_grads(
        lambda *a: _reference_in(kind, a, causal=True), w, args)
    for oracle, name in ((square, "two tiles"), (ref, "reference")):
        _close(walk[0], oracle[0], tol, f"loss vs {name}")
        for g, o, part in zip(walk[1], oracle[1], "qkv"):
            _close(g, o, 2 * tol, f"d{part} vs {name}")
    _close(entry(*args, causal=True),
           entry(*args, causal=True, block_q=s // 2, block_k=s // 2),
           tol, "context")
    _close(_lse(kind, args, s), _lse(kind, args, s // 2), 1e-5, "lse")


@pytest.mark.parametrize("case", ["kv_mask", "dropout", "segments"])
def test_walk_masks_and_dropout_match_the_square(case):
    """A chunk's masks and dropout counters are its own global rows and
    keys: the key-padding mask, the per-token segments of the packed
    layout (one tile of 512 packed tokens) and a dropout mask equal bit
    for bit to the one two tiles of 256 draw (f32: a single flipped keep
    bit would show as an error of order one)."""
    s = 512
    if case == "segments":
        kind, args, w = _walk_args("bshd", s, jnp.float32)
        qkv = tuple(x[0] for x in args)
        cu = jnp.array([0, 100, 260, 397, 512], jnp.int32)
        fn = lambda blk: lambda *a: flash_attention_varlen(  # noqa: E731
            *a, cu, causal=True, block_q=blk, block_k=blk)
        ref = lambda *a: mha_reference_varlen(*a, cu, causal=True)  # noqa
        got, sq, want = (_value_and_grads(f, w[0], qkv)
                         for f in (fn(s), fn(s // 2), ref))
    else:
        kind, args, w = _walk_args("bshd" if case == "kv_mask" else "bnsd",
                                   s, jnp.float32, b=2)
        kw = (dict(kv_mask=jnp.arange(s)[None, :] < jnp.array([[s], [300]]))
              if case == "kv_mask" else dict(dropout_p=0.2, dropout_seed=21))
        entry = ENTRY[kind]
        got, sq = (_value_and_grads(
            lambda *a, blk=blk: entry(*a, causal=True, block_q=blk,
                                      block_k=blk, **kw), w, args)
            for blk in (s, s // 2))
        want = _value_and_grads(
            lambda *a: _reference_in(kind, a, causal=True, **kw), w, args)
    for oracle, name in ((sq, "two tiles"), (want, "reference")):
        _close(got[0], oracle[0], 2e-5, f"loss vs {name}")
        for g, o, part in zip(got[1], oracle[1], "qkv"):
            _close(g, o, 5e-5, f"d{part} vs {name}")


@pytest.mark.parametrize("case", ["non_causal", "bias", "rectangular"])
def test_walk_does_not_engage(case):
    """Non-causal, a bias (its dbias tiles are owned (iq, ik)) and
    ``s_q != s_k`` walk the whole square: share 1.0, the tile's ``walk``
    0, results as the reference's."""
    s_q, s_k = (256, 512) if case == "rectangular" else (256, 256)
    causal = case != "non_causal"
    assert dense_walk_share(s_q, s_k, causal=causal,
                            has_bias=case == "bias") == 1.0
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    q = jax.random.normal(ks[0], (1, 2, s_q, 64))
    k, v = (jax.random.normal(kk, (1, 2, s_k, 64)) for kk in ks[1:3])
    w = jax.random.normal(ks[3], (1, 2, s_q, 64))
    bias = (jax.random.normal(ks[4], (1, 2, s_q, s_k)) if case == "bias"
            else None)
    plan = fa._plan(q, k, v, bias, None, None, None, 0.125, causal, 0.0,
                    1024, 1024, True, "bnsd")
    assert plan.tile.walk == 0 and plan.tile.share == 1.0
    got = _value_and_grads(lambda *a: flash_attention(
        *a, causal=causal, bias=bias), w, (q, k, v))
    want = _value_and_grads(lambda *a: mha_reference(
        *a, causal=causal, bias=bias), w, (q, k, v))
    _close(got[0], want[0], 2e-5, "loss")
    for g, o, part in zip(got[1], want[1], "qkv"):
        _close(g, o, 5e-5, f"d{part}")


@pytest.mark.parametrize("s,block,causal,bias,share", [
    (1024, 1024, True, False, 5 / 8),       # cells 1 and 3: the qkv call
    (512, 1024, False, False, 1.0),         # BERT: non-causal
    (512, 1024, True, False, 3 / 4),        # two chunks of 256
    (256, 1024, True, False, 1.0),          # one chunk: the square
    (1024, 512, True, False, 1.0),          # two tiles: the tile skip
    (2048, 1024, True, False, 1.0),         # the online multi-tile path
    (1024, 1024, True, True, 1.0),          # a bias keeps the square
])
def test_dense_walk_share(s, block, causal, bias, share):
    """The share is the plan's, from static shapes and arguments alone."""
    assert dense_walk_share(s, s, block, block, causal, bias) == share
    q = jnp.zeros((1, s, 2, 64), jnp.bfloat16)
    plan = fa._plan(q, q, q, None, None, None, None, 0.125, causal, 0.0,
                    block, block, True, "bshd")
    if not bias:
        assert plan.tile.share == share
    t = plan.tile
    assert sum(r * k for _, r, k in t.chunks) == (
        t.share * t.block_q * t.block_k)


# ---------------------------------------------------------------------------
# the chip compiler's word on the walked bodies (compile only: a described
# v5e, no chip): Mosaic takes the static slices at c rows and (r+1) c lanes
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("b,s,causal,share", [
    (8, 1024, True, 5 / 8),             # gpt2-345m's cells
    (16, 512, False, 1.0),              # bert-large's cell
], ids=["gpt2-345m", "bert-large"])
def test_v5e_compiles_the_walked_kernels_at_the_cells_shapes(
        one_chip, monkeypatch, b, s, causal, share):
    """Forward and the fused ``bwd_dkv`` (no ``bwd_dq``) of the cells'
    ``qkv`` call, 16 heads of 64, bf16."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dense_walk_share(s, s, causal=causal) == share
    x = jax.ShapeDtypeStruct((b, s, 3, 16, 64), jnp.bfloat16,
                             sharding=one_chip)

    def step(x):
        o, vjp = jax.vjp(lambda x: flash_attention_qkv(x, causal=causal), x)
        return o, vjp(o)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(step).lower(x).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    assert "apex_tpu_flash_fwd" in text and "apex_tpu_flash_bwd_dkv" in text
    assert "apex_tpu_flash_bwd_dq" not in text
