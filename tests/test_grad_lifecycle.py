"""Bucketed flat-buffer gradient lifecycle oracle (ISSUE-14).

The acceptance contract for ``GradBuckets`` + ``reduce_flat`` +
``unscale_flat`` + the packed optimizer fed the reduced buffer: training
with the flat-bucket lifecycle must be **step-for-step bit-identical**
(f32-hex loss records) to the per-leaf reference — per-leaf ``psum`` via
``sync_gradients``, pytree amp unscale, pytree ``FusedAdam`` — on the
8-virtual-device CPU mesh under ``shard_map``, including overflow-skip
steps (a NaN-poisoned batch trips ``found_inf`` identically on both
paths) and with ``allreduce_always_fp32`` both off and on. Plus the
layout/scope/telemetry unit contracts the lifecycle rests on.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.amp import LossScaler
from apex_tpu.analysis import check_pack_spec
from apex_tpu.multi_tensor_apply.packing import ROW, PackSpec
from apex_tpu.optimizers import FusedAdam, FusedSGD
from apex_tpu.parallel import (
    DistributedDataParallel,
    GradBuckets,
    sync_gradients,
    sync_gradients_bucketed,
)

CHUNK = 2 * ROW  # small kernel chunk so multi-bucket layouts stay tiny


def _mesh():
    return Mesh(np.array(jax.devices()), ("data",))


def _params(dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    mk = lambda k, shape: (  # noqa: E731
        0.1 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    return {
        "w1": mk(ks[0], (12, 64)),
        "b1": mk(ks[1], (64,)),
        "w2": mk(ks[2], (64, 4)),
        "b2": mk(ks[3], (4,)),
    }


def _batches(steps, batch=16, poison_at=None):
    """Deterministic regression batches; ``poison_at`` plants a NaN
    feature in that step's batch (NaN grads -> overflow skip)."""
    out = []
    for s in range(steps):
        k = jax.random.PRNGKey(100 + s)
        x = jax.random.normal(k, (batch, 12), jnp.float32)
        y = jnp.sum(x, axis=1, keepdims=True) * jnp.ones((1, 4))
        if s == poison_at:
            x = x.at[0, 0].set(jnp.nan)
        out.append((x, y))
    return out


def _loss_fn(params, x, y):
    h = jnp.tanh(x.astype(params["w1"].dtype) @ params["w1"]
                 + params["b1"])
    pred = h @ params["w2"] + params["b2"]
    return jnp.mean((pred.astype(jnp.float32) - y) ** 2)


def _run(steps, batches, flat, always_fp32, parity_downcast,
         bucket_cap_mb=0.002):
    """One training run; returns the per-step f32 loss records as hex.

    ``flat=True`` is the bucketed lifecycle (reduce_flat -> unscale_flat
    -> packed FusedAdam on the reduced buffer); ``False`` the per-leaf
    reference; ``flat="fused"`` the one-sweep fused spelling (raw
    per-bucket psum, read-only ``found_inf_flat``, the unscale multiply
    AND the deferred gradient average riding ``grad_scale`` into
    ``step_flat``'s in-kernel noop update, forward from views of the
    master buffer — exact vs the reference because loss scale and world
    size are powers of two). ``parity_downcast`` selects
    reference-parity cast-back after an fp32 reduction (per leaf vs per
    bucket) — with it off, both paths keep the reduction's fp32
    (``keep_fp32`` / the flat default).
    """
    params = _params()
    buckets = GradBuckets(params, bucket_cap_mb=bucket_cap_mb,
                          chunk_size=CHUNK)
    assert buckets.n_buckets >= 2, "oracle must exercise multiple buckets"
    scaler = LossScaler(loss_scale="dynamic", init_scale=2.0 ** 4,
                        scale_window=3)
    sstate = scaler.init_state()
    fused = flat == "fused"
    world = len(jax.devices())
    if flat:
        opt = FusedAdam(lr=1e-2, master_weights=True, packed=True,
                        packed_spec=buckets.spec)
        ddp = DistributedDataParallel(
            "data", allreduce_always_fp32=always_fp32,
            gradient_average=not fused,
            bucket_cap_mb=bucket_cap_mb)
    else:
        opt = FusedAdam(lr=1e-2, master_weights=True)
    opt_state = opt.init(params)

    def shard_step(params, opt_state, sstate, x, y):
        if fused:
            # masters ARE the params; bf16 leaves are unpack views
            params = buckets.unpack(opt_state.master_params)

        def scaled(p):
            loss = _loss_fn(p, x, y)
            return scaler.scale_loss(sstate, loss.astype(jnp.float32)), loss

        (_, loss), grads = jax.value_and_grad(scaled, has_aux=True)(params)
        if fused:
            bufs, _ = ddp.reduce_flat(grads, buckets=buckets,
                                      concat=False)
            new_ss = scaler.found_inf_flat(sstate, bufs)
            opt_state = opt.step_flat(
                bufs, opt_state, found_inf=new_ss.found_inf,
                grad_scale=new_ss.loss_scale * world)
        elif flat:
            g, _ = ddp.reduce_flat(grads, buckets=buckets,
                                   match_leaf_dtype=parity_downcast)
            g, new_ss = scaler.unscale_flat(sstate, g,
                                            out_dtype=jnp.float32)
            params, opt_state = opt.step(g, opt_state, params,
                                         found_inf=new_ss.found_inf)
        else:
            grads = sync_gradients(
                grads, "data", allreduce_always_fp32=always_fp32,
                keep_fp32=not parity_downcast)
            g, new_ss = scaler.unscale(sstate, grads,
                                       out_dtype=jnp.float32)
            params, opt_state = opt.step(g, opt_state, params,
                                         found_inf=new_ss.found_inf)
        new_ss = scaler.update_scale(new_ss)
        loss = jax.lax.pmean(loss.astype(jnp.float32), "data")
        return params, opt_state, new_ss, loss

    step = jax.jit(shard_map(
        shard_step, mesh=_mesh(),
        in_specs=(P(), P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P(), P()), check_vma=False))

    records = []
    for x, y in batches:
        params, opt_state, sstate, loss = step(params, opt_state, sstate,
                                               x, y)
        records.append(np.float32(loss).tobytes().hex())
    return records


@pytest.mark.parametrize(
    "always_fp32,parity_downcast",
    [(False, True),   # half-precision reduction, reference cast-back
     (True, True),    # fp32 reduction + reference per-leaf/bucket downcast
     (True, False)],  # fp32 reduction kept fp32 (the audit-clean default)
    ids=["bf16_reduce", "fp32_reduce_parity", "fp32_reduce_keep"])
def test_flat_lifecycle_bit_identical_to_per_leaf(always_fp32,
                                                  parity_downcast):
    steps = 8
    # step 3 overflows (NaN batch): found_inf must trip, the update must
    # skip and the scaler must back off IDENTICALLY on both paths
    batches = _batches(steps, poison_at=3)
    ref = _run(steps, batches, flat=False, always_fp32=always_fp32,
               parity_downcast=parity_downcast)
    got = _run(steps, batches, flat=True, always_fp32=always_fp32,
               parity_downcast=parity_downcast)
    assert got == ref, (
        "flat-bucket lifecycle diverged from the per-leaf reference: "
        f"\nref={ref}\ngot={got}")
    # the poisoned step really produced a NaN loss record (the overflow
    # path was exercised, not dodged)
    poisoned = np.frombuffer(bytes.fromhex(ref[3]), np.float32)[0]
    assert np.isnan(poisoned)
    healthy = np.frombuffer(bytes.fromhex(ref[4]), np.float32)[0]
    assert np.isfinite(healthy)


def test_fused_lifecycle_bit_identical_to_per_leaf():
    """The one-sweep fused spelling (the bench/headline lifecycle):
    raw-sum bucket psums, read-only found_inf, unscale AND gradient
    average deferred into step_flat's in-kernel noop update, forward
    from master-buffer views — still bit-identical to the per-leaf
    reference, overflow-skip steps included (the noop select must leave
    step/m/v/masters untouched exactly like the reference's skipped
    lax.cond)."""
    steps = 8
    batches = _batches(steps, poison_at=3)
    ref = _run(steps, batches, flat=False, always_fp32=True,
               parity_downcast=False)
    got = _run(steps, batches, flat="fused", always_fp32=True,
               parity_downcast=False)
    assert got == ref, (
        "fused flat lifecycle diverged from the per-leaf reference: "
        f"\nref={ref}\ngot={got}")
    poisoned = np.frombuffer(bytes.fromhex(ref[3]), np.float32)[0]
    assert np.isnan(poisoned)


def test_step_flat_matches_step_and_noop_contract():
    """step_flat == step on the same reduced buffer (modulo the carry
    shape), and its in-kernel noop leaves step/m/v/masters bit-frozen."""
    params = _params(jnp.float32)
    buckets = GradBuckets(params, bucket_cap_mb=0.002, chunk_size=CHUNK)
    grads = jax.tree_util.tree_map(
        lambda p: jnp.ones_like(p) * 1e-2, params)
    flat = buckets.concat(buckets.pack(grads))
    bufs = jax.tree_util.tree_map(lambda x: x, buckets.pack(grads))
    from apex_tpu.parallel import BucketBuffers

    opt = FusedAdam(lr=1e-2, master_weights=True, packed=True,
                    packed_spec=buckets.spec)
    s0 = opt.init(params)
    no = jnp.asarray(False)
    # compare jit-to-jit: the contract is bit-identity of the compiled
    # steps (XLA's fusion choices differ between eager and traced runs)
    p_ref, s_ref = jax.jit(opt.step)(flat, opt.init(params), params,
                                     found_inf=no)
    s_got = jax.jit(lambda b, s: opt.step_flat(b, s, found_inf=no))(
        BucketBuffers(tuple(bufs)), s0)
    # same state bits, and the master buffer IS the params (unpack views
    # equal the step()-returned tree)
    np.testing.assert_array_equal(np.asarray(s_got.exp_avg),
                                  np.asarray(s_ref.exp_avg))
    np.testing.assert_array_equal(np.asarray(s_got.master_params),
                                  np.asarray(s_ref.master_params))
    got_tree = buckets.unpack(s_got.master_params)
    for k in params:
        np.testing.assert_array_equal(np.asarray(got_tree[k]),
                                      np.asarray(p_ref[k]))
    # overflow: every field frozen, including the step counter
    s_skip = opt.step_flat(flat, s_got, found_inf=jnp.asarray(True),
                           grad_scale=2.0)
    assert int(s_skip.step) == int(s_got.step)
    for a, b in zip(jax.tree_util.tree_leaves(s_skip),
                    jax.tree_util.tree_leaves(s_got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # guard: the flat-carry contract needs resident masters
    with pytest.raises(ValueError, match="master_weights"):
        FusedAdam(lr=1e-2, packed=True).step_flat(flat, s0)


def test_found_inf_flat_matches_unscale_flat_verdict():
    """The read-only overflow probe agrees with the unscale sweep's
    verdict on both clean and poisoned buffers, from the flat buffer or
    the BucketBuffers handoff."""
    from apex_tpu.parallel import BucketBuffers

    params = _params(jnp.float32)
    buckets = GradBuckets(params, bucket_cap_mb=0.002, chunk_size=CHUNK)
    scaler = LossScaler(loss_scale=4.0)
    for poison in (False, True):
        grads = jax.tree_util.tree_map(jnp.ones_like, params)
        if poison:
            grads["w1"] = grads["w1"].at[0, 0].set(jnp.inf)
        flat = buckets.concat(buckets.pack(grads))
        bufs = BucketBuffers(tuple(buckets.pack(grads)))
        _, ref = scaler.unscale_flat(scaler.init_state(), flat,
                                     out_dtype=jnp.float32)
        got_flat = scaler.found_inf_flat(scaler.init_state(), flat)
        got_bufs = scaler.found_inf_flat(scaler.init_state(), bufs)
        assert bool(got_flat.found_inf) == bool(ref.found_inf) == poison
        assert bool(got_bufs.found_inf) == poison


def test_bucket_layout_structure_and_invariants():
    params = _params(jnp.float32)
    buckets = GradBuckets(params, bucket_cap_mb=0.002, chunk_size=CHUNK)
    spec = buckets.spec
    assert buckets.n_buckets >= 2
    buckets.check()
    assert check_pack_spec(spec) == []
    # bucket bounds are chunk-aligned and cover [0, total)
    assert spec.bucket_bounds[0] == 0
    assert spec.bucket_bounds[-1] == spec.total
    assert all(b % spec.chunk_size == 0 for b in spec.bucket_bounds)
    # leaf ranges partition the leaves in order
    flatranges = [r for lo, hi in spec.bucket_leaf_ranges
                  for r in range(lo, hi)]
    assert flatranges == list(range(spec.n_leaves))
    # per-bucket packing concatenates into exactly the global pack
    glob = spec.pack(params, jnp.float32)
    cat = buckets.concat(buckets.pack(params, jnp.float32))
    np.testing.assert_array_equal(np.asarray(glob), np.asarray(cat))
    # and the global buffer unpacks back to the tree
    out = buckets.unpack(glob)
    for k in params:
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(params[k]))


def test_autobuilt_fp32_reduction_sizes_cap_at_fp32():
    """allreduce_always_fp32 must not double the collective buffers:
    the default-built buckets size bucket_cap_mb in fp32 (the dtype the
    psum actually moves), not the bf16 grad dtype."""
    # 4 x 1-chunk bf16 leaves; cap = 2 fp32 chunks. fp32 sizing -> 2
    # buckets of cap bytes each; bf16 sizing would cram all 4 into one
    # 2x-cap fp32 buffer.
    chunk = 65536  # the GradBuckets default (auto-build has no knob)
    tree = {f"w{i}": jnp.zeros((chunk,), jnp.bfloat16) for i in range(4)}
    cap_mb = 2 * chunk * 4 / 2 ** 20
    assert GradBuckets(tree, bucket_cap_mb=cap_mb).n_buckets == 1
    assert GradBuckets(tree, bucket_cap_mb=cap_mb,
                       reduce_dtype=jnp.float32).n_buckets == 2

    def reduce_fn(t):
        return sync_gradients_bucketed(
            t, "data", bucket_cap_mb=cap_mb,
            allreduce_always_fp32=True)[0]

    f = shard_map(reduce_fn, mesh=_mesh(), in_specs=P(), out_specs=P(),
                  check_vma=False)
    from apex_tpu.analysis import comm_volume
    assert comm_volume(f, tree)["psum"]["count"] == 2


def test_adopted_spec_rejects_conflicting_chunk_size():
    params = _params(jnp.float32)
    buckets = GradBuckets(params, bucket_cap_mb=0.002, chunk_size=CHUNK)
    with pytest.raises(ValueError, match="chunk_size"):
        FusedAdam(lr=1e-3, packed=True, packed_chunk_size=4 * CHUNK,
                  packed_spec=buckets.spec).init(params)
    # matching or omitted chunk_size still adopts the spec
    s = FusedAdam(lr=1e-3, packed=True, packed_chunk_size=CHUNK,
                  packed_spec=buckets.spec).init(params)
    assert s.spec is buckets.spec


def test_oversized_leaf_gets_its_own_bucket():
    # one leaf larger than the cap must not raise — it becomes its own
    # bucket (the reference's message_size overflow behaviour)
    tree = {"big": jnp.zeros((8 * CHUNK,), jnp.float32),
            "small": jnp.zeros((8,), jnp.float32)}
    buckets = GradBuckets(tree, bucket_cap_mb=0.001, chunk_size=CHUNK)
    assert buckets.n_buckets == 2
    buckets.check()


def test_corrupt_bucket_bounds_fail_check():
    import copy

    spec = GradBuckets(_params(jnp.float32), bucket_cap_mb=0.002,
                       chunk_size=CHUNK).spec
    bad = copy.copy(spec)
    bad.bucket_bounds = tuple(
        list(spec.bucket_bounds[:-1]) + [spec.total + 1])
    codes = {f.code for f in check_pack_spec(bad)}
    assert "bucket_bounds_cover" in codes
    assert "bucket_not_chunk_aligned" in codes
    # mismatched range/bounds tables produce a finding, not an
    # IndexError aborting the audit
    worse = copy.copy(spec)
    worse.bucket_bounds = spec.bucket_bounds[:-1]
    assert "bucket_tables_mismatch" in {
        f.code for f in check_pack_spec(worse)}


def test_bucketed_reduce_one_psum_per_bucket_with_named_scopes():
    """The collective structure the overlap story rests on: exactly one
    psum per bucket, each under its apex_tpu.grad_bucket/<i> scope (the
    PR-2 xplane parser's attribution hook)."""
    params = _params(jnp.float32)
    buckets = GradBuckets(params, bucket_cap_mb=0.002, chunk_size=CHUNK)

    def reduce_fn(tree):
        return sync_gradients_bucketed(tree, "data", buckets=buckets)[0]

    f = shard_map(reduce_fn, mesh=_mesh(), in_specs=P(),
                  out_specs=P(), check_vma=False)
    # one data psum per bucket (the world-size psum of a literal 1
    # constant-folds at trace time) — eqn-counted by the walker, not
    # text-matched (ISSUE-19)
    from apex_tpu.analysis import comm_volume
    vol = comm_volume(f, params)
    assert vol["psum"] == {"count": buckets.n_buckets,
                           "bytes": buckets.spec.total * 4,
                           "axes": ["data"]}
    # scopes ride the name stack into the compiled program — the xplane
    # attribution surface (test_observability.py's convention)
    hlo = jax.jit(f).lower(params).compile().as_text()
    for i in range(buckets.n_buckets):
        assert f"apex_tpu.grad_bucket/{i}" in hlo


def test_sync_gradients_keep_fp32_is_audit_clean():
    """The PR-4 double_cast fix: the legacy per-leaf fp32 round-trip
    trips the auditor; keep_fp32 (and the flat path) do not."""
    from apex_tpu.analysis import audit_step

    grads = {"w": jnp.ones((256, 256), jnp.bfloat16)}

    def legacy(g):
        g = sync_gradients(g, "data", allreduce_always_fp32=True)
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32) * 0.5, g)

    def clean(g):
        g = sync_gradients(g, "data", allreduce_always_fp32=True,
                           keep_fp32=True)
        return jax.tree_util.tree_map(lambda x: x * 0.5, g)

    def run(fn):
        mapped = shard_map(fn, mesh=_mesh(), in_specs=P(), out_specs=P(),
                           check_vma=False)
        return audit_step(mapped, grads, rules=("dtype_flow",))

    assert "double_cast" in run(legacy).codes()
    assert "double_cast" not in run(clean).codes()


def test_flat_grads_reject_layout_mismatch():
    params = _params(jnp.float32)
    opt = FusedAdam(lr=1e-3, packed=True)
    state = opt.init(params)
    wrong = jnp.zeros((state.spec.total + ROW,), jnp.float32)
    with pytest.raises(ValueError, match="PackSpec"):
        opt.step(wrong, state, params)
    with pytest.raises(ValueError, match="packed_spec requires"):
        FusedAdam(packed_spec=state.spec)
    # the flat wrapper cannot hand back a buffer in a layout nothing
    # else shares: buckets= is required
    with pytest.raises(ValueError, match="buckets"):
        DistributedDataParallel("data", bucket_cap_mb=1.0).wrap_grad_fn(
            lambda p: p, flat=True)


def test_single_bare_leaf_pytree_still_packs():
    """A grads pytree that IS a bare 1-D array must keep the pytree
    reading (packed, dtype-normalised) — not be mistaken for a
    pre-packed buffer and rejected for its unpadded length."""
    w = jnp.ones((1000,), jnp.float32)
    opt = FusedAdam(lr=1e-3, packed=True, packed_chunk_size=CHUNK)
    state = opt.init(w)
    assert state.spec.total != w.shape[0]  # the ambiguity under test
    p1, _ = opt.step(jnp.ones_like(w) * 1e-2, state, w)
    # and the genuinely pre-packed spelling of the same update agrees
    flat = state.spec.pack(jnp.ones_like(w) * 1e-2, jnp.float32)
    p2, _ = opt.step(flat, opt.init(w), w)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


def test_found_inf_flat_flags_overflow_under_collapsed_scale():
    """scale < 1: a finite scaled gradient whose deferred 1/scale
    multiply would overflow fp32 must trip the read-only probe (the
    fused spelling has no later sweep to catch it)."""
    scaler = LossScaler(loss_scale=2.0 ** -10)
    big = jnp.full((8,), 1e36, jnp.float32)  # finite; 1e36/2**-10 = inf
    state = scaler.found_inf_flat(scaler.init_state(), big)
    assert bool(state.found_inf)
    # same magnitude at scale >= 1 stays clean (verdict-parity regime)
    ok = LossScaler(loss_scale=1.0)
    assert not bool(ok.found_inf_flat(ok.init_state(), big).found_inf)


def test_fused_sgd_accepts_reduced_flat_buffer():
    """The SGD spelling of the handoff: flat grads == packed pytree
    grads, bit-for-bit."""
    params = _params(jnp.float32)
    grads = jax.tree_util.tree_map(
        lambda p: jnp.ones_like(p) * 1e-2, params)
    buckets = GradBuckets(params, bucket_cap_mb=0.002, chunk_size=CHUNK)
    opt = FusedSGD(lr=0.1, momentum=0.9, packed=True,
                   packed_spec=buckets.spec)
    s1, s2 = opt.init(params), opt.init(params)
    flat = buckets.concat(buckets.pack(grads))
    p_flat, s_flat = opt.step(flat, s1, params)
    p_tree, s_tree = opt.step(grads, s2, params)
    for k in params:
        np.testing.assert_array_equal(np.asarray(p_flat[k]),
                                      np.asarray(p_tree[k]))
    np.testing.assert_array_equal(np.asarray(s_flat.exp_avg),
                                  np.asarray(s_tree.exp_avg))


def test_unscale_flat_found_inf_and_provenance():
    """One flat sweep yields unscale + found_inf + per-leaf overflow
    provenance through the row-aligned offsets."""
    from apex_tpu.telemetry.numerics import NumericsMonitor

    params = _params(jnp.float32)
    buckets = GradBuckets(params, bucket_cap_mb=0.002, chunk_size=CHUNK)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    grads["w2"] = grads["w2"].at[3, 1].set(jnp.inf)
    flat = buckets.concat(buckets.pack(grads))

    scaler = LossScaler(loss_scale=2.0)
    sstate = scaler.init_state()
    monitor = NumericsMonitor(spec=buckets.spec)
    nstate = monitor.init()
    out, sstate, nstate = scaler.unscale_flat(
        sstate, flat, out_dtype=jnp.float32,
        numerics=(monitor, nstate))
    assert bool(sstate.found_inf)
    # provenance names exactly the poisoned leaf (flatten order:
    # b1, b2, w1, w2 — dict keys sort)
    names = buckets.spec.leaf_names()
    bad = [n for n, f in zip(names, np.asarray(nstate.grad_nonfinite))
           if f > 0]
    assert bad == ["['w2']"]
    # the healthy positions really got unscaled (x * 1/2)
    np.testing.assert_allclose(np.asarray(out)[0], 0.5)


def test_sweep_bytes_feeds_telemetry_gbps():
    """GradBuckets.sweep_bytes mirrors PackedState.sweep_bytes and wires
    the per-drain achieved-GB/s denominator."""
    from apex_tpu import telemetry

    params = _params(jnp.bfloat16)
    buckets = GradBuckets(params, bucket_cap_mb=0.002, chunk_size=CHUNK)
    total = buckets.spec.total
    # bf16 grads read (2 B) + bf16 bucket write, local read+write of the
    # reduced buckets (2 B each): 4 sweeps of the padded length
    assert buckets.sweep_bytes() == 2 * total + 3 * 2 * total
    f32 = GradBuckets(params, bucket_cap_mb=0.002, chunk_size=CHUNK,
                      reduce_dtype=jnp.float32)
    assert f32.sweep_bytes() == 2 * total + 3 * 4 * total

    records = []
    metrics = telemetry.init_metrics()
    step = jax.jit(functools.partial(
        telemetry.drain, sink=records.append, every_n=1,
        bytes_per_step=buckets.sweep_bytes()))
    for _ in range(3):
        metrics = telemetry.accumulate(metrics, loss=jnp.float32(1.0),
                                       tokens=8)
        metrics = step(metrics)
    jax.effects_barrier()
    assert len(records) == 3
    # from the second drain on, the denominator yields achieved_gbps
    assert "achieved_gbps" in records[-1]
    assert records[-1]["achieved_gbps"] > 0
