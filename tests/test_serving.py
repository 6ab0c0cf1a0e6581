"""apex_tpu.serving: paged-KV decode engine + continuous batching.

Coverage map (the ISSUE-6 acceptance surface):

- flash-decode parity vs. dense reference attention — single-query
  rows, ragged page tables, fully-masked (empty) slots, bf16 vs f32
  tolerance; XLA fallback AND the real kernel body (interpret mode);
- PagedKVSpec: chunk-aligned PackSpec layout (check_pack_spec clean),
  pack/unpack round trip, alignment validation;
- scheduler property test: random admit/evict/preempt traces never
  leak or double-free pages;
- ServingEngine.generate token-identity vs. the per-request
  dense-attention greedy decode loop across a staggered continuous-
  batching trace, including under forced preemption;
- assert_step_clean on the jitted decode step (KV cache donated, no
  ungated callbacks) with the in-jit telemetry drain ARMED;
- satellites: amp.cast_params_for_inference, telemetry.percentiles,
  tools/serving_check.py exit codes;
- tensor parallelism (ISSUE-16): TP=2/4 token identity vs TP=1 on the
  8-virtual-device mesh (tools/serving_check tp_identity), the 3-psum-
  per-program jaxpr pin with no pool-shaped all-gather, head-sharded
  PagedKVSpec geometry, sharding-preserving inference cast, the
  top_k<=filter-width submit guard, TP-tagged telemetry + DP x TP fleet
  summary, topology-preserving recover/rebuild/swap.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.flash_decode import (
    flash_decode,
    flash_decode_available,
    paged_decode_reference,
)
from apex_tpu.serving import (
    PageAllocator,
    PagedKVSpec,
    Request,
    Scheduler,
    SchedulerError,
    ServingEngine,
    reference_decode,
)
from apex_tpu.transformer.testing import GPTConfig, init_gpt_params


def _tiny_cfg(dtype=jnp.float32):
    return GPTConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=64,
        hidden_dropout=0.0, attention_dropout=0.0,
        params_dtype=jnp.float32, compute_dtype=dtype)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = _tiny_cfg()
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    # amplified position table: greedy continuations become position-
    # sensitive instead of collapsing to a fixed point, so the identity
    # tests genuinely exercise the growing cache
    params["embedding"]["position"] = params["embedding"]["position"] * 40.0
    return cfg, params


# ---------------------------------------------------------------------------
# flash decode parity
# ---------------------------------------------------------------------------

def _decode_case(dtype, seed=0, P=8, n=4, ps=16, d=16, B=5, mp=3):
    rng = np.random.default_rng(seed)
    k_pages = jnp.asarray(rng.normal(size=(P, n, ps, d)), dtype)
    v_pages = jnp.asarray(rng.normal(size=(P, n, ps, d)), dtype)
    q = jnp.asarray(rng.normal(size=(B, n, d)), dtype)
    pt = jnp.asarray(rng.integers(1, P, size=(B, mp)), jnp.int32)
    lens = jnp.asarray([0, 5, 16, 33, 48], jnp.int32)
    return q, k_pages, v_pages, pt, lens


@pytest.mark.parametrize("mode", ["xla", "kernel"])
def test_flash_decode_matches_reference(mode):
    """Ragged lengths (mid-page tails, full pages, empty slot) against
    the dense gathered softmax."""
    q, k_pages, v_pages, pt, lens = _decode_case(jnp.float32)
    ref = np.asarray(paged_decode_reference(q, k_pages, v_pages, pt, lens))
    if mode == "xla":
        out = flash_decode(q, k_pages, v_pages, pt, lens, use_kernel=False)
        tol = 1e-6
    else:
        out = flash_decode(q, k_pages, v_pages, pt, lens, interpret=True)
        tol = 1e-5
    np.testing.assert_allclose(np.asarray(out), ref, rtol=tol, atol=tol)


def test_flash_decode_matches_dense_attention():
    """The paged path equals plain softmax attention over the tokens the
    page table stitches together (the 'single-query row' contract)."""
    q, k_pages, v_pages, pt, lens = _decode_case(jnp.float32)
    out = np.asarray(
        flash_decode(q, k_pages, v_pages, pt, lens, interpret=True))
    P, n, ps, d = k_pages.shape
    mp = pt.shape[1]
    for b in range(q.shape[0]):
        L = int(lens[b])
        if L == 0:
            np.testing.assert_array_equal(out[b], 0.0)
            continue
        kk = np.asarray(k_pages)[np.asarray(pt)[b]]  # [mp, n, ps, d]
        kk = kk.transpose(1, 0, 2, 3).reshape(n, mp * ps, d)[:, :L]
        vv = np.asarray(v_pages)[np.asarray(pt)[b]]
        vv = vv.transpose(1, 0, 2, 3).reshape(n, mp * ps, d)[:, :L]
        s = np.einsum("nd,nkd->nk", np.asarray(q)[b], kk) / np.sqrt(d)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        naive = np.einsum("nk,nkd->nd", p, vv)
        np.testing.assert_allclose(out[b], naive, rtol=1e-5, atol=1e-5)


def test_flash_decode_fully_masked_pages_inert():
    """Garbage-page entries past the length never contaminate the
    output: same result whether the tail entries point at real pages or
    at the garbage page."""
    q, k_pages, v_pages, pt, lens = _decode_case(jnp.float32)
    pt2 = np.asarray(pt).copy()
    ps = k_pages.shape[2]
    for b in range(pt2.shape[0]):
        used = -(-int(lens[b]) // ps)
        pt2[b, used:] = 0  # garbage page
    a = flash_decode(q, k_pages, v_pages, pt, lens, interpret=True)
    bb = flash_decode(q, k_pages, v_pages, jnp.asarray(pt2), lens,
                      interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))


def test_flash_decode_bf16_vs_f32_tolerance():
    """bf16 pages/queries track the f32 math within bf16-level error."""
    qf, kf, vf, pt, lens = _decode_case(jnp.float32, seed=3)
    ref = np.asarray(flash_decode(qf, kf, vf, pt, lens, use_kernel=False))
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (qf, kf, vf))
    out = flash_decode(qb, kb, vb, pt, lens, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), ref, rtol=5e-2, atol=5e-2)


def test_flash_decode_mixed_kv_dtype_no_pool_cast():
    """f32 compute over a bf16 KV pool (the halve-the-cache config):
    parity holds on both paths WITHOUT materializing a f32 copy of the
    whole pool — the jaxpr must contain no pool-shaped convert."""
    qf, kf, vf, pt, lens = _decode_case(jnp.float32, seed=5)
    ref = np.asarray(flash_decode(qf, kf, vf, pt, lens, use_kernel=False))
    kb, vb = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16)
    for kw in ({"use_kernel": False}, {"interpret": True}):
        out = flash_decode(qf, kb, vb, pt, lens, **kw)
        assert out.dtype == jnp.float32
        np.testing.assert_allclose(
            np.asarray(out), ref, rtol=5e-2, atol=5e-2)
    pool_shape = kb.shape
    jaxpr = jax.make_jaxpr(
        lambda *a: flash_decode(*a, use_kernel=False))(qf, kb, vb, pt, lens)
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type":
            assert tuple(eqn.invars[0].aval.shape) != pool_shape, (
                "whole-pool dtype cast reintroduced")


def test_flash_decode_shape_validation():
    q, k_pages, v_pages, pt, lens = _decode_case(jnp.float32)
    with pytest.raises(ValueError, match="do not match q"):
        flash_decode(q[:, :2], k_pages, v_pages, pt, lens)
    assert flash_decode_available(16, 64)
    assert not flash_decode_available(12, 64)   # page % 8
    assert not flash_decode_available(16, 512)  # head dim
    # the page is the block's sublane dim: 8 tokens is one fp32 tile but
    # half a bf16 (16 x 128) tile — the old rule let ps = 8 bf16 through
    assert flash_decode_available(8, 64, jnp.float32)
    assert not flash_decode_available(8, 64, jnp.bfloat16)
    assert flash_decode_available(16, 64, jnp.bfloat16)
    kb, vb = (x[:, :, :8].astype(jnp.bfloat16) for x in (k_pages, v_pages))
    with pytest.raises(ValueError, match="bfloat16 tiles"):
        flash_decode(q, kb, vb, pt, lens // 2, interpret=True)


def test_default_page_size_follows_kv_dtype():
    """16 heads x 64 dim (GPT-2 345M): the ROW rule alone picks 8
    tokens; a bf16 pool needs 16 for the kernel's page block — and the
    engine derives it from the pool dtype, not the caller."""
    from apex_tpu.serving import default_page_size

    assert default_page_size(16, 64) == 8
    assert default_page_size(16, 64, jnp.bfloat16) == 16
    assert default_page_size(4, 64, jnp.bfloat16) == 16  # tp=4 shard
    cfg = GPTConfig(
        num_layers=1, hidden_size=256, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=32,
        hidden_dropout=0.0, attention_dropout=0.0,
        compute_dtype=jnp.bfloat16)
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, n_slots=2, interpret=True)
    assert eng.spec.page_size == 16 and eng.spec.dtype == jnp.bfloat16
    eng32 = ServingEngine(cfg, params, n_slots=2, interpret=True,
                          kv_dtype=jnp.float32)
    assert eng32.spec.page_size == 8


# ---------------------------------------------------------------------------
# paged KV spec / cache layout
# ---------------------------------------------------------------------------

def test_paged_kv_spec_is_chunk_aligned_packspec():
    """Every page is one chunk of the PackSpec view; the PR-4 checker
    passes (the layout gate the packed optimizers run under)."""
    from apex_tpu.analysis import check_pack_spec
    from apex_tpu.multi_tensor_apply.packing import ROW

    spec = PagedKVSpec(2, 4, 16, page_size=16, num_pages=6,
                       pages_per_seq=3, dtype=jnp.float32)
    assert spec.page_elems % ROW == 0
    assert spec.pack_spec.chunk_size == spec.page_elems
    assert check_pack_spec(spec.pack_spec) == []
    # leaf offsets are page multiples: pages start on chunk boundaries
    for off in spec.pack_spec.offsets:
        assert off % spec.page_elems == 0


def test_paged_kv_spec_rejects_misaligned_page():
    with pytest.raises(ValueError, match="ROW-aligned"):
        PagedKVSpec(1, 3, 16, page_size=8, num_pages=4, pages_per_seq=2)
    with pytest.raises(ValueError, match="garbage"):
        PagedKVSpec(1, 4, 16, page_size=16, num_pages=1, pages_per_seq=2)


def test_paged_kv_pack_unpack_roundtrip():
    spec = PagedKVSpec(2, 4, 16, page_size=16, num_pages=4,
                       pages_per_seq=2, dtype=jnp.float32)
    cache = spec.init_cache()
    rng = np.random.default_rng(0)
    cache = cache._replace(pages=jnp.asarray(
        rng.normal(size=cache.pages.shape), jnp.float32))
    flat = spec.pack(cache)
    assert flat.shape == (spec.pack_spec.total,)
    back = spec.unpack(flat)
    np.testing.assert_array_equal(np.asarray(back.pages),
                                  np.asarray(cache.pages))


def test_page_allocator_invariants():
    al = PageAllocator(6)  # pages 1..5 usable
    assert al.free_count == 5
    got = [al.alloc() for _ in range(5)]
    assert sorted(got) == [1, 2, 3, 4, 5]
    assert al.alloc() is None
    al.free(got[:2])
    with pytest.raises(ValueError, match="double-free"):
        al.free(got[:1])
    with pytest.raises(ValueError, match="garbage"):
        al.free([0])
    al.free(got[2:])
    al.check()
    assert al.free_count == 5 and al.used_count == 0


# ---------------------------------------------------------------------------
# scheduler property test
# ---------------------------------------------------------------------------

def test_scheduler_random_traces_never_leak_pages():
    """Randomized admit/advance/evict/preempt traces: page accounting
    stays exact at every boundary and drains to empty."""
    rng = np.random.default_rng(1234)
    for trial in range(8):
        spec = PagedKVSpec(
            1, 4, 16, page_size=16,
            num_pages=int(rng.integers(3, 9)), pages_per_seq=4)
        sched = Scheduler(spec, n_slots=int(rng.integers(1, 4)),
                          max_prompt_len=spec.max_seq_len)
        live = []
        for r in range(int(rng.integers(3, 10))):
            total = int(rng.integers(2, spec.max_seq_len))
            plen = int(rng.integers(1, total))
            req = Request(prompt=list(rng.integers(0, 50, size=plen)),
                          max_new_tokens=total - plen)
            if spec.pages_for(total) > spec.n_usable_pages:
                # a request the pool can never hold is refused at
                # submit (it would sink the whole trace mid-flight)
                with pytest.raises(SchedulerError,
                                   match="never be served"):
                    sched.submit(req)
                continue
            sched.submit(req)
            live.append(req)
        guard = 0
        while not sched.idle:
            guard += 1
            assert guard < 5000, "scheduler trace did not terminate"
            sched.admit()
            # validated traces never sink: ensure_capacity must always
            # succeed (preempting as needed), whatever the pool size
            sched.ensure_capacity()
            sched.check_invariants()
            served = sched.running()
            sched.advance([i for i, _ in served])
            for i, run in served:
                if not run.prefilling:  # a token was generated
                    run.req.out_tokens.append(0)
                if run.req.done:
                    sched.evict(i)
            sched.check_invariants()
        sched.check_invariants()
        assert sched.allocator.used_count == 0
        assert sched.allocator.free_count == spec.n_usable_pages


def test_scheduler_refuses_replay_overflow_at_submit():
    """A request whose preemption-replay prompt could outgrow
    max_prompt_len must be refused at submit(): admit() pops before
    validating, so a late rejection would silently drop the request."""
    spec = PagedKVSpec(1, 4, 16, page_size=16, num_pages=5,
                       pages_per_seq=4)
    sched = Scheduler(spec, n_slots=2, max_prompt_len=16)
    # prompt fits (12 <= 16) and total fits the pages (32 <= 64), but a
    # preemption after 5+ generated tokens would replay a 17+ prompt
    with pytest.raises(SchedulerError, match="replay"):
        sched.submit(Request(prompt=list(range(12)), max_new_tokens=20))
    assert not sched.waiting
    # worst replay exactly at the cap (12 + 5 - 1 = 16) is admissible
    sched.submit(Request(prompt=list(range(12)), max_new_tokens=5))
    assert len(sched.waiting) == 1


# ---------------------------------------------------------------------------
# engine: token identity under continuous batching
# ---------------------------------------------------------------------------

def test_engine_token_identical_staggered_trace(tiny_model):
    """The acceptance criterion: generate() over a staggered
    continuous-batching trace (more requests than slots, arrivals
    mid-flight, evictions freeing slots for waiting requests) emits
    token-for-token what the per-request dense-attention greedy loop
    emits."""
    cfg, params = tiny_model
    rng = np.random.default_rng(42)
    lens = (5, 9, 3, 12, 7)
    reqs = [
        Request(prompt=[int(t) for t in rng.integers(0, 128, size=L)],
                max_new_tokens=6, arrival_step=3 * i)
        for i, L in enumerate(lens)
    ]
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=12,
                        max_prompt_len=16)
    out = eng.generate(reqs, max_steps=1000)
    eng.scheduler.check_invariants()
    assert eng.scheduler.allocator.used_count == 0
    for r in reqs:
        ref = reference_decode(cfg, params, r.prompt, r.max_new_tokens)
        assert out[r.rid] == ref, (
            f"request {r.rid}: engine {out[r.rid]} != reference {ref}")
    st = eng.last_stats
    assert st["completed"] == len(reqs)
    assert 0 < st["occupancy"] <= 1.0
    assert st["generated_tokens"] == sum(len(v) for v in out.values())
    # latency percentiles come from the shared reducer
    assert set(st["latency_ms"]) == {"p50", "p90", "p99"}


def test_engine_token_identical_under_preemption(tiny_model):
    """A pool too small for two full requests forces recompute-mode
    preemption (evict + requeue + prefill replay); the emitted tokens
    must not change."""
    cfg, params = tiny_model
    rng = np.random.default_rng(7)
    reqs = [
        Request(prompt=[int(t) for t in rng.integers(0, 128, size=L)],
                max_new_tokens=8, arrival_step=i)
        for i, L in enumerate((14, 11, 13, 9))
    ]
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=4,
                        max_prompt_len=16)
    out = eng.generate(reqs, max_steps=2000)
    eng.scheduler.check_invariants()
    assert eng.last_stats["preemptions"] > 0, (
        "trace was sized to force preemption")
    for r in reqs:
        ref = reference_decode(cfg, params, r.prompt, r.max_new_tokens)
        assert out[r.rid] == ref


def test_engine_eos_stops_early(tiny_model):
    """EOS termination: the engine stops a request at the token the
    reference loop stops at."""
    cfg, params = tiny_model
    rng = np.random.default_rng(3)
    prompt = [int(t) for t in rng.integers(0, 128, size=6)]
    # pick the 3rd greedy token as the EOS so the cut happens mid-run
    free_run = reference_decode(cfg, params, prompt, 8)
    eos = free_run[2]
    ref = reference_decode(cfg, params, prompt, 8, eos_id=eos)
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=8,
                        max_prompt_len=16)
    out = eng.generate(
        [Request(prompt=prompt, max_new_tokens=8, eos_id=eos)],
        max_steps=200)
    assert list(out.values())[0] == ref
    assert ref[-1] == eos and len(ref) == 3


def test_engine_bf16_serving_smoke(tiny_model):
    """bf16 weights + bf16 paged KV (the deployment configuration,
    weights cast through amp's inference cast): runs to completion with
    in-range tokens and bf16 cache/params."""
    cfg32, params = tiny_model
    cfg = _tiny_cfg(jnp.bfloat16)
    rng = np.random.default_rng(5)
    reqs = [Request(prompt=[int(t) for t in rng.integers(0, 128, size=7)],
                    max_new_tokens=5)]
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=8,
                        max_prompt_len=16)
    assert eng.params["layers"]["qkv_w"].dtype == jnp.bfloat16
    assert eng.spec.dtype == jnp.bfloat16
    out = eng.generate(reqs, max_steps=200)
    toks = list(out.values())[0]
    assert len(toks) == 5 and all(0 <= t < 128 for t in toks)


def test_engine_decode_logits_match_training_forward(tiny_model):
    """Numerics, not just argmax: after prefilling a prompt through the
    paged path, the engine's next-token logits match the training
    forward's last-position logits."""
    from apex_tpu.transformer.testing.standalone_transformer_lm import (
        gpt_forward,
    )

    cfg, params = tiny_model
    rng = np.random.default_rng(11)
    prompt = [int(t) for t in rng.integers(0, 128, size=9)]
    eng = ServingEngine(cfg, params, n_slots=1, num_pages=8,
                        max_prompt_len=16)
    eng.submit(Request(prompt=prompt, max_new_tokens=1))
    # run the prefill steps; capture the logits-bearing emission step
    # via the engine's own step loop
    emitted = None
    for _ in range(len(prompt)):
        em = eng.run_step()
        if em[0] >= 0:
            emitted = int(em[0])
    assert emitted is not None
    ref_logits = gpt_forward(
        cfg, params, jnp.asarray([prompt], jnp.int32), deterministic=True)
    assert emitted == int(jnp.argmax(ref_logits[0, -1]))


# ---------------------------------------------------------------------------
# audit: the serving analogue of the training-step invariants
# ---------------------------------------------------------------------------

def test_decode_step_audits_clean_with_telemetry_armed(tiny_model):
    """assert_step_clean on the REAL jitted decode step: KV cache, slot
    state and MetricsState donated; the armed in-jit telemetry drain is
    cond-gated (an ungated callback would be an error finding)."""
    from apex_tpu import telemetry

    cfg, params = tiny_model
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=6,
                        max_prompt_len=16, telemetry_every=4,
                        sink=telemetry.RingBufferRecorder())
    report = eng.audit()  # raises on error-severity findings
    assert report.ok


def test_decode_step_undonated_kv_is_flagged(tiny_model):
    """Red test: the same step WITHOUT donation must trip the auditor's
    undonated-state rule on the KV cache."""
    from apex_tpu.analysis import audit_step

    cfg, params = tiny_model
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=6,
                        max_prompt_len=16)
    fn, args = eng.step_program()
    undonated = jax.jit(fn.__wrapped__)  # strip jit+donation
    report = audit_step(undonated, *args, name="undonated_serving_step")
    assert not report.ok
    assert "undonated_state" in set(report.codes())


def test_engine_untileable_head_dim_fails_at_construction():
    """A (page_size, head_dim) the kernel cannot tile must raise in
    __init__ when the kernel path is selected — not mid-trace at the
    first decode step — and still construct under the XLA fallback."""
    # 1 head x 8 tokens x 512 dim: ROW-aligned (spec OK) but head_dim
    # 512 > 256 exceeds the kernel's MXU tiling bound
    cfg = GPTConfig(
        num_layers=1, hidden_size=512, num_attention_heads=1,
        vocab_size=128, max_position_embeddings=32,
        hidden_dropout=0.0, attention_dropout=0.0,
        params_dtype=jnp.float32, compute_dtype=jnp.float32)
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="tile"):
        ServingEngine(cfg, params, n_slots=2, num_pages=6, page_size=8,
                      max_prompt_len=16, use_kernel=True)
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=6, page_size=8,
                        max_prompt_len=16, use_kernel=False)
    assert eng.spec.head_dim == 512


def test_engine_in_jit_telemetry_counts_tokens(tiny_model):
    """The PR-2 metrics ride the decode step: drained windows count the
    emitted tokens (prefill steps contribute zero)."""
    from apex_tpu import telemetry

    cfg, params = tiny_model
    ring = telemetry.RingBufferRecorder()
    rng = np.random.default_rng(9)
    reqs = [Request(prompt=[int(t) for t in rng.integers(0, 128, size=4)],
                    max_new_tokens=6)]
    eng = ServingEngine(cfg, params, n_slots=1, num_pages=6,
                        max_prompt_len=16, telemetry_every=1, sink=ring)
    eng.generate(reqs, max_steps=100)
    jax.effects_barrier()
    drains = [r for r in ring.records if r.get("event") == "metrics"]
    assert drains, "telemetry drains must reach the sink"
    assert sum(r["tokens"] for r in drains) == pytest.approx(6.0)
    summaries = [r for r in ring.records
                 if r.get("event") == "serving_summary"]
    assert summaries and summaries[0]["generated_tokens"] == 6


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------

def test_cast_params_for_inference_dtype_coverage():
    """Every float leaf lands in the target dtype; integer leaves and
    (optionally) batchnorm-ish leaves are untouched."""
    from apex_tpu.amp import cast_params_for_inference

    params = {
        "w": jnp.ones((4, 4), jnp.float32),
        "half": jnp.ones((4,), jnp.float16),
        "ids": jnp.arange(4, dtype=jnp.int32),
        "bn": {"batchnorm_scale": jnp.ones((4,), jnp.float32)},
    }
    out = cast_params_for_inference(params, jnp.bfloat16)
    assert out["w"].dtype == jnp.bfloat16
    assert out["half"].dtype == jnp.bfloat16
    assert out["ids"].dtype == jnp.int32
    assert out["bn"]["batchnorm_scale"].dtype == jnp.bfloat16
    kept = cast_params_for_inference(params, jnp.bfloat16,
                                     keep_batchnorm_fp32=True)
    assert kept["bn"]["batchnorm_scale"].dtype == jnp.float32


def test_cast_params_for_inference_no_copy_when_cast():
    """Already-cast leaves come back as the SAME array objects — a
    second cast is free (no device copies, no new buffers)."""
    from apex_tpu.amp import cast_params_for_inference

    params = {"w": jnp.ones((4, 4), jnp.float32),
              "ids": jnp.arange(4, dtype=jnp.int32)}
    once = cast_params_for_inference(params, jnp.bfloat16)
    twice = cast_params_for_inference(once, jnp.bfloat16)
    assert twice["w"] is once["w"]
    assert twice["ids"] is once["ids"]
    # and an fp32 target over fp32 inputs is the identity
    same = cast_params_for_inference(params, jnp.float32)
    assert same["w"] is params["w"]


def test_percentiles_reducer():
    from apex_tpu.telemetry import percentiles

    vals = list(range(1, 101))
    ps = percentiles(vals)
    assert ps["p50"] == pytest.approx(50.5)
    assert ps["p99"] == pytest.approx(99.01)
    recs = [{"ms": float(v)} for v in vals]
    recs.append({"other": 1.0})            # missing field skipped
    recs.append({"ms": "nan"})             # JSONL non-finite repr skipped
    recs.append({"ms": "inf"})
    assert percentiles(recs, field="ms") == ps
    assert percentiles([], field="ms") == {}
    assert percentiles([{"ms": None}], field="ms") == {}
    assert percentiles([1.0], ps=(25, 75)) == {"p25": 1.0, "p75": 1.0}


def test_health_report_dispatch_interval_percentiles():
    """health_report folds bench per-step dispatch stamps into per-leg
    dispatch-interval percentiles via the shared reducer."""
    from tools.health_report import health_from_records, render_report

    records = [{"event": "step", "leg": "gpt", "step": i,
                "t_dispatch": 1000.0 + 0.010 * i} for i in range(11)]
    h = health_from_records(records)
    assert h["dispatch_interval_ms"]["gpt"]["p50"] == pytest.approx(
        10.0, rel=1e-6)
    assert "dispatch interval [gpt]" in render_report(h)


def test_serving_check_cli_exit_codes():
    """CI contract: --self exits 0 when clean; bad usage exits 2 (via
    argparse); unknown check names are rejected."""
    import tools.serving_check as sc

    assert sc.main(["--self", "--check", "decode_parity", "--json"]) == 0
    with pytest.raises(SystemExit) as e:
        sc.main([])  # no --self: usage error
    assert e.value.code == 2
    with pytest.raises(SystemExit):
        sc.main(["--self", "--check", "nope"])


def test_serving_check_detects_broken_engine(monkeypatch):
    """A mismatching engine turns into exit 1, not a silent pass."""
    import tools.serving_check as sc

    def broken():
        return {"ok": False, "mismatches": [{"rid": 0}]}

    monkeypatch.setitem(sc.CHECKS, "token_identity", broken)
    assert sc.main(["--self", "--check", "token_identity"]) == 1


def test_scheduler_rejects_oversized_requests(tiny_model):
    cfg, params = tiny_model
    eng = ServingEngine(cfg, params, n_slots=1, num_pages=8,
                        max_prompt_len=8)
    with pytest.raises(SchedulerError, match="max_prompt_len"):
        eng.submit(Request(prompt=list(range(9)), max_new_tokens=1))
    with pytest.raises(SchedulerError, match="max_position_embeddings"):
        eng.submit(Request(prompt=list(range(8)), max_new_tokens=100))
    with pytest.raises(SchedulerError, match="max_new_tokens"):
        eng.submit(Request(prompt=[1, 2], max_new_tokens=0))


def test_scheduler_rejects_request_pool_can_never_hold(tiny_model):
    """A request needing more pages than the whole pool must be refused
    at submit — admitted, it would preempt everything and then sink the
    batch mid-generate (review finding)."""
    cfg, params = tiny_model
    # pool: 3 usable pages of 16 tokens (48); total = 16+48 = 64 needs
    # 4 pages, yet passes the max_prompt_len / maxpos / max_seq checks
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=4,
                        max_prompt_len=16)
    too_big = 16 + 48
    assert too_big <= cfg.max_position_embeddings <= eng.spec.max_seq_len
    assert eng.spec.pages_for(too_big) > eng.spec.n_usable_pages
    with pytest.raises(SchedulerError, match="never be served"):
        eng.submit(Request(prompt=list(range(1, 17)), max_new_tokens=48))
    # requests the pool CAN hold (2 pages each, 3 usable -> they must
    # timeshare) still run to completion, token-identically
    reqs = [Request(prompt=list(range(1, 17)), max_new_tokens=8),
            Request(prompt=list(range(2, 18)), max_new_tokens=8,
                    arrival_step=1)]
    out = eng.generate(reqs, max_steps=500)
    eng.scheduler.check_invariants()
    assert eng.scheduler.allocator.used_count == 0
    for r in reqs:
        assert out[r.rid] == reference_decode(
            cfg, params, r.prompt, r.max_new_tokens)


# ---------------------------------------------------------------------------
# tensor parallelism (ISSUE-16): TP-sharded engine over the named mesh
# ---------------------------------------------------------------------------

def test_tp_identity_sweep():
    """The ISSUE-16 oracle, wired tier-1: ``tools/serving_check.py``'s
    ``tp_identity`` leg — TP=2 and TP=4 engines on the 8-virtual-device
    mesh are byte-identical to TP=1 across a staggered trace with
    chunked prefill, speculation, sampled + greedy slots and forced
    preemption, and every TP program's jaxpr carries exactly 3 psums."""
    import tools.serving_check as sc

    res = sc.check_tp_identity()
    assert res["tps"] == [2, 4], res
    assert res["ok"], res


def test_tp_spec_shard_and_page_size(tiny_model):
    """Geometry: the per-shard spec holds heads/tp of every page as one
    ROW-aligned PackSpec (check_pack_spec clean at shard_count=tp), and
    the default page size derives from the LOCAL head count."""
    from apex_tpu.analysis.rules import check_pack_spec

    cfg, params = tiny_model
    e1 = ServingEngine(cfg, params, n_slots=2, use_kernel=False)
    e2 = ServingEngine(cfg, params, n_slots=2, tp=2, use_kernel=False)
    assert e2.spec_local.num_heads == e2.spec.num_heads // 2
    assert e2.spec_local.page_size == e2.spec.page_size
    # per-shard K/V page still ROW-aligned -> larger default page than
    # the unsharded engine needs (4 heads/16 dim: 16 -> 32 tokens)
    assert e2.spec.page_size > e1.spec.page_size
    assert not check_pack_spec(e2.spec.pack_spec, shard_count=2)
    assert e2.spec_local.cache_bytes() * 2 == e2.spec.cache_bytes()
    # indivisible head counts / vocab are construction errors
    with pytest.raises(ValueError, match="not divisible"):
        ServingEngine(cfg, params, n_slots=2, tp=3, use_kernel=False)


def test_tp_psum_pin_and_no_pool_gather(tiny_model):
    """The collective budget, pinned on the traced programs: exactly
    one psum per transformer sublayer tail plus ONE fused sampler
    reduction = 3 per program (the fori_loop body appears once in the
    jaxpr) — and no all-gather ever touches a pool-shaped array (the
    only gathered operands are tiny sampler candidate matrices)."""
    import math
    import re

    cfg, params = tiny_model
    eng = ServingEngine(cfg, params, n_slots=2, tp=2, use_kernel=False,
                        prefill_chunk=3, spec_k=2)
    counts = eng.program_psum_counts()
    assert counts == {"decode": 3, "chunk_prefill": 3, "spec_verify": 3}
    pool_elems = math.prod(eng.spec_local.pool_leaf_shape)
    for fn, args in (eng.step_program(), eng.chunk_step_program(),
                     eng.spec_step_program()):
        txt = str(jax.make_jaxpr(fn)(*args))
        gathered = [m for m in txt.splitlines() if "all_gather" in m]
        assert gathered  # the sampler's candidate gather is there
        # an all-gather's output is >= its operand: bounding every
        # gathered RESULT far below one pool leaf proves no KV page
        # (page_size x head_dim trailing dims) ever crossed shards
        for line in gathered:
            for shp in re.findall(r"\[([\d,]+)\]", line):
                dims = tuple(int(x) for x in shp.split(","))
                assert math.prod(dims) < pool_elems // 4, (
                    f"all-gather of pool-scale operand {dims}: {line}")


def test_tp_engine_summary_and_events(tiny_model):
    """_summarize carries tp / per-shard pool bytes / psum counts, and
    fleet telemetry events are tagged with the TP degree."""
    from apex_tpu.serving import ReplicaFleet
    from apex_tpu.telemetry import RingBufferRecorder

    cfg, params = tiny_model
    eng = ServingEngine(cfg, params, n_slots=2, tp=2, use_kernel=False)
    out = eng.generate([Request(prompt=[1, 2, 3], max_new_tokens=4)],
                       max_steps=200)
    assert len(out) == 1
    st = eng.last_stats
    assert st["tp"] == 2
    assert st["kv_bytes_per_shard"] == eng.spec_local.cache_bytes()
    assert st["psum_per_program"] == {"decode": 3}
    # tp=1 engines report the null collective budget
    e1 = ServingEngine(cfg, params, n_slots=2, use_kernel=False)
    e1.generate([Request(prompt=[1, 2, 3], max_new_tokens=2)],
                max_steps=100)
    assert e1.last_stats["tp"] == 1
    assert e1.last_stats["psum_per_program"] is None

    ring = RingBufferRecorder()
    fleet = ReplicaFleet(cfg, params, n_replicas=2, tp=2, sink=ring,
                         n_slots=2, use_kernel=False)
    reqs = [Request(prompt=[2 + i, 3 + i], max_new_tokens=3)
            for i in range(3)]
    fleet.generate(reqs, max_steps=300)
    st = fleet.last_stats
    assert st["tp"] == 2 and st["total_chips"] == 4
    assert st["psum_per_program"] == {"decode": 3}
    tagged = [r for r in ring.records if "tp" in r and "replica_id" in r]
    assert tagged and all(r["tp"] == 2 for r in tagged)
    # DP x TP replicas own disjoint device groups
    groups = [{d.id for d in
               rep.engine._mesh.devices.reshape(-1)}
              for rep in fleet.replicas]
    assert groups[0].isdisjoint(groups[1])


def test_tp_audit_covers_sharded_programs(tiny_model):
    """engine.audit() stays clean on the TP-traced step: KV / slot /
    metrics donation and the cond-gated telemetry callback survive the
    shard_map wrapper, with the pool PackSpec checked at shard_count=tp
    (the in-jit drain ARMED, as in the tp=1 audit)."""
    from apex_tpu.telemetry import RingBufferRecorder

    cfg, params = tiny_model
    eng = ServingEngine(cfg, params, n_slots=2, tp=2, use_kernel=False,
                        telemetry_every=4, prefill_chunk=3, spec_k=2,
                        sink=RingBufferRecorder())
    report = eng.audit()
    assert report.ok


def test_tp_rejects_deep_top_k(tiny_model):
    """The TP sampler has no full-vocab-sort fallback: top_k beyond
    TOP_FILTER_WIDTH is refused at submit with a typed reason (tp=1
    keeps accepting it — the lax.cond deep path serves it there)."""
    from apex_tpu.serving import SamplingParams
    from apex_tpu.serving.robustness import RejectionCode
    from apex_tpu.serving.sampling import TOP_FILTER_WIDTH

    cfg, params = tiny_model
    deep = SamplingParams(temperature=0.9, top_k=TOP_FILTER_WIDTH + 1,
                          seed=3)
    eng = ServingEngine(cfg, params, n_slots=2, tp=2, use_kernel=False)
    reason = eng._engine_reject_reason(
        Request(prompt=[1, 2], max_new_tokens=2, sampling=deep))
    assert reason is not None
    assert reason.code is RejectionCode.UNSUPPORTED_SAMPLING
    with pytest.raises(SchedulerError, match="filter width"):
        eng.submit(Request(prompt=[1, 2], max_new_tokens=2,
                           sampling=deep))
    e1 = ServingEngine(cfg, params, n_slots=2, use_kernel=False)
    assert e1._engine_reject_reason(
        Request(prompt=[1, 2], max_new_tokens=2, sampling=deep)) is None


def test_cast_params_for_inference_preserves_sharding(tiny_model):
    """Satellite 1 (red test): casting a mesh-sharded param tree keeps
    every leaf's NamedSharding — a TP engine's column/row weight slices
    must not silently gather onto one device — and an already-cast
    sharded leaf comes back as the SAME buffer (zero-copy identity)."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from apex_tpu.amp import cast_params_for_inference
    from apex_tpu.transformer import parallel_state

    mesh = parallel_state.tp_submesh(2)
    col = NamedSharding(mesh, PartitionSpec("tensor", None))
    rep = NamedSharding(mesh, PartitionSpec())
    params = {
        "w_col": jax.device_put(
            jnp.asarray(np.arange(32.0).reshape(8, 4), jnp.float32), col),
        "b_rep": jax.device_put(jnp.ones((4,), jnp.float32), rep),
        "ids": jnp.arange(4, dtype=jnp.int32),
    }
    out = cast_params_for_inference(params, jnp.bfloat16)
    assert out["w_col"].dtype == jnp.bfloat16
    assert out["w_col"].sharding.is_equivalent_to(col, 2)
    assert out["b_rep"].sharding.is_equivalent_to(rep, 1)
    assert out["ids"] is params["ids"]
    # idempotent re-cast of the sharded tree: same buffers, no copies
    again = cast_params_for_inference(out, jnp.bfloat16)
    assert again["w_col"] is out["w_col"]
    assert again["b_rep"] is out["b_rep"]


def test_tp_recover_and_swap_keep_topology(tiny_model):
    """recover_from / rebuild_like / swap_params preserve the TP
    geometry (captured ctor kwargs): the revived engine decodes
    token-identically on the same device group, and a weight swap lays
    the fresh tree down SHARDED before the cast."""
    cfg, params = tiny_model
    eng = ServingEngine(cfg, params, n_slots=2, tp=2, use_kernel=False)
    reqs = [Request(prompt=[3, 4, 5, 6], max_new_tokens=5)]
    ref = reference_decode(cfg, params, [3, 4, 5, 6], 5)
    out = eng.generate(list(reqs), max_steps=200)
    assert out[reqs[0].rid] == ref

    fresh = ServingEngine.rebuild_like(eng)
    assert fresh.tp == 2 and fresh._mesh is not None
    r2 = Request(prompt=[3, 4, 5, 6], max_new_tokens=5)
    assert fresh.generate([r2], max_steps=200)[r2.rid] == ref

    # hot swap: sharded placement preserved, decode follows new weights
    params2 = jax.tree_util.tree_map(lambda x: x, params)
    params2["embedding"]["position"] = (
        params["embedding"]["position"] * 0.5)
    fresh.swap_params(params2)
    qkv = fresh.params["layers"]["qkv_w"]
    assert "tensor" in str(qkv.sharding.spec)
    r3 = Request(prompt=[3, 4, 5, 6], max_new_tokens=5)
    assert (fresh.generate([r3], max_steps=200)[r3.rid]
            == reference_decode(cfg, params2, [3, 4, 5, 6], 5))
