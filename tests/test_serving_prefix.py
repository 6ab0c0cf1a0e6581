"""ISSUE-12: chunked prefill + radix/hash prefix cache over the paged
KV pool.

Coverage map (the acceptance surface):

- PageAllocator extensions in isolation: refcount alloc/share/free
  semantics, cache pin/unpin, COW ``fork`` bookkeeping, double-free /
  foreign-free / misuse still raise, seeded-violation red tests for
  ``check()``;
- PrefixCache semantics: full-page chain keys, partial-tail
  exact-prompt match, LRU eviction that NEVER frees a reader-held
  page, flush (the hot-swap barrier), index/allocator coherence;
- scheduler integration: cache-hit admission cursor (capped at
  prompt_len - 1), COW fork emission + refcount bookkeeping,
  ``check_invariants()`` refcount cross-checks (red test included);
- token identity, both ways of the oracle: chunked prefill (any chunk
  size) == token-at-a-time == dense reference, and cache-hit decode ==
  cold decode — across staggered admit/evict/preempt traces, combined
  chunk x cache x tiny-pool preemption;
- eviction-under-pressure chaos property trace: random traces with
  stolen allocations AND forced cache evictions, ``check_invariants()``
  after every step, zero reader-held pages after drain, token identity
  throughout;
- admission/routing satellites: feasibility counts only uncached
  tokens, ``probe``'s post-hit prefill estimate, the
  ``_summarize`` prefill-vs-decode token split;
- the red hot-swap test: a stale prefix-cache entry surviving a
  rolling-update weight swap (``ReplicaFleet.try_join``) is
  impossible;
- CI wiring: the new ``serving_check.py --self`` legs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.serving import (
    PageAllocator,
    PagedKVSpec,
    PrefixCache,
    Request,
    RequestStatus,
    Scheduler,
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


@pytest.fixture(scope="module", autouse=True)
def _shed_compile_caches():
    """This module compiles many small engine programs late in the
    full suite; shed the executables the preceding files accumulated
    (the full-suite CPU lane runs close to its memory ceiling — the
    same pressure tests/test_crash_resume.py documents)."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def tiny_model():
    cfg = _tiny_cfg()
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    params["embedding"]["position"] = params["embedding"]["position"] * 40.0
    return cfg, params


def _spec(num_pages=8, page_size=16, pages_per_seq=4):
    # head_dim 64 keeps even the 4-token pages ROW-aligned (ROW=1024)
    return PagedKVSpec(1, 4, 64, page_size=page_size,
                       num_pages=num_pages, pages_per_seq=pages_per_seq)


# ---------------------------------------------------------------------------
# allocator: refcounts, pins, COW fork
# ---------------------------------------------------------------------------

def test_allocator_refcount_share_free_semantics():
    al = PageAllocator(5)  # pages 1..4
    p = al.alloc()
    assert al.refcount(p) == 1 and al.used_count == 1
    al.share(p)
    al.share(p)
    assert al.refcount(p) == 3
    al.free([p])
    al.free([p])
    assert al.refcount(p) == 1 and al.free_count == 3
    al.free([p])
    # third reader released -> back on the free list
    assert al.refcount(p) == 0 and al.free_count == 4
    with pytest.raises(ValueError, match="double-free"):
        al.free([p])
    al.check()


def test_allocator_pin_keeps_zero_reader_page_live():
    al = PageAllocator(4)
    p = al.alloc()
    al.pin(p)
    al.free([p])  # last READER gone; the pin keeps it live
    assert al.refcount(p) == 0 and al.is_pinned(p)
    assert al.used_count == 0          # no readers -> not "used"
    assert al.cached_count == 1
    assert al.free_count == 2          # p is NOT free
    al.check()
    al.unpin(p)                        # pin released -> freed
    assert al.free_count == 3
    with pytest.raises(ValueError, match="not live"):
        al.share(p)
    with pytest.raises(ValueError, match="not live"):
        al.pin(p)
    with pytest.raises(ValueError, match="not pinned"):
        al.unpin(p)


def test_allocator_pin_misuse_raises():
    al = PageAllocator(4)
    p = al.alloc()
    al.pin(p)
    with pytest.raises(ValueError, match="already pinned"):
        al.pin(p)
    with pytest.raises(ValueError, match="garbage"):
        al.free([0])


def test_allocator_is_shared():
    al = PageAllocator(5)
    p = al.alloc()
    assert not al.is_shared(p)          # one reader, no pin: exclusive
    al.share(p)
    assert al.is_shared(p)              # second reader
    al.free([p])
    al.pin(p)
    assert al.is_shared(p)              # one reader + index pin
    al.unpin(p)
    assert not al.is_shared(p)


def test_allocator_fork_bookkeeping():
    al = PageAllocator(5)
    src = al.alloc()
    al.share(src)                       # someone else reads src too
    dst = al.fork(src)
    assert dst is not None and dst != src
    assert al.refcount(src) == 1        # our hold moved off src
    assert al.refcount(dst) == 1
    al.check()
    # the scheduler's pressure path: the destination was obtained
    # separately (eviction/preemption machinery); fork just swaps holds
    pre = al.alloc()
    assert al.fork(dst, pre) == pre
    assert al.refcount(dst) == 0 and al.refcount(pre) == 1
    al.share(pre)
    with pytest.raises(ValueError, match="freshly allocated"):
        al.fork(src, pre)               # dst already has two holds
    al.free([pre])
    # fork on a dry pool reports None and leaves src untouched
    while al.alloc() is not None:
        pass
    assert al.fork(pre) is None
    assert al.refcount(pre) == 1


def test_allocator_check_red_seeded_violations():
    al = PageAllocator(5)
    p = al.alloc()
    al._ref[p] = 0  # zero readers, no pin, not released: a leak
    with pytest.raises(AssertionError, match="zero readers"):
        al.check()
    al._ref[p] = 1
    al._pinned.add(99)  # pin on a page that is not live
    with pytest.raises(AssertionError, match="pinned"):
        al.check()


# ---------------------------------------------------------------------------
# prefix cache semantics
# ---------------------------------------------------------------------------

def test_prefix_cache_full_page_chain_match():
    spec = _spec(num_pages=8, page_size=4)
    al = PageAllocator(spec.num_pages)
    cache = PrefixCache(spec, al)
    toks = list(range(10))  # 2 full pages + 2-token tail
    p0, p1 = al.alloc(), al.alloc()
    assert cache.insert(toks[:4], p0)
    assert cache.insert(toks[:8], p1)
    assert cache.match_len(toks) == 8
    assert cache.match_len(toks[:6]) == 4   # only the first page
    assert cache.match_len([99] + toks[1:]) == 0
    pages, matched = cache.acquire(toks)
    assert pages == [p0, p1] and matched == 8
    assert al.refcount(p0) == 2  # original owner + the acquirer
    # re-inserting an indexed key is a no-op (no double pin)
    assert not cache.insert(toks[:4], p0)
    cache.check()


def test_prefix_cache_partial_tail_exact_prompt_only():
    spec = _spec(num_pages=8, page_size=4)
    al = PageAllocator(spec.num_pages)
    cache = PrefixCache(spec, al)
    toks = list(range(6))  # 1 full page + 2-token tail
    p0, p1 = al.alloc(), al.alloc()
    cache.insert(toks[:4], p0)
    cache.insert(toks[:6], p1)  # the tail, keyed by the EXACT prompt
    assert cache.match_len(toks) == 6
    # a longer prompt sharing the head matches only the full page: the
    # tail key covers exactly 6 tokens, not "6 of my 8"
    assert cache.match_len(toks + [7, 8]) == 4
    assert cache.match_len(toks[:5]) == 4


def test_prefix_cache_eviction_never_frees_reader_held_pages():
    spec = _spec(num_pages=8, page_size=4)
    al = PageAllocator(spec.num_pages)
    cache = PrefixCache(spec, al)
    held, loose = al.alloc(), al.alloc()
    cache.insert([1, 2, 3, 4], held)
    cache.insert([5, 6, 7, 8], loose)
    al.free([loose])  # publisher released -> zero readers, LRU-oldest
    # `held` keeps its reader; eviction must pick `loose` even though
    # `held` is older in LRU order after a touch
    cache.acquire([5, 6, 7, 8])        # touch loose: now MRU + a reader
    al.free([loose])                   # release the touch again
    assert cache.evict_one() == loose  # held is skipped: reader-held
    assert al.refcount(held) == 1 and al.is_pinned(held)
    assert cache.evict_one() is None   # nothing evictable remains
    al.free([held])
    assert cache.evict_one() == held   # now it can go
    assert al.free_count == spec.n_usable_pages
    cache.check()


def test_prefix_cache_flush_is_total():
    spec = _spec(num_pages=8, page_size=4)
    al = PageAllocator(spec.num_pages)
    cache = PrefixCache(spec, al)
    a, b = al.alloc(), al.alloc()
    cache.insert([1, 2, 3, 4], a)
    cache.insert([9, 9, 9, 9], b)
    al.free([b])                       # b: index pin only
    assert cache.flush() == 2
    assert len(cache) == 0
    assert al.free_count == spec.n_usable_pages - 1  # a still read
    al.free([a])
    assert al.free_count == spec.n_usable_pages
    al.check()


def test_prefix_cache_check_red():
    spec = _spec(num_pages=8, page_size=4)
    al = PageAllocator(spec.num_pages)
    cache = PrefixCache(spec, al)
    p = al.alloc()
    cache.insert([1, 2, 3, 4], p)
    al._pinned.discard(p)  # corrupt: entry lost its pin
    with pytest.raises(AssertionError, match="pin"):
        cache.check()


# ---------------------------------------------------------------------------
# scheduler integration: hit cursor, COW, invariants
# ---------------------------------------------------------------------------

def _drive_prefill(sched, steps=100):
    """Advance a standalone scheduler like the engine would."""
    for _ in range(steps):
        if sched.idle:
            return
        sched.admit()
        sched.ensure_capacity()
        sched.take_forks()
        sched.take_dirty_slots()
        served = sched.running()
        sched.advance([i for i, _ in served])
        for i, run in served:
            if not run.prefilling:
                run.req.out_tokens.append(0)
            if run.req.done:
                sched.evict(i)
        sched.check_invariants()


def test_scheduler_cache_hit_starts_past_cached_head():
    spec = _spec(num_pages=10, page_size=4, pages_per_seq=6)
    sched = Scheduler(spec, n_slots=1, max_prompt_len=spec.max_seq_len,
                      prefix_cache=True)
    prompt = list(range(10))  # 2 full pages + 2-token tail
    r1 = Request(prompt=list(prompt), max_new_tokens=2)
    sched.submit(r1)
    _drive_prefill(sched)
    assert r1.cached_tokens == 0
    # pages for the full prompt are now indexed (2 full + exact tail)
    assert sched.cache.match_len(prompt) == 10
    r2 = Request(prompt=list(prompt), max_new_tokens=2)
    sched.submit(r2)
    sched.admit()
    (_, run), = sched.running()
    # full-prompt hit, capped: the FINAL prompt token is recomputed
    assert run.pos == 9 and run.cached_tokens == 9
    assert len(run.pages) == 3
    sched.check_invariants()
    # the write at pos 9 lands inside the shared tail -> COW fork
    sched.ensure_capacity()
    forks = sched.take_forks()
    assert len(forks) == 1
    src, dst = forks[0]
    assert src != dst and dst in run.pages and src not in run.pages
    sched.check_invariants()


def test_scheduler_invariants_red_refcount_mismatch():
    spec = _spec(num_pages=10, page_size=4)
    sched = Scheduler(spec, n_slots=1, max_prompt_len=spec.max_seq_len,
                      prefix_cache=True)
    sched.submit(Request(prompt=list(range(6)), max_new_tokens=2))
    sched.admit()
    sched.ensure_capacity()
    (_, run), = sched.running()
    # seed a violation: an extra reader nobody accounts for
    sched.allocator.share(run.pages[0])
    with pytest.raises(AssertionError, match="refcount"):
        sched.check_invariants()


# ---------------------------------------------------------------------------
# token identity: the oracle, both ways
# ---------------------------------------------------------------------------

def _mk_staggered(rng, lens, max_new=6, stride=3):
    return [
        Request(prompt=[int(t) for t in rng.integers(0, 128, size=L)],
                max_new_tokens=max_new, arrival_step=stride * i)
        for i, L in enumerate(lens)
    ]


@pytest.mark.parametrize("chunk", [2, 5, 16])
def test_chunked_prefill_token_identical(tiny_model, chunk):
    """Acceptance: chunked prefill (any chunk size) over a staggered
    continuous-batching trace is token-identical to token-at-a-time
    prefill — and finishes in fewer steps. The chunk=2 case is also
    grounded against the dense reference directly (token-at-a-time
    itself is dense-grounded in tests/test_serving.py)."""
    cfg, params = tiny_model
    rng = np.random.default_rng(42)
    reqs = _mk_staggered(rng, (5, 9, 3, 12, 7))
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=12,
                        max_prompt_len=16, prefill_chunk=chunk)
    out = eng.generate(reqs, max_steps=1000)
    eng.scheduler.check_invariants()
    assert eng.scheduler.allocator.used_count == 0
    if chunk == 2:
        for r in reqs:
            assert out[r.rid] == reference_decode(
                cfg, params, r.prompt, r.max_new_tokens)
    base = ServingEngine(cfg, params, n_slots=2, num_pages=12,
                        max_prompt_len=16, prefill_chunk=1)
    rng = np.random.default_rng(42)
    ref_reqs = _mk_staggered(rng, (5, 9, 3, 12, 7))
    out1 = base.generate(ref_reqs, max_steps=1000)
    for r, rr in zip(reqs, ref_reqs):
        assert out[r.rid] == out1[rr.rid]
    assert eng.last_stats["steps"] < base.last_stats["steps"]


def test_chunked_prefill_identical_under_preemption(tiny_model):
    """Chunk + tiny pool: recompute-mode preemption mid-chunked-prefill
    must not change a single token."""
    cfg, params = tiny_model
    rng = np.random.default_rng(7)
    reqs = [Request(prompt=[int(t) for t in rng.integers(0, 128, size=L)],
                    max_new_tokens=8, arrival_step=i)
            for i, L in enumerate((14, 11, 13, 9))]
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=4,
                        max_prompt_len=16, prefill_chunk=4)
    out = eng.generate(reqs, max_steps=2000)
    eng.scheduler.check_invariants()
    assert eng.last_stats["preemptions"] > 0
    for r in reqs:
        assert out[r.rid] == reference_decode(cfg, params, r.prompt,
                                              r.max_new_tokens)


def test_cache_hit_decode_byte_identical_to_cold(tiny_model):
    """Acceptance: a cache-hit decode is identical to the cold decode
    of the same request — shared heads, an exact-duplicate prompt (the
    COW path), warm stats prove the hits actually happened."""
    cfg, params = tiny_model
    rng = np.random.default_rng(11)
    head = [int(t) for t in rng.integers(0, 128, size=32)]
    prompts = [head + [int(t) for t in rng.integers(0, 128, size=4)],
               head + [int(t) for t in rng.integers(0, 128, size=7)],
               list(head)]
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=24,
                        prefill_chunk=4)
    cold = [Request(prompt=list(p), max_new_tokens=6) for p in prompts]
    out_cold = eng.generate(cold, max_steps=2000)
    cold_steps = eng.last_stats["steps"]
    warm = [Request(prompt=list(p), max_new_tokens=6) for p in prompts]
    out_warm = eng.generate(warm, max_steps=2000)
    eng.scheduler.check_invariants()
    st = eng.last_stats["prefix_cache"]
    assert st["hits"] == len(prompts) and st["hit_rate"] > 0
    assert st["hit_tokens"] >= 3 * 32
    assert st["cached_prompt_tokens"] > 0
    assert eng.last_stats["steps"] < cold_steps
    assert eng.scheduler.allocator.used_count == 0
    for p, c, w in zip(prompts, cold, warm):
        ref = reference_decode(cfg, params, p, 6)
        assert out_cold[c.rid] == ref
        assert out_warm[w.rid] == ref


def test_cache_and_chunk_identity_under_preempt_evict_churn(tiny_model):
    """Acceptance: chunk x cache x tiny pool x staggered arrivals —
    preemptions, cache evictions under pressure, COW forks, replay
    self-hits — every request still token-identical, invariants clean,
    zero reader-held pages.

    Oracle: a chunk=1, cache-off engine over the same traces (itself
    pinned to the dense reference by the existing identity tests) —
    one compiled program instead of per-token eager dense forwards, so
    the randomized sweep stays cheap under full-suite load."""
    cfg, params = tiny_model

    def mk(seed):
        rng = np.random.default_rng(seed)
        lens = rng.integers(3, 15, size=6)
        return [Request(
            prompt=[int(t) for t in rng.integers(0, 128, size=int(L))],
            max_new_tokens=int(rng.integers(2, 9)),
            arrival_step=int(rng.integers(0, 12)))
            for L in lens]

    for seed in (3, 7, 19):
        base = ServingEngine(cfg, params, n_slots=2, num_pages=12,
                             max_prompt_len=16, prefill_chunk=1,
                             prefix_cache=False)
        ref_reqs = mk(seed)
        ref_out = base.generate(ref_reqs, max_steps=4000)
        eng = ServingEngine(cfg, params, n_slots=2, num_pages=4,
                            max_prompt_len=16, prefill_chunk=3)
        reqs = mk(seed)
        out = eng.generate(reqs, max_steps=4000)
        eng.scheduler.check_invariants()
        assert eng.scheduler.allocator.used_count == 0
        for ref_r, r in zip(ref_reqs, reqs):
            assert r.prompt == ref_r.prompt
            assert out[r.rid] == ref_out[ref_r.rid], (seed, r.rid)


# ---------------------------------------------------------------------------
# eviction-under-pressure chaos property trace
# ---------------------------------------------------------------------------

def test_chaos_eviction_under_pressure_property_trace(tiny_model):
    """The satellite contract: with stolen allocations AND forced cache
    evictions firing mid-trace, ``check_invariants()`` (refcount
    cross-checks included) passes after EVERY step, eviction never
    frees a page a live reader holds (that is what the invariants
    assert), every request completes token-identically, and the trace
    drains to zero reader-held pages. Oracle: the chunk=1, cache-off
    engine over the same requests (itself pinned to the dense
    reference by the smaller identity tests)."""
    from apex_tpu.resilience import ServingChaos

    cfg, params = tiny_model

    def mk(seed):
        rng = np.random.default_rng(seed)
        return [Request(
            prompt=[int(t) for t in rng.integers(0, 128, size=int(L))],
            max_new_tokens=5, arrival_step=int(rng.integers(0, 8)))
            for L in rng.integers(4, 14, size=5)]

    for seed in (0, 5):
        base = ServingEngine(cfg, params, n_slots=2, num_pages=12,
                             max_prompt_len=16, prefill_chunk=1,
                             prefix_cache=False)
        ref_reqs = mk(seed)
        ref_out = base.generate(ref_reqs, max_steps=3000)
        reqs = mk(seed)
        chaos = (ServingChaos()
                 .fail_allocs(3)
                 .evict_prefix_cache(2)
                 .evict_prefix_cache(2))
        eng = ServingEngine(cfg, params, n_slots=2, num_pages=5,
                            max_prompt_len=16, prefill_chunk=3,
                            chaos=chaos)
        pending = sorted(reqs, key=lambda r: (r.arrival_step, r.rid))
        step = 0
        while pending or not eng.scheduler.idle:
            while pending and pending[0].arrival_step <= step:
                eng.try_submit(pending.pop(0))
            if not eng.scheduler.idle:
                eng.run_step()
            eng.scheduler.check_invariants()
            step += 1
            assert step < 3000, "chaos trace did not terminate"
        assert any(f[0] == "cache_evict" for f in chaos.faults_fired)
        assert eng.scheduler.allocator.used_count == 0
        for ref_r, r in zip(ref_reqs, reqs):
            assert r.status is RequestStatus.COMPLETED
            assert list(r.out_tokens) == ref_out[ref_r.rid], \
                (seed, r.rid)


def test_poisoned_prefill_pages_never_published(tiny_model):
    """Review regression: a slot whose logits go non-finite wrote
    non-finite K/V that same step — the pages it completed this step
    must NOT be published to the prefix index (a later request sharing
    the prefix would decode from NaN K/V and cascade the quarantine).
    The quarantined slot is excluded from advance() before publication
    runs; a subsequent identical-prompt request must decode cold,
    token-identical to the dense reference."""
    from apex_tpu.resilience import ServingChaos

    cfg, params = tiny_model
    rng = np.random.default_rng(21)
    # 20-token prompt = page 0 (16) + partial tail; chunk 16 completes
    # page 0 in the victim's FIRST step — exactly when poison fires
    prompt = [int(t) for t in rng.integers(0, 128, size=20)]
    victim = Request(prompt=list(prompt), max_new_tokens=4)
    chaos = ServingChaos().poison_request(victim.rid)
    eng = ServingEngine(cfg, params, n_slots=1, num_pages=8,
                        max_prompt_len=24, prefill_chunk=16,
                        chaos=chaos)
    eng.generate([victim], max_steps=200)
    assert victim.status is RequestStatus.FAILED
    # nothing of the poisoned prefill may be resident
    assert eng.prefix_cache.match_len(prompt) == 0
    eng.scheduler.check_invariants()
    retry = Request(prompt=list(prompt), max_new_tokens=4)
    out = eng.generate([retry], max_steps=200)
    ref = reference_decode(cfg, params, prompt, 4)
    assert out[retry.rid] == ref
    assert eng.scheduler.allocator.used_count == 0


# ---------------------------------------------------------------------------
# admission / routing satellites
# ---------------------------------------------------------------------------

def test_admission_feasibility_counts_only_uncached_tokens(tiny_model):
    """A request whose deadline is infeasible against its FULL prompt
    but feasible against its uncached head must be refused cold and
    admitted warm — admission bills only the prefill actually owed."""
    from apex_tpu.serving import AdmissionConfig, RejectionCode

    cfg, params = tiny_model
    rng = np.random.default_rng(2)
    prompt = [int(t) for t in rng.integers(0, 128, size=32)]

    def engine():
        return ServingEngine(
            cfg, params, n_slots=2, num_pages=24, prefill_chunk=1,
            admission=AdmissionConfig(step_time_init_s=0.01))

    # 32 prefill steps * 10ms = 320ms lower bound > 100ms budget
    hurried = Request(prompt=list(prompt), max_new_tokens=2,
                      ttft_budget_ms=100.0)
    cold = engine()
    reason = cold.try_submit(hurried)
    assert reason is not None
    assert reason.code is RejectionCode.DEADLINE_INFEASIBLE
    warm = engine()
    warm.generate([Request(prompt=list(prompt), max_new_tokens=2)],
                  max_steps=200)
    # cached head: ~1 uncached token -> ~10ms << 100ms budget
    hurried2 = Request(prompt=list(prompt), max_new_tokens=2,
                       ttft_budget_ms=100.0)
    assert warm._prefill_steps(hurried2) <= 2
    assert warm.try_submit(hurried2) is None


def test_probe_uses_post_hit_prefill_estimate(tiny_model):
    """The router cost satellite: est steps-to-first-token shrink once
    the prompt head is cached, and shrink further with a larger
    prefill chunk."""
    cfg, params = tiny_model
    rng = np.random.default_rng(4)
    prompt = [int(t) for t in rng.integers(0, 128, size=32)]
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=24,
                        prefill_chunk=1)
    probe_req = Request(prompt=list(prompt), max_new_tokens=2)
    _, cold_est = eng.probe(probe_req)
    eng.generate([Request(prompt=list(prompt), max_new_tokens=2)],
                 max_steps=200)
    _, warm_est = eng.probe(probe_req)
    assert warm_est < cold_est
    chunky = ServingEngine(cfg, params, n_slots=2, num_pages=24,
                           prefill_chunk=8)
    _, chunk_est = chunky.probe(probe_req)
    assert chunk_est < cold_est


def test_summarize_splits_prefill_and_decode_tokens(tiny_model):
    """The small-fix satellite: prefill vs decode token counts are
    separate (steps conflated them), and they reconcile with the trace
    — prefill_tokens = prompt tokens actually computed (cached head
    excluded), decode_tokens = generated tokens beyond each first."""
    cfg, params = tiny_model
    rng = np.random.default_rng(9)
    prompt = [int(t) for t in rng.integers(0, 128, size=10)]
    eng = ServingEngine(cfg, params, n_slots=1, num_pages=8,
                        max_prompt_len=16, prefill_chunk=3)
    eng.generate([Request(prompt=list(prompt), max_new_tokens=4)],
                 max_steps=200)
    st = eng.last_stats
    # 10 prompt tokens consumed in ceil(10/3)=4 prefill slot-steps;
    # the first generated token is emitted by the LAST prefill step,
    # the remaining 3 by decode steps
    assert st["prefill_tokens"] == 10
    assert st["prefill_slot_steps"] == 4
    assert st["decode_tokens"] == 3
    assert st["generated_tokens"] == 4
    assert st["prefill_chunk"] == 3
    assert st["cached_prompt_tokens"] == 0
    assert st["prefix_cache"]["hit_rate"] is None \
        or st["prefix_cache"]["hits"] == 0
    # warm re-run: the cached head moves work out of prefill_tokens
    eng.generate([Request(prompt=list(prompt), max_new_tokens=4)],
                 max_steps=200)
    st2 = eng.last_stats
    assert st2["cached_prompt_tokens"] == 9
    assert st2["prefill_tokens"] == 1
    assert st2["prefix_cache"]["hits"] == 1


def test_chunk_step_audits_clean(tiny_model):
    """Both jitted programs (1-token decode + chunked prefill) pass the
    PR-4 auditor: KV/slot/metrics donated, cond-gated callbacks only."""
    from apex_tpu import telemetry

    cfg, params = tiny_model
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=6,
                        max_prompt_len=16, prefill_chunk=4,
                        telemetry_every=4,
                        sink=telemetry.RingBufferRecorder())
    report = eng.audit()  # audits decode AND chunk steps; raises on error
    assert report.ok


# ---------------------------------------------------------------------------
# weight hot-swap: stale cache entries are impossible
# ---------------------------------------------------------------------------

def test_stale_prefix_cache_cannot_survive_weight_swap(tiny_model):
    """RED contract: K/V cached under old weights MUST NOT survive a
    rolling-update weight swap. ``try_join`` goes through
    ``swap_params`` which flushes the per-replica cache — post-swap
    traffic with the SAME prompts decodes per the NEW weights (if a
    stale entry survived, the emitted tokens would match the old
    model's and this test would fail)."""
    from apex_tpu.serving import ReplicaFleet

    cfg, params = tiny_model
    params2 = jax.tree_util.tree_map(lambda x: x, params)
    params2["embedding"]["position"] = (
        params["embedding"]["position"] * 0.5)
    rng = np.random.default_rng(13)
    prompts = [[int(t) for t in rng.integers(0, 128, size=20)]
               for _ in range(2)]
    fleet = ReplicaFleet(cfg, params, n_replicas=2, n_slots=2,
                         num_pages=16, prefill_chunk=4)
    phase1 = [Request(prompt=list(p), max_new_tokens=4)
              for p in prompts for _ in range(2)]
    fleet.generate(phase1, max_steps=2000)
    assert any(len(rep.engine.prefix_cache) > 0
               for rep in fleet.replicas)
    fleet.schedule_rolling_update(params2)
    fleet.generate([], max_steps=200)  # drain the swap wave
    assert fleet.rolling_update_done
    for rep in fleet.replicas:
        assert len(rep.engine.prefix_cache) == 0, (
            f"replica {rep.idx}: stale prefix-cache entries survived "
            "the weight swap")
    # SAME prompts post-swap: must decode per the NEW weights
    phase2 = [Request(prompt=list(p), max_new_tokens=4) for p in prompts]
    out2 = fleet.generate(phase2, max_steps=2000)
    for p, r in zip(prompts, phase2):
        ref_new = reference_decode(cfg, params2, p, 4)
        assert out2[r.rid] == ref_new
    fleet.check_invariants()
    assert fleet.page_leaks() == 0


def test_engine_swap_params_flushes_cache(tiny_model):
    cfg, params = tiny_model
    rng = np.random.default_rng(17)
    prompt = [int(t) for t in rng.integers(0, 128, size=20)]
    eng = ServingEngine(cfg, params, n_slots=1, num_pages=8,
                        prefill_chunk=4)
    eng.generate([Request(prompt=list(prompt), max_new_tokens=3)],
                 max_steps=200)
    assert len(eng.prefix_cache) > 0
    assert eng.scheduler.allocator.cached_count > 0
    eng.swap_params(params)
    assert len(eng.prefix_cache) == 0
    assert eng.scheduler.allocator.cached_count == 0
    eng.scheduler.check_invariants()


def test_restarted_replica_gets_fresh_cache(tiny_model):
    """rebuild_like / recover_from build a NEW engine: a fresh pool and
    a fresh (empty) prefix cache — the restart path cannot carry
    stale entries by construction."""
    cfg, params = tiny_model
    rng = np.random.default_rng(19)
    eng = ServingEngine(cfg, params, n_slots=1, num_pages=8,
                        prefill_chunk=4)
    eng.generate([Request(
        prompt=[int(t) for t in rng.integers(0, 128, size=20)],
        max_new_tokens=3)], max_steps=200)
    assert len(eng.prefix_cache) > 0
    fresh = ServingEngine.rebuild_like(eng)
    assert fresh.prefix_cache is not None
    assert len(fresh.prefix_cache) == 0
    assert fresh.prefill_chunk == eng.prefill_chunk


# ---------------------------------------------------------------------------
# CI wiring: serving_check legs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("leg", ["chunked_prefill_identity",
                                 "prefix_hit_identity"])
def test_serving_check_prefix_legs_pass(leg):
    import tools.serving_check as sc

    assert sc.main(["--self", "--check", leg, "--json"]) == 0
