"""Speculative decoding + carried sampling (ISSUE-13).

The two halves of pushing decode below one model pass per token, held
to the same oracle discipline as everything before them:

- **carried sampling** (``serving.sampling``) — temperature/top-k/top-p
  with the stateless ``(seed, rid, position)`` hash-counter PRNG:
  greedy stays bit-identical to argmax, sampled decode is BYTE-
  identical to the seeded dense reference
  (``reference_sample_decode``), and draws survive preemption replay
  because they are keyed by position, not by an RNG state chain;
- **speculative decoding** (``serving.spec_decode``) — on-device n-gram
  drafting over each slot's own history, one chunk-shaped target pass
  verifying ``spec_k + 1`` positions, in-jit longest-matched-prefix
  accept, and page-bookkeeping rollback of the rejected tail through
  the SAME ``Scheduler.rollback_kv`` helper the PR-12 cache-pressure
  path uses (seeded-violation red test included);
- the robustness interplay: invariants after every step of a chaos
  trace with speculation + sampling armed, zero page leaks, survivor
  token identity, a quarantined slot's drafted pages never published,
  and admission/router billing UNCHANGED (worst-case offered tokens —
  speculation can only improve feasibility, never overcommit).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.serving import (
    Request,
    RequestStatus,
    SamplingParams,
    Scheduler,
    ServingEngine,
    ngram_propose,
    reference_decode,
    reference_sample_decode,
    sample_tokens,
)
from apex_tpu.transformer.testing import GPTConfig, init_gpt_params


def _tiny_cfg(dtype=jnp.float32, max_pos=64):
    return GPTConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=max_pos,
        hidden_dropout=0.0, attention_dropout=0.0,
        params_dtype=jnp.float32, compute_dtype=dtype)


@pytest.fixture(scope="module", autouse=True)
def _shed_compile_caches():
    """Many small engine programs compile in this module; shed the
    executables the preceding files accumulated (the full-suite CPU
    lane runs close to its memory ceiling)."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def tiny_model():
    cfg = _tiny_cfg()
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    params["embedding"]["position"] = params["embedding"]["position"] * 40.0
    return cfg, params


@pytest.fixture(scope="module")
def cyclic_model():
    """Position-independent weights: greedy decode falls into a cycle,
    so the n-gram draft actually accepts — the accept-rate half of the
    acceptance criteria needs repetition to exist."""
    cfg = _tiny_cfg(max_pos=128)
    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    params["embedding"]["position"] = params["embedding"]["position"] * 0.0
    return cfg, params


# ---------------------------------------------------------------------------
# sampling: the carried stateless PRNG
# ---------------------------------------------------------------------------

def _policy_arrays(sp: SamplingParams, rid: int, pos: int):
    return (jnp.asarray([sp.temperature], jnp.float32),
            jnp.asarray([sp.top_k], jnp.int32),
            jnp.asarray([sp.top_p], jnp.float32),
            jnp.asarray([sp.seed], jnp.int32),
            jnp.asarray([rid], jnp.int32),
            jnp.asarray([pos], jnp.int32))


def test_sample_tokens_greedy_is_argmax():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(5, 33)), jnp.float32)
    B = 5
    out = sample_tokens(logits, jnp.zeros(B), jnp.zeros(B, jnp.int32),
                        jnp.ones(B), jnp.zeros(B, jnp.int32),
                        jnp.arange(B, dtype=jnp.int32),
                        jnp.arange(B, dtype=jnp.int32))
    assert (np.asarray(out) == np.asarray(jnp.argmax(logits, -1))).all()


def test_sample_tokens_deterministic_and_row_independent():
    """The identity precondition: a batched row draws exactly what the
    [1, V] reference row draws (sorting/cumsum/argmax are all
    row-local), and the draw is a pure function of its key."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(4, 50)), jnp.float32)
    t = jnp.full(4, 0.8)
    k = jnp.asarray([0, 7, 0, 3], jnp.int32)
    p = jnp.asarray([1.0, 0.9, 0.7, 1.0], jnp.float32)
    s = jnp.asarray([3, 3, 5, 5], jnp.int32)
    r = jnp.asarray([10, 11, 10, 11], jnp.int32)
    pos = jnp.asarray([2, 2, 9, 9], jnp.int32)
    a = sample_tokens(logits, t, k, p, s, r, pos)
    b = sample_tokens(logits, t, k, p, s, r, pos)
    assert (np.asarray(a) == np.asarray(b)).all()
    for i in range(4):
        row = sample_tokens(logits[i:i + 1], t[i:i + 1], k[i:i + 1],
                            p[i:i + 1], s[i:i + 1], r[i:i + 1],
                            pos[i:i + 1])
        assert int(row[0]) == int(a[i])


def test_sample_tokens_respects_topk_and_topp():
    rng = np.random.default_rng(2)
    row = rng.normal(size=(1, 64)).astype(np.float32)
    top3 = set(np.argsort(-row[0])[:3].tolist())
    # one batched call = 150 independent positions of the same row
    R = 150
    logits = jnp.asarray(np.repeat(row, R, axis=0))
    toks = sample_tokens(logits, jnp.full(R, 1.5),
                         jnp.full(R, 3, jnp.int32), jnp.ones(R),
                         jnp.zeros(R, jnp.int32),
                         jnp.zeros(R, jnp.int32),
                         jnp.arange(R, dtype=jnp.int32))
    seen = set(np.asarray(toks).tolist())
    assert seen <= top3 and len(seen) > 1
    # a sharply peaked distribution under small top_p is greedy
    sharp = jnp.zeros((20, 64)).at[:, 5].add(10.0)
    toks = sample_tokens(sharp, jnp.full(20, 1.0),
                         jnp.zeros(20, jnp.int32),
                         jnp.full(20, 0.5, jnp.float32),
                         jnp.zeros(20, jnp.int32),
                         jnp.zeros(20, jnp.int32),
                         jnp.arange(20, dtype=jnp.int32))
    assert (np.asarray(toks) == 5).all()
    # top_k=1 is greedy at any temperature
    tok = sample_tokens(jnp.asarray(row), jnp.full(1, 2.0),
                        jnp.asarray([1], jnp.int32), jnp.ones(1),
                        jnp.zeros(1, jnp.int32), jnp.zeros(1, jnp.int32),
                        jnp.asarray([0], jnp.int32))
    assert int(tok[0]) == int(np.argmax(row[0]))


def test_topk_prefilter_matches_full_sort_per_row():
    """ISSUE-14 satellite: the lax.top_k prefilter and the full-sort
    fallback must be bitwise interchangeable per row — a batch whose
    OTHER rows force the deep path returns identical tokens for a row
    the prefix already covered (the engine-vs-reference byte-identity
    contract cannot depend on batch composition)."""
    from apex_tpu.serving.sampling import TOP_FILTER_WIDTH, _thresholds

    rng = np.random.default_rng(11)
    V = 4 * TOP_FILTER_WIDTH
    logits = jnp.asarray(rng.normal(size=(3, V)).astype(np.float32))
    temps = jnp.full(3, 1.0)
    # row 0: ordinary nucleus config (prefix covers it); row 1: top_k
    # beyond the prefix width (forces the fallback); row 2: near-flat
    # logits at high temperature with p close to 1 (top-width mass
    # cannot reach p — the other fallback trigger)
    flat_row = jnp.asarray(
        0.01 * rng.normal(size=(V,)).astype(np.float32))
    logits = logits.at[2].set(flat_row)
    top_ks = jnp.asarray([8, TOP_FILTER_WIDTH + 7, 0], jnp.int32)
    top_ps = jnp.asarray([0.9, 1.0, 0.999], jnp.float32)
    seeds = jnp.zeros(3, jnp.int32)
    rids = jnp.asarray([4, 5, 6], jnp.int32)
    pos = jnp.asarray([10, 11, 12], jnp.int32)

    # rows 1 and 2 genuinely trigger the deep path; row 0 does not
    scaled = logits / temps[:, None]
    _, _, covered = _thresholds(
        jax.lax.top_k(scaled, TOP_FILTER_WIDTH)[0], scaled, top_ks,
        top_ps)
    assert np.asarray(covered).tolist() == [True, False, False]

    batched = np.asarray(sample_tokens(
        logits, temps, top_ks, top_ps, seeds, rids, pos))
    for i in range(3):
        single = np.asarray(sample_tokens(
            logits[i:i + 1], temps[i:i + 1], top_ks[i:i + 1],
            top_ps[i:i + 1], seeds[i:i + 1], rids[i:i + 1],
            pos[i:i + 1]))
        assert batched[i] == single[0], f"row {i} depends on the batch"
    # the top_k>width row still respects its filter
    topk_set = set(np.argsort(-np.asarray(logits[1]))
                   [:TOP_FILTER_WIDTH + 7].tolist())
    assert int(batched[1]) in topk_set


def test_sample_tokens_key_separation():
    """Different (seed | rid | position) keys decorrelate draws — the
    carried-PRNG contract that makes two same-seed requests sample
    independent streams."""
    rng = np.random.default_rng(3)
    row = (rng.normal(size=(1, 40)) * 0.1).astype(np.float32)
    R = 24
    logits = jnp.asarray(np.repeat(row, R, axis=0))

    def draws(seed, rid, base_pos):
        return np.asarray(sample_tokens(
            logits, jnp.full(R, 1.5), jnp.zeros(R, jnp.int32),
            jnp.ones(R), jnp.full(R, seed, jnp.int32),
            jnp.full(R, rid, jnp.int32),
            base_pos + jnp.arange(R, dtype=jnp.int32))).tolist()

    base = draws(0, 0, 0)
    assert draws(0, 0, 0) == base              # pure function of the key
    assert draws(1, 0, 0) != base              # seed lane
    assert draws(0, 1, 0) != base              # rid lane
    assert draws(0, 0, 100) != base            # position lane
    assert len(set(base)) > 1                  # actually random-ish


def test_sampling_params_validation():
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(top_k=-1)
    assert SamplingParams().is_greedy
    assert not SamplingParams(temperature=0.5).is_greedy


def test_engine_spec_knob_validation(tiny_model):
    """Bad speculation knobs fail at construction with a clear error,
    not deep inside the first traced step."""
    cfg, params = tiny_model
    with pytest.raises(ValueError, match="spec_ngram"):
        ServingEngine(cfg, params, n_slots=1, num_pages=4,
                      spec_k=2, spec_ngram=0)
    with pytest.raises(ValueError, match="spec_ngram"):
        ServingEngine(cfg, params, n_slots=1, num_pages=4,
                      spec_k=2, spec_ngram=5000)
    with pytest.raises(ValueError, match="spec_k"):
        ServingEngine(cfg, params, n_slots=1, num_pages=4,
                      spec_k=5000)


# ---------------------------------------------------------------------------
# n-gram drafting
# ---------------------------------------------------------------------------

def test_ngram_propose_matches_most_recent_occurrence():
    hist = jnp.asarray([[1, 2, 3, 4, 1, 2, 3, 9, 1, 2, 0, 0, 0]],
                       jnp.int32)  # known: 1 2 3 4 1 2 3 9 1 2
    drafts, n = ngram_propose(hist, jnp.asarray([10]), k=3, n=2)
    # tail (1,2) last matched at s=4 -> continuation 3, 9, 1
    assert list(np.asarray(drafts[0])) == [3, 9, 1]
    assert int(n[0]) == 3


def test_ngram_propose_no_match_and_short_history():
    hist = jnp.asarray([[1, 2, 3, 4, 5, 0, 0]], jnp.int32)
    drafts, n = ngram_propose(hist, jnp.asarray([5]), k=3, n=2)
    assert int(n[0]) == 0
    # history shorter than the n-gram: no drafting, no crash
    drafts, n = ngram_propose(hist, jnp.asarray([2]), k=3, n=3)
    assert int(n[0]) == 0
    # disabled row (len 0)
    drafts, n = ngram_propose(hist, jnp.asarray([0]), k=3, n=2)
    assert int(n[0]) == 0


def test_ngram_propose_caps_at_history_end():
    # tail (7, 8) matches at s=0; the continuation (9, 7, 8) runs to
    # the END of the known history and stops there — never past it
    hist = jnp.asarray([[7, 8, 9, 7, 8, 0]], jnp.int32)
    drafts, n = ngram_propose(hist, jnp.asarray([5]), k=4, n=2)
    assert int(n[0]) == 3
    assert list(np.asarray(drafts[0])) == [9, 7, 8, 0]  # zero-padded
    # shrinking the window: k caps the proposal
    drafts, n = ngram_propose(hist, jnp.asarray([5]), k=2, n=2)
    assert int(n[0]) == 2
    assert list(np.asarray(drafts[0])) == [9, 7]


# ---------------------------------------------------------------------------
# rollback_kv: the shared un-write helper (+ seeded-violation red test)
# ---------------------------------------------------------------------------

def _sched_with_slot(n_tokens, spec_k=0, page_size=16):
    from apex_tpu.serving import PagedKVSpec

    spec = PagedKVSpec(1, 4, 64, page_size=page_size, num_pages=8,
                       pages_per_seq=4)
    sched = Scheduler(spec, 1, max_prompt_len=48, spec_k=spec_k)
    req = Request(prompt=list(range(1, 9)), max_new_tokens=40)
    sched.submit(req)
    sched.admit()
    run = sched.slots[0]
    run.pos = n_tokens
    run.pages = [sched.allocator.alloc()
                 for _ in range(spec.pages_for(max(n_tokens, 1)))]
    return sched, run


def test_rollback_kv_frees_speculative_tail_pages():
    """The spec-decode rejection path: pages allocated for the
    worst-case draft write-ahead are returned once the accepted run is
    known, and the accounting still balances."""
    sched, run = _sched_with_slot(4)
    # simulate worst-case paging for pos + 1 + k = 4 + 1 + 36: grab 2
    # extra pages past the cursor's page
    extra = [sched.allocator.alloc(), sched.allocator.alloc()]
    run.pages.extend(extra)
    free_before = sched.allocator.free_count
    sched.rollback_kv(0, run, run.pos)
    assert len(run.pages) == sched.spec.pages_for(run.pos)
    assert sched.allocator.free_count == free_before + 2
    assert not sched.take_dirty_slots()  # cursor unmoved: no resync
    sched.check_invariants()


def test_rollback_kv_rewinds_cursor_and_marks_dirty():
    sched, run = _sched_with_slot(40)
    assert len(run.pages) == 3
    sched.rollback_kv(0, run, 16, keep_pages=1)
    assert run.pos == 16 and len(run.pages) == 1
    assert sched.take_dirty_slots() == {0}
    sched.check_invariants()


def test_rollback_kv_seeded_violation_red():
    """Red test: un-writing WITHOUT the helper (dropping the pages
    from the slot's list but never releasing the holds) leaks — the
    refcount cross-check in check_invariants must catch it."""
    sched, run = _sched_with_slot(40)
    run.pages = run.pages[:1]  # the bug: no allocator.free / helper
    with pytest.raises(AssertionError, match="refcount|reader"):
        sched.check_invariants()


def test_release_tail_red_on_double_release():
    from apex_tpu.serving import PageAllocator

    alloc = PageAllocator(6)
    pages = [alloc.alloc() for _ in range(3)]
    kept = alloc.release_tail(pages, 1)
    assert kept == pages[:1]
    with pytest.raises(ValueError, match="double-free|foreign"):
        alloc.release_tail(pages, 1)  # tail holds already dropped
    with pytest.raises(ValueError, match="keep"):
        alloc.release_tail(pages, -1)


# ---------------------------------------------------------------------------
# greedy spec-decode: the lossless contract
# ---------------------------------------------------------------------------

def _mk_staggered(cfg, seed=7, lens=(14, 11, 13, 9), max_new=8):
    rng = np.random.default_rng(seed)
    return [
        Request(prompt=[int(t) for t in
                        rng.integers(0, cfg.vocab_size, size=L)],
                max_new_tokens=max_new, arrival_step=2 * i)
        for i, L in enumerate(lens)
    ]


@pytest.fixture(scope="module")
def staggered_refs(tiny_model):
    """Dense greedy references for the staggered trace, computed once
    (reference_decode recompiles per prefix length — the expensive
    half of every identity test)."""
    cfg, params = tiny_model
    return [reference_decode(cfg, params, r.prompt, r.max_new_tokens)
            for r in _mk_staggered(cfg)]


def test_spec_greedy_token_identity_staggered(tiny_model,
                                              staggered_refs):
    """spec_k > 0 greedy == plain greedy == dense reference across the
    staggered continuous-batching trace on a tiny pool (shared slots,
    preemption pressure)."""
    cfg, params = tiny_model
    refs = staggered_refs
    for k in (1, 3):
        reqs = _mk_staggered(cfg)
        eng = ServingEngine(cfg, params, n_slots=2, num_pages=6,
                            max_prompt_len=16, spec_k=k)
        out = eng.generate(reqs, max_steps=2000)
        eng.scheduler.check_invariants()
        for i, r in enumerate(reqs):
            assert out[r.rid] == refs[i], (k, i)
            assert r.status is RequestStatus.COMPLETED
        assert eng.scheduler.allocator.used_count == 0


def test_spec_greedy_identity_under_preemption(tiny_model):
    """Chaos-stolen allocations force preemption mid-speculation: the
    replay path must still reproduce plain greedy decode exactly (the
    drafted/rolled-back state never leaks into the replay). Oracle:
    the undisturbed spec-off engine over the same trace — itself
    pinned to the dense reference by the staggered identity test and
    the `spec_greedy_identity` CLI leg."""
    from apex_tpu.resilience import ServingChaos

    cfg, params = tiny_model
    base = ServingEngine(cfg, params, n_slots=2, num_pages=12,
                         max_prompt_len=16, prefix_cache=False)
    ref_reqs = _mk_staggered(cfg)
    ref_out = base.generate(ref_reqs, max_steps=2000)
    reqs = _mk_staggered(cfg)
    chaos = ServingChaos().fail_allocs(4)
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=5,
                        max_prompt_len=16, spec_k=3, chaos=chaos)
    out = eng.generate(reqs, max_steps=2000)
    eng.scheduler.check_invariants()
    assert sum(r.preemptions for r in reqs) > 0
    for ref_r, r in zip(ref_reqs, reqs):
        assert out[r.rid] == ref_out[ref_r.rid], r.rid
    assert eng.scheduler.allocator.used_count == 0


def test_spec_accepts_and_shortens_on_repetitive_trace(cyclic_model):
    """The point of the tentpole: on repetition, accepted drafts push
    decode tokens/step above 1 and the trace finishes in fewer engine
    steps — while staying token-identical."""
    cfg, params = cyclic_model
    rng = np.random.default_rng(3)
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, size=8)]
    ref = reference_decode(cfg, params, prompt, 24)
    steps = {}
    for k in (0, 4):
        req = Request(prompt=list(prompt), max_new_tokens=24)
        eng = ServingEngine(cfg, params, n_slots=2, num_pages=12,
                            max_prompt_len=64, prefill_chunk=4,
                            spec_k=k)
        out = eng.generate([req], max_steps=500)
        eng.scheduler.check_invariants()
        assert out[req.rid] == ref, k
        assert eng.scheduler.allocator.used_count == 0
        steps[k] = eng.last_stats["steps"]
        if k > 0:
            st = eng.last_stats
            assert st["drafted_tokens"] > 0
            assert st["accepted_tokens"] > 0
            assert st["accept_rate"] > 0
            assert st["tokens_per_step"] > 1.0
            assert st["spec_k"] == k
        else:
            assert eng.last_stats["tokens_per_step"] == 1.0
    assert steps[4] < steps[0]


def test_spec_greedy_identity_with_prefix_cache(cyclic_model):
    """Speculation composes with the radix prefix cache: a warm pass
    (cache hits + COW forks on the shared head) under spec_k > 0 stays
    byte-identical to the cold dense reference, with zero leaks."""
    cfg, params = cyclic_model
    rng = np.random.default_rng(11)
    head = [int(t) for t in rng.integers(0, cfg.vocab_size, size=16)]
    prompts = [head + [int(t) for t in
                       rng.integers(0, cfg.vocab_size, size=4)],
               list(head)]
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=16,
                        max_prompt_len=48, prefill_chunk=4, spec_k=3)
    cold = [Request(prompt=list(p), max_new_tokens=6) for p in prompts]
    out_cold = eng.generate(cold, max_steps=2000)
    warm = [Request(prompt=list(p), max_new_tokens=6) for p in prompts]
    out_warm = eng.generate(warm, max_steps=2000)
    eng.scheduler.check_invariants()
    st = eng.last_stats["prefix_cache"]
    assert st["hits"] == len(prompts)
    for p, c, w in zip(prompts, cold, warm):
        ref = reference_decode(cfg, params, p, 6)
        assert out_cold[c.rid] == ref
        assert out_warm[w.rid] == ref
    assert eng.scheduler.allocator.used_count == 0


def test_spec_respects_eos_and_max_new(cyclic_model):
    """A mid-burst EOS (or max_new) truncates the accepted run: the
    surplus accepted tokens are discarded with the completed request,
    never published or fed back."""
    cfg, params = cyclic_model
    rng = np.random.default_rng(13)
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, size=8)]
    ref = reference_decode(cfg, params, prompt, 16)
    # pick the cycle token as EOS so it fires mid-repetition (when
    # speculation is accepting whole bursts)
    eos = ref[-1]
    ref_eos = reference_decode(cfg, params, prompt, 16, eos_id=eos)
    req = Request(prompt=list(prompt), max_new_tokens=16, eos_id=eos)
    eng = ServingEngine(cfg, params, n_slots=1, num_pages=12,
                        max_prompt_len=48, spec_k=4)
    out = eng.generate([req], max_steps=500)
    eng.scheduler.check_invariants()
    assert out[req.rid] == ref_eos
    assert req.status is RequestStatus.COMPLETED
    assert eng.scheduler.allocator.used_count == 0
    # surplus accepted tokens truncated at EOS must not inflate the
    # gated metrics: delivered decode tokens = generated minus the
    # prefill-completion first token, and the summary reconciles
    st = eng.last_stats
    assert st["generated_tokens"] == len(out[req.rid])
    assert st["decode_tokens"] == len(out[req.rid]) - 1
    assert st["decode_tokens"] == \
        st["decode_slot_steps"] + st["accepted_tokens"]


# ---------------------------------------------------------------------------
# sampled decode: the seeded oracle
# ---------------------------------------------------------------------------

def _mk_sampled(cfg, rid_base=41_000):
    sps = [SamplingParams(temperature=0.9, top_k=20, seed=11),
           SamplingParams(temperature=1.2, top_p=0.85, seed=42),
           None,  # greedy rider in the same batch
           SamplingParams(temperature=0.7, top_k=12, top_p=0.9, seed=7)]
    rng = np.random.default_rng(5)
    return [Request(prompt=[int(t) for t in
                            rng.integers(0, cfg.vocab_size, size=L)],
                    max_new_tokens=8, arrival_step=i, sampling=sp,
                    rid=rid_base + i)
            for i, (L, sp) in enumerate(zip((12, 9, 11, 8), sps))]


@pytest.fixture(scope="module")
def sampled_refs(tiny_model):
    """Seeded dense references for the mixed sampled/greedy trace —
    shared (draws key on (seed, rid, position) only, so any engine
    running the same rids reproduces them)."""
    cfg, params = tiny_model
    return {r.rid: reference_sample_decode(
        cfg, params, r.prompt, r.max_new_tokens,
        sampling=r.sampling, rid=r.rid) for r in _mk_sampled(cfg)}


def test_sampled_decode_byte_identical_to_reference(tiny_model,
                                                    sampled_refs):
    """Engine sampled decode == reference_sample_decode, byte for
    byte, with speculation off AND on — mixed sampled/greedy batch,
    tiny pool."""
    cfg, params = tiny_model
    refs = sampled_refs
    for k in (0, 3):
        reqs = _mk_sampled(cfg)
        eng = ServingEngine(cfg, params, n_slots=2, num_pages=6,
                            max_prompt_len=16, prefill_chunk=3,
                            spec_k=k)
        out = eng.generate(reqs, max_steps=2000)
        eng.scheduler.check_invariants()
        for r in reqs:
            assert out[r.rid] == refs[r.rid], (k, r.rid)
        assert eng.scheduler.allocator.used_count == 0


def test_sampled_decode_survives_preemption_replay(tiny_model,
                                                   sampled_refs):
    """The carried-PRNG point: a preempted sampled request's replay
    regenerates the SAME draws (position-keyed, not state-chained), so
    its final tokens match the undisturbed reference."""
    from apex_tpu.resilience import ServingChaos

    cfg, params = tiny_model
    refs = sampled_refs
    reqs = _mk_sampled(cfg)
    chaos = ServingChaos().fail_allocs(4)
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=5,
                        max_prompt_len=16, spec_k=2, chaos=chaos)
    out = eng.generate(reqs, max_steps=2000)
    eng.scheduler.check_invariants()
    assert sum(r.preemptions for r in reqs) > 0
    for r in reqs:
        assert out[r.rid] == refs[r.rid], r.rid
    assert eng.scheduler.allocator.used_count == 0


def test_sampled_spec_equals_plain_sampled(cyclic_model):
    """Spec-decode under SAMPLING is sequence-identical to plain
    sampled decode (the reparameterized rejection rule: acceptance =
    match against the position's own deterministic draw), even while
    drafts are accepted."""
    cfg, params = cyclic_model
    rng = np.random.default_rng(17)
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, size=8)]
    sp = SamplingParams(temperature=0.3, top_k=2, seed=3)
    req = Request(prompt=list(prompt), max_new_tokens=16,
                  sampling=sp, rid=43_000)
    eng = ServingEngine(cfg, params, n_slots=1, num_pages=12,
                        max_prompt_len=64, spec_k=4)
    out = eng.generate([req], max_steps=500)[req.rid]
    st = eng.last_stats
    eng.scheduler.check_invariants()
    assert eng.scheduler.allocator.used_count == 0
    # the reference IS plain sequential sampling (the k=0 engine is
    # pinned byte-identical to it elsewhere) — spec-on must match it
    # even while drafts are being accepted
    ref = reference_sample_decode(cfg, params, prompt, 16, sampling=sp,
                                  rid=43_000)
    assert out == ref
    # low temperature + top_k=2 on a cyclic model repeats enough for
    # the n-gram draft to land accepts
    assert st["accepted_tokens"] > 0


# ---------------------------------------------------------------------------
# billing: speculation never changes admission / router accounting
# ---------------------------------------------------------------------------

def test_admission_billing_unchanged_by_spec(tiny_model):
    """Satellite contract: admission and the fleet router keep billing
    worst-case offered tokens (one per slot-step) — a spec engine's
    probe/queued-token estimates equal the k=0 engine's, so
    speculation can only improve feasibility, never overcommit."""
    from apex_tpu.serving import AdmissionConfig

    cfg, params = tiny_model
    rng = np.random.default_rng(19)
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, size=10)]

    def probe_est(spec_k):
        eng = ServingEngine(cfg, params, n_slots=2, num_pages=8,
                            max_prompt_len=16, prefill_chunk=2,
                            spec_k=spec_k,
                            admission=AdmissionConfig(max_queue=8))
        for j in range(3):
            eng.try_submit(Request(prompt=list(prompt),
                                   max_new_tokens=4))
        reason, est = eng.probe(Request(prompt=list(prompt),
                                        max_new_tokens=4))
        return reason, est, eng._queued_tokens()

    r0, est0, q0 = probe_est(0)
    r4, est4, q4 = probe_est(4)
    assert r0 is None and r4 is None
    assert est0 == est4
    assert q0 == q4


# ---------------------------------------------------------------------------
# chaos: speculation + sampling under fire
# ---------------------------------------------------------------------------

def test_chaos_property_trace_spec_and_sampling(tiny_model):
    """The chaos satellite: random admit/evict/preempt/poison/prefix-
    eviction churn with speculation AND sampling armed —
    ``check_invariants()`` after EVERY step, zero page leaks, and
    SURVIVOR token identity against a spec-off engine over the same
    requests (itself pinned to the dense references by the tests
    above). The poisoned request must quarantine alone."""
    from apex_tpu.resilience import ServingChaos

    cfg, params = tiny_model

    def mk(seed):
        rng = np.random.default_rng(seed)
        out = []
        for j, L in enumerate(rng.integers(4, 14, size=5)):
            sp = (SamplingParams(temperature=0.8, top_k=16,
                                 seed=int(rng.integers(0, 99)))
                  if j % 2 else None)
            out.append(Request(
                prompt=[int(t) for t in rng.integers(0, 128, size=int(L))],
                max_new_tokens=5, arrival_step=int(rng.integers(0, 8)),
                sampling=sp, rid=50_000 + 100 * seed + j))
        return out

    for seed in (5,):
        base = ServingEngine(cfg, params, n_slots=2, num_pages=12,
                             max_prompt_len=16, spec_k=0,
                             prefix_cache=False)
        ref_reqs = mk(seed)
        ref_out = base.generate(ref_reqs, max_steps=3000)
        reqs = mk(seed)
        victim = reqs[2]
        chaos = (ServingChaos()
                 .fail_allocs(3)
                 .evict_prefix_cache(2)
                 .poison_request(victim.rid))
        eng = ServingEngine(cfg, params, n_slots=2, num_pages=6,
                            max_prompt_len=16, prefill_chunk=3,
                            spec_k=3, chaos=chaos)
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
        assert eng.scheduler.allocator.used_count == 0
        assert victim.status is RequestStatus.FAILED
        assert (victim.failure or {}).get("kind") == "nonfinite_logits"
        for ref_r, r in zip(ref_reqs, reqs):
            if r is victim:
                continue
            assert r.status is RequestStatus.COMPLETED, (seed, r.rid)
            assert list(r.out_tokens) == ref_out[ref_r.rid], \
                (seed, r.rid)


def test_quarantined_drafted_tokens_never_publish(cyclic_model):
    """Satellite: a quarantined slot's drafted/generated tokens must
    never enter the prefix cache. Decode-phase pages are never
    published by design; this pins the composed behaviour — poison a
    request AFTER its prompt published, while speculation is
    accepting, and assert the cache serves later requests the clean
    prompt K/V only (byte-identical decode) with zero leaks."""
    from apex_tpu.resilience import ServingChaos

    cfg, params = cyclic_model
    rng = np.random.default_rng(21)
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, size=20)]
    victim = Request(prompt=list(prompt), max_new_tokens=12)
    # poison fires at step 4: prompt (20 tokens / chunk 16) done by
    # step 2, so the victim is mid-decode with drafts in flight
    chaos = ServingChaos().poison_request(victim.rid, at_step=4)
    eng = ServingEngine(cfg, params, n_slots=1, num_pages=12,
                        max_prompt_len=64, prefill_chunk=16, spec_k=4,
                        chaos=chaos)
    eng.generate([victim], max_steps=200)
    assert victim.status is RequestStatus.FAILED
    eng.scheduler.check_invariants()
    # the published entries cover at most the PROMPT; nothing the
    # quarantined decode drafted/emitted is indexed
    assert eng.prefix_cache.match_len(
        prompt + list(victim.out_tokens) + [1]) <= len(prompt)
    retry = Request(prompt=list(prompt), max_new_tokens=12)
    out = eng.generate([retry], max_steps=200)
    ref = reference_decode(cfg, params, prompt, 12)
    assert out[retry.rid] == ref
    assert eng.scheduler.allocator.used_count == 0


# ---------------------------------------------------------------------------
# summary / fleet plumbing
# ---------------------------------------------------------------------------

def test_summarize_spec_fields_reconcile(cyclic_model):
    cfg, params = cyclic_model
    rng = np.random.default_rng(23)
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, size=8)]
    req = Request(prompt=list(prompt), max_new_tokens=24)
    eng = ServingEngine(cfg, params, n_slots=1, num_pages=12,
                        max_prompt_len=64, spec_k=4)
    eng.generate([req], max_steps=500)
    st = eng.last_stats
    assert st["spec_k"] == 4
    assert st["accepted_tokens"] <= st["drafted_tokens"]
    assert st["accept_rate"] == pytest.approx(
        st["accepted_tokens"] / st["drafted_tokens"], abs=1e-3)
    # decode tokens = one per decode slot-step + every accepted draft
    assert st["decode_tokens"] == \
        st["decode_slot_steps"] + st["accepted_tokens"]
    assert st["tokens_per_step"] == pytest.approx(
        st["decode_tokens"] / st["decode_slot_steps"], abs=1e-3)
    assert st["generated_tokens"] == 24


def test_fleet_summary_aggregates_spec_counters(cyclic_model):
    from apex_tpu.serving import ReplicaFleet

    cfg, params = cyclic_model
    rng = np.random.default_rng(29)
    reqs = [Request(prompt=[int(t) for t in
                            rng.integers(0, cfg.vocab_size, size=8)],
                    max_new_tokens=16, arrival_step=i)
            for i in range(4)]
    fleet = ReplicaFleet(cfg, params, n_replicas=2, n_slots=2,
                         num_pages=12, max_prompt_len=64, spec_k=4)
    fleet.generate(reqs, max_steps=2000)
    st = fleet.last_stats
    assert st["drafted_tokens"] > 0
    assert st["accepted_tokens"] > 0
    assert st["spec_accept_rate"] > 0
    assert st["decode_tokens_per_step"] > 1.0
    per = st["per_replica"]
    assert sum(v["drafted_tokens"] for v in per.values()) \
        == st["drafted_tokens"]
    assert fleet.page_leaks() == 0


def test_spec_engine_audits_clean(tiny_model):
    """All three jitted programs (1-token, chunked prefill,
    speculative) pass the PR-4 static auditor with telemetry armed."""
    from apex_tpu.telemetry import RingBufferRecorder

    cfg, params = tiny_model
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=8,
                        max_prompt_len=16, prefill_chunk=3, spec_k=2,
                        telemetry_every=4, sink=RingBufferRecorder())
    report = eng.audit()
    assert report.ok


def test_spec_recover_from_replays_token_identical(cyclic_model):
    """Engine kill mid-speculation + recover_from: survivors replay to
    completion token-identical (generated tokens ride the replay
    prompt; the spec/sampling state is carried, not lost)."""
    from apex_tpu.resilience import ChaosError, ServingChaos

    cfg, params = cyclic_model
    rng = np.random.default_rng(31)
    reqs = [Request(prompt=[int(t) for t in
                            rng.integers(0, cfg.vocab_size, size=8)],
                    max_new_tokens=16, arrival_step=i)
            for i in range(3)]
    refs = {r.rid: reference_decode(cfg, params, r.prompt,
                                    r.max_new_tokens) for r in reqs}
    chaos = ServingChaos().kill_engine_at(6)
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=12,
                        max_prompt_len=64, spec_k=3, chaos=chaos)
    with pytest.raises(ChaosError):
        eng.generate(list(reqs), max_steps=2000)
    eng2, survivors = ServingEngine.recover_from(eng)
    eng2.generate(survivors, max_steps=2000)
    eng2.scheduler.check_invariants()
    for r in reqs:
        assert list(r.out_tokens) == refs[r.rid]
        assert r.status is RequestStatus.COMPLETED
    assert eng2.scheduler.allocator.used_count == 0


# ---------------------------------------------------------------------------
# CI wiring: serving_check legs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("leg", ["spec_greedy_identity",
                                 "sampled_seeded_identity"])
def test_serving_check_spec_legs_pass(leg):
    import tools.serving_check as sc

    assert sc.main(["--self", "--check", leg, "--json"]) == 0
