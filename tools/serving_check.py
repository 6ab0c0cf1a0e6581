"""Serving smoke — fast CI proof the paged-KV decode engine is correct.

Like ``tools/static_audit.py --self`` and ``tools/resilience_check.py``,
this self-hosts the subsystem on a tiny model, small enough for the
tier-1 CPU lane:

- ``decode_parity``   the flash-decode kernel (the REAL kernel body:
                      compiled on a TPU, interpreted elsewhere) and
                      the XLA fallback both match
                      the dense gathered reference on ragged page
                      tables, including empty (fully-masked) slots.
- ``token_identity``  ``ServingEngine.generate`` over a staggered
                      continuous-batching trace (admits mid-flight,
                      evictions, shared slots) is token-identical to the
                      per-request dense-attention greedy decode loop
                      (``serving.reference_decode`` — the full training
                      forward recomputed per token).

Prefix-cache / chunked-prefill legs (ISSUE-12 — the token-identity
oracle extended verbatim):

- ``chunked_prefill_identity``  the SAME staggered trace run at
                      several ``prefill_chunk`` sizes (including a
                      chunk larger than any prompt) emits exactly the
                      token-at-a-time engine's tokens — chunked prompt
                      ingestion changes step count, never content.
- ``prefix_hit_identity``  requests sharing prompt heads (and one
                      exact-duplicate prompt) run twice on one engine:
                      the warm pass MUST hit the radix/hash prefix
                      cache (skipping that prefill work) and both
                      passes MUST be byte-identical to the cold dense
                      reference; the duplicate's first decode write
                      exercises the COW fork; zero reader-held pages
                      remain.
- ``step_audit``      the jitted decode step passes the PR-4 static
                      auditor clean: KV cache / slot state / metrics
                      donated, no ungated callbacks, PackSpec layout
                      verified — with the in-jit telemetry drain ARMED,
                      so the cond-gating is what is being audited.

Sampling / speculative-decoding legs (ISSUE-13 — tokens/step > 1
without giving up the identity oracle):

- ``spec_greedy_identity``  greedy decode with ``spec_k > 0`` (n-gram
                      draft -> one-pass verify -> longest-matched-
                      prefix accept) is token-identical to plain
                      greedy on the staggered trace, AND on a
                      repetition-heavy trace it must actually accept:
                      fewer engine steps, decode tokens/step > 1.
- ``sampled_seeded_identity``  temperature/top-k/top-p decode with the
                      carried (seed, rid, position) hash-counter PRNG
                      is byte-identical to the seeded dense reference
                      (``reference_sample_decode``), speculation off
                      and on, greedy riders in the same batch.

Chaos legs (``serving.robustness`` + ``resilience.ServingChaos`` — the
engine must DEGRADE, not corrupt, under injected faults):

- ``poison_quarantine``  a chaos-poisoned (non-finite-logits) request
                         terminates ``FAILED`` with slot/step
                         provenance while every other request's tokens
                         stay identical to the dense greedy reference;
                         zero page leaks.
- ``timeout_eviction``   a request past its latency budget is evicted
                         and finalized ``TIMED_OUT`` (pages freed,
                         structured ``request_end`` event) while the
                         unbudgeted request completes token-identically.
- ``kill_recover``       a chaos kill mid-flight + ``recover_from``:
                         the fresh engine replays all in-flight
                         requests to completion, token-identical to an
                         uninterrupted run.

Fleet legs (``serving.fleet`` — ISSUE-11: the multi-replica router
must hold the zero-loss contract under replica outages):

- ``fleet_kill_migrate``  3 CPU-faked replicas, one killed mid-storm
                          by ``ServingChaos.kill_replica_at``: every
                          in-flight request of the dead replica
                          migrates to the survivors on the replay
                          carrier and completes token-identical to an
                          undisturbed run — requests_lost MUST be 0.
- ``fleet_drain_join``    a rolling weight update mid-traffic: each
                          replica drains, swaps weights via
                          ``cast_params_for_inference``, rejoins —
                          zero dropped requests, and post-update
                          traffic decodes per the NEW weights.

Real-process fleet leg (``serving.proc_fleet`` — ISSUE-20: the same
zero-loss contract against replicas that actually DIE):

- ``proc_fleet_failover`` 3 worker SUBPROCESSES (one ServingEngine
                          each, framed pipe transport + heartbeat
                          files): one is SIGKILLed MID-FRAME and
                          another's heartbeat wedged in the same run —
                          the FleetSupervisor detects death (exit) and
                          hang (staleness), restarts both, migrates
                          their in-flight work on the replay carrier:
                          requests_lost == 0, every token
                          byte-identical to the dense reference, torn
                          reply frame + torn telemetry line counted,
                          zero page leaks.

Tensor-parallel leg (ISSUE-16 — the identity oracle over the TP
sharding):

- ``tp_identity``     ``ServingEngine(tp=2/4)`` on the virtual-device
                      CPU mesh is byte-identical to the tp=1 engine
                      across a staggered trace with chunked prefill,
                      speculation, mixed sampled/greedy slots and
                      forced preemption — and each TP program's jaxpr
                      carries exactly 3 psums (2 sublayer tails + 1
                      fused sampler reduction).

Usage::

    python tools/serving_check.py --self           # table, exit 1 on fail
    python tools/serving_check.py --self --json
    python tools/serving_check.py --self --check decode_parity

Exit codes (CI contract, same as static_audit/resilience_check): 0 = all
checks pass, 1 = a check failed, 2 = infra/usage error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# script-mode invocation (`python tools/serving_check.py ...`) puts
# tools/ at sys.path[0]; the repo root must be importable for apex_tpu
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _tiny_cfg():
    import jax.numpy as jnp

    from apex_tpu.transformer.testing import GPTConfig

    return GPTConfig(
        num_layers=2, hidden_size=64, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=64,
        hidden_dropout=0.0, attention_dropout=0.0,
        params_dtype=jnp.float32, compute_dtype=jnp.float32)


def _tiny_params(cfg):
    import jax

    from apex_tpu.transformer.testing import init_gpt_params

    params = init_gpt_params(cfg, jax.random.PRNGKey(0))
    # position-sensitive continuations (a plain random init greedy-
    # decodes into a fixed point, which would under-exercise the cache)
    params["embedding"]["position"] = (
        params["embedding"]["position"] * 40.0)
    return params


def check_decode_parity() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.ops.flash_decode import (
        flash_decode, paged_decode_reference,
    )

    rng = np.random.default_rng(0)
    P, n, ps, d, B, mp = 8, 4, 16, 16, 5, 3
    k_pages = jnp.asarray(rng.normal(size=(P, n, ps, d)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(P, n, ps, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, n, d)), jnp.float32)
    pt = jnp.asarray(rng.integers(1, P, size=(B, mp)), jnp.int32)
    lens = jnp.asarray([0, 5, 16, 33, 48], jnp.int32)

    ref = np.asarray(paged_decode_reference(q, k_pages, v_pages, pt, lens))
    xla = np.asarray(flash_decode(q, k_pages, v_pages, pt, lens,
                                  use_kernel=False))
    # the REAL kernel body either way: compiled on a TPU, interpreted
    # anywhere else
    kern = np.asarray(flash_decode(
        q, k_pages, v_pages, pt, lens,
        interpret=jax.default_backend() != "tpu"))
    xla_err = float(np.abs(xla - ref).max())
    kern_err = float(np.abs(kern - ref).max())
    empty_zero = float(np.abs(kern[0]).max()) == 0.0
    ok = xla_err < 1e-5 and kern_err < 1e-4 and empty_zero
    return {"ok": ok, "xla_max_err": xla_err, "kernel_max_err": kern_err,
            "empty_slot_zero": empty_zero}


def check_token_identity() -> dict:
    import numpy as np

    from apex_tpu.serving import Request, ServingEngine, reference_decode

    cfg = _tiny_cfg()
    params = _tiny_params(cfg)
    rng = np.random.default_rng(7)
    lens = (14, 11, 13, 9)
    reqs = [
        Request(prompt=list(rng.integers(0, cfg.vocab_size, size=L)),
                max_new_tokens=8, arrival_step=2 * i)
        for i, L in enumerate(lens)
    ]
    # tiny pool -> real continuous batching: shared slots, staggered
    # admits, at least the possibility of preemption
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=5,
                        max_prompt_len=16)
    out = eng.generate(reqs, max_steps=2000)
    eng.scheduler.check_invariants()
    mismatches = []
    for r in reqs:
        ref = reference_decode(cfg, params, r.prompt, r.max_new_tokens)
        if out[r.rid] != ref:
            mismatches.append({"rid": r.rid, "engine": out[r.rid],
                               "reference": ref})
    ok = (not mismatches
          and eng.last_stats["completed"] == len(reqs)
          and eng.scheduler.allocator.used_count == 0)
    return {"ok": ok, "mismatches": mismatches,
            "steps": eng.last_stats["steps"],
            "occupancy": eng.last_stats["occupancy"],
            "preemptions": eng.last_stats["preemptions"]}


def check_chunked_prefill_identity() -> dict:
    import numpy as np

    from apex_tpu.serving import Request, ServingEngine, reference_decode

    cfg = _tiny_cfg()
    params = _tiny_params(cfg)

    def mk():
        rng = np.random.default_rng(7)
        return [
            Request(prompt=list(rng.integers(0, cfg.vocab_size, size=L)),
                    max_new_tokens=8, arrival_step=2 * i)
            for i, L in enumerate((14, 11, 13, 9))
        ]

    refs = {i: reference_decode(cfg, params, r.prompt, r.max_new_tokens)
            for i, r in enumerate(mk())}
    mismatches, steps = [], {}
    for chunk in (1, 3, 8, 16):
        reqs = mk()
        # tiny pool: the chunked path must survive real continuous
        # batching (shared slots, preemption) too, not just ingestion
        eng = ServingEngine(cfg, params, n_slots=2, num_pages=5,
                            max_prompt_len=16, prefill_chunk=chunk)
        out = eng.generate(reqs, max_steps=2000)
        eng.scheduler.check_invariants()
        steps[chunk] = eng.last_stats["steps"]
        for i, r in enumerate(reqs):
            if out[r.rid] != refs[i]:
                mismatches.append({"chunk": chunk, "req": i,
                                   "engine": out[r.rid],
                                   "reference": refs[i]})
        if eng.scheduler.allocator.used_count != 0:
            mismatches.append({"chunk": chunk, "page_leaks":
                               eng.scheduler.allocator.used_count})
    # chunked ingestion must actually shorten the trace
    speedup_ok = steps[8] < steps[1]
    ok = not mismatches and speedup_ok
    return {"ok": ok, "mismatches": mismatches, "steps_by_chunk": steps,
            "chunked_fewer_steps": speedup_ok}


def check_prefix_hit_identity() -> dict:
    import numpy as np

    from apex_tpu.serving import Request, ServingEngine, reference_decode

    cfg = _tiny_cfg()
    params = _tiny_params(cfg)
    rng = np.random.default_rng(11)
    head = list(rng.integers(0, cfg.vocab_size, size=32))
    prompts = [
        head[:32] + list(rng.integers(0, cfg.vocab_size, size=6)),
        head[:32] + list(rng.integers(0, cfg.vocab_size, size=4)),
        list(head[:32]),   # page-aligned full-prompt duplicate (COW)
    ]
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=24,
                        prefill_chunk=4)
    cold = [Request(prompt=list(p), max_new_tokens=6) for p in prompts]
    out_cold = eng.generate(cold, max_steps=2000)
    warm = [Request(prompt=list(p), max_new_tokens=6) for p in prompts]
    out_warm = eng.generate(warm, max_steps=2000)
    eng.scheduler.check_invariants()
    st = eng.last_stats["prefix_cache"]
    mismatches = []
    for p, c, w in zip(prompts, cold, warm):
        ref = reference_decode(cfg, params, p, 6)
        if out_cold[c.rid] != ref:
            mismatches.append({"pass": "cold", "engine": out_cold[c.rid],
                               "reference": ref})
        if out_warm[w.rid] != ref:
            mismatches.append({"pass": "warm", "engine": out_warm[w.rid],
                               "reference": ref})
    ok = (not mismatches
          and st["hits"] == len(prompts)           # every warm prompt hit
          and st["hit_tokens"] >= 3 * 32           # at least the heads
          and eng.scheduler.allocator.used_count == 0)
    return {"ok": ok, "mismatches": mismatches, "prefix_cache": st,
            "page_leaks": eng.scheduler.allocator.used_count}


def check_spec_greedy_identity() -> dict:
    """The lossless contract: speculative decoding (``spec_k > 0``)
    under greedy sampling is TOKEN-IDENTICAL to plain greedy decode —
    on the staggered continuous-batching trace (tiny pool: shared
    slots, possible preemption) AND on a repetition-heavy trace where
    drafting actually accepts (position-independent model -> cyclic
    greedy decode), where it must also finish in fewer engine steps
    with decode tokens/step > 1."""
    import numpy as np

    from apex_tpu.serving import Request, ServingEngine, reference_decode

    cfg = _tiny_cfg()
    params = _tiny_params(cfg)

    def mk():
        rng = np.random.default_rng(7)
        return [
            Request(prompt=list(rng.integers(0, cfg.vocab_size, size=L)),
                    max_new_tokens=8, arrival_step=2 * i)
            for i, L in enumerate((14, 11, 13, 9))
        ]

    refs = {i: reference_decode(cfg, params, r.prompt, r.max_new_tokens)
            for i, r in enumerate(mk())}
    mismatches = []
    reqs = mk()
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=6,
                        max_prompt_len=16, spec_k=3)
    out = eng.generate(reqs, max_steps=2000)
    eng.scheduler.check_invariants()
    for i, r in enumerate(reqs):
        if out[r.rid] != refs[i]:
            mismatches.append({"req": i, "engine": out[r.rid],
                               "reference": refs[i]})
    if eng.scheduler.allocator.used_count:
        mismatches.append({"page_leaks":
                           eng.scheduler.allocator.used_count})
    # the accepting half: a cyclic (position-free) model repeats, so
    # the n-gram draft nails the continuation — speculation must BOTH
    # stay lossless and actually go below one pass per token
    import jax

    cyc = jax.tree_util.tree_map(lambda x: x, params)
    cyc["embedding"]["position"] = params["embedding"]["position"] * 0.0
    rng = np.random.default_rng(3)
    prompt = list(rng.integers(0, cfg.vocab_size, size=8))
    ref = reference_decode(cfg, cyc, prompt, 24)
    stats = {}
    for k in (0, 4):
        req = Request(prompt=list(prompt), max_new_tokens=24)
        eng = ServingEngine(cfg, cyc, n_slots=2, num_pages=12,
                            max_prompt_len=48, prefill_chunk=4,
                            spec_k=k)
        out = eng.generate([req], max_steps=500)
        eng.scheduler.check_invariants()
        if out[req.rid] != ref:
            mismatches.append({"cyclic_spec_k": k, "engine": out[req.rid],
                               "reference": ref})
        stats[k] = {"steps": eng.last_stats["steps"],
                    "accept_rate": eng.last_stats["accept_rate"],
                    "tokens_per_step": eng.last_stats["tokens_per_step"]}
    speedup_ok = stats[4]["steps"] < stats[0]["steps"]
    accept_ok = ((stats[4]["accept_rate"] or 0) > 0
                 and (stats[4]["tokens_per_step"] or 0) > 1)
    ok = not mismatches and speedup_ok and accept_ok
    return {"ok": ok, "mismatches": mismatches,
            "cyclic_stats": stats, "spec_fewer_steps": speedup_ok,
            "spec_accepting": accept_ok}


def check_sampled_seeded_identity() -> dict:
    """Non-greedy decode is BYTE-identical to the seeded dense
    reference (``reference_sample_decode``: same temperature/top-k/
    top-p filters, same (seed, rid, position) hash-counter draws) —
    with speculation off AND on, across a mixed sampled/greedy batch
    on a tiny pool."""
    import numpy as np

    from apex_tpu.serving import (
        Request, SamplingParams, ServingEngine, reference_sample_decode,
    )

    cfg = _tiny_cfg()
    params = _tiny_params(cfg)
    sps = [SamplingParams(temperature=0.9, top_k=20, seed=11),
           SamplingParams(temperature=1.2, top_p=0.85, seed=42),
           None,  # greedy rider in the same batch
           SamplingParams(temperature=0.7, top_k=12, top_p=0.9, seed=7)]

    def mk():
        rng = np.random.default_rng(5)
        return [Request(prompt=list(rng.integers(0, cfg.vocab_size,
                                                 size=L)),
                        max_new_tokens=8, arrival_step=i, sampling=sp,
                        rid=31_000 + i)
                for i, (L, sp) in enumerate(zip((12, 9, 11, 8), sps))]

    refs = {i: reference_sample_decode(cfg, params, r.prompt,
                                       r.max_new_tokens,
                                       sampling=r.sampling, rid=r.rid)
            for i, r in enumerate(mk())}
    mismatches = []
    for k in (0, 3):
        reqs = mk()
        eng = ServingEngine(cfg, params, n_slots=2, num_pages=6,
                            max_prompt_len=16, prefill_chunk=3,
                            spec_k=k)
        out = eng.generate(reqs, max_steps=2000)
        eng.scheduler.check_invariants()
        for i, r in enumerate(reqs):
            if out[r.rid] != refs[i]:
                mismatches.append({"spec_k": k, "req": i,
                                   "engine": out[r.rid],
                                   "reference": refs[i]})
        if eng.scheduler.allocator.used_count:
            mismatches.append({"spec_k": k, "page_leaks":
                               eng.scheduler.allocator.used_count})
    return {"ok": not mismatches, "mismatches": mismatches}


def check_step_audit() -> dict:
    from apex_tpu.serving import ServingEngine
    from apex_tpu.telemetry import RingBufferRecorder

    cfg = _tiny_cfg()
    params = _tiny_params(cfg)
    # prefill_chunk > 1 and spec_k > 0 arm ALL THREE programs — the
    # audit covers the 1-token, chunked-prefill and speculative steps
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=8,
                        max_prompt_len=16, telemetry_every=4,
                        prefill_chunk=3, spec_k=2,
                        sink=RingBufferRecorder())
    try:
        report = eng.audit()
    except AssertionError as e:
        return {"ok": False, "error": str(e)[:2000]}
    return {"ok": report.ok, **report.counts(),
            "codes": sorted(set(report.codes()))}


def check_poison_quarantine() -> dict:
    import numpy as np

    from apex_tpu.resilience import ServingChaos
    from apex_tpu.serving import (
        Request, RequestStatus, ServingEngine, reference_decode,
    )
    from apex_tpu.telemetry import RingBufferRecorder

    cfg = _tiny_cfg()
    params = _tiny_params(cfg)
    rng = np.random.default_rng(13)
    reqs = [
        Request(prompt=list(rng.integers(0, cfg.vocab_size, size=L)),
                max_new_tokens=6)
        for L in (6, 9, 4)
    ]
    chaos = ServingChaos().poison_request(reqs[1].rid, at_step=7)
    ring = RingBufferRecorder()
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=12,
                        max_prompt_len=16, chaos=chaos, sink=ring)
    out = eng.generate(list(reqs), max_steps=2000)
    eng.scheduler.check_invariants()
    victim = reqs[1]
    mismatches = []
    for r in (reqs[0], reqs[2]):
        ref = reference_decode(cfg, params, r.prompt, r.max_new_tokens)
        if out[r.rid] != ref:
            mismatches.append({"rid": r.rid, "engine": out[r.rid],
                               "reference": ref})
    fails = [e for e in ring.events("request_end")
             if e["status"] == "failed"]
    ok = (victim.status is RequestStatus.FAILED
          and (victim.failure or {}).get("kind") == "nonfinite_logits"
          and (victim.failure or {}).get("step") == 7
          and not mismatches
          and len(fails) == 1
          and eng.scheduler.allocator.used_count == 0)
    return {"ok": ok, "victim_status": victim.status.value,
            "failure": victim.failure, "mismatches": mismatches,
            "page_leaks": eng.scheduler.allocator.used_count}


def check_timeout_eviction() -> dict:
    import numpy as np

    from apex_tpu.serving import (
        Request, RequestStatus, ServingEngine, VirtualClock,
        reference_decode,
    )
    from apex_tpu.telemetry import RingBufferRecorder

    cfg = _tiny_cfg()
    params = _tiny_params(cfg)
    rng = np.random.default_rng(17)
    free = Request(prompt=list(rng.integers(0, cfg.vocab_size, size=6)),
                   max_new_tokens=6)
    # one slot: the budgeted request waits behind `free` and expires
    hurried = Request(
        prompt=list(rng.integers(0, cfg.vocab_size, size=6)),
        max_new_tokens=6, latency_budget_ms=5000.0)
    ring = RingBufferRecorder()
    eng = ServingEngine(cfg, params, n_slots=1, num_pages=8,
                        max_prompt_len=16, clock=VirtualClock(dt=1.0),
                        sink=ring)
    out = eng.generate([free, hurried], max_steps=500)
    eng.scheduler.check_invariants()
    ref = reference_decode(cfg, params, free.prompt, free.max_new_tokens)
    touts = [e for e in ring.events("request_end")
             if e["status"] == "timed_out"]
    ok = (hurried.status is RequestStatus.TIMED_OUT
          and free.status is RequestStatus.COMPLETED
          and out[free.rid] == ref
          and len(touts) == 1 and touts[0]["rid"] == hurried.rid
          and eng.scheduler.allocator.used_count == 0)
    return {"ok": ok, "hurried_status": hurried.status.value,
            "hurried_reason": hurried.end_reason,
            "page_leaks": eng.scheduler.allocator.used_count}


def check_kill_recover() -> dict:
    import numpy as np

    from apex_tpu.resilience import ChaosError, ServingChaos
    from apex_tpu.serving import (
        Request, RequestStatus, ServingEngine, reference_decode,
    )

    cfg = _tiny_cfg()
    params = _tiny_params(cfg)
    rng = np.random.default_rng(23)
    reqs = [
        Request(prompt=list(rng.integers(0, cfg.vocab_size, size=L)),
                max_new_tokens=6, arrival_step=i)
        for i, L in enumerate((8, 5, 11))
    ]
    chaos = ServingChaos().kill_engine_at(10)
    eng = ServingEngine(cfg, params, n_slots=2, num_pages=12,
                        max_prompt_len=16, chaos=chaos)
    died = False
    try:
        eng.generate(list(reqs), max_steps=2000)
    except ChaosError:
        died = True
    if not died:
        return {"ok": False, "error": "chaos kill did not fire"}
    eng2, survivors = ServingEngine.recover_from(eng)
    eng2.generate(survivors, max_steps=2000)
    eng2.scheduler.check_invariants()
    mismatches = []
    for r in reqs:
        ref = reference_decode(cfg, params, r.prompt, r.max_new_tokens)
        if list(r.out_tokens) != ref:
            mismatches.append({"rid": r.rid, "engine": list(r.out_tokens),
                               "reference": ref})
    ok = (not mismatches
          and all(r.status is RequestStatus.COMPLETED for r in reqs)
          and len(survivors) >= 1
          and eng2.scheduler.allocator.used_count == 0)
    return {"ok": ok, "recovered": len(survivors),
            "restarts": [r.restarts for r in reqs],
            "mismatches": mismatches,
            "page_leaks": eng2.scheduler.allocator.used_count}


def check_fleet_kill_migrate() -> dict:
    import numpy as np

    from apex_tpu.resilience import ServingChaos
    from apex_tpu.serving import (
        ReplicaFleet, ReplicaState, Request, RequestStatus,
        reference_decode,
    )
    from apex_tpu.telemetry import RingBufferRecorder

    cfg = _tiny_cfg()
    params = _tiny_params(cfg)
    rng = np.random.default_rng(29)
    reqs = [
        Request(prompt=list(rng.integers(0, cfg.vocab_size,
                                         size=int(rng.integers(4, 12)))),
                max_new_tokens=6, arrival_step=i)
        for i in range(9)
    ]
    chaos = ServingChaos().kill_replica_at(1, 6)
    ring = RingBufferRecorder()
    fleet = ReplicaFleet(cfg, params, n_replicas=3, sink=ring,
                         chaos=chaos, n_slots=2, num_pages=12,
                         max_prompt_len=24)
    out = fleet.generate(reqs, max_steps=3000)
    fleet.check_invariants()
    st = fleet.last_stats
    migrated_rids = {e["rid"] for e in ring.events("migrate")}
    mismatches = []
    for r in reqs:
        ref = reference_decode(cfg, params, r.prompt, r.max_new_tokens)
        if out[r.rid] != ref:
            mismatches.append({"rid": r.rid, "engine": out[r.rid],
                               "reference": ref})
    ok = (st["replica_deaths"] == 1
          and st["requests_lost"] == 0
          and st["migrated"] >= 1
          and bool(migrated_rids)
          and fleet.replicas[1].state is ReplicaState.DEAD
          and not mismatches
          and all(r.status is RequestStatus.COMPLETED for r in reqs)
          and fleet.page_leaks() == 0)
    return {"ok": ok, "requests_lost": st["requests_lost"],
            "migrated": st["migrated"],
            "replica_deaths": st["replica_deaths"],
            "mismatches": mismatches, "page_leaks": fleet.page_leaks()}


def check_fleet_drain_join() -> dict:
    import jax
    import numpy as np

    from apex_tpu.serving import (
        ReplicaFleet, Request, RequestStatus, reference_decode,
    )
    from apex_tpu.telemetry import RingBufferRecorder

    cfg = _tiny_cfg()
    params = _tiny_params(cfg)
    params2 = jax.tree_util.tree_map(lambda x: x, params)
    params2["embedding"]["position"] = (
        params["embedding"]["position"] * 0.5)
    rng = np.random.default_rng(31)
    ring = RingBufferRecorder()
    fleet = ReplicaFleet(cfg, params, n_replicas=2, sink=ring,
                         n_slots=2, num_pages=12, max_prompt_len=16)
    phase1 = [Request(prompt=list(rng.integers(0, cfg.vocab_size,
                                               size=6)),
                      max_new_tokens=5, arrival_step=i)
              for i in range(4)]
    fleet.schedule_rolling_update(params2)
    out1 = fleet.generate(phase1, max_steps=2000)
    st = fleet.last_stats
    swaps = ring.events("weight_swap")
    # zero-drop contract: every phase-1 request completed (on the old
    # or new weights, depending on when its replica swapped)
    drops = [r.rid for r in phase1
             if r.status is not RequestStatus.COMPLETED]
    mismatches = []
    for r in phase1:
        refs = (reference_decode(cfg, params, r.prompt,
                                 r.max_new_tokens),
                reference_decode(cfg, params2, r.prompt,
                                 r.max_new_tokens))
        if out1[r.rid] not in refs:
            mismatches.append({"rid": r.rid, "engine": out1[r.rid]})
    # post-update traffic must decode per the NEW weights everywhere
    phase2 = [Request(prompt=list(rng.integers(0, cfg.vocab_size,
                                               size=6)),
                      max_new_tokens=5) for _ in range(4)]
    out2 = fleet.generate(phase2, max_steps=2000)
    for r in phase2:
        ref2 = reference_decode(cfg, params2, r.prompt,
                                r.max_new_tokens)
        if out2[r.rid] != ref2:
            mismatches.append({"rid": r.rid, "engine": out2[r.rid],
                               "reference": ref2})
    ok = (fleet.rolling_update_done
          and len(swaps) == 2
          and not drops
          and st["requests_lost"] == 0
          and not mismatches
          and fleet.page_leaks() == 0)
    return {"ok": ok, "swaps": len(swaps), "dropped": drops,
            "requests_lost": st["requests_lost"],
            "mismatches": mismatches, "page_leaks": fleet.page_leaks()}


def check_tp_identity() -> dict:
    """The tensor-parallel oracle (ISSUE-16): a ``ServingEngine(tp=N)``
    on the virtual-device CPU mesh emits EXACTLY the tp=1 engine's
    tokens — across a staggered continuous-batching trace with chunked
    prefill, speculative decoding, mixed sampled/greedy slots (incl. a
    no-filter high-temperature row: the full-vocab distributed Gumbel
    draw) and forced preemption (tiny pool). Byte-identity, not
    tolerance: head-sharded attention and column/row GEMM shards
    compute bitwise the same values, and the vocab-parallel sampler's
    candidate gather reproduces the replicated filter exactly. Skipped
    (vacuous pass) when the host exposes only 1 device."""
    import jax
    import numpy as np

    from apex_tpu.serving import Request, SamplingParams, ServingEngine

    n_dev = len(jax.devices())
    tps = [t for t in (2, 4) if t <= n_dev]
    if not tps:
        return {"ok": True, "skipped": "single-device host", "tps": []}

    cfg = _tiny_cfg()
    params = _tiny_params(cfg)

    def mk():
        rng = np.random.default_rng(19)
        sps = [None,
               SamplingParams(temperature=0.9, top_k=12, top_p=0.9,
                              seed=17),
               SamplingParams(temperature=1.4, seed=23),  # no filters
               None,
               SamplingParams(temperature=0.8, top_p=0.8, seed=29)]
        return [Request(prompt=list(rng.integers(0, cfg.vocab_size,
                                                 size=L)),
                        max_new_tokens=8, arrival_step=2 * i,
                        sampling=sp, rid=47_000 + i)
                for i, (L, sp) in enumerate(zip((14, 11, 13, 9, 12),
                                                sps))]

    def run(tp):
        # tiny pool -> shared slots / possible preemption; chunked
        # prefill + speculation arm all three jitted programs
        eng = ServingEngine(cfg, params, n_slots=2, num_pages=6,
                            max_prompt_len=16, prefill_chunk=3,
                            spec_k=2, tp=tp)
        out = eng.generate(mk(), max_steps=2000)
        eng.scheduler.check_invariants()
        leaks = eng.scheduler.allocator.used_count
        return out, eng.last_stats, leaks

    base, base_stats, base_leaks = run(1)
    mismatches = []
    psums = {}
    for tp in tps:
        out, stats, leaks = run(tp)
        psums[tp] = stats["psum_per_program"]
        for rid in base:
            if out.get(rid) != base[rid]:
                mismatches.append({"tp": tp, "rid": rid,
                                   "tp_engine": out.get(rid),
                                   "tp1": base[rid]})
        if leaks:
            mismatches.append({"tp": tp, "page_leaks": leaks})
    # the collective budget: 2 sublayer tails + 1 fused sampler psum
    psum_ok = all(all(v == 3 for v in p.values()) for p in psums.values())
    ok = not mismatches and psum_ok and base_leaks == 0
    return {"ok": ok, "tps": tps, "mismatches": mismatches,
            "psum_per_program": psums, "psum_budget_ok": psum_ok}


def check_proc_fleet_failover() -> dict:
    """The real-process chaos bar (ISSUE-20): 3 worker SUBPROCESSES,
    one SIGKILLed mid-frame and another wedged (heartbeat stalled) in
    the SAME run — the FleetSupervisor must detect both (death by exit,
    hang by staleness), SIGKILL + restart them, and migrate their
    in-flight work: every offered request reaches exactly one terminal
    state, requests_lost == 0, survivor AND migrant tokens
    byte-identical to the undisturbed dense reference, zero page leaks,
    and the torn reply frame + torn telemetry line are COUNTED, never
    crashed on."""
    import tempfile

    import numpy as np

    from apex_tpu.resilience import ServingChaos
    from apex_tpu.serving import (
        FleetSupervisor, Request, RequestStatus, reference_decode,
    )
    from apex_tpu.serving.worker import model_from_spec
    from apex_tpu.telemetry import read_jsonl

    spec = {"kind": "tiny_gpt",
            "engine": {"n_slots": 2, "num_pages": 8,
                       "max_prompt_len": 16}}
    cfg, params = model_from_spec(spec)
    rng = np.random.default_rng(11)
    reqs = [
        Request(prompt=list(rng.integers(0, cfg.vocab_size,
                                         size=int(rng.integers(7, 14)))),
                max_new_tokens=6, arrival_step=i)
        for i in range(8)
    ]
    chaos = (ServingChaos()
             .kill_worker_at(1, 4, mid_frame=True)
             .wedge_worker_at(2, 6, stall_s=60.0))
    wd = tempfile.mkdtemp(prefix="serving-proc-")
    with FleetSupervisor(spec, 3, workdir=wd, chaos=chaos,
                         heartbeat_timeout_s=2.0, rpc_timeout_s=6.0,
                         startup_timeout_s=240.0) as sup:
        sup.launch()
        out = sup.generate(reqs, max_steps=2000)
        st = sup.last_stats
        leaks = sup.page_leaks()
    kinds = sorted(i["kind"] for i in st["incidents"])
    mismatches = []
    for r in reqs:
        ref = reference_decode(cfg, params, r.prompt, r.max_new_tokens)
        if out[r.rid] != ref:
            mismatches.append({"rid": r.rid, "worker": out[r.rid],
                               "reference": ref})
    # the killed worker's torn telemetry line must read back tolerantly
    import glob

    telem_stats = {}
    telem_records = 0
    for path in sorted(glob.glob(os.path.join(wd, "replica-*.jsonl"))):
        telem_records += len(read_jsonl(path, stats=telem_stats))
    ok = (kinds == ["worker_death", "worker_hang"]
          and st["requests_lost"] == 0
          and st["migrated"] >= 1
          and st["replica_deaths"] == 2
          and st["mttr_s"] is not None
          and st["torn_frames"] >= 1
          and not mismatches
          and all(r.status is RequestStatus.COMPLETED for r in reqs)
          and leaks == 0
          and telem_records > 0
          and telem_stats.get("torn_lines", 0) >= 1
          # workers are CPU stand-ins whatever this process runs on, and
          # their records say so
          and st["worker_platforms"] == ["cpu"])
    return {"ok": ok, "incidents": kinds,
            "worker_platforms": st["worker_platforms"],
            "requests_lost": st["requests_lost"],
            "migrated": st["migrated"], "mttr_s": st["mttr_s"],
            "torn_frames": st["torn_frames"],
            "torn_telemetry_lines": telem_stats.get("torn_lines", 0),
            "mismatches": mismatches, "page_leaks": leaks}


CHECKS = {
    "decode_parity": check_decode_parity,
    "tp_identity": check_tp_identity,
    "chunked_prefill_identity": check_chunked_prefill_identity,
    "prefix_hit_identity": check_prefix_hit_identity,
    "spec_greedy_identity": check_spec_greedy_identity,
    "sampled_seeded_identity": check_sampled_seeded_identity,
    "fleet_kill_migrate": check_fleet_kill_migrate,
    "fleet_drain_join": check_fleet_drain_join,
    "proc_fleet_failover": check_proc_fleet_failover,
    "token_identity": check_token_identity,
    "step_audit": check_step_audit,
    "poison_quarantine": check_poison_quarantine,
    "timeout_eviction": check_timeout_eviction,
    "kill_recover": check_kill_recover,
}


def run_checks(names=None) -> dict:
    out = {"event": "serving_check", "checks": {}}
    ok = True
    for name in (list(names) if names else sorted(CHECKS)):
        res = CHECKS[name]()
        out["checks"][name] = res
        ok = ok and bool(res["ok"])
    out["ok"] = ok
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Self-check of apex_tpu.serving on its own stack")
    ap.add_argument("--self", action="store_true", dest="self_check",
                    help="run the built-in serving smokes (required mode)")
    ap.add_argument("--check", action="append", choices=sorted(CHECKS),
                    help="restrict to specific check(s)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full result as JSON")
    args = ap.parse_args(argv)
    if not args.self_check:
        ap.error("nothing to do: pass --self (run the serving smokes)")

    try:
        result = run_checks(args.check)
    except Exception as e:  # infra failure must not read as "correct"
        print(f"serving check failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps(result, indent=2, default=str))
    else:
        for name, res in result["checks"].items():
            status = "PASS" if res["ok"] else "FAIL"
            detail = {k: v for k, v in res.items()
                      if k not in ("ok", "mismatches")}
            print(f"{status}  {name}  {detail}")
        print("summary:", json.dumps({"ok": result["ok"]}))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
