"""ServingEngine: the continuous-batching decode loop.

One jitted, fixed-shape **unified step** serves every phase: each active
slot consumes one token per step — a prompt token while prefilling, its
own last sampled token while decoding — so prefill and decode
interleave freely inside one program (Orca-style iteration-level
batching) and a long prompt never stalls other requests' token cadence.
With ``prefill_chunk=N > 1`` a SECOND fixed-shape program (same carry,
same donation) ingests up to N prompt tokens per prefilling slot-step;
boundaries with any prefilling slot run it, pure-decode boundaries keep
the 1-token program — mixed steps stay one fixed-shape program and the
decode hot path pays no chunk padding.

The **prefix cache** (on by default; ``prefix_cache=False`` disables)
lets a request whose prompt head is already resident skip that prefill
entirely: the scheduler's radix/hash index shares the cached pages
read-only at admission, the engine applies the pending copy-on-write
page forks each boundary (``_copy_pool_pages``, donated) before the
step's K/V writes, admission/probe estimates bill only UNCACHED
tokens, and :meth:`ServingEngine.swap_params` flushes the cache with
every weight hot-swap (stale old-weight K/V cannot survive a rolling
update).

Sync discipline (the serving analogue of the training-step rules the
PR-4 auditor enforces):

- the KV cache, the per-slot device state and the telemetry
  ``MetricsState`` are **donated** into the step — page writes and slot
  updates are in place;
- the sampled token feeds back to the next step **on device** (the
  ``SlotState`` carry), so the host never round-trips a token to keep a
  slot running;
- in-jit telemetry drains through the PR-2 cond-gated async callback —
  there is no other callback in the program. ``audit()`` /
  ``analysis.assert_step_clean`` verify all of this on the traced step;
- the single host read per step is the fetch of that step's emitted
  tokens, which the scheduler needs for EOS/finish decisions (and the
  caller needs anyway — it IS the output). A ``HangWatchdog`` can arm
  that one sync (``watchdog=``), so a wedged device/step surfaces as a
  ``HangError`` with all-thread stacks instead of a silent stall.

Robustness (``serving.robustness`` — the serving twin of
``apex_tpu.resilience``): every request ends in exactly one typed
terminal state; per-request TTFT / total-latency deadlines are enforced
at each scheduling boundary (an expired slot is evicted, its pages
freed, the request finalized ``TIMED_OUT``); admission control bounds
the queue with watermark backpressure and token-budget feasibility;
the step carries an in-jit non-finite check on each slot's logits, so
a poisoned request is quarantined alone (``FAILED`` with slot/step
provenance) while every other request's tokens stay byte-identical;
and a dead engine's in-flight requests are recovered onto a fresh one
through the recompute-preemption replay path
(:meth:`ServingEngine.recover_from`).

Scheduling (admission, lazy page allocation, preemption, eviction) runs
on the host between steps (``serving.scheduler``); its decisions reach
the device as one masked slot-state update plus the small per-step
page-table upload.

Weights are cast ONCE at engine construction through the amp cast
tables (``amp.cast_params_for_inference``) — bf16 serving reuses the
training stack's mixed-precision discipline with no master copies.
"""
from __future__ import annotations

import time
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec

from .. import telemetry
from ..amp import cast_params_for_inference
from ..ops.flash_decode import (
    _kernel_ok,
    flash_decode_available,
    page_sublanes,
)
from ..resilience.watchdog import HangError
from ..transformer import parallel_state
from .decode_model import (  # noqa: F401
    decode_tokens,
    prefill_chunk_tokens,
    reference_decode,
)
from .kv_cache import KVCacheState, PagedKVSpec, PrefixCache  # noqa: F401
from .robustness import (
    AdmissionConfig,
    AdmissionController,
    DegradationPolicy,
    RejectionCode,
    RejectionError,
    RejectionReason,
    RequestStatus,
    TransientRequestFailure,
    already_in_flight,
    is_terminal,
    recover_requests,
    request_expired,
)
from .sampling import TOP_FILTER_WIDTH
from .sampling import i32_wrap as _i32_wrap
from .sampling import resolve, sample_tokens, sample_tokens_tp
from .scheduler import Request, Scheduler, SchedulerError
from .spec_decode import (  # noqa: F401  (sentinel re-export: the
    NO_TOKEN,  # fetched array carries tokens AND the per-slot fault
    POISONED,  # flag, so fault isolation adds no second host sync)
    run_spec_step,
)

Pytree = Any


class SlotState(NamedTuple):
    """Per-slot device state carried (donated) step to step.

    The sampling policy rows (``temps``/``top_ks``/``top_ps``/``seeds``/
    ``rids``) make non-greedy decode a pure function of the carried
    state — every draw is keyed ``(seed, rid, position)`` through the
    stateless hash counter (``serving.sampling``), so there is no RNG
    state to snapshot or migrate. ``hist`` is the consumed-token
    history (prompt + generated, one scratch column at the end): the
    speculative decoder's on-device n-gram table, maintained by the
    step itself.
    """

    tokens: jax.Array       # [B] i32 — token each slot consumes next
    positions: jax.Array    # [B] i32 — its position
    active: jax.Array       # [B] bool
    prompt_buf: jax.Array   # [B, max_seq_len] i32 — prompt (replay) text
    prompt_lens: jax.Array  # [B] i32
    temps: jax.Array        # [B] f32 — 0 = greedy argmax
    top_ks: jax.Array       # [B] i32 — 0 = disabled
    top_ps: jax.Array       # [B] f32 — 1.0 = disabled
    seeds: jax.Array        # [B] i32 — per-request PRNG seed
    rids: jax.Array         # [B] i32 — request id (the PRNG lane key)
    hist: jax.Array         # [B, max_seq_len + 1] i32 — consumed tokens


def default_page_size(num_heads: int, head_dim: int,
                      dtype: Any = jnp.float32) -> int:
    """Smallest power-of-two page that is a whole number of ``dtype``
    vreg tiles (8 tokens at fp32, 16 at bf16 — the flash_decode kernel's
    page block) and whose K/V page is ROW-aligned
    (``kv_cache.PagedKVSpec`` requirement)."""
    from ..multi_tensor_apply.packing import ROW

    for ps in (8, 16, 32, 64, 128, 256):
        if (ps % page_sublanes(dtype) == 0
                and (num_heads * ps * head_dim) % ROW == 0):
            return ps
    raise ValueError(
        f"no power-of-two page size <= 256 aligns {num_heads} heads x "
        f"{head_dim} dim {jnp.dtype(dtype).name} pages to {ROW} elements")


class ServingEngine:
    """Single-chip paged-KV decode engine over a
    ``standalone_transformer_lm`` GPT parameter pytree.

    ``generate(requests)`` drives submitted :class:`~.scheduler.Request`
    objects to completion under continuous batching and returns
    ``{rid: [token, ...]}``; greedy (argmax) sampling — the decoding
    mode the token-identity acceptance is defined over.
    """

    def __init__(
        self,
        cfg,
        params: Pytree,
        *,
        n_slots: int = 4,
        page_size: Optional[int] = None,
        num_pages: Optional[int] = None,
        pages_per_seq: Optional[int] = None,
        max_prompt_len: Optional[int] = None,
        kv_dtype: Any = None,
        telemetry_every: int = 0,
        record_every: int = 16,
        sink=None,
        use_kernel: Optional[bool] = None,
        interpret: bool = False,
        admission: Optional[AdmissionConfig] = None,
        degradation: Optional[DegradationPolicy] = None,
        watchdog=None,
        step_timeout_s: Optional[float] = None,
        chaos=None,
        clock: Optional[Callable[[], float]] = None,
        prefill_chunk: int = 1,
        prefix_cache: bool = True,
        spec_k: int = 0,
        spec_ngram: int = 3,
        tp: int = 1,
        devices: Optional[Sequence[Any]] = None,
        trace: bool = True,
    ):
        # recovery (recover_from) rebuilds an engine with the same
        # geometry/policies; capture the kwargs before unpacking
        self._ctor_kw = dict(
            n_slots=n_slots, page_size=page_size, num_pages=num_pages,
            pages_per_seq=pages_per_seq, max_prompt_len=max_prompt_len,
            kv_dtype=kv_dtype, telemetry_every=telemetry_every,
            record_every=record_every, sink=sink, use_kernel=use_kernel,
            interpret=interpret, admission=admission,
            degradation=degradation, watchdog=watchdog,
            step_timeout_s=step_timeout_s, chaos=chaos, clock=clock,
            prefill_chunk=prefill_chunk, prefix_cache=prefix_cache,
            spec_k=spec_k, spec_ngram=spec_ngram, tp=tp, devices=devices,
            trace=trace)
        self.cfg = cfg
        n, d = cfg.num_attention_heads, cfg.kv_channels
        #: tensor-parallel degree. tp > 1 head-shards the paged KV pool
        #: and column/row-shards the GEMMs over a single-axis
        #: ``(tensor,)`` submesh; the host half (scheduler, page
        #: tables, admission, prefix cache) is untouched — slot state
        #: stays replicated and the emitted-token fetch stays the one
        #: host sync per step.
        self.tp = int(tp)
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        if n % self.tp:
            raise ValueError(
                f"num_attention_heads {n} not divisible by tp={self.tp}")
        # a TP engine's K/V page must be ROW-aligned PER SHARD (each
        # shard holds n/tp heads of every page), so the default page
        # size derives from the LOCAL head count — spec.shard() below
        # re-validates whatever the caller forces
        kv_dtype = jnp.dtype(kv_dtype or cfg.compute_dtype)
        ps = page_size or default_page_size(n // self.tp, d, kv_dtype)
        max_seq = cfg.max_position_embeddings
        # mp*ps may overshoot max_seq (pages quantize); submit() holds
        # requests to max_position_embeddings either way
        mp = pages_per_seq or -(-max_seq // ps)
        num_pages = num_pages or (n_slots * mp + 1)
        self.spec = PagedKVSpec(
            cfg.num_layers, n, d, page_size=ps, num_pages=num_pages,
            pages_per_seq=mp, dtype=kv_dtype)
        self.n_slots = int(n_slots)
        self.max_prompt_len = int(max_prompt_len or max_seq)
        # the on-device prompt buffer must hold preemption-replay
        # prompts (original prompt + generated so far): cap = max seq
        self._buf_len = min(self.spec.max_seq_len, max_seq)
        #: the per-shard spec the traced programs see (``== self.spec``
        #: at tp=1): n/tp heads, same page geometry — one chunk-aligned
        #: PackSpec per shard. Host logic keeps using the GLOBAL spec.
        self.spec_local = self.spec.shard(self.tp)
        self._mesh = None
        self._psum_counts: Optional[Dict[str, int]] = None
        self._comm_volume: Optional[Dict[str, Dict]] = None
        if self.tp > 1:
            # mechanical layout gate: the global flat pool must divide
            # into tp ROW-aligned extents (the per-shard PackSpec the
            # local spec's own constructor already validated)
            from ..analysis.rules import check_pack_spec
            findings = check_pack_spec(self.spec.pack_spec,
                                       shard_count=self.tp,
                                       where="serving_kv_pool")
            if findings:
                raise ValueError(
                    "KV pool layout is not tp-shardable: "
                    + "; ".join(f"{f.code}: {f.message}" for f in findings))
            vocab = int(params["embedding"]["word"].shape[0])
            if vocab % self.tp:
                raise ValueError(
                    f"vocab {vocab} not divisible by tp={self.tp} "
                    "(lm_logits is vocab-parallel)")
            self._mesh = parallel_state.tp_submesh(self.tp,
                                                   devices=devices)
            # weights onto the mesh BEFORE the cast — the cast
            # preserves each leaf's NamedSharding, so the column/row
            # slices are laid down exactly once
            params = jax.device_put(params,
                                    self._tp_param_shardings(params))
        # one-shot inference cast through the amp tables: bf16/fp16
        # weights for a low-precision compute dtype, no master copies
        self.params = cast_params_for_inference(params, cfg.compute_dtype)
        self.sink = sink if sink is not None else telemetry.NullRecorder()
        self.telemetry_every = int(telemetry_every)
        self.record_every = int(record_every)
        self._use_kernel = use_kernel
        self._interpret = bool(interpret)
        # fail at construction, not at the first traced step: if the
        # kernel path would be selected, its tileability contract must
        # hold for this (page_size, head_dim)
        if (_kernel_ok(use_kernel, self._interpret)
                and not flash_decode_available(ps, d, kv_dtype)):
            raise ValueError(
                f"flash_decode kernel cannot tile page_size={ps}, "
                f"head_dim={d} (needs page_size % "
                f"{page_sublanes(kv_dtype)} == 0 for {kv_dtype.name} pages "
                "and head_dim <= 256); pass use_kernel=False for the XLA "
                "fallback or pick a compatible page_size")
        self._chaos = chaos
        self.prefill_chunk = max(1, int(prefill_chunk))
        if self.prefill_chunk > self._buf_len:
            raise ValueError(
                f"prefill_chunk {prefill_chunk} exceeds the prompt "
                f"buffer ({self._buf_len} tokens)")
        #: speculative decoding: draft up to `spec_k` tokens per decode
        #: slot per step (0 = off) from an `spec_ngram`-gram lookup
        #: over the slot's own history, verified in one target pass
        self.spec_k = max(0, int(spec_k))
        self.spec_ngram = int(spec_ngram)
        if self.spec_k > 0 and not (
                1 <= self.spec_ngram < self._buf_len):
            raise ValueError(
                f"spec_ngram must be in [1, {self._buf_len}) "
                f"(the sequence buffer), got {spec_ngram}")
        if self.spec_k >= self._buf_len:
            raise ValueError(
                f"spec_k {spec_k} exceeds the sequence buffer "
                f"({self._buf_len} tokens)")
        self.scheduler = Scheduler(self.spec, self.n_slots,
                                   max_prompt_len=self._buf_len,
                                   chaos=chaos,
                                   prefix_cache=bool(prefix_cache),
                                   prefill_chunk=self.prefill_chunk,
                                   spec_k=self.spec_k)
        #: the per-engine radix/hash prefix index (None when disabled);
        #: per-REPLICA in a fleet — each engine's cache is private to
        #: its own pool and flushed on its own weight swaps
        self.prefix_cache = self.scheduler.cache
        self.admission = (
            AdmissionController(admission, self.n_slots,
                                degradation=degradation)
            if admission is not None else None)
        if degradation is not None and admission is None:
            raise ValueError(
                "degradation= requires admission= (the DegradationPolicy "
                "acts through the AdmissionController's pressure state)")
        self.watchdog = watchdog
        self._step_timeout_s = step_timeout_s
        self._clock = clock if clock is not None else time.perf_counter
        #: end-to-end tracing (telemetry.spans): span records through
        #: this engine's sink (a fleet's TaggedRecorder tags them with
        #: the replica id for free) + the bounded flight-recorder ring
        #: dumped as a black box on hangs and recovery. Span timestamps
        #: only reuse clock values the engine already read, so tracing
        #: adds ZERO clock reads (VirtualClock budgets are denominated
        #: in reads) and traced runs stay deterministic.
        self.tracer = (telemetry.Tracer(sink=self.sink, clock=self._clock)
                       if trace else None)
        self.kv = self._place_kv(self.spec.init_cache())
        self.slots = self._replicated(self._init_slots())
        self.metrics = self._replicated(telemetry.init_metrics())
        self._step = self._build_step()
        # the chunked-prefill program (built lazily on first use): same
        # carry, same donation, up to `prefill_chunk` prompt tokens per
        # prefilling slot; pure-decode boundaries keep using the
        # 1-token program so the decode hot path pays no chunk padding
        self._chunk_step = None
        # the speculative draft->verify->accept program (spec_k > 0):
        # ONE fixed-shape program of width max(prefill_chunk, spec_k+1)
        # serves every boundary — prefill slots ride its chunk columns,
        # decode slots verify their drafts in the same pass
        self._spec_step = None
        self._copy_pages = jax.jit(_copy_pool_pages, donate_argnums=(0,))
        self._mutate = jax.jit(_mutate_slots, donate_argnums=(0,))
        self._occupants: List[Optional[int]] = [None] * self.n_slots
        self._no_poison = self._replicated(
            jnp.zeros((self.n_slots,), bool))
        self.steps_run = 0
        self.last_stats: Dict[str, Any] = {}
        self._accum = self._fresh_accum()

    def begin_run(self) -> None:
        """Reset the per-run accounting accumulators — called by
        ``generate()`` and by fleet drivers that step the engine via
        ``run_step`` directly, so :attr:`run_accum` describes one
        trace, not the engine's lifetime."""
        self._accum = self._fresh_accum()

    @property
    def run_accum(self) -> Dict[str, Any]:
        """The current run's raw accumulators (steps, slot-step and
        wall-time splits, queue high-water) — the public read the
        fleet's per-replica summary folds."""
        return self._accum

    def _fresh_accum(self) -> Dict[str, Any]:
        return {
            "steps": 0, "active_slot_steps": 0, "prefill_slot_steps": 0,
            "decode_slot_steps": 0, "step_time_s": 0.0,
            "prefill_step_time_s": 0.0, "decode_step_time_s": 0.0,
            "step_times_ms": [], "max_queue_depth": 0,
            # token-granular split (a chunked prefill slot-step consumes
            # up to `prefill_chunk` tokens, so slot-steps alone no
            # longer measure prefill work) + prefix-cache attribution
            "prefill_tokens": 0, "decode_tokens": 0,
            "cached_prompt_tokens": 0,
            # speculative decoding: drafts offered to verification vs
            # drafts accepted (decode_tokens - accepted = the one
            # "free" token per decode slot-step)
            "drafted_tokens": 0, "accepted_tokens": 0,
            # cache counters are engine-lifetime; snapshot them so the
            # run summary reports THIS run's deltas
            "cache_base": (self.prefix_cache.stats()
                           if self.prefix_cache is not None else None),
        }

    # -- construction ------------------------------------------------------
    def _init_slots(self) -> SlotState:
        B, W = self.n_slots, self._buf_len
        return SlotState(
            tokens=jnp.zeros((B,), jnp.int32),
            positions=jnp.zeros((B,), jnp.int32),
            active=jnp.zeros((B,), bool),
            prompt_buf=jnp.zeros((B, W), jnp.int32),
            prompt_lens=jnp.zeros((B,), jnp.int32),
            temps=jnp.zeros((B,), jnp.float32),
            top_ks=jnp.zeros((B,), jnp.int32),
            top_ps=jnp.ones((B,), jnp.float32),
            seeds=jnp.zeros((B,), jnp.int32),
            rids=jnp.zeros((B,), jnp.int32),
            hist=jnp.zeros((B, W + 1), jnp.int32),
        )

    # -- tensor parallelism ------------------------------------------------
    @property
    def _tp_axis(self) -> Optional[str]:
        """The named axis the traced programs reduce over (None = the
        replicated single-chip engine; the code paths are identical)."""
        return parallel_state.TENSOR_AXIS if self.tp > 1 else None

    def _tp_param_pspecs(self, params):
        """PartitionSpec tree for the Megatron serving sharding map:
        QKV/fc1 column-parallel (head-major out dim — whole heads per
        shard, matching the pool's head shard), proj/fc2 row-parallel
        (contraction dim; their psum is the sublayer tail), everything
        else — LNs, both embeddings, row-parallel biases — replicated.
        The word embedding stays replicated on purpose: the input
        lookup is a plain local take, and only ``lm_logits`` slices it
        vocab-parallel (no embedding psum)."""
        t = parallel_state.TENSOR_AXIS
        col = {
            "qkv_w": PartitionSpec(None, t, None),
            "qkv_b": PartitionSpec(None, t),
            "fc1_w": PartitionSpec(None, t, None),
            "fc1_b": PartitionSpec(None, t),
            "proj_w": PartitionSpec(None, None, t),
            "fc2_w": PartitionSpec(None, None, t),
        }

        def leaf_spec(path, x):
            last = path[-1]
            name = last.key if hasattr(last, "key") else str(last)
            return col.get(name, PartitionSpec())

        return jax.tree_util.tree_map_with_path(leaf_spec, params)

    def _tp_param_shardings(self, params):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self._mesh, s),
            self._tp_param_pspecs(params),
            is_leaf=lambda s: isinstance(s, PartitionSpec))

    def _kv_pspec(self) -> PartitionSpec:
        """Pool ``[L, 2, pages, heads, page, dim]``: head-sharded."""
        return PartitionSpec(None, None, None, parallel_state.TENSOR_AXIS,
                             None, None)

    def _replicated(self, tree):
        """Pin host-carried state (slots/metrics/poison) replicated on
        the TP mesh, so donation in == out and no step reshards it."""
        if self._mesh is None:
            return tree
        sh = NamedSharding(self._mesh, PartitionSpec())
        return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh),
                                      tree)

    def _place_kv(self, kv: KVCacheState) -> KVCacheState:
        if self._mesh is None:
            return kv
        return jax.device_put(
            kv, NamedSharding(self._mesh, self._kv_pspec()))

    def _maybe_shard_map(self, core, n_rep: int):
        """Wrap a step core ``core(params, kv, *rep_args) -> (kv,
        slots, emitted)`` in ``shard_map`` over the TP mesh — the
        identity at tp=1, so the replicated engine's traced program is
        exactly the historical one. ``check_vma=False`` is the ddp_step
        precedent: slot math runs redundantly per shard on replicated
        inputs and collectives keep it bitwise identical across shards,
        which vma tracking cannot see."""
        if self._mesh is None:
            return core
        rep = PartitionSpec()
        return shard_map(
            core, mesh=self._mesh,
            in_specs=(self._tp_param_pspecs(self.params),
                      self._kv_pspec()) + (rep,) * n_rep,
            out_specs=(self._kv_pspec(), rep, rep),
            check_vma=False)

    def program_comm_volume(self) -> Optional[Dict[str, Dict]]:
        """Static ``{program: {collective: {count, bytes, axes}}}``
        report over every enabled serving program, from
        :func:`apex_tpu.analysis.comm_volume` (a jaxpr walk — trace
        only, no execution). ``None`` at tp=1: there are no collectives
        to report. Cached: the programs are fixed at construction."""
        if self.tp == 1:
            return None
        if self._comm_volume is None:
            from ..analysis import comm_volume

            progs = [("decode", self.step_program())]
            if self.prefill_chunk > 1:
                progs.append(("chunk_prefill", self.chunk_step_program()))
            if self.spec_k > 0:
                progs.append(("spec_verify", self.spec_step_program()))
            self._comm_volume = {
                name: comm_volume(fn, *args)
                for name, (fn, args) in progs}
        return self._comm_volume

    def program_psum_counts(self) -> Optional[Dict[str, int]]:
        """Walker-based psum eqn count per enabled serving program
        (None at tp=1 — there are no collectives to count). Derived
        from :meth:`program_comm_volume`, NOT from counting "psum" in
        the jaxpr text (which also matches scope strings and
        ``reduce_scatter``'s psum_scatter spelling). The fori_loop
        layer body appears once, so each program counts its two
        sublayer tails plus the sampler's one fused reduction = 3 —
        the number the psum-pin test and ``_summarize`` report."""
        vols = self.program_comm_volume()
        if vols is None:
            return None
        if self._psum_counts is None:
            self._psum_counts = {
                name: int(v.get("psum", {}).get("count", 0))
                for name, v in vols.items()}
        return self._psum_counts

    def _build_step(self):
        cfg, spec = self.cfg, self.spec_local
        buf_len = self._buf_len
        use_kernel, interpret = self._use_kernel, self._interpret
        tel_every, sink = self.telemetry_every, self.sink
        axis, vocab = self._tp_axis, self.cfg.vocab_size

        def core(params, kv, slots, page_tables, poison):
            logits, kv = decode_tokens(
                cfg, params, spec, kv, slots.tokens, slots.positions,
                slots.active, page_tables,
                use_kernel=use_kernel, interpret=interpret,
                tp_axis=axis)
            # chaos seam: the poison mask turns a slot's logits
            # non-finite IN-JIT (the shape of a corrupted activation /
            # poisoned weight shard) — one compiled program serves the
            # armed and unarmed arms, like resilience.poison_grads
            logits = jnp.where(poison[:, None], jnp.float32(jnp.nan),
                               logits)
            # the carried sampler: greedy rows are the exact argmax
            # (byte-identical to the pre-sampling engine); sampled rows
            # draw via the (seed, rid, position) hash counter — the
            # emitted token OCCUPIES position pos + 1, which is its
            # PRNG key. Fault isolation rides along: the per-slot
            # non-finite check on the SAME logits the argmax consumes
            # becomes the POISONED sentinel — no extra host sync (and
            # under TP it shares the sampler's one fused psum).
            if axis is None:
                bad = (slots.active
                       & ~jnp.all(jnp.isfinite(logits), axis=-1))
                sampled = sample_tokens(
                    logits, slots.temps, slots.top_ks, slots.top_ps,
                    slots.seeds, slots.rids, slots.positions + 1)
            else:
                sampled, nonfin = sample_tokens_tp(
                    logits, slots.temps, slots.top_ks, slots.top_ps,
                    slots.seeds, slots.rids, slots.positions + 1,
                    axis_name=axis, vocab_size=vocab)
                bad = slots.active & nonfin
            next_pos = slots.positions + 1
            still_prefill = next_pos < slots.prompt_lens
            prompt_next = jnp.take_along_axis(
                slots.prompt_buf,
                jnp.minimum(next_pos, buf_len - 1)[:, None], axis=1)[:, 0]
            # a slot that just consumed its LAST prompt token emits its
            # first generated token; decode slots emit every step
            emitted = jnp.where(slots.active & ~still_prefill,
                                sampled, jnp.int32(NO_TOKEN))
            emitted = jnp.where(bad, jnp.int32(POISONED), emitted)
            next_tok = jnp.where(still_prefill, prompt_next, sampled)
            slots = slots._replace(
                tokens=jnp.where(slots.active, next_tok, slots.tokens),
                positions=jnp.where(slots.active, next_pos,
                                    slots.positions),
            )
            return kv, slots, emitted

        # telemetry stays OUTSIDE the shard_map: the drain's cond-gated
        # host callback must trace once per program, not once per shard
        core = self._maybe_shard_map(core, n_rep=3)

        def step(params, kv, slots, page_tables, poison, metrics):
            kv, slots, emitted = core(params, kv, slots, page_tables,
                                      poison)
            if tel_every > 0:
                metrics = telemetry.accumulate(
                    metrics,
                    tokens=jnp.sum((emitted >= 0).astype(jnp.float32)))
                metrics = telemetry.drain(
                    metrics, sink, every_n=tel_every, tag="serving")
            return kv, slots, emitted, metrics

        return jax.jit(step, donate_argnums=(1, 2, 5))

    def _build_chunk_step(self):
        """The chunked-prefill sibling of :meth:`_build_step`: same
        signature, same donation, same one-emission-per-slot contract —
        but a prefilling slot consumes up to ``prefill_chunk`` prompt
        tokens (decode slots ride along consuming their one carried
        token). Selected by :meth:`run_step` whenever any slot is
        prefilling; mixed prefill/decode steps therefore stay ONE
        fixed-shape program."""
        cfg, spec = self.cfg, self.spec_local
        buf_len = self._buf_len
        chunk = self.prefill_chunk
        use_kernel, interpret = self._use_kernel, self._interpret
        tel_every, sink = self.telemetry_every, self.sink
        axis, vocab = self._tp_axis, self.cfg.vocab_size

        def core(params, kv, slots, page_tables, poison):
            logits, kv, take = prefill_chunk_tokens(
                cfg, params, spec, kv, slots.tokens, slots.positions,
                slots.active, slots.prompt_buf, slots.prompt_lens,
                page_tables, chunk=chunk,
                use_kernel=use_kernel, interpret=interpret,
                tp_axis=axis)
            logits = jnp.where(poison[:, None], jnp.float32(jnp.nan),
                               logits)
            next_pos = slots.positions + take
            # the emission point's logits produce the token that will
            # OCCUPY position pos + take — its PRNG key
            if axis is None:
                bad = (slots.active
                       & ~jnp.all(jnp.isfinite(logits), axis=-1))
                sampled = sample_tokens(
                    logits, slots.temps, slots.top_ks, slots.top_ps,
                    slots.seeds, slots.rids, next_pos)
            else:
                sampled, nonfin = sample_tokens_tp(
                    logits, slots.temps, slots.top_ks, slots.top_ps,
                    slots.seeds, slots.rids, next_pos,
                    axis_name=axis, vocab_size=vocab)
                bad = slots.active & nonfin
            still_prefill = next_pos < slots.prompt_lens
            prompt_next = jnp.take_along_axis(
                slots.prompt_buf,
                jnp.minimum(next_pos, buf_len - 1)[:, None], axis=1)[:, 0]
            emitted = jnp.where(slots.active & ~still_prefill,
                                sampled, jnp.int32(NO_TOKEN))
            emitted = jnp.where(bad, jnp.int32(POISONED), emitted)
            next_tok = jnp.where(still_prefill, prompt_next, sampled)
            slots = slots._replace(
                tokens=jnp.where(slots.active, next_tok, slots.tokens),
                positions=jnp.where(slots.active, next_pos,
                                    slots.positions),
            )
            return kv, slots, emitted

        core = self._maybe_shard_map(core, n_rep=3)

        def step(params, kv, slots, page_tables, poison, metrics):
            kv, slots, emitted = core(params, kv, slots, page_tables,
                                      poison)
            if tel_every > 0:
                metrics = telemetry.accumulate(
                    metrics,
                    tokens=jnp.sum((emitted >= 0).astype(jnp.float32)))
                metrics = telemetry.drain(
                    metrics, sink, every_n=tel_every, tag="serving")
            return kv, slots, emitted, metrics

        return jax.jit(step, donate_argnums=(1, 2, 5))

    def _chunk_step_fn(self):
        if self._chunk_step is None:
            self._chunk_step = self._build_chunk_step()
        return self._chunk_step

    def _build_spec_step(self):
        """The speculative draft->verify->accept program
        (``spec_decode.run_spec_step``): one fixed-shape step of width
        ``max(prefill_chunk, spec_k + 1)`` serving every boundary.
        Same carry and donation as the other two programs; the fetched
        array is the ``[B, C + 1]`` emitted matrix (tokens in order,
        ``NO_TOKEN`` padding, ``POISONED`` quarantine in column 0, the
        drafted-token count in the last column) — still ONE host sync
        per step."""
        cfg, spec = self.cfg, self.spec_local
        spec_k, ngram = self.spec_k, self.spec_ngram
        chunk = self.prefill_chunk
        use_kernel, interpret = self._use_kernel, self._interpret
        tel_every, sink = self.telemetry_every, self.sink
        axis = self._tp_axis

        def core(params, kv, slots, page_tables, poison, draft_caps):
            return run_spec_step(
                cfg, params, spec, kv, slots, page_tables, poison,
                draft_caps, spec_k=spec_k, ngram=ngram,
                prefill_chunk=chunk,
                use_kernel=use_kernel, interpret=interpret,
                tp_axis=axis)

        core = self._maybe_shard_map(core, n_rep=4)

        def step(params, kv, slots, page_tables, poison, draft_caps,
                 metrics):
            kv, slots, emitted = core(params, kv, slots, page_tables,
                                      poison, draft_caps)
            if tel_every > 0:
                metrics = telemetry.accumulate(
                    metrics,
                    tokens=jnp.sum(
                        (emitted[:, :-1] >= 0).astype(jnp.float32)))
                metrics = telemetry.drain(
                    metrics, sink, every_n=tel_every, tag="serving")
            return kv, slots, emitted, metrics

        return jax.jit(step, donate_argnums=(1, 2, 6))

    def _spec_step_fn(self):
        if self._spec_step is None:
            self._spec_step = self._build_spec_step()
        return self._spec_step

    # -- audit surface -----------------------------------------------------
    def step_program(self):
        """(jitted step, example args): the surface
        ``analysis.assert_step_clean`` audits — donated KV/slot/metrics
        state, cond-gated callbacks only."""
        B, mp = self.n_slots, self.spec.pages_per_seq
        args = (self.params, self.spec.init_cache(), self._init_slots(),
                jnp.zeros((B, mp), jnp.int32), jnp.zeros((B,), bool),
                telemetry.init_metrics())
        return self._step, args

    def chunk_step_program(self):
        """(jitted chunked-prefill step, example args) — the second
        audit surface when ``prefill_chunk > 1``."""
        fn, args = self.step_program()
        return self._chunk_step_fn(), args

    def spec_step_program(self):
        """(jitted speculative step, example args) — the audit surface
        when ``spec_k > 0`` (the extra positional arg is the host's
        per-slot draft cap)."""
        _, args = self.step_program()
        args = args[:5] + (jnp.zeros((self.n_slots,), jnp.int32),
                           args[5])
        return self._spec_step_fn(), args

    def audit(self, **kw):
        """Static audit of the decode step — and, when chunked prefill
        / speculative decoding are enabled, those programs too (PR-4
        auditor); raises on error-severity findings, returns the
        (last) report."""
        from ..analysis import assert_step_clean

        fn, args = self.step_program()
        kw.setdefault("pack_specs", [self.spec.pack_spec])
        if self.tp > 1:
            # the pack-spec gate re-checks the pool layout against the
            # engine's shard count — the audited programs ARE the
            # shard_map-wrapped TP traces
            kw.setdefault("shard_count", self.tp)
        report = assert_step_clean(
            fn, *args, name=kw.pop("name", "serving_decode_step"), **kw)
        if self.prefill_chunk > 1:
            cfn, cargs = self.chunk_step_program()
            report = assert_step_clean(
                cfn, *cargs, name="serving_chunk_prefill_step", **kw)
        if self.spec_k > 0:
            sfn, sargs = self.spec_step_program()
            report = assert_step_clean(
                sfn, *sargs, name="serving_spec_decode_step", **kw)
        return report

    # -- request intake ----------------------------------------------------
    def _engine_reject_reason(self, req: Request
                              ) -> Optional[RejectionReason]:
        if len(req.prompt) > self.max_prompt_len:
            return RejectionReason(
                RejectionCode.PROMPT_TOO_LONG,
                f"request {req.rid}: prompt {len(req.prompt)} exceeds "
                f"max_prompt_len {self.max_prompt_len}")
        total = len(req.prompt) + req.max_new_tokens
        if total > self.cfg.max_position_embeddings:
            return RejectionReason(
                RejectionCode.EXCEEDS_MAX_SEQ,
                f"request {req.rid}: prompt+max_new = {total} exceeds "
                f"max_position_embeddings "
                f"{self.cfg.max_position_embeddings}")
        if req.max_new_tokens < 1:
            return RejectionReason(
                RejectionCode.BAD_MAX_NEW,
                f"request {req.rid}: max_new_tokens < 1")
        if self.tp > 1:
            sp = resolve(req.sampling)
            # the TP sampler has no deep-top_k fallback: thresholds come
            # from the gathered per-shard top-64 candidates, so a top_k
            # beyond the filter width cannot be honored exactly —
            # refuse at submit rather than silently truncate
            if sp.top_k > TOP_FILTER_WIDTH:
                return RejectionReason(
                    RejectionCode.UNSUPPORTED_SAMPLING,
                    f"request {req.rid}: top_k {sp.top_k} exceeds the "
                    f"tensor-parallel filter width {TOP_FILTER_WIDTH} "
                    f"(tp={self.tp} has no full-vocab sort fallback)")
        return None

    def probe(self, req: Request
              ) -> Tuple[Optional[RejectionReason], float]:
        """Read-only feasibility x cost for one request against this
        engine — the router's view of a replica. Returns ``(reason,
        est_steps)``:

        - ``reason``: the refusal :meth:`try_submit` would produce
          right now (engine limits, scheduler validation, admission
          control via :meth:`AdmissionController.probe`), or ``None``
          when the request would be admitted;
        - ``est_steps``: estimated engine steps until this request's
          FIRST token — current token backlog (queued + in-flight
          remainders) shared over ``n_slots`` token-at-a-time slots,
          plus its own replay prefill. Multiply by the controller's
          ``estimated_step_time_s`` for a wall-clock cost.

        Nothing is mutated: no ``t_arrival`` stamp, no status change,
        no finalize, no admission latch/counter updates — a fleet
        router costs every replica per request, and only the winner's
        ``try_submit`` may act.
        """
        queued_tokens = self._queued_tokens()  # one O(queue) scan
        backlog = queued_tokens + sum(
            max(0, run.total_len() - run.pos)
            for _, run in self.scheduler.running())
        # post-hit, post-chunk prefill cost: only the UNCACHED replay
        # head is actually computed, `prefill_chunk` tokens per step —
        # the estimate the fleet router's cost model consumes
        prefill_steps = self._prefill_steps(req)
        est_steps = backlog / max(1, self.n_slots) + prefill_steps
        if req.status in (RequestStatus.QUEUED, RequestStatus.RUNNING):
            return already_in_flight(req), est_steps
        reason = self._engine_reject_reason(req)
        if reason is None:
            reason = self.scheduler.validate(req)
        if reason is None and self.admission is not None:
            reason = self.admission.probe(
                req, queue_depth=len(self.scheduler.waiting),
                queued_tokens=queued_tokens,
                prefill_steps=prefill_steps)
        return reason, est_steps

    def try_submit(self, req: Request) -> Optional[RejectionReason]:
        """Admit a request, or refuse it with a typed reason (finalized
        ``REJECTED`` + ``reject`` telemetry) — the non-raising door
        ``generate()`` and overload callers use.

        Resubmitting a terminal request (after a rejection, or a
        recovered ``FAILED``) starts a fresh lifecycle attempt;
        ``t_arrival`` is stamped only once, so deadline budgets span
        resubmits and restarts — the user has been waiting the whole
        time, and the SLO accounting must say so.
        """
        if req.status in (RequestStatus.QUEUED, RequestStatus.RUNNING):
            # a duplicate submission of in-flight work would put ONE
            # Request object in two queue positions / slots (shared
            # out_tokens, double finalize); refuse WITHOUT finalizing —
            # the live submission keeps running
            reason = already_in_flight(req)
            self.sink.record({"event": "reject", "rid": req.rid,
                              **reason.as_record()})
            return reason
        if is_terminal(req.status):
            req.status = RequestStatus.PENDING
            req.end_reason = None
        now = self._clock()
        if req.t_arrival is None:
            req.t_arrival = now
        ctx = None
        if self.tracer is not None:
            # trace identity stamped once per lifecycle attempt; a
            # migrant/resubmit keeps its context (and its attribution
            # ledger — the user has been waiting the whole time)
            ctx = self.tracer.begin_request_trace(req)
            if req.attr is None:
                telemetry.attr_init(req, now)
            else:
                telemetry.attr_account(
                    req, now,
                    "migration" if getattr(req, "_migrating", False)
                    else "queue_wait")
            req._migrating = False
        ctl = self.admission
        depth = len(self.scheduler.waiting)
        reason = self._engine_reject_reason(req)
        if reason is None:
            reason = self.scheduler.validate(req)
        if reason is None and ctl is not None:
            queued_tokens = self._queued_tokens()
            reason = ctl.check(req, queue_depth=depth,
                               queued_tokens=queued_tokens,
                               prefill_steps=self._prefill_steps(req))
        if reason is not None:
            self.sink.record({"event": "reject", "rid": req.rid,
                              "queue_depth": depth,
                              **reason.as_record()})
            if ctx is not None:
                self.tracer.emit("admission", ctx.trace_id, now, now,
                                 parent_id=ctx.span_id,
                                 outcome=reason.code.value,
                                 queue_depth=depth)
            self._finalize(req, RequestStatus.REJECTED,
                           reason.code.value, now=now)
            return reason
        if ctl is not None:
            # graceful degradation, applied only to work that is
            # actually being admitted: less work per request keeps the
            # door open under pressure, and the cut is recorded against
            # the run that honors it (a rejected request keeps its
            # requested max_new for any later resubmit)
            cap = ctl.cap_for(req, depth)
            if cap is not None:
                self.sink.record({
                    "event": "degrade", "rid": req.rid,
                    "max_new_tokens": cap,
                    "requested_max_new": req.max_new_tokens})
                req.max_new_tokens = cap
        if ctx is not None:
            self.tracer.emit("admission", ctx.trace_id, now, now,
                             parent_id=ctx.span_id, outcome="queued",
                             queue_depth=depth)
        req.status = RequestStatus.QUEUED
        self.scheduler.waiting.append(req)
        return None

    def submit(self, req: Request) -> None:
        """The raising intake (historical API): refusal raises
        :class:`~.robustness.RejectionError` (a ``SchedulerError``)
        carrying the typed reason."""
        reason = self.try_submit(req)
        if reason is not None:
            raise RejectionError(reason)

    def cancel(self, req: Request) -> bool:
        """Withdraw a request: removed from the queue or evicted from
        its slot (pages freed), finalized ``CANCELLED``. Returns False
        when it is not in flight (already terminal / unknown)."""
        sched = self.scheduler
        now = self._clock()
        if sched.remove_waiting(req):
            self._finalize(req, RequestStatus.CANCELLED, "cancelled",
                           now=now)
            return True
        for i, run in sched.running():
            if run.req is req:
                sched.evict(i)
                self._finalize(req, RequestStatus.CANCELLED, "cancelled",
                               now=now)
                return True
        return False

    def _uncached_replay(self, req: Request) -> int:
        """Replay-prompt tokens this engine would actually PREFILL for
        ``req`` right now: the replay length minus its cached head
        (capped so the final prompt token is always recomputed — its
        logits produce the first generated token). An estimate: entries
        can be evicted before the request admits.

        Memoized per request against the cache's mutation generation —
        admission walks every queued request on every probe/submit, and
        between index mutations those walks are identical."""
        replay = len(req.prompt) + len(req.out_tokens)
        cache = self.prefix_cache
        if cache is None or replay < 2:
            return replay
        # keyed on the cache IDENTITY too: a fleet router probes every
        # replica, each with its own cache and generation counter
        memo = getattr(req, "_uncached_memo", None)
        probe_key = (id(cache), cache.generation, replay)
        if memo is not None and memo[0] == probe_key:
            return memo[1]
        cached = min(cache.match_len(list(req.prompt)
                                     + list(req.out_tokens)),
                     replay - 1)
        uncached = replay - cached
        req._uncached_memo = (probe_key, uncached)
        return uncached

    def _prefill_steps(self, req: Request) -> int:
        """Engine steps until ``req``'s first token once scheduled:
        ceil(uncached replay / prefill_chunk)."""
        return -(-self._uncached_replay(req) // self.prefill_chunk)

    def _queued_tokens(self) -> int:
        """Token-budget view of the waiting queue: tokens still to be
        consumed (UNCACHED replay head + remaining generation — a
        queued request whose prompt head sits in the prefix cache owes
        the pool and the step budget only its uncached tail)."""
        return sum(
            self._uncached_replay(r)
            + r.max_new_tokens - len(r.out_tokens)
            for r in self.scheduler.waiting)

    # -- lifecycle ---------------------------------------------------------
    def _finalize(self, req: Request, status: RequestStatus, reason: str,
                  *, now: float, failure: Optional[dict] = None,
                  term: str = "queue_wait") -> None:
        """One typed terminal state per request + a structured
        ``request_end`` record through the PR-2 recorder — and, under
        tracing, the trace's single TERMINAL span (the "request" root
        children parent to), closing the attribution ledger with
        ``term`` for the final interval (zero-length when ``run_step``
        already accounted this boundary)."""
        if is_terminal(req.status):  # explicit: must survive python -O
            raise AssertionError(
                f"request {req.rid} finalized twice "
                f"({req.status.name} -> {status.name})")
        req.status = status
        req.end_reason = reason
        if failure is not None:
            req.failure = dict(failure)
        if req.t_done is None and status is RequestStatus.COMPLETED:
            req.t_done = now
        rec = {
            "event": "request_end", "rid": req.rid,
            "status": status.value, "reason": reason,
            "generated": len(req.out_tokens),
            "preemptions": req.preemptions,
            "restarts": req.restarts,
        }
        # health-plane enrichment (telemetry.timeseries consumes these):
        # latencies from stamps the engine already took and the SLO
        # verdict from static budgets — zero new clock reads here
        if req.t_arrival is not None:
            if req.t_first_token is not None:
                rec["ttft_ms"] = round(
                    1e3 * (req.t_first_token - req.t_arrival), 6)
            rec["latency_ms"] = round(
                1e3 * ((req.t_done if req.t_done is not None else now)
                       - req.t_arrival), 6)
        rec["slo_ok"] = self._within_budget(req)
        if req.labels:
            rec["labels"] = dict(req.labels)
        if failure is not None:
            rec["failure"] = dict(failure)
        self.sink.record(rec)
        self._emit_terminal_span(req, status, reason, now=now, term=term)

    def _emit_terminal_span(self, req: Request, status: RequestStatus,
                            reason: str, *, now: float, term: str) -> None:
        if self.tracer is None:
            return
        telemetry.spans.emit_terminal_span(
            self.tracer, req, status.value, reason, now=now, term=term,
            slo_ok=self._within_budget(req))

    def _enforce_deadlines(self, now: float) -> None:
        """Evict expired work at the scheduling boundary: a request past
        its total-latency budget — or still waiting on its first token
        past its TTFT budget — is finalized ``TIMED_OUT``, its slot
        freed and pages returned, instead of silently occupying
        capacity."""
        sched = self.scheduler

        def expired(req: Request) -> Optional[str]:
            return request_expired(req, now)

        for req in list(sched.waiting):
            why = expired(req)
            if why is not None:
                sched.remove_waiting(req)
                self._finalize(req, RequestStatus.TIMED_OUT, why, now=now)
        for i, run in list(sched.running()):
            why = expired(run.req)
            if why is not None:
                sched.evict(i)
                self._finalize(run.req, RequestStatus.TIMED_OUT, why,
                               now=now,
                               term=("decode" if not run.prefilling else
                                     "replay" if run.replay else
                                     "prefill_compute"))

    def _boundary_degradation(self, now: float) -> None:
        """Pressure degrades queued work. While the queue sits at/above
        the high watermark (or backpressure is latched), waiting
        requests are capped to the policy's ``cap_max_new`` — they have
        not started decoding, so the cut frees real capacity (the
        submit-path cap can never reach them: any submit that sees
        pressure is refused by the same check). Past ``shed_after``
        pressured boundaries, shedding starts: deadline-infeasible
        first, then lowest-priority-youngest, until the queue drains to
        the low watermark."""
        ctl = self.admission
        sched = self.scheduler
        shed_now = ctl.note_boundary(len(sched.waiting))
        d = ctl.degradation
        if (d is not None and d.cap_max_new is not None
                and (ctl.backpressure
                     or len(sched.waiting) >= ctl.high_count)):
            for req in sched.waiting:
                if req.max_new_tokens > d.cap_max_new:
                    self.sink.record({
                        "event": "degrade", "rid": req.rid,
                        "max_new_tokens": int(d.cap_max_new),
                        "requested_max_new": req.max_new_tokens})
                    req.max_new_tokens = int(d.cap_max_new)
        if not shed_now:
            return
        while len(sched.waiting) > ctl.low_count:
            victim = ctl.pick_shed_victim(sched.waiting,
                                          self._queued_tokens())
            if victim is None:
                break
            sched.remove_waiting(victim)
            ctl.shed += 1
            self.sink.record({"event": "shed", "rid": victim.rid,
                              "priority": victim.priority,
                              "queue_depth": len(sched.waiting)})
            self._finalize(victim, RequestStatus.REJECTED, "shed",
                           now=now)

    # -- the loop ----------------------------------------------------------
    def _sync_device_slots(self) -> None:
        """Push occupancy changes (admissions, evictions, preemptions)
        — and cursor rewinds (cache-pressure rollback) — to the device
        slot state as ONE masked update. An admission with a prefix-
        cache hit starts at its cached cursor: positions and the next
        token to consume come from ``run.pos``, not 0."""
        sched = self.scheduler
        B, W = self.n_slots, self._buf_len
        dirty = sched.take_dirty_slots()
        mask = np.zeros((B,), bool)
        tokens = np.zeros((B,), np.int32)
        positions = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        prompt_buf = np.zeros((B, W), np.int32)
        prompt_lens = np.zeros((B,), np.int32)
        temps = np.zeros((B,), np.float32)
        top_ks = np.zeros((B,), np.int32)
        top_ps = np.ones((B,), np.float32)
        seeds = np.zeros((B,), np.int32)
        rids = np.zeros((B,), np.int32)
        hist = np.zeros((B, W + 1), np.int32)
        for i in range(B):
            run = sched.slots[i]
            rid = None if run is None else run.req.rid
            if rid == self._occupants[i] and i not in dirty:
                continue  # unchanged occupancy: device carry is current
            mask[i] = True
            self._occupants[i] = rid
            if run is None:
                continue  # deactivate row (zeros, active=False)
            plen = len(run.prompt)
            assert run.pos < plen, "admission must start inside the prompt"
            tokens[i] = run.prompt[run.pos]
            positions[i] = run.pos
            active[i] = True
            prompt_buf[i, :plen] = np.asarray(run.prompt, np.int32)
            prompt_lens[i] = plen
            sp = resolve(run.req.sampling)
            temps[i] = sp.temperature
            top_ks[i] = sp.top_k
            top_ps[i] = sp.top_p
            seeds[i] = _i32_wrap(sp.seed)
            rids[i] = _i32_wrap(run.req.rid)
            # the replay prompt IS the consumed history so far (it
            # folds generated tokens back in), so a (re)admitted slot's
            # on-device n-gram table resumes exactly where it left off
            hist[i, :plen] = prompt_buf[i, :plen]
        if not mask.any():
            return
        new = SlotState(
            tokens=jnp.asarray(tokens), positions=jnp.asarray(positions),
            active=jnp.asarray(active),
            prompt_buf=jnp.asarray(prompt_buf),
            prompt_lens=jnp.asarray(prompt_lens),
            temps=jnp.asarray(temps), top_ks=jnp.asarray(top_ks),
            top_ps=jnp.asarray(top_ps), seeds=jnp.asarray(seeds),
            rids=jnp.asarray(rids), hist=jnp.asarray(hist))
        self.slots = self._mutate(self.slots, jnp.asarray(mask), new)

    def _poison_mask(self, step_no: int):
        """The chaos poison-injection mask for this step ([B] bool on
        device; the cached all-False buffer when nothing fires)."""
        if self._chaos is None:
            return self._no_poison
        occupants = [None if s is None else s.req.rid
                     for s in self.scheduler.slots]
        mask = self._chaos.poison_mask(occupants, step_no)
        if mask is None:
            return self._no_poison
        return jnp.asarray(mask)

    def _fetch_emitted(self, emitted, step_no: int) -> np.ndarray:
        """The step's one host sync, optionally under an armed
        watchdog deadline (a wedged sync raises ``HangError`` with
        all-thread stacks + a ``hang`` event instead of stalling the
        engine forever). The chaos wedge fires inside the armed window
        — that is the fault the watchdog exists to catch."""
        def fetch():
            if self._chaos is not None:
                self._chaos.maybe_wedge(step_no)
            return np.asarray(emitted)

        if self.watchdog is None:
            return fetch()
        try:
            with self.watchdog.armed("serving_step_host_sync",
                                     timeout_s=self._step_timeout_s,
                                     context={"step": step_no}):
                return fetch()
        except HangError as e:
            # the post-mortem black box: the flight ring (what the
            # engine was doing) merged with the hang's all-thread
            # stacks (where it stopped), through the same sink the
            # hang event landed in
            if self.tracer is not None:
                self.tracer.dump_blackbox(
                    reason="hang", sink=self.sink, stacks=e.stacks,
                    what=e.what, step=step_no)
            raise

    def run_step(self) -> np.ndarray:
        """One scheduling boundary + one device step; returns the
        fetched emitted-token array: ``[B]`` (-1 = no token, -2 =
        quarantined) for the plain programs, or — with ``spec_k > 0``
        — the ``[B, C+1]`` matrix (per-slot emitted tokens in order,
        ``NO_TOKEN`` padding, ``POISONED`` in column 0, the
        drafted-token count in the last column)."""
        sched = self.scheduler
        step_no = self.steps_run
        if self._chaos is not None:
            self._chaos.maybe_kill(step_no)  # raises ChaosError
        boundary_t = now = self._clock()
        self._enforce_deadlines(now)
        if self.admission is not None:
            self._boundary_degradation(now)
        if self._chaos is not None and sched.cache is not None:
            # eviction-under-pressure chaos: force cache evictions at
            # this boundary (evict_one still refuses reader-held pages
            # — that is the property under test). getattr: duck-typed
            # chaos doubles predating the fault stay valid.
            taker = getattr(self._chaos, "take_cache_evictions", None)
            for _ in range(taker() if taker is not None else 0):
                if sched.cache.evict_one() is None:
                    break
        admitted = sched.admit()
        self._accum["cached_prompt_tokens"] += sum(
            run.cached_tokens for _, run in admitted)
        if self.tracer is not None:
            for i, run in admitted:
                run.t_admit = boundary_t
                ctx = run.req.trace
                if ctx is not None:
                    self.tracer.emit(
                        "admit", ctx.trace_id, boundary_t, boundary_t,
                        parent_id=ctx.span_id, slot=i, pos=run.pos,
                        cached_tokens=run.cached_tokens,
                        replay=run.replay)
        preempted = sched.ensure_capacity()
        if self.tracer is not None:
            for r in preempted:
                ctx = r.trace
                if ctx is not None:
                    self.tracer.emit(
                        "preempt", ctx.trace_id, boundary_t, boundary_t,
                        parent_id=ctx.span_id,
                        preemptions=r.preemptions)
        # pressure rollbacks recompute tokens already counted as
        # cache-skipped: correct the savings accounting
        self._accum["cached_prompt_tokens"] -= \
            sched.take_rollback_tokens()
        forks = sched.take_forks()
        if self.tracer is not None and forks:
            self.tracer.emit("cow_fork", "engine-steps", boundary_t,
                             boundary_t, step=step_no,
                             n_copies=len(forks), ring_only=True)
        while forks:
            # apply the pending COW page copies BEFORE this step's K/V
            # writes land (padded to a fixed shape so the copy program
            # compiles once: 0 -> 0 copies the garbage page onto
            # itself; a write never targets more than one shared page
            # per slot, so one batch is the common case)
            batch, forks = forks[:self.n_slots], forks[self.n_slots:]
            src = np.zeros((self.n_slots,), np.int32)
            dst = np.zeros((self.n_slots,), np.int32)
            for j, (s, d) in enumerate(batch):
                src[j], dst[j] = s, d
            self.kv = self._copy_pages(self.kv, jnp.asarray(src),
                                       jnp.asarray(dst))
        self._sync_device_slots()
        page_tables = jnp.asarray(sched.page_table_array())
        poison = self._poison_mask(step_no)
        # host classification BEFORE the step (deterministic mirrors):
        # which slots consume prompt vs generated tokens this step, and
        # how many tokens each takes (chunked prefill consumes up to
        # `prefill_chunk` per prefilling slot)
        served = sched.running()
        prefill_slots = [i for i, r in served if r.prefilling]
        decode_slots = {i for i, r in served if not r.prefilling}
        prefill_tokens = sum(sched.next_take(r)
                             for _, r in served if r.prefilling)
        t0 = time.perf_counter()
        if self.spec_k > 0:
            # the unified speculative program serves every boundary;
            # the host's per-slot draft cap bounds drafting to the
            # pages ensure_capacity just allocated
            caps = np.zeros((self.n_slots,), np.int32)
            for i, r in served:
                caps[i] = sched.draft_cap(r)
            self.kv, self.slots, emitted, self.metrics = \
                self._spec_step_fn()(
                    self.params, self.kv, self.slots, page_tables,
                    poison, jnp.asarray(caps), self.metrics)
        else:
            step_fn = (self._chunk_step_fn()
                       if self.prefill_chunk > 1 and prefill_slots
                       else self._step)
            self.kv, self.slots, emitted, self.metrics = step_fn(
                self.params, self.kv, self.slots, page_tables, poison,
                self.metrics)
        em = self._fetch_emitted(emitted, step_no)  # the one host sync
        dt = time.perf_counter() - t0
        now = self._clock()
        if self.admission is not None:
            # feed the EWMA in the SAME clock the deadline budgets are
            # denominated in (boundary-to-boundary), so token-budget
            # feasibility stays meaningful under an injected clock;
            # bench timing (_acct) stays on perf_counter
            self.admission.observe_step(now - boundary_t)
        if self.tracer is not None:
            # latency attribution: partition [last accounting -> now]
            # for every request visible at this boundary, using the
            # SAME `now` that stamps t_first_token/t_done below — so
            # the per-term sums equal the measured latencies exactly.
            # Phase is the slot's state at step START (decode vs
            # prefill vs replay; a cache-hit admission's first interval
            # buckets to cached_skip once).
            for r in sched.waiting:
                telemetry.attr_account(r, now, "queue_wait")
            for i, run in served:
                telemetry.attr_account(
                    run.req, now,
                    self._phase_term(run, i in decode_slots))
        # normalize the fetched array: the legacy programs emit one
        # token per slot ([B]); the speculative program emits a token
        # MATRIX plus a drafted-count column ([B, C + 1])
        if em.ndim == 1:
            tok_rows = em[:, None]
            drafted = np.zeros((self.n_slots,), np.int64)
        else:
            tok_rows = em[:, :-1]
            drafted = em[:, -1].astype(np.int64)
        # quarantined slots are excluded from advance BEFORE it runs:
        # advance() publishes freshly completed prompt pages to the
        # prefix cache, and a slot whose logits went non-finite this
        # step wrote non-finite K/V this step — publishing it would
        # hand poisoned pages to every later request sharing the
        # prefix (cache-hit identity AND fault isolation both break)
        bad_slots = {i for i, _ in served
                     if int(tok_rows[i, 0]) == POISONED}
        emitted_by_slot: Dict[int, List[int]] = {}
        consumed: Dict[int, int] = {}
        for i, run in served:
            if i in bad_slots:
                continue
            toks = []
            for t in tok_rows[i]:
                t = int(t)
                if t == NO_TOKEN:
                    break
                toks.append(t)
            emitted_by_slot[i] = toks
            if i in decode_slots:
                # the cursor moved by the ACCEPTED run (first emitted
                # token + every accepted draft) — decided on device,
                # read off the emitted row
                consumed[i] = len(toks)
        sched.advance([i for i, _ in served if i not in bad_slots],
                      consumed=consumed)
        n_decode_tokens = 0
        n_accepted = 0
        for i, run in served:
            req = run.req
            if i in bad_slots:
                # fault isolation: quarantine ONLY this slot — evict,
                # free its pages, finalize FAILED with provenance; the
                # other slots' rows never mixed with its math, so their
                # tokens are byte-identical to an undisturbed run
                sched.evict(i)
                if self.tracer is not None and req.trace is not None:
                    self.tracer.emit(
                        "quarantine", req.trace.trace_id, now, now,
                        parent_id=req.trace.span_id, slot=i,
                        step=step_no, position=run.pos)
                self._finalize(
                    req, RequestStatus.FAILED, "nonfinite_logits",
                    now=now,
                    failure={"kind": "nonfinite_logits", "slot": i,
                             "step": step_no, "rid": req.rid,
                             "position": run.pos,
                             "transient": True})
                continue
            toks = emitted_by_slot.get(i) or []
            kept = 0
            for tok in toks:
                if req.t_first_token is None:
                    req.t_first_token = now
                    if self.tracer is not None:
                        # freeze the TTFT attribution at the SAME now
                        # that stamps the latency — terms sum exactly
                        telemetry.attr_snapshot_ttft(req)
                        ctx = req.trace
                        if ctx is not None:
                            self.tracer.emit(
                                "prefill", ctx.trace_id,
                                run.t_admit if run.t_admit is not None
                                else now, now,
                                parent_id=ctx.span_id, slot=i,
                                cached_tokens=run.cached_tokens,
                                replay=run.replay)
                req.out_tokens.append(tok)
                kept += 1
                if req.done:
                    # surplus accepted tokens past max_new/EOS are
                    # discarded with the slot — the request is done
                    req.t_done = now
                    sched.evict(i)
                    self._finalize(req, RequestStatus.COMPLETED,
                                   "done", now=now)
                    break
            if i in decode_slots:
                # count only DELIVERED tokens (surplus accepted tokens
                # truncated at EOS/max_new must not inflate the
                # accept-rate / tokens-per-step metrics the bench gates)
                n_decode_tokens += kept
                n_accepted += max(0, kept - 1)
        if self.spec_k > 0:
            # rejected drafts' bookkeeping rollback: return the
            # worst-case tail pages the accepted run did not reach
            # (stale K/V inside kept pages is overwritten before the
            # cursor can ever expose it — see Scheduler.rollback_kv)
            for i, run in served:
                if (i in decode_slots and i not in bad_slots
                        and sched.slots[i] is run):
                    sched.rollback_kv(i, run, run.pos)
        if self.tracer is not None:
            # flight-recorder heartbeat: one ring-only span per step
            # (never hits the sink — volume stays off the stream, the
            # black box still shows what the engine was doing)
            self.tracer.emit(
                "engine_step", "engine-steps", boundary_t, now,
                step=step_no, active=len(served),
                admitted=len(admitted), preempted=len(preempted),
                queue_depth=len(sched.waiting), ring_only=True)
        self.steps_run += 1
        self._acct(len(served), len(prefill_slots), len(decode_slots),
                   prefill_tokens, dt,
                   n_decode_tokens=n_decode_tokens,
                   n_drafted=int(sum(drafted[i] for i in decode_slots
                                     if i not in bad_slots)),
                   n_accepted=n_accepted)
        return em

    @staticmethod
    def _phase_term(run, decoding: bool) -> str:
        """The attribution bucket for one slot's boundary interval.
        Flips the slot's one-shot ``hit_attributed`` latch: a cache-hit
        admission's first interval is the skip the cache collapsed the
        prefill into, and buckets to ``cached_skip`` exactly once."""
        if decoding:
            return "decode"
        if run.replay:
            return "replay"
        if run.cached_tokens > 0 and not run.hit_attributed:
            run.hit_attributed = True
            return "cached_skip"
        return "prefill_compute"

    def _acct(self, n_active, n_prefill, n_decode, n_prefill_tokens, dt,
              *, n_decode_tokens=None, n_drafted=0, n_accepted=0):
        a = self._accum
        a["steps"] += 1
        a["active_slot_steps"] += n_active
        a["prefill_slot_steps"] += n_prefill
        a["decode_slot_steps"] += n_decode
        a["prefill_tokens"] += n_prefill_tokens
        # under speculative decoding a decode slot-step emits 1 +
        # accepted tokens; the caller counts what was actually kept
        a["decode_tokens"] += (n_decode if n_decode_tokens is None
                               else n_decode_tokens)
        a["drafted_tokens"] += n_drafted
        a["accepted_tokens"] += n_accepted
        a["step_time_s"] += dt
        a["max_queue_depth"] = max(a["max_queue_depth"],
                                   len(self.scheduler.waiting))
        # mixed steps pro-rate wall time by slot counts (matching the
        # slot-step accounting above) — under continuous batching most
        # steps serve both phases at once
        if n_prefill or n_decode:
            frac = n_prefill / (n_prefill + n_decode)
            a["prefill_step_time_s"] += dt * frac
            a["decode_step_time_s"] += dt * (1.0 - frac)
        a["step_times_ms"].append(dt * 1e3)
        if self.record_every and a["steps"] % self.record_every == 0:
            self.sink.record({
                "event": "serving_step", "step": self.steps_run,
                "active": n_active,
                "occupancy": n_active / self.n_slots,
                "free_pages": self.scheduler.allocator.free_count,
                "queue_depth": len(self.scheduler.waiting),
            })

    def _drain(self, pending: List[Request], start_step: int,
               max_steps: Optional[int]) -> int:
        """Submit arrivals and run steps until the trace drains; the
        shared loop under ``generate()`` and its retry passes."""
        step_i = start_step
        while True:
            while pending and pending[0].arrival_step <= step_i:
                self.try_submit(pending.pop(0))
            if not pending and self.scheduler.idle:
                return step_i
            if max_steps is not None and step_i >= max_steps:
                raise SchedulerError(
                    f"generate exceeded max_steps={max_steps} with "
                    f"{len(pending)} pending and "
                    f"{self.scheduler.n_active} active")
            if self.scheduler.idle:
                step_i += 1  # gap before the next arrival
                continue
            self.run_step()
            step_i += 1

    def generate(self, requests: Sequence[Request],
                 max_steps: Optional[int] = None,
                 retry_failed=None) -> Dict[int, List[int]]:
        """Run a request trace to completion under continuous batching.

        Requests with ``arrival_step > 0`` are held back and submitted
        at that step boundary — the staggered-admission traces the
        token-identity acceptance runs. Rejected requests (admission
        control, legacy refusals) are finalized ``REJECTED`` and the
        trace continues. Returns ``{rid: tokens}`` and fills
        :attr:`last_stats` (latency percentiles over COMPLETED requests
        via ``telemetry.percentiles``, throughput, occupancy, the
        terminal-state buckets, the prefill/decode split).

        ``retry_failed``: a :class:`~apex_tpu.resilience.RetryPolicy`
        for request-level retry of transient ``FAILED`` requests (e.g.
        a quarantined non-finite burst): each retry pass resubmits them
        through the recompute replay path (generated tokens are kept),
        under the policy's attempt count and wall-clock ``deadline``
        budget (its ``retry_on`` filter is ignored here — the trigger
        is always the internal retry signal); requests still failing
        when the policy exhausts stay ``FAILED``.
        """
        self.begin_run()
        pending = sorted(requests, key=lambda r: (r.arrival_step, r.rid))
        all_reqs = list(pending)
        t_start = time.perf_counter()
        step_i = self._drain(pending, 0, max_steps)
        if retry_failed is not None:
            self._retry_failed(all_reqs, step_i, max_steps, retry_failed)
        wall = time.perf_counter() - t_start
        self.last_stats = self._summarize(all_reqs, wall)
        self.sink.record({"event": "serving_summary", **self.last_stats})
        return {r.rid: list(r.out_tokens) for r in all_reqs}

    def _retry_failed(self, all_reqs, step_i, max_steps, policy) -> None:
        """Request-level retry of FAILED-transient requests under a
        ``RetryPolicy``. Only the policy's pacing knobs (attempts,
        backoff, wall-clock ``deadline``) apply — the trigger is always
        :class:`TransientRequestFailure`, so callers need not (and must
        not) tune ``retry_on`` for this internal loop. A retry pass
        that blows the step budget (``max_steps``) is abandoned: the
        stranded requests are finalized ``FAILED`` instead of escaping
        ``generate()`` mid-lifecycle."""
        import dataclasses as _dc

        from ..resilience.retry import retry_call

        def transient_failed():
            return [r for r in all_reqs
                    if r.status is RequestStatus.FAILED
                    and (r.failure or {}).get("transient")]

        if not transient_failed():
            return

        def attempt():
            retryable = transient_failed()
            for r in retryable:
                r.status = RequestStatus.PENDING
                r.end_reason = None
                r.retries += 1
                r.arrival_step = 0
            self._drain(list(retryable), step_i, max_steps)
            still = transient_failed()
            if still:
                raise TransientRequestFailure(still)

        eff = _dc.replace(policy, retry_on=(TransientRequestFailure,),
                          message_filter=None)
        try:
            retry_call(attempt, policy=eff, tag="serving request retry",
                       sink=self.sink)
        except TransientRequestFailure:
            pass  # policy exhausted: they stay FAILED, summary shows it
        except SchedulerError as e:
            now = self._clock()
            for r in all_reqs:
                if not is_terminal(r.status):
                    self._abort_in_flight(r, now)
            self.sink.record({"event": "retry_abandoned",
                              "error": str(e)})

    def _abort_in_flight(self, req: Request, now: float,
                         reason: str = "retry_abandoned") -> None:
        """Pull a non-terminal request out of the queue/its slot (pages
        freed) and finalize it FAILED — the abandonment path when a
        retry pass cannot continue."""
        sched = self.scheduler
        if not sched.remove_waiting(req):
            for i, run in sched.running():
                if run.req is req:
                    sched.evict(i)
                    break
        self._finalize(req, RequestStatus.FAILED, reason, now=now)

    def _summarize(self, reqs, wall_s) -> Dict[str, Any]:
        a = self._accum
        # bucket by terminal state: percentiles below are computed over
        # COMPLETED requests only — a timed-out or failed request's
        # stamps must not contaminate the latency distribution
        completed = [r for r in reqs
                     if r.status is RequestStatus.COMPLETED]
        by_status = {
            s.value: sum(r.status is s for r in reqs)
            for s in (RequestStatus.COMPLETED, RequestStatus.REJECTED,
                      RequestStatus.TIMED_OUT, RequestStatus.FAILED,
                      RequestStatus.CANCELLED)}
        total_tokens = sum(len(r.out_tokens) for r in reqs)
        lat_ms = [(r.t_done - r.t_arrival) * 1e3 for r in completed
                  if r.t_done is not None and r.t_arrival is not None]
        ttft_ms = [(r.t_first_token - r.t_arrival) * 1e3
                   for r in completed
                   if r.t_first_token is not None
                   and r.t_arrival is not None]
        slot_steps = a["active_slot_steps"]
        slo = [r for r in completed if self._within_budget(r)]
        goodput_tokens = sum(len(r.out_tokens) for r in slo)
        return {
            "n_requests": len(reqs),
            "completed": len(completed),
            "by_status": by_status,
            "preemptions": sum(r.preemptions for r in reqs),
            "retries": sum(r.retries for r in reqs),
            "steps": a["steps"],
            "wall_s": round(wall_s, 4),
            "generated_tokens": total_tokens,
            "tokens_per_sec": round(total_tokens / wall_s, 2)
            if wall_s > 0 else None,
            # SLO view: requests that completed within their own
            # budgets (no-budget requests count as attained), over ALL
            # submitted requests — rejected/shed/timed-out work counts
            # against attainment, that is the point of measuring it
            "slo_attained": len(slo),
            "slo_attainment": round(len(slo) / len(reqs), 4)
            if reqs else None,
            "goodput_tokens": goodput_tokens,
            "goodput_tokens_per_sec": round(goodput_tokens / wall_s, 2)
            if wall_s > 0 else None,
            # mean batch occupancy — the serving analogue of the
            # pipeline bubble fraction: idle slot-steps are the bubble
            "occupancy": round(
                slot_steps / (a["steps"] * self.n_slots), 4)
            if a["steps"] else None,
            "max_queue_depth": a["max_queue_depth"],
            "latency_ms": telemetry.percentiles(lat_ms),
            "ttft_ms": telemetry.percentiles(ttft_ms),
            "step_ms": telemetry.percentiles(a["step_times_ms"]),
            "prefill_slot_steps": a["prefill_slot_steps"],
            "decode_slot_steps": a["decode_slot_steps"],
            # token-granular split: a chunked prefill slot-step ingests
            # up to `prefill_chunk` tokens, so slot-steps alone no
            # longer measure prefill work — occupancy and the router's
            # steps-to-first-token estimate use these instead
            "prefill_tokens": a["prefill_tokens"],
            "decode_tokens": a["decode_tokens"],
            "cached_prompt_tokens": a["cached_prompt_tokens"],
            # speculative decoding: drafts offered vs accepted, and the
            # headline decode tokens-per-slot-step (> 1 iff speculation
            # is accepting — the sub-one-pass-per-token measure; the
            # admission/router cost model deliberately IGNORES this and
            # keeps billing one token per slot-step, so speculation can
            # only improve feasibility, never overcommit the pool)
            "spec_k": self.spec_k,
            "drafted_tokens": a["drafted_tokens"],
            "accepted_tokens": a["accepted_tokens"],
            "accept_rate": round(
                a["accepted_tokens"] / a["drafted_tokens"], 4)
            if a["drafted_tokens"] else None,
            "tokens_per_step": round(
                a["decode_tokens"] / a["decode_slot_steps"], 4)
            if a["decode_slot_steps"] else None,
            "prefill_chunk": self.prefill_chunk,
            "prefix_cache": self.prefix_cache_run_stats(),
            "prefill_step_time_s": round(a["prefill_step_time_s"], 4),
            "decode_step_time_s": round(a["decode_step_time_s"], 4),
            # tensor-parallel geometry: per-shard pool footprint is the
            # capacity-planning number (each chip holds heads/tp of
            # every page), psum counts are the collective budget the
            # jaxpr pin enforces (2 sublayer tails + 1 sampler psum)
            "tp": self.tp,
            "kv_bytes_per_shard": self.spec_local.cache_bytes(),
            "psum_per_program": self.program_psum_counts(),
            # full static comm report ({program: {collective: {count,
            # bytes, axes}}}) — what the comm-volume tests pin
            "comm_volume": self.program_comm_volume(),
            # latency attribution (telemetry.spans): per-term TTFT/e2e
            # percentiles, the sum-vs-measured identity's max relative
            # error, and the dominant-cause tally over SLO violators;
            # None with tracing off
            "attribution": telemetry.attribution_summary(
                reqs, violators=[r for r in reqs
                                 if not self._within_budget(r)]),
        }

    def prefix_cache_run_stats(self) -> Optional[Dict[str, Any]]:
        """THIS run's prefix-cache deltas (hits/misses/hit_tokens/
        insertions/evictions since :meth:`begin_run`) + the live entry
        count and a request-level hit rate; None when the cache is
        disabled. The fleet's per-replica summary folds this."""
        cache = self.prefix_cache
        if cache is None:
            return None
        base = self._accum.get("cache_base") or {}
        cur = cache.stats()
        out = {k: cur[k] - base.get(k, 0)
               for k in ("hits", "misses", "hit_tokens", "insertions",
                         "evictions")}
        out["entries"] = cur["entries"]
        looked = out["hits"] + out["misses"]
        out["hit_rate"] = round(out["hits"] / looked, 4) if looked else None
        out["cached_prompt_tokens"] = self._accum["cached_prompt_tokens"]
        return out

    @staticmethod
    def _within_budget(req: Request) -> bool:
        if req.t_arrival is None:
            return True
        if (req.latency_budget_ms is not None and req.t_done is not None
                and (req.t_done - req.t_arrival) * 1e3
                > req.latency_budget_ms):
            return False
        if (req.ttft_budget_ms is not None
                and req.t_first_token is not None
                and (req.t_first_token - req.t_arrival) * 1e3
                > req.ttft_budget_ms):
            return False
        return True

    # -- weight swap -------------------------------------------------------
    def swap_params(self, params: Pytree) -> None:
        """Replace the serving weights in place (through the same
        one-shot inference cast the ctor runs) AND flush the prefix
        cache: cached K/V was computed under the OLD weights, so a
        stale entry surviving a hot swap would serve old-model prefixes
        under the new model — the fleet's ``try_join`` weight swap goes
        through here, which is what makes that impossible."""
        if self._mesh is not None:
            # lay the fresh weights down sharded BEFORE the cast (the
            # cast preserves per-leaf shardings) — same order as the
            # ctor, so a swap never round-trips slices through one chip
            params = jax.device_put(params,
                                    self._tp_param_shardings(params))
        self.params = cast_params_for_inference(params,
                                                self.cfg.compute_dtype)
        if self.prefix_cache is not None:
            flushed = self.prefix_cache.flush()
            self.sink.record({"event": "prefix_cache_flush",
                              "entries": flushed})

    # -- recovery ----------------------------------------------------------
    @classmethod
    def rebuild_like(cls, old: "ServingEngine",
                     params: Optional[Pytree] = None) -> "ServingEngine":
        """A fresh engine with ``old``'s config/weights/geometry/
        policies (the captured ctor kwargs) and NO request recovery —
        the replica-restart primitive (``ReplicaFleet.restart_replica``
        uses it after migration already pulled the dead engine's
        requests; see :meth:`recover_from` when the requests should
        come along)."""
        return cls(old.cfg, params if params is not None else old.params,
                   **old._ctor_kw)

    @classmethod
    def recover_from(cls, dead: "ServingEngine", **overrides
                     ) -> Tuple["ServingEngine", List[Request]]:
        """Restart-with-replay: build a fresh engine with the dead
        engine's config/weights/policies and pull its non-terminal
        requests out for re-submission — in-flight work rides the
        existing recompute-preemption replay path (generated tokens
        fold into the replay prompt), so survivors complete
        token-identically to an uninterrupted run.

        Returns ``(engine, survivors)``; drive them with
        ``engine.generate(survivors)``. ``overrides`` patch ctor kwargs
        (e.g. ``chaos=None`` to disarm a fault injector).
        """
        kw = dict(dead._ctor_kw)
        kw.update(overrides)
        survivors = recover_requests(dead)
        eng = cls(dead.cfg, dead.params, **kw)
        eng.sink.record({
            "event": "engine_recovery",
            "recovered": len(survivors),
            "rids": [r.rid for r in survivors],
            "dead_steps_run": dead.steps_run,
        })
        if dead.tracer is not None:
            # the dead engine's flight ring, replayed into the fresh
            # engine's sink: the crash's last-moments black box
            dead.tracer.dump_blackbox(
                reason="engine_recovery", sink=eng.sink,
                recovered=len(survivors), dead_steps_run=dead.steps_run)
        return eng, survivors


def _copy_pool_pages(kv: KVCacheState, src: jax.Array,
                     dst: jax.Array) -> KVCacheState:
    """COW device half: copy pool pages ``src[i] -> dst[i]`` across
    every layer's K and V (jitted with the cache donated — an in-place
    scatter). Padding entries are ``0 -> 0``: the garbage page copied
    onto itself."""
    pages = kv.pages
    pages = pages.at[:, :, dst].set(pages[:, :, src])
    return KVCacheState(pages=pages)


def _mutate_slots(slots: SlotState, mask: jax.Array,
                  new: SlotState) -> SlotState:
    """Masked row replacement (jitted with the old state donated)."""
    def sel(old, nw):
        m = mask.reshape(mask.shape + (1,) * (old.ndim - 1))
        return jnp.where(m, nw, old)

    return jax.tree_util.tree_map(sel, slots, new)
