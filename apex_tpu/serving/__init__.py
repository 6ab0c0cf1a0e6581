"""apex_tpu.serving: single-chip paged-KV inference with continuous
batching.

The "millions of users" half of the north star, assembled from the
training stack's own machinery:

- :mod:`~apex_tpu.serving.kv_cache` — the paged KV cache:
  :class:`PagedKVSpec` lays the page pools out as chunk-aligned packed
  buffers on ``multi_tensor_apply.packing.PackSpec`` (one page = one
  chunk; ``analysis.check_pack_spec`` verifies it), the host-side
  :class:`PageAllocator` (reader refcounts + cache pins + COW fork),
  and :class:`PrefixCache` — the radix/hash prefix index keying pages
  by the hash of the token prefix through them, so shared prompt heads
  skip their prefill (vLLM block reuse x SGLang RadixAttention);
- :mod:`~apex_tpu.serving.decode_model` — token-at-a-time AND
  chunked-prefill GPT forwards against the cache, attention by
  ``ops.flash_decode`` (online-softmax across pages, Pallas
  scalar-prefetch kernel with XLA fallback; the chunk flattens into a
  single-query batch with per-column kv_lens = causal by construction);
- :mod:`~apex_tpu.serving.scheduler` — Orca-style iteration-level
  continuous batching: admit/evict between steps, lazy chunk-aware
  page allocation, prefix-cache acquisition/publication/COW-forking,
  cache-eviction-before-preemption under pool pressure, recompute-mode
  preemption when the pool runs dry;
- :mod:`~apex_tpu.serving.engine` — :class:`ServingEngine`: jitted
  fixed-shape steps interleaving prefill and decode (one token per
  slot-step; up to ``prefill_chunk`` prompt tokens while prefilling),
  KV/slot/metrics state donated, sampled tokens fed back on device,
  telemetry through the PR-2 cond-gated drain, and the PR-4 auditor as
  the invariant gate (``engine.audit()`` — both programs);
- :mod:`~apex_tpu.serving.robustness` — serving under fire: the typed
  request lifecycle (``RequestStatus``), per-request TTFT/latency
  deadlines, one :class:`RejectionReason` taxonomy for every refusal,
  watermark admission control + :class:`DegradationPolicy` shedding,
  in-jit non-finite quarantine, and restart-with-replay recovery
  (``ServingEngine.recover_from``) — chaos-proven by
  ``resilience.ServingChaos``;
- :mod:`~apex_tpu.serving.fleet` — :class:`ReplicaFleet`: N engines
  behind a deadline-aware router (feasibility x load over each
  replica's EWMA step-time cost model), drain/join rolling weight
  swaps with zero dropped requests, and replica-kill migration riding
  the replay carrier (requests-lost = 0, token-identical survivors);
- :mod:`~apex_tpu.serving.proc_fleet` /
  :mod:`~apex_tpu.serving.worker` /
  :mod:`~apex_tpu.serving.transport` — the REAL-process fleet (opt-in;
  the in-process fleet above stays the default): one ``ServingEngine``
  per supervised worker subprocess, crash-safe length-prefixed framing
  with torn-frame accounting, heartbeat liveness, SIGKILL + restart +
  zero-loss migration under :class:`FleetSupervisor`.

``tools/serving_check.py --self`` is the CI smoke; ``docs/serving.md``
the design document. No benchmark cell serves yet: nothing here is
measured on the chip (``PERF.md`` §7 row 1).
"""
from .engine import (  # noqa: F401
    NO_TOKEN,
    POISONED,
    ServingEngine,
    SlotState,
    default_page_size,
)
from .decode_model import (  # noqa: F401
    chunk_hidden,
    decode_tokens,
    prefill_chunk_tokens,
    reference_decode,
    reference_sample_decode,
)
from .sampling import (  # noqa: F401
    GREEDY,
    SamplingParams,
    sample_tokens,
)
from .spec_decode import (  # noqa: F401
    ngram_propose,
    run_spec_step,
)
from .fleet import (  # noqa: F401
    Replica,
    ReplicaFleet,
    ReplicaState,
)
from .proc_fleet import (  # noqa: F401
    FleetSupervisor,
)
from .transport import (  # noqa: F401
    Channel,
    FrameReader,
    TransportError,
    WorkerUnavailable,
    read_frames,
    request_from_wire,
    request_to_wire,
    write_frame,
)
from .kv_cache import (  # noqa: F401
    KVCacheState,
    PageAllocator,
    PagedKVSpec,
    PrefixCache,
    page_table_row,
    write_chunk_kv,
    write_token_kv,
)
from .robustness import (  # noqa: F401
    AdmissionConfig,
    AdmissionController,
    DegradationPolicy,
    RejectionCode,
    RejectionError,
    RejectionReason,
    RequestStatus,
    TERMINAL_STATES,
    TransientRequestFailure,
    VirtualClock,
    is_terminal,
    recover_requests,
)
from .scheduler import (  # noqa: F401
    Request,
    RunningSlot,
    Scheduler,
    SchedulerError,
)

__all__ = [
    "AdmissionConfig",
    "AdmissionController",
    "Channel",
    "DegradationPolicy",
    "FleetSupervisor",
    "FrameReader",
    "GREEDY",
    "SamplingParams",
    "KVCacheState",
    "NO_TOKEN",
    "POISONED",
    "PageAllocator",
    "PagedKVSpec",
    "PrefixCache",
    "RejectionCode",
    "RejectionError",
    "RejectionReason",
    "Replica",
    "ReplicaFleet",
    "ReplicaState",
    "Request",
    "RequestStatus",
    "RunningSlot",
    "Scheduler",
    "SchedulerError",
    "ServingEngine",
    "SlotState",
    "TERMINAL_STATES",
    "TransientRequestFailure",
    "TransportError",
    "VirtualClock",
    "WorkerUnavailable",
    "chunk_hidden",
    "decode_tokens",
    "default_page_size",
    "is_terminal",
    "ngram_propose",
    "page_table_row",
    "prefill_chunk_tokens",
    "read_frames",
    "recover_requests",
    "reference_decode",
    "reference_sample_decode",
    "request_from_wire",
    "request_to_wire",
    "run_spec_step",
    "sample_tokens",
    "write_chunk_kv",
    "write_frame",
    "write_token_kv",
]
