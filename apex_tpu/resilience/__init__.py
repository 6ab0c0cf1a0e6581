"""apex_tpu.resilience: keep training through the failures the monitors see.

PRs 2–4 built the observability half of production training (in-jit
telemetry, numerics provenance, static step audits); this package is the
response half — the run must *survive* what they detect:

- :mod:`~apex_tpu.resilience.manager` — preemption-safe
  :class:`CheckpointManager`: atomic step directories (tmp + rename),
  ``keep_n`` retention + GC, async saves barriered at the next save,
  corrupted-checkpoint fallback on restore, SIGTERM emergency flush;
- :mod:`~apex_tpu.resilience.state` — :class:`TrainState`
  capture/restore (params, packed or pytree optimizer state, scaler,
  RNG, data-iterator position, telemetry counters) and the
  :func:`resume_or_init` one-liner; resumed runs continue the loss
  curve bit-exactly on CPU/interpret;
- :mod:`~apex_tpu.resilience.rewind` — :class:`RewindController`: a
  host ring of the last K good states, triggered by the PR-3 anomaly
  engine (``scaler_stall`` / ``scale_collapse``) or the scaler's
  consecutive-skip counter; rewinds past poisoned data windows;
- :mod:`~apex_tpu.resilience.watchdog` — :class:`HangWatchdog`: bounded
  blocking points with all-thread stack dumps instead of silent pod
  deadlocks;
- :mod:`~apex_tpu.resilience.retry` — the jittered-backoff
  :class:`RetryPolicy` used by checkpoint IO and the serving
  transport;
- :mod:`~apex_tpu.resilience.chaos` — fault injection (NaN gradients,
  failed/truncated checkpoint writes, fake preemption, stalled
  callbacks, SIGKILLed fake hosts) driving the tests and
  ``tools/resilience_check.py --self``;
- :mod:`~apex_tpu.resilience.elastic` — the ELASTIC SERVICE: a
  :class:`Supervisor` running the train loop as N fake-host
  subprocesses with death/hang detection and world restart, the
  two-phase multi-host checkpoint commit
  (:class:`ElasticCheckpointManager` — per-host ``shard-<h>.part``
  staging, filesystem rendezvous, rank-0 ``COMMIT`` promotion,
  markerless steps are garbage), and topology-elastic resume
  (:func:`reflatten_flat` re-slices the packed opt state bit-exactly
  onto a different world size). CLI: ``tools/elastic_supervisor.py``.

See ``docs/resilience.md`` for the end-to-end story.
"""
from .chaos import (  # noqa: F401
    ChaosError,
    ChaosHost,
    ChaosMonkey,
    ServingChaos,
    StallingSink,
    WorkerChaos,
    corrupt_checkpoint,
    poison_grads,
    request_storm,
    send_preemption,
)
from .elastic import (  # noqa: F401
    COMMIT_MARKER,
    ElasticCheckpointManager,
    Heartbeat,
    Supervisor,
    WorldFailedError,
    grad_buckets_for_world,
    pack_spec_for_world,
    reflatten_flat,
    sharded_leaf_indices,
    world_chunk_size,
)
from .liveness import (  # noqa: F401
    live_beat,
    read_json_tolerant,
    sweep_stale,
    writer_alive,
)
from .manager import (  # noqa: F401
    CHECKPOINT_IO_POLICY,
    CheckpointManager,
    PreemptionError,
)
from .retry import (  # noqa: F401
    ELASTIC_BARRIER_POLICY,
    TRANSPORT_POLICY,
    BarrierNotReady,
    RetryPolicy,
    retry_call,
)
from .rewind import (  # noqa: F401
    RewindController,
    RewindExhaustedError,
)
from .state import (  # noqa: F401
    IndexedBatches,
    ResumableIterator,
    TrainState,
    capture,
    host_snapshot,
    resume_or_init,
)
from .watchdog import (  # noqa: F401
    HangError,
    HangWatchdog,
    dump_all_stacks,
)

__all__ = [
    "CHECKPOINT_IO_POLICY", "CheckpointManager", "PreemptionError",
    "ELASTIC_BARRIER_POLICY", "TRANSPORT_POLICY",
    "BarrierNotReady", "RetryPolicy", "retry_call",
    "RewindController", "RewindExhaustedError",
    "IndexedBatches", "ResumableIterator", "TrainState", "capture",
    "host_snapshot", "resume_or_init",
    "HangError", "HangWatchdog", "dump_all_stacks",
    "ChaosError", "ChaosHost", "ChaosMonkey", "ServingChaos",
    "StallingSink", "WorkerChaos", "corrupt_checkpoint", "poison_grads",
    "request_storm", "send_preemption",
    "COMMIT_MARKER", "ElasticCheckpointManager", "Heartbeat",
    "Supervisor", "WorldFailedError", "grad_buckets_for_world",
    "pack_spec_for_world", "reflatten_flat", "sharded_leaf_indices",
    "world_chunk_size",
    "live_beat", "read_json_tolerant", "sweep_stale", "writer_alive",
]
